"""Seconds of the program audit inside ``FFModel.compile`` (registry
``setup.audit_s``, the span ``compile.audit``; the model's
``audit_profile["wall_time_s"]``, summed over the process's compiles):
each step program traced ahead of its first dispatch, its jaxpr walked.
A part of ``setup_model_compile_s``. None where the program keeps no
such sum. Layer: Builder API, compile."""


def read(run):
    return run["facts"]["jax"].get("audit_s")
