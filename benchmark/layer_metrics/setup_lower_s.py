"""Seconds lowering the graph to its compiled model (registry
``setup.lower_s``, the span ``compile.lower`` around ``compile_model``):
the ops made, ``setup_init_params_s``, the optimizer's state, the step
functions wrapped in ``jit``. A part of ``setup_model_compile_s``. None
where the program keeps no such sum. Layer: Builder API, compile."""


def read(run):
    return run["facts"]["jax"].get("lower_s")
