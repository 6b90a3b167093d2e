"""Seconds inside ``FFModel.compile``, every call of the process summed
(registry ``setup.model_compile_s``, the span ``compile``): validation,
search, ``setup_lower_s``, ``setup_audit_s``, the ledger's record. None
where the program keeps no such sum. Layer: Builder API, compile."""


def read(run):
    return run["facts"]["jax"].get("model_compile_s")
