"""Seconds this process spent tracing functions to jaxprs: the sum of
JAX's ``jaxpr_trace_duration`` events (registry ``jax.trace_s``,
``utils/compile_cache.py``). Inclusive, as JAX reports them: a ``jit``
traced inside another's trace is in both, and so is whatever ran eagerly
while a function was traced. The process's, window included; the window
traces nothing new (``cache_misses_warm``), so set-up's to under a
second. None where the program keeps no such sum. Layer: Builder API,
compile."""


def read(run):
    return run["facts"]["jax"].get("trace_s")
