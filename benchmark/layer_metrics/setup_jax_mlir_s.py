"""Seconds this process spent lowering jaxprs to MLIR modules: the sum
of JAX's ``jaxpr_to_mlir_module_duration`` events (registry
``jax.mlir_s``, ``utils/compile_cache.py``). Inclusive, as JAX reports
them: what is traced or run while a module is built lies inside it. The
process's, window included; the window lowers nothing new
(``cache_misses_warm``), so set-up's to under a second. None where the
program keeps no such sum. Layer: Builder API, compile."""


def read(run):
    return run["facts"]["jax"].get("mlir_s")
