"""Seconds this process spent reading executables back from the
persistent compilation cache, hits only: the sum of JAX's
``cache_retrieval_time_sec`` events (registry ``jax.cache_read_s``,
``utils/compile_cache.py``). A part of ``compile_request_s``, which
holds it; the rest of that one is XLA compiling. The process's, window
included. None where the program keeps no such sum. Layer: Builder API,
compile."""


def read(run):
    return run["facts"]["jax"].get("cache_read_s")
