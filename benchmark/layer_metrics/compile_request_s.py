"""Seconds this process spent inside XLA compile requests, persistent-
cache reads included (registry ``jax.compile_s``,
``utils/compile_cache.py``). Layer: Builder API, compile."""


def read(run):
    return run["facts"]["jax"]["compile_s"]
