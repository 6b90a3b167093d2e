"""Compile requests the persistent cache did not hold (registry
``jax.cache_misses``): 0 in any run after a checkout's first. Layer:
Builder API, compile."""


def read(run):
    return run["facts"]["jax"]["cache_misses"]
