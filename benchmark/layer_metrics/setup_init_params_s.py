"""Seconds inside ``init_params`` (registry ``setup.init_params_s``, the
span ``compile.init_params``): one ``jit`` a weight that is drawn, traced,
lowered, compiled (or read from the cache) and run; a declared weight
costs nothing here. A part of ``setup_lower_s``. None where the program
keeps no such sum. Layer: Builder API, compile."""


def read(run):
    return run["facts"]["jax"].get("init_params_s")
