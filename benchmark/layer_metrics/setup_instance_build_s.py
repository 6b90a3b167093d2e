"""Seconds constructing ``GenerationInstance`` (registry
``setup.instance_build_s``, the span ``serving.build``): the scheduler,
the paged decoder and its pool, the decoder's audit,
``setup_calibration_s``. None where the program keeps no such sum.
Layer: Paged decoder."""


def read(run):
    return run["facts"]["jax"].get("instance_build_s")
