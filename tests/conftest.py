"""Test configuration: hermetic 8-device CPU mesh.

The reference only tests multi-device behavior on real clusters
(SURVEY.md §4 "what's missing"); we instead run every DP/TP/EP test on a
virtual 8-device CPU platform via XLA's host-device emulation.
"""

import os

# tests run hermetically on the virtual CPU mesh whatever the caller's
# environment names: nothing imports jax before this file, so the
# environment variable alone selects the platform
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# XLA:CPU sizes its thread pools from NPROC where it is set, else from the
# host's cores: 8 threads here for 8 devices whose shares of a program
# BLOCK a thread each while they wait for one another. On a crowded box
# (six xdist workers) a bucketed ``fit`` then stood still with 5 of an
# all-reduce's 8 participants arrived and every thread asleep, and after
# 40 s XLA aborted the worker ("Termination timeout ... only 5 of them
# arrived"): ROADMAP D1. Room for four programs in flight
os.environ.setdefault("NPROC", "32")

import jax  # noqa: E402  (import after env setup)

assert len(jax.devices()) == 8, jax.devices()
