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

import jax  # noqa: E402  (import after env setup)

assert len(jax.devices()) == 8, jax.devices()
