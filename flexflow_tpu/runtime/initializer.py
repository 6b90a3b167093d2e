"""Weight initializers.

TPU-native equivalent of the reference's initializer tasks
(reference: include/flexflow/initializer.h, src/runtime/initializer.cc,
initializer_kernel.cu — Glorot/Zero/Constant/Uniform/Normal as Legion GPU
tasks using curand). Here each initializer is a pure function of a PRNG key
and shape, executed on-device by XLA at compile's parameter-init step; the
per-device curand plumbing is unnecessary because jax.random is splittable
and deterministic across shardings.
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp


class Initializer:
    def __call__(self, key: jax.Array, shape: Tuple[int, ...], dtype) -> jax.Array:
        raise NotImplementedError


class DeclaredInitializer(Initializer):
    """A weight that is declared and not drawn: ``init_params`` leaves a
    ``jax.ShapeDtypeStruct`` (shape, dtype, sharding) in the parameter
    tree where the array would be, for a graph whose weights are loaded
    from elsewhere before anything runs. A model of billions of
    parameters then never holds a random copy beside the loaded one.
    Running the graph before its weights are in place fails at the first
    op that reads one."""

    def __call__(self, key, shape, dtype):
        raise RuntimeError("a declared weight was asked for its values: "
                           "load the graph's parameters first")


class GlorotUniformInitializer(Initializer):
    """reference: initializer.h GlorotUniform; matches fan computation of
    initializer_kernel.cu (fan_in/fan_out over first two dims, receptive
    field = trailing dims)."""

    def __init__(self, seed: int = 0):
        self.seed = seed

    def __call__(self, key, shape, dtype):
        if len(shape) < 2:
            fan_in = fan_out = shape[0] if shape else 1
        else:
            receptive = 1
            for s in shape[:-2]:
                receptive *= s
            fan_in = shape[-2] * receptive
            fan_out = shape[-1] * receptive
        limit = math.sqrt(6.0 / (fan_in + fan_out))
        return jax.random.uniform(key, shape, dtype, minval=-limit, maxval=limit)


class ZeroInitializer(Initializer):
    """reference: initializer.h ZeroInitializer."""

    def __call__(self, key, shape, dtype):
        return jnp.zeros(shape, dtype)


class ConstantInitializer(Initializer):
    """reference: initializer.h ConstantInitializer."""

    def __init__(self, value: float):
        self.value = value

    def __call__(self, key, shape, dtype):
        return jnp.full(shape, self.value, dtype)


class UniformInitializer(Initializer):
    """reference: initializer.h UniformInitializer."""

    def __init__(self, seed: int = 0, minv: float = -0.05, maxv: float = 0.05):
        self.seed = seed
        self.minv = minv
        self.maxv = maxv

    def __call__(self, key, shape, dtype):
        return jax.random.uniform(key, shape, dtype, minval=self.minv, maxval=self.maxv)


class NormInitializer(Initializer):
    """reference: initializer.h NormInitializer (gaussian)."""

    def __init__(self, seed: int = 0, mean: float = 0.0, stddev: float = 0.05):
        self.seed = seed
        self.mean = mean
        self.stddev = stddev

    def __call__(self, key, shape, dtype):
        return self.mean + self.stddev * jax.random.normal(key, shape, dtype)


DefaultWeightInitializer = GlorotUniformInitializer
DefaultBiasInitializer = ZeroInitializer
