"""LayerNorm and RMSNorm operators.

TPU-native equivalent of the reference's LayerNorm
(reference: src/ops/layer_norm.cc + .cu — custom Welford kernels; builder
model.h:472 with ``axes``/``elementwise_affine``/``eps``). XLA fuses the
mean/variance/normalize chain into one pass, replacing the hand-written
kernels.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ffconst import OpType
from ..core.op import Op, WeightSpec, register_op
from ..runtime.initializer import ConstantInitializer, ZeroInitializer


@register_op
class LayerNorm(Op):
    op_type = OpType.LAYERNORM

    def __init__(self, layer, input_shapes):
        super().__init__(layer, input_shapes)
        nd = len(input_shapes[0].sizes)
        self.axes = tuple(a % nd for a in self.attrs["axes"])
        self.eps = float(self.attrs.get("eps", 1e-5))
        self.affine = bool(self.attrs.get("elementwise_affine", True))
        self.norm_shape = tuple(input_shapes[0].sizes[a] for a in sorted(self.axes))

    def infer_output_shapes(self):
        return [(self.input_shapes[0].sizes, self.input_shapes[0].dtype)]

    def weight_specs(self):
        if not self.affine:
            return []
        dt = self.input_shapes[0].dtype
        return [
            WeightSpec("scale", self.norm_shape, dt, ConstantInitializer(1.0), weight_decay=False),
            WeightSpec("bias", self.norm_shape, dt, ZeroInitializer(), weight_decay=False),
        ]

    def forward(self, ctx, inputs, weights):
        (x,) = inputs
        axes = sorted(self.axes)
        mean = jnp.mean(x, axis=axes, keepdims=True)
        var = jnp.var(x, axis=axes, keepdims=True)
        y = (x - mean) * jax.lax.rsqrt(var + self.eps)
        if self.affine:
            # broadcast scale/bias over the normalized axes
            shape = [1] * x.ndim
            for a in axes:
                shape[a] = x.shape[a]
            y = y * weights["scale"].reshape(shape) + weights["bias"].reshape(shape)
        return [y]


@register_op
class RMSNorm(Op):
    """Root-mean-square norm over the last axis with a learned gain:
    ``x / sqrt(mean(x^2) + eps) * scale`` (Zhang & Sennrich 2019; no
    reference analog). The statistics are taken in float32 whatever the
    activations' dtype; the output keeps the input's."""

    op_type = OpType.RMS_NORM

    def __init__(self, layer, input_shapes):
        super().__init__(layer, input_shapes)
        self.eps = float(self.attrs.get("eps", 1e-6))

    def infer_output_shapes(self):
        return [(self.input_shapes[0].sizes, self.input_shapes[0].dtype)]

    def weight_specs(self):
        return [WeightSpec(
            "scale", (self.input_shapes[0].sizes[-1],),
            self.input_shapes[0].dtype,
            self.attrs.get("kernel_initializer") or ConstantInitializer(1.0),
            weight_decay=False)]

    def forward(self, ctx, inputs, weights):
        (x,) = inputs
        return [rms_norm(x, weights["scale"], self.eps)]

    def flops(self) -> float:
        n = 1
        for s in self.input_shapes[0].sizes:
            n *= s
        return 4.0 * n


@register_op
class ScaleShift(Op):
    """``scale * x + shift``, two learned vectors over the last axis (a
    learned residual scaling; no reference analog), in float32 whatever
    the activations' dtype; the output keeps the input's."""

    op_type = OpType.SCALE_SHIFT

    def infer_output_shapes(self):
        return [(self.input_shapes[0].sizes, self.input_shapes[0].dtype)]

    def weight_specs(self):
        n, dt = self.input_shapes[0].sizes[-1], self.input_shapes[0].dtype
        return [WeightSpec("scale", (n,), dt,
                           self.attrs.get("kernel_initializer")
                           or ConstantInitializer(1.0), weight_decay=False),
                WeightSpec("shift", (n,), dt,
                           self.attrs.get("bias_initializer")
                           or ZeroInitializer(), weight_decay=False)]

    def forward(self, ctx, inputs, weights):
        (x,) = inputs
        f32 = jnp.float32
        return [(x.astype(f32) * weights["scale"].astype(f32)
                 + weights["shift"].astype(f32)).astype(x.dtype)]

    def flops(self) -> float:
        n = 1
        for s in self.input_shapes[0].sizes:
            n *= s
        return 2.0 * n


def rms_norm(x, scale, eps: float):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)
