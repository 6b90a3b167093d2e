"""A residual path of ``n`` streams (manifold-constrained hyper-connections,
arXiv:2512.24880, on the hyper-connections of arXiv:2409.19606; no
reference analog).

A token's residual is ``X`` in ``R^{n x d}``, stored as ONE tensor ``(B, S,
n d)``, stream ``i`` the columns ``[i d, (i + 1) d)``, in the activations'
dtype; every sum here is float32 inside. One op type, four ``part``s, so a
graph walks the path as any ops and threads no second value:

* ``"spread"``: ``(B, S, d)`` -> the ``n`` streams, each a copy;
* ``"pre"``: the streams -> the sublayer's input ``u = sum_i pre_i X_i``
  ``(B, S, d)`` and the coefficients ``[post | C]`` ``(B, S, n + n n)``
  float32, from the op's own weights (below);
* ``"post"``: (streams, the sublayer's output ``y``, coefficients) -> the
  streams ``X'_j = post_j y + sum_i C[j, i] X_i``;
* ``"sum"``: the streams -> their sum ``(B, S, d)``.

``pre``'s weights: ``w`` (n d, 2 n + n n), ``scale`` (3,) = ``a_pre,
a_post, a_res``, ``bias`` (2 n + n n). Per token::

    r = rsqrt(mean(vec(X)^2) + norm_eps);  m = (vec(X) w) r
    pre  = sigmoid(a_pre m[:n] + b[:n]) + eps
    post = 2 sigmoid(a_post m[n:2n] + b[n:2n])
    C0   = softmax_rows(reshape(a_res m[2n:] + b[2n:], (n, n))) + eps
    C    = ``iters`` rounds of (each column over its sum + eps, then each
           row over its sum + eps): nearly doubly stochastic

A path of one stream is the plain residual a builder writes with ``add``;
the op refuses ``n < 2``.
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

from ..core.op import Op, WeightSpec, register_op, sub_scope
from ..ffconst import DataType, OpType
from ..runtime.initializer import (ConstantInitializer,
                                   DefaultWeightInitializer, ZeroInitializer)

PARTS = ("spread", "pre", "post", "sum")


def sinkhorn(c, iters: int, eps: float):
    """``c`` (..., n, n) positive -> ``iters`` rounds of columns, then
    rows, each over its sum + ``eps``."""
    for _ in range(iters):
        c = c / (c.sum(-2, keepdims=True) + eps)
        c = c / (c.sum(-1, keepdims=True) + eps)
    return c


@register_op
class StreamMix(Op):
    """One ``part`` of the module's docstring."""

    op_type = OpType.STREAM_MIX

    def __init__(self, layer, input_shapes):
        super().__init__(layer, input_shapes)
        a = self.attrs
        self.part = a["part"]
        if self.part not in PARTS:
            raise ValueError(f"{self.name}: part {self.part!r} not in {PARTS}")
        self.n = int(a["streams"])
        if self.n < 2:
            raise ValueError(f"{self.name}: a residual path of {self.n} "
                             f"stream is the plain residual; build it with "
                             f"add")
        wide = input_shapes[0].sizes[-1]
        self.dim = wide if self.part == "spread" else wide // self.n
        if self.part != "spread" and self.dim * self.n != wide:
            raise ValueError(f"{self.name}: {wide} columns are not "
                             f"{self.n} streams")
        self.iters = int(a.get("sinkhorn_iters", 20))
        self.eps = float(a.get("eps", 1e-6))
        self.norm_eps = float(a.get("norm_eps", 1e-6))
        self.coefs = self.n + self.n * self.n

    def infer_output_shapes(self):
        lead = tuple(self.input_shapes[0].sizes[:-1])
        dt = self.input_shapes[0].dtype
        one, all_ = (lead + (self.dim,), dt), (lead + (self.n * self.dim,), dt)
        if self.part == "pre":
            return [one, (lead + (self.coefs,), DataType.FLOAT)]
        return [all_ if self.part in ("spread", "post") else one]

    def weight_specs(self) -> List[WeightSpec]:
        if self.part != "pre":
            return []
        dt = self.input_shapes[0].dtype
        init = self.attrs.get("kernel_initializer") or DefaultWeightInitializer()
        n = self.n
        return [
            WeightSpec("w", (n * self.dim, 2 * n + n * n), dt, init),
            WeightSpec("scale", (3,), dt,
                       self.attrs.get("scale_initializer")
                       or ConstantInitializer(1.0), weight_decay=False),
            WeightSpec("bias", (2 * n + n * n,), dt,
                       self.attrs.get("bias_initializer")
                       or ZeroInitializer(), weight_decay=False),
        ]

    def _streams(self, x):
        return x.reshape(x.shape[:-1] + (self.n, self.dim))

    @sub_scope("mix")
    def pre(self, weights, x):
        f32 = jnp.float32
        n = self.n
        sq = jnp.mean(jnp.square(x.astype(f32)), -1, keepdims=True)
        m = jnp.dot(x, weights["w"], preferred_element_type=f32) \
            * jax.lax.rsqrt(sq + self.norm_eps)
        a = weights["scale"].astype(f32)
        b = weights["bias"].astype(f32)
        pre = jax.nn.sigmoid(a[0] * m[..., :n] + b[:n]) + self.eps
        post = 2.0 * jax.nn.sigmoid(a[1] * m[..., n:2 * n] + b[n:2 * n])
        c0 = jax.nn.softmax((a[2] * m[..., 2 * n:] + b[2 * n:]).reshape(
            m.shape[:-1] + (n, n)), axis=-1) + self.eps
        c = sinkhorn(c0, self.iters, self.eps)
        u = jnp.einsum("...i,...id->...d", pre, self._streams(x).astype(f32))
        return [u.astype(x.dtype),
                jnp.concatenate([post, c.reshape(m.shape[:-1] + (n * n,))],
                                axis=-1)]

    @sub_scope("mix")
    def post(self, x, y, coef):
        f32 = jnp.float32
        n = self.n
        post = coef[..., :n]
        c = coef[..., n:].reshape(coef.shape[:-1] + (n, n))
        out = (jnp.einsum("...ji,...id->...jd", c,
                          self._streams(x).astype(f32))
               + post[..., None] * y.astype(f32)[..., None, :])
        return [out.reshape(x.shape).astype(x.dtype)]

    def forward(self, ctx, inputs, weights):
        x = inputs[0]
        if self.part == "pre":
            return self.pre(weights, x)
        if self.part == "post":
            return self.post(*inputs)
        with sub_scope("mix"):
            if self.part == "spread":
                return [jnp.tile(x, (1,) * (x.ndim - 1) + (self.n,))]
            return [self._streams(x).astype(jnp.float32).sum(-2).astype(
                x.dtype)]

    def flops(self) -> float:
        tokens = 1
        for s in self.input_shapes[0].sizes[:-1]:
            tokens *= s
        n, d = self.n, self.dim
        if self.part == "pre":
            return 2.0 * tokens * n * d * (2 * n + n * n + 2)
        if self.part == "post":
            return 2.0 * tokens * n * d * (n + 1)
        return float(tokens * n * d)
