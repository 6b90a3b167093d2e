"""Linear (dense) operator.

TPU-native equivalent of the reference's Linear op
(reference: src/ops/linear.cc, src/ops/kernels/linear_kernels.cu — cuBLAS
GEMM with fused activation; builder ``FFModel::dense`` model.h:487).

The GEMM lowers to ``jnp.dot_general`` which XLA tiles onto the MXU;
activation fuses into the matmul epilogue automatically. Parameter
parallelism (the reference's replica-dim weight / partition-linear-combine
and replicate-linear-combine substitution patterns,
src/runtime/substitution.cc:77-108) is expressed by sharding the weight's
in- or out-feature dim over the ``model`` mesh axis in :meth:`propagate`.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp

from ..ffconst import ActiMode, DataType, OpType
from ..core.op import LowerCtx, Op, WeightSpec, register_op
from ..core.parallel_tensor import ParallelDim, ParallelTensorShape
from ..runtime.initializer import DefaultBiasInitializer, DefaultWeightInitializer


def apply_activation(x: jnp.ndarray, mode: ActiMode) -> jnp.ndarray:
    if mode is ActiMode.NONE:
        return x
    if mode is ActiMode.RELU:
        return jnp.maximum(x, 0)
    if mode is ActiMode.SIGMOID:
        return jax_sigmoid(x)
    if mode is ActiMode.TANH:
        return jnp.tanh(x)
    if mode is ActiMode.GELU:
        import jax.nn

        return jax.nn.gelu(x, approximate=False)
    if mode is ActiMode.SILU:
        import jax.nn

        return jax.nn.silu(x)
    if mode is ActiMode.RELU2:
        return jnp.square(jnp.maximum(x, 0))
    raise ValueError(mode)


def jax_sigmoid(x):
    import jax.nn

    return jax.nn.sigmoid(x)


@register_op
class Linear(Op):
    op_type = OpType.LINEAR

    def __init__(self, layer, input_shapes):
        super().__init__(layer, input_shapes)
        self.out_dim: int = layer.attrs["out_dim"]
        self.activation: ActiMode = layer.attrs.get("activation", ActiMode.NONE)
        self.use_bias: bool = layer.attrs.get("use_bias", True)
        self.in_dim: int = input_shapes[0].sizes[-1]
        # a head tied to an embedding: no kernel of its own, it multiplies
        # by the transposed (out_dim, in_dim) table of the op it names
        self.tied_to: Optional[str] = layer.attrs.get("tied_to")
        if self.tied_to:
            self.borrows = {"tied": (self.tied_to, "weight")}

    def infer_output_shapes(self):
        sizes = self.input_shapes[0].sizes[:-1] + (self.out_dim,)
        return [(sizes, self.input_shapes[0].dtype)]

    def weight_specs(self) -> List[WeightSpec]:
        specs = [] if self.tied_to else [
            WeightSpec(
                "kernel",
                (self.in_dim, self.out_dim),
                self.input_shapes[0].dtype,
                self.attrs.get("kernel_initializer") or DefaultWeightInitializer(),
                weight_decay=True,
            )
        ]
        if self.use_bias:
            specs.append(
                WeightSpec(
                    "bias",
                    (self.out_dim,),
                    self.input_shapes[0].dtype,
                    self.attrs.get("bias_initializer") or DefaultBiasInitializer(),
                    weight_decay=False,
                )
            )
        return specs

    def forward(self, ctx: LowerCtx, inputs: Sequence[jnp.ndarray], weights):
        (x,) = inputs
        if self.tied_to:
            # contracted over the table's second axis where it lies: no
            # transposed copy of it is made
            y = jnp.einsum("...e,ve->...v", x, weights["tied"],
                           preferred_element_type=x.dtype)
        else:
            y = jnp.dot(x, weights["kernel"], preferred_element_type=x.dtype)
        if self.use_bias:
            y = y + weights["bias"]
        return [apply_activation(y, self.activation)]

    def propagate(self, input_shapes, strategy: Dict[str, str]):
        """Parallel-dim mapping.

        strategy keys:
          * ``"out"``: mesh axis to shard the out-feature dim — the
            reference's *replicate-linear-combine* pattern (weight
            out-dim partitioned, input replicated, output partitioned on
            features; substitution.cc:1756-1767).
          * ``"in"``: mesh axis to shard the in-feature (reduction) dim —
            the *partition-linear-combine* pattern: input features
            partitioned, partial sums all-reduced (GSPMD emits the
            reduction from the contracted-dim sharding).
        """
        in0 = input_shapes[0]
        out_sizes = in0.sizes[:-1] + (self.out_dim,)
        out_dims = [
            ParallelDim(s, d.degree, d.axis) if (d := in0.dims[i]).is_partitioned else ParallelDim(s)
            for i, s in enumerate(out_sizes[:-1])
        ]
        kdims = [ParallelDim(self.in_dim), ParallelDim(self.out_dim)]
        out_feat = ParallelDim(self.out_dim)

        out_axis = strategy.get("out")
        in_axis = strategy.get("in")
        # an axis already sharding a batch/seq dim of the output cannot
        # also shard the feature dim (one mesh axis maps to at most one
        # dim per tensor — NamedSharding rejects the layout)
        used = {d.axis for d in out_dims if d.is_partitioned}
        if out_axis and out_axis not in used:
            deg = strategy.get("_axis_sizes", {}).get(out_axis, 1)
            if deg > 1 and self.out_dim % deg == 0:
                kdims[1] = ParallelDim(self.out_dim, deg, out_axis)
                out_feat = ParallelDim(self.out_dim, deg, out_axis)
        if in_axis and in_axis not in {d.axis for d in in0.dims[:-1]
                                       if d.is_partitioned}:
            deg = strategy.get("_axis_sizes", {}).get(in_axis, 1)
            if deg > 1 and self.in_dim % deg == 0:
                kdims[0] = ParallelDim(self.in_dim, deg, in_axis)

        out_shape = ParallelTensorShape(tuple(out_dims + [out_feat]), in0.dtype)
        # (a tied head owns no kernel: the embedding's table is counted,
        # sharded and synchronised once, under the embedding)
        weight_shapes = {} if self.tied_to else {
            "kernel": ParallelTensorShape(tuple(kdims), in0.dtype),
        }
        if self.use_bias:
            weight_shapes["bias"] = ParallelTensorShape((out_feat,), in0.dtype)
        return [out_shape], weight_shapes

    def flops(self) -> float:
        batch = 1
        for s in self.input_shapes[0].sizes[:-1]:
            batch *= s
        return 2.0 * batch * self.in_dim * self.out_dim

    def input_contraction_dims(self):
        return [(0, len(self.input_shapes[0].dims) - 1, "kernel", 0)]


@register_op
class GatedMLP(Op):
    """``(act(x W_gate) * (x W_up)) W_down`` — the gated feed-forward
    block (Shazeer 2020, "GLU variants"), one op with three matrices of
    shapes (in, width), (in, width), (width, in). The products accumulate
    in float32 and the gate is applied there; the output keeps the
    input's dtype."""

    op_type = OpType.GATED_MLP

    def __init__(self, layer, input_shapes):
        super().__init__(layer, input_shapes)
        self.width: int = int(layer.attrs["width"])
        self.activation: ActiMode = layer.attrs.get("activation",
                                                    ActiMode.SILU)
        self.in_dim: int = input_shapes[0].sizes[-1]
        # the clamp before the product (None: none)
        self.limit = (None if layer.attrs.get("limit") is None
                      else float(layer.attrs["limit"]))

    def infer_output_shapes(self):
        return [(self.input_shapes[0].sizes, self.input_shapes[0].dtype)]

    def weight_specs(self) -> List[WeightSpec]:
        dt = self.input_shapes[0].dtype
        init = self.attrs.get("kernel_initializer") or DefaultWeightInitializer()
        return [WeightSpec("gate", (self.in_dim, self.width), dt, init),
                WeightSpec("up", (self.in_dim, self.width), dt, init),
                WeightSpec("down", (self.width, self.in_dim), dt, init)]

    def forward(self, ctx: LowerCtx, inputs, weights):
        (x,) = inputs
        return [gated_mlp(x, weights["gate"], weights["up"], weights["down"],
                          self.activation, self.limit)]

    def flops(self) -> float:
        batch = 1
        for s in self.input_shapes[0].sizes[:-1]:
            batch *= s
        return 6.0 * batch * self.in_dim * self.width

    def input_contraction_dims(self):
        last = len(self.input_shapes[0].dims) - 1
        return [(0, last, "gate", 0), (0, last, "up", 0)]


def gated_mlp(x, gate, up, down, activation: ActiMode = ActiMode.SILU,
              limit=None):
    g = jnp.dot(x, gate, preferred_element_type=jnp.float32)
    u = jnp.dot(x, up, preferred_element_type=jnp.float32)
    if limit is not None:
        g, u = jnp.minimum(g, limit), jnp.clip(u, -limit, limit)
    h = (apply_activation(g, activation) * u).astype(x.dtype)
    return jnp.dot(h, down, preferred_element_type=jnp.float32).astype(x.dtype)
