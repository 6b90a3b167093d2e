"""FusedOp: elementwise-chain fusion.

TPU-native equivalent of the reference's FusedOp
(reference: include/flexflow/ops/fused.h:17-70, ``FFModel::apply_fusion``
model.cc:2495-2603, giant switch dispatch src/ops/fused.cu:67; driven by
``--fusion``).

Design translation: the reference fuses adjacent ops into one Legion task
to cut *launch overhead*. Under jit, XLA already fuses the generated HLO —
launch overhead is gone by construction — so fusion here serves the other
consumers of graph granularity: the strategy search and the simulator see
one node per fused chain (smaller DP state space, one cost probe), exactly
like the reference's search operating post-fusion.

Only straight-line chains of weightless single-input/single-output
elementwise ops fuse (the reference similarly restricts: same MachineView,
no parallel ops — model.cc:2519-2560).
"""

from __future__ import annotations

from typing import Dict, List, Set

from ..ffconst import OpType
from ..core.layer import Layer
from ..core.op import Op, create_op, op_scope, register_op

FUSIBLE = {
    OpType.RELU, OpType.IDENTITY, OpType.SIGMOID, OpType.TANH, OpType.ELU,
    OpType.GELU, OpType.RSQRT, OpType.POW, OpType.SIN, OpType.COS,
    OpType.EXP, OpType.SCALAR_MULTIPLY, OpType.SCALAR_ADD, OpType.SCALAR_SUB,
    OpType.SCALAR_TRUE_DIV, OpType.DROPOUT,
}


@register_op
class FusedOp(Op):
    op_type = OpType.FUSED

    def __init__(self, layer, input_shapes):
        super().__init__(layer, input_shapes)
        self.sub_layers: List[Layer] = layer.attrs["sub_layers"]
        # chain sub-ops through their logical shapes
        self.sub_ops: List[Op] = []
        cur = list(input_shapes)
        for sl in self.sub_layers:
            op = create_op(sl, cur)
            outs, _ = op.propagate(cur, {"_axis_sizes": self.attrs.get("_axis_sizes", {})})
            op.output_shapes = outs
            self.sub_ops.append(op)
            cur = outs

    def infer_output_shapes(self):
        last = self.sub_ops[-1].output_shapes[0]
        return [(last.sizes, last.dtype)]

    def forward(self, ctx, inputs, weights):
        import jax

        x = inputs[0]
        base_rng = ctx.rng
        for i, op in enumerate(self.sub_ops):
            # distinct rng per sub-op: two fused dropouts must not share a
            # mask (matches the per-op fold_in in the unfused graph)
            ctx.rng = (jax.random.fold_in(base_rng, i)
                       if base_rng is not None else None)
            with op_scope(op):      # a member keeps its own name
                (x,) = op.forward(ctx, [x], {})
        ctx.rng = base_rng
        return [x]

    def flops(self) -> float:
        return sum(op.flops() for op in self.sub_ops)


def apply_fusion(layers: List[Layer], protected: Set[int]) -> List[Layer]:
    """Fuse maximal chains of FUSIBLE layers (reference:
    FFModel::apply_fusion, model.cc:2495). ``protected`` is the set of
    tensor ids that must survive as real graph outputs (the logits tensor,
    anything the user kept a handle to is fine — only tensors consumed by
    later layers or the loss matter)."""
    consumers: Dict[int, int] = {}
    for l in layers:
        for t in l.inputs:
            consumers[t.tensor_id] = consumers.get(t.tensor_id, 0) + 1

    fused: List[Layer] = []
    run: List[Layer] = []

    def chainable(prev: Layer, nxt: Layer) -> bool:
        out = prev.outputs[0]
        return (
            nxt.inputs[0].tensor_id == out.tensor_id
            and consumers.get(out.tensor_id, 0) == 1
            and out.tensor_id not in protected
        )

    def flush():
        if len(run) >= 2:
            fl = Layer(OpType.FUSED,
                       name="fused_" + "_".join(l.name for l in run),
                       inputs=list(run[0].inputs),
                       attrs={"sub_layers": list(run)})
            # non-mutating: the shared Tensor objects keep their original
            # owner_layer, so a later compile() with fusion disabled sees
            # the pristine builder graph (toposort validates by tensor id)
            fl.outputs = list(run[-1].outputs)
            fused.append(fl)
        else:
            fused.extend(run)
        run.clear()

    for l in layers:
        is_fusible = (
            l.op_type in FUSIBLE
            and len(l.inputs) == 1
            and len(l.outputs) == 1
        )
        if is_fusible and run and chainable(run[-1], l):
            run.append(l)
        else:
            flush()
            if is_fusible:
                run.append(l)
            else:
                fused.append(l)
    flush()
    return fused
