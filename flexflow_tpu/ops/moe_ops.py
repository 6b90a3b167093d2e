"""MoE operator family: TopK, GroupBy, Aggregate, AggregateSpec, Cache.

TPU-native equivalents of the reference's MoE pipeline
(reference: src/ops/topk.cc, group_by.cc, aggregate.cc, aggregate_spec.cc,
cache.cc; composite FFModel::moe src/ops/moe.cc:20-45; SURVEY.md §2.2).

Design translation: the reference scatters rows with data-dependent CUDA
kernels. Under SPMD/XLA shapes must be static, so routing uses the
capacity-based one-hot **dispatch/combine** formulation (cumsum position
ranking): tokens beyond an expert's capacity are dropped, exactly matching
the reference's fixed expert-tensor capacity
``ceil(alpha * k / n * batch)`` (group_by.cc:143). GroupBy and Aggregate
recompute the *same* routing from ``gate_assign``, so their row orders
agree just like the reference's paired scatter/gather kernels.

The load-balancing term (reference: aggregate.cu
``agg_backward_kernel_gate`` — balance gradient
``(lambda_bal * n / batch) * count[e]`` added to full_gate_grads, then
zero-meaned per row) is reproduced exactly as an auxiliary straight-through
loss collected via ``LowerCtx.aux_losses``: its gradient wrt
``full_gate_preds`` equals the reference's kernel output. The combine-path
gradient reaches the router through softmax(top-k) autodiff rather than the
reference's direct injection into full_gate — the modern formulation of the
same credit assignment.
"""

from __future__ import annotations

import functools
import math
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map

from ..ffconst import ActiMode, DataType, OpType
from ..core.op import Op, register_op, sub_scope
from ..core.parallel_tensor import ParallelDim, ParallelTensorShape


@register_op
class TopK(Op):
    """reference: src/ops/topk.cc (builder model.h:537). Returns values and
    int32 indices over the last dim."""

    op_type = OpType.TOPK

    def infer_output_shapes(self):
        sizes = self.input_shapes[0].sizes
        k = self.attrs["k"]
        out = sizes[:-1] + (k,)
        return [(out, self.input_shapes[0].dtype), (out, DataType.INT32)]

    def forward(self, ctx, inputs, weights):
        vals, idx = jax.lax.top_k(inputs[0], self.attrs["k"])
        return [vals, idx.astype(jnp.int32)]


def expert_capacity(batch: int, k: int, n: int, alpha: float) -> int:
    """reference: group_by.cc:143 — ceil(alpha * k / n * batch)."""
    return int(math.ceil(alpha * k / n * batch))


def _use_pallas(ctx, tokens: int, k: int, n: int, capacity: int) -> bool:
    """Single-device lowering with kernels on, at a routing shape the
    kernels' SMEM operands fit (kernels/moe_kernels.supported)."""
    from ..kernels import use_pallas
    from ..kernels.moe_kernels import supported

    return use_pallas(ctx) and supported(tokens, k, n, capacity)


def moe_dispatch_mask(assign: jnp.ndarray, n: int, capacity: int) -> jnp.ndarray:
    """Routing shared by GroupBy and Aggregate.

    ``assign``: (B, k) int expert ids. Returns dispatch one-hot
    (T=B*k, n, capacity) float32: dispatch[t, e, c] = 1 iff flattened token
    t is the c-th token routed to expert e (tokens past capacity dropped,
    like the reference's fixed-size expert tensors).
    """
    flat = assign.reshape(-1).astype(jnp.int32)  # (T,)
    onehot = jax.nn.one_hot(flat, n, dtype=jnp.int32)  # (T, n)
    pos = jnp.cumsum(onehot, axis=0) - onehot  # rank of t within its expert
    pos = jnp.sum(pos * onehot, axis=1)  # (T,)
    keep = pos < capacity
    poh = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)  # (T, capacity)
    return (onehot.astype(jnp.float32) * keep[:, None].astype(jnp.float32))[
        :, :, None
    ] * poh[:, None, :]


def _dispatch_rows(ctx, x, assign, n: int, capacity: int, k: int):
    """Global-order dispatch: x (B, feat...) -> stacked (n, capacity,
    feat...) expert rows (the shared scatter of GroupBy / GroupByStacked;
    reference: group_by.cu)."""
    feat = x.shape[1:]
    xf = x.reshape(x.shape[0], -1)
    if _use_pallas(ctx, x.shape[0], k, n, capacity):
        from ..kernels.moe_kernels import moe_dispatch

        rows = moe_dispatch(xf, assign, n, capacity)
    else:
        # each sample is duplicated for each of its k expert picks
        xk = jnp.repeat(xf, k, axis=0)  # (T, d)
        dispatch = moe_dispatch_mask(assign, n, capacity)  # (T,n,c)
        rows = jnp.einsum("tnc,tf->ncf", dispatch, xk)  # (n,c,d)
    return rows.reshape((n, capacity) + feat)


@register_op
class GroupBy(Op):
    """reference: src/ops/group_by.cc — scatter input rows into n
    fixed-capacity expert tensors according to gate assignment."""

    op_type = OpType.GROUP_BY

    def __init__(self, layer, input_shapes):
        super().__init__(layer, input_shapes)
        self.n = self.attrs["n"]
        self.alpha = float(self.attrs["alpha"])
        self.k = input_shapes[1].sizes[-1]
        self.batch = input_shapes[0].sizes[0]
        self.capacity = expert_capacity(self.batch, self.k, self.n, self.alpha)

    def infer_output_shapes(self):
        d = self.input_shapes[0].sizes[1:]
        return [((self.capacity,) + d, self.input_shapes[0].dtype)] * self.n

    def forward(self, ctx, inputs, weights):
        x, assign = inputs
        rows = _dispatch_rows(ctx, x, assign, self.n, self.capacity, self.k)
        return [rows[e] for e in range(self.n)]


class _AggregateBase(Op):
    def __init__(self, layer, input_shapes):
        super().__init__(layer, input_shapes)
        self.n = self.attrs["n"]
        self.lambda_bal = float(self.attrs["lambda_bal"])
        self.k = input_shapes[0].sizes[-1]
        self.batch = input_shapes[0].sizes[0]
        self.capacity = input_shapes[4].sizes[0]
        self.out_dim = input_shapes[4].sizes[-1]

    def infer_output_shapes(self):
        # (batch, out_dim) — reference: aggregate.cc:149-152
        return [((self.batch, self.out_dim), self.input_shapes[4].dtype)]

    def _combine(self, gate_weights, assign, stacked, ctx=None):
        """Gate-weighted combine of stacked (n, capacity, d) expert rows
        (reference: aggregate.cu gather). Batch comes from the RUNTIME
        arrays, not compile-time shapes — the pipeline engine (and any
        microbatching caller) feeds fractions of the compiled batch, and a
        static reshape would silently mis-fold tokens into features."""
        if ctx is not None and _use_pallas(
                ctx, assign.shape[0], self.k, self.n, self.capacity):
            from ..kernels.moe_kernels import moe_combine

            return moe_combine(stacked, assign,
                               gate_weights.reshape(-1, self.k))
        dispatch = moe_dispatch_mask(assign, self.n, self.capacity)  # (T,n,c)
        combine = dispatch * gate_weights.reshape(-1)[:, None, None]
        out_flat = jnp.einsum("tnc,ncf->tf", combine, stacked)  # (T,d)
        return out_flat.reshape(-1, self.k, out_flat.shape[-1]).sum(axis=1)

    def _stack(self, exp_preds):
        return jnp.stack([p.reshape(self.capacity, -1) for p in exp_preds])

    def _balance_aux(self, full_gate, assign):
        """Straight-through auxiliary loss whose gradient wrt ``full_gate``
        is the reference's balance gradient: (lambda*n/B)*count[e],
        zero-meaned per row (aggregate.cu agg_backward_kernel_gate)."""
        if self.lambda_bal == 0.0:
            return None
        counts = jnp.sum(
            jax.nn.one_hot(assign.reshape(-1), self.n, dtype=jnp.float32), axis=0
        )
        # runtime batch (assign rows): microbatched callers feed fractions
        # of the compiled batch and the per-sample scale must not change
        g = (self.lambda_bal * self.n / assign.shape[0]) * counts  # (n,)
        g = g - jnp.mean(g)
        return jnp.sum(jax.lax.stop_gradient(g)[None, :] * full_gate)


@register_op
class Aggregate(_AggregateBase):
    """reference: src/ops/aggregate.cc — gate-weighted combine of expert
    outputs + load-balancing gradient."""

    op_type = OpType.AGGREGATE

    def forward(self, ctx, inputs, weights):
        gate_preds, assign, _true_assign, full_gate = inputs[:4]
        out = self._combine(gate_preds, assign, self._stack(inputs[4:]), ctx)
        aux = self._balance_aux(full_gate, assign)
        if aux is not None and hasattr(ctx, "aux_losses") and ctx.aux_losses is not None:
            ctx.aux_losses.append(aux)
        return [out]


@register_op
class AggregateSpec(_AggregateBase):
    """reference: src/ops/aggregate_spec.cc — the variant used with
    replicated labels; combines selected experts with uniform 1/k weight
    (per-expert losses are formed downstream against replicated labels)."""

    op_type = OpType.AGGREGATE_SPEC

    def forward(self, ctx, inputs, weights):
        gate_preds, assign, _true_assign, full_gate = inputs[:4]
        uniform = jnp.full_like(gate_preds, 1.0 / self.k)
        out = self._combine(uniform, assign, self._stack(inputs[4:]), ctx)
        aux = self._balance_aux(full_gate, assign)
        if aux is not None and hasattr(ctx, "aux_losses") and ctx.aux_losses is not None:
            ctx.aux_losses.append(aux)
        return [out]


# --------------------------------------------------------------------------
# Stacked MoE pipeline — the EXPERT-PARALLEL formulation.
#
# The n-output GroupBy above mirrors the reference API (one tensor per
# expert, each with its own dense ops), but n separate ops cannot shard
# "across experts". The stacked pipeline keeps all experts in ONE
# (n, capacity, d) tensor whose expert dim is a first-class ParallelDim:
# shard it over a mesh axis and the experts are truly distributed
# (SURVEY.md §2.3 EP; reference: group_by.cu/aggregate.cu data movement).
#
# ROUTING-LAYOUT INVARIANT: GroupByStacked and AggregateStacked each decide
# between two routings from the SAME structural predicate —
#   expert dim sharded over axis ax  AND  ax == the token (batch) axis
#   AND capacity % N == 0:
#     -> per-shard dispatch + all-to-all over ICI (rows grouped by source
#        shard; reference analog: group_by.cu scatter + NCCL a2a)
#   otherwise:
#     -> global one-hot dispatch/combine einsums (rows in global token
#        order; GSPMD inserts whatever collectives the shardings imply)
# Both ops see the same shapes, so the predicate — and therefore the row
# layout — always agrees between dispatch and combine.
#
# CAPACITY SEMANTICS under the hand-scheduled path: capacity is enforced
# PER SHARD (c_loc = capacity / degree), the standard per-device capacity
# of SPMD MoE systems — a hot expert can drop a token on one shard that the
# global formulation (whole-batch ranking) would have kept. Exact numerical
# parity with the unsharded model therefore requires alpha headroom such
# that no tokens drop; with drops, both formulations are valid MoE
# semantics but differ on which overflow tokens are cut.
# --------------------------------------------------------------------------


def _ep_axis(shape: ParallelTensorShape, token_dim) -> Tuple[str, int] | None:
    """The (axis, degree) of the hand-scheduled EP path, or None.

    ``shape``: the stacked (n, capacity, d) tensor; ``token_dim``: the
    batch ParallelDim of the assign tensor. See ROUTING-LAYOUT INVARIANT.
    """
    ed = shape.dims[0]
    if not ed.is_partitioned:
        return None
    if token_dim is None or not token_dim.is_partitioned:
        return None
    if ed.axis != token_dim.axis:
        return None
    if shape.dims[1].size % ed.degree != 0:
        return None
    return ed.axis, ed.degree


@register_op
class GroupByStacked(Op):
    """GroupBy emitting one stacked (n, capacity, d) tensor (see the
    module-level EP note; reference: src/ops/group_by.cc semantics)."""

    op_type = OpType.GROUP_BY_STACKED

    def __init__(self, layer, input_shapes):
        super().__init__(layer, input_shapes)
        self.n = self.attrs["n"]
        self.alpha = float(self.attrs["alpha"])
        self.k = input_shapes[1].sizes[-1]
        self.batch = input_shapes[0].sizes[0]
        self.capacity = expert_capacity(self.batch, self.k, self.n, self.alpha)

    def infer_output_shapes(self):
        d = self.input_shapes[0].sizes[1:]
        return [((self.n, self.capacity) + d, self.input_shapes[0].dtype)]

    def propagate(self, input_shapes, strategy):
        out_shapes, weight_shapes = super().propagate(input_shapes, strategy)
        axis_sizes = strategy.get("_axis_sizes", {})
        ax = strategy.get("expert")
        if ax:
            deg = axis_sizes.get(ax, 1)
            if deg > 1 and self.n % deg != 0:
                # never silently ignore a pinned strategy: the search
                # pre-filters candidates, so this only fires on user error
                raise ValueError(
                    f"{self.name}: expert axis {ax!r} (degree {deg}) does "
                    f"not divide num experts {self.n}")
            if deg <= 1 and ax not in axis_sizes:
                raise ValueError(
                    f"{self.name}: expert axis {ax!r} is not a mesh axis "
                    f"(have {sorted(axis_sizes)})")
            if deg > 1:
                # base propagate may have matched dim0 (size n) against the
                # input batch dim; overwrite with the expert sharding
                out_shapes[0] = ParallelTensorShape(
                    (ParallelDim(self.n, deg, ax),)
                    + tuple(ParallelDim(d.size) for d in out_shapes[0].dims[1:]),
                    out_shapes[0].dtype,
                )
        else:
            # dim0 is the EXPERT dim — it must not inherit the input's
            # batch sharding even when n happens to equal the batch size
            out_shapes[0] = ParallelTensorShape(
                tuple(ParallelDim(d.size) for d in out_shapes[0].dims),
                out_shapes[0].dtype,
            )
        return out_shapes, weight_shapes

    def forward(self, ctx, inputs, weights):
        x, assign = inputs
        feat = x.shape[1:]
        ep = _ep_axis(self.output_shapes[0], self.input_shapes[1].dims[0]) \
            if self.output_shapes else None
        if ep is not None and ctx.mesh is not None:
            from jax.sharding import PartitionSpec as P

            from ..kernels import pallas_mode
            from ..parallel.collectives import expert_all_to_all

            from ..kernels.moe_kernels import supported as kernel_fits

            ax, deg = ep
            c_loc = self.capacity // deg
            n, k = self.n, self.k
            use_kernel = pallas_mode() is not None and kernel_fits(
                x.shape[0] // deg, k, n, c_loc)

            def body(x_loc, assign_loc):
                # per-shard dispatch (reference: group_by.cu scatter)
                xf = x_loc.reshape(x_loc.shape[0], -1)
                if use_kernel:
                    from ..kernels.moe_kernels import moe_dispatch

                    return moe_dispatch(xf, assign_loc, n, c_loc)
                xk = jnp.repeat(xf, k, axis=0)
                disp = moe_dispatch_mask(assign_loc, n, c_loc)
                return jnp.einsum("tnc,tf->ncf", disp, xk)

            rows = shard_map(
                body, mesh=ctx.mesh,
                in_specs=(P(ax, *([None] * (x.ndim - 1))), P(ax, None)),
                out_specs=P(None, ax, None),
                check_vma=False,  # pallas_call outputs carry no vma typing
            )(x, assign)
            # redistribute token-sharded rows onto the expert owners (ICI
            # all-to-all; reference analog: NCCL a2a in group_by's shuffle)
            rows = expert_all_to_all(rows, ctx.mesh, ax)
            return [rows.reshape((self.n, self.capacity) + feat)]
        return [_dispatch_rows(ctx, x, assign, self.n, self.capacity, self.k)]

    def flops(self) -> float:
        d = 1
        for s in self.input_shapes[0].sizes[1:]:
            d *= s
        return 2.0 * self.batch * self.k * self.n * self.capacity * d


@register_op
class ExpertLinear(Op):
    """Per-expert dense over the stacked (n, capacity, d) tensor: weight
    (n, d, out) shards on the expert dim, so each device computes only its
    experts (reference analog: the per-expert Linear ops of moe.cc:20-45,
    here batched so EP is expressible)."""

    op_type = OpType.EXPERT_LINEAR

    def __init__(self, layer, input_shapes):
        super().__init__(layer, input_shapes)
        self.out_dim = layer.attrs["out_dim"]
        self.activation = layer.attrs.get("activation", ActiMode.NONE)
        self.use_bias = layer.attrs.get("use_bias", True)
        self.n = input_shapes[0].sizes[0]
        self.capacity = input_shapes[0].sizes[1]
        self.in_dim = input_shapes[0].sizes[-1]

    def infer_output_shapes(self):
        return [((self.n, self.capacity, self.out_dim),
                 self.input_shapes[0].dtype)]

    def weight_specs(self):
        from ..core.op import WeightSpec
        from ..runtime.initializer import (DefaultBiasInitializer,
                                           DefaultWeightInitializer)

        dt = self.input_shapes[0].dtype
        specs = [WeightSpec(
            "kernel", (self.n, self.in_dim, self.out_dim), dt,
            self.attrs.get("kernel_initializer") or DefaultWeightInitializer(),
            weight_decay=True,
        )]
        if self.use_bias:
            specs.append(WeightSpec(
                "bias", (self.n, self.out_dim), dt,
                self.attrs.get("bias_initializer") or DefaultBiasInitializer(),
                weight_decay=False,
            ))
        return specs

    def propagate(self, input_shapes, strategy):
        out_shapes, weight_shapes = super().propagate(input_shapes, strategy)
        axis_sizes = strategy.get("_axis_sizes", {})
        in0 = input_shapes[0]
        # expert sharding: explicit strategy, else inherit the input's
        # expert-dim sharding so weights stay local to their experts
        ax = strategy.get("expert") or (
            in0.dims[0].axis if in0.dims[0].is_partitioned else None
        )
        if ax:
            deg = axis_sizes.get(ax, in0.dims[0].degree or 1)
            if deg > 1 and self.n % deg == 0:
                out_shapes[0] = out_shapes[0].partitioned(0, deg, ax)
                weight_shapes["kernel"] = weight_shapes["kernel"].partitioned(0, deg, ax)
                if self.use_bias:
                    weight_shapes["bias"] = weight_shapes["bias"].partitioned(0, deg, ax)
        return out_shapes, weight_shapes

    def forward(self, ctx, inputs, weights):
        (x,) = inputs
        y = jnp.einsum("ecd,edh->ech", x, weights["kernel"])
        if self.use_bias:
            y = y + weights["bias"][:, None, :]
        from .linear import apply_activation

        return [apply_activation(y, self.activation)]

    def flops(self) -> float:
        return 2.0 * self.n * self.capacity * self.in_dim * self.out_dim


@register_op
class AggregateStacked(_AggregateBase):
    """Aggregate over the stacked expert tensor. Inputs:
    [gate_preds (B,k), gate_assign (B,k), full_gate (B,n),
    exp_stacked (n, capacity, f)] -> (B, f). Routing layout follows the
    module-level invariant (must mirror GroupByStacked's choice)."""

    op_type = OpType.AGGREGATE_STACKED

    def __init__(self, layer, input_shapes):
        Op.__init__(self, layer, input_shapes)
        self.n = self.attrs["n"]
        self.lambda_bal = float(self.attrs["lambda_bal"])
        self.k = input_shapes[0].sizes[-1]
        self.batch = input_shapes[0].sizes[0]
        self.capacity = input_shapes[3].sizes[1]
        self.out_dim = input_shapes[3].sizes[-1]

    def infer_output_shapes(self):
        return [((self.batch, self.out_dim), self.input_shapes[3].dtype)]

    def forward(self, ctx, inputs, weights):
        gate_preds, assign, full_gate, stacked = inputs
        ep = _ep_axis(self.input_shapes[3], self.input_shapes[1].dims[0])
        if ep is not None and ctx.mesh is not None:
            from jax.sharding import PartitionSpec as P

            from ..kernels import pallas_mode
            from ..parallel.collectives import experts_to_tokens

            from ..kernels.moe_kernels import supported as kernel_fits

            ax, deg = ep
            c_loc = self.capacity // deg
            n, k = self.n, self.k
            use_kernel = pallas_mode() is not None and kernel_fits(
                assign.shape[0] // deg, k, n, c_loc)
            # expert outputs back to the token-owning shards (inverse a2a)
            rows = experts_to_tokens(
                stacked.reshape(self.n, self.capacity, -1), ctx.mesh, ax)

            def body(rows_loc, assign_loc, gate_loc):
                if use_kernel:
                    from ..kernels.moe_kernels import moe_combine

                    return moe_combine(rows_loc, assign_loc, gate_loc)
                disp = moe_dispatch_mask(assign_loc, n, c_loc)
                comb = disp * gate_loc.reshape(-1)[:, None, None]
                out = jnp.einsum("tnc,ncf->tf", comb, rows_loc)
                return out.reshape(gate_loc.shape[0], k, -1).sum(axis=1)

            out = shard_map(
                body, mesh=ctx.mesh,
                in_specs=(P(None, ax, None), P(ax, None), P(ax, None)),
                out_specs=P(ax, None),
                check_vma=False,  # pallas_call outputs carry no vma typing
            )(rows, assign, gate_preds)
        else:
            out = self._combine(
                gate_preds, assign,
                stacked.reshape(self.n, self.capacity, -1), ctx)
        aux = self._balance_aux(full_gate, assign)
        if aux is not None and ctx.aux_losses is not None:
            ctx.aux_losses.append(aux)
        return [out]

    def flops(self) -> float:
        return 2.0 * self.batch * self.k * self.n * self.capacity * self.out_dim


@register_op
class Cache(Op):
    """reference: src/ops/cache.cc — caches an intermediate tensor (expert
    assignments) across iterations, scored by a user function; pairs with
    the recompile-on-condition hook (moe.cc:180-204). Under jit the cached
    value is a pass-through; the trigger machinery lives in
    runtime/recompile.py."""

    op_type = OpType.CACHE

    def infer_output_shapes(self):
        return [(self.input_shapes[0].sizes, self.input_shapes[0].dtype)]

    def forward(self, ctx, inputs, weights):
        return [inputs[0]]


# rows a matrix read once from HBM multiplies in the time of its read on
# the chip this is measured on (a v5e: 197 TFLOP/s over 819 GB/s): up to
# here the dense form's rows cost no more than the matrices' bytes
RIDGE_ROWS = 240
# under the ridge, the share of the held experts a call can name
# (``RoutedExperts.named_share``) below which the kernel, which reads the
# named experts' matrices alone, takes the call from the dense form, which
# reads them all. Measured on a v5e at Trinity-Large's share (32 gated
# experts of 3,072 x 3,072 held of 256, top-4; ``tools/
# expert_forms_crossover.py trinity-large-ep8``, PERF.md section 6, PR 43):
# a layer call in the dense form takes 2.47-2.58 ms at 1 to 128 rows
# whatever is named (2.82 at 240), in the kernel 0.05 ms and 0.080 ms a
# NAMED expert (0.87 ms for 10 of 32 at 32 rows, 1.85 for 23 at 64, 2.38
# for 30 at 128): they cross where 30.5 of the 32 are named, a share of
# 0.95. Placed a step under that, since the sweep is one expert shape and
# a smaller expert pays more of its time a tile: at 0.87 (128 rows) the
# kernel still leads by 4 % on an even routing and by 29 % on an uneven
# one; at 0.996, a step of 128 slots of 8 picks over 192 or of 22 over
# 512, the dense form stays. That is the cut a PROGRAM is made by, from
# what its rows CAN name at an even routing; a decode step whose estimate
# is past it is cut again on the device, a step at a time, by what its live
# rows DO name, against the same share of the count held
# (``RoutedExperts.kernel_limit``). Measured there with the named count
# forced, at four shapes (the same tool, PERF.md section 6, PR 54): a
# named expert's matrices at 751-760 GB/s behind 0.04-0.08 ms a call, all
# the held ones' in the dense form at 723-741 GB/s, which cross at 15.3 of
# 16 experts of 2,048 x 2,048 (48 rows), 125.6 of 128 of 1,024 x 2,688
# (128 rows), 11.7 of 12 of 7,168 x 2,048 (128 rows) and 32 of 32 of
# 3,072 x 3,072 (32 rows): every one past 0.95, so 0.9 is on the dense
# side of them all, and at 0.9 the kernel leads by 8 to 10 %
NAMED_SHARE_KERNEL = 0.9
# a router's pick of k among n: k passes of a first maximum against one sort
# of the row (``k_largest``). A pass reads the row once, a bitonic sort of n
# takes ``b (b + 1) / 2`` stages over it, ``b = ceil(log2 n)``, whatever k
# is: passes where ``SELECT_PASS_STAGES`` x k is no more than the stages.
# Measured on a v5e (``tools/route_select_forms.py``, PERF.md section 6, PR
# 62: a pick alone behind a sigmoid, the device's busy time a call). At 256
# rows the sort takes 0.0221 ms of 512 whatever k from 6 up (0.0092 at k =
# 4, 0.0057 at 2: under 6 ``lax.top_k`` is no full sort here), 0.0101 of
# 256, 0.0093 of 192, 0.0050 of 128, 0.0022 of 64, 0.0009 of 8; the passes
# 0.0105 ms for 8 of 512 (0.0177 for 12, 0.0260 for 16, 0.0407 for 22: a
# pass costs more the more there are), 0.0072 for 8 of 256, 0.0040 for 8 of
# 192, 0.0012 for 2 of 64. They cross at k = 1 to 2 of 8, 2 to 4 of 16, 4
# to 6 of 32, 8 of 64, 6 to 8 of 128, 16 to 22 of 192, 8 to 12 of 256 and
# 12 to 16 of 512, the same at 2,048 rows to one step of k: 2.3 to 6 stages
# a pass. At 3.5 the rule is on the measured side of every pick the six
# routed configurations make, decode rows | prompt rows, passes | sort in
# us: 8 of 512 10.5 | 22.1 and 36.5 | 85.6, 8 of 192 2.6 | 5.2 and 12.1 |
# 33.1, 4 of 256 2.1 | 4.3 and 18.6 | 71.9, 8 of 256 3.6 | 4.3 and 46.0 |
# 71.9, 1 of 16 0.9 | 1.4 and 1.3 | 18.6, a group's best 2 of 64 3.0 | 14.2
# and 9.3 | 53.6, of 24 1.4 | 4.1 and 4.5 | 25.9; the sort for 22 of 512,
# 20.8 | 11.8 and 152.1 | 85.9, and for a grouped router's 4 groups of 8,
# 1.2 | 1.0 and 1.4 | 1.2. On the wrong side, none of them asked for: 8 of
# 128 (6.8 | 5.0) and 12 and 16 of 192 (6.2 and 8.8 | 9.4). What the
# grouped routers paid was less the sorting than its shape, the best 2 of
# each group sorted over THREE axes and the kept groups scattered: a
# layer's whole ``route`` at Ling's 256 rows takes 0.253 ms as it stood,
# 0.063 with the same three sorts over rows and a compare for the scatter,
# 0.038 as this rule cuts it (0.274, 0.236, 0.126 at 1,024 rows)
SELECT_PASS_STAGES = 3.5
# of the rows of a call, the share one held expert may be named by before
# the grouped form gives the call to the dense one
CAPACITY_SHARE = 4
# the held experts' own matrices among a routed-experts op's weights
EXPERT_MATRICES = ("w_gate", "w_up", "w_down")


def select_form(k: int, n: int) -> str:
    """How :func:`k_largest` picks ``k`` of ``n``: ``"passes"`` or
    ``"sort"``, by the two numbers alone (``SELECT_PASS_STAGES``)."""
    bits = max(1, (n - 1).bit_length())
    return ("passes" if SELECT_PASS_STAGES * k <= bits * (bits + 1) / 2
            else "sort")


def _order_keys(bits):
    """int32 bit patterns of float32 numbers <-> int32 keys that compare as
    the numbers do in the total order a sort puts them in (-0.0 under 0.0,
    a NaN past +inf). Its own inverse: the sign bit stays."""
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


def k_largest(x, k: int, form: Optional[str] = None):
    """The ``k`` largest of the last axis of a float32 ``x``: their
    values ``(..., k)`` and int32 indices, descending, the lower index
    first among equals: ``jax.lax.top_k``'s answer to the bit, NaN, -inf
    and signed zeros included, in the form :func:`select_form` names
    (``form`` says it in its place, for tests and the sweep).
    ``"passes"``: k times the first maximum of the row, one variadic
    reduction over (key, index) a pass, the picked entry then struck by
    its INDEX (its key the least there is and its index past every
    other), so that a row of equal entries, of NaN or of -inf, still
    yields k distinct ids; the values are the keys the reductions
    returned, so nothing is gathered, sorted or scattered. A selection:
    no gradient passes through either form (``route`` takes its weights
    from the scores at the ids)."""
    if x.dtype != jnp.float32:
        raise TypeError(f"k_largest orders float32 bits, not {x.dtype}")
    x = jax.lax.stop_gradient(x)
    n = x.shape[-1]
    if (form or select_form(k, n)) == "sort":
        # as rows: over three axes the chip's compiler sorts several times
        # slower (``SELECT_PASS_STAGES``: Ling's ``route`` 0.253 | 0.063 ms)
        values, ids = jax.lax.top_k(x.reshape(-1, n), k)
        lead = x.shape[:-1] + (k,)
        return values.reshape(lead), ids.reshape(lead)
    lowest, past = np.iinfo(np.int32).min, np.iinfo(np.int32).max

    def first_max(a, b):
        (ak, ai), (bk, bi) = a, b
        first = (ak > bk) | ((ak == bk) & (ai < bi))
        return jnp.where(first, ak, bk), jnp.where(first, ai, bi)

    keys = _order_keys(jax.lax.bitcast_convert_type(x, jnp.int32))
    index = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    values, ids = [], []
    for _ in range(k):
        key, idx = jax.lax.reduce(
            (keys, index), (np.int32(lowest), np.int32(past)), first_max,
            (x.ndim - 1,))
        values.append(key)
        ids.append(idx)
        struck = index == idx[..., None]
        keys, index = (jnp.where(struck, lowest, keys),
                       jnp.where(struck, past, index))
    values = jax.lax.bitcast_convert_type(
        _order_keys(jnp.stack(values, -1)), jnp.float32)
    return values, jnp.stack(ids, -1)


def _expert_matrices(weights):
    return {name: weights[name] for name in EXPERT_MATRICES
            if name in weights}


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def kernel_form(op, matrices, v, ids, gates):
    """``op._apply_grouped`` as one kernel call
    (``kernels/grouped_experts.py``): (the held experts' weighted sum,
    the rows its products ran over, () uint32). ``matrices`` are the
    op's ``EXPERT_MATRICES``."""
    from ..kernels.grouped_experts import grouped_experts

    return grouped_experts(v, ids, gates, matrices, first=op.first,
                           gated=op.gated, limit=op.limit)


def _kernel_form_fwd(op, *args):
    return kernel_form(op, *args), args


def _kernel_form_bwd(op, args, cotangents):
    # nothing trains through the kernel yet: the jnp form's gradients
    # (none for the ids, none from the count of rows)
    matrices, v, ids, gates = args
    grads = jax.vjp(lambda m, v, g: op._apply_grouped(m, v, ids, g),
                    matrices, v, gates)[1](cotangents[0])
    return grads[0], grads[1], np.zeros(ids.shape, jax.dtypes.float0), grads[2]


kernel_form.defvjp(_kernel_form_fwd, _kernel_form_bwd)


@register_op
class RoutedExperts(Op):
    """Dropless top-k routing over ``n_routed`` experts, of which this op
    holds ``experts_held = (first, count)`` (no reference analog; the
    formulation of DeepSeek-V3 2024, and with ``latent`` the LatentMoE of
    the Nemotron 3 line). Per token ``u``:

    * scores ``s = sigmoid(float32(u) W_router)`` (``scoring``
      ``"softmax"``: the softmax over all experts), in float32 whatever
      the activations' dtype; with ``router="mlp"`` the router's logits
      are an MLP's over a state ``r`` of ``router_width`` numbers a
      token: ``r = u W_dn + b_dn + depth_scale * r_prev`` (``r_prev`` the
      state of the routed layer before, the op's second input; a model's
      first such layer has neither it nor ``depth_scale``), logits
      ``gelu(gelu(rms(r) W_1 + b_1) W_2 + b_2) W_router`` (exact GELU),
      all float32, and ``r`` is the op's second output;
    * with ``selection_bias`` the choice is made by ``s + b`` (a learned
      bias an expert; the weights below are still ``s``'s);
    * with ``n_group`` > 1 the experts are ``n_group`` groups of equal
      size, a group scores the sum of its two highest, the
      ``topk_group`` highest groups stay;
    * ``T`` = the ``experts_per_token`` highest among what stays;
      ``g_e = s_e / sum_{T} s`` (``norm_topk``) times ``routed_scale``;
    * with ``latent`` the experts read ``v = u W_dn`` (``latent`` wide)
      and their weighted sum goes through ``W_up`` back to the model's
      width; else ``v = u``;
    * output ``sum_{e in T, e held} g_e MLP_e(v)``, each of width
      ``width``: ``activation`` ``"silu_gated"``: ``(silu(v Wg) * (v
      Wu)) Wd``; ``"relu2"``: ``relu(v Wu)^2 Wd``.

    It routes over ALL ``n_routed`` experts and computes only the pairs
    whose expert it holds, adding nothing for the others: the sum over
    the holders of all shares is the whole layer's routed part (with
    ``latent``, after ``W_up``). Nothing is dropped (:meth:`apply`).
    Weights: ``router`` (E, n_routed) (``"mlp"``: (router_width,
    n_routed), behind ``router_down`` (E, router_width), ``router_b``,
    ``depth_scale``, ``router_norm``, ``router_w1``, ``router_b1``,
    ``router_w2``, ``router_b2``), ``bias`` (n_routed,),
    ``latent_down`` (E, latent), ``latent_up`` (latent, E), ``w_gate``
    (gated only) and ``w_up`` (count, V, width), ``w_down`` (count,
    width, V), V the width the experts read.
    """

    op_type = OpType.ROUTED_EXPERTS

    def __init__(self, layer, input_shapes):
        super().__init__(layer, input_shapes)
        a = self.attrs
        self.in_dim: int = input_shapes[0].sizes[-1]
        self.n_routed = int(a["n_routed"])
        self.k = int(a["experts_per_token"])
        self.width = int(a["width"])
        self.n_group = int(a.get("n_group") or 1)
        self.topk_group = int(a.get("topk_group") or self.n_group)
        self.scoring = a.get("scoring", "sigmoid")
        self.norm_topk = bool(a.get("norm_topk", True))
        self.routed_scale = float(a.get("routed_scale", 1.0))
        self.selection_bias = bool(a.get("selection_bias", False))
        self.gated = a.get("activation", "silu_gated") == "silu_gated"
        # the clamp before a gated expert's product (None: none)
        self.limit = None if a.get("limit") is None else float(a["limit"])
        if self.limit is not None and not self.gated:
            raise ValueError(f"{layer.name}: a limit clamps a gated "
                             f"expert's gate and up-projection")
        self.latent = int(a.get("latent") or 0)
        self.work_dim = self.latent or self.in_dim
        first, count = a.get("experts_held") or (0, self.n_routed)
        self.first, self.count = int(first), int(count)
        if self.scoring not in ("sigmoid", "softmax"):
            raise ValueError(f"scoring {self.scoring!r} is neither "
                             f"'sigmoid' nor 'softmax'")
        self.router = a.get("router", "linear")
        if self.router not in ("linear", "mlp"):
            raise ValueError(f"router {self.router!r} is neither 'linear' "
                             f"nor 'mlp'")
        self.router_width = int(a.get("router_width") or 0)
        self.router_eps = float(a.get("router_eps", 1e-5))
        # an MLP router reads the state of the routed layer before (the
        # second input) and hands its own on (the second output)
        self.takes_state = len(input_shapes) > 1
        if self.takes_state and self.router != "mlp":
            raise ValueError(f"{self.name}: only an MLP router takes a "
                             f"router state")
        if a.get("activation", "silu_gated") not in ("silu_gated", "relu2"):
            raise ValueError(f"activation {a['activation']!r} is neither "
                             f"'silu_gated' nor 'relu2'")
        if self.n_routed % self.n_group:
            raise ValueError(f"{self.n_routed} experts are not {self.n_group}"
                             f" equal groups")
        if not (0 <= self.first and self.count >= 1
                and self.first + self.count <= self.n_routed):
            raise ValueError(f"experts_held {(self.first, self.count)} is "
                             f"not a share of {self.n_routed} experts")
        if self.k > self.topk_group * (self.n_routed // self.n_group):
            raise ValueError("fewer experts stay than a token takes")

    def infer_output_shapes(self):
        x = self.input_shapes[0]
        outs = [(x.sizes, x.dtype)]
        if self.router == "mlp":
            outs.append((x.sizes[:-1] + (self.router_width,), DataType.FLOAT))
        return outs

    def _router_specs(self, dt, init):
        from ..core.op import WeightSpec
        from ..runtime.initializer import ConstantInitializer, ZeroInitializer

        if self.router != "mlp":
            return [WeightSpec("router", (self.in_dim, self.n_routed), dt,
                               init)]
        r = self.router_width
        one = self.attrs.get("gain_initializer") or ConstantInitializer(1.0)
        zero = self.attrs.get("bias_initializer") or ZeroInitializer()

        def vector(name, how):
            return WeightSpec(name, (r,), dt, how, weight_decay=False)

        return ([WeightSpec("router_down", (self.in_dim, r), dt, init),
                 vector("router_b", zero)]
                + ([vector("depth_scale", one)] if self.takes_state else [])
                + [vector("router_norm", one),
                   WeightSpec("router_w1", (r, r), dt, init),
                   vector("router_b1", zero),
                   WeightSpec("router_w2", (r, r), dt, init),
                   vector("router_b2", zero),
                   WeightSpec("router", (r, self.n_routed), dt, init)])

    def weight_specs(self):
        from ..core.op import WeightSpec
        from ..runtime.initializer import (DefaultWeightInitializer,
                                           ZeroInitializer)

        dt = self.input_shapes[0].dtype
        init = self.attrs.get("kernel_initializer") or DefaultWeightInitializer()
        e, v, w, c = self.in_dim, self.work_dim, self.width, self.count
        specs = self._router_specs(dt, init)
        if self.selection_bias:
            specs.append(WeightSpec(
                "bias", (self.n_routed,), dt,
                self.attrs.get("bias_initializer") or ZeroInitializer(),
                weight_decay=False))
        if self.latent:
            specs += [WeightSpec("latent_down", (e, v), dt, init),
                      WeightSpec("latent_up", (v, e), dt, init)]
        if self.gated:
            specs.append(WeightSpec("w_gate", (c, v, w), dt, init))
        return specs + [WeightSpec("w_up", (c, v, w), dt, init),
                        WeightSpec("w_down", (c, w, v), dt, init)]

    # ---- the two halves (serving reads the first's ids) -------------------
    def _router_logits(self, weights, x2d, prev):
        """The router's (T, n_routed) float32 logits and, from an MLP
        router, its (T, router_width) float32 state."""
        f32 = jnp.float32

        def mm(a, name):
            return jnp.dot(a, weights[name].astype(f32),
                           precision=jax.lax.Precision.HIGHEST)

        x = x2d.astype(f32)
        if self.router != "mlp":
            return mm(x, "router"), None
        from .norm import rms_norm

        r = mm(x, "router_down") + weights["router_b"].astype(f32)
        if self.takes_state:
            r = r + weights["depth_scale"].astype(f32) * prev.reshape(
                r.shape).astype(f32)
        h = rms_norm(r, weights["router_norm"], self.router_eps)
        for i in ("1", "2"):
            h = jax.nn.gelu(mm(h, "router_w" + i)
                            + weights["router_b" + i].astype(f32),
                            approximate=False)
        return mm(h, "router"), r

    @sub_scope("route")
    def route(self, weights, x2d, ids=None, prev=None):
        """``x2d`` (T, E) -> expert ids (T, k) int32, their weights (T,
        k) float32, over all ``n_routed`` experts, and the router's state
        (T, router_width) float32 (None from a router that keeps none;
        ``prev`` is the state of the routed layer before). With ``ids``
        given the selection is skipped: those experts are taken, weighted
        by this op's own scores of them (a comparison that has to follow
        another program's routing)."""
        logits, state = self._router_logits(weights, x2d, prev)
        s = (jax.nn.sigmoid(logits) if self.scoring == "sigmoid"
             else jax.nn.softmax(logits, axis=-1))
        choice = s
        if self.selection_bias:
            choice = s + weights["bias"].astype(jnp.float32)
        if ids is not None:
            ids = ids.astype(jnp.int32)
        elif self.n_group > 1 and self.topk_group < self.n_group:
            t = s.shape[0]
            per = self.n_routed // self.n_group
            grouped = choice.reshape(t, self.n_group, per)
            gscore = k_largest(grouped, min(2, per))[0].sum(-1)
            _, gidx = k_largest(gscore, self.topk_group)
            keep = (gidx[:, :, None] == jnp.arange(
                self.n_group, dtype=jnp.int32)).any(1)
            choice = jnp.where(keep[:, :, None], grouped, -1.0).reshape(
                t, self.n_routed)
        if ids is None:
            _, ids = k_largest(choice, self.k)
        g = jnp.take_along_axis(s, ids, axis=-1)
        if self.norm_topk:
            g = g / (g.sum(-1, keepdims=True) + 1e-20)
        return ids.astype(jnp.int32), g * self.routed_scale, state

    def select_form(self) -> str:
        """How :meth:`route` picks its ``k`` of ``n_routed``
        (:func:`select_form`: ``"passes"`` or ``"sort"``; a grouped
        router's two smaller picks, the best 2 of a group and
        ``topk_group`` of the groups, follow the same rule at their own
        sizes). By the op's two numbers alone, whatever the rows."""
        return select_form(self.k, self.n_routed)

    def held_hits(self, ids):
        """``ids`` (T, k) -> (T, k, count) bool: which held expert, if
        any, each pick names."""
        return ((ids - self.first)[..., None]
                == jnp.arange(self.count, dtype=jnp.int32))

    def named_share(self, rows: int) -> float:
        """The share of the held experts' matrices a call of ``rows``
        tokens has to read, at the most: a token names a given expert
        with probability ``k / n_routed``, so ``rows`` of them leave it
        unnamed with ``(1 - k / n_routed) ** rows`` where the routing is
        even (an uneven one names fewer). 39.6 % for 32 rows of 4 picks
        over 256, 99.6 % for 128 of 8 over 192 or of 22 over 512."""
        return 1.0 - (1.0 - self.k / self.n_routed) ** rows

    def kernel_limit(self) -> int:
        """The most held experts a counted call may name and still be the
        kernel's: ``NAMED_SHARE_KERNEL`` of those held, the share a
        program is cut by applied to what a call does name: 14 of 16,
        115 of 128, 10 of 12."""
        return int(NAMED_SHARE_KERNEL * self.count)

    def expert_form(self, rows: int, dtype=None, mesh=None,
                    active: bool = False) -> str:
        """How :meth:`apply` multiplies ``rows`` tokens of ``dtype`` (the
        op's declared one where not given) in a program over ``mesh``:
        ``"dense"`` (every token through every held expert), ``"kernel"``
        (the pairs the routing names, in tiles the routing names:
        ``kernels/grouped_experts.py``), ``"counted"`` (one of those two
        a CALL, chosen on the device by the count of held experts the
        call's live rows name: :meth:`_apply_counted`) or ``"grouped"``
        (the same pairs
        in jnp: an expert's rows side by side in a tile of
        :meth:`capacity` rows, what overflows a tile in a few spill
        tiles). A rule over what a trace sees, no knob. Up to
        ``RIDGE_ROWS`` rows the matrices' bytes decide (a decode step's
        slots, the one row behind a head's cut): the kernel reads the
        matrices of the experts the routing names and the dense form
        those of all it holds, so the kernel where the call can name
        under ``NAMED_SHARE_KERNEL`` of them at an even routing
        (:meth:`named_share`). Past that estimate the dense form, unless
        the caller says which of its rows are live (``active``: a decode
        step, whose idle slots name nothing and whose router may be
        uneven, neither of which the estimate knows): such a call counts
        what it names and is the kernel's up to :meth:`kernel_limit`
        experts, the dense form's beyond. Never the jnp grouped form
        under the ridge. Past the ridge the
        products decide (a prefill's bucket): the kernel, else the jnp
        grouped form. The kernel only where Pallas is on, the program is
        one device's (the kernel has no ``shard_map`` composition:
        ``kernels.use_pallas``) and its ``supported()`` takes the shapes
        (not the CPU, a mesh, float32 rows, widths of no whole lane
        tiles, rows past its fast memory); where it is not, a call that
        would be counted is dense. That holds for a training
        call too: ``fit`` reaches :meth:`apply` through :meth:`forward`,
        and few rows on one TPU device take ``kernel_form``, whose
        backward is the jnp grouped form's. PERF.md section 6, PRs 40,
        41, 43 and 54, has the forms measured."""
        few = rows <= RIDGE_ROWS
        estimate_dense = few and (
            self.named_share(rows) >= NAMED_SHARE_KERNEL)
        if estimate_dense and not active:
            return "dense"
        from ..kernels.grouped_experts import supported

        if dtype is None:
            dtype = self.input_shapes[0].dtype.to_jnp()
        if (mesh is None or mesh.size == 1) and supported(
                rows, self.k, self.work_dim, self.width, self.count,
                self.gated, dtype):
            return "counted" if estimate_dense else "kernel"
        return "dense" if few else "grouped"

    def capacity(self, rows: int) -> int:
        """Rows of a held expert's tile in the jnp grouped form (the
        kernel has no capacity: its tiles follow the routing): a quarter
        of the call's, whole sublane tiles. The routing names an expert
        ``rows x k / n_routed`` times on average (a twenty-fourth of the
        rows at 22 of 512 and at 8 of 192), so a tile holds an expert six
        times as full as the mean."""
        return -(-rows // (CAPACITY_SHARE * 16)) * 16

    @property
    def spill_tiles(self) -> int:
        """Tiles of the jnp grouped form beside the held experts' own
        (the kernel has none): what takes the rows an expert is named by
        beyond its tile (a sixteenth as many as the experts held, one at
        the least)."""
        return max(1, self.count // 16)

    def rows_computed(self, rows: int, dtype=None, mesh=None,
                      active: bool = False) -> Optional[int]:
        """Rows the held experts' products run over for ``rows`` tokens
        by the form :meth:`expert_form` names, where the shapes say them:
        every token an expert in the dense form; a tile an expert and the
        spill tiles in the jnp grouped one (where they hold what
        overflows). None for the kernel and for a counted call: their
        rows follow the routing and
        are counted on the device (:meth:`apply`'s ``computed``, which a
        prompt program and a decode step alike add to their counters)."""
        form = self.expert_form(rows, dtype, mesh, active)
        if form in ("kernel", "counted"):
            return None
        if form == "dense":
            return self.count * rows
        return (self.count + self.spill_tiles) * self.capacity(rows)

    def _gated(self, g, u):
        """``silu(g) * u``, behind the clamp where the op has a ``limit``
        (``g`` cut at it from above, ``u`` to ``[-limit, limit]``)."""
        if self.limit is not None:
            g = jnp.minimum(g, self.limit)
            u = jnp.clip(u, -self.limit, self.limit)
        return jax.nn.silu(g) * u

    def _apply_dense(self, weights, v, ids, gates):
        """Every token passes through every held expert and is weighted
        by its gate, 0 where the token did not take the expert: the
        shapes do not depend on the routing, each matrix is read once a
        call, and the weighted sum over the experts is part of the down
        product. That is ``n_routed / k`` times the rows the routing
        names; at a decode step's few rows the matrices' bytes decide,
        not the rows, and where the step names nearly every held expert
        (128 slots of 8 picks over 192) this form stands at those bytes
        (PERF.md section 6, PR 27: pairs sorted by expert and
        ``jax.lax.ragged_dot`` read 3,683 tokens/s where this reads
        5,012). Where it names a few of them (32 slots of 4 picks over
        256: two in five) this form reads the rest for nothing, and
        :meth:`expert_form` gives the call to the kernel: by what its
        rows can name (PR 43) or, a decode step, by what its live rows
        do (:meth:`_apply_counted`, PR 54)."""
        w = (self.held_hits(ids) * gates[..., None]).sum(1)     # (T, count)
        def up(name):
            return jnp.einsum("te,cef->ctf", v, weights[name],
                              preferred_element_type=jnp.float32)

        if self.gated:
            h = self._gated(up("w_gate"), up("w_up"))
        else:
            h = jnp.square(jnp.maximum(up("w_up"), 0.0))
        h = (h * w.T[:, :, None]).astype(v.dtype)
        return jnp.einsum("ctf,cfe->te", h, weights["w_down"],
                          preferred_element_type=jnp.float32
                          ).astype(v.dtype)

    def _apply_grouped(self, weights, v, ids, gates):
        """The pairs (token, pick) sorted by held expert, each expert's
        first :meth:`capacity` rows gathered side by side into its tile,
        what an expert is named by beyond its tile into ``spill_tiles``
        more tiles (each with its expert's matrices gathered beside it),
        one batched product a matrix over the tiles, and each token
        taking its own pairs' rows back, weighted by its gates. The
        shapes and the work do not depend on the routing, however uneven.
        Nothing is dropped: where the spill tiles do not hold what
        overflows either, the call is the dense form's (a ``lax.cond``,
        so the result never depends on the form). ``jax.lax.ragged_dot``
        over the sorted pairs (XLA's grouped matmul on the TPU) was built
        first and read SLOWER than the dense form at every size: its
        static row count is the pairs a token CAN name, and every group
        costs it a 256-row tile (PERF.md section 6, PR 40)."""
        t, k = ids.shape
        cap, spill = self.capacity(t), self.spill_tiles
        local = ids - self.first
        held = (local >= 0) & (local < self.count)
        key = jnp.where(held, local, self.count).reshape(-1)     # (T k,)
        order = jnp.argsort(key)               # stable: a token's order
        starts = jnp.searchsorted(key[order], jnp.arange(
            self.count + 1, dtype=key.dtype))
        sizes = starts[1:] - starts[:-1]
        # tiles an expert needs beyond its own, and where its run of the
        # spill tiles begins
        extra = jnp.maximum(sizes - 1, 0) // cap
        spill_ends = jnp.cumsum(extra)
        spill_starts = spill_ends - extra

        def tiles():
            # spill tile j is chunk ``1 + j - spill_starts[e]`` of the
            # expert e whose run holds j (a tile past the runs repeats the
            # last expert's rows, which no token takes back)
            j = jnp.arange(spill)
            e_spill = jnp.minimum(jnp.searchsorted(spill_ends, j,
                                                   side="right"),
                                  self.count - 1)
            first = jnp.concatenate([
                starts[:-1],
                starts[e_spill] + (1 + j - spill_starts[e_spill]) * cap])
            # slot c of a tile is the pair at sorted place first + c; a
            # slot past its expert's pairs holds some other pair's row
            pair = order[jnp.minimum(first[:, None] + jnp.arange(cap),
                                     t * k - 1)]       # (count + spill, cap)
            rows = v[pair // k]

            def mlp(rows, pick):
                def up(name):
                    return jnp.einsum("...cv,...vf->...cf", rows,
                                      pick(weights[name]),
                                      preferred_element_type=jnp.float32)

                if self.gated:
                    h = self._gated(up("w_gate"), up("w_up"))
                else:
                    h = jnp.square(jnp.maximum(up("w_up"), 0.0))
                return jnp.einsum("...cf,...fv->...cv", h.astype(v.dtype),
                                  pick(weights["w_down"]),
                                  preferred_element_type=jnp.float32)

            def spilled(tile):
                # a tile at a time, its expert's matrices sliced where
                # they lie (a gather of eight experts' matrices reads all
                # of them: 4 ms a call at 128 held)
                rows, e = tile
                return mlp(rows, lambda w: jax.lax.dynamic_index_in_dim(
                    w, e, 0, keepdims=False))

            y = jnp.concatenate([
                mlp(rows[:self.count], lambda w: w),
                jax.lax.map(spilled, (rows[self.count:], e_spill))])
            y = y * gates.reshape(-1)[pair][..., None]
            # a pair's slot: by its place among its expert's pairs, in the
            # expert's own tile or in its run of the spill tiles
            e = jnp.clip(local, 0, self.count - 1)
            rank = jnp.argsort(order).reshape(t, k) - starts[e]
            tile = jnp.where(rank < cap, e,
                             self.count + spill_starts[e] + rank // cap - 1)
            mine = y.reshape((self.count + spill) * cap, -1)[
                jnp.where(held, tile * cap + rank % cap, 0)]
            return jnp.where(held[..., None], mine, 0.0).sum(1).astype(
                v.dtype)

        return jax.lax.cond(spill_ends[-1] <= spill, tiles,
                            lambda: self._apply_dense(weights, v, ids, gates))

    def _apply_counted(self, weights, v, ids, gates):
        """One of two forms, chosen by the call itself: it counts the
        held experts its pairs name (:meth:`held_hits`, any over the
        pairs: a few thousand bools beside the matrices), and up to
        :meth:`kernel_limit` of them the kernel reads those experts'
        matrices alone, beyond it the dense form reads all. A
        ``lax.cond`` over the same ``ids`` and ``gates``: a row's sum is
        over the same pairs either way, to the rounding the two forms
        differ by. Returns (the sum, the rows the products ran over, 1
        where the kernel ran else 0), the last two () uint32."""
        few = self.held_hits(ids).any((0, 1)).sum() <= self.kernel_limit()
        matrices = _expert_matrices(weights)
        y, rows = jax.lax.cond(
            few, lambda: kernel_form(self, matrices, v, ids, gates),
            lambda: (self._apply_dense(matrices, v, ids, gates),
                     jnp.uint32(self.count * v.shape[0])))
        return y, rows, few.astype(jnp.uint32)

    def apply(self, weights, x2d, ids, gates, computed=None, mesh=None,
              active=None):
        """The held experts' part of the layer for the routing given:
        (T, E) in the activations' dtype, by the form
        :meth:`expert_form` names for these rows in a program over
        ``mesh``; with ``latent``, between the projection down and the
        projection up. ``active`` (T,) bool comes from a caller that
        knows which rows are live (a decode step's slots): where the form
        reads by the routing (the kernel, a counted call) a row that is
        not names no expert, and what it gets back is read by no one.
        With ``computed`` a list, the rows the products
        ran over are appended to it, () uint32: counted on the device by
        the kernel (real tiles x tile rows), :meth:`rows_computed` for
        the jnp forms; a counted call appends behind them 1 where it was
        the kernel's and 0 where the dense form's."""
        v = x2d
        if self.latent:
            with sub_scope("latent"):
                v = jnp.dot(x2d, weights["latent_down"],
                            preferred_element_type=jnp.float32
                            ).astype(x2d.dtype)
        with sub_scope("experts"):
            live = active is not None
            form = self.expert_form(v.shape[0], v.dtype, mesh, live)
            if live and form in ("kernel", "counted"):
                ids = jnp.where(active[:, None], ids, self.first - 1)
            if form == "counted":
                y, *rows = self._apply_counted(weights, v, ids, gates)
            elif form == "kernel":
                y, *rows = kernel_form(self, _expert_matrices(weights), v,
                                       ids, gates)
            else:
                y = getattr(self, f"_apply_{form}")(weights, v, ids, gates)
                rows = [jnp.uint32(self.rows_computed(v.shape[0], v.dtype,
                                                      mesh, live))]
        if computed is not None:
            computed += rows
        if self.latent:
            with sub_scope("latent"):
                y = jnp.dot(y, weights["latent_up"],
                            preferred_element_type=jnp.float32
                            ).astype(x2d.dtype)
        return y

    def forward(self, ctx, inputs, weights):
        x, *prev = inputs
        x2d = x.reshape(-1, x.shape[-1])
        ids, gates, state = self.route(weights, x2d, None, *prev)
        return self.outputs(x, self.apply(
            weights, x2d, ids, gates, mesh=getattr(ctx, "mesh", None)), state)

    def outputs(self, x, y, state):
        """The op's outputs, shaped as its input ``x`` (..., E) is: the
        held experts' part ``y`` (T, E) and, from an MLP router, its
        ``state`` (T, router_width)."""
        outs = [y.reshape(x.shape)]
        if state is not None:
            outs.append(state.reshape(x.shape[:-1] + (-1,)))
        return outs

    def flops(self) -> float:
        t = 1
        for s in self.input_shapes[0].sizes[:-1]:
            t *= s
        mats = 3.0 if self.gated else 2.0
        # what apply() computes for these rows, by its form (the kernel's
        # follow the routing: an even one's pairs, and half a tile left
        # empty an expert it names)
        rows = self.rows_computed(t)
        if rows is None:
            from ..kernels.grouped_experts import tile_rows

            rows = (t * self.k * self.count // self.n_routed
                    + int(self.count * self.named_share(t))
                    * tile_rows(t) // 2)
        r = self.router_width
        router = (self.in_dim * self.n_routed if self.router != "mlp"
                  else self.in_dim * r + 2 * r * r + r * self.n_routed)
        return (2.0 * t * router
                + 4.0 * t * self.in_dim * self.latent
                + 2.0 * mats * rows * self.work_dim * self.width)
