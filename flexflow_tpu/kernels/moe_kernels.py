"""MoE dispatch/combine data movement as Pallas TPU kernels.

The reference moves rows with data-dependent CUDA scatter kernels
(reference: src/ops/group_by.cu ``gb_forward_kernel``, src/ops/aggregate.cu
``agg_forward_kernel``). Under XLA's static-shape SPMD the framework's jnp
fallback (ops/moe_ops.py) expresses the same movement as one-hot einsums,
which costs O(T·n·capacity·d) MXU FLOPs for what is really a copy. These
kernels do the copy as a copy:

* :func:`row_gather` — ``out[i] = scale[i] * x[idx[i]]``. The row index is
  a scalar-prefetch operand, so each grid step's BlockSpec ``index_map``
  DMAs exactly the needed source row HBM→VMEM (the Pallas scalar-prefetch
  gather pattern).
* :func:`row_gather_sum` — ``out[b] = Σ_j w[b,j] · x[idx[b,j]]``,
  accumulated in VMEM scratch across the (sequential) TPU grid's inner
  dimension; realizes the gate-weighted combine and every backward pass of
  dispatch/combine.

Routing (cumsum ranking to fixed ``capacity`` slots, matching the
reference's ``alpha``-capacity semantics, group_by.cc:143) stays in jnp —
it is O(T·n) integer work that XLA handles well; only the O(T·d) row
movement goes through Pallas.

:func:`moe_dispatch` / :func:`moe_combine` wrap both with custom VJPs and
are the entry points used by ops/moe_ops.py.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_mode


# One v5e core has 1 MiB of SMEM, and Mosaic refuses a program whose
# scalar-prefetch operands do not fit it. Every kernel call here prefetches
# two 4-byte-per-element operands, one element per pick (tokens * k) or
# per slot (experts * capacity); the budget leaves a quarter to the
# compiler.
SMEM_BUDGET_BYTES = 768 * 1024


def supported(tokens: int, k: int, n: int, capacity: int) -> bool:
    """Whether :func:`moe_dispatch` / :func:`moe_combine` and their
    backward passes compile at this routing shape: the larger of the
    per-pick and per-slot operand pairs must fit the SMEM budget. Callers
    fall back to the one-hot einsum formulation (ops/moe_ops.py)."""
    return 2 * 4 * max(tokens * k, n * capacity) <= SMEM_BUDGET_BYTES


def _row_gather_kernel(idx_ref, scale_ref, x_ref, out_ref):
    i = pl.program_id(0)
    out_ref[...] = (scale_ref[i] * x_ref[...].astype(jnp.float32)
                    ).astype(out_ref.dtype)


def row_gather(x: jax.Array, idx: jax.Array, scale: jax.Array,
               interpret: bool = False) -> jax.Array:
    """out[i, :] = scale[i] * x[idx[i], :]  (idx int32, scale float32).

    Rows travel as (R, 1, d) so each (1, 1, d) block's trailing dims always
    satisfy the TPU (8, 128) tiling rule (a (1, d) block would not when
    R > 1).
    """
    r_out = idx.shape[0]
    d = x.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(r_out,),
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda i, idx_ref, scale_ref: (idx_ref[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, d), lambda i, idx_ref, scale_ref: (i, 0, 0)),
    )
    out = pl.pallas_call(
        _row_gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r_out, 1, d), x.dtype),
        interpret=interpret,
        name="moe_row_gather",
    )(idx.astype(jnp.int32), scale.astype(jnp.float32), x[:, None, :])
    return out[:, 0, :]


def _row_gather_sum_kernel(idx_ref, w_ref, x_ref, out_ref, acc_ref):
    b, j = pl.program_id(0), pl.program_id(1)
    k = pl.num_programs(1)

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += w_ref[b * k + j] * x_ref[0].astype(jnp.float32)  # (1, d)

    @pl.when(j == k - 1)
    def _():
        out_ref[0] = acc_ref[...].astype(out_ref.dtype)


def row_gather_sum(x: jax.Array, idx: jax.Array, w: jax.Array,
                   interpret: bool = False) -> jax.Array:
    """out[b, :] = sum_j w[b, j] * x[idx[b, j], :]   (idx: (B, k) int32).

    Same (R, 1, d) layout trick as :func:`row_gather`. The scalar-prefetch
    operands go to SMEM flattened to (B*k,): SMEM pads the last dim of a
    2-D array to 128 words, so a (B, k) operand would take B*512 bytes
    and stop fitting at a few thousand tokens.
    """
    bsz, k = idx.shape
    d = x.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bsz, k),
        in_specs=[
            pl.BlockSpec((1, 1, d),
                         lambda b, j, idx_ref, w_ref: (idx_ref[b * k + j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, d), lambda b, j, idx_ref, w_ref: (b, 0, 0)),
        scratch_shapes=[pltpu.VMEM((1, d), jnp.float32)],
    )
    out = pl.pallas_call(
        _row_gather_sum_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, 1, d), x.dtype),
        interpret=interpret,
        name="moe_row_gather_sum",
    )(idx.astype(jnp.int32).reshape(-1), w.astype(jnp.float32).reshape(-1),
      x[:, None, :])
    return out[:, 0, :]


def compute_routing(assign: jax.Array, n: int, capacity: int
                    ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Capacity routing shared by dispatch and combine.

    ``assign``: (B, k) int expert ids. Returns
      slot   (B, k) int32 — flat slot ``e*capacity + pos`` per token pick
                            (clamped to 0 when dropped),
      keep   (B, k) f32   — 1 iff the pick ranked under capacity,
      src    (n·capacity,) int32 — source *batch row* feeding each slot
                            (0 for empty slots),
      valid  (n·capacity,) f32 — 1 iff the slot is fed.
    """
    bsz, k = assign.shape
    flat = assign.reshape(-1).astype(jnp.int32)                 # (T,)
    onehot = jax.nn.one_hot(flat, n, dtype=jnp.int32)           # (T, n)
    pos = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=1)
    keep = pos < capacity
    slot = jnp.where(keep, flat * capacity + pos, 0)
    tokens = jnp.arange(bsz * k, dtype=jnp.int32)
    src = jnp.zeros((n * capacity,), jnp.int32).at[
        jnp.where(keep, slot, n * capacity)].set(tokens // k, mode="drop")
    valid = jnp.zeros((n * capacity,), jnp.float32).at[
        jnp.where(keep, slot, n * capacity)].set(1.0, mode="drop")
    return (slot.reshape(bsz, k).astype(jnp.int32),
            keep.reshape(bsz, k).astype(jnp.float32), src, valid)


def _zero_ct(x):
    """Zero cotangent: float0 for integer primals (custom_vjp contract)."""
    if jnp.issubdtype(x.dtype, jnp.floating):
        return jnp.zeros_like(x)
    return np.zeros(x.shape, jax.dtypes.float0)


@jax.custom_vjp
def _dispatch(x2d, slot, keep, src, valid):
    interp = pallas_mode() == "interpret"
    return row_gather(x2d, src, valid, interpret=interp)


def _dispatch_fwd(x2d, slot, keep, src, valid):
    return _dispatch(x2d, slot, keep, src, valid), (slot, keep, src, valid)


def _dispatch_bwd(res, g):
    slot, keep, src, valid = res
    interp = pallas_mode() == "interpret"
    # dx[b] = Σ_j keep[b,j] · g_rows[slot[b,j]]
    dx = row_gather_sum(g, slot, keep, interpret=interp)
    return dx, _zero_ct(slot), _zero_ct(keep), _zero_ct(src), _zero_ct(valid)


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


def _slot_to_pick(slot, keep, n_slots, valid):
    """Invert slot: for each slot s, the flat pick index (b·k+j) feeding it.

    Dropped picks carry a clamped slot of 0 (compute_routing) — scatter them
    out of bounds so they cannot clobber slot 0's true pick.
    """
    bsz, k = slot.shape
    picks = jnp.arange(bsz * k, dtype=jnp.int32)
    idx = jnp.where(keep.reshape(-1) > 0, slot.reshape(-1), n_slots)
    inv = jnp.zeros((n_slots,), jnp.int32).at[idx].set(picks, mode="drop")
    # empty slots hold a garbage pick; caller multiplies by `valid`
    return jnp.where(valid > 0, inv, 0)


@jax.custom_vjp
def _combine(rows2d, w, slot, keep, src, valid):
    interp = pallas_mode() == "interpret"
    return row_gather_sum(rows2d, slot, w * keep, interpret=interp)


def _combine_fwd(rows2d, w, slot, keep, src, valid):
    out = _combine(rows2d, w, slot, keep, src, valid)
    return out, (rows2d, w, slot, keep, src, valid)


def _combine_bwd(res, g):
    rows2d, w, slot, keep, src, valid = res
    interp = pallas_mode() == "interpret"
    # drows[s] = valid[s] · w_at[s] · g[src[s]]
    pick = _slot_to_pick(slot, keep, src.shape[0], valid)
    w_at_slot = (w * keep).reshape(-1)[pick]
    drows = row_gather(g, src, valid * w_at_slot, interpret=interp)
    # dw[b,j] = keep[b,j] · ⟨g[b], rows[slot[b,j]]⟩
    bsz, k = slot.shape
    picked = row_gather(rows2d, slot.reshape(-1), keep.reshape(-1),
                        interpret=interp)
    dw = jnp.einsum("bkd,bd->bk", picked.reshape(bsz, k, -1), g)
    return (drows, dw, _zero_ct(slot), _zero_ct(keep),
            _zero_ct(src), _zero_ct(valid))


_combine.defvjp(_combine_fwd, _combine_bwd)


def moe_dispatch(x: jax.Array, assign: jax.Array, n: int, capacity: int
                 ) -> jax.Array:
    """Scatter batch rows into (n, capacity, d) expert tensors (GroupBy).

    Differentiable wrt ``x``; dropped picks get zero rows, matching the
    reference's zero-initialized fixed-capacity expert tensors.
    """
    bsz = x.shape[0]
    x2d = x.reshape(bsz, -1)
    slot, keep, src, valid = compute_routing(assign, n, capacity)
    rows = _dispatch(x2d, slot, keep, src, valid)
    return rows.reshape((n, capacity) + x.shape[1:])


def moe_combine(expert_rows: jax.Array, assign: jax.Array, gate_w: jax.Array
                ) -> jax.Array:
    """Gate-weighted combine of (n, capacity, d) expert outputs (Aggregate).

    Differentiable wrt ``expert_rows`` and ``gate_w`` (shape (B, k)).
    """
    n, capacity = expert_rows.shape[0], expert_rows.shape[1]
    rows2d = expert_rows.reshape(n * capacity, -1)
    slot, keep, src, valid = compute_routing(assign, n, capacity)
    return _combine(rows2d, gate_w, slot, keep, src, valid)
