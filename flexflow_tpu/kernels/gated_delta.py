"""The gated delta rule's decode step as a Pallas TPU kernel: every
active slot's state read once, updated, and written back in place.

A gated-delta-rule op (ops/gated_delta.py ``GatedDeltaNet``) keeps, for a
request, one float32 state ``S`` of ``(d_k, d_v)`` a head. The serving
pool stores it as one row of a ``(rows, d_k, H * d_v)`` arena: the keys'
axis on the sublanes and all heads' values side by side on the lanes, so
that the tiles pad nothing (30 heads of 192 values are 5,760 = 45 x 128
lanes; ``d_v = 192`` alone on the lanes would pad a third) and a row is
one contiguous DMA. A decode step does, per slot::

    S <- alpha S;  u = beta (v - S^T k);  S <- S + k u^T;  o = S^T q

which reads ``S`` once and writes it once: 2.2 MB in and out a slot and
layer at the published widths, and a few multiply-adds a number, so the
memory bounds it. The kernel:

* takes the slots' arena rows by scalar prefetch; grid step ``i`` is slot
  ``i``, its block the arena row ``rows[i]`` on the way in and, aliased
  (``input_output_aliases``), on the way out: the donated arena is
  updated in place and rows no slot names are not touched. Idle slots
  name row 0, the null row, and update that;
* walks the row in groups of heads that fill whole lane tiles (one head
  where ``d_v`` is a multiple of 128, two where it is an odd multiple of
  64). ``S^T k`` and ``S^T q`` are sums down the sublanes of ``S`` times
  the head's key (query) spread along its lanes, ``k u^T`` the outer
  product the same way: elementwise work on (d_k, 128) tiles, float32
  throughout, no matrix unit (a product of one row against ``S`` would
  load every tile of ``S`` as a weight for one row of work).

:func:`gated_delta_step` is the jnp form (:func:`delta_rule_step` over
gathered rows, scattered back): the kernel's reference, and what runs
where :func:`supported` refuses.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_mode
from .flash_attention import VMEM_BUDGET_BYTES, VMEM_LIMIT_BYTES
from .moe_kernels import SMEM_BUDGET_BYTES

LANES = 128


def _group(value_dim: int) -> int:
    """Heads a group holds so that it fills whole lane tiles; 0 where no
    such group is built."""
    if value_dim % LANES == 0:
        return 1
    return 2 if value_dim % (LANES // 2) == 0 else 0


def supported(slots: int, heads: int, key_dim: int, value_dim: int,
              arena_shape, arena_dtype) -> bool:
    """Whether the kernel takes this call: a float32 arena ``(rows, d_k,
    H d_v)`` whose heads group into whole lane tiles and whose keys fill
    whole sublane tiles, rows that fit SMEM, a working set (the row twice
    in and twice out) within the VMEM budget."""
    if pallas_mode() is None:
        return False
    group = _group(value_dim)
    if jnp.dtype(arena_dtype) != jnp.dtype(jnp.float32):
        return False
    if tuple(arena_shape[1:]) != (key_dim, heads * value_dim):
        return False
    if group == 0 or heads % group or key_dim % 8:
        return False
    if 4 * slots > SMEM_BUDGET_BYTES:
        return False
    return 4 * 4 * key_dim * heads * value_dim <= VMEM_BUDGET_BYTES


def _kernel(rows_ref, qt_ref, kt_ref, vab_ref, s_ref, s_out, o_ref,
            *, heads, value_dim, group):
    del rows_ref                      # the index maps read it
    dk = s_ref.shape[0]
    width = group * value_dim         # lanes of a group of heads
    lane = jax.lax.broadcasted_iota(jnp.int32, (dk, LANES), 1)
    qt, kt = qt_ref[...], kt_ref[...]                         # (d_k, H)

    def spread(cols, first, tile):
        """Lane tile ``tile`` of a group whose first head is ``first``:
        each head's column of ``cols`` (d_k, H) along that head's lanes."""
        start = tile * LANES
        h0, h1 = start // value_dim, (start + LANES - 1) // value_dim
        a = jnp.broadcast_to(cols[:, first + h0:first + h0 + 1], (dk, LANES))
        if h0 == h1:
            return a
        b = jnp.broadcast_to(cols[:, first + h1:first + h1 + 1], (dk, LANES))
        return jnp.where(lane < h1 * value_dim - start, a, b)

    for grp in range(heads // group):
        first = grp * group
        for tile in range(width // LANES):
            at = pl.ds(grp * width + tile * LANES, LANES)
            kx = spread(kt, first, tile)
            v, alpha, beta = (vab_ref[j:j + 1, at] for j in range(3))
            s = s_ref[:, at] * alpha
            u = beta * (v - jnp.sum(s * kx, axis=0, keepdims=True))
            s = s + kx * u
            s_out[:, at] = s
            o_ref[:, at] = jnp.sum(s * spread(qt, first, tile), axis=0,
                                   keepdims=True)


@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def _gated_delta(arena, rows, qt, kt, vab, *, heads, interpret):
    n, dk, _ = qt.shape
    width = arena.shape[-1]
    value_dim = width // heads
    per_slot = lambda *tail: pl.BlockSpec(  # noqa: E731
        (None,) + tail, lambda i, rows: (i,) + (0,) * len(tail))
    row = pl.BlockSpec((None, dk, width), lambda i, rows: (rows[i], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[per_slot(dk, heads), per_slot(dk, heads),
                  per_slot(3, width), row],
        out_specs=[row, per_slot(1, width)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, heads=heads, value_dim=value_dim,
                          group=_group(value_dim)),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(arena.shape, arena.dtype),
                   jax.ShapeDtypeStruct((n, 1, width), jnp.float32)],
        # operand 4 (after the prefetched rows): the arena, in place
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="gated_delta_decode",
    )(rows.astype(jnp.int32), qt, kt, vab, arena)


def gated_delta_decode(arena, rows, q, k, v, alpha, beta):
    """One token a slot through the states in ``arena`` (rows, d_k, H
    d_v) float32, donated. ``rows`` (N,) int32 each slot's arena row;
    ``q``, ``k`` (N, H, d_k), ``v`` (N, H, d_v), ``alpha``, ``beta`` (N,
    H), float32. Returns (o (N, H, d_v) float32, the arena with those
    rows updated). Callers check :func:`supported` first. The kernel's
    call is jitted on its own, so that the layers of a model trace it
    once."""
    n, heads, _ = q.shape
    value_dim = v.shape[-1]
    f32 = jnp.float32
    lanes = lambda a: jnp.repeat(a.astype(f32), value_dim, axis=-1)  # noqa: E731
    vab = jnp.stack([v.astype(f32).reshape(n, -1), lanes(alpha),
                     lanes(beta)], axis=1)                    # (N, 3, H d_v)
    arena, o = _gated_delta(
        arena, rows, q.astype(f32).transpose(0, 2, 1),
        k.astype(f32).transpose(0, 2, 1), vab, heads=heads,
        interpret=pallas_mode() == "interpret")
    return o.reshape(n, heads, value_dim), arena


def delta_rule_step(state, q, k, v, alpha, beta):
    """The step in jnp, one token a row. ``state`` (N, d_k, H d_v)
    float32, laid out as an arena row; the rest as
    :func:`gated_delta_decode` takes them. Returns (o (N, H, d_v), the new
    state)."""
    n, h, dk = q.shape
    hi = jax.lax.Precision.HIGHEST
    st = state.reshape(n, dk, h, -1) * alpha[:, None, :, None]
    u = beta[..., None] * (v - jnp.einsum("ndhv,nhd->nhv", st, k,
                                          precision=hi))
    st = st + k.transpose(0, 2, 1)[..., None] * u[:, None]
    o = jnp.einsum("ndhv,nhd->nhv", st, q, precision=hi)
    return o, st.reshape(state.shape)


def gated_delta_step(arena, rows, q, k, v, alpha, beta):
    """:func:`gated_delta_decode` in jnp: the slots' rows gathered,
    :func:`delta_rule_step`, the rows scattered back (idle slots all name
    the null row; whichever lands there is as good as another)."""
    o, new = delta_rule_step(arena[rows], q, k, v, alpha, beta)
    return o, arena.at[rows].set(new)


__all__ = ["delta_rule_step", "gated_delta_decode", "gated_delta_step",
           "supported"]
