"""The grouped form of a routed-experts layer as one Pallas TPU kernel.

``RoutedExperts`` (ops/moe_ops.py) computes, for the rows of a call, only
the (token, pick) pairs whose expert it holds. Its jnp grouped form
sorts the pairs, gathers their rows into fixed tiles in HBM, multiplies
the tiles, and gathers every pair's row back: the products are a third of
its time (PERF.md section 6, PR 40 and PR 41). Here the routing names the
tiles and everything else happens where the rows are:

* outside the kernel, small and in XLA (:func:`tile_table`): the pairs
  sorted by held expert, pairs of experts not held last; an expert named
  by ``n`` pairs gets ``ceil(n / tile)`` row tiles of its own, one after
  another, an expert named by none gets none, and its matrices are not
  read. A tile is (its expert, its first sorted place, how many of its
  rows are real). The grid is static at the worst case
  (:func:`grid_tiles`); a tile past the last real one repeats the block
  before it (no DMA) and multiplies nothing (``pl.when``). No capacity,
  so nothing overflows and nothing is dropped however uneven the
  routing;
* the tiles follow the call's rows (:func:`tile_rows`): 128 rows for a
  prefill's bucket or chunk, ``ceil(pairs / 128) + count`` tiles; for a
  call of no more rows than that (a decode step's slots, the one row
  behind a head's cut) a tile of the call's own rows in whole bfloat16
  sublane tiles of 16, which holds all of any expert's pairs since no
  token names an expert twice: ``count`` tiles and no more, of which the
  named experts' are real. That is what lets a step that names two in
  five of the experts it holds read those alone (PERF.md section 6,
  PR 43);
* an expert's matrices are blocks of ``w_up`` / ``w_gate`` / ``w_down``
  where they lie, chosen by the tile's expert through the scalar-prefetch
  index map: consecutive tiles of one expert reuse the resident block,
  the next expert's arrives behind the current tile's products. Where an
  expert's matrices do not fit VMEM whole they are cut along ``width``
  (:func:`plan`): the up products of a cut and its share of the down
  product, summed over the cuts in VMEM;
* the rows ``v`` stay in VMEM for the call and a tile's rows are COPIED
  from it, one row a pair, by the prefetched token index; a tile's
  results are ADDED, row by row and weighted by the pair's gate, into the
  ``(rows, work_dim)`` float32 output, which stays in VMEM until the last
  tile. Copies and adds, not one-hot products: a row's result depends on
  that row alone, so a NaN in one token's row reaches no other token's
  (a one-hot product would spread it: 0 x NaN). Mosaic addresses single
  rows of 32-bit arrays only, so the bfloat16 rows travel as pairs of
  columns ``c`` and ``c + work_dim / 2`` in one uint32 (packed outside,
  one elementwise pass over ``v``; unpacked on the tile, exactly).

bfloat16 operands, float32 accumulation, the activation and the gate's
weight in float32: the mathematics of ``RoutedExperts._apply_grouped``
and ``_apply_dense``, which are this kernel's references and take what
:func:`supported` refuses: rows other than bfloat16, widths of no whole
lane tiles, 8 rows or more that are no whole sublane tiles (fewer are
padded to one, by rows whose picks name no held expert), tables past the
scalar memory, rows past the fast memory.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_mode
from .moe_kernels import SMEM_BUDGET_BYTES

# rows of a tile at the most: one pass of the chip's 128 x 128 matrix unit;
# an expert named by fewer multiplies a tile all the same
TILE_ROWS = 128
# a v5e core has 128 MiB of VMEM; the call is given most of it, and plans
# its blocks to a budget below that (the compiler's own temporaries)
VMEM_LIMIT_BYTES = 100 * 1024 * 1024
VMEM_BUDGET_BYTES = 88 * 1024 * 1024
# rows of a 32-bit sublane tile: what the packed rows and the output come in
SUBLANES = 8


def tile_rows(rows: int) -> int:
    """Rows of a tile for a call of ``rows`` tokens: ``TILE_ROWS``, and
    for a call of no more than that its own rows in whole bfloat16
    sublane tiles of 16. No token names an expert twice, so such a tile
    holds all the pairs of any one expert."""
    return min(TILE_ROWS, -(-rows // (2 * SUBLANES)) * 2 * SUBLANES)


def grid_tiles(rows: int, picks: int, count: int) -> int:
    """The tiles a call is laid out for, the worst routing's: every
    expert's last tile part empty, ``ceil(pairs / tile) + count``, and no
    more than ``ceil(rows / tile)`` an expert, which a token names once
    at the most: ``count`` tiles for a call of one tile's rows."""
    tile = tile_rows(rows)
    return min(-(-rows * picks // tile) + count, count * -(-rows // tile))


def _vmem_bytes(rows: int, work_dim: int, cut: int, gated: bool) -> int:
    """The call's VMEM as it is allocated: the packed rows and the
    float32 output whole, once; each weight block twice (the pipeline's
    two buffers); a tile's packed rows, unpacked rows and results; and
    the float32 temporaries of one step (the up products, the activation,
    the down product)."""
    mats = 3 if gated else 2
    tile = tile_rows(rows)
    return (2 * rows * work_dim + 4 * rows * work_dim
            + 2 * 2 * mats * work_dim * cut
            + tile * work_dim * (2 + 2 + 4)
            + 4 * tile * (mats * cut + work_dim))


def plan(rows: int, picks: int, work_dim: int, width: int, count: int,
         gated: bool, dtype) -> Optional[int]:
    """The columns of ``width`` a weight block of :func:`grouped_experts`
    holds for ``rows`` tokens of ``picks`` experts each (``width`` itself
    where an expert's matrices fit whole), or None where it cannot run
    them: rows other than
    bfloat16 (a row is copied as 32-bit pairs of columns), a ``work_dim``
    whose half is no whole lane tiles, a ``width`` of no whole lane tiles,
    8 rows or more that are no whole sublane tiles (fewer are padded to
    one: :func:`grouped_experts`), a routing whose sorted pairs and tile
    table do not fit SMEM, or rows and output that leave VMEM no room for
    a weight block of 128 columns. From shapes alone."""
    if jnp.dtype(dtype) != jnp.dtype(jnp.bfloat16):
        return None
    rows = max(rows, SUBLANES)
    if work_dim % 256 or width % 128 or rows % SUBLANES or count < 1:
        return None
    pairs = rows * picks
    if (count + 1) << max(1, (pairs - 1).bit_length()) >= 1 << 31:
        return None                  # tile_table's sort key is one int32
    if 4 * (2 * pairs + 3 * grid_tiles(rows, picks, count) + 1) > (
            SMEM_BUDGET_BYTES):
        return None
    lanes = width // 128
    for blocks in range(1, lanes + 1):
        if lanes % blocks:
            continue
        cut = width // blocks
        if _vmem_bytes(rows, work_dim, cut, gated) <= VMEM_BUDGET_BYTES:
            return cut
    return None


def supported(rows: int, picks: int, work_dim: int, width: int, count: int,
              gated: bool, dtype) -> bool:
    """Whether the kernel takes this call (Pallas on, and :func:`plan`
    finds blocks for these shapes). Callers take the jnp grouped form
    where it does not."""
    return pallas_mode() is not None and plan(
        rows, picks, work_dim, width, count, gated, dtype) is not None


def tile_table(ids, first: int, count: int):
    """``ids`` (T, k) int32 -> what names the kernel's tiles, for the held
    experts ``first .. first + count - 1``:

    * ``order`` (T k,) int32: the pairs' flat indices sorted by held
      expert (and by index within one: a token's own order), pairs of
      experts not held last;
    * ``expert``, ``place``, ``real`` (tiles,) int32 for ``tiles =``
      :func:`grid_tiles`, of :func:`tile_rows` rows each: a tile's held
      expert, the sorted place of its first row, and how many of its rows
      are pairs (0 for a tile past the last real one, which names the last
      real tile's expert);
    * ``tiles_real`` () int32."""
    t, k = ids.shape
    tile = tile_rows(t)
    local = ids - first
    held = (local >= 0) & (local < count)
    # one unstable sort of one operand, the key above the pair's index (the
    # chip's compiler takes a quarter of a minute over a stable sort or an
    # ``argsort`` of this many, and two seconds over this)
    bits = max(1, (t * k - 1).bit_length())
    both = jax.lax.sort(
        (jnp.where(held, local, count).reshape(-1) << bits)
        | jnp.arange(t * k, dtype=jnp.int32), is_stable=False)
    order = both & ((1 << bits) - 1)
    # (every search below compares all: a few thousand by a hundred, and
    # no ``while`` in the program)
    starts = jnp.searchsorted(
        both >> bits, jnp.arange(count + 1, dtype=jnp.int32),
        method="compare_all").astype(jnp.int32)
    sizes = starts[1:] - starts[:-1]
    per = -(-sizes // tile)                           # an expert's tiles
    ends = jnp.cumsum(per)
    tiles_real = ends[-1]
    j = jnp.arange(grid_tiles(t, k, count), dtype=jnp.int32)
    at = jnp.minimum(j, jnp.maximum(tiles_real - 1, 0))
    expert = jnp.minimum(
        jnp.searchsorted(ends, at, side="right", method="compare_all"),
        count - 1).astype(jnp.int32)
    chunk = at - (ends - per)[expert]              # which of its expert's
    real = jnp.where(j < tiles_real,
                     jnp.clip(sizes[expert] - chunk * tile, 0, tile), 0)
    return (order, expert, starts[expert] + chunk * tile,
            real.astype(jnp.int32), tiles_real.astype(jnp.int32))


def _kernel(expert_ref, place_ref, real_ref, tiles_ref, token_ref, gate_ref,
            packed_ref, *refs, gated: bool, cuts: int, limit=None):
    if gated:
        w_gate_ref, w_up_ref, w_down_ref, out_ref, rows_u32, rows, res = refs
    else:
        w_up_ref, w_down_ref, out_ref, rows_u32, rows, res = refs
    i, j = pl.program_id(0), pl.program_id(1)
    n, place = real_ref[i], place_ref[i]

    @pl.when((i == 0) & (j == 0))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(n > 0)
    def _():
        @pl.when(j == 0)
        def _():
            def take(r, carry):
                rows_u32[pl.ds(r, 1), :] = packed_ref[
                    pl.ds(token_ref[place + r], 1), :]
                return carry

            jax.lax.fori_loop(0, n, take, None)
            # a uint32 is columns c (low half) and c + work_dim / 2 of a
            # bfloat16 row: each half widened to the float32 of the same
            # value, then narrowed again, exactly. Slots past the tile's
            # pairs keep whatever they held: rows are independent and no
            # token takes those back.
            u = rows_u32[...]
            low = jax.lax.bitcast_convert_type(u << 16, jnp.float32)
            high = jax.lax.bitcast_convert_type(
                u & jnp.uint32(0xFFFF0000), jnp.float32)
            rows[...] = jnp.concatenate([low, high], axis=1).astype(rows.dtype)

        x = rows[...]

        def up(ref):
            return jnp.dot(x, ref[...], preferred_element_type=jnp.float32)

        if gated:
            g, u = up(w_gate_ref), up(w_up_ref)
            if limit is not None:      # compiled out where there is none
                g, u = jnp.minimum(g, limit), jnp.clip(u, -limit, limit)
            h = jax.nn.silu(g) * u
        else:
            h = jnp.square(jnp.maximum(up(w_up_ref), 0.0))
        y = jnp.dot(h.astype(x.dtype), w_down_ref[...],
                    preferred_element_type=jnp.float32)
        if cuts == 1:
            res[...] = y
        else:
            @pl.when(j == 0)
            def _():
                res[...] = y

            @pl.when(j > 0)
            def _():
                res[...] += y

        @pl.when(j == cuts - 1)
        def _():
            def give(r, carry):
                at = pl.ds(token_ref[place + r], 1)
                out_ref[at, :] = out_ref[at, :] + (
                    gate_ref[place + r] * res[pl.ds(r, 1), :])
                return carry

            jax.lax.fori_loop(0, n, give, None)


@functools.partial(jax.jit, static_argnames=(
    "first", "gated", "cut", "interpret", "limit"))
def _grouped_experts(v, ids, gates, w_gate, w_up, w_down, *, first, gated,
                     cut, interpret, limit=None):
    t, work_dim = v.shape
    count, _, width = w_up.shape
    cuts = width // cut
    half = work_dim // 2
    tile = tile_rows(t)
    order, expert, place, real, tiles_real = tile_table(ids, first, count)
    bits = jax.lax.bitcast_convert_type(v, jnp.uint16).astype(jnp.uint32)
    packed = bits[:, :half] | (bits[:, half:] << 16)

    def up_block(i, j, expert, place, real, tiles, token, gate):
        # a tile past the last real one: the block before it, again
        return expert[i], 0, jnp.where(i < tiles[0], j, cuts - 1)

    def down_block(i, j, expert, place, real, tiles, token, gate):
        return expert[i], jnp.where(i < tiles[0], j, cuts - 1), 0

    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    up_spec = pl.BlockSpec((None, work_dim, cut), up_block)
    weights = ([w_gate] if gated else []) + [w_up, w_down]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(expert.shape[0], cuts),
        in_specs=([whole] + [up_spec] * (len(weights) - 1)
                  + [pl.BlockSpec((None, cut, work_dim), down_block)]),
        out_specs=whole,
        scratch_shapes=[pltpu.VMEM((tile, half), jnp.uint32),
                        pltpu.VMEM((tile, work_dim), v.dtype),
                        pltpu.VMEM((tile, work_dim), jnp.float32)])
    out = pl.pallas_call(
        functools.partial(_kernel, gated=gated, cuts=cuts, limit=limit),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, work_dim), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="grouped_experts",
    )(expert, place, real, tiles_real.reshape(1), order // ids.shape[1],
      gates.astype(jnp.float32).reshape(-1)[order], packed, *weights)
    return out.astype(v.dtype), (tiles_real * tile).astype(jnp.uint32)


def grouped_experts(v, ids, gates, weights, *, first: int, gated: bool,
                    limit: Optional[float] = None):
    """The held experts' weighted sum for the routing given; ``limit``
    (``gated`` only): the gate cut at it from above and the up-projection
    to ``[-limit, limit]`` before the product.

    ``v`` (T, work_dim) bfloat16; ``ids`` (T, k) int32 over all routed
    experts and ``gates`` (T, k) float32; ``weights`` the op's ``w_up``
    (count, work_dim, width), ``w_down`` (count, width, work_dim) and,
    ``gated``, ``w_gate``, of the experts ``first .. first + count - 1``.
    Returns ((T, work_dim) in ``v``'s dtype, the rows the products ran
    over: real tiles x tile rows, () uint32, counted here). Fewer rows
    than a sublane tile's (the one row behind a head's cut) are padded to
    it with rows whose picks name no held expert. Behind a ``jit`` of its
    own: a program of many expert layers lowers it once. Callers check
    :func:`supported` first."""
    w_up = weights["w_up"]
    t = v.shape[0]
    cut = plan(t, ids.shape[1], v.shape[1], w_up.shape[2], w_up.shape[0],
               gated, v.dtype)
    if cut is None:
        raise ValueError(f"grouped_experts cannot run rows {v.shape} "
                         f"{v.dtype} through experts {w_up.shape}")
    short = max(SUBLANES - t, 0)
    if short:
        pad = ((0, short), (0, 0))
        v, gates = jnp.pad(v, pad), jnp.pad(gates, pad)
        ids = jnp.pad(ids, pad, constant_values=first - 1)
    out, computed = _grouped_experts(
        v, ids, gates, weights.get("w_gate") if gated else None, w_up,
        weights["w_down"], first=first, gated=gated, cut=cut,
        interpret=pallas_mode() == "interpret", limit=limit)
    return (out[:t] if short else out), computed


__all__ = ["grid_tiles", "grouped_experts", "plan", "supported",
           "tile_rows", "tile_table"]
