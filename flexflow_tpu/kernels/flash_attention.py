"""Fused (flash-style) attention as Pallas TPU kernels, forward and backward.

Replaces the reference's cuDNN MultiHeadAttn device path
(reference: src/ops/attention.cu:35-128). No array with two sequence
axes is written to HBM: every kernel walks (query block, key block)
tiles of the scores, and what crosses a tile's edge is a running
softmax (forward) or the saved log-sum-exp (backward).

Layout. The kernels take q, k, v as ``(B, S, H*D)``: what a projection
``(B, S, E) x (E, H*D)`` writes and what the output projection reads,
so nothing is transposed through HBM on either side. A grid step owns
one *lane tile* of that last axis: ``W = max(128, D)`` lanes, which is
``G = W // D`` heads side by side (two heads of 64). A head's products
are taken over the whole tile with the other heads' lanes of K and V
zeroed: the MXU contracts over 128 lanes either way, so a head of 64
costs what it would alone, and q, o and the gradients stay whole tiles.

Every tile is computed *transposed*: keys on sublanes, queries on
lanes. What a softmax keeps for a query (running maximum, sum,
log-sum-exp, ``delta``) is then a lane-dense (1, block_q) row that
broadcasts along sublanes, and a reduction over keys is elementwise
over vregs with one short sublane reduce at its end: no cross-lane
reduction and no (block_q, 1) column anywhere (a first version with
queries on sublanes spent more on those than on its products: PERF.md
section 6, PR 31). Two kernels a step of training, by their
``pallas_call`` names, or three where the sequence is too long for the
backward to be one (:func:`backward_form`):

* ``flash_attention_fwd`` — grid (B, H/G, query blocks, key blocks), the
  key axis innermost; m, l and the transposed output accumulator
  (W, block_q) live in VMEM scratch across a query block's key blocks
  (the running softmax), so VMEM does not grow with the sequence. The
  accumulator is transposed back once a query block.
* ``flash_attention_bwd`` — grid (B, H/G, key blocks, query blocks), the
  query axis innermost. A tile's scores, exponentials and dS are made
  once and enter five products: dK and dV accumulate over a key
  block's query blocks, already the right way round, and dQ^T over the
  key blocks, which are the OUTER block axis: a (batch, lane tile)'s
  whole dQ^T stays in VMEM scratch, a (W, block_q) panel a query block,
  and each panel is transposed into the resident (Sq, W) output block
  at the last key block. That accumulator is the one thing that grows
  with the sequence (12 bytes a query and lane, counted with its output
  block), so the form is read from the shape: where it does not fit the
  VMEM budget the backward is the two kernels below, which rebuild
  each tile twice (seven products for five) and hold nothing whole.
* ``flash_attention_dq`` — the forward's grid; dQ^T accumulates over a
  query block's key blocks.
* ``flash_attention_dkv`` — the fused kernel's grid and body without
  dQ.

Precision: MXU operands in the dtype the op was given, float32
accumulation; scores, row maximum, sum, log-sum-exp and
``delta = rowsum(dO * O)`` in float32; P and dS are rounded to the
operand dtype only as they enter a product.

Causal attention (top-left aligned, as ``single_device_attention``'s
``tril``) skips the key blocks above the diagonal: the body under
``pl.when``, and the DMA by clamping the block index to the last block
needed (an unchanged index is not fetched again). Only blocks the
diagonal crosses pay for the mask.

The log-sum-exp and ``delta`` travel as ``(B, H, 1, S)`` float32: a
lane-dense row a head, as the kernels use them.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_mode

NEG_INF = -1e30  # large-negative instead of -inf: keeps exp()/max() NaN-free

# The scoped-VMEM limit handed to Mosaic for these kernels (a v5e core
# has 128 MiB; the compiler's default scope is 16 MiB), and the share of
# it supported() lets the counted working set take — the rest is room
# for what the count cannot see (compiler temporaries, relayouts).
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
VMEM_BUDGET_BYTES = 48 * 1024 * 1024

LANES = 128
# block sizes tried in this order (the first that divides the sequence);
# the chip's measurements behind the order: PERF.md section 6, PR 31
BLOCKS = (512, 256, 128)
# sequences from which the kernels beat XLA's softmax(QK^T)V forward and
# backward on a v5e (bf16, batch x heads 64, d 64 and 128: PERF.md
# section 6, PR 31); below it the (S, S) tile is small enough that XLA's
# fusions win and the `xla` path stays
MIN_SEQ = 1024

# dot_general dimension numbers for a @ b.T and a.T @ b: the MXU takes
# either operand transposed, so no kernel materializes a transpose
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


# -- a lane tile's heads ------------------------------------------------------

def _tile_width(heads: int, d: int) -> Optional[int]:
    """Lanes a grid step owns of the (B, S, H*D) operands: whole heads,
    whole 128-lane tiles, or the whole axis. None where no such width
    exists (a head that straddles tiles)."""
    if d % LANES == 0:
        return d
    if LANES % d == 0 and heads % (LANES // d) == 0:
        return LANES
    if heads * d < LANES:
        return heads * d
    return None


def _head_masks(width: int, d: int, axis: int):
    """For each head of a tile, the mask of its lanes (``axis`` 1: a
    (1, width) mask) or, for a transposed (width, n) array, of its rows
    (``axis`` 0: (width, 1)); None for a head that fills the tile."""
    if width == d:
        return [None]
    at = jax.lax.broadcasted_iota(
        jnp.int32, (1, width) if axis else (width, 1), axis)
    return [(at >= c * d) & (at < (c + 1) * d) for c in range(width // d)]


def _only(lanes, x):
    """``x`` with the other heads' lanes zeroed."""
    return x if lanes is None else jnp.where(lanes, x, jnp.zeros_like(x))


def _spread(rows, masks):
    """Per-head (1, n) rows -> one (width, n) array, each head's rows
    (``masks``: :func:`_head_masks` over axis 0) holding its own."""
    out = rows[0]
    for row, m in zip(rows[1:], masks[1:]):
        out = jnp.where(m, row, out)
    return out


def _prescale(q, scale: float):
    """``(q * scale, 1.0)`` where the product is exact in q's dtype (a
    power of two: heads of 16, 64, 256), so the scores need no pass of
    their own; else ``(q, scale)`` and the caller scales the float32
    scores, because rounding ``q * scale`` would be a second rounding
    of an operand."""
    if math.frexp(scale)[0] == 0.5:
        return q * jnp.asarray(scale, q.dtype), 1.0
    return q, scale


def _keep(keys: int, queries: int, q0, k0):
    """Causal mask of a (keys, queries) tile whose first query is ``q0``
    and first key ``k0``: True where the query sees the key."""
    r = jax.lax.broadcasted_iota(jnp.int32, (keys, queries), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (keys, queries), 1)
    return q0 + c >= k0 + r


def _causal_tiles(causal: bool, q0, k0, block_q: int, block_k: int, tile):
    """Run ``tile(masked, keys, queries)`` over the block at (q0, k0),
    ``keys`` and ``queries`` the slices of the block it covers: not at
    all where no query sees a key, unmasked where every query sees every
    key. With equal blocks the one block the diagonal crosses is the
    aligned one, whose quarter of late keys and early queries is all
    masked: it runs as the early keys against every query and the late
    keys against the late queries, three quarters of the work."""
    whole = (slice(0, block_k), slice(0, block_q))
    if not causal:
        tile(False, *whole)
        return
    needed = k0 <= q0 + block_q - 1
    full = k0 + block_k - 1 <= q0
    pl.when(needed & full)(lambda: tile(False, *whole))

    @pl.when(needed & jnp.logical_not(full))
    def _():
        half = block_q // 2
        if block_q == block_k and half % 8 == 0 and (
                half % LANES == 0 or pallas_mode() == "interpret"):
            tile(True, slice(0, half), slice(0, block_q))
            tile(True, slice(half, block_k), slice(half, block_q))
        else:
            tile(True, *whole)


def _size(sl: slice) -> int:
    return sl.stop - sl.start


# -- kernels ------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                *, scale, causal, d, block_q, block_k):
    i, kk, nk = pl.program_id(2), pl.program_id(3), pl.num_programs(3)
    width = q_ref.shape[-1]
    lanes, rows = _head_masks(width, d, 1), _head_masks(width, d, 0)

    @pl.when(kk == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def tile(masked, ks, qs):
        k, v = k_ref[0, ks], v_ref[0, ks]
        q, post = _prescale(q_ref[0, qs], scale)
        if masked:
            keep = _keep(_size(ks), _size(qs), i * block_q + qs.start,
                         kk * block_k + ks.start)
        alphas, pv = [], None
        for c, mine in enumerate(lanes):
            st = _dot(_only(mine, k), q, _NT)              # (keys, queries)
            if post != 1.0:
                st = st * post
            if masked:
                st = jnp.where(keep, st, NEG_INF)
            m_prev = m_ref[c, :, qs]                       # (1, queries)
            m_new = jnp.maximum(m_prev, jnp.max(st, axis=0, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            pt = jnp.exp(st - m_new)
            l_ref[c, :, qs] = (alpha * l_ref[c, :, qs]
                               + jnp.sum(pt, axis=0, keepdims=True))
            m_ref[c, :, qs] = m_new
            x = _dot(_only(mine, v), pt.astype(v.dtype), _TN)  # (width, queries)
            pv = x if pv is None else pv + x               # this head's rows
            alphas.append(alpha)
        acc_ref[:, qs] = acc_ref[:, qs] * _spread(alphas, rows) + pv

    _causal_tiles(causal, i * block_q, kk * block_k, block_q, block_k, tile)

    @pl.when(kk == nk - 1)
    def _():
        ls = [l_ref[c] for c in range(len(lanes))]
        o_ref[0] = (acc_ref[...] / _spread(ls, rows)).T.astype(o_ref.dtype)
        for c, l in enumerate(ls):
            lse_ref[0, c] = m_ref[c] + jnp.log(l)


def _dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dq_ref,
               acc_ref, *, scale, causal, d, block_q, block_k):
    i, kk, nk = pl.program_id(2), pl.program_id(3), pl.num_programs(3)
    lanes = _head_masks(q_ref.shape[-1], d, 1)

    @pl.when(kk == 0)
    def _():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def tile(masked, ks, qs):
        k, v, g = k_ref[0, ks], v_ref[0, ks], g_ref[0, qs]
        q, post = _prescale(q_ref[0, qs], scale)
        if masked:
            keep = _keep(_size(ks), _size(qs), i * block_q + qs.start,
                         kk * block_k + ks.start)
        dq = None
        for c, mine in enumerate(lanes):
            kc = _only(mine, k)
            st = _dot(kc, q, _NT)                          # (keys, queries)
            if post != 1.0:
                st = st * post
            if masked:
                st = jnp.where(keep, st, NEG_INF)
            pt = jnp.exp(st - lse_ref[0, c, :, qs])
            dpt = _dot(_only(mine, v), g, _NT)
            dst = pt * (dpt - delta_ref[0, c, :, qs])
            x = _dot(kc, dst.astype(k.dtype), _TN)         # (width, queries)
            dq = x if dq is None else dq + x               # this head's rows
        acc_ref[:, qs] += dq

    _causal_tiles(causal, i * block_q, kk * block_k, block_q, block_k, tile)

    @pl.when(kk == nk - 1)
    def _():
        dq_ref[0] = (acc_ref[...] * scale).T.astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, *rest, scale, causal, d, block_q, block_k):
    """dK and dV of a key block over its query blocks. Given a dQ output
    and its accumulator as well (``flash_attention_bwd``), the tile's dS
    makes dQ too: a (batch, lane tile)'s whole dQ^T is held in VMEM, a
    ``(width, block_q)`` panel a query block, across the key blocks."""
    kk, i = pl.program_id(2), pl.program_id(3)
    nk, nq = pl.num_programs(2), pl.num_programs(3)
    lanes = _head_masks(q_ref.shape[-1], d, 1)
    dq_ref, dk_acc, dv_acc, dq_acc = (rest if len(rest) == 4
                                      else (None, *rest, None))

    @pl.when(i == 0)
    def _():
        dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)
        dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)

    if dq_ref is not None:
        @pl.when(kk == 0)
        def _():
            dq_acc[i] = jnp.zeros(dq_acc.shape[1:], jnp.float32)

    def tile(masked, ks, qs):
        q, g, k, v = q_ref[0, qs], g_ref[0, qs], k_ref[0, ks], v_ref[0, ks]
        q_scaled, post = _prescale(q, scale)
        if masked:
            keep = _keep(_size(ks), _size(qs), i * block_q + qs.start,
                         kk * block_k + ks.start)
        dk = dv = dq = None
        for c, mine in enumerate(lanes):
            kc = _only(mine, k)
            st = _dot(kc, q_scaled, _NT)                   # (keys, queries)
            if post != 1.0:
                st = st * post
            if masked:
                st = jnp.where(keep, st, NEG_INF)
            pt = jnp.exp(st - lse_ref[0, c, :, qs])        # rows broadcast
            dpt = _dot(_only(mine, v), g, _NT)
            dst = pt * (dpt - delta_ref[0, c, :, qs])
            xv = _dot(pt.astype(g.dtype), g)               # every head's lanes
            xk = _dot(dst.astype(q.dtype), q)
            # head 0's product fills the tile; each later head takes its lanes
            dv = xv if dv is None else jnp.where(mine, xv, dv)
            dk = xk if dk is None else jnp.where(mine, xk, dk)
            if dq_ref is not None:
                x = _dot(kc, dst.astype(k.dtype), _TN)     # (width, queries)
                dq = x if dq is None else dq + x           # this head's rows
        dv_acc[ks] += dv
        dk_acc[ks] += dk
        if dq_ref is not None:
            dq_acc[i, :, qs] += dq

    _causal_tiles(causal, i * block_q, kk * block_k, block_q, block_k, tile)

    @pl.when(i == nq - 1)
    def _():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    if dq_ref is not None:
        @pl.when(kk == nk - 1)
        def _():
            at = pl.multiple_of(i * block_q, block_q)
            dq_ref[0, pl.ds(at, block_q)] = (
                dq_acc[i] * scale).T.astype(dq_ref.dtype)


# -- the calls ----------------------------------------------------------------

# batch and lane tile are independent; the inner block axis carries the
# accumulators, and in the fused backward the outer one carries dQ's
def _compiler_params(outer: str = "parallel"):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", outer, "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)


_COMPILER_PARAMS = _compiler_params()


def _last_key_block(i, block_q: int, block_k: int, nk: int):
    """The last key block a causal query block ``i`` needs."""
    return jnp.minimum(((i + 1) * block_q - 1) // block_k, nk - 1)


def _first_query_block(kk, block_q: int, block_k: int, nq: int):
    """The first query block that sees causal key block ``kk``."""
    return jnp.minimum((kk * block_k) // block_q, nq - 1)


def _specs(causal, width, heads_per_tile, block_q, block_k, nq, nk,
           queries_inner: bool):
    """Block specs of a query-side operand, a key-side operand and a
    statistics row. Grid (b, j, i, kk), or (b, j, kk, i) with
    ``queries_inner``; the inner index is clamped to the blocks a causal
    tile row (or column) needs, so a skipped step fetches nothing."""
    if queries_inner:
        def qi(kk, i):
            return (jnp.maximum(i, _first_query_block(kk, block_q, block_k,
                                                      nq)) if causal else i)

        qmap = lambda b, j, kk, i: (b, qi(kk, i), j)
        kmap = lambda b, j, kk, i: (b, kk, j)
        smap = lambda b, j, kk, i: (b, j, 0, qi(kk, i))
    else:
        def ki(i, kk):
            return (jnp.minimum(kk, _last_key_block(i, block_q, block_k, nk))
                    if causal else kk)

        qmap = lambda b, j, i, kk: (b, i, j)
        kmap = lambda b, j, i, kk: (b, ki(i, kk), j)
        smap = lambda b, j, i, kk: (b, j, 0, i)
    return (pl.BlockSpec((1, block_q, width), qmap),
            pl.BlockSpec((1, block_k, width), kmap),
            pl.BlockSpec((1, heads_per_tile, 1, block_q), smap))


# The calls are jitted on their own: a model has one attention a layer,
# all alike, and an inner jit is traced once and lowered to one function
# that every layer calls. Left inline, each layer's three kernels were
# traced and lowered to Mosaic again in every program that holds them,
# which no compile cache spares (its key is the lowered text): 20 s a
# program of 24 layers on the chip's host (PERF.md section 6, PR 31).
_STATIC = ("heads", "causal", "scale", "block_q", "block_k", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _forward(q, k, v, *, heads, causal, scale, block_q, block_k, interpret):
    """-> (out (B, Sq, H*D), lse (B, H, 1, Sq))."""
    b, sq, f = q.shape
    skv, d = k.shape[1], f // heads
    width = _tile_width(heads, d)
    g, nq, nk = width // d, sq // block_q, skv // block_k
    qspec, kspec, sspec = _specs(causal, width, g, block_q, block_k, nq, nk,
                                 queries_inner=False)
    row = pltpu.VMEM((g, 1, block_q), jnp.float32)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal, d=d,
                          block_q=block_q, block_k=block_k),
        grid=(b, f // width, nq, nk),
        in_specs=[qspec, kspec, kspec],
        out_specs=[qspec, sspec],
        out_shape=[jax.ShapeDtypeStruct((b, sq, f), q.dtype),
                   jax.ShapeDtypeStruct((b, heads, 1, sq), jnp.float32)],
        scratch_shapes=[row, row,
                        pltpu.VMEM((width, block_q), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v)


@functools.partial(jax.jit, static_argnames=_STATIC + ("fused",))
def _backward(q, k, v, out, lse, g_out, *, heads, causal, scale, block_q,
              block_k, interpret, fused):
    """-> (dq, dk, dv): one kernel (``fused``: :func:`backward_form`) or
    two."""
    b, sq, f = q.shape
    skv, d = k.shape[1], f // heads
    width = _tile_width(heads, d)
    g, nq, nk = width // d, sq // block_q, skv // block_k
    # delta = rowsum(dO * O), once, as lane-dense rows like the lse
    delta = jnp.sum((g_out.astype(jnp.float32) * out.astype(jnp.float32))
                    .reshape(b, sq, heads, d), axis=-1)
    delta = delta.transpose(0, 2, 1)[:, :, None, :]
    kw = dict(scale=scale, causal=causal, d=d, block_q=block_q,
              block_k=block_k)
    operands = (q, k, v, g_out, lse, delta)
    dq_shape = jax.ShapeDtypeStruct((b, sq, f), q.dtype)
    qspec, kspec, sspec = _specs(causal, width, g, block_q, block_k, nq, nk,
                                 queries_inner=True)
    acc = pltpu.VMEM((block_k, width), jnp.float32)
    out_specs, scratch = [kspec, kspec], [acc, acc]
    out_shape = [jax.ShapeDtypeStruct((b, skv, f), k.dtype),
                 jax.ShapeDtypeStruct((b, skv, f), v.dtype)]
    if fused:
        # dQ whole: its block stays where it is across both block axes
        # and goes to HBM once a (batch, lane tile)
        out_specs.append(pl.BlockSpec((1, sq, width),
                                      lambda b, j, kk, i: (b, 0, j)))
        out_shape.append(dq_shape)
        scratch.append(pltpu.VMEM((nq, width, block_q), jnp.float32))
    outs = pl.pallas_call(
        functools.partial(_dkv_kernel, **kw),
        grid=(b, f // width, nk, nq),
        in_specs=[qspec, kspec, kspec, qspec, sspec, sspec],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_compiler_params("arbitrary" if fused else "parallel"),
        interpret=interpret,
        name="flash_attention_bwd" if fused else "flash_attention_dkv",
    )(*operands)
    if fused:
        dk, dv, dq = outs
        return dq, dk, dv
    qspec, kspec, sspec = _specs(causal, width, g, block_q, block_k, nq, nk,
                                 queries_inner=False)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **kw),
        grid=(b, f // width, nq, nk),
        in_specs=[qspec, kspec, kspec, qspec, sspec, sspec],
        out_specs=qspec,
        out_shape=dq_shape,
        scratch_shapes=[pltpu.VMEM((width, block_q), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="flash_attention_dq",
    )(*operands)
    return (dq, *outs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash(q, k, v, static):
    return _forward(q, k, v, **dict(static))[0]


def _flash_fwd(q, k, v, static):
    out, lse = _forward(q, k, v, **dict(static))
    return out, (q, k, v, out, lse)


def _flash_bwd(static, res, g_out):
    from ..obs.metrics import metrics_registry

    kw = dict(static)
    form = backward_form(res[0].shape, kw["heads"], kw["block_q"],
                         kw["block_k"])
    metrics_registry().counter(f"attention.backward.{form}").inc()
    return _backward(*res, g_out, fused=form == "fused", **kw)


_flash.defvjp(_flash_fwd, _flash_bwd)


# -- who takes the kernels, and with what blocks ------------------------------

def _pick_block(s: int) -> Optional[int]:
    """The first of :data:`BLOCKS` that divides ``s``. Under the
    interpreter, which has no tiles to respect, smaller ones too and at
    last the whole sequence."""
    prefs = BLOCKS
    if pallas_mode() == "interpret":
        prefs += (64, 32, 16, 8, s)
    for blk in prefs:
        if blk <= s and s % blk == 0:
            return blk
    return None


def _vmem_bytes(block_q: int, block_k: int, width: int, d: int,
                whole_dq: int = 0) -> int:
    """Largest VMEM working set of the kernels for float32 inputs (the
    widest), counted the way it is allocated: every input and output
    block twice (the Pallas pipeline double-buffers them), the float32
    accumulators, the statistics rows (a sublane tile each), the zeroed
    copies of K and V a head takes, and the (block_k, block_q) float32
    temporaries of one tile (s, p, dp, ds, the mask) for each head of
    the lane tile. None of that grows with the sequence. The fused
    backward holds the dQ of ``whole_dq`` queries besides: its float32
    accumulator and, twice, the output block it is written to."""
    w = -(-width // LANES) * LANES
    blocks = 2 * 4 * w * (3 * block_q + 4 * block_k)    # q, g, dq; k, v, dk, dv
    acc = 4 * w * (block_q + 2 * block_k)
    stats = 4 * 8 * block_q * (2 * 2 + 2) * (width // d)
    copies = 4 * w * 4 * max(block_q, block_k)
    tiles = 4 * 5 * (width // d) * block_q * block_k
    dq = 4 * w * whole_dq * (1 + 2)                     # scratch, out twice
    return blocks + acc + stats + copies + tiles + dq


def backward_form(q_shape, heads: int, block_q: int, block_k: int) -> str:
    """Which backward a packed ``(B, Sq, H*D)`` query takes, read from
    its shape: ``"fused"``, one kernel that makes a tile's dS once for
    dQ, dK and dV, where the whole dQ of a (batch, lane tile) fits the
    VMEM budget beside the tiles (float32 at 128 lanes: some 20k
    queries); ``"split"``, the dQ kernel and the dK/dV kernel, whose
    working sets do not grow with the sequence, beyond that."""
    _, sq, f = q_shape
    d = f // heads
    fits = _vmem_bytes(block_q, block_k, _tile_width(heads, d), d,
                       whole_dq=sq) <= VMEM_BUDGET_BYTES
    return "fused" if fits else "split"


def supported(q_shape, k_shape, causal: bool = False, dtype=None) -> bool:
    """Whether the kernels take these (B, S, H, D) shapes: a float32 or
    bfloat16 operand, heads that fill whole lane tiles (128 % d == 0
    with an even split of the heads, or d a multiple of 128), sequences
    a block of :data:`BLOCKS` divides, and a working set inside the VMEM
    budget. Callers take ``single_device_attention`` (or ring attention)
    otherwise. Independent of the sequence length: no operand is held
    whole."""
    del causal  # the same tiles either way
    if pallas_mode() is None:
        return False
    if dtype is not None and jnp.dtype(dtype) not in (
            jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return False
    (_, sq, heads, d), skv = q_shape, k_shape[1]
    width = _tile_width(heads, d)
    if width is None:
        return False
    if pallas_mode() != "interpret" and width % LANES:
        return False
    bq, bk = _pick_block(sq), _pick_block(skv)
    if bq is None or bk is None:
        return False
    return _vmem_bytes(bq, bk, width, d) <= VMEM_BUDGET_BYTES


def engaged(sq: int, skv: int, d: int, causal: bool = False,
            dtype=jnp.bfloat16) -> bool:
    """Whether attention at these sizes takes the kernels: a rule over
    what the op traces, no file and no knob. On the chip (``auto``):
    both sequences at least :data:`MIN_SEQ`, from where the kernels beat
    XLA's softmax(QK^T)V forward and backward at d 64 and 128 in
    bfloat16 and in float32 (table: PERF.md section 6, PR 31; PARITY.md
    "Flash-attention dispatch policy"). Shorter sequences keep the `xla`
    path: its (S, S) tile is a few hundred KB a head and XLA's fusions
    win. ``FLEXFLOW_TPU_PALLAS=compiled`` forces the kernels on wherever
    :func:`supported` allows, ``interpret`` runs them in the
    interpreter for the numerics tests, ``off`` wins over everything."""
    from . import pallas_forced

    del d, causal, dtype  # measured: the crossover is the same for all
    mode = pallas_mode()
    if mode is None:
        return False
    if mode == "interpret" or pallas_forced():
        return True
    return min(sq, skv) >= MIN_SEQ


def sharded_supported(q_shape, k_shape, mesh, batch_axis, heads_axis,
                      causal: bool = False, dtype=None) -> bool:
    """Whether the shard_map-wrapped kernel handles these GLOBAL (B,S,H,D)
    shapes on this mesh: batch/heads must divide by their axis sizes and
    the per-shard block must satisfy :func:`supported`."""
    from ..core.machine import mesh_axis_sizes

    sizes = mesh_axis_sizes(mesh)
    ddeg = sizes.get(batch_axis, 1) if batch_axis else 1
    hdeg = sizes.get(heads_axis, 1) if heads_axis else 1
    b, sq, h, d = q_shape
    if b % ddeg or h % hdeg:
        return False
    lq = (b // ddeg, sq, h // hdeg, d)
    lk = (k_shape[0] // ddeg, k_shape[1], k_shape[2] // hdeg, d)
    return supported(lq, lk, causal, dtype)


def sharded_flash_attention(q, k, v, mesh, batch_axis, heads_axis,
                            causal: bool = False,
                            scale: Optional[float] = None) -> jax.Array:
    """Flash attention composed with SPMD sharding via shard_map.

    Attention is independent across batch and heads, so each device runs
    the single-core kernel on its (B/dp, S, H/tp, D) block — this is what
    lets the Pallas path engage on dp x tp meshes instead of falling back
    to the jnp einsums (the reference's cuDNN path is likewise per-GPU
    under its MachineView — src/ops/attention.cu). Sequence-sharded
    attention goes through parallel/ring_attention.py instead.
    """
    from jax.sharding import PartitionSpec

    spec = PartitionSpec(batch_axis, None, heads_axis, None)
    fn = functools.partial(flash_attention, causal=causal, scale=scale)
    return shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)


def flash_attention_packed(q, k, v, heads: int, causal: bool = False,
                           scale: Optional[float] = None,
                           block_q: Optional[int] = None,
                           block_k: Optional[int] = None) -> jax.Array:
    """Fused attention over q/k/v as (B, S, H*D), the kernels' own
    layout: what ``(B, S, E) x (E, H*D)`` writes, so a caller that
    projects that way moves nothing through HBM. Returns (B, S, H*D).

    Differentiable (custom VJP). Caller is responsible for checking
    :func:`supported` and falling back to
    ``parallel.ring_attention.single_device_attention`` otherwise (e.g.
    with attention dropout, which this kernel does not implement).
    ``block_q`` / ``block_k`` override the blocks the shapes choose
    (tests, :func:`autotune`).
    """
    sq, skv, d = q.shape[1], k.shape[1], q.shape[2] // heads
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    bq = block_q or _pick_block(sq)
    bk = block_k or _pick_block(skv)
    if (_tile_width(heads, d) is None or bq is None or bk is None
            or sq % bq or skv % bk):
        raise ValueError(
            f"flash_attention: no blocks for sequences ({sq}, {skv}) and "
            f"{heads} heads of {d}; check supported() and fall back to "
            f"single_device_attention")
    return _flash(q, k, v, tuple(zip(_STATIC, (
        heads, causal, float(scale), bq, bk, pallas_mode() == "interpret"))))


def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None) -> jax.Array:
    """:func:`flash_attention_packed` for q/k/v as (B, S, H, D) (the
    framework's bshd convention); returns (B, S, H, D)."""
    b, sq, h, d = q.shape
    skv = k.shape[1]
    out = flash_attention_packed(
        q.reshape(b, sq, h * d), k.reshape(b, skv, h * d),
        v.reshape(b, skv, h * d), h, causal, scale, block_q, block_k)
    return out.reshape(b, sq, h, d)


# -- measuring it -------------------------------------------------------------

def autotune(shape=(4, 1024, 16, 64),
             candidates: Sequence[Tuple[int, int]] = ((512, 512), (256, 256),
                                                      (128, 128)),
             causal: bool = True, dtype=jnp.bfloat16,
             iters: int = 5, layers: int = 8) -> Dict:
    """Time what training runs at ``shape`` (B, S, H, D) on the CURRENT
    backend: the forward and backward passes together, in ``dtype``,
    through the public :func:`flash_attention` for each ``(block_q,
    block_k)`` candidate, and through ``single_device_attention``, the
    path dispatch falls back to. One program runs ``layers`` such
    passes one after the other (each on the last one's gradients, so
    none can be dropped), which keeps the host's dispatch out of a
    short kernel's time. Returns ``{"blocks": {(bq, bk): seconds a
    pass}, "backward": {(bq, bk): the form :func:`backward_form` gave
    the pass that was timed}, "best": (bq, bk), "xla_s": seconds,
    "xla_ratio": xla_s / best}``. It measures and decides nothing:
    :func:`engaged` is a rule over shapes, and its constants are edited
    from tables this function produced on the chip
    (tools/flash_crossover.py)."""
    import time

    import numpy as np

    from . import pallas_forced
    from ..parallel.ring_attention import single_device_attention

    _, s, h, d = shape
    rng = np.random.default_rng(0)
    qkv = tuple(jnp.asarray(rng.normal(size=shape).astype(np.float32), dtype)
                for _ in range(3))
    scale = d ** -0.5

    def both_passes(attend):
        grads = jax.grad(
            lambda q, k, v: jnp.sum(attend(q, k, v).astype(jnp.float32)),
            argnums=(0, 1, 2))

        def layer(xs, _):
            return tuple(x + 1e-3 * g for x, g in zip(xs, grads(*xs))), None

        return jax.jit(lambda xs: jax.lax.scan(layer, xs, None,
                                               length=layers)[0])

    def median_time(fn) -> float:
        jax.block_until_ready(fn(qkv))                  # warm-up, compile
        windows = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(iters):
                out = fn(qkv)
            jax.block_until_ready(out)
            windows.append((time.perf_counter() - t0) / (iters * layers))
        return sorted(windows)[1]

    blocks = {}
    for bq, bk in candidates:
        if s % bq or s % bk:
            continue  # the sequence does not tile at this size
        if _vmem_bytes(bq, bk, _tile_width(h, d) or LANES,
                       d) > VMEM_BUDGET_BYTES:
            continue
        try:
            blocks[(bq, bk)] = median_time(both_passes(functools.partial(
                flash_attention, causal=causal, scale=scale, block_q=bq,
                block_k=bk)))
        except Exception:  # compile/alloc failure: skip this candidate
            if pallas_forced():
                raise  # forced Mosaic: a refusal is the finding, not a skip
    xla_s = median_time(both_passes(
        lambda q, k, v: single_device_attention(q, k, v, causal, scale)))
    best = min(blocks, key=blocks.get) if blocks else None
    forms = {blk: backward_form((shape[0], s, h * d), h, *blk)
             for blk in blocks}
    return {"blocks": blocks, "backward": forms, "best": best, "xla_s": xla_s,
            "xla_ratio": (round(xla_s / blocks[best], 4) if blocks else None)}
