"""Paged-attention decode as a Pallas TPU kernel: the KV pool read in place.

The paged decode step (serving/generation.py) attends W new tokens a slot
(W = 1 for a decode step, k+1 for a speculative verify window) over what
the slot has cached in a :class:`~flexflow_tpu.serving.kv_cache
.PagedKVPool` arena. The jnp path gathers every slot's *whole* block table
into a ``(slots, max_length, H, D)`` copy first, which costs a pass over
an arena's worth of bytes whatever is cached. This kernel reads K and V
from the arena where they lie:

* the arena is ``(num_blocks, block_size, H*D)``: one block is
  ``block_size`` rows of ``H*D`` lanes, so it tiles the TPU's
  (sublane, 128) layout with no padding when ``H*D`` is a multiple of
  128, whatever the head width (GPT-2's 64 is half a lane tile), and a
  block is one contiguous DMA;
* block tables and ``seq_lens`` arrive by scalar prefetch; for each slot
  (one grid step) the kernel walks ``ceil((seq_len + W) / block_size)``
  blocks of its table and no more, ``pages_per_chunk`` blocks a loop
  iteration, with the next chunk's DMAs (the next slot's first chunk
  after a slot's last) in flight behind the current chunk's math. A
  chunk follows the rows' BYTES (``_pages_per_chunk``): an iteration
  costs a DMA's round trip whatever it brings, so narrow rows (two
  key-value heads of 128: 512 B) get as many pages as keep a MiB of K
  and V in flight, 1,024 tokens, and rows of 2 KB and wider the 256
  tokens they always had;
* all heads of a chunk are scored by ONE matrix product: the slot's
  query row is spread into a block-diagonal ``(H_pad, H*D)`` operand
  (row h keeps head h's 64 lanes, zeros elsewhere), so
  ``q_bd @ K_chunk^T`` is the ``(H_pad, tokens)`` score matrix and
  ``P @ V_chunk`` an ``(H_pad, H*D)`` accumulator whose diagonal blocks
  are the heads' outputs. No lane slice at a half tile, no per-head
  loop; the MXU has the headroom (the step is bound by the bytes);
* grouped heads (``H`` query heads on ``Hkv`` key-value heads, the
  arena's rows ``Hkv * D`` lanes wide, ``D`` whole lane tiles or half of
  one, two key-value heads a tile): the ``H / Hkv`` query heads of a
  group are that many rows of the same score matrix over the one key
  row, row h keeping its query in the lanes of key-value head ``h // (H
  / Hkv)``;
* a key head need not be a value head's width (``Hkv * Dv`` lanes a row
  of V, ``Dv`` whole lane tiles or 64), nor whole lane tiles itself: a
  key head of ``128 a + 64`` numbers (192: one and a half tiles) is
  stored SPLIT (:func:`key_parts`, :func:`split_heads`: every head's
  first ``128 a`` numbers, then every head's last 64), so that each
  head's slices start where a 64-wide head's do; the query's two slices
  are spread the same way and the one product sums both parts;
* a sink a query head (``sink`` (H,)) is the running softmax's initial
  state, ``m = s_h, l = 1, acc = 0`` in place of ``-inf, 0, 0``: one
  more column that carries no value, and no column at all;
* scores, the running maximum and sum, and the weighted sum are float32;
  K, V and the probabilities fed to the MXU are in the arena's dtype,
  which is what the jnp path feeds it.

Masking is by position, as in the jnp path: column ``t`` of a slot counts
for window row ``w`` iff ``t <= seq_len + w``, so stale rows after a
speculative roll-back, the null block's contents and blocks that are
reserved but not yet written never reach a result. They must be finite
(the pool's contract): a masked probability is an exact 0.0, and
0 * finite = 0. For the same reason the chunk buffers are zeroed once
before the first DMA: a block that is not live is not fetched, and what
a buffer holds in its place is then old arena data or zeros.

The jnp path (``serving.cache_entry.PairEntry.read`` and the attend after
it) is this kernel's reference and takes every entry :func:`supported`
refuses: int8 arenas, widths Mosaic does not tile, and the CPU.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_mode
from .flash_attention import NEG_INF, VMEM_BUDGET_BYTES, VMEM_LIMIT_BYTES, _NT, _dot
from .moe_kernels import SMEM_BUDGET_BYTES

# What a loop iteration brings is what is in flight behind the math of the
# iteration before, and an iteration costs a DMA's round trip whatever it
# brings: 0.65 us on the v5e with the kernel alone, 1.1 us inside a decode
# step, in which the HBM's 819 GB/s deliver 0.5 to 0.9 MB. So a chunk is
# as many pages as bring K and V together to CHUNK_BYTES (PERF.md section
# 6, PR 51: the kernel alone at five cells' shapes, the pages a chunk side
# by side, tools/paged_chunk_sweep.py: rows of 512 B 157 us a layer at
# 256 tokens, 113 at 1,024, 133 at 2,048; rows of 1 KB 571 at 256, 481 at
# 512, 479 at 1,024) ...
CHUNK_BYTES = 1 << 20
# ... never fewer than this many tokens, a multiple of the 128 lanes the
# score matrix has them on: rows of 2 KB and wider gain nothing past the
# chunk that measured best of 128/256/512 at GPT-2 large's rows (PR 26;
# PR 51's table: 1,068 us at 256 and at 512 tokens of 2 KB rows, 62 and
# 64 us at 2.5 KB) ...
CHUNK_TOKENS = 256
# ... and never more than this many pages unless CHUNK_TOKENS need more:
# the longest unrolled run of DMA starts and waits. Blocks of 16 tokens
# reach it at 256 tokens, and rows of 512 B there are 8 KB DMAs, some 38 ns
# each whatever the chunk (642 us a layer at 16 pages, 590 at 32, 624 at
# 64, against 169 us of bytes): the chunk is not that shape's handle
MAX_PAGES = 16
# rows of the block-diagonal query operand: W windows of H_pad heads
MAX_QUERY_ROWS = 256


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _sublanes(dtype) -> int:
    return 32 // jnp.dtype(dtype).itemsize     # 8 for f32, 16 for bf16


def key_parts(head_dim: int):
    """The widths a key head's numbers are stored as: itself, or for a
    head of ``128 a + 64`` numbers past one tile its first ``128 a`` and
    its last 64, the layout :func:`split_heads` makes."""
    if head_dim > 128 and head_dim % 128 == 64:
        return (head_dim - 64, 64)
    return (head_dim,)


def split_heads(x, heads: int):
    """(..., heads * D) rows of heads side by side -> the same rows as an
    arena keeps them: as they are, or, where :func:`key_parts` splits a
    head, every head's first part then every head's last."""
    d = x.shape[-1] // heads
    parts = key_parts(d)
    if len(parts) == 1:
        return x
    xh = x.reshape(x.shape[:-1] + (heads, d))
    return jnp.concatenate(
        [xh[..., :parts[0]].reshape(x.shape[:-1] + (-1,)),
         xh[..., parts[0]:].reshape(x.shape[:-1] + (-1,))], axis=-1)


def join_heads(x, heads: int):
    """:func:`split_heads` undone."""
    d = x.shape[-1] // heads
    parts = key_parts(d)
    if len(parts) == 1:
        return x
    edge = heads * parts[0]
    lead = x.shape[:-1]
    return jnp.concatenate(
        [x[..., :edge].reshape(lead + (heads, parts[0])),
         x[..., edge:].reshape(lead + (heads, parts[1]))],
        axis=-1).reshape(lead + (heads * d,))


def _pages_for_tokens(block_size: int, max_blocks: int,
                      chunk_tokens: int) -> int:
    """Pages of a chunk of about ``chunk_tokens``: whole lane tiles of
    tokens, and no wider than the smallest such that covers a table."""
    lane_pages = max(1, 128 // block_size)
    per = max(lane_pages, chunk_tokens // block_size // lane_pages
              * lane_pages)
    return min(per, _round_up(max_blocks, lane_pages))


def _pages_per_chunk(block_size: int, max_blocks: int,
                     row_bytes: int) -> int:
    """Pages a loop iteration brings of arenas whose rows (one token's K,
    or its V; their mean where they differ) are ``row_bytes`` wide: see
    ``CHUNK_BYTES``."""
    wanted = CHUNK_BYTES // (2 * row_bytes * block_size)
    least = max(1, CHUNK_TOKENS // block_size)
    return _pages_for_tokens(block_size, max_blocks,
                             block_size * max(least, min(wanted, MAX_PAGES)))


def chunk_tokens(arena_shape, arena_dtype, max_blocks: int,
                 value_lanes: Optional[int] = None) -> int:
    """Tokens a loop iteration scores over arenas of ``arena_shape``
    (num_blocks, block_size, Hkv*D) (the values' rows ``value_lanes``
    wide where they are not the keys'): what a decoder's
    ``attention_path`` says of its decode kernel."""
    _, block_size, hd = arena_shape
    return block_size * _pages_per_chunk(
        block_size, max_blocks,
        (hd + (value_lanes or hd)) * jnp.dtype(arena_dtype).itemsize // 2)


def _vmem_bytes(rows: int, window: int, heads: int, head_dim: int,
                block_size: int, max_blocks: int, dtype,
                kv_heads: Optional[int] = None,
                value_lanes: Optional[int] = None) -> int:
    """The kernel's VMEM working set as it is allocated: q and o whole
    (float32, double-buffered by the pipeline), both chunk buffers of K
    and V, the float32 accumulator, and the (rows, chunk) float32
    temporaries of one iteration (s, p, mask) beside the block-diagonal
    operand and its mask."""
    hd = (kv_heads or heads) * head_dim
    vd = value_lanes or hd
    m = window * _round_up(heads, 16)
    item = jnp.dtype(dtype).itemsize
    chunk = _pages_per_chunk(block_size, max_blocks,
                             (hd + vd) * item // 2) * block_size
    return (2 * 4 * rows * heads * (hd + vd) // (kv_heads or heads)  # q, o
            + 2 * chunk * (hd + vd) * item     # K, V chunks, two buffers
            + 4 * m * (2 * hd + vd)            # q_bd, its f32 source, acc
            + 4 * m * 128 * 2                  # m, l columns (lane padded)
            + 4 * 3 * m * chunk)               # s, p, mask


def supported(q_shape, arena_shape, arena_dtype, max_blocks: int,
              value_lanes: Optional[int] = None) -> bool:
    """Whether the kernel takes this call. ``q_shape``: (slots, W, H, D);
    ``arena_shape``: (num_blocks, block_size, Hkv*D), ``Hkv`` dividing
    ``H`` (grouped heads: ``D`` then whole lane tiles, or 64, two heads a
    tile: Granite's 32 on 8; or ``128 a + 64``, stored split:
    :func:`key_parts`); ``value_lanes``: ``Hkv * Dv``, the width of V's
    rows where it is not K's (grouped heads only: a key head a query
    head keeps q and o in one layout, of one width, unsplit). Refuses
    what Mosaic
    would: rows that do not fill whole 128-lane tiles, blocks that are
    not whole sublane tiles of the arena's dtype or do not divide a lane
    tile of tokens, dtypes other than float32 and bfloat16 (an int8
    entry is not a ``(k, v)`` pair at all and never gets here), more
    query rows than one score matrix should hold, tables that do not fit
    SMEM, a working set over the VMEM budget. Callers take the jnp path
    then."""
    if pallas_mode() is None:
        return False
    n, w, heads, head_dim = q_shape
    _, block_size, hd = arena_shape
    dtype = jnp.dtype(arena_dtype)
    if dtype not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return False
    vd = value_lanes or hd
    if hd % head_dim or hd % 128 or vd % 128:
        return False
    kv_heads = hd // head_dim
    if heads % kv_heads or vd % kv_heads:
        return False
    parts = key_parts(head_dim)
    if kv_heads != heads:
        if any(p % 64 for p in parts) or (vd // kv_heads) % 64:
            return False
    elif vd != hd or len(parts) > 1:
        return False
    if block_size % _sublanes(dtype) or (128 % block_size
                                         and block_size % 128):
        return False
    if w * _round_up(heads, 16) > MAX_QUERY_ROWS:
        return False
    if 4 * (n * max_blocks + n) > SMEM_BUDGET_BYTES:
        return False
    rows = _round_up(n * w, 8)
    return _vmem_bytes(rows, w, heads, head_dim, block_size, max_blocks,
                       dtype, kv_heads, vd) <= VMEM_BUDGET_BYTES


def _kernel(lens_ref, tables_ref,            # scalar prefetch (SMEM)
            q_ref, k_hbm, v_hbm, *rest,      # inputs (a sink's column last)
            scale, window, heads, head_dim, block_size, max_blocks,
            pages, slots, kv_heads, v_dim, sink):
    sink_ref = rest[0] if sink else None
    (o_ref,                                  # output
     kbuf, vbuf, sems, cur_ref, m_ref, l_ref, acc_ref) = rest[sink:]
    b = pl.program_id(0)
    hd = kv_heads * head_dim
    parts = key_parts(head_dim)
    # (the values' diagonal is the keys' unless their widths differ)
    apart = v_dim != head_dim or len(parts) > 1
    group = heads // kv_heads
    hpad = _round_up(heads, 16)
    chunk = pages * block_size

    def live_blocks(slot):
        # blocks that hold a position some window row may see
        return jnp.minimum(
            (lens_ref[slot] + window + block_size - 1) // block_size,
            max_blocks)

    def copies(slot, i, buf, wait):
        """Start (or wait for) the DMAs of chunk ``i`` of ``slot`` into
        chunk buffer ``buf``: its live blocks only."""
        live = live_blocks(slot)
        for j in range(pages):
            g = i * pages + j

            @pl.when(g < live)
            def _():
                page = tables_ref[slot * max_blocks + g]
                for hbm, vmem, s in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1)):
                    cp = pltpu.make_async_copy(
                        hbm.at[page], vmem.at[buf, j], sems.at[s, buf])
                    if wait:
                        cp.wait()
                    else:
                        cp.start()

    @pl.when(b == 0)
    def _():
        cur_ref[0] = 0
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)
        copies(0, 0, 0, wait=False)

    # row h of a group keeps head h's lanes: the block-diagonal operand,
    # and at the end the diagonal blocks of the accumulator
    r = jax.lax.broadcasted_iota(jnp.int32, (hpad, hd), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (hpad, hd), 1)
    if group == 1:
        diag = (c >= r * head_dim) & (c < r * head_dim + head_dim)
        q_bd = jnp.concatenate(
            [jnp.where(diag, jnp.broadcast_to(
                q_ref[pl.ds(b * window + w, 1), :], (hpad, hd)), 0.0)
             for w in range(window)], axis=0).astype(kbuf.dtype)  # (M, HD)
    else:
        # q and o are (rows, H, D): row h of a group of hpad keeps head
        # h's query in the lanes of its key-value head, whole lane tiles
        if len(parts) == 1:
            diag = (c // head_dim == r // group) & (r < heads)
        else:
            # the split layout: all heads' first parts, then their last
            edge = kv_heads * parts[0]
            diag = (jnp.where(c < edge, c // parts[0],
                              (c - edge) // parts[1]) == r // group) & (
                r < heads)

        def spread(qh):               # (H, D) -> (hpad, Hkv D)
            if hpad != heads:
                qh = jnp.concatenate(
                    [qh, jnp.zeros((hpad - heads, head_dim), qh.dtype)], 0)
            if len(parts) == 1:
                cols = [qh] * kv_heads
            else:
                cols = ([qh[:, :parts[0]]] * kv_heads
                        + [qh[:, parts[0]:]] * kv_heads)
            return jnp.where(diag, jnp.concatenate(cols, 1), 0.0)

        q_bd = jnp.concatenate(
            [spread(q_ref[b * window + w]) for w in range(window)],
            axis=0).astype(kbuf.dtype)
    m_rows = window * hpad
    # the last position each row may see: seq_len + its window index
    row = jax.lax.broadcasted_iota(jnp.int32, (m_rows, chunk), 0)
    limit = jnp.full((m_rows, chunk), lens_ref[b], jnp.int32)
    for w in range(1, window):
        limit = limit + (row >= w * hpad).astype(jnp.int32)
    col = jax.lax.broadcasted_iota(jnp.int32, (m_rows, chunk), 1)

    if sink:
        # one more column of the softmax that carries no value: where
        # the running maximum and sum start
        m_ref[...] = jnp.concatenate([sink_ref[...]] * window, axis=0)
        l_ref[...] = jnp.ones_like(l_ref)
    else:
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    n_chunks = (live_blocks(b) + pages - 1) // pages          # >= 1

    def body(i, carry):
        cur = cur_ref[0]
        last = i + 1 >= n_chunks
        nxt_slot = jnp.where(last, b + 1, b)
        nxt_i = jnp.where(last, 0, i + 1)

        @pl.when(nxt_slot < slots)
        def _():
            copies(nxt_slot, nxt_i, 1 - cur, wait=False)

        copies(b, i, cur, wait=True)
        k = kbuf[cur].reshape(chunk, hd)
        v = vbuf[cur].reshape(chunk, kv_heads * v_dim)
        s = _dot(q_bd, k, _NT) * scale                        # (M, chunk)
        s = jnp.where(i * chunk + col <= limit, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + _dot(p.astype(v.dtype), v)
        m_ref[...] = m_new
        cur_ref[0] = 1 - cur
        return carry

    jax.lax.fori_loop(0, n_chunks, body, None)

    out = acc_ref[...] / l_ref[...]                           # (M, HD)
    if apart:
        rv = jax.lax.broadcasted_iota(jnp.int32, (hpad, kv_heads * v_dim), 0)
        cv = jax.lax.broadcasted_iota(jnp.int32, (hpad, kv_heads * v_dim), 1)
        diag = (cv // v_dim == rv // group) & (rv < heads)
    for w in range(window):
        kept = jnp.where(diag, out[w * hpad:(w + 1) * hpad], 0.0)
        if group == 1:
            o_ref[pl.ds(b * window + w, 1), :] = jnp.sum(
                kept, axis=0, keepdims=True)
        else:
            # a row's output lies in its key-value head's lanes alone
            o_ref[b * window + w] = sum(
                kept[:heads, j * v_dim:(j + 1) * v_dim]
                for j in range(kv_heads))


@functools.partial(jax.jit, static_argnames=("scale", "pages", "interpret"))
def _paged_attention(q, k_arena, v_arena, tables, seq_lens, sink=None, *,
                     scale, pages, interpret):
    n, window, heads, head_dim = q.shape
    _, block_size, hd = k_arena.shape
    kv_heads = hd // head_dim
    vd = v_arena.shape[2]
    v_dim = vd // kv_heads
    max_blocks = tables.shape[1]
    rows = _round_up(n * window, 8)
    # a key head a query head: q and o are rows of H*D lanes; grouped:
    # (rows, H, D), a head a sublane row
    q_shape = ((rows, hd) if kv_heads == heads else (rows, heads, head_dim))
    q2 = q.astype(jnp.float32).reshape((n * window,) + q_shape[1:])
    if rows != n * window:
        q2 = jnp.pad(q2, ((0, rows - n * window),) + ((0, 0),) *
                     (q2.ndim - 1))
    hpad = _round_up(heads, 16)
    m_rows = window * hpad
    whole = pl.BlockSpec(q_shape, lambda b, lens, tabs: (0,) * len(q_shape))
    o_shape = q_shape if v_dim == head_dim else (rows, heads, v_dim)
    whole_o = whole if v_dim == head_dim else pl.BlockSpec(
        o_shape, lambda b, lens, tabs: (0, 0, 0))
    more_in, more = [], []
    if sink is not None:
        # the sinks as a column of the score matrix's rows (a padding
        # row's is 0: its output is dropped)
        more_in = [pl.BlockSpec((hpad, 1), lambda b, lens, tabs: (0, 0))]
        more = [jnp.pad(sink.astype(jnp.float32),
                        (0, hpad - heads)).reshape(hpad, 1)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n,),
        in_specs=[whole,
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)] + more_in,
        out_specs=whole_o,
        scratch_shapes=[
            pltpu.VMEM((2, pages, block_size, hd), k_arena.dtype),
            pltpu.VMEM((2, pages, block_size, vd), v_arena.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((m_rows, 1), jnp.float32),
            pltpu.VMEM((m_rows, 1), jnp.float32),
            pltpu.VMEM((m_rows, vd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, window=window, heads=heads,
            head_dim=head_dim, block_size=block_size,
            max_blocks=max_blocks, pages=pages, slots=n, kv_heads=kv_heads,
            v_dim=v_dim, sink=sink is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(o_shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="paged_attention_decode",
    )(seq_lens.astype(jnp.int32), tables.astype(jnp.int32).reshape(-1),
      q2, k_arena, v_arena, *more)
    return out[:n * window].reshape(n, window, heads, v_dim)


def paged_attention_decode(q, k_arena, v_arena, tables, seq_lens,
                           scale: Optional[float] = None,
                           pages_per_chunk: Optional[int] = None,
                           sink: Optional[jax.Array] = None
                           ) -> jax.Array:
    """Attention of W new tokens a slot over the slot's cached K/V, read
    through its block table from the arenas in place.

    ``q``: (slots, W, H, D); ``k_arena``/``v_arena``: (num_blocks,
    block_size, Hkv*D) and (.., Hkv*Dv), K's rows as :func:`split_heads`
    lays them, already holding the window's own rows; ``sink``: (H,) or
    None; ``tables``:
    (slots, max_blocks) int32; ``seq_lens``: (slots,) int32, the tokens
    cached before the window. Row w of a slot sees positions
    ``0 .. seq_len + w``. ``pages_per_chunk`` (blocks a loop iteration;
    times ``block_size`` a multiple of 128) is for tests and tuning.
    Returns (slots, W, H, Dv) float32. Callers check :func:`supported`
    first."""
    head_dim = q.shape[-1]
    block_size = k_arena.shape[1]
    scale = float(scale) if scale is not None else head_dim ** -0.5
    pages = (int(pages_per_chunk) if pages_per_chunk
             else _pages_per_chunk(
                 block_size, tables.shape[1],
                 (k_arena.shape[2] + v_arena.shape[2])
                 * k_arena.dtype.itemsize // 2))
    if (pages * block_size) % 128:
        raise ValueError(f"a chunk of {pages} blocks of {block_size} "
                         f"tokens is no multiple of 128 lanes")
    return _paged_attention(q, k_arena, v_arena, tables, seq_lens, sink,
                            scale=scale, pages=pages,
                            interpret=pallas_mode() == "interpret")


__all__ = ["join_heads", "key_parts", "paged_attention_decode",
           "split_heads", "supported"]
