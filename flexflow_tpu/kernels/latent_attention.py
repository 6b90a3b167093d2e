"""Latent-attention decode as a Pallas TPU kernel: a paged latent cache
read in place.

A latent-attention op (ops/attention.py ``LatentAttention``) caches ONE
row a token, ``[c | k_rope]`` padded with zeros to whole 128-lane tiles,
in a :class:`~flexflow_tpu.serving.kv_cache.PagedKVPool` arena
``(num_blocks, block_size, row)``. In the absorbed form a decode step's
query is, per head, a vector over that same row (``q_nope`` folded
through the key half of the up-projection, beside ``q_rope``), and the
weighted sum is taken over the rows themselves (the value half is
applied afterwards). So keys and values are the same bytes, read once:

* block tables and ``seq_lens`` arrive by scalar prefetch; each slot (one
  grid step) walks ``ceil((seq_len + 1) / block_size)`` blocks of its
  table and no more, ``pages_per_chunk`` a loop iteration, the next
  chunk's DMAs (the next slot's first chunk after a slot's last) in
  flight behind the current chunk's math — the scheme of
  ``paged_attention.py``, whose masking contract also holds here;
* what the kernel takes over its products' time is its scalar code: a
  copy a block of 16 such rows (20 KB), started and waited for under a
  guard each, 32 of them unrolled a chunk, was 1.4 of the kernel's
  5.0 ms alone (blocks of 64: 3.6; PERF.md section 6, PR 56). So a
  chunk is fetched in groups of :func:`run_blocks` table entries, ONE
  copy a group whose entries are neighbours ascending in the arena (what
  ``PagedKVPool`` hands a request, seams apart), in one of three forms,
  decided a chunk beside the kernel (:func:`_chunk_forms`, a third scalar
  prefetch: compares inside the kernel cost more than the copies they
  save): every block live and every group a run, which most chunks are,
  and the copies stand in a straight line with nothing to decide; a
  slot's last chunk whose whole live groups are runs, a short loop over
  them and one over the blocks of the frontier; a chunk a seam lies in,
  a guarded copy a live block, so that tables that are no runs at all
  cost the kernel a tenth more than a copy a block everywhere did, not
  half as much again (PERF.md section 7). The same rows land in the
  same places in every form;
* one ``(H, row) @ (row, chunk)`` product scores all heads against a
  chunk (every head attends the one shared row: no block-diagonal
  operand is needed), and ``P @ chunk`` accumulates ``(H, row)``;
* scores, running maximum and sum, and the accumulator are float32; the
  rows and the probabilities fed to the MXU are in the arena's dtype.

The jnp path (``serving.cache_entry.LatentEntry.step``'s gather) is the
kernel's reference and takes every call :func:`supported` refuses.
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
from . import paged_attention
from .paged_attention import _sublanes

# tokens a loop iteration scores (a multiple of the 128 lanes the score
# matrix has them on): one buffer a chunk where paged_attention has two.
# Alone, at 128 slots of 1,000-3,000 cached tokens, 256 / 512 / 1024 read
# 6.73 / 5.40 / 5.16 ms for six layers on the v5e; in the benchmark's
# cell, whose slots start at 512 tokens, the kernel took 4.45 ms a step
# at 512 and 4.89 at 1024 (a short slot pays for a whole chunk): 512
# (PERF.md section 6, PR 27)
CHUNK_TOKENS = 512
# what ONE copy should carry where the table lets it. Alone, at the
# reasoning cell's 128 slots of 1,900 cached tokens in blocks of 16
# (20 KB), six layers over tables that are runs read 4.56 / 3.84 / 3.71 /
# 3.62 ms at 1 / 2 / 4 / 8 blocks a copy for 2.26 ms of bytes, and 3.55
# to 3.67 in blocks of 128 tokens, the products' floor; 4, not 8, because
# a group leaves the blocks of a slot's frontier to single copies
# (PERF.md section 6, PR 56, tools/latent_fetch_sweep.py)
RUN_BYTES = 64 * 1024


def _pages_per_chunk(block_size: int, max_blocks: int) -> int:
    return paged_attention._pages_for_tokens(block_size, max_blocks,
                                             CHUNK_TOKENS)


def run_blocks(arena_shape, arena_dtype, max_blocks: int) -> int:
    """Table entries ONE copy brings where they are neighbours ascending:
    the smallest power of two whose blocks carry ``RUN_BYTES`` (4 blocks
    of 16 rows of 640 bfloat16 lanes, 80 KB), 1 where a block alone does
    (64 of the same rows), a divisor of a chunk's pages, so that no
    group lies across two chunks. The arena's shape and dtype decide:
    ``PagedKVPool`` counts its tables' runs by the same number."""
    _, block_size, row = arena_shape
    block_bytes = block_size * row * jnp.dtype(arena_dtype).itemsize
    pages = _pages_per_chunk(block_size, max_blocks)
    run = 1
    while run * block_bytes < RUN_BYTES and pages % (2 * run) == 0:
        run *= 2
    return run


# how a chunk's copies are issued (``_chunk_forms``)
BROKEN, TAIL, WHOLE = 0, 1, 2


def _chunk_forms(tables, seq_lens, block_size: int, pages: int, run: int):
    """(slots, chunks of the table) int32, of a chunk's groups of ``run``
    table entries: ``WHOLE`` where every one is live (the query may see a
    position of each of its blocks) and neighbours ascending, so that ONE
    copy a group fetches the chunk; ``TAIL`` where those that are live
    are neighbours ascending, and the blocks after them come one by one;
    else ``BROKEN``: a copy a live block."""
    n, max_blocks = tables.shape
    chunks = -(-max_blocks // pages)
    live = jnp.minimum((seq_lens + block_size) // block_size, max_blocks)
    groups = jnp.pad(tables, ((0, 0), (0, chunks * pages - max_blocks)),
                     constant_values=-1).reshape(n, chunks * pages // run,
                                                 run)
    ascending = jnp.all(groups == groups[..., :1] + jnp.arange(run), axis=-1)
    ends = (jnp.arange(ascending.shape[1], dtype=jnp.int32) + 1) * run
    is_live = ends[None, :] <= live[:, None]
    per_chunk = lambda x: jnp.all(x.reshape(n, chunks, pages // run), -1)  # noqa: E731
    return jnp.where(per_chunk(ascending & is_live), WHOLE,
                     jnp.where(per_chunk(ascending | ~is_live), TAIL,
                               BROKEN)).astype(jnp.int32)


def _vmem_bytes(heads: int, row: int, out_width: int, block_size: int,
                max_blocks: int, dtype) -> int:
    chunk = _pages_per_chunk(block_size, max_blocks) * block_size
    item = jnp.dtype(dtype).itemsize
    return (2 * heads * row * item + 2 * 4 * heads * out_width   # q, o blocks
            + 2 * chunk * row * item                             # two buffers
            + 4 * heads * row + 4 * heads * 128 * 2              # acc, m, l
            + 4 * 3 * heads * chunk)                             # s, p, mask


def supported(q_shape, arena_shape, arena_dtype, max_blocks: int,
              out_width: int) -> bool:
    """Whether the kernel takes this call. ``q_shape``: (slots, H, row);
    ``arena_shape``: (num_blocks, block_size, row). Refuses what Mosaic
    would: rows or outputs that are not whole 128-lane tiles, heads that
    are not whole sublane tiles of the arena's dtype, blocks that are not
    whole sublane tiles or do not divide a lane tile of tokens, dtypes
    other than float32 and bfloat16, tables (and a form a chunk of them)
    that do not fit SMEM, a working set over the VMEM budget."""
    if pallas_mode() is None:
        return False
    n, heads, row = q_shape
    _, block_size, arena_row = arena_shape
    dtype = jnp.dtype(arena_dtype)
    if dtype not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return False
    if arena_row != row or row % 128 or out_width % 128 or out_width > row:
        return False
    if heads % _sublanes(dtype):
        return False
    if block_size % _sublanes(dtype) or (128 % block_size
                                         and block_size % 128):
        return False
    pages = _pages_per_chunk(block_size, max_blocks)
    if 4 * n * (max_blocks + 1 + -(-max_blocks // pages)) > SMEM_BUDGET_BYTES:
        return False
    return _vmem_bytes(heads, row, out_width, block_size, max_blocks,
                       dtype) <= VMEM_BUDGET_BYTES


def _kernel(lens_ref, tables_ref, forms_ref,  # scalar prefetch (SMEM)
            q_ref, rows_hbm,                 # inputs
            o_ref,                           # output
            buf, sems, cur_ref, m_ref, l_ref, acc_ref,
            *, scale, block_size, max_blocks, pages, run, slots,
            out_width):
    b = pl.program_id(0)
    chunk = pages * block_size
    row = buf.shape[-1]
    table_chunks = -(-max_blocks // pages)

    def live_blocks(slot):
        # blocks that hold a position the slot's query may see
        return jnp.minimum((lens_ref[slot] + block_size) // block_size,
                           max_blocks)

    def copies(slot, i, which, wait):
        # start and wait decide alike, from the same scalars both times
        form = forms_ref[slot * table_chunks + i]
        live = live_blocks(slot) - i * pages       # of this chunk's blocks
        base = slot * max_blocks + i * pages

        def fetch(j, n):
            # ``n`` neighbours from the chunk's table entry ``j`` on
            cp = pltpu.make_async_copy(
                rows_hbm.at[pl.ds(tables_ref[base + j], n)],
                buf.at[which, pl.ds(j, n)], sems.at[which])
            if wait:
                cp.wait()
            else:
                cp.start()

        @pl.when(form == WHOLE)
        def _():
            for j in range(0, pages, run):
                fetch(j, run)

        @pl.when(form == TAIL)
        def _():
            whole = live // run
            jax.lax.fori_loop(0, whole,
                              lambda k, carry: fetch(k * run, run), None)
            jax.lax.fori_loop(whole * run, live,
                              lambda j, carry: fetch(j, 1), None)

        @pl.when(form == BROKEN)
        def _():
            for j in range(pages):
                @pl.when(j < live)
                def _():
                    fetch(j, 1)

    @pl.when(b == 0)
    def _():
        cur_ref[0] = 0
        buf[...] = jnp.zeros_like(buf)
        copies(0, 0, 0, wait=False)

    q = q_ref[...]                                            # (H, row)
    heads = q.shape[0]
    col = jax.lax.broadcasted_iota(jnp.int32, (heads, chunk), 1)
    seen = lens_ref[b]                     # the last position the query sees
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
        rows = buf[cur].reshape(chunk, row)
        s = _dot(q, rows, _NT) * scale                        # (H, chunk)
        s = jnp.where(i * chunk + col <= seen, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + _dot(p.astype(rows.dtype), rows)
        m_ref[...] = m_new
        cur_ref[0] = 1 - cur
        return carry

    jax.lax.fori_loop(0, n_chunks, body, None)
    o_ref[...] = (acc_ref[...] / l_ref[...])[:, :out_width]


@functools.partial(jax.jit, static_argnames=("scale", "pages", "run",
                                             "out_width", "interpret"))
def _latent_attention(q, arena, tables, seq_lens, *, scale, pages, run,
                      out_width, interpret):
    n, heads, row = q.shape
    _, block_size, _ = arena.shape
    max_blocks = tables.shape[1]
    tables = tables.astype(jnp.int32)
    seq_lens = seq_lens.astype(jnp.int32)
    forms = _chunk_forms(tables, seq_lens, block_size, pages, run)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n,),
        in_specs=[pl.BlockSpec((None, heads, row),
                               lambda b, lens, tabs, forms: (b, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((None, heads, out_width),
                               lambda b, lens, tabs, forms: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, pages, block_size, row), arena.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((heads, 1), jnp.float32),
            pltpu.VMEM((heads, 1), jnp.float32),
            pltpu.VMEM((heads, row), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, block_size=block_size,
            max_blocks=max_blocks, pages=pages, run=run, slots=n,
            out_width=out_width),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, heads, out_width), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="latent_attention_decode",
    )(seq_lens, tables.reshape(-1), forms.reshape(-1),
      q.astype(arena.dtype), arena)


def latent_attention_decode(q, arena, tables, seq_lens, *, scale: float,
                            out_width: int,
                            pages_per_chunk: Optional[int] = None,
                            blocks_per_run: Optional[int] = None
                            ) -> jax.Array:
    """One new token a slot attending the slot's cached latent rows,
    read through its block table from the arena in place.

    ``q``: (slots, H, row) — per head the absorbed query over a row's
    lanes, zeros where the row is padding; ``arena``: (num_blocks,
    block_size, row), already holding the new token's row; ``tables``:
    (slots, max_blocks) int32; ``seq_lens``: (slots,) int32, the tokens
    cached before this one: the query sees positions ``0 .. seq_len``.
    Returns (slots, H, out_width) float32: ``sum_s p_h(s) row(s)`` over
    the first ``out_width`` lanes (the latent part). ``pages_per_chunk``
    and ``blocks_per_run`` stand in for the rules' own numbers in the tests
    and the sweeps. Callers check :func:`supported` first."""
    block_size = arena.shape[1]
    pages = (int(pages_per_chunk) if pages_per_chunk
             else _pages_per_chunk(block_size, tables.shape[1]))
    if (pages * block_size) % 128:
        raise ValueError(f"a chunk of {pages} blocks of {block_size} "
                         f"tokens is no multiple of 128 lanes")
    run = (int(blocks_per_run) if blocks_per_run
           else run_blocks(arena.shape, arena.dtype, tables.shape[1]))
    if pages % run:
        raise ValueError(f"a chunk of {pages} blocks is no whole groups of "
                         f"{run}")
    return _latent_attention(q, arena, tables, seq_lens, scale=float(scale),
                             pages=pages, run=run, out_width=int(out_width),
                             interpret=pallas_mode() == "interpret")


__all__ = ["latent_attention_decode", "run_blocks", "supported"]
