"""A prompt chunk's attention over plain keys and values as a Pallas TPU
kernel: the scores never leave VMEM, key blocks no query sees are skipped.

A chunked prefill (serving/generation.py ``_chunk_step``) attends a
chunk's S queries over what its request holds: a windowed layer's ``[ring
| chunk]`` rows, a full layer's rows through its block table. The jnp
form (``serving.cache_entry._attend_spans``) walks spans of keys and
writes a span's ``(heads, S, span)`` float32 scores to HBM, then passes
over them for the mask, the maximum, the exponential, the sum and the
cast: several times the scores' bytes a span, where the products need a
tenth of that time. This kernel computes the same sums a ``(key block,
query block)`` tile at a time:

* queries ``(n, S, H*D)`` with their absolute positions ``(n, S)``; keys
  and values ``(n, L, Hkv*D)`` in the arena's own row layout (all heads
  side by side) with the absolute position each key row holds ``(n,
  L)``, :data:`NOWHERE` for a row that holds nothing. Positions are data:
  a ring is stored rotated, a first chunk's ring is empty, a short last
  chunk's rows past its length hold nothing. A query at ``p`` sees the
  keys at ``p - window + 1 .. p`` (``.. p`` with no window), which is
  ``MultiHeadAttention.sees``;
* grid ``(n, Hkv, query blocks, key blocks)``, the key axis innermost. A
  grid step owns one key-value head's ``D`` lanes of K and V (``D`` whole
  lane tiles) and the ``H / Hkv`` query heads of its group, ``H / Hkv * D``
  lanes of q and o: the group's heads read the one key tile where it
  lies, each a product of its own over it, nothing repeated;
* a group too wide for the fast memory at once (16 query heads a key
  head) is walked a part of its heads a grid step (:func:`heads_a_step`:
  the largest divisor of the group that fits), each part reading the
  key tile again;
* a value head need not be a key head's width (``Dv`` whole lane tiles),
  and a key head may be ``128 a + 64`` numbers, which the arena stores
  SPLIT (``paged_attention.key_parts``): a grid step reads its head's
  first part where a whole head's would lie and the tile that holds its
  last 64 beside the next head's, and the queries' last 64 arrive in
  that half of a tile of their own with zeros in the other (made in jnp
  ahead of the call), so the score is the sum of two aligned products;
* a sink a query head (``sink`` (H,)) is where a query's running maximum
  and sum start (``m = s_h, l = 1``), one more column of the softmax
  that carries no value;
* which key blocks a query block visits is a small table made in jnp
  ahead of the call (:func:`block_table`: the key blocks whose ``[min
  kpos, max kpos]`` meets the block's ``[min qpos - window + 1, max
  qpos]``, first, and whether every query of the block sees every key of
  it), scalar-prefetched and read by the index maps: a step past a
  block's visits names its last visit again, which is not copied again,
  and computes nothing. A block that holds the ring's wrap is simply
  visited. Only the tiles an edge crosses (the diagonal, the band's far
  side, a ring's wrap, a length's end) pay for the mask;
* every tile is computed transposed, keys on sublanes and queries on
  lanes, as ``flash_attention`` computes its own and for its reason: what
  the running softmax keeps for a query is a lane-dense row. The key
  positions arrive spread over a lane tile ``(n, L, 128)`` (made in jnp:
  a column of 4-byte rows is no DMA to wish for), the queries' as a row;
* the walk's arithmetic: operands in the rows' dtype into float32
  products, float32 maximum, sum and accumulator, the probabilities cast
  to the values' dtype before the second product, ``-1e30`` not ``-inf``,
  a masked probability an exact 0.0 (a query may see nothing of a tile it
  visits), a row that sees nothing divided by ``max(l, 1e-30)``.

Rows no query sees must be finite in V (the pool's contract, as for
``paged_attention``): a masked probability is 0.0 and 0 * finite = 0. In
K they may hold anything, and a block that is not visited is not read.

``_attend_spans`` is this kernel's reference and takes everything
:func:`supported` refuses: the CPU, head widths of no whole lane tiles
(GPT-2's 64), int8 rows, a working set past the fast-memory budget.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_mode
from .flash_attention import (LANES, NEG_INF, VMEM_BUDGET_BYTES,
                              VMEM_LIMIT_BYTES, _NT, _TN, _dot, _prescale)
from .moe_kernels import SMEM_BUDGET_BYTES
from .paged_attention import key_parts

# the position of a key row that holds nothing: later than any query
NOWHERE = 2 ** 30
# query and key rows a tile: the first that divides the chunk, and keys
# padded up to whole blocks (PERF.md section 6, PR 44, has the sizes
# measured on the v5e at head width 128)
BLOCKS_Q = (512, 256, 128)
BLOCK_K = 512
# what a tile is: not visited, crossed by an edge (masked), or seen whole
SKIP, EDGE, WHOLE = 0, 1, 2


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def blocks(s: int, keys: int) -> Optional[Tuple[int, int]]:
    """(query rows, key rows) a tile for a chunk of ``s`` queries over
    ``keys`` key rows, None where no block divides the chunk. Under the
    interpreter, which has no tiles to respect, smaller ones too."""
    prefs = BLOCKS_Q
    if pallas_mode() == "interpret":
        prefs += (64, 32, 16, 8)
    block_q = next((b for b in prefs if s % b == 0), None)
    if block_q is None:
        return None
    return block_q, min(BLOCK_K, _round_up(keys, min(LANES, block_q)))


def _vmem_bytes(block_q: int, block_k: int, group: int, d: int,
                dtype, dv: Optional[int] = None) -> int:
    """The kernel's VMEM working set: q and o blocks of a step's ``group``
    heads, K, V and the spread key positions (all double-buffered by the
    pipeline), the transposed float32 accumulator and the softmax rows,
    and a tile's float32 scores, probabilities, their cast and the mask,
    counted for every head of the step at once (the heads' products are
    unrolled and may overlap). ``d``: a query head's lanes as the kernel
    reads them (:func:`_query_lanes`), ``dv`` a value head's."""
    dv = dv or d
    item = jnp.dtype(dtype).itemsize
    tile = block_q * block_k
    return (2 * block_q * group * (d + dv) * item       # q, o
            + 2 * block_k * (d + dv) * item             # K, V
            + 2 * block_k * LANES * 4 + 2 * 8 * block_q * 4   # positions
            + 4 * group * dv * block_q                  # accumulator
            + 2 * 4 * group * 8 * block_q               # m, l
            + group * tile * (4 + 4 + item) + 4 * tile)


def _query_lanes(d: int) -> int:
    """Lanes a query head takes as the kernel reads it: its width, or for
    a split head its first part and a whole tile for its last 64."""
    parts = key_parts(d)
    return d if len(parts) == 1 else parts[0] + LANES


def heads_a_step(block_q: int, block_k: int, group: int, d: int, dv: int,
                 dtype) -> Optional[int]:
    """Query heads of a group one grid step attends: all of them, or the
    largest divisor of the group whose working set is within the VMEM
    budget; None where one head's is not."""
    return next((g for g in range(group, 0, -1) if group % g == 0
                 and _vmem_bytes(block_q, block_k, g, _query_lanes(d), dtype,
                                 dv) <= VMEM_BUDGET_BYTES), None)


def supported(q_shape, q_dtype, kv_shape, kv_dtype,
              value_lanes: Optional[int] = None) -> bool:
    """Whether the kernel takes this call. ``q_shape``: (n, S, H, D);
    ``kv_shape``: (n, L, Hkv*D); ``value_lanes``: ``Hkv * Dv`` where V's
    rows are not K's width. Needs Pallas on (a TPU backend, or
    ``FLEXFLOW_TPU_PALLAS=interpret``), a value head of whole lane tiles
    and a key head of whole lane tiles or of whole tiles and 64 more
    (stored split, an even number of key heads then),
    query heads a multiple of the key heads, float32 or bfloat16 rows of
    the queries' own dtype (an int8 entry is no ``(k, v)`` pair), a chunk
    some block divides, a table that fits SMEM and a working set within
    the VMEM budget. Callers take ``_attend_spans`` otherwise."""
    if pallas_mode() is None:
        return False
    n, s, heads, d = q_shape
    keys, hd = kv_shape[1:]
    dtype = jnp.dtype(kv_dtype)
    if dtype != jnp.dtype(q_dtype) or dtype not in (
            jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return False
    parts = key_parts(d)
    if parts[0] % LANES or hd % d or heads % (hd // d):
        return False
    kv_heads = hd // d
    vd = value_lanes or hd
    if vd % kv_heads or (vd // kv_heads) % LANES:
        return False
    if len(parts) > 1 and kv_heads % 2:
        return False
    took = blocks(s, keys)
    if took is None:
        return False
    block_q, block_k = took
    tiles = n * (s // block_q) * (_round_up(keys, block_k) // block_k)
    if 2 * 4 * tiles > SMEM_BUDGET_BYTES:
        return False
    return heads_a_step(block_q, block_k, heads // kv_heads, d,
                        vd // kv_heads, dtype) is not None


def block_table(qpos, kpos, window: Optional[int], block_q: int,
                block_k: int):
    """For each query block the key blocks it visits, first and in their
    order, and what each is. ``qpos`` (n, S), ``kpos`` (n, L) int32. A
    key block is visited where a row of it holds a position some query of
    the block may see: ``[min kpos, max kpos]`` (over the rows that hold
    something) meets ``[min qpos - window + 1, max qpos]``; it is
    :data:`WHOLE` where every row holds something every query of the
    block sees, else :data:`EDGE`. Returns ``(ids, kinds)``, (n, query
    blocks, key blocks) int32 each: past a block's visits ``ids`` repeats
    the last one (a block index that does not change is not fetched
    again; block 0 for a block that visits nothing) and ``kinds`` is
    :data:`SKIP`."""
    n = qpos.shape[0]
    q = qpos.reshape(n, -1, 1, block_q)
    k = kpos.reshape(n, 1, -1, block_k)
    qlo, qhi = q.min(-1), q.max(-1)                    # (n, nq, 1)
    held = k != NOWHERE
    klo = k.min(-1)            # NOWHERE where nothing is held: not visited
    khi = jnp.where(held, k, -1).max(-1)               # (n, 1, nk)
    visit = klo <= qhi
    whole = held.all(-1) & (khi <= qlo)
    if window:
        visit &= qlo - khi < window
        whole &= qhi - klo < window
    order = jnp.argsort(~visit, axis=-1, stable=True).astype(jnp.int32)
    count = visit.sum(-1, keepdims=True, dtype=jnp.int32)
    at = jax.lax.broadcasted_iota(jnp.int32, visit.shape, 2)
    # (a block that visits nothing: ``order`` is the tiles' own, tile 0)
    ids = jnp.take_along_axis(
        order, jnp.minimum(at, jnp.maximum(count - 1, 0)), axis=-1)
    kinds = jnp.where(
        at < count,
        jnp.where(jnp.take_along_axis(jnp.broadcast_to(whole, visit.shape),
                                      ids, axis=-1), WHOLE, EDGE), SKIP)
    return ids, kinds.astype(jnp.int32)


def _kernel(ids_ref, kinds_ref,                       # scalar prefetch
            q_ref, qpos_ref, k_ref, v_ref, kpos_ref,  # inputs
            *rest, scale, window, group, d, dv, tail, sink):
    # after the inputs every call has: the tile of the keys' last 64s (a
    # split head's) and the sinks' rows, then the output and the scratch
    tail_ref = rest[0] if tail else None
    sink_ref = rest[tail] if sink else None
    o_ref, m_ref, l_ref, acc_ref = rest[tail + sink:]
    b, i, kk = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    nq, nk = pl.num_programs(2), pl.num_programs(3)
    kind = kinds_ref[(b * nq + i) * nk + kk]
    block_q = q_ref.shape[1]

    @pl.when(kk == 0)
    def _():
        if sink:
            m_ref[...] = sink_ref[0]
            l_ref[...] = jnp.ones(l_ref.shape, jnp.float32)
        else:
            m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
            l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def tile(masked: bool):
        k, v = k_ref[0], v_ref[0]                          # (keys, D)
        if masked:
            kp = kpos_ref[0]                               # (keys, lanes)
            kp = jnp.concatenate([kp] * (block_q // kp.shape[1]), axis=1)
            qp = qpos_ref[0]                               # (1, queries)
            seen = kp <= qp
            if window:
                seen &= qp - kp < window
        for g in range(group):
            head = slice(g * d, (g + 1) * d)
            q, post = _prescale(q_ref[0, :, head], scale)
            if tail:
                # a split head: its first part, and its last 64 in their
                # half of a tile (the queries' other half holds zeros)
                st = (_dot(k, q[:, :d - LANES], _NT)
                      + _dot(tail_ref[0], q[:, d - LANES:], _NT))
            else:
                st = _dot(k, q, _NT)                       # (keys, queries)
            head = slice(g * dv, (g + 1) * dv)
            if post != 1.0:
                st = st * post
            if masked:
                st = jnp.where(seen, st, NEG_INF)
            m_prev = m_ref[g]                              # (1, queries)
            m_new = jnp.maximum(m_prev, jnp.max(st, axis=0, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            pt = jnp.exp(st - m_new)
            if masked:
                # (a query that has seen nothing yet and sees nothing
                # here has m_new = -1e30, and exp(0) is no zero)
                pt = jnp.where(seen, pt, 0.0)
            l_ref[g] = alpha * l_ref[g] + jnp.sum(pt, axis=0, keepdims=True)
            m_ref[g] = m_new
            acc_ref[head] = alpha * acc_ref[head] + _dot(
                v, pt.astype(v.dtype), _TN)                # (D, queries)

    pl.when(kind == WHOLE)(lambda: tile(False))
    pl.when(kind == EDGE)(lambda: tile(True))

    @pl.when(kk == nk - 1)
    def _():
        for g in range(group):
            head = slice(g * dv, (g + 1) * dv)
            out = acc_ref[head] / jnp.maximum(l_ref[g], 1e-30)
            o_ref[0, :, head] = out.T.astype(o_ref.dtype)


# jitted on its own, the static arguments few: the windowed layers of a
# program are alike and lower to one function that each of them calls
# (the reason ``flash_attention._forward`` gives)
@functools.partial(jax.jit, static_argnames=(
    "kv_heads", "scale", "window", "block_q", "block_k", "interpret"))
def _chunk_attention(q, qpos, k, v, kpos, sink=None, *, kv_heads, scale,
                     window, block_q, block_k, interpret):
    n, s, f = q.shape
    keys, hd = k.shape[1:]
    d = hd // kv_heads
    dv = v.shape[2] // kv_heads
    whole_group = f // hd
    parts = key_parts(d)
    # query heads a grid step attends, and the steps a key head takes
    group = heads_a_step(block_q, block_k, whole_group, d, dv, q.dtype)
    if group is None:
        raise ValueError("one head's working set is past the VMEM budget")
    steps = whole_group // group
    if len(parts) > 1:
        # a split head's queries as the kernel reads them: the first part,
        # then the last 64 in the half of a tile where the keys' tile
        # holds this key head's (an even head's first), zeros in the other
        qh = q.reshape(n, s, kv_heads, whole_group, d)
        last = qh[..., parts[0]:]
        even = (jax.lax.iota(jnp.int32, kv_heads) % 2 == 0)[:, None, None]
        zero = jnp.zeros_like(last)
        q = jnp.concatenate(
            [qh[..., :parts[0]], jnp.where(even, last, zero),
             jnp.where(even, zero, last)], axis=-1).reshape(n, s, -1)
    d = _query_lanes(d)
    pad = -keys % block_k
    if pad:
        k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in (k, v))
        kpos = jnp.pad(kpos, ((0, 0), (0, pad)), constant_values=NOWHERE)
    nq, nk = s // block_q, (keys + pad) // block_k
    qpos, kpos = qpos.astype(jnp.int32), kpos.astype(jnp.int32)
    ids, kinds = block_table(qpos, kpos, window, block_q, block_k)
    lanes = min(LANES, block_q)

    def at(b, i, kk, ids):
        return ids[(b * nq + i) * nk + kk]

    # grid axis 1 walks the key heads, ``steps`` parts of a group each
    if steps == 1:
        def head_of(j):
            return j
    else:
        def head_of(j):
            return j // steps

    def rows_spec(width):
        return pl.BlockSpec((1, block_q, group * width),
                            lambda b, j, i, kk, ids, kinds: (b, i, j))

    def keys_spec(width, lane_block):
        return pl.BlockSpec(
            (1, block_k, width),
            lambda b, j, i, kk, ids, kinds: (b, at(b, i, kk, ids),
                                             lane_block(head_of(j))))

    more_in, more = [], []
    if len(parts) > 1:
        # the tile of K that holds key head j's last 64 numbers: behind
        # all heads' first parts, two heads a tile
        first = kv_heads * parts[0] // LANES
        more_in.append(keys_spec(LANES, lambda j: first + j // 2))
        more.append(k)
    if sink is not None:
        more_in.append(pl.BlockSpec(
            (1, group, 1, block_q),
            lambda b, j, i, kk, ids, kinds: (j, 0, 0, 0)))
        more.append(jnp.broadcast_to(
            sink.astype(jnp.float32).reshape(-1, group, 1, 1),
            (kv_heads * steps, group, 1, block_q)))
    row = pltpu.VMEM((group, 1, block_q), jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n, kv_heads * steps, nq, nk),
        in_specs=[
            rows_spec(d),
            pl.BlockSpec((1, 1, block_q),
                         lambda b, j, i, kk, ids, kinds: (b, 0, i)),
            keys_spec(parts[0], lambda j: j), keys_spec(dv, lambda j: j),
            pl.BlockSpec(
                (1, block_k, lanes),
                lambda b, j, i, kk, ids, kinds: (b, at(b, i, kk, ids), 0)),
        ] + more_in,
        out_specs=rows_spec(dv),
        scratch_shapes=[row, row,
                        pltpu.VMEM((group * dv, block_q), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, window=window, group=group,
                          d=d, dv=dv, tail=len(parts) > 1,
                          sink=sink is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, s, f // hd * kv_heads * dv),
                                       q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="chunk_attention",
    )(ids.reshape(-1), kinds.reshape(-1), q, qpos[:, None, :], k, v,
      jnp.broadcast_to(kpos[:, :, None], kpos.shape + (lanes,)), *more)


def chunk_attention(q, qpos, k, v, kpos, *, kv_heads: int, scale: float,
                    window: Optional[int] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    sink: Optional[jax.Array] = None) -> jax.Array:
    """A chunk's queries attended over plain keys and values by absolute
    position.

    ``q``: (n, S, H*D) at positions ``qpos`` (n, S); ``k``: (n, L,
    Hkv*D) and ``v``: (n, L, Hkv*Dv), ``kv_heads`` heads side by side (K's
    as ``paged_attention.split_heads`` lays them), row r of request i
    holding position ``kpos[i, r]`` (:data:`NOWHERE`: nothing); ``sink``:
    (H,) or None. A query at ``p``
    sees the keys at ``p - window + 1 .. p`` (``window`` None or 0: all up
    to ``p``). ``block_q`` / ``block_k`` (rows a tile) are for tests and
    tuning. Returns (n, S, H*Dv) in the queries' dtype; a query that sees
    nothing gets zeros. Callers check :func:`supported` first."""
    s = q.shape[1]
    if block_q is None or block_k is None:
        took = blocks(s, k.shape[1])
        if took is None:
            raise ValueError(f"no block divides a chunk of {s} queries")
        block_q, block_k = block_q or took[0], block_k or took[1]
    if s % block_q:
        raise ValueError(f"blocks of {block_q} queries do not divide {s}")
    return _chunk_attention(
        q, qpos, k, v, kpos, sink, kv_heads=int(kv_heads), scale=float(scale),
        window=int(window) if window else None, block_q=int(block_q),
        block_k=int(block_k), interpret=pallas_mode() == "interpret")


__all__ = ["NOWHERE", "block_table", "blocks", "chunk_attention",
           "supported"]
