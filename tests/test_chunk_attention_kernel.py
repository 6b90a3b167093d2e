"""The chunk-attention kernel against the span walk it replaces
(``cache_entry._attend_spans``), through the Pallas interpreter (tier-1
has no chip): the same queries, rows and positions, the same answer. The
chunk programs compiled for the chip at the published widths are in
tests/test_tpu_lowering.py, the toy model through the kernel in
tests/test_trinity_lm.py."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flexflow_tpu.kernels import chunk_attention as ca
from flexflow_tpu.ops.attention import MultiHeadAttention
from flexflow_tpu.serving import cache_entry

HEAD_DIM, CHUNK, RING, TILE = 128, 32, 32, 16
NOWHERE = ca.NOWHERE

# The largest difference allowed, as a share of the largest output. Both
# forms keep a running softmax in float32 over key blocks of 16 in the
# same order, so float32 rows differ in the last bits (1e-6 read); with
# bfloat16 rows both round the probabilities to bfloat16 before the
# second product and the result on its way out: a last place of
# bfloat16, never two.
TOLERANCE = {"float32": 5e-6, "bfloat16": 2.0 ** -6}


@pytest.fixture(autouse=True)
def interpreted(monkeypatch):
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")


class _Op:
    """What the walk asks of the attention op."""
    sees = MultiHeadAttention.sees

    def __init__(self, window):
        self.window, self.scale = window, HEAD_DIM ** -0.5


def _ring_case(offsets, lengths, window=RING):
    """``[ring | chunk]`` as ``WindowEntry.chunk`` builds it: ring row r
    holds the last position before the chunk that is r modulo the ring,
    if the request has got that far."""
    offsets, lengths = np.asarray(offsets), np.asarray(lengths)
    pos = offsets[:, None] + np.arange(CHUNK)[None]
    before = offsets[:, None] - 1
    held = before - np.mod(before - np.arange(RING)[None], RING)
    live = np.arange(CHUNK)[None] < lengths[:, None]
    kpos = np.concatenate([np.where(held >= 0, held, NOWHERE),
                           np.where(live, pos, NOWHERE)], axis=1)
    return window, pos, kpos


def _table_case(offsets, lengths, rows=96):
    """A full layer's rows through its table, as ``PairEntry.chunk``
    hands them over: row r at position r, nothing past the chunk."""
    offsets, lengths = np.asarray(offsets), np.asarray(lengths)
    pos = offsets[:, None] + np.arange(CHUNK)[None]
    at = np.arange(rows)[None]
    return None, pos, np.where(at < (offsets + lengths)[:, None], at, NOWHERE)


CASES = {
    # a band, the ring full and stored rotated: the wrap (39 | 8) lies
    # inside the ring's first tile
    "band-rotated-ring": lambda: _ring_case([40], [CHUNK]),
    # a first chunk: the ring holds nothing, its tiles are not visited
    "band-empty-ring": lambda: _ring_case([0], [CHUNK]),
    # a ring that is not full yet (positions 0..15 of 32)
    "band-half-ring": lambda: _ring_case([16], [CHUNK]),
    # a short last chunk: rows past the length hold nothing
    "band-short-chunk": lambda: _ring_case([64], [5]),
    # two requests of different offsets (and lengths) in one call
    "band-two-requests": lambda: _ring_case([0, 72], [CHUNK, 20]),
    # a band narrower than the chunk: late queries see no ring row
    "narrow-band": lambda: _ring_case([64], [CHUNK], window=8),
    # no band: the table's rows, causal, the prompt at 64 of 96
    "table": lambda: _table_case([32], [CHUNK]),
    "table-short-chunk": lambda: _table_case([64], [7]),
    "table-two-requests": lambda: _table_case([0, 64], [CHUNK, 9]),
}


def _inputs(pos, kpos, heads, kv_heads, dtype, seed=0):
    rng = np.random.default_rng(seed)
    n, keys = kpos.shape
    q = rng.normal(size=(n, CHUNK, heads * HEAD_DIM))
    k = rng.normal(size=(n, keys, kv_heads * HEAD_DIM))
    v = rng.normal(size=(n, keys, kv_heads * HEAD_DIM))
    return (jnp.asarray(q, dtype), jnp.asarray(pos, jnp.int32),
            jnp.asarray(k, dtype), jnp.asarray(v, dtype),
            jnp.asarray(kpos, jnp.int32))


def _walk(op, q, qpos, k, v, kpos, kv_heads):
    """The span walk over the same rows, spans of a tile."""
    n, s, f = q.shape
    keys = k.shape[1]
    k4, v4 = (a.reshape(n, keys, kv_heads, HEAD_DIM) for a in (k, v))

    def read(j):
        return tuple(jax.lax.dynamic_slice_in_dim(a, j * TILE, TILE, 1)
                     for a in (k4, v4, kpos))

    return cache_entry._attend_spans(
        op, q.reshape(n, s, f // HEAD_DIM, HEAD_DIM), qpos, kv_heads, read,
        0, keys // TILE).reshape(q.shape)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_gives_the_walks_attention(case, dtype):
    """12 query heads over 2 key-value heads of 128, tiles of 16 x 16."""
    window, pos, kpos = CASES[case]()
    args = _inputs(pos, kpos, 12, 2, jnp.dtype(dtype))
    got = ca.chunk_attention(*args, kv_heads=2, scale=HEAD_DIM ** -0.5,
                             window=window, block_q=TILE, block_k=TILE)
    want = np.asarray(_walk(_Op(window), *args, 2), np.float32)
    assert got.dtype == args[0].dtype and got.shape == args[0].shape
    assert np.abs(np.asarray(got, np.float32) - want).max() \
        <= TOLERANCE[dtype] * np.abs(want).max()


@pytest.mark.parametrize("block_q,block_k", [(32, 16), (8, 32), (16, 64)])
def test_tiles_of_other_sizes_give_the_same(block_q, block_k):
    """Key rows that are no whole tiles are padded with rows that hold
    nothing; a head a key head (6 on 6)."""
    window, pos, kpos = _ring_case([40, 8], [CHUNK, 30])
    args = _inputs(pos, kpos[:, :56], 6, 6, jnp.float32, seed=3)
    got = ca.chunk_attention(*args, kv_heads=6, scale=0.125, window=window,
                             block_q=block_q, block_k=block_k)
    op = _Op(window)
    op.scale = 0.125           # a power of two: folded into the queries
    pad = ((0, 0), (0, 8), (0, 0))
    want = np.asarray(_walk(op, args[0], args[1], jnp.pad(args[2], pad),
                            jnp.pad(args[3], pad), jnp.pad(
                                args[4], pad[:2], constant_values=NOWHERE), 6))
    assert np.abs(np.asarray(got) - want).max() <= 5e-6 * np.abs(want).max()


@pytest.mark.parametrize("where", ["outside-the-band", "past-the-length",
                                   "an-empty-ring"])
def test_a_nan_in_a_key_row_no_query_sees_reaches_no_output(where):
    """Key rows no query sees hold NaN: rows of a tile an edge crosses
    (masked before the maximum and again as probabilities) and whole
    tiles that are not visited (never read); where a whole tile goes
    unseen its values are NaN too."""
    if where == "outside-the-band":
        # queries at 64..95 under a band of 8: the ring (32..63) is seen
        # from row 57 on, the chunk's own rows within 8 of a query
        window, pos, kpos = _ring_case([64], [CHUNK], window=8)
        unseen = kpos[0] < 64 - 7
        whole = kpos[0] < 48                        # the ring's first tile
    elif where == "past-the-length":
        window, pos, kpos = _table_case([32], [5])
        unseen = kpos[0] == NOWHERE
        whole = np.arange(kpos.shape[1]) >= 48
    else:
        window, pos, kpos = _ring_case([0], [CHUNK])
        unseen = whole = kpos[0] == NOWHERE
    q, qpos, k, v, kp = _inputs(pos, kpos, 12, 2, jnp.float32, seed=5)
    clean = ca.chunk_attention(q, qpos, k, v, kp, kv_heads=2, scale=0.1,
                               window=window, block_q=TILE, block_k=TILE)
    k = k.at[0, np.flatnonzero(unseen)].set(jnp.nan)
    v = v.at[0, np.flatnonzero(whole)].set(jnp.nan)
    got = ca.chunk_attention(q, qpos, k, v, kp, kv_heads=2, scale=0.1,
                             window=window, block_q=TILE, block_k=TILE)
    assert np.isfinite(np.asarray(got)).all()
    assert np.array_equal(np.asarray(got), np.asarray(clean))


def test_the_table_names_the_tiles_a_query_block_sees():
    """A full ring rotated by 8 under a band of 32, tiles of 16: a query
    block visits the ring's tiles that hold a position inside its band
    and the chunk's up to its own, the ones no edge crosses unmasked,
    and names its last visit again past them."""
    window, pos, kpos = _ring_case([40], [CHUNK])
    ids, kinds = ca.block_table(jnp.asarray(pos, jnp.int32),
                                jnp.asarray(kpos, jnp.int32), window, TILE,
                                TILE)
    ids, kinds = np.asarray(ids)[0], np.asarray(kinds)[0]
    # ring tile 0 holds 32..39 | 8..15, tile 1 16..31; the chunk's tiles
    # 40..55 and 56..71. Queries 40..55 see 9..55 (the last of them from
    # 24 on), queries 56..71 25..71 (the last from 40 on)
    assert ids[0].tolist() == [0, 1, 2, 2] \
        and kinds[0].tolist() == [ca.EDGE, ca.EDGE, ca.EDGE, ca.SKIP]
    assert ids[1].tolist() == [0, 1, 2, 3] \
        and kinds[1].tolist() == [ca.EDGE, ca.EDGE, ca.WHOLE, ca.EDGE]
    # a first chunk visits no ring tile; a block that sees nothing at all
    # names tile 0 and computes nothing
    _, pos, kpos = _ring_case([0], [CHUNK])
    ids, kinds = ca.block_table(jnp.asarray(pos, jnp.int32),
                                jnp.asarray(kpos, jnp.int32), window, TILE,
                                TILE)
    assert np.asarray(kinds)[0].tolist() == [[ca.EDGE, 0, 0, 0],
                                             [ca.WHOLE, ca.EDGE, 0, 0]]
    assert np.asarray(ids)[0].tolist() == [[2, 2, 2, 2], [2, 3, 3, 3]]
    none = jnp.full((1, 64), NOWHERE, jnp.int32)
    ids, kinds = ca.block_table(jnp.asarray(pos, jnp.int32), none, None,
                                TILE, TILE)
    assert not np.asarray(ids).any() and not np.asarray(kinds).any()
    got = ca.chunk_attention(*_inputs(pos, np.asarray(none), 2, 2,
                                      jnp.float32), kv_heads=2, scale=1.0,
                             block_q=TILE, block_k=TILE)
    assert not np.asarray(got).any()      # a row that sees nothing: zeros


def test_supported_takes_whole_lane_tiles_of_one_dtype(monkeypatch):
    """Heads of 128 in float32 or bfloat16 rows of the queries' own
    dtype, query heads a multiple of the key heads, a chunk some block
    divides; nothing without Pallas."""
    q, kv = (1, 2048, 48, 128), (1, 6144, 8 * 128)
    bf16, f32 = jnp.bfloat16, jnp.float32
    assert ca.supported(q, bf16, kv, bf16) and ca.supported(q, f32, kv, f32)
    assert ca.blocks(2048, 6144) == (512, 512)
    assert not ca.supported(q, f32, kv, bf16)           # two dtypes
    assert not ca.supported(q, jnp.int8, kv, jnp.int8)
    assert not ca.supported((1, 2048, 16, 64), bf16, (1, 6144, 1024), bf16)
    assert not ca.supported((1, 2048, 20, 128), bf16, kv, bf16)  # 20 on 8
    assert not ca.supported((1, 2044, 48, 128), bf16, kv, bf16)  # no block
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "compiled")
    assert ca.blocks(200, 6144) is None and ca.blocks(256, 300) == (256, 384)
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "off")
    assert not ca.supported(q, bf16, kv, bf16)
    monkeypatch.delenv("FLEXFLOW_TPU_PALLAS")
    assert not ca.supported(q, bf16, kv, bf16)          # the CPU
