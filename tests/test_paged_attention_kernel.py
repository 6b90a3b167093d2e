"""The paged-attention decode kernel against the jnp path it replaces,
through the Pallas interpreter (tier-1 has no chip): same arena, same
tables, same answer. The compiled kernel at the benchmark cell's shapes
is in tests_tpu/test_compiled_kernels.py."""

import math

import numpy as np
import pytest

import jax.numpy as jnp

from flexflow_tpu.kernels import latent_attention, paged_attention
from flexflow_tpu.ops.attention import MultiHeadAttention
from flexflow_tpu.serving.cache_entry import PairEntry, _attend
from flexflow_tpu.serving.kv_cache import NULL_BLOCK, Addresses

HEAD_DIM, BLOCK, MAX_BLOCKS = 64, 16, 20
MAX_LENGTH = BLOCK * MAX_BLOCKS          # 320 tokens: more than one chunk
HUGE = 3.0e4                             # "large finite": garbage to mask

# The largest difference allowed, as a share of the largest output (a
# slot's output is a convex mix of V rows, about 4 at most here).
# float32 arenas: both paths do float32 arithmetic in different orders (a
# running softmax over chunks against one softmax over the table), which
# moves the last bits: 3e-7 of the largest read; 5e-6 leaves room and is
# a thousandth of what one bfloat16 rounding would move. bfloat16
# arenas: both outputs are rounded to bfloat16 on their way out, and the
# jnp path rounds scores and probabilities to it as well where the kernel
# keeps them float32 until the matrix product, so they differ by a last
# place of bfloat16 (2**-7 of a value just over a power of two: 0.03125
# read at an output of 4.1) and never by two: 2**-6.
TOLERANCE = {"float32": 5e-6, "bfloat16": 2.0 ** -6}


class _Op:
    """The attention op's face as ``PairEntry.step`` sees it (the op's
    own projections), with identity weights so the test drives q, k and
    v directly."""
    use_bias = False
    qk_norm = False
    rotary = None
    gate = False
    sinks = False
    value_scale = None
    head_dim = HEAD_DIM
    _scale = None                      # no scale of the model's own
    scale = MultiHeadAttention.scale
    project_qkv = MultiHeadAttention.project_qkv
    sink = MultiHeadAttention.sink
    project_out = MultiHeadAttention.project_out
    _project_out = MultiHeadAttention._project_out


def _case(heads, dtype, window, seed=0):
    """A pool whose null block and every row past a slot's window hold
    large finite garbage, tables over shuffled blocks, and ragged
    lengths: an inactive slot (0, all-null table), one exactly on a
    block boundary, one a block minus one, one that ends at the table's
    last row, and one mid-block across the chunk boundary."""
    rng = np.random.default_rng(seed)
    hd = heads * HEAD_DIM
    lens = np.array([0, 3 * BLOCK, 5 * BLOCK - 1, MAX_LENGTH - window,
                     267], np.int32)
    n = lens.size
    nb = n * MAX_BLOCKS + 1
    k = np.full((nb, BLOCK, hd), HUGE, np.float32)
    v = np.full((nb, BLOCK, hd), -HUGE, np.float32)
    tables = np.full((n, MAX_BLOCKS), NULL_BLOCK, np.int32)
    perm = rng.permutation(np.arange(1, nb))
    for i, length in enumerate(lens):
        if length == 0:
            continue
        tables[i] = perm[i * MAX_BLOCKS:(i + 1) * MAX_BLOCKS]
        for pos in range(length):      # what earlier steps cached
            blk, off = tables[i, pos // BLOCK], pos % BLOCK
            k[blk, off] = rng.normal(size=hd)
            v[blk, off] = rng.normal(size=hd)
    x = rng.normal(size=(n, window, hd)).astype(np.float32)
    return (jnp.asarray(x, dtype), (jnp.asarray(k, dtype),
                                    jnp.asarray(v, dtype)),
            jnp.asarray(tables), jnp.asarray(lens))


def _identity_weights(heads, dtype):
    hd = heads * HEAD_DIM
    eye = np.eye(hd, dtype=np.float32).reshape(hd, heads, HEAD_DIM)
    w = jnp.asarray(eye, dtype)
    return {"wq": w, "wk": w, "wv": w,
            "wo": jnp.asarray(eye.reshape(heads, HEAD_DIM, hd), dtype)}


def _run(monkeypatch, mode, heads, dtype, window):
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", mode)
    x, entry, tables, lens = _case(heads, dtype, window)
    out, new_entry = PairEntry(heads, HEAD_DIM).step(
        _Op(), _identity_weights(heads, dtype), x, None, entry,
        Addresses(tables), lens)
    return np.asarray(out, np.float32), new_entry, np.asarray(lens)


@pytest.mark.parametrize("window", [1, 3])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("heads", [16, 20])
def test_kernel_matches_jnp_path(monkeypatch, heads, dtype, window):
    """Heads of 64 at GPT-2 medium's and large's counts, both arena
    dtypes, the decode step and a verify window: the kernel's output is
    the jnp path's within the dtype's tolerance, for active and inactive
    slots alike, and the garbage never shows."""
    q_shape = (5, window, heads, HEAD_DIM)
    arena = (5 * MAX_BLOCKS + 1, BLOCK, heads * HEAD_DIM)
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    assert paged_attention.supported(q_shape, arena, dtype, MAX_BLOCKS)
    got, got_entry, lens = _run(monkeypatch, "interpret", heads, dtype,
                                window)
    want, want_entry, _ = _run(monkeypatch, "off", heads, dtype, window)
    assert np.isfinite(got).all()
    active = lens > 0
    worst = float(np.abs(got[active] - want[active]).max())
    bound = TOLERANCE[dtype] * float(np.abs(want[active]).max())
    assert worst <= bound, f"off by {worst:.3e} > {bound:.3e}"
    assert float(np.abs(got[active]).max()) < 10.0, "garbage leaked"
    # both paths wrote the same rows into the same arenas
    for a, b in zip(got_entry, want_entry):
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32))


def _wide_case(dtype, seed=0):
    """The chains cell's layout cut small: 8 query heads on 2 key-value
    heads of 128 (rows of 256 lanes), blocks of 64, tables of three of
    the chunks THE RULE gives this width and dtype, over shuffled blocks;
    the null block and every row past a slot's own hold large finite
    garbage. Lengths (the step's own row counted): a slot that ends in a
    part-full chunk, an idle one between two live ones (the next slot's
    first chunk is fetched behind it), one that spans exactly one chunk,
    one exactly three, and one a row into its second."""
    heads, kv_heads, head_dim, block = 8, 2, 128, 64
    hd = kv_heads * head_dim
    chunk = paged_attention.chunk_tokens((1, block, hd), dtype, 10 ** 6)
    max_blocks = 3 * chunk // block
    rng = np.random.default_rng(seed)
    lens = np.array([chunk + chunk // 3, 0, chunk - 1, 3 * chunk - 1,
                     chunk], np.int32)
    n = lens.size
    nb = n * max_blocks + 1
    k = np.full((nb, block, hd), HUGE, np.float32)
    v = np.full((nb, block, hd), -HUGE, np.float32)
    tables = np.full((n, max_blocks), NULL_BLOCK, np.int32)
    perm = rng.permutation(np.arange(1, nb)).reshape(n, max_blocks)
    for i, length in enumerate(lens):
        if length == 0:
            continue
        tables[i] = perm[i]
        pos = np.arange(length + 1)        # cached, and the step's own
        for arena in (k, v):
            arena[tables[i, pos // block], pos % block] = rng.normal(
                size=(pos.size, hd))
    q = rng.normal(size=(n, 1, heads, head_dim)).astype(np.float32)
    return (jnp.asarray(q, dtype), jnp.asarray(k, dtype),
            jnp.asarray(v, dtype), jnp.asarray(tables), jnp.asarray(lens),
            chunk)


@pytest.mark.parametrize("dtype,chunk_tokens", [("float32", 512),
                                                ("bfloat16", 1024)])
def test_kernel_matches_jnp_path_at_two_wide_heads(monkeypatch, dtype,
                                                   chunk_tokens):
    """Rows of two key-value heads of 128 get the chunk their bytes ask
    (a MiB of K and V: 1,024 tokens of bfloat16, 512 of float32), and
    with it the kernel's output is the gather's within the dtype's
    tolerance for slots that end part-way into a chunk, on a chunk's
    last row, a row past it, and behind an idle slot; the garbage never
    shows."""
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    q, k, v, tables, lens, chunk = _wide_case(dtype)
    assert chunk == chunk_tokens
    assert paged_attention.supported(q.shape, k.shape, k.dtype,
                                     tables.shape[1])
    got = np.asarray(paged_attention.paged_attention_decode(
        q, k, v, tables, lens))
    kk, vv = PairEntry(2, 128, 8).read((k, v), tables)
    seen = jnp.arange(kk.shape[1])[None, None, :] <= lens[:, None, None]
    want = np.asarray(_attend(q, kk, vv, lambda: seen[:, None], 128 ** -0.5),
                      np.float32)
    assert np.isfinite(got).all()
    active = np.asarray(lens) > 0
    # the gather path rounds its output to the arena's dtype
    got = np.asarray(jnp.asarray(got, dtype), np.float32)
    worst = float(np.abs(got[active] - want[active]).max())
    bound = TOLERANCE[dtype] * float(np.abs(want[active]).max())
    assert worst <= bound, f"off by {worst:.3e} > {bound:.3e}"
    assert float(np.abs(got[active]).max()) < 10.0, "garbage leaked"


@pytest.mark.parametrize("shape", ["gpt2", "two-wide-heads"])
def test_kernel_reads_live_blocks_only(monkeypatch, shape):
    """A block past a slot's last live one is never fetched: NaN there
    (which no mask could hide from a matrix product) changes nothing; at
    GPT-2's heads in chunks of 128 tokens, and at two key-value heads of
    128 in the chunk the rule gives them, 512 tokens."""
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    if shape == "gpt2":
        heads, block = 16, BLOCK
        x, (k, v), tables, lens = _case(heads, "float32", 1)
        q = x.reshape(x.shape[0], 1, heads, HEAD_DIM)
        # chunks of 128 tokens: the long slots walk two and three of them
        pages = 8
    else:
        q, k, v, tables, lens, _ = _wide_case("float32")
        block, pages = 64, None
    clean = np.asarray(paged_attention.paged_attention_decode(
        q, k, v, tables, lens, pages_per_chunk=pages))
    live = {NULL_BLOCK}
    for row, length in zip(np.asarray(tables), np.asarray(lens)):
        live.update(row[:math.ceil((length + 1) / block)].tolist())
    dead = np.array(sorted(set(range(k.shape[0])) - live))
    assert dead.size > 0
    k = k.at[dead].set(jnp.nan)
    v = v.at[dead].set(jnp.nan)
    dirty = np.asarray(paged_attention.paged_attention_decode(
        q, k, v, tables, lens, pages_per_chunk=pages))
    assert np.array_equal(clean, dirty)


# (slots, query heads, key-value heads, head width, block, table), the
# arenas bfloat16: each serving cell's pool as its traffic file and
# configuration make it, and the pages and tokens of a chunk there. The
# first five are what every PR before 51 ran (256 tokens): a change to the
# rule that moves one of them moves a cell the change was not made for.
CELL_CHUNKS = {
    "offline": ((16, 20, 20, 64, 16, 64), 16, 256),
    "documents": ((32, 30, 30, 128, 16, 128), 16, 256),
    "mixedlengths-ring": ((32, 48, 8, 128, 64, 64), 4, 256),
    "mixedlengths-full": ((32, 48, 8, 128, 64, 272), 4, 256),
    "agents": ((128, 32, 2, 128, 16, 128), 16, 256),
    "retrieval": ((48, 32, 8, 64, 64, 144), 8, 512),
    "chains": ((48, 8, 2, 128, 64, 72), 16, 1024),
}


@pytest.mark.parametrize("cell", list(CELL_CHUNKS) + ["reasoning"])
def test_chunk_follows_the_row_bytes(monkeypatch, cell):
    """The pages and tokens a loop iteration brings at each serving
    cell's shape: K and V together a MiB where 16 pages hold it, never
    under 256 tokens; ``supported()`` takes every one, and reckons the
    working set with the chunk the rule gives (both buffers of K and V
    and the iteration's float32 temporaries). The latent kernel's chunk,
    which borrows the helper, is the 512 tokens it was."""
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    if cell == "reasoning":
        assert latent_attention._pages_per_chunk(16, 256) == 32
        return
    (slots, heads, kv_heads, head_dim, block, table), pages, tokens = \
        CELL_CHUNKS[cell]
    hd = kv_heads * head_dim
    arena = (slots * table + 1, block, hd)
    assert paged_attention._pages_per_chunk(block, table, 2 * hd) == pages
    assert paged_attention.chunk_tokens(arena, "bfloat16", table) == tokens
    assert pages * block == tokens and tokens % 128 == 0
    # float32 rows are twice the bytes: half the tokens, from 256 up
    assert paged_attention.chunk_tokens(arena, "float32", table) == max(
        256, tokens // 2)
    assert paged_attention.supported((slots, 1, heads, head_dim), arena,
                                     "bfloat16", table)
    m = -(-heads // 16) * 16
    reckoned = paged_attention._vmem_bytes(
        -(-slots // 8) * 8, 1, heads, head_dim, block, table, "bfloat16",
        kv_heads)
    assert (2 * 2 * tokens * hd * 2 + 4 * 3 * m * tokens <= reckoned
            <= paged_attention.VMEM_BUDGET_BYTES)


@pytest.mark.parametrize("why,heads,head_dim,block,dtype,window", [
    ("head width 8: rows are no whole lane tiles", 4, 8, 8, "float32", 1),
    ("blocks of 8 are half a bfloat16 sublane tile", 16, 64, 8,
     "bfloat16", 1),
    ("int8 arenas are dequantised by the jnp path", 16, 64, 16, "int8", 1),
    ("a window of 16 x 32 padded heads is too many rows", 20, 64, 16,
     "bfloat16", 16),
])
def test_supported_refuses(monkeypatch, why, heads, head_dim, block, dtype,
                           window):
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    assert not paged_attention.supported(
        (4, window, heads, head_dim), (33, block, heads * head_dim), dtype,
        8), why


def test_refused_entries_take_the_jnp_path(monkeypatch):
    """What ``supported()`` refuses still decodes, through the gather:
    the zoo's toy width (heads of 8, blocks of 8) and an int8 entry
    give the same logits with kernels on as with kernels off, and
    ``attention_path`` says which path a decoder's programs took."""
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.ffconst import CompMode
    from flexflow_tpu.models.gpt import GPTConfig, build_gpt
    from flexflow_tpu.serving.generation import PagedDecoder

    ff = FFModel(FFConfig(batch_size=2, seed=0, ledger="off",
                          computation_mode=CompMode.INFERENCE))
    build_gpt(ff, 2, 6, GPTConfig(vocab_size=48, max_positions=32,
                                  hidden_size=32, num_heads=4,
                                  num_layers=1))
    ff.compile(optimizer=None, loss_type=None, metrics=[])
    prompt = np.arange(5, dtype=np.int32)
    logits = {}
    for mode in ("interpret", "off"):
        monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", mode)
        for kv_dtype in ("float32", "int8"):
            dec = PagedDecoder(ff, max_length=32, decode_slots=2,
                               block_size=8, kv_dtype=kv_dtype,
                               kv_divergence_budget=10.0)
            assert dec.attention_path == {"decode": "gather", "chunk": None,
                                          "decode_chunk_tokens": None}
            table = dec.pool.try_admit(8)
            dec.prefill(prompt, table)
            tables = np.zeros((2, dec.max_blocks_per_request), np.int32)
            tables[0] = table
            logits[mode, kv_dtype] = dec.decode(
                np.array([7, 0], np.int32), tables,
                np.array([5, 0], np.int32))[0]
    for kv_dtype in ("float32", "int8"):
        assert np.array_equal(logits["interpret", kv_dtype],
                              logits["off", kv_dtype])


def test_decoder_reports_the_kernel_path(monkeypatch):
    """A model of GPT-2's head width takes the kernel for its decode
    step and its verify window, says so in ``attention_path``, and
    decodes what the jnp path decodes."""
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.ffconst import CompMode
    from flexflow_tpu.models.gpt import GPTConfig, build_gpt
    from flexflow_tpu.serving.generation import PagedDecoder

    ff = FFModel(FFConfig(batch_size=2, seed=0, ledger="off",
                          computation_mode=CompMode.INFERENCE))
    build_gpt(ff, 2, 6, GPTConfig(vocab_size=48, max_positions=64,
                                  hidden_size=128, num_heads=2,
                                  num_layers=1))
    ff.compile(optimizer=None, loss_type=None, metrics=[])
    prompt = np.arange(21, dtype=np.int32) % 48
    out = {}
    for mode, path in (("interpret", "kernel"), ("off", "gather")):
        monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", mode)
        dec = PagedDecoder(ff, max_length=64, decode_slots=2, block_size=16)
        # a table of 4 blocks of 16: a lane tile of tokens covers it
        chunk = {"kernel": 128, "gather": None}[path]
        assert dec.attention_path == {"decode": path, "chunk": None,
                                      "decode_chunk_tokens": chunk}
        table = dec.pool.try_admit(40)
        dec.prefill(prompt, table)
        tables = np.zeros((2, dec.max_blocks_per_request), np.int32)
        tables[0] = table
        lens = np.array([21, 0], np.int32)
        step = dec.decode(np.array([7, 0], np.int32), tables, lens)[0]
        window = dec.verify(np.array([[7, 9, 11], [0, 0, 0]], np.int32),
                            tables, lens)[0]
        assert dec.attention_path == {"decode": path, "chunk": None,
                                      "decode_chunk_tokens": chunk,
                                      "verify": path}
        out[mode] = (step, window)
    for got, want in zip(out["interpret"], out["off"]):
        assert float(np.abs(got - want).max()) <= 1e-4 * float(
            np.abs(want).max())
