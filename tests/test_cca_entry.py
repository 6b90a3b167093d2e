"""``CcaEntry``: a pair a token AND a row a request in one layer
(serving/cache_entry.py), at toy widths on the CPU: a prompt in chunks
with a partial last one, then step-by-step decoding, against the
reference's whole forward (the convolutions' tail and the shifted value
carried through both); chunks against the bucketed prefill; a row handed
on; the dense generator; a pool that holds three kinds' arenas, its
bytes and its rows; and the seven older models' pools, byte for byte
what they were."""

import os
import sys

import numpy as np
import pytest

import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import zaya as reference  # noqa: E402
from flexflow_tpu import FFConfig, FFModel  # noqa: E402
from flexflow_tpu.ffconst import CompMode  # noqa: E402
from flexflow_tpu.models import zoo_smoke_builders  # noqa: E402
from flexflow_tpu.serving import cache_entry  # noqa: E402
from flexflow_tpu.serving.cache_entry import (CcaEntry, PairEntry,  # noqa: E402
                                              SsmStateEntry)
from flexflow_tpu.serving.generation import Generator, PagedDecoder  # noqa: E402
from flexflow_tpu.serving.kv_cache import PagedKVPool, pool_bytes  # noqa: E402
from test_zaya import MAX_LEN, TOY, program  # noqa: E402

BLOCK = 8
LAYERS = [f"block{i}_attn" for i in range(TOY["num_hidden_layers"])]


@pytest.fixture(scope="module")
def toy():
    return program(TOY)


@pytest.fixture()
def short_spans(monkeypatch):
    """Key spans of 16: a chunk's attend walks several of them."""
    monkeypatch.setattr(cache_entry, "SPAN_TOKENS", 16)


def _decoder(ff, **kw):
    return PagedDecoder(ff, MAX_LEN, decode_slots=3, block_size=BLOCK,
                        calibrate=False, **kw)


def _prompt(n):
    return np.random.default_rng(n).integers(
        0, TOY["vocab_size"], n).astype(np.int32)


def _reference_rows(weights, toks, n_rows):
    return np.asarray(reference.forward_jit(
        weights, jnp.asarray(toks[None]), TOY, "float32"))[0, -n_rows:]


def _paged_run(dec, prompt, steps, slot=0):
    """The prompt chunk by chunk, then greedy decode steps in ``slot``:
    the logits of each step and the token sequence."""
    n = len(prompt)
    table = dec.pool.try_admit(n + steps + 1)
    for at in range(0, n, dec.prefill_chunk):
        logits = dec.prefill_chunk_at(prompt, table, at)
    rows, toks = [logits], list(prompt)
    for k in range(steps):
        toks.append(int(rows[-1].argmax()))
        tokens = np.zeros(dec.decode_slots, np.int32)
        tables = np.zeros((dec.decode_slots, dec.max_blocks_per_request),
                          np.int32)
        lens = np.zeros(dec.decode_slots, np.int32)
        tokens[slot], lens[slot] = toks[-1], n + k
        tables[slot, :len(table)] = table
        rows.append(dec.decode(tokens, tables, lens)[slot])
    dec.pool.free(table)
    return np.stack(rows), np.asarray(toks, np.int32)


# ---- chunks and steps against the whole forward ---------------------------------

@pytest.mark.parametrize("n,chunk,steps", [
    (1, 16, 6),      # one token: the tail is all zeros but its own row
    (9, 16, 4),      # under a chunk: one partial chunk from zeros
    (16, 16, 4),     # at it
    (39, 16, 8),     # three chunks, a last one of 7
    (40, 8, 5),      # five chunks of one block each
    (50, 24, 3),     # chunks of three blocks, a last one of 2 tokens
], ids=["one", "under", "at", "three-chunks", "block-chunks", "short-last"])
def test_chunked_prefill_and_decode_equal_the_references_forward(
        toy, short_spans, n, chunk, steps):
    """A prompt prefilled in chunks, each behind what the chunks before
    left (the pair through the block table; the last two rows of z and
    the last token's half value from the request's row), then decode
    steps one token at a time: the LOGITS of the reference's cache-free
    forward over the whole sequence. 2e-4 of the logits' range: float32
    summation order."""
    ff, weights = toy
    dec = _decoder(ff, prefill_chunk=chunk)
    assert dec.attention_path == {"decode": "gather", "chunk": "scan",
                                  "decode_chunk_tokens": None}
    rows, toks = _paged_run(dec, _prompt(n), steps, slot=1)
    want = _reference_rows(weights, toks, len(rows))
    assert np.abs(rows - want).max() <= 2e-4 * np.abs(want).max()
    kv = dec.pool.stats()
    assert kv["entry"] == "cca"
    assert (kv["kv_heads"], kv["query_heads"]) == (2, 4)


@pytest.mark.parametrize("n", [5, 16, 29])
def test_chunks_leave_what_the_bucketed_prefill_leaves(toy, n):
    """The same prompt through ``prefill`` (one bucket from zeros) and
    through chunks of 8: the same tail, the same half value, the same
    keys and values at the prompt's positions; and the tail is the last
    two rows of ``z``, the reference's q~ and k~ of the last two tokens."""
    ff, weights = toy
    prompt = _prompt(n)
    left = []
    for kw in (dict(prefill_buckets=[32]), dict(prefill_chunk=8)):
        dec = _decoder(ff, **kw)
        table = dec.pool.try_admit(n + 1)
        dec.prefill(prompt, table)
        row = int(dec.pool.rows_of(table[None])[0])
        flat = (table[np.arange(n) // BLOCK] * BLOCK + np.arange(n) % BLOCK)
        left.append([(np.asarray(k).reshape(-1, k.shape[-1])[flat],
                      np.asarray(v).reshape(-1, v.shape[-1])[flat],
                      np.asarray(t)[row], np.asarray(p)[row])
                     for k, v, t, p in (dec.pool.kv[name]
                                        for name in LAYERS)])
    for bucketed, chunked in zip(*left):
        for a, b in zip(bucketed, chunked):
            assert np.abs(a - b).max() <= 1e-5 * max(np.abs(a).max(), 1e-6)
    # layer 0's tail against the reference's own projections
    w = {k[3:]: v.astype(jnp.float32) for k, v in weights.items()
         if k.startswith("l0.")}
    x = weights["embed"].astype(jnp.float32)[prompt]
    u = x * jnp.reciprocal(jnp.sqrt(jnp.mean(x * x, -1, keepdims=True)
                                    + TOY["rms_norm_eps"])) * w["norm_attn"]
    z = jnp.concatenate([jnp.einsum("se,ehd->shd", u, w["wq"]),
                         jnp.einsum("se,ehd->shd", u, w["wk"])], 1)
    z = np.pad(np.asarray(z).reshape(n, -1), ((2, 0), (0, 0)))[-2:]
    assert np.abs(left[1][0][2] - z.reshape(-1)).max() < 1e-5
    half = np.asarray(jnp.einsum("e,egd->gd", u[-1], w["wv2"])).reshape(-1)
    assert np.abs(left[1][0][3] - half).max() < 1e-5


def test_a_row_that_held_another_requests_tail_starts_from_zeros(toy):
    """A request's first chunk does not read the row: a row handed on
    from a retired request, tail and half value and all, gives the logits
    a fresh pool gives."""
    ff, _ = toy
    dec = _decoder(ff, prefill_chunk=16)
    fresh, _ = _paged_run(dec, _prompt(21), 3)
    for name in LAYERS:                       # every row full of rubbish
        k, v, tails, prevs = dec.pool.kv[name]
        dec.pool.kv[name] = (k, v, jnp.full_like(tails, 7.0),
                             jnp.full_like(prevs, -3.0))
    again, _ = _paged_run(dec, _prompt(21), 3)
    assert np.array_equal(fresh, again)


def test_two_slots_step_side_by_side(toy):
    """Two requests of different lengths decoding in one step, a third
    slot idle: each one's logits are what it gets alone (a slot's tail
    and half value are its own row's, the idle slot's the null row's)."""
    ff, _ = toy
    dec = _decoder(ff, prefill_chunk=16)
    alone = [_paged_run(dec, _prompt(n), 3, slot=s)
             for s, n in ((0, 7), (2, 19))]
    tables = [dec.pool.try_admit(n + 4) for n in (7, 19)]
    for (rows, toks), table, n in zip(alone, tables, (7, 19)):
        for at in range(0, n, 16):
            dec.prefill_chunk_at(toks[:n], table, at)
    for k in range(3):
        tokens = np.zeros(3, np.int32)
        tabs = np.zeros((3, dec.max_blocks_per_request), np.int32)
        lens = np.zeros(3, np.int32)
        for slot, (rows, toks), table, n in zip((0, 2), alone, tables,
                                                (7, 19)):
            tokens[slot], lens[slot] = toks[n + k], n + k
            tabs[slot, :len(table)] = table
        out = dec.decode(tokens, tabs, lens)
        for slot, (rows, _) in zip((0, 2), alone):
            assert np.abs(out[slot] - rows[k + 1]).max() <= 1e-5
    for table in tables:
        dec.pool.free(table)


def test_the_dense_generator_carries_tail_and_value_too(toy):
    """``Generator`` (the dense cache: ``dense_shapes``, ``dense_step``)
    generates what the paged programs generate."""
    ff, _ = toy
    prompt = _prompt(13)
    dense = Generator(ff, MAX_LEN, 1).generate(prompt[None], 5)[0]
    _, toks = _paged_run(_decoder(ff, prefill_chunk=8), prompt, 5)
    assert np.array_equal(np.asarray(dense), toks)


def test_the_kind_has_no_int8_form_and_one_token_a_step(toy):
    ff, _ = toy
    with pytest.raises(ValueError, match="no int8 form"):
        _decoder(ff, kv_dtype="int8")
    kind = _decoder(ff).pool.kinds[LAYERS[0]]
    assert isinstance(kind, CcaEntry) and kind.max_window == 1
    assert kind.chunked and kind.arenas(2, 8, jnp.float32) and kind.keeps_row


# ---- the pool: a row a token and a row a request, arena by arena --------------------

CCA = CcaEntry(2, 128, 8, tail=2, channels=1280)     # the published widths
PAIR = PairEntry(2, 128, 8)
SSM = SsmStateEntry(4, 8, 16, 3, 96)


def test_a_token_and_a_request_weigh_what_the_arithmetic_says():
    """At ZAYA1-8B's widths in bfloat16: 2 x 256 numbers a token (1,024
    B: 20,480 B over 20 layers), and a request 2 x 1,280 + 128 numbers
    (5,376 B: 0.1 MB over 20 layers)."""
    bf16 = jnp.bfloat16
    assert CCA.token_bytes(bf16) == 1024 == PAIR.token_bytes(bf16)
    assert CCA.request_bytes(bf16) == (2 * 1280 + 128) * 2 == 5376
    assert PAIR.request_bytes(bf16) == 0 and not PAIR.keeps_row
    assert SSM.token_bytes(bf16) == 0 and SSM.keeps_row     # all of it a row
    assert [a.shape for a in CCA.arenas(5, 64, bf16)] == [(5, 64, 256)] * 2
    assert [a.shape for a in CCA.request_arenas(7, 64, bf16)] == [(7, 2560),
                                                              (7, 128)]
    specs = {f"l{i}": CCA for i in range(20)}
    # the cell's pool: 48 worst-case slots of 4,608 tokens, and 49 rows
    blocks = 48 * 72 + 1
    assert pool_bytes(specs, blocks, 64, "bfloat16", bf16, 49) \
        == blocks * 64 * 20480 + 49 * 20 * 5376 == 4536427520


def test_a_pool_of_three_kinds_allocates_counts_and_frees():
    """A ``CcaEntry``, a ``PairEntry`` and an ``SsmStateEntry`` in one
    pool: the pair arenas of the first two get the blocks, the Mamba
    state and the CCA tails the rows; the bytes are the sum of the terms;
    admission takes blocks AND a row, ``free`` returns both; the books
    count a stepped row for the two kinds that keep one."""
    f32 = jnp.float32
    pool = PagedKVPool({"cca": CCA, "pair": PAIR, "ssm": SSM}, num_blocks=9,
                       block_size=8, max_blocks_per_request=3, dtype=f32,
                       num_rows=4)
    shapes = {n: [a.shape for a in e] for n, e in pool.kv.items()}
    assert shapes["cca"] == [(9, 8, 256)] * 2 + [(4, 2560), (4, 128)]
    assert shapes["pair"] == [(9, 8, 256)] * 2
    assert shapes["ssm"] == [(4, 16, 32), (4, 288)]
    per_token = 2 * 256 * 4 * 2
    per_row = (2560 + 128) * 4 + (16 * 32) * 4 + 288 * 4
    assert pool.memory_bytes() == 9 * 8 * per_token + 4 * per_row
    assert pool.memory_bytes() == sum(
        a.size * a.dtype.itemsize for e in pool.kv.values() for a in e)
    assert pool.stats()["state"]["row_bytes"] == per_row
    a, b = pool.try_admit(20), pool.try_admit(9)
    assert pool.in_use() == 5 and sorted(pool.rows_of(np.stack([a, b]))) \
        == [1, 2]
    third = pool.try_admit(8)
    assert pool.try_admit(8) is None          # blocks are left, rows are not
    pool.free(a)
    assert pool.in_use() == 3 and pool.stats()["state"]["in_use"] == 2
    again = pool.try_admit(8)                 # and takes the freed row
    assert sorted(pool.rows_of(np.stack([b, third, again]))) == [1, 2, 3]
    pool.count_step(np.array([3, 9]))
    pool.count_chunk(0)
    pool.count_chunk(16)
    st = pool.stats(np.array([3, 9]))["state"]
    assert (st["rows_stepped"], st["rows_started"], st["rows_carried"]) \
        == (2 * 2, 2, 2)
    for t in (b, third, again):
        pool.free(t)
    assert pool.in_use() == 0 and pool.stats()["state"]["in_use"] == 0
    with pytest.raises(RuntimeError, match="double free"):
        pool.free(b)


# what PR 50's parent allocated for the zoo's presets (2 slots, contexts
# of 32, blocks of 8; 4 for the sparse one): the pool's bytes, its rows
# and the first op's arenas, as the parent's tree printed them
PARENT_POOLS = {
    ("gpt", "float32"): (36864, 0, [(9, 8, 32), (9, 8, 32)]),
    ("gpt", "bfloat16"): (18432, 0, [(9, 8, 32), (9, 8, 32)]),
    ("latent_moe", "float32"): (73728, 0, [(9, 8, 128)]),
    ("latent_moe", "bfloat16"): (36864, 0, [(9, 8, 128)]),
    ("hybrid", "float32"): (50688, 3, [(3, 8, 64), (3, 384)]),
    ("hybrid", "bfloat16"): (34560, 3, [(3, 8, 64), (3, 384)]),
    ("sparse_hybrid", "float32"): (20096, 3, [(3, 4, 8, 8)]),
    ("sparse_hybrid", "bfloat16"): (14656, 3, [(3, 4, 8, 8)]),
    ("nemotron_h", "float32"): (14592, 3, [(3, 8, 32), (3, 192)]),
    ("nemotron_h", "bfloat16"): (8832, 3, [(3, 8, 32), (3, 192)]),
    ("trinity", "float32"): (55296, 3, [(6, 8, 32), (6, 8, 32)]),
    ("trinity", "bfloat16"): (27648, 3, [(6, 8, 32), (6, 8, 32)]),
    ("granite_hybrid", "float32"): (27264, 3, [(3, 8, 64), (3, 240)]),
    ("granite_hybrid", "bfloat16"): (19776, 3, [(3, 8, 64), (3, 240)]),
}


@pytest.mark.parametrize("model,kv_dtype", sorted(PARENT_POOLS))
def test_the_older_kinds_pools_are_byte_for_byte_what_they_were(model,
                                                                 kv_dtype):
    """The seven kinds that were there say as they did whether an arena
    is a row a token or a row a request: the pools of the presets whose
    lowered programs ``tests/test_hybrid_lm.py`` guards weigh what they
    weighed on the parent commit, row for row."""
    ff = FFModel(FFConfig(batch_size=2, seed=0, ledger="off",
                          search_cache="off",
                          computation_mode=CompMode.INFERENCE))
    zoo_smoke_builders()[model](ff, 2)
    ff.compile(optimizer=None, loss_type=None, metrics=[])
    dec = PagedDecoder(ff, 32, decode_slots=2,
                       block_size=4 if model == "sparse_hybrid" else 8,
                       prefill_buckets=[16], kv_dtype=kv_dtype,
                       calibrate=False)
    first = sorted(dec.pool.kv.items())[0][1]
    assert (dec.pool.memory_bytes(), dec.pool.num_rows,
            [tuple(a.shape) for a in first]) == PARENT_POOLS[model, kv_dtype]
    assert dec.pool.memory_bytes() == sum(
        a.size * a.dtype.itemsize for e in dec.pool.kv.values() for a in e)
