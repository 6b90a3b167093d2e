"""Mamba-2 and grouped-head attention blocks with a gated MLP each behind
scaled residuals and a head tied to the embedding, at toy widths on the
CPU: the program against the plain reference
(``benchmark/reference/granite_hybrid.py``, which imports nothing of the
program): the whole forward; a prompt prefilled in chunks THROUGH THE
STATES (``SsmStateEntry.chunk``), then decode, against the reference's
full forward; chunks against the bucketed prefill; a row handed on; a NaN
kept to its request; the reference's two forms of the recurrence; the
tied weight (one buffer, a gradient that is the sum of both uses); the
attention op's ``scale``. The programs compiled for the chip at the
published widths are in tests/test_tpu_lowering.py."""

import dataclasses
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.families import granite_hybrid as family  # noqa: E402
from benchmark.reference import granite_hybrid as reference  # noqa: E402
from flexflow_tpu import FFConfig, FFModel  # noqa: E402
from flexflow_tpu.core.op import LowerCtx  # noqa: E402
from flexflow_tpu.ffconst import (CompMode, DataType, LossType,  # noqa: E402
                                  MetricsType)
from flexflow_tpu.models import (GraniteHybridConfig,  # noqa: E402
                                 build_granite_hybrid_lm, zoo_smoke_builders)
from flexflow_tpu.serving import cache_entry  # noqa: E402
from flexflow_tpu.serving.generation import PagedDecoder  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "tests", "data", "configs",
                       "granite-toy.json")) as _f:
    TOY = json.load(_f)
SEED = 2 ** 31 + 5
MAX_LEN = 80
BLOCK = 8
MIXERS = [f"block{i}_mixer" for i, k in enumerate(TOY["layer_types"])
          if k == "mamba"]


def _program(config, seed=SEED, slots=3):
    """The program's graph for ``config`` in float32 holding the
    reference's seeded weights; returns (ff, weights)."""
    cfg = dataclasses.replace(family.program_config(config),
                              param_dtype=DataType.FLOAT, draw_weights=True)
    ff = FFModel(FFConfig(batch_size=slots, ledger="off",
                          computation_mode=CompMode.INFERENCE))
    build_granite_hybrid_lm(ff, slots, MAX_LEN, cfg)
    ff.compile(optimizer=None, loss_type=None, metrics=[])
    weights = reference.init_weights(config, seed)
    ff.compiled.params = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), family.to_program(weights, config))
    ff.compiled.bump_params_version()
    return ff, weights


def _op(ff, name):
    return next(op for op in ff.compiled.ops if op.name == name)


@pytest.fixture(scope="module")
def toy():
    return _program(TOY)


@pytest.fixture()
def short_spans(monkeypatch):
    """Key spans of 16: a chunk's attend walks several of them."""
    monkeypatch.setattr(cache_entry, "SPAN_TOKENS", 16)


def _decoder(ff, **kw):
    return PagedDecoder(ff, MAX_LEN, decode_slots=3, block_size=BLOCK,
                        calibrate=False, **kw)


def _reference_rows(weights, toks, n_rows):
    return np.asarray(reference.forward(
        weights, jnp.asarray(toks[None]), TOY, "float32", rows=n_rows))[0]


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


# ---- the whole forward, the attention op's scale, the tie --------------------

def test_whole_forward_equals_the_references(toy):
    """The whole model cache-free (each kind's ``whole``) against the
    reference's forward over 50 tokens: the multipliers, the pre-norms,
    the gated norm's order, the scale of the scores, the tied head. 2e-4
    of the logits' range: float32 summation order over 4 blocks."""
    ff, weights = toy
    toks = np.random.default_rng(1).integers(
        0, TOY["vocab_size"], 50).astype(np.int32)
    got = _decoder(ff)._dense_reference_logits(toks)
    want = np.asarray(reference.forward(weights, jnp.asarray(toks[None]),
                                        TOY, "float32"))[0]
    assert want.shape == (50, TOY["vocab_size"])
    assert np.abs(got - want).max() <= 2e-4 * np.abs(want).max()
    # a dropped multiplier shows: the reference without the residual's
    # 0.22 is further off than that by orders
    off = np.asarray(reference.forward(
        weights, jnp.asarray(toks[None]), dict(TOY, residual_multiplier=1.0),
        "float32"))[0]
    assert np.abs(got - off).max() > 0.05 * np.abs(want).max()


def test_scale_absent_leaves_one_over_root_d_and_set_is_taken(toy):
    """``MultiHeadAttention.scale``: the attribute where it is set (the
    toy's 0.25 on heads of 8), ``1 / sqrt(head_dim)`` where it is not;
    the op's forward is the reference's attention piece less its norm and
    its residual."""
    ff, weights = toy
    op = _op(ff, "block2_mixer")
    assert op.scale == 0.25 and op.head_dim == 8 and op.rotary is None
    plain = FFModel(FFConfig(batch_size=2, ledger="off",
                             computation_mode=CompMode.INFERENCE))
    x = plain.create_tensor((2, 8, 32), DataType.FLOAT, name="x")
    plain.multihead_attention(x, x, x, 32, 4, causal=True, name="attn")
    plain.compile(optimizer=None, loss_type=None, metrics=[])
    assert _op(plain, "attn").scale == 1.0 / np.sqrt(8.0)
    assert "scale" not in _op(plain, "attn").attrs
    s = 24
    x = jax.random.normal(jax.random.key(3), (2, s, 32))
    lw = {k[3:]: v for k, v in weights.items() if k.startswith("l2.")}
    u = reference._rms(x, lw["norm"], 1e-5)
    got = op.forward(LowerCtx(mesh=None, training=False), [u, u, u],
                     ff.compiled.params[op.name])[0]
    pieces = reference._pieces(reference._key(TOY), "float32", "tokens")
    want = (pieces["attention"](x, lw) - x) / TOY["residual_multiplier"]
    assert np.abs(np.asarray(got - want)).max() <= 2e-5 * float(
        np.abs(want).max())


def test_the_tied_head_reads_the_embeddings_array(toy):
    """The parameter tree holds the table ONCE: no ``lm_head`` entry, the
    head op has no weight of its own and borrows ``embed``'s, and the
    count of parameters is the reference's (the embedding once)."""
    from flexflow_tpu.core.op import weights_of

    ff, weights = toy
    cm = ff.compiled
    head = _op(ff, "lm_head")
    assert head.weight_specs() == [] and head.weight_shapes == {}
    assert head.borrows == {"tied": ("embed", "weight")}
    assert "lm_head" not in cm.params
    assert weights_of(head, cm.params)["tied"] is cm.params["embed"]["weight"]
    n = sum(int(np.prod(a.shape))
            for a in jax.tree_util.tree_leaves(cm.params))
    assert n == reference.param_count(TOY)
    assert sum(int(np.prod(ws.shape)) for op in cm.ops
               for ws in op.weight_specs()) == n
    # a tree that lacks the owner says so by name
    with pytest.raises(ValueError, match="lm_head reads embed's 'weight'"):
        weights_of(head, {})
    # and the builder refuses a tie it cannot make
    bad = FFModel(FFConfig(batch_size=2, ledger="off"))
    t = bad.create_tensor((2, 4), DataType.INT32, name="tokens")
    h = bad.embedding(t, 16, 8, name="embed")
    with pytest.raises(ValueError, match="no embedding layer"):
        bad.dense(h, 16, use_bias=False, tied_to="nobody")
    with pytest.raises(ValueError, match=r"its table is \(16, 8\)"):
        bad.dense(h, 12, use_bias=False, tied_to="embed")


def test_fits_gradient_on_the_tied_matrix_is_the_sum_of_both_uses():
    """A two-layer model trained through ``compile``'s own loss: the
    gradient that reaches the embedding's table is the gradient of the
    same model with an UNTIED head holding a copy of it, its embedding's
    plus its head's transposed."""
    from flexflow_tpu.runtime.compiler import _forward_graph

    def build(tied):
        ff = FFModel(FFConfig(batch_size=2, seed=0, ledger="off",
                              search_cache="off"))
        t = ff.create_tensor((2, 6), DataType.INT32, name="tokens")
        h = ff.embedding(t, 24, 8, name="embed")
        h = ff.dense(h, 8, name="mid")
        ff.dense(h, 24, use_bias=False, name="lm_head",
                 **({"tied_to": "embed"} if tied else {}))
        ff.compile(optimizer=None,
                   loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                   metrics=[MetricsType.SPARSE_CATEGORICAL_CROSSENTROPY])
        return ff.compiled

    tied, untied = build(True), build(False)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 24, (2, 6)),
                       jnp.int32)

    def loss(cm, params):
        acts, _, _ = _forward_graph(cm.ops, None, params,
                                    {cm.input_tensors[0].tensor_id: toks},
                                    False, None)
        logits = acts[cm.logits_tensor.tensor_id]
        return jnp.sum(jax.nn.log_softmax(logits)[..., 0])

    params = dict(tied.params)
    g = jax.grad(lambda p: loss(tied, p))(params)
    assert set(g) == {"embed", "mid"}
    table = params["embed"]["weight"]
    both = dict(params, lm_head={"kernel": table.T})
    gu = jax.grad(lambda p: loss(untied, p))(both)
    want = gu["embed"]["weight"] + gu["lm_head"]["kernel"].T
    assert np.abs(np.asarray(gu["lm_head"]["kernel"])).max() > 0
    assert np.abs(np.asarray(g["embed"]["weight"] - want)).max() \
        <= 1e-5 * float(np.abs(want).max())


# ---- chunks through the states, then decode ----------------------------------

@pytest.mark.parametrize("n,chunk,steps", [
    (9, 16, 4),      # under a chunk: one partial chunk from zeros
    (16, 16, 4),     # at it
    (39, 16, 6),     # past two chunks, a last one of 7
    (40, 8, 5),      # five chunks of one block and a scan block each
    (61, 24, 3),     # chunks that are no multiple of the scan's block of 8
], ids=["under", "at", "past-two", "block-chunks", "odd-chunks"])
def test_chunked_prefill_and_decode_equal_the_references_forward(
        toy, short_spans, n, chunk, steps):
    """A prompt prefilled in chunks, each behind what the chunks before
    left (a Mamba layer behind its state and its convolution's tail, the
    attention layer through its block table), then decode steps: the
    LOGITS of the reference's cache-free forward over the whole sequence.
    2e-4 of the logits' range: float32 summation order."""
    ff, weights = toy
    dec = _decoder(ff, prefill_chunk=chunk)
    assert dec.attention_path == {"decode": "gather", "chunk": "scan",
                                  "decode_chunk_tokens": None}
    prompt = np.random.default_rng(n).integers(
        0, TOY["vocab_size"], n).astype(np.int32)
    rows, toks = _paged_run(dec, prompt, steps, slot=1)
    want = _reference_rows(weights, toks, len(rows))
    assert np.abs(rows - want).max() <= 2e-4 * np.abs(want).max()
    kv = dec.pool.stats()
    assert kv["entry"] == {"ssm_state": 3, "pair": 1}
    assert (kv["kv_heads"], kv["query_heads"]) == (2, 4)
    assert kv["state_dtype"] == "float32"


@pytest.mark.parametrize("n", [11, 29, 32])
def test_chunks_leave_the_states_and_tails_the_bucketed_prefill_leaves(
        toy, n):
    """The same prompt through chunks of 8 and through ONE bucket of 32
    (``SsmStateEntry.prefill``, which this PR leaves as it was): the same
    state and the same convolution tail in every Mamba layer's row, and
    the same logits, to float32 summation order (a chunk boundary is a
    block boundary of the scan); the first layer's tail exactly."""
    ff, _ = toy
    prompt = np.random.default_rng(n).integers(
        0, TOY["vocab_size"], n).astype(np.int32)
    got = []
    for kw in (dict(prefill_buckets=[32]), dict(prefill_chunk=8)):
        dec = _decoder(ff, **kw)
        table = dec.pool.try_admit(n + 1)
        logits = dec.prefill(prompt, table)
        row = int(dec.pool.rows_of(table[None])[0])
        got.append((logits, {m: tuple(np.asarray(a[row])
                                      for a in dec.pool.kv[m])
                             for m in MIXERS}))
    (l0, whole), (l1, chunks) = got
    assert np.abs(l0 - l1).max() <= 2e-4 * np.abs(l0).max()
    for m in MIXERS:
        assert np.abs(whole[m][0] - chunks[m][0]).max() \
            <= 2e-5 * np.abs(whole[m][0]).max()
        assert np.abs(whole[m][1] - chunks[m][1]).max() \
            <= 2e-5 * np.abs(whole[m][1]).max() > 0
    # (the first layer's inputs are the same numbers: its tail exactly)
    assert np.array_equal(whole[MIXERS[0]][1], chunks[MIXERS[0]][1])


def test_a_row_that_held_another_requests_state_starts_from_zeros(
        toy, short_spans):
    """A row is handed on as it is: a request that takes a row after
    another reads none of what that one left (a first chunk starts from
    zeros, not from the row), and its later chunks read its own."""
    ff, weights = toy
    dec = _decoder(ff, prefill_chunk=16)
    rng = np.random.default_rng(7)
    first = rng.integers(0, TOY["vocab_size"], 60).astype(np.int32)
    _paged_run(dec, first, 3)
    assert all(float(jnp.abs(dec.pool.kv[m][0][1]).max()) > 0
               for m in MIXERS)                  # row 1 holds what it left
    second = rng.integers(0, TOY["vocab_size"], 37).astype(np.int32)
    rows, toks = _paged_run(dec, second, 4)
    want = _reference_rows(weights, toks, len(rows))
    assert np.abs(rows - want).max() <= 2e-4 * np.abs(want).max()


def test_a_nan_in_one_requests_state_reaches_no_other(toy, short_spans):
    """Two requests side by side; every row the second holds, its states,
    its tails and its blocks, is made NaN: the first's next chunk and
    decode steps give the logits they gave without it."""
    ff, _ = toy
    rng = np.random.default_rng(11)
    a = rng.integers(0, TOY["vocab_size"], 40).astype(np.int32)
    b = rng.integers(0, TOY["vocab_size"], 37).astype(np.int32)

    def run(poison):
        dec = _decoder(ff, prefill_chunk=16)
        ta, tb = dec.pool.try_admit(48), dec.pool.try_admit(48)
        for at in (0, 16):
            dec.prefill_chunk_at(a, ta, at)
        for at in (0, 16, 32):
            dec.prefill_chunk_at(b, tb, at)
        if poison:
            row = int(dec.pool.rows_of(tb[None])[0])
            for name, kind in dec.pool.kinds.items():
                where = np.asarray([row]) if kind.keeps_row \
                    else tb[tb != 0]
                dec.pool.kv[name] = tuple(
                    arena.at[where].set(jnp.nan)
                    for arena in dec.pool.kv[name])
        rows = [dec.prefill_chunk_at(a, ta, 32)]
        for k in range(3):
            tokens = np.zeros(3, np.int32)
            tables = np.zeros((3, dec.max_blocks_per_request), np.int32)
            lens = np.zeros(3, np.int32)
            tokens[0], lens[0] = int(rows[-1].argmax()), 40 + k
            tables[0, :len(ta)] = ta
            tokens[1], lens[1] = 1, 37 + k      # the poisoned one decodes on
            tables[1, :len(tb)] = tb
            out = dec.decode(tokens, tables, lens)
            rows.append(out[0])
            if poison:
                assert np.isnan(out[1]).any()
        return np.stack(rows)

    clean, poisoned = run(False), run(True)
    assert np.isfinite(poisoned).all()
    assert np.array_equal(clean, poisoned)


def test_the_gated_delta_kind_takes_chunks_too():
    """Until PR 63 ``StateEntry`` (the gated-delta rule's) defined no
    ``chunk`` and a model that keeps one refused ``prefill_chunk`` at
    construction; now it says it takes it, as the Mamba kind and the pair
    do (``tests/test_glm_lm.py`` holds its chunk to the bucket prefill's
    rows), and the hybrid toy builds its chunk programs."""
    ff = FFModel(FFConfig(batch_size=2, seed=0, ledger="off",
                          computation_mode=CompMode.INFERENCE))
    zoo_smoke_builders()["hybrid"](ff, 2)
    ff.compile(optimizer=None, loss_type=None, metrics=[])
    dec = PagedDecoder(ff, 32, decode_slots=2, block_size=8,
                       prefill_chunk=16, calibrate=False)
    assert dec.prefill_chunk == 16
    assert cache_entry.StateEntry.chunked
    assert cache_entry.SsmStateEntry.chunked and cache_entry.PairEntry.chunked
    assert not cache_entry.LatentEntry.chunked


# ---- the reference's own two forms -------------------------------------------

@pytest.mark.parametrize("s,block", [(5, 8), (24, 8), (43, 8), (43, 16)])
def test_the_references_blocked_scan_is_its_token_by_token_one(s, block):
    """``scan_blocked`` (the published blocked form) against
    ``scan_tokens`` (the recurrence as written) on random inputs in the
    recurrence's own ranges, lengths under, at and across blocks; and the
    whole forward by either."""
    rng = np.random.default_rng(s)
    b, h, p, n = 2, 4, 16, 8
    dt = jnp.asarray(rng.uniform(0.001, 0.5, (b, s, h)), jnp.float32)
    la = dt * -jnp.asarray(rng.uniform(1, 16, (h,)), jnp.float32)
    xs = jnp.asarray(rng.normal(size=(b, s, h, p)), jnp.float32)
    bm, cm = (jnp.asarray(rng.normal(size=(b, s, h, n)), jnp.float32)
              for _ in range(2))
    want = np.asarray(reference.scan_tokens(la, dt, xs, bm, cm))
    got = np.asarray(reference.scan_blocked(la, dt, xs, bm, cm, block))
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= 2e-5 * np.abs(want).max()
    if block == 8:
        weights = reference.init_weights(TOY, SEED)
        toks = jnp.asarray(rng.integers(0, TOY["vocab_size"], (1, s)))
        a, c = (np.asarray(reference.forward(weights, toks, TOY, "float32",
                                             recurrence=r))
                for r in ("tokens", "blocked"))
        assert np.abs(a - c).max() <= 2e-5 * np.abs(a).max()


# ---- the scheduler's books, the zoo ------------------------------------------

def test_the_scheduler_counts_the_state_rows_its_chunks_carried(toy):
    """``stats()["kv"]["state"]``: a chunk at offset 0 starts a row a
    Mamba layer from zeros, a later one carries it on; beside them the
    rows the decode steps stepped and the loop's chunks and tokens."""
    from flexflow_tpu.serving import GenerationInstance

    ff, _ = toy
    inst = GenerationInstance(ff, decode_slots=3, block_size=BLOCK,
                              max_length=MAX_LEN, prefill_chunk=16)
    try:
        rng = np.random.default_rng(3)
        lens = (5, 16, 40, 33)                      # 1 + 1 + 3 + 3 chunks
        for n in lens:
            inst.generate(rng.integers(0, TOY["vocab_size"], n)
                          .astype(np.int32), max_new_tokens=3,
                          temperature=0.0)
        st = inst.stats()
    finally:
        inst.stop()
    state = st["kv"]["state"]
    assert state["rows_started"] == 3 * len(lens)
    assert state["rows_carried"] == 3 * (8 - len(lens))
    assert state["rows_stepped"] == 3 * 2 * len(lens)
    assert st["loop"]["prefill_chunks"] == 8
    assert st["loop"]["prefill_tokens"] == sum(lens)
    assert state["row_bytes"] == reference.state_bytes_per_request(
        TOY, tail_bytes=4)


def test_the_zoo_preset_builds_and_serves():
    """The zoo's ``granite_hybrid`` preset through a
    ``GenerationInstance`` with chunks: greedy output equal to the dense
    ``Generator``'s, whatever the chunk."""
    from flexflow_tpu.serving import GenerationInstance
    from flexflow_tpu.serving.generation import Generator

    ff = FFModel(FFConfig(batch_size=3, seed=0, ledger="off",
                          computation_mode=CompMode.INFERENCE))
    zoo_smoke_builders()["granite_hybrid"](ff, 3)
    ff.compile(optimizer=None, loss_type=None, metrics=[])
    assert GraniteHybridConfig().layer_types.count("attention") == 1
    prompt = np.random.default_rng(0).integers(0, 128, 21).astype(np.int32)
    want = Generator(ff, 48, batch_size=1).generate(prompt[None], 5)[0]
    inst = GenerationInstance(ff, decode_slots=3, block_size=8,
                              max_length=48, prefill_chunk=8)
    try:
        got = inst.generate(prompt, max_new_tokens=5, temperature=0.0)
        kv = inst.stats()["kv"]
    finally:
        inst.stop()
    assert np.array_equal(got, want)
    assert kv["entry"] == {"ssm_state": 2, "pair": 1}
    assert kv["attention_path"] == {"decode": "gather", "chunk": "scan",
                                    "decode_chunk_tokens": None}
