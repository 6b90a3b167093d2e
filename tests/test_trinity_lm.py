"""Windowed layers beside a full one, gated grouped heads with rotary
positions in the windowed layers only, and routed experts held by share,
at toy widths on the CPU: the program against the plain reference
(``benchmark/reference/trinity.py``, which imports nothing of the
program): the attention op's whole forward by attribute; the whole model;
prompts prefilled in chunks and decoded through both kinds of cache entry
(a ring a request beside blocks a token) against the reference's full
forward, with a window of two blocks and prompts under it, at it and past
it by more than a ring; a chunk whose padding crosses the ring's wrap; a
NaN that stays in its own request; the shares against the uncut layer;
the pool's bytes and the counters the benchmark's readers take. The
programs compiled for the chip at the published widths are in
tests/test_tpu_lowering.py, the older programs' digests in
tests/test_hybrid_lm.py."""

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

from benchmark.families import trinity as family  # noqa: E402
from benchmark.reference import trinity as reference  # noqa: E402
from flexflow_tpu import FFConfig, FFModel  # noqa: E402
from flexflow_tpu.core.op import LowerCtx  # noqa: E402
from flexflow_tpu.ffconst import CompMode, DataType  # noqa: E402
from flexflow_tpu.models import build_trinity_lm  # noqa: E402
from flexflow_tpu.serving import GenerationInstance, cache_entry  # noqa: E402
from flexflow_tpu.serving.generation import PagedDecoder  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "tests", "data", "configs",
                       "trinity-toy.json")) as _f:
    # a window of two blocks of 8
    TOY = dict(json.load(_f), sliding_window=16)
# the whole toy model: every expert held
WHOLE = dict(TOY, num_experts=8, expert_first=0)
SEED = 2 ** 31 + 5
MAX_LEN = 96
BLOCK = 8


def _program(config, seed=SEED, slots=3, max_len=MAX_LEN):
    """The program's graph for ``config`` in float32 holding the
    reference's seeded weights; returns (ff, weights)."""
    cfg = dataclasses.replace(family.program_config(config),
                              param_dtype=DataType.FLOAT, draw_weights=True)
    ff = FFModel(FFConfig(batch_size=slots, ledger="off",
                          computation_mode=CompMode.INFERENCE))
    build_trinity_lm(ff, slots, max_len, cfg)
    ff.compile(optimizer=None, loss_type=None, metrics=[])
    weights = reference.init_weights(config, seed)
    ff.compiled.params = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), family.to_program(weights, config))
    ff.compiled.bump_params_version()
    return ff, weights


def _op(ff, name):
    return next(op for op in ff.compiled.ops if op.name == name)


def _pieces(config):
    return reference._pieces(reference._key(config), "float32")


def _layer(weights, i):
    p = f"l{i}."
    return {k[len(p):]: v for k, v in weights.items() if k.startswith(p)}


@pytest.fixture(scope="module")
def toy():
    return _program(TOY)


@pytest.fixture()
def short_spans(monkeypatch):
    """Key spans of 16: a chunk's attend walks several of them."""
    monkeypatch.setattr(cache_entry, "SPAN_TOKENS", 16)


# ---- the attention op by attribute -------------------------------------------

@pytest.mark.parametrize("layer", [0, 2], ids=["windowed", "full"])
def test_attention_op_forward_equals_the_references(toy, layer):
    """``MultiHeadAttention``'s whole forward with a ``head_dim`` of its
    own (4 heads of 8 on a hidden size of 32 they do not divide into:
    here they happen to; the projections are (E, H, D) all the same), a
    norm over each head of q and k, the gate, and in the windowed layer
    the band's mask and rotary positions: the reference's attention piece
    less its residual and the norm behind it. 40 positions: the window of
    16 bites."""
    ff, weights = toy
    op = _op(ff, f"block{layer}_attn")
    assert (op.window, op.rotary) == ((16, 10000.0) if layer == 0
                                      else (None, None))
    assert op.gate and op.qk_norm_per_head and op.head_dim == 8
    w = ff.compiled.params[op.name]
    assert w["q_norm"].shape == w["k_norm"].shape == (8,)
    assert w["wg"].shape == (32, 4, 8)
    s = 40
    x = jax.random.normal(jax.random.key(layer), (2, s, 32))
    lw = _layer(weights, layer)
    u = reference._rms(x, lw["norm_in"], 1e-5)
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (2, s))
    ins = [u, u, u] + ([pos] if layer == 0 else [])
    got = op.forward(LowerCtx(mesh=None, training=False), ins, w)[0]
    # the piece is x + rms_post(out): undo both with a gain of ones
    ones = dict(lw, norm_post_attn=jnp.ones(32))
    want = _pieces(TOY)["attention"](x, ones, sliding=layer == 0) - x
    normed = reference._rms(got, jnp.ones(32), 1e-5)
    assert np.abs(np.asarray(normed - want)).max() <= 2e-5 * float(
        np.abs(want).max())
    # the band is counted, not the square
    windowed, full = _op(ff, "block0_attn"), _op(ff, "block2_attn")
    assert full.flops() - windowed.flops() == 2.0 * 3 * 4 * MAX_LEN * (
        MAX_LEN - 16) * 8 * 2


@pytest.mark.parametrize("config", [TOY, WHOLE], ids=["share", "whole"])
def test_whole_forward_equals_the_references(config):
    """The whole model cache-free (each kind's ``whole``: a windowed
    layer by its mask) against the reference's forward over 50 tokens,
    the reference taking the program's routing: 2e-4 of the logits'
    range, float32 summation order over 4 layers."""
    ff, weights = _program(config)
    dec = PagedDecoder(ff, MAX_LEN, decode_slots=3, block_size=BLOCK,
                       calibrate=False)
    toks = np.random.default_rng(1).integers(
        0, config["vocab_size"], 50).astype(np.int32)
    free, info = reference.forward_with_routing(
        weights, jnp.asarray(toks[None]), config, "float32")
    got = dec._dense_reference_logits(
        toks, {n: np.asarray(layer["ids"]) for n, layer in zip(
            family.expert_layer_names(config), info)})
    want = np.asarray(free)[0]
    assert np.abs(got - want).max() <= 2e-4 * np.abs(want).max()


# ---- chunks, then decode, through both kinds ---------------------------------

def _paged_run(dec, names, prompt, steps, slot=0):
    """The prompt chunk by chunk, then greedy decode steps in ``slot``;
    the logits of each step, the token sequence, and the routing per
    expert layer where the programs made one."""
    n, c = len(prompt), dec.prefill_chunk
    table = dec.pool.try_admit(n + steps + 1)
    ids = [[] for _ in names]
    for at in range(0, n, c):
        logits = dec.prefill_chunk_at(prompt, table, at)
        live = min(c, n - at)
        for j, nm in enumerate(names):
            got = dec.last_routing.get(nm)
            got = (np.zeros((0, 2), np.int32) if got is None
                   else np.asarray(got)[0, :live])
            ids[j] += [np.full((live - len(got), 2), -1, np.int32), got]
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
        for j, nm in enumerate(names):
            ids[j].append(np.asarray(dec.last_routing[nm])[slot:slot + 1])
    dec.pool.free(table)
    return (np.stack(rows), np.asarray(toks, np.int32),
            [np.concatenate(x) for x in ids])


def _against_reference(config, weights, rows, toks, ids):
    """The reference under the program's routing (its own where the
    programs made none) at the rows' positions; float32 routes alike."""
    _, free = reference.forward_with_routing(
        weights, jnp.asarray(toks[None]), config, "float32")
    known = [np.all(g >= 0, -1) for g in ids]
    filled = [np.where(k[:, None], g, np.asarray(f["own_ids"]))
              for g, k, f in zip(ids, known, free)]
    logits, info = reference.forward_with_routing(
        weights, jnp.asarray(toks[None]), config, "float32", routing=filled)
    for g, k, layer in zip(ids, known, info):
        assert np.array_equal(np.sort(g[k], -1),
                              np.sort(np.asarray(layer["own_ids"])[k], -1))
    return np.asarray(logits)[0, len(toks) - len(rows):]


@pytest.mark.parametrize("n,chunk,steps", [
    (9, 16, 4),      # under the window
    (16, 16, 4),     # at it: the first decode step wraps the ring
    (53, 16, 6),     # past it by more than two rings, a last chunk of 5
    (30, 24, 5),     # a chunk longer than the ring, its padding (30..47)
                     # across the ring's wrap at 32
    (40, 8, 20),     # a chunk of one block; decode past another ring
], ids=["under", "at", "past", "padding-wraps", "block-chunks"])
def test_chunked_prefill_and_decode_equal_the_references_forward(
        toy, short_spans, n, chunk, steps):
    """A prompt prefilled in chunks (each behind what the chunks before
    left: the full layer through its block table, a windowed layer over
    ``[its ring | the chunk]``), then decode steps (the windowed layers
    read ``min(n, 16)`` rows of their rings): the LOGITS of the
    reference's cache-free forward over the whole sequence. 2e-4 of the
    logits' range: float32 summation order."""
    ff, weights = toy
    dec = PagedDecoder(ff, MAX_LEN, decode_slots=3, block_size=BLOCK,
                       prefill_chunk=chunk, calibrate=False)
    names = family.expert_layer_names(TOY)
    prompt = np.random.default_rng(n).integers(
        0, TOY["vocab_size"], n).astype(np.int32)
    rows, toks, ids = _paged_run(dec, names, prompt, steps, slot=1)
    want = _against_reference(TOY, weights, rows, toks, ids)
    assert np.abs(rows - want).max() <= 2e-4 * np.abs(want).max()
    kv = dec.pool.stats()
    assert kv["entry"] == {"window": 3, "pair": 1} and kv["window"] == 16
    assert (kv["kv_heads"], kv["query_heads"]) == (2, 4)


def test_a_second_request_in_the_ring_sees_nothing_of_the_first(
        toy, short_spans):
    """A ring is handed on as it is: a request that takes a slot's row
    after a longer one reads none of what that one left (a first chunk
    skips the ring; a short prompt's steps are clamped to its length)."""
    ff, weights = toy
    dec = PagedDecoder(ff, MAX_LEN, decode_slots=3, block_size=BLOCK,
                       prefill_chunk=16, calibrate=False)
    names = family.expert_layer_names(TOY)
    rng = np.random.default_rng(7)
    long = rng.integers(0, TOY["vocab_size"], 60).astype(np.int32)
    _paged_run(dec, names, long, 3)
    short = rng.integers(0, TOY["vocab_size"], 5).astype(np.int32)
    rows, toks, ids = _paged_run(dec, names, short, 4)
    want = _against_reference(TOY, weights, rows, toks, ids)
    assert np.abs(rows - want).max() <= 2e-4 * np.abs(want).max()


def test_a_nan_in_one_requests_rows_reaches_no_other(toy, short_spans):
    """Two requests side by side; every row the second holds, in its
    rings and in its blocks, is made NaN: the first's next chunk and
    decode steps give the logits they gave without it."""
    ff, _ = toy
    rng = np.random.default_rng(11)
    a = rng.integers(0, TOY["vocab_size"], 40).astype(np.int32)
    b = rng.integers(0, TOY["vocab_size"], 37).astype(np.int32)

    def run(poison):
        dec = PagedDecoder(ff, MAX_LEN, decode_slots=3, block_size=BLOCK,
                           prefill_chunk=16, calibrate=False)
        ta, tb = dec.pool.try_admit(48), dec.pool.try_admit(48)
        for at in (0, 16):
            dec.prefill_chunk_at(a, ta, at)
        for at in (0, 16, 32):
            dec.prefill_chunk_at(b, tb, at)
        if poison:
            row = int(dec.pool.rows_of(tb[None])[0])
            for name, kind in dec.pool.kinds.items():
                entry = dec.pool.kv[name]
                if kind.per_request:
                    ring = 16 // BLOCK
                    where = np.arange(row * ring, (row + 1) * ring)
                else:
                    where = tb[tb != 0]
                dec.pool.kv[name] = tuple(
                    arena.at[where].set(jnp.nan) for arena in entry)
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


def test_pair_entry_takes_chunks_for_a_model_of_plain_attention():
    """``PagedDecoder(prefill_chunk=...)`` over a GPT: ``PairEntry.chunk``
    (no window, learned positions) gives the bucketed prefill's logits
    and cache, and the int8 pair still refuses."""
    from flexflow_tpu.models import GPTConfig, build_gpt

    ff = FFModel(FFConfig(batch_size=2, ledger="off", seed=3,
                          computation_mode=CompMode.INFERENCE))
    build_gpt(ff, 2, 64, GPTConfig(vocab_size=64, max_positions=64,
                                   hidden_size=16, num_heads=2, num_layers=2))
    ff.compile(optimizer=None, loss_type=None, metrics=[])
    prompt = np.random.default_rng(2).integers(0, 64, 37).astype(np.int32)
    outs = []
    for kw in (dict(prefill_buckets=[64]), dict(prefill_chunk=16)):
        dec = PagedDecoder(ff, 64, decode_slots=2, block_size=8,
                           calibrate=False, **kw)
        table = dec.pool.try_admit(45)
        rows = [dec.prefill(prompt, table)]
        for k in range(3):
            tokens = np.zeros(2, np.int32)
            tables = np.zeros((2, dec.max_blocks_per_request), np.int32)
            lens = np.zeros(2, np.int32)
            tokens[0], lens[0] = int(rows[-1].argmax()), 37 + k
            tables[0, :len(table)] = table
            rows.append(dec.decode(tokens, tables, lens)[0])
        outs.append(np.stack(rows))
    assert np.abs(outs[0] - outs[1]).max() <= 2e-5 * np.abs(outs[0]).max()
    with pytest.raises(ValueError, match="prefills a prompt whole"):
        PagedDecoder(ff, 64, decode_slots=2, block_size=8, kv_dtype="int8",
                     prefill_chunk=16, calibrate=False)


# ---- the share ---------------------------------------------------------------

def test_the_two_shares_add_up_to_the_uncut_layer():
    """The share tied to the model: the routed parts of the two holders
    (experts 0-3, 4-7) and the shared expert counted once are the uncut
    layer's ``f``, in the reference and in the program alike."""
    weights = reference.init_weights(WHOLE, SEED)
    lw = _layer(weights, 1)
    # scaled up, so that the layer's output stands beside float32's
    # cancellation (N(0, 0.02) at these widths leaves it under it)
    lw.update({k: lw[k].astype(jnp.float32) * 8 for k in lw
               if k.startswith(("experts.", "shared."))})
    x = jax.random.normal(jax.random.key(4), (1, 11, 32))
    f = _pieces(WHOLE)
    s, ids, _ = f["scores_of"](x, lw)
    m = reference._rms(x, lw["norm_pre_mlp"], 1e-5)[0]
    shared = np.asarray(reference._gated_mlp(
        m, lw["shared.gate"], lw["shared.up"], lw["shared.down"], "float32"))
    whole = np.asarray(f["routed_part"](x, lw, s, ids)) + shared
    # the uncut layer's own piece agrees: x + rms_post(f) with a gain of 1
    piece = f["expert_ffn"](x, dict(lw, norm_post_mlp=jnp.ones(32)), s, ids)
    assert np.allclose(piece - x, reference._rms(whole, jnp.ones(32), 1e-5),
                       atol=1e-5)
    parts, program_parts = [], []
    for first in (0, 4):
        cfg = dict(TOY, expert_first=first)
        share = dict(lw, **{k: lw[k][first:first + 4] for k in lw
                            if k.startswith("experts.")})
        parts.append(np.asarray(_pieces(cfg)["routed_part"](x, share, s,
                                                             ids)))
        ff, _ = _program(cfg)
        op = _op(ff, "block1_experts")
        w = {"router": share["router"], "bias": share["bias"],
             "w_gate": share["experts.gate"], "w_up": share["experts.up"],
             "w_down": share["experts.down"]}
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        ids_p, gates_p = op.route(w, m)
        assert np.array_equal(np.sort(np.asarray(ids_p), -1),
                              np.sort(np.asarray(ids), -1))
        program_parts.append(np.asarray(op.apply(w, m, ids_p, gates_p)))
    assert min(np.abs(p).max() for p in program_parts) > 0.01
    tol = 2e-5 * np.abs(whole).max()
    assert np.abs(sum(parts) + shared - whole).max() <= tol
    assert np.abs(sum(program_parts) + shared - whole).max() <= tol


# ---- the pool and the counters -----------------------------------------------

def test_a_windowed_layer_reserves_a_ring_and_the_pools_bytes_say_so(toy):
    """The windowed kind: a ``per_request`` arena of ``window /
    block_size`` blocks a row in the pair layout, one step a slot, no
    int8 form; the pool's bytes are the full layer's blocks and a ring a
    row and windowed layer."""
    ff, _ = toy
    dec = PagedDecoder(ff, MAX_LEN, decode_slots=3, block_size=BLOCK,
                       prefill_chunk=16, calibrate=False)
    kinds = dec.pool.kinds
    win, full = kinds["block0_attn"], kinds["block2_attn"]
    assert isinstance(win, cache_entry.WindowEntry) and win.window == 16
    assert type(full) is cache_entry.PairEntry
    assert win.per_request and win.chunked and full.chunked
    assert win.max_window == 1 and win.int8_form is None
    assert [win.rows_read(n) for n in (0, 7, 15, 16, 90)] \
        == [1, 8, 16, 16, 16]
    assert full.rows_read(90) is None
    # 4 rows (3 slots and the null row) of 2 blocks; 2 heads of 8, float32
    assert dec.pool.kv["block0_attn"][0].shape == (4 * 2, BLOCK, 16)
    blocks = 3 * (MAX_LEN // BLOCK) + 1
    assert dec.pool.kv["block2_attn"][0].shape == (blocks, BLOCK, 16)
    ring = 2 * 16 * 16 * 4
    assert win.token_bytes(jnp.float32) == ring
    assert dec.pool.memory_bytes() == 3 * 4 * ring + blocks * BLOCK * 2 * 64
    assert dec.pool.stats()["state"]["row_bytes"] == 3 * ring
    with pytest.raises(ValueError, match="no whole blocks"):
        PagedDecoder(ff, MAX_LEN, decode_slots=3, block_size=12,
                     calibrate=False)
    with pytest.raises(ValueError, match="int8"):
        PagedDecoder(ff, MAX_LEN, decode_slots=3, block_size=BLOCK,
                     kv_dtype="int8", calibrate=False)
    with pytest.raises(ValueError, match="spec_k=0"):
        dec.check_window(2)


def test_the_scheduler_counts_the_windows_rows_and_the_chunks_keys(toy):
    """``stats()["kv"]["window"]`` and ``stats()["loop"]`` over a session
    of known lengths: rows read ``min(length + 1, 16)`` a step and slot,
    rows a layer that keeps everything would read, a ring reserved a
    slot-step; a chunk's keys ``position + 1`` a query, or the window."""
    ff, _ = toy
    inst = GenerationInstance(ff, decode_slots=3, block_size=BLOCK,
                              max_length=MAX_LEN, prefill_chunk=16)
    try:
        rng = np.random.default_rng(5)
        jobs = [(9, 4), (40, 6)]
        for n, new in jobs:
            out = inst.generate(rng.integers(0, 96, n).astype(np.int32),
                                max_new_tokens=new, temperature=0.0)
            assert out.shape == (n + new,)
        st = inst.stats()
    finally:
        inst.stop()
    w = st["kv"]["window"]
    # the first token is the prefill's: new - 1 steps, at lengths n, n + 1..
    lens = [n + k for n, new in jobs for k in range(new - 1)]
    assert w["rows_read"] == sum(min(x + 1, 16) for x in lens)
    assert w["rows_full"] == sum(x + 1 for x in lens)
    assert w["rows_reserved"] == 16 * len(lens)
    assert (w["rows"], w["ops"], w["rows_held"]) == (16, 3, 0)
    loop = st["loop"]
    assert loop["prefill_chunks"] == 1 + 3 and loop["prefill_tokens"] == 49
    assert loop["prefill_keys"] == sum(p + 1 for n, _ in jobs
                                       for p in range(n))
    assert loop["prefill_keys_window"] == sum(min(p + 1, 16) for n, _ in jobs
                                              for p in range(n))
    # the held pairs of the prompts' chunks are counted (the last layer's
    # experts run for a prompt's last position alone)
    moe = st["moe"]
    assert set(moe) == set(family.expert_layer_names(TOY))
    assert 0 < moe["block1_experts"]["prompt_pairs_held"] <= 49 * 2
    assert moe["block3_experts"]["prompt_pairs_held"] <= 2 * 2


def test_the_zoo_preset_builds_and_serves():
    from flexflow_tpu.models import zoo_smoke_builders

    ff = FFModel(FFConfig(batch_size=2, ledger="off",
                          computation_mode=CompMode.INFERENCE))
    zoo_smoke_builders()["trinity"](ff, 2)
    ff.compile(optimizer=None, loss_type=None, metrics=[])
    kinds = {op.name: (op.window, op.rotary) for op in ff.compiled.ops
             if hasattr(op, "window")}
    assert sorted(kinds.values(), key=str) == [(16, 10000.0)] * 3 + [
        (None, None)]
    inst = GenerationInstance(ff, decode_slots=2, block_size=8,
                              max_length=32, prefill_chunk=8)
    try:
        out = inst.generate(np.arange(19, dtype=np.int32), max_new_tokens=5,
                            temperature=0.0)
    finally:
        inst.stop()
    assert out.shape == (24,)


def test_the_grouped_kernel_plans_a_chunks_experts():
    """What ``kernels/grouped_experts.plan`` does with a chunk of this
    model at its published widths (2,048 tokens of 4 picks over 32 held
    gated experts of 3,072 x 3,072): it takes them, an expert's matrices
    cut into weight blocks of 1,024 columns; the one row of a prompt's
    last layer behind the head's cut it refuses, and that row takes the
    dense form."""
    from flexflow_tpu.kernels import grouped_experts

    assert grouped_experts.plan(2048, 4, 3072, 3072, 32, True,
                                jnp.bfloat16) == 1024
    assert grouped_experts.plan(1, 4, 3072, 3072, 32, True,
                                jnp.bfloat16) is None
