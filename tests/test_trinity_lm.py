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


def _program(config, seed=SEED, slots=3, max_len=MAX_LEN, mesh=None):
    """The program's graph for ``config`` in float32 holding the
    reference's seeded weights; returns (ff, weights)."""
    cfg = dataclasses.replace(family.program_config(config),
                              param_dtype=DataType.FLOAT, draw_weights=True)
    ff = FFModel(FFConfig(batch_size=slots, ledger="off",
                          computation_mode=CompMode.INFERENCE))
    build_trinity_lm(ff, slots, max_len, cfg)
    ff.compile(optimizer=None, loss_type=None, metrics=[], mesh=mesh)
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


# the toy at the width the chunk's kernel takes: heads of one lane tile
LANE_TOY = dict(TOY, head_dim=128)


@pytest.fixture(scope="module")
def lane_toy():
    """The toy with heads of 128 in a model over ONE device (the tests'
    eight virtual devices are a mesh the kernel does not take)."""
    from flexflow_tpu.core.machine import make_mesh

    return _program(LANE_TOY, mesh=make_mesh(devices=jax.devices()[:1]))


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
@pytest.mark.parametrize("form", ["scan", "kernel"])
def test_chunked_prefill_and_decode_equal_the_references_forward(
        request, monkeypatch, short_spans, form, n, chunk, steps):
    """A prompt prefilled in chunks (each behind what the chunks before
    left: the full layer through its block table, a windowed layer over
    ``[its ring | the chunk]``), then decode steps (the windowed layers
    read ``min(n, 16)`` rows of their rings): the LOGITS of the
    reference's cache-free forward over the whole sequence. 2e-4 of the
    logits' range: float32 summation order. ``scan``: the chunks' span
    walk on the CPU; ``kernel``: the chunk programs through
    ``kernels/chunk_attention.py`` under the interpreter, heads of 128
    (tiles of the chunk's 8, 16 or 8 queries by 24, 32 or 24 keys)."""
    if form == "kernel":
        monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    config = LANE_TOY if form == "kernel" else TOY
    ff, weights = request.getfixturevalue(
        "lane_toy" if form == "kernel" else "toy")
    dec = PagedDecoder(ff, MAX_LEN, decode_slots=3, block_size=BLOCK,
                       prefill_chunk=chunk, calibrate=False)
    assert dec.attention_path["chunk"] == form
    names = family.expert_layer_names(config)
    prompt = np.random.default_rng(n).integers(
        0, config["vocab_size"], n).astype(np.int32)
    rows, toks, ids = _paged_run(dec, names, prompt, steps, slot=1)
    want = _against_reference(config, weights, rows, toks, ids)
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
                if kind.keeps_row:
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
        ids_p, gates_p, _ = op.route(w, m)
        assert np.array_equal(np.sort(np.asarray(ids_p), -1),
                              np.sort(np.asarray(ids), -1))
        program_parts.append(np.asarray(op.apply(w, m, ids_p, gates_p)))
    assert min(np.abs(p).max() for p in program_parts) > 0.01
    tol = 2e-5 * np.abs(whole).max()
    assert np.abs(sum(parts) + shared - whole).max() <= tol
    assert np.abs(sum(program_parts) + shared - whole).max() <= tol


# ---- the pool and the counters -----------------------------------------------

def test_a_windowed_layer_reserves_a_ring_and_the_pools_bytes_say_so(toy):
    """The windowed kind: a request's arenas of ``window /
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
    assert win.keeps_row and win.chunked and full.chunked
    assert win.max_window == 1 and win.int8_form is None
    assert [win.rows_read(n) for n in (0, 7, 15, 16, 90)] \
        == [1, 8, 16, 16, 16]
    assert full.rows_read(90) is None
    # 4 rows (3 slots and the null row) of 2 blocks; 2 heads of 8, float32
    assert dec.pool.kv["block0_attn"][0].shape == (4 * 2, BLOCK, 16)
    blocks = 3 * (MAX_LEN // BLOCK) + 1
    assert dec.pool.kv["block2_attn"][0].shape == (blocks, BLOCK, 16)
    ring = 2 * 16 * 16 * 4
    assert win.request_bytes(jnp.float32) == ring
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


@pytest.mark.parametrize("form", ["scan", "kernel"])
def test_the_scheduler_says_how_a_chunk_is_attended(request, monkeypatch,
                                                    form):
    """``stats()["kv"]["attention_path"]["chunk"]``: ``"kernel"`` where
    every kind's chunk goes through ``kernels/chunk_attention.py`` (the
    toy with heads of 128 over one device, under the interpreter),
    ``"scan"`` on the CPU, and the same tokens either way as the
    cache-free generator's."""
    from flexflow_tpu.serving.generation import Generator

    if form == "kernel":
        monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    ff, _ = request.getfixturevalue("lane_toy" if form == "kernel" else "toy")
    prompt = np.random.default_rng(3).integers(0, 96, 21).astype(np.int32)
    inst = GenerationInstance(ff, decode_slots=3, block_size=BLOCK,
                              max_length=MAX_LEN, prefill_chunk=16)
    try:
        out = inst.generate(prompt, max_new_tokens=4, temperature=0.0)
        paths = inst.stats()["kv"]["attention_path"]
    finally:
        inst.stop()
    assert paths["chunk"] == form
    assert paths["decode"] == ("kernel" if form == "kernel" else "gather")
    assert np.array_equal(out, Generator(ff, MAX_LEN, batch_size=1).generate(
        prompt[None], 4)[0])


def test_the_chunks_kernel_refuses_what_it_cannot_take(monkeypatch):
    """``chunk_path`` is ``"kernel"`` for heads of 128 in float32 or
    bfloat16 rows under the interpreter, and ``"scan"`` for GPT-2's head
    width, an int8 pair, the CPU without the interpreter and a model
    over more than one device; a decoder that takes no chunks says None.
    The plain-attention model's chunk program lowers to the same text
    with the interpreter on and with Pallas off: it keeps the walk."""
    from flexflow_tpu.models import GPTConfig, build_gpt
    from flexflow_tpu.serving.cache_entry import Int8PairEntry, PairEntry

    def path(kind, dtype=jnp.float32, store=jnp.float32):
        entry = tuple(jnp.zeros(a.shape, a.dtype)
                      for a in kind.arenas(4, BLOCK, store)
                      + kind.request_arenas(4, BLOCK, store))
        return kind.chunk_path(entry, 1, 16, 12, dtype)

    lanes = PairEntry(2, 128, 4)
    assert path(lanes) == "scan"                       # the CPU
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    assert path(lanes) == "kernel"
    assert path(lanes, jnp.bfloat16, jnp.bfloat16) == "kernel"
    assert path(cache_entry.WindowEntry(2, 128, 4, 16)) == "kernel"
    assert path(lanes, jnp.float32, jnp.bfloat16) == "scan"  # two dtypes
    assert path(PairEntry(2, 64, 4)) == "scan"
    assert path(Int8PairEntry(2, 128, 4)) == "scan"
    assert path(lanes.over(8)) == "scan" and lanes.over(8) == lanes

    ff = FFModel(FFConfig(batch_size=2, ledger="off", seed=3,
                          computation_mode=CompMode.INFERENCE))
    build_gpt(ff, 2, 64, GPTConfig(vocab_size=64, max_positions=64,
                                   hidden_size=16, num_heads=2, num_layers=2))
    ff.compile(optimizer=None, loss_type=None, metrics=[])
    texts = []
    for mode in ("interpret", "off"):
        monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", mode)
        dec = PagedDecoder(ff, 64, decode_slots=2, block_size=8,
                           calibrate=False, prefill_chunk=16)
        assert dec.attention_path["chunk"] == "scan"
        assert PagedDecoder(ff, 64, decode_slots=2, block_size=8,
                            calibrate=False).attention_path["chunk"] is None
        tabs = np.zeros((1, dec.max_blocks_per_request), np.int32)
        texts.append(jax.jit(
            lambda *a: dec._chunk_step(*a, head=True)).lower(
            dec._exec_params(), jnp.zeros((1, 16), jnp.int32), dec.pool.kv,
            dec._addresses(tabs), jnp.zeros((1,), jnp.int32),
            jnp.full((1,), 16, jnp.int32)).as_text())
    assert texts[0] == texts[1] and "while" in texts[0]


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
    """What ``kernels/grouped_experts.plan`` does with the calls of this
    model at its published widths (4 picks over 32 held gated experts of
    3,072 x 3,072): a chunk's 2,048 tokens in tiles of 128 rows, an
    expert's matrices cut into weight blocks of 1,024 columns; a decode
    step's 32 slots in tiles of their own 32 rows, one an expert at the
    most, whose smaller rows and output leave room for blocks of 1,536;
    the one row of a prompt's last layer behind the head's cut as a
    sublane tile's 8 (``grouped_experts`` pads it); 12 rows, which are no
    whole sublane tiles, not at all."""
    from flexflow_tpu.kernels import grouped_experts

    share = (4, 3072, 3072, 32, True, jnp.bfloat16)
    assert grouped_experts.plan(2048, *share) == 1024
    assert grouped_experts.plan(32, *share) == 1536
    assert grouped_experts.plan(1, *share) == grouped_experts.plan(
        8, *share) == 1536
    assert grouped_experts.plan(12, *share) is None
    assert [grouped_experts.tile_rows(r) for r in (8, 16, 32, 40, 128, 2048)
            ] == [16, 16, 32, 48, 128, 128]
    assert [grouped_experts.grid_tiles(r, 4, 32) for r in (8, 32, 128, 2048)
            ] == [32, 32, 32, 64 + 32]


# ---- a call of few rows through the grouped kernel, interpreted --------------

def _held_experts(e=256, width=128):
    """A routed-experts op over bfloat16 rows at the smallest widths the
    kernel takes, experts 64-95 of 256 held, top-4, gated: Trinity's
    share at toy widths; with seeded weights."""
    from flexflow_tpu.core.layer import Layer
    from flexflow_tpu.core.parallel_tensor import ParallelTensorShape
    from flexflow_tpu.ffconst import OpType
    from flexflow_tpu.ops.moe_ops import RoutedExperts

    op = RoutedExperts(
        Layer(OpType.ROUTED_EXPERTS, "x", attrs=dict(
            n_routed=256, experts_per_token=4, width=width,
            experts_held=(64, 32), routed_scale=2.448)),
        [ParallelTensorShape.unpartitioned((1, 8, e), DataType.BFLOAT16)])
    key = jax.random.key(3)
    w = {ws.name: (0.08 * jax.random.normal(
        jax.random.fold_in(key, i), ws.shape)).astype(jnp.bfloat16)
        for i, ws in enumerate(op.weight_specs())}
    return op, w


def _few_rows_routing(name, rows):
    """(rows, 4) expert ids of 256, experts 64-95 held, no expert twice
    in a row's picks."""
    t = np.arange(rows)
    if name == "no_pair_held":
        ids = np.stack([t % 64, 96 + t, 130 + t, 200 + t % 50], 1)
    elif name == "one_expert_by_all":       # expert 70: one tile, full
        ids = np.stack([np.full(rows, 70), t % 64, 100 + t, 200 + t], 1)
    else:                 # a held expert or two a row, most held get none
        ids = np.stack([64 + (5 * t) % 32, 60 + (t % 8), 100 + t, 200 + t],
                       1)
    ids = jnp.asarray(ids, jnp.int32)
    if name == "idle_rows_masked":          # every other row is padding
        ids = jnp.where((t % 2 == 0)[:, None], ids, 64 - 1)
    return ids


@pytest.mark.parametrize("routing", ["spread", "no_pair_held",
                                     "one_expert_by_all", "idle_rows_masked"])
@pytest.mark.parametrize("rows", [8, 16, 32])
def test_a_call_of_few_rows_through_the_kernel_is_the_dense_forms(
        monkeypatch, rows, routing):
    """A decode step's call (8, 16 and 32 rows of 4 picks over 32 held of
    256) through the kernel is the dense form's sum to bfloat16's
    rounding of the terms, for a routing that names a few held experts,
    one that names none (exactly 0), one that puts every row on one
    expert (one tile, full) and one whose idle rows' picks were masked
    (they read nothing and get exactly 0); the rows it counts are a tile
    of the call's rows (in whole 16s) an expert NAMED."""
    from flexflow_tpu.kernels import grouped_experts as kernel

    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    op, w = _held_experts()
    v = jax.random.normal(jax.random.key(rows), (rows, 256)
                          ).astype(jnp.bfloat16)
    ids = _few_rows_routing(routing, rows)
    _, gates, _ = op.route(w, v, jnp.maximum(ids, 0))
    assert op.expert_form(rows) == "kernel"
    counted = []
    got = np.asarray(op.apply(w, v, ids, gates, counted), np.float32)
    want = np.asarray(op._apply_dense(w, v, ids, gates), np.float32)
    load = np.bincount(np.asarray(ids).ravel() + 1, minlength=257)[65:97]
    assert [int(c) for c in counted] == [
        int((load > 0).sum()) * kernel.tile_rows(rows)]
    if routing == "no_pair_held":
        assert load.sum() == 0 and not got.any() and not want.any()
        return
    scale = np.abs(want).max()
    assert scale > 0.05 and np.abs(got - want).max() <= 0.01 * scale
    if routing == "one_expert_by_all":
        assert load[70 - 64] == rows
    if routing == "idle_rows_masked":
        assert not got[1::2].any() and got[0::2].any()
        assert load.sum() <= 2 * (rows // 2)


def test_one_row_is_padded_to_a_tile_and_a_nan_stays_in_its_row(monkeypatch):
    """The one row behind a head's cut: padded to a sublane tile by rows
    whose picks name no held expert, it comes back one row, the dense
    form's. And of 16 rows, a NaN in one reaches no other: every other
    row is what it is without the NaN."""
    from flexflow_tpu.kernels import grouped_experts as kernel

    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    op, w = _held_experts()
    v = jax.random.normal(jax.random.key(1), (16, 256)).astype(jnp.bfloat16)
    ids = _few_rows_routing("spread", 16)
    _, gates, _ = op.route(w, v, ids)
    assert op.expert_form(1) == "kernel"
    counted = []
    one = op.apply(w, v[3:4], ids[3:4], gates[3:4], counted)
    assert one.shape == (1, 256) and int(counted[0]) == 16   # one expert
    want = np.asarray(op._apply_dense(w, v, ids, gates), np.float32)
    assert np.abs(np.asarray(one, np.float32) - want[3:4]).max() <= (
        0.01 * np.abs(want).max())
    good, _ = kernel.grouped_experts(v, ids, gates, w, first=64, gated=True)
    bad, _ = kernel.grouped_experts(v.at[5, 7].set(jnp.nan), ids, gates, w,
                                    first=64, gated=True)
    good, bad = np.asarray(good, np.float32), np.asarray(bad, np.float32)
    assert np.isnan(bad[5]).all()
    assert np.array_equal(np.delete(bad, 5, 0), np.delete(good, 5, 0))


def test_gradients_of_a_call_of_few_rows_are_the_dense_forms(monkeypatch):
    """``fit`` reaches the kernel through ``op.forward``: 16 bfloat16
    rows on one device take ``kernel_form``, whose backward is the jnp
    grouped form's, and the gradients with respect to the rows and every
    weight agree with those through the dense form."""
    op, w = _held_experts()
    x = jax.random.normal(jax.random.key(8), (1, 16, 256)
                          ).astype(jnp.bfloat16)
    ids = _few_rows_routing("spread", 16)
    ctx = LowerCtx(mesh=None, training=True, aux_losses=[],
                   compute_dtype=None)

    def loss(w, x):
        # (the router's own choice at random weights names few of the 32
        # held: the routing is given, the weights are the router's)
        x2d = x.reshape(-1, x.shape[-1])
        _, gates, _ = op.route(w, x2d, ids)
        y = op.apply(w, x2d, ids, gates, mesh=ctx.mesh)
        return jnp.sum(jnp.square(y.astype(jnp.float32)))

    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "off")
    assert op.expert_form(16) == "dense"
    want_loss, want = jax.value_and_grad(loss, (0, 1))(w, x)
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    assert op.expert_form(16) == "kernel"
    assert "grouped_experts" in jax.make_jaxpr(
        lambda w, x: op.forward(ctx, [x], w))(w, x).pretty_print()
    assert "grouped_experts" in jax.make_jaxpr(jax.grad(loss, (0, 1)))(
        w, x).pretty_print()
    got_loss, got = jax.jit(jax.value_and_grad(loss, (0, 1)))(w, x)
    assert float(want_loss) > 0
    assert abs(float(got_loss) - float(want_loss)) <= 0.01 * float(want_loss)
    for name in ("router", "w_gate", "w_up", "w_down"):
        g, h = (np.asarray(a[0][name], np.float32) for a in (got, want))
        assert np.abs(h).max() > 0, name
        assert np.abs(g - h).max() <= 0.02 * np.abs(h).max(), name
    g, h = (np.asarray(a[1], np.float32) for a in (got, want))
    assert np.abs(g - h).max() <= 0.02 * np.abs(h).max()


def test_decode_steps_through_the_kernel_count_their_rows(monkeypatch):
    """A model whose decode step names a fifth of the experts it holds (8
    slots of 2 picks over 64, 8 held) takes the kernel for its steps: the
    logits are the dense form's to bfloat16's rounding, ``form_decode``
    says so, ``rows_computed`` is the integer the steps counted on the
    device (a tile of 16 rows an expert the ACTIVE slots named: seven
    idle slots' padding names none), and the one row behind the head's
    cut took the kernel too."""
    from flexflow_tpu.core.machine import make_mesh
    from flexflow_tpu.kernels import grouped_experts as kernel

    config = dict(TOY, hidden_size=256, moe_intermediate_size=128,
                  num_experts=8, expert_first=16,
                  published=dict(TOY["published"], num_experts=64))
    names = family.expert_layer_names(config)

    def run(mode):
        monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", mode)
        ff = FFModel(FFConfig(batch_size=8, ledger="off", seed=7,
                              compute_dtype="bfloat16",
                              computation_mode=CompMode.INFERENCE))
        build_trinity_lm(ff, 8, MAX_LEN, dataclasses.replace(
            family.program_config(config), draw_weights=True))
        ff.compile(optimizer=None, loss_type=None, metrics=[],
                   mesh=make_mesh(devices=jax.devices()[:1]))
        dec = PagedDecoder(ff, MAX_LEN, decode_slots=8, block_size=BLOCK,
                           prefill_chunk=16, calibrate=False)
        prompt = np.random.default_rng(4).integers(0, 96, 21).astype(
            np.int32)
        before = dec.expert_stats()
        rows, toks, ids = _paged_run(dec, names, prompt, 3, slot=2)
        return dec, rows, toks, ids, before, dec.expert_stats()

    dec, rows, toks, ids, before, st = run("interpret")
    assert {op.name: dec._decode_form(op) for op in dec._expert_ops} == {
        nm: "kernel" for nm in names}
    for j, nm in enumerate(names):
        rec = st[nm]
        assert rec["form_decode"] == "kernel" and rec["steps"] == 3
        assert rec["kernel_steps"] == 3
        assert before[nm]["rows_computed"] == 0
        assert len(rec["rows_per_held_expert"]) == 8
        assert sum(rec["rows_per_held_expert"]) == rec["pairs_held"]
        # the three steps' own picks (the last three rows of the routing)
        named = sum(len({e for e in step if 16 <= e < 24})
                    for step in ids[j][-3:])
        assert isinstance(rec["rows_computed"], int)
        assert rec["rows_computed"] == named * kernel.tile_rows(8)
        assert rec["idle_held_experts"] == 3 * 8 - named
    # the last layer's experts ran for the prompt's last row alone, padded
    assert st[names[-1]]["prompt_rows_computed"] in (0, 16, 32)
    dense, want, toks_dense, _, _, st_dense = run("off")
    assert {r["form_decode"] for r in st_dense.values()} == {"dense"}
    assert {r["rows_computed"] for r in st_dense.values()} == {3 * 8 * 8}
    assert {r["kernel_steps"] for r in st_dense.values()} == {0}
    # (the prompt's logits whatever the steps chose; the steps' where the
    # two sessions' greedy tokens agree, as they do at these seeds)
    same = 1 + sum(np.cumprod(toks[21:] == toks_dense[21:]))
    assert same >= 2
    assert np.abs(rows - want)[:same].max() <= 0.03 * np.abs(want).max()


# ---- a decode step that counts what it names (PR 54) --------------------------

def _all_held(count=16, e=256, width=128):
    """A routed-experts op that holds all it routes over, one pick a
    token, gated, bfloat16, at the smallest widths the kernel takes:
    ZAYA1's share at toy widths; with seeded weights."""
    from flexflow_tpu.core.layer import Layer
    from flexflow_tpu.core.parallel_tensor import ParallelTensorShape
    from flexflow_tpu.ffconst import OpType
    from flexflow_tpu.ops.moe_ops import RoutedExperts

    op = RoutedExperts(
        Layer(OpType.ROUTED_EXPERTS, "x", attrs=dict(
            n_routed=count, experts_per_token=1, width=width,
            scoring="softmax", norm_topk=False)),
        [ParallelTensorShape.unpartitioned((1, 8, e), DataType.BFLOAT16)])
    key = jax.random.key(5)
    w = {ws.name: (0.08 * jax.random.normal(
        jax.random.fold_in(key, i), ws.shape)).astype(jnp.bfloat16)
        for i, ws in enumerate(op.weight_specs())}
    return op, w


@pytest.mark.parametrize("live", ["all_rows", "every_other_row",
                                  "an_expert_named_by_idle_rows_alone"])
@pytest.mark.parametrize("named", [1, 6, 14, 15, 16])
def test_a_counted_call_takes_the_form_its_live_rows_name(
        monkeypatch, named, live):
    """48 rows of one pick over 16 held CAN name 95 % of them, so the
    shapes say dense; a call that says which rows are live counts the
    experts those name and is the kernel's up to 14 of the 16
    (``kernel_limit``), the dense form's beyond: the sum is
    ``_apply_dense``'s over the live rows' picks, bit for bit where the
    dense arm ran and to bfloat16's rounding of the terms where the
    kernel did; ``computed`` is a tile of 48 rows an expert NAMED in the
    one arm and every row through every expert in the other, and behind
    it which arm ran; an idle row names nothing and gets exactly 0, and
    an expert whose only namers are idle has no tile and does not count
    (15 picked, 14 named: the kernel's)."""
    from flexflow_tpu.kernels import grouped_experts as kernel

    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    op, w = _all_held()
    rows = 48
    assert op.kernel_limit() == 14
    assert op.expert_form(rows) == "dense"
    assert op.expert_form(rows, active=True) == "counted"
    assert op.rows_computed(rows) == 16 * rows
    assert op.rows_computed(rows, active=True) is None
    assert op.expert_form(rows, jnp.float32, active=True) == "dense"
    t = np.arange(rows)
    if live == "all_rows":
        active, ids, want_named = np.ones(rows, bool), t % named, named
    elif live == "every_other_row":
        # the idle rows' padding picks expert 15, as a token 0 might
        active = t % 2 == 0
        ids, want_named = np.where(active, (t // 2) % named, 15), named
    else:
        # the last expert picked is picked by idle rows alone
        active = t % named != named - 1 if named > 1 else np.zeros(rows, bool)
        ids, want_named = t % named, named - 1
    v = jax.random.normal(jax.random.key(named), (rows, 256)
                          ).astype(jnp.bfloat16)
    ids = jnp.asarray(ids[:, None], jnp.int32)
    _, gates, _ = op.route(w, v, ids)
    counted = []
    got = np.asarray(op.apply(w, v, ids, gates, counted,
                              active=jnp.asarray(active)), np.float32)
    masked = jnp.where(jnp.asarray(active)[:, None], ids, -1)
    want = np.asarray(op._apply_dense(w, v, masked, gates), np.float32)
    took_kernel = want_named <= 14
    assert [int(c) for c in counted] == [
        want_named * kernel.tile_rows(rows) if took_kernel else 16 * rows,
        int(took_kernel)]
    assert not got[~active].any() and not want[~active].any()
    if not active.any():
        return
    scale = np.abs(want).max()
    assert scale > 0.05 and got[active].any()
    if took_kernel:
        assert np.abs(got - want).max() <= 0.01 * scale
    else:
        assert np.array_equal(got, want)


def test_decode_steps_count_the_steps_that_took_the_kernel(monkeypatch):
    """A model that holds all 8 experts it routes over, 16 slots of 2
    picks: the slots CAN name 99 % of them, so the decode program's
    experts are ``counted``. A step of one live slot names 2 and takes
    the kernel; a step of sixteen live slots names all 8, past the limit
    of 7, and takes the dense form: ``kernel_steps`` counts the first
    kind, layer by layer as the steps' own routing says, under ``steps``;
    ``rows_computed`` is a tile of 16 rows an expert named in the one and
    16 rows through 8 experts in the other; the logits and greedy tokens
    are those of the same model with the kernel off."""
    from flexflow_tpu.core.machine import make_mesh
    from flexflow_tpu.kernels import grouped_experts as kernel

    config = dict(WHOLE, hidden_size=256, moe_intermediate_size=128)
    names = family.expert_layer_names(config)
    slots = 16
    prompt = np.random.default_rng(4).integers(0, 96, 21).astype(np.int32)
    crowd = np.random.default_rng(5).integers(0, 96, slots).astype(np.int32)

    def run(mode):
        monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", mode)
        ff = FFModel(FFConfig(batch_size=slots, ledger="off", seed=7,
                              compute_dtype="bfloat16",
                              computation_mode=CompMode.INFERENCE))
        build_trinity_lm(ff, slots, MAX_LEN, dataclasses.replace(
            family.program_config(config), draw_weights=True))
        ff.compile(optimizer=None, loss_type=None, metrics=[],
                   mesh=make_mesh(devices=jax.devices()[:1]))
        dec = PagedDecoder(ff, MAX_LEN, decode_slots=slots, block_size=BLOCK,
                           prefill_chunk=16, calibrate=False)
        # three steps of one live slot
        rows, toks, _ = _paged_run(dec, names, prompt, 3, slot=2)
        alone = dec.expert_stats()
        # and two of sixteen: every slot a request of one token
        tables = [dec.pool.try_admit(4) for _ in range(slots)]
        table = np.zeros((slots, dec.max_blocks_per_request), np.int32)
        for s, tb in enumerate(tables):
            table[s, :len(tb)] = tb
        named = {nm: [] for nm in names}
        for k in range(2):
            dec.decode(crowd + k, table, np.full(slots, k, np.int32))
            for nm in names:
                named[nm].append(len(set(
                    np.asarray(dec.last_routing[nm]).ravel().tolist())))
        return dec, rows, toks, alone, dec.expert_stats(), named

    dec, rows, toks, alone, both, named = run("interpret")
    assert {dec._decode_form(op) for op in dec._expert_ops} == {"counted"}
    assert {op.kernel_limit() for op in dec._expert_ops} == {7}
    tile = kernel.tile_rows(slots)
    for nm in names:
        assert alone[nm]["form_decode"] == "counted"
        assert alone[nm]["steps"] == alone[nm]["kernel_steps"] == 3
        assert alone[nm]["rows_computed"] == tile * (
            3 * 8 - alone[nm]["idle_held_experts"])
        assert both[nm]["steps"] == 5
        crowded = [n for n in named[nm]]
        assert both[nm]["kernel_steps"] == 3 + sum(n <= 7 for n in crowded)
        assert both[nm]["rows_computed"] - alone[nm]["rows_computed"] == sum(
            n * tile if n <= 7 else 8 * slots for n in crowded)
    # both arms were reached: some layer's crowded step named all eight
    assert any(n == 8 for nm in names for n in named[nm])
    assert all(r["kernel_steps"] <= r["steps"] for r in both.values())
    _, want, toks_dense, _, dense, _ = run("off")
    assert {r["form_decode"] for r in dense.values()} == {"dense"}
    assert {r["kernel_steps"] for r in dense.values()} == {0}
    assert {r["rows_computed"] for r in dense.values()} == {5 * 8 * slots}
    same = 1 + sum(np.cumprod(toks[21:] == toks_dense[21:]))
    assert same >= 2
    assert np.abs(rows - want)[:same].max() <= 0.03 * np.abs(want).max()
