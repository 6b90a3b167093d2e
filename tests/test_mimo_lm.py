"""Full layers of few key-value heads beside windowed layers of more,
keys wider than values, rotary over the first third of a head with a base
a kind of layer, a learned sink in the windowed layers' softmax, scaled
values and sigmoid experts held by share (the MiMo-V2 line), at toy
widths on the CPU: the program against the plain reference
(``benchmark/reference/mimo.py``, which imports nothing of the program):
the attention op's whole forward by kind of layer; the whole model;
prompts prefilled in chunks and decoded through both kinds of cache entry
against the reference's full forward, with a ring of two blocks, a prompt
that wraps it and one that fills it inside decoding, in the jnp forms at
key 48 / value 32 and through both kernels under the interpreter at key
192 / value 128; the two kernels against the jnp forms with and without a
sink; the sixteen shares against the uncut layer; what the pool and the
path report say. The programs compiled for the chip at the published
widths, and the older cells' kernels' lowered text, are in
tests/test_tpu_lowering.py."""

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

from benchmark.families import mimo as family  # noqa: E402
from benchmark.reference import mimo as reference  # noqa: E402
from flexflow_tpu import FFConfig, FFModel  # noqa: E402
from flexflow_tpu.core.op import LowerCtx  # noqa: E402
from flexflow_tpu.ffconst import CompMode, DataType  # noqa: E402
from flexflow_tpu.kernels import chunk_attention, paged_attention  # noqa: E402
from flexflow_tpu.models import build_trinity_lm  # noqa: E402
from flexflow_tpu.serving import GenerationInstance, cache_entry  # noqa: E402
from flexflow_tpu.serving.generation import PagedDecoder  # noqa: E402
from flexflow_tpu.serving.kv_cache import pool_bytes  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "tests", "data", "configs",
                       "mimo-toy.json")) as _f:
    # keys of 48 beside values of 32 (the 192 / 128 ratio), 8 query heads
    # on 2 (full) and 4 (windowed) key-value heads, a window of two blocks
    # of 8, the first 16 numbers of a head rotated
    TOY = json.load(_f)
# the whole toy model: every expert held
WHOLE = dict(TOY, n_routed_experts=8, expert_first=0)
# the toy at the widths the kernels take: a key head of one and a half
# lane tiles, stored split, beside a value head of one
LANE_TOY = dict(TOY, head_dim=192, swa_head_dim=192, v_head_dim=128,
                swa_v_head_dim=128)
SEED = 2 ** 31 + 55
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


@pytest.fixture(scope="module")
def lane_toy():
    """The toy with keys of 192 and values of 128 in a model over ONE
    device (the tests' eight virtual devices are a mesh the chunk's
    kernel does not take)."""
    from flexflow_tpu.core.machine import make_mesh

    return _program(LANE_TOY, mesh=make_mesh(devices=jax.devices()[:1]))


@pytest.fixture()
def short_spans(monkeypatch):
    """Key spans of 16: a chunk's attend walks several of them."""
    monkeypatch.setattr(cache_entry, "SPAN_TOKENS", 16)


# ---- the attention op by kind of layer ---------------------------------------

@pytest.mark.parametrize("layer", [0, 1], ids=["full", "windowed"])
def test_attention_op_forward_equals_the_references(toy, layer):
    """``MultiHeadAttention``'s whole forward with a value width of its
    own, the first 16 of a head's 48 numbers rotated (base 1e7 in the full
    layer, 1e4 in the windowed one), values times 0.707, and in the
    windowed layer the band's mask and a sink a head: the reference's
    attention piece less its residual. 40 positions: the window of 16
    bites."""
    ff, weights = toy
    op = _op(ff, f"block{layer}_attn")
    assert (op.window, op.rotary, op.sinks, op.num_kv_heads) == (
        (16, 1e4, True, 4) if layer else (None, 1e7, False, 2))
    assert (op.head_dim, op.v_head_dim, op.rotary_dim, op.value_scale) == (
        48, 32, 16, 0.707)
    assert not op.gate and not op.qk_norm
    w = ff.compiled.params[op.name]
    assert w["wv"].shape == (64, op.num_kv_heads, 32)
    assert w["wo"].shape == (8, 32, 64) and w["wq"].shape == (64, 8, 48)
    assert ("sinks" in w) == bool(layer)
    s = 40
    x = jax.random.normal(jax.random.key(layer), (2, s, 64))
    lw = _layer(weights, layer)
    u = reference._rms(x, lw["norm_in"], 1e-5)
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (2, s))
    got = op.forward(LowerCtx(mesh=None, training=False), [u, u, u, pos],
                     w)[0]
    want = _pieces(TOY)["attention"](x, lw, kind=layer) - x
    assert np.abs(np.asarray(got - want)).max() <= 2e-5 * float(
        np.abs(want).max())
    # the sink counts: without it the windowed layer's output moves
    if layer:
        bare = op.forward(LowerCtx(mesh=None, training=False),
                          [u, u, u, pos], dict(w, sinks=w["sinks"] - 30.0))[0]
        assert np.abs(np.asarray(bare - want)).max() > 0.05 * float(
            np.abs(want).max())
    # the two widths are counted, and the band, not the square
    full, windowed = _op(ff, "block0_attn"), _op(ff, "block1_attn")
    b, e = 3, 64
    assert full.flops() == 2.0 * b * MAX_LEN * e * (8 * (48 + 32)
                                                    + 2 * (48 + 32)) + \
        2.0 * b * 8 * MAX_LEN * MAX_LEN * (48 + 32)
    assert windowed.flops() == 2.0 * b * MAX_LEN * e * (8 * 80 + 4 * 80) + \
        2.0 * b * 8 * MAX_LEN * 16 * 80


@pytest.mark.parametrize("config", [TOY, WHOLE], ids=["share", "whole"])
def test_whole_forward_equals_the_references(config):
    """The whole model cache-free (each kind's ``whole``) against the
    reference's forward over 50 tokens, the reference taking the
    program's routing: 2e-4 of the logits' range, float32 summation order
    over 4 layers."""
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
    (53, 16, 6),     # wraps the ring three times, a last chunk of 5
    (12, 16, 10),    # the ring fills and first wraps INSIDE decoding
    (30, 24, 5),     # a chunk longer than the ring, its padding (30..47)
                     # across the ring's wrap at 32
], ids=["wraps", "fills-in-decode", "padding-wraps"])
@pytest.mark.parametrize("form", ["scan", "kernel"])
def test_chunked_prefill_and_decode_equal_the_references_forward(
        request, monkeypatch, short_spans, form, n, chunk, steps):
    """A prompt prefilled in chunks (the full layers through their block
    tables at 2 key-value heads, a windowed layer over ``[its ring | the
    chunk]`` at 4, behind its sink), then decode steps: the LOGITS of the
    reference's cache-free forward over the whole sequence. 2e-4 of the
    logits' range: float32 summation order. ``scan``: the jnp forms at key
    48 / value 32 (the gather and the span walk); ``kernel``: both
    kernels under the interpreter at key 192 / value 128, the keys stored
    split."""
    if form == "kernel":
        monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    config = LANE_TOY if form == "kernel" else TOY
    ff, weights = request.getfixturevalue(
        "lane_toy" if form == "kernel" else "toy")
    dec = PagedDecoder(ff, MAX_LEN, decode_slots=3, block_size=BLOCK,
                       prefill_chunk=chunk, calibrate=False)
    said = "kernel" if form == "kernel" else "gather"
    assert dec.attention_path["chunk"] == form
    assert dec.attention_path["decode"] == said
    # ... and of BOTH kinds of layer, so a chip run says what it timed
    assert dec.attention_path_by_entry == {
        "pair": {"decode": said, "chunk": form},
        "window": {"decode": said, "chunk": form}}
    names = family.expert_layer_names(config)
    prompt = np.random.default_rng(n).integers(
        0, config["vocab_size"], n).astype(np.int32)
    rows, toks, ids = _paged_run(dec, names, prompt, steps, slot=1)
    want = _against_reference(config, weights, rows, toks, ids)
    assert np.abs(rows - want).max() <= 2e-4 * np.abs(want).max()


def test_dense_steps_equal_the_references_forward(toy):
    """The dense form (what the calibration gate and ``generate`` without
    a pool use) carries the sink and the two widths: a prompt and decode
    steps through ``dense_step`` give the whole forward's logits."""
    ff, weights = toy
    dec = PagedDecoder(ff, MAX_LEN, decode_slots=3, block_size=BLOCK,
                       calibrate=False)
    for op in dec._attn_ops:
        kind = dec.pool.kinds[op.name]
        k, v = kind.dense_shapes(2, 24, jnp.float32)
        assert k.shape == (2, 24, op.num_kv_heads, 48)
        assert v.shape == (2, 24, op.num_kv_heads, 32)
        w = ff.compiled.params[op.name]
        x = jax.random.normal(jax.random.key(3), (2, 24, 64))
        pos = jnp.broadcast_to(jnp.arange(24, dtype=jnp.int32), (2, 24))
        whole = kind.whole(op, w, x, pos)[0]
        cache = tuple(jnp.zeros(a.shape, a.dtype) for a in (k, v))
        first, cache = kind.dense_step(op, w, x[:, :20], pos[:, :20], cache,
                                       0)
        outs = [first]
        for t in range(20, 24):
            o, cache = kind.dense_step(op, w, x[:, t:t + 1], pos[:, t:t + 1],
                                       cache, t)
            outs.append(o)
        got = jnp.concatenate(outs, axis=1)
        assert np.abs(np.asarray(got - whole)).max() <= 2e-5 * float(
            np.abs(whole).max())


# ---- the two kernels against the jnp forms -----------------------------------

def _rows(key, shape):
    return jax.random.normal(jax.random.key(key), shape, jnp.float32)


@pytest.mark.parametrize("sink", [False, True], ids=["plain", "sink"])
@pytest.mark.parametrize("kv_heads,dk,dv", [(2, 192, 128), (4, 192, 128),
                                            (2, 128, 256), (2, 128, 128)],
                         ids=["192-128-on-2", "192-128-on-4", "128-256",
                              "128-128"])
def test_the_paged_kernel_is_the_gathers_attend(monkeypatch, kv_heads, dk,
                                                dv, sink):
    """``paged_attention_decode`` under the interpreter at unequal widths
    (keys stored split where a head is one and a half tiles) against the
    jnp attend over the gathered rows, with and without a sink a head; 8
    query heads, slots of 0, 5, 40 and 61 cached tokens, blocks of 8."""
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    heads, bs, mb, n = 8, 8, 8, 4
    lens = jnp.asarray([0, 5, 40, 61], jnp.int32)
    q = _rows(1, (n, 1, heads, dk))
    k = _rows(2, (n * mb + 1, bs, kv_heads, dk))
    v = _rows(3, (n * mb + 1, bs, kv_heads, dv))
    tables = (1 + jnp.arange(n * mb, dtype=jnp.int32)).reshape(n, mb)
    s = _rows(4, (heads,)) * 2 if sink else None
    k_arena = paged_attention.split_heads(
        k.reshape(k.shape[:2] + (-1,)), kv_heads)
    v_arena = v.reshape(v.shape[:2] + (-1,))
    assert paged_attention.supported(q.shape, k_arena.shape, jnp.float32, mb,
                                     v_arena.shape[-1])
    got = paged_attention.paged_attention_decode(
        q, k_arena, v_arena, tables, lens, scale=dk ** -0.5, sink=s)
    assert got.shape == (n, 1, heads, dv)
    kg = k[tables].reshape(n, mb * bs, kv_heads, dk)
    vg = v[tables].reshape(n, mb * bs, kv_heads, dv)
    want = cache_entry._attend(
        q, kg, vg, lambda: (jnp.arange(mb * bs)[None, None, :]
                            <= lens[:, None, None])[:, None], dk ** -0.5, s)
    assert np.abs(np.asarray(got - want)).max() <= 2e-5
    if sink:
        bare = cache_entry._attend(
            q, kg, vg, lambda: (jnp.arange(mb * bs)[None, None, :]
                                <= lens[:, None, None])[:, None], dk ** -0.5)
        assert np.abs(np.asarray(got - bare)).max() > 1e-2


@pytest.mark.parametrize("sink", [False, True], ids=["plain", "sink"])
@pytest.mark.parametrize("kv_heads,heads,dk,dv,window", [
    (2, 8, 192, 128, None), (4, 8, 192, 128, 16), (2, 4, 128, 256, 16),
    (2, 32, 192, 128, None)],
    ids=["full-192-128", "windowed-192-128", "windowed-128-256",
         "group-of-16"])
def test_the_chunk_kernel_is_the_span_walks_attend(monkeypatch, kv_heads,
                                                   heads, dk, dv, window,
                                                   sink):
    """``chunk_attention`` under the interpreter at unequal widths against
    ``_attend_spans``: 16 queries at positions 24..39 over 48 key rows of
    which the first 8 hold nothing, rows in the arena's layout (keys
    split), with and without a sink; a group of 16 query heads a key head
    is walked in parts where it does not fit at once."""
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    n, s, keys = 2, 16, 48

    class Op:
        scale = dk ** -0.5

        def sees(self, qpos, kpos):
            seen = kpos <= qpos
            return seen & (qpos - kpos < window) if window else seen

    Op.window = window
    q = _rows(5, (n, s, heads, dk))
    k = _rows(6, (n, keys, kv_heads, dk))
    v = _rows(7, (n, keys, kv_heads, dv))
    sk = _rows(8, (heads,)) * 2 if sink else None
    qpos = jnp.broadcast_to(24 + jnp.arange(s, dtype=jnp.int32), (n, s))
    at = jnp.arange(keys, dtype=jnp.int32)
    kpos = jnp.broadcast_to(jnp.where(at >= 8, at - 8, cache_entry.NOWHERE),
                            (n, keys))
    k_rows = paged_attention.split_heads(k.reshape(n, keys, -1), kv_heads)
    v_rows = v.reshape(n, keys, -1)
    assert chunk_attention.supported(q.shape, q.dtype, k_rows.shape,
                                     k_rows.dtype, v_rows.shape[-1])
    if heads // kv_heads == 16:
        monkeypatch.setattr(chunk_attention, "VMEM_BUDGET_BYTES", 1 << 20)
        assert chunk_attention.heads_a_step(16, 48, 16, dk, dv,
                                            jnp.float32) < 16
    got = cache_entry._attend_kernel(Op(), q, qpos, k_rows, v_rows, kpos, sk)
    assert got.shape == (n, s, heads, dv)
    span = 16
    want = cache_entry._attend_spans(
        Op(), q, qpos, kv_heads,
        lambda j: tuple(jax.lax.dynamic_slice_in_dim(a, j * span, span, 1)
                        for a in (k, v, kpos)), 0, keys // span, sk, dv)
    assert np.abs(np.asarray(got - want)).max() <= 2e-5
    if sink:
        bare = cache_entry._attend_spans(
            Op(), q, qpos, kv_heads,
            lambda j: tuple(jax.lax.dynamic_slice_in_dim(a, j * span, span, 1)
                            for a in (k, v, kpos)), 0, keys // span, None, dv)
        assert np.abs(np.asarray(got - bare)).max() > 1e-2


@pytest.mark.parametrize("heads,d", [(4, 192), (8, 192), (3, 320), (4, 128),
                                     (8, 64), (2, 48)])
def test_split_heads_is_undone_by_join_heads(heads, d):
    """A key head of whole lane tiles and 64 more is stored in two parts,
    every head's first then every head's last, each starting on a 64-lane
    boundary; every other width as it is."""
    x = _rows(9, (5, heads * d))
    parts = paged_attention.key_parts(d)
    assert parts == ((d - 64, 64) if d in (192, 320) else (d,))
    y = paged_attention.split_heads(x, heads)
    assert np.array_equal(paged_attention.join_heads(y, heads), x)
    if len(parts) == 1:
        assert y is x
    else:
        xh = np.asarray(x).reshape(5, heads, d)
        assert np.array_equal(y[:, :heads * parts[0]],
                              xh[..., :parts[0]].reshape(5, -1))
        assert np.array_equal(y[:, heads * parts[0]:],
                              xh[..., parts[0]:].reshape(5, -1))


def test_the_kernels_refuse_what_they_cannot_take(monkeypatch):
    """The refusals unequal widths bring: a key head a query head with two
    widths or a split head (q and o share one layout there), a value head
    of no whole 64 lanes, an odd number of split key heads in a chunk, and
    an int8 form of two widths."""
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    f32 = jnp.float32
    ok = paged_attention.supported
    assert ok((4, 1, 8, 192), (9, 8, 2 * 192), f32, 8, 2 * 128)
    assert not ok((4, 1, 2, 192), (9, 8, 2 * 192), f32, 8, 2 * 128)
    assert not ok((4, 1, 2, 192), (9, 8, 2 * 192), f32, 8)
    assert not ok((4, 1, 8, 192), (9, 8, 2 * 192), f32, 8, 2 * 96)
    assert not ok((4, 1, 8, 192), (9, 8, 2 * 192), f32, 8, 2 * 32)
    okc = chunk_attention.supported
    assert okc((1, 16, 8, 192), f32, (1, 48, 2 * 192), f32, 2 * 128)
    assert not okc((1, 16, 9, 192), f32, (1, 48, 3 * 192), f32, 3 * 128)
    assert not okc((1, 16, 8, 192), f32, (1, 48, 2 * 192), f32, 2 * 64)
    assert not okc((1, 16, 8, 64), f32, (1, 48, 2 * 64), f32, 2 * 128)
    pair = cache_entry.PairEntry(2, 48, 8, value_dim=32)
    assert pair.int8_form is None
    assert cache_entry.PairEntry(2, 48, 8, sink=True).int8_form.sink


# ---- the older cells' kernels ------------------------------------------------
# sha256 of the traced program (``jax.make_jaxpr``: the ``pallas_call`` with
# its kernel's body, grid, block shapes and index maps; source locations
# struck) of the two serving kernels at the shapes the eight older serving
# cells run, recorded on 95eeb19, the commit before the kernels took a value
# width, a split key head and a sink: none of the three may change what an
# older cell runs. A digest that moves with a change to a kernel's body is
# recorded anew, with the cells' numbers on the chip beside it.
PAGED_SHAPES = {  # cell: (slots, H, D, Hkv, block size, blocks a table)
    "offline": (16, 20, 64, 20, 16, 64),
    "mixedlengths-full": (32, 48, 128, 8, 64, 272),
    "mixedlengths-ring": (32, 48, 128, 8, 64, 64),
    "rag": (48, 32, 64, 8, 64, 144),
    "chains": (48, 8, 128, 2, 64, 72),
    "agents": (128, 32, 128, 2, 16, 128),
    "documents": (32, 30, 128, 30, 16, 128),
}
CHUNK_SHAPES = {  # cell: (queries, H, D, Hkv, key rows, window)
    "mixedlengths-full": (2048, 48, 128, 8, 17408, None),
    "mixedlengths-window": (2048, 48, 128, 8, 4096 + 2048, 4096),
    "chains": (2048, 8, 128, 2, 4608, None),
}
DIGESTS = {
    ("paged", "offline"):
        "88b0a3380932add4d95543576f75d89b33bdfda15f3394d4f8aef45bc8b660ab",
    ("paged", "mixedlengths-full"):
        "10c357bc1a6d38fadf26fa59ab4a131f3547118f3181a2a405cf1801cf1bbcc9",
    ("paged", "mixedlengths-ring"):
        "4444435d092891e61cd4ce531eb190103be15ca52b6ecdb8955c1d1e98466ab2",
    ("paged", "rag"):
        "0a12ac0d091e59888870dbc4a38e96185e5b60fac9fd15726eb586db2e3ad94d",
    ("paged", "chains"):
        "e96e437fa6858bdee257615c9c9f9bf4a8f0631b29862ca7b66fe2bb4129a82d",
    ("paged", "agents"):
        "4c9544712f509b9677538bbb098fb7c79b01889dc583375828984837226cb58a",
    ("paged", "documents"):
        "9581ddb894d9c7d411818da85ce802b29043b6dc70be25f7c30ebe4dbadeb329",
    ("chunk", "mixedlengths-full"):
        "f5e7abd467359e8f9e55ba66f0364ca8e7a1999e4879c0ec27781ace83da098a",
    ("chunk", "mixedlengths-window"):
        "112f03a0c859a54d55d7f584568825996cfa0f99aaf5a0b4a34f6eb9cee3174e",
    ("chunk", "chains"):
        "8fd6b990af03c6c513e4d7d32475e2884438527914383cf3195bc91340877a3c",
}


@pytest.mark.parametrize("kernel,cell", sorted(DIGESTS))
def test_the_older_cells_kernels_trace_to_the_programs_they_were(
        monkeypatch, kernel, cell):
    import hashlib
    import re

    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "compiled")

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype)

    if kernel == "paged":
        n, h, d, hkv, bs, mb = PAGED_SHAPES[cell]
        arena = sds((n * mb + 1, bs, hkv * d))
        assert paged_attention.supported((n, 1, h, d), arena.shape,
                                         jnp.bfloat16, mb)
        traced = jax.make_jaxpr(
            lambda q, k, v, t, lens: paged_attention.paged_attention_decode(
                q, k, v, t, lens, scale=d ** -0.5))(
            sds((n, 1, h, d)), arena, arena, sds((n, mb), jnp.int32),
            sds((n,), jnp.int32))
    else:
        s, h, d, hkv, keys, window = CHUNK_SHAPES[cell]
        rows = sds((1, keys, hkv * d))
        assert chunk_attention.supported((1, s, h, d), jnp.bfloat16,
                                         rows.shape, jnp.bfloat16)
        traced = jax.make_jaxpr(
            lambda q, qp, k, v, kp: chunk_attention.chunk_attention(
                q, qp, k, v, kp, kv_heads=hkv, scale=d ** -0.5,
                window=window))(
            sds((1, s, h * d)), sds((1, s), jnp.int32), rows, rows,
            sds((1, keys), jnp.int32))
    text = re.sub(r" at [^\s]+:\d+", "", str(traced))
    assert hashlib.sha256(text.encode()).hexdigest() == DIGESTS[kernel, cell]


# ---- the share ---------------------------------------------------------------

def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """The share tied to the model: the routed parts of sixteen holders
    (2 experts each of 32, top-8) are the uncut layer's ``f``, in the
    reference and in the program alike."""
    uncut = dict(TOY, n_routed_experts=32, expert_first=0,
                 num_experts_per_tok=8,
                 published=dict(TOY["published"], n_routed_experts=32))
    weights = reference.init_weights(uncut, SEED)
    lw = _layer(weights, 1)
    # scaled up, so that the layer's output stands beside float32's
    # cancellation (N(0, 0.02) at these widths leaves it under it)
    lw.update({k: lw[k].astype(jnp.float32) * 8 for k in lw
               if k.startswith("experts.")})
    x = jax.random.normal(jax.random.key(4), (1, 11, 64))
    f = _pieces(uncut)
    s, ids, _ = f["scores_of"](x, lw)
    assert ids.shape == (11, 8)
    m = reference._rms(x, lw["norm_pre_mlp"], 1e-5)[0]
    whole = np.asarray(f["routed_part"](x, lw, s, ids))
    # the uncut layer's own piece agrees: x + f
    assert np.allclose(f["expert_ffn"](x, lw, s, ids) - x, whole, atol=1e-5)
    parts, program_parts = [], []
    ff, _ = _program(dict(uncut, n_routed_experts=2))
    op = _op(ff, "block1_experts")
    for first in range(0, 32, 2):
        cfg = dict(uncut, n_routed_experts=2, expert_first=first)
        share = dict(lw, **{k: lw[k][first:first + 2] for k in lw
                            if k.startswith("experts.")})
        parts.append(np.asarray(_pieces(cfg)["routed_part"](x, share, s,
                                                             ids)))
        # the program's op for experts ``first``, ``first + 1``: the same
        # op with another run of experts held
        op.first = first
        w = {"router": share["router"], "bias": share["bias"],
             "w_gate": share["experts.gate"], "w_up": share["experts.up"],
             "w_down": share["experts.down"]}
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        ids_p, gates_p, _ = op.route(w, m)
        assert np.array_equal(np.sort(np.asarray(ids_p), -1),
                              np.sort(np.asarray(ids), -1))
        program_parts.append(np.asarray(op.apply(w, m, ids_p, gates_p)))
    assert len(parts) == 16
    assert np.abs(whole).max() > 0.01
    tol = 2e-5 * np.abs(whole).max()
    assert np.abs(sum(parts) - whole).max() <= tol
    assert np.abs(sum(program_parts) - whole).max() <= tol


# ---- the pool and what a run says --------------------------------------------

def test_the_pool_keeps_two_widths_and_says_so(toy):
    """Two arenas of two widths in both kinds: a token takes ``Hkv * (48 +
    32)`` numbers in a full layer, a request a ring of 16 such rows at 4
    heads in a windowed one; ``stats()["kv"]`` says each entry's widths,
    heads and sink; there is no int8 form."""
    ff, _ = toy
    dec = PagedDecoder(ff, MAX_LEN, decode_slots=3, block_size=BLOCK,
                       calibrate=False)
    kinds = dec.pool.kinds
    pair, ring = kinds["block0_attn"], kinds["block1_attn"]
    assert (pair.heads, pair.head_dim, pair.value_dim, pair.sink) == (
        2, 48, 32, False)
    assert (ring.heads, ring.head_dim, ring.value_dim, ring.sink,
            ring.window) == (4, 48, 32, True, 16)
    k, v = dec.pool.kv["block0_attn"]
    assert k.shape[1:] == (BLOCK, 2 * 48) and v.shape[1:] == (BLOCK, 2 * 32)
    k, v = dec.pool.kv["block1_attn"]
    assert k.shape[1:] == (BLOCK, 4 * 48) and v.shape[1:] == (BLOCK, 4 * 32)
    assert pair.token_bytes(jnp.float32) == 2 * 80 * 4
    assert ring.request_bytes(jnp.float32) == 16 * 4 * 80 * 4
    # 10 blocks of 8 tokens in two full layers, 4 rings in two windowed
    assert pool_bytes(kinds, 10, BLOCK, "float32", jnp.float32, 4) == (
        10 * BLOCK * 2 * (2 * 80 * 4) + 4 * 2 * (16 * 4 * 80 * 4))
    kv = dec.pool.stats()
    assert kv["entry"] == {"pair": 2, "window": 2} and kv["window"] == 16
    assert kv["by_entry"] == {
        "pair": {"kv_heads": 2, "query_heads": 8, "key_dim": 48,
                 "value_dim": 32, "sink": False},
        "window": {"kv_heads": 4, "query_heads": 8, "key_dim": 48,
                   "value_dim": 32, "sink": True, "window": 16}}
    with pytest.raises(ValueError, match="int8"):
        PagedDecoder(ff, MAX_LEN, decode_slots=3, block_size=BLOCK,
                     kv_dtype="int8", calibrate=False)


def test_the_scheduler_counts_the_last_chunks_and_says_both_kinds(toy):
    """``stats()["loop"]`` over a session of known lengths: beside every
    chunk's tokens and keys, those of the chunks that were their prompt's
    last (the only ones whose last layer attends, which the cell's
    ``chunk_attention_mfu`` counts by); ``stats()["kv"]`` says each kind
    of entry's heads, widths and sink, and how each is read."""
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
    loop = st["loop"]
    assert loop["prefill_chunks"] == 1 + 3 and loop["prefill_tokens"] == 49
    assert loop["prefill_keys"] == sum(p + 1 for n, _ in jobs
                                       for p in range(n))
    # the last chunks: all of the 9, and positions 32..39 of the 40
    assert loop["prefill_tokens_last"] == 9 + 8
    assert loop["prefill_keys_last"] == sum(range(1, 10)) + sum(
        range(33, 41))
    kv = st["kv"]
    assert kv["by_entry"]["window"]["sink"] is True
    assert kv["by_entry"]["pair"] == {"kv_heads": 2, "query_heads": 8,
                                      "key_dim": 48, "value_dim": 32,
                                      "sink": False}
    assert kv["attention_path_by_entry"] == {
        "pair": {"decode": "gather", "chunk": "scan"},
        "window": {"decode": "gather", "chunk": "scan"}}
    w = kv["window"]
    assert (w["rows"], w["ops"]) == (16, 2)
