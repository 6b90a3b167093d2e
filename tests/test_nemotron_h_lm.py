"""State-space layers, latent experts held by share and grouped-head
attention, at toy widths on the CPU: the program against the plain
reference (``benchmark/reference/nemotron_h.py``, which imports nothing
of the program): the ``M`` op's chunked form, its step and the
token-by-token recurrence; prefill then decode through the pool against
the reference's full forward; the expert layer's two forms against each
other and the reference, and the grouped kernel (Pallas interpreter) against
both; the choice by ``s + b``; grouped heads through
``PairEntry`` and the paged kernel; the four shares against the uncut
layer. The programs compiled for the chip at the published widths are in
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

from benchmark.families import nemotron_h as family  # noqa: E402
from benchmark.reference import nemotron_h as reference  # noqa: E402
from flexflow_tpu import FFConfig, FFModel  # noqa: E402
from flexflow_tpu.ffconst import CompMode, DataType  # noqa: E402
from flexflow_tpu.kernels import paged_attention  # noqa: E402
from flexflow_tpu.models import build_nemotron_h_lm  # noqa: E402
from flexflow_tpu.ops import mamba2  # noqa: E402
from flexflow_tpu.serving import cache_entry  # noqa: E402
from flexflow_tpu.serving.generation import PagedDecoder  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "tests", "data", "configs",
                       "nemotron-h-toy.json")) as _f:
    TOY = json.load(_f)
# the whole toy model: every expert held
WHOLE = dict(TOY, n_routed_experts=16, expert_first=0)
SEED = 2 ** 31 + 5
MAX_LEN = 48


def _program(config, seed=SEED, slots=3):
    """The program's graph for ``config`` in float32 holding the
    reference's seeded weights; returns (ff, weights)."""
    cfg = dataclasses.replace(family.program_config(config),
                              param_dtype=DataType.FLOAT, draw_weights=True)
    ff = FFModel(FFConfig(batch_size=slots, ledger="off",
                          computation_mode=CompMode.INFERENCE))
    build_nemotron_h_lm(ff, slots, MAX_LEN, cfg)
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


# ---- the M op ----------------------------------------------------------------

@pytest.mark.parametrize("length,bucket", [(5, 5), (19, 19), (19, 32),
                                           (24, 24), (9, 16)])
def test_mamba_chunked_form_step_and_recurrence_agree(toy, length, bucket):
    """The chunked whole-sequence form (chunks of 8; lengths that are no
    multiple of it; a bucket padded past the true length), the one-token
    step carried from an empty state, and the reference's token-by-token
    recurrence give the same outputs, and the first two the same state
    and convolution tail at the prompt's TRUE length. float32 against
    float32: 2e-5 of the outputs' range is summation order."""
    ff, weights = toy
    op = _op(ff, "block1_mixer")
    w = ff.compiled.params["block1_mixer"]
    x = jax.random.normal(jax.random.key(length), (1, bucket, 32))
    # the reference's piece is the whole block: x + mixer(norm(x))
    lw = _layer(weights, 1)
    u = reference._rms(x, lw["norm"], 1e-5)
    want = np.asarray(_pieces(TOY)["mamba"](x[:, :length], lw)
                      - x[:, :length])
    y, state, tail = op.whole(w, u, jnp.asarray([length], jnp.int32))
    tol = 2e-5 * np.abs(want).max()
    assert np.abs(np.asarray(y)[:, :length] - want).max() <= tol
    st, tl = op.empty(1, u.dtype)
    outs = []
    for t in range(length):
        o, st, tl = op.run(w, u[:, t:t + 1], st, tl)
        outs.append(np.asarray(o)[0, 0])
    assert np.abs(np.stack(outs) - want[0]).max() <= tol
    assert np.abs(np.asarray(state - st)).max() <= 2e-5 * np.abs(st).max()
    assert np.array_equal(np.asarray(tail), np.asarray(tl))


def test_mamba_step_on_the_arena_rows_is_the_plain_step():
    """``ssd_step_rows`` (the arena updated where it lies, each row
    taking the inputs of the slot that names it) against ``ssd_step`` on
    the gathered rows; the null row and the rows no slot names stay. A
    row is stored as the pool stores it, ``(S, H P)``."""
    rng = np.random.default_rng(0)
    rows, n, h, p, s, g = 6, 3, 4, 8, 8, 2
    arena = jnp.asarray(rng.normal(size=(rows, s, h * p)), jnp.float32)

    def states(a):                       # rows (.., S, H P) -> (.., H, P, S)
        return jnp.moveaxis(a.reshape(a.shape[:-1] + (h, p)), -3, -1)

    slot_rows = jnp.asarray([4, 0, 2], jnp.int32)
    u = jnp.asarray(rng.normal(size=(n, h, p)), jnp.float32)
    decay = jnp.asarray(rng.uniform(0.5, 1.0, (n, h)), jnp.float32)
    bm, cm = (jnp.asarray(rng.normal(size=(n, g, s)), jnp.float32)
              for _ in range(2))
    y, new = mamba2.ssd_step_rows(arena, slot_rows, u, decay, bm, cm)
    want_y, want = mamba2.ssd_step(states(arena[slot_rows]), u, decay, bm,
                                   cm)
    for i, r in enumerate([4, 0, 2]):
        if r == 0:
            continue
        assert np.allclose(y[i], want_y[i], atol=1e-5)
        assert np.allclose(states(new[r]), want[i], atol=1e-6)
    for r in (0, 1, 3, 5):
        assert np.array_equal(np.asarray(new[r]), np.asarray(arena[r]))


# ---- the whole model through the pool ---------------------------------------

def _paged_run(dec, names, prompt, steps, slot=0):
    """Prefill then greedy decode steps in ``slot``; the logits of each
    step, the token sequence, the routing per expert layer."""
    n = len(prompt)
    table = dec.pool.try_admit(n + steps + 1)
    rows, toks = [dec.prefill(prompt, table)], list(prompt)
    ids = [[np.asarray(dec.last_routing[nm])[0, :n]] for nm in names]
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


@pytest.mark.parametrize("config", [TOY, WHOLE], ids=["share", "whole"])
def test_paged_prefill_and_decode_equal_the_references_forward(config):
    """A prompt of 19 tokens in a bucket of 32 (two whole chunks of 8 and
    a partial one, then padding), then decode steps through the pool (the
    states stepped where they lie, the ``*`` layer's grouped heads through
    its block table): the logits of the reference's cache-free forward
    over the whole sequence, the reference taking the program's routing;
    and in float32 the two route alike. 2e-4 of the logits' range:
    float32 summation order over 5 layers."""
    ff, weights = _program(config)
    dec = PagedDecoder(ff, MAX_LEN, decode_slots=3, block_size=8,
                       prefill_buckets=[16, 32])
    names = family.expert_layer_names(config)
    prompt = np.random.default_rng(3).integers(
        0, config["vocab_size"], 19).astype(np.int32)
    rows, toks, ids = _paged_run(dec, names, prompt, 4, slot=1)
    logits, info = reference.forward_with_routing(
        weights, jnp.asarray(toks[None]), config, "float32", routing=ids)
    want = np.asarray(logits)[0, len(toks) - len(rows):]
    assert np.abs(rows - want).max() <= 2e-4 * np.abs(want).max()
    for got, layer in zip(ids, info):
        assert np.array_equal(np.sort(got, -1),
                              np.sort(np.asarray(layer["own_ids"]), -1))
    kv = dec.pool.stats()
    assert kv["entry"] == {"ssm_state": 2, "pair": 1}
    assert kv["state_dtype"] == "float32"
    assert (kv["kv_heads"], kv["query_heads"]) == (2, 4)
    # a request's row: 2 M layers of a float32 state and a 3-position tail
    assert kv["state"]["row_bytes"] == 2 * (4 * 8 * 8 * 4 + 3 * 64 * 4)
    st = dec.expert_stats()
    assert set(st) == set(names)
    held = config["n_routed_experts"]
    for rec in st.values():
        assert rec["steps"] == 4 and rec["pairs_routed"] == 4 * 4
        assert sum(rec["rows_per_held_expert"]) == rec["pairs_held"]
        # the rows computed beside the rows named: a decode step's three
        # slots through every held expert, the bucket's 32 rows likewise
        assert rec["form_decode"] == rec["form_prefill"] == "dense"
        assert rec["rows_computed"] == 4 * 3 * held
        assert rec["prompt_rows_computed"] == 32 * held
        assert 0 <= rec["prompt_pairs_held"] <= 19 * 4
        if held == 16:
            assert rec["prompt_pairs_held"] == 19 * 4


# ---- the expert layer --------------------------------------------------------

def _expert_case(ff, weights, tokens=40):
    op = _op(ff, "block0_mixer")
    w = ff.compiled.params["block0_mixer"]
    u = jax.random.normal(jax.random.key(1), (tokens, 32))
    # experts 4-7 are held. Token 0 names none of them; every other token
    # names one of 4, 5 and 7 (13 rows each: a tile of 16 holds them);
    # expert 6 gets no row from anyone
    ids = np.array([[(4, 5, 7)[t % 3], 8 + t % 4, 12 + t % 4, t % 4]
                    for t in range(tokens)], np.int32)
    ids[0] = [0, 1, 2, 3]
    return op, w, u, jnp.asarray(ids)


def test_expert_forms_agree_with_each_other_and_the_reference(toy):
    """Every token through every held expert (``dense``) and the named
    pairs gathered into a tile an expert (``grouped``) are the
    reference's sum, for a routing in which one token names no held
    expert and one held expert gets no row; and where one expert is named
    more often than its tile holds, the rows beyond it go through a spill
    tile, and beyond that the grouped form gives the dense form's result
    (its fallback: nothing is dropped). 1e-5 of the
    outputs' range: float32 summation order."""
    ff, weights = toy
    op, w, u, ids = _expert_case(ff, weights)
    _, gates, _ = op.route(w, u, ids)
    v = u @ w["latent_down"]
    counts = np.bincount(np.asarray(ids).ravel(), minlength=16)[4:8]
    assert 0 < counts.max() <= op.capacity(40) == 16 and counts[2] == 0
    dense = np.asarray(op._apply_dense(w, v, ids, gates))
    grouped = np.asarray(op._apply_grouped(w, v, ids, gates))
    # expert 5 named by 26 tokens: 10 rows spill into the one spill tile
    spilled = np.array(ids)
    spilled[1:27, 0] = 5
    _, g2, _ = op.route(w, u, jnp.asarray(spilled))
    d2 = np.asarray(op._apply_dense(w, v, jnp.asarray(spilled), g2))
    s2 = np.asarray(op._apply_grouped(w, v, jnp.asarray(spilled), g2))
    assert op.spill_tiles == 1 and np.abs(d2 - s2).max() <= 1e-5 * np.abs(
        d2).max() and not np.array_equal(d2, s2)
    # by all 40: more than the spill tile holds, the dense form's result
    crowded = jnp.asarray(spilled).at[:, 0].set(5)
    _, g3, _ = op.route(w, u, crowded)
    assert np.array_equal(np.asarray(op._apply_grouped(w, v, crowded, g3)),
                          np.asarray(op._apply_dense(w, v, crowded, g3)))
    assert np.abs(dense[0]).max() == 0.0 and np.abs(grouped[0]).max() == 0.0
    tol = 1e-5 * np.abs(dense).max()
    assert np.abs(dense - grouped).max() <= tol
    # the reference's layer less its residual and its shared expert, with
    # the routed part's weights scaled up (N(0, 0.02) at these widths
    # leaves it at 3e-6 beside a residual of 3, below float32's
    # cancellation)
    big = {"latent_down": "latent_down", "latent_up": "latent_up",
           "experts.up": "w_up", "experts.down": "w_down"}
    lw = dict(_layer(weights, 0), norm=jnp.ones(32))
    lw.update({k: lw[k].astype(jnp.float32) * 8 for k in big})
    w = dict(w, **{v: w[v] * 8 for v in big.values()})
    x = u[None]                # the piece norms its input: gain 1 here
    un = reference._rms(x, lw["norm"], 1e-5)[0]
    ids_n, gates_n, _ = op.route(w, un, ids)
    s = jax.nn.sigmoid(un @ w["router"])
    whole = np.asarray(_pieces(TOY)["expert_ffn"](x, lw, s, ids))[0]
    shared = np.asarray(reference._relu2_mlp(
        un, lw["shared.up"], lw["shared.down"], "float32"))
    got = np.asarray(op.apply(w, un, ids_n, gates_n))
    want = whole - np.asarray(x[0]) - shared
    assert np.abs(want).max() > 0.1
    assert np.abs(got - want).max() <= 2e-5 * np.abs(want).max()


# ---- the grouped kernel (kernels/grouped_experts.py), interpreted ------------

def _bf16_experts(activation, e=256, width=128, **attrs):
    """A routed-experts op over bfloat16 rows at the smallest widths the
    kernel takes (``work_dim`` 256), experts 4-9 of 16 held, top-4; with
    seeded weights."""
    from flexflow_tpu.core.layer import Layer
    from flexflow_tpu.core.parallel_tensor import ParallelTensorShape
    from flexflow_tpu.ffconst import OpType
    from flexflow_tpu.ops.moe_ops import RoutedExperts

    attrs = dict(dict(n_routed=16, experts_per_token=4, width=width,
                      experts_held=(4, 6), activation=activation,
                      routed_scale=2.5), **attrs)
    op = RoutedExperts(
        Layer(OpType.ROUTED_EXPERTS, "x", attrs=attrs),
        [ParallelTensorShape.unpartitioned((1, 8, e), DataType.BFLOAT16)])
    key = jax.random.key(3)
    w = {ws.name: (0.08 * jax.random.normal(
        jax.random.fold_in(key, i), ws.shape)).astype(jnp.bfloat16)
        for i, ws in enumerate(op.weight_specs())}
    return op, w


def _routing(name, rows):
    """(rows, 4) expert ids of 16, experts 4-9 held."""
    t = np.arange(rows)
    if name in ("uniform", "ragged_rows", "nan_row"):
        ids = np.stack([(t + 3 * j) % 16 for j in range(4)], 1)
    elif name == "one_expert_by_all":      # expert 5: 2-3 tiles of its own
        ids = np.stack([np.full(rows, 5), t % 4, 10 + t % 6,
                        np.full(rows, 4)], 1)
    elif name == "held_experts_named_by_none":   # 6, 8 and 9 get no row
        ids = np.stack([np.full(rows, 4), 5 + 2 * (t % 2), t % 4,
                        10 + t % 6], 1)
    else:                                   # no pair held at all
        ids = np.stack([t % 4, 10 + t % 3, 13 + t % 3, (t + 1) % 4], 1)
    return jnp.asarray(ids, jnp.int32)


def _float32_experts(op, w, v, ids, gates):
    """The held pairs' weighted sum in float32 numpy, a pair at a time."""
    w = {k: np.asarray(a, np.float32) for k, a in w.items()}
    v, ids, gates = (np.asarray(a) for a in (v.astype(jnp.float32), ids,
                                             gates))
    out = np.zeros(v.shape, np.float32)
    for t, e in zip(*np.nonzero((ids >= op.first)
                                & (ids < op.first + op.count))):
        c = ids[t, e] - op.first
        u = v[t] @ w["w_up"][c]
        if op.gated:
            g = v[t] @ w["w_gate"][c]
            h = g / (1 + np.exp(-g)) * u
        else:
            h = np.maximum(u, 0) ** 2
        out[t] += gates[t, e] * (h @ w["w_down"][c])
    return out


@pytest.mark.parametrize("routing", [
    "uniform", "one_expert_by_all", "held_experts_named_by_none",
    "no_pair_held", "ragged_rows", "nan_row"])
@pytest.mark.parametrize("activation", ["relu2", "silu_gated"])
def test_grouped_kernel_agrees_with_the_dense_form_and_float32(
        monkeypatch, activation, routing):
    """The kernel's sum over the held pairs is the dense form's and the
    float32 reference's, to bfloat16's rounding of the terms (1 % of the
    outputs' range), for a routing that spreads evenly, one whose every
    token names one expert (tiles of its own, one after another), one in
    which half the held experts get no row (no tile), one that names no
    held expert (exactly 0), rows that fill no whole tile, and two
    prompts in one call with a NaN in one token's row: that row alone is
    NaN, and every other row is what it is without the NaN. The rows it
    counts are the real tiles' (``ceil(n / 128)`` an expert)."""
    from flexflow_tpu.kernels import grouped_experts as kernel

    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    op, w = _bf16_experts(activation)
    rows = 200 if routing == "ragged_rows" else 256
    v = jax.random.normal(jax.random.key(5), (rows, 256)).astype(jnp.bfloat16)
    ids = _routing(routing, rows)
    _, gates, _ = op.route(w, v, ids)
    assert kernel.supported(rows, 4, 256, 128, 6, op.gated, v.dtype)
    got, counted = kernel.grouped_experts(v, ids, gates, w, first=op.first,
                                          gated=op.gated)
    assert got.dtype == v.dtype and got.shape == v.shape
    got = np.asarray(got, np.float32)
    load = np.bincount(np.asarray(ids).ravel(), minlength=16)[4:10]
    assert int(counted) == 128 * int(np.ceil(load / 128).sum())
    dense = np.asarray(op._apply_dense(w, v, ids, gates), np.float32)
    want = _float32_experts(op, w, v, ids, gates)
    if routing == "no_pair_held":
        assert load.sum() == 0 and np.array_equal(got, np.zeros_like(got))
        assert np.array_equal(dense, got)
        return
    scale = np.abs(want).max()
    assert scale > 0.05
    assert np.abs(got - want).max() <= 0.01 * scale
    assert np.abs(got - dense).max() <= 0.01 * scale
    if routing == "one_expert_by_all":
        assert load[1] == rows and int(counted) >= 2 * 128
    if routing == "held_experts_named_by_none":
        assert (load[[2, 4, 5]] == 0).all()
    if routing == "nan_row":
        # token 2 of the second prompt (row 130) names held experts
        assert load.sum() and ((np.asarray(ids)[130] >= 4)
                               & (np.asarray(ids)[130] < 10)).any()
        bad, _ = kernel.grouped_experts(
            v.at[130, 7].set(jnp.nan), ids, gates, w, first=op.first,
            gated=op.gated)
        bad = np.asarray(bad, np.float32)
        assert np.isnan(bad[130]).all()
        assert np.array_equal(np.delete(bad, 130, 0), np.delete(got, 130, 0))


def test_the_kernel_takes_the_cells_shapes_and_the_jnp_form_the_rest(
        monkeypatch):
    """``supported()`` from shapes alone: both routed cells' expert
    layers at the three buckets (the Nemotron share's matrices whole, the
    A.X-K1 share's cut along ``width``), not float32 rows, not a
    ``work_dim`` of odd lane tiles, not A.X-K1's rows at 2,048 (its rows
    and output alone are past the fast memory); without Pallas nothing.
    What it refuses runs the jnp grouped form, and says so."""
    from flexflow_tpu.kernels import grouped_experts as kernel

    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    nemotron = (22, 1024, 2688, 128, False, jnp.bfloat16)
    axk1 = (8, 7168, 2048, 12, True, jnp.bfloat16)
    for rows in (512, 768, 1024):
        assert kernel.plan(rows, *nemotron) == 2688
        assert kernel.plan(rows, *axk1) in (256, 512)
        assert kernel.supported(rows, *nemotron)
        assert kernel.supported(rows, *axk1)
    assert kernel.plan(4096, *nemotron) and not kernel.plan(8192, *nemotron)
    assert kernel.plan(1536, *axk1) == 128
    assert kernel.plan(2048, *axk1) is None
    assert kernel.plan(1024, *nemotron[:-1], jnp.float32) is None
    assert kernel.plan(1024, 22, 1024 + 128, 2688, 128, False,
                       jnp.bfloat16) is None
    assert kernel.plan(1020, *nemotron) is None
    # the op's rule: the kernel past the ridge where it fits, else jnp
    op, w = _bf16_experts("relu2")
    assert [op.expert_form(r) for r in (128, 240, 256)] == [
        "dense", "dense", "kernel"]
    assert op.expert_form(256, jnp.float32) == "grouped"
    # a program over more than one device keeps the jnp form (the kernel
    # has no shard_map composition); the rows the shapes say are those of
    # the form that runs, and the kernel's are not the shapes' to say
    devices = np.array(jax.devices())
    assert op.expert_form(256, mesh=jax.sharding.Mesh(
        devices[:1], ("x",))) == "kernel"
    two = jax.sharding.Mesh(devices[:2], ("x",))
    assert op.expert_form(256, mesh=two) == "grouped"
    assert op.rows_computed(256) is None
    assert op.rows_computed(256, mesh=two) == (6 + 1) * 64
    assert op.rows_computed(256, jnp.float32) == (6 + 1) * 64
    assert op.rows_computed(240) == 6 * 240
    assert op.flops() == op.flops()       # a number where the kernel runs
    narrow, wn = _bf16_experts("relu2", e=128)
    assert narrow.expert_form(256) == "grouped"
    v = jax.random.normal(jax.random.key(6), (256, 128)).astype(jnp.bfloat16)
    ids = _routing("uniform", 256)
    _, gates, _ = narrow.route(wn, v, ids)
    counted = []
    got = narrow.apply(wn, v, ids, gates, counted)
    assert np.array_equal(np.asarray(got, np.float32), np.asarray(
        narrow._apply_grouped(wn, v, ids, gates), np.float32))
    assert [int(c) for c in counted] == [narrow.rows_computed(256)]
    counted = []
    v = jax.random.normal(jax.random.key(6), (256, 256)).astype(jnp.bfloat16)
    _, gates, _ = op.route(w, v, ids)
    op.apply(w, v, ids, gates, counted)
    assert [int(c) for c in counted] == [6 * 128]
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "off")
    assert not kernel.supported(1024, *nemotron)
    assert op.expert_form(256) == "grouped"
    assert op.rows_computed(256) == (6 + 1) * 64


@pytest.mark.parametrize("activation", ["relu2", "silu_gated"])
def test_gradients_through_the_kernel_are_the_jnp_forms(monkeypatch,
                                                        activation):
    """``fit`` and the compiler's graph walk reach the kernel through
    ``op.forward``: 256 bfloat16 rows differentiate (a bare Pallas call
    with scalar prefetch has no JVP), and the gradients with respect to
    the rows and to every weight, the router's among them, are the jnp
    grouped form's; over a mesh of two devices the jnp form runs."""
    from flexflow_tpu.core.op import LowerCtx

    op, w = _bf16_experts(activation)
    x = jax.random.normal(jax.random.key(8), (1, 256, 256)
                          ).astype(jnp.bfloat16)
    ctx = LowerCtx(mesh=None, training=True, aux_losses=[],
                   compute_dtype=None)

    def loss(w, x):
        (y,) = op.forward(ctx, [x], w)
        return jnp.sum(jnp.square(y.astype(jnp.float32)))

    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "off")
    assert op.expert_form(256) == "grouped"
    want_loss, want = jax.value_and_grad(loss, (0, 1))(w, x)
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    assert op.expert_form(256) == "kernel"
    text = jax.make_jaxpr(jax.grad(loss, (0, 1)))(w, x).pretty_print()
    assert "grouped_experts" in text
    got_loss, got = jax.jit(jax.value_and_grad(loss, (0, 1)))(w, x)
    assert abs(float(got_loss) - float(want_loss)) <= 0.01 * float(want_loss)
    assert set(got[0]) == set(w) and float(want_loss) > 0
    for g, h in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g, h = (np.asarray(a, np.float32) for a in (g, h))
        assert g.shape == h.shape and np.abs(h).max() > 0
        # the forward values differ by bfloat16's rounding of the terms,
        # and the loss's cotangent 2 y with them
        assert np.abs(g - h).max() <= 0.02 * np.abs(h).max()
    two = LowerCtx(mesh=jax.sharding.Mesh(np.array(jax.devices()[:2]),
                                          ("x",)),
                   training=True, aux_losses=[], compute_dtype=None)
    assert "grouped_experts" not in jax.make_jaxpr(
        lambda w, x: op.forward(two, [x], w))(w, x).pretty_print()


def _published_shares():
    """The three routed configurations' expert layers at their published
    shapes, bfloat16 rows: {name: op}."""
    from flexflow_tpu.core.layer import Layer
    from flexflow_tpu.core.parallel_tensor import ParallelTensorShape
    from flexflow_tpu.ffconst import OpType
    from flexflow_tpu.ops.moe_ops import RoutedExperts

    def make(e, **attrs):
        return RoutedExperts(
            Layer(OpType.ROUTED_EXPERTS, "x", attrs=attrs),
            [ParallelTensorShape.unpartitioned((1, 8, e),
                                               DataType.BFLOAT16)])

    return {
        "nemotron": make(4096, n_routed=512, experts_per_token=22,
                         width=2688, experts_held=(0, 128), latent=1024,
                         activation="relu2"),
        "axk1": make(7168, n_routed=192, experts_per_token=8, width=2048,
                     experts_held=(0, 12), n_group=8, topk_group=4),
        "trinity": make(3072, n_routed=256, experts_per_token=4, width=3072,
                        experts_held=(0, 32))}


def test_the_form_is_a_rule_over_the_shapes_traced():
    """``expert_form``: a decode step's rows keep the dense form, a
    prefill's pass the crossover, at the new configuration's shapes and
    at A.X-K1's; no knob."""
    shares = _published_shares()
    nemotron, axk1 = shares["nemotron"], shares["axk1"]
    for op in (nemotron, axk1):
        assert op.expert_form(128) == "dense"
        assert [op.expert_form(b) for b in (768, 1024)] == ["grouped"] * 2
        assert op.expert_form(240) == "dense"
    # a tile an expert, a quarter of the rows: six times the mean's pairs
    assert nemotron.capacity(1024) == 256 and nemotron.capacity(600) == 160
    assert nemotron.rows_computed(128) == 128 * 128
    assert (nemotron.spill_tiles, axk1.spill_tiles) == (8, 1)
    assert nemotron.rows_computed(1024) == (128 + 8) * 256
    assert axk1.rows_computed(1024) == (12 + 1) * 256


@pytest.mark.parametrize("name, slots, share", [
    ("trinity", 32, 0.396), ("nemotron", 128, 0.996), ("axk1", 128, 0.996)])
def test_under_the_ridge_the_form_follows_the_share_of_experts_named(
        monkeypatch, name, slots, share):
    """Under the ridge the question is how many of the held experts'
    matrices a call must read: ``named_share`` from ``rows``, ``k`` and
    ``n_routed``, all static in a trace. At the three configurations'
    published shapes and their cells' slots: Trinity's step names two in
    five and takes the kernel where the kernel is supported (one device,
    bfloat16 rows, Pallas on), the dense form on the CPU, under a mesh
    and for float32 rows; Nemotron's and A.X-K1's steps name all but one
    in 250 and keep the dense form at 128 and at 240 rows, unless the
    call says which of its rows are live (``active``: a decode step):
    then, where the kernel is supported, the call is ``counted`` (it
    counts the held experts its live rows name and takes the kernel up
    to 0.9 of them: 115 of 128, 10 of 12), and a call the shapes give to
    the kernel stays the kernel's; no form under
    the ridge is ever the jnp grouped one; and the rows computed are a
    number from the shapes, or counted on the device."""
    from flexflow_tpu.ops.moe_ops import NAMED_SHARE_KERNEL, RIDGE_ROWS

    op = _published_shares()[name]
    assert abs(op.named_share(slots) - share) < 0.0005
    assert 0.4 < NAMED_SHARE_KERNEL < 0.99
    two = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("x",))
    one = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("x",))
    for mode in ("off", "interpret"):
        monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", mode)
        want = "kernel" if (name, mode) == ("trinity", "interpret") \
            else "dense"
        assert op.expert_form(slots) == op.expert_form(slots, mesh=one) \
            == want
        assert op.expert_form(slots, mesh=two) == "dense"
        assert op.expert_form(slots, jnp.float32) == "dense"
        assert op.expert_form(240) == "dense"
        live = {"dense": "counted"}.get(want, want) if mode == "interpret" \
            else "dense"
        assert op.expert_form(slots, active=True) == op.expert_form(
            slots, mesh=one, active=True) == live
        assert op.expert_form(slots, mesh=two, active=True) == "dense"
        assert op.expert_form(slots, jnp.float32, active=True) == "dense"
        assert (op.rows_computed(slots, active=True) is None) == (
            live != "dense")
        assert op.kernel_limit() == int(NAMED_SHARE_KERNEL * op.count) \
            == {"trinity": 28, "nemotron": 115, "axk1": 10}[name]
        # (a call that is no whole sublane tiles: the kernel refuses it)
        assert op.expert_form(12) == "dense"
        for rows in (1, 8, 16, 32, 64, 128, 200, RIDGE_ROWS):
            for kw in ({}, {"mesh": two}, {"dtype": jnp.float32}):
                form = op.expert_form(rows, **kw)
                assert form in ("dense", "kernel"), (rows, kw)
                computed = op.rows_computed(rows, **kw)
                assert (computed is None) == (form == "kernel")
                assert form == "kernel" or computed == op.count * rows
        # the head's one row names 2-4 % of the held: the kernel's
        assert op.expert_form(1) == (
            "kernel" if mode == "interpret" else "dense")
        assert op.flops() > 0


def test_the_choice_is_by_biased_scores_and_the_weights_by_plain(toy):
    """With a bias that lifts the four lowest-scored experts over the
    rest, the choice (``s + b``) is those four, not the four a choice by
    ``s`` takes, and the weights are ``s``'s (normalised, times the
    scale); the reference chooses alike."""
    ff, weights = toy
    op = _op(ff, "block0_mixer")
    w = dict(ff.compiled.params["block0_mixer"])
    u = jax.random.normal(jax.random.key(2), (6, 32))
    s = np.asarray(jax.nn.sigmoid(u @ w["router"]))
    plain = np.sort(np.argsort(-s, -1)[:, :4], -1)
    ids0, _, _ = op.route(dict(w, bias=jnp.zeros(16)), u)
    assert np.array_equal(np.sort(np.asarray(ids0), -1), plain)
    lowest = np.argsort(s.mean(0))[:4]
    bias = np.zeros(16, np.float32)
    bias[lowest] = 2.0
    ids, gates, _ = op.route(dict(w, bias=jnp.asarray(bias)), u)
    assert np.array_equal(np.sort(np.asarray(ids), -1),
                          np.tile(np.sort(lowest), (6, 1)))
    assert not np.array_equal(np.sort(np.asarray(ids), -1), plain)
    picked = np.take_along_axis(s, np.asarray(ids), -1)
    assert np.allclose(gates, 2.5 * picked / picked.sum(-1, keepdims=True),
                       rtol=1e-5)
    lw = dict(_layer(weights, 0), bias=jnp.asarray(bias),
              norm=jnp.ones(32))
    # the reference norms its input: hand it what norms to ``u``
    _, own, choice = _pieces(TOY)["scores_of"](u[None] * 1.0, lw)
    un = reference._rms(u, jnp.ones(32), 1e-5)
    ids_n, _, _ = op.route(dict(w, bias=jnp.asarray(bias)), un)
    assert np.array_equal(np.sort(np.asarray(own), -1),
                          np.sort(np.asarray(ids_n), -1))


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The share tied to the model: the routed parts of the four holders
    (experts 0-3, 4-7, 8-11, 12-15; each after ``W_up``) and the shared
    expert counted once are the uncut layer, in the reference and in the
    program alike."""
    weights = reference.init_weights(WHOLE, SEED)
    lw = _layer(weights, 0)
    # scaled up, so that the layer's output stands beside its residual
    # (N(0, 0.02) at these widths leaves it under float32's cancellation)
    lw.update({k: lw[k].astype(jnp.float32) * 8 for k in (
        "latent_down", "latent_up", "experts.up", "experts.down",
        "shared.up", "shared.down")})
    x = jax.random.normal(jax.random.key(4), (1, 11, 32))
    f = _pieces(WHOLE)
    s, ids, _ = f["scores_of"](x, lw)
    whole = np.asarray(f["expert_ffn"](x, lw, s, ids) - x)
    un = reference._rms(x, lw["norm"], 1e-5)[0]
    shared = np.asarray(reference._relu2_mlp(
        un, lw["shared.up"], lw["shared.down"], "float32"))
    parts, program_parts = [], []
    for first in (0, 4, 8, 12):
        cfg = dict(TOY, expert_first=first)
        share = dict(lw, **{k: lw[k][first:first + 4]
                            for k in ("experts.up", "experts.down")})
        parts.append(np.asarray(_pieces(cfg)["expert_ffn"](x, share, s, ids)
                                - x)[0] - shared)
        ff, _ = _program(cfg)
        op = _op(ff, "block0_mixer")
        w = {k: share[v].astype(jnp.float32)
             for k, v in family._EXPERTS.items()}
        ids_p, gates_p, _ = op.route(w, un)
        assert np.array_equal(np.sort(np.asarray(ids_p), -1),
                              np.sort(np.asarray(ids), -1))
        program_parts.append(np.asarray(op.apply(w, un, ids_p, gates_p)))
    assert min(np.abs(p).max() for p in program_parts) > 0.01
    tol = 2e-5 * np.abs(whole).max()
    assert np.abs(sum(parts) + shared - whole[0]).max() <= tol
    assert np.abs(sum(program_parts) + shared - whole[0]).max() <= tol


# ---- grouped heads -----------------------------------------------------------

def test_grouped_heads_through_the_pair_entry(toy):
    """The ``*`` layer (4 query heads on 2 key-value heads): the entry
    keeps 2 heads' keys and values a token, and prefill then steps
    through the block tables give the reference's attention over the
    whole sequence."""
    ff, weights = toy
    op = _op(ff, "block4_mixer")
    kind = cache_entry.kind_for(op, None, MAX_LEN)
    assert isinstance(kind, cache_entry.PairEntry)
    assert (kind.heads, kind.head_dim, kind.query_heads) == (2, 8, 4)
    assert [a.shape for a in kind.arenas(5, 8, jnp.float32)] \
        == [(5, 8, 16)] * 2
    assert kind.int8_form.query_heads == 4
    w = ff.compiled.params["block4_mixer"]
    lw = dict(_layer(weights, 4), norm=jnp.ones(32))
    x = jax.random.normal(jax.random.key(7), (1, 13, 32))
    u = reference._rms(x, lw["norm"], 1e-5)
    want = np.asarray(_pieces(TOY)["attention"](x, lw) - x)[0]
    out, _, _ = kind.whole(op, w, u, None)
    assert np.abs(np.asarray(out)[0] - want).max() <= 2e-5 * np.abs(
        want).max()
    from flexflow_tpu.serving.kv_cache import Addresses

    entry = tuple(jnp.zeros(a.shape, a.dtype)
                  for a in kind.arenas(5, 8, jnp.float32))
    tables = jnp.asarray([[3, 1]], jnp.int32)
    addr = Addresses(tables, None)
    out, entry = kind.prefill(op, w, u[:, :9], None, entry, addr,
                              jnp.asarray([9], jnp.int32))
    assert np.abs(np.asarray(out)[0] - want[:9]).max() <= 2e-5 * np.abs(
        want).max()
    for t in range(9, 13):
        out, entry = kind.step(op, w, u[:, t:t + 1], None, entry, addr,
                               jnp.asarray([t], jnp.int32))
        assert np.abs(np.asarray(out)[0, 0] - want[t]).max() \
            <= 2e-5 * np.abs(want).max()


@pytest.mark.parametrize("window", [1, 2])
@pytest.mark.parametrize("h,hkv,d", [(4, 2, 128), (8, 2, 64), (32, 8, 64)],
                         ids=["lane-tile", "two-a-tile", "granite"])
def test_paged_kernel_with_grouped_heads_equals_the_gather(monkeypatch,
                                                           window, h, hkv, d):
    """The paged decode kernel through the Pallas interpreter with grouped
    heads: 4 query heads on 2 key-value heads of 128 (a head a whole lane
    tile: the arena's rows are 256 lanes), and heads of 64, two key-value
    heads a lane tile (8 on 2; Granite's 32 on 8, rows of 512 lanes, with
    a softmax scale of its own): the group's query heads are rows of one
    score matrix, each keeping its query in its key-value head's lanes;
    against the jnp gather over the same arenas, for slots of different
    lengths and an idle slot."""
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    rng = np.random.default_rng(window)
    n, bs, mb, nb = 3, 16, 4, 9
    scale = 0.015625 if h == 32 else d ** -0.5
    q = jnp.asarray(rng.normal(size=(n, window, h, d)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(nb, bs, hkv * d)), jnp.float32)
            for _ in range(2))
    tables = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0], [0, 0, 0, 0]],
                         jnp.int32)
    lens = jnp.asarray([37, 16, 0], jnp.int32)
    assert paged_attention.supported(q.shape, k.shape, k.dtype, mb)
    # a key head a query head at the same widths stays supported; grouped
    # heads narrower than half a lane tile, or rows that do not fill
    # whole lane tiles, are not
    assert paged_attention.supported((n, 1, hkv, d), k.shape, k.dtype, mb)
    assert not paged_attention.supported((n, 1, 8, 32), (nb, bs, 4 * 32),
                                         k.dtype, mb)
    assert not paged_attention.supported((n, 1, 6, 64), (nb, bs, 3 * 64),
                                         k.dtype, mb)
    got = paged_attention.paged_attention_decode(q, k, v, tables, lens,
                                                 scale=scale)
    kind = cache_entry.PairEntry(hkv, d, h)
    kk, vv = kind.read((k, v), tables)
    pos = lens[:, None] + jnp.arange(window)[None, :]
    mask = jnp.arange(kk.shape[1])[None, None, :] <= pos[:, :, None]
    want = cache_entry._attend(q, kk, vv, lambda: mask[:, None], scale)
    assert np.abs(np.asarray(got - want))[:2].max() <= 1e-5


def test_the_state_kind_refuses_rollback_and_int8_by_name(toy):
    """A state cannot be rolled back and has no int8 form: speculative
    windows (so self-drafting) and an int8 pool refuse at construction,
    naming the ops; and the decode step's two slots-in-flight (a waiting
    request's row, the row nobody holds) stay as they were."""
    from flexflow_tpu.serving import GenerationInstance

    ff, _ = toy
    with pytest.raises(
            ValueError,
            match=r"speculative verify over a ssm_state cache entry is not "
                  r"built \(block1_mixer and 1 more\): serve this model "
                  r"with spec_k=0"):
        GenerationInstance(ff, decode_slots=2, block_size=8,
                           max_length=MAX_LEN, spec_k=2, draft_ff=ff)
    with pytest.raises(ValueError, match=r"block1_mixer: a ssm_state cache "
                                         r"entry has no int8 form"):
        PagedDecoder(ff, MAX_LEN, decode_slots=2, block_size=8,
                     kv_dtype="int8")
    kind = cache_entry.SsmStateEntry(4, 8, 8, 3, 64)
    assert kind.max_window == 1 and kind.int8_form is None
    # (it takes chunks since ``SsmStateEntry.chunk``: a prompt in chunks
    # of 16 leaves the logits a bucket of 32 leaves)
    assert kind.keeps_row and kind.chunked
    prompt = np.random.default_rng(5).integers(
        0, TOY["vocab_size"], 27).astype(np.int32)
    outs = []
    for kw in (dict(prefill_buckets=[32]), dict(prefill_chunk=16)):
        dec = PagedDecoder(ff, MAX_LEN, decode_slots=2, block_size=8,
                           calibrate=False, **kw)
        table = dec.pool.try_admit(28)
        outs.append(dec.prefill(prompt, table))
    assert np.abs(outs[0] - outs[1]).max() <= 2e-4 * np.abs(outs[0]).max()
