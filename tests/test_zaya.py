"""The ZAYA1 line at toy widths on the CPU: compressed convolutional
attention and top-1 routed experts under an MLP router, the program
against the plain reference (``benchmark/reference/zaya.py``, which
imports nothing of the program): the whole forward, and each learned
factor's share of it; the router's state down four layers; the holders'
shares of an expert layer; ``fit``; the builder's stages; the partial
rotation. The paged programs are in tests/test_cca_entry.py, the programs
compiled for the chip at the published widths in
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

from benchmark.families import zaya as family  # noqa: E402
from benchmark.reference import zaya as reference  # noqa: E402
from flexflow_tpu import AdamOptimizer, FFConfig, FFModel  # noqa: E402
from flexflow_tpu.core.op import LowerCtx, weights_of  # noqa: E402
from flexflow_tpu.ffconst import (CompMode, DataType, LossType,  # noqa: E402
                                  MetricsType, OpType)
from flexflow_tpu.models import (ZayaConfig, build_zaya_lm,  # noqa: E402
                                 zoo_smoke_builders)
from flexflow_tpu.serving.generation import PagedDecoder  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "tests", "data", "configs",
                       "zaya-toy.json")) as _f:
    TOY = json.load(_f)
FOUR = dict(TOY, num_hidden_layers=4, layer_types=["hybrid"] * 4)
SEED = 2 ** 31 + 50
MAX_LEN = 80


def program(config, seed=SEED, slots=3, mode=CompMode.INFERENCE, **compile_kw):
    """The program's graph for ``config`` in float32 holding the
    reference's seeded weights; returns (ff, weights)."""
    cfg = dataclasses.replace(family.program_config(config),
                              param_dtype=DataType.FLOAT, draw_weights=True)
    ff = FFModel(FFConfig(batch_size=slots, seed=0, ledger="off",
                          search_cache="off", computation_mode=mode))
    build_zaya_lm(ff, slots, MAX_LEN, cfg)
    ff.compile(**(compile_kw or dict(optimizer=None, loss_type=None,
                                     metrics=[])))
    weights = reference.init_weights(config, seed)
    ff.compiled.params = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), family.to_program(weights, config))
    ff.compiled.bump_params_version()
    return ff, weights


@pytest.fixture(scope="module")
def toy():
    return program(TOY)


def _tokens(n, seed=1):
    return np.random.default_rng(seed).integers(
        0, TOY["vocab_size"], n).astype(np.int32)


def _whole(ff, toks):
    return PagedDecoder(ff, MAX_LEN, decode_slots=3, block_size=8,
                        calibrate=False)._dense_reference_logits(toks)


def _reference(weights, toks, config=TOY):
    return np.asarray(reference.forward_jit(
        weights, jnp.asarray(toks[None]), config, "float32"))[0]


# ---- the whole forward ----------------------------------------------------------

def test_whole_forward_equals_the_references(toy):
    """The whole model cache-free (``CcaEntry.whole``, the expert op's
    ``route`` and ``apply``) against the reference's forward over 50
    tokens: both convolutions, the q-k mean, the shifted value, the
    lengths and temperatures, the partial rotation, the router's chain
    and its state, the residual scalings, the tied head. 2e-4 of the
    logits' range: float32 summation order."""
    ff, weights = toy
    toks = _tokens(50)
    got, want = _whole(ff, toks), _reference(weights, toks)
    assert want.shape == (50, TOY["vocab_size"])
    assert np.abs(got - want).max() <= 2e-4 * np.abs(want).max()


@pytest.mark.parametrize("leaf,value", [
    ("temp", 0.0),          # exp(tau) left out
    ("res1.c", 1.0),        # a branch's scale dropped
    ("res2.a", 1.0),        # the stream's
    ("res2.d", 0.0),        # a shift
    ("conv0_b", 0.0),       # the depthwise convolution's bias
    ("conv1_b", 0.0),       # the grouped one's
    ("norm_moe", 1.0),      # a gain
    ("conv0", 1.0),         # the depthwise taps
])
def test_a_factor_left_out_shows(toy, leaf, value):
    """Every learned factor is drawn away from 1 and 0: the reference
    without one of them is further from the program than the comparison
    allows, by an order."""
    ff, weights = toy
    toks = _tokens(50)
    got = _whole(ff, toks)
    off = {k: jnp.full_like(v, value) if k.split(".", 1)[1] == leaf else v
           for k, v in weights.items() if k.startswith("l")}
    off = dict(weights, **off)
    want = _reference(off, toks)
    assert np.abs(got - want).max() > 2e-3 * np.abs(want).max()


def test_rotary_over_part_of_a_head():
    """``apply_rotary`` with a ``rotary_dim``: the first values rotated
    in their own halves, the rest as they were; without it, the whole
    head as before."""
    from flexflow_tpu.ops.attention import apply_rotary, rotary_inv_freq

    x = jax.random.normal(jax.random.key(0), (2, 5, 3, 16))
    pos = jnp.arange(5)[None] + jnp.array([[0], [7]])
    inv = rotary_inv_freq(8, 5e6)
    got = np.asarray(apply_rotary(x, pos, inv, 8))
    ang = np.asarray(pos, np.float64)[..., None, None] * np.asarray(inv)
    a, b = np.asarray(x)[..., :4], np.asarray(x)[..., 4:8]
    want = np.concatenate([a * np.cos(ang) - b * np.sin(ang),
                           b * np.cos(ang) + a * np.sin(ang),
                           np.asarray(x)[..., 8:]], -1)
    assert np.abs(got - want).max() < 1e-5
    whole = rotary_inv_freq(16, 5e6)
    assert np.array_equal(np.asarray(apply_rotary(x, pos, whole, 16)),
                          np.asarray(apply_rotary(x, pos, whole)))
    assert np.abs(got - np.asarray(apply_rotary(x, pos, whole))).max() > 0.1


# ---- the router's state down the layers ---------------------------------------------

def _walk(cm, toks):
    """The graph op by op over whole sequences; the activations by
    tensor id."""
    ctx = LowerCtx(mesh=None, training=False, aux_losses=[],
                   compute_dtype=None)
    s = len(toks)
    acts = {cm.input_tensors[0].tensor_id: jnp.asarray(toks[None]),
            cm.input_tensors[1].tensor_id: jnp.arange(s, dtype=jnp.int32)[
                None]}
    for op in cm.ops:
        outs = op.forward(ctx, [acts[t.tensor_id] for t in op.layer.inputs],
                          weights_of(op, cm.params))
        acts.update({t.tensor_id: o for t, o in zip(op.layer.outputs, outs)})
    return acts


def test_the_router_state_runs_down_four_layers():
    """Each expert op's second output, the router state it hands to the
    next, is the reference's ``r`` of that layer; the model's first layer
    takes none and holds no depth scale; and the experts chosen are the
    reference's."""
    ff, weights = program(FOUR)
    cm = ff.compiled
    toks = _tokens(30, seed=4)
    acts = _walk(cm, toks)
    _, info = reference.forward_with_routing(weights, jnp.asarray(
        toks[None]), FOUR, "float32")
    ops = [op for op in cm.ops if op.op_type is OpType.ROUTED_EXPERTS]
    assert [len(op.layer.inputs) for op in ops] == [1, 2, 2, 2]
    assert "depth_scale" not in cm.params[ops[0].name]
    assert all("depth_scale" in cm.params[op.name] for op in ops[1:])
    for op, layer, nxt in zip(ops, info, ops[1:] + [None]):
        state = acts[op.layer.outputs[1].tensor_id]
        assert state.shape == (1, 30, TOY["router_hidden_size"])
        assert state.dtype == jnp.float32
        want = np.asarray(layer["state"])
        assert np.abs(np.asarray(state[0]) - want).max() \
            <= 1e-4 * np.abs(want).max()
        if nxt is not None:    # and it is what the next layer reads
            assert nxt.layer.inputs[1].tensor_id \
                == op.layer.outputs[1].tensor_id
        x2d = acts[op.layer.inputs[0].tensor_id][0]
        prev = [acts[t.tensor_id][0] for t in op.layer.inputs[1:]]
        ids, gates, _ = op.route(weights_of(op, cm.params), x2d, None, *prev)
        assert np.array_equal(np.asarray(ids), np.asarray(layer["own_ids"]))
        p = np.take_along_axis(np.asarray(layer["gate_scores"]),
                               np.asarray(ids), -1)
        assert np.abs(np.asarray(gates) - p).max() < 1e-5   # unnormalised


# ---- the share --------------------------------------------------------------------------

def _expert_layer(held):
    ff = FFModel(FFConfig(batch_size=2, seed=3, ledger="off",
                          search_cache="off",
                          computation_mode=CompMode.INFERENCE))
    x = ff.create_tensor((2, 6, 32), DataType.FLOAT, name="x")
    r = ff.create_tensor((2, 6, 8), DataType.FLOAT, name="r")
    ff.routed_experts(x, n_routed=16, experts_per_token=1, width=16,
                      scoring="softmax", norm_topk=False,
                      selection_bias=True, router="mlp", router_width=8,
                      router_state=r, experts_held=held, name="experts")
    ff.compile(optimizer=None, loss_type=None, metrics=[])
    return ff.compiled


def test_the_holders_shares_add_up_to_the_uncut_layer():
    """With ``experts_held`` (0, 8) and (8, 8) each holder routes over
    all 16 experts by the same MLP router and computes its own; the two
    parts add up to the uncut layer's output, and both hand on the same
    state."""
    whole = _expert_layer(None)
    op = whole.ops[0]
    w = {k: v + 0.3 * jax.random.normal(jax.random.key(i), v.shape)
         for i, (k, v) in enumerate(sorted(whole.params["experts"].items()))}
    x = jax.random.normal(jax.random.key(7), (2, 6, 32))
    r = jax.random.normal(jax.random.key(8), (2, 6, 8))
    ctx = LowerCtx(mesh=None, training=False, aux_losses=[],
                   compute_dtype=None)
    want, state = op.forward(ctx, [x, r], w)
    ids = np.asarray(op.route(w, x.reshape(-1, 32), None, r)[0])
    assert (ids < 8).any() and (ids >= 8).any()
    parts = []
    for first in (0, 8):
        half = _expert_layer((first, 8)).ops[0]
        mine = {k: v[first:first + 8] if k in ("w_gate", "w_up", "w_down")
                else v for k, v in w.items()}
        y, s = half.forward(ctx, [x, r], mine)
        assert np.array_equal(np.asarray(s), np.asarray(state))
        parts.append(np.asarray(y))
    assert np.abs(parts[0]).max() > 0 and np.abs(parts[1]).max() > 0
    assert np.abs(parts[0] + parts[1] - np.asarray(want)).max() \
        <= 1e-5 * np.abs(np.asarray(want)).max()


def test_only_an_mlp_router_takes_a_state():
    ff = FFModel(FFConfig(batch_size=2, ledger="off",
                          computation_mode=CompMode.INFERENCE))
    x = ff.create_tensor((2, 4, 32), DataType.FLOAT)
    with pytest.raises(ValueError, match="neither 'linear' nor 'mlp'"):
        ff.routed_experts(x, n_routed=8, experts_per_token=2, width=16,
                          router="conv", router_width=8)


# ---- fit ----------------------------------------------------------------------------------

def _train_program():
    return program(TOY, slots=2, mode=CompMode.TRAINING,
                   optimizer=AdamOptimizer(alpha=1e-2),
                   loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                   metrics=[MetricsType.SPARSE_CATEGORICAL_CROSSENTROPY])


def _batch():
    rng = np.random.default_rng(5)
    x = rng.integers(0, TOY["vocab_size"], (2, MAX_LEN)).astype(np.int32)
    pos = np.broadcast_to(np.arange(MAX_LEN, dtype=np.int32), (2, MAX_LEN))
    return x, np.ascontiguousarray(pos), np.roll(x, -1, axis=1)


def test_fit_runs_three_steps_with_a_falling_loss():
    ff, _ = _train_program()
    x, pos, y = _batch()
    hist = ff.fit([x, pos], y, epochs=3, batch_size=2, shuffle=False,
                  verbose=False)
    losses = [h.sparse_cce_loss / max(h.train_all, 1) for h in hist]
    assert len(losses) == 3 and all(np.isfinite(losses)), losses
    assert losses[2] < losses[1] < losses[0], losses


def test_the_graphs_gradients_are_the_references():
    """``jax.grad`` through the graph's training forward against
    ``jax.grad`` through the reference's forward, the same float32
    weights and the same loss (the mean log-likelihood of the next
    token): every leaf to 1e-4 of the leaf's largest gradient. The
    routing is a choice and carries no gradient in either; the chosen
    expert's probability does."""
    from flexflow_tpu.runtime.compiler import _forward_graph

    ff, weights = _train_program()
    cm = ff.compiled
    x, pos, y = _batch()
    x, pos, y = x[:, :24], pos[:, :24], y[:, :24]

    def nll(logits):
        lp = jax.nn.log_softmax(logits.astype(jnp.float32))
        return -jnp.mean(jnp.take_along_axis(lp, jnp.asarray(y)[..., None],
                                             -1))

    def program_loss(params):
        acts, _, _ = _forward_graph(
            cm.ops, None, params,
            {cm.input_tensors[0].tensor_id: jnp.asarray(x),
             cm.input_tensors[1].tensor_id: jnp.asarray(pos)}, True, None)
        return nll(acts[cm.logits_tensor.tensor_id])

    w32 = {k: v.astype(jnp.float32) for k, v in weights.items()}

    def reference_loss(w):
        return nll(reference.forward_jit(w, jnp.asarray(x), TOY, "float32"))

    got = jax.grad(program_loss)(cm.params)
    want = family.to_program(jax.grad(reference_loss)(w32), TOY)
    assert abs(float(program_loss(cm.params)) - float(reference_loss(w32))) \
        < 1e-5
    for name, leaves in want.items():
        for leaf, g in leaves.items():
            scale = float(jnp.abs(g).max())
            # (the balancing bias stands in the choice only)
            assert (scale > 0) == (leaf != "bias"), (name, leaf)
            assert float(jnp.abs(got[name][leaf] - g).max()) \
                <= 1e-4 * scale, (name, leaf)


# ---- the builder ------------------------------------------------------------------------

def test_a_later_stage_takes_the_router_state_as_an_input():
    """``first_layer`` past 0: the graph's first expert layer is not the
    model's first, so it holds a depth scale and reads the state the
    stage before hands over, a third input of the graph."""
    ff = FFModel(FFConfig(batch_size=2, ledger="off", search_cache="off",
                          computation_mode=CompMode.INFERENCE))
    build_zaya_lm(ff, 2, 16, ZayaConfig(
        vocab_size=64, hidden_size=32, num_layers=2, first_layer=20,
        num_heads=4, num_kv_heads=2, head_dim=16, n_routed=4,
        expert_width=16, router_width=8))
    ff.compile(optimizer=None, loss_type=None, metrics=[])
    cm = ff.compiled
    assert [t.name for t in cm.input_tensors] == ["tokens", "positions",
                                                  "router_state"]
    first = next(op for op in cm.ops if op.op_type is OpType.ROUTED_EXPERTS)
    assert first.layer.inputs[1].tensor_id == cm.input_tensors[2].tensor_id
    assert cm.params[first.name]["depth_scale"].shape == (8,)
    assert "lm_head" not in cm.params       # the head IS the table


def test_the_zoo_preset_builds_and_serves():
    from flexflow_tpu.serving import GenerationInstance

    ff = FFModel(FFConfig(batch_size=2, seed=0, ledger="off",
                          search_cache="off",
                          computation_mode=CompMode.INFERENCE))
    zoo_smoke_builders()["zaya"](ff, 2)
    ff.compile(optimizer=None, loss_type=None, metrics=[])
    inst = GenerationInstance(ff, decode_slots=2, block_size=4,
                              max_length=16, prefill_chunk=8)
    try:
        out = inst.generate(np.arange(11, dtype=np.int32) % 128,
                            max_new_tokens=4, temperature=0.0)
        st = inst.stats()
    finally:
        inst.stop()
    assert out.shape == (15,)
    assert st["kv"]["entry"] == "cca" and st["kv"]["state"]["rows"] == 3
    assert set(st["moe"]) == {"block0_experts", "block1_experts"}
