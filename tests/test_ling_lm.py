"""Ling-3.0-flash's mechanisms at toy widths on the CPU: the delta rule
with a decay a key CHANNEL (whole-sequence form, one-token form and the
widened kernel, interpreted, against the token loop; at the gate's lower
bound and near none), latent attention with a direct query projection and
a head-wise gate, and the whole toy model (two periods of two KDA layers
to one latent layer behind a dense layer, 16 experts in 4 groups, 2 groups
kept) through the builder, ``compile()`` and the paged programs against the
plain reference (``benchmark/reference/ling.py``, which imports nothing of
the program): the full forward, a prompt into a padded bucket then 8
decode steps, the two entry kinds in one pool, the holders' shares against
the uncut layer, and what is not built refusing by name. The programs at
the published widths for a described v5e are in tests/test_tpu_lowering.py."""

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

from benchmark.families import ling as family  # noqa: E402
from benchmark.reference import ling as reference  # noqa: E402
from flexflow_tpu import FFConfig, FFModel  # noqa: E402
from flexflow_tpu.core.machine import make_mesh  # noqa: E402
from flexflow_tpu.ffconst import CompMode, DataType, OpType  # noqa: E402
from flexflow_tpu.kernels import gated_delta as gd  # noqa: E402
from flexflow_tpu.models import build_latent_moe_lm  # noqa: E402
from flexflow_tpu.ops.gated_delta import (  # noqa: E402
    CHUNK, SUB, chunked_channel_rule, chunked_delta_rule)
from flexflow_tpu.serving import GenerationInstance  # noqa: E402
from flexflow_tpu.serving.cache_entry import LatentEntry, StateEntry  # noqa: E402
from flexflow_tpu.serving.generation import PagedDecoder  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "tests", "data", "configs",
                       "ling-toy.json")) as _f:
    TOY = json.load(_f)
SEED = 2 ** 31 + 58
MAX_LEN = 48
H, DK, DV = 3, 8, 16


# ---- the rule with a decay a key channel ------------------------------------------

def _qkv(rng, b, s, g_lo, g_hi, h=H, dk=DK, dv=DV):
    q = rng.normal(size=(b, s, h, dk)).astype(np.float32)
    k = rng.normal(size=(b, s, h, dk)).astype(np.float32)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(b, s, h, dv)).astype(np.float32)
    g = rng.uniform(g_lo, g_hi, size=(b, s, h, dk)).astype(np.float32)
    beta = rng.uniform(0, 1, size=(b, s, h)).astype(np.float32)
    return q, k, v, g, beta


def _by_hand(q, k, v, g, beta, state=None):
    """The recurrence as ``KimiDeltaAttention``'s docstring writes it, a
    token at a time, in float64."""
    b, s, h, dk = q.shape
    state = (np.zeros((b, h, dk, v.shape[-1])) if state is None
             else np.asarray(state, np.float64))
    out = np.zeros(v.shape)
    for t in range(s):
        state = np.exp(g[:, t].astype(np.float64))[..., None] * state
        r = v[:, t] - np.einsum("bhdv,bhd->bhv", state, k[:, t])
        state = state + (beta[:, t][..., None, None] * k[:, t][..., None]
                         * r[:, :, None, :])
        out[:, t] = np.einsum("bhdv,bhd->bhv", state, q[:, t])
    return out, state


@pytest.mark.parametrize("s", [1, CHUNK, 2 * CHUNK + 22])
@pytest.mark.parametrize("g_lo,g_hi", [(-5.0, -5.0), (-1e-3, 0.0),
                                       (-5.0, 0.0)],
                         ids=["at-the-bound", "near-none", "mixed"])
def test_channel_rule_is_the_token_loop(s, g_lo, g_hi):
    """Whole chunks, a sequence no chunk divides and one token; every
    token of every chunk at the gate's lower bound of -5 (where ``k
    exp(-cumsum g)`` would pass float32 after 17 tokens), every token near
    no decay at all, and both among each other."""
    q, k, v, g, beta = _qkv(np.random.default_rng(s), 2, s, g_lo, g_hi)
    want, want_state = _by_hand(q, k, v, g, beta)
    got, state = chunked_channel_rule(
        *map(jnp.asarray, (q, k, v, g, beta)), jnp.zeros((2, H, DK, DV)))
    assert np.isfinite(np.asarray(got)).all()
    assert np.abs(got - want).max() < 2e-5 * max(1.0, np.abs(want).max())
    assert np.abs(state - want_state).max() < 2e-5


def test_channel_rule_with_one_decay_a_head_is_the_scalar_rule():
    """``g`` the same for every channel of a head: the form of
    ``GatedDeltaNet``, which a (B, S, H) ``g`` still takes op for op."""
    rng = np.random.default_rng(2)
    q, k, v, g, beta = _qkv(rng, 1, 100, -0.2, -1e-3)
    g = np.broadcast_to(g[..., :1], g.shape)
    zero = jnp.zeros((1, H, DK, DV))
    got, state = chunked_channel_rule(*map(jnp.asarray, (q, k, v, g, beta)),
                                      zero)
    want, want_state = chunked_delta_rule(
        *map(jnp.asarray, (q, k, v, g[..., 0], beta)), zero)
    assert np.abs(got - want).max() < 2e-5 * np.abs(want).max()
    assert np.abs(state - want_state).max() < 2e-5
    assert SUB * 5 < 88 and CHUNK % SUB == 0


def test_channel_rule_carries_its_state_into_the_next_call():
    q, k, v, g, beta = map(jnp.asarray, _qkv(np.random.default_rng(6), 1, 100,
                                             -5.0, 0.0))
    zero = jnp.zeros((1, H, DK, DV))
    whole, end = chunked_channel_rule(q, k, v, g, beta, zero)
    cut = 37
    first, mid = chunked_channel_rule(q[:, :cut], k[:, :cut], v[:, :cut],
                                      g[:, :cut], beta[:, :cut], zero)
    second, end2 = chunked_channel_rule(q[:, cut:], k[:, cut:], v[:, cut:],
                                        g[:, cut:], beta[:, cut:], mid)
    both = jnp.concatenate([first, second], axis=1)
    assert np.abs(both - whole).max() < 2e-5 * np.abs(whole).max()
    assert np.abs(end - end2).max() < 2e-5


def test_one_token_form_with_a_channel_decay_is_the_token_loop():
    q, k, v, g, beta = _qkv(np.random.default_rng(5), 2, 40, -5.0, 0.0)
    want, want_state = _by_hand(q, k, v, g, beta)
    state = jnp.zeros((2, DK, H * DV))
    outs = []
    for t in range(40):
        o, state = gd.delta_rule_step(state, q[:, t], k[:, t], v[:, t],
                                      np.exp(g[:, t]), beta[:, t])
        outs.append(o)
    got = np.stack(outs, 1)
    assert np.abs(got - want).max() < 2e-5 * np.abs(want).max()
    lanes = np.moveaxis(want_state, 1, 2).reshape(2, DK, H * DV)
    assert np.abs(state - lanes).max() < 2e-5


@pytest.mark.parametrize("h,dk,dv", [(4, 8, 64), (2, 16, 128)])
def test_widened_kernel_interpreted_is_its_jnp_form(monkeypatch, h, dk, dv):
    """The decode kernel with a ``(d_k,)`` decay a head, heads that share
    a lane tile (64) and that fill one (128); two idle slots on the null
    row; and one decay a head through the same kernel as before."""
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    rng = np.random.default_rng(0)
    n, rows_n = 5, 7
    arena = jnp.asarray(rng.normal(size=(rows_n, dk, h * dv))
                        .astype(np.float32))
    rows = jnp.asarray([3, 0, 5, 0, 1], jnp.int32)
    q, k, v, g, beta = _qkv(rng, n, 1, -5.0, 0.0, h, dk, dv)
    live, slots = [1, 3, 5], [0, 2, 4]
    for alpha in (np.exp(g[:, 0]), np.exp(g[:, 0, :, 0])):
        args = (q[:, 0], k[:, 0], v[:, 0], alpha, beta[:, 0])
        o1, a1 = gd.gated_delta_decode(arena, rows, *args)
        o2, a2 = gd.gated_delta_step(arena, rows, *args)
        assert np.abs(np.asarray(o1)[slots]
                      - np.asarray(o2)[slots]).max() < 1e-5
        assert np.abs(np.asarray(a1)[live]
                      - np.asarray(a2)[live]).max() < 1e-5
        assert np.array_equal(np.asarray(a1)[[2, 4, 6]],
                              np.asarray(arena)[[2, 4, 6]])
    # the channel's own factor reaches its own row of the state
    o, a = gd.gated_delta_decode(
        arena, rows, q[:, 0], k[:, 0] * 0, v[:, 0], np.exp(g[:, 0]),
        beta[:, 0] * 0)
    want = np.asarray(arena)[3].reshape(dk, h, dv) \
        * np.exp(g[0, 0]).T[..., None]
    assert np.abs(np.asarray(a)[3].reshape(dk, h, dv) - want).max() < 1e-6


# ---- the toy model -------------------------------------------------------------------

def program(config, seed=SEED, slots=3, **compile_kw):
    """The program's graph for ``config`` in float32 holding the
    reference's seeded weights; returns (ff, weights)."""
    cfg = dataclasses.replace(family.program_config(config, MAX_LEN),
                              param_dtype=DataType.FLOAT, draw_weights=True)
    ff = FFModel(FFConfig(batch_size=slots, seed=0, ledger="off",
                          search_cache="off",
                          computation_mode=CompMode.INFERENCE))
    build_latent_moe_lm(ff, slots, MAX_LEN, cfg)
    ff.compile(optimizer=None, loss_type=None, metrics=[], **compile_kw)
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


def _reference(weights, toks, config=TOY, **kw):
    return np.asarray(reference.forward_jit(
        weights, jnp.asarray(toks[None]), config, "float32", **kw))[0]


def test_the_pattern_is_the_published_indexes(toy):
    """Published layers 1-6 of a period of three behind two dense layers:
    layer 1 KDA with the dense MLP, 2 and 5 latent, the others KDA, all
    from 2 on with experts; one graph, a positions input that four layers
    of six do not read."""
    ff, _ = toy
    assert family.layer_types(TOY) == ("kda", "latent", "kda", "kda",
                                       "latent", "kda")
    assert reference.layer_kinds(TOY) == [
        ("kda", True), ("latent", False), ("kda", False), ("kda", False),
        ("latent", False), ("kda", False)]
    types = {op.name: op.op_type for op in ff.compiled.ops}
    assert types["block0_attn"] is OpType.KIMI_DELTA_ATTENTION
    assert types["block1_attn"] is OpType.LATENT_ATTENTION
    assert types["block0_mlp"] is OpType.GATED_MLP
    assert "block0_experts" not in types and "block1_mlp" not in types
    assert types["block1_experts"] is OpType.ROUTED_EXPERTS
    assert len(ff.compiled.input_tensors) == 2
    assert family.expert_layer_names(TOY) == [
        f"block{i}_experts" for i in range(1, 6)]
    latent = [op for op in ff.compiled.ops if op.name == "block1_attn"][0]
    assert latent.q_rank is None and latent.output_gate == "head"
    assert set(ff.compiled.params["block1_attn"]) == {
        "wq", "wkv_a", "kv_norm", "wkv_b", "wg", "wo"}
    assert ff.compiled.params["block0_attn"]["dt_bias"].shape == (2 * 64,)
    assert ff.compiled.params["block0_attn"]["wg"].shape == (64, 2)
    assert reference.param_count(TOY) == sum(
        int(np.prod(a.shape)) for ws in ff.compiled.params.values()
        for a in ws.values())


def test_whole_forward_equals_the_references(toy):
    """The whole model cache-free (the KDA op's ``whole`` through the
    per-channel chunk form, ``LatentEntry.whole`` with the direct query,
    the interleaved rotation and the head's gate, the biased selection)
    against the reference's forward over 47 tokens. 2e-4 of the logits'
    range: float32 summation order."""
    ff, weights = toy
    toks = _tokens(47)
    got = PagedDecoder(ff, MAX_LEN, decode_slots=3, block_size=8,
                       calibrate=False)._dense_reference_logits(toks)
    want = _reference(weights, toks)
    assert want.shape == (47, TOY["vocab_size"])
    assert np.abs(got - want).max() <= 2e-4 * np.abs(want).max()


@pytest.mark.parametrize("leaf,value", [
    ("l0.dt_bias", 0.0),       # the decay's bias a channel left out
    ("l0.a_log", 0.0),         # its scale a head
    ("l0.wg", 0.0),            # the KDA head's gate stuck at a half
    ("l1.wg", 0.0),            # the latent head's gate
    ("l1.bias", 0.0),          # the selection's bias
    ("l2.norm", 1.0),          # the per-head norm's gain
])
def test_the_reference_reads_every_new_weight(toy, leaf, value):
    """A weight of this family's own, set to what leaving it out would
    mean, moves the reference's logits: the comparison above would see a
    program that dropped it."""
    _, weights = toy
    toks = _tokens(30, seed=3)
    want = _reference(weights, toks)
    changed = dict(weights)
    changed[leaf] = jnp.full_like(weights[leaf], value)
    assert np.abs(_reference(changed, toks) - want).max() \
        > 1e-3 * np.abs(want).max()


def test_latent_attention_alone_takes_the_direct_query_and_the_gate():
    """A model of latent layers only (a period of one): ``q_lora_rank``
    None, the head's gate and the interleaved pairs through the expanded
    form, against the reference."""
    config = dict(TOY, layer_group_size=1, num_hidden_layers=2)
    ff, weights = program(config)
    assert family.layer_types(config) == ("latent", "latent")
    toks = _tokens(33, seed=4)
    got = PagedDecoder(ff, MAX_LEN, decode_slots=3, block_size=8,
                       calibrate=False)._dense_reference_logits(toks)
    want = _reference(weights, toks, config)
    assert np.abs(got - want).max() <= 2e-4 * np.abs(want).max()


def _paged_logits(dec, prompt, steps):
    n = len(prompt)
    table = dec.pool.try_admit(n + steps + 1)
    rows, toks = [dec.prefill(prompt, table)], list(prompt)
    for k in range(steps):
        toks.append(int(rows[-1].argmax()))
        tokens = np.zeros(dec.decode_slots, np.int32)
        tables = np.zeros((dec.decode_slots, dec.max_blocks_per_request),
                          np.int32)
        lens = np.zeros(dec.decode_slots, np.int32)
        tokens[0], lens[0] = toks[-1], n + k
        tables[0, :len(table)] = table
        rows.append(dec.decode(tokens, tables, lens)[0])
    # the request's row of every state arena, (d_k, H d_v), a head a slice
    row = int(dec.pool.rows_of(np.asarray(table)[None])[0])
    states = [np.moveaxis(np.asarray(dec.pool.kv[name][0][row]).reshape(
        kind.key_dim, kind.heads, kind.value_dim), 1, 0)
        for name, kind in dec.pool.kinds.items() if kind.name == "state"]
    dec.pool.free(table)
    return np.stack(rows), np.asarray(toks, np.int32), states


@pytest.mark.parametrize("n", [21, 32])
def test_padded_prefill_then_decode_equal_the_references_forward(toy, n):
    """A prompt of 21 tokens into the bucket of 32 (each state and its
    three tails stop at the true length) and one that fills its bucket,
    then 8 decode steps through ``GenerationInstance``'s pool (state rows
    and latent blocks of one request), against the reference's full
    forward over the whole sequence: LOGITS, at 2e-4 of their range
    (float32 summation order; the absorbed latent form and the one-token
    rule are other sums than the reference's)."""
    ff, weights = toy
    inst = GenerationInstance(ff, decode_slots=3, block_size=8,
                              max_length=MAX_LEN, prefill_buckets=[16, 32])
    try:
        rows, toks, _ = _paged_logits(inst.decoder, _tokens(n, seed=n), 8)
        st = inst.stats()["kv"]
    finally:
        inst.stop()
    want = _reference(weights, toks)[len(toks) - len(rows):]
    assert rows.shape == (9, TOY["vocab_size"])
    assert np.abs(rows - want).max() <= 2e-4 * np.abs(want).max()
    assert st["entry"] == {"state": 4, "latent": 2}
    assert st["state"]["prefill_path"] == "scan"


@pytest.mark.parametrize("n", [21, 32])
def test_the_pools_state_rows_are_the_references_states(toy, n):
    """What the same prefill and 8 decode steps LEFT in the pool: the
    request's row of each KDA op's float32 arena against the state the
    reference's token loop holds after the same 29 or 40 tokens
    (``forward_with_states``), each at 2e-4 of its range; a row that had
    gone through bfloat16 would stand 4e-3 off and hold only numbers whose
    low 16 bits are 0 (the benchmark's ``state_coarse_share``)."""
    ff, weights = toy
    inst = GenerationInstance(ff, decode_slots=3, block_size=8,
                              max_length=MAX_LEN, prefill_buckets=[16, 32])
    try:
        _, toks, got = _paged_logits(inst.decoder, _tokens(n, seed=n), 8)
    finally:
        inst.stop()
    want = reference.forward_with_states(
        weights, jnp.asarray(toks[None]), TOY, "float32")[2]
    assert len(got) == len(want) == 4
    for g, w in zip(got, want):
        w = np.asarray(w)[0]
        assert g.shape == w.shape == (2, 64, 64) and g.dtype == np.float32
        assert np.abs(g - w).max() <= 2e-4 * np.abs(w).max()
        live = g[g != 0]
        assert ((live.view(np.uint32) & 0xFFFF) == 0).mean() < 1e-3


def test_two_kinds_share_one_pool_and_its_bytes_are_their_sum(toy):
    ff, _ = toy
    dec = PagedDecoder(ff, MAX_LEN, decode_slots=3, block_size=8,
                       calibrate=False)
    kinds = dec.pool.kinds
    assert [type(kinds[f"block{i}_attn"]) for i in range(6)] == [
        StateEntry, LatentEntry, StateEntry, StateEntry, LatentEntry,
        StateEntry]
    state = kinds["block0_attn"]
    assert state == StateEntry(2, 64, 64, 3, 3 * 2 * 64, True)
    assert kinds["block1_attn"] == LatentEntry(32 + 8)
    per_request = 4 * state.request_bytes(jnp.float32)
    assert per_request == 4 * (2 * 64 * 64 * 4 + 3 * 384 * 4)
    per_token = 2 * kinds["block1_attn"].token_bytes(jnp.float32)
    assert per_token == 2 * 128 * 4          # 40 numbers on 128 lanes
    assert dec.pool.memory_bytes() == (
        dec.pool.num_rows * per_request
        + dec.pool.num_blocks * dec.pool.block_size * per_token)
    assert dec.pool.num_rows == 3 + 1        # a row a slot and the null row


def test_what_the_kinds_do_not_define_refuses_by_name(toy):
    ff, _ = toy
    with pytest.raises(ValueError, match="no int8 form"):
        PagedDecoder(ff, MAX_LEN, decode_slots=3, block_size=8,
                     kv_dtype="int8")
    # (the state kind takes chunks since PR 63; the plain latent row does
    # not)
    with pytest.raises(ValueError, match="latent cache entry prefills a "
                                         "prompt whole"):
        PagedDecoder(ff, MAX_LEN, decode_slots=3, block_size=8,
                     prefill_chunk=16)
    with pytest.raises(ValueError, match="speculative verify"):
        GenerationInstance(ff, decode_slots=3, block_size=8,
                           max_length=MAX_LEN, spec_k=2, draft_ff=ff)


# ---- the share -------------------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_expert_layer():
    """Four holders of a routing group each, every one routing over all
    sixteen experts under the same bias and adding nothing for the experts
    it lacks: their routed parts, and the shared expert counted once, are
    the uncut layer, in the reference and in the program's op alike."""
    whole = dict(TOY, num_experts=16, expert_first=0)
    weights = reference.init_weights(whole, SEED)
    w = reference._layer(weights, 1)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 9, 64)),
                    jnp.float32)
    f = reference._pieces(reference._key(whole), "float32", "float32")
    u = f["normed"](x, w["norm2"])
    s = f["scores_of"](u, w)
    ids, _ = f["choose"](s, w["bias"])
    want = np.asarray(f["expert_ffn"](u, w, s, ids))
    shared = np.asarray(reference._gated(
        u, w["shared.gate"], w["shared.up"], w["shared.down"], "float32"))
    assert float(np.abs(np.asarray(w["bias"], np.float32)).max()) > 0
    total, total_op = np.zeros_like(want), np.zeros_like(want)
    for first in range(0, 16, 4):
        part = dict(TOY, num_experts=4, expert_first=first)
        held = dict(w, **{"experts." + k: w["experts." + k][first:first + 4]
                          for k in ("gate", "up", "down")})
        fh = reference._pieces(reference._key(part), "float32", "float32")
        total += np.asarray(fh["expert_ffn"](u, held, s, ids)) - shared
        total_op += _expert_op_output(first, held, u)
    assert np.abs(total + shared - want).max() < 1e-5 * np.abs(want).max()
    assert np.abs(total_op + shared - want).max() \
        < 1e-4 * np.abs(want).max()


def _expert_op_output(first, held, u):
    ff = FFModel(FFConfig(batch_size=2, ledger="off",
                          computation_mode=CompMode.INFERENCE))
    x = ff.create_tensor((2, 9, 64), DataType.FLOAT, name="x")
    ff.routed_experts(x, n_routed=16, experts_per_token=2, width=32,
                      n_group=4, topk_group=2, routed_scale=2.5,
                      selection_bias=True, experts_held=(first, 4),
                      name="experts")
    ff.compile(optimizer=None, loss_type=None, metrics=[])
    (op,) = [o for o in ff.compiled.ops if o.name == "experts"]
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    return np.asarray(op.forward(None, [u], {
        "router": f32(held["router"]), "bias": f32(held["bias"]),
        "w_gate": f32(held["experts.gate"]), "w_up": f32(held["experts.up"]),
        "w_down": f32(held["experts.down"])})[0])


def test_the_calibrated_bias_evens_the_loads():
    """``init_weights`` leaves every expert layer a bias under which the
    sixteen experts' loads over the calibration's own sample are within
    the tolerance of even; without it the bias is zero and a drawn
    router's loads are not."""
    whole = dict(TOY, num_experts=16, expert_first=0)
    for balanced in (True, False):
        weights = reference.init_weights(whole, SEED, balanced=balanced)
        key = jax.random.fold_in(reference.fold_seed(SEED),
                                 len(reference.param_shapes(whole)))
        seq = reference.BALANCE_SEQ
        tokens = jax.random.randint(
            key, (reference.BALANCE_TOKENS // seq, seq), 0,
            whole["vocab_size"], jnp.int32)
        _, info = reference.forward_with_routing(weights, tokens, whole)
        worst = 0.0
        for layer in info:
            loads = np.bincount(np.asarray(layer["own_ids"]).reshape(-1),
                                minlength=16)
            worst = max(worst, np.abs(loads / loads.mean() - 1.0).max())
        if balanced:
            # (the bias is stored in bfloat16, a few tokens' worth)
            assert worst <= reference.BALANCE_TOLERANCE + 0.02
        else:
            assert worst > 2 * reference.BALANCE_TOLERANCE
            assert all(float(jnp.abs(v).max()) == 0.0
                       for k, v in weights.items() if k.endswith(".bias"))


# ---- what the family refuses -------------------------------------------------------------

def test_family_refuses_a_clamped_kept_layer_and_an_unknown_key():
    family.check(TOY)
    # published layer 7 is clamped in the toy's lists; layers 1-6 are kept
    with pytest.raises(ValueError, match="non-zero SwiGLU limit"):
        family.check(dict(TOY, first_layer=2))
    with pytest.raises(ValueError, match="implements no key"):
        family.check(dict(TOY, attention_sink=True))
    with pytest.raises(ValueError, match="written for 'bailing_hybrid'"):
        family.check(dict(TOY, model_type="bailing_moe_v2"))
    with pytest.raises(ValueError, match="use_kda_lora"):
        family.check(dict(TOY, use_kda_lora=True))
    with pytest.raises(ValueError, match="multi-token"):
        family.check(dict(TOY, num_nextn_predict_layers=1))
    with pytest.raises(ValueError, match="leaves what a sub-chunk"):
        program(dict(TOY, kda_lower_bound=-6))


def test_kernel_path_under_the_interpreter(monkeypatch):
    """Under the Pallas interpreter, in a model over one device, the toy's
    KDA states (2 heads of 64 share a lane tile) are stepped by the widened
    kernel: greedy ids equal the jnp step's."""
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    ff, weights = program(TOY, mesh=make_mesh(devices=jax.devices()[:1]))
    dec = PagedDecoder(ff, MAX_LEN, decode_slots=3, block_size=8,
                       calibrate=False, prefill_buckets=[16, 32])
    assert dec.attention_path_by_entry["state"]["decode"] == "kernel"
    rows, toks, _ = _paged_logits(dec, _tokens(13, seed=9), 4)
    want = _reference(weights, toks)[len(toks) - len(rows):]
    assert np.abs(rows - want).max() <= 2e-4 * np.abs(want).max()
