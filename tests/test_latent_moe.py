"""Latent attention, routed experts held by share, and the paged latent
cache, at toy widths on the CPU: the program against the plain reference
(``benchmark/reference/axk1.py``, which imports nothing of the program),
the absorbed form against the expanded one, the shares against the whole,
dropless routing under imbalance, the decode kernel against the jnp
gather through the Pallas interpreter, weights held once, and what is not
built refusing loudly. The compiled kernel at the benchmark cell's shapes
is in tests_tpu/test_compiled_kernels.py."""

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

from benchmark.families import axk1 as family  # noqa: E402
from benchmark.reference import axk1 as reference  # noqa: E402
from flexflow_tpu import FFConfig, FFModel  # noqa: E402
from flexflow_tpu.ffconst import CompMode, DataType  # noqa: E402
from flexflow_tpu.kernels import latent_attention  # noqa: E402
from flexflow_tpu.models import LatentMoEConfig, build_latent_moe_lm  # noqa: E402
from flexflow_tpu.serving.generation import (  # noqa: E402
    Generator, PagedDecoder, _ExecParamsCache)
from flexflow_tpu.serving.cache_entry import LatentEntry  # noqa: E402
from flexflow_tpu.serving.kv_cache import NULL_BLOCK, PagedKVPool  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "tests", "data", "configs",
                       "axk1-toy.json")) as _f:
    TOY = json.load(_f)
# the whole toy model: every expert held
WHOLE = dict(TOY, n_routed_experts=8, expert_first=0)
SEED = 2 ** 31 + 5
MAX_LEN = 32


def _program(config, seed=SEED, dtype=DataType.FLOAT, slots=3):
    """The program's graph for ``config`` in float32 (or bfloat16)
    holding the reference's seeded weights; returns (ff, weights)."""
    cfg = dataclasses.replace(family.program_config(config, MAX_LEN),
                              param_dtype=dtype, draw_weights=True)
    ff = FFModel(FFConfig(
        batch_size=slots, computation_mode=CompMode.INFERENCE, ledger="off",
        **({"compute_dtype": "bfloat16"} if dtype is DataType.BFLOAT16
           else {})))
    build_latent_moe_lm(ff, slots, MAX_LEN, cfg)
    ff.compile(optimizer=None, loss_type=None, metrics=[])
    weights = reference.init_weights(config, seed)
    cast = (lambda a: a) if dtype is DataType.BFLOAT16 else \
        (lambda a: a.astype(jnp.float32))
    ff.compiled.params = jax.tree_util.tree_map(
        cast, family.to_program(weights, config))
    ff.compiled.bump_params_version()
    return ff, weights


def _paged_run(dec, names, prompt, steps):
    """Prefill then greedy decode steps in slot 0; the logits of each
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
        tokens[0], lens[0] = toks[-1], n + k
        tables[0, :len(table)] = table
        rows.append(dec.decode(tokens, tables, lens)[0])
        for j, nm in enumerate(names):
            ids[j].append(np.asarray(dec.last_routing[nm])[:1])
    dec.pool.free(table)
    return (np.stack(rows), np.asarray(toks, np.int32),
            [np.concatenate(x) for x in ids])


@pytest.mark.parametrize("config", [TOY, WHOLE], ids=["share", "whole"])
def test_paged_prefill_and_decode_equal_the_references_forward(config):
    """Prefill then decode through the paged latent cache (the absorbed
    form) gives the logits of the reference's cache-free forward over the
    whole sequence, with the reference taking the program's routing; and
    in float32 the two route alike."""
    ff, weights = _program(config)
    dec = PagedDecoder(ff, MAX_LEN, decode_slots=3, block_size=8)
    names = family.expert_layer_names(config)
    prompt = np.random.default_rng(3).integers(
        0, config["vocab_size"], 7).astype(np.int32)
    rows, toks, ids = _paged_run(dec, names, prompt, 3)
    logits, info = reference.forward_with_routing(
        weights, jnp.asarray(toks[None]), config, "float32", routing=ids)
    want = np.asarray(logits)[0, len(toks) - len(rows):]
    assert np.abs(rows - want).max() <= 2e-4 * np.abs(want).max()
    for got, layer in zip(ids, info):
        assert np.array_equal(np.sort(got, -1),
                              np.sort(np.asarray(layer["own_ids"]), -1))
    st = dec.expert_stats()
    assert set(st) == set(names)
    for rec in st.values():
        assert rec["steps"] == 3 and rec["pairs_routed"] == 3 * 2
        assert sum(rec["rows_per_held_expert"]) == rec["pairs_held"]
        assert rec["held"] == [config["expert_first"],
                               config["n_routed_experts"]]


def test_absorbed_and_expanded_latent_attention_give_the_same_sums():
    """The dense generator decodes in the expanded form (keys and values
    up-projected from the cached rows), the paged decoder in the absorbed
    one: the same logits to float32 reordering."""
    ff, _ = _program(WHOLE, slots=2)
    gen = Generator(ff, max_length=MAX_LEN, batch_size=2)
    dec = PagedDecoder(ff, MAX_LEN, decode_slots=2, block_size=8)
    prompt = np.arange(5, dtype=np.int32) + 11
    dense, cache, _ = gen.prefill(prompt[None])
    table = dec.pool.try_admit(9)
    paged = dec.prefill(prompt, table)
    assert np.abs(np.asarray(dense)[0] - paged).max() < 1e-5
    tables = np.zeros((2, dec.max_blocks_per_request), np.int32)
    tables[0] = table
    nxt = int(paged.argmax())
    for step in range(3):
        toks = np.array([nxt, 0], np.int32)
        got = dec.decode(toks, tables, np.array([5 + step, 0], np.int32))[0]
        want, cache = gen._step(gen._exec_params(),
                                jnp.asarray(toks[:, None]), cache,
                                jnp.int32(5 + step))
        want = np.asarray(want)[0, -1]
        assert np.abs(got - want).max() < 1e-5 * max(1.0, np.abs(want).max())
        nxt = int(want.argmax())


def _expert_op(held, params):
    """A routed-experts op of the toy's shapes holding ``held``."""
    ff = FFModel(FFConfig(batch_size=2, ledger="off",
                          computation_mode=CompMode.INFERENCE))
    x = ff.create_tensor((2, 6, 32), DataType.FLOAT, name="x")
    ff.routed_experts(x, n_routed=8, experts_per_token=2, width=16,
                      n_group=4, topk_group=2, routed_scale=2.5,
                      experts_held=held, name="experts")
    ff.compile(optimizer=None, loss_type=None, metrics=[])
    (op,) = [o for o in ff.compiled.ops if o.name == "experts"]
    first, count = held
    return op, {"router": params["router"],
                **{k: params[k][first:first + count]
                   for k in ("w_gate", "w_up", "w_down")}}


def _expert_params(rng):
    return {"router": jnp.asarray(rng.normal(size=(32, 8)), jnp.float32),
            "w_gate": jnp.asarray(rng.normal(size=(8, 32, 16)) * 0.2,
                                  jnp.float32),
            "w_up": jnp.asarray(rng.normal(size=(8, 32, 16)) * 0.2,
                                jnp.float32),
            "w_down": jnp.asarray(rng.normal(size=(8, 16, 32)) * 0.2,
                                  jnp.float32)}


def test_the_shares_add_up_to_the_uncut_expert_layer():
    """The share test: four holders of two experts each, every one
    routing over all eight and adding nothing for the experts it lacks,
    sum to the uncut layer's routed part; with the shared expert counted
    once that is the reference's whole expert layer."""
    weights = reference.init_weights(WHOLE, SEED)
    p = "l1."
    w = {k[len(p):]: v for k, v in weights.items() if k.startswith(p)}
    params = {"router": w["router"].astype(jnp.float32),
              "w_gate": w["experts.gate"].astype(jnp.float32),
              "w_up": w["experts.up"].astype(jnp.float32),
              "w_down": w["experts.down"].astype(jnp.float32)}
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 6, 32)),
                    jnp.float32)
    # the reference's layer on x: its norm, its routing, all experts, the
    # shared expert, and the residual it adds the result to
    f = reference._pieces(reference._key(WHOLE), "float32")
    s, ids, _ = f["scores_of"](x, w)
    want = np.asarray(f["expert_ffn"](x, w, s, ids) - x)
    u = reference._rms(x, w["norm2"], 1e-6)
    shared = np.asarray(reference._gated(
        u, w["shared.gate"], w["shared.up"], w["shared.down"], "float32"))
    total = np.zeros_like(want)
    for first in range(0, 8, 2):
        op, held = _expert_op((first, 2), params)
        total += np.asarray(op.forward(None, [u], held)[0])
    whole_op, whole = _expert_op((0, 8), params)
    uncut = np.asarray(whole_op.forward(None, [u], whole)[0])
    assert np.abs(total - uncut).max() < 1e-5
    assert np.abs(total + shared - want).max() < 1e-4 * np.abs(want).max()


def test_dropless_under_imbalance():
    """Every token to one held expert: it takes them all, none dropped.
    Every token to absent experts: the op adds nothing (the layer is then
    the shared expert alone)."""
    rng = np.random.default_rng(1)
    params = _expert_params(rng)
    x = jnp.asarray(np.abs(rng.normal(size=(2, 6, 32))), jnp.float32)
    # positive activations and a router that is +1 on experts 4 and 5 (one
    # group) and -1 elsewhere: every token takes exactly those two
    router = -np.ones((32, 8), np.float32)
    router[:, 4:6] = 1.0
    params["router"] = jnp.asarray(router)
    op, held = _expert_op((4, 1), params)                 # holds 4 alone
    ids, gates, _ = op.route(held, x.reshape(-1, 32))
    assert np.array_equal(np.sort(np.asarray(ids), -1),
                          np.tile([4, 5], (12, 1)))
    got = np.asarray(op.forward(None, [x], held)[0]).reshape(12, 32)
    x2 = np.asarray(x).reshape(12, 32)
    g4 = np.asarray(gates)[np.asarray(ids) == 4]
    h = jax.nn.silu(x2 @ np.asarray(params["w_gate"][4])) \
        * (x2 @ np.asarray(params["w_up"][4]))
    want = g4[:, None] * (np.asarray(h) @ np.asarray(params["w_down"][4]))
    assert np.abs(got - want).max() < 1e-4 * np.abs(want).max()
    assert np.abs(got).min(axis=-1).max() > 0      # all 12 tokens served
    absent_op, absent = _expert_op((0, 2), params)        # holds 0 and 1
    assert np.abs(np.asarray(
        absent_op.forward(None, [x], absent)[0])).max() == 0.0


# ---- the decode kernel, through the interpreter -----------------------------

HEADS, RANK, ROPE, BLOCK, MAX_BLOCKS = 16, 96, 32, 16, 40
ROW = RANK + ROPE                      # 128: one lane tile
HUGE = 3.0e4


def _kernel_case(dtype, seed=0):
    rng = np.random.default_rng(seed)
    lens = np.array([0, 3 * BLOCK, 5 * BLOCK - 1, MAX_BLOCKS * BLOCK - 1,
                     523], np.int32)
    n = lens.size
    nb = n * MAX_BLOCKS + 1
    arena = np.full((nb, BLOCK, ROW), HUGE, np.float32)
    tables = np.full((n, MAX_BLOCKS), NULL_BLOCK, np.int32)
    perm = rng.permutation(np.arange(1, nb))
    for i, length in enumerate(lens):
        if length == 0:
            continue
        tables[i] = perm[i * MAX_BLOCKS:(i + 1) * MAX_BLOCKS]
        for pos in range(length + 1):          # the new token's row too
            arena[tables[i, pos // BLOCK], pos % BLOCK] = rng.normal(size=ROW)
    q = rng.normal(size=(n, HEADS, ROW)).astype(np.float32)
    return (jnp.asarray(q, dtype), jnp.asarray(arena, dtype),
            jnp.asarray(tables), jnp.asarray(lens))


def _gather_reference(q, arena, tables, lens, scale):
    n, mb = tables.shape
    view = arena[tables].reshape(n, mb * BLOCK, ROW).astype(jnp.float32)
    s = jnp.einsum("nhr,nlr->nhl", q.astype(jnp.float32), view) * scale
    kpos = jnp.arange(mb * BLOCK)
    s = jnp.where((kpos[None] <= lens[:, None])[:, None], s, -1e30)
    return np.asarray(jnp.einsum("nhl,nlc->nhc", jax.nn.softmax(s, -1),
                                 view[..., :RANK]))


@pytest.mark.parametrize("dtype,tol", [("float32", 5e-6),
                                       ("bfloat16", 2.0 ** -6)])
def test_latent_kernel_matches_the_gather(monkeypatch, dtype, tol):
    """Ragged lengths (an idle slot, a block boundary, a block less one,
    the table's last row, mid-block past a chunk), garbage wherever a
    slot may not look: the kernel's sums are the gather's."""
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    q, arena, tables, lens = _kernel_case(dtype)
    assert latent_attention.supported(q.shape, arena.shape, dtype,
                                      MAX_BLOCKS, ROW)
    got = np.asarray(latent_attention.latent_attention_decode(
        q, arena, tables, lens, scale=0.1, out_width=ROW))[..., :RANK]
    want = _gather_reference(q, arena, tables, lens, 0.1)
    active = np.asarray(lens) > 0
    assert np.isfinite(got).all()
    worst = np.abs(got[active] - want[active]).max()
    assert worst <= tol * np.abs(want[active]).max(), worst
    assert np.abs(got[active]).max() < 10.0, "garbage leaked"


def test_latent_kernel_reads_live_blocks_only(monkeypatch):
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    q, arena, tables, lens = _kernel_case("float32")
    run = lambda a: np.asarray(latent_attention.latent_attention_decode(  # noqa: E731
        q, a, tables, lens, scale=0.1, out_width=ROW, pages_per_chunk=8))
    live = {NULL_BLOCK}
    for row, length in zip(np.asarray(tables), np.asarray(lens)):
        live.update(row[:(int(length) + BLOCK) // BLOCK].tolist())
    dead = np.array(sorted(set(range(arena.shape[0])) - live))
    assert dead.size > 0
    assert np.array_equal(run(arena), run(arena.at[dead].set(jnp.nan)))


def test_paged_decoder_takes_the_kernel_where_it_is_supported(monkeypatch):
    """A toy whose rows fill a lane tile and whose heads fill a sublane
    tile decodes through the kernel under the interpreter, to the same
    logits as the gather."""
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    cfg = LatentMoEConfig(
        vocab_size=64, max_positions=64, hidden_size=32, num_layers=2,
        num_heads=8, q_lora_rank=16, kv_lora_rank=128, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=8, dense_width=32, expert_width=16,
        n_routed=4, experts_per_token=2)

    def decoder():
        ff = FFModel(FFConfig(batch_size=2, ledger="off",
                              computation_mode=CompMode.INFERENCE))
        build_latent_moe_lm(ff, 2, 16, cfg)
        ff.compile(optimizer=None, loss_type=None, metrics=[])
        return PagedDecoder(ff, 64, decode_slots=2, block_size=8)

    def run(dec):
        table = dec.pool.try_admit(12)
        dec.prefill(np.arange(6, dtype=np.int32), table)
        tables = np.zeros((2, dec.max_blocks_per_request), np.int32)
        tables[0] = table
        return dec.decode(np.array([3, 0], np.int32), tables,
                          np.array([6, 0], np.int32))[0]

    dec = decoder()
    assert dec.attention_path["decode"] == "kernel"
    got = run(dec)
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "off")
    ref = decoder()
    assert ref.attention_path["decode"] == "gather"
    want = run(ref)
    assert np.abs(got - want).max() < 1e-5 * max(1.0, np.abs(want).max())


# ---- weights held once; what is not built refuses ---------------------------

def test_bfloat16_weights_are_held_once():
    """A graph that states bfloat16 storage is initialised in bfloat16,
    and the decode's cast-once cache hands back the very same arrays: no
    second copy. A float32 graph still gets its bfloat16 copy."""
    ff, _ = _program(TOY, dtype=DataType.BFLOAT16)
    cm = ff.compiled
    leaves = jax.tree_util.tree_leaves(cm.params)
    assert all(l.dtype == jnp.bfloat16 for l in leaves)
    cast = jax.tree_util.tree_leaves(
        _ExecParamsCache().get(cm, jnp.bfloat16))
    assert all(a is b for a, b in zip(leaves, cast))
    drawn = FFModel(FFConfig(batch_size=2, ledger="off",
                             computation_mode=CompMode.INFERENCE))
    build_latent_moe_lm(drawn, 2, 8, dataclasses.replace(
        family.program_config(TOY, 8), draw_weights=True))
    drawn.compile(optimizer=None, loss_type=None, metrics=[])
    assert all(l.dtype == jnp.bfloat16 for l in
               jax.tree_util.tree_leaves(drawn.compiled.params))
    f32, _ = _program(TOY)
    leaves = jax.tree_util.tree_leaves(f32.compiled.params)
    cast = jax.tree_util.tree_leaves(
        _ExecParamsCache().get(f32.compiled, jnp.bfloat16))
    assert all(a is not b and b.dtype == jnp.bfloat16
               for a, b in zip(leaves, cast))


def test_declared_weights_are_shapes_until_loaded():
    ff = FFModel(FFConfig(batch_size=2, ledger="off",
                          computation_mode=CompMode.INFERENCE))
    family.build(ff, TOY, 2, 16)
    ff.compile(optimizer=None, loss_type=None, metrics=[])
    leaves = jax.tree_util.tree_leaves(ff.compiled.params)
    assert leaves and all(isinstance(l, jax.ShapeDtypeStruct)
                          and l.dtype == jnp.bfloat16 for l in leaves)
    n = sum(int(np.prod(l.shape)) for l in leaves)
    assert n == reference.param_count(TOY)


def test_int8_and_verify_over_a_latent_entry_refuse():
    with pytest.raises(ValueError, match="no int8 form"):
        PagedKVPool({"attn": LatentEntry(24)}, num_blocks=4, block_size=8,
                    max_blocks_per_request=2, kv_dtype="int8")
    ff, _ = _program(TOY)
    with pytest.raises(ValueError, match="no int8 form"):
        PagedDecoder(ff, MAX_LEN, decode_slots=3, block_size=8,
                     kv_dtype="int8")
    dec = PagedDecoder(ff, MAX_LEN, decode_slots=3, block_size=8)
    with pytest.raises(ValueError, match="speculative verify"):
        dec.verify(np.zeros((3, 2), np.int32),
                   np.zeros((3, dec.max_blocks_per_request), np.int32),
                   np.zeros(3, np.int32))
    from flexflow_tpu.serving import GenerationInstance

    with pytest.raises(ValueError, match="speculative verify"):
        GenerationInstance(ff, decode_slots=3, block_size=8,
                           max_length=MAX_LEN, spec_k=2, draft_ff=ff)


def test_pool_stats_name_the_entry():
    ff, _ = _program(TOY)
    dec = PagedDecoder(ff, MAX_LEN, decode_slots=3, block_size=8)
    st = dec.pool.stats()
    assert (st["entry"], st["row_width"], st["row_lanes"]) == ("latent", 24,
                                                               128)
    assert st["memory_bytes"] == 3 * dec.pool.num_blocks * 8 * 128 * 4
    assert all(len(e) == 1 for e in dec.pool.kv.values())


def test_expert_counters_are_two_words_and_carry():
    """A count is two uint32 words: the low word's wrap carries into the
    high one, so a long-lived server's counters never go negative."""
    from flexflow_tpu.serving.generation import _count_up

    acc = jnp.asarray([[2 ** 32 - 3, 7], [1, 0]], jnp.uint32)
    out = np.asarray(_count_up(acc, jnp.asarray([5, 1024], jnp.uint32)))
    assert out.tolist() == [[2, 1031], [2, 0]]


def test_expert_counters_live_outside_the_pool_and_read_beside_a_step():
    """The pool holds cache entries only; the counters are the decoder's,
    donated to each decode step, and a reader on another thread gets
    whole numbers while steps run (never a donated buffer)."""
    import threading

    ff, _ = _program(TOY)
    dec = PagedDecoder(ff, MAX_LEN, decode_slots=3, block_size=8)
    names = family.expert_layer_names(TOY)
    assert not set(names) & set(dec.pool.kv)
    table = dec.pool.try_admit(MAX_LEN)
    dec.prefill(np.arange(4, dtype=np.int32), table)
    tables = np.full((3, dec.max_blocks_per_request), NULL_BLOCK, np.int32)
    tables[0, :table.shape[0]] = table
    seen, stop = [], threading.Event()

    def reader():
        while not stop.is_set():
            seen.append(dec.expert_stats()[names[0]]["steps"])

    t = threading.Thread(target=reader)
    t.start()
    try:
        for step in range(12):
            dec.decode(np.array([1, 0, 0], np.int32), tables,
                       np.array([4 + step, 0, 0], np.int32))
    finally:
        stop.set()
        t.join()
    assert seen == sorted(seen) and seen[-1] <= 12
    rec = dec.expert_stats()[names[0]]
    assert rec["steps"] == 12 and rec["pairs_routed"] == 12 * 2


def test_only_sigmoid_and_softmax_scores_are_built():
    with pytest.raises(ValueError, match="neither 'sigmoid' nor 'softmax'"):
        ff = FFModel(FFConfig(batch_size=2, ledger="off",
                              computation_mode=CompMode.INFERENCE))
        x = ff.create_tensor((2, 4, 32), DataType.FLOAT)
        ff.routed_experts(x, n_routed=8, experts_per_token=2, width=16,
                          scoring="tanh")
        ff.compile(optimizer=None, loss_type=None, metrics=[])


def test_rotary_frequencies_and_scale_match_the_reference():
    """The op's YaRN frequencies and softmax scale are the reference's
    (written twice, independently)."""
    from flexflow_tpu.ops.attention import rotary_inv_freq

    sc = {"beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1,
          "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
          "type": "yarn"}
    a = rotary_inv_freq(64, 10000.0, sc)
    b = reference.yarn_inv_freq(64, 10000.0, sc)
    assert np.allclose(a, b, rtol=1e-6)
    assert a[0] == pytest.approx(1.0) and a[-1] < b[0] / 32 * 1.01
    cfg = dict(TOY, qk_nope_head_dim=128, qk_rope_head_dim=64,
               rope_scaling=sc)
    assert reference.softmax_scale(cfg) == pytest.approx(
        192 ** -0.5 * 1.3466 ** 2, rel=1e-4)


def test_kv_calibration_follows_the_paged_programs_routing():
    """The KV calibration gate measures the cache, not the routing: its
    dense reference takes the experts the paged programs took, so a flip
    between two programs a rounding apart cannot trip KVQ001."""
    ff, _ = _program(TOY)
    dec = PagedDecoder(ff, MAX_LEN, decode_slots=3, block_size=8,
                       kv_dtype="bfloat16")
    assert dec.kv_dtype == "bfloat16" and dec.kv_quant_report is None
    assert dec.kv_divergence is not None and dec.kv_divergence < 0.05
    names = family.expert_layer_names(TOY)
    toks = np.arange(6, dtype=np.int32) + 3
    free = dec._dense_reference_logits(toks)
    # every token forced to experts the reference would not take: the
    # logits move, so the routing given is the routing used
    forced = {nm: np.tile(np.array([[0, 1]], np.int32), (6, 1))
              for nm in names}
    assert np.abs(dec._dense_reference_logits(toks, forced) - free).max() > 0
    (op,) = [o for o in ff.compiled.ops if o.name == names[0]]
    x = jnp.asarray(np.random.default_rng(0).normal(size=(5, 32)),
                    jnp.float32)
    p = ff.compiled.params[names[0]]
    ids, gates, _ = op.route(p, x)
    same_ids, same_gates, _ = op.route(p, x, ids)
    assert np.array_equal(ids, same_ids)
    assert np.allclose(gates, same_gates)
