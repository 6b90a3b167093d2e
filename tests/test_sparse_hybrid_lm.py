"""A causal LM of block-sparse attention and decay-only linear attention
layers (models/sparse_hybrid.py) through the serving path: a prompt
prefilled in chunks and then decoded through the pool (K, V and pooled
keys a token beside a state a request) against the plain reference's full
forward, whatever the chunk length and across the switch at ``dense_len``;
the selection's ids against the reference's; grouped heads in the paged
and the dense forms; the linear op's chunk form against its token scan;
what a slot's second request sees of the first; what the kinds refuse."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.families import minicpm_sala as family
from benchmark.reference import minicpm_sala as reference
from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.ffconst import CompMode
from flexflow_tpu.ops import block_sparse_attention as bsa
from flexflow_tpu.ops import lightning_attention as la
from flexflow_tpu.serving import (GenerationInstance, Generator,
                                  PagedDecoder)
from flexflow_tpu.serving.cache_entry import DecayStateEntry, SparseEntry

SPARSE, LINEAR = "minicpm4", "lightning-attn"
CONFIG = {
    "vocab_size": 96, "hidden_size": 32, "intermediate_size": 64,
    "num_hidden_layers": 4, "mixer_types": [LINEAR, SPARSE, LINEAR, SPARSE],
    "first_layer": 2, "published": {"num_hidden_layers": 8},
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "lightning_nh": 4, "lightning_nkv": 4, "lightning_head_dim": 8,
    "rope_theta": 10000, "scale_emb": 12, "scale_depth": 1.4,
    "dim_model_base": 16, "rms_norm_eps": 1e-6,
    "max_position_embeddings": 512,
    "sparse_config": {"kernel_size": 8, "kernel_stride": 4, "block_size": 16,
                      "window_size": 20, "dense_len": 64, "init_blocks": 1,
                      "topk": 4}}
SEED = 2 ** 31 + 34
MAX_LENGTH = 160
DENSE_LEN = CONFIG["sparse_config"]["dense_len"]


@pytest.fixture(scope="module")
def model():
    """The toy, holding the reference's seeded weights in float32."""
    ff = FFModel(FFConfig(batch_size=3, ledger="off", seed=0,
                          computation_mode=CompMode.INFERENCE))
    family.build(ff, CONFIG, 3, MAX_LENGTH)
    ff.compile(optimizer=None, loss_type=None, metrics=[])
    weights = {k: v.astype(jnp.float32)
               for k, v in reference.init_weights(CONFIG, SEED).items()}
    cm = ff.compiled
    cm.params = jax.tree_util.tree_map(
        jax.device_put, family.to_program(weights, CONFIG),
        cm.param_shardings)
    cm.bump_params_version()
    return ff, weights


def _decoder(ff, chunk, **kw):
    return PagedDecoder(ff, MAX_LENGTH, decode_slots=3, block_size=16,
                        prefill_chunk=chunk, **kw)


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, CONFIG["vocab_size"], size=n).astype(np.int32)


def _serve(dec, prompt, steps, slot=1):
    """A request's prefill (in chunks where the decoder has them) and
    ``steps`` greedy decode steps in ``slot``, the other slots idle: the
    logits of each, the tokens, and per sparse layer the block ids picked
    at every position."""
    names = family.sparse_layer_names(CONFIG)
    n = len(prompt)
    table = dec.pool.try_admit(n + steps + 1)
    ids = [[] for _ in names]
    chunk = dec.prefill_chunk or n
    if dec.prefill_chunk:
        for at in range(0, n, chunk):
            row = dec.prefill_chunk_at(prompt, table, at)
            for j, name in enumerate(names):
                ids[j].append(np.asarray(
                    dec.last_routing[name])[:, :, :min(chunk, n - at)])
    else:
        row = dec.prefill(prompt, table)
        for j, name in enumerate(names):
            ids[j].append(np.asarray(dec.last_routing[name])[:1, :, :n])
    rows, toks = [row], list(prompt)
    for k in range(steps):
        toks.append(int(rows[-1].argmax()))
        tokens = np.zeros(dec.decode_slots, np.int32)
        tables = np.zeros((dec.decode_slots, dec.max_blocks_per_request),
                          np.int32)
        lens = np.zeros(dec.decode_slots, np.int32)
        tokens[slot], lens[slot], tables[slot] = toks[-1], n + k, table
        rows.append(dec.decode(tokens, tables, lens)[slot])
        for j, name in enumerate(names):
            ids[j].append(np.asarray(
                dec.last_routing[name])[slot:slot + 1])
    dec.pool.free(table)
    return (np.stack(rows), np.asarray(toks, np.int32),
            [np.concatenate(layer, axis=2) for layer in ids])


def _picked_sets(ids, pos):
    """The blocks picked at position ``pos`` a key-value head, as sets of
    the blocks a query there may read."""
    last = pos // CONFIG["sparse_config"]["block_size"]
    return [frozenset(int(b) for b in head[pos] if b <= last)
            for head in ids[0]]


def test_graph_and_kinds(model):
    ff, _ = model
    dec = _decoder(ff, 48)
    kinds = dec.pool.kinds
    assert [type(kinds[f"block{i}_mixer"]) for i in range(4)] == [
        DecayStateEntry, SparseEntry, DecayStateEntry, SparseEntry]
    assert len(ff.compiled.input_tensors) == 2       # tokens and positions
    keys, values, pooled = dec.pool.kv["block1_mixer"]
    # head-major blocks, and a pooled key every stride tokens
    assert keys.shape == values.shape == (dec.pool.num_blocks, 2, 16, 8)
    assert pooled.shape == (dec.pool.num_blocks * 4, 16)
    (state,) = dec.pool.kv["block0_mixer"]
    assert state.shape == (4, 4, 8, 8) and state.dtype == jnp.float32
    assert dec.pool.kinds["block1_mixer"].token_bytes(jnp.float32) == (
        2 * 16 * 4 + 16 * 4 // 4)
    assert dec.attention_path["decode"] == "gather"
    # layers 2 and 4 of 8: the decay of the PUBLISHED index
    op = next(o for o in ff.compiled.ops if o.name == "block2_mixer")
    np.testing.assert_allclose(op.slopes, reference.decay_slopes(4, 4, 8))


@pytest.mark.parametrize("chunk", [None, 32, 48])
@pytest.mark.parametrize("prompt_len", [40, 100])
def test_chunked_prefill_then_decode_is_the_references_forward(
        model, chunk, prompt_len):
    """Whole, in chunks that divide the prompt's blocks and in chunks that
    do not: the logits of the last prompt position and of eight decode
    steps are the reference's own over the whole sequence, and the blocks
    picked are its picks (a prompt of 40 stays below ``dense_len``, one of
    100 starts past it)."""
    ff, weights = model
    dec = _decoder(ff, chunk, prefill_buckets=None if chunk else [128])
    rows, toks, ids = _serve(dec, _prompt(prompt_len, prompt_len), 8)
    want, info = reference.forward(weights, toks[None], CONFIG, "float32",
                                   rows=len(rows))
    np.testing.assert_allclose(rows, np.asarray(want)[0], atol=2e-6)
    for layer, said in zip(ids, info):
        own = np.asarray(said["own_ids"])
        for pos in range(DENSE_LEN, len(toks)):
            assert _picked_sets(layer, pos) == _picked_sets(own, pos), pos


def test_a_request_crosses_dense_len_while_decoding(model):
    """A prompt of 58 and twelve steps: the first six queries read every
    block, the rest a selection, in one decode program; the logits stay
    the reference's and do not depend on where prefill ended."""
    ff, weights = model
    rows, toks, ids = _serve(_decoder(ff, 32), _prompt(58, 7), 12)
    want, info = reference.forward(weights, toks[None], CONFIG, "float32",
                                   rows=len(rows))
    np.testing.assert_allclose(rows, np.asarray(want)[0], atol=2e-6)
    # the same sequence with the switch inside the prefill
    later, _, _ = _serve(_decoder(ff, 32), toks[:66], 4)
    np.testing.assert_allclose(later, rows[8:], atol=2e-6)
    for pos in range(DENSE_LEN, len(toks)):
        assert _picked_sets(ids[0], pos) == _picked_sets(
            np.asarray(info[0]["own_ids"]), pos)
        # 4 of the 5 blocks there are: the selection drops one
        assert all(len(s) == 4 for s in _picked_sets(ids[0], pos))


def test_the_reference_follows_a_given_selection_and_says_where_it_differs(
        model):
    ff, weights = model
    rows, toks, ids = _serve(_decoder(ff, 48), _prompt(100, 3), 2)
    _, info = reference.forward(weights, toks[None], CONFIG, "float32",
                                selection=ids, rows=3)
    assert all(not np.asarray(said["differ"]).any() for said in info)
    # another block in the dropped one's place at one position: the
    # reference attends it, counts one differing triple, and the margin is
    # the share of its lowest kept score that the intruder falls short by
    wrong = [layer.copy() for layer in ids]
    pos = len(toks) - 1                 # the last decode step's position
    last = pos // 16
    kept = set(wrong[0][0, 0, pos].tolist())
    dropped = next(b for b in range(last + 1) if b not in kept)
    swap = int(np.argmax(wrong[0][0, 0, pos] == 1))      # block 1: not forced
    wrong[0][0, 0, pos, swap] = dropped
    forced, info = reference.forward(weights, toks[None], CONFIG, "float32",
                                     selection=wrong, rows=3)
    differ = np.asarray(info[0]["differ"])
    assert differ.sum() == 1 and differ[0, 0, pos]
    assert 0 < float(np.asarray(info[0]["shortfall"])[0, 0, pos]) <= 1
    # the logits at that position follow the given blocks; the ones
    # before it (causal) are what they were
    off = np.abs(np.asarray(forced)[0] - rows).max(-1)
    assert off[-1] > 20 * off[0] and off[0] < 2e-6


def test_generate_in_chunks_equals_the_dense_generator_and_leaves_no_state(
        model):
    """Six requests over three slots, prompts on both sides of
    ``dense_len``, chunks of 32 interleaved with decode steps: every
    output is the dense generator's, a reused slot's second request sees
    nothing of the first, and the counters say what ran."""
    ff, _ = model
    gen = Generator(ff, MAX_LENGTH, batch_size=1)
    inst = GenerationInstance(ff, decode_slots=3, block_size=16,
                              max_length=MAX_LENGTH, prefill_chunk=32)
    prompts = [_prompt(n, n) for n in (100, 40, 70, 129, 5, 90)]
    futures = [inst.generate_async(p, 24) for p in prompts]
    for p, f in zip(prompts, futures):
        np.testing.assert_array_equal(f.result(300),
                                      gen.generate(p[None], 24)[0])
    st = inst.stats()
    inst.stop()
    chunks = sum(-(-len(p) // 32) for p in prompts)
    assert st["loop"]["prefill_chunks"] == chunks == st["prefill_dispatches"]
    assert st["loop"]["prefill_tokens"] == sum(len(p) for p in prompts)
    assert st["prefill_prompts"] == st["completed"] == 6
    assert st["knobs"]["prefill_chunk"] == 32
    assert st["kv"]["entry"] == {"decay_state": 2, "sparse": 2}
    assert st["kv"]["state"]["rows_stepped"] == 2 * 6 * 23
    sel = st["kv"]["selected"]
    assert 0 < sel["blocks_read"] < sel["blocks_live"]
    assert st["kv"]["kernel_rows"] == 0 and st["kv"]["state"]["in_use"] == 0
    assert st["loop"]["ahead"]["steps_sync"] == 0


def test_a_decoding_slot_waits_one_chunk_at_most(model):
    """One request decodes while another's prompt of five chunks is
    prefilled: a chunk a pass, so the first request's steps run between
    them and it finishes long before a whole prompt a pass would let it."""
    ff, _ = model
    inst = GenerationInstance(ff, decode_slots=2, block_size=16,
                              max_length=MAX_LENGTH, prefill_chunk=16)
    first = inst.generate_async(_prompt(10, 1), 30)
    while inst.stats()["tokens"] < 2:            # the first is decoding
        pass
    s0 = inst.stats()
    second = inst.generate_async(_prompt(120, 2), 2)
    second.result(300)
    s1 = inst.stats()
    first.result(300)
    inst.stop()
    chunks = s1["loop"]["prefill_chunks"] - s0["loop"]["prefill_chunks"]
    assert chunks == 8
    # each of those passes also ran a decode step of the first request
    assert s1["decode_steps"] - s0["decode_steps"] >= chunks - 1


def test_grouped_heads_in_the_paged_and_the_dense_form_are_plain_attention():
    """Below ``dense_len`` the op is causal attention in which query head h
    reads key-value head h // group: the dense form and the paged chunk
    and step against a plain rectangle."""
    geom = dict(kernel=8, stride=4, block=16, window=20, dense_len=64,
                init_blocks=1, topk=4)
    ff = FFModel(FFConfig(batch_size=2, ledger="off", seed=1,
                          computation_mode=CompMode.INFERENCE))
    x = ff.create_tensor((2, 48, 32), name="x")
    ff.block_sparse_attention(x, num_heads=4, num_kv_heads=2, head_dim=8,
                              selection=geom, name="attn")
    ff.compile(optimizer=None, loss_type=None, metrics=[])
    op = ff.compiled.ops[0]
    w = ff.compiled.params["attn"]
    xs = jax.random.normal(jax.random.key(0), (2, 48, 32))
    qg, k, v = op.project(w, xs)
    q = qg.reshape(2, 48, 4, 8)
    k4, v4 = (jnp.repeat(a, 2, axis=2) for a in (k, v))   # a head its own
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k4) * 8 ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((48, 48), bool)), s, -jnp.inf)
    plain = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v4)
    want = op.finish(w, xs, plain)
    np.testing.assert_allclose(op.forward(None, [xs], w)[0], want, atol=1e-6)
    kind = SparseEntry.for_op(op, None, 64)
    entry = tuple(jnp.zeros(a.shape, a.dtype)
                  for a in kind.arenas(9, 16, jnp.float32))
    from flexflow_tpu.serving.kv_cache import Addresses

    addr = Addresses(jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32))
    got, entry, _ = kind.chunk(op, w, xs[:, :32], None, entry, addr,
                               jnp.zeros(2, jnp.int32),
                               jnp.full(2, 32, jnp.int32))
    np.testing.assert_allclose(got, want[:, :32], atol=1e-6)
    for t in range(32, 36):
        got, entry, _ = kind.step(op, w, xs[:, t:t + 1], None, entry, addr,
                                  jnp.full(2, t, jnp.int32))
        np.testing.assert_allclose(got[:, 0], want[:, t], atol=1e-6)


@pytest.mark.parametrize("s", [1, 100, 128, 300])
def test_linear_chunk_form_is_the_token_scan(s):
    key = jax.random.key(s)
    kq, kk, kv, ks = jax.random.split(key, 4)
    b, h, d = 2, 4, 8
    q, k, v = (jax.random.normal(a, (b, s, h, d)) for a in (kq, kk, kv))
    state = jax.random.normal(ks, (b, h, d, d))
    lam = jnp.exp(-jnp.asarray(la.decay_slopes(h, 3, 8)))
    want, st = [], state
    for t in range(s):
        o, st = la.decay_step(st, q[:, t], k[:, t], v[:, t], lam)
        want.append(o)
    g = jnp.broadcast_to(jnp.log(lam), (b, s, h))
    got, end = la.chunked_decay_rule(q, k, v, g, state)
    np.testing.assert_allclose(got, jnp.stack(want, 1), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(end, st, atol=2e-5, rtol=2e-5)
    # the rows of an arena, slot by slot: row 0 is nobody's
    arena = jnp.concatenate([jnp.zeros((1, h, d, d)), state, state])
    rows = jnp.asarray([1, 0, 2], jnp.int32)    # slot 1 is idle
    q1, k1, v1 = (a[jnp.asarray([0, 1, 1]), 0] for a in (q, k, v))
    o, new = la.decay_step_rows(arena, rows, q1, k1, v1, lam)
    step_o, step_state = la.decay_step(state, q[:, 0], k[:, 0], v[:, 0], lam)
    np.testing.assert_allclose(o[jnp.asarray([0, 2])], step_o, atol=1e-5)
    np.testing.assert_array_equal(o[1], 0)
    np.testing.assert_allclose(new[1:3], step_state, atol=1e-6)
    np.testing.assert_array_equal(new[0], 0)
    np.testing.assert_array_equal(new[3:], arena[3:])    # not stepped


def test_padded_chunk_leaves_the_true_lengths_state(model):
    """A linear layer's chunk of 48 of which 20 count leaves the state 20
    tokens leave, and continues from a state only past offset 0."""
    ff, _ = model
    op = next(o for o in ff.compiled.ops if o.name == "block0_mixer")
    w = ff.compiled.params["block0_mixer"]
    x = jax.random.normal(jax.random.key(5), (1, 48, 32))
    pos = jnp.arange(48, dtype=jnp.int32)[None]
    _, whole = op.run(w, x[:, :20], pos[:, :20], op.empty_state(1))
    _, padded = op.run(w, x, pos, op.empty_state(1), jnp.asarray([20]))
    np.testing.assert_allclose(padded, whole, atol=1e-6)
    kind = DecayStateEntry.for_op(op, ff.compiled.input_tensors[1].tensor_id,
                                  MAX_LENGTH)
    from flexflow_tpu.serving.kv_cache import Addresses

    stale = (jnp.ones((3, 4, 8, 8), jnp.float32),)
    addr = Addresses(jnp.zeros((1, 1), jnp.int32), jnp.asarray([2]))
    _, first = kind.chunk(op, w, x, pos, stale, addr, jnp.asarray([0]),
                          jnp.asarray([20]))
    np.testing.assert_allclose(first[0][2], whole[0], atol=1e-6)
    np.testing.assert_array_equal(first[0][:2], 1.0)


def test_the_kinds_refuse_what_they_do_not_build(model):
    ff, _ = model
    with pytest.raises(ValueError, match="no int8 form"):
        _decoder(ff, 32, kv_dtype="int8")
    with pytest.raises(ValueError, match="spec_k=0"):
        _decoder(ff, 32).check_window(2)
    with pytest.raises(ValueError, match="block_size 8 has to be that"):
        PagedDecoder(ff, MAX_LENGTH, decode_slots=2, block_size=8)
    with pytest.raises(ValueError, match="not a multiple of block_size"):
        _decoder(ff, 40)
    with pytest.raises(ValueError, match="always kept"):
        bsa.Selection(kernel=8, stride=4, block=16, window=64, topk=4)
    with pytest.raises(ValueError, match="neither"):
        family.build(FFModel(FFConfig(batch_size=1, ledger="off")),
                     dict(CONFIG, mixer_types=["full"] * 4), 1, 16)


def test_a_kind_that_prefills_whole_refuses_chunks():
    """A plain latent row is prefilled whole (a pair of keys and values
    takes chunks since PR 42: ``tests/test_trinity_lm.py``; a gated-delta
    state since PR 63, so the hybrid toy no longer refuses)."""
    from flexflow_tpu.models import zoo_smoke_builders

    for name, refuses in (("latent_moe", True), ("hybrid", False)):
        ff = FFModel(FFConfig(batch_size=2, ledger="off",
                              computation_mode=CompMode.INFERENCE))
        zoo_smoke_builders()[name](ff, 2)
        ff.compile(optimizer=None, loss_type=None, metrics=[])
        if not refuses:
            PagedDecoder(ff, 16, decode_slots=2, block_size=16,
                         prefill_chunk=16, calibrate=False)
            continue
        with pytest.raises(ValueError, match="latent cache entry prefills "
                                             "a prompt whole"):
            PagedDecoder(ff, 16, decode_slots=2, block_size=16,
                         prefill_chunk=16)


def test_calibration_runs_over_the_three_arenas(model):
    ff, _ = model
    dec = _decoder(ff, 32, kv_dtype="bfloat16")
    assert dec.kv_dtype == "bfloat16" and dec.kv_quant_report is None
    assert 0 <= dec.kv_divergence < 0.05
    assert dec.pool.kv["block1_mixer"][2].dtype == jnp.bfloat16
    assert dec.pool.kv["block0_mixer"][0].dtype == jnp.float32
