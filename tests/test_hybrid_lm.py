"""A causal LM of gated-delta-rule and full-attention layers
(models/hybrid.py) through the serving path: prefill then decode through
the mixed pool (a (k, v) pair a token beside a state a request) against
the plain reference's full forward, what a slot's second request sees of
the first, what idle slots touch, what the state kind refuses, and that
the models without such a layer lower to the programs they had."""

import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import olmo_hybrid as reference
from flexflow_tpu import AdamOptimizer, FFConfig, FFModel
from flexflow_tpu.ffconst import CompMode, DataType, LossType, MetricsType
from flexflow_tpu.models import (HybridLMConfig, build_hybrid_lm,
                                 zoo_smoke_builders)
from flexflow_tpu.serving import (GenerationInstance, Generator,
                                  PagedDecoder)
from flexflow_tpu.serving.cache_entry import PairEntry, StateEntry

LINEAR, FULL = "linear_attention", "full_attention"
CONFIG = {
    "vocab_size": 96, "hidden_size": 32, "intermediate_size": 64,
    "num_hidden_layers": 4, "num_attention_heads": 4,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-6,
    "layer_types": [LINEAR, LINEAR, FULL, LINEAR],
    "linear_num_key_heads": 4, "linear_num_value_heads": 4,
    "linear_key_head_dim": 8, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True}
SEED = 2 ** 31 + 32


def _build(slots=3, seq=16, config=CONFIG, **ffkw):
    ff = FFModel(FFConfig(batch_size=slots, ledger="off", seed=0,
                          computation_mode=CompMode.INFERENCE, **ffkw))
    build_hybrid_lm(ff, slots, seq, HybridLMConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        layer_types=tuple(config["layer_types"]),
        num_heads=config["num_attention_heads"],
        linear_heads=config["linear_num_value_heads"],
        linear_key_dim=config["linear_key_head_dim"],
        linear_value_dim=config["linear_value_head_dim"],
        mlp_width=config["intermediate_size"]))
    ff.compile(optimizer=None, loss_type=None, metrics=[])
    return ff


def _load(ff, config=CONFIG, seed=SEED):
    """The reference's seeded weights, in float32, into the program."""
    from benchmark.families import olmo_hybrid as family  # noqa: F401

    weights = {k: v.astype(jnp.float32)
               for k, v in reference.init_weights(config, seed).items()}
    cm = ff.compiled
    cm.params = jax.tree_util.tree_map(
        jax.device_put, family.to_program(weights, config),
        cm.param_shardings)
    cm.bump_params_version()
    return weights


@pytest.fixture(scope="module")
def model():
    ff = _build()
    return ff, _load(ff)


def _serve(dec, prompt, steps, slot=1):
    """A request's prefill and ``steps`` greedy decode steps in ``slot``,
    the other slots idle: the logits of each, and the tokens."""
    n = len(prompt)
    table = dec.pool.try_admit(n + steps + 1)
    rows, toks = [dec.prefill(prompt, table)], list(prompt)
    slots = dec.decode_slots
    for k in range(steps):
        toks.append(int(rows[-1].argmax()))
        tokens = np.zeros(slots, np.int32)
        tables = np.zeros((slots, dec.max_blocks_per_request), np.int32)
        lens = np.zeros(slots, np.int32)
        tokens[slot], lens[slot], tables[slot] = toks[-1], n + k, table
        rows.append(dec.decode(tokens, tables, lens)[slot])
    return np.stack(rows), np.asarray(toks, np.int32), table


def _reference_rows(weights, toks, n_rows, config=CONFIG):
    logits = reference.forward_jit(weights, jnp.asarray(toks[None, :]),
                                   config, "float32")
    return np.asarray(logits)[0, len(toks) - n_rows:]


def test_graph_and_kinds(model):
    ff, _ = model
    cm = ff.compiled
    assert len(cm.input_tensors) == 1       # no positions input
    dec = PagedDecoder(ff, 128, decode_slots=3, block_size=8)
    kinds = dec.pool.kinds
    assert [type(kinds[f"block{i}_mixer"]) for i in range(4)] == [
        StateEntry, StateEntry, PairEntry, StateEntry]
    state, tail = dec.pool.kv["block0_mixer"]
    assert state.shape == (4, 8, 4 * 16) and state.dtype == jnp.float32
    assert tail.shape == (4, 3 * (2 * 4 * 8 + 4 * 16))
    k, _ = dec.pool.kv["block2_mixer"]
    assert k.shape == (dec.pool.num_blocks, 8, 32)
    # no model's name beyond the builder: the kinds come from the op types
    assert dec.pool.num_rows == dec.decode_slots + 1


@pytest.mark.parametrize("prompt_len,bucket", [(70, 96), (96, 96), (5, 16)])
def test_prefill_then_decode_is_the_references_forward(model, prompt_len,
                                                       bucket):
    """Through both caches: a prompt that ends inside a chunk and inside
    its bucket (70 of 96), one that fills its bucket, one shorter than a
    chunk; then six decode steps."""
    ff, weights = model
    dec = PagedDecoder(ff, 128, decode_slots=3, block_size=8,
                       prefill_buckets=[16, 96, 128])
    assert dec.bucket_for(prompt_len) == bucket
    prompt = np.random.default_rng(prompt_len).integers(
        0, 96, size=prompt_len).astype(np.int32)
    rows, toks, table = _serve(dec, prompt, 6)
    dec.pool.free(table)
    want = _reference_rows(weights, toks, len(rows))
    spread = np.linalg.norm(want - want.mean(-1, keepdims=True), axis=-1)
    err = np.linalg.norm(rows - want, axis=-1) / spread
    assert err.max() < 2e-4, err


def test_generate_equals_the_dense_generator_and_slots_are_reused(model):
    """Seven requests over three slots: every slot serves a second and a
    third request, each equal to the request decoded alone through the
    dense generator (whose cache is a state and a tail a row)."""
    ff, _ = model
    rng = np.random.default_rng(9)
    reqs = [(rng.integers(0, 96, (n,)).astype(np.int32), m)
            for n, m in [(3, 6), (20, 4), (9, 9), (70, 5), (2, 3), (33, 7),
                         (12, 8)]]
    gen = Generator(ff, max_length=128, batch_size=1)
    want = [gen.generate(p[None, :], m)[0] for p, m in reqs]
    inst = GenerationInstance(ff, decode_slots=3, block_size=8,
                              max_length=128, prefill_buckets=[16, 96, 128])
    try:
        futs = [inst.generate_async(p, m, temperature=0.0) for p, m in reqs]
        got = [f.result(timeout=300) for f in futs]
        stats = inst.stats()
    finally:
        inst.stop()
    for out, ref in zip(got, want):
        np.testing.assert_array_equal(out, ref)
    kv = stats["kv"]
    assert kv["entry"] == {"state": 3, "pair": 1}
    assert kv["state_dtype"] == "float32"
    st = kv["state"]
    assert st["rows"] == 4 and st["in_use"] == 0 and st["high_water"] == 3
    assert st["row_bytes"] == 3 * (8 * 64 * 4 + 3 * 128 * 4)
    # active slots x the three state layers, summed over the steps
    assert st["rows_stepped"] == 3 * (sum(m - 1 for _, m in reqs))
    assert st["prefill_path"] == "scan"          # the CPU: the jnp form
    assert kv["in_use"] == 0


@pytest.mark.parametrize("kv_dtype", ["float32", "bfloat16"])
def test_requests_in_slots_out_of_row_order_decode_as_alone(model, kv_dtype):
    """Three requests whose arena rows (1, 2, 3 by admission) sit in the
    slots in another order, an idle slot between them and one joining
    late: every step's tails go back to the row their slot names, so each
    request's logits are what it reads decoded alone in slot 1 of a fresh
    decoder, to the bit (the same programs, the other slots idle)."""
    ff, _ = model
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 96, size=n).astype(np.int32)
               for n in (9, 14, 5)]
    steps = 6

    def decoder():
        return PagedDecoder(ff, 64, decode_slots=5, block_size=8,
                            prefill_buckets=[16], kv_dtype=kv_dtype,
                            calibrate=False)

    alone = []
    for p in prompts:
        rows, toks, _ = _serve(decoder(), p, steps)
        alone.append((rows, toks))
    dec = decoder()
    tables = [dec.pool.try_admit(len(p) + steps + 1) for p in prompts]
    assert dec.pool.rows_of(np.stack(tables)).tolist() == [1, 2, 3]
    got = [[dec.prefill(p, t)] for p, t in zip(prompts, tables)]
    slot_of = {0: 4, 1: 2, 2: 0}          # request -> slot; 1 and 3 idle
    joins = {0: 0, 1: 2, 2: 0}            # request 1 waits two steps
    for k in range(steps + 2):
        tokens = np.zeros(5, np.int32)
        tabs = np.zeros((5, dec.max_blocks_per_request), np.int32)
        lens = np.zeros(5, np.int32)
        live = [r for r in range(3) if joins[r] <= k < joins[r] + steps]
        for r in live:
            slot, j = slot_of[r], k - joins[r]
            tokens[slot] = alone[r][1][len(prompts[r]) + j]
            lens[slot], tabs[slot] = len(prompts[r]) + j, tables[r]
        out = dec.decode(tokens, tabs, lens)
        for r in live:
            got[r].append(out[slot_of[r]])
    for r in range(3):
        np.testing.assert_array_equal(np.stack(got[r]), alone[r][0])
    tails = np.asarray(dec.pool.kv["block0_mixer"][1].astype(jnp.float32))
    assert np.isfinite(tails).all() and not tails[0].any()  # never written


def test_two_requests_on_five_slots_equal_the_dense_generator(model):
    """Through the scheduler with most slots idle for the whole session
    (their tables name row 0): token for token the dense generator's."""
    ff, _ = model
    rng = np.random.default_rng(11)
    reqs = [(rng.integers(0, 96, (n,)).astype(np.int32), m)
            for n, m in [(13, 9), (4, 12)]]
    gen = Generator(ff, max_length=64, batch_size=1)
    want = [gen.generate(p[None, :], m)[0] for p, m in reqs]
    inst = GenerationInstance(ff, decode_slots=5, block_size=8,
                              max_length=64, prefill_buckets=[16])
    try:
        got = [f.result(timeout=300) for f in
               [inst.generate_async(p, m, temperature=0.0) for p, m in reqs]]
        state = inst.stats()["kv"]["state"]
    finally:
        inst.stop()
    for out, ref in zip(got, want):
        np.testing.assert_array_equal(out, ref)
    assert state["high_water"] == 2 and state["in_use"] == 0


def test_a_slots_second_request_sees_nothing_of_the_first(model):
    ff, _ = model
    dec = PagedDecoder(ff, 128, decode_slots=3, block_size=8,
                       prefill_buckets=[16, 96])
    rng = np.random.default_rng(2)
    second = rng.integers(0, 96, size=11).astype(np.int32)
    fresh, _, table = _serve(dec, second, 3)
    dec.pool.free(table)
    # a long first request leaves its state in the row the second takes
    first = rng.integers(0, 96, size=60).astype(np.int32)
    _, _, table = _serve(dec, first, 4)
    row = int(dec.pool.rows_of(table[None])[0])
    dec.pool.free(table)
    again, _, table = _serve(dec, second, 3)
    assert int(dec.pool.rows_of(table[None])[0]) == row
    dec.pool.free(table)
    assert np.array_equal(fresh, again)


def test_idle_slots_touch_only_the_null_row(model):
    ff, _ = model
    dec = PagedDecoder(ff, 128, decode_slots=3, block_size=8,
                       prefill_buckets=[16])
    prompts = [np.arange(1, 8, dtype=np.int32),
               np.arange(20, 30, dtype=np.int32)]
    tables = [dec.pool.try_admit(24) for _ in prompts]
    for p, t in zip(prompts, tables):
        dec.prefill(p, t)
    rows = dec.pool.rows_of(np.stack(tables))
    assert sorted(rows) == [1, 2]
    before = jax.device_get(dec.pool.kv["block0_mixer"])
    # slot 0 carries the first request; slots 1 and 2 idle
    tabs = np.zeros((3, dec.max_blocks_per_request), np.int32)
    tabs[0] = tables[0]
    assert dec.pool.rows_of(tabs).tolist() == [rows[0], 0, 0]
    dec.decode(np.array([5, 0, 0], np.int32), tabs,
               np.array([7, 0, 0], np.int32))
    after = jax.device_get(dec.pool.kv["block0_mixer"])
    other, free = int(rows[1]), 3
    for a, b in zip(before, after):
        assert np.array_equal(a[other], b[other])   # the waiting request
        assert np.array_equal(a[free], b[free])     # the row nobody holds
        assert not np.array_equal(a[rows[0]], b[rows[0]])
        assert np.isfinite(b[0]).all()
    for t in tables:
        dec.pool.free(t)


def test_state_kind_refuses_rollback_and_int8_by_name(model):
    ff, _ = model
    with pytest.raises(
            ValueError,
            match=r"speculative verify over a state cache entry is not "
                  r"built \(block0_mixer and 2 more\): serve this model "
                  r"with spec_k=0"):
        GenerationInstance(ff, decode_slots=2, block_size=8, max_length=64,
                           spec_k=2, draft_ff=ff)
    with pytest.raises(ValueError, match=r"block0_mixer: a state cache "
                                         r"entry has no int8 form"):
        PagedDecoder(ff, 64, decode_slots=2, block_size=8, kv_dtype="int8")
    assert StateEntry(4, 8, 16, 3, 128).max_window == 1
    assert StateEntry(4, 8, 16, 3, 128).int8_form is None


def test_calibration_runs_over_the_mixed_pool_and_the_state_stays_float32():
    ff = _build()
    _load(ff)
    dec = PagedDecoder(ff, 64, decode_slots=2, block_size=8,
                       kv_dtype="bfloat16", kv_divergence_budget=0.5)
    assert dec.kv_dtype == "bfloat16" and dec.kv_quant_report is None
    assert 0.0 < dec.kv_divergence < 0.1
    state, tail = dec.pool.kv["block0_mixer"]
    assert state.dtype == jnp.float32 and tail.dtype == jnp.bfloat16
    assert dec.pool.kv["block2_mixer"][0].dtype == jnp.bfloat16
    assert dec.pool.stats()["state"]["in_use"] == 0   # its row came back


def test_decode_takes_both_kernels_where_both_are_supported(monkeypatch):
    """A toy whose heads fill lane tiles for both readers: the paged
    attention kernel over the pairs and the state kernel over the states,
    under the interpreter, to the gather's logits."""
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    config = dict(CONFIG, hidden_size=128, num_attention_heads=2,
                  num_key_value_heads=2, linear_num_key_heads=2,
                  linear_num_value_heads=2, linear_value_head_dim=64,
                  layer_types=[LINEAR, FULL], num_hidden_layers=2)

    def run():
        ff = _build(slots=2, config=config)
        _load(ff, config)
        dec = PagedDecoder(ff, 64, decode_slots=2, block_size=8,
                           prefill_buckets=[16])
        rows, _, table = _serve(dec, np.arange(3, 14, dtype=np.int32), 3,
                                slot=0)
        dec.pool.free(table)
        return dec.attention_path["decode"], rows

    path, got = run()
    assert path == "kernel"
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "off")
    path, want = run()
    assert path == "gather"
    assert np.abs(got - want).max() < 1e-4 * np.abs(want).max()


def test_prefill_takes_the_chunks_kernel_and_generates_the_scans_tokens(
        monkeypatch):
    """A toy whose linear layers the whole-sequence kernel takes (4 heads
    of 32 and 64: one group): under the interpreter every prefill
    program runs it, ``stats()`` says so, and the greedy outputs of five
    requests over two slots and two buckets are, token for token, those
    of the jnp form."""
    config = dict(CONFIG, hidden_size=128, num_attention_heads=2,
                  num_key_value_heads=2, linear_key_head_dim=32,
                  linear_value_head_dim=64)
    rng = np.random.default_rng(4)
    reqs = [(rng.integers(0, 96, (n,)).astype(np.int32), m)
            for n, m in [(5, 6), (130, 5), (40, 7), (97, 4), (80, 6)]]

    def run():
        ff = _build(slots=2, config=config)
        _load(ff, config)
        inst = GenerationInstance(ff, decode_slots=2, block_size=8,
                                  max_length=144,
                                  prefill_buckets=[80, 144])
        try:
            futs = [inst.generate_async(p, m, temperature=0.0)
                    for p, m in reqs]
            return ([f.result(timeout=600) for f in futs],
                    inst.stats()["kv"]["state"]["prefill_path"])
        finally:
            inst.stop()

    want, path = run()
    assert path == "scan"
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    got, path = run()
    assert path == "kernel"
    for out, ref in zip(got, want):
        np.testing.assert_array_equal(out, ref)


def test_bfloat16_weights_are_held_once_and_declared():
    ff = FFModel(FFConfig(batch_size=2, ledger="off",
                          computation_mode=CompMode.INFERENCE))
    build_hybrid_lm(ff, 2, 16, HybridLMConfig(
        vocab_size=64, hidden_size=32, num_heads=4, linear_heads=4,
        linear_key_dim=8, linear_value_dim=16, mlp_width=64,
        param_dtype=DataType.BFLOAT16, draw_weights=False))
    ff.compile(optimizer=None, loss_type=None, metrics=[])
    leaves = jax.tree_util.tree_leaves(ff.compiled.params)
    assert all(isinstance(a, jax.ShapeDtypeStruct)
               and a.dtype == jnp.bfloat16 for a in leaves)


def test_unknown_layer_type_is_refused():
    ff = FFModel(FFConfig(batch_size=2, ledger="off"))
    with pytest.raises(ValueError, match="layer 1: 'sliding'"):
        build_hybrid_lm(ff, 2, 16, HybridLMConfig(
            layer_types=(LINEAR, "sliding")))


# ---- the models without such a layer keep their programs ------------------------

# sha256 of the lowered text (``jit(...).lower(...).as_text()``) of each
# program, recorded on the commit before the state kind (543e5ce): the new
# argument (``Addresses`` with no rows), ``qk_norm`` and the optional
# positions leave GPT-2's and the latent model's programs letter for
# letter what they were. After a change that is meant to move one of
# them, print the new ones: ``python tests/test_hybrid_lm.py``.
# ``hybrid.decode`` was recorded on 0f38e32, before the whole-sequence
# kernel: what changes a prefill's recurrence must not reach the decode
# program. The two ``train`` programs were recorded again with the
# one-pass sparse cross-entropy (``runtime/loss.py``
# ``sparse_log_likelihood``), which is meant to move them and nothing
# else: the five serving programs kept their digests through it.
# ``hybrid.decode`` was recorded again when ``StateEntry.step`` took to
# writing its tails back row by row of the arena, each row taking the
# values of the slot that names it (``spread_rows``, now ``ops/rows.py``'s), in
# place of a scatter: the one program that change is meant to move; the
# other six kept theirs. ``latent_moe.prefill`` was recorded again when a
# prompt program took to counting, beside the expert ids it keeps, the
# live tokens' pairs whose expert its op holds (``generation.HELD_PAIRS``:
# the rows named beside the rows computed); grouped heads, the experts'
# new attributes, their grouped product (which these rows do not reach)
# and the fourth per-request kind left the other six letter for letter;
# and once more when it took to returning, under the same key, the rows
# its experts' products ran over beside them (counted on the device where
# the grouped kernel runs; a (2, ops) array where it was (ops,)): again
# the one program moved, every decode program among the six that stayed.
# The three ``nemotron_h``, ``hybrid.prefill`` and ``sparse_hybrid``
# digests were recorded on 8791b58, the commit before an attention op
# took a window, rotary positions, a head size of its own, a per-head
# norm and a gate, and before a pair took chunks: each of those is absent
# by default, and every program of the older configurations stayed.
# The two ``granite_hybrid`` digests were recorded with the builder (PR
# 46); the other twelve kept theirs through the attention op's ``scale``,
# the weight two ops read (``core/op.py`` ``weights_of``) and the Mamba
# kind's ``chunk`` (its bucketed ``prefill`` and its ``step`` are what
# they were: ``nemotron_h.decode`` and ``nemotron_h.prefill`` letter for
# letter). The four ``granite_hybrid`` and ``nemotron_h`` digests were
# recorded again when the Mamba kind took to storing a state's row as
# ``(N, H P)``, the layout the ``ssd_step_decode`` kernel steps in place
# (PR 47: ``SsmStateEntry.arenas``, ``_put``, ``_rows`` and
# ``ssd_step_rows`` are the places that know it): the four programs that
# hold such an arena, and no other; on the CPU the kernel's
# ``supported()`` refuses and the step is ``ssd_step_rows`` as before,
# over the new rows. The other ten kept theirs letter for letter.
# ``sparse_hybrid.decode`` was recorded again when ``decay_step_rows``
# took to TAKING each row's q, k and v from the slot that names it
# (``ops/rows.py`` ``named_by``, as ``ssd_step_rows`` does) where it
# multiplied them in by a one-hot product over the slots, which made one
# request's NaN every live request's (PR 60, ROADMAP D19): the one
# program that is meant to move. The hand-over's one owner, the one
# ``ConvTail`` and the kinds' one way to declare a request's arenas left
# the other thirteen letter for letter.
# The three ``latent_moe`` and the two ``nemotron_h`` digests were recorded
# again when ``RoutedExperts.route`` took its picks from
# ``moe_ops.k_largest`` (PR 62: passes of a first maximum or the sort over
# rows by ``select_form``, no gradient through either, and a grouped
# router's kept groups a compare where they were a scatter): the five
# programs that hold a router, and no other; the other nine kept theirs
# letter for letter.
RECORDED = {
    "gpt.decode": "3aafb0f57e8d64295ce268b7d45e62c31463373b34e36d3e30ea9871343f04a7",
    "gpt.prefill": "1878e51f7de936c6f1c483b255b386023f2cd48c08326fa40f65e2199d075cb3",
    "granite_hybrid.decode": "e53be8f475abf4dbb3c3f6ad8519f758191cbaffeb423b024159374e1cb795f7",
    "granite_hybrid.prefill": "b67646167b8a5d6226b90af276e2ce9befad6e662d067becd50ca1f6ec370113",
    "gpt.train": "b910d8faaa4dc59157d5baffb29bd2f5ec466eb49c9b8bed2e2fb14878d1376f",
    "hybrid.decode": "1ccca11bf7d46d6b6847716a414a3e25a563e2874e54dbb59ea32f0f8053fd95",
    "hybrid.prefill": "5dd6b2309d3ad4c5946d040785336ddaedeae8f84a69854a1c0fb4a3e549f238",
    "latent_moe.decode": "7c3054bc20767f73b0315bd46831536c11652c78d6e5c14ac8b7fc727c7504c9",
    "latent_moe.prefill": "b0b53bb7b566e0b97e328e83e659c62c3e7320cbb5c0c77341f9244e6d9d9722",
    "latent_moe.train": "3c74ffaba4145640497a6542b1baad7fe902ba06299a754d6bf920f28bcc6e79",
    "nemotron_h.decode": "deb0b2e34dc3a9db25bb8a0b83456408adb911dcba9dd5960fdf55741c396dac",
    "nemotron_h.prefill": "707922d11ce4a348231afa0e10c62b3191b313487c996349ce220e3b6702b036",
    "sparse_hybrid.decode": "80df1ca5e53b4103e5843bd02083672a838377c71953d5a1e2a7fc90798a354b",
    "sparse_hybrid.prefill": "8af30f7980a5ae6c8d6bc03e7fc11a1bf8b0b63173f63adc8bc10cad4bb2f962",
}


def _lowered(name: str) -> str:
    from flexflow_tpu.serving.kv_cache import Addresses

    model, program = name.split(".")
    build = zoo_smoke_builders()[model]

    def sds(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)

    if program == "train":
        ff = FFModel(FFConfig(batch_size=2, seed=0, ledger="off",
                              search_cache="off"))
        build(ff, 2)
        ff.compile(optimizer=AdamOptimizer(alpha=1e-3),
                   loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                   metrics=[MetricsType.SPARSE_CATEGORICAL_CROSSENTROPY])
        spec = [s for s in ff.compiled.audit_exec
                if s.name == "train_step"][0]
        args = list(spec.args)           # labels a token, not a row
        args[-1] = jax.ShapeDtypeStruct(
            tuple(ff.compiled.input_tensors[0].dims), jnp.int32)
        return spec.fn.lower(*args).as_text()
    ff = FFModel(FFConfig(batch_size=2, seed=0, ledger="off",
                          search_cache="off",
                          computation_mode=CompMode.INFERENCE))
    build(ff, 2)
    ff.compile(optimizer=None, loss_type=None, metrics=[])
    # (a sparse entry's pool takes its selection's block and no other)
    dec = PagedDecoder(ff, 32, decode_slots=2,
                       block_size=4 if model == "sparse_hybrid" else 8,
                       prefill_buckets=[16])
    lens = jax.ShapeDtypeStruct((2,), jnp.int32)

    def addr(n):
        return Addresses(
            jax.ShapeDtypeStruct((n, dec.max_blocks_per_request), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.int32)
            if dec.pool.num_rows else None)

    if program == "decode":
        return dec._decode.lower(
            dec._params_sds(), lens, sds(dec.pool.kv), addr(2), lens,
            sds(dec._expert_acc), lens,
            jax.ShapeDtypeStruct((2,), jnp.bool_)).as_text()
    return dec._prefill_fn(16, 1).lower(
        dec._params_sds(), jax.ShapeDtypeStruct((1, 16), jnp.int32),
        sds(dec.pool.kv), addr(1),
        jax.ShapeDtypeStruct((1,), jnp.int32)).as_text()


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_programs_without_a_state_lower_to_the_text_they_had(name):
    assert hashlib.sha256(_lowered(name).encode()).hexdigest() \
        == RECORDED[name]


if __name__ == "__main__":
    for name in sorted(RECORDED):
        print(f'    "{name}": '
              f'"{hashlib.sha256(_lowered(name).encode()).hexdigest()}",')
