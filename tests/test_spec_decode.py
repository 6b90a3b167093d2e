"""Speculative decoding + quantized paged KV (serving/generation.py
verify path + draft registration, serving/scheduler.py _spec_once,
serving/kv_cache.py int8/bf16 arenas).

The invariants that matter:

* greedy speculative output is EXACTLY the non-speculative output
  (np.array_equal) for every zoo causal LM, ragged arrivals included —
  the target's verify logits decide every token, the draft only
  prices the dispatch;
* temperature sampling uses the standard rejection-sampling correction
  with per-row seeded streams, so spec runs replay bit-identically;
* rejected suffixes roll the scatter cursor back without touching
  other slots; mid-flight deadline expiry and decode-worker crashes
  keep every accepted future resolving with speculation on;
* the int8 pool's calibration divergence gate (KVQ001) falls back
  LOUDLY to float32 when exceeded, and at equal pool bytes int8 admits
  >= 2x the worst-case requests float32 does;
* ``PagedKVPool.memory_bytes()`` and the sim's serving memory math
  agree byte-for-byte for every arena dtype.
"""

import time

import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.ffconst import CompMode, OpType
from flexflow_tpu.models import GPTConfig, build_gpt, zoo_smoke_builders
from flexflow_tpu.obs.metrics import metrics_registry
from flexflow_tpu.runtime import faults
from flexflow_tpu.serving import (ContinuousBatchingScheduler,
                                  DeadlineExceeded, InferenceEngine,
                                  PagedDecoder, PagedKVPool,
                                  build_draft_model)
from flexflow_tpu.serving.cache_entry import LatentEntry, PairEntry
from flexflow_tpu.sim import serving_kv_pool_bytes

V = 50
GCFG = GPTConfig(vocab_size=V, max_positions=32, hidden_size=32,
                 num_heads=4, num_layers=2)


@pytest.fixture(autouse=True)
def _clear_plan():
    yield
    faults.configure_faults(FFConfig(fault_plan=None))


def _gpt(**cfg_kw):
    cfg_kw.setdefault("ledger", "off")
    ff = FFModel(FFConfig(batch_size=4, seed=0,
                          computation_mode=CompMode.INFERENCE, **cfg_kw))
    build_gpt(ff, 4, 6, GCFG)
    ff.compile(optimizer=None, loss_type=None, metrics=[])
    return ff


@pytest.fixture(scope="module")
def gpt():
    return _gpt()


@pytest.fixture(scope="module")
def gpt_draft(gpt):
    return build_draft_model(gpt, "self:1")


def _serve(ff, reqs, *, sched_kw=None, seeds=True, temperature=0.0):
    """Ragged-arrival serve: submit in waves of 3 with result() joins
    in between, so the in-flight mix churns slots mid-decode."""
    eng = InferenceEngine()
    kw = {"decode_slots": 3, "block_size": 8, "max_length": 32}
    kw.update(sched_kw or {})
    eng.register_generator(ff, name="lm", **kw)
    futs = []
    outs = [None] * len(reqs)
    for i, (prompt, m) in enumerate(reqs):
        futs.append(eng.generate_async(
            "lm", prompt, m, temperature=temperature,
            **({"seed": 1000 + i} if seeds else {})))
        if i % 3 == 2:
            outs[i - 2] = futs[i - 2].result(timeout=120)
    for i, f in enumerate(futs):
        if outs[i] is None:
            outs[i] = f.result(timeout=120)
    eng.stop()
    return outs


# ------------------------------------------ greedy == non-spec (per zoo)
def test_spec_greedy_identical_per_zoo_causal_lm():
    """For EVERY zoo causal LM: the engine with a draft + spec_k must
    emit exactly the tokens the plain engine emits under greedy
    sampling, ragged arrivals included. The draft here is a fresh
    1-layer random GPT — terrible acceptance, identical output: the
    target's verify rows decide every token."""
    covered = []
    for name, build in zoo_smoke_builders().items():
        probe = FFModel(FFConfig(batch_size=4,
                                 computation_mode=CompMode.INFERENCE,
                                 ledger="off"))
        build(probe, 4)
        if not any(layer.op_type is OpType.MULTIHEAD_ATTENTION
                   and layer.attrs.get("causal")
                   and len({t.tensor_id for t in layer.inputs}) == 1
                   for layer in probe.layers):
            continue
        if any(layer.op_type in (OpType.GATED_DELTA_NET, OpType.MAMBA2)
               for layer in probe.layers):
            # a state cannot be rolled back: such a model refuses spec_k
            # at construction (tests/test_hybrid_lm.py,
            # tests/test_nemotron_h_lm.py)
            continue
        if any(layer.attrs.get("window") for layer in probe.layers):
            # nor can a ring, whose rejected rows overwrote its oldest
            # (tests/test_trinity_lm.py)
            continue
        probe.compile(optimizer=None, loss_type=None, metrics=[])
        vocab = int(probe.compiled.logits_tensor.dims[-1])
        rng = np.random.default_rng(3)
        reqs = [(rng.integers(0, vocab, (n,)).astype(np.int32), m)
                for n, m in [(3, 6), (5, 2), (2, 7), (4, 4), (2, 5),
                             (6, 3)]]
        draft = build_draft_model(probe,
                                  "gpt:layers=1,hidden=32,heads=4")
        base = _serve(probe, reqs)
        spec = _serve(probe, reqs,
                      sched_kw={"draft_ff": draft, "spec_k": 3})
        for b, s in zip(base, spec):
            np.testing.assert_array_equal(b, s)
        covered.append(name)
    assert covered, "no causal LM in the zoo?"


def test_spec_self_draft_greedy_identical_and_counts(gpt, gpt_draft):
    """self:1 draft (shared weights): still bit-identical greedy, and
    the spec ledger counts hang together — one verify dispatch per
    round, k proposals per slot-round, emitted tokens equal the
    requested totals."""
    rng = np.random.default_rng(11)
    reqs = [(rng.integers(0, V, (n,)).astype(np.int32), m)
            for n, m in [(3, 6), (6, 2), (2, 9), (5, 1), (4, 7)]]
    base = _serve(gpt, reqs)
    sched = ContinuousBatchingScheduler(gpt, max_length=32,
                                        decode_slots=3, block_size=8,
                                        draft_ff=gpt_draft, spec_k=3)
    futs = [sched.submit(p, m, seed=1000 + i)
            for i, (p, m) in enumerate(reqs)]
    outs = [f.result(timeout=120) for f in futs]
    stats = sched.stats()
    sched.stop()
    for b, s in zip(base, outs):
        np.testing.assert_array_equal(b, s)
    sp = stats["spec"]
    assert sp["k"] == 3
    assert sp["rounds"] > 0
    # one verify (= decode) dispatch per round: the scheduler's rounds
    # are exactly the target decoder's dispatches
    assert stats["decode_steps"] == stats["decode_dispatches"]
    assert sp["rounds"] == stats["decode_dispatches"]
    assert sp["proposed"] == 3 * sp["slot_rounds"]
    # the first token of each request comes from prefill; everything
    # after rides a spec round
    assert sp["emitted"] == sum(m for _, m in reqs) - len(reqs)
    assert 0.0 <= sp["accept_rate"] <= 1.0
    assert 1.0 <= sp["tokens_per_dispatch"] <= 4.0
    assert stats["knobs"]["spec_k"] == 3


def test_spec_requires_draft_loudly(gpt):
    with pytest.raises(ValueError, match="draft"):
        ContinuousBatchingScheduler(gpt, max_length=32, decode_slots=2,
                                    block_size=8, spec_k=2)


def test_generation_instance_accepts_draft_spec_string(gpt):
    """The user-facing seam: an explicit ``draft_ff="self:1"`` keyword
    resolves the spec string through build_draft_model exactly like the
    serving_draft_model config knob does — no pre-built model needed."""
    from flexflow_tpu.serving import GenerationInstance

    inst = GenerationInstance(gpt, decode_slots=2, block_size=8,
                              max_length=32, spec_k=2, draft_ff="self:1")
    try:
        out = np.asarray(inst.generate([7, 3, 11], max_new_tokens=4,
                                       temperature=0.0))
        assert out.shape[-1] >= 4
        assert (inst.stats().get("spec") or {}).get("rounds")
    finally:
        inst.stop()


# ------------------------------------------- seeded temperature replay
def test_spec_rejection_sampling_seeded_replay(gpt, gpt_draft):
    """Temperature sampling through the rejection-correction path must
    REPLAY: same seeds, same arrival order -> bit-identical outputs
    across two full engine sessions."""
    rng = np.random.default_rng(5)
    reqs = [(rng.integers(0, V, (n,)).astype(np.int32), m)
            for n, m in [(3, 6), (4, 4), (2, 8), (5, 3)]]
    kw = {"draft_ff": gpt_draft, "spec_k": 2}
    a = _serve(gpt, reqs, sched_kw=kw, temperature=0.8)
    b = _serve(gpt, reqs, sched_kw=kw, temperature=0.8)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    # and the sampler really sampled (greedy run differs somewhere)
    g = _serve(gpt, reqs, sched_kw=kw, temperature=0.0)
    assert any(not np.array_equal(x, y) for x, y in zip(a, g))


# ---------------------------------------- rollback under deadline/crash
def test_spec_deadline_mid_flight_rejected_before_next_round(gpt,
                                                             gpt_draft):
    """An ACTIVE request whose deadline passes with speculation on is
    rejected before the next spec round, its blocks freed, other slots
    untouched (white-box: drive _decode_once directly)."""
    sched = ContinuousBatchingScheduler(gpt, max_length=32,
                                        decode_slots=2, block_size=8,
                                        draft_ff=gpt_draft, spec_k=2)
    from flexflow_tpu.serving.scheduler import GenerationRequest

    doomed = GenerationRequest(0, np.zeros(3, np.int32), 8, 0.0, 0,
                               None, deadline_s=0.01)
    doomed.table = sched.decoder.pool.try_admit(3 + 8)
    sched._prefill_group([(0, doomed)], sched.decoder.bucket_for(3))
    live = GenerationRequest(1, np.ones(3, np.int32), 4, 0.0, 0, None,
                             deadline_s=None)
    live.table = sched.decoder.pool.try_admit(3 + 4)
    sched._prefill_group([(1, live)], sched.decoder.bucket_for(3))
    time.sleep(0.02)  # deadline passes mid-flight
    before = sched.decoder.pool.in_use()
    sched._decode_once()
    with pytest.raises(DeadlineExceeded, match="mid-decode"):
        doomed.future.result(timeout=5)
    # the doomed slot's blocks are back; the live one kept decoding
    assert sched.decoder.pool.in_use() < before
    with sched._mu:
        assert sched._slots[0] is None
        assert sched._slots[1] is live
    assert len(live.tokens) > 1
    sched.stop()


def test_spec_crashed_worker_respawns_futures_resolve(gpt, gpt_draft):
    """serving.worker fault mid-session with speculation ON: the decode
    worker crashes between spec rounds, respawns, and every accepted
    future resolves to the exact non-speculative tokens — the rollback
    bookkeeping (seq_len advanced atomically with each commit) leaves
    nothing half-accepted for the respawned worker to trip on."""
    base = _serve(gpt, [(np.full(3, 7, np.int32), 8),
                        (np.full(4, 9, np.int32), 6),
                        (np.full(2, 4, np.int32), 7)])
    plan = {"schema": 1, "sites": {"serving.worker":
                                   {"at_step": 3, "max_fires": 1}}}
    faults.configure_faults(FFConfig(fault_plan=plan))
    before = metrics_registry().counter("serving.worker_respawns").value
    sched = ContinuousBatchingScheduler(gpt, max_length=32,
                                        decode_slots=3, block_size=8,
                                        draft_ff=gpt_draft, spec_k=2,
                                        worker_retry_budget=2)
    futs = [sched.submit(np.full(3, 7, np.int32), 8, seed=1000),
            sched.submit(np.full(4, 9, np.int32), 6, seed=1001),
            sched.submit(np.full(2, 4, np.int32), 7, seed=1002)]
    outs = [f.result(timeout=120) for f in futs]
    sched.stop()
    faults.configure_faults(FFConfig(fault_plan=None))
    assert metrics_registry().counter(
        "serving.worker_respawns").value > before
    for out, ref in zip(outs, base):
        np.testing.assert_array_equal(out, ref)


# --------------------------------------------- quantized KV: gate + math
def test_kv_int8_within_budget_stays_quantized(gpt):
    dec = PagedDecoder(gpt, max_length=32, decode_slots=2, block_size=8,
                       kv_dtype="int8")
    assert dec.kv_dtype == "int8"
    assert dec.kv_quant_report is None
    assert dec.kv_divergence is not None
    assert dec.kv_divergence <= dec.kv_divergence_budget == 0.05
    assert dec.pool.stats()["kv_dtype"] == "int8"


def test_kv_divergence_budget_fires_loud_fallback(gpt, capsys):
    """An impossible budget: the calibration gate must fall back to
    float32 arenas LOUDLY — stderr line, KVQ001 finding, fallback
    counter — never serve silently degraded logits."""
    before = metrics_registry().counter(
        "serving.kv_dtype_fallbacks").value
    dec = PagedDecoder(gpt, max_length=32, decode_slots=2, block_size=8,
                       kv_dtype="int8", kv_divergence_budget=1e-9)
    assert dec.kv_dtype == "float32"
    assert dec.pool.stats()["kv_dtype"] == "float32"
    assert dec.kv_divergence is not None and dec.kv_divergence > 1e-9
    assert dec.kv_quant_report is not None
    assert any(f.code == "KVQ001" for f in dec.kv_quant_report.warnings)
    assert metrics_registry().counter(
        "serving.kv_dtype_fallbacks").value == before + 1
    assert "KVQ001" in capsys.readouterr().err
    # the fallback pool still serves: a quick greedy decode works
    table = dec.pool.try_admit(3 + 2)
    logits = dec.prefill(np.zeros(3, np.int32) + 1, table)
    tok = int(np.argmax(logits))
    dec.decode(np.array([tok], np.int32) * np.ones(2, np.int32),
               np.stack([table, np.zeros_like(table)]),
               np.array([3, 0], np.int32))
    dec.pool.free(table)


def test_kv_scheduler_stats_carry_divergence(gpt):
    sched = ContinuousBatchingScheduler(gpt, max_length=32,
                                        decode_slots=2, block_size=8,
                                        kv_dtype="int8")
    fut = sched.submit(np.zeros(3, np.int32), 4)
    fut.result(timeout=120)
    stats = sched.stats()
    sched.stop()
    assert stats["kv"]["kv_dtype"] == "int8"
    assert stats["kv"]["quant_fallback"] is False
    assert isinstance(stats["kv"]["divergence"], float)
    assert stats["knobs"]["kv_dtype"] == "int8"


def test_admission_doubles_at_fixed_pool_bytes():
    """The tentpole's capacity claim, as arithmetic: pick the largest
    int8 pool that fits the float32 pool's byte budget — it must admit
    >= 2x the worst-case requests."""
    specs = {"a": PairEntry(4, 8), "b": PairEntry(4, 8)}
    bs, max_len = 8, 32
    n_f32 = 13
    budget = serving_kv_pool_bytes(specs, n_f32, bs, "float32")
    n_q = n_f32
    while serving_kv_pool_bytes(specs, n_q + 1, bs, "int8") <= budget:
        n_q += 1

    def admissible(dtype, nb):
        pool = PagedKVPool(specs, num_blocks=nb, block_size=bs,
                           max_blocks_per_request=max_len // bs,
                           kv_dtype=dtype)
        n = 0
        while True:
            try:
                if pool.try_admit(max_len) is None:
                    break
            except Exception:  # noqa: BLE001 — exhausted
                break
            n += 1
        return n

    a32, a8 = admissible("float32", n_f32), admissible("int8", n_q)
    assert a8 >= 2 * a32, (a8, a32, n_f32, n_q)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
def test_pool_bytes_parity_with_sim(dtype):
    """PagedKVPool.memory_bytes() and the sim's serving memory math are
    one arithmetic (the capacity planner prices admission off the sim
    numbers), and it counts the bytes of the arenas the pool holds."""
    specs = {"l0": PairEntry(4, 8), "l1": PairEntry(2, 16),
             "l2": LatentEntry(24)}
    if dtype == "int8":
        del specs["l2"]             # a latent row has no int8 form
    pool = PagedKVPool(specs, num_blocks=9, block_size=8,
                       max_blocks_per_request=4, kv_dtype=dtype)
    assert pool.memory_bytes() == serving_kv_pool_bytes(
        specs, 9, 8, dtype) == sum(a.nbytes for entry in pool.kv.values()
                                   for a in entry)
    if dtype == "int8":
        # scale/zero sidecars included, still at most half of f32
        assert pool.memory_bytes() <= serving_kv_pool_bytes(
            specs, 9, 8, "float32") // 2
