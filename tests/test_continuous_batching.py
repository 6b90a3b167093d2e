"""Continuous batching + paged KV cache (serving/scheduler.py,
serving/kv_cache.py, PagedDecoder in serving/generation.py).

The invariants that matter:

* the paged decode path is BIT-IDENTICAL to the dense cache decode path
  for the same request set, per zoo causal-LM model;
* the continuous-batching engine produces exactly the tokens sequential
  static-batch serving produces under a seeded sampler, regardless of
  arrival order / in-flight mix;
* one decode dispatch per step, auditor-clean with the pool donated;
* PR 11 degradation semantics survive the new engine: bounded shed with
  the kv pool as the binding constraint, deadline rejects before the
  next decode step, crashed decode workers respawn with every accepted
  future resolving.
"""

import time

import numpy as np
import pytest

import jax.numpy as jnp

from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.ffconst import CompMode, OpType
from flexflow_tpu.models import GPTConfig, build_gpt, zoo_smoke_builders
from flexflow_tpu.obs.metrics import metrics_registry
from flexflow_tpu.runtime import faults
from flexflow_tpu.serving import (ContinuousBatchingScheduler,
                                  DeadlineExceeded, Generator,
                                  InferenceEngine, PagedDecoder, ShedError)

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


# ------------------------------------------- paged == dense (same arithmetic)
def _assert_same_arithmetic(a, b, what):
    """Two float32 programs of DIFFERENT shapes computing the same sums
    (a paged pool against a dense cache, one prefill row against a
    padded group of them): XLA orders a reduction by its shape, so the
    last bits differ (5e-7 of the largest logit read here) and
    ``np.array_equal`` between them was never sound; bit-identity stays
    where ONE program runs twice. The bound is 1e-4 of the largest
    logit: 200 times what reordering moves, and a fortieth of bfloat16's
    2**-8, so a bf16 arena, cast or accumulator anywhere in the path
    still fails it."""
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    assert a.shape == b.shape, f"{what}: {a.shape} vs {b.shape}"
    bound = 1e-4 * float(np.abs(a).max())
    worst = float(np.abs(a - b).max())
    assert worst <= bound, f"{what}: off by {worst:.3e} > {bound:.3e}"


def test_paged_decode_bit_identical_per_zoo_causal_lm():
    """For EVERY zoo model that is a causal LM, prefill and decode
    logits through the paged pool must equal the dense cache path to
    float32 reordering (:func:`_assert_same_arithmetic`), and the same
    paged program run twice on the same pool bit for bit."""
    covered = []
    for name, build in zoo_smoke_builders().items():
        probe = FFModel(FFConfig(batch_size=4,
                                 computation_mode=CompMode.INFERENCE,
                                 ledger="off"))
        build(probe, 4)
        if not any((layer.op_type is OpType.MULTIHEAD_ATTENTION
                    and layer.attrs.get("causal")
                    and len({t.tensor_id for t in layer.inputs}) == 1)
                   or layer.op_type in (OpType.LATENT_ATTENTION,
                                        OpType.GATED_DELTA_NET)
                   for layer in probe.layers):
            continue  # not a causal LM — the generator would reject it
        probe.compile(optimizer=None, loss_type=None, metrics=[])
        vocab = probe.compiled.logits_tensor.dims[-1]
        max_len = 32
        gen = Generator(probe, max_length=max_len, batch_size=4)
        dec = PagedDecoder(probe, max_length=max_len, decode_slots=4,
                           block_size=8)
        # a step over a per-request state advances it: that program run
        # twice is two steps, not one step twice
        repeats = not any(k.keeps_row for k in dec.pool.kinds.values())
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, vocab, (n,)).astype(np.int32)
                   for n in (3, 6, 2, 5)]
        for slot, prompt in enumerate(prompts):
            dense_last, cache, pos = gen.prefill(prompt[None, :])
            table = dec.pool.try_admit(prompt.size + 4)
            paged_last = dec.prefill(prompt, table)
            _assert_same_arithmetic(
                np.asarray(dense_last)[0], paged_last,
                f"{name}: prefill logits (slot {slot})")
            # two decode steps, teacher-forced on the dense argmax
            nxt = int(np.asarray(dense_last)[0].argmax())
            tables = np.zeros((4, dec.max_blocks_per_request), np.int32)
            tables[0] = table
            seq_lens = np.zeros(4, np.int32)
            for step in range(2):
                seq_lens[0] = prompt.size + step
                toks = np.zeros(4, np.int32)
                toks[0] = nxt
                paged = dec.decode(toks, tables, seq_lens)[0]
                step_tokens = np.zeros((4, 1), np.int32)
                step_tokens[0, 0] = nxt
                dense, cache = gen._step(
                    gen._exec_params(), jnp.asarray(step_tokens), cache,
                    jnp.int32(prompt.size + step))
                dense = np.asarray(dense)[0, -1]
                _assert_same_arithmetic(
                    dense, paged, f"{name}: decode step {step} logits")
                # one program, twice: the step rewrites the row it
                # wrote, so the pool and the logits repeat bit for bit
                if repeats:
                    again = dec.decode(toks, tables, seq_lens)[0]
                    assert np.array_equal(paged, again), \
                        f"{name}: decode step {step} does not repeat"
                nxt = int(dense.argmax())
            dec.pool.free(table)
        covered.append(name)
    assert {"gpt", "latent_moe", "hybrid"} <= set(covered), \
        f"zoo causal-LM sweep covered {covered}"


def test_paged_decoder_audit_clean_with_donated_pool(gpt):
    """The paged decode executable passes the program auditor (default
    audit_programs='error' raised nothing at construction) with the
    pool donated."""
    dec = PagedDecoder(gpt, max_length=32, decode_slots=4, block_size=8)
    assert dec.audit_report is not None
    assert dec.audit_report.errors == []
    assert "serving.paged_decode_step" in dec.audit_report.programs


# ------------------------------------- engine == sequential (seeded sampler)
def _reference_rows(ff, reqs, temperature):
    """Sequential static-batch reference: each request decoded alone
    through the DENSE generator with its own seed."""
    gen = Generator(ff, max_length=32)
    out = []
    for i, (prompt, m) in enumerate(reqs):
        out.append(gen.generate(prompt[None, :], m,
                                temperature=temperature,
                                seed=[1000 + i])[0])
    return out


@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_engine_tokens_equal_sequential_static_batch(gpt, temperature):
    """Ragged arrivals, heterogeneous prompt/generation lengths, an
    in-flight mix that churns slots — the engine must produce exactly
    the tokens sequential serving produces, because batching strategy
    must never change results."""
    rng = np.random.default_rng(7)
    reqs = [(rng.integers(0, V, (n,)).astype(np.int32), m)
            for n, m in [(3, 6), (6, 2), (2, 9), (5, 1), (4, 7), (2, 3),
                         (3, 5), (6, 4)]]
    eng = InferenceEngine()
    eng.register_generator(gpt, name="lm", decode_slots=3, block_size=8,
                           max_length=32)
    futs = []
    for i, (prompt, m) in enumerate(reqs):
        futs.append(eng.generate_async("lm", prompt, m,
                                       temperature=temperature,
                                       seed=1000 + i))
        if i % 3 == 2:
            time.sleep(0.002)  # ragged arrival
    outs = [f.result(timeout=120) for f in futs]
    eng.stop()
    for out, ref in zip(outs, _reference_rows(gpt, reqs, temperature)):
        np.testing.assert_array_equal(out, ref)


@pytest.fixture(scope="module")
def hybrid():
    """The zoo's toy of gated-delta-rule layers among full-attention
    ones: a state a request beside the (k, v) rows a token."""
    ff = FFModel(FFConfig(batch_size=4, seed=0, ledger="off",
                          computation_mode=CompMode.INFERENCE))
    zoo_smoke_builders()["hybrid"](ff, 4)
    ff.compile(optimizer=None, loss_type=None, metrics=[])
    return ff


@pytest.mark.parametrize("temperature,group", [(0.0, 1), (0.8, 1),
                                                (0.0, 4)])
def test_engine_over_a_state_kind_equals_sequential(hybrid, temperature,
                                                    group):
    """The same promise over a mixed pool: eight requests churn three
    slots (each slot's row serves several requests in turn), greedy and
    sampled, admitted one prompt a dispatch and in padded groups of up
    to four (whose dummy rows land on the null row)."""
    vocab = int(hybrid.compiled.logits_tensor.dims[-1])
    rng = np.random.default_rng(17)
    reqs = [(rng.integers(0, vocab, (n,)).astype(np.int32), m)
            for n, m in [(3, 6), (6, 2), (2, 9), (5, 1), (4, 7), (2, 3),
                         (3, 5), (6, 4)]]
    sched = ContinuousBatchingScheduler(
        hybrid, max_length=32, decode_slots=3, block_size=8,
        max_prefills_per_step=group, prefill_token_budget=32 * group)
    futs = [sched.submit(p, m, temperature=temperature, seed=1000 + i)
            for i, (p, m) in enumerate(reqs)]
    outs = [f.result(timeout=120) for f in futs]
    stats = sched.stats()
    sched.stop()
    for out, ref in zip(outs, _reference_rows(hybrid, reqs, temperature)):
        np.testing.assert_array_equal(out, ref)
    state = stats["kv"]["state"]
    assert state["in_use"] == 0 and 1 <= state["high_water"] <= 3
    assert stats["kv"]["in_use"] == 0
    assert state["rows_stepped"] == 3 * stats["kv"]["blocks_in_tables"] \
        // sched.decoder.max_blocks_per_request
    assert stats["decode_steps"] == stats["decode_dispatches"]
    ahead = stats["loop"]["ahead"]
    assert (ahead["steps_ahead"] > 0) == (temperature == 0.0)


def test_eos_retires_early(gpt):
    """An eos sample retires the request exactly like the dense
    generator's forced-eos early stop."""
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, V, (4,)).astype(np.int32)
    gen = Generator(gpt, max_length=32)
    # pick the greedy token at step 0 as the eos id: the engine must
    # stop right after emitting it
    ref = gen.generate(prompt[None, :], 6)[0]
    eos = int(ref[prompt.size])
    sched = ContinuousBatchingScheduler(gpt, max_length=32,
                                        decode_slots=2, block_size=8)
    out = sched.generate(prompt, 6, eos_id=eos)
    sched.stop()
    assert out.tolist() == list(prompt) + [eos]


def test_one_dispatch_per_step_regardless_of_mix(gpt):
    sched = ContinuousBatchingScheduler(gpt, max_length=32,
                                        decode_slots=4, block_size=8,
                                        max_prefills_per_step=4)
    rng = np.random.default_rng(5)
    futs = [sched.submit(rng.integers(0, V, (n,)).astype(np.int32), m)
            for n, m in [(2, 8), (5, 2), (3, 6), (6, 3), (4, 4)]]
    for f in futs:
        f.result(timeout=120)
    stats = sched.stats()
    sched.stop()
    assert stats["decode_steps"] == stats["decode_dispatches"]
    assert stats["decode_steps"] >= 7  # longest request decodes 7 steps
    # in-flight batching: strictly fewer decode steps than sequential
    assert stats["decode_steps"] < sum(m - 1 for m in (8, 2, 6, 3, 4))


def test_stats_kv_counts_the_share_of_its_tables_a_step_reads(gpt):
    """``stats()["kv"]``: which reader the decode program took (the toy
    width is no whole lane tile, so the gather) and, summed over decode
    steps, the blocks that hold the active slots' tokens against the
    blocks their tables span."""
    sched = ContinuousBatchingScheduler(gpt, max_length=32,
                                        decode_slots=2, block_size=8)
    prompt = np.arange(5, dtype=np.int32)
    sched.generate(prompt, 6)   # one slot active: 5 decode steps
    kv = sched.stats()["kv"]
    sched.stop()
    assert kv["attention_path"] == {"decode": "gather", "chunk": None,
                                    "decode_chunk_tokens": None}
    # seq_len 5..9 plus the row the step writes: one block at 5..7 (6..8
    # tokens), two at 8..9 (9..10 tokens); a table spans 32 / 8 = 4
    assert kv["blocks_read"] == 3 * 1 + 2 * 2
    assert kv["blocks_in_tables"] == 5 * 4


# -------------------------------------------- token-budget prefill batching
def test_prefill_many_bit_identical_to_single_path(gpt):
    """Multi-prompt bucketed prefill: each prompt's last-position logits
    through one batched dispatch must equal the single-prompt prefill
    path to float32 reordering (two programs of different widths:
    :func:`_assert_same_arithmetic`; rows are independent — batched
    dense causal attention, per-row block-table scatter, dummy rows
    write the null block)."""
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, V, (n,)).astype(np.int32)
               for n in (3, 6, 2, 5, 4)]
    one = PagedDecoder(gpt, max_length=32, decode_slots=8, block_size=8)
    many = PagedDecoder(gpt, max_length=32, decode_slots=8, block_size=8)
    singles, tabs_one, tabs_many = [], [], []
    for p in prompts:
        tabs_one.append(one.pool.try_admit(p.size + 2))
        singles.append(one.prefill(p, tabs_one[-1]))
        tabs_many.append(many.pool.try_admit(p.size + 2))
    batched = many.prefill_many(prompts, tabs_many)
    assert len(batched) == len(prompts)
    for i, (s, b) in enumerate(zip(singles, batched)):
        _assert_same_arithmetic(s, b, f"prompt {i} prefill logits")
    # the batched path wrote the SAME kv pool contents for each request:
    # a decode step (one program for both) after either prefill agrees
    seq_lens = np.zeros(8, np.int32)
    toks = np.zeros(8, np.int32)
    tables_one = np.zeros((8, one.max_blocks_per_request), np.int32)
    tables_many = np.zeros((8, many.max_blocks_per_request), np.int32)
    for i, p in enumerate(prompts):
        seq_lens[i] = p.size
        toks[i] = int(batched[i].argmax())
        tables_one[i], tables_many[i] = tabs_one[i], tabs_many[i]
    d_one = one.decode(toks, tables_one, seq_lens)
    d_many = many.decode(toks, tables_many, seq_lens)
    _assert_same_arithmetic(d_one[:len(prompts)], d_many[:len(prompts)],
                            "decode after prefill")
    # one executable per (bucket, width) — the seen-set that makes an
    # unseen shape a counted compile miss
    assert all(w > 1 for (_b, w) in many._prefill_fns)
    assert all(w == 1 for (_b, w) in one._prefill_fns)


def test_prefill_fetches_each_rows_last_position_only(gpt, monkeypatch):
    """What a prefill hands the host is (rows, vocab) — each prompt's
    last position, which is all a caller reads — not the bucket's
    (rows, bucket, vocab); and it is the dense forward's row."""
    dec = PagedDecoder(gpt, max_length=32, decode_slots=4, block_size=8)
    fetched = []
    fetch = dec._fetch
    monkeypatch.setattr(dec, "_fetch",
                        lambda a: fetched.append(a.shape) or fetch(a))
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, V, (n,)).astype(np.int32) for n in (5, 9, 3)]
    tabs = [dec.pool.try_admit(p.size + 1) for p in prompts]
    rows = dec.prefill_many(prompts, tabs)
    assert fetched == [(4, V)] and rows.shape == (3, V)
    for p, row in zip(prompts, rows):
        want = dec._dense_reference_logits(p)[-1]
        np.testing.assert_allclose(row, want, rtol=2e-5, atol=2e-5)


def test_token_budget_scheduler_batches_prefills_same_tokens(gpt):
    """prefill_token_budget>0: the scheduler admits >1 queued prompt per
    bucketed prefill dispatch under the token budget, generating exactly
    the tokens the single-prefill path generates, with one decode
    dispatch per step preserved."""
    rng = np.random.default_rng(9)
    reqs = [(rng.integers(0, V, (n,)).astype(np.int32), m)
            for n, m in [(2, 4), (5, 3), (3, 4), (6, 2), (4, 3),
                         (2, 3), (7, 2), (3, 3)]]

    def run(**kw):
        sched = ContinuousBatchingScheduler(gpt, max_length=32,
                                            decode_slots=8, block_size=8,
                                            max_prefills_per_step=8, **kw)
        futs = [sched.submit(p, m, seed=100 + i)
                for i, (p, m) in enumerate(reqs)]
        outs = [f.result(timeout=120).tolist() for f in futs]
        stats = sched.stats()
        sched.stop()
        return outs, stats

    base_outs, base = run()
    tb_outs, tb = run(prefill_token_budget=16)
    assert tb_outs == base_outs
    # decode loop untouched: one dispatch per step in both modes
    assert base["decode_steps"] == base["decode_dispatches"]
    assert tb["decode_steps"] == tb["decode_dispatches"]
    # the budget path batched: fewer dispatches than prompts (the first
    # prefill compiles while the rest of the burst queues up)
    assert base["prefill_dispatches"] == base["prefill_prompts"] == 8
    assert tb["prefill_prompts"] == 8
    assert tb["prefill_dispatches"] < 8
    # the knob is only stamped on the record when it is on
    assert "prefill_token_budget" not in base["knobs"]
    assert tb["knobs"]["prefill_token_budget"] == 16


# ------------------------------------------------- degradation semantics
def test_burst_sheds_with_kv_pool_as_binding_constraint(gpt):
    """A burst past admission_limit sheds; the pool (2 worst-case
    requests) is what makes the queue back up."""
    sched = ContinuousBatchingScheduler(
        gpt, max_length=32, decode_slots=4, block_size=8,
        num_blocks=9,  # capacity 8 = two 4-block worst cases
        admission_limit=2)
    rng = np.random.default_rng(11)
    accepted, shed = [], 0
    for i in range(10):
        try:
            accepted.append(sched.submit(
                rng.integers(0, V, (4,)).astype(np.int32), 20))
        except ShedError:
            shed += 1
    assert shed > 0, "burst past the bound must shed"
    outs = [f.result(timeout=120) for f in accepted]
    assert all(o.shape == (24,) for o in outs)
    stats = sched.stats()
    sched.stop()
    assert stats["shed"] == shed
    assert stats["kv"]["high_water"] <= stats["kv"]["capacity_blocks"]
    # a request that can NEVER fit sheds immediately, even on an idle pool
    sched2 = ContinuousBatchingScheduler(gpt, max_length=32,
                                         decode_slots=2, block_size=8,
                                         num_blocks=3)
    with pytest.raises(ShedError, match="exceeds the whole pool"):
        sched2.submit(np.zeros(8, np.int32), 20)
    sched2.stop()


def test_deadline_expired_rejected_before_pickup(gpt):
    """Queue-expired requests reject fast at pickup (PR 11 semantics):
    a long-running request holds the only pool slot, so the deadlined
    request expires while queued."""
    sched = ContinuousBatchingScheduler(
        gpt, max_length=32, decode_slots=1, block_size=8,
        num_blocks=5)  # one worst-case request at a time
    rng = np.random.default_rng(13)
    long_f = sched.submit(rng.integers(0, V, (4,)).astype(np.int32), 24)
    dead_f = sched.submit(rng.integers(0, V, (4,)).astype(np.int32), 2,
                          deadline_s=0.0005)
    with pytest.raises(DeadlineExceeded):
        dead_f.result(timeout=120)
    assert long_f.result(timeout=120).shape == (28,)
    stats = sched.stats()
    sched.stop()
    assert stats["deadline_rejects"] == 1
    assert stats["kv"]["in_use"] == 0  # everything freed


def test_deadline_expired_mid_flight_rejected_before_next_step(gpt):
    """An ACTIVE request whose deadline passes is rejected before its
    next decode step, its blocks freed (white-box: drive _decode_once
    directly so the expiry is deterministic)."""
    sched = ContinuousBatchingScheduler(gpt, max_length=32,
                                        decode_slots=2, block_size=8)
    from flexflow_tpu.serving.scheduler import GenerationRequest

    req = GenerationRequest(0, np.zeros(3, np.int32), 8, 0.0, 0, None,
                            deadline_s=0.01)
    req.table = sched.decoder.pool.try_admit(3 + 8)
    sched._prefill_group([(0, req)], sched.decoder.bucket_for(3))
    time.sleep(0.02)  # deadline passes mid-flight
    sched._decode_once()
    with pytest.raises(DeadlineExceeded, match="mid-decode"):
        req.future.result(timeout=5)
    assert sched.decoder.pool.in_use() == 0
    with sched._mu:
        assert sched._slots[0] is None
    sched.stop()


def test_crashed_decode_worker_respawns_futures_resolve(gpt):
    """serving.worker fault mid-session: the decode worker crashes,
    respawns under the budget, and every accepted future still
    resolves to the exact sequential-reference tokens."""
    plan = {"schema": 1, "sites": {"serving.worker":
                                   {"at_step": 3, "max_fires": 1}}}
    faults.configure_faults(FFConfig(fault_plan=plan))
    before = metrics_registry().counter("serving.worker_respawns").value
    sched = ContinuousBatchingScheduler(gpt, max_length=32,
                                        decode_slots=2, block_size=8,
                                        worker_retry_budget=2)
    rng = np.random.default_rng(17)
    reqs = [(rng.integers(0, V, (n,)).astype(np.int32), m)
            for n, m in [(3, 6), (4, 4), (2, 5)]]
    futs = [sched.submit(p, m, seed=1000 + i)
            for i, (p, m) in enumerate(reqs)]
    outs = [f.result(timeout=120) for f in futs]
    sched.stop()
    faults.configure_faults(FFConfig(fault_plan=None))
    assert metrics_registry().counter(
        "serving.worker_respawns").value > before
    for out, ref in zip(outs, _reference_rows(gpt, reqs, 0.0)):
        np.testing.assert_array_equal(out, ref)


def test_respawn_budget_exhausted_fails_loudly(gpt):
    """Past the budget every accepted future resolves with the abandon
    error and the breaker sheds new admissions."""
    plan = {"schema": 1, "sites": {"serving.worker": {"p": 1.0}}}
    faults.configure_faults(FFConfig(fault_plan=plan))
    sched = ContinuousBatchingScheduler(gpt, max_length=32,
                                        decode_slots=2, block_size=8,
                                        worker_retry_budget=1)
    fut = sched.submit(np.zeros(3, np.int32), 4)
    with pytest.raises(RuntimeError, match="respawn budget"):
        fut.result(timeout=120)
    faults.configure_faults(FFConfig(fault_plan=None))
    with pytest.raises(ShedError):
        sched.submit(np.zeros(3, np.int32), 4)
    assert sched.decoder.pool.in_use() == 0
    sched.stop()


def test_breaker_opens_on_consecutive_decode_failures(gpt, monkeypatch):
    sched = ContinuousBatchingScheduler(gpt, max_length=32,
                                        decode_slots=2, block_size=8,
                                        breaker_threshold=2,
                                        breaker_cooldown_s=30.0,
                                        worker_retry_budget=0)
    for way in ("decode", "decode_ahead"):  # the pass's two ways to step
        monkeypatch.setattr(sched.decoder, way,
                            lambda *a, **k: (_ for _ in ()).throw(
                                RuntimeError("wedged device")))
    futs = [sched.submit(np.zeros(3, np.int32), 4) for _ in range(2)]
    for f in futs:
        with pytest.raises(RuntimeError, match="wedged"):
            f.result(timeout=120)
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        try:
            sched.submit(np.zeros(3, np.int32), 4)
        except ShedError:
            break
        time.sleep(0.02)
    else:
        pytest.fail("breaker never opened")
    sched.stop()


# ------------------------------------------------- generator registration
def test_engine_registration_and_restart(gpt):
    eng = InferenceEngine()
    eng.register_generator(gpt, name="lm", decode_slots=2, block_size=8,
                           max_length=32)
    assert eng.generators() == ["lm"]
    with pytest.raises(ValueError, match="already registered"):
        eng.register_generator(gpt, name="lm")
    # the collision check is bidirectional: a classic instance cannot
    # silently take a generator's name either
    with pytest.raises(ValueError, match="generation instance"):
        eng.register_ffmodel(gpt, name="lm")
    out = eng.generate("lm", np.zeros(3, np.int32), 3)
    assert out.shape == (6,)
    eng.stop()
    assert eng.generators() == []  # one-shot schedulers drop at stop
    eng.register_generator(gpt, name="lm", decode_slots=2, block_size=8,
                           max_length=32)
    out2 = eng.generate("lm", np.zeros(3, np.int32), 3)
    np.testing.assert_array_equal(out, out2)
    eng.stop()


def test_config_knobs_flow_into_instance():
    ff = _gpt(serving_decode_slots=3, serving_block_size=4,
              serving_num_blocks=13, serving_max_length=24,
              serving_prefill_buckets="8,24",
              serving_max_prefills_per_step=2)
    eng = InferenceEngine()
    inst = eng.register_generator(ff, name="lm")
    dec = inst.scheduler.decoder
    assert dec.decode_slots == 3
    assert dec.block_size == 4
    assert dec.pool.num_blocks == 13
    assert dec.max_length == 24
    assert dec.prefill_buckets == [8, 24]
    assert inst.scheduler.max_prefills_per_step == 2
    eng.stop()


def test_repository_generator_entry(tmp_path):
    """A repository entry with "generator": true places a continuous-
    batching instance (serving/placement.py)."""
    import json

    cfgfile = tmp_path / "repo.json"
    cfgfile.write_text(json.dumps({"models": {
        "lm": {"generator": True, "mesh_shape": {"data": 1},
               "decode_slots": 2, "block_size": 8, "max_length": 24},
    }}))

    def build_lm(ff, bs):
        build_gpt(ff, bs, 6, GCFG)

    eng = InferenceEngine()
    placed = eng.load_repository(str(cfgfile),
                                 builders={"lm": build_lm})
    assert placed == {"lm": 1}
    assert eng.generators() == ["lm"]
    dec = eng.generator("lm").scheduler.decoder
    assert dec.decode_slots == 2 and dec.max_length == 24
    out = eng.generate("lm", np.zeros(3, np.int32), 3)
    assert out.shape == (6,)
    eng.stop()
    # multiple generator instances are rejected (one scheduler, one pool)
    cfgfile.write_text(json.dumps({"models": {
        "lm": {"generator": True, "instances": 2}}}))
    with pytest.raises(ValueError, match="instances must be 1"):
        InferenceEngine().load_repository(str(cfgfile),
                                          builders={"lm": build_lm})


def test_healthz_reports_serving_gauges(gpt):
    """/healthz grows the serving block once a scheduler has run:
    tokens/s + kv occupancy, the live SLO scrape."""
    from flexflow_tpu.obs.server import _healthz

    sched = ContinuousBatchingScheduler(gpt, max_length=32,
                                        decode_slots=2, block_size=8)
    sched.generate(np.zeros(3, np.int32), 3)
    sched.stop()
    doc = _healthz()
    assert doc["serving"]["tokens_per_s"] > 0
    assert doc["serving"]["kv_blocks_in_use"] == 0  # all freed


def test_prefill_bucket_compiles_cached_and_counted(gpt):
    c = metrics_registry().counter("serving.prefill_bucket_compiles")
    before = c.value
    dec = PagedDecoder(gpt, max_length=32, decode_slots=2, block_size=8,
                       prefill_buckets=[8, 16, 32])
    for n in (3, 5, 7):  # all map to bucket 8 — ONE compile
        t = dec.pool.try_admit(n + 2)
        dec.prefill(np.zeros(n, np.int32), t)
        dec.pool.free(t)
    assert c.value == before + 1
    t = dec.pool.try_admit(12 + 2)  # bucket 16 — second compile
    dec.prefill(np.zeros(12, np.int32), t)
    dec.pool.free(t)
    assert c.value == before + 2


# ------------------------------------------------- observability surface
def test_serving_ledger_record_and_explain(gpt, tmp_path):
    import dataclasses

    ff = _gpt(ledger="on", ledger_dir=str(tmp_path))
    eng = InferenceEngine()
    eng.register_generator(ff, name="lm", decode_slots=2, block_size=8,
                           max_length=32)
    rng = np.random.default_rng(23)
    futs = [eng.generate_async("lm", rng.integers(0, V, (3,))
                               .astype(np.int32), m) for m in (4, 2, 6)]
    for f in futs:
        f.result(timeout=120)
    eng.stop()
    from flexflow_tpu.obs.ledger import load_runs

    recs = load_runs(str(tmp_path), kind="serving")
    assert len(recs) == 1
    rec = recs[0]
    assert rec["serving_engine"] == "continuous"
    assert rec["completed"] == 3
    assert rec["tokens"] == 12
    for phase in ("queue_wait", "prefill", "decode"):
        assert {"p50", "p99"} <= set(rec["phases"][phase]), phase
    assert rec["kv"]["high_water"] >= 1
    assert rec["knobs"]["decode_slots"] == 2
    assert rec["model_sig"]
    # explain_run narrates it: dominant phase + degradation + kv
    from tools.explain_run import explain

    doc = explain(run_id=rec["run_id"], ledger_dir=str(tmp_path))
    assert doc["exit"] == 0
    sv = doc["serving"]
    assert sv["engine"] == "continuous"
    assert sv["dominant_phase"] in ("queue_wait", "prefill", "decode")
    assert sv["missing_phase_percentiles"] == []
    # a continuous record MISSING its phase percentiles exits 1
    from flexflow_tpu.obs import ledger as _ledger

    broken = {k: v for k, v in rec.items()}
    broken.pop("run_id")
    broken["phases"] = {"queue_wait": rec["phases"]["queue_wait"]}
    _ledger.record_run("serving", broken,
                       config=dataclasses.replace(
                           ff.config, ledger_dir=str(tmp_path)))
    newest = _ledger.load_runs(str(tmp_path), kind="serving")[-1]
    doc2 = explain(run_id=newest["run_id"], ledger_dir=str(tmp_path))
    assert doc2["exit"] == 1
    assert set(doc2["serving"]["missing_phase_percentiles"]) == \
        {"prefill", "decode"}


def test_sentinel_cohorts_serving_tokens_per_s(tmp_path):
    """serve_bench's ledger records gate like fit records: same
    (model_sig, decode_slots, block_size) cohort compares, a different
    geometry is a different cohort, and a slowdown past the margin
    regresses."""
    from tools.perf_sentinel import run_sentinel

    from flexflow_tpu.obs.ledger import record_bench

    def rec(value, slots=4, block=8):
        record_bench(
            "serve_bench", {"ok": True},
            perf={"metric": "serving.tokens_per_s", "value": value,
                  "higher_is_better": True},
            label="serve:sig0",
            knobs={"model_sig": "sig0", "decode_slots": slots,
                   "block_size": block},
            config=FFConfig(ledger_dir=str(tmp_path)))

    for v in (1000.0, 1040.0, 980.0):
        rec(v)
        time.sleep(0.002)  # ts_unix_s is ms-rounded: keep append order
    rec(400.0)  # a real regression in the same cohort
    time.sleep(0.002)
    rec(5000.0, slots=8)  # different geometry: its own (new) cohort
    out = run_sentinel(ledger_dir=str(tmp_path), margin=0.3)
    serving_rows = [r for r in out["cohorts"]
                    if r["metric"] == "serving.tokens_per_s"]
    assert len(serving_rows) == 2  # geometry split the cohorts
    verdicts = {r["verdict"] for r in serving_rows}
    assert "regression" in verdicts  # the 400 tok/s drop trips
    assert "no_baseline" in verdicts  # the new geometry has no priors
    assert out["exit"] == 1


def test_request_span_tree(gpt):
    """request ⊃ queue_wait → prefill → decode → reply on the request's
    own virtual track."""
    from flexflow_tpu.obs.trace import configure_tracer, tracer

    configure_tracer(enabled=True)
    try:
        sched = ContinuousBatchingScheduler(gpt, max_length=32,
                                            decode_slots=2, block_size=8)
        sched.generate(np.zeros(3, np.int32), 4)
        sched.stop()
        events = [e for e in tracer().events()
                  if e.get("cat") == "serving"]
        names = {e["name"] for e in events}
        assert {"serving.request", "serving.queue_wait",
                "serving.prefill", "serving.decode",
                "serving.reply"} <= names
        decode = [e for e in events if e["name"] == "serving.decode"]
        assert decode[-1]["args"]["steps"] == 3
    finally:
        configure_tracer(enabled=False)


# --------------------------------------------- the loop's phases (PR 25)
def _loop_sched(gpt, mode, **kw):
    """A scheduler on the plain or the speculative path."""
    if mode == "spec":
        from flexflow_tpu.serving.generation import build_draft_model

        kw.update(draft_ff=build_draft_model(gpt, "self:1"), spec_k=2)
    return ContinuousBatchingScheduler(gpt, max_length=32, decode_slots=2,
                                       block_size=8, **kw)


def _run_three(sched, new_tokens=6):
    futs = [sched.submit(np.arange(1, n + 1, dtype=np.int32), new_tokens)
            for n in (2, 3, 4)]
    return [f.result(timeout=300) for f in futs]


def test_loop_spans_reach_the_profile_nested_in_a_step(gpt, tmp_path):
    """A CPU profile around a toy session holds every ``serving.loop.*``
    span on ``/host:CPU``, on the scheduler's thread, read back the way
    the benchmark reads a trace; every span but ``wait`` lies inside a
    ``serving.loop.step``, and ``dispatch`` and ``fetch`` of a prefill
    inside its ``serving.loop.prefill``. The ring is off throughout."""
    from flexflow_tpu.obs.trace import configure_tracer, tracer
    from test_obs import _host_spans, _profile

    was = tracer().enabled
    configure_tracer(enabled=False)  # an earlier test may have armed it
    before = tracer().event_count()
    sched = _loop_sched(gpt, "plain")
    sched.generate(np.zeros(3, np.int32), 3)  # compile outside the profile
    with _profile(tmp_path):
        _run_three(sched)
        time.sleep(0.05)  # the loop goes back to waiting ...
        sched.generate(np.zeros(3, np.int32), 3)  # ... and is woken
    sched.stop()
    assert tracer().event_count() == before
    configure_tracer(enabled=was)
    (evs,) = _host_spans(tmp_path, "serving.loop.").values()  # one thread
    names = {n for n, _, _ in evs}
    assert names == {"serving.loop." + k for k in (
        "wait", "step", "admit", "prefill", "inputs", "dispatch", "fetch",
        "sample")}
    steps = [(s, e) for n, s, e in evs if n == "serving.loop.step"]
    prefills = [(s, e) for n, s, e in evs if n == "serving.loop.prefill"]

    def inside(spans, s, e):
        return any(s0 <= s and e <= e0 for s0, e0 in spans)

    for n, s, e in evs:
        if n == "serving.loop.wait":
            assert not inside(steps, s, e)
        elif n != "serving.loop.step":
            assert inside(steps, s, e), n
    n_disp = sum(n == "serving.loop.dispatch" for n, _, _ in evs)
    n_fetch = sum(n == "serving.loop.fetch" for n, _, _ in evs)
    assert n_disp == n_fetch >= len(prefills) == 4
    assert sum(inside(prefills, s, e) for n, s, e in evs
               if n == "serving.loop.dispatch") == 4


@pytest.mark.parametrize("mode", ["plain", "spec"])
def test_loop_phases_telescope_and_steps_count_dispatches(gpt, mode):
    """``stats()["loop"]``: the phases sum to the loop's lifetime (within
    1 %; in fact to rounding), a step is counted for every decode step
    or speculative round, and its wall time is observed once."""
    sched = _loop_sched(gpt, mode)
    assert sched.stats()["loop"]["elapsed_s"] == 0.0  # never started
    t_before = time.perf_counter()
    _run_three(sched)
    st = sched.stats()
    t_after = time.perf_counter()
    loop = st["loop"]
    assert set(loop["phase_s"]) == {"wait", "admit", "prefill", "inputs",
                                    "dispatch", "fetch", "sample", "other"}
    assert all(v >= 0 for v in loop["phase_s"].values())
    total = sum(loop["phase_s"].values())
    assert 0 < loop["elapsed_s"] <= t_after - t_before
    assert abs(total - loop["elapsed_s"]) <= 0.01 * loop["elapsed_s"]
    assert loop["steps"] == st["decode_steps"] > 0
    assert loop["step_wall"]["count"] == loop["steps"]
    assert sum(loop["step_wall"]["buckets"].values()) == loop["steps"]
    for k in ("prefill", "dispatch", "fetch", "sample"):
        assert loop["phase_s"][k] > 0, k
    # steps' wall time is loop time: no step is longer than the whole
    assert loop["step_wall"]["sum"] <= total - loop["phase_s"]["wait"]
    sched.stop()
    # a stopped loop's clock stands still
    a = sched.stats()["loop"]
    time.sleep(0.02)
    assert sched.stats()["loop"] == a
    assert abs(sum(a["phase_s"].values()) - a["elapsed_s"]) < 1e-6


@pytest.mark.parametrize("mode", ["plain", "spec"])
def test_token_gap_counts_tokens_less_first_tokens(gpt, mode):
    """Every token after a request's first is stamped: ``token_gap``
    holds one gap a token, less one a request."""
    sched = _loop_sched(gpt, mode)
    outs = _run_three(sched, new_tokens=5)
    sched.generate(np.zeros(2, np.int32), 1)  # a first token and no other
    st = sched.stats()
    sched.stop()
    assert [len(o) for o in outs] == [7, 8, 9]
    assert st["tokens"] == 16 and st["completed"] == 4
    gap = st["loop"]["token_gap"]
    assert gap["count"] == st["tokens"] - st["completed"] == 12
    assert sum(gap["buckets"].values()) == 12 and gap["min"] >= 0


def test_loop_spans_in_the_ring_keep_the_trace_valid(gpt):
    """With the ring enabled the loop's spans are ring events too, on the
    scheduler's own thread, and the exported trace still nests."""
    from flexflow_tpu.obs.trace import (configure_tracer, tracer,
                                        validate_chrome_trace)

    configure_tracer(enabled=True)
    tracer().clear()
    try:
        sched = _loop_sched(gpt, "plain")
        _run_three(sched)
        sched.stop()
        events = tracer().events()
    finally:
        configure_tracer(enabled=False)
        tracer().clear()
    loop = [e for e in events if e["name"].startswith("serving.loop.")]
    assert {e["name"].rsplit(".", 1)[1] for e in loop} >= {
        "step", "admit", "prefill", "inputs", "dispatch", "fetch", "sample"}
    assert len({e["tid"] for e in loop}) == 1
    admits = [e for e in loop if e["name"] == "serving.loop.admit"]
    assert sum(e["args"]["admitted"] for e in admits) == 3
    steps = [e for e in loop if e["name"] == "serving.loop.step"]
    assert [e["args"]["step"] for e in steps] == sorted(
        e["args"]["step"] for e in steps)
    assert validate_chrome_trace({"traceEvents": events}) == []


def test_phase_totals_outlive_the_percentile_window(gpt, monkeypatch):
    """``stats()["phases"][k]["count"]`` is the window's length and
    saturates; ``n`` and ``sum_s`` are the session's."""
    from flexflow_tpu.serving import scheduler as sched_mod

    monkeypatch.setattr(sched_mod, "_PHASE_WINDOW", 2)
    sched = _loop_sched(gpt, "plain")
    _run_three(sched)
    sched.generate(np.zeros(2, np.int32), 2)
    ph = sched.stats()["phases"]
    sched.stop()
    for k in ("queue_wait", "prefill", "decode", "ttft", "per_token", "e2e"):
        assert ph[k]["count"] == 2 and ph[k]["n"] == 4, k
        assert ph[k]["sum_s"] >= ph[k]["count"] * ph[k]["mean"] > 0, k


def test_loop_snapshots_stay_whole_under_concurrent_readers(gpt):
    """``stats()`` is read from other threads while the loop's thread
    moves the clock: every snapshot still telescopes (a torn one, taken
    between a phase's two updates, would not) and no phase runs
    backwards. More readers than cores, a short switch interval."""
    import os
    import sys
    import threading

    sched = _loop_sched(gpt, "plain")
    sched.generate(np.zeros(3, np.int32), 2)  # compiled before the race
    stop = threading.Event()
    bad = []

    def reader():
        last = None
        while not stop.is_set():
            loop = sched.stats()["loop"]
            total = sum(loop["phase_s"].values())
            if abs(total - loop["elapsed_s"]) > 1e-6:
                bad.append(("torn", total, loop["elapsed_s"]))
            if last is not None and any(
                    loop["phase_s"][k] < v - 1e-9
                    for k, v in last["phase_s"].items()):
                bad.append(("backwards", last["phase_s"], loop["phase_s"]))
            last = loop

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    readers = [threading.Thread(target=reader)
               for _ in range((os.cpu_count() or 2) + 2)]
    try:
        for t in readers:
            t.start()
        deadline = time.perf_counter() + 20
        for _ in range(3):
            if time.perf_counter() < deadline:
                _run_three(sched, new_tokens=8)
    finally:
        stop.set()
        for t in readers:
            t.join(timeout=30)
        sys.setswitchinterval(was)
    assert not any(t.is_alive() for t in readers)
    sched.stop()
    assert bad == []
    assert sched.stats()["loop"]["steps"] == sched.stats()["decode_steps"]


# ----------------------------------- the loop runs one step ahead (PR 29)
_MIX = [(3, 6), (6, 2), (2, 9), (5, 1), (4, 7), (2, 3), (3, 5), (6, 4)]


def _mix_reqs():
    rng = np.random.default_rng(7)
    return [(rng.integers(0, V, (n,)).astype(np.int32), m) for n, m in _MIX]


def _serve_mix(gpt, temperature=0.0, rider=False):
    """The 8-request ragged mix through a fresh scheduler of 4 slots;
    ``rider``: one sampled request that outlives the mix rides along in
    the first slot. Returns the mix's outputs and the last ``stats()``."""
    sched = ContinuousBatchingScheduler(gpt, max_length=32,
                                        decode_slots=4, block_size=8)
    ride = (sched.submit(np.array([1, 2], np.int32), 30, temperature=0.8,
                         seed=5) if rider else None)
    futs = []
    for i, (prompt, m) in enumerate(_mix_reqs()):
        futs.append(sched.submit(prompt, m, temperature=temperature,
                                 seed=1000 + i))
        if i % 3 == 2:
            time.sleep(0.002)  # ragged arrival
    outs = [f.result(timeout=120) for f in futs]
    if ride is not None:
        assert ride.result(timeout=120).shape == (32,)
    st = sched.stats()
    sched.stop()
    return outs, st


def test_greedy_tokens_same_running_ahead_and_forced_through_sync(gpt):
    """The greedy mix runs every step but a run's first ahead; one
    sampled request riding along forces every step through the
    synchronous pass. The mix's tokens are the same, and sequential
    serving's."""
    ahead, st_a = _serve_mix(gpt)
    sync, st_s = _serve_mix(gpt, rider=True)
    ref = _reference_rows(gpt, _mix_reqs(), 0.0)
    for a, s, r in zip(ahead, sync, ref):
        np.testing.assert_array_equal(a, r)
        np.testing.assert_array_equal(s, r)
    ran = st_a["loop"]["ahead"]
    assert ran["steps_ahead"] > 0 and ran["steps_sync"] == 0, ran
    assert ran["rows_dropped"] == 0  # no eos_id: nothing ends unforeseen
    assert ran["steps_ahead"] <= st_a["decode_steps"] == st_a["loop"]["steps"]
    forced = st_s["loop"]["ahead"]
    assert forced["steps_ahead"] == 0, forced
    assert forced["steps_sync"] == st_s["decode_steps"] > 0
    assert st_a["decode_steps"] == st_a["decode_dispatches"]


@pytest.mark.parametrize("mode", ["sampled", "spec"])
def test_sampled_and_speculative_sessions_never_run_ahead(gpt, mode):
    if mode == "sampled":
        _, st = _serve_mix(gpt, temperature=0.8)
    else:
        sched = _loop_sched(gpt, "spec")
        _run_three(sched)
        st = sched.stats()
        sched.stop()
    ran = st["loop"]["ahead"]
    assert ran["steps_ahead"] == 0 and ran["rows_dropped"] == 0, ran
    assert ran["steps_sync"] == st["loop"]["steps"] == st["decode_steps"] > 0


def test_eos_learnt_a_step_late_drops_one_row(gpt):
    """An ``eos_id`` hit in mid-generation, another slot live: the step
    after the hit was dispatched before the hit was read, so its row
    for the ended request is dropped, never committed or counted. The
    blocks the hit freed admit a waiting request, and every request's
    tokens are sequential serving's."""
    gen = Generator(gpt, max_length=32)
    rng = np.random.default_rng(21)
    for _ in range(50):
        a = rng.integers(0, V, (4,)).astype(np.int32)
        new = gen.generate(a[None, :], 8)[0][a.size:].tolist()
        hits = [k for k in range(2, 6) if new[k] not in new[:k]]
        if hits:
            k = hits[0]
            break
    else:
        pytest.fail("no prompt whose greedy run has a fresh token in 2..5")
    b, c = (rng.integers(0, V, (4,)).astype(np.int32) for _ in range(2))
    sched = ContinuousBatchingScheduler(
        gpt, max_length=32, decode_slots=2, block_size=8,
        num_blocks=5)  # two requests of 12 tokens at a time
    fa = sched.submit(a, 8, eos_id=new[k])
    fb = sched.submit(b, 8)
    fc = sched.submit(c, 8)  # waits for the blocks the eos frees
    outs = [f.result(timeout=120) for f in (fa, fb, fc)]
    st = sched.stats()
    sched.stop()
    assert outs[0].tolist() == a.tolist() + new[:k + 1]
    for out, ref in zip(outs[1:], _reference_rows(gpt, [(b, 8), (c, 8)],
                                                  0.0)):
        np.testing.assert_array_equal(out, ref)
    ran = st["loop"]["ahead"]
    assert ran["rows_dropped"] == 1 and ran["steps_sync"] == 0, ran
    assert st["tokens"] == sum(len(o) - 4 for o in outs) == k + 1 + 16
    assert st["decode_steps"] == st["decode_dispatches"] == st["loop"]["steps"]
    assert sched.decoder.pool.in_use() == 0


def test_session_after_the_benchmarks_warm_up_compiles_nothing(gpt):
    """``benchmark/serving.py`` ``warm_up``, word for word: the prefill
    of each bucket and ``decode(*idle)`` twice, until a round compiles
    nothing. A session after it, running ahead, compiles nothing: the
    loop's way into the decode program is the executable ``decode()``
    compiled, and the loop does no device arithmetic of its own."""
    from flexflow_tpu.utils.compile_cache import compile_stats

    buckets = [8, 16]
    sched = ContinuousBatchingScheduler(gpt, max_length=32, decode_slots=3,
                                        block_size=8, prefill_buckets=buckets)
    dec = sched.decoder
    slots = dec.decode_slots
    idle = (np.zeros(slots, np.int32),
            np.zeros((slots, dec.max_blocks_per_request), np.int32),
            np.zeros(slots, np.int32))
    for round_ in range(4):
        before = compile_stats()["compiles"]
        for b in buckets + buckets[:1]:
            table = dec.pool.try_admit(b + 1)
            try:
                dec.prefill(np.arange(b, dtype=np.int32) % V, table)
            finally:
                dec.pool.free(table)
            dec.decode(*idle)
            dec.decode(*idle)
        if compile_stats()["compiles"] == before:
            break
    else:
        pytest.fail("still compiling after four rounds of warm-up")
    before = compile_stats()["compiles"]
    rng = np.random.default_rng(9)
    futs = [sched.submit(rng.integers(0, V, (n,)).astype(np.int32), m)
            for n, m in [(5, 9), (12, 6), (8, 12), (3, 4), (16, 7)]]
    for f in futs:
        f.result(timeout=120)
    st = sched.stats()
    sched.stop()
    assert compile_stats()["compiles"] == before
    assert st["loop"]["ahead"]["steps_ahead"] > 0
    assert st["loop"]["ahead"]["steps_sync"] == 0


class _Unreadable:
    """Ids whose fetch fails: a step that fails on the device."""

    nbytes = 8

    def __array__(self, *a, **k):
        raise RuntimeError("wedged device")


@pytest.mark.parametrize("fails_at", ["dispatch", "fetch"])
def test_failure_with_a_step_in_flight_fails_both_steps_once(
        gpt, monkeypatch, fails_at):
    """The third step fails, at its dispatch or at the fetch of its ids
    (a dispatch later, the fourth step by then in flight): the requests
    of every step in flight fail, each once, every block comes back,
    and the breaker counts one failure."""
    sched = ContinuousBatchingScheduler(gpt, max_length=32, decode_slots=2,
                                        block_size=8, breaker_threshold=5,
                                        worker_retry_budget=0)
    real = sched.decoder.decode_ahead
    calls = []  # the steps in flight at each dispatch

    def decode_ahead(*args):
        calls.append(len(sched._in_flight))
        if len(calls) == 3:
            if fails_at == "dispatch":
                raise RuntimeError("wedged device")
            real(*args)
            return _Unreadable()
        return real(*args)

    monkeypatch.setattr(sched.decoder, "decode_ahead", decode_ahead)
    errors = metrics_registry().counter("serving.errors").value
    futs = [sched.submit(np.arange(1, n + 1, dtype=np.int32), 10)
            for n in (3, 4)]
    for f in futs:
        with pytest.raises(RuntimeError, match="wedged"):
            f.result(timeout=120)
    # the loop serves on: the failure took no block and no slot with it
    out = sched.generate(np.arange(1, 4, dtype=np.int32), 5)
    st = sched.stats()
    with sched._mu:
        streak = sched._consec_failures
    sched.stop()
    assert calls[2] == 1  # the failing step had one before it unread
    assert metrics_registry().counter("serving.errors").value == errors + 1
    assert streak == 0 and out.shape == (8,)  # a served step closed it
    assert st["completed"] == 1 and st["tokens"] >= 5
    assert sched.decoder.pool.in_use() == 0
    assert not sched._in_flight


@pytest.mark.parametrize("how", ["whole", "chunked"])
def test_a_prefill_that_fails_takes_its_request_alone(gpt, monkeypatch, how):
    """The second prompt's prefill fails (a group of one; or its first
    chunk, the request already in its slot): that request fails, its
    blocks come back and its slot is empty; its neighbours are served."""
    chunk = {"prefill_chunk": 8} if how == "chunked" else {}
    sched = ContinuousBatchingScheduler(gpt, max_length=32, decode_slots=3,
                                        block_size=8, **chunk)
    name = "prefill_chunk_at" if chunk else "prefill_many"
    real = getattr(sched.decoder, name)
    calls = []

    def prefill(*args):
        calls.append(1)
        if len(calls) == 2:
            raise RuntimeError("wedged device")
        return real(*args)

    monkeypatch.setattr(sched.decoder, name, prefill)
    errors = metrics_registry().counter("serving.errors").value
    futs = [sched.submit(np.arange(1, n + 1, dtype=np.int32), 6)
            for n in (3, 4, 5)]
    with pytest.raises(RuntimeError, match="wedged"):
        futs[1].result(timeout=120)
    assert [futs[i].result(timeout=120).shape for i in (0, 2)] == [(9,),
                                                                   (11,)]
    st = sched.stats()
    with sched._mu:
        slots = list(sched._slots)
    sched.stop()
    assert metrics_registry().counter("serving.errors").value == errors + 1
    assert st["completed"] == 2 and st["prefill_prompts"] == 2
    assert slots == [None] * 3 and sched.decoder.pool.in_use() == 0


def test_worker_crash_with_a_step_in_flight_loses_no_token(gpt, monkeypatch):
    """The step in flight lives on the scheduler: the worker that the
    ``serving.worker`` fault kills leaves it there, and the respawned
    one reads it. Every token is sequential serving's and counted
    once."""
    plan = {"schema": 1, "sites": {"serving.worker":
                                   {"at_step": 4, "max_fires": 1}}}
    faults.configure_faults(FFConfig(fault_plan=plan))
    sched = ContinuousBatchingScheduler(gpt, max_length=32, decode_slots=2,
                                        block_size=8, worker_retry_budget=2)
    at_crash = []
    passes = sched._loop_passes

    def loop_passes():
        try:
            passes()
        except Exception:
            at_crash.append(len(sched._in_flight))
            raise

    monkeypatch.setattr(sched, "_loop_passes", loop_passes)
    rng = np.random.default_rng(17)
    reqs = [(rng.integers(0, V, (n,)).astype(np.int32), m)
            for n, m in [(3, 8), (4, 6), (2, 7)]]
    futs = [sched.submit(p, m) for p, m in reqs]
    outs = [f.result(timeout=120) for f in futs]
    st = sched.stats()
    sched.stop()
    assert at_crash == [1]
    for out, ref in zip(outs, _reference_rows(gpt, reqs, 0.0)):
        np.testing.assert_array_equal(out, ref)
    assert st["tokens"] == 8 + 6 + 7
    assert st["loop"]["ahead"]["rows_dropped"] == 0
    assert st["decode_steps"] == st["decode_dispatches"] == st["loop"]["steps"]
