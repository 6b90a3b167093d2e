"""Perf advisor: dominant-phase rule table, suggestion ranking, the
measured --apply-top loop, /advice + serving-attribution parity, and
the sentinel/explain integrations."""

import importlib.util
import json
import os
import subprocess
import sys
import urllib.request

import numpy as np
import pytest

from flexflow_tpu.obs.advisor import (ADVISOR_SCHEMA, RULE_FAMILIES,
                                      advise_record, advisor_mode,
                                      judge_experiment, top_suggestion,
                                      validate_report)

_TOOLS = os.path.join(os.path.dirname(__file__), os.pardir, "tools")


def _tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_TOOLS, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _write_ledger(dirpath, recs, name="runs-t.jsonl"):
    os.makedirs(str(dirpath), exist_ok=True)
    with open(os.path.join(str(dirpath), name), "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")


# ------------------------------------------------------- record factories
def _fit_rec(dominant, knobs=None, mesh=None, pipeline=None, n_ops=8,
             run_id="r1", ts=1.0, value=10.0, label=None):
    """A ledger-shaped fit record whose attribution makes ``dominant``
    the dominant phase (it gets 60% of the step, the rest is spread)."""
    phases = {name: {"seconds": 0.004}
              for name in ("input_wait", "host_dispatch",
                           "device_compute", "collective_transfer",
                           "optimizer_fold")}
    phases["pipeline_bubble"] = {"seconds": 0.004 if pipeline else 0.0}
    phases[dominant] = {"seconds": 0.06}
    measured = sum(p["seconds"] for p in phases.values())
    for row in phases.values():  # the real table's render contract
        row["fraction"] = round(row["seconds"] / measured, 4)
        row["basis"] = "modeled"
    rec = {
        "schema": 1, "kind": "fit", "run_id": run_id, "ts_unix_s": ts,
        "pid": 1, "machine": {"backend": "cpu"},
        "model_sig": label or "mlpsig", "n_ops": n_ops,
        "mesh": mesh if mesh is not None else {"data": 8},
        "knobs": {"prefetch_depth": 0, "steps_per_dispatch": 1,
                  "grad_accum_steps": 1, "zero_optimizer": False,
                  "compute_dtype": None, **(knobs or {})},
        "perf": {"metric": "fit.steps_per_s", "value": value,
                 "higher_is_better": True},
        "attribution": {"measured_step_s": measured,
                        "dominant_phase": dominant, "phases": phases},
    }
    if label:
        rec["label"] = label
    if pipeline:
        rec["pipeline"] = pipeline
    return rec


def _serving_rec(dominant, knobs=None, run_id="s1", ts=1.0, kv=None):
    means = {"queue_wait": 0.01, "prefill": 0.01, "decode": 0.01}
    means[dominant] = 0.2
    return {
        "schema": 1, "kind": "serving", "run_id": run_id,
        "ts_unix_s": ts, "pid": 1, "machine": {"backend": "cpu"},
        "serving_engine": "continuous", "model": "gpt",
        "tokens_per_s": 50.0, "completed": 8,
        "knobs": {"decode_slots": 4, "block_size": 8, "num_blocks": 24,
                  "max_prefills_per_step": 1, **(knobs or {})},
        "kv": kv or {"high_water": 6, "capacity_blocks": 24},
        "phases": {k: {"count": 8, "mean": v, "p50": v, "p99": v * 1.5}
                   for k, v in means.items()},
    }


def _families(report):
    return [s["family"] for s in report["suggestions"]]


# --------------------------------------------------- golden rules per phase
def test_rule_input_wait_maps_to_prefetch():
    rep = advise_record(_fit_rec("input_wait"))
    top = rep["suggestions"][0]
    assert top["phase"] == "input_wait" and top["family"] == "prefetch"
    assert top["knobs"] == {"prefetch_depth": 2}
    assert top["expected"]["basis"] == "measured"
    # already prefetching: the rule deepens instead of re-enabling
    rep2 = advise_record(_fit_rec("input_wait",
                                  knobs={"prefetch_depth": 2}))
    top2 = rep2["suggestions"][0]
    assert top2["family"] == "prefetch" and top2["proposed"] == 4


def test_rule_host_dispatch_maps_to_multi_step_dispatch():
    rep = advise_record(_fit_rec("host_dispatch"))
    top = rep["suggestions"][0]
    assert top["phase"] == "host_dispatch"
    assert top["family"] == "multi_step_dispatch"
    assert top["knobs"] == {"steps_per_dispatch": 2}


def test_rule_host_dispatch_pipelined_maps_to_compiled_engine():
    pipe = {"engine": "host", "schedule": "1f1b", "num_stages": 2,
            "num_microbatches": 4, "interleave": 1,
            "bubble_fraction": 0.2, "dispatches_per_step": 20,
            "compiled_mesh_eligible": True, "fallback_reason": None}
    rep = advise_record(_fit_rec("host_dispatch",
                                 mesh={"pipe": 2, "data": 4},
                                 pipeline=pipe))
    top = rep["suggestions"][0]
    assert top["family"] == "compiled_pipeline"
    assert top["knobs"] == {"pipeline_engine": "compiled"}
    # 20 dispatches -> 1: expected delta ~ 0.95x the phase
    assert top["expected"]["phase_delta_s"] == pytest.approx(
        0.06 * 0.95, rel=1e-6)


def test_rule_pipeline_bubble_maps_to_schedule_family():
    # gpipe at S=4/M=8: the tick-table model prices its bubble 0.4667
    # (the recorded schedule_summary value); interleaved x2 (0.3425)
    # and M-doubling (0.4353) both beat it, 1f1b ties and is dropped
    pipe = {"engine": "compiled", "schedule": "gpipe", "num_stages": 4,
            "num_microbatches": 8, "interleave": 1,
            "bubble_fraction": 0.4667, "dispatches_per_step": 1,
            "compiled_mesh_eligible": True, "fallback_reason": None}
    rep = advise_record(_fit_rec("pipeline_bubble",
                                 mesh={"pipe": 4, "data": 2},
                                 pipeline=pipe, n_ops=32))
    fams = {s["family"] for s in rep["suggestions"]
            if s["phase"] == "pipeline_bubble"}
    assert "schedule" in fams and fams <= set(
        RULE_FAMILIES["pipeline_bubble"])
    sched = next(s for s in rep["suggestions"]
                 if s["family"] == "schedule")
    assert sched["knobs"]["pipeline_schedule"] == "interleaved"
    # the microbatch-doubling move rides grad_accum_steps
    micro = [s for s in rep["suggestions"] if s["family"] == "microbatches"]
    assert micro and micro[0]["knobs"] == {"grad_accum_steps": 2}


def test_rule_collective_maps_to_mesh_reshape():
    rep = advise_record(_fit_rec("collective_transfer"))
    top = rep["suggestions"][0]
    assert top["phase"] == "collective_transfer"
    assert top["family"] == "mesh_reshape"
    cand = top["knobs"]["mesh_shape"]
    # same device count, data degree reduced but kept >= 2
    assert int(np.prod(list(cand.values()))) == 8
    assert 2 <= cand["data"] < 8


def test_rule_optimizer_fold_maps_to_zero():
    rep = advise_record(_fit_rec("optimizer_fold"))
    top = rep["suggestions"][0]
    assert top["family"] == "optimizer_sharding"
    assert top["knobs"] == {"zero_optimizer": True}
    # already sharded -> the rule stays silent for this phase
    rep2 = advise_record(_fit_rec("optimizer_fold",
                                  knobs={"zero_optimizer": True}))
    assert all(s["phase"] != "optimizer_fold"
               for s in rep2["suggestions"])


def test_rule_device_compute_maps_to_precision():
    rep = advise_record(_fit_rec("device_compute"))
    top = rep["suggestions"][0]
    assert top["phase"] == "device_compute"
    assert top["family"] in RULE_FAMILIES["device_compute"]
    assert top["knobs"] == {"compute_dtype": "bfloat16"}


def test_rule_token_bucketing_prices_padded_flops():
    """A padded-token-heavy bucketed fit record (the ledger ``buckets``
    block record_fit carries from ``fit_profile``) maps to the
    token-native knob deltas, priced by the measured padded-FLOPs
    fraction."""
    assert "token_bucketing" in RULE_FAMILIES["device_compute"]
    # fixed-row bucketed fit, 60% padding -> propose a token budget
    rec = _fit_rec("device_compute")
    rec["buckets"] = {"padded_token_fraction": 0.6, "pad_max": False,
                      "token_budget": 0, "ladder": [8, 16, 32]}
    rep = advise_record(rec)
    sug = next(s for s in rep["suggestions"]
               if s["family"] == "token_bucketing")
    assert sug["knob"] == "token_budget"
    assert sug["knobs"] == {"token_budget": 128}  # 4x the ladder top
    assert sug["expected"]["priced_by"] == "padded_flops_fraction"
    # pad-to-max dispatch -> propose dropping to per-rung widths, and
    # the full padded fraction prices the delta (vs half for packing)
    rec2 = _fit_rec("device_compute")
    rec2["buckets"] = {"padded_token_fraction": 0.6, "pad_max": True,
                       "token_budget": 128, "ladder": [8, 16, 32]}
    rep2 = advise_record(rec2)
    sug2 = next(s for s in rep2["suggestions"]
                if s["family"] == "token_bucketing")
    assert sug2["knobs"] == {"seq_bucket_pad_max": "off"}
    assert (sug2["expected"]["phase_delta_s"]
            > sug["expected"]["phase_delta_s"])
    # a well-packed run (20% padding) stays silent — no noop advice
    rec3 = _fit_rec("device_compute")
    rec3["buckets"] = {"padded_token_fraction": 0.2, "pad_max": False,
                       "token_budget": 128, "ladder": [8, 16, 32]}
    rep3 = advise_record(rec3)
    assert all(s["family"] != "token_bucketing"
               for s in rep3["suggestions"])


def test_rule_rank_skew_golden():
    """Golden: a skew-dominant cohort record (OBS003-bearing cohort
    block, or a rank_skew-dominant cohort attribution table) maps to
    elastic shrink of the straggler + steps_per_dispatch amortization,
    both priced basis="measured" from the skew fraction."""
    assert RULE_FAMILIES["rank_skew"] == ("elastic_shrink",
                                          "multi_step_dispatch")
    rec = _fit_rec("device_compute", knobs={"process_count": 4})
    rec["cohort"] = {  # the supervisor-annotated skew verdict
        "schema": 1, "ranks": [0, 1, 2, 3], "straggler_rank": 2,
        "steady_skew_frac": 0.4, "threshold": 0.25,
        "per_rank_mean_step_s": {"0": 0.01, "1": 0.01, "2": 0.014,
                                 "3": 0.01},
        "findings": [{"code": "OBS003", "severity": "warning",
                      "message": "rank 2 is pacing the cohort"}],
    }
    rep = advise_record(rec)
    skew = [s for s in rep["suggestions"] if s["phase"] == "rank_skew"]
    assert {s["family"] for s in skew} == {"elastic_shrink",
                                           "multi_step_dispatch"}
    shrink = next(s for s in skew if s["family"] == "elastic_shrink")
    assert shrink["knob"] == "process_count"
    assert shrink["current"] == 4 and shrink["proposed"] == 3
    assert shrink["expected"]["basis"] == "measured"
    # priced FROM the measured skew fraction: 0.4 x the measured step
    measured = rec["attribution"]["measured_step_s"]
    assert shrink["expected"]["phase_delta_s"] == pytest.approx(
        0.4 * measured, rel=1e-6)
    assert "rank 2" in shrink["rationale"]
    disp = next(s for s in skew if s["family"] == "multi_step_dispatch")
    assert disp["knobs"] == {"steps_per_dispatch": 2}
    assert disp["expected"]["basis"] == "measured"
    # a clean cohort block (no OBS003, sub-threshold skew) stays silent
    rec2 = _fit_rec("device_compute", knobs={"process_count": 4})
    rec2["cohort"] = dict(rec["cohort"], findings=[],
                          steady_skew_frac=0.05)
    rep2 = advise_record(rec2)
    assert all(s["phase"] != "rank_skew" for s in rep2["suggestions"])
    # the other trigger: a cohort attribution table whose dominant
    # phase IS rank_skew (no annotated block needed)
    rec3 = _fit_rec("device_compute", knobs={"process_count": 2})
    attr = rec3["attribution"]
    attr["phases"]["rank_skew"] = {"seconds": 0.08, "fraction": 0.5,
                                   "basis": "measured"}
    attr["measured_step_s"] += 0.08
    attr["dominant_phase"] = "rank_skew"
    rep3 = advise_record(rec3)
    skew3 = [s for s in rep3["suggestions"] if s["phase"] == "rank_skew"]
    assert skew3 and skew3[0]["expected"]["phase_delta_s"] == \
        pytest.approx(0.08, rel=1e-6)


def test_serving_rules_map_phases_to_knob_families():
    for dominant, family, knob in (
            ("queue_wait", "decode_slots", "decode_slots"),
            ("prefill", "prefill_interleave", "max_prefills_per_step"),
            # decode-dominant with speculation off: the spec rule
            # outprices block_size (one verify dispatch retires ~1+ak
            # tokens vs a constant-factor gather saving)
            ("decode", "speculation", "serving_spec_k")):
        rep = advise_record(_serving_rec(dominant))
        assert rep["kind"] == "serving"
        assert rep["dominant_phase"] == dominant
        top = rep["suggestions"][0]
        assert top["family"] == family and top["knob"] == knob, dominant


def test_serving_spec_rule_golden():
    """Golden: decode-dominant + spec off -> serving_spec_k, modeled
    pricing without priors, measured pricing when a prior serving
    record carries a spec.accept_rate; silent once speculation is on
    (block_size becomes the decode top again)."""
    rep = advise_record(_serving_rec("decode"))
    top = rep["suggestions"][0]
    assert top["family"] == "speculation"
    assert top["knob"] == "serving_spec_k"
    assert top["knobs"] == {"serving_spec_k": 4}
    assert top["expected"]["basis"] == "modeled"
    # measured pricing: a prior run with speculation on measured alpha
    prior = _serving_rec("decode", run_id="s0", ts=0.5)
    prior["spec"] = {"k": 4, "accept_rate": 0.8}
    rep_m = advise_record(_serving_rec("decode"), priors=[prior])
    top_m = rep_m["suggestions"][0]
    assert top_m["knob"] == "serving_spec_k"
    assert top_m["expected"]["basis"] == "measured"
    # measured alpha=0.8 prices a bigger decode saving than the
    # modeled alpha=0.6 default
    assert (top_m["expected"]["phase_delta_s"]
            > top["expected"]["phase_delta_s"])
    # speculation already on -> no spec suggestion; block_size rules
    rec_on = _serving_rec("decode", knobs={"spec_k": 4})
    rec_on["spec"] = {"k": 4, "accept_rate": 0.5}
    rep_on = advise_record(rec_on)
    assert all(s["family"] != "speculation" for s in rep_on["suggestions"])
    top_on = next(s for s in rep_on["suggestions"]
                  if s["phase"] == "decode")
    assert top_on["family"] == "block_size"


def test_serving_prefill_rule_never_proposes_a_noop():
    """max_prefills_per_step already at the slot-capped bound: the rule
    must stay silent rather than emit proposed == current (which would
    A/B-benchmark two identical configs)."""
    rep = advise_record(_serving_rec(
        "prefill", knobs={"decode_slots": 4,
                          "max_prefills_per_step": 4}))
    sugs = [] if rep is None else rep["suggestions"]
    for s in sugs:
        assert s["proposed"] != s["current"], s
    assert all(s["family"] != "prefill_interleave" for s in sugs)


def test_serving_kv_pool_rule_fires_at_capacity():
    """Golden: the kv_pool rule is dtype-aware — at capacity with f32
    arenas it suggests quantizing (int8 frees the same bytes num_blocks*2
    would buy, at zero extra memory); only an already-quantized pool gets
    the num_blocks*2 grow."""
    rep = advise_record(_serving_rec(
        "queue_wait", kv={"high_water": 24, "capacity_blocks": 24}))
    fams = _families(rep)
    assert "kv_pool" in fams
    kvsug = next(s for s in rep["suggestions"] if s["family"] == "kv_pool")
    assert kvsug["knobs"] == {"serving_kv_dtype": "int8"}
    assert kvsug["proposed"] == "int8" and kvsug["current"] == "float32"
    # already int8: quantization can't free more — grow the pool
    rep8 = advise_record(_serving_rec(
        "queue_wait", kv={"high_water": 24, "capacity_blocks": 24,
                          "kv_dtype": "int8"}))
    kvsug8 = next(s for s in rep8["suggestions"]
                  if s["family"] == "kv_pool")
    assert kvsug8["knobs"] == {"num_blocks": 48}


# --------------------------------------------------- ranking + validation
def test_ranking_stable_and_dominant_first():
    rec = _fit_rec("input_wait")
    a, b = advise_record(rec), advise_record(rec)
    assert a == b  # bit-identical reruns
    assert a["suggestions"][0]["phase"] == "input_wait"
    assert [s["rank"] for s in a["suggestions"]] == list(
        range(len(a["suggestions"])))
    fracs = [s["expected"]["step_delta_frac"] for s in a["suggestions"]]
    assert fracs == sorted(fracs, reverse=True)


def test_unadvisable_records_return_none():
    assert advise_record({"kind": "bench", "perf": {}}) is None
    assert advise_record({"kind": "fit", "attribution": {}}) is None
    # classic serving records (no phases) are not advisable
    assert advise_record({"kind": "serving", "counters": {}}) is None


def test_validate_report_catches_malformed():
    rep = advise_record(_fit_rec("input_wait"))
    assert validate_report(rep) == []
    bad = json.loads(json.dumps(rep))
    del bad["suggestions"][0]["expected"]
    assert any("expected" in p for p in validate_report(bad))
    bad2 = json.loads(json.dumps(rep))
    bad2["suggestions"][0]["family"] = "nonsense"
    assert any("rule table" in p for p in validate_report(bad2))
    assert validate_report({"schema": ADVISOR_SCHEMA, "kind": "fit",
                            "suggestions": []})


def test_advisor_mode_guard():
    import types

    assert advisor_mode(types.SimpleNamespace(advisor="on")) == "on"
    assert advisor_mode(types.SimpleNamespace(advisor="off")) == "off"
    with pytest.raises(ValueError, match="advisor="):
        advisor_mode(types.SimpleNamespace(advisor="typo"))


# -------------------------------------------------------- experiment judge
def _pair(base_phase, cand_phase, phase="input_wait",
          metric="steps_per_s", base_m=10.0, cand_m=11.0):
    return {"baseline": {"phases": {phase: base_phase}, metric: base_m},
            "candidate": {"phases": {phase: cand_phase}, metric: cand_m}}


def test_judge_experiment_accepts_and_rejects():
    sug = advise_record(_fit_rec("input_wait"))["suggestions"][0]
    # targeted phase improved in the pair medians -> accepted
    good = judge_experiment(sug, [_pair(0.010, 0.004),
                                  _pair(0.012, 0.005)])
    assert good["verdict"] == "accepted"
    assert good["phase_ratio"] < 1.0 and good["pairs"] == 2
    # targeted phase regressed -> rejected even if the metric wobbles up
    bad = judge_experiment(sug, [_pair(0.004, 0.010),
                                 _pair(0.005, 0.012)])
    assert bad["verdict"] == "rejected" and bad["phase_ratio"] > 1.0
    # median of pair ratios: one bad pair does not flip two good ones
    mixed = judge_experiment(sug, [_pair(0.010, 0.004),
                                   _pair(0.004, 0.010),
                                   _pair(0.010, 0.005)])
    assert mixed["verdict"] == "accepted"
    # no phase evidence at all -> rejected, never silently accepted
    none = judge_experiment(sug, [{"baseline": {}, "candidate": {}}])
    assert none["verdict"] == "rejected" and none["phase_ratio"] is None


# ------------------------------------------------------------ tool e2e
def test_tool_advises_seeded_ledger(tmp_path):
    adv = _tool("perf_advisor")
    _write_ledger(tmp_path, [_fit_rec("input_wait"),
                             _serving_rec("queue_wait", ts=2.0)])
    out = adv.run_advisor(ledger_dir=str(tmp_path))
    assert out["exit"] == 0 and out["schema_problems"] == []
    kinds = {r["kind"] for r in out["reports"]}
    assert kinds == {"fit", "serving"}
    json.dumps(out)  # one-line-JSON-able


def test_tool_exit1_on_unadvisable_regression(tmp_path):
    """A sentinel regression whose newest record has no phase table is
    a broken loop: detection without an applicable remedy exits 1."""
    adv = _tool("perf_advisor")
    recs = []
    for i, v in enumerate((10.0, 10.5, 9.9, 3.0)):
        recs.append({"schema": 1, "kind": "bench", "run_id": f"b{i}",
                     "ts_unix_s": i + 1, "pid": 1,
                     "machine": {"backend": "cpu"}, "label": "bench1",
                     "mesh": {"data": 8}, "knobs": {"batch": 64},
                     "perf": {"metric": "steps_per_s", "value": v,
                              "higher_is_better": True}})
    _write_ledger(tmp_path, recs)
    out = adv.run_advisor(ledger_dir=str(tmp_path), margin=0.2)
    assert out["exit"] == 1
    assert out["unadvisable_regressions"] == ["steps_per_s"]
    (row,) = out["regressions"]
    assert row["advised"] is False


def test_tool_regression_with_advisable_record_exits_clean(tmp_path):
    adv = _tool("perf_advisor")
    recs = [_fit_rec("input_wait", run_id=f"r{i}", ts=i + 1, value=v)
            for i, v in enumerate((10.0, 10.5, 9.9))]
    recs.append(_fit_rec("input_wait", run_id="r9", ts=9, value=3.0))
    _write_ledger(tmp_path, recs)
    out = adv.run_advisor(ledger_dir=str(tmp_path), margin=0.2)
    assert out["exit"] == 0
    (row,) = out["regressions"]
    assert row["advised"] is True


def test_apply_top_accept_and_reject_with_canned_children(tmp_path):
    """--apply-top wiring: interleaved pair order, verdicts both ways,
    the advisor_experiment ledger record, and sentinel exclusion —
    children canned so the suite pays no subprocess cost."""
    adv = _tool("perf_advisor")
    _write_ledger(tmp_path, [_fit_rec("input_wait")])
    calls = []

    def improving(kind, spec):
        calls.append((kind, json.dumps(spec.get("knobs"),
                                       sort_keys=True)))
        better = spec["knobs"].get("prefetch_depth")
        return {"ok": True, "steps_per_s": 12.0 if better else 10.0,
                "phases": {"input_wait": 0.002 if better else 0.006}}

    out = adv.run_advisor(ledger_dir=str(tmp_path), apply_top=1,
                          pairs=2, child_runner=improving)
    (exp,) = out["experiments"]
    assert exp["verdict"] == "accepted"
    assert exp["phase_ratio"] == pytest.approx(2.0 / 6.0, abs=1e-3)
    assert exp["candidate_knobs"] == {"prefetch_depth": 2}
    assert len(calls) == 4  # 2 pairs x (baseline + candidate)
    # alternating order: pair 0 baseline-first, pair 1 candidate-first
    assert calls[0][1] != calls[1][1] and calls[2][1] == calls[1][1]

    def worsening(kind, spec):
        better = spec["knobs"].get("prefetch_depth")
        return {"ok": True, "steps_per_s": 9.0 if better else 10.0,
                "phases": {"input_wait": 0.009 if better else 0.006}}

    out2 = adv.run_advisor(ledger_dir=str(tmp_path), apply_top=1,
                           pairs=2, child_runner=worsening)
    assert out2["experiments"][0]["verdict"] == "rejected"

    # both experiments are durable ledger records of the excluded kind
    from flexflow_tpu.obs.ledger import scan_ledger

    runs = scan_ledger(str(tmp_path))["runs"]
    exps = [r for r in runs if r.get("kind") == "advisor_experiment"]
    assert len(exps) == 2
    assert {r["verdict"] for r in exps} == {"accepted", "rejected"}
    sent = _tool("perf_sentinel")
    s = sent.run_sentinel(ledger_dir=str(tmp_path),
                          blackbox_dir=str(tmp_path / "bb"))
    assert s["ledger"]["advisor_excluded"] == 2
    assert all(r["kind"] != "advisor_experiment" for r in s["cohorts"])


def test_out_of_envelope_suggestion_marked_and_skipped(tmp_path):
    """A mesh suggestion from a 16-device host cannot be benchmarked on
    this 8-device harness: the tool flips applicable to False, the
    regression gate sees it, and --apply-top reports it as 'skipped'
    instead of dying or silently vanishing."""
    adv = _tool("perf_advisor")
    recs = [_fit_rec("collective_transfer", run_id=f"r{i}", ts=i + 1,
                     value=v, mesh={"data": 16})
            for i, v in enumerate((10.0, 10.5, 9.9))]
    recs.append(_fit_rec("collective_transfer", run_id="r9", ts=9,
                         value=3.0, mesh={"data": 16}))
    _write_ledger(tmp_path, recs)
    out = adv.run_advisor(ledger_dir=str(tmp_path), margin=0.2,
                          apply_top=1, child_runner=lambda k, s: {})
    rep = next(r for r in out["reports"] if r["kind"] == "fit")
    mesh_sugs = [s for s in rep["suggestions"]
                 if s["family"] == "mesh_reshape"]
    assert mesh_sugs and all(not s["applicable"] for s in mesh_sugs)
    skipped = [e for e in out["experiments"]
               if e["verdict"] == "skipped"]
    assert skipped and "envelope" in skipped[0]["reason"]
    # a regression whose only suggestions are out-of-envelope is
    # unadvisable when nothing else applies; here other phases still
    # yield in-envelope suggestions, so the row stays advised
    (row,) = out["regressions"]
    assert row["advised"] is True


def test_apply_top_child_failure_becomes_error_row(tmp_path):
    """A dead child (wrong-host mesh, timeout, crash) must not take
    down the one-JSON-line report — it becomes an 'error' experiment
    row and the tool still exits by its own contract."""
    adv = _tool("perf_advisor")
    _write_ledger(tmp_path, [_fit_rec("input_wait")])

    def dying(kind, spec):
        raise RuntimeError("advisor fit child failed (rc 1): boom")

    out = adv.run_advisor(ledger_dir=str(tmp_path), apply_top=1,
                          pairs=2, child_runner=dying)
    (exp,) = out["experiments"]
    assert exp["verdict"] == "error" and "boom" in exp["error"]
    assert out["exit"] == 0  # advice itself was fine
    json.dumps(out)


def test_malformed_report_exits_one_not_traceback(tmp_path,
                                                  monkeypatch):
    """The documented 'exit 1 on a malformed report' contract: a rule
    bug surfaces as schema_problems + exit 1, never a traceback."""
    import flexflow_tpu.obs.advisor as advisor_mod

    adv = _tool("perf_advisor")
    _write_ledger(tmp_path, [_fit_rec("input_wait")])

    def broken(rec, max_suggestions=5, **kw):
        raise AssertionError("advisor built a malformed report: [...]")

    monkeypatch.setattr(advisor_mod, "advise_record", broken)
    out = adv.run_advisor(ledger_dir=str(tmp_path))
    assert out["exit"] == 1
    assert out["schema_problems"]
    json.dumps(out)


def test_serving_apply_top_with_canned_children(tmp_path):
    adv = _tool("perf_advisor")
    _write_ledger(tmp_path, [_serving_rec("queue_wait")])

    def runner(kind, spec):
        assert kind == "serve"
        wide = spec["knobs"].get("decode_slots", 4) > 4
        return {"ok": True, "tokens_per_s": 80.0 if wide else 50.0,
                "phases": {"queue_wait": 0.05 if wide else 0.2,
                           "prefill": 0.01, "decode": 0.01}}

    out = adv.run_advisor(ledger_dir=str(tmp_path), apply_top=1,
                          pairs=2, child_runner=runner)
    (exp,) = out["experiments"]
    assert exp["workload"] == "serve"
    assert exp["metric"] == "tokens_per_s"
    assert exp["verdict"] == "accepted"
    assert exp["candidate_knobs"]["decode_slots"] == 8


@pytest.mark.slow
def test_apply_top_real_children_fit_and_serving(tmp_path):
    """The acceptance loop with REAL child processes: one fit cohort
    (input_wait -> prefetch) and one serving cohort (queue_wait ->
    decode_slots), each completing an interleaved A/B benchmark whose
    experiment lands in the ledger and stays out of sentinel cohorts."""
    adv = _tool("perf_advisor")
    _write_ledger(tmp_path, [_fit_rec("input_wait"),
                             _serving_rec("queue_wait",
                                          knobs={"decode_slots": 2,
                                                 "num_blocks": 0},
                                          ts=2.0)])
    out = adv.run_advisor(ledger_dir=str(tmp_path), apply_top=1,
                          pairs=2, smoke=True)
    assert len(out["experiments"]) == 2
    kinds = {e["workload"]: e for e in out["experiments"]}
    assert set(kinds) == {"fit", "serve"}
    for e in out["experiments"]:
        assert e["pairs"] == 2 and e["phase_ratio"] is not None
        assert e["verdict"] in ("accepted", "rejected")
        assert e["ledger_run_id"]
    sent = _tool("perf_sentinel")
    s = sent.run_sentinel(ledger_dir=str(tmp_path),
                          blackbox_dir=str(tmp_path / "bb"))
    assert s["ledger"]["advisor_excluded"] == 2


def test_child_fit_subprocess_smoke():
    """One REAL measurement child: the subprocess harness builds, fits,
    and reports phases — the contract every experiment rides on."""
    spec = {"knobs": {"prefetch_depth": 0}, "samples": 128, "dim": 32,
            "hidden": 16, "batch": 32, "epochs": 2}
    proc = subprocess.run(
        [sys.executable, os.path.join(_TOOLS, "perf_advisor.py"),
         "--child-fit", json.dumps(spec)],
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-800:]
    doc = json.loads(proc.stdout.strip().splitlines()[-1])
    assert doc["ok"] and doc["steps_per_s"] > 0
    assert set(doc["phases"]) >= {"input_wait", "host_dispatch",
                                  "device_compute"}


# ------------------------------------------- /advice + serving attribution
def test_advice_endpoint_404_then_report(monkeypatch):
    import flexflow_tpu.obs.server as obs_server_mod
    from flexflow_tpu.obs.server import (ObsServer, publish_advice)

    # a serving session that ran earlier in this process published one
    monkeypatch.setattr(obs_server_mod, "_LATEST_ADVICE", None)
    srv = ObsServer(port=0)
    port = srv.start()
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/advice", timeout=10)
        assert ei.value.code == 404
        rep = advise_record(_fit_rec("input_wait"))
        publish_advice(rep)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/advice", timeout=10) as r:
            doc = json.loads(r.read())
        assert doc["schema"] == ADVISOR_SCHEMA
        assert doc["suggestions"][0]["family"] == "prefetch"
        # /advice is in the unknown-path endpoint listing
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/nope", timeout=10)
        assert "/advice" in ei.value.read().decode()
    finally:
        srv.stop()


def test_serving_attribution_parity_and_kinds():
    """Satellite: serving phase tables share the /attribution surface —
    a serving-only process stops 404ing, and a fit report never loses
    its slot to a serving one."""
    import flexflow_tpu.obs.server as obs_server_mod
    from flexflow_tpu.obs.attribution import serving_attribution
    from flexflow_tpu.obs.server import (latest_attribution,
                                         publish_attribution)

    stats = {"serving_engine": "continuous", "model": "gpt",
             "tokens_per_s": 50.0, "completed": 3,
             "knobs": {"decode_slots": 4, "block_size": 8},
             "kv": {"high_water": 3, "capacity_blocks": 20},
             "phases": {"queue_wait": {"count": 3, "mean": 0.2,
                                       "p50": 0.2, "p99": 0.3},
                        "prefill": {"count": 3, "mean": 0.01,
                                    "p50": 0.01, "p99": 0.01},
                        "decode": {"count": 3, "mean": 0.05,
                                   "p50": 0.05, "p99": 0.06}}}
    rec = serving_attribution(stats)
    assert rec["kind"] == "serving"
    assert rec["dominant_phase"] == "queue_wait"
    assert set(rec["phases"]) == {"queue_wait", "prefill", "decode"}
    # empty session -> nothing to publish (no None-filled table)
    assert serving_attribution({"phases": {}}) is None

    with obs_server_mod._attr_mu:
        saved = dict(obs_server_mod._LATEST_ATTRIBUTION)
        obs_server_mod._LATEST_ATTRIBUTION.clear()
    try:
        assert latest_attribution() is None
        publish_attribution(rec, kind="serving")
        # serving-only process: the unqualified read serves the table
        assert latest_attribution()["kind"] == "serving"
        publish_attribution({"dominant_phase": "device_compute",
                             "phases": {}})  # a fit report arrives
        assert latest_attribution()["dominant_phase"] == "device_compute"
        # ...but the serving slot survives, keyed
        assert latest_attribution("serving")["kind"] == "serving"
    finally:
        with obs_server_mod._attr_mu:
            obs_server_mod._LATEST_ATTRIBUTION.clear()
            obs_server_mod._LATEST_ATTRIBUTION.update(saved)


def test_scheduler_session_publishes_attribution_and_advice():
    """A real continuous-batching session leaves both surfaces
    populated — the serving half of the closed loop."""
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.ffconst import CompMode
    from flexflow_tpu.models import GPTConfig, build_gpt
    from flexflow_tpu.obs.server import latest_advice, latest_attribution
    from flexflow_tpu.serving.scheduler import ContinuousBatchingScheduler

    cfg = GPTConfig(vocab_size=32, max_positions=32, hidden_size=16,
                    num_heads=2, num_layers=1)
    ff = FFModel(FFConfig(batch_size=2, seed=0, ledger="off",
                          computation_mode=CompMode.INFERENCE))
    build_gpt(ff, 2, 4, cfg)
    ff.compile(optimizer=None, loss_type=None, metrics=[])
    sched = ContinuousBatchingScheduler(ff, name="adv_par", max_length=16,
                                        decode_slots=2, block_size=4)
    prompts = [np.array([1, 2, 3], np.int32), np.array([4, 5], np.int32)]
    futs = [sched.submit(p, 3) for p in prompts]
    for f in futs:
        f.result(timeout=300)
    sched.stop()
    attr = latest_attribution("serving")
    assert attr is not None and attr["kind"] == "serving"
    assert attr["dominant_phase"] in ("queue_wait", "prefill", "decode")
    adv = latest_advice()
    assert adv is not None and adv["kind"] == "serving"
    assert adv["suggestions"]


# -------------------------------------------------- sentinel integration
def test_sentinel_regression_row_carries_advice(tmp_path):
    sent = _tool("perf_sentinel")
    recs = [_fit_rec("input_wait", run_id=f"r{i}", ts=i + 1, value=v)
            for i, v in enumerate((10.0, 10.5, 9.9))]
    recs.append(_fit_rec("input_wait", run_id="r9", ts=9, value=3.0))
    _write_ledger(tmp_path, recs)
    out = sent.run_sentinel(ledger_dir=str(tmp_path), margin=0.2,
                            blackbox_dir=str(tmp_path / "bb"))
    (reg,) = out["regressions"]
    assert reg["advice"] is not None
    assert reg["advice"]["family"] == "prefetch"
    assert reg["dominant_phase"] == "input_wait"
    json.dumps(out)


def test_sentinel_counts_no_baseline_cohorts(tmp_path):
    sent = _tool("perf_sentinel")
    _write_ledger(tmp_path, [
        _fit_rec("input_wait", run_id="a1", ts=1, value=10.0),
        _fit_rec("input_wait", run_id="a2", ts=2, value=10.0,
                 label="other"),
    ])
    out = sent.run_sentinel(ledger_dir=str(tmp_path),
                            blackbox_dir=str(tmp_path / "bb"))
    assert out["no_baseline"] == 2 and out["judged"] == 0
    assert out["verdict"] == "no_baseline"


# --------------------------------------------------- explain integration
def test_explain_knob_diff_vs_best_prior(tmp_path):
    exp = _tool("explain_run")
    recs = [
        _fit_rec("input_wait", run_id="best1", ts=1, value=20.0,
                 knobs={"prefetch_depth": 2}),
        _fit_rec("input_wait", run_id="slow1", ts=2, value=8.0,
                 knobs={"prefetch_depth": 0}),
    ]
    _write_ledger(tmp_path, recs)
    doc = exp.explain(run_id="slow1", ledger_dir=str(tmp_path))
    bp = doc["cohort"]["best_prior"]
    assert bp["run_id"] == "best1" and bp["value"] == 20.0
    assert bp["knob_diff"]["prefetch_depth"] == {"this": 0, "best": 2}
    # advice + narration render without error
    assert doc["advice"]["suggestions"]
    text = exp._render_text(doc)
    assert "knobs changed" in text and "advice" in text
    assert doc["exit"] == 0


def test_explain_best_prior_is_actually_prior(tmp_path):
    """Explaining an OLDER record must not diff against a run appended
    after it — 'prior' is a time cutoff, not just an id exclusion."""
    exp = _tool("explain_run")
    _write_ledger(tmp_path, [
        _fit_rec("input_wait", run_id="old1", ts=1, value=8.0,
                 knobs={"prefetch_depth": 0}),
        _fit_rec("input_wait", run_id="new1", ts=5, value=30.0,
                 knobs={"prefetch_depth": 4}),
    ])
    doc = exp.explain(run_id="old1", ledger_dir=str(tmp_path))
    assert "best_prior" not in (doc["cohort"] or {})
    doc2 = exp.explain(run_id="new1", ledger_dir=str(tmp_path))
    assert doc2["cohort"]["best_prior"]["run_id"] == "old1"


def test_explain_narrates_experiments(tmp_path):
    exp = _tool("explain_run")
    fit = _fit_rec("input_wait", run_id="f1", ts=1)
    expe = {"schema": 1, "kind": "advisor_experiment", "run_id": "e1",
            "ts_unix_s": 2, "pid": 1, "machine": {"backend": "cpu"},
            "advisor": True, "label": "mlpsig", "target_run_id": "f1",
            "verdict": "accepted",
            "experiment": {"suggestion_id": "prefetch_depth=2",
                           "phase": "input_wait", "phase_ratio": 0.7,
                           "metric_ratio": 1.2, "verdict": "accepted",
                           "predicted": {"step_delta_frac": 0.5},
                           "measured": {"phase_delta_frac": 0.3}}}
    _write_ledger(tmp_path, [fit, expe])
    doc = exp.explain(run_id="f1", ledger_dir=str(tmp_path))
    (row,) = doc["advisor_experiments"]
    assert row["verdict"] == "accepted"
    assert row["phase_ratio"] == 0.7
    assert "accepted" in exp._render_text(doc)
    # the experiment record itself is selectable without crashing
    doc2 = exp.explain(run_id="e1", ledger_dir=str(tmp_path))
    assert doc2["exit"] == 0


# ---------------------------------------------------------- sim pricing
def test_mesh_reshape_candidates_pricing():
    from flexflow_tpu.sim.simulator import (mesh_reshape_candidates,
                                            ring_allreduce_factor)

    assert ring_allreduce_factor(1) == 0.0
    assert ring_allreduce_factor(8) == pytest.approx(1.75)
    cands = mesh_reshape_candidates({"data": 8})
    assert cands and all(
        int(np.prod(list(c["mesh"].values()))) == 8 for c in cands)
    assert all(c["mesh"].get("data", 1) >= 2 for c in cands)
    ratios = [c["allreduce_factor_ratio"] for c in cands]
    assert ratios == sorted(ratios)
    assert all(r < 1.0 for r in ratios)
    # nothing to split on small or dataless meshes
    assert mesh_reshape_candidates({"data": 2}) == []
    assert mesh_reshape_candidates({"pipe": 8}) == []


def test_schedule_bubble_candidates_pricing():
    from flexflow_tpu.sim.simulator import schedule_bubble_candidates

    rows = schedule_bubble_candidates("gpipe", 1, 2, 4, n_ops=16)
    kinds = {(r["schedule"], r["num_microbatches"]) for r in rows}
    assert ("gpipe", 8) in kinds  # the microbatch-doubling move
    assert any(r["schedule"] != "gpipe" for r in rows)
    bubbles = [r["bubble_fraction"] for r in rows]
    assert bubbles == sorted(bubbles)
    # the current schedule at the current settings is never a candidate
    assert ("gpipe", 4) not in kinds


# ---------------------------------------------------------- fit-tail hook
def test_fit_attaches_and_publishes_advice(tmp_path, monkeypatch):
    monkeypatch.setenv("FLEXFLOW_TPU_LEDGER_DIR", str(tmp_path))
    from flexflow_tpu import (ActiMode, DataType, FFConfig, FFModel,
                              LossType, SGDOptimizer)
    from flexflow_tpu.obs.ledger import scan_ledger
    from flexflow_tpu.obs.server import latest_advice

    cfg = FFConfig(batch_size=16, seed=0)
    ff = FFModel(cfg)
    x = ff.create_tensor((16, 16), DataType.FLOAT, name="adv_hx")
    t = ff.dense(x, 16, ActiMode.RELU, name="adv_hfc")
    t = ff.dense(t, 4, name="adv_hhead")
    ff.softmax(t, name="adv_hsm")
    ff.compile(optimizer=SGDOptimizer(lr=0.05),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               metrics=[])
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(64, 16)).astype(np.float32)
    ys = rng.integers(0, 4, size=(64, 1)).astype(np.int32)
    ff.fit(xs, ys, epochs=2, verbose=False)
    adv = (ff.fit_profile or {}).get("advice")
    assert adv is not None and adv["suggestions"]
    assert validate_report(adv) == []
    assert latest_advice() is not None
    # the advice block rides the ledger fit record
    fits = [r for r in scan_ledger(str(tmp_path))["runs"]
            if r.get("kind") == "fit"]
    assert fits and fits[-1].get("advice", {}).get("suggestions")


def test_fit_advisor_off_and_typo(tmp_path, monkeypatch):
    monkeypatch.setenv("FLEXFLOW_TPU_LEDGER_DIR", str(tmp_path))
    from flexflow_tpu import (ActiMode, DataType, FFConfig, FFModel,
                              LossType, SGDOptimizer)

    def _mlp(advisor):
        cfg = FFConfig(batch_size=16, seed=0, advisor=advisor)
        ff = FFModel(cfg)
        x = ff.create_tensor((16, 8), DataType.FLOAT, name="adv_ox")
        t = ff.dense(x, 8, ActiMode.RELU, name="adv_ofc")
        ff.softmax(ff.dense(t, 4, name="adv_oh"), name="adv_osm")
        ff.compile(optimizer=SGDOptimizer(lr=0.05),
                   loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                   metrics=[])
        return ff

    rng = np.random.default_rng(0)
    xs = rng.normal(size=(32, 8)).astype(np.float32)
    ys = rng.integers(0, 4, size=(32, 1)).astype(np.int32)
    ff = _mlp("off")
    ff.fit(xs, ys, epochs=1, verbose=False)
    assert "advice" not in (ff.fit_profile or {})
    ff2 = _mlp("typo")
    with pytest.raises(ValueError, match="advisor="):
        ff2.fit(xs, ys, epochs=1, verbose=False)
