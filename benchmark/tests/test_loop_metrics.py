"""The readers of the scheduler's own clock (``stats()["loop"]``, PR 25)
on a toy closed-loop and a toy open-loop run on the CPU, and the reader
of the idle time no host span covers on a small synthetic reduction. A
program without the counters, as any parent of PR 25 is, makes every
one of them report nothing."""

import copy
import json
import os
import time

import pytest

from benchmark import loop
from benchmark.tests import toy

SEED = 2 ** 31 + 79
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
LOOP_METRICS = ["loop_step_wall_ms", "loop_host_ms", "loop_fetch_ms",
                "loop_prefill_share", "loop_step_wall_max_ms"]


@pytest.fixture(scope="module")
def layout(tmp_path_factory):
    from benchmark.spec import Layout

    return Layout(toy.make_checkout(str(tmp_path_factory.mktemp("checkout"))))


def _facts(layout, workload):
    """One untraced toy run of the cell's kind, with all it found: what
    ``run_cell`` hands the readers as ``run``."""
    import jax

    from benchmark import run

    cell = layout.cell(workload)
    ctx = run.Ctx(layout, cell, SEED, 1.0, False, jax.devices()[:1],
                  time.perf_counter())
    layout.kind(cell["mix"]["kind"]).run(ctx)
    assert ctx.checks.correct, ctx.checks.rows
    return {"facts": ctx.facts, "trace": None}


@pytest.fixture(scope="module")
def closed(layout):
    return _facts(layout, "gpt2-toy.serve-offline-toy")


@pytest.fixture(scope="module")
def opened(layout):
    return _facts(layout, "gpt2-toy.serve-chat-toy")


@pytest.mark.parametrize("metric", LOOP_METRICS)
def test_loop_reader_gives_a_number_from_the_runs_facts(layout, closed,
                                                        metric):
    value = layout.reader(metric).read(closed)
    assert isinstance(value, float) and value > 0
    json.dumps(value)
    # the same run from a program without the counters: nothing, no error
    parent = copy.deepcopy(closed)
    for k in ("stats0", "stats1"):
        del parent["facts"][k]["loop"]
    assert layout.reader(metric).read(parent) is None
    assert layout.reader(metric).read({"facts": {}, "trace": None}) is None


def test_loop_readers_agree_with_each_other_and_the_window(layout, closed):
    read = {m: layout.reader(m).read(closed) for m in LOOP_METRICS}
    w = loop.window(closed)
    f = closed["facts"]
    # a pass is counted when it ends, the decoder's step when it is
    # dispatched: a reading taken mid-step differs by that one
    assert abs(w["steps"] - f["decode_steps_in_window"]) <= 1
    # the phases telescope: steps times the step's wall time is the window
    # (the two readings of stats() are taken at its two ends)
    assert read["loop_step_wall_ms"] * w["steps"] / 1e3 == \
        pytest.approx(f["window_s"], rel=0.02)
    assert w["phase_s"]["wait"] < 0.02 * f["window_s"]  # a closed loop
    parts = (read["loop_host_ms"] + read["loop_fetch_ms"]
             + 1e3 * w["phase_s"]["prefill"] / w["steps"])
    assert parts == pytest.approx(read["loop_step_wall_ms"])
    assert 0 <= read["loop_prefill_share"] < 100
    # the longest step is no shorter than the mean one, and a bucket's
    # bound overstates by less than a quarter of a doubling
    rows = loop.bucket_rows(closed, "step_wall")
    assert sum(n for _, n in rows) == w["steps"]
    s0 = f["stats0"]["loop"]["step_wall"]
    s1 = f["stats1"]["loop"]["step_wall"]
    mean_ms = 1e3 * (s1["sum"] - s0.get("sum", 0.0)) / w["steps"]
    assert read["loop_step_wall_max_ms"] >= mean_ms
    assert read["loop_step_wall_max_ms"] <= \
        1e3 * s1["max"] * 2 ** 0.25 * 1.001


def test_token_gap_reader_on_the_open_loop_kinds_stats(layout, opened):
    reader = layout.reader("token_gap_p95_ms")
    value = reader.read(opened)
    assert isinstance(value, float) and value > 0
    rows = loop.bucket_rows(opened, "token_gap")
    f = opened["facts"]
    # one gap for every token of the window that was not a request's first
    tokens = f["stats1"]["tokens"] - f["stats0"]["tokens"]
    firsts = (f["stats1"]["prefill_prompts"]
              - f["stats0"]["prefill_prompts"])
    assert sum(n for _, n in rows) == tokens - firsts
    assert rows[0][0] <= value / 1e3 <= rows[-1][0]
    parent = copy.deepcopy(opened)
    del parent["facts"]["stats0"]["loop"]
    assert reader.read(parent) is None


def test_bucket_percentile_is_the_bound_of_the_bucket_that_holds_it():
    rows = [(0.001, 90), (0.002, 5), (0.064, 4), (2.0, 1)]
    assert loop.bucket_percentile(rows, 0.5) == 0.001
    assert loop.bucket_percentile(rows, 0.9) == 0.001  # the 90th of 100
    assert loop.bucket_percentile(rows, 0.95) == 0.002  # the 95th
    assert loop.bucket_percentile(rows, 0.97) == 0.064
    assert loop.bucket_percentile(rows, 1.0) == 2.0
    assert loop.bucket_percentile([(0.5, 1)], 0.95) == 0.5


def test_idle_no_span_share_on_a_small_reduction(layout):
    reader = layout.reader("idle_no_span_share")
    assert reader.read({"facts": {}, "trace": None}) is None
    # the ledger's PR 24 reading: 0.155 s of 0.166 s idle under no span
    tr = {"window_s": 2.95, "busy_s": 2.784,
          "idle_gaps": [["(no span)", 0.155], ["np.asarray", 0.008],
                        ["Transpose::ExecuteChunk", 0.003]]}
    assert reader.read({"facts": {}, "trace": tr}) == pytest.approx(
        100 * 0.155 / 0.166)
    # every gap under a span of the scheduler's thread: none left over
    tr["idle_gaps"] = [["serving.loop.fetch", 0.150],
                       ["serving.loop.sample", 0.016]]
    assert reader.read({"facts": {}, "trace": tr}) == 0.0
    tr["busy_s"] = tr["window_s"]  # never idle: no share to speak of
    assert reader.read({"facts": {}, "trace": tr}) is None


def test_idle_gaps_take_the_programs_span_names_from_a_trace():
    """``reduce_trace`` hands an idle gap to the shortest host span over
    its midpoint, whoever recorded it: a ``serving.loop.*`` annotation of
    the program's scheduler thread needs no edit to the reduction."""
    from benchmark import reduce

    ms = 1_000_000
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ["fusion.1", 0, 10 * ms], ["copy.2", 20 * ms, 10 * ms],
                ["fusion.3", 34 * ms, 6 * ms]]},
            {"name": "XLA Modules", "events": []}]},
        {"name": "/host:CPU", "lines": [
            {"name": "ffserve-gen-lm", "events": [
                ["serving.loop.step", 0, 40 * ms],
                ["serving.loop.sample", 10 * ms, 10 * ms],
                ["serving.loop.fetch", 30 * ms, 3 * ms]]}]}]}
    red = reduce.reduce_trace(trace, window=(0, 40 * ms))
    gaps = dict(red["idle_gaps"])
    assert gaps == {"serving.loop.sample": pytest.approx(0.010),
                    "serving.loop.fetch": pytest.approx(0.004)}


def test_the_six_entries_are_in_the_benchmark_and_name_their_readers():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in LOOP_METRICS + ["idle_no_span_share"]:
        m = entries[name]  # by name: wherever the list holds it
        assert "gpt2-large.serve-offline" in m["workloads"]
        assert m["moves"] == "serve_tokens_per_s" and m["better"] == "lower"
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".py"))
    assert "token_gap_p95_ms" not in entries  # held back with its cell
