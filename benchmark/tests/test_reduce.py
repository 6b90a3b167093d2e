"""The trace reduction on a hand-made trace with hand-worked answers,
on a small trace recorded on the chip, and the loader on a trace
recorded here."""

import json
import os

import pytest

from benchmark import reduce as R

HERE = os.path.dirname(os.path.abspath(__file__))

# One chip; times in ns. Operations: fusion [100,150) copy [140,200)
# (overlapping: nested ops must not count twice) all-reduce [220,260)
# fusion [240,250) (compute under the collective) fusion [300,320).
# Programs: step(1) [100,200), step(2) [220,320).
# Host spans: window [0,400), fit [50,350), sync [255,300), and one of the
# host tracer's own, Wait(7) [0,60).
HAND = {"planes": [
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [
            ["fusion.1", 100, 50], ["copy.2", 140, 60],
            ["all-reduce.3", 220, 40], ["fusion.4", 240, 10],
            ["fusion.5", 300, 20]]},
        {"name": "XLA Modules", "events": [
            ["jit_step(1)", 100, 100], ["jit_step(2)", 220, 100]]}]},
    {"name": "/host:CPU", "lines": [
        {"name": "main", "events": [
            ["bench.window", 0, 400], ["bench.fit", 50, 300],
            ["bench.sync", 255, 45], ["Wait(7)", 0, 60]]}]}]}


def test_hand_worked_numbers():
    r = R.reduce_trace(HAND)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(400e-9)
    # busy: [100,200) + [220,260) + [300,320) = 160
    assert r["busy_s"] == pytest.approx(160e-9)
    assert r["idle_share"] == pytest.approx(1 - 160 / 400)
    assert r["programs"] == {"jit_step": {"count": 2.0,
                                          "device_s": pytest.approx(200e-9)}}
    assert R.program_time(r, r"step")["count"] == 2.0
    assert R.program_time(r, r"decode") is None
    ops = dict(r["ops"])
    assert ops["fusion"] == pytest.approx(80e-9)
    assert ops["copy"] == pytest.approx(60e-9)
    # the collective runs 40 ns, 10 of them under a fusion
    assert r["collective_s"] == pytest.approx(40e-9)
    assert r["collective_exposed_s"] == pytest.approx(30e-9)
    # idle: [0,100) [200,220) [260,300) [320,400) = 240; each interval goes
    # to the shortest span over its midpoint: 50 -> Wait (60 long, fit is
    # 300), 210 -> fit, 280 -> sync, 360 -> none (fit ends at 350)
    gaps = dict(r["idle_gaps"])
    assert gaps == {"Wait": pytest.approx(100e-9),
                    "bench.sync": pytest.approx(40e-9),
                    "bench.fit": pytest.approx(20e-9),
                    "(no span)": pytest.approx(80e-9)}
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_explicit_window_clips_everything():
    r = R.reduce_trace(HAND, window=(150, 250))
    # busy inside: [150,200) + [220,250) = 80 of 100
    assert r["busy_s"] == pytest.approx(80e-9)
    assert r["programs"] == {}  # no program lies wholly inside


def test_two_chips_are_averaged():
    two = json.loads(json.dumps(HAND))
    second = json.loads(json.dumps(HAND["planes"][0]))
    second["name"] = "/device:TPU:1"
    second["lines"][0]["events"] = [["fusion.1", 100, 100]]
    two["planes"].insert(1, second)
    r = R.reduce_trace(two)
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx((160e-9 + 100e-9) / 2)
    assert r["programs"]["jit_step"]["count"] == 2.0  # per chip


def test_interval_arithmetic():
    assert R.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert R.subtract([(0, 10), (20, 30)], [(2, 3), (5, 25)]) == \
        [(0, 2), (3, 5), (25, 30)]
    assert R.gaps([(2, 4), (6, 8)], 0, 10) == [(0, 2), (4, 6), (8, 10)]
    assert R.total(R.clip([(0, 10)], 3, 5)) == 2


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        R.reduce_trace({"planes": [HAND["planes"][1]]})


def test_loader_reads_a_trace_recorded_here(tmp_path):
    """The profiler wrapper and ``load_xplane`` on this machine's CPU: the
    benchmark's spans come back on the trace's clock, nested."""
    import jax
    import jax.numpy as jnp

    from benchmark import device

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    prof = device.Profiler(True, str(tmp_path / "trace"))
    prof.start()
    with prof.span("window"):
        with prof.span("inner"):
            f(x).block_until_ready()
    prof.stop()
    assert prof.done and prof.window_s > 0
    spans = {n: (s, e) for n, s, e in R._spans(
        R.load_xplane(R.find_xplane(prof.out_dir)))}
    assert set(spans) == {"bench.window", "bench.inner"}
    assert spans["bench.window"][0] <= spans["bench.inner"][0]
    assert spans["bench.inner"][1] <= spans["bench.window"][1]
    assert device.Profiler(False, str(tmp_path / "off")).span("x") is not None


def test_recorded_tpu_trace_gives_known_numbers():
    """A piece of a trace recorded on the v5e (the first 11 ms of a
    ``fit`` call of gpt2-medium.fit-1024): the numbers below were read
    once and checked against a count made another way — every
    nanosecond of the window marked busy or not in an array."""
    import numpy as np

    with open(os.path.join(HERE, "data", "recorded_fit_trace.json")) as f:
        trace = json.load(f)
    lo, hi = 46_000_000, 57_000_000
    r = R.reduce_trace(trace, window=(lo, hi))
    assert r["devices"] == 1 and r["window_s"] == pytest.approx(0.011)
    assert r["busy_s"] == pytest.approx(0.006519619, abs=1e-9)
    assert r["idle_share"] == pytest.approx(0.407307, abs=1e-6)
    ops = [ln for ln in trace["planes"][0]["lines"]
           if ln["name"] == "XLA Ops"][0]["events"]
    busy = np.zeros(hi - lo, bool)
    for _, s, d in ops:
        busy[max(s, lo) - lo:max(min(s + d, hi), lo) - lo] = True
    assert int(busy.sum()) == round(r["busy_s"] * 1e9)
    # the whole HLO text of an operation is its name on the TPU
    top = dict(r["ops"])
    assert top["fusion"] == pytest.approx(0.003152943, abs=1e-9)
    assert top["convert_reduce_fusion"] == pytest.approx(0.001882129, abs=1e-9)
    # every idle nanosecond lies under the benchmark's span of the call
    assert dict(r["idle_gaps"]) == {
        "bench.fit": pytest.approx(r["window_s"] - r["busy_s"])}
    # two executions of the step program lie wholly in a wider window
    wide = R.reduce_trace(trace, window=(45_000_000, 320_000_000))
    step = R.program_time(wide, r"train_step")
    assert step["count"] == 2.0
    assert step["device_s"] / 2 == pytest.approx(0.132715314, abs=1e-9)
