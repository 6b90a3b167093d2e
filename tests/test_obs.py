"""Flight-recorder tests (obs/): span tracer on/off + Chrome trace
schema, metrics registry merge/export round-trips, sim-vs-measured
divergence on a small fit, and the serving request span tree."""

import json
import time

import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
from flexflow_tpu.models.mlp import build_mlp
from flexflow_tpu.obs.metrics import (BUCKET_BOUNDS, Histogram,
                                      MetricsRegistry, bucket_delta,
                                      metrics_registry)
from flexflow_tpu.obs.trace import (VIRTUAL_TID_BASE, Tracer,
                                    configure_tracer, span, tracer,
                                    validate_chrome_trace)


@pytest.fixture()
def armed_tracer():
    """Fresh, ENABLED global tracer for a test; disarmed afterwards so
    unrelated tests keep their zero-overhead fast path."""
    tr = tracer()
    was = tr.enabled
    tr.enabled = True
    tr.clear()
    yield tr
    tr.clear()
    tr.enabled = was


@pytest.fixture()
def disarmed_tracer():
    """The global tracer DISABLED for a test, whatever an earlier test of
    this process left it at (``trace="on"`` only ever ratchets it on)."""
    tr = tracer()
    was = tr.enabled
    tr.enabled = False
    yield tr
    tr.enabled = was


def _mlp(n_hidden=(16,), **cfg):
    ff = FFModel(FFConfig(batch_size=16, seed=0, **cfg))
    build_mlp(ff, 16, in_dim=8, hidden_dims=n_hidden, num_classes=4)
    ff.compile(optimizer=SGDOptimizer(lr=0.05),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               metrics=[])
    return ff


def _data(n=64):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, 8)).astype(np.float32)
    y = rng.integers(0, 4, size=(n, 1)).astype(np.int32)
    return x, y


# ------------------------------------------------------------------ tracer
def test_disabled_tracer_records_nothing_and_is_cheap(disarmed_tracer):
    tr = disarmed_tracer
    before = tr.event_count()
    t0 = time.perf_counter()
    for _ in range(100_000):
        with span("noop", cat="test", i=1):
            pass
    elapsed = time.perf_counter() - t0
    assert tr.event_count() == before
    # cheap: with no profiler running a span is an inactive
    # ``TraceAnnotation`` (a flag test in C++) plus one small Python
    # object; about a microsecond a span, so 100k take some 0.1 s here
    # (loose bound — the point is no locking and no ring append, not a
    # precise number).
    assert elapsed < 2.0, f"disabled span() too slow: {elapsed:.3f}s"


def _host_spans(trace_dir, prefix):
    """``{thread line: [(name, start_ns, end_ns), ...]}`` of the spans on
    ``/host:CPU`` whose names start with ``prefix``, read the way the
    benchmark reads a trace."""
    from benchmark import reduce

    trace = reduce.load_xplane(reduce.find_xplane(str(trace_dir)))
    out = {}
    for plane in trace["planes"]:
        if plane["name"] != reduce.HOST_PLANE:
            continue
        for line in plane["lines"]:
            evs = [(n, s, s + d) for n, s, d in line["events"]
                   if n.startswith(prefix)]
            if evs:
                out[line["name"]] = evs
    return out


def _profile(trace_dir):
    """A CPU profile with the host tracer on and Python's off: what a
    ``--trace 1`` run of the benchmark takes, less the device."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 2
    opts.python_tracer_level = 0
    return jax.profiler.trace(str(trace_dir), profiler_options=opts)


def test_span_is_a_profiler_annotation_with_the_ring_off(disarmed_tracer,
                                                         tmp_path):
    """A span needs no knob to reach the profiler's trace: with the ring
    disabled it still lands on ``/host:CPU``, from a worker thread, with
    its arguments kept out of its name; the ring stays empty."""
    import threading

    tr = disarmed_tracer
    before = tr.event_count()

    def work():
        with span("obs.test.outer", cat="test", step=3) as sp:
            with span("obs.test.inner", cat="test"):
                time.sleep(0.002)
            sp.set(found=1)

    with _profile(tmp_path):
        t = threading.Thread(target=work)
        t.start()
        t.join()
    assert tr.event_count() == before
    (evs,) = _host_spans(tmp_path, "obs.test.").values()
    by_name = {n: (s, e) for n, s, e in evs}
    assert set(by_name) == {"obs.test.outer", "obs.test.inner"}
    (o0, o1), (i0, i1) = by_name["obs.test.outer"], by_name["obs.test.inner"]
    assert o0 <= i0 and i1 <= o1 and i1 - i0 >= 2_000_000


def test_span_set_adds_arguments_to_the_ring_event(armed_tracer):
    with span("obs.test.set", cat="test", a=1) as sp:
        sp.set(b=2)
    (ev,) = [e for e in armed_tracer.events()
             if e["name"] == "obs.test.set"]
    assert ev["args"] == {"a": 1, "b": 2}


def test_fit_step_and_input_wait_reach_the_profile(disarmed_tracer,
                                                   tmp_path):
    """``fit.step`` and ``fit.input_wait`` are spans of the loop thread
    itself, so a profile of a fit holds them with tracing off."""
    ff = _mlp()
    x, y = _data()
    ff.fit(x, y, epochs=1, verbose=False)  # compile outside the profile
    with _profile(tmp_path):
        ff.fit(x, y, epochs=1, verbose=False)
    names = [n for evs in _host_spans(tmp_path, "fit.").values()
             for n, _, _ in evs]
    assert names.count("fit.step") == 4  # 64 rows in batches of 16
    assert names.count("fit.input_wait") >= 4


def test_span_events_have_required_fields_and_nest(armed_tracer, tmp_path):
    with span("outer", cat="test", k=1):
        with span("inner", cat="test"):
            pass
        with span("inner2", cat="test"):
            pass
    armed_tracer.instant("marker", cat="test", x=2)
    p = str(tmp_path / "trace.json")
    n = armed_tracer.export(p)
    payload = json.load(open(p))
    assert n == 4 and len(payload["traceEvents"]) == 4
    for ev in payload["traceEvents"]:
        for field in ("name", "ph", "ts", "pid", "tid"):
            assert field in ev, ev
    assert validate_chrome_trace(payload) == []
    # outer must CONTAIN both inners on the same track
    evs = {e["name"]: e for e in payload["traceEvents"]}
    out, inn = evs["outer"], evs["inner"]
    assert out["ph"] == "X" and evs["marker"]["ph"] == "i"
    assert out["ts"] <= inn["ts"]
    assert inn["ts"] + inn["dur"] <= out["ts"] + out["dur"] + 0.05
    assert out["tid"] == inn["tid"]


def test_validate_chrome_trace_rejects_partial_overlap():
    bad = {"traceEvents": [
        {"name": "a", "ph": "X", "ts": 0.0, "dur": 10.0, "pid": 1, "tid": 1},
        {"name": "b", "ph": "X", "ts": 5.0, "dur": 10.0, "pid": 1, "tid": 1},
    ]}
    assert validate_chrome_trace(bad) != []
    assert validate_chrome_trace({"traceEvents": [{"ph": "X"}]}) != []
    assert validate_chrome_trace([]) != []


def test_export_metadata_carries_cross_process_anchor(armed_tracer,
                                                      tmp_path):
    """Satellite: ``ts`` is relative to a per-process perf_counter
    epoch, so merged traces from different processes misalign unless the
    export records a wall-clock anchor + process label — and the
    validator enforces both on any payload that claims metadata."""
    with span("anchored", cat="test"):
        pass
    p = str(tmp_path / "trace.json")
    armed_tracer.export(p)
    payload = json.load(open(p))
    md = payload["metadata"]
    anchor = md["wall_clock_anchor_unix_s"]
    assert anchor > 0 and abs(anchor - time.time()) < 3600
    assert md["process"] and str(md["pid"]) in md["process"]
    assert validate_chrome_trace(payload) == []
    # a payload claiming metadata without the anchor/label is rejected
    assert validate_chrome_trace(
        {"traceEvents": [], "metadata": {}}) != []
    assert validate_chrome_trace(
        {"traceEvents": [],
         "metadata": {"wall_clock_anchor_unix_s": anchor}}) != []
    # in-memory event lists (no metadata claim) stay valid
    assert validate_chrome_trace({"traceEvents": []}) == []


def test_tracer_ring_buffer_bounds_memory():
    tr = Tracer(enabled=True, capacity=8)
    for i in range(50):
        tr.complete(f"e{i}", tr.now(), 0.0, cat="test")
    assert tr.event_count() == 8
    assert tr.events()[0]["name"] == "e42"  # oldest fell off


def test_configure_tracer_mode_guard():
    with pytest.raises(ValueError, match="trace="):
        configure_tracer(FFConfig(batch_size=8, trace="bogus"))


def test_fit_and_compile_emit_spans(armed_tracer):
    ff = _mlp(trace="on")
    x, y = _data()
    ff.fit(x, y, epochs=1, verbose=False)
    names = {e["name"] for e in armed_tracer.events()}
    assert {"compile", "compile.lower", "compile.validate_pcg",
            "fit.step", "fit.host_sync", "fit.input_wait"} <= names
    assert validate_chrome_trace(
        {"traceEvents": armed_tracer.events()}) == []


# ----------------------------------------------------------------- metrics
def test_registry_counter_gauge_histogram_round_trip():
    reg = MetricsRegistry()
    reg.counter("a.count").inc()
    reg.counter("a.count").inc(2)
    reg.gauge("a.gauge").set(1.5)
    for v in range(10):
        reg.histogram("a.lat").observe(v / 10.0)
    doc = reg.to_json()
    assert doc["a.count"] == 3
    assert doc["a.gauge"] == 1.5
    assert doc["a.lat"]["count"] == 10
    assert 0.0 <= doc["a.lat"]["p50"] <= doc["a.lat"]["p99"] <= 0.9
    # JSON round trip (histogram keeps count/sum/min/max)
    back = MetricsRegistry.from_json(json.loads(json.dumps(doc)))
    assert back.to_json()["a.count"] == 3
    assert back.to_json()["a.lat"]["count"] == 10
    # Prometheus text exposition
    text = reg.to_prometheus()
    assert "# TYPE flexflow_a_count counter" in text
    assert "# TYPE flexflow_a_gauge gauge" in text
    assert 'flexflow_a_lat{quantile="0.5"}' in text
    assert "flexflow_a_lat_count 10" in text


def test_histogram_snapshots_subtract_to_the_observations_between():
    """The buckets are cumulative in time at fixed bounds, so the
    difference of two snapshots is the distribution of exactly what was
    observed between them, however many samples the reservoir dropped."""
    h = Histogram(reservoir=4)
    for v in (0.001, 0.002, 0.5):
        h.observe(v)
    s0 = json.loads(json.dumps(h.to_json()))
    between = [0.098] * 470 + [2.5] + [0.00005, 1e4]
    for v in between:
        h.observe(v)
    s1 = json.loads(json.dumps(h.to_json()))
    delta = bucket_delta(s0, s1)
    assert sum(n for _, n in delta) == len(between)
    assert s1["count"] - s0["count"] == len(between)
    got = dict(delta)
    # each observation sits in the bucket whose bound is the first at or
    # above it; what lies past the last bound is in the bucket "inf"
    le_98ms = min(b for b in BUCKET_BOUNDS if b >= 0.098)
    le_2500ms = min(b for b in BUCKET_BOUNDS if b >= 2.5)

    def key(bound):  # a bucket is keyed by its bound at six digits
        return float(f"{bound:.6g}")

    assert got[key(le_98ms)] == 470
    assert got[key(le_2500ms)] == 1
    assert got[key(BUCKET_BOUNDS[0])] == 1
    assert got[float("inf")] == 1
    # four bounds a doubling: a 2.5 s stall and a 98 ms step lie 19 apart
    assert (BUCKET_BOUNDS.index(le_2500ms)
            - BUCKET_BOUNDS.index(le_98ms)) == 19
    assert le_98ms / 0.098 < 2 ** 0.25
    # no earlier snapshot: everything up to the later one
    assert sum(n for _, n in bucket_delta(None, s1)) == s1["count"]


def test_histogram_merge_adds_buckets_and_json_keeps_them():
    a, b = MetricsRegistry(), MetricsRegistry()
    for v in (0.01, 0.01, 3.0):
        a.histogram("lat").observe(v)
    for v in (0.01, 40.0):
        b.histogram("lat").observe(v)
    a.merge(MetricsRegistry.from_json(json.loads(json.dumps(b.to_json()))))
    doc = a.to_json()["lat"]
    assert doc["count"] == 5 and sum(doc["buckets"].values()) == 5
    rows = bucket_delta(None, doc)
    assert [n for _, n in rows] == [3, 1, 1]
    assert rows[0][0] >= 0.01 > rows[0][0] / 2 ** 0.25
    assert Histogram().to_json() == {"count": 0}


def test_registry_merge():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("c").inc(2)
    b.counter("c").inc(3)
    b.gauge("g").set(7.0)
    a.histogram("h").observe(1.0)
    b.histogram("h").observe(3.0)
    a.merge(b)
    doc = a.to_json()
    assert doc["c"] == 5 and doc["g"] == 7.0
    assert doc["h"]["count"] == 2 and doc["h"]["sum"] == 4.0
    # type mismatch is an error, not silent data corruption
    c = MetricsRegistry()
    c.gauge("c").set(1.0)
    with pytest.raises(TypeError):
        a.merge(c)


def test_histogram_merge_keeps_both_reservoir_windows():
    """Satellite regression: merging used to append ALL of other's
    window into the maxlen-bounded deque, evicting every one of self's
    samples whenever other had >= reservoir entries — merged percentiles
    reflected only one process. The merge must keep a proportional,
    interleaved sample of BOTH windows."""
    from flexflow_tpu.obs.metrics import Histogram

    a, b = Histogram(reservoir=64), Histogram(reservoir=64)
    for _ in range(100):  # both windows individually overflow the cap
        a.observe(1.0)
        b.observe(3.0)
    a.merge(b)
    assert a.count == 200 and a.sum == 400.0
    assert a.min == 1.0 and a.max == 3.0
    vals = list(a._recent)
    assert len(vals) == 64  # still bounded
    n1, n3 = vals.count(1.0), vals.count(3.0)
    assert n1 > 0 and n3 > 0, "one process's window was evicted entirely"
    assert abs(n1 - n3) <= 2  # equal-sized windows share ~equally
    # pooled percentiles span both processes
    assert a.percentile(0.25) == 1.0 and a.percentile(0.75) == 3.0
    # interleaved, not concatenated: future appends evict fairly
    assert vals[0] != vals[1]
    # asymmetric WINDOW sizes keep proportional shares (48 vs 16 of 64)
    c, d = Histogram(reservoir=64), Histogram(reservoir=64)
    for _ in range(48):
        c.observe(1.0)
    for _ in range(16):
        d.observe(3.0)
    for _ in range(16):  # overflow the merged capacity
        d.observe(3.0)
    c.merge(d)
    cv = list(c._recent)
    assert len(cv) == 64
    # 48:32 windows -> ~3:2 shares of the 64-slot merged reservoir
    assert 34 <= cv.count(1.0) <= 42 and 22 <= cv.count(3.0) <= 30
    # small merges (under the cap) keep every sample
    e, f = Histogram(reservoir=64), Histogram(reservoir=64)
    e.observe(1.0)
    f.observe(3.0)
    e.merge(f)
    assert sorted(e._recent) == [1.0, 3.0]


def test_fit_feeds_registry_counters():
    before = metrics_registry().counter("fit.steps").value
    ff = _mlp()
    x, y = _data()
    ff.fit(x, y, epochs=2, verbose=False)
    after = metrics_registry().counter("fit.steps").value
    assert after - before == 8  # 64 samples / 16 batch * 2 epochs


# -------------------------------------------------------------- divergence
def test_divergence_record_on_two_op_mlp_fit():
    ff = _mlp(n_hidden=(), divergence="on")  # dense + softmax: 2 ops
    assert len(ff.compiled.ops) == 2
    x, y = _data()
    ff.fit(x, y, epochs=2, verbose=False)
    from flexflow_tpu.runtime.profiling import divergence_report

    d = divergence_report(ff)
    assert d is not None
    assert d["source"] in ("search", "schedule_model", "simulator")
    assert d["predicted_step_s"] > 0 and d["measured_step_s"] > 0
    assert d["e2e_ratio"] == pytest.approx(
        d["measured_step_s"] / d["predicted_step_s"], rel=1e-3)
    assert len(d["epoch_ratios"]) == 2
    names = {r["name"] for r in d["per_op"]}
    assert names == {op.name for op in ff.compiled.ops}
    for r in d["per_op"]:
        assert r["measured_ms"] >= 0 and r["ratio"] is not None


def test_divergence_obs001_fires_past_threshold(capsys):
    # threshold 0: ANY measurable error fires the warn-level finding
    ff = _mlp(n_hidden=(), divergence="e2e", divergence_threshold=0.0)
    x, y = _data()
    ff.fit(x, y, epochs=1, verbose=False)
    d = ff.fit_profile["divergence"]
    assert d["threshold"] == 0.0
    assert d["findings"] and d["findings"][0]["code"] == "OBS001"
    assert d["findings"][0]["severity"] == "warning"
    assert ff.obs_report is not None and not ff.obs_report.errors
    assert "OBS001" in capsys.readouterr().out
    # e2e mode skips the expensive per-op comparison
    assert "per_op" not in d


def test_stale_obs001_cleared_by_next_fit(capsys):
    # regression: fit #1 fires OBS001; fit #2 with divergence off (or
    # nothing to compare) must not leave the previous verdict attached
    ff = _mlp(n_hidden=(), divergence="e2e", divergence_threshold=0.0)
    x, y = _data()
    ff.fit(x, y, epochs=1, verbose=False)
    assert ff.obs_report is not None
    ff.config.divergence = "off"
    ff.fit(x, y, epochs=1, verbose=False)
    assert ff.obs_report is None


def test_divergence_off_by_default_and_mode_guard():
    ff = _mlp()
    x, y = _data()
    ff.fit(x, y, epochs=1, verbose=False)
    assert "divergence" not in ff.fit_profile
    ff2 = _mlp(divergence="bogus")
    with pytest.raises(ValueError, match="divergence="):
        ff2.fit(x, y, epochs=1, verbose=False)


def test_obs001_in_code_catalog():
    from flexflow_tpu.analysis import CODE_CATALOG

    assert "OBS001" in CODE_CATALOG
    assert "OBS002" in CODE_CATALOG


# ----------------------------------------------------------------- serving
def test_serving_request_span_tree(armed_tracer):
    from flexflow_tpu.serving.engine import InferenceEngine

    ff = FFModel(FFConfig(batch_size=8, seed=0))
    build_mlp(ff, 8, in_dim=8, hidden_dims=(16,), num_classes=4)
    ff.compile(optimizer=None, loss_type=None, metrics=[])
    eng = InferenceEngine(batch_timeout_s=0.002)
    eng.register_ffmodel(ff, name="obs_serve")
    rng = np.random.default_rng(0)
    for _ in range(3):
        out = eng.infer("obs_serve",
                        [rng.normal(size=(8,)).astype(np.float32)])
        assert out.shape == (4,)
    eng.stop()
    evs = [e for e in armed_tracer.events() if e.get("cat") == "serving"]
    # one tree per request, each on its own virtual track
    tracks = {}
    for e in evs:
        assert e["tid"] >= VIRTUAL_TID_BASE
        tracks.setdefault(e["tid"], []).append(e)
    assert len(tracks) == 3
    for tid, tes in tracks.items():
        by_name = {e["name"]: e for e in tes}
        assert set(by_name) == {"serving.request", "serving.queue_wait",
                                "serving.batch_assembly", "serving.infer",
                                "serving.reply"}
        req = by_name["serving.request"]
        end = req["ts"] + req["dur"]
        for name, e in by_name.items():
            if name == "serving.request":
                continue
            assert e["ts"] >= req["ts"] - 0.05
            assert e["ts"] + e["dur"] <= end + 0.05, name
    assert validate_chrome_trace({"traceEvents": evs}) == []
    reg = metrics_registry()
    assert reg.counter("serving.requests").value >= 3
    assert reg.histogram("serving.queue_wait_s").count >= 3


# -------------------------------------------------------------- obs_report
def test_obs_report_tool_smoke():
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "obs_report", os.path.join(os.path.dirname(__file__), os.pardir,
                                   "tools", "obs_report.py"))
    obs_report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(obs_report)
    tr = tracer()
    was = tr.enabled
    try:
        out = obs_report.run_report(samples=32, epochs=2, requests=2)
    finally:
        tr.enabled = was  # the tool arms the global tracer
        tr.clear()
    assert out["exit"] == 0, out
    assert out["trace"]["events"] > 0 and out["trace"]["valid"]
    assert out["divergence"]["e2e_ratio"] and out["divergence"]["per_op"]
    assert out["pipeline"]["schedule"] in ("gpipe", "1f1b", "interleaved")
    assert "fit.steps" in out["metrics"]
    assert "serving.requests" in out["metrics"]
    json.dumps(out)  # one-line-JSON-able