"""Set-up split from inside: the compile listener's stage sums and the six
phase counters (``utils/compile_cache.py`` ``compile_stats``), at toy size
on the CPU. Counts and orderings only: no number here is a speed.
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.ffconst import CompMode
from flexflow_tpu.models import GPTConfig, build_gpt
from flexflow_tpu.obs.metrics import metrics_registry
from flexflow_tpu.obs.trace import tracer
from flexflow_tpu.serving.engine import GenerationInstance
from flexflow_tpu.utils.compile_cache import (compile_stats,
                                              configure_compile_cache)
from test_obs import _host_spans, _profile

JAX_KEYS = ("compiles", "compile_s", "cache_hits", "cache_misses",
            "trace_s", "mlir_s", "cache_read_s")
SETUP_KEYS = ("model_compile_s", "lower_s", "init_params_s", "audit_s",
              "instance_build_s", "calibration_s")
CFG = GPTConfig(vocab_size=50, max_positions=32, hidden_size=32,
                num_heads=4, num_layers=2)


def _compiled(**cfg):
    ff = FFModel(FFConfig(batch_size=3, seed=0,
                          computation_mode=CompMode.INFERENCE, **cfg))
    build_gpt(ff, 3, 8, CFG)
    ff.compile(optimizer=None, loss_type=None, metrics=[])
    return ff


def _delta(after, before):
    return {k: after[k] - before[k] for k in after}


@pytest.fixture(scope="module")
def setup_run():
    """One ``FFModel.compile`` and one ``GenerationInstance`` over an int8
    pool (so that the calibration runs) under ``trace="on"``: what the
    counters gained, the wall time around each, and the ring's events."""
    tr = tracer()
    was = tr.enabled
    tr.clear()
    stats0 = compile_stats()
    t0 = time.perf_counter()
    ff = _compiled(trace="on")
    t1 = time.perf_counter()
    inst = GenerationInstance(ff, decode_slots=3, block_size=8,
                              max_length=32, kv_dtype="int8")
    t2 = time.perf_counter()
    try:
        yield {"stats": _delta(compile_stats(), stats0),
               "compile_wall_s": t1 - t0, "build_wall_s": t2 - t1,
               "events": list(tr.events()), "inst": inst}
    finally:
        inst.stop()
        tr.clear()
        tr.enabled = was


def test_compile_stats_has_the_thirteen_keys(setup_run):
    stats = compile_stats()
    assert tuple(stats) == JAX_KEYS + SETUP_KEYS
    assert all(isinstance(v, float) for v in stats.values())
    # each is a registry counter under its prefix, always on
    reg = metrics_registry()
    for k in JAX_KEYS:
        assert reg.get(f"jax.{k}") is not None, k
    for k in SETUP_KEYS:
        assert reg.get(f"setup.{k}") is not None, k


@pytest.mark.parametrize("key", SETUP_KEYS + ("trace_s", "mlir_s"))
def test_a_phase_that_ran_counted_seconds(setup_run, key):
    assert setup_run["stats"][key] > 0.0


@pytest.mark.parametrize("part,whole", [
    ("lower_s", "model_compile_s"),
    ("init_params_s", "lower_s"),
    ("audit_s", "model_compile_s"),
    ("calibration_s", "instance_build_s"),
    ("cache_read_s", "compile_s"),
])
def test_a_part_is_no_more_than_what_holds_it(setup_run, part, whole):
    stats = setup_run["stats"]
    assert 0.0 <= stats[part] <= stats[whole]


@pytest.mark.parametrize("phase,wall", [
    ("model_compile_s", "compile_wall_s"),
    ("instance_build_s", "build_wall_s"),
])
def test_a_phase_is_under_the_wall_time_around_it(setup_run, phase, wall):
    assert setup_run["stats"][phase] <= setup_run[wall]


def test_the_audits_histogram_is_gone_and_its_profile_key_stays(setup_run):
    assert not [n for n in metrics_registry().names()
                if n.startswith("audit.") and n.endswith("_s")]
    ff = setup_run["inst"]._ff
    assert ff.audit_profile["wall_time_s"] > 0.0


def test_a_second_compile_adds_to_the_sums(setup_run):
    before = compile_stats()
    t0 = time.perf_counter()
    _compiled()
    wall = time.perf_counter() - t0
    gained = _delta(compile_stats(), before)
    for k in ("model_compile_s", "lower_s", "init_params_s", "audit_s"):
        assert 0.0 < gained[k] <= wall, k
    assert gained["instance_build_s"] == gained["calibration_s"] == 0.0
    assert compile_stats()["model_compile_s"] > before["model_compile_s"] > 0


def test_a_jits_request_is_one_compile_and_its_seconds():
    """``jax.compiles`` and ``jax.compile_s`` count what they counted: a
    ``jit``'s first call is one request, and its seconds are the event's."""
    from jax._src import monitoring as _monitoring

    configure_compile_cache()
    seen = []

    def listen(event, duration, **_kw):
        seen.append((event, duration))

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        before = compile_stats()
        out = jax.jit(lambda x: jnp.tanh(x) * 3.25 + 0.125)(
            np.ones((3, 5), np.float32))
        out.block_until_ready()
        gained = _delta(compile_stats(), before)
    finally:
        _monitoring.unregister_event_duration_listener(listen)
    requests = [d for e, d in seen if e.endswith("backend_compile_duration")]
    assert len(requests) == 1
    assert gained["compiles"] == 1.0
    assert gained["compile_s"] == pytest.approx(requests[0])
    for key, event in (("trace_s", "jaxpr_trace_duration"),
                       ("mlir_s", "jaxpr_to_mlir_module_duration"),
                       ("cache_read_s", "cache_retrieval_time_sec")):
        assert gained[key] == pytest.approx(
            sum(d for e, d in seen if e.endswith(event))), key
    assert gained["trace_s"] > 0.0 and gained["mlir_s"] > 0.0
    assert gained["cache_read_s"] <= gained["compile_s"]
    # the phases are the program's own: a bare jit moves none of them
    assert all(gained[k] == 0.0 for k in SETUP_KEYS)


@pytest.mark.parametrize("event,key,counts", [
    ("/jax/core/compile/backend_compile_duration", "compile_s", 1.0),
    ("/jax/core/compile/jaxpr_trace_duration", "trace_s", 0.0),
    ("/jax/core/compile/jaxpr_to_mlir_module_duration", "mlir_s", 0.0),
    ("/jax/compilation_cache/cache_retrieval_time_sec", "cache_read_s", 0.0),
    ("/jax/compilation_cache/compile_time_saved_sec", None, 0.0),
])
def test_an_event_feeds_one_sum_and_nothing_else(event, key, counts):
    configure_compile_cache()
    before = compile_stats()
    jax.monitoring.record_event_duration_secs(event, 0.25, fun_name="f")
    gained = _delta(compile_stats(), before)
    assert gained.pop("compiles") == counts
    for k, v in gained.items():
        assert v == (0.25 if k == key else 0.0), k


def test_the_set_up_spans_are_in_the_ring_and_nested(setup_run):
    spans = {}
    for e in setup_run["events"]:
        if e.get("ph") == "X":
            spans.setdefault(e["name"], e)  # the first of each name

    def inside(inner, outer):
        a, b = spans[inner], spans[outer]
        return (a["tid"] == b["tid"] and b["ts"] <= a["ts"]
                and a["ts"] + a["dur"] <= b["ts"] + b["dur"])

    for name in ("compile", "compile.lower", "compile.init_params",
                 "compile.audit", "serving.build", "serving.build.calibrate"):
        assert name in spans, name
    assert inside("compile.init_params", "compile.lower")
    assert inside("compile.lower", "compile")
    assert inside("compile.audit", "compile")
    assert inside("serving.build.calibrate", "serving.build")
    done = spans["compile"]["ts"] + spans["compile"]["dur"]
    assert done <= spans["serving.build"]["ts"]
    assert spans["compile"]["args"]["n_ops"] > 0
    assert spans["compile"]["args"]["pipelined"] is False


def test_the_set_up_spans_are_on_the_host_plane_of_a_profile(tmp_path):
    """With the ring off and no knob set, a profile taken over a set-up
    holds every phase on ``/host:CPU``, a phase inside what holds it."""
    with _profile(tmp_path):
        inst = GenerationInstance(_compiled(), decode_slots=3, block_size=8,
                                  max_length=32, kv_dtype="int8")
    inst.stop()
    # the thread that built both holds them all, on one line
    (compile_evs,) = _host_spans(tmp_path, "compile").values()
    (build_evs,) = _host_spans(tmp_path, "serving.build").values()
    found = {}
    for name, start, end in compile_evs + build_evs:
        found.setdefault(name, (start, end))
    assert {"compile", "compile.lower", "compile.init_params",
            "compile.audit", "serving.build",
            "serving.build.calibrate"} <= set(found)

    def inside(inner, outer):
        (a0, a1), (b0, b1) = found[inner], found[outer]
        return b0 <= a0 and a1 <= b1

    assert inside("compile.init_params", "compile.lower")
    assert inside("compile.lower", "compile")
    assert inside("compile.audit", "compile")
    assert inside("serving.build.calibrate", "serving.build")


# ---- the benchmark's ten readers of these sums ------------------------------

READS = {"setup_jax_trace_s": "trace_s", "setup_jax_mlir_s": "mlir_s",
         "setup_cache_read_s": "cache_read_s",
         "setup_model_compile_s": "model_compile_s",
         "setup_lower_s": "lower_s", "setup_init_params_s": "init_params_s",
         "setup_audit_s": "audit_s",
         "setup_instance_build_s": "instance_build_s",
         "setup_calibration_s": "calibration_s"}


def _read(entry, facts, setup_s=100.0, lead_in_s=None):
    """What the benchmark's reader of ``entry`` makes of a run whose
    ``facts["jax"]`` is ``facts``."""
    from benchmark.spec import Layout

    mix = {} if lead_in_s is None else {"lead_in_s": lead_in_s}
    return Layout().reader(entry).read(
        {"facts": {"jax": facts}, "setup_s": setup_s, "mix": mix})


@pytest.mark.parametrize("entry", sorted(READS) + ["setup_unattributed_s"])
def test_a_reader_finds_nothing_in_a_parents_facts(entry):
    """The parent's ``compile_stats()`` has four keys: every new reader
    returns None there and does not raise (the driver lays the new
    readers over the parent's checkout for its traced runs)."""
    parent = {"compiles": 3.0, "compile_s": 1.5, "cache_hits": 3.0,
              "cache_misses": 0.0}
    assert _read(entry, parent, 50.0, 8) is None


@pytest.mark.parametrize("entry", sorted(READS))
def test_a_reader_reads_its_own_sum(entry):
    facts = {k: float(i + 1) for i, k in enumerate(JAX_KEYS + SETUP_KEYS)}
    assert _read(entry, facts) == facts[READS[entry]]
    # what this process's compile_stats() holds is what a run's facts hold
    assert READS[entry] in compile_stats()


@pytest.mark.parametrize("lead_in_s,want", [(None, 100.0 - 30.0 - 12.5),
                                            (20, 100.0 - 30.0 - 12.5 - 20)])
def test_unattributed_is_set_up_less_the_two_phases_and_the_lead_in(
        lead_in_s, want):
    facts = dict.fromkeys(JAX_KEYS + SETUP_KEYS, 1.0)
    facts.update(model_compile_s=30.0, instance_build_s=12.5)
    assert _read("setup_unattributed_s", facts, 100.0, lead_in_s) == want


def test_the_ten_entries_move_set_up_in_their_cells():
    from benchmark.spec import Layout

    layout = Layout()
    entries = {m["name"]: m for m in layout.bench["per_layer"]}
    serving = [w["name"] for w in layout.bench["workloads"]
               if w["name"] != "gpt2-medium.fit-1024"]
    for name in sorted(READS) + ["setup_unattributed_s"]:
        e = entries[name]
        assert (e["unit"], e["better"], e["source"], e["moves"]) == (
            "s", "lower", "program_counter", "setup_s"), name
        if name in ("setup_instance_build_s", "setup_calibration_s"):
            assert e["workloads"] == serving and e["layer"] == "Paged decoder"
        else:
            assert "workloads" not in e, name
    fit = {m["name"] for m in layout.cell("gpt2-medium.fit-1024")["per_layer"]}
    assert len(fit & set(entries) & (set(READS) | {"setup_unattributed_s"})) == 8
