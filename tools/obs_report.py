#!/usr/bin/env python
"""Flight-recorder report: one JSON line over a traced, pipelined fit.

Exercises the full observability surface end to end — the CI smoke for
``flexflow_tpu/obs/`` and the bench-trend record:

* compiles a 2-stage **pipelined** MLP (pipe x data mesh) and fits it
  with the span tracer armed (``config.trace=on``), divergence tracking
  in full per-op mode (``config.divergence=on``), executable telemetry
  pulling XLA's cost/memory analyses off every program
  (``exec_telemetry=on``), and the stall watchdog armed
  (``watchdog=on`` — the report asserts ZERO black-box dumps on this
  healthy run);
* serves a few requests through the :class:`InferenceEngine` so the
  serving span trees + queue/latency metrics populate;
* exports the trace buffer as Chrome trace-event JSON and validates it
  (``obs.trace.validate_chrome_trace``: required fields + span nesting);
* prints ONE line::

    {"trace": {"events": N, "by_cat": {...}, "valid": true, "path": ...},
     "metrics": {...full registry snapshot...},
     "divergence": {"e2e_ratio": ..., "per_op": [...], ...},
     "attribution": {"reconciliation": {...}, "dominant_phase": ...,
                     "phases": {...}, "top_ops": [...]},
     "pipeline": {"schedule": ..., "engine": ..., "dispatches_per_step": ...},
     "ledger": {"dir": ..., "runs": N, "kinds": [...]},
     "sentinel": {"judged": N, "no_baseline": N, "regressions": N},
     "exec": {"programs": {name: {"flops": ..., "bytes_accessed": ...,
              "peak_bytes": ...} or {"unavailable": reason}}, ...},
     "watchdog": {"enabled": true, "sources_seen": [...], "dumps": 0},
     "exit": 0}

``metrics`` holds, among the rest, how set-up went (always on,
``utils/compile_cache.py``): the compiler's sums ``jax.compiles``,
``jax.compile_s``, ``jax.cache_hits``, ``jax.cache_misses``,
``jax.trace_s``, ``jax.mlir_s``, ``jax.cache_read_s`` and the phases
``setup.model_compile_s``, ``setup.lower_s``, ``setup.init_params_s``,
``setup.audit_s``, ``setup.instance_build_s``, ``setup.calibration_s``.

Exit status 1 when the trace fails validation, the divergence block is
missing, the attribution phase table is absent or fails to reconcile
with the measured step time, the serving/fit counters did not populate,
the ledger stayed empty, a telemetry block lacks both numbers and an
``unavailable`` reason, or the watchdog wrote a dump during the healthy
run.

Usage::

    python tools/obs_report.py                 # default smoke workload
    python tools/obs_report.py --epochs 4 --samples 256
    python tools/obs_report.py --trace-out /tmp/ff_trace.json
    python tools/obs_report.py --prometheus    # also dump the scrape text
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

# hermetic multi-device CPU mesh when launched standalone (mirrors
# tests/conftest.py; a real TPU/GPU environment overrides via env)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def _fit_pipelined(samples: int, epochs: int) -> tuple:
    """2-stage pipelined MLP fit with the WHOLE observability surface
    armed — trace, per-op divergence, executable telemetry, watchdog —
    returns (fit report, exec-telemetry block)."""
    from flexflow_tpu import (DataType, FFConfig, FFModel, LossType,
                              SGDOptimizer, make_mesh)
    from flexflow_tpu.runtime.profiling import fit_report

    bs = 16
    mesh_shape = {"pipe": 2, "data": 4}
    # watchdog threshold well above a cold XLA pipeline-program compile
    # (which happens INSIDE the watched step loop on first dispatch) so
    # the smoke never false-dumps on a loaded CI box
    cfg = FFConfig(batch_size=bs, seed=0, trace="on", divergence="on",
                   exec_telemetry="on", watchdog="on",
                   watchdog_threshold_s=300.0, mesh_shape=mesh_shape)
    ff = FFModel(cfg)
    x = ff.create_tensor((bs, 16), DataType.FLOAT, name="obs_x")
    t = ff.dense(x, 32, name="obs_fc1")
    t = ff.relu(t, name="obs_act")
    t = ff.dense(t, 4, name="obs_head")
    ff.softmax(t, name="obs_sm")
    # an explicit mesh object: compile() auto-enables the pipeline
    # engine from the mesh's pipe axis (stage count = pipe degree)
    ff.compile(optimizer=SGDOptimizer(lr=0.05),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               metrics=[], mesh=make_mesh(mesh_shape))
    assert ff.pipelined is not None, "pipe mesh did not enable the engine"
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(samples, 16)).astype(np.float32)
    w = rng.normal(size=(16, 4)).astype(np.float32)
    ys = np.argmax(xs @ w, axis=1).astype(np.int32).reshape(-1, 1)
    ff.fit(xs, ys, epochs=epochs, verbose=False)
    # merge the compile-time telemetry (eval/forward programs) with the
    # pipeline engine's schedule-program telemetry
    exec_block = {"programs": {}, "reconciliation": []}
    for tel in (ff.exec_telemetry,
                getattr(ff.pipelined, "exec_telemetry", None)):
        if tel:
            exec_block["programs"].update(tel.get("programs") or {})
            exec_block["reconciliation"] += tel.get("reconciliation") or []
    return fit_report(ff) or {}, exec_block


def _serve_smoke(requests: int) -> int:
    """A few requests through the engine so serving spans/metrics fire."""
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models.mlp import build_mlp
    from flexflow_tpu.serving.engine import InferenceEngine

    ff = FFModel(FFConfig(batch_size=8, seed=0))
    build_mlp(ff, 8, in_dim=8, hidden_dims=(16,), num_classes=4)
    ff.compile(optimizer=None, loss_type=None, metrics=[])
    eng = InferenceEngine(batch_timeout_s=0.002)
    eng.register_ffmodel(ff, name="obs_mlp")
    rng = np.random.default_rng(0)
    for _ in range(requests):
        out = eng.infer("obs_mlp", [rng.normal(size=(8,)).astype(np.float32)])
        assert out.shape == (4,), out.shape
    eng.stop()
    return requests


def run_report(samples: int = 64, epochs: int = 2, requests: int = 4,
               trace_out: str = "") -> dict:
    from flexflow_tpu.obs.ledger import ledger_dir, scan_ledger
    from flexflow_tpu.obs.metrics import metrics_registry
    from flexflow_tpu.obs.trace import (configure_tracer, tracer,
                                        validate_chrome_trace)
    from flexflow_tpu.obs.watchdog import watchdog

    configure_tracer(enabled=True)
    report, exec_block = _fit_pipelined(samples, epochs)
    _serve_smoke(requests)

    tr = tracer()
    path = trace_out or os.path.join(tempfile.gettempdir(),
                                     "flexflow_obs_trace.json")
    n_events = tr.export(path)
    with open(path) as f:
        problems = validate_chrome_trace(json.load(f))

    snapshot = metrics_registry().to_json()
    divergence = report.get("divergence") or {}
    attribution = report.get("attribution") or {}
    pipeline = report.get("pipeline") or {}
    missing = [k for k in ("fit.steps", "serving.requests")
               if k not in snapshot]
    # ---- durable blocks: ledger corpus, exec telemetry, watchdog -----
    scan = scan_ledger()
    ledger_block = {
        "dir": ledger_dir(),
        "files": scan["files"],
        "runs": len(scan["runs"]),
        "corrupt_lines": scan["corrupt_lines"],
        "kinds": sorted({r.get("kind") for r in scan["runs"]}),
    }
    wd_block = watchdog().stats()
    # the report is a snapshot; disarm so an in-process caller (the
    # tier-1 smoke) does not keep a monitor thread — and its 60s default
    # threshold — running under the rest of the suite
    watchdog().disarm()
    # sentinel visibility: thin-baseline cohorts are NOT vacuously
    # green — count them here (and on stderr) so an empty trend line
    # (e.g. a fresh BENCH trajectory) is visible in make ci output
    sentinel_block = _sentinel_counts()
    exec_ok = bool(exec_block.get("programs")) and all(
        any(k in b for k in ("flops", "bytes_accessed", "peak_bytes",
                             "unavailable"))
        for b in exec_block["programs"].values())
    # attribution gate: the phase table must exist for the traced fit
    # and telescope back to the measured step time — a non-reconciling
    # table means the engine mis-decomposed and the report exits 1
    attr_ok = bool(attribution) and bool(
        (attribution.get("reconciliation") or {}).get("reconciles"))
    ok = (n_events > 0 and not problems and not missing
          and bool(divergence.get("e2e_ratio"))
          and divergence.get("per_op")
          and attr_ok
          and ledger_block["runs"] > 0
          and exec_ok
          and wd_block["enabled"] and wd_block["dumps"] == 0)
    return {
        "trace": {
            "events": n_events,
            "by_cat": tr.counts_by_cat(),
            "valid": not problems,
            "problems": problems[:5],
            "path": path,
        },
        "metrics": snapshot,
        "divergence": divergence,
        "attribution": {
            "reconciliation": attribution.get("reconciliation"),
            "dominant_phase": attribution.get("dominant_phase"),
            "phases": attribution.get("phases"),
            "top_ops": [r.get("name")
                        for r in attribution.get("top_ops") or []],
        } if attribution else {},
        "pipeline": {k: pipeline.get(k) for k in
                     ("schedule", "engine", "dispatches_per_step",
                      "bubble_fraction")} if pipeline else {},
        "ledger": ledger_block,
        "sentinel": sentinel_block,
        "exec": exec_block,
        "watchdog": wd_block,
        "steps_per_s": report.get("steps_per_s"),
        "missing_metrics": missing,
        "exit": 0 if ok else 1,
    }


def _sentinel_counts() -> dict:
    """One-line cohort visibility: how many ledger cohorts the sentinel
    can actually judge vs how many are silently baseline-less."""
    import importlib.util

    try:
        spec = importlib.util.spec_from_file_location(
            "perf_sentinel_for_report",
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "perf_sentinel.py"))
        sent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sent)
        s = sent.run_sentinel()
        block = {"verdict": s.get("verdict"),
                 "judged": s.get("judged", 0),
                 "no_baseline": s.get("no_baseline", 0),
                 "regressions": len(s.get("regressions") or [])}
    except Exception as e:  # noqa: BLE001 — visibility, not a gate
        block = {"error": f"{type(e).__name__}: {e}"}
    nb = block.get("no_baseline")
    if nb:
        print(f"[obs-report] sentinel: {nb} cohort(s) without a "
              f"baseline (judged {block.get('judged', 0)}) — thin "
              f"trend lines are NOT vacuously green", file=sys.stderr,
              flush=True)
    return block


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--samples", type=int, default=64)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--trace-out", default="",
                    help="write the Chrome trace here (default: tmpdir)")
    ap.add_argument("--prometheus", action="store_true",
                    help="also print the Prometheus text exposition")
    ns = ap.parse_args(argv)
    out = run_report(samples=ns.samples, epochs=ns.epochs,
                     requests=ns.requests, trace_out=ns.trace_out)
    print(json.dumps(out, sort_keys=True))
    if ns.prometheus:
        from flexflow_tpu.obs.metrics import metrics_registry

        sys.stderr.write(metrics_registry().to_prometheus())
    return out["exit"]


if __name__ == "__main__":
    sys.exit(main())
