"""Profiling and graph exports.

TPU-native equivalents of the reference's observability surface
(SURVEY.md §5 "Tracing/profiling"):

* per-op profiling (``--profiling`` → cudaEvent brackets,
  linear_kernels.cu:95-111) → :func:`profile_ops`: each op's forward is
  jitted and timed standalone with the compile cached, like the
  reference's ``measure_operator_cost`` device timing.
* Legion-level profiling (``-lg:prof``) → :func:`trace`: a context
  manager around ``jax.profiler`` writing a TensorBoard-loadable trace.
* ``--compgraph`` (``export_strategy_computation_graph``, graph.h:339) →
  :func:`export_computation_graph`: dot of the op graph with shardings,
  optionally cost-annotated (``--include-costs-dot-graph`` parity).
* ``--taskgraph`` (``export_strategy_task_graph_file``, model.cc:3666) →
  :func:`export_task_graph`: dot/JSON of the simulator's SimTask graph,
  transitively reduced (via the native graph library when built).
* search observability → :func:`search_report`: the last search's timing,
  cache-hit, candidate-coverage, and pruned-candidate counters (recorded
  by ``FFModel._finish_search``); included in the JSON task-graph export
  so bound-based pruning is never a silent truncation.
* step-loop observability → :class:`EpochThroughput` / :func:`fit_report`:
  per-epoch throughput counters of the async input pipeline + dispatch-
  ahead train loop (steps/s, host-input-wait seconds, prefetch queue-depth
  histogram, dispatch-ahead occupancy), recorded by ``FFModel.fit``/
  ``eval`` into ``FFModel.fit_profile``/``eval_profile``.

This module is also the **façade over the flight recorder**
(:mod:`..obs`): the span tracer (:class:`Tracer`/:func:`span`, Chrome
trace-event JSON via ``Tracer.export``), the metrics registry
(:func:`metrics_registry`, JSON + Prometheus-text export), and
sim-vs-measured divergence tracking (:func:`divergence_report`,
``fit_profile["divergence"]``, OBS001) are all re-exported here so one
import serves the whole observability surface.
"""

from __future__ import annotations

import contextlib
import json
import time
from typing import Dict, List, Optional

import numpy as np

# --- flight-recorder façade (obs/): tracer + metrics + divergence ---------
from ..obs.divergence import (  # noqa: F401
    divergence_report,
    maybe_record_divergence,
    predicted_step_time,
    record_divergence,
)
from ..obs.metrics import (  # noqa: F401
    Counter,
    EpochThroughput,
    Gauge,
    Histogram,
    MetricsRegistry,
    metrics_registry,
)
from ..obs.trace import (  # noqa: F401
    Tracer,
    configure_tracer,
    span,
    trace_enabled,
    tracer,
    validate_chrome_trace,
)
from ..obs.ledger import (  # noqa: F401
    cohort_key,
    last_record,
    ledger_dir,
    load_runs,
    merge_runs,
    record_run,
    scan_ledger,
)
from ..obs.exec_telemetry import (  # noqa: F401
    collect_traced,
    reconcile_peak_memory,
)
from ..obs.watchdog import (  # noqa: F401
    Watchdog,
    configure_watchdog,
    watchdog,
)
from ..obs.attribution import (  # noqa: F401
    attribute_fit,
    attribution_report,
    format_phase_table,
    serving_attribution,
)
from ..obs.advisor import (  # noqa: F401
    advise_record,
    top_suggestion,
)
from ..obs.costcorpus import (  # noqa: F401
    corpus_dir,
    load_rows,
    scan_corpus,
)
from ..obs.server import (  # noqa: F401
    ObsServer,
    configure_obs_server,
    latest_advice,
    latest_attribution,
    obs_server,
)
from ..utils.dot import DotFile


def synth_array(t, rng, int_high: int = 2) -> np.ndarray:
    """Random host array matching a frontend Tensor's declared shape AND
    dtype — the single synthesizer shared by per-op profiling and
    calibration timing (two drifting copies previously disagreed on
    float-dtype handling).

    ``int_high``: exclusive upper bound for integer inputs. Callers timing
    embedding-heavy workloads should pass the real vocab bound — ids
    drawn from {0, 1} gather two cache-hot rows of a huge table and make
    the measurement systematically optimistic."""
    dt = np.dtype(t.dtype.to_jnp())
    if np.issubdtype(dt, np.integer):
        return rng.integers(0, max(2, int_high), size=t.dims).astype(dt)
    if dt == np.bool_:
        return rng.integers(0, 2, size=t.dims).astype(bool)
    return rng.normal(size=t.dims).astype(dt)


def _min_vocab_bound(ffmodel_or_ops) -> int:
    """Smallest embedding vocab among the model's ops (a safe id bound:
    ids must index every embedding they reach)."""
    ops = getattr(ffmodel_or_ops, "compiled", None)
    ops = ops.ops if ops is not None else ffmodel_or_ops
    vocabs = [op.attrs["num_entries"] for op in ops
              if op.attrs.get("num_entries")]
    return min(vocabs) if vocabs else 2


# --------------------------------------------------------------- jax tracing
@contextlib.contextmanager
def trace(logdir: str):
    """Profile a region into a TensorBoard trace (reference analog:
    Legion Prof via -lg:prof)."""
    import jax

    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


# ----------------------------------------------------------- per-op profiling
def _op_backward_ms(op, ctx, ins, weights, forward_ms: float,
                    iters: int, warmup: int) -> Optional[float]:
    """Time one op's backward pass standalone: jit the fwd+vjp of a
    scalar reduction over the op's float outputs w.r.t. its float
    inputs and weights, then subtract the already-measured forward time
    (jitting the vjp application alone would bake the residuals in as
    closed-over constants — exactly what AUD001 exists to flag).
    Returns None for non-differentiable ops (integer-only
    inputs+weights, or no float output to pull a cotangent through)."""
    import jax
    import jax.numpy as jnp

    diff_idx = [i for i, a in enumerate(ins)
                if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating)]
    wkeys = sorted(k for k, v in weights.items()
                   if jnp.issubdtype(jnp.asarray(v).dtype, jnp.floating))
    if not diff_idx and not wkeys:
        return None

    def scalar_loss(diff_ins, diff_w):
        full_ins = list(ins)
        for i, a in zip(diff_idx, diff_ins):
            full_ins[i] = a
        full_w = dict(weights)
        full_w.update(diff_w)
        outs = op.forward(ctx, full_ins, full_w)
        tot = None
        for o in outs:
            if jnp.issubdtype(o.dtype, jnp.floating):
                s = o.astype(jnp.float32).sum()
                tot = s if tot is None else tot + s
        if tot is None:
            raise TypeError("no float output to differentiate")
        return tot

    fwd_bwd = jax.jit(jax.grad(scalar_loss, argnums=(0, 1)))
    d_ins = [ins[i] for i in diff_idx]
    d_w = {k: weights[k] for k in wkeys}
    try:
        g = fwd_bwd(d_ins, d_w)  # compile
        jax.block_until_ready(g)
    except Exception:  # non-differentiable op — report None, not a crash
        return None
    for _ in range(warmup):
        g = fwd_bwd(d_ins, d_w)
    jax.block_until_ready(g)
    t0 = time.perf_counter()
    for _ in range(iters):
        g = fwd_bwd(d_ins, d_w)
    jax.block_until_ready(g)
    full_ms = (time.perf_counter() - t0) / iters * 1e3
    # the timed program runs forward AND backward; the backward share is
    # what is left after the standalone forward (clamped: timer noise on
    # a loaded host can put full under fwd for trivial ops)
    return max(0.0, full_ms - forward_ms)


def profile_ops(ffmodel, iters: int = 10, warmup: int = 2,
                backward: bool = False) -> List[Dict]:
    """Time each compiled op's forward standalone (reference: per-op
    cudaEvent profiling under --profiling, OpMeta::profiling op_meta.h:17).
    Returns one record per op: name, type, ms, flops, arithmetic intensity.

    ``backward=True`` additionally times each op's backward via
    ``jax.vjp`` (a jitted fwd+grad program minus the forward) under the
    same real mesh sharding — ``backward_ms`` per record, None for
    non-differentiable ops. The per-op divergence comparison and the
    cost-corpus collector (obs/costcorpus.py) both ride this."""
    import jax

    from ..core.op import LowerCtx, weights_of

    cm = ffmodel.compiled
    assert cm is not None, "compile() first"
    rng = np.random.default_rng(0)
    acts: Dict[int, np.ndarray] = {}
    bound = _min_vocab_bound(cm.ops)
    for t, sh in zip(cm.input_tensors, cm.input_shardings):
        acts[t.tensor_id] = jax.device_put(
            synth_array(t, rng, int_high=bound), sh)
    records: List[Dict] = []
    ctx = LowerCtx(mesh=cm.mesh, training=False, rng=None)
    for op in cm.ops:
        ins = [acts[t.tensor_id] for t in op.layer.inputs]
        weights = weights_of(op, cm.params)

        fwd = jax.jit(lambda ins, weights, _op=op: _op.forward(ctx, ins, weights))
        outs = fwd(ins, weights)  # compile + fill acts
        jax.block_until_ready(outs)
        for _ in range(warmup):
            outs = fwd(ins, weights)
        jax.block_until_ready(outs)
        t0 = time.perf_counter()
        for _ in range(iters):
            outs = fwd(ins, weights)
        jax.block_until_ready(outs)
        ms = (time.perf_counter() - t0) / iters * 1e3
        for t, o in zip(op.layer.outputs, outs):
            acts[t.tensor_id] = o
        fl = op.flops()
        rec = {
            "name": op.name,
            "type": op.op_type.value,
            "forward_ms": ms,
            "flops": fl,
            "gflops_per_s": (fl / (ms * 1e-3)) / 1e9 if ms > 0 else 0.0,
        }
        if backward:
            rec["backward_ms"] = _op_backward_ms(
                op, ctx, ins, weights, ms, iters, warmup)
        records.append(rec)
    return records


# ----------------------------------------------------- step-loop observability
# EpochThroughput moved to obs/metrics.py (re-exported above): the per-
# epoch fit_profile record is unchanged, but every sample now also feeds
# the process-wide metrics registry ("fit.*" series).


def fit_report(ffmodel) -> Optional[Dict]:
    """The last ``fit``'s step-loop throughput profile, or None when no
    fit has run: ``{"epochs": [per-epoch records], "steps_per_s",
    "prefetch_depth", "max_inflight_steps", "steps_per_dispatch"}``. Each
    epoch record carries ``steps``, ``wall_s``, ``steps_per_s``,
    ``input_wait_s`` (host time on the critical path), ``input_mb_per_s``,
    ``queue_depth_hist`` and ``dispatch_ahead_occupancy``. Pipelined
    fits add a ``"pipeline"`` record (see :func:`pipeline_report`);
    with ``config.divergence`` enabled a ``"divergence"`` record
    (sim-vs-measured step-time and per-op ratios — see
    :func:`divergence_report`) rides along too."""
    return getattr(ffmodel, "fit_profile", None)


def pipeline_report(ffmodel) -> Optional[Dict]:
    """The pipeline engine's record from the last fit (or directly from
    the live engine when no fit ran yet): schedule name, per-stage tick
    timeline (``s0 |F0|F1|B0|..|``), analytic bubble fraction, per-stage
    peak live microbatches, schedule-implied peak activation bytes, the
    engine in use (``host`` one-dispatch-per-action vs ``compiled``
    single-dispatch), and measured dispatch/transfer counts from the most
    recent step. None when the model is not pipelined."""
    fp = getattr(ffmodel, "fit_profile", None) or {}
    if "pipeline" in fp:
        return fp["pipeline"]
    pm = getattr(ffmodel, "pipelined", None)
    return pm.profile() if pm is not None else None


# -------------------------------------------------------- search observability
def search_report(ffmodel) -> Optional[Dict]:
    """The last auto-parallelization search's counters, or None when no
    search ran this compile: ``search_time_s``, ``cache``
    ("hit"/"miss"/"refresh"/"off"), ``candidates`` (total variant x mesh
    work items), ``pruned`` (skipped by the lower-bound prune — reported
    so coverage is never silently truncated), ``states_explored``,
    ``workers``, the chosen ``mesh_shape`` and ``est_step_time``."""
    return getattr(ffmodel, "search_profile", None)


# ----------------------------------------------------------------- dot export
def export_computation_graph(ffmodel, path: str,
                             include_costs: bool = False) -> None:
    """reference: --compgraph → Graph::export_strategy_computation_graph
    (graph.h:339-344); --include-costs-dot-graph adds per-op cost rows."""
    cm = ffmodel.compiled
    assert cm is not None, "compile() first"
    dot = DotFile("computation_graph")
    cost_by_op = {}
    if include_costs:
        from ..sim import OpCostModel, Simulator, detect_machine_model

        machine = detect_machine_model(cm.mesh.devices.size)
        cost_model = OpCostModel(machine)
        for op in cm.ops:
            c = cost_model.measure(op)
            cost_by_op[op.name] = c
    for op in cm.ops:
        shard = ", ".join(
            str(ps.partition_spec()) for ps in op.output_shapes
        )
        label = f"{{{op.name}|{op.op_type.value}|{shard}"
        if op.name in cost_by_op:
            c = cost_by_op[op.name]
            label += f"|fwd {c.forward_time*1e3:.3f} ms, bwd {c.backward_time*1e3:.3f} ms"
        label += "}"
        dot.add_node(op.name, label)
    producer = {
        t.tensor_id: op for op in cm.ops for t in op.layer.outputs
    }
    for op in cm.ops:
        for t in op.layer.inputs:
            src = producer.get(t.tensor_id)
            if src is not None:
                dot.add_edge(src.name, op.name, label="x".join(map(str, t.dims)))
    dot.write(path)


def export_task_graph(ffmodel, path: str, fmt: str = "dot") -> None:
    """reference: --taskgraph → export_strategy_task_graph_file
    (model.cc:3666). Exports the simulator's SimTask graph with simulated
    start times; edges transitively reduced through the native graph
    library when available."""
    from ..sim import OpCostModel, Simulator, detect_machine_model

    cm = ffmodel.compiled
    assert cm is not None, "compile() first"
    machine = detect_machine_model(cm.mesh.devices.size)
    sim = Simulator(machine, OpCostModel(machine))
    total = sim.simulate_runtime(cm.ops)
    tasks = sim.last_tasks()  # start times filled by the replay
    edges = [(d, i) for i, t in enumerate(tasks) for d in t.deps]
    try:
        from ..native_bridge import available, transitive_reduction

        if available():
            edges = transitive_reduction(len(tasks), edges)
    except Exception:
        pass
    if fmt == "json":
        payload = {
            "total_time_s": total,
            "tasks": [
                {"id": i, "name": t.name, "kind": t.kind,
                 "run_time_s": t.run_time, "start_time_s": t.start_time}
                for i, t in enumerate(tasks)
            ],
            "edges": [list(e) for e in edges],
        }
        search = search_report(ffmodel)
        if search is not None:
            payload["search"] = search
        with open(path, "w") as f:
            json.dump(payload, f, indent=2)
        return
    dot = DotFile("task_graph")
    for i, t in enumerate(tasks):
        dot.add_node(
            str(i),
            f"{{{t.name}|{t.kind}|{t.run_time*1e6:.1f} us @ {t.start_time*1e6:.1f} us}}",
        )
    for s, d in edges:
        dot.add_edge(str(s), str(d))
    dot.write(path)
