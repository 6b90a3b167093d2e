"""Flight recorder: the unified observability subsystem.

Three zero-dependency parts (motivated by the paper's predict→measure
loop — a profiling-guided search is only trustworthy when its
predictions stay observable at runtime; cf. "A Learned Performance
Model for Tensor Processing Units", arXiv:2008.01040, and FlexFlow's
``--profiling``/Legion Prof per-op device timing, arXiv:1807.05358):

* :mod:`.trace` — the **span tracer**: every span is a
  ``jax.profiler`` annotation, so it is on the device trace's clock
  under any profile, and with ``config.trace=on`` also an event of a
  thread-safe ring exported as Chrome/Perfetto trace-event JSON. Spans
  cover compile (search, validation, lowering, cache hit/miss), the
  fit/eval step loop (dispatch, input wait, host sync, recompile
  checks), the pipeline engines, and serving (the scheduler thread's
  ``serving.loop.*`` phases; one span tree per request in the ring).
* :mod:`.metrics` — named counters / gauges / histograms in one
  process-wide **registry** with JSON and Prometheus-text export, fed
  by the Prefetcher, the dispatch-ahead window, the strategy cache,
  recompile triggers, the serving engine, and the pipeline engines.
* :mod:`.divergence` — **sim-vs-measured** comparison: the search /
  simulator's ``est_step_time`` and per-op cost-model times vs measured
  wall times, recorded as a ``divergence`` section of ``fit_report()``
  and raising the coded finding OBS001 (warn) past a configurable
  threshold.

The EXPLAIN half (why a run performed the way it did):

* :mod:`.attribution` — **step-time attribution**: the measured
  steady-state step time decomposed into phases (input wait, host
  dispatch, device compute, collective/transfer, pipeline bubble,
  optimizer fold) by joining the tracer ring, the throughput record,
  and the pipeline profile against the simulator's predicted task
  timeline; top-k ops by measured-vs-predicted time and the largest
  divergence contributors, in ``fit_profile["attribution"]``.
* :mod:`.costcorpus` — **per-op cost corpus**: every compiled op timed
  forward AND backward under its real sharding, featurized
  (shapes/dtypes/mesh degrees/flops/bytes) and appended as
  schema-versioned, dedup-keyed JSONL to ``.ffcache/costmodel/corpus/``
  — the learned cost model's training set (ROADMAP item 2).
* :mod:`.server` — **observability HTTP server**: a zero-dep
  ``http.server`` background thread (role ``ff-obs-server``) serving
  ``/metrics``, ``/healthz``, ``/runs``, ``/trace``, ``/attribution``,
  ``/cohort``.
* :mod:`.cohort` — **cohort observability**: per-rank trace/metrics
  exports under ``config.cohort_obs=on``, cross-process trace
  unification on the PR 8 wall-clock anchors, cross-rank ``fit.step``
  skew attribution (straggler verdict, OBS003), and the fleet-level
  roll-up report ``tools/mh_launch.py --cohort-obs`` folds into its
  supervisor output.

Plus the DURABLE half (telemetry that outlives the process):

* :mod:`.ledger` — **run ledger**: every compile/fit/eval/serving/bench
  run appends a schema-versioned JSONL record to ``.ffcache/obs/runs/``
  (machine fingerprint, knobs, search/cache outcome, throughput,
  divergence, metrics snapshot) with load/filter/merge APIs — the
  corpus the learned-cost-model flywheel and ``tools/perf_sentinel.py``
  read.
* :mod:`.exec_telemetry` — **XLA executable telemetry**: per-program
  ``cost_analysis()``/``memory_analysis()`` (flops, bytes accessed,
  peak memory) recorded into the ledger and ``exec.*`` metrics, with
  the static-vs-XLA peak-memory reconciliation (OBS002, warn).
* :mod:`.watchdog` — **stall watchdog**: an opt-in daemon monitoring
  heartbeats from the fit loop, the Prefetcher worker, and serving
  workers; a silent source past the threshold (or a fatal signal)
  writes a black-box dump — thread stacks, tracer ring, metrics
  snapshot, last ledger record — to ``.ffcache/obs/blackbox/``.

``runtime/profiling.py`` is the façade re-exporting this module's
public surface next to the historical profiling exports;
``tools/obs_report.py`` renders the one-line JSON summary.
"""

from .trace import (  # noqa: F401
    Tracer,
    configure_tracer,
    span,
    trace_enabled,
    tracer,
    validate_chrome_trace,
)
from .metrics import (  # noqa: F401
    Counter,
    EpochThroughput,
    Gauge,
    Histogram,
    MetricsRegistry,
    metrics_registry,
)
from .divergence import (  # noqa: F401
    divergence_report,
    maybe_record_divergence,
    predicted_step_time,
    record_divergence,
)
from .ledger import (  # noqa: F401
    LEDGER_SCHEMA,
    cohort_key,
    last_record,
    ledger_dir,
    load_runs,
    merge_runs,
    record_run,
    scan_ledger,
)
from .exec_telemetry import (  # noqa: F401
    collect_traced,
    reconcile_peak_memory,
    telemetry_mode,
)
from .watchdog import (  # noqa: F401
    Watchdog,
    configure_watchdog,
    watchdog,
)
from .attribution import (  # noqa: F401
    attribute_fit,
    attribution_report,
    format_phase_table,
    maybe_attribute,
    serving_attribution,
)
from .advisor import (  # noqa: F401
    RULE_FAMILIES,
    advise_record,
    judge_experiment,
    maybe_advise,
    top_suggestion,
    validate_report,
)
from .costcorpus import (  # noqa: F401
    append_rows,
    build_rows,
    corpus_dir,
    load_rows,
    scan_corpus,
)
from .server import (  # noqa: F401
    ObsServer,
    configure_obs_server,
    latest_advice,
    latest_attribution,
    latest_cohort,
    obs_server,
    publish_advice,
    publish_attribution,
    publish_cohort,
    stop_obs_server,
)
from .cohort import (  # noqa: F401
    build_cohort_report,
    cohort_attribution,
    cohort_dir,
    maybe_export_cohort,
    merge_metric_snapshots,
    merge_traces,
    step_skew,
)
