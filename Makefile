# One-command gates (mirrored by .github/workflows/ci.yml; reference:
# .github/workflows/{build,gpu-ci,multinode-test}.yml).
#
#   make ci       — everything below, in order (the green gate)
#   make native   — build the C++ helpers (scheduler/batcher/sim engine)
#   make lint     — static checks: hot-path race/sync lint over the
#                   package source + bytecode-compile every module
#   make concurrency-lint — whole-package concurrency audit (CCY0xx:
#                   thread-role inference, unguarded shared mutation,
#                   ABBA lock cycles, blocking under a lock, Condition
#                   discipline, thread leaks, guarded-by inconsistency)
#                   + reasonless-pragma hygiene; one JSON line
#                   (tools/concurrency_lint.py); exit 1 on any error
#                   finding or decorative suppression
#   make knob-lint — config-knob key-coverage audit (KNB0xx: compile/
#                   perf reachability of every FFConfig knob read,
#                   strategy-cache + ledger-cohort key coverage, dead
#                   knobs, CLI-flag parity, serializer schema
#                   validation) + reasonless-pragma hygiene; one JSON
#                   line (tools/knob_lint.py); exit 1 on any error
#                   finding or decorative suppression
#   make pcg-lint — PCG validator + strategy linter over the model zoo;
#                   one JSON line (tools/pcg_lint.py)
#   make audit    — program audit (jaxpr-level AUD0xx checks: donation,
#                   baked consts, callbacks, accumulator precision,
#                   collective legality, retrace risk) over every zoo
#                   model's compiled step executables + the caller-side
#                   donated-reuse lint; one JSON line incl. audit/compile
#                   wall-time ratio (budget < 5%); exit 1 on any
#                   error-level finding (tools/program_audit.py)
#   make test     — full suite on the virtual 8-device CPU mesh
#   make dryrun   — compile+run one training step per parallelism mode
#   make bench    — the benchmark (one process, on a TPU; exits non-zero without one)
#   make bench-fit — step-loop overlap bench (prefetch / dispatch-ahead /
#                    multi-step dispatch) on the e2e MLP; one JSON line
#   make bench-pipe — pipeline schedule/engine bench (host GPipe vs 1F1B
#                     vs single-dispatch compiled): dispatch counts, step
#                     time, peak activation bytes; one JSON line
#   make serve-bench-smoke — continuous-batching serving guard
#                   (tools/serve_bench.py --smoke): replays a seeded
#                   open-arrival trace of heterogeneous generation
#                   requests through the static-batch baseline AND the
#                   continuous-batching engine (paged KV cache, split
#                   prefill/decode executables); one JSON line with
#                   tokens/s + p50/p99 TTFT/per-token for both; exits
#                   non-zero unless continuous strictly wins on
#                   tokens/s and the decode loop issued exactly one
#                   dispatch per decode step; appends the
#                   serving.tokens_per_s ledger record the sentinel
#                   cohorts; a second --trace longtail invocation
#                   replays a seeded length-distribution trace and
#                   exits non-zero unless token-budget prefill
#                   batching strictly beats uniform pad-to-max with
#                   identical generated sequences
#   make obs-report — flight-recorder smoke (obs/): traced pipelined fit
#                     + serving requests -> one JSON line with the trace
#                     event counts (schema-validated), the metrics
#                     snapshot, the sim-vs-measured divergence block,
#                     the run-ledger corpus stats, the XLA executable
#                     telemetry (flops/bytes/peak memory per program),
#                     and the watchdog state (zero dumps on health)
#   make sentinel — perf regression tripwire over the run ledger: newest
#                   run vs the per-(model, mesh, knobs) cohort baseline
#                   (median of priors); one JSON line incl. ledger /
#                   exec-telemetry / watchdog blocks + the attributed
#                   dominant phase per cohort verdict; fault-injected
#                   (chaos) runs are cohort-excluded; exit 1 on a
#                   regression beyond the margin
#   make chaos    — fault-tolerance matrix (tools/chaos_bench.py): runs
#                   the deterministic fault plans (subprocess kill at
#                   step N, torn checkpoint, NaN loss, watchdog stall,
#                   serving-worker crash, overload shed) and asserts
#                   every recovery invariant — resume bit-identity, no
#                   torn reads, every accepted serving future resolves,
#                   black-box dump on stall, bounded shed, zero overhead
#                   when the plan is off; one JSON line; exit 1 on any
#                   violated invariant. Includes the multihost subset
#                   (mid-fit peer kill -> supervisor relaunch resumes
#                   bit-identically; shrink N -> re-search + elastic
#                   restore) via tools/mh_launch.py
#   make mh-smoke — elastic multi-host matrix (tools/mh_launch.py
#                   --smoke): real 2-process jax.distributed cohorts
#                   under the supervisor — baseline agreement + one
#                   deduped process_count-keyed ledger cohort, mid-fit
#                   SIGKILL of one peer -> relaunch resumes
#                   bit-identically from the sharded checkpoints
#                   (strategy-cache warm hit), slow-peer hang ->
#                   black-box dump + relaunch, seeded init-timeout
#                   retry + sentinel cohort exclusion, shrunk-world
#                   resume -> re-search (cache miss) + counted elastic
#                   restore, and the cohort-obs gate (clean cohort:
#                   merged trace validates on one-lane-per-rank + zero
#                   OBS003; seeded multihost.slow_peer: the slowed rank
#                   is NAMED straggler and the rank_skew table
#                   telescopes); one JSON line; exit 1 on any violated
#                   invariant
#   make explain  — explain the newest ledger run: attribution phase
#                   breakdown (must reconcile with the measured step
#                   time), top ops measured-vs-predicted, divergence
#                   outliers, sentinel cohort trend + knob diff vs the
#                   cohort family's best prior run; one JSON line
#                   (tools/explain_run.py --latest --json)
#   make advise   — perf advisor (tools/perf_advisor.py): maps the
#                   newest fit/serving records' dominant phases (and
#                   every sentinel regression cohort) to ranked,
#                   schema-validated knob deltas with predicted phase
#                   deltas; one JSON line; exit 1 on a malformed report
#                   or a regression verdict with zero applicable
#                   suggestions. `--apply-top N` (manual) A/B-benchmarks
#                   the top suggestions in child processes (interleaved
#                   median-of-pair-ratios) and appends cohort-excluded
#                   advisor_experiment ledger records

PY ?= python
CPU_MESH = JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8"

.PHONY: ci native native-check lint concurrency-lint knob-lint \
        pcg-lint audit \
        test dryrun bench bench-fit bench-pipe bench-pipe-smoke \
        serve-bench serve-bench-smoke obs-report sentinel chaos \
        mh-smoke explain advise

# sentinel runs AFTER obs-report so a fresh checkout's first ci already
# has ledger records to judge (first run: no baseline -> clean exit);
# chaos runs after sentinel (its fault matrix uses its own tmp ledger,
# never the corpus the sentinel just judged); mh-smoke's cohorts use
# per-run scratch dirs likewise; explain narrates the newest of those
# records and advise closes the loop — the dominant phase mapped to
# ranked knob deltas over the same ledger
# ci runs chaos with --skip-multihost: mh-smoke (next in line) runs the
# FULL multihost matrix, so repeating its kill/shrink cohorts inside
# chaos would only double the subprocess bill; standalone `make chaos`
# keeps the complete default matrix
ci: native native-check lint concurrency-lint knob-lint test dryrun \
    obs-report \
    bench-pipe-smoke serve-bench-smoke sentinel chaos-ci mh-smoke \
    explain advise audit

lint:
	$(PY) -c "from flexflow_tpu.analysis.hotpath_lint import main; \
	  raise SystemExit(main(['flexflow_tpu']))"
	$(PY) -m compileall -q flexflow_tpu tools

concurrency-lint:
	$(PY) tools/concurrency_lint.py

knob-lint:
	$(PY) tools/knob_lint.py

pcg-lint:
	$(CPU_MESH) $(PY) tools/pcg_lint.py --hotpath

audit:
	$(CPU_MESH) $(PY) tools/program_audit.py

native:
	$(MAKE) -C native -s

native-check:
	$(CPU_MESH) $(PY) -c "from flexflow_tpu import native_bridge as nb; \
	  print('native helpers:', 'OK' if nb.available() else 'FALLBACK (pure python)')"

test:
	$(CPU_MESH) $(PY) -m pytest tests/ -x -q

dryrun:
	$(PY) -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

bench:
	$(PY) bench.py

bench-fit:
	$(CPU_MESH) $(PY) tools/fit_bench.py

bench-pipe:
	$(CPU_MESH) $(PY) tools/pipe_bench.py

# tier-1 envelope guard: forces engine="compiled" for an interleaved
# schedule and a pipe×data submesh point — exits non-zero if either
# falls back to the host engine (mirrors tests/test_pipe_bench.py)
bench-pipe-smoke:
	$(CPU_MESH) $(PY) tools/pipe_bench.py --smoke

serve-bench:
	$(CPU_MESH) $(PY) tools/serve_bench.py

# continuous-batching guard: continuous must strictly beat static on
# tokens/s over the seeded heterogeneous open-arrival trace, with one
# decode dispatch per step regardless of active-request count; then the
# two composable speed paths — speculation must strictly win tokens/s
# with bit-identical greedy outputs, and int8 paged KV must double
# admissible concurrency at equal pool bytes inside the divergence
# budget
serve-bench-smoke:
	$(CPU_MESH) $(PY) tools/serve_bench.py --smoke
	$(CPU_MESH) $(PY) tools/serve_bench.py --smoke --trace longtail
	$(CPU_MESH) $(PY) tools/serve_bench.py --smoke --spec
	$(CPU_MESH) $(PY) tools/serve_bench.py --smoke --kv-dtype int8

obs-report:
	$(CPU_MESH) $(PY) tools/obs_report.py

sentinel:
	$(CPU_MESH) $(PY) tools/perf_sentinel.py

chaos:
	$(CPU_MESH) $(PY) tools/chaos_bench.py

.PHONY: chaos-ci
chaos-ci:
	$(CPU_MESH) $(PY) tools/chaos_bench.py --skip-multihost

mh-smoke:
	$(PY) tools/mh_launch.py --smoke

explain:
	$(CPU_MESH) $(PY) tools/explain_run.py --latest --json

advise:
	$(CPU_MESH) $(PY) tools/perf_advisor.py
