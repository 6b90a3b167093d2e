"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process does everything: it finds the cell in ``BENCHMARK.json``,
builds the program's model from the configuration's file through its
family, fills it with weights made from ``--seed``, warms up the shapes
the cell's traffic uses, compares the program's outputs with the plain
reference, measures for ``--seconds`` seconds, and prints as the last
line of its output one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device`` and, traced,
``breakdown``.

Without a TPU, or with fewer chips than the cell asks for, it exits 2
and prints no result: there is no flag, variable or path by which a
CPU run prints a device metric. :func:`run_cell` is the same run as a
function, which the tests drive at toy sizes on the CPU.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up runs from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from typing import Dict, Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TRACE_SECONDS = 3.0  # a traced run profiles at most this much of the window


class Ctx:
    """What a kind is given: the cell, the seed, the window, the
    devices, and where to put what it found."""

    def __init__(self, layout, cell: Dict, seed: int, seconds: float,
                 trace: bool, devices, t_process: float):
        from benchmark import check, device

        self.layout = layout
        self.cell = cell
        self.config: Dict = cell["config"]
        self.mix: Dict = cell["mix"]
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace_seconds = TRACE_SECONDS
        self.devices = list(devices)
        self.chips = int(cell["workload"]["chips"])
        self.family = layout.family(self.config["family"])
        self.reference = layout.reference(self.family.REFERENCE)
        self.workdir = os.path.join(layout.root, ".bench_work",
                                    cell["workload"]["name"])
        os.makedirs(self.workdir, exist_ok=True)
        self.profiler = device.Profiler(
            trace, os.path.join(self.workdir, "trace"))
        self.checks = check.Checks()
        self.facts: Dict = {}
        self.t_process = t_process
        self.t_window: Optional[float] = None
        self.t_closed: Optional[float] = None

    def span(self, name: str):
        return self.profiler.span(name)

    def note(self, what: str) -> None:
        """A line of the run's log: seconds since the process started,
        and what the compiler has done so far."""
        from flexflow_tpu.utils.compile_cache import compile_stats

        c = compile_stats()
        print(f"[bench] t={time.perf_counter() - self.t_process:7.1f}s "
              f"{what} (compiles {int(c['compiles'])}, "
              f"{c['compile_s']:.1f}s in them, cache hits "
              f"{int(c['cache_hits'])}, misses {int(c['cache_misses'])})",
              flush=True)

    def window_opens(self, at: Optional[float] = None) -> float:
        """The measured window starts (now, or at the stated moment of
        ``time.perf_counter``): set-up ends here."""
        self.t_window = time.perf_counter() if at is None else float(at)
        return self.t_window

    def window_closed(self, at: float) -> None:
        self.t_closed = float(at)


def run_cell(layout, workload: str, seed: int, seconds: float, trace: bool,
             devices, t_process: float) -> Dict:
    """One run of one cell on ``devices``; returns the result line as a
    dict (with the run's ``facts`` and ``checks`` beside the contract's
    keys, which the driver ignores)."""
    from benchmark import counts, device
    from benchmark import reduce as trace_reduce

    cell = layout.cell(workload)
    ctx = Ctx(layout, cell, seed, seconds, trace, devices, t_process)
    kind = layout.kind(cell["mix"]["kind"])
    out = kind.run(ctx)
    from flexflow_tpu.utils.compile_cache import compile_stats

    ctx.facts["jax"] = compile_stats()  # the whole process, set-up included
    ctx.note("kind returned")
    if ctx.t_window is None:
        raise RuntimeError(f"kind {cell['mix']['kind']!r} opened no window")
    setup_s = ctx.t_window - t_process
    reduced = None
    if trace:
        if not ctx.profiler.done:
            raise RuntimeError("a traced run has to profile its window")
        reduced = trace_reduce.reduce_trace(trace_reduce.load_xplane(
            trace_reduce.find_xplane(ctx.profiler.out_dir)))
    dev = device.describe(ctx.devices)
    run = {"cell": cell, "config": ctx.config, "mix": ctx.mix,
           "family": ctx.family,  # what a shared reader asks its counts of
           "facts": ctx.facts, "trace": reduced, "setup_s": setup_s,
           "end_to_end": out["end_to_end"], "chips": ctx.chips,
           "peaks": (counts.peaks_for(dev["kind"])
                     if dev["platform"] == "tpu" else None)}
    metrics: Dict[str, Dict] = {}
    if trace:
        for m in cell["per_layer"]:
            value = layout.reader(m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        dev["busy_s"] = reduced["busy_s"]
        dev["window_s"] = reduced["window_s"]
    else:
        values = dict(out["end_to_end"], setup_s=setup_s)
        for m in cell["end_to_end"]:
            if m["name"] in values:
                metrics[m["name"]] = {"value": float(values[m["name"]]),
                                      "unit": m["unit"]}
    result = {"correct": ctx.checks.correct,
              "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": metrics,
              "device": dev}
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced["ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    ctx.note("reduced and read")
    result["checks"] = ctx.checks.rows
    result["facts"] = _small(ctx.facts)
    result["_abandon_threads"] = bool(out.get("abandon_threads"))
    return result


def _small(facts: Dict) -> Dict:
    """The run's facts without the long lists, for the result line."""
    keep = {}
    for k, v in facts.items():
        if isinstance(v, (int, float, str, bool)) or v is None:
            keep[k] = v
        elif isinstance(v, dict) and k in ("fit_check", "serve_check",
                                           "search_profile"):
            keep[k] = {a: b for a, b in v.items()
                       if isinstance(b, (int, float, str, bool, dict))}
    return keep


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark import device
    from benchmark.spec import Layout

    layout = Layout(ROOT)
    chips = int(layout.cell(args.workload)["workload"]["chips"])
    try:
        devices = device.require_tpu(chips)
    except device.NoAccelerator as e:
        print(f"[bench] no result: {e}", file=sys.stderr, flush=True)
        return 2
    import jax

    was = jax.config.jax_compilation_cache_max_size
    cache = device.place_compile_cache(ROOT)
    print(f"[bench] compile cache max size was {was}, now "
          f"{jax.config.jax_compilation_cache_max_size}", flush=True)
    print(f"[bench] {args.workload} seed={args.seed} seconds={args.seconds} "
          f"trace={args.trace} devices={len(devices)} x "
          f"{devices[0].device_kind!r} compile_cache={cache!r}", flush=True)
    try:
        result = run_cell(layout, args.workload, args.seed, args.seconds,
                          bool(args.trace), devices, T_PROCESS)
    except Exception:  # noqa: BLE001 — no result line on any failure
        traceback.print_exc()
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(1)  # a scheduler thread may still hold the device
    abandon = result.pop("_abandon_threads")
    print(json.dumps(result), flush=True)
    sys.stderr.flush()
    if abandon:
        # requests nothing measures are still decoding in the scheduler's
        # thread; leaving without waiting for them ends them with the
        # process (no child process exists)
        os._exit(0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
