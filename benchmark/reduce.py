"""From a profiler trace to numbers: busy and idle time of the device,
device time per program and per operation, collective time that no
compute hides, and the idle gaps by the benchmark span that covered
them.

The arithmetic works on a plain structure, so that it can be checked on
a small recorded trace (``benchmark/tests/data/``) without a profiler::

    {"planes": [{"name": str,
                 "lines": [{"name": str,
                            "events": [[name, start_ns, duration_ns], ...]}]}]}

:func:`load_xplane` makes that structure from the ``.xplane.pb`` the JAX
profiler writes, with nothing but JAX. On a TPU each chip is a plane
``/device:TPU:<n>`` whose line ``XLA Ops`` has one event per executed
operation and whose line ``XLA Modules`` has one per executed program;
host threads are lines of the plane ``/host:CPU``, where
``jax.profiler.TraceAnnotation`` spans land on the same clock.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]  # [start_ns, end_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast|send|recv)")
SPAN_PREFIX = "bench."


def load_xplane(path: str) -> Dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                      for ev in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


# ---- interval arithmetic ---------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: Sequence[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def clip(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of the union ``a`` that the union ``b`` does not cover."""
    out: List[Interval] = []
    b = list(b)
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return subtract([(lo, hi)], clip(busy, lo, hi))


# ---- the reduction ---------------------------------------------------------

def _base_name(name: str) -> str:
    """``fusion.123`` -> ``fusion``; ``jit_step(7)`` -> ``jit_step``;
    ``%copy.25 = bf16[...] copy(...)`` (the TPU names an operation by its
    whole HLO text) -> ``copy``."""
    name = name.strip()
    if name.startswith("%"):
        name = name[1:].split(" ", 1)[0]
    name = re.sub(r"\(.*\)$", "", name)
    return re.sub(r"[.\d]+$", "", name) or name


def _events(plane: Dict, line_name: str) -> List[List]:
    return [ev for line in plane["lines"] if line["name"] == line_name
            for ev in line["events"]]


def _spans(trace: Dict, only_ours: bool = True) -> List[Tuple[str, int, int]]:
    """The host threads' spans: the benchmark's own (``bench.*``), or
    every span the host tracer recorded (dispatches, transfers, waits),
    named without their arguments."""
    out = []
    for plane in trace["planes"]:
        if plane["name"] != HOST_PLANE:
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name.startswith(SPAN_PREFIX):
                    out.append((name, start, start + dur))
                elif not only_ours and dur > 0:
                    out.append((_base_name(name), start, start + dur))
    return out


def _attribute_gaps(idle: Sequence[Interval],
                     spans: Sequence[Tuple[str, int, int]]) -> Dict[str, int]:
    """Nanoseconds of idle time by host span: each idle interval goes,
    whole, to the shortest span that covers its midpoint (``(no span)``
    where none does; the window's own span is no answer). Spans are
    taken shortest first and each claims the unclaimed intervals whose
    midpoints it covers, found by bisection: a traced serving window
    has some hundred thousand of each."""
    import numpy as np

    if not idle:
        return {}
    start = np.array([s for s, _ in idle], np.int64)
    length = np.array([e - s for s, e in idle], np.int64)
    mid = start + length // 2
    owner = np.full(len(idle), -1, np.int64)
    names: List[str] = []
    index: Dict[str, int] = {}
    for name, s, e in sorted(spans, key=lambda sp: sp[2] - sp[1]):
        if name == SPAN_PREFIX + "window":
            continue
        i0, i1 = np.searchsorted(mid, (s, e), side="left")
        if i1 <= i0:
            continue
        free = owner[i0:i1] < 0
        if free.any():
            if name not in index:
                index[name] = len(names)
                names.append(name)
            owner[i0:i1][free] = index[name]
    out = {names[i]: int(length[owner == i].sum()) for i in range(len(names))}
    if (owner < 0).any():
        out["(no span)"] = int(length[owner < 0].sum())
    return out


def reduce_trace(trace: Dict, window: Optional[Interval] = None,
                 top: int = 10) -> Dict:
    """Everything the per-layer readers take from a trace.

    ``window``: the traced interval in the trace's own nanoseconds;
    without it, from the first to the last device event (or, with a
    ``bench.window`` span on the host, that span). Seconds throughout:

    * ``window_s``, ``busy_s`` (mean over the device planes of the union
      of their operations' intervals), ``idle_share``, ``devices``;
    * ``programs``: per program of ``XLA Modules``, ``count`` and
      ``device_s`` (summed over planes, divided by the planes: per chip);
    * ``ops``: the ``top`` operations by time, ``[name, seconds]`` per chip;
    * ``collective_s`` and ``collective_exposed_s`` per chip: the union
      of the collective operations, and its part during which no other
      operation runs on that chip;
    * ``idle_gaps``: ``[span, seconds]`` — the idle intervals of the first
      device plane by what the host was doing: each goes to the shortest
      host span covering its midpoint, the benchmark's own or the host
      tracer's (``(no span)`` where none does).
    """
    dev = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    if not dev:
        raise ValueError("the trace has no /device:TPU:<n> plane")
    spans = _spans(trace)
    if window is None:
        win = [s for s in spans if s[0] == SPAN_PREFIX + "window"]
        if win:
            window = (min(s[1] for s in win), max(s[2] for s in win))
        else:
            evs = [ev for p in dev for ev in _events(p, OPS_LINE)]
            if not evs:
                raise ValueError("no operation ran on the device")
            window = (min(e[1] for e in evs), max(e[1] + e[2] for e in evs))
    lo, hi = window
    n = len(dev)
    busy_ns = 0
    coll_ns = exposed_ns = 0
    op_ns: Dict[str, int] = {}
    programs: Dict[str, Dict[str, float]] = {}
    first_busy: List[Interval] = []
    for i, plane in enumerate(dev):
        ops = [(name, s, s + d) for name, s, d in _events(plane, OPS_LINE)]
        busy = clip(union((s, e) for _, s, e in ops), lo, hi)
        if i == 0:
            first_busy = busy
        busy_ns += total(busy)
        ops = [(_base_name(nm), s, e) for nm, s, e in ops]
        coll = clip(union((s, e) for nm, s, e in ops
                          if COLLECTIVE.match(nm)), lo, hi)
        other = clip(union((s, e) for nm, s, e in ops
                           if not COLLECTIVE.match(nm)), lo, hi)
        coll_ns += total(coll)
        exposed_ns += total(subtract(coll, other))
        for nm, s, e in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                op_ns[nm] = op_ns.get(nm, 0) + d
        for nm, s, d in _events(plane, MODULES_LINE):
            if s >= lo and s + d <= hi:  # whole executions only
                rec = programs.setdefault(_base_name(nm),
                                          {"count": 0, "device_s": 0.0})
                rec["count"] += 1
                rec["device_s"] += d / 1e9
    for rec in programs.values():
        rec["count"] = rec["count"] / n
        rec["device_s"] = rec["device_s"] / n
    gap_ns = _attribute_gaps(gaps(first_busy, lo, hi),
                             _spans(trace, only_ours=False))
    window_s = (hi - lo) / 1e9
    busy_s = busy_ns / n / 1e9
    ranked = sorted(op_ns.items(), key=lambda kv: -kv[1])
    return {
        "devices": n, "window_s": window_s, "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "programs": programs,
        "ops": [[k, v / n / 1e9] for k, v in ranked[:top]],
        "collective_s": coll_ns / n / 1e9,
        "collective_exposed_s": exposed_ns / n / 1e9,
        "idle_gaps": [[k, v / 1e9] for k, v in sorted(
            gap_ns.items(), key=lambda kv: -kv[1])[:top]],
    }


def program_time(reduced: Dict, pattern: str) -> Optional[Dict[str, float]]:
    """``count`` and ``device_s`` summed over the programs whose name
    matches ``pattern``; None where none ran inside the window."""
    rx = re.compile(pattern)
    hit = [r for name, r in reduced["programs"].items() if rx.search(name)]
    if not hit:
        return None
    return {"count": sum(r["count"] for r in hit),
            "device_s": sum(r["device_s"] for r in hit)}


def least_share(run: Dict, program: str, asks: str) -> Optional[float]:
    """The least time one execution of ``program`` could take, as the
    run's family states it (its function ``asks``, seconds), over the
    program's measured device time an execution, in %; None untraced,
    without peaks, where no such program ran whole inside the window, or
    where the family does not say."""
    if run["trace"] is None or run["peaks"] is None:
        return None
    t = program_time(run["trace"], program)
    ask = getattr(run["family"], asks, None)
    if t is None or ask is None:
        return None
    least_s = ask(run)
    if least_s is None:
        return None
    return 100.0 * least_s / (t["device_s"] / t["count"])


def describe(trace: Dict, sample: int = 4) -> str:
    """Planes, lines and a few events of each: look at a trace by hand
    before trusting code against it."""
    rows = []
    for plane in trace["planes"]:
        rows.append(f"plane {plane['name']!r}")
        for line in plane["lines"]:
            evs = line["events"]
            names = sorted({_base_name(e[0]) for e in evs})
            rows.append(f"  line {line['name']!r}: {len(evs)} events, "
                        f"{len(names)} names: {names[:12]}")
            for e in evs[:sample]:
                rows.append(f"    {e}")
    return "\n".join(rows)


def _main(argv) -> int:
    """``python3 -m benchmark.reduce <trace dir> [<out.json>]``: describe
    the newest trace under the directory, print its reduction, and
    optionally write the plain structure (device lines cut to the first
    ``N`` events) for a test to keep."""
    import json

    trace = load_xplane(find_xplane(argv[1]))
    print(describe(trace))
    try:
        print(json.dumps(reduce_trace(trace), indent=1))
    except ValueError as e:
        print(f"no reduction: {e}")
    if len(argv) > 2:
        keep = int(argv[3]) if len(argv) > 3 else 400
        small = {"planes": [
            {"name": p["name"],
             "lines": [{"name": ln["name"], "events": ln["events"][:keep]}
                       for ln in p["lines"]
                       if DEVICE_PLANE.match(p["name"])
                       or any(e[0].startswith(SPAN_PREFIX)
                              for e in ln["events"])]}
            for p in trace["planes"]
            if DEVICE_PLANE.match(p["name"]) or p["name"] == HOST_PLANE]}
        for p in small["planes"]:
            if p["name"] == HOST_PLANE:
                for ln in p["lines"]:
                    ln["events"] = [e for e in ln["events"]
                                    if e[0].startswith(SPAN_PREFIX)]
        with open(argv[2], "w") as f:
            json.dump(small, f)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(_main(sys.argv))
