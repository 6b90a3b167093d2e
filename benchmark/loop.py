"""What the readers of the scheduler's own clock share: the deltas of
``stats()["loop"]`` across the measured window.

``stats()["loop"]`` holds, cumulative since the scheduler's loop thread
started: ``steps`` (passes that ran a decode step or a speculative
round), ``phase_s`` (seconds by phase: ``wait``, ``admit``, ``prefill``,
``inputs``, ``dispatch``, ``fetch``, ``sample``, ``other``; they
telescope, so their sum is the thread's lifetime) and two histograms,
``step_wall`` and ``token_gap``, whose ``buckets`` are ``{upper bound in
seconds: count}``, never reset. The serving kinds keep ``stats()`` at
the window's two ends (``stats0``, ``stats1``), so everything here is a
difference of two readings. A program without these counters (any
before PR 25) gives None, and the readers then report nothing.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

HOST_PHASES = ("admit", "inputs", "dispatch", "sample", "other")


def _ends(run: Dict):
    """``stats()["loop"]`` at the window's two ends, or None where
    either reading lacks it."""
    f = run["facts"]
    l0 = (f.get("stats0") or {}).get("loop")
    l1 = (f.get("stats1") or {}).get("loop")
    return (l0, l1) if l0 and l1 else None


def window(run: Dict) -> Optional[Dict]:
    """``{"steps": n, "phase_s": {phase: seconds}, "busy_s": all phases
    less ``wait``}`` of the window; None without the counters or where
    no step ran."""
    ends = _ends(run)
    if ends is None:
        return None
    l0, l1 = ends
    steps = l1["steps"] - l0["steps"]
    if steps <= 0:
        return None
    phase_s = {k: v - l0["phase_s"].get(k, 0.0)
               for k, v in l1["phase_s"].items()}
    return {"steps": steps, "phase_s": phase_s,
            "busy_s": sum(v for k, v in phase_s.items() if k != "wait")}


def bucket_rows(run: Dict,
                histogram: str) -> Optional[List[Tuple[float, int]]]:
    """``[(upper bound in seconds, count), ...]``, ascending: the
    window's own observations of one of the loop's histograms; None
    without the counters or where nothing was observed."""
    ends = _ends(run)
    if ends is None:
        return None
    l0, l1 = ends
    b0 = l0[histogram].get("buckets") or {}
    b1 = l1[histogram].get("buckets") or {}
    rows = sorted((float(k), n - b0.get(k, 0)) for k, n in b1.items())
    rows = [(b, n) for b, n in rows if n > 0]
    return rows or None


def bucket_percentile(rows: List[Tuple[float, int]], q: float) -> float:
    """The upper bound of the bucket that holds the ``q`` quantile
    (nearest rank): within a factor 2 ** 0.25 above the true value."""
    total = sum(n for _, n in rows)
    rank = min(total, max(1, int(round(q * (total - 1))) + 1))
    seen = 0
    for bound, n in rows:
        seen += n
        if seen >= rank:
            return bound
    return rows[-1][0]
