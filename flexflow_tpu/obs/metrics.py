"""Metrics registry: named counters / gauges / histograms, one process-
wide instance, JSON + Prometheus-text export.

This replaces the bespoke counter code the hot paths each grew (the
Prefetcher's ad-hoc wait/depth fields, the serving engine's nothing, the
pipeline engines' bare ``step_dispatches`` ints): every call site feeds
the SAME registry, so one scrape (``metrics_registry().to_prometheus()``)
or one snapshot (``.to_json()``) shows the whole system — search cache
hits, prefetch queue depth, dispatch-ahead occupancy, recompile
triggers, serving queue wait percentiles, pipeline bubble/dispatch
counters. ``tools/obs_report.py`` renders the snapshot; the ROADMAP's
"serves heavy traffic" north star gets its scrape endpoint for free by
dumping the Prometheus text.

The per-epoch :class:`EpochThroughput` record (the fit/eval loop's
``fit_profile`` contract, unchanged) lives here too and mirrors its
samples into the registry — per-epoch records for ``fit_report()``,
cumulative series for the scrape.
"""

from __future__ import annotations

import bisect
import collections
import re
import threading
import time
from typing import Dict, List, Optional

# quantiles exported for every histogram (Prometheus summary convention)
_QUANTILES = (0.5, 0.9, 0.99)

# upper bounds of every histogram's buckets: four a doubling from 0.1 ms
# to 105 s (81 bounds; a last bucket takes what lies above). Fixed, so
# two snapshots, or two processes' histograms, subtract and add bucket
# by bucket. In JSON a bucket is keyed by its bound (``_BUCKET_KEYS``).
BUCKET_BOUNDS = tuple(1e-4 * 2.0 ** (i / 4.0) for i in range(81))
_BUCKET_KEYS = tuple(f"{b:.6g}" for b in BUCKET_BOUNDS) + ("inf",)
_BUCKET_INDEX = {k: i for i, k in enumerate(_BUCKET_KEYS)}


def nearest_rank_percentile(xs, q: float) -> float:
    """THE nearest-rank quantile used everywhere latency percentiles
    are reported (Histogram reservoirs, the serving scheduler's session
    phases, serve_bench) — one formula, so p99s from different surfaces
    stay comparable. ``xs`` must be non-empty and sorted ascending."""
    return xs[min(len(xs) - 1, max(0, int(round(q * (len(xs) - 1)))))]


class Counter:
    """Monotonic counter."""

    __slots__ = ("value",)

    def __init__(self):
        self.value = 0.0

    def inc(self, n: float = 1.0) -> None:
        # GIL-atomic enough for stats (float add); a torn read costs one
        # sample of drift, never a crash — the hot step loop must not
        # take a lock per increment
        self.value += n  # concurrency: race-ok (lock-free by design: GIL-atomic add, drift tolerated)

    def to_json(self):
        v = self.value
        return int(v) if float(v).is_integer() else v

    def merge(self, other: "Counter") -> None:
        self.value += other.value  # concurrency: race-ok (merge folds quiesced worker registries)


class Gauge:
    """Last-written value."""

    __slots__ = ("value",)

    def __init__(self):
        self.value = 0.0

    def set(self, v: float) -> None:
        self.value = float(v)  # concurrency: race-ok (lock-free by design: GIL-atomic store of a last-writer-wins sample)

    def to_json(self):
        return self.value

    def merge(self, other: "Gauge") -> None:
        self.value = other.value  # concurrency: race-ok (merge folds quiesced worker registries)


class Histogram:
    """count/sum/min/max plus a bounded reservoir of the most recent
    samples for percentile estimation (latency p50/p90/p99). The
    reservoir keeps the RECENT window — the flight-recorder convention,
    matched to the tracer's ring buffer.

    Beside it, counts per bucket at the fixed ``BUCKET_BOUNDS``, never
    reset: the difference of two ``to_json()["buckets"]`` snapshots is
    the distribution of exactly the observations between them, which a
    reservoir of the last 1024 cannot give (:func:`bucket_delta`)."""

    __slots__ = ("count", "sum", "min", "max", "_recent", "_buckets")

    def __init__(self, reservoir: int = 1024):
        self.count = 0
        self.sum = 0.0
        self.min = float("inf")
        self.max = float("-inf")
        self._recent: collections.deque = collections.deque(maxlen=reservoir)
        self._buckets = [0] * len(_BUCKET_KEYS)

    def observe(self, v: float) -> None:
        v = float(v)
        self.count += 1  # concurrency: race-ok (lock-free by design: GIL-atomic add, drift tolerated)
        self.sum += v  # concurrency: race-ok (lock-free by design, see count)
        if v < self.min:
            self.min = v  # concurrency: race-ok (lock-free by design, see count)
        if v > self.max:
            self.max = v  # concurrency: race-ok (lock-free by design, see count)
        self._recent.append(v)
        self._buckets[bisect.bisect_left(BUCKET_BOUNDS, v)] += 1  # concurrency: race-ok (lock-free by design, see count)

    def percentile(self, q: float) -> float:
        xs = sorted(self._recent)
        if not xs:
            return 0.0
        return nearest_rank_percentile(xs, q)

    @property
    def mean(self) -> float:
        return self.sum / self.count if self.count else 0.0

    def to_json(self) -> Dict:
        if not self.count:
            return {"count": 0}
        return {
            "count": self.count,
            "sum": round(self.sum, 9),
            "mean": round(self.mean, 9),
            "min": self.min,
            "max": self.max,
            **{f"p{int(q * 100)}": self.percentile(q) for q in _QUANTILES},
            # {upper bound: count}, the buckets that hold something
            "buckets": {k: n for k, n in zip(_BUCKET_KEYS, self._buckets)
                        if n},
        }

    def merge(self, other: "Histogram") -> None:
        self.count += other.count  # concurrency: race-ok (merge folds quiesced worker registries)
        self.sum += other.sum  # concurrency: race-ok (merge folds quiesced registries, see count)
        self.min = min(self.min, other.min)  # concurrency: race-ok (see count)
        self.max = max(self.max, other.max)  # concurrency: race-ok (see count)
        self._buckets = [a + b for a, b in  # concurrency: race-ok (see count)
                         zip(self._buckets, other._buckets)]
        # reservoir merge: appending ALL of other's window into the
        # maxlen-bounded deque would evict every one of self's samples
        # whenever other has >= maxlen entries — merged percentiles would
        # reflect only one process. Instead each window is subsampled
        # (evenly strided, order preserved) to its proportional share of
        # the capacity and the two are interleaved, so future appends
        # evict both processes' samples fairly.
        if not other._recent:
            return
        cap = self._recent.maxlen
        a, b = list(self._recent), list(other._recent)
        if cap is not None and len(a) + len(b) > cap:
            na = min(len(a), max(1, round(cap * len(a) / (len(a) + len(b)))))
            a, b = _strided(a, na), _strided(b, cap - na)
        self._recent = collections.deque(  # concurrency: race-ok (see count)
            _interleave(a, b), maxlen=cap)


def bucket_delta(before: Optional[Dict], after: Dict) -> List[tuple]:
    """``[(upper bound, count), ...]``, ascending: the observations
    between two ``Histogram.to_json()`` snapshots of one histogram
    (``before`` may be None or empty: everything up to ``after``)."""
    b0 = (before or {}).get("buckets") or {}
    rows = [(float(k), n - b0.get(k, 0))
            for k, n in (after.get("buckets") or {}).items()]
    return sorted((b, n) for b, n in rows if n)


def _strided(xs: List[float], n: int) -> List[float]:
    """``n`` evenly-spaced samples of ``xs``, order preserved (the
    deterministic subsample the reservoir merge uses)."""
    if n >= len(xs):
        return list(xs)
    if n <= 0:
        return []
    step = len(xs) / n
    return [xs[min(len(xs) - 1, int(i * step))] for i in range(n)]


def _interleave(a: List[float], b: List[float]) -> List[float]:
    out: List[float] = []
    la, lb = len(a), len(b)
    for i in range(max(la, lb)):
        if i < la:
            out.append(a[i])
        if i < lb:
            out.append(b[i])
    return out


def _prom_name(name: str) -> str:
    """Dotted registry names -> Prometheus-legal metric names."""
    return "flexflow_" + re.sub(r"[^a-zA-Z0-9_]", "_", name)


class MetricsRegistry:
    """Name -> metric map. Creation is locked; recording goes straight
    to the (lock-free) metric objects."""

    def __init__(self):
        self._metrics: Dict[str, object] = {}
        self._lock = threading.Lock()

    def _get(self, name: str, cls):
        m = self._metrics.get(name)
        if m is None:
            with self._lock:
                m = self._metrics.setdefault(name, cls())
        if not isinstance(m, cls):
            raise TypeError(
                f"metric {name!r} already registered as "
                f"{type(m).__name__}, requested {cls.__name__}")
        return m

    def counter(self, name: str) -> Counter:
        return self._get(name, Counter)

    def gauge(self, name: str) -> Gauge:
        return self._get(name, Gauge)

    def histogram(self, name: str) -> Histogram:
        return self._get(name, Histogram)

    def names(self) -> List[str]:
        return sorted(self._metrics)

    def get(self, name: str):
        return self._metrics.get(name)

    def reset(self) -> None:
        with self._lock:
            self._metrics.clear()

    def merge(self, other: "MetricsRegistry") -> "MetricsRegistry":
        """Fold another registry in (same-name metrics must share a
        type): counters add, gauges take the other's value, histograms
        pool. Multi-process aggregation (one registry per worker,
        merged by the parent) and the round-trip tests use this."""
        for name in other.names():
            om = other.get(name)
            self._get(name, type(om)).merge(om)
        return self

    # ---------------------------------------------------------------- export
    def to_json(self) -> Dict:
        return {name: m.to_json() for name, m in sorted(self._metrics.items())}

    def to_prometheus(self) -> str:
        """Prometheus text exposition: counters/gauges as-is, histograms
        as summaries (quantile series + _sum/_count)."""
        lines: List[str] = []
        for name, m in sorted(self._metrics.items()):
            pn = _prom_name(name)
            if isinstance(m, Counter):
                lines.append(f"# TYPE {pn} counter")
                lines.append(f"{pn} {m.value:g}")
            elif isinstance(m, Gauge):
                lines.append(f"# TYPE {pn} gauge")
                lines.append(f"{pn} {m.value:g}")
            else:
                lines.append(f"# TYPE {pn} summary")
                for q in _QUANTILES:
                    lines.append(
                        f'{pn}{{quantile="{q}"}} {m.percentile(q):g}')
                lines.append(f"{pn}_sum {m.sum:g}")
                lines.append(f"{pn}_count {m.count}")
        return "\n".join(lines) + ("\n" if lines else "")

    @staticmethod
    def from_json(doc: Dict) -> "MetricsRegistry":
        """Rebuild a registry from :meth:`to_json` output (histograms
        keep count/sum/min/max and the bucket counts — the reservoir,
        hence percentiles, is not serialized). Types round-trip by JSON
        representation:
        gauges always serialize as floats (``3.0``) and counters as
        ints when integral (``3``), so an integral-valued gauge still
        rebuilds as a Gauge and merges cleanly with a live registry.
        The one ambiguity left: a counter incremented by FRACTIONAL
        amounts rebuilds as a Gauge — keep fractional series on
        histograms/gauges (every built-in series does)."""
        reg = MetricsRegistry()
        for name, v in doc.items():
            if isinstance(v, dict):
                h = reg.histogram(name)
                h.count = int(v.get("count", 0))
                h.sum = float(v.get("sum", 0.0))
                h.min = float(v.get("min", float("inf")))
                h.max = float(v.get("max", float("-inf")))
                for k, n in (v.get("buckets") or {}).items():
                    h._buckets[_BUCKET_INDEX[k]] = int(n)
            elif isinstance(v, float):
                reg.gauge(name).set(v)
            else:
                reg.counter(name).inc(v)
        return reg


_REGISTRY = MetricsRegistry()


def metrics_registry() -> MetricsRegistry:
    return _REGISTRY


# --------------------------------------------------- step-loop throughput
class EpochThroughput:
    """Per-epoch counters of the fit/eval step loop (the observability
    half of the async input pipeline): how fast steps dispatched, how
    long the loop sat waiting for host input, how full the prefetch
    queue ran, and how deep the dispatch-ahead window actually was.

    The fit loop drives it; :class:`~..runtime.dataloader.Prefetcher`
    feeds the wait/depth counters. ``finish()`` renders one JSON-able
    record (the ``fit_profile`` epoch schema — unchanged across the
    move from runtime/profiling.py). Every sample is mirrored into the
    process registry (``fit.*`` series) so the scrape sees cumulative
    history across epochs and models.
    """

    def __init__(self, prefix: str = "fit"):
        self.steps = 0
        self.input_wait_s = 0.0
        self.depth_hist: Dict[int, int] = {}
        self._inflight_sum = 0
        self._inflight_obs = 0
        self.input_bytes = 0
        self._t0 = time.perf_counter()
        self.prefix = prefix  # registry series + trace span name prefix
        r = _REGISTRY
        # XLA compile requests so far (utils/compile_cache.py feeds the
        # counter): finish() reports how many fell inside this epoch —
        # a steady-state epoch should show none
        self._compiles0 = r.counter("jax.compiles").value
        self._m_wait = r.histogram(f"{prefix}.input_wait_s")
        self._m_depth = r.histogram(f"{prefix}.queue_depth")
        self._m_inflight = r.histogram(f"{prefix}.inflight_steps")
        self._m_steps = r.counter(f"{prefix}.steps")

    def record_wait(self, seconds: float) -> None:
        """Time the consumer spent blocked on host batch assembly/transfer
        (serial mode: the whole inline assembly; prefetch mode: queue-get
        block time — ~0 when the pipeline keeps up)."""
        self.input_wait_s += seconds
        self._m_wait.observe(seconds)

    def record_depth(self, depth: int) -> None:
        """Prefetch queue depth sampled at each batch request."""
        self.depth_hist[depth] = self.depth_hist.get(depth, 0) + 1
        self._m_depth.observe(depth)

    def record_inflight(self, n: int) -> None:
        """Dispatch-ahead window size observed when a step was issued."""
        self._inflight_sum += n
        self._inflight_obs += 1
        self._m_inflight.observe(n)

    def record_steps(self, n: int, nbytes: int = 0) -> None:
        self.steps += n
        self.input_bytes += nbytes
        self._m_steps.inc(n)

    def record_tokens(self, valid: int, total: int) -> None:
        """Token accounting for dynamic-shape epochs (runtime/buckets.py
        plans): ``valid`` real tokens out of ``total`` dispatched —
        ``finish()`` emits the padded-token fraction only when this was
        recorded, so fixed-shape epoch records are unchanged."""
        v, t = getattr(self, "_tokens", (0, 0))
        self._tokens = (v + int(valid), t + int(total))
        _REGISTRY.counter(f"{self.prefix}.valid_tokens").inc(int(valid))
        _REGISTRY.counter(f"{self.prefix}.total_tokens").inc(int(total))

    def finish(self) -> Dict:
        wall = time.perf_counter() - self._t0
        occ = (self._inflight_sum / self._inflight_obs
               if self._inflight_obs else 0.0)
        if wall > 0:
            _REGISTRY.gauge(f"{self.prefix}.steps_per_s").set(
                round(self.steps / wall, 3))
        rec = {
            "steps": self.steps,
            "wall_s": round(wall, 6),
            "steps_per_s": round(self.steps / wall, 3) if wall > 0 else 0.0,
            "input_wait_s": round(self.input_wait_s, 6),
            "input_mb_per_s": round(
                self.input_bytes / wall / 2**20, 3) if wall > 0 else 0.0,
            "queue_depth_hist": dict(sorted(self.depth_hist.items())),
            "dispatch_ahead_occupancy": round(occ, 3),
            "compiles": max(0, int(_REGISTRY.counter("jax.compiles").value
                                   - self._compiles0)),
        }
        tokens = getattr(self, "_tokens", None)
        if tokens is not None:
            rec["tokens"] = tokens[0]
            rec["padded_token_fraction"] = round(
                1.0 - tokens[0] / max(1, tokens[1]), 6)
        return rec


__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "EpochThroughput",
    "metrics_registry", "BUCKET_BOUNDS", "bucket_delta",
]
