"""The one traffic generator: a mix's data file in, the run's work out.

A mix is ``benchmark/traffic/<name>.json`` with a ``kind`` (which file of
``benchmark/kinds/`` runs it) and that kind's parameters. Lengths and
gaps are not drawn: a distribution is read at evenly spaced quantiles,
so a mix of N requests always holds the same N lengths, and the list is
put in order once by the mix's own ``trace_seed``. ``--seed`` never
changes the schedule; it makes the weights and the token ids.
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict, List, NamedTuple

import numpy as np

KINDS_WITH_SCHEDULE = ("serve_open", "serve_closed")


class Request(NamedTuple):
    due_s: float       # seconds after the load starts (0 in a closed loop)
    prompt_len: int
    answer_len: int


def quantiles(dist: Dict, n: int) -> List[float]:
    """``dist`` at the n quantiles (i + 0.5) / n, in rising order."""
    qs = [(i + 0.5) / n for i in range(n)]
    kind = dist["dist"]
    if kind == "lognormal":
        mu, sigma = math.log(dist["median"]), float(dist["sigma"])
        nd = NormalDist()
        return [math.exp(mu + sigma * nd.inv_cdf(q)) for q in qs]
    if kind == "uniform":
        lo, hi = float(dist["min"]), float(dist["max"])
        return [lo + (hi - lo) * q for q in qs]
    if kind == "exponential":
        mean = float(dist["mean"])
        return [-mean * math.log1p(-q) for q in qs]
    if kind == "fixed":
        return [float(dist["value"])] * n
    raise ValueError(f"unknown distribution {kind!r}")


def lengths(dist: Dict, n: int) -> List[int]:
    """n whole lengths at the distribution's quantiles, clipped to its
    ``min`` and ``max``."""
    lo, hi = int(dist["min"]), int(dist["max"])
    return [min(hi, max(lo, int(round(x)))) for x in quantiles(dist, n)]


def schedule(mix: Dict) -> List[Request]:
    """The mix's whole list of requests, in the order in which they are
    sent. The same mix gives the same list, always.

    ``serve_open``: ``round(rate_rps * horizon_s)`` requests; gaps are
    an exponential's quantiles with mean ``1 / rate_rps`` (so they sum
    to the horizon within a percent or so), shuffled; with a ``burst``,
    the first ``requests`` of them due after ``at_s`` become due evenly
    inside ``within_s`` from there, and the later ones follow the
    burst's end by their own gaps (the list ends that much sooner).
    ``serve_closed``: ``jobs`` requests, all due at once; the kind keeps
    ``clients`` of them in flight.
    """
    kind = mix["kind"]
    if kind not in KINDS_WITH_SCHEDULE:
        raise ValueError(f"kind {kind!r} has no schedule")
    rng = np.random.default_rng(int(mix["trace_seed"]))
    if kind == "serve_closed":
        n = int(mix["jobs"])
        due = [0.0] * n
    else:
        rate, horizon = float(mix["rate_rps"]), float(mix["horizon_s"])
        n = int(round(rate * horizon))
        gaps = np.array(quantiles({"dist": "exponential",
                                   "mean": 1.0 / rate}, n))
        rng.shuffle(gaps)
        due = np.cumsum(gaps) - gaps[0]
        burst = mix.get("burst")
        if burst:
            k, at, within = (int(burst["requests"]), float(burst["at_s"]),
                             float(burst["within_s"]))
            first = int(np.searchsorted(due, at))
            if first + k > n:
                raise ValueError("the burst does not fit the horizon")
            # the k requests from `first` on arrive inside `within`; the
            # ones after them keep their own gaps from the burst's end
            after = due[first + k:] - due[first + k - 1] if first + k < n \
                else np.array([])
            due[first:first + k] = at + within * np.arange(k) / k
            due[first + k:] = at + within + after
        due = [float(x) for x in due]
    prompts = np.array(lengths(mix["prompt"], n))
    answers = np.array(lengths(mix["answer"], n))
    rng.shuffle(prompts)
    rng.shuffle(answers)
    cap = int(mix["max_length"])
    out = []
    for t, p, a in zip(due, prompts, answers):
        if p + a > cap:
            raise ValueError(f"a request of {p}+{a} tokens exceeds "
                             f"max_length {cap}")
        out.append(Request(t, int(p), int(a)))
    return out


def bucket_for(buckets: List[int], prompt_len: int) -> int:
    for b in sorted(buckets):
        if b >= prompt_len:
            return b
    raise ValueError(f"prompt of {prompt_len} tokens exceeds the largest "
                     f"prefill bucket {max(buckets)}")


def buckets_used(mix: Dict, reqs: List[Request]) -> List[int]:
    return sorted({bucket_for(mix["prefill_buckets"], r.prompt_len)
                   for r in reqs})


def token_ids(seed: int, index: int, length: int, vocab: int) -> np.ndarray:
    """The prompt of request ``index`` under ``--seed``."""
    rng = np.random.default_rng([int(seed), int(index)])
    return rng.integers(0, vocab, size=length, dtype=np.int64).astype(np.int32)


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the
    order statistics, as numpy's default does."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of nothing")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)
