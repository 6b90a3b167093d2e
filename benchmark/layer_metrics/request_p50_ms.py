"""Median, over the scored requests, of completion minus due time: the
steadier statistic beside ``request_p95_ms``. Layer: Scheduler."""

from benchmark import traffic


def read(run):
    lat = run["facts"].get("latency_s")
    return None if not lat else 1e3 * traffic.percentile(lat, 50)
