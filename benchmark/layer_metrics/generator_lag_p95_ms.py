"""How late the load generator sent the scored requests: 95th percentile
of send time minus due time, from the benchmark's own stamps. A starved
generator must not read as a fast server. Layer: Load generator."""

from benchmark import traffic


def read(run):
    lag = run["facts"].get("generator_lag_s")
    return None if not lag else 1e3 * traffic.percentile(lag, 95)
