"""Median time from ``generate_async`` to the first token
(``stats()["phases"]["ttft"]``, on the scheduler's clock). Layer:
Scheduler."""


def read(run):
    ph = (run["facts"].get("stats2") or {}).get("phases", {}).get("ttft")
    return None if not ph else 1e3 * ph["p50"]
