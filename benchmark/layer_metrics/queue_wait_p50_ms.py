"""Median time a request waited for a decode slot
(``stats()["phases"]["queue_wait"]``, over the lead-in and the window).
Layer: Scheduler."""


def read(run):
    ph = (run["facts"].get("stats2") or {}).get("phases", {}).get("queue_wait")
    return None if not ph else 1e3 * ph["p50"]
