"""Median wall time of one prefill dispatch, during which every decode
slot stalls (``stats()["phases"]["prefill"]``). Layer: Paged decoder."""


def read(run):
    ph = (run["facts"].get("stats2") or {}).get("phases", {}).get("prefill")
    return None if not ph else 1e3 * ph["p50"]
