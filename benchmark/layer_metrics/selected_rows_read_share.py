"""Rows an indexed attention layer's decode steps read over the rows live
in their slots, in %: the window's deltas of ``stats()["kv"]["index"]``'s
``rows_read`` (a query's picks' rows and its own pool's) and ``rows_live``
(every row cached before it, and its own). Under 100 wherever a slot's
context passes the indexer's budget. None from a program without the
counters. Layer: KV pool."""


def read(run):
    f = run["facts"]
    s0, s1 = f.get("stats0"), f.get("stats1")
    if not s0 or not s1 or "index" not in s1.get("kv", {}):
        return None
    a, b = s0["kv"].get("index") or {}, s1["kv"]["index"]
    live = b["rows_live"] - a.get("rows_live", 0)
    if live <= 0:
        return None
    return 100.0 * (b["rows_read"] - a.get("rows_read", 0)) / live
