"""(query, row) pairs the window's prefill chunks' attention ran its
products over, over the pairs their indexer took: the window's deltas of
``stats()["kv"]["index"]``'s ``rows_attended`` and ``rows_taken``. 1 where
a chunk attends only what it selected; above it where it walks the key
spans under the selection's mask. None from a program without the
counters or a window without a chunk. Layer: Kernels."""


def read(run):
    f = run["facts"]
    s0, s1 = f.get("stats0"), f.get("stats1")
    if not s0 or not s1 or "index" not in s1.get("kv", {}):
        return None
    a, b = s0["kv"].get("index") or {}, s1["kv"]["index"]
    taken = b["rows_taken"] - a.get("rows_taken", 0)
    if taken <= 0:
        return None
    return (b["rows_attended"] - a.get("rows_attended", 0)) / taken
