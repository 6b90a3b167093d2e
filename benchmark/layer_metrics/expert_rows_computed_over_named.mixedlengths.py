"""Rows the held experts' products ran over against rows the routing
named, over the window, prefill chunks and decode together, all expert
layers: the deltas of ``stats()["moe"]``'s ``rows_computed`` and
``prompt_rows_computed`` (a decode step's from the programs' shapes by the
form it took: every slot an expert in the dense form; a chunk's counted on
the device by the kernel: real tiles x tile rows) over ``pairs_held`` and
``prompt_pairs_held`` (counted on the device: the active slots' and the
live prompt tokens' pairs whose expert is held). With 32 slots, 256
experts routed over and 4 picks a token the dense form reads 64 rows a
named one where every slot is busy (0.5 rows an expert a step), the
kernel's tiles of 128 rows a few: what a decode form that read only the
named experts would save. Layer: Expert layer."""


KEYS = (("rows_computed", "pairs_held"),
        ("prompt_rows_computed", "prompt_pairs_held"))


def read(run):
    f = run["facts"]
    m0 = (f.get("stats0") or {}).get("moe")
    m1 = (f.get("stats1") or {}).get("moe")
    if not m0 or not m1:
        return None
    computed = named = 0
    for name, b in m1.items():
        a = m0.get(name)
        if a is None or any(k not in b or k not in a
                            for pair in KEYS for k in pair):
            return None
        for c, n in KEYS:
            computed += b[c] - a[c]
            named += b[n] - a[n]
    return computed / named if named > 0 else None
