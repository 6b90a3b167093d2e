"""Rows the held experts' products ran over against rows the routing
named, over the window, prefill and decode together, all expert layers:
the deltas of ``stats()["moe"]``'s ``rows_computed`` and
``prompt_rows_computed`` (counted from the programs' shapes by the form
each took, or on the device by the grouped kernel: real tiles x tile
rows) over ``pairs_held`` and ``prompt_pairs_held`` (counted on the
device: the active slots' and the live prompt tokens' pairs whose expert
is held). 1 is a product over the named rows alone. A form that gives
every row to every held expert reads R / k with R experts routed over and
k picks a token; the grouped kernel (every program since PR 43 whose rows
can name under 0.9 of the experts held, or number past 240) reads a tile
an expert that got a row: a bucket's padding, idle slots and short tiles
are what is left above 1. Layer: Expert layer."""

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
