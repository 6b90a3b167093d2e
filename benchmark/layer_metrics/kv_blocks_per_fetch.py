"""Blocks ONE copy of the decode kernel would bring, over the tables the
pool handed out in the window: the deltas of ``stats()["kv"]["fetch_runs"]``.
A table is walked in groups of ``run_blocks`` entries; a group whose
entries are neighbours ascending in the arena (``groups_run`` of
``groups``) can come by one copy, any other by a copy a block:
``run_blocks x groups / (groups_run + run_blocks x (groups -
groups_run))``. 1.0 where every block is a copy of its own, ``run_blocks``
where every group is a run; seams between the stretches a table is made
of lie in between. This is the TABLES' share of runs, counted once a
request at admission over the whole reserved table; it is not a count of
the copies the kernel issues, which also takes a slot's last chunk, and a
chunk a seam lies in, by a copy a block (``tools/latent_fetch_sweep.py``
``copies_a_call`` counts those from live lengths). None from a program
whose pool has no such counter, and in a window that admitted nothing.
Layer: KV pool."""


def read(run):
    f = run["facts"]
    r0 = ((f.get("stats0") or {}).get("kv") or {}).get("fetch_runs")
    r1 = ((f.get("stats1") or {}).get("kv") or {}).get("fetch_runs")
    if not r0 or not r1:
        return None
    run_blocks = r1["run_blocks"]
    groups = r1["groups"] - r0["groups"]
    as_one = r1["groups_run"] - r0["groups_run"]
    if groups <= 0:
        return None
    return run_blocks * groups / (as_one + run_blocks * (groups - as_one))
