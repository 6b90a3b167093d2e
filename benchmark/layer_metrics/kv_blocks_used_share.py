"""The most blocks of the KV pool that requests had reserved at once
(``stats()["kv"]["high_water"]``) over the blocks it can give out, in %.
Admission reserves a request's worst case, so this is the share of the
pool the traffic claims, not the share that holds live tokens. Layer:
KV pool."""


def read(run):
    kv = (run["facts"].get("stats2") or {}).get("kv")
    return None if not kv else 100.0 * kv["high_water"] / kv["capacity_blocks"]
