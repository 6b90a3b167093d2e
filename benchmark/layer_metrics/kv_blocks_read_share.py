"""Blocks a decode step's attention read over the blocks in the active
slots' tables, in %: the window's deltas of ``stats()["kv"]``'s
``blocks_read`` and ``blocks_in_tables``. The blocks are whatever the
model's paged entries hold a token: keys and values, or a latent row
(that cell reported it as ``latent_blocks_read_share`` until PR 49); where
layers keep rings or states beside them, the full attention layers'
tables alone (a windowed layer has no table). Layer: KV pool."""


def read(run):
    f = run["facts"]
    s0, s1 = f.get("stats0"), f.get("stats1")
    if not s0 or not s1 or "blocks_in_tables" not in s1.get("kv", {}):
        return None
    tables = s1["kv"]["blocks_in_tables"] - s0["kv"].get("blocks_in_tables", 0)
    if tables <= 0:
        return None
    return 100.0 * (s1["kv"]["blocks_read"]
                    - s0["kv"].get("blocks_read", 0)) / tables
