"""What the readers of a cell with per-request states share: the window's
deltas of ``stats()["kv"]["state"]`` (the pool's rows and the scheduler's
``rows_stepped``: active slots x the ops that keep a state, summed over
decode steps). A program without them gives None, and the readers then
report nothing.
"""

from __future__ import annotations

from typing import Dict, Optional


def _ends(run: Dict):
    f = run["facts"]
    s0, s1 = f.get("stats0"), f.get("stats1")
    if not s0 or not s1 or "state" not in s1.get("kv", {}):
        return None
    return s0, s1


def rows_stepped(run: Dict) -> Optional[int]:
    """(slot, layer) states the window's decode steps updated."""
    ends = _ends(run)
    if ends is None:
        return None
    s0, s1 = ends
    return (s1["kv"]["state"]["rows_stepped"]
            - (s0["kv"].get("state") or {}).get("rows_stepped", 0))


def rows_per_step(run: Dict) -> Optional[float]:
    ends = _ends(run)
    if ends is None:
        return None
    steps = ends[1]["decode_steps"] - ends[0]["decode_steps"]
    return rows_stepped(run) / steps if steps > 0 else None


def cache_bytes(run: Dict, counts) -> Optional[tuple]:
    """``(state, kv)``: the bytes the window's decode steps moved of
    states (``rows_stepped`` times a state's bytes, in and out) and of
    keys and values (``blocks_read`` times a block's), by the family's
    ``counts`` module (its ``state_bytes``, ``kv_bytes_per_token``)."""
    rows = rows_stepped(run)
    blocks = blocks_read(run)
    if rows is None or blocks is None:
        return None
    state = rows * 2 * counts.state_bytes(run["config"])
    kv = (blocks * run["facts"]["stats1"]["kv"]["block_size"]
          * counts.kv_bytes_per_token(run["config"]))
    return state, kv


def blocks_read(run: Dict) -> Optional[int]:
    ends = _ends(run)
    if ends is None:
        return None
    s0, s1 = ends
    return s1["kv"]["blocks_read"] - s0["kv"].get("blocks_read", 0)
