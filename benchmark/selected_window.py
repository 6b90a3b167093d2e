"""What the readers of a cell share whose model selects the key blocks it
reads and prefills its prompts in chunks: the window's deltas of
``stats()["kv"]["selected"]`` (per decode step and active slot the blocks
a sparse layer's step read, beside the live blocks of its table) and of
``stats()["loop"]``'s chunk counters. A program without them gives None,
and the readers then report nothing.
"""

from __future__ import annotations

from typing import Dict, Optional


def _ends(run: Dict, group: str, key: str):
    f = run["facts"]
    s0, s1 = f.get("stats0"), f.get("stats1")
    if not s0 or not s1 or key not in (s1.get(group) or {}):
        return None
    return s0, s1


def selected(run: Dict) -> Optional[Dict[str, int]]:
    """``blocks_read`` and ``blocks_live`` of the window's decode steps."""
    ends = _ends(run, "kv", "selected")
    if ends is None:
        return None
    s0, s1 = ends
    was = s0["kv"].get("selected") or {}
    return {k: v - was.get(k, 0) for k, v in s1["kv"]["selected"].items()}


def per_step(run: Dict) -> Optional[Dict[str, float]]:
    """Per decode step of the window: the blocks selected and the live
    blocks (one sparse layer's), the live tokens at the least (a slot of
    ``b`` live blocks holds more than ``(b - 1) block_size`` tokens) and
    the (slot, layer) states stepped."""
    sel = selected(run)
    if sel is None:
        return None
    s0, s1 = run["facts"]["stats0"], run["facts"]["stats1"]
    steps = s1["decode_steps"] - s0["decode_steps"]
    if steps <= 0 or "state" not in s1["kv"]:
        return None
    slot_steps = ((s1["tokens"] - s0["tokens"])
                  - (s1["prefill_prompts"] - s0["prefill_prompts"]))
    rows = (s1["kv"]["state"]["rows_stepped"]
            - (s0["kv"].get("state") or {}).get("rows_stepped", 0))
    return {"selected": sel["blocks_read"] / steps,
            "live": sel["blocks_live"] / steps,
            "live_tokens": max(0.0, (sel["blocks_live"] - slot_steps)
                               * s1["kv"]["block_size"] / steps),
            "state_rows": rows / steps}


def chunks(run: Dict) -> Optional[Dict[str, int]]:
    """``chunks`` run and prompt ``tokens`` prefilled in the window."""
    ends = _ends(run, "loop", "prefill_chunks")
    if ends is None:
        return None
    l0, l1 = ends[0].get("loop") or {}, ends[1]["loop"]
    n = l1["prefill_chunks"] - l0.get("prefill_chunks", 0)
    if n <= 0:
        return None
    return {"chunks": n,
            "tokens": l1["prefill_tokens"] - l0.get("prefill_tokens", 0)}
