"""What the readers of a routed model's cell share: the window's deltas
of ``stats()["moe"]`` (the program's device-side counters of its expert
layers) and of ``stats()["kv"]``'s block counters. A program without
them gives None, and the readers then report nothing.
"""

from __future__ import annotations

from typing import Dict, List, Optional


def expert_layers(run: Dict) -> Optional[List[Dict]]:
    """Per expert layer of the window: ``steps``, ``count`` (experts
    held), ``rows`` (count,) rows each held expert got, ``idle`` (held
    experts without a row, summed over steps); None without the counters
    or where no step ran."""
    f = run["facts"]
    m0 = (f.get("stats0") or {}).get("moe")
    m1 = (f.get("stats1") or {}).get("moe")
    if not m0 or not m1:
        return None
    out = []
    for name, b in m1.items():
        a = m0.get(name)
        if a is None or b["steps"] - a["steps"] <= 0:
            return None
        out.append({
            "steps": b["steps"] - a["steps"], "count": b["held"][1],
            "rows": [y - x for x, y in zip(a["rows_per_held_expert"],
                                           b["rows_per_held_expert"])],
            "idle": b["idle_held_experts"] - a["idle_held_experts"]})
    return out or None


def expert_hit_share(run: Dict) -> Optional[float]:
    """Share of (held expert, step) pairs in which the expert got a row,
    over all expert layers."""
    layers = expert_layers(run)
    if layers is None:
        return None
    pairs = sum(l["steps"] * l["count"] for l in layers)
    return 1.0 - sum(l["idle"] for l in layers) / pairs


def live_tokens_per_step(run: Dict) -> Optional[float]:
    """Tokens cached in the active slots at a decode step, at the least:
    a slot that read ``b`` blocks holds more than ``(b - 1) *
    block_size`` tokens, so the window's ``blocks_read`` less one block a
    slot-step, times the block size, over the steps."""
    f = run["facts"]
    s0, s1 = f.get("stats0"), f.get("stats1")
    if not s0 or not s1:
        return None
    steps = s1["decode_steps"] - s0["decode_steps"]
    blocks = s1["kv"].get("blocks_read", 0) - s0["kv"].get("blocks_read", 0)
    if steps <= 0 or blocks <= 0:
        return None
    slot_steps = ((s1["tokens"] - s0["tokens"])
                  - (s1["prefill_prompts"] - s0["prefill_prompts"]))
    return max(0.0, (blocks - slot_steps) * s1["kv"]["block_size"] / steps)


def op_seconds(run: Dict, needle: str) -> Optional[float]:
    """Device seconds of the traced window's operations whose name holds
    ``needle``; None where it is not among the trace's listed ones."""
    tr = run.get("trace")
    if tr is None:
        return None
    hit = [s for name, s in tr["ops"] if needle in name]
    return sum(hit) if hit else None
