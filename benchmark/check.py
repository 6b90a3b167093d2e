"""The comparison that decides ``correct``: numbers, each beside its
limit. The limits are data of the configuration's file (``limits``),
set from readings on the chip that PERF.md section 2 records.
"""

from __future__ import annotations

import math
from typing import Dict, List


class Checks:
    """Every number a run compared, beside its limit."""

    def __init__(self):
        self.rows: List[Dict] = []

    def at_most(self, name: str, value: float, limit: float) -> bool:
        ok = bool(math.isfinite(value) and value <= limit)
        self.rows.append({"name": name, "value": float(value),
                          "limit": float(limit), "ok": ok})
        print(f"[bench] check {name}: {value:.6g} <= {limit:.6g} "
              f"{'ok' if ok else 'NOT CORRECT'}", flush=True)
        return ok

    def equal(self, name: str, value, want) -> bool:
        ok = value == want
        self.rows.append({"name": name, "value": value, "limit": want,
                          "ok": bool(ok)})
        print(f"[bench] check {name}: {value} == {want} "
              f"{'ok' if ok else 'NOT CORRECT'}", flush=True)
        return bool(ok)

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows)


def rel_l2(got, want) -> float:
    """||got - want|| / ||want||, in float64 on the host."""
    import numpy as np

    got = np.asarray(got, np.float64).ravel()
    want = np.asarray(want, np.float64).ravel()
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-30))


def logit_error(got, want) -> float:
    """The largest, over the compared positions, of the distance between
    the program's logits and the reference's as a share of the spread of
    the reference's logits about their mean. Rows are positions."""
    import numpy as np

    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape or got.ndim != 2:
        raise ValueError(f"logits {got.shape} against {want.shape}")
    spread = np.linalg.norm(want - want.mean(-1, keepdims=True), axis=-1)
    return float(np.max(np.linalg.norm(got - want, axis=-1)
                        / np.maximum(spread, 1e-30)))
