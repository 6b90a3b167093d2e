"""What the readers of a scope's share of a peak have in common: the
least time the work under some sub-scopes of the ``MULTIHEAD_ATTENTION``
ops of a program could take, which the cell's family says
(``benchmark/families/<family>.py``, over its ``counts*.py`` and the
window's counters), over the device time the owner table
(``benchmark/owners.py``) finds under them, in %. Nothing where the
profile holds no such scope, the window no such counters or the family
does not say; the work is named by its scope, not by what implements it.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

from benchmark import owners


def attention_share(run: Dict, program: str, subs: Tuple[str, ...],
                    asks: str) -> Optional[float]:
    if run["trace"] is None or run["peaks"] is None:
        return None
    ask = getattr(run["family"], asks, None)
    device_ms = owners.device_ms(run, program,
                                 kinds=("MULTIHEAD_ATTENTION",), subs=subs)
    least_s = ask(run) if ask else None
    if not device_ms or least_s is None:
        return None
    return 100.0 * 1e3 * least_s / device_ms
