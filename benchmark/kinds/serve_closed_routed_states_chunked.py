"""``kind: serve_closed_routed_states_chunked`` — the closed loop for a
model that routes over experts, selects the rows its sparse attention
reads, keeps a float32 state a request, and whose prompts are longer than
any prefill bucket: ONE call of ``kinds/serve_closed_plain_chunked.py``
``run_with`` (PR 46 wrote it to take the build, the warm-up and the
comparison as arguments) with the chunked kind's build and warm-up
(``selected.build``, ``selected.warm_up``: the instance takes the mix's
``prefill_chunk`` and no buckets) and the four-part comparison of
``benchmark/selected_states.py``. It copies no loop.
"""

from __future__ import annotations

from typing import Dict

from benchmark import selected, selected_states


def run(ctx) -> Dict:
    return ctx.layout.kind("serve_closed_plain_chunked").run_with(
        ctx, selected.build, selected.warm_up, selected_states.compare_paged)
