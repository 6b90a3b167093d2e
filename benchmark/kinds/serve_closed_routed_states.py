"""``kind: serve_closed_routed_states`` — ``serve_closed_routed`` for a
model that keeps a float32 recurrent state a request beside its routed
experts: the same build, warm-up, load, window and counters, with the
three-part comparison of ``benchmark/routed_states.py`` (routing, logits
under the program's routing, and the state rows the programs left in the
pool) in the two-part one's place.

``kinds/serve_closed_routed.py`` calls ``routed.compare_paged`` itself
and is not this PR's to edit; the closed loop that takes its build, its
warm-up and its comparison as ARGUMENTS is
``kinds/serve_closed_plain_chunked.py`` ``run_with`` (PR 46 wrote it for
this), so this kind is one call of it and copies nothing:
``serving.build`` over the mix's buckets, ``serving.warm_up`` of those
buckets, ``routed_states.compare_paged``.
"""

from __future__ import annotations

from typing import Dict

from benchmark import routed_states, serving, traffic


def run(ctx) -> Dict:
    buckets = []

    def build(ctx):
        reqs = traffic.schedule(dict(ctx.mix, kind="serve_closed"))
        ff, inst, weights, found = serving.build(ctx, reqs)
        buckets.extend(found)
        return ff, inst, weights

    def warm_up(ctx, inst):
        serving.warm_up(ctx, inst, buckets)

    return ctx.layout.kind("serve_closed_plain_chunked").run_with(
        ctx, build, warm_up, routed_states.compare_paged)
