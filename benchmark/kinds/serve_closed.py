"""``kind: serve_closed`` — offline generation through
``GenerationInstance``: ``clients`` jobs always in flight, each
client's next job sent when its last one completes, from the mix's
fixed list (taken round again when it runs out). After ``lead_in_s``
of that the window opens; tokens are those the scheduler counted
between the window's two ends.
"""

from __future__ import annotations

import queue
import time
from typing import Dict

from benchmark import serving, traffic


def run(ctx) -> Dict:
    mix, cfg = ctx.mix, ctx.config
    reqs = traffic.schedule(mix)
    ff, inst, weights, buckets = serving.build(ctx, reqs)
    serving.warm_up(ctx, inst, buckets)
    serving.compare_paged(ctx, inst, weights, ctx.checks)
    del weights
    vocab = int(cfg["vocab_size"])
    done: "queue.Queue" = queue.Queue()
    sent, finished, failed = 0, 0, 0
    bad_shape = 0

    def send() -> None:
        nonlocal sent
        i = sent % len(reqs)  # the list goes round when it runs out
        r = reqs[i]
        sent += 1
        fut = inst.generate_async(
            traffic.token_ids(ctx.seed, i, r.prompt_len, vocab),
            r.answer_len, temperature=0.0)
        fut.add_done_callback(lambda f, i=i: done.put((i, f)))

    def collect(until: float) -> None:
        """Refill the slots as jobs complete, until ``until``."""
        nonlocal finished, failed, bad_shape
        while True:
            left = until - time.perf_counter()
            if left <= 0:
                return
            try:
                i, fut = done.get(timeout=left)
            except queue.Empty:
                return
            finished += 1
            if fut.exception() is not None:
                failed += 1
            elif fut.result().shape != (reqs[i].prompt_len
                                        + reqs[i].answer_len,):
                bad_shape += 1
            send()

    before = serving.counters()
    with ctx.span("lead_in"):
        for _ in range(int(mix["clients"])):
            send()
        collect(time.perf_counter() + float(mix["lead_in_s"]))
    s0 = inst.stats()
    t0 = ctx.window_opens()
    finished0 = finished
    if ctx.profiler.enabled:
        ctx.profiler.start()
        with ctx.span("window"):
            collect(t0 + min(ctx.trace_seconds, ctx.seconds))
        ctx.profiler.stop()
    collect(t0 + ctx.seconds)
    s1 = inst.stats()
    t1 = time.perf_counter()
    ctx.window_closed(t1)
    serving.finish_checks(ctx, inst, before, ctx.checks)
    ctx.checks.equal("serve.wrong_length_outputs", bad_shape, 0)
    tokens = s1["tokens"] - s0["tokens"]
    def phase_s(s, k):  # seconds the scheduler's clock gave a phase so far
        p = (s.get("phases") or {}).get(k)
        return p["count"] * p["mean"] if p else 0.0

    ctx.facts.update(stats0=s0, stats1=s1, window_s=t1 - t0, tokens=tokens,
                     jobs_finished_in_window=finished - finished0,
                     # where a run's tokens went, beside the result line
                     decode_steps_in_window=(s1["decode_steps"]
                                             - s0["decode_steps"]),
                     prefills_in_window=(s1["prefill_prompts"]
                                         - s0["prefill_prompts"]),
                     prefill_s_in_window=(phase_s(s1, "prefill")
                                          - phase_s(s0, "prefill")),
                     prompt_lens=[reqs[i % len(reqs)].prompt_len
                                  for i in range(sent)])
    # the jobs still in their slots are not waited for: they would decode
    # for up to a whole answer, and nothing of them is measured
    return {"attempted": sent, "failed": failed,
            "end_to_end": {"serve_tokens_per_s": tokens / (t1 - t0)},
            "abandon_threads": True}
