"""``kind: serve_closed_plain_chunked`` — ``serve_closed``'s closed loop
for prompts longer than any prefill bucket, served by a model that
neither routes nor selects: the chunked kind's build and warm-up
(``selected.build``, ``selected.warm_up``: the instance takes the mix's
``prefill_chunk`` and no buckets) and a comparison of plain logits over
every item of the mix's ``check`` LIST (``benchmark/plain_chunked.py``).

``kinds/serve_closed.py`` is not this PR's to edit, so the loop stands
here a fifth time: as :func:`run_with`, which takes the build, the
warm-up and the comparison as ARGUMENTS, so that the ``benchmark`` PR
which PERF.md section 7 asks for can point the four older kinds at it
and delete their copies. ``build(ctx)`` returns ``(ff, inst, weights)``,
``warm_up(ctx, inst)`` compiles every program the window runs,
``compare(ctx, inst, weights, checks)`` decides ``correct``.
"""

from __future__ import annotations

import queue
import time
from typing import Callable, Dict

import jax

from benchmark import plain_chunked, selected, serving, traffic


def run_with(ctx, build: Callable, warm_up: Callable,
             compare: Callable) -> Dict:
    mix, cfg = ctx.mix, ctx.config
    # the schedule is the closed kind's own
    reqs = traffic.schedule(dict(mix, kind="serve_closed"))
    ff, inst, weights = build(ctx)
    warm_up(ctx, inst)
    compare(ctx, inst, weights, ctx.checks)
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
    # no silent fall-back: on the chip the decode step's attention reads
    # the pool in place, by the kernel; off it (the CPU's toy) the jnp
    # gather is the path there is
    path = s1["kv"]["attention_path"].get("decode")
    ctx.checks.equal("serve.attention_path_decode", path,
                     "kernel" if jax.default_backend() == "tpu" else path)
    tokens = s1["tokens"] - s0["tokens"]
    loop0, loop1 = s0["loop"], s1["loop"]
    ctx.facts.update(stats0=s0, stats1=s1, window_s=t1 - t0, tokens=tokens,
                     jobs_finished_in_window=finished - finished0,
                     decode_steps_in_window=(s1["decode_steps"]
                                             - s0["decode_steps"]),
                     prefills_in_window=(s1["prefill_prompts"]
                                         - s0["prefill_prompts"]),
                     chunks_in_window=(loop1["prefill_chunks"]
                                       - loop0["prefill_chunks"]),
                     prompt_tokens_in_window=(loop1["prefill_tokens"]
                                              - loop0["prefill_tokens"]),
                     clients_decoding_at_open=s0["prefill_prompts"],
                     prompt_lens=[reqs[i % len(reqs)].prompt_len
                                  for i in range(sent)])
    # the jobs still in their slots are not waited for
    return {"attempted": sent, "failed": failed,
            "end_to_end": {"serve_tokens_per_s": tokens / (t1 - t0)},
            "abandon_threads": True}


def run(ctx) -> Dict:
    return run_with(ctx, selected.build, selected.warm_up,
                    plain_chunked.compare_paged)
