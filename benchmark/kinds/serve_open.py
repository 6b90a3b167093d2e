"""``kind: serve_open`` — independent users: requests sent on the mix's
fixed schedule whether or not earlier ones have finished.

A generator thread sleeps to each request's due time and calls
``generate_async``; completion is stamped in ``add_done_callback``.
Latency runs from the due time, not from when the generator got round
to sending. The first ``lead_in_s`` of the schedule bring the slots to
their steady occupancy and are not scored; scored requests are those
due inside the window after it, and the run goes on until they have
finished or ``drain_limit_s`` has passed (one not finished by then
counts as failed).
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List

from benchmark import serving, traffic


def replay(ctx, inst, reqs, lead_in: float, seconds: float,
           drain_limit: float, on_window=None) -> Dict:
    """Send ``reqs`` at their due times through ``inst`` and wait for
    them. Returns the stamps: ``t_load`` (due time 0 on the clock of
    ``time.perf_counter``), per request ``sent_at`` and ``done_at`` (0.0
    = not finished by the drain limit, NaN = failed), the errors, and
    the scheduler's ``stats()`` at the window's two ends and after the
    drain. ``on_window(t0)`` runs in this thread once the window has
    opened (the traced run profiles there)."""
    vocab = int(ctx.config["vocab_size"])
    prompts = [traffic.token_ids(ctx.seed, i, r.prompt_len, vocab)
               for i, r in enumerate(reqs)]
    sent_at: List[float] = [0.0] * len(reqs)
    done_at: List[float] = [0.0] * len(reqs)
    errors: List[BaseException] = []
    lock = threading.Lock()
    left = threading.Semaphore(0)

    def stamp(i: int, fut) -> None:
        done_at[i] = time.perf_counter()
        if fut.exception() is not None:
            with lock:
                errors.append(fut.exception())
            done_at[i] = float("nan")
        left.release()

    t_load = time.perf_counter() + 0.05

    def generate() -> None:
        for i, r in enumerate(reqs):
            wait = t_load + r.due_s - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            sent_at[i] = time.perf_counter()
            try:
                fut = inst.generate_async(prompts[i], r.answer_len,
                                          temperature=0.0)
            except Exception as e:  # noqa: BLE001 — shed at admission
                with lock:
                    errors.append(e)
                done_at[i] = float("nan")
                left.release()
                continue
            fut.add_done_callback(lambda f, i=i: stamp(i, f))

    gen = threading.Thread(target=generate, name="bench-generator")
    gen.start()
    # the window opens when the lead-in ends
    time.sleep(max(0.0, t_load + lead_in - time.perf_counter()))
    s0 = inst.stats()
    t0 = t_load + lead_in
    if on_window is not None:
        on_window(t0)
    time.sleep(max(0.0, t0 + seconds - time.perf_counter()))
    s1 = inst.stats()
    gen.join(timeout=30)
    if gen.is_alive():
        raise RuntimeError("the generator did not reach the schedule's end")
    deadline = time.perf_counter() + drain_limit
    got = 0
    while got < len(reqs):
        if not left.acquire(timeout=max(0.0, deadline - time.perf_counter())):
            break
        got += 1
    return {"t_load": t_load, "t0": t0, "t1": time.perf_counter(),
            "sent_at": sent_at, "done_at": done_at, "errors": errors,
            "stats0": s0, "stats1": s1, "stats2": inst.stats()}


def run(ctx) -> Dict:
    mix = ctx.mix
    lead_in = float(mix["lead_in_s"])
    reqs = [r for r in traffic.schedule(mix)
            if r.due_s < lead_in + ctx.seconds]
    scored = [i for i, r in enumerate(reqs) if r.due_s >= lead_in]
    if not scored:
        raise RuntimeError("no request is due inside the window")
    ff, inst, weights, buckets = serving.build(ctx, reqs)
    serving.warm_up(ctx, inst, buckets)
    serving.compare_paged(ctx, inst, weights, ctx.checks)
    del weights
    before = serving.counters()

    def on_window(t0: float) -> None:
        ctx.window_opens(t0)
        if ctx.profiler.enabled:
            ctx.profiler.start()
            with ctx.span("window"):
                time.sleep(min(ctx.trace_seconds, ctx.seconds))
            ctx.profiler.stop()

    st = replay(ctx, inst, reqs, lead_in, ctx.seconds,
                float(mix["drain_limit_s"]), on_window)
    ctx.window_closed(st["t1"])
    t_load, done_at, sent_at = st["t_load"], st["done_at"], st["sent_at"]
    unfinished = [i for i in scored if done_at[i] == 0.0]
    failed = sum(1 for i in scored
                 if done_at[i] == 0.0 or done_at[i] != done_at[i])
    serving.finish_checks(ctx, inst, before, ctx.checks)
    ctx.checks.equal("serve.scored_requests_failed", failed, 0)
    ok = [i for i in scored if done_at[i] > 0.0]
    latency = [done_at[i] - (t_load + reqs[i].due_s) for i in ok]
    per_token = [lat / reqs[i].answer_len for lat, i in zip(latency, ok)]
    lag = [sent_at[i] - (t_load + reqs[i].due_s) for i in scored]
    ctx.facts.update(
        stats0=st["stats0"], stats1=st["stats1"], stats2=st["stats2"],
        window_s=ctx.seconds, requests=len(reqs), scored=len(scored),
        unfinished=len(unfinished),
        errors=[repr(e) for e in st["errors"][:5]],
        latency_s=latency, per_token_s=per_token, generator_lag_s=lag,
        drain_s=st["t1"] - (st["t0"] + ctx.seconds))
    # in the order due: a stall shows as a run of late requests
    print("[bench] latency_ms of the scored requests, in the order due: "
          + " ".join(str(int(1e3 * x)) for x in latency), flush=True)
    e2e = {}
    if ok:
        e2e = {"request_p95_ms": 1e3 * traffic.percentile(latency, 95),
               "per_token_p95_ms": 1e3 * traffic.percentile(per_token, 95)}
    return {"attempted": len(scored), "failed": failed, "end_to_end": e2e,
            "abandon_threads": bool(unfinished)}
