"""Find a serving cell's knee, once, when the cell is defined.

    python3 benchmark/sweep.py --workload <open-loop cell> [--shares 0.5,0.7,0.85,1.0,1.15]
                               [--slots N] [--seconds 30] [--service-s S]

One model build; for each share the cell's own mix is replayed at
``share * slots / service_s`` requests a second (the same distributions
and ``trace_seed``, no burst), and a row is printed: the rate, the tokens per
second completed in the window, the scheduler's queue depth sampled
through the window (mean of the first and of the second half — a queue
that grows says the rate is past the knee), the latency tails of the
requests due in the window and the mean wall time of a decode step.
``service_s`` defaults to a first guess from the mix (mean answer times
a step time measured in a short closed burst at the start).
The knee is the highest rate whose queue does not grow; the mix's
``rate_rps`` is four fifths of it. Nothing here is part of a benchmark
run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--shares", default="0.5,0.7,0.85,1.0,1.15")
    ap.add_argument("--slots", type=int, default=None)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--service-s", type=float, default=None)
    ap.add_argument("--seed", type=int, default=2 ** 31 + 24)
    args = ap.parse_args(argv)

    from benchmark import device, serving, traffic
    from benchmark.run import Ctx
    from benchmark.spec import Layout

    layout = Layout(ROOT)
    cell = layout.cell(args.workload)
    devices = device.require_tpu(int(cell["workload"]["chips"]))
    device.place_compile_cache(ROOT)
    mix = cell["mix"]
    if args.slots:
        mix["decode_slots"] = args.slots
    ctx = Ctx(layout, cell, args.seed, args.seconds, False, devices,
              time.perf_counter())
    open_kind = layout.kind("serve_open")
    base = traffic.schedule(mix)
    ff, inst, weights, buckets = serving.build(ctx, base)
    serving.warm_up(ctx, inst, buckets)
    del weights
    slots = int(mix["decode_slots"])
    # a step's wall time with every slot busy: a short closed burst
    s0 = inst.stats()
    t0 = time.perf_counter()
    mid = sorted(base, key=lambda r: r.prompt_len)[len(base) // 2]
    futs = [inst.generate_async(
        traffic.token_ids(args.seed, i, mid.prompt_len,
                          int(ctx.config["vocab_size"])),
        min(48, int(mix["max_length"]) - mid.prompt_len), temperature=0.0)
        for i in range(slots)]
    for f in futs:
        f.result(timeout=600)
    burst_s = time.perf_counter() - t0
    s1 = inst.stats()
    step_s = burst_s / max(1, s1["decode_steps"] - s0["decode_steps"])
    mean_answer = sum(r.answer_len for r in base) / len(base)
    service_s = args.service_s or mean_answer * step_s
    print(f"[sweep] slots={slots} full-batch step {1e3 * step_s:.1f} ms "
          f"(burst incl. {slots} prefills), mean answer {mean_answer:.1f} "
          f"tokens, service {service_s:.2f} s, capacity guess "
          f"{slots / service_s:.3f} rps", flush=True)
    lead_in = float(mix["lead_in_s"])
    rows = []
    for share in [float(x) for x in args.shares.split(",")]:
        rate = share * slots / service_s
        # the cell's own distributions at this rate, without its burst
        reqs = traffic.schedule(dict(
            mix, rate_rps=rate, burst=None,
            horizon_s=lead_in + args.seconds + 2.0))
        reqs = [r for r in reqs if r.due_s < lead_in + args.seconds]
        depth, stop = [], threading.Event()

        def sample():
            while not stop.wait(0.25):
                st = inst.stats()
                depth.append((time.perf_counter(), st["queued"],
                              st["active"]))

        sampler = threading.Thread(target=sample)
        sa = inst.stats()
        sampler.start()
        st = open_kind.replay(ctx, inst, reqs, lead_in, args.seconds,
                              float(mix["drain_limit_s"]))
        stop.set()
        sampler.join()
        t_load, t_w0 = st["t_load"], st["t0"]
        inwin = [(t, q, a) for t, q, a in depth
                 if t_w0 <= t < t_w0 + args.seconds]
        half = t_w0 + args.seconds / 2
        q1 = [q for t, q, _ in inwin if t < half]
        q2 = [q for t, q, _ in inwin if t >= half]
        act = [a for _, _, a in inwin]
        scored = [i for i, r in enumerate(reqs) if r.due_s >= lead_in
                  and st["done_at"][i] > 0]
        lat = [st["done_at"][i] - (t_load + reqs[i].due_s) for i in scored]
        per_tok = [x / reqs[i].answer_len for x, i in zip(lat, scored)]
        w0, w1, w2 = st["stats0"], st["stats1"], st["stats2"]
        steps = w1["decode_steps"] - w0["decode_steps"]
        row = {
            "share": share, "rate_rps": rate, "sent": len(reqs),
            "scored": len(scored),
            "tokens_per_s": (w1["tokens"] - w0["tokens"]) / args.seconds,
            "queue_first_half": sum(q1) / max(1, len(q1)),
            "queue_second_half": sum(q2) / max(1, len(q2)),
            "active_mean": sum(act) / max(1, len(act)),
            "step_wall_ms": 1e3 * args.seconds / max(1, steps),
            "request_p50_ms": 1e3 * traffic.percentile(lat or [0], 50),
            "request_p95_ms": 1e3 * traffic.percentile(lat or [0], 95),
            "per_token_p95_ms": 1e3 * traffic.percentile(per_tok or [0], 95),
            "queue_wait_p50_ms_cum": 1e3 * (w2["phases"]["queue_wait"]
                                            or {}).get("p50", 0.0),
            "drain_s": st["t1"] - (t_w0 + args.seconds),
            "errors": len(st["errors"]),
        }
        rows.append(row)
        print("[sweep] " + json.dumps(row), flush=True)
    out = os.path.join(ROOT, "chiprun_out", "pr24")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"sweep_{args.workload}_{slots}.json"),
              "w") as f:
        json.dump({"slots": slots, "step_s": step_s,
                   "service_s": service_s, "rows": rows}, f, indent=1)
    sys.stdout.flush()
    os._exit(0)


if __name__ == "__main__":
    sys.exit(main())
