"""The control of the comparison that decides ``correct``, and the
readings its limits are set from.

    python3 benchmark/control.py --workload <cell> --seeds <n> [--first-seed <s>]

For each seed, in one process and with one model build (the weights are
made anew from each seed; nothing is timed), it reads two numbers per
comparison:

* ``sound``: the program against the reference (float32, ``highest``),
  as every benchmark run compares them;
* ``control``: the reference itself computed in float8 e4m3 — the
  nearest precision below the bfloat16 the configurations state — put
  in the program's place. The comparison has to refuse it.

A limit goes above the largest ``sound`` and below the smallest
``control``, with room on both sides, and only where the second is at
least three times the first (``--require-separation``, the default,
exits 1 otherwise). ``bfloat16`` readings (the reference in the
program's own precision) are printed beside them to show what part of
``sound`` is rounding and what part is the program.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CONTROL_PRECISION = "float8"


def readings(layout, workload: str, seeds: List[int], devices) -> List[Dict]:
    """Per seed: the comparison's numbers for the program (``sound``),
    for the float8 reference in its place (``control``) and for the
    bfloat16 reference in its place."""
    import time

    import jax

    from benchmark import check, serving
    from benchmark.run import Ctx

    cell = layout.cell(workload)
    kind_name = cell["mix"]["kind"]
    ctx = Ctx(layout, cell, seeds[0], 0.0, False, devices,
              time.perf_counter())
    fit = layout.kind("fit") if kind_name == "fit" else None
    out = []
    if fit is not None:
        batch = fit.batch_per_chip(ctx) * len(devices)
        ff, _ = fit.build(ctx, batch)
    else:
        from benchmark import traffic

        ff, inst, _, _ = serving.build(ctx, traffic.schedule(ctx.mix))
    cm = ff.compiled
    for seed in seeds:
        ctx.seed = int(seed)
        weights = ctx.reference.init_weights(ctx.config, seed)
        cm.params = jax.tree_util.tree_map(
            jax.device_put, ctx.family.to_program(weights, ctx.config),
            cm.param_shardings)
        cm.bump_params_version()
        rec: Dict = {"seed": int(seed)}
        if fit is not None:
            tok, pos, lab = fit.seeded_batches(
                seed, int(ctx.config["vocab_size"]), batch,
                int(ctx.mix["seq"]))
            b = (tok, pos, lab)
            loss_r, grads_r = fit.reference_step(ctx, weights, b, "float32")
            grads_p = fit.program_step(ctx, ff, b)
            # the float8 control alone: each precision of the training
            # reference is a minute of compilation
            loss_c, grads_c = fit.reference_step(ctx, weights, b,
                                                 CONTROL_PRECISION)
            n = fit.fit_numbers(loss_c, grads_c, loss_r, grads_r)
            rec[CONTROL_PRECISION] = {k: n[k] for k in (
                "loss_abs", "grad_rel", "grad_rel_worst_leaf")}
            rec["control_by_leaf"] = n["grad_rel_by_leaf"]
            loss_p = fit.program_loss(ff, b)  # donates the weights' buffers
            n = fit.fit_numbers(loss_p, grads_p, loss_r, grads_r)
            rec["sound"] = {k: n[k] for k in ("loss_abs", "grad_rel",
                                              "grad_rel_worst_leaf")}
            rec["sound_by_leaf"] = n["grad_rel_by_leaf"]
        else:
            inst.decoder.invalidate_params_cache()
            rows, toks = serving.program_rows(ctx, inst)
            ref = serving.reference_rows(ctx, weights, toks, len(rows),
                                         "float32")
            rec["sound"] = {"logit_rel": check.logit_error(rows, ref)}
            for name in (CONTROL_PRECISION, "bfloat16"):
                got = serving.reference_rows(ctx, weights, toks, len(rows),
                                             name)
                rec[name] = {"logit_rel": check.logit_error(got, ref)}
        rec["control"] = rec.pop(CONTROL_PRECISION)
        print("[control] " + json.dumps(rec), flush=True)
        out.append(rec)
    if fit is None:
        inst.stop()
    return out


def separation(recs: List[Dict]) -> Dict[str, Dict[str, float]]:
    """Per number: the sound runs' largest, the control's smallest, and
    their ratio."""
    out = {}
    for key in recs[0]["sound"]:
        if not isinstance(recs[0]["sound"][key], (int, float)):
            continue
        hi = max(r["sound"][key] for r in recs)
        lo = min(r["control"][key] for r in recs)
        out[key] = {"sound_max": hi, "control_min": lo,
                    "ratio": lo / hi if hi > 0 else float("inf")}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 1000)
    ap.add_argument("--no-require-separation", action="store_true")
    args = ap.parse_args(argv)

    from benchmark import device
    from benchmark.spec import Layout

    layout = Layout(ROOT)
    chips = int(layout.cell(args.workload)["workload"]["chips"])
    try:
        devices = device.require_tpu(chips)
    except device.NoAccelerator as e:
        print(f"[control] {e}", file=sys.stderr)
        return 2
    device.place_compile_cache(ROOT)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    sep = separation(readings(layout, args.workload, seeds, devices))
    print("[control] separation " + json.dumps(sep), flush=True)
    bad = [k for k, v in sep.items() if v["ratio"] < 3.0]
    if bad and not args.no_require_separation:
        print(f"[control] no limit will hold for {bad}: the control's "
              "smallest is under three times the sound runs' largest",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
