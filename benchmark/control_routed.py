"""The control of the two-part comparison (``benchmark/routed.py``) and
the readings its limits are set from: ``control.py`` for a cell of kind
``serve_closed_routed``.

    python3 benchmark/control_routed.py --workload <cell> --seeds <n> [--first-seed <s>]

For each seed, in one process and with one model build (the weights are
made anew from each seed, the old ones dropped first: the chip holds one
copy; nothing is timed), it reads the comparison's three numbers —
``score_margin``, ``differing_share``, ``logit_error`` — twice:

* ``sound``: the timed programs' outputs against the reference, as every
  benchmark run compares them;
* ``control``: the reference itself, free-running in float8 e4m3 — the
  nearest precision below the bfloat16 the configuration states — put in
  the program's place. The comparison has to refuse it, by one of its
  limits at least.

``bfloat16`` readings (the reference in the program's own precision) are
printed beside them: what part of ``sound`` is rounding. So are
``float8_scaled`` readings (each operand scaled into e4m3's range before
it is rounded, as a float8 deployment does): the control's unscaled
weights lie in e4m3's subnormals, and this says how near a realistic
lower precision comes to the limits (``refused_by`` names the limits of
the configuration that refuse it). A limit goes at
the geometric mean of the largest ``sound`` and the smallest ``control``
reading (``separation`` prints both and their ratio).
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
NUMBERS = ("score_margin", "differing_share", "logit_error")


def readings(layout, workload: str, seeds: List[int], devices) -> List[Dict]:
    import time

    import jax

    from benchmark import routed, serving, traffic
    from benchmark.run import Ctx

    cell = layout.cell(workload)
    ctx = Ctx(layout, cell, seeds[0], 0.0, False, devices,
              time.perf_counter())
    reqs = traffic.schedule(dict(ctx.mix, kind="serve_closed"))
    ff, inst, weights, _ = serving.build(ctx, reqs)
    cm = ff.compiled
    out = []
    for i, seed in enumerate(seeds):
        ctx.seed = int(seed)
        if i:
            # one copy on the chip: the old weights go before the new come
            cm.params = None
            inst.decoder.invalidate_params_cache()
            del weights
            weights = ctx.reference.init_weights(ctx.config, seed)
            cm.params = jax.tree_util.tree_map(
                jax.device_put, ctx.family.to_program(weights, ctx.config),
                cm.param_shardings)
            cm.bump_params_version()
        rec: Dict = {"seed": int(seed)}
        rows, toks, ids = routed.program_outputs(ctx, inst)
        n = routed.compare(ctx, weights, rows, toks, ids)
        rec["sound"] = {k: n[k] for k in NUMBERS}
        for name in (CONTROL_PRECISION, "bfloat16", "float8_scaled"):
            got, got_ids = routed.outputs_of_reference(ctx, weights, toks,
                                                       len(rows), name)
            n = routed.compare(ctx, weights, got, toks, got_ids)
            rec[name] = {k: n[k] for k in NUMBERS}
        rec["control"] = rec.pop(CONTROL_PRECISION)
        print("[control] " + json.dumps(rec), flush=True)
        out.append(rec)
    inst.stop()
    return out


def separation(recs: List[Dict]) -> Dict[str, Dict[str, float]]:
    """Per number: the sound runs' largest, the control's smallest, their
    ratio and their geometric mean (where a limit goes)."""
    out = {}
    for key in NUMBERS:
        hi = max(r["sound"][key] for r in recs)
        lo = min(r["control"][key] for r in recs)
        out[key] = {"sound_max": hi, "control_min": lo,
                    "ratio": lo / hi if hi > 0 else float("inf"),
                    "geometric_mean": (hi * lo) ** 0.5}
    return out


def scaled_against_limits(recs: List[Dict], config: Dict) -> Dict:
    """The ``float8_scaled`` readings' smallest of each number, and the
    configuration's limits that refuse it on every seed read."""
    limits = {"score_margin": config["routing_check"]["score_margin"],
              "differing_share": config["routing_check"]["differing_share"],
              "logit_error": config["limits"]["serve_logit_rel"]}
    low = {k: min(r["float8_scaled"][k] for r in recs) for k in NUMBERS}
    return {"min": low, "limits": limits,
            "refused_by": [k for k in NUMBERS if low[k] > limits[k]]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=8)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 1000)
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
    recs = readings(layout, args.workload, seeds, devices)
    sep = separation(recs)
    print("[control] separation " + json.dumps(sep), flush=True)
    print("[control] float8_scaled " + json.dumps(scaled_against_limits(
        recs, layout.cell(args.workload)["config"])), flush=True)
    refused = [k for k, v in sep.items() if v["ratio"] > 1.0]
    if not refused:
        print("[control] no number separates the control from the sound "
              "program", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
