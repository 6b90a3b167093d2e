"""The controls of the two-part comparison for the MiMo cell, and the
readings its limits are set from: ``control_routed_chunked.py``'s run (one
model build, the weights made anew from each seed, every item of the mix's
``check`` list; nothing is timed) with three more controls beside the
float8 one, each the float32 reference itself with ONE thing the layer's
equations state left out, free-running in the program's place:

    python3 benchmark/control_mimo.py --workload <cell> --seeds <n> [--first-seed <s>]

``no_sink`` (the windowed layers' softmax without its learned column),
``no_value_scale`` (``attention_value_scale`` 1), ``whole_rotary``
(``partial_rotary_factor`` 1: the whole head rotated). The comparison has
to refuse each by one of its limits at least, as it has to refuse
``control`` (float8 e4m3, the nearest precision below the bfloat16 the
configuration states); ``bfloat16`` says what part of ``sound`` is
rounding, ``float8_scaled`` what a float8 deployment computes.
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

# what each control leaves out, as the configuration's keys
LEFT_OUT = {
    "no_sink": {"add_swa_attention_sink_bias": False},
    "no_value_scale": {"attention_value_scale": 1.0},
    "whole_rotary": {"partial_rotary_factor": 1.0},
}


def readings(layout, workload: str, seeds: List[int], devices) -> List[Dict]:
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import control_routed, routed, routed_chunked, selected
    from benchmark.run import Ctx

    cell = layout.cell(workload)
    ctx = Ctx(layout, cell, seeds[0], 0.0, False, devices,
              time.perf_counter())
    ff, inst, weights = selected.build(ctx)
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
        for index, item in enumerate(ctx.mix["check"]):
            rec: Dict = {"seed": int(seed),
                         "prompt_len": int(item["prompt_len"])}
            rows, toks, ids = routed_chunked.program_outputs(ctx, inst, item,
                                                             index)
            n = routed_chunked.compare(ctx, weights, rows, toks, ids)
            rec["sound"] = {k: n[k] for k in control_routed.NUMBERS}
            for name in (control_routed.CONTROL_PRECISION, "bfloat16",
                         "float8_scaled"):
                got, got_ids = routed.outputs_of_reference(
                    ctx, weights, toks, len(rows), name)
                n = routed_chunked.compare(ctx, weights, got, toks, got_ids)
                rec[name] = {k: n[k] for k in control_routed.NUMBERS}
            rec["control"] = rec.pop(control_routed.CONTROL_PRECISION)
            for name, change in LEFT_OUT.items():
                logits, info = ctx.reference.forward_with_routing(
                    weights, jnp.asarray(toks[None, :]),
                    dict(ctx.config, **change), "float32")
                n = routed_chunked.compare(
                    ctx, weights, np.asarray(logits)[0, len(toks) - len(rows):],
                    toks, [np.asarray(layer["ids"]) for layer in info])
                rec[name] = {k: n[k] for k in control_routed.NUMBERS}
            print("[control] " + json.dumps(rec), flush=True)
            out.append(rec)
    inst.stop()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=4)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 1000)
    args = ap.parse_args(argv)

    from benchmark import control_routed, device
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
    sep = control_routed.separation(recs)
    print("[control] separation " + json.dumps(sep), flush=True)
    limits = layout.cell(args.workload)["config"]
    lim = {"score_margin": limits["routing_check"]["score_margin"],
           "differing_share": limits["routing_check"]["differing_share"],
           "logit_error": limits["limits"]["serve_logit_rel"]}
    for name in ("control",) + tuple(LEFT_OUT):
        passed = [r["seed"] for r in recs
                  if all(r[name][k] <= lim[k] for k in lim)]
        print(f"[control] {name} within every limit on {len(passed)} of "
              f"{len(recs)} readings", flush=True)
    print("[control] float8_scaled " + json.dumps(
        control_routed.scaled_against_limits(recs, limits)), flush=True)
    if not [k for k, v in sep.items() if v["ratio"] > 1.0]:
        print("[control] no number separates the control from the sound "
              "program", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
