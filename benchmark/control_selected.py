"""The readings the long-documents cell's limits are set from, as
``benchmark/control_hybrid.py`` reads the hybrid cell's: per seed the
program against the reference (``sound``: the three numbers of
``benchmark/selected.py``), the reference in float8 e4m3 in the program's
place (``control``, which the comparison has to refuse by one of its
limits) and in bfloat16; and, beside them, the KV calibration's own
reading (``kv_divergence``), which the mix's ``kv_divergence_budget`` is
set from.

    python3 benchmark/control_selected.py --workload <cell> --seeds <n> [--first-seed <s>] [--no-bfloat16]

One process and one model build for all seeds; the weights of the seed
before are let go before the next are made, and the calibration is run
again on each seed's weights with no budget (``control_hybrid.py`` says
why). A reference forward over the cell's 17,000 positions takes the
better part of a minute on the chip and a seed takes five of them, so a
call reads few seeds.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

NO_BUDGET = 1e9
NUMBERS = ("logit_error", "differing_share", "score_margin")


def readings(layout, workload: str, seeds: List[int], devices,
             precisions=("float8", "bfloat16")) -> List[Dict]:
    import jax

    from benchmark import selected
    from benchmark.run import Ctx

    cell = layout.cell(workload)
    cell["mix"] = dict(cell["mix"], kv_divergence_budget=NO_BUDGET)
    ctx = Ctx(layout, cell, seeds[0], 0.0, False, devices,
              time.perf_counter())
    ff, inst, weights = selected.build(ctx)
    del weights                            # each seed makes its own
    cm, dec = ff.compiled, inst.decoder
    out = []
    for seed in seeds:
        ctx.seed = int(seed)
        cm.params = {}                     # one set of weights at a time
        dec.invalidate_params_cache()
        weights = ctx.reference.init_weights(ctx.config, seed)
        cm.params = jax.tree_util.tree_map(
            jax.device_put, ctx.family.to_program(weights, ctx.config),
            cm.param_shardings)
        cm.bump_params_version()
        dec._calibrate_kv_quant(NO_BUDGET)
        rows, toks, ids = selected.program_outputs(ctx, inst)
        rec: Dict = {"seed": int(seed), "kv_divergence": dec.kv_divergence}

        def numbers(got_rows, got_ids):
            n = selected.compare(ctx, weights, got_rows, toks, got_ids)
            return {k: n[k] for k in NUMBERS}

        rec["sound"] = numbers(rows, ids)
        for name in precisions:
            rec["control" if name == "float8" else name] = numbers(
                *selected.outputs_of_reference(ctx, weights, toks, len(rows),
                                               name))
        print("[control] " + json.dumps(rec), flush=True)
        out.append(rec)
        del weights
    inst.stop()
    return out


def refused(rec: Dict, config: Dict) -> bool:
    """Whether the comparison's limits refuse the record's control."""
    c, sc = rec["control"], config["selection_check"]
    return (c["logit_error"] > config["limits"]["serve_logit_rel"]
            or c["differing_share"] > sc["differing_share"]
            or c["score_margin"] > sc["score_margin"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 1000)
    ap.add_argument("--no-bfloat16", action="store_true")
    args = ap.parse_args(argv)

    from benchmark import control, device
    from benchmark.spec import Layout

    layout = Layout(ROOT)
    try:
        devices = device.require_tpu(
            int(layout.cell(args.workload)["workload"]["chips"]))
    except device.NoAccelerator as e:
        print(f"[control] {e}", file=sys.stderr)
        return 2
    device.place_compile_cache(ROOT)
    recs = readings(layout, args.workload,
                    [args.first_seed + 7919 * i for i in range(args.seeds)],
                    devices, ("float8",) if args.no_bfloat16
                    else ("float8", "bfloat16"))
    sep = control.separation(recs)
    sep["kv_divergence"] = {"smallest": min(r["kv_divergence"] for r in recs),
                            "largest": max(r["kv_divergence"] for r in recs)}
    print("[control] separation " + json.dumps(sep), flush=True)
    sys.stdout.flush()
    # the scheduler's thread may still hold the device
    os._exit(0 if max(v["ratio"] for k, v in sep.items()
                      if k in NUMBERS) >= 3.0 else 1)


if __name__ == "__main__":
    sys.exit(main())
