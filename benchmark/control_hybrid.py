"""The readings the hybrid cell's limits are set from, as
``benchmark/control.py`` reads the GPT-2 cells': per seed the program
against the reference (``sound``), the reference in float8 e4m3 in the
program's place (``control``, which the comparison has to refuse) and in
bfloat16; and, beside them, the KV calibration's own reading
(``kv_divergence``: the paged programs against the program's dense
forward), which the mix's ``kv_divergence_budget`` is set from.

    python3 benchmark/control_hybrid.py --workload <cell> --seeds <n> [--first-seed <s>]

One process and one model build for all seeds. It differs from
``control.py`` in two things a model of 8 GB forces: the weights of the
seed before are let go before the next are made (two sets do not fit the
chip), and the calibration is run again on each seed's weights with no
budget, so that it reads and never falls back (a second pool does not fit
either).
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


def readings(layout, workload: str, seeds: List[int], devices) -> List[Dict]:
    import jax

    from benchmark import check, control, serving, traffic
    from benchmark.run import Ctx

    cell = layout.cell(workload)
    cell["mix"] = dict(cell["mix"], kv_divergence_budget=NO_BUDGET)
    ctx = Ctx(layout, cell, seeds[0], 0.0, False, devices,
              time.perf_counter())
    ff, inst, _, _ = serving.build(ctx, traffic.schedule(ctx.mix))
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
        rows, toks = serving.program_rows(ctx, inst)
        ref = serving.reference_rows(ctx, weights, toks, len(rows),
                                     "float32")
        rec: Dict = {"seed": int(seed), "kv_divergence": dec.kv_divergence,
                     "sound": {"logit_rel": check.logit_error(rows, ref)}}
        for name, key in ((control.CONTROL_PRECISION, "control"),
                          ("bfloat16", "bfloat16")):
            got = serving.reference_rows(ctx, weights, toks, len(rows), name)
            rec[key] = {"logit_rel": check.logit_error(got, ref)}
        print("[control] " + json.dumps(rec), flush=True)
        out.append(rec)
        del weights
    inst.stop()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=8)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 1000)
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
                    devices)
    sep = control.separation(recs)
    sep["kv_divergence"] = {"smallest": min(r["kv_divergence"] for r in recs),
                            "largest": max(r["kv_divergence"] for r in recs)}
    print("[control] separation " + json.dumps(sep), flush=True)
    sys.stdout.flush()
    # the scheduler's thread may still hold the device
    os._exit(0 if sep["logit_rel"]["ratio"] >= 3.0 else 1)


if __name__ == "__main__":
    sys.exit(main())
