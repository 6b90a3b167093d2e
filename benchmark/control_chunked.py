"""The control of the plain-logits comparison for a cell of kind
``serve_closed_plain_chunked`` (``benchmark/plain_chunked.py``) and the
readings its limit is set from: ``control_hybrid.py`` with the chunked
kind's build (``control_hybrid.py`` builds through ``serving.build``,
which cannot carry chunks) and every item of the mix's ``check`` list.

    python3 benchmark/control_chunked.py --workload <cell> --seeds <n> [--first-seed <s>]

For each seed and check item, in one process and with one model build
(the weights are made anew from each seed, the old ones dropped first:
the chip holds one copy; nothing is timed), it reads ``logit_error`` for
``sound`` (the timed programs' outputs against the float32 reference, as
every benchmark run compares them), ``control`` (the reference itself in
scaled float8 e4m3, each operand scaled to the format's range as a float8
deployment computes: the nearest precision below the bfloat16 the
configuration states, in the program's place; the comparison has to
refuse it), ``float8`` (operands rounded as they are: further off) and
``bfloat16`` (what part of ``sound`` is rounding). Every ``sound`` and
``control`` reading then goes through the harness's comparison
(``check.Checks.at_most`` under the configuration's limit): the exit code
is 0 only if it passed the program and refused the control on every seed
and item.
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

CONTROL_PRECISION = "float8_scaled"


def readings(layout, workload: str, seeds: List[int], devices) -> List[Dict]:
    import jax

    from benchmark import check, plain_chunked, selected
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
        for item, (rows, toks) in zip(
                ctx.mix["check"], plain_chunked.program_outputs(ctx, inst)):
            ref = plain_chunked.outputs_of_reference(
                ctx, weights, toks, len(rows), "float32")
            rec: Dict = {"seed": int(seed),
                         "prompt_len": int(item["prompt_len"]),
                         "reference_logit_std": float(ref.std()),
                         "sound": check.logit_error(rows, ref)}
            for name in (CONTROL_PRECISION, "float8", "bfloat16"):
                rec[name] = check.logit_error(
                    plain_chunked.outputs_of_reference(
                        ctx, weights, toks, len(rows), name), ref)
            rec["control"] = rec.pop(CONTROL_PRECISION)
            print("[control] " + json.dumps(rec), flush=True)
            out.append(rec)
    inst.stop()
    return out


def separation(recs: List[Dict]) -> Dict[str, float]:
    """The sound runs' largest, the control's smallest, their ratio and
    their geometric mean (where the limit goes)."""
    hi = max(r["sound"] for r in recs)
    lo = min(r["control"] for r in recs)
    return {"sound_max": hi, "control_min": lo,
            "ratio": lo / hi if hi > 0 else float("inf"),
            "geometric_mean": (hi * lo) ** 0.5,
            "bfloat16_max": max(r["bfloat16"] for r in recs),
            "float8_min": min(r["float8"] for r in recs)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=4)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 1000)
    args = ap.parse_args(argv)

    from benchmark import check, device
    from benchmark.spec import Layout

    layout = Layout(ROOT)
    cell = layout.cell(args.workload)
    try:
        devices = device.require_tpu(int(cell["workload"]["chips"]))
    except device.NoAccelerator as e:
        print(f"[control] {e}", file=sys.stderr)
        return 2
    device.place_compile_cache(ROOT)
    recs = readings(layout, args.workload,
                    [args.first_seed + 7919 * i for i in range(args.seeds)],
                    devices)
    sep = separation(recs)
    # through the harness's own comparison, under the configuration's
    # limit: it has to pass the sound program and refuse the control on
    # every seed and item, and the exit code says whether it did
    limit = cell["config"]["limits"]["serve_logit_rel"]
    sound, control = check.Checks(), check.Checks()
    for r in recs:
        of = f"[{r['prompt_len']}] seed {r['seed']}"
        sound.at_most("serve.paged_logits_vs_reference" + of, r["sound"],
                      limit)
        control.at_most("control.paged_logits_vs_reference" + of,
                        r["control"], limit)
    sep["limit"] = limit
    sep["refused"] = not any(row["ok"] for row in control.rows)
    print("[control] separation " + json.dumps(sep), flush=True)
    sys.stdout.flush()
    # the scheduler's thread may still hold the device
    os._exit(0 if sound.correct and sep["refused"] else 1)


if __name__ == "__main__":
    sys.exit(main())
