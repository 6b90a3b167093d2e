"""The controls of the four-part comparison (``benchmark/selected_states.py``)
for a GLM cell, and the readings its limits are set from.

    python3 benchmark/control_glm.py --workload <cell> --seeds <n> [--first-seed <s>]

For each seed, in one process and with one model build (the weights are
made anew from each seed, the old ones dropped first: the chip holds one
copy; nothing is timed), it reads the comparison's seven numbers
(``selected_states.limits``) on the mix's FIRST check item (the long
prompt, whose queries select) of ``sound``, the timed programs' outputs,
their picks and the state rows they left in the pool, as every benchmark
run compares them, and of the reference itself, free-running and changed,
in the program's place:

* ``weights_float8``: every product's operands read as scaled float8 e4m3,
  the states float32. REFUSED by the routing, the selection AND the
  logits, on every seed;
* ``state_bfloat16``: bfloat16 products, every KDA state rounded to
  bfloat16 after every token. REFUSED by the state rows on every seed;
* ``half_budget``: bfloat16 products, the indexer taking half the pools
  that are due (256 where 512 are). REFUSED by the selection on every
  seed;
* ``bfloat16``: the reference in the program's own precision. PASSED on
  every seed;
* ``sinkhorn_3``: bfloat16 products, 3 Sinkhorn rounds in the stream mixes
  where 20 are due. READ, and held to no verdict: ``limits_why`` says
  whether any limit refuses it and by how much.

A limit goes between the largest ``sound`` reading and the smallest
reading of the arm it is there to refuse (``separation`` prints both and
their ratio).
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

ROUTING = ("score_margin", "differing_share")
SELECTION = ("selection_score_margin", "selection_differing_share")
STATES = ("state_error", "state_coarse_share")


def arms(config: Dict) -> Dict[str, tuple]:
    """name: (what the reference is changed by, the parts that EACH have
    to refuse it on every seed; () passes; None: read only)."""
    half = (int(config["index_topk"]) // int(config["index_kpool"])) // 2 - 1
    return {
        "weights_float8": (dict(precision="float8"),
                           (ROUTING, SELECTION, ("logit_error",))),
        "state_bfloat16": (dict(precision="bfloat16",
                                state_dtype="bfloat16"), (STATES,)),
        "half_budget": (dict(precision="bfloat16", picks=half),
                        (SELECTION,)),
        "bfloat16": (dict(precision="bfloat16"), ()),
        "sinkhorn_3": (dict(precision="bfloat16", sinkhorn_iters=3), None)}


def readings(layout, workload: str, seeds: List[int], devices) -> List[Dict]:
    import time

    import jax

    from benchmark import selected, selected_states
    from benchmark.run import Ctx

    cell = layout.cell(workload)
    ctx = Ctx(layout, cell, seeds[0], 0.0, False, devices,
              time.perf_counter())
    numbers = tuple(selected_states.limits(ctx.config))
    item = ctx.mix["check"][0]
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
        sound_out = selected_states.program_outputs(ctx, inst, item, 0)
        toks = sound_out[1]
        got = {"sound": sound_out}
        for arm, (changed, _) in arms(ctx.config).items():
            got[arm] = selected_states.outputs_of_reference(
                ctx, weights, toks, len(sound_out[0]), **changed)
        free = selected_states.free_running(ctx, weights, toks)
        rec: Dict = {"seed": int(seed)}
        for arm, outputs in got.items():
            n = selected_states.compare(ctx, weights, *outputs, free=free)
            rec[arm] = {k: n[k] for k in numbers}
        print("[control] " + json.dumps(rec), flush=True)
        out.append(rec)
    inst.stop()
    return out


def separation(recs: List[Dict], config: Dict) -> Dict:
    """Per refused arm and number of the parts that refuse it: the sound
    runs' largest, the arm's smallest, their ratio and their geometric
    mean."""
    out: Dict = {}
    for arm, (_, parts) in arms(config).items():
        keys = ([k for part in parts for k in part] if parts
                else list(recs[0]["sound"]) if parts is None else [])
        out[arm] = {}
        for key in keys:
            hi = max(r["sound"][key] for r in recs)
            lo = min(r[arm][key] for r in recs)
            out[arm][key] = {"sound_max": hi, "control_min": lo,
                             "ratio": lo / hi if hi > 0 else float("inf"),
                             "geometric_mean": (hi * lo) ** 0.5}
    return out


def verdicts(recs: List[Dict], config: Dict) -> Dict[str, List]:
    """Per arm, seed by seed, the limits of the configuration that refuse
    it (empty: it passes)."""
    from benchmark import selected_states

    limits = selected_states.limits(config)
    return {arm: [[k for k, limit in limits.items()
                   if not r[arm][k] <= limit] for r in recs]
            for arm in ("sound",) + tuple(arms(config))}


def sound(verdict: Dict[str, List], config: Dict) -> bool:
    """The limits pass the program and the stated precision on every seed,
    and every part named for a refused arm refuses it on every seed."""
    held = dict(arms(config), sound=(None, ()))
    for arm, seeds in verdict.items():
        parts = held[arm][1]
        if parts is None:
            continue
        for refused_by in seeds:
            if not parts and refused_by:
                return False
            if any(not set(part) & set(refused_by) for part in parts):
                return False
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 1000)
    args = ap.parse_args(argv)

    from benchmark import device
    from benchmark.spec import Layout

    layout = Layout(ROOT)
    cell = layout.cell(args.workload)
    try:
        devices = device.require_tpu(int(cell["workload"]["chips"]))
    except device.NoAccelerator as e:
        print(f"[control] {e}", file=sys.stderr)
        return 2
    device.place_compile_cache(ROOT)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    recs = readings(layout, args.workload, seeds, devices)
    config = cell["config"]
    print("[control] separation " + json.dumps(separation(recs, config)),
          flush=True)
    v = verdicts(recs, config)
    print("[control] refused_by " + json.dumps(v), flush=True)
    if not sound(v, config):
        print("[control] the limits pass a control or refuse the program "
              "or its stated precision", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
