"""The controls of the three-part comparison (``benchmark/routed_states.py``)
for a Ling cell, and the readings its limits are set from:
``control_routed.py`` with this family's two lower precisions, each held
to a verdict.

    python3 benchmark/control_ling.py --workload <cell> --seeds <n> [--first-seed <s>]

For each seed, in one process and with one model build (the weights are
made anew from each seed, the old ones dropped first: the chip holds one
copy; nothing is timed), it reads the comparison's five numbers
(``routed_states.limits``) of ``sound``, the timed programs' outputs and
the state rows they left in the pool, as every benchmark run compares
them, and of the reference itself, free-running, in the program's place:

* ``weights_float8``: every product's operands read as float8 e4m3 (each
  scaled into e4m3's range, as a float8 deployment does), the states
  float32: the bfloat16 the configuration states its products in, read
  in the nearest precision below. REFUSED on every seed, or the limits
  are too wide;
* ``state_bfloat16``: bfloat16 products, every KDA state rounded to
  bfloat16 after every token: the float32 the configuration states its
  states in, kept in the nearest precision below. REFUSED on every seed.
  The logits do not see it (it reads UNDER the sound program there, which
  rounds its residual stream); the state rows do;
* ``bfloat16``: the reference in the program's own precision, states
  float32. PASSED on every seed, or the limits refuse what the
  configuration states.

A limit goes between the largest ``sound`` reading and the smallest
reading of the arm it is there to refuse (``separation`` prints, for each
refused arm and number, both and their ratio).
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

# name: (precision of the products, dtype of the KDA states, refused?)
ARMS = {"weights_float8": ("float8", "float32", True),
        "state_bfloat16": ("bfloat16", "bfloat16", True),
        "bfloat16": ("bfloat16", "float32", False)}
REFUSED = tuple(a for a, (_, _, refused) in ARMS.items() if refused)


def outputs_of_reference(ctx, weights, toks, n_rows: int, precision: str,
                         state_dtype: str):
    """``routed.outputs_of_reference`` with the states' dtype chosen and
    the states the last token left beside the logits and the routing."""
    import jax.numpy as jnp
    import numpy as np

    logits, info, states = ctx.reference.forward_with_states(
        weights, jnp.asarray(toks[None, :]), ctx.config, precision,
        state_dtype=state_dtype)
    return (np.asarray(logits)[0, len(toks) - n_rows:],
            [np.asarray(layer["ids"]) for layer in info],
            [np.asarray(s)[0] for s in states])


def readings(layout, workload: str, seeds: List[int], devices) -> List[Dict]:
    import time

    import jax

    from benchmark import routed_states, serving, traffic
    from benchmark.run import Ctx

    cell = layout.cell(workload)
    ctx = Ctx(layout, cell, seeds[0], 0.0, False, devices,
              time.perf_counter())
    numbers = tuple(routed_states.limits(ctx.config))
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
        rows, toks, ids, states = routed_states.program_outputs(ctx, inst)
        got = {"sound": (rows, ids, states)}
        for arm, (precision, state_dtype, _) in ARMS.items():
            got[arm] = outputs_of_reference(ctx, weights, toks, len(rows),
                                            precision, state_dtype)
        rec: Dict = {"seed": int(seed)}
        for arm, (logits, arm_ids, arm_states) in got.items():
            n = routed_states.compare(ctx, weights, logits, toks, arm_ids,
                                      arm_states)
            rec[arm] = {k: n[k] for k in numbers}
        print("[control] " + json.dumps(rec), flush=True)
        out.append(rec)
    inst.stop()
    return out


def separation(recs: List[Dict]) -> Dict[str, Dict[str, Dict[str, float]]]:
    """Per refused arm and number: the sound runs' largest, the arm's
    smallest, their ratio and their geometric mean."""
    out: Dict = {}
    for arm in REFUSED:
        out[arm] = {}
        for key in recs[0]["sound"]:
            hi = max(r["sound"][key] for r in recs)
            lo = min(r[arm][key] for r in recs)
            out[arm][key] = {"sound_max": hi, "control_min": lo,
                             "ratio": lo / hi if hi > 0 else float("inf"),
                             "geometric_mean": (hi * lo) ** 0.5}
    return out


def verdicts(recs: List[Dict], config: Dict) -> Dict[str, List]:
    """Per arm, seed by seed, the limits of the configuration that refuse
    it (empty: it passes)."""
    from benchmark import routed_states

    limits = routed_states.limits(config)
    return {arm: [[k for k, limit in limits.items() if r[arm][k] > limit]
                  for r in recs] for arm in ("sound",) + tuple(ARMS)}


def sound(verdict: Dict[str, List]) -> bool:
    """The limits pass the program and the stated precision on every seed
    and refuse each lower precision on every seed."""
    return all(all(v) if arm in REFUSED else not any(v)
               for arm, v in verdict.items())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=4)
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
    print("[control] separation " + json.dumps(separation(recs)), flush=True)
    v = verdicts(recs, layout.cell(args.workload)["config"])
    print("[control] refused_by " + json.dumps(v), flush=True)
    if not sound(v):
        print("[control] the limits pass a lower precision or refuse the "
              "program or its stated precision", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
