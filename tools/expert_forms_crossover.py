"""Where the grouped product over named pairs beats every token through
every held expert: the table behind ``RoutedExperts.expert_form``
(``ops/moe_ops.py``).

    chiprun -- python tools/expert_forms_crossover.py

On the chip only (it exits 2 anywhere else: a CPU timing is no speed).
For each expert layer in ``LAYERS`` (the Nemotron-3-Super share: 128 of
512 experts held, top-22, squared ReLU inside a 1024-wide latent; the
A.X-K1 share: 12 of 192 held, top-8, gated SiLU at 7168) and each count of
rows in ``ROWS`` (a decode step's 128 slots, the prefill buckets) it
times ``RoutedExperts.apply`` both ways, bfloat16 weights and rows, a
uniform random routing: the mean wall time of ``REPEATS`` calls behind
one warm-up, each waited for (a call is milliseconds, the dispatch some
tens of microseconds). It also says what ``expert_form`` chooses there
and the largest difference of the two forms' outputs over their range.
One JSON line a row on stdout, the table again under
``chiprun_out/expert_forms_crossover.json``. Nothing reads that file:
``RIDGE_ROWS`` and ``CAPACITY_SHARE`` are edited by hand from it, and
PERF.md section 6 keeps the table they were edited from.
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

ROWS = (128, 256, 512, 768, 1024)
REPEATS = 10
LAYERS = {
    "nemotron3-super-ep4": (4096, dict(
        n_routed=512, experts_per_token=22, width=2688,
        experts_held=(0, 128), latent=1024, activation="relu2",
        selection_bias=True, routed_scale=5.0)),
    "axk1-ep16": (7168, dict(
        n_routed=192, experts_per_token=8, width=2048,
        experts_held=(0, 12), n_group=8, topk_group=4, routed_scale=2.5)),
}


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.default_backend() != "tpu":
        print(f"no table: the backend is {jax.default_backend()!r}",
              file=sys.stderr)
        return 2
    from flexflow_tpu.core.layer import Layer
    from flexflow_tpu.core.parallel_tensor import ParallelTensorShape
    from flexflow_tpu.ffconst import DataType, OpType
    from flexflow_tpu.ops.moe_ops import RoutedExperts

    table = []
    for name, (e, attrs) in LAYERS.items():
        op = RoutedExperts(
            Layer(OpType.ROUTED_EXPERTS, "experts", attrs=attrs),
            [ParallelTensorShape.unpartitioned((1, 8, e),
                                               DataType.BFLOAT16)])
        key = jax.random.key(0)
        weights = {}
        for i, ws in enumerate(op.weight_specs()):
            weights[ws.name] = (0.02 * jax.random.normal(
                jax.random.fold_in(key, i), ws.shape, jnp.float32)
            ).astype(jnp.bfloat16)
        for rows in ROWS:
            x = jax.random.normal(jax.random.fold_in(key, rows), (rows, e),
                                  jnp.float32).astype(jnp.bfloat16)
            ids, gates = jax.jit(op.route)(weights, x)
            v = x if not op.latent else jnp.dot(
                x, weights["latent_down"]).astype(jnp.bfloat16)
            row = {"layer": name, "rows": rows,
                   "rule": op.expert_form(rows),
                   "pairs_held": int(np.sum(
                       (np.asarray(ids) >= op.first)
                       & (np.asarray(ids) < op.first + op.count)))}
            outs = {}
            for form in ("dense", "grouped"):
                fn = jax.jit(getattr(op, f"_apply_{form}"))
                try:
                    outs[form] = jax.block_until_ready(
                        fn(weights, v, ids, gates))
                    t0 = time.perf_counter()
                    for _ in range(REPEATS):
                        jax.block_until_ready(fn(weights, v, ids, gates))
                    row[f"{form}_ms"] = 1e3 * (time.perf_counter()
                                               - t0) / REPEATS
                except Exception as err:  # noqa: BLE001 — e.g. no memory
                    row[f"{form}_ms"] = None
                    row[f"{form}_error"] = str(err).splitlines()[0][:200]
            if len(outs) == 2:
                a, b = (np.asarray(o, np.float32) for o in outs.values())
                row["forms_differ_rel"] = float(
                    np.abs(a - b).max() / max(np.abs(a).max(), 1e-30))
            print(json.dumps(row), flush=True)
            table.append(row)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "expert_forms_crossover.json"), "w") as f:
        json.dump(table, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
