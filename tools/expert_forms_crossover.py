"""The held experts' three forms side by side: the table behind
``RoutedExperts.expert_form`` (``ops/moe_ops.py``) and
``kernels/grouped_experts.py``.

    chiprun -- python tools/expert_forms_crossover.py [layer[:named] ...]

On the chip only (it exits 2 anywhere else: a CPU timing is no speed).
For each expert layer in ``LAYERS`` (the Nemotron-3-Super share: 128 of
512 experts held, top-22, squared ReLU inside a 1024-wide latent; the
A.X-K1 share: 12 of 192 held, top-8, gated SiLU at 7168; the
Trinity-Large share: 32 of 256 held, top-4, gated SiLU at 3072; the ZAYA1
share: all 16 held, top-1, gated SiLU at 2048; all of
them, or those named), each count of its rows (a decode step's 128
slots and the prefill buckets; for the Trinity share the calls under the
ridge, from a head's one row over a step's 32 slots to 240, where the
share of the held experts a call can name, ``named_share``, goes from 2
to 98 %: what places ``NAMED_SHARE_KERNEL``) and two
routings (``uniform``: the op's own over random rows; ``uneven``: experts
drawn by a log-normal popularity, ``UNEVEN_SIGMA``, which loads the
busiest held expert some six times the mean, as the agents cell's random
weights do) it times, bfloat16 weights and rows: every token through
every held expert (``dense``), the jnp grouped form (``grouped``) and the
Pallas kernel (``kernel``; None where ``supported()`` refuses the
shapes), two ways behind one warm-up: ``<form>_ms``, the mean wall time of
``REPEATS`` calls each waited for (PR 40's table: it holds, beside the
device's time, the best part of a millisecond of dispatch and wake-up a
call), and ``<form>_inflight_ms``, ``REPEATS`` calls dispatched one
behind another and waited for once (the device's pace, which is what a
program that holds the call pays: PERF.md section 6, PR 41, has it beside
the traced time; the jnp grouped form's parts, timed once for PR 41's
premise, are there too). It says what ``expert_form`` chooses, the rows
the kernel counted, and the largest difference of the grouped forms'
outputs from the dense one's over its range. One JSON line a row on
stdout, the table again under
``chiprun_out/expert_forms_crossover.json``. Nothing reads that file:
``RIDGE_ROWS``, ``NAMED_SHARE_KERNEL`` and ``CAPACITY_SHARE`` are edited
by hand from it, and PERF.md section 6 keeps the tables they were edited
from.

Behind a layer's sweep over the rows, where ``NAMED_AT`` gives it a
decode step's rows, the sweep over the NAMED count at those rows: routings
made to name an eighth, a quarter, three eighths, a half, three quarters,
seven eighths and all of the held experts (``named_ids``: a row's picks
among the held experts as many as an even routing gives it, the rest
outside them), through the dense form, the kernel and the call that
chooses between them by its count (``counted``:
``RoutedExperts._apply_counted``, with ``counted_kernel`` saying which it
chose and ``limit`` the op's ``kernel_limit``). Where ``kernel_inflight_ms``
crosses ``dense_inflight_ms`` is what ``kernel_limit``
(``NAMED_SHARE_KERNEL`` of the count held) has to stay under, and
``counted_inflight_ms`` beside the form it chose is what the ``cond``
costs (PERF.md section 6, PR 54; under some 0.4 ms a call in flight the
host's dispatch sets the pace, not the device).
``<layer>:named`` runs that sweep alone.
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
ROWS_FEW = (1, 8, 16, 32, 64, 128, 240)
REPEATS = 10
REPEATS_NAMED = 40
NAMED_SHARES = (1 / 8, 1 / 4, 3 / 8, 1 / 2, 3 / 4, 7 / 8, 1)
UNEVEN_SIGMA = 1.0
LAYERS = {   # the model's width, the op's attributes, the rows of a call
    "nemotron3-super-ep4": (4096, dict(
        n_routed=512, experts_per_token=22, width=2688,
        experts_held=(0, 128), latent=1024, activation="relu2",
        selection_bias=True, routed_scale=5.0), ROWS),
    "axk1-ep16": (7168, dict(
        n_routed=192, experts_per_token=8, width=2048,
        experts_held=(0, 12), n_group=8, topk_group=4, routed_scale=2.5),
        ROWS),
    "trinity-large-ep8": (3072, dict(
        n_routed=256, experts_per_token=4, width=3072,
        experts_held=(0, 32), routed_scale=2.448), ROWS_FEW),
    "zaya1-8b-pp2": (2048, dict(
        n_routed=16, experts_per_token=1, width=2048,
        experts_held=(0, 16), scoring="softmax", norm_topk=False,
        selection_bias=True, router="mlp", router_width=256), (48,)),
}
# a decode step's rows in the layer's cell: where the named count is swept
NAMED_AT = {"zaya1-8b-pp2": 48, "nemotron3-super-ep4": 128,
            "axk1-ep16": 128, "trinity-large-ep8": 32}


def uneven_ids(key, rows: int, n_routed: int, k: int):
    """(rows, k) expert ids drawn without replacement by a log-normal
    popularity an expert (Gumbel top-k)."""
    import jax

    pop, noise = jax.random.split(key)
    logits = (UNEVEN_SIGMA * jax.random.normal(pop, (n_routed,))
              + jax.random.gumbel(noise, (rows, n_routed)))
    return jax.lax.top_k(logits, k)[1]


def named_ids(op, rows: int, named: int):
    """(rows, k) expert ids that name exactly the first ``named`` of the
    held experts, none twice in a row: of a row's picks as many as an even
    routing puts among the held experts (``k x count / n_routed``, rounded
    up) go round those, the rest to experts not held (past every expert
    where all are held: they name nothing)."""
    import numpy as np

    held = min(named, -(-op.k * op.count // op.n_routed))
    t, j = np.arange(rows)[:, None], np.arange(op.k)[None, :]
    outside = op.first + op.count if op.first == 0 else 0
    return np.where(j < held, op.first + (t * held + j) % named,
                    outside + j).astype(np.int32)


def main(layers=()) -> int:
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
    from flexflow_tpu.kernels.grouped_experts import (grouped_experts,
                                                      supported)
    from flexflow_tpu.ops.moe_ops import RoutedExperts

    def timed(fn, *args, repeats=REPEATS):
        """(the result, ms a call each waited for, ms a call in flight)."""
        out = jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(repeats):
            jax.block_until_ready(fn(*args))
        t1 = time.perf_counter()
        jax.block_until_ready([fn(*args) for _ in range(repeats)])
        return (out, 1e3 * (t1 - t0) / repeats,
                1e3 * (time.perf_counter() - t1) / repeats)

    table = []
    for name in layers or LAYERS:
        name, _, only = name.partition(":")
        e, attrs, rows_of = LAYERS[name]
        if only == "named":
            rows_of = ()
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
        forms = {"dense": jax.jit(op._apply_dense),
                 "grouped": jax.jit(op._apply_grouped),
                 "kernel": jax.jit(lambda w, v, ids, gates: grouped_experts(
                     v, ids, gates, w, first=op.first, gated=op.gated))}
        route = jax.jit(op.route)
        for rows in rows_of:
            x = jax.random.normal(jax.random.fold_in(key, rows), (rows, e),
                                  jnp.float32).astype(jnp.bfloat16)
            v = x if not op.latent else jnp.dot(
                x, weights["latent_down"]).astype(jnp.bfloat16)
            for routing in ("uniform", "uneven"):
                if routing == "uneven" and rows <= 256 and (
                        rows_of is not ROWS_FEW):
                    continue
                ids, gates, _ = route(weights, x, None if (
                    routing == "uniform") else uneven_ids(
                        jax.random.fold_in(key, 7 * rows), rows,
                        op.n_routed, op.k))
                load = np.bincount(np.asarray(ids).ravel(),
                                   minlength=op.n_routed)[
                    op.first:op.first + op.count]
                row = {"layer": name, "rows": rows, "routing": routing,
                       "rule": op.expert_form(rows),
                       "named_share": op.named_share(rows),
                       "experts_named": int((load > 0).sum()),
                       "pairs_held": int(load.sum()),
                       "load_max_over_mean": float(
                           load.max() / max(load.mean(), 1e-30))}
                outs = {}
                for form, fn in forms.items():
                    if form == "kernel" and not supported(
                            rows, op.k, op.work_dim, op.width, op.count,
                            op.gated, v.dtype):
                        row["kernel_ms"] = None
                        continue
                    try:
                        out, row[f"{form}_ms"], row[
                            f"{form}_inflight_ms"] = timed(fn, weights, v,
                                                           ids, gates)
                        if form == "kernel":
                            out, counted = out
                            row["kernel_rows"] = int(counted)
                        outs[form] = np.asarray(out, np.float32)
                    except Exception as err:  # noqa: BLE001 — e.g. memory
                        row[f"{form}_ms"] = None
                        row[f"{form}_error"] = str(err).splitlines()[0][:200]
                if "dense" in outs:
                    scale = max(np.abs(outs["dense"]).max(), 1e-30)
                    for form in ("grouped", "kernel"):
                        if form in outs:
                            row[f"{form}_differs_rel"] = float(np.abs(
                                outs[form] - outs["dense"]).max() / scale)
                print(json.dumps(row), flush=True)
                table.append(row)
        rows = NAMED_AT.get(name)
        if rows is None or not supported(rows, op.k, op.work_dim, op.width,
                                         op.count, op.gated, jnp.bfloat16):
            continue
        forms = dict(forms, counted=jax.jit(op._apply_counted))
        del forms["grouped"]
        x = jax.random.normal(jax.random.fold_in(key, rows), (rows, e),
                              jnp.float32).astype(jnp.bfloat16)
        v = x if not op.latent else jnp.dot(
            x, weights["latent_down"]).astype(jnp.bfloat16)
        for named in sorted({max(1, round(s * op.count))
                             for s in NAMED_SHARES}):
            ids, gates, _ = route(weights, x, named_ids(op, rows, named))
            row = {"layer": name, "rows": rows, "routing": "named",
                   "named": named, "limit": op.kernel_limit(),
                   "pairs_held": int(((ids >= op.first) & (
                       ids < op.first + op.count)).sum())}
            for form, fn in forms.items():
                out, row[f"{form}_ms"], row[f"{form}_inflight_ms"] = timed(
                    fn, weights, v, ids, gates, repeats=REPEATS_NAMED)
                if form == "counted":
                    row["counted_kernel"] = int(out[2])
            print(json.dumps(row), flush=True)
            table.append(row)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "expert_forms_crossover.json"), "w") as f:
        json.dump(table, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
