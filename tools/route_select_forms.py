"""A router's pick of k among n by both of its forms: the table behind
``SELECT_PASS_STAGES`` (``ops/moe_ops.py`` ``k_largest``, ``select_form``).

    chiprun -- python tools/route_select_forms.py [--no-grid] [cell ...]

On the chip only (it exits 2 anywhere else: a CPU timing is no speed;
about five minutes). Every time is the device's busy time a call, from a
profile of ``CALLS`` calls (the union of the TPU plane's ``XLA Ops``: a
pick takes some ten microseconds, far under what the host needs to
dispatch it).

For each cell of ``BENCHMARK.json`` whose graph holds a routed-experts
op (all of them, or those named), with the op built as the cell builds
it (the family's ``build`` over the configuration's file, never
compiled: ``n_group``, ``topk_group``, ``k`` and ``n_routed`` are the
op's own) and the rows of the cell's decode step and of its widest
prefill bucket or chunk:

* ``call``: each pick ``route`` makes (a grouped router's best 2 of each
  group over ``(rows, groups, a group's experts)``, its ``topk_group`` of
  the groups, then k of ``n_routed``) alone behind a sigmoid, as
  ``passes_ms`` and ``sort_ms``, with what the rule says (``rule``) and
  whether the two forms returned the same bits (``same``);
* ``route``: the op's whole ``route`` (the router's product, the scores,
  the picks, the weights) with every pick by the sort, every pick by
  passes and as the rule cuts them, beside ``parent_ms``: the lines
  ``route`` held up to PR 61 (``jax.lax.top_k`` three times, the second
  over three axes, and a scatter for the kept groups), kept here for the
  record.

Behind the cells, unless ``--no-grid``, every k of ``GRID_K`` among
every n of ``GRID_N`` at 256 and 2,048 rows, both forms alone: where they
cross is what ``SELECT_PASS_STAGES`` (passes a stage of a bitonic sort
of n) has to sit under. One JSON line a row on stdout, the table again
under ``chiprun_out/route_select_forms.json``. Nothing reads that file:
the constant is edited by hand from it, and PERF.md section 6 (PR 62)
keeps the table it was edited from.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CALLS = 5
GRID_K = (1, 2, 4, 6, 8, 12, 16, 22, 32)
GRID_N = (8, 16, 32, 64, 128, 192, 256, 512)
GRID_ROWS = (256, 2048)


def device_ms(fn, *args):
    """(the result, the device's busy ms a call) over ``CALLS`` calls
    behind one that compiled."""
    import jax

    from benchmark import reduce

    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            for _ in range(CALLS):
                out = jax.block_until_ready(fn(*args))
        trace = reduce.load_xplane(reduce.find_xplane(tmp))
    busy = reduce.total(reduce.union(
        (start, start + length) for plane in trace["planes"]
        if plane["name"].startswith("/device:TPU")
        for line in plane["lines"] if line["name"] == "XLA Ops"
        for _, start, length in line["events"]))
    return out, busy / 1e6 / CALLS


def routed_op(layout, workload):
    """(the cell's first routed-experts op, its decode rows, its widest
    prompt rows), or None for a cell whose graph routes nothing."""
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.core.op import create_op
    from flexflow_tpu.core.parallel_tensor import ParallelTensorShape
    from flexflow_tpu.ffconst import CompMode, OpType

    cell = layout.cell(workload)
    mix, config = cell["mix"], cell["config"]
    if "decode_slots" not in mix:
        return None
    slots = int(mix["decode_slots"])
    ff = FFModel(FFConfig(
        compute_dtype="bfloat16", search_cache="off", ledger="off",
        batch_size=slots, computation_mode=CompMode.INFERENCE))
    layout.family(config["family"]).build(ff, config, slots,
                                          int(mix["max_length"]))
    layer = next((la for la in ff.layers
                  if la.op_type is OpType.ROUTED_EXPERTS), None)
    if layer is None:
        return None
    op = create_op(layer, [ParallelTensorShape.unpartitioned(t.dims, t.dtype)
                           for t in layer.inputs])
    return op, slots, int(mix.get("prefill_chunk")
                          or max(mix["prefill_buckets"]))


def picks(op, rows):
    """The picks ``route`` makes of ``rows`` tokens: (what, shape, k)."""
    out = []
    if op.n_group > 1 and op.topk_group < op.n_group:
        per = op.n_routed // op.n_group
        out += [("best of a group", (rows, op.n_group, per), min(2, per)),
                ("groups", (rows, op.n_group), op.topk_group)]
    return out + [("experts", (rows, op.n_routed), op.k)]


def parent_route(op, weights, x2d, prev):
    """``RoutedExperts.route`` as it stood up to PR 61."""
    import jax
    import jax.numpy as jnp

    logits, _ = op._router_logits(weights, x2d, prev)
    s = (jax.nn.sigmoid(logits) if op.scoring == "sigmoid"
         else jax.nn.softmax(logits, axis=-1))
    choice = s + weights["bias"].astype(jnp.float32) if (
        op.selection_bias) else s
    if op.n_group > 1 and op.topk_group < op.n_group:
        t = s.shape[0]
        per = op.n_routed // op.n_group
        grouped = choice.reshape(t, op.n_group, per)
        gscore = jax.lax.top_k(grouped, min(2, per))[0].sum(-1)
        _, gidx = jax.lax.top_k(gscore, op.topk_group)
        keep = jnp.zeros((t, op.n_group), bool).at[
            jnp.arange(t)[:, None], gidx].set(True)
        choice = jnp.where(keep[:, :, None], grouped, -1.0).reshape(
            t, op.n_routed)
    _, ids = jax.lax.top_k(choice, op.k)
    g = jnp.take_along_axis(s, ids, axis=-1)
    if op.norm_topk:
        g = g / (g.sum(-1, keepdims=True) + 1e-20)
    return ids.astype(jnp.int32), g * op.routed_scale


def both_forms(shape, k, seed):
    """{"passes_ms", "sort_ms", "same"} of one pick alone behind a
    sigmoid."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.ops.moe_ops import k_largest

    x = jnp.asarray(np.random.default_rng(seed).normal(size=shape),
                    jnp.float32)
    out, got = {}, {}
    for form in ("passes", "sort"):
        fn = jax.jit(lambda x, form=form: k_largest(
            jax.nn.sigmoid(x), k, form=form))
        got[form], ms = device_ms(fn, x)
        out[form + "_ms"] = round(ms, 5)
    out["same"] = all(
        np.array_equal(np.asarray(a).view(np.int32),
                       np.asarray(b).view(np.int32))
        for a, b in zip(got["passes"], got["sort"]))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cells", nargs="*")
    ap.add_argument("--no-grid", action="store_true")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.default_backend() != "tpu":
        print(f"no table: the backend is {jax.default_backend()!r}",
              file=sys.stderr)
        return 2
    from benchmark.spec import Layout
    from flexflow_tpu.ops import moe_ops

    layout = Layout(ROOT)
    table = []

    def say(row):
        print(json.dumps(row), flush=True)
        table.append(row)

    for workload in args.cells or [w["name"]
                                   for w in layout.bench["workloads"]]:
        found = routed_op(layout, workload)
        if found is None:
            continue
        op, decode_rows, prompt_rows = found
        e = op.in_dim
        router = dict(n_routed=op.n_routed, k=op.k, n_group=op.n_group,
                      topk_group=op.topk_group, scoring=op.scoring,
                      router=op.router, select_form=op.select_form())
        key = jax.random.key(0)
        weights = {
            ws.name: (0.02 * jax.random.normal(
                jax.random.fold_in(key, i), ws.shape, jnp.float32)
            ).astype(jnp.bfloat16)
            for i, ws in enumerate(op.weight_specs())
            if ws.name not in moe_ops.EXPERT_MATRICES
            and not ws.name.startswith("latent")}
        for rows in (decode_rows, prompt_rows):
            for what, shape, k in picks(op, rows):
                say(dict(cell=workload, rows=rows, call=what, k=k,
                         n=shape[-1], rule=moe_ops.select_form(k, shape[-1]),
                         **both_forms(shape, k, rows)))
            x = jax.random.normal(jax.random.fold_in(key, rows), (rows, e),
                                  jnp.float32).astype(jnp.bfloat16)
            prev = (jnp.zeros((rows, op.router_width), jnp.float32)
                    if op.takes_state else None)
            row = dict(cell=workload, rows=rows, route=router)
            want, row["parent_ms"] = device_ms(
                jax.jit(lambda w, x: parent_route(op, w, x, prev)),
                weights, x)
            stages = moe_ops.SELECT_PASS_STAGES
            for name, value in (("sort", float("inf")), ("passes", 0.0),
                                ("rule", stages)):
                moe_ops.SELECT_PASS_STAGES = value
                try:
                    got, ms = device_ms(
                        jax.jit(lambda w, x: op.route(w, x, prev=prev)[:2]),
                        weights, x)
                finally:
                    moe_ops.SELECT_PASS_STAGES = stages
                row[name + "_ms"] = round(ms, 5)
                row[name + "_same"] = all(
                    np.array_equal(np.asarray(a), np.asarray(b))
                    for a, b in zip(got, want))
            row["parent_ms"] = round(row["parent_ms"], 5)
            say(row)
    if not args.no_grid:
        for rows in GRID_ROWS:
            for n in GRID_N:
                for k in (k for k in GRID_K if k <= n):
                    say(dict(grid=True, rows=rows, k=k, n=n,
                             rule=moe_ops.select_form(k, n),
                             **both_forms((rows, n), k, rows + n)))
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "route_select_forms.json"), "w") as f:
        json.dump(table, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
