"""Where the fused gated-delta-rule kernel beats the jnp scan: the table
behind ``kernels/gated_delta.py``'s ``chunks_supported()`` rule.

    chiprun -- python tools/delta_rule_crossover.py           # the table
    chiprun -- python tools/delta_rule_crossover.py --layer   # and a whole layer

On the chip only (it exits 2 anywhere else: a CPU timing is no speed).
At the hybrid configuration's widths (30 heads, keys of 96, values of
192, batch 1, float32) and each sequence S of ``SEQS`` it reads the
DEVICE time of the whole-sequence recurrence through
``gated_delta_chunks`` at each chunk of ``ROWS`` and through
``chunked_delta_rule``, the jnp scan: one call a program (chained calls
on shared inputs would let XLA compute what does not depend on the state
once for all), five calls each inside ONE profiler window for the whole
table (a window costs the better part of a minute to close), the
program's time on the device a call (a host clock reads the dispatch,
half a millisecond, at these sizes). It also reads the kernel's largest
error against the scan, of o and of the state over their range, at the
widest sequence and on an ill-conditioned draw (keys of a chunk nearly
parallel, beta 1.9-2.0, alpha 0.99-1.0). ``--layer`` reads
``GatedDeltaNet.whole`` (projections, convolution, norms and all) at the
cell's three buckets both ways, with the device operations of the
widest. One JSON line a row on stdout, the table again under
``chiprun_out/delta_rule_crossover.json``. Nothing reads that file: the
rule's constants (``ROWS``, ``MIN_SEQ``) are edited by hand from it,
and PERF.md section 6 keeps the table they were edited from.

The kernel's programs take q, k, v flat, ``(B, S, H d)``, as the op hands
them over. A program that reshapes ``(B, S, H, d)`` operands into the
call AND the call's o back out of it stood still on a v5e at S 1024 and
chunks of 128, and nowhere else (PERF.md section 7, PR 33: XLA keeps the
call's operands and its result in VMEM there, beside the 64 MiB the call
reserves).
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

SEQS = (12, 64, 128, 256, 512, 768, 1024, 1536)
ROWS = (64, 128)
BUCKETS = (768, 1024, 1536)
HEADS, KEY_DIM, VALUE_DIM, EMBED = 30, 96, 192, 3840
CALLS = 5


def draw(rng, s, hard=False):
    """q, k, v, g, beta, state at the cell's widths; ``hard``: the
    ill-conditioned draw."""
    import numpy as np

    h, dk, dv = HEADS, KEY_DIM, VALUE_DIM
    q = rng.normal(size=(1, s, h, dk)).astype(np.float32) * dk ** -0.5
    k = rng.normal(size=(1, s, h, dk)).astype(np.float32)
    if hard:                          # one direction a head, a little noise
        k = (rng.normal(size=(1, 1, h, dk)) + 0.05 * k).astype(np.float32)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(1, s, h, dv)).astype(np.float32)
    lo, hi = (0.99, 1.0) if hard else (0.8, 1.0)
    g = np.log(rng.uniform(lo, hi, size=(1, s, h))).astype(np.float32)
    beta = rng.uniform(*((1.9, 2.0) if hard else (0.0, 2.0)),
                       size=(1, s, h)).astype(np.float32)
    state = rng.normal(size=(1, h, dk, dv)).astype(np.float32) * 0.1
    return q, k, v, g, beta, state


def device_ms(calls, top=0):
    """``calls``: {name: (a jitted function whose ``__name__`` is
    ``name``, its arguments)}. Each is warmed up, then all are called
    ``CALLS`` times in turn inside one profiler window. Returns ({name:
    its program's milliseconds on the device a call}, the window's
    ``top`` device operations in milliseconds a round of calls)."""
    import jax

    from benchmark import reduce

    for fn, args in calls.values():
        jax.block_until_ready(fn(*args))                # compile, warm up
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0   # it swells the trace past reading
    trace_dir = tempfile.mkdtemp(prefix="delta_rule_trace_")
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    for fn, args in calls.values():
        out = None
        for _ in range(CALLS):
            out = fn(*args)
        jax.block_until_ready(out)
    jax.profiler.stop_trace()
    r = reduce.reduce_trace(reduce.load_xplane(reduce.find_xplane(trace_dir)),
                            top=max(top, 1))
    missing = [name for name in calls if f"jit_{name}" not in r["programs"]]
    if missing:
        raise RuntimeError(f"no program in the trace for {missing}: it holds "
                           f"{sorted(r['programs'])}")
    ms = {name: round(r["programs"][f"jit_{name}"]["device_s"] / CALLS * 1e3, 4)
          for name in calls}
    return ms, {nm: round(sec / CALLS * 1e3, 4) for nm, sec in r["ops"][:top]}


def named(name, fn):
    """``fn`` jitted under ``name``: what its program is called in a
    trace (``jit_<name>``; a name ends in a letter, since the trace's
    reduction cuts a program's trailing digits off)."""
    import jax

    fn.__name__ = name
    return jax.jit(fn)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--layer", action="store_true",
                    help="also time GatedDeltaNet.whole at the buckets")
    ap.add_argument("--seqs", default=",".join(map(str, SEQS)),
                    help="the sequences of the table")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.default_backend() != "tpu":
        print(f"[delta_rule_crossover] no result: the backend is "
              f"{jax.default_backend()!r}, not a TPU", file=sys.stderr)
        return 2
    from flexflow_tpu.kernels import gated_delta as gd
    from flexflow_tpu.ops import gated_delta as op_mod

    kind = jax.devices()[0].device_kind
    rng = np.random.default_rng(33)
    rows_out = []

    def emit(row):
        row["device_kind"] = kind
        rows_out.append(row)
        print(json.dumps(row), flush=True)

    def flat(data):
        """q, k, v as the op hands them to the kernel: (B, S, H d)."""
        q, k, v, *rest = data
        return tuple(a.reshape(*a.shape[:2], -1) for a in (q, k, v)) + (
            *rest,)

    def kernel_at(rows, name):
        return named(name, lambda *a: gd._gated_delta_chunks(
            *a, heads=HEADS, rows=rows, interpret=False))

    def scan_at(name):
        return named(name, lambda *a: op_mod.chunked_delta_rule(*a))

    seqs = [int(s) for s in args.seqs.split(",")]
    calls = {}
    for s in seqs:                    # a program a (way, S): one window
        data = tuple(map(jnp.asarray, draw(rng, s)))
        for rows in ROWS:
            calls[f"s{s}_rows{rows}_kernel"] = (
                kernel_at(rows, f"s{s}_rows{rows}_kernel"), flat(data))
        calls[f"s{s}_scan"] = (scan_at(f"s{s}_scan"), data)
    ms, _ = device_ms(calls)
    for s in seqs:
        row = {f"kernel_{rows}": ms[f"s{s}_rows{rows}_kernel"] for rows in ROWS}
        row["scan"] = ms[f"s{s}_scan"]
        emit({"seq": s, "device_ms": row,
              "scan_over_kernel": round(
                  row["scan"] / row[f"kernel_{gd.ROWS}"], 3),
              "engaged": op_mod.delta_rule_path(s, HEADS, KEY_DIM,
                                                VALUE_DIM)})

    for hard in (False, True):
        data = tuple(map(jnp.asarray, draw(rng, seqs[-1], hard)))
        want_o, want_s = scan_at("scan")(*data)
        row = {"error_at": seqs[-1], "ill_conditioned": hard}
        for rows in ROWS:
            o, st = kernel_at(rows, "kernel")(*flat(data))
            row[f"o_rel_{rows}"] = float(
                jnp.abs(o.reshape(want_o.shape) - want_o).max()
                / jnp.abs(want_o).max())
            row[f"state_rel_{rows}"] = float(jnp.abs(st - want_s).max()
                                             / jnp.abs(want_s).max())
        emit(row)

    if args.layer:
        from flexflow_tpu.core.layer import Layer
        from flexflow_tpu.core.parallel_tensor import ParallelTensorShape
        from flexflow_tpu.ffconst import DataType, OpType

        layer = Layer(OpType.GATED_DELTA_NET, "gdn", attrs=dict(
            num_heads=HEADS, key_dim=KEY_DIM, value_dim=VALUE_DIM,
            conv_taps=4, allow_neg_eigval=True))
        op = op_mod.GatedDeltaNet(layer, [ParallelTensorShape.unpartitioned(
            (1, BUCKETS[-1], EMBED), DataType.FLOAT)])
        w = {ws.name: jnp.asarray(
            rng.normal(size=ws.shape).astype(np.float32)
            * (0.02 if len(ws.shape) > 1 else 1.0), jnp.bfloat16)
            for ws in op.weight_specs()}

        def whole_by(name, mode):
            # a function of its own a mode: jit caches by the function,
            # and the rule is asked when it traces
            def whole(w, x, n):
                os.environ["FLEXFLOW_TPU_PALLAS"] = mode
                try:
                    return op.whole(w, x, n)
                finally:
                    os.environ.pop("FLEXFLOW_TPU_PALLAS")
            return named(name, whole)

        calls = {}
        for s in BUCKETS:             # a program a (way, bucket): one window
            x = jnp.asarray(rng.normal(size=(1, s, EMBED)), jnp.bfloat16)
            n = jnp.asarray([s - 37], jnp.int32)
            for way, mode in (("kernel", "compiled"), ("scan", "off")):
                calls[f"s{s}_layer_{way}"] = (
                    whole_by(f"s{s}_layer_{way}", mode), (w, x, n))
        ms, _ = device_ms(calls)
        for s in BUCKETS:
            emit({"layer_seq": s, "device_ms": {
                way: ms[f"s{s}_layer_{way}"] for way in ("kernel", "scan")}})
        for way in ("kernel", "scan"):  # the widest bucket's operations
            name = f"s{BUCKETS[-1]}_layer_{way}"
            _, ops = device_ms({name: calls[name]}, top=14)
            emit({"layer_seq": BUCKETS[-1], "ops_ms": {way: ops}})

    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "delta_rule_crossover.json"), "w") as f:
        json.dump(rows_out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
