"""Where the fused gated-delta-rule kernel beats the jnp scan: the table
behind ``kernels/gated_delta.py``'s ``chunks_supported()`` rule.

    chiprun -- python tools/delta_rule_crossover.py           # the table
    chiprun -- python tools/delta_rule_crossover.py --layer   # and a whole layer
    chiprun -- python tools/delta_rule_crossover.py --decay channel --layer

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

``--decay channel`` is the same for a decay a key CHANNEL (the KDA
layers of ``ling-3.0-flash-ep8`` and ``glm-5.3-flash-ep8``): the kernel
(its Pallas call ``channel_delta_chunks``) against
``chunked_channel_rule`` at ``CHANNEL_SEQS`` with 32 and with 64 heads of
128 x 128, gates uniform in (-5, 0) (the published bound); then the
kernel with 1, 2, 4 and 8 heads a grid step at both cells' widest shape
(the rule's ``_chunk_group`` replaced for the trace: the table behind
``CHAINS``); the errors on that draw and with EVERY gate at the bound;
and with ``--layer`` ``KimiDeltaAttention.run`` behind a state at each
shape the two cells run (Ling's three buckets at one and two rows, GLM's
chunk), each program's FIRST call under a time limit of its own
(``FIRST_CALL_S``: the process dumps its stacks and exits 1 past it).

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
# a decay a key channel: the table's sequences, heads and widths, and the
# shapes (rows, tokens, heads, the model's width) the two cells run
CHANNEL_SEQS = (64, 128, 256, 512, 768, 1024, 2048, 4096)
CHANNEL_HEADS, CHANNEL_DIM = (32, 64), 128
CHANNEL_SHAPES = {"ling": [(b, s, 32, 2560) for s in (512, 768, 1024)
                           for b in (1, 2)],
                  "glm": [(1, 2048, 64, 4096)]}
GROUPS = (1, 2, 4, 8)
FIRST_CALL_S = 240


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


def draw_channel(rng, b, s, h, d, bound=False):
    """q, k, v, g (B, S, H, d), beta, state for a decay a key channel:
    unit q and k, gates uniform in (-5, 0) or, ``bound``, all at -5."""
    import numpy as np

    q, k, v = (rng.normal(size=(b, s, h, d)).astype(np.float32)
               for _ in range(3))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * d ** 0.5
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g = (np.full((b, s, h, d), -5.0) if bound
         else rng.uniform(-5.0, 0.0, size=(b, s, h, d))).astype(np.float32)
    beta = rng.uniform(0.0, 1.0, size=(b, s, h)).astype(np.float32)
    state = rng.normal(size=(b, h, d, d)).astype(np.float32) * 0.1
    return q, k, v, g, beta, state


def first_call(fn, args, name):
    """``fn(*args)`` to its end under ``FIRST_CALL_S``: a program that
    stands still on the chip (PERF.md section 7, PR 33) ends the process
    with every thread's stack, exit code 1, its name the last line
    printed."""
    import faulthandler

    import jax

    print(f"[delta_rule_crossover] first call: {name}", flush=True)
    faulthandler.dump_traceback_later(FIRST_CALL_S, exit=True)
    jax.block_until_ready(fn(*args))
    faulthandler.cancel_dump_traceback_later()


def device_ms(calls, top=0):
    """``calls``: {name: (a jitted function whose ``__name__`` is
    ``name``, its arguments)}. Each is warmed up, then all are called
    ``CALLS`` times in turn inside one profiler window. Returns ({name:
    its program's milliseconds on the device a call}, the window's
    ``top`` device operations in milliseconds a round of calls)."""
    import jax

    from benchmark import reduce

    for name, (fn, args) in calls.items():
        first_call(fn, args, name)                      # compile, warm up
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
    ap.add_argument("--seqs", default=None,
                    help="the sequences of the table")
    ap.add_argument("--decay", choices=("scalar", "channel"),
                    default="scalar", help="the decay's form")
    args = ap.parse_args(argv)
    if args.seqs is None:
        args.seqs = ",".join(map(str, CHANNEL_SEQS if args.decay == "channel"
                                 else SEQS))

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

    if args.decay == "channel":
        channel_tables(args, emit, flat, rng)
        return dump(rows_out)

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

    return dump(rows_out)


def dump(rows_out) -> int:
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "delta_rule_crossover.json"), "w") as f:
        json.dump(rows_out, f, indent=1)
    return 0


def channel_tables(args, emit, flat, rng):
    """The tables of ``--decay channel`` (the module's docstring)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.kernels import gated_delta as gd
    from flexflow_tpu.ops import gated_delta as op_mod

    d = CHANNEL_DIM

    def flat_g(data):
        q, k, v, g, *rest = flat(data)
        return (q, k, v, g.reshape(*g.shape[:2], -1), *rest)

    def kernel(name, heads, group=None):
        def call(*a):
            was = gd._chunk_group
            if group is not None:     # read when the call is traced
                gd._chunk_group = lambda *_: group
            try:
                o, state = gd._gated_delta_chunks.__wrapped__(
                    *a, heads=heads, rows=gd.ROWS,
                    interpret=gd.pallas_mode() == "interpret")
            finally:
                gd._chunk_group = was
            # a program of its own for the compile cache (whose key
            # leaves the name out: the rule's own group would be served
            # the table's executable, under the table's name)
            return (o, state) if group is None else (o, state, state[0, 0])
        return named(name, call)

    def scan(name):
        return named(name, lambda *a: op_mod.chunked_channel_rule(*a))

    seqs = [int(s) for s in args.seqs.split(",")]
    calls = {}
    for h in CHANNEL_HEADS:           # a program a (way, heads, S): one window
        for s in seqs:
            data = tuple(map(jnp.asarray, draw_channel(rng, 1, s, h, d)))
            calls[f"h{h}_s{s}_kernel"] = (kernel(f"h{h}_s{s}_kernel", h),
                                          flat_g(data))
            calls[f"h{h}_s{s}_scan"] = (scan(f"h{h}_s{s}_scan"), data)
    widest = (CHANNEL_SHAPES["ling"][-1], CHANNEL_SHAPES["glm"][-1])
    # a name ends in a letter: group 1 is "a", 2 "b", ...
    grouped = lambda b, s, h, i: f"h{h}_b{b}_s{s}_group{'abcd'[i]}"  # noqa: E731
    for b, s, h, _ in widest:
        data = flat_g(tuple(map(jnp.asarray, draw_channel(rng, b, s, h, d))))
        for i, group in enumerate(GROUPS):
            name = grouped(b, s, h, i)
            calls[name] = (kernel(name, h, group), data)
    ms, _ = device_ms(calls)
    for h in CHANNEL_HEADS:
        for s in seqs:
            row = {"kernel": ms[f"h{h}_s{s}_kernel"],
                   "scan": ms[f"h{h}_s{s}_scan"]}
            emit({"decay": "channel", "heads": h, "seq": s, "device_ms": row,
                  "scan_over_kernel": round(row["scan"] / row["kernel"], 3),
                  "engaged": op_mod.delta_rule_path(s, h, d, d,
                                                    channel_decay=True)})
    for b, s, h, _ in widest:
        emit({"decay": "channel", "heads": h, "rows": b, "seq": s,
              "heads_a_step_device_ms": {
                  group: ms[grouped(b, s, h, i)]
                  for i, group in enumerate(GROUPS)},
              "rule": gd._chunk_group(d, d, h)})

    b, s, h, _ = widest[-1]
    for bound in (False, True):       # the errors, at the gate's bound too
        data = tuple(map(jnp.asarray, draw_channel(rng, b, s, h, d, bound)))
        want_o, want_s = scan("scan")(*data)
        o, st = kernel("kernel", h)(*flat_g(data))[:2]
        emit({"decay": "channel", "error_at": s, "gates_at_bound": bound,
              "o_rel": float(jnp.abs(o.reshape(want_o.shape) - want_o).max()
                             / jnp.abs(want_o).max()),
              "state_rel": float(jnp.abs(st - want_s).max()
                                 / jnp.abs(want_s).max()),
              "finite": bool(jnp.isfinite(o).all() & jnp.isfinite(st).all())})

    if not args.layer:
        return
    from flexflow_tpu.core.layer import Layer
    from flexflow_tpu.core.parallel_tensor import ParallelTensorShape
    from flexflow_tpu.ffconst import DataType, OpType

    calls = {}
    for cell, shapes in CHANNEL_SHAPES.items():
        b0, s0, h, e = shapes[-1]
        ranks = dict(decay_rank=d, gate_rank=d, output_gate="channel") \
            if cell == "glm" else {}
        layer = Layer(OpType.KIMI_DELTA_ATTENTION, "kda", attrs=dict(
            num_heads=h, key_dim=d, value_dim=d, conv_taps=4, **ranks))
        op = op_mod.KimiDeltaAttention(
            layer, [ParallelTensorShape.unpartitioned((b0, s0, e),
                                                      DataType.FLOAT)])
        w = {ws.name: jnp.asarray(
            rng.normal(size=ws.shape).astype(np.float32)
            * (0.02 if len(ws.shape) > 1 else 1.0), jnp.bfloat16)
            for ws in op.weight_specs()}

        def run_by(name, mode, op=op):
            def run(w, x, state, tail, n):
                os.environ["FLEXFLOW_TPU_PALLAS"] = mode
                try:
                    return op.run(w, x, state, tail, n)
                finally:
                    os.environ.pop("FLEXFLOW_TPU_PALLAS")
            return named(name, run)

        for b, s, _, _ in shapes:
            x = jnp.asarray(rng.normal(size=(b, s, e)), jnp.bfloat16)
            state = jnp.asarray(rng.normal(size=(b, h, d, d)) * 0.1,
                                jnp.float32)
            tail = jnp.asarray(rng.normal(size=(b, 3, op.channels)),
                               jnp.bfloat16)
            n = jnp.asarray([s - 37] * b, jnp.int32)
            for way, mode in (("kernel", "compiled"), ("scan", "off")):
                name = f"{cell}_b{'ab'[b - 1]}_s{s}_layer_{way}"
                calls[name] = (run_by(name, mode), (w, x, state, tail, n))
    ms, _ = device_ms(calls)
    for cell, shapes in CHANNEL_SHAPES.items():
        for b, s, _, _ in shapes:
            name = f"{cell}_b{'ab'[b - 1]}_s{s}_layer_"
            emit({"decay": "channel", "cell": cell, "rows": b, "layer_seq": s,
                  "device_ms": {way: ms[name + way]
                                for way in ("kernel", "scan")}})
    b, s, _, _ = CHANNEL_SHAPES["glm"][-1]    # the chunk's operations
    name = f"glm_b{'ab'[b - 1]}_s{s}_layer_kernel"
    _, ops = device_ms({name: calls[name]}, top=14)
    emit({"decay": "channel", "cell": "glm", "layer_seq": s,
          "ops_ms": {"kernel": ops}})


if __name__ == "__main__":
    sys.exit(main())
