"""Where the fused attention kernels beat XLA's softmax(QK^T)V: the table
behind ``kernels/flash_attention.py``'s ``engaged()`` rule.

    chiprun -- python tools/flash_crossover.py            # the whole table
    chiprun -- python tools/flash_crossover.py --cell     # the fit cell's shape only

On the chip only (it exits 2 anywhere else: a CPU timing is no speed).
For each sequence S in {128, 256, 512, 1024, 2048} and head size d in
{64, 128}, at batch x heads 64, causal, bfloat16, it times the forward
and backward passes together (``flash_attention.autotune``: eight
passes a program, the median of three windows) through the kernels at
each block size that tiles S (``backward`` says which form of the
backward each timing ran: ``fused``, one kernel, or ``split``, two), and
through ``single_device_attention``, the `xla` path. One JSON line a
shape on stdout, the table again under
``chiprun_out/flash_crossover.json``. Nothing reads that file: the
rule's constants (``MIN_SEQ``, ``BLOCKS``) are edited by hand from it,
and PERF.md section 6 keeps the table they were edited from.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

SEQS = (128, 256, 512, 1024, 2048)
HEAD_DIMS = (64, 128)
BATCH_X_HEADS = 64
BLOCKS = ((1024, 1024), (1024, 512), (512, 1024), (512, 512), (512, 256),
          (256, 512), (256, 256), (256, 128), (128, 128))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", action="store_true",
                    help="only (4, 1024, 16, 64), the fit cell's shape")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--noncausal", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    if jax.default_backend() != "tpu":
        print(f"[flash_crossover] no result: the backend is "
              f"{jax.default_backend()!r}, not a TPU", file=sys.stderr)
        return 2
    from flexflow_tpu.kernels import flash_attention as fa

    shapes = ([(4, 1024, 16, 64)] if args.cell else
              [(BATCH_X_HEADS // 16, s, 16, d) if d == 64
               else (BATCH_X_HEADS // 8, s, 8, d)
               for d in HEAD_DIMS for s in SEQS])
    causal, dtype = not args.noncausal, jnp.dtype(args.dtype)
    # the rule on `auto`, as fit runs; then force, so that a refusal raises
    rules = [fa.engaged(s[1], s[1], s[3], causal, dtype) for s in shapes]
    os.environ["FLEXFLOW_TPU_PALLAS"] = "compiled"
    rows = []
    for shape, rule in zip(shapes, rules):
        r = fa.autotune(shape=shape, candidates=BLOCKS, causal=causal,
                        dtype=dtype)
        row = {"shape": list(shape), "dtype": args.dtype,
               "causal": causal,
               "device_kind": jax.devices()[0].device_kind,
               "kernel_ms": {f"{bq}x{bk}": round(t * 1e3, 4)
                             for (bq, bk), t in r["blocks"].items()},
               "backward": {f"{bq}x{bk}": form
                            for (bq, bk), form in r["backward"].items()},
               "best": list(r["best"]) if r["best"] else None,
               "xla_ms": round(r["xla_s"] * 1e3, 4),
               "xla_over_kernel": r["xla_ratio"],
               "engaged": rule}
        rows.append(row)
        print(json.dumps(row), flush=True)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "flash_crossover.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
