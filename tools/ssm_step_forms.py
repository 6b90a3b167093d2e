"""What a pass that reads and writes Mamba-2 states in place reaches on
this chip, by form (chip only; about two minutes): the
``ssd_step_decode`` kernel and the ``ssd_step_rows`` fusion, each alone
on donated arenas at the two cells' shapes (eight arenas a timed call:
one arena's step is shorter than a call's dispatch), none, a quarter,
half and all of the slots idle, in GB/s of the stepped rows' bytes in and
out and of the whole arena's. PERF.md
section 6 (PR 47) holds the table this printed, and that of the forms
not taken (rows of ``(H, P, N)``, a row cut in parts).

    python tools/ssm_step_forms.py [--iters 50]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from flexflow_tpu.kernels import ssd_step
from flexflow_tpu.ops import mamba2

# (arena rows, slots, heads, head_dim, state, groups)
SHAPES = {"granite-4.0-h-micro": (49, 48, 64, 64, 128, 1),
          "nemotron3-super-ep4": (129, 128, 128, 64, 128, 8)}


FORMS = {"ssd_step_rows (the fusion)": mamba2.ssd_step_rows,
         "ssd_step_decode (the kernel)": ssd_step.ssd_step_decode}


# arenas a timed call steps, one after the other as a model's layers do:
# one arena's step is shorter than the host's dispatch of a call
LAYERS = 8


def inputs(shape, idle, seed=0):
    """``idle`` of the slots name row 0, the others a row each in no
    order. Returns (the arenas, the slots' rows, u, decay, B, C)."""
    rows, slots, h, p, n, g = shape
    rng = np.random.default_rng(seed)
    named = rng.permutation(np.arange(1, rows)).astype(np.int32)[:slots]
    named[rng.permutation(slots)[:idle]] = 0
    f32 = jnp.float32
    arena = jnp.asarray(rng.normal(size=(rows, n, h * p)), f32)
    return ([arena + i for i in range(LAYERS)], jnp.asarray(named),
            jnp.asarray(rng.normal(size=(slots, h, p)), f32),
            jnp.asarray(rng.uniform(0.5, 1.0, (slots, h)), f32),
            jnp.asarray(rng.normal(size=(slots, g, n)), f32),
            jnp.asarray(rng.normal(size=(slots, g, n)), f32))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=50)
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        sys.exit(f"chip only: the backend is {jax.default_backend()}")
    table = []
    for cell, shape in SHAPES.items():
        rows, slots, h, p, n, g = shape
        row_bytes = 2 * h * p * n * 4                  # in and out
        for idle in (0, slots // 4, slots // 2, slots):
            want = None
            for name, form in FORMS.items():
                arenas, named, *rest = inputs(shape, idle)
                keep = np.asarray(named) != 0

                def layers(arenas, named, *rest, form=form):
                    out = [form(a, named, *rest) for a in arenas]
                    return [y for y, _ in out], [a for _, a in out]

                fn = jax.jit(layers, donate_argnums=(0,))
                ys, arenas = fn(arenas, named, *rest)
                y = np.asarray(ys[0])
                want = y if want is None else want
                err = float(np.abs(y - want)[keep].max(initial=0.0)
                            / np.abs(want).max())
                for _ in range(3):
                    ys, arenas = fn(arenas, named, *rest)
                jax.block_until_ready(arenas)
                t0 = time.perf_counter()
                for _ in range(args.iters):
                    ys, arenas = fn(arenas, named, *rest)
                jax.block_until_ready((ys, arenas))
                ms = (time.perf_counter() - t0) / args.iters / LAYERS * 1e3
                line = dict(cell=cell, slots=slots, idle=idle, form=name,
                            ms_a_layer=round(ms, 4),
                            GBps_of_live_rows=round(
                                keep.sum() * row_bytes / ms / 1e6, 1),
                            GBps_of_arena=round(
                                rows * row_bytes / ms / 1e6, 1),
                            y_err_of_range=err)
                print(json.dumps(line), flush=True)
                table.append(line)
                del arenas
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/ssm_step_forms.json", "w") as f:
        json.dump(table, f, indent=1)


if __name__ == "__main__":
    main()
