"""The paged decode kernel alone at each serving cell's shape, the pages
a chunk side by side: the table behind ``kernels/paged_attention.py``
``CHUNK_BYTES`` / ``MAX_PAGES``.

    chiprun -- python tools/paged_chunk_sweep.py [case ...]

On the chip only (it exits 2 anywhere else: a CPU timing is no speed).
A case is a cell's pool: slots, query heads on key-value heads of a head
width, block size, table length, so many layers with bfloat16 arenas of
their own of ``slots x table + 1`` blocks each (gigabytes together, as in
a cell; every call over ONE layer's arenas read the same within half a
per cent, my chip runs, PR 51), tables over shuffled blocks, so many slots live with lengths spread
between the cell's shortest and longest context (a beta draw of the
cell's mean) and the rest idle (``seq_len`` 0, the null table). For each
``pages_per_chunk`` of the case, and for the chunk the kernel's own rule
gives (``pages`` ``null``), it times the layers' calls chained inside one
program (a layer's query takes a thousandth of the layer before's output,
so no call is merged or reordered), ``REPEATS`` programs dispatched one
behind another and waited for once, and reads the kernel's own device
time from a profile of the same calls (the events named
``paged_attention_decode``). Beside both stands the bytes' time:
the live tokens' K and V rows over the chip's 819 GB/s. One JSON line a
row on stdout, the table again under
``chiprun_out/paged_chunk_sweep.json``. Nothing reads that file: the
rule is edited by hand from it, and PERF.md section 6 (PR 51) keeps the
table it was edited from.
"""

from __future__ import annotations

import glob
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

REPEATS = 10
HBM_BYTES_PER_S = 819e9           # TPU v5e (benchmark/peaks.json)
# name: slots, heads, kv_heads, head_dim, block, table, layers, live
# slots, shortest, longest and mean context, pages a chunk to try
CASES = {
    "chains": (48, 8, 2, 128, 64, 72, 20, 28, 256, 4608, 1900,
               (4, 8, 16, 32)),
    "retrieval": (48, 32, 8, 64, 64, 144, 6, 44, 1024, 9216, 4000,
                  (4, 8, 16)),
    "mixedlengths_full": (32, 48, 8, 128, 64, 272, 3, 30, 512, 17408, 5800,
                          (4, 8)),
    "offline": (16, 20, 20, 64, 16, 64, 36, 16, 128, 896, 450, (16, 32)),
    "agents": (128, 32, 2, 128, 16, 128, 16, 120, 512, 1792, 1100,
               (16, 32, 64)),
}


def kernel_device_us(trace_dir: str,
                     kernel: str = "paged_attention_decode") -> list:
    """Device durations (us) of the events of the kernel of that name: an
    event is named by its instruction's whole text, so one that only
    READS the kernel's output names it too; the kernel's own starts with
    its name."""
    from jax.profiler import ProfileData

    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    out = []
    for plane in (ProfileData.from_file(found[-1]).planes if found else ()):
        if not plane.name.startswith("/device:TPU"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            out.extend(ev.duration_ns / 1e3 for ev in line.events
                       if ev.name.lstrip("%").startswith(kernel))
    return out


def main(argv) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.default_backend() != "tpu":
        print(f"paged_chunk_sweep: the backend is {jax.default_backend()}, "
              f"not a TPU", file=sys.stderr)
        return 2
    from flexflow_tpu.kernels import paged_attention as pa

    rows = []
    for name in (argv or CASES):
        (slots, heads, kv_heads, head_dim, block, table, n_layers, live, lo,
         hi, mean, pages_list) = CASES[name]
        rng = np.random.default_rng(51)
        hd = kv_heads * head_dim
        nb = slots * table + 1
        # a beta of shape (a, 2) on [lo, hi - 1] with the cell's mean
        share = (mean - lo) / (hi - 1 - lo)
        draw = rng.beta(2 * share / (1 - share), 2.0, size=live)
        lens = np.zeros(slots, np.int32)
        where = rng.permutation(slots)[:live]
        lens[where] = (lo + draw * (hi - 1 - lo)).astype(np.int32)
        tables = rng.permutation(np.arange(1, nb)).astype(np.int32).reshape(
            slots, table)
        tables[lens == 0] = 0
        kq, *keys = jax.random.split(jax.random.PRNGKey(51),
                                     1 + 2 * n_layers)
        ks, vs = ([jax.random.normal(key, (nb, block, hd), jnp.bfloat16)
                   for key in half]
                  for half in (keys[:n_layers], keys[n_layers:]))
        q = jax.random.normal(kq, (slots, 1, heads, head_dim), jnp.bfloat16)
        tables_d, lens_d = jnp.asarray(tables), jnp.asarray(lens)
        live_tokens = int(lens.sum()) + live            # the step's own row
        bytes_us = 1e6 * live_tokens * hd * 2 * 2 / HBM_BYTES_PER_S
        rule = pa.chunk_tokens(ks[0].shape, ks[0].dtype, table) // block
        for pages in pages_list + (None,):
            def layers(q, ks, vs, tables, lens, pages=pages):
                out = None
                for k, v in zip(ks, vs):
                    out = pa.paged_attention_decode(
                        q, k, v, tables, lens, pages_per_chunk=pages)
                    q = q + (1e-3 * out).astype(q.dtype)
                return out
            fn = jax.jit(layers)
            args = (q, ks, vs, tables_d, lens_d)
            jax.block_until_ready(fn(*args))
            t = time.perf_counter()
            for _ in range(REPEATS):
                last = fn(*args)
            jax.block_until_ready(last)
            wall_us = 1e6 * (time.perf_counter() - t) / (REPEATS * n_layers)
            with tempfile.TemporaryDirectory() as tmp:
                with jax.profiler.trace(tmp):
                    jax.block_until_ready(fn(*args))
                dev = kernel_device_us(tmp)
            device_us = float(np.median(dev)) if dev else None
            got = pages or rule
            row = {
                "case": name, "pages": pages, "pages_run": got,
                "chunk_tokens": got * block,
                "in_flight_bytes": got * block * hd * 2 * 2,
                "layers": n_layers, "live_slots": live,
                "live_tokens": live_tokens,
                "bytes_us": round(bytes_us, 2),
                "wall_us": round(wall_us, 2),
                "device_us": device_us and round(device_us, 2),
                "device_events": len(dev),
                "share_of_bytes_time": round(
                    100 * bytes_us / (device_us or wall_us), 1)}
            rows.append(row)
            print(json.dumps(row), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "paged_chunk_sweep.json"),
              "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
