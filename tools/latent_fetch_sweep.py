"""The latent decode kernel alone at the reasoning cell's shape, by the
bytes ONE copy brings: the table behind ``kernels/latent_attention.py``
``RUN_BYTES``.

    chiprun -- python tools/latent_fetch_sweep.py [blocks] [runs]

On the chip only (it exits 2 anywhere else: a CPU timing is no speed).
128 slots of 64 heads over rows of 640 bfloat16 lanes, tables of 4,096
tokens, lengths drawn as ``benchmark/traffic/serve-reasoning.json`` draws
them (a prompt of 512-1,024 and so much of an answer of 1,024-3,072 as a
request caught at a random moment has written: 1,900 cached tokens a
slot), six layers with an arena each, the calls chained inside one
program as ``tools/paged_chunk_sweep.py`` chains them.

``blocks``: the same bytes and the same chunk of 512 tokens over arenas
cut into blocks of 16, 32, 64 and 128 tokens, every table shuffled and
every block a copy of its own (``run`` 1): a quarter and an eighth of the
copies. At PR 56's parent, whose kernel started and waited for every
copy under a guard of its own, this read 4.99 / 3.95 / 3.63 / 3.55 ms.

``runs``: blocks of 16, a group of ``run`` table entries fetched by ONE
copy where they are neighbours ascending (``run`` 1, 2, 4, 8 and the
kernel's own rule, ``null``), over tables that are ``ascending`` (every
group a run), ``shuffled`` (none) and ``seams`` (ascending stretches of
40-200 blocks, what a LIFO pool's second generation of tables is made
of). As PR 56 left the kernel, the rule's ``run`` (4) read 3.71 / 3.97 /
5.44 ms on the three.

Beside each time stand the copies the calls issue, the bytes' time at
the chip's 819 GB/s, and ``same``: whether the sums are, to the bit,
those of the first row over the same tables. One JSON line a row on
stdout, the table again under ``chiprun_out/latent_fetch_sweep.json``.
Nothing reads that file: PERF.md section 6 (PR 56) keeps the table the
rule was written from.
"""

from __future__ import annotations

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
SLOTS, HEADS, ROW, OUT_WIDTH = 128, 64, 640, 512
TABLE_TOKENS, LAYERS, CHUNK = 4096, 6, 512
KERNEL = "latent_attention_decode"


def cached_lengths(rng, slots: int):
    """What the slots of the reasoning mix hold at a random moment: a
    request is caught with a chance that grows with its answer."""
    import numpy as np

    prompt = rng.integers(512, 1025, size=slots)
    answer = np.empty(slots, np.int64)
    for i in range(slots):
        while True:
            a = int(rng.integers(1024, 3073))
            if rng.random() * 3072 < a:
                break
        answer[i] = a
    return (prompt + rng.random(slots) * answer).astype(np.int32)


def make_tables(rng, order: str, slots: int, table: int):
    """(slots, table) block ids from 1 up, each once."""
    import numpy as np

    ids = np.arange(1, slots * table + 1, dtype=np.int32)
    if order == "shuffled":
        ids = rng.permutation(ids)
    elif order == "seams":
        cuts, at = [], 0
        while at < ids.size:
            step = int(rng.integers(40, 201))
            cuts.append(ids[at:at + step])
            at += step
        ids = np.concatenate([cuts[i] for i in rng.permutation(len(cuts))])
    elif order != "ascending":
        raise ValueError(order)
    return ids.reshape(slots, table)


def copies_a_call(tables, lens, block: int, run: int) -> int:
    """The copies one call issues: one a whole group of ``run`` live
    entries that are neighbours ascending, else one a live entry."""
    from flexflow_tpu.serving.kv_cache import run_groups

    total = 0
    for row, length in zip(tables, lens):
        live = min((int(length) + block) // block, row.size)
        whole, as_one = run_groups(row[:live], run)
        total += as_one + live - run * as_one
    return total


def main(argv) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.default_backend() != "tpu":
        print(f"latent_fetch_sweep: the backend is {jax.default_backend()}, "
              f"not a TPU", file=sys.stderr)
        return 2
    from flexflow_tpu.kernels import latent_attention as la
    from tools.paged_chunk_sweep import kernel_device_us

    parts = argv or ["blocks", "runs"]
    plan = []
    if "blocks" in parts:
        plan += [(block, "shuffled", 1) for block in (16, 32, 64, 128)]
    if "runs" in parts:
        plan += [(16, order, run)
                 for order in ("ascending", "seams", "shuffled")
                 for run in (1, 2, 4, 8, None)]
    want = {}
    rng = np.random.default_rng(56)
    lens = cached_lengths(rng, SLOTS)
    lens_d = jnp.asarray(lens)
    live_tokens = int(lens.sum()) + SLOTS               # the step's own row
    bytes_us = 1e6 * live_tokens * ROW * 2 / HBM_BYTES_PER_S
    q = jax.random.normal(jax.random.PRNGKey(56), (SLOTS, HEADS, ROW),
                          jnp.bfloat16)
    rows, arenas, arenas_block = [], None, None
    for block, order, run in plan:
        table = TABLE_TOKENS // block
        nb = SLOTS * table + 1
        if arenas_block != block:
            arenas = None                               # free the last cut
            arenas = [jax.random.normal(key, (nb, block, ROW), jnp.bfloat16)
                      for key in jax.random.split(jax.random.PRNGKey(block),
                                                  LAYERS)]
            arenas_block = block
        tables = make_tables(np.random.default_rng(56), order, SLOTS, table)
        run_got = run or la.run_blocks(arenas[0].shape, arenas[0].dtype,
                                       table)

        def layers(q, arenas, tables, lens, run=run, block=block):
            out = None
            for arena in arenas:
                out = la.latent_attention_decode(
                    q, arena, tables, lens, scale=ROW ** -0.5,
                    out_width=OUT_WIDTH, pages_per_chunk=CHUNK // block,
                    blocks_per_run=run)
                q = q + jnp.pad(1e-3 * out, ((0, 0), (0, 0),
                                             (0, ROW - OUT_WIDTH))
                                ).astype(q.dtype)
            return out
        fn = jax.jit(layers)
        args = (q, arenas, jnp.asarray(tables), lens_d)
        first = np.asarray(jax.block_until_ready(fn(*args)))
        same = bool(np.array_equal(first, want.setdefault((block, order),
                                                          first)))
        t = time.perf_counter()
        for _ in range(REPEATS):
            last = fn(*args)
        jax.block_until_ready(last)
        wall_us = 1e6 * (time.perf_counter() - t) / REPEATS
        with tempfile.TemporaryDirectory() as tmp:
            with jax.profiler.trace(tmp):
                jax.block_until_ready(fn(*args))
            dev = kernel_device_us(tmp, KERNEL)
        device_us = float(np.sum(dev)) if len(dev) == LAYERS else None
        copies = LAYERS * copies_a_call(tables, lens, block, run_got)
        row = {
            "block": block, "tables": order, "run": run, "run_got": run_got,
            "same": same,
            "copy_bytes": run_got * block * ROW * 2,
            "copies": copies, "live_tokens": live_tokens,
            "bytes_us": round(LAYERS * bytes_us, 1),
            "wall_us": round(wall_us, 1),
            "device_us": device_us and round(device_us, 1),
            "device_events": len(dev),
            "share_of_bytes_time": round(
                100 * LAYERS * bytes_us / (device_us or wall_us), 1)}
        rows.append(row)
        print(json.dumps(row), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "latent_fetch_sweep.json"),
              "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
