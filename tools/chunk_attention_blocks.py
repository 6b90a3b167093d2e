"""A chunk's attention at the mixed-lengths cell's shapes, the kernel's
tile sizes side by side with the span walk: the table behind
``kernels/chunk_attention.py`` ``BLOCKS_Q`` / ``BLOCK_K``.

    chiprun -- python tools/chunk_attention_blocks.py

On the chip only (it exits 2 anywhere else: a CPU timing is no speed).
One chunk of 2,048 queries, 48 heads on 8 key-value heads of 128,
bfloat16, in the cases a Trinity-Large chunk program meets: a windowed
layer's ``[ring | chunk]`` (6,144 rows, window 4,096) with the ring full
at an offset of 8,192, and empty (a first chunk); the full layer's table
(17,408 rows) with the prompt at 2,048, 8,192 and 16,384 tokens. For
each it times the walk (``cache_entry._attend_spans``, spans of 512, as
the chunk programs call it) and the kernel at each ``(block_q, block_k)``
of ``TILES``, ``REPEATS`` calls dispatched one behind another and waited
for once (the device's pace), and gives the kernel's largest difference
from the walk over the walk's range, the tiles it visits and the share of
them an edge crosses. One JSON line a row on stdout, the table again
under ``chiprun_out/chunk_attention_blocks.json``. Nothing reads that
file: the block sizes are edited by hand from it, and PERF.md section 6
(PR 44) keeps the table they were edited from.
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

REPEATS = 10
HEADS, KV_HEADS, HEAD_DIM, CHUNK, WINDOW, TABLE = 48, 8, 128, 2048, 4096, 17408
TILES = ((512, 512), (256, 512), (512, 256), (256, 256), (1024, 512),
         (512, 1024), (256, 1024))
# name: (window, key rows, the chunk's offset)
CASES = {"window_full_ring": (WINDOW, WINDOW + CHUNK, 8192),
         "window_first_chunk": (WINDOW, WINDOW + CHUNK, 0),
         "full_at_2048": (None, TABLE, 0),
         "full_at_8192": (None, TABLE, 6144),
         "full_at_16384": (None, TABLE, 14336)}


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.default_backend() != "tpu":
        print(f"chunk_attention_blocks: the backend is "
              f"{jax.default_backend()}, not a TPU", file=sys.stderr)
        return 2
    from flexflow_tpu.kernels import chunk_attention as ca
    from flexflow_tpu.serving import cache_entry as ce

    class Op:
        scale = HEAD_DIM ** -0.5

        def __init__(self, window):
            self.window = window

        def sees(self, qpos, kpos):
            seen = kpos <= qpos
            if self.window:
                seen &= qpos - kpos < self.window
            return seen

    def timed(fn, *args):
        out = jax.block_until_ready(fn(*args))
        t = time.perf_counter()
        for _ in range(REPEATS):
            last = fn(*args)
        jax.block_until_ready(last)
        return out, 1e3 * (time.perf_counter() - t) / REPEATS

    rng = np.random.default_rng(0)
    rows = []
    for name, (window, keys, offset) in CASES.items():
        op = Op(window)
        q = jnp.asarray(rng.normal(size=(1, CHUNK, HEADS * HEAD_DIM)),
                        jnp.bfloat16)
        k, v = (jnp.asarray(rng.normal(size=(1, keys, KV_HEADS * HEAD_DIM)),
                            jnp.bfloat16) for _ in range(2))
        qpos = offset + jnp.arange(CHUNK, dtype=jnp.int32)[None]
        if window:
            before = offset - 1
            held = before - np.mod(before - np.arange(window), window)
            kpos = np.concatenate([np.where(held >= 0, held, ca.NOWHERE),
                                   np.asarray(qpos[0])])[None]
        else:
            at = np.arange(keys)
            kpos = np.where(at < offset + CHUNK, at, ca.NOWHERE)[None]
        kpos = jnp.asarray(kpos, jnp.int32)
        span = ce.SPAN_TOKENS

        @jax.jit
        def walk(q, qpos, k, v, kpos):
            k4, v4 = (a.reshape(1, keys, KV_HEADS, HEAD_DIM) for a in (k, v))

            def read(j):
                return tuple(jax.lax.dynamic_slice_in_dim(a, j * span, span, 1)
                             for a in (k4, v4, kpos))

            live = jnp.max(jnp.where(kpos == ca.NOWHERE, -1,
                                     jnp.arange(keys)[None])) + 1
            lo = jnp.where(kpos[0, 0] == ca.NOWHERE, window or 0, 0) // span
            return ce._attend_spans(
                op, q.reshape(1, CHUNK, HEADS, HEAD_DIM), qpos, KV_HEADS,
                read, lo, (live + span - 1) // span).reshape(q.shape)

        want, walk_ms = timed(walk, q, qpos, k, v, kpos)
        want = np.asarray(want, np.float32)
        for block_q, block_k in TILES:
            def kernel(q, qpos, k, v, kpos):
                return ca.chunk_attention(
                    q, qpos, k, v, kpos, kv_heads=KV_HEADS, scale=op.scale,
                    window=window, block_q=block_q, block_k=block_k)

            got, ms = timed(jax.jit(kernel), q, qpos, k, v, kpos)
            kinds = np.asarray(ca.block_table(qpos, kpos, window, block_q,
                                              block_k)[1])
            rows.append({
                "case": name, "block_q": block_q, "block_k": block_k,
                "walk_ms": walk_ms, "kernel_ms": ms,
                "tiles_visited": int((kinds != ca.SKIP).sum()),
                "tiles": int(kinds.size),
                "edge_share": float((kinds == ca.EDGE).sum()
                                    / max((kinds != ca.SKIP).sum(), 1)),
                "of_range": float(np.abs(np.asarray(got, np.float32)
                                         - want).max() / np.abs(want).max())})
            print(json.dumps(rows[-1]), flush=True)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "chunk_attention_blocks.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
