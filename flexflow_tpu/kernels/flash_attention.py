"""Fused (flash-style) attention as a Pallas TPU kernel.

Replaces the reference's cuDNN MultiHeadAttn device path
(reference: src/ops/attention.cu:35-128) with a TPU kernel that tiles
queries into ``block_q`` rows, holds K/V for one (batch, head) in VMEM, and
computes softmax(QKᵀ)V per tile without ever writing the (S, S) logits to
HBM. The backward pass is the standard two-kernel flash recomputation
(dq over q-tiles; dk/dv over k-tiles) using the saved log-sum-exp.

Layout: public entry takes (B, S, H, D) — the framework's bshd convention
(ops/attention.py) — and transposes to (B*H, S, D) for the kernel grid.
Compute is float32 on the MXU regardless of input dtype; outputs are cast
back.

VMEM: each kernel holds one whole (S, D) panel per full operand plus
(block, S) float32 logits temporaries; :func:`_vmem_bytes` counts that
working set the way the compiler allocates it (pipeline double buffers,
float32 copies, lane padding) and :func:`supported` refuses what does not
fit, so longer sequences take the jnp path or ring attention
(parallel/ring_attention.py) instead of failing in Mosaic.

The log-sum-exp travels as (B*H, S, 1): a (block_q, 1) column is what
the row reductions produce and what the backward broadcasts against, so
no kernel moves data between sublanes and lanes, and a block's last dim
is the array's full last dim whatever ``block_q`` is.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_mode

NEG_INF = -1e30  # large-negative instead of -inf: keeps exp()/max() NaN-free

# The scoped-VMEM limit handed to Mosaic for these kernels (a v5e core
# has 128 MiB; the compiler's default scope is 16 MiB), and the share of
# it supported() lets the counted working set take — the rest is room
# for what the count cannot see (compiler temporaries, relayouts).
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
VMEM_BUDGET_BYTES = 48 * 1024 * 1024
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _causal_mask(block_q: int, skv: int, q_offset):
    qpos = q_offset + jax.lax.broadcasted_iota(jnp.int32, (block_q, skv), 0)
    kpos = jax.lax.broadcasted_iota(jnp.int32, (block_q, skv), 1)
    return qpos >= kpos


# dot_general dimension numbers for a @ b.T and a.T @ b: the MXU takes
# either operand transposed, so no kernel materializes a transpose
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal, block_q):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale           # (block_q, D)
    k = k_ref[0].astype(jnp.float32)                   # (Skv, D)
    v = v_ref[0].astype(jnp.float32)
    s = _dot(q, k, _NT)                                # (block_q, Skv)
    if causal:
        s = jnp.where(_causal_mask(block_q, k.shape[0], qi * block_q), s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o_ref[0] = (_dot(p, v) / l).astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(l)                        # (block_q, 1)


def _dq_kernel(q_ref, k_ref, v_ref, o_ref, g_ref, lse_ref, dq_ref,
               *, scale, causal, block_q):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    g = g_ref[0].astype(jnp.float32)
    o = o_ref[0].astype(jnp.float32)
    s = _dot(q, k, _NT)
    if causal:
        s = jnp.where(_causal_mask(block_q, k.shape[0], qi * block_q), s, NEG_INF)
    p = jnp.exp(s - lse_ref[0])                         # softmax probabilities
    dp = _dot(g, v, _NT)
    delta = jnp.sum(g * o, axis=-1, keepdims=True)      # rowsum(dO ∘ O)
    ds = p * (dp - delta)
    dq_ref[0] = (_dot(ds, k) * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, o_ref, g_ref, lse_ref, dk_ref, dv_ref,
                *, scale, causal, block_k):
    ki = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale            # (Sq, D)
    k = k_ref[0].astype(jnp.float32)                    # (block_k, D)
    v = v_ref[0].astype(jnp.float32)
    g = g_ref[0].astype(jnp.float32)
    o = o_ref[0].astype(jnp.float32)
    s = _dot(q, k, _NT)                                 # (Sq, block_k)
    if causal:
        sq = q.shape[0]
        qpos = jax.lax.broadcasted_iota(jnp.int32, (sq, block_k), 0)
        kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (sq, block_k), 1)
        s = jnp.where(qpos >= kpos, s, NEG_INF)
    p = jnp.exp(s - lse_ref[0])                         # lse: (Sq, 1)
    dv_ref[0] = _dot(p, g, _TN).astype(dv_ref.dtype)
    dp = _dot(g, v, _NT)
    delta = jnp.sum(g * o, axis=-1, keepdims=True)
    ds = p * (dp - delta)
    dk_ref[0] = _dot(ds, q, _TN).astype(dk_ref.dtype)   # q already carries `scale`


def _pick_block(s: int, pref: int) -> Optional[int]:
    for b in (pref, 256, 128, 64, 32, 16, 8):
        if b <= s and s % b == 0:
            return b
    return None


# -- block-size tuning --------------------------------------------------------
# Round-2 measurement on a real v5e showed the default tile a hair SLOWER
# than XLA's fused attention at the bench shape; the right block_q depends
# on seq/head_dim and the chip. Resolution order: the FLEXFLOW_FA_BLOCK_Q
# env override, then a per-shape autotune cache (populated by autotune(),
# persisted to FLEXFLOW_FA_TUNE_CACHE if set), then 128.
_TUNE_CACHE: dict = {}
_CACHE_FILE_LOADED: Optional[str] = None  # path last loaded successfully


def _ensure_cache_loaded() -> None:
    """Load FLEXFLOW_FA_TUNE_CACHE into the process cache once per path:
    a missing file retries (it may appear later), a present-but-bad file
    does not (one parse attempt, not one per attention call). A path
    CHANGE drops the previous file's winners first — they were tuned for
    something else."""
    import os

    global _CACHE_FILE_LOADED
    path = os.environ.get("FLEXFLOW_FA_TUNE_CACHE")
    if path and _CACHE_FILE_LOADED != path and os.path.exists(path):
        _TUNE_CACHE.clear()
        try:
            load_tune_cache(path)
        except (OSError, ValueError):
            pass
        _CACHE_FILE_LOADED = path


def tune_entry(sq: int, skv: int, d: int,
               causal: bool = False) -> Optional[dict]:
    """Public accessor for one tune-cache record
    (``{"block_q": int, "xla_ratio": float|None}``), loading the
    persisted cache first. The key/entry format is private to this
    module — consumers (bench.py) must come through here."""
    _ensure_cache_loaded()
    return _TUNE_CACHE.get((sq, skv, d, bool(causal)))


def default_block_q(sq: int, skv: int, d: int,
                    causal: bool = False) -> int:
    import os

    env = os.environ.get("FLEXFLOW_FA_BLOCK_Q")
    if env:
        try:
            v = int(env)
        except ValueError as e:
            raise ValueError(
                f"FLEXFLOW_FA_BLOCK_Q={env!r} is not an integer") from e
        if v < 8 or v % 8 != 0:
            raise ValueError(
                f"FLEXFLOW_FA_BLOCK_Q={v} must be a positive multiple of 8")
        return v
    entry = tune_entry(sq, skv, d, causal)
    return entry["block_q"] if entry else 128


def proven(sq: int, skv: int, d: int, causal: bool = False) -> bool:
    """True iff a recorded autotune shows the kernel MATCHING OR BEATING
    XLA's fused attention at this shape (``xla_ratio >= 1.0``)."""
    entry = tune_entry(sq, skv, d, causal)
    return bool(entry) and (entry.get("xla_ratio") or 0.0) >= 1.0


def engaged(sq: int, skv: int, d: int, causal: bool = False) -> bool:
    """Dispatch policy for the flash kernel (win-or-off, round 5): the
    only measured comparison (round 2, real v5e) had the kernel at 0.98x
    vs XLA's fused attention — losing to the thing it exists to beat —
    so on the default ``auto`` setting the kernel engages ONLY at shapes
    where a recorded autotune proves a >=1.0x ratio (``proven``).
    ``FLEXFLOW_TPU_PALLAS=compiled`` forces it on everywhere (autotune /
    benchmarking); ``interpret`` keeps engaging it for numerics tests;
    ``off`` wins over everything. Rationale: PARITY.md §flash-attention."""
    from . import pallas_forced

    mode = pallas_mode()
    if mode is None:
        return False
    if mode == "interpret":
        return True
    if pallas_forced():
        return True  # explicitly forced, not auto-on-TPU
    return proven(sq, skv, d, causal)


def autotune(shape=(4, 512, 8, 64), candidates=(64, 128, 256, 512),
             causal: bool = False, iters: int = 10,
             cache_path: Optional[str] = None) -> dict:
    """Time the forward kernel per candidate block_q on the CURRENT
    backend and remember the winner for this (seq, seq, head_dim).

    Run once on real hardware (tests_tpu/ has a gated smoke); results are
    process-cached and optionally persisted as JSON. Returns
    {block_q: seconds} for inspection."""
    import json
    import os
    import time

    import numpy as np

    from . import pallas_forced

    b, s, h, d = shape
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b * h, s, d)).astype(np.float32))
    interpret = pallas_mode() == "interpret"
    results = {}
    for cand in candidates:
        bq = _pick_block(s, cand)
        if bq != cand:
            continue  # shape can't tile at this size
        # VMEM gate shared with supported(): don't let one oversized
        # candidate's Mosaic failure discard the other timings
        if _vmem_bytes(s, s, cand, cand, d) > VMEM_BUDGET_BYTES:
            continue
        fn = jax.jit(functools.partial(
            _flash, causal=causal, scale=d ** -0.5, block_q=cand,
            interpret=interpret))
        try:
            out = fn(q, q, q)
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            for _ in range(iters):
                out = fn(q, q, q)
            jax.block_until_ready(out)
        except Exception:  # compile/alloc failure: skip this candidate
            if pallas_forced():
                raise  # forced Mosaic: a refusal is the finding, not a skip
            continue
        results[cand] = (time.perf_counter() - t0) / iters
    if results:
        best = min(results, key=results.get)
        # time XLA's own fused attention at the same shape: the engage
        # policy (``engaged``) only turns the kernel on where this ratio
        # proves a win (>= 1.0). This measurement DECIDES dispatch, so
        # both sides use the median of 3 windows — a single transient
        # stall must not persist a wrong on/off decision into the cache
        xla_ratio = None
        scale = d ** -0.5

        def _median_time(fn, arg) -> float:
            out = fn(arg, arg, arg)
            jax.block_until_ready(out)  # warmup/compile
            windows = []
            for _ in range(3):
                t0 = time.perf_counter()
                for _ in range(iters):
                    out = fn(arg, arg, arg)
                jax.block_until_ready(out)
                windows.append((time.perf_counter() - t0) / iters)
            return sorted(windows)[1]

        try:
            # the baseline is the EXACT implementation dispatch falls
            # back to when the kernel is off (ops/attention.py →
            # single_device_attention), on its own (b, s, h, d) layout —
            # not a re-derivation that XLA might compile differently.
            # BOTH sides time the full (B, S, H, D) entry: the kernel
            # side goes through the public flash_attention so the
            # bshd↔(B*H,S,D) transposes the production dispatch pays are
            # inside the measured ratio — a kernel that wins only on the
            # pre-transposed layout must not record a >=1.0 and engage
            from ..parallel.ring_attention import single_device_attention

            q4 = jnp.asarray(np.random.default_rng(0).normal(
                size=(b, s, h, d)).astype(np.float32))
            best_fn = jax.jit(functools.partial(
                flash_attention, causal=causal, scale=scale,
                block_q=best))
            t_kernel = _median_time(best_fn, q4)
            ref_fn = jax.jit(lambda q_, k_, v_: single_device_attention(
                q_, k_, v_, causal, scale))
            t_xla = _median_time(ref_fn, q4)
            xla_ratio = round(t_xla / t_kernel, 4)
        except Exception:
            if pallas_forced():
                raise
        _TUNE_CACHE[(s, s, d, bool(causal))] = {
            "block_q": best, "xla_ratio": xla_ratio}
        path = cache_path or os.environ.get("FLEXFLOW_FA_TUNE_CACHE")
        # multi-host: only process 0 persists (all processes tuned the
        # same shapes); write-temp + os.replace keeps readers from ever
        # seeing a truncated file
        if path and jax.process_index() == 0:
            try:
                import fcntl

                # lock the read-merge-replace so two processes tuning
                # different shapes can't lose each other's entries
                # (same pattern as native_bridge._build)
                with open(f"{path}.lock", "w") as lk:
                    fcntl.flock(lk, fcntl.LOCK_EX)
                    data = {}
                    if os.path.exists(path):
                        with open(path) as f:
                            data = json.load(f)
                    data[f"{s}x{s}x{d}x{int(bool(causal))}"] = {
                        "block_q": best, "xla_ratio": xla_ratio}
                    tmp = f"{path}.tmp.{os.getpid()}"
                    with open(tmp, "w") as f:
                        json.dump(data, f)
                    os.replace(tmp, path)
            except (OSError, ValueError):  # incl. a corrupt existing file
                pass
    return results


def load_tune_cache(path: str) -> int:
    """Load a persisted autotune cache; returns entries loaded."""
    import json

    with open(path) as f:
        data = json.load(f)
    n = 0
    for k, v in data.items():
        parts = [int(x) for x in k.split("x")]
        if len(parts) == 3:  # pre-causal-key format
            parts.append(0)
        s1, s2, d, c = parts
        if isinstance(v, dict):
            entry = {"block_q": int(v["block_q"]),
                     "xla_ratio": v.get("xla_ratio")}
        else:  # legacy bare-int format: block size only, no win evidence
            entry = {"block_q": int(v), "xla_ratio": None}
        _TUNE_CACHE[(s1, s2, d, bool(c))] = entry
        n += 1
    return n


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, scale, block_q, interpret):
    out, _ = _flash_fwd(q, k, v, causal, scale, block_q, interpret)
    return out


def _flash_fwd(q, k, v, causal, scale, block_q, interpret):
    bh, sq, d = q.shape
    skv = k.shape[1]
    grid = (bh, sq // block_q)
    qspec = pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0))
    kvspec = pl.BlockSpec((1, skv, d), lambda b, i: (b, 0, 0))
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal, block_q=block_q),
        grid=grid,
        in_specs=[qspec, kvspec, kvspec],
        out_specs=[qspec, pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, scale, block_q, interpret, res, g):
    q, k, v, out, lse = res
    bh, sq, d = q.shape
    skv = k.shape[1]
    block_k = _pick_block(skv, block_q)
    qspec = pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0))
    kvfull = pl.BlockSpec((1, skv, d), lambda b, i: (b, 0, 0))
    lspec = pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal, block_q=block_q),
        grid=(bh, sq // block_q),
        in_specs=[qspec, kvfull, kvfull, qspec, qspec, lspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="flash_attention_dq",
    )(q, k, v, out, g, lse)
    qfull = pl.BlockSpec((1, sq, d), lambda b, i: (b, 0, 0))
    kspec = pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0))
    lfull = pl.BlockSpec((1, sq, 1), lambda b, i: (b, 0, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal, block_k=block_k),
        grid=(bh, skv // block_k),
        in_specs=[qfull, kspec, kspec, qfull, qfull, lfull],
        out_specs=[kspec, kspec],
        out_shape=[
            jax.ShapeDtypeStruct((bh, skv, d), k.dtype),
            jax.ShapeDtypeStruct((bh, skv, d), v.dtype),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="flash_attention_dkv",
    )(q, k, v, out, g, lse)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def _vmem_bytes(sq: int, skv: int, block_q: int, block_k: int, d: int) -> int:
    """Largest VMEM working set of the three kernels for float32 inputs
    (the widest), counted the way it is allocated: every input and output
    block twice (the Pallas pipeline double-buffers them), a float32 copy
    of every loaded operand, the (rows, cols) float32 temporaries of the
    softmax recomputation (s, p, and in the backward dp, ds), all with
    the last dim padded to 128 lanes — so one log-sum-exp element costs a
    whole 512-byte lane row. Shared by supported() and autotune()."""
    def lanes(n):
        return -(-n // 128) * 128

    dd = lanes(d)
    lse_row = 4 * 128
    fwd = (2 * 4 * (2 * block_q + 2 * skv) * dd            # q, o; k, v panels
           + 2 * block_q * lse_row
           + 4 * (block_q + 2 * skv) * dd                  # f32 q, k, v
           + 4 * 3 * block_q * lanes(skv))                 # s, p, mask
    dq = (2 * 4 * (4 * block_q + 2 * skv) * dd             # q, o, g, dq; k, v
          + 2 * block_q * lse_row
          + 4 * (3 * block_q + 2 * skv) * dd
          + 4 * 5 * block_q * lanes(skv))                  # s, p, dp, ds, mask
    dkv = (2 * 4 * (3 * sq + 4 * block_k) * dd             # q, o, g; k, v, dk, dv
           + 2 * sq * lse_row
           + 4 * (3 * sq + 2 * block_k) * dd
           + 4 * 5 * sq * lanes(block_k))
    return max(fwd, dq, dkv)


def supported(q_shape, k_shape, causal: bool = False) -> bool:
    """Whether the kernel path handles these (B, S, H, D) shapes.

    Checks that the sequence lengths tile (blocks are multiples of 16
    rows — the bf16 sublane tile — or the whole sequence) and that the
    working set :func:`_vmem_bytes` counts fits the budget; longer
    sequences fall back to the
    jnp path / ring attention rather than failing at Mosaic compile.
    Budgets with the SAME block the kernel will resolve (env/tuned/128) —
    a tuned 512 tile must not pass a gate computed for 128.
    """
    if pallas_mode() is None:
        return False
    sq, skv = q_shape[1], k_shape[1]
    d = q_shape[3]
    try:
        pref = default_block_q(sq, skv, d, causal)
    except ValueError:
        return False  # malformed env override: fall back to the jnp path
    bq = _pick_block(sq, pref)
    bk = _pick_block(skv, pref)
    if bq is None or bk is None:
        return False
    if (bq % 16 and bq != sq) or (bk % 16 and bk != skv):
        return False
    return _vmem_bytes(sq, skv, bq, bk, d) <= VMEM_BUDGET_BYTES


def sharded_supported(q_shape, k_shape, mesh, batch_axis, heads_axis,
                      causal: bool = False) -> bool:
    """Whether the shard_map-wrapped kernel handles these GLOBAL (B,S,H,D)
    shapes on this mesh: batch/heads must divide by their axis sizes and
    the per-shard block must satisfy :func:`supported`."""
    from ..core.machine import mesh_axis_sizes

    sizes = mesh_axis_sizes(mesh)
    ddeg = sizes.get(batch_axis, 1) if batch_axis else 1
    hdeg = sizes.get(heads_axis, 1) if heads_axis else 1
    b, sq, h, d = q_shape
    if b % ddeg or h % hdeg:
        return False
    lq = (b // ddeg, sq, h // hdeg, d)
    lk = (k_shape[0] // ddeg, k_shape[1], k_shape[2] // hdeg, d)
    return supported(lq, lk, causal)


def sharded_flash_attention(q, k, v, mesh, batch_axis, heads_axis,
                            causal: bool = False,
                            scale: Optional[float] = None,
                            block_q: Optional[int] = None) -> jax.Array:
    """Flash attention composed with SPMD sharding via shard_map.

    Attention is independent across batch and heads, so each device runs
    the single-core kernel on its (B/dp, S, H/tp, D) block — this is what
    lets the Pallas path engage on dp x tp meshes instead of falling back
    to the jnp einsums (the reference's cuDNN path is likewise per-GPU
    under its MachineView — src/ops/attention.cu). Sequence-sharded
    attention goes through parallel/ring_attention.py instead.
    """
    from jax.sharding import PartitionSpec

    spec = PartitionSpec(batch_axis, None, heads_axis, None)
    fn = functools.partial(flash_attention, causal=causal, scale=scale,
                           block_q=block_q)
    return shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)


def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None) -> jax.Array:
    """Fused attention. q/k/v: (B, S, H, D) (framework bshd convention).

    Differentiable (custom VJP). Caller is responsible for checking
    :func:`supported` and falling back to
    ``parallel.ring_attention.single_device_attention`` otherwise (e.g.
    with attention dropout, which this kernel does not implement).
    """
    b, sq, h, d = q.shape
    skv = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    if block_q is None:
        block_q = default_block_q(sq, skv, d, causal)
    bq = _pick_block(sq, block_q)
    if bq is None or _pick_block(skv, block_q) is None:
        raise ValueError(
            f"flash_attention: seq lengths ({sq}, {skv}) have no valid "
            f"block size (must be divisible by 8); check supported() and "
            f"fall back to single_device_attention"
        )
    interpret = pallas_mode() == "interpret"
    # (B, S, H, D) -> (B*H, S, D)
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * h, skv, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * h, skv, d)
    ot = _flash(qt, kt, vt, causal, scale, bq, interpret)
    return ot.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
