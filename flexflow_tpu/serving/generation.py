"""Autoregressive generation with KV caches over a compiled model graph.

No reference analog (the reference predates LLM serving; its triton/
prototype served batch CNN inference) — this is the modern-completeness
piece on top of the serving engine. TPU-native design:

* the decode step is ONE jitted function per block length (prefill length
  and 1), produced by walking the compiled model's op graph — every op
  runs its ordinary shape-polymorphic ``forward`` on the (B, S_blk, ·)
  activations EXCEPT self-attention, which reads/writes a static-shape
  KV cache via ``lax.dynamic_update_slice`` (XLA-friendly: no growing
  shapes, position masking instead of shape change);
* the cache is a pytree {attention op name: (k, v)} of
  (B, max_length, H, D) arrays, donated through the decode step so XLA
  updates it in place;
* sampling (greedy / temperature) happens on host between steps, like
  every production TPU decode loop.

Two cache layouts share the graph walk:

* :class:`Generator` — the dense rectangle: ``(B, max_length, H, D)``
  per op, one fixed batch decoded in lockstep (offline/batch use, and
  the bit-compared reference for the paged path);
* :class:`PagedDecoder` — the continuous-batching layout: a
  :class:`~flexflow_tpu.serving.kv_cache.PagedKVPool` of
  ``(num_blocks, block_size, H*D)`` arenas plus per-request block
  tables. Decode attention reads K/V **through the block table**: in
  place, by the paged-attention kernel (kernels/paged_attention.py),
  where its ``supported()`` admits the entry, and otherwise by a gather
  of each slot's table (the jnp path: int8 entries, widths Mosaic
  refuses, the CPU). The compiled decode program's shape depends only
  on (decode slots, pool geometry), so one program serves every
  in-flight request mix, and prompts run through a separate **bucketed
  prefill executable** (pad-to-bucket ladder, per-bucket compile cached
  and counted) whose K/V is scattered into the pool in the same
  dispatch.

The two layouts compute the same sums per request
(tests/test_continuous_batching.py holds them to float32 reordering
per zoo causal-LM model): the paged read reconstructs exactly the dense
cache rows for written positions, and every unwritten/foreign lane is
masked to -1e30 before softmax, where ``exp`` underflows to exactly
0.0 — adding exact zeros never perturbs the valid lanes' accumulation.

Works for any builder graph whose attention ops are causal
self-attention (models/gpt.py; an imported HF decoder fits the same
contract) or latent attention (models/latent_moe.py): a latent op's
cache is ONE row a token, ``[c | k_rope]`` — a 1-tuple entry in either
layout — which prefill attends in the expanded form and the paged decode
step in the absorbed one (:func:`_latent_attn_paged`; in place by
kernels/latent_attention.py where its ``supported()`` admits the entry).
Routed-experts ops run inside the same programs; the paged ones keep the
expert ids they chose (``PagedDecoder.last_routing``) and count their
load on the device (``PagedDecoder.expert_stats``).
"""

from __future__ import annotations

import math
import threading
import weakref
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from ..ffconst import OpType
from ..core.op import LowerCtx
from ..kernels import latent_attention, paged_attention
from ..obs.trace import span
from .kv_cache import NULL_BLOCK, PagedKVPool


def _attn_with_cache(op, weights, x, kcache, vcache, offset):
    """Causal self-attention over [cache ∪ current block].

    ``offset``: traced scalar — absolute position of the block's first
    token. Scores span the FULL static cache length; future/unwritten
    positions are masked by position comparison (static shapes, jit-safe).
    """
    qh = jnp.einsum("bse,ehd->bshd", x, weights["wq"])
    kh = jnp.einsum("bse,ehd->bshd", x, weights["wk"])
    vh = jnp.einsum("bse,ehd->bshd", x, weights["wv"])
    if op.use_bias:
        qh = qh + weights["bq"]
        kh = kh + weights["bk"]
        vh = vh + weights["bv"]
    kcache = jax.lax.dynamic_update_slice(kcache, kh, (0, offset, 0, 0))
    vcache = jax.lax.dynamic_update_slice(vcache, vh, (0, offset, 0, 0))
    scale = 1.0 / math.sqrt(op.head_dim)
    scores = jnp.einsum("bqhd,bkhd->bhqk", qh, kcache) * scale
    s_blk = x.shape[1]
    qpos = offset + jax.lax.iota(jnp.int32, s_blk)             # (S_blk,)
    kpos = jax.lax.iota(jnp.int32, kcache.shape[1])            # (max_len,)
    mask = kpos[None, :] <= qpos[:, None]                      # causal+written
    scores = jnp.where(mask[None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    ctxv = jnp.einsum("bhqk,bkhd->bqhd", probs, vcache)
    out = jnp.einsum("bqhd,hde->bqe", ctxv, weights["wo"])
    if op.use_bias:
        out = out + weights["bo"]
    return out, kcache, vcache


def _quant_rows(x):
    """Asymmetric int8 per-(token, head) quantization over head_dim.
    ``x``: (T, H, D) -> (q int8, scale f32 (T, H), zero f32 (T, H)).
    Zero-point at the range midpoint, scale spanning [-127, 127], so
    dequantization is ``q * scale + zero``."""
    x = x.astype(jnp.float32)
    hi = x.max(-1)
    lo = x.min(-1)
    zero = 0.5 * (hi + lo)
    scale = jnp.maximum((hi - lo) / 254.0, 1e-8)
    q = jnp.clip(jnp.round((x - zero[..., None]) / scale[..., None]),
                 -127, 127)
    return q.astype(jnp.int8), scale, zero


def _entry_write(entry, flat, kh, vh):
    """Scatter T new K/V rows (``kh``/``vh``: (T, H, D)) into a pool
    arena entry at flat token slots ``flat`` (T,), quantizing when the
    entry is an int8 6-tuple (values + scale/zero sidecars share the
    same flat addressing). An arena is ``(num_blocks, block_size,
    H*D)``, so a token is one row of its ``(num_blocks*block_size,
    H*D)`` view — a reshape that moves nothing under the TPU's tiling —
    and the scatter updates the donated buffer in place. Returns the
    updated entry."""
    t = kh.shape[0]

    def put(arena, rows):
        nb, bs = arena.shape[:2]
        flat_arena = arena.reshape((nb * bs,) + arena.shape[2:])
        return flat_arena.at[flat].set(
            rows.astype(arena.dtype)).reshape(arena.shape)

    if len(entry) == 1:
        # a latent entry: ``kh`` is the (T, width) rows, padded with
        # zeros to the arena's whole lane tiles; ``vh`` is unused
        lanes = entry[0].shape[-1]
        return (put(entry[0], jnp.pad(kh, ((0, 0),
                                           (0, lanes - kh.shape[-1])))),)
    if len(entry) == 2:
        k, v = entry
        return (put(k, kh.reshape(t, -1)), put(v, vh.reshape(t, -1)))
    kq, vq, ks, kz, vs, vz = entry
    qk, sk, zk = _quant_rows(kh)
    qv, sv, zv = _quant_rows(vh)
    return (put(kq, qk.reshape(t, -1)), put(vq, qv.reshape(t, -1)),
            put(ks, sk), put(kz, zk), put(vs, sv), put(vz, zv))


def _entry_read(entry, tables, heads):
    """Gather each slot's logical (max_blocks*block_size, H, D) K/V
    view through its block table, dequantizing int8 entries to f32
    INSIDE the dispatch (the arena stays quantized; only the gathered
    working set pays the f32 width). The jnp path: what the paged-
    attention kernel is checked against, and what runs where the kernel
    does not (int8 entries, widths Mosaic refuses, the CPU)."""
    n = tables.shape[0]

    def view(arena):
        return arena[tables].reshape(n, -1, heads, arena.shape[-1] // heads)

    if len(entry) == 2:
        k, v = entry
        return view(k), view(v)
    kq, vq, ks, kz, vs, vz = entry
    k = (view(kq).astype(jnp.float32) * view(ks) + view(kz))
    v = (view(vq).astype(jnp.float32) * view(vs) + view(vz))
    return k, v


def _kernel_reads(entry, q_shape, max_blocks) -> bool:
    """Whether the paged-attention kernel reads this entry in place for
    a (slots, W, H, D) query: a ``(k, v)`` pair of a shape and dtype
    its ``supported()`` admits, on a backend where Pallas kernels run."""
    return len(entry) == 2 and paged_attention.supported(
        q_shape, entry[0].shape, entry[0].dtype, max_blocks)


def _attn_with_paged_cache(op, weights, x, entry, tables, seq_lens):
    """W-token causal self-attention through a paged KV pool.

    ``x``: (n, W, E) — W new tokens per decode slot at absolute
    positions ``seq_lens .. seq_lens + W - 1`` (W=1 is the plain decode
    step; W=k+1 is the speculative verify window). ``entry``: the pool
    arena entry for this op — (k, v) arenas, or the int8 6-tuple with
    scale/zero sidecars. ``tables``: (n, max_blocks) int32 per-slot
    block tables. ``seq_lens``: (n,) int32 — tokens already cached per
    slot, i.e. the window's first absolute position.

    Writes the W new K/V rows at each slot's positions (inactive slots,
    whose tables are all :data:`~flexflow_tpu.serving.kv_cache
    .NULL_BLOCK`, write into the null block — harmless by construction;
    positions past the table's span are redirected there too), then
    reads each slot's cache through its table — the kernel walks the
    slot's live blocks in the arena, the jnp path gathers its logical
    ``(max_blocks*block_size)`` view — and masks per query position
    exactly like the dense path, so window position j's output is the
    dense cache decode at absolute position ``seq_lens + j``: the
    window's own future K/V rows, stale rows after a speculative
    roll-back and the null block's garbage are masked to -1e30, where
    exp underflows to exact 0.0. Which reader runs is decided on what
    the trace can see (the entry's structure and dtype, W, the head and
    block sizes, the backend: :func:`_kernel_reads`).
    """
    qh = jnp.einsum("bse,ehd->bshd", x, weights["wq"])
    kh = jnp.einsum("bse,ehd->bshd", x, weights["wk"])
    vh = jnp.einsum("bse,ehd->bshd", x, weights["wv"])
    if op.use_bias:
        qh = qh + weights["bq"]
        kh = kh + weights["bk"]
        vh = vh + weights["bv"]
    bs = entry[0].shape[1]
    n, w, heads, hdim = qh.shape
    mb = tables.shape[1]
    pos = seq_lens[:, None] + jax.lax.iota(jnp.int32, w)[None, :]  # (n, W)
    blk = jnp.take_along_axis(tables, jnp.clip(pos // bs, 0, mb - 1),
                              axis=1)                           # (n, W)
    # positions past the table span (a verify window overrunning a
    # request's worst case) land in the null block, never a clamped
    # real block — by then the request has retired, so the rows are
    # write-only garbage like every other masked lane
    flat = jnp.where(pos < mb * bs, blk * bs + pos % bs,
                     NULL_BLOCK * bs)                           # (n, W)
    entry = _entry_write(entry, flat.reshape(-1),
                         kh.reshape(n * w, heads, hdim),
                         vh.reshape(n * w, heads, hdim))
    scale = 1.0 / math.sqrt(op.head_dim)
    if _kernel_reads(entry, qh.shape, mb):
        # the kernel walks each slot's live blocks in the arena itself
        ctxv = paged_attention.paged_attention_decode(
            qh, entry[0], entry[1], tables, seq_lens,
            scale=scale).astype(qh.dtype)
    else:
        # gather each slot's logical view: (n, MB, BS, HD) -> (n, L, H, D)
        k, v = _entry_read(entry, tables, heads)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qh, k) * scale   # (n,H,W,L)
        kpos = jax.lax.iota(jnp.int32, k.shape[1])              # (L,)
        mask = kpos[None, None, :] <= pos[:, :, None]           # (n, W, L)
        scores = jnp.where(mask[:, None, :, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        ctxv = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    out = jnp.einsum("bqhd,hde->bqe", ctxv, weights["wo"])
    if op.use_bias:
        out = out + weights["bo"]
    return out, entry


def _latent_kernel_reads(op, entry, slots: int, max_blocks: int) -> bool:
    """Whether the latent-attention kernel reads this 1-tuple entry in
    place for a one-token step of ``slots`` slots."""
    arena = entry[0]
    return latent_attention.supported(
        (slots, op.num_heads, arena.shape[-1]), arena.shape, arena.dtype,
        max_blocks, op.kv_rank)


def _latent_attn_with_cache(op, weights, x, positions, rows_cache, offset):
    """The dense-rectangle form of latent attention: the block's rows
    written at ``offset`` into a (B, max_length, width) cache, attention
    in the expanded form over the whole static length, masked by
    position (the sibling of :func:`_attn_with_cache`)."""
    q_nope, q_rope, rows = op.queries_and_rows(weights, x, positions)
    rows_cache = jax.lax.dynamic_update_slice(
        rows_cache, rows.astype(rows_cache.dtype), (0, offset, 0))
    qpos = offset + jax.lax.iota(jnp.int32, x.shape[1])
    kpos = jax.lax.iota(jnp.int32, rows_cache.shape[1])
    out = op.attend_expanded(weights, q_nope, q_rope,
                             rows_cache.astype(x.dtype),
                             kpos[None, :] <= qpos[:, None])
    return out, rows_cache


def _latent_attn_paged(op, weights, x, positions, entry, tables, seq_lens):
    """One new token a slot through a paged latent cache, in the
    absorbed form: ``x`` (n, 1, E) at positions ``seq_lens``. Writes the
    token's row ``[c | k_rope]`` at the slot's position (inactive slots
    into the null block), then attends the slot's cached rows through
    its table: per head the query over a row's lanes is ``q_nope`` folded
    through the key half of ``W_kvb`` beside ``q_rope``, the weighted sum
    of the rows' latent part is unfolded through the value half. The
    kernel reads the arena in place over live blocks only; the jnp path
    gathers each slot's logical view (its reference, and what runs where
    its ``supported()`` says no). Masked lanes are exact zeros, as in
    :func:`_attn_with_paged_cache`."""
    n, w, _ = x.shape
    if w != 1:
        raise ValueError(
            f"{op.name}: a latent cache entry takes one new token a slot "
            f"(speculative verify windows are not built for it), got {w}")
    arena = entry[0]
    bs, lanes = arena.shape[1], arena.shape[2]
    mb = tables.shape[1]
    q_nope, q_rope, rows = op.queries_and_rows(weights, x, positions)
    blk = jnp.take_along_axis(
        tables, jnp.clip(seq_lens[:, None] // bs, 0, mb - 1), axis=1)[:, 0]
    flat = jnp.where(seq_lens < mb * bs, blk * bs + seq_lens % bs,
                     NULL_BLOCK * bs)
    entry = _entry_write(entry, flat, rows[:, 0], None)
    arena = entry[0]
    wkvb = op.kvb_heads(weights)                      # (rank, H, nope + v)
    q_lat = jnp.einsum("nhd,chd->nhc", q_nope[:, 0], wkvb[..., :op.nope_dim],
                       preferred_element_type=jnp.float32)
    q_full = jnp.concatenate(
        [q_lat.astype(arena.dtype), q_rope[:, 0].astype(arena.dtype),
         jnp.zeros((n, op.num_heads, lanes - op.row_width), arena.dtype)],
        axis=-1)                                      # (n, H, lanes)
    if _latent_kernel_reads(op, entry, n, mb):
        with jax.named_scope("latent_attention_decode"):
            ctxv = latent_attention.latent_attention_decode(
                q_full, arena, tables, seq_lens, scale=op.scale,
                out_width=op.kv_rank)
    else:
        view = arena[tables].reshape(n, mb * bs, lanes)      # (n, L, lanes)
        scores = jnp.einsum("nhr,nlr->nhl", q_full, view,
                            preferred_element_type=jnp.float32) * op.scale
        kpos = jax.lax.iota(jnp.int32, mb * bs)
        scores = jnp.where((kpos[None, :] <= seq_lens[:, None])[:, None, :],
                           scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        ctxv = jnp.einsum("nhl,nlc->nhc", probs.astype(arena.dtype),
                          view[..., :op.kv_rank],
                          preferred_element_type=jnp.float32)
    o = jnp.einsum("nhc,chd->nhd", ctxv.astype(x.dtype),
                   wkvb[..., op.nope_dim:],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    out = jnp.dot(o.reshape(n, 1, op.num_heads * op.v_dim), weights["wo"],
                  preferred_element_type=jnp.float32).astype(x.dtype)
    return out, entry


def _latent_attn_prefill(op, weights, x, positions, entry, tables, lengths):
    """A group of prompts through latent attention in the expanded form
    (keys and values up-projected from the prompt's own rows, dense
    causal attention), the rows scattered into the pool through each
    prompt's block table with padding positions sent to the null block:
    the sibling of the attention closure of ``_prefill_step``."""
    b, s_blk, _ = x.shape
    bs = entry[0].shape[1]
    q_nope, q_rope, rows = op.queries_and_rows(weights, x, positions)
    pos = jax.lax.iota(jnp.int32, s_blk)
    with jax.named_scope("latent_attention_prefill"):
        out = op.attend_expanded(weights, q_nope, q_rope, rows,
                                 pos[None, :] <= pos[:, None])
    blk = tables[:, pos // bs]
    flat = jnp.where(pos[None, :] < lengths[:, None],
                     blk * bs + (pos % bs)[None, :], NULL_BLOCK * bs)
    entry = _entry_write(entry, flat.reshape(-1),
                         rows.reshape(b * s_blk, -1), None)
    return out, entry


def _expert_counts(op, ids, active):
    """What one decode step adds to an expert op's counters: ``[1, pairs
    routed, pairs held, held experts that got no row, rows of each held
    expert ...]`` over the active slots' tokens. ``ids`` (T, k),
    ``active`` (T,) bool."""
    hit = op.held_hits(ids) & active[:, None, None]
    rows = hit.sum((0, 1)).astype(jnp.uint32)                 # (count,)
    head = jnp.stack([jnp.uint32(1),
                      (active.sum() * ids.shape[1]).astype(jnp.uint32),
                      rows.sum(), (rows == 0).sum().astype(jnp.uint32)])
    return jnp.concatenate([head, rows])


def _count_up(acc, add):
    """``acc`` (2, n) uint32, low words over high words: a 64-bit count
    in two words, so that a server that never restarts does not wrap
    (1,024 pairs a step fill 32 bits in 4 M steps)."""
    low = acc[0] + add
    return jnp.stack([low, acc[1] + (low < acc[0]).astype(jnp.uint32)])


def sample_next_token(row_logits: np.ndarray, temperature: float,
                      rng: Optional[np.random.Generator]) -> int:
    """One host-side sampling decision for one request — THE sampling
    function, shared by the dense generator and the continuous
    scheduler so batching strategy can never change tokens: greedy
    (temperature=0) argmax, else a softmax draw from ``rng``."""
    if temperature > 0:
        p = np.exp((row_logits - row_logits.max()) / temperature)
        p /= p.sum()
        return int(rng.choice(row_logits.shape[-1], p=p))
    return int(row_logits.argmax(-1))


class _ExecParamsCache:
    """Cast-once cache for the decode compute dtype (bf16: cast per
    params VERSION, not per token inside the jitted step).

    Keyed on ``(cm.params_version, per-leaf identity via weakrefs)`` —
    deliberately NOT on ``id(params)`` with the reference dropped
    (``id`` values are reusable after GC: a freed-and-reallocated params
    tree could silently reuse a stale cast copy) and NOT by pinning the
    previous tree alive (a swapped-out params tree must stay
    collectable). The weakref leg compares EVERY leaf, so whole-tree
    replacement AND partial weight surgery (swapping one layer's arrays
    in place) both re-derive without a bump; the version leg
    (``bump_params_version()``, bumped by checkpoint restore and guard
    rollback) is the explicit invalidation for anything identity cannot
    see.
    """

    __slots__ = ("_version", "_leaf_refs", "_cast")

    def __init__(self):
        self.invalidate()

    def invalidate(self) -> None:
        self._version = None
        self._leaf_refs = None
        self._cast = None

    def get(self, cm, compute_dtype):
        params = cm.params
        if compute_dtype is None:
            return params
        version = getattr(cm, "params_version", 0)
        leaves = jax.tree_util.tree_leaves(params)
        if (self._cast is not None and self._version == version
                and self._leaf_refs is not None
                and len(self._leaf_refs) == len(leaves)
                and all(r() is leaf for r, leaf
                        in zip(self._leaf_refs, leaves))):
            return self._cast
        cast = jax.tree_util.tree_map(
            lambda v: v.astype(compute_dtype)
            if jnp.issubdtype(v.dtype, jnp.floating) else v, params)
        self._version = version
        self._leaf_refs = tuple(weakref.ref(leaf) for leaf in leaves)
        self._cast = cast
        return cast


def _audit_serving_program(program_name: str, jitted, sds_args, cfg):
    """Shared program-audit + exec-telemetry gate for a serving
    executable (the dense decode step, the paged decode step): returns
    ``(audit_report, exec_telemetry)`` per the config's
    ``audit_programs`` / ``exec_telemetry`` modes, or (None, None) when
    both are off. Never masks the decode path: a trace failure is
    recorded as an AUD000 finding + an explicit telemetry
    ``unavailable`` reason instead of raising here."""
    mode = getattr(cfg, "audit_programs", "off") or "off"
    from ..obs.exec_telemetry import telemetry_mode

    tmode = telemetry_mode(cfg)
    if mode == "off" and tmode == "off":
        return None, None
    from ..analysis.program_audit import audit_traced

    audit_report = exec_telemetry = None
    try:
        traced = jitted.trace(*sds_args)
    except Exception as e:  # noqa: BLE001 — audit must not mask decode
        # AUD000 contract: record the trace failure instead of leaving
        # audit_report empty-but-clean-looking; the first real decode
        # surfaces the true error with full context
        from ..analysis.findings import ValidationReport

        report = ValidationReport(source="serving", tag="audit")
        report.programs = {program_name: {"trace_failed": True}}
        report.add(
            "AUD000",
            f"program {program_name!r} could not be traced for "
            f"audit: {type(e).__name__}: {e}",
            severity="warning")
        if tmode == "on":
            # the telemetry contract: every failure mode is an explicit
            # unavailable reason, never a bare None
            exec_telemetry = {"programs": {program_name: {
                "unavailable":
                    f"trace failed: {type(e).__name__}: {e}"}}}
        if mode != "off":
            audit_report = report
            report.handle(mode)
        return audit_report, exec_telemetry
    report = audit_traced(program_name, traced, config=cfg,
                          source="serving")
    from ..obs.metrics import metrics_registry

    if mode != "off":
        audit_report = report
        reg = metrics_registry()
        reg.counter("audit.programs").inc()
        reg.counter("audit.errors").inc(len(report.errors))
        reg.counter("audit.warnings").inc(len(report.warnings))
    if tmode == "on":
        # telemetry reconciled against the static peak-live estimate
        # the audit walk just produced
        from ..obs.exec_telemetry import collect_one

        static_peak = (report.programs.get(program_name)
                       or {}).get("peak_live_bytes")
        exec_telemetry = collect_one(
            program_name, traced, config=cfg, static_peak=static_peak,
            allow=getattr(cfg, "exec_mem_allow", None))
    if mode != "off":
        audit_report.handle(mode)
    return audit_report, exec_telemetry


class _DecodeGraph:
    """The shared compiled-graph contract both cache layouts walk:
    validated causal self-attention ops, the (tokens, positions) input
    binding, the position-embedding capacity bound, and the exec-params
    cast cache."""

    def __init__(self, ff, max_length: int):
        cm = ff.compiled
        if cm is None:
            raise ValueError("compile() the model before generating")
        self._cm = cm
        self.max_length = int(max_length)
        self._attn_ops = [op for op in cm.ops if op.op_type in (
            OpType.MULTIHEAD_ATTENTION, OpType.LATENT_ATTENTION)]
        self._token_id = cm.input_tensors[0]
        self._pos_id = cm.input_tensors[1]
        for op in self._attn_ops:
            if op.op_type is OpType.LATENT_ATTENTION:
                # its second input is the graph's positions (rotary,
                # inside the op), not a learned table's
                if op.layer.inputs[1].tensor_id != self._pos_id.tensor_id:
                    raise ValueError(
                        f"{op.name}: latent attention has to take the "
                        f"graph's positions input")
                if self.max_length > op.max_positions:
                    raise ValueError(
                        f"max_length {self.max_length} exceeds the "
                        f"positions {op.name} was built for "
                        f"({op.max_positions})")
                continue
            ids = {t.tensor_id for t in op.layer.inputs}
            if len(ids) != 1 or not op.causal:
                raise ValueError(
                    f"{op.name}: generation needs causal SELF-attention")
        self._expert_ops = [op for op in cm.ops
                            if op.op_type is OpType.ROUTED_EXPERTS]
        # the position-embedding table bounds how far the MODEL can decode;
        # jnp.take clamps out-of-range ids silently, so enforce it here
        pos_tid = self._pos_id.tensor_id
        for op in cm.ops:
            if (op.op_type is OpType.EMBEDDING
                    and op.layer.inputs[0].tensor_id == pos_tid):
                cap = op.attrs["num_entries"]
                if self.max_length > cap:
                    raise ValueError(
                        f"max_length {self.max_length} exceeds the position "
                        f"embedding capacity {cap} ({op.name})")
        self._params_cache = _ExecParamsCache()

    def _compute_dtype(self):
        from ..runtime.compiler import _resolve_compute_dtype

        return _resolve_compute_dtype(self._cm.config.compute_dtype)

    def _exec_params(self):
        """Params in the decode compute dtype (cast once per params
        version — see :class:`_ExecParamsCache`)."""
        return self._params_cache.get(self._cm, self._compute_dtype())

    def invalidate_params_cache(self) -> None:
        """Drop the cast copy after mutating ``cm.params`` leaves in
        place (replacing the tree, or bumping ``cm.params_version``,
        invalidates automatically)."""
        self._params_cache.invalidate()

    def _forward_block(self, params, acts, attn, experts=None):
        """Walk the op graph over the activations in ``acts``; ``attn``
        handles each causal self-attention op (cache layout specific;
        a latent-attention op is handed the positions too) and
        ``experts``, where given, each routed-experts op (the paged
        programs keep the routing they chose). Returns the (B, S, vocab)
        float32 logits."""
        ctx = LowerCtx(mesh=None, training=False, aux_losses=[],
                       compute_dtype=None)
        for op in self._cm.ops:
            ins = [acts[t.tensor_id] for t in op.layer.inputs]
            p = params.get(op.name, {})
            if op.op_type is OpType.MULTIHEAD_ATTENTION:
                outs = [attn(op, p, ins[0])]
            elif op.op_type is OpType.LATENT_ATTENTION:
                outs = [attn(op, p, ins[0], ins[1])]
            elif op.op_type is OpType.ROUTED_EXPERTS and experts is not None:
                outs = [experts(op, p, ins[0])]
            else:
                outs = op.forward(ctx, ins, p)
            for out, t in zip(outs, op.layer.outputs):
                acts[t.tensor_id] = out
        logits = acts[self._cm.logits_tensor.tensor_id]
        return logits.astype(jnp.float32)


class Generator(_DecodeGraph):
    """KV-cache incremental decoding for a compiled causal LM.

    ``cm``: a CompiledModel whose graph takes (tokens, positions) int32
    inputs and produces (B, S, vocab) logits, with causal self-attention
    ops (models/gpt.py's contract).
    """

    def __init__(self, ff, max_length: int, batch_size: Optional[int] = None):
        super().__init__(ff, max_length)
        cm = self._cm
        self.batch_size = batch_size or cm.input_tensors[0].dims[0]
        self._step = jax.jit(self._block_step, donate_argnums=(2,))
        # program-audit gate (analysis/program_audit.py) over the decode
        # step at its steady-state (B, 1) shape. The KV cache is donated
        # (exact aval alias with the new cache); `params` has no
        # matching output and the cast copy is reused across steps, so
        # the audit proves nothing further is safely donatable here.
        self.audit_report = None
        # XLA executable telemetry for the decode step (filled when
        # config.exec_telemetry="on")
        self.exec_telemetry = None
        self._maybe_audit()

    def _maybe_audit(self) -> None:
        cfg = self._cm.config
        cdt = self._compute_dtype()
        cache_dt = cdt or jnp.float32

        def _sds(a):
            dt = (cache_dt if cdt is not None
                  and jnp.issubdtype(a.dtype, jnp.floating) else a.dtype)
            return jax.ShapeDtypeStruct(a.shape, dt)

        params_sds = jax.tree_util.tree_map(_sds, self._cm.params)
        tokens_sds = jax.ShapeDtypeStruct((self.batch_size, 1), jnp.int32)
        cache_sds = {
            op.name: tuple(jax.ShapeDtypeStruct(shape, cache_dt)
                           for shape in self._cache_shapes(op))
            for op in self._attn_ops}
        offset_sds = jax.ShapeDtypeStruct((), jnp.int32)
        self.audit_report, self.exec_telemetry = _audit_serving_program(
            "serving.decode_step", self._step,
            (params_sds, tokens_sds, cache_sds, offset_sds), cfg)

    # ---- cache ------------------------------------------------------------
    def _cache_shapes(self, op) -> Tuple[Tuple[int, ...], ...]:
        """The dense cache of one attention op: a (k, v) pair of
        (B, max_length, H, D), or a latent op's one (B, max_length,
        width) array of rows."""
        if op.op_type is OpType.LATENT_ATTENTION:
            return ((self.batch_size, self.max_length, op.row_width),)
        shape = (self.batch_size, self.max_length, op.num_heads, op.head_dim)
        return (shape, shape)

    def init_cache(self) -> Dict[str, Tuple[jnp.ndarray, ...]]:
        dt = self._compute_dtype() or jnp.float32
        return {op.name: tuple(jnp.zeros(shape, dt)
                               for shape in self._cache_shapes(op))
                for op in self._attn_ops}

    # ---- one block step (prefill: S=prompt, decode: S=1) -----------------
    def _block_step(self, params, tokens, cache, offset):
        b, s_blk = tokens.shape
        positions = offset + jax.lax.iota(jnp.int32, s_blk)[None, :]
        positions = jnp.broadcast_to(positions, (b, s_blk))
        acts = {self._token_id.tensor_id: tokens,
                self._pos_id.tensor_id: positions}
        new_cache = dict(cache)

        def attn(op, p, x, pos=None):
            if pos is not None:
                out, rows = _latent_attn_with_cache(
                    op, p, x, pos, new_cache[op.name][0], offset)
                new_cache[op.name] = (rows,)
                return out
            k, v = new_cache[op.name]
            out, k, v = _attn_with_cache(op, p, x, k, v, offset)
            new_cache[op.name] = (k, v)
            return out

        logits = self._forward_block(params, acts, attn)
        return logits, new_cache

    # ---- public API --------------------------------------------------------
    def prefill(self, prompt_ids: np.ndarray, cache=None, offset: int = 0):
        """Run a prompt block starting at absolute position ``offset``
        (pass the previous round's end position + its cache to continue a
        conversation). Accepts partial batches (rows < the compiled
        width are padded and stripped of meaning — their logits are
        junk, callers mask them). Returns (last-token logits, cache,
        end position)."""
        prompt_ids = np.asarray(prompt_ids, np.int32)
        b = prompt_ids.shape[0]
        if b > self.batch_size:
            raise ValueError(
                f"{b} prompts > compiled batch width {self.batch_size}")
        if b < self.batch_size:
            prompt_ids = np.concatenate([
                prompt_ids,
                np.zeros((self.batch_size - b,) + prompt_ids.shape[1:],
                         np.int32)], axis=0)
        prompt_ids = jnp.asarray(prompt_ids, jnp.int32)
        end = offset + prompt_ids.shape[1]
        if end > self.max_length:
            # dynamic_update_slice CLAMPS out-of-bounds starts, which would
            # silently misplace the written K/V — reject instead
            raise ValueError(
                f"offset {offset} + prompt {prompt_ids.shape[1]} exceeds "
                f"max_length {self.max_length}")
        if cache is None:
            if offset != 0:
                raise ValueError(
                    "offset > 0 needs the cache from the previous round "
                    "(a fresh cache has no K/V for positions < offset)")
            cache = self.init_cache()
        elif offset == 0:
            raise ValueError(
                "continuing with an existing cache requires the offset the "
                "previous round ended at (offset=0 would overwrite it)")
        logits, cache = self._step(self._exec_params(), prompt_ids, cache,
                                   jnp.int32(offset))
        return logits[:, -1, :], cache, end

    def generate(self, prompt_ids: np.ndarray, max_new_tokens: int,
                 temperature: float = 0.0,
                 seed: Union[int, Sequence[int]] = 0,
                 eos_id: Optional[int] = None) -> np.ndarray:
        """Greedy (temperature=0) or sampled decoding. ``prompt_ids``:
        (b, S_prompt) int32 with b ≤ the compiled batch width — partial
        batches are first-class: rows beyond b are inactive padding,
        never sampled (mask-aware), so a ragged arrival never needs
        filler requests. ``seed``: one int (one shared stream, drawn in
        row order — the historical semantics) or a length-b sequence of
        per-row seeds (each row draws from its own stream, so results
        are independent of co-batched rows). Returns
        (b, S_prompt + new) token ids."""
        prompt_ids = np.asarray(prompt_ids, np.int32)
        b, s0 = prompt_ids.shape
        if b > self.batch_size:
            raise ValueError(
                f"{b} prompts > compiled batch width {self.batch_size}")
        if s0 + max_new_tokens > self.max_length:
            raise ValueError(
                f"{s0} prompt + {max_new_tokens} new > max_length "
                f"{self.max_length}")
        if isinstance(seed, (int, np.integer)):
            shared = np.random.default_rng(int(seed))
            rngs = [shared] * b
        else:
            if len(seed) != b:
                raise ValueError(
                    f"per-row seeds: got {len(seed)} for {b} rows")
            rngs = [np.random.default_rng(int(s)) for s in seed]
        logits, cache, pos = self.prefill(prompt_ids)
        exec_params = self._exec_params()
        out = [prompt_ids]
        done = np.zeros(b, bool)
        for i in range(max_new_tokens):
            lg = np.asarray(logits)[:b]  # inactive padding rows never sampled
            nxt = np.array([sample_next_token(lg[j], temperature, rngs[j])
                            for j in range(b)], np.int32)
            if eos_id is not None:
                nxt = np.where(done, eos_id, nxt)
                done |= nxt == eos_id
            out.append(nxt[:, None])
            if i == max_new_tokens - 1 or (eos_id is not None and done.all()):
                break  # last token already sampled: skip the unused step
            step_tokens = np.zeros((self.batch_size, 1), np.int32)
            step_tokens[:b, 0] = nxt
            step_logits, cache = self._step(
                exec_params, jnp.asarray(step_tokens), cache,
                jnp.int32(pos))
            logits = step_logits[:, -1, :]
            pos += 1
        return np.concatenate(out, axis=1)


def default_prefill_buckets(max_length: int,
                            smallest: int = 8) -> List[int]:
    """The pad-to-bucket ladder: powers of two from ``smallest``,
    capped by a final bucket of exactly ``max_length``."""
    out: List[int] = []
    b = smallest
    while b < max_length:
        out.append(b)
        b *= 2
    out.append(max_length)
    return out


class PagedDecoder(_DecodeGraph):
    """Split prefill/decode executables over a paged KV pool — the
    continuous-batching compute core (the scheduling loop lives in
    serving/scheduler.py).

    * ``decode_slots``: the fixed decode batch width — ONE jitted decode
      program batches every active request (inactive slots ride along
      masked); the program's shape never depends on the live mix, so the
      decode loop issues one dispatch per step regardless of
      active-request count.
    * the pool (``num_blocks`` × ``block_size`` per attention op) is
      donated through both executables; admission reserves each
      request's worst case so the decode can never outgrow it.
    * prompts run through per-bucket prefill executables (pad-to-bucket
      ladder; compiles cached and counted on
      ``serving.prefill_bucket_compiles``) that compute the prompt's
      K/V, scatter it into the pool through the block table, and return
      the full-prompt logits — one dispatch per prefill.
    * the decode program keeps the greedy token's loop on the device: it
      returns each row's ``argmax`` as int32 ids beside the logits and
      takes the step before's ids back (``prev_ids``, ``take_prev``).
      :meth:`decode` dispatches a step, waits, and returns its numpy
      logits; :meth:`decode_ahead` dispatches the same executable and
      returns the ids on the device without waiting, which is how the
      scheduler runs one step ahead of what it has read.
    """

    def __init__(self, ff, max_length: int, *, decode_slots: int = 4,
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 kv_dtype: str = "float32",
                 kv_divergence_budget: Optional[float] = None,
                 calibrate: bool = True):
        super().__init__(ff, max_length)
        if decode_slots < 1:
            raise ValueError(f"decode_slots {decode_slots} < 1")
        self.decode_slots = int(decode_slots)
        self.block_size = int(block_size)
        self.max_blocks_per_request = max(
            1, math.ceil(self.max_length / self.block_size))
        if num_blocks is None:
            # auto: every decode slot can hold one worst-case request,
            # plus the reserved null block
            num_blocks = (self.decode_slots * self.max_blocks_per_request
                          + 1)
        dt = self._compute_dtype() or jnp.float32
        self.kv_dtype = str(kv_dtype)
        self.pool = PagedKVPool(
            self._pool_specs(), num_blocks=int(num_blocks),
            block_size=self.block_size,
            max_blocks_per_request=self.max_blocks_per_request, dtype=dt,
            kv_dtype=self.kv_dtype)
        # one small accumulator for each routed-experts op (_count_up),
        # donated to the decode program beside the pool and returned by
        # it: counted on the device, fetched only by expert_stats(). The
        # lock covers the moment between a dispatch that donates them and
        # the assignment of what it returns.
        self._expert_acc: Dict[str, jax.Array] = {
            op.name: jnp.zeros((2, 4 + op.count), jnp.uint32)
            for op in self._expert_ops}
        self._expert_acc_lock = threading.Lock()
        # the greedy ids the last decode step chose, (slots,) int32 on
        # the device: the next step's ``prev_ids``. Placed as the
        # program places what it returns (replicated over the model's
        # mesh, committed), so that the first step's signature is every
        # later step's and costs no compile of its own.
        self._ids: jax.Array = jax.device_put(
            np.zeros((self.decode_slots,), np.int32),
            NamedSharding(self._cm.mesh, PartitionSpec()))
        # the expert ids the last prefill or decode call chose, {routed-
        # experts op name: (rows..., k) int32 device array}: kept for
        # whoever asks (a comparison with a reference), never fetched by
        # the scheduler's loop
        self.last_routing: Dict[str, jax.Array] = {}
        if prefill_buckets is None:
            prefill_buckets = default_prefill_buckets(self.max_length)
        self.prefill_buckets = sorted(
            {min(int(bkt), self.max_length) for bkt in prefill_buckets})
        if self.prefill_buckets[-1] < self.max_length:
            self.prefill_buckets.append(self.max_length)
        self._decode = jax.jit(self._decode_step, donate_argnums=(2, 5))
        # one verify executable per window width W=k+1 (spec_k is a
        # session knob, so in practice this holds one entry)
        self._verify_fns: Dict[int, object] = {}
        # how each program's attention reads the pool, fixed when the
        # program is built: "kernel" (paged attention, in place) or
        # "gather" (the jnp path); "verify" appears with its program
        self.attention_path: Dict[str, str] = {
            "decode": self._attention_path(1)}
        self._prefill_fns: Dict[Tuple[int, int], object] = {}
        self.decode_dispatches = 0
        self.decode_steps = 0
        # called between a jitted call's return and the fetch of its
        # logits: where the scheduler's clock divides dispatch from fetch
        self.on_dispatched = None
        self.audit_report = None
        self.exec_telemetry = None
        # KVQ001 state: measured max-abs logit divergence of the
        # quantized pool vs the f32 dense reference, and the loud
        # fallback report when it exceeded the budget
        self.kv_divergence: Optional[float] = None
        self.kv_divergence_budget: Optional[float] = None
        self.kv_quant_report = None
        self._maybe_audit()
        if self.kv_dtype != "float32" and calibrate:
            self._calibrate_kv_quant(kv_divergence_budget)

    # ---- compiled programs -------------------------------------------------
    def _decode_step(self, params, tokens, pool, tables, seq_lens,
                     expert_acc, prev_ids, take_prev):
        """One decode step for all slots: tokens (slots,) int32, pool
        {op: arena entry} donated, tables (slots, MB) int32, seq_lens
        (slots,) int32, expert_acc {routed-experts op: counters}
        donated, prev_ids (slots,) int32 the ids the step before
        returned (not donated: the host may still be fetching them),
        take_prev (slots,) bool. Slot i's token is ``prev_ids[i]`` where
        ``take_prev[i]``, else ``tokens[i]``: a greedy token goes from
        one step to the next without leaving the device. Returns
        ((slots, vocab) float32 logits, new pool, the expert ids
        chosen, new counters, (slots,) int32 ids: each row's first
        maximum, what ``np.argmax`` of the fetched row gives)."""
        tokens = jnp.where(take_prev, prev_ids, tokens)[:, None]
        positions = seq_lens[:, None]                           # (slots, 1)
        acts = {self._token_id.tensor_id: tokens,
                self._pos_id.tensor_id: positions}
        new_pool = dict(pool)
        new_acc = dict(expert_acc)
        routed: Dict[str, jax.Array] = {}
        # a slot with no block reserved is idle: its token is padding
        active = tables[:, 0] != NULL_BLOCK

        def attn(op, p, x, pos=None):
            if pos is not None:
                out, new_pool[op.name] = _latent_attn_paged(
                    op, p, x, pos, new_pool[op.name], tables, seq_lens)
                return out
            out, new_pool[op.name] = _attn_with_paged_cache(
                op, p, x, new_pool[op.name], tables, seq_lens)
            return out

        def experts(op, p, x):
            x2d = x.reshape(-1, x.shape[-1])
            ids, gates = op.route(p, x2d)
            routed[op.name] = ids
            new_acc[op.name] = _count_up(
                new_acc[op.name], _expert_counts(op, ids, active))
            return op.apply(p, x2d, ids, gates).reshape(x.shape)

        logits = self._forward_block(params, acts, attn, experts)[:, -1, :]
        ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return logits, new_pool, routed, new_acc, ids

    def _verify_step(self, params, tokens, pool, tables, seq_lens):
        """Speculative verify: tokens (slots, W) int32 — each slot's
        last accepted token followed by W-1 draft proposals, at absolute
        positions ``seq_lens .. seq_lens + W - 1``. Writes K/V for ALL
        W positions through the block tables and returns the full
        ((slots, W, vocab) float32 logits, new pool) in ONE dispatch:
        row j is the target's distribution for the token AFTER window
        position j — exactly what W sequential single-token decode steps
        would produce, because each query position only attends to keys
        at positions ≤ its own. Rejected suffixes need no undo: the
        scheduler rolls ``seq_len`` back and the stale rows stay masked
        by position until the next window (which always starts at or
        before them, since ≥1 token is accepted per round) overwrites
        them."""
        w = tokens.shape[1]
        positions = (seq_lens[:, None]
                     + jax.lax.iota(jnp.int32, w)[None, :])     # (slots, W)
        acts = {self._token_id.tensor_id: tokens,
                self._pos_id.tensor_id: positions}
        new_pool = dict(pool)

        def attn(op, p, x):
            out, new_pool[op.name] = _attn_with_paged_cache(
                op, p, x, new_pool[op.name], tables, seq_lens)
            return out

        logits = self._forward_block(params, acts, attn)
        return logits, new_pool, {}

    def _prefill_step(self, params, tokens, pool, tables, lengths):
        """Bucketed prefill for a GROUP of requests: tokens (P, Sb)
        int32 (each prompt padded to the bucket), pool donated, tables
        (P, MB) int32, lengths (P,) int32 true prompt lengths. Rows
        are independent — batched dense causal attention (padding keys
        are causally masked for every valid query row), each row's K/V
        scattered through its own block table with padding positions
        redirected into the null block — so one multi-prompt dispatch
        computes exactly what P single-prompt dispatches would, in one
        XLA program. Returns ((P, vocab) float32 logits of each row's
        last prompt position, new pool): that row is all a caller
        reads, and the other Sb - 1 never leave the device (fetched
        whole they were 63-84 MB a prefill at 20480 wide, a tenth of a
        serving loop's time: PERF.md section 6, PR 27)."""
        b, s_blk = tokens.shape
        positions = jnp.broadcast_to(
            jax.lax.iota(jnp.int32, s_blk)[None, :], (b, s_blk))
        acts = {self._token_id.tensor_id: tokens,
                self._pos_id.tensor_id: positions}
        new_pool = dict(pool)
        bs = self.block_size
        routed: Dict[str, jax.Array] = {}

        def experts(op, p, x):
            x2d = x.reshape(-1, x.shape[-1])
            ids, gates = op.route(p, x2d)
            routed[op.name] = ids.reshape(b, s_blk, -1)
            return op.apply(p, x2d, ids, gates).reshape(x.shape)

        def attn(op, p, x, pos=None):
            if pos is not None:
                out, new_pool[op.name] = _latent_attn_prefill(
                    op, p, x, pos, new_pool[op.name], tables, lengths)
                return out
            qh = jnp.einsum("bse,ehd->bshd", x, p["wq"])
            kh = jnp.einsum("bse,ehd->bshd", x, p["wk"])
            vh = jnp.einsum("bse,ehd->bshd", x, p["wv"])
            if op.use_bias:
                qh = qh + p["bq"]
                kh = kh + p["bk"]
                vh = vh + p["bv"]
            scale = 1.0 / math.sqrt(op.head_dim)
            scores = jnp.einsum("bqhd,bkhd->bhqk", qh, kh) * scale
            pos = jax.lax.iota(jnp.int32, s_blk)
            mask = pos[None, :] <= pos[:, None]                 # causal
            scores = jnp.where(mask[None, None, :, :], scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1)
            ctxv = jnp.einsum("bhqk,bkhd->bqhd", probs, vh)
            out = jnp.einsum("bqhd,hde->bqe", ctxv, p["wo"])
            if op.use_bias:
                out = out + p["bo"]
            # scatter each row's prompt K/V into the pool: row i's
            # position p lands in block tables[i, p // bs] at offset
            # p % bs; padding positions (p >= lengths[i]) are
            # redirected into the null block (real positions never
            # collide — each row owns its blocks). _entry_write
            # quantizes on the way in for int8 arenas.
            blk = tables[:, pos // bs]                          # (P, Sb)
            flat = jnp.where(pos[None, :] < lengths[:, None],
                             blk * bs + (pos % bs)[None, :],
                             NULL_BLOCK * bs)                   # (P, Sb)
            heads, hdim = kh.shape[2], kh.shape[3]
            new_pool[op.name] = _entry_write(
                new_pool[op.name], flat.reshape(-1),
                kh.reshape(b * s_blk, heads, hdim),
                vh.reshape(b * s_blk, heads, hdim))
            return out

        logits = self._forward_block(params, acts, attn, experts)
        last = logits[jnp.arange(b), jnp.maximum(lengths - 1, 0)]
        return last, new_pool, routed

    def _pool_specs(self) -> Dict[str, Tuple[int, ...]]:
        """What a token's row is for each attention op (kv_cache.py)."""
        return {op.name: ((op.row_width,)
                          if op.op_type is OpType.LATENT_ATTENTION
                          else (op.num_heads, op.head_dim))
                for op in self._attn_ops}

    def expert_stats(self) -> Dict[str, Dict]:
        """Per routed-experts op, counted on the device over the decode
        steps' active slots: ``steps``, ``pairs_routed`` (tokens x picks),
        ``pairs_held`` (those whose expert this op holds),
        ``idle_held_experts`` (held experts that got no row, summed over
        steps), ``rows_per_held_expert`` (count,). One fetch of a few
        hundred bytes, which waits for a decode step in flight; {} for a
        graph with no such op."""
        if not self._expert_ops:
            return {}
        with self._expert_acc_lock:
            fetched = jax.device_get(self._expert_acc)
        out = {}
        for op in self._expert_ops:
            acc = fetched[op.name].astype(np.uint64)
            acc = [int(v) for v in (acc[1] << np.uint64(32)) | acc[0]]
            out[op.name] = {
                "held": [op.first, op.count], "n_routed": op.n_routed,
                "steps": acc[0], "pairs_routed": acc[1],
                "pairs_held": acc[2], "idle_held_experts": acc[3],
                "rows_per_held_expert": acc[4:]}
        return out

    def _attention_path(self, window: int) -> str:
        """What a W-token step's attention does with the pool as it is
        now: "kernel" where every attention op's entry is read in
        place, else "gather"."""
        def in_place(op):
            entry = self.pool.kv[op.name]
            if op.op_type is OpType.LATENT_ATTENTION:
                return window == 1 and _latent_kernel_reads(
                    op, entry, self.decode_slots,
                    self.max_blocks_per_request)
            return _kernel_reads(
                entry, (self.decode_slots, window, op.num_heads,
                        op.head_dim), self.max_blocks_per_request)

        return "kernel" if all(in_place(op)
                               for op in self._attn_ops) else "gather"

    def _prefill_fn(self, bucket: int, width: int = 1):
        """The (bucket, row-width) executable — the seen-set is the
        dict itself, so ``serving.prefill_bucket_compiles`` counts
        distinct compiled shapes, not dispatches."""
        key = (bucket, width)
        fn = self._prefill_fns.get(key)
        if fn is None:
            fn = jax.jit(self._prefill_step, donate_argnums=(2,))
            self._prefill_fns[key] = fn
            from ..obs.metrics import metrics_registry

            metrics_registry().counter(
                "serving.prefill_bucket_compiles").inc()
        return fn

    def bucket_for(self, prompt_len: int) -> int:
        for b in self.prefill_buckets:
            if b >= prompt_len:
                return b
        raise ValueError(
            f"prompt length {prompt_len} exceeds the largest prefill "
            f"bucket {self.prefill_buckets[-1]}")

    # ---- audit -------------------------------------------------------------
    def _maybe_audit(self) -> None:
        cfg = self._cm.config
        cdt = self._compute_dtype()
        cache_dt = cdt or jnp.float32

        def _sds(a):
            dt = (cache_dt if cdt is not None
                  and jnp.issubdtype(a.dtype, jnp.floating) else a.dtype)
            return jax.ShapeDtypeStruct(a.shape, dt)

        params_sds = jax.tree_util.tree_map(_sds, self._cm.params)
        pool_sds = {name: tuple(jax.ShapeDtypeStruct(k.shape, k.dtype)
                                for k in kv)
                    for name, kv in self.pool.kv.items()}
        tables_sds = jax.ShapeDtypeStruct(
            (self.decode_slots, self.max_blocks_per_request), jnp.int32)
        # tokens, seq_lens and prev_ids: one (slots,) int32 each
        lens_sds = jax.ShapeDtypeStruct((self.decode_slots,), jnp.int32)
        acc_sds = {name: jax.ShapeDtypeStruct(a.shape, a.dtype)
                   for name, a in self._expert_acc.items()}
        take_sds = jax.ShapeDtypeStruct((self.decode_slots,), jnp.bool_)
        self.audit_report, self.exec_telemetry = _audit_serving_program(
            "serving.paged_decode_step", self._decode,
            (params_sds, lens_sds, pool_sds, tables_sds, lens_sds,
             acc_sds, lens_sds, take_sds), cfg)

    # ---- host API (the scheduler's surface) --------------------------------
    def prefill(self, prompt: np.ndarray, table: np.ndarray) -> np.ndarray:
        """Prefill one request through its bucket executable, scattering
        its K/V into the pool. ``prompt``: (S,) int32; ``table``: the
        request's block table. Returns the last-prompt-position logits
        (vocab,) float32."""
        return self.prefill_many([prompt], [table])[0]

    def prefill_many(self, prompts: Sequence[np.ndarray],
                     tables: Sequence[np.ndarray]) -> np.ndarray:
        """Prefill a group of requests in ONE dispatch. ``prompts``:
        (S_i,) int32 each, with matching block tables; the whole group
        runs at the bucket of its longest prompt (the scheduler groups
        by bucket before calling). The row count is padded up to the
        next power of two with zero-length dummy rows whose writes all
        land in the null block, so the executable set stays bounded at
        distinct (bucket, pow2 rows) pairs. Returns (len(prompts),
        vocab) float32 last-prompt-position logits, row-aligned with
        ``prompts``."""
        if not prompts or len(prompts) != len(tables):
            raise ValueError("prefill group needs matching non-empty "
                             "prompt/table lists")
        arrs = [np.asarray(p, np.int32).ravel() for p in prompts]
        lens = [int(a.shape[0]) for a in arrs]
        if min(lens) < 1:
            raise ValueError("empty prompt")
        if max(lens) > self.max_length:
            raise ValueError(
                f"prompt {max(lens)} tokens > max_length "
                f"{self.max_length}")
        bucket = self.bucket_for(max(lens))
        width = 1
        while width < len(arrs):
            width *= 2
        toks = np.zeros((width, bucket), np.int32)
        tabs = np.full((width, self.max_blocks_per_request), NULL_BLOCK,
                       np.int32)
        lengths = np.zeros((width,), np.int32)
        for i, (a, t) in enumerate(zip(arrs, tables)):
            toks[i, :lens[i]] = a
            t = np.asarray(t, np.int32).ravel()
            tabs[i, :t.shape[0]] = t
            lengths[i] = lens[i]
        fn = self._prefill_fn(bucket, width)
        with span("serving.loop.dispatch", cat="serving"):
            logits, self.pool.kv, self.last_routing = fn(
                self._exec_params(), jnp.asarray(toks), self.pool.kv,
                jnp.asarray(tabs), jnp.asarray(lengths))
        return self._fetch(logits)[:len(arrs)]

    def decode(self, tokens: np.ndarray, tables: np.ndarray,
               seq_lens: np.ndarray) -> np.ndarray:
        """One decode step for all slots (ONE dispatch regardless of how
        many are active), waited for. Returns (slots, vocab) float32
        logits."""
        return self._fetch(self._dispatch_decode(tokens, tables, seq_lens,
                                                 None)[0])

    def decode_ahead(self, tokens: np.ndarray, tables: np.ndarray,
                     seq_lens: np.ndarray,
                     take_prev: np.ndarray) -> jax.Array:
        """The same step through the same executable, dispatched and
        not waited for. Slot i's token is the greedy one the step
        before chose (still on the device) where ``take_prev[i]``, else
        ``tokens[i]``. Returns this step's (slots,) int32 greedy ids as
        a device array: ``np.asarray`` of it is the wait for the step;
        the logits are never fetched."""
        return self._dispatch_decode(tokens, tables, seq_lens, take_prev)[1]

    def _dispatch_decode(self, tokens, tables, seq_lens, take_prev):
        """The decode program's one call site, so that a step waited for
        and a step run ahead pass the same signature (a second one
        would be a second compile): (logits, ids), both on the device.
        ``take_prev`` None: every slot takes ``tokens``."""
        self.decode_steps += 1
        self.decode_dispatches += 1
        if take_prev is None:
            take_prev = np.zeros(self.decode_slots, bool)
        with span("serving.loop.dispatch", cat="serving"), \
                self._expert_acc_lock:
            (logits, self.pool.kv, self.last_routing, self._expert_acc,
             self._ids) = self._decode(
                self._exec_params(),
                jnp.asarray(np.asarray(tokens, np.int32)),
                self.pool.kv,
                jnp.asarray(np.asarray(tables, np.int32)),
                jnp.asarray(np.asarray(seq_lens, np.int32)),
                self._expert_acc, self._ids,
                jnp.asarray(np.asarray(take_prev, bool)))
        return logits, self._ids

    def verify(self, tokens: np.ndarray, tables: np.ndarray,
               seq_lens: np.ndarray) -> np.ndarray:
        """Speculative verify step for all slots: ``tokens`` (slots, W)
        int32 — each slot's last accepted token plus W-1 draft
        proposals. ONE dispatch (the verify IS the step's decode
        dispatch — same counters, same invariant). Returns (slots, W,
        vocab) float32 logits: row j is the target's next-token
        distribution after window position j."""
        tokens = np.asarray(tokens, np.int32)
        w = int(tokens.shape[1])
        self._refuse_verify_over_latent()
        fn = self._verify_fns.get(w)
        if fn is None:
            fn = jax.jit(self._verify_step, donate_argnums=(2,))
            self._verify_fns[w] = fn
            self.attention_path["verify"] = self._attention_path(w)
        self.decode_steps += 1
        self.decode_dispatches += 1
        with span("serving.loop.dispatch", cat="serving"):
            logits, self.pool.kv, self.last_routing = fn(
                self._exec_params(), jnp.asarray(tokens), self.pool.kv,
                jnp.asarray(np.asarray(tables, np.int32)),
                jnp.asarray(np.asarray(seq_lens, np.int32)))
        return self._fetch(logits)

    def _refuse_verify_over_latent(self) -> None:
        """Speculative verify (W > 1 tokens a slot) is not built for a
        latent cache entry: refuse, loudly, rather than fall back."""
        latent = [op.name for op in self._attn_ops
                  if op.op_type is OpType.LATENT_ATTENTION]
        if latent:
            raise ValueError(
                f"speculative verify over a latent cache entry is not "
                f"built ({latent[0]} and {len(latent) - 1} more): serve "
                f"this model with spec_k=0")

    def _fetch(self, logits) -> np.ndarray:
        """The other half of a dispatch: wait for the device and copy
        the logits to the host."""
        if self.on_dispatched is not None:
            self.on_dispatched()
        with span("serving.loop.fetch", cat="serving",
                  bytes=logits.size * logits.dtype.itemsize):
            return np.asarray(logits)

    # ---- KV quantization gate (KVQ001) -------------------------------------
    def _dense_reference_logits(self, tokens: np.ndarray,
                                routing=None) -> np.ndarray:
        """Eager (un-jitted) dense causal forward over one full
        sequence — the cache-free reference the quantized pool is
        calibrated against. ``routing`` ({routed-experts op name: (S, k)
        expert ids}) makes the expert layers take those experts. Returns
        (S, vocab) float32 logits."""
        tokens = np.asarray(tokens, np.int32)
        s = tokens.shape[0]
        acts = {
            self._token_id.tensor_id: jnp.asarray(tokens[None, :]),
            self._pos_id.tensor_id:
                jnp.asarray(np.arange(s, dtype=np.int32)[None, :])}

        def attn(op, p, x, pos=None):
            if pos is not None:     # latent attention's own dense forward
                return op.forward(None, [x, pos], p)[0]
            qh = jnp.einsum("bse,ehd->bshd", x, p["wq"])
            kh = jnp.einsum("bse,ehd->bshd", x, p["wk"])
            vh = jnp.einsum("bse,ehd->bshd", x, p["wv"])
            if op.use_bias:
                qh = qh + p["bq"]
                kh = kh + p["bk"]
                vh = vh + p["bv"]
            scale = 1.0 / math.sqrt(op.head_dim)
            scores = jnp.einsum("bqhd,bkhd->bhqk", qh, kh) * scale
            pos = jax.lax.iota(jnp.int32, s)
            mask = pos[None, :] <= pos[:, None]
            scores = jnp.where(mask[None, None, :, :], scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1)
            ctxv = jnp.einsum("bhqk,bkhd->bqhd", probs, vh)
            out = jnp.einsum("bqhd,hde->bqe", ctxv, p["wo"])
            if op.use_bias:
                out = out + p["bo"]
            return out

        def experts(op, p, x):
            x2d = x.reshape(-1, x.shape[-1])
            ids, gates = op.route(p, x2d, jnp.asarray(routing[op.name]))
            return op.apply(p, x2d, ids, gates).reshape(x.shape)

        logits = self._forward_block(self._exec_params(), acts, attn,
                                     experts if routing else None)
        return np.asarray(logits[0], np.float32)

    def _calibrate_kv_quant(self, budget: Optional[float]) -> None:
        """The ``serving_kv_divergence_budget`` gate: run a calibration
        prompt through the REAL quantized prefill + decode programs,
        compare the decode logits against the dense f32-arena reference,
        and fall back LOUDLY to a float32 pool (KVQ001 finding +
        ``serving.kv_dtype_fallbacks`` counter + stderr) when the
        max-abs logit divergence exceeds the budget. The measured
        divergence is kept on :attr:`kv_divergence` either way, so the
        ledger records how close a passing config sailed."""
        cfg = self._cm.config
        if budget is None:
            budget = getattr(cfg, "serving_kv_divergence_budget", None)
        # 0.0 is the knob's "unset" sentinel (config default), not a
        # zero-tolerance request — both map to the 0.05 default budget.
        budget = float(budget) if budget else 0.05
        self.kv_divergence_budget = budget
        vocab = int(self._cm.logits_tensor.dims[-1])
        prompt_len = int(max(1, min(self.block_size + 1,
                                    self.max_length - 1, 12)))
        rng = np.random.default_rng(0)
        prompt = rng.integers(0, vocab, size=prompt_len).astype(np.int32)
        # reference: dense cache-free forward, then one more position
        ref = self._dense_reference_logits(prompt)
        nxt = int(ref[-1].argmax(-1))
        # quantized path: the exact programs serving will dispatch
        table = self.pool.try_admit(prompt_len + 1)
        if table is None:  # pragma: no cover — fresh pool always fits
            raise RuntimeError("calibration admission failed on a "
                               "fresh pool")
        try:
            self.prefill(prompt, table)
            routed = {k: [np.asarray(v)[0, :prompt_len]]
                      for k, v in self.last_routing.items()}
            toks = np.zeros(self.decode_slots, np.int32)
            toks[0] = nxt
            tabs = np.full((self.decode_slots, self.max_blocks_per_request),
                           NULL_BLOCK, np.int32)
            tabs[0, :table.shape[0]] = table
            lens = np.zeros(self.decode_slots, np.int32)
            lens[0] = prompt_len
            q_row = self.decode(toks, tabs, lens)[0]
            for k, v in self.last_routing.items():
                routed[k].append(np.asarray(v)[:1])
        finally:
            self.pool.free(table)
        # a routed layer is discontinuous: two programs a rounding apart
        # may take different experts, and the logits then differ by a
        # whole expert's output, which says nothing about the cache. The
        # reference therefore follows the routing the paged programs
        # chose (its own scores for the weights).
        ref_row = self._dense_reference_logits(
            np.concatenate([prompt, [nxt]]),
            {k: np.concatenate(v) for k, v in routed.items()})[-1]
        self.kv_divergence = float(np.max(np.abs(q_row - ref_row)))
        if self.kv_divergence <= budget:
            return
        import sys

        from ..analysis.findings import ValidationReport
        from ..obs.metrics import metrics_registry

        report = ValidationReport(source="serving", tag="kv_quant")
        report.add(
            "KVQ001",
            f"kv_dtype={self.kv_dtype!r} calibration divergence "
            f"{self.kv_divergence:.3e} exceeds "
            f"serving_kv_divergence_budget {budget:.3e}; falling back "
            f"to float32 arenas (admission headroom reverts to the f32 "
            f"pool size)",
            severity="warning")
        self.kv_quant_report = report
        metrics_registry().counter("serving.kv_dtype_fallbacks").inc()
        print(f"[serving] KVQ001: {report.warnings[0].message}",
              file=sys.stderr)
        self.kv_dtype = "float32"
        dt = self._compute_dtype() or jnp.float32
        fallback = PagedKVPool(
            self._pool_specs(), num_blocks=self.pool.num_blocks,
            block_size=self.block_size,
            max_blocks_per_request=self.max_blocks_per_request, dtype=dt,
            kv_dtype="float32")
        self.pool = fallback  # concurrency: race-ok (calibration runs inside __init__, before the scheduler's thread or any stats() reader exists)
        self.attention_path["decode"] = self._attention_path(1)


def build_draft_model(ff, spec: str):
    """Build + compile a draft causal LM sharing ``ff``'s vocab and
    position contract (:func:`~flexflow_tpu.runtime.compiler
    .causal_lm_signature`), for speculative decoding. ``spec``:

    * ``"self:N"`` — layer-skip self-drafting: a GPT with the target's
      own geometry truncated to its first N transformer blocks, with
      every shared-name parameter (embeddings, blocks 0..N-1, final LN,
      LM head) COPIED from the target — the draft approximates the
      target by construction, no separate training needed (the standard
      draft-free speculation baseline);
    * ``"gpt:layers=1,hidden=16,heads=2"`` — a fresh randomly
      initialized GPT at the target's vocab/max_positions (every key
      optional; hidden/heads default to the target's).

    Returns the compiled draft FFModel.
    """
    import copy

    from ..ffconst import CompMode
    from ..models.gpt import GPTConfig, build_gpt
    from ..runtime.compiler import causal_lm_signature
    from ..runtime.model import FFModel

    cm = ff.compiled
    if cm is None:
        raise ValueError("compile() the target before building a draft")
    sig = causal_lm_signature(cm)
    attn_ops = [op for op in cm.ops
                if op.op_type is OpType.MULTIHEAD_ATTENTION]
    if not attn_ops:
        raise ValueError("target has no attention ops — not a causal LM")
    t_heads = attn_ops[0].num_heads
    t_hidden = attn_ops[0].num_heads * attn_ops[0].head_dim
    kind, _, rest = spec.partition(":")
    if kind == "self":
        layers = int(rest or 1)
        if layers < 1 or layers > len(attn_ops):
            raise ValueError(
                f"draft spec {spec!r}: need 1 <= N <= "
                f"{len(attn_ops)} target blocks")
        up = cm.params.get("block0_mlp_up", {}).get("kernel")
        ratio = (int(up.shape[-1] // t_hidden) if up is not None else 4)
        gcfg = GPTConfig(
            vocab_size=sig["vocab_size"],
            max_positions=sig["max_positions"] or 1024,
            hidden_size=t_hidden, num_heads=t_heads,
            num_layers=layers, mlp_ratio=ratio)
    elif kind == "gpt":
        kw = {}
        for part in filter(None, rest.split(",")):
            key, _, val = part.partition("=")
            kw[key.strip()] = int(val)
        gcfg = GPTConfig(
            vocab_size=sig["vocab_size"],
            max_positions=sig["max_positions"] or 1024,
            hidden_size=kw.get("hidden", t_hidden),
            num_heads=kw.get("heads", t_heads),
            num_layers=kw.get("layers", 1),
            mlp_ratio=kw.get("mlp_ratio", 4))
    else:
        raise ValueError(
            f"draft spec {spec!r}: expected 'self:N' or "
            f"'gpt:layers=...,hidden=...,heads=...'")
    dcfg = copy.deepcopy(ff.config)
    dcfg.computation_mode = CompMode.INFERENCE
    draft = FFModel(dcfg)
    build_gpt(draft, cm.input_tensors[0].dims[0], 8, gcfg)
    draft.compile(optimizer=None, loss_type=None, metrics=[])
    if kind == "self":
        # graft the target's weights onto every shared-name layer —
        # shapes match by construction (same vocab/hidden/heads/ratio)
        for name, weights in draft.compiled.params.items():
            src = cm.params.get(name)
            if not src:
                continue
            draft.compiled.params[name] = {
                w: (src[w] if w in src and src[w].shape == arr.shape
                    else arr)
                for w, arr in weights.items()}
        draft.compiled.bump_params_version()
    return draft
