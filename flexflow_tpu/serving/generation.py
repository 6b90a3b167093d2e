"""Autoregressive generation with KV caches over a compiled model graph.

No reference analog (the reference predates LLM serving; its triton/
prototype served batch CNN inference) — this is the modern-completeness
piece on top of the serving engine. TPU-native design:

* each program is ONE jitted function produced by walking the compiled
  model's op graph — every op runs its ordinary shape-polymorphic
  ``forward`` on the (B, S_blk, ·) activations EXCEPT the ops that keep
  something for a sequence (attention, a recurrent state), which are
  handed to their entry kind (:mod:`~flexflow_tpu.serving.cache_entry`:
  what an op keeps for a token or for a request, and how each program
  here writes and reads it, is the kind's; this module knows no more
  than that every such op has one, and hands each what addresses it:
  block tables and state rows together, as one ``Addresses``);
* the cache is a pytree {attention op name: entry} of static shape,
  donated through the program so XLA updates it in place;
* sampling (greedy / temperature) happens on host between steps, except
  the greedy token, which the paged decode program picks itself.

Two cache layouts share the graph walk:

* :class:`Generator` — the dense rectangle: ``(B, max_length, ·)`` per
  op, one fixed batch decoded in lockstep (offline/batch use, and the
  reference the paged path is held to: tests/test_continuous_batching.py,
  to float32 reordering per zoo causal-LM model);
* :class:`PagedDecoder` — the continuous-batching layout: a
  :class:`~flexflow_tpu.serving.kv_cache.PagedKVPool` of arenas plus
  per-request block tables. The compiled decode program's shape depends
  only on (decode slots, pool geometry), so one program serves every
  in-flight request mix, and prompts run through a separate **bucketed
  prefill executable** (pad-to-bucket ladder, per-bucket compile cached
  and counted) whose rows are scattered into the pool in the same
  dispatch.

Routed-experts ops run inside the same programs; the paged ones keep the
expert ids they chose (``PagedDecoder.last_routing``) and count their
load on the device (``PagedDecoder.expert_stats``).
"""

from __future__ import annotations

import math
import threading
import time
import weakref
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from ..ffconst import OpType
from ..core.op import LowerCtx, fixed_scope, op_scope, weights_of
from ..obs.metrics import metrics_registry
from ..obs.trace import span
from .cache_entry import kind_for
from .kv_cache import NULL_BLOCK, Addresses, PagedKVPool


def _expert_counts(op, ids, active, *computed):
    """What one decode step adds to an expert op's counters: ``[1, pairs
    routed, pairs held, held experts that got no row, rows of each held
    expert ...]`` over the active slots' tokens and, where the step's
    experts read by the routing, what ``RoutedExperts.apply`` ``computed``
    behind them, () uint32 each: the rows the kernel ran over and, from
    a step that chose its form by its count, 1 where it chose the kernel.
    ``ids`` (T, k), ``active`` (T,) bool."""
    hit = op.held_hits(ids) & active[:, None, None]
    rows = hit.sum((0, 1)).astype(jnp.uint32)                 # (count,)
    head = jnp.stack([jnp.uint32(1),
                      (active.sum() * ids.shape[1]).astype(jnp.uint32),
                      rows.sum(), (rows == 0).sum().astype(jnp.uint32)])
    return jnp.concatenate([head, rows, *(c[None] for c in computed)])


def _count_up(acc, add):
    """``acc`` (2, ...) uint32, low words over high words (a decode
    program's (2, 4 + count) an op, one word more where its experts
    run as the kernel and two where a step chooses; the prompt programs'
    (2, 2, ops)), and
    ``add`` of the shape of one: a 64-bit count in two words, so that a
    server that never restarts does not wrap (1,024 pairs a step fill 32
    bits in 4 M steps)."""
    low = acc[0] + add
    return jnp.stack([low, acc[1] + (low < acc[0]).astype(jnp.uint32)])


# the key under which a prompt program returns, beside the ids its ops
# chose, (2, ops) uint32 in the ops' order: the pairs (live token, pick)
# whose expert each routed-experts op holds, over the rows its experts'
# products ran over (``RoutedExperts.apply``'s ``computed``: the grouped
# kernel counts them on the device, the jnp forms say them from the shapes)
HELD_PAIRS = "#held_pairs"
_count_up_jit = jax.jit(_count_up, donate_argnums=(0,))


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
    """The shared compiled-graph contract both cache layouts walk: the
    attention ops and the entry kind of each, the (tokens, positions)
    input binding, the position-embedding capacity bound, and the
    exec-params cast cache."""

    def __init__(self, ff, max_length: int):
        cm = ff.compiled
        if cm is None:
            raise ValueError("compile() the model before generating")
        self._cm = cm
        self.max_length = int(max_length)
        self._token_id = cm.input_tensors[0]
        # a graph none of whose layers reads positions (its order is in
        # its recurrent layers) has no such input
        self._pos_id = (cm.input_tensors[1] if len(cm.input_tensors) > 1
                        else None)
        pos_tid = None if self._pos_id is None else self._pos_id.tensor_id
        # what each attention op keeps for a token, chosen once (and told
        # how many devices the model's programs run over)
        kinds = {op.name: kind_for(op, pos_tid, self.max_length)
                 for op in cm.ops}
        devices = 1 if cm.mesh is None else cm.mesh.size
        self._kinds = {name: k.over(devices) for name, k in kinds.items()
                       if k is not None}
        self._attn_ops = [op for op in cm.ops if op.name in self._kinds]
        self._expert_ops = [op for op in cm.ops
                            if op.op_type is OpType.ROUTED_EXPERTS]
        # the position-embedding table bounds how far the MODEL can decode;
        # jnp.take clamps out-of-range ids silently, so enforce it here
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

    def _params_sds(self):
        """The shapes of :meth:`_exec_params`, for tracing a program
        without running it."""
        cdt = self._compute_dtype()
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, cdt if cdt is not None and jnp.issubdtype(
                    a.dtype, jnp.floating) else a.dtype), self._cm.params)

    def invalidate_params_cache(self) -> None:
        """Drop the cast copy after mutating ``cm.params`` leaves in
        place (replacing the tree, or bumping ``cm.params_version``,
        invalidates automatically)."""
        self._params_cache.invalidate()

    def _inputs(self, tokens, positions) -> Dict:
        """The graph's input activations, by tensor id."""
        acts = {self._token_id.tensor_id: tokens}
        if self._pos_id is not None:
            acts[self._pos_id.tensor_id] = positions
        return acts

    def _forward_block(self, params, acts, attn, experts=None, tail=None):
        """Walk the op graph over the activations in ``acts``; ``attn``
        handles each attention op, given ``(op, weights, x, positions)``
        (it calls the op's entry kind in the program's cache layout) and
        ``experts``, where given, each routed-experts op, given ``(op,
        weights, x)`` and, behind another whose router hands a state on,
        that state; it returns the op's outputs, a list (the paged
        programs keep the routing they chose). Returns the (B, S, vocab)
        float32 logits.

        ``tail`` says what to do behind the last op that keeps something
        for a sequence, where every op left works a position at a time:
        ``"skip"`` ends the walk there (a chunk that is not a prompt's
        last writes its entries and needs nothing more; returns None), a
        (B,) int32 array keeps that one position of each row, so that the
        head is computed for it alone (returns (B, 1, vocab))."""
        ctx = LowerCtx(mesh=None, training=False, aux_losses=[],
                       compute_dtype=None)
        # (a routed-experts op picks its form by the model's mesh: its
        # kernel is one device's)
        on_mesh = LowerCtx(mesh=self._cm.mesh, training=False,
                           aux_losses=[], compute_dtype=None)
        positions = (None if self._pos_id is None
                     else acts[self._pos_id.tensor_id])
        for op in self._cm.ops:
            ins = [acts[t.tensor_id] for t in op.layer.inputs]
            p = weights_of(op, params)
            with op_scope(op):
                if op.name in self._kinds:
                    outs = [attn(op, p, ins[0], positions)]
                elif op.op_type is not OpType.ROUTED_EXPERTS:
                    outs = op.forward(ctx, ins, p)
                elif experts is not None:
                    outs = experts(op, p, *ins)
                else:
                    outs = op.forward(on_mesh, ins, p)
            for out, t in zip(outs, op.layer.outputs):
                acts[t.tensor_id] = out
            if tail is not None and op is self._attn_ops[-1]:
                if isinstance(tail, str):
                    return None
                with fixed_scope("tail"):
                    acts = {tid: jnp.take_along_axis(
                        a, tail.reshape((-1, 1) + (1,) * (a.ndim - 2)),
                        axis=1) for tid, a in acts.items()}
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
        tokens_sds = jax.ShapeDtypeStruct((self.batch_size, 1), jnp.int32)
        self.audit_report, self.exec_telemetry = _audit_serving_program(
            "serving.decode_step", self._step,
            (self._params_sds(), tokens_sds, jax.eval_shape(self.init_cache),
             jax.ShapeDtypeStruct((), jnp.int32)), self._cm.config)

    # ---- cache ------------------------------------------------------------
    def init_cache(self) -> Dict[str, Tuple[jnp.ndarray, ...]]:
        """The dense form of each op's entry, as its kind shapes it: a
        (k, v) pair of (B, max_length, H, D), a latent op's one (B,
        max_length, width) array of rows, a state and its convolution's
        tail a row."""
        dt = self._compute_dtype() or jnp.float32
        return {name: tuple(jnp.zeros(a.shape, a.dtype) for a in
                            kind.dense_shapes(self.batch_size,
                                              self.max_length, dt))
                for name, kind in self._kinds.items()}

    # ---- one block step (prefill: S=prompt, decode: S=1) -----------------
    def _block_step(self, params, tokens, cache, offset):
        b, s_blk = tokens.shape
        positions = offset + jax.lax.iota(jnp.int32, s_blk)[None, :]
        positions = jnp.broadcast_to(positions, (b, s_blk))
        acts = self._inputs(tokens, positions)
        new_cache = dict(cache)

        def attn(op, p, x, pos):
            out, new_cache[op.name] = self._kinds[op.name].dense_step(
                op, p, x, pos, new_cache[op.name], offset)
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
                 prefill_chunk: Optional[int] = None,
                 kv_dtype: str = "float32",
                 kv_divergence_budget: Optional[float] = None,
                 calibrate: bool = True):
        super().__init__(ff, max_length)
        if decode_slots < 1:
            raise ValueError(f"decode_slots {decode_slots} < 1")
        self.decode_slots = int(decode_slots)
        self.block_size = int(block_size)
        # prompts prefilled as chunks of this many tokens, each continuing
        # from what the chunks before left (None: a whole prompt a bucket)
        self.prefill_chunk = int(prefill_chunk) if prefill_chunk else None
        if self.prefill_chunk:
            if self.prefill_chunk % self.block_size:
                raise ValueError(
                    f"prefill_chunk {self.prefill_chunk} is not a multiple "
                    f"of block_size {self.block_size}")
            prefill_buckets = [self.prefill_chunk]
        self.max_blocks_per_request = max(
            1, math.ceil(self.max_length / self.block_size))
        if num_blocks is None:
            # auto: every decode slot can hold one worst-case request,
            # plus the reserved null block
            num_blocks = (self.decode_slots * self.max_blocks_per_request
                          + 1)
        self.kv_dtype = str(kv_dtype)
        self.pool = self._new_pool(int(num_blocks))
        if self.prefill_chunk:
            # (the kinds as the pool stores them: a pair takes chunks,
            # its int8 form does not)
            kinds = self.pool.kinds
            whole = [name for name, k in kinds.items() if not k.chunked]
            if whole:
                raise ValueError(
                    f"prefill_chunk: a {kinds[whole[0]].name} cache "
                    f"entry prefills a prompt whole, it does not continue "
                    f"from a chunk ({whole[0]} and {len(whole) - 1} more)")
        # one small accumulator for each routed-experts op (_count_up),
        # donated to the decode program beside the pool and returned by
        # it: counted on the device, fetched only by expert_stats(). The
        # lock covers the moment between a dispatch that donates them and
        # the assignment of what it returns.
        self._expert_acc: Dict[str, jax.Array] = {
            op.name: jnp.zeros(
                (2, 4 + op.count + {"kernel": 1, "counted": 2}.get(
                    self._decode_form(op), 0)), jnp.uint32)
            for op in self._expert_ops}
        self._expert_acc_lock = threading.Lock()
        # the same for the prompt programs: the pairs they named among the
        # held experts over the rows their experts' products ran over
        # (both counted on the device, HELD_PAIRS), two words each
        self._prompt_acc = jnp.zeros((2, 2, len(self._expert_ops)),
                                     jnp.uint32)
        # the greedy ids the last decode step chose, (slots,) int32 on
        # the device: the next step's ``prev_ids``. Placed as the
        # program places what it returns (replicated over the model's
        # mesh, committed), so that the first step's signature is every
        # later step's and costs no compile of its own.
        self._ids: jax.Array = jax.device_put(
            np.zeros((self.decode_slots,), np.int32),
            NamedSharding(self._cm.mesh, PartitionSpec()))
        # the ids the last prefill or decode call's ops chose, {op name:
        # int32 device array}: a routed-experts op's experts (rows..., k),
        # a selecting attention op's key blocks (rows, key-value heads,
        # positions, picks); kept for whoever asks (a comparison with a
        # reference), never fetched by the scheduler's loop
        self.last_routing: Dict[str, jax.Array] = {}
        if prefill_buckets is None:
            prefill_buckets = default_prefill_buckets(self.max_length)
        self.prefill_buckets = sorted(
            {min(int(bkt), self.max_length) for bkt in prefill_buckets})
        if (self.prefill_buckets[-1] < self.max_length
                and not self.prefill_chunk):
            self.prefill_buckets.append(self.max_length)
        # the chunk programs, by whether they compute the head
        self._chunk_fns: Dict[bool, object] = {}
        self._decode = jax.jit(self._decode_step, donate_argnums=(2, 5))
        # one verify executable per window width W=k+1 (spec_k is a
        # session knob, so in practice this holds one entry)
        self._verify_fns: Dict[int, object] = {}
        # how each program's attention reads the pool, fixed when the
        # program is built: "kernel" (paged attention, in place) or
        # "gather" (the jnp path); "verify" appears with its program.
        # "chunk": how a prompt's chunk is attended, "kernel" (every
        # kind's chunk through kernels/chunk_attention.py) or "scan" (a
        # walk in jnp); None where prompts are prefilled whole.
        # "decode_chunk_tokens": the tokens a loop iteration of the decode
        # kernel brings (the narrowest of the ops' kernels; None where
        # the step gathers)
        self.attention_path: Dict[str, Union[None, str, int]] = {}
        self.attention_path_by_entry: Dict[str, Dict] = {}
        self._set_attention_path()
        self._prefill_fns: Dict[Tuple[int, int], object] = {}
        # how the prefill programs run the recurrence of the ops that
        # keep a state, fixed when a program is built, from the rule its
        # lowering asks: "kernel" where every program built so far takes
        # the fused whole-sequence kernel (the widest bucket speaks
        # until one is), else "scan"; None in a pool that keeps no state
        self.prefill_path = self._prefill_path(self.prefill_buckets[-1])
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
            _t0_calibrate = time.perf_counter()
            with span("serving.build.calibrate", cat="serving",
                      kv_dtype=self.kv_dtype):
                self._calibrate_kv_quant(kv_divergence_budget)
            metrics_registry().counter("setup.calibration_s").inc(
                time.perf_counter() - _t0_calibrate)

    # ---- compiled programs -------------------------------------------------
    def _decode_step(self, params, tokens, pool, addr, seq_lens,
                     expert_acc, prev_ids, take_prev):
        """One decode step for all slots: tokens (slots,) int32, pool
        {op: arena entry} donated, addr the slots' ``Addresses`` (tables
        (slots, MB) int32 and, over a pool of states, rows), seq_lens
        (slots,) int32, expert_acc {routed-experts op: counters}
        donated, prev_ids (slots,) int32 the ids the step before
        returned (not donated: the host may still be fetching them),
        take_prev (slots,) bool. Slot i's token is ``prev_ids[i]`` where
        ``take_prev[i]``, else ``tokens[i]``: a greedy token goes from
        one step to the next without leaving the device. Returns
        ((slots, vocab) float32 logits, new pool, the expert ids
        chosen, new counters, (slots,) int32 ids: each row's first
        maximum, what ``np.argmax`` of the fetched row gives)."""
        with fixed_scope("sample"):    # the greedy hand-over, both ends
            tokens = jnp.where(take_prev, prev_ids, tokens)[:, None]
        positions = seq_lens[:, None]                           # (slots, 1)
        acts = self._inputs(tokens, positions)
        new_pool = dict(pool)
        new_acc = dict(expert_acc)
        routed: Dict[str, jax.Array] = {}
        # a slot with no block reserved is idle: its token is padding
        active = addr.tables[:, 0] != NULL_BLOCK

        def attn(op, p, x, pos):
            out, new_pool[op.name], *picked = self.pool.kinds[op.name].step(
                op, p, x, pos, new_pool[op.name], addr, seq_lens)
            routed.update({op.name: ids for ids in picked})
            return out

        def experts(op, p, x, *prev):
            x2d = x.reshape(-1, x.shape[-1])
            ids, gates, state = op.route(p, x2d, None, *prev)
            routed[op.name] = ids
            computed: List[jax.Array] = []

            def count():
                with fixed_scope("counters"):
                    new_acc[op.name] = _count_up(
                        new_acc[op.name],
                        _expert_counts(op, ids, active, *computed))

            if self._decode_form(op) in ("kernel", "counted"):
                # the kernel reads the matrices of the experts its rows
                # name, and a counted step is the kernel's or the dense
                # form's by how many they name: an idle slot's padding
                # names none (``active``), and the rows the kernel says
                # it ran, and whether a counted step ran it, go into the
                # op's counters (so they are counted behind it; a dense
                # step counts first, as it always did, and its program
                # keeps its text)
                y = op.apply(p, x2d, ids, gates, computed, self._cm.mesh,
                             active)
                count()
            else:
                count()
                y = op.apply(p, x2d, ids, gates, mesh=self._cm.mesh)
            return op.outputs(x, y, state)

        logits = self._forward_block(params, acts, attn, experts)[:, -1, :]
        with fixed_scope("sample"):
            ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return logits, new_pool, routed, new_acc, ids

    def _verify_step(self, params, tokens, pool, addr, seq_lens):
        """Speculative verify: tokens (slots, W) int32 — each slot's
        last accepted token followed by W-1 draft proposals, at absolute
        positions ``seq_lens .. seq_lens + W - 1``. Writes the rows of ALL
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
        acts = self._inputs(tokens, positions)
        new_pool = dict(pool)

        def attn(op, p, x, pos):
            out, new_pool[op.name] = self.pool.kinds[op.name].step(
                op, p, x, pos, new_pool[op.name], addr, seq_lens)
            return out

        logits = self._forward_block(params, acts, attn)
        return logits, new_pool, {}

    def _prefill_step(self, params, tokens, pool, addr, lengths):
        """Bucketed prefill for a GROUP of requests: tokens (P, Sb)
        int32 (each prompt padded to the bucket), pool donated, addr the
        prompts' ``Addresses`` (tables (P, MB) int32 and, over a pool of
        states, rows), lengths (P,) int32 true prompt lengths. Rows
        are independent (padding keys are causally masked for every
        valid query row, padding rows go to the null block, a state
        stops at its prompt's true length), so one
        multi-prompt dispatch computes exactly what P single-prompt
        dispatches would, in one XLA program. Returns ((P, vocab)
        float32 logits of each row's last prompt position, new pool,
        the expert ids chosen): that row is all a caller
        reads, and the other Sb - 1 never leave the device (fetched
        whole they were 63-84 MB a prefill at 20480 wide, a tenth of a
        serving loop's time: PERF.md section 6, PR 27)."""
        b, s_blk = tokens.shape
        positions = jnp.broadcast_to(
            jax.lax.iota(jnp.int32, s_blk)[None, :], (b, s_blk))
        acts = self._inputs(tokens, positions)
        new_pool = dict(pool)
        routed: Dict[str, jax.Array] = {}

        def attn(op, p, x, pos):
            out, new_pool[op.name], *picked = self.pool.kinds[
                op.name].prefill(op, p, x, pos, new_pool[op.name], addr,
                                 lengths)
            routed.update({op.name: ids for ids in picked})
            return out

        held: List[jax.Array] = []
        computed: List[jax.Array] = []
        logits = self._forward_block(
            params, acts, attn, self._routing_kept(
                routed, lambda s: positions < lengths[:, None], held,
                computed))
        if held:
            routed[HELD_PAIRS] = jnp.stack([jnp.stack(held),
                                            jnp.stack(computed)])
        with fixed_scope("tail"):
            last = logits[jnp.arange(b), jnp.maximum(lengths - 1, 0)]
        return last, new_pool, routed

    def _chunk_step(self, params, tokens, pool, addr, offsets, lengths, *,
                    head: bool):
        """One chunk of a prompt a row: tokens (P, C) int32 at positions
        ``offsets .. offsets + C - 1`` (offsets (P,) int32, multiples of
        the chunk), of which the first ``lengths`` (P,) count, BEHIND what
        the chunks before wrote where ``addr`` says: the blocks and, over
        a pool of states, the rows. Every op that keeps something
        continues from it (its kind's ``chunk``). ``head`` (static): the
        chunk is its prompts' last, and the program returns the (P, vocab)
        float32 logits of each row's last position, the head computed for
        that position alone; else the walk ends behind the last such op
        and it returns None in their place. Also returns the new pool and
        the ids the ops chose (experts, selected blocks)."""
        positions = offsets[:, None] + jax.lax.iota(
            jnp.int32, tokens.shape[1])[None, :]
        acts = self._inputs(tokens, positions)
        new_pool = dict(pool)
        routed: Dict[str, jax.Array] = {}

        def attn(op, p, x, pos):
            out, new_pool[op.name], *picked = self.pool.kinds[op.name].chunk(
                op, p, x, pos, new_pool[op.name], addr, offsets, lengths)
            routed.update({op.name: ids for ids in picked})
            return out

        held: List[jax.Array] = []
        computed: List[jax.Array] = []
        logits = self._forward_block(
            # (behind the tail's cut a row is its last live position)
            params, acts, attn, self._routing_kept(
                routed, lambda s: (
                    positions < (offsets + lengths)[:, None]
                    if s == positions.shape[1]
                    else jnp.ones((positions.shape[0], s), bool)), held,
                computed),
            tail=jnp.maximum(lengths - 1, 0) if head else "skip")
        if held:
            # (a chunk that is not its prompt's last ends behind the last
            # attention op: the expert layers after it did not run)
            rest = [jnp.zeros((), jnp.uint32)] * (len(self._expert_ops)
                                                  - len(held))
            routed[HELD_PAIRS] = jnp.stack([jnp.stack(held + rest),
                                            jnp.stack(computed + rest)])
        return (logits[:, 0] if head else None), new_pool, routed

    def _routing_kept(self, routed: Dict[str, jax.Array], live=None,
                      held=None, computed=None):
        """What a prompt program does with a routed-experts op: route,
        keep the (rows, positions, k) expert ids in ``routed``, apply;
        with ``held`` a list and ``live(positions)`` giving (rows,
        positions) bool, append the count of the live tokens' pairs whose expert the
        op holds, and to ``computed`` the rows its products ran over."""
        def experts(op, p, x, *prev):
            x2d = x.reshape(-1, x.shape[-1])
            ids, gates, state = op.route(p, x2d, None, *prev)
            routed[op.name] = ids.reshape(x.shape[:2] + (-1,))
            if held is not None:
                with fixed_scope("counters"):
                    mine = (ids >= op.first) & (ids < op.first + op.count)
                    held.append(jnp.sum(mine
                                        & live(x.shape[1]).reshape(-1, 1),
                                        dtype=jnp.uint32))
            return op.outputs(x, op.apply(p, x2d, ids, gates, computed,
                                          self._cm.mesh), state)

        return experts

    def _count_prompt_rows(self) -> None:
        """After a prompt program: its held pairs and the rows its
        experts computed into the device-side counts."""
        counts = self.last_routing.pop(HELD_PAIRS, None)
        if counts is None:
            return
        with self._expert_acc_lock:
            self._prompt_acc = _count_up_jit(self._prompt_acc, counts)

    def _new_pool(self, num_blocks: int) -> PagedKVPool:
        """A pool of the ops' entries stored as ``kv_dtype`` says, and
        with it the kinds the programs call (``pool.kinds``): the one
        place either is made, so a kind cannot outlive its arenas. Of
        per-request entries it holds a row a decode slot and the null
        row: a request is admitted into a free slot."""
        return PagedKVPool(
            self._kinds, num_blocks=num_blocks, block_size=self.block_size,
            max_blocks_per_request=self.max_blocks_per_request,
            dtype=self._compute_dtype() or jnp.float32,
            kv_dtype=self.kv_dtype, num_rows=self.decode_slots + 1)

    def _addresses(self, tables: np.ndarray) -> Addresses:
        """The programs' one address argument for the requests of
        ``tables`` (N, MB): the tables and, over a pool of states, the
        row each one's request holds."""
        tables = np.asarray(tables, np.int32)
        rows = self.pool.rows_of(tables)
        return Addresses(jnp.asarray(tables),
                         None if rows is None else jnp.asarray(rows))

    def _decode_form(self, op) -> str:
        """The form a decode step's slots take through a routed-experts
        op's held experts (``op.expert_form``, of a call that says which
        of its rows are live)."""
        return op.expert_form(self.decode_slots, self._compute_dtype(),
                              self._cm.mesh, active=True)

    def expert_stats(self) -> Dict[str, Dict]:
        """Per routed-experts op, counted on the device over the decode
        steps' active slots: ``steps``, ``pairs_routed`` (tokens x picks),
        ``pairs_held`` (those whose expert this op holds),
        ``idle_held_experts`` (held experts that got no row, summed over
        steps), ``rows_per_held_expert`` (count,), ``kernel_steps`` (the
        steps whose experts ran as the kernel). One fetch of a few
        hundred bytes, which waits for a decode step in flight; {} for a
        graph with no such op."""
        if not self._expert_ops:
            return {}
        with self._expert_acc_lock:
            fetched, prompt = jax.device_get((self._expert_acc,
                                              self._prompt_acc))
        prompt = prompt.astype(np.uint64)
        prompt = ((prompt[1] << np.uint64(32)) | prompt[0]).tolist()
        dtype = self._compute_dtype()         # None: the graph's own
        out = {}
        mesh = self._cm.mesh
        for i, op in enumerate(self._expert_ops):
            acc = fetched[op.name].astype(np.uint64)
            acc = [int(v) for v in (acc[1] << np.uint64(32)) | acc[0]]
            form = self._decode_form(op)
            step_rows = op.rows_computed(self.decode_slots, dtype, mesh,
                                         active=True)
            out[op.name] = {
                "held": [op.first, op.count], "n_routed": op.n_routed,
                "steps": acc[0], "pairs_routed": acc[1],
                "pairs_held": acc[2], "idle_held_experts": acc[3],
                "rows_per_held_expert": acc[4:4 + op.count],
                # how the held experts' products ran: the rows they went
                # over (a decode step's from the shapes, or as the step
                # counted them where the kernel ran and the routing says
                # them: the word behind the experts' rows; the prompt
                # programs' as those counted them) beside the rows the
                # routing named (``pairs_held``), and by which form
                # (``op.expert_form``; a prompt's at the widest bucket);
                # of a decode program whose steps choose ("counted"), how
                # many chose the kernel: the accumulator's last word
                "form_decode": form,
                "form_prefill": op.expert_form(self.prefill_buckets[-1],
                                               dtype, mesh),
                # how the router picked its experts in those programs
                # (``op.select_form``: "passes" or "sort"; its rule reads
                # the op's k and n_routed and no rows, so one word twice)
                "select_decode": op.select_form(),
                "select_prefill": op.select_form(),
                "rows_computed": (acc[4 + op.count] if step_rows is None
                                  else acc[0] * step_rows),
                "kernel_steps": (acc[-1] if form == "counted"
                                 else acc[0] * (form == "kernel")),
                "prompt_pairs_held": prompt[0][i],
                "prompt_rows_computed": prompt[1][i]}
        return out

    def _attention_path(self, window: int) -> str:
        """What a W-token step does with the pool as it is now: "kernel"
        where every op's entry, of whatever kind that has a kernel to read
        it by (a kind of ``one_form`` has none to fall back from), is read
        in place, else "gather"."""
        return "kernel" if all(
            self.pool.kinds[op.name].reads_in_place(
                op, self.pool.kv[op.name], self.decode_slots, window,
                self.max_blocks_per_request)
            for op in self._attn_ops
            if not self.pool.kinds[op.name].one_form) else "gather"

    def _set_attention_path(self) -> None:
        """The decode and chunk programs' entries of ``attention_path``,
        from the pool as it is now: ``"kernel"`` where every op's entry,
        of whatever kind, takes it; and the same by kind of entry
        (``attention_path_by_entry``: a model's full layers beside its
        windowed ones), so that a chip run says what it timed in each."""
        by: Dict[str, Dict[str, Optional[str]]] = {}
        for op in self._attn_ops:
            kind, entry = self.pool.kinds[op.name], self.pool.kv[op.name]
            said = by.setdefault(kind.name, {
                "decode": "kernel",
                "chunk": "kernel" if self.prefill_chunk else None})
            if not kind.reads_in_place(op, entry, self.decode_slots, 1,
                                       self.max_blocks_per_request):
                said["decode"] = "gather"
            if self.prefill_chunk and kind.chunk_path(
                    entry, 1, self.prefill_chunk,
                    self.max_blocks_per_request,
                    self._compute_dtype() or jnp.float32) != "kernel":
                said["chunk"] = "scan"
        self.attention_path_by_entry = by
        # (of the whole program: the kinds that have a kernel to fall back
        # from; a kind of one form says "gather" of itself above)
        one_form = {k.name for k in self.pool.kinds.values() if k.one_form}
        decode = ("gather" if any(s["decode"] == "gather"
                                  for name, s in by.items()
                                  if name not in one_form) else "kernel")
        chunk = ("scan" if any(s["chunk"] == "scan" for s in by.values())
                 else "kernel") if self.prefill_chunk else None
        chunks = [self.pool.kinds[op.name].decode_chunk_tokens(
            self.pool.kv[op.name], self.max_blocks_per_request)
            for op in self._attn_ops] if decode == "kernel" else []
        self.attention_path.update(
            decode=decode, chunk=chunk,
            decode_chunk_tokens=min((c for c in chunks if c), default=None))

    def _prefill_path(self, bucket: int) -> Optional[str]:
        said = {kind.prefill_path(bucket) for kind in self.pool.kinds.values()}
        said.discard(None)
        return ("kernel" if said == {"kernel"} else "scan") if said else None

    def _prefill_fn(self, bucket: int, width: int = 1):
        """The (bucket, row-width) executable — the seen-set is the
        dict itself, so ``serving.prefill_bucket_compiles`` counts
        distinct compiled shapes, not dispatches."""
        key = (bucket, width)
        fn = self._prefill_fns.get(key)
        if fn is None:
            fn = jax.jit(self._prefill_step, donate_argnums=(2,))
            self._prefill_fns[key] = fn
            if self._prefill_path(bucket) == "scan":
                self.prefill_path = "scan"
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
        pool_sds, acc_sds = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            (self.pool.kv, self._expert_acc))
        # tokens, seq_lens, prev_ids and the rows: one (slots,) int32 each
        lens_sds = jax.ShapeDtypeStruct((self.decode_slots,), jnp.int32)
        addr_sds = Addresses(
            jax.ShapeDtypeStruct(
                (self.decode_slots, self.max_blocks_per_request), jnp.int32),
            lens_sds if self.pool.num_rows else None)
        take_sds = jax.ShapeDtypeStruct((self.decode_slots,), jnp.bool_)
        self.audit_report, self.exec_telemetry = _audit_serving_program(
            "serving.paged_decode_step", self._decode,
            (self._params_sds(), lens_sds, pool_sds, addr_sds, lens_sds,
             acc_sds, lens_sds, take_sds), self._cm.config)

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
        if self.prefill_chunk:
            return np.stack([self._prefill_in_chunks(p, t)
                             for p, t in zip(prompts, tables)])
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
                self._addresses(tabs), jnp.asarray(lengths))
            self._count_prompt_rows()
        return self._fetch(logits)[:len(arrs)]

    def _prefill_in_chunks(self, prompt, table) -> np.ndarray:
        """A whole prompt, chunk after chunk; its last position's logits."""
        prompt = np.asarray(prompt, np.int32).ravel()
        if not 1 <= prompt.shape[0] <= self.max_length:
            raise ValueError(f"prompt of {prompt.shape[0]} tokens: expected "
                             f"1 .. max_length {self.max_length}")
        for at in range(0, prompt.shape[0], self.prefill_chunk):
            logits = self.prefill_chunk_at(prompt, table, at)
        return logits

    def prefill_chunk_at(self, prompt: np.ndarray, table: np.ndarray,
                         offset: int) -> Optional[np.ndarray]:
        """The chunk of ``prompt`` (S,) that starts at ``offset`` (a
        multiple of ``prefill_chunk``; the chunks before it have run),
        written through ``table``. Where it is the prompt's last, waits
        and returns the last position's (vocab,) float32 logits; else
        returns None without waiting for the device."""
        c = self.prefill_chunk
        part = prompt[offset:offset + c]
        last = offset + c >= prompt.shape[0]
        fn = self._chunk_fns.get(last)
        if fn is None:
            def program(*args):
                return self._chunk_step(*args, head=last)

            # the name a device trace shows the program under
            program.__name__ = "_chunk_step_head" if last else "_chunk_step"
            fn = jax.jit(program, donate_argnums=(2,))
            self._chunk_fns[last] = fn
            from ..obs.metrics import metrics_registry

            metrics_registry().counter(
                "serving.prefill_bucket_compiles").inc()
        toks = np.zeros((1, c), np.int32)
        toks[0, :part.shape[0]] = part
        tabs = np.full((1, self.max_blocks_per_request), NULL_BLOCK, np.int32)
        table = np.asarray(table, np.int32).ravel()
        tabs[0, :table.shape[0]] = table
        with span("serving.loop.dispatch", cat="serving"):
            logits, self.pool.kv, self.last_routing = fn(
                self._exec_params(), jnp.asarray(toks), self.pool.kv,
                self._addresses(tabs), jnp.asarray([offset], jnp.int32),
                jnp.asarray([part.shape[0]], jnp.int32))
            self._count_prompt_rows()
        return self._fetch(logits)[0] if last else None

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
                self.pool.kv, self._addresses(tables),
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
        self.check_window(w)
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
                self._addresses(tables),
                jnp.asarray(np.asarray(seq_lens, np.int32)))
        return self._fetch(logits)

    def check_window(self, window: int) -> None:
        """Refuse, loudly, a step of ``window`` new tokens a slot (a
        speculative verify of ``spec_k + 1``) that an entry kind of this
        model does not take, rather than fall back."""
        narrow = [name for name, kind in self._kinds.items()
                  if kind.max_window is not None and window > kind.max_window]
        if narrow:
            raise ValueError(
                f"speculative verify over a "
                f"{self._kinds[narrow[0]].name} cache entry is not "
                f"built ({narrow[0]} and {len(narrow) - 1} more): serve "
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
        acts = self._inputs(
            jnp.asarray(tokens[None, :]),
            jnp.asarray(np.arange(s, dtype=np.int32)[None, :]))

        def attn(op, p, x, pos):
            return self._kinds[op.name].whole(op, p, x, pos)[0]

        def experts(op, p, x, *prev):
            x2d = x.reshape(-1, x.shape[-1])
            ids, gates, state = op.route(
                p, x2d, jnp.asarray(routing[op.name]), *prev)
            return op.outputs(x, op.apply(p, x2d, ids, gates,
                                          mesh=self._cm.mesh), state)

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
            # the experts' routing alone: a selecting attention op's
            # picks are its own business
            experts = [op.name for op in self._expert_ops]

            def every_row(ids):
                """(a chunked prompt's last layer routes its last
                position alone, the head's: the rows before it are read
                by nothing, and take its ids)"""
                return np.concatenate(
                    [np.repeat(ids[:1], prompt_len - len(ids), axis=0), ids])

            routed = {k: [every_row(
                np.asarray(self.last_routing[k])[0, :prompt_len])]
                for k in experts}
            toks = np.zeros(self.decode_slots, np.int32)
            toks[0] = nxt
            tabs = np.full((self.decode_slots, self.max_blocks_per_request),
                           NULL_BLOCK, np.int32)
            tabs[0, :table.shape[0]] = table
            lens = np.zeros(self.decode_slots, np.int32)
            lens[0] = prompt_len
            q_row = self.decode(toks, tabs, lens)[0]
            for k in experts:
                routed[k].append(np.asarray(self.last_routing[k])[:1])
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
        self.pool = self._new_pool(self.pool.num_blocks)  # concurrency: race-ok (calibration runs inside __init__, before the scheduler's thread or any stats() reader exists)
        self._set_attention_path()


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
