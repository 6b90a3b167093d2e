"""Single-dispatch pipeline engine: the whole schedule as ONE program.

The host-driven engine (:mod:`.pipeline`) issues one program dispatch per
schedule action — O(stages × microbatches) per train step, each paying
host-side dispatch latency, with Python-side fences exposing the bubble.
This engine lowers the ENTIRE warmup/steady/cooldown schedule into one
jitted SPMD program:

* ``lax.scan`` over schedule ticks; per tick every stage executes its
  scheduled action (``lax.switch`` over {idle, F, B, FB}, with an inner
  switch over the per-stage chunk programs — stages are heterogeneous op
  sub-graphs, not a repeated layer). Interleaved virtual stages ride the
  same tick table: the chunk a stage runs at tick t comes from a static
  per-(tick, stage) chunk table, so V chunks per stage cost nothing but
  table entries;
* stage-boundary transfers are **collective permutes over the pipe
  ring** inside ``shard_map`` — the ICI hop, expressed where it happens
  instead of as host-driven ``device_put`` edges. The ring (with its
  wrap edge) is what lets chunk c on stage S-1 feed chunk c+1 back onto
  stage 0 under interleaving; with V == 1 the wrap edge only ever
  carries zeros;
* edge-buffer and saved-input slots are **statically allocated by an
  interval pass** over the tick table (allocate at arrival/save, free
  after the consuming tick), so in-flight values never collide even
  when an interleaved stage consumes across chunks out of arrival
  order;
* gradients accumulate into a per-stage packed buffer in fixed
  microbatch order (the same order as the host engine, so per-step
  losses/grads match bit for bit up to XLA refusion);
* the per-stage optimizer update runs INSIDE the same program, with the
  optimizer hyperparameters as traced arguments — one dispatch per train
  step, O(1) instead of O(stages × microbatches).

Heterogeneous stages under one SPMD program require uniform per-device
state, so each stage's parameters / optimizer state / boundary
activations are packed into flat, padded buffers stacked over the pipe
axis (``(S, L)`` sharded one row per stage — per-device memory stays
~1/S of the model, exactly like the host engine). float32 leaves pack
verbatim, bfloat16 upcasts losslessly, int32 bit-casts; anything else
falls outside the envelope and :func:`make_pipelined_model` falls back
to the host engine.

Envelope (checked by :func:`compiled_engine_unsupported`):

* mesh families ``pipe`` and ``pipe×data``: every mesh axis except the
  pipe axis and the data axis has size 1. Under a data submesh the
  program shard_maps over BOTH axes manually: microbatches stay
  batch-sharded over the data axis, each backward's gradient
  contribution is ``psum`` over data (one unconditional collective per
  tick, outside the action switch, so every ``lax.switch`` branch
  agrees on the collective signature — the AUD005 contract), and the
  recorded per-microbatch losses/aux reduce once after the scan
  (``psum * 1/dp`` — the mean-of-equal-shard-means identity, exact for
  power-of-two shard counts). The cotangent seed carries the extra
  ``1/dp`` so local-mean vjps reproduce the host engine's global-mean
  gradients;
* schedules ``gpipe``, ``1f1b`` and ``interleaved`` (any interleave the
  schedule IR accepts);
* under a data submesh the graph must be batch-linear: ops whose
  forward or aux losses couple examples across the batch (BatchNorm
  statistics, the MoE gating/aggregation family, Dropout's full-batch
  mask) would compute different numbers per data shard than the host
  engine's GSPMD lowering — those graphs stay host-driven
  (:func:`dp_unsupported_reason`);
* backward is remat-by-construction: each backward replays its chunk's
  forward from the saved packed boundary input — only stage-boundary
  activations ever live in the scan carry, which is what makes the 1F1B
  O(num_stages) activation bound real at the buffer level
  (``saved: (K+1, A)`` with K = the interval pass's peak concurrent
  saved inputs; row K is the scratch slot chunk-0 forwards write).
"""

from __future__ import annotations

import heapq
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..core.machine import DATA_AXIS, mesh_axis_sizes
from ..ffconst import OpType
from .pipeline import PipelineConfig, PipelinedModel

_PACK_DTYPES = (jnp.float32, jnp.bfloat16, jnp.int32)

# ops whose math couples examples ACROSS the batch: under the manual
# data-submesh lowering each data shard would compute its own statistics
# (BatchNorm), routing fractions (the MoE family's load-balance aux is a
# product of batch means, not a mean of per-example terms), or dropout
# mask stream — valid training, but not bit-identical to the host
# engine's GSPMD full-batch lowering, so those graphs stay host-driven.
_DP_BATCH_COUPLED_OPS = frozenset({
    OpType.BATCHNORM, OpType.DROPOUT, OpType.GROUP_BY, OpType.AGGREGATE,
    OpType.AGGREGATE_SPEC, OpType.GROUP_BY_STACKED, OpType.EXPERT_LINEAR,
    OpType.AGGREGATE_STACKED, OpType.CACHE,
})


def dp_unsupported_reason(ops, dp: int) -> Optional[str]:
    """None when the op graph is batch-linear (safe under the manual
    data-submesh lowering); else the one-line reason. dp == 1 is always
    fine — there is no data axis to disagree over."""
    if dp <= 1 or ops is None:
        return None
    bad = sorted({op.op_type.value for op in ops
                  if op.op_type in _DP_BATCH_COUPLED_OPS})
    if bad:
        return (f"batch-coupled op(s) {bad} under a data submesh "
                f"(per-shard statistics would diverge from the host "
                f"engine's full-batch lowering)")
    return None


def compiled_engine_unsupported(mesh: Mesh, cfg: PipelineConfig,
                                ops=None,
                                batch_size: Optional[int] = None
                                ) -> Optional[str]:
    """None when the single-dispatch engine can run on (mesh, cfg); else
    a one-line reason (the factory's fallback message and the forced-
    engine error). ``ops``/``batch_size`` sharpen the data-submesh
    checks when the caller has them (the factory and the engine ctor
    do; mesh-only callers get the mesh-family answer)."""
    if cfg.schedule not in ("gpipe", "1f1b", "interleaved"):
        return (f"schedule {cfg.schedule!r} is host-driven "
                f"(compiled supports gpipe|1f1b|interleaved)")
    sizes = mesh_axis_sizes(mesh)
    extra = {a: s for a, s in sizes.items()
             if a not in (cfg.axis, DATA_AXIS) and s > 1}
    if extra:
        return (f"mesh has non-trivial axes {extra} besides "
                f"'{cfg.axis}'/'{DATA_AXIS}' — compiled covers the pipe "
                f"and pipe×data families only")
    if sizes.get(cfg.axis, 1) < 2:
        return f"mesh {cfg.axis} axis has degree < 2"
    dp = sizes.get(DATA_AXIS, 1)
    if dp > 1:
        reason = dp_unsupported_reason(ops, dp)
        if reason:
            return reason
        if batch_size is not None:
            M = max(1, int(cfg.num_microbatches))
            if batch_size % M or (batch_size // M) % dp:
                return (f"batch {batch_size} does not split into "
                        f"{M} microbatches × {dp} data shards")
    return None


# ------------------------------------------------------------- packing
def _leaf_segments(tree) -> Tuple[List[Tuple], Any, int]:
    """(segments, treedef, total_f32_len) for a pytree of arrays/specs.
    Each segment is (offset, length, shape, dtype). Raises
    NotImplementedError on unpackable dtypes — the factory's fallback
    trigger."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    segs = []
    off = 0
    for l in leaves:
        dt = jnp.dtype(l.dtype)
        if dt not in _PACK_DTYPES:
            raise NotImplementedError(
                f"cannot pack dtype {dt} into the single-dispatch "
                f"engine's f32 buffers")
        n = int(np.prod(l.shape)) if l.shape else 1
        segs.append((off, n, tuple(l.shape), dt))
        off += n
    return segs, treedef, off


def _pack(leaves, segs, total: int) -> jax.Array:
    """Flatten leaves into one (total,) f32 buffer. bf16 upcasts
    (lossless), int32 bit-casts (exact); ``float0`` leaves — the vjp
    cotangents of integer boundary tensors (MoE routing indices crossing
    a stage cut) — carry no information and pack as zeros."""
    parts = []
    used = 0
    for l, (off, n, shape, dt) in zip(leaves, segs):
        if jnp.dtype(getattr(l, "dtype", jnp.float32)) == \
                jax.dtypes.float0:
            parts.append(jnp.zeros((n,), jnp.float32))
            used += n
            continue
        v = jnp.reshape(l, (-1,)) if l.shape else jnp.reshape(l, (1,))
        if dt == jnp.bfloat16:
            v = v.astype(jnp.float32)
        elif dt == jnp.int32:
            v = jax.lax.bitcast_convert_type(v, jnp.float32)
        parts.append(v)
        used += n
    if total > used:
        parts.append(jnp.zeros((total - used,), jnp.float32))
    return jnp.concatenate(parts) if parts else jnp.zeros((total,),
                                                          jnp.float32)


def _unpack(buf: jax.Array, segs, treedef, cotangent: bool = False):
    """Inverse of :func:`_pack`. With ``cotangent=True`` integer
    segments yield ``float0`` zeros — the only cotangent type jax.vjp
    accepts for integer primal outputs."""
    leaves = []
    for off, n, shape, dt in segs:
        if cotangent and dt == jnp.int32:
            leaves.append(np.zeros(shape, jax.dtypes.float0))
            continue
        v = jax.lax.dynamic_slice_in_dim(buf, off, n)
        if dt == jnp.bfloat16:
            v = v.astype(jnp.bfloat16)
        elif dt == jnp.int32:
            v = jax.lax.bitcast_convert_type(v, jnp.int32)
        leaves.append(jnp.reshape(v, shape))
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ------------------------------------------------------------- tables
_IDLE, _F, _B, _FB = 0, 1, 2, 3


def _interval_slots(T: int, S: int, produces: Dict, consumes: Dict
                    ) -> Tuple[np.ndarray, np.ndarray, int]:
    """Static slot assignment by interval allocation: ``produces`` maps
    ``(chunk, mb) -> (tick, stage)`` where the value lands in a stage's
    buffer, ``consumes`` maps the same key to the tick/stage that reads
    it. A slot is taken from the stage's free pool at the producing
    tick and returned AFTER the consuming tick (an arrival and a
    same-tick consumption of an older value therefore never share a
    slot — the engine integrates arrivals at tick start, before the
    read). Returns (write_table, read_table, ring_size); write entries
    with no event point at the scratch slot ``ring_size``."""
    w = np.full((T, S), -1, np.int64)
    r = np.zeros((T, S), np.int64)
    arr_by_tick: Dict[int, List] = {}
    con_by_tick: Dict[int, List] = {}
    for key, (t, s) in produces.items():
        arr_by_tick.setdefault(t, []).append((s, key))
    for key, (t, s) in consumes.items():
        con_by_tick.setdefault(t, []).append((s, key))
    free: List[List[int]] = [[] for _ in range(S)]
    hi = [0] * S
    slot_of: Dict = {}
    R = 0
    for t in range(T):
        for s, key in sorted(arr_by_tick.get(t, ())):
            if key not in consumes:
                continue  # produced but never read (cannot happen for a
                #            validated schedule; defensive)
            if free[s]:
                slot = heapq.heappop(free[s])
            else:
                slot = hi[s]
                hi[s] += 1
                R = max(R, hi[s])
            slot_of[key] = slot
            w[t, s] = slot
        ends = []
        for s, key in sorted(con_by_tick.get(t, ())):
            slot = slot_of.pop(key)
            r[t, s] = slot
            ends.append((s, slot))
        for s, slot in ends:
            heapq.heappush(free[s], slot)
    R = max(R, 1)
    w = np.where(w >= 0, w, R)
    return w.astype(np.int32), r.astype(np.int32), R


def _build_tables(sched) -> Dict[str, Any]:
    """Static per-(tick, stage) control tables driving the scan body:
    action kind/microbatch/chunk, edge-buffer write/read slots, and the
    saved-input save/read slots. Edge arrivals ride the ring permute in
    the scan carry — a value produced at tick t integrates at the START
    of tick t+1 on the destination stage ``(chunk±1) % S`` (the modular
    stage arithmetic is what makes interleaved wrap edges work)."""
    S, T = sched.num_stages, sched.num_ticks
    C = S * sched.interleave
    kinds = np.zeros((T, S), np.int32)
    mbs = np.zeros((T, S), np.int32)
    chs = np.zeros((T, S), np.int32)
    karr = {"F": _F, "B": _B, "FB": _FB}
    prod_f: Dict = {}
    cons_f: Dict = {}
    prod_b: Dict = {}
    cons_b: Dict = {}
    prod_s: Dict = {}
    cons_s: Dict = {}
    for t, row in enumerate(sched.ticks):
        for s, a in enumerate(row):
            if a is None:
                continue
            kinds[t, s] = karr[a.kind]
            mbs[t, s] = a.mb
            chs[t, s] = a.chunk
            if a.kind == "F" and a.chunk < C - 1:
                prod_f[(a.chunk + 1, a.mb)] = (t + 1, (a.chunk + 1) % S)
            if a.kind in ("F", "FB") and a.chunk > 0:
                cons_f[(a.chunk, a.mb)] = (t, s)
            if a.kind in ("B", "FB") and a.chunk > 0:
                prod_b[(a.chunk - 1, a.mb)] = (t + 1, (a.chunk - 1) % S)
            if a.kind == "B" and a.chunk < C - 1:
                cons_b[(a.chunk, a.mb)] = (t, s)
            # saved inputs for the remat backward: chunk-0 forwards
            # replay from the model inputs and save nothing
            if a.kind == "F" and a.chunk > 0:
                prod_s[(a.chunk, a.mb)] = (t, s)
            if a.kind == "B" and a.chunk > 0:
                cons_s[(a.chunk, a.mb)] = (t, s)
    wf, rf, R_f = _interval_slots(T, S, prod_f, cons_f)
    wb, rb, R_b = _interval_slots(T, S, prod_b, cons_b)
    sv, rs, K = _interval_slots(T, S, prod_s, cons_s)
    return dict(kinds=kinds, mbs=mbs, chunks=chs, wf=wf, rf=rf, wb=wb,
                rb=rb, sv=sv, rs=rs, R_f=R_f, R_b=R_b, K=K)


class CompiledPipelinedModel(PipelinedModel):
    """Single-dispatch engine: train_step = ONE jitted program.

    Extends the host engine (which provides stage splitting, parameter
    placement, the per-chunk programs used by ``forward_only``/eval, and
    the sync/checkpoint surface); the packed buffers used by the
    compiled step are (re)built lazily from ``stage_params`` /
    ``stage_opt_state`` on the first ``train_step`` after construction
    or any ``sync_from``, so external weight surgery (checkpoint
    restore, recompile carry-over) flows in naturally.
    """

    engine_name = "compiled"

    # class-level defaults: the stage_params/stage_opt_state property
    # setters fire during the BASE __init__, before this subclass's
    # __init__ body runs, so the state they touch must already resolve
    _packed = None
    _views_stale = False

    def __init__(self, ops, mesh, cfg: PipelineConfig, **kw):
        reason = compiled_engine_unsupported(
            mesh, cfg, ops=ops,
            batch_size=getattr(kw.get("audit_config"), "batch_size",
                               None))
        if reason is not None:
            raise NotImplementedError(reason)
        super().__init__(ops, mesh, cfg, **kw)
        S = len(self.stages)
        sizes = mesh_axis_sizes(mesh)
        self._dp = sizes.get(DATA_AXIS, 1)
        pipe_index = list(mesh.axis_names).index(cfg.axis)
        if self._dp > 1:
            data_index = list(mesh.axis_names).index(DATA_AXIS)
            flat = np.moveaxis(mesh.devices, (pipe_index, data_index),
                               (0, 1)).reshape(S, self._dp)
            self._pmesh = Mesh(flat, ("pipe", DATA_AXIS))
        else:
            flat = np.moveaxis(mesh.devices, pipe_index, 0).reshape(S)
            self._pmesh = Mesh(flat, ("pipe",))
        # static packing metadata (raises NotImplementedError on
        # unpackable dtypes BEFORE any device work — the factory's
        # fallback point)
        self._param_segs = []   # per stage: (segs, treedef, len)
        for s in range(S):
            self._param_segs.append(_leaf_segments(self.stage_params[s]))
        self._opt_segs = [
            _leaf_segments(self.stage_opt_state[s]) for s in range(S)]
        self._Lp = max(seg[2] for seg in self._param_segs)
        self._Lo = max(max(seg[2] for seg in self._opt_segs), 1)
        self._tables = _build_tables(self.schedule)
        self._packed = None       # (theta, opt) device buffers
        self._views_stale = False
        self._programs: Dict[Tuple, Any] = {}  # per (mb_shape sig) jit
        self._boundary_meta = None  # filled per microbatch shape
        # XLA executable telemetry for the schedule program (filled per
        # fresh program build when config.exec_telemetry="on")
        self.exec_telemetry = None

    # ----------------------------------------------------- pack/unpack
    def _ensure_packed(self) -> None:
        if self._packed is not None:
            return
        S = len(self.stages)
        rows_p, rows_o = [], []
        for s in range(S):
            psegs, ptd, pn = self._param_segs[s]
            leaves = jax.tree_util.tree_flatten(
                self._stage_params_raw[s])[0]
            rows_p.append(np.asarray(_pack(
                [jnp.asarray(np.asarray(l)) for l in leaves], psegs,
                self._Lp)))
            osegs, otd, on = self._opt_segs[s]
            oleaves = jax.tree_util.tree_flatten(
                self._stage_opt_state_raw[s])[0]
            rows_o.append(np.asarray(_pack(
                [jnp.asarray(np.asarray(l)) for l in oleaves], osegs,
                self._Lo)))
        sh = NamedSharding(self._pmesh, PartitionSpec("pipe"))
        theta = jax.device_put(np.stack(rows_p), sh)
        opt = jax.device_put(np.stack(rows_o), sh)
        self._packed = [theta, opt]

    def _refresh_views(self) -> None:
        """Unpack the packed training state back into the per-stage
        dict views (stage_params / stage_opt_state) on their submeshes.
        Called lazily by every dict-reading access point."""
        if not self._views_stale or self._packed is None:
            return
        self._views_stale = False
        theta = np.asarray(jax.device_get(self._packed[0]))
        opt = np.asarray(jax.device_get(self._packed[1]))
        for s in range(len(self.stages)):
            psegs, ptd, _ = self._param_segs[s]
            tree = _unpack(jnp.asarray(theta[s]), psegs, ptd)
            old = self._stage_params_raw[s]
            for opn, ws in tree.items():
                for w, v in ws.items():
                    old[opn][w] = jax.device_put(
                        np.asarray(v), old[opn][w].sharding)
            osegs, otd, _ = self._opt_segs[s]
            otree = _unpack(jnp.asarray(opt[s]), osegs, otd)

            def place(new_leaf, old_leaf):
                return jax.device_put(np.asarray(new_leaf),
                                      old_leaf.sharding)

            self._stage_opt_state_raw[s] = jax.tree_util.tree_map(
                place, otree, self._stage_opt_state_raw[s])

    # property interposition: dict reads refresh lazily; dict REBINDS
    # (sync_from, recompile reseeding) invalidate the packed buffers
    @property
    def stage_params(self):
        self._refresh_views()
        return self._stage_params_raw

    @stage_params.setter
    def stage_params(self, v):
        self._stage_params_raw = v
        self._packed = None

    @property
    def stage_opt_state(self):
        self._refresh_views()
        return self._stage_opt_state_raw

    @stage_opt_state.setter
    def stage_opt_state(self, v):
        self._stage_opt_state_raw = v
        self._packed = None

    def sync_from(self, cm) -> None:
        super().sync_from(cm)
        self._packed = None
        self._views_stale = False

    # ------------------------------------------------------- boundaries
    def _boundary_segments(self, mb: int):
        """Per-boundary packed-activation segments at PER-DEVICE
        microbatch size ``mb`` (the data-shard slice under pipe×data),
        derived by chaining jax.eval_shape over the chunk programs (the
        ONLY reliable source of boundary dtypes under mixed precision /
        integer pass-through)."""
        C = len(self.chunks)
        tid_dims = {}
        tid_dtype = {}
        for chunk in self.chunks:
            for op in chunk:
                for t in list(op.layer.inputs):
                    tid_dims[t.tensor_id] = tuple(t.dims)
                    tid_dtype[t.tensor_id] = t.dtype.to_jnp()
        acts = {}
        for tid in self.input_ids:
            dims = tid_dims[tid]
            acts[tid] = jax.ShapeDtypeStruct((mb,) + dims[1:],
                                             tid_dtype[tid])
        key = jax.random.key(0)
        segs = []
        for c in range(C - 1):
            fwd = self._chunk_apply(c, training=True, mesh=False)
            params = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                self._stage_params_raw[self.chunk_stage(c)])
            cp = {op.name: params[op.name] for op in self.chunks[c]
                  if op.name in params}
            out, _aux = jax.eval_shape(fwd, cp, acts, key)
            segs.append(_leaf_segments(out))
            acts = out
        A = max(s[2] for s in segs)
        return segs, A

    # ---------------------------------------------------------- program
    def _chunk_params_from(self, theta_row, c: int):
        s = self.chunk_stage(c)
        segs, td, _n = self._param_segs[s]
        return _unpack(theta_row, segs, td)

    def _build_program(self, mb: int, xs_shapes, y_shape, y_dtype,
                       with_metrics: bool):
        S = len(self.stages)
        C = len(self.chunks)
        V = self.cfg.interleave
        M = self.cfg.num_microbatches
        dp = self._dp
        tb = self._tables
        mb_local = mb // dp
        bsegs, A = self._boundary_segments(mb_local)
        K = tb["K"]
        R_f, R_b = tb["R_f"], tb["R_b"]
        kinds = jnp.asarray(tb["kinds"])
        mbs_t = jnp.asarray(tb["mbs"])
        chs_t = jnp.asarray(tb["chunks"])
        wf = jnp.asarray(tb["wf"])
        rf = jnp.asarray(tb["rf"])
        wb = jnp.asarray(tb["wb"])
        rb = jnp.asarray(tb["rb"])
        sv = jnp.asarray(tb["sv"])
        rs = jnp.asarray(tb["rs"])
        T = tb["kinds"].shape[0]
        loss_fn = self.loss_fn
        logits_id = self.logits_id
        cdt = self.compute_dtype
        chunk_fns = [self._chunk_apply(c, training=True, mesh=False)
                     for c in range(C)]
        # 1/dp as a STRONG-typed constant: under a data submesh the
        # chunk programs see local batch shards, so the recorded
        # local-mean losses reduce by psum * inv_dp (mean of equal-shard
        # means) and the vjp cotangent seed carries the same factor —
        # exact scalings for power-of-two shard counts, which is what
        # keeps the data-submesh family bit-identical to the host
        # engine's GSPMD full-batch means
        inv_dp = jnp.float32(1.0 / dp)
        # logits shape for the metrics buffer (from the tail chunk)
        logits_sds = None
        if with_metrics:
            acts_spec = _unpack(jnp.zeros((A,), jnp.float32),
                                bsegs[C - 2][0], bsegs[C - 2][1])
            params_spec = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                self._stage_params_raw[S - 1])
            cp = {op.name: params_spec[op.name]
                  for op in self.chunks[C - 1] if op.name in params_spec}
            out, _ = jax.eval_shape(
                chunk_fns[C - 1],
                cp,
                jax.tree_util.tree_map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                    acts_spec),
                jax.random.key(0))
            lg = out[logits_id]
            lg_dt = jnp.float32 if cdt is not None else lg.dtype
            logits_sds = (lg.shape, lg_dt)

        # ring permutes over the pipe axis: chunk c lives on stage
        # c % S, so EVERY forward send goes to the ring-next stage and
        # every backward send to ring-prev — including the wrap edges
        # interleaving needs (with V == 1 the wrap only carries zeros)
        fwd_perm = [(i, (i + 1) % S) for i in range(S)]
        bwd_perm = [(i, (i - 1) % S) for i in range(S)]

        def shard_body(theta, opt, rng, hyper, inv_m_t, y_st, *xs_st):
            # theta: (1, Lp) local row; squeeze to (Lp,)
            th = theta[0]
            op_buf = opt[0]
            sidx = jax.lax.axis_index("pipe")
            # 1/M arrives as a TRACED argument (not a closure): a baked
            # scalar closure is exactly the AUD006 retrace hazard the
            # program audit flags, and the traced form is bit-identical.
            # Under a data submesh the seed gains the exact 1/dp factor
            # (local-mean vjp -> global-mean cotangents, see above).
            daux = inv_m_t * inv_dp if dp > 1 else inv_m_t
            cot = daux

            def inputs_for(m):
                return {tid: jax.lax.dynamic_index_in_dim(
                            x, m, 0, keepdims=False)
                        for tid, x in zip(self.input_ids, xs_st)}

            def mb_rng(m, c):
                return jax.random.fold_in(rng, m * 131 + c)

            # ---- per-kind branches; uniform operand/result signatures.
            # Every branch returns (send_f, send_b, saved, g_contrib,
            # losses, auxes, logits_b): the gradient contribution comes
            # OUT of the switch so the data-axis psum (when dp > 1) is
            # one unconditional collective per tick — every switch
            # branch agrees on the collective signature (AUD005).
            def idle_fn(opr):
                (m, ch, rfv, rbv, svv, rsv, fsl, bsl, saved, losses,
                 auxes, logits_b) = opr
                return (jnp.zeros((A,), jnp.float32),
                        jnp.zeros((A,), jnp.float32),
                        saved, jnp.zeros((self._Lp,), jnp.float32),
                        losses, auxes, logits_b)

            def f_fn(opr):
                (m, ch, rfv, rbv, svv, rsv, fsl, bsl, saved, losses,
                 auxes, logits_b) = opr
                inbuf = jax.lax.dynamic_index_in_dim(fsl, rfv, 0,
                                                     keepdims=False)

                def br(c):
                    def run(_):
                        if c == 0:
                            acts = inputs_for(m)
                        else:
                            acts = _unpack(inbuf, bsegs[c - 1][0],
                                           bsegs[c - 1][1])
                        out, aux = chunk_fns[c](
                            self._chunk_params_from(th, c), acts,
                            mb_rng(m, c))
                        send = _pack(
                            jax.tree_util.tree_flatten(out)[0],
                            bsegs[c][0], A)
                        return send, jnp.asarray(aux, jnp.float32)
                    return run

                send_f, aux = jax.lax.switch(
                    ch, [br(c) for c in range(C - 1)], 0)
                # save the packed input for the backward replay
                # (chunk-0 forwards replay from xs directly; the static
                # slot table points them at the scratch row K)
                saved = jax.lax.dynamic_update_index_in_dim(
                    saved, jnp.where(ch > 0, inbuf,
                                     jnp.zeros((A,), jnp.float32)),
                    svv, 0)
                # per-(virtual-chunk, microbatch) aux cell — one row per
                # chunk the stage hosts, so interleaved chunks never
                # clobber each other's aux terms
                auxes = auxes.at[ch // S, m].set(aux)
                return (send_f, jnp.zeros((A,), jnp.float32), saved,
                        jnp.zeros((self._Lp,), jnp.float32),
                        losses, auxes, logits_b)

            def b_fn(opr):
                (m, ch, rfv, rbv, svv, rsv, fsl, bsl, saved, losses,
                 auxes, logits_b) = opr
                d_out_buf = jax.lax.dynamic_index_in_dim(
                    bsl, rbv, 0, keepdims=False)
                saved_in = jax.lax.dynamic_index_in_dim(
                    saved, rsv, 0, keepdims=False)

                def br(c):
                    def run(_):
                        if c == 0:
                            acts_in = inputs_for(m)
                        else:
                            acts_in = _unpack(saved_in, bsegs[c - 1][0],
                                              bsegs[c - 1][1])
                        d_out = _unpack(d_out_buf, bsegs[c][0],
                                        bsegs[c][1], cotangent=True)
                        params_c = self._chunk_params_from(th, c)
                        _, vjp = jax.vjp(
                            lambda p, a: chunk_fns[c](p, a,
                                                      mb_rng(m, c)),
                            params_c, acts_in)
                        dparams, dacts = vjp((d_out, daux))
                        g = _pack(jax.tree_util.tree_flatten(dparams)[0],
                                  self._param_segs[
                                      self.chunk_stage(c)][0],
                                  self._Lp)
                        if c > 0:
                            send_b = _pack(
                                jax.tree_util.tree_flatten(dacts)[0],
                                bsegs[c - 1][0], A)
                        else:
                            send_b = jnp.zeros((A,), jnp.float32)
                        return send_b, g
                    return run

                send_b, g = jax.lax.switch(
                    ch, [br(c) for c in range(C - 1)], 0)
                return (jnp.zeros((A,), jnp.float32), send_b, saved,
                        g, losses, auxes, logits_b)

            def fb_fn(opr):
                (m, ch, rfv, rbv, svv, rsv, fsl, bsl, saved, losses,
                 auxes, logits_b) = opr
                c = C - 1
                inbuf = jax.lax.dynamic_index_in_dim(fsl, rfv, 0,
                                                     keepdims=False)
                acts_in = _unpack(inbuf, bsegs[c - 1][0], bsegs[c - 1][1])
                ym = jax.lax.dynamic_index_in_dim(y_st, m, 0,
                                                  keepdims=False)
                params_c = self._chunk_params_from(th, c)

                def f(p, a):
                    out, aux = chunk_fns[c](p, a, mb_rng(m, c))
                    logits = out[logits_id]
                    if cdt is not None:
                        logits = logits.astype(jnp.float32)
                    loss = loss_fn(logits, ym)
                    return loss + aux, (loss, aux, logits)

                _, vjp, (loss, aux, logits) = jax.vjp(f, params_c,
                                                      acts_in,
                                                      has_aux=True)
                dparams, dacts = vjp(cot)
                g = _pack(jax.tree_util.tree_flatten(dparams)[0],
                          self._param_segs[self.chunk_stage(c)][0],
                          self._Lp)
                send_b = _pack(jax.tree_util.tree_flatten(dacts)[0],
                               bsegs[c - 1][0], A)
                losses = losses.at[m].set(loss)
                auxes = auxes.at[V - 1, m].set(jnp.asarray(aux,
                                                           jnp.float32))
                if logits_b is not None:
                    logits_b = jax.lax.dynamic_update_index_in_dim(
                        logits_b, logits.astype(logits_b.dtype), m, 0)
                return (jnp.zeros((A,), jnp.float32), send_b, saved,
                        g, losses, auxes, logits_b)

            def tick(carry, t):
                (fsl, bsl, saved, in_f, in_b, gacc, losses, auxes,
                 logits_b) = carry
                # integrate last tick's arrivals (scratch slots absorb
                # no-arrival ticks)
                fsl = jax.lax.dynamic_update_index_in_dim(
                    fsl, in_f, wf[t, sidx], 0)
                bsl = jax.lax.dynamic_update_index_in_dim(
                    bsl, in_b, wb[t, sidx], 0)
                opr = (mbs_t[t, sidx], chs_t[t, sidx], rf[t, sidx],
                       rb[t, sidx], sv[t, sidx], rs[t, sidx], fsl, bsl,
                       saved, losses, auxes, logits_b)
                send_f, send_b, saved, g, losses, auxes, logits_b = \
                    jax.lax.switch(kinds[t, sidx],
                                   [idle_fn, f_fn, b_fn, fb_fn], opr)
                if dp > 1:
                    # gradient-sync collective per backward, OUTSIDE the
                    # action switch: idle/forward ticks psum exact zeros
                    # (x + 0 is bit-exact), backward ticks reduce their
                    # contribution over the data axis BEFORE it joins
                    # the accumulator — the host engine's per-microbatch
                    # all-reduce-then-accumulate order, bit for bit
                    g = jax.lax.psum(g, DATA_AXIS)
                gacc = gacc + g
                in_f2 = jax.lax.ppermute(send_f, "pipe", fwd_perm)
                in_b2 = jax.lax.ppermute(send_b, "pipe", bwd_perm)
                return (fsl, bsl, saved, in_f2, in_b2, gacc, losses,
                        auxes, logits_b), None

            zeros_a = jnp.zeros((A,), jnp.float32)
            carry0 = (
                jnp.zeros((R_f + 1, A), jnp.float32),
                jnp.zeros((R_b + 1, A), jnp.float32),
                jnp.zeros((K + 1, A), jnp.float32),
                zeros_a, zeros_a,
                jnp.zeros((self._Lp,), jnp.float32),
                jnp.zeros((M,), jnp.float32),
                jnp.zeros((V, M), jnp.float32),
                (jnp.zeros((M,) + logits_sds[0], logits_sds[1])
                 if logits_sds is not None else None),
            )
            carry, _ = jax.lax.scan(tick, carry0, jnp.arange(T))
            (_fsl, _bsl, _saved, _inf, _inb, gacc, losses, auxes,
             logits_b) = carry
            if dp > 1:
                # the recorded per-microbatch losses/aux are local
                # shard means; one reduction turns them into the global
                # means the host engine reports (exact for power-of-two
                # shard counts)
                losses = jax.lax.psum(losses, DATA_AXIS) * inv_dp
                auxes = jax.lax.psum(auxes, DATA_AXIS) * inv_dp

            # ---- per-stage optimizer update, inside the same program
            def upd(s):
                def run(_):
                    psegs, ptd, _n = self._param_segs[s]
                    osegs, otd, _on = self._opt_segs[s]
                    p = _unpack(th, psegs, ptd)
                    g = _unpack(gacc, psegs, ptd)
                    st = _unpack(op_buf, osegs, otd)
                    new_p, new_st = self.optimizer.update(
                        p, g, st, self.stage_wd[s], hyper)
                    return (_pack(jax.tree_util.tree_flatten(new_p)[0],
                                  psegs, self._Lp),
                            _pack(jax.tree_util.tree_flatten(new_st)[0],
                                  osegs, self._Lo))
                return run

            new_th, new_opt = jax.lax.switch(
                sidx, [upd(s) for s in range(S)], 0)
            outs = (new_th[None], new_opt[None], losses[None],
                    auxes[None])
            if logits_b is not None:
                outs = outs + (logits_b[None],)
            return outs

        P = PartitionSpec
        rep = P()
        batch_spec = P(None, DATA_AXIS) if dp > 1 else rep
        in_specs = (P("pipe", None), P("pipe", None), rep, rep, rep,
                    batch_spec) + tuple(batch_spec for _ in xs_shapes)
        out_specs = (P("pipe", None), P("pipe", None), P("pipe", None),
                     P("pipe", None, None))
        if with_metrics:
            out_specs = out_specs + (
                P("pipe", None, DATA_AXIS) if dp > 1 else P("pipe"),)
        fn = shard_map(shard_body, mesh=self._pmesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
        return jax.jit(fn, donate_argnums=(0, 1))

    # ----------------------------------------------------------- audit
    def _audit_program(self, key, args) -> None:
        """Program-audit one freshly built schedule program
        (analysis/program_audit.py; mode from the compile()'s FFConfig
        threaded through ``audit_config``). The shard_map body is where
        the ppermute partner tables and the per-stage lax.switch
        programs live — AUD005's deadlock class. Tracing here is shared
        with the dispatch that follows (jit AOT cache)."""
        cfg = self.audit_config
        mode = (getattr(cfg, "audit_programs", "off") or "off") \
            if cfg is not None else "off"
        from ..obs.exec_telemetry import telemetry_mode

        tmode = telemetry_mode(cfg) if cfg is not None else "off"
        if mode == "off" and tmode == "off":
            return
        from ..analysis.findings import ValidationReport
        from ..analysis.program_audit import audit_traced
        from ..obs.metrics import metrics_registry
        from ..obs.trace import span as _obs_span

        pname = f"pipeline.{self.cfg.schedule}"
        try:
            with _obs_span("pipe.audit", cat="pipeline",
                           schedule=self.cfg.schedule):
                traced = self._programs[key].trace(*args)
        except Exception as e:  # noqa: BLE001 — audit must not mask dispatch
            # AUD000 contract: a trace failure is recorded, never
            # silently dropped (audit_report would otherwise keep the
            # PREVIOUS program's clean report and read as a clean audit
            # of THIS one); the dispatch below surfaces the real error
            report = ValidationReport(source="pipeline", tag="audit")
            report.programs = {pname: {"trace_failed": True}}
            report.add(
                "AUD000",
                f"program '{pname}' could not be traced for audit: "
                f"{type(e).__name__}: {e}",
                severity="warning")
            traced = None
        else:
            report = audit_traced(pname, traced, config=cfg,
                                  source="pipeline")
        if mode != "off":
            self.audit_report = report
            reg = metrics_registry()
            reg.counter("audit.programs").inc()
            reg.counter("audit.errors").inc(len(report.errors))
            reg.counter("audit.warnings").inc(len(report.warnings))
        if tmode == "on":
            if traced is None:
                # the telemetry contract: every failure mode is an
                # explicit unavailable reason, never a bare None
                self.exec_telemetry = {"programs": {
                    pname: {"unavailable": "trace failed (see AUD000)"}}}
            else:
                # XLA executable telemetry for the ONE schedule program
                # (flops/bytes/peak memory), reconciled against the
                # audit's static peak-live estimate (OBS002 warn)
                from ..obs.exec_telemetry import collect_one

                static_peak = (report.programs.get(pname) or {}).get(
                    "peak_live_bytes")
                self.exec_telemetry = collect_one(
                    pname, traced, config=cfg, static_peak=static_peak,
                    allow=getattr(cfg, "exec_mem_allow", None))
        if mode != "off":
            report.handle(mode)

    # --------------------------------------------------------- training
    def train_step(self, rng, xs: Sequence[jax.Array], y: jax.Array,
                   sync: bool = True):
        M = self.cfg.num_microbatches
        S = len(self.stages)
        C = len(self.chunks)
        assert xs[0].shape[0] % M == 0, (
            f"batch {xs[0].shape[0]} not divisible by microbatches {M}")
        mb = xs[0].shape[0] // M
        if self._dp > 1 and mb % self._dp != 0:
            raise ValueError(
                f"microbatch {mb} not divisible by the stage submesh's "
                f"data degree {self._dp} (compiled pipe×data "
                f"engine shards each microbatch over the data axis)")
        self._ensure_packed()
        self.step_dispatches = 0
        self.step_transfers = self.schedule.transfer_edges()
        batch_sh = NamedSharding(
            self._pmesh,
            PartitionSpec(None, DATA_AXIS) if self._dp > 1
            else PartitionSpec())
        rep = NamedSharding(self._pmesh, PartitionSpec())

        def stack(a):
            a = jnp.asarray(a)
            return jax.device_put(
                jnp.reshape(a, (M, a.shape[0] // M) + a.shape[1:]),
                batch_sh)

        xs_st = [stack(x) for x in xs]
        y_st = stack(y)
        self.step_dispatches += len(xs_st) + 1  # input placements
        with_metrics = self.metrics_fn is not None
        key = (tuple((tuple(x.shape), str(x.dtype)) for x in xs_st),
               (tuple(y_st.shape), str(y_st.dtype)), with_metrics)
        new_program = key not in self._programs
        if new_program:
            self._programs[key] = self._build_program(
                mb, [x.shape for x in xs_st], y_st.shape, y_st.dtype,
                with_metrics)
        hyper = {k: jnp.asarray(v, jnp.float32)
                 for k, v in self.optimizer.hyperparams().items()}
        inv_m = jnp.asarray(1.0 / M, jnp.float32)
        rng = jax.device_put(rng, rep)
        if new_program:
            # program-audit gate on the freshly built schedule program
            # (ppermute tables, switch-branch collective agreement, ...);
            # the AOT trace it takes is the one the dispatch below replays
            self._audit_program(
                key, (self._packed[0], self._packed[1], rng, hyper,
                      inv_m, y_st) + tuple(xs_st))
        # flight recorder: the whole warmup/steady/cooldown schedule is
        # ONE program — record its few dispatches as one annotated span
        # (schedule metadata in args) instead of a span per tick
        from ..obs.trace import span as _obs_span

        with _obs_span("pipe.step.compiled", cat="pipeline",
                       schedule=self.cfg.schedule,
                       interleave=self.cfg.interleave,
                       stages=S, microbatches=M,
                       dispatches=self.step_dispatches + 1):
            out = self._programs[key](self._packed[0], self._packed[1],
                                      rng, hyper, inv_m, y_st, *xs_st)
        self.step_dispatches += 1  # the ONE schedule program
        self._feed_step_metrics()
        theta, opt, losses_all, auxes_all = out[:4]
        self._packed = [theta, opt]
        self._views_stale = True
        losses = [losses_all[S - 1, m] for m in range(M)]
        # (microbatch-major, chunk-ascending) — the host engines' (and
        # the historical) loss-combine order, bit for bit; chunk c's aux
        # cell lives at stage c % S, virtual row c // S
        aux_flat = [auxes_all[c % S, c // S, m]
                    for m in range(M) for c in range(C)]
        if not sync:
            return losses, aux_flat
        loss = float(
            sum(jax.device_get(l) for l in losses)
            + sum(jax.device_get(a) for a in aux_flat)
        ) / M
        bm = {}
        if with_metrics:
            logits_all = out[4]
            logits = jnp.concatenate(
                [jax.device_get(logits_all[S - 1, m]) for m in range(M)],
                axis=0)
            bm = self.metrics_fn(logits, jax.device_get(jnp.asarray(y)))
        return loss, bm

    # the host engine's forward_only / sync_to / all_params read the
    # dict views; the property getters refresh them from the packed
    # buffers first, so nothing else to override here.
