"""Pipeline parallelism: schedule-driven engines (GPipe / 1F1B /
interleaved).

The reference RESERVED pipeline parallelism but never implemented it
(reference: PIPELINE_{INIT,FWD,BWD}_TASK_ID task ids exist, model.h:190-192,
but no Pipeline op exists anywhere in src/ — SURVEY.md §2.3). Here it is a
first-class strategy whose SCHEDULE is itself a knob the simulator can
price and the search can select (``config.pipeline_schedule =
gpipe|1f1b|interleaved|auto``).

Two engines execute the same schedule IR (:mod:`.schedule`):

* :class:`PipelinedModel` — the **host-driven** engine (this module):
  replays the tick table with one compiled program dispatch per action.
  General: any mesh (dp/tp inside stages), any schedule including
  interleaved virtual stages. Under 1F1B it frees each microbatch's
  residuals as soon as its backward consumes them, so live activations
  are O(num_stages) instead of O(num_microbatches).
* :class:`~.pipeline_compiled.CompiledPipelinedModel` — the
  **single-dispatch** engine (:mod:`.pipeline_compiled`): the whole
  warmup/steady/cooldown schedule lowered into ONE jitted program
  (``lax.scan`` over schedule ticks, stage-boundary transfers as
  collective permutes over the pipe ring inside ``shard_map``). Covers
  every schedule (gpipe/1f1b/interleaved) on the ``pipe`` and
  ``pipe×data`` mesh families (batch-linear graphs only under a data
  submesh); :func:`make_pipelined_model` picks it automatically when
  the envelope holds and falls back to the host engine otherwise,
  recording the reason on ``fallback_reason``.

Both engines share the stage split, per-chunk programs, parameter
placement, and gradient-accumulation order (backwards run in microbatch
order per stage under EVERY schedule), so per-step losses and grads are
schedule-invariant and engine-invariant up to float reassociation by XLA.

Design (TPU single-controller), host engine:

* the op chain is split into ``num_stages * interleave`` contiguous
  chunks balanced by FLOPs; chunk *c* lives on the mesh slice
  ``pipe = c % num_stages`` (a submesh keeping every other axis, so dp/tp
  still apply *inside* a stage);
* each chunk compiles exactly TWO programs on its submesh — a jitted
  forward and a jitted backward (the backward rematerializes the chunk's
  forward via ``jax.vjp`` inside the jit when ``remat=True``; by default
  the vjp residuals of the jitted forward are kept and freed at the
  consuming backward);
* the global batch splits into ``num_microbatches`` microbatches, each
  kept **sharded over the stage submesh's data axis**; the schedule's
  overlap emerges from JAX's async dispatch — actions in one tick are
  enqueued back to back and run concurrently on disjoint device groups;
* gradients accumulate over microbatches (fixed microbatch order) and
  each stage's optimizer update runs on its own submesh with the
  optimizer hyperparameters passed as TRACED arguments (mirroring
  runtime/compiler.py's ``hyper``), so LR schedules never retrace;
* inter-stage activation (and cotangent) transfers are device_put edges
  between submeshes — the ICI hop where the reference would have issued a
  Legion region copy.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..core.machine import DATA_AXIS, PIPE_AXIS, mesh_axis_sizes
from ..core.op import LowerCtx, op_scope, weights_of
from .schedule import (Action, PipelineSchedule, build_schedule,
                       check_schedule, schedule_summary)


@dataclasses.dataclass
class PipelineConfig:
    """compile(..., pipeline=PipelineConfig(...)).

    ``schedule``: microbatch ordering — ``"gpipe"`` (all forwards, then
    all backwards: the historical engine), ``"1f1b"`` (one-forward-
    one-backward steady state: live activations capped at
    O(num_stages)), or ``"interleaved"`` (1F1B over ``interleave``
    virtual chunks per stage: ~interleave× smaller bubble for
    interleave× boundary traffic). ``"auto"`` is resolved by the caller
    (FFModel.compile via the simulator's schedule cost model) before the
    engine is built.

    ``remat=False`` (default) stores each chunk's vjp residuals per
    microbatch — no recompute; residuals are freed as soon as the
    consuming backward runs, so the live set follows the schedule.
    ``remat=True`` rematerializes each chunk's forward inside its
    compiled backward: ~1.33x the FLOPs, but only stage-boundary
    activations are ever stored.

    ``engine``: ``"auto"`` picks the single-dispatch compiled engine
    (:mod:`.pipeline_compiled`) when its envelope holds — any schedule,
    on the pipe or pipe×data mesh families with a batch-linear graph —
    else the host-driven engine (with the reason recorded on
    ``fallback_reason``); ``"host"``/``"compiled"`` force one (forcing
    ``"compiled"`` outside its envelope raises).
    """

    num_stages: int
    num_microbatches: int = 4
    axis: str = PIPE_AXIS
    remat: bool = False
    schedule: str = "gpipe"
    interleave: int = 1
    engine: str = "auto"
    # set by FFModel._resolve_pipeline once config.grad_accum_steps has
    # been folded into num_microbatches, so a recompile that passes the
    # resolved config back through compile() never folds twice
    accum_folded: bool = False


def split_stages(ops: List, num_stages: int) -> List[List]:
    """Balanced contiguous split by FLOPs.

    Stage boundaries are chosen at FLOP prefix-sum quantiles, closing a
    stage early when exactly one op per remaining stage is left — so every
    stage is non-empty and the concatenation of stages is the original op
    order (contiguous in topological order).
    """
    n = len(ops)
    if n < num_stages:
        raise ValueError(f"cannot split {n} ops into {num_stages} stages")
    costs = [max(op.flops(), 1.0) for op in ops]
    total = sum(costs)
    bounds: List[int] = []
    acc = 0.0
    for i, c in enumerate(costs):
        acc += c
        if len(bounds) == num_stages - 1:
            break
        rem_ops = n - (i + 1)
        rem_stages = num_stages - len(bounds) - 1
        if (
            acc >= total * (len(bounds) + 1) / num_stages
            or rem_ops == rem_stages
        ):
            bounds.append(i + 1)
    return [ops[a:b] for a, b in zip([0] + bounds, bounds + [n])]


class PipelinedModel:
    """Schedule-driven pipeline engine behind FFModel.compile(pipeline=...).

    ``train_step(rng, xs, y) -> (loss, batch_metrics)`` mutates the
    per-stage params/opt_state in place, replaying the schedule's tick
    table (one program dispatch per action — the host-driven engine; see
    :mod:`.pipeline_compiled` for the single-dispatch engine).
    """

    engine_name = "host"
    # set by make_pipelined_model when engine="auto" picked this host
    # engine although the caller might have expected the compiled one;
    # None on the compiled engine and on forced-host builds. profile()
    # publishes it so explain_run can tell a deliberate fallback from a
    # silent one.
    fallback_reason: Optional[str] = None

    def __init__(self, ops, mesh: Mesh, cfg: PipelineConfig, optimizer,
                 loss_fn, metrics_fn, input_ids: List[int], logits_id: int,
                 params: Dict, wd_mask: Dict, opt_state=None,
                 compute_dtype=None, audit_config=None):
        # program-audit gate config (FFConfig or None): the compiled
        # engine audits each schedule program it builds when
        # audit_config.audit_programs says so; the host engine has no
        # monolithic program to audit, so it only stores the handle
        self.audit_config = audit_config
        self.audit_report = None
        axis_sizes = mesh_axis_sizes(mesh)
        if cfg.axis not in axis_sizes:
            raise ValueError(f"mesh has no '{cfg.axis}' axis for pipelining")
        S = axis_sizes[cfg.axis]
        if cfg.num_stages != S:
            raise ValueError(
                f"num_stages={cfg.num_stages} must equal mesh {cfg.axis} "
                f"size {S}"
            )
        check_schedule(cfg.schedule, S, cfg.num_microbatches, cfg.interleave)
        from ..ffconst import OpType

        if any(op.op_type is OpType.BATCHNORM for op in ops):
            import warnings

            warnings.warn(
                "pipelined training does not update BatchNorm running "
                "statistics (stage programs don't track state updates); "
                "eval will normalize with the initial running stats",
                stacklevel=3)
        self.cfg = cfg
        self.mesh = mesh
        self.optimizer = optimizer
        # bf16 mixed precision inside the stage programs (fp32 masters);
        # resolved string -> jnp dtype by the compiler's shared helper
        from ..runtime.compiler import _resolve_compute_dtype

        self.compute_dtype = _resolve_compute_dtype(compute_dtype) \
            if isinstance(compute_dtype, (str, type(None))) else compute_dtype
        self.loss_fn = loss_fn
        self.metrics_fn = metrics_fn
        self.input_ids = input_ids
        self.logits_id = logits_id
        # contiguous FLOP-balanced chunks; chunk c lives on stage c % S
        self.chunks: List[List] = split_stages(ops, S * cfg.interleave)
        self.stages: List[List] = [
            [op for c in range(s, len(self.chunks), S)
             for op in self.chunks[c]]
            for s in range(S)
        ]
        self.schedule: PipelineSchedule = build_schedule(
            cfg.schedule, S, cfg.num_microbatches, cfg.interleave)

        # per-stage submeshes: slice the pipe axis, keep the other axes
        pipe_index = list(mesh.axis_names).index(cfg.axis)
        other_axes = [a for a in mesh.axis_names if a != cfg.axis]
        self.submeshes: List[Mesh] = []
        for s in range(S):
            devs = np.take(mesh.devices, s, axis=pipe_index)
            if not other_axes:  # keep a mesh, even if trivial
                devs = np.asarray(devs, dtype=object).reshape(1)
                self.submeshes.append(Mesh(devs, ("_stage",)))
            else:
                self.submeshes.append(Mesh(devs, tuple(other_axes)))

        # move each stage's params onto its submesh (pipe axis dropped from
        # specs — params are partitioned BY stage, not across it)
        self.stage_params: List[Dict] = []
        self.stage_wd: List[Dict] = []
        for s, stage_ops in enumerate(self.stages):
            sp, sw = {}, {}
            for op in stage_ops:
                if op.name in params:
                    sp[op.name] = {
                        w: jax.device_put(v, self._weight_sharding(s, op, w))
                        for w, v in params[op.name].items()
                    }
                    sw[op.name] = wd_mask[op.name]
            self.stage_params.append(sp)
            self.stage_wd.append(sw)
        self.stage_opt_state = (
            [optimizer.init_state(sp) for sp in self.stage_params]
            if opt_state is None else self._slice_opt_state(opt_state)
        )
        C = len(self.chunks)
        self._chunk_fwd = [self._make_chunk_fwd(c, training=True)
                           for c in range(C)]
        self._chunk_fwd_eval = [self._make_chunk_fwd(c, training=False)
                                for c in range(C)]
        self._chunk_bwd = [self._make_chunk_bwd(c) for c in range(C)]
        self._stage_update = [self._make_stage_update(s) for s in range(S)]
        self._bwd_last = self._make_last_chunk_bwd()
        # one jitted tree-add per stage param structure (grad accumulation
        # as ONE dispatch, not one per leaf)
        self._acc = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
        # per-step dispatch/transfer accounting (pipe_bench + fit_profile)
        self.step_dispatches = 0
        self.step_transfers = 0

    # ------------------------------------------------------------------ #
    def chunk_stage(self, c: int) -> int:
        """The physical stage hosting chunk ``c``."""
        return c % len(self.stages)

    def _weight_sharding(self, s: int, op, wname: str) -> NamedSharding:
        ps = op.weight_shapes[wname]
        sub = self.submeshes[s]
        spec = tuple(
            e if e in sub.axis_names else None
            for e in ps.partition_spec()
        )
        return NamedSharding(sub, PartitionSpec(*spec))

    def _act_sharding(self, s: int, v) -> NamedSharding:
        """Batch-dim sharding over the submesh's data axis (replicated only
        when the microbatch doesn't divide, or there is no data axis)."""
        sub = self.submeshes[s]
        sizes = mesh_axis_sizes(sub)
        dp = sizes.get(DATA_AXIS, 1)
        if v.ndim >= 1 and dp > 1 and v.shape[0] % dp == 0:
            return NamedSharding(
                sub, PartitionSpec(DATA_AXIS, *([None] * (v.ndim - 1)))
            )
        return NamedSharding(sub, PartitionSpec(*([None] * v.ndim)))

    def _ship(self, s: int, tree):
        """Move an activation/cotangent dict onto stage s's submesh,
        keeping the batch dim sharded over the stage's data axis."""
        self.step_transfers += 1
        return {
            k: jax.device_put(v, self._act_sharding(s, v))
            for k, v in tree.items()
        }

    def _slice_opt_state(self, opt_state):
        """Per-stage optimizer state seeded from a full-model state (so a
        checkpoint restored into the CompiledModel flows into the pipeline).

        State leaves that mirror a parameter (momentum / Adam m,v) get that
        parameter's submesh sharding; everything else (scalars, Adam's t)
        is replicated on the submesh.
        """
        states = []
        for s, sp in enumerate(self.stage_params):
            sub = self.optimizer.slice_state(opt_state, list(sp.keys()))

            def place(node, like):
                if isinstance(node, dict):
                    # Adam's top-level m/v mirror the params tree; op-name
                    # and weight-name levels align with `like` directly
                    return {
                        k: place(
                            v,
                            like.get(k) if isinstance(like, dict) and k in like
                            else (sp if k in ("m", "v") else None),
                        )
                        for k, v in node.items()
                    }
                if (
                    like is not None
                    and getattr(node, "shape", None) == getattr(like, "shape", None)
                ):
                    return jax.device_put(node, like.sharding)
                return jax.device_put(
                    jnp.asarray(node),
                    NamedSharding(self.submeshes[s], PartitionSpec()),
                )

            states.append(place(sub, sp))
        return states

    @staticmethod
    def _mb_rng(rng, m: int, c: int):
        """Per-(microbatch, chunk) PRNG key. The remat backward MUST derive
        the identical key as the forward sweep so recomputed dropout masks
        match — this is the single derivation point. (With interleave==1
        the chunk index IS the historical stage index, so keys — and
        therefore dropout masks and trained weights — are bit-identical
        to the pre-schedule-knob engine.)"""
        return (jax.random.fold_in(rng, m * 131 + c)
                if rng is not None else None)

    def _live_after(self, c: int) -> set:
        """Tensor ids that must cross the c -> c+1 chunk boundary."""
        needed = {self.logits_id}
        for later in self.chunks[c + 1:]:
            for op in later:
                for t in op.layer.inputs:
                    needed.add(t.tensor_id)
        return needed

    def _chunk_apply(self, c: int, training: bool, mesh=None):
        """The pure chunk function: acts-in -> (acts-out, aux-loss sum).
        ``mesh`` defaults to the hosting stage's submesh; the compiled
        engine passes ``False`` (no mesh: ops lower without sharding
        constraints — every stage is a single device there)."""
        chunk_ops = self.chunks[c]
        if mesh is None:
            mesh = self.submeshes[self.chunk_stage(c)]
        elif mesh is False:
            mesh = None
        needed = self._live_after(c)

        cdt = self.compute_dtype
        from ..runtime.compiler import cast_op_params, make_caster

        cast = make_caster(cdt)

        def fwd(chunk_params, acts: Dict[int, jax.Array], rng):
            ctx = LowerCtx(mesh=mesh, training=training, aux_losses=[],
                           compute_dtype=cdt)
            acts = {k: cast(v) for k, v in acts.items()}
            for oi, op in enumerate(chunk_ops):
                ctx.rng = (jax.random.fold_in(rng, oi)
                           if rng is not None else None)
                ins = [acts[t.tensor_id] for t in op.layer.inputs]
                with op_scope(op):
                    p = cast_op_params(cast, op,
                                       weights_of(op, chunk_params), cdt)
                    outs = op.forward(ctx, ins, p)
                    for out, t in zip(outs, op.layer.outputs):
                        acts[t.tensor_id] = cast(out)
            out_acts = {k: v for k, v in acts.items() if k in needed}
            aux = ctx.aux_losses or []
            # aux as a summed scalar so the vjp cotangent is one scalar;
            # fp32 like the main compiler's loss path
            aux_sum = (sum(jnp.asarray(a, jnp.float32) for a in aux)
                       if aux else jnp.zeros(()))
            return out_acts, aux_sum

        return fwd

    def _chunk_params(self, c: int) -> Dict:
        """The hosting stage's param subtree restricted to chunk c."""
        sp = self.stage_params[self.chunk_stage(c)]
        return {op.name: sp[op.name] for op in self.chunks[c]
                if op.name in sp}

    def _make_chunk_fwd(self, c: int, training: bool):
        fwd = self._chunk_apply(c, training)
        if not training:
            return jax.jit(lambda p, a: fwd(p, a, None))
        return jax.jit(fwd)

    def _make_chunk_bwd(self, c: int):
        """One compiled backward per chunk: recomputes the chunk forward
        inside the jit (rematerialization) and pulls cotangents back
        through it, so no per-op residuals ever leave the program."""
        fwd = self._chunk_apply(c, training=True)

        @jax.jit
        def bwd(chunk_params, acts_in, rng, d_out, d_aux):
            _, vjp = jax.vjp(lambda p, a: fwd(p, a, rng), chunk_params,
                             acts_in)
            dparams, dacts = vjp((d_out, d_aux))
            return dparams, dacts

        return bwd

    def _make_last_chunk_bwd(self):
        """The pipeline tail as ONE compiled program: recompute the last
        chunk's forward, compute the loss, and pull cotangents back — no
        separate logits fetch, loss dispatch, or zero-cotangent fill."""
        C = len(self.chunks)
        fwd = self._chunk_apply(C - 1, training=True)
        loss_fn = self.loss_fn
        logits_id = self.logits_id

        @jax.jit
        def bwd_last(chunk_params, acts_in, rng, y, cot):
            def f(p, a):
                out, aux = fwd(p, a, rng)
                logits = out[logits_id]
                if self.compute_dtype is not None:
                    logits = logits.astype(jnp.float32)  # fp32 loss
                loss = loss_fn(logits, y)
                return loss + aux, (loss, aux, logits)

            _, vjp, (loss, aux, logits) = jax.vjp(
                f, chunk_params, acts_in, has_aux=True
            )
            dparams, dacts = vjp(cot)
            return loss, aux, logits, dparams, dacts

        return bwd_last

    def _make_stage_update(self, s: int):
        """Jitted per-stage optimizer update. Hyperparameters (lr/alpha)
        enter as a TRACED argument read fresh per call — mirroring
        runtime/compiler.py's ``hyper`` — so LR schedules take effect
        without retracing (pjit caches by the underlying function, so a
        're-jit' would silently reuse the stale executable)."""
        opt = self.optimizer
        wd = self.stage_wd[s]

        @jax.jit
        def upd(stage_params, grads, opt_state, hyper):
            return opt.update(stage_params, grads, opt_state, wd, hyper)

        return upd

    # ------------------------------------------------------------------ #
    def train_step(self, rng, xs: Sequence[jax.Array], y: jax.Array,
                   sync: bool = True):
        """One pipelined training step, replaying ``self.schedule``.

        ``sync=True`` (default) fetches the scalar loss to host — which
        fences the step and exposes the schedule bubble. ``sync=False``
        returns the per-microbatch device scalars instead
        (``(loss_parts, aux_parts)``, combine as
        ``(sum(map(float, loss_parts)) + sum(map(float, aux_parts))) / M``)
        so back-to-back steps overlap across the bubble: stage 0 starts
        step N+1's microbatches as soon as its own backward of step N is
        done, while later stages drain.
        """
        M = self.cfg.num_microbatches
        S = len(self.stages)
        C = len(self.chunks)
        assert xs[0].shape[0] % M == 0, (
            f"batch {xs[0].shape[0]} not divisible by microbatches {M}"
        )
        self.step_dispatches = 0
        self.step_transfers = 0
        xs_mb = [jnp.split(jnp.asarray(x), M, axis=0) for x in xs]
        y_mb = jnp.split(jnp.asarray(y), M, axis=0)
        inv_m = 1.0 / M
        cot = jnp.asarray(inv_m)  # every microbatch's loss (and each
        daux = cot                # chunk's aux term) carries 1/M weight
        grad_acc: List[Any] = [None] * S

        def acc_stage(s, dparams):
            # chunk grads land in the stage accumulator keyed by op name;
            # chunks of one stage have disjoint op names, so a plain merge
            # is exact — the jitted tree-add only fires when the SAME
            # chunk's grads accumulate across microbatches
            if grad_acc[s] is None:
                grad_acc[s] = dict(dparams)
                return
            overlap = {k: v for k, v in dparams.items() if k in grad_acc[s]}
            fresh = {k: v for k, v in dparams.items()
                     if k not in grad_acc[s]}
            if overlap:
                self.step_dispatches += 1
                summed = self._acc(
                    {k: grad_acc[s][k] for k in overlap}, overlap)
                grad_acc[s].update(summed)
            grad_acc[s].update(fresh)

        remat = self.cfg.remat
        # per-(chunk, mb) in-flight state; everything is freed (popped)
        # the moment its consumer runs, so the live set follows the
        # schedule — the 1F1B memory bound
        fwd_buf: Dict[Tuple[int, int], Dict] = {}   # shipped chunk inputs
        saved_in: Dict[Tuple[int, int], Dict] = {}  # remat: saved inputs
        vjps: Dict[Tuple[int, int], Any] = {}       # non-remat: vjp closures
        dacts_buf: Dict[Tuple[int, int], Dict] = {}  # incoming cotangents
        losses: List[Any] = [None] * M
        aux_terms: Dict[Tuple[int, int], Any] = {}  # (mb, chunk) -> scalar
        logits_mb: List[Any] = [None] * M

        def inputs_for(m: int) -> Dict:
            return self._ship(
                0, {tid: mb[m] for tid, mb in zip(self.input_ids, xs_mb)})

        from ..obs.trace import tracer as _obs_tracer

        _tr = _obs_tracer()
        for ti, row in enumerate(self.schedule.ticks):
            _t_tick = _tr.now() if _tr.enabled else 0.0
            for s, a in enumerate(row):
                if a is None:
                    continue
                c, m = a.chunk, a.mb
                mrng = self._mb_rng(rng, m, c)
                if a.kind == "F":
                    acts = (inputs_for(m) if c == 0
                            else fwd_buf.pop((c, m)))
                    self.step_dispatches += 1
                    if remat:
                        saved_in[(c, m)] = acts
                        out, aux = self._chunk_fwd[c](
                            self._chunk_params(c), acts, mrng)
                    else:
                        (out, aux), vjps[(c, m)] = jax.vjp(
                            lambda p, a_, _f=self._chunk_fwd[c], _r=mrng:
                                _f(p, a_, _r),
                            self._chunk_params(c), acts,
                        )
                    aux_terms[(m, c)] = aux
                    fwd_buf[(c + 1, m)] = self._ship(
                        self.chunk_stage(c + 1), out)
                elif a.kind == "FB":
                    acts = (inputs_for(m) if c == 0
                            else fwd_buf.pop((c, m)))
                    ym = jax.device_put(
                        y_mb[m], self._act_sharding(s, y_mb[m]))
                    self.step_dispatches += 1
                    loss, aux, logits, dparams, dacts = self._bwd_last(
                        self._chunk_params(c), acts, mrng, ym, cot)
                    acc_stage(s, dparams)
                    aux_terms[(m, c)] = aux
                    losses[m] = loss
                    logits_mb[m] = logits
                    if c > 0:
                        dacts_buf[(c - 1, m)] = self._ship(
                            self.chunk_stage(c - 1), dacts)
                else:  # backward
                    dacts = dacts_buf.pop((c, m))
                    self.step_dispatches += 1
                    if remat:
                        dparams, dacts = self._chunk_bwd[c](
                            self._chunk_params(c), saved_in.pop((c, m)),
                            mrng, dacts, daux)
                    else:
                        dparams, dacts = vjps.pop((c, m))((dacts, daux))
                    acc_stage(s, dparams)
                    if c > 0:
                        dacts_buf[(c - 1, m)] = self._ship(
                            self.chunk_stage(c - 1), dacts)
            if _tr.enabled:
                # tick replay trace: one span per schedule row with the
                # actions it dispatched (host-side issue time)
                _tr.complete(
                    "pipe.tick", _t_tick, _tr.now() - _t_tick,
                    cat="pipeline",
                    args={"tick": ti,
                          "actions": [f"s{s}:{a.kind}{a.mb}"
                                      for s, a in enumerate(row)
                                      if a is not None]})

        # ---- per-stage optimizer update on each submesh
        hyper = self.optimizer.hyperparams()
        for s in range(S):
            self.step_dispatches += 1
            self.stage_params[s], self.stage_opt_state[s] = \
                self._stage_update[s](self.stage_params[s], grad_acc[s],
                                      self.stage_opt_state[s], hyper)
        self._feed_step_metrics()

        # flatten aux in (microbatch-major, chunk-ascending) order — the
        # historical host combine order, so the reported loss is
        # bit-identical across schedules and engines
        aux_flat = [aux_terms[(m, c)] for m in range(M) for c in range(C)
                    if (m, c) in aux_terms]
        if not sync:
            return losses, aux_flat
        loss = float(
            sum(jax.device_get(l) for l in losses)
            + sum(jax.device_get(a) for a in aux_flat)
        ) * inv_m
        bm = {}
        if self.metrics_fn is not None:
            logits = jnp.concatenate(
                [jax.device_get(l) for l in logits_mb], axis=0
            )
            bm = self.metrics_fn(logits, jax.device_get(jnp.asarray(y)))
        return loss, bm

    def forward_only(self, xs: Sequence[jax.Array]):
        # the dispatch/transfer counters report the most recent TRAIN
        # step (profiling.pipeline_report's contract); an eval pass
        # must not inflate them
        saved = (self.step_dispatches, self.step_transfers)
        try:
            acts = self._ship(
                0, {tid: jnp.asarray(x)
                    for tid, x in zip(self.input_ids, xs)}
            )
            for c in range(len(self.chunks)):
                acts, _ = self._chunk_fwd_eval[c](self._chunk_params(c),
                                                  acts)
                if c < len(self.chunks) - 1:
                    acts = self._ship(self.chunk_stage(c + 1), acts)
            return acts[self.logits_id]
        finally:
            self.step_dispatches, self.step_transfers = saved

    # ------------------------------------------------------ observability
    def _feed_step_metrics(self) -> None:
        """Mirror the per-step dispatch/transfer counters into the
        process metrics registry (obs/metrics.py) — the pipeline's
        bubble/dispatch series next to the fit/serving counters, one
        scrape for the whole system."""
        from ..obs.metrics import metrics_registry

        reg = metrics_registry()
        reg.counter("pipeline.steps").inc()
        reg.counter("pipeline.dispatches").inc(self.step_dispatches)
        reg.counter("pipeline.transfers").inc(self.step_transfers)
        reg.gauge("pipeline.dispatches_per_step").set(self.step_dispatches)

    def _boundary_mb_bytes(self, mb_size: int) -> List[int]:
        """Per-chunk input bytes for ONE microbatch (chunk 0 = the model
        inputs; chunk c>0 = the c-1 -> c boundary tensors), at logical
        (unsharded) sizes."""
        tid_dims: Dict[int, Tuple] = {}
        tid_item: Dict[int, int] = {}
        for chunk in self.chunks:
            for op in chunk:
                for t in list(op.layer.inputs) + list(op.layer.outputs):
                    tid_dims[t.tensor_id] = tuple(t.dims)
                    try:
                        tid_item[t.tensor_id] = t.dtype.itemsize()
                    except Exception:
                        tid_item[t.tensor_id] = 4

        def nbytes(tid: int) -> int:
            dims = tid_dims.get(tid)
            if not dims:
                return 0
            n = mb_size
            for d in dims[1:]:
                n *= d
            return n * tid_item.get(tid, 4)

        out = [sum(nbytes(t) for t in self.input_ids)]
        for c in range(len(self.chunks) - 1):
            out.append(sum(nbytes(t) for t in self._live_after(c)))
        return out

    def peak_activation_bytes(self, mb_size: Optional[int] = None) -> Dict:
        """Schedule-implied peak live stage-boundary activation bytes:
        walk the tick table holding each forward's chunk-input bytes live
        until its backward consumes them. The comparable metric across
        schedules and engines (vjp residuals scale with the same live
        set). Returns {"per_stage": [...], "max": int, "total": int} —
        ``total`` sums the per-stage peaks (machine-wide worst case;
        the headline GPipe-vs-1F1B comparison)."""
        bbytes = self._boundary_mb_bytes(mb_size or 1)
        S = len(self.stages)
        live = [0] * S
        peak = [0] * S
        for row in self.schedule.ticks:
            for s, a in enumerate(row):
                if a is None:
                    continue
                b = bbytes[a.chunk]
                if a.kind == "F":
                    live[s] += b
                elif a.kind == "B":
                    peak[s] = max(peak[s], live[s])
                    live[s] -= b
                else:  # FB holds its input for the tick, then releases
                    peak[s] = max(peak[s], live[s] + b)
            for s in range(S):
                peak[s] = max(peak[s], live[s])
        return {"per_stage": peak, "max": max(peak), "total": sum(peak)}

    def profile(self, mb_size: Optional[int] = None) -> Dict:
        """One JSON-able record of what this engine executes per step:
        the schedule summary (bubble fraction, per-stage peak live
        microbatches), the engine name, measured dispatch/transfer counts
        from the most recent ``train_step``, and the schedule-implied
        peak activation bytes. Lands in ``fit_profile["pipeline"]``."""
        from ..sim.cost_model import OpCostModel

        from .schedule import render_timeline

        rec = schedule_summary(self.schedule,
                               bwd_ratio=OpCostModel.BWD_FACTOR)
        rec["engine"] = self.engine_name
        rec["requested_engine"] = self.cfg.engine
        rec["fallback_reason"] = self.fallback_reason
        # the envelope verdict for THIS mesh family (schedule/op checks
        # aside): explain_run flags a compiled-eligible mesh that ran
        # host with no recorded reason as a silent fallback
        from ..sim.simulator import compiled_envelope_ok

        rec["compiled_mesh_eligible"] = compiled_envelope_ok(
            mesh_axis_sizes(self.mesh), self.cfg.axis)
        rec["remat"] = bool(self.cfg.remat)
        rec["dispatches_per_step"] = self.step_dispatches
        rec["transfers_per_step"] = self.step_transfers
        rec["timeline"] = render_timeline(self.schedule)
        from ..obs.metrics import metrics_registry

        metrics_registry().gauge("pipeline.bubble_fraction").set(
            rec.get("bubble_fraction", 0.0))
        if mb_size:
            rec["peak_activation_bytes"] = \
                self.peak_activation_bytes(mb_size)
        return rec

    # convenience: gather all params back to host (checkpointing, tests)
    def all_params(self) -> Dict:
        merged: Dict = {}
        for sp in self.stage_params:
            merged.update(sp)
        return merged

    def sync_to(self, cm) -> None:
        """Write trained stage params AND optimizer state back into the
        CompiledModel (full-mesh shardings), so checkpointing/eval/
        get_weights after a pipelined fit see the trained state."""
        for sp in self.stage_params:
            for op_name, ws in sp.items():
                if op_name not in cm.params:
                    continue
                for w, v in ws.items():
                    cm.params[op_name][w] = jax.device_put(
                        np.asarray(v), cm.param_shardings[op_name][w]
                    )

        def onto(template, sub):
            # recurse the (subset) state tree, placing each leaf with the
            # full-model template leaf's sharding
            if isinstance(sub, dict):
                return {
                    k: onto(template[k], v) if k in template else v
                    for k, v in sub.items()
                }
            return jax.device_put(np.asarray(sub), template.sharding)

        merged = cm.opt_state
        for s, sub in enumerate(self.stage_opt_state):
            placed = onto(merged, sub)
            merged = self.optimizer.merge_state(merged, placed)
        cm.opt_state = merged

    def refresh_updates(self) -> None:
        """Historical hook called after a hyperparameter change
        (learning-rate schedules). No-op by design since the per-stage
        updates take ``optimizer.hyperparams()`` as a TRACED argument
        read fresh each step — mutating lr/alpha is already live.
        Re-jitting here would be a lie: pjit's cache is keyed on the
        underlying function and would silently reuse the stale
        executable."""

    def sync_from(self, cm) -> None:
        """Re-seed stage params/opt_state from the CompiledModel (after a
        checkpoint restore into cm)."""
        for s, stage_ops in enumerate(self.stages):
            for op in stage_ops:
                if op.name in cm.params:
                    self.stage_params[s][op.name] = {
                        w: jax.device_put(
                            np.asarray(v), self._weight_sharding(s, op, w)
                        )
                        for w, v in cm.params[op.name].items()
                    }
        self.stage_opt_state = self._slice_opt_state(cm.opt_state)


def make_pipelined_model(ops, mesh, cfg: PipelineConfig, optimizer,
                         loss_fn, metrics_fn, input_ids, logits_id,
                         params, wd_mask, opt_state=None,
                         compute_dtype=None, audit_config=None):
    """Engine selection: the single-dispatch compiled engine when the
    (mesh, schedule, optimizer-state) envelope allows, else the
    host-driven engine. ``cfg.engine`` forces either; forcing
    ``"compiled"`` outside its envelope raises with the reason."""
    kw = dict(optimizer=optimizer, loss_fn=loss_fn, metrics_fn=metrics_fn,
              input_ids=input_ids, logits_id=logits_id, params=params,
              wd_mask=wd_mask, opt_state=opt_state,
              compute_dtype=compute_dtype, audit_config=audit_config)
    if cfg.engine not in ("auto", "host", "compiled"):
        raise ValueError(
            f"pipeline engine {cfg.engine!r}: expected auto|host|compiled")
    if cfg.engine == "host":
        return PipelinedModel(ops, mesh, cfg, **kw)
    from .pipeline_compiled import (CompiledPipelinedModel,
                                    compiled_engine_unsupported)
    reason = compiled_engine_unsupported(
        mesh, cfg, ops=ops,
        batch_size=getattr(audit_config, "batch_size", None))
    if reason is None:
        try:
            return CompiledPipelinedModel(ops, mesh, cfg, **kw)
        except NotImplementedError as e:
            if cfg.engine == "compiled":
                raise
            reason = str(e)
    if cfg.engine == "compiled":
        raise ValueError(
            f"pipeline engine 'compiled' unsupported here: {reason}")
    pm = PipelinedModel(ops, mesh, cfg, **kw)
    # auto requested, host delivered: keep the reason on the engine so
    # fit_profile["pipeline"]/the ledger record WHY (explain_run's
    # silent-fallback gate reads it)
    pm.fallback_reason = reason
    return pm
