"""Compilation: lazy layer graph → sharded, jitted train/eval steps.

TPU-native equivalent of ``FFModel::compile``
(reference: src/runtime/model.cc:2803-3167; call stack in SURVEY.md §3.2).

Translation of the reference pipeline:

* ``create_operators_from_layers`` (model.cc:2785) → :func:`build_ops`:
  instantiate an Op per Layer, run shape inference.
* graph-optimize task / strategy search → :func:`assign_strategies`:
  per-op strategy dicts (data-parallel default, per-layer overrides, or a
  search-produced strategy map). Machine views → the global device mesh.
* ``map_output_tensors`` / region+partition creation → sharding
  propagation: each op's ``propagate`` produces ParallelTensorShapes whose
  ``partition_spec()`` lowers to ``jax.lax.with_sharding_constraint``.
* per-op Legion index launches + tracing → ONE jitted step function; XLA
  fuses and the jit cache replays (Legion tracing's role —
  flexflow_cffi.py:2098-2103 — comes for free).
* NCCL communicator setup (model.cc:3129-3167) → nothing: the SPMD
  partitioner emits ICI collectives from the shardings.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..analysis.findings import layer_provenance
from ..ffconst import CompMode, DataType, LossType, MetricsType, OpType
from ..config import FFConfig
from ..core.layer import Layer
from ..core.machine import DATA_AXIS, make_mesh, mesh_axis_sizes
from ..core.op import (LowerCtx, Op, create_op, fixed_scope, op_scope,
                       weights_of)
from ..core.parallel_tensor import ParallelDim, ParallelTensorShape
from ..core.tensor import Tensor
from ..obs.metrics import metrics_registry
from ..obs.trace import span
from .loss import compute_loss, sparse_ce_from_logits
from .metrics import compute_batch_metrics
from .initializer import DeclaredInitializer
from .optimizer import Optimizer


@dataclasses.dataclass
class CompiledModel:
    """Result of compile: everything needed to run training/inference."""

    config: FFConfig
    mesh: Mesh
    ops: List[Op]
    input_tensors: List[Tensor]
    label_tensor: Optional[Tensor]
    logits_tensor: Tensor
    loss_type: Optional[LossType]
    metrics: List[MetricsType]
    optimizer: Optional[Optimizer]
    params: Dict[str, Dict[str, jax.Array]]
    opt_state: Any
    wd_mask: Dict[str, Dict[str, bool]]
    param_shardings: Dict[str, Dict[str, NamedSharding]]
    input_shardings: List[NamedSharding]
    label_sharding: Optional[NamedSharding]
    train_step: Any
    train_k_steps: Any  # multi-step executable (lax.scan super-batch);
    #                     None when the model has no train step
    eval_step: Any
    forward_fn: Any
    grad_step: Any
    raw_forward: Any  # un-jitted forward (params, *xs) -> logits, for
    #                   callers that want to jit/transform it themselves
    tensor_pshapes: Dict[int, ParallelTensorShape]
    from_logits: bool = False  # CE loss path: graph does not end in softmax
    _iteration: int = 0
    # re-trace the train step after mutating optimizer hyperparameters
    # (learning-rate schedules): the compiled step bakes them in at trace
    # time. Set by compile_model; costs one XLA compile per call.
    refresh_train_step: Any = None
    # program-audit handles (analysis/program_audit.ExecutableSpec): the
    # jitted step executables plus abstract example arguments matching a
    # real call, so the compile() audit gate's AOT trace is shared with
    # the first dispatch instead of being paid twice
    audit_exec: Optional[List[Any]] = None
    # XLA executable telemetry (obs/exec_telemetry.py): per-program
    # flops / bytes-accessed / peak-memory blocks pulled off the
    # compiled executables when config.exec_telemetry="on" (filled by
    # FFModel.compile; None when the knob is off)
    exec_telemetry: Optional[Dict] = None
    # params generation counter: bumped whenever the params tree is
    # replaced or mutated in place (checkpoint restore, guard rollback,
    # manual weight surgery via bump_params_version()). Derived caches —
    # the serving decode path's bf16 cast copy — key on this instead of
    # ``id(params)`` (ids are reusable after GC) or pinning the old tree
    # alive.
    params_version: int = 0
    # dispatch-shape ledger for bucketed train/eval (config.seq_buckets):
    # every (kind, rows, seq_length) this model has dispatched. The fit
    # loop consults it BEFORE dispatch so an unseen bucket shape is a
    # counted, ledger-attributed compile miss, never a silent retrace
    # (AUD006 is the static complement). Lives on the CompiledModel so
    # replaying a seen trace across fit() calls registers zero misses.
    _seen_shapes: set = dataclasses.field(default_factory=set)

    def note_dispatch_shape(self, kind: str, rows: int,
                            seq_length: int) -> bool:
        """Record a (kind, rows, seq_length) dispatch shape; True the
        first time it is seen — the caller counts that as the bucket
        compile the matching jit retrace is about to pay."""
        key = (kind, int(rows), int(seq_length))
        if key in self._seen_shapes:
            return False
        self._seen_shapes.add(key)
        return True

    # ---- public resume-state surface ---------------------------------- #
    # Checkpoint, recompile, playoff and ledger paths all need the step
    # counter; they go through these instead of reaching into the
    # private _iteration field.
    @property
    def iteration(self) -> int:
        """Global step counter (monotonic across fits/recompiles)."""
        return self._iteration

    @iteration.setter
    def iteration(self, value: int) -> None:
        self._iteration = int(value)

    def bump_params_version(self) -> None:
        """Call after replacing or in-place mutating ``params`` so
        derived caches (the serving exec-params cast) re-derive."""
        self.params_version += 1

    def resume_state(self) -> Dict:
        """The JSON-scalar resume view (checkpoint extra + ledger
        records); params/opt_state travel separately (sharded arrays)."""
        return {"iteration": int(self._iteration)}

    def load_resume_state(self, state: Dict) -> None:
        self._iteration = int((state or {}).get("iteration", 0))


def toposort_layers(layers: List[Layer]) -> List[Layer]:
    """Builder order is already topological (each layer only consumes
    previously-created tensors), mirroring the reference's operator list
    ordering; validate rather than re-sort.

    Validation is by produced TENSOR ids, not owner_layer pointers, so
    graph passes that re-wrap layers (fusion) need not mutate the shared
    Tensor objects' owner_layer fields."""
    produced = set()
    for l in layers:
        for t in l.outputs:
            produced.add(t.tensor_id)
    seen = set()
    for l in layers:
        for t in l.inputs:
            if t.tensor_id in produced and t.tensor_id not in seen:
                raise ValueError(
                    f"{layer_provenance(l)}: layer graph not "
                    f"topologically ordered (consumes tensor "
                    f"'{t.name}' produced by a later layer)")
        for t in l.outputs:
            seen.add(t.tensor_id)
    return layers


def build_ops(
    layers: List[Layer],
    input_pshapes: Dict[int, ParallelTensorShape],
    axis_sizes: Dict[str, int],
    strategies: Dict[str, Dict[str, str]],
) -> Tuple[List[Op], Dict[int, ParallelTensorShape]]:
    """Instantiate ops and propagate shardings through the graph."""
    pshapes: Dict[int, ParallelTensorShape] = dict(input_pshapes)
    ops: List[Op] = []
    for layer in toposort_layers(layers):
        # every compile-time failure below carries full layer provenance
        # (name, op type, originating rewrite rule — the validator's
        # plumbing, analysis/findings.py) instead of a bare mismatch
        in_shapes = [pshapes[t.tensor_id] for t in layer.inputs]
        op = create_op(layer, in_shapes)
        strategy = dict(strategies.get(layer.name, {}))
        strategy["_axis_sizes"] = axis_sizes
        op.axis_sizes = dict(axis_sizes)  # single source for sim/search costs
        try:
            out_shapes, weight_shapes = op.propagate(in_shapes, strategy)
        except (AssertionError, ValueError, KeyError, IndexError) as e:
            raise ValueError(
                f"{layer_provenance(layer)}: sharding propagation "
                f"rejected strategy {strategies.get(layer.name)} on "
                f"inputs {[str(s) for s in in_shapes]}: {e}") from e
        for ps in list(out_shapes) + list(weight_shapes.values()):
            if ps.has_duplicate_axes():
                raise ValueError(
                    f"{layer_provenance(layer)}: strategy "
                    f"{strategies.get(layer.name)} "
                    f"maps one mesh axis onto two dims of a tensor "
                    f"({ps.partition_spec()}) — impossible GSPMD layout; "
                    f"pick a different axis for this op")
        op.output_shapes = out_shapes
        op.weight_shapes = weight_shapes
        # sanity: inferred logical sizes must match the declared outputs
        declared = layer.outputs
        for i, (t, ps) in enumerate(zip(declared, out_shapes)):
            if tuple(t.dims) != tuple(ps.sizes):
                raise ValueError(
                    f"{layer_provenance(layer)} output {i}: declared "
                    f"dims {tuple(t.dims)} vs propagated "
                    f"{tuple(ps.sizes)}")
            pshapes[t.tensor_id] = ps
        ops.append(op)
    return ops, pshapes


def _named_sharding(mesh: Mesh, ps: ParallelTensorShape) -> NamedSharding:
    return NamedSharding(mesh, ps.partition_spec())


def init_params(
    ops: List[Op],
    mesh: Mesh,
    seed: int,
    dtype_override=None,
) -> Tuple[Dict, Dict, Dict]:
    """Initialize all weights on-device with their target shardings.

    reference analog: per-op init tasks + initializer tasks
    (src/runtime/initializer.cc); here a single jitted init per weight with
    ``out_shardings`` so large weights are born sharded (no host round-trip).
    """
    import zlib

    root = jax.random.key(seed)
    params: Dict[str, Dict[str, jax.Array]] = {}
    shardings: Dict[str, Dict[str, NamedSharding]] = {}
    wd_mask: Dict[str, Dict[str, bool]] = {}
    for op in ops:
        specs = op.weight_specs()
        if not specs:
            continue
        params[op.name] = {}
        shardings[op.name] = {}
        wd_mask[op.name] = {}
        # key on a stable hash of the op name (not its graph index) so
        # inits are invariant to graph passes that renumber ops (fusion,
        # recompile) — the same named layer always draws the same weights
        op_key = jax.random.fold_in(root, zlib.crc32(op.name.encode()))
        for wi, ws in enumerate(specs):
            key = jax.random.fold_in(op_key, wi)
            sh = _named_sharding(mesh, op.weight_shapes[ws.name])
            jdtype = dtype_override or ws.dtype.to_jnp()
            init_fn = ws.initializer
            if isinstance(init_fn, DeclaredInitializer):
                # declared, not drawn: the loader puts the array here
                params[op.name][ws.name] = jax.ShapeDtypeStruct(
                    ws.shape, jdtype, sharding=sh)
                shardings[op.name][ws.name] = sh
                wd_mask[op.name][ws.name] = ws.weight_decay
                continue

            @functools.partial(jax.jit, out_shardings=sh)
            def _init(key, _fn=init_fn, _shape=ws.shape, _dt=jdtype):
                return _fn(key, _shape, _dt)

            params[op.name][ws.name] = _init(key)
            shardings[op.name][ws.name] = sh
            wd_mask[op.name][ws.name] = ws.weight_decay
    return params, shardings, wd_mask


# mixed precision: ops whose weights must stay full-precision in the
# forward pass — normalization statistics accumulate badly in bf16 (the
# Keras mixed_bfloat16 policy makes the same exception for BatchNorm)
_FULL_PRECISION_PARAM_OPS = frozenset({OpType.BATCHNORM})


def causal_lm_signature(cm: CompiledModel) -> Dict[str, Optional[int]]:
    """The serving tokenizer/vocab contract of a compiled causal LM:
    vocab size (the logits tensor's trailing dim) and position capacity
    (the position-embedding table's ``num_entries``, None when the
    graph has no position embedding).

    This is the draft-model compile seam for speculative decoding: a
    draft proposes token ids the TARGET must be able to verify, so the
    two models must agree on vocab exactly and the draft must cover the
    serving ``max_length`` — validated once here at registration, never
    per dispatch."""
    vocab = int(cm.logits_tensor.dims[-1])
    max_positions: Optional[int] = None
    if len(cm.input_tensors) >= 2:
        pos_tid = cm.input_tensors[1].tensor_id
        for op in cm.ops:
            if (op.op_type is OpType.EMBEDDING
                    and op.layer.inputs[0].tensor_id == pos_tid):
                max_positions = int(op.attrs["num_entries"])
    return {"vocab_size": vocab, "max_positions": max_positions}


def _resolve_compute_dtype(name: Optional[str]):
    if name in (None, "float32", "fp32", "f32"):
        return None
    if name in ("bfloat16", "bf16"):
        return jnp.bfloat16
    if name in ("float16", "fp16", "f16"):
        # fp16's narrow exponent range needs loss scaling, which this path
        # does not implement (bf16 shares fp32's exponent range and needs
        # none); reject rather than silently fail to converge
        raise ValueError(
            "compute_dtype float16 is unsupported (no loss scaling); "
            "use bfloat16 — the TPU-native mixed-precision dtype")
    raise ValueError(f"unknown compute_dtype {name!r}")


def make_caster(compute_dtype):
    """The ONE mixed-precision cast policy, shared by the main compiler
    and the pipeline engine: float leaves -> compute_dtype, everything
    else untouched; None -> identity."""
    if compute_dtype is None:
        return lambda x: x

    def cast(x):
        if jnp.issubdtype(jnp.result_type(x), jnp.floating):
            return x.astype(compute_dtype)
        return x

    return cast


def cast_op_params(cast, op, params: Dict, compute_dtype):
    """Per-op weight cast under the shared full-precision exception list
    (BatchNorm statistics stay fp32)."""
    if compute_dtype is None or op.op_type in _FULL_PRECISION_PARAM_OPS:
        return params
    return {k: cast(v) for k, v in params.items()}


def _forward_graph(
    ops: List[Op],
    mesh: Mesh,
    params: Dict,
    inputs: Dict[int, jnp.ndarray],
    training: bool,
    rng: Optional[jax.Array],
    seq_length: int = -1,
    compute_dtype=None,
):
    """Run the op graph; returns (acts dict, aux_losses, state_updates).

    Sharding constraints on op outputs realize the PCG's parallel-op
    transitions (SURVEY.md §7: Partition/Combine/Replicate/Reduction map to
    resharding).

    ``compute_dtype`` (e.g. bf16): activations and op weights are cast on
    entry to each op and outputs cast back to the compute dtype, while the
    ``params`` argument itself (the fp32 master copy) is untouched —
    ``jax.grad`` through the casts yields fp32 gradients against the
    masters (loss-scale-free bf16 mixed precision, the TPU-native recipe)."""
    ctx = LowerCtx(mesh=mesh, training=training, seq_length=seq_length,
                   aux_losses=[], state_updates={} if training else None,
                   compute_dtype=compute_dtype)
    cast = make_caster(compute_dtype)
    acts: Dict[int, jnp.ndarray] = {k: cast(v) for k, v in inputs.items()}
    for oi, op in enumerate(ops):
        ins = [acts[t.tensor_id] for t in op.layer.inputs]
        ctx.rng = jax.random.fold_in(rng, oi) if rng is not None else None
        with op_scope(op):
            p = cast_op_params(cast, op, weights_of(op, params),
                               compute_dtype)
            outs = op.forward(ctx, ins, p)
            for out, t, ps in zip(outs, op.layer.outputs, op.output_shapes):
                out = cast(out)
                if mesh is not None and (
                    any(d.is_partitioned for d in ps.dims)
                    or getattr(op, "force_constraint", False)
                ):
                    out = jax.lax.with_sharding_constraint(
                        out, _named_sharding(mesh, ps))
                acts[t.tensor_id] = out
    return acts, ctx.aux_losses, ctx.state_updates or {}


def compile_model(
    config: FFConfig,
    layers: List[Layer],
    input_tensors: List[Tensor],
    logits_tensor: Tensor,
    optimizer: Optional[Optimizer],
    loss_type: Optional[LossType],
    metrics: List[MetricsType],
    strategies: Optional[Dict[str, Dict[str, str]]] = None,
    mesh: Optional[Mesh] = None,
    comp_mode: CompMode = CompMode.TRAINING,
) -> CompiledModel:
    """The compile entry point (reference: FFModel::compile model.cc:2803)."""
    if mesh is None:
        mesh = make_mesh(config.mesh_shape)
    axis_sizes = mesh_axis_sizes(mesh)
    strategies = dict(strategies or {})

    # --- input sharding: batch dim over the data axis (the reference's
    # default Repartition-on-batch when only_data_parallel, model.cc:2638;
    # with search enabled inputs still default to sample-parallel).
    # --disable-sample-parallel keeps inputs replicated.
    data_degree = (axis_sizes.get(DATA_AXIS, 1)
                   if config.enable_sample_parallel else 1)
    input_pshapes: Dict[int, ParallelTensorShape] = {}
    for t in input_tensors:
        dims = []
        for i, s in enumerate(t.dims):
            if i == 0 and data_degree > 1 and s % data_degree == 0:
                dims.append(ParallelDim(s, data_degree, DATA_AXIS))
            else:
                dims.append(ParallelDim(s))
        input_pshapes[t.tensor_id] = ParallelTensorShape(tuple(dims), t.dtype)

    ops, pshapes = build_ops(layers, input_pshapes, axis_sizes, strategies)

    # --- label tensor (reference: model.cc:3085-3124 creates the label
    # ParallelTensor matching the final op's batch partitioning)
    label_tensor = None
    label_sharding = None
    if loss_type is not None:
        logits_ps = pshapes[logits_tensor.tensor_id]
        if loss_type is LossType.SPARSE_CATEGORICAL_CROSSENTROPY:
            lab_sizes: Tuple[int, ...] = (logits_tensor.dims[0], 1)
            lab_dtype = DataType.INT32
        else:
            lab_sizes = logits_tensor.dims
            lab_dtype = logits_tensor.dtype
        lab_dims = [ParallelDim(s) for s in lab_sizes]
        if logits_ps.dims[0].is_partitioned and lab_sizes[0] == logits_ps.dims[0].size:
            lab_dims[0] = ParallelDim(
                lab_sizes[0], logits_ps.dims[0].degree, logits_ps.dims[0].axis
            )
        lab_ps = ParallelTensorShape(tuple(lab_dims), lab_dtype)
        label_tensor = Tensor(lab_sizes, lab_dtype, name="label")
        pshapes[label_tensor.tensor_id] = lab_ps
        label_sharding = _named_sharding(mesh, lab_ps)

    _t0_init = time.perf_counter()
    with span("compile.init_params", cat="compile"):
        params, param_shardings, wd_mask = init_params(
            ops, mesh, config.seed)
    metrics_registry().counter("setup.init_params_s").inc(
        time.perf_counter() - _t0_init)
    opt_state = optimizer.init_state(params) if optimizer is not None else None

    # ---- ZeRO-1: shard optimizer state over the data axis -----------------
    # Each state array inherits its weight's TP sharding (zeros_like keeps
    # shardings); ZeRO additionally partitions the first data-axis-divisible
    # unsharded dim over DATA, so momentum/variance live 1/dp-th per chip.
    # The same constraint inside the step keeps them sharded across updates
    # (SURVEY.md §7 step 10: ZeRO-sharded optimizer states).
    opt_state_shardings = None
    if (config.zero_optimizer and opt_state is not None
            and axis_sizes.get(DATA_AXIS, 1) > 1):
        dp = axis_sizes[DATA_AXIS]

        def _zero_sharding(leaf):
            if not hasattr(leaf, "shape") or leaf.ndim == 0:
                return None
            spec = list(getattr(leaf.sharding, "spec", ())) or [None] * leaf.ndim
            spec += [None] * (leaf.ndim - len(spec))
            # a weight explicitly sharded over the data axis already
            # distributes its state; adding it again would duplicate the
            # mesh axis in the spec (invalid)
            if any(DATA_AXIS == s or (isinstance(s, tuple) and DATA_AXIS in s)
                   for s in spec):
                return None
            for d in range(leaf.ndim):
                if spec[d] is None and leaf.shape[d] % dp == 0 \
                        and leaf.shape[d] >= dp:
                    spec[d] = DATA_AXIS
                    return NamedSharding(mesh, PartitionSpec(*spec))
            return None

        _leaves, _treedef = jax.tree_util.tree_flatten(opt_state)
        _shards = [_zero_sharding(l) for l in _leaves]
        opt_state = _treedef.unflatten([
            jax.device_put(l, s) if s is not None else l
            for l, s in zip(_leaves, _shards)])
        opt_state_shardings = (_treedef, _shards)

    input_shardings = [
        _named_sharding(mesh, input_pshapes[t.tensor_id]) for t in input_tensors
    ]

    n_inputs = len(input_tensors)
    input_ids = [t.tensor_id for t in input_tensors]
    logits_id = logits_tensor.tensor_id
    # CE losses: raw-logit graphs (no trailing Softmax) get a fused
    # log-softmax inside the loss; softmax-terminated graphs are treated as
    # probabilities, matching the reference's Loss::backward convention.
    # Value-preserving tail ops (identity/reshape/transpose/dropout) are
    # walked through so softmax→identity still counts as probabilities.
    _producer = {
        t.tensor_id: op for op in ops for t in op.layer.outputs
    }
    _passthrough = {OpType.IDENTITY, OpType.RESHAPE, OpType.TRANSPOSE,
                    OpType.DROPOUT}
    _tid = logits_id
    _logits_op = _producer.get(_tid)
    while _logits_op is not None and _logits_op.op_type in _passthrough:
        _tid = _logits_op.layer.inputs[0].tensor_id
        _logits_op = _producer.get(_tid)
    from_logits = _logits_op is None or _logits_op.op_type is not OpType.SOFTMAX

    cdt = _resolve_compute_dtype(config.compute_dtype)
    # token-native dynamic shapes: bucketed compiles pad rows with -1
    # labels, and the masked sparse-CE path makes those positions exact
    # zeros in loss/metrics/gradients. Compile-time constant — with the
    # knob off the historical unmasked programs are traced unchanged.
    mask_pad = getattr(config, "seq_buckets", "off") != "off"

    def _f32(x):
        # loss/metrics always in float32, whatever the compute dtype
        return x.astype(jnp.float32) if cdt is not None else x

    # sparse CE on raw logits reads them in the dtype the head wrote and
    # does its float32 arithmetic on the fly: a float32 copy of a
    # (tokens, vocabulary) array is most of what such a loss costs
    one_pass = (loss_type is LossType.SPARSE_CATEGORICAL_CROSSENTROPY
                and from_logits)

    def _loss(acts, y):
        """The loss and, on the one-pass path, the per-position
        log-likelihoods it is the mean of (the metrics read them too)."""
        if one_pass:
            return sparse_ce_from_logits(acts[logits_id], y, mask_pad)
        return compute_loss(loss_type, _f32(acts[logits_id]), y,
                            from_logits, mask_pad), None

    # ---- train step --------------------------------------------------------
    # ``seq_length`` is a leading STATIC argument on every step function:
    # each distinct value compiles its own executable (bucketed compile) —
    # the iteration-level truncation of the reference's
    # FFIterationConfig.seq_length (config.h:162-167, consumed by
    # BatchMatmul's a/b_seq_length_dim, model.cc:2415-2420). The public
    # wrappers keep the old calling convention with seq_length as a
    # keyword defaulting to -1 (no truncation).
    accum = max(1, int(getattr(config, "grad_accum_steps", 1)))

    def train_step(seq_length, hyper, params, opt_state, rng, *batch):
        xs = batch[:n_inputs]
        y = batch[n_inputs]

        def loss_fn(params, xs, y, rng):
            acts, aux, updates = _forward_graph(
                ops, mesh, params, dict(zip(input_ids, xs)), True, rng,
                seq_length, cdt,
            )
            with fixed_scope("loss"):
                loss, ll = _loss(acts, y)
                for a in aux:
                    loss = loss + _f32(a)
                # weight regularizers (keras frontend: kernel_regularizer
                # attr; reference keras/regularizers.py) — differentiable
                # penalties on the fp32 master weights
                for op in ops:
                    reg = op.attrs.get("kernel_regularizer")
                    if reg is not None and hasattr(reg, "penalty") \
                            and op.name in params \
                            and "kernel" in params[op.name]:
                        loss = loss + reg.penalty(params[op.name]["kernel"])
            return loss, (_f32(acts[logits_id]), ll, updates)

        vag = jax.value_and_grad(loss_fn, has_aux=True)
        if accum == 1:
            (loss, (logits, ll, updates)), grads = vag(params, xs, y, rng)
            with fixed_scope("metrics"):
                batch_metrics = compute_batch_metrics(
                    metrics, loss_type, logits, y, from_logits, mask_pad, ll)
        else:
            # gradient accumulation: split the batch into K microbatches,
            # run them through a lax.scan (ONE compiled body, K x less
            # activation memory), average grads, update once
            if y.shape[0] % accum != 0:
                raise ValueError(
                    f"batch {y.shape[0]} not divisible by "
                    f"grad_accum_steps {accum}")

            def resh(a):
                return a.reshape((accum, a.shape[0] // accum) + a.shape[1:])

            xs_k = tuple(resh(a) for a in xs)
            y_k = resh(y)
            rngs = jax.random.split(rng, accum)

            def one(xs_i, y_i, rng_i):
                (li, (lgi, lli, updi)), gi = vag(params, xs_i, y_i, rng_i)
                with fixed_scope("metrics"):
                    bmi = compute_batch_metrics(
                        metrics, loss_type, lgi, y_i, from_logits, mask_pad,
                        lli)
                return li, gi, bmi, updi

            def micro(carry, mb):
                g_acc, bm_acc, l_acc, upd_acc = carry
                li, gi, bmi, updi = one(*mb)
                g_acc = jax.tree_util.tree_map(jnp.add, g_acc, gi)
                bm_acc = {k: bm_acc[k] + bmi[k] for k in bm_acc}
                # BN running stats: sum now, average after the scan — one
                # EMA advance driven by the full batch's mean statistics
                upd_acc = {k: upd_acc[k] + v for k, v in updi.items()}
                return (g_acc, bm_acc, l_acc + li, upd_acc), None

            # zero-seed the carry from abstract shapes so the body is
            # traced/compiled ONCE (an unrolled first microbatch would
            # duplicate the whole fwd+bwd graph)
            shapes = jax.eval_shape(
                one, tuple(a[0] for a in xs_k), y_k[0], rngs[0])
            _, g_s, bm_s, upd_s = shapes
            zeros = lambda tree: jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype), tree)
            carry0 = (zeros(g_s), zeros(bm_s), jnp.zeros((), jnp.float32),
                      zeros(upd_s))
            (grads, batch_metrics, loss_sum, upd_sum), _ = jax.lax.scan(
                micro, carry0, (xs_k, y_k, rngs))
            grads = jax.tree_util.tree_map(lambda g: g / accum, grads)
            updates = {k: v / accum for k, v in upd_sum.items()}
            loss = loss_sum / accum
        with fixed_scope("optimizer"):
            new_params, new_opt_state = optimizer.update(
                params, grads, opt_state, wd_mask, hyper)
            if opt_state_shardings is not None:
                # keep ZeRO state sharded across updates: GSPMD
                # reduce-scatters the grad into the sharded moment update
                # and all-gathers only the weight delta
                td, shards = opt_state_shardings
                ls = td.flatten_up_to(new_opt_state)
                new_opt_state = td.unflatten([
                    jax.lax.with_sharding_constraint(l, s)
                    if s is not None else l
                    for l, s in zip(ls, shards)])
        # non-trainable state (BatchNorm running stats) written after the
        # optimizer update — reference: cuDNN BN forward-training updates
        # the running averages in the same pass (batch_norm.cu)
        for (opn, wn), v in updates.items():
            new_params[opn] = {**new_params[opn],
                               wn: jax.lax.stop_gradient(v).astype(
                                   new_params[opn][wn].dtype)}
        return new_params, new_opt_state, loss, batch_metrics

    # ---- multi-step executable (dispatch-ahead amortization) ---------------
    # K train steps in ONE dispatch: lax.scan of the step body over a
    # stacked (k, batch, ...) super-batch + a (k,) rng-key vector. Each
    # scan iteration is EXACTLY one train_step application (same params ->
    # grads -> update chain), so K scanned steps are numerically
    # equivalent to K serial dispatches; per-dispatch host/infeed overhead
    # is paid once instead of K times (the small-step regime where
    # dispatch dominates — Kaufman et al. 2020). The WHOLE step lives in
    # the one program: forward/backward, gradient-sync collectives, the
    # optimizer update, AND the per-step batch-metric fold — the metric
    # accumulator rides the scan carry and folds each step's metrics in
    # step order, so the returned totals match k serial accumulates bit
    # for bit while the host parks exactly ONE device dict per dispatch
    # instead of k. Per-step losses still come back stacked (k,) — the
    # loss trajectory, guard sum, and recompile trigger need step
    # granularity and k scalars are free.
    def train_k_steps(seq_length, hyper, params, opt_state, rngs, *stacked):
        bm_spec = jax.eval_shape(
            train_step, seq_length, hyper, params, opt_state, rngs[0],
            *(s[0] for s in stacked))[3]
        bm0 = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), bm_spec)

        def body(carry, per_step):
            params_i, opt_i, bm_acc = carry
            rng_i, batch_i = per_step[0], per_step[1:]
            params_i, opt_i, loss_i, bm_i = train_step(
                seq_length, hyper, params_i, opt_i, rng_i, *batch_i)
            # device-side metric folding in step order (zero + x is
            # bit-exact, so the k-fold equals k serial host folds)
            bm_acc = {k: bm_acc[k] + bm_i[k] for k in bm_acc}
            return (params_i, opt_i, bm_acc), loss_i

        (params, opt_state, bm_folded), losses = jax.lax.scan(
            body, (params, opt_state, bm0), (rngs,) + stacked)
        return params, opt_state, losses, bm_folded

    # ---- standalone grad step (for the manual backward() verb) ------------
    def grad_step(seq_length, params, rng, *batch):
        xs = batch[:n_inputs]
        y = batch[n_inputs]

        def loss_fn(params):
            acts, aux, _updates = _forward_graph(
                ops, mesh, params, dict(zip(input_ids, xs)), True, rng,
                seq_length, cdt,
            )
            loss, _ll = _loss(acts, y)
            for a in aux:
                loss = loss + _f32(a)
            return loss

        return jax.grad(loss_fn)(params)

    # ---- eval / forward ----------------------------------------------------
    def eval_step(seq_length, params, *batch):
        xs = batch[:n_inputs]
        y = batch[n_inputs]
        acts, _, _ = _forward_graph(ops, mesh, params, dict(zip(input_ids, xs)),
                                    False, None, seq_length, cdt)
        logits = _f32(acts[logits_id])
        loss, ll = _loss(acts, y) if loss_type else (jnp.zeros(()), None)
        return loss, logits, compute_batch_metrics(
            metrics, loss_type, logits, y, from_logits, mask_pad, ll)

    def forward_fn(params, *xs, seq_length: int = -1):
        acts, _, _ = _forward_graph(ops, mesh, params, dict(zip(input_ids, xs)),
                                    False, None, seq_length, cdt)
        return _f32(acts[logits_id])

    def _wrap(jitted):
        """seq_length keyword -> leading static positional."""
        def call(*args, seq_length: int = -1):
            return jitted(seq_length, *args)
        return call

    def _wrap_train(jitted):
        """Like _wrap, plus the optimizer's hyperparams as a DYNAMIC
        argument read fresh per call — lr schedules/backoffs take effect
        without re-tracing (pjit caches by the underlying function, so a
        re-jit would silently reuse the stale executable)."""
        def call(*args, seq_length: int = -1):
            return jitted(seq_length, optimizer.hyperparams(), *args)
        return call

    jit_train = None
    jit_train_k = None
    jit_grad = None
    _train_exec = None
    _train_k_exec = None
    if optimizer is not None and loss_type is not None:
        _train_exec = jax.jit(train_step, static_argnums=0,
                              donate_argnums=(2, 3))
        jit_train = _wrap_train(_train_exec)
        # one executable per distinct super size (the leading dim is part
        # of the trace shape) — the Prefetcher's plan only uses power-of-
        # two sizes up to k, so at most log2(k) entries compile
        _train_k_exec = jax.jit(train_k_steps, static_argnums=0,
                                donate_argnums=(2, 3))
        jit_train_k = _wrap_train(_train_k_exec)
        jit_grad = _wrap(jax.jit(grad_step, static_argnums=0))
    # ---- AUD002-driven donation: the eval label buffer -------------------
    # For dense losses the label tensor's aval equals the logits output's
    # aval (label-matches-logits convention, model.cc:3085), so XLA can
    # write the eval logits straight into the label's buffer. The eval
    # loop builds a fresh label per step and never reads it after the
    # call (the audit's caller-reuse check keeps it that way), so
    # donation is safe and outputs are bit-identical — aliasing never
    # changes values, XLA inserts copies where ordering requires. Sparse
    # labels ((B, 1) int32) have no matching output and stay undonated.
    _donate_eval: Tuple[int, ...] = ()
    if label_tensor is not None:
        _logits_out_dtype = (jnp.float32 if cdt is not None
                             else pshapes[logits_id].dtype.to_jnp())
        if (tuple(label_tensor.dims) == tuple(logits_tensor.dims)
                and label_tensor.dtype.to_jnp() == _logits_out_dtype):
            # y is positional arg 2 + n_inputs of eval_step
            _donate_eval = (2 + n_inputs,)
    _eval_exec = jax.jit(eval_step, static_argnums=0,
                         donate_argnums=_donate_eval)
    jit_eval = _wrap(_eval_exec)
    _jit_fwd = jax.jit(forward_fn, static_argnames=("seq_length",))

    def jit_forward(params, *xs, seq_length: int = -1):
        return _jit_fwd(params, *xs, seq_length=seq_length)

    # ---- program-audit handles (analysis/program_audit.py) ---------------
    # abstract example arguments with the SAME avals as a real dispatch:
    # the audit gate traces through jit's AOT API, and matching avals
    # mean that trace is the one the first real call replays
    from ..analysis.program_audit import ExecutableSpec

    def _sds(a):
        return (jax.ShapeDtypeStruct(a.shape, a.dtype)
                if hasattr(a, "shape") and hasattr(a, "dtype") else a)

    _params_sds = jax.tree_util.tree_map(_sds, params)
    _batch_sds = [jax.ShapeDtypeStruct(tuple(t.dims), t.dtype.to_jnp())
                  for t in input_tensors]
    if label_tensor is not None:
        _batch_sds.append(jax.ShapeDtypeStruct(
            tuple(label_tensor.dims), label_tensor.dtype.to_jnp()))
        audit_exec = [ExecutableSpec(
            "eval_step", _eval_exec, (-1, _params_sds, *_batch_sds),
            static_args={"seq_length": -1})]
    else:
        # inference-only compile (no loss/label): eval_step cannot be
        # traced without a label aval, and the program such callers
        # actually dispatch is the forward pass — audit that instead
        audit_exec = [ExecutableSpec(
            "forward", _jit_fwd, (_params_sds, *_batch_sds))]
    if _train_exec is not None:
        _opt_sds = jax.tree_util.tree_map(_sds, opt_state)
        audit_exec.insert(0, ExecutableSpec(
            "train_step", _train_exec,
            (-1, optimizer.hyperparams(), _params_sds, _opt_sds,
             jax.random.key(config.seed), *_batch_sds),
            static_args={"seq_length": -1}))
        # whole-program multi-step executable: when the step loop will
        # actually dispatch it (steps_per_dispatch > 1), the audit gate
        # covers it too — donation, baked consts, collective legality
        # and the in-scan metric fold all live in THIS program, and its
        # AOT trace is the one the first super-batch dispatch replays
        _k = max(1, int(getattr(config, "steps_per_dispatch", 1)))
        if _k > 1:
            _rngs_k = jnp.stack([jax.random.key(config.seed)] * _k)
            _batch_k = [jax.ShapeDtypeStruct((_k,) + tuple(b.shape),
                                             b.dtype)
                        for b in _batch_sds]
            audit_exec.insert(1, ExecutableSpec(
                "train_k_steps", _train_k_exec,
                (-1, optimizer.hyperparams(), _params_sds, _opt_sds,
                 _rngs_k, *_batch_k),
                static_args={"seq_length": -1}))

    cm = CompiledModel(
        config=config,
        mesh=mesh,
        ops=ops,
        input_tensors=list(input_tensors),
        label_tensor=label_tensor,
        logits_tensor=logits_tensor,
        loss_type=loss_type,
        metrics=list(metrics),
        optimizer=optimizer,
        params=params,
        opt_state=opt_state,
        wd_mask=wd_mask,
        param_shardings=param_shardings,
        input_shardings=input_shardings,
        label_sharding=label_sharding,
        train_step=jit_train,
        train_k_steps=jit_train_k,
        eval_step=jit_eval,
        forward_fn=jit_forward,
        grad_step=jit_grad,
        raw_forward=forward_fn,
        from_logits=from_logits,
        tensor_pshapes=pshapes,
        audit_exec=audit_exec,
    )

    def _refresh_train_step():
        # No-op by design: optimizer hyperparams are DYNAMIC step
        # arguments (optimizer.hyperparams() read fresh per call), so
        # mutating lr/alpha is already live. Kept as the stable hook the
        # guard/scheduler call — re-jitting here would be a lie: pjit's
        # cache is keyed on the underlying function and would silently
        # reuse the stale executable.
        pass

    cm.refresh_train_step = _refresh_train_step
    return cm
