"""Operator base class and registry.

TPU-native re-design of the reference's ``Op``
(reference: include/flexflow/operator.h:51-277). The reference Op carries
Legion task launchers (``init/forward/backward``), per-device ``OpMeta``,
region requirements, and a ``measure_operator_cost`` hook. Here an Op is a
pure function over jax arrays plus metadata:

* ``infer_output_shapes`` — shape rule (reference: each op's output-shape
  logic in its constructor, e.g. src/ops/linear.cc).
* ``weight_specs`` — declared weights with initializers (reference: weight
  ParallelTensor creation per op).
* ``forward`` — jax lowering. **No hand-written backward**: the whole step
  is differentiated with ``jax.grad``; custom VJPs appear only where a
  Pallas kernel needs one.
* ``propagate`` — parallel-dim mapping: given input ParallelTensorShapes and
  this op's strategy, produce output/weight shardings (reference:
  ``ParallelDimMappingRecord`` operator.h:22 + ``solve_parallel_dim_mappings``
  model.h:238).
* ``flops``/cost hooks for the simulator (reference:
  ``measure_operator_cost``).

The per-device ``OpMeta``/``FFHandler`` machinery has no equivalent: device
state lives in sharded arrays, and XLA owns kernel selection.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Type
from urllib.parse import quote, unquote

import jax
import jax.numpy as jnp

from ..ffconst import DataType, OpType
from .layer import Layer
from .machine import DATA_AXIS, MachineView
from .parallel_tensor import ParallelDim, ParallelTensorShape


@dataclasses.dataclass
class WeightSpec:
    """A trainable weight declared by an op."""

    name: str
    shape: Tuple[int, ...]
    dtype: DataType = DataType.FLOAT
    initializer: Optional[Any] = None  # Initializer instance or None => op default
    weight_decay: bool = True          # dense kernels yes, biases/norm scales no


@dataclasses.dataclass
class LowerCtx:
    """Context threaded through op lowering inside the jitted step."""

    mesh: Any = None
    training: bool = True
    rng: Optional[jax.Array] = None      # per-op PRNG key (dropout etc.)
    seq_length: int = -1                 # FFIterationConfig.seq_length
    compute_dtype: Optional[Any] = None  # e.g. jnp.bfloat16 for mixed precision
    # auxiliary losses collected during forward (e.g. MoE load-balancing —
    # the reference injects these as hand-written gradients in aggregate.cu;
    # here they are differentiable terms added to the training loss)
    aux_losses: Optional[list] = None
    # non-trainable state written during the training forward (BatchNorm
    # running statistics): {(op_name, weight_name): new_value}. The train
    # step writes these back into params AFTER the optimizer update, under
    # stop_gradient (their grads are zero anyway: training never reads
    # them). None = caller doesn't track state (eval / pipeline stages).
    state_updates: Optional[dict] = None


# ---------------------------------------------------------------------------
# scopes: whose work a lowered operation is. A scope is metadata on the HLO
# (the ``op_name`` path a device trace carries), entered at trace time only.
# ---------------------------------------------------------------------------
SCOPE_PREFIX = "ff."
# the fixed scopes of what a step does outside any graph op
FIXED_SCOPES = ("loss", "metrics", "optimizer", "sample", "tail", "counters")
# the pieces of work inside an op that different changes aim at, the same
# word in every entry kind (serving/cache_entry.py)
SUB_SCOPES = ("project", "write", "attend", "select", "conv", "rule",
              "chunks", "route", "latent", "experts", "gate", "window",
              "mix", "out")
# the group a metric sums an op type under; a type not named is "other"
OP_GROUPS: Dict[str, Tuple[OpType, ...]] = {
    # the types a serving program gives a pair, latent or sparse entry kind
    "attention": (OpType.MULTIHEAD_ATTENTION, OpType.LATENT_ATTENTION,
                  OpType.BLOCK_SPARSE_ATTENTION,
                  OpType.COMPRESSED_CONV_ATTENTION),
    # the types that keep a state a request
    "state": (OpType.GATED_DELTA_NET, OpType.KIMI_DELTA_ATTENTION,
              OpType.LIGHTNING_ATTENTION, OpType.MAMBA2),
    "matmul": (OpType.LINEAR, OpType.GATED_MLP, OpType.EXPERT_LINEAR,
               OpType.ROUTED_EXPERTS),
    # a residual path of several streams, read and written around a sublayer
    "stream": (OpType.STREAM_MIX,),
}
_GROUP_OF = {t.name: g for g, types in OP_GROUPS.items() for t in types}


def op_scope(op: "Op"):
    """The scope an op lowers under: ``jax.named_scope`` of
    ``ff.<OpType name>.<op name>``, e.g. ``ff.LINEAR.h3.mlp.fc``.

    The grammar, which :func:`parse_scope` inverts from the string alone.
    One component of an HLO ``op_name`` path (components are joined by
    ``/`` and wrapped by JAX's transforms, ``transpose(jvp(...))``) is
    this package's where it starts with ``ff.``. An OpType's name is
    upper case with no dot, so the second dot ends it and what follows,
    dots included, is the op's name percent-encoded (``urllib.parse.quote``
    with nothing safe: a path splits on ``/``, transforms wrap in ``(``
    and ``)``; letters, digits and ``_.-~`` stay as they are). A lower-case word and no second dot is one of
    :data:`FIXED_SCOPES` (``ff.loss``). Of the components behind the scope,
    those that are :data:`SUB_SCOPES` name the pieces of work inside the
    op, in order (``attend``; ``conv``, ``project``; a piece inside a loop
    stands behind JAX's ``while/body``): no primitive or transform of
    JAX's has one of those words for its whole name. A scope inside a
    scope (a fused op's members, ``ff.counters`` inside an expert layer,
    a piece inside a piece) owns what is under it: the innermost
    speaks."""
    return jax.named_scope(
        f"{SCOPE_PREFIX}{op.op_type.name}.{quote(op.name, safe='')}")


def _word_scope(word: str, vocabulary: Tuple[str, ...], prefix: str = ""):
    if word not in vocabulary:
        raise ValueError(f"no scope {word!r} among {vocabulary}")
    return jax.named_scope(prefix + word)


def fixed_scope(what: str):
    """``ff.<what>``, for what a step does outside any graph op: one of
    :data:`FIXED_SCOPES`."""
    return _word_scope(what, FIXED_SCOPES, SCOPE_PREFIX)


def sub_scope(piece: str):
    """A piece of work inside an op's scope, one of :data:`SUB_SCOPES`."""
    return _word_scope(piece, SUB_SCOPES)


def parse_scope(op_name_path: str):
    """``(type, name, sub-scopes, phase)`` of an HLO ``op_name`` path, or
    None where no component is this package's. ``type`` is the OpType's
    name, or the word of a fixed scope with ``name`` ``""``; ``phase`` is
    ``"bwd"`` where the path holds ``transpose(``, else ``"fwd"``."""
    parts = op_name_path.split("/")
    found = None
    for i, part in enumerate(parts):
        inner = part[part.rfind("(") + 1:].rstrip(")")
        if inner.startswith(SCOPE_PREFIX):
            found = (i, inner[len(SCOPE_PREFIX):])
    if found is None:
        return None
    i, body = found
    kind, dot, name = body.partition(".")
    if dot and kind.isupper():
        name = unquote(name)
    else:
        kind, name = body, ""
    subs = tuple(part for part in parts[i + 1:] if part in SUB_SCOPES)
    phase = "bwd" if "transpose(" in op_name_path else "fwd"
    return kind, name, subs, phase


def scope_group(kind: str) -> str:
    """The group of :data:`OP_GROUPS` an OpType's name falls in, else
    ``"other"`` (a new op type, until someone says otherwise)."""
    return _GROUP_OF.get(kind, "other")


def weights_of(op: "Op", params: Dict) -> Dict:
    """``op``'s weights out of a parameter tree ``{op name: {weight name:
    array}}``: its own, and what it borrows of another op's (a head tied
    to the embedding): the same array, so a gradient through the tree is
    the sum over both uses."""
    own = params.get(op.name, {})
    if not op.borrows:
        return own
    own = dict(own)
    for name, (owner, theirs) in op.borrows.items():
        if owner not in params:
            raise ValueError(
                f"{op.name} reads {owner}'s {theirs!r}, which this parameter "
                f"tree does not hold (a pipeline stage of its own?)")
        own[name] = params[owner][theirs]
    return own


class Op:
    """Base operator. Subclasses set ``op_type`` and implement the hooks."""

    op_type: OpType = OpType.NOOP

    def __init__(self, layer: Layer, input_shapes: List[ParallelTensorShape]):
        self.layer = layer
        self.name = layer.name
        self.attrs = layer.attrs
        self.input_shapes = input_shapes
        # filled by the compiler:
        self.output_shapes: List[ParallelTensorShape] = []
        self.weight_shapes: Dict[str, ParallelTensorShape] = {}
        self.machine_view: Optional[MachineView] = None
        # weights this op reads and does not own: {the name ``forward``
        # finds it under: (the owner op's name, the owner's name for it)}.
        # The parameter tree holds such a weight once, under its owner
        # (:func:`weights_of` hands it over)
        self.borrows: Dict[str, Tuple[str, str]] = {}

    # ---- shape rule -------------------------------------------------------
    def infer_output_shapes(self) -> List[Tuple[Tuple[int, ...], DataType]]:
        raise NotImplementedError

    # ---- weights ----------------------------------------------------------
    def weight_specs(self) -> List[WeightSpec]:
        return []

    # ---- lowering ---------------------------------------------------------
    def forward(
        self,
        ctx: LowerCtx,
        inputs: Sequence[jnp.ndarray],
        weights: Dict[str, jnp.ndarray],
    ) -> List[jnp.ndarray]:
        raise NotImplementedError

    # ---- parallel-dim mapping --------------------------------------------
    def propagate(
        self, input_shapes: List[ParallelTensorShape], strategy: Dict[str, str]
    ) -> Tuple[List[ParallelTensorShape], Dict[str, ParallelTensorShape]]:
        """Map input shardings to output/weight shardings under ``strategy``.

        Default rule (covers most elementwise/batch-preserving ops): outputs
        inherit the partitioning of input 0 on dims they share size with,
        batch dim first; weights replicated. Mirrors the identity
        parallel-dim mapping records most reference ops register.

        ``honored_strategy_keys`` records the entries whose requested
        effect this propagation realized WITHOUT changing the shapes an
        ablation would compare — schedule selections (attention's
        ``seq`` ring/a2a choice) and shardings already realized on the
        requested dim by inheritance (a downstream conv's ``spatial``).
        The PCG006 ablation check (analysis/pcg_check.py) consults it so
        schedule-only entries are not misread as silently dropped.
        """
        self.honored_strategy_keys = set()
        out_shapes = []
        in0 = input_shapes[0] if input_shapes else None
        for sizes, dtype in self.infer_output_shapes():
            dims = []
            for i, s in enumerate(sizes):
                src = None
                if in0 is not None and i < len(in0.dims) and in0.dims[i].size == s:
                    src = in0.dims[i]
                if src is not None and src.is_partitioned:
                    dims.append(ParallelDim(s, src.degree, src.axis))
                else:
                    dims.append(ParallelDim(s))
            out_shapes.append(ParallelTensorShape(tuple(dims), dtype))
        weight_shapes = {
            ws.name: ParallelTensorShape.unpartitioned(ws.shape, ws.dtype)
            for ws in self.weight_specs()
        }
        return out_shapes, weight_shapes

    # ---- cost hooks (simulator; reference: measure_operator_cost) --------
    def flops(self) -> float:
        """Forward FLOPs estimate for the analytic cost model."""
        return 0.0

    def input_contraction_dims(self) -> List[Tuple[int, int, Optional[str], int]]:
        """Contraction structure for comm-cost modeling: tuples of
        (input_index, input_dim, weight_name, weight_dim) where input_dim is
        summed against weight_dim. Lets the simulator distinguish a sharded
        contraction (partial sums → all-reduce) from a sharding mismatch
        (→ all-gather of the input) — the cost difference between the
        reference's partition-linear-combine and replicate-linear-combine
        patterns (substitution.cc:77-108)."""
        return []

    def __repr__(self) -> str:
        return f"{type(self).__name__}({self.name})"


# ---------------------------------------------------------------------------
# registry: OpType -> Op subclass (reference analog: the create_operator_from
# _layer static-factory switch, src/runtime/model.cc:2605)
# ---------------------------------------------------------------------------
_OP_REGISTRY: Dict[OpType, Type[Op]] = {}


def register_op(cls: Type[Op]) -> Type[Op]:
    _OP_REGISTRY[cls.op_type] = cls
    return cls


def create_op(layer: Layer, input_shapes: List[ParallelTensorShape]) -> Op:
    try:
        cls = _OP_REGISTRY[layer.op_type]
    except KeyError:
        raise NotImplementedError(f"no op registered for {layer.op_type}") from None
    return cls(layer, input_shapes)


def registered_ops() -> Dict[OpType, Type[Op]]:
    return dict(_OP_REGISTRY)
