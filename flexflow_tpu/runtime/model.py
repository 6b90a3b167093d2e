"""FFModel: the user-facing model builder and training driver.

TPU-native equivalent of the reference's ``FFModel``
(reference: include/flexflow/model.h:326-958, src/runtime/model.cc). The
builder surface mirrors the reference's ~60 methods (model.h:326-554); the
training verbs (``fit``/``eval``/``forward``/``backward``/``update``/
``zero_gradients``) mirror the Python ``flexflow.core`` surface
(python/flexflow/core/flexflow_cffi.py:887-2105).

Execution model: instead of per-op Legion index launches, ``compile``
produces ONE jitted SPMD step (see runtime/compiler.py); the training verbs
drive it.
"""

from __future__ import annotations

import collections
import os
import sys
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp

from ..ffconst import (
    ActiMode,
    AggrMode,
    CompMode,
    DataType,
    LossType,
    MetricsType,
    OpType,
    PoolType,
)
from ..config import FFConfig, FFIterationConfig
from ..core.layer import Layer
from ..core.machine import DATA_AXIS, make_mesh, mesh_axis_sizes
from ..core.tensor import Parameter, Tensor
from ..obs.metrics import metrics_registry
from ..obs.trace import configure_tracer, span, tracer
from .buckets import (DynamicShapeError, PackingSpec, resolve_ladder,
                      row_lengths)
from .compiler import CompiledModel, compile_model
from .dataloader import DataLoaderGroup, Prefetcher, SingleDataLoader
from .loss import loss_from_string
from .metrics import PerfMetrics
from .profiling import EpochThroughput
from .optimizer import Optimizer, SGDOptimizer

_METRICS_FROM_STRING = {
    "accuracy": MetricsType.ACCURACY,
    "categorical_crossentropy": MetricsType.CATEGORICAL_CROSSENTROPY,
    "sparse_categorical_crossentropy": MetricsType.SPARSE_CATEGORICAL_CROSSENTROPY,
    "mean_squared_error": MetricsType.MEAN_SQUARED_ERROR,
    "root_mean_squared_error": MetricsType.ROOT_MEAN_SQUARED_ERROR,
    "mean_absolute_error": MetricsType.MEAN_ABSOLUTE_ERROR,
}


class FFModel:
    def __init__(self, config: Optional[FFConfig] = None):
        # every path that jits (fit, eval, serving) builds an FFModel
        # first: place the persistent compilation cache here, before it
        from ..utils.compile_cache import configure_compile_cache

        configure_compile_cache()
        self.config = config or FFConfig()
        self.layers: List[Layer] = []
        self.input_tensors: List[Tensor] = []
        self.optimizer: Optional[Optimizer] = None
        self.compiled: Optional[CompiledModel] = None
        self.pipelined = None  # PipelinedModel when compile(pipeline=...)
        self.search_result = None  # GraphSearchResult from the last search
        # timing/coverage/cache counters from the last _run_search (see
        # _finish_search); surfaced by runtime/profiling.py exports
        self.search_profile = None
        # step-loop throughput counters from the last fit()/eval() (per-
        # epoch steps/s, host-input-wait, queue-depth histogram, dispatch-
        # ahead occupancy); surfaced by runtime/profiling.fit_report
        self.fit_profile = None
        self.eval_profile = None
        # analysis.ValidationReport from the last compile()'s PCG gate
        # (config.validate_pcg); None when the gate is off
        self.pcg_report = None
        self._pcg_prevalidated = None  # cache-hit report handoff
        # analysis.ValidationReport from the last compile()'s program
        # audit (config.audit_programs, analysis/program_audit.py);
        # None when the gate is off. audit_profile carries the gate's
        # wall time + per-program stats for the <5%-of-compile budget.
        self.audit_report = None
        self.audit_profile = None
        self._search_strategies: Dict[str, Dict[str, str]] = {}
        self.iter_config = FFIterationConfig()
        self._param_index: Dict[int, Tuple[str, str]] = {}  # tensor_id -> (op, weight)
        self._label_np: Optional[np.ndarray] = None
        # manual-loop state (forward/backward/update verbs)
        self._cur_batch: Optional[List[jax.Array]] = None
        self._cur_logits = None
        self._cur_grads = None
        self._rng_counter = 0

    # ------------------------------------------------------------------ #
    # graph construction                                                 #
    # ------------------------------------------------------------------ #
    def create_tensor(
        self,
        dims: Sequence[int],
        dtype: DataType = DataType.FLOAT,
        name: Optional[str] = None,
        create_grad: bool = True,
    ) -> Tensor:
        """reference: FFModel::create_tensor (model.h:345); dims are
        batch-first (numpy order), matching the Python cffi surface."""
        t = Tensor(tuple(dims), dtype, name=name, model=self, create_gradients=create_grad)
        self.input_tensors.append(t)
        return t

    def _add_layer(
        self,
        op_type: OpType,
        inputs: List[Tensor],
        attrs: Dict[str, Any],
        out_dims_list: List[Tuple[Tuple[int, ...], DataType]],
        name: Optional[str],
    ) -> Union[Tensor, List[Tensor]]:
        layer = Layer(op_type, name=name, inputs=inputs, attrs=attrs)
        for i, (dims, dtype) in enumerate(out_dims_list):
            t = Tensor(dims, dtype, owner_layer=layer, owner_idx=i, model=self,
                       name=f"{layer.name}:out{i}")
            layer.outputs.append(t)
        self.layers.append(layer)
        return layer.outputs[0] if len(layer.outputs) == 1 else list(layer.outputs)

    def _infer_and_add(self, op_type, inputs, attrs, name):
        """Build a probe op to run shape inference at build time."""
        from ..core.op import create_op
        from ..core.parallel_tensor import ParallelTensorShape

        probe_layer = Layer(op_type, name="__probe__", inputs=inputs, attrs=attrs)
        probe = create_op(
            probe_layer,
            [ParallelTensorShape.unpartitioned(t.dims, t.dtype) for t in inputs],
        )
        outs = probe.infer_output_shapes()
        return self._add_layer(op_type, inputs, attrs, outs, name)

    # ---- dense / conv / pool / norm ----------------------------------- #
    def dense(
        self,
        input: Tensor,
        out_dim: int,
        activation: ActiMode = ActiMode.NONE,
        use_bias: bool = True,
        datatype: DataType = DataType.NONE,
        kernel_initializer=None,
        bias_initializer=None,
        kernel_regularizer=None,
        name: Optional[str] = None,
        strategy: Optional[Dict[str, str]] = None,
        tied_to: Optional[str] = None,
    ) -> Tensor:
        """reference: FFModel::dense (model.h:487, src/ops/linear.cc).
        ``kernel_regularizer`` (keras/regularizers.py) adds a
        differentiable penalty on the kernel to the training loss.
        ``tied_to`` (a TPU-native extension) names an embedding layer of
        ``out_dim`` entries as wide as ``input``: the layer then has no
        kernel of its own and multiplies by that table transposed, which
        the parameter tree holds once (a language model's tied head)."""
        if tied_to is not None:
            owner = next((l for l in self.layers if l.name == tied_to), None)
            if owner is None or owner.op_type is not OpType.EMBEDDING:
                raise ValueError(f"tied_to {tied_to!r}: no embedding layer "
                                 f"of that name before this one")
            table = (owner.attrs["num_entries"], owner.attrs["out_dim"])
            if table != (out_dim, input.dims[-1]):
                raise ValueError(
                    f"tied_to {tied_to!r}: its table is {table}, this layer "
                    f"needs {(out_dim, input.dims[-1])}")
            if kernel_regularizer is not None:
                raise ValueError("a tied layer has no kernel to regularize")
        attrs = dict(
            out_dim=out_dim,
            activation=activation,
            use_bias=use_bias,
            kernel_initializer=kernel_initializer,
            bias_initializer=bias_initializer,
            kernel_regularizer=kernel_regularizer,
        )
        if tied_to is not None:
            attrs["tied_to"] = tied_to
        if strategy:
            attrs["strategy"] = strategy
        return self._infer_and_add(OpType.LINEAR, [input], attrs, name)

    def conv2d(
        self,
        input: Tensor,
        out_channels: int,
        kernel_h: int,
        kernel_w: int,
        stride_h: int,
        stride_w: int,
        padding_h: int,
        padding_w: int,
        activation: ActiMode = ActiMode.NONE,
        groups: int = 1,
        use_bias: bool = True,
        kernel_initializer=None,
        bias_initializer=None,
        name: Optional[str] = None,
    ) -> Tensor:
        """reference: FFModel::conv2d (model.h:403, src/ops/conv_2d.cc).
        Input layout NCHW, matching the reference."""
        attrs = dict(
            out_channels=out_channels,
            kernel=(kernel_h, kernel_w),
            stride=(stride_h, stride_w),
            padding=(padding_h, padding_w),
            activation=activation,
            groups=groups,
            use_bias=use_bias,
            kernel_initializer=kernel_initializer,
            bias_initializer=bias_initializer,
        )
        return self._infer_and_add(OpType.CONV2D, [input], attrs, name)

    def pool2d(
        self,
        input: Tensor,
        kernel_h: int,
        kernel_w: int,
        stride_h: int,
        stride_w: int,
        padding_h: int,
        padding_w: int,
        pool_type: PoolType = PoolType.MAX,
        activation: ActiMode = ActiMode.NONE,
        name: Optional[str] = None,
    ) -> Tensor:
        """reference: FFModel::pool2d (model.h:461, src/ops/pool_2d.cc)."""
        attrs = dict(
            kernel=(kernel_h, kernel_w),
            stride=(stride_h, stride_w),
            padding=(padding_h, padding_w),
            pool_type=pool_type,
            activation=activation,
        )
        return self._infer_and_add(OpType.POOL2D, [input], attrs, name)

    def batch_norm(self, input: Tensor, relu: bool = True,
                   eps: float = 1e-5, name: Optional[str] = None) -> Tensor:
        """reference: FFModel::batch_norm (model.h:478, src/ops/batch_norm.cc)."""
        return self._infer_and_add(
            OpType.BATCHNORM, [input], dict(relu=relu, eps=float(eps)), name)

    def layer_norm(
        self,
        input: Tensor,
        axes: Sequence[int],
        elementwise_affine: bool = True,
        eps: float = 1e-5,
        name: Optional[str] = None,
    ) -> Tensor:
        """reference: FFModel::layer_norm (model.h:472, src/ops/layer_norm.cc)."""
        attrs = dict(axes=tuple(axes), elementwise_affine=elementwise_affine, eps=eps)
        return self._infer_and_add(OpType.LAYERNORM, [input], attrs, name)

    def rms_norm(self, input: Tensor, eps: float = 1e-6,
                 kernel_initializer=None,
                 name: Optional[str] = None) -> Tensor:
        """Root-mean-square norm over the last axis with a learned gain
        (ops/norm.py RMSNorm)."""
        return self._infer_and_add(
            OpType.RMS_NORM, [input],
            dict(eps=float(eps), kernel_initializer=kernel_initializer), name)

    def scale_shift(self, input: Tensor, kernel_initializer=None,
                    bias_initializer=None,
                    name: Optional[str] = None) -> Tensor:
        """``scale * x + shift``, two learned vectors over the last axis
        (ops/norm.py ScaleShift)."""
        return self._infer_and_add(
            OpType.SCALE_SHIFT, [input],
            dict(kernel_initializer=kernel_initializer,
                 bias_initializer=bias_initializer), name)

    def gated_mlp(self, input: Tensor, width: int,
                  activation: ActiMode = ActiMode.SILU,
                  kernel_initializer=None, limit: Optional[float] = None,
                  name: Optional[str] = None) -> Tensor:
        """``(act(x W_gate) * (x W_up)) W_down`` (ops/linear.py
        GatedMLP); with ``limit`` the gate is cut at ``limit`` from above
        and the up-projection to ``[-limit, limit]`` before the product."""
        attrs = dict(width=int(width), activation=activation,
                     kernel_initializer=kernel_initializer)
        if limit is not None:           # absent: the graph it was
            attrs["limit"] = float(limit)
        return self._infer_and_add(OpType.GATED_MLP, [input], attrs, name)

    def _stream_mix(self, part: str, inputs, streams: int, name, **more):
        return self._infer_and_add(
            OpType.STREAM_MIX, inputs,
            dict(part=part, streams=int(streams), **more), name)

    def stream_spread(self, input: Tensor, streams: int,
                      name: Optional[str] = None) -> Tensor:
        """``(B, S, d)`` copied into ``streams`` residual streams, ``(B,
        S, streams d)`` (ops/stream_mix.py)."""
        return self._stream_mix("spread", [input], streams, name)

    def stream_mix_pre(self, streams_in: Tensor, streams: int, *,
                       sinkhorn_iters: int = 20, eps: float = 1e-6,
                       norm_eps: float = 1e-6, kernel_initializer=None,
                       scale_initializer=None, bias_initializer=None,
                       name: Optional[str] = None) -> List[Tensor]:
        """The streams -> ``[u, coefficients]``: a sublayer's input and
        what :meth:`stream_mix_post` writes its output back under."""
        return self._stream_mix(
            "pre", [streams_in], streams, name,
            sinkhorn_iters=int(sinkhorn_iters), eps=float(eps),
            norm_eps=float(norm_eps), kernel_initializer=kernel_initializer,
            scale_initializer=scale_initializer,
            bias_initializer=bias_initializer)

    def stream_mix_post(self, streams_in: Tensor, y: Tensor, coefs: Tensor,
                        streams: int, name: Optional[str] = None) -> Tensor:
        """(streams, the sublayer's output, :meth:`stream_mix_pre`'s
        coefficients) -> the streams behind the sublayer."""
        return self._stream_mix("post", [streams_in, y, coefs], streams,
                                name)

    def stream_sum(self, streams_in: Tensor, streams: int,
                   name: Optional[str] = None) -> Tensor:
        """The streams summed, ``(B, S, d)``."""
        return self._stream_mix("sum", [streams_in], streams, name)

    def latent_attention(self, input: Tensor, positions: Tensor, *,
                         num_heads: int, q_lora_rank: Optional[int],
                         kv_lora_rank: int,
                         qk_nope_head_dim: int, qk_rope_head_dim: int,
                         v_head_dim: int, max_positions: int,
                         rope_theta: float = 10000.0,
                         rope_scaling: Optional[Dict[str, Any]] = None,
                         eps: float = 1e-6, output_gate: Optional[str] = None,
                         rope_interleaved: bool = False,
                         indexer: Optional[Dict[str, Any]] = None,
                         kernel_initializer=None, gain_initializer=None,
                         bias_initializer=None,
                         name: Optional[str] = None) -> Tensor:
        """Causal self-attention over a low-rank latent with rotary
        positions (ops/attention.py LatentAttention). ``positions`` is
        the graph's int32 positions input; ``rope_scaling`` an optional
        YaRN dict; ``q_lora_rank=None`` projects the queries in one step;
        ``output_gate="head"`` gates each head's output by a sigmoid;
        ``rope_interleaved`` turns the pairs ``(2i, 2i + 1)``; ``indexer``
        (``heads``, ``dim``, ``rope_dim``, ``pool``, ``topk``, ``theta``):
        a learned indexer picks the pools of ``pool`` rows a query reads,
        ``topk`` rows in all."""
        attrs = dict(
            num_heads=int(num_heads),
            q_lora_rank=None if q_lora_rank is None else int(q_lora_rank),
            kv_lora_rank=int(kv_lora_rank),
            qk_nope_head_dim=int(qk_nope_head_dim),
            qk_rope_head_dim=int(qk_rope_head_dim),
            v_head_dim=int(v_head_dim), max_positions=int(max_positions),
            rope_theta=float(rope_theta),
            rope_scaling=dict(rope_scaling) if rope_scaling else None,
            eps=float(eps), kernel_initializer=kernel_initializer,
            gain_initializer=gain_initializer)
        # (absent where they are the default's: an older graph's
        # attributes are what they were)
        if output_gate is not None:
            attrs["output_gate"] = str(output_gate)
        if rope_interleaved:
            attrs["rope_interleaved"] = True
        if indexer:
            attrs.update(indexer=dict(indexer),
                         bias_initializer=bias_initializer)
        return self._infer_and_add(OpType.LATENT_ATTENTION,
                                   [input, positions], attrs, name)

    def compressed_conv_attention(self, input: Tensor, positions: Tensor, *,
                                  num_heads: int, num_kv_heads: int,
                                  head_dim: int, taps0: int = 2,
                                  taps1: int = 2, rotary: float = 10000.0,
                                  rotary_dim: Optional[int] = None,
                                  kernel_initializer=None,
                                  gain_initializer=None,
                                  bias_initializer=None,
                                  name: Optional[str] = None) -> Tensor:
        """Causal grouped-head self-attention whose queries and keys pass
        two causal convolutions of ``taps0`` (depthwise) and ``taps1``
        (grouped by head) taps, whose values take half of each head from
        the token before, and whose first ``rotary_dim`` values a head
        are rotated by ``positions``, the graph's int32 (B, S) input
        (ops/attention.py CompressedConvAttention)."""
        attrs = dict(num_heads=int(num_heads), num_kv_heads=int(num_kv_heads),
                     head_dim=int(head_dim), taps0=int(taps0),
                     taps1=int(taps1), rotary=float(rotary),
                     rotary_dim=int(rotary_dim or head_dim),
                     kernel_initializer=kernel_initializer,
                     gain_initializer=gain_initializer,
                     bias_initializer=bias_initializer)
        return self._infer_and_add(OpType.COMPRESSED_CONV_ATTENTION,
                                   [input, positions], attrs, name)

    def gated_delta_net(self, input: Tensor, *, num_heads: int, key_dim: int,
                        value_dim: int, conv_taps: int = 4,
                        allow_neg_eigval: bool = False, eps: float = 1e-6,
                        kernel_initializer=None, gain_initializer=None,
                        gate_initializer=None,
                        name: Optional[str] = None) -> Tensor:
        """Linear attention by the gated delta rule over a state of fixed
        size a sequence (ops/gated_delta.py GatedDeltaNet): ``num_heads``
        heads of ``key_dim`` keys and ``value_dim`` values, a causal
        depthwise convolution of ``conv_taps`` taps before them."""
        attrs = dict(
            num_heads=int(num_heads), key_dim=int(key_dim),
            value_dim=int(value_dim), conv_taps=int(conv_taps),
            allow_neg_eigval=bool(allow_neg_eigval), eps=float(eps),
            kernel_initializer=kernel_initializer,
            gain_initializer=gain_initializer,
            gate_initializer=gate_initializer)
        return self._infer_and_add(OpType.GATED_DELTA_NET, [input], attrs,
                                   name)

    def kimi_delta_attention(self, input: Tensor, *, num_heads: int,
                             key_dim: int, value_dim: int,
                             conv_taps: int = 4, lower_bound: float = -5.0,
                             eps: float = 1e-6, kernel_initializer=None,
                             gain_initializer=None, gate_initializer=None,
                             decay_rank: Optional[int] = None,
                             output_gate: str = "head",
                             gate_rank: Optional[int] = None,
                             name: Optional[str] = None) -> Tensor:
        """Linear attention by the delta rule with a decay a key channel,
        ``exp(lower_bound * sigmoid(.))``, and one sigmoid output gate a
        head (ops/gated_delta.py KimiDeltaAttention); the state a
        sequence keeps is :meth:`gated_delta_net`'s. ``decay_rank``: the
        decay's projection through that rank; ``output_gate="channel"``:
        a gate a value channel, through ``gate_rank``."""
        attrs = dict(
            num_heads=int(num_heads), key_dim=int(key_dim),
            value_dim=int(value_dim), conv_taps=int(conv_taps),
            lower_bound=float(lower_bound), eps=float(eps),
            kernel_initializer=kernel_initializer,
            gain_initializer=gain_initializer,
            gate_initializer=gate_initializer)
        # (absent where they are the default's)
        if decay_rank:
            attrs["decay_rank"] = int(decay_rank)
        if output_gate != "head":
            attrs.update(output_gate=str(output_gate),
                         gate_rank=int(gate_rank))
        return self._infer_and_add(OpType.KIMI_DELTA_ATTENTION, [input],
                                   attrs, name)

    def block_sparse_attention(self, input: Tensor, *, num_heads: int,
                               num_kv_heads: int, head_dim: int,
                               selection: Optional[Dict[str, int]] = None,
                               eps: float = 1e-6, kernel_initializer=None,
                               gain_initializer=None,
                               name: Optional[str] = None) -> Tensor:
        """Causal attention with grouped key-value heads, an output gate
        and, past ``selection["dense_len"]`` positions, a selection of the
        key blocks it reads (ops/block_sparse_attention.py
        BlockSparseAttention); ``selection`` holds the sizes of
        ``Selection``."""
        attrs = dict(
            num_heads=int(num_heads), num_kv_heads=int(num_kv_heads),
            head_dim=int(head_dim),
            selection={k: int(v) for k, v in (selection or {}).items()},
            eps=float(eps), kernel_initializer=kernel_initializer,
            gain_initializer=gain_initializer)
        return self._infer_and_add(OpType.BLOCK_SPARSE_ATTENTION, [input],
                                   attrs, name)

    def lightning_attention(self, input: Tensor, positions: Tensor, *,
                            num_heads: int, head_dim: int, layer_index: int,
                            num_layers: int, rope_theta: float = 10000.0,
                            eps: float = 1e-6, kernel_initializer=None,
                            gain_initializer=None,
                            name: Optional[str] = None) -> Tensor:
        """Linear attention with a fixed decay a head over a state of
        fixed size a sequence (ops/lightning_attention.py
        LightningAttention); the decay is that of layer ``layer_index``
        of ``num_layers``; ``positions`` is the graph's int32 positions
        input (rotary)."""
        attrs = dict(
            num_heads=int(num_heads), head_dim=int(head_dim),
            layer_index=int(layer_index), num_layers=int(num_layers),
            rope_theta=float(rope_theta), eps=float(eps),
            kernel_initializer=kernel_initializer,
            gain_initializer=gain_initializer)
        return self._infer_and_add(OpType.LIGHTNING_ATTENTION,
                                   [input, positions], attrs, name)

    def mamba2(self, input: Tensor, *, num_heads: int, head_dim: int,
               state_size: int, n_groups: int = 1, conv_taps: int = 4,
               chunk_size: int = 128, eps: float = 1e-5,
               kernel_initializer=None, gain_initializer=None,
               gate_initializer=None, name: Optional[str] = None) -> Tensor:
        """A state-space mixer over a state of fixed size a sequence
        (ops/mamba2.py Mamba2): ``num_heads`` heads of ``head_dim``, a
        state of ``state_size`` a head channel, B and C shared by the
        heads of each of ``n_groups`` groups, a causal depthwise
        convolution of ``conv_taps`` taps before them."""
        attrs = dict(
            num_heads=int(num_heads), head_dim=int(head_dim),
            state_size=int(state_size), n_groups=int(n_groups),
            conv_taps=int(conv_taps), chunk_size=int(chunk_size),
            eps=float(eps), kernel_initializer=kernel_initializer,
            gain_initializer=gain_initializer,
            gate_initializer=gate_initializer)
        return self._infer_and_add(OpType.MAMBA2, [input], attrs, name)

    def routed_experts(self, input: Tensor, *, n_routed: int,
                       experts_per_token: int, width: int,
                       n_group: int = 1, topk_group: Optional[int] = None,
                       scoring: str = "sigmoid", norm_topk: bool = True,
                       routed_scale: float = 1.0,
                       experts_held: Optional[Tuple[int, int]] = None,
                       selection_bias: bool = False,
                       activation: str = "silu_gated",
                       latent: Optional[int] = None,
                       router: str = "linear",
                       router_width: Optional[int] = None,
                       router_state: Optional[Tensor] = None,
                       router_eps: float = 1e-5,
                       limit: Optional[float] = None,
                       kernel_initializer=None, bias_initializer=None,
                       gain_initializer=None,
                       name: Optional[str] = None):
        """Dropless top-k routed experts of which this op holds
        ``experts_held = (first, count)`` (default: all of them)
        (ops/moe_ops.py RoutedExperts). ``scoring``: ``"sigmoid"`` or
        ``"softmax"`` over all experts; ``selection_bias``: a learned
        bias an expert, added to the scores in the choice only;
        ``activation``: ``"silu_gated"`` (``silu(u Wg) * (u Wu)``) or
        ``"relu2"`` (a plain MLP, ``relu(u W1)^2``); ``latent``: the
        width the experts work in, between a projection down before them
        and one up after their sum. ``router="mlp"``: the scores come
        from an MLP over a state of ``router_width`` numbers a token,
        which takes the state of the layer before (``router_state``, that
        op's second output; None in a model's first such layer) and hands
        its own on: the builder then returns ``[output, state]``."""
        attrs = dict(
            n_routed=int(n_routed), experts_per_token=int(experts_per_token),
            width=int(width), n_group=int(n_group),
            topk_group=int(topk_group or n_group), scoring=scoring,
            norm_topk=bool(norm_topk), routed_scale=float(routed_scale),
            experts_held=(tuple(int(v) for v in experts_held)
                          if experts_held else None),
            kernel_initializer=kernel_initializer)
        # stated only where they depart, so that a graph without them is
        # the graph it was
        if selection_bias:
            attrs.update(selection_bias=True,
                         bias_initializer=bias_initializer)
        if activation != "silu_gated":
            attrs["activation"] = activation
        if latent:
            attrs["latent"] = int(latent)
        if limit is not None:
            attrs["limit"] = float(limit)
        inputs = [input]
        if router != "linear":
            attrs.update(router=router, router_width=int(router_width),
                         router_eps=float(router_eps),
                         gain_initializer=gain_initializer,
                         bias_initializer=bias_initializer)
            if router_state is not None:
                inputs.append(router_state)
        return self._infer_and_add(OpType.ROUTED_EXPERTS, inputs, attrs,
                                   name)

    # ---- elementwise --------------------------------------------------- #
    def _binary(self, op_type, x, y, name=None, inplace_a=False):
        return self._infer_and_add(op_type, [x, y], {}, name)

    def add(self, x, y, name=None, inplace_a=False):
        return self._binary(OpType.EW_ADD, x, y, name)

    def subtract(self, x, y, name=None, inplace_a=False):
        return self._binary(OpType.EW_SUB, x, y, name)

    def multiply(self, x, y, name=None, inplace_a=False):
        return self._binary(OpType.EW_MUL, x, y, name)

    def divide(self, x, y, name=None, inplace_a=False):
        return self._binary(OpType.EW_DIV, x, y, name)

    def max(self, x, y, name=None, inplace_a=False):
        return self._binary(OpType.EW_MAX, x, y, name)

    def min(self, x, y, name=None, inplace_a=False):
        return self._binary(OpType.EW_MIN, x, y, name)

    def _unary(self, op_type, x, name=None, **attrs):
        return self._infer_and_add(op_type, [x], attrs, name)

    def exp(self, x, name=None):
        return self._unary(OpType.EXP, x, name)

    def relu(self, x, name=None, inplace=True):
        return self._unary(OpType.RELU, x, name)

    def identity(self, x, name=None):
        return self._unary(OpType.IDENTITY, x, name)

    def sigmoid(self, x, name=None):
        return self._unary(OpType.SIGMOID, x, name)

    def tanh(self, x, name=None):
        return self._unary(OpType.TANH, x, name)

    def elu(self, x, name=None, inplace=True):
        return self._unary(OpType.ELU, x, name)

    def gelu(self, x, name=None):
        return self._unary(OpType.GELU, x, name)

    def rsqrt(self, x, name=None):
        return self._unary(OpType.RSQRT, x, name)

    def sin(self, x, name=None):
        return self._unary(OpType.SIN, x, name)

    def cos(self, x, name=None):
        return self._unary(OpType.COS, x, name)

    def pow(self, x, exponent: float, name=None):
        return self._unary(OpType.POW, x, name, scalar=exponent)

    def scalar_multiply(self, x, scalar: float, name=None, inplace=True):
        return self._unary(OpType.SCALAR_MULTIPLY, x, name, scalar=scalar)

    def scalar_add(self, x, scalar: float, name=None, inplace=True):
        return self._unary(OpType.SCALAR_ADD, x, name, scalar=scalar)

    def scalar_sub(self, x, scalar: float, name=None, inplace=True):
        return self._unary(OpType.SCALAR_SUB, x, name, scalar=scalar)

    def scalar_true_divide(self, x, scalar: float, name=None, inplace=True):
        return self._unary(OpType.SCALAR_TRUE_DIV, x, name, scalar=scalar)

    # ---- structural ----------------------------------------------------- #
    def flat(self, input: Tensor, name=None) -> Tensor:
        return self._infer_and_add(OpType.FLAT, [input], {}, name)

    def reshape(self, input: Tensor, shape: Sequence[int], name=None) -> Tensor:
        return self._infer_and_add(OpType.RESHAPE, [input], dict(shape=tuple(shape)), name)

    def transpose(self, input: Tensor, perm: Sequence[int], name=None) -> Tensor:
        return self._infer_and_add(OpType.TRANSPOSE, [input], dict(perm=tuple(perm)), name)

    def reverse(self, input: Tensor, axis: int, name=None) -> Tensor:
        return self._infer_and_add(OpType.REVERSE, [input], dict(axis=axis), name)

    def concat(self, tensors: List[Tensor], axis: int, name=None) -> Tensor:
        return self._infer_and_add(OpType.CONCAT, list(tensors), dict(axis=axis), name)

    def split(self, input: Tensor, sizes: Union[int, Sequence[int]], axis: int, name=None) -> List[Tensor]:
        if isinstance(sizes, int):
            total = input.dims[axis % len(input.dims)]
            assert total % sizes == 0
            splits = [total // sizes] * sizes
        else:
            splits = list(sizes)
        out = self._infer_and_add(OpType.SPLIT, [input], dict(axis=axis, splits=splits), name)
        return out if isinstance(out, list) else [out]

    def cast(self, input: Tensor, dtype: DataType, name=None) -> Tensor:
        return self._infer_and_add(OpType.CAST, [input], dict(dtype=dtype), name)

    def softmax(self, input: Tensor, axis: int = -1, name=None) -> Tensor:
        return self._infer_and_add(OpType.SOFTMAX, [input], dict(dim=axis), name)

    def dropout(self, input: Tensor, rate: float = 0.5, seed: int = 0, name=None) -> Tensor:
        return self._infer_and_add(OpType.DROPOUT, [input], dict(rate=rate, seed=seed), name)

    def mean(self, input: Tensor, dims: Sequence[int], keepdims: bool = False, name=None) -> Tensor:
        return self._infer_and_add(OpType.MEAN, [input], dict(axes=tuple(dims), keepdims=keepdims), name)

    def reduce_sum(self, input: Tensor, axes: Sequence[int], keepdims: bool = False, name=None) -> Tensor:
        return self._infer_and_add(OpType.REDUCE_SUM, [input], dict(axes=tuple(axes), keepdims=keepdims), name)

    # ---- embedding / gather / attention / matmul ------------------------ #
    def embedding(
        self,
        input: Tensor,
        num_entries: int,
        out_dim: int,
        aggr: AggrMode = AggrMode.NONE,
        dtype: DataType = DataType.FLOAT,
        kernel_initializer=None,
        name=None,
        strategy: Optional[Dict[str, str]] = None,
    ) -> Tensor:
        """reference: FFModel::embedding (model.h:424, src/ops/embedding.cc)."""
        attrs = dict(
            num_entries=num_entries,
            out_dim=out_dim,
            aggr=aggr,
            dtype=dtype,
            kernel_initializer=kernel_initializer,
        )
        if strategy:
            attrs["strategy"] = strategy
        return self._infer_and_add(OpType.EMBEDDING, [input], attrs, name)

    def gather(self, input: Tensor, index: Tensor, dim: int, name=None) -> Tensor:
        """reference: FFModel::gather (model.h:433, src/ops/gather.cc)."""
        return self._infer_and_add(OpType.GATHER, [input, index], dict(dim=dim), name)

    def batch_matmul(
        self,
        A: Tensor,
        B: Tensor,
        a_seq_length_dim: int = -1,
        b_seq_length_dim: int = -1,
        name=None,
    ) -> Tensor:
        """reference: FFModel::batch_matmul (model.h:481, src/ops/batch_matmul.cc)."""
        attrs = dict(a_seq_length_dim=a_seq_length_dim, b_seq_length_dim=b_seq_length_dim)
        return self._infer_and_add(OpType.BATCHMATMUL, [A, B], attrs, name)

    def multihead_attention(
        self,
        query: Tensor,
        key: Tensor,
        value: Tensor,
        embed_dim: int,
        num_heads: int,
        kdim: int = 0,
        vdim: int = 0,
        dropout: float = 0.0,
        bias: bool = True,
        add_bias_kv: bool = False,
        add_zero_attn: bool = False,
        kernel_initializer=None,
        causal: bool = False,
        name=None,
        strategy: Optional[Dict[str, str]] = None,
        qk_norm: bool = False,
        norm_eps: float = 1e-6,
        gain_initializer=None,
        num_kv_heads: Optional[int] = None,
        head_dim: Optional[int] = None,
        window: Optional[int] = None,
        rotary: Optional[float] = None,
        positions: Optional[Tensor] = None,
        gate: bool = False,
        scale: Optional[float] = None,
        v_head_dim: Optional[int] = None,
        rotary_dim: Optional[int] = None,
        sinks: bool = False,
        sink_initializer=None,
        value_scale: Optional[float] = None,
    ) -> Tensor:
        """reference: FFModel::multihead_attention (model.h:542,
        src/ops/attention.cc — cuDNN multihead attention). ``causal`` is a
        TPU-native extension (the reference has no causal masking), as is
        ``qk_norm``: an RMSNorm with a learned gain over the whole
        projected q and over the whole projected k, before the heads are
        split (``"head"``: over each head's values, one gain of
        ``head_dim`` for all heads). Further extensions, each absent by
        default: ``head_dim`` (a head's width where it is not ``embed_dim
        / num_heads``), ``window`` (a query sees the ``window`` positions
        that end at its own), ``rotary`` (theta: q and k are rotated by
        ``positions``, the graph's int32 (B, S) input, over the whole
        head), ``gate`` (the attended values times ``sigmoid(query
        W_g)`` before the output projection), ``scale`` (what the scores
        are multiplied by where it is not ``1 / sqrt(head_dim)``),
        ``v_head_dim`` (a value head's width where it is not the key
        head's), ``rotary_dim`` (the first numbers of a head that
        ``rotary`` rotates, where it is not the whole head), ``sinks`` (a
        learned scalar a query head, one more column of the softmax that
        carries no value), ``value_scale`` (what the projected values are
        multiplied by)."""
        attrs = dict(
            embed_dim=embed_dim,
            num_heads=num_heads,
            kdim=kdim or embed_dim,
            vdim=vdim or embed_dim,
            dropout=dropout,
            bias=bias,
            add_bias_kv=add_bias_kv,
            add_zero_attn=add_zero_attn,
            kernel_initializer=kernel_initializer,
            causal=causal,
        )
        if qk_norm:
            attrs.update(qk_norm="head" if qk_norm == "head" else True,
                         norm_eps=float(norm_eps),
                         gain_initializer=gain_initializer)
        if num_kv_heads and int(num_kv_heads) != int(num_heads):
            # grouped heads: query head h reads key-value head
            # h // (num_heads / num_kv_heads)
            attrs["num_kv_heads"] = int(num_kv_heads)
        if head_dim:
            attrs["head_dim"] = int(head_dim)
        if window:
            attrs["window"] = int(window)
        if gate:
            attrs["gate"] = True
        if scale:
            attrs["scale"] = float(scale)
        if v_head_dim and int(v_head_dim) != int(
                head_dim or embed_dim // num_heads):
            attrs["v_head_dim"] = int(v_head_dim)
        if sinks:
            attrs.update(sinks=True, sink_initializer=sink_initializer)
        if value_scale:
            attrs["value_scale"] = float(value_scale)
        inputs = [query, key, value]
        if rotary:
            if positions is None:
                raise ValueError("rotary positions need the positions input")
            attrs["rotary"] = float(rotary)
            if rotary_dim:
                attrs["rotary_dim"] = int(rotary_dim)
            inputs.append(positions)
        if strategy:
            attrs["strategy"] = strategy
        return self._infer_and_add(
            OpType.MULTIHEAD_ATTENTION, inputs, attrs, name
        )

    def slice_tensor(self, input: Tensor, items, name=None) -> Tensor:
        """Static strided slice / integer indexing (ops/structural.py
        Slice; torch ``x[:, 0]`` and ONNX Slice import through this)."""
        return self._infer_and_add(OpType.SLICE, [input],
                                   dict(items=list(items)), name)

    def constant(self, value, name=None) -> Tensor:
        """A baked-in constant tensor (no reference analog — used by the
        HF importer for folded buffers; ops/structural.py Constant)."""
        v = np.asarray(value)
        if np.issubdtype(v.dtype, np.integer):
            # int64 buffers (torch ids) downcast: jax runs 32-bit by default
            dt = DataType.INT32
            v = v.astype(np.int32)
        elif v.dtype == np.float64:
            dt = DataType.FLOAT
            v = v.astype(np.float32)
        elif v.dtype == np.bool_:
            dt = DataType.BOOL
        else:
            dt = DataType.FLOAT
            v = v.astype(np.float32)
        return self._infer_and_add(OpType.CONSTANT, [],
                                   dict(value=v, dtype=dt), name)

    # ---- recurrent family ------------------------------------------------ #
    def _recurrent(self, op_type, input, initial_state, attrs, name):
        inputs = [input]
        if initial_state is not None:
            states = (initial_state if isinstance(initial_state, (list, tuple))
                      else [initial_state])
            inputs.extend(states)
        out = self._infer_and_add(op_type, inputs, attrs, name)
        return out

    def lstm(
        self,
        input: Tensor,
        hidden_size: int,
        return_sequences: bool = True,
        return_state: bool = False,
        initial_state=None,
        kernel_initializer=None,
        recurrent_initializer=None,
        name=None,
    ):
        """LSTM over (batch, seq, features) (reference: the legacy NMT
        engine's LSTM, nmt/lstm.cu — here a first-class op lowered to
        lax.scan; ops/recurrent.py). ``initial_state``: (h0, c0) tensors.
        Returns the sequence (or last hidden), plus (h, c) when
        ``return_state``."""
        attrs = dict(hidden_size=hidden_size,
                     return_sequences=return_sequences,
                     return_state=return_state,
                     kernel_initializer=kernel_initializer,
                     recurrent_initializer=recurrent_initializer)
        return self._recurrent(OpType.LSTM, input, initial_state, attrs, name)

    def gru(
        self,
        input: Tensor,
        hidden_size: int,
        return_sequences: bool = True,
        return_state: bool = False,
        initial_state=None,
        kernel_initializer=None,
        recurrent_initializer=None,
        name=None,
    ):
        """GRU (torch nn.GRU gate/weight conventions; ops/recurrent.py)."""
        attrs = dict(hidden_size=hidden_size,
                     return_sequences=return_sequences,
                     return_state=return_state,
                     kernel_initializer=kernel_initializer,
                     recurrent_initializer=recurrent_initializer)
        return self._recurrent(OpType.GRU, input, initial_state, attrs, name)

    def rnn(
        self,
        input: Tensor,
        hidden_size: int,
        activation: ActiMode = ActiMode.TANH,
        return_sequences: bool = True,
        return_state: bool = False,
        initial_state=None,
        name=None,
    ):
        """Vanilla RNN (reference: nmt/rnn.h; ops/recurrent.py)."""
        attrs = dict(hidden_size=hidden_size, activation=activation,
                     return_sequences=return_sequences,
                     return_state=return_state)
        return self._recurrent(OpType.RNN, input, initial_state, attrs, name)

    # ---- MoE family ------------------------------------------------------ #
    def top_k(self, input: Tensor, k: int, sorted: bool = True, name=None) -> List[Tensor]:
        """reference: FFModel::top_k (model.h:537, src/ops/topk.cc)."""
        out = self._infer_and_add(OpType.TOPK, [input], dict(k=k, sorted=sorted), name)
        return out if isinstance(out, list) else [out]

    def group_by(self, input: Tensor, assign: Tensor, n: int, alpha: float, name=None) -> List[Tensor]:
        """reference: FFModel::group_by (model.h:438, src/ops/group_by.cc)."""
        out = self._infer_and_add(OpType.GROUP_BY, [input, assign], dict(n=n, alpha=alpha), name)
        return out if isinstance(out, list) else [out]

    def aggregate(self, inputs: List[Tensor], n: int, lambda_bal: float, name=None) -> Tensor:
        """reference: FFModel::aggregate (model.h:451, src/ops/aggregate.cc).
        inputs = [gate_preds, gate_assign, true_gate_assign, full_gate_grads,
        exp_pred_1, ..., exp_pred_n]."""
        return self._infer_and_add(OpType.AGGREGATE, list(inputs), dict(n=n, lambda_bal=lambda_bal), name)

    def aggregate_spec(self, inputs: List[Tensor], n: int, lambda_bal: float, name=None) -> Tensor:
        """reference: FFModel::aggregate_spec (model.h:459)."""
        return self._infer_and_add(OpType.AGGREGATE_SPEC, list(inputs), dict(n=n, lambda_bal=lambda_bal), name)

    def group_by_stacked(self, input: Tensor, assign: Tensor, n: int,
                         alpha: float, name=None,
                         strategy: Optional[Dict[str, str]] = None) -> Tensor:
        """GroupBy emitting one stacked (n, capacity, d) tensor whose expert
        dim is shardable over a mesh axis — the expert-parallel formulation
        (reference semantics: src/ops/group_by.cc; EP per SURVEY.md §2.3).
        ``strategy={"expert": axis}`` pins the EP axis."""
        attrs = dict(n=n, alpha=alpha)
        if strategy:
            attrs["strategy"] = strategy
        return self._infer_and_add(OpType.GROUP_BY_STACKED, [input, assign],
                                   attrs, name)

    def expert_linear(self, input: Tensor, out_dim: int,
                      activation: ActiMode = ActiMode.NONE,
                      use_bias: bool = True, kernel_initializer=None,
                      name=None) -> Tensor:
        """Per-expert dense over a stacked (n, capacity, d) tensor; the
        (n, d, out) weight shards on the expert dim (batched equivalent of
        the reference's per-expert Linear ops, moe.cc:20-45)."""
        attrs = dict(out_dim=out_dim, activation=activation, use_bias=use_bias)
        if kernel_initializer is not None:
            attrs["kernel_initializer"] = kernel_initializer
        return self._infer_and_add(OpType.EXPERT_LINEAR, [input], attrs, name)

    def aggregate_stacked(self, gate_preds: Tensor, assign: Tensor,
                          full_gate: Tensor, exp_stacked: Tensor, n: int,
                          lambda_bal: float, name=None) -> Tensor:
        """Aggregate over the stacked expert tensor (reference semantics:
        src/ops/aggregate.cc, incl. the lambda_bal balance gradient)."""
        return self._infer_and_add(
            OpType.AGGREGATE_STACKED,
            [gate_preds, assign, full_gate, exp_stacked],
            dict(n=n, lambda_bal=lambda_bal), name)

    def moe(
        self,
        input: Tensor,
        num_exp: int,
        num_select: int,
        expert_hidden_size: int,
        alpha: float = 2.0,
        lambda_bal: float = 0.04,
        stacked: bool = False,
        expert_axis: Optional[str] = None,
        name=None,
    ) -> Tensor:
        """Composite MoE layer (reference: FFModel::moe src/ops/moe.cc:20-45:
        gate = dense(input, num_exp, RELU); topk_{vals,idx} = top_k(gate, k);
        exp_i = group_by(input, idx, n, alpha); agg = aggregate(
        [softmax(vals), idx, idx, gate, softmax(dense(exp_i, hidden, RELU))…])).

        ``stacked=True`` builds the expert-parallel formulation instead:
        one group_by_stacked -> expert_linear -> aggregate_stacked chain
        whose expert dim shards over a mesh axis (``expert_axis``, or a
        compile(strategies=...) entry, or found by the search).
        Same math; the n-branch form mirrors the reference API.
        """
        if expert_axis is not None and not stacked:
            raise ValueError("expert_axis requires stacked=True (the "
                             "n-branch formulation cannot shard experts)")
        nm = name or "moe"
        gate = self.dense(input, num_exp, ActiMode.RELU, name=f"{nm}_gate")
        topk_out, topk_idx = self.top_k(gate, num_select, sorted=False)
        gate_sm = self.softmax(topk_out)
        if stacked:
            grouped = self.group_by_stacked(
                input, topk_idx, num_exp, alpha, name=f"{nm}_group",
                strategy={"expert": expert_axis} if expert_axis else None)
            h = self.expert_linear(grouped, expert_hidden_size, ActiMode.RELU,
                                   name=f"{nm}_experts")
            h = self.softmax(h)
            return self.aggregate_stacked(gate_sm, topk_idx, gate, h,
                                          num_exp, lambda_bal,
                                          name=f"{nm}_agg")
        agg_inputs = [gate_sm, topk_idx, topk_idx, gate]
        grouped = self.group_by(input, topk_idx, num_exp, alpha)
        for i, g in enumerate(grouped):
            h = self.dense(g, expert_hidden_size, ActiMode.RELU, name=f"{nm}_exp{i}")
            agg_inputs.append(self.softmax(h))
        return self.aggregate(agg_inputs, num_exp, lambda_bal, name=f"{nm}_agg")

    # ---- parallel ops (reference: src/parallel_ops — SURVEY.md §2.3) ----- #
    def repartition(self, input: Tensor, dim: int, axis: str,
                    degree: Optional[int] = None, name=None) -> Tensor:
        """reference: Repartition (src/parallel_ops/partition.cc)."""
        attrs = dict(dim=dim, axis=axis)
        if degree:
            attrs["degree"] = degree
        return self._infer_and_add(OpType.REPARTITION, [input], attrs, name)

    def combine(self, input: Tensor, dim: int, name=None) -> Tensor:
        """reference: Combine (src/parallel_ops/combine.cc)."""
        return self._infer_and_add(OpType.COMBINE, [input], dict(dim=dim), name)

    def replicate(self, input: Tensor, axis: str, name=None) -> Tensor:
        """reference: Replicate (src/parallel_ops/replicate.cc)."""
        return self._infer_and_add(OpType.REPLICATE, [input], dict(axis=axis), name)

    def reduction(self, input: Tensor, axis: str, name=None) -> Tensor:
        """reference: Reduction (src/parallel_ops/reduction.cc)."""
        return self._infer_and_add(OpType.REDUCTION, [input], dict(axis=axis), name)

    def allreduce(self, input: Tensor, name=None) -> Tensor:
        return self._infer_and_add(OpType.ALLREDUCE, [input], {}, name)

    # ---- profiling / graph exports (reference: --profiling, --taskgraph,
    # --compgraph — SURVEY.md §5 tracing/profiling) ----------------------- #
    def profile_ops(self, iters: int = 10):
        from .profiling import profile_ops

        return profile_ops(self, iters=iters)

    def export_computation_graph(self, path: str, include_costs: bool = False) -> None:
        from .profiling import export_computation_graph

        export_computation_graph(self, path, include_costs)

    def export_task_graph(self, path: str, fmt: str = "dot") -> None:
        from .profiling import export_task_graph

        export_task_graph(self, path, fmt)

    def profiler_trace(self, logdir: str):
        """Context manager: jax profiler trace (reference analog: Legion
        Prof, -lg:prof)."""
        from .profiling import trace

        return trace(logdir)

    # ---- checkpoint / resume (no reference equivalent — SURVEY.md §5
    # lists checkpointing as absent upstream; first-class here) ----------- #
    def save_checkpoint(self, path: str, step: int = 0) -> None:
        from .checkpoint import save_checkpoint

        save_checkpoint(self, path, step)

    def load_checkpoint(self, path: str, step: Optional[int] = None) -> int:
        from .checkpoint import load_checkpoint

        return load_checkpoint(self, path, step)

    # ---- strategy import/export (reference: --import-strategy /
    # --export-strategy, model.cc:3609-3618, src/runtime/strategy.cc) ------ #
    def export_strategy(self, path: str) -> None:
        import json

        strat = {}
        merged = dict(self._search_strategies)
        for layer in self.layers:
            if "strategy" in layer.attrs and layer.attrs["strategy"]:
                merged[layer.name] = layer.attrs["strategy"]
        for name, s in merged.items():
            clean = {k: v for k, v in s.items() if not k.startswith("_")}
            if clean:
                strat[name] = clean
        with open(path, "w") as f:
            json.dump({"version": 1, "strategies": strat}, f, indent=2)

    def import_strategy(self, path: str) -> Dict[str, Dict[str, str]]:
        import json

        with open(path) as f:
            data = json.load(f)
        strat = data.get("strategies", data)
        for layer in self.layers:
            if layer.name in strat:
                layer.attrs["strategy"] = dict(strat[layer.name])
        return strat

    # ------------------------------------------------------------------ #
    # compile & training verbs                                           #
    # ------------------------------------------------------------------ #
    def compile(
        self,
        optimizer: Optional[Optimizer] = None,
        loss_type: Optional[Union[LossType, str]] = None,
        metrics: Optional[Sequence[Union[MetricsType, str]]] = None,
        comp_mode: Optional[CompMode] = None,
        strategies: Optional[Dict[str, Dict[str, str]]] = None,
        mesh=None,
        pipeline=None,
        logits_tensor: Optional[Tensor] = None,
    ) -> None:
        """reference: FFModel::compile (model.cc:2803); Python surface
        flexflow_cffi.py:2022. ``pipeline`` takes a
        ``parallel.pipeline.PipelineConfig`` to train with a GPipe schedule
        over the mesh's pipe axis (no reference equivalent — PP is reserved
        but unimplemented upstream, model.h:190-192)."""
        # comp_mode defaults from the config field (reference:
        # FFConfig.computation_mode / comp_mode in config.h) — serving
        # constructs FFConfig(computation_mode=INFERENCE) and compiles
        # without the kwarg, so the field is the one source of truth;
        # an explicit kwarg still wins. The mode is a _SEARCH_KNOBS key
        # dimension: inference plans never warm-hit training plans.
        if comp_mode is None:
            comp_mode = self.config.computation_mode
        configure_tracer(self.config)  # config.trace="on" arms the recorder
        # typo'd obs mode knobs fail HERE, before any search/XLA work is
        # paid (the convention every mode knob follows)
        from ..obs.attribution import attribution_mode as _attr_mode
        from ..obs.costcorpus import corpus_mode as _corpus_mode
        from ..obs.exec_telemetry import telemetry_mode as _telemetry_mode
        from ..obs.ledger import ledger_mode as _ledger_mode
        from ..obs.server import configure_obs_server as _cfg_obs_server

        _ledger_mode(self.config)
        _telemetry_mode(self.config)
        _attr_mode(self.config)
        _corpus_mode(self.config)
        # a malformed fault plan fails here too — before any search/XLA
        # work — and arming it at compile covers serving-only flows
        from .faults import configure_faults as _cfg_faults

        _cfg_faults(self.config)
        # config.obs_server_port arms the scrape/health surface (ratchet-
        # on, like the tracer; a bad port value raises here)
        _cfg_obs_server(self.config)
        _t0_compile = time.perf_counter()
        # the whole of compile, entered and left by hand as fit.step is
        # (the body is long and an exception ends the compile)
        _compile_span = span("compile", cat="compile")
        _compile_span.__enter__()
        if optimizer is not None:
            self.optimizer = optimizer
        elif self.optimizer is None:
            # default optimizer from config flags (reference: --lr/--wd
            # consumed by the examples' optimizer construction)
            self.optimizer = SGDOptimizer(
                lr=self.config.learning_rate,
                weight_decay=self.config.weight_decay,
            )
        if isinstance(loss_type, str):
            loss_type = loss_from_string(loss_type)
        mtypes: List[MetricsType] = []
        for m in metrics or []:
            mtypes.append(_METRICS_FROM_STRING[m] if isinstance(m, str) else m)
        # explicit output override for multi-leaf graphs (an imported
        # module whose recurrent state is also a graph leaf, a BERT whose
        # pooler is not the tensor to train on); default: the last leaf
        logits = logits_tensor if logits_tensor is not None \
            else self._final_output()
        # drop any cache-hit pre-validation from a PREVIOUS compile (the
        # gate below only reuses a report produced this compile)
        self._pcg_prevalidated = None
        # collect per-layer strategy attrs (the ParallelConfig-override path)
        self._search_layers = None  # set by _run_search when a rewrite wins
        strat = dict(strategies or {})
        for layer in self.layers:
            if "strategy" in layer.attrs and layer.name not in strat:
                strat[layer.name] = layer.attrs["strategy"]
        # only_data_parallel drops all overrides (reference: model.cc:2638)
        if self.config.only_data_parallel:
            strat = {}
        elif self.config.import_strategy_file:
            # imported strategy replaces the search entirely (reference:
            # --import-strategy, model.cc:3609)
            strat.update(self.import_strategy(self.config.import_strategy_file))
        elif self.config.search_budget != 0 and not strat:
            # auto-parallelization search (reference: the GRAPH_OPTIMIZE_TASK
            # launched inside compile, model.cc:2824-2831). Unity DP by
            # default; config.search_method="mcmc" selects the MLSys'19
            # annealing fallback bounded by search_budget/search_alpha.
            # Explicit per-layer strategies (builder overrides) win over
            # search. Results are kept off layer.attrs so a re-compile
            # after a config change re-runs the search.
            strat, mesh = self._run_search(mesh, logits)
        # record the strategies actually in effect (search-found, imported,
        # or compile(strategies=...)-supplied) so export_strategy sees them
        self._search_strategies = dict(strat)
        # the search may have chosen a structurally-rewritten graph
        # (search/graph_xfer.py); its boundary tensors — including the
        # logits — are the original Tensor objects, so everything
        # downstream (loss attachment, metrics) is unchanged
        compile_layers = self._search_layers or self.layers
        # --- PCG validation gate (analysis/pcg_check.py): the
        # post-search, post-rewrite graph plus the strategies actually
        # in effect, checked statically before any param init / XLA
        # trace. Runs BEFORE fusion — strategy entries name these
        # layers; the fused graph is derived mechanically and its
        # residual failure modes surface through build_ops' provenance-
        # carrying errors. Findings carry PCG0xx codes and layer
        # provenance (incl. the originating rewrite rule);
        # config.validate_pcg picks raise/print/skip.
        self.pcg_report = None
        vmode = self._validate_mode()
        if vmode != "off":
            from ..core.machine import DATA_AXIS, mesh_axis_sizes as _mas

            if mesh is not None:
                vaxes = _mas(mesh)
            elif self.config.mesh_shape:
                vaxes = dict(self.config.mesh_shape)
            else:
                vaxes = {DATA_AXIS: len(jax.devices())}
            # a cache hit already validated this exact strategies object
            # against these layers/mesh in _validate_cached (and applied
            # the mode policy there) — reuse its report instead of
            # paying a second identical propagation walk
            pre = getattr(self, "_pcg_prevalidated", None)
            if pre is not None and pre[0] == id(strat):
                self.pcg_report = pre[1]
            else:
                from ..analysis import validate_pcg as _validate_pcg

                src = ("rewrite" if self._search_layers is not None
                       else "builder")
                with span("compile.validate_pcg", cat="compile", source=src):
                    self.pcg_report = _validate_pcg(
                        compile_layers, self._used_inputs(), strat, vaxes,
                        protected=frozenset({logits.tensor_id}),
                        config=self.config, source=src)
                self.pcg_report.handle(vmode)
        self._pcg_prevalidated = None
        if self.config.perform_fusion:
            # reference: the --fusion pass packing adjacent ops
            # (model.cc:2964-3061); here it shrinks the graph the search
            # and simulator see — XLA fuses the HLO either way
            from ..ops.fused import apply_fusion

            compile_layers = apply_fusion(compile_layers, {logits.tensor_id})
        if pipeline is None and mesh is not None:
            # the search may have chosen a pipe-prefixed mesh; honor it by
            # auto-enabling the GPipe engine (stage count = pipe degree).
            # Guard against fusion shrinking the graph below the stage
            # count — then pipelining is impossible and we compile plain
            # (the pipe axis stays unused/replicated rather than crashing).
            from ..core.machine import mesh_axis_sizes as _mas

            pipe_deg = _mas(mesh).get("pipe", 1)
            if pipe_deg > 1 and len(compile_layers) >= pipe_deg:
                from ..parallel.pipeline import PipelineConfig
                from ..search.unity import pipe_microbatches

                pipeline = PipelineConfig(
                    num_stages=pipe_deg,
                    num_microbatches=pipe_microbatches(self.config.batch_size),
                    schedule=self.config.pipeline_schedule,
                    interleave=(
                        max(2, int(self.config.pipeline_interleave))
                        if self.config.pipeline_schedule == "interleaved"
                        else 1),
                    remat=self.config.pipeline_remat)
            elif (pipe_deg > 1 and self.pcg_report is not None
                  and "PCG011" not in self.pcg_report.codes()):
                # the gate ran pre-fusion (strategy names live there);
                # fusion shrinking the graph below the stage count is
                # only knowable HERE — report the silent un-pipe the
                # fallback below performs (PCG011, warning; skipped when
                # the pre-fusion walk already flagged the same bound)
                f = self.pcg_report.add(
                    "PCG011",
                    f"mesh pipe axis has degree {pipe_deg} but the "
                    f"post-fusion graph has only {len(compile_layers)} "
                    f"ops; compiling un-piped — the pipe axis stays "
                    f"idle", severity="warning")
                if vmode == "warn":
                    print(f"[pcg] {f.format()}", flush=True)
        _t0_lower = time.perf_counter()
        with span("compile.lower", cat="compile",
                  n_layers=len(compile_layers)):
            try:
                self.compiled = compile_model(
                    self.config,
                    compile_layers,
                    self._used_inputs(),
                    logits,
                    self.optimizer,
                    loss_type,
                    mtypes,
                    strategies=strat,
                    mesh=mesh,
                    comp_mode=comp_mode,
                )
            except Exception:
                # gate ordering: under validate_pcg="warn" an error-
                # severity finding proceeds by contract, but when
                # tracing/lowering then dies the user must see the CODED
                # finding that predicted it next to the raw JAX error.
                # The original exception type is preserved (the failure
                # may be unrelated — OOM, a user-callback bug — and
                # callers catch specific types); the coded findings are
                # printed as context instead of rewriting the exception.
                if self.pcg_report is not None and self.pcg_report.errors:
                    print(
                        f"[pcg] compile failed after validate_pcg='warn' "
                        f"proceeded past {len(self.pcg_report.errors)} "
                        f"error-severity finding(s) — likely the cause:",
                        file=sys.stderr, flush=True)
                    for f in self.pcg_report.errors:
                        print(f"[pcg] {f.format()}", file=sys.stderr,
                              flush=True)
                raise
        metrics_registry().counter("setup.lower_s").inc(
            time.perf_counter() - _t0_lower)
        self.pipelined = None
        if pipeline is not None:
            from ..parallel.pipeline import make_pipelined_model
            from .loss import compute_loss
            from .metrics import compute_batch_metrics

            cm = self.compiled
            pipeline = self._resolve_pipeline(pipeline, cm)
            lt, fl = cm.loss_type, cm.from_logits
            with span("compile.pipeline", cat="compile",
                      schedule=pipeline.schedule,
                      stages=pipeline.num_stages,
                      microbatches=pipeline.num_microbatches):
                self.pipelined = make_pipelined_model(
                    cm.ops, cm.mesh, pipeline, self.optimizer,
                    loss_fn=lambda lg, y: compute_loss(lt, lg, y, fl),
                    metrics_fn=(lambda lg, y: compute_batch_metrics(
                        cm.metrics, lt, lg, y, fl)) if mtypes else None,
                    input_ids=[t.tensor_id for t in self._used_inputs()],
                    logits_id=logits.tensor_id,
                    params=cm.params,
                    wd_mask=cm.wd_mask,
                    opt_state=cm.opt_state,
                    compute_dtype=self.config.compute_dtype,
                    audit_config=self.config,
                )
        # --- program-audit gate (analysis/program_audit.py): what we
        # actually hand to XLA — the jaxprs of the jitted step
        # executables — statically checked for donation coverage, baked
        # constants, host callbacks, accumulator precision, collective
        # legality and retrace risk, with AUD0xx-coded findings. Runs on
        # EVERY compile, including cache-rehydrated strategies (the same
        # trust boundary _validate_cached enforces pre-lowering). The
        # pipeline/serving engines audit their own programs at build
        # time with the same config.
        self.audit_report = None
        self.audit_profile = None
        amode = self._audit_mode()
        # with a pipeline engine active, fit() dispatches the engine's
        # own (already audited) schedule programs and cm.train_step
        # never runs — tracing/compiling it here (audit OR telemetry)
        # would be cost no first dispatch ever amortizes
        _skip = ("train_step",) if self.pipelined is not None else ()
        if amode != "off" and self.compiled is not None:
            from ..analysis.program_audit import audit_compiled_model

            _t0_audit = time.perf_counter()
            asrc = ("cache" if (self.search_profile or {}).get("cache")
                    == "hit" else "builder")
            with span("compile.audit", cat="compile", source=asrc):
                self.audit_report = audit_compiled_model(
                    self.compiled, config=self.config, source=asrc,
                    skip=_skip)
            _dt_audit = time.perf_counter() - _t0_audit
            _progs = dict(getattr(self.audit_report, "programs", {}) or {})
            self.audit_profile = {
                "wall_time_s": _dt_audit,
                # the gate's own marginal cost: the AOT traces (trace_s)
                # are shared with the first dispatch via jit's trace
                # cache, so only the jaxpr walk is true overhead
                "walk_s": sum(p.get("walk_s", 0.0)
                              for p in _progs.values()),
                "trace_s": sum(p.get("trace_s", 0.0)
                               for p in _progs.values()),
                "programs": _progs,
            }
            reg = metrics_registry()
            reg.counter("audit.programs").inc(
                len(self.audit_profile["programs"]))
            reg.counter("audit.errors").inc(
                len(self.audit_report.errors))
            reg.counter("audit.warnings").inc(
                len(self.audit_report.warnings))
            reg.counter("setup.audit_s").inc(_dt_audit)
            self.audit_report.handle(amode)
        # --- executable telemetry (obs/exec_telemetry.py): what XLA
        # itself reports about each compiled step program — flops, bytes
        # accessed, peak memory — reconciled against the audit's static
        # peak-live estimate (OBS002 warn past exec_mem_threshold).
        # Opt-in: the AOT compile the analyses need is not shared with
        # the dispatch cache.
        self.exec_telemetry = None
        from ..obs.exec_telemetry import telemetry_mode as _tel_mode

        if _tel_mode(self.config) == "on" and self.compiled is not None:
            from ..obs.exec_telemetry import collect_compiled_model

            _static = {
                name: (p or {}).get("peak_live_bytes")
                for name, p in ((self.audit_profile or {}).get(
                    "programs") or {}).items()}
            with span("compile.exec_telemetry", cat="compile"):
                self.exec_telemetry = collect_compiled_model(
                    self.compiled, config=self.config, skip=_skip,
                    static_peaks=_static,
                    allow=getattr(self.config, "exec_mem_allow", None))
            self.compiled.exec_telemetry = self.exec_telemetry
        # graph exports requested via flags (reference: --compgraph /
        # --taskgraph dumps written right after compile, model.cc:3666-3674)
        if self.config.export_strategy_computation_graph_file:
            self.export_computation_graph(
                self.config.export_strategy_computation_graph_file,
                include_costs=self.config.include_costs_dot_graph,
            )
        if self.config.export_strategy_task_graph_file:
            self.export_task_graph(self.config.export_strategy_task_graph_file)
        self._index_params()
        # context for the execution playoff (fit-time searched-vs-DP race)
        self._compile_ctx = dict(loss_type=loss_type, mtypes=mtypes,
                                 comp_mode=comp_mode, logits=logits)
        self._playoff_done = False
        # set by _maybe_playoff when a race actually ran: the measured
        # decision plus the contention probe — tests assert on this so a
        # silent-skip regression (the except-all guard) fails loudly
        self._playoff_record = None
        _dt_compile = time.perf_counter() - _t0_compile
        _compile_span.set(n_ops=len(self.compiled.ops),
                          pipelined=self.pipelined is not None)
        _compile_span.__exit__(None, None, None)
        metrics_registry().counter("setup.model_compile_s").inc(_dt_compile)
        # durable telemetry: one ledger record per compile — machine
        # fingerprint, knobs, search/cache outcome, audit summary, exec
        # telemetry (obs/ledger.py; config.ledger="off" disables)
        from ..obs.ledger import record_compile

        record_compile(self, _dt_compile)

    def _resolve_pipeline(self, pipeline, cm):
        """Finalize a PipelineConfig against the compiled model:

        * ``config.grad_accum_steps`` folds into the microbatch count
          (pipelined microbatching IS gradient accumulation — K extra
          accumulation steps == K x the microbatches, same averaging,
          same activation budget);
        * ``schedule="auto"`` resolves through the simulator's schedule
          cost model — the search's choice when a search ran on this
          pipe mesh, else an analytical ranking over the compiled ops
          (sim/simulator.py rank_pipeline_schedules). The per-candidate
          pricing records land in ``self._pipe_schedule_records``.
        """
        import dataclasses as _dc

        cfg = self.config
        accum = max(1, int(getattr(cfg, "grad_accum_steps", 1)))
        if accum > 1 and not pipeline.accum_folded:
            pipeline = _dc.replace(
                pipeline,
                num_microbatches=pipeline.num_microbatches * accum,
                accum_folded=True)
        self._pipe_schedule_records = []
        if pipeline.schedule != "auto":
            return pipeline
        sr = self.search_result
        if (sr is not None and getattr(sr, "pipe_schedule", None)
                and sr.mesh_shape.get("pipe") == pipeline.num_stages):
            self._pipe_schedule_records = list(
                getattr(sr, "pipe_schedule_records", []))
            return _dc.replace(pipeline, schedule=sr.pipe_schedule,
                               interleave=sr.pipe_interleave)
        from ..core.machine import mesh_axis_sizes as _mas
        from ..search.unity import _stage_cut_bytes
        from ..sim import (OpCostModel, detect_machine_model,
                           load_machine_model)
        from ..parallel.pipeline_compiled import dp_unsupported_reason
        from ..sim.simulator import (compiled_envelope_ok,
                                     pipeline_schedule_candidates,
                                     rank_pipeline_schedules)

        machine = (load_machine_model(cfg.machine_model_file)
                   if cfg.machine_model_file
                   else detect_machine_model(cm.mesh.devices.size))
        cost = OpCostModel(machine)
        t_sub = sum(cost.measure(op).total_time for op in cm.ops)
        sizes = _mas(cm.mesh)
        n_ops = len(cm.ops)
        layers = [op.layer for op in cm.ops]
        cands = pipeline_schedule_candidates(
            "auto", getattr(cfg, "pipeline_interleave", 2),
            pipeline.num_stages, n_ops)

        def cut_fn(nc: int) -> float:
            return (float("inf") if nc > n_ops
                    else _stage_cut_bytes(layers, nc))

        # the compiled envelope verdict for THIS mesh AND graph: the
        # pipe/pipe×data mesh families, minus batch-coupled graphs
        # under a data submesh — so auto ranks with the dispatch
        # overhead the engine selection will actually deliver
        compiled_ok = (
            compiled_envelope_ok(sizes, pipeline.axis)
            and dp_unsupported_reason(
                cm.ops, sizes.get("data", 1)) is None)
        kind, v, recs = rank_pipeline_schedules(
            cands, pipeline.num_stages, pipeline.num_microbatches,
            t_sub, machine, cut_bytes_fn=cut_fn,
            data_degree=sizes.get("data", 1),
            compiled_ok=compiled_ok,
            bwd_ratio=OpCostModel.BWD_FACTOR)
        self._pipe_schedule_records = recs
        if cfg.profiling:
            ranking = ", ".join(
                "%s=%.3fms" % (r["schedule"], r["est_step_time"] * 1e3)
                for r in recs)
            print(f"[pipeline] auto schedule -> {kind}"
                  + (f" x{v}" if v > 1 else "") + f" ({ranking})",
                  flush=True)
        return _dc.replace(pipeline, schedule=kind, interleave=v)

    def _index_params(self) -> None:
        """Parameter index for get/set weights (recompile-safe: drop stale
        Parameter handles from a previous compile)."""
        self._param_index.clear()
        for op in self.compiled.ops:
            op.layer.weights.clear()
            for ws in op.weight_specs():
                p = Parameter(
                    op.weight_shapes[ws.name].sizes,
                    ws.dtype,
                    owner_layer=op.layer,
                    name=f"{op.name}/{ws.name}",
                )
                op.layer.weights.append(p)
                self._param_index[p.tensor_id] = (op.name, ws.name)

    def _run_search(self, mesh, logits=None):
        """Run the auto-parallelization search (reference: §2.5 — Unity DP
        by default via ``graph_optimize``; ``config.search_method="mcmc"``
        selects the MLSys'19 annealing path bounded by
        ``search_budget``/``search_alpha``). Returns (strategies, mesh).
        ``logits``: the training-output tensor — structural rewrites must
        not eliminate it."""
        from ..search.mcmc import mcmc_optimize
        from ..search.unity import (_memory_budget,
                                    data_parallel_input_pshapes, full_search,
                                    graph_optimize)
        from ..sim import (OpCostModel, Simulator, detect_machine_model,
                           load_machine_model)
        from ..core.machine import mesh_axis_sizes

        cfg = self.config
        # extra substitution rules, scoped to THIS config so they never
        # leak into other models' searches (reference:
        # --substitution-json-path, substitution_loader.cc:78). Two schemas
        # are accepted: the REFERENCE's GraphXfer rule collection
        # ({"rule": [...]}, substitution_loader.h:168 — translated to
        # structural rewrites) and this framework's strategy-template
        # format ({"rules": {...}}).
        cfg._substitution_rules = None  # drop stale rules on recompile
        cfg._graphxfer_rewrites = None
        if cfg.substitution_json_path:
            import json as _json

            with open(cfg.substitution_json_path) as f:
                peek = _json.load(f)
            if "rule" in peek:
                from ..search.graph_xfer import load_graphxfer_rules
                from ..search.rule_interpreter import interpret_rules

                coll = load_graphxfer_rules(peek)  # already parsed
                cfg._graphxfer_rewrites, xfer_report = interpret_rules(coll)
                if cfg.profiling:
                    print(f"[search] graphxfer rules: {xfer_report} -> "
                          f"{len(cfg._graphxfer_rewrites)} rewrites",
                          flush=True)
            else:
                from ..search.substitution import load_substitution_rules

                cfg._substitution_rules = load_substitution_rules(
                    cfg.substitution_json_path)

        def make_machine(n=None):
            # --machine-model-file overrides platform detection (reference:
            # model.cc:3678-3685 EnhancedMachineModel selection)
            if cfg.machine_model_file:
                return load_machine_model(cfg.machine_model_file)
            return detect_machine_model(n)

        inputs = self._used_inputs()
        use_mcmc = getattr(cfg, "search_method", "unity") == "mcmc"
        beam = max(cfg.base_optimize_threshold, 8)
        protected = frozenset(
            {logits.tensor_id} if logits is not None
            else {self._final_output().tensor_id})
        # pipe-stage bound: the POST-fusion graph must still have one op
        # per stage, else compile() cannot honor a pipe mesh
        n_effective = len(self.layers)
        if cfg.perform_fusion:
            from ..ops.fused import apply_fusion

            n_effective = len(apply_fusion(self.layers, set(protected)))
        t_search = time.perf_counter()
        pinned = mesh is not None or bool(cfg.mesh_shape)
        if pinned and mesh is None:
            mesh = make_mesh(cfg.mesh_shape)
        machine = make_machine(mesh.devices.size if pinned else None)
        # persistent strategy cache (reference: --import-strategy
        # model.cc:3609 made automatic): consulted BEFORE any search —
        # a hit reconstructs the stored result and compiles with zero
        # cost-model/simulator work
        cache_mode = getattr(cfg, "search_cache", "off") or "off"
        if cache_mode not in ("on", "off", "refresh"):
            # a typo ('onn', 'true', 'ON') must not silently disable the
            # cache and re-pay every search
            raise ValueError(
                f"search_cache={cache_mode!r}: expected 'on', 'off' or "
                "'refresh'")
        cache_key = None
        self._strategy_cache_key = None  # search_profile["cache_key"]
        cache_dir = getattr(cfg, "search_cache_dir", ".ffcache/strategies")
        if cache_mode in ("on", "refresh") and not use_mcmc:
            from ..search.cache import (cache_path, load_payload,
                                        result_from_payload,
                                        strategy_cache_key)

            cache_key = strategy_cache_key(
                self.layers, inputs, machine, cfg,
                mesh_axes=mesh_axis_sizes(mesh) if pinned else None,
                protected=protected)
            # the multihost checkpoint manifest records this key so an
            # unchanged-topology resume provably warm-hits the same entry
            self._strategy_cache_key = cache_key
            if cache_mode == "on":
                payload = load_payload(cache_dir, cache_key)
                if payload is not None:
                    result = result_from_payload(payload, self.layers, cfg,
                                                 protected)
                    # trust boundary: a rehydrated payload is validated
                    # BEFORE any compile work — a corrupted entry raises
                    # a PCG0xx-coded error (validate_pcg="error") or
                    # demotes to a miss ("warn"), never compiles
                    if result is not None and not self._validate_cached(
                            result, inputs, protected,
                            cache_path(cache_dir, cache_key)):
                        result = None
                    if result is not None:
                        if not pinned:
                            self.config.mesh_shape = result.mesh_shape
                            mesh = make_mesh(result.mesh_shape)
                        return self._finish_search(result, mesh, t_search,
                                                   "hit")
        if pinned:
            # mesh pinned by the user: search strategies on it only. A
            # pipe axis (user-pinned or persisted from a previous search)
            # is handled like full_search does: the inner DP runs on the
            # per-stage submesh with the HBM cap scaled by the stage count,
            # and the GPipe bubble model adjusts the result.
            from ..search.unity import _pipe_adjusted

            full_axis_sizes = mesh_axis_sizes(mesh)
            pipe = full_axis_sizes.get("pipe", 1)
            axis_sizes = {a: s for a, s in full_axis_sizes.items()
                          if a != "pipe"}
            cap = machine.chip.hbm_capacity * pipe
            input_pshapes = data_parallel_input_pshapes(
                inputs, axis_sizes, cfg.enable_sample_parallel)
            if use_mcmc:
                sim = Simulator(
                    machine, OpCostModel(machine),
                    overlap_grad_sync=cfg.search_overlap_backward_update)
                result = mcmc_optimize(
                    self.layers, input_pshapes, axis_sizes, sim, cfg,
                    seed=cfg.seed,
                )
                if pipe > 1:
                    result = _pipe_adjusted(result, self.layers, pipe,
                                            machine, cfg.batch_size,
                                            fused=cfg.perform_fusion,
                                            config=cfg)
            else:
                # structural variants compete on the pinned mesh too —
                # each evaluated by the SAME candidate body full_search
                # uses (unity._evaluate_candidate: memory-aware budget,
                # ZeRO optimizer-state sharding, GPipe adjustment)
                from ..search.graph_xfer import graph_variants
                from ..search.unity import (_effective_layer_count,
                                            _evaluate_candidate)

                result = None
                errs: list = []
                n_cand = 0
                shared_cm = OpCostModel(machine)
                for rewrites, vlayers in graph_variants(
                        self.layers, cfg,
                        rewrites=getattr(cfg, "_graphxfer_rewrites", None),
                        protected=protected):
                    # a variant too small for the mesh's pipe degree would
                    # silently un-pipe in compile(); skip it — UNLESS the
                    # original graph can't pipe either (then compile's
                    # plain-compile fallback is the intended behavior and
                    # the search must not dead-end)
                    n_var = _effective_layer_count(
                        vlayers, cfg.perform_fusion, protected)
                    if pipe > 1 and n_var < pipe and n_effective >= pipe:
                        continue
                    n_cand += 1
                    r = _evaluate_candidate(
                        vlayers, full_axis_sizes, inputs, machine, cfg,
                        beam, shared_cm, _memory_budget(cfg, machine),
                        err_sink=errs, strict_budget=False)
                    if r is None:
                        continue
                    if rewrites:
                        r.rewrites, r.layers = list(rewrites), vlayers
                    if result is None or r.est_step_time < result.est_step_time:
                        result = r
                if result is None:
                    raise RuntimeError(
                        "no feasible strategy on the pinned mesh"
                    ) from (errs[0] if errs else None)
                # adoption margin on the pinned mesh too: sharding over
                # the pinned axes must beat leaving them idle (pure DP)
                # by more than the cost model's error bar
                from ..search.unity import (_is_sharded_result,
                                            adoption_margin, graph_optimize)

                if _is_sharded_result(result):
                    # the DP fallback must be priced under the SAME
                    # accounting the candidates just used: reuse the
                    # loop's memoized cost model, and with ZeRO the
                    # optimizer state is sharded over the data axis for
                    # DP exactly as it was for every candidate
                    dp_mult = (2.0 / axis_sizes.get("data", 1)
                               if cfg.zero_optimizer else 2.0)
                    dp_sim = Simulator(
                        machine, shared_cm,
                        overlap_grad_sync=cfg.search_overlap_backward_update,
                        optimizer_state_mult=dp_mult)
                    try:
                        dp_r = graph_optimize(
                            self.layers, input_pshapes, axis_sizes, dp_sim,
                            cfg, beam, memory_cap=cap, dp_only=True)
                        # the memory-aware search's budget binds the DP
                        # fallback too: never demote to a plan that
                        # replicates weights past the user's threshold.
                        # Checked on the PRE-pipe-adjusted (whole-model)
                        # footprint against budget*pipe, the same
                        # convention memory_aware_search uses above.
                        if (cfg.perform_memory_search and dp_r.est_memory
                                > _memory_budget(cfg, machine) * pipe):
                            dp_r = None
                        elif pipe > 1:
                            dp_r = _pipe_adjusted(dp_r, self.layers, pipe,
                                                  machine, cfg.batch_size,
                                                  fused=cfg.perform_fusion,
                                                  config=cfg)
                    except RuntimeError:
                        dp_r = None
                    if (dp_r is not None and result.est_step_time
                            * adoption_margin(cfg, machine)
                            > dp_r.est_step_time):
                        result = dp_r
                result.candidates = n_cand
                result.workers = 1  # the pinned variant loop is serial
        else:
            result = full_search(
                self.layers, inputs, machine, cfg, beam_width=beam,
                max_pipe=max(1, n_effective // 2), protected=protected,
            )
            self.config.mesh_shape = result.mesh_shape
            mesh = make_mesh(result.mesh_shape)
        if cache_key is not None:
            from ..search.cache import store_result, strategy_cache_key

            # self.layers rides along so the stored strategy keys (which
            # may embed process-local auto names) can remap positionally
            # when another process rehydrates them
            store_result(cache_dir, cache_key, result, layers=self.layers)
            if not pinned:
                # the first compile pins config.mesh_shape to the searched
                # mesh, so a recompile keys the cache with the mesh PINNED
                # — store under that key too so the warm path still hits
                key2 = strategy_cache_key(self.layers, inputs, machine, cfg,
                                          mesh_axes=result.mesh_shape,
                                          protected=protected)
                if key2 != cache_key:
                    store_result(cache_dir, key2, result,
                                 layers=self.layers)
        # cache_key None = the cache never engaged (off, or mcmc bypass):
        # the label must say so even when cache_mode asked for "refresh"
        return self._finish_search(
            result, mesh, t_search,
            "off" if cache_key is None else
            ("refresh" if cache_mode == "refresh" else "miss"))

    def _validate_mode(self) -> str:
        """The config.validate_pcg gate mode, with the same typo guard
        the cache mode gets (a misspelled mode must not silently turn
        the correctness gate off)."""
        mode = getattr(self.config, "validate_pcg", "error") or "off"
        if mode not in ("error", "warn", "off"):
            raise ValueError(
                f"validate_pcg={mode!r}: expected 'error', 'warn' or "
                "'off'")
        return mode

    def _audit_mode(self) -> str:
        """The config.audit_programs gate mode, with the same typo guard
        the other gates get."""
        mode = getattr(self.config, "audit_programs", "error") or "off"
        if mode not in ("error", "warn", "off"):
            raise ValueError(
                f"audit_programs={mode!r}: expected 'error', 'warn' or "
                "'off'")
        return mode

    def _validate_cached(self, result, inputs, protected,
                         entry_path: str) -> bool:
        """PCG-validate a strategy rehydrated from the persistent cache
        (the variant graph when the stored rewrites re-applied, else the
        builder graph). Returns False to demote the hit to a miss; in
        "error" mode a corrupt entry raises the coded error instead —
        the user asked for a hard gate and silently re-searching would
        hide the corruption."""
        mode = self._validate_mode()
        if mode == "off":
            return True
        from ..analysis import validate_pcg

        vlayers = result.layers or self.layers
        report = validate_pcg(
            vlayers, inputs, result.strategies, result.mesh_shape,
            protected=protected, config=self.config,
            source=f"cache:{entry_path}")
        # "error" mode raises the coded error on any error finding;
        # "warn" mode prints EVERY finding (warnings included — the
        # documented contract), then errors demote the hit to a miss
        report.handle(mode)
        if report.errors:
            print(f"[search] cached strategy {entry_path} failed PCG "
                  f"validation ({report.errors[0].code}); treating as a "
                  f"miss", flush=True)
            return False
        # compile()'s gate reuses this report for the SAME strategies
        # object instead of re-walking the identical triple
        self._pcg_prevalidated = (id(result.strategies), report)
        return True

    def _finish_search(self, result, mesh, t_start, cache_label: str):
        """Shared tail of _run_search for searched AND cache-hit results:
        records the result + the search profile (timing / coverage /
        cache-hit counters surfaced by runtime/profiling.py), honors the
        profiling print and --export-strategy, and hands compile() the
        (strategies, mesh) pair."""
        self.search_result = result
        # a structural rewrite won: compile() builds the rewritten graph
        self._search_layers = getattr(result, "layers", None)
        self.search_profile = {
            "search_time_s": time.perf_counter() - t_start,
            "cache": cache_label,
            "cache_key": getattr(self, "_strategy_cache_key", None),
            "candidates": getattr(result, "candidates", 0),
            "pruned": getattr(result, "pruned", 0),
            "states_explored": result.states_explored,
            # what the evaluation ACTUALLY used (1 = serial incl. pool
            # fallback; 0 = no evaluation ran, e.g. a cache hit) — the
            # config knob alone can't distinguish these
            "workers": getattr(result, "workers", 0),
            "mesh_shape": dict(result.mesh_shape),
            "est_step_time": result.est_step_time,
        }
        # flight recorder: the search phase as one span + the cache
        # outcome as a counter series (hit/miss/refresh/off)
        tracer().complete(
            "compile.search", t_start,
            self.search_profile["search_time_s"], cat="compile",
            args={"cache": cache_label,
                  "candidates": self.search_profile["candidates"],
                  "pruned": self.search_profile["pruned"],
                  "mesh": dict(result.mesh_shape),
                  "est_step_time": result.est_step_time})
        metrics_registry().counter(f"search.cache.{cache_label}").inc()
        metrics_registry().gauge("search.est_step_time_s").set(
            result.est_step_time)
        if self.config.profiling:
            rw = getattr(result, "rewrites", None)
            p = self.search_profile
            print(
                f"[search] mesh={result.mesh_shape} est_step={result.est_step_time*1e3:.3f}ms "
                f"mem={result.est_memory/2**20:.1f}MiB states={result.states_explored}"
                f" cand={p['candidates']} pruned={p['pruned']}"
                f" cache={cache_label} t={p['search_time_s']:.3f}s"
                + (f" rewrites={rw}" if rw else ""),
                flush=True,
            )
        if self.config.export_strategy_file:
            self._search_strategies = dict(result.strategies)
            self.export_strategy(self.config.export_strategy_file)
        return result.strategies, mesh

    # ---- execution playoff (reference: the search grounds its rankings in
    # measured kernel costs, Op::inner_measure_operator_cost model.cu:17-53;
    # here: race the searched compile against a plain data-parallel compile
    # for a few REAL steps on the first fit batch and keep the winner) ----- #
    def _time_compiled(self, cm, pipelined, xs, y_arr, bs, steps) -> float:
        """Time ``steps`` real train steps WITHOUT perturbing training
        state: the functional path runs on copies (the jitted step donates
        its param/opt-state buffers, so originals must not be passed);
        the pipelined path mutates its stage state and is restored from
        the paired CompiledModel afterwards."""
        import time as _time

        xs_np = [np.asarray(a) for a in xs]
        y_np = np.asarray(y_arr)
        if cm.loss_type is LossType.SPARSE_CATEGORICAL_CROSSENTROPY:
            y_np = y_np.reshape(y_np.shape[0], -1).astype(np.int32)
        n_batches = max(1, len(y_np) // bs)
        p = s = None
        if pipelined is None:
            p = jax.tree.map(lambda a: a.copy(), cm.params)
            s = jax.tree.map(lambda a: a.copy(), cm.opt_state)

        def one(i):
            nonlocal p, s
            # mirror the fit loop per step: a DIFFERENT batch each time
            # (cache-streaming behavior, not one hot batch replayed) and
            # host->device placement inside the timed region — both
            # differ materially between strategies (batch-sharded inputs
            # move 1/n per device, replicated inputs move n full copies)
            lo = (i % n_batches) * bs
            batch = [jax.device_put(a[lo:lo + bs], sh)
                     for a, sh in zip(xs_np, cm.input_shardings)]
            label = jax.device_put(y_np[lo:lo + bs], cm.label_sharding)
            rng = jax.random.fold_in(
                jax.random.key(self.config.seed), 1 << 20 | i)
            if pipelined is not None:
                out = pipelined.train_step(rng, batch, label)
            else:
                p, s, out, _ = cm.train_step(
                    p, s, rng, *batch, label,
                    seq_length=self.iter_config.seq_length)
            jax.block_until_ready(out)

        # warmup TWICE: the first call compiles, and the SECOND can
        # recompile (step outputs carry shardings/layouts that differ
        # from the freshly-placed initial state — measured ~3s on dlrm);
        # only the third call on is steady-state
        one(0)
        one(1)
        t0 = _time.perf_counter()
        for i in range(steps):
            one(i + 2)
        elapsed = (_time.perf_counter() - t0) / steps
        if pipelined is not None:
            # undo the timing steps: cm still holds the pre-playoff state
            pipelined.sync_from(cm)
        return elapsed

    @staticmethod
    def _dispatch_probe(n: int = 20) -> dict:
        """Contention guard for the playoff: time a trivial jitted
        dispatch ``n`` times. On an idle host median ≈ floor; a loaded
        host (e.g. a concurrent test run on a one-core machine) inflates
        the median well past the floor, which means the searched-vs-DP
        race about to run would record a contention artifact rather than
        a strategy difference. The raw numbers go into the playoff record
        so an AE artifact row can be judged post hoc (reference analogue:
        Op::inner_measure_operator_cost assumes an owned device,
        model.cu:17-53)."""
        import time as _time

        f = jax.jit(lambda x: x + 1.0)
        x = jnp.zeros((8,), jnp.float32)
        jax.block_until_ready(f(x))  # compile outside the timed region
        ts = []
        for _ in range(n):
            t0 = _time.perf_counter()
            jax.block_until_ready(f(x))
            ts.append(_time.perf_counter() - t0)
        ts.sort()
        floor, med = ts[0], ts[n // 2]
        return {"floor_us": round(floor * 1e6, 1),
                "median_us": round(med * 1e6, 1),
                "tainted": FFModel._probe_taint(floor, med)}

    @staticmethod
    def _probe_taint(floor: float, med: float) -> bool:
        """Taint rule: intermittent load shows up as median >> floor; the
        absolute term keeps sub-100us timer jitter from flagging an idle
        machine."""
        return med > 2.0 * floor and med > 100e-6

    def _maybe_playoff(self, xs, y_arr, bs) -> None:
        cfg = self.config
        steps = getattr(cfg, "playoff_steps", 0)
        if steps <= 0 or getattr(self, "_playoff_done", True):
            return
        from ..core.machine import mesh_axis_sizes

        nontrivial = (
            any(v for v in self._search_strategies.values())
            or self._search_layers is not None
            or self.pipelined is not None
            or any(a != "data" and s > 1 for a, s in
                   mesh_axis_sizes(self.compiled.mesh).items())
        )
        if not nontrivial:
            self._playoff_done = True  # plain DP: nothing to ever race
            return
        if len(y_arr) < bs:
            return  # too little data THIS call; retry on the next fit
        self._playoff_done = True
        import dataclasses as _dc

        from .compiler import compile_model

        try:
            probe = self._dispatch_probe()
            if probe["tainted"]:
                print(f"[playoff] contention: dispatch median "
                      f"{probe['median_us']:.0f}us vs floor "
                      f"{probe['floor_us']:.0f}us — host loaded, timings "
                      f"suspect", flush=True)
            t_searched = self._time_compiled(
                self.compiled, self.pipelined, xs, y_arr, bs, steps)
            dp_cfg = _dc.replace(cfg, only_data_parallel=True,
                                 mesh_shape=None, playoff_steps=0)
            ctx = self._compile_ctx
            # the ORIGINAL builder graph — exactly what the user's
            # --only-data-parallel run would execute (a structural
            # rewrite the search chose is part of what's being raced:
            # measured evidence showed a rewritten graph's DP compile
            # running 12% slower than plain DP on the moe workload).
            # Weights carry over by op/weight name; layers a rewrite
            # replaced keep their fresh init, same as the rewrite itself
            layers = self.layers
            if cfg.perform_fusion:
                from ..ops.fused import apply_fusion

                layers = apply_fusion(list(layers),
                                      {ctx["logits"].tensor_id})
            dp_cm = compile_model(
                dp_cfg, layers, self._used_inputs(), ctx["logits"],
                self.optimizer, ctx["loss_type"], ctx["mtypes"],
                strategies={}, mesh=None, comp_mode=ctx["comp_mode"])
            src_params = self.compiled.params
            for opn, ws in dp_cm.params.items():
                for w in ws:
                    sv = src_params.get(opn, {}).get(w)
                    if sv is not None and tuple(sv.shape) == tuple(ws[w].shape):
                        ws[w] = jax.device_put(
                            np.asarray(sv), dp_cm.param_shardings[opn][w])
            # optimizer state too (momentum from a checkpoint restore must
            # survive the swap); tree structures match because the graph
            # and optimizer are identical — only shardings differ
            from jax.sharding import NamedSharding

            def _move_leaf(sv, dv):
                if tuple(np.shape(sv)) != tuple(np.shape(dv)):
                    return dv
                if isinstance(getattr(dv, "sharding", None), NamedSharding):
                    return jax.device_put(np.asarray(sv), dv.sharding)
                # scalar counters (Adam's t) live uncommitted; a committed
                # copy would pin them to one device and break the SPMD step
                return np.asarray(sv)

            sl, st = jax.tree_util.tree_flatten(self.compiled.opt_state)
            dl, dt = jax.tree_util.tree_flatten(dp_cm.opt_state)
            if st == dt:
                dp_cm.opt_state = jax.tree_util.tree_unflatten(
                    dt, [_move_leaf(sv, dv) for sv, dv in zip(sl, dl)])
            t_dp = self._time_compiled(dp_cm, None, xs, y_arr, bs, steps)
        except Exception as e:  # a playoff failure must never kill training
            print(f"[playoff] skipped: {type(e).__name__}: {e}", flush=True)
            return
        # always printed: the measured decision is part of the training
        # record (the AE runner parses it into the artifact)
        kept = "dp" if t_dp < t_searched else "searched"
        print(f"[playoff] searched {t_searched*1e3:.2f}ms/step vs "
              f"dp {t_dp*1e3:.2f}ms/step -> {kept}", flush=True)
        self._playoff_record = {
            "searched_ms": t_searched * 1e3, "dp_ms": t_dp * 1e3,
            "kept": kept, "probe": probe,
        }
        if t_dp < t_searched:
            # measured loser is discarded: train plain data-parallel on
            # the ORIGINAL graph (sharding choices AND structural
            # rewrites both lost the race)
            dp_cm.iteration = self.compiled.iteration
            self.compiled = dp_cm
            self.pipelined = None
            self._search_strategies = {}
            self._search_layers = None
            self._index_params()

    def _used_inputs(self) -> List[Tensor]:
        used = set()
        for layer in self.layers:
            for t in layer.inputs:
                if t.owner_layer is None:
                    used.add(t.tensor_id)
        return [t for t in self.input_tensors if t.tensor_id in used]

    def _final_output(self) -> Tensor:
        """The final op's output (reference: loss/metrics attach to the last
        operator — model.cc:2875)."""
        produced = {}
        consumed = set()
        for layer in self.layers:
            for t in layer.outputs:
                produced[t.tensor_id] = t
            for t in layer.inputs:
                consumed.add(t.tensor_id)
        leaves = [t for tid, t in produced.items() if tid not in consumed]
        if not leaves:
            raise ValueError("empty model")
        return leaves[-1]

    def _next_rng(self) -> jax.Array:
        self._rng_counter += 1
        return jax.random.fold_in(jax.random.key(self.config.seed), self._rng_counter)

    # ---- high-level fit/eval (reference: flexflow_cffi.py:2062-2105) ----- #
    def _dynamic_shapes_spec(self, cm, loaders, y_arr):
        """Resolve the token-native dynamic-shape knobs into a
        (PackingSpec, per-row lengths) pair, or ``None`` with the mode
        off. Validates at entry (the mode-knob convention): a ladder
        typo, a budget without buckets, or labels that violate the
        trailing ``-1`` padding contract all raise a coded
        DynamicShapeError before a single step runs. Stores the
        resolved ladder on the model so the ledger's cohort key sees
        the envelope actually dispatched."""
        cfg = self.config
        mode = getattr(cfg, "seq_buckets", "off")
        budget = max(0, int(getattr(cfg, "token_budget", 0) or 0))
        pad_max = getattr(cfg, "seq_bucket_pad_max", "off")
        if pad_max not in ("on", "off"):
            raise DynamicShapeError(
                "DYN003", f"seq_bucket_pad_max={pad_max!r} "
                "(expected 'on' or 'off')")
        if mode == "off":
            if budget:
                raise DynamicShapeError(
                    "DYN003", "token_budget requires seq_buckets "
                    "(the packing plan is defined per bucket ladder)")
            return None
        if cm.loss_type is not LossType.SPARSE_CATEGORICAL_CROSSENTROPY:
            raise DynamicShapeError(
                "DYN003", "seq_buckets needs token-level sparse-CE "
                "labels (the row lengths come from their -1 padding)")
        if self.pipelined is not None:
            raise DynamicShapeError(
                "DYN003", "seq_buckets does not compose with the "
                "pipeline engine yet (its schedule programs are "
                "compiled for one microbatch shape)")
        lengths = row_lengths(y_arr)
        seq_dim = y_arr.shape[1]
        hi = int(getattr(cfg, "seq_bucket_max", 0) or 0) or seq_dim
        ladder = resolve_ladder(mode, getattr(cfg, "seq_bucket_min", 8),
                                min(hi, seq_dim))
        dp = (mesh_axis_sizes(cm.mesh).get(DATA_AXIS, 1)
              if cfg.enable_sample_parallel else 1)
        # which loaders carry the sequence axis: dim 1 matching the
        # label seq dim (tokens/positions/(N,S) labels); feature-only
        # inputs keep their width
        seq_axes = tuple(l.data.ndim >= 2 and l.data.shape[1] == seq_dim
                         for l in loaders)
        pad_values = tuple([0] * (len(loaders) - 1) + [-1])
        self._resolved_ladder = ladder
        self._resolved_token_budget = budget
        return PackingSpec(
            ladder=ladder, token_budget=budget,
            batch_size=loaders[0].batch_size, quantum=dp,
            pad_max=(pad_max == "on"), seq_axes=seq_axes,
            pad_values=pad_values), lengths

    def _make_loader_group(self, xs, y, bs: int, cm,
                           shuffle: bool) -> DataLoaderGroup:
        """The shared loader stack of fit() and eval(): one
        SingleDataLoader per input with its compiled sharding, plus the
        label loader (sparse-CE labels reshaped/cast once, host-side).
        With ``config.seq_buckets`` active the group carries the
        dynamic-shape packing spec and builds its per-epoch plan at
        every reset."""
        loaders = [
            SingleDataLoader(np.asarray(a), bs, sh)
            for a, sh in zip(xs, cm.input_shardings)
        ]
        y_arr = np.asarray(y)
        if cm.loss_type is LossType.SPARSE_CATEGORICAL_CROSSENTROPY:
            y_arr = y_arr.reshape(y_arr.shape[0], -1).astype(np.int32)
        loaders.append(SingleDataLoader(y_arr, bs, cm.label_sharding))
        dyn = self._dynamic_shapes_spec(cm, loaders, y_arr)
        if dyn is None:
            return DataLoaderGroup(loaders, seed=self.config.seed,
                                   shuffle=shuffle)
        spec, lengths = dyn
        return DataLoaderGroup(loaders, seed=self.config.seed,
                               shuffle=shuffle, packing=spec,
                               lengths=lengths)

    def _step_loop_knobs(self, cm, recompile_state=None):
        """(prefetch_depth, max_inflight, steps_per_dispatch) for the
        async step loop. Multi-step dispatch needs a scannable train step
        and no per-step hooks: the pipeline engine and recompile-on-
        condition both require step granularity, so they force k=1 — as
        do dynamic shapes (variable (rows, width) batches cannot stack
        into one scanned super-batch)."""
        cfg = self.config
        depth = max(0, int(getattr(cfg, "prefetch_depth", 0)))
        max_inflight = max(1, int(getattr(cfg, "max_inflight_steps", 2)))
        k = max(1, int(getattr(cfg, "steps_per_dispatch", 1)))
        if (self.pipelined is not None or recompile_state is not None
                or cm.train_k_steps is None
                or getattr(cfg, "seq_buckets", "off") != "off"):
            k = 1
        return depth, max_inflight, k

    @staticmethod
    def _advance_window(stats, inflight, result, n_steps: int,
                        nbytes: int, max_inflight: int) -> None:
        """The dispatch-ahead window shared by fit and eval: record the
        occupancy sample, push the just-dispatched step's result, and
        block on the oldest once more than ``max_inflight`` are
        outstanding (jax async dispatch overlaps them; the bound keeps
        dispatch queues and host memory sane)."""
        stats.record_inflight(len(inflight))
        stats.record_steps(n_steps, nbytes)
        inflight.append(result)
        while len(inflight) > max_inflight:
            jax.block_until_ready(inflight.popleft())

    @staticmethod
    def _step_loop_profile(epoch_records, depth, max_inflight, k) -> dict:
        """The throughput record fit/eval publish (profiling.fit_report)."""
        total_steps = sum(r["steps"] for r in epoch_records)
        total_wall = sum(r["wall_s"] for r in epoch_records)
        return {
            "epochs": epoch_records,
            "steps_per_s": (round(total_steps / total_wall, 3)
                            if total_wall > 0 else 0.0),
            "prefetch_depth": depth,
            "max_inflight_steps": max_inflight,
            "steps_per_dispatch": k,
        }

    def _resume_setup(self, guard, resume_from: Optional[str],
                      verbose: bool):
        """fit()'s crash-safety bootstrap. Opens the checkpoint manager
        (when periodic checkpointing or a resume is requested), restores
        the newest INTACT checkpoint from ``resume_from`` — params,
        optimizer state, iteration, rng counter, lr, guard budget — and
        returns ``(mgr, interval, start_epoch, skip_steps)`` telling the
        epoch loop where to pick the run back up. An empty resume dir
        starts fresh (relaunch loops pass ``resume_from``
        unconditionally)."""
        cfg = self.config
        interval = max(0, int(getattr(cfg, "checkpoint_interval_steps", 0)
                              or 0))
        mgr = None
        start_epoch = skip_steps = 0
        if interval or resume_from:
            from .checkpoint import (CheckpointManager,
                                     MultiHostCheckpointManager,
                                     is_multihost_dir)

            ckpt_dir = (resume_from
                        or getattr(cfg, "checkpoint_dir", None)
                        or os.path.join(".ffcache", "ckpt"))
            keep = max(1, int(getattr(
                cfg, "checkpoint_max_to_keep", 3) or 3))
            if jax.process_count() > 1 or is_multihost_dir(ckpt_dir):
                # multi-process cohort (or a cohort's directory read by
                # a resized relaunch): per-process shard payloads plus
                # rank 0's topology-stamped manifest barrier
                mgr = MultiHostCheckpointManager(
                    ckpt_dir, max_to_keep=keep,
                    barrier_timeout_s=getattr(
                        cfg, "checkpoint_barrier_timeout_s", None))
            else:
                mgr = CheckpointManager(ckpt_dir, max_to_keep=keep)
        if resume_from and mgr.latest_step() is not None:
            # newest intact step, where intact = payload AND resume
            # sidecar (a payload-only step would restart the epoch /
            # shuffle position from zero on mid-run params); fallbacks
            # are counted, exhaustion raises loudly. A topology change
            # (resized world, reshaped mesh) raises the coded CKPT001
            # error unless config.elastic_resume opts into the explicit
            # portable restore — search already re-ran for the new
            # topology at compile() (the strategy-cache key covers it)
            from .checkpoint import CheckpointTopologyError

            try:
                step = mgr.restore(self, require_extra=True)
            except CheckpointTopologyError as e:
                if not getattr(cfg, "elastic_resume", False):
                    raise
                import sys

                print(f"[resume] topology changed ({e}); performing the "
                      f"elastic portable restore", file=sys.stderr,
                      flush=True)
                step = mgr.restore_elastic(self)
            extra = mgr.restore_extra(step) or {}
            self._rng_counter = int(
                extra.get("rng_counter", self._rng_counter))
            lr = extra.get("lr")
            if lr is not None:
                # restores mid-run schedules AND guard backoffs; live
                # immediately (hyperparams are dynamic step arguments)
                self.set_learning_rate(float(lr))
            if guard is not None:
                guard.load_state(extra.get("guard"))
            start_epoch = int(extra.get("epoch", 0))
            skip_steps = int(extra.get("step_in_epoch", 0))
            metrics_registry().counter("checkpoint.resumes").inc()
            if verbose or cfg.profiling:
                print(f"[resume] restored step {step} from "
                      f"{mgr.directory} (epoch {start_epoch}, "
                      f"step-in-epoch {skip_steps})", flush=True)
        return mgr, interval, start_epoch, skip_steps

    def _save_resume_checkpoint(self, mgr, epoch: int, steps_in_epoch: int,
                                guard) -> None:
        """One full-resume checkpoint: sharded params/opt state plus the
        step-loop position (epoch, step-in-epoch, rng counter, lr, guard
        budget) in the atomic sidecar. Commit is asynchronous (Orbax) —
        the device->host copy completes before save() returns, so the
        step loop may immediately donate the live buffers."""
        cm = self.compiled
        if self.pipelined is not None:
            # the stage copies hold the live weights mid-fit; fold them
            # into the CompiledModel view the checkpoint reads
            self.pipelined.sync_to(cm)
        opt = self.optimizer
        lr = getattr(opt, "lr", getattr(opt, "alpha", None))
        from .checkpoint import topology_signature

        extra = {
            "schema": 1,
            "epoch": int(epoch),
            "step_in_epoch": int(steps_in_epoch),
            "rng_counter": int(self._rng_counter),
            "lr": float(lr) if lr is not None else None,
            "guard": guard.state() if guard is not None else None,
            # topology stamp: a resume under a different process count /
            # device count / mesh fails loudly (CKPT001) instead of
            # restoring into the wrong sharding
            "topology": topology_signature(cm.mesh),
            **cm.resume_state(),
        }
        mgr.save(self, cm.iteration, extra=extra, wait=False)
        metrics_registry().counter("checkpoint.saves").inc()

    def fit(
        self,
        x: Union[np.ndarray, List[np.ndarray]],
        y: np.ndarray,
        batch_size: Optional[int] = None,
        epochs: Optional[int] = None,
        shuffle: bool = True,
        verbose: bool = True,
        recompile_state=None,
        guard=None,
        resume_from: Optional[str] = None,
    ) -> List[PerfMetrics]:
        """``guard``: a :class:`runtime.guard.TrainingGuard` — non-finite
        epoch losses roll back to the last healthy snapshot with lr
        backoff instead of poisoning the run (no reference equivalent:
        SURVEY.md §5 lists failure detection as absent upstream).

        Crash safety: with ``config.checkpoint_interval_steps`` > 0 the
        loop saves a FULL resume checkpoint (params, optimizer state,
        step/epoch position, rng counter, dataloader shuffle state,
        guard budget, lr) every N steps, asynchronously, into
        ``config.checkpoint_dir``. ``resume_from=dir`` restores the
        newest intact checkpoint from ``dir`` and replays the loop from
        exactly there — same shuffle permutations, same rng folds, same
        batch boundaries — so the resumed run's params are bit-identical
        to the uninterrupted run's (tools/chaos_bench.py proves it). An
        empty ``dir`` starts fresh, so a crash-looped launcher can pass
        ``resume_from`` unconditionally.

        The step loop is asynchronous end to end: a Prefetcher assembles
        and device_puts batches ahead of compute (config.prefetch_depth),
        at most config.max_inflight_steps dispatched steps stay in flight,
        metric/guard accumulation stays device-side, and the host syncs
        only at epoch boundaries (and guard checks). With
        ``config.steps_per_dispatch`` k>1, k batches run per dispatch via
        the lax.scan multi-step executable. Per-epoch throughput counters
        land in ``self.fit_profile``."""
        assert self.compiled is not None, "call compile() first"
        configure_tracer(self.config)
        from ..obs.attribution import attribution_mode
        from ..obs.costcorpus import corpus_mode
        from ..obs.divergence import divergence_mode
        from ..obs.ledger import ledger_mode, record_fit
        from ..obs.server import configure_obs_server
        from ..obs.watchdog import beat as _wd_beat
        from ..obs.watchdog import configure_watchdog

        divergence_mode(self.config)  # typo fails BEFORE training, not after
        ledger_mode(self.config)      # same contract for the ledger knob
        attribution_mode(self.config)
        corpus_mode(self.config)
        # cohort observability (obs/cohort.py): validated at entry like
        # every mode knob; "on" arms the tracer — the fit.step spans ARE
        # the cross-rank skew substrate the fit-tail export ships
        from ..obs.cohort import cohort_obs_mode, maybe_export_cohort

        if cohort_obs_mode(self.config) == "on":
            configure_tracer(enabled=True)
        # fault plan: validated + armed before any step runs (zero cost
        # off: every site below is one global None-check)
        from . import faults as _fx

        _fx.configure_faults(self.config)
        configure_obs_server(self.config)  # ratchet-on scrape surface
        # config.watchdog="on" arms the stall monitor (threshold/dir from
        # config); the step loop below heartbeats it via the Prefetcher's
        # watched section plus the explicit per-step beat
        configure_watchdog(self.config)
        if guard is not None and self.pipelined is not None:
            raise ValueError("TrainingGuard does not support pipelined "
                             "models yet (stage state lives off the "
                             "CompiledModel)")
        xs = x if isinstance(x, (list, tuple)) else [x]
        if (getattr(self.config, "playoff_steps", 0) > 0
                and not getattr(self, "_playoff_done", True)):
            self._maybe_playoff([np.asarray(a) for a in xs], np.asarray(y),
                                batch_size or self.config.batch_size)
        cm = self.compiled
        epochs = epochs or self.config.epochs
        bs = batch_size or self.config.batch_size
        if self.pipelined is not None:
            mb = self.pipelined.cfg.num_microbatches
            if bs % mb != 0:
                raise ValueError(
                    f"batch_size {bs} is not divisible by the pipeline's "
                    f"{mb} microbatches (set when the model was compiled "
                    f"for the pipe mesh); pass a compatible batch_size or "
                    f"recompile with pipeline=PipelineConfig(...)")
        group = self._make_loader_group(xs, y, bs, cm, shuffle)
        depth, max_inflight, k = self._step_loop_knobs(cm, recompile_state)
        # token-native dynamic shapes: per-batch (rows, width) dispatch
        # shapes, each unseen one a counted compile miss
        dyn = group.packing is not None
        bucket_missed = 0
        tok_valid = tok_total = 0
        # crash-safe resume + periodic checkpointing (runtime/checkpoint)
        ckpt_mgr, ckpt_interval, start_epoch, skip_steps = \
            self._resume_setup(guard, resume_from, verbose)
        steps_since_ckpt = 0
        batch_nbytes = group.batch_nbytes
        history: List[PerfMetrics] = []
        epoch_records: List[dict] = []
        # the most recent step's READY loss, carried ACROSS epochs: the
        # recompile trigger reads it with a persistent one-step lag, so
        # every step's loss — including each epoch's final batch —
        # reaches last_metric at some check point
        prev_loss = None
        if guard is not None:
            guard.ensure_snapshot(self)  # epoch-0 divergence rolls back too
        if start_epoch:
            # replay the skipped epochs' shuffle resets so the resume
            # epoch draws the SAME permutation the original run drew
            group.advance_epochs(start_epoch)
        for epoch in range(epochs):
            if epoch < start_epoch:
                continue  # completed before the crash (rng replayed above)
            stats = EpochThroughput()
            pf = Prefetcher(group, depth, steps_per_item=k, stats=stats)
            pm = PerfMetrics()
            last_loss = None
            loss_accum = None  # device-side; NaN/inf in ANY batch survives
            inflight = collections.deque()
            steps_in_epoch = skip_steps if epoch == start_epoch else 0
            for nk, batch in pf.epoch(skip=steps_in_epoch):
                # span per step: host-side dispatch + window control
                # time, numbered for the profiler. Entered and left by
                # hand (the body is long and an exception ends the fit)
                _step = span("fit.step", cat="fit", step_num=cm.iteration,
                             k=nk)
                _step.__enter__()
                if self.pipelined is not None:
                    loss, bm = self.pipelined.train_step(
                        self._next_rng(), batch[:-1], batch[-1]
                    )
                    guard_add = loss
                elif nk > 1:
                    # multi-step executable: nk batches in ONE dispatch;
                    # the rng sequence advances exactly as nk serial
                    # steps would
                    rngs = jnp.stack(
                        [self._next_rng() for _ in range(nk)])
                    cm.params, cm.opt_state, losses, bm_folded = \
                        cm.train_k_steps(
                            cm.params, cm.opt_state, rngs, *batch,
                            seq_length=self.iter_config.seq_length,
                        )
                    loss = losses[-1]
                    # the nk per-step metric dicts were ALREADY folded
                    # in step order inside the scanned program (the
                    # whole-program discipline: optimizer, grad-sync
                    # collectives and metric fold in one dispatch); the
                    # host parks exactly one device dict per dispatch,
                    # so epoch totals still match nk serial steps bit
                    # for bit at 1/nk the host fold work
                    bm = None
                    pm.accumulate(bm_folded)
                    guard_add = losses.sum() if guard is not None else None
                else:
                    sl = self.iter_config.seq_length
                    if dyn:
                        # dispatch at the batch's bucket: seq_length is
                        # a STATIC step argument, so each (rows, width)
                        # is its own executable — note the shape FIRST
                        # so an unseen bucket is a counted miss, never
                        # a silent retrace
                        rows, sl = batch[-1].shape[0], batch[-1].shape[1]
                        if cm.note_dispatch_shape("train", rows, sl):
                            bucket_missed += 1
                            metrics_registry().counter(
                                "fit.bucket_compiles").inc()
                    cm.params, cm.opt_state, loss, bm = cm.train_step(
                        cm.params, cm.opt_state, self._next_rng(), *batch,
                        seq_length=sl,
                    )
                    guard_add = loss
                if _fx.active():
                    # fault site: NaN loss — poisons the guard's epoch
                    # accumulator exactly as a real bf16 overflow would
                    rule = _fx.fire("train.nan_loss")
                    if rule is not None:
                        loss = loss * np.float32("nan")
                        if guard_add is not None:
                            guard_add = guard_add * np.float32("nan")
                if bm is not None:  # k>1 accumulated per-step above
                    pm.accumulate(bm)
                last_loss = loss
                if guard is not None:
                    # sum, not last value: a mid-epoch NaN/inf must not be
                    # masked by a finite final batch (clipped CE losses
                    # stay finite on garbage params)
                    loss_accum = (guard_add if loss_accum is None
                                  else loss_accum + guard_add)
                self._advance_window(stats, inflight, loss, nk,
                                     batch_nbytes * nk, max_inflight)
                _wd_beat("fit.loop")  # watchdog heartbeat (no-op when off)
                cm.iteration += nk
                steps_in_epoch += nk
                # reference: --print-freq (config.print_freq) — the
                # mid-epoch progress cadence. Host-side counters only:
                # no device value is read, so the async pipeline never
                # syncs for a progress line
                pf = self.config.print_freq
                if (verbose and pf > 0
                        and steps_in_epoch // pf
                        != (steps_in_epoch - nk) // pf):
                    print(f"[fit] epoch {epoch} step {steps_in_epoch} "
                          f"(iteration {cm.iteration})", flush=True)
                if ckpt_interval and ckpt_mgr is not None:
                    steps_since_ckpt += nk
                    if steps_since_ckpt >= ckpt_interval:
                        steps_since_ckpt = 0
                        # with a guard armed, verify the partial epoch's
                        # loss sum BEFORE snapshotting/persisting: an
                        # unchecked interval snapshot would capture
                        # already-diverged params as the rollback point
                        # (and reset the restore budget), and a NaN
                        # checkpoint would poison resume. The host sync
                        # is paid at checkpoint boundaries only — the
                        # save's device->host copy syncs anyway.
                        healthy = True
                        if guard is not None and loss_accum is not None:
                            healthy = bool(np.isfinite(float(loss_accum)))  # hotpath: sync-ok (checkpoint-boundary only, throttled to checkpoint_interval_steps; the save below syncs regardless)
                        if healthy:
                            if guard is not None:
                                # sub-epoch rollback point: long epochs
                                # no longer lose a whole epoch to a
                                # divergence
                                guard.snapshot(self, scope="interval")
                            self._save_resume_checkpoint(
                                ckpt_mgr, epoch, steps_in_epoch, guard)
                if _fx.active():
                    # fault sites: a slow step that must trip the PR 8
                    # watchdog, then a hard kill (AFTER the checkpoint
                    # save above — "kill at step N" leaves steps <= N)
                    rule = _fx.fire("train.stall")
                    if rule is not None:
                        time.sleep(float(rule.get("stall_s", 1.0)))  # hotpath: sync-ok (float() of a plan-dict scalar, not a device value; chaos-run only — the site is unreachable without an armed fault plan)
                    rule = _fx.fire("train.kill")
                    if rule is not None:
                        os._exit(int(rule.get("exit_code", 41)))
                    # multihost chaos: a slow peer stalls its heartbeat
                    # (the supervisor's hang detector + the watchdog's
                    # black box must fire), a killed peer dies hard
                    # AFTER the checkpoint block like train.kill
                    rule = _fx.fire("multihost.slow_peer")
                    if rule is not None:
                        time.sleep(float(rule.get("stall_s", 2.0)))  # hotpath: sync-ok (plan-dict scalar sleep; chaos-run only — unreachable without an armed fault plan)
                    rule = _fx.fire("multihost.peer_kill")
                    if rule is not None:
                        os._exit(int(rule.get("exit_code", 43)))
                if recompile_state is not None:
                    # reference: recompile_on_condition evaluated per
                    # iteration inside the train loop (model.cc:2422).
                    # The device->host metric read is throttled to the
                    # state's check_interval and fed the most recent
                    # READY loss (the previous step's, already
                    # materialized while this step dispatched) so it
                    # does not stall the async pipeline every iteration.
                    from .recompile import recompile_on_condition

                    ci = max(1, getattr(recompile_state,
                                        "check_interval", 1))
                    if (recompile_state.iteration + 1) % ci == 0:
                        src = prev_loss if prev_loss is not None else loss
                        recompile_state.last_metric = float(src)  # hotpath: sync-ok (throttled to check_interval; reads the PREVIOUS step's already-ready loss)
                    with span("fit.recompile_check", cat="fit"):
                        fired = recompile_on_condition(self, recompile_state)
                    if fired:
                        cm = self.compiled
                prev_loss = loss
                _step.__exit__(None, None, None)
            with span("fit.host_sync", cat="fit", epoch=epoch):
                pm.flush()  # the epoch-boundary host sync (device-side accum)
            if dyn:
                v, t = group.epoch_token_stats
                stats.record_tokens(v, t)
                tok_valid += v
                tok_total += t
            epoch_records.append(stats.finish())
            if self.config.profiling:
                r = epoch_records[-1]
                print(f"[fit] epoch {epoch}: {r['steps_per_s']:.1f} steps/s"
                      f" input_wait {r['input_wait_s']*1e3:.1f}ms"
                      f" occupancy {r['dispatch_ahead_occupancy']:.2f}"
                      f" depth_hist {r['queue_depth_hist']}", flush=True)
            if guard is not None:
                # a zero-batch epoch (loss_accum None) ran nothing: healthy
                accum = (float(loss_accum) if loss_accum is not None
                         else 0.0)
                if not np.isfinite(accum):
                    from .guard import DivergenceError

                    if not guard.recover(self, verbose=verbose):
                        raise DivergenceError(
                            f"epoch {epoch} loss sum {accum} and the "
                            f"guard's restore budget is exhausted")
                    history.append(pm)
                    continue
                guard.snapshot(self)
            if verbose:
                # host sync only when someone reads the value
                lv = float(last_loss) if last_loss is not None else float("nan")
                print(
                    f"epoch {epoch}: loss {lv:.4f}  {pm.report(cm.metrics)}",
                    flush=True,
                )
            history.append(pm)
        if ckpt_mgr is not None:
            ckpt_mgr.close()  # waits out any pending async commit
        self.fit_profile = self._step_loop_profile(
            epoch_records, depth, max_inflight, k)
        if dyn:
            # the dynamic-shape envelope + compile accounting the ledger
            # record and the advisor's padded-FLOPs rule read
            self.fit_profile["buckets"] = {
                "ladder": list(self._resolved_ladder),
                "token_budget": self._resolved_token_budget,
                "pad_max": group.packing.pad_max,
                "new_compiles": bucket_missed,
                "known_shapes": len(cm._seen_shapes),
                "padded_token_fraction": round(
                    1.0 - tok_valid / max(1, tok_total), 6),
            }
        if guard is not None:
            # recovery narrative for the ledger record + explain_run
            self.fit_profile["guard"] = guard.report()
        if self.pipelined is not None:
            # per-stage schedule timeline + bubble fraction + measured
            # dispatch counts (runtime/profiling.pipeline_report)
            self.fit_profile["pipeline"] = self.pipelined.profile(
                bs // self.pipelined.cfg.num_microbatches)
            if self.config.profiling:
                p = self.fit_profile["pipeline"]
                print(f"[fit] pipeline {p['engine']}:{p['schedule']} "
                      f"bubble {p['bubble_fraction']:.3f} "
                      f"dispatches/step {p['dispatches_per_step']}",
                      flush=True)
            # keep the CompiledModel view current so checkpoint/eval/
            # get_weights after a pipelined fit see trained weights
            self.pipelined.sync_to(cm)
        # sim-vs-measured divergence (config.divergence; obs/divergence.py)
        from ..obs.divergence import maybe_record_divergence

        maybe_record_divergence(self)
        # step-time attribution (config.attribution; obs/attribution.py):
        # AFTER divergence so the per-op measured rows are joinable
        from ..obs.attribution import maybe_attribute

        maybe_attribute(self)
        if self.config.profiling and (self.fit_profile or {}).get(
                "attribution"):
            from ..obs.attribution import format_phase_table

            print(format_phase_table(self.fit_profile["attribution"]),
                  flush=True)
        # perf advisor (config.advisor; obs/advisor.py): the dominant
        # phase mapped to ranked knob deltas — fit_profile["advice"] +
        # the obs server's /advice endpoint
        from ..obs.advisor import maybe_advise

        maybe_advise(self)
        if self.config.profiling and (self.fit_profile or {}).get(
                "advice"):
            top = self.fit_profile["advice"]["suggestions"][0]
            print(f"[advise] {top['phase']} -> {top['knob']}="
                  f"{top['proposed']} (expected "
                  f"-{top['expected']['step_delta_frac'] * 100:.1f}% "
                  f"step time, {top['expected']['basis']})", flush=True)
        # per-op cost corpus (config.cost_corpus; obs/costcorpus.py):
        # measured fwd+bwd rows for the learned cost model's flywheel
        from ..obs.costcorpus import maybe_collect_corpus

        maybe_collect_corpus(self)
        # durable telemetry: one ledger record per fit — throughput,
        # divergence block, attribution, watchdog state, metrics snapshot
        record_fit(self)
        # cohort artifacts (config.cohort_obs; obs/cohort.py): this
        # rank's labeled trace + metrics snapshot + manifest, for the
        # supervisor's cross-rank merge/skew report
        maybe_export_cohort(self)
        return history

    def eval(self, x, y, batch_size: Optional[int] = None, verbose: bool = True) -> PerfMetrics:
        """reference: flexflow_cffi.py:2106. Shares fit()'s async step
        loop: prefetched input pipeline, bounded dispatch-ahead window,
        device-side metric accumulation with one sync at the end; the
        throughput record lands in ``self.eval_profile``."""
        assert self.compiled is not None
        configure_tracer(self.config)
        from ..obs.ledger import ledger_mode
        from ..obs.watchdog import beat as _wd_beat
        from ..obs.watchdog import configure_watchdog

        ledger_mode(self.config)  # typo fails BEFORE the eval, not after
        configure_watchdog(self.config)
        cm = self.compiled
        xs = x if isinstance(x, (list, tuple)) else [x]
        bs = batch_size or self.config.batch_size
        group = self._make_loader_group(xs, y, bs, cm, shuffle=False)
        depth, max_inflight, _ = self._step_loop_knobs(cm)
        dyn = group.packing is not None
        bucket_missed = 0
        batch_nbytes = group.batch_nbytes
        stats = EpochThroughput(prefix="eval")  # eval.* registry series
        pf = Prefetcher(group, depth, stats=stats)
        pm = PerfMetrics()
        inflight = collections.deque()
        for _nk, batch in pf.epoch(reshuffle=False):
            _step = span("eval.step", cat="eval")
            _step.__enter__()
            sl = self.iter_config.seq_length
            if dyn:
                rows, sl = batch[-1].shape[0], batch[-1].shape[1]
                if cm.note_dispatch_shape("eval", rows, sl):
                    bucket_missed += 1
                    metrics_registry().counter(
                        "eval.bucket_compiles").inc()
            loss, logits, bm = cm.eval_step(
                cm.params, *batch, seq_length=sl)
            pm.accumulate(bm)
            self._advance_window(stats, inflight, loss, 1, batch_nbytes,
                                 max_inflight)
            _wd_beat("eval.loop")  # watchdog heartbeat (no-op when off)
            _step.__exit__(None, None, None)
        with span("eval.host_sync", cat="eval"):
            pm.flush()
        if dyn:
            stats.record_tokens(*group.epoch_token_stats)
        self.eval_profile = self._step_loop_profile(
            [stats.finish()], depth, max_inflight, 1)
        if dyn:
            v, t = group.epoch_token_stats
            self.eval_profile["buckets"] = {
                "ladder": list(self._resolved_ladder),
                "token_budget": self._resolved_token_budget,
                "pad_max": group.packing.pad_max,
                "new_compiles": bucket_missed,
                "known_shapes": len(cm._seen_shapes),
                "padded_token_fraction": round(
                    1.0 - v / max(1, t), 6),
            }
        if self.config.profiling:
            rec = self.eval_profile["epochs"][0]
            print(f"[eval] {rec['steps_per_s']:.1f} steps/s input_wait "
                  f"{rec['input_wait_s']*1e3:.1f}ms occupancy "
                  f"{rec['dispatch_ahead_occupancy']:.2f}", flush=True)
        if verbose:
            print(f"eval: {pm.report(cm.metrics)}", flush=True)
        from ..obs.ledger import record_fit

        record_fit(self, kind="eval")
        return pm

    # ---- manual-loop verbs (reference: model.cc:2415-2495) --------------- #
    def set_batch(self, xs: List[np.ndarray], y: Optional[np.ndarray] = None) -> None:
        cm = self.compiled
        if not isinstance(xs, (list, tuple)):  # single-input convenience
            xs = [xs]
        batch = [jax.device_put(np.asarray(a), sh) for a, sh in zip(xs, cm.input_shardings)]
        if y is not None:
            batch.append(jax.device_put(np.asarray(y), cm.label_sharding))
        self._cur_batch = batch

    def forward(self, seq_length: Optional[int] = None) -> jax.Array:
        """reference: FFModel::forward (model.cc:2415). ``seq_length``
        truncates sequence ops for this iteration (FFIterationConfig —
        each distinct value is its own compiled executable)."""
        cm = self.compiled
        assert self._cur_batch is not None, "set_batch first"
        xs = self._cur_batch[: len(cm.input_tensors)]
        sl = self.iter_config.seq_length if seq_length is None else seq_length
        self._cur_logits = cm.forward_fn(cm.params, *xs, seq_length=sl)
        return self._cur_logits

    def zero_gradients(self) -> None:
        """reference: FFModel::zero_gradients (model.cc:3359). Gradients are
        recomputed functionally each step; nothing to zero."""
        self._cur_grads = None

    def backward(self, seq_length: Optional[int] = None) -> None:
        """reference: FFModel::backward (model.cc:2438). Functionally:
        compute grads for the current batch via the jitted grad step built
        at compile time."""
        cm = self.compiled
        assert self._cur_batch is not None and cm.loss_type is not None
        sl = self.iter_config.seq_length if seq_length is None else seq_length
        self._cur_grads = cm.grad_step(cm.params, self._next_rng(),
                                       *self._cur_batch, seq_length=sl)

    def update(self) -> None:
        """reference: FFModel::update (model.cc:2469) — optimizer step."""
        cm = self.compiled
        assert self._cur_grads is not None, "backward first"
        cm.params, cm.opt_state = cm.optimizer.update(
            cm.params, self._cur_grads, cm.opt_state, cm.wd_mask
        )
        self._cur_grads = None

    def set_learning_rate(self, lr: float) -> None:
        """Change the optimizer learning rate mid-training (reference:
        Optimizer::set_learning_rate used by the keras
        LearningRateScheduler callback). Hyperparameters are DYNAMIC
        arguments of the compiled step (optimizer.hyperparams() read per
        call), so the change is live immediately — no re-trace."""
        opt = self.optimizer
        if not hasattr(opt, "lr") and not hasattr(opt, "alpha"):
            raise ValueError("optimizer has no learning-rate attribute")
        if hasattr(opt, "lr"):
            opt.lr = float(lr)
        else:
            opt.alpha = float(lr)
        if self.compiled is not None and self.compiled.refresh_train_step:
            self.compiled.refresh_train_step()
        if self.pipelined is not None:
            self.pipelined.refresh_updates()

    # ---- weight access --------------------------------------------------- #
    def get_layers(self) -> Dict[int, Layer]:
        return dict(enumerate(self.layers))

    def get_layer_by_name(self, name: str) -> Optional[Layer]:
        for l in self.layers:
            if l.name == name:
                return l
        return None

    def _get_tensor_value(self, t: Tensor) -> np.ndarray:
        opn, wn = self._param_index[t.tensor_id]
        return np.asarray(self.compiled.params[opn][wn])

    def _set_tensor_value(self, t: Tensor, arr: np.ndarray) -> None:
        opn, wn = self._param_index[t.tensor_id]
        cur = self.compiled.params[opn][wn]
        assert tuple(arr.shape) == tuple(cur.shape), (arr.shape, cur.shape)
        self.compiled.params[opn][wn] = jax.device_put(
            np.asarray(arr, dtype=cur.dtype), self.compiled.param_shardings[opn][wn]
        )

    def get_perf_metrics(self) -> PerfMetrics:
        return PerfMetrics()
