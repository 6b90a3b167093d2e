"""BatchMatmul and MultiHeadAttention operators.

TPU-native equivalents of:
* BatchMatmul — reference: src/ops/batch_matmul.cc, kernels/batch_matmul.cu
  (cuBLAS strided-batched GEMM; builder model.h:481 with
  ``a_seq_length_dim``/``b_seq_length_dim`` truncation hooks).
* MultiHeadAttention — reference: src/ops/attention.cc + attention.cu
  (cuDNN MultiHeadAttn; builder model.h:542). The reference packs
  wq/wk/wv/wo into one cuDNN weight blob; here they are separate named
  weights, and the computation is the standard scaled-dot-product
  formulation, which XLA fuses into MXU-friendly batched GEMMs.

Head-dim partitioning (the reference's attribute parallelism on heads —
substitution.cc:1763-1770 ``create_partition_attention_combine``) is
strategy key ``{"heads": axis}``: weights shard on their head dim and GSPMD
partitions the attention over heads.
"""

from __future__ import annotations

import math
from typing import List

import jax
import jax.numpy as jnp

from ..ffconst import DataType, OpType
from ..core.op import Op, WeightSpec, register_op
from ..core.parallel_tensor import ParallelDim, ParallelTensorShape
from ..runtime.initializer import DefaultWeightInitializer, ZeroInitializer


@register_op
class BatchMatmul(Op):
    op_type = OpType.BATCHMATMUL

    def infer_output_shapes(self):
        a, b = self.input_shapes
        assert len(a.sizes) == len(b.sizes) >= 3
        assert a.sizes[:-2] == b.sizes[:-2], "batch dims must match"
        assert a.sizes[-1] == b.sizes[-2], f"contract {a.sizes} x {b.sizes}"
        out = a.sizes[:-1] + (b.sizes[-1],)
        return [(out, a.dtype)]

    def forward(self, ctx, inputs, weights):
        a, b = inputs
        # seq-length truncation hook (reference: a_seq_length_dim /
        # b_seq_length_dim consume FFIterationConfig.seq_length). Under jit
        # each distinct seq_length compiles its own executable; the slice is
        # static.
        sl = ctx.seq_length
        if sl and sl > 0:
            ad = self.attrs.get("a_seq_length_dim", -1)
            bd = self.attrs.get("b_seq_length_dim", -1)
            if ad >= 0:
                a = jax.lax.slice_in_dim(a, 0, sl, axis=ad)
            if bd >= 0:
                b = jax.lax.slice_in_dim(b, 0, sl, axis=bd)
        return [jnp.matmul(a, b, preferred_element_type=a.dtype)]

    def flops(self) -> float:
        a, b = self.input_shapes
        batch = 1
        for s in a.sizes[:-2]:
            batch *= s
        return 2.0 * batch * a.sizes[-2] * a.sizes[-1] * b.sizes[-1]


@register_op
class MultiHeadAttention(Op):
    op_type = OpType.MULTIHEAD_ATTENTION

    def __init__(self, layer, input_shapes):
        super().__init__(layer, input_shapes)
        a = self.attrs
        self.embed_dim = a["embed_dim"]
        self.num_heads = a["num_heads"]
        self.kdim = a.get("kdim") or self.embed_dim
        self.vdim = a.get("vdim") or self.embed_dim
        self.dropout = float(a.get("dropout", 0.0))
        self.use_bias = bool(a.get("bias", True))
        # per-head projection sizes (reference: attention.cc qProjSize =
        # qdim / num_heads)
        assert self.embed_dim % self.num_heads == 0
        self.head_dim = self.embed_dim // self.num_heads
        self.q_in = input_shapes[0].sizes[-1]
        self.k_in = input_shapes[1].sizes[-1]
        self.v_in = input_shapes[2].sizes[-1]
        self.causal = bool(a.get("causal", False))
        # set by propagate when the strategy sequence-shards this op
        self.seq_axis: str | None = None
        self.seq_mode: str = "ring"  # "ring" | "a2a" (Ulysses)

    def infer_output_shapes(self):
        q = self.input_shapes[0].sizes
        return [(q[:-1] + (self.embed_dim,), self.input_shapes[0].dtype)]

    def weight_specs(self) -> List[WeightSpec]:
        dt = self.input_shapes[0].dtype
        init = self.attrs.get("kernel_initializer") or DefaultWeightInitializer()
        h, d = self.num_heads, self.head_dim
        specs = [
            WeightSpec("wq", (self.q_in, h, d), dt, init),
            WeightSpec("wk", (self.k_in, h, d), dt, init),
            WeightSpec("wv", (self.v_in, h, d), dt, init),
            WeightSpec("wo", (h, d, self.embed_dim), dt, init),
        ]
        if self.use_bias:
            specs += [
                WeightSpec("bq", (h, d), dt, ZeroInitializer(), weight_decay=False),
                WeightSpec("bk", (h, d), dt, ZeroInitializer(), weight_decay=False),
                WeightSpec("bv", (h, d), dt, ZeroInitializer(), weight_decay=False),
                WeightSpec("bo", (self.embed_dim,), dt, ZeroInitializer(), weight_decay=False),
            ]
        return specs

    def forward(self, ctx, inputs, weights):
        q, k, v = inputs
        # (B, S, E) x (E, H, D) -> (B, S, H, D)
        qh = jnp.einsum("bse,ehd->bshd", q, weights["wq"])
        kh = jnp.einsum("bse,ehd->bshd", k, weights["wk"])
        vh = jnp.einsum("bse,ehd->bshd", v, weights["wv"])
        if self.use_bias:
            qh = qh + weights["bq"]
            kh = kh + weights["bk"]
            vh = vh + weights["bv"]
        scale = 1.0 / math.sqrt(self.head_dim)
        drop = self.dropout if (ctx.training and ctx.rng is not None) else 0.0
        from ..parallel.ring_attention import ring_attention, single_device_attention

        if self.seq_axis is not None and ctx.mesh is not None:
            # sequence parallelism: exact attention over seq-sharded q/k/v.
            # "ring": collective-permute ring over ICI; "a2a": Ulysses
            # all-to-all head resharding (no reference equivalent —
            # SURVEY.md §5 names these the TPU-native plan)
            from ..parallel.ring_attention import ulysses_attention

            sp = ulysses_attention if self.seq_mode == "a2a" else ring_attention
            path = "ulysses" if self.seq_mode == "a2a" else "ring"
            ctxv = sp(
                qh, kh, vh, ctx.mesh, self.seq_axis,
                causal=self.causal, scale=scale,
                dropout_rate=drop, rng=ctx.rng,
            )
        else:
            from ..kernels import flash_attention as fa

            ctxv = None
            # win-or-off policy: on `auto` the kernel engages only at
            # shapes where a recorded autotune beat XLA fused
            # (fa.engaged; PARITY.md §flash-attention)
            if drop == 0.0 and fa.engaged(
                    qh.shape[1], kh.shape[1], qh.shape[-1], self.causal):
                mesh = ctx.mesh
                if mesh is None or mesh.size == 1:
                    if fa.supported(qh.shape, kh.shape, self.causal):
                        # Pallas fused attention: (S,S) logits never
                        # touch HBM.
                        ctxv = fa.flash_attention(
                            qh, kh, vh, causal=self.causal, scale=scale)
                else:
                    # multi-device: shard_map the kernel over the batch /
                    # heads mesh axes (attention is independent across
                    # both), so dp x tp configs run the fused kernel too
                    bdim = self.input_shapes[0].dims[0]
                    batch_ax = bdim.axis if bdim.is_partitioned else None
                    wq = self.weight_shapes.get("wq")
                    hdim = wq.dims[1] if wq is not None else None
                    heads_ax = (hdim.axis if hdim is not None and
                                hdim.is_partitioned else None)
                    if fa.sharded_supported(qh.shape, kh.shape, mesh,
                                            batch_ax, heads_ax,
                                            self.causal):
                        ctxv = fa.sharded_flash_attention(
                            qh, kh, vh, mesh, batch_ax, heads_ax,
                            causal=self.causal, scale=scale)
            path = "flash"
            if ctxv is None:
                path = "xla"
                ctxv = single_device_attention(
                    qh, kh, vh, self.causal, scale, drop, ctx.rng
                )
        # which implementation this lowering took, counted once per trace:
        # on `auto` a missing tune entry selects the jnp path without a
        # word, and a chip run has to be able to say which one it timed
        from ..obs.metrics import metrics_registry

        metrics_registry().counter(f"attention.path.{path}").inc()
        out = jnp.einsum("bqhd,hde->bqe", ctxv, weights["wo"])
        if self.use_bias:
            out = out + weights["bo"]
        return [out]

    def propagate(self, input_shapes, strategy):
        out_shapes, weight_shapes = super().propagate(input_shapes, strategy)
        axis_sizes = strategy.get("_axis_sizes", {})
        ax = strategy.get("heads")
        if ax:
            deg = axis_sizes.get(ax, 1)
            if deg > 1 and self.num_heads % deg == 0:
                for wn in ("wq", "wk", "wv"):
                    weight_shapes[wn] = weight_shapes[wn].partitioned(1, deg, ax)
                weight_shapes["wo"] = weight_shapes["wo"].partitioned(0, deg, ax)
                for bn in ("bq", "bk", "bv"):
                    if bn in weight_shapes:
                        weight_shapes[bn] = weight_shapes[bn].partitioned(0, deg, ax)
        sax = strategy.get("seq")
        if sax:
            deg = axis_sizes.get(sax, 1)
            seqs = {s.sizes[1] for s in input_shapes[:3]}
            seq = input_shapes[0].sizes[1]
            # self-attention-shaped only: q/k/v seq equal and divisible
            if deg > 1 and len(seqs) == 1 and seq % deg == 0:
                self.seq_axis = sax
                mode = strategy.get("seq_mode", "ring")
                # Ulysses needs heads divisible by the axis degree
                self.seq_mode = ("a2a" if mode == "a2a"
                                 and self.num_heads % deg == 0 else "ring")
                out_shapes[0] = out_shapes[0].partitioned(1, deg, sax)
                # the entry selects the SP communication schedule even
                # when the seq dim arrived already sharded (downstream
                # layers) — honored, though shapes may not change
                self.honored_strategy_keys.add("seq")
        return out_shapes, weight_shapes

    def flops(self) -> float:
        b, s = self.input_shapes[0].sizes[0], self.input_shapes[0].sizes[1]
        e, h, d = self.embed_dim, self.num_heads, self.head_dim
        proj = 2.0 * b * s * e * h * d * 4  # q,k,v,o projections
        attn = 2.0 * b * h * s * s * d * 2  # logits + context
        return proj + attn
