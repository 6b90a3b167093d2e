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

``MultiHeadAttention.scale`` is what the scores are multiplied by: ``1 /
sqrt(head_dim)``, or the op's ``scale`` attribute where a model states
its own (Granite's ``attention_multiplier``); the forward, every cache
entry kind and both serving kernels read the property.

Four attributes that are absent for most models (MiMo's layers state
all four): ``v_head_dim``, a value head's width where it is not the key
head's (q and k are ``(H, head_dim)``, v and the attended values ``(H,
v_head_dim)``, ``wo`` ``(H, v_head_dim, E)``); ``rotary_dim``, the
first numbers of a head that ``rotary`` rotates (the rest pass);
``sinks``, a learned scalar a query head that is one more column of the
softmax and carries no value (``o = sum_j exp(a_j - m) v_j / (exp(s_h -
m) + sum_j exp(a_j - m))``); ``value_scale``, what the projected values
are multiplied by.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import List, Optional

import jax
import jax.numpy as jnp

from ..ffconst import DataType, OpType
from ..core.op import Op, WeightSpec, register_op, sub_scope
from ..core.parallel_tensor import ParallelDim, ParallelTensorShape
from ..runtime.initializer import (ConstantInitializer,
                                   DefaultWeightInitializer, ZeroInitializer)


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
        # qdim / num_heads), or a ``head_dim`` of the op's own
        if a.get("head_dim"):
            self.head_dim = int(a["head_dim"])
        else:
            assert self.embed_dim % self.num_heads == 0
            self.head_dim = self.embed_dim // self.num_heads
        # grouped heads: query head h reads key-value head
        # h // (num_heads / num_kv_heads)
        self.num_kv_heads = int(a.get("num_kv_heads") or self.num_heads)
        if self.num_heads % self.num_kv_heads:
            raise ValueError(f"{self.num_heads} query heads are not "
                             f"{self.num_kv_heads} equal groups")
        self.q_in = input_shapes[0].sizes[-1]
        self.k_in = input_shapes[1].sizes[-1]
        self.v_in = input_shapes[2].sizes[-1]
        self.causal = bool(a.get("causal", False))
        # an RMSNorm with a gain over the whole projected q, and one over
        # the whole projected k, before the heads are split; ``"head"``:
        # over each head's values, one gain of ``head_dim`` for all heads
        self.qk_norm = bool(a.get("qk_norm", False))
        self.qk_norm_per_head = a.get("qk_norm") == "head"
        self.norm_eps = float(a.get("norm_eps", 1e-6))
        # a key is seen from the ``window`` positions that end at the
        # query's own (None: from every later one)
        self.window = int(a["window"]) if a.get("window") else None
        if self.window and not self.causal:
            raise ValueError(f"{self.name}: a window is a causal op's")
        # rotary positions over the whole head, by theta; the op then
        # takes the graph's positions as its fourth input
        self.rotary = float(a["rotary"]) if a.get("rotary") else None
        # ... or over the first ``rotary_dim`` numbers of it
        self.rotary_dim = (int(a["rotary_dim"]) if a.get("rotary_dim")
                           else None)
        self.inv_freq = (rotary_inv_freq(self.rotary_dim or self.head_dim,
                                         self.rotary)
                         if self.rotary else None)
        # a value head's width, where it is not a key head's
        self.v_head_dim = int(a.get("v_head_dim") or self.head_dim)
        # a learned scalar a query head: one more column of the softmax
        # that carries no value
        self.sinks = bool(a.get("sinks", False))
        # what the projected values are multiplied by
        self.value_scale = (float(a["value_scale"])
                            if a.get("value_scale") else None)
        # the attended values times sigmoid(x W_g), before W_o
        self.gate = bool(a.get("gate", False))
        # what the scores are multiplied by, where it is not
        # 1 / sqrt(head_dim) (a model's own ``attention_multiplier``)
        self._scale = float(a["scale"]) if a.get("scale") else None
        # set by propagate when the strategy sequence-shards this op
        self.seq_axis: str | None = None
        self.seq_mode: str = "ring"  # "ring" | "a2a" (Ulysses)

    def infer_output_shapes(self):
        q = self.input_shapes[0].sizes
        return [(q[:-1] + (self.embed_dim,), self.input_shapes[0].dtype)]

    def weight_specs(self) -> List[WeightSpec]:
        dt = self.input_shapes[0].dtype
        init = self.attrs.get("kernel_initializer") or DefaultWeightInitializer()
        h, d, hkv = self.num_heads, self.head_dim, self.num_kv_heads
        dv = self.v_head_dim
        specs = [
            WeightSpec("wq", (self.q_in, h, d), dt, init),
            WeightSpec("wk", (self.k_in, hkv, d), dt, init),
            WeightSpec("wv", (self.v_in, hkv, dv), dt, init),
            WeightSpec("wo", (h, dv, self.embed_dim), dt, init),
        ]
        if self.use_bias:
            specs += [
                WeightSpec("bq", (h, d), dt, ZeroInitializer(), weight_decay=False),
                WeightSpec("bk", (hkv, d), dt, ZeroInitializer(), weight_decay=False),
                WeightSpec("bv", (hkv, dv), dt, ZeroInitializer(), weight_decay=False),
                WeightSpec("bo", (self.embed_dim,), dt, ZeroInitializer(), weight_decay=False),
            ]
        if self.qk_norm:
            gain = (self.attrs.get("gain_initializer")
                    or ConstantInitializer(1.0))
            per = self.qk_norm_per_head
            specs += [
                WeightSpec("q_norm", (d,) if per else (h, d), dt, gain,
                           weight_decay=False),
                WeightSpec("k_norm", (d,) if per else (hkv, d), dt, gain,
                           weight_decay=False),
            ]
        if self.gate:
            specs.append(WeightSpec("wg", (self.q_in, h, dv), dt, init))
        if self.sinks:
            specs.append(WeightSpec(
                "sinks", (h,), dt,
                self.attrs.get("sink_initializer") or ZeroInitializer(),
                weight_decay=False))
        return specs

    # ---- the pieces serving composes (serving/cache_entry.py) -------------
    @property
    def scale(self) -> float:
        if self._scale is not None:
            return self._scale
        return 1.0 / math.sqrt(self.head_dim)

    @sub_scope("project")
    def project_qkv(self, weights, q_in, k_in, v_in, positions=None):
        """(B, S, E) x (E, H, D) -> the (B, S, H, D) queries, the (B, S,
        Hkv, D) keys and the (B, S, Hkv, Dv) values, biases added, normed,
        with ``rotary`` rotated by ``positions`` (B, S) and the values
        times ``value_scale``."""
        qh = jnp.einsum("bse,ehd->bshd", q_in, weights["wq"])
        kh = jnp.einsum("bse,ehd->bshd", k_in, weights["wk"])
        vh = jnp.einsum("bse,ehd->bshd", v_in, weights["wv"])
        if self.use_bias:
            qh = qh + weights["bq"]
            kh = kh + weights["bk"]
            vh = vh + weights["bv"]
        if self.qk_norm:
            qh = self._normed(qh, weights["q_norm"])
            kh = self._normed(kh, weights["k_norm"])
        if self.rotary:
            qh = apply_rotary(qh, positions, self.inv_freq, self.rotary_dim)
            kh = apply_rotary(kh, positions, self.inv_freq, self.rotary_dim)
        if self.value_scale is not None:
            vh = vh * jnp.asarray(self.value_scale, vh.dtype)
        return qh, kh, vh

    def sink(self, weights):
        """The (H,) sinks, or None for an op that has none."""
        return weights["sinks"] if self.sinks else None

    def _normed(self, x, gain):
        """RMSNorm over all heads' values of a position: ``x`` (..., H, D)
        or packed (..., H D), ``gain`` (H, D); per head: over each head's
        D values of ``x`` (..., H, D), ``gain`` (D,)."""
        from .norm import rms_norm

        if self.qk_norm_per_head:
            return rms_norm(x, gain, self.norm_eps)
        flat = x.reshape(x.shape[:2] + (-1,))
        return rms_norm(flat, gain.reshape(-1), self.norm_eps).reshape(x.shape)

    def project_out(self, weights, ctxv, x=None):
        """The attended (B, S, H, D) values -> (B, S, E); with ``gate``
        times ``sigmoid(x W_g)`` first, ``x`` (B, S, E) the op's input."""
        if self.gate:
            with sub_scope("gate"):
                g = jnp.einsum("bse,ehd->bshd", x, weights["wg"])
                ctxv = (ctxv * jax.nn.sigmoid(g.astype(jnp.float32))
                        ).astype(x.dtype)
        return self._project_out(weights, ctxv)

    @sub_scope("project")
    def _project_out(self, weights, ctxv):
        out = jnp.einsum("bqhd,hde->bqe", ctxv, weights["wo"])
        if self.use_bias:
            out = out + weights["bo"]
        return out

    def _all_heads(self, kh, vh):
        """Grouped (B, S, Hkv, D) keys and values as every query head
        reads them, (B, S, H, D): what a whole forward's attention takes
        (serving's forms read the groups where they lie)."""
        group = self.num_heads // self.num_kv_heads
        if group == 1:
            return kh, vh
        return jnp.repeat(kh, group, axis=2), jnp.repeat(vh, group, axis=2)

    def _fused(self, ctx, inputs, weights):
        """The op through the fused kernels (kernels/flash_attention.py:
        no array with two sequence axes reaches HBM), or None where the
        shapes it traces keep the `xla` path: short sequences
        (``fa.engaged``), heads that fill no lane tile, a dtype or a
        mesh the kernels do not take (``fa.supported``)."""
        from ..kernels import flash_attention as fa

        q_in, k_in, v_in = inputs[:3]
        h, d = self.num_heads, self.head_dim
        if self.num_kv_heads != h:
            return None               # the kernels take a key head a query head
        if self.window or self.rotary or self.gate or self.qk_norm_per_head:
            return None               # nor a band, positions or a gate
        if (self.sinks or self.v_head_dim != d
                or self.value_scale is not None):
            return None               # nor a sink, nor two widths a head
        q_shape = q_in.shape[:2] + (h, d)
        k_shape = k_in.shape[:2] + (h, d)
        if not fa.engaged(q_shape[1], k_shape[1], d, self.causal, q_in.dtype):
            return None
        mesh = ctx.mesh
        if mesh is None or mesh.size == 1:
            if not fa.supported(q_shape, k_shape, self.causal, q_in.dtype):
                return None
            # the kernels' own layout, heads side by side on the last
            # axis: the projections write it and read it as they are, so
            # q, k, v, o and their gradients are never transposed

            def packed(x, w, b):
                y = jnp.einsum("bse,ef->bsf", x,
                               weights[w].reshape(x.shape[-1], h * d))
                return y + weights[b].reshape(h * d) if self.use_bias else y

            with sub_scope("project"):
                qp, kp = packed(q_in, "wq", "bq"), packed(k_in, "wk", "bk")
                if self.qk_norm:
                    qp = self._normed(qp, weights["q_norm"])
                    kp = self._normed(kp, weights["k_norm"])
                vp = packed(v_in, "wv", "bv")
            with sub_scope("attend"):
                ctxv = fa.flash_attention_packed(
                    qp, kp, vp, h, causal=self.causal, scale=self.scale)
            with sub_scope("project"):
                out = jnp.einsum("bqf,fe->bqe", ctxv,
                                 weights["wo"].reshape(h * d, self.embed_dim))
                return out + weights["bo"] if self.use_bias else out
        # multi-device: shard_map the kernels over the batch / heads mesh
        # axes (attention is independent across both), so dp x tp
        # configs run them too
        bdim = self.input_shapes[0].dims[0]
        batch_ax = bdim.axis if bdim.is_partitioned else None
        wq = self.weight_shapes.get("wq")
        hdim = wq.dims[1] if wq is not None else None
        heads_ax = (hdim.axis if hdim is not None and hdim.is_partitioned
                    else None)
        if not fa.sharded_supported(q_shape, k_shape, mesh, batch_ax,
                                    heads_ax, self.causal, q_in.dtype):
            return None
        qh, kh, vh = self.project_qkv(weights, *inputs)
        with sub_scope("attend"):
            ctxv = fa.sharded_flash_attention(
                qh, kh, vh, mesh, batch_ax, heads_ax, causal=self.causal,
                scale=self.scale)
        return self.project_out(weights, ctxv, q_in)

    def sees(self, qpos, kpos):
        """Whether a query at ``qpos`` sees a key at ``kpos`` (arrays that
        broadcast): causal, and inside the window where the op has one."""
        seen = kpos <= qpos
        if self.window:
            seen &= qpos - kpos < self.window
        return seen

    def forward(self, ctx, inputs, weights):
        drop = self.dropout if (ctx.training and ctx.rng is not None) else 0.0
        from ..parallel.ring_attention import ring_attention, single_device_attention

        if self.seq_axis is not None and ctx.mesh is not None:
            if self.window or self.sinks:
                raise NotImplementedError(
                    f"{self.name}: an op with a window or sinks is not "
                    f"sequence-sharded")
            # sequence parallelism: exact attention over seq-sharded q/k/v.
            # "ring": collective-permute ring over ICI; "a2a": Ulysses
            # all-to-all head resharding (no reference equivalent —
            # SURVEY.md §5 names these the TPU-native plan)
            from ..parallel.ring_attention import ulysses_attention

            sp = ulysses_attention if self.seq_mode == "a2a" else ring_attention
            path = "ulysses" if self.seq_mode == "a2a" else "ring"
            qh, kh, vh = self.project_qkv(weights, *inputs)
            qkv = (qh,) + self._all_heads(kh, vh)
            with sub_scope("attend"):
                ctxv = sp(*qkv, ctx.mesh, self.seq_axis, causal=self.causal,
                          scale=self.scale, dropout_rate=drop, rng=ctx.rng)
            out = self.project_out(weights, ctxv, inputs[0])
        else:
            # attention dropout keeps the `xla` path: the kernels do not
            # implement it
            out = self._fused(ctx, inputs, weights) if drop == 0.0 else None
            path = "flash"
            if out is None:
                path = "xla"
                qh, kh, vh = self.project_qkv(weights, *inputs)
                qkv = (qh,) + self._all_heads(kh, vh)
                with sub_scope("attend"):
                    ctxv = single_device_attention(
                        *qkv, self.causal, self.scale, drop, ctx.rng,
                        self.window, self.sink(weights))
                out = self.project_out(weights, ctxv, inputs[0])
        # which implementation this lowering took, counted once per trace:
        # the rule is over shapes, and a chip run has to be able to say
        # which one it timed
        from ..obs.metrics import metrics_registry

        metrics_registry().counter(f"attention.path.{path}").inc()
        return [out]

    def propagate(self, input_shapes, strategy):
        out_shapes, weight_shapes = super().propagate(input_shapes, strategy)
        axis_sizes = strategy.get("_axis_sizes", {})
        ax = strategy.get("heads")
        if ax:
            deg = axis_sizes.get(ax, 1)
            if deg > 1 and self.num_kv_heads % deg == 0:
                for wn in ("wq", "wk", "wv"):
                    weight_shapes[wn] = weight_shapes[wn].partitioned(1, deg, ax)
                weight_shapes["wo"] = weight_shapes["wo"].partitioned(0, deg, ax)
                for bn in ("bq", "bk", "bv", "q_norm", "k_norm", "sinks"):
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
        dv = self.v_head_dim
        # q and o (and the gate) over every head, k and v over the
        # key-value heads; keys of d, values of dv
        proj = 2.0 * b * s * e * (
            h * (d + (2 if self.gate else 1) * dv)
            + self.num_kv_heads * (d + dv))
        # logits + context over the keys a query may see: the square, or
        # with a window its band
        keys = min(s, self.window) if self.window else s
        attn = 2.0 * b * h * s * keys * (d + dv)
        return proj + attn


# ---------------------------------------------------------------------------
# latent attention (MLA)
# ---------------------------------------------------------------------------

def rotary_inv_freq(dim: int, theta: float, scaling=None):
    """The ``dim // 2`` rotary frequencies ``theta^(-2i/dim)``; with a
    YaRN dict (``factor``, ``original_max_position_embeddings``,
    ``beta_fast``, ``beta_slow``; Peng et al. 2023) each is blended with
    itself divided by ``factor`` along a linear ramp between the
    dimensions that make ``beta_fast`` and ``beta_slow`` turns over the
    original positions: fast dimensions keep their frequency, slow ones
    are interpolated."""
    import numpy as np

    i = np.arange(0, dim, 2, dtype=np.float64)
    extra = 1.0 / (float(theta) ** (i / dim))
    if not scaling:
        return extra.astype(np.float32)
    factor = float(scaling["factor"])
    orig = float(scaling["original_max_position_embeddings"])

    def turns_dim(turns):
        return (dim * math.log(orig / (turns * 2 * math.pi))
                / (2 * math.log(float(theta))))

    low = max(math.floor(turns_dim(float(scaling.get("beta_fast", 32)))), 0)
    high = min(math.ceil(turns_dim(float(scaling.get("beta_slow", 1)))),
               dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return (extra / factor * ramp + extra * (1.0 - ramp)).astype(np.float32)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def apply_rotary(x, positions, inv_freq, rotary_dim=None,
                 interleaved=False):
    """Rotate the pairs ``(x[i], x[i + d/2])`` of the last axis by
    ``positions * inv_freq[i]``. ``x``: (..., S, [H,] d) with
    ``positions`` (..., S); float32 inside, the input's dtype out. With
    ``rotary_dim`` the first ``rotary_dim`` values of the last axis are
    rotated (their own halves paired) and the rest pass as they are.
    ``interleaved``: the pairs are ``(x[2i], x[2i + 1])``, each rotated
    where it lies."""
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq)
    if x.ndim == ang.ndim + 1:                   # a heads axis before d
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    if interleaved:
        if rotary_dim is not None:
            raise ValueError("interleaved pairs over part of a head are "
                             "not built")
        pairs = xf.reshape(xf.shape[:-1] + (xf.shape[-1] // 2, 2))
        a, b = pairs[..., 0], pairs[..., 1]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         axis=-1).reshape(x.shape).astype(x.dtype)
    rest = []
    if rotary_dim is not None and rotary_dim < x.shape[-1]:
        xf, rest = xf[..., :rotary_dim], [xf[..., rotary_dim:]]
    a, b = jnp.split(xf, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin] + rest,
                           axis=-1).astype(x.dtype)


@register_op
class CompressedConvAttention(Op):
    """Causal grouped-head self-attention whose queries and keys are mixed
    along the sequence before they meet (compressed convolutional
    attention, Zyphra 2025; no reference analog). ``H`` query heads and
    ``G`` key-value heads of ``d``, ``H / G`` query heads a key-value
    head; per token ``u`` (zeros stand before position 0):

    1. :meth:`project`: ``z = [u W_q ; u W_k]``, ``(H + G) d`` wide;
    2. :meth:`mix`, over a window of the ``taps0 + taps1 - 2`` rows before
       the tokens and the tokens: a depthwise causal convolution of
       ``taps0`` taps and a bias, then one of ``taps1`` taps grouped by
       head (a ``d x d`` matrix a tap and head) and a bias; to the result
       the mean of each query head's and its key head's UNMIXED values is
       added (for a key head, the mean over its query heads of those
       means); each head is scaled to the length ``sqrt(d)``, a key head
       times ``exp(temp)`` besides (one learned temperature a key head);
       the first ``rotary_dim`` values of each head are rotated by the
       positions;
    3. :meth:`values`: a value head's first half is ``u W_v1``'s, its
       second half ``W_v2``'s of the token BEFORE;
    4. causal attention ``softmax(q k / sqrt(d)) v`` and :meth:`out`.

    What a cache keeps: keys (rotated) and values a token, and a request
    the last ``tail`` rows of ``z`` and the last token's ``u W_v2``
    (serving/cache_entry.py ``CcaEntry``). Inputs: the activations (B, S,
    E) and the graph's int32 positions (B, S)."""

    op_type = OpType.COMPRESSED_CONV_ATTENTION

    def __init__(self, layer, input_shapes):
        super().__init__(layer, input_shapes)
        a = self.attrs
        self.embed_dim: int = input_shapes[0].sizes[-1]
        self.num_heads = int(a["num_heads"])
        self.num_kv_heads = int(a["num_kv_heads"])
        self.head_dim = int(a["head_dim"])
        if self.num_heads % self.num_kv_heads:
            raise ValueError(f"{self.num_heads} query heads are not "
                             f"{self.num_kv_heads} equal groups")
        if self.head_dim % 2:
            raise ValueError("a value head is two halves")
        self.taps = (int(a.get("taps0", 2)), int(a.get("taps1", 2)))
        if min(self.taps) < 1:
            raise ValueError(f"taps {self.taps}: a convolution has a tap")
        # rows of z before a token that its mixed q and k read
        self.tail = self.taps[0] + self.taps[1] - 2
        self.channels = (self.num_heads + self.num_kv_heads) * self.head_dim
        self.rotary_dim = int(a.get("rotary_dim") or self.head_dim)
        self.inv_freq = rotary_inv_freq(self.rotary_dim,
                                        float(a.get("rotary", 10000.0)))
        self.scale = 1.0 / math.sqrt(self.head_dim)
        self.causal, self.window = True, None

    def infer_output_shapes(self):
        return [(self.input_shapes[0].sizes, self.input_shapes[0].dtype)]

    def weight_specs(self) -> List[WeightSpec]:
        dt = self.input_shapes[0].dtype
        init = self.attrs.get("kernel_initializer") or DefaultWeightInitializer()
        gain = self.attrs.get("gain_initializer") or ConstantInitializer(1.0)
        zero = self.attrs.get("bias_initializer") or ZeroInitializer()
        e, h, g, d = (self.embed_dim, self.num_heads, self.num_kv_heads,
                      self.head_dim)
        t0, t1 = self.taps
        return [
            WeightSpec("wq", (e, h, d), dt, init),
            WeightSpec("wk", (e, g, d), dt, init),
            WeightSpec("wv1", (e, g, d // 2), dt, init),
            WeightSpec("wv2", (e, g, d // 2), dt, init),
            WeightSpec("wo", (h, d, e), dt, init),
            WeightSpec("conv0", (t0, self.channels), dt, gain),
            WeightSpec("conv0_b", (self.channels,), dt, zero,
                       weight_decay=False),
            WeightSpec("conv1", (h + g, t1, d, d), dt, init),
            WeightSpec("conv1_b", (h + g, d), dt, zero, weight_decay=False),
            WeightSpec("temp", (g,), dt, zero, weight_decay=False),
        ]

    # ---- the pieces serving composes (serving/cache_entry.py) -------------
    @sub_scope("project")
    def project(self, weights, u):
        """``u`` (B, S, E) -> ``z`` (B, S, (H + G) d): the queries' heads,
        then the keys', before any mixing."""
        b, s, _ = u.shape
        q = jnp.einsum("bse,ehd->bshd", u, weights["wq"])
        k = jnp.einsum("bse,ehd->bshd", u, weights["wk"])
        return jnp.concatenate([q, k], axis=2).reshape(b, s, self.channels)

    @sub_scope("mix")
    def mix(self, weights, window, positions):
        """``window`` (B, tail + S, channels): the ``tail`` rows of ``z``
        before the S tokens at ``positions`` (B, S), then theirs; a row
        before position 0 holds zeros, and so does what the first
        convolution would make of it. Returns the (B, S, H, d) queries and
        (B, S, G, d) keys as they are attended, float32 inside, the
        window's dtype out."""
        f32 = jnp.float32
        h, g, d = self.num_heads, self.num_kv_heads, self.head_dim
        t0, t1 = self.taps
        b, s = positions.shape
        z = window.astype(f32)
        n1 = s + t1 - 1                      # rows of the first one's output
        w0 = weights["conv0"].astype(f32)
        z1 = sum(w0[j] * z[:, j:j + n1] for j in range(t0)) \
            + weights["conv0_b"].astype(f32)
        # its row i stands at position positions[:, 0] - (t1 - 1) + i
        at = positions[:, :1] - (t1 - 1) + jax.lax.iota(jnp.int32, n1)[None]
        z1 = jnp.where((at >= 0)[..., None], z1, 0.0).reshape(
            b, n1, h + g, d)
        w1 = weights["conv1"].astype(f32)
        z2 = sum(jnp.einsum("bshi,hio->bsho", z1[:, j:j + s], w1[:, j],
                            precision=jax.lax.Precision.HIGHEST)
                 for j in range(t1)) + weights["conv1_b"].astype(f32)
        raw = z[:, self.tail:].reshape(b, s, h + g, d)
        q_raw = raw[:, :, :h].reshape(b, s, g, h // g, d)
        m_q = 0.5 * (q_raw + raw[:, :, h:, None])
        q = z2[:, :, :h] + m_q.reshape(b, s, h, d)
        k = z2[:, :, h:] + m_q.mean(3)

        def unit(x):
            return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True)
                                     + 1e-12) * math.sqrt(d)

        q = unit(q)
        k = unit(k) * jnp.exp(weights["temp"].astype(f32))[:, None]
        q = apply_rotary(q, positions, self.inv_freq, self.rotary_dim)
        k = apply_rotary(k, positions, self.inv_freq, self.rotary_dim)
        return q.astype(window.dtype), k.astype(window.dtype)

    @sub_scope("project")
    def values(self, weights, u, prev):
        """``u`` (B, S, E) and ``prev`` (B, G d/2), the token before the
        first one's ``u W_v2`` (zeros before position 0) -> the (B, S, G,
        d) values and every token's own ``u W_v2`` (B, S, G d/2)."""
        b, s, _ = u.shape
        g, half = self.num_kv_heads, self.head_dim // 2
        now = jnp.einsum("bse,egd->bsgd", u, weights["wv1"])
        own = jnp.einsum("bse,egd->bsgd", u, weights["wv2"])
        before = jnp.concatenate(
            [prev.reshape(b, 1, g, half).astype(own.dtype), own[:, :-1]],
            axis=1)
        return (jnp.concatenate([now, before], axis=-1),
                own.reshape(b, s, g * half))

    @sub_scope("out")
    def out(self, weights, ctxv):
        return jnp.einsum("bqhd,hde->bqe", ctxv, weights["wo"])

    def sees(self, qpos, kpos):
        return kpos <= qpos

    def whole(self, weights, u, positions):
        """Whole sequences from nothing: the (B, S, H, d) queries and the
        (B, S, G, d) keys and values as they are attended."""
        z = self.project(weights, u)
        q, k = self.mix(weights, jnp.pad(z, ((0, 0), (self.tail, 0), (0, 0))),
                        positions)
        v, _ = self.values(weights, u, jnp.zeros(
            (u.shape[0], self.num_kv_heads * self.head_dim // 2), u.dtype))
        return q, k, v

    def forward(self, ctx, inputs, weights):
        from ..parallel.ring_attention import single_device_attention

        u, positions = inputs
        q, k, v = self.whole(weights, u, positions)
        group = self.num_heads // self.num_kv_heads
        with sub_scope("attend"):
            ctxv = single_device_attention(
                q, jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2),
                True, self.scale, 0.0, None, None)
        return [self.out(weights, ctxv)]

    def flops(self) -> float:
        b, s = self.input_shapes[0].sizes[:2]
        e, h, g, d = (self.embed_dim, self.num_heads, self.num_kv_heads,
                      self.head_dim)
        proj = 2.0 * b * s * e * d * (2 * h + 2 * g)
        conv = 2.0 * b * s * (h + g) * self.taps[1] * d * d
        return proj + conv + 4.0 * b * h * s * s * d


@dataclasses.dataclass(frozen=True)
class Indexer:
    """A latent-attention op's learned indexer (DeepSeek-V3.2's sparse
    attention, with keys pooled over positions): ``heads`` query heads of
    ``dim`` against ONE key head, rotary over the first ``rope_dim``
    dimensions of both (interleaved pairs, base ``theta``); keys are kept
    as the MEAN of each complete pool of ``pool`` positions; a query at
    position ``t`` always reads the pool that holds ``t`` and, of the
    pools before it, the ``picks`` with the highest ``sum_j w_j relu(q_j .
    K_p)``: ``topk`` rows in all. The selection comes as ids
    (:meth:`picked`: serving gathers by them) or as a mask (:meth:`taken`:
    the plain whole-sequence form attends under it)."""

    heads: int
    dim: int
    rope_dim: int
    pool: int
    topk: int
    theta: float = 10000.0
    eps: float = 1e-6          # under the root of the key's LayerNorm

    def __post_init__(self):
        if (self.topk % self.pool or self.topk < 2 * self.pool
                or self.rope_dim % 2 or self.rope_dim > self.dim):
            raise ValueError(f"an indexer of {self}")

    @property
    def picks(self) -> int:
        """Pools a query takes by their scores, beside the one it is in."""
        return self.topk // self.pool - 1

    @property
    def inv_freq(self):
        return rotary_inv_freq(self.rope_dim, self.theta)

    def turn(self, x, positions):
        """Rotary over the first ``rope_dim`` of the last axis."""
        r = self.rope_dim
        return jnp.concatenate(
            [apply_rotary(x[..., :r], positions, self.inv_freq,
                          interleaved=True), x[..., r:]], axis=-1)

    def pooled(self, keys):
        """(B, S, dim) keys, S whole pools -> (B, S / pool, dim): each
        pool's mean, float32 inside."""
        b, s, d = keys.shape
        return keys.astype(jnp.float32).reshape(
            b, s // self.pool, self.pool, d).mean(2).astype(keys.dtype)

    def scores(self, q, w, pooled, qpos):
        """``q`` (B, Sq, heads, dim), ``w`` (B, Sq, heads) float32,
        ``pooled`` (B, P, dim), ``qpos`` (B, Sq) -> (B, Sq, P) float32:
        ``sum_j w_j relu(q_j . K_p)`` where pool p lies wholly before the
        query's own pool, else -inf."""
        dots = jnp.einsum("bqhd,bpd->bqhp", q, pooled,
                          preferred_element_type=jnp.float32)
        sc = jnp.einsum("bqhp,bqh->bqp", jnp.maximum(dots, 0.0), w)
        before = (jax.lax.iota(jnp.int32, pooled.shape[1])[None, None, :]
                  < (qpos // self.pool)[..., None])
        return jnp.where(before, sc, -jnp.inf)

    def picked(self, scores):
        """``scores`` (B, Sq, P) -> the pools a query takes by their
        scores, (B, Sq, count) int32, ``count = min(picks, P)``, highest
        first, -1 where it has fewer before it; ties go to the lower pool
        (``lax.top_k``'s order; as rows: over three axes the chip's
        compiler sorts several times slower)."""
        count = min(self.picks, scores.shape[-1])
        vals, ids = jax.lax.top_k(
            scores.reshape(-1, scores.shape[-1]), count)
        lead = scores.shape[:-1] + (count,)
        return jnp.where(vals > -jnp.inf, ids.astype(jnp.int32),
                         -1).reshape(lead)

    def taken(self, scores):
        """The same selection as a mask, (B, Sq, P) bool (the plain form's:
        a compare of every pick with every pool)."""
        at = jax.lax.iota(jnp.int32, scores.shape[-1])
        return (self.picked(scores)[..., None] == at).any(-2)

    def sees(self, taken, qpos, kpos):
        """``taken`` (B, Sq, P) -> (B, Sq, Sk) bool: whether the query at
        ``qpos`` (B, Sq) reads the key at ``kpos`` (B, Sk): in a pool it
        took or in its own, and not after it."""
        kp = kpos // self.pool
        picked = jnp.take_along_axis(
            taken, jnp.broadcast_to(
                jnp.clip(kp, 0, taken.shape[-1] - 1)[:, None, :],
                taken.shape[:2] + kp.shape[-1:]), axis=-1)
        own = kp[:, None, :] == (qpos // self.pool)[..., None]
        return (picked | own) & (kpos[:, None, :] <= qpos[..., None])


@register_op
class LatentAttention(Op):
    """Causal self-attention over a low-rank latent (multi-head latent
    attention, DeepSeek-V2 2024; no reference analog). Per token:

    * ``cq = norm(x W_qa)``; ``[q_nope | q_rope] = cq W_qb`` per head;
    * ``[ckv | kr] = x W_kva``; ``c = norm(ckv)``; one ``k_rope =
      rope(kr)`` for all heads, ``q_rope = rope(q_rope)``;
    * ``[k_nope | v] = c W_kvb`` per head; scores ``(q_nope . k_nope +
      q_rope . k_rope) * scale``, causal softmax, ``sum p v``, ``W_o``;
    * ``q_lora_rank=None``: ``[q_nope | q_rope] = x W_q`` direct, no
      low-rank step and no norm; ``output_gate="head"``: each head's
      ``sum p v`` times ``sigmoid(x w_gate,h)`` before ``W_o`` (``wg`` is
      (E, H)); ``rope_interleaved``: the rotary pairs are ``(2i, 2i +
      1)``; ``qk_rope_head_dim=0``: no rotary part at all, the row is
      the latent alone;
    * ``indexer`` (:class:`Indexer`; needs a query rank): ``q_I = cq
      W_qI`` per indexer head, ``k_I = layer_norm(x W_kI)`` one head,
      ``w = (x W_w) heads^-1/2 dim^-1/2``, both rotated; a query reads
      only the rows of the pools it takes (:meth:`Indexer.sees`).

    What a cache has to keep of a token is the row ``[c | k_rope]``
    (:meth:`queries_and_rows`), not keys and values:
    serving/cache_entry.py prefills in this expanded form and decodes in
    the absorbed one (``W_kvb`` folded into the query and the output).
    Inputs: the activations (B, S, E) and the graph's int32 positions (B, S).
    Matrices keep 2-D shapes, heads side by side in the columns, so a
    loader can hand them over as stored."""

    op_type = OpType.LATENT_ATTENTION

    def __init__(self, layer, input_shapes):
        super().__init__(layer, input_shapes)
        a = self.attrs
        self.embed_dim: int = input_shapes[0].sizes[-1]
        self.num_heads: int = int(a["num_heads"])
        # None: the queries are projected in one step
        self.q_rank: Optional[int] = (None if a.get("q_lora_rank") is None
                                      else int(a["q_lora_rank"]))
        self.output_gate = a.get("output_gate")
        if self.output_gate not in (None, "head"):
            raise ValueError(f"output_gate {self.output_gate!r} is neither "
                             f"None nor 'head'")
        self.rope_interleaved = bool(a.get("rope_interleaved", False))
        self.kv_rank: int = int(a["kv_lora_rank"])
        self.nope_dim: int = int(a["qk_nope_head_dim"])
        self.rope_dim: int = int(a["qk_rope_head_dim"])
        self.v_dim: int = int(a["v_head_dim"])
        self.eps = float(a.get("eps", 1e-6))
        self.max_positions = int(a["max_positions"])
        scaling = a.get("rope_scaling") or None
        self.inv_freq = rotary_inv_freq(self.rope_dim,
                                        float(a.get("rope_theta", 10000.0)),
                                        scaling)
        self.scale = (self.nope_dim + self.rope_dim) ** -0.5
        if scaling and float(scaling.get("mscale_all_dim", 0)):
            m = yarn_mscale(float(scaling["factor"]),
                            float(scaling["mscale_all_dim"]))
            self.scale *= m * m
        # the row a cache keeps of a token
        self.row_width = self.kv_rank + self.rope_dim
        self.causal = True
        ix = a.get("indexer") or None
        self.indexer: Optional[Indexer] = None if ix is None else Indexer(
            **{k: (float(v) if k in ("theta", "eps") else int(v))
               for k, v in ix.items()})
        if self.indexer is not None and self.q_rank is None:
            raise ValueError(f"{self.name}: an indexer reads the queries' "
                             f"low-rank step; q_lora_rank is None")

    def infer_output_shapes(self):
        return [(self.input_shapes[0].sizes, self.input_shapes[0].dtype)]

    def weight_specs(self) -> List[WeightSpec]:
        dt = self.input_shapes[0].dtype
        init = self.attrs.get("kernel_initializer") or DefaultWeightInitializer()
        gain = self.attrs.get("gain_initializer") or ConstantInitializer(1.0)
        e, h = self.embed_dim, self.num_heads
        qk = h * (self.nope_dim + self.rope_dim)
        queries = [WeightSpec("wq", (e, qk), dt, init)] \
            if self.q_rank is None else [
            WeightSpec("wq_a", (e, self.q_rank), dt, init),
            WeightSpec("q_norm", (self.q_rank,), dt, gain, weight_decay=False),
            WeightSpec("wq_b", (self.q_rank, qk), dt, init)]
        gate = [WeightSpec("wg", (e, h), dt, init)] if self.output_gate \
            else []
        ix = self.indexer
        if ix is not None:
            zero = self.attrs.get("bias_initializer") or ZeroInitializer()
            gate = gate + [
                WeightSpec("wq_i", (self.q_rank, ix.heads * ix.dim), dt, init),
                WeightSpec("wk_i", (e, ix.dim), dt, init),
                WeightSpec("k_norm_i", (ix.dim,), dt, gain,
                           weight_decay=False),
                WeightSpec("k_bias_i", (ix.dim,), dt, zero,
                           weight_decay=False),
                WeightSpec("ww_i", (e, ix.heads), dt, init)]
        return queries + [
            WeightSpec("wkv_a", (e, self.kv_rank + self.rope_dim), dt, init),
            WeightSpec("kv_norm", (self.kv_rank,), dt, gain,
                       weight_decay=False),
            WeightSpec("wkv_b", (self.kv_rank,
                                 h * (self.nope_dim + self.v_dim)), dt, init),
        ] + gate + [WeightSpec("wo", (h * self.v_dim, e), dt, init)]

    # ---- the pieces serving composes ------------------------------------
    @sub_scope("project")
    def queries_and_rows(self, weights, x, positions, with_cq=False):
        """``x`` (B, S, E), ``positions`` (B, S) -> ``q_nope`` (B, S, H,
        nope), ``q_rope`` (B, S, H, rope) rotated, and the latent rows
        (B, S, kv_rank + rope): ``[c | k_rope]``; ``with_cq``: also the
        queries' normed low-rank step (B, S, q_rank)."""
        from .norm import rms_norm

        b, s, _ = x.shape
        h = self.num_heads
        cq = None
        if self.q_rank is None:
            q = _mm(x, weights["wq"])
        else:
            cq = rms_norm(_mm(x, weights["wq_a"]), weights["q_norm"],
                          self.eps)
            q = _mm(cq, weights["wq_b"])
        q = q.reshape(b, s, h, self.nope_dim + self.rope_dim)
        q_nope, q_rope = q[..., :self.nope_dim], q[..., self.nope_dim:]
        kva = _mm(x, weights["wkv_a"])
        c = rms_norm(kva[..., :self.kv_rank], weights["kv_norm"], self.eps)
        turn = functools.partial(apply_rotary, positions=positions,
                                 inv_freq=self.inv_freq,
                                 interleaved=self.rope_interleaved)
        if not self.rope_dim:          # no rotary part: the latent alone
            return (q_nope, q_rope, c) + ((cq,) if with_cq else ())
        k_rope = turn(kva[..., self.kv_rank:])
        q_rope = turn(q_rope)
        out = q_nope, q_rope, jnp.concatenate([c, k_rope], axis=-1)
        return out + ((cq,) if with_cq else ())

    @sub_scope("select")
    def index(self, weights, x, cq, positions):
        """The indexer's side of a token: ``q_I`` (B, S, heads, dim)
        rotated, ``w`` (B, S, heads) float32, and its key ``k_I`` (B, S,
        dim), normed and rotated, in the activations' dtype."""
        ix = self.indexer
        b, s, _ = x.shape
        f32 = jnp.float32
        q = ix.turn(_mm(cq, weights["wq_i"]).reshape(b, s, ix.heads, ix.dim),
                    positions)
        k = jnp.dot(x, weights["wk_i"], preferred_element_type=f32)
        k = k - k.mean(-1, keepdims=True)
        k = (k * jax.lax.rsqrt(jnp.mean(k * k, -1, keepdims=True) + ix.eps)
             * weights["k_norm_i"].astype(f32)
             + weights["k_bias_i"].astype(f32))
        k = ix.turn(k, positions).astype(x.dtype)
        w = jnp.dot(x, weights["ww_i"], preferred_element_type=f32) \
            * (ix.heads * ix.dim) ** -0.5
        return q, w, k

    def sees(self, qpos, kpos):
        return kpos <= qpos

    def gated(self, weights, x, o):
        """The heads' outputs ``o`` (B, S, H, v) as ``W_o`` takes them:
        times ``sigmoid(x w_gate)``, one a head, where the op has an
        output gate; as they are where it has none."""
        if not self.output_gate:
            return o
        with sub_scope("gate"):
            z = jnp.dot(x, weights["wg"], preferred_element_type=jnp.float32)
            return (o * jax.nn.sigmoid(z)[..., None]).astype(o.dtype)

    def kvb_heads(self, weights):
        """``W_kvb`` as (kv_rank, H, nope + v): its key part is
        ``[..., :nope]``, its value part ``[..., nope:]``."""
        return weights["wkv_b"].reshape(self.kv_rank, self.num_heads,
                                        self.nope_dim + self.v_dim)

    @sub_scope("attend")
    def attend_expanded(self, weights, q_nope, q_rope, rows, mask, x=None):
        """Attention in the expanded form over the rows given (queries
        (B, Sq, H, ·), rows (B, Sk, width), ``mask`` (Sq, Sk) or (B, Sq,
        Sk) True where a query sees a key; ``x`` (B, Sq, E) the op's
        input, which an output gate reads); returns (B, Sq, E)."""
        c, k_rope = rows[..., :self.kv_rank], rows[..., self.kv_rank:]
        kv = jnp.einsum("bkc,chd->bkhd", c, self.kvb_heads(weights),
                        preferred_element_type=jnp.float32).astype(c.dtype)
        k_nope, v = kv[..., :self.nope_dim], kv[..., self.nope_dim:]
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope,
                            preferred_element_type=jnp.float32)
        if self.rope_dim:
            scores = scores + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope,
                                         preferred_element_type=jnp.float32)
        scores = scores * self.scale
        mask = mask[None, None] if mask.ndim == 2 else mask[:, None]
        probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
        ctxv = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v,
                          preferred_element_type=jnp.float32).astype(v.dtype)
        b, sq = ctxv.shape[:2]
        ctxv = self.gated(weights, x, ctxv)
        return _mm(ctxv.reshape(b, sq, self.num_heads * self.v_dim),
                   weights["wo"])

    def selected(self, weights, x, positions, before=None, offset=0):
        """The op under its indexer over whole sequences, every query's
        selection as a mask over the keys (the plain form: serving's
        kinds read the taken rows alone): ``x`` (B, S, E) at ``positions``
        (B, S) = ``offset ..``, behind ``before`` = (rows (B, L, width),
        index keys (B, L, dim)) that hold positions ``0 .. offset - 1``
        (None: nothing, L = S), L whole pools. Returns (out (B, S, E),
        (rows, index keys) with the block written at ``offset``, the pools
        each query took by their scores, (B, S, L / pool) bool)."""
        ix = self.indexer
        q_nope, q_rope, rows, cq = self.queries_and_rows(
            weights, x, positions, with_cq=True)
        qi, w, ki = self.index(weights, x, cq, positions)
        if before is None:
            pad = ((0, 0), (0, -x.shape[1] % ix.pool), (0, 0))
            rows, ki = jnp.pad(rows, pad), jnp.pad(ki, pad)
        else:
            with sub_scope("write"):
                rows, ki = (jax.lax.dynamic_update_slice(
                    old, new.astype(old.dtype), (0, offset, 0))
                    for old, new in zip(before, (rows, ki)))
        with sub_scope("select"):
            taken = ix.taken(ix.scores(qi, w, ix.pooled(ki), positions))
            kpos = jnp.broadcast_to(
                jax.lax.iota(jnp.int32, rows.shape[1])[None],
                rows.shape[:2])
            mask = ix.sees(taken, positions, kpos)
        out = self.attend_expanded(weights, q_nope, q_rope,
                                   rows.astype(x.dtype), mask, x)
        return out, (rows, ki), taken

    def forward(self, ctx, inputs, weights):
        x, positions = inputs
        if self.indexer is not None:
            return [self.selected(weights, x, positions)[0]]
        q_nope, q_rope, rows = self.queries_and_rows(weights, x, positions)
        s = x.shape[1]
        pos = jax.lax.iota(jnp.int32, s)
        return [self.attend_expanded(weights, q_nope, q_rope, rows,
                                     pos[None, :] <= pos[:, None], x)]

    def flops(self) -> float:
        b, s = self.input_shapes[0].sizes[:2]
        e, h = self.embed_dim, self.num_heads
        qk = self.nope_dim + self.rope_dim
        proj = 2.0 * b * s * (
            (e * h * qk if self.q_rank is None
             else e * self.q_rank + self.q_rank * h * qk)
            + e * (self.kv_rank + self.rope_dim)
            + self.kv_rank * h * (self.nope_dim + self.v_dim)
            + h * self.v_dim * e)
        ix = self.indexer
        if ix is None:
            return proj + 2.0 * b * h * s * s * (qk + self.v_dim)
        # the indexer's projections, its scores over the pooled keys, and
        # the attention over at most ``topk`` rows a query
        index = 2.0 * b * s * (self.q_rank * ix.heads * ix.dim
                               + e * (ix.dim + ix.heads)
                               + ix.heads * ix.dim * (s // ix.pool) / 2)
        return (proj + index
                + 2.0 * b * h * s * min(s, ix.topk) * (qk + self.v_dim))


def _mm(x, w):
    """A product accumulated in float32, in the activations' dtype out."""
    return jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype)
