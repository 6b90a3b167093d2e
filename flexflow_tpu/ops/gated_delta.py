"""GatedDeltaNet: a linear-attention layer whose memory is a state of
fixed size a sequence (Gated DeltaNet, Yang, Kautz & Hatamizadeh 2024;
no reference analog).

``x`` is (B, S, E); H heads, key width ``d_k``, value width ``d_v``.

* ``q = x W_q``, ``k = x W_k`` (each ``H d_k`` wide), ``v = x W_v``
  (``H d_v``). Each channel ``c`` of ``[q | k | v]`` goes through a causal
  depthwise convolution of ``K`` taps and SiLU: ``u_t[c] = silu(sum_{j <
  K} w[j, c] in_{t-K+1+j}[c])``, zeros before the sequence. Per head, q
  and k are L2-normalised over ``d_k`` and q is scaled by ``d_k^-1/2``.
* ``beta_t = sigmoid(x_t W_b)`` per head, doubled where
  ``allow_neg_eigval`` (beta in (0, 2)); ``g_t = -exp(A_log) *
  softplus(x_t W_a + dt_bias)`` per head, ``alpha_t = exp(g_t)``.
* the state ``S`` of a head is ``(d_k, d_v)``, zero before the sequence::

      S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
      o_t = S_t^T q_t

  that is ``S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t
  v_t^T``.
* out: ``y_t = (RMSNorm_{d_v}(o_t) * silu(x_t W_g)) W_o``, the norm's gain
  of width ``d_v`` shared by the heads.

What a sequence keeps of this layer is ``S`` (``H d_k d_v`` numbers) and
the last ``K - 1`` inputs of the convolution: one row a REQUEST, not a
row a token (serving/cache_entry.py ``StateEntry``).

Two forms compute the recurrence. :func:`chunked_delta_rule` takes whole
sequences, ``CHUNK`` tokens at a time: within a chunk everything is a
matrix product (the chunk's updates ``u_t = beta_t (v_t - alpha_t
S_{t-1}^T k_t)`` solve a unit lower-triangular system that does not
involve the incoming state: the WY form), between chunks the state is
carried by ``lax.scan``. A layer runs it, between the norms on either
side of it, by the path the shapes choose: :func:`fused_rule`
(``kernels/gated_delta.py`` ``gated_delta_chunks``, one kernel a call)
where :func:`delta_rule_path` says ``"kernel"``, else :func:`scan_rule`,
the jnp form, which stays as the kernel's reference; both take a decay
that is a number a head (:func:`chunked_delta_rule`) or one a key
channel (:func:`chunked_channel_rule`, :class:`KimiDeltaAttention`).
``kernels/gated_delta.py`` also takes one token (``delta_rule_step`` in
jnp, ``gated_delta_decode`` the kernel). All are float32 with products
at ``highest``; the projections around them are in the activations'
dtype with float32 accumulation.
"""

from __future__ import annotations

import functools
from typing import List

import jax
import jax.numpy as jnp

from ..core.op import Op, WeightSpec, register_op, sub_scope
from ..ffconst import OpType
from ..kernels import gated_delta as kernel
from ..runtime.initializer import (ConstantInitializer,
                                   DefaultWeightInitializer, ZeroInitializer)
from .attention import _mm
from .norm import rms_norm

CHUNK = 64  # tokens a step of the whole-sequence form's scan
_HI = jax.lax.Precision.HIGHEST


def _unit_lower_inverse(low):
    """``(I + low)^-1`` for strictly lower-triangular ``low`` (..., C, C),
    by forward substitution a row at a time: row ``i`` of the inverse is
    ``e_i - low[i, :i] @ inverse[:i]``."""
    c = low.shape[-1]
    eye = jnp.eye(c, dtype=low.dtype)

    def row(i, inv):
        li = jax.lax.dynamic_index_in_dim(low, i, axis=-2, keepdims=False)
        new = eye[i] - jnp.einsum("...j,...jc->...c", li, inv, precision=_HI)
        return jax.lax.dynamic_update_index_in_dim(inv, new, i, axis=-2)

    # rows below i are still zero when row i is made, and low[i, j >= i] is
    # zero anyway
    return jax.lax.fori_loop(0, c, row, jnp.zeros_like(low))


def chunked_delta_rule(q, k, v, g, beta, state):
    """The gated delta rule over whole sequences. ``q``, ``k`` (B, S, H,
    d_k), ``v`` (B, S, H, d_v), ``g`` = log alpha and ``beta`` (B, S, H),
    ``state`` (B, H, d_k, d_v) the state before the sequence, all float32.
    A position with ``g = 0`` and ``beta = 0`` leaves the state as it was.
    Returns (o (B, S, H, d_v), the state after position S - 1)."""
    b, s, h, dk = q.shape
    q, k, v, g, beta = _in_chunks(q, k, v, g, beta)
    gc = jnp.cumsum(g, axis=-1)                              # (n, B, H, C)
    idx = jax.lax.iota(jnp.int32, CHUNK)
    lower = idx[:, None] >= idx[None, :]
    # decay[t, i] = prod_{i < j <= t} alpha_j, for i <= t
    decay = jnp.exp(jnp.where(lower, gc[..., :, None] - gc[..., None, :],
                              -jnp.inf))
    kk = jnp.einsum("...td,...id->...ti", k, k, precision=_HI)
    low = beta[..., None] * kk * jnp.where(idx[:, None] > idx[None, :],
                                           decay, 0.0)
    inv = _unit_lower_inverse(low)
    since = jnp.exp(gc)               # the decay since the chunk's start
    # u_t = uv_t - w_t S_0: what position t adds to the state as k_t u_t^T
    uv = jnp.einsum("...ti,...id->...td", inv, beta[..., None] * v,
                    precision=_HI)
    w = jnp.einsum("...ti,...id->...td", inv, (beta * since)[..., None] * k,
                   precision=_HI)
    qk = jnp.einsum("...td,...id->...ti", q, k, precision=_HI) * decay
    q_in = since[..., None] * q                              # against S_0
    k_out = jnp.exp(gc[..., -1:] - gc)[..., None] * k        # up to the end
    a_end = since[..., -1][..., None, None]
    return _carry_chunks(state, (uv, w, qk, q_in, k_out, a_end), b, s, h)


def _in_chunks(*arrays):
    """Each of ``arrays`` (B, S, H, ...) padded with zeros to whole chunks
    and cut into them: (n, B, H, CHUNK, ...)."""
    b, s = arrays[0].shape[:2]
    pad = -s % CHUNK
    if pad:
        arrays = tuple(jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) *
                               (a.ndim - 2)) for a in arrays)
    n = (s + pad) // CHUNK

    def chunks(a):                    # (B, n C, H, ...) -> (n, B, H, C, ...)
        a = a.reshape((b, n, CHUNK) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    return tuple(map(chunks, arrays))


def _carry_chunks(state, chunks, b, s, h):
    """The state carried over the chunks (the leading axis of each of
    ``chunks`` = (uv, w, qk, q_in, k_out, a_end), what a chunk needs that
    does not involve the incoming state); returns (o (B, S, H, d_v), the
    state after the last)."""
    def step(st, xs):
        uv_c, w_c, qk_c, q_c, k_c, a_c = xs
        u = uv_c - jnp.einsum("...td,...dv->...tv", w_c, st, precision=_HI)
        o = (jnp.einsum("...td,...dv->...tv", q_c, st, precision=_HI)
             + jnp.einsum("...ti,...iv->...tv", qk_c, u, precision=_HI))
        st = a_c * st + jnp.einsum("...td,...tv->...dv", k_c, u,
                                   precision=_HI)
        return st, o

    state, o = jax.lax.scan(step, state, chunks)
    n = o.shape[0]
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)            # (B, n, C, H, dv)
    return o.reshape(b, n * CHUNK, h, -1)[:, :s], state


SUB = kernel.SUB  # tokens a sub-chunk of the per-channel form (see there)


def chunked_channel_rule(q, k, v, g, beta, state):
    """:func:`chunked_delta_rule` with a decay a key CHANNEL: ``g`` = log
    alpha is (B, S, H, d_k) and the state's row ``d`` decays by
    ``alpha_t[d]``::

        S' = diag(alpha_t) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T

    (the reference of the kernel's form with such a decay, and what
    runs where :func:`delta_rule_path` says ``"scan"``.)

    One decay can no longer be pulled out of a chunk's products: the
    pair's ``sum_d k_t[d] k_i[d] exp(G_t[d] - G_i[d])`` (``G`` the log-decay
    summed from the chunk's start) factorised as ``(k_t exp G_t) . (k_i
    exp -G_i)`` overflows float32 once ``-G_i`` passes 88, which a gate
    bounded below by -5 a token does after 17 tokens. So the products are
    taken against the start of the ROW's sub-chunk of ``SUB`` tokens: row
    ``t`` of sub-chunk ``a`` is ``k_t exp(G_t - G^a)`` (at most 1) and
    column ``i`` is ``k_i exp(G^a - G_i)``, at most 1 for a column of an
    earlier sub-chunk and at most ``exp(SUB * 5) = e^80`` within ``a``
    itself; columns behind ``a`` are never read and their exponent is
    struck before it is taken. Safe for ``g >= -88 / SUB = -5.5``
    everywhere; everything else of the chunk (the unit-lower system, what
    meets the incoming state, the carry) is the scalar form's with the
    decay since the chunk's start a vector where it was a number."""
    b, s, h, dk = q.shape
    nsub = CHUNK // SUB
    q, k, v, g, beta = _in_chunks(q, k, v, g, beta)
    gc = jnp.cumsum(g, axis=-2)                          # (n, B, H, C, d_k)
    idx = jax.lax.iota(jnp.int32, CHUNK)
    # G^a: the sum before sub-chunk a's first token, (.., nsub, 1, d_k)
    lead = gc.shape[:-2]
    starts = jnp.concatenate(
        [jnp.zeros(lead + (1, dk), gc.dtype), gc[..., SUB - 1:-1:SUB, :]],
        axis=-2)[..., :, None, :]
    rows = jnp.exp(gc.reshape(lead + (nsub, SUB, dk)) - starts)
    # a column is read by the rows of its own sub-chunk and of later ones
    seen = (idx[None, :] < (jax.lax.iota(jnp.int32, nsub)[:, None] + 1) * SUB)
    cols = k[..., None, :, :] * jnp.exp(jnp.where(
        seen[:, :, None], starts - gc[..., None, :, :], 0.0))
    # (.., nsub, C, d_k)

    def pairs(a):                     # sum_d a_t[d] k_i[d] exp(G_t - G_i)[d]
        a = a.reshape(lead + (nsub, SUB, dk)) * rows
        return jnp.einsum("...atd,...aid->...ati", a, cols,
                          precision=_HI).reshape(lead + (CHUNK, CHUNK))

    low = beta[..., None] * jnp.where(idx[:, None] > idx[None, :], pairs(k),
                                      0.0)
    inv = _unit_lower_inverse(low)
    since = jnp.exp(gc)               # the decay since the chunk's start
    uv = jnp.einsum("...ti,...id->...td", inv, beta[..., None] * v,
                    precision=_HI)
    w = jnp.einsum("...ti,...id->...td", inv, beta[..., None] * since * k,
                   precision=_HI)
    qk = jnp.where(idx[:, None] >= idx[None, :], pairs(q), 0.0)
    q_in = since * q                                         # against S_0
    k_out = jnp.exp(gc[..., -1:, :] - gc) * k                # up to the end
    a_end = since[..., -1, :][..., None]                     # (.., d_k, 1)
    return _carry_chunks(state, (uv, w, qk, q_in, k_out, a_end), b, s, h)


UNIT_EPS = 1e-6  # under the root of a head's L2 norm of q and of k


def unit_heads(q, k, v):
    """q, k (B, S, H, d_k) and v (B, S, H, d_v) as the convolution wrote
    them -> q and k each head's L2-normalised, q scaled by ``d_k^-1/2``,
    and v."""
    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + UNIT_EPS)

    return unit(q) * q.shape[-1] ** -0.5, unit(k), v


def scan_rule(eps: float, q, k, v, g, beta, state, gain):
    """What a layer does between its convolution and its gate, in jnp:
    ``q``, ``k`` (B, S, H d_k) and ``v`` (B, S, H d_v) as the convolution
    wrote them; unit q and k a head, :func:`chunked_delta_rule`, then
    RMSNorm over each head's d_v times ``gain`` (d_v,). Returns (y (B, S,
    H d_v) float32, the state after the sequence)."""
    b, s, h = g.shape[:3]
    rule = chunked_delta_rule if g.ndim == 3 else chunked_channel_rule
    o, state = rule(
        *unit_heads(*(a.reshape(b, s, h, -1) for a in (q, k, v))), g, beta,
        state)
    return rms_norm(o, gain, eps).reshape(v.shape), state


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def fused_rule(eps, q, k, v, g, beta, state, gain):
    """:func:`scan_rule` as one kernel call, the norms on either side of
    the recurrence made on a head's tile in VMEM."""
    return kernel.gated_delta_chunks(q, k, v, g, beta, state,
                                     unit_eps=UNIT_EPS, norm=(gain, eps))


def _fused_fwd(eps, *args):
    return fused_rule(eps, *args), args


def _fused_bwd(eps, args, cotangents):
    # nothing trains through the kernel yet: the jnp form's gradients
    return jax.vjp(functools.partial(scan_rule, eps), *args)[1](cotangents)


fused_rule.defvjp(_fused_fwd, _fused_bwd)


def delta_rule_path(seq: int, heads: int, key_dim: int, value_dim: int,
                    dtype=jnp.float32, channel_decay: bool = False) -> str:
    """How a layer computes its recurrence over these shapes:
    ``"kernel"`` (:func:`fused_rule`) or ``"scan"`` (:func:`scan_rule`).
    A rule over what a trace sees, the backend among it; no knob. One
    kernel takes both decays, a number a head or one a key channel."""
    return "kernel" if kernel.chunks_supported(
        seq, heads, key_dim, value_dim, dtype, channel_decay) else "scan"


@register_op
class GatedDeltaNet(Op):
    """The layer of the module's docstring. Matrices keep 2-D shapes,
    heads side by side in the columns; ``conv`` is (taps, 2 H d_k + H
    d_v), the channels in the order ``[q | k | v]``."""

    op_type = OpType.GATED_DELTA_NET

    def __init__(self, layer, input_shapes):
        super().__init__(layer, input_shapes)
        a = self.attrs
        self.embed_dim: int = input_shapes[0].sizes[-1]
        self.num_heads = int(a["num_heads"])
        self.key_dim = int(a["key_dim"])
        self.value_dim = int(a["value_dim"])
        self.conv_taps = int(a.get("conv_taps", 4))
        self.neg_eigval = bool(a.get("allow_neg_eigval", False))
        self.eps = float(a.get("eps", 1e-6))
        self.channel_decay = False     # one decay a head and a step
        self.qk_width = self.num_heads * self.key_dim
        self.v_width = self.num_heads * self.value_dim
        self.channels = 2 * self.qk_width + self.v_width

    def infer_output_shapes(self):
        return [(self.input_shapes[0].sizes, self.input_shapes[0].dtype)]

    def weight_specs(self) -> List[WeightSpec]:
        dt = self.input_shapes[0].dtype
        init = self.attrs.get("kernel_initializer") or DefaultWeightInitializer()
        gain = self.attrs.get("gain_initializer") or ConstantInitializer(1.0)
        # A_log = 0 and dt_bias = 0 give alpha = exp(-softplus(.)); a
        # loader or a family's draw puts the published ranges in
        gate = self.attrs.get("gate_initializer") or ZeroInitializer()
        e, h = self.embed_dim, self.num_heads
        return [
            WeightSpec("wq", (e, self.qk_width), dt, init),
            WeightSpec("wk", (e, self.qk_width), dt, init),
            WeightSpec("wv", (e, self.v_width), dt, init),
            WeightSpec("wg", (e, self.v_width), dt, init),
            WeightSpec("wa", (e, h), dt, init),
            WeightSpec("wb", (e, h), dt, init),
            WeightSpec("conv", (self.conv_taps, self.channels), dt, init),
            WeightSpec("a_log", (h,), dt, gate, weight_decay=False),
            WeightSpec("dt_bias", (h,), dt, gate, weight_decay=False),
            WeightSpec("norm", (self.value_dim,), dt, gain,
                       weight_decay=False),
            WeightSpec("wo", (self.v_width, e), dt, init),
        ]

    # ---- the pieces serving composes (serving/cache_entry.py) -------------
    @sub_scope("project")
    def conv_inputs(self, weights, x):
        """(B, S, E) -> the convolution's inputs ``[q | k | v]`` (B, S,
        channels), in the activations' dtype: what the tail keeps."""
        return jnp.concatenate([_mm(x, weights[w])
                                for w in ("wq", "wk", "wv")], axis=-1)

    @sub_scope("conv")
    def convolve(self, weights, window):
        """``window`` (B, K - 1 + S, channels): each position's inputs
        behind the ``K - 1`` before it. The sum of K shifted products,
        then SiLU; (B, S, channels) float32."""
        s = window.shape[1] - (self.conv_taps - 1)
        w = weights["conv"].astype(jnp.float32)
        window = window.astype(jnp.float32)
        acc = sum(w[j] * window[:, j:j + s] for j in range(self.conv_taps))
        return jax.nn.silu(acc)

    def split(self, u):
        """The convolved (B, S, channels) -> its q, k and v channels."""
        return (u[..., :self.qk_width],
                u[..., self.qk_width:2 * self.qk_width],
                u[..., 2 * self.qk_width:])

    def heads(self, u):
        """The convolved (B, S, channels) -> q, k (B, S, H, d_k), each
        L2-normalised, q scaled by ``d_k^-1/2``, and v (B, S, H, d_v)."""
        b, s, _ = u.shape
        h, dk = self.num_heads, self.key_dim
        # :meth:`split`'s slices, each reshaped as it is cut: the order
        # the decode program's lowered text has had
        q = u[..., :self.qk_width].reshape(b, s, h, dk)
        k = u[..., self.qk_width:2 * self.qk_width].reshape(b, s, h, dk)
        v = u[..., 2 * self.qk_width:].reshape(b, s, h, self.value_dim)
        return unit_heads(q, k, v)

    @sub_scope("project")
    def gates(self, weights, x):
        """(B, S, E) -> ``g`` = log alpha and beta, (B, S, H) float32."""
        f32 = jnp.float32
        a = jnp.dot(x, weights["wa"], preferred_element_type=f32)
        bl = jnp.dot(x, weights["wb"], preferred_element_type=f32)
        g = -jnp.exp(weights["a_log"].astype(f32)) * jax.nn.softplus(
            a + weights["dt_bias"].astype(f32))
        beta = jax.nn.sigmoid(bl)
        return g, beta * 2.0 if self.neg_eigval else beta

    @sub_scope("project")
    def finish(self, weights, x, o, normed=False):
        """The recurrence's (B, S, H, d_v) float32 outputs -> (B, S, E):
        RMSNorm over ``d_v`` (``normed``: made already, by
        :func:`fused_rule` or :func:`scan_rule`), times ``silu(x W_g)``,
        through ``W_o``."""
        b, s = o.shape[:2]
        z = jnp.dot(x, weights["wg"], preferred_element_type=jnp.float32)
        y = o if normed else rms_norm(o, weights["norm"], self.eps)
        y = y.reshape(b, s, self.v_width)
        return _mm((y * jax.nn.silu(z)).astype(x.dtype), weights["wo"])

    def run(self, weights, x, state, tail, lengths=None):
        """A block of S tokens a row behind ``state`` (B, H, d_k, d_v)
        float32 and ``tail`` (B, K - 1, channels), the last inputs of the
        convolution before the block; ``lengths`` (B,) the tokens of each
        row that count (None: all S). Positions past a row's length leave
        its state as it was (alpha = 1, beta = 0 there) and the new tail
        is taken at the true length. Returns (y (B, S, E), state, tail)."""
        b, s, _ = x.shape
        taps = self.conv_taps
        if lengths is None:
            lengths = jnp.full((b,), s, jnp.int32)
        with sub_scope("conv"):
            window = jnp.concatenate(
                [tail.astype(x.dtype), self.conv_inputs(weights, x)], axis=1)
            q, k, v = self.split(self.convolve(weights, window))
        g, beta = self.gates(weights, x)
        with sub_scope("rule"):
            live = (jax.lax.iota(jnp.int32, s)[None, :]
                    < lengths[:, None])[..., None]
            # by the path the shapes choose; the kernel's gradients are
            # the jnp form's
            rule = (fused_rule if delta_rule_path(
                s, self.num_heads, self.key_dim, self.value_dim, q.dtype,
                self.channel_decay) == "kernel" else scan_rule)
            y, state = rule(
                self.eps, q, k, v,
                jnp.where(live[..., None] if self.channel_decay else live,
                          g, 0.0),
                jnp.where(live, beta, 0.0), state, weights["norm"])
        with sub_scope("conv"):
            # window position p is block position p - (K - 1): the K - 1
            # inputs before position ``length`` start at ``length``
            tail = jax.vmap(lambda w, n: jax.lax.dynamic_slice_in_dim(
                w, n, taps - 1, axis=0))(window, lengths)
        return self.finish(weights, x, y, normed=True), state, tail

    def whole(self, weights, x, lengths=None):
        """Whole sequences from an empty state: :meth:`run` behind zeros."""
        b = x.shape[0]
        return self.run(
            weights, x,
            jnp.zeros((b, self.num_heads, self.key_dim, self.value_dim),
                      jnp.float32),
            jnp.zeros((b, self.conv_taps - 1, self.channels), x.dtype),
            lengths)

    def forward(self, ctx, inputs, weights):
        return [self.whole(weights, inputs[0])[0]]

    def flops(self) -> float:
        b, s = self.input_shapes[0].sizes[:2]
        e, h, dk, dv = (self.embed_dim, self.num_heads, self.key_dim,
                        self.value_dim)
        proj = 2.0 * b * s * e * (2 * self.qk_width + 3 * self.v_width + 2 * h)
        # a chunk of C tokens: k k^T, q k^T and the inverse's two products
        # over (C, C); w S, q S, k^T u and (q k^T) u against the state
        chunk = 2.0 * b * s * h * (CHUNK * (2 * dk + 2 * (dk + dv))
                                   + 3 * dk * dv)
        return proj + chunk + 2.0 * b * s * self.conv_taps * self.channels


@register_op
class KimiDeltaAttention(GatedDeltaNet):
    """Kimi Delta Attention (Kimi Linear, 2025; the linear layers of the
    Ling 3.0 line): :class:`GatedDeltaNet` with a decay a key CHANNEL.
    What differs from it, per token:

    * ``g_t = lower * sigmoid(exp(A_log_h) * (x_t W_f + dt_bias))``, (H,
      d_k) wide: ``W_f`` (E, H d_k) full-rank, ``A_log`` one a head,
      ``dt_bias`` one a channel, ``lower`` < 0 the gate's bound, so
      ``alpha_t = exp(g_t)`` lies in ``(e^lower, 1)`` (the safe gate of the
      public kernels); the state's row ``d`` decays by ``alpha_t[d]``:
      ``S' = diag(alpha_t) S_{t-1}``, then the delta rule as before;
    * ``beta_t = sigmoid(x_t W_b)``, never doubled;
    * out: ``concat_h(RMSNorm_{d_v}(o_t,h) * sigmoid(x_t w_gate,h)) W_o``:
      ONE gate a head (``wg`` is (E, H)), a sigmoid;
    * ``decay_rank``: the decay's projection goes through that rank, ``x
      W_fa W_fb`` (``wf_a`` (E, rank), ``wf_b`` (rank, H d_k)) in ``wf``'s
      place; ``output_gate="channel"``: a gate a value CHANNEL through
      ``gate_rank``, ``sigmoid(x W_ga W_gb)`` (``wg_a`` (E, rank), ``wg_b``
      (rank, H d_v)) in ``wg``'s place (the Kimi Linear form).

    The convolution, the unit q and k, the state's shape and what a
    request keeps are :class:`GatedDeltaNet`'s, so serving stores it as
    that op's :class:`~flexflow_tpu.serving.cache_entry.StateEntry`. The
    whole-sequence form is ``kernels/gated_delta.py``
    ``gated_delta_chunks`` with a ``(d_k,)`` decay a head and token (the
    Pallas call ``channel_delta_chunks``) where :func:`delta_rule_path`
    says ``"kernel"``, else :func:`chunked_channel_rule`, its jnp form
    and reference; one token that module's step with a ``(d_k,)`` decay
    a head."""

    op_type = OpType.KIMI_DELTA_ATTENTION

    def __init__(self, layer, input_shapes):
        super().__init__(layer, input_shapes)
        self.lower_bound = float(self.attrs.get("lower_bound", -5.0))
        if not -88.0 / SUB <= self.lower_bound < 0:
            raise ValueError(
                f"{self.name}: a decay bounded by {self.lower_bound} a "
                f"token leaves what a sub-chunk of {SUB} tokens can hold "
                f"in float32 (no lower than {-88.0 / SUB})")
        if self.neg_eigval:
            raise ValueError(f"{self.name}: beta is never doubled here")
        self.channel_decay = True
        self.decay_rank = int(self.attrs.get("decay_rank") or 0)
        self.output_gate = self.attrs.get("output_gate", "head")
        if self.output_gate not in ("head", "channel"):
            raise ValueError(f"{self.name}: output_gate "
                             f"{self.output_gate!r} is neither 'head' nor "
                             f"'channel'")
        self.gate_rank = int(self.attrs.get("gate_rank") or 0)
        if (self.output_gate == "channel") != bool(self.gate_rank):
            raise ValueError(f"{self.name}: a gate a channel goes through "
                             f"a gate_rank, a gate a head through none")

    def weight_specs(self) -> List[WeightSpec]:
        dt = self.input_shapes[0].dtype
        init = self.attrs.get("kernel_initializer") or DefaultWeightInitializer()
        gain = self.attrs.get("gain_initializer") or ConstantInitializer(1.0)
        gate = self.attrs.get("gate_initializer") or ZeroInitializer()
        e, h = self.embed_dim, self.num_heads
        decay = [WeightSpec("wf", (e, self.qk_width), dt, init)] \
            if not self.decay_rank else [
            WeightSpec("wf_a", (e, self.decay_rank), dt, init),
            WeightSpec("wf_b", (self.decay_rank, self.qk_width), dt, init)]
        gate_out = [WeightSpec("wg", (e, h), dt, init)] \
            if not self.gate_rank else [
            WeightSpec("wg_a", (e, self.gate_rank), dt, init),
            WeightSpec("wg_b", (self.gate_rank, self.v_width), dt, init)]
        return [
            WeightSpec("wq", (e, self.qk_width), dt, init),
            WeightSpec("wk", (e, self.qk_width), dt, init),
            WeightSpec("wv", (e, self.v_width), dt, init),
            *decay,
            WeightSpec("wb", (e, h), dt, init),
            *gate_out,
            WeightSpec("conv", (self.conv_taps, self.channels), dt, init),
            WeightSpec("a_log", (h,), dt, gate, weight_decay=False),
            WeightSpec("dt_bias", (self.qk_width,), dt, gate,
                       weight_decay=False),
            WeightSpec("norm", (self.value_dim,), dt, gain,
                       weight_decay=False),
            WeightSpec("wo", (self.v_width, e), dt, init),
        ]

    @sub_scope("gate")
    def gates(self, weights, x):
        """(B, S, E) -> ``g`` = log alpha (B, S, H, d_k) and beta (B, S,
        H), float32."""
        f32 = jnp.float32
        b, s, _ = x.shape
        h, dk = self.num_heads, self.key_dim
        if self.decay_rank:
            f = jnp.dot(_mm(x, weights["wf_a"]), weights["wf_b"],
                        preferred_element_type=f32)
        else:
            f = jnp.dot(x, weights["wf"], preferred_element_type=f32)
        bl = jnp.dot(x, weights["wb"], preferred_element_type=f32)
        a = jnp.exp(weights["a_log"].astype(f32))[:, None]
        g = self.lower_bound * jax.nn.sigmoid(
            a * (f.reshape(b, s, h, dk)
                 + weights["dt_bias"].astype(f32).reshape(h, dk)))
        return g, jax.nn.sigmoid(bl)

    @sub_scope("out")
    def finish(self, weights, x, o, normed=False):
        """The recurrence's (B, S, H, d_v) float32 outputs -> (B, S, E):
        RMSNorm over ``d_v`` a head (``normed``: made already), times the
        head's ``sigmoid(x w_gate)``, through ``W_o``."""
        b, s = o.shape[:2]
        y = o if normed else rms_norm(o, weights["norm"], self.eps)
        if self.gate_rank:
            z = jnp.dot(_mm(x, weights["wg_a"]), weights["wg_b"],
                        preferred_element_type=jnp.float32)
            y = y.reshape(b, s, self.v_width) * jax.nn.sigmoid(z)
            return _mm(y.astype(x.dtype), weights["wo"])
        z = jnp.dot(x, weights["wg"], preferred_element_type=jnp.float32)
        y = y.reshape(b, s, self.num_heads, self.value_dim) \
            * jax.nn.sigmoid(z)[..., None]
        return _mm(y.reshape(b, s, self.v_width).astype(x.dtype),
                   weights["wo"])

    def flops(self) -> float:
        b, s = self.input_shapes[0].sizes[:2]
        e, h = self.embed_dim, self.num_heads
        # the decay's projection (full-rank, or through its rank) and the
        # output gate's in the place of W_g's and W_a's
        r, gr = self.decay_rank, self.gate_rank
        decay = r * (e + self.qk_width) if r else e * self.qk_width
        gate = gr * (e + self.v_width) if gr else e * h
        return (super().flops()
                + 2.0 * b * s * (decay + gate - e * (self.v_width + h)))
