"""Mamba2: a state-space mixer whose memory is a state of fixed size a
sequence (Mamba-2, Dao & Gu 2024: the SSD recurrence; no reference
analog).

``x`` is (B, S, E); H heads of width P (inner width ``H P``), a state of
N numbers a head channel, G groups of ``H / G`` heads that share B and C,
a convolution of K taps over ``channels = H P + 2 G N``.

* ``[z | xBC | dt] = x W_in`` (``H P | channels | H`` columns);
* ``xBC_t[c] = silu(bias[c] + sum_{j < K} w[j, c] in_{t-K+1+j}[c])``, a
  causal depthwise convolution, zeros before the sequence; ``[xs | B | C]
  = xBC`` (H heads of P | G groups of N | G groups of N), head ``h`` reads
  group ``h // (H / G)``;
* ``dt_t = softplus(dt_t + dt_bias)``, ``a_t = exp(dt_t A)``, ``A =
  -exp(A_log)``, one scalar a head;
* the state ``S`` of a head is ``(P, N)``, float32, zero before the
  sequence::

      S_t = a_t S_{t-1} + dt_t xs_t B_t^T
      y_t = S_t C_t + D xs_t

* out: ``RMSNorm_G(y_t * silu(z_t)) W_out``, the norm over each of the G
  groups of ``H P / G`` channels, with a gain a channel.

What a sequence keeps of this layer is ``S`` (``H P N`` numbers) and the
last ``K - 1`` inputs of the convolution: one row a REQUEST
(serving/cache_entry.py ``SsmStateEntry``).

Two forms compute the recurrence, both jnp, float32 with products at
``highest``. :func:`chunked_ssd` takes whole sequences, ``chunk`` tokens
at a time: within a chunk the masked products ``(C B^T * L) (dt xs)`` with
``L[t, s] = exp(sum_{s < r <= t} dt_r A)``, every chunk at once; between
chunks the state is carried by ``lax.scan``. :func:`ssd_step_rows` takes
one token a slot on the rows of an arena where they lie: the slots'
inputs are spread over the rows and the arena is updated elementwise, so
that no state is gathered or scattered and the step holds no loop. It
is the CPU's form and the reference of the form the chip runs,
``kernels/ssd_step.py`` ``ssd_step_decode``: the same step as one Pallas
kernel over the live rows alone (``serving/cache_entry.py``
``SsmStateEntry.step`` takes the kernel where its ``supported()``
agrees). The projections around them are in the activations' dtype
with float32 accumulation.
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

from ..core.op import Op, WeightSpec, register_op, sub_scope
from ..ffconst import OpType
from ..runtime.initializer import (ConstantInitializer,
                                   DefaultWeightInitializer, ZeroInitializer)
from .attention import _mm
from .rows import named

_HI = jax.lax.Precision.HIGHEST


def chunked_ssd(xs, dt, a, bm, cm, state, chunk: int):
    """The SSD recurrence over whole sequences. ``xs`` (B, S, H, P),
    ``dt`` (B, S, H), ``a`` (H,) = A (negative), ``bm``, ``cm`` (B, S, G,
    N), ``state`` (B, H, P, N) the state before the sequence, all
    float32. A position with ``dt = 0`` leaves the state as it was.
    Returns (y (B, S, H, P) without the ``D`` term, the state after
    position S - 1)."""
    b, s, h, p = xs.shape
    g_, n = bm.shape[2:]
    per = h // g_
    pad = -s % chunk
    if pad:
        xs, dt, bm, cm = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) *
                                  (v.ndim - 2)) for v in (xs, dt, bm, cm))
    nc = (s + pad) // chunk

    def chunks(v):                       # (B, nc C, ...) -> (B, nc, C, ...)
        return v.reshape((b, nc, chunk) + v.shape[2:])

    u = chunks(xs * dt[..., None]).reshape(b, nc, chunk, g_, per, p)
    bm, cm = chunks(bm), chunks(cm)
    gc = jnp.cumsum(chunks(dt * a), axis=2)                  # (B, nc, C, H)
    gh = jnp.moveaxis(gc, 2, 3).reshape(b, nc, g_, per, chunk)
    idx = jax.lax.iota(jnp.int32, chunk)
    # decay[t, s] = prod_{s < r <= t} a_r, for s <= t
    decay = jnp.exp(jnp.where(idx[:, None] >= idx[None, :],
                              gh[..., :, None] - gh[..., None, :], -jnp.inf))
    cb = jnp.einsum("bctgn,bcsgn->bcgts", cm, bm, precision=_HI)
    y = jnp.einsum("bcgjts,bcsgjp->bctgjp", cb[:, :, :, None] * decay, u,
                   precision=_HI)
    # what a chunk adds to the state it ends in, and what it keeps of the
    # state it began with
    to_end = jnp.exp(gc[:, :, -1:, :] - gc).reshape(b, nc, chunk, g_, per)
    added = jnp.einsum("bcsgjp,bcsgn->bcgjpn", u * to_end[..., None], bm,
                       precision=_HI)
    kept = jnp.exp(gc[:, :, -1, :]).reshape(b, nc, g_, per)

    def carry(st, xs_):
        add_c, keep_c = xs_
        return keep_c[..., None, None] * st + add_c, st

    state, before = jax.lax.scan(
        carry, state.reshape(b, g_, per, p, n),
        (jnp.moveaxis(added, 1, 0), jnp.moveaxis(kept, 1, 0)))
    since = jnp.exp(gc).reshape(b, nc, chunk, g_, per)
    y = y + since[..., None] * jnp.einsum(
        "bctgn,cbgjpn->bctgjp", cm, before, precision=_HI)
    return (y.reshape(b, nc * chunk, h, p)[:, :s],
            state.reshape(b, h, p, n))


def ssd_step(state, u, decay, bm, cm):
    """One token a row: ``state`` (N, H, P, S) float32, ``u`` = dt xs (N,
    H, P), ``decay`` = exp(dt A) (N, H), ``bm``, ``cm`` (N, G, S). Returns
    (y (N, H, P) without the ``D`` term, the new state)."""
    per = state.shape[1] // bm.shape[1]
    bh, ch = (jnp.repeat(v, per, axis=1) for v in (bm, cm))  # (N, H, S)
    state = (decay[..., None, None] * state
             + u[..., None] * bh[:, :, None, :])
    return jnp.einsum("nhps,nhs->nhp", state, ch, precision=_HI), state


def ssd_step_rows(arena, rows, u, decay, bm, cm):
    """:func:`ssd_step` on the rows of an arena, in place: ``arena`` (R, S,
    H P) holds a state a row as the pool stores it, the state's axis
    before the heads' channels (``kernels/ssd_step.py``), and slot n steps
    row ``rows[n]`` (row 0 is nobody's: a slot that names it steps
    nothing and reads what lies there). Each row takes the inputs of the
    slot that names it (``ops/rows.py``; by the mask itself: ``named_by``
    would reduce it to ``live`` before the takes, and the programs'
    recorded text has it behind them) and the arena is updated
    elementwise where it lies: one read and one write of the states, no
    gather or scatter of them. ``y`` is read from the state before the
    update (``S_t C = a S_{t-1} C + u (B . C)``), so the pass that writes
    the new state is the pass that reads the old. Returns (y (N, H, P),
    the new arena)."""
    n, h, p = u.shape
    per = h // bm.shape[1]
    hot = named(arena.shape[0], rows)                            # (N, R)
    who = jnp.argmax(hot, axis=0)
    ur, ar, br, cr = (v[who] for v in (u, decay, bm, cm))

    def lanes(v):          # a value a head, or a group, along the channels
        return jnp.repeat(v, h * p // v.shape[-1], axis=-1)

    ul, al = ur.reshape(-1, h * p), lanes(ar)                    # (R, H P)
    bl, cl = (lanes(jnp.swapaxes(v, 1, 2)) for v in (br, cr))   # (R, S, H P)
    y = (al * jnp.sum(arena * cl, axis=1)
         + ul * lanes(jnp.sum(br * cr, -1)))
    arena = jnp.where(hot.any(0)[:, None, None],
                      al[:, None] * arena + ul[:, None] * bl, arena)
    return y[rows].reshape(n, h, p), arena


@register_op
class Mamba2(Op):
    """The layer of the module's docstring. Matrices keep 2-D shapes;
    ``w_in``'s columns are ``[z | xBC | dt]``, ``conv`` is (taps,
    channels) in the order ``[xs | B | C]``."""

    op_type = OpType.MAMBA2

    def __init__(self, layer, input_shapes):
        super().__init__(layer, input_shapes)
        a = self.attrs
        self.embed_dim: int = input_shapes[0].sizes[-1]
        self.num_heads = int(a["num_heads"])
        self.head_dim = int(a["head_dim"])
        self.state_size = int(a["state_size"])
        self.n_groups = int(a.get("n_groups", 1))
        self.conv_taps = int(a.get("conv_taps", 4))
        self.chunk = int(a.get("chunk_size", 128))
        self.eps = float(a.get("eps", 1e-5))
        if self.num_heads % self.n_groups:
            raise ValueError(f"{self.num_heads} heads are not "
                             f"{self.n_groups} equal groups")
        self.inner = self.num_heads * self.head_dim
        self.bc_width = self.n_groups * self.state_size
        self.channels = self.inner + 2 * self.bc_width

    def infer_output_shapes(self):
        return [(self.input_shapes[0].sizes, self.input_shapes[0].dtype)]

    def weight_specs(self) -> List[WeightSpec]:
        dt = self.input_shapes[0].dtype
        init = self.attrs.get("kernel_initializer") or DefaultWeightInitializer()
        gain = self.attrs.get("gain_initializer") or ConstantInitializer(1.0)
        # A_log = 0, dt_bias = 0 and D = 1 give a = exp(-softplus(.)); a
        # loader or a family's draw puts the published ranges in
        gate = self.attrs.get("gate_initializer") or ZeroInitializer()
        h = self.num_heads
        return [
            WeightSpec("w_in", (self.embed_dim,
                                self.inner + self.channels + h), dt, init),
            WeightSpec("conv", (self.conv_taps, self.channels), dt, init),
            WeightSpec("conv_bias", (self.channels,), dt, gate,
                       weight_decay=False),
            WeightSpec("a_log", (h,), dt, gate, weight_decay=False),
            WeightSpec("dt_bias", (h,), dt, gate, weight_decay=False),
            WeightSpec("d", (h,), dt, gain, weight_decay=False),
            WeightSpec("norm", (self.inner,), dt, gain, weight_decay=False),
            WeightSpec("w_out", (self.inner, self.embed_dim), dt, init),
        ]

    # ---- the pieces serving composes (serving/cache_entry.py) -------------
    @sub_scope("project")
    def project(self, weights, x):
        """(B, S, E) -> the gate ``z`` (B, S, H P), the convolution's
        inputs (B, S, channels), both in the activations' dtype (the
        second is what the tail keeps), and ``dt`` (B, S, H) float32 after
        its bias and softplus."""
        y = _mm(x, weights["w_in"])
        z, conv_in = y[..., :self.inner], y[..., self.inner:-self.num_heads]
        dt = jax.nn.softplus(y[..., -self.num_heads:].astype(jnp.float32)
                             + weights["dt_bias"].astype(jnp.float32))
        return z, conv_in, dt

    @sub_scope("conv")
    def convolve(self, weights, window):
        """``window`` (B, K - 1 + S, channels): each position's inputs
        behind the ``K - 1`` before it. The sum of K shifted products and
        the bias, then SiLU; (B, S, channels) float32."""
        s = window.shape[1] - (self.conv_taps - 1)
        w = weights["conv"].astype(jnp.float32)
        window = window.astype(jnp.float32)
        acc = sum(w[j] * window[:, j:j + s] for j in range(self.conv_taps))
        return jax.nn.silu(acc + weights["conv_bias"].astype(jnp.float32))

    def split(self, conv):
        """The convolved (B, S, channels) -> xs (B, S, H, P), B and C (B,
        S, G, N)."""
        b, s, _ = conv.shape
        g, n = self.n_groups, self.state_size
        return (conv[..., :self.inner].reshape(b, s, self.num_heads,
                                               self.head_dim),
                conv[..., self.inner:self.inner + g * n].reshape(b, s, g, n),
                conv[..., self.inner + g * n:].reshape(b, s, g, n))

    def decay_rate(self, weights):
        """``A = -exp(A_log)``, (H,) float32."""
        return -jnp.exp(weights["a_log"].astype(jnp.float32))

    @sub_scope("project")
    def finish(self, weights, z, xs, y):
        """The recurrence's (B, S, H, P) float32 outputs -> (B, S, E): the
        ``D`` term, the gate, the grouped RMSNorm, ``W_out``."""
        b, s = y.shape[:2]
        y = y + weights["d"].astype(jnp.float32)[:, None] * xs
        y = y.reshape(b, s, self.inner) * jax.nn.silu(z.astype(jnp.float32))
        grouped = y.reshape(b, s, self.n_groups, -1)
        grouped = grouped * jax.lax.rsqrt(
            jnp.mean(grouped * grouped, -1, keepdims=True) + self.eps)
        y = grouped.reshape(b, s, self.inner) * weights["norm"].astype(
            jnp.float32)
        return _mm(y.astype(z.dtype), weights["w_out"])

    def run(self, weights, x, state, tail, lengths=None):
        """A block of S tokens a row behind ``state`` (B, H, P, N) float32
        and ``tail`` (B, K - 1, channels), the last inputs of the
        convolution before the block; ``lengths`` (B,) the tokens of each
        row that count (None: all S). Positions past a row's length leave
        its state as it was (``dt = 0`` there) and the new tail is taken
        at the true length. Returns (y (B, S, E), state, tail)."""
        b, s, _ = x.shape
        taps = self.conv_taps
        if lengths is None:
            lengths = jnp.full((b,), s, jnp.int32)
        z, conv_in, dt = self.project(weights, x)
        with sub_scope("conv"):
            window = jnp.concatenate([tail.astype(x.dtype), conv_in], axis=1)
            xs, bm, cm = self.split(self.convolve(weights, window))
        with sub_scope("rule"):
            live = (jax.lax.iota(jnp.int32, s)[None, :]
                    < lengths[:, None])[..., None]
            y, state = chunked_ssd(xs, jnp.where(live, dt, 0.0),
                                   self.decay_rate(weights), bm, cm, state,
                                   self.chunk)
        with sub_scope("conv"):
            # window position p is block position p - (K - 1): the K - 1
            # inputs before position ``length`` start at ``length``
            tail = jax.vmap(lambda w, n: jax.lax.dynamic_slice_in_dim(
                w, n, taps - 1, axis=0))(window, lengths)
        return self.finish(weights, z, xs, y), state, tail

    def empty(self, batch: int, dtype):
        """The state and the tail before a sequence."""
        return (jnp.zeros((batch, self.num_heads, self.head_dim,
                           self.state_size), jnp.float32),
                jnp.zeros((batch, self.conv_taps - 1, self.channels), dtype))

    def whole(self, weights, x, lengths=None):
        """Whole sequences from an empty state: :meth:`run` behind zeros."""
        return self.run(weights, x, *self.empty(x.shape[0], x.dtype), lengths)

    def forward(self, ctx, inputs, weights):
        return [self.whole(weights, inputs[0])[0]]

    def flops(self) -> float:
        b, s = self.input_shapes[0].sizes[:2]
        h, p, n, g = (self.num_heads, self.head_dim, self.state_size,
                      self.n_groups)
        proj = 2.0 * b * s * self.embed_dim * (
            2 * self.inner + self.channels + h)
        # a chunk of C tokens: C B^T a group and (C B^T * L) u a head over
        # (C, C); C S, and u B^T into the state, a head
        rule = 2.0 * b * s * (self.chunk * (g * n + h * p) + 2 * h * p * n)
        return proj + rule + 2.0 * b * s * self.conv_taps * self.channels
