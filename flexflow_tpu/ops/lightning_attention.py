"""LightningAttention: linear attention with a fixed decay a head
(Lightning Attention-2, Qin et al. 2024; no reference analog).

``x`` is (B, S, E); H heads of width D (keys and values alike).

* ``q = norm_D(x W_q)``, ``k = norm_D(x W_k)`` (an RMSNorm over each
  head's D with one gain of width D, shared by the heads), ``v = x W_v``;
  q and k are rotated (rotate-half rotary embedding, the token's absolute
  position) and q is scaled by ``D^-1/2``.
* the state ``S`` of a head is (D, D), zero before the sequence::

      S_t = lambda_h S_{t-1} + k_t^T v_t
      o_t = q_t S_t

  with ``lambda_h = exp(-s_h)``, :func:`decay_slopes`.
* out: ``y_t = (norm_D(o_t) * sigmoid(x_t W_g)) W_o``.

What a sequence keeps of this layer is ``S`` alone, float32: one row a
REQUEST (serving/cache_entry.py ``DecayStateEntry``).

:func:`chunked_decay_rule` takes whole blocks of tokens behind a state,
``CHUNK`` at a time: inside a chunk the outputs are two matrix products
(against the incoming state, and the chunk's own ``q k^T`` under the
decay's lower triangle), between chunks the state is carried by
``lax.scan``. :func:`decay_step` takes one token. Both are float32 with
products at ``highest``; the projections around them are in the
activations' dtype with float32 accumulation.
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from ..core.op import Op, WeightSpec, register_op, sub_scope
from ..ffconst import OpType
from ..runtime.initializer import ConstantInitializer, DefaultWeightInitializer
from .attention import _mm, apply_rotary, rotary_inv_freq
from .norm import rms_norm
from .rows import named_by

CHUNK = 128  # tokens a step of the block form's scan
_HI = jax.lax.Precision.HIGHEST


def decay_slopes(num_heads: int, layer: int, num_layers: int) -> np.ndarray:
    """``s_h`` of head h of ``num_heads`` in layer ``layer`` of
    ``num_layers``: ALiBi's geometric sequence ``2^(-8 (h + 1) / H)``
    times ``1 - layer / (num_layers - 1) + 1e-5``, so that later layers
    forget more slowly (the form of Lightning Attention-2's published
    code)."""
    h = np.arange(1, num_heads + 1, dtype=np.float64)
    depth = 1.0 - layer / max(num_layers - 1, 1) + 1e-5
    return (2.0 ** (-8.0 * h / num_heads) * depth).astype(np.float32)


def chunked_decay_rule(q, k, v, g, state):
    """``q``, ``k`` (B, S, H, D) and ``v`` (B, S, H, Dv), ``g`` (B, S, H)
    each position's log decay, ``state`` (B, H, D, Dv) the state before
    the block, all float32. A position with ``g = 0`` and ``k = 0``
    leaves the state as it was. Returns (o (B, S, H, Dv), the state after
    position S - 1)."""
    b, s, h, _ = q.shape
    pad = -s % CHUNK
    if pad:
        q, k, v, g = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) *
                              (a.ndim - 2)) for a in (q, k, v, g))
    n = (s + pad) // CHUNK

    def chunks(a):                    # (B, n C, H, ...) -> (n, B, H, C, ...)
        a = a.reshape((b, n, CHUNK) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    q, k, v, g = map(chunks, (q, k, v, g))
    gc = jnp.cumsum(g, axis=-1)                              # (n, B, H, C)
    idx = jax.lax.iota(jnp.int32, CHUNK)
    lower = idx[:, None] >= idx[None, :]
    # decay[t, i] = prod_{i < j <= t} lambda_j, for i <= t
    decay = jnp.exp(jnp.where(lower, gc[..., :, None] - gc[..., None, :],
                              -jnp.inf))
    qk = jnp.einsum("...td,...id->...ti", q, k, precision=_HI) * decay
    q_in = jnp.exp(gc)[..., None] * q                        # against S_0
    k_out = jnp.exp(gc[..., -1:] - gc)[..., None] * k        # up to the end
    a_end = jnp.exp(gc[..., -1])[..., None, None]

    def step(st, xs):
        qk_c, q_c, k_c, v_c, a_c = xs
        o = (jnp.einsum("...td,...dv->...tv", q_c, st, precision=_HI)
             + jnp.einsum("...ti,...iv->...tv", qk_c, v_c, precision=_HI))
        st = a_c * st + jnp.einsum("...td,...tv->...dv", k_c, v_c,
                                   precision=_HI)
        return st, o

    state, o = jax.lax.scan(step, state, (qk, q_in, k_out, v, a_end))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)            # (B, n, C, H, Dv)
    return o.reshape(b, n * CHUNK, h, -1)[:, :s], state


def decay_step(state, q, k, v, lam):
    """One token a row: ``state`` (N, H, D, Dv), ``q``, ``k`` (N, H, D),
    ``v`` (N, H, Dv), ``lam`` (H,), float32. Returns (o (N, H, Dv), the
    new state)."""
    state = (lam[None, :, None, None] * state
             + k[..., :, None] * v[..., None, :])
    return jnp.einsum("nhd,nhdv->nhv", q, state, precision=_HI), state


def decay_step_rows(arena, rows, q, k, v, lam):
    """:func:`decay_step` on the rows of an arena, in place: ``arena``
    (R, H, D, Dv) holds a state a row, slot n steps row ``rows[n]`` (row
    0 is nobody's: a slot that names it steps nothing and reads zeros,
    a row nobody names taking a query of zeros). Each row TAKES the q, k
    and v of the slot that names it (``ops/rows.py`` ``named_by``: moved,
    not multiplied in, so one slot's NaN stays in its own row) and the
    arena is updated elementwise under ``live``, so that no state is
    gathered or scattered (a gather of rows of 2 MB lowers to a loop
    over the slots); the slots take their rows' outputs back. Returns (o
    (N, H, Dv), the new arena)."""
    slot_of, live = named_by(arena.shape[0], rows)
    qr = jnp.where(live[:, None, None], q[slot_of], 0.0)
    kr, vr = k[slot_of], v[slot_of]
    arena = jnp.where(live[:, None, None, None],
                      lam[None, :, None, None] * arena
                      + kr[..., :, None] * vr[..., None, :], arena)
    o = jnp.einsum("rhd,rhdv->rhv", qr, arena, precision=_HI)
    return o[rows], arena


@register_op
class LightningAttention(Op):
    """The layer of the module's docstring. Inputs: the activations (B,
    S, E) and the graph's int32 positions (B, S). Matrices keep 2-D
    shapes, heads side by side in the columns."""

    op_type = OpType.LIGHTNING_ATTENTION

    def __init__(self, layer, input_shapes):
        super().__init__(layer, input_shapes)
        a = self.attrs
        self.embed_dim: int = input_shapes[0].sizes[-1]
        self.num_heads = int(a["num_heads"])
        self.head_dim = int(a["head_dim"])
        self.eps = float(a.get("eps", 1e-6))
        self.width = self.num_heads * self.head_dim
        self.inv_freq = rotary_inv_freq(self.head_dim,
                                        float(a.get("rope_theta", 10000.0)))
        self.slopes = decay_slopes(self.num_heads, int(a["layer_index"]),
                                   int(a["num_layers"]))
        self.causal = True

    def infer_output_shapes(self):
        return [(self.input_shapes[0].sizes, self.input_shapes[0].dtype)]

    def weight_specs(self) -> List[WeightSpec]:
        dt = self.input_shapes[0].dtype
        init = self.attrs.get("kernel_initializer") or DefaultWeightInitializer()
        gain = self.attrs.get("gain_initializer") or ConstantInitializer(1.0)
        e, w, d = self.embed_dim, self.width, self.head_dim
        return ([WeightSpec(n, (e, w), dt, init)
                 for n in ("wq", "wk", "wv", "wg")]
                + [WeightSpec(n, (d,), dt, gain, weight_decay=False)
                   for n in ("q_norm", "k_norm", "o_norm")]
                + [WeightSpec("wo", (w, e), dt, init)])

    # ---- the pieces serving composes (serving/cache_entry.py) -------------
    @sub_scope("project")
    def heads(self, weights, x, positions):
        """(B, S, E) -> q (normed, rotated, scaled), k (normed, rotated)
        and v, (B, S, H, D) float32."""
        b, s, _ = x.shape
        shape = (b, s, self.num_heads, self.head_dim)

        def head(w, gain=None):
            y = _mm(x, weights[w]).reshape(shape).astype(jnp.float32)
            if gain is None:
                return y
            return apply_rotary(rms_norm(y, weights[gain], self.eps),
                                positions, self.inv_freq)

        return (head("wq", "q_norm") * self.head_dim ** -0.5,
                head("wk", "k_norm"), head("wv"))

    @sub_scope("project")
    def finish(self, weights, x, o):
        """The recurrence's (B, S, H, D) float32 outputs -> (B, S, E)."""
        b, s = o.shape[:2]
        z = jnp.dot(x, weights["wg"], preferred_element_type=jnp.float32)
        y = rms_norm(o, weights["o_norm"], self.eps).reshape(b, s, self.width)
        return _mm((y * jax.nn.sigmoid(z)).astype(x.dtype), weights["wo"])

    def run(self, weights, x, positions, state, lengths=None):
        """A block of S tokens a row behind ``state`` (B, H, D, D)
        float32; ``lengths`` (B,) the tokens of each row that count
        (None: all S): positions past a row's length leave its state as
        it was. Returns (y (B, S, E), state)."""
        b, s, _ = x.shape
        q, k, v = self.heads(weights, x, positions)
        with sub_scope("chunks"):
            g = jnp.broadcast_to(-jnp.asarray(self.slopes), (b, s,
                                                             self.num_heads))
            if lengths is not None:
                live = (jax.lax.iota(jnp.int32, s)[None, :]
                        < lengths[:, None])[..., None]
                g = jnp.where(live, g, 0.0)
                k = jnp.where(live[..., None], k, 0.0)
            o, state = chunked_decay_rule(q, k, v, g, state)
        return self.finish(weights, x, o), state

    def step(self, weights, x, positions, arena, rows):
        """One token a slot: ``x`` (N, 1, E), slot n's state row
        ``rows[n]`` of ``arena`` (R, H, D, D). Returns (y (N, 1, E), the
        new arena)."""
        q, k, v = self.heads(weights, x, positions)
        with sub_scope("rule"):
            o, arena = decay_step_rows(arena, rows, q[:, 0], k[:, 0], v[:, 0],
                                       jnp.exp(-jnp.asarray(self.slopes)))
        return self.finish(weights, x, o[:, None]), arena

    def empty_state(self, batch: int):
        return jnp.zeros((batch, self.num_heads, self.head_dim,
                          self.head_dim), jnp.float32)

    def forward(self, ctx, inputs, weights):
        x, positions = inputs
        return [self.run(weights, x, positions,
                         self.empty_state(x.shape[0]))[0]]

    def flops(self) -> float:
        b, s = self.input_shapes[0].sizes[:2]
        d = self.head_dim
        proj = 2.0 * b * s * self.embed_dim * self.width * 5
        # a chunk of C tokens: q k^T and (q k^T) v over (C, C); q S and
        # k^T v against the state
        return proj + 2.0 * b * s * self.num_heads * (2 * CHUNK * d
                                                      + 2 * d * d)
