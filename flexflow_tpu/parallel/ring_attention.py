"""Ring attention: sequence/context parallelism over a mesh axis.

The reference has NO sequence-dim collective attention (SURVEY.md §2.3/§5:
only iteration-level seq truncation exists; ring attention is listed as the
TPU-native plan). This module supplies it as a first-class capability:

* q/k/v are sharded on the sequence dim over mesh axis ``seq``;
* each device computes attention of its local query block against the
  k/v block it currently holds, then passes k/v to its ring neighbor via
  ``collective-permute`` over ICI (the Ring Attention schedule, Liu et al.
  2023), accumulating with the numerically-stable online-softmax (flash)
  recurrence so the full softmax is exact;
* causal masking keeps the schedule static for XLA (blocks are masked,
  not skipped);
* attention dropout is applied blockwise to the unnormalized exp weights
  while the normalizer accumulates undropped weights — algebraically
  identical to dropping the normalized probabilities, so sharded and
  unsharded training match in distribution.

Communication: n-1 block sends of k/v per device (the final compute step
does not permute), overlapping with the local block matmuls.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import pcast
from jax.sharding import Mesh, PartitionSpec as P


def _drop(p: jnp.ndarray, rate: float, rng: Optional[jax.Array]):
    if rate <= 0.0 or rng is None:
        return p
    keep = 1.0 - rate
    mask = jax.random.bernoulli(rng, keep, p.shape)
    return jnp.where(mask, p / keep, 0.0)


def _block_attn(q, k, v, m_prev, l_prev, o_prev, mask, dropout_rate=0.0, rng=None):
    """One online-softmax accumulation step.

    q: (B,Sq,H,D) k,v: (B,Sk,H,D); m,l,o running max/normalizer/output.
    mask: (Sq,Sk) additive mask (0 or -inf) or None.
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k)
    if mask is not None:
        s = s + mask[None, None, :, :]
    m_cur = jnp.max(s, axis=-1)  # (B,H,Sq)
    m_new = jnp.maximum(m_prev, m_cur)
    # guard fully-masked rows (m_new = -inf): exp(-inf - -inf) -> nan
    m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(jnp.isneginf(s), 0.0, p)
    corr = jnp.exp(jnp.where(jnp.isneginf(m_prev), -jnp.inf, m_prev - m_safe))
    corr = jnp.where(jnp.isneginf(m_prev), 0.0, corr)
    # normalizer uses undropped weights; output uses dropped weights — see
    # module docstring for the equivalence argument
    l_new = corr * l_prev + jnp.sum(p, axis=-1)
    pd = _drop(p, dropout_rate, rng)
    o_new = corr[..., None] * o_prev + jnp.einsum("bhqk,bkhd->bhqd", pd, v)
    return m_new, l_new, o_new


def sink_softmax(scores, sink=None):
    """The softmax over the last axis of (masked) ``scores`` (B, heads...,
    Sq, Sk); with ``sink`` (shaped as the heads axes) over one more
    column, ``sink``'s, which carries no value and is dropped."""
    if sink is None:
        return jax.nn.softmax(scores, axis=-1)
    col = jnp.broadcast_to(sink.astype(scores.dtype)[None, ..., None, None],
                           scores.shape[:-1] + (1,))
    return jax.nn.softmax(jnp.concatenate([scores, col], axis=-1),
                          axis=-1)[..., :-1]


def single_device_attention(q, k, v, causal: bool, scale: float,
                            dropout_rate: float = 0.0,
                            rng: Optional[jax.Array] = None,
                            window: Optional[int] = None,
                            sink: Optional[jax.Array] = None):
    """Plain scaled-dot-product attention (the n=1 path and the shared
    implementation for the unsharded MultiHeadAttention lowering). With a
    ``window`` (a causal op's) a query sees the ``window`` keys that end
    at its own. ``sink`` (H,): one more column of each head's softmax,
    which carries no value. The values' width may differ from the keys'."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q * scale, k)
    if causal:
        Sq, Sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((Sq, Sk), bool))
        if window:
            mask &= ~jnp.tril(jnp.ones((Sq, Sk), bool), -window)
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = _drop(sink_softmax(s, sink), dropout_rate, rng)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


# backwards-compat alias (tests/earlier callers)
_single_device_attention = single_device_attention


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis: str,
    causal: bool = False,
    scale: Optional[float] = None,
    dropout_rate: float = 0.0,
    rng: Optional[jax.Array] = None,
) -> jax.Array:
    """Exact attention with q/k/v sequence-sharded over ``axis``.

    Shapes: (batch, seq, heads, head_dim); q/k/v must share the same seq
    length, divisible by the axis size (validated by the caller's
    ``propagate`` — MultiHeadAttention falls back to local attention
    otherwise). Returns the attention output with the same sharding.
    """
    if q.shape[1] != k.shape[1] or k.shape[1] != v.shape[1]:
        raise ValueError(
            f"ring attention requires equal q/k/v seq lengths, got "
            f"{q.shape[1]}/{k.shape[1]}/{v.shape[1]}"
        )
    n = mesh.shape[axis]
    scale = scale if scale is not None else q.shape[-1] ** -0.5

    if n == 1:
        return single_device_attention(q, k, v, causal, scale, dropout_rate, rng)

    def body(ql, kl, vl):
        # ql/kl/vl: local blocks (B, S/n, H, D)
        ridx = jax.lax.axis_index(axis)
        Sq = ql.shape[1]
        ql = ql * scale
        B, _, H, D = ql.shape
        m0 = jnp.full((B, H, Sq), -jnp.inf, ql.dtype)
        l0 = jnp.zeros((B, H, Sq), ql.dtype)
        o0 = jnp.zeros((B, H, Sq, D), ql.dtype)
        # mark accumulators as device-varying for shard_map's VMA typing
        m0, l0, o0 = (pcast(a, (axis,), to="varying") for a in (m0, l0, o0))
        perm = [(i, (i + 1) % n) for i in range(n)]

        def accumulate(s, kb, vb, m, l, o):
            # block held in step s came from device (ridx - s) mod n
            src = (ridx - s) % n
            if causal:
                qpos = ridx * Sq + jnp.arange(Sq)[:, None]
                kpos = src * Sq + jnp.arange(Sq)[None, :]
                mask = jnp.where(qpos >= kpos, 0.0, -jnp.inf)
            else:
                mask = None
            step_rng = (
                jax.random.fold_in(jax.random.fold_in(rng, s), ridx)
                if (rng is not None and dropout_rate > 0.0)
                else None
            )
            return _block_attn(ql, kb, vb, m, l, o, mask, dropout_rate, step_rng)

        def step(carry, s):
            kb, vb, m, l, o = carry
            m, l, o = accumulate(s, kb, vb, m, l, o)
            kb = jax.lax.ppermute(kb, axis, perm)
            vb = jax.lax.ppermute(vb, axis, perm)
            return (kb, vb, m, l, o), None

        # n-1 compute+permute steps, then a final compute with no permute
        (kb, vb, m, l, o), _ = jax.lax.scan(
            step, (kl, vl, m0, l0, o0), jnp.arange(n - 1)
        )
        m, l, o = accumulate(jnp.asarray(n - 1), kb, vb, m, l, o)
        l = jnp.where(l == 0.0, 1.0, l)
        out = o / l[..., None]
        return jnp.einsum("bhqd->bqhd", out)

    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(P(None, axis, None, None),) * 3,
        out_specs=P(None, axis, None, None),
    )
    return fn(q, k, v)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis: str,
    causal: bool = False,
    scale: Optional[float] = None,
    dropout_rate: float = 0.0,
    rng: Optional[jax.Array] = None,
) -> jax.Array:
    """All-to-all (DeepSpeed-Ulysses-style) sequence parallelism.

    The other SP schedule the scaling literature uses (no reference
    analog — SURVEY.md §5 names "ring attention or all-to-all
    sequence/context parallelism" as the TPU-native plan): q/k/v arrive
    sequence-sharded over ``axis``; one all-to-all re-shards them to
    head-sharded with the FULL sequence per device, attention runs locally
    and exactly, and a second all-to-all restores sequence sharding.

    Trade-off vs :func:`ring_attention`: 4 all-to-alls of activation
    blocks (q/k/v in, output back) instead of 2(n-1) k/v permutes —
    cheaper when heads are plentiful and the axis degree divides them
    (required: heads % degree == 0); ring wins when n is large or heads
    are few. Both are exposed to the strategy search as ``seq_mode``
    alternatives, priced accordingly (sim/simulator.py _comm_time).
    """
    n = mesh.shape[axis]
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    if n == 1:
        return single_device_attention(q, k, v, causal, scale, dropout_rate, rng)
    if q.shape[2] % n != 0:
        raise ValueError(
            f"ulysses attention needs heads % degree == 0, got "
            f"{q.shape[2]} % {n}")
    if q.shape[1] != k.shape[1] or k.shape[1] != v.shape[1]:
        raise ValueError("ulysses attention requires equal q/k/v seq lengths")

    def body(ql, kl, vl):
        # (B, S/n, H, D) --all_to_all--> (B, S, H/n, D)
        def seq_to_heads(x):
            return jax.lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                      tiled=True)

        step_rng = (
            jax.random.fold_in(rng, jax.lax.axis_index(axis))
            if (rng is not None and dropout_rate > 0.0) else None
        )
        o = single_device_attention(
            seq_to_heads(ql), seq_to_heads(kl), seq_to_heads(vl),
            causal, scale, dropout_rate, step_rng)
        # (B, S, H/n, D) --all_to_all--> (B, S/n, H, D)
        return jax.lax.all_to_all(o, axis, split_axis=1, concat_axis=2,
                                  tiled=True)

    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(P(None, axis, None, None),) * 3,
        out_specs=P(None, axis, None, None),
    )
    return fn(q, k, v)
