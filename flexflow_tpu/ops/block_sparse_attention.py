"""BlockSparseAttention: causal attention with grouped key-value heads
that, past a context length, reads only the key blocks it selects
(InfLLM v2, the MiniCPM4 line; no reference analog).

``x`` is (B, S, E); H query heads and ``Hkv`` key-value heads of width D,
query head h reading key-value head ``h // (H / Hkv)``; no positional
encoding (the layers beside it carry the order).

* ``q = norm_D(x W_q)``, ``k = norm_D(x W_k)`` (an RMSNorm over each
  head's D with one gain of width D), ``v = x W_v``.
* a query at position ``p < dense_len`` attends every position ``<= p``.
* at ``p >= dense_len``, per key-value head: **kernels** ``c_i =
  mean(k[stride i : stride i + kernel])`` for every i whose last key is at
  or before p; ``a_(h, i) = softmax_i(q_h . c_i D^-1/2)`` for each of the
  group's heads and ``s_i = sum_h a_(h, i)``; a **block** b (positions
  ``block b .. block b + block - 1``) scores the largest ``s_i`` over the
  kernels that touch it; the first ``init_blocks`` blocks and the blocks
  touching the last ``window`` positions score infinity; the ``topk``
  blocks of highest score among blocks ``0 .. p // block`` are kept, and
  the group's heads take a causal softmax over the keys of those blocks
  only.
* out: ``y = (o * sigmoid(x W_g)) W_o``.

What a cache keeps of a token is its K and V rows and, every ``stride``
tokens, one kernel (serving/cache_entry.py ``SparseEntry``). The pieces
here are what its forms share: :func:`pool_keys` makes kernels,
:func:`select` scores and picks blocks, :func:`attend_blocked` is the
attend over a long context key block by key block (no array grows with
the square of the context), and :meth:`BlockSparseAttention.attend_dense`
is the plain rectangle, for short sequences (the op's ``forward``, the
dense generator).
"""

from __future__ import annotations

import dataclasses
from typing import List

import jax
import jax.numpy as jnp

from ..core.op import Op, WeightSpec, register_op, sub_scope
from ..ffconst import OpType
from ..runtime.initializer import ConstantInitializer, DefaultWeightInitializer
from .attention import _mm
from .norm import rms_norm

NEG = -1e30  # what a masked score is set to: exp underflows to exactly 0


@dataclasses.dataclass(frozen=True)
class Selection:
    """The sizes of the selection (the family's ``sparse_config``)."""

    kernel: int = 32
    stride: int = 16
    block: int = 64
    window: int = 2048
    dense_len: int = 8192
    init_blocks: int = 1
    topk: int = 64

    def __post_init__(self):
        if (self.block % self.stride or self.kernel % self.stride
                or self.kernel > self.block):
            raise ValueError(
                f"block {self.block} and kernel {self.kernel} have to be "
                f"multiples of the stride {self.stride}, the kernel no "
                f"longer than a block")
        forced = self.init_blocks + -(-self.window // self.block) + 1
        if forced > self.topk:
            raise ValueError(
                f"the {forced} blocks always kept (the first "
                f"{self.init_blocks}, a window of {self.window}) exceed "
                f"topk {self.topk}")

    @property
    def per_block(self) -> int:
        """Kernels that start in one block."""
        return self.block // self.stride

    def kernels_in(self, length: int) -> int:
        """Whole kernels over ``length`` keys."""
        return max(length - self.kernel + self.stride, 0) // self.stride

    def widest_read(self, blocks: int) -> int:
        """Blocks a query over a context of ``blocks`` reads at most: a
        query below ``dense_len`` keeps up to ``dense_len / block``."""
        return min(blocks, max(self.topk, -(-self.dense_len // self.block)))

    def blocks_read(self, length: int) -> int:
        """Blocks a query behind ``length`` cached keys reads."""
        live = length // self.block + 1
        return live if length < self.dense_len else min(live, self.topk)


def pool_keys(keys, geom: Selection):
    """``keys`` (B, T, Hkv, D), T a multiple of the stride -> the kernels
    that lie inside them, (B, T / stride - kernel / stride + 1, Hkv, D)
    float32: kernel j is the mean of keys ``stride j .. stride j + kernel
    - 1``."""
    b, t = keys.shape[:2]
    n = geom.kernel // geom.stride
    means = keys.astype(jnp.float32).reshape(
        (b, t // geom.stride, geom.stride) + keys.shape[2:]).mean(2)
    count = means.shape[1] - n + 1
    return sum(means[:, i:i + count] for i in range(n)) / n


def kernel_scores(qg, kernels, qpos, geom: Selection, scale: float):
    """``qg`` (B, Sq, Hkv, G, D), ``kernels`` (B, Lc, Hkv, D), ``qpos``
    (B, Sq) -> ``s`` (B, Hkv, Sq, Lc) float32, -inf at the kernels a
    query does not see yet."""
    logits = jnp.einsum("bqhgd,blhd->bhgql", qg, kernels,
                        preferred_element_type=jnp.float32) * scale
    last_key = jax.lax.iota(jnp.int32, kernels.shape[1]) * geom.stride \
        + geom.kernel - 1
    seen = (last_key[None, None, :] <= qpos[:, :, None])[:, None]
    probs = jax.nn.softmax(jnp.where(seen[:, :, None], logits, NEG), axis=-1)
    return jnp.where(seen, probs.sum(2), -jnp.inf)


def block_scores(s, qpos, geom: Selection):
    """``s`` (B, Hkv, Sq, blocks * per_block) the kernels' scores ->
    (B, Hkv, Sq, blocks): each block's largest over the kernels that
    touch it, infinity at the blocks always kept (all of them for a query
    below ``dense_len``), -inf past the query's own block."""
    r = geom.per_block
    nb = s.shape[-1] // r
    own = s.reshape(s.shape[:-1] + (nb, r))
    score = own.max(-1)
    # kernels that start in block b - 1 and end in block b
    spill = [j for j in range(r)
             if j * geom.stride + geom.kernel - 1 >= geom.block]
    if spill:
        over = own[..., spill[0]:].max(-1)
        score = jnp.maximum(score, jnp.concatenate(
            [jnp.full_like(over[..., :1], -jnp.inf), over[..., :-1]], -1))
    b = jax.lax.iota(jnp.int32, nb)
    last = (qpos // geom.block)[..., None]                    # (B, Sq, 1)
    first_near = (jnp.maximum(qpos - (geom.window - 1), 0)
                  // geom.block)[..., None]
    kept = ((b < geom.init_blocks) | (b >= first_near)
            | (qpos < geom.dense_len)[..., None])
    score = jnp.where(kept[:, None], jnp.inf, score)
    return jnp.where((b <= last)[:, None], score, -jnp.inf)


def select(qg, kernels, qpos, geom: Selection, scale: float, count: int):
    """The ``count`` blocks of highest score a query and key-value head:
    ids (B, Hkv, Sq, count) int32, best first. Below ``dense_len`` every
    block up to the query's own is kept, so ``count`` has to cover them
    there."""
    with sub_scope("select"):
        score = block_scores(kernel_scores(qg, kernels, qpos, geom, scale),
                             qpos, geom)
        return jax.lax.top_k(score, count)[1].astype(jnp.int32)


def picked_blocks(ids, blocks: int):
    """ids (..., count) -> (..., blocks) bool, True at the picked."""
    return (ids[..., None] == jax.lax.iota(jnp.int32, blocks)).any(-2)


def attend_blocked(qg, read, picked, qpos, spans, geom: Selection,
                   span_blocks: int, scale: float):
    """Attention of ``qg`` (B, Sq, Hkv, G, D) over a context read
    ``span_blocks`` key blocks at a time: ``read(j)`` gives span j's keys
    and values, (B, span_blocks, Hkv, block, D) each; ``picked`` (B, Hkv,
    Sq, blocks) says which blocks a query reads, ``qpos`` (B, Sq) bounds
    them causally; ``spans`` (traced) is how many spans hold a key any
    query sees. A running softmax: nothing wider than (Sq, span) exists.
    Returns (B, Sq, Hkv * G, D) in the queries' dtype."""
    b, sq, hkv, g, d = qg.shape
    span = span_blocks * geom.block

    def body(j, carry):
        m, l, acc = carry
        k, v = read(j)
        s = jnp.einsum("bqhgd,bchkd->bhgqck", qg, k,
                       preferred_element_type=jnp.float32)
        s = s.reshape(b, hkv, g, sq, span) * scale
        kpos = j * span + jax.lax.iota(jnp.int32, span)
        see = jnp.repeat(jax.lax.dynamic_slice_in_dim(
            picked, j * span_blocks, span_blocks, axis=3), geom.block, axis=3)
        see = (see & (kpos <= qpos[:, None, :, None]))[:, :, None]
        m_new = jnp.maximum(m, jnp.where(see, s, NEG).max(-1))
        p = jnp.where(see, jnp.exp(s - m_new[..., None]), 0.0)
        fade = jnp.exp(m - m_new)
        pv = jnp.einsum(
            "bhgqck,bchkd->bhgqd",
            p.reshape(b, hkv, g, sq, span_blocks, geom.block).astype(v.dtype),
            v, preferred_element_type=jnp.float32)
        return m_new, l * fade + p.sum(-1), acc * fade[..., None] + pv

    with sub_scope("attend"):
        init = (jnp.full((b, hkv, g, sq), NEG, jnp.float32),
                jnp.zeros((b, hkv, g, sq), jnp.float32),
                jnp.zeros((b, hkv, g, sq, d), jnp.float32))
        _, l, acc = jax.lax.fori_loop(0, spans, body, init)
        out = acc / l[..., None]                              # (B, Hkv, G, Sq, D)
        return jnp.moveaxis(out, 3, 1).reshape(b, sq, hkv * g, d).astype(
            qg.dtype)


@register_op
class BlockSparseAttention(Op):
    """The layer of the module's docstring. Matrices keep 2-D shapes,
    heads side by side in the columns."""

    op_type = OpType.BLOCK_SPARSE_ATTENTION

    def __init__(self, layer, input_shapes):
        super().__init__(layer, input_shapes)
        a = self.attrs
        self.embed_dim: int = input_shapes[0].sizes[-1]
        self.num_heads = int(a["num_heads"])
        self.kv_heads = int(a["num_kv_heads"])
        self.head_dim = int(a["head_dim"])
        if self.num_heads % self.kv_heads:
            raise ValueError(f"{self.name}: {self.num_heads} heads over "
                             f"{self.kv_heads} key-value heads")
        self.group = self.num_heads // self.kv_heads
        self.eps = float(a.get("eps", 1e-6))
        self.geom = Selection(**a["selection"])
        self.scale = self.head_dim ** -0.5
        self.causal = True

    def infer_output_shapes(self):
        return [(self.input_shapes[0].sizes, self.input_shapes[0].dtype)]

    def weight_specs(self) -> List[WeightSpec]:
        dt = self.input_shapes[0].dtype
        init = self.attrs.get("kernel_initializer") or DefaultWeightInitializer()
        gain = self.attrs.get("gain_initializer") or ConstantInitializer(1.0)
        e, d = self.embed_dim, self.head_dim
        qw, kw = self.num_heads * d, self.kv_heads * d
        return [
            WeightSpec("wq", (e, qw), dt, init),
            WeightSpec("wk", (e, kw), dt, init),
            WeightSpec("wv", (e, kw), dt, init),
            WeightSpec("wg", (e, qw), dt, init),
            WeightSpec("q_norm", (d,), dt, gain, weight_decay=False),
            WeightSpec("k_norm", (d,), dt, gain, weight_decay=False),
            WeightSpec("wo", (qw, e), dt, init),
        ]

    # ---- the pieces serving composes (serving/cache_entry.py) -------------
    @sub_scope("project")
    def project(self, weights, x):
        """(B, S, E) -> the queries grouped by key-value head (B, S, Hkv,
        G, D) and the keys and values (B, S, Hkv, D), q and k normed."""
        b, s, _ = x.shape
        d = self.head_dim
        q = rms_norm(_mm(x, weights["wq"]).reshape(b, s, self.kv_heads,
                                                    self.group, d),
                     weights["q_norm"], self.eps)
        k = rms_norm(_mm(x, weights["wk"]).reshape(b, s, self.kv_heads, d),
                     weights["k_norm"], self.eps)
        return q, k, _mm(x, weights["wv"]).reshape(b, s, self.kv_heads, d)

    @sub_scope("project")
    def finish(self, weights, x, o):
        """The attended (B, S, H, D) values -> (B, S, E): the output gate,
        then ``W_o``."""
        b, s = o.shape[:2]
        z = jnp.dot(x, weights["wg"], preferred_element_type=jnp.float32)
        o = o.reshape(b, s, -1).astype(jnp.float32) * jax.nn.sigmoid(z)
        return _mm(o.astype(x.dtype), weights["wo"])

    def attend_dense(self, qg, keys, values, qpos):
        """The plain rectangle: ``qg`` (B, Sq, Hkv, G, D) at positions
        ``qpos`` (B, Sq) over ``keys``, ``values`` (B, L, Hkv, D), L a
        multiple of the block, kernels made from the keys themselves.
        Returns ((B, Sq, H, D), the picked ids (B, Hkv, Sq, count))."""
        g = self.geom
        b, length = keys.shape[:2]
        sq = qg.shape[1]
        nb = length // g.block
        kernels = pool_keys(keys, g).astype(keys.dtype)
        kernels = jnp.pad(kernels, ((0, 0), (0, nb * g.per_block
                                             - kernels.shape[1]),
                                    (0, 0), (0, 0)))
        count = g.widest_read(nb)
        ids = select(qg, kernels, qpos, g, self.scale, count)
        with sub_scope("attend"):
            # of what a query past dense_len picked, the first topk count
            rank = jax.lax.iota(jnp.int32, count) < g.topk
            counted = rank | (qpos < g.dense_len)[:, None, :, None]
            see = picked_blocks(jnp.where(counted, ids, nb), nb)
            see = jnp.repeat(see, g.block, axis=-1) & (
                jax.lax.iota(jnp.int32, length) <= qpos[:, None, :, None])
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, keys,
                           preferred_element_type=jnp.float32) * self.scale
            p = jax.nn.softmax(jnp.where(see[:, :, None], s, NEG), axis=-1)
            o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(values.dtype),
                           values, preferred_element_type=jnp.float32)
        return (o.reshape(b, sq, self.num_heads, -1).astype(qg.dtype),
                ids[..., :min(count, g.topk)])

    def whole(self, weights, x, offset=0, cache=None):
        """A block of S tokens at ``offset`` behind ``cache`` (keys and
        values (B, L, Hkv, D), which it is written into; None: nothing
        behind it). Returns (y (B, S, E), the cache, the picked ids)."""
        b, s, _ = x.shape
        qg, k, v = self.project(weights, x)
        if cache is None:
            pad = -s % self.geom.block
            cache = tuple(jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                          for a in (k, v))
        else:
            cache = tuple(jax.lax.dynamic_update_slice(
                c, a.astype(c.dtype), (0, offset, 0, 0))
                for c, a in zip(cache, (k, v)))
        qpos = jnp.broadcast_to(offset + jax.lax.iota(jnp.int32, s), (b, s))
        o, ids = self.attend_dense(qg, cache[0].astype(x.dtype),
                                   cache[1].astype(x.dtype), qpos)
        return self.finish(weights, x, o), cache, ids

    def forward(self, ctx, inputs, weights):
        return [self.whole(weights, inputs[0])[0]]

    def flops(self) -> float:
        b, s = self.input_shapes[0].sizes[:2]
        e, d, g = self.embed_dim, self.head_dim, self.geom
        proj = 2.0 * b * s * e * d * (3 * self.num_heads + 2 * self.kv_heads)
        # a query reads min(its context, topk blocks): the mean over
        # positions 0 .. s - 1, for scores and for the weighted sum
        cap = g.topk * g.block
        keys = s / 2.0 if s <= cap else cap - cap * cap / (2.0 * s)
        attend = 4.0 * b * s * self.num_heads * d * keys
        scored = 2.0 * b * s * self.num_heads * d * s / (2.0 * g.stride)
        return proj + attend + scored
