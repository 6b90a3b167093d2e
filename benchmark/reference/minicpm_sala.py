"""Plain MiniCPM-SALA (``openbmb/MiniCPM-SALA`` on the Hugging Face hub,
``model_type`` ``minicpm_sala``) in ``jax.numpy``: the yardstick the
benchmark compares the program with. Nothing here imports ``flexflow_tpu``
and nothing here is fast: no cache, no kernel, no chunks of a prompt; the
recurrence of a linear layer runs token by token (``lax.scan`` over
``t``); a sparse layer scores every kernel and every key for a block of
queries at a time (so that 17k positions fit beside a program that fills
the chip), and picks its blocks from a (kernels, blocks) table of which
kernel touches which block.

The layer equations. ``x`` is (B, S, E); every norm an RMSNorm with a gain
(eps ``rms_norm_eps``); ``norm_D`` is one over a head's D = 128 values with
a gain of width D shared by the heads; ``mixer_types`` says layer by layer
which mixer a block has; layer i of the file is layer ``first_layer + i``
of the ``published.num_hidden_layers`` = L published ones.

* block (pre-norm, the MiniCPM line): ``h = x + r mixer(RMSNorm(x))``,
  ``y = h + r mlp(RMSNorm(h))``, ``r = scale_depth / sqrt(L)`` (the
  PUBLISHED depth), ``mlp(u) = (silu(u W_gate) * (u W_up)) W_down``; the
  token embedding times ``scale_emb``; a final RMSNorm; logits ``= (h /
  (hidden_size / dim_model_base)) W_head``, the head untied.
* ``lightning-attn`` (H = ``lightning_nh`` = ``lightning_nkv`` heads of
  ``lightning_head_dim``): with ``u = RMSNorm(x)``, ``q = norm_D(u W_q)``,
  ``k = norm_D(u W_k)`` (``qk_norm``), ``v = u W_v``; the rotate-half
  rotary embedding on q and k at the token's absolute position, theta
  ``rope_theta`` (``lightning_use_rope``); per head ``S_t = lambda_h
  S_(t-1) + k_t^T v_t`` (D x D, float32, zero before the sequence),
  ``o_t = (q_t / sqrt(D)) S_t`` (``lightning_scale``); ``o = norm_D(o)``
  (``use_output_norm``) ``* sigmoid(u W_g)`` (``use_output_gate``); out
  ``= o W_o``. ``lambda_h = exp(-s_h)``, ``s_h = 2^(-8 (h + 1) / H) * (1 -
  l / (L - 1) + 1e-5)`` for head h of published layer l (``assumed``).
* ``minicpm4`` (``num_attention_heads`` query heads on
  ``num_key_value_heads`` key-value heads of ``head_dim``, query head h
  reading key-value head ``h // group``; no rotary embedding:
  ``attn_use_rope`` false): ``q = norm_D(u W_q)``, ``k = norm_D(u W_k)``,
  ``v = u W_v``. A query at position ``p < dense_len`` attends every
  position ``<= p``. At ``p >= dense_len``, per key-value head: kernels
  ``c_i = mean(k[stride i : stride i + kernel])`` for every i with
  ``stride i + kernel - 1 <= p``; ``a_(h, i) = softmax_i(q_h . c_i /
  sqrt(D))`` for each of the group's heads and ``s_i = sum_h a_(h, i)``; a
  block b (positions ``block b .. block b + block - 1``) scores the
  largest ``s_i`` over the kernels that touch it; the first
  ``init_blocks`` blocks and the blocks touching the last ``window``
  positions score infinity; the ``topk`` blocks of highest score among
  blocks ``0 .. p // block`` are kept (they count the forced ones), and
  the group's heads take a causal softmax over the keys of those blocks
  only. ``o = o * sigmoid(u W_g)`` (``attn_use_output_gate``); out ``= o
  W_o``.

Departures from the published code (each also a line of the
configuration's ``assumed``): the six sizes of ``sparse_config`` (the
catalog's config lacks the block; MiniCPM4's published values); the decay's
slopes (Lightning Attention-2's published form, by published layer); the
switch at ``dense_len`` by the QUERY's position (so that a request's
logits do not depend on where prefill ends and decoding starts); forced
blocks counted inside the ``topk``; ``mup_denominator`` read by no
equation; separate ``W_q, W_k, W_v, W_g`` (a fused layout is the same
function of random weights); no biases.

Weights are **bfloat16**, held once on the device; the forward upcasts
them piece by piece (a Python loop over layers of small jitted pieces, one
matrix product a piece, a few thousand positions a piece, the head in
slices of the vocabulary and only for the positions asked for).

``precision`` chooses how every matrix product is computed (projections,
the MLP, the kernels' scores, attention's scores and weighted sum, the
head): ``float32`` (``highest``; the reference), ``bfloat16`` (operands
rounded, float32 accumulation: what the configuration states the program
computes in), ``float8`` (operands rounded to e4m3, saturating: the
control, which the comparison has to refuse). Norms, gates, softmax, the
pooling of keys and the recurrence are float32 at every precision.

The block selection is discontinuous, as routing is. :func:`forward` takes
``selection`` (per sparse layer the block ids a program picked) and then
attends THOSE blocks, while still scoring the blocks itself on the same
input: per sparse layer it returns where the two sets differ and by what
margin of its own scores (``benchmark/selected.py`` reads them).
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("float32", "bfloat16", "float8")
INIT_STD = 0.02
SPARSE, LINEAR = "minicpm4", "lightning-attn"
HEAD_SLICES = 8      # the head's product, in this many slices of the vocabulary
ROWS = 4096          # positions a piece of a position-wise product takes
QUERIES = 128        # queries a piece of a sparse layer takes
E4M3_MAX = 448.0


def fold_seed(seed: int) -> jax.Array:
    """A PRNG key (``rbg``: the device's own bit generator) from any
    non-negative whole number: the low 31 bits seed it, the rest is
    folded in."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed {seed} < 0")
    key = jax.random.key(seed & 0x7FFFFFFF, impl="rbg")
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def sizes(config: Dict) -> Dict:
    types = list(config["mixer_types"])
    if len(types) != int(config["num_hidden_layers"]):
        raise ValueError(f"{len(types)} mixer_types for "
                         f"{config['num_hidden_layers']} layers")
    for key in ("qk_norm", "lightning_use_rope", "use_output_norm",
                "use_output_gate", "attn_use_output_gate"):
        if not config.get(key, True):
            raise ValueError(f"{key} false is not written here")
    if config.get("attn_use_rope") or config.get("attention_bias"):
        raise ValueError("sparse layers with a rotary embedding or biases "
                         "are not written here")
    lh = int(config["lightning_nh"])
    if int(config["lightning_nkv"]) != lh:
        raise ValueError("linear layers with fewer key heads than heads "
                         "are not written here")
    sp = config["sparse_config"]
    published = config.get("published") or {}
    return {
        "vocab": int(config["vocab_size"]), "e": int(config["hidden_size"]),
        "types": types, "heads": int(config["num_attention_heads"]),
        "kv": int(config["num_key_value_heads"]),
        "d": int(config["head_dim"]), "lh": lh,
        "ld": int(config["lightning_head_dim"]),
        "width": int(config["intermediate_size"]),
        "depth": int(published.get("num_hidden_layers",
                                   config["num_hidden_layers"])),
        "first": int(config.get("first_layer", 0)),
        "kernel": int(sp["kernel_size"]), "stride": int(sp["kernel_stride"]),
        "block": int(sp["block_size"]), "window": int(sp["window_size"]),
        "dense_len": int(sp["dense_len"]),
        "init_blocks": int(sp["init_blocks"]), "topk": int(sp["topk"])}


def param_shapes(config: Dict) -> Dict[str, Tuple[int, ...]]:
    z = sizes(config)
    e = z["e"]
    shapes = {"embed": (z["vocab"], e), "norm_f": (e,),
              "lm_head": (e, z["vocab"])}
    for i, kind in enumerate(z["types"]):
        p = f"l{i}."
        if kind == LINEAR:
            w = z["lh"] * z["ld"]
            shapes.update({p + "wq": (e, w), p + "wk": (e, w),
                           p + "wv": (e, w), p + "wg": (e, w),
                           p + "wo": (w, e), p + "q_norm": (z["ld"],),
                           p + "k_norm": (z["ld"],),
                           p + "o_norm": (z["ld"],)})
        elif kind == SPARSE:
            qw, kw = z["heads"] * z["d"], z["kv"] * z["d"]
            shapes.update({p + "wq": (e, qw), p + "wk": (e, kw),
                           p + "wv": (e, kw), p + "wg": (e, qw),
                           p + "wo": (qw, e), p + "q_norm": (z["d"],),
                           p + "k_norm": (z["d"],)})
        else:
            raise ValueError(f"layer {i}: {kind!r} is neither {SPARSE!r} "
                             f"nor {LINEAR!r}")
        shapes.update({p + "norm1": (e,), p + "norm2": (e,),
                       p + "mlp.gate": (e, z["width"]),
                       p + "mlp.up": (e, z["width"]),
                       p + "mlp.down": (z["width"], e)})
    return shapes


def param_count(config: Dict) -> int:
    return sum(int(np.prod(s)) for s in param_shapes(config).values())


@functools.partial(jax.jit, static_argnames=("shape", "gain"))
def _draw(key, scale, *, shape, gain):
    """One weight, bfloat16: a gain (1 + N(0, 0.02)) or a matrix (N(0,
    0.02) times ``scale``)."""
    x = INIT_STD * jax.random.normal(key, shape, jnp.float32)
    return (1.0 + x if gain else x * scale).astype(jnp.bfloat16)


def init_weights(config: Dict, seed: int) -> Dict[str, jax.Array]:
    """Every weight, bfloat16, made on the device from the seed: matrices
    and the embedding N(0, 0.02), the projections back into the residual
    stream (``wo``, ``mlp.down``) over sqrt(2 * published layers), norm
    gains 1 + N(0, 0.02) so that a dropped gain shows. The decay has no
    weight: its slopes are fixed."""
    key = fold_seed(seed)
    resid = 1.0 / math.sqrt(2.0 * sizes(config)["depth"])
    out = {}
    for i, (name, shape) in enumerate(sorted(param_shapes(config).items())):
        leaf = name.rsplit(".", 1)[-1]
        out[name] = _draw(jax.random.fold_in(key, i),
                          jnp.float32(resid if leaf in ("wo", "down")
                                      else 1.0),
                          shape=tuple(shape), gain="norm" in leaf)
    return out


def decay_slopes(heads: int, layer: int, depth: int) -> np.ndarray:
    """``s_h`` of the module's docstring, float32 (H,)."""
    h = np.arange(1, heads + 1, dtype=np.float64)
    return (2.0 ** (-8.0 * h / heads)
            * (1.0 - layer / max(depth - 1, 1) + 1e-5)).astype(np.float32)


# ---- arithmetic ------------------------------------------------------------

def _round(x, precision: str):
    if precision == "bfloat16":
        # reduce_precision, not astype and back: XLA may drop the pair
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    if precision == "float8":
        # saturating, as a float8 unit converts: e4m3 has no infinity
        return jnp.clip(x, -E4M3_MAX, E4M3_MAX).astype(
            jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def _mm(spec: str, a, b, precision: str):
    a = _round(a.astype(jnp.float32), precision)
    b = _round(b.astype(jnp.float32), precision)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms(x, g, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * g.astype(jnp.float32))


def _key(config: Dict) -> Tuple:
    flat = dict(config, **{f"sparse_config.{k}": v for k, v in
                           config["sparse_config"].items()},
                depth=sizes(config)["depth"])
    return tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v)
        for k, v in flat.items()
        if isinstance(v, (int, float, str, bool)) or k == "mixer_types"))


def _in_pieces(fn, rows: int, *arrays):
    """``fn`` over pieces of ``rows`` positions (axis 1) of the arrays."""
    s = arrays[0].shape[1]
    return jnp.concatenate([fn(*(a[:, at:at + rows] for a in arrays))
                            for at in range(0, s, rows)], axis=1)


@functools.lru_cache(maxsize=None)
def _pieces(config_items: Tuple, precision: str):
    """The jitted pieces of one configuration and precision. A piece
    holds at most ONE matrix product over at most ``ROWS`` positions, so
    that the float32 copy of a bfloat16 weight and a product's output
    live for that piece alone."""
    config = {k: (list(v) if isinstance(v, tuple) else v)
              for k, v in config_items}
    config["sparse_config"] = {
        k.split(".", 1)[1]: v for k, v in config.items()
        if k.startswith("sparse_config.")}
    z = sizes(dict(config, published={"num_hidden_layers": config["depth"]}))
    eps = float(config.get("rms_norm_eps", 1e-6))
    theta = float(config.get("rope_theta", 10000.0))
    heads, kv, d, lh, ld = z["heads"], z["kv"], z["d"], z["lh"], z["ld"]
    group = heads // kv
    kernel, stride, block = z["kernel"], z["stride"], z["block"]

    @jax.jit
    def project_piece(x, w):
        return _mm("bsi,io->bso", x, w, precision)

    def project(x, w):
        """(B, S, in) x (in, out) -> (B, S, out)."""
        return _in_pieces(lambda a: project_piece(a, w), ROWS, x)

    @jax.jit
    def head_norm(y, g):
        """norm_D over each head of (B, S, H D)."""
        b, s, _ = y.shape
        return _rms(y.reshape(b, s, -1, g.shape[0]), g, eps)

    @jax.jit
    def rotate(x):
        """The rotate-half rotary embedding at positions 0 .. S - 1: the
        pairs ``(x[i], x[i + D/2])`` turned by ``p theta^(-2i/D)``, as ``x
        cos + turned(x) sin`` with ``turned(x) = [-x[D/2:], x[:D/2]]``
        (the halves are not cut and joined again: the chip's compiler
        fails on that at these shapes)."""
        dim = x.shape[-1]
        half = dim // 2
        inv = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
        ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
        ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
        turned = jnp.where(jnp.arange(dim) < half, -jnp.roll(x, -half, -1),
                           jnp.roll(x, half, -1))
        return x * jnp.cos(ang) + turned * jnp.sin(ang)

    @jax.jit
    def recurrence(q, k, v, slopes):
        """(B, S, H, D) each, token by token; (B, S, H, D)."""
        lam = jnp.exp(-slopes)[None, :, None, None]

        def token(state, xs):              # state (B, H, D, D)
            qt, kt, vt = xs
            state = lam * state + kt[..., :, None] * vt[..., None, :]
            return state, jnp.sum(state * qt[..., None], axis=-2)

        t_first = lambda a: jnp.moveaxis(a, 1, 0)  # noqa: E731
        b = q.shape[0]
        _, o = jax.lax.scan(token, jnp.zeros((b, lh, ld, ld), jnp.float32),
                            tuple(map(t_first, (q * ld ** -0.5, k, v))))
        return jnp.moveaxis(o, 0, 1)

    @jax.jit
    def gated(o, z_gate):
        return o.reshape(z_gate.shape) * jax.nn.sigmoid(z_gate)

    def linear_mixer(u, w, layer):
        q = rotate(head_norm(project(u, w["wq"]), w["q_norm"]))
        k = rotate(head_norm(project(u, w["wk"]), w["k_norm"]))
        b, s, _ = u.shape
        v = project(u, w["wv"]).reshape(b, s, lh, ld)
        o = recurrence(q, k, v, jnp.asarray(
            decay_slopes(lh, z["first"] + layer, z["depth"])))
        o = _rms(o, w["o_norm"], eps)
        return project(gated(o, project(u, w["wg"])), w["wo"]), None

    @jax.jit
    def pooled(k):
        """The kernels of (B, S, Hkv, D) keys: (B, n, Hkv, D), kernel i
        the mean of keys ``stride i .. stride i + kernel - 1``."""
        n = max((k.shape[1] - kernel) // stride + 1, 0)
        idx = (jnp.arange(n) * stride)[:, None] + jnp.arange(kernel)
        return k[:, idx].mean(2) if n else k[:, :0]

    @functools.partial(jax.jit, static_argnames=("forced",))
    def attend_piece(q, k, v, kernels, first, picks, forced):
        """``q`` (B, Q, Hkv, G, D) the queries at positions ``first ..
        first + Q - 1`` over the whole (B, S, Hkv, D) keys and values.
        ``picks`` (B, Hkv, Q, topk) the blocks to attend past
        ``dense_len`` where ``forced``, else its own choice. Returns the
        attended (B, Q, H, D), its own picks, where the given picks
        differ from them and by what share of its own lowest score."""
        b, nq = q.shape[:2]
        s = k.shape[1]
        nb = -(-s // block)
        pos = first + jnp.arange(nq)                                  # (Q,)
        scale = d ** -0.5
        # the kernels' scores: softmax over those a query sees, summed
        # over the group's heads
        n = kernels.shape[1]
        logits = _mm("bqhgd,bnhd->bhgqn", q, kernels, precision) * scale
        seen = (jnp.arange(n) * stride + kernel - 1)[None, :] <= pos[:, None]
        probs = jax.nn.softmax(jnp.where(seen, logits, -jnp.inf), axis=-1)
        score_k = jnp.where(seen, jnp.nan_to_num(probs).sum(2), -jnp.inf)
        # which kernel touches which block
        lo = jnp.arange(n) * stride
        blk = jnp.arange(nb) * block
        touch = ((lo[:, None] + kernel - 1 >= blk[None, :])
                 & (lo[:, None] <= blk[None, :] + block - 1))       # (n, nb)
        score = jnp.max(jnp.where(touch, score_k[..., None], -jnp.inf),
                        axis=-2)                              # (B, Hkv, Q, nb)
        b_idx = jnp.arange(nb)
        near = b_idx[None, :] >= (jnp.maximum(pos - (z["window"] - 1), 0)
                                  // block)[:, None]
        score = jnp.where((b_idx < z["init_blocks"])[None, :] | near,
                          jnp.inf, score)
        valid = b_idx[None, :] <= (pos // block)[:, None]            # (Q, nb)
        score = jnp.where(valid, score, -jnp.inf)
        count = picks.shape[-1]
        own = jax.lax.top_k(score, count)[1]
        own_set = (own[..., None] == b_idx).any(-2) & valid
        if forced:
            got_set = (picks[..., None] == b_idx).any(-2) & valid
            floor = jnp.min(jnp.where(own_set, score, jnp.inf), axis=-1)
            short = jnp.max(jnp.where(got_set & ~own_set,
                                      (floor[..., None] - score)
                                      / jnp.maximum(floor[..., None], 1e-30),
                                      0.0), axis=-1)
            differ = (got_set != own_set).any(-1)
        else:
            got_set, short = own_set, jnp.zeros(own_set.shape[:-1])
            differ = jnp.zeros(own_set.shape[:-1], bool)
        sparse = (pos >= z["dense_len"])[:, None]
        reads = jnp.where(sparse, got_set, valid)             # (B, Hkv, Q, nb)
        kpos = jnp.arange(s)
        see = (jnp.repeat(reads, block, axis=-1)[..., :s]
               & (kpos[None, :] <= pos[:, None]))
        att = _mm("bqhgd,bkhd->bhgqk", q, k, precision) * scale
        p = jax.nn.softmax(jnp.where(see[:, :, None], att, -jnp.inf), axis=-1)
        o = _mm("bhgqk,bkhd->bqhgd", p, v, precision)
        past = sparse[:, 0]
        return (o.reshape(b, nq, heads, d), own, differ & past,
                jnp.where(past, short, 0.0))

    def sparse_mixer(u, w, picks):
        b, s, _ = u.shape
        q = head_norm(project(u, w["wq"]), w["q_norm"]).reshape(
            b, s, kv, group, d)
        k = head_norm(project(u, w["wk"]), w["k_norm"])
        v = project(u, w["wv"]).reshape(b, s, kv, d)
        kernels = pooled(k)
        count = min(z["topk"], -(-s // block))
        outs, own, differ, short = [], [], [], []
        for at in range(0, s, QUERIES):
            given = (jnp.zeros((b, kv, min(QUERIES, s - at), count),
                               jnp.int32) if picks is None
                     else jnp.asarray(picks)[:, :, at:at + QUERIES])
            o, ids, df, sh = attend_piece(
                q[:, at:at + QUERIES], k, v, kernels, at, given,
                forced=picks is not None)
            outs.append(o), own.append(ids), differ.append(df)
            short.append(sh)
        o = jnp.concatenate(outs, axis=1)
        info = {"own_ids": jnp.concatenate(own, axis=2),
                "differ": jnp.concatenate(differ, axis=2),
                "shortfall": jnp.concatenate(short, axis=2)}
        return project(gated(o, project(u, w["wg"])), w["wo"]), info

    r = float(config["scale_depth"]) / math.sqrt(z["depth"])

    @jax.jit
    def normed(x, g):
        return _rms(x, g, eps)

    @jax.jit
    def residual(x, branch):
        return x + r * branch

    @jax.jit
    def gate(a, up):
        return jax.nn.silu(a) * up

    def mlp(u, w):
        def piece(a):
            return project_piece(gate(project_piece(a, w["mlp.gate"]),
                                      project_piece(a, w["mlp.up"])),
                                 w["mlp.down"])
        return _in_pieces(piece, ROWS, u)

    def head(x, g, lm_head):
        u = normed(x, g) / (z["e"] / float(config["dim_model_base"]))
        cols = lm_head.shape[1]
        step = -(-cols // HEAD_SLICES)
        return jnp.concatenate([project(u, lm_head[:, c:c + step])
                                for c in range(0, cols, step)], axis=-1)

    @jax.jit
    def embed(table, tokens):
        return table[tokens].astype(jnp.float32) * float(config["scale_emb"])

    return {LINEAR: linear_mixer, SPARSE: sparse_mixer, "normed": normed,
            "residual": residual, "mlp": mlp, "head": head, "embed": embed}


def forward(weights: Dict, tokens, config: Dict, precision: str = "float32",
            selection: Optional[List] = None, rows: Optional[int] = None):
    """``tokens`` (B, S) int32 -> (logits (B, rows or S, V) float32 of the
    last ``rows`` positions (None: all), and per sparse layer a dict of
    ``own_ids`` (B, Hkv, S, picks), ``differ`` (B, Hkv, S) and
    ``shortfall`` (B, Hkv, S)). ``selection``: per sparse layer the (B,
    Hkv, S, picks) block ids to attend past ``dense_len`` in place of the
    layer's own."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    f = _pieces(_key(config), precision)
    x = f["embed"](weights["embed"], jnp.asarray(tokens))
    info: List[Dict] = []
    for i, kind in enumerate(config["mixer_types"]):
        p = f"l{i}."
        w = {k[len(p):]: v for k, v in weights.items() if k.startswith(p)}
        u = f["normed"](x, w["norm1"])
        if kind == SPARSE:
            mixed, said = f[SPARSE](
                u, w, None if selection is None else selection[len(info)])
            info.append(said)
        else:
            mixed, _ = f[LINEAR](u, w, i)
        h = f["residual"](x, mixed)
        # waited for layer by layer: dispatched ahead, the pieces' buffers
        # would all be reserved at once
        x = jax.block_until_ready(
            f["residual"](h, f["mlp"](f["normed"](h, w["norm2"]), w)))
    if rows is not None:
        x = x[:, x.shape[1] - rows:]
    return f["head"](x, weights["norm_f"], weights["lm_head"]), info


def forward_jit(weights: Dict, tokens, config: Dict,
                precision: str = "float32"):
    """``tokens`` (B, S) int32 -> logits (B, S, V) float32, the layers'
    own selection: as ``benchmark/serving.py`` calls it."""
    return forward(weights, tokens, config, precision)[0]
