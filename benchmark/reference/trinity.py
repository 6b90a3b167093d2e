"""Plain Trinity (``arcee-ai/Trinity-Large-Preview`` on the Hugging Face
hub, ``model_type`` ``afmoe``) in ``jax.numpy``: the yardstick the
benchmark compares the program with. Nothing here imports
``flexflow_tpu`` and nothing here is fast: no cache, no kernels, no
grouped products; every layer attends the whole sequence under its mask
(a few hundred queries at a time, so that six thousand positions fit
beside the program), and every token goes through every held expert and
is weighted by its gate (0 where it was not routed).

The layer equations (``rms`` an RMSNorm of ``rms_norm_eps`` with a gain;
no bias on any linear map). ``h0 = E[tokens] * sqrt(hidden_size)``
(``mup_enabled``); ``layer_types`` names each layer's attention:

* ``a = rms_in(h)``; ``q = a Wq`` (H = ``num_attention_heads`` heads of D
  = ``head_dim``), ``k = a Wk``, ``v = a Wv`` (``num_key_value_heads``
  heads), ``g = a Wg`` (H D columns); ``q = rms_q(q)``, ``k = rms_k(k)``
  over each head's D values, one gain of D for all heads;
* ``sliding_attention``: q and k are rotated by their positions
  (``rope_theta``, all D dimensions, the pairs ``(x[i], x[i + D/2])``),
  and key j is seen from position p iff ``0 <= p - j < sliding_window``;
  ``full_attention``: no positions at all, every key ``j <= p`` is seen;
* query head i reads key-value head ``i // (H / Hkv)``; scores ``q . k /
  sqrt(D)``, softmax in float32; ``h = h + rms_post_attn((attended *
  sigmoid(g)) Wo)``;
* ``m = rms_pre_mlp(h)``; the first ``num_dense_layers`` layers: ``f =
  (silu(m W1) * (m W3)) W2`` of width ``intermediate_size``; after them
  ``s = sigmoid(float32(m) Wr)`` over ALL the published experts, in
  float32 at every ``precision``; ``T`` = the ``num_experts_per_tok``
  largest of ``s + b`` (``b`` the selection bias, in the choice only);
  ``w_e = route_scale * s_e / (sum_T s + 1e-20)`` (``route_norm``); ``f =
  shared(m) + sum_{e in T} w_e expert_e(m)``, the shared expert and every
  routed one a gated SiLU MLP of width ``moe_intermediate_size``;
* ``h = h + rms_post_mlp(f)``; after the last layer a final ``rms`` and
  the untied head.

What the published ``config.json`` does not settle is set by the
family's published code, and listed once, in the configuration file's
``assumed`` block (``configs/trinity-large-ep8.json``).

**The share.** A configuration file may describe one holder of a larger
deployment: ``num_experts`` is then the experts HELD (a contiguous run
from ``expert_first``, default 0) while the router and its bias keep
``published.num_experts`` columns, and ``vocab_size`` the rows of the
vocabulary held. The forward computes that holder's partial result: the
routed sum runs over the held experts of ``T`` only; nothing stands in
for the absent experts; the shared expert, attention, the router and the
dense layers are whole. The holders' routed parts add up to the whole
layer's (``tests/test_trinity_lm.py``).

Weights are **bfloat16**; the forward upcasts them, one projection and
one expert at a time: a Python loop over layers of small jitted pieces,
so that at the published widths it runs beside a program that holds the
same arrays.

``precision`` chooses how every matrix product but the router's is
computed: ``float32`` (``highest``; the reference), ``bfloat16``
(operands rounded, float32 accumulation: what the configuration states
the program computes in), ``float8`` (operands rounded to e4m3 as they
are, saturating: the control, which the comparison has to refuse),
``float8_scaled`` (each operand scaled so that its largest magnitude is
e4m3's 448, then rounded: what a deployment in float8 computes).

``routing=`` (a list, one ``(tokens, picks)`` int array per expert layer)
makes the forward use those experts, weighted by its own scores of them.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("float32", "bfloat16", "float8", "float8_scaled")
INIT_STD = 0.02
E4M3_MAX = 448.0
SLIDING, FULL = "sliding_attention", "full_attention"
QUERY_BLOCK = 512        # queries attended at a time


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
    """The shapes a configuration states, the share included."""
    pub = config.get("published") or {}
    held = int(config["num_experts"])
    types = tuple(config["layer_types"])
    if len(types) != int(config["num_hidden_layers"]) or set(types) - {
            SLIDING, FULL}:
        raise ValueError(f"layer_types is not {config['num_hidden_layers']} "
                         f"of {SLIDING!r}, {FULL!r}")
    return {
        "vocab": int(config["vocab_size"]), "e": int(config["hidden_size"]),
        "types": types, "layers": len(types),
        "dense": int(config["num_dense_layers"]),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "window": int(config["sliding_window"]),
        "theta": float(config["rope_theta"]),
        "dense_width": int(config["intermediate_size"]),
        "expert_width": int(config["moe_intermediate_size"]),
        "shared": int(config.get("num_shared_experts", 1)),
        "held": held, "first": int(config.get("expert_first", 0)),
        "routed": int(pub.get("num_experts", held)),
        "k": int(config["num_experts_per_tok"]),
    }


def layer_shapes(config: Dict, i: int) -> Dict[str, Tuple[int, ...]]:
    """Layer ``i``'s weights (its four norms included)."""
    z = sizes(config)
    e, h, hkv, d = z["e"], z["heads"], z["kv_heads"], z["head_dim"]
    out = {"norm_in": (e,), "norm_post_attn": (e,), "norm_pre_mlp": (e,),
           "norm_post_mlp": (e,), "wq": (e, h, d), "wk": (e, hkv, d),
           "wv": (e, hkv, d), "wg": (e, h, d), "wo": (h, d, e),
           "q_norm": (d,), "k_norm": (d,)}
    if i < z["dense"]:
        w = z["dense_width"]
        out.update({"mlp.gate": (e, w), "mlp.up": (e, w), "mlp.down": (w, e)})
        return out
    w, n = z["expert_width"], z["held"]
    out.update({"router": (e, z["routed"]), "bias": (z["routed"],),
                "experts.gate": (n, e, w), "experts.up": (n, e, w),
                "experts.down": (n, w, e)})
    if z["shared"]:
        ws = z["shared"] * w
        out.update({"shared.gate": (e, ws), "shared.up": (e, ws),
                    "shared.down": (ws, e)})
    return out


def param_shapes(config: Dict) -> Dict[str, Tuple[int, ...]]:
    z = sizes(config)
    shapes = {"embed": (z["vocab"], z["e"]), "norm_f": (z["e"],),
              "lm_head": (z["e"], z["vocab"])}
    for i in range(z["layers"]):
        shapes.update({f"l{i}.{k}": s
                       for k, s in layer_shapes(config, i).items()})
    return shapes


def param_count(config: Dict) -> int:
    return sum(int(np.prod(s)) for s in param_shapes(config).values())


# how each leaf is drawn: the rest are matrices, N(0, 0.02)
_RESIDUAL = ("wo", "down")
_GAINS = ("norm_in", "norm_post_attn", "norm_pre_mlp", "norm_post_mlp",
          "norm_f", "q_norm", "k_norm")


@functools.partial(jax.jit, static_argnames=("shape", "gain"))
def _draw(key, scale, *, shape, gain):
    """One weight, bfloat16."""
    x = INIT_STD * jax.random.normal(key, shape, jnp.float32)
    return (1.0 + x if gain else x * scale).astype(jnp.bfloat16)


def init_weights(config: Dict, seed: int) -> Dict[str, jax.Array]:
    """Every weight, bfloat16, made on the device from the seed: matrices,
    the embedding and the selection bias N(0, 0.02), the projections back
    into the residual stream (``wo`` and every ``down``) over
    sqrt(layers), norm gains 1 + N(0, 0.02) so that a dropped gain shows.
    One small jitted draw a weight (one compilation a shape, which the
    layers share)."""
    key = fold_seed(seed)
    resid = 1.0 / math.sqrt(float(sizes(config)["layers"]))
    out = {}
    for i, (name, shape) in enumerate(sorted(param_shapes(config).items())):
        leaf = name.rsplit(".", 1)[-1]
        out[name] = _draw(
            jax.random.fold_in(key, i),
            jnp.float32(resid if leaf in _RESIDUAL else 1.0),
            shape=tuple(shape), gain=leaf in _GAINS)
    return out


# ---- arithmetic ------------------------------------------------------------

def _round(x, precision: str):
    if precision == "bfloat16":
        # reduce_precision, not astype and back: XLA may drop the pair
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    if precision == "float8":
        # saturating, as a float8 unit converts: e4m3 has no infinity
        return jnp.clip(x, -E4M3_MAX, E4M3_MAX).astype(
            jnp.float8_e4m3fn).astype(jnp.float32)
    if precision == "float8_scaled":
        top = jnp.max(jnp.abs(x))
        s = jnp.where(top > 0, top / E4M3_MAX, 1.0)
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x


def _mm(spec: str, a, b, precision: str):
    a = _round(a.astype(jnp.float32), precision)
    b = _round(b.astype(jnp.float32), precision)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms(x, g, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * g.astype(jnp.float32))


def _gated_mlp(m, gate, up, down, precision):
    h = (jax.nn.silu(_mm("...e,ef->...f", m, gate, precision))
         * _mm("...e,ef->...f", m, up, precision))
    return _mm("...f,fe->...e", h, down, precision)


def _rope(x, positions, theta):
    """Rotate the pairs ``(x[i], x[i + D/2])`` of (B, S, H, D) by
    ``positions * theta^(-2i/D)``."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[..., None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _key(config: Dict) -> Tuple:
    """What the pieces depend on, hashable: the scalars, the layers'
    types and the router's published width."""
    scalars = tuple(sorted((k, v) for k, v in config.items()
                           if isinstance(v, (int, float, str, bool))))
    return (scalars, tuple(config["layer_types"]), sizes(config)["routed"])


@functools.lru_cache(maxsize=None)
def _pieces(config_key: Tuple, precision: str):
    """The jitted pieces of one configuration and precision."""
    scalars, types, routed = config_key
    config = dict(scalars, layer_types=list(types),
                  published={"num_experts": routed})
    z = sizes(config)
    eps = float(config.get("rms_norm_eps", 1e-5))
    f32 = jnp.float32

    @functools.partial(jax.jit, static_argnames=("sliding",))
    def attention(x, w, sliding):
        b, s, _ = x.shape
        h, hkv, d = z["heads"], z["kv_heads"], z["head_dim"]
        a = _rms(x, w["norm_in"], eps)
        q = _rms(_mm("bse,ehd->bshd", a, w["wq"], precision), w["q_norm"],
                 eps)
        k = _rms(_mm("bse,ehd->bshd", a, w["wk"], precision), w["k_norm"],
                 eps)
        v = _mm("bse,ehd->bshd", a, w["wv"], precision)
        gate = jax.nn.sigmoid(_mm("bse,ehd->bshd", a, w["wg"], precision))
        pos = jnp.arange(s)
        if sliding:
            where = jnp.broadcast_to(pos, (b, s))
            q, k = _rope(q, where, z["theta"]), _rope(k, where, z["theta"])
        blocks = -(-s // QUERY_BLOCK)
        pad = blocks * QUERY_BLOCK - s
        qb = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
            b, blocks, QUERY_BLOCK, hkv, h // hkv, d)
        pb = jnp.pad(pos, (0, pad)).reshape(blocks, QUERY_BLOCK)

        def block(args):
            qi, pi = args                      # (B, Q, Hkv, G, D), (Q,)
            scores = _mm("bqhgd,bkhd->bhgqk", qi, k, precision) / math.sqrt(d)
            seen = pos[None, :] <= pi[:, None]
            if sliding:
                seen &= pi[:, None] - pos[None, :] < z["window"]
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
            return _mm("bhgqk,bkhd->bqhgd", probs, v, precision)

        o = jax.lax.map(block, (jnp.moveaxis(qb, 1, 0), pb))
        o = jnp.moveaxis(o, 0, 1).reshape(b, blocks * QUERY_BLOCK, h, d)[:, :s]
        out = _mm("bqhd,hde->bqe", o * gate, w["wo"], precision)
        return x + _rms(out, w["norm_post_attn"], eps)

    @jax.jit
    def dense_ffn(x, w):
        m = _rms(x, w["norm_pre_mlp"], eps)
        f = _gated_mlp(m, w["mlp.gate"], w["mlp.up"], w["mlp.down"],
                       precision)
        return x + _rms(f, w["norm_post_mlp"], eps)

    @jax.jit
    def scores_of(x, w):
        """The router's scores of every published expert, float32 at
        every precision, and the choice by ``s + b``."""
        m = _rms(x, w["norm_pre_mlp"], eps).reshape(-1, z["e"])
        logits = jnp.einsum("te,en->tn", m, w["router"].astype(f32),
                            precision=jax.lax.Precision.HIGHEST)
        s = jax.nn.sigmoid(logits)
        choice = s + w["bias"].astype(f32)
        _, ids = jax.lax.top_k(choice, z["k"])
        return s, ids.astype(jnp.int32), choice

    @jax.jit
    def routed_part(x, w, s, ids):
        """The held experts' part of the layer's ``f`` (T, E), before the
        shared expert and the norm behind it. ``ids`` (T, k): the experts
        each token takes; their weights come from ``s``, this forward's
        own scores."""
        m = _rms(x, w["norm_pre_mlp"], eps).reshape(-1, z["e"])
        g = jnp.take_along_axis(s, ids, axis=-1)
        if config.get("route_norm", True):
            g = g / (g.sum(-1, keepdims=True) + 1e-20)
        g = g * float(config.get("route_scale", 1.0))
        # (T, held): a token's weight for each held expert, 0 where it
        # did not take it
        local = ids - z["first"]
        dense_g = jnp.zeros((m.shape[0], z["held"]), f32)
        for j in range(z["k"]):
            ok = (local[:, j] >= 0) & (local[:, j] < z["held"])
            dense_g = dense_g.at[jnp.arange(m.shape[0]),
                                 jnp.clip(local[:, j], 0, z["held"] - 1)].add(
                jnp.where(ok, g[:, j], 0.0))

        def one(acc, ew):                    # one expert upcast at a time
            gate, up, down, ge = ew
            return acc + ge[:, None] * _gated_mlp(m, gate, up, down,
                                                  precision), None

        f, _ = jax.lax.scan(
            one, jnp.zeros_like(m),
            (w["experts.gate"], w["experts.up"], w["experts.down"],
             dense_g.T))
        return f

    @jax.jit
    def expert_ffn(x, w, s, ids):
        m = _rms(x, w["norm_pre_mlp"], eps).reshape(-1, z["e"])
        f = routed_part(x, w, s, ids)
        if z["shared"]:
            f = f + _gated_mlp(m, w["shared.gate"], w["shared.up"],
                               w["shared.down"], precision)
        return x + _rms(f.reshape(x.shape), w["norm_post_mlp"], eps)

    @jax.jit
    def head(x, g, lm_head):
        return _mm("bse,ev->bsv", _rms(x, g, eps), lm_head, precision)

    @jax.jit
    def embed(table, tokens):
        return table[tokens].astype(f32) * math.sqrt(z["e"])

    return {"attention": attention, "dense_ffn": dense_ffn,
            "scores_of": scores_of, "routed_part": routed_part,
            "expert_ffn": expert_ffn, "head": head,
            "embed": embed}


def forward_with_routing(weights: Dict, tokens, config: Dict,
                         precision: str = "float32",
                         routing: Optional[List] = None):
    """``tokens`` (B, S) int32 -> (logits (B, S, V) float32, info) where
    ``info`` lists, per expert layer, ``ids`` (B*S, k), the experts this
    forward used, ``own_ids`` (its own choice) and ``scores`` (B*S,
    published experts): the scores the CHOICE is made by, ``s + b``, which
    is what a routing comparison measures margins in; ``gate_scores`` is
    ``s``."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    z = sizes(config)
    f = _pieces(_key(config), precision)
    x = f["embed"](weights["embed"], jnp.asarray(tokens))
    info = []
    for i, kind in enumerate(z["types"]):
        p = f"l{i}."
        w = {k[len(p):]: v for k, v in weights.items() if k.startswith(p)}
        x = f["attention"](x, w, sliding=kind == SLIDING)
        if i < z["dense"]:
            x = f["dense_ffn"](x, w)
            continue
        s, own, choice = f["scores_of"](x, w)
        ids = own if routing is None else jnp.asarray(
            routing[len(info)], jnp.int32).reshape(own.shape)
        x = f["expert_ffn"](x, w, s, ids)
        info.append({"ids": ids, "own_ids": own, "scores": choice,
                     "gate_scores": s})
    return f["head"](x, weights["norm_f"], weights["lm_head"]), info


def forward_jit(weights, tokens, config, precision="float32", routing=None):
    return forward_with_routing(weights, tokens, config, precision,
                                routing)[0]
