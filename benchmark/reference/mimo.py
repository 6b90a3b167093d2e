"""Plain MiMo-V2 (``XiaomiMiMo/MiMo-V2.5`` on the Hugging Face hub,
``model_type`` ``mimo_v2``; the language model, fed text tokens) in
``jax.numpy``: the yardstick the benchmark compares the program with.
Nothing here imports ``flexflow_tpu`` and nothing here is fast: no cache,
no kernels, no grouped products; every layer attends the whole sequence
under its mask (a few hundred queries at a time, so that six thousand
positions fit beside the program), and every token goes through every
held expert and is weighted by its gate (0 where it was not routed).

The layer equations (``rms`` an RMSNorm of ``layernorm_epsilon`` with a
gain; no bias on any linear map; ``h0 = E[tokens]``, unscaled).
``hybrid_layer_pattern`` names each layer's attention, 0 full and 1
windowed; ``moe_layer_freq`` its feed-forward, 0 dense and 1 experts:

* ``u = rms_in(h)``; ``q = u Wq`` (H = ``num_attention_heads`` heads of D
  = ``head_dim``), ``k = u Wk`` (Hkv heads of D), ``v = u Wv *
  attention_value_scale`` (Hkv heads of Dv = ``v_head_dim``); Hkv is
  ``num_key_value_heads`` in a full layer and ``swa_num_key_value_heads``
  in a windowed one;
* the first R = ``int(D * partial_rotary_factor)`` numbers of each head
  of q and k are rotated by the position, the pairs ``(x[i], x[i + R/2])``,
  base ``rope_theta`` in a full layer and ``swa_rope_theta`` in a
  windowed one, unscaled; the other D - R pass;
* query head i reads key-value head ``i // (H / Hkv)``; scores ``q . k /
  sqrt(D)``; key j is seen from position p iff ``j <= p`` and, in a
  windowed layer, ``p - j < sliding_window``; where the layer's kind has
  a sink (``add_swa_attention_sink_bias``,
  ``add_full_attention_sink_bias``) head i has a learned scalar ``s_i``
  that is one more column of the softmax and carries no value: ``o =
  sum_j exp(a_j - m) v_j / (exp(s_i - m) + sum_j exp(a_j - m))``;
  softmax in float32; ``h = h + concat(o) Wo``, Wo (H, Dv, E);
* ``m = rms_pre_mlp(h)``; a dense layer: ``f = (silu(m W1) * (m W3))
  W2`` of width ``intermediate_size``; an expert layer: ``s =
  sigmoid(float32(m) Wr)`` over ALL the published experts, in float32 at
  every ``precision``; ``T`` = the ``num_experts_per_tok`` largest of ``s
  + b`` (``b`` the selection bias of ``noaux_tc``, in the choice only;
  one group); ``w_e = s_e / (sum_T s + 1e-20)`` (``norm_topk_prob``)
  times ``routed_scaling_factor`` (null: 1); ``f = sum_{e in T} w_e
  expert_e(m)``, every expert a gated SiLU MLP of width
  ``moe_intermediate_size``; no shared expert;
* ``h = h + f``; after the last layer a final ``rms`` and the untied
  head.

What the published ``config.json`` does not settle is listed once, in
the configuration file's ``assumed`` block
(``configs/mimo-v2.5-ep16.json``). Left out, because the catalog's
``config`` gives them no shape: the three multi-token-prediction layers
and the V2.5 vision and audio encoders.

**The share.** A configuration file may describe one holder of a larger
deployment: ``n_routed_experts`` is then the experts HELD (a contiguous
run from ``expert_first``, default 0) while the router and its bias keep
``published.n_routed_experts`` columns, and ``vocab_size`` the rows of
the vocabulary held. The forward computes that holder's partial result:
the routed sum runs over the held experts of ``T`` only; nothing stands
in for the absent experts; attention, the router and the dense layers
are whole. The holders' routed parts add up to the whole layer's
(``tests/test_mimo_lm.py``).

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
SINK_STD = 1.0
E4M3_MAX = 448.0
FULL, WINDOWED = 0, 1
QUERY_BLOCK = 128        # queries attended at a time
TOKEN_BLOCK = 1024       # tokens through the dense MLP at a time


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
    held = int(config["n_routed_experts"])
    kinds = tuple(int(k) for k in config["hybrid_layer_pattern"])
    ffn = tuple(int(k) for k in config["moe_layer_freq"])
    n = int(config["num_hidden_layers"])
    if len(kinds) != n or len(ffn) != n or set(kinds) - {FULL, WINDOWED}:
        raise ValueError(f"hybrid_layer_pattern and moe_layer_freq are not "
                         f"{n} of 0, 1")
    d = int(config["head_dim"])
    return {
        "vocab": int(config["vocab_size"]), "e": int(config["hidden_size"]),
        "kinds": kinds, "ffn": ffn, "layers": n,
        "heads": int(config["num_attention_heads"]),
        "kv_heads": {FULL: int(config["num_key_value_heads"]),
                     WINDOWED: int(config["swa_num_key_value_heads"])},
        "head_dim": d, "v_dim": int(config["v_head_dim"]),
        "rotary": int(d * float(config.get("partial_rotary_factor", 1.0))),
        "window": int(config["sliding_window"]),
        "theta": {FULL: float(config["rope_theta"]),
                  WINDOWED: float(config["swa_rope_theta"])},
        "sink": {FULL: bool(config.get("add_full_attention_sink_bias")),
                 WINDOWED: bool(config.get("add_swa_attention_sink_bias"))},
        "value_scale": float(config.get("attention_value_scale") or 1.0),
        "dense_width": int(config["intermediate_size"]),
        "expert_width": int(config["moe_intermediate_size"]),
        "held": held, "first": int(config.get("expert_first", 0)),
        "routed": int(pub.get("n_routed_experts", held)),
        "k": int(config["num_experts_per_tok"]),
    }


def layer_shapes(config: Dict, i: int) -> Dict[str, Tuple[int, ...]]:
    """Layer ``i``'s weights (its two norms included)."""
    z = sizes(config)
    kind = z["kinds"][i]
    e, h, hkv = z["e"], z["heads"], z["kv_heads"][kind]
    d, dv = z["head_dim"], z["v_dim"]
    out = {"norm_in": (e,), "norm_pre_mlp": (e,), "wq": (e, h, d),
           "wk": (e, hkv, d), "wv": (e, hkv, dv), "wo": (h, dv, e)}
    if z["sink"][kind]:
        out["sinks"] = (h,)
    if not z["ffn"][i]:
        w = z["dense_width"]
        out.update({"mlp.gate": (e, w), "mlp.up": (e, w), "mlp.down": (w, e)})
        return out
    w, n = z["expert_width"], z["held"]
    out.update({"router": (e, z["routed"]), "bias": (z["routed"],),
                "experts.gate": (n, e, w), "experts.up": (n, e, w),
                "experts.down": (n, w, e)})
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
_GAINS = ("norm_in", "norm_pre_mlp", "norm_f")


@functools.partial(jax.jit, static_argnames=("shape", "gain"))
def _draw(key, scale, *, shape, gain):
    """One weight, bfloat16."""
    x = jax.random.normal(key, shape, jnp.float32)
    return (1.0 + INIT_STD * x if gain else x * scale).astype(jnp.bfloat16)


def init_weights(config: Dict, seed: int) -> Dict[str, jax.Array]:
    """Every weight, bfloat16, made on the device from the seed: matrices,
    the embedding and the selection bias N(0, 0.02), the projections back
    into the residual stream (``wo`` and every ``down``) over
    sqrt(layers), norm gains 1 + N(0, 0.02) so that a dropped gain shows,
    the sinks N(0, 1): near the scores' own size, so that a dropped sink
    shows. One small jitted draw a weight (one compilation a shape, which
    the layers share)."""
    key = fold_seed(seed)
    resid = 1.0 / math.sqrt(float(sizes(config)["layers"]))
    out = {}
    for i, (name, shape) in enumerate(sorted(param_shapes(config).items())):
        leaf = name.rsplit(".", 1)[-1]
        std = (SINK_STD if leaf == "sinks"
               else INIT_STD * (resid if leaf in _RESIDUAL else 1.0))
        out[name] = _draw(jax.random.fold_in(key, i), jnp.float32(std),
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


def _rope(x, positions, theta, r):
    """Rotate the pairs ``(x[i], x[i + r/2])`` of the first ``r`` numbers
    of each head of (B, S, H, D) by ``positions * theta^(-2i/r)``; the
    other ``D - r`` pass."""
    inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    ang = positions.astype(jnp.float32)[..., None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., r:]], -1)


_LISTS = ("hybrid_layer_pattern", "moe_layer_freq")


def _key(config: Dict) -> Tuple:
    """What the pieces depend on, hashable: the scalars, the layers'
    kinds and the router's published width."""
    scalars = tuple(sorted((k, v) for k, v in config.items()
                           if isinstance(v, (int, float, str, bool))))
    return (scalars, tuple(tuple(config[k]) for k in _LISTS),
            sizes(config)["routed"])


@functools.lru_cache(maxsize=None)
def _pieces(config_key: Tuple, precision: str):
    """The jitted pieces of one configuration and precision."""
    scalars, lists, routed = config_key
    config = dict(scalars, published={"n_routed_experts": routed},
                  **{k: list(v) for k, v in zip(_LISTS, lists)})
    z = sizes(config)
    eps = float(config.get("layernorm_epsilon", 1e-5))
    f32 = jnp.float32

    @functools.partial(jax.jit, static_argnames=("kind",))
    def attention(x, w, kind):
        b, s, _ = x.shape
        h, hkv, d, dv = z["heads"], z["kv_heads"][kind], z["head_dim"], \
            z["v_dim"]
        u = _rms(x, w["norm_in"], eps)
        q = _mm("bse,ehd->bshd", u, w["wq"], precision)
        k = _mm("bse,ehd->bshd", u, w["wk"], precision)
        v = _mm("bse,ehd->bshd", u, w["wv"], precision) * z["value_scale"]
        pos = jnp.arange(s)
        where = jnp.broadcast_to(pos, (b, s))
        q = _rope(q, where, z["theta"][kind], z["rotary"])
        k = _rope(k, where, z["theta"][kind], z["rotary"])
        blocks = -(-s // QUERY_BLOCK)
        pad = blocks * QUERY_BLOCK - s
        qb = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
            b, blocks, QUERY_BLOCK, hkv, h // hkv, d)
        pb = jnp.pad(pos, (0, pad)).reshape(blocks, QUERY_BLOCK)
        sink = (w["sinks"].astype(f32).reshape(hkv, h // hkv)
                if z["sink"][kind] else None)

        def block(args):
            qi, pi = args                      # (B, Q, Hkv, G, D), (Q,)
            scores = _mm("bqhgd,bkhd->bhgqk", qi, k, precision) / math.sqrt(d)
            seen = pos[None, :] <= pi[:, None]
            if kind == WINDOWED:
                seen &= pi[:, None] - pos[None, :] < z["window"]
            scores = jnp.where(seen, scores, -jnp.inf)
            if sink is not None:
                # one more column of the softmax, which carries no value
                col = jnp.broadcast_to(sink[None, :, :, None, None],
                                       scores.shape[:-1] + (1,))
                scores = jnp.concatenate([scores, col], -1)
            probs = jax.nn.softmax(scores, -1)[..., :s]
            return _mm("bhgqk,bkhd->bqhgd", probs, v, precision)

        o = jax.lax.map(block, (jnp.moveaxis(qb, 1, 0), pb))
        o = jnp.moveaxis(o, 0, 1).reshape(b, blocks * QUERY_BLOCK, h, dv)[:, :s]
        return x + _mm("bqhd,hde->bqe", o, w["wo"], precision)

    @jax.jit
    def dense_ffn(x, w):
        # a block of tokens at a time: (S, 16,384) float32 three times
        # over is more than fits beside the program at six thousand
        b, s, e = x.shape
        blocks = -(-s // TOKEN_BLOCK)
        m = jnp.pad(_rms(x, w["norm_pre_mlp"], eps),
                    ((0, 0), (0, blocks * TOKEN_BLOCK - s), (0, 0)))
        f = jax.lax.map(
            lambda mi: _gated_mlp(mi, w["mlp.gate"], w["mlp.up"],
                                  w["mlp.down"], precision),
            jnp.moveaxis(m.reshape(b, blocks, TOKEN_BLOCK, e), 1, 0))
        return x + jnp.moveaxis(f, 0, 1).reshape(b, -1, e)[:, :s]

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
        """The held experts' part of the layer's ``f`` (T, E). ``ids`` (T,
        k): the experts each token takes; their weights come from ``s``,
        this forward's own scores."""
        m = _rms(x, w["norm_pre_mlp"], eps).reshape(-1, z["e"])
        g = jnp.take_along_axis(s, ids, axis=-1)
        if config.get("norm_topk_prob", True):
            g = g / (g.sum(-1, keepdims=True) + 1e-20)
        g = g * float(config.get("routed_scaling_factor") or 1.0)
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
        return x + routed_part(x, w, s, ids).reshape(x.shape)

    @jax.jit
    def head(x, g, lm_head):
        return _mm("bse,ev->bsv", _rms(x, g, eps), lm_head, precision)

    @jax.jit
    def embed(table, tokens):
        return table[tokens].astype(f32)

    return {"attention": attention, "dense_ffn": dense_ffn,
            "scores_of": scores_of, "routed_part": routed_part,
            "expert_ffn": expert_ffn, "head": head, "embed": embed}


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
    for i, kind in enumerate(z["kinds"]):
        p = f"l{i}."
        w = {k[len(p):]: v for k, v in weights.items() if k.startswith(p)}
        x = f["attention"](x, w, kind=kind)
        if not z["ffn"][i]:
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
