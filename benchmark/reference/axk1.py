"""Plain A.X-K1 (``skt/A.X-K1`` on the Hugging Face hub; the DeepSeek-V3
block) in ``jax.numpy``: the yardstick the benchmark compares the program
with. Nothing here imports ``flexflow_tpu`` and nothing here is fast: no
cache, no kernels, no grouped products; every token goes through every
held expert and is weighted by its gate (0 where it was not routed).

The layer equations (``x`` a block's input; every norm an RMSNorm with a
gain):

* block: ``h1 = x + MLA(norm1(x))``; ``y = h1 + FFN(norm2(h1))``; ``FFN``
  is the dense gated MLP in the first ``first_k_dense_replace`` layers and
  the expert layer after them; gated MLP: ``(silu(u Wg) * (u Wu)) Wd``;
* MLA per token ``t``: ``cq = norm_q(u W_qa)``; ``[q_nope | q_rope] = cq
  W_qb`` per head; ``[ckv | kr] = u W_kva``; ``c = norm_kv(ckv)``;
  ``k_rope = rope(kr, t)`` (one for all heads), ``q_rope = rope(q_rope,
  t)``; ``[k_nope | v] = c W_kvb`` per head; ``score_h(t, s) = (q_nope_h .
  k_nope_h + q_rope_h . k_rope) * scale``, causal softmax, ``sum p v``,
  ``W_o``. ``scale = (nope + rope)^-0.5 * m^2``, ``m = 0.1 *
  mscale_all_dim * ln(factor) + 1`` (YaRN); the rotary frequencies blend
  ``theta^(-2i/d)`` and the same over ``factor`` along the linear ramp
  between the dimensions that ``beta_fast`` and ``beta_slow`` find at the
  original positions; pairs are ``(i, i + d/2)``;
* expert layer per token: ``s = sigmoid(float32(u) W_router)`` over ALL
  the published experts, in float32 at every ``precision``; the experts
  are ``n_group`` groups, a group scores the sum of its two highest
  ``s``, the ``topk_group`` highest groups stay; ``T`` = the
  ``num_experts_per_tok`` highest ``s`` within them; ``g_e = s_e /
  sum_T s * routed_scaling_factor``; output ``sum_{e in T} g_e MLP_e(u) +
  MLP_shared(u)``.

**The share.** A configuration file may describe one holder of a larger
deployment: ``n_routed_experts`` is then the experts HELD (a contiguous
run from ``expert_first``, default 0) while the router keeps
``published.n_routed_experts`` columns, and ``vocab_size`` the rows of the
vocabulary held. The forward computes that holder's partial result: the
routed sum runs over the held experts of ``T`` only, nothing stands in
for the absent ones; the shared expert, attention and the dense layers
are whole.

Weights are **bfloat16** (the deployment's weights are; the forward
upcasts them, one projection and one expert at a time: the forward is a
Python loop over layers of small jitted pieces, so that at the published
widths it runs beside a program that holds the same arrays).

``precision`` chooses how every matrix product but the router's is
computed: ``float32`` (``highest``; the reference), ``bfloat16`` (operands
rounded, float32 accumulation: what the configuration states the
program computes in), ``float8`` (operands rounded to e4m3 as they are:
the control, which the comparison has to refuse; weights of N(0, 0.02)
lie in e4m3's subnormals, so it is a coarse one), ``float8_scaled``
(each operand scaled so that its largest magnitude is e4m3's 448, then
rounded: what a deployment in float8 computes, read beside the control to
say how near a realistic lower precision comes to the limits).

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


def fold_seed(seed: int) -> jax.Array:
    """A PRNG key (``rbg``: the device's own bit generator) from any
    non-negative whole number: the low 31 bits seed it, the rest is
    folded in."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed {seed} < 0")
    key = jax.random.key(seed & 0x7FFFFFFF, impl="rbg")
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def sizes(config: Dict) -> Dict[str, int]:
    """The shapes a configuration states, the share included."""
    pub = config.get("published") or {}
    held = int(config["n_routed_experts"])
    return {
        "vocab": int(config["vocab_size"]), "e": int(config["hidden_size"]),
        "layers": int(config["num_hidden_layers"]),
        "dense_layers": int(config["first_k_dense_replace"]),
        "heads": int(config["num_attention_heads"]),
        "q_rank": int(config["q_lora_rank"]),
        "kv_rank": int(config["kv_lora_rank"]),
        "nope": int(config["qk_nope_head_dim"]),
        "rope": int(config["qk_rope_head_dim"]),
        "v": int(config["v_head_dim"]),
        "dense_width": int(config["intermediate_size"]),
        "expert_width": int(config["moe_intermediate_size"]),
        "shared": int(config.get("n_shared_experts", 0)),
        "held": held, "first": int(config.get("expert_first", 0)),
        "routed": int(pub.get("n_routed_experts", held)),
        "k": int(config["num_experts_per_tok"]),
        "n_group": int(config.get("n_group") or 1),
        "topk_group": int(config.get("topk_group") or config.get("n_group")
                          or 1),
    }


def param_shapes(config: Dict) -> Dict[str, Tuple[int, ...]]:
    z = sizes(config)
    e, h = z["e"], z["heads"]
    shapes = {"embed": (z["vocab"], e), "norm_f": (e,),
              "lm_head": (e, z["vocab"])}
    for i in range(z["layers"]):
        p = f"l{i}."
        shapes.update({
            p + "norm1": (e,), p + "norm2": (e,),
            p + "wq_a": (e, z["q_rank"]), p + "q_norm": (z["q_rank"],),
            p + "wq_b": (z["q_rank"], h * (z["nope"] + z["rope"])),
            p + "wkv_a": (e, z["kv_rank"] + z["rope"]),
            p + "kv_norm": (z["kv_rank"],),
            p + "wkv_b": (z["kv_rank"], h * (z["nope"] + z["v"])),
            p + "wo": (h * z["v"], e)})
        if i < z["dense_layers"]:
            w = z["dense_width"]
            shapes.update({p + "mlp.gate": (e, w), p + "mlp.up": (e, w),
                           p + "mlp.down": (w, e)})
        else:
            w, n = z["expert_width"], z["held"]
            shapes.update({p + "router": (e, z["routed"]),
                           p + "experts.gate": (n, e, w),
                           p + "experts.up": (n, e, w),
                           p + "experts.down": (n, w, e)})
            if z["shared"]:
                ws = z["shared"] * w
                shapes.update({p + "shared.gate": (e, ws),
                               p + "shared.up": (e, ws),
                               p + "shared.down": (ws, e)})
    return shapes


def param_count(config: Dict) -> int:
    return sum(int(np.prod(s)) for s in param_shapes(config).values())


@functools.partial(jax.jit, static_argnames=("shape", "kind"))
def _draw(key, scale, *, shape, kind):
    """One weight, bfloat16. ``kind``: ``gain`` (1 + N(0, 0.02)) or
    ``matrix`` (N(0, 0.02) times ``scale``)."""
    x = INIT_STD * jax.random.normal(key, shape, jnp.float32)
    x = 1.0 + x if kind == "gain" else x * scale
    return x.astype(jnp.bfloat16)


def init_weights(config: Dict, seed: int) -> Dict[str, jax.Array]:
    """Every weight, bfloat16, made on the device from the seed: matrices
    and the embedding N(0, 0.02), the projections back into the residual
    stream (``wo``, ``*.down``) over sqrt(2 * layers), norm gains 1 +
    N(0, 0.02) so that a dropped gain shows. One small jitted draw a
    weight (one compilation a shape, which the layers share): the whole
    model is never a temporary beside itself."""
    key = fold_seed(seed)
    resid = 1.0 / math.sqrt(2.0 * int(config["num_hidden_layers"]))
    out = {}
    for i, (name, shape) in enumerate(sorted(param_shapes(config).items())):
        leaf = name.rsplit(".", 1)[-1]
        gain = len(shape) == 1
        scale = resid if leaf in ("wo", "down") else 1.0
        out[name] = _draw(jax.random.fold_in(key, i), jnp.float32(scale),
                          shape=tuple(shape),
                          kind="gain" if gain else "matrix")
    return out


# ---- arithmetic ------------------------------------------------------------

def _round(x, precision: str):
    if precision == "bfloat16":
        # reduce_precision, not astype and back: XLA may drop the pair
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    if precision == "float8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    if precision == "float8_scaled":
        top = jnp.max(jnp.abs(x))
        s = jnp.where(top > 0, top / 448.0, 1.0)
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


def _gated(u, gate, up, down, precision):
    a = _mm("...e,ef->...f", u, gate, precision)
    b = _mm("...e,ef->...f", u, up, precision)
    return _mm("...f,fe->...e", jax.nn.silu(a) * b, down, precision)


def yarn_inv_freq(dim: int, theta: float, scaling: Optional[Dict]):
    i = np.arange(0, dim, 2, dtype=np.float64)
    extra = 1.0 / (float(theta) ** (i / dim))
    if not scaling:
        return extra.astype(np.float32)
    factor = float(scaling["factor"])
    orig = float(scaling["original_max_position_embeddings"])

    def dim_of(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(float(theta)))

    low = max(math.floor(dim_of(float(scaling["beta_fast"]))), 0)
    high = min(math.ceil(dim_of(float(scaling["beta_slow"]))), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (extra / factor * ramp + extra * (1 - ramp)).astype(np.float32)


def softmax_scale(config: Dict) -> float:
    z = sizes(config)
    scale = (z["nope"] + z["rope"]) ** -0.5
    sc = config.get("rope_scaling")
    if sc and float(sc.get("mscale_all_dim", 0)) and float(sc["factor"]) > 1:
        m = 0.1 * float(sc["mscale_all_dim"]) * math.log(float(sc["factor"])) \
            + 1.0
        scale *= m * m
    return scale


def _rope(x, pos, inv_freq):
    """Pairs (i, i + d/2) of the last axis turned by pos * inv_freq[i];
    ``x`` (B, S, [H,] d), ``pos`` (S,)."""
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq)   # (S, d/2)
    if x.ndim == 4:
        ang = ang[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _key(config: Dict) -> Tuple:
    def flat(v):
        return tuple(sorted(v.items())) if isinstance(v, dict) else v

    return tuple(sorted((k, flat(v)) for k, v in config.items()
                        if isinstance(v, (int, float, str, bool))
                        or k in ("rope_scaling", "published")))


@functools.lru_cache(maxsize=None)
def _pieces(config_items: Tuple, precision: str):
    """The jitted pieces of one configuration and precision."""
    config = {k: (dict(v) if isinstance(v, tuple) else v)
              for k, v in config_items}
    z = sizes(config)
    eps = float(config.get("rms_norm_eps", 1e-6))
    inv_freq = yarn_inv_freq(z["rope"], float(config.get("rope_theta", 1e4)),
                             config.get("rope_scaling"))
    scale = softmax_scale(config)
    h = z["heads"]

    @jax.jit
    def attention(x, w):
        b, s, _ = x.shape
        pos = jnp.arange(s)
        u = _rms(x, w["norm1"], eps)
        cq = _rms(_mm("bse,er->bsr", u, w["wq_a"], precision), w["q_norm"],
                  eps)
        q = _mm("bsr,rf->bsf", cq, w["wq_b"], precision).reshape(
            b, s, h, z["nope"] + z["rope"])
        q_nope, q_rope = q[..., :z["nope"]], _rope(q[..., z["nope"]:], pos,
                                                   inv_freq)
        kva = _mm("bse,er->bsr", u, w["wkv_a"], precision)
        c = _rms(kva[..., :z["kv_rank"]], w["kv_norm"], eps)
        k_rope = _rope(kva[..., z["kv_rank"]:], pos, inv_freq)
        kv = _mm("bsc,cf->bsf", c, w["wkv_b"], precision).reshape(
            b, s, h, z["nope"] + z["v"])
        k_nope, v = kv[..., :z["nope"]], kv[..., z["nope"]:]
        scores = (_mm("bqhd,bkhd->bhqk", q_nope, k_nope, precision)
                  + _mm("bqhd,bkd->bhqk", q_rope, k_rope, precision)) * scale
        causal = jnp.tril(jnp.ones((s, s), bool))
        probs = jax.nn.softmax(jnp.where(causal[None, None], scores,
                                         -jnp.inf), axis=-1)
        o = _mm("bhqk,bkhd->bqhd", probs, v, precision).reshape(
            b, s, h * z["v"])
        return x + _mm("bsf,fe->bse", o, w["wo"], precision)

    @jax.jit
    def dense_ffn(x, w):
        u = _rms(x, w["norm2"], eps)
        return x + _gated(u, w["mlp.gate"], w["mlp.up"], w["mlp.down"],
                          precision)

    @jax.jit
    def scores_of(x, w):
        """The router's scores of every published expert, float32 at
        every precision, and the group-limited selection."""
        u = _rms(x, w["norm2"], eps).reshape(-1, z["e"])
        logits = jnp.einsum("te,en->tn", u, w["router"].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        if config.get("scoring_func", "sigmoid") == "sigmoid":
            s = jax.nn.sigmoid(logits)
        else:
            s = jax.nn.softmax(logits, axis=-1)
        choice = s
        if z["n_group"] > 1 and z["topk_group"] < z["n_group"]:
            t = s.shape[0]
            g = s.reshape(t, z["n_group"], -1)
            gscore = jax.lax.top_k(g, min(2, g.shape[-1]))[0].sum(-1)
            _, gidx = jax.lax.top_k(gscore, z["topk_group"])
            keep = jnp.zeros((t, z["n_group"]), bool).at[
                jnp.arange(t)[:, None], gidx].set(True)
            choice = jnp.where(keep[:, :, None], g, -1.0).reshape(t, -1)
        _, ids = jax.lax.top_k(choice, z["k"])
        return s, ids.astype(jnp.int32), choice

    @jax.jit
    def expert_ffn(x, w, s, ids):
        """``ids`` (T, k): the experts each token takes; their weights
        come from ``s``, this forward's own scores."""
        u = _rms(x, w["norm2"], eps)
        u2 = u.reshape(-1, z["e"])
        g = jnp.take_along_axis(s, ids, axis=-1)
        if config.get("norm_topk_prob", True):
            g = g / (g.sum(-1, keepdims=True) + 1e-20)
        g = g * float(config.get("routed_scaling_factor", 1.0))
        # (T, held): a token's weight for each held expert, 0 where it
        # did not take it
        local = ids - z["first"]
        dense_g = jnp.zeros((u2.shape[0], z["held"]), jnp.float32)
        for j in range(z["k"]):
            ok = (local[:, j] >= 0) & (local[:, j] < z["held"])
            dense_g = dense_g.at[jnp.arange(u2.shape[0]),
                                 jnp.clip(local[:, j], 0, z["held"] - 1)].add(
                jnp.where(ok, g[:, j], 0.0))

        def one(acc, ew):                    # one expert upcast at a time
            gate, up, down, ge = ew
            return acc + ge[:, None] * _gated(u2, gate, up, down,
                                              precision), None

        out, _ = jax.lax.scan(
            one, jnp.zeros_like(u2),
            (w["experts.gate"], w["experts.up"], w["experts.down"],
             dense_g.T))
        if z["shared"]:
            out = out + _gated(u2, w["shared.gate"], w["shared.up"],
                               w["shared.down"], precision)
        return x + out.reshape(x.shape)

    @jax.jit
    def head(x, g, lm_head):
        return _mm("bse,ev->bsv", _rms(x, g, eps), lm_head, precision)

    @jax.jit
    def embed(table, tokens):
        return table[tokens].astype(jnp.float32)

    return {"attention": attention, "dense_ffn": dense_ffn,
            "scores_of": scores_of, "expert_ffn": expert_ffn, "head": head,
            "embed": embed}


def forward_with_routing(weights: Dict, tokens, config: Dict,
                         precision: str = "float32",
                         routing: Optional[List] = None):
    """``tokens`` (B, S) int32 -> (logits (B, S, V) float32, info) where
    ``info`` lists, per expert layer, ``ids`` (B*S, k) — the experts this
    forward used — ``own_ids`` (its own choice) and ``scores`` (B*S,
    published experts) with ``choice`` (the scores with the groups that
    did not stay at -1)."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    z = sizes(config)
    f = _pieces(_key(config), precision)
    x = f["embed"](weights["embed"], jnp.asarray(tokens))
    info = []
    for i in range(z["layers"]):
        p = f"l{i}."
        w = {k[len(p):]: v for k, v in weights.items() if k.startswith(p)}
        x = f["attention"](x, w)
        if i < z["dense_layers"]:
            x = f["dense_ffn"](x, w)
            continue
        s, own, choice = f["scores_of"](x, w)
        ids = own if routing is None else jnp.asarray(
            routing[len(info)], jnp.int32).reshape(own.shape)
        x = f["expert_ffn"](x, w, s, ids)
        info.append({"ids": ids, "own_ids": own, "scores": s,
                     "choice": choice})
    return f["head"](x, weights["norm_f"], weights["lm_head"]), info


def forward_jit(weights, tokens, config, precision="float32", routing=None):
    return forward_with_routing(weights, tokens, config, precision,
                                routing)[0]
