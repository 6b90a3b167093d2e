"""Plain ZAYA1 (``Zyphra/ZAYA1-8B`` on the Hugging Face hub, ``model_type``
``zaya``) in ``jax.numpy``: the yardstick the benchmark compares the
program with. Nothing here imports ``flexflow_tpu`` and nothing here is
fast: no cache, no kernels, no grouped products; every layer attends the
whole sequence under its mask (a few hundred queries at a time, so that
a prompt of the cell's length fits beside the program), every token goes
through every expert and is weighted by its gate (0 where it was not
routed), and the head is read a block of the vocabulary at a time.

The layer equations (``E`` hidden, ``H`` query heads and ``G`` key-value
heads of ``d``, ``g(h) = h // (H / G)``; ``rms`` an RMSNorm of
``rms_norm_eps`` with a gain; zeros stand before position 0), ``x`` the
residual stream, ``a .. d`` learned vectors of ``E`` a sublayer::

    x = (a1 * x + b1) + (c1 * CCA(rms(x)) + d1)
    x = (a2 * x + b2) + (c2 * MoE(rms(x), r_prev) + d2)

**CCA(u)**:

1. ``q~ = u Wq`` (H heads), ``k~ = u Wk`` (G heads), ``z = [q~ ; k~]``;
2. two causal convolutions over the sequence, ``cca_time0`` and
   ``cca_time1`` taps: depthwise ``z1_t = sum_j alpha_j * z_{t-T0+1+j} +
   beta`` (``z1`` before position 0 is zeros, as ``z`` is), then grouped
   by head, a ``d x d`` matrix a tap and head: ``z2_t[h] = sum_j
   z1_{t-T1+1+j}[h] A[h, j] + beta'[h]``; ``q1, k1`` = the split of ``z2``;
3. ``m_q[h] = (q~[h] + k~[g(h)]) / 2``, ``m_k[j]`` = the mean of ``m_q[h]``
   over ``g(h) = j``; ``q2 = q1 + m_q``, ``k2 = k1 + m_k``;
4. ``v_t[j] = [u_t Wv1[j] ; u_{t-1} Wv2[j]]``: the second half of every
   value head comes from the token before (``u_{-1} = 0``);
5. ``q3[h] = sqrt(d) q2[h] / |q2[h]|``, ``k3[j] = exp(tau_j) sqrt(d) k2[j]
   / |k2[j]|`` (``|x|^2 + 1e-12`` under the root);
6. the first ``partial_rotary_factor * d`` values of each head of q3 and
   k3 rotated by the position (``rope_theta``, the pairs ``(x[i], x[i +
   r/2])`` inside those ``r``), the rest pass;
7. causal grouped attention ``softmax(q k / sqrt(d)) v`` in float32,
   times ``Wo``.

**MoE(u, r_prev)**, ``num_experts`` experts, one a token:

1. ``r = u Wdn + bdn``, and in every layer but the MODEL's first ``r = r
   + gamma * r_prev``; this ``r`` is what the next layer receives;
2. ``p = softmax(gelu(gelu(rms(r) W1 + b1) W2 + b2) W3)`` (exact GELU),
   all float32 at every ``precision``;
3. ``e = argmax(p + bias)`` (the bias in the choice only), output ``p_e
   (silu(u Wg_e) * (u Wu_e)) Wd_e``.

Embedding, the layers, a final ``rms``, and the head, which IS the
embedding's table. What the published ``config.json`` does not settle is
listed once, in the configuration file's ``assumed`` block
(``configs/zaya1-8b-pp2.json``).

A configuration may be one stage of a pipeline: ``num_hidden_layers`` its
own layers, ``first_layer`` the model's layer it starts at (past 0 the
first layer here would take the state of the stage before; this file
runs stage 0). The stage carries embedding and head, so that it yields
tokens.

Weights are **bfloat16**; the forward upcasts them a piece at a time: a
Python loop over layers of small jitted pieces, so that at the published
widths it runs beside a program that holds the same arrays.
``precision`` chooses how every matrix product but the router's is
computed (``reference/trinity.py``: ``float32``, ``bfloat16``,
``float8``, ``float8_scaled``). ``routing=`` (a list, one ``(tokens, 1)``
int array per layer) makes the forward use those experts, weighted by its
own scores of them.

With ``reference_head_rows`` in the configuration the head is computed
for that many last positions only and the logits before them are zeros
(a host array): at 262,272 columns a 2,500-token prompt's logits are 2.6
GB, beside a program that fills the chip; the comparison reads the last
``1 + decode_steps`` rows.
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
QUERY_BLOCK = 512        # queries attended at a time
VOCAB_BLOCK = 16384      # rows of the table the head reads at a time, at most


def fold_seed(seed: int) -> jax.Array:
    """A PRNG key (``rbg``) from any non-negative whole number: the low
    31 bits seed it, the rest is folded in."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed {seed} < 0")
    key = jax.random.key(seed & 0x7FFFFFFF, impl="rbg")
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def sizes(config: Dict) -> Dict:
    """The shapes a configuration states."""
    layers = int(config["num_hidden_layers"])
    if list(config["layer_types"]) != ["hybrid"] * layers:
        raise ValueError(f"layer_types is not {layers} of 'hybrid'")
    rope = config["rope_parameters"]["hybrid"]
    d = int(config["head_dim"])
    if int(config["num_experts_per_tok"]) != 1:
        raise ValueError("one expert a token here")
    return {
        "vocab": int(config["vocab_size"]), "e": int(config["hidden_size"]),
        "layers": layers, "first": int(config.get("first_layer", 0)),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]), "d": d,
        "taps": (int(config["cca_time0"]), int(config["cca_time1"])),
        "theta": float(rope["rope_theta"]),
        "rot": int(d * float(rope["partial_rotary_factor"])),
        "experts": int(config["num_experts"]),
        "width": int(config["moe_intermediate_size"]),
        "router": int(config["router_hidden_size"]),
    }


def layer_shapes(config: Dict, i: int) -> Dict[str, Tuple[int, ...]]:
    """Layer ``i``'s weights (``i`` counted in this file's layers)."""
    z = sizes(config)
    e, h, g, d, r, n, w = (z["e"], z["heads"], z["kv_heads"], z["d"],
                           z["router"], z["experts"], z["width"])
    c = (h + g) * d
    out = {"norm_attn": (e,), "norm_moe": (e,),
           "wq": (e, h, d), "wk": (e, g, d), "wv1": (e, g, d // 2),
           "wv2": (e, g, d // 2), "wo": (h, d, e),
           "conv0": (z["taps"][0], c), "conv0_b": (c,),
           "conv1": (h + g, z["taps"][1], d, d), "conv1_b": (h + g, d),
           "temp": (g,),
           "router.down": (e, r), "router.b": (r,), "router.norm": (r,),
           "router.w1": (r, r), "router.b1": (r,), "router.w2": (r, r),
           "router.b2": (r,), "router.out": (r, n), "bias": (n,),
           "experts.gate": (n, e, w), "experts.up": (n, e, w),
           "experts.down": (n, w, e)}
    for res in ("res1", "res2"):
        out.update({f"{res}.{v}": (e,) for v in "abcd"})
    if z["first"] + i > 0:          # the model's first layer has none
        out["router.depth"] = (r,)
    return out


def param_shapes(config: Dict) -> Dict[str, Tuple[int, ...]]:
    """Every weight held; the table once, for embedding and head."""
    z = sizes(config)
    shapes = {"embed": (z["vocab"], z["e"]), "norm_f": (z["e"],)}
    for i in range(z["layers"]):
        shapes.update({f"l{i}.{k}": s
                       for k, s in layer_shapes(config, i).items()})
    return shapes


def param_count(config: Dict) -> int:
    return sum(int(np.prod(s)) for s in param_shapes(config).values())


def _how(leaf: str, config: Dict) -> Tuple[float, float]:
    """(mean, std) a leaf is drawn by: gains, scales, the depth scale, the
    temperature and the biases AWAY from 1 and 0, so that a factor left
    out shows; the projections back into the residual stream over
    sqrt(layers); the small matrices of the router and the grouped
    convolution by their fan-in."""
    z = sizes(config)
    if leaf in ("wo", "experts.down"):
        return 0.0, INIT_STD / math.sqrt(z["layers"])
    if leaf in ("norm_attn", "norm_moe", "norm_f", "router.norm"):
        return 1.0, 0.1
    if leaf.endswith(".a"):
        return 0.85, 0.05
    if leaf.endswith(".c"):
        return 0.7, 0.1
    if leaf.endswith((".b", ".d")) and leaf.startswith("res"):
        return 0.0, INIT_STD
    if leaf in ("conv0", "router.depth"):
        return 0.6, 0.15
    if leaf in ("conv0_b", "conv1_b", "router.b", "router.b1", "router.b2"):
        return 0.0, 0.1
    if leaf == "temp":
        return 0.3, 0.1
    if leaf == "conv1":
        return 0.0, 0.7 / math.sqrt(z["d"])
    if leaf in ("router.w1", "router.w2", "router.out"):
        return 0.0, 1.4 / math.sqrt(z["router"])
    return 0.0, INIT_STD          # matrices, the table; the balancing bias's
    #                               start, which ``balance`` moves


@functools.partial(jax.jit, static_argnames=("shape",))
def _draw(key, mean, std, *, shape):
    """One weight, bfloat16."""
    return (mean + std * jax.random.normal(key, shape, jnp.float32)).astype(
        jnp.bfloat16)


def init_weights(config: Dict, seed: int) -> Dict[str, jax.Array]:
    """Every weight, bfloat16, made on the device from the seed
    (:func:`_how`), one small jitted draw a weight (one compilation a
    shape, which the layers share); then the balancing biases, which are
    not drawn but CALIBRATED (:func:`balance`)."""
    key = fold_seed(seed)
    out = {}
    for i, (name, shape) in enumerate(sorted(param_shapes(config).items())):
        mean, std = _how(name.split(".", 1)[1] if name.startswith("l")
                         else name, config)
        out[name] = _draw(jax.random.fold_in(key, i), jnp.float32(mean),
                          jnp.float32(std), shape=tuple(shape))
    return balance(out, config, jax.random.fold_in(key, 2 ** 30))


BALANCE_TOKENS = 2048    # tokens a layer's loads are evened over
BALANCE_STEPS = 600


@jax.jit
def _even_bias(p, start):
    """The bias that evens the loads of ``argmax(p + bias)`` over the
    sample ``p`` (T, experts): from ``start``, an expert over its even
    share lowered and one under it raised by their distance from it, the
    step shrinking from 0.3 a hundredfold: the rule the model's training
    keeps its loads even by, run to rest."""
    t, n = p.shape

    def step(i, b):
        load = jnp.zeros(n, jnp.float32).at[jnp.argmax(p + b, -1)].add(
            1.0 / t)
        return b + 0.3 * 0.01 ** (i / BALANCE_STEPS) * (1.0 / n - load)

    return jax.lax.fori_loop(0, BALANCE_STEPS, step, start)


def balance(weights: Dict, config: Dict, key) -> Dict:
    """``weights`` with every layer's balancing bias set so that the
    layer takes its experts evenly: a sample of ``BALANCE_TOKENS`` token
    ids (the model's positions at most) drawn from ``key`` goes through
    this file's own float32 forward, and layer by layer the bias is evened on
    the sample's probabilities (:func:`_even_bias`) before the sample
    goes on under it. A DRAWN router sends most tokens to a few experts
    whatever the token (its logits' part that no token moves is as large
    as the part one does: on the chip 62 % of the rows went to one expert
    of 16 and nine got none), which no deployment does, and the work a
    chunk's experts do then follows the seed; in the published model this
    bias is what training moves until the loads are even."""
    z = sizes(config)
    f = _pieces(_key(config), "float32")
    n = min(BALANCE_TOKENS, int(config["max_position_embeddings"]))
    tokens = jax.random.randint(key, (1, n), 0, z["vocab"], jnp.int32)
    out = dict(weights)
    x, r = f["embed"](out["embed"], tokens), None
    for i in range(z["layers"]):
        p_ = f"l{i}."
        w = {k[len(p_):]: v for k, v in out.items() if k.startswith(p_)}
        x = f["attention"](x, w)
        r, p, _, _ = f["scores_of"](x, w, r)
        bias = _even_bias(p, w["bias"].astype(jnp.float32)).astype(
            jnp.bfloat16)
        out[p_ + "bias"] = bias
        ids = jnp.argmax(p + bias.astype(jnp.float32), -1).astype(
            jnp.int32)[:, None]
        x = f["expert_ffn"](x, w, p, ids)
    return out


# ---- arithmetic ------------------------------------------------------------

def _round(x, precision: str):
    if precision == "bfloat16":
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    if precision == "float8":
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


def _f32mm(a, b):
    return jnp.dot(a, b.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)


def _rms(x, g, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * g.astype(jnp.float32))


def _affine(x, scale, shift):
    return x * scale.astype(jnp.float32) + shift.astype(jnp.float32)


def _shifted(x, by: int):
    """``x`` (B, S, ...) moved ``by`` positions later, zeros in front."""
    if by == 0:
        return x
    pad = [(0, 0), (by, 0)] + [(0, 0)] * (x.ndim - 2)
    return jnp.pad(x, pad)[:, :x.shape[1]]


def _rope(x, positions, theta, rot):
    """Rotate the pairs ``(x[i], x[i + rot/2])`` of the first ``rot``
    values of (B, S, H, D) by ``positions * theta^(-2i/rot)``."""
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = positions.astype(jnp.float32)[..., None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rot:]], -1)


def _key(config: Dict) -> Tuple:
    """What the pieces depend on, hashable."""
    z = sizes(config)
    return tuple(sorted((k, v) for k, v in z.items())) + (
        ("eps", float(config.get("rms_norm_eps", 1e-5))),)


@functools.lru_cache(maxsize=None)
def _pieces(config_key: Tuple, precision: str):
    """The jitted pieces of one configuration and precision."""
    z = dict(config_key)
    eps = z["eps"]
    h, g, d = z["heads"], z["kv_heads"], z["d"]
    f32 = jnp.float32

    @jax.jit
    def attention(x, w):
        b, s, _ = x.shape
        u = _rms(x, w["norm_attn"], eps)
        q0 = _mm("bse,ehd->bshd", u, w["wq"], precision)
        k0 = _mm("bse,ehd->bshd", u, w["wk"], precision)
        zc = jnp.concatenate([q0, k0], axis=2)            # (B, S, H + G, d)
        t0, t1 = z["taps"]
        alpha = w["conv0"].astype(f32).reshape(t0, h + g, d)
        z1 = sum(alpha[j] * _shifted(zc, t0 - 1 - j) for j in range(t0)) \
            + w["conv0_b"].astype(f32).reshape(h + g, d)
        z2 = sum(_mm("bshi,hio->bsho", _shifted(z1, t1 - 1 - j),
                     w["conv1"][:, j], precision) for j in range(t1)) \
            + w["conv1_b"].astype(f32)
        m_q = 0.5 * (q0.reshape(b, s, g, h // g, d) + k0[:, :, :, None])
        q = z2[:, :, :h] + m_q.reshape(b, s, h, d)
        k = z2[:, :, h:] + m_q.mean(3)

        def unit(y):
            return y * jax.lax.rsqrt(jnp.sum(y * y, -1, keepdims=True)
                                     + 1e-12) * math.sqrt(d)

        q = unit(q)
        k = unit(k) * jnp.exp(w["temp"].astype(f32))[:, None]
        pos = jnp.arange(s)
        where = jnp.broadcast_to(pos, (b, s))
        q, k = (_rope(y, where, z["theta"], z["rot"]) for y in (q, k))
        v = jnp.concatenate(
            [_mm("bse,egd->bsgd", u, w["wv1"], precision),
             _shifted(_mm("bse,egd->bsgd", u, w["wv2"], precision), 1)], -1)
        blocks = -(-s // QUERY_BLOCK)
        pad = blocks * QUERY_BLOCK - s
        qb = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
            b, blocks, QUERY_BLOCK, g, h // g, d)
        pb = jnp.pad(pos, (0, pad)).reshape(blocks, QUERY_BLOCK)

        def block(args):
            qi, pi = args                      # (B, Q, G, H/G, d), (Q,)
            scores = _mm("bqhgd,bkhd->bhgqk", qi, k, precision) / math.sqrt(d)
            seen = pos[None, :] <= pi[:, None]
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
            return _mm("bhgqk,bkhd->bqhgd", probs, v, precision)

        o = jax.lax.map(block, (jnp.moveaxis(qb, 1, 0), pb))
        o = jnp.moveaxis(o, 0, 1).reshape(b, blocks * QUERY_BLOCK, h, d)[:, :s]
        out = _mm("bqhd,hde->bqe", o, w["wo"], precision)
        return (_affine(x, w["res1.a"], w["res1.b"])
                + _affine(out, w["res1.c"], w["res1.d"]))

    @jax.jit
    def scores_of(x, w, r_prev):
        """The router's state, its probabilities of every expert (float32
        at every precision), its choice by ``p + bias`` and what the
        choice is made by. ``r_prev`` None in the model's first layer."""
        m = _rms(x, w["norm_moe"], eps).reshape(-1, z["e"])
        r = _f32mm(m, w["router.down"]) + w["router.b"].astype(f32)
        if r_prev is not None:
            r = r + w["router.depth"].astype(f32) * r_prev
        hid = _rms(r, w["router.norm"], eps)
        for i in ("1", "2"):
            hid = jax.nn.gelu(_f32mm(hid, w["router.w" + i])
                              + w["router.b" + i].astype(f32),
                              approximate=False)
        p = jax.nn.softmax(_f32mm(hid, w["router.out"]), axis=-1)
        choice = p + w["bias"].astype(f32)
        ids = jnp.argmax(choice, axis=-1).astype(jnp.int32)[:, None]
        return r, p, ids, choice

    @jax.jit
    def routed_part(x, w, p, ids):
        """The experts' part of the layer (T, E): ``ids`` (T, 1) the
        expert each token takes, weighted by ``p``, this forward's own
        probability of it."""
        m = _rms(x, w["norm_moe"], eps).reshape(-1, z["e"])
        gates = jnp.take_along_axis(p, ids, axis=-1)[:, 0]
        dense_g = jnp.where(
            ids == jnp.arange(z["experts"], dtype=jnp.int32)[None, :],
            gates[:, None], 0.0)                        # (T, experts)

        def one(acc, ew):                    # one expert upcast at a time
            gate, up, down, ge = ew
            hid = (jax.nn.silu(_mm("te,ef->tf", m, gate, precision))
                   * _mm("te,ef->tf", m, up, precision))
            return acc + ge[:, None] * _mm("tf,fe->te", hid, down,
                                           precision), None

        f, _ = jax.lax.scan(
            one, jnp.zeros_like(m),
            (w["experts.gate"], w["experts.up"], w["experts.down"],
             dense_g.T))
        return f

    @jax.jit
    def expert_ffn(x, w, p, ids):
        f = routed_part(x, w, p, ids).reshape(x.shape)
        return (_affine(x, w["res2.a"], w["res2.b"])
                + _affine(f, w["res2.c"], w["res2.d"]))

    @jax.jit
    def head(x, gain, table):
        """The tied head, a block of the vocabulary at a time."""
        y = _rms(x, gain, eps)
        v = table.shape[0]
        n = next(n for n in range(1, v + 1)
                 if v % n == 0 and v // n <= VOCAB_BLOCK)
        parts = jax.lax.map(
            lambda rows: _mm("bse,ve->bsv", y, rows, precision),
            table.reshape(n, v // n, table.shape[1]))
        return jnp.moveaxis(parts, 0, 2).reshape(y.shape[:2] + (v,))

    @jax.jit
    def embed(table, tokens):
        return table[tokens].astype(f32)

    return {"attention": attention, "scores_of": scores_of,
            "routed_part": routed_part, "expert_ffn": expert_ffn,
            "head": head, "embed": embed}


def forward_with_routing(weights: Dict, tokens, config: Dict,
                         precision: str = "float32",
                         routing: Optional[List] = None):
    """``tokens`` (B, S) int32 -> (logits (B, S, V) float32, info) where
    ``info`` lists, per layer, ``ids`` (B*S, 1), the expert this forward
    used, ``own_ids`` (its own choice), ``scores`` (B*S, experts): what
    the CHOICE is made by, ``p + bias``, which is what a routing
    comparison measures margins in; ``gate_scores`` is ``p`` and
    ``state`` the router's state."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    z = sizes(config)
    if z["first"]:
        raise ValueError("a later stage's first layer takes the router "
                         "state of the stage before: this file runs stage 0")
    f = _pieces(_key(config), precision)
    tokens = jnp.asarray(tokens)
    x = f["embed"](weights["embed"], tokens)
    info, r = [], None
    for i in range(z["layers"]):
        p_ = f"l{i}."
        w = {k[len(p_):]: v for k, v in weights.items() if k.startswith(p_)}
        x = f["attention"](x, w)
        r, p, own, choice = f["scores_of"](x, w, r)
        ids = own if routing is None else jnp.asarray(
            routing[i], jnp.int32).reshape(own.shape)
        x = f["expert_ffn"](x, w, p, ids)
        info.append({"ids": ids, "own_ids": own, "scores": choice,
                     "gate_scores": p, "state": r})
    rows = config.get("reference_head_rows")
    if not rows or int(rows) >= x.shape[1]:
        return f["head"](x, weights["norm_f"], weights["embed"]), info
    rows = int(rows)
    logits = np.zeros(x.shape[:2] + (z["vocab"],), np.float32)
    logits[:, -rows:] = np.asarray(
        f["head"](x[:, -rows:], weights["norm_f"], weights["embed"]))
    return logits, info


def forward_jit(weights, tokens, config, precision="float32", routing=None):
    return forward_with_routing(weights, tokens, config, precision,
                                routing)[0]
