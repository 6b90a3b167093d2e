"""Plain Olmo-Hybrid (``allenai/Olmo-Hybrid-7B`` on the Hugging Face hub,
``model_type`` ``olmo_hybrid``) in ``jax.numpy``: the yardstick the
benchmark compares the program with. Nothing here imports ``flexflow_tpu``
and nothing here is fast: no cache, no kernel, no chunks; the recurrence
of a linear layer runs token by token (``lax.scan`` over ``t``), the
convolution is a sum of four shifted products, attention is the full (S,
S) matrix.

The layer equations. ``x`` is (B, S, E); every norm an RMSNorm with a
gain; ``layer_types`` says layer by layer which mixer a block has.

* block (the OLMo 2 line's post-norm; ``assumed``): ``h = x +
  norm1(mixer(x))``; ``y = h + norm2(mlp(h))``, ``mlp(h) = (silu(h W_gate)
  * (h W_up)) W_down``; token embedding, the blocks, a final norm, an
  untied head.
* ``linear_attention`` (a gated-delta-rule layer, Gated DeltaNet): H heads,
  keys of ``d_k``, values of ``d_v``. ``q = x W_q``, ``k = x W_k`` (each H
  d_k wide), ``v = x W_v`` (H d_v). Each channel ``c`` of ``[q | k | v]``
  goes through a causal depthwise convolution of 4 taps and SiLU: ``u_t[c]
  = silu(sum_{j=0..3} w[j, c] in_{t-3+j}[c])``, zeros before the sequence.
  Per head, q and k are L2-normalised over ``d_k`` (``a / sqrt(sum a^2 +
  1e-6)``) and q is scaled by ``d_k^-1/2``. ``beta_t = sigmoid(x_t W_b)``
  per head, doubled because ``linear_allow_neg_eigval`` is true (beta in
  (0, 2)). ``g_t = -exp(A_log) * softplus(x_t W_a + dt_bias)`` per head,
  ``alpha_t = exp(g_t)``. The state S of a head is (d_k, d_v), zero before
  the sequence::

      S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
      o_t = S_t^T q_t

  that is ``S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t
  v_t^T``. Out: ``y_t = (RMSNorm_{d_v}(o_t) * silu(x_t W_g)) W_o``, the
  norm's gain of width ``d_v`` shared by the heads.
* ``full_attention``: causal multi-head attention, no bias, an RMSNorm
  over the whole projected q and one over the whole projected k before the
  heads are split, scale ``head_dim^-1/2``, no rotary embedding.

Departures from the published description (each also a line of the
configuration's ``assumed``): the gated delta rule is taken for the
``linear_*`` keys of the config; ``W_q, W_k, W_v, W_g, W_a, W_b`` are
separate matrices (a fused layout is the same function of random
weights); the convolution has no bias; the block's norm placement and the
q/k norm follow the family's convention; no rotary embedding because the
published ``rope_theta`` is null.

Weights are **bfloat16**, held once on the device; the forward upcasts
them piece by piece (a Python loop over layers of small jitted pieces, one
matrix product a piece, the head in slices of the vocabulary), so that at
the published widths it runs beside a program that holds the same arrays.
The full layers' projections are stored as the program's attention op
stores them ((E, H, D), (H, D, E)) so that both hold ONE array.

``precision`` chooses how every matrix product is computed (projections,
the MLP, attention's scores and weighted sum, the head): ``float32``
(``highest``; the reference), ``bfloat16`` (operands rounded, float32
accumulation: what the configuration states the program computes in),
``float8`` (operands rounded to e4m3 as they are, saturating at its 448:
the control, which the comparison has to refuse). Norms, gates, the convolution, softmax and the
recurrence are float32 at every precision, as the configuration states.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("float32", "bfloat16", "float8")
INIT_STD = 0.02
LINEAR, FULL = "linear_attention", "full_attention"
# the published initialisation of such layers' gates (Mamba 2, Gated
# DeltaNet): A uniform in (0, 16), the step dt log-uniform in (0.001, 0.1)
A_MAX, DT_MIN, DT_MAX = 16.0, 1e-3, 1e-1
HEAD_SLICES = 8  # the head's product, in this many slices of the vocabulary
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
    e, h = int(config["hidden_size"]), int(config["num_attention_heads"])
    if int(config.get("num_key_value_heads", h)) != h:
        raise ValueError("grouped key-value heads are not written here")
    types = list(config["layer_types"])
    if len(types) != int(config["num_hidden_layers"]):
        raise ValueError(f"{len(types)} layer_types for "
                         f"{config['num_hidden_layers']} layers")
    lk, lv = (int(config["linear_num_key_heads"]),
              int(config["linear_num_value_heads"]))
    if lk != lv:
        raise ValueError("linear layers with fewer key heads than value "
                         "heads are not written here")
    return {"vocab": int(config["vocab_size"]), "e": e, "types": types,
            "heads": h, "d": e // h, "lh": lv,
            "dk": int(config["linear_key_head_dim"]),
            "dv": int(config["linear_value_head_dim"]),
            "taps": int(config["linear_conv_kernel_dim"]),
            "width": int(config["intermediate_size"])}


def param_shapes(config: Dict) -> Dict[str, Tuple[int, ...]]:
    z = sizes(config)
    e, h, d, lh, dk, dv = z["e"], z["heads"], z["d"], z["lh"], z["dk"], z["dv"]
    shapes = {"embed": (z["vocab"], e), "norm_f": (e,),
              "lm_head": (e, z["vocab"])}
    for i, kind in enumerate(z["types"]):
        p = f"l{i}."
        shapes.update({p + "norm1": (e,), p + "norm2": (e,),
                       p + "mlp.gate": (e, z["width"]),
                       p + "mlp.up": (e, z["width"]),
                       p + "mlp.down": (z["width"], e)})
        if kind == LINEAR:
            shapes.update({
                p + "wq": (e, lh * dk), p + "wk": (e, lh * dk),
                p + "wv": (e, lh * dv), p + "wg": (e, lh * dv),
                p + "wa": (e, lh), p + "wb": (e, lh),
                p + "conv": (z["taps"], 2 * lh * dk + lh * dv),
                p + "a_log": (lh,), p + "dt_bias": (lh,),
                p + "norm": (dv,), p + "wo": (lh * dv, e)})
        elif kind == FULL:
            shapes.update({
                p + "wq": (e, h, d), p + "wk": (e, h, d), p + "wv": (e, h, d),
                p + "wo": (h, d, e), p + "q_norm": (h, d),
                p + "k_norm": (h, d)})
        else:
            raise ValueError(f"layer {i}: unknown type {kind!r}")
    return shapes


def param_count(config: Dict) -> int:
    return sum(int(np.prod(s)) for s in param_shapes(config).values())


@functools.partial(jax.jit, static_argnames=("shape", "kind"))
def _draw(key, scale, *, shape, kind):
    """One weight, bfloat16. ``kind``: ``gain`` (1 + N(0, 0.02)),
    ``matrix`` (N(0, 0.02) times ``scale``), ``a_log`` (log of a uniform
    in (0, A_MAX)) or ``dt_bias`` (the inverse softplus of a log-uniform
    step in (DT_MIN, DT_MAX))."""
    if kind == "a_log":
        x = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1e-3, A_MAX))
    elif kind == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(DT_MIN), math.log(DT_MAX)))
        x = dt + jnp.log(-jnp.expm1(-dt))
    else:
        x = INIT_STD * jax.random.normal(key, shape, jnp.float32)
        x = 1.0 + x if kind == "gain" else x * scale
    return x.astype(jnp.bfloat16)


def init_weights(config: Dict, seed: int) -> Dict[str, jax.Array]:
    """Every weight, bfloat16, made on the device from the seed: matrices
    and the embedding N(0, 0.02), the projections back into the residual
    stream (``wo``, ``mlp.down``) over sqrt(2 * layers), norm gains 1 +
    N(0, 0.02) so that a dropped gain shows, ``a_log`` and ``dt_bias`` as
    the layers' published initialisation draws them. One small jitted
    draw a weight (one compilation a shape, which the layers share)."""
    key = fold_seed(seed)
    resid = 1.0 / math.sqrt(2.0 * int(config["num_hidden_layers"]))
    out = {}
    for i, (name, shape) in enumerate(sorted(param_shapes(config).items())):
        leaf = name.rsplit(".", 1)[-1]
        if leaf in ("a_log", "dt_bias"):
            kind = leaf
        elif "norm" in leaf:
            kind = "gain"
        else:
            kind = "matrix"
        out[name] = _draw(jax.random.fold_in(key, i),
                          jnp.float32(resid if leaf in ("wo", "down")
                                      else 1.0),
                          shape=tuple(shape), kind=kind)
    return out


# ---- arithmetic ------------------------------------------------------------

def _round(x, precision: str):
    if precision == "bfloat16":
        # reduce_precision, not astype and back: XLA may drop the pair
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    if precision == "float8":
        # saturating, as a float8 unit converts: e4m3 has no infinity, and
        # a bare cast turns what lies beyond its 448 into NaN (the MLP's
        # silu(a) * up passes it at these widths)
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
    return tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v)
        for k, v in config.items()
        if isinstance(v, (int, float, str, bool)) or k == "layer_types"))


@functools.lru_cache(maxsize=None)
def _pieces(config_items: Tuple, precision: str):
    """The jitted pieces of one configuration and precision. A piece
    holds at most ONE matrix product, so that the float32 copy of a
    bfloat16 weight (and what a ``highest`` product splits it into) lives
    for that product alone: the forward's own memory stays a few hundred
    megabytes beside a program that fills the chip."""
    config = {k: (list(v) if isinstance(v, tuple) else v)
              for k, v in config_items}
    z = sizes(config)
    eps = float(config.get("rms_norm_eps", 1e-6))
    double = bool(config.get("linear_allow_neg_eigval", False))
    lh, dk, dv, taps = z["lh"], z["dk"], z["dv"], z["taps"]
    h, d = z["heads"], z["d"]

    @jax.jit
    def project(x, w):
        """(B, S, in) x (in, ...) -> (B, S, out): one matrix product."""
        return _mm("bsi,io->bso", x, w.reshape(x.shape[-1], -1), precision)

    @jax.jit
    def recurrence(cin, a_logit, b_logit, w):
        """``cin`` (B, S, channels) the convolution's inputs ``[q | k |
        v]``, ``a_logit`` = x W_a and ``b_logit`` = x W_b (B, S, H): the
        convolution, the gates, the recurrence token by token and the
        output's norm; (B, S, H d_v)."""
        b, s, _ = cin.shape
        f32 = jnp.float32
        # the convolution as a sum of four shifted products
        cw = w["conv"].astype(f32)
        u = sum(cw[j] * jnp.pad(cin, ((0, 0), (taps - 1 - j, 0),
                                      (0, 0)))[:, :s] for j in range(taps))
        u = jax.nn.silu(u)
        q = u[..., :lh * dk].reshape(b, s, lh, dk)
        k = u[..., lh * dk:2 * lh * dk].reshape(b, s, lh, dk)
        v = u[..., 2 * lh * dk:].reshape(b, s, lh, dv)
        q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) \
            * dk ** -0.5
        k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
        beta = jax.nn.sigmoid(b_logit)
        beta = beta * 2.0 if double else beta
        g = -jnp.exp(w["a_log"].astype(f32)) * jax.nn.softplus(
            a_logit + w["dt_bias"].astype(f32))
        alpha = jnp.exp(g)

        def token(state, xs):              # state (B, H, d_k, d_v)
            qt, kt, vt, at, bt = xs        # (B, H, .) one position
            state = state * at[..., None, None]
            r = vt - jnp.sum(state * kt[..., None], axis=-2)
            state = state + kt[..., None] * (bt[..., None] * r)[..., None, :]
            return state, jnp.sum(state * qt[..., None], axis=-2)

        t_first = lambda a: jnp.moveaxis(a, 1, 0)  # noqa: E731
        _, o = jax.lax.scan(token, jnp.zeros((b, lh, dk, dv), f32),
                            tuple(map(t_first, (q, k, v, alpha, beta))))
        o = _rms(jnp.moveaxis(o, 0, 1), w["norm"], eps)   # (B, S, H, d_v)
        return o.reshape(b, s, lh * dv)

    def linear_mixer(x, w):
        cin = jnp.concatenate([project(x, w[n]) for n in ("wq", "wk", "wv")],
                              axis=-1)
        small = {n: w[n] for n in ("conv", "a_log", "dt_bias", "norm")}
        o = recurrence(cin, project(x, w["wa"]), project(x, w["wb"]), small)
        return project(o * jax.nn.silu(project(x, w["wg"])), w["wo"])

    @jax.jit
    def attend(q, k, v, q_gain, k_gain):
        b, s, _ = q.shape
        q = _rms(q, q_gain.reshape(-1), eps).reshape(b, s, h, d)
        k = _rms(k, k_gain.reshape(-1), eps).reshape(b, s, h, d)
        v = v.reshape(b, s, h, d)
        scores = _mm("bqhd,bkhd->bhqk", q, k, precision) * d ** -0.5
        causal = jnp.tril(jnp.ones((s, s), bool))
        probs = jax.nn.softmax(jnp.where(causal[None, None], scores,
                                         -jnp.inf), axis=-1)
        return _mm("bhqk,bkhd->bqhd", probs, v, precision).reshape(
            b, s, h * d)

    def full_mixer(x, w):
        o = attend(project(x, w["wq"]), project(x, w["wk"]),
                   project(x, w["wv"]), w["q_norm"], w["k_norm"])
        return project(o, w["wo"])

    @jax.jit
    def add_normed(x, branch, gain):
        return x + _rms(branch, gain, eps)

    @jax.jit
    def gate(a, up):
        return jax.nn.silu(a) * up

    def after_mixer(x, mixed, w):
        h1 = add_normed(x, mixed, w["norm1"])
        m = project(gate(project(h1, w["mlp.gate"]),
                         project(h1, w["mlp.up"])), w["mlp.down"])
        return add_normed(h1, m, w["norm2"])

    @jax.jit
    def normed(x, g):
        return _rms(x, g, eps)

    def head(x, g, lm_head):
        u = normed(x, g)
        cols = lm_head.shape[1]
        step = -(-cols // HEAD_SLICES)
        return jnp.concatenate([project(u, lm_head[:, c:c + step])
                                for c in range(0, cols, step)], axis=-1)

    @jax.jit
    def embed(table, tokens):
        return table[tokens].astype(jnp.float32)

    return {LINEAR: linear_mixer, FULL: full_mixer, "after": after_mixer,
            "head": head, "embed": embed}


def forward_jit(weights: Dict, tokens, config: Dict,
                precision: str = "float32"):
    """``tokens`` (B, S) int32 -> logits (B, S, V) float32."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    f = _pieces(_key(config), precision)
    x = f["embed"](weights["embed"], jnp.asarray(tokens))
    for i, kind in enumerate(config["layer_types"]):
        p = f"l{i}."
        w = {k[len(p):]: v for k, v in weights.items() if k.startswith(p)}
        # waited for layer by layer: dispatched ahead, the pieces' buffers
        # would all be reserved at once
        x = jax.block_until_ready(f["after"](x, f[kind](x, w), w))
    return f["head"](x, weights["norm_f"], weights["lm_head"])
