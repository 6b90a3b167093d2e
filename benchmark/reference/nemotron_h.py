"""Plain Nemotron-H with LatentMoE (``nvidia/NVIDIA-Nemotron-3-Super-120B-
A12B-BF16`` on the Hugging Face hub, ``model_type`` ``nemotron_h``) in
``jax.numpy``: the yardstick the benchmark compares the program with.
Nothing here imports ``flexflow_tpu`` and nothing here is fast: no cache,
no kernels, no chunked or grouped products; the state-space layer is the
token-by-token recurrence, and every token goes through every held
expert and is weighted by its gate (0 where it was not routed).

The layer equations (``x`` a layer's input; ``u = RMSNorm(x)`` with a
gain; ``layer_norm_epsilon`` in every norm; no bias on any linear map).
``hybrid_override_pattern`` names the layers letter by letter:

* block: ``y = x + mixer(u)``, ONE mixer a layer; after the last layer a
  final RMSNorm and the untied head;
* ``M``, Mamba-2 (H = ``mamba_num_heads`` heads of P = ``mamba_head_dim``;
  N = ``ssm_state_size``; G = ``n_groups``; K = ``conv_kernel`` taps):
  ``[z | xBC | dt] = u W_in`` (``H P | H P + 2 G N | H`` columns); ``xBC_t
  = silu(bias + sum_{j < K} w_j xBC_{t-K+1+j})``, a causal depthwise
  convolution over all ``H P + 2 G N`` channels, zeros before the
  sequence; ``[xs | B | C] = xBC`` (H heads of P | G groups of N | G
  groups of N), head h reads group ``h // (H / G)``; ``dt_t = softplus(dt_t
  + dt_bias)``, ``a_t = exp(dt_t A)``, ``A = -exp(A_log)``, one scalar a
  head; per head a state ``S`` (P x N, float32, zero before the
  sequence): ``S_t = a_t S_{t-1} + dt_t xs_t B_t^T``, ``y_t = S_t C_t + D
  xs_t``; ``y = RMSNorm_G(y * silu(z))``, the norm over each of the G
  groups of ``H P / G`` channels, with a gain a channel; out ``= y
  W_out``. ``time_step_min``, ``_max`` and ``_floor`` shape the initial
  ``dt_bias`` only; ``chunk_size`` is the published kernels' tiling of
  this recurrence and no part of its result;
* ``E``, LatentMoE: ``s = sigmoid(float32(u) W_r)`` over ALL the
  published experts, in float32 at every ``precision``; ``T`` = the
  ``num_experts_per_tok`` largest of ``s + b`` (``b`` the selection bias,
  in the choice only); ``g_e = routed_scaling_factor * s_e / sum_T s``;
  ``v = u W_dn`` (``hidden_size`` -> ``moe_latent_size``); ``MLP_e(v) =
  relu(v W1_e)^2 W2_e`` (latent -> ``moe_intermediate_size`` -> latent);
  out ``= (sum_{e in T} g_e MLP_e(v)) W_up + relu(u W1_s)^2 W2_s`` (the
  shared expert, ``hidden_size`` -> ``moe_shared_expert_intermediate_size``
  -> ``hidden_size``);
* ``*``, attention (``num_attention_heads`` query heads on
  ``num_key_value_heads`` key-value heads of ``head_dim``): ``q = u W_q``,
  ``k = u W_k``, ``v = u W_v``; query head h reads key-value head ``h //
  (heads / kv heads)``; causal softmax of ``q . k / sqrt(head_dim)``; out
  ``= o W_o``.

What the published ``config.json`` does not settle is set by the
family's published code and report, and listed once, in the
configuration file's ``assumed`` block (``configs/nemotron3-super-
ep4.json``): no rotary embedding in ``*``; the router and the shared
expert read ``u`` at the full width and only the routed experts the
latent, with no norm or bias on the latent projections; the draws of
``dt_bias``, ``A_log``, ``D``, ``b`` and the convolution. The multi-token
prediction module is a drafting module beside the model, no part of the
next token's logits, and is not here.

**The share.** A configuration file may describe one holder of a larger
deployment: ``n_routed_experts`` is then the experts HELD (a contiguous
run from ``expert_first``, default 0) while the router and its bias keep
``published.n_routed_experts`` columns, and ``vocab_size`` the rows of
the vocabulary held. The forward computes that holder's partial result:
the routed sum runs over the held experts of ``T`` only and goes through
``W_up`` as it is (``W_up`` is linear: the holders' partial sums after
``W_up`` add up to the whole layer's routed part); nothing stands in for
the absent experts; the shared expert, the ``M`` and ``*`` layers, the
router and the latent projections are whole.

Weights are **bfloat16**; the forward upcasts them, one projection and
one expert at a time: a Python loop over layers of small jitted pieces,
so that at the published widths it runs beside a program that holds the
same arrays.

``precision`` chooses how every matrix product but the router's is
computed (the recurrence's own state stays float32, as the configuration
states it): ``float32`` (``highest``; the reference), ``bfloat16``
(operands rounded, float32 accumulation: what the configuration states
the program computes in), ``float8`` (operands rounded to e4m3 as they
are, saturating: the control, which the comparison has to refuse),
``float8_scaled`` (each operand scaled so that its largest magnitude is
e4m3's 448, then rounded: what a deployment in float8 computes).

``routing=`` (a list, one ``(tokens, picks)`` int array per ``E`` layer)
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
MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


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
    pattern = str(config["hybrid_override_pattern"])
    if len(pattern) != int(config["num_hidden_layers"]) or set(
            pattern) - {MAMBA, EXPERTS, ATTENTION}:
        raise ValueError(f"pattern {pattern!r} is not "
                         f"{config['num_hidden_layers']} letters of M, E, *")
    h, p = int(config["mamba_num_heads"]), int(config["mamba_head_dim"])
    g, n = int(config["n_groups"]), int(config["ssm_state_size"])
    return {
        "vocab": int(config["vocab_size"]), "e": int(config["hidden_size"]),
        "pattern": pattern, "layers": len(pattern),
        "m_heads": h, "m_dim": p, "state": n, "groups": g,
        "inner": h * p, "channels": h * p + 2 * g * n,
        "taps": int(config["conv_kernel"]),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "held": held, "first": int(config.get("expert_first", 0)),
        "routed": int(pub.get("n_routed_experts", held)),
        "k": int(config["num_experts_per_tok"]),
        "latent": int(config["moe_latent_size"]),
        "expert_width": int(config["moe_intermediate_size"]),
        "shared_width": int(config["moe_shared_expert_intermediate_size"]),
    }


def layer_shapes(config: Dict, kind: str) -> Dict[str, Tuple[int, ...]]:
    """One layer's weights by its letter (the norm before it included)."""
    z = sizes(config)
    e = z["e"]
    if kind == MAMBA:
        return {"norm": (e,),
                "w_in": (e, z["inner"] + z["channels"] + z["m_heads"]),
                "conv": (z["taps"], z["channels"]),
                "conv_bias": (z["channels"],), "a_log": (z["m_heads"],),
                "dt_bias": (z["m_heads"],), "d": (z["m_heads"],),
                "gate_norm": (z["inner"],), "w_out": (z["inner"], e)}
    if kind == EXPERTS:
        lat, w, ws, n = (z["latent"], z["expert_width"], z["shared_width"],
                         z["held"])
        return {"norm": (e,), "router": (e, z["routed"]),
                "bias": (z["routed"],), "latent_down": (e, lat),
                "latent_up": (lat, e), "experts.up": (n, lat, w),
                "experts.down": (n, w, lat), "shared.up": (e, ws),
                "shared.down": (ws, e)}
    h, hkv, d = z["heads"], z["kv_heads"], z["head_dim"]
    return {"norm": (e,), "wq": (e, h, d), "wk": (e, hkv, d),
            "wv": (e, hkv, d), "wo": (h, d, e)}


def param_shapes(config: Dict) -> Dict[str, Tuple[int, ...]]:
    z = sizes(config)
    shapes = {"embed": (z["vocab"], z["e"]), "norm_f": (z["e"],),
              "lm_head": (z["e"], z["vocab"])}
    for i, kind in enumerate(z["pattern"]):
        shapes.update({f"l{i}.{k}": s
                       for k, s in layer_shapes(config, kind).items()})
    return shapes


def param_count(config: Dict) -> int:
    return sum(int(np.prod(s)) for s in param_shapes(config).values())


def state_bytes_per_request(config: Dict, tail_bytes: int = 2) -> int:
    """What a request keeps of the ``M`` layers: a float32 state and the
    last ``taps - 1`` inputs of the convolution each."""
    z = sizes(config)
    return z["pattern"].count(MAMBA) * (
        4 * z["m_heads"] * z["m_dim"] * z["state"]
        + tail_bytes * (z["taps"] - 1) * z["channels"])


# how each leaf is drawn: the rest are matrices, N(0, 0.02)
_RESIDUAL = ("w_out", "wo", "latent_up", "down")
_KINDS = {"norm": "gain", "gate_norm": "gain", "norm_f": "gain",
          "a_log": "a_log", "dt_bias": "dt_bias", "d": "one",
          "conv": "conv", "conv_bias": "conv"}


@functools.partial(jax.jit, static_argnames=("shape", "kind", "taps"))
def _draw(key, scale, *, shape, kind, taps=4):
    """One weight, bfloat16."""
    f32 = jnp.float32
    if kind == "one":
        return jnp.ones(shape, jnp.bfloat16)
    if kind == "a_log":                      # A = -U(1, 16)
        x = jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
    elif kind == "dt_bias":                  # softplus^-1 of a log-uniform
        dt = jnp.exp(jax.random.uniform(key, shape, f32, math.log(0.001),
                                        math.log(0.1)))
        x = dt + jnp.log(-jnp.expm1(-dt))
    elif kind == "conv":                     # U(-1 / sqrt(taps), ...)
        bound = 1.0 / math.sqrt(taps)
        x = jax.random.uniform(key, shape, f32, -bound, bound)
    else:
        x = INIT_STD * jax.random.normal(key, shape, f32)
        x = 1.0 + x if kind == "gain" else x * scale
    return x.astype(jnp.bfloat16)


def init_weights(config: Dict, seed: int) -> Dict[str, jax.Array]:
    """Every weight, bfloat16, made on the device from the seed: matrices,
    the embedding and the selection bias N(0, 0.02), the projections back
    into the residual stream (``w_out``, ``wo``, ``latent_up``,
    ``shared.down``) over sqrt(layers) (``rescale_prenorm_residual``),
    norm gains 1 + N(0, 0.02) so that a dropped gain shows, ``A_log``,
    ``dt_bias``, ``D`` and the convolution as the configuration's
    ``assumed`` block says. One small jitted draw a weight (one
    compilation a shape, which the layers share)."""
    key = fold_seed(seed)
    z = sizes(config)
    resid = 1.0 / math.sqrt(float(z["layers"]))
    out = {}
    for i, (name, shape) in enumerate(sorted(param_shapes(config).items())):
        leaf = name.rsplit(".", 1)[-1]
        experts_down = name.endswith("experts.down")   # into the latent
        out[name] = _draw(
            jax.random.fold_in(key, i),
            jnp.float32(resid if leaf in _RESIDUAL and not experts_down
                        else 1.0),
            shape=tuple(shape), kind=_KINDS.get(leaf, "matrix"),
            taps=z["taps"])
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


def _relu2_mlp(v, up, down, precision):
    h = jnp.square(jnp.maximum(_mm("...e,ef->...f", v, up, precision), 0.0))
    return _mm("...f,fe->...e", h, down, precision)


def _key(config: Dict) -> Tuple:
    def flat(v):
        return tuple(sorted(v.items())) if isinstance(v, dict) else v

    return tuple(sorted((k, flat(v)) for k, v in config.items()
                        if isinstance(v, (int, float, str, bool))
                        or k == "published"))


@functools.lru_cache(maxsize=None)
def _pieces(config_items: Tuple, precision: str):
    """The jitted pieces of one configuration and precision."""
    config = {k: (dict(v) if isinstance(v, tuple) else v)
              for k, v in config_items}
    z = sizes(config)
    eps = float(config.get("layer_norm_epsilon", 1e-5))
    f32 = jnp.float32

    @jax.jit
    def mamba(x, w):
        b, s, _ = x.shape
        h, p, n, g = z["m_heads"], z["m_dim"], z["state"], z["groups"]
        u = _rms(x, w["norm"], eps)
        proj = _mm("bse,ef->bsf", u, w["w_in"], precision)
        zg = proj[..., :z["inner"]]
        xbc = proj[..., z["inner"]:z["inner"] + z["channels"]]
        dt = jax.nn.softplus(proj[..., -h:] + w["dt_bias"].astype(f32))
        # the causal depthwise convolution, a tap at a time
        k = z["taps"]
        padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
        conv = sum(w["conv"].astype(f32)[j] * padded[:, j:j + s]
                   for j in range(k))
        xbc = jax.nn.silu(conv + w["conv_bias"].astype(f32))
        xs = xbc[..., :z["inner"]].reshape(b, s, h, p)
        bm = xbc[..., z["inner"]:z["inner"] + g * n].reshape(b, s, g, n)
        cm = xbc[..., z["inner"] + g * n:].reshape(b, s, g, n)
        bm, cm = (jnp.repeat(v, h // g, axis=2) for v in (bm, cm))
        a = jnp.exp(dt * -jnp.exp(w["a_log"].astype(f32)))    # (B, S, H)

        def token(state, t):                 # the recurrence, as written
            a_t, dt_t, x_t, b_t, c_t = t
            state = (a_t[..., None, None] * state
                     + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None])
            y = jnp.einsum("bhpn,bhn->bhp", state, c_t,
                           precision=jax.lax.Precision.HIGHEST)
            return state, y

        _, y = jax.lax.scan(
            token, jnp.zeros((b, h, p, n), f32),
            tuple(jnp.moveaxis(v, 1, 0) for v in (a, dt, xs, bm, cm)))
        y = jnp.moveaxis(y, 0, 1) + w["d"].astype(f32)[:, None] * xs
        y = y.reshape(b, s, z["inner"]) * jax.nn.silu(zg)
        grouped = y.reshape(b, s, g, -1)
        grouped = grouped * jax.lax.rsqrt(
            jnp.mean(grouped * grouped, -1, keepdims=True) + eps)
        y = grouped.reshape(b, s, z["inner"]) * w["gate_norm"].astype(f32)
        return x + _mm("bsf,fe->bse", y, w["w_out"], precision)

    @jax.jit
    def attention(x, w):
        b, s, _ = x.shape
        h, hkv, d = z["heads"], z["kv_heads"], z["head_dim"]
        u = _rms(x, w["norm"], eps)
        q = _mm("bse,ehd->bshd", u, w["wq"], precision)
        k = jnp.repeat(_mm("bse,ehd->bshd", u, w["wk"], precision),
                       h // hkv, axis=2)
        v = jnp.repeat(_mm("bse,ehd->bshd", u, w["wv"], precision),
                       h // hkv, axis=2)
        scores = _mm("bqhd,bkhd->bhqk", q, k, precision) / math.sqrt(d)
        causal = jnp.tril(jnp.ones((s, s), bool))
        probs = jax.nn.softmax(jnp.where(causal[None, None], scores,
                                         -jnp.inf), axis=-1)
        o = _mm("bhqk,bkhd->bqhd", probs, v, precision)
        return x + _mm("bqhd,hde->bqe", o, w["wo"], precision)

    @jax.jit
    def scores_of(x, w):
        """The router's scores of every published expert, float32 at
        every precision, and the choice by ``s + b``."""
        u = _rms(x, w["norm"], eps).reshape(-1, z["e"])
        logits = jnp.einsum("te,en->tn", u, w["router"].astype(f32),
                            precision=jax.lax.Precision.HIGHEST)
        s = jax.nn.sigmoid(logits)
        choice = s + w["bias"].astype(f32)
        _, ids = jax.lax.top_k(choice, z["k"])
        return s, ids.astype(jnp.int32), choice

    @jax.jit
    def expert_ffn(x, w, s, ids):
        """``ids`` (T, k): the experts each token takes; their weights
        come from ``s``, this forward's own scores."""
        u2 = _rms(x, w["norm"], eps).reshape(-1, z["e"])
        g = jnp.take_along_axis(s, ids, axis=-1)
        if config.get("norm_topk_prob", True):
            g = g / (g.sum(-1, keepdims=True) + 1e-20)
        g = g * float(config.get("routed_scaling_factor", 1.0))
        # (T, held): a token's weight for each held expert, 0 where it
        # did not take it
        local = ids - z["first"]
        dense_g = jnp.zeros((u2.shape[0], z["held"]), f32)
        for j in range(z["k"]):
            ok = (local[:, j] >= 0) & (local[:, j] < z["held"])
            dense_g = dense_g.at[jnp.arange(u2.shape[0]),
                                 jnp.clip(local[:, j], 0, z["held"] - 1)].add(
                jnp.where(ok, g[:, j], 0.0))
        v = _mm("te,el->tl", u2, w["latent_down"], precision)

        def one(acc, ew):                    # one expert upcast at a time
            up, down, ge = ew
            return acc + ge[:, None] * _relu2_mlp(v, up, down,
                                                  precision), None

        routed, _ = jax.lax.scan(
            one, jnp.zeros_like(v),
            (w["experts.up"], w["experts.down"], dense_g.T))
        out = (_mm("tl,le->te", routed, w["latent_up"], precision)
               + _relu2_mlp(u2, w["shared.up"], w["shared.down"], precision))
        return x + out.reshape(x.shape)

    @jax.jit
    def head(x, g, lm_head):
        return _mm("bse,ev->bsv", _rms(x, g, eps), lm_head, precision)

    @jax.jit
    def embed(table, tokens):
        return table[tokens].astype(f32)

    return {"mamba": mamba, "attention": attention, "scores_of": scores_of,
            "expert_ffn": expert_ffn, "head": head, "embed": embed}


def forward_with_routing(weights: Dict, tokens, config: Dict,
                         precision: str = "float32",
                         routing: Optional[List] = None):
    """``tokens`` (B, S) int32 -> (logits (B, S, V) float32, info) where
    ``info`` lists, per ``E`` layer, ``ids`` (B*S, k), the experts this
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
    for i, kind in enumerate(z["pattern"]):
        p = f"l{i}."
        w = {k[len(p):]: v for k, v in weights.items() if k.startswith(p)}
        if kind == MAMBA:
            x = f["mamba"](x, w)
        elif kind == ATTENTION:
            x = f["attention"](x, w)
        else:
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
