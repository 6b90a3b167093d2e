"""Plain Ling-3.0-flash (``inclusionAI/Ling-3.0-flash`` on the Hugging Face
hub, ``model_type`` ``bailing_hybrid``) in ``jax.numpy``: the yardstick the
benchmark compares the program with. Nothing here imports ``flexflow_tpu``
and nothing here is fast: no cache, no kernels, no chunks; a KDA layer's
recurrence runs token by token (``lax.scan`` over ``t``), its convolution
is a sum of four shifted products, latent attention is the full (S, S)
matrix, every token goes through every held expert and is weighted by its
gate (0 where it was not routed).

The layer equations. ``x`` is (B, S, E); every norm an RMSNorm with a
gain; the program's layer ``i`` is published layer ``first_layer + i``
and takes its mixer and its feed-forward by that PUBLISHED index ``p``.

* block, pre-norm: ``h = x + mixer(norm1(x))``; ``y = h + ffn(norm2(h))``;
  a final norm, an untied head.
* **KDA mixer** (``(p + 1) % layer_group_size != 0``; H heads of ``d_k =
  d_v = head_dim``): ``q, k, v = silu(conv(x W_q)), silu(conv(x W_k)),
  silu(conv(x W_v))``, the convolution causal, depthwise, of
  ``short_conv_kernel_size`` taps, zeros before the sequence, no bias; per
  head q and k are L2-normalised over ``d_k`` (``a / sqrt(sum a^2 +
  1e-6)``) and q is scaled by ``d_k^-1/2``. Decay, one a key CHANNEL:
  ``g_t = kda_lower_bound * sigmoid(exp(A_log_h) * (x_t W_f + dt_bias))``
  (``W_f`` (E, H d_k), ``A_log`` one a head, ``dt_bias`` one a channel),
  ``alpha_t = exp(g_t)`` in ``(e^-5, 1)``; ``beta_t = sigmoid(x_t W_b)``,
  one a head. The state ``S`` of a head is ``(d_k, d_v)``, float32, zero
  before the sequence::

      S' = diag(alpha_t) S_{t-1}
      S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
      o_t = S_t^T q_t

  Out: ``y_t = concat_h(RMSNorm_{d_v}(o_t,h) * sigmoid(x_t w_gate,h))
  W_o``: one gain of width ``d_v`` shared by the heads, ONE gate a head
  (``W_gate`` (E, H)).
* **latent mixer** (``(p + 1) % layer_group_size == 0``): ``q = x W_q``
  direct (``q_lora_rank`` null), ``[q_nope | q_rope]`` a head; ``[ckv |
  kr] = x W_kva``; ``c = norm_kv(ckv)``; ``[k_nope | v] = c W_kvb`` a
  head; rotary over the ``qk_rope_head_dim`` numbers in interleaved pairs
  ``(2i, 2i + 1)``, frequencies ``rope_theta^(-2i/d)``, no scaling, one
  ``k_rope`` for all heads; scores ``(q_nope . k_nope + q_rope . k_rope) *
  (nope + rope)^-1/2``, causal softmax, ``sum p v``; each head's output
  times ``sigmoid(x_t w_gate,h)`` (the same head-wise gate), then ``W_o``.
* **feed-forward**: ``p < first_k_dense_replace``: the gated MLP
  ``(silu(u Wg) * (u Wu)) Wd`` of ``intermediate_size``. Else: scores ``s =
  sigmoid(float32(u) W_router)`` over ALL published experts, float32 at
  every ``precision``; the choice is made by ``s + b`` (the selection
  bias): ``n_group`` groups, a group scores the sum of its two highest ``s
  + b``, the ``topk_group`` highest groups stay, ``T`` = the
  ``num_experts_per_tok`` highest ``s + b`` within them; ``g_e = s_e /
  sum_T s * routed_scaling_factor``; output ``sum_{e in T} g_e MLP_e(u) +
  MLP_shared(u)``.

**The share.** A configuration file may describe one holder of a larger
deployment: ``num_experts`` is then the experts HELD (a contiguous run
from ``expert_first``, default 0) while the router and its bias keep
``published.num_experts`` columns, ``vocab_size`` the rows of the
vocabulary held, and ``first_layer`` / ``num_hidden_layers`` the run of
published layers held. The forward computes that holder's partial result:
the routed sum runs over the held experts of ``T`` only, nothing stands
in for the absent ones; the shared expert, both mixers, the router and
the dense MLP are whole. The holders' routed parts, and the shared
expert counted once, add up to the uncut layer.

Weights are **bfloat16** (the deployment's are), made on the device from
the seed (:func:`init_weights`, which also says how the gates are drawn
and calibrates the selection bias); the forward upcasts them one
projection and one expert at a time: a Python loop over layers of small
jitted pieces, so that at the published widths it runs beside a program
that holds the same arrays.

``precision`` chooses how every matrix product but the router's is
computed: ``float32`` (``highest``; the reference), ``bfloat16`` (operands
rounded, float32 accumulation: what the configuration states the program
computes in), ``float8`` (operands rounded to e4m3, saturating, each
scaled so that its largest magnitude is e4m3's 448: what reading bfloat16
weights as float8 gives: the control, which the comparison has to refuse).
``state_dtype`` is what a KDA state is rounded to after every token:
``float32``, or ``bfloat16``, which ``control_ling.py`` reads beside the
control. Norms, gates, the convolution, softmax and the recurrence are
float32 at every precision.

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

PRECISIONS = ("float32", "bfloat16", "float8")
STATE_DTYPES = ("float32", "bfloat16")
KDA, LATENT = "kda", "latent"
GAIN_STD = 0.02    # norm gains 1 + N(0, GAIN_STD): a dropped gain shows
UNIT_EPS = 1e-6    # under the root of a head's L2 norm of q and of k
E4M3_MAX = 448.0
# the gates' draw: exp(A_log) log-uniform over (A_MIN, A_MAX) a head; a
# channel's dt_bias such that at x W_f = 0 it keeps exp(-1 / tau) a token,
# tau log-uniform over (TAU_MIN, TAU_MAX) tokens: the median channel keeps
# exp(-1/64) = 0.9845 a token, the fastest 0.78, the slowest 0.9990
A_MIN, A_MAX = 0.5, 2.0
TAU_MIN, TAU_MAX = 4.0, 1024.0
# the selection bias's calibration (:func:`balance`)
BALANCE_TOKENS = 4096
BALANCE_SEQ = 512
BALANCE_TOLERANCE = 0.10
BALANCE_STEPS = 400
BALANCE_RATE = 0.01
HEAD_SLICES = 4    # the head's product, in this many slices of the vocabulary


def fold_seed(seed: int) -> jax.Array:
    """A PRNG key (``rbg``: the device's own bit generator) from any
    non-negative whole number: the low 31 bits seed it, the rest is
    folded in."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed {seed} < 0")
    key = jax.random.key(seed & 0x7FFFFFFF, impl="rbg")
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def layer_kinds(config: Dict) -> List[Tuple[str, bool]]:
    """Per held layer, in order: (its mixer, whether its feed-forward is
    the dense MLP), by its PUBLISHED index."""
    first = int(config.get("first_layer", 0))
    period = int(config["layer_group_size"])
    dense = int(config["first_k_dense_replace"])
    return [(LATENT if (p + 1) % period == 0 else KDA, p < dense)
            for p in range(first, first + int(config["num_hidden_layers"]))]


def sizes(config: Dict) -> Dict:
    """The shapes a configuration states, the share included."""
    pub = config.get("published") or {}
    held = int(config["num_experts"])
    return {
        "vocab": int(config["vocab_size"]), "e": int(config["hidden_size"]),
        "kinds": layer_kinds(config),
        "heads": int(config["num_attention_heads"]),
        "dk": int(config["head_dim"]), "dv": int(config["head_dim"]),
        "taps": int(config["short_conv_kernel_size"]),
        "lower": float(config["kda_lower_bound"]),
        "kv_rank": int(config["kv_lora_rank"]),
        "nope": int(config["qk_nope_head_dim"]),
        "rope": int(config["qk_rope_head_dim"]),
        "v": int(config["v_head_dim"]),
        "dense_width": int(config["intermediate_size"]),
        "expert_width": int(config["moe_intermediate_size"]),
        "shared_width": (int(config.get("num_shared_experts", 0))
                         * int(config["moe_shared_expert_intermediate_size"])),
        "held": held, "first": int(config.get("expert_first", 0)),
        "routed": int(pub.get("num_experts", held)),
        "k": int(config["num_experts_per_tok"]),
        "n_group": int(config.get("n_group") or 1),
        "topk_group": int(config.get("topk_group") or config.get("n_group")
                          or 1),
    }


def param_shapes(config: Dict) -> Dict[str, Tuple[int, ...]]:
    z = sizes(config)
    e, h, dk, dv = z["e"], z["heads"], z["dk"], z["dv"]
    shapes = {"embed": (z["vocab"], e), "norm_f": (e,),
              "lm_head": (e, z["vocab"])}
    for i, (mixer, dense) in enumerate(z["kinds"]):
        p = f"l{i}."
        shapes.update({p + "norm1": (e,), p + "norm2": (e,)})
        if mixer == KDA:
            shapes.update({
                p + "wq": (e, h * dk), p + "wk": (e, h * dk),
                p + "wv": (e, h * dv), p + "wf": (e, h * dk),
                p + "wb": (e, h), p + "wg": (e, h),
                p + "conv": (z["taps"], 2 * h * dk + h * dv),
                p + "a_log": (h,), p + "dt_bias": (h * dk,),
                p + "norm": (dv,), p + "wo": (h * dv, e)})
        else:
            shapes.update({
                p + "wq": (e, h * (z["nope"] + z["rope"])),
                p + "wkv_a": (e, z["kv_rank"] + z["rope"]),
                p + "kv_norm": (z["kv_rank"],),
                p + "wkv_b": (z["kv_rank"], h * (z["nope"] + z["v"])),
                p + "wg": (e, h), p + "wo": (h * z["v"], e)})
        if dense:
            w = z["dense_width"]
            shapes.update({p + "mlp.gate": (e, w), p + "mlp.up": (e, w),
                           p + "mlp.down": (w, e)})
            continue
        w, n = z["expert_width"], z["held"]
        shapes.update({p + "router": (e, z["routed"]),
                       p + "bias": (z["routed"],),
                       p + "experts.gate": (n, e, w),
                       p + "experts.up": (n, e, w),
                       p + "experts.down": (n, w, e)})
        if z["shared_width"]:
            ws = z["shared_width"]
            shapes.update({p + "shared.gate": (e, ws),
                           p + "shared.up": (e, ws),
                           p + "shared.down": (ws, e)})
    return shapes


def param_count(config: Dict) -> int:
    return sum(int(np.prod(s)) for s in param_shapes(config).values())


@functools.partial(jax.jit, static_argnames=("shape", "kind"))
def _draw(key, scale, *, shape, kind):
    """One weight, bfloat16. ``kind``: ``gain`` (1 + N(0, GAIN_STD)),
    ``matrix`` (N(0, 1) times ``scale``), ``a_log`` (the log of a
    log-uniform in (A_MIN, A_MAX)), ``zero``."""
    if kind == "zero":
        return jnp.zeros(shape, jnp.bfloat16)
    if kind == "a_log":
        return jax.random.uniform(key, shape, jnp.float32, math.log(A_MIN),
                                  math.log(A_MAX)).astype(jnp.bfloat16)
    x = jax.random.normal(key, shape, jnp.float32)
    x = 1.0 + GAIN_STD * x if kind == "gain" else x * scale
    return x.astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("dk", "lower"))
def _draw_dt_bias(key, a_log, *, dk, lower):
    """``dt_bias`` (H d_k,) behind the head's drawn ``A_log`` (as the
    forward reads it, bfloat16): at ``x W_f = 0`` channel ``c`` decays by
    ``g = -1 / tau_c`` a token, ``tau`` log-uniform over (TAU_MIN,
    TAU_MAX): ``dt_bias = logit(g / lower) / exp(A_log)``."""
    tau = jnp.exp(jax.random.uniform(
        key, (a_log.shape[0], dk), jnp.float32, math.log(TAU_MIN),
        math.log(TAU_MAX)))
    share = (-1.0 / tau) / lower                   # sigmoid's value wanted
    logit = jnp.log(share) - jnp.log1p(-share)
    a = jnp.exp(a_log.astype(jnp.float32))[:, None]
    return (logit / a).reshape(-1).astype(jnp.bfloat16)


def init_weights(config: Dict, seed: int, balanced: bool = True
                 ) -> Dict[str, jax.Array]:
    """Every weight, bfloat16, made on the device from the seed: a matrix
    N(0, 1 / fan_in) (its rows the fan-in; a stack of experts' the same a
    matrix), the projections back into the residual stream (``wo``,
    ``*.down``) further over sqrt(2 * layers held), the embedding N(0, 1),
    norm gains 1 + N(0, 0.02) so that a dropped gain shows, the
    convolution's taps N(0, 1 / taps), ``A_log`` and ``dt_bias`` as the
    constants above say (:func:`_draw_dt_bias`). One small jitted draw a
    weight (one compilation a shape, which the layers share): the whole
    model is never a temporary beside itself.

    ``balanced``: every expert layer's selection bias ``b`` is then
    calibrated (:func:`balance`) on ``BALANCE_TOKENS`` tokens of the seed
    until the published experts' loads are within ``BALANCE_TOLERANCE`` of
    even: what the published ``moe_router_enable_expert_bias`` does in the
    trained model, so that every seed gives a held expert the same work.
    Without it the bias is zero (a drawn router's loads follow the seed)."""
    key = fold_seed(seed)
    z = sizes(config)
    resid = 1.0 / math.sqrt(2.0 * len(z["kinds"]))
    out: Dict[str, jax.Array] = {}
    names = sorted(param_shapes(config).items())
    for i, (name, shape) in enumerate(names):
        leaf = name.rsplit(".", 1)[-1]
        k = jax.random.fold_in(key, i)
        if leaf == "dt_bias":           # behind its head's a_log (sorted)
            out[name] = _draw_dt_bias(
                k, out[name[:-len("dt_bias")] + "a_log"], dk=z["dk"],
                lower=z["lower"])
            continue
        if leaf == "a_log":
            kind, scale = "a_log", 1.0
        elif leaf == "bias":
            kind, scale = "zero", 1.0
        elif len(shape) == 1:
            kind, scale = "gain", 1.0
        else:
            kind = "matrix"
            scale = 1.0 if name == "embed" else shape[-2] ** -0.5
            if leaf in ("wo", "down"):
                scale *= resid
        out[name] = _draw(k, jnp.float32(scale), shape=tuple(shape),
                          kind=kind)
    if balanced and any(not dense for _, dense in z["kinds"]):
        out = balance(out, config, jax.random.fold_in(key, len(names)))
    return out


# ---- arithmetic ------------------------------------------------------------

def _round(x, precision: str):
    if precision == "bfloat16":
        # reduce_precision, not astype and back: XLA may drop the pair
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    if precision == "float8":
        top = jnp.max(jnp.abs(x))
        s = jnp.where(top > 0, top / E4M3_MAX, 1.0)
        return jnp.clip(x / s, -E4M3_MAX, E4M3_MAX).astype(
            jnp.float8_e4m3fn).astype(jnp.float32) * s
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


def _rope(x, pos, inv_freq):
    """Interleaved pairs ``(2i, 2i + 1)`` of the last axis turned by ``pos
    * inv_freq[i]``; ``x`` (B, S, [H,] d), ``pos`` (S,)."""
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq)   # (S, d/2)
    if x.ndim == 4:
        ang = ang[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _key(config: Dict) -> Tuple:
    def flat(v):
        return tuple(sorted(v.items())) if isinstance(v, dict) else v

    return tuple(sorted((k, flat(v)) for k, v in config.items()
                        if isinstance(v, (int, float, str, bool))
                        or k == "published"))


def select(choice, n_group: int, topk_group: int, k: int):
    """``choice`` (T, experts) = ``s + b`` -> (ids (T, k) int32, the
    choice with the groups that did not stay at -inf)."""
    if n_group > 1 and topk_group < n_group:
        t = choice.shape[0]
        g = choice.reshape(t, n_group, -1)
        gscore = jax.lax.top_k(g, min(2, g.shape[-1]))[0].sum(-1)
        _, gidx = jax.lax.top_k(gscore, topk_group)
        keep = jnp.zeros((t, n_group), bool).at[
            jnp.arange(t)[:, None], gidx].set(True)
        choice = jnp.where(keep[:, :, None], g, -jnp.inf).reshape(t, -1)
    _, ids = jax.lax.top_k(choice, k)
    return ids.astype(jnp.int32), choice


@functools.lru_cache(maxsize=None)
def _pieces(config_items: Tuple, precision: str, state_dtype: str):
    """The jitted pieces of one configuration, precision and state dtype."""
    config = {k: (dict(v) if isinstance(v, tuple) else v)
              for k, v in config_items}
    z = sizes(config)
    eps = float(config.get("rms_norm_eps", 1e-6))
    h, dk, dv, taps = z["heads"], z["dk"], z["dv"], z["taps"]
    f32 = jnp.float32
    inv_freq = (1.0 / float(config.get("rope_theta", 1e4)) ** (
        np.arange(0, z["rope"], 2, dtype=np.float64) / z["rope"])
                ).astype(np.float32)
    scale = (z["nope"] + z["rope"]) ** -0.5

    @jax.jit
    def project(x, w):
        """(B, S, in) x (in, out) -> (B, S, out): one matrix product."""
        return _mm("bsi,io->bso", x, w, precision)

    @jax.jit
    def normed(x, g):
        return _rms(x, g, eps)

    @jax.jit
    def recurrence(cin, f_logit, b_logit, w):
        """``cin`` (B, S, channels) the convolution's inputs ``[q | k |
        v]``, ``f_logit`` = u W_f (B, S, H d_k), ``b_logit`` = u W_b (B,
        S, H): the convolution, the gates, the recurrence token by token
        and the output's norm; (B, S, H, d_v), and the state the last
        token left, (B, H, d_k, d_v)."""
        b, s, _ = cin.shape
        cw = w["conv"].astype(f32)
        u = sum(cw[j] * jnp.pad(cin, ((0, 0), (taps - 1 - j, 0),
                                      (0, 0)))[:, :s] for j in range(taps))
        u = jax.nn.silu(u)
        q = u[..., :h * dk].reshape(b, s, h, dk)
        k = u[..., h * dk:2 * h * dk].reshape(b, s, h, dk)
        v = u[..., 2 * h * dk:].reshape(b, s, h, dv)
        q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + UNIT_EPS) \
            * dk ** -0.5
        k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + UNIT_EPS)
        beta = jax.nn.sigmoid(b_logit)
        a = jnp.exp(w["a_log"].astype(f32))[:, None]             # (H, 1)
        g = z["lower"] * jax.nn.sigmoid(
            a * (f_logit.reshape(b, s, h, dk)
                 + w["dt_bias"].astype(f32).reshape(h, dk)))
        alpha = jnp.exp(g)                                   # (B, S, H, d_k)

        def token(state, xs):              # state (B, H, d_k, d_v)
            qt, kt, vt, at, bt = xs        # (B, H, .) one position
            state = state * at[..., None]
            r = vt - jnp.sum(state * kt[..., None], axis=-2)
            state = state + kt[..., None] * (bt[..., None] * r)[..., None, :]
            if state_dtype == "bfloat16":
                state = jax.lax.reduce_precision(state, exponent_bits=8,
                                                 mantissa_bits=7)
            return state, jnp.sum(state * qt[..., None], axis=-2)

        t_first = lambda x: jnp.moveaxis(x, 1, 0)  # noqa: E731
        last, o = jax.lax.scan(token, jnp.zeros((b, h, dk, dv), f32),
                               tuple(map(t_first, (q, k, v, alpha, beta))))
        return _rms(jnp.moveaxis(o, 0, 1), w["norm"], eps), last

    @jax.jit
    def head_gated(o, gate_logit):
        """(B, S, H, d) heads' outputs times one sigmoid gate a head."""
        b, s = o.shape[:2]
        return (o * jax.nn.sigmoid(gate_logit)[..., None]).reshape(b, s, -1)

    def kda_mixer(u, w):
        cin = jnp.concatenate([project(u, w[n]) for n in ("wq", "wk", "wv")],
                              axis=-1)
        small = {n: w[n] for n in ("conv", "a_log", "dt_bias", "norm")}
        o, state = recurrence(cin, project(u, w["wf"]), project(u, w["wb"]),
                              small)
        return project(head_gated(o, project(u, w["wg"])), w["wo"]), state

    @jax.jit
    def attend(q, kva, kv_gain, wkv_b):
        b, s, _ = q.shape
        pos = jnp.arange(s)
        q = q.reshape(b, s, h, z["nope"] + z["rope"])
        q_nope, q_rope = q[..., :z["nope"]], _rope(q[..., z["nope"]:], pos,
                                                   inv_freq)
        c = _rms(kva[..., :z["kv_rank"]], kv_gain, eps)
        k_rope = _rope(kva[..., z["kv_rank"]:], pos, inv_freq)
        kv = _mm("bsc,cf->bsf", c, wkv_b, precision).reshape(
            b, s, h, z["nope"] + z["v"])
        k_nope, v = kv[..., :z["nope"]], kv[..., z["nope"]:]
        scores = (_mm("bqhd,bkhd->bhqk", q_nope, k_nope, precision)
                  + _mm("bqhd,bkd->bhqk", q_rope, k_rope, precision)) * scale
        causal = jnp.tril(jnp.ones((s, s), bool))
        probs = jax.nn.softmax(jnp.where(causal[None, None], scores,
                                         -jnp.inf), axis=-1)
        return _mm("bhqk,bkhd->bqhd", probs, v, precision)

    def latent_mixer(u, w):
        o = attend(project(u, w["wq"]), project(u, w["wkv_a"]),
                   w["kv_norm"], w["wkv_b"])
        return project(head_gated(o, project(u, w["wg"])), w["wo"]), None

    @jax.jit
    def add(x, y):
        return x + y

    @jax.jit
    def dense_ffn(u, w):
        return _gated(u, w["mlp.gate"], w["mlp.up"], w["mlp.down"],
                      precision)

    @jax.jit
    def scores_of(u, w):
        """The router's scores ``s`` of every published expert, float32
        at every precision."""
        return jax.nn.sigmoid(jnp.einsum(
            "te,en->tn", u.reshape(-1, z["e"]), w["router"].astype(f32),
            precision=jax.lax.Precision.HIGHEST))

    @jax.jit
    def choose(s, bias):
        return select(s + bias.astype(f32), z["n_group"], z["topk_group"],
                      z["k"])

    @jax.jit
    def expert_ffn(u, w, s, ids):
        """``ids`` (T, k): the experts each token takes; their weights
        come from ``s``, this forward's own scores. The held experts'
        part, plus the shared expert."""
        u2 = u.reshape(-1, z["e"])
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

        def one(acc, ew):                    # one expert upcast at a time
            gate, up, down, ge = ew
            return acc + ge[:, None] * _gated(u2, gate, up, down,
                                              precision), None

        out, _ = jax.lax.scan(
            one, jnp.zeros_like(u2),
            (w["experts.gate"], w["experts.up"], w["experts.down"],
             dense_g.T))
        if z["shared_width"]:
            out = out + _gated(u2, w["shared.gate"], w["shared.up"],
                               w["shared.down"], precision)
        return out.reshape(u.shape)

    def head(x, g, lm_head):
        u = normed(x, g)
        cols = lm_head.shape[1]
        step = -(-cols // HEAD_SLICES)
        return jnp.concatenate([project(u, lm_head[:, c:c + step])
                                for c in range(0, cols, step)], axis=-1)

    @jax.jit
    def embed(table, tokens):
        return table[tokens].astype(f32)

    return {KDA: kda_mixer, LATENT: latent_mixer, "normed": normed,
            "add": add, "dense_ffn": dense_ffn, "scores_of": scores_of,
            "choose": choose, "expert_ffn": expert_ffn, "head": head,
            "embed": embed}


def _layer(weights: Dict, i: int) -> Dict:
    p = f"l{i}."
    return {k[len(p):]: v for k, v in weights.items() if k.startswith(p)}


def forward_with_states(weights: Dict, tokens, config: Dict,
                        precision: str = "float32",
                        routing: Optional[List] = None,
                        state_dtype: str = "float32"):
    """``tokens`` (B, S) int32 -> (logits (B, S, V) float32, info, states)
    where ``info`` lists, per expert layer, ``ids`` (B*S, k), the experts
    this forward used, ``own_ids`` (its own choice) and ``scores`` (B*S,
    published experts): the scores the CHOICE is made by, ``s + b``, which
    is what a routing comparison measures margins in; ``gate_scores`` is
    ``s``; and ``states`` lists, per KDA layer, the state the last token
    left, (B, H, d_k, d_v) float32."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    if state_dtype not in STATE_DTYPES:
        raise ValueError(f"state_dtype {state_dtype!r} not in {STATE_DTYPES}")
    z = sizes(config)
    f = _pieces(_key(config), precision, state_dtype)
    x = f["embed"](weights["embed"], jnp.asarray(tokens))
    info, states = [], []
    for i, (mixer, dense) in enumerate(z["kinds"]):
        w = _layer(weights, i)
        y, state = f[mixer](f["normed"](x, w["norm1"]), w)
        if mixer == KDA:
            states.append(state)
        x = f["add"](x, y)
        u = f["normed"](x, w["norm2"])
        if dense:
            x = f["add"](x, f["dense_ffn"](u, w))
        else:
            s = f["scores_of"](u, w)
            own, _ = f["choose"](s, w["bias"])
            ids = own if routing is None else jnp.asarray(
                routing[len(info)], jnp.int32).reshape(own.shape)
            x = f["add"](x, f["expert_ffn"](u, w, s, ids))
            info.append({"ids": ids, "own_ids": own,
                         "scores": s + w["bias"].astype(jnp.float32),
                         "gate_scores": s})
        # waited for layer by layer: dispatched ahead, the pieces' buffers
        # would all be reserved at once
        x = jax.block_until_ready(x)
    return f["head"](x, weights["norm_f"], weights["lm_head"]), info, states


def forward_with_routing(weights: Dict, tokens, config: Dict,
                         precision: str = "float32",
                         routing: Optional[List] = None,
                         state_dtype: str = "float32"):
    """:func:`forward_with_states`'s logits and ``info``."""
    return forward_with_states(weights, tokens, config, precision, routing,
                               state_dtype)[:2]


def forward_jit(weights, tokens, config, precision="float32", routing=None,
                state_dtype="float32"):
    return forward_with_routing(weights, tokens, config, precision, routing,
                                state_dtype)[0]


# ---- the selection bias ------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n_group", "topk_group", "k"))
def _even_bias(s, *, n_group, topk_group, k):
    """The bias ``b`` under which the selection by ``s + b`` over the
    sample ``s`` (T, experts) loads every expert within
    ``BALANCE_TOLERANCE`` of even: from zero, an expert under the mean load
    raised and one over it lowered by ``BALANCE_RATE`` times its relative
    distance from it, until every load is within the tolerance (or
    ``BALANCE_STEPS``). Returns (b, the largest relative distance left)."""
    t, n = s.shape
    mean = t * k / n

    def loads(b):
        ids, _ = select(s + b, n_group, topk_group, k)
        return jnp.zeros(n, jnp.float32).at[ids.reshape(-1)].add(1.0)

    def off(load):
        return jnp.max(jnp.abs(load / mean - 1.0))

    def cond(c):
        i, _, load = c
        return (i < BALANCE_STEPS) & (off(load) > BALANCE_TOLERANCE)

    def step(c):
        i, b, load = c
        b = b + BALANCE_RATE * (1.0 - load / mean)
        return i + 1, b, loads(b)

    zero = jnp.zeros(n, jnp.float32)
    _, b, load = jax.lax.while_loop(cond, step, (0, zero, loads(zero)))
    return b, off(load)


def balance(weights: Dict, config: Dict, key) -> Dict:
    """``weights`` with every expert layer's selection bias calibrated: a
    sample of ``BALANCE_TOKENS`` token ids drawn from ``key`` (sequences of
    ``BALANCE_SEQ``) goes through this file's own float32 forward, and
    layer by layer the bias is evened on the sample's scores
    (:func:`_even_bias`) before the sample goes on under it. A DRAWN
    router's loads follow the seed (a column of it that lies along the
    stream's common part makes its expert everybody's), which no
    deployment's do, and the work a step's held experts do then follows
    the seed; in the published model this bias is what training moves
    until the loads are even (``moe_router_enable_expert_bias``,
    ``noaux_tc``)."""
    z = sizes(config)
    f = _pieces(_key(config), "float32", "float32")
    seq = min(BALANCE_SEQ, BALANCE_TOKENS)
    tokens = jax.random.randint(key, (BALANCE_TOKENS // seq, seq), 0,
                                z["vocab"], jnp.int32)
    out = dict(weights)
    x = f["embed"](out["embed"], tokens)
    for i, (mixer, dense) in enumerate(z["kinds"]):
        w = _layer(out, i)
        x = f["add"](x, f[mixer](f["normed"](x, w["norm1"]), w)[0])
        u = f["normed"](x, w["norm2"])
        if dense:
            x = f["add"](x, f["dense_ffn"](u, w))
            continue
        s = f["scores_of"](u, w)
        bias, left = _even_bias(s, n_group=z["n_group"],
                                topk_group=z["topk_group"], k=z["k"])
        out[f"l{i}.bias"] = bias = bias.astype(jnp.bfloat16)
        ids, _ = f["choose"](s, bias)
        x = jax.block_until_ready(f["add"](x, f["expert_ffn"](u, w, s, ids)))
    return out
