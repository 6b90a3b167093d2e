"""Plain GLM-5.3-Flash (``zai-org/GLM-5.3-Flash`` on the Hugging Face hub,
``model_type`` ``glm5_next_text``) in ``jax.numpy``: the yardstick the
benchmark compares the program with. Nothing here imports ``flexflow_tpu``
and nothing here is fast: no cache, no kernels, no chunks; a KDA layer's
recurrence runs token by token (``lax.scan`` over ``t``), the sparse
layer's indexer scores every pool for every query and its attention is the
full (queries, keys) matrix under the selection's mask, every token goes
through every held expert and is weighted by its gate.

The equations (``n = hc_mult``, ``d = hidden_size``; every norm an RMSNorm
with a gain and ``rms_norm_eps`` unless said otherwise; the program's layer
``i`` is published layer ``first_layer + i``).

* **Residual**: a token's residual is ``X`` in ``R^{n x d}``. Start: the
  embedding copied into the n streams. End: the streams summed, a final
  norm, an untied head.
* **Stream mix**, one of its own for the mixer and one for the
  feed-forward of every layer (``W_hc`` (n d, 2n + n^2), scales ``a_pre,
  a_post, a_res``, bias ``b`` (2n + n^2))::

      r = rsqrt(mean(vec(X)^2) + rms_norm_eps);  m = (vec(X) W_hc) r
      pre  = sigmoid(a_pre m[0:n] + b[0:n]) + hc_eps
      post = 2 sigmoid(a_post m[n:2n] + b[n:2n])
      C0   = softmax_rows(reshape(a_res m[2n:] + b[2n:], (n, n))) + hc_eps
      C    = hc_sinkhorn_iters rounds of: each column over its sum +
             hc_eps, then each row over its sum + hc_eps
      u = sum_i pre_i X_i;  y = F(norm(u));  X'_j = post_j y + sum_i C[j, i] X_i

* **KDA mixer** (``layer_types`` ``linear_attention``; ``H`` heads of ``d_k
  = d_v = linear_attn_config.head_dim``): ``q, k, v = silu(conv(x W_q)),
  silu(conv(x W_k)), silu(conv(x W_v))``, the convolution causal,
  depthwise, ``short_conv_kernel_size`` taps, zeros before the sequence;
  q and k of unit length a head (``a / sqrt(sum a^2 + 1e-6)``), q scaled
  by ``d_k^-1/2``; ``f = (x W_fa) W_fb`` through rank ``head_dim``; ``g =
  gate_lower_bound * sigmoid(exp(A_log_h) (f + dt_bias))``, ``alpha =
  exp(g)``; ``beta = sigmoid(x W_b)``::

      S' = diag(alpha_t) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
      o_t = S_t^T q_t

  out ``concat_h(RMSNorm_{d_v}(o_h) * sigmoid((x W_ga) W_gb)_h) W_o``: a
  gate a CHANNEL through rank ``head_dim``, one gain of width ``d_v``.
* **Sparse latent mixer** (``deepseek_sparse_attention``): ``c_q =
  norm(x W_qa)``; ``q = c_q W_qb``, heads of ``qk_nope_head_dim``, no
  rotary part; ``c_kv = norm(x W_kva)``; ``[k | v] = c_kv W_kvb`` a head;
  softmax scale ``qk_nope_head_dim^-1/2``; out through ``W_o``.
  Indexer: ``qI = c_q W_qI`` (``index_n_heads`` x ``index_head_dim``);
  ``kI = layer_norm(x W_kI)`` (one head; gain, bias, eps 1e-6); ``w = (x
  W_w) index_n_heads^-1/2 index_head_dim^-1/2``; rotary over the first
  ``index_rope_dim`` dims of ``qI`` and ``kI``, interleaved pairs, base
  ``index_rope_theta``. Pool ``p`` holds positions ``kpool p .. kpool p +
  kpool - 1``; a COMPLETE pool's key is the MEAN of its keys (after norm
  and rotary). The query at ``t``, ``P_t = floor(t / kpool)``: pool
  ``P_t`` is always taken, its rows masked to positions ``<= t``; of the
  pools ``p < P_t``, scored ``I_{t,p} = sum_j w_{t,j} relu(qI_{t,j} .
  K_p)``, the ``index_topk / index_kpool - 1`` highest are taken (all of
  them while there are no more; ties to the lower pool). The softmax runs
  over the rows of the pools taken and nothing else.
* **Feed-forward**: ``mlp_layer_types`` ``dense``: a gated MLP of
  ``intermediate_size``. Else ``s = sigmoid(x W_r)`` over the published
  experts in float32; choice by ``s + b``, the ``num_experts_per_tok``
  highest; weights ``s`` of the chosen, normalised to sum 1, times
  ``routed_scaling_factor``; plus one shared gated MLP. Every gated MLP
  clamps before the product: ``gate = min(gate, swiglu_limit)``, ``up =
  clip(up, -swiglu_limit, swiglu_limit)``, ``silu(gate) * up``.

**The share.** ``n_routed_experts`` experts are HELD, ``expert_first ..``,
of ``published.n_routed_experts`` routed over; a holder computes the part
its held experts give and the shared expert; what absent experts would add
is left out. The holders' routed parts, and the shared expert counted
once, add up to the uncut layer.

Weights are **bfloat16** (the deployment's are), made on the device from
the seed (:func:`init_weights`); the forward upcasts them a projection, a
group of heads and an expert at a time, a Python loop over layers of
small jitted pieces and the sparse layer's queries a tile at a time, so
that at the published widths and 9,000 tokens it runs beside a program
that holds the same arrays and its pool.

``precision``: how every matrix product but the router's is computed:
``float32`` (``highest``; the reference), ``bfloat16`` (operands rounded),
``float8`` (operands read as scaled e4m3: the control). ``state_dtype``:
what a KDA state is rounded to after every token. ``routing=`` and
``selection=`` make the forward use the experts and the pools a program
took, while it still chooses its own on the same input and says where the
two differ.
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
KDA, SPARSE = "linear_attention", "deepseek_sparse_attention"
GAIN_STD = 0.02    # norm gains 1 + N(0, GAIN_STD): a dropped gain shows
UNIT_EPS = 1e-6    # under the root of a head's L2 norm of q and of k
INDEX_NORM_EPS = 1e-6
E4M3_MAX = 448.0
# the KDA gates' draw (as ``reference/ling.py``): exp(A_log) log-uniform
# over (A_MIN, A_MAX) a head; a channel's dt_bias such that at f = 0 it
# keeps exp(-1 / tau) a token, tau log-uniform over (TAU_MIN, TAU_MAX)
A_MIN, A_MAX = 0.5, 2.0
TAU_MIN, TAU_MAX = 4.0, 1024.0
# the stream mix's draw: at m = 0 ``pre`` reads one stream at sigmoid(2) =
# 0.88 and the others at 0.12, ``post`` 1, ``C0``'s rows softmax(RES_DIAG
# on the diagonal + N(0, RES_SPREAD)): near the identity plus a spread that
# leaves the columns uneven, so that the rounds have work; ``W_hc`` N(0, 1 /
# (n d)) makes m N(0, 1) a coefficient, which the scales carry into the
# sigmoids' and the softmax's steep part: the dynamic part moves a
# coefficient of ``C0`` by 0.18 at the median. Over 40,000 drawn tokens the
# columns of ``C`` then sum to 1 within 3e-4 for 99 in 100 after 20 rounds
# (the worst 6e-3) and within 1.5e-2 at the median after 3: 20 rounds are
# not 3. (A dynamic part of N(0, 1) under the residual's softmax leaves one
# token in five outside 1e-3 after 20 rounds: too wide for them.)
PRE_LOGIT = 2.0
MIX_SCALES = (1.0, 0.5, 0.5)
RES_DIAG, RES_SPREAD = 2.0, 0.5
INDEX_BIAS_STD = 0.5   # the index key's bias: a dropped bias shows
# the selection bias's calibration (:func:`balance`)
BALANCE_TOKENS = 4096
BALANCE_SEQ = 512
BALANCE_TOLERANCE = 0.10
BALANCE_STEPS = 400
BALANCE_RATE = 0.01
HEAD_SLICES = 4    # the head's product, in this many slices of the vocabulary
QUERIES = 512      # queries a tile of the sparse layer's scores
HEAD_GROUP = 8     # heads a piece of the sparse layer's attention
SEGMENT = 1024     # tokens a piece of a KDA mixer and of the dense MLP


def fold_seed(seed: int) -> jax.Array:
    """A PRNG key (``rbg``) from any non-negative whole number: the low 31
    bits seed it, the rest is folded in."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed {seed} < 0")
    key = jax.random.key(seed & 0x7FFFFFFF, impl="rbg")
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def layer_kinds(config: Dict) -> List[Tuple[str, bool]]:
    """Per held layer, in order: (its mixer, whether its feed-forward is
    the dense MLP), as the file's ``layer_types`` and ``mlp_layer_types``
    (cut to the held layers) say."""
    types, mlps = config["layer_types"], config["mlp_layer_types"]
    n = int(config["num_hidden_layers"])
    if len(types) != n or len(mlps) != n:
        raise ValueError(f"{len(types)} layer_types and {len(mlps)} "
                         f"mlp_layer_types for {n} layers")
    return [(t, m == "dense") for t, m in zip(types, mlps)]


def sizes(config: Dict) -> Dict:
    """The shapes a configuration states, the share included."""
    pub = config.get("published") or {}
    lin = config["linear_attn_config"]
    held = int(config["n_routed_experts"])
    idim = int(config["index_head_dim"])
    return {
        "vocab": int(config["vocab_size"]), "e": int(config["hidden_size"]),
        "kinds": layer_kinds(config),
        "n": int(config["hc_mult"]), "iters": int(config["hc_sinkhorn_iters"]),
        "hc_eps": float(config["hc_eps"]),
        "kh": int(lin["num_heads"]), "dk": int(lin["head_dim"]),
        "dv": int(lin["head_dim"]), "rank": int(lin["head_dim"]),
        "taps": int(lin["short_conv_kernel_size"]),
        "lower": float(lin["gate_lower_bound"]),
        "heads": int(config["num_attention_heads"]),
        "q_rank": int(config["q_lora_rank"]),
        "kv_rank": int(config["kv_lora_rank"]),
        "nope": int(config["qk_nope_head_dim"]),
        "v": int(config["v_head_dim"]),
        "ih": int(config["index_n_heads"]), "idim": idim,
        "irope": int(config.get("index_rope_dim", min(64, idim))),
        "itheta": float(config.get("index_rope_theta", 10000.0)),
        "pool": int(config["index_kpool"]), "topk": int(config["index_topk"]),
        "dense_width": int(config["intermediate_size"]),
        "expert_width": int(config["moe_intermediate_size"]),
        "shared_width": (int(config.get("n_shared_experts", 0))
                         * int(config["moe_intermediate_size"])),
        "held": held, "first": int(config.get("expert_first", 0)),
        "routed": int(pub.get("n_routed_experts", held)),
        "k": int(config["num_experts_per_tok"]),
        "limit": (None if config.get("swiglu_limit") is None
                  else float(config["swiglu_limit"])),
    }


def picks_of(z: Dict) -> int:
    """Pools a query takes by their scores, beside its own."""
    return z["topk"] // z["pool"] - 1


def param_shapes(config: Dict) -> Dict[str, Tuple[int, ...]]:
    z = sizes(config)
    e, n = z["e"], z["n"]
    kh, dk, dv, r = z["kh"], z["dk"], z["dv"], z["rank"]
    h = z["heads"]
    shapes = {"embed": (z["vocab"], e), "norm_f": (e,),
              "lm_head": (e, z["vocab"])}
    for i, (mixer, dense) in enumerate(z["kinds"]):
        p = f"l{i}."
        shapes.update({p + "norm1": (e,), p + "norm2": (e,)})
        for part in ("mix1.", "mix2."):
            shapes.update({p + part + "w": (n * e, 2 * n + n * n),
                           p + part + "scale": (3,),
                           p + part + "bias": (2 * n + n * n,)})
        if mixer == KDA:
            shapes.update({
                p + "wq": (e, kh * dk), p + "wk": (e, kh * dk),
                p + "wv": (e, kh * dv),
                p + "wf_a": (e, r), p + "wf_b": (r, kh * dk),
                p + "wb": (e, kh),
                p + "wg_a": (e, r), p + "wg_b": (r, kh * dv),
                p + "conv": (z["taps"], 2 * kh * dk + kh * dv),
                p + "a_log": (kh,), p + "dt_bias": (kh * dk,),
                p + "norm": (dv,), p + "wo": (kh * dv, e)})
        else:
            shapes.update({
                p + "wq_a": (e, z["q_rank"]), p + "q_norm": (z["q_rank"],),
                p + "wq_b": (z["q_rank"], h * z["nope"]),
                p + "wkv_a": (e, z["kv_rank"]),
                p + "kv_norm": (z["kv_rank"],),
                p + "wkv_b": (z["kv_rank"], h * (z["nope"] + z["v"])),
                p + "wo": (h * z["v"], e),
                p + "wq_i": (z["q_rank"], z["ih"] * z["idim"]),
                p + "wk_i": (e, z["idim"]), p + "k_norm_i": (z["idim"],),
                p + "k_bias_i": (z["idim"],), p + "ww_i": (e, z["ih"])})
        if dense:
            w = z["dense_width"]
            shapes.update({p + "mlp.gate": (e, w), p + "mlp.up": (e, w),
                           p + "mlp.down": (w, e)})
            continue
        w, held = z["expert_width"], z["held"]
        shapes.update({p + "router": (e, z["routed"]),
                       p + "bias": (z["routed"],),
                       p + "experts.gate": (held, e, w),
                       p + "experts.up": (held, e, w),
                       p + "experts.down": (held, w, e)})
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
    """``dt_bias`` (H d_k,) behind the head's drawn ``A_log``: at ``f = 0``
    channel ``c`` decays by ``g = -1 / tau_c`` a token, ``tau`` log-uniform
    over (TAU_MIN, TAU_MAX): ``dt_bias = logit(g / lower) / exp(A_log)``."""
    tau = jnp.exp(jax.random.uniform(
        key, (a_log.shape[0], dk), jnp.float32, math.log(TAU_MIN),
        math.log(TAU_MAX)))
    share = (-1.0 / tau) / lower                   # sigmoid's value wanted
    logit = jnp.log(share) - jnp.log1p(-share)
    a = jnp.exp(a_log.astype(jnp.float32))[:, None]
    return (logit / a).reshape(-1).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("n", "stream"))
def _draw_mix_bias(key, *, n, stream):
    """A stream mix's ``b`` (2n + n^2,): ``pre`` reads stream ``stream`` at
    ``sigmoid(PRE_LOGIT)`` and the others at ``sigmoid(-PRE_LOGIT)``,
    ``post`` 1, ``C0`` ``RES_DIAG`` on the diagonal plus N(0,
    RES_SPREAD)."""
    pre = jnp.where(jnp.arange(n) == stream, PRE_LOGIT, -PRE_LOGIT)
    res = (RES_DIAG * jnp.eye(n)
           + RES_SPREAD * jax.random.normal(key, (n, n), jnp.float32))
    return jnp.concatenate([pre, jnp.zeros(n), res.reshape(-1)]).astype(
        jnp.bfloat16)


def init_weights(config: Dict, seed: int, balanced: bool = True
                 ) -> Dict[str, jax.Array]:
    """Every weight, bfloat16, made on the device from the seed: a matrix
    N(0, 1 / fan_in) (its rows the fan-in; a stack of experts' the same a
    matrix; ``W_hc`` among them), the projections back into the residual
    (``wo``, ``*.down``) further over sqrt(2 * layers held), the embedding
    N(0, 1), norm gains 1 + N(0, 0.02), the convolution's taps N(0, 1 /
    taps), ``A_log`` and ``dt_bias`` as the constants above say, a stream
    mix's scales ``MIX_SCALES`` and its bias :func:`_draw_mix_bias` (mix
    ``j`` of the model reads stream ``j mod n`` first), the index key's
    bias N(0, INDEX_BIAS_STD). One small jitted draw a weight: the whole
    model is never a temporary beside itself.

    ``balanced``: every expert layer's selection bias ``b`` is then
    calibrated (:func:`balance`) until the published experts' loads are
    within ``BALANCE_TOLERANCE`` of even: what ``noaux_tc`` training does
    in the published model, so that every seed gives a held expert the
    same work. Without it the bias is zero."""
    key = fold_seed(seed)
    z = sizes(config)
    resid = 1.0 / math.sqrt(2.0 * len(z["kinds"]))
    out: Dict[str, jax.Array] = {}
    names = sorted(param_shapes(config).items())
    mixes = 0
    for i, (name, shape) in enumerate(names):
        leaf = name.rsplit(".", 1)[-1]
        k = jax.random.fold_in(key, i)
        if ".mix" in name and leaf != "w":
            if leaf == "scale":
                out[name] = jnp.asarray(MIX_SCALES, jnp.bfloat16)
            else:
                out[name] = _draw_mix_bias(k, n=z["n"], stream=mixes % z["n"])
                mixes += 1
            continue
        if leaf == "dt_bias":           # behind its head's a_log (sorted)
            out[name] = _draw_dt_bias(
                k, out[name[:-len("dt_bias")] + "a_log"], dk=z["dk"],
                lower=z["lower"])
            continue
        if leaf == "a_log":
            kind, scale = "a_log", 1.0
        elif leaf == "bias":            # the router's: calibrated below
            kind, scale = "zero", 1.0
        elif leaf == "k_bias_i":
            kind, scale = "matrix", INDEX_BIAS_STD
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


def _rope(x, pos, inv_freq, width: int):
    """Interleaved pairs ``(2i, 2i + 1)`` of the first ``width`` of the
    last axis turned by ``pos * inv_freq[i]``; ``x`` (B, S, [H,] d)."""
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq)
    if x.ndim == 4:
        ang = ang[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    part = x[..., :width]
    pairs = part.reshape(part.shape[:-1] + (width // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    turned = jnp.stack([a * cos - b * sin, b * cos + a * sin],
                       axis=-1).reshape(part.shape)
    return jnp.concatenate([turned, x[..., width:]], axis=-1)


def _key(config: Dict) -> Tuple:
    def flat(v):
        if isinstance(v, dict):
            return tuple(sorted((k, flat(x)) for k, x in v.items()))
        return tuple(v) if isinstance(v, list) else v

    return tuple(sorted(
        (k, flat(v)) for k, v in config.items()
        if isinstance(v, (int, float, str, bool))
        or k in ("published", "linear_attn_config", "layer_types",
                 "mlp_layer_types")))


def select(choice, k: int):
    """``choice`` (T, experts) = ``s + b`` -> ids (T, k) int32 (``n_group``
    1: no groups)."""
    return jax.lax.top_k(choice, k)[1].astype(jnp.int32)


def sinkhorn(c, iters: int, eps: float):
    for _ in range(iters):
        c = c / (c.sum(-2, keepdims=True) + eps)
        c = c / (c.sum(-1, keepdims=True) + eps)
    return c


@functools.lru_cache(maxsize=None)
def _pieces(config_items: Tuple, precision: str, state_dtype: str,
            iters: int, picks: int):
    """The jitted pieces of one configuration, precision, state dtype,
    count of Sinkhorn rounds and count of pools taken."""
    def thaw(v):
        if isinstance(v, tuple) and v and isinstance(v[0], tuple):
            return {k: thaw(x) for k, x in v}
        return list(v) if isinstance(v, tuple) else v

    config = {k: thaw(v) for k, v in config_items}
    z = sizes(config)
    eps = float(config.get("rms_norm_eps", 1e-6))
    n, hc_eps = z["n"], z["hc_eps"]
    kh, dk, dv, taps = z["kh"], z["dk"], z["dv"], z["taps"]
    h, nope, vd = z["heads"], z["nope"], z["v"]
    ih, idim, irope, pool = z["ih"], z["idim"], z["irope"], z["pool"]
    limit = z["limit"]
    f32 = jnp.float32
    inv_freq = (1.0 / z["itheta"] ** (
        np.arange(0, irope, 2, dtype=np.float64) / irope)).astype(np.float32)
    scale = nope ** -0.5

    def _gated(u, gate, up, down):
        a = _mm("...e,ef->...f", u, gate, precision)
        b = _mm("...e,ef->...f", u, up, precision)
        if limit is not None:
            a, b = jnp.minimum(a, limit), jnp.clip(b, -limit, limit)
        return _mm("...f,fe->...e", jax.nn.silu(a) * b, down, precision)

    @jax.jit
    def project(x, w):
        """(B, S, in) x (in, out) -> (B, S, out): one matrix product."""
        return _mm("bsi,io->bso", x, w, precision)

    @jax.jit
    def normed(x, g):
        return _rms(x, g, eps)

    @jax.jit
    def mix_pre(x, w):
        """``x`` (B, S, n, d) -> (u (B, S, d), post (B, S, n), C (B, S, n,
        n))."""
        b, s = x.shape[:2]
        flat = x.reshape(b, s, -1)
        r = jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True) + eps)
        m = _mm("bsi,io->bso", flat, w["w"], precision) * r
        a = w["scale"].astype(f32)
        bias = w["bias"].astype(f32)
        pre = jax.nn.sigmoid(a[0] * m[..., :n] + bias[:n]) + hc_eps
        post = 2.0 * jax.nn.sigmoid(a[1] * m[..., n:2 * n] + bias[n:2 * n])
        c0 = jax.nn.softmax((a[2] * m[..., 2 * n:] + bias[2 * n:]).reshape(
            b, s, n, n), axis=-1) + hc_eps
        return (jnp.einsum("bsi,bsid->bsd", pre, x), post,
                sinkhorn(c0, iters, hc_eps))

    @functools.partial(jax.jit, donate_argnums=(0,))
    def mix_post(x, y, post, c):
        """(the streams before are given up: nothing reads them again)"""
        return (jnp.einsum("bsji,bsid->bsjd", c, x)
                + post[..., None] * y[:, :, None, :])

    @jax.jit
    def recurrence(cin, f, b_logit, w, state, before):
        """A segment of a sequence behind ``state`` (B, H, d_k, d_v) and
        ``before`` (B, taps - 1, channels), the convolution's inputs just
        before it (zeros at the sequence's start): ``cin`` (B, S, channels)
        the convolution's inputs ``[q | k | v]``, ``f`` (B, S, H d_k),
        ``b_logit`` (B, S, H): the convolution, the gates, the recurrence
        token by token and the output's norm; (B, S, H, d_v), the state
        the last token left, and its ``before``."""
        b, s, _ = cin.shape
        cw = w["conv"].astype(f32)
        window = jnp.concatenate([before, cin], axis=1)
        u = sum(cw[j] * window[:, j:j + s] for j in range(taps))
        u = jax.nn.silu(u)
        q = u[..., :kh * dk].reshape(b, s, kh, dk)
        k = u[..., kh * dk:2 * kh * dk].reshape(b, s, kh, dk)
        v = u[..., 2 * kh * dk:].reshape(b, s, kh, dv)
        q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + UNIT_EPS) \
            * dk ** -0.5
        k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + UNIT_EPS)
        beta = jax.nn.sigmoid(b_logit)
        a = jnp.exp(w["a_log"].astype(f32))[:, None]             # (H, 1)
        g = z["lower"] * jax.nn.sigmoid(
            a * (f.reshape(b, s, kh, dk)
                 + w["dt_bias"].astype(f32).reshape(kh, dk)))
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
        last, o = jax.lax.scan(token, state,
                               tuple(map(t_first, (q, k, v, alpha, beta))))
        return (_rms(jnp.moveaxis(o, 0, 1), w["norm"], eps), last,
                window[:, s:])

    @jax.jit
    def channel_gated(o, gate_logit):
        """(B, S, H, d) heads' outputs times a sigmoid gate a channel."""
        b, s = o.shape[:2]
        return o.reshape(b, s, -1) * jax.nn.sigmoid(gate_logit)

    def kda_mixer(u, w, picked):
        """(a segment of the sequence at a time: the convolution's inputs
        of 9,000 tokens at 64 heads are 0.9 GB in float32)"""
        b = u.shape[0]
        small = {m: w[m] for m in ("conv", "a_log", "dt_bias", "norm")}
        state = jnp.zeros((b, kh, dk, dv), f32)
        before = jnp.zeros((b, taps - 1, 2 * kh * dk + kh * dv), f32)
        out = []
        for at in range(0, u.shape[1], SEGMENT):
            seg = u[:, at:at + SEGMENT]
            cin = jnp.concatenate([project(seg, w[m])
                                   for m in ("wq", "wk", "wv")], axis=-1)
            o, state, before = recurrence(
                cin, project(project(seg, w["wf_a"]), w["wf_b"]),
                project(seg, w["wb"]), small, state, before)
            gate = project(project(seg, w["wg_a"]), w["wg_b"])
            out.append(project(channel_gated(o, gate), w["wo"]))
        return jnp.concatenate(out, axis=1), state

    @jax.jit
    def index_side(u, cq, w):
        """``qI`` (B, S, ih, idim) and ``kI`` (B, S, idim), both rotated,
        the pools' keys (B, S // pool, idim) and ``w`` (B, S, ih)."""
        b, s, _ = u.shape
        pos = jnp.arange(s)
        qi = _rope(_mm("bsr,ro->bso", cq, w["wq_i"], precision).reshape(
            b, s, ih, idim), pos, inv_freq, irope)
        ki = _mm("bse,eo->bso", u, w["wk_i"], precision)
        ki = ki - ki.mean(-1, keepdims=True)
        ki = (ki * jax.lax.rsqrt(jnp.mean(ki * ki, -1, keepdims=True)
                                 + INDEX_NORM_EPS)
              * w["k_norm_i"].astype(f32) + w["k_bias_i"].astype(f32))
        ki = _rope(ki, pos, inv_freq, irope)
        whole = s // pool
        pooled = ki[:, :whole * pool].reshape(b, whole, pool, idim).mean(2)
        wi = _mm("bse,eo->bso", u, w["ww_i"], precision) \
            * (ih * idim) ** -0.5
        return qi, pooled, wi

    @functools.partial(jax.jit, static_argnames=("first", "forced"))
    def choose_pools(qi, wi, pooled, given, *, first, forced):
        """The tile of queries at positions ``first ..``: ``qi`` (B, Q, ih,
        idim), ``wi`` (B, Q, ih), ``given`` (B, Q, P) bool the pools a
        program took by their scores where ``forced``. Returns (the pools
        the attention reads (B, Q, P) bool, the reference's own picks the
        same way, the (query, pick) pairs of ``given`` that are not the
        reference's (B, Q), the shortfall (B, Q))."""
        b, q = qi.shape[:2]
        p = pooled.shape[1]
        count = min(picks, p)
        pos = first + jnp.arange(q)
        dots = _mm("bqhd,bpd->bqhp", qi, pooled, precision)
        sc = jnp.einsum("bqhp,bqh->bqp", jnp.maximum(dots, 0.0), wi)
        before = jnp.arange(p)[None, :] < (pos // pool)[:, None]     # (Q, P)
        sc = jnp.where(before, sc, -jnp.inf)
        vals, ids = jax.lax.top_k(sc, count)
        own = jnp.zeros((b, q, p + 1), bool).at[
            jnp.arange(b)[:, None, None], jnp.arange(q)[None, :, None],
            jnp.where(vals > -jnp.inf, ids, p)].set(True)[..., :p]
        if not forced:
            zero = jnp.zeros((b, q), f32)
            return own, own, zero.astype(jnp.int32), zero
        # past the dense regime alone: before it every pool is taken
        sparse = (pos // pool > picks)[None, :]
        differ = jnp.where(sparse, (given & ~own).sum(-1), 0)
        inf = jnp.inf
        low_own = jnp.where(own, sc, inf).min(-1)
        top_own = jnp.where(own, sc, -inf).max(-1)
        low_got = jnp.where(given, sc, inf).min(-1)
        wrong = given.sum(-1) != own.sum(-1)         # too few, or too many
        short = jnp.where(
            wrong, inf,
            jnp.maximum(low_own - low_got, 0.0)
            / jnp.maximum(top_own - low_own, 1e-30))
        return (given, own, differ,
                jnp.where(sparse & ((differ > 0) | wrong), short, 0.0))

    @functools.partial(jax.jit, static_argnames=("first",))
    def attend_piece(q, k, v, mask, *, first):
        """``q`` (B, Q, G, nope) at positions ``first ..`` over ``k``, ``v``
        (B, S, G, .) where ``mask`` (B, Q, P) says which pools a query
        reads (its own always, nothing after itself)."""
        nq, s = q.shape[1], k.shape[1]
        pos = first + jnp.arange(nq)
        kpos = jnp.arange(s)
        kp = kpos // pool
        taken = jnp.take_along_axis(
            jnp.pad(mask, ((0, 0), (0, 0), (0, 1))),
            jnp.broadcast_to(jnp.minimum(kp, mask.shape[-1])[None, None, :],
                             mask.shape[:2] + (s,)), axis=-1)
        see = ((taken | (kp[None, :] == (pos // pool)[:, None])[None])
               & (kpos[None, :] <= pos[:, None])[None])
        att = _mm("bqhd,bkhd->bhqk", q, k, precision) * scale
        pr = jax.nn.softmax(jnp.where(see[:, None], att, -jnp.inf), axis=-1)
        return _mm("bhqk,bkhd->bqhd", pr, v, precision)

    def sparse_mixer(u, w, picked):
        b, s, _ = u.shape
        cq = normed(project(u, w["wq_a"]), w["q_norm"])
        c = normed(project(u, w["wkv_a"]), w["kv_norm"])
        qi, pooled, wi = index_side(u, cq, w)
        p = pooled.shape[1]
        masks, own, differ, short = [], [], [], []
        for at in range(0, s, QUERIES):
            nq = min(QUERIES, s - at)
            given = (jnp.zeros((b, nq, p), bool) if picked is None
                     else jnp.asarray(picked, bool)[:, at:at + nq, :p])
            m, o, df, sh = choose_pools(
                qi[:, at:at + nq], wi[:, at:at + nq], pooled, given,
                first=at, forced=picked is not None)
            masks.append(m), own.append(o), differ.append(df)
            short.append(sh)
        wq_b = w["wq_b"].reshape(-1, h, nope)
        wkv_b = w["wkv_b"].reshape(-1, h, nope + vd)
        wo = w["wo"].reshape(h, vd, -1)
        y = 0.0
        for g in range(0, h, HEAD_GROUP):      # a group's part of W_o's sum
            q = _piece_heads(cq, wq_b[:, g:g + HEAD_GROUP])
            kv = _piece_heads(c, wkv_b[:, g:g + HEAD_GROUP])
            k, v = kv[..., :nope], kv[..., nope:]
            o = jnp.concatenate([
                attend_piece(q[:, at:at + QUERIES], k, v, m, first=at)
                for at, m in zip(range(0, s, QUERIES), masks)], axis=1)
            y = y + project(o.reshape(b, s, -1),
                            wo[g:g + HEAD_GROUP].reshape(-1, wo.shape[-1]))
        info = {"own": jnp.concatenate(own, axis=1),
                "differ": jnp.concatenate(differ, axis=1),
                "shortfall": jnp.concatenate(short, axis=1)}
        return y, info

    @jax.jit
    def _piece_heads(x, w):
        """(B, S, in) x (in, G, out) -> (B, S, G, out)."""
        return _mm("bsi,igo->bsgo", x, w, precision)

    @jax.jit
    def _dense_piece(u, w):
        return _gated(u, w["mlp.gate"], w["mlp.up"], w["mlp.down"])

    def dense_ffn(u, w):
        return jnp.concatenate([_dense_piece(u[:, at:at + SEGMENT], w)
                                for at in range(0, u.shape[1], SEGMENT)],
                               axis=1)

    @jax.jit
    def scores_of(u, w):
        """The router's scores ``s`` of every published expert, float32
        at every precision."""
        return jax.nn.sigmoid(jnp.einsum(
            "te,en->tn", u.reshape(-1, z["e"]), w["router"].astype(f32),
            precision=jax.lax.Precision.HIGHEST))

    @jax.jit
    def choose(s, bias):
        return select(s + bias.astype(f32), z["k"])

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
        local = ids - z["first"]
        dense_g = jnp.zeros((u2.shape[0], z["held"]), f32)
        for j in range(z["k"]):
            ok = (local[:, j] >= 0) & (local[:, j] < z["held"])
            dense_g = dense_g.at[jnp.arange(u2.shape[0]),
                                 jnp.clip(local[:, j], 0, z["held"] - 1)].add(
                jnp.where(ok, g[:, j], 0.0))

        def one(acc, ew):                    # one expert upcast at a time
            gate, up, down, ge = ew
            return acc + ge[:, None] * _gated(u2, gate, up, down), None

        out, _ = jax.lax.scan(
            one, jnp.zeros_like(u2),
            (w["experts.gate"], w["experts.up"], w["experts.down"],
             dense_g.T))
        if z["shared_width"]:
            out = out + _gated(u2, w["shared.gate"], w["shared.up"],
                               w["shared.down"])
        return out.reshape(u.shape)

    def head(x, g, lm_head, rows):
        """The streams' sum, normed, through the head: the last ``rows``
        positions (None: all)."""
        u = spread_sum(x)
        if rows is not None:
            u = u[:, u.shape[1] - rows:]
        u = normed(u, g)
        cols = lm_head.shape[1]
        step = -(-cols // HEAD_SLICES)
        return jnp.concatenate([project(u, lm_head[:, c:c + step])
                                for c in range(0, cols, step)], axis=-1)

    @jax.jit
    def spread_sum(x):
        return x.sum(2)

    @jax.jit
    def embed(table, tokens):
        x = table[tokens].astype(f32)
        return jnp.broadcast_to(x[:, :, None, :],
                                x.shape[:2] + (n, x.shape[-1]))

    return {KDA: kda_mixer, SPARSE: sparse_mixer, "normed": normed,
            "mix_pre": mix_pre, "mix_post": mix_post,
            "dense_ffn": dense_ffn, "scores_of": scores_of,
            "choose": choose, "expert_ffn": expert_ffn, "head": head,
            "embed": embed}


def _layer(weights: Dict, i: int) -> Dict:
    p = f"l{i}."
    return {k[len(p):]: v for k, v in weights.items() if k.startswith(p)}


def _mix(w: Dict, part: str) -> Dict:
    return {k: w[f"{part}.{k}"] for k in ("w", "scale", "bias")}


def forward(weights: Dict, tokens, config: Dict, precision: str = "float32",
            *, routing: Optional[List] = None,
            selection: Optional[List] = None, state_dtype: str = "float32",
            rows: Optional[int] = None, sinkhorn_iters: Optional[int] = None,
            picks: Optional[int] = None, balancing: bool = False) -> Dict:
    """``tokens`` (B, S) int32 -> ``logits`` (B, rows or S, V) float32;
    ``experts``: per expert layer ``ids`` (B*S, k) the experts this forward
    used, ``own_ids`` its own choice, ``scores`` (B*S, published experts)
    the scores the CHOICE is made by, ``s + b``, ``gate_scores`` ``s``;
    ``sparse``: per sparse layer ``own`` (B, S, S // kpool) bool, the pools
    its own indexer takes by their scores (a query's own pool is read
    besides), and against ``selection`` (per sparse layer such a mask, at
    least as wide; the attention then reads THOSE) ``differ`` (B, S) the
    given picks that are not its own and ``shortfall`` (B, S): how far its
    own score of the worst given pool lies under the lowest it took
    itself, as a share of the spread of the scores it took (inf: too few
    picks, or too many);
    ``states``: per KDA layer the state the last token left, (B, H, d_k,
    d_v) float32. ``sinkhorn_iters`` and ``picks`` replace the
    configuration's (the controls' readings). ``balancing``: each expert
    layer's bias is evened on this forward's own scores as it goes, and
    the calibrated biases are returned under ``biases``."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    if state_dtype not in STATE_DTYPES:
        raise ValueError(f"state_dtype {state_dtype!r} not in {STATE_DTYPES}")
    z = sizes(config)
    f = _pieces(_key(config), precision, state_dtype,
                z["iters"] if sinkhorn_iters is None else int(sinkhorn_iters),
                picks_of(z) if picks is None else int(picks))
    x = f["embed"](weights["embed"], jnp.asarray(tokens))
    experts, sparse, states, biases = [], [], [], {}
    for i, (mixer, dense) in enumerate(z["kinds"]):
        w = _layer(weights, i)
        u, post, c = f["mix_pre"](x, _mix(w, "mix1"))
        picked = None
        if mixer == SPARSE and selection is not None:
            picked = selection[len(sparse)]
        y, extra = f[mixer](f["normed"](u, w["norm1"]), w, picked)
        (states if mixer == KDA else sparse).append(extra)
        x = f["mix_post"](x, y, post, c)
        u, post, c = f["mix_pre"](x, _mix(w, "mix2"))
        u = f["normed"](u, w["norm2"])
        if dense:
            y = f["dense_ffn"](u, w)
        else:
            s = f["scores_of"](u, w)
            bias = w["bias"]
            if balancing:
                bias, _ = _even_bias(s, k=z["k"])
                biases[f"l{i}.bias"] = bias = bias.astype(jnp.bfloat16)
            own = f["choose"](s, bias)
            ids = own if routing is None else jnp.asarray(
                routing[len(experts)], jnp.int32).reshape(own.shape)
            y = f["expert_ffn"](u, w, s, ids)
            experts.append({"ids": ids, "own_ids": own,
                            "scores": s + bias.astype(jnp.float32),
                            "gate_scores": s})
        # waited for layer by layer: dispatched ahead, the pieces' buffers
        # would all be reserved at once
        x = jax.block_until_ready(f["mix_post"](x, y, post, c))
    return {"logits": f["head"](x, weights["norm_f"], weights["lm_head"],
                                rows),
            "experts": experts, "sparse": sparse, "states": states,
            "biases": biases}


# ---- the selection bias ------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("k",))
def _even_bias(s, *, k):
    """The bias ``b`` under which the selection by ``s + b`` over the
    sample ``s`` (T, experts) loads every expert within
    ``BALANCE_TOLERANCE`` of even: from zero, an expert under the mean load
    raised and one over it lowered by ``BALANCE_RATE`` times its relative
    distance from it, until every load is within the tolerance (or
    ``BALANCE_STEPS``). Returns (b, the largest relative distance left)."""
    t, n = s.shape
    mean = t * k / n

    def loads(b):
        return jnp.zeros(n, jnp.float32).at[select(s + b, k).reshape(-1)].add(
            1.0)

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
    router's loads follow the seed, which no deployment's do, and the work
    a step's held experts do then follows the seed; in the published model
    this bias is what training moves until the loads are even
    (``noaux_tc``)."""
    seq = min(BALANCE_SEQ, BALANCE_TOKENS)
    tokens = jax.random.randint(key, (BALANCE_TOKENS // seq, seq), 0,
                                int(config["vocab_size"]), jnp.int32)
    return dict(weights, **forward(weights, tokens, config, rows=1,
                                   balancing=True)["biases"])
