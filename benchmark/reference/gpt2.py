"""Plain GPT-2 (Radford et al. 2019; ``openai-community/gpt2*`` on the
Hugging Face hub) in ``jax.numpy``: the yardstick the benchmark compares
the program with. Nothing here imports ``flexflow_tpu`` and nothing here
is fast: no kernels, no cache, no batching tricks.

Weights keep GPT-2's own layout (``c_attn`` is one (E, 3E) matrix,
``Conv1D`` weights are (in, out)). Departures from the published model,
each listed under ``assumed`` in the configuration's file:

* the output head ``lm_head`` (E, V) is a matrix of its own and not the
  transpose of ``wte`` — ``flexflow_tpu/models/gpt.py`` builds it so;
* the activation is the configuration's ``activation_function``; the
  configurations here state ``gelu`` (the exact erf form
  ``models/gpt.py`` computes), where the published files say
  ``gelu_new`` (the tanh approximation).

``precision`` chooses how every matrix product is computed:

* ``float32`` — float32 operands under ``default_matmul_precision(
  "highest")`` (a TPU otherwise multiplies float32 in bfloat16 passes).
  This is the reference.
* ``bfloat16`` — operands rounded to bfloat16, float32 accumulation: what
  the configurations state the program's products are computed in.
* ``float8`` — operands rounded to float8 e4m3 and, in the backward pass,
  gradients to e5m2: the control, the next precision below bfloat16 (the
  benchmark's contract), which the comparison has to refuse.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

PRECISIONS = ("float32", "bfloat16", "float8")
INIT_STD = 0.02  # GPT-2's initializer_range


def fold_seed(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number, however large: the
    low 31 bits seed the key and the rest is folded in. The key is of
    the ``rbg`` kind (the device's own bit generator): drawing 400 M
    normals from the default threefry costs the TPU compiler twice the
    time and the chip far more."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed {seed} < 0")
    key = jax.random.key(seed & 0x7FFFFFFF, impl="rbg")
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _sizes(config: Dict) -> Tuple[int, int, int, int, int, int]:
    e = int(config["n_embd"])
    inner = config.get("n_inner") or 4 * e
    return (int(config["vocab_size"]), int(config["n_positions"]), e,
            int(config["n_head"]), int(config["n_layer"]), int(inner))


def param_shapes(config: Dict) -> Dict[str, Tuple[int, ...]]:
    v, p, e, _, n_layer, inner = _sizes(config)
    shapes = {"wte": (v, e), "wpe": (p, e), "ln_f.g": (e,), "ln_f.b": (e,),
              "lm_head": (e, v)}
    for i in range(n_layer):
        shapes.update({
            f"h{i}.ln_1.g": (e,), f"h{i}.ln_1.b": (e,),
            f"h{i}.attn.c_attn.w": (e, 3 * e), f"h{i}.attn.c_attn.b": (3 * e,),
            f"h{i}.attn.c_proj.w": (e, e), f"h{i}.attn.c_proj.b": (e,),
            f"h{i}.ln_2.g": (e,), f"h{i}.ln_2.b": (e,),
            f"h{i}.mlp.c_fc.w": (e, inner), f"h{i}.mlp.c_fc.b": (inner,),
            f"h{i}.mlp.c_proj.w": (inner, e), f"h{i}.mlp.c_proj.b": (e,),
        })
    return shapes


_PER_LAYER = ("ln_1.g", "ln_1.b", "attn.c_attn.w", "attn.c_attn.b",
              "attn.c_proj.w", "attn.c_proj.b", "ln_2.g", "ln_2.b",
              "mlp.c_fc.w", "mlp.c_fc.b", "mlp.c_proj.w", "mlp.c_proj.b")
_GLOBAL = ("wte", "wpe", "ln_f.g", "ln_f.b", "lm_head")


def init_weights(config: Dict, seed: int) -> Dict[str, jax.Array]:
    """Every weight, float32, made on the device in one jitted call from
    the seed. Matrices and embeddings are N(0, 0.02) as GPT-2 initialises
    them, the residual projections scaled by 1/sqrt(2 * n_layer); biases
    and LayerNorm offsets are N(0, 0.02) and gains 1 + N(0, 0.02) and not
    GPT-2's zeros and ones, so that a dropped bias or gain shows in the
    comparison. Each kind of block weight is drawn for all blocks at
    once (one random op a kind: hundreds of separate ones take the TPU
    compiler over a minute)."""
    shapes = param_shapes(config)
    n_layer = int(config["n_layer"])

    def draw(key, name, shape):
        x = INIT_STD * jax.random.normal(key, shape, jnp.float32)
        if name.endswith("c_proj.w"):
            x = x / math.sqrt(2.0 * n_layer)
        return 1.0 + x if name.endswith(".g") else x

    @jax.jit
    def make(key):
        out = {}
        for i, name in enumerate(_GLOBAL):
            out[name] = draw(jax.random.fold_in(key, i), name, shapes[name])
        for j, name in enumerate(_PER_LAYER):
            stacked = draw(jax.random.fold_in(key, len(_GLOBAL) + j), name,
                           (n_layer,) + shapes[f"h0.{name}"])
            for i in range(n_layer):
                out[f"h{i}.{name}"] = stacked[i]
        return out

    return make(fold_seed(seed))


def _einsum(spec: str, a, b):
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _to_bf16(x):
    # reduce_precision, not a round trip through astype: XLA may drop a
    # float32 -> bfloat16 -> float32 pair as excess precision
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _to_e4m3(x):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mm_float8(spec: str, a, b):
    """A matrix product as float8 training computes it: both operands
    rounded to e4m3 on the way forward; on the way back the incoming
    gradient rounded to e5m2 under one scale for the tensor (its largest
    magnitude mapped to 4096, well inside e5m2's range), then multiplied
    with the rounded operands. Accumulation is float32 throughout."""
    return _einsum(spec, _to_e4m3(a), _to_e4m3(b))


def _mm_float8_fwd(spec, a, b):
    ra, rb = _to_e4m3(a), _to_e4m3(b)
    return _einsum(spec, ra, rb), (ra, rb)


def _mm_float8_bwd(spec, res, g):
    ra, rb = res
    scale = jnp.maximum(jnp.max(jnp.abs(g)), 1e-30) / 4096.0
    gq = (g / scale).astype(jnp.float8_e5m2).astype(jnp.float32) * scale
    _, vjp = jax.vjp(lambda x, y: _einsum(spec, x, y), ra, rb)
    return vjp(gq)


_mm_float8.defvjp(_mm_float8_fwd, _mm_float8_bwd)


def _mm(spec: str, a, b, precision: str):
    if precision == "float8":
        return _mm_float8(spec, a, b)
    if precision == "bfloat16":
        a, b = _to_bf16(a), _to_bf16(b)
    return _einsum(spec, a, b)


def _layer_norm(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _act(x, name: str):
    if name == "gelu":
        return jax.nn.gelu(x, approximate=False)
    if name == "gelu_new":
        return jax.nn.gelu(x, approximate=True)
    raise ValueError(f"activation_function {name!r} is not one GPT-2 uses")


def forward(weights: Dict, tokens, config: Dict,
            precision: str = "float32"):
    """``tokens`` (B, S) int32 -> logits (B, S, V) float32. Positions are
    0..S-1; attention is causal over the whole sequence."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    _, _, e, n_head, n_layer, _ = _sizes(config)
    eps = float(config.get("layer_norm_epsilon", 1e-5))
    act = config.get("activation_function", "gelu_new")
    b, s = tokens.shape
    d = e // n_head
    w = weights
    x = w["wte"][tokens] + w["wpe"][jnp.arange(s)][None]
    causal = jnp.tril(jnp.ones((s, s), bool))
    for i in range(n_layer):
        h = _layer_norm(x, w[f"h{i}.ln_1.g"], w[f"h{i}.ln_1.b"], eps)
        qkv = _mm("bse,ef->bsf", h, w[f"h{i}.attn.c_attn.w"], precision) \
            + w[f"h{i}.attn.c_attn.b"]
        q, k, v = (t.reshape(b, s, n_head, d)
                   for t in jnp.split(qkv, 3, axis=-1))
        scores = _mm("bqhd,bkhd->bhqk", q, k, precision) / math.sqrt(d)
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        ctx = _mm("bhqk,bkhd->bqhd", probs, v, precision).reshape(b, s, e)
        x = x + _mm("bse,ef->bsf", ctx, w[f"h{i}.attn.c_proj.w"],
                    precision) + w[f"h{i}.attn.c_proj.b"]
        h = _layer_norm(x, w[f"h{i}.ln_2.g"], w[f"h{i}.ln_2.b"], eps)
        m = _act(_mm("bse,ef->bsf", h, w[f"h{i}.mlp.c_fc.w"], precision)
                 + w[f"h{i}.mlp.c_fc.b"], act)
        x = x + _mm("bsf,fe->bse", m, w[f"h{i}.mlp.c_proj.w"], precision) \
            + w[f"h{i}.mlp.c_proj.b"]
    x = _layer_norm(x, w["ln_f.g"], w["ln_f.b"], eps)
    return _mm("bse,ev->bsv", x, w["lm_head"], precision)


def loss(weights: Dict, tokens, labels, config: Dict,
         precision: str = "float32"):
    """Mean next-token cross-entropy over every position of the batch."""
    logp = jax.nn.log_softmax(forward(weights, tokens, config, precision))
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)
    return -picked.mean()


@functools.lru_cache(maxsize=None)
def _jitted(kind: str, config_items: Tuple, precision: str,
            names: Tuple[str, ...] = ()):
    """The jitted forward, or the jitted loss with its gradient at the
    weights in ``names``; one per configuration and precision, so that a
    process that re-seeds the weights traces each once."""
    config = dict(config_items)
    if kind == "forward":
        return jax.jit(lambda w, t: forward(w, t, config, precision))

    def sampled_loss(sub, rest, t, y):
        return loss({**rest, **sub}, t, y, config, precision)

    return jax.jit(jax.value_and_grad(sampled_loss))


def _key(config: Dict) -> Tuple:
    return tuple(sorted((k, v) for k, v in config.items()
                        if isinstance(v, (int, float, str))))


def forward_jit(weights, tokens, config, precision="float32"):
    return _jitted("forward", _key(config), precision)(weights, tokens)


def loss_and_grads(weights, tokens, labels, config, names,
                   precision="float32"):
    """Mean loss over the batch and its gradient at the weights in
    ``names``, one sequence at a time (a batch's float32 attention
    probabilities would not fit beside a training run's state)."""
    fn = _jitted("grad", _key(config), precision, tuple(names))
    sub = {k: weights[k] for k in names}
    rest = {k: v for k, v in weights.items() if k not in sub}
    n = tokens.shape[0]
    total, grads = 0.0, None
    for i in range(n):
        l, g = fn(sub, rest, tokens[i:i + 1], labels[i:i + 1])
        total += float(l) / n
        grads = g if grads is None else jax.tree_util.tree_map(
            jnp.add, grads, g)
    return total, jax.tree_util.tree_map(lambda a: a / n, grads)
