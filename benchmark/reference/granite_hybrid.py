"""Plain Granite 4.0-H (``ibm-granite/granite-4.0-h-micro`` on the Hugging
Face hub, ``model_type`` ``granitemoehybrid``) in ``jax.numpy``: the
yardstick the benchmark compares the program with. Nothing here imports
``flexflow_tpu`` and nothing here is fast: no cache, no kernels, no
chunks of a prompt; the state-space layer is the token-by-token
recurrence (``recurrence="blocked"`` computes the same values by the
published blocked form, ``mamba_chunk_size`` tokens at a time: the tests
hold the two to each other).

The equations (``rms`` an RMSNorm of ``rms_norm_eps`` with a gain; ``E``
the ``vocab_size x hidden_size`` embedding; no bias on any linear map)::

    h = E[tokens] * embedding_multiplier
    layer i (layer_types[i]):
      a = rms_in(h)
      "mamba":     [z | xBC | dt] = a W_in      (H P | H P + 2 G N | H columns)
                   xBC_t = silu(bias + sum_{j<K} w[j] * in_{t-K+1+j})
                         (causal, depthwise, zeros before the sequence)
                   [xs | B | C] = xBC           (H heads of P | G of N | G of N)
                   dt_t = softplus(dt_t + dt_bias);  a_t = exp(dt_t A),
                   A = -exp(A_log), one scalar a head
                   S_t = a_t S_{t-1} + dt_t xs_t B_t^T   (a head: P x N, float32)
                   y_t = S_t C_t + D xs_t
                   m = rms_gate(y_t * silu(z_t)) W_out   (the norm over each of
                       the G groups of H P / G channels, a gain a channel)
      "attention": q = a Wq (heads, d); k = a Wk, v = a Wv (kv heads, d);
                   NO positions; query head i reads key-value head
                   i // (heads / kv heads); scores q.k * attention_multiplier,
                   causal, softmax in float32; m = attended Wo
      h = h + residual_multiplier * m
      b = rms_post(h);  f = (silu(b W_gate) * (b W_up)) W_down
      h = h + residual_multiplier * f
    logits = (rms_f(h) E^T) / logits_scaling          (the head is E itself)

``W_gate`` and ``W_up`` are the two halves of the published
``shared_mlp``'s one input matrix. What the published ``config.json``
does not settle is listed once, in the configuration file's ``assumed``
block (``configs/granite-4.0-h-micro.json``).

Weights are **bfloat16**, held once (the embedding is the head); the
forward upcasts them a piece at a time: a Python loop over layers of
small jitted pieces, attention a block of queries at a time, the MLP a
block of positions, the head a block of the vocabulary and only for the
last ``rows`` positions, so that at the published widths 5,008 positions
run beside a program that holds the same arrays and a full pool.

``precision`` chooses how every matrix product is computed (the
recurrence's own state stays float32, as the configuration states it):
``float32`` (``highest``; the reference), ``bfloat16`` (operands rounded,
float32 accumulation: what the configuration states the program computes
in), ``float8`` (operands rounded to e4m3 as they are, saturating),
``float8_scaled`` (each operand scaled so that its largest magnitude is
e4m3's 448, then rounded: what a deployment in float8 computes; the
control, which the comparison has to refuse).
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("float32", "bfloat16", "float8", "float8_scaled")
INIT_STD = 0.02
E4M3_MAX = 448.0
MAMBA, ATTENTION = "mamba", "attention"
QUERY_BLOCK = 512          # queries attended at a time
MLP_BLOCK = 1024           # positions through the MLP at a time
VOCAB_BLOCKS = 8           # pieces the head's matrix is upcast in
_HI = jax.lax.Precision.HIGHEST


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
    """The shapes a configuration states."""
    kinds = tuple(config["layer_types"])
    if len(kinds) != int(config["num_hidden_layers"]) or set(kinds) - {
            MAMBA, ATTENTION}:
        raise ValueError(f"layer_types is not {config['num_hidden_layers']} "
                         f"of {MAMBA!r} and {ATTENTION!r}")
    h, p = int(config["mamba_n_heads"]), int(config["mamba_d_head"])
    g, n = int(config["mamba_n_groups"]), int(config["mamba_d_state"])
    heads = int(config["num_attention_heads"])
    return {
        "vocab": int(config["vocab_size"]), "e": int(config["hidden_size"]),
        "kinds": kinds, "layers": len(kinds),
        "m_heads": h, "m_dim": p, "state": n, "groups": g,
        "inner": h * p, "channels": h * p + 2 * g * n,
        "taps": int(config["mamba_d_conv"]),
        "block": int(config["mamba_chunk_size"]),
        "heads": heads, "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["hidden_size"]) // heads,
        "width": int(config["shared_intermediate_size"]),
    }


def layer_shapes(config: Dict, kind: str) -> Dict[str, Tuple[int, ...]]:
    """One layer's weights by its kind: the mixer behind its norm, then
    the MLP behind its own."""
    z = sizes(config)
    e, w = z["e"], z["width"]
    mlp = {"norm": (e,), "mlp_norm": (e,), "gate": (e, w), "up": (e, w),
           "down": (w, e)}
    if kind == MAMBA:
        return dict(mlp, **{
            "w_in": (e, z["inner"] + z["channels"] + z["m_heads"]),
            "conv": (z["taps"], z["channels"]),
            "conv_bias": (z["channels"],), "a_log": (z["m_heads"],),
            "dt_bias": (z["m_heads"],), "d": (z["m_heads"],),
            "gate_norm": (z["inner"],), "w_out": (z["inner"], e)})
    h, hkv, d = z["heads"], z["kv_heads"], z["head_dim"]
    return dict(mlp, wq=(e, h, d), wk=(e, hkv, d), wv=(e, hkv, d),
                wo=(h, d, e))


def param_shapes(config: Dict) -> Dict[str, Tuple[int, ...]]:
    """Every array the model holds: the embedding ONCE (the head is it)."""
    z = sizes(config)
    shapes = {"embed": (z["vocab"], z["e"]), "norm_f": (z["e"],)}
    for i, kind in enumerate(z["kinds"]):
        shapes.update({f"l{i}.{k}": s
                       for k, s in layer_shapes(config, kind).items()})
    return shapes


def param_count(config: Dict) -> int:
    return sum(int(np.prod(s)) for s in param_shapes(config).values())


def state_bytes_per_request(config: Dict, tail_bytes: int = 2) -> int:
    """What a request keeps of the Mamba layers: a float32 state and the
    last ``taps - 1`` inputs of the convolution each."""
    z = sizes(config)
    return z["kinds"].count(MAMBA) * (
        4 * z["m_heads"] * z["m_dim"] * z["state"]
        + tail_bytes * (z["taps"] - 1) * z["channels"])


# how each leaf is drawn: the rest are matrices, N(0, 0.02)
_KINDS = {"norm": "gain", "mlp_norm": "gain", "gate_norm": "gain",
          "norm_f": "gain", "a_log": "a_log", "dt_bias": "dt_bias",
          "d": "one", "conv": "conv", "conv_bias": "conv"}


@functools.partial(jax.jit, static_argnames=("shape", "kind", "taps"))
def _draw(key, *, shape, kind, taps=4):
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
        x = 1.0 + x if kind == "gain" else x
    return x.astype(jnp.bfloat16)


def init_weights(config: Dict, seed: int) -> Dict[str, jax.Array]:
    """Every weight, bfloat16, made on the device from the seed: matrices
    and the embedding N(0, 0.02) (the projections back into the residual
    stream too: the 0.22 is the model's own damping), norm gains 1 + N(0,
    0.02) so that a dropped gain shows, ``A_log``, ``dt_bias``, ``D`` and
    the convolution as the configuration's ``assumed`` block says. One
    small jitted draw a weight (one compilation a shape, which the layers
    share)."""
    key = fold_seed(seed)
    z = sizes(config)
    out = {}
    for i, (name, shape) in enumerate(sorted(param_shapes(config).items())):
        leaf = name.rsplit(".", 1)[-1]
        out[name] = _draw(jax.random.fold_in(key, i), shape=tuple(shape),
                          kind=_KINDS.get(leaf, "matrix"), taps=z["taps"])
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
    return jnp.einsum(spec, a, b, precision=_HI,
                      preferred_element_type=jnp.float32)


def _rms(x, g, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * g.astype(jnp.float32))


def _blocks(x, size: int):
    """(B, S, ...) -> (blocks, B, size, ...), zeros behind the sequence."""
    s = x.shape[1]
    x = jnp.pad(x, ((0, 0), (0, -s % size)) + ((0, 0),) * (x.ndim - 2))
    return jnp.moveaxis(
        x.reshape((x.shape[0], -1, size) + x.shape[2:]), 1, 0)


def _unblocks(y, s: int):
    """:func:`_blocks` undone: (blocks, B, size, ...) -> (B, S, ...)."""
    y = jnp.moveaxis(y, 0, 1)
    return y.reshape((y.shape[0], -1) + y.shape[3:])[:, :s]


def scan_tokens(la, dt, xs, bm, cm):
    """The recurrence as written, a token at a time: ``la`` = dt A (the
    logarithm of ``a_t``) and ``dt`` (B, S, H), ``xs`` (B, S, H, P),
    ``bm``, ``cm`` (B, S, H, N), all float32, from a zero state. Returns
    y (B, S, H, P) without the ``D`` term."""
    b, _, h, p = xs.shape
    a = jnp.exp(la)

    def token(state, t):
        a_t, dt_t, x_t, b_t, c_t = t
        state = (a_t[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None])
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t, precision=_HI)

    _, y = jax.lax.scan(
        token, jnp.zeros((b, h, p, bm.shape[-1]), jnp.float32),
        tuple(jnp.moveaxis(v, 1, 0) for v in (a, dt, xs, bm, cm)))
    return jnp.moveaxis(y, 0, 1)


def scan_blocked(la, dt, xs, bm, cm, block: int):
    """The same values by the published blocked form: within a block of
    ``block`` tokens ``y_t = sum_{s <= t} (C_t . B_s) (prod_{s < r <= t}
    a_r) dt_s xs_s`` plus what the state before the block gives, and the
    state is carried from block to block."""
    b, s, h, p = xs.shape
    n = bm.shape[-1]
    la, u, bb, cb = (_blocks(v, block) for v in (
        la, xs * dt[..., None], bm, cm))
    idx = jnp.arange(block)

    def one(state, blk):
        la_, u_, b_, c_ = blk
        cum = jnp.cumsum(la_, axis=1)                        # (B, T, H)
        decay = jnp.exp(jnp.where(
            (idx[:, None] >= idx[None, :])[None, :, :, None],
            cum[:, :, None] - cum[:, None, :], -jnp.inf))    # (B, T, S, H)
        cbm = jnp.einsum("bthn,bshn->btsh", c_, b_, precision=_HI)
        y = jnp.einsum("btsh,bshp->bthp", cbm * decay, u_, precision=_HI)
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            "bthn,bhpn->bthp", c_, state, precision=_HI)
        to_end = jnp.exp(cum[:, -1:] - cum)                  # (B, T, H)
        state = (jnp.exp(cum[:, -1])[..., None, None] * state
                 + jnp.einsum("bshp,bshn->bhpn", u_ * to_end[..., None], b_,
                              precision=_HI))
        return state, y

    _, y = jax.lax.scan(one, jnp.zeros((b, h, p, n), jnp.float32),
                        (la, u, bb, cb))
    return _unblocks(y, s)


def _key(config: Dict) -> Tuple:
    return tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v)
        for k, v in config.items()
        if isinstance(v, (int, float, str, bool)) or k == "layer_types"))


@functools.lru_cache(maxsize=None)
def _pieces(config_items: Tuple, precision: str, recurrence: str):
    """The jitted pieces of one configuration and precision."""
    config = {k: (list(v) if isinstance(v, tuple) else v)
              for k, v in config_items}
    z = sizes(config)
    eps = float(config.get("rms_norm_eps", 1e-5))
    resid = float(config.get("residual_multiplier", 1.0))
    scale = float(config.get("attention_multiplier")
                  or 1.0 / math.sqrt(z["head_dim"]))
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
        la = dt * -jnp.exp(w["a_log"].astype(f32))            # (B, S, H)
        y = (scan_tokens(la, dt, xs, bm, cm) if recurrence == "tokens"
             else scan_blocked(la, dt, xs, bm, cm, z["block"]))
        y = y + w["d"].astype(f32)[:, None] * xs
        y = y.reshape(b, s, z["inner"]) * jax.nn.silu(zg)      # gate first
        grouped = y.reshape(b, s, g, -1)
        grouped = grouped * jax.lax.rsqrt(
            jnp.mean(grouped * grouped, -1, keepdims=True) + eps)
        y = grouped.reshape(b, s, z["inner"]) * w["gate_norm"].astype(f32)
        return x + resid * _mm("bsf,fe->bse", y, w["w_out"], precision)

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
        kpos = jnp.arange(s)

        def block(args):                     # a block of queries
            qb, first = args
            scores = _mm("bqhd,bkhd->bhqk", qb, k, precision) * scale
            qpos = first + jnp.arange(qb.shape[1])
            probs = jax.nn.softmax(
                jnp.where((kpos[None, :] <= qpos[:, None])[None, None],
                          scores, -jnp.inf), axis=-1)
            return _mm("bhqk,bkhd->bqhd", probs, v, precision)

        qb = _blocks(q, QUERY_BLOCK)
        o = _unblocks(jax.lax.map(
            block, (qb, jnp.arange(qb.shape[0]) * QUERY_BLOCK)), s)
        return x + resid * _mm("bqhd,hde->bqe", o, w["wo"], precision)

    @jax.jit
    def mlp(x, w):
        def block(xb):
            v = _rms(xb, w["mlp_norm"], eps)
            hid = (jax.nn.silu(_mm("bse,ef->bsf", v, w["gate"], precision))
                   * _mm("bse,ef->bsf", v, w["up"], precision))
            return _mm("bsf,fe->bse", hid, w["down"], precision)

        return x + resid * _unblocks(
            jax.lax.map(block, _blocks(x, MLP_BLOCK)), x.shape[1])

    @jax.jit
    def head(x, g, table):
        """The tied head, a block of the vocabulary at a time."""
        u = _rms(x, g, eps)
        v = table.shape[0]
        n = VOCAB_BLOCKS if v % VOCAB_BLOCKS == 0 else 1
        parts = jax.lax.map(
            lambda rows: _mm("bse,ve->bsv", u, rows, precision),
            table.reshape(n, v // n, -1))                # (n, B, S, V / n)
        logits = jnp.moveaxis(parts, 0, 2).reshape(x.shape[:2] + (v,))
        return logits / float(config.get("logits_scaling", 1.0))

    @jax.jit
    def embed(table, tokens):
        return (table[tokens].astype(f32)
                * float(config.get("embedding_multiplier", 1.0)))

    return {MAMBA: mamba, ATTENTION: attention, "mlp": mlp, "head": head,
            "embed": embed}


def forward(weights: Dict, tokens, config: Dict, precision: str = "float32",
            rows: Optional[int] = None, recurrence: str = "tokens"):
    """``tokens`` (B, S) int32 -> logits (B, S, V) float32, or with
    ``rows`` those of the last ``rows`` positions alone (B, rows, V): the
    head is computed for no other."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    if recurrence not in ("tokens", "blocked"):
        raise ValueError(f"recurrence {recurrence!r}")
    z = sizes(config)
    f = _pieces(_key(config), precision, recurrence)
    x = f["embed"](weights["embed"], jnp.asarray(tokens))
    for i, kind in enumerate(z["kinds"]):
        p = f"l{i}."
        w = {k[len(p):]: v for k, v in weights.items() if k.startswith(p)}
        x = f["mlp"](f[kind](x, w), w)
    if rows is not None:
        x = x[:, x.shape[1] - int(rows):]
    return f["head"](x, weights["norm_f"], weights["embed"])
