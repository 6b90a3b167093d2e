"""The gated-delta-rule op (ops/gated_delta.py) and its decode kernel
(kernels/gated_delta.py): the chunked whole-sequence form, the one-token
form and the reference's token-by-token recurrence give the same sums; a
padded prefill stops at the true length; the kernel, interpreted, is its
jnp form; and ``qk_norm`` on multi-head attention."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.ffconst import CompMode, DataType
from flexflow_tpu.kernels import gated_delta as gd
from flexflow_tpu.ops.gated_delta import CHUNK, chunked_delta_rule

H, DK, DV, E = 3, 8, 16, 24


def _qkv(rng, b, s, h=H, dk=DK, dv=DV):
    q = rng.normal(size=(b, s, h, dk)).astype(np.float32)
    k = rng.normal(size=(b, s, h, dk)).astype(np.float32)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(b, s, h, dv)).astype(np.float32)
    g = -rng.uniform(1e-3, 0.2, size=(b, s, h)).astype(np.float32)
    beta = rng.uniform(0, 2, size=(b, s, h)).astype(np.float32)
    return q, k, v, g, beta


def _by_hand(q, k, v, g, beta):
    """The recurrence as the module's docstring writes it, in float64."""
    b, s, h, dk = q.shape
    state = np.zeros((b, h, dk, v.shape[-1]))
    out = np.zeros(v.shape)
    for t in range(s):
        state = np.exp(g[:, t])[..., None, None] * state
        r = v[:, t] - np.einsum("bhdv,bhd->bhv", state, k[:, t])
        state = state + (beta[:, t][..., None, None] * k[:, t][..., None]
                         * r[:, :, None, :])
        out[:, t] = np.einsum("bhdv,bhd->bhv", state, q[:, t])
    return out, state


@pytest.mark.parametrize("s", [1, CHUNK, 2 * CHUNK + 22])
def test_chunked_form_is_the_recurrence(s):
    """Also with a sequence no chunk divides, and one shorter than a
    chunk."""
    q, k, v, g, beta = _qkv(np.random.default_rng(s), 2, s)
    want, want_state = _by_hand(q, k, v, g, beta)
    got, state = chunked_delta_rule(*map(jnp.asarray, (q, k, v, g, beta)),
                                    jnp.zeros((2, H, DK, DV)))
    assert np.abs(got - want).max() < 2e-5 * np.abs(want).max()
    assert np.abs(state - want_state).max() < 2e-5


def test_one_token_form_is_the_recurrence_and_the_chunked_form():
    q, k, v, g, beta = _qkv(np.random.default_rng(5), 2, 70)
    want, want_state = _by_hand(q, k, v, g, beta)
    state = jnp.zeros((2, DK, H * DV))
    outs = []
    for t in range(70):
        o, state = gd.delta_rule_step(state, q[:, t], k[:, t], v[:, t],
                                      np.exp(g[:, t]), beta[:, t])
        outs.append(o)
    got = np.stack(outs, 1)
    assert np.abs(got - want).max() < 2e-5 * np.abs(want).max()
    # the arena's layout: keys down, heads side by side across
    lanes = np.moveaxis(want_state, 1, 2).reshape(2, DK, H * DV)
    assert np.abs(state - lanes).max() < 2e-5
    chunked, _ = chunked_delta_rule(*map(jnp.asarray, (q, k, v, g, beta)),
                                    jnp.zeros((2, H, DK, DV)))
    assert np.abs(got - chunked).max() < 2e-5 * np.abs(want).max()


def test_a_chunk_carries_its_state_into_the_next_call():
    q, k, v, g, beta = map(jnp.asarray, _qkv(np.random.default_rng(6), 1, 100))
    whole, end = chunked_delta_rule(q, k, v, g, beta,
                                    jnp.zeros((1, H, DK, DV)))
    cut = 37
    first, mid = chunked_delta_rule(q[:, :cut], k[:, :cut], v[:, :cut],
                                    g[:, :cut], beta[:, :cut],
                                    jnp.zeros((1, H, DK, DV)))
    second, end2 = chunked_delta_rule(q[:, cut:], k[:, cut:], v[:, cut:],
                                      g[:, cut:], beta[:, cut:], mid)
    both = jnp.concatenate([first, second], axis=1)
    assert np.abs(both - whole).max() < 2e-5 * np.abs(whole).max()
    assert np.abs(end - end2).max() < 2e-5


# ---- the op ------------------------------------------------------------------

def _op(neg=True, taps=4):
    ff = FFModel(FFConfig(batch_size=2, ledger="off",
                          computation_mode=CompMode.INFERENCE))
    x = ff.create_tensor((2, 12, E), DataType.FLOAT, name="x")
    ff.gated_delta_net(x, num_heads=H, key_dim=DK, value_dim=DV,
                       conv_taps=taps, allow_neg_eigval=neg, name="gdn")
    ff.compile(optimizer=None, loss_type=None, metrics=[])
    cm = ff.compiled
    op = [o for o in cm.ops if o.name == "gdn"][0]
    rng = np.random.default_rng(11)
    w = {k: jnp.asarray(rng.normal(size=v.shape).astype(np.float32)
                        * (0.3 if v.ndim > 1 else 1.0))
         for k, v in cm.params["gdn"].items()}
    return ff, op, w


def test_op_shapes_weights_and_flops():
    ff, op, w = _op()
    assert {k: v.shape for k, v in w.items()} == {
        "wq": (E, H * DK), "wk": (E, H * DK), "wv": (E, H * DV),
        "wg": (E, H * DV), "wa": (E, H), "wb": (E, H),
        "conv": (4, 2 * H * DK + H * DV), "a_log": (H,), "dt_bias": (H,),
        "norm": (DV,), "wo": (H * DV, E)}
    assert op.output_shapes[0].sizes == (2, 12, E)
    assert op.flops() > 2.0 * 2 * 12 * E * (2 * H * DK + 3 * H * DV)
    out = ff.compiled.forward_fn(ff.compiled.params,
                                 jnp.ones((2, 12, E), jnp.float32))
    assert out.shape == (2, 12, E) and bool(jnp.isfinite(out).all())


def test_padded_prefill_leaves_the_true_lengths_state_and_tail():
    """A prompt of 70 in a bucket of 150, beside one of 150: each row's
    state and convolution tail are those of its own length, and its
    outputs up to there those of the unpadded run."""
    _, op, w = _op()
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 150, E)).astype(np.float32))
    lengths = jnp.asarray([70, 150], jnp.int32)
    y, state, tail = op.whole(w, x, lengths)
    y0, state0, tail0 = op.whole(w, x[:1, :70])
    assert np.abs(y[0, :70] - y0[0]).max() < 1e-5 * np.abs(y0).max()
    assert np.abs(state[0] - state0[0]).max() < 1e-5
    assert np.array_equal(tail[0], tail0[0])
    assert np.array_equal(tail[0], op.conv_inputs(w, x)[0, 67:70])
    y1, state1, tail1 = op.whole(w, x[1:])
    assert np.abs(state[1] - state1[0]).max() < 1e-5
    assert np.array_equal(tail[1], tail1[0])
    # a prompt shorter than the convolution: zeros before the sequence
    _, _, short = op.whole(w, x, jnp.asarray([2, 0], jnp.int32))
    assert np.array_equal(short[0, 0], np.zeros(op.channels))
    assert np.array_equal(short[0, 1:], op.conv_inputs(w, x)[0, :2])
    assert not short[1].any()


def test_run_behind_a_state_continues_the_sequence():
    _, op, w = _op()
    x = jnp.asarray(np.random.default_rng(4).normal(size=(1, 90, E))
                    .astype(np.float32))
    y, state, tail = op.whole(w, x)
    y_a, st_a, tail_a = op.whole(w, x[:, :51])
    y_b, st_b, tail_b = op.run(w, x[:, 51:], st_a, tail_a)
    assert np.abs(jnp.concatenate([y_a, y_b], 1) - y).max() \
        < 1e-5 * np.abs(y).max()
    assert np.abs(st_b - state).max() < 1e-5
    assert np.abs(tail_b - tail).max() < 1e-5   # other shapes' matmuls


@pytest.mark.parametrize("neg", [True, False])
def test_beta_doubles_only_where_negative_eigenvalues_are_allowed(neg):
    _, op, w = _op(neg=neg)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 5, E))
                    .astype(np.float32) * 4)
    g, beta = op.gates(w, x)
    assert float(beta.max()) <= (2.0 if neg else 1.0)
    assert (float(beta.max()) > 1.0) == neg
    assert float(g.max()) <= 0.0


# ---- the kernel ---------------------------------------------------------------

@pytest.mark.parametrize("h,dk,dv", [(4, 8, 64), (2, 16, 128), (6, 8, 192)])
def test_kernel_interpreted_is_its_jnp_form(monkeypatch, h, dk, dv):
    """Heads that pair up over three lane tiles (192), that fill one
    (128), and that share one (64); two idle slots on the null row."""
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    rng = np.random.default_rng(0)
    n, rows_n = 5, 7
    arena = jnp.asarray(rng.normal(size=(rows_n, dk, h * dv))
                        .astype(np.float32))
    rows = jnp.asarray([3, 0, 5, 0, 1], jnp.int32)
    q, k, v, g, beta = _qkv(rng, n, 1, h, dk, dv)
    args = (q[:, 0], k[:, 0], v[:, 0], np.exp(g[:, 0]), beta[:, 0])
    assert gd.supported(n, h, dk, dv, arena.shape, arena.dtype)
    o1, a1 = gd.gated_delta_decode(arena, rows, *args)
    o2, a2 = gd.gated_delta_step(arena, rows, *args)
    live, slots = [1, 3, 5], [0, 2, 4]
    assert np.abs(np.asarray(o1)[slots] - np.asarray(o2)[slots]).max() < 1e-5
    assert np.abs(np.asarray(a1)[live] - np.asarray(a2)[live]).max() < 1e-5
    # rows no slot names are not touched
    assert np.array_equal(np.asarray(a1)[[2, 4, 6]],
                          np.asarray(arena)[[2, 4, 6]])


def test_kernel_refuses_what_it_does_not_build(monkeypatch):
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    ok = (4, 4, 8, 64, (9, 8, 256), jnp.float32)
    assert gd.supported(*ok)
    assert not gd.supported(4, 4, 8, 64, (9, 8, 256), jnp.bfloat16)
    assert not gd.supported(4, 4, 8, 48, (9, 8, 192), jnp.float32)
    assert not gd.supported(4, 3, 8, 64, (9, 8, 192), jnp.float32)  # odd
    assert not gd.supported(4, 4, 12, 64, (9, 12, 256), jnp.float32)
    assert not gd.supported(4, 4, 8, 64, (9, 8, 128), jnp.float32)
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "off")
    assert not gd.supported(*ok)


# ---- qk_norm on multi-head attention -------------------------------------------

def _mha(**kw):
    ff = FFModel(FFConfig(batch_size=2, ledger="off", seed=0,
                          computation_mode=CompMode.INFERENCE))
    x = ff.create_tensor((2, 6, 16), DataType.FLOAT, name="x")
    ff.multihead_attention(x, x, x, 16, 4, causal=True, name="attn", **kw)
    ff.compile(optimizer=None, loss_type=None, metrics=[])
    return ff


def test_qk_norm_is_an_rms_norm_over_the_whole_projection():
    ff = _mha(bias=False, qk_norm=True, norm_eps=1e-6)
    cm = ff.compiled
    assert set(cm.params["attn"]) == {"wq", "wk", "wv", "wo", "q_norm",
                                      "k_norm"}
    rng = np.random.default_rng(1)
    w = {k: jnp.asarray(rng.normal(size=v.shape).astype(np.float32))
         for k, v in cm.params["attn"].items()}
    x = jnp.asarray(rng.normal(size=(2, 6, 16)).astype(np.float32))
    got = cm.forward_fn({"attn": w}, x)

    def norm(a, gain):                   # over all 16 projected values
        a = np.asarray(a, np.float64)
        return a / np.sqrt((a * a).mean(-1, keepdims=True) + 1e-6) \
            * np.asarray(gain).reshape(-1)

    wq, wk, wv = (np.asarray(w[n]).reshape(16, 16) for n in ("wq", "wk", "wv"))
    q = norm(np.asarray(x) @ wq, w["q_norm"]).reshape(2, 6, 4, 4)
    k = norm(np.asarray(x) @ wk, w["k_norm"]).reshape(2, 6, 4, 4)
    v = (np.asarray(x) @ wv).reshape(2, 6, 4, 4)
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / 2.0
    s = np.where(np.tril(np.ones((6, 6), bool)), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.einsum("bhqk,bkhd->bqhd", p, v).reshape(2, 6, 16) \
        @ np.asarray(w["wo"]).reshape(16, 16)
    assert np.abs(got - want).max() < 1e-4 * np.abs(want).max()


def test_without_qk_norm_the_op_declares_what_it_always_did():
    ff = _mha()
    assert set(ff.compiled.params["attn"]) == {"wq", "wk", "wv", "wo", "bq",
                                               "bk", "bv", "bo"}
    op = [o for o in ff.compiled.ops if o.name == "attn"][0]
    assert op.qk_norm is False and "qk_norm" not in op.attrs
