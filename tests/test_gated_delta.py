"""The gated-delta-rule op (ops/gated_delta.py) and its kernels
(kernels/gated_delta.py): the chunked whole-sequence form, the one-token
form and the reference's token-by-token recurrence give the same sums; a
padded prefill stops at the true length; each kernel, interpreted, is its
jnp form (the whole-sequence one on an ill-conditioned chunk too, where
a series in powers of the system would not be); and ``qk_norm`` on
multi-head attention."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.ffconst import CompMode, DataType
from flexflow_tpu.kernels import gated_delta as gd
from flexflow_tpu.ops import gated_delta as op_mod
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


def _by_hand(q, k, v, g, beta, state=None):
    """The recurrence as the module's docstring writes it, in float64."""
    b, s, h, dk = q.shape
    state = (np.zeros((b, h, dk, v.shape[-1])) if state is None
             else np.asarray(state, np.float64))
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


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("heads, dk, dv", [(4, 8, 16), (4, 32, 32),
                                           (4, 8, 8)])
def test_tails_step_is_convolve_over_the_rows_windows(monkeypatch, heads,
                                                      dk, dv, dtype):
    """``tails_step`` (interpreted), one position a row over FLAT tails
    (taps side by side on the lanes: 128 and 384 channels; 96 it refuses)
    of 19 rows, more than two sublane tiles and no whole number of them,
    against ``convolve`` over the window the serving step used to build,
    ``(n, taps, channels)``: the same float32 products summed in the same
    order, so the same numbers; a live row's tail shifted behind its
    inputs bit for bit, the others as they were."""
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    ff = FFModel(FFConfig(batch_size=2, ledger="off",
                          computation_mode=CompMode.INFERENCE))
    x = ff.create_tensor((2, 12, E), DataType.FLOAT, name="x")
    ff.gated_delta_net(x, num_heads=heads, key_dim=dk, value_dim=dv,
                       name="gdn")
    ff.compile(optimizer=None, loss_type=None, metrics=[])
    op = [o for o in ff.compiled.ops if o.name == "gdn"][0]
    c, r = op.channels, 19
    assert gd.tails_supported((r, 3 * c), dtype, c) == (c % 128 == 0)
    assert not gd.tails_supported((r, 3 * c), jnp.int8, c)
    if c % 128:
        return
    rng = np.random.default_rng(c)
    w = jnp.asarray(rng.normal(size=(4, c)), dtype)
    tails = jnp.asarray(rng.normal(size=(r, 3 * c)), dtype)
    new = jnp.asarray(rng.normal(size=(r, c)), dtype)
    live = jnp.asarray(rng.integers(0, 2, size=r).astype(bool))
    window = jnp.concatenate([tails.reshape(r, 3, c), new[:, None]], axis=1)
    want = jax.jit(op.convolve)({"conv": w}, window)[:, 0]
    got, stepped = jax.jit(gd.tails_step)(tails, live, new, w)
    assert got.shape == (r, c) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    assert stepped.dtype == tails.dtype
    np.testing.assert_array_equal(
        np.asarray(stepped.astype(jnp.float32)),
        np.asarray(jnp.where(live[:, None], window[:, 1:].reshape(r, -1),
                             tails).astype(jnp.float32)))


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


# ---- the whole-sequence kernel ------------------------------------------------

def _hard(rng, b, s, h, dk, dv):
    """Keys of a chunk nearly parallel, beta 1.9-2.0, alpha 0.99-1.0: the
    unit-lower system's strict part has entries near 2 all over."""
    q, k, v, g, beta = _qkv(rng, b, s, h, dk, dv)
    k = rng.normal(size=(b, 1, h, dk)) + 0.05 * k
    k = (k / np.linalg.norm(k, axis=-1, keepdims=True)).astype(np.float32)
    g = np.log(rng.uniform(0.99, 1.0, size=(b, s, h))).astype(np.float32)
    beta = rng.uniform(1.9, 2.0, size=(b, s, h)).astype(np.float32)
    return q, k, v, g, beta


# (batch, tokens, heads, d_k, d_v, a state before the sequence, the draw)
CHUNKS_CASES = {
    "published-widths": (1, 128, 30, 96, 192, False, _qkv),
    "no-multiple-of-64": (1, 150, 4, 32, 64, False, _qkv),
    "behind-a-state": (1, 200, 6, 96, 192, True, _qkv),  # a group and a half
    "two-rows": (2, 70, 2, 64, 128, True, _qkv),
    "ill-conditioned": (1, 256, 4, 32, 64, True, _hard),
}


@pytest.mark.parametrize("case", CHUNKS_CASES)
def test_chunks_kernel_interpreted_is_the_scan_and_the_recurrence(
        monkeypatch, case):
    """The whole-sequence kernel against ``chunked_delta_rule`` and
    against the recurrence token by token (float64, and the one-token
    jnp form in float32); on the ill-conditioned draw it stays as close
    to the recurrence as the scan's row substitution does."""
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    b, s, h, dk, dv, behind, draw = CHUNKS_CASES[case]
    rng = np.random.default_rng(len(case))
    q, k, v, g, beta = draw(rng, b, s, h, dk, dv)
    state = (rng.normal(size=(b, h, dk, dv)).astype(np.float32) if behind
             else np.zeros((b, h, dk, dv), np.float32))
    assert gd.chunks_supported(s, h, dk, dv, jnp.float32)
    args = tuple(map(jnp.asarray, (q, k, v, g, beta, state)))
    got, end = gd.gated_delta_chunks(*args)
    scan, scan_end = chunked_delta_rule(*args)
    want, want_end = _by_hand(q, k, v, g, beta, state)
    scale, end_scale = np.abs(want).max(), np.abs(want_end).max()
    tol = 2e-4 if draw is _hard else 2e-5
    assert np.abs(got - want).max() < tol * scale
    assert np.abs(end - want_end).max() < tol * end_scale
    assert np.abs(got - scan).max() < tol * scale
    assert np.abs(end - scan_end).max() < tol * end_scale
    # no further from the truth than a few times the scan's own distance
    assert np.abs(got - want).max() < max(
        4 * np.abs(scan - want).max(), 2e-6 * scale)
    # the one-token form over the arena's layout, float32
    lanes = jnp.moveaxis(args[5], 1, 2).reshape(b, dk, h * dv)
    for t in range(min(s, 70)):
        o, lanes = gd.delta_rule_step(lanes, q[:, t], k[:, t], v[:, t],
                                      np.exp(g[:, t]), beta[:, t])
        assert np.abs(o - got[:, t]).max() < tol * scale, t


@pytest.mark.parametrize("b,s,h,dk,dv", [(1, 128, 30, 96, 192),
                                        (2, 150, 4, 32, 64)])
def test_fused_rule_makes_the_norms_on_either_side_as_the_scan_rule_does(
        monkeypatch, b, s, h, dk, dv):
    """What a layer runs between its convolution and its gate: q and k
    as the convolution wrote them, flat, made unit a head in the kernel,
    and o RMS-normalised a head times the gain on its way out, against
    the same in jnp around ``chunked_delta_rule``."""
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    rng = np.random.default_rng(s)
    q, k, v, g, beta = _qkv(rng, b, s, h, dk, dv)
    q, k = 3.0 * q, 0.3 * k * rng.uniform(0.5, 2.0, size=(b, s, h, 1))
    flat = [jnp.asarray(a.reshape(b, s, -1), jnp.float32) for a in (q, k, v)]
    rest = (jnp.asarray(g), jnp.asarray(beta),
            jnp.asarray(rng.normal(size=(b, h, dk, dv)).astype(np.float32)),
            jnp.asarray(rng.uniform(0.5, 1.5, size=(dv,)).astype(np.float32)))
    got, end = op_mod.fused_rule(1e-6, *flat, *rest)
    want, want_end = op_mod.scan_rule(1e-6, *flat, *rest)
    assert got.shape == want.shape == (b, s, h * dv)
    assert np.abs(got - want).max() < 2e-5 * np.abs(want).max()
    assert np.abs(end - want_end).max() < 2e-5 * np.abs(want_end).max()


def test_a_series_in_powers_of_the_system_fails_where_the_kernel_does_not():
    """Why the kernel substitutes: ``(I - L)(I + L^2)(I + L^4)...`` is
    ``(I + L)^-1`` on paper and, on the ill-conditioned draw, float32
    noise; the kernel's doubling blocks invert the same tile to float32's
    accuracy."""
    rng = np.random.default_rng(7)
    _, k, _, g, beta = _hard(rng, 1, gd.ROWS, 1, 32, 64)
    k, g, beta = k[0, :, 0], g[0, :, 0], beta[0, :, 0]
    gc = np.cumsum(g.astype(np.float64))
    idx = np.arange(gd.ROWS)
    low = np.where(idx[:, None] > idx[None, :], beta[:, None]
                   * (k.astype(np.float64) @ k.T)
                   * np.exp(gc[:, None] - gc[None, :]), 0.0)
    want = np.linalg.inv(np.eye(gd.ROWS) + low)
    got, = gd._unit_lower_inverses([jnp.asarray(low, jnp.float32)],
                                   gd._tile_masks(gd.ROWS))
    assert np.abs(got - want).max() < 1e-4 * np.abs(want).max()
    l32 = low.astype(np.float32)
    series, power = np.eye(gd.ROWS, dtype=np.float32) - l32, l32 @ l32
    with np.errstate(over="ignore", invalid="ignore"):
        while np.abs(power).max() > 0 and np.isfinite(power).all():
            series, power = series @ (np.eye(gd.ROWS, dtype=np.float32)
                                      + power), power @ power
    assert not np.abs(series - want).max() < 1.0 * np.abs(want).max()


def test_chunks_kernel_carries_its_state_into_the_next_call(monkeypatch):
    """Whole = the first half, then the second from the state it left."""
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    rng = np.random.default_rng(9)
    args = tuple(map(jnp.asarray, _qkv(rng, 1, 300, 2, 64, 128)))
    zero = jnp.zeros((1, 2, 64, 128))
    whole, end = gd.gated_delta_chunks(*args, zero)
    cut = 137
    first, mid = gd.gated_delta_chunks(*(a[:, :cut] for a in args), zero)
    second, end2 = gd.gated_delta_chunks(*(a[:, cut:] for a in args), mid)
    both = jnp.concatenate([first, second], axis=1)
    assert np.abs(both - whole).max() < 2e-5 * np.abs(whole).max()
    assert np.abs(end - end2).max() < 2e-5 * np.abs(end).max()


# ---- the whole-sequence kernel with a decay a key channel ---------------------

def _channel_draw(rng, b, s, h, dk, dv, g_lo=-5.0, g_hi=0.0):
    """Unit q (scaled) and k, gates uniform in ``(g_lo, g_hi)`` a key
    channel (the published bound -5), beta in (0, 1), a state that is
    not zero."""
    q, k, v = (rng.normal(size=(b, s, h, d)).astype(np.float32)
               for d in (dk, dk, dv))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * dk ** 0.5
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g = rng.uniform(g_lo, g_hi, size=(b, s, h, dk)).astype(np.float32)
    beta = rng.uniform(0, 1, size=(b, s, h)).astype(np.float32)
    state = rng.normal(size=(b, h, dk, dv)).astype(np.float32)
    return tuple(map(jnp.asarray, (q, k, v, g, beta, state)))


def _channel_whole(rng, b, s, h, dk, dv, **gates):
    args = _channel_draw(rng, b, s, h, dk, dv, **gates)
    assert gd.chunks_supported(s, h, dk, dv, jnp.float32, True)
    return (gd.gated_delta_chunks(*args),
            op_mod.chunked_channel_rule(*args))


def _channel_ragged(rng):
    """Two rows of 300 positions, the first stopped inside its second
    chunk as ``KimiDeltaAttention.run`` stops it (``g = 0``, ``beta = 0``
    past its length): its state is what its 150 tokens alone leave."""
    q, k, v, g, beta, state = _channel_draw(rng, 2, 300, 2, 64, 128)
    live = (np.arange(300)[None, :] < np.array([150, 300])[:, None])
    g = jnp.where(live[..., None, None], g, 0.0)
    beta = jnp.where(live[..., None], beta, 0.0)
    got = gd.gated_delta_chunks(q, k, v, g, beta, state)
    want = op_mod.chunked_channel_rule(q, k, v, g, beta, state)
    alone = op_mod.chunked_channel_rule(
        *(a[:1, :150] for a in (q, k, v, g, beta)), state[:1])
    assert np.abs(got[1][0] - alone[1][0]).max() \
        < 2e-5 * np.abs(alone[1]).max()
    return got, want


def _channel_in_two_calls(rng):
    """One call, and two with the state handed over at a position no
    chunk and no sub-chunk ends at."""
    *args, state = _channel_draw(rng, 1, 300, 2, 64, 128)
    whole = gd.gated_delta_chunks(*args, state)
    cut = 137
    first, mid = gd.gated_delta_chunks(*(a[:, :cut] for a in args), state)
    second, end = gd.gated_delta_chunks(*(a[:, cut:] for a in args), mid)
    return (jnp.concatenate([first, second], axis=1), end), whole


def _channel_fused(rng):
    """``fused_rule`` against ``scan_rule``: q and k as the convolution
    wrote them, flat, made unit a head in the kernel, and o
    RMS-normalised a head times the gain on its way out."""
    b, s, h, dk, dv = 2, 150, 2, 128, 128
    q, k, v, g, beta, state = _channel_draw(rng, b, s, h, dk, dv)
    q, k = 3.0 * q, 0.3 * k * rng.uniform(0.5, 2.0, size=(b, s, h, 1))
    flat = [jnp.asarray(a, jnp.float32).reshape(b, s, -1) for a in (q, k, v)]
    rest = (g, beta, state, jnp.asarray(
        rng.uniform(0.5, 1.5, size=(dv,)).astype(np.float32)))
    assert op_mod.delta_rule_path(s, h, dk, dv, channel_decay=True) == "kernel"
    got = op_mod.fused_rule(1e-6, *flat, *rest)
    assert got[0].shape == (b, s, h * dv)
    return got, op_mod.scan_rule(1e-6, *flat, *rest)


# each: rng -> ((o, state) of the kernel, (o, state) it is held to)
CHANNEL_CASES = {
    "behind-a-state": lambda rng: _channel_whole(rng, 1, 200, 2, 128, 128),
    "gates-at-the-bound-for-whole-chunks": lambda rng: _channel_whole(
        rng, 1, 256, 2, 128, 128, g_lo=-5.0, g_hi=-5.0),
    "gates-near-one": lambda rng: _channel_whole(
        rng, 1, 200, 2, 128, 128, g_lo=-1e-2, g_hi=-1e-4),
    "no-multiple-of-rows-and-a-row-stopped-inside-a-chunk": _channel_ragged,
    "in-one-call-and-in-two": _channel_in_two_calls,
    "64-heads-of-128-by-128-one-chunk": lambda rng: _channel_whole(
        rng, 1, 70, 64, 128, 128),
    "32-heads-of-128-by-128-two-rows": lambda rng: _channel_whole(
        rng, 2, 130, 32, 128, 128),
    "pairs-of-heads-a-tile": lambda rng: _channel_whole(
        rng, 1, 150, 4, 64, 64),
    "a-group-of-four-and-a-half": lambda rng: _channel_whole(
        rng, 1, 150, 6, 96, 192),
    "fused-rule-makes-the-norms": _channel_fused,
}


@pytest.mark.parametrize("case", CHANNEL_CASES)
def test_channel_chunks_kernel_interpreted_is_the_channel_rule(
        monkeypatch, case):
    """The whole-sequence kernel with a decay a key CHANNEL (the Pallas
    call ``channel_delta_chunks``) against ``chunked_channel_rule``,
    outputs and outgoing state, over the range of each: behind a state,
    with every gate at the published bound for whole chunks (the
    overflow the sub-chunks exist for: nothing but finite numbers), with
    rows that stop inside a chunk, handed over between two calls, at both
    cells' heads and widths, and with the norms made on the head's
    tile."""
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    (got, end), (want, want_end) = CHANNEL_CASES[case](
        np.random.default_rng(len(case)))
    assert got.shape == want.shape and end.shape == want_end.shape
    assert np.isfinite(got).all() and np.isfinite(end).all()
    assert np.abs(got - want).max() < 2e-5 * np.abs(want).max()
    assert np.abs(end - want_end).max() < 2e-5 * np.abs(want_end).max()


def test_channel_chunks_kernel_is_the_token_loop(monkeypatch):
    """Against the recurrence token by token in float64, the state's row
    ``d`` decayed by ``alpha_t[d]``: the kernel is no further from it
    than the jnp form (whose chunks are half as long)."""
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    rng = np.random.default_rng(64)
    q, k, v, g, beta, state = _channel_draw(rng, 1, 200, 2, 64, 128,
                                            g_lo=-3.0)
    st = np.asarray(state, np.float64)
    want = np.zeros(v.shape)
    for t in range(q.shape[1]):
        st = np.exp(np.asarray(g[:, t], np.float64))[..., None] * st
        r = v[:, t] - np.einsum("bhdv,bhd->bhv", st, k[:, t])
        st = st + (np.asarray(beta[:, t])[..., None, None]
                   * np.asarray(k[:, t])[..., None] * r[:, :, None, :])
        want[:, t] = np.einsum("bhdv,bhd->bhv", st, q[:, t])
    got, end = gd.gated_delta_chunks(q, k, v, g, beta, state)
    scan, _ = op_mod.chunked_channel_rule(q, k, v, g, beta, state)
    scale = np.abs(want).max()
    assert np.abs(got - want).max() < 2e-5 * scale
    assert np.abs(end - st).max() < 2e-5 * np.abs(st).max()
    assert np.abs(got - want).max() < max(4 * np.abs(scan - want).max(),
                                          2e-6 * scale)


def _wide_op(batch=2, seq=150):
    """An op at widths the whole-sequence kernel takes (4 heads of 32 and
    64: one group), with gates in the published range."""
    ff = FFModel(FFConfig(batch_size=batch, ledger="off",
                          computation_mode=CompMode.INFERENCE))
    x = ff.create_tensor((batch, seq, E), DataType.FLOAT, name="x")
    ff.gated_delta_net(x, num_heads=4, key_dim=32, value_dim=64,
                       allow_neg_eigval=True, name="gdn")
    ff.compile(optimizer=None, loss_type=None, metrics=[])
    cm = ff.compiled
    op = [o for o in cm.ops if o.name == "gdn"][0]
    rng = np.random.default_rng(11)
    w = {k: jnp.asarray(rng.normal(size=v.shape).astype(np.float32)
                        * (0.3 if v.ndim > 1 else 1.0))
         for k, v in cm.params["gdn"].items()}
    return ff, op, w


def test_op_through_the_kernel_stops_each_row_at_its_true_length(monkeypatch):
    """``GatedDeltaNet.run`` by the path the shapes choose: under the
    interpreter the kernel, whose rows of 70 and of 150 tokens in one
    block of 150 leave the states, tails and outputs the scan leaves
    (``g = 0`` and ``beta = 0`` past a row's length)."""
    _, op, w = _wide_op()
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 150, E)).astype(np.float32))
    lengths = jnp.asarray([70, 150], jnp.int32)
    assert op_mod.delta_rule_path(150, 4, 32, 64) == "scan"
    y0, state0, tail0 = op.whole(w, x, lengths)
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    assert op_mod.delta_rule_path(150, 4, 32, 64) == "kernel"
    y, state, tail = op.whole(w, x, lengths)
    assert np.abs(y[0, :70] - y0[0, :70]).max() < 1e-5 * np.abs(y0).max()
    assert np.abs(y[1] - y0[1]).max() < 1e-5 * np.abs(y0).max()
    assert np.abs(state - state0).max() < 1e-5 * np.abs(state0).max()
    assert np.array_equal(tail, tail0)
    alone, alone_state, _ = op.whole(w, x[:1, :70])
    assert np.abs(state[0] - alone_state[0]).max() \
        < 1e-5 * np.abs(state0).max()
    assert np.abs(y[0, :70] - alone[0]).max() < 1e-5 * np.abs(y0).max()


def test_gradient_through_the_kernel_is_the_scans(monkeypatch):
    """``jax.grad`` through ``GatedDeltaNet.forward`` where the kernel is
    taken: the custom VJP's backward is the jnp form's, so the gradients
    are those of the scan path (to the forward's rounding), and a bare
    ``pallas_call``'s refusal to differentiate never shows."""
    ff, op, w = _wide_op(batch=1, seq=130)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(1, 130, E))
                    .astype(np.float32))

    def loss(w, x):
        return jnp.sum(jnp.square(op.forward(None, [x], w)[0]))

    want = jax.grad(loss, argnums=(0, 1))(w, x)
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    got = jax.grad(loss, argnums=(0, 1))(w, x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.abs(a - b).max() <= 1e-4 * np.abs(b).max() + 1e-6


# what the whole-sequence kernel takes and what it refuses, and why
SUPPORTED = {
    "the cell's widths": ((1536, 30, 96, 192, jnp.float32), True),
    "one chunk": ((128, 30, 96, 192, jnp.float32), True),
    "one chunk of the scan's: its loop is shorter than a padded chunk": (
        (64, 30, 96, 192, jnp.float32), False),
    "the KV calibration's prompt": ((12, 30, 96, 192, jnp.float32), False),
    "heads that fill a tile each": ((256, 3, 128, 128, jnp.float32), True),
    "pairs of heads a tile": ((256, 4, 64, 64, jnp.float32), True),
    "bfloat16: the recurrence is float32": (
        (1536, 30, 96, 192, jnp.bfloat16), False),
    "keys off the sublane tiling": ((256, 4, 36, 64, jnp.float32), False),
    "an odd head shares its value tile with nobody": (
        (256, 3, 64, 64, jnp.float32), False),
    "a group too long to unroll (16 heads of 8)": (
        (256, 16, 8, 64, jnp.float32), False),
    "the toy widths of this file": ((150, H, DK, DV, jnp.float32), False),
}


@pytest.mark.parametrize("why", SUPPORTED)
def test_chunks_kernel_takes_by_shape(monkeypatch, why):
    args, takes = SUPPORTED[why]
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    assert gd.chunks_supported(*args) is takes
    assert op_mod.delta_rule_path(*args) == ("kernel" if takes else "scan")
    # a decay a key channel: the same shapes, by the same rule
    assert gd.chunks_supported(*args, True) is takes
    assert op_mod.delta_rule_path(*args, channel_decay=True) == (
        "kernel" if takes else "scan")
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "off")
    assert not gd.chunks_supported(*args)
    monkeypatch.delenv("FLEXFLOW_TPU_PALLAS")
    assert not gd.chunks_supported(*args)        # the CPU: the scan


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
