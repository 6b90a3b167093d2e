"""Pallas kernel numerics vs the jnp reference paths (interpreter mode).

Mirrors the reference's per-op GPU tests (tests/ops/test_harness.py, which
compares CUDA kernel dumps against numpy/torch references — SURVEY.md §4):
here each Pallas kernel is validated against the framework's own jnp
formulation, in the Pallas interpreter on the hermetic CPU platform.
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")


FORMS = ("fused", "split")


@pytest.fixture
def backward_form(request, monkeypatch):
    """Pin the backward of fused attention to the form the test is
    parametrised with (``indirect``), whatever the shape's rule would
    answer, and check afterwards that every lowering counted it."""
    from flexflow_tpu.kernels import flash_attention as fa
    from flexflow_tpu.obs.metrics import metrics_registry

    form = request.param
    monkeypatch.setattr(fa, "backward_form", lambda *a, **kw: form)
    reg = metrics_registry()
    before = {f: reg.counter(f"attention.backward.{f}").value for f in FORMS}
    yield form
    took = {f for f in FORMS
            if reg.counter(f"attention.backward.{f}").value > before[f]}
    assert took == {form}


def _qkv(b=2, s=128, h=2, d=8, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(size=(b, s, h, d)).astype(np.float32))
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_forward(causal):
    from flexflow_tpu.kernels.flash_attention import flash_attention
    from flexflow_tpu.parallel.ring_attention import single_device_attention

    q, k, v = _qkv()
    scale = q.shape[-1] ** -0.5
    got = flash_attention(q, k, v, causal=causal, scale=scale)
    want = single_device_attention(q, k, v, causal, scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("backward_form", FORMS, indirect=True)
@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_grads(causal, backward_form):
    from flexflow_tpu.kernels.flash_attention import flash_attention
    from flexflow_tpu.parallel.ring_attention import single_device_attention

    q, k, v = _qkv(b=1, s=64, h=2, d=8, seed=1)
    scale = q.shape[-1] ** -0.5
    tgt = jnp.asarray(np.random.default_rng(2).normal(size=q.shape), jnp.float32)

    def loss_fa(q, k, v):
        return jnp.sum((flash_attention(q, k, v, causal=causal, scale=scale) - tgt) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum((single_device_attention(q, k, v, causal, scale) - tgt) ** 2)

    g_fa = jax.grad(loss_fa, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_fa, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4, err_msg=f"d{name}")


@pytest.mark.parametrize("causal", [False, True])
def test_sharded_flash_attention_matches_reference(causal):
    """shard_map composition: the kernel over a data x model mesh equals
    the unsharded jnp attention (this is the path dp x tp configs take)."""
    from jax.sharding import Mesh
    from flexflow_tpu.kernels.flash_attention import (
        sharded_flash_attention, sharded_supported)
    from flexflow_tpu.parallel.ring_attention import single_device_attention

    q, k, v = _qkv(b=4, s=64, h=4, d=8)
    scale = q.shape[-1] ** -0.5
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("data", "model"))
    assert sharded_supported(q.shape, k.shape, mesh, "data", "model")
    got = sharded_flash_attention(q, k, v, mesh, "data", "model",
                                  causal=causal, scale=scale)
    want = single_device_attention(q, k, v, causal, scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("backward_form", FORMS, indirect=True)
def test_sharded_flash_attention_grads(backward_form):
    """Differentiated through ``shard_map``: each device's block takes
    the backward its own (per-device) shape is given, one kernel or
    two, and the gradients equal the unsharded reference's."""
    from jax.sharding import Mesh
    from flexflow_tpu.kernels.flash_attention import sharded_flash_attention
    from flexflow_tpu.parallel.ring_attention import single_device_attention

    q, k, v = _qkv(b=2, s=64, h=4, d=8, seed=6)
    scale = q.shape[-1] ** -0.5
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
    got = jax.grad(lambda q, k, v: jnp.sum(sharded_flash_attention(
        q, k, v, mesh, "data", "model", causal=True, scale=scale) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda q, k, v: jnp.sum(single_device_attention(
        q, k, v, True, scale) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4, err_msg=f"d{name}")


def test_attention_op_uses_sharded_kernel_on_mesh(monkeypatch):
    """End-to-end: a dp x tp-compiled model takes the shard_map kernel path
    (outputs must match the jnp path it replaces)."""
    from flexflow_tpu import FFConfig, FFModel, LossType
    from flexflow_tpu.kernels import flash_attention as fa_mod
    from flexflow_tpu.models.transformer import (TransformerConfig,
                                                 build_transformer)
    from flexflow_tpu.runtime.optimizer import SGDOptimizer

    calls = []
    real = fa_mod.sharded_flash_attention
    monkeypatch.setattr(
        fa_mod, "sharded_flash_attention",
        lambda *a, **kw: (calls.append((a[4], a[5])), real(*a, **kw))[1])

    def run(pallas_env):
        import os
        old = os.environ.get("FLEXFLOW_TPU_PALLAS")
        os.environ["FLEXFLOW_TPU_PALLAS"] = pallas_env
        try:
            cfg = TransformerConfig(hidden_size=32, num_heads=4,
                                    num_layers=1, sequence_length=64)
            ff = FFModel(FFConfig(batch_size=4, seed=0,
                                  mesh_shape={"data": 2, "model": 4}))
            x, _ = build_transformer(ff, 4, cfg, tp_axis="model")
            ff.compile(optimizer=SGDOptimizer(lr=0.01),
                       loss_type=LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
                       metrics=[])
            cm = ff.compiled
            rng = np.random.default_rng(0)
            xb = rng.normal(size=(4, 64, 32)).astype(np.float32)
            out = cm.raw_forward(cm.params, jnp.asarray(xb))
            return np.asarray(out)
        finally:
            if old is None:
                os.environ.pop("FLEXFLOW_TPU_PALLAS", None)
            else:
                os.environ["FLEXFLOW_TPU_PALLAS"] = old

    got = run("interpret")   # kernel path via shard_map (interpreter)
    assert calls and calls[0] == ("data", "model"), (
        f"sharded kernel path did not engage (calls={calls})")
    want = run("off")        # jnp einsum path
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_row_gather_and_sum():
    from flexflow_tpu.kernels.moe_kernels import row_gather, row_gather_sum

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(10, 16)).astype(np.float32))
    idx = jnp.asarray([3, 0, 9, 3], jnp.int32)
    scale = jnp.asarray([1.0, 0.0, 2.0, -1.0], jnp.float32)
    got = row_gather(x, idx, scale, interpret=True)
    want = np.asarray(scale)[:, None] * np.asarray(x)[np.asarray(idx)]
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)

    idx2 = jnp.asarray([[1, 2], [0, 0], [9, 4]], jnp.int32)
    w = jnp.asarray([[0.5, 1.5], [1.0, 0.0], [2.0, 1.0]], jnp.float32)
    got2 = row_gather_sum(x, idx2, w, interpret=True)
    want2 = np.einsum("bk,bkd->bd", np.asarray(w), np.asarray(x)[np.asarray(idx2)])
    np.testing.assert_allclose(np.asarray(got2), want2, rtol=1e-6)


def _moe_setup(seed=0, b=16, d=12, n=4, k=2, capacity=6):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
    assign = jnp.asarray(rng.integers(0, n, size=(b, k)), jnp.int32)
    gate = jnp.asarray(rng.uniform(0.1, 1.0, size=(b, k)).astype(np.float32))
    return x, assign, gate, n, k, capacity


def _ref_dispatch(x, assign, n, capacity, k):
    from flexflow_tpu.ops.moe_ops import moe_dispatch_mask

    xk = jnp.repeat(x, k, axis=0)
    disp = moe_dispatch_mask(assign, n, capacity)
    return jnp.einsum("tnc,tf->ncf", disp, xk)


def _ref_combine(rows, assign, gate, n, capacity, k):
    from flexflow_tpu.ops.moe_ops import moe_dispatch_mask

    disp = moe_dispatch_mask(assign, n, capacity)
    comb = disp * gate.reshape(-1)[:, None, None]
    out = jnp.einsum("tnc,ncf->tf", comb, rows)
    return out.reshape(gate.shape[0], k, -1).sum(axis=1)


def test_moe_dispatch_matches_einsum():
    from flexflow_tpu.kernels.moe_kernels import moe_dispatch

    x, assign, gate, n, k, cap = _moe_setup()
    got = moe_dispatch(x, assign, n, cap)
    want = _ref_dispatch(x, assign, n, cap, k)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6)


def test_moe_combine_matches_einsum_and_grads():
    from flexflow_tpu.kernels.moe_kernels import moe_combine, moe_dispatch

    x, assign, gate, n, k, cap = _moe_setup(seed=3)
    rows = _ref_dispatch(x, assign, n, cap, k)

    got = moe_combine(rows, assign, gate)
    want = _ref_combine(rows, assign, gate, n, cap, k)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6)

    # end-to-end dispatch→combine gradient parity with the einsum path
    def f_pallas(x, gate):
        rows = moe_dispatch(x, assign, n, cap)
        return jnp.sum(moe_combine(rows, assign, gate) ** 2)

    def f_ref(x, gate):
        rows = _ref_dispatch(x, assign, n, cap, k)
        return jnp.sum(_ref_combine(rows, assign, gate, n, cap, k) ** 2)

    gp = jax.grad(f_pallas, argnums=(0, 1))(x, gate)
    gr = jax.grad(f_ref, argnums=(0, 1))(x, gate)
    for a, b, name in zip(gp, gr, ("dx", "dgate")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5, err_msg=name)


def test_moe_model_trains_with_pallas_kernels():
    """End-to-end: the MoE model compiles single-device with the Pallas
    dispatch/combine kernels engaged (interpret mode) and still learns."""
    import jax
    from flexflow_tpu import (FFConfig, FFModel, LossType, MetricsType,
                              make_mesh)
    from flexflow_tpu.runtime.optimizer import AdamOptimizer
    from flexflow_tpu.models.moe import MoeConfig, build_moe_mnist

    bs = 32
    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
    cfg = MoeConfig(input_dim=16, num_exp=4, num_select=2,
                    expert_hidden_size=32)
    ff = FFModel(FFConfig(batch_size=bs, epochs=10, seed=0))
    build_moe_mnist(ff, bs, cfg)
    ff.compile(optimizer=AdamOptimizer(alpha=0.01),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               metrics=[MetricsType.ACCURACY], mesh=mesh)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(128, 16)).astype(np.float32)
    w = rng.normal(size=(16, 10)).astype(np.float32)
    y = np.argmax(x @ w, axis=1).astype(np.int32).reshape(-1, 1)
    hist = ff.fit(x, y, verbose=False)
    assert hist[-1].accuracy > 0.4, hist[-1].accuracy


def _attention_errors(q_shape, k_shape, causal, dtype, block_q, block_k):
    """The kernels' output and three gradients against
    ``single_device_attention`` in float32 on the same (rounded) inputs,
    each as its largest error over the reference's largest magnitude."""
    from flexflow_tpu.kernels.flash_attention import flash_attention
    from flexflow_tpu.parallel.ring_attention import single_device_attention

    rng = np.random.default_rng(3)
    q, w = (jnp.asarray(rng.normal(size=q_shape), dtype) for _ in range(2))
    k, v = (jnp.asarray(rng.normal(size=k_shape), dtype) for _ in range(2))
    scale = q_shape[-1] ** -0.5

    def got(q, k, v):
        out = flash_attention(q, k, v, causal=causal, scale=scale,
                              block_q=block_q, block_k=block_k)
        assert out.dtype == dtype and out.shape == q_shape
        return jnp.sum(out.astype(jnp.float32) * w.astype(jnp.float32)), out

    def want(q, k, v):
        out = single_device_attention(q, k, v, causal, scale)
        return jnp.sum(out * w.astype(jnp.float32)), out

    (_, out), grads = jax.value_and_grad(got, argnums=(0, 1, 2),
                                         has_aux=True)(q, k, v)
    (_, out_ref), grads_ref = jax.value_and_grad(
        want, argnums=(0, 1, 2), has_aux=True)(
            *(a.astype(jnp.float32) for a in (q, k, v)))
    errs = {}
    for name, a, r in zip(("out", "dq", "dk", "dv"), (out, *grads),
                          (out_ref, *grads_ref)):
        assert a.dtype == dtype, name
        a = np.asarray(a.astype(jnp.float32))
        assert np.isfinite(a).all(), name
        errs[name] = float(np.max(np.abs(a - np.asarray(r)))
                           / np.max(np.abs(np.asarray(r))))
    return errs


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("heads,d", [(2, 64), (1, 128)])
@pytest.mark.parametrize("backward_form", FORMS, indirect=True)
def test_flash_blocked_kernels_match_reference(heads, d, causal, dtype, tol,
                                               backward_form):
    """The key-blocked kernels, forward and all three gradients, over
    more than one key block and more than one query block: two heads of
    64 side by side in a lane tile and one head of 128, causal and not,
    float32 operands and bfloat16 ones (float32 statistics either
    way), the backward as one kernel and as two."""
    shape = (1, 256, heads, d)
    errs = _attention_errors(shape, shape, causal, dtype, 64, 128)
    assert max(errs.values()) <= tol, errs


@pytest.mark.parametrize("sq,skv", [(256, 128), (128, 256), (192, 64)])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("backward_form", FORMS, indirect=True)
def test_flash_blocked_kernels_unequal_sequences(sq, skv, causal,
                                                 backward_form):
    """sq != skv, both ways round: the causal mask is
    ``single_device_attention``'s top-left ``tril``, so a long query's
    late rows see every key and a long key's late blocks are never read
    (their dK, dV are zero)."""
    errs = _attention_errors((2, sq, 4, 32), (2, skv, 4, 32), causal,
                             jnp.float32, 64, 64)
    assert max(errs.values()) <= 1e-5, errs


@pytest.mark.parametrize("dtype,tol,same", [(jnp.float32, 1e-5, 1e-6),
                                            (jnp.bfloat16, 2e-2, 8e-3)])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("heads,d", [(2, 64), (1, 128)])
def test_flash_backward_forms_agree(monkeypatch, heads, d, causal, dtype,
                                    tol, same):
    """Three key blocks against three query blocks of 128, so that the
    fused kernel's dQ accumulates across key blocks with a causal tile
    skipped between them (query block 0 sees key block 0 alone, block 2
    all three) and the aligned diagonal tile runs as three quarters:
    each form within the reference's tolerance, and the two within a
    rounding of each other (the same products, another order of
    sums)."""
    from flexflow_tpu.kernels import flash_attention as fa
    from flexflow_tpu.parallel.ring_attention import single_device_attention

    shape = (2, 384, heads, d)
    rng = np.random.default_rng(5)
    q, k, v, w = (jnp.asarray(rng.normal(size=shape), dtype)
                  for _ in range(4))
    scale = d ** -0.5

    def loss(attend):
        return lambda q, k, v: jnp.sum(
            attend(q, k, v).astype(jnp.float32) * w.astype(jnp.float32))

    grads = {}
    for form in FORMS:
        monkeypatch.setattr(fa, "backward_form", lambda *a, _f=form: _f)
        grads[form] = jax.grad(loss(functools.partial(
            fa.flash_attention, causal=causal, scale=scale, block_q=128,
            block_k=128)), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: single_device_attention(
        q, k, v, causal, scale)), argnums=(0, 1, 2))(
            *(a.astype(jnp.float32) for a in (q, k, v)))
    for name, a, b, r in zip(("dq", "dk", "dv"), grads["fused"],
                             grads["split"], want):
        a, b, r = (np.asarray(x.astype(jnp.float32)) for x in (a, b, r))
        top = np.max(np.abs(r))
        assert np.max(np.abs(a - r)) / top <= tol, (name, "fused")
        assert np.max(np.abs(b - r)) / top <= tol, (name, "split")
        assert np.max(np.abs(a - b)) / top <= same, name


@pytest.mark.parametrize("q_shape,heads,want", [
    ((4, 1024, 16 * 64), 16, "fused"),         # the fit cell
    ((1, 8192, 8 * 128), 8, "fused"),
    ((2, 16384, 2 * 64), 2, "fused"),
    ((1, 32768, 2 * 64), 2, "split"),          # dQ whole: 50 MB at 128 lanes
    ((1, 65536, 8 * 128), 8, "split"),
])
def test_flash_backward_form_follows_the_shape(q_shape, heads, want):
    """Which backward runs is read from the shape: one kernel while the
    whole dQ of a (batch, lane tile) fits the VMEM budget beside the
    tiles, two beyond; ``_vmem_bytes`` counts what the whole dQ adds
    (its float32 accumulator, and the output block twice, 12 bytes a
    query and lane) and nothing else changes with the sequence."""
    from flexflow_tpu.kernels import flash_attention as fa

    assert fa.backward_form(q_shape, heads, 512, 512) == want
    d = q_shape[2] // heads
    width, sq = fa._tile_width(heads, d), q_shape[1]
    base = fa._vmem_bytes(512, 512, width, d)
    assert fa._vmem_bytes(512, 512, width, d, whole_dq=sq) - base == (
        12 * width * sq)
    assert (base + 12 * width * sq <= fa.VMEM_BUDGET_BYTES) == (
        want == "fused")


def _two_sequence_axes(closed_jaxpr, seq: int):
    """Shapes of every array in the jaxpr (nested calls, loops and
    custom derivatives included; a ``pallas_call``'s body, which lives
    in VMEM, excluded) that has ``seq`` on two axes or more."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            for var in list(eqn.invars) + list(eqn.outvars):
                shape = getattr(getattr(var, "aval", None), "shape", ())
                if sum(1 for n in shape if n == seq) >= 2:
                    found.append((eqn.primitive.name, tuple(shape)))
            if eqn.primitive.name == "pallas_call":
                continue
            for p in eqn.params.values():
                for sub in (p if isinstance(p, (list, tuple)) else (p,)):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(closed_jaxpr.jaxpr)
    return found


@pytest.mark.parametrize("mode,expect_ss", [("interpret", False),
                                            ("off", True)])
def test_toy_gpt_step_writes_no_two_sequence_axes_array(monkeypatch, mode,
                                                        expect_ss):
    """One training step of a toy GPT: on the fused path its jaxpr holds
    no array with two sequence-length axes, forward or backward (and
    the attention counter says `flash`); with the kernels off the same
    step holds the (B, H, S, S) scores, which is what the search for
    them must be able to find."""
    from flexflow_tpu import (AdamOptimizer, FFConfig, FFModel, LossType,
                              MetricsType)
    from flexflow_tpu.models.gpt import GPTConfig, build_gpt
    from flexflow_tpu.obs.metrics import metrics_registry

    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", mode)
    seq, batch = 96, 2          # 96 is no other dimension of the model
    reg = metrics_registry()
    before = {p: reg.counter(f"attention.path.{p}").value
              for p in ("flash", "xla")}
    ff = FFModel(FFConfig(batch_size=batch, seed=0, search_cache="off"))
    build_gpt(ff, batch, seq, GPTConfig(vocab_size=61, max_positions=128,
                                        hidden_size=64, num_heads=2,
                                        num_layers=2))
    ff.compile(optimizer=AdamOptimizer(alpha=1e-3),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               metrics=[MetricsType.SPARSE_CATEGORICAL_CROSSENTROPY])
    cm = ff.compiled
    tok = jnp.zeros((batch, seq), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda p, s, rng, tok, pos, lab: cm.train_step(p, s, rng, tok, pos,
                                                       lab))(
        cm.params, cm.opt_state, jax.random.key(0), tok, tok, tok)
    taken = {p for p, v in before.items()
             if reg.counter(f"attention.path.{p}").value > v}
    assert taken == ({"xla"} if expect_ss else {"flash"})
    found = _two_sequence_axes(jaxpr, seq)
    assert bool(found) == expect_ss, found[:5]


def test_flash_autotune_mechanics(monkeypatch):
    """autotune() times what training runs — forward and backward, in
    the dtype given — for each (block_q, block_k) that tiles the
    sequence, names the fastest, and decides nothing: the rule answers
    as before it ran (interpret mode here; tools/flash_crossover.py
    runs it compiled on the chip)."""
    from flexflow_tpu.kernels import flash_attention as fa

    before = [fa.engaged(s, s, 8) for s in (64, 1024)]
    r = fa.autotune(shape=(1, 64, 2, 8), candidates=((16, 16), (32, 64),
                                                     (48, 48)),
                    causal=True, dtype=jnp.bfloat16, iters=1, layers=2)
    assert set(r["blocks"]) == {(16, 16), (32, 64)}   # 48 does not tile 64
    assert all(t > 0 for t in r["blocks"].values())
    assert r["best"] == min(r["blocks"], key=r["blocks"].get)
    # beside each timing, the form of the backward it ran
    assert r["backward"] == {blk: "fused" for blk in r["blocks"]}
    assert [fa.engaged(s, s, 8) for s in (64, 1024)] == before
    assert not hasattr(fa, "load_tune_cache")          # no file, no cache


def test_flash_env_block_override(monkeypatch):
    """Blocks follow the shapes: the first of ``BLOCKS`` that divides a
    sequence, whatever ``FLEXFLOW_FA_BLOCK_Q`` or
    ``FLEXFLOW_FA_TUNE_CACHE`` say (neither is read any more); only the
    explicit arguments override, and the result does not depend on
    them."""
    from flexflow_tpu.kernels import flash_attention as fa

    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "compiled")
    monkeypatch.setenv("FLEXFLOW_FA_BLOCK_Q", "32")
    monkeypatch.setenv("FLEXFLOW_FA_TUNE_CACHE", "/nonexistent/tune.json")
    assert [fa._pick_block(s) for s in (1024, 768, 384, 192, 2048)] == [
        512, 256, 128, None, 512]
    assert not fa.supported((1, 192, 16, 64), (1, 192, 16, 64))
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    assert fa._pick_block(192) == 64        # the interpreter has no tiles
    q, k, v = _qkv(b=1, s=64, h=2, d=8, seed=4)
    a = fa.flash_attention(q, k, v, causal=True)
    b = fa.flash_attention(q, k, v, causal=True, block_q=16, block_k=32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("mode,on_tpu,want", [
    ("auto", False, (False, False)),     # the CPU: every lowering the jnp one
    ("interpret", False, (True, True)),  # numerics tests keep the kernels
    ("compiled", False, (True, True)),   # forced on wherever supported
    ("off", True, (False, False)),       # off wins over everything
    ("auto", True, (True, False)),       # the chip: the rule over shapes
])
def test_flash_win_or_off_policy(monkeypatch, mode, on_tpu, want):
    """Who takes the kernels is read from the shapes (PARITY.md
    "Flash-attention dispatch policy"): on a TPU, sequences from
    ``MIN_SEQ`` up, the fit cell's (1024, 1024, 64) among them;
    shorter ones keep the `xla` path. No file, no recorded tuning."""
    from flexflow_tpu.kernels import flash_attention as fa

    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", mode)
    if on_tpu:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert (fa.engaged(1024, 1024, 64, True, jnp.bfloat16),
            fa.engaged(128, 128, 64, True, jnp.bfloat16)) == want


@pytest.mark.parametrize("q_shape,k_shape,dtype,want", [
    ((4, 1024, 16, 64), (4, 1024, 16, 64), jnp.bfloat16, True),   # the cell
    ((4, 1024, 16, 64), (4, 1024, 16, 64), jnp.float32, True),
    ((1, 8192, 8, 128), (1, 8192, 8, 128), jnp.bfloat16, True),   # S7
    ((1, 65536, 8, 128), (1, 65536, 8, 128), jnp.bfloat16, True),
    ((2, 512, 20, 64), (2, 2048, 20, 64), jnp.bfloat16, True),    # sq != skv
    ((2, 1024, 3, 64), (2, 1024, 3, 64), jnp.bfloat16, False),    # odd heads
    ((2, 1024, 4, 80), (2, 1024, 4, 80), jnp.bfloat16, False),    # straddles
    ((2, 1024, 4, 16), (2, 1024, 4, 16), jnp.bfloat16, False),    # 64 lanes
    ((2, 192, 16, 64), (2, 192, 16, 64), jnp.bfloat16, False),    # no block
    ((2, 1024, 16, 64), (2, 1024, 16, 64), jnp.float16, False),
])
def test_flash_supported_follows_shapes_and_dtype(monkeypatch, q_shape,
                                                  k_shape, dtype, want):
    """What Mosaic would refuse, ``supported()`` refuses first: heads
    that fill no whole lane tile, sequences no block divides, a dtype
    the MXU path does not take. Its VMEM count no longer grows with the
    sequence (d 128 at 8192 and beyond: ROADMAP S7)."""
    from flexflow_tpu.kernels import flash_attention as fa

    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "compiled")
    assert fa.supported(q_shape, k_shape, True, dtype) is want


def test_flash_autotune_records_xla_ratio(monkeypatch):
    """autotune() times the `xla` path (``single_device_attention``,
    both passes, the same dtype) at the same shape and reports the
    ratio the crossover table is made of."""
    from flexflow_tpu.kernels import flash_attention as fa

    r = fa.autotune(shape=(1, 64, 1, 8), candidates=((16, 32),),
                    causal=False, dtype=jnp.float32, iters=1, layers=1)
    # recorded-fields assertion, NOT a wall-clock comparison: the
    # interpreted kernel against XLA:CPU says nothing about either
    assert r["xla_s"] > 0 and r["best"] == (16, 32)
    assert r["xla_ratio"] == round(r["xla_s"] / r["blocks"][(16, 32)], 4)


def test_moe_kernels_supported_counts_smem_operands():
    """The kernels prefetch two 4-byte operands per pick or per slot into
    a 1 MiB SMEM: the zoo MoE fits; 32k tokens (refused by Mosaic on a
    v5e) do not, and take the einsum path instead."""
    from flexflow_tpu.kernels.moe_kernels import supported

    assert supported(64, 2, 5, 52)
    assert supported(24576, 2, 8, 12288)          # 98,304 slots: the edge
    assert not supported(32768, 2, 8, 16384)
