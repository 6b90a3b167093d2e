"""Pallas kernel numerics vs the jnp reference paths (interpreter mode).

Mirrors the reference's per-op GPU tests (tests/ops/test_harness.py, which
compares CUDA kernel dumps against numpy/torch references — SURVEY.md §4):
here each Pallas kernel is validated against the framework's own jnp
formulation, in the Pallas interpreter on the hermetic CPU platform.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")


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


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_grads(causal):
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


def test_flash_autotune_mechanics(monkeypatch):
    """autotune() picks a block size, caches it per shape, persists and
    reloads (interpret mode here; the TPU-gated smoke in tests_tpu/ runs
    it compiled)."""
    import json

    from flexflow_tpu.kernels import flash_attention as fa

    # isolate from the developer's real tuning env: interpret-mode winners
    # must never leak into a hardware cache file
    monkeypatch.delenv("FLEXFLOW_FA_TUNE_CACHE", raising=False)
    monkeypatch.delenv("FLEXFLOW_FA_BLOCK_Q", raising=False)

    results = fa.autotune(shape=(1, 64, 1, 8), candidates=(16, 32, 64),
                          iters=1)
    assert results and set(results) <= {16, 32, 64}
    best = min(results, key=results.get)
    assert fa.default_block_q(64, 64, 8) == best
    import tempfile, os
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "tune.json")
        fa.autotune(shape=(1, 64, 1, 8), candidates=(16, 32), iters=1,
                    cache_path=p)
        fa._TUNE_CACHE.clear()
        assert fa.load_tune_cache(p) == 1
        assert fa.default_block_q(64, 64, 8) in (16, 32)
    fa._TUNE_CACHE.clear()


def test_flash_env_block_override(monkeypatch):
    from flexflow_tpu.kernels import flash_attention as fa

    monkeypatch.setenv("FLEXFLOW_FA_BLOCK_Q", "32")
    assert fa.default_block_q(512, 512, 64) == 32


def test_flash_win_or_off_policy(monkeypatch):
    """Round-5 dispatch policy (PARITY.md §flash-attention): on `auto`
    the kernel engages only at shapes where a recorded autotune beat XLA
    fused; `compiled` forces it; `off` wins over everything; legacy
    bare-int cache entries carry no win evidence."""
    from flexflow_tpu.kernels import flash_attention as fa

    monkeypatch.delenv("FLEXFLOW_FA_TUNE_CACHE", raising=False)
    monkeypatch.delenv("FLEXFLOW_FA_BLOCK_Q", raising=False)
    fa._TUNE_CACHE.clear()

    # no evidence: auto-on-TPU must NOT engage (pretend we're on TPU by
    # forcing mode through the env is 'compiled' which is force — so
    # check the auto path on this CPU host where pallas_mode() is None)
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "auto")
    assert not fa.engaged(512, 512, 64)

    # interpret mode: numerics tests keep exercising the kernel
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    assert fa.engaged(512, 512, 64)

    # forced: engages regardless of evidence
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "compiled")
    assert fa.engaged(512, 512, 64)

    # off beats forced-adjacent states
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "off")
    assert not fa.engaged(512, 512, 64)

    # proven(): ratio >= 1.0 required; legacy int entries prove nothing
    fa._TUNE_CACHE[(512, 512, 64, False)] = {"block_q": 128,
                                             "xla_ratio": 1.07}
    assert fa.proven(512, 512, 64)
    fa._TUNE_CACHE[(512, 512, 64, False)] = {"block_q": 128,
                                             "xla_ratio": 0.98}
    assert not fa.proven(512, 512, 64)
    fa._TUNE_CACHE[(512, 512, 64, False)] = {"block_q": 128,
                                             "xla_ratio": None}
    assert not fa.proven(512, 512, 64)
    fa._TUNE_CACHE.clear()


def test_flash_autotune_records_xla_ratio(monkeypatch, tmp_path):
    """autotune() times XLA fused at the same shape and persists the
    ratio; load_tune_cache round-trips both new-dict and legacy-int
    formats."""
    import json

    from flexflow_tpu.kernels import flash_attention as fa

    monkeypatch.delenv("FLEXFLOW_FA_TUNE_CACHE", raising=False)
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    fa._TUNE_CACHE.clear()
    p = str(tmp_path / "tune.json")
    fa.autotune(shape=(1, 64, 1, 8), candidates=(16, 32), iters=1,
                cache_path=p)
    entry = fa._TUNE_CACHE[(64, 64, 8, False)]
    assert entry["block_q"] in (16, 32)
    # recorded-fields assertion, NOT a wall-clock comparison: asserting
    # the interpret-mode kernel loses to XLA (< 1.0) was timing-flaky
    # under full-suite load on a saturated host. What matters is that
    # the ratio was measured and persisted, and that engagement asks
    # proven() (which needs ratio >= 1.0) rather than mere presence.
    assert isinstance(entry["xla_ratio"], float) and entry["xla_ratio"] > 0
    assert fa.proven(64, 64, 8) == (entry["xla_ratio"] >= 1.0)
    with open(p) as f:
        data = json.load(f)
    data["128x128x8x0"] = 64  # legacy bare-int entry
    with open(p, "w") as f:
        json.dump(data, f)
    fa._TUNE_CACHE.clear()
    assert fa.load_tune_cache(p) == 2
    assert fa._TUNE_CACHE[(64, 64, 8, False)]["block_q"] == entry["block_q"]
    assert fa._TUNE_CACHE[(128, 128, 8, False)] == {"block_q": 64,
                                                    "xla_ratio": None}
    fa._TUNE_CACHE.clear()


def test_moe_kernels_supported_counts_smem_operands():
    """The kernels prefetch two 4-byte operands per pick or per slot into
    a 1 MiB SMEM: the zoo MoE fits; 32k tokens (refused by Mosaic on a
    v5e) do not, and take the einsum path instead."""
    from flexflow_tpu.kernels.moe_kernels import supported

    assert supported(64, 2, 5, 52)
    assert supported(24576, 2, 8, 12288)          # 98,304 slots: the edge
    assert not supported(32768, 2, 8, 16384)
