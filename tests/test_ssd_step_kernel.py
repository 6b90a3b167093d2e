"""The Mamba-2 decode-step kernel (``kernels/ssd_step.py``) in the Pallas
interpreter against ``ops/mamba2.py`` ``ssd_step`` on the gathered rows:
the stepped rows and their ``y``, the rows no slot names bit for bit, the
null row under any number of idle slots, and what ``supported()``
refuses."""

import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.kernels import ssd_step
from flexflow_tpu.ops import mamba2

# heads, head_dim, state, groups: Granite's shape class (one group) and
# Nemotron's (several groups, more heads), each lane tile within a group
GRANITE = (4, 32, 16, 1)
NEMOTRON = (8, 64, 24, 4)


def _states(rows, heads, head_dim):
    """Arena rows (.., N, H P) as the op holds a state: (.., H, P, N)."""
    return np.moveaxis(np.asarray(rows).reshape(
        rows.shape[:-1] + (heads, head_dim)), -3, -1)


@pytest.mark.parametrize("slot_rows", [
    [1, 2, 3, 4, 5],            # all slots live
    [4, 0, 2, 0, 6],            # some idle: row 0 named more than once
    [0, 0, 0, 0, 0],            # nobody live
    [6, 1, 0, 5, 3],            # rows in no order
], ids=["live", "idle", "all_idle", "unordered"])
@pytest.mark.parametrize("shape", [GRANITE, NEMOTRON],
                         ids=["granite", "nemotron"])
def test_kernel_is_the_plain_step_on_the_named_rows(monkeypatch, shape,
                                                    slot_rows):
    """``y`` and the stepped rows within 1e-6 of range of ``ssd_step`` on
    the gathered rows; every row no slot names, and the null row however
    many idle slots name it, bit for bit what it was; an idle slot reads
    ``y = 0``."""
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    h, p, s, g = shape
    rng = np.random.default_rng(0)
    n, rows = len(slot_rows), 7
    arena = jnp.asarray(rng.normal(size=(rows, s, h * p)), jnp.float32)
    named = jnp.asarray(slot_rows, jnp.int32)
    u = jnp.asarray(rng.normal(size=(n, h, p)), jnp.float32)
    decay = jnp.asarray(rng.uniform(0.5, 1.0, (n, h)), jnp.float32)
    bm, cm = (jnp.asarray(rng.normal(size=(n, g, s)), jnp.float32)
              for _ in range(2))
    assert ssd_step.supported(n, h, p, s, g, arena.shape, arena.dtype)
    y, new = ssd_step.ssd_step_decode(arena, named, u, decay, bm, cm)
    want_y, want = mamba2.ssd_step(
        jnp.asarray(_states(arena[named], h, p)), u, decay, bm, cm)
    y, new, want_y, want = map(np.asarray, (y, new, want_y, want))
    for i, r in enumerate(slot_rows):
        if r == 0:
            assert not y[i].any()
            continue
        assert np.abs(y[i] - want_y[i]).max() <= 1e-6 * np.abs(want_y).max()
        assert np.abs(_states(new[r], h, p) - want[i]).max() \
            <= 1e-6 * np.abs(want).max()
    for r in set(range(rows)) - (set(slot_rows) - {0}):
        assert np.array_equal(new[r], np.asarray(arena)[r]), r
    # the jnp form over the same arena agrees on what a step leaves
    y_rows, new_rows = mamba2.ssd_step_rows(arena, named, u, decay, bm, cm)
    live = np.asarray(slot_rows) != 0
    assert np.allclose(np.asarray(y_rows)[live], y[live], atol=1e-5)
    assert np.allclose(np.asarray(new_rows), new, atol=1e-6)


@pytest.mark.parametrize("why, mode, heads, head_dim, state, groups, dtype", [
    ("taken", "interpret", 4, 32, 16, 1, jnp.float32),
    ("a bfloat16 arena", "interpret", 4, 32, 16, 1, jnp.bfloat16),
    ("a state of no whole sublane tile", "interpret", 4, 32, 12, 1,
     jnp.float32),
    ("channels of no whole lane tile", "interpret", 3, 32, 16, 1,
     jnp.float32),
    ("a lane tile over two groups", "interpret", 4, 32, 16, 2, jnp.float32),
    ("a row past the fast memory", "interpret", 512, 64, 128, 1,
     jnp.float32),
    ("kernels off", "off", 4, 32, 16, 1, jnp.float32),
    ("the CPU", "auto", 4, 32, 16, 1, jnp.float32),
])
def test_supported_refusals(monkeypatch, why, mode, heads, head_dim, state,
                            groups, dtype):
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", mode)
    shape = (7, state, heads * head_dim)
    assert ssd_step.supported(5, heads, head_dim, state, groups, shape,
                              dtype) == (why == "taken")
    # and an arena stored another way is not this kernel's
    assert not ssd_step.supported(5, heads, head_dim, state, groups,
                                  (7, heads, head_dim, state), dtype)


@pytest.mark.parametrize("family", ["granite", "nemotron"])
def test_a_served_model_steps_its_states_by_the_kernel(monkeypatch, family):
    """``chip_smoke.ssm_toy_families``' two models (a Mamba-2 and an
    attention block at widths both kinds' kernels take) served through
    ``GenerationInstance``, two requests of different lengths on three
    slots so that a slot is always idle: with
    the kernels interpreted the decode step reads every cache in place
    (``attention_path`` ``kernel``) and each lowering counts
    ``ssm_step.path.kernel``; with them off it counts ``.rows``; the
    greedy tokens are the same either way."""
    import chip_smoke
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.ffconst import CompMode
    from flexflow_tpu.obs.metrics import metrics_registry
    from flexflow_tpu.serving import GenerationInstance

    slots, vocab, max_length = 3, chip_smoke.SSM_TOY["vocab"], \
        chip_smoke.SSM_TOY["max_length"]
    build, cfg, how = chip_smoke.ssm_toy_families()[family]
    reg = metrics_registry()
    rng = np.random.default_rng(3)
    reqs = [(rng.integers(0, vocab, n).astype(np.int32), m)
            for n, m in ((30, 6), (12, 4))]
    outs = {}
    for mode, path, decode in (("interpret", "kernel", "kernel"),
                               ("off", "rows", "gather")):
        monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", mode)
        before = {p: reg.counter(f"ssm_step.path.{p}").value
                  for p in ("kernel", "rows")}
        ff = FFModel(FFConfig(batch_size=slots, seed=0, ledger="off",
                              search_cache="off",
                              computation_mode=CompMode.INFERENCE))
        build(ff, slots, max_length, cfg)
        ff.compile(optimizer=None, loss_type=None, metrics=[])
        inst = GenerationInstance(ff, decode_slots=slots, block_size=16,
                                  max_length=max_length, **how)
        try:
            futures = [inst.generate_async(p, m, temperature=0.0)
                       for p, m in reqs]
            outs[mode] = [f.result(timeout=600) for f in futures]
            said = inst.stats()["kv"]["attention_path"]
            assert said["decode"] == decode
            # a table of 6 blocks of 16 is covered by a lane tile of
            # tokens: the paged kernel's chunk, where it is the kernel
            assert said["decode_chunk_tokens"] == (
                128 if decode == "kernel" else None)
        finally:
            inst.stop()
        took = {p for p, v in before.items()
                if reg.counter(f"ssm_step.path.{p}").value > v}
        assert took == {path}
    for a, b in zip(outs["interpret"], outs["off"]):
        assert np.array_equal(a, b)
