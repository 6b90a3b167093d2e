"""Compiled-mode Pallas kernel validation on real TPU hardware.

The hermetic suite (tests/) runs the kernels in the Pallas interpreter on
the virtual CPU mesh; this suite runs them THROUGH MOSAIC on an actual
chip:

    python -m pytest tests_tpu/ -q

The check bodies are the functions ``chip_smoke.py`` calls in its kernels
phase (one process holds the chip, so the smoke calls them in-process
instead of running this suite): each asserts that its program lowered to
a Mosaic custom call and compares with the float32 ``jnp`` reference at
``highest`` matmul precision, at the tolerances written beside
``chip_smoke.FLASH_RANGE_TOL`` / ``MOE_TOL`` / ``PAGED_RANGE_TOL`` with
their reasons. Without a TPU
every test here fails (conftest.py); none skips.
"""

import pytest

import chip_smoke


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize(
    "shape,causal",
    [((2, 128, 2, 64), False), ((2, 512, 16, 64), True),
     ((4, 1024, 16, 64), True),       # the fit cell's own shape
     ((1, 1024, 8, 128), True), ((1, 2048, 20, 64), False)],
)
def test_flash_attention_compiled(shape, causal, dtype):
    """Forward and the one-kernel backward through Mosaic (the check
    requires the counter ``attention.backward.fused``)."""
    chip_smoke.check_flash_attention(shape, causal, dtype, mosaic=True)


@pytest.mark.parametrize(
    "moe", [chip_smoke.GPT2_MEDIUM.moe, (64, 32, 32, 8, 2, 2.0)])
def test_moe_kernels_compiled(moe):
    chip_smoke.check_moe_kernels(moe, mosaic=True)


@pytest.mark.parametrize("window", [1, 4])
@pytest.mark.parametrize("heads", [20, 16])
def test_paged_attention_compiled(heads, window):
    """The offline cell's pool (16 slots, 1025 blocks of 16 tokens, 20
    heads of 64, bfloat16) and GPT-2 medium's heads, the decode step
    and a verify window."""
    chip_smoke.check_paged_attention(16, heads, 64, 16, 64, "bfloat16",
                                     mosaic=True, window=window)


@pytest.mark.parametrize("heads,kv_heads,head_dim,max_blocks",
                         [(8, 2, 128, 72), (32, 8, 64, 144)],
                         ids=["chains", "retrieval"])
def test_paged_attention_grouped_compiled(heads, kv_heads, head_dim,
                                          max_blocks):
    """Grouped heads at the chunk their rows' bytes ask: the chains
    cell's pool (48 slots, 8 query heads on 2 key-value heads of 128,
    tables of 72 blocks of 64 tokens, bfloat16: 16 pages an iteration)
    and the retrieval cell's (32 on 8 of 64, tables of 144: 8 pages)."""
    chip_smoke.check_paged_attention(48, heads, head_dim, 64, max_blocks,
                                     "bfloat16", mosaic=True,
                                     kv_heads=kv_heads)


@pytest.mark.parametrize("slots,max_blocks", [(128, 256), (4, 64)])
def test_latent_attention_compiled(slots, max_blocks):
    """The reasoning cell's pool (128 slots of 256 blocks of 16 tokens,
    64 heads over rows of 512 + 64 padded to 640 lanes, bfloat16: 131 KB
    of block tables in SMEM) and a small one."""
    chip_smoke.check_latent_attention(slots, 64, 512, 64, 16, max_blocks,
                                      "bfloat16", mosaic=True)


@pytest.mark.parametrize("slots,heads,groups", [(48, 64, 1), (128, 128, 8)])
def test_ssd_step_compiled(slots, heads, groups):
    """The retrieval cell's Mamba-2 states (48 slots, 64 heads of 64 in
    one group, a state of 128: rows of 2.1 MB) and the agents cell's (128
    slots, 128 heads in 8 groups: rows of 4.2 MB)."""
    chip_smoke.check_ssd_step(slots, heads, 64, 128, groups, mosaic=True)


@pytest.mark.parametrize("slots,channels", [(256, 12288), (32, 11520)])
def test_state_tails_compiled(slots, channels):
    """A delta-rule step's tails through the kernel against the
    slot-order lines, at the long-answers cell's shape (256 slots, rows of
    3 x 12,288 bfloat16) and the documents cell's (32 slots, 3 x
    11,520)."""
    chip_smoke.check_state_tails(slots, channels, mosaic=True)


def test_counted_experts_compiled():
    """The chains cell's expert layer at a decode step (48 slots of one
    pick over 16 experts of 2,048 x 2,048, bfloat16): the shapes say
    dense, the step counts; 6 named take the kernel, 16 the dense form,
    both against the float32 product, one ``conditional`` in the
    program with the Mosaic call in its one arm."""
    chip_smoke.check_counted_experts(48, 16, 2048, 6, mosaic=True)


def test_flash_autotune_on_chip(monkeypatch):
    """Compiled-mode autotune at the fit cell's shape: what training
    runs (bfloat16, causal, forward and backward) through every block
    size that tiles it and through the `xla` path; under forced compiled
    mode a Mosaic refusal of any candidate raises instead of being
    skipped. The rule takes the kernels at this shape, the backward as
    one kernel, and they win."""
    import jax.numpy as jnp

    from flexflow_tpu.kernels import flash_attention as fa

    assert fa.engaged(1024, 1024, 64, True, jnp.bfloat16)   # on `auto`
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "compiled")
    r = fa.autotune(shape=(4, 1024, 16, 64),
                    candidates=((512, 512), (256, 256), (128, 128)))
    print("flash autotune:",
          {k: round(v * 1e3, 3) for k, v in r["blocks"].items()},
          "backward:", r["backward"],
          "xla ms:", round(r["xla_s"] * 1e3, 3), "ratio:", r["xla_ratio"])
    assert len(r["blocks"]) == 3 and r["xla_ratio"] > 1.0
    assert set(r["backward"].values()) == {"fused"}
