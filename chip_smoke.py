"""chip_smoke.py — does the system still start on the chip?

``python chip_smoke.py`` (no arguments, one process, from the repo root)
drives the two main paths through the entry points a user calls, at the
published widths of GPT-2-medium (``gpt2-medium`` config.json: 24 layers,
hidden 1024, 16 heads, MLP 4096, vocab 50257, 1024 positions), with
random weights made from a seed:

* **kernels** — every Pallas kernel compiled by Mosaic and compared with
  its ``jnp`` reference at ``highest`` matmul precision, forward and
  backward: flash attention at the train phase's own shape, and
  ``moe_dispatch``/``moe_combine`` at the zoo MoE's shape;
* **serve** — ``GenerationInstance`` → scheduler → ``PagedDecoder``
  answering eight overlapping greedy requests, and the paged prefill's
  logits against ``ff.compiled.raw_forward`` for one prompt;
* **train** — builder API → ``FFModel.compile`` → ``fit`` for two epochs
  in bfloat16 with Adam; on more than one device the same phase runs
  twice over a mesh of all devices, once data-parallel and once with the
  Unity search on, and their first losses must agree.

It exits 0 only if every phase passed on a TPU, and prints as its last
line ``{"ok": true, "device": {...}}``. With any other backend it names
what it found and exits non-zero before building a model; there is no
flag, environment variable or code path by which it passes without a
chip. The phases are functions of :class:`SmokeSizes` so that
``tests/test_chip_smoke.py`` drives the same control flow at toy size on
the CPU mesh; the command line takes no size.

Phases run smallest first, so each line's peak device memory (high-water
marks of the process, which the backend cannot reset) is that phase's
own. Everything the run writes goes under ``chiprun_out/``, apart
from the compilation cache (``JAX_COMPILATION_CACHE_DIR`` if set, else
``.jax_cache/`` in the checkout — flexflow_tpu/utils/compile_cache.py).
"""

from __future__ import annotations

import dataclasses
import gc
import json
import math
import os
import sys
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

_ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(_ROOT, "chiprun_out", "chip_smoke")


@dataclasses.dataclass(frozen=True)
class SmokeSizes:
    """Everything about the run that has a size. ``GPT2_MEDIUM`` is what
    ``main`` runs; ``TOY`` is what the CPU test runs."""

    # the language model (models/gpt.py GPTConfig)
    vocab: int
    positions: int
    hidden: int
    heads: int
    layers: int
    mlp_ratio: int
    # train: sequence length, steps per epoch (two epochs), Adam's alpha,
    # and the per-device batch (None = the largest power of two that the
    # device's memory holds, train_batch_that_fits)
    seq: int
    steps_per_epoch: int
    lr: float
    batch_per_device: Optional[int]
    # serve: the instance's max_length and the request mix (prompt and
    # answer lengths drawn from a seed within these bounds)
    max_length: int
    requests: int
    prompt_len: Tuple[int, int]
    new_tokens: Tuple[int, int]
    # kernels: the MoE shape (tokens, d_in, d_out, experts, picks, alpha);
    # flash attention runs at (train batch, seq, heads, hidden // heads)
    moe: Tuple[int, int, int, int, int, float]


GPT2_MEDIUM = SmokeSizes(
    vocab=50257, positions=1024, hidden=1024, heads=16, layers=24,
    mlp_ratio=4, seq=1024, steps_per_epoch=4, lr=2e-4,
    batch_per_device=None, max_length=1024, requests=8,
    prompt_len=(32, 512), new_tokens=(32, 64),
    # models/moe.py MoeConfig at FFConfig's default batch of 64
    moe=(64, 784, 64, 5, 2, 2.0))

TOY = SmokeSizes(
    vocab=96, positions=64, hidden=32, heads=4, layers=2, mlp_ratio=4,
    seq=32, steps_per_epoch=2, lr=1e-2, batch_per_device=1, max_length=32,
    requests=8, prompt_len=(3, 16), new_tokens=(2, 5),
    moe=(16, 12, 8, 4, 2, 2.0))


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# --------------------------------------------------------------------------
# bookkeeping shared by the phases
# --------------------------------------------------------------------------

def _peak_bytes() -> Dict[str, Optional[int]]:
    """High-water marks of device 0. On a TPU a running program's
    temporaries are counted under ``peak_bytes_reserved``, beside the
    live arrays of ``peak_bytes_in_use``; the two regions share the
    device's memory."""
    import jax

    stats = jax.devices()[0].memory_stats() or {}  # None on the CPU backend
    return {k: stats.get(k)
            for k in ("peak_bytes_in_use", "peak_bytes_reserved")}


class _Phase:
    """Times one phase and reads what the compiler did during it."""

    def __init__(self, name: str):
        from flexflow_tpu.utils.compile_cache import compile_stats

        self.name = name
        self._stats = compile_stats
        self._c0 = compile_stats()
        self._t0 = time.perf_counter()
        # set by built(): the wall time of building the model and its
        # programs, and the compile seconds already inside it
        self._build_s = self._build_compile_s = 0.0

    def built(self) -> None:
        """The phase has built its model; what follows is the workload."""
        self._build_s = time.perf_counter() - self._t0
        self._build_compile_s = self._stats()["compile_s"] - self._c0["compile_s"]

    def report(self, **facts) -> Dict:
        c1 = self._stats()
        d = {k: c1[k] - self._c0[k] for k in c1}
        rec = {
            "phase": self.name,
            "wall_s": round(time.perf_counter() - self._t0, 2),
            # set-up = the build (graph, search, parameter init, tracing
            # and whatever it compiled) + the seconds JAX spent in XLA
            # compile requests after it (the first dispatch of each
            # program), persistent-cache reads included
            "setup_s": round(self._build_s + d["compile_s"]
                             - self._build_compile_s, 2),
            "xla_compile_s": round(d["compile_s"], 2),
            "compiles": int(d["compiles"]),
            "cache_hits": int(d["cache_hits"]),
            "cache_misses": int(d["cache_misses"]),
            **_peak_bytes(),
            **facts,
        }
        print("[chip_smoke] " + " ".join(f"{k}={v}" for k, v in rec.items()),
              flush=True)
        return rec


def _gpt_config(sizes: SmokeSizes):
    from flexflow_tpu.models.gpt import GPTConfig

    return GPTConfig(vocab_size=sizes.vocab, max_positions=sizes.positions,
                     hidden_size=sizes.hidden, num_heads=sizes.heads,
                     num_layers=sizes.layers, mlp_ratio=sizes.mlp_ratio)


def _ff_config(**kw):
    """FFConfig for a smoke phase: no strategy cache, and the run ledger
    under the output directory instead of the cwd's .ffcache/."""
    from flexflow_tpu import FFConfig

    return FFConfig(seed=0, compute_dtype="bfloat16", search_cache="off",
                    ledger_dir=os.path.join(OUT_DIR, "ledger"), **kw)


def gpt_param_count(sizes: SmokeSizes) -> int:
    h, v = sizes.hidden, sizes.vocab
    block = (4 * h * h + 4 * h            # attention q, k, v, o + biases
             + 2 * sizes.mlp_ratio * h * h + (sizes.mlp_ratio + 1) * h
             + 4 * h)                     # two LayerNorms
    return (v * h + sizes.positions * h + sizes.layers * block + 2 * h
            + h * v)


def train_bytes_estimate(sizes: SmokeSizes, batch: int) -> int:
    """Estimated peak training footprint of one device holding ``batch``
    samples, in bytes:

    * state: 12 per parameter (float32 weight, Adam m and v; each
      gradient is consumed by its update and never held beside them) —
      4.9 GB for GPT-2-medium with the zoo model's untied vocabulary head
      (406 M parameters);
    * per sample, the step program's temporaries: the unfused attention
      probabilities in bfloat16, ``layers * heads * seq^2 * 2`` (0.8 GB);
      the logits three times over in float32 (the logits, their
      log-softmax, its gradient: 0.6 GB); two ``seq * hidden`` bfloat16
      activations per block.

    The per-block count is fitted, not derived: on a v5e the train phase
    measured 8.3 GB at batch 2 and 11.3 GB at batch 4
    (``peak_bytes_in_use + peak_bytes_reserved``; my chip run, PR 21)
    against 7.9 and 11.0 from this estimate. The phase prints the peak
    it measured beside the estimate."""
    per_sample = (sizes.layers * sizes.heads * sizes.seq ** 2 * 2
                  + 3 * sizes.seq * sizes.vocab * 4
                  + sizes.layers * 2 * sizes.seq * sizes.hidden * 2)
    return 12 * gpt_param_count(sizes) + batch * per_sample


def train_batch_that_fits(sizes: SmokeSizes, hbm_bytes: int) -> int:
    """Largest power-of-two per-device batch whose
    :func:`train_bytes_estimate` fits nine tenths of ``hbm_bytes`` (the
    rest is left to XLA's own temporaries)."""
    batch = 1
    if train_bytes_estimate(sizes, batch) > 0.9 * hbm_bytes:
        raise SmokeFailure(
            f"one sample does not fit: an estimated "
            f"{train_bytes_estimate(sizes, 1) / 1e9:.2f} GB > 0.9 * "
            f"{hbm_bytes / 1e9:.2f} GB")
    while train_bytes_estimate(sizes, 2 * batch) <= 0.9 * hbm_bytes:
        batch *= 2
    return batch


def resolve_batch_per_device(sizes: SmokeSizes) -> int:
    if sizes.batch_per_device is not None:
        return sizes.batch_per_device
    import jax

    stats = jax.devices()[0].memory_stats()
    _require(stats is not None and "bytes_limit" in stats,
             "the device reports no memory limit to size the batch from")
    return train_batch_that_fits(sizes, int(stats["bytes_limit"]))


# --------------------------------------------------------------------------
# kernels
# --------------------------------------------------------------------------

# Tolerances, kernel against the float32 reference at `highest` precision.
# Flash attention: the largest error of each output over the largest
# magnitude of its reference. Mosaic's default contract precision, like
# XLA's, feeds the MXU bfloat16 passes even for float32 operands, so every
# product carries one bfloat16 rounding of an operand (2^-8 = 0.4 % of its
# value) whatever the input dtype; bfloat16 inputs add the rounding of the
# outputs themselves (2^-9). Measured on a v5e at (4, 1024, 16, 64): 0.20 %
# to 0.73 % of range (the largest: dk with float32 inputs); the bound is
# under three times that.
FLASH_RANGE_TOL = 2e-2
# The MoE kernels copy rows and form k-term weighted sums in float32
# (measured: exact to 2e-6). The gate gradient is the exception: the
# kernels gather its rows, then a jnp einsum contracts them at the
# backend's default matmul precision (measured 1.2e-4 at 256-wide rows).
MOE_TOL = dict(rtol=1e-5, atol=1e-5)
MOE_DGATE_TOL = dict(rtol=1e-3, atol=1e-3)
# Paged attention: the largest error over the largest magnitude of the
# reference, as for flash attention and for the same reason (one bfloat16
# rounding of an operand a product, and with bfloat16 arenas the
# probabilities rounded to bfloat16 before they meet V).
PAGED_RANGE_TOL = 2e-2


def _assert_mosaic(jitted, *args) -> None:
    """The lowered program must hold a Mosaic custom call: an interpreted
    kernel lowers to plain HLO and would pass a numeric check unnoticed."""
    text = jitted.lower(*args).as_text()
    _require("tpu_custom_call" in text,
             "kernel did not lower to a Mosaic tpu_custom_call")


def check_flash_attention(shape, causal: bool, dtype: str,
                          mosaic: bool) -> Dict[str, float]:
    """Flash attention (forward, and the backward as the one kernel
    every shape checked here is given) against
    ``single_device_attention`` in float32 at ``highest`` precision, on
    the same inputs. Returns each output's largest error as a share of
    its reference's largest magnitude."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.kernels.flash_attention import flash_attention, supported
    from flexflow_tpu.obs.metrics import metrics_registry
    from flexflow_tpu.parallel.ring_attention import single_device_attention

    _require(supported(shape, shape, causal, dtype),
             f"flash_attention.supported() refuses {shape} in {dtype}")
    b, s, h, d = shape
    rng = np.random.default_rng(0)
    q, k, v, w = (jnp.asarray(rng.normal(size=shape).astype(np.float32),
                              dtype) for _ in range(4))
    scale = d ** -0.5

    def kernel_loss(q, k, v):
        out = flash_attention(q, k, v, causal=causal, scale=scale)
        return jnp.sum(out.astype(jnp.float32) * w.astype(jnp.float32)), out

    def ref_loss(q, k, v):
        out = single_device_attention(q, k, v, causal, scale)
        return jnp.sum(out * w.astype(jnp.float32)), out

    got_fn = jax.jit(jax.value_and_grad(kernel_loss, argnums=(0, 1, 2),
                                        has_aux=True))
    reg = metrics_registry()
    before = _path_counts(reg, "attention", ("fused", "split"), "backward")
    if mosaic:
        _assert_mosaic(got_fn, q, k, v)
    (_, out), grads = got_fn(q, k, v)
    took = _paths_taken(reg, "attention", before, "backward")
    _require(took == ["fused"],
             f"flash backward at {shape} took the {'+'.join(took)!r} form")
    with jax.default_matmul_precision("highest"):
        (_, out_ref), grads_ref = jax.jit(jax.value_and_grad(
            ref_loss, argnums=(0, 1, 2), has_aux=True))(
                *(a.astype(jnp.float32) for a in (q, k, v)))
    errs = {}
    for name, a, r in zip(("out", "dq", "dk", "dv"), (out, *grads),
                          (out_ref, *grads_ref)):
        a = np.asarray(a.astype(jnp.float32))
        r = np.asarray(r)
        _require(np.isfinite(a).all(), f"flash {name}: non-finite values")
        errs[name] = float(np.max(np.abs(a - r)) / np.max(np.abs(r)))
        _require(errs[name] <= FLASH_RANGE_TOL,
                 f"flash {name} ({dtype}, {shape}): max error "
                 f"{errs[name]:.2e} of range > {FLASH_RANGE_TOL}")
    return errs


def check_moe_kernels(moe, mosaic: bool) -> Dict[str, float]:
    """``moe_dispatch`` / ``moe_combine`` and their gradients against the
    one-hot einsum formulation (ops/moe_ops.py) at ``highest`` precision."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.kernels.moe_kernels import moe_combine, moe_dispatch
    from flexflow_tpu.ops.moe_ops import expert_capacity, moe_dispatch_mask

    tokens, d_in, d_out, n, k, alpha = moe
    cap = expert_capacity(tokens, k, n, alpha)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(tokens, d_in)).astype(np.float32))
    rows_in = jnp.asarray(rng.normal(size=(n, cap, d_out)).astype(np.float32))
    assign = jnp.asarray(rng.integers(0, n, size=(tokens, k)), jnp.int32)
    gate = jnp.asarray(rng.uniform(0.1, 1.0, size=(tokens, k))
                       .astype(np.float32))

    def ref_dispatch(x):
        disp = moe_dispatch_mask(assign, n, cap)
        return jnp.einsum("tnc,tf->ncf", disp, jnp.repeat(x, k, axis=0))

    def ref_combine(rows, gate):
        disp = moe_dispatch_mask(assign, n, cap)
        out = jnp.einsum("tnc,ncf->tf",
                         disp * gate.reshape(-1)[:, None, None], rows)
        return out.reshape(tokens, k, -1).sum(axis=1)

    def loss(dispatch, combine):
        def f(x, rows, gate):
            return (jnp.sum(dispatch(x) ** 2)
                    + jnp.sum(combine(rows, gate) ** 2))
        return f

    dispatch = jax.jit(lambda x: moe_dispatch(x, assign, n, cap))
    combine = jax.jit(lambda rows, gate: moe_combine(rows, assign, gate))
    grad = jax.jit(jax.grad(loss(lambda x: moe_dispatch(x, assign, n, cap),
                                 lambda r, g: moe_combine(r, assign, g)),
                            argnums=(0, 1, 2)))
    if mosaic:
        _assert_mosaic(dispatch, x)
        _assert_mosaic(combine, rows_in, gate)
        _assert_mosaic(grad, x, rows_in, gate)
    got = (dispatch(x), combine(rows_in, gate), *grad(x, rows_in, gate))
    with jax.default_matmul_precision("highest"):
        want = (jax.jit(ref_dispatch)(x), jax.jit(ref_combine)(rows_in, gate),
                *jax.jit(jax.grad(loss(ref_dispatch, ref_combine),
                                  argnums=(0, 1, 2)))(x, rows_in, gate))
    errs = {}
    for name, a, r in zip(("dispatch", "combine", "dx", "drows", "dgate"),
                          got, want):
        a, r = np.asarray(a), np.asarray(r)
        _require(np.isfinite(a).all(), f"moe {name}: non-finite values")
        errs[name] = float(np.max(np.abs(a - r)))
        np.testing.assert_allclose(
            a, r, err_msg=f"moe {name}",
            **(MOE_DGATE_TOL if name == "dgate" else MOE_TOL))
    return errs


def check_paged_attention(slots: int, heads: int, head_dim: int,
                          block_size: int, max_blocks: int, dtype: str,
                          mosaic: bool, window: int = 1,
                          kv_heads: Optional[int] = None) -> float:
    """The paged-attention decode kernel against the gather-and-softmax
    it replaces, in float32 at ``highest`` precision over the same
    arenas: slots of ragged lengths (one inactive, one full), tables
    over shuffled blocks, garbage in the null block; ``heads`` query
    heads on ``kv_heads`` key-value heads (None: a key head a query
    head). Returns the largest error as a share of the reference's
    largest magnitude."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.kernels.paged_attention import (paged_attention_decode,
                                                      supported)

    kv_heads = kv_heads or heads
    hd = kv_heads * head_dim
    nb = slots * max_blocks + 1
    q_shape = (slots, window, heads, head_dim)
    _require(supported(q_shape, (nb, block_size, hd), dtype, max_blocks),
             f"paged_attention.supported() refuses {q_shape} over "
             f"{(nb, block_size, hd)} {dtype}")
    rng = np.random.default_rng(0)
    length = max_blocks * block_size
    lens = rng.integers(1, length - window, size=slots).astype(np.int32)
    lens[0], lens[-1] = 0, length - window
    tables = rng.permutation(np.arange(1, nb)).astype(np.int32).reshape(
        slots, max_blocks)
    tables[0] = 0
    k, v = (jnp.asarray(rng.normal(size=(nb, block_size, hd))
                        .astype(np.float32), dtype) for _ in range(2))
    k, v = k.at[0].set(1e4), v.at[0].set(-1e4)
    q = jnp.asarray(rng.normal(size=q_shape).astype(np.float32), dtype)
    tables, lens = jnp.asarray(tables), jnp.asarray(lens)
    got_fn = jax.jit(paged_attention_decode)
    if mosaic:
        _assert_mosaic(got_fn, q, k, v, tables, lens)
    got = np.asarray(got_fn(q, k, v, tables, lens), np.float32)

    def reference(q, k, v):
        # a group of query heads g reads its key-value head k
        kk, vv = (a[tables].reshape(slots, length, kv_heads, head_dim)
                  for a in (k, v))
        qg = q.reshape(slots, window, kv_heads, heads // kv_heads, head_dim)
        s = jnp.einsum("bqkgd,blkd->bkgql", qg, kk) * head_dim ** -0.5
        pos = lens[:, None] + jnp.arange(window)[None, :]
        seen = jnp.arange(length)[None, None, :] <= pos[:, :, None]
        p = jax.nn.softmax(jnp.where(seen[:, None, None], s, -jnp.inf),
                           axis=-1)
        return jnp.einsum("bkgql,blkd->bqkgd", p, vv).reshape(q.shape)

    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(reference)(
            *(a.astype(jnp.float32) for a in (q, k, v))))
    _require(np.isfinite(got).all(), "paged attention: non-finite values")
    # the inactive slot attends to the null block alone: left out
    err = float(np.max(np.abs(got[1:] - want[1:])) / np.max(np.abs(want[1:])))
    _require(err <= PAGED_RANGE_TOL,
             f"paged attention ({dtype}, {q_shape}, {nb} blocks): max error "
             f"{err:.2e} of range > {PAGED_RANGE_TOL}")
    return err


def check_latent_attention(slots: int, heads: int, rank: int, rope: int,
                           block_size: int, max_blocks: int, dtype: str,
                           mosaic: bool) -> float:
    """The latent-attention decode kernel against the gather-and-softmax
    it replaces, in float32 at ``highest`` precision over the same arena
    of rows ``[c | k_rope | 0...]``: slots of ragged lengths (one
    inactive, one full), tables over shuffled blocks and over stretches
    of neighbours, garbage in the null block. Returns the largest error
    as a share of the reference's largest magnitude."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.kernels.latent_attention import (
        latent_attention_decode, supported)
    from flexflow_tpu.serving.cache_entry import latent_row_lanes

    row = latent_row_lanes(rank + rope)
    nb = slots * max_blocks + 1
    q_shape = (slots, heads, row)
    _require(supported(q_shape, (nb, block_size, row), dtype, max_blocks,
                       rank),
             f"latent_attention.supported() refuses {q_shape} over "
             f"{(nb, block_size, row)} {dtype}")
    rng = np.random.default_rng(0)
    length = max_blocks * block_size
    lens = rng.integers(1, length - 1, size=slots).astype(np.int32)
    lens[0], lens[-1] = 0, length - 1
    tables = np.arange(1, nb, dtype=np.int32).reshape(slots, max_blocks)
    for i, blocks in enumerate(tables):
        if i % 2:
            blocks[:] = rng.permutation(blocks)
            continue
        # stretches of 3 to 40 neighbours ascending, which the kernel
        # fetches a group by one copy, seams apart
        cuts = np.cumsum(rng.integers(3, 41, size=max_blocks // 3))
        stretches = np.split(blocks.copy(), cuts[cuts < max_blocks])
        blocks[:] = np.concatenate(
            [stretches[j] for j in rng.permutation(len(stretches))])
    tables[0] = 0
    pad = np.zeros((1, 1, row), np.float32)
    pad[..., :rank + rope] = 1.0          # the arena's padding lanes are 0
    arena = jnp.asarray(rng.normal(size=(nb, block_size, row))
                        .astype(np.float32) * pad, dtype).at[0].set(1e4)
    q = jnp.asarray(rng.normal(size=q_shape).astype(np.float32) * pad[0],
                    dtype)
    tables, lens = jnp.asarray(tables), jnp.asarray(lens)
    scale = (rank + rope) ** -0.5
    got_fn = jax.jit(lambda q, a, t, l: latent_attention_decode(
        q, a, t, l, scale=scale, out_width=rank))
    if mosaic:
        _assert_mosaic(got_fn, q, arena, tables, lens)
    got = np.asarray(got_fn(q, arena, tables, lens), np.float32)

    def reference(q, arena):
        view = arena[tables].reshape(slots, length, row)
        s = jnp.einsum("nhr,nlr->nhl", q, view) * scale
        seen = jnp.arange(length)[None, :] <= lens[:, None]
        p = jax.nn.softmax(jnp.where(seen[:, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("nhl,nlc->nhc", p, view[..., :rank])

    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(reference)(q.astype(jnp.float32),
                                             arena.astype(jnp.float32)))
    _require(np.isfinite(got).all(), "latent attention: non-finite values")
    err = float(np.max(np.abs(got[1:] - want[1:])) / np.max(np.abs(want[1:])))
    _require(err <= PAGED_RANGE_TOL,
             f"latent attention ({dtype}, {q_shape}, {nb} blocks): max "
             f"error {err:.2e} of range > {PAGED_RANGE_TOL}")
    return err


# The state-update kernel is float32 elementwise work and sums down 96
# sublanes, no matrix unit: it differs from its jnp form (float32 products
# at `highest`) by the order of a sum.
GATED_DELTA_RANGE_TOL = 1e-4


def check_gated_delta(slots: int, heads: int, key_dim: int, value_dim: int,
                      mosaic: bool) -> float:
    """The gated-delta-rule decode kernel against its jnp form
    (``kernels/gated_delta.py`` ``gated_delta_step``) over the same arena
    of states: every slot on a row of its own but two idle ones on the
    null row, decays in (0.9, 1), beta in (0, 2). Returns the largest
    error of the outputs and of the live rows' new states, each as a
    share of its reference's largest magnitude; rows no slot names must
    come back bit for bit."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.kernels import gated_delta as gd

    rows_n = slots + 1
    shape = (rows_n, key_dim, heads * value_dim)
    _require(gd.supported(slots, heads, key_dim, value_dim, shape,
                          jnp.float32),
             f"gated_delta.supported() refuses {slots} slots over {shape}")
    rng = np.random.default_rng(0)
    arena = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    rows = rng.permutation(np.arange(1, rows_n)).astype(np.int32)
    rows[[1, slots - 1]] = 0
    q = rng.normal(size=(slots, heads, key_dim)).astype(np.float32)
    k = rng.normal(size=(slots, heads, key_dim)).astype(np.float32)
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * key_dim ** 0.5
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(slots, heads, value_dim)).astype(np.float32)
    alpha = rng.uniform(0.9, 1.0, size=(slots, heads)).astype(np.float32)
    beta = rng.uniform(0.0, 2.0, size=(slots, heads)).astype(np.float32)
    args = tuple(map(jnp.asarray, (rows, q, k, v, alpha, beta)))
    got_fn = jax.jit(gd.gated_delta_decode)
    if mosaic:
        _assert_mosaic(got_fn, arena, *args)
    o, new = got_fn(arena, *args)
    o_ref, new_ref = jax.jit(gd.gated_delta_step)(arena, *args)
    live = rows != 0
    held = rows[live]
    untouched = np.setdiff1d(np.arange(1, rows_n), held)
    _require(np.array_equal(np.asarray(new)[untouched],
                            np.asarray(arena)[untouched]),
             "gated delta: a row no slot names was written")
    _require(np.isfinite(np.asarray(new)).all(),
             "gated delta: non-finite state")
    err = 0.0
    for a, r in ((np.asarray(o)[live], np.asarray(o_ref)[live]),
                 (np.asarray(new)[held], np.asarray(new_ref)[held])):
        err = max(err, float(np.max(np.abs(a - r)) / np.max(np.abs(r))))
    _require(err <= GATED_DELTA_RANGE_TOL,
             f"gated delta ({slots} slots, {shape}): max error {err:.2e} "
             f"of range > {GATED_DELTA_RANGE_TOL}")
    return err


# Kernel and jnp form are float32 elementwise work and one sum over the
# state's axis; they differ by that sum's order and by which state ``y``
# is read from (the kernel: the new one; the jnp form: the old one and
# ``u (B . C)``).
SSD_STEP_RANGE_TOL = 1e-5


def check_ssd_step(slots: int, heads: int, head_dim: int, state: int,
                   groups: int, mosaic: bool) -> float:
    """The Mamba-2 decode-step kernel against its jnp form
    (``ops/mamba2.py`` ``ssd_step_rows``) over the same arena of states:
    every slot on a row of its own, in no order, but two idle ones on the
    null row, decays in (0.5, 1). Returns the largest error of the live
    slots' outputs and new states, each as a share of its reference's
    largest magnitude; the null row and the rows no slot names must come
    back bit for bit."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.kernels import ssd_step
    from flexflow_tpu.ops import mamba2

    rows_n = slots + 2
    shape = (rows_n, state, heads * head_dim)
    _require(ssd_step.supported(slots, heads, head_dim, state, groups, shape,
                                jnp.float32),
             f"ssd_step.supported() refuses {slots} slots over {shape}")
    rng = np.random.default_rng(0)
    arena = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    rows = rng.permutation(np.arange(1, rows_n))[:slots].astype(np.int32)
    rows[[1, slots - 1]] = 0
    args = tuple(map(jnp.asarray, (
        rows, rng.normal(size=(slots, heads, head_dim)).astype(np.float32),
        rng.uniform(0.5, 1.0, size=(slots, heads)).astype(np.float32),
        rng.normal(size=(slots, groups, state)).astype(np.float32),
        rng.normal(size=(slots, groups, state)).astype(np.float32))))
    got_fn = jax.jit(ssd_step.ssd_step_decode)
    if mosaic:
        _assert_mosaic(got_fn, arena, *args)
    y, new = got_fn(arena, *args)
    y_ref, new_ref = jax.jit(mamba2.ssd_step_rows)(arena, *args)
    live = rows != 0
    held = rows[live]
    untouched = np.setdiff1d(np.arange(rows_n), held)
    _require(np.array_equal(np.asarray(new)[untouched],
                            np.asarray(arena)[untouched]),
             "ssd step: the null row or a row no slot names was written")
    err = 0.0
    for a, r in ((np.asarray(y)[live], np.asarray(y_ref)[live]),
                 (np.asarray(new)[held], np.asarray(new_ref)[held])):
        err = max(err, float(np.max(np.abs(a - r)) / np.max(np.abs(r))))
    _require(err <= SSD_STEP_RANGE_TOL,
             f"ssd step ({slots} slots, {shape}): max error {err:.2e} of "
             f"range > {SSD_STEP_RANGE_TOL}")
    return err


# The kernel and the jnp lines sum the same four float32 products in the
# same order; the compiler may still contract a product and a sum into one
# rounding in one and not in the other.
STATE_TAILS_RANGE_TOL = 1e-6


def check_state_tails(slots: int, channels: int, mosaic: bool) -> float:
    """A delta-rule step's tails through the kernel
    (``ConvTail.step_arena``, ``kernels/gated_delta.py`` ``tails_step``:
    flat on the lanes, one pass over the arena in arena order) against
    the slot-order lines it replaced (a window of ``(n, taps, channels)``
    through ``GatedDeltaNet.convolve``'s sum, ``spread_rows`` back) over
    the same bfloat16 arena: every slot on a row of its own, in no order,
    but two idle ones on the null row. The stepped arena must come back
    bit for bit, the null row with it; returns the largest error of the
    live slots' convolved rows as a share of the reference's largest
    magnitude."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.kernels import gated_delta as gd
    from flexflow_tpu.ops.rows import spread_rows
    from flexflow_tpu.serving.cache_entry import ConvTail

    taps, bf, f32 = 4, jnp.bfloat16, jnp.float32
    rows_n, c = slots + 1, channels
    _require(gd.tails_supported((rows_n, (taps - 1) * c), bf, c),
             f"gated_delta.tails_supported() refuses {rows_n} rows of "
             f"{taps - 1} x {c}")
    rng = np.random.default_rng(0)
    rows = rng.permutation(np.arange(1, rows_n)).astype(np.int32)
    rows[[1, slots - 1]] = 0
    w = jnp.asarray(rng.normal(size=(taps, c)), bf)
    args = (jnp.asarray(rng.normal(size=(rows_n, (taps - 1) * c)), bf),
            jnp.asarray(rows), jnp.asarray(rng.normal(size=(slots, c)), bf))

    def slot_order(tails, rows, inputs):
        window = jnp.concatenate(
            [tails[rows].reshape(slots, taps - 1, c), inputs[:, None]], 1)
        acc = sum(w.astype(f32)[j] * window.astype(f32)[:, j:j + 1]
                  for j in range(taps))
        return jax.nn.silu(acc), spread_rows(
            tails, rows, window[:, 1:].reshape(slots, -1))

    got_fn = jax.jit(ConvTail.step_arena)
    if mosaic:
        _assert_mosaic(got_fn, *args, w)
    u, new = got_fn(*args, w)
    u_ref, new_ref = jax.jit(slot_order)(*args)
    _require(np.array_equal(np.asarray(new.astype(f32)),
                            np.asarray(new_ref.astype(f32))),
             f"state tails ({slots} slots, {c} channels): the kernel left "
             "other tails than the slot-order lines")
    live = rows != 0
    err = float(np.max(np.abs(np.asarray(u) - np.asarray(u_ref))[live])
                / np.max(np.abs(np.asarray(u_ref)[live])))
    _require(err <= STATE_TAILS_RANGE_TOL,
             f"state tails ({slots} slots, {c} channels): max error "
             f"{err:.2e} of range > {STATE_TAILS_RANGE_TOL}")
    return err


# Both arms of a counted call multiply bfloat16 rows by bfloat16 matrices
# with float32 sums and round the activation to bfloat16 before the down
# product: against the same product in float32 each carries those roundings
# (2^-9 of a term), as a share of the largest output some 3e-3 measured.
COUNTED_EXPERTS_RANGE_TOL = 2e-2


def check_counted_experts(rows: int, count: int, width: int, named: int,
                          mosaic: bool) -> Dict[str, float]:
    """A decode step's held experts where the shapes say dense and the
    step counts (``RoutedExperts._apply_counted``): ``rows`` tokens of one
    pick over ``count`` gated experts of ``width`` x ``width``, all held,
    bfloat16. A routing that names ``named`` of them (under the op's
    ``kernel_limit``) must take the kernel and say so, one that names all
    must take the dense form, and each arm's sum is compared with the
    same product in float32 at ``highest`` precision. The one program
    holds one conditional, with the Mosaic call in one arm. Returns each
    arm's largest error as a share of its reference's largest
    magnitude."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.core.layer import Layer
    from flexflow_tpu.core.parallel_tensor import ParallelTensorShape
    from flexflow_tpu.ffconst import DataType, OpType
    from flexflow_tpu.kernels.grouped_experts import tile_rows
    from flexflow_tpu.ops.moe_ops import RoutedExperts

    op = RoutedExperts(
        Layer(OpType.ROUTED_EXPERTS, "experts", attrs=dict(
            n_routed=count, experts_per_token=1, width=width,
            scoring="softmax", norm_topk=False)),
        [ParallelTensorShape.unpartitioned((1, rows, width),
                                           DataType.BFLOAT16)])
    _require(op.expert_form(rows, active=True) == "counted",
             f"{rows} rows of one pick over {count} experts of {width} are "
             f"{op.expert_form(rows, active=True)!r}, not counted")
    _require(named <= op.kernel_limit() < count,
             f"{named} named is past the limit {op.kernel_limit()}")
    key = jax.random.key(0)
    w = {ws.name: (0.05 * jax.random.normal(
        jax.random.fold_in(key, i), ws.shape)).astype(jnp.bfloat16)
        for i, ws in enumerate(op.weight_specs())}
    v = jax.random.normal(jax.random.fold_in(key, 99), (rows, width)
                          ).astype(jnp.bfloat16)
    got_fn = jax.jit(op._apply_counted)
    f32 = {k: a.astype(jnp.float32) for k, a in w.items()}
    errs = {}
    for arm, n in (("kernel", named), ("dense", count)):
        ids = jnp.asarray((np.arange(rows) % n)[:, None], jnp.int32)
        _, gates, _ = op.route(w, v, ids)
        if mosaic and arm == "kernel":
            text = got_fn.lower(w, v, ids, gates).compile().as_text()
            _require(text.count(" conditional(") == 1,
                     "a counted call holds one conditional")
            _require(text.count("tpu_custom_call") >= 1
                     and "grouped_experts" in text,
                     "kernel did not lower to a Mosaic tpu_custom_call")
        y, computed, took = got_fn(w, v, ids, gates)
        _require(int(took) == (arm == "kernel"),
                 f"{n} of {count} named took the wrong arm ({int(took)})")
        _require(int(computed) == (n * tile_rows(rows) if arm == "kernel"
                                   else count * rows),
                 f"{arm}: {int(computed)} rows computed")
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(op._apply_dense(f32, v.astype(jnp.float32),
                                             ids, gates))
        err = float(np.abs(np.asarray(y, np.float32) - ref).max()
                    / np.abs(ref).max())
        _require(err <= COUNTED_EXPERTS_RANGE_TOL,
                 f"counted experts, {arm} arm: max error {err:.2e} of range "
                 f"> {COUNTED_EXPERTS_RANGE_TOL}")
        errs[arm] = err
    return errs


# The whole-sequence kernel's products are float32 at float32 contract
# precision, as its jnp form's are at `highest`; they differ by the order
# of sums and by how each inverts a chunk's unit-lower system (doubling
# blocks against rows). bfloat16 operands would read some 1e-2.
GATED_DELTA_CHUNKS_RANGE_TOL = 1e-4


def check_gated_delta_chunks(seq: int, heads: int, key_dim: int,
                             value_dim: int, mosaic: bool) -> Dict[str, float]:
    """What a gated-delta-rule layer runs between its convolution and
    its gate, the kernel's way (``ops/gated_delta.py`` ``fused_rule``:
    unit q and k, the whole-sequence recurrence, RMSNorm, one kernel)
    against the jnp way (``scan_rule``, around ``chunked_delta_rule``)
    over one row of ``seq`` tokens behind a state that is not zero:
    decays in (0.8, 1), beta in (0, 2). Returns the largest error of the
    normalised o and of the state after the sequence, each as a share of
    its reference's largest magnitude."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.kernels import gated_delta as gd
    from flexflow_tpu.ops.gated_delta import fused_rule, scan_rule

    _require(gd.chunks_supported(seq, heads, key_dim, value_dim, jnp.float32),
             f"gated_delta.chunks_supported() refuses {seq} tokens of "
             f"{heads} heads of {key_dim} and {value_dim}")
    rng = np.random.default_rng(0)
    q, k = (rng.normal(size=(1, seq, heads * key_dim)).astype(np.float32)
            for _ in range(2))
    v = rng.normal(size=(1, seq, heads * value_dim)).astype(np.float32)
    g = np.log(rng.uniform(0.8, 1.0, size=(1, seq, heads))).astype(np.float32)
    beta = rng.uniform(0.0, 2.0, size=(1, seq, heads)).astype(np.float32)
    state = 0.1 * rng.normal(
        size=(1, heads, key_dim, value_dim)).astype(np.float32)
    gain = rng.uniform(0.5, 1.5, size=(value_dim,)).astype(np.float32)
    args = tuple(map(jnp.asarray, (q, k, v, g, beta, state, gain)))
    got_fn = jax.jit(lambda *a: fused_rule(1e-6, *a))
    if mosaic:
        _assert_mosaic(got_fn, *args)
    got = got_fn(*args)
    want = jax.jit(lambda *a: scan_rule(1e-6, *a))(*args)
    errs = {}
    for name, a, r in zip(("o", "state"), got, want):
        a, r = np.asarray(a), np.asarray(r)
        _require(np.isfinite(a).all(), f"gated delta chunks: non-finite {name}")
        errs[name] = float(np.max(np.abs(a - r)) / np.max(np.abs(r)))
        _require(errs[name] <= GATED_DELTA_CHUNKS_RANGE_TOL,
                 f"gated delta chunks ({seq} tokens, {heads} heads): {name} "
                 f"max error {errs[name]:.2e} of range > "
                 f"{GATED_DELTA_CHUNKS_RANGE_TOL}")
    return errs


def phase_kernels(sizes: SmokeSizes, batch: int) -> Dict:
    from flexflow_tpu.kernels import pallas_mode

    mode = pallas_mode()
    _require(mode is not None, "Pallas kernels are off (FLEXFLOW_TPU_PALLAS)")
    mosaic = mode == "compiled"
    ph = _Phase("kernels")
    shape = (batch, sizes.seq, sizes.heads, sizes.hidden // sizes.heads)
    errs = {}
    for dtype in ("float32", "bfloat16"):
        e = check_flash_attention(shape, True, dtype, mosaic)
        errs.update({f"flash_{dtype}_{k}": f"{v:.1e}" for k, v in e.items()})
    errs.update({f"moe_{k}": f"{v:.1e}"
                 for k, v in check_moe_kernels(sizes.moe, mosaic).items()})
    if sizes.hidden % 128 == 0:  # the toy's rows are no whole lane tiles
        for window in (1, 4):
            errs[f"paged_w{window}"] = "%.1e" % check_paged_attention(
                4, sizes.heads, sizes.hidden // sizes.heads, 16,
                sizes.max_length // 16, KV_DTYPE, mosaic, window)
    # grouped heads at the chunk their rows' bytes ask: the chains cell's
    # pool (8 on 2 of 128, 16 pages of 64 an iteration) and the retrieval
    # cell's (32 on 8 of 64, 8 pages); 3 slots of 6 blocks under the
    # interpreter
    for name, heads, kv_heads, head_dim, table in (
            ("chains", 8, 2, 128, 72), ("retrieval", 32, 8, 64, 144)):
        errs[f"paged_{name}"] = "%.1e" % check_paged_attention(
            48 if mosaic else 3, heads, head_dim, 64, table if mosaic else 6,
            KV_DTYPE, mosaic, kv_heads=kv_heads)
    # a latent cache's rows at the benchmark's widths (64 heads over rows
    # of 512 + 64, padded to 640 lanes), beside paged_attention_decode
    errs["latent"] = "%.1e" % check_latent_attention(
        4, 64, 512, 64, 16, sizes.max_length // 16, KV_DTYPE, mosaic)
    # a hybrid model's states at the benchmark's widths (30 heads, keys of
    # 96, values of 192) and its 32 slots; 4 under the interpreter
    errs["gated_delta"] = "%.1e" % check_gated_delta(
        32 if mosaic else 4, 30, 96, 192, mosaic)
    # and its prefill at the cell's widest bucket (a chunk and a partial
    # one of a group and a half of heads under the interpreter)
    e = check_gated_delta_chunks(*((1536, 30) if mosaic else (150, 6)),
                                 96, 192, mosaic)
    errs.update({f"gated_delta_chunks_{k}": f"{v:.1e}" for k, v in e.items()})
    # Mamba-2 states at the retrieval and the agents cells' widths (64
    # heads in one group, 128 in 8; rows of 2.1 and 4.2 MB) and slots; a
    # toy of each shape class under the interpreter
    for name, shape in (("granite", (48, 64, 64, 128, 1) if mosaic
                         else (4, 4, 32, 16, 1)),
                        ("nemotron", (128, 128, 64, 128, 8) if mosaic
                         else (4, 8, 32, 16, 2))):
        errs[f"ssd_step_{name}"] = "%.1e" % check_ssd_step(*shape, mosaic)
    # a delta-rule step's tails at the long-answers and the documents
    # cells' shapes (256 slots of 3 x 12,288, 32 of 3 x 11,520): the
    # kernel against the slot-order lines; toys under the interpreter
    for name, shape in (("ling", (256, 12288) if mosaic else (6, 128)),
                        ("olmo", (32, 11520) if mosaic else (20, 384))):
        errs[f"state_tails_{name}"] = "%.1e" % check_state_tails(*shape,
                                                                 mosaic)
    # a decode step's held experts at the chains cell's share (48 slots of
    # one pick over 16 experts of 2,048 x 2,048, 6 named), both arms of
    # the counted call; 256-wide experts under the interpreter
    e = check_counted_experts(48, 16, 2048 if mosaic else 256, 6, mosaic)
    errs.update({f"counted_experts_{k}": f"{v:.1e}" for k, v in e.items()})
    return ph.report(interpret=not mosaic, flash_shape=shape,
                     moe_shape=sizes.moe, **errs)


# --------------------------------------------------------------------------
# serve
# --------------------------------------------------------------------------

# Paged prefill against raw_forward, last prompt position, max |Δlogit|.
# Both sides run the same bfloat16 weights and activations; they differ in
# how attention is written (the prefill scales the scores after the
# product and masks with -1e30, single_device_attention scales q first and
# masks with -inf), so every block rounds a little differently to
# bfloat16 (2^-9 relative) and the differences add up over the depth:
# 0.014 measured on a v5e over 24 blocks. The logits of a freshly
# initialised model span several units, and a wrong block table, mask or
# position moves them by that much.
SERVE_LOGIT_ATOL = 0.05
KV_DTYPE = "bfloat16"


def serve_hybrid() -> Dict[str, str]:
    """A small hybrid of one gated-delta-rule and one full-attention
    layer (4 linear heads of 32 and 64, 2 heads of 128: widths both
    kinds' kernels take) through ``GenerationInstance``: three greedy
    requests over two slots and three prefill buckets. Returns how its
    programs ran: on the chip the prefills must take the whole-sequence
    kernel and the decode step read both caches in place and step its
    convolution tails by theirs."""
    import jax

    from flexflow_tpu import FFModel
    from flexflow_tpu.ffconst import CompMode
    from flexflow_tpu.models import HybridLMConfig, build_hybrid_lm
    from flexflow_tpu.serving import GenerationInstance

    slots, vocab, max_length = 2, 512, 288
    ff = FFModel(_ff_config(batch_size=slots,
                            computation_mode=CompMode.INFERENCE))
    build_hybrid_lm(ff, slots, max_length, HybridLMConfig(
        vocab_size=vocab, hidden_size=256, num_heads=2, linear_heads=4,
        layer_types=("linear_attention", "full_attention"),
        linear_key_dim=32, linear_value_dim=64, mlp_width=512))
    ff.compile(optimizer=None, loss_type=None, metrics=[])
    inst = GenerationInstance(ff, decode_slots=slots, max_length=max_length,
                              kv_dtype=KV_DTYPE,
                              prefill_buckets=[96, 160, max_length])
    try:
        rng = np.random.default_rng(1)
        reqs = [(rng.integers(0, vocab, n).astype(np.int32), m)
                for n, m in ((20, 6), (150, 5), (200, 4))]
        futures = [inst.generate_async(p, m, temperature=0.0)
                   for p, m in reqs]
        for (prompt, m), fut in zip(reqs, futures):
            out = fut.result(timeout=900)
            _require(out.shape == (prompt.size + m,)
                     and np.array_equal(out[:prompt.size], prompt),
                     f"hybrid request of {prompt.size}+{m} tokens returned "
                     f"{out.shape}")
        kv = inst.stats()["kv"]
    finally:
        inst.stop()
    # (the step's convolution tails among them: 512 channels, taps of
    # whole lane tiles)
    paths = {"hybrid_prefill_path": kv["state"]["prefill_path"],
             "hybrid_attention_path": kv["attention_path"]["decode"],
             "hybrid_tails_path": kv["tails_path"]}
    _require(set(paths.values()) == {"kernel"}
             or jax.default_backend() != "tpu",
             f"the hybrid's programs took {paths}")
    return paths


SSM_TOY = dict(slots=2, vocab=512, max_length=96)


def ssm_toy_families() -> Dict[str, tuple]:
    """A small model of each family that keeps Mamba-2 states: a Mamba-2
    and an attention block at widths both kinds' kernels take (Granite: 4
    heads of 64 in one group, a prompt prefilled in chunks through the
    state; Nemotron-H: 8 heads of 32 in 2 groups, prefilled in a bucket).
    {name: (the builder, its configuration, how ``GenerationInstance``
    prefills)}; ``tests/test_ssd_step_kernel.py`` serves the same two."""
    from flexflow_tpu.models import (GraniteHybridConfig, NemotronHConfig,
                                     build_granite_hybrid_lm,
                                     build_nemotron_h_lm)

    common = dict(vocab_size=SSM_TOY["vocab"], hidden_size=256,
                  state_size=16, num_heads=2, num_kv_heads=1, chunk_size=16)
    return {
        "granite": (build_granite_hybrid_lm, GraniteHybridConfig(
            layer_types=("mamba", "attention"), mamba_heads=4,
            mamba_head_dim=64, mlp_width=512, **common),
            dict(prefill_chunk=32)),
        "nemotron": (build_nemotron_h_lm, NemotronHConfig(
            pattern="M*", mamba_heads=8, mamba_head_dim=32, n_groups=2,
            **common),
            dict(prefill_buckets=[48, SSM_TOY["max_length"]])),
    }


def serve_ssm() -> Dict[str, str]:
    """:func:`ssm_toy_families` through ``GenerationInstance``: two greedy
    requests over two slots. Returns which form each model's decode step
    took for its states (the counters ``ssm_step.path.*``): on the chip
    it must be the kernel, and the step read both caches in place."""
    import jax

    from flexflow_tpu import FFModel
    from flexflow_tpu.ffconst import CompMode
    from flexflow_tpu.obs.metrics import metrics_registry
    from flexflow_tpu.serving import GenerationInstance

    slots, vocab, max_length = (SSM_TOY[k] for k in
                                ("slots", "vocab", "max_length"))
    families = ssm_toy_families()
    reg = metrics_registry()
    paths = {}
    for name, (build, cfg, how) in families.items():
        before = _path_counts(reg, "ssm_step", ("kernel", "rows"))
        ff = FFModel(_ff_config(batch_size=slots,
                                computation_mode=CompMode.INFERENCE))
        build(ff, slots, max_length, cfg)
        ff.compile(optimizer=None, loss_type=None, metrics=[])
        inst = GenerationInstance(ff, decode_slots=slots, block_size=16,
                                  max_length=max_length, kv_dtype=KV_DTYPE,
                                  **how)
        try:
            rng = np.random.default_rng(2)
            reqs = [(rng.integers(0, vocab, n).astype(np.int32), m)
                    for n, m in ((40, 5), (20, 4))]
            futures = [inst.generate_async(p, m, temperature=0.0)
                       for p, m in reqs]
            for (prompt, m), fut in zip(reqs, futures):
                out = fut.result(timeout=900)
                _require(out.shape == (prompt.size + m,)
                         and np.array_equal(out[:prompt.size], prompt),
                         f"{name} request of {prompt.size}+{m} tokens "
                         f"returned {out.shape}")
            decode = inst.stats()["kv"]["attention_path"]["decode"]
        finally:
            inst.stop()
        took = "+".join(_paths_taken(reg, "ssm_step", before))
        _require((took, decode) == ("kernel", "kernel")
                 or jax.default_backend() != "tpu",
                 f"{name}'s decode step took ssm_step.path.{took} and read "
                 f"its caches by {decode}")
        paths[f"{name}_ssm_step_path"] = took
    return paths


def phase_serve(sizes: SmokeSizes) -> Dict:
    import jax
    import jax.numpy as jnp

    from flexflow_tpu import FFModel
    from flexflow_tpu.ffconst import CompMode
    from flexflow_tpu.models.gpt import build_gpt
    from flexflow_tpu.obs.metrics import metrics_registry
    from flexflow_tpu.serving import GenerationInstance

    ph = _Phase("serve")
    reg = metrics_registry()
    watched = ("serving.errors", "serving.shed", "serving.kv_dtype_fallbacks",
               "serving.worker_crashes")
    before = {n: reg.counter(n).value for n in watched}

    slots = 4
    ff = FFModel(_ff_config(batch_size=slots,
                            computation_mode=CompMode.INFERENCE))
    build_gpt(ff, slots, sizes.max_length, _gpt_config(sizes))
    ff.compile(optimizer=None, loss_type=None, metrics=[])
    inst = GenerationInstance(ff, decode_slots=slots,
                              max_length=sizes.max_length, kv_dtype=KV_DTYPE)
    ph.built()
    try:
        rng = np.random.default_rng(0)
        reqs = []
        for _ in range(sizes.requests):
            n = int(rng.integers(sizes.prompt_len[0], sizes.prompt_len[1] + 1))
            m = int(rng.integers(sizes.new_tokens[0], sizes.new_tokens[1] + 1))
            reqs.append((rng.integers(0, sizes.vocab, n).astype(np.int32), m))
        # all submitted at once into fewer slots than requests: the later
        # ones are prefilled between the earlier ones' decode steps
        futures = [inst.generate_async(p, m, temperature=0.0)
                   for p, m in reqs]
        # the scheduler turns an exception into a failed request, not a
        # dead process: result() re-raises it here
        outs = [f.result(timeout=900) for f in futures]
        for (prompt, m), out in zip(reqs, outs):
            _require(out.shape == (prompt.size + m,)
                     and np.array_equal(out[:prompt.size], prompt)
                     and int(out.min()) >= 0 and int(out.max()) < sizes.vocab,
                     f"request of {prompt.size}+{m} tokens returned "
                     f"{out.shape}")
        st = inst.stats()
        _require(st["completed"] == sizes.requests
                 and st["prefill_prompts"] == sizes.requests,
                 f"completed {st['completed']} of {sizes.requests}")
        _require(st["decode_dispatches"] == st["decode_steps"] > 0,
                 f"decode dispatches {st['decode_dispatches']} != steps "
                 f"{st['decode_steps']}")
        _require(st["shed"] == 0 and st["deadline_rejects"] == 0,
                 f"shed {st['shed']}, deadline rejects "
                 f"{st['deadline_rejects']}")
        moved = {n: reg.counter(n).value - before[n] for n in watched}
        _require(not any(moved.values()), f"serving counters moved: {moved}")
        # no silent fall-back: on the chip the decode step's attention
        # reads the pool in place, through the paged-attention kernel
        path = st["kv"]["attention_path"]["decode"]
        _require(path == "kernel" or jax.default_backend() != "tpu",
                 f"the decode step's attention took the {path!r} path")
        # nor a silent synchronous loop: every request here is greedy,
        # so each step but a run's first is dispatched while the one
        # before it is still unread
        ran = st["loop"]["ahead"]
        ahead_share = ran["steps_ahead"] / max(
            1, ran["steps_ahead"] + ran["steps_sync"])
        _require(ahead_share > 0.9,
                 f"{ran['steps_ahead']} steps ran ahead, "
                 f"{ran['steps_sync']} were waited for")
        arena = next(iter(inst.decoder.pool.kv.values()))[0]
        _require(st["kv"]["kv_dtype"] == KV_DTYPE
                 and arena.dtype == jnp.dtype(KV_DTYPE)
                 and not st["kv"].get("quant_fallback", False),
                 f"asked for {KV_DTYPE} KV, got {st['kv']['kv_dtype']} "
                 f"arenas of {arena.dtype}")

        # one prompt (the shortest: the cheapest reference to compile):
        # paged prefill logits against the dense forward. The scheduler is
        # idle now, so the pool is ours to donate through.
        prompt = min((p for p, _ in reqs), key=lambda p: p.size)
        table = inst.decoder.pool.try_admit(prompt.size + 1)
        try:
            paged = inst.decoder.prefill(prompt, table)
        finally:
            inst.decoder.pool.free(table)
        pos = np.arange(prompt.size, dtype=np.int32)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(ff.compiled.raw_forward)(
                ff.compiled.params, prompt[None, :], pos[None, :])
        ref = np.asarray(ref)[0, -1]
        _require(paged.shape == (sizes.vocab,) and np.isfinite(paged).all(),
                 "paged prefill logits: wrong shape or non-finite")
        err = float(np.max(np.abs(paged - ref)))
        _require(err <= SERVE_LOGIT_ATOL,
                 f"paged prefill vs raw_forward: max |dlogit| {err:.3e} > "
                 f"{SERVE_LOGIT_ATOL} (logit range {ref.min():.2f}.."
                 f"{ref.max():.2f})")
    finally:
        inst.stop()
    return ph.report(
        **serve_hybrid(), **serve_ssm(),
        requests=sizes.requests, tokens=st["tokens"],
        decode_steps=st["decode_steps"],
        prefill_dispatches=st["prefill_dispatches"],
        kv_dtype=st["kv"]["kv_dtype"], attention_path=path,
        steps_ahead_share=round(ahead_share, 4),
        kv_blocks_read_share=round(
            st["kv"]["blocks_read"] / max(1, st["kv"]["blocks_in_tables"]),
            4),
        kv_divergence=st["kv"].get("divergence"),
        prefill_vs_raw_forward=f"{err:.2e}", logit_atol=SERVE_LOGIT_ATOL,
        ttft_p50_s=(st["phases"]["ttft"] or {}).get("p50"))


# --------------------------------------------------------------------------
# train
# --------------------------------------------------------------------------

# First (epoch-0 mean) loss against ln(vocab): a freshly initialised model
# spreads its probability almost evenly, so its loss starts within a few
# hundredths of ln(vocab) and the first steps move it down from there; a
# label shift, a wrong vocabulary or a broken loss lands far outside.
FIRST_LOSS_BAND = 1.0
# Data-parallel against the searched plan, epoch-0 mean loss: the same
# seed, data and global batch, summed in another order in bfloat16.
PLAN_LOSS_ATOL = 0.05


def _train_data(sizes: SmokeSizes, n: int):
    """A seeded, fixed, learnable dataset: tokens drawn from a Zipf law
    over the vocabulary, so that a few Adam steps lower the loss by
    learning the unigram distribution."""
    rng = np.random.default_rng(0)
    p = 1.0 / np.arange(1, sizes.vocab + 1)
    tok = rng.choice(sizes.vocab, size=(n, sizes.seq + 1),
                     p=p / p.sum()).astype(np.int32)
    pos = np.broadcast_to(np.arange(sizes.seq, dtype=np.int32),
                          (n, sizes.seq)).copy()
    return tok[:, :-1].copy(), pos, tok[:, 1:].copy()


def _path_counts(reg, family: str, names,
                 kind: str = "path") -> Dict[str, int]:
    """The ``<family>.<kind>.<name>`` counters: which implementation a
    lowering took, counted once per trace."""
    return {n: reg.counter(f"{family}.{kind}.{n}").value for n in names}


def _paths_taken(reg, family: str, before: Dict[str, int],
                 kind: str = "path") -> List[str]:
    now = _path_counts(reg, family, before, kind)
    return sorted(n for n, v0 in before.items() if now[n] > v0)


def phase_train(sizes: SmokeSizes, plan: str, batch_per_device: int) -> Dict:
    """``plan``: ``single`` (one device), ``dp`` (every device, data
    parallel) or ``searched`` (every device, the Unity search's choice)."""
    import jax

    from flexflow_tpu import AdamOptimizer, FFModel, LossType, MetricsType
    from flexflow_tpu.models.gpt import build_gpt
    from flexflow_tpu.obs.metrics import metrics_registry

    ph = _Phase(f"train[{plan}]")
    devices = jax.devices()
    n_dev = len(devices)
    _require((plan == "single") == (n_dev == 1),
             f"plan {plan!r} on {n_dev} device(s)")
    batch = batch_per_device * n_dev
    reg = metrics_registry()
    paths0 = _path_counts(reg, "attention",
                          ("flash", "xla", "ring", "ulysses"))
    forms0 = _path_counts(reg, "loss",
                          ("one_pass", "log_softmax", "probabilities"))
    backward0 = _path_counts(reg, "attention", ("fused", "split"), "backward")

    ff = FFModel(_ff_config(batch_size=batch, epochs=2,
                            only_data_parallel=plan != "searched",
                            search_budget=8 if plan == "searched" else 0))
    build_gpt(ff, batch, sizes.seq, _gpt_config(sizes))
    ff.compile(optimizer=AdamOptimizer(alpha=sizes.lr),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               metrics=[MetricsType.SPARSE_CATEGORICAL_CROSSENTROPY])
    ph.built()
    cm = ff.compiled

    tokens, positions, labels = _train_data(
        sizes, batch * sizes.steps_per_epoch)
    history = ff.fit([tokens, positions], labels, verbose=False)
    losses = [pm.sparse_cce_loss / max(1, pm.train_all) for pm in history]
    paths = _paths_taken(reg, "attention", paths0)
    forms = _paths_taken(reg, "loss", forms0)
    backward = _paths_taken(reg, "attention", backward0, "backward")
    # no silent fall-back: on the chip, at this shape, the step's
    # attention is the fused kernels (no (S, S) array in HBM), taken by
    # the shapes alone — main() pops every variable that could force it
    _require(paths == ["flash"] or jax.default_backend() != "tpu",
             f"the train step's attention took the {'+'.join(paths)!r} "
             f"path at sequence {sizes.seq}, {sizes.heads} heads of "
             f"{sizes.hidden // sizes.heads}")
    # and its backward is one kernel: a sequence of this length leaves
    # the whole dQ of a lane tile room in fast memory
    _require(backward == ["fused"] or paths != ["flash"],
             f"the train step's attention backward took the "
             f"{'+'.join(backward)!r} form at sequence {sizes.seq}")
    # sparse labels on raw logits: the loss reads the head's logits where
    # the head wrote them, by the loss type alone
    _require(forms == ["one_pass"],
             f"the train step's loss took the {'+'.join(forms)!r} form")

    _require(len(losses) == 2 and all(math.isfinite(x) for x in losses),
             f"epoch losses {losses}")
    ln_v = math.log(sizes.vocab)
    _require(abs(losses[0] - ln_v) <= FIRST_LOSS_BAND,
             f"first loss {losses[0]:.3f} outside ln({sizes.vocab})="
             f"{ln_v:.3f} +- {FIRST_LOSS_BAND}")
    _require(losses[1] < losses[0],
             f"loss did not fall: {losses[0]:.4f} -> {losses[1]:.4f}")
    epochs = ff.fit_profile["epochs"]
    _require([e["steps"] for e in epochs] == [sizes.steps_per_epoch] * 2,
             f"steps per epoch {[e['steps'] for e in epochs]}")
    _require(epochs[1]["compiles"] == 0,
             f"{epochs[1]['compiles']} compile(s) after the first epoch")

    # where the state and the batch actually are
    leaves = jax.tree_util.tree_leaves((cm.params, cm.opt_state))
    on = set().union(*(leaf.sharding.device_set for leaf in leaves))
    _require(on == set(devices),
             f"parameters and optimizer state sit on {len(on)} of {n_dev} "
             f"devices")
    for sh in (*cm.input_shardings, cm.label_sharding):
        _require(sh.device_set == set(devices),
                 f"batch sharding {sh} covers {len(sh.device_set)} of "
                 f"{n_dev} devices")
    if plan == "dp" and n_dev > 1:
        _require(cm.input_shardings[0].spec[0] == "data",
                 f"data-parallel batch is not split: "
                 f"{cm.input_shardings[0].spec}")
    facts = {}
    if plan == "searched":
        sp = ff.search_profile
        facts = dict(mesh=sp["mesh_shape"], candidates=sp["candidates"],
                     search_workers=sp["workers"],
                     search_s=round(sp["search_time_s"], 2),
                     sharded_layers=sum(
                         1 for s in ff._search_strategies.values() if s),
                     pipelined=ff.pipelined is not None)
    return ph.report(
        batch=batch, batch_per_device=batch_per_device, devices=n_dev,
        state_on_devices=len(on),
        batch_on_devices=len(cm.input_shardings[0].device_set),
        batch_spec=tuple(cm.input_shardings[0].spec),
        seq=sizes.seq, params=gpt_param_count(sizes),
        estimated_bytes=train_bytes_estimate(sizes, batch_per_device),
        steps=2 * sizes.steps_per_epoch, attention_path="+".join(paths),
        attention_backward="+".join(backward), loss_path="+".join(forms),
        loss_epoch0=round(losses[0], 4), loss_epoch1=round(losses[1], 4),
        epoch_wall_s=[e["wall_s"] for e in epochs],
        compiles_by_epoch=[e["compiles"] for e in epochs], **facts)


# --------------------------------------------------------------------------
# the run
# --------------------------------------------------------------------------

def _release() -> None:
    """Drop a finished phase's device buffers before the next one."""
    import jax

    gc.collect()
    jax.clear_caches()


def run(sizes: SmokeSizes) -> List[Dict]:
    """Every phase at ``sizes`` on whatever backend JAX has; raises on the
    first check that does not hold. Returns one record per phase."""
    import jax

    from flexflow_tpu import native_bridge
    from flexflow_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()  # before the first jit of the process
    os.makedirs(OUT_DIR, exist_ok=True)
    print(f"[chip_smoke] native={native_bridge.status()!r} "
          f"compile_cache={jax.config.jax_compilation_cache_dir!r} "
          f"(JAX_COMPILATION_CACHE_DIR "
          f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})",
          flush=True)

    batch = resolve_batch_per_device(sizes)
    records = [phase_kernels(sizes, batch)]
    _release()
    records.append(phase_serve(sizes))
    _release()
    if len(jax.devices()) == 1:
        records.append(phase_train(sizes, "single", batch))
    else:
        dp = phase_train(sizes, "dp", batch)
        _release()
        searched = phase_train(sizes, "searched", batch)
        records += [dp, searched]
        gap = abs(dp["loss_epoch0"] - searched["loss_epoch0"])
        _require(gap <= PLAN_LOSS_ATOL,
                 f"first losses disagree: dp {dp['loss_epoch0']}, "
                 f"searched {searched['loss_epoch0']}")
        print(f"[chip_smoke] plans agree: |first loss dp - searched| = "
              f"{gap:.2e} <= {PLAN_LOSS_ATOL}", flush=True)
    return records


def main() -> int:
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"[chip_smoke] platform={device['platform']} "
          f"device_kind={device['kind']!r} count={device['count']}",
          flush=True)
    if jax.default_backend() != "tpu":
        print(f"[chip_smoke] FAIL: jax.default_backend() is "
              f"{jax.default_backend()!r}, not 'tpu'; nothing was built",
              file=sys.stderr, flush=True)
        return 2
    # the kernels phase is about Mosaic, and the train phase about which
    # attention the shapes alone choose: on the chip neither is up to
    # the environment
    os.environ.pop("FLEXFLOW_TPU_PALLAS", None)
    # the tree may be a copy whose file times mean nothing: rebuild the
    # native library from native/src (run() says which one it got)
    from flexflow_tpu import native_bridge

    native_bridge.rebuild()
    records = run(GPT2_MEDIUM)
    with open(os.path.join(OUT_DIR, "result.json"), "w") as f:
        json.dump({"device": device, "phases": records}, f, indent=1,
                  default=str)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
