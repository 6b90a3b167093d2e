"""``kind: fit`` — training through ``FFModel.compile`` and ``fit``.

Mix parameters: ``seq``; ``batch_per_chip`` (a number, or ``"fits"`` for
the largest power of two ``counts.train_batch_that_fits`` finds room
for); ``steps_per_fit`` (steps of one ``fit`` call); ``adam_alpha``;
``ffconfig`` (further ``FFConfig`` fields: the plan).

The window is a loop of ``fit`` calls over the same seeded data, each
of ``steps_per_fit`` steps and each ending in the epoch's host sync;
the rate counts the steps of the calls that ended inside the window,
over the time from its start to the last one's end.
"""

from __future__ import annotations

import math
import os
import time
from typing import Dict

import numpy as np

from benchmark import check, counts


def seeded_batches(seed: int, vocab: int, n: int, seq: int):
    """Seeded tokens from a Zipf law over the vocabulary (learnable, as
    ``chip_smoke.py`` trains on), as (tokens, positions, labels)."""
    rng = np.random.default_rng([int(seed), 1])
    p = 1.0 / np.arange(1, vocab + 1)
    tok = rng.choice(vocab, size=(n, seq + 1), p=p / p.sum()).astype(np.int32)
    pos = np.broadcast_to(np.arange(seq, dtype=np.int32), (n, seq)).copy()
    return tok[:, :-1].copy(), pos, tok[:, 1:].copy()


def batch_per_chip(ctx) -> int:
    """The mix's ``batch_per_chip``: a number, or ``"fits"`` for the
    largest power of two the chip's memory holds."""
    bpc = ctx.mix["batch_per_chip"]
    if bpc != "fits":
        return int(bpc)
    limit = (ctx.devices[0].memory_stats() or {}).get("bytes_limit")
    if limit is None:
        raise RuntimeError("the device reports no memory limit to size "
                           "the batch from")
    return counts.train_batch_that_fits(ctx.config, int(ctx.mix["seq"]),
                                        int(limit))


def build(ctx, batch: int):
    """The compiled model with the seed's weights in it."""
    import jax

    from flexflow_tpu import (AdamOptimizer, FFConfig, FFModel, LossType,
                              MetricsType)

    mix, cfg = ctx.mix, ctx.config
    ff = FFModel(FFConfig(
        seed=int(ctx.seed) & 0x7FFFFFFF, compute_dtype="bfloat16",
        search_cache="off", ledger_dir=os.path.join(ctx.workdir, "ledger"),
        batch_size=batch, epochs=1,
        **mix.get("ffconfig", {})))
    ctx.family.build(ff, cfg, batch, int(mix["seq"]))
    with ctx.span("compile"):
        ff.compile(optimizer=AdamOptimizer(alpha=float(mix["adam_alpha"])),
                   loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                   metrics=[MetricsType.SPARSE_CATEGORICAL_CROSSENTROPY])
    if ff.pipelined is not None:
        raise RuntimeError("the plan is pipelined: its stage state lives "
                           "off CompiledModel.params, where the benchmark "
                           "puts the seed's weights")
    ctx.note("compiled")
    cm = ff.compiled
    weights = ctx.reference.init_weights(cfg, ctx.seed)
    tree = ctx.family.to_program(weights, cfg)
    cm.params = jax.tree_util.tree_map(jax.device_put, tree,
                                       cm.param_shardings)
    cm.bump_params_version()
    return ff, weights


def program_step(ctx, ff, batch_arrays):
    """The step program on one batch: its gradients at the sampled
    leaves (``backward()``) and its loss (one ``fit`` step on the same
    batch, which reports the loss before its update). The ``fit`` step
    donates the parameters, whose buffers the reference's weights share:
    call :func:`reference_step` first."""
    tok, pos, lab = batch_arrays
    names = ctx.family.grad_sample_names(ctx.config)
    ff.set_batch([tok, pos], lab)
    ff.backward()
    grads = ctx.family.grads_to_reference(ff._cur_grads, ctx.config, names)
    grads = {k: np.asarray(v, np.float32) for k, v in grads.items()}
    ff.zero_gradients()
    return grads


def program_loss(ff, batch_arrays) -> float:
    tok, pos, lab = batch_arrays
    history = ff.fit([tok, pos], lab, shuffle=False, verbose=False)
    return history[0].sparse_cce_loss / max(1, history[0].train_all)


def reference_step(ctx, weights, batch_arrays, precision: str):
    """The reference's loss and its gradients at the sampled leaves."""
    import jax.numpy as jnp

    tok, _, lab = batch_arrays
    loss, grads = ctx.reference.loss_and_grads(
        weights, jnp.asarray(tok), jnp.asarray(lab), ctx.config,
        ctx.family.grad_sample_names(ctx.config), precision)
    return loss, {k: np.asarray(v, np.float32) for k, v in grads.items()}


def fit_numbers(loss_got, grads_got, loss_want, grads_want) -> Dict:
    """The two numbers the fit comparison judges: the loss's distance
    from the reference's, and the largest relative L2 distance of a
    sampled gradient from the reference's."""
    rel = {k: check.rel_l2(grads_got[k], grads_want[k]) for k in grads_want}
    worst = max(rel, key=rel.get)
    return {"loss_abs": abs(loss_got - loss_want), "grad_rel": rel[worst],
            "grad_rel_worst_leaf": worst, "grad_rel_by_leaf": rel}


def compare_step(ctx, ff, weights, batch_arrays, checks) -> None:
    limits = ctx.config["limits"]
    grads_p = program_step(ctx, ff, batch_arrays)
    ctx.note("program's gradients")
    loss_r, grads_r = reference_step(ctx, weights, batch_arrays, "float32")
    ctx.note("reference's loss and gradients")
    loss_p = program_loss(ff, batch_arrays)  # donates what `weights` share
    ctx.note("program's first step")
    nums = fit_numbers(loss_p, grads_p, loss_r, grads_r)
    ln_v = math.log(int(ctx.config["vocab_size"]))
    checks.at_most("fit.first_loss_minus_ln_vocab", abs(loss_p - ln_v),
                   limits["fit_first_loss_band"])
    checks.at_most("fit.loss_abs_diff", nums["loss_abs"],
                   limits["fit_loss_abs"])
    checks.at_most(f"fit.grad_rel_l2[{nums['grad_rel_worst_leaf']}]",
                   nums["grad_rel"], limits["fit_grad_rel"])
    ctx.facts["fit_check"] = dict(nums, loss_program=loss_p,
                                  loss_reference=loss_r)


def run(ctx) -> Dict:
    import jax

    from flexflow_tpu.obs.metrics import metrics_registry

    mix, cfg = ctx.mix, ctx.config
    seq, steps = int(mix["seq"]), int(mix["steps_per_fit"])
    bpc = batch_per_chip(ctx)
    batch = bpc * ctx.chips
    ff, weights = build(ctx, batch)
    ctx.note("weights made and placed")
    cm = ff.compiled
    tok, pos, lab = seeded_batches(ctx.seed, int(cfg["vocab_size"]),
                                   batch * steps, seq)
    compare_step(ctx, ff, weights, (tok[:batch], pos[:batch], lab[:batch]),
                 ctx.checks)
    del weights
    # steady state: one whole fit call with the window's own shapes
    with ctx.span("warmup"):
        ff.fit([tok, pos], lab, verbose=False)
        jax.block_until_ready(cm.params)
    ctx.note("warmed up")
    reg = metrics_registry()
    compiles0 = reg.counter("jax.compiles").value
    ctx.facts.update(batch=batch, batch_per_chip=int(bpc), seq=seq,
                     steps_per_fit=steps,
                     search_profile=getattr(ff, "search_profile", None))

    calls, losses, profiles = 0, [], []
    t0 = ctx.window_opens()
    t_end = t0
    while t_end - t0 < ctx.seconds:
        traced = ctx.profiler.enabled and calls == 1
        if traced:
            ctx.profiler.start()
        with ctx.span("fit"):
            history = ff.fit([tok, pos], lab, verbose=False)
            jax.block_until_ready(cm.params)
        if traced:
            ctx.profiler.stop()
        t_end = time.perf_counter()
        calls += 1
        losses.append(history[0].sparse_cce_loss
                      / max(1, history[0].train_all))
        profiles.append(ff.fit_profile["epochs"][0])
    window_s = t_end - t0
    ctx.window_closed(t_end)
    ctx.note(f"window closed after {calls} fit calls")
    tokens = calls * steps * batch * seq
    ctx.checks.equal("fit.compiles_in_window",
                     int(reg.counter("jax.compiles").value - compiles0), 0)
    ctx.checks.equal("fit.losses_finite",
                     all(math.isfinite(x) for x in losses), True)
    ctx.checks.equal("fit.loss_fell", bool(losses[-1] < losses[0]
                                           or len(losses) == 1), True)
    ctx.facts.update(fit_calls=calls, window_s=window_s, tokens=tokens,
                     losses=losses, epochs=profiles)
    return {"attempted": calls * steps, "failed": 0,
            "end_to_end": {"train_tokens_per_s": tokens / window_s}}
