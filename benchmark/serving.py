"""What the two serving kinds share: the instance with the seed's
weights in it, the warm-up of the mix's own shapes, and the comparison
of the paged prefill and decode with the reference's full forward.
"""

from __future__ import annotations

import os
from typing import Dict, List

import numpy as np

from benchmark import check, traffic

WATCHED = ("serving.errors", "serving.shed", "serving.deadline_rejects",
           "serving.kv_dtype_fallbacks", "serving.worker_crashes")


def build(ctx, reqs: List[traffic.Request]):
    """``GenerationInstance`` over the configuration's model, compiled
    for inference, holding the seed's weights; only the prefill buckets
    the schedule uses are passed."""
    import jax

    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.ffconst import CompMode
    from flexflow_tpu.serving import GenerationInstance

    mix, cfg = ctx.mix, ctx.config
    slots, max_length = int(mix["decode_slots"]), int(mix["max_length"])
    ff = FFModel(FFConfig(
        seed=int(ctx.seed) & 0x7FFFFFFF, compute_dtype="bfloat16",
        search_cache="off", ledger_dir=os.path.join(ctx.workdir, "ledger"),
        batch_size=slots, computation_mode=CompMode.INFERENCE))
    ctx.family.build(ff, cfg, slots, max_length)
    with ctx.span("compile"):
        ff.compile(optimizer=None, loss_type=None, metrics=[])
    ctx.note("compiled")
    cm = ff.compiled
    weights = ctx.reference.init_weights(cfg, ctx.seed)
    cm.params = jax.tree_util.tree_map(
        jax.device_put, ctx.family.to_program(weights, cfg),
        cm.param_shardings)
    cm.bump_params_version()
    buckets = traffic.buckets_used(mix, reqs)
    check_bucket = traffic.bucket_for(mix["prefill_buckets"],
                                      int(mix["check"]["prompt_len"]))
    inst = GenerationInstance(
        ff, decode_slots=slots, block_size=int(mix["block_size"]),
        max_length=max_length, kv_dtype=mix["kv_dtype"],
        kv_divergence_budget=float(mix["kv_divergence_budget"]),
        prefill_buckets=sorted(set(buckets) | {check_bucket}))
    ctx.note("instance built")
    return ff, inst, weights, buckets


def warm_up(ctx, inst, buckets: List[int]) -> None:
    """Every program the window will run, through the decoder the
    scheduler drives: the prefill of each bucket the schedule uses (the
    scheduler admits one prompt a dispatch, so the row width is 1) and
    the decode step. The scheduler is idle, so its statistics stay
    empty and the pool is ours to donate through.

    A program is compiled anew for each layout its donated pool arrives
    in, and a pool leaves a prefill and a decode step in layouts of the
    compiler's choosing. So the round (every bucket, then the decode
    step twice) is repeated until a whole round compiles nothing: by
    then every program has seen the pool as every other leaves it."""
    from flexflow_tpu.utils.compile_cache import compile_stats

    dec = inst.decoder
    vocab = int(ctx.config["vocab_size"])
    slots = dec.decode_slots
    idle = (np.zeros(slots, np.int32),
            np.zeros((slots, dec.max_blocks_per_request), np.int32),
            np.zeros(slots, np.int32))
    with ctx.span("warmup"):
        for round_ in range(4):
            before = compile_stats()["compiles"]
            for b in buckets + buckets[:1]:
                table = dec.pool.try_admit(b + 1)
                try:
                    dec.prefill(traffic.token_ids(ctx.seed, 10 ** 6 + b, b,
                                                  vocab), table)
                finally:
                    dec.pool.free(table)
                dec.decode(*idle)
                dec.decode(*idle)
            if compile_stats()["compiles"] == before:
                break
        else:
            raise RuntimeError("the serving programs still compile after "
                               "four rounds of warm-up")
    ctx.note(f"warmed up prefill buckets {buckets} and the decode step in "
             f"{round_ + 1} rounds")


def program_rows(ctx, inst):
    """One request's prefill and a few greedy decode steps through the
    paged cache, in slot 0 with the other slots idle: the logits of each
    step, and the whole token sequence."""
    mix = ctx.mix
    dec = inst.decoder
    n, steps = int(mix["check"]["prompt_len"]), int(mix["check"]["decode_steps"])
    prompt = traffic.token_ids(ctx.seed, 10 ** 6, n,
                               int(ctx.config["vocab_size"]))
    table = dec.pool.try_admit(n + steps + 1)
    slots = dec.decode_slots
    rows, toks = [], list(prompt)
    try:
        rows.append(dec.prefill(prompt, table))
        for k in range(steps):
            toks.append(int(rows[-1].argmax()))
            tokens = np.zeros(slots, np.int32)
            tables = np.zeros((slots, dec.max_blocks_per_request), np.int32)
            lens = np.zeros(slots, np.int32)
            tokens[0], lens[0] = toks[-1], n + k
            tables[0, :len(table)] = table
            rows.append(dec.decode(tokens, tables, lens)[0])
    finally:
        dec.pool.free(table)
    return np.stack(rows), np.asarray(toks, np.int32)


def reference_rows(ctx, weights, toks, n_rows: int, precision: str):
    """The reference's forward over the whole sequence, at its last
    ``n_rows`` positions: those whose next token the program's prefill
    and decode steps predicted."""
    import jax.numpy as jnp

    logits = ctx.reference.forward_jit(weights, jnp.asarray(toks[None, :]),
                                       ctx.config, precision)
    return np.asarray(logits)[0, len(toks) - n_rows:]


def compare_paged(ctx, inst, weights, checks) -> None:
    rows, toks = program_rows(ctx, inst)
    ref = reference_rows(ctx, weights, toks, len(rows), "float32")
    err = check.logit_error(rows, ref)
    checks.at_most("serve.paged_logits_vs_reference", err,
                   ctx.config["limits"]["serve_logit_rel"])
    ctx.note("paged logits compared")
    ctx.facts["serve_check"] = {
        "positions": len(rows), "logit_error": err,
        "reference_logit_std": float(ref.std())}


def decode_tokens_per_step(s0: Dict, s1: Dict):
    """Tokens one decode step produced between two ``stats()`` readings
    (the first token of a request is its prefill's, not a step's); None
    where no step ran."""
    steps = s1["decode_steps"] - s0["decode_steps"]
    if steps <= 0:
        return None
    return ((s1["tokens"] - s0["tokens"])
            - (s1["prefill_prompts"] - s0["prefill_prompts"])) / steps


def counters() -> Dict[str, float]:
    from flexflow_tpu.obs.metrics import metrics_registry

    reg = metrics_registry()
    return {n: reg.counter(n).value for n in WATCHED + ("jax.compiles",)}


def finish_checks(ctx, inst, before: Dict[str, float], checks) -> None:
    """What has to hold of any serving window: nothing compiled, nothing
    shed or crashed, the KV arenas in the dtype the mix states, one
    dispatch a decode step."""
    after = counters()
    moved = {k: after[k] - before[k] for k in before if after[k] != before[k]}
    checks.equal("serve.counters_moved_in_window", moved, {})
    st = inst.stats()
    checks.equal("serve.kv_dtype", st["kv"].get("kv_dtype"),
                 ctx.mix["kv_dtype"])
    checks.equal("serve.decode_dispatches_per_step",
                 st["decode_dispatches"] == st["decode_steps"], True)
