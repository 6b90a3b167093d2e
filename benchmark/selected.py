"""What a cell needs whose model SELECTS the key blocks its attention
reads and whose prompts are prefilled in chunks: the instance built with
``prefill_chunk``, the warm-up of the chunk programs, and the comparison
that decides ``correct``.

The block selection is discontinuous, as routing is (``routed.py``): a
block's score is a maximum over pooled keys' softmax weights, and the
sixty-fourth block and the sixty-fifth can lie a rounding apart, so a
program that computes in bfloat16 and a float32 reference pick a few
blocks differently, and free-running logits then differ by what those
blocks held. So the comparison has two parts, both on what the timed
programs produced (one prompt prefilled in chunks, then a few decode
steps through the paged cache, slot 0, the other slots idle):

(a) *selection*: the block ids the programs picked per sparse layer,
    key-value head and position past ``dense_len``
    (``PagedDecoder.last_routing``) against the float32 reference's own
    choice ON THE SAME INPUT (the reference follows the program's picks
    in the layers before, so a difference is counted where it happens).
    Wherever the two sets differ, the reference's own score of the worst
    block the program took must lie within ``selection_check
    .score_margin`` (a share of the lowest score the reference took) of
    that score; the share of differing (layer, head, position) triples is
    bounded by ``selection_check.differing_share``;
(b) *logits*: the reference run WITH the program's picks against the
    program's logits, by ``check.logit_error`` under
    ``limits.serve_logit_rel``.

Both parts are read from ONE forward of the reference.
``outputs_of_reference`` puts the reference itself, at a lower precision,
in the program's place: the control (``control_selected.py``).
"""

from __future__ import annotations

import os
from typing import Dict, List, Tuple

import numpy as np

from benchmark import check, traffic


def build(ctx):
    """``serving.build`` for prompts longer than any bucket: the
    ``GenerationInstance`` takes the mix's ``prefill_chunk`` and no
    buckets."""
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
    inst = GenerationInstance(
        ff, decode_slots=slots, block_size=int(mix["block_size"]),
        max_length=max_length, kv_dtype=mix["kv_dtype"],
        kv_divergence_budget=float(mix["kv_divergence_budget"]),
        prefill_chunk=int(mix["prefill_chunk"]))
    ctx.note("instance built")
    return ff, inst, weights


def warm_up(ctx, inst) -> None:
    """Every program the window will run, through the decoder the
    scheduler drives: a chunk that is not its prompt's last, one that is
    (it computes the head), and the decode step; the round repeated until
    a whole round compiles nothing, as ``serving.warm_up`` does and for
    its reason (a donated pool arrives in the layout the program before
    left it in)."""
    from flexflow_tpu.utils.compile_cache import compile_stats

    dec = inst.decoder
    vocab = int(ctx.config["vocab_size"])
    n = dec.prefill_chunk + 1          # two chunks: a whole one and a last
    idle = (np.zeros(dec.decode_slots, np.int32),
            np.zeros((dec.decode_slots, dec.max_blocks_per_request),
                     np.int32),
            np.zeros(dec.decode_slots, np.int32))
    with ctx.span("warmup"):
        for round_ in range(4):
            before = compile_stats()["compiles"]
            for _ in range(2):
                table = dec.pool.try_admit(n + 1)
                try:
                    dec.prefill(traffic.token_ids(ctx.seed, 10 ** 6 + 1, n,
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
    ctx.note(f"warmed up the chunk programs and the decode step in "
             f"{round_ + 1} rounds")


def program_outputs(ctx, inst) -> Tuple[np.ndarray, np.ndarray, List]:
    """One request's prompt prefilled chunk by chunk and a few greedy
    decode steps through the paged cache, in slot 0 with the other slots
    idle: the logits of each step (1 + decode_steps rows), the whole
    token sequence, and per sparse layer the (1, Hkv, len(tokens), picks)
    block ids the programs picked at each position."""
    mix = ctx.mix
    dec = inst.decoder
    names = ctx.family.sparse_layer_names(ctx.config)
    n, steps = int(mix["check"]["prompt_len"]), int(mix["check"]["decode_steps"])
    prompt = traffic.token_ids(ctx.seed, 10 ** 6, n,
                               int(ctx.config["vocab_size"]))
    table = dec.pool.try_admit(n + steps + 1)
    slots, c = dec.decode_slots, dec.prefill_chunk
    rows, toks = [], list(prompt)
    ids: List[List[np.ndarray]] = [[] for _ in names]
    try:
        for at in range(0, n, c):
            logits = dec.prefill_chunk_at(prompt, table, at)
            for j, name in enumerate(names):
                ids[j].append(np.asarray(
                    dec.last_routing[name])[:, :, :min(c, n - at)])
        rows.append(logits)
        for k in range(steps):
            toks.append(int(rows[-1].argmax()))
            tokens = np.zeros(slots, np.int32)
            tables = np.zeros((slots, dec.max_blocks_per_request), np.int32)
            lens = np.zeros(slots, np.int32)
            tokens[0], lens[0] = toks[-1], n + k
            tables[0, :len(table)] = table
            rows.append(dec.decode(tokens, tables, lens)[0])
            for j, name in enumerate(names):
                ids[j].append(np.asarray(dec.last_routing[name])[:1])
    finally:
        dec.pool.free(table)
    return (np.stack(rows), np.asarray(toks, np.int32),
            [np.concatenate(layer, axis=2) for layer in ids])


def outputs_of_reference(ctx, weights, toks, n_rows: int, precision: str):
    """The reference at ``precision``, free-running, in the program's
    place: its logits at the positions the program's ``n_rows`` rows
    stand for and its own picks."""
    import jax.numpy as jnp

    logits, info = ctx.reference.forward(
        weights, jnp.asarray(toks[None, :]), ctx.config, precision,
        rows=n_rows)
    return (np.asarray(logits)[0],
            [np.asarray(layer["own_ids"]) for layer in info])


def compare(ctx, weights, rows, toks, got_ids) -> Dict:
    """Both parts for one set of outputs; returns the numbers. The
    reference's last ``len(rows)`` positions are the ones whose next
    token the program's rows predicted."""
    import jax.numpy as jnp

    forced, info = ctx.reference.forward(
        weights, jnp.asarray(toks[None, :]), ctx.config, "float32",
        selection=got_ids, rows=len(rows))
    dense_len = int(ctx.config["sparse_config"]["dense_len"])
    past = max(len(toks) - dense_len, 0)
    triples = differing = 0
    worst = 0.0
    for layer in info:
        differ = np.asarray(layer["differ"])
        triples += differ.shape[0] * differ.shape[1] * past
        differing += int(differ.sum())
        worst = max(worst, float(np.asarray(layer["shortfall"]).max()))
    ref = np.asarray(forced)[0]
    return {"differing_share": differing / max(triples, 1),
            "score_margin": worst, "triples": triples,
            "differing": differing,
            "logit_error": check.logit_error(rows, ref),
            "reference_logit_std": float(ref.std()),
            "positions": len(rows)}


def compare_paged(ctx, inst, weights, checks) -> None:
    """``serving.compare_paged``'s place, for a model that selects."""
    rows, toks, got_ids = program_outputs(ctx, inst)
    n = compare(ctx, weights, rows, toks, got_ids)
    sc = ctx.config["selection_check"]
    checks.at_most("serve.selection_score_margin", n["score_margin"],
                   sc["score_margin"])
    checks.at_most("serve.selection_differing_share", n["differing_share"],
                   sc["differing_share"])
    checks.at_most("serve.paged_logits_vs_reference", n["logit_error"],
                   ctx.config["limits"]["serve_logit_rel"])
    ctx.note("selection and paged logits compared")
    ctx.facts["serve_check"] = {k: v for k, v in n.items()
                                if isinstance(v, (int, float))}
