"""The comparison that decides ``correct`` for a model with a
discontinuous layer: routed experts.

With random weights a token's last expert taken and its first expert
left are often a rounding apart, so a program that computes in bfloat16
and a float32 reference route a few (token, layer) pairs differently, and
free-running logits then differ by a whole expert's output. So the
comparison has two parts, both on what the timed programs produced (the
bucketed prefill of one prompt, then a few decode steps through the
paged cache, slot 0, the other slots idle):

(a) *routing*: the expert ids the programs chose per expert layer, token
    and pick (``PagedDecoder.last_routing``) against the float32
    reference's own choice. Wherever they differ, the reference's own
    score of the expert the program took must lie within
    ``routing_check.score_margin`` of the lowest score the reference took
    (and, where the program's expert sits in a group the reference
    dropped, that group's score — the sum of its two highest — within
    twice the margin of the last group kept); the share of differing
    (token, layer) pairs is bounded by ``routing_check.differing_share``;
(b) *logits*: the reference run WITH the program's routing (its own
    scores for the weights), against the program's logits, by
    ``check.logit_error`` under ``limits.serve_logit_rel``.

Both parts are read from ONE forward of the reference, the one that
takes the program's routing: in each expert layer the reference scores
the experts on an input that followed the program's choices in the layers
before, so a pair the two route differently is counted where it happens
and not again in every later layer (a free-running reference's stream
parts from the program's at the first flip: on the chip a first run read
13 % of pairs differing and a margin of 0.030 that way, most of it
echo). The free-running share is kept beside the result as a fact.

``outputs_of_reference`` puts the reference itself, at a lower precision,
in the program's place: the control (``control_routed.py``).
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from benchmark import check, traffic


def program_outputs(ctx, inst) -> Tuple[np.ndarray, np.ndarray, List]:
    """``serving.program_rows`` with the routing kept: the logits of each
    step (1 + decode_steps rows), the whole token sequence, and per
    expert layer the (len(tokens), k) expert ids the programs chose."""
    mix = ctx.mix
    dec = inst.decoder
    names = ctx.family.expert_layer_names(ctx.config)
    n, steps = int(mix["check"]["prompt_len"]), int(mix["check"]["decode_steps"])
    prompt = traffic.token_ids(ctx.seed, 10 ** 6, n,
                               int(ctx.config["vocab_size"]))
    table = dec.pool.try_admit(n + steps + 1)
    slots = dec.decode_slots
    rows, toks = [], list(prompt)
    ids: List[List[np.ndarray]] = [[] for _ in names]
    try:
        rows.append(dec.prefill(prompt, table))
        for j, name in enumerate(names):
            ids[j].append(np.asarray(dec.last_routing[name])[0, :n])
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
            [np.concatenate(layer) for layer in ids])


def outputs_of_reference(ctx, weights, toks, n_rows: int, precision: str):
    """The reference at ``precision``, free-running, in the program's
    place: its logits at the last ``n_rows`` positions and its routing."""
    import jax.numpy as jnp

    logits, info = ctx.reference.forward_with_routing(
        weights, jnp.asarray(toks[None, :]), ctx.config, precision)
    return (np.asarray(logits)[0, len(toks) - n_rows:],
            [np.asarray(layer["ids"]) for layer in info])


def routing_numbers(config: Dict, got_ids: List[np.ndarray],
                    info: List[Dict]) -> Dict:
    """Part (a): ``differing_share`` of (token, layer) pairs whose expert
    sets differ, and ``score_margin``, the largest shortfall of a taken
    expert's reference score below what the reference took."""
    n_group = int(config.get("n_group") or 1)
    topk_group = int(config.get("topk_group") or n_group)
    differing, pairs, worst = 0, 0, 0.0
    for got, layer in zip(got_ids, info):
        s = np.asarray(layer["scores"], np.float64)          # (T, routed)
        own = np.asarray(layer["own_ids"])
        got = np.asarray(got)
        if got.shape != own.shape:
            raise ValueError(f"routing {got.shape} against {own.shape}")
        t = s.shape[0]
        per = s.shape[1] // n_group
        gscore = np.sort(s.reshape(t, n_group, per), -1)[..., -2:].sum(-1) \
            if per >= 2 else s.reshape(t, n_group, per).sum(-1)
        kept_floor = np.sort(gscore, -1)[:, -topk_group]     # last group kept
        took_floor = np.take_along_axis(s, own, -1).min(-1)  # lowest taken
        for i in range(t):
            pairs += 1
            extra = set(got[i].tolist()) - set(own[i].tolist())
            if not extra and len(set(got[i].tolist())) == got.shape[1]:
                continue
            differing += 1
            if len(set(got[i].tolist())) != got.shape[1]:
                worst = float("inf")      # an expert taken twice
            for e in extra:
                short = max(0.0, took_floor[i] - s[i, e])
                g = e // per
                short = max(short, (kept_floor[i] - gscore[i, g]) / 2.0)
                worst = max(worst, short)
    return {"differing_share": differing / max(pairs, 1),
            "score_margin": worst, "pairs": pairs, "differing": differing}


def compare(ctx, weights, rows, toks, got_ids) -> Dict:
    """Both parts for one set of outputs; returns the numbers."""
    import jax.numpy as jnp

    tok = jnp.asarray(toks[None, :])
    forced, info = ctx.reference.forward_with_routing(
        weights, tok, ctx.config, "float32", routing=got_ids)
    numbers = routing_numbers(ctx.config, got_ids, info)
    _, free = ctx.reference.forward_with_routing(weights, tok, ctx.config,
                                                 "float32")
    numbers["free_running_differing_share"] = routing_numbers(
        ctx.config, got_ids, free)["differing_share"]
    ref = np.asarray(forced)[0, len(toks) - len(rows):]
    numbers["logit_error"] = check.logit_error(rows, ref)
    numbers["reference_logit_std"] = float(ref.std())
    numbers["positions"] = len(rows)
    return numbers


def compare_paged(ctx, inst, weights, checks) -> None:
    """``serving.compare_paged``'s place, for a routed model."""
    rows, toks, got_ids = program_outputs(ctx, inst)
    n = compare(ctx, weights, rows, toks, got_ids)
    rc = ctx.config["routing_check"]
    checks.at_most("serve.routing_score_margin", n["score_margin"],
                   rc["score_margin"])
    checks.at_most("serve.routing_differing_share", n["differing_share"],
                   rc["differing_share"])
    checks.at_most("serve.paged_logits_vs_reference", n["logit_error"],
                   ctx.config["limits"]["serve_logit_rel"])
    ctx.note("routing and paged logits compared")
    ctx.facts["serve_check"] = {k: v for k, v in n.items()
                                if isinstance(v, (int, float))}
