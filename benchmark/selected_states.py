"""The comparison that decides ``correct`` for a model that routes its
tokens over experts, SELECTS the rows its sparse attention reads by a
learned indexer, keeps a float32 recurrent state a request, and is served
in chunks: four parts, all on what the timed programs produced (one prompt
prefilled chunk by chunk, then a few decode steps through the paged cache,
slot 0, the other slots idle; every item of the mix's ``check`` LIST).

(a) *routing*, as ``benchmark/routed.py``: the experts the programs chose
    against the float32 reference's own choice on the same input
    (``routing_check``);
(b) *selection*: the pools the programs took for each query past the dense
    regime against the reference's own choice ON THE SAME INPUT (it
    follows the program's picks and routing in the layers before, so a
    difference is counted where it happens): the share of differing
    (query, pick) pairs under ``selection_check.differing_share``, and
    wherever they differ the reference's score of the worst pool the
    program took within ``selection_check.score_margin`` of the lowest it
    took itself, as a share of the spread of the scores it took (an
    indexer's score is a signed sum, so a share of the score itself would
    mean nothing near zero);
(c) *logits*: the reference run WITH the program's routing and selection
    against the program's logits (``limits.serve_logit_rel``);
(d) *states*, as ``benchmark/routed_states.py``: the request's row of every
    state op's arena against the reference's recurrence after the same
    tokens (``state_check``).

``outputs_of_reference`` puts the reference itself, changed, in the
program's place: the controls (``control_glm.py``).
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from benchmark import check, routed, routed_states, traffic


def program_outputs(ctx, inst, item: Dict, index: int) -> Tuple:
    """Check item ``index``'s prompt prefilled chunk by chunk and a few
    greedy decode steps, in slot 0 with the other slots idle: (the logits
    of each step (1 + decode_steps rows), the whole token sequence, per
    expert layer the (len(tokens), k) expert ids (-1 where a program ran
    no such layer for a token: ``routed_chunked.program_outputs``), per
    sparse layer the pools each query took by their scores, (1,
    len(tokens), len(tokens) // pool) bool (the programs keep them as ids:
    :func:`as_mask`), the request's state rows read before its table is
    freed)."""
    dec = inst.decoder
    cfg = ctx.config
    experts = ctx.family.expert_layer_names(cfg)
    sparse = ctx.family.sparse_layer_names(cfg)
    n, steps = int(item["prompt_len"]), int(item["decode_steps"])
    prompt = traffic.token_ids(ctx.seed, 10 ** 6 + index, n,
                               int(cfg["vocab_size"]))
    table = dec.pool.try_admit(n + steps + 1)
    slots, c = dec.decode_slots, dec.prefill_chunk
    k = int(cfg["num_experts_per_tok"])
    pools = (n + steps) // int(cfg["index_kpool"])
    rows, toks = [], list(prompt)
    ids: List[List[np.ndarray]] = [[] for _ in experts]
    picks: List[List[np.ndarray]] = [[] for _ in sparse]
    try:
        for at in range(0, n, c):
            logits = dec.prefill_chunk_at(prompt, table, at)
            live = min(c, n - at)
            for j, name in enumerate(experts):
                got = dec.last_routing.get(name)
                got = (np.zeros((0, k), np.int32) if got is None
                       else np.asarray(got)[0, :live])
                ids[j] += [np.full((live - len(got), k), -1, np.int32), got]
            for j, name in enumerate(sparse):
                picks[j].append(as_mask(
                    np.asarray(dec.last_routing[name])[:, :live], pools))
        rows.append(logits)
        for step in range(steps):
            toks.append(int(rows[-1].argmax()))
            tokens = np.zeros(slots, np.int32)
            tables = np.zeros((slots, dec.max_blocks_per_request), np.int32)
            lens = np.zeros(slots, np.int32)
            tokens[0], lens[0] = toks[-1], n + step
            tables[0, :len(table)] = table
            rows.append(dec.decode(tokens, tables, lens)[0])
            for j, name in enumerate(experts):
                ids[j].append(np.asarray(dec.last_routing[name])[:1])
            for j, name in enumerate(sparse):
                picks[j].append(as_mask(
                    np.asarray(dec.last_routing[name])[:1], pools))
        states = routed_states.state_rows(ctx, dec, table)
    finally:
        dec.pool.free(table)
    return (np.stack(rows), np.asarray(toks, np.int32),
            [np.concatenate(layer) for layer in ids],
            [np.concatenate(layer, axis=1) for layer in picks], states)


def as_mask(ids: np.ndarray, pools: int) -> np.ndarray:
    """Pool ids (..., picks), -1 for none -> (..., pools) bool."""
    mask = np.zeros(ids.shape[:-1] + (pools + 1,), bool)
    np.put_along_axis(mask, np.where(ids >= 0, ids, pools), True, axis=-1)
    return mask[..., :pools]


def outputs_of_reference(ctx, weights, toks, n_rows: int, **changed) -> Tuple:
    """The reference, free-running and ``changed`` (a precision, a state
    dtype, a count of pools taken, a count of Sinkhorn rounds), in the
    program's place: :func:`program_outputs`' tuple."""
    import jax.numpy as jnp

    out = ctx.reference.forward(weights, jnp.asarray(toks[None, :]),
                                ctx.config, rows=n_rows, **changed)
    return (np.asarray(out["logits"])[0], toks,
            [np.asarray(layer["ids"]) for layer in out["experts"]],
            [np.asarray(layer["own"]) for layer in out["sparse"]],
            [np.asarray(s)[0] for s in out["states"]])


def selection_numbers(config: Dict, sparse: List[Dict], positions: int
                      ) -> Dict:
    """Part (b) from the reference's ``sparse`` info of a forward that was
    given the program's picks."""
    pool = int(config["index_kpool"])
    picks = int(config["index_topk"]) // pool - 1
    past = int((np.arange(positions) // pool > picks).sum())
    differing = sum(int(np.asarray(layer["differ"]).sum()) for layer in sparse)
    worst = max((float(np.asarray(layer["shortfall"]).max())
                 for layer in sparse), default=0.0)
    pairs = past * picks * len(sparse)
    return {"selection_differing_share": differing / max(pairs, 1),
            "selection_score_margin": worst, "selection_pairs": pairs,
            "selection_differing": differing}


def free_running(ctx, weights, toks) -> List[Dict]:
    """The float32 reference's own routing over ``toks``: what fills the
    rows a program named no experts for."""
    import jax.numpy as jnp

    return ctx.reference.forward(weights, jnp.asarray(toks[None, :]),
                                 ctx.config, rows=1)["experts"]


def compare(ctx, weights, rows, toks, got_ids, got_picks, states,
            free: List[Dict] = None) -> Dict:
    """All four parts for one set of outputs; returns the numbers.
    ``free``: :func:`free_running` of the same tokens, where the caller
    has it already."""
    import jax.numpy as jnp

    ref, cfg = ctx.reference, ctx.config
    tok = jnp.asarray(toks[None, :])
    free = free_running(ctx, weights, toks) if free is None else free
    known = [np.all(np.asarray(g) >= 0, axis=-1) for g in got_ids]
    filled = [np.where(k[:, None], g, np.asarray(f["own_ids"]))
              for g, k, f in zip(got_ids, known, free)]
    forced = ref.forward(weights, tok, cfg, routing=filled,
                         selection=got_picks, rows=len(rows))

    def cut(layers):
        return [{key: np.asarray(layer[key])[k]
                 for key in ("scores", "own_ids")}
                for layer, k in zip(layers, known)]

    got = [np.asarray(g)[k] for g, k in zip(got_ids, known)]
    numbers = routed.routing_numbers(cfg, got, cut(forced["experts"]))
    numbers["free_running_differing_share"] = routed.routing_numbers(
        cfg, got, cut(free))["differing_share"]
    numbers.update(selection_numbers(cfg, forced["sparse"], len(toks)))
    want = np.asarray(forced["logits"])[0]
    numbers["logit_error"] = check.logit_error(rows, want)
    numbers["reference_logit_std"] = float(want.std())
    numbers["positions"] = len(rows)
    numbers.update(routed_states.state_numbers(
        states, [np.asarray(s)[0] for s in forced["states"]]))
    return numbers


def limits(config: Dict) -> Dict[str, float]:
    """Each compared number's limit, by the number's name."""
    sc = config["selection_check"]
    return dict(routed_states.limits(config),
                selection_score_margin=sc["score_margin"],
                selection_differing_share=sc["differing_share"])


CHECKS = dict(routed_states.CHECKS,
              selection_score_margin="serve.selection_score_margin",
              selection_differing_share="serve.selection_differing_share")


def compare_paged(ctx, inst, weights, checks) -> None:
    """``serving.compare_paged``'s place: every item of the mix's
    ``check`` through the programs and against the reference."""
    worst: Dict[str, float] = {}
    for index, item in enumerate(ctx.mix["check"]):
        n = compare(ctx, weights, *program_outputs(ctx, inst, item, index))
        tag = f"[{int(item['prompt_len'])}]"
        for key, limit in limits(ctx.config).items():
            checks.at_most(CHECKS[key] + tag, n[key], limit)
        for k, v in n.items():
            if isinstance(v, (int, float)):
                worst[k] = max(worst.get(k, v), v)
    ctx.note("routing, selection, paged logits and state rows compared, "
             "every check item")
    ctx.facts["serve_check"] = worst
