"""``benchmark/routed.py``'s two-part comparison for a routed model whose
prompts are prefilled in chunks, and what its cell's readers share.

The comparison is ``routed.compare`` as it is (routing first, then logits
under the program's routing, both from ONE forward of the reference that
takes the program's routing); what differs is how the programs' outputs
are gathered: a prompt goes through the chunk programs chunk by chunk
(``PagedDecoder.prefill_chunk_at``), each chunk's routing kept, and the
mix's ``check`` is a LIST of prompts, every one of which is compared
(a prompt that wraps a windowed layer's ring and one that never fills
it). ``selected.build`` and ``selected.warm_up`` build the instance with
the mix's ``prefill_chunk`` and warm the chunk programs up: they are the
chunked kind's and ask nothing of the model.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from benchmark import routed, traffic


def program_outputs(ctx, inst, item: Dict, index: int
                    ) -> Tuple[np.ndarray, np.ndarray, List]:
    """Check item ``index``'s prompt prefilled chunk by chunk and a few
    greedy decode steps through the paged cache, in slot 0 with the other
    slots idle: the logits of each step (1 + decode_steps rows), the whole
    token sequence, and per expert layer the (len(tokens), k) expert ids
    the programs chose, -1 where a program ran no such layer for a token
    (the last layer's experts for the tokens of all chunks but the last:
    nothing reads what they would give)."""
    dec = inst.decoder
    names = ctx.family.expert_layer_names(ctx.config)
    n, steps = int(item["prompt_len"]), int(item["decode_steps"])
    prompt = traffic.token_ids(ctx.seed, 10 ** 6 + index, n,
                               int(ctx.config["vocab_size"]))
    table = dec.pool.try_admit(n + steps + 1)
    slots, c = dec.decode_slots, dec.prefill_chunk
    picks = int(ctx.config["num_experts_per_tok"])
    rows, toks = [], list(prompt)
    ids: List[List[np.ndarray]] = [[] for _ in names]
    try:
        for at in range(0, n, c):
            logits = dec.prefill_chunk_at(prompt, table, at)
            live = min(c, n - at)
            for j, name in enumerate(names):
                # a chunk that is not its prompt's last ends behind the
                # last attention op, and the last one goes on from there
                # with its last position alone: the experts after that op
                # named nothing for the other tokens
                got = dec.last_routing.get(name)
                got = (np.zeros((0, picks), np.int32) if got is None
                       else np.asarray(got)[0, :live])
                ids[j] += [np.full((live - len(got), picks), -1, np.int32),
                           got]
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
            [np.concatenate(layer) for layer in ids])


def compare(ctx, weights, rows, toks, got_ids) -> Dict:
    """``routed.compare`` where ``got_ids`` may hold rows of -1 (no
    routing: the reference then takes its own free-running choice there,
    and the row is in neither count)."""
    import jax.numpy as jnp

    from benchmark import check

    ref, cfg = ctx.reference, ctx.config
    tok = jnp.asarray(toks[None, :])
    _, free = ref.forward_with_routing(weights, tok, cfg, "float32")
    known = [np.all(np.asarray(g) >= 0, axis=-1) for g in got_ids]
    filled = [np.where(k[:, None], g, np.asarray(f["own_ids"]))
              for g, k, f in zip(got_ids, known, free)]
    forced, info = ref.forward_with_routing(weights, tok, cfg, "float32",
                                            routing=filled)

    def cut(layers):
        return [{key: np.asarray(layer[key])[k]
                 for key in ("scores", "own_ids")}
                for layer, k in zip(layers, known)]

    got = [np.asarray(g)[k] for g, k in zip(got_ids, known)]
    numbers = routed.routing_numbers(cfg, got, cut(info))
    numbers["free_running_differing_share"] = routed.routing_numbers(
        cfg, got, cut(free))["differing_share"]
    want = np.asarray(forced)[0, len(toks) - len(rows):]
    numbers["logit_error"] = check.logit_error(rows, want)
    numbers["reference_logit_std"] = float(want.std())
    numbers["positions"] = len(rows)
    return numbers


def compare_paged(ctx, inst, weights, checks) -> None:
    """``serving.compare_paged``'s place: every item of the mix's
    ``check`` through the programs and against the reference."""
    rc = ctx.config["routing_check"]
    worst: Dict[str, float] = {}
    for index, item in enumerate(ctx.mix["check"]):
        rows, toks, got_ids = program_outputs(ctx, inst, item, index)
        n = compare(ctx, weights, rows, toks, got_ids)
        tag = f"[{int(item['prompt_len'])}]"
        checks.at_most("serve.routing_score_margin" + tag,
                       n["score_margin"], rc["score_margin"])
        checks.at_most("serve.routing_differing_share" + tag,
                       n["differing_share"], rc["differing_share"])
        checks.at_most("serve.paged_logits_vs_reference" + tag,
                       n["logit_error"],
                       ctx.config["limits"]["serve_logit_rel"])
        for k, v in n.items():
            if isinstance(v, (int, float)):
                worst[k] = max(worst.get(k, v), v)
    ctx.note("routing and paged logits compared, every check item")
    ctx.facts["serve_check"] = worst


# ---- what the cell's readers share -----------------------------------------

def _delta(run: Dict, *path) -> Optional[Dict]:
    """The window's deltas of the numbers under ``stats()[path...]``."""
    f = run["facts"]
    a, b = f.get("stats0"), f.get("stats1")
    for key in path:
        a = (a or {}).get(key)
        b = (b or {}).get(key)
    if not isinstance(a, dict) or not isinstance(b, dict):
        return None
    return {k: b[k] - a[k] for k in b
            if isinstance(b[k], (int, float)) and not isinstance(b[k], bool)
            and isinstance(a.get(k), (int, float))}


def window_rows(run: Dict) -> Optional[Dict]:
    """``stats()["kv"]["window"]`` over the window, one windowed op's:
    ``rows_read``, ``rows_full``, ``rows_reserved`` (sums over the decode
    steps' active slots) and ``steps``."""
    d = _delta(run, "kv", "window")
    f = run["facts"]
    if not d or d.get("rows_full", 0) <= 0:
        return None
    steps = f["stats1"]["decode_steps"] - f["stats0"]["decode_steps"]
    return dict(d, steps=steps) if steps > 0 else None


def chunks(run: Dict) -> Optional[Dict]:
    """The window's prefill chunks: ``chunks``, ``tokens``, the keys
    their queries saw in a full layer and in a windowed one, and the
    pairs their routing named among the held experts (all layers)."""
    d = _delta(run, "loop")
    if not d or d.get("prefill_chunks", 0) <= 0 or "prefill_keys" not in d:
        return None
    f = run["facts"]
    m0, m1 = f["stats0"].get("moe"), f["stats1"].get("moe")
    if not m0 or not m1:
        return None
    pairs = sum(m1[k]["prompt_pairs_held"] - m0[k]["prompt_pairs_held"]
                for k in m1)
    return {"chunks": d["prefill_chunks"], "tokens": d["prefill_tokens"],
            "keys_full": d["prefill_keys"],
            "keys_window": d["prefill_keys_window"], "pairs_held": pairs}
