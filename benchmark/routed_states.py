"""``benchmark/routed.py``'s two-part comparison with a third part for a
model that keeps a float32 recurrent state a request beside its routed
experts: what the timed programs LEFT in the pool.

The logits cannot hold a state to its precision. A program that rounds
its residual stream to bfloat16 reads a ``logit_error`` of 0.0073 where
the reference with every state rounded to bfloat16 after every token
reads 0.0042 (``PERF.md`` section 2), so a state arena kept in bfloat16,
half a decode step's state bytes, would pass parts (a) and (b) as a
gain. So:

(c) *states*: after the prompt's prefill and the decode steps, and
    before the request's table is freed, the request's row of every
    state op's arena is read back and set against the state the float32
    reference's recurrence holds after the same tokens under the
    program's routing:

    * ``state_error``: the largest, over the state layers, of
      ``||S_program - S_reference|| / ||S_reference||``, under
      ``state_check.error``. It says that the pool's rows are the
      recurrence's state at the prompt's TRUE length and after every
      step. It does NOT tell a state kept in bfloat16 (1.4 times the
      sound program's own reading after 708 roundings): the next number
      does;
    * ``state_coarse_share``: the share of the rows' non-zero numbers
      that bfloat16 holds exactly (their low 16 bits are 0), under
      ``state_check.coarse_share``. A float32 state reads 2^-16 of them;
      a state that went through bfloat16 on its way to or from the arena
      reads 1, however few the steps; one that went through float16 or
      tensorfloat32 an eighth.

``control_ling.py`` puts the reference with its states kept in bfloat16
in the program's place; part (c) has to refuse it.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from benchmark import check, routed, traffic


def program_outputs(ctx, inst) -> Tuple[np.ndarray, np.ndarray, List, List]:
    """``routed.program_outputs`` with the request's state rows read
    before its table is freed: per state layer ``(H, d_k, d_v)``
    float32, as the arena holds them."""
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
        states = state_rows(ctx, dec, table)
    finally:
        dec.pool.free(table)
    return (np.stack(rows), np.asarray(toks, np.int32),
            [np.concatenate(layer) for layer in ids], states)


def state_rows(ctx, dec, table) -> List[np.ndarray]:
    """The row ``table``'s request holds in each state op's arena
    (``(d_k, H d_v)``, the heads side by side), as ``(H, d_k, d_v)``."""
    row = int(dec.pool.rows_of(np.asarray(table)[None])[0])
    out = []
    for name in ctx.family.state_layer_names(ctx.config):
        kind = dec.pool.kinds[name]
        lanes = np.asarray(dec.pool.kv[name][0][row])
        out.append(np.moveaxis(lanes.reshape(
            kind.key_dim, kind.heads, kind.value_dim), 1, 0))
    return out


def state_numbers(got: List[np.ndarray], want: List[np.ndarray]) -> Dict:
    """Part (c) for one set of states against the reference's."""
    if len(got) != len(want):
        raise ValueError(f"{len(got)} states against {len(want)}")
    flat = np.concatenate([np.asarray(s, np.float32).ravel() for s in got])
    live = flat[flat != 0]
    coarse = (live.view(np.uint32) & 0xFFFF) == 0
    return {"state_error": max(check.rel_l2(g, w) for g, w in zip(got, want)),
            "state_coarse_share": float(coarse.mean()) if live.size else 1.0,
            "state_layers": len(got)}


def compare(ctx, weights, rows, toks, got_ids, states) -> Dict:
    """All three parts for one set of outputs; returns the numbers."""
    import jax.numpy as jnp

    numbers = routed.compare(ctx, weights, rows, toks, got_ids)
    want = ctx.reference.forward_with_states(
        weights, jnp.asarray(toks[None, :]), ctx.config, "float32",
        routing=got_ids)[2]
    numbers.update(state_numbers(states, [np.asarray(s)[0] for s in want]))
    return numbers


def limits(config: Dict) -> Dict[str, float]:
    """Each compared number's limit, by the number's name."""
    rc, sc = config["routing_check"], config["state_check"]
    return {"score_margin": rc["score_margin"],
            "differing_share": rc["differing_share"],
            "logit_error": config["limits"]["serve_logit_rel"],
            "state_error": sc["error"],
            "state_coarse_share": sc["coarse_share"]}


CHECKS = {"score_margin": "serve.routing_score_margin",
          "differing_share": "serve.routing_differing_share",
          "logit_error": "serve.paged_logits_vs_reference",
          "state_error": "serve.state_rows_vs_reference",
          "state_coarse_share": "serve.state_rows_coarse_share"}


def compare_paged(ctx, inst, weights, checks) -> None:
    """``routed.compare_paged``'s place, for a routed model with states."""
    n = compare(ctx, weights, *program_outputs(ctx, inst))
    for key, limit in limits(ctx.config).items():
        checks.at_most(CHECKS[key], n[key], limit)
    ctx.note("routing, paged logits and state rows compared")
    ctx.facts["serve_check"] = {k: v for k, v in n.items()
                                if isinstance(v, (int, float))}
