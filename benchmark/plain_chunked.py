"""What a cell needs whose prompts are prefilled in chunks and whose model
neither routes nor selects: the comparison that decides ``correct`` is
plain logits, on what the timed programs produced, and what the cell's
readers share.

The items of the mix's ``check`` LIST go through the programs the
scheduler drives TOGETHER and with every decode slot live, as the window
runs them (:func:`program_outputs`: each prompt chunk by chunk through
``PagedDecoder.prefill_chunk_at``, then greedy decode steps through the
paged cache, the items in the last slots and a filler request of its own
prompt in every other), each against ONE full forward of the float32
reference over its whole token sequence, by ``check.logit_error`` under
``limits.serve_logit_rel``. ``outputs_of_reference`` puts the reference
itself, at a lower precision, in the program's place: the control
(``control_chunked.py``). ``selected.build`` and ``selected.warm_up``
build the instance with the mix's ``prefill_chunk`` and warm the chunk
programs up: they are the chunked kind's and ask nothing of the model.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from benchmark import check, traffic


def program_outputs(ctx, inst) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Every item of the mix's ``check`` through the programs at once, no
    decode slot idle: the fillers (prompts of 1 to ``prefill_chunk``
    tokens, one chunk each, from the seed) are prefilled first, so the
    items' blocks and rows lie behind theirs, then each item's prompt
    chunk by chunk, then all slots decode greedily side by side, the items
    in the last slots. An item, in the list's order: the logits of its
    prompt's last position and of each of ITS decode steps (1 +
    decode_steps rows), and its whole token sequence. A kernel that reads
    another slot's row, block or lane at many live slots moves these
    logits; the fillers' own are not compared (no reference forward is
    paid for them)."""
    dec = inst.decoder
    items = ctx.mix["check"]
    vocab = int(ctx.config["vocab_size"])
    slots, c = dec.decode_slots, dec.prefill_chunk
    fillers = slots - len(items)
    steps = max(int(item["decode_steps"]) for item in items)
    prompts = [traffic.token_ids(ctx.seed, 2 * 10 ** 6 + j,
                                 1 + j * (c - 1) // max(fillers - 1, 1), vocab)
               for j in range(fillers)]
    prompts += [traffic.token_ids(ctx.seed, 10 ** 6 + index,
                                  int(item["prompt_len"]), vocab)
                for index, item in enumerate(items)]
    tables: List[np.ndarray] = []
    try:
        last = []
        for prompt in prompts:
            table = dec.pool.try_admit(len(prompt) + steps + 1)
            if table is None:
                raise RuntimeError("the pool cannot hold the check's "
                                   f"{slots} requests at once")
            tables.append(table)
            for at in range(0, len(prompt), c):
                logits = dec.prefill_chunk_at(prompt, table, at)
            last.append(logits)
        rows = [[logits] for logits in last[fillers:]]
        toks = [list(prompt) for prompt in prompts]
        for k in range(steps):
            for seq, logits in zip(toks, last):
                seq.append(int(logits.argmax()))
            last = dec.decode(
                np.asarray([seq[-1] for seq in toks], np.int32),
                np.stack(tables),
                np.asarray([len(p) + k for p in prompts], np.int32))
            for kept, logits in zip(rows, last[fillers:]):
                kept.append(logits)
    finally:
        for table in tables:
            dec.pool.free(table)
    out = []
    for item, kept, seq in zip(items, rows, toks[fillers:]):
        n = int(item["decode_steps"])
        out.append((np.stack(kept[:1 + n]),
                    np.asarray(seq[:int(item["prompt_len"]) + n], np.int32)))
    return out


def outputs_of_reference(ctx, weights, toks, n_rows: int, precision: str
                         ) -> np.ndarray:
    """The reference at ``precision`` over the whole sequence, at its last
    ``n_rows`` positions: those whose next token the program's rows
    predicted."""
    import jax.numpy as jnp

    return np.asarray(ctx.reference.forward(
        weights, jnp.asarray(toks[None, :]), ctx.config, precision,
        rows=n_rows))[0]


def compare(ctx, weights, rows, toks) -> Dict:
    ref = outputs_of_reference(ctx, weights, toks, len(rows), "float32")
    return {"logit_error": check.logit_error(rows, ref),
            "reference_logit_std": float(ref.std()),
            "positions": len(rows)}


def compare_paged(ctx, inst, weights, checks) -> None:
    """``serving.compare_paged``'s place: every item of the mix's
    ``check`` through the programs and against the reference."""
    worst: Dict[str, float] = {}
    for item, (rows, toks) in zip(ctx.mix["check"],
                                  program_outputs(ctx, inst)):
        n = compare(ctx, weights, rows, toks)
        checks.at_most(
            f"serve.paged_logits_vs_reference[{int(item['prompt_len'])}]",
            n["logit_error"], ctx.config["limits"]["serve_logit_rel"])
        for k, v in n.items():
            worst[k] = max(worst.get(k, v), v)
    ctx.note("paged logits compared, every check item")
    ctx.facts["serve_check"] = worst


# ---- what the cell's readers share -----------------------------------------

def _ends(run: Dict):
    f = run["facts"]
    s0, s1 = f.get("stats0"), f.get("stats1")
    return (s0, s1) if s0 and s1 else None


def chunks(run: Dict) -> Optional[Dict]:
    """The window's prefill chunks: ``chunks``, their live ``tokens`` and
    the ``keys`` their queries saw in one attention layer."""
    ends = _ends(run)
    if ends is None:
        return None
    l0, l1 = (s.get("loop") or {} for s in ends)
    n = l1.get("prefill_chunks", 0) - l0.get("prefill_chunks", 0)
    if n <= 0 or "prefill_keys" not in l1:
        return None
    return {"chunks": n,
            "tokens": l1["prefill_tokens"] - l0["prefill_tokens"],
            "keys": l1["prefill_keys"] - l0["prefill_keys"]}


def chunk_state_rows(run: Dict) -> Optional[Dict]:
    """State rows the window's chunks ``started`` from zeros and
    ``carried`` on from what the chunk before left."""
    ends = _ends(run)
    if ends is None:
        return None
    a, b = (s.get("kv", {}).get("state") or {} for s in ends)
    if "rows_carried" not in b:
        return None
    return {"started": b["rows_started"] - a.get("rows_started", 0),
            "carried": b["rows_carried"] - a.get("rows_carried", 0)}
