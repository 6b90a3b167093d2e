"""Tokens a decode step produced over the decode slots, in %: the deltas
across the window of ``stats()["tokens"]`` (less the first tokens, which
prefills produce: ``prefill_prompts``) and ``stats()["decode_steps"]``.
Layer: Scheduler."""

from benchmark import serving


def read(run):
    f = run["facts"]
    s0, s1 = f.get("stats0"), f.get("stats1")
    if not s0 or not s1:
        return None
    per_step = serving.decode_tokens_per_step(s0, s1)
    if per_step is None:
        return None
    return 100.0 * per_step / s1["knobs"]["decode_slots"]
