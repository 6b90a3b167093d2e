"""Device time of one execution of a prefill chunk program (``XLA
Modules`` events of ``jit__chunk_step`` and ``jit__chunk_step_head``: a
whole chunk of the mix's ``prefill_chunk`` tokens behind the cache and,
where the model keeps them, the states; the second with the head for its
last row), from the profiler's trace. Layer: Paged decoder."""

from benchmark import reduce

PROGRAM = r"_chunk_step"


def read(run):
    if run["trace"] is None:
        return None
    t = reduce.program_time(run["trace"], PROGRAM)
    return None if t is None else 1e3 * t["device_s"] / t["count"]
