"""Device time of one execution of the bucketed prefill program (``XLA
Modules`` events of ``jit__prefill_step``, every bucket of the traced
window together), from the profiler's trace. Layer: Paged decoder."""

from benchmark import reduce

PROGRAM = r"_prefill_step"


def read(run):
    if run["trace"] is None:
        return None
    t = reduce.program_time(run["trace"], PROGRAM)
    return None if t is None else 1e3 * t["device_s"] / t["count"]
