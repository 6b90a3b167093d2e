"""Device time of one execution of the paged decode program (``XLA
Modules`` events of ``jit__decode_step``), from the profiler's trace.
Layer: Paged decoder."""

from benchmark import reduce

PROGRAM = r"_decode_step"


def read(run):
    if run["trace"] is None:
        return None
    t = reduce.program_time(run["trace"], PROGRAM)
    return None if t is None else 1e3 * t["device_s"] / t["count"]
