"""Device time of one execution of the step program (``XLA Modules``
events of ``jit_train_step``), per chip, from the profiler's trace.
Layer: Step program."""

from benchmark import reduce

PROGRAM = r"train_step"


def read(run):
    if run["trace"] is None:
        return None
    t = reduce.program_time(run["trace"], PROGRAM)
    return None if t is None else 1e3 * t["device_s"] / t["count"]
