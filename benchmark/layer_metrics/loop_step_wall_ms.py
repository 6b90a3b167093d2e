"""Wall time of one pass of the scheduler's loop, on the scheduler's own
clock: the window's delta of ``stats()["loop"]["phase_s"]`` over all
phases but ``wait``, over the delta of ``steps``. Times the steps in the
window give the window back. Layer: Scheduler."""

from benchmark import loop


def read(run):
    w = loop.window(run)
    return None if w is None else 1e3 * w["busy_s"] / w["steps"]
