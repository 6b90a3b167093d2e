"""Wall time of one pass of the scheduler's loop that ran a decode step,
on the scheduler's own clock: the window's delta of
``stats()["loop"]["phase_s"]`` over all phases but ``wait``, over the
delta of ``steps`` (a pass that ran only a chunk, no slot decoding yet,
adds to the time and not to the steps). Layer: Scheduler."""

from benchmark import loop


def read(run):
    w = loop.window(run)
    return None if w is None else 1e3 * w["busy_s"] / w["steps"]
