"""Time a decode pass spends waiting for the device: the window's
``fetch`` of ``stats()["loop"]["phase_s"]`` per step. Where the loop runs
one step ahead (greedy, as this cell is) it is the ``np.asarray`` of the
ids of the step before the one just dispatched, 4 bytes a slot: what is
left of that program when the host arrives, the host's slack, and so
below the decode program's device time (a prefill's fetch is in
``prefill``). Layer: Paged decoder."""

from benchmark import loop


def read(run):
    w = loop.window(run)
    return None if w is None else 1e3 * w["phase_s"]["fetch"] / w["steps"]
