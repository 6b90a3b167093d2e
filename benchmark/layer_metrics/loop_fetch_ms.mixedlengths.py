"""Time a decode pass spends waiting for the device: the window's
``fetch`` of ``stats()["loop"]["phase_s"]`` per step. The loop runs one
step ahead, and a chunk dispatched between two steps is not waited for,
so the fetch of a step's ids waits for the chunk queued before it too:
here it holds most of a chunk's device time. Layer: Paged decoder."""

from benchmark import loop


def read(run):
    w = loop.window(run)
    return None if w is None else 1e3 * w["phase_s"]["fetch"] / w["steps"]
