"""Time a decode step spends in ``np.asarray(logits)``, the wait for the
device and the copy of the logits to the host: the window's ``fetch`` of
``stats()["loop"]["phase_s"]`` per step (a prefill's fetch is in
``prefill``). Not below the decode program's device time. Layer: Paged
decoder."""

from benchmark import loop


def read(run):
    w = loop.window(run)
    return None if w is None else 1e3 * w["phase_s"]["fetch"] / w["steps"]
