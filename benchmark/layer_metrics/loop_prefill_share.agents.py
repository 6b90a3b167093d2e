"""Share of the scheduler thread's time, waiting for work left out, that
went to prefill dispatches and the fetch of their logits: the window's
``prefill`` of ``stats()["loop"]["phase_s"]`` over all phases but
``wait``, in %. Layer: Paged decoder."""

from benchmark import loop


def read(run):
    w = loop.window(run)
    return None if w is None else 100.0 * w["phase_s"]["prefill"] / w["busy_s"]
