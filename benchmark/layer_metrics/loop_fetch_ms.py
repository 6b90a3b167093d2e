"""Time a decode step spends in the fetch of its result, the wait for the
device and the copy to the host: the window's ``fetch`` of
``stats()["loop"]["phase_s"]`` per step (a prefill's fetch is in
``prefill``). Since PR 29 a greedy session dispatches step n+1 before it
fetches step n's ids (4 bytes a slot), so the fetch waits only for what
is left of a step already under way and reads BELOW the decode program's
device time; a sampled session fetches the logits and waits the whole
step. Layer: Paged decoder."""

from benchmark import loop


def read(run):
    w = loop.window(run)
    return None if w is None else 1e3 * w["phase_s"]["fetch"] / w["steps"]
