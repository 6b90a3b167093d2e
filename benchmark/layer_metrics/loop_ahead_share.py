"""Decode steps dispatched while the step before was still unread, over
all steps of the window, in %: the window's delta of
``stats()["loop"]["ahead"]["steps_ahead"]`` over that of
``stats()["loop"]["steps"]``. None where the program has no ``ahead``
(any before the loop ran a step ahead). Layer: Scheduler."""

from benchmark import loop


def read(run):
    ends = loop._ends(run)
    if ends is None or "ahead" not in ends[0] or "ahead" not in ends[1]:
        return None
    steps = ends[1]["steps"] - ends[0]["steps"]
    if steps <= 0:
        return None
    return 100.0 * (ends[1]["ahead"]["steps_ahead"]
                    - ends[0]["ahead"]["steps_ahead"]) / steps
