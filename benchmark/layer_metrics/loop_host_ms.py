"""Host time of a decode step that is neither prefill nor the wait for
the device: the window's ``admit`` + ``inputs`` + ``dispatch`` +
``sample`` + ``other`` of ``stats()["loop"]["phase_s"]``, per step.
Layer: Scheduler."""

from benchmark import loop


def read(run):
    w = loop.window(run)
    if w is None:
        return None
    return 1e3 * sum(w["phase_s"][k] for k in loop.HOST_PHASES) / w["steps"]
