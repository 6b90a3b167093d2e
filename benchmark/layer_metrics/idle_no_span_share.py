"""Share of the device's idle time in the traced window that lies under
no host span at all, in %: ``(no span)`` of the reduction's
``idle_gaps`` over ``window_s - busy_s``. 0 where ``(no span)`` is not
among the gaps the reduction keeps (its ten largest). Layer: Device."""


def read(run):
    tr = run["trace"]
    if tr is None:
        return None
    idle_s = tr["window_s"] - tr["busy_s"]
    if idle_s <= 0:
        return None
    no_span = sum(s for name, s in tr["idle_gaps"] if name == "(no span)")
    return 100.0 * no_span / idle_s
