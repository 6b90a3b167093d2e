"""1 minus the union of the device's operation intervals over the traced
window (the first seconds of the serving window), in %. Layer: Device."""


def read(run):
    tr = run["trace"]
    return None if tr is None else 100.0 * tr["idle_share"]
