"""1 minus the union of the device's operation intervals over the traced
window, in %: the first seconds of a serving window, or one whole ``fit``
call (the training cell's entry, ``device_idle_share.train``, stands apart
because it moves ``train_tokens_per_s``; it is read here). Layer:
Device."""


def read(run):
    tr = run["trace"]
    return None if tr is None else 100.0 * tr["idle_share"]
