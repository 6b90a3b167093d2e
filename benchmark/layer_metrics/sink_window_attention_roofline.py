"""The windowed layers' decode attend behind their sinks against its
roofline, in %: the least time it could take (the visible rows' keys and
values once, ``min(length + 1, window)`` rows a slot and windowed layer at
the windowed layers' key-value heads, keys of ``head_dim`` beside values
of ``v_head_dim``, over the HBM peak: the family's
``window_attend_least_s`` over its ``counts*.py`` and the window's
``rows_read`` a step; a sink is a number a head and counts for nothing)
over the device time under the sub-scope ``window`` (inside ``attend``) of
the ``MULTIHEAD_ATTENTION`` ops in the decode program, kernel or not
(``benchmark/scope_share.py``). At a ring of two blocks the rows are few
and what is left is the read's fixed costs: the share says how far those
are from the bytes. Layer: Kernels."""

from benchmark import scope_share

PROGRAM = r"_decode_step"
ASKS = "window_attend_least_s"


def read(run):
    return scope_share.attention_share(run, PROGRAM, ("window",), ASKS)
