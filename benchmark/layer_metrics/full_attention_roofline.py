"""The full attention layers' decode attend against its roofline, in %:
the least time it could take (the live rows' keys and values once, keys
of ``head_dim`` beside values of ``v_head_dim`` at the full layers'
key-value heads, rows to each slot's length with no block padding, over
the HBM peak: the family's ``full_attend_least_s`` over its
``counts*.py`` and the window's ``rows_full`` a step) over the device
time under the sub-scope ``attend`` itself (not ``window`` inside it) of
the ``MULTIHEAD_ATTENTION`` ops in the decode program, kernel or not
(``benchmark/scope_share.py``). Layer: Kernels."""

from benchmark import scope_share

PROGRAM = r"_decode_step"
ASKS = "full_attend_least_s"


def read(run):
    return scope_share.attention_share(run, PROGRAM, ("attend",), ASKS)
