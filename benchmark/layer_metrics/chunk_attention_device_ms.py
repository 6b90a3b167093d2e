"""Exclusive device milliseconds per execution of a prefill chunk program
(``jit__chunk_step`` and ``jit__chunk_step_head``) under the sub-scopes
``attend`` (a full layer's walk over the key spans of its table) and
``window`` (a windowed layer's over its ring and the chunk) of the
``MULTIHEAD_ATTENTION`` ops; a model without windowed layers has only the
first, from the owner table of the traced window (``benchmark/owners.py``:
an operation's duration less what is nested inside it, by the scope in its
``op_name`` path). None where the profile holds no such scope. Layer:
Kernels."""

from benchmark import owners

PROGRAM = r"_chunk_step"


def read(run):
    return owners.device_ms(run, PROGRAM, kinds=("MULTIHEAD_ATTENTION",),
                            subs=("attend", "window"))
