"""Exclusive device milliseconds per execution of the paged decode program
(``jit__decode_step``) that lie under the ops of the type
``ROUTED_EXPERTS`` (the router and the held experts' products, in the
dense form: all 32 held experts of a layer read for 16 pairs; the shared
expert is a ``GATED_MLP`` op beside them), from the owner table of the traced
window (``benchmark/owners.py``: an operation's duration less what is
nested inside it, by the scope in its ``op_name`` path). None where the
profile holds no such scope. Layer:
Expert layer."""

from benchmark import owners

PROGRAM = r"_decode_step"


def read(run):
    return owners.device_ms(run, PROGRAM, kinds=('ROUTED_EXPERTS',), subs=())
