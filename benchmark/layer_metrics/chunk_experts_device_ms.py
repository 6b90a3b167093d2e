"""Exclusive device milliseconds per execution of a prefill chunk program
(``jit__chunk_step`` and ``jit__chunk_step_head``) that lie under the ops
of the type ``ROUTED_EXPERTS`` (the router and the grouped kernel over the
pairs the routing named among the held experts), from the owner table of
the traced window (``benchmark/owners.py``: an operation's duration less
what is nested inside it, by the scope in its ``op_name`` path). None where
the profile holds no such scope. Layer: Expert layer."""

from benchmark import owners

PROGRAM = r"_chunk_step"


def read(run):
    return owners.device_ms(run, PROGRAM, kinds=("ROUTED_EXPERTS",))
