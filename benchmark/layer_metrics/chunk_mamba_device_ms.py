"""Exclusive device milliseconds per execution of a prefill chunk program
(``jit__chunk_step`` and ``jit__chunk_step_head``) that lie under the ops
of the type ``MAMBA2`` (the state-space mixers: their projections, the
convolution behind the kept tails, the blocked recurrence under ``rule``,
the states' and tails' way back), from the owner table of the traced window
(``benchmark/owners.py``: an operation's duration less what is nested
inside it, by the scope in its ``op_name`` path). None where the profile
holds no such scope. Layer: Paged decoder."""

from benchmark import owners

PROGRAM = r"_chunk_step"


def read(run):
    return owners.device_ms(run, PROGRAM, kinds=("MAMBA2",))
