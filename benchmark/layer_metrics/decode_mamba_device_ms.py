"""Exclusive device milliseconds per execution of the paged decode program
(``jit__decode_step``) that lie under the ops of the type ``MAMBA2`` (the
state-space layers: their projections, the convolution over the kept tails,
the states' update where they lie, the tails' way back). Since PR 47 the
states are stepped in place by one kernel under ``rule``, which reads each
live row once and writes it once: the states' way in and their way out are
both in this number (``mamba_state_roofline`` holds ``rule`` to its bytes),
from the owner table of the traced window (``benchmark/owners.py``: an
operation's duration less what is nested inside it, by the scope in its
``op_name`` path). None where the profile holds no such scope. Layer: Paged
decoder."""

from benchmark import owners

PROGRAM = r"_decode_step"


def read(run):
    return owners.device_ms(run, PROGRAM, kinds=("MAMBA2",))
