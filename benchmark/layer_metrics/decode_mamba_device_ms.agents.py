"""Exclusive device milliseconds per execution of the paged decode program
(``jit__decode_step``) that lie under the ops of the type ``MAMBA2`` (the
state-space layers: their projections, the convolution over the kept
tails, the states' update where they lie, the tails' way back), from the
owner table of the traced window (``benchmark/owners.py``). None where
the profile holds no such scope. Layer: Paged decoder."""

from benchmark import owners

PROGRAM = r"_decode_step"


def read(run):
    return owners.device_ms(run, PROGRAM, kinds=("MAMBA2",))
