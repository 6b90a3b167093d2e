"""Exclusive device milliseconds per execution of the paged decode program
(``jit__decode_step``) that lie under the ops of the type ``MAMBA2`` (the
36 state-space mixers: their projections, the convolution over the kept
tails, the states' update where they lie, the tails' way back), from the
owner table of the traced window (``benchmark/owners.py``). The compiler
brings each layer's arena of states into fast memory ahead of its update
by copies of its own, which run beside other ops and carry no scope: the
states' way IN is not in this number (nor under ``rule``: a share of the
states' roofline by that scope read 184 % here and is not shipped), their
way out is. None where the profile holds no such scope. Layer: Paged
decoder."""

from benchmark import owners

PROGRAM = r"_decode_step"


def read(run):
    return owners.device_ms(run, PROGRAM, kinds=("MAMBA2",))
