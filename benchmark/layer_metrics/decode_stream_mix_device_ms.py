"""Exclusive device milliseconds per execution of the paged decode program
(``jit__decode_step``) that lie under the ops of the type ``STREAM_MIX``
(the residual streams spread, read into each sublayer's input, written
back under its Sinkhorn-projected mix, and summed), from the owner table of
the traced window (``benchmark/owners.py``). None where the profile holds
no such scope. Layer: Paged decoder."""

from benchmark import owners

PROGRAM = r"_decode_step"


def read(run):
    return owners.device_ms(run, PROGRAM, kinds=("STREAM_MIX",))
