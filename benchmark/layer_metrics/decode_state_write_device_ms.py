"""Exclusive device milliseconds per execution of the paged decode program
(``jit__decode_step``) that lie under the sub-scopes ``write`` and ``conv``
of the group ``state`` (the convolution over the kept tails and the tails'
write back; since PR 39 the states themselves are updated where they lie,
under ``rule``, and no scatter stands here), from the owner table of the
traced window (``benchmark/owners.py``: an operation's duration less what
is nested inside it, by the scope in its ``op_name`` path). None where the
profile holds no such scope. Layer: KV pool."""

from benchmark import owners

PROGRAM = r"_decode_step"


def read(run):
    return owners.device_ms(run, PROGRAM, group="state",
                            subs=("write", "conv"))
