"""Exclusive device milliseconds per execution of the paged decode program
(``jit__decode_step``) under the sub-scope ``window`` (inside ``attend``)
of the ``MULTIHEAD_ATTENTION`` ops: the windowed layers' read of their
rings, all of them together, from the owner table of the traced window
(``benchmark/owners.py``: an operation's duration less what is nested
inside it, by the scope in its ``op_name`` path). None where the profile
holds no such scope. Layer: Kernels."""

from benchmark import owners

PROGRAM = r"_decode_step"


def read(run):
    return owners.device_ms(run, PROGRAM, kinds=("MULTIHEAD_ATTENTION",),
                            subs=("window",))
