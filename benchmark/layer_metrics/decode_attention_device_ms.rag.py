"""Exclusive device milliseconds per execution of the paged decode program
(``jit__decode_step``) under the sub-scope ``attend`` of the
``MULTIHEAD_ATTENTION`` ops: the four attention layers' read of every
slot's live blocks (32 query heads on 8 key-value heads of 64, through
the paged kernel in place; before the kernel took heads of half a lane
tile it was a gather of every slot's whole table and read 36 ms of a 60
ms step), from the owner table of the traced window
(``benchmark/owners.py``). None where the profile holds no such scope.
Layer: Kernels."""

from benchmark import owners

PROGRAM = r"_decode_step"


def read(run):
    return owners.device_ms(run, PROGRAM, kinds=("MULTIHEAD_ATTENTION",),
                            subs=("attend",))
