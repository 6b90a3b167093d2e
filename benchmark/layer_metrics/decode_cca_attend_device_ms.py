"""Exclusive device milliseconds per execution of the paged decode program
(``jit__decode_step``) under the sub-scope ``attend`` of the
``COMPRESSED_CONV_ATTENTION`` ops, all layers together: the read of every
slot's live blocks of keys and values (the paged decode kernel at 8 query
heads on 2 key-value heads of 128), from the owner table of the traced
window (``benchmark/owners.py``). None where the profile holds no such
scope. Layer: Kernels."""

from benchmark import owners

PROGRAM = r"_decode_step"
KIND = "COMPRESSED_CONV_ATTENTION"


def read(run):
    return owners.device_ms(run, PROGRAM, kinds=(KIND,), subs=("attend",))
