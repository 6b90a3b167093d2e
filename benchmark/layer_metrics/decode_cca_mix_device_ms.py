"""Exclusive device milliseconds per execution of the paged decode program
(``jit__decode_step``) under the sub-scopes ``project``, ``mix`` and
``write`` of the ``COMPRESSED_CONV_ATTENTION`` ops, all layers together:
the projections of q, k and the two half values, the two causal
convolutions over a request's tail with the q-k mean, the lengths and the
rotation, and the writes of the pair, the tail and the half value; nothing
of the attend or the output projection. From the owner table of the traced
window (``benchmark/owners.py``: an operation's duration less what is
nested inside it, by the scope in its ``op_name`` path). None where the
profile holds no such scope. Layer: Paged decoder."""

from benchmark import owners

PROGRAM = r"_decode_step"
KIND = "COMPRESSED_CONV_ATTENTION"


def read(run):
    return owners.device_ms(run, PROGRAM, kinds=(KIND,),
                            subs=("project", "mix", "write"))
