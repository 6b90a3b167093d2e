"""Exclusive device milliseconds per execution of the paged decode program
(``jit__decode_step``) under the sub-scope ``attend`` itself of the
``MULTIHEAD_ATTENTION`` ops (a windowed layer's attend lies under
``window`` inside it and is not here): the full attention layers' read of
every slot's live blocks, and nothing of their projections or writes (the
whole group is ``decode_attention_device_ms``), from the owner table of the
traced window (``benchmark/owners.py``: an operation's duration less what
is nested inside it, by the scope in its ``op_name`` path). None where the
profile holds no such scope. Layer: Kernels."""

from benchmark import owners

PROGRAM = r"_decode_step"


def read(run):
    return owners.device_ms(run, PROGRAM, kinds=("MULTIHEAD_ATTENTION",),
                            subs=("attend",))
