"""Exclusive device milliseconds per execution of the paged decode program
(``jit__decode_step``) that lie under the sub-scope ``select`` of the group
``attention`` (an indexer's projections, its scores over the pooled keys
and its top-k), from the owner table of the traced window
(``benchmark/owners.py``: an operation's duration less what is nested
inside it, by the scope in its ``op_name`` path). None where the profile
holds no such scope. Layer: Kernels."""

from benchmark import owners

PROGRAM = r"_decode_step"


def read(run):
    return owners.device_ms(run, PROGRAM, group="attention", subs=("select",))
