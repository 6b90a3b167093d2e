"""Exclusive device milliseconds per execution of the paged decode program
(``jit__decode_step``) that lie under the ops of the group ``attention``,
every sub-scope (``project``, ``write``, ``attend``: the kernel and what
stands around it). The sub-scope ``attend`` alone of the
``MULTIHEAD_ATTENTION`` ops is ``decode_full_attention_device_ms``, from
the owner table of the traced window (``benchmark/owners.py``: an
operation's duration less what is nested inside it, by the scope in its
``op_name`` path). None where the profile holds no such scope. Layer: Paged
decoder."""

from benchmark import owners

PROGRAM = r"_decode_step"


def read(run):
    return owners.device_ms(run, PROGRAM, group="attention")
