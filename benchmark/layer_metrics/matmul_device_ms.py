"""Exclusive device milliseconds per execution of the step program
(``jit_train_step``) that lie under the ops of the group ``matmul``
(``LINEAR`` and its kin, the head among them; forward and backward), from
the owner table of the traced window (``benchmark/owners.py``: an
operation's duration less what is nested inside it, by the scope in its
``op_name`` path). None where the profile holds no such scope. Layer: Step
program."""

from benchmark import owners

PROGRAM = r"train_step"


def read(run):
    return owners.device_ms(run, PROGRAM, group="matmul")
