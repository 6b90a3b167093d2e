"""Exclusive device milliseconds per execution of the step program
(``jit_train_step``) that lie under the fixed scope ``ff.optimizer`` (the
update; a fusion that holds a weight-gradient product AND the update goes
whole to the owner XLA names for it), from the owner table of the traced
window (``benchmark/owners.py``: an operation's duration less what is
nested inside it, by the scope in its ``op_name`` path). None where the
profile holds no such scope. Layer: Step program."""

from benchmark import owners

PROGRAM = r"train_step"


def read(run):
    return owners.device_ms(run, PROGRAM, kinds=("optimizer",))
