"""Exclusive device milliseconds per execution of the paged decode program
(``jit__decode_step``) that lie under the ops of the type
``KIMI_DELTA_ATTENTION`` (the KDA layers: their projections, the decay's
full-rank projection and sigmoid under ``gate``, the convolution over the
kept tails, the states' update where they lie under ``rule``, the tails'
way back, the per-head norm and gate under ``out``), from the owner table
of the traced window (``benchmark/owners.py``: an operation's duration
less what is nested inside it, by the scope in its ``op_name`` path). None
where the profile holds no such scope. Layer: Paged decoder."""

from benchmark import owners

PROGRAM = r"_decode_step"


def read(run):
    return owners.device_ms(run, PROGRAM, kinds=("KIMI_DELTA_ATTENTION",))
