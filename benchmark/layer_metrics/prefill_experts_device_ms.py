"""Exclusive device milliseconds per execution of the bucketed prefill program
(``jit__prefill_step``, every bucket of the traced window together) that
lie under the ops of the type ``ROUTED_EXPERTS`` (the router, the latent
projections and the held experts' products), from the owner table of the
traced window (``benchmark/owners.py``: an operation's duration less what
is nested inside it, by the scope in its ``op_name`` path). None where the
profile holds no such scope. None too where no prefill ran whole inside the
window. Layer: Expert layer."""

from benchmark import owners

PROGRAM = r"_prefill_step"


def read(run):
    return owners.device_ms(run, PROGRAM, kinds=("ROUTED_EXPERTS",))
