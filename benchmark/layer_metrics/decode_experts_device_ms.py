"""Exclusive device milliseconds per execution of the paged decode program
(``jit__decode_step``) that lie under the ops of the type
``ROUTED_EXPERTS`` (the router, a latent model's projections, and the held
experts' products: since PR 43 the grouped kernel over the experts the
step's routing named, wherever the slots can name under 0.9 of those held;
a shared expert is ``LINEAR`` or ``GATED_MLP`` ops beside them and not
here), from the owner table of the traced window (``benchmark/owners.py``:
an operation's duration less what is nested inside it, by the scope in its
``op_name`` path). None where the profile holds no such scope. Layer:
Expert layer."""

from benchmark import owners

PROGRAM = r"_decode_step"


def read(run):
    return owners.device_ms(run, PROGRAM, kinds=("ROUTED_EXPERTS",))
