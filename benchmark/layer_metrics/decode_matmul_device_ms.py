"""Exclusive device milliseconds per execution of the paged decode program
(``jit__decode_step``) that lie under the ops of the group ``matmul``
(``LINEAR``, ``GATED_MLP``, ``EXPERT_LINEAR`` and ``ROUTED_EXPERTS``: the
layers' projections and MLPs, the shared and the routed experts, the head),
from the
owner table of the traced window (``benchmark/owners.py``: an operation's
duration less what is nested inside it, by the scope in its ``op_name``
path). None where the profile holds no such scope. The latent model's
entry, ``decode_matmul_device_ms.reasoning``, stands apart because there the
group is the expert layer's work (layer: Expert layer); it is read here.
Layer: Paged decoder."""

from benchmark import owners

PROGRAM = r"_decode_step"


def read(run):
    return owners.device_ms(run, PROGRAM, group="matmul")
