"""Exclusive device milliseconds per execution of a prefill chunk program
(``jit__chunk_step`` and ``jit__chunk_step_head``) that lie under the ops
of the type ``KIMI_DELTA_ATTENTION`` (the KDA layers' projections, the
convolution behind the kept tails, the per-channel rule over the chunk from
the request's state, the state and tails written back), from the owner
table of the traced window (``benchmark/owners.py``). None where the
profile holds no such scope. Layer: Paged decoder."""

from benchmark import owners

PROGRAM = r"_chunk_step"


def read(run):
    return owners.device_ms(run, PROGRAM, kinds=("KIMI_DELTA_ATTENTION",))
