"""The operations a prefill chunk needs over the chip's bfloat16 peak, as
a share of the chunk programs' measured device time (``XLA Modules``
events of ``jit__chunk_step`` and ``jit__chunk_step_head``), in %: every
matrix once a LIVE token (a prompt's last chunk is padded, and padding
counts for nothing) and what the model's mixers do for a chunk, as far
as the window's counters prove it. Which operations those are is the
family's to say (``chunk_least_s`` of
``benchmark/families/<family>.py``, over its ``counts*.py``); a family
that does not say reports nothing. The whole chunk's share of the peak
beside its kernels' rooflines. Layer: Kernels."""

from benchmark import reduce

PROGRAM = r"_chunk_step"
ASKS = "chunk_least_s"


def read(run):
    return reduce.least_share(run, PROGRAM, ASKS)
