"""The least time the chip could take for one decode step, as a share of
the decode program's measured device time (``XLA Modules`` events of
``jit__decode_step``), in %. Memory bounds it: a step multiplies each
weight by a handful of rows, so the least time is the bytes the step has
to move over the chip's HBM bandwidth: every matrix once in bfloat16 and
whatever the model's requests keep (keys and values, latent rows, states,
rings) as far as the step reads it, counted LOW from the window's
counters. Which bytes those are is the family's to say
(``decode_step_least_s`` of ``benchmark/families/<family>.py``, over its
``counts*.py``); a family that does not say reports nothing. Layer:
Kernels."""

from benchmark import reduce

PROGRAM = r"_decode_step"
ASKS = "decode_step_least_s"


def read(run):
    return reduce.least_share(run, PROGRAM, ASKS)
