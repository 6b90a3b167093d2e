"""The least time the chip could take for one decode step of a model of
Mamba-2 and attention blocks with a tied head, as a share of the decode
program's measured device time, in %: every matrix read once in bfloat16
with the embedding once (as the head), every stepped state once in and
once out (the window's ``rows_stepped`` a step) and every live token's
keys and values once (the live tokens counted low from the window's
``blocks_read``), ``counts_granite_hybrid.decode_bytes_per_step``, over
the chip's HBM bandwidth. Memory bounds it. Layer: Kernels."""

from benchmark import (counts_granite_hybrid, reduce, routed_window,
                       state_window)

PROGRAM = r"_decode_step"


def read(run):
    if run["trace"] is None or run["peaks"] is None:
        return None
    t = reduce.program_time(run["trace"], PROGRAM)
    live = routed_window.live_tokens_per_step(run)
    rows = state_window.rows_per_step(run)
    if t is None or live is None or rows is None:
        return None
    least_s = (counts_granite_hybrid.decode_bytes_per_step(
        run["config"], live, rows) / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (t["device_s"] / t["count"])
