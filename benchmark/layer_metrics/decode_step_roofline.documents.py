"""The least time the chip could take for one decode step of a model of
gated-delta-rule and full-attention layers — every matrix read once in
bfloat16, the state of every active slot and linear layer once in and
once out at its unpadded float32 bytes (the window's ``rows_stepped``),
every live token's keys and values once (counted low from the window's
``blocks_read``): ``counts_hybrid.decode_bytes_per_step`` over the chip's
HBM bandwidth — as a share of the decode program's measured device time,
in %. Memory bounds it. Layer: Kernels."""

from benchmark import counts_hybrid, reduce, routed_window, state_window

PROGRAM = r"_decode_step"


def read(run):
    if run["trace"] is None or run["peaks"] is None:
        return None
    t = reduce.program_time(run["trace"], PROGRAM)
    rows = state_window.rows_per_step(run)
    live = routed_window.live_tokens_per_step(run)
    if t is None or rows is None or live is None:
        return None
    least_s = (counts_hybrid.decode_bytes_per_step(run["config"], live, rows)
               / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (t["device_s"] / t["count"])
