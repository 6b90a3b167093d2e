"""The least time the chip could take for one decode step of a model of
block-sparse and decay-only linear layers — every matrix read once in
bfloat16, the state of every active slot and linear layer once in and
once out in float32 (the window's ``rows_stepped``), the blocks the
sparse layers' steps selected (the window's ``selected.blocks_read``)
and every live request's pooled keys once (counted low from
``selected.blocks_live``): ``counts_sala.decode_bytes_per_step`` over the
chip's HBM bandwidth — as a share of the decode program's measured device
time, in %. Memory bounds it. Layer: Kernels."""

from benchmark import counts_sala, reduce, selected_window

PROGRAM = r"_decode_step"


def read(run):
    if run["trace"] is None or run["peaks"] is None:
        return None
    t = reduce.program_time(run["trace"], PROGRAM)
    step = selected_window.per_step(run)
    if t is None or step is None:
        return None
    least_s = (counts_sala.decode_bytes_per_step(
        run["config"], step["state_rows"], step["selected"],
        step["live_tokens"]) / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (t["device_s"] / t["count"])
