"""The operations a prefill chunk needs over the chip's bfloat16 peak, as
a share of the chunk programs' measured device time, in %: every layer's
matrices once a live token (the window's ``prefill_tokens`` over its
``prefill_chunks``: a prompt's last chunk is padded, and padding counts
for nothing) and the linear layers' state products, the sparse layers'
attention left out (the counters prove no context):
``counts_sala.chunk_flops``. The share of its peak of what is three
quarters of this cell's device time. Layer: Kernels."""

from benchmark import counts_sala, reduce, selected_window

PROGRAM = r"_chunk_step"


def read(run):
    if run["trace"] is None or run["peaks"] is None:
        return None
    t = reduce.program_time(run["trace"], PROGRAM)
    n = selected_window.chunks(run)
    if t is None or n is None:
        return None
    least_s = (counts_sala.chunk_flops(run["config"], n["tokens"] / n["chunks"])
               / run["peaks"]["bf16_flops_per_s"])
    return 100.0 * least_s / (t["device_s"] / t["count"])
