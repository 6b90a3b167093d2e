"""The operations a prefill chunk needs over the chip's bfloat16 peak, as a
share of the chunk programs' measured device time, in %: every layer's
matrices once a live token (the window's ``prefill_tokens``: padding
counts for nothing), the scan's products by the published blocked form,
and the scores and weighted sums of the keys each query sees
(``prefill_keys``) in the four attention layers:
``counts_granite_hybrid.chunk_flops`` over the window's chunks. Layer:
Kernels."""

from benchmark import counts_granite_hybrid, plain_chunked, reduce

PROGRAM = r"_chunk_step"


def read(run):
    if run["trace"] is None or run["peaks"] is None:
        return None
    t = reduce.program_time(run["trace"], PROGRAM)
    n = plain_chunked.chunks(run)
    if t is None or n is None:
        return None
    least_s = (counts_granite_hybrid.chunk_flops(
        run["config"], n["tokens"], n["keys"]) / n["chunks"]
        / run["peaks"]["bf16_flops_per_s"])
    return 100.0 * least_s / (t["device_s"] / t["count"])
