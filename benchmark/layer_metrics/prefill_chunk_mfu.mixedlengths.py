"""The operations a prefill chunk needs over the chip's bfloat16 peak, as
a share of the chunk programs' measured device time, in %: every fixed
matrix once a live token (the window's ``prefill_tokens``: padding counts
for nothing), the held experts' matrices once a pair the routing named
among them (``prompt_pairs_held``), and the scores and weighted sums of
the keys each query sees (``prefill_keys`` in the full layer,
``prefill_keys_window`` in a windowed one):
``counts_trinity.chunk_flops`` over the window's chunks. Layer:
Kernels."""

from benchmark import counts_trinity, reduce, routed_chunked

PROGRAM = r"_chunk_step"


def read(run):
    if run["trace"] is None or run["peaks"] is None:
        return None
    t = reduce.program_time(run["trace"], PROGRAM)
    n = routed_chunked.chunks(run)
    if t is None or n is None:
        return None
    least_s = (counts_trinity.chunk_flops(
        run["config"], n["tokens"], n["pairs_held"], n["keys_full"],
        n["keys_window"]) / n["chunks"] / run["peaks"]["bf16_flops_per_s"])
    return 100.0 * least_s / (t["device_s"] / t["count"])
