"""The least time the chip could take for one decode step of a model of
windowed and full attention layers and routed experts, as a share of the
decode program's measured device time, in %: every matrix read once in
bfloat16 (of the held experts only the share that got a row: the window's
``stats()["moe"]``) and every visible row's keys and values once (a
windowed layer's ``min(length + 1, window)`` rows a slot, the full
layer's all: the window's ``stats()["kv"]["window"]``),
``counts_trinity.decode_bytes_per_step``, over the chip's HBM bandwidth.
Memory bounds it. Layer: Kernels."""

from benchmark import counts_trinity, reduce, routed_chunked, routed_window

PROGRAM = r"_decode_step"


def read(run):
    if run["trace"] is None or run["peaks"] is None:
        return None
    t = reduce.program_time(run["trace"], PROGRAM)
    hit = routed_window.expert_hit_share(run)
    rows = routed_chunked.window_rows(run)
    if t is None or hit is None or rows is None:
        return None
    least_s = (counts_trinity.decode_bytes_per_step(
        run["config"], rows["rows_read"] / rows["steps"],
        rows["rows_full"] / rows["steps"], hit)
        / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (t["device_s"] / t["count"])
