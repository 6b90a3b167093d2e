"""The gated-delta-rule decode kernel against its roofline, in %: the
least time the traced window's decode steps' kernels could take (per step
the stepped states' bytes in and out over the HBM peak:
``counts_hybrid.gated_delta_least_s`` of the window's ``rows_stepped`` a
step) over the kernel's device time in the trace (operations named
``gated_delta_decode``). None where the kernel is not among the trace's
listed operations. Layer: Kernels."""

from benchmark import counts_hybrid, reduce, routed_window, state_window

KERNEL = "gated_delta_decode"
PROGRAM = r"_decode_step"


def read(run):
    if run["trace"] is None or run["peaks"] is None:
        return None
    kernel_s = routed_window.op_seconds(run, KERNEL)
    t = reduce.program_time(run["trace"], PROGRAM)
    rows = state_window.rows_per_step(run)
    if not kernel_s or t is None or rows is None:
        return None
    least_s = t["count"] * counts_hybrid.gated_delta_least_s(
        run["config"], rows, run["peaks"])
    return 100.0 * least_s / kernel_s
