"""The windowed layers' decode attend against its roofline, in %: the
least time it could take (the visible rows' keys and values once, ``min(
length + 1, window)`` rows a slot and windowed layer, over the HBM peak:
``counts_trinity.window_attend_bytes`` of the window's ``rows_read`` a
step) over the device time under the sub-scope ``window`` (inside
``attend``) of the ``MULTIHEAD_ATTENTION`` ops in the decode program
(``benchmark/owners.py``), kernel or not: the work is named by its scope,
not by what implements it. None where the profile holds no such scope.
Layer: Kernels."""

from benchmark import counts_trinity, owners, routed_chunked

PROGRAM = r"_decode_step"


def read(run):
    if run["trace"] is None or run["peaks"] is None:
        return None
    attend_ms = owners.device_ms(run, PROGRAM,
                                 kinds=("MULTIHEAD_ATTENTION",),
                                 subs=("window",))
    rows = routed_chunked.window_rows(run)
    if not attend_ms or rows is None:
        return None
    least_s = (counts_trinity.window_attend_bytes(
        run["config"], rows["rows_read"] / rows["steps"])
        / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * 1e3 * least_s / attend_ms
