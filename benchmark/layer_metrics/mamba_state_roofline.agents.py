"""The state-space layers' step against its roofline, in %: the least
time a decode step's state updates could take (the stepped states' bytes
once in and once out over the HBM peak:
``counts_nemotron_h.state_step_least_s`` of the window's ``rows_stepped``
a step) over the device time under the sub-scope ``rule`` of the
``MAMBA2`` ops in the decode program (``benchmark/owners.py``), kernel or
not: the work is named by its scope, not by what implements it. None
where the profile holds no such scope. Layer: Kernels."""

from benchmark import counts_nemotron_h, owners, state_window

PROGRAM = r"_decode_step"


def read(run):
    if run["trace"] is None or run["peaks"] is None:
        return None
    rule_ms = owners.device_ms(run, PROGRAM, kinds=("MAMBA2",),
                               subs=("rule",))
    rows = state_window.rows_per_step(run)
    if not rule_ms or rows is None:
        return None
    least_s = counts_nemotron_h.state_step_least_s(run["config"], rows,
                                                   run["peaks"])
    return 100.0 * 1e3 * least_s / rule_ms
