"""The state-space layers' step against its roofline, in %: the least
time a decode step's state updates could take (the stepped states' bytes
once in and once out over the HBM peak: ``state_step_least_s`` of
``benchmark/families/<family>.py``, over its ``counts*.py``, of the
window's ``rows_stepped`` a step) over the device time under the
sub-scope ``rule`` of the ``MAMBA2`` ops in the decode program
(``benchmark/owners.py``), kernel or not: the work is named by its scope,
not by what implements it. Since PR 47 the states' way in and out both lie
under ``rule`` (one kernel over the live rows, in place); before, the
compiler's staging copies carried no scope and the share could pass 100.
None where the profile holds no such scope or the family keeps no such
states. Layer: Kernels."""

from benchmark import owners

PROGRAM = r"_decode_step"
ASKS = "state_step_least_s"


def read(run):
    if run["trace"] is None or run["peaks"] is None:
        return None
    ask = getattr(run["family"], ASKS, None)
    if ask is None:
        return None
    rule_ms = owners.device_ms(run, PROGRAM, kinds=("MAMBA2",),
                               subs=("rule",))
    least_s = ask(run)
    if not rule_ms or least_s is None:
        return None
    return 100.0 * 1e3 * least_s / rule_ms
