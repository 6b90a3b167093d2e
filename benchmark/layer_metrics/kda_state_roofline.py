"""The KDA layers' one-token state update against its roofline, in %: the
least time a decode step's state updates could take (the stepped states'
float32 bytes once in and once out over the HBM peak:
``state_step_least_s`` of ``benchmark/families/<family>.py``, over its
``counts*.py``, of the window's ``rows_stepped`` a step) over the device
time under the sub-scope ``rule`` of the ``KIMI_DELTA_ATTENTION`` ops in
the decode program (``benchmark/owners.py``), kernel or not: the work is
named by its scope, not by what implements it (on the chip it is
``gated_delta_decode`` with a ``(d_k,)`` decay a head and what stands
around it). None where the profile holds no such scope or the family
keeps no such states. Layer: Kernels."""

from benchmark import owners

PROGRAM = r"_decode_step"
ASKS = "state_step_least_s"


def read(run):
    if run["trace"] is None or run["peaks"] is None:
        return None
    ask = getattr(run["family"], ASKS, None)
    if ask is None:
        return None
    rule_ms = owners.device_ms(run, PROGRAM, kinds=("KIMI_DELTA_ATTENTION",),
                               subs=("rule",))
    least_s = ask(run)
    if not rule_ms or least_s is None:
        return None
    return 100.0 * 1e3 * least_s / rule_ms
