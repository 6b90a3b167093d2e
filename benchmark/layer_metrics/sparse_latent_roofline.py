"""An indexed latent-attention layer's decode step against its roofline,
in %: the least time the step's reads could take (the pooled index keys of
the live rows once and the taken rows once over the HBM peak:
``sparse_step_least_s`` of ``benchmark/families/<family>.py``, over its
``counts*.py`` and the window's ``stats()["kv"]["index"]``) over the
device time under the sub-scopes ``select`` and ``attend`` of the
``LATENT_ATTENTION`` ops in the decode program (``benchmark/owners.py``):
the same work whatever implements it. None where the profile holds no such
scope or the family has no such layer. Layer: Kernels."""

from benchmark import owners

PROGRAM = r"_decode_step"
ASKS = "sparse_step_least_s"


def read(run):
    if run["trace"] is None or run["peaks"] is None:
        return None
    ask = getattr(run["family"], ASKS, None)
    if ask is None:
        return None
    ms = owners.device_ms(run, PROGRAM, kinds=("LATENT_ATTENTION",),
                          subs=("select", "attend"))
    least_s = ask(run)
    if not ms or least_s is None:
        return None
    return 100.0 * 1e3 * least_s / ms
