"""The decode attend of the compressed-convolutional-attention layers
against its roofline, in %: the least time it could take (the live
tokens' keys and values once a layer, counted low from the window's
``blocks_read``, over the HBM peak: the family's ``cca_attend_least_s``
over ``counts_zaya.attend_bytes_per_step``) over the device time under
the sub-scope ``attend`` of the ``COMPRESSED_CONV_ATTENTION`` ops in the
decode program (``benchmark/owners.py``), kernel or not: the work is
named by its scope, not by what implements it. None where the profile
holds no such scope or the family does not say. Layer: Kernels."""

from benchmark import owners

PROGRAM = r"_decode_step"
KIND = "COMPRESSED_CONV_ATTENTION"
ASKS = "cca_attend_least_s"


def read(run):
    if run["trace"] is None or run["peaks"] is None:
        return None
    ask = getattr(run["family"], ASKS, None)
    attend_ms = owners.device_ms(run, PROGRAM, kinds=(KIND,),
                                 subs=("attend",))
    least_s = None if ask is None else ask(run)
    if not attend_ms or least_s is None:
        return None
    return 100.0 * 1e3 * least_s / attend_ms
