"""The least time the chip could take for one decode step of a model of
latent attention and routed experts — every matrix read once in bfloat16
(of the held experts only the share that got a row: the window's
``stats()["moe"]``) and every live latent row once in every layer
(``counts_latent_moe.decode_bytes_per_step``; the live tokens counted low
from the window's ``blocks_read``) over the chip's HBM bandwidth — as a
share of the decode program's measured device time, in %. Memory bounds
it. Layer: Kernels."""

from benchmark import counts_latent_moe, reduce, routed_window

PROGRAM = r"_decode_step"


def read(run):
    if run["trace"] is None or run["peaks"] is None:
        return None
    t = reduce.program_time(run["trace"], PROGRAM)
    hit = routed_window.expert_hit_share(run)
    live = routed_window.live_tokens_per_step(run)
    if t is None or hit is None or live is None:
        return None
    least_s = (counts_latent_moe.decode_bytes_per_step(run["config"], live,
                                                       hit)
               / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (t["device_s"] / t["count"])
