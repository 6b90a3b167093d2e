"""The latent-attention decode kernel against its roofline, in %: the
least time the traced window's decode steps' kernels could take (per
step and layer the live rows' bytes over the HBM peak, or their
operations over the bfloat16 peak, whichever is larger:
``counts_latent_moe.latent_attention_least_s``) over the kernel's device
time in the trace (operations named ``latent_attention_decode``). None
where the kernel is not among the trace's listed operations. Layer:
Kernels."""

from benchmark import counts_latent_moe, reduce, routed_window

KERNEL = "latent_attention_decode"
PROGRAM = r"_decode_step"


def read(run):
    if run["trace"] is None or run["peaks"] is None:
        return None
    kernel_s = routed_window.op_seconds(run, KERNEL)
    t = reduce.program_time(run["trace"], PROGRAM)
    live = routed_window.live_tokens_per_step(run)
    if not kernel_s or t is None or live is None:
        return None
    least_s = t["count"] * counts_latent_moe.latent_attention_least_s(
        run["config"], live, run["peaks"])
    return 100.0 * least_s / kernel_s
