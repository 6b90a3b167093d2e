"""The operations a chunk's recurrence needs over the chip's bfloat16 peak, as
a share of the device time under the sub-scope ``rule`` of the ``MAMBA2``
ops in the chunk programs, in %: ``counts_granite_hybrid.scan_flops`` of
the window's live prompt tokens a chunk (the blocked form's products, all
36 layers) against ``benchmark/owners.py``'s exclusive time, kernel or not.
The program computes them in float32 at ``highest`` (several passes of the
bfloat16 unit a product), so the share reads low by that much. None where
the profile holds no such scope. Layer: Kernels."""

from benchmark import counts_granite_hybrid, owners, plain_chunked

PROGRAM = r"_chunk_step"


def read(run):
    if run["trace"] is None or run["peaks"] is None:
        return None
    rule_ms = owners.device_ms(run, PROGRAM, kinds=("MAMBA2",),
                               subs=("rule",))
    n = plain_chunked.chunks(run)
    if not rule_ms or n is None:
        return None
    least_s = (counts_granite_hybrid.scan_flops(run["config"], n["tokens"])
               / n["chunks"] / run["peaks"]["bf16_flops_per_s"])
    return 100.0 * 1e3 * least_s / rule_ms
