"""The least time a chip could take for its share of a training step —
the floating-point operations the step needs (forward and backward,
nothing recomputed; ``counts.train_flops_per_token``) over the chip's
bf16 peak — as a share of the step program's measured device time, in
%. Compute bounds it: at batch x sequence of thousands of tokens the
weights are read once per step. Layer: Kernels."""

from benchmark import counts, reduce

PROGRAM = r"train_step"


def read(run):
    if run["trace"] is None or run["peaks"] is None:
        return None
    t = reduce.program_time(run["trace"], PROGRAM)
    if t is None:
        return None
    f = run["facts"]
    flops = (counts.train_flops_per_token(run["config"], f["seq"])
             * f["batch_per_chip"] * f["seq"])
    least_s = flops / run["peaks"]["bf16_flops_per_s"]
    return 100.0 * least_s / (t["device_s"] / t["count"])
