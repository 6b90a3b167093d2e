"""The least time the chip could take for one decode step — the bytes
the step has to read (every matrix-product weight once in bfloat16, and
the keys and values of the tokens live in the active slots once;
``counts.decode_bytes_per_step``) over the chip's HBM bandwidth — as a
share of the decode program's measured device time, in %. Memory bounds
it: a step multiplies each weight by a handful of rows.

The live tokens are counted low, so that the share is a true lower
bound: each active slot is credited its request's prompt alone (the
mean prompt of the jobs sent), not the answer tokens it has cached by
then; active slots are the window's ``slot_occupancy``. Layer:
Kernels."""

from benchmark import counts, reduce, serving

PROGRAM = r"_decode_step"


def read(run):
    if run["trace"] is None or run["peaks"] is None:
        return None
    t = reduce.program_time(run["trace"], PROGRAM)
    f = run["facts"]
    s0, s1 = f.get("stats0"), f.get("stats1")
    if t is None or not s0 or not s1 or not f.get("prompt_lens"):
        return None
    active = serving.decode_tokens_per_step(s0, s1)
    if active is None:
        return None
    prompt = sum(f["prompt_lens"]) / len(f["prompt_lens"])
    least_s = (counts.decode_bytes_per_step(run["config"], active * prompt)
               / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (t["device_s"] / t["count"])
