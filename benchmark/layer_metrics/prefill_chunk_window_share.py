"""Share of the traced window that the device spent in prefill chunk
programs, in %: their device seconds (``jit__chunk_step*``) over the
window's. Until PR 49 the cells whose prompts are prefilled in chunks
reported it as ``loop_prefill_share``, which is the scheduler's own
``prefill`` phase and cannot say it there: a chunk that is not its
prompt's last is dispatched and not waited for, so its time shows on the
host as the next fetch's wait. Layer: Paged decoder."""

from benchmark import reduce

PROGRAM = r"_chunk_step"


def read(run):
    tr = run["trace"]
    if tr is None or not tr.get("window_s"):
        return None
    t = reduce.program_time(tr, PROGRAM)
    return None if t is None else 100.0 * t["device_s"] / tr["window_s"]
