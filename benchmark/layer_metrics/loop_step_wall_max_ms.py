"""The longest pass of the scheduler's loop inside the window, as the
upper bound of the highest bucket of ``stats()["loop"]["step_wall"]``
whose count moved (four bounds a doubling: at most 19 % over the true
value). A quiet run reads a bucket or two above the mean step; one stall
of seconds reads seconds. Where prompts are prefilled in chunks, one
chunk at most between two decode steps, it reads a step plus a chunk
(plus, where the chunk is a prompt's last, the fetch of its logits behind
everything in flight); a whole prompt between two steps would read
seconds. Layer: Scheduler."""

from benchmark import loop


def read(run):
    rows = loop.bucket_rows(run, "step_wall")
    if rows is None:
        return None
    top = rows[-1][0]
    if top == float("inf"):  # past the last bound, 105 s: the run's longest
        top = run["facts"]["stats1"]["loop"]["step_wall"]["max"]
    return 1e3 * top
