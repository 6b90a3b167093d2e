"""Rows a windowed layer reserves over the rows its requests hold, over
the window's decode steps: the deltas of ``stats()["kv"]["window"]``'s
``rows_reserved`` (a whole ring a slot, whatever its length) and
``rows_read`` (``min(length + 1, window)``: what the slot's request has
in its ring once the step's row is written). 1 is a ring always full;
what is above it a paged ring would hand back. Layer: KV pool."""

from benchmark import routed_chunked


def read(run):
    rows = routed_chunked.window_rows(run)
    if rows is None or rows["rows_read"] <= 0:
        return None
    return rows["rows_reserved"] / rows["rows_read"]
