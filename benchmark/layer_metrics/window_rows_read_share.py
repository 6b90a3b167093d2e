"""Rows a windowed layer's decode step read over the rows a layer that
keeps everything would have read, in %: the window's deltas of
``stats()["kv"]["window"]``'s ``rows_read`` (the sum over the steps'
active slots of ``min(length + 1, window)``) and ``rows_full`` (of
``length + 1``). The lengths' arithmetic: what the window saves of a
step's reads. Layer: KV pool."""

from benchmark import routed_chunked


def read(run):
    rows = routed_chunked.window_rows(run)
    return (None if rows is None
            else 100.0 * rows["rows_read"] / rows["rows_full"])
