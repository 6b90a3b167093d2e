"""Blocks a sparse layer's decode step read over the live blocks in the
active slots' tables, in %: the window's deltas of
``stats()["kv"]["selected"]``'s ``blocks_read`` and ``blocks_live``. 100
where nothing is selected (every context below ``dense_len``). Layer: KV
pool."""

from benchmark import selected_window


def read(run):
    sel = selected_window.selected(run)
    if sel is None or sel["blocks_live"] <= 0:
        return None
    return 100.0 * sel["blocks_read"] / sel["blocks_live"]
