"""Of the cache bytes a decode step reads and writes, the share that is
per-request state, in %: the window's ``rows_stepped`` times a state's
float32 bytes, in and out, against that and the window's ``blocks_read``
times a block's keys and values over the ``*`` layers
(``counts_nemotron_h``). Layer: KV pool."""

from benchmark import counts_nemotron_h, state_window


def read(run):
    rows = state_window.rows_stepped(run)
    blocks = state_window.blocks_read(run)
    if rows is None or blocks is None:
        return None
    state = rows * 2 * counts_nemotron_h.state_bytes(run["config"])
    kv = (blocks * run["facts"]["stats1"]["kv"]["block_size"]
          * counts_nemotron_h.kv_bytes_per_token(run["config"]))
    return 100.0 * state / (state + kv) if state + kv > 0 else None
