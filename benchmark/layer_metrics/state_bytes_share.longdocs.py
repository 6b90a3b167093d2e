"""Of the cache bytes a decode step reads and writes, the share that is
per-request state, in %: the window's ``rows_stepped`` times a state's
float32 bytes, in and out, against that, the selected blocks' keys and
values over the sparse layers and the live requests' pooled keys
(``counts_sala``). Layer: KV pool."""

from benchmark import counts_sala, selected_window


def read(run):
    step = selected_window.per_step(run)
    if step is None:
        return None
    cfg = run["config"]
    state = step["state_rows"] * 2 * counts_sala.state_bytes(cfg)
    rest = (counts_sala.decode_bytes_per_step(
        cfg, 0, step["selected"], step["live_tokens"])
        - counts_sala.matrix_params(cfg) * 2)
    return 100.0 * state / (state + rest) if state + rest > 0 else None
