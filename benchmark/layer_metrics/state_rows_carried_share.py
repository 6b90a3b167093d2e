"""Of the state rows the window's prefill chunks read, the share that
CONTINUED a request's row (a chunk past its prompt's first:
``SsmStateEntry.chunk`` behind the state and the tail the chunk before
wrote) and did not start from zeros, in %: the window's deltas of
``rows_carried`` and ``rows_started`` under ``stats()``'s ``kv``,
``state``. The lengths' arithmetic: a prompt of n chunks carries n - 1 of
them. None from a program without the counters. Layer: KV pool."""

from benchmark import plain_chunked

def read(run):
    n = plain_chunked.chunk_state_rows(run)
    if n is None or n["started"] + n["carried"] <= 0:
        return None
    return 100.0 * n["carried"] / (n["started"] + n["carried"])
