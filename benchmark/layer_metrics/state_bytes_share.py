"""Of the cache bytes a decode step reads and writes, the share that is
per-request state, in %: the window's ``rows_stepped`` times a state's
float32 bytes, in and out, against that and the rest of what the step
reads of the cache (the blocks its attention read times a block's keys
and values; in a model that selects its blocks, the selected blocks and
the pooled keys). Both parts are the family's to say (``cache_bytes`` of
``benchmark/families/<family>.py``, over its ``counts*.py``); a family
that keeps no state reports nothing. Neither direction is better by
itself: it says which of the two a step's cache traffic is. Layer: KV
pool."""

ASKS = "cache_bytes"


def read(run):
    ask = getattr(run["family"], ASKS, None)
    parts = None if ask is None else ask(run)
    if parts is None:
        return None
    state, rest = parts
    return 100.0 * state / (state + rest) if state + rest > 0 else None
