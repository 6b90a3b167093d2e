"""Share of the scheduler thread's time, waiting for work left out, that
went to prefill dispatches and the fetch of their logits: the window's
``prefill`` of ``stats()["loop"]["phase_s"]`` over all phases but
``wait``, in %. The cells whose prompts are prefilled whole, in buckets;
where they go in chunks between decode steps the phase holds only the
last chunks' fetches, and ``prefill_chunk_window_share`` says what the
chunks took. Layer: Paged decoder."""

from benchmark import loop


def read(run):
    w = loop.window(run)
    return None if w is None else 100.0 * w["phase_s"]["prefill"] / w["busy_s"]
