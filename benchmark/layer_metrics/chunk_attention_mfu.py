"""A prefill chunk's attention against the chip's bfloat16 peak, in %: the
operations it needs (``2 x H x (head_dim + v_head_dim)`` a (query, seen
key) pair: every query head's score over the key width and its weighted
sum over the value width; the seen keys from the loop's chunk counters,
``prefill_keys`` in each full layer, ``prefill_keys_window`` in each
windowed one, and in the last layer, which attends in a prompt's last
chunk only, ``prefill_keys_last``: the family's
``chunk_attention_least_s`` over its ``counts*.py``) over the device time
under the sub-scopes ``attend`` and ``window`` of the
``MULTIHEAD_ATTENTION`` ops in the chunk programs (``jit__chunk_step`` and
``jit__chunk_step_head``), kernel or not (``benchmark/scope_share.py``).
Layer: Kernels."""

from benchmark import scope_share

PROGRAM = r"_chunk_step"
ASKS = "chunk_attention_least_s"


def read(run):
    return scope_share.attention_share(run, PROGRAM, ("attend", "window"),
                                       ASKS)
