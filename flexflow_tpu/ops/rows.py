"""The hand-over between a step's slots and the rows of a per-request
arena (a state or a convolution tail a request: serving/cache_entry.py,
and the row steps of ops/mamba2.py and ops/lightning_attention.py): slot n
steps row ``rows[n]``, and the mask that says so is written here alone."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def named(num_rows: int, rows):
    """The (slots, rows) mask: whether slot n names row r of an arena of
    ``num_rows`` rows, for ``rows`` (N,) int32. Row 0 is nobody's."""
    return ((rows[:, None] == jax.lax.iota(jnp.int32, num_rows))
            & (rows[:, None] != 0))


def named_by(num_rows: int, rows):
    """Of an arena's ``num_rows`` rows, which of the slots ``rows`` (N,)
    names each (0 where none does) and whether one does: ``(slot_of,
    live)``, (R,) each. A row takes ``a[slot_of]``, to be ignored or
    zeroed where not ``live``; the slots take results back by ``o[rows]``."""
    hot = named(num_rows, rows)
    return jnp.argmax(hot, axis=0), hot.any(0)


def spread_rows(arena, rows, new):
    """``new`` (N, width) written over the rows ``rows`` (N,) of a
    per-request arena (R, width) where the arena lies: each row finds
    the slot that names it through a one-hot mask of (slots, rows) and
    takes that slot's values, and the donated arena keeps the rest, in
    one elementwise pass. No scatter: N rows this wide scattered lower
    to a sequential loop over the slots on the TPU, thirty times what
    the arena's bytes need; a take of R rows does not. A live row is
    named by at most one slot and its values are moved, not computed, so
    the stepped rows are the scatter's bit for bit in any dtype, and a
    slot's NaN stays in its own row (a one-hot PRODUCT over the slots is
    a tenth of a millisecond a step faster at the hybrid cell's shapes
    and gives up both, and on the chip float32 values under 1e-31 and
    the sign of a negative zero: ``PERF.md``, PRs 39 and 60). Row 0 is
    nobody's: a slot that names it writes nothing."""
    hot = named(arena.shape[0], rows)
    return jnp.where(hot.any(0)[:, None],
                     new.astype(arena.dtype)[jnp.argmax(hot, axis=0)], arena)


__all__ = ["named", "named_by", "spread_rows"]
