"""Pallas TPU kernels for the hot ops XLA fuses poorly.

The reference implements these as handwritten CUDA kernels (SURVEY.md §2.2:
attention.cu, group_by.cu, aggregate.cu); here they are Pallas TPU kernels
that keep the working set in VMEM and feed the MXU directly:

* :mod:`flash_attention` — fused scaled-dot-product attention, forward
  and backward, blocked over keys with a running softmax: no array with
  two sequence axes reaches HBM (reference: src/ops/attention.cu uses
  cuDNN MultiHeadAttn for the same reason). Taken by shape
  (``engaged``), in training, eval and forward programs.
* :mod:`paged_attention` — the paged decode step's attention over a
  ``PagedKVPool`` arena read in place: block tables by scalar prefetch,
  a slot's live blocks only, all heads of a chunk in one matrix product
  (serving/generation.py falls back to its jnp gather for what
  ``supported()`` refuses: int8 arenas, widths that are no whole lane
  tiles, the CPU). A windowed layer's ring
  (``serving/cache_entry.py`` ``WindowEntry``) is read by the same
  kernel: its block table is made in the program from the slots' rows
  and the lengths are clamped to the ring, so a step reads ``min(n,
  window)`` rows a slot.
* :mod:`chunk_attention` — a chunked prefill's attention over plain keys
  and values: a chunk's queries over a windowed layer's ``[ring |
  chunk]`` rows or a full layer's rows gathered through its table, by
  absolute position (the positions are data: a ring is stored rotated),
  the ``H / Hkv`` query heads of a group over the one key tile, a
  running softmax in VMEM, and only the key blocks some query of a
  block can see visited, from a table made in jnp and scalar-prefetched
  (``serving/cache_entry.py`` ``PairEntry.chunk_path`` chooses; the walk
  over key spans in jnp, ``cache_entry._attend_spans``, stays for what
  ``supported()`` refuses: the CPU, heads of no whole lane tiles such as
  GPT-2's 64, int8 pairs, a model over more than one device). It reads
  a COPY of the table's rows; reading the table in place, as
  ``paged_attention`` does, is not written.
* :mod:`gated_delta` — the gated delta rule of a linear-attention layer:
  the decode step over a pool's per-request states in place, and the
  whole-sequence form of a prefill or a chunk as one kernel a layer, the
  state in VMEM from the first chunk to the last, with a decay a head or
  a decay a key channel (``ops/gated_delta.py`` keeps the jnp forms,
  taken where ``supported()`` / ``chunks_supported()`` refuse).
* :mod:`ssd_step` — the Mamba-2 decode step over a pool's per-request
  states in place: the live slots' rows of a ``(rows, N, H P)`` arena by
  scalar prefetch, each read once, stepped and written back through the
  aliased arena, rows no slot names left alone and idle slots at no
  traffic (``serving/cache_entry.py`` ``SsmStateEntry.step`` chooses;
  ``ops/mamba2.py`` ``ssd_step_rows``, one elementwise pass over the
  whole arena in jnp, stays for what ``supported()`` refuses: the CPU, a
  state of no whole sublane tile, channels of no whole lane tiles, a row
  past the fast memory). The whole-sequence form a prefill or a chunk
  runs is jnp (``chunked_ssd``); no kernel is written for it.
* :mod:`grouped_experts` — the held experts of a routed layer over a
  call's rows as one kernel: row tiles named by the routing (an expert
  named by ``n`` pairs gets ``ceil(n / tile)``, one named by none gets
  none and its matrices are not read), each named expert's matrices read
  once where they lie, the pairs' rows copied out of VMEM and their
  weighted results added back into it row by row. Tiles of 128 rows for
  a prefill's bucket or chunk; for a call of fewer rows (a decode step
  that names few of the experts it holds, the one row behind a head's
  cut) tiles of the call's own rows, one an expert at the most
  (``ops/moe_ops.py`` ``RoutedExperts.expert_form`` chooses; ``apply``
  keeps the jnp forms for what ``supported()`` refuses: float32 rows,
  widths of no whole lane tiles, rows past the fast memory, the CPU; and
  for a model over more than one device, and as the backward).
* :mod:`moe_kernels` — row gather / weighted row-gather-sum with
  scalar-prefetched indices, realizing the MoE dispatch/combine data
  movement (reference: src/ops/group_by.cu, aggregate.cu scatter kernels)
  without one-hot matmuls.

Dispatch policy: kernels engage automatically on TPU backends; on CPU the
jnp reference paths run instead (identical math). ``FLEXFLOW_TPU_PALLAS``
overrides: ``off`` disables kernels everywhere, ``interpret`` runs them in
the Pallas interpreter (used by the hermetic CPU test suite to validate
kernel numerics).
"""

from __future__ import annotations

import os

import jax


def pallas_mode() -> str | None:
    """Returns ``"compiled"``, ``"interpret"``, or None (kernels disabled)."""
    v = os.environ.get("FLEXFLOW_TPU_PALLAS", "auto")
    if v == "off":
        return None
    if v == "interpret":
        return "interpret"
    if v == "compiled" or jax.default_backend() == "tpu":
        return "compiled"
    return None


def pallas_forced() -> bool:
    """True when the operator EXPLICITLY forced compiled kernels on
    (``FLEXFLOW_TPU_PALLAS=compiled``) — as opposed to ``pallas_mode()``
    returning "compiled" merely because the backend is a TPU. Flash
    attention's rule over shapes (``flash_attention.engaged``) yields to
    it; the env contract lives here so it is parsed in one module."""
    return os.environ.get("FLEXFLOW_TPU_PALLAS") == "compiled"


def interpret_flag() -> bool:
    return pallas_mode() == "interpret"


def use_pallas(ctx) -> bool:
    """Op-level gate for kernels WITHOUT a shard_map composition yet
    (MoE dispatch/combine): single-device lowerings only. Flash attention
    has its own mesh-aware gate (``flash_attention.sharded_supported``) and
    engages on dp x tp meshes via shard_map."""
    return pallas_mode() is not None and (
        getattr(ctx, "mesh", None) is None or ctx.mesh.size == 1
    )
