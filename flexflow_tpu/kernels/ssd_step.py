"""The Mamba-2 decode step as a Pallas TPU kernel: every live slot's state
read once from the pool's arena where it lies, stepped, and written back
in place.

A Mamba-2 op (ops/mamba2.py ``Mamba2``) keeps, for a request, one float32
state of ``N`` numbers for each of its ``H P`` channels. The serving pool
stores it as one row of a ``(rows, N, H P)`` arena: the state's axis on
the sublanes and all heads' channels side by side on the lanes, as
``kernels/gated_delta.py`` stores its states and for its reasons (a row
is one contiguous DMA, the tiles pad nothing, and the step's one
contraction, ``y = S C`` over ``N``, runs DOWN sublanes: adds of whole
vector registers. With ``N`` on the lanes it is a sum ALONG the lanes of
every one of a row's 512 registers, and ``u`` has to be spread along
lanes a register at a time: PERF.md section 6, PR 47, has both forms'
rates on the chip). A decode step does, per slot and channel ``c`` of
head ``h``, with ``B`` and ``C`` those of the head's group::

    S[:, c] <- a[h] S[:, c] + u[c] B;    y[c] = S[:, c] . C

(``ops/mamba2.py`` ``ssd_step``'s arithmetic: ``y`` is read from the NEW
state) which reads ``S`` once and writes it once, 2.1 MB each way a slot
and layer at Granite's widths and 4.2 MB at Nemotron's, for four
multiply-adds a number: the memory bounds it. The kernel:

* takes the slots' arena rows by scalar prefetch; grid step ``i`` is a
  slot, its block the whole arena row ``rows[i]`` on the way in and,
  aliased (``input_output_aliases``), on the way out: the donated arena
  is updated in place and rows no slot names are neither read nor
  written. The grid goes through the slots in the order of their rows
  (a second prefetched table says which slot a grid step is: its
  inputs and its ``y`` are blocks of that slot), so the idle slots,
  which all name row 0, nobody's, stand together at the front: a grid
  step whose block is the one before's moves nothing, the first idle
  slot copies the block through, the others leave it, and each reads
  ``y = 0``. Row 0 is read once and written once, as it was, however
  many slots are idle, and an idle slot costs no traffic (the
  ``ssd_step_rows`` fusion passes over every row of the arena, live or
  not);
* a block is a whole row, in and out with two buffers each within the
  VMEM budget (a row of 4.2 MB cut in two or four parts of its ``N`` was
  no faster: PERF.md section 6, PR 47);
* walks the block a lane tile at a time: ``a`` and ``u`` are rows of 128
  lanes spread down the sublanes (a slot's ``H P`` values of each come
  folded over the sublanes of whole tiles, ``(8, H P / 8)``, and ``y``
  goes back so: a slot's block pads nothing, and neither do the fusions
  around the call, which one row a slot would hold to an eighth of
  every register); ``B`` and ``C`` come as rows of ``N``
  lanes, a group each (4 KB a slot; as columns of one lane they would
  pad to a tile of 64 KB each), are turned once a slot and the group's
  column spread along the lanes once a group (a lane tile lies within
  one group: :func:`supported`). Elementwise work on (N, 128) tiles and one
  sum down sublanes a tile, float32 throughout, no matrix unit
  (Mosaic's default for float32 operands is bfloat16 passes, and one
  row against ``S`` would load every tile of ``S`` as a weight).

``ops/mamba2.py`` ``ssd_step_rows`` is the jnp form over the same arena:
the kernel's reference, the CPU's path, and what runs where
:func:`supported` refuses.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_mode
from .flash_attention import LANES, VMEM_BUDGET_BYTES, VMEM_LIMIT_BYTES
from .moe_kernels import SMEM_BUDGET_BYTES

SUBLANES = 8


def supported(slots: int, heads: int, head_dim: int, state_size: int,
              groups: int, arena_shape, arena_dtype) -> bool:
    """Whether the kernel takes this call: a float32 arena ``(rows, N, H
    P)`` whose ``N`` fills whole sublane tiles and whose channels whole
    lane tiles, each lane tile within one group of heads, tables that fit
    SMEM, a working set (the row twice in and twice out) within the VMEM
    budget."""
    if pallas_mode() is None:
        return False
    width = heads * head_dim
    if jnp.dtype(arena_dtype) != jnp.dtype(jnp.float32):
        return False
    if tuple(arena_shape[1:]) != (state_size, width):
        return False
    if state_size % SUBLANES or heads % groups or (width // groups) % LANES:
        return False
    if 2 * 4 * slots > SMEM_BUDGET_BYTES:
        return False
    return 4 * 4 * state_size * width <= VMEM_BUDGET_BYTES


def _kernel(rows_ref, order_ref, u_ref, a_ref, bc_ref, s_ref, s_out, y_ref):
    del order_ref                     # the index maps read it
    i = pl.program_id(0)
    live = rows_ref[i] != 0
    ns, width = s_ref.shape
    groups = bc_ref.shape[0] // 2
    tiles = width // groups // LANES          # lane tiles a group
    fold = u_ref.shape[1] // LANES            # and a sublane of u, a and y

    @pl.when(live)
    def _():
        bct = bc_ref[...].T                   # (N, 2 G): a column a group
        for g in range(groups):
            bx, cx = (jnp.broadcast_to(bct[:, j:j + 1], (ns, LANES))
                      for j in (g, groups + g))
            for tile in range(g * tiles, (g + 1) * tiles):
                at = pl.ds(tile * LANES, LANES)
                of = (pl.ds(tile // fold, 1), pl.ds(tile % fold * LANES, LANES))
                s = s_ref[:, at] * a_ref[of] + bx * u_ref[of]
                s_out[:, at] = s
                y_ref[of] = jnp.sum(s * cx, axis=0, keepdims=True)

    @pl.when(jnp.logical_not(live))
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

        # the idle slots stand first and share row 0's block: the first
        # of them copies it through, and the block stays for the others
        @pl.when(i == 0)
        def _():
            s_out[...] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssd_step(arena, rows, u, a, bc, *, interpret):
    n, sub, lanes = u.shape
    state_size = bc.shape[2]
    order = jnp.argsort(rows).astype(jnp.int32)
    per_slot = lambda *tail: pl.BlockSpec(  # noqa: E731
        (None,) + tail, lambda i, rows, order: (order[i],) + (0,) * len(tail))
    row = pl.BlockSpec((None, state_size, sub * lanes),
                       lambda i, rows, order: (rows[i], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n,),
        in_specs=[per_slot(sub, lanes), per_slot(sub, lanes),
                  per_slot(bc.shape[1], state_size), row],
        out_specs=[row, per_slot(sub, lanes)],
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(arena.shape, arena.dtype),
                   jax.ShapeDtypeStruct(u.shape, jnp.float32)],
        # operand 5 (after the two prefetched tables): the arena, in place
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="ssd_step_decode",
    )(rows[order], order, u, a, bc, arena)


def ssd_step_decode(arena, rows, u, decay, bm, cm):
    """One token a slot through the states in ``arena`` (rows, N, H P)
    float32, donated. ``rows`` (slots,) int32 each slot's arena row (0:
    an idle slot, which steps nothing and reads ``y = 0``); ``u`` = dt xs
    (slots, H, P), ``decay`` = exp(dt A) (slots, H), ``bm``, ``cm``
    (slots, G, N), float32. Returns (y (slots, H, P) float32 without the
    ``D`` term, the arena with those rows stepped). Callers check
    :func:`supported` first. The kernel's call is jitted on its own, so
    that the layers of a model trace it once."""
    n, heads, head_dim = u.shape
    f32 = jnp.float32
    # a slot's channels folded over the sublanes of whole tiles, so that
    # a slot's block pads nothing (one row of H P lanes is a tile an
    # eighth full, here and in the fusions that make it)
    sub = math.gcd(SUBLANES, heads * head_dim // LANES)
    bc = jnp.concatenate([bm, cm], axis=1).astype(f32)       # (slots, 2 G, N)
    arena, y = _ssd_step(
        arena, rows.astype(jnp.int32), u.astype(f32).reshape(n, sub, -1),
        jnp.repeat(decay.astype(f32), head_dim, axis=-1).reshape(n, sub, -1),
        bc, interpret=pallas_mode() == "interpret")
    return y.reshape(n, heads, head_dim), arena
