"""The gated delta rule as Pallas TPU kernels: the decode step (every
active slot's state read once, updated, and written back in place), the
step of the convolution's kept inputs beside it (:func:`tails_step`) and,
further down, the whole-sequence form a prefill runs.

A gated-delta-rule op (ops/gated_delta.py ``GatedDeltaNet``) keeps, for a
request, one float32 state ``S`` of ``(d_k, d_v)`` a head. The serving
pool stores it as one row of a ``(rows, d_k, H * d_v)`` arena: the keys'
axis on the sublanes and all heads' values side by side on the lanes, so
that the tiles pad nothing (30 heads of 192 values are 5,760 = 45 x 128
lanes; ``d_v = 192`` alone on the lanes would pad a third) and a row is
one contiguous DMA. A decode step does, per slot::

    S <- alpha S;  u = beta (v - S^T k);  S <- S + k u^T;  o = S^T q

which reads ``S`` once and writes it once: 2.2 MB in and out a slot and
layer at the published widths, and a few multiply-adds a number, so the
memory bounds it. The kernel:

* takes the slots' arena rows by scalar prefetch; grid step ``i`` is slot
  ``i``, its block the arena row ``rows[i]`` on the way in and, aliased
  (``input_output_aliases``), on the way out: the donated arena is
  updated in place and rows no slot names are not touched. Idle slots
  name row 0, the null row, and update that;
* walks the row in groups of heads that fill whole lane tiles (one head
  where ``d_v`` is a multiple of 128, two where it is an odd multiple of
  64). ``S^T k`` and ``S^T q`` are sums down the sublanes of ``S`` times
  the head's key (query) spread along its lanes, ``k u^T`` the outer
  product the same way: elementwise work on (d_k, 128) tiles, float32
  throughout, no matrix unit (a product of one row against ``S`` would
  load every tile of ``S`` as a weight for one row of work).

:func:`gated_delta_step` is the jnp form (:func:`delta_rule_step` over
gathered rows, scattered back): the kernel's reference, and what runs
where :func:`supported` refuses.

:func:`gated_delta_chunks` is the whole-sequence form
(``ops/gated_delta.py`` ``chunked_delta_rule`` is its jnp form, its
reference and what runs where :func:`chunks_supported` refuses). It is
matrix products, ``ROWS`` tokens at a time: within a chunk the updates
``u_t = beta_t (v_t - alpha_t S_{t-1}^T k_t)`` solve a unit
lower-triangular system that does not involve the incoming state, and
between chunks the state is carried. The kernel:

* grids over (row of the batch, group of heads, chunk), the chunks in
  turn, the group's states in a float32 VMEM scratch from the first chunk
  (read from the incoming state) to the last (written out once): the
  state never goes through HBM between chunks;
* takes q, k, v as ``(B, S, H d)``, heads side by side on the lanes as
  the projections and the convolution write them, and writes o the same
  way: no transpose through HBM. A group is the fewest heads whose keys
  AND values fill whole lane tiles (4 at widths 96 and 192); a head's
  window of whole tiles is loaded, rotated to lane 0 and masked, so
  every product sees whole tiles with zeros in the padding. Where the
  heads are no multiple of the group the last grid step's block hangs
  over the arrays' edge: what it reads there is never selected and what
  it writes there is dropped;
* makes what a chunk needs in VMEM from that chunk's q, k, v, beta and
  log-decay (summed from the chunk's start by XLA before the call: (B, S,
  H), the one thing not made here): the decay of every pair of
  positions, ``k k^T``, ``q k^T``, the system's inverse, u, o and the new
  state. The inverse is built by block substitution in doubling blocks
  (:func:`_unit_lower_inverses`), never by a series in powers of the
  system; the 0/1 tiles that pick a level's blocks are the same for
  every chunk and head, so XLA makes them once a call
  (:func:`_tile_masks`) and they stay in VMEM;
* goes through a group's heads stage by stage, not head by head: a head
  is a chain of dependent products, and four chains side by side keep the
  matrix unit fed where one leaves it waiting;
* can make the two norms the op computes around the recurrence (unit q
  and k a head, RMSNorm of o a head) on the head's tile, where each is a
  lane sum; XLA reduces over a head's lanes of a ``(B, S, H d)`` array by
  transposing the whole array and back;
* computes in float32 throughout, every product at float32 contract
  precision (``highest``): Mosaic's default for float32 operands is bf16
  passes.

The same call takes a decay a key CHANNEL (``g`` (B, S, H, d_k):
``ops/gated_delta.py`` ``KimiDeltaAttention``, ``chunked_channel_rule``
its jnp form; the Pallas call is then named ``channel_delta_chunks``).
The grid, the state scratch, the system, its inverse and the carry are
the same code; what differs is how a chunk's ``k k^T`` and ``q k^T``
under the decay are made, since one decay can no longer be pulled out
of the products: ``sum_d k_t[d] k_i[d] exp(G_t[d] - G_i[d])`` (``G`` the
log-decay summed from the chunk's start) factorised as ``(k_t exp G_t) .
(k_i exp -G_i)`` leaves float32 once ``-G_i`` passes 88, which a gate
bounded below by -5 a token does after 17 tokens. So the chunk's 128
rows go in SUB-chunks of ``SUB`` = 16 tokens (:func:`_channel_decays`):

* what comes in beside q, k and v is the log-decay summed from each
  sub-chunk's start, (B, S, H d_k) as the keys lie (XLA sums it before
  the call, 16 rows at a time): never a sum over the whole chunk, whose
  differences would round where a sub-chunk's do not;
* rows of sub-chunk ``a`` are taken against its start, ``k_t exp(G_t -
  G^a)``, at most 1; the columns they meet are ``k_i exp(G^a - G_i)``: at
  most ``e^80`` inside ``a``, at most 1 before it, zeros behind it (never
  read). In VMEM that is ONE (rows, d_k) exponential a sign: a column of
  an earlier sub-chunk is taken against its own sub-chunk's END and
  carried on to ``G^a`` by a (1, d_k) factor a pair of sub-chunks, each
  a product of whole sub-chunks' decays (at most 1, so they underflow
  where the pair's decay does and never overflow);
* eight products of (2 SUB, d_k) rows, a sub-chunk's keys above its
  queries, against their (rows, d_k) columns stand where the scalar form
  has one of (2 rows, d_k): the same operations in eight independent
  pieces, stacked down the sublanes into the same two (rows, rows)
  tiles;
* the decay since the chunk's start is a (rows, d_k) array where it was
  a column, the keys up to the chunk's end likewise, and the whole
  chunk's decay scales the state's ROWS: its (1, d_k) row goes through
  the diagonal of a (d_k, d_k) tile and a lane sum to lie down the
  sublanes.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_mode
from .flash_attention import _NT, VMEM_BUDGET_BYTES, VMEM_LIMIT_BYTES
from .moe_kernels import SMEM_BUDGET_BYTES
from .paged_attention import _round_up

LANES = 128


def _group(value_dim: int) -> int:
    """Heads a group holds so that it fills whole lane tiles; 0 where no
    such group is built."""
    if value_dim % LANES == 0:
        return 1
    return 2 if value_dim % (LANES // 2) == 0 else 0


def supported(slots: int, heads: int, key_dim: int, value_dim: int,
              arena_shape, arena_dtype) -> bool:
    """Whether the kernel takes this call: a float32 arena ``(rows, d_k,
    H d_v)`` whose heads group into whole lane tiles and whose keys fill
    whole sublane tiles, rows that fit SMEM, a working set (the row twice
    in and twice out) within the VMEM budget."""
    if pallas_mode() is None:
        return False
    group = _group(value_dim)
    if jnp.dtype(arena_dtype) != jnp.dtype(jnp.float32):
        return False
    if tuple(arena_shape[1:]) != (key_dim, heads * value_dim):
        return False
    if group == 0 or heads % group or key_dim % 8:
        return False
    if 4 * slots > SMEM_BUDGET_BYTES:
        return False
    return 4 * 4 * key_dim * heads * value_dim <= VMEM_BUDGET_BYTES


def _kernel(rows_ref, qt_ref, kt_ref, vab_ref, *rest, heads, value_dim,
            group, channel):
    del rows_ref                      # the index maps read it
    # a decay a key channel comes as the keys do, (d_k, H), one more
    # operand before the state
    at_ref = rest[0] if channel else None
    s_ref, s_out, o_ref = rest[-3:]
    dk = s_ref.shape[0]
    width = group * value_dim         # lanes of a group of heads
    lane = jax.lax.broadcasted_iota(jnp.int32, (dk, LANES), 1)
    qt, kt = qt_ref[...], kt_ref[...]                         # (d_k, H)

    def spread(cols, first, tile):
        """Lane tile ``tile`` of a group whose first head is ``first``:
        each head's column of ``cols`` (d_k, H) along that head's lanes."""
        start = tile * LANES
        h0, h1 = start // value_dim, (start + LANES - 1) // value_dim
        a = jnp.broadcast_to(cols[:, first + h0:first + h0 + 1], (dk, LANES))
        if h0 == h1:
            return a
        b = jnp.broadcast_to(cols[:, first + h1:first + h1 + 1], (dk, LANES))
        return jnp.where(lane < h1 * value_dim - start, a, b)

    for grp in range(heads // group):
        first = grp * group
        for tile in range(width // LANES):
            at = pl.ds(grp * width + tile * LANES, LANES)
            kx = spread(kt, first, tile)
            v, alpha, beta = (vab_ref[j:j + 1, at] for j in range(3))
            if channel:               # the row's own factor down the keys
                alpha = spread(at_ref[...], first, tile)
            s = s_ref[:, at] * alpha
            u = beta * (v - jnp.sum(s * kx, axis=0, keepdims=True))
            s = s + kx * u
            s_out[:, at] = s
            o_ref[:, at] = jnp.sum(s * spread(qt, first, tile), axis=0,
                                   keepdims=True)


@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def _gated_delta(arena, rows, qt, kt, vab, at=None, *, heads, interpret):
    n, dk, _ = qt.shape
    decays = () if at is None else (at,)
    width = arena.shape[-1]
    value_dim = width // heads
    per_slot = lambda *tail: pl.BlockSpec(  # noqa: E731
        (None,) + tail, lambda i, rows: (i,) + (0,) * len(tail))
    row = pl.BlockSpec((None, dk, width), lambda i, rows: (rows[i], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[per_slot(dk, heads), per_slot(dk, heads),
                  per_slot(3, width)]
        + [per_slot(dk, heads) for _ in decays] + [row],
        out_specs=[row, per_slot(1, width)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, heads=heads, value_dim=value_dim,
                          group=_group(value_dim), channel=bool(decays)),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(arena.shape, arena.dtype),
                   jax.ShapeDtypeStruct((n, 1, width), jnp.float32)],
        # the last operand (the fifth, after the prefetched rows, or the
        # sixth behind a decay a channel): the arena, in place
        input_output_aliases={4 + len(decays): 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="gated_delta_decode",
    )(rows.astype(jnp.int32), qt, kt, vab, *decays, arena)


def gated_delta_decode(arena, rows, q, k, v, alpha, beta):
    """One token a slot through the states in ``arena`` (rows, d_k, H
    d_v) float32, donated. ``rows`` (N,) int32 each slot's arena row;
    ``q``, ``k`` (N, H, d_k), ``v`` (N, H, d_v), ``alpha``, ``beta`` (N,
    H), float32; ``alpha`` (N, H, d_k) is a decay a key channel: the
    state's row ``d`` of head ``h`` is multiplied by ``alpha[n, h, d]``.
    Returns (o (N, H, d_v) float32, the arena with those rows updated).
    Callers check :func:`supported` first. The kernel's call is jitted on
    its own, so that the layers of a model trace it once."""
    n, heads, _ = q.shape
    value_dim = v.shape[-1]
    f32 = jnp.float32
    lanes = lambda a: jnp.repeat(a.astype(f32), value_dim, axis=-1)  # noqa: E731
    keys = lambda a: a.astype(f32).transpose(0, 2, 1)  # noqa: E731
    # a decay a channel rides with the keys, (N, d_k, H) behind ``vab``,
    # whose second row is then not read
    channel = alpha.ndim == 3
    vab = jnp.stack([v.astype(f32).reshape(n, -1),
                     lanes(beta if channel else alpha), lanes(beta)],
                    axis=1)                                   # (N, 3, H d_v)
    arena, o = _gated_delta(
        arena, rows, keys(q), keys(k), vab,
        *((keys(alpha),) if channel else ()), heads=heads,
        interpret=pallas_mode() == "interpret")
    return o.reshape(n, heads, value_dim), arena


# ---- a step's convolution tails: one pass over the arena in arena order -----

# lanes of a row the body takes at a time (a run of whole lane tiles that
# divides the channels): four taps and their float32 sum in registers
TAILS_LANES = 512


def tails_supported(arena_shape, arena_dtype, channels: int) -> bool:
    """Whether :func:`tails_step` takes this arena: ``(rows, tail *
    channels)`` of 2- or 4-byte numbers, taps of whole lane tiles, and one
    sublane tile of rows (in and out, twice each for the pipeline) with
    its inputs and its float32 results within the VMEM budget. No floor
    on the rows: at the documents cell's 33 the kernel is 25 us a layer
    where the jnp lines are 57 (``tools/state_tails_forms.py``,
    ``PERF.md`` section 6, PR 59)."""
    if pallas_mode() is None or channels % LANES:
        return False
    if len(arena_shape) != 2 or arena_shape[1] % channels:
        return False
    item = jnp.dtype(arena_dtype).itemsize
    if item not in (2, 4):
        return False
    block = (32 // item) * (4 * arena_shape[1] * item
                            + 2 * channels * (item + 4))
    return block <= VMEM_BUDGET_BYTES


def _tails_kernel(live_ref, x_ref, w_ref, t_ref, t_out, u_ref, *, taps):
    c = x_ref.shape[1]
    lanes = max(n for n in range(LANES, TAILS_LANES + 1, LANES) if c % n == 0)
    live = live_ref[...] != 0                                 # (rows, 1)
    for at in range(0, c, lanes):
        cut = [t_ref[:, j * c + at:j * c + at + lanes]
               for j in range(taps - 1)] + [x_ref[:, at:at + lanes]]
        # ``GatedDeltaNet.convolve``'s sum: float32 products from tap 0
        acc = sum(w_ref[j:j + 1, at:at + lanes] * t.astype(jnp.float32)
                  for j, t in enumerate(cut))
        u_ref[:, at:at + lanes] = acc * jax.nn.sigmoid(acc)
        for j in range(taps - 1):
            t_out[:, j * c + at:j * c + at + lanes] = jnp.where(
                live, cut[j + 1], cut[j])


@functools.partial(jax.jit, static_argnames=("interpret",))
def _tails_step(tails, live, x_r, w, *, interpret):
    r, width = tails.shape
    taps, c = w.shape
    rb = 32 // tails.dtype.itemsize                   # one sublane tile
    by_rows = lambda cols: pl.BlockSpec((rb, cols), lambda i: (i, 0))  # noqa: E731
    return pl.pallas_call(
        functools.partial(_tails_kernel, taps=taps),
        grid=(pl.cdiv(r, rb),),
        in_specs=[by_rows(1), by_rows(c),
                  pl.BlockSpec((taps, c), lambda i: (0, 0)), by_rows(width)],
        out_specs=[by_rows(width), by_rows(c)],
        out_shape=[jax.ShapeDtypeStruct(tails.shape, tails.dtype),
                   jax.ShapeDtypeStruct((r, c), jnp.float32)],
        input_output_aliases={3: 0},                  # the arena, in place
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="state_tails_step",
    )(live.astype(jnp.int32)[:, None], x_r, w.astype(jnp.float32), tails)


def tails_step(tails, live, x_r, w):
    """One position's convolution a row over a request's kept inputs
    ("tails") where they lie: ``tails`` (R, (K - 1) C), donated, a row's
    last ``K - 1`` inputs oldest first side by side on the lanes, so that
    tap ``j`` is the lanes ``[j C, (j + 1) C)``: whole lane tiles, a slice
    that moves nothing; ``x_r`` (R, C) each ROW's new inputs; ``live``
    (R,) whether a slot names the row; ``w`` (K, C) the taps' weights.
    Returns (``silu`` of the K float32 products summed from tap 0, (R, C)
    float32; the arena with every live row shifted by one tap behind its
    inputs, the others as they were). A grid step takes one sublane tile
    of rows at their whole width, which under the arena's tiling is one
    contiguous copy each way: NOT a slot a grid step with the row's
    address prefetched, as :func:`gated_delta_decode` has it: one row of
    a 2-D bfloat16 arena is ``width / 128`` pieces of 256 bytes. Callers
    check :func:`tails_supported` first; the call is jitted on its own,
    so that a model's layers trace it once."""
    tails, u = _tails_step(tails, live, x_r.astype(tails.dtype), w,
                           interpret=pallas_mode() == "interpret")
    return u, tails


# ---- the whole-sequence form: a chunk of tokens a grid step -----------------

# tokens a grid step takes: one unit-lower system of ROWS x ROWS a head.
# 128 fills the matrix unit's tiles where ops/gated_delta.py's CHUNK of
# 64 half-fills them (PERF.md section 6, PR 33: the table on the chip)
ROWS = 128
# sequences from which the kernel beats the jnp scan on a v5e: one chunk
# of the scan's (64 tokens) or less is one short loop there and one
# padded chunk of ROWS here (PERF.md section 6, PR 33: the table). With a
# decay a key channel the cut is the same (PR 64, ``tools/
# delta_rule_crossover.py --decay channel``: from 128 tokens the kernel is
# 2.2 to 4.6 times the faster; at 64 it reads 0.077 | 0.192 ms against
# the scan's 0.109 | 0.230 at 32 | 64 heads of 128, within a third either
# way, and nothing was read below, where no cell's program lies)
MIN_SEQ = 65
# the body unrolls a group of heads; past this many no group is built
MAX_GROUP = 8
# tokens a sub-chunk of the form with a decay a key CHANNEL: a chunk's
# products are taken against the start of the row's sub-chunk, and a gate
# bounded below by -88 / SUB a token (-5.5) keeps exp(SUB |g|) in float32
SUB = 16
_HI = jax.lax.Precision.HIGHEST


def _whole_tiles(width: int) -> int:
    """Heads of ``width`` lanes that fill whole lane tiles side by side."""
    return LANES // math.gcd(width, LANES)


# heads a grid step takes side by side where fewer would fill whole lane
# tiles: a head is a chain of dependent products, and the matrix unit
# waits between one chain's stages (PR 33: four at 96 and 192; PR 64, a
# decay a channel, ``tools/delta_rule_crossover.py --decay channel``: 4.86,
# 3.17, 2.81 ms a layer at 1, 2 and 4 with 64 heads of 128 and 2,048
# tokens, 2.33, 1.56, 1.38 with 32 heads and two rows of 1,024; 8 read
# 2.24 and 1.38, at twice the body to trace and compile a program)
CHAINS = 4


def _chunk_group(key_dim: int, value_dim: int, heads: int) -> int:
    """Heads a grid step takes: the fewest whose keys AND values fill
    whole lane tiles of the (B, S, H d) operands (4 at 96 and 192), and
    as many such sets as make ``CHAINS`` heads, no more than the op
    has."""
    whole = math.lcm(_whole_tiles(key_dim), _whole_tiles(value_dim))
    return whole * max(1, min(CHAINS // whole, -(-heads // whole)))


def _chunks_vmem_bytes(group: int, key_dim: int, value_dim: int,
                       channel: bool = False) -> int:
    """The working set of a grid step: q, k, v in and o out and the
    chunk's constant tiles twice (the pipeline's two buffers), the
    states in, out and in the scratch, and each head's (ROWS, ROWS) and
    (2 ROWS, lanes) temporaries (the heads go stage by stage, so all of
    them live at once), all float32. A decay a ``channel`` comes in as
    the keys do and makes ten (ROWS, keys) arrays more a head (the
    decays of rows and columns, the keys and queries under them, one
    sub-chunk's columns)."""
    kp, vp = _round_up(key_dim, LANES), _round_up(value_dim, LANES)
    blocks = 2 * ROWS * group * (2 * key_dim + 2 * value_dim)
    masks = 2 * (ROWS.bit_length() + 1) * ROWS * ROWS
    states = group * (4 * key_dim * value_dim + kp * vp)
    temps = group * (8 * ROWS * ROWS + 6 * ROWS * (kp + vp))
    if channel:
        blocks += 2 * ROWS * group * key_dim
        temps += group * 10 * ROWS * kp
    return 4 * (blocks + masks + states + temps)


def chunks_supported(seq: int, heads: int, key_dim: int, value_dim: int,
                     dtype, channel: bool = False) -> bool:
    """Whether :func:`gated_delta_chunks` takes these shapes: more than
    one chunk of the jnp scan's, float32, keys that fill whole sublane
    tiles of a state, heads that group into whole lane tiles (values in
    whole groups: a tile two heads share is written by both), a group
    the body can unroll, a working set within the VMEM budget;
    ``channel``: for a decay a key channel."""
    if pallas_mode() is None or seq < MIN_SEQ:
        return False
    if jnp.dtype(dtype) != jnp.dtype(jnp.float32):
        return False
    if key_dim % 8 or heads % _whole_tiles(value_dim):
        return False
    group = _chunk_group(key_dim, value_dim, heads)
    if group > MAX_GROUP:
        return False
    return _chunks_vmem_bytes(group, key_dim, value_dim,
                              channel) <= VMEM_BUDGET_BYTES


def _mm(a, b, dims=(((1,), (0,)), ((), ()))):
    """A float32 product at float32 contract precision (Mosaic's default
    is bf16 passes even for float32 operands)."""
    return jax.lax.dot_general(a, b, dims, precision=_HI,
                               preferred_element_type=jnp.float32)


def _take(ref, start: int, width: int):
    """Lanes ``[start, start + width)`` of a (rows, lanes) ref as whole
    lane tiles: moved to lane 0, zeros after them. The head's window of
    whole tiles is loaded, rotated, cut and masked (a select, so that
    what lies beside the head, another head or a boundary block's
    padding, does not reach the result)."""
    lo, hi = start // LANES * LANES, _round_up(start + width, LANES)
    x = ref[:, lo:hi]
    if start != lo:
        x = pltpu.roll(x, (hi - lo) - (start - lo), 1)
    x = x[:, :_round_up(width, LANES)]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where(lane < width, x, 0.0)


def _put(tiles, x, start: int, width: int):
    """:func:`_take` backwards: ``x`` (rows, whole tiles; zeros past
    ``width``) added into the lane tiles ``tiles`` (a list) at lanes
    ``[start, start + width)``."""
    lo, hi = start // LANES * LANES, _round_up(start + width, LANES)
    if hi - lo > x.shape[1]:
        x = jnp.concatenate(
            [x, jnp.zeros((x.shape[0], hi - lo - x.shape[1]), x.dtype)], 1)
    if start != lo:
        x = pltpu.roll(x, start - lo, 1)
    for n in range((hi - lo) // LANES):
        part, at = x[:, n * LANES:(n + 1) * LANES], lo // LANES + n
        tiles[at] = part if tiles[at] is None else tiles[at] + part


def _tile_masks(rows: int):
    """The constant 0/1 tiles of a chunk, float32 (3 + levels, rows,
    rows) over (t, i): ``t >= i``; ``t > i``; ``t`` and ``i`` in one pair;
    then for each level l >= 1 the entries that join two diagonal blocks
    of 2^l into one of 2^(l+1) (same block of 2^(l+1), other block of
    2^l). Made once a call by XLA and held in VMEM for the whole grid: a
    mask made in the kernel is integer work on every head of every
    chunk."""
    t = jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 0)
    i = jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 1)
    masks = [t >= i, t > i, (t >> 1) == (i >> 1)]
    for level in range(1, rows.bit_length() - 1):
        masks.append(((t >> level + 1) == (i >> level + 1))
                     & ((t >> level) != (i >> level)))
    return jnp.stack(masks).astype(jnp.float32)


def _cut(x, lo: int, hi: int):
    """Rows ``[lo, hi)`` of ``x`` (rows, lanes) by lax's own slice: the
    operation ``x[lo:hi]`` traces to, at a third of the time to trace it
    (a kernel's body is traced anew for every program that holds it, and
    this one slices some hundreds of times)."""
    if hi - lo == x.shape[0]:
        return x
    return jax.lax.slice(x, (lo, 0), (hi, x.shape[1]))


def _stack(parts):
    """``parts`` one under the other: ``jnp.concatenate``'s operation."""
    return jax.lax.concatenate(parts, 0)


def _unit_lower_inverses(lows, m_ref):
    """``(I + low)^-1`` for each strictly lower-triangular tile of
    ``lows``, by block substitution in doubling blocks: with ``T`` the
    inverse of the diagonal blocks of size s and ``B`` the part of
    ``low`` that joins two neighbours into one block of 2 s, the inverse
    of those blocks is ``T - T B T`` (the 2 x 2 block formula). Blocks of
    2 need no product. Only the later half of each block of 2 s changes,
    so from s = 8 (a sublane tile) the two products run over those rows
    alone, gathered tile by tile. Every step is a substitution, so
    nothing is lost where a series in powers of ``low`` cancels (keys
    nearly parallel, beta near 2). The tiles are independent chains of
    dependent products: they go level by level side by side, so that one
    tile's products fill the matrix unit while another's drain."""
    rows = lows[0].shape[0]
    eye = m_ref[0] - m_ref[1]
    invs = [eye - low * m_ref[2] for low in lows]
    for level in range(1, rows.bit_length() - 1):
        s, joins = 1 << level, m_ref[2 + level]
        if s < 8:
            invs = [inv - _mm(inv, _mm(low * joins, inv))
                    for low, inv in zip(lows, invs)]
            continue
        later = range(s, rows, 2 * s)
        gather = lambda x: _stack([_cut(x, p, p + s) for p in later])  # noqa: E731
        zeros = jnp.zeros((s, rows), jnp.float32)
        through = [_mm(gather(low * joins), inv)          # (rows / 2, rows)
                   for low, inv in zip(lows, invs)]
        change = [_mm(gather(inv), _stack(
            [part for n in range(len(later))
             for part in (zeros, _cut(thr, n * s, (n + 1) * s))]))
            for inv, thr in zip(invs, through)]
        invs = [_stack(
            [part for n, p in enumerate(later)
             for part in (_cut(inv, p - s, p),
                          _cut(inv, p, p + s) - _cut(chg, n * s, (n + 1) * s))])
            for inv, chg in zip(invs, change)]
    return invs


def _channel_decays(log, k, q):
    """What a chunk with a decay a key CHANNEL makes of its decay before
    any product, for a whole group of heads at once (it is elementwise
    along the lanes, so the heads go side by side, ``lanes`` = the
    group's padded keys: a body a head would be traced and lowered a
    head, four times the operations for the same work). ``log`` (rows,
    lanes): the log-decay summed from the start of each row's SUB-chunk
    of ``SUB`` tokens (at least ``-88``: the gate's bound); ``k``, ``q``
    (rows, lanes). With ``G`` the log-decay summed from the chunk's
    start and ``G^a`` its value before sub-chunk ``a``, returns

    * ``lhs``: for each sub-chunk ``a`` its keys' rows above its queries'
      (2 SUB, lanes), each times ``exp(G_t - G^a)``, at most 1;
    * ``cols``: for each ``a`` the keys as columns (rows, lanes), ``k_i
      exp(G^a - G_i)``: at most 1 in the sub-chunks before ``a``, at most
      ``exp(SUB |g|)`` inside it, zeros behind it (never read);
    * ``since`` (rows, lanes), ``exp(G_t)``; ``out`` (rows, lanes), ``k_i
      exp(G_end - G_i)``; ``carry`` (1, lanes), ``exp(G_end)``.

    ONE exponential of (rows, lanes) a sign: a column of an earlier
    sub-chunk is taken against its own sub-chunk's END and carried to
    ``G^a`` by a (1, lanes) factor a pair of sub-chunks, each a product
    of whole sub-chunks' decays."""
    rows, lanes = log.shape
    nsub = rows // SUB
    cut = lambda x, b: _cut(x, b * SUB, (b + 1) * SUB)  # noqa: E731
    over = lambda x: jax.lax.broadcast_in_dim(  # noqa: E731
        x, (SUB, lanes), (0, 1))
    down = jnp.exp(log)               # exp(G_t - G^a), a the row's own
    up = k * jnp.exp(-log)            # k_i exp(G^a - G_i), a the column's own
    # a whole sub-chunk's decay, and between[a][c] = exp(G^a - G^c), c <= a
    whole = [jnp.exp(_cut(log, (b + 1) * SUB - 1, (b + 1) * SUB))
             for b in range(nsub)]
    between = []
    for a in range(nsub + 1):
        run = [None] * (a + 1)
        for c in range(a - 1, -1, -1):
            run[c] = whole[c] if run[c + 1] is None else run[c + 1] * whole[c]
        between.append(run)
    # a column against its sub-chunk's end, then carried on to G^a
    ended = [cut(up, b) * over(whole[b]) for b in range(nsub)]
    carried = lambda a, b: (  # noqa: E731
        ended[b] if a == b + 1 else ended[b] * over(between[a][b + 1]))
    zeros = jnp.zeros((SUB, lanes), jnp.float32)
    cols = [_stack([carried(a, b) for b in range(a)] + [cut(up, a)]
                  + [zeros] * (nsub - a - 1)) for a in range(nsub)]
    out = _stack([carried(nsub, b) for b in range(nsub)])
    kd, qd = k * down, q * down
    lhs = [_stack([cut(kd, a), cut(qd, a)]) for a in range(nsub)]
    since = down * _stack([jnp.ones((SUB, lanes), jnp.float32)]
                         + [over(between[a][0]) for a in range(1, nsub)])
    return lhs, cols, since, out, between[nsub][0]


def _chunks_kernel(q_ref, k_ref, v_ref, gb_ref, gt_ref, m_ref, s0_ref, *rest,
                   group, key_dim, value_dim, unit_eps, norm_eps,
                   channel=False):
    """One chunk of a group of heads. The decay is a number a head and
    token (``gb_ref`` (rows, 2 group): the log-decay summed from the
    chunk's start beside beta; ``gt_ref`` (group, rows): the sums along
    the lanes) or, ``channel``, a number a key channel (``gb_ref`` (rows,
    group keys): the log-decay summed from each SUB-chunk's start, as
    the keys lie; ``gt_ref`` (rows, group): beta). What differs between
    the two is how the chunk's ``k k^T`` and ``q k^T`` under the decay
    are made and what the decay since the chunk's start is, a column or
    a (rows, keys) array; the system, its inverse and the carry are one
    code."""
    gain_ref = rest[0] if norm_eps is not None else None
    o_ref, s_out, s_scr = rest[-3:]
    c = pl.program_id(2)
    rows = q_ref.shape[0]
    kp = s_scr.shape[1]
    heads = range(group)

    @pl.when(c == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)
        for j in heads:
            s_scr[j, :key_dim, :value_dim] = s0_ref[j]

    # stage by stage over the group's heads, not head by head: the heads
    # are independent, and each stage of one is a chain the next waits for
    qs = [_take(q_ref, j * key_dim, key_dim) for j in heads]     # (rows, kp)
    ks = [_take(k_ref, j * key_dim, key_dim) for j in heads]
    vs = [_take(v_ref, j * value_dim, value_dim) for j in heads]  # (rows, vp)
    if unit_eps is not None:      # GatedDeltaNet.heads: unit q and k a head
        unit = lambda a: a * jax.lax.rsqrt(  # noqa: E731
            jnp.sum(a * a, axis=1, keepdims=True) + unit_eps)
        qs = [unit(q) * key_dim ** -0.5 for q in qs]
        ks = [unit(k) for k in ks]
    if channel:
        betas = [gt_ref[:, j:j + 1] for j in heads]               # (rows, 1)
        # the group's heads side by side on the lanes, and a head's back
        wide = lambda xs: jax.lax.concatenate(xs, 1)  # noqa: E731
        head = lambda x, j: jax.lax.slice_in_dim(  # noqa: E731
            x, j * kp, (j + 1) * kp, axis=1)
        lhs, cols, since, out, carry = _channel_decays(
            wide([_take(gb_ref, j * key_dim, key_dim) for j in heads]),
            wide(ks), wide(qs))
        sinces, outs, carries = ([head(x, j) for j in heads]
                                 for x in (since, out, carry))
        # a sub-chunk's rows against every column it reads: (2 SUB, rows),
        # its keys' pairs above its queries'
        pairs = [[_mm(head(rows_a, j), head(cols_a, j), _NT)
                  for rows_a, cols_a in zip(lhs, cols)] for j in heads]
        part = lambda pair, at: _stack(  # noqa: E731
            [_cut(p, at, at + SUB) for p in pair])
        lows = [beta * part(pair, 0) * m_ref[1]
                for beta, pair in zip(betas, pairs)]
        qks = [part(pair, SUB) * m_ref[0] for pair in pairs]
    else:
        # gc: the cumulative log-decay from the chunk's start, down the
        # sublanes and along the lanes; beta down the sublanes
        gcs = [gb_ref[:, j:j + 1] for j in heads]                 # (rows, 1)
        betas = [gb_ref[:, group + j:group + j + 1] for j in heads]
        # decay[t, i] = prod_{i < j <= t} alpha_j, for i <= t (0 above)
        decays = [jnp.exp(jnp.minimum(gc - gt_ref[j:j + 1, :], 0.0))
                  * m_ref[0] for j, gc in zip(heads, gcs)]
        kqs = [_mm(jnp.concatenate([k, q], axis=0), k, _NT)   # (2 rows, rows)
               for k, q in zip(ks, qs)]
        lows = [beta * kq[:rows] * decay * m_ref[1]
                for beta, kq, decay in zip(betas, kqs, decays)]
    invs = _unit_lower_inverses(lows, m_ref)
    if not channel:
        # the decay since the chunk's start
        sinces = [jnp.exp(gc) for gc in gcs]
    # u_t = uv_t - w_t S_0: what position t adds to the state as k_t u_t^T
    wus = [_mm(inv, jnp.concatenate([(beta * since) * k, beta * v], axis=1))
           for inv, beta, since, k, v in zip(invs, betas, sinces, ks, vs)]
    states = [s_scr[j] for j in heads]
    againsts = [_mm(jnp.concatenate([wu[:, :kp], since * q], axis=0), state)
                for wu, since, q, state in zip(wus, sinces, qs, states)]
    us = [wu[:, kp:] - against[:rows] for wu, against in zip(wus, againsts)]
    if channel:
        throughs = [_mm(jnp.concatenate([qk, out.T], axis=0), u)
                    for qk, out, u in zip(qks, outs, us)]
        # the whole chunk's decay down the state's sublanes: the (1, kp)
        # row through the diagonal, summed along the lanes
        diagonal = (jax.lax.broadcasted_iota(jnp.int32, (kp, kp), 0)
                    == jax.lax.broadcasted_iota(jnp.int32, (kp, kp), 1))
        keeps = [jnp.sum(jnp.where(diagonal, carry, 0.0), axis=1,
                         keepdims=True) for carry in carries]
    else:
        # the whole chunk's log-decay, (1, 1): summed out of the lanes'
        # form (a slice of the sublanes' form sits on sublane 7, and
        # Mosaic broadcasts along one of sublanes and lanes at a time)
        last = jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1) == rows - 1
        ends = [jnp.sum(jnp.where(last, gt_ref[j:j + 1, :], 0.0), axis=1,
                        keepdims=True) for j in heads]
        throughs = [_mm(jnp.concatenate(
            [kq[rows:] * decay, (jnp.exp(end - gc) * k).T], axis=0), u)
            for kq, decay, end, gc, k, u in zip(kqs, decays, ends, gcs, ks,
                                                us)]
    tiles = [None] * (group * value_dim // LANES)
    for j in heads:
        keep = keeps[j] if channel else jnp.exp(ends[j])
        s_scr[j] = keep * states[j] + throughs[j][rows:]
        o = againsts[j][rows:] + throughs[j][:rows]
        if norm_eps is not None:  # GatedDeltaNet.finish: RMSNorm a head
            o = o * jax.lax.rsqrt(jnp.sum(o * o, axis=1, keepdims=True)
                                  * (1.0 / value_dim) + norm_eps) * gain_ref[...]
        _put(tiles, o, j * value_dim, value_dim)
    for n, tile in enumerate(tiles):
        o_ref[:, n * LANES:(n + 1) * LANES] = tile

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        for j in heads:
            s_out[j] = s_scr[j, :key_dim, :value_dim]


@functools.partial(jax.jit, static_argnames=(
    "heads", "rows", "unit_eps", "norm_eps", "interpret"))
def _gated_delta_chunks(q, k, v, g, beta, state, gain=None, *, heads, rows,
                        unit_eps=None, norm_eps=None, interpret):
    b, s, _ = q.shape
    key_dim, value_dim = q.shape[-1] // heads, v.shape[-1] // heads
    channel = g.shape[-1] != heads    # (B, S, H d_k), flat as the keys are
    group = _chunk_group(key_dim, value_dim, heads)
    groups = -(-heads // group)
    pad = -s % rows
    if pad:
        q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
                            for a in (q, k, v, g, beta))
    n = (s + pad) // rows

    def grouped(a):               # (B, n, rows, H) -> (B, groups, n, rows, group)
        a = jnp.pad(a, ((0, 0),) * 3 + ((0, groups * group - heads),))
        return a.reshape(b, n, rows, groups, group).transpose(0, 3, 1, 2, 4)

    lanes = lambda d: pl.BlockSpec(  # noqa: E731
        (None, rows, group * d), lambda bi, gi, ci: (bi, ci, gi))
    gates = lambda *blk: pl.BlockSpec(  # noqa: E731
        (None, None, None) + blk, lambda bi, gi, ci: (bi, gi, ci, 0, 0))
    if channel:
        # the log-decay summed from each SUB-chunk's start, as the keys
        # lie: what a chunk's decays are made of in VMEM (never a sum over
        # the whole chunk, whose differences would round where a
        # sub-chunk's do not)
        gc = jnp.cumsum(g.reshape(b, -1, SUB, heads * key_dim),
                        axis=2).reshape(g.shape)
    else:
        # the log-decay summed from each chunk's start: (B, S, H), the
        # one thing of a chunk made before the call (its (rows, rows)
        # decay, its system and the system's inverse are made in VMEM)
        gc = grouped(jnp.cumsum(g.reshape(b, n, rows, heads), axis=2))
    masks = _tile_masks(rows)
    states = pl.BlockSpec((None, group, key_dim, value_dim),
                          lambda bi, gi, ci: (bi, gi, 0, 0))
    vp = _round_up(value_dim, LANES)
    normed = () if norm_eps is None else (jnp.pad(
        gain.astype(jnp.float32), (0, vp - value_dim)).reshape(1, vp),)
    beta = grouped(beta.reshape(b, n, rows, heads))
    if channel:
        decay = (gc, beta)
        decay_specs = [lanes(key_dim), gates(rows, group)]
    else:
        decay = (jnp.concatenate([gc, beta], axis=-1),
                 gc.transpose(0, 1, 2, 4, 3))
        decay_specs = [gates(rows, 2 * group), gates(group, rows)]
    o, state = pl.pallas_call(
        functools.partial(_chunks_kernel, group=group, key_dim=key_dim,
                          value_dim=value_dim, unit_eps=unit_eps,
                          norm_eps=norm_eps, channel=channel),
        grid=(b, groups, n),
        in_specs=[lanes(key_dim), lanes(key_dim), lanes(value_dim),
                  *decay_specs,
                  pl.BlockSpec(masks.shape, lambda bi, gi, ci: (0, 0, 0)),
                  states] + [pl.BlockSpec((1, vp), lambda bi, gi, ci: (0, 0))
                             for _ in normed],
        out_specs=[lanes(value_dim), states],
        out_shape=[jax.ShapeDtypeStruct(v.shape, jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM(
            (group, _round_up(key_dim, LANES), vp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="channel_delta_chunks" if channel else "gated_delta_chunks",
    )(q, k, v, *decay, masks, state, *normed)
    return o[:, :s], state


def gated_delta_chunks(q, k, v, g, beta, state, *, unit_eps=None, norm=None):
    """The gated delta rule over whole sequences, as
    ``ops/gated_delta.py`` ``chunked_delta_rule`` takes and returns them
    (``q``, ``k`` (B, S, H, d_k), ``v`` (B, S, H, d_v), ``g`` = log alpha
    and ``beta`` (B, S, H), ``state`` (B, H, d_k, d_v), float32), one
    kernel call: grid over (row, group of heads, chunk of ``ROWS``
    tokens), the chunks in turn, the group's states in a VMEM scratch
    from the first chunk to the last. q, k, v may as well come flat, (B,
    S, H d) as the projections and the convolution wrote them, heads side
    by side on the lanes, which is how the kernel reads them; o comes
    back in v's form. The chunk's decay, its unit-lower system and the
    system's inverse exist only in VMEM.

    ``g`` (B, S, H, d_k) is a decay a key CHANNEL
    (``chunked_channel_rule``'s): the same call and grid, the Pallas
    call named ``channel_delta_chunks``; no lower than ``-88 / SUB`` a
    token, which the op checks of its gate's bound.

    Two things the op does around the recurrence reduce over a head's
    lanes, which XLA does by transposing the whole (B, S, H d) array
    and back; on a head's tile in VMEM they are a lane sum. ``unit_eps``:
    q and k arrive as the convolution wrote them and each head's is
    L2-normalised here, ``a * rsqrt(sum(a^2) + unit_eps)``, q then scaled
    by ``d_k^-1/2`` (``GatedDeltaNet.heads``). ``norm`` = (gain (d_v,),
    eps): o leaves RMS-normalised over each head's d_v, times the gain
    (``GatedDeltaNet.finish``'s first step).

    Callers check :func:`chunks_supported` first. The call is jitted on
    its own, so that the layers of a model trace it once a shape."""
    b, s, heads = g.shape[:3]
    f32 = jnp.float32
    flat = lambda a: a.astype(f32).reshape(b, s, -1)  # noqa: E731
    gain, norm_eps = (None, None) if norm is None else norm
    o, state = _gated_delta_chunks(
        flat(q), flat(k), flat(v), flat(g), beta.astype(f32),
        state.astype(f32), gain, heads=heads, rows=ROWS, unit_eps=unit_eps,
        norm_eps=norm_eps, interpret=pallas_mode() == "interpret")
    return o.reshape(v.shape), state


def delta_rule_step(state, q, k, v, alpha, beta):
    """The step in jnp, one token a row. ``state`` (N, d_k, H d_v)
    float32, laid out as an arena row; the rest as
    :func:`gated_delta_decode` takes them. Returns (o (N, H, d_v), the new
    state)."""
    n, h, dk = q.shape
    hi = jax.lax.Precision.HIGHEST
    # ``alpha`` (N, H): one decay a head; (N, H, d_k): one a key channel
    st = state.reshape(n, dk, h, -1)
    st = st * (alpha[:, None, :, None] if alpha.ndim == 2
               else alpha.transpose(0, 2, 1)[..., None])
    u = beta[..., None] * (v - jnp.einsum("ndhv,nhd->nhv", st, k,
                                          precision=hi))
    st = st + k.transpose(0, 2, 1)[..., None] * u[:, None]
    o = jnp.einsum("ndhv,nhd->nhv", st, q, precision=hi)
    return o, st.reshape(state.shape)


def gated_delta_step(arena, rows, q, k, v, alpha, beta):
    """:func:`gated_delta_decode` in jnp: the slots' rows gathered,
    :func:`delta_rule_step`, the rows scattered back (idle slots all name
    the null row; whichever lands there is as good as another)."""
    o, new = delta_rule_step(arena[rows], q, k, v, alpha, beta)
    return o, arena.at[rows].set(new)


__all__ = ["chunks_supported", "delta_rule_step", "gated_delta_chunks",
           "gated_delta_decode", "gated_delta_step", "supported"]
