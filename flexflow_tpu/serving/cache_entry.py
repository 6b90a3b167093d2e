"""The cache entry of a sequence-mixing op: what a layer keeps for a
token, or for a request.

One decision lives here and nowhere else: what one token's row (or one
request's state) is, how it is allocated and what it weighs, how a step
of W new tokens and a prefill write it and read it, what its dense form
is, and what it cannot do. An :class:`EntryKind` answers, :data:`KINDS`
names one per op type, and everything else asks: the pool (kv_cache.py)
allocates what a kind describes and counts its bytes, the programs
(generation.py) hand every such op to its kind, the scheduler reads its
limits. A new kind is a class and a line in :data:`KINDS`; those three
modules do not change.

A kind keeps a row a TOKEN (``arenas``), addressed through the slots'
block tables, or a row a REQUEST (``request_arenas``), addressed by the
slots' rows, or both; the programs pass both addresses as one
:class:`~flexflow_tpu.serving.kv_cache.Addresses`. What a step hands
between its slots and an arena's rows goes through ``ops/rows.py``, and a
request's convolution tail is a :class:`ConvTail`, whichever kind holds it.

A kind that defines ``chunk`` serves a prompt in chunks, each behind what
the chunks before left: the pair through its block table, the window over
its ring, the sparse kind under its selection, the decay state and the
Mamba-2 state (:class:`SsmStateEntry`) and the gated-delta state
(:class:`StateEntry`), each its state AND its convolution's tail, from the
request's row, the latent row under an indexer's selection
(:class:`SparseLatentEntry`). The plain latent row and the int8 pair
prefill a prompt whole.

An entry is the tuple of arrays its kind allocates, donated through the
programs; the kind is static Python beside it. Every reader masks by
position: a query at absolute position ``p`` sees keys at positions
``<= p``, everything else (a window's own future rows, stale rows after
a speculative roll-back, the null block's garbage) is set to -1e30
before the softmax, where ``exp`` underflows to exactly 0.0, so the
paged forms compute the dense rectangle's sums.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.op import sub_scope
from ..ffconst import OpType
from ..kernels import (chunk_attention, gated_delta, latent_attention,
                       paged_attention, ssd_step)
from ..obs.metrics import metrics_registry
from ..ops import block_sparse_attention as bsa
from ..ops import mamba2
from ..ops.attention import Indexer
from ..ops.gated_delta import delta_rule_path
from ..ops.rows import named_by, spread_rows
from ..parallel.ring_attention import sink_softmax
from .kv_cache import NULL_BLOCK, NULL_ROW


def _iota(n):
    return jax.lax.iota(jnp.int32, n)


def _scores(q, k, scale):
    """(B, Sq, H, D) queries against (B, Sk, H, D) keys -> (B, H, Sq, Sk)."""
    return jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale


def _weigh(scores, mask, v, sink=None):
    """The masked softmax of ``scores`` over the (B, Sk, H, D) values;
    ``mask`` broadcasts against (B, H, Sq, Sk), True where a query sees a
    key. With :func:`_scores`, the one copy of the attend every (k, v)
    form shares."""
    probs = sink_softmax(jnp.where(mask, scores, -1e30), sink)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _attend(q, k, v, mask, scale, sink=None):
    """The masked softmax attention of (B, Sq, H, D) queries over (B, Sk,
    Hkv, D) keys and (B, Sk, Hkv, Dv) values; ``mask()`` gives what
    broadcasts against (B, 1, Sq, Sk), made behind the scores (the order
    the programs' lowered text has had). A key head a query head:
    :func:`_scores` and :func:`_weigh`. Grouped heads: the ``H / Hkv``
    query heads of a group read the one key head where it lies, nothing
    is repeated. ``sink`` (H,): one more column of each head's softmax."""
    h, hkv = q.shape[2], k.shape[2]
    if h == hkv:
        scores = _scores(q, k, scale)
        return _weigh(scores, mask(), v, sink)
    b, sq, _, d = q.shape
    qg = q.reshape(b, sq, hkv, h // hkv, d)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k) * scale
    probs = sink_softmax(jnp.where(mask()[:, :, None], scores, -1e30),
                     None if sink is None else sink.reshape(hkv, h // hkv))
    return jnp.einsum("bhgqk,bkhd->bqhgd", probs, v).reshape(
        b, sq, h, v.shape[-1])


# keys one step of the WALK a chunk's attend takes where the kernel does
# not (:func:`_attend_spans`: the CPU, head widths of no whole lane tiles,
# a model over more than one device): the scores of a span are (heads,
# chunk, SPAN_TOKENS) float32 in HBM, the only array of two sequence axes
# a chunk's attention holds. Where ``kernels/chunk_attention.py`` takes
# the chunk (:meth:`PairEntry.chunk_path`) no such array is made at all
SPAN_TOKENS = 512
# the position of a key that holds nothing: later than any query
NOWHERE = chunk_attention.NOWHERE


def _attend_spans(op, q, qpos, kv_heads, read, lo, hi, sink=None, dv=None):
    """A chunk's (B, S, H, D) queries at positions ``qpos`` (B, S) over
    the key spans ``lo .. hi - 1`` (int32 scalars, traced: the spans
    outside them are not read at all): ``read(j)`` gives span j's (B, T,
    Hkv, D) keys and values and the (B, T) positions they hold
    (:data:`NOWHERE` for a row that holds nothing), and a query sees what
    ``op.sees`` says. A running maximum and sum in float32, a span at a
    time, so no (H, S, context) array is made; the ``H / Hkv`` query
    heads of a group read the one key head where it lies. The values are
    ``dv`` wide where that is not the keys' width; ``sink`` (H,) is where
    each head's running maximum and sum start (``m = s_h, l = 1``: one
    more column of the softmax that carries no value). Returns (B, S, H,
    Dv) in the queries' dtype."""
    b, s, h, d = q.shape
    dv = dv or d
    g = h // kv_heads
    qg = q.reshape(b, s, kv_heads, g, d)
    f32 = jnp.float32

    def body(j, carry):
        m, l, acc = carry
        k, v, kpos = read(j)
        sc = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                        preferred_element_type=f32) * op.scale
        seen = op.sees(qpos[:, :, None], kpos[:, None, :])[:, None, None]
        m_new = jnp.maximum(m, jnp.max(jnp.where(seen, sc, -1e30), axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(seen, jnp.exp(sc - m_new[..., None]), 0.0)
        l = alpha * l + p.sum(-1)
        acc = alpha[..., None] * acc + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p.astype(v.dtype), v,
            preferred_element_type=f32)
        return m_new, l, acc

    shape = (b, kv_heads, g, s)
    if sink is None:
        start = (jnp.full(shape, -1e30, f32), jnp.zeros(shape, f32))
    else:
        start = (jnp.broadcast_to(sink.astype(f32).reshape(
            kv_heads, g)[None, :, :, None], shape), jnp.ones(shape, f32))
    _, l, acc = jax.lax.fori_loop(
        lo, hi, body, start + (jnp.zeros(shape + (dv,), f32),))
    out = acc / jnp.maximum(l, 1e-30)[..., None]        # (B, Hkv, G, S, D)
    return jnp.moveaxis(out, 3, 1).reshape(b, s, h, dv).astype(q.dtype)


def _attend_kernel(op, q, qpos, keys, values, kpos, sink=None):
    """The same chunk through ``kernels/chunk_attention.py``: (B, S, H, D)
    queries over (B, L, Hkv D) keys and (B, L, Hkv Dv) values in the
    arena's row layout, row r at position ``kpos[:, r]``. Returns (B, S,
    H, Dv)."""
    b, s, h, d = q.shape
    return chunk_attention.chunk_attention(
        q.reshape(b, s, h * d), qpos, keys, values, kpos,
        kv_heads=keys.shape[-1] // d, scale=op.scale,
        window=op.window, sink=sink).reshape(b, s, h, -1)


def _put(arena, flat, rows):
    """Scatter ``rows`` (T, width) into an arena at flat token slots
    ``flat`` (T,). A token is one row of the arena's ``(num_blocks *
    block_size, width)`` view, a reshape that moves nothing under the
    TPU's tiling, so the donated buffer is updated in place."""
    nb, bs = arena.shape[:2]
    flat_arena = arena.reshape((nb * bs,) + arena.shape[2:])
    return flat_arena.at[flat].set(rows.astype(arena.dtype)).reshape(
        arena.shape)


def _prefill_slots(tables, lengths, pos, bs):
    """Where a group of prompts' rows go: row i's position p lands in
    block ``tables[i, p // bs]`` at offset ``p % bs``; padding positions
    (``p >= lengths[i]``) go to the null block. (P, Sb) flat slots."""
    blk = tables[:, pos // bs]
    return jnp.where(pos[None, :] < lengths[:, None],
                     blk * bs + (pos % bs)[None, :], NULL_BLOCK * bs)


def _quant_rows(x):
    """Asymmetric int8 per-(token, head) quantization over head_dim.
    ``x``: (T, H, D) -> (q int8, scale f32 (T, H), zero f32 (T, H)).
    Zero-point at the range midpoint, scale spanning [-127, 127], so
    dequantization is ``q * scale + zero``."""
    x = x.astype(jnp.float32)
    hi = x.max(-1)
    lo = x.min(-1)
    zero = 0.5 * (hi + lo)
    scale = jnp.maximum((hi - lo) / 254.0, 1e-8)
    q = jnp.clip(jnp.round((x - zero[..., None]) / scale[..., None]),
                 -127, 127)
    return q.astype(jnp.int8), scale, zero


def latent_row_lanes(width: int) -> int:
    """A latent row's width in the arena: whole 128-lane tiles."""
    return -(-int(width) // 128) * 128


@dataclasses.dataclass(frozen=True)
class ConvTail:
    """The last ``tail`` inputs of a layer's causal convolution, ``channels``
    wide, kept a REQUEST: one arena ``(rows, tail * channels)``, a row's
    inputs oldest first side by side on the lanes, stored as the per-token
    kinds' rows are. The kinds that hold one (``conv_tail``) write none of
    this themselves. A step has two forms: in slot order (:meth:`take`,
    the new inputs :meth:`behind`, the op's own convolution,
    :meth:`slide`) and in arena order (:meth:`step_arena`, where
    :meth:`path` says ``"kernel"``); the first is the second's reference,
    and the CPU's."""

    tail: int          # positions of the convolution's inputs kept
    channels: int

    def paged(self, rows, dtype):
        return jax.ShapeDtypeStruct((rows, self.tail * self.channels), dtype)

    def dense(self, batch, dtype):
        """The dense form's array: a tail a sequence, uncut."""
        return jax.ShapeDtypeStruct((batch, self.tail, self.channels), dtype)

    def path(self, dtype=jnp.bfloat16) -> str:
        """``"kernel"`` where :meth:`step_arena` takes an arena of ``dtype``
        (a tap of whole lane tiles, the kernels running), else ``"rows"``."""
        return "kernel" if gated_delta.tails_supported(
            (2, self.tail * self.channels), dtype, self.channels) else "rows"

    def take(self, arena, rows, later=None):
        """The tails of ``rows``, (N, tail, channels); with ``later`` (N,)
        bool, zeros where it is false: a prompt's first chunk starts from
        nothing, not from what the request before left in the row."""
        if later is None:
            return arena[rows].reshape(len(rows), self.tail, self.channels)
        # (the mask first, then the take: the order the programs have had)
        return jnp.where(later[:, None, None], arena[rows].reshape(
            len(rows), self.tail, self.channels), 0)

    @staticmethod
    def behind(taken, new):
        """The window to convolve: ``new`` (N, S, channels) behind them."""
        return jnp.concatenate([taken, new.astype(taken.dtype)], axis=1)

    @sub_scope("write")
    def slide(self, arena, rows, window):
        """A step's ``window`` (N, tail + 1, channels) less its oldest
        position, back over the slots' rows (row 0 is nobody's)."""
        return spread_rows(arena, rows, window[:, 1:].reshape(len(rows), -1))

    @staticmethod
    def step_arena(arena, rows, inputs, w):
        """The slots' convolved rows (N, 1, channels) float32 and the arena
        stepped, in arena order: row r takes the inputs of the slot that
        names it (a row nobody names takes zeros and is left as it was),
        ``gated_delta.tails_step`` convolves every row behind its own
        taps (tap ``j`` is the lanes ``[j C, (j + 1) C)``, whole lane
        tiles where ``C = channels`` is a multiple of 128) and shifts the
        live ones where they lie, and the slots take their rows' results:
        two takes of rows ``channels`` wide, neither a gather of the
        slots' rows cut into ``(n, taps, channels)`` (a relayout of the
        whole take each way: 4 of a 26 ms step at 256 slots of 36,864
        numbers, ``PERF.md``, PR 59) nor a scatter (32 rows this wide run
        as a sequential loop of dynamic-update-slices, 2.5 ms of a 19 ms
        step). A live row's new tail is moved, not computed."""
        slot_of, live = named_by(arena.shape[0], rows)
        x_r = jnp.where(live[:, None], inputs[slot_of], 0)
        u_r, arena = gated_delta.tails_step(arena, live, x_r, w)
        return u_r[rows][:, None], arena

    @staticmethod
    def put(arena, rows, tail):
        """The prompts' tails (N, tail, channels) over their rows (padding
        rows over the null row; one to a few rows keep the scatter)."""
        return arena.at[rows].set(
            tail.reshape(len(rows), -1).astype(arena.dtype))

    def left(self, window, lengths):
        """Of a chunk's ``window`` (N, tail + S, channels), the ``tail``
        positions that end at each prompt's TRUE length ``lengths``."""
        at = lengths[:, None] + _iota(self.tail)[None, :]
        return jnp.take_along_axis(window, at[:, :, None], axis=1)


class EntryKind:
    """What one op keeps for one token (or one request), and everything
    that depends on it. ``op`` is the op, ``weights`` its parameters,
    ``x`` (B, S, E) its input, ``positions`` (B, S) the graph's; an
    ``entry`` is the tuple of arenas :meth:`arenas` describes, ``addr``
    the slots' :class:`~flexflow_tpu.serving.kv_cache.Addresses` (block
    tables (B, max_blocks) and rows (B,)). A kind defines:

    * ``for_op(op, positions_id, max_length)`` (a classmethod): the kind
      of ``op`` in a graph whose positions input has that tensor id,
      decoded up to ``max_length``; raises what the op cannot serve;
    * ``arenas(num_blocks, block_size, dtype)``: what one op keeps a
      TOKEN, a ``jax.ShapeDtypeStruct`` each, of ``num_blocks`` blocks,
      and ``request_arenas(rows, block_size, dtype)``: what it keeps a
      REQUEST, of ``rows`` rows; either may be ``()``, and an entry is
      the first's arrays, then the second's;
    * ``reads_in_place(op, entry, slots, window, max_blocks)``: whether a
      ``window``-token step reads ``entry`` by a kernel, in place, and
      ``decode_chunk_tokens(entry, max_blocks)``: the tokens one loop
      iteration of that kernel brings (None: it walks no chunks), and
      ``fetch_run_blocks(entry, max_blocks)``: the neighbouring blocks
      one copy of it brings;
    * ``step(op, weights, x, positions, entry, addr, seq_lens)``: W new
      tokens a slot at positions ``seq_lens .. seq_lens + W - 1``, their
      rows written through the tables (an idle slot's, and positions past
      a table's span, into the null block), then each slot's cache
      attended through its table; a kind of a row a request updates each
      slot's row (an idle slot's is the null row); returns (out, entry);
    * ``prefill(op, weights, x, positions, entry, addr, lengths)``: a
      group of prompts padded to one bucket, of true ``lengths``, from
      nothing: what they leave is written where ``addr`` says (padding
      into the null block, padding rows into the null row); returns
      (out, entry);
    * ``whole(op, weights, x, positions)``: whole sequences, cache-free
      (what the KV calibration gate compares the paged programs with);
      returns (out, ...), the rest the kind's own business;
    * ``dense_shapes(batch, max_length, dtype)``, the dense form's arrays
      (a ``jax.ShapeDtypeStruct`` each), and ``dense_step(op, weights, x,
      positions, cache, offset)``: a block of tokens at ``offset``,
      behind what the cache holds; returns (out, cache).

    A kind of a row a token defines ``write(entry, flat, *rows)`` ((T,
    ...) rows into flat token slots (T,)) and a ``whole`` that returns
    (out, the rows ``write`` takes, the (S,) positions), and inherits
    ``prefill``.

    A kind that is ``chunked`` also defines ``chunk(op, weights, x,
    positions, entry, addr, offsets, lengths)``: a block of S tokens a
    prompt at positions ``offsets .. offsets + lengths - 1`` (``offsets``
    (P,) multiples of the block size), BEHIND what the chunks before it
    left where ``addr`` says; a chunk at offset 0 starts from nothing.
    Only such kinds serve a prompt in chunks
    (``PagedDecoder(prefill_chunk=...)``); ``chunk_path`` says which form
    its attention takes. ``step`` and ``chunk`` may
    return a third value, the ids of what the op chose to read (a
    selection of blocks), which the programs keep for whoever asks."""

    name = ""                          # what stats()["kv"]["entry"] says
    max_window: Optional[int] = None   # new tokens a slot a step; None: any
    int8_form: Optional["EntryKind"] = None
    chunked = False                    # defines ``chunk``
    # its step has ONE form, which reads nothing by a kernel: it says
    # "gather" of itself and has no say in whether a program fell back
    one_form = False

    def stats(self) -> Dict:
        return {"entry": self.name}

    def reads_in_place(self, op, entry, slots, window, max_blocks) -> bool:
        return False                    # no kernel: a step gathers

    def decode_chunk_tokens(self, entry, max_blocks: int) -> Optional[int]:
        return None

    def fetch_run_blocks(self, entry, max_blocks: int) -> int:
        """Table entries ONE copy of the kind's decode kernel brings where
        they are neighbours ascending in the arena; 1: a copy a block."""
        return 1

    def blocks_read(self, length: int) -> Optional[int]:
        """Blocks of a request's table a step behind ``length`` cached
        tokens reads, for a kind that reads a selection of them; None for
        one that reads them all."""
        return None

    def rows_read(self, length: int) -> Optional[int]:
        """Rows of a request a step behind ``length`` cached tokens (an
        int, or an array of them) reads, for a kind that keeps and reads
        at most a window of them; None for one that keeps them all."""
        return None

    def side_rows(self, length: int) -> int:
        """Rows a request of ``length`` cached tokens holds beside its
        row a token (pooled keys)."""
        return 0

    def step_reads(self, lengths) -> Dict[str, Dict[str, int]]:
        """What one step reads of ONE op of this kind, summed over slots
        behind ``lengths`` (an int array) cached tokens, under the word
        the pool's books keep it by; empty for a kind that reads all it
        keeps."""
        return {}

    def chunk_reads(self, offset: int, tokens: int) -> Dict[str, Dict[str, int]]:
        """:meth:`step_reads` for one prompt's chunk of ``tokens`` tokens
        at ``offset``, under the same words."""
        return {}

    def prefill_path(self, bucket: int) -> Optional[str]:
        """How :meth:`prefill` computes a ``bucket`` of tokens, for a kind
        whose prefill has more than one form; None for one that has one."""
        return None

    def chunk_path(self, entry, prompts: int, chunk: int, max_blocks: int,
                   dtype) -> str:
        """How :meth:`chunk` attends ``prompts`` chunks of ``chunk``
        queries of ``dtype`` over ``entry``: ``"kernel"``
        (``kernels/chunk_attention.py``) or ``"scan"`` (a walk in jnp)."""
        return "scan"

    def over(self, devices: int) -> "EntryKind":
        """This kind in a model over ``devices`` devices."""
        return self

    def arenas(self, num_blocks, block_size, dtype) -> Tuple:
        return ()                       # nothing a token

    def request_arenas(self, rows, block_size, dtype) -> Tuple:
        """What the kind keeps a REQUEST: arenas of ``rows`` rows, which
        the pool allocates behind :meth:`arenas`' own in the op's entry
        and addresses by the slots' rows (``block_size`` is the pool's,
        for a kind whose row is made of its blocks). None, for most
        kinds."""
        return ()

    @property
    def keeps_row(self) -> bool:
        """Whether a request holds a row of any of this kind's arenas."""
        return bool(self.request_arenas(1, 1, jnp.float32))

    def token_bytes(self, dtype) -> int:
        """Bytes one token takes in one op's arenas stored as ``dtype``:
        plain arithmetic on :meth:`arenas`, nothing is allocated."""
        return sum(math.prod(a.shape) * a.dtype.itemsize
                   for a in self.arenas(1, 1, dtype))

    def request_bytes(self, dtype) -> int:
        """Bytes one request takes in :meth:`request_arenas`."""
        return sum(math.prod(a.shape) * a.dtype.itemsize
                   for a in self.request_arenas(1, 1, dtype))

    def prefill(self, op, weights, x, positions, entry, addr, lengths):
        """:meth:`whole`, and the rows scattered through each prompt's
        table (padding into the null block); for a ``chunked`` kind, the
        prompts' first chunk."""
        if self.chunked:
            return self.chunk(op, weights, x, positions, entry, addr,
                              jnp.zeros_like(lengths), lengths)
        return self._prefill_whole(op, weights, x, positions, entry, addr,
                                   lengths)

    def _prefill_whole(self, op, weights, x, positions, entry, addr, lengths):
        out, rows, pos = self.whole(op, weights, x, positions)
        flat = _prefill_slots(addr.tables, lengths, pos, entry[0].shape[1])
        return out, self.write(entry, flat.reshape(-1), *(
            r.reshape((-1,) + r.shape[2:]) for r in rows))


@dataclasses.dataclass(frozen=True)
class PairEntry(EntryKind):
    """Keys and values, ``heads * head_dim`` numbers each a token (the
    values ``heads * value_dim`` where ``value_dim`` is not 0: two arenas
    of two widths); ``heads`` are the key-value heads, of which
    ``query_heads`` query heads read one each ``query_heads / heads`` (0:
    a key head a query head). Keys lie in their arena as
    ``paged_attention.split_heads`` lays them (a head of one and a half
    lane tiles in two parts; every other width as it is), which the
    kernels read and the jnp forms undo. ``sink``: the op has a learned
    sink a query head, which every form's softmax takes from the op's
    weights. The int8 form is for equal widths."""

    heads: int
    head_dim: int
    query_heads: int = 0
    value_dim: int = dataclasses.field(default=0, kw_only=True)
    sink: bool = dataclasses.field(default=False, kw_only=True)
    # devices the model's programs run over: the chunk's kernel has no
    # ``shard_map`` composition and is one device's (``kernels.use_pallas``)
    devices: int = dataclasses.field(default=1, kw_only=True, compare=False)
    name = "pair"
    chunked = True

    @classmethod
    def for_op(cls, op, positions_id, max_length):
        if (len({t.tensor_id for t in op.layer.inputs[:3]}) != 1
                or not op.causal):
            raise ValueError(
                f"{op.name}: generation needs causal SELF-attention")
        if op.rotary and op.layer.inputs[3].tensor_id != positions_id:
            raise ValueError(f"{op.name}: rotary positions have to be the "
                             f"graph's positions input")
        grouped = op.num_kv_heads != op.num_heads
        heads = (op.num_kv_heads, op.head_dim, op.num_heads if grouped else 0)
        more = dict(
            value_dim=op.v_head_dim if op.v_head_dim != op.head_dim else 0,
            sink=op.sinks)
        if op.window:
            return WindowEntry(*heads, op.window, **more)
        return cls(*heads, **more)

    @property
    def v_dim(self) -> int:
        return self.value_dim or self.head_dim

    @property
    def int8_form(self):
        if self.value_dim:
            return None            # quantized heads are of one width
        return Int8PairEntry(self.heads, self.head_dim, self.query_heads,
                             sink=self.sink)

    def over(self, devices):
        return dataclasses.replace(self, devices=int(devices))

    def _chunk_keys(self, entry, chunk: int, max_blocks: int) -> int:
        """Key rows a chunk's queries are attended over."""
        return max_blocks * entry[0].shape[1]

    def chunk_path(self, entry, prompts, chunk, max_blocks, dtype):
        """``"kernel"`` by what the program can see, no knob: a model
        over one device and what ``chunk_attention.supported`` takes (a
        TPU backend, heads of whole lane tiles, bfloat16 or float32 rows
        of the queries' dtype)."""
        rows = entry[0]
        return "kernel" if self.devices == 1 and chunk_attention.supported(
            (prompts, chunk, self.query_heads or self.heads, self.head_dim),
            dtype, (prompts, self._chunk_keys(entry, chunk, max_blocks),
                    rows.shape[-1]), rows.dtype,
            entry[1].shape[-1]) else "scan"

    def stats(self):
        out = {"entry": self.name}
        if self.query_heads:
            out.update(kv_heads=self.heads, query_heads=self.query_heads)
        if self.value_dim or self.sink:
            out.update(kv_heads=self.heads, key_dim=self.head_dim,
                       value_dim=self.v_dim, sink=self.sink)
        return out

    def arenas(self, num_blocks, block_size, dtype):
        return tuple(jax.ShapeDtypeStruct(
            (num_blocks, block_size, self.heads * d), dtype)
            for d in (self.head_dim, self.v_dim))

    @sub_scope("write")
    def write(self, entry, flat, kh, vh):
        """T new (T, H, D) keys and (T, H, Dv) values at flat token slots
        (T,)."""
        t = kh.shape[0]
        k, v = entry
        return (_put(k, flat, self._key_rows(kh.reshape(t, -1))),
                _put(v, flat, vh.reshape(t, -1)))

    def _key_rows(self, rows):
        """(..., H D) keys, heads side by side, as their arena lays them."""
        return paged_attention.split_heads(rows, self.heads)

    def read(self, entry, tables):
        """Each slot's logical (max_blocks * block_size, H, D) keys and
        values, gathered through its table: what the kernel is checked
        against, and what runs where it does not."""
        return (self._view(entry[0], tables, keys=True),) + tuple(
            self._view(a, tables) for a in entry[1:])

    def _view(self, arena, tables, keys=False):
        rows = arena[tables]
        if keys:
            rows = paged_attention.join_heads(rows, self.heads)
        return rows.reshape(tables.shape[0], -1, self.heads,
                            arena.shape[-1] // self.heads)

    def reads_in_place(self, op, entry, slots, window, max_blocks):
        return paged_attention.supported(
            (slots, window, self.query_heads or self.heads, self.head_dim),
            entry[0].shape, entry[0].dtype, max_blocks, entry[1].shape[-1])

    def decode_chunk_tokens(self, entry, max_blocks):
        return paged_attention.chunk_tokens(entry[0].shape, entry[0].dtype,
                                            max_blocks, entry[1].shape[-1])

    def prefill(self, op, weights, x, positions, entry, addr, lengths):
        """A bucket's prompts whole (:meth:`whole`), their rows scattered
        through the tables: a bucket is attended in one piece, as before
        this kind had a :meth:`chunk`."""
        return self._prefill_whole(op, weights, x, positions, entry, addr,
                                   lengths)

    def chunk(self, op, weights, x, positions, entry, addr, offsets, lengths):
        """The chunk's rows written through the tables, then each prompt's
        blocks attended through its table: by the kernel over the table's
        rows gathered once (:meth:`chunk_path`; the blocks past what the
        longest prompt of the group has got to hold nothing and are not
        visited), else a span at a time as far as that."""
        qh, kh, vh = op.project_qkv(weights, x, x, x, positions)
        ctxv, entry = self._chunk_rows(op, qh, kh, vh, entry, addr, offsets,
                                       lengths, op.sink(weights))
        return op.project_out(weights, ctxv, x), entry

    def _chunk_rows(self, op, qh, kh, vh, entry, addr, offsets, lengths,
                    sink=None):
        """:meth:`chunk` behind the projections: the chunk's (P, S, Hkv,
        D) keys and values written, its (P, S, H, D) queries attended;
        returns (the attended values, the entry)."""
        bs = entry[0].shape[1]
        n, s = qh.shape[:2]
        heads, hdim = kh.shape[2:]
        tables = addr.tables
        mb = tables.shape[1]
        pos = offsets[:, None] + _iota(s)[None, :]
        live = _iota(s)[None, :] < lengths[:, None]
        blk = jnp.take_along_axis(tables, jnp.clip(pos // bs, 0, mb - 1),
                                  axis=1)
        flat = jnp.where(live & (pos < mb * bs), blk * bs + pos % bs,
                         NULL_BLOCK * bs)
        entry = self.write(entry, flat.reshape(-1),
                           kh.reshape(n * s, heads, hdim),
                           vh.reshape(n * s, heads, -1))
        if self.chunk_path(entry, n, s, mb, qh.dtype) == "kernel":
            with sub_scope("attend"):
                at = _iota(mb * bs)[None, :]
                ctxv = _attend_kernel(
                    op, qh, pos, *(a[tables].reshape(n, mb * bs, -1)
                                   for a in entry),
                    jnp.where(at < (offsets + lengths)[:, None], at, NOWHERE),
                    sink)
            return ctxv, entry
        per = max(1, SPAN_TOKENS // bs)            # blocks a span
        span = per * bs
        padded = jnp.pad(tables, ((0, 0), (0, -mb % per)),
                         constant_values=NULL_BLOCK)

        def read(j):
            blocks = jax.lax.dynamic_slice_in_dim(padded, j * per, per, 1)
            return self.read(entry, blocks) + (
                jnp.broadcast_to(j * span + _iota(span), (n, span)),)

        with sub_scope("attend"):
            ctxv = _attend_spans(
                op, qh, pos, heads, read, 0,
                jnp.maximum((jnp.max(offsets + lengths) + span - 1) // span,
                            1), sink, self.v_dim)
        return ctxv, entry

    def step(self, op, weights, x, positions, entry, addr, seq_lens):
        qh, kh, vh = op.project_qkv(weights, x, x, x, positions)
        ctxv, entry = self._step_rows(op, qh, kh, vh, entry, addr, seq_lens,
                                      op.sink(weights))
        return op.project_out(weights, ctxv, x), entry

    def _step_rows(self, op, qh, kh, vh, entry, addr, seq_lens, sink=None):
        """:meth:`step` behind the projections: the W new (N, W, Hkv, D)
        keys and values written through the tables, the (N, W, H, D)
        queries attended; returns (the attended values, the entry)."""
        bs = entry[0].shape[1]
        n, w = qh.shape[:2]
        heads, hdim = kh.shape[2:]
        tables = addr.tables
        mb = tables.shape[1]
        pos = seq_lens[:, None] + _iota(w)[None, :]                # (n, W)
        blk = jnp.take_along_axis(tables, jnp.clip(pos // bs, 0, mb - 1),
                                  axis=1)
        # past the table's span (a verify window overrunning a request's
        # worst case) is the null block, never a clamped real block
        flat = jnp.where(pos < mb * bs, blk * bs + pos % bs,
                         NULL_BLOCK * bs)
        entry = self.write(entry, flat.reshape(-1),
                           kh.reshape(n * w, heads, hdim),
                           vh.reshape(n * w, heads, -1))
        with sub_scope("attend"):
            if self.reads_in_place(op, entry, n, w, mb):
                # the kernel walks each slot's live blocks in the arena
                # itself
                ctxv = paged_attention.paged_attention_decode(
                    qh, entry[0], entry[1], tables, seq_lens,
                    scale=op.scale, sink=sink).astype(qh.dtype)
            else:
                k, v = self.read(entry, tables)             # (n, L, H, D)
                ctxv = _attend(
                    qh, k, v, lambda: (_iota(k.shape[1])[None, None, :]
                                       <= pos[:, :, None])[:, None, :, :],
                    op.scale, sink)
        return ctxv, entry

    def whole(self, op, weights, x, positions):
        qh, kh, vh = op.project_qkv(weights, x, x, x, positions)
        pos = None

        def causal():
            nonlocal pos
            pos = _iota(x.shape[1])
            return op.sees(kpos=pos[None, :],
                           qpos=pos[:, None])[None, None, :, :]

        with sub_scope("attend"):
            ctxv = _attend(qh, kh, vh, causal, op.scale, op.sink(weights))
        return op.project_out(weights, ctxv, x), (kh, vh), pos

    def dense_shapes(self, batch, max_length, dtype):
        return tuple(jax.ShapeDtypeStruct(
            (batch, max_length, self.heads, d), dtype)
            for d in (self.head_dim, self.v_dim))

    def dense_step(self, op, weights, x, positions, cache, offset):
        qh, kh, vh = op.project_qkv(weights, x, x, x, positions)
        ctxv, cache = self._dense_rows(op, qh, kh, vh, cache, offset,
                                       op.sink(weights))
        return op.project_out(weights, ctxv, x), cache

    def _dense_rows(self, op, qh, kh, vh, cache, offset, sink=None):
        """:meth:`dense_step` behind the projections: the block's keys
        and values written at ``offset``, its queries attended; returns
        (the attended values, the cache)."""
        kcache, vcache = cache
        with sub_scope("write"):
            # dynamic_update_slice keeps the shape static; unwritten and
            # future positions are masked by position comparison
            kcache = jax.lax.dynamic_update_slice(kcache, kh,
                                                  (0, offset, 0, 0))
            vcache = jax.lax.dynamic_update_slice(vcache, vh,
                                                  (0, offset, 0, 0))
        with sub_scope("attend"):
            ctxv = _attend(
                qh, kcache, vcache,
                lambda: op.sees(kpos=_iota(kcache.shape[1])[None, :],
                                qpos=(offset + _iota(qh.shape[1]))[:, None]
                                )[None, None, :, :], op.scale, sink)
        return ctxv, (kcache, vcache)


@dataclasses.dataclass(frozen=True)
class Int8PairEntry(PairEntry):
    """The pair quantized: ``(k_q, v_q, k_scale, k_zero, v_scale,
    v_zero)``. ``head_dim + 8`` bytes a head a token against float32's
    ``4 * head_dim``, so a byte budget admits twice the requests or more;
    only the gathered working set pays the float32 width (the kernel's
    ``supported()`` takes no int8 arena, so a step always gathers)."""

    name = "int8"
    int8_form = None
    chunked = False        # a chunk's attend reads the arenas as they are

    def arenas(self, num_blocks, block_size, dtype):
        a = jax.ShapeDtypeStruct(
            (num_blocks, block_size, self.heads * self.head_dim), jnp.int8)
        s = jax.ShapeDtypeStruct((num_blocks, block_size, self.heads),
                                 jnp.float32)
        return (a, a, s, s, s, s)

    @sub_scope("write")
    def write(self, entry, flat, kh, vh):
        t = kh.shape[0]
        kq, vq, ks, kz, vs, vz = entry
        qk, sk, zk = _quant_rows(kh)
        qv, sv, zv = _quant_rows(vh)
        return (_put(kq, flat, qk.reshape(t, -1)),
                _put(vq, flat, qv.reshape(t, -1)),
                _put(ks, flat, sk), _put(kz, flat, zk),
                _put(vs, flat, sv), _put(vz, flat, zv))

    def read(self, entry, tables):
        kq, vq, ks, kz, vs, vz = entry
        view = lambda a: self._view(a, tables)  # noqa: E731
        return (view(kq).astype(jnp.float32) * view(ks) + view(kz),
                view(vq).astype(jnp.float32) * view(vs) + view(vz))


@dataclasses.dataclass(frozen=True)
class WindowEntry(PairEntry):
    """The pair of an op with a ``window``: a request keeps the last
    ``window`` tokens' keys and values and no more, in a ring of its own.
    It keeps nothing a token: its arenas are a REQUEST's, rows of ``window
    / block_size`` blocks in the pair layout: position p lies in block ``(p
    // block_size) % ring`` of the request's row at offset ``p %
    block_size``, i.e. at row ``p % window`` of its ring, where position
    ``p - window`` lay before it. Keys are stored rotated, so where a row
    lies is free; a query at position p sees exactly ``p - window + 1 ..
    p``, which after its own row is written is all the ring holds. The
    ring's block table is made inside the programs from ``addr.rows``, so
    the step's kernel and the gather read it as they read a pair's, with
    lengths clamped to the ring. A whole ring is reserved whatever the
    request's length (``rows_read`` and the pool's bytes say what that
    costs); a step takes one token a slot, and there is no int8 form."""

    window: int = 0
    name = "window"
    max_window = 1
    int8_form = None

    def stats(self):
        return dict(super().stats(), entry=self.name, window=self.window)

    def rows_read(self, length):
        return np.minimum(np.asarray(length) + 1, self.window)

    def step_reads(self, lengths):
        return {"window": {"rows_read": int(self.rows_read(lengths).sum()),
                           "rows_full": int((lengths + 1).sum()),
                           "rows_reserved": len(lengths) * self.window}}

    def ring_blocks(self, block_size: int) -> int:
        if self.window % block_size:
            raise ValueError(
                f"a window of {self.window} tokens is no whole blocks of "
                f"{block_size}: the ring is made of the pool's blocks")
        return self.window // block_size

    arenas = EntryKind.arenas            # nothing a token: a ring a request

    def request_arenas(self, rows, block_size, dtype):
        return super().arenas(rows * self.ring_blocks(block_size),
                              block_size, dtype)

    def _chunk_keys(self, entry, chunk, max_blocks):
        return self.window + chunk

    def _tables(self, entry, rows):
        """The block table of each request's ring, (N, ring)."""
        ring = self.ring_blocks(entry[0].shape[1])
        return rows[:, None] * ring + _iota(ring)[None, :]

    def reads_in_place(self, op, entry, slots, window, max_blocks):
        return window == 1 and paged_attention.supported(
            (slots, 1, self.query_heads or self.heads, self.head_dim),
            entry[0].shape, entry[0].dtype,
            self.ring_blocks(entry[0].shape[1]), entry[1].shape[-1])

    def decode_chunk_tokens(self, entry, max_blocks):
        return super().decode_chunk_tokens(
            entry, self.ring_blocks(entry[0].shape[1]))

    def step(self, op, weights, x, positions, entry, addr, seq_lens):
        qh, kh, vh = op.project_qkv(weights, x, x, x, positions)
        n = x.shape[0]                   # one token a slot: ``max_window``
        w = self.window
        # an idle slot's row is the null row: its ring takes the write
        entry = self.write(entry, addr.rows * w + seq_lens % w,
                           kh[:, 0], vh[:, 0])
        tables = self._tables(entry, addr.rows)
        held = jnp.minimum(seq_lens, w - 1)     # rows before the new one
        sink = op.sink(weights)
        with sub_scope("attend"), sub_scope("window"):
            if self.reads_in_place(op, entry, n, 1, 0):
                ctxv = paged_attention.paged_attention_decode(
                    qh, entry[0], entry[1], tables, held,
                    scale=op.scale, sink=sink).astype(qh.dtype)
            else:
                k, v = self.read(entry, tables)             # (n, W, H, D)
                ctxv = _attend(
                    qh, k, v, lambda: (_iota(w)[None, None, :]
                                       <= held[:, None, None])[:, None, :, :],
                    op.scale, sink)
        return op.project_out(weights, ctxv, x), entry

    prefill = EntryKind.prefill          # a first chunk, not a whole bucket

    def chunk(self, op, weights, x, positions, entry, addr, offsets, lengths):
        """``[what the ring holds | the chunk]`` attended by absolute
        position, the chunk written afterwards (it overwrites rows its own
        early queries still read): of a chunk longer than the ring, the
        last ``window`` live rows."""
        qh, kh, vh = op.project_qkv(weights, x, x, x, positions)
        n, s = x.shape[:2]
        heads, hdim = kh.shape[2:]
        w = self.window
        pos = offsets[:, None] + _iota(s)[None, :]
        live = _iota(s)[None, :] < lengths[:, None]
        # ring row r holds the last position before the chunk that is r
        # modulo the window, if the request has got that far
        before = offsets[:, None] - 1
        held = before - jnp.mod(before - _iota(w)[None, :], w)
        # (a request's ring is taken in the arena's own layout, rows of
        # all heads side by side: splitting the heads first would copy
        # the whole arena into another tiling)
        keys, values = (
            jnp.concatenate([a.reshape(-1, w, a.shape[-1])[addr.rows],
                             new.astype(a.dtype)], axis=1)
            for a, new in zip(entry, (self._key_rows(kh.reshape(n, s, -1)),
                                      vh.reshape(n, s, -1))))
        kpos = jnp.concatenate([jnp.where(held >= 0, held, NOWHERE),
                                jnp.where(live, pos, NOWHERE)], axis=1)
        sink = op.sink(weights)
        with sub_scope("attend"), sub_scope("window"):
            # a group of first chunks' rings hold nothing, a short last
            # chunk's rows past its length neither: the kernel visits
            # none of their blocks, the walk skips their spans
            if self.chunk_path(entry, n, s, 0, qh.dtype) == "kernel":
                ctxv = _attend_kernel(op, qh, pos, keys, values, kpos, sink)
            else:
                span = SPAN_TOKENS
                pad = -(w + s) % span
                keys, values = (
                    jnp.pad(a, ((0, 0), (0, pad), (0, 0))).reshape(
                        n, -1, heads, a.shape[-1] // heads)
                    for a in (paged_attention.join_heads(keys, heads),
                              values))
                kpos = jnp.pad(kpos, ((0, 0), (0, pad)),
                               constant_values=NOWHERE)

                def read(j):
                    return tuple(
                        jax.lax.dynamic_slice_in_dim(a, j * span, span, 1)
                        for a in (keys, values, kpos))

                ctxv = _attend_spans(
                    op, qh, pos, heads, read,
                    jnp.where(jnp.all(offsets == 0), w // span, 0),
                    (w + jnp.max(lengths) + span - 1) // span, sink,
                    self.v_dim)
        keep = live & (pos >= (offsets + lengths)[:, None] - w)
        flat = jnp.where(keep, addr.rows[:, None], NULL_ROW) * w + pos % w
        entry = self.write(entry, flat.reshape(-1),
                           kh.reshape(n * s, heads, hdim),
                           vh.reshape(n * s, heads, -1))
        return op.project_out(weights, ctxv, x), entry


@dataclasses.dataclass(frozen=True)
class CcaEntry(PairEntry):
    """The entry of a compressed convolutional attention op
    (``ops/attention.py`` ``CompressedConvAttention``): the pair a TOKEN,
    keys (rotated) and values in the pair's arenas and layout, read by
    the pair's kernels, AND a row a REQUEST (:meth:`request_arenas`): the
    last ``tail`` rows of ``z``, what the op's two convolutions read
    before a token, flat, and the last token's ``u W_v2``, the half of
    the next token's values that comes from it; both stored as the pair
    is. An entry is ``(keys, values, tails, prevs)``. The tails are a
    :class:`ConvTail`'s, taken, slid and put by it; the half values go
    the same ways beside them (a step takes one token a slot and puts
    the slots' rows back through ``spread_rows``; a prompt's first chunk
    starts from zeros, whatever the row held, a later one from what the
    chunk before wrote; what is written is what the chunk's TRUE length
    leaves). No int8 form."""

    tail: int = 0
    channels: int = 0
    name = "cca"
    max_window = 1
    int8_form = None

    @classmethod
    def for_op(cls, op, positions_id, max_length):
        if op.layer.inputs[1].tensor_id != positions_id:
            raise ValueError(f"{op.name}: its rotary positions have to be "
                             f"the graph's positions input")
        return cls(op.num_kv_heads, op.head_dim, op.num_heads,
                   tail=op.tail, channels=op.channels)

    @property
    def conv_tail(self) -> ConvTail:
        return ConvTail(self.tail, self.channels)

    def request_arenas(self, rows, block_size, dtype):
        return (self.conv_tail.paged(rows, dtype),
                jax.ShapeDtypeStruct((rows, self.heads * self.head_dim // 2),
                                     dtype))

    def reads_in_place(self, op, entry, slots, window, max_blocks):
        return window == 1 and super().reads_in_place(
            op, entry[:2], slots, window, max_blocks)

    def _mixed(self, op, weights, x, positions, tail, prev):
        """The op's queries, keys and values for ``x`` (N, S, E) behind a
        request's ``tail`` (N, tail, channels) and ``prev`` (N, .), and
        what the tokens leave of both: the window's rows of ``z`` (N, tail
        + S, channels) and each token's own ``u W_v2`` (N, S, .)."""
        z = op.project(weights, x)
        window = jnp.concatenate([tail.astype(z.dtype), z], axis=1)
        qh, kh = op.mix(weights, window, positions)
        vh, own = op.values(weights, x, prev)
        return qh, kh, vh, window, own

    def step(self, op, weights, x, positions, entry, addr, seq_lens):
        tails, prevs = entry[2:]         # one token a slot: ``max_window``
        with sub_scope("mix"):
            tail = self.conv_tail.take(tails, addr.rows)
            prev = prevs[addr.rows]
        qh, kh, vh, window, own = self._mixed(op, weights, x, positions,
                                              tail, prev)
        tails = self.conv_tail.slide(tails, addr.rows, window)
        with sub_scope("write"):
            prevs = spread_rows(prevs, addr.rows, own[:, 0])
        ctxv, pair = self._step_rows(op, qh, kh, vh, entry[:2], addr,
                                     seq_lens)
        return op.out(weights, ctxv), pair + (tails, prevs)

    prefill = EntryKind.prefill          # a first chunk, not a whole bucket

    def chunk(self, op, weights, x, positions, entry, addr, offsets, lengths):
        tails, prevs = entry[2:]
        later = offsets > 0
        with sub_scope("mix"):
            tail = self.conv_tail.take(tails, addr.rows, later)
            prev = jnp.where(later[:, None], prevs[addr.rows], 0)
        qh, kh, vh, window, own = self._mixed(op, weights, x, positions,
                                              tail, prev)
        with sub_scope("write"):
            # the last ``tail`` rows of z behind the chunk's TRUE length,
            # and its last live token's half value
            left = self.conv_tail.left(window, lengths)
            last = jnp.take_along_axis(
                own, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1)
            rows = (self.conv_tail.put(tails, addr.rows, left),
                    prevs.at[addr.rows].set(last[:, 0].astype(prevs.dtype)))
        ctxv, pair = self._chunk_rows(op, qh, kh, vh, entry[:2], addr,
                                      offsets, lengths)
        return op.out(weights, ctxv), pair + rows

    def whole(self, op, weights, x, positions):
        qh, kh, vh = op.whole(weights, x, positions)
        pos = _iota(x.shape[1])
        with sub_scope("attend"):
            ctxv = _attend(qh, kh, vh, lambda: (
                pos[None, :] <= pos[:, None])[None, None, :, :], op.scale)
        return op.out(weights, ctxv), (kh, vh), pos

    def dense_shapes(self, batch, max_length, dtype):
        return super().dense_shapes(batch, max_length, dtype) + (
            self.conv_tail.dense(batch, dtype),
            jax.ShapeDtypeStruct((batch, self.heads * self.head_dim // 2),
                                 dtype))

    def dense_step(self, op, weights, x, positions, cache, offset):
        tail, prev = cache[2:]
        qh, kh, vh, window, own = self._mixed(op, weights, x, positions,
                                              tail, prev)
        ctxv, pair = self._dense_rows(op, qh, kh, vh, cache[:2], offset)
        return op.out(weights, ctxv), pair + (
            window[:, window.shape[1] - self.tail:].astype(tail.dtype),
            own[:, -1].astype(prev.dtype))


@dataclasses.dataclass(frozen=True)
class LatentEntry(EntryKind):
    """A latent-attention op's one row a token: its normalized latent and
    its rotary key, which keys and values are both read from."""

    row_width: int
    name = "latent"
    max_window = 1

    @classmethod
    def for_op(cls, op, positions_id, max_length):
        # its second input is the graph's positions (rotary, inside the
        # op), not a learned table's
        if op.layer.inputs[1].tensor_id != positions_id:
            raise ValueError(f"{op.name}: latent attention has to take the "
                             f"graph's positions input")
        if max_length > op.max_positions:
            raise ValueError(
                f"max_length {max_length} exceeds the positions {op.name} "
                f"was built for ({op.max_positions})")
        return cls(op.row_width)

    def arenas(self, num_blocks, block_size, dtype):
        return (jax.ShapeDtypeStruct(
            (num_blocks, block_size, latent_row_lanes(self.row_width)),
            dtype),)

    def stats(self):
        return {"entry": self.name, "row_width": self.row_width,
                "row_lanes": latent_row_lanes(self.row_width)}

    @sub_scope("write")
    def write(self, entry, flat, rows):
        """(T, width) rows, padded with zeros to the arena's lanes."""
        lanes = entry[0].shape[-1]
        return (_put(entry[0], flat,
                     jnp.pad(rows, ((0, 0), (0, lanes - rows.shape[-1])))),)

    def reads_in_place(self, op, entry, slots, window, max_blocks):
        arena = entry[0]
        return window == 1 and latent_attention.supported(
            (slots, op.num_heads, arena.shape[-1]), arena.shape,
            arena.dtype, max_blocks, op.kv_rank)

    def decode_chunk_tokens(self, entry, max_blocks):
        block_size = entry[0].shape[1]
        return block_size * latent_attention._pages_per_chunk(block_size,
                                                              max_blocks)

    def fetch_run_blocks(self, entry, max_blocks):
        return latent_attention.run_blocks(entry[0].shape, entry[0].dtype,
                                           max_blocks)

    def step(self, op, weights, x, positions, entry, addr, seq_lens):
        """The absorbed form: per head the query over a row's lanes is
        ``q_nope`` folded through the key half of ``W_kvb`` beside
        ``q_rope``, and the weighted sum of the rows' latent part is
        unfolded through the value half."""
        n, w, _ = x.shape                # w is 1: ``max_window``
        arena = entry[0]
        bs, lanes = arena.shape[1], arena.shape[2]
        tables = addr.tables
        mb = tables.shape[1]
        q_nope, q_rope, rows = op.queries_and_rows(weights, x, positions)
        entry = self.write(entry, self._step_slots(tables, seq_lens, bs),
                           rows[:, 0])
        arena = entry[0]
        q_full, wkvb = self._absorbed(op, weights, q_nope, q_rope, lanes,
                                      arena.dtype)
        with sub_scope("attend"):
            if self.reads_in_place(op, entry, n, w, mb):
                ctxv = latent_attention.latent_attention_decode(
                    q_full, arena, tables, seq_lens, scale=op.scale,
                    out_width=op.kv_rank)
            else:
                view = arena[tables].reshape(n, mb * bs, lanes)  # (n, L, ·)
                ctxv = self._attend_rows(
                    op, q_full, view,
                    lambda: _iota(mb * bs)[None, :] <= seq_lens[:, None])
        return self._unabsorbed(op, weights, x, wkvb, ctxv), entry

    @staticmethod
    def _step_slots(tables, seq_lens, bs):
        """The flat token slot a step's one new row a slot goes to (past
        the table's span: the null block)."""
        mb = tables.shape[1]
        blk = jnp.take_along_axis(
            tables, jnp.clip(seq_lens[:, None] // bs, 0, mb - 1), axis=1)[:, 0]
        return jnp.where(seq_lens < mb * bs, blk * bs + seq_lens % bs,
                         NULL_BLOCK * bs)

    @staticmethod
    def _absorbed(op, weights, q_nope, q_rope, lanes: int, dtype):
        """A step's queries over a row's ``lanes`` in the arena's
        ``dtype``, (n, H, lanes), and ``W_kvb`` by heads."""
        n = q_nope.shape[0]
        with sub_scope("project"):
            wkvb = op.kvb_heads(weights)              # (rank, H, nope + v)
            q_lat = jnp.einsum("nhd,chd->nhc", q_nope[:, 0],
                               wkvb[..., :op.nope_dim],
                               preferred_element_type=jnp.float32)
            q_full = jnp.concatenate(
                [q_lat.astype(dtype), q_rope[:, 0].astype(dtype),
                 jnp.zeros((n, op.num_heads, lanes - op.row_width),
                           dtype)], axis=-1)          # (n, H, lanes)
        return q_full, wkvb

    @staticmethod
    def _attend_rows(op, q_full, view, mask):
        """The absorbed queries over each slot's rows ``view`` (n, L,
        lanes) where ``mask()`` (n, L), made behind the scores (the order
        the programs' lowered text has had): the weighted sums of the
        rows' latent part, (n, H, rank) float32."""
        scores = jnp.einsum(
            "nhr,nlr->nhl", q_full, view,
            preferred_element_type=jnp.float32) * op.scale
        probs = jax.nn.softmax(
            jnp.where(mask()[:, None, :], scores, -1e30), axis=-1)
        return jnp.einsum("nhl,nlc->nhc", probs.astype(view.dtype),
                          view[..., :op.kv_rank],
                          preferred_element_type=jnp.float32)

    @staticmethod
    def _unabsorbed(op, weights, x, wkvb, ctxv):
        """The rows' weighted sums through the value half of ``W_kvb``,
        the output gate and ``W_o``: (n, 1, E)."""
        n = x.shape[0]
        with sub_scope("project"):
            o = jnp.einsum("nhc,chd->nhd", ctxv.astype(x.dtype),
                           wkvb[..., op.nope_dim:],
                           preferred_element_type=jnp.float32).astype(x.dtype)
            if op.output_gate:
                o = op.gated(weights, x, o[:, None])[:, 0]
            return jnp.dot(o.reshape(n, 1, op.num_heads * op.v_dim),
                           weights["wo"],
                           preferred_element_type=jnp.float32).astype(x.dtype)

    def whole(self, op, weights, x, positions):
        """The expanded form: keys and values up-projected from the
        sequences' own rows."""
        q_nope, q_rope, rows = op.queries_and_rows(weights, x, positions)
        pos = _iota(x.shape[1])
        out = op.attend_expanded(weights, q_nope, q_rope, rows,
                                 pos[None, :] <= pos[:, None], x)
        return out, (rows,), pos

    def dense_shapes(self, batch, max_length, dtype):
        return (jax.ShapeDtypeStruct((batch, max_length, self.row_width),
                                     dtype),)

    def dense_step(self, op, weights, x, positions, cache, offset):
        q_nope, q_rope, rows = op.queries_and_rows(weights, x, positions)
        with sub_scope("write"):
            rows_cache = jax.lax.dynamic_update_slice(
                cache[0], rows.astype(cache[0].dtype), (0, offset, 0))
        qpos = offset + _iota(x.shape[1])
        kpos = _iota(rows_cache.shape[1])
        out = op.attend_expanded(weights, q_nope, q_rope,
                                 rows_cache.astype(x.dtype),
                                 kpos[None, :] <= qpos[:, None], x)
        return out, (rows_cache,)


@dataclasses.dataclass(frozen=True)
class SparseLatentEntry(LatentEntry):
    """A latent-attention op under a learned indexer
    (:class:`~flexflow_tpu.ops.attention.Indexer`). What it keeps: the
    latent row a token, a POOL of ``pool`` rows side by side on the lanes
    (``(blocks, block_size / pool, pool * lanes)``: what a query reads is
    whole pools, and a pool is then ONE row of the arena's flat view, so
    that the taken pools are gathered where they lie, 4 KB a slice); ONE
    pooled index key a pool under the same block ids (``(blocks,
    block_size / pool, dim)``: pool i of a request lies in its block ``i
    // per_block``, the second granularity :class:`SparseEntry` has for
    its kernels); and a REQUEST's open pool: the float32 sum of the at most
    ``pool - 1`` keys whose pool is not whole yet (all a mean needs of
    them; the open pool is the query's own and is always read, so no
    score is taken against it).

    A step puts its row into its pool, adds its key to the open pool and
    closes it with the ``pool``-th, scores the slot's whole pools through
    its table, takes ``picks`` of them and gathers them and its own for
    the absorbed form. A chunk (at an offset of whole blocks, so of whole
    pools) writes its pools and the keys of those it closes, scores its
    queries against all the request's pools and, a tile of queries at a
    time, gathers each query's taken pools and attends them in the
    absorbed form: its work does not grow with the context. A prompt's
    FIRST chunk, while it ends inside the dense regime, takes every pool
    there is: it walks its few key spans causally, as a pair's chunk does
    (``chunk_reads`` counts the rows either form's products ran over
    beside the rows taken). The step has this one form: nothing here reads
    by a kernel (``one_form``), so the kind has no say in whether a decode
    step "fell back" from one. Both programs keep the pools each query
    took by their scores, (rows, positions, picks) int32, -1 for a pick a
    query has no pool for."""

    index: Optional[Indexer] = None
    name = "sparse_latent"
    chunked = True
    one_form = True

    @classmethod
    def for_op(cls, op, positions_id, max_length):
        if op.rope_dim or op.output_gate:
            raise ValueError(f"{op.name}: an indexer over rows with a "
                             f"rotary part or an output gate is not built")
        return cls(LatentEntry.for_op(op, positions_id, max_length).row_width,
                   op.indexer)

    @property
    def lanes(self) -> int:
        return latent_row_lanes(self.row_width)

    def arenas(self, num_blocks, block_size, dtype):
        ix = self.index
        if block_size % ix.pool:
            raise ValueError(f"a block of {block_size} tokens is not whole "
                             f"pools of {ix.pool}")
        per = block_size // ix.pool
        return (jax.ShapeDtypeStruct((num_blocks, per, ix.pool * self.lanes),
                                     dtype),
                jax.ShapeDtypeStruct((num_blocks, per, ix.dim), dtype))

    def request_arenas(self, rows, block_size, dtype):
        return (jax.ShapeDtypeStruct((rows, self.index.dim), jnp.float32),)

    def token_bytes(self, dtype) -> int:
        item = jnp.dtype(dtype).itemsize
        return self.lanes * item + self.index.dim * item // self.index.pool

    def stats(self):
        ix = self.index
        return dict(super().stats(), index_pool=ix.pool, index_topk=ix.topk)

    def reads_in_place(self, op, entry, slots, window, max_blocks):
        return False

    def decode_chunk_tokens(self, entry, max_blocks):
        return None

    def fetch_run_blocks(self, entry, max_blocks):
        return 1

    # ---- the books ----------------------------------------------------------
    def dense_through(self) -> int:
        """Cached tokens up to which every pool before a query is taken."""
        return (self.index.picks + 1) * self.index.pool

    def rows_taken(self, length):
        """Rows the query behind ``length`` cached tokens (an int or an
        array) reads: its picks' and its own pool's up to itself."""
        ix = self.index
        return (np.minimum(length // ix.pool, ix.picks) * ix.pool
                + length % ix.pool + 1)

    def step_reads(self, lengths):
        ix = self.index
        pools = lengths // ix.pool
        return {"index": {
            "pools_scored": int(pools.sum()),
            "pools_taken": int((np.minimum(pools, ix.picks) + 1).sum()),
            "rows_read": int(self.rows_taken(lengths).sum()),
            "rows_live": int((lengths + 1).sum()),
            "dense_steps": int(bool(len(lengths))
                               and bool((pools <= ix.picks).all())),
            "rows_taken": 0, "rows_attended": 0}}

    def chunk_reads(self, offset, tokens):
        pos = np.arange(offset, offset + tokens, dtype=np.int64)
        end = offset + tokens
        # the rows a query's products run over: a dense chunk walks whole
        # key spans, any other gathers its budget whatever it masks of it
        each = (-(-end // SPAN_TOKENS) * SPAN_TOKENS
                if end <= self.dense_through() else self.index.topk)
        return {"index": {"rows_taken": int(self.rows_taken(pos).sum()),
                          "rows_attended": int(tokens * each)}}

    # ---- addressing -----------------------------------------------------------
    def _pool_slots(self, tables, idx, keep, per: int):
        """The flat slot of pools ``idx`` (N, J) of the requests of
        ``tables`` in either arena's flat view; the null block's first
        where not ``keep``."""
        blk = jnp.take_along_axis(
            tables, jnp.clip(idx // per, 0, tables.shape[1] - 1), axis=1)
        return jnp.where(keep & (idx >= 0) & (idx < tables.shape[1] * per),
                         blk * per + idx % per, NULL_BLOCK * per)

    @staticmethod
    def _flat(arena):
        """An arena by pools, ``(blocks * per_block, width)``: a reshape
        that moves nothing."""
        return arena.reshape((-1,) + arena.shape[2:])

    @sub_scope("write")
    def _put_pools(self, arena, slots, pools):
        return self._flat(arena).at[slots.reshape(-1)].set(
            pools.reshape((-1,) + arena.shape[2:]).astype(
                arena.dtype)).reshape(arena.shape)

    def _taken_pools(self, arena, tables, pools, qpos):
        """The pools ``pools`` (N, J; -1: none) of the requests of
        ``tables``, a pool a slice of the arena's flat view, (N, J, pool *
        lanes), and whether the query at ``qpos`` (N,) reads each row of
        them, (N, J, pool)."""
        ix = self.index
        runs = self._flat(arena)[self._pool_slots(
            tables, pools, pools >= 0, arena.shape[1])]
        kpos = pools[..., None] * ix.pool + _iota(ix.pool)
        return runs, (pools >= 0)[..., None] & (kpos <= qpos[:, None, None])

    def _attend_pools(self, op, q, runs, seen):
        """Absorbed queries ``q`` (N, H, lanes) over their taken pools
        ``runs`` (N, J, pool * lanes) where ``seen`` (N, J, pool): the
        weighted sums of the rows' latent part, (N, H, rank) float32. A
        pool's rows are taken as the lane groups they lie in (whole lane
        tiles), each its own product: cutting the pools into rows would
        copy all that was gathered."""
        pool, lanes = self.index.pool, self.lanes
        rows = [runs[..., r * lanes:r * lanes + op.kv_rank]
                for r in range(pool)]                     # (N, J, rank) each
        scores = jnp.stack(
            [jnp.einsum("nhc,njc->nhj", q[..., :op.kv_rank], row,
                        preferred_element_type=jnp.float32)
             for row in rows], axis=-1) * op.scale        # (N, H, J, pool)
        shape = scores.shape
        probs = jax.nn.softmax(
            jnp.where(seen[:, None], scores, -1e30).reshape(shape[:2] + (-1,)),
            axis=-1).reshape(shape).astype(runs.dtype)
        return sum(jnp.einsum("nhj,njc->nhc", probs[..., r], row,
                              preferred_element_type=jnp.float32)
                   for r, row in enumerate(rows))

    def _every_pool(self, pos, count: int):
        """The picks of queries at ``pos`` (..., S) in the dense regime:
        every pool before their own, in order, (..., S, count)."""
        nth = _iota(count)
        return jnp.where(nth < (pos // self.index.pool)[..., None], nth, -1)

    # ---- the programs' forms ----------------------------------------------
    def step(self, op, weights, x, positions, entry, addr, seq_lens):
        ix = self.index
        n = x.shape[0]                   # one token a slot: ``max_window``
        arena, keys, opened = entry
        per, lanes = arena.shape[1], self.lanes
        tables = addr.tables
        mb = tables.shape[1]
        q_nope, q_rope, rows, cq = op.queries_and_rows(weights, x, positions,
                                                       with_cq=True)
        qi, wi, ki = op.index(weights, x, cq, positions)
        own = (seq_lens // ix.pool)[:, None]
        slot = self._pool_slots(tables, own, True, per)
        # the row into its place in its pool (the pool read, one lane group
        # of it replaced, put back)
        at = seq_lens % ix.pool
        with sub_scope("write"):
            row = jnp.pad(rows[:, 0], ((0, 0), (0, lanes - rows.shape[-1])))
            mine = (_iota(ix.pool * lanes)[None, :] // lanes) == at[:, None]
            pool_row = jnp.where(mine, jnp.tile(row, (1, ix.pool)).astype(
                arena.dtype), self._flat(arena)[slot[:, 0]])
        arena = self._put_pools(arena, slot, pool_row)
        # the open pool takes the key; the pool's last key closes it
        with sub_scope("write"):
            total = jnp.where((at == 0)[:, None], 0.0,
                              opened[addr.rows]) + ki[:, 0].astype(jnp.float32)
            opened = spread_rows(opened, addr.rows, total)
        keys = self._put_pools(
            keys, self._pool_slots(tables, own, (at == ix.pool - 1)[:, None],
                                   per), total / ix.pool)
        q_full, wkvb = self._absorbed(op, weights, q_nope, q_rope, lanes,
                                      arena.dtype)
        with sub_scope("select"):
            pooled = keys[tables].reshape(n, mb * per, ix.dim)
            ids = ix.picked(ix.scores(qi, wi, pooled, seq_lens[:, None]))
        with sub_scope("attend"):
            ctxv = self._attend_pools(op, q_full, *self._taken_pools(
                arena, tables, jnp.concatenate([ids[:, 0], own], axis=1),
                seq_lens))
        return (self._unabsorbed(op, weights, x, wkvb, ctxv),
                (arena, keys, opened), ids)

    def chunk(self, op, weights, x, positions, entry, addr, offsets, lengths):
        ix = self.index
        n, s, _ = x.shape
        arena, keys, opened = entry
        per, lanes = arena.shape[1], self.lanes
        bs = per * ix.pool
        tables = addr.tables
        mb = tables.shape[1]
        q_nope, q_rope, rows, cq = op.queries_and_rows(weights, x, positions,
                                                       with_cq=True)
        qi, wi, ki = op.index(weights, x, cq, positions)
        pos = offsets[:, None] + _iota(s)[None, :]
        live = _iota(s)[None, :] < lengths[:, None]
        # the chunk's pools (it starts at a whole pool): those that hold a
        # live token are written whole (a pool's rows past the prompt's
        # end are written again, each by its own step, before any query
        # can read them); the keys of those that end inside it; and what
        # the one its true length leaves open holds so far
        idx = (offsets // ix.pool)[:, None] + _iota(s // ix.pool)[None, :]
        begun = idx * ix.pool < (offsets + lengths)[:, None]
        whole = (idx + 1) * ix.pool <= (offsets + lengths)[:, None]
        arena = self._put_pools(
            arena, self._pool_slots(tables, idx, begun, per),
            jnp.pad(rows, ((0, 0), (0, 0), (0, lanes - rows.shape[-1]))))
        sums = jnp.where(live[..., None], ki.astype(jnp.float32), 0.0).reshape(
            n, s // ix.pool, ix.pool, ix.dim).sum(2)
        keys = self._put_pools(
            keys, self._pool_slots(tables, idx, whole, per), sums / ix.pool)
        with sub_scope("write"):
            last = jnp.take_along_axis(
                sums, jnp.clip(lengths // ix.pool, 0,
                               s // ix.pool - 1)[:, None, None], axis=1)[:, 0]
            opened = opened.at[addr.rows].set(
                jnp.where((lengths % ix.pool > 0)[:, None], last, 0.0))
        count = min(ix.picks, mb * per)
        wkvb = op.kvb_heads(weights)
        qb = 128 if s % 128 == 0 else s

        def parts(a):                  # (N, S, ...) -> (S / qb, N, qb, ...)
            return jnp.moveaxis(a.reshape((n, s // qb, qb) + a.shape[2:]),
                                1, 0)

        def joined(a):                 # and back
            return jnp.moveaxis(a, 0, 1).reshape((n, s) + a.shape[3:])

        def sparse():
            """A tile of queries at a time (their scores are (queries,
            heads, pools) float32, their taken rows (queries, topk, lanes)):
            score, pick, gather, attend in the absorbed form."""
            with sub_scope("select"):
                pooled = keys[tables].reshape(n, mb * per, ix.dim)
            with sub_scope("project"):
                q_lat = jnp.einsum(
                    "bshd,chd->bshc", q_nope, wkvb[..., :op.nope_dim],
                    preferred_element_type=jnp.float32).astype(arena.dtype)

            def tile(part):
                qi_t, wi_t, pos_t, q_t = part
                with sub_scope("select"):
                    ids = ix.picked(ix.scores(qi_t, wi_t, pooled, pos_t))
                with sub_scope("attend"):
                    pools = jnp.concatenate(
                        [ids, (pos_t // ix.pool)[..., None]], axis=-1)
                    ctxv = self._attend_pools(
                        op, q_t.reshape((n * qb,) + q_t.shape[2:]),
                        *self._taken_pools(
                            arena, jnp.repeat(tables, qb, axis=0),
                            pools.reshape(n * qb, -1), pos_t.reshape(-1)))
                return (ctxv.astype(x.dtype).reshape(
                    (n, qb) + ctxv.shape[1:]), ids)

            ctxv, ids = jax.lax.map(tile, (parts(qi), parts(wi), parts(pos),
                                           parts(q_lat)))
            with sub_scope("project"):
                o = jnp.einsum("bshc,chd->bshd", joined(ctxv),
                               wkvb[..., op.nope_dim:],
                               preferred_element_type=jnp.float32)
            return o.astype(x.dtype), joined(ids)

        def dense():
            """Every pool before a query is taken: the causal walk over
            the chunk's few key spans, keys and values expanded a span."""
            span_blocks = max(1, SPAN_TOKENS // bs)
            span = span_blocks * bs
            padded = jnp.pad(tables, ((0, 0), (0, -mb % span_blocks)),
                             constant_values=NULL_BLOCK)

            def read(j):
                blocks = jax.lax.dynamic_slice_in_dim(
                    padded, j * span_blocks, span_blocks, axis=1)
                c = arena[blocks].reshape(n, span, lanes)[..., :op.kv_rank]
                kv = jnp.einsum(
                    "bkc,chd->bkhd", c, wkvb,
                    preferred_element_type=jnp.float32).astype(c.dtype)
                kpos = jnp.broadcast_to(j * span + _iota(span)[None],
                                        (n, span))
                return kv[..., :op.nope_dim], kv[..., op.nope_dim:], kpos

            with sub_scope("attend"):
                hi = (jnp.max(offsets + lengths) + span - 1) // span
                o = _attend_spans(op, q_nope, pos, op.num_heads, read, 0,
                                  jnp.maximum(hi, 1), dv=op.v_dim)
            return o, self._every_pool(pos, count)

        o, ids = jax.lax.cond(
            jnp.max(offsets + lengths) <= self.dense_through(), dense, sparse)
        with sub_scope("project"):
            out = jnp.dot(o.reshape(n, s, op.num_heads * op.v_dim),
                          weights["wo"],
                          preferred_element_type=jnp.float32).astype(x.dtype)
        return out, (arena, keys, opened), ids

    def whole(self, op, weights, x, positions):
        return op.selected(weights, x, positions)

    def dense_shapes(self, batch, max_length, dtype):
        ix = self.index
        length = -(-max_length // ix.pool) * ix.pool
        return (jax.ShapeDtypeStruct((batch, length, self.row_width), dtype),
                jax.ShapeDtypeStruct((batch, length, ix.dim), dtype))

    def dense_step(self, op, weights, x, positions, cache, offset):
        out, cache, _ = op.selected(weights, x, positions, cache, offset)
        return out, cache


class StateKind(EntryKind):
    """A kind that keeps a recurrent state a REQUEST and nothing a token:
    float32 whatever ``kv_dtype`` says (rounded each step it would drift
    for a request's whole life; ``stats()`` says so), one token a slot a
    step (a state cannot be rolled back). Nothing reads row 0, the null
    row that idle slots name, as zeros."""

    max_window = 1

    def stats(self):
        return {"entry": self.name, "state_dtype": "float32"}


class TailedStateKind(StateKind):
    """A :class:`StateKind` whose op reads its inputs through a short
    causal convolution: a request's row is the state, in the layout the
    kind's step kernel takes (``row_shape``; the op's own is
    ``state_shape``, ``_to_row`` between them), and a :class:`ConvTail`.
    A kind says its two shapes, ``_to_row`` and its ``step``."""

    @property
    def conv_tail(self) -> ConvTail:
        return ConvTail(self.tail, self.channels)

    def request_arenas(self, rows, block_size, dtype):
        return (jax.ShapeDtypeStruct((rows,) + self.row_shape, jnp.float32),
                self.conv_tail.paged(rows, dtype))

    def dense_shapes(self, batch, max_length, dtype):
        return (jax.ShapeDtypeStruct((batch,) + self.state_shape,
                                     jnp.float32),
                self.conv_tail.dense(batch, dtype))

    def prefill(self, op, weights, x, positions, entry, addr, lengths):
        """The op's chunked whole-sequence form from an empty state: what
        a prompt of its TRUE length leaves, whatever the bucket, written
        over the request's row (padding rows over the null row)."""
        out, state, tail = op.whole(weights, x, lengths)
        return out, self._put(entry, addr.rows, state, tail)

    @sub_scope("write")
    def _put(self, entry, rows, state, tail):
        """The prompts' states, as the op gives them, and tails over
        their rows (padding rows over the null row)."""
        return (entry[0].at[rows].set(self._to_row(state)),
                self.conv_tail.put(entry[1], rows, tail))

    def whole(self, op, weights, x, positions):
        out, state, tail = op.whole(weights, x)
        return out, (state, tail), None

    def dense_step(self, op, weights, x, positions, cache, offset):
        out, state, tail = op.run(weights, x, *cache)
        return out, (state, tail.astype(cache[1].dtype))


@dataclasses.dataclass(frozen=True)
class StateEntry(TailedStateKind):
    """A gated-delta-rule op's one row a REQUEST: the float32 state of
    its heads, ``(d_k, H d_v)`` with the heads side by side on the lanes
    (``kernels/gated_delta.py``: the tiles pad nothing), and the last
    ``taps - 1`` inputs of its convolution (:class:`ConvTail`). A step
    takes, convolves and puts back the tails flat as they lie, in ONE
    pass over the arena's rows in arena order (``ConvTail.step_arena``);
    where that kernel refuses (a width of no whole lane tiles; the CPU)
    the tail's slot-order form runs, which is also its reference
    (``tails_path`` and the counters ``state_tails.path.kernel`` /
    ``.rows`` say which). The state itself the state kernel steps in
    place. Of row 0 the tails' pass writes nothing (an idle slot
    convolves its taps behind zeros); the state kernel and a prefill's
    padding rows do."""

    heads: int
    key_dim: int
    value_dim: int
    tail: int          # positions of the convolution's inputs kept
    channels: int
    channel_decay: bool = False   # a decay a key channel (KimiDeltaAttention)
    name = "state"
    chunked = True

    @classmethod
    def for_op(cls, op, positions_id, max_length):
        return cls(op.num_heads, op.key_dim, op.value_dim, op.conv_taps - 1,
                   op.channels, op.channel_decay)

    @property
    def row_shape(self):
        return (self.key_dim, self.heads * self.value_dim)

    @property
    def state_shape(self):
        return (self.heads, self.key_dim, self.value_dim)

    def _to_row(self, state):
        return jnp.moveaxis(state, 1, 2).reshape(
            state.shape[0], self.key_dim, -1)

    def _rows(self, arena, rows):
        """:meth:`_to_row` backwards: the states of ``rows`` as the op
        takes them, (N, H, d_k, d_v)."""
        return jnp.moveaxis(arena[rows].reshape(
            len(rows), self.key_dim, self.heads, self.value_dim), 2, 1)

    def stats(self):
        return dict(super().stats(), tails_path=self.tails_path())

    def reads_in_place(self, op, entry, slots, window, max_blocks):
        return window == 1 and gated_delta.supported(
            slots, self.heads, self.key_dim, self.value_dim, entry[0].shape,
            entry[0].dtype)

    def tails_path(self, dtype=jnp.bfloat16) -> str:
        """How a step takes its tails out of an arena of ``dtype``:
        ``"kernel"`` (arena order) or ``"rows"`` (slot order)."""
        return self.conv_tail.path(dtype)

    def step(self, op, weights, x, positions, entry, addr, seq_lens):
        n = x.shape[0]                   # one token a slot: ``max_window``
        state, tails = entry
        tail = self.conv_tail
        path = tail.path(tails.dtype)
        # which form this lowering took, counted once a trace (as
        # ``ssm_step.path.*``): a chip run has to be able to say
        metrics_registry().counter(f"state_tails.path.{path}").inc()
        with sub_scope("conv"):
            if path == "kernel":
                u, tails = tail.step_arena(
                    tails, addr.rows, op.conv_inputs(weights, x)[:, 0],
                    weights["conv"])
            else:
                window = tail.behind(tail.take(tails, addr.rows),
                                     op.conv_inputs(weights, x))
                u = op.convolve(weights, window)
            q, k, v = op.heads(u)
        g, beta = op.gates(weights, x)
        if path == "rows":
            tails = tail.slide(tails, addr.rows, window)
        with sub_scope("rule"):
            update = (gated_delta.gated_delta_decode
                      if self.reads_in_place(op, entry, n, 1, 0)
                      else gated_delta.gated_delta_step)
            o, state = update(state, addr.rows, q[:, 0], k[:, 0], v[:, 0],
                              jnp.exp(g[:, 0]), beta[:, 0])
        return op.finish(weights, x, o[:, None]), (state, tails)

    def chunk(self, op, weights, x, positions, entry, addr, offsets, lengths):
        """:meth:`SsmStateEntry.chunk`'s contract: a first chunk starts
        from zeros, a later one from the state and the tail the chunk
        before wrote, through the op's own ``run`` (the bucket prefill's
        form: the whole-sequence kernel behind the incoming state where
        :meth:`chunk_path` says ``"kernel"``, whichever the decay's
        form), and what is written is what the chunk's TRUE length
        leaves."""
        later = offsets > 0
        state = jnp.where(later[:, None, None, None],
                          self._rows(entry[0], addr.rows), 0.0)
        tail = self.conv_tail.take(entry[1], addr.rows, later)
        out, state, tail = op.run(weights, x, state, tail, lengths)
        return out, self._put(entry, addr.rows, state, tail)

    def prefill_path(self, bucket):
        """``"kernel"`` (the fused whole-sequence kernel) or ``"scan"``
        (the jnp form), by the rule the op's lowering asks."""
        return delta_rule_path(bucket, self.heads, self.key_dim,
                               self.value_dim,
                               channel_decay=self.channel_decay)

    def chunk_path(self, entry, prompts, chunk, max_blocks, dtype):
        """A chunk goes through the op's ``run`` as a bucket does, so by
        the same rule at the chunk's length (the recurrence is float32
        whatever ``dtype`` the model computes in)."""
        return self.prefill_path(chunk)


@dataclasses.dataclass(frozen=True)
class SparseEntry(EntryKind):
    """A block-sparse attention op's rows: keys and values of its ``Hkv``
    key-value heads a token, head-major inside a block (``(blocks, Hkv,
    block_size, D)``: a head's block is one contiguous read, which is
    what a step gathers ``topk`` of), and beside them one pooled key (a
    **kernel**) every ``stride`` tokens, ``(blocks * per_block, Hkv D)``,
    addressed through the same block tables: kernel i of a request lies
    in its block ``i // per_block``. The pool's blocks have to be the
    selection's. A step takes one token a slot (the selection is the
    last position's)."""

    kv_heads: int
    head_dim: int
    geom: bsa.Selection
    name = "sparse"
    max_window = 1
    chunked = True

    @classmethod
    def for_op(cls, op, positions_id, max_length):
        return cls(op.kv_heads, op.head_dim, op.geom)

    def arenas(self, num_blocks, block_size, dtype):
        if block_size != self.geom.block:
            raise ValueError(
                f"a sparse cache entry selects blocks of {self.geom.block} "
                f"tokens: the pool's block_size {block_size} has to be that")
        a = jax.ShapeDtypeStruct(
            (num_blocks, self.kv_heads, block_size, self.head_dim), dtype)
        return (a, a, jax.ShapeDtypeStruct(
            (num_blocks * self.geom.per_block, self.kv_heads * self.head_dim),
            dtype))

    def token_bytes(self, dtype) -> int:
        row = self.kv_heads * self.head_dim * jnp.dtype(dtype).itemsize
        return 2 * row + row // self.geom.stride

    def stats(self):
        return {"entry": self.name, "kernels_per_block": self.geom.per_block}

    def blocks_read(self, length):
        return self.geom.blocks_read(length)

    def side_rows(self, length):
        return self.geom.kernels_in(length)

    def step_reads(self, lengths):
        return {"selected": {
            "blocks_read": sum(self.blocks_read(int(n)) for n in lengths),
            "blocks_live": int((lengths // self.geom.block + 1).sum())}}

    # ---- addressing -------------------------------------------------------
    @staticmethod
    def _where(tables, idx, live, per: int):
        """(block, offset) of items ``idx`` (N, W), ``per`` of them a
        block, in the requests of ``tables``: the null block where not
        ``live``, before the request or past its table."""
        span = tables.shape[1]
        blk = jnp.take_along_axis(tables, jnp.clip(idx // per, 0, span - 1),
                                  axis=1)
        ok = live & (idx >= 0) & (idx < span * per)
        return jnp.where(ok, blk, NULL_BLOCK), idx % per

    @sub_scope("write")
    def _write(self, entry, tables, pos, live, k, v):
        """``k``, ``v`` (N, W, Hkv, D) at positions ``pos`` (N, W)."""
        keys, values, kernels = entry
        blk, off = self._where(tables, pos, live, keys.shape[2])
        h = _iota(self.kv_heads)
        at = (blk[..., None], h, off[..., None])
        return (keys.at[at].set(k.astype(keys.dtype)),
                values.at[at].set(v.astype(values.dtype)), kernels)

    def _read(self, arena, tables, pos):
        """The rows at positions ``pos`` (N, W): (N, W, Hkv, D)."""
        blk, off = self._where(tables, pos, True, arena.shape[2])
        return arena[blk[..., None], _iota(self.kv_heads), off[..., None]]

    @sub_scope("write")
    def _write_kernels(self, entry, tables, first, pooled, keep):
        """``pooled`` (N, J, Hkv, D): kernels ``first + j`` of each
        request, written where ``keep`` (N, J)."""
        r = self.geom.per_block
        idx = first[:, None] + _iota(pooled.shape[1])[None, :]
        blk, off = self._where(tables, idx, keep, r)
        arena = entry[2]
        rows = pooled.reshape(pooled.shape[:2] + (-1,)).astype(arena.dtype)
        return entry[:2] + (arena.at[blk * r + off].set(rows),)

    def _kernels(self, entry, tables):
        """Each request's kernels through its table, (N, max_blocks *
        per_block, Hkv, D)."""
        r = self.geom.per_block
        rows = (tables[:, :, None] * r + _iota(r)).reshape(tables.shape[0], -1)
        return entry[2][rows].reshape(rows.shape + (self.kv_heads,
                                                    self.head_dim))

    # ---- the programs' forms ----------------------------------------------
    def step(self, op, weights, x, positions, entry, addr, seq_lens):
        g = self.geom
        tables = addr.tables
        n, mb = tables.shape
        qg, k, v = op.project(weights, x)             # one token a slot
        pos = seq_lens[:, None]
        active = tables[:, 0] != NULL_BLOCK
        entry = self._write(entry, tables, pos, active[:, None], k, v)
        # the kernel whose last key this one is, if it is one's
        back = pos - (g.kernel - 1) + _iota(g.kernel)[None, :]
        pooled = self._read(entry[0], tables, back).astype(
            jnp.float32).mean(1, keepdims=True)
        begun = seq_lens - (g.kernel - 1)
        entry = self._write_kernels(
            entry, tables, begun // g.stride, pooled,
            (active & (begun >= 0) & (begun % g.stride == 0))[:, None])
        keys, values, _ = entry
        with sub_scope("select"):
            score = bsa.block_scores(bsa.kernel_scores(
                qg, self._kernels(entry, tables), pos, g, op.scale),
                pos, g)[:, :, 0]                      # (n, Hkv, mb)
        below = seq_lens < g.dense_len
        narrow = min(g.topk, mb)

        def attend(count):
            ids = jax.lax.top_k(score, count)[1].astype(jnp.int32)
            counted = (_iota(count) < g.topk) | below[:, None, None]
            phys = tables[_iota(n)[:, None, None], ids]
            h = _iota(self.kv_heads)[None, :, None]
            kg, vg = keys[phys, h], values[phys, h]   # (n, Hkv, count, bs, D)
            s = jnp.einsum("nhgd,nhcbd->nhgcb", qg[:, 0], kg,
                           preferred_element_type=jnp.float32) * op.scale
            kpos = ids[..., None] * g.block + _iota(g.block)
            see = counted[..., None] & (kpos <= seq_lens[:, None, None, None])
            s = jnp.where(see[:, :, None], s, bsa.NEG)
            p = jax.nn.softmax(s.reshape(s.shape[:3] + (-1,)), axis=-1)
            o = jnp.einsum("nhgcb,nhcbd->nhgd",
                           p.reshape(s.shape).astype(vg.dtype), vg,
                           preferred_element_type=jnp.float32)
            return o.astype(x.dtype), ids[..., :narrow]

        # a slot below dense_len reads every block it has, up to dense_len
        # / block of them; a step none of whose slots is gathers topk
        wide = g.widest_read(mb)
        with sub_scope("attend"):
            if wide == narrow:
                o, ids = attend(narrow)
            else:
                o, ids = jax.lax.cond(jnp.any(active & below),
                                      lambda: attend(wide),
                                      lambda: attend(narrow))
        out = op.finish(weights, x, o.reshape(n, 1, -1, self.head_dim))
        return out, entry, ids[:, :, None]

    def chunk(self, op, weights, x, positions, entry, addr, offsets, lengths):
        g = self.geom
        tables = addr.tables
        n, s, _ = x.shape
        mb = tables.shape[1]
        qg, k, v = op.project(weights, x)
        pos = offsets[:, None] + _iota(s)[None, :]
        live = _iota(s)[None, :] < lengths[:, None]
        entry = self._write(entry, tables, pos, live, k, v)
        # the kernels that end inside the chunk: the first of them begins
        # kernel - stride keys before it
        lap = g.kernel - g.stride
        before = self._read(entry[0], tables,
                            offsets[:, None] - lap + _iota(lap)[None, :])
        pad = -s % g.stride
        pooled = bsa.pool_keys(jnp.concatenate(
            [before.astype(k.dtype), jnp.pad(k, ((0, 0), (0, pad), (0, 0),
                                                 (0, 0)))], axis=1), g)
        first = (offsets - lap) // g.stride
        idx = first[:, None] + _iota(pooled.shape[1])[None, :]
        done = idx * g.stride + g.kernel <= (offsets + lengths)[:, None]
        entry = self._write_kernels(entry, tables, first, pooled, done)
        kernels = self._kernels(entry, tables)
        # selection, a few queries at a time: the kernels' scores are
        # (heads, queries, kernels)
        count = min(g.topk, mb)
        span_blocks = max(1, 512 // g.block)
        width = -(-mb // span_blocks) * span_blocks
        qb = 256 if s % 256 == 0 else s

        def pick(part):
            q_part, pos_part = part
            ids = bsa.select(q_part, kernels, pos_part, g, op.scale, count)
            seen = bsa.picked_blocks(ids, width) | (
                pos_part < g.dense_len)[:, None, :, None]
            return ids, seen

        def parts(a):                  # (N, S, ...) -> (S / qb, N, qb, ...)
            return jnp.moveaxis(a.reshape((n, s // qb, qb) + a.shape[2:]),
                                1, 0)

        ids, seen = jax.lax.map(pick, (parts(qg), parts(pos)))
        ids, seen = (jnp.moveaxis(a, 0, 2).reshape(
            (n, self.kv_heads, s, a.shape[-1])) for a in (ids, seen))
        padded = jnp.pad(tables, ((0, 0), (0, width - mb)),
                         constant_values=NULL_BLOCK)
        keys, values, _ = entry

        def read(j):
            blocks = jax.lax.dynamic_slice_in_dim(padded, j * span_blocks,
                                                  span_blocks, axis=1)
            return keys[blocks], values[blocks]

        span = span_blocks * g.block
        spans = jnp.maximum((jnp.max(offsets + lengths) + span - 1) // span,
                            1)
        o = bsa.attend_blocked(qg, read, seen, pos, spans, g, span_blocks,
                               op.scale)
        return op.finish(weights, x, o), entry, ids

    def whole(self, op, weights, x, positions):
        return op.whole(weights, x)

    def dense_shapes(self, batch, max_length, dtype):
        blocks = -(-max_length // self.geom.block)
        a = jax.ShapeDtypeStruct(
            (batch, blocks * self.geom.block, self.kv_heads, self.head_dim),
            dtype)
        return (a, a)

    def dense_step(self, op, weights, x, positions, cache, offset):
        return op.whole(weights, x, offset, cache)[:2]


@dataclasses.dataclass(frozen=True)
class DecayStateEntry(StateKind):
    """A lightning-attention op's one row a REQUEST: the float32 state of
    its heads, ``(H, D, D)``, and no convolution."""

    heads: int
    head_dim: int
    name = "decay_state"
    chunked = True

    @classmethod
    def for_op(cls, op, positions_id, max_length):
        if op.layer.inputs[1].tensor_id != positions_id:
            raise ValueError(f"{op.name}: lightning attention has to take "
                             f"the graph's positions input")
        return cls(op.num_heads, op.head_dim)

    def request_arenas(self, rows, block_size, dtype):
        return (jax.ShapeDtypeStruct(
            (rows, self.heads, self.head_dim, self.head_dim), jnp.float32),)

    def step(self, op, weights, x, positions, entry, addr, seq_lens):
        out, arena = op.step(weights, x, positions, entry[0], addr.rows)
        return out, (arena,)

    def chunk(self, op, weights, x, positions, entry, addr, offsets, lengths):
        """A row holds what the request before left: a first chunk starts
        from zeros, not from it."""
        state = jnp.where((offsets > 0)[:, None, None, None],
                          entry[0][addr.rows], 0.0)
        out, state = op.run(weights, x, positions, state, lengths)
        with sub_scope("write"):
            return out, (entry[0].at[addr.rows].set(state),)

    def whole(self, op, weights, x, positions):
        return op.run(weights, x, positions, op.empty_state(x.shape[0]))

    def dense_shapes(self, batch, max_length, dtype):
        return self.request_arenas(batch, 0, dtype)

    def dense_step(self, op, weights, x, positions, cache, offset):
        out, state = op.run(weights, x, positions, cache[0])
        return out, (state,)


@dataclasses.dataclass(frozen=True)
class SsmStateEntry(TailedStateKind):
    """A state-space op's one row a REQUEST (``ops/mamba2.py``): the
    float32 state of its heads, ``(N, H P)`` with the state's axis on the
    sublanes and the heads' channels side by side on the lanes
    (``kernels/ssd_step.py``: the step's sum over ``N`` runs down
    sublanes), and the last ``taps - 1`` inputs of its convolution
    (:class:`ConvTail`, stepped in slot order). A step updates the states
    where they lie, so the decode program gathers and scatters no state
    and holds no loop: on the chip by the ``ssd_step_decode`` kernel, one
    call a layer that moves the live slots' rows and no other
    (:meth:`reads_in_place`; the counter ``ssm_step.path.kernel``);
    elsewhere by ``ssd_step_rows``, one elementwise pass over the arena
    in which each row takes the inputs of the slot that names it
    (``ssm_step.path.rows``: the CPU, the kernel's reference). A prompt
    in a bucket is prefilled whole from zeros (:meth:`prefill`); a prompt
    in chunks continues from the row (:meth:`chunk`); both turn the op's
    ``(H, P, N)`` state a request into the row's layout and back
    (:meth:`_to_row`, :meth:`_rows`)."""

    heads: int
    head_dim: int
    state_size: int
    tail: int          # positions of the convolution's inputs kept
    channels: int
    name = "ssm_state"
    chunked = True

    @classmethod
    def for_op(cls, op, positions_id, max_length):
        return cls(op.num_heads, op.head_dim, op.state_size,
                   op.conv_taps - 1, op.channels)

    @property
    def row_shape(self):
        return (self.state_size, self.heads * self.head_dim)

    @property
    def state_shape(self):
        return (self.heads, self.head_dim, self.state_size)

    def _to_row(self, state):
        return jnp.swapaxes(
            state.reshape(state.shape[0], -1, self.state_size), 1, 2)

    def _rows(self, arena, rows):
        """:meth:`_to_row` backwards: the states of ``rows`` as the op
        takes them, (N, H, P, S)."""
        return jnp.swapaxes(arena[rows], 1, 2).reshape(
            len(rows), self.heads, self.head_dim, self.state_size)

    def reads_in_place(self, op, entry, slots, window, max_blocks):
        return window == 1 and ssd_step.supported(
            slots, self.heads, self.head_dim, self.state_size, op.n_groups,
            entry[0].shape, entry[0].dtype)

    def step(self, op, weights, x, positions, entry, addr, seq_lens):
        n = x.shape[0]                   # one token a slot: ``max_window``
        state, tails = entry
        tail = self.conv_tail
        z, conv_in, dt = op.project(weights, x)
        with sub_scope("conv"):
            window = tail.behind(tail.take(tails, addr.rows), conv_in)
            xs, bm, cm = op.split(op.convolve(weights, window))
        tails = tail.slide(tails, addr.rows, window)
        with sub_scope("rule"):
            dt1 = dt[:, 0]
            update, path = ((ssd_step.ssd_step_decode, "kernel")
                            if self.reads_in_place(op, entry, n, 1, 0)
                            else (mamba2.ssd_step_rows, "rows"))
            # which form this lowering took, counted once a trace (as
            # ``attention.path.*``): a chip run has to be able to say
            metrics_registry().counter(f"ssm_step.path.{path}").inc()
            y, state = update(
                state, addr.rows, xs[:, 0] * dt1[..., None],
                jnp.exp(dt1 * op.decay_rate(weights)), bm[:, 0], cm[:, 0])
        return op.finish(weights, z, xs, y[:, None]), (state, tails)

    def chunk(self, op, weights, x, positions, entry, addr, offsets, lengths):
        """A row holds what the request before left: a first chunk starts
        from zeros, not from it; a later one from the state and the tail
        the chunk before wrote. What is written is what the chunk's TRUE
        length leaves."""
        later = offsets > 0
        state = jnp.where(later[:, None, None, None],
                          self._rows(entry[0], addr.rows), 0.0)
        tail = self.conv_tail.take(entry[1], addr.rows, later)
        out, state, tail = op.run(weights, x, state, tail, lengths)
        return out, self._put(entry, addr.rows, state, tail)


# the kind of each op type that keeps something for a sequence: ``for_op``
# as :meth:`EntryKind.for_op`
KINDS: Dict[OpType, Callable[..., EntryKind]] = {
    OpType.MULTIHEAD_ATTENTION: PairEntry.for_op,
    OpType.COMPRESSED_CONV_ATTENTION: CcaEntry.for_op,
    OpType.LATENT_ATTENTION: lambda op, *a: (
        LatentEntry if op.indexer is None else SparseLatentEntry).for_op(
            op, *a),
    OpType.GATED_DELTA_NET: StateEntry.for_op,
    OpType.KIMI_DELTA_ATTENTION: StateEntry.for_op,
    OpType.BLOCK_SPARSE_ATTENTION: SparseEntry.for_op,
    OpType.LIGHTNING_ATTENTION: DecayStateEntry.for_op,
    OpType.MAMBA2: SsmStateEntry.for_op,
}


def kind_for(op, positions_id: int, max_length: int) -> Optional[EntryKind]:
    """The entry kind of ``op``, or None for an op that keeps nothing; an
    attention op type with no kind raises, naming the op, rather than
    run its ``forward`` over one step's tokens alone."""
    make = KINDS.get(op.op_type)
    if make is not None:
        return make(op, positions_id, max_length)
    if "ATTENTION" in op.op_type.name:
        raise ValueError(
            f"{op.name}: no cache entry kind is registered for "
            f"{op.op_type.name} (serving/cache_entry.py KINDS)")
    return None


__all__ = ["CcaEntry", "ConvTail", "DecayStateEntry", "EntryKind",
           "Int8PairEntry", "KINDS", "LatentEntry", "PairEntry", "SparseEntry",
           "SparseLatentEntry",
           "SsmStateEntry", "StateEntry", "StateKind", "TailedStateKind",
           "WindowEntry", "kind_for", "latent_row_lanes"]
