"""Paged (block) KV cache pool for continuous-batching generation.

The dense generator (:class:`serving.generation.Generator`) reserves a
``(B, max_length, H, D)`` rectangle per attention op — every request
pays the worst-case sequence length for its whole lifetime, so the
number of co-resident requests is fixed at compile time. The paged pool
is the vLLM-style alternative: one ``(num_blocks, block_size, H*D)``
arena per attention op (the shape is the next section's subject), carved
into fixed-size blocks, with a per-request **block table** mapping
logical token positions to physical blocks. Requests allocate their
worst case (prompt + ``max_new_tokens``, rounded up to blocks) at
admission and free it at retirement, so

* pool memory is bounded by construction — admission **sheds**
  (:class:`KVPoolExhausted`, a :class:`ShedError`) instead of OOMing
  mid-decode;
* the decode executable's shape depends only on (decode slots, pool
  geometry), never on the live request mix — one compiled program
  serves every in-flight combination;
* occupancy is observable: the ``serving.kv_blocks_in_use`` gauge and
  the session high-water mark.

Block 0 is the **null block**: never allocated, the scatter target for
inactive decode slots and prompt padding, and the gather source for
unreserved block-table entries. Its contents are arbitrary-but-finite;
every read through it is masked out by position before softmax.

**The arena's shape** is chosen for the one reader that cannot take
another: the paged-attention kernel (``kernels/paged_attention.py``),
whose operand has one fixed layout. A token is ONE row of ``H*D`` values,
all heads side by side, and a block is ``block_size`` such rows. On a TPU
an array lives in (sublane, 128-lane) tiles of its two minor dimensions:
``(…, H, D) = (…, 20, 64)`` in bf16 would pad to (32, 128) tiles, 3.2
times the bytes, and a head-major ``(H, num_blocks, block_size, 64)``
pads its 64 lanes to 128, twice the bytes; ``(block_size, H*D) = (16,
1280)`` tiles exactly whenever ``H*D`` is a multiple of 128 and
``block_size`` of the sublane tile (16 rows in bf16, 8 in float32), so
the bytes below are the bytes on the device, a block is one contiguous
DMA, and the ``(num_blocks*block_size, H*D)`` view the writers scatter
rows into is the same buffer, not a copy. Prefill's scatter, decode's
scatter and both readers (the kernel in place; the jnp gather, which
reshapes the gathered view to ``(…, H, D)``) share this one shape, and
the donated arena is only ever updated in place.

Memory math (per attention op): ``2 * num_blocks * block_size * heads *
head_dim * dtype_bytes`` — e.g. 256 blocks x 16 tokens x 8 heads x 64
dims in bf16 = 2 * 256*16*8*64 * 2B = 8 MiB per layer, serving up to
``(num_blocks-1) // blocks_per_request`` concurrent worst-case requests;
GPT-2 large at 16 slots of 1024 tokens: 1025 x 16 x 1280 x 2 B = 42 MB an
arena, 72 arenas, 3.0 GB.

**Per-request entries.** A layer may keep, instead of a row a token, a
state of fixed size a request (a gated-delta-rule op:
:class:`~flexflow_tpu.serving.cache_entry.StateEntry`). A kind says what
it keeps a token in ``arenas`` and what it keeps a request in
``request_arenas``, and the pool gives the first ``num_blocks`` blocks
and the second ``num_rows`` rows: row 0 is the **null row**, what idle
slots and a prefill's padding rows name, as block 0 is for tokens.
Admission reserves
a request's blocks AND a row, or neither; the block table stays the
request's one handle (:meth:`PagedKVPool.rows_of` finds the row of a
table's request by its first block, which no other live request holds),
:meth:`PagedKVPool.free` returns both, and the bytes count both. Inside
the programs the two travel together as :class:`Addresses`. A kind may
keep both in one layer (compressed convolutional attention's pair a
token and, a request, its convolutions' tail and the last token's half
value): its bytes count under both terms.

**The books.** What the decode steps read is the pool's to count, since
the kinds, the geometry and ``stats()["kv"]`` are its own: the serving
loop hands :meth:`PagedKVPool.count_step` a step's cached lengths,
:meth:`PagedKVPool.chunk_keys` a chunk's span and
:meth:`PagedKVPool.count_chunk` its offset (the state rows it started
from zeros or carried on), and each kind says what a step behind such
lengths reads of it (``step_reads``, ``rows_read``).

**Quantized arenas** (``kv_dtype``): the pool can store its arenas in
``"bfloat16"`` (cast-in/cast-out) or ``"int8"``: each op's entry in its
kind's int8 form (:class:`~flexflow_tpu.serving.cache_entry
.Int8PairEntry`: values quantized per token and head, float32 sidecars
under the same (block, slot) addresses, so worst-case admission at a
fixed byte budget doubles or better). The numerics gate
(``serving_kv_divergence_budget``, KVQ001) lives in
:class:`~flexflow_tpu.serving.generation.PagedDecoder`, which
calibrates at construction and falls back loudly to f32.
"""

from __future__ import annotations

import math
import threading
from typing import TYPE_CHECKING, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

import jax.numpy as jnp

from ..obs.metrics import metrics_registry
from .errors import KVPoolExhausted

if TYPE_CHECKING:
    from .cache_entry import EntryKind

NULL_BLOCK = 0  # reserved scatter/gather sink; never allocated
NULL_ROW = 0    # the same for per-request arenas


class Addresses(NamedTuple):
    """What addresses the entries of a program's slots (or of a prefill's
    prompts): ``tables`` (N, max_blocks) int32, each one's block table,
    for the kinds that keep a row a token, and ``rows`` (N,) int32, each
    one's row of the per-request arenas (None in a pool that has none:
    the programs of a model with no such kind take no such argument)."""

    tables: "jnp.ndarray"
    rows: Optional["jnp.ndarray"] = None

# arena storage modes: "float32" stores in the pool's compute dtype
# (the historical behavior — ``dtype`` may itself be bf16 under a
# bf16 compute config), "bfloat16" forces bf16 arenas, "int8" adds
# per-token per-head f32 scale/zero-point sidecars
KV_DTYPES = ("float32", "bfloat16", "int8")


def stored_as(name: str, kind, kv_dtype: str, dtype):
    """The (kind, dtype) an op's arenas are stored as under ``kv_dtype``:
    its own kind in bfloat16 or the pool's ``dtype``, or under ``"int8"``
    its int8 form (which it has to have, and which states its arenas'
    dtypes itself)."""
    if kv_dtype != "int8":
        return kind, jnp.bfloat16 if kv_dtype == "bfloat16" else dtype
    if kind.int8_form is None:
        raise ValueError(
            f"{name}: a {kind.name} cache entry has no int8 form "
            f"(kv_dtype='int8' quantizes the heads of a (k, v) pair); "
            f"use 'bfloat16'")
    return kind.int8_form, dtype


def pool_bytes(specs, num_blocks: int, block_size: int,
               kv_dtype: str = "float32", dtype=jnp.float32,
               num_rows: int = 0) -> int:
    """Arena bytes of a pool of ``specs`` (``{op name: entry kind}``)
    across all ops, sidecars included: a term a token (``num_blocks *
    block_size`` of them) for the kinds that keep a row a token and a term
    a request (``num_rows``) for those that keep a state. Plain
    arithmetic, which :meth:`PagedKVPool.memory_bytes` and the sim's
    capacity planning both call. ``dtype`` is what the ``"float32"`` mode
    stores in (the pool's compute dtype, which may itself be bf16)."""
    per_tok = per_row = 0
    for name, spec in dict(specs).items():
        kind, store = stored_as(name, spec, kv_dtype, dtype)
        per_tok += kind.token_bytes(store)
        per_row += kind.request_bytes(store)
    return (int(num_blocks) * int(block_size) * per_tok
            + int(num_rows) * per_row)


def run_groups(blocks, run: int) -> Tuple[int, int]:
    """Of a table's entries ``blocks``: its whole groups of ``run``
    entries, and those of them that are neighbours ascending in the
    arena, which ONE copy fetches."""
    whole = len(blocks) // run
    if run == 1 or not whole:
        return whole, whole
    groups = np.asarray(blocks[:whole * run], np.int64).reshape(whole, run)
    return whole, int((groups == groups[:, :1] + np.arange(run))
                      .all(axis=1).sum())


class PagedKVPool:
    """Block pool + allocator for one model's attention ops.

    ``specs``: ``{attention op name: entry kind}`` says what a token's
    row is for each op (:mod:`~flexflow_tpu.serving.cache_entry`): the
    pool allocates the arenas the kind describes, as :attr:`kv`'s tuple
    of arrays for that op, and keeps the kind they are stored as in
    :attr:`kinds` (the op's own, or its int8 form). All ops share the same block geometry and
    allocator (a token occupies one slot in EVERY layer's arena, so one
    block id spans all layers — the allocator hands out block ids, not
    per-layer storage).

    The jnp arenas live in :attr:`kv` and are updated functionally by
    the decode/prefill executables (donated through, swapped back in by
    the scheduler); the allocator state (free list, high-water) is host
    state guarded by one lock — allocation happens on the scheduler
    thread, capacity introspection on callers' threads.
    """

    def __init__(self, specs: Dict[str, "EntryKind"], *,
                 num_blocks: int, block_size: int,
                 max_blocks_per_request: int, dtype=jnp.float32,
                 kv_dtype: str = "float32", num_rows: int = 0):
        if num_blocks < 2:
            raise ValueError(f"num_blocks {num_blocks} < 2: block 0 is the "
                             f"reserved null block, so a usable pool needs "
                             f"at least one more")
        if block_size < 1:
            raise ValueError(f"block_size {block_size} < 1")
        if max_blocks_per_request < 1:
            raise ValueError(
                f"max_blocks_per_request {max_blocks_per_request} < 1")
        if kv_dtype not in KV_DTYPES:
            raise ValueError(f"kv_dtype {kv_dtype!r}: expected one of "
                             f"{KV_DTYPES}")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.max_blocks_per_request = int(max_blocks_per_request)
        self.dtype = dtype
        self.kv_dtype = kv_dtype
        self.specs = dict(specs)
        # arena entry per op: the tuple of arrays its kind describes,
        # donated through the programs; the kind stays beside it
        self.kinds: Dict[str, "EntryKind"] = {}
        stored = {name: stored_as(name, spec, kv_dtype, dtype)
                  for name, spec in self.specs.items()}
        # the ops that keep a row a request, and the rows of each of their
        # arenas, the null row among them; 0 in a pool that has no such op
        self._state_ops = sum(kind.keeps_row for kind, _ in stored.values())
        self.num_rows = int(num_rows) if self._state_ops else 0
        if self._state_ops and self.num_rows < 2:
            raise ValueError(f"num_rows {num_rows} < 2: row 0 is the "
                             f"reserved null row, so a pool of per-request "
                             f"entries needs at least one more")
        self.kv: Dict[str, Tuple[jnp.ndarray, ...]] = {}
        for name, (kind, store) in stored.items():
            self.kinds[name] = kind
            self.kv[name] = tuple(
                jnp.zeros(a.shape, a.dtype) for a in
                kind.arenas(self.num_blocks, self.block_size, store)
                + kind.request_arenas(self.num_rows, self.block_size, store))
        # LIFO free list: freshly freed blocks are reused first (their
        # stale contents are masked by position either way). A freed table
        # goes back reversed, so that what pops next comes in the order the
        # table had it: a request's table is a few ascending stretches of
        # earlier tables, which a kernel that fetches neighbours by ONE
        # copy reads as runs (kernels/latent_attention.py)
        self._free: List[int] = list(range(self.num_blocks - 1, 0, -1))
        # the table entries ONE such copy brings (1: a copy a block), and
        # of the tables handed out their whole groups of so many entries
        # and those that are neighbours ascending
        self._run_blocks = max(
            kind.fetch_run_blocks(self.kv[name], self.max_blocks_per_request)
            for name, kind in self.kinds.items())
        self._fetch_runs = {"groups": 0, "groups_run": 0}
        # the same for rows (a prefill overwrites the whole row), and the
        # row each live request holds, by the request's first block
        self._free_rows: List[int] = list(range(self.num_rows - 1, 0, -1))
        self._row_of_block = np.zeros(self.num_blocks, np.int32)
        self._rows_high_water = 0
        self._mu = threading.Lock()
        self._high_water = 0
        # running sums over the decode steps count_step was told of
        self._steps = {"blocks_read": 0, "blocks_in_tables": 0,
                       "rows_stepped": 0}
        # and over the prompt chunks count_chunk was told of: the state
        # rows a chunk started from zeros (a prompt's first) and those it
        # carried on from what the chunk before left
        self._chunk_rows = {"rows_started": 0, "rows_carried": 0}
        # the ops whose steps read less than they keep, and the sums of
        # what they read, by the word their kind's step_reads says
        # ("selected": a selection of a request's blocks; "window": at
        # most a window of its rows; "index": an indexer's pools of rows).
        # One word's ops share one geometry, so the sums count the first
        self._readers: Dict[str, List["EntryKind"]] = {}
        self._reads: Dict[str, Dict[str, int]] = {}
        for kind in self.kinds.values():
            for word, zeros in kind.step_reads(np.zeros(0, np.int64)).items():
                self._readers.setdefault(word, []).append(kind)
                self._reads.setdefault(word, zeros)
        self._gauge()

    # ---- geometry ----------------------------------------------------------
    @property
    def capacity_blocks(self) -> int:
        """Allocatable blocks (block 0 is the reserved null block)."""
        return self.num_blocks - 1

    def blocks_for(self, tokens: int) -> int:
        """Blocks needed to hold ``tokens`` cache entries."""
        return max(1, math.ceil(int(tokens) / self.block_size))

    def memory_bytes(self) -> int:
        """Total arena bytes across all ops, dtype-aware: int8 pools
        count their f32 scale/zero-point sidecars too (the honest
        admission-doubling denominator)."""
        return pool_bytes(self.specs, self.num_blocks, self.block_size,
                          self.kv_dtype, self.dtype, self.num_rows)

    # ---- allocator ---------------------------------------------------------
    def in_use(self) -> int:
        with self._mu:
            return self.capacity_blocks - len(self._free)

    @property
    def high_water(self) -> int:
        with self._mu:
            return self._high_water

    def try_admit(self, total_tokens: int) -> Optional[np.ndarray]:
        """Reserve the worst case for a request of ``total_tokens``
        (prompt + max_new_tokens): its blocks and, in a pool of
        per-request entries, a row; both or neither. Returns a padded
        block table ``(max_blocks_per_request,)`` int32 (unused tail
        entries = :data:`NULL_BLOCK`), the request's handle for both, or
        None when the pool is currently too full — the caller waits for
        retirements and retries.

        Raises :class:`KVPoolExhausted` when the request can NEVER fit
        (worst case exceeds total pool capacity) — that is a shed, not
        a wait."""
        need = self.blocks_for(total_tokens)
        if need > self.max_blocks_per_request:
            raise KVPoolExhausted(
                f"request needs {need} blocks > max_blocks_per_request "
                f"{self.max_blocks_per_request} "
                f"({total_tokens} tokens, block_size {self.block_size})")
        if need > self.capacity_blocks:
            raise KVPoolExhausted(
                f"request worst case ({need} blocks for {total_tokens} "
                f"tokens) exceeds the whole pool "
                f"({self.capacity_blocks} allocatable blocks)")
        with self._mu:
            if need > len(self._free) or (self.num_rows
                                          and not self._free_rows):
                return None
            blocks = [self._free.pop() for _ in range(need)]
            whole, as_one = run_groups(blocks, self._run_blocks)
            self._fetch_runs["groups"] += whole
            self._fetch_runs["groups_run"] += as_one
            used = self.capacity_blocks - len(self._free)
            if used > self._high_water:
                self._high_water = used
            if self.num_rows:
                self._row_of_block[blocks[0]] = self._free_rows.pop()
                self._rows_high_water = max(
                    self._rows_high_water,
                    self.num_rows - 1 - len(self._free_rows))
        self._gauge()
        table = np.full(self.max_blocks_per_request, NULL_BLOCK, np.int32)
        table[:need] = blocks
        return table

    def free(self, table: np.ndarray) -> None:
        """Return a request's reserved blocks (every non-null table
        entry) and its row to the pool."""
        blocks = [int(b) for b in np.asarray(table).ravel()
                  if int(b) != NULL_BLOCK]
        with self._mu:
            if self.num_rows and blocks:
                row = int(self._row_of_block[blocks[0]])
                if row == NULL_ROW:
                    raise RuntimeError(
                        f"double free: the request of block {blocks[0]} "
                        f"holds no state row")
                self._row_of_block[blocks[0]] = NULL_ROW
                self._free_rows.append(row)
            self._free.extend(reversed(blocks))
            if len(self._free) > self.capacity_blocks:
                raise RuntimeError(
                    f"double free: {len(self._free)} free blocks > "
                    f"capacity {self.capacity_blocks}")
        self._gauge()

    def rows_of(self, tables: np.ndarray) -> Optional[np.ndarray]:
        """The row each table's request holds in the per-request arenas,
        (N,) int32 for ``tables`` (N, max_blocks): found by the request's
        first block; an idle slot's all-null table gives the null row.
        None in a pool that has no such arena."""
        if not self.num_rows:
            return None
        with self._mu:
            return self._row_of_block[np.asarray(tables)[:, 0]]

    def _gauge(self) -> None:
        metrics_registry().gauge("serving.kv_blocks_in_use").set(
            self.in_use())

    # ---- the books --------------------------------------------------------
    def count_step(self, lengths: np.ndarray) -> None:
        """One decode step (or speculative round) whose active slots'
        requests have ``lengths`` cached tokens: the blocks it reads of
        the tables it is given (a slot's cached tokens and the row it
        writes, whatever the kinds), a state row a slot and per-request
        op, and what the kinds that read less than they keep say."""
        n = np.asarray(lengths, np.int64)
        live = int(((n + self.block_size) // self.block_size).sum())
        reads = {word: kinds[0].step_reads(n)[word]
                 for word, kinds in self._readers.items()}
        with self._mu:
            self._steps["blocks_read"] += live
            self._steps["blocks_in_tables"] += \
                n.size * self.max_blocks_per_request
            self._steps["rows_stepped"] += n.size * self._state_ops
            for word, counts in reads.items():
                for key, v in counts.items():
                    self._reads[word][key] += v

    def count_chunk(self, offset: int, tokens: int = 0) -> None:
        """One chunk of a prompt, ``tokens`` tokens at ``offset``: a state
        row a per-request op, started from zeros at offset 0 and carried on
        past it, and what the kinds that read less than they keep say of
        it (``chunk_reads``)."""
        reads = {word: kinds[0].chunk_reads(offset, tokens).get(word, {})
                 for word, kinds in self._readers.items()}
        with self._mu:
            if self._state_ops:
                self._chunk_rows["rows_carried" if offset > 0
                                 else "rows_started"] += self._state_ops
            for word, counts in reads.items():
                for key, v in counts.items():
                    self._reads[word][key] += v

    def chunk_keys(self, offset: int, tokens: int) -> Tuple[int, int]:
        """The keys a chunk's queries see, at positions ``offset ..
        offset + tokens - 1``: in an op that keeps everything (a query at
        position p sees p + 1), and in a windowed one (a window's worth at
        most; 0 in a pool that has none)."""
        pos = np.arange(offset, offset + tokens, dtype=np.int64)
        windowed = self._readers.get("window")
        return (int((pos + 1).sum()),
                int(windowed[0].rows_read(pos).sum()) if windowed else 0)

    def stats(self, lengths=None) -> Dict:
        """Session-level occupancy snapshot (ledger / bench / healthz).
        Given the ``lengths`` cached of the requests in their slots (the
        serving loop's call), also the books: what the counted steps read,
        and what those requests hold now."""
        with self._mu:
            used = self.capacity_blocks - len(self._free)
            hw = self._high_water
            steps = dict(self._steps, **self._chunk_rows)
            fetch_runs = dict(self._fetch_runs, run_blocks=self._run_blocks)
            reads = {word: dict(sums) for word, sums in self._reads.items()}
            state = {"state": {
                "rows": self.num_rows,
                "in_use": self.num_rows - 1 - len(self._free_rows),
                "high_water": self._rows_high_water,
                # a request's row over all the ops that keep one, as stored
                "row_bytes": pool_bytes(self.specs, 0, 0, self.kv_dtype,
                                        self.dtype, 1)}} if self.num_rows else {}
        out = {
            "num_blocks": self.num_blocks,
            "block_size": self.block_size,
            "capacity_blocks": self.capacity_blocks,
            "max_blocks_per_request": self.max_blocks_per_request,
            "in_use": used,
            "high_water": hw,
            "memory_bytes": int(self.memory_bytes()),
            "kv_dtype": self.kv_dtype,
            # of the tables handed out so far: their whole groups of
            # ``run_blocks`` entries, and those ONE copy fetches
            "fetch_runs": fetch_runs,
            # what an op keeps, in its kind's words: "pair" (k, v),
            # "int8" (values and sidecars), "latent" (one row, its width
            # as cached and as the arena pads it) or "state" (a row a
            # request)
            **self._entry_stats(),
            **state,
        }
        if lengths is None:
            return out
        held = np.asarray(lengths, np.int64)
        out["blocks_read"] = steps["blocks_read"]
        out["blocks_in_tables"] = steps["blocks_in_tables"]
        if "selected" in reads:
            # what the steps of ONE op that selects blocks read, beside
            # the live blocks of the slots they carried, and the pooled
            # keys the requests in their slots hold now
            kind = self._readers["selected"][0]
            out["selected"] = reads["selected"]
            out["kernel_rows"] = sum(kind.side_rows(int(n)) for n in held)
        if "index" in reads:
            # ONE indexed op's steps over their active slots: the pools
            # scored and taken, the rows read beside the rows live, the
            # steps all of whose slots were dense; and its chunks: the
            # rows their queries took beside those their products ran over
            out["index"] = reads["index"]
        if "window" in reads:
            # ONE windowed op's rows over the steps' active slots (read,
            # what an op that keeps everything would have read, the rings
            # reserved), and the rows the requests in their slots hold
            # now. A wart kept: this dict takes the place of the kind's
            # own word ``"window": <rows>`` (``rows`` carries the number),
            # because the benchmark's readers take the dict by this name
            kinds = self._readers["window"]
            out["window"] = dict(
                reads["window"], rows=kinds[0].window, ops=len(kinds),
                rows_held=int(np.minimum(held, kinds[0].window).sum()))
        if "state" in out:
            out["state"].update({k: steps[k] for k in (
                "rows_stepped", "rows_started", "rows_carried")})
        return out

    def _entry_stats(self) -> Dict:
        """One kind's own words where every op says the same; else each
        kind's name with its count of ops, beside what the kinds say
        besides."""
        said = [kind.stats() for kind in self.kinds.values()]
        if all(s == said[0] for s in said):
            return said[0]
        out: Dict = {}
        for s in said:
            out.update(s)
        out["entry"] = {name: sum(s["entry"] == name for s in said)
                        for name in dict.fromkeys(s["entry"] for s in said)}
        # where two kinds say one word differently (the key-value heads of
        # a model's full and windowed layers), each kind's own words
        words = {s["entry"]: {k: v for k, v in s.items() if k != "entry"}
                 for s in said}
        if any(out[k] != v for w in words.values() for k, v in w.items()):
            out["by_entry"] = words
        return out


__all__ = ["Addresses", "KV_DTYPES", "NULL_BLOCK", "NULL_ROW", "PagedKVPool",
           "KVPoolExhausted", "pool_bytes", "run_groups", "stored_as"]
