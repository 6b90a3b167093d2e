"""Data loading.

TPU-native equivalent of the reference's ``SingleDataLoader``
(reference: include/flexflow/dataloader.h:34-125, src/dataloader/
dataloader.cc — full dataset resident in zero-copy DRAM, ``next_batch``
index-launches per-device copy tasks that slice the batch for each shard).

Here the full dataset stays in host numpy (the zero-copy-DRAM analog);
``next_batch`` slices the global batch and ``jax.device_put``s it with the
batch NamedSharding, so each device receives exactly its shard — the same
per-device slicing the reference's copy tasks perform, but driven by the
sharding instead of a task launch per device.

:class:`Prefetcher` moves that host work off the device's critical path:
a bounded background queue assembles the next batches (shuffle-perm
gather, dtype cast, super-batch stacking) ahead of time, so host input
work for step *i+1* overlaps compute for step *i* — the reference's
ahead-of-compute Legion copy tasks (dataloader.cc:232); placement stays
on the dispatch thread, whose asynchronous ``device_put`` overlaps the
transfer with compute on its own. Batch ORDER is bit-identical to the
serial loader at any depth: the worker is the group's only consumer and
pulls batches in exactly the sequence the serial path would.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Iterator, List, Optional, Sequence, Tuple

import numpy as np

import jax
from jax.sharding import NamedSharding, PartitionSpec

from ..obs.trace import span
from ..obs.watchdog import beat as _wd_beat
from ..obs.watchdog import watch as _wd_watch
from .buckets import PackingSpec, build_epoch_plan, plan_token_stats
from .faults import TransientFault
from .faults import active as _faults_active
from .faults import inject as _fault_inject
from .retry import RetryPolicy

# transient placement failures (and the device_put.transient fault site)
# back off briefly and retry; a persistent failure surfaces after the
# budget. Seeded: a replayed chaos plan backs off identically.
_PUT_RETRY = RetryPolicy(max_attempts=3, base_delay_s=0.002,
                         max_delay_s=0.02, retry_on=(TransientFault,),
                         label="device_put", seed=0)


def _put_once(batch: np.ndarray, sharding: Optional[NamedSharding]) -> jax.Array:
    """Place a host batch: sharded placement routes through the
    process-aware path (parallel/multihost.py — single-process it is a
    plain device_put); unsharded falls back to the default device."""
    _fault_inject("device_put.transient", TransientFault)
    if sharding is None:
        return jax.device_put(batch)
    from ..parallel.multihost import process_local_batch

    return process_local_batch(batch, sharding)


def _put(batch: np.ndarray, sharding: Optional[NamedSharding]) -> jax.Array:
    """``_put_once`` behind the retry policy — engaged only while a
    fault plan is armed (the off path is one global read; real
    placement errors are not transient on a healthy single host)."""
    if _faults_active():
        return _PUT_RETRY.call(_put_once, batch, sharding)
    return _put_once(batch, sharding)


def _super_sharding(sharding: Optional[NamedSharding]) -> Optional[NamedSharding]:
    """Sharding for a (k, batch, ...) super-batch: the per-step sharding
    shifted one dim right, the stacked step dim replicated."""
    if sharding is None:
        return None
    return NamedSharding(sharding.mesh,
                         PartitionSpec(None, *tuple(sharding.spec)))


class SingleDataLoader:
    """One tensor's dataloader (reference: dataloader.h:34).

    ``num_samples`` must be divisible into whole batches by the caller
    (the reference truncates to full batches; we do the same).
    """

    def __init__(
        self,
        full_array: np.ndarray,
        batch_size: int,
        sharding: Optional[NamedSharding] = None,
        dtype=None,
    ):
        self.data = np.ascontiguousarray(full_array if dtype is None else full_array.astype(dtype))
        self.batch_size = batch_size
        self.sharding = sharding
        self.num_samples = self.data.shape[0]
        self.next_index = 0
        # optional row permutation (set by DataLoaderGroup shuffling); kept
        # as indices over the pristine dataset so the order for a given
        # seed+epoch matches the native loader exactly
        self.perm: Optional[np.ndarray] = None

    @property
    def num_batches(self) -> int:
        return self.num_samples // self.batch_size

    @property
    def batch_nbytes(self) -> int:
        """Host bytes one batch moves (throughput accounting)."""
        row = self.data.nbytes // max(1, self.num_samples)
        return row * min(self.batch_size, self.num_samples)

    def reset(self) -> None:
        """reference: SingleDataLoader::reset."""
        # epoch handshake: reset() runs before the Prefetcher worker
        # starts and after it joins — the roles never overlap in time
        self.next_index = 0  # concurrency: race-ok (epoch handshake: worker joins before reset)

    def next_batch_host(self) -> np.ndarray:
        """Host-side batch assembly only (shuffle-perm gather); the
        device_put half lives in :meth:`next_batch` so the Prefetcher can
        stage both off the critical path."""
        i = self.next_index
        if i + self.batch_size > self.num_samples:
            i = 0
            # single consumer: either the epoch's Prefetcher worker OR
            # the serial caller pulls batches, never both concurrently
            # (the worker joins before the serial path resumes)
            self.next_index = 0  # concurrency: race-ok (single consumer per epoch, worker joins first)
        if self.perm is not None:
            batch = self.data[self.perm[i : i + self.batch_size]]
        else:
            batch = self.data[i : i + self.batch_size]
        self.next_index = i + self.batch_size  # concurrency: race-ok (single consumer per epoch)
        return batch

    def next_batch(self) -> jax.Array:
        """reference: next_batch_xd_launcher (dataloader.cc:232)."""
        return _put(self.next_batch_host(), self.sharding)


class DataLoaderGroup:
    """Batched iteration over aligned input+label loaders with optional
    shared shuffling (the reference shuffles via app-level random_shuffle
    in examples' DataLoader::shuffle).

    When the native runtime library is available, shuffle + row gathering +
    one-batch-ahead prefetch run on a C++ worker thread
    (native/src/dataloader.cc), overlapping host batch assembly with device
    step time — the reference's ahead-of-compute copy-task pattern. The
    pure-numpy path below is the fallback; :class:`Prefetcher` adds the
    Python-level ahead-of-time queue over either.
    """

    def __init__(self, loaders: List[SingleDataLoader], seed: int = 0,
                 shuffle: bool = False,
                 packing: Optional[PackingSpec] = None,
                 lengths: Optional[np.ndarray] = None):
        assert loaders
        n = {l.num_samples for l in loaders}
        assert len(n) == 1, "all loaders must have the same sample count"
        self.loaders = loaders
        self.shuffle = shuffle
        self._rng = np.random.default_rng(seed)
        # token-native dynamic shapes (runtime/buckets.py): when a
        # PackingSpec rides along, every epoch reset rebuilds the packed
        # plan from the permuted per-row ``lengths`` — batches become
        # (pad_rows, width) groups padded to their ladder rung instead
        # of fixed (batch_size, max) slabs. The plan is a pure function
        # of (seed, epoch), so skip/replay/resume reproduce it exactly.
        self.packing = packing
        self._lengths = (np.asarray(lengths, dtype=np.int64)
                        if lengths is not None else None)
        self._pack_plan = None
        self._plan_idx = 0
        self._row_cursor = 0
        self._pack_perm: Optional[np.ndarray] = None
        self.epoch_token_stats: Tuple[int, int] = (0, 0)
        self._native = None
        if packing is not None:
            # packed assembly is Python-only: the native loader's
            # fixed-row prefetch cannot express variable (rows, width)
            return
        try:
            from .. import native_bridge

            # native path needs at least one whole batch; smaller datasets
            # use the Python wrap-around semantics below
            if (native_bridge.available()
                    and loaders[0].num_samples >= loaders[0].batch_size):
                self._native = native_bridge.NativeLoader(
                    [l.data for l in loaders],
                    loaders[0].batch_size,
                    shuffle=shuffle,
                    seed=seed,
                )
        except Exception:
            self._native = None

    @property
    def num_batches(self) -> int:
        if self.packing is not None:
            assert self._pack_plan is not None, \
                "packed loader group used before its first reset()"
            return len(self._pack_plan)
        return self.loaders[0].num_batches

    @property
    def batch_nbytes(self) -> int:
        if self._native is not None:
            return self._native.batch_nbytes
        # packed mode: batch geometry varies per group; the fixed-row
        # estimate below stays the throughput-accounting approximation
        return sum(l.batch_nbytes for l in self.loaders)

    def reset(self, reshuffle: bool = True) -> None:
        if self._native is not None:
            self._native.reset(reshuffle)
            return
        for l in self.loaders:
            l.reset()
        if self.shuffle and reshuffle:
            perm = self._rng.permutation(self.loaders[0].num_samples)
            for l in self.loaders:
                l.perm = perm
        if self.packing is not None:
            perm = self.loaders[0].perm
            if perm is None:  # shuffle off: epoch order is dataset order
                perm = np.arange(self.loaders[0].num_samples)
            self._pack_perm = perm  # concurrency: race-ok (epoch handshake: worker joins before reset)
            self._pack_plan = build_epoch_plan(self._lengths[perm],  # concurrency: race-ok (epoch handshake: worker joins before reset)
                                               self.packing)
            self._plan_idx = 0  # concurrency: race-ok (epoch handshake: worker joins before reset)
            self._row_cursor = 0  # concurrency: race-ok (epoch handshake: worker joins before reset)
            self.epoch_token_stats = plan_token_stats(self._pack_plan)

    def advance_epochs(self, n: int) -> None:
        """Advance the shuffle stream exactly as ``n`` epoch resets
        would (crash-safe resume replay: a resumed fit must draw the
        SAME permutation for its resume epoch that the original run's
        epoch-``n`` reset drew)."""
        for _ in range(max(0, int(n))):
            self.reset(reshuffle=True)

    def skip_batches(self, n: int) -> None:
        """Consume and discard ``n`` batches (host side only, no device
        placement) — the resume path's fast-forward within an epoch.
        Implemented as real host pulls so cursor/wrap/native semantics
        stay bit-identical to the steps the original run took."""
        if self.packing is not None:
            # cursor arithmetic only — the gather/pad work is pure
            # function of the plan, so skipping it cannot drift
            for _ in range(max(0, int(n))):
                if self._plan_idx >= len(self._pack_plan):
                    self._plan_idx = 0  # concurrency: race-ok (single consumer per epoch, worker joins first)
                    self._row_cursor = 0  # concurrency: race-ok (single consumer per epoch, worker joins first)
                self._row_cursor += self._pack_plan[self._plan_idx].rows  # concurrency: race-ok (single consumer per epoch)
                self._plan_idx += 1  # concurrency: race-ok (single consumer per epoch)
            return
        for _ in range(max(0, int(n))):
            self.next_batch_host()

    def _next_packed_host(self) -> List[np.ndarray]:
        """One packed group: ``rows`` consecutive permuted samples,
        sequence dims sliced to the group's rung, row count padded to
        ``pad_rows`` with all-padding rows (labels -1 -> the masked
        loss/metric paths make them exact zeros)."""
        if self._plan_idx >= len(self._pack_plan):
            # wrap like SingleDataLoader: replay the epoch plan without
            # redrawing the permutation
            self._plan_idx = 0  # concurrency: race-ok (single consumer per epoch, worker joins first)
            self._row_cursor = 0  # concurrency: race-ok (single consumer per epoch, worker joins first)
        g = self._pack_plan[self._plan_idx]
        idx = self._pack_perm[self._row_cursor:self._row_cursor + g.rows]
        self._plan_idx += 1  # concurrency: race-ok (single consumer per epoch)
        self._row_cursor += g.rows  # concurrency: race-ok (single consumer per epoch)
        out = []
        spec = self.packing
        for li, l in enumerate(self.loaders):
            rows = l.data[idx]
            if spec.seq_axes[li]:
                rows = rows[:, :g.width]
            if g.pad_rows > g.rows:
                pad = np.full((g.pad_rows - g.rows,) + rows.shape[1:],
                              spec.pad_values[li], dtype=rows.dtype)
                rows = np.concatenate([rows, pad])
            out.append(np.ascontiguousarray(rows))
        return out

    def next_batch_host(self) -> List[np.ndarray]:
        """One batch per loader, still on host (numpy)."""
        if self.packing is not None:
            return self._next_packed_host()
        if self._native is not None:
            rows = self._native.next_batch()
            if rows is None:  # epoch end: wrap like SingleDataLoader does
                self._native.reset(reshuffle=False)
                rows = self._native.next_batch()
            return [np.asarray(r) for r in rows]
        return [l.next_batch_host() for l in self.loaders]

    def assemble_host(self, k: int) -> List[np.ndarray]:
        """Host half of a (super-)batch: gather ``k`` consecutive batches
        and stack them on a leading step dim (k=1: no stack). This is
        the work the Prefetcher's thread runs ahead of compute."""
        if k > 1 and self.packing is not None:
            raise ValueError("packed (dynamic-shape) batches cannot be "
                             "stacked into a super-batch; the step loop "
                             "forces steps_per_dispatch=1 when "
                             "seq_buckets is active")
        if k <= 1:
            return self.next_batch_host()
        host = [self.next_batch_host() for _ in range(k)]
        return [np.stack([h[i] for h in host])
                for i in range(len(self.loaders))]

    def place(self, host: List[np.ndarray], k: int) -> List[jax.Array]:
        """Device half: one device_put per tensor, with the per-step
        sharding shifted right for a stacked super-batch. device_put is
        asynchronous on accelerator runtimes, so issuing it from the
        dispatch thread already overlaps the transfer with compute —
        and keeps it off the worker thread, where a concurrent transfer
        contends with XLA's CPU execution locks."""
        if k > 1:
            return [_put(a, _super_sharding(l.sharding))
                    for a, l in zip(host, self.loaders)]
        return [_put(a, l.sharding) for a, l in zip(host, self.loaders)]

    def next_batch(self) -> List[jax.Array]:
        return self.place(self.next_batch_host(), 1)

    def next_super_batch(self, k: int) -> List[jax.Array]:
        """``k`` consecutive batches stacked on a new leading step dim —
        the input of the multi-step executable (compiler.train_k_steps)."""
        return self.place(self.assemble_host(k), k)


# ------------------------------------------------------------- prefetching
class _WorkerError:
    def __init__(self, exc: BaseException):
        self.exc = exc


_DONE = object()
_CLOSED = object()


class _Channel:
    """Bounded producer/consumer handoff with explicit close.

    The Prefetcher's previous shutdown handshake was a stop Event the
    worker polled between 50ms-timeout ``queue.put`` attempts — a worker
    blocked on a full queue noticed consumer abandonment only at the
    next poll tick, and the sentinel could be dropped without the
    consumer ever learning the worker was gone. Here ``close()`` wakes
    BOTH sides deterministically under one Condition: a producer blocked
    on a full buffer returns ``False`` immediately (stop signal), a
    consumer blocked on an empty buffer gets :data:`_CLOSED`.
    """

    def __init__(self, capacity: int):
        self._cv = threading.Condition()
        self._items: collections.deque = collections.deque()
        self._capacity = max(1, int(capacity))
        self._closed = False

    def put(self, item) -> bool:
        """Block until there is space; ``False`` once closed (the
        consumer abandoned the epoch — the producer must stop)."""
        with self._cv:
            while len(self._items) >= self._capacity and not self._closed:
                self._cv.wait()
            if self._closed:
                return False
            self._items.append(item)
            self._cv.notify_all()
            return True

    def get(self):
        """Block until an item arrives; :data:`_CLOSED` once closed and
        drained."""
        with self._cv:
            while not self._items and not self._closed:
                self._cv.wait()
            if self._items:
                item = self._items.popleft()
                self._cv.notify_all()
                return item
            return _CLOSED

    def depth(self) -> int:
        with self._cv:
            return len(self._items)

    def close(self) -> None:
        with self._cv:
            self._closed = True
            self._cv.notify_all()


class Prefetcher:
    """Bounded ahead-of-compute batch pipeline over a DataLoaderGroup.

    ``depth == 0``: serial passthrough — assembly + placement inline on
    the caller's thread, the historical fit-loop behavior. ``depth > 0``:
    a daemon worker thread pulls HOST batches from the group (numpy OR
    native path — shuffle-perm gather, dtype cast, super-batch stacking)
    and parks up to ``depth`` assembled batches in a queue, so host input
    work for step i+1 overlaps device compute for step i (double-buffered
    at depth>=2). The consumer issues the ``device_put`` at dispatch —
    asynchronous on accelerator runtimes, so the transfer still overlaps
    compute, without the worker contending with XLA's execution locks.
    Order and values are bit-identical to serial: one worker, one group,
    same pull sequence, and placement is value-preserving.

    ``steps_per_item > 1`` groups that many consecutive batches into one
    stacked super-batch per queue item (for ``train_k_steps``), ramping
    the super size up from 1 at epoch start so the cold queue never
    stalls the device for k assemblies; the epoch remainder rides as a
    smaller super.

    ``stats`` (profiling.EpochThroughput, optional) receives
    host-input-wait seconds and a queue-depth sample per batch.
    """

    def __init__(self, group: DataLoaderGroup, depth: int,
                 steps_per_item: int = 1, stats=None):
        self.group = group
        self.depth = max(0, int(depth))
        self.k = max(1, int(steps_per_item))
        self.stats = stats

    def _plan(self) -> List[int]:
        """Per-epoch item sizes. k>1 groups batches into supers; with a
        background queue the sizes RAMP (1, 2, 4, ..., k) so the first
        dispatch waits on one batch, not k — the queue is cold at every
        epoch start and a full-k first item would stall the device for
        k assemblies. Super sizes are only ever powers of two up to k
        and the epoch remainder rides as SINGLE batches (the plain
        train_step), so the scan executable compiles for at most
        log2(k) distinct sizes, never for transient remainders.
        Grouping never changes batch order or per-step metric order."""
        nb = self.group.num_batches
        if self.k <= 1:
            return [1] * nb
        plan: List[int] = []
        emitted = {1}  # sizes whose executables the plan already implies
        rem = nb
        size = 1 if self.depth > 0 else self.k
        while rem > 0:
            if size < self.k and rem >= size:  # warm-up ramp: 1, 2, 4, ...
                s = size
                size *= 2
            elif size >= self.k and rem >= self.k:
                s = self.k
            else:
                # tail: step down through sizes the plan already emitted
                # (largest fitting one), so the remainder costs as few
                # dispatches as possible without compiling a new size
                s = max((e for e in emitted if e <= rem), default=1)
            emitted.add(s)
            plan.append(s)
            rem -= s
        return plan

    def epoch(self, reshuffle: bool = True,
              skip: int = 0) -> Iterator[Tuple[int, list]]:
        """Reset the group and yield one epoch of ``(n_steps, batch)``
        items (placed device arrays); ``batch`` is a stacked super-batch
        when ``n_steps > 1``. ``skip`` fast-forwards past the first N
        steps (crash-safe resume): the shuffle reset still happens, the
        skipped batches are consumed host-side only, and the remaining
        items are exactly what the un-skipped epoch would have yielded
        from step N on — ``skip`` must land on an item boundary of the
        deterministic dispatch plan (checkpoints are only ever taken
        there)."""
        self.group.reset(reshuffle)
        plan = self._plan()
        if skip:
            done = idx = 0
            while idx < len(plan) and done < skip:
                done += plan[idx]
                idx += 1
            if done != skip:
                raise ValueError(
                    f"resume skip={skip} does not align with the dispatch "
                    f"plan's item boundaries (prefix sums {plan[:idx]})")
            self.group.skip_batches(skip)
            plan = plan[idx:]
        # span name/cat track whichever loop drives us (fit vs eval) so
        # the trace agrees with the registry series the stats feed
        pfx = self.stats.prefix if self.stats is not None else "fit"
        # watchdog: the consumer loop is a watched section — every
        # resumption of this generator (one per dispatch-loop iteration)
        # heartbeats it, so a hang in dispatch, in the channel wait, or
        # in serial assembly goes silent and dumps. The watch OPENS at
        # the second iteration: the first step's dispatch blocks through
        # the cold XLA compile (legitimately minutes on a big model),
        # which must not read as a stall.
        section = None
        if self.depth == 0:
            try:
                for i, k in enumerate(plan):
                    if i == 1:
                        section = _wd_watch(f"{pfx}.loop")
                        section.__enter__()
                    elif i > 1:
                        _wd_beat(f"{pfx}.loop")
                    with span(f"{pfx}.input_wait", cat=pfx, k=k,
                              mode="serial"):
                        t0 = time.perf_counter()
                        host = self.group.assemble_host(k)
                        wait = time.perf_counter() - t0
                    if self.stats is not None:
                        # serial mode: the whole inline assembly IS the wait
                        self.stats.record_wait(wait)
                        self.stats.record_depth(0)
                    yield k, self.group.place(host, k)
            finally:
                if section is not None:
                    section.__exit__(None, None, None)
            return
        chan = _Channel(self.depth)

        def _work():
            try:
                for k in plan:
                    # the assembly must make progress; the put may block
                    # legitimately on a full channel (consumer pacing),
                    # so only the assembly is inside the watched section
                    with _wd_watch("prefetch.worker"):
                        # fault site: a worker exception here must reach
                        # the consumer as the raised error (below, via
                        # _WorkerError) and never leak this thread
                        _fault_inject("prefetch.worker")
                        item = (k, self.group.assemble_host(k))
                    if not chan.put(item):
                        return  # consumer closed the channel mid-epoch
                chan.put(_DONE)
            except BaseException as e:  # surfaced on the consumer side
                chan.put(_WorkerError(e))

        worker = threading.Thread(target=_work, daemon=True,
                                  name="ff-prefetch")
        worker.start()
        try:
            i = -1
            while True:
                i += 1
                if i == 1:
                    # second iteration: the first step's cold XLA
                    # compile is behind us (see the serial path)
                    section = _wd_watch(f"{pfx}.loop")
                    section.__enter__()
                elif i > 1:
                    _wd_beat(f"{pfx}.loop")
                depth_sample = chan.depth()
                # (the wait for the end-of-epoch sentinel is a span too:
                # the loop thread did wait there)
                with span(f"{pfx}.input_wait", cat=pfx, depth=depth_sample,
                          mode="prefetch"):
                    t0 = time.perf_counter()
                    item = chan.get()
                    wait = time.perf_counter() - t0
                if item is _DONE or item is _CLOSED:
                    return
                if isinstance(item, _WorkerError):
                    raise item.exc
                if self.stats is not None:
                    # real batches only (the end-of-epoch sentinel is
                    # not an input wait)
                    self.stats.record_depth(depth_sample)
                    self.stats.record_wait(wait)
                k, host = item
                yield k, self.group.place(host, k)
        finally:
            if section is not None:
                section.__exit__(None, None, None)
            # close-then-join: a worker blocked on a full channel wakes
            # immediately (put returns False) — the generator can be
            # abandoned mid-epoch without leaking its worker thread
            chan.close()
            worker.join()
