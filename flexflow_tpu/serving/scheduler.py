"""Continuous (in-flight) batching over the paged KV cache.

The classic engine (engine.py) assembles a batch, runs it, replies, and
only then looks at the queue again — fine for one-shot inference, fatal
for autoregressive generation where requests finish at different steps:
a static batch holds every slot hostage to its longest member. This
scheduler admits and retires requests **between individual decode
steps**:

* ONE jitted decode program at a fixed ``decode_slots`` width batches
  all active requests (paged pool + block tables donated through it —
  :class:`~flexflow_tpu.serving.generation.PagedDecoder`); the decode
  loop issues one dispatch per step regardless of how many slots are
  live;
* a greedy session's loop runs **one step ahead**: a pass dispatches
  step n+1 (each slot's token taken from step n's ids on the device)
  and only then reads step n's ids, so the host's work hides behind the
  chip's; a sampled slot or speculation makes the pass the synchronous
  one (:meth:`ContinuousBatchingScheduler._decode_once`);
* prompts run through the separate bucketed prefill executable, their
  K/V scattered straight into the pool; at most
  ``max_prefills_per_step`` prefills are interleaved between decode
  steps while requests are active, so a long prompt burst cannot stall
  in-flight decodes unboundedly;
* with ``prefill_chunk`` a prompt is prefilled as chunks of that many
  tokens, each continuing from the blocks and states the chunks before
  it wrote (the entry kinds' ``chunk``): a request takes its slot and
  its blocks at admission, and the loop runs at most ONE chunk between
  two decode steps, so a decoding slot's gap is a step plus a chunk
  however long the prompts are;
* admission control degrades gracefully (PR 11 semantics): a queue past
  ``admission_limit`` sheds (:class:`ShedError`), a request whose worst
  case (prompt + ``max_new_tokens``) can never fit the pool sheds
  immediately (:class:`KVPoolExhausted`), a deadline that expires —
  in queue OR mid-flight — rejects fast (:class:`DeadlineExceeded`)
  before the next decode step, ``breaker_threshold`` consecutive decode
  failures open a cooldown breaker, and a crashed decode worker
  respawns under ``worker_retry_budget`` with every accepted future
  still resolving (the scheduler owns the request state, not the dead
  thread).

Determinism contract: sampling is per-request — each request draws from
``np.random.default_rng(seed)`` in its own token order through the
shared :func:`~flexflow_tpu.serving.generation.sample_next_token` — and
the paged decode computes the dense cache's sums (to float32
reordering), so the engine produces the tokens sequential static-batch
serving would.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from concurrent.futures import Future
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..obs.metrics import Histogram, metrics_registry
from ..obs.trace import VIRTUAL_TID_BASE, span, tracer
from ..obs.watchdog import watch as _wd_watch
from ..runtime.faults import InjectedFault, TransientFault
from ..runtime.faults import fire as _fault_fire
from ..runtime.retry import RetryPolicy
from .errors import DeadlineExceeded, ShedError
from .generation import PagedDecoder, sample_next_token

# generation request tracks live above the classic engine's range so the
# two engines' per-request trace tracks can never collide
_GEN_TID_BASE = VIRTUAL_TID_BASE + (1 << 19)

# transient decode/prefill dispatch failures back off briefly before the
# step is failed (mirrors the classic engine's dispatch retry)
_DECODE_RETRY = RetryPolicy(max_attempts=3, base_delay_s=0.002,
                            max_delay_s=0.02, retry_on=(TransientFault,),
                            label="serving_decode", seed=0)

# per-session latency windows kept for the ledger record's percentiles
# (bounded: a long session keeps the most recent window, like the
# metrics registry's reservoirs)
_PHASE_WINDOW = 4096

# serving-attribution publication cadence (retirements between
# refreshes of the obs server's /attribution surface; the first
# retirement and stop() always publish)
_PUBLISH_EVERY = 16


def _temp_softmax(row_logits: np.ndarray, temperature: float) -> np.ndarray:
    """The temperature softmax :func:`sample_next_token` samples from,
    as an explicit distribution — the speculative path's rejection test
    needs p and q themselves, with numerics identical to the sampling
    path (same max-shift, same normalization)."""
    p = np.exp((np.asarray(row_logits, np.float64)
                - float(row_logits.max())) / temperature)
    return p / p.sum()


def _percentiles(xs) -> Optional[Dict]:
    from ..obs.metrics import nearest_rank_percentile

    xs = sorted(xs)
    if not xs:
        return None
    return {"count": len(xs), "mean": sum(xs) / len(xs),
            "p50": nearest_rank_percentile(xs, 0.5),
            "p99": nearest_rank_percentile(xs, 0.99)}


# what the scheduler's thread can be doing; every moment of its life is
# charged to exactly one of these
LOOP_PHASES = ("wait", "admit", "prefill", "inputs", "dispatch", "fetch",
               "sample", "other")


class _LoopClock:
    """The scheduler thread's time by phase, always on. The thread
    calls :meth:`enter` at every boundary: one clock read, which closes
    the phase it was in and opens the next, so the phases telescope —
    their sum is the time since the loop first started. ``steps``
    counts the passes that ran a decode step or a speculative round
    and ``step_wall`` holds their wall times; ``token_gap`` the time
    between a request's consecutive tokens; ``ahead`` how the steps met
    the host: ``steps_ahead`` were dispatched while the step before was
    still unread, ``steps_sync`` had their logits waited for, and
    ``rows_dropped`` rows were computed for a request that had already
    ended (a step dispatched with nothing before it unread, and read a
    pass later, is in neither count)."""

    def __init__(self):
        self._lock = threading.Lock()  # enter() against snapshot()
        self.phase_s = dict.fromkeys(LOOP_PHASES, 0.0)
        self.phase: Optional[str] = None  # None: the loop is not running
        self.t = 0.0  # the last boundary
        self.t_start: Optional[float] = None  # the first
        self.steps = 0
        self.step_wall = Histogram()
        self.token_gap = Histogram()
        self.ahead = {"steps_ahead": 0, "steps_sync": 0, "rows_dropped": 0}
        # prompts prefilled in chunks: the chunks run, and their tokens
        # (and the keys those tokens' queries saw in an op that keeps
        # everything, and in one that keeps a window)
        self.chunks = {"prefill_chunks": 0, "prefill_tokens": 0,
                       "prefill_keys": 0, "prefill_keys_window": 0,
                       # ... and of the chunks that were their prompt's
                       # last, the only ones whose last layer attends
                       "prefill_tokens_last": 0, "prefill_keys_last": 0}

    def enter(self, phase: Optional[str]):
        """The thread is in ``phase`` from now on. Returns the boundary
        and the phase it closed."""
        with self._lock:
            now = time.perf_counter()
            was = self.phase
            if was is not None:
                self.phase_s[was] += now - self.t
            elif self.t_start is None:
                self.t_start = now
            else:  # a respawned worker: the gap since the crash
                self.phase_s["other"] += now - self.t
            self.phase = phase
            self.t = now
        return now, was

    def count_step(self, wall_s: float) -> None:
        with self._lock:
            self.steps += 1
        self.step_wall.observe(wall_s)

    def count(self, key: str, n: int = 1) -> None:
        """``n`` more of ``ahead[key]``."""
        with self._lock:
            self.ahead[key] += n

    def count_chunk(self, tokens: int, keys: int = 0,
                    window_keys: int = 0, last: bool = False) -> None:
        with self._lock:
            self.chunks["prefill_chunks"] += 1
            self.chunks["prefill_tokens"] += tokens
            self.chunks["prefill_keys"] += keys
            self.chunks["prefill_keys_window"] += window_keys
            if last:
                self.chunks["prefill_tokens_last"] += tokens
                self.chunks["prefill_keys_last"] += keys

    def snapshot(self) -> Dict:
        """``stats()["loop"]``; the phase that is open is charged up to
        this moment, so two snapshots subtract to what lay between."""
        with self._lock:
            now = time.perf_counter()  # under the lock: not before self.t
            phase_s = dict(self.phase_s)
            if self.phase is not None:
                phase_s[self.phase] += now - self.t
            else:
                now = self.t  # the loop has ended, or never began
            steps = self.steps
            ahead = dict(self.ahead)
            chunks = dict(self.chunks)
            elapsed = 0.0 if self.t_start is None else now - self.t_start
        return {"steps": steps, "elapsed_s": elapsed, "phase_s": phase_s,
                "ahead": ahead, **chunks,
                "step_wall": self.step_wall.to_json(),
                "token_gap": self.token_gap.to_json()}


class _Phase:
    """``with`` block of the scheduler's thread: a ``serving.loop.<name>``
    span, during which the clock charges ``phase``; on exit the phase
    that the block interrupted resumes. ``t0``/``t1``: its two ends."""

    __slots__ = ("_clock", "_phase", "_outer", "span", "t0", "t1")

    def __init__(self, clock: _LoopClock, name: str, phase: str, args: Dict):
        self._clock = clock
        self._phase = phase
        self.span = span("serving.loop." + name, cat="serving", **args)

    def __enter__(self):
        self.span.__enter__()
        self.t0, self._outer = self._clock.enter(self._phase)
        return self

    def __exit__(self, *exc):
        self.t1, _ = self._clock.enter(self._outer)
        self.span.__exit__(*exc)
        return False


# a decode step dispatched and not yet read: ``rows`` [(slot, request)]
# it carries, ``ids`` its (slots,) greedy tokens on the device, ``t0``
# the moment of its dispatch
_Step = collections.namedtuple("_Step", "rows ids t0")


class GenerationRequest:
    """One queued/in-flight generation request. The ``future`` resolves
    to the full (prompt + generated) int32 token array — exactly
    ``Generator.generate``'s row contract."""

    __slots__ = ("request_id", "prompt", "max_new_tokens", "temperature",
                 "seed", "eos_id", "deadline_s", "t_enqueue", "future",
                 # scheduler-thread-only runtime state
                 "table", "seq_len", "in_flight", "prefill_left", "tokens",
                 "rng", "t_admit",
                 "t_prefill_done", "t_first_token", "t_last_token",
                 "decode_t0", "decode_steps")

    def __init__(self, request_id: int, prompt: np.ndarray,
                 max_new_tokens: int, temperature: float, seed: int,
                 eos_id: Optional[int], deadline_s: Optional[float]):
        self.request_id = request_id
        self.prompt = np.asarray(prompt, np.int32).ravel()
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.seed = int(seed)
        self.eos_id = eos_id
        self.deadline_s = deadline_s
        self.t_enqueue = time.perf_counter()
        self.future: Future = Future()
        self.table = None
        self.seq_len = 0  # rows cached, those of steps in flight included
        self.in_flight = 0  # tokens dispatched for and not yet read
        self.prefill_left = 0  # prompt tokens its chunks have yet to take
        self.tokens: List[int] = []
        self.rng = None
        self.t_admit = None
        self.t_prefill_done = None
        self.t_first_token = None
        self.t_last_token = None
        self.decode_t0 = None
        self.decode_steps = 0

    def expired(self, now: float) -> bool:
        return (self.deadline_s is not None
                and now - self.t_enqueue > self.deadline_s)


class ContinuousBatchingScheduler:
    """The continuous-batching loop for ONE compiled causal LM.

    Locking discipline (mirrors engine.py, checked by the concurrency
    auditor): one Condition ``_mu`` guards the queue, the slot array,
    lifecycle flags, breaker state, and the session stats; every
    blocking operation — prefill/decode dispatches, thread join — runs
    OUTSIDE it (CCY003). Slot/request runtime state is only MUTATED by
    the scheduler thread; other threads read it under ``_mu`` for
    stats."""

    def __init__(self, ff, name: str = "lm", *,
                 max_length: Optional[int] = None,
                 decode_slots: int = 4, block_size: int = 16,
                 num_blocks: Optional[int] = None,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 prefill_chunk: Optional[int] = None,
                 max_prefills_per_step: int = 1,
                 prefill_token_budget: int = 0,
                 admission_limit: Optional[int] = None,
                 default_deadline_s: Optional[float] = None,
                 breaker_threshold: int = 0,
                 breaker_cooldown_s: float = 1.0,
                 worker_retry_budget: int = 2,
                 draft_ff=None, spec_k: int = 0,
                 kv_dtype: str = "float32",
                 kv_divergence_budget: Optional[float] = None):
        if max_length is None:
            max_length = _position_capacity(ff)
        self.name = name
        self._ff = ff
        self.decoder = PagedDecoder(
            ff, max_length, decode_slots=decode_slots,
            block_size=block_size, num_blocks=num_blocks,
            prefill_buckets=prefill_buckets, prefill_chunk=prefill_chunk,
            kv_dtype=kv_dtype, kv_divergence_budget=kv_divergence_budget)
        self.spec_k = max(0, int(spec_k))
        self.draft: Optional[PagedDecoder] = None
        if self.spec_k > 0:
            self.decoder.check_window(self.spec_k + 1)
            if draft_ff is None:
                raise ValueError(
                    f"{name!r}: spec_k={self.spec_k} needs a draft model "
                    f"— pass draft_ff (or set serving_draft_model so the "
                    f"GenerationInstance builds one)")
            from ..runtime.compiler import causal_lm_signature

            tsig = causal_lm_signature(ff.compiled)
            dsig = causal_lm_signature(draft_ff.compiled)
            if dsig["vocab_size"] != tsig["vocab_size"]:
                raise ValueError(
                    f"{name!r}: draft vocab {dsig['vocab_size']} != "
                    f"target vocab {tsig['vocab_size']} — speculation "
                    f"needs the shared tokenizer/vocab contract")
            if (dsig["max_positions"] is not None
                    and dsig["max_positions"] < self.decoder.max_length):
                raise ValueError(
                    f"{name!r}: draft position capacity "
                    f"{dsig['max_positions']} < serving max_length "
                    f"{self.decoder.max_length}")
            # the draft decoder SHARES the target's block tables (same
            # geometry: block_size / num_blocks / max_length), writing
            # its own arenas at the same coordinates; its allocator is
            # never used — admission lives in the target pool only
            self.draft = PagedDecoder(
                draft_ff, self.decoder.max_length,
                decode_slots=self.decoder.decode_slots,
                block_size=self.decoder.block_size,
                num_blocks=self.decoder.pool.num_blocks,
                prefill_buckets=self.decoder.prefill_buckets,
                prefill_chunk=self.decoder.prefill_chunk,
                kv_dtype=self.decoder.kv_dtype, calibrate=False)
            # a draft's rejected tokens are rolled back as the target's are
            self.draft.check_window(self.spec_k + 1)
        # requests admitted whose prompts are still being prefilled in
        # chunks, oldest first (the loop's thread alone touches it)
        self._prefilling: collections.deque = collections.deque()
        self._spec_rounds = 0
        self._spec_slot_rounds = 0
        self._spec_proposed = 0
        self._spec_matched = 0
        self._spec_emitted = 0
        self.max_prefills_per_step = max(1, int(max_prefills_per_step))
        self.prefill_token_budget = max(0, int(prefill_token_budget))
        self._prefill_dispatches = 0
        self._prefill_prompts = 0
        self.admission_limit = (int(admission_limit)
                                if admission_limit else None)
        self.default_deadline_s = (float(default_deadline_s)
                                   if default_deadline_s else None)
        self.breaker_threshold = max(0, int(breaker_threshold))
        self.breaker_cooldown_s = float(breaker_cooldown_s)
        self.worker_retry_budget = max(0, int(worker_retry_budget))
        self._mu = threading.Condition()
        self._queue: collections.deque = collections.deque()
        self._slots: List[Optional[GenerationRequest]] = \
            [None] * self.decoder.decode_slots
        self._ids = itertools.count()
        self._thread: Optional[threading.Thread] = None
        self._closed = False
        self._session_recorded = False
        self._abandoned = False
        self._consec_failures = 0
        self._breaker_open_until = 0.0
        self._tokens_total = 0
        self._t_first_activity: Optional[float] = None
        # per-phase latency windows for the session ledger record, and
        # beside each the session's whole count and sum (the window
        # saturates at _PHASE_WINDOW; these do not)
        self._lat: Dict[str, collections.deque] = {
            k: collections.deque(maxlen=_PHASE_WINDOW)
            for k in ("queue_wait", "prefill", "decode", "ttft",
                      "per_token", "e2e")}
        self._lat_total: Dict[str, List[float]] = {
            k: [0, 0.0] for k in self._lat}
        self._clock = _LoopClock()
        # decode steps dispatched and not yet read, oldest first: one
        # between two passes of a greedy session, two between a pass's
        # dispatch and its fetch. The loop's thread alone touches it; it
        # lives here and not in that thread's frame, so a respawned
        # worker reads what the dead one dispatched
        self._in_flight: collections.deque = collections.deque()
        for dec in filter(None, (self.decoder, self.draft)):
            dec.on_dispatched = self._on_dispatched
        self._publish_due = False
        self._shed = 0
        self._deadline_rejects = 0
        self._completed = 0

    # ---- admission ---------------------------------------------------------
    def submit(self, prompt: np.ndarray, max_new_tokens: int,
               temperature: float = 0.0, seed: int = 0,
               eos_id: Optional[int] = None,
               deadline_s: Optional[float] = None) -> Future:
        """Submit one request. Raises :class:`ShedError` at admission
        when the queue is past its bound, the breaker is open, or the
        request's worst case can never fit the pool."""
        prompt = np.asarray(prompt, np.int32).ravel()
        if prompt.size < 1:
            raise ValueError("empty prompt")
        total = prompt.size + int(max_new_tokens)
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens {max_new_tokens} < 1")
        if total > self.decoder.max_length:
            raise ValueError(
                f"{prompt.size} prompt + {max_new_tokens} new > "
                f"max_length {self.decoder.max_length}")
        reg = metrics_registry()
        # pool-capacity shed: a request that can NEVER fit must not
        # poison the queue head forever (raises KVPoolExhausted=ShedError)
        need = self.decoder.pool.blocks_for(total)
        if need > self.decoder.pool.capacity_blocks:
            with self._mu:
                self._shed += 1
            reg.counter("serving.shed").inc()
            self.decoder.pool.try_admit(total)  # raises with the details
        req = GenerationRequest(
            next(self._ids), prompt, max_new_tokens, temperature, seed,
            eos_id,
            float(deadline_s) if deadline_s is not None
            else self.default_deadline_s)
        with self._mu:
            if self._closed:
                raise RuntimeError(
                    f"{self.name!r}: generation scheduler is stopped")
            now = time.monotonic()
            if self._breaker_open_until and now < self._breaker_open_until:
                self._shed += 1
                reg.counter("serving.breaker_shed").inc()
                reg.counter("serving.shed").inc()
                raise ShedError(
                    f"{self.name!r}: decode failure breaker is open "
                    f"({self.breaker_threshold} consecutive step "
                    f"failures); shedding until the cooldown elapses")
            if self._breaker_open_until and now >= self._breaker_open_until:
                # cooldown elapsed: close the breaker, let traffic probe
                self._breaker_open_until = 0.0
                self._consec_failures = 0
            if (self.admission_limit is not None
                    and len(self._queue) >= self.admission_limit):
                self._shed += 1
                reg.counter("serving.shed").inc()
                raise ShedError(
                    f"{self.name!r}: admission queue at its bound "
                    f"({self.admission_limit}); shedding")
            self._queue.append(req)
            depth = len(self._queue)
            if self._t_first_activity is None:
                self._t_first_activity = time.perf_counter()
            self._start_locked()
            self._mu.notify_all()
        reg.counter("serving.requests").inc()
        reg.counter("serving.gen_requests").inc()
        reg.histogram("serving.queue_depth").observe(depth)
        return req.future

    def generate(self, prompt: np.ndarray, max_new_tokens: int,
                 timeout: Optional[float] = 120.0, **kw) -> np.ndarray:
        return self.submit(prompt, max_new_tokens, **kw).result(timeout)

    # ---- lifecycle ---------------------------------------------------------
    def _start_locked(self) -> None:
        if self._thread is not None or self._closed:
            return
        t = threading.Thread(target=self._worker_main, daemon=True,
                             name=f"ffserve-gen-{self.name}")
        self._thread = t
        t.start()

    def stop(self) -> None:
        """Drain and stop: QUEUED requests fail fast with a clean
        RuntimeError (the classic engine's parked-request semantics);
        ACTIVE requests decode to completion (their worst case is
        bounded by construction). Writes the session's serving ledger
        record. A stopped scheduler does not restart."""
        with self._mu:
            self._closed = True
            self._mu.notify_all()
            t = self._thread
            # idempotent: a GenerationInstance stopped directly and then
            # again through engine.stop() must not append a duplicate
            # session record
            already = self._session_recorded
            self._session_recorded = True
        if t is not None:
            t.join(timeout=120)  # outside _mu (CCY003)
        if not already:
            self._record_session()

    # ---- worker ------------------------------------------------------------
    def _worker_main(self) -> None:
        """Respawn supervisor (the classic engine's _worker_main
        analog): the decode loop's state lives on the scheduler object,
        so a respawned worker resumes every in-flight request."""
        reg = metrics_registry()
        for crashes in range(self.worker_retry_budget + 1):
            try:
                self._loop()
                return  # clean shutdown
            except Exception as e:  # noqa: BLE001 — the decode loop died
                reg.counter("serving.worker_crashes").inc()
                if crashes >= self.worker_retry_budget:
                    reg.counter("serving.worker_abandoned").inc()
                    print(f"[serving] generation worker {self.name} "
                          f"crashed {crashes + 1}x ({type(e).__name__}: "
                          f"{e}); respawn budget exhausted — abandoning",
                          file=__import__("sys").stderr, flush=True)
                    self._abandon(e)
                    return
                reg.counter("serving.worker_respawns").inc()
                print(f"[serving] generation worker {self.name} crashed "
                      f"({type(e).__name__}: {e}); respawning "
                      f"({crashes + 1}/{self.worker_retry_budget})",
                      file=__import__("sys").stderr, flush=True)

    def _abandon(self, err: Exception) -> None:
        """Respawn budget exhausted: every accepted future must still
        resolve — fail queued AND active requests loudly, free their
        blocks, and open the breaker forever (admission sheds)."""
        with self._mu:
            self._abandoned = True
            self._breaker_open_until = float("inf")
            pending = list(self._queue)
            self._queue.clear()
            active = [r for r in self._slots if r is not None]
            self._slots = [None] * len(self._slots)
        self._in_flight.clear()  # their rows' requests fail with the rest
        metrics_registry().counter("serving.abandoned_failed").inc(
            len(pending) + len(active))
        wrapped = RuntimeError(
            f"{self.name!r}: generation worker exhausted its respawn "
            f"budget ({type(err).__name__}: {err}); request failed")
        for r in active:
            self.decoder.pool.free(r.table)
        for r in pending + active:
            if not r.future.done():
                r.future.set_exception(wrapped)

    def _phase(self, name: str, phase: Optional[str] = None,
               **args) -> _Phase:
        """The loop thread's next phase: the span ``serving.loop.<name>``
        and the clock's ``phase`` (``name`` unless given)."""
        return _Phase(self._clock, name, phase or name, args)

    def _loop(self) -> None:
        self._clock.enter("other")  # a respawned worker resumes the clock
        try:
            self._loop_passes()
        finally:
            self._clock.enter(None)

    def _loop_passes(self) -> None:
        import contextlib

        def idle() -> bool:
            return (not self._queue and not self._in_flight
                    and not any(r is not None for r in self._slots))

        first_step = True
        while True:
            with self._mu:
                if not self._closed and idle():
                    with self._phase("wait"):
                        while not self._closed and idle():
                            self._mu.wait()
                if self._closed and idle():
                    return
                closed = self._closed
                queued = len(self._queue)
                active = sum(1 for r in self._slots if r is not None)
            # one pass: admit, then one decode step or speculative
            # round; whatever of it no inner span covers is "other"
            step = self._phase("step", "other", step=self._clock.steps,
                               active=active, queued=queued)
            stepped = ahead = False
            with step:
                # fault site: decode-worker crash — state stays on the
                # scheduler, so the respawned worker resumes every request
                rule = _fault_fire("serving.worker")
                if rule is not None:
                    raise InjectedFault(
                        f"injected fault at site 'serving.worker' ({rule})")
                self._admit(closed)
                with self._mu:
                    active = any(r is not None for r in self._slots)
                if active or self._in_flight:
                    # watchdog: only ACTIVE decode work is watched; the
                    # first step runs unwatched through the cold XLA compile
                    ctx = (contextlib.nullcontext() if first_step
                           else _wd_watch(f"serving.gen.{self.name}"))
                    first_step = False
                    with ctx:
                        stepped, ahead = self._decode_once()
                step.span.set(ahead=int(ahead))
                if self._publish_due:
                    self._publish_due = False  # hotpath: lock-ok (flag of the loop thread alone)
                    self._publish_attribution()
            if stepped:
                self._clock.count_step(step.t1 - step.t0)

    # ---- admission between decode steps ------------------------------------
    def _admit(self, closed: bool) -> None:
        """Move queued requests into free decode slots: deadline-expired
        requests reject fast, pool-full requests wait (FIFO head keeps
        its place). A prompt is prefilled whole as it is admitted
        (:meth:`_admit_whole`) or, with ``prefill_chunk``, a chunk a
        pass from then on (:meth:`_admit_chunked`)."""
        with self._phase("admit") as ph:
            admit = (self._admit_chunked if self.decoder.prefill_chunk
                     else self._admit_whole)
            ph.span.set(admitted=admit(closed))
        if self._prefilling:
            self._prefill_next_chunk()

    def _pop_live(self, closed: bool) -> Optional[GenerationRequest]:
        """The queue's next request that is still wanted, or None where
        the queue is empty: a request met after ``stop()`` fails with
        "engine stopped", one past its deadline is rejected."""
        while True:
            with self._mu:
                if not self._queue:
                    return None
                req = self._queue.popleft()
            if closed:
                if not req.future.done():
                    req.future.set_exception(
                        RuntimeError("engine stopped"))
                continue
            now = time.perf_counter()
            if req.expired(now):
                with self._mu:
                    self._deadline_rejects += 1
                metrics_registry().counter("serving.deadline_rejects").inc()
                if not req.future.done():
                    req.future.set_exception(DeadlineExceeded(
                        f"request {req.request_id} waited "
                        f"{now - req.t_enqueue:.3f}s > deadline "
                        f"{req.deadline_s:.3f}s"))
                continue
            return req

    def _reserve(self, req: GenerationRequest, taken=()) -> Optional[int]:
        """A free decode slot (not among ``taken``) and the request's
        worst case in the pool, or the request goes back to the head of
        the queue (a retirement frees both: bounded). Returns the slot,
        with ``req.table`` and its queue wait set; else None."""
        slot = None
        with self._mu:
            for i, r in enumerate(self._slots):
                if r is None and i not in taken:
                    slot = i
                    break
        table = None if slot is None else self.decoder.pool.try_admit(
            req.prompt.size + req.max_new_tokens)
        if table is None:
            with self._mu:
                self._queue.appendleft(req)
            return None
        now = time.perf_counter()
        with self._mu:
            req.table = table
            req.t_admit = now
            self._observe_lat("queue_wait", now - req.t_enqueue)
        metrics_registry().histogram("serving.gen_queue_wait_s").observe(
            now - req.t_enqueue)
        return slot

    def _admit_chunked(self, closed: bool) -> int:
        """Admission where prompts are prefilled in chunks: a queued
        request that finds a free slot and its worst case in the pool
        takes both at once, which costs the device nothing; its prompt
        is then prefilled a chunk a pass (:meth:`_prefill_next_chunk`),
        oldest request first, while the other slots decode."""
        admitted = 0
        while True:
            req = self._pop_live(closed)
            if req is None:
                return admitted
            slot = self._reserve(req)
            if slot is None:
                return admitted
            with self._mu:
                req.prefill_left = int(req.prompt.size)
                self._slots[slot] = req
            self._prefilling.append(req)
            admitted += 1

    def _prefill_next_chunk(self) -> None:
        """The next chunk of the oldest prompt still being prefilled:
        ONE chunk between two decode steps, so a decoding slot waits a
        step and a chunk at most. A chunk that is not its prompt's last
        is dispatched and not waited for; the last one's logits are
        fetched and its request's first token sampled. A failure fails
        that request alone."""
        while self._prefilling and self._prefilling[0].future.done():
            self._prefilling.popleft()  # expired or failed since admitted
        if not self._prefilling:
            return
        req = self._prefilling[0]
        at = int(req.prompt.size) - req.prefill_left
        n = min(self.decoder.prefill_chunk, req.prefill_left)
        last = n == req.prefill_left
        try:
            with self._phase("chunk", "prefill", request_id=req.request_id,
                             offset=at, tokens=n, last=int(last)) as ph:
                logits = _DECODE_RETRY.call(self.decoder.prefill_chunk_at,
                                            req.prompt, req.table, at)
        except Exception as e:  # noqa: BLE001 — fail THIS request only
            self._prefilling.popleft()
            self._fail_prefill([req], e)
            return
        self._clock.count_chunk(n, *self.decoder.pool.chunk_keys(at, n),
                                last=last)
        self.decoder.pool.count_chunk(at, n)
        with self._mu:
            self._prefill_dispatches += 1
            if last:
                self._prefill_prompts += 1
                req.t_prefill_done = ph.t1
                req.seq_len = int(req.prompt.size)
                req.rng = np.random.default_rng(req.seed)
                self._observe_lat("prefill", ph.t1 - req.t_admit)
            req.prefill_left -= n
        if not last:
            return
        self._prefilling.popleft()
        metrics_registry().histogram("serving.prefill_s").observe(
            ph.t1 - req.t_admit)
        with self._phase("sample", tokens=1):
            self._append_token(req, logits)

    def _admit_whole(self, closed: bool) -> int:
        """Admission where prompts are prefilled whole: each request
        that finds a free slot and its worst case in the pool joins the
        group of its prefill bucket, and a group is ONE dispatch
        (:meth:`_prefill_group`), sent when it is full and, for what is
        left, at the end of the pass in bucket order. A group holds
        ``prefill_token_budget // bucket`` prompts, at least one: without
        a budget every prompt is a group, prefilled before the next is
        looked at. What a pass may admit while a slot decodes bounds the
        stall a burst of prompts causes: ``max_prefills_per_step``
        prompts prefilled or, under a budget, the budget in padded
        tokens (and the pass's first prompt whatever its bucket).
        Returns how many prompts it prefilled."""
        with self._mu:
            active = any(r is not None for r in self._slots)
            n_slots = len(self._slots)
        budget = self.prefill_token_budget
        bound = (self.max_prefills_per_step if active and not budget
                 else n_slots)
        groups: Dict[int, List] = {}  # bucket: [(slot, request)] not yet sent
        admitted = spent = 0
        while admitted < bound:
            req = self._pop_live(closed)
            if req is None:
                break
            bucket = self.decoder.bucket_for(req.prompt.size)
            if budget and active and spent and spent + bucket > budget:
                with self._mu:
                    self._queue.appendleft(req)
                break
            slot = self._reserve(
                req, {slot for g in groups.values() for slot, _ in g})
            if slot is None:
                break
            spent += bucket
            group = groups.setdefault(bucket, [])
            group.append((slot, req))
            if len(group) == max(1, budget // bucket):
                admitted += self._prefill_group(groups.pop(bucket), bucket)
        for bucket in sorted(groups):
            admitted += self._prefill_group(groups[bucket], bucket)
        return admitted

    def _observe_lat(self, phase: str, seconds: float) -> None:
        """One sample of a request phase; the caller holds ``_mu``."""
        self._lat[phase].append(seconds)
        total = self._lat_total[phase]
        total[0] += 1  # hotpath: lock-ok (the caller holds _mu)
        total[1] += seconds  # hotpath: lock-ok (the caller holds _mu)

    def _prefill_group(self, members: List, bucket: int) -> int:
        """ONE prefill dispatch for the same-bucket requests ``members``
        ([(slot, request)], their tables reserved), each one's first
        token sampled and the request seated in its slot (unless that
        token was its last). A dispatch failure fails exactly the
        group's requests. Returns how many it prefilled."""
        reqs = [r for _, r in members]
        prompts = [r.prompt for r in reqs]
        tables = [r.table for r in reqs]
        try:
            with self._phase(
                    "prefill", bucket=bucket,
                    request_ids=",".join(str(r.request_id) for r in reqs),
                    tokens=sum(int(r.prompt.size) for r in reqs)) as ph:
                logits = _DECODE_RETRY.call(self.decoder.prefill_many,
                                            prompts, tables)
                if self.draft is not None:
                    # prime the draft's arenas through the SAME block
                    # tables (its prefill logits are unused — the first
                    # generated token is sampled from the target,
                    # exactly like non-speculative serving)
                    _DECODE_RETRY.call(self.draft.prefill_many, prompts,
                                       tables)
        except Exception as e:  # noqa: BLE001 — fail the group only
            self._fail_prefill(reqs, e)
            return 0
        t0, t_done = ph.t0, ph.t1
        with self._mu:
            self._prefill_dispatches += 1
            self._prefill_prompts += len(reqs)
            for req in reqs:
                req.t_prefill_done = t_done
                req.seq_len = req.prompt.size
                req.rng = np.random.default_rng(req.seed)
                self._observe_lat("prefill", t_done - t0)
        metrics_registry().histogram("serving.prefill_s").observe(
            t_done - t0)
        with self._phase("sample", tokens=len(members)):
            for i, (slot, req) in enumerate(members):
                self._append_token(req, logits[i])
                if req.future.done():  # single-token request retired here
                    continue
                with self._mu:
                    self._slots[slot] = req
        return len(members)

    def _fail_prefill(self, reqs, e: Exception) -> None:
        """A prefill dispatch failed: fail exactly its requests, their
        blocks freed and their slots (a prompt prefilled in chunks sits
        in its slot from admission) emptied."""
        metrics_registry().counter("serving.errors").inc()
        for req in reqs:
            with self._mu:
                for i, r in enumerate(self._slots):
                    if r is req:
                        self._slots[i] = None
            self.decoder.pool.free(req.table)
            if not req.future.done():
                req.future.set_exception(e)

    # ---- decode ------------------------------------------------------------
    def _step_inputs(self):
        """What a decode step or speculative round starts from: the
        slots it carries, with each one's last token, block table and
        cached length in slot-width arrays; None where it would carry
        none. A live slot is left out where the token it has in flight
        is the last its request may have: the row would be computed for
        nobody."""
        reg = metrics_registry()
        with self._phase("inputs") as ph:
            now = ph.t0
            with self._mu:
                slots = list(self._slots)
            # deadline gate: expired in-flight requests are rejected
            # BEFORE their next decode step (their remaining tokens would
            # be served to nobody); their blocks free immediately
            expired = set()
            for i, req in enumerate(slots):
                if req is not None and req.expired(now):
                    expired.add(i)
                    with self._mu:
                        self._slots[i] = None
                        self._deadline_rejects += 1
                    reg.counter("serving.deadline_rejects").inc()
                    self.decoder.pool.free(req.table)
                    if not req.future.done():
                        req.future.set_exception(DeadlineExceeded(
                            f"request {req.request_id} exceeded its "
                            f"deadline {req.deadline_s:.3f}s mid-decode "
                            f"({len(req.tokens)}/{req.max_new_tokens} "
                            f"tokens)"))
            active = [(i, r) for i, r in enumerate(slots)
                      if r is not None and i not in expired
                      and not r.prefill_left
                      and len(r.tokens) + r.in_flight < r.max_new_tokens]
            if not active:
                return None
            n_slots = len(slots)
            tokens = np.zeros(n_slots, np.int32)
            tables = np.zeros((n_slots, active[0][1].table.size), np.int32)
            seq_lens = np.zeros(n_slots, np.int32)
            with self._mu:
                for i, req in active:
                    tokens[i] = req.tokens[-1]
                    tables[i] = req.table
                    seq_lens[i] = req.seq_len
                    if req.decode_t0 is None:
                        req.decode_t0 = now
            self.decoder.pool.count_step(seq_lens[[i for i, _ in active]])
        return active, tokens, tables, seq_lens

    def _dispatch(self, fn, *args):
        """One jitted call and, where ``fn`` waits for them, the fetch
        of its logits, on the clock: ``dispatch`` until the call
        returns, ``fetch`` (entered by :meth:`_on_dispatched`, between
        the decoder's two spans) until the logits are on the host.
        Returns what ``fn`` returns and the two ends."""
        t0, outer = self._clock.enter("dispatch")
        try:
            out = _DECODE_RETRY.call(fn, *args)
        finally:
            t1, _ = self._clock.enter(outer)
        return out, t0, t1

    def _on_dispatched(self) -> None:
        """A decoder's jitted call has returned and its fetch begins. In
        a prefill the clock stays in ``prefill``, which covers both."""
        if self._clock.phase == "dispatch":
            self._clock.enter("fetch")

    def _fail_step(self, active, e: Exception) -> None:
        """A step failed, at its dispatch or (a step run ahead) at the
        fetch of its ids a dispatch later: fail its requests and those
        of every step in flight, each once, free their blocks, and
        count ONE failure towards the breaker. A row whose request has
        already ended (its slot holds another, or nothing) has nothing
        left to fail."""
        reg = metrics_registry()
        reg.counter("serving.errors").inc()
        rows = dict(active)
        for step in self._in_flight:
            rows.update(step.rows)
        self._in_flight.clear()
        for i, req in rows.items():
            with self._mu:
                if self._slots[i] is not req:
                    continue
                self._slots[i] = None
            self.decoder.pool.free(req.table)
            if not req.future.done():
                req.future.set_exception(e)
        if self.breaker_threshold:
            with self._mu:
                self._consec_failures += 1
                # transition-only (==): repeated failures behind an
                # open breaker must not re-extend the cooldown
                opened = (self._consec_failures
                          == self.breaker_threshold)
                if opened:
                    self._breaker_open_until = (
                        time.monotonic() + self.breaker_cooldown_s)
            if opened:
                reg.counter("serving.breaker_opens").inc()

    def _decode_once(self):
        """One pass's decode work for the live slots. Returns
        ``(stepped, ahead)``: whether a step (or speculative round) was
        dispatched and served, and whether it was dispatched while the
        step before it was still unread.

        Where every live slot is greedy the pass **runs one step
        ahead**: it builds step n+1's inputs, dispatches it through
        :meth:`PagedDecoder.decode_ahead` (a slot that was in step n
        takes its token from n's ids on the device; a slot admitted
        since, from the host), and only then reads step n's ids,
        commits them and retires, so the wait for the chip falls while
        the chip already holds its next program. What n+1 needs without
        n's tokens the host has: ``seq_len`` (advanced at dispatch),
        the block table, and whether n's token is the request's last
        (:meth:`_step_inputs` leaves the slot out). An ``eos_id`` hit it
        learns one step late: the row that n+1 computed for the slot is
        dropped when it arrives, and its write went to a row of the
        request's own blocks, behind every later owner's writes in the
        device's order and masked by ``seq_len`` until overwritten (the
        argument :meth:`_spec_once` makes for rejected suffixes).

        A sampled slot (``temperature > 0``) or speculation makes the
        pass the synchronous one: read what is in flight, dispatch,
        wait for the logits, :func:`sample_next_token` from the
        request's own stream. The choice is read from the slots, pass
        by pass; both ways run the one decode executable."""
        if self.spec_k > 0 and self.draft is not None:
            stepped = self._spec_once()
            if stepped:
                self._clock.count("steps_sync")
            return stepped, False
        with self._mu:
            greedy = all(r is None or r.temperature == 0
                         for r in self._slots)
        if not greedy:
            self._read_steps(keep=0)  # this step starts from their tokens
        inputs = self._step_inputs()
        if inputs is None:
            self._read_steps(keep=0)
            return False, False
        active, tokens, tables, seq_lens = inputs
        if not greedy:
            return self._decode_sync(active, tokens, tables, seq_lens), False
        take_prev = np.zeros(len(tokens), bool)
        for i, req in active:
            take_prev[i] = req.in_flight > 0  # hotpath: lock-ok (pass-local array)
        try:
            ids, t0, _ = self._dispatch(self.decoder.decode_ahead, tokens,
                                        tables, seq_lens, take_prev)
        except Exception as e:  # noqa: BLE001 — fail the steps' requests
            self._fail_step(active, e)
            return False, False
        ahead = bool(self._in_flight)
        with self._mu:
            for _, req in active:
                req.seq_len += 1
                req.in_flight += 1
        self._in_flight.append(_Step(active, ids, t0))
        if ahead:
            self._clock.count("steps_ahead")
        self._read_steps(keep=1)
        return True, ahead

    def _read_steps(self, keep: int) -> None:
        """Read the steps in flight, oldest first, until ``keep`` are
        left: wait for a step's ids (``fetch``: 4 bytes a slot), commit
        each row's token to its request and retire what it finishes
        (``sample``). A row whose request has ended since the dispatch
        (an ``eos_id`` hit, a deadline, a failure) is dropped."""
        while len(self._in_flight) > keep:
            step = self._in_flight[0]
            try:
                with self._phase("fetch", bytes=step.ids.nbytes) as ph:
                    ids = np.asarray(step.ids)
            except Exception as e:  # noqa: BLE001 — fail the steps' requests
                self._fail_step((), e)
                return
            self._in_flight.popleft()
            metrics_registry().histogram("serving.decode_step_s").observe(
                ph.t1 - step.t0)
            dropped = 0
            with self._phase("sample", tokens=len(step.rows)):
                for i, req in step.rows:
                    with self._mu:
                        live = self._slots[i] is req
                        if live:
                            req.in_flight -= 1
                            req.decode_steps += 1
                    if live:
                        self._commit_token(req, int(ids[i]))
                    else:
                        dropped += 1
                if self.breaker_threshold:
                    with self._mu:  # a served step closes the failure streak
                        self._consec_failures = 0
            if dropped:
                self._clock.count("rows_dropped", dropped)

    def _decode_sync(self, active, tokens, tables, seq_lens) -> bool:
        """The synchronous step: dispatch, wait for the logits, sample
        each row on the host. True where the step was served."""
        try:
            logits, t0, t1 = self._dispatch(self.decoder.decode, tokens,
                                            tables, seq_lens)
        except Exception as e:  # noqa: BLE001 — fail the step's requests
            self._fail_step(active, e)
            return False
        self._clock.count("steps_sync")
        metrics_registry().histogram("serving.decode_step_s").observe(
            t1 - t0)
        with self._phase("sample", tokens=len(active)):
            for i, req in active:
                with self._mu:
                    req.seq_len += 1
                    req.decode_steps += 1
                self._append_token(req, logits[i])
            if self.breaker_threshold:
                with self._mu:  # a served step closes the failure streak
                    self._consec_failures = 0
        return True

    def _spec_once(self) -> bool:
        """One speculative round: ``spec_k`` draft proposals per live
        slot (k+1 draft dispatches — the extra one writes the last
        proposal's K/V so the draft cache stays position-complete for
        the next round), then ONE target verify dispatch over the
        (k+1)-token window. The verify IS the step's decode dispatch,
        so the one-decode-dispatch-per-step invariant holds with
        speculation on.

        Commit rule per slot, walking the verify rows in order (row j
        is the target's distribution AFTER window position j):

        * greedy — commit the target's argmax; a proposal that matches
          it keeps the walk going (its K/V is already cached at the
          right position), the first mismatch commits the target's
          correction and rolls the cursor back by simple ``seq_len``
          arithmetic (stale suffix rows stay masked by position and are
          overwritten next round). Token-for-token the target's own
          argmax chain — identical to non-speculative decoding.
        * temperature — standard rejection sampling: accept proposal d
          with prob min(1, p(d)/q(d)); on reject, sample the correction
          from normalize(max(p-q, 0)). All draws come from the
          request's own seeded stream in a fixed order (k proposal
          draws, then the acceptance draws), so runs replay.
        * full match — one bonus token from the last verify row, the
          (k+1)-th emission of the round.

        Rejected suffixes never touch other slots: acceptance is pure
        per-row host bookkeeping over the shared dispatch."""
        reg = metrics_registry()
        inputs = self._step_inputs()
        if inputs is None:
            return False
        active, base_tokens, tables, seq_lens = inputs
        k = self.spec_k
        n_slots = len(base_tokens)
        t0 = None  # the round's first dispatch
        proposals = np.zeros((n_slots, k), np.int32)
        qdists: List[Optional[List[np.ndarray]]] = [None] * n_slots
        try:
            cur = base_tokens.copy()
            lens = seq_lens.copy()
            for j in range(k + 1):
                dlogits, tj, _ = self._dispatch(self.draft.decode, cur,
                                                tables, lens)
                t0 = tj if t0 is None else t0
                lens = lens + 1
                if j == k:
                    break  # cache-sync dispatch: writes d_k, logits unused
                nxt = np.zeros(n_slots, np.int32)
                with self._phase("sample", tokens=len(active)):
                    for i, req in active:
                        if req.temperature > 0:
                            q = _temp_softmax(dlogits[i], req.temperature)
                            if qdists[i] is None:
                                qdists[i] = []  # hotpath: lock-ok (round-local list, never shared)
                            qdists[i].append(q)
                            nxt[i] = int(req.rng.choice(q.shape[-1], p=q))  # hotpath: lock-ok (round-local array)
                        else:
                            nxt[i] = int(dlogits[i].argmax(-1))  # hotpath: lock-ok (round-local array)
                    proposals[:, j] = nxt  # hotpath: lock-ok (round-local array)
                cur = nxt
            window = np.zeros((n_slots, k + 1), np.int32)
            window[:, 0] = base_tokens  # hotpath: lock-ok (round-local array)
            window[:, 1:] = proposals  # hotpath: lock-ok (round-local array)
            vlogits, _, t1 = self._dispatch(self.decoder.verify, window,
                                            tables, seq_lens)
        except Exception as e:  # noqa: BLE001 — fail the step's requests
            self._fail_step(active, e)
            return False
        reg.histogram("serving.decode_step_s").observe(t1 - t0)
        with self._phase("sample", tokens=len(active)):
            for i, req in active:
                matched = 0
                emitted = 0
                done = False
                accepted = True
                for j in range(k):
                    row = np.asarray(vlogits[i, j])
                    d = int(proposals[i, j])
                    if req.temperature > 0:
                        p = _temp_softmax(row, req.temperature)
                        q = qdists[i][j]
                        u = req.rng.uniform()
                        if q[d] > 0 and u < min(
                                1.0, float(p[d]) / float(q[d])):
                            tok = d
                            accepted = True
                        else:
                            resid = np.maximum(p - q, 0.0)
                            tot = resid.sum()
                            tok = (int(req.rng.choice(
                                       resid.shape[-1], p=resid / tot))
                                   if tot > 0 else
                                   int(req.rng.choice(p.shape[-1], p=p)))
                            accepted = False
                    else:
                        tok = int(row.argmax(-1))
                        accepted = tok == d
                    emitted += 1
                    done = self._commit_token(req, tok, advance_seq=True)
                    if done or not accepted:
                        break
                    matched += 1
                if accepted and not done and matched == k:
                    # every proposal accepted: the bonus token rides the
                    # last verify row for free
                    tok = sample_next_token(np.asarray(vlogits[i, k]),
                                            req.temperature, req.rng)
                    emitted += 1
                    self._commit_token(req, tok, advance_seq=True)
                with self._mu:
                    req.decode_steps += 1
                    self._spec_slot_rounds += 1
                    self._spec_proposed += k
                    self._spec_matched += matched
                    self._spec_emitted += emitted
                reg.histogram("serving.spec_accept_rate").observe(matched / k)
                reg.histogram("serving.spec_tokens_per_dispatch").observe(
                    emitted)
            with self._mu:  # one verify dispatch served this whole round
                self._spec_rounds += 1
            if self.breaker_threshold:
                with self._mu:  # a served step closes the failure streak
                    self._consec_failures = 0
        return True

    def _append_token(self, req: GenerationRequest, row_logits) -> None:
        """Sample the next token for one request (mask-aware: only
        called for live requests) and retire it when finished."""
        tok = sample_next_token(np.asarray(row_logits), req.temperature,
                                req.rng)
        self._commit_token(req, tok)

    def _commit_token(self, req: GenerationRequest, tok: int,
                      advance_seq: bool = False) -> bool:
        """Record one committed token for a live request and retire it
        when finished. ``advance_seq`` bumps ``seq_len`` atomically
        with the append (the speculative path: each commit means the
        previous token's K/V is now validly cached); the plain decode
        path advances ``seq_len`` per dispatch instead. Returns True
        when the request retired."""
        now = time.perf_counter()
        ttft = None
        with self._mu:
            if advance_seq:
                req.seq_len += 1
            req.tokens.append(int(tok))
            if req.t_first_token is None:
                req.t_first_token = now
                ttft = now - req.t_enqueue
                self._observe_lat("ttft", ttft)
            self._tokens_total += 1
            total = self._tokens_total
            t_start = self._t_first_activity
            gap = (None if req.t_last_token is None
                   else now - req.t_last_token)
            req.t_last_token = now
        if ttft is not None:
            metrics_registry().histogram("serving.ttft_s").observe(ttft)
        if gap is not None:
            # every token after a request's first. A speculative round
            # commits its tokens at one instant: the round's gap once,
            # then gaps of microseconds, which land in the lowest bucket
            self._clock.token_gap.observe(gap)
        if t_start is not None and now > t_start:
            metrics_registry().gauge("serving.tokens_per_s").set(
                total / (now - t_start))
        done = (len(req.tokens) >= req.max_new_tokens
                or (req.eos_id is not None and tok == req.eos_id))
        if done:
            self._retire(req, now)
        return done

    def _retire(self, req: GenerationRequest, now: float) -> None:
        reg = metrics_registry()
        self.decoder.pool.free(req.table)
        with self._mu:
            for i, r in enumerate(self._slots):
                if r is req:
                    self._slots[i] = None
            self._completed += 1
        out = np.concatenate(
            [req.prompt, np.asarray(req.tokens, np.int32)])
        n = len(req.tokens)
        e2e = now - req.t_enqueue
        with self._mu:  # stats() snapshots these under the same lock
            self._observe_lat("e2e", e2e)
            self._observe_lat("per_token", e2e / n)
            if req.decode_t0 is not None:
                self._observe_lat("decode", now - req.decode_t0)
        reg.histogram("serving.gen_e2e_s").observe(e2e)
        reg.histogram("serving.per_token_s").observe(e2e / n)
        reg.counter("serving.batches").inc()
        self._record_request_spans(req, now)
        req.future.set_result(out)
        # publish AFTER the future resolves and after the pass's other
        # slots have their tokens (the loop does it at the end of the
        # pass: telemetry must not ride the client-visible latency), and
        # throttled: the first retirement arms the /attribution surface,
        # then every _PUBLISH_EVERY-th refreshes it; stop() publishes
        # the final table either way — eventual freshness
        with self._mu:
            completed = self._completed
        if completed % _PUBLISH_EVERY == 1:
            self._publish_due = True  # hotpath: lock-ok (flag of the loop thread alone)

    # ---- observability -----------------------------------------------------
    def _record_request_spans(self, req: GenerationRequest,
                              t_end: float) -> None:
        """request ⊃ queue_wait → prefill → decode×n → reply, each
        request on its own virtual track (the classic engine's span-tree
        contract, with the decode phase annotated by its step count)."""
        tr = tracer()
        if not tr.enabled:
            return
        tid = _GEN_TID_BASE + req.request_id
        args = {"model": self.name, "request_id": req.request_id,
                "tokens": len(req.tokens)}
        tr.complete("serving.request", req.t_enqueue,
                    t_end - req.t_enqueue, cat="serving", tid=tid,
                    args=args)
        tr.complete("serving.queue_wait", req.t_enqueue,
                    req.t_admit - req.t_enqueue, cat="serving", tid=tid)
        if req.t_prefill_done is not None:
            tr.complete("serving.prefill", req.t_admit,
                        req.t_prefill_done - req.t_admit, cat="serving",
                        tid=tid)
        if req.decode_t0 is not None:
            tr.complete("serving.decode", req.decode_t0,
                        t_end - req.decode_t0, cat="serving", tid=tid,
                        args={"steps": req.decode_steps})
        tr.complete("serving.reply", t_end, 0.0, cat="serving", tid=tid)

    def stats(self) -> Dict:
        """Live session snapshot: phases, pool occupancy, throughput —
        the ledger record's body and /healthz's serving block."""
        with self._mu:
            queued = len(self._queue)
            active = sum(1 for r in self._slots if r is not None)
            tokens = self._tokens_total
            t_start = self._t_first_activity
            shed = self._shed
            deadline = self._deadline_rejects
            completed = self._completed
            prefill_dispatches = self._prefill_dispatches
            prefill_prompts = self._prefill_prompts
            # the window's percentiles ("count" is the window's length,
            # at most _PHASE_WINDOW) beside the session's true totals
            phases = {k: _percentiles(v) for k, v in self._lat.items()}
            for k, (n, sum_s) in self._lat_total.items():
                if phases[k] is not None:
                    phases[k].update(n=n, sum_s=sum_s)
            spec_rounds = self._spec_rounds
            spec_slot_rounds = self._spec_slot_rounds
            spec_proposed = self._spec_proposed
            spec_matched = self._spec_matched
            spec_emitted = self._spec_emitted
            lengths = [r.seq_len for r in self._slots if r is not None]
        now = time.perf_counter()
        tps = (tokens / (now - t_start)
               if t_start is not None and now > t_start else 0.0)
        # the pool's occupancy and its books of what the steps read; the
        # decoder's own words beside them
        kv = self.decoder.pool.stats(lengths)
        kv["attention_path"] = dict(self.decoder.attention_path)
        kv["attention_path_by_entry"] = {
            k: dict(v) for k, v in
            self.decoder.attention_path_by_entry.items()}
        if "state" in kv:
            kv["state"]["prefill_path"] = self.decoder.prefill_path
        if self.decoder.kv_divergence is not None:
            kv["divergence"] = self.decoder.kv_divergence
            kv["quant_fallback"] = self.decoder.kv_quant_report is not None
        moe = self.decoder.expert_stats()
        return {
            **({"moe": moe} if moe else {}),
            "serving_engine": "continuous",
            "model": self.name,
            "queued": queued,
            "active": active,
            "completed": completed,
            "tokens": tokens,
            "tokens_per_s": round(tps, 3),
            "shed": shed,
            "deadline_rejects": deadline,
            "phases": phases,
            "loop": self._clock.snapshot(),
            "kv": kv,
            "decode_steps": self.decoder.decode_steps,
            "decode_dispatches": self.decoder.decode_dispatches,
            "prefill_dispatches": prefill_dispatches,
            "prefill_prompts": prefill_prompts,
            "prefill_buckets": list(self.decoder.prefill_buckets),
            **({"spec": {
                "k": self.spec_k,
                # rounds = verify dispatches; slot_rounds = per-slot
                # acceptance walks (rounds x live slots at the time)
                "rounds": spec_rounds,
                "slot_rounds": spec_slot_rounds,
                "proposed": spec_proposed,
                "matched": spec_matched,
                "emitted": spec_emitted,
                "accept_rate": (round(spec_matched / spec_proposed, 4)
                                if spec_proposed else 0.0),
                # mean tokens ONE slot retires per verify dispatch
                # (1..k+1 — the speculative multiplier)
                "tokens_per_dispatch": (
                    round(spec_emitted / spec_slot_rounds, 3)
                    if spec_slot_rounds else 0.0),
                "draft_dispatches": self.draft.decode_dispatches,
            }} if self.spec_k > 0 and self.draft is not None else {}),
            "knobs": {
                "decode_slots": self.decoder.decode_slots,
                "block_size": self.decoder.block_size,
                "num_blocks": self.decoder.pool.num_blocks,
                "max_length": self.decoder.max_length,
                "max_prefills_per_step": self.max_prefills_per_step,
                **({"prefill_chunk": self.decoder.prefill_chunk}
                   if self.decoder.prefill_chunk else {}),
                **({"prefill_token_budget": self.prefill_token_budget}
                   if self.prefill_token_budget > 0 else {}),
                **({"spec_k": self.spec_k} if self.spec_k > 0 else {}),
                **({"kv_dtype": self.decoder.kv_dtype}
                   if self.decoder.kv_dtype != "float32" else {}),
            },
        }

    def _publish_attribution(self) -> None:
        """Serving attribution parity: keep the obs server's
        ``/attribution`` surface current for this session (fit runs
        publish their phase table from the fit tail; continuous
        sessions publish queue_wait/prefill/decode here — on the first
        retirement, every ``_PUBLISH_EVERY`` after, and at session
        end — so a serving-only process never 404s)."""
        try:
            from ..obs.attribution import serving_attribution
            from ..obs.server import publish_attribution

            rec = serving_attribution(self.stats())
            if rec is not None:
                publish_attribution(rec, kind="serving")
        except Exception:  # noqa: BLE001 — telemetry never fails serving
            metrics_registry().counter("serving.obs_errors").inc()

    def _record_session(self) -> None:
        """One serving ledger record per scheduler session (stop())."""
        from ..obs.ledger import model_context, record_serving

        extra = self.stats()
        try:
            ctx = model_context(self._ff)
            if ctx.get("model_sig"):
                extra["model_sig"] = ctx["model_sig"]
        except Exception:  # noqa: BLE001 — telemetry never kills stop
            pass
        self._publish_attribution()
        # close the advisor loop for serving-only processes: the
        # session's phase table is an advisable record — publish the
        # ranked knob deltas on /advice next to the phase table
        try:
            from ..obs.advisor import advise_record
            from ..obs.server import publish_advice

            report = advise_record(dict(extra))
            if report is not None:
                publish_advice(report)
        except Exception:  # noqa: BLE001 — advice never kills stop
            metrics_registry().counter("advisor.errors").inc()
        record_serving(extra, config=self._ff.config)


def _position_capacity(ff) -> int:
    """Default ``max_length``: the position-embedding table's capacity
    (the model's own hard decoding bound)."""
    from ..ffconst import OpType

    cm = ff.compiled
    if cm is None:
        raise ValueError("compile() the model before serving it")
    if len(cm.input_tensors) >= 2:
        pos_tid = cm.input_tensors[1].tensor_id
        for op in cm.ops:
            if (op.op_type is OpType.EMBEDDING
                    and op.layer.inputs[0].tensor_id == pos_tid):
                return int(op.attrs["num_entries"])
    raise ValueError(
        "cannot infer max_length: no position-embedding op found — pass "
        "max_length explicitly")


__all__ = ["ContinuousBatchingScheduler", "GenerationRequest"]
