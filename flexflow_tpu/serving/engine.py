"""Inference engine: model instances + dynamic micro-batching.

reference: the Triton backend prototype's model lifecycle + request
scheduling (/root/reference/triton/src/backend.cc — TRITONBACKEND_Model*
lifecycle hooks; instance.cc — per-instance execution; strategies loaded
per model). TPU re-design decisions:

* an *instance* is one compiled inference executable over one device mesh
  (the jit cache plays Triton's model-warmup role; the GSPMD partitioner
  plays its instance-group placement);
* *dynamic batching* pads the gathered requests to the instance's compiled
  batch size — XLA needs static shapes, so the batcher trades a bounded
  wait (`batch_timeout_s`) for MXU-efficient full batches;
* the queue discipline is native C++ (native/src/batcher.cc) with a pure
  Python fallback, mirroring the framework's native-with-fallback pattern.

Graceful degradation (the fault-tolerance layer's serving half): under
overload or failure the engine **sheds, rejects fast, and respawns**
instead of queue-collapsing —

* a bounded admission queue (``admission_limit``): requests past the
  bound raise :class:`ShedError` immediately (counted on
  ``serving.shed``) instead of growing an unbounded backlog;
* per-request deadlines (``deadline_s``, engine default
  ``default_deadline_s``): a request whose deadline passed before a
  worker picked it up resolves its future with
  :class:`DeadlineExceeded` right away (``serving.deadline_rejects``)
  instead of burning an MXU batch on an answer nobody is waiting for;
* crashed batcher-workers respawn under ``worker_retry_budget``
  (``serving.worker_respawns``), re-queuing any in-hand batch first so
  every accepted future still resolves;
* a failure breaker: ``breaker_threshold`` consecutive batch failures
  open the breaker for ``breaker_cooldown_s`` — new requests shed
  (``serving.breaker_shed``) while the backend is presumed down, then
  the breaker closes and traffic resumes;
* the dispatch into the compiled executable retries transient failures
  through the shared backoff policy (runtime/retry.py).
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from concurrent.futures import Future
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..obs.metrics import metrics_registry
from ..obs.trace import VIRTUAL_TID_BASE, span, tracer
from ..obs.watchdog import watch as _wd_watch
from ..runtime.faults import InjectedFault, TransientFault
from ..runtime.faults import fire as _fault_fire
from ..runtime.faults import inject as _fault_inject
from ..runtime.retry import RetryPolicy

# transient dispatch failures (incl. the device_put.transient fault
# site inside ModelInstance.infer) back off briefly before the batch is
# failed; a persistent error still surfaces per-request
_DISPATCH_RETRY = RetryPolicy(max_attempts=3, base_delay_s=0.002,
                              max_delay_s=0.02,
                              retry_on=(TransientFault,),
                              label="serving_dispatch", seed=0)

# degradation errors live in serving/errors.py (shared with the paged
# KV pool + continuous scheduler); re-exported here for back-compat
from .errors import DeadlineExceeded, ShedError  # noqa: E402


class _PyBatcher:
    """Pure-Python fallback with NativeBatcher's exact semantics."""

    def __init__(self, max_batch: int, timeout_s: float):
        self.max_batch = int(max_batch)
        self._timeout = float(timeout_s)
        self._q: collections.deque = collections.deque()  # (id, t_enqueued)
        self._mu = threading.Condition()
        self._closed = False

    def submit(self, request_id: int) -> None:
        with self._mu:
            if self._closed:
                # a request appended after close() would never be drained
                # (the workers exit once the queue empties) — fail fast so
                # the engine can re-submit to the re-armed batcher
                raise RuntimeError("batcher is closed")
            self._q.append((request_id, time.monotonic()))
            self._mu.notify_all()

    def pending(self) -> int:
        with self._mu:
            return len(self._q)

    def next_batch(self) -> Optional[List[int]]:
        with self._mu:
            while True:
                if self._q:
                    deadline = self._q[0][1] + self._timeout
                    now = time.monotonic()
                    if (len(self._q) >= self.max_batch or self._closed
                            or now >= deadline):
                        ids = []
                        while self._q and len(ids) < self.max_batch:
                            ids.append(self._q.popleft()[0])
                        return ids
                    self._mu.wait(deadline - now)
                else:
                    if self._closed:
                        return None
                    self._mu.wait()

    def close(self) -> None:
        with self._mu:
            self._closed = True
            self._mu.notify_all()

    def destroy(self) -> None:
        pass


def _make_batcher(max_batch: int, timeout_s: float):
    from .. import native_bridge

    try:
        return native_bridge.NativeBatcher(max_batch, timeout_s)
    except Exception:
        return _PyBatcher(max_batch, timeout_s)


class ModelInstance:
    """One compiled inference executable (reference: triton/src/instance.cc
    ModelInstance — per-device execution state for a loaded model).

    Wraps a compiled :class:`flexflow_tpu.FFModel`: requests of any count
    ≤ the compiled batch size are padded up and run through the jitted
    forward; rows beyond the request count are discarded.
    """

    def __init__(self, ff, name: str = "model"):
        if ff.compiled is None:
            raise ValueError("compile() the FFModel before serving it")
        # a serving-only process never runs fit()/eval(), so the served
        # model's config must arm the stall monitor here or the worker
        # watch sections would be permanent no-ops — and likewise the
        # scrape/health surface (config.obs_server_port), which ROADMAP
        # item 1's SLO-aware serving scrapes for /metrics + /healthz
        from ..obs.server import configure_obs_server
        from ..obs.watchdog import configure_watchdog
        from ..runtime.faults import configure_faults

        configure_watchdog(ff.config)
        configure_obs_server(ff.config)
        configure_faults(ff.config)  # serving-only chaos arms here
        self.name = name
        self._ff = ff
        cm = ff.compiled
        self._cm = cm
        self.batch_size = cm.input_tensors[0].dims[0]
        self.n_inputs = len(cm.input_tensors)

    @property
    def devices(self) -> frozenset:
        """The device set this instance executes on (reference:
        instance.cc's per-instance device binding) — disjointness across
        instances is the placement invariant."""
        mesh = self._cm.mesh
        if mesh is None:
            return frozenset()
        return frozenset(mesh.devices.flat)

    @classmethod
    def from_onnx(cls, onnx_path: str, config=None, name: str = "model",
                  mesh=None):
        """Load + compile an ONNX graph for inference (reference: the
        Triton backend's own ONNX parser, triton/src/onnx_parser.cc — here
        the framework's single ONNX frontend serves both paths)."""
        from ..config import FFConfig
        from ..ffconst import CompMode
        from ..onnx_frontend import ONNXModel
        from ..runtime.model import FFModel

        import dataclasses as _dc

        config = config or FFConfig(computation_mode=CompMode.INFERENCE)
        # structural rewrites replace builder layers, which would orphan
        # the recorded initializer weights (and a merged layer has no
        # meaningful weight mapping for imported arrays). Copy, don't
        # mutate the caller's config object.
        config = _dc.replace(config, enable_graph_rewrites=False)
        ff = FFModel(config)
        onnx_model = ONNXModel(onnx_path)
        # bind graph inputs: dynamic/zero batch dims become config.batch_size
        inputs = []
        graph = onnx_model.model.graph
        for gi in graph.input:
            if gi.name in onnx_model.inits:
                continue
            dims = [d.dim_value
                    for d in gi.type.tensor_type.shape.dim]
            dims[0] = dims[0] if dims[0] > 0 else config.batch_size
            if any(d <= 0 for d in dims[1:]):
                raise ValueError(
                    f"ONNX input {gi.name!r} has dynamic non-batch dims "
                    f"{dims}: export with static shapes (XLA needs them)")
            inputs.append(ff.create_tensor(tuple(dims), name=gi.name))
        onnx_model.apply(ff, inputs)
        ff.compile(optimizer=None, loss_type=None, metrics=[], mesh=mesh)
        # bind the exported weights — without this the served model would
        # run on random init (reference: onnx_parser.cc loads initializers)
        onnx_model.copy_weights(ff)
        return cls(ff, name=name)

    def infer(self, inputs: Sequence[np.ndarray]) -> List[np.ndarray]:
        """Run one padded batch. ``inputs``: one array per model input,
        leading dim = request count ≤ batch_size. Returns per-request
        outputs (padding rows stripped)."""
        n = int(inputs[0].shape[0])
        if n > self.batch_size:
            raise ValueError(f"{n} requests > compiled batch {self.batch_size}")
        # fault site: a transient placement/dispatch failure — the
        # engine's retry policy absorbs it (no-op while no plan is armed)
        _fault_inject("device_put.transient", TransientFault)
        padded = []
        for a in inputs:
            a = np.asarray(a)
            if a.shape[0] < self.batch_size:
                pad = np.zeros((self.batch_size - a.shape[0],) + a.shape[1:],
                               a.dtype)
                a = np.concatenate([a, pad], axis=0)
            padded.append(a)
        logits = self._cm.forward_fn(self._cm.params, *padded)
        return [np.asarray(logits)[:n]]


class GenerationInstance:
    """One continuous-batching autoregressive serving instance: a
    compiled causal LM behind a
    :class:`~flexflow_tpu.serving.scheduler.ContinuousBatchingScheduler`
    (paged KV pool, split prefill/decode executables, in-flight
    batching). The generation analog of :class:`ModelInstance` — same
    lifecycle hooks (watchdog / obs server / faults arm here for a
    serving-only process), same degradation machinery (admission bound,
    deadlines, breaker, worker respawn), engine-registered under a name
    like any model.

    Serving knobs default from the model's config
    (``config.serving_*``); keyword arguments override per instance.
    """

    def __init__(self, ff, name: str = "lm", **scheduler_kw):
        if ff.compiled is None:
            raise ValueError("compile() the FFModel before serving it")
        # the whole construction, entered and left by hand
        _t0_build = time.perf_counter()
        _build_span = span("serving.build", cat="serving", instance=name)
        _build_span.__enter__()
        from ..obs.server import configure_obs_server
        from ..obs.watchdog import configure_watchdog
        from ..runtime.faults import configure_faults
        from .scheduler import ContinuousBatchingScheduler

        configure_watchdog(ff.config)
        configure_obs_server(ff.config)
        configure_faults(ff.config)
        cfg = ff.config
        defaults = {
            "decode_slots": getattr(cfg, "serving_decode_slots", 4),
            "block_size": getattr(cfg, "serving_block_size", 16),
            "max_prefills_per_step": getattr(
                cfg, "serving_max_prefills_per_step", 1),
            "prefill_token_budget": getattr(
                cfg, "serving_prefill_token_budget", 0),
            "spec_k": getattr(cfg, "serving_spec_k", 0),
            "kv_dtype": getattr(cfg, "serving_kv_dtype", "float32")
            or "float32",
        }
        budget = getattr(cfg, "serving_kv_divergence_budget", 0.0)
        if budget:
            defaults["kv_divergence_budget"] = float(budget)
        num_blocks = getattr(cfg, "serving_num_blocks", 0)
        if num_blocks:
            defaults["num_blocks"] = int(num_blocks)
        max_length = getattr(cfg, "serving_max_length", 0)
        if max_length:
            defaults["max_length"] = int(max_length)
        buckets = getattr(cfg, "serving_prefill_buckets", None)
        if buckets:
            defaults["prefill_buckets"] = [
                int(x) for x in str(buckets).split(",") if x.strip()]
        defaults.update(scheduler_kw)
        # the draft registers ALONGSIDE the target: an explicit
        # draft_ff keyword wins; otherwise a non-empty
        # serving_draft_model spec ("self:N" / "gpt:...") builds one
        # sharing the target's vocab/position contract. Either path
        # accepts a spec STRING (resolved here) or an already-built
        # model. spec_k without a draft fails loudly in the scheduler.
        if (defaults.get("spec_k", 0) and "draft_ff" not in defaults
                and getattr(cfg, "serving_draft_model", "")):
            defaults["draft_ff"] = str(cfg.serving_draft_model)
        if isinstance(defaults.get("draft_ff"), str):
            from .generation import build_draft_model

            defaults["draft_ff"] = build_draft_model(
                ff, defaults["draft_ff"])
        self.name = name
        self._ff = ff
        self.scheduler = ContinuousBatchingScheduler(ff, name=name,
                                                     **defaults)
        _build_span.__exit__(None, None, None)
        metrics_registry().counter("setup.instance_build_s").inc(
            time.perf_counter() - _t0_build)

    @property
    def decoder(self):
        return self.scheduler.decoder

    def generate_async(self, prompt, max_new_tokens: int, **kw) -> Future:
        return self.scheduler.submit(prompt, max_new_tokens, **kw)

    def generate(self, prompt, max_new_tokens: int,
                 timeout: Optional[float] = 120.0, **kw) -> np.ndarray:
        return self.scheduler.generate(prompt, max_new_tokens,
                                       timeout=timeout, **kw)

    def stats(self) -> Dict:
        return self.scheduler.stats()

    def stop(self) -> None:
        self.scheduler.stop()


class InferenceRequest:
    """A queued request: per-input rows + a Future for the result.
    ``t_enqueue`` anchors the request's span tree (obs/trace.py) and the
    queue-wait latency metric."""

    __slots__ = ("inputs", "future", "request_id", "t_enqueue",
                 "deadline_s")

    def __init__(self, request_id: int, inputs: Sequence[np.ndarray],
                 deadline_s: Optional[float] = None):
        self.request_id = request_id
        self.inputs = [np.asarray(a) for a in inputs]
        self.future: Future = Future()
        self.t_enqueue = time.perf_counter()
        # seconds from enqueue after which the request is rejected fast
        # instead of served late (None = no deadline); t_enqueue is
        # perf_counter-based, the same clock the workers read
        self.deadline_s = deadline_s


class InferenceEngine:
    """Multi-model serving engine (reference: triton/src/backend.cc model
    repository + scheduler; instance.cc instance groups). Each model owns
    one dynamic batcher and N instances on DISJOINT device submeshes
    (serving/placement.py); one worker thread per instance drains the
    shared batcher, so instances of the same model execute concurrently.
    Requests are single samples (leading dim added here) or micro-batches
    of rows.
    """

    def __init__(self, batch_timeout_s: float = 0.005,
                 admission_limit: Optional[int] = None,
                 default_deadline_s: Optional[float] = None,
                 breaker_threshold: int = 0,
                 breaker_cooldown_s: float = 1.0,
                 worker_retry_budget: int = 2):
        self.batch_timeout_s = batch_timeout_s
        # graceful-degradation knobs (module docstring): None/0 = off —
        # the historical accept-everything behavior
        self.admission_limit = (int(admission_limit)
                                if admission_limit else None)
        self.default_deadline_s = (float(default_deadline_s)
                                   if default_deadline_s else None)
        self.breaker_threshold = max(0, int(breaker_threshold))
        self.breaker_cooldown_s = float(breaker_cooldown_s)
        self.worker_retry_budget = max(0, int(worker_retry_budget))
        self._models: Dict[str, List[ModelInstance]] = {}
        self._batchers: Dict[str, object] = {}
        self._requests: Dict[str, Dict[int, InferenceRequest]] = {}
        self._workers: Dict[Tuple[str, int], threading.Thread] = {}
        # continuous-batching generation instances, by name (the
        # GenerationInstance path; each owns its scheduler thread)
        self._generators: Dict[str, GenerationInstance] = {}
        # breaker state, per model (guarded by _mu like the registry):
        # consecutive failed batches + the monotonic instant the open
        # breaker closes again (inf = dead model, sheds until stop())
        self._consec_failures: Dict[str, int] = {}
        self._breaker_open_until: Dict[str, float] = {}
        # worker slots whose respawn budget is exhausted (guarded by
        # _mu); when EVERY slot of a model is abandoned the model is
        # dead — pending futures are failed and admission sheds
        self._abandoned: set = set()
        self._ids = itertools.count()
        self._mu = threading.Lock()
        self._started = False
        # True for the whole close/join/re-arm sequence of stop():
        # _start_locked() no-ops while set, so a racing infer_async/start
        # cannot respawn workers that stop() would then pop and whose
        # batcher it would swap out from under them (requests submitted
        # in the window retry and land in the re-armed batcher; the next
        # infer after stop() spawns the workers that drain them)
        self._stopping = False

    # ---- model repository --------------------------------------------------
    # Locking discipline (checked statically by analysis/concurrency_check:
    # CCY001/CCY006 treat _models/_batchers/_requests/_workers/_started —
    # and the breaker state _consec_failures/_breaker_open_until — as
    # _mu-guarded): every read or write of the registry dicts holds _mu;
    # worker join and batcher close/submit happen OUTSIDE _mu so a blocked
    # thread can never stall the registry (CCY003).
    def register(self, instance: ModelInstance) -> None:
        """Register one instance. Repeated registrations under the same
        name form an instance group — their device sets must be disjoint
        (the placement invariant instance.cc enforces per group)."""
        with self._mu:
            self._register_locked(instance)

    def _register_locked(self, instance: ModelInstance) -> None:
        if instance.name in self._generators:
            raise ValueError(
                f"{instance.name!r} already names a generation instance "
                f"— one name, one model (classic and generation paths "
                f"must never split an identity)")
        group = self._models.get(instance.name)
        if group:
            # full spec check: a different-topology instance silently
            # joining a group would serve a DIFFERENT function for a
            # fraction of requests (whichever worker drains the batch)
            def sig(i):
                cm = i._cm
                # op TYPES + shapes, not names: layer-name counters are
                # process-global, so two builds of the same model differ
                # in names while being the same function
                return (
                    i.batch_size, i.n_inputs,
                    tuple((tuple(t.dims), t.dtype)
                          for t in cm.input_tensors),
                    tuple(cm.logits_tensor.dims),
                    tuple((o.op_type,
                           tuple(tuple(t.dims) for t in o.layer.outputs))
                          for o in cm.ops),
                )

            if sig(instance) != sig(group[0]):
                raise ValueError(
                    f"instance group {instance.name!r} mixes model specs "
                    f"(inputs/outputs/graph must match instance 0)")
            used = frozenset().union(*(i.devices for i in group))
            if instance.devices & used:
                raise ValueError(
                    f"instance of {instance.name!r} overlaps devices "
                    f"already serving that model: "
                    f"{sorted(str(d) for d in instance.devices & used)}")
            group.append(instance)
        else:
            self._models[instance.name] = [instance]
            self._batchers[instance.name] = _make_batcher(
                instance.batch_size, self.batch_timeout_s)
            self._requests[instance.name] = {}
        if self._started:
            self._spawn(instance.name)

    def register_ffmodel(self, ff, name: str = "model") -> ModelInstance:
        inst = ModelInstance(ff, name=name)
        self.register(inst)
        return inst

    def register_onnx(self, onnx_path: str, name: str = "model",
                      config=None, mesh=None) -> ModelInstance:
        inst = ModelInstance.from_onnx(onnx_path, config=config, name=name,
                                       mesh=mesh)
        self.register(inst)
        return inst

    def register_onnx_instances(self, onnx_path: str, name: str,
                                meshes, batch_size=None) -> List[ModelInstance]:
        """N instances of one ONNX model on the given (disjoint) meshes."""
        from ..config import FFConfig
        from ..ffconst import CompMode

        out = []
        for mesh in meshes:
            config = FFConfig(computation_mode=CompMode.INFERENCE)
            if batch_size:
                config.batch_size = int(batch_size)
            out.append(self.register_onnx(onnx_path, name=name,
                                          config=config, mesh=mesh))
        return out

    def register_built_instances(self, build, name: str, meshes,
                                 batch_size: int = 8,
                                 strategies=None) -> List[ModelInstance]:
        """N instances of a builder-defined model, one compile per mesh
        (reference: backend.cc creating `count` ModelInstances per group).
        ``build(ff, batch_size)`` constructs the graph like the examples'
        build functions; ``strategies`` is the per-model strategy dict the
        reference keeps in per-model files."""
        import jax

        from ..config import FFConfig
        from ..ffconst import CompMode
        from ..runtime.model import FFModel

        out = []
        for mesh in meshes:
            ff = FFModel(FFConfig(batch_size=batch_size,
                                  computation_mode=CompMode.INFERENCE))
            build(ff, batch_size)
            ff.compile(optimizer=None, loss_type=None, metrics=[],
                       mesh=mesh, strategies=strategies)
            if out:
                # every instance serves the SAME function: replicate
                # instance 0's weights (fresh builds differ — layer-name
                # counters are process-global, so init streams diverge).
                # Pair ops by ORDER, not name, for the same reason.
                src = out[0]._cm
                dst = ff.compiled
                for op0, op1 in zip(src.ops, dst.ops):
                    if op0.name not in src.params:
                        continue
                    for w, v in src.params[op0.name].items():
                        dst.params[op1.name][w] = jax.device_put(
                            np.asarray(v),
                            dst.param_shardings[op1.name][w])
            out.append(self.register_ffmodel(ff, name=name))
        return out

    def load_repository(self, path: str, builders=None,
                        devices=None) -> Dict[str, int]:
        """Per-model config file -> placed instance groups
        (serving/placement.py; reference: the Triton model repository)."""
        from .placement import load_repository

        return load_repository(self, path, builders=builders,
                               devices=devices)

    def register_generator(self, ff, name: str = "lm",
                           **kw) -> GenerationInstance:
        """Register a continuous-batching generation instance under
        ``name``. The engine's degradation knobs (admission bound,
        default deadline, breaker, respawn budget) are the scheduler's
        defaults — the GenerationInstance path rides the same
        admission/breaker/respawn machinery as the classic path —
        overridable per call (plus the serving_* geometry knobs)."""
        defaults = dict(admission_limit=self.admission_limit,
                        default_deadline_s=self.default_deadline_s,
                        breaker_threshold=self.breaker_threshold,
                        breaker_cooldown_s=self.breaker_cooldown_s,
                        worker_retry_budget=self.worker_retry_budget)
        defaults.update(kw)
        inst = GenerationInstance(ff, name=name, **defaults)
        with self._mu:
            if name in self._models or name in self._generators:
                raise ValueError(
                    f"{name!r} already registered (generation instances "
                    f"do not form groups — one scheduler owns the pool)")
            self._generators[name] = inst
        return inst

    def generate_async(self, model: str, prompt,
                       max_new_tokens: int, **kw) -> Future:
        """Submit one generation request to a registered generator.
        Same degradation contract as the scheduler's ``submit``:
        :class:`ShedError` at admission (queue bound, open breaker,
        pool-impossible worst case), :class:`DeadlineExceeded` on the
        future when the deadline expires first."""
        with self._mu:
            inst = self._generators[model]
        return inst.generate_async(prompt, max_new_tokens, **kw)

    def generate(self, model: str, prompt, max_new_tokens: int,
                 timeout: Optional[float] = 120.0, **kw) -> np.ndarray:
        return self.generate_async(model, prompt, max_new_tokens,
                                   **kw).result(timeout)

    def models(self) -> List[str]:
        with self._mu:
            return list(self._models)

    def generators(self) -> List[str]:
        with self._mu:
            return list(self._generators)

    def generator(self, name: str) -> GenerationInstance:
        with self._mu:
            return self._generators[name]

    def instances(self, name: str) -> List[ModelInstance]:
        with self._mu:
            return list(self._models[name])

    # ---- lifecycle ---------------------------------------------------------
    def _spawn(self, name: str) -> None:
        """Caller holds ``self._mu`` (a freshly started worker blocks on
        the lock until the registry mutation completes)."""
        for idx in range(len(self._models[name])):
            if (name, idx) in self._workers:
                continue
            t = threading.Thread(target=self._worker_main, args=(name, idx),
                                 daemon=True, name=f"ffserve-{name}-{idx}")
            self._workers[(name, idx)] = t
            t.start()

    def _start_locked(self) -> None:
        if self._started or self._stopping:
            return
        self._started = True
        for name in self._models:
            self._spawn(name)

    def start(self) -> None:
        with self._mu:
            self._start_locked()

    def stop(self) -> None:
        # snapshot under the lock; close() and join() run OUTSIDE it —
        # joining a worker stuck in first-call XLA compilation while
        # holding _mu would freeze every infer_async/register (CCY003)
        with self._mu:
            workers = dict(self._workers)
            batchers = dict(self._batchers)
            generators = dict(self._generators)
            self._generators = {}
            # the first registered model's config gates the session's
            # ledger record (ledger="off" must disable ALL appends)
            _groups = next(iter(self._models.values()), None)
            ledger_cfg = _groups[0]._ff.config if _groups else None
            self._started = False
            self._stopping = True
        # generation schedulers drain + stop first (joins OUTSIDE _mu;
        # each writes its own continuous-engine serving record). They
        # are one-shot: re-register to serve generation again.
        for g in generators.values():
            g.stop()
        for b in batchers.values():
            b.close()
        still_alive = set()
        for (name, idx), t in workers.items():
            t.join(timeout=10)
            if t.is_alive():  # e.g. stuck in first-call XLA compilation
                still_alive.add(name)
        # closed batchers can't be reopened: re-arm each model with a fresh
        # queue so a later start()/infer() serves again instead of hanging.
        # A batcher whose worker didn't exit is LEAKED, not destroyed — the
        # worker may still call next_batch on it (freeing would be a
        # use-after-free on the native handle).
        # workers joined, so nobody else drains a dead batcher: ids parked
        # by a submit that raced the close (e.g. a second stop() destroying
        # the batcher another infer_async just landed in) are collected
        # here for a clean refusal instead of a future that hangs forever.
        # Outside _mu — next_batch never blocks on a closed batcher, but
        # it does take the batcher's own internal lock (CCY003). Nothing
        # can re-fill a closed batcher: submit fails fast once closed.
        leftover: Dict[str, List[int]] = {}
        for name, b in batchers.items():
            if name in still_alive:
                continue
            ids: List[int] = []
            while True:
                batch = b.next_batch()
                if not batch:
                    break
                ids.extend(batch)
            if ids:
                leftover[name] = ids
        with self._mu:
            for key in workers:
                self._workers.pop(key, None)
            for name, b in batchers.items():
                if name not in still_alive:
                    for i in leftover.get(name, ()):
                        req = self._requests[name].pop(i, None)
                        if req is not None and not req.future.done():
                            req.future.set_exception(
                                RuntimeError("engine stopped"))
                    b.destroy()
                self._batchers[name] = _make_batcher(
                    self._models[name][0].batch_size, self.batch_timeout_s)
            # a stopped engine is a clean slate: dead-model markers and
            # breaker state are session-scoped (a restart re-probes)
            self._abandoned.clear()
            self._breaker_open_until.clear()
            self._consec_failures.clear()
            self._stopping = False
        # durable telemetry: one ledger record per CLASSIC serving
        # session (generation sessions recorded their own continuous-
        # engine records above) — request/batch/error counters + latency
        # percentile snapshots (never raises; ledger.errors counts)
        if batchers:
            from ..obs.ledger import record_serving

            record_serving({"models": sorted(batchers)},
                           config=ledger_cfg)

    # ---- request path ------------------------------------------------------
    def infer_async(self, model: str, inputs: Sequence[np.ndarray],
                    deadline_s: Optional[float] = None) -> Future:
        """Submit one request (arrays WITHOUT the batch dim). The future
        resolves to the model's per-request output array.

        Degradation semantics: raises :class:`ShedError` at admission
        when the queue is past ``admission_limit`` or the model's
        failure breaker is open — callers back off instead of piling
        onto a collapsing queue. ``deadline_s`` (default: the engine's
        ``default_deadline_s``) rejects the request fast with
        :class:`DeadlineExceeded` if no worker picks it up in time."""
        with self._mu:
            self._start_locked()
            inst = self._models[model][0]  # all group instances share the spec
            until = self._breaker_open_until.get(model, 0.0)
            if until:
                if time.monotonic() < until:
                    breaker_open = True
                else:  # cooldown elapsed: close the breaker, let traffic probe
                    self._breaker_open_until.pop(model, None)
                    self._consec_failures[model] = 0
                    breaker_open = False
            else:
                breaker_open = False
        reg = metrics_registry()
        if breaker_open:
            reg.counter("serving.breaker_shed").inc()
            reg.counter("serving.shed").inc()
            raise ShedError(
                f"{model!r}: failure breaker is open "
                f"({self.breaker_threshold} consecutive batch failures); "
                f"shedding until the cooldown elapses")
        if self.admission_limit is not None:
            # bounded admission: pending() takes the batcher's own lock,
            # never _mu — the bound is advisory under concurrency (two
            # racing submits may both read limit-1), which is fine: the
            # point is a BOUNDED queue, not an exact one
            with self._mu:
                batcher0 = self._batchers[model]
            if batcher0.pending() >= self.admission_limit:
                reg.counter("serving.shed").inc()
                raise ShedError(
                    f"{model!r}: admission queue at its bound "
                    f"({self.admission_limit}); shedding")
        # validate per-request shapes HERE so one malformed request fails
        # alone instead of poisoning every co-batched request
        if len(inputs) != inst.n_inputs:
            raise ValueError(
                f"{model!r} takes {inst.n_inputs} inputs, got {len(inputs)}")
        for a, t in zip(inputs, inst._cm.input_tensors):
            want = tuple(t.dims[1:])
            if tuple(np.shape(a)) != want:
                raise ValueError(
                    f"{model!r} input {t.name!r}: expected per-request shape "
                    f"{want}, got {np.shape(a)}")
        req = InferenceRequest(
            next(self._ids), [np.asarray(a)[None, ...] for a in inputs],
            # coerced HERE so a malformed deadline fails the submitting
            # caller, never the worker with a whole batch in hand
            deadline_s=(float(deadline_s) if deadline_s is not None
                        else self.default_deadline_s))
        for attempt in range(64):
            with self._mu:
                batcher = self._batchers[model]
                self._requests[model][req.request_id] = req
            try:
                batcher.submit(req.request_id)
                break
            except RuntimeError:
                # a concurrent stop() closed this batcher between the
                # registry read and the submit; un-register and retry
                # against the re-armed batcher stop() installs
                with self._mu:
                    self._requests[model].pop(req.request_id, None)
                time.sleep(0.005)
        else:
            raise RuntimeError(
                f"{model!r}: batcher stayed closed across retries "
                f"(engine is shutting down?)")
        # the submit may have landed in a batcher re-armed by a concurrent
        # stop() (which leaves the engine stopped): respawn the workers
        # that drain it — no-op in the common already-started case
        self.start()
        reg.counter("serving.requests").inc()
        reg.histogram("serving.queue_depth").observe(batcher.pending())
        return req.future

    def infer(self, model: str, inputs: Sequence[np.ndarray],
              timeout: Optional[float] = 60.0) -> np.ndarray:
        return self.infer_async(model, inputs).result(timeout)

    # ---- worker ------------------------------------------------------------
    def _worker_main(self, name: str, idx: int = 0) -> None:
        """Worker supervisor: respawn the drain loop after a crash, up
        to ``worker_retry_budget`` times (the reference analogue: a
        Triton instance restart). A clean exit (closed batcher) ends the
        thread; a crash past the budget abandons the slot LOUDLY —
        counted, printed — and the engine keeps serving on the group's
        surviving workers."""
        reg = metrics_registry()
        for crashes in range(self.worker_retry_budget + 1):
            try:
                self._worker(name, idx)
                return  # batcher closed — normal shutdown
            except Exception as e:  # noqa: BLE001 — the drain loop died
                reg.counter("serving.worker_crashes").inc()
                if crashes >= self.worker_retry_budget:
                    reg.counter("serving.worker_abandoned").inc()
                    print(f"[serving] worker {name}/{idx} crashed "
                          f"{crashes + 1}x ({type(e).__name__}: {e}); "
                          f"respawn budget exhausted — abandoning",
                          file=__import__("sys").stderr, flush=True)
                    self._abandon(name, idx)
                    return
                reg.counter("serving.worker_respawns").inc()
                print(f"[serving] worker {name}/{idx} crashed "
                      f"({type(e).__name__}: {e}); respawning "
                      f"({crashes + 1}/{self.worker_retry_budget})",
                      file=__import__("sys").stderr, flush=True)

    def _abandon(self, name: str, idx: int) -> None:
        """Budget-exhausted slot: when the LAST worker of a model dies,
        nobody will ever drain its queue — fail every pending future
        loudly (accepted futures must resolve, even with an error) and
        leave the breaker open forever so admission sheds instead of
        queueing into the void. stop() clears the dead state; a
        restart serves again."""
        with self._mu:
            self._abandoned.add((name, idx))
            group = self._models.get(name) or []
            dead = all((name, i) in self._abandoned
                       for i in range(len(group)))
            pending: List[InferenceRequest] = []
            if dead:
                self._breaker_open_until[name] = float("inf")
                pending = list(self._requests[name].values())
                self._requests[name].clear()
        if not pending:
            return
        metrics_registry().counter("serving.abandoned_failed").inc(
            len(pending))
        err = RuntimeError(
            f"{name!r}: all workers exhausted their respawn budget; "
            f"request failed (engine sheds until stop()/restart)")
        for r in pending:
            if not r.future.done():
                r.future.set_exception(err)

    def _requeue(self, name: str, ids: List[int]) -> None:
        """Put a crashed worker's in-hand batch back on the queue so its
        futures resolve through the respawned worker (accepted futures
        must ALWAYS resolve). A batcher closed by a concurrent stop()
        refuses the submit; stop()'s leftover sweep then fails those
        futures explicitly."""
        with self._mu:
            batcher = self._batchers[name]
        for i in ids:
            try:
                batcher.submit(i)
            except RuntimeError:
                with self._mu:
                    req = self._requests[name].pop(i, None)
                if req is not None and not req.future.done():
                    req.future.set_exception(
                        RuntimeError("engine stopped during respawn"))

    def _worker(self, name: str, idx: int = 0) -> None:
        import contextlib

        with self._mu:
            inst = self._models[name][idx]
            batcher = self._batchers[name]
        reg = metrics_registry()
        first_batch = True
        while True:
            ids = batcher.next_batch()
            if ids is None:
                return
            # fault site: worker crash with a batch in hand — re-queue
            # it FIRST (futures must resolve through the respawn), then
            # die so _worker_main's budget is exercised
            rule = _fault_fire("serving.worker")
            if rule is not None:
                self._requeue(name, ids)
                raise InjectedFault(
                    f"injected fault at site 'serving.worker' ({rule})")
            with self._mu:
                reqs = [self._requests[name].pop(i) for i in ids
                        if i in self._requests[name]]
            if not reqs:
                continue
            t_pickup = time.perf_counter()
            # watchdog: only ACTIVE batch processing is watched — idle
            # blocking on next_batch() above is the normal empty-queue
            # state, but a hang while requests are in hand (a wedged
            # device) must black-box dump. The FIRST batch runs
            # unwatched: its infer blocks through the cold XLA compile,
            # which is legitimate, not a stall.
            ctx = (contextlib.nullcontext() if first_batch
                   else _wd_watch(f"serving.{name}.{idx}"))
            first_batch = False
            with ctx:
                try:
                    # deadline gate: reject-fast BEFORE burning a batch
                    # on requests nobody is waiting for anymore. Inside
                    # the try on purpose: from the _requests.pop above
                    # to set_result below, ANY failure must resolve the
                    # in-hand futures (the except arm does) — popped
                    # requests can never be re-delivered
                    expired = [r for r in reqs
                               if r.deadline_s is not None
                               and t_pickup - r.t_enqueue > r.deadline_s]
                    if expired:
                        for r in expired:
                            reg.counter("serving.deadline_rejects").inc()
                            if not r.future.done():
                                r.future.set_exception(DeadlineExceeded(
                                    f"request {r.request_id} waited "
                                    f"{t_pickup - r.t_enqueue:.3f}s > "
                                    f"deadline {r.deadline_s:.3f}s"))
                        reqs = [r for r in reqs if r not in expired]
                    if not reqs:
                        continue
                    stacked = [
                        np.concatenate([r.inputs[k] for r in reqs], axis=0)
                        for k in range(inst.n_inputs)
                    ]
                    t_assembled = time.perf_counter()
                    # transient dispatch failures retry with backoff
                    # before the whole batch is failed (runtime/retry.py)
                    outs = _DISPATCH_RETRY.call(inst.infer, stacked)[0]
                    t_infer = time.perf_counter()
                    row = 0
                    ends = []
                    for r in reqs:
                        cnt = r.inputs[0].shape[0]
                        r.future.set_result(
                            outs[row:row + cnt][0]
                            if cnt == 1 else outs[row:row + cnt])
                        row += cnt
                        ends.append(time.perf_counter())
                    reg.counter("serving.batches").inc()
                    reg.histogram("serving.batch_size").observe(row)
                    reg.histogram("serving.infer_s").observe(
                        t_infer - t_assembled)
                    for r, t_end in zip(reqs, ends):
                        reg.histogram("serving.queue_wait_s").observe(
                            t_pickup - r.t_enqueue)
                        reg.histogram("serving.e2e_s").observe(
                            t_end - r.t_enqueue)
                    self._record_request_spans(name, reqs, t_pickup,
                                               t_assembled, t_infer, ends)
                    if self.breaker_threshold:
                        with self._mu:  # a served batch closes the streak
                            self._consec_failures[name] = 0
                except Exception as e:  # surface per-request, keep serving
                    reg.counter("serving.errors").inc()
                    for r in reqs:
                        if not r.future.done():
                            r.future.set_exception(e)
                    if self.breaker_threshold:
                        with self._mu:
                            n = self._consec_failures.get(name, 0) + 1
                            self._consec_failures[name] = n
                            # transition-only (==, not >=): failures of
                            # already-admitted requests draining behind
                            # an open breaker must not re-extend the
                            # cooldown or re-count the same outage
                            if n == self.breaker_threshold:
                                # open: shed at admission until cooldown
                                self._breaker_open_until[name] = (
                                    time.monotonic()
                                    + self.breaker_cooldown_s)
                        if n == self.breaker_threshold:
                            reg.counter("serving.breaker_opens").inc()

    @staticmethod
    def _record_request_spans(model: str, reqs, t_pickup, t_assembled,
                              t_infer, ends) -> None:
        """One span tree per request, each on its own virtual track
        (obs/trace.py VIRTUAL_TID_BASE) so request spans never partially
        overlap: request ⊃ queue_wait → batch_assembly → infer → reply.
        Batch-level phases repeat inside every member request's tree —
        the per-request read ("where did MY latency go") is the point."""
        tr = tracer()
        if not tr.enabled:
            return
        for r, t_end in zip(reqs, ends):
            # request_id is unique for the engine's lifetime: every
            # request gets its OWN track, so concurrent requests can
            # never partially overlap on a shared tid (the invariant
            # validate_chrome_trace enforces)
            tid = VIRTUAL_TID_BASE + r.request_id
            args = {"model": model, "request_id": r.request_id}
            tr.complete("serving.request", r.t_enqueue,
                        t_end - r.t_enqueue, cat="serving", tid=tid,
                        args=args)
            tr.complete("serving.queue_wait", r.t_enqueue,
                        t_pickup - r.t_enqueue, cat="serving", tid=tid)
            tr.complete("serving.batch_assembly", t_pickup,
                        t_assembled - t_pickup, cat="serving", tid=tid)
            tr.complete("serving.infer", t_assembled, t_infer - t_assembled,
                        cat="serving", tid=tid)
            tr.complete("serving.reply", t_infer, t_end - t_infer,
                        cat="serving", tid=tid)
