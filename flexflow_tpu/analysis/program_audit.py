"""Executable auditor: jaxpr-level static checks on compiled programs.

PR 3's passes validate the PCG and strategies *before* lowering; nothing
audited what is actually handed to XLA. This pass walks the
``ClosedJaxpr`` of every step executable — the jitted train/eval steps
(:mod:`..runtime.compiler`), the single-dispatch pipeline program
(:mod:`..parallel.pipeline_compiled`), the serving decode step
(:mod:`..serving.generation`) — and emits coded findings through
:mod:`.findings`:

* **AUD001** — large closed-over constants baked into the program. A
  captured array rides inside the executable: it is replicated on every
  compile, invisible to donation, and silently re-embedded on retrace.
* **AUD002** — donation coverage: a large traced argument whose aval
  matches an un-aliased output is not donated (XLA could write the
  output into the input's buffer; without donation peak HBM pays for
  both); plus a source-level check for caller-side reuse of a buffer
  that was already donated (:func:`lint_donated_reuse`).
* **AUD003** — ``pure_callback`` / ``io_callback`` / ``jax.debug.print``
  inside a step program: a host round-trip on every dispatch.
* **AUD004** — accumulator precision: a loop-carried accumulator whose
  carry dtype is bf16/f16 and whose body add-accumulates into it — the
  lowered reality behind LINT003's source-cast heuristic.
* **AUD005** — collective legality inside ``shard_map``: ``ppermute``
  partner tables must be (partial) permutations with in-range ranks,
  and the ordered collective sequence must agree across every
  ``lax.switch``/``lax.cond`` branch (heterogeneous per-stage programs —
  a mismatch is a cross-host deadlock on a real multi-process mesh).
* **AUD006** — retrace risk: a weak-typed scalar closure baked into the
  program (jit keys its cache on *arguments*; mutating the closure
  silently replays the stale executable — the exact class
  ``runtime/recompile.py``'s guards cannot see), or an unhashable
  static-argument value (a guaranteed ``TypeError`` at dispatch time).

Suppressions use the shared pragma grammar (:mod:`.pragmas`) anchored at
the source line the finding's equation is attributed to::

    table = jnp.asarray(np_table)   # audit: const-ok (4KB lookup table)

Wiring: ``FFModel.compile()`` runs :func:`audit_compiled_model` as a
default-on gate next to the PCG gate (``config.audit_programs=
error|warn|off``, ``--audit-programs``); the pipeline and serving
engines audit their programs at build time; ``tools/program_audit.py``
sweeps the model zoo into one JSON line. The audit traces through the
``jax.jit`` AOT API (``jitted.trace(...)``), whose trace cache is shared
with the first real call — the trace is paid once, not twice.
"""

from __future__ import annotations

import ast
import dataclasses
import time
from collections import Counter
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax

from . import pragmas
from .findings import Finding, ValidationReport

try:  # jaxpr core types: the public extension surface when available
    from jax.extend import core as _jcore

    _jcore.ClosedJaxpr  # noqa: B018 — probe the attr, older jax lacks it
except (ImportError, AttributeError):  # pragma: no cover - version shim
    from jax import core as _jcore

_Jaxpr = _jcore.Jaxpr
_ClosedJaxpr = _jcore.ClosedJaxpr
_Var = _jcore.Var
_Literal = _jcore.Literal

# ------------------------------------------------------------ thresholds
DEFAULT_CONST_BYTES = 1 << 20   # AUD001: consts below this are fine
DEFAULT_DONATE_BYTES = 1 << 20  # AUD002: args below this are not worth it

_CALLBACK_PRIMS = {
    "pure_callback": "jax.pure_callback",
    "io_callback": "jax.experimental.io_callback",
    "debug_callback": "jax.debug.callback",
    "debug_print": "jax.debug.print",
}
# collectives that synchronize across an axis — the set whose cross-rank
# ORDER must agree, or a multi-process mesh deadlocks
_COLLECTIVE_PRIMS = {
    "psum", "ppermute", "pmax", "pmin", "pbroadcast", "all_gather",
    "all_to_all", "reduce_scatter", "psum_scatter", "pgather",
}
_LOW_PRECISION = {"bfloat16", "float16"}
# value-preserving chains followed when deciding whether a scan carry is
# add-accumulated (a convert between the add and the carry is exactly
# the bf16 round-trip AUD004 exists to catch)
_PASSTHROUGH_PRIMS = {"convert_element_type", "broadcast_in_dim",
                      "reshape", "squeeze", "stop_gradient"}
# caller-side donating executables: public wrapper name ->
# (donated positional indices, minimum positional-arg count). Negative
# indices count from the END of the positional args (the eval label
# rides after a model-dependent number of inputs). The arg floor
# disambiguates by arity what AST analysis cannot by type: the
# CompiledModel wrappers take (params, opt_state, rng, *batch) — at
# least 4 positionals — while PipelinedModel.train_step(rng, xs, y)
# shares the name but donates nothing from the caller's view.
DONATING_STEP_CALLS: Dict[str, Tuple[Tuple[int, ...], int]] = {
    "train_step": ((0, 1), 4),   # (params, opt_state) donated
    "train_k_steps": ((0, 1), 4),
    "eval_step": ((-1,), 3),     # label buffer donated (dense loss)
}


# ---------------------------------------------------------------- helpers
def _aval_nbytes(aval) -> int:
    try:
        shape = tuple(aval.shape)
        itemsize = np.dtype(aval.dtype).itemsize
    except (AttributeError, TypeError):
        return 0  # extended dtypes (PRNG keys), tokens: not a buffer risk
    n = 1
    for s in shape:
        n *= int(s)
    return n * itemsize


def _aval_key(aval):
    """Aliasing key: XLA can alias a donated input to an output with the
    same shape+dtype."""
    try:
        return (tuple(aval.shape), str(np.dtype(aval.dtype)))
    except (AttributeError, TypeError):
        return None


def _aval_str(aval) -> str:
    try:
        return aval.str_short()
    except Exception:  # pragma: no cover - cosmetic
        return str(aval)


def _frame(eqn) -> Tuple[Optional[str], Optional[int]]:
    """(file, line) of the user frame that created one equation."""
    try:
        from jax._src import source_info_util as _siu

        fr = _siu.user_frame(eqn.source_info.traceback)
        if fr is not None:
            return fr.file_name, fr.start_line
    except Exception:
        pass
    return None, None


def _suppressed(file: Optional[str], line: Optional[int],
                token: str) -> bool:
    return pragmas.file_has(file, line, "audit", token)


def _sub_jaxprs(eqn):
    """Every sub-jaxpr carried in one equation's params, with consts."""
    for v in eqn.params.values():
        for item in (v if isinstance(v, (tuple, list)) else (v,)):
            if isinstance(item, _ClosedJaxpr):
                yield item.jaxpr, item.consts
            elif isinstance(item, _Jaxpr):
                yield item, []


def _shard_axes(eqn) -> Dict[str, int]:
    """Axis sizes a shard_map equation binds (best effort)."""
    mesh = eqn.params.get("mesh")
    try:
        return {str(a): int(s) for a, s in dict(mesh.shape).items()}
    except Exception:
        return {}


def _walk(jaxpr: _Jaxpr, consts: Sequence, scope: Optional[Dict[str, int]]):
    """Yield every (jaxpr, consts, shard_scope, eqn_path) reachable from
    ``jaxpr``. ``shard_scope`` is the axis-size dict once inside a
    shard_map region (collective checks engage there), else None."""
    yield jaxpr, consts, scope
    for eqn in jaxpr.eqns:
        sub_scope = scope
        if eqn.primitive.name == "shard_map":
            sub_scope = dict(scope or {})
            sub_scope.update(_shard_axes(eqn))
        for sub, sub_consts in _sub_jaxprs(eqn):
            yield from _walk(sub, sub_consts, sub_scope)


def _count_eqns(jaxpr: _Jaxpr) -> int:
    n = len(jaxpr.eqns)
    for eqn in jaxpr.eqns:
        for sub, _c in _sub_jaxprs(eqn):
            n += _count_eqns(sub)
    return n


# ---------------------------------------------------- AUD001: big consts
def _check_consts(name: str, jaxpr: _Jaxpr, consts: Sequence,
                  report: ValidationReport, threshold: int,
                  stats: Dict) -> None:
    total = 0
    for jx, cs, _scope in _walk(jaxpr, consts, None):
        for var, c in zip(jx.constvars, cs):
            nbytes = _aval_nbytes(var.aval)
            total += nbytes
            if nbytes < threshold:
                continue
            consumer = next((e for e in jx.eqns if var in e.invars), None)
            file = line = None
            where = ""
            if consumer is not None:
                file, line = _frame(consumer)
                where = f", consumed by '{consumer.primitive.name}'"
            if _suppressed(file, line, "const-ok"):
                stats["suppressed"] += 1
                continue
            report.add(
                "AUD001",
                f"program '{name}' bakes a "
                f"{nbytes / 2**20:.1f}MiB constant "
                f"({_aval_str(var.aval)}) into the executable{where} — "
                f"pass it as an argument so it is shardable/donatable "
                f"(or annotate '# audit: const-ok (reason)')",
                severity="warning", file=file, line=line)
    stats["consts_bytes"] = total


# ------------------------------------------- AUD002: donation coverage
def _check_donation(name: str, closed: _ClosedJaxpr,
                    donated: Optional[Sequence[bool]],
                    arg_names: Optional[Sequence[str]],
                    report: ValidationReport, threshold: int,
                    allow_undonated: Dict[str, str],
                    stats: Dict) -> None:
    in_avals = list(closed.in_avals)
    if donated is None:
        donated = [False] * len(in_avals)
    stats["args"] = len(in_avals)
    stats["donated_args"] = sum(bool(d) for d in donated)
    # un-claimed output avals: donated inputs claim their match first
    free_outs = Counter(k for k in map(_aval_key, closed.out_avals)
                        if k is not None)
    for aval, d in zip(in_avals, donated):
        key = _aval_key(aval)
        if d and key is not None and free_outs.get(key, 0) > 0:
            free_outs[key] -= 1
    for i, (aval, d) in enumerate(zip(in_avals, donated)):
        if d:
            continue
        nbytes = _aval_nbytes(aval)
        key = _aval_key(aval)
        if nbytes < threshold or key is None or free_outs.get(key, 0) < 1:
            continue
        label = (arg_names[i] if arg_names and i < len(arg_names)
                 else f"#{i}")
        waived = next((r for frag, r in allow_undonated.items()
                       if frag in label), None)
        if waived is not None:
            stats["suppressed"] += 1
            continue
        free_outs[key] -= 1
        report.add(
            "AUD002",
            f"program '{name}': argument {label} "
            f"({nbytes / 2**20:.1f}MiB, {_aval_str(aval)}) is not "
            f"donated but an output with the same aval exists — "
            f"donate it so XLA aliases the buffers instead of holding "
            f"both live",
            severity="warning")


# ------------------------------------------------- AUD003: host callbacks
def _check_callbacks(name: str, jaxpr: _Jaxpr, consts: Sequence,
                     report: ValidationReport, stats: Dict) -> None:
    for jx, _cs, _scope in _walk(jaxpr, consts, None):
        for eqn in jx.eqns:
            prim = eqn.primitive.name
            if prim not in _CALLBACK_PRIMS:
                continue
            file, line = _frame(eqn)
            if _suppressed(file, line, "callback-ok"):
                stats["suppressed"] += 1
                continue
            report.add(
                "AUD003",
                f"host callback {_CALLBACK_PRIMS[prim]} inside step "
                f"program '{name}' — a device-to-host round-trip every "
                f"dispatch (annotate '# audit: callback-ok (reason)' "
                f"if intentional)",
                severity="error", file=file, line=line)


# ------------------------------------- AUD004: low-precision accumulators
def _producer_map(jaxpr: _Jaxpr) -> Dict[Any, Any]:
    prod = {}
    for eqn in jaxpr.eqns:
        for ov in eqn.outvars:
            if isinstance(ov, _Var):
                prod[ov] = eqn
    return prod


def _resolves_to(var, target, prod, depth: int = 8) -> bool:
    """True when ``var`` is ``target`` through value-preserving chains."""
    while depth > 0:
        if var is target:
            return True
        if not isinstance(var, _Var):
            return False
        eqn = prod.get(var)
        if eqn is None or eqn.primitive.name not in _PASSTHROUGH_PRIMS:
            return False
        var = eqn.invars[0]
        depth -= 1
    return False


def _is_add_accum(body: _Jaxpr, carry_in, carry_out) -> bool:
    """Does the loop body add-accumulate into this carry slot?"""
    prod = _producer_map(body)
    var = carry_out
    for _ in range(8):  # walk back through value-preserving tails
        if not isinstance(var, _Var):
            return False
        eqn = prod.get(var)
        if eqn is None:
            return False
        if eqn.primitive.name in ("add", "add_any", "sub"):
            return any(_resolves_to(iv, carry_in, prod)
                       for iv in eqn.invars)
        if eqn.primitive.name not in _PASSTHROUGH_PRIMS:
            return False
        var = eqn.invars[0]
    return False


def _check_accumulators(name: str, jaxpr: _Jaxpr, consts: Sequence,
                        report: ValidationReport, stats: Dict) -> None:
    for jx, _cs, _scope in _walk(jaxpr, consts, None):
        for eqn in jx.eqns:
            if eqn.primitive.name != "scan":
                continue
            body = eqn.params["jaxpr"]
            body_jx = body.jaxpr if isinstance(body, _ClosedJaxpr) else body
            nc = eqn.params.get("num_consts", 0)
            ncar = eqn.params.get("num_carry", 0)
            carries_in = body_jx.invars[nc:nc + ncar]
            carries_out = body_jx.outvars[:ncar]
            for ci, (iv, ov) in enumerate(zip(carries_in, carries_out)):
                try:
                    dt = str(np.dtype(iv.aval.dtype))
                except (AttributeError, TypeError):
                    continue
                if dt not in _LOW_PRECISION:
                    continue
                if not _is_add_accum(body_jx, iv, ov):
                    continue
                file, line = _frame(eqn)
                if _suppressed(file, line, "accum-ok"):
                    stats["suppressed"] += 1
                    continue
                report.add(
                    "AUD004",
                    f"program '{name}': scan carry #{ci} "
                    f"({_aval_str(iv.aval)}) add-accumulates in {dt} — "
                    f"every iteration rounds the running sum; keep "
                    f"accumulators in float32 (LINT003's cast heuristic, "
                    f"confirmed at the jaxpr level)",
                    severity="error", file=file, line=line)


# -------------------------------------- AUD005: collective legality
def _perm_problem(perm, axis_sizes: Dict[str, int],
                  axis_name) -> Optional[str]:
    pairs = [tuple(p) for p in perm]
    srcs = [p[0] for p in pairs]
    dsts = [p[1] for p in pairs]
    if len(set(srcs)) != len(srcs):
        dup = [s for s in set(srcs) if srcs.count(s) > 1]
        return f"duplicate source rank(s) {sorted(dup)}"
    if len(set(dsts)) != len(dsts):
        dup = [d for d in set(dsts) if dsts.count(d) > 1]
        return (f"duplicate destination rank(s) {sorted(dup)} — two "
                f"ranks would send to one receiver")
    names = (axis_name if isinstance(axis_name, (tuple, list))
             else (axis_name,))
    size = 1
    for a in names:
        size *= axis_sizes.get(str(a), 0) or 0
    if size:
        bad = [r for r in srcs + dsts if not (0 <= r < size)]
        if bad:
            return (f"rank(s) {sorted(set(bad))} out of range for axis "
                    f"{'x'.join(map(str, names))} of size {size}")
    return None


def _collective_signature(jaxpr: _Jaxpr) -> Tuple:
    """Ordered (primitive, axes, perm) sequence — the cross-rank sync
    schedule a branch would execute."""
    sig = []
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim in _COLLECTIVE_PRIMS:
            axes = eqn.params.get("axes", eqn.params.get("axis_name"))
            axes = tuple(axes) if isinstance(axes, (tuple, list)) \
                else (axes,)
            perm = eqn.params.get("perm")
            perm = tuple(tuple(p) for p in perm) if perm is not None \
                else None
            sig.append((prim, axes, perm))
        for sub, _c in _sub_jaxprs(eqn):
            sig.extend(_collective_signature(sub))
    return tuple(sig)


def _fmt_sig(sig: Tuple) -> str:
    return "[" + ", ".join(
        p + "@" + "/".join(map(str, a)) for p, a, _perm in sig) + "]"


def _check_collectives(name: str, jaxpr: _Jaxpr, consts: Sequence,
                       report: ValidationReport, stats: Dict) -> None:
    for jx, _cs, scope in _walk(jaxpr, consts, None):
        if scope is None:
            continue  # collective rules engage inside shard_map only
        for eqn in jx.eqns:
            prim = eqn.primitive.name
            if prim == "ppermute":
                problem = _perm_problem(
                    eqn.params.get("perm", ()), scope,
                    eqn.params.get("axis_name"))
                if problem:
                    file, line = _frame(eqn)
                    report.add(
                        "AUD005",
                        f"program '{name}': ppermute partner table "
                        f"{tuple(eqn.params.get('perm', ()))} is not a "
                        f"partial permutation ({problem}) — ranks would "
                        f"wait on transfers that never arrive",
                        severity="error", file=file, line=line)
            elif prim == "cond":
                sigs = [_collective_signature(b.jaxpr)
                        for b in eqn.params.get("branches", ())]
                if sigs and any(s != sigs[0] for s in sigs[1:]):
                    file, line = _frame(eqn)
                    uniq = sorted({_fmt_sig(s) for s in sigs})
                    report.add(
                        "AUD005",
                        f"program '{name}': lax.switch/cond branches "
                        f"disagree on their collective sequence "
                        f"({' vs '.join(uniq)}) — stages taking "
                        f"different branches deadlock on a real "
                        f"multi-process mesh",
                        severity="error", file=file, line=line)


# ------------------------------------------------- AUD006: retrace risk
def _check_retrace(name: str, jaxpr: _Jaxpr, consts: Sequence,
                   static_args: Optional[Dict[str, Any]],
                   report: ValidationReport, stats: Dict) -> None:
    for key, val in (static_args or {}).items():
        try:
            hash(val)
        except TypeError:
            report.add(
                "AUD006",
                f"program '{name}': static argument '{key}' = "
                f"{type(val).__name__} is unhashable — jit cannot key "
                f"its cache on it (guaranteed TypeError at dispatch)",
                severity="error")
    for jx, cs, _scope in _walk(jaxpr, consts, None):
        for var, c in zip(jx.constvars, cs):
            aval = var.aval
            try:
                weak = bool(getattr(aval, "weak_type", False))
                is_scalar_float = (aval.ndim == 0 and np.issubdtype(
                    np.dtype(aval.dtype), np.floating))
            except (AttributeError, TypeError):
                continue
            if not (weak and is_scalar_float):
                continue
            consumer = next((e for e in jx.eqns if var in e.invars), None)
            file = line = None
            if consumer is not None:
                file, line = _frame(consumer)
            if _suppressed(file, line, "retrace-ok"):
                stats["suppressed"] += 1
                continue
            report.add(
                "AUD006",
                f"program '{name}': weak-typed scalar closure "
                f"(value {np.asarray(c).item():g}) is baked into the "
                f"executable — jit re-traces on argument changes only, "
                f"so mutating it silently replays the stale program "
                f"(runtime/recompile.py guards cannot see it either); "
                f"pass it as a traced argument like "
                f"optimizer.hyperparams()",
                severity="warning", file=file, line=line)


# ------------------------------------------------ liveness / peak buffers
def _liveness(closed: _ClosedJaxpr,
              donated: Optional[Sequence[bool]]) -> Dict[str, int]:
    """Static peak-live estimate over the top-level jaxpr: a linear scan
    with donated inputs dying at last use, non-donated inputs (the
    caller still holds them) and outputs live to the end. Nested
    programs count as atomic ops — this is a *relative* audit metric
    (donation coverage shows up as a lower peak), not an XLA buffer
    assignment."""
    jaxpr = closed.jaxpr
    if donated is None:
        donated = [False] * len(jaxpr.invars)
    END = len(jaxpr.eqns) + 1
    last_use: Dict[Any, int] = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if isinstance(v, _Var):
                last_use[v] = i
    for v in jaxpr.outvars:
        if isinstance(v, _Var):
            last_use[v] = END
    for v, d in zip(jaxpr.invars, donated):
        if not d:
            last_use[v] = END
    # the alias is what donation buys: XLA writes an output into a
    # donated input's buffer when the avals match, so that output
    # allocates NOTHING — pair them greedily (same key order as
    # _check_donation) and count aliased outputs at zero bytes
    free_by_key: Dict[Any, List[Any]] = {}
    for v, d in zip(jaxpr.invars, donated):
        if d:
            free_by_key.setdefault(_aval_key(v.aval), []).append(v)
    aliased_outs = set()
    for v in jaxpr.outvars:
        if isinstance(v, _Var) and v not in aliased_outs:
            cands = free_by_key.get(_aval_key(v.aval))
            if cands:
                cands.pop(0)
                aliased_outs.add(v)
    def _bytes(v) -> int:
        return 0 if v in aliased_outs else _aval_nbytes(v.aval)

    # invert last_use into per-index death lists and keep running
    # totals: one pass, O(eqns + vars) — a per-equation rescan of
    # last_use would be quadratic on the thousand-equation programs
    # this runs on at every compile
    deaths: Dict[int, List[Any]] = {}
    live_bytes = live_count = 0
    seen = set()
    for v in list(jaxpr.invars) + list(jaxpr.constvars):
        if last_use.get(v) is None or v in seen:
            continue
        seen.add(v)
        deaths.setdefault(last_use[v], []).append(v)
        b = _bytes(v)
        live_bytes += b
        live_count += 1 if b else 0
    peak_bytes, peak_count = live_bytes, live_count
    for i, eqn in enumerate(jaxpr.eqns):
        for ov in eqn.outvars:
            if isinstance(ov, _Var) and last_use.get(ov) is not None \
                    and ov not in seen:
                seen.add(ov)
                deaths.setdefault(last_use[ov], []).append(ov)
                b = _bytes(ov)
                live_bytes += b
                live_count += 1 if b else 0
        peak_bytes = max(peak_bytes, live_bytes)
        peak_count = max(peak_count, live_count)
        for v in deaths.pop(i, ()):
            b = _bytes(v)
            live_bytes -= b
            live_count -= 1 if b else 0
    return {"peak_live_bytes": int(peak_bytes),
            "peak_live_buffers": int(peak_count)}


# ------------------------------------------------------------- entry API
@dataclasses.dataclass
class ExecutableSpec:
    """One program to audit: a jitted function plus abstract example
    arguments (ShapeDtypeStructs or small concretes) matching a real
    call, so the AOT trace is shared with the first dispatch."""

    name: str
    fn: Any                       # jax.jit product (has .trace)
    args: Tuple = ()
    static_args: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # arg-path fragment -> reason: donation deliberately withheld
    # (e.g. the caller reuses the buffer); the audit records these as
    # suppressed instead of AUD002
    allow_undonated: Dict[str, str] = dataclasses.field(
        default_factory=dict)


def _new_stats() -> Dict[str, Any]:
    return {"eqns": 0, "consts_bytes": 0, "args": 0, "donated_args": 0,
            "suppressed": 0}


def audit_closed_jaxpr(
    name: str,
    closed: _ClosedJaxpr,
    *,
    donated: Optional[Sequence[bool]] = None,
    arg_names: Optional[Sequence[str]] = None,
    static_args: Optional[Dict[str, Any]] = None,
    allow_undonated: Optional[Dict[str, str]] = None,
    config=None,
    report: Optional[ValidationReport] = None,
    source: str = "program",
) -> ValidationReport:
    """Run every AUD check over one ClosedJaxpr. Findings accumulate on
    ``report`` (created when None); per-program stats land in
    ``report.programs[name]``."""
    report = report if report is not None else ValidationReport(
        source=source, tag="audit")
    if not hasattr(report, "programs"):
        report.programs = {}
    const_thresh = int(getattr(config, "audit_const_bytes",
                               DEFAULT_CONST_BYTES) or DEFAULT_CONST_BYTES)
    donate_thresh = int(getattr(config, "audit_donate_bytes",
                                DEFAULT_DONATE_BYTES)
                        or DEFAULT_DONATE_BYTES)
    stats = _new_stats()
    stats["eqns"] = _count_eqns(closed.jaxpr)
    _check_consts(name, closed.jaxpr, closed.consts, report,
                  const_thresh, stats)
    _check_donation(name, closed, donated, arg_names, report,
                    donate_thresh, dict(allow_undonated or {}), stats)
    _check_callbacks(name, closed.jaxpr, closed.consts, report, stats)
    _check_accumulators(name, closed.jaxpr, closed.consts, report, stats)
    _check_collectives(name, closed.jaxpr, closed.consts, report, stats)
    _check_retrace(name, closed.jaxpr, closed.consts, static_args,
                   report, stats)
    stats.update(_liveness(closed, donated))
    report.programs[name] = stats
    return report


def _traced_donation(traced) -> Tuple[Optional[List[bool]],
                                      Optional[List[str]]]:
    """Per-flat-arg (donated, name) extracted from a jax.stages.Traced."""
    try:
        flat = jax.tree_util.tree_flatten_with_path(traced.args_info)[0]
        donated = [bool(getattr(info, "donated", False))
                   for _p, info in flat]
        names = ["arg" + jax.tree_util.keystr(p) for p, _i in flat]
        return donated, names
    except Exception:
        return None, None


def audit_traced(name: str, traced, **kw) -> ValidationReport:
    """Audit a ``jax.stages.Traced`` (from ``jitted.trace(*args)``) —
    donation flags and argument names come from its ``args_info``."""
    donated, names = _traced_donation(traced)
    closed = traced.jaxpr
    n = len(closed.in_avals)
    if donated is not None and len(donated) != n:
        donated, names = None, None  # defensive: never mis-zip
    return audit_closed_jaxpr(name, closed, donated=donated,
                              arg_names=names, **kw)


def audit_spec(spec: ExecutableSpec, *, config=None,
               report: Optional[ValidationReport] = None,
               source: str = "program") -> ValidationReport:
    """Trace one :class:`ExecutableSpec` and audit it. A trace failure
    becomes an AUD000 warning finding rather than masking the compile
    (the real dispatch will surface the true error with full context);
    an unhashable-static TypeError keeps its meaningful AUD006 code."""
    report = report if report is not None else ValidationReport(
        source=source, tag="audit")
    if not hasattr(report, "programs"):
        report.programs = {}
    t0 = time.perf_counter()
    try:
        traced = spec.fn.trace(*spec.args)
    except Exception as e:  # noqa: BLE001 — audit must not mask compile
        report.add(
            "AUD006" if isinstance(e, TypeError)
            and "unhashable" in str(e) else "AUD000",
            f"program '{spec.name}' could not be traced for audit: "
            f"{type(e).__name__}: {e}",
            severity="warning")
        report.programs[spec.name] = dict(_new_stats(), trace_failed=True)
        return report
    t_trace = time.perf_counter() - t0
    t1 = time.perf_counter()
    report = audit_traced(spec.name, traced,
                          static_args=spec.static_args,
                          allow_undonated=spec.allow_undonated,
                          config=config, report=report, source=source)
    # the AOT trace is shared with the first real dispatch (jit's trace
    # cache), so walk_s is the gate's own marginal cost; trace_s is the
    # first dispatch's tracing, merely paid early
    report.programs[spec.name]["trace_s"] = round(t_trace, 6)
    report.programs[spec.name]["walk_s"] = round(
        time.perf_counter() - t1, 6)
    return report


def audit_compiled_model(cm, *, config=None, source: str = "compile",
                         skip: Sequence[str] = ()) -> ValidationReport:
    """Audit every step executable a CompiledModel exposes via its
    ``audit_exec`` specs (built by runtime/compiler.py). ``skip`` names
    specs the caller knows will never be dispatched (e.g. ``train_step``
    when a pipeline engine drives training) — tracing those would be
    pure overhead, not shared with any first call."""
    report = ValidationReport(source=f"audit:{source}", tag="audit")
    report.programs = {}
    for spec in (getattr(cm, "audit_exec", None) or []):
        if spec.name in skip:
            continue
        audit_spec(spec, config=config, report=report, source=source)
    return report


# --------------------------------- AUD002 (caller side): donated reuse
def _attach_parents(tree: ast.AST) -> None:
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            child._pa_parent = node  # type: ignore[attr-defined]


def _enclosing_stmt(node: ast.AST) -> Optional[ast.stmt]:
    cur = node
    while cur is not None and not isinstance(cur, ast.stmt):
        cur = getattr(cur, "_pa_parent", None)
    return cur


def _scope_walk(fn: ast.AST):
    """Walk one function's OWN scope: nested def/lambda subtrees are
    pruned (their same-named params and locals are different bindings —
    scanning into them would flag a nested function's `params` as reuse
    of the outer donated buffer)."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def _assign_target_names(stmt: Optional[ast.stmt]) -> set:
    names = set()
    if isinstance(stmt, ast.Assign):
        for t in stmt.targets:
            for n in ast.walk(t):
                if isinstance(n, ast.Name):
                    names.add(n.id)
    elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
        for n in ast.walk(stmt.target):
            if isinstance(n, ast.Name):
                names.add(n.id)
    return names


def lint_donated_reuse(src: str, filename: str = "<string>",
                       donating: Optional[Dict[str, Tuple[int, ...]]]
                       = None) -> List[Finding]:
    """AUD002 caller-side check: a local name passed at a donated
    position of a step executable and then *read* again (before any
    rebind) in the same function — the donated buffer is already dead,
    so the reuse raises at runtime (or worse, on a real TPU, reads
    freed memory). Conservative by construction: only plain-name
    arguments in the same function body are tracked; rebinding in the
    same assignment (``p, s, ... = cm.train_step(p, s, ...)``) is the
    sanctioned idiom and passes. Only ``obj.method(...)`` call forms
    with the table's minimum arity count — the raw step functions
    inside runtime/compiler.py share these names but donate nothing at
    those positions. Suppress with ``# audit: donate-ok (reason)`` on
    the reuse line."""
    donating = dict(DONATING_STEP_CALLS if donating is None else donating)
    findings: List[Finding] = []
    try:
        tree = ast.parse(src, filename=filename)
    except SyntaxError as e:
        findings.append(Finding(
            code="HOT000", severity="error", file=filename,
            line=e.lineno or 0, message=f"syntax error: {e.msg}"))
        return findings
    _attach_parents(tree)
    lines = src.splitlines()
    for fn in [n for n in ast.walk(tree)
               if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]:
        for call in [n for n in _scope_walk(fn) if isinstance(n, ast.Call)]:
            if not isinstance(call.func, ast.Attribute):
                continue  # bare names are the raw (non-donating) fns
            attr = call.func.attr
            if attr not in donating:
                continue
            positions, min_args = donating[attr]
            if len(call.args) < min_args:
                continue  # arity says: not the donating wrapper
            stmt = _enclosing_stmt(call)
            rebound = _assign_target_names(stmt)
            for pos in positions:
                if not (-len(call.args) <= pos < len(call.args)):
                    continue
                arg = call.args[pos]
                if not isinstance(arg, ast.Name) or arg.id in rebound:
                    continue
                nm = arg.id
                # events after the call, in source order, same scope
                events = sorted(
                    ((n.lineno, n) for n in _scope_walk(fn)
                     if isinstance(n, ast.Name) and n.id == nm
                     and n.lineno > call.lineno),
                    key=lambda t: t[0])
                for lineno, n in events:
                    if isinstance(n.ctx, ast.Store):
                        break  # rebound before any read: safe
                    if pragmas.line_has(lines, lineno, "audit",
                                        "donate-ok"):
                        break
                    findings.append(Finding(
                        code="AUD002", severity="error", file=filename,
                        line=lineno,
                        message=f"'{nm}' was donated to {attr}() at "
                                f"line {call.lineno} and is read again "
                                f"here — the buffer is already consumed "
                                f"(annotate "
                                f"'# audit: donate-ok (reason)' "
                                f"if this is not a live read)"))
                    break
    return findings


def lint_donated_reuse_paths(paths: Sequence[str]) -> List[Finding]:
    """Run :func:`lint_donated_reuse` over .py files/directories."""
    import os

    findings: List[Finding] = []
    for p in paths:
        files = []
        if os.path.isfile(p):
            files = [p]
        else:
            for dirpath, dirnames, filenames in os.walk(p):
                dirnames[:] = [d for d in dirnames if d != "__pycache__"]
                files.extend(os.path.join(dirpath, f)
                             for f in sorted(filenames)
                             if f.endswith(".py"))
        for f in files:
            with open(f) as fh:
                findings.extend(lint_donated_reuse(fh.read(), filename=f))
    return findings
