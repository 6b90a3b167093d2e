"""The owner table of a traced window: whose work the device's time was.

The program lowers every graph op under a scope of its own type and name
(``flexflow_tpu/core/op.py`` ``op_scope``: ``ff.LINEAR.h3.mlp.fc``, the
fixed scopes ``ff.loss``, ``ff.optimizer``, ..., sub-scopes ``project``,
``write``, ``attend``, ...), and XLA carries the scope in each
instruction's ``op_name``. The profiler keeps that path with the device
trace, but not where ``jax.profiler.ProfileData`` shows it: on a TPU an
``XLA Ops`` event's own statistics are its offset and duration, and the
path is the statistic ``tf_op`` of the event's METADATA (one record per
distinct instruction, beside ``program_id``, ``hlo_category``, ``flops``,
``bytes_accessed``), which ``ProfileData`` does not expose. So this
module reads the ``.xplane.pb`` itself, by the protobuf wire format (a
few dozen lines over the standard library and numpy), into the plain
structure ``benchmark/reduce.py`` works on with a fourth entry an event::

    {"planes": [{"name": str,
                 "lines": [{"name": str,
                            "events": [[name, start_ns, duration_ns,
                                        {"tf_op": str, ...}], ...]}]}]}

(the loader hands the ``XLA Ops`` line over as columns, an instruction
once and an event an index: a serving window of 3 s holds two million
events) and :func:`owner_table` reduces that: for each program of ``XLA
Modules`` whose execution lies whole inside the window, its executions,
its device seconds, and for each owner ``(type, name, sub-scope, phase)`` its
EXCLUSIVE device seconds: an event's duration less that of the events
nested inside it on its line (a ``while``'s children are events of the
same line), each event given to the execution that contains it. Rows of
a program sum to its busy time; what no scope owns is the row
``(unowned)``, kept by XLA name. A fusion that holds two owners' work
goes whole to the owner XLA names for it: the table cannot split what
XLA fused. A ``while`` or ``conditional`` that carries no path of its own
takes the owner of most of what runs inside it.

    python3 -m benchmark.owners <trace dir> [--by type|name|xla]

prints the table of the newest profile under the directory, a program a
block: a row an op type (with the XLA names it is made of), a row an op,
or a row an XLA name (with the owners it is split among).
"""

from __future__ import annotations

import os
import re
import time
from typing import Dict, Iterator, List, Optional, Tuple

from benchmark import reduce

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
UNOWNED = "(unowned)"
WINDOW_SPAN = reduce.SPAN_PREFIX + "window"
KEPT_STATS = ("tf_op", "program_id")
Owner = Tuple[str, str, str, str]      # type, name, sub-scope, phase

try:  # the scheme is the program's: a program without it has no owners
    from flexflow_tpu.core.op import parse_scope, scope_group
except ImportError:  # the parent of the PR that brought the scopes
    parse_scope = scope_group = None


# ---- the .xplane.pb by its wire format --------------------------------------
# XSpace{1 planes}; XPlane{2 name, 3 lines, 4 event_metadata<id, ...>,
# 5 stat_metadata<id, ...>}; XLine{2 name, 3 timestamp_ns, 4 events};
# XEvent{1 metadata_id, 2 offset_ps, 3 duration_ps}; XEventMetadata{1 id,
# 2 name, 5 stats}; XStatMetadata{1 id, 2 name}; XStat{1 metadata_id,
# 2 double, 3 uint64, 4 int64, 5 str, 6 bytes, 7 ref (a stat_metadata id
# whose name is the string)}: tsl/profiler/protobuf/xplane.proto.

def _varint(buf: bytes, p: int) -> Tuple[int, int]:
    b = buf[p]
    p += 1
    if b < 0x80:
        return b, p
    out, shift = b & 0x7F, 7
    while True:
        b = buf[p]
        p += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, p
        shift += 7


def _fields(buf: bytes, lo: int, hi: int) -> Iterator[Tuple[int, int, object]]:
    """``(field number, wire type, value)`` of the message in
    ``buf[lo:hi]``: a varint's value, ``(start, end)`` of a
    length-delimited field, None for the fixed widths (skipped)."""
    p = lo
    while p < hi:
        key, p = _varint(buf, p)
        wire = key & 7
        if wire == 0:
            value, p = _varint(buf, p)
        elif wire == 2:
            n, p = _varint(buf, p)
            value = (p, p + n)
            p += n
        elif wire == 1:
            value, p = None, p + 8
        elif wire == 5:
            value, p = None, p + 4
        else:
            raise ValueError(f"wire type {wire} at byte {p}: not an xplane")
        yield key >> 3, wire, value


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_entry(buf: bytes, span) -> Tuple[int, Optional[Tuple[int, int]]]:
    key, value = 0, None
    for num, _, v in _fields(buf, *span):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


def _stat_names(buf: bytes, spans) -> Dict[int, str]:
    names = {}
    for span in spans:
        key, value = _map_entry(buf, span)
        if value is not None:
            for num, _, v in _fields(buf, *value):
                if num == 2:
                    names[key] = _text(buf, v)
    return names


def _event_metadata(buf: bytes, spans, stat_names: Dict[int, str]):
    """``{metadata id: (name, {statistic: value})}``, of the statistics
    in :data:`KEPT_STATS`."""
    out = {}
    for span in spans:
        key, value = _map_entry(buf, span)
        if value is None:
            continue
        name, stats = "", {}
        for num, _, v in _fields(buf, *value):
            if num == 2:
                name = _text(buf, v)
            elif num == 5:
                stat, got = None, None
                for snum, _, sv in _fields(buf, *v):
                    if snum == 1:
                        stat = stat_names.get(sv)
                    elif snum in (3, 4):
                        got = sv
                    elif snum == 5:
                        got = _text(buf, sv)
                    elif snum == 7:
                        got = stat_names.get(sv, "")
                if stat in KEPT_STATS and got is not None:
                    stats[stat] = got
        out[key] = (name, stats)
    return out


def _line(buf: bytes, span) -> Tuple[str, int, List[int]]:
    """A line's name, its timestamp in ns and where each of its events
    starts: they are written one behind the other after name and
    timestamp, each behind its length."""
    name, t0_ns, starts = "", 0, []
    hi = span[1]
    for num, _, v in _fields(buf, *span):
        if num == 2:
            name = _text(buf, v)
        elif num == 3:
            t0_ns = v
        elif num == 4:
            starts.append(v[0])
            p = v[1]
            while p < hi and buf[p] == 0x22:    # the next event's key
                n, q = buf[p + 1], p + 2
                if n >= 0x80:
                    n, q = _varint(buf, p + 1)
                starts.append(q)
                p = q + n
            break
    return name, t0_ns, starts


def _events(data, starts: List[int]):
    """``(metadata ids, offsets in ps, durations in ps)`` of the XEvents
    that start at ``starts`` in the bytes ``data`` (a uint8 array with
    eight spare bytes behind it), as arrays: a serving window holds millions
    of events, and a varint at a time in Python takes ten seconds over
    them. An event is ``0x08 <metadata id> 0x10 <offset> 0x18 <duration>``
    and then its statistics; a field that is zero is not written. A
    varint is read from the eight bytes behind its key as one word: its
    length from the first byte without the continuation bit, its value
    from the seven low bits of each byte."""
    import numpy as np

    words = np.lib.stride_tricks.sliding_window_view(data, 8)
    pos = np.array(starts, np.int64)
    out = []
    for key in (0x08, 0x10, 0x18):
        has = data[pos] == key
        word = words[pos + 1].copy().view("<u8").ravel()
        stop = ~word & np.uint64(0x8080808080808080)
        if not stop.all():
            raise ValueError("a varint of more than eight bytes")
        size = (np.log2((stop & (~stop + np.uint64(1))).astype(np.float64))
                .astype(np.int64) + 1) // 8
        value = np.zeros(len(pos), np.uint64)
        for i in range(8):
            value |= ((word >> np.uint64(8 * i)) & np.uint64(0x7F)) \
                << np.uint64(7 * i)
        value &= (np.uint64(1) << (7 * size).astype(np.uint64)) - np.uint64(1)
        out.append(np.where(has, value, np.uint64(0)))
        pos = np.where(has, pos + 1 + size, pos)
    return out


def load_xplane(path: str) -> Dict:
    """The structure of the module's docstring from an ``.xplane.pb``:
    of each ``/device:TPU:<n>`` plane the lines ``XLA Ops`` and ``XLA
    Modules`` (an event's fourth entry is its metadata's statistics, one
    dict per distinct instruction), and of ``/host:CPU`` the
    ``bench.window`` spans. The ``XLA Ops`` line comes as columns, not as
    a list an event: ``kinds`` (``[name, statistics]`` per distinct
    instruction) and the arrays ``kind`` (an index into it), ``start_ns``
    and ``duration_ns``; :func:`owner_table` takes either form."""
    import numpy as np

    with open(path, "rb") as f:
        buf = f.read()
    data = np.frombuffer(buf + bytes(8), np.uint8)
    planes = []
    for num, _, plane in _fields(buf, 0, len(buf)):
        if num != 1:
            continue
        name, lines, emeta, smeta = "", [], [], []
        for pnum, _, v in _fields(buf, *plane):
            if pnum == 2:
                name = _text(buf, v)
            elif pnum == 3:
                lines.append(v)
            elif pnum == 4:
                emeta.append(v)
            elif pnum == 5:
                smeta.append(v)
        device = bool(reduce.DEVICE_PLANE.match(name))
        if not device and name != reduce.HOST_PLANE:
            continue
        meta = _event_metadata(buf, emeta, _stat_names(buf, smeta))
        out = []
        for span in lines:
            lname, t0_ns, starts = _line(buf, span)
            if device and lname not in (reduce.OPS_LINE, reduce.MODULES_LINE):
                continue
            if not starts:
                continue
            mid, off, dur = _events(data, starts)
            start_ns, dur_ns = t0_ns + off / 1e3, dur / 1e3
            if lname == reduce.OPS_LINE:    # millions of events: columns
                ids, kind = np.unique(mid, return_inverse=True)
                out.append({"name": lname, "kind": kind,
                            "kinds": [list(meta.get(int(m), ("", {})))
                                      for m in ids],
                            "start_ns": start_ns, "duration_ns": dur_ns})
                continue
            events = [[meta[m][0], s, d, meta[m][1]] for m, s, d in zip(
                mid.tolist(), start_ns.tolist(), dur_ns.tolist())
                if m in meta and (device or meta[m][0] == WINDOW_SPAN)]
            if events:
                out.append({"name": lname, "events": events})
        planes.append({"name": name, "lines": out})
    return {"planes": planes}


# ---- the table ---------------------------------------------------------------

def owner_of(path: Optional[str]) -> Optional[Owner]:
    """``(type, name, innermost sub-scope or "", phase)`` of an
    ``op_name`` path as the profiler keeps it (``<path>:<op type>``, the
    type empty from JAX); None where no scope of the program's is in it."""
    if not path or parse_scope is None:
        return None
    parsed = parse_scope(path.rstrip(":"))
    if parsed is None:
        return None
    kind, name, subs, phase = parsed
    return kind, name, subs[-1] if subs else "", phase


def _columns(plane: Dict):
    """A device plane's ``XLA Ops`` as ``(kinds, kind, start_ns,
    duration_ns)``: from the columns the loader makes, or from a list an
    event (each event then an instruction of its own)."""
    import numpy as np

    for line in plane["lines"]:
        if line["name"] == reduce.OPS_LINE and "kind" in line:
            return (line["kinds"], line["kind"], line["start_ns"],
                    line["duration_ns"])
    events = reduce._events(plane, reduce.OPS_LINE)
    return ([(ev[0], ev[3]) for ev in events], np.arange(len(events)),
            np.array([ev[1] for ev in events], np.float64),
            np.array([ev[2] for ev in events], np.float64))


def _window(trace: Dict, dev: List[Dict]) -> Tuple[float, float]:
    """``reduce.reduce_trace``'s window: the ``bench.window`` spans of
    the host, else from the first to the last device operation."""
    spans = [ev for p in trace["planes"] if p["name"] == reduce.HOST_PLANE
             for ln in p["lines"] for ev in ln["events"]
             if ev[0] == WINDOW_SPAN]
    if spans:
        return (min(ev[1] for ev in spans),
                max(ev[1] + ev[2] for ev in spans))
    ops = [_columns(p) for p in dev]
    if not any(len(kind) for _, kind, _, _ in ops):
        raise ValueError("no operation ran on the device")
    return (min(float(start.min()) for _, kind, start, _ in ops if len(kind)),
            max(float((start + dur).max()) for _, kind, start, dur in ops
                if len(kind)))


def owner_table(trace: Dict, window: Optional[Tuple[float, float]] = None
                ) -> Dict:
    """Seconds a chip throughout (summed over the device planes, divided
    by them):

    * ``programs``: ``{name: {"count", "device_s", "busy_s", "rows":
      {owner or "(unowned)": s}, "xla": {owner: {XLA name: s}},
      "unowned": {XLA name: [s, path, one such instruction]}}}`` for the
      programs with an execution whole inside the window; ``busy_s`` is
      the sum of the rows, and ``xla`` says which of XLA's names each row
      is made of (the older records' names);
    * ``busy_s``, ``unowned_s``: over those programs;
    * ``scoped``: whether any operation of the trace lies under a scope
      of the program's (none: a program without the scheme, or an
      executable that a compile cache kept from one)."""
    import numpy as np

    dev = [p for p in trace["planes"] if reduce.DEVICE_PLANE.match(p["name"])]
    if not dev:
        raise ValueError("the trace has no /device:TPU:<n> plane")
    lo, hi = window or _window(trace, dev)
    n = len(dev)
    programs: Dict[str, Dict] = {}
    scoped = False

    def add(prog: str, kind, owner: Optional[Owner], ns: float) -> None:
        """``ns`` of exclusive time of the instruction ``kind`` (its
        owner, XLA base name, path and text) to its program's rows."""
        rec, (_, base, path, text) = programs[prog], kind
        who, alone = owner or UNOWNED, ns / 1e9
        rec["busy_s"] += alone
        rec["rows"][who] = rec["rows"].get(who, 0.0) + alone
        by_xla = rec["xla"].setdefault(who, {})
        by_xla[base] = by_xla.get(base, 0.0) + alone
        if owner is None:
            rec["unowned"].setdefault(base, [0.0, path, text])[0] += alone

    for plane in dev:
        runs = sorted((s, s + d, reduce._base_name(nm))
                      for nm, s, d, _ in reduce._events(plane,
                                                        reduce.MODULES_LINE)
                      if s >= lo and s + d <= hi)    # whole executions only
        for s, e, prog in runs:
            rec = programs.setdefault(prog, {
                "count": 0, "device_s": 0.0, "busy_s": 0.0, "rows": {},
                "xla": {}, "unowned": {}})
            rec["count"] += 1
            rec["device_s"] += (e - s) / 1e9
        kinds, kind, start, dur = _columns(plane)
        if not runs or not len(kind):
            continue
        # a serving window holds millions of events of a few thousand
        # distinct instructions: each instruction is looked at once, and
        # the events are summed as arrays
        kinds = [(owner_of(stats.get("tf_op")), reduce._base_name(name),
                  (stats.get("tf_op") or "").rstrip(":"), name)
                 for name, stats in kinds]
        names = sorted({r[2] for r in runs})
        # each operation to the execution that holds its start
        at = np.searchsorted([r[0] for r in runs], start, side="right") - 1
        keep = (at >= 0) & (start < np.array([r[1] for r in runs])[at])
        order = np.flatnonzero(keep)
        order = order[np.lexsort((-dur[order], start[order]))]
        start, dur, kind = start[order], dur[order], kind[order]
        prog = np.array([names.index(r[2]) for r in runs])[at[order]]
        # in the line's order of nesting, a parent stands before what it
        # contains: an operation is nested where one before it ends later
        ends = np.maximum.accumulate(start + dur)
        nested = np.concatenate(([False], start[1:] < ends[:-1]))
        tangled = nested | np.concatenate((nested[1:], [False]))
        cells = np.bincount(prog[~tangled] * len(kinds) + kind[~tangled],
                            weights=dur[~tangled])
        for cell in np.flatnonzero(cells):
            k = kinds[cell % len(kinds)]
            add(names[cell // len(kinds)], k, k[0], float(cells[cell]))
            scoped = scoped or k[0] is not None
        # what contains, and what is contained: exclusive time, children
        # before parents, and a pathless container takes the owner of
        # most of what it holds
        ops = [(float(start[j]), float(dur[j]), int(kind[j]), int(prog[j]))
               for j in np.flatnonzero(tangled)]
        parent, stack = [], []
        for j, (s, d, _, _) in enumerate(ops):
            while stack and ops[stack[-1]][0] + ops[stack[-1]][1] <= s:
                stack.pop()
            parent.append(stack[-1] if stack else -1)
            stack.append(j)
        inside: List[Optional[Dict]] = [None] * len(ops)
        held = [0.0] * len(ops)
        for j in range(len(ops) - 1, -1, -1):
            s, d, k, p = ops[j]
            own = kinds[k][0]
            if own is None and inside[j] and not kinds[k][2]:
                best = max(inside[j], key=inside[j].get)
                own = None if best == UNOWNED else best
            if parent[j] >= 0:
                i = parent[j]
                held[i] += d
                if inside[i] is None:
                    inside[i] = {}
                who = own or UNOWNED
                inside[i][who] = inside[i].get(who, 0.0) + d
            add(names[p], kinds[k], own, max(0.0, d - held[j]))
            scoped = scoped or own is not None
    for rec in programs.values():
        rec["count"] /= n
        rec["device_s"] /= n
        rec["busy_s"] /= n
        rec["rows"] = {k: v / n for k, v in rec["rows"].items()}
        rec["xla"] = {k: {b: v / n for b, v in names.items()}
                      for k, names in rec["xla"].items()}
        for cell in rec["unowned"].values():
            cell[0] /= n
    busy = sum(r["busy_s"] for r in programs.values())
    unowned = sum(r["rows"].get(UNOWNED, 0.0) for r in programs.values())
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy,
            "unowned_s": unowned, "scoped": scoped, "programs": programs}


# ---- what the readers ask ------------------------------------------------------

def table_of(run: Dict) -> Optional[Dict]:
    """The owner table of a traced run's own profile (the profiler's
    ``out_dir`` is ``<checkout>/.bench_work/<cell>/trace``), made once a
    run; None untraced, without a profile, or where nothing in it is
    scoped (a program without the scheme: its readers report nothing)."""
    if run.get("trace") is None:
        return None
    if "_owners" not in run:
        t0 = time.perf_counter()
        try:
            path = reduce.find_xplane(os.path.join(
                ROOT, ".bench_work", run["cell"]["workload"]["name"],
                "trace"))
            table = owner_table(load_xplane(path))
        except Exception as e:  # noqa: BLE001 — a reader reports nothing
            print(f"[bench] owners: no table ({type(e).__name__}: {e})",
                  flush=True)
            table = None
        else:
            print(f"[bench] owners: {os.path.getsize(path) / 1e6:.1f} MB of "
                  f"profile read and reduced in "
                  f"{time.perf_counter() - t0:.2f} s; scoped="
                  f"{table['scoped']}", flush=True)
        run["_owners"] = table if table and table["scoped"] else None
    return run["_owners"]


def owned_share(run: Dict) -> Optional[float]:
    """100 x (1 - ``(unowned)`` over the busy time of all programs of the
    window), in %."""
    table = table_of(run)
    if table is None or table["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - table["unowned_s"] / table["busy_s"])


def device_ms(run: Dict, program: str, group: Optional[str] = None,
              kinds: Tuple[str, ...] = (), subs: Tuple[str, ...] = ()
              ) -> Optional[float]:
    """Exclusive device milliseconds per execution of the programs whose
    name matches ``program``, summed over the owners of ``group`` (a
    group of the program's own table) or of the types ``kinds``, and,
    with ``subs``, over those sub-scopes alone; None where no such
    program ran whole inside the window or no such owner is in it."""
    table = table_of(run)
    if table is None:
        return None
    rx = re.compile(program)
    count = seconds = 0.0
    found = False
    for name, rec in table["programs"].items():
        if not rx.search(name):
            continue
        count += rec["count"]
        for owner, s in rec["rows"].items():
            if owner == UNOWNED:
                continue
            kind, _, sub, _ = owner
            if ((scope_group(kind) == group or kind in kinds)
                    and (not subs or sub in subs)):
                seconds += s
                found = True
    return 1e3 * seconds / count if found and count > 0 else None


# ---- the operator's use ---------------------------------------------------------

def render(table: Dict, by: str = "type", top: int = 10) -> str:
    """The table as text, a program a block. ``by``: ``"type"`` (a row a
    group, op type, sub-scope and phase, with the XLA names it is made
    of), ``"name"`` (a row an op) or ``"xla"`` (a row an XLA name, with
    the owners it is split among: how to read an older record)."""
    out = [f"window {table['window_s']:.3f} s, busy {table['busy_s']:.3f} s "
           f"in whole executions, unowned {table['unowned_s']:.3f} s "
           f"({100.0 * table['unowned_s'] / max(table['busy_s'], 1e-12):.1f}"
           f" %), scoped={table['scoped']}"]

    def label(owner):
        if owner == UNOWNED:
            return (UNOWNED,)
        kind, name, sub, phase = owner
        return owner if by == "name" else (scope_group(kind), kind, sub,
                                           phase)

    for prog, rec in sorted(table["programs"].items(),
                            key=lambda kv: -kv[1]["busy_s"]):
        per = 1e3 / max(rec["count"], 1e-12)
        out.append(f"\n{prog}: {rec['count']:g} executions, device "
                   f"{rec['device_s']:.4f} s ({rec['device_s'] * per:.3f} ms "
                   f"each), busy {rec['busy_s']:.4f} s")
        rows: Dict[Tuple, Dict[Tuple, float]] = {}
        for owner, names in rec["xla"].items():
            for base, s in names.items():
                key, part = ((base,), label(owner)) if by == "xla" else (
                    label(owner), (base,))
                cell = rows.setdefault(key, {})
                cell[part] = cell.get(part, 0.0) + s
        for key, parts in sorted(rows.items(),
                                 key=lambda kv: -sum(kv[1].values())):
            s = sum(parts.values())
            made = ", ".join(
                f"{' '.join(str(k) for k in part if k != '')} {v * per:.3f}"
                for part, v in sorted(parts.items(),
                                      key=lambda kv: -kv[1])[:4])
            out.append(f"  {s * per:9.4f} ms  "
                       f"{100.0 * s / max(rec['busy_s'], 1e-12):5.1f} %  "
                       + " ".join(str(k) for k in key if k != "")
                       + ("" if by == "name" else f"  <- {made}"))
        worst = sorted(rec["unowned"].items(), key=lambda kv: -kv[1][0])
        for base, (s, path, text) in worst[:top]:
            out.append(f"    (unowned) {s * per:9.4f} ms  {base}"
                       f"  [{path or 'no path'}]  e.g. {text[:200]}")
    return "\n".join(out)


def _main(argv) -> int:
    args = list(argv[1:])
    by = "type"
    if "--by" in args:
        by = args.pop(args.index("--by") + 1)
        args.remove("--by")
    if by not in ("type", "name", "xla") or len(args) != 1:
        print(__doc__.split("\n\n")[-2])
        return 2
    print(render(owner_table(load_xplane(reduce.find_xplane(args[0]))), by))
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(_main(sys.argv))
