"""From a profiler trace (``.xplane.pb``) to the program's own spans and
scopes: what the step loop's host thread did between steps, and which
part of the compiled step the device's time went to.

``trace_reduce`` attributes the device's idle gaps to host spans by name,
whatever thread wrote them; this module keeps the vocabulary the program
writes (docs/observability.md "Spans and scopes"): the host spans that
tile one trainer step (``trace_reduce.PROGRAM_SPANS``, the one tuple of
their names), on the thread that runs the steps, the module each device
operation ran in
(``jit_train_step`` ...) and the ``jax.named_scope`` path it was traced
under.  A program that writes none of them (an older commit) reduces to
empty tables, and every reader built on this returns None.

Like ``trace_reduce`` the reduction works on a plain structure, so that a
hand-built trace tests it::

    {"threads": {thread: [(name, start_ns, dur_ns), ...]},  # host spans
     "ops":     {plane: [(name, start_ns, dur_ns, module, scope), ...]}}

``thread`` is one line of the host plane (index and name: two threads may
share a name), ``module`` the compiled program the operation belongs to
(``jit_train_step``), ``scope`` its name-scope path without the leading
``jit(...)`` components and the primitive at its end
(``transpose(jvp(forward))/BiGRU/recurrence_fwd/while/body``).

Where the scope comes from.  The trace names a device operation by its
HLO instruction, and ``jax.profiler.ProfileData`` exposes an event's own
stats only; the scope (``tf_op``) and the program (``program_id``) are
stats of the event's *metadata*, which the file carries and that reader
does not show.  :func:`load` therefore reads the file's protobuf wire
format itself (:func:`_fields`: six message types of
``tsl/profiler/protobuf/xplane.proto``, field numbers below), with no
dependency beyond the standard library.
"""

from __future__ import annotations

import re
import struct
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from benchmark.harness import trace_reduce
from benchmark.harness.trace_reduce import (
    DEVICE_PLANE_PREFIX, OP_LINES, PROGRAM_SPANS, SLICE_SPAN, _clip, _gaps,
    _innermost_labels, _union)

Span = Tuple[str, float, float]              # name, start_ns, dur_ns
Op = Tuple[str, float, float, str, str]      # ... module, scope

#: The trainer's step annotations; the thread that carries most of them
#: is the step thread.
STEP_SPANS = ("train", "eval")
NO_SPAN = "(no program span)"
NO_SCOPE = "(no scope)"
MODULES_LINE = "XLA Modules"
#: First components of a train or eval step's scope paths.
STEP_SCOPE_ROOTS = ("forward", "loss", "optimizer", "metrics")

_PROGRAM_ID = re.compile(r"\((\d+)\)$")
_TRANSFORM = re.compile(r"^\w+\((.*)\)$")


# -- the file ------------------------------------------------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one protobuf message: an int for a
    varint, the bytes for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wire == 1:
            value = buf[i:i + 8]
            i += 8
        elif wire == 5:
            value = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield key >> 3, value


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _map_entry(buf) -> Tuple[int, bytes]:
    key, value = 0, b""
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _stat(buf, stat_names: Dict[int, str]) -> Tuple[str, object]:
    """XStat: metadata_id=1, double=2, uint64=3, int64=4, str=5, bytes=6,
    ref=7 (a string kept as the name of another stat metadata)."""
    name, value = "", None
    for f, v in _fields(buf):
        if f == 1:
            name = stat_names.get(v, "")
        elif f == 2:
            value = struct.unpack("<d", bytes(v))[0]
        elif f == 3:
            value = v
        elif f == 4:
            value = _signed(v)
        elif f in (5, 6):
            value = bytes(v).decode("utf-8", "replace")
        elif f == 7:
            value = stat_names.get(v, "")
    return name, value


def _plane(buf) -> Dict:
    """XPlane: name=2, lines=3, event_metadata=4, stat_metadata=5."""
    name, lines, event_meta, stat_meta = "", [], [], []
    for f, v in _fields(buf):
        if f == 2:
            name = bytes(v).decode()
        elif f == 3:
            lines.append(v)
        elif f == 4:
            event_meta.append(v)
        elif f == 5:
            stat_meta.append(v)
    return {"name": name, "lines": lines, "event_meta": event_meta,
            "stat_meta": stat_meta}


def _stat_names(plane: Dict) -> Dict[int, str]:
    """XStatMetadata: id=1, name=2."""
    out = {}
    for entry in plane["stat_meta"]:
        key, value = _map_entry(entry)
        for f, v in _fields(value):
            if f == 2:
                out[key] = bytes(v).decode()
    return out


def _event_metadata(plane: Dict, want_stats: Sequence[str] = ()
                    ) -> Dict[int, Tuple[str, Dict[str, object]]]:
    """XEventMetadata: id=1, name=2, stats=5 -> {id: (name, stats)}."""
    stat_names = _stat_names(plane) if want_stats else {}
    out = {}
    for entry in plane["event_meta"]:
        key, value = _map_entry(entry)
        name, stats = "", {}
        for f, v in _fields(value):
            if f == 2:
                name = bytes(v).decode("utf-8", "replace")
            elif f == 5 and want_stats:
                k, val = _stat(v, stat_names)
                if k in want_stats:
                    stats[k] = val
        out[key] = (name, stats)
    return out


def _line(buf) -> Tuple[str, int, List[bytes]]:
    """XLine: name=2, timestamp_ns=3, events=4."""
    name, t0, events = "", 0, []
    for f, v in _fields(buf):
        if f == 2:
            name = bytes(v).decode()
        elif f == 3:
            t0 = _signed(v)
        elif f == 4:
            events.append(v)
    return name, t0, events


def _event(buf) -> Tuple[int, int, int]:
    """XEvent: metadata_id=1, offset_ps=2, duration_ps=3."""
    meta = offset = dur = 0
    for f, v in _fields(buf):
        if f == 1:
            meta = v
        elif f == 2:
            offset = _signed(v)
        elif f == 3:
            dur = _signed(v)
    return meta, offset, dur


def scope_of(tf_op: str) -> str:
    """The name-scope path an operation was traced under, from the
    trace's ``tf_op`` stat: ``jit(train_step)/jvp(forward)/BiGRU/head/
    reduce_max:`` (op name, a colon, an op type that jax leaves empty)
    -> ``jvp(forward)/BiGRU/head``: without the leading ``jit(...)``
    components and the primitive at the end.  Empty for an operation
    traced under no scope, and for one whose name is not a traced
    operation's at all (an argument's, or none: the compiler's own)."""
    name = tf_op.rpartition(":")[0] if ":" in tf_op else tf_op
    if not name.startswith(("jit(", "pjit(")):
        return ""
    parts = name.split("/")
    while parts and parts[0].startswith(("jit(", "pjit(")):
        parts = parts[1:]
    return "/".join(parts[:-1])


def short_hlo_name(name: str) -> str:
    """``%fusion.28 = f32[...] fusion(...)`` -> ``fusion.28``."""
    return name.partition(" = ")[0].lstrip("%")[:96]


def load(path: str) -> Dict:
    """Read a trace file (as the profiler wrote it, or gzipped) into the
    plain structure :func:`reduce` takes."""
    import gzip

    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as fh:
        data = memoryview(fh.read())
    wanted = set(PROGRAM_SPANS) | {SLICE_SPAN}
    threads: Dict[str, List[Span]] = {}
    ops: Dict[str, List[Op]] = {}
    for f, v in _fields(data):
        if f != 1:  # XSpace.planes
            continue
        plane = _plane(v)
        if plane["name"].startswith("/host:"):
            names = {k: n for k, (n, _) in _event_metadata(plane).items()
                     if n in wanted}
            for i, raw in enumerate(plane["lines"]):
                line_name, t0, events = _line(raw)
                spans = []
                for ev in events:
                    meta, offset, dur = _event(ev)
                    if meta in names:
                        spans.append((names[meta], t0 + offset / 1e3,
                                      dur / 1e3))
                if spans:
                    threads[f"{i}:{line_name}"] = spans
        elif plane["name"].startswith(DEVICE_PLANE_PREFIX):
            meta = _event_metadata(plane, ("tf_op", "program_id"))
            lines = [_line(raw) for raw in plane["lines"]]
            modules = {}
            for line_name, _, events in lines:
                if line_name == MODULES_LINE:
                    for ev in events:
                        name = meta.get(_event(ev)[0], ("", {}))[0]
                        m = _PROGRAM_ID.search(name)
                        if m:
                            modules[int(m.group(1))] = name[:m.start()]
            out = []
            for line_name, t0, events in lines:
                if line_name not in OP_LINES:
                    continue
                for ev in events:
                    mid, offset, dur = _event(ev)
                    name, stats = meta.get(mid, ("", {}))
                    out.append((
                        short_hlo_name(name), t0 + offset / 1e3, dur / 1e3,
                        modules.get(stats.get("program_id"), ""),
                        scope_of(stats.get("tf_op") or "")))
            if out:
                ops[plane["name"]] = inherit_scopes(out)
    return {"threads": threads, "ops": ops}


def inherit_scopes(events: List[Op]) -> List[Op]:
    """An operation with no scope of its own that holds others takes the
    part of the scope path they share: the trace carries no ``tf_op`` for
    a ``while``, and its own time (loop control between the body's
    operations) belongs with the body it runs."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    shared: Dict[int, List[str]] = {}
    open_: List[int] = []
    for i in order:
        _, start, _, _, scope = events[i]
        while open_ and (events[open_[-1]][1] + events[open_[-1]][2]
                         <= start):
            open_.pop()
        if open_ and scope and not events[open_[-1]][4]:
            parts, holder = scope.split("/"), open_[-1]
            if holder in shared:
                n = 0
                for a, b in zip(shared[holder], parts):
                    if a != b:
                        break
                    n += 1
                parts = parts[:n]
            shared[holder] = parts
        open_.append(i)
    out = list(events)
    for i, parts in shared.items():
        out[i] = events[i][:4] + ("/".join(parts),)
    return out


# -- the reduction ---------------------------------------------------------------

def slice_bounds(trace: Dict) -> Optional[Tuple[float, float]]:
    """The ``bench_slice`` annotation where the benchmark wrote one, else
    the extent of everything kept (a capture made by ``train
    --jax-profile`` has no slice: the whole capture is read)."""
    starts, ends = [], []
    for spans in trace["threads"].values():
        for name, s, d in spans:
            if name == SLICE_SPAN:
                return (s, s + d)
            starts.append(s)
            ends.append(s + d)
    for events in trace["ops"].values():
        for _, s, d, _, _ in events:
            starts.append(s)
            ends.append(s + d)
    return (min(starts), max(ends)) if starts else None


def step_thread(trace: Dict, lo: float, hi: float) -> Optional[str]:
    """The host thread that carries the trainer's step annotations."""
    best, most = None, 0
    for thread, spans in sorted(trace["threads"].items()):
        n = sum(1 for name, s, _ in spans
                if name in STEP_SPANS and lo <= s < hi)
        if n > most:
            best, most = thread, n
    return best


def scope_root(scope: str) -> str:
    """First component of a scope path with the gradient's transforms
    taken off: ``transpose(jvp(forward))/...`` -> ``forward``."""
    root = scope.partition("/")[0]
    while True:
        m = _TRANSFORM.match(root)
        if not m:
            return root
        root = m.group(1)


def reduce(trace: Dict) -> Optional[Dict]:
    """Tables the readers and ``tools/span_report.py`` draw from.

    ``spans``: per program span on the step thread that *started* in the
    slice, its count, total and mean seconds (whole durations); spans of
    other threads under ``other_threads``.  ``self_s``: step-thread time
    in the slice under no program span.  ``idle_by_span``: the first
    device plane's idle gaps by the innermost program span of the step
    thread.  ``busy_by_module`` / ``busy_by_scope``: device seconds in
    the slice by compiled program and by (program, scope path), each
    instant counted once, for the innermost operation covering it.
    """
    bounds = slice_bounds(trace)
    if bounds is None or bounds[1] <= bounds[0]:
        return None
    lo, hi = bounds
    thread = step_thread(trace, lo, hi)

    def table(spans):
        out: Dict[str, Dict[str, float]] = {}
        for name, s, d in spans:
            if name == SLICE_SPAN or not lo <= s < hi:
                continue
            row = out.setdefault(name, {"count": 0, "total_s": 0.0})
            row["count"] += 1
            row["total_s"] += d / 1e9
        for row in out.values():
            row["mean_s"] = row["total_s"] / row["count"]
        return out

    mine = [sp for sp in trace["threads"].get(thread, ())
            if sp[0] != SLICE_SPAN]
    covered = sum(e - s for s, e in _union(_clip(mine, lo, hi)))
    result = {
        "window_s": (hi - lo) / 1e9,
        "step_thread": thread,
        "spans": table(mine),
        "other_threads": {t: rows for t, rows in (
            (t, table(sp)) for t, sp in sorted(trace["threads"].items())
            if t != thread) if rows},
        "self_s": ((hi - lo) - covered) / 1e9 if thread else None,
        "idle_by_span": {}, "busy_s": None,
        "busy_by_module": {}, "busy_by_scope": {},
    }
    if not trace["ops"]:
        return result
    plane = sorted(trace["ops"])[0]
    events = trace["ops"][plane]
    busy = _union(_clip([(n, s, d) for n, s, d, _, _ in events], lo, hi))
    result["busy_s"] = sum(e - s for s, e in busy) / 1e9

    # idle gaps of the device, by what the step thread was inside
    labels = _innermost_labels(mine, lo, hi)
    idle: Dict[str, float] = {}
    j = 0
    for gs, ge in _gaps(busy, lo, hi):
        while j < len(labels) and labels[j][1] <= gs:
            j += 1
        k = j
        while k < len(labels) and labels[k][0] < ge:
            a, b = max(labels[k][0], gs), min(labels[k][1], ge)
            name = labels[k][2]
            if name == trace_reduce.NO_SPAN:
                name = NO_SPAN
            if b > a:
                idle[name] = idle.get(name, 0.0) + (b - a) / 1e9
            k += 1
    result["idle_by_span"] = idle

    # busy time by program and scope: label every instant with the
    # innermost operation covering it (a ``while`` holds its body's)
    tagged = [(f"{module}\t{scope or NO_SCOPE}\t{name}", s, d)
              for name, s, d, module, scope in events]
    by_op: Dict[str, float] = {}
    for a, b, label in _innermost_labels(tagged, lo, hi):
        if label != trace_reduce.NO_SPAN:
            by_op[label] = by_op.get(label, 0.0) + (b - a) / 1e9
    for label, seconds in by_op.items():
        module, scope, name = label.split("\t")
        result["busy_by_module"][module] = (
            result["busy_by_module"].get(module, 0.0) + seconds)
        key = (module, scope, name if scope == NO_SCOPE else "")
        result["busy_by_scope"][key] = (
            result["busy_by_scope"].get(key, 0.0) + seconds)
    return result


def for_record(record: Dict) -> Optional[Dict]:
    """The reduction of the run's traced tail, loaded once a run and kept
    on the record for the readers that follow."""
    if "program_spans" not in record:
        tracer = record.get("tracer")
        path = tracer.trace_file() if tracer is not None else None
        record["program_spans"] = (
            reduce(load(path)) if path is not None else None)
    return record["program_spans"]


# -- what the readers under layer_metrics/ report ---------------------------------

def span_mean_us(name: str):
    """Mean duration, in microseconds, of the step thread's ``name``
    spans that started in the slice; None where the program wrote none."""
    def read(record):
        r = for_record(record)
        row = r and r["spans"].get(name)
        return row["mean_s"] * 1e6 if row else None
    return read


def loop_self_us(record):
    """Step-thread time in the slice under no program span, a train step
    started in it.  Only where the program's spans tile the step (it
    writes ``train_fold``): without them the remainder is not the loop's
    own Python but everything unnamed."""
    r = for_record(record)
    if not r or "train_fold" not in r["spans"] or "train" not in r["spans"]:
        return None
    return r["self_s"] / r["spans"]["train"]["count"] * 1e6


def recurrence_dev_share(record):
    """Device busy time of operations traced under a ``recurrence_*``
    scope over device busy time, train-step program only, per cent."""
    r = for_record(record)
    if not r:
        return None
    total = rec = 0.0
    for (module, scope, _), seconds in r["busy_by_scope"].items():
        if module != "jit_train_step":
            continue
        total += seconds
        if "recurrence_" in scope:
            rec += seconds
    return 100.0 * rec / total if total else None
