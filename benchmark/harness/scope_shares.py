"""Device time of the train step by named scope: the arithmetic shared by
the decoder cell's per-layer readers.

``program_spans.for_record`` reduces the traced slice to device seconds
by (compiled program, scope path); a scope *named* ``moe_experts`` shows
there as a component of the path, under the gradient's transforms and
the recomputation's (``transpose(jvp(forward))/.../checkpoint/.../
moe_experts``), so a reader asks for a component, never for a whole
path.  A program that writes none of the components asked for (an older
commit, another family) gives None, never 0.
"""

from __future__ import annotations

import statistics
from typing import Optional, Sequence

from benchmark.harness import program_spans
from benchmark.harness.device import peaks_for
from benchmark.harness.program_spans import (
    _TRANSFORM, _event, _event_metadata, _fields, _line, _plane)
from benchmark.harness.trace_reduce import DEVICE_PLANE_PREFIX, SLICE_SPAN

STEP_PROGRAM = "jit_train_step"


def _components(scope: str):
    for part in scope.split("/"):
        while True:  # transpose(jvp(loss)) -> loss
            m = _TRANSFORM.match(part)
            if not m:
                break
            part = m.group(1)
        yield part


def scope_seconds(record, names: Sequence[str]) -> Optional[float]:
    """Device seconds of the train-step program, in the traced slice,
    under any scope component in ``names``; None where there is no trace
    or none of the components occurs."""
    r = program_spans.for_record(record)
    if not r:
        return None
    wanted, found, total = set(names), False, 0.0
    for (module, scope, _), seconds in r["busy_by_scope"].items():
        if module == STEP_PROGRAM and wanted.intersection(_components(scope)):
            total += seconds
            found = True
    return total if found else None


def step_seconds(record) -> Optional[float]:
    r = program_spans.for_record(record)
    if not r:
        return None
    total = sum(s for (module, _, _), s in r["busy_by_scope"].items()
                if module == STEP_PROGRAM)
    return total or None


def dev_share(*names: str):
    """A reader: busy time under the named scopes over the train step's
    busy time, per cent."""
    def read(record):
        part, whole = scope_seconds(record, names), step_seconds(record)
        if part is None or not whole:
            return None
        return 100.0 * part / whole
    return read


def program_runs_in_slice(path: str, program: str = STEP_PROGRAM
                          ) -> Optional[float]:
    """How many executions of ``program`` the first device ran inside
    the traced slice, from the trace's own ``XLA Modules`` line: whole
    executions count one, those the slice's edges cut count the part of
    a typical execution (the median of the whole ones) they have inside.
    None where the trace has no slice, no device plane or no whole
    execution."""
    import gzip

    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as fh:
        data = memoryview(fh.read())
    bounds, runs = None, None
    for f, v in _fields(data):
        if f != 1:  # XSpace.planes
            continue
        plane = _plane(v)
        host = plane["name"].startswith("/host:")
        if not host and not (plane["name"].startswith(DEVICE_PLANE_PREFIX)
                             and runs is None):
            continue
        names = {k: n for k, (n, _) in _event_metadata(plane).items()}
        for raw in plane["lines"]:
            line_name, t0, events = _line(raw)
            if not host and line_name != program_spans.MODULES_LINE:
                continue
            found = []
            for ev in events:
                meta, offset, dur = _event(ev)
                name = names.get(meta, "")
                if host and name == SLICE_SPAN:
                    bounds = (t0 + offset / 1e3, t0 + (offset + dur) / 1e3)
                elif not host and name.startswith(program + "("):
                    found.append((t0 + offset / 1e3, dur / 1e3))
            if not host:
                runs = found
    if not bounds or not runs:
        return None
    lo, hi = bounds
    whole = [d for s, d in runs if s >= lo and s + d <= hi]
    if not whole:
        return None
    inside = sum(max(0.0, min(s + d, hi) - max(s, lo)) for s, d in runs)
    return inside / statistics.median(whole)


def traced_train_steps(record) -> Optional[float]:
    """Train steps the device ran in the traced slice.  Read from the
    device's own line of the trace (:func:`program_runs_in_slice`): in a
    device-paced cell the host is a pass ahead, and the ``train``
    annotations that began in the slice (the fall-back) are the pass's,
    not the slice's."""
    if "device_train_steps" not in record:
        tracer = record.get("tracer")
        path = tracer.trace_file() if tracer is not None else None
        record["device_train_steps"] = (
            program_runs_in_slice(path) if path is not None else None)
    if record["device_train_steps"]:
        return record["device_train_steps"]
    t = record.get("trace")
    steps = t["steps"].get("train", 0) if t else 0
    return steps or None


def roofline_share(record, names: Sequence[str], flops: float,
                   bytes_: float) -> Optional[float]:
    """The least time the chip could take for ``flops`` and ``bytes_``
    (the larger of the two bounds) over the device time under the named
    scopes, per cent; None off a TPU or where the scopes are absent."""
    if record["device"]["platform"] != "tpu":
        return None
    seconds = scope_seconds(record, names)
    if not seconds:
        return None
    peak_flops, peak_bytes = peaks_for(record["device"]["kind"])
    return 100.0 * max(flops / peak_flops, bytes_ / peak_bytes) / seconds
