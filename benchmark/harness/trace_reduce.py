"""From a profiler trace (``.xplane.pb``) to busy time, idle share, the
heaviest device operations and the idle gaps by what the host was doing.

The reduction works on a plain structure, so that a hand-built trace
tests it::

    {"device": {plane_name: [(name, start_ns, dur_ns), ...]},   # op events
     "host":   [(name, start_ns, dur_ns), ...]}                 # annotations

:func:`load` fills it from a file with ``jax.profiler.ProfileData``.

Which events count.  A TPU plane (``/device:TPU:<n>``) carries several
lines; only lines of single operations count as busy time
(:data:`OP_LINES`) — the ``Steps`` and ``XLA Modules`` lines span whole
programs, idle gaps between their operations included, and would hide
them.  Host events count only if they are annotations the benchmark or
the program wrote (:data:`HOST_SPANS`); with the Python tracer off the
host plane holds little else.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]  # name, start_ns, dur_ns

DEVICE_PLANE_PREFIX = "/device:TPU:"
#: Lines of a device plane whose events are single operations.
OP_LINES = ("XLA Ops",)
#: Lines that span whole programs or steps: never busy time.
SPAN_LINES = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Ops",
              "Framework Name Scope", "Source code")
#: The slice the harness traced: opened after ``start_trace`` returned,
#: closed before ``stop_trace`` is called.
SLICE_SPAN = "bench_slice"
#: Host spans the program writes around the parts of one trainer step
#: (``Trainer._run_batches``, ``Trainer.fit``, ``data.prefetch_batches``);
#: ``train``/``eval`` are its step annotations.
PROGRAM_SPANS = (
    "train_next_batch", "train", "train_fold", "train_pass_drain",
    "eval_next_batch", "eval", "eval_fold", "eval_pass_drain",
    "fit_epoch_end", "input_compose", "input_place")
#: Host annotations gaps are attributed to.  ``bench_*`` come from the
#: benchmark's own drivers; ``pool_flush`` is the gateway's
#: StepTraceAnnotation; the rest are the trainer's.
HOST_SPANS = ("bench_submit", "bench_pump", "bench_wait", "bench_round",
              "bench_drain", "bench_epoch", "pool_flush") + PROGRAM_SPANS
NO_SPAN = "(no host span)"
#: The program's step annotations: one per pool flush / train step.
STEP_SPANS = ("pool_flush", "train", "eval")


_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")


def short_op_name(name: str) -> str:
    """The trace names a device operation by its whole HLO line; keep the
    instruction's name and the first shape of its result:
    ``%copy.9 = f32[2883585,108]{1,0:T(8,128)} copy(...)`` becomes
    ``copy.9 f32[2883585,108]``."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:96]
    shape = _SHAPE.search(rest)
    head = head.lstrip("%")
    return f"{head} {shape.group(0)}" if shape else head


def load(path: str) -> Dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    wanted = set(HOST_SPANS) | {SLICE_SPAN}
    device: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            lines = list(plane.lines)
            op_lines = [ln for ln in lines if ln.name in OP_LINES]
            if not op_lines:
                op_lines = [ln for ln in lines if ln.name not in SPAN_LINES]
            events = [(short_op_name(e.name), float(e.start_ns),
                       float(e.duration_ns))
                      for ln in op_lines for e in ln.events]
            if events:
                device[plane.name] = events
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name in wanted:
                        host.append((e.name, float(e.start_ns),
                                     float(e.duration_ns)))
    return {"device": device, "host": host}


# -- interval arithmetic -----------------------------------------------------

def _union(intervals: Sequence[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(events: Sequence[Event], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    out = []
    for _, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((a, b))
    return out


def _gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    out = []
    t = lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def slice_bounds(trace: Dict) -> Optional[Tuple[float, float]]:
    """The traced slice: the ``bench_slice`` annotation where there is
    one, else the extent of the device events."""
    for name, s, d in trace["host"]:
        if name == SLICE_SPAN:
            return (s, s + d)
    starts = [s for evs in trace["device"].values() for _, s, _ in evs]
    ends = [s + d for evs in trace["device"].values() for _, s, d in evs]
    if not starts:
        return None
    return (min(starts), max(ends))


def _innermost_labels(host: Sequence[Event], lo: float, hi: float
                      ) -> List[Tuple[float, float, str]]:
    """Cut [lo, hi] into segments labelled with the innermost host span
    covering each (the span that started last), or NO_SPAN."""
    spans = [(s, s + d, n) for n, s, d in host
             if n != SLICE_SPAN and s + d > lo and s < hi]
    cuts = sorted({lo, hi}
                  | {min(max(s, lo), hi) for s, _, _ in spans}
                  | {min(max(e, lo), hi) for _, e, _ in spans})
    spans.sort()
    out: List[Tuple[float, float, str]] = []
    active: List[Tuple[float, float, str]] = []
    i = 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(spans) and spans[i][0] <= a:
            active.append(spans[i])
            i += 1
        active = [sp for sp in active if sp[1] > a]
        label = max(active)[2] if active else NO_SPAN
        if out and out[-1][2] == label and out[-1][1] == a:
            out[-1] = (out[-1][0], b, label)
        else:
            out.append((a, b, label))
    return out


def reduce(trace: Dict, *, top: int = 10) -> Optional[Dict]:
    """Everything the per-layer readers and the ``breakdown`` need.

    ``busy_s`` is the union of the op intervals inside the slice,
    averaged over the device planes that ran anything; ``idle_share`` is
    1 - busy over the slice.  ``idle_by_span`` sums, over every idle gap
    of the first device plane, the seconds each host span covered (the
    innermost one where they nest).  ``steps`` counts the program's step
    annotations that *started* inside the slice.
    """
    bounds = slice_bounds(trace)
    if bounds is None or not trace["device"]:
        return None
    lo, hi = bounds
    if hi <= lo:
        return None
    busy_per_plane = {}
    for plane, events in sorted(trace["device"].items()):
        busy_per_plane[plane] = _union(_clip(events, lo, hi))
    busy_ns = [sum(e - s for s, e in b) for b in busy_per_plane.values()]
    busy_s = sum(busy_ns) / len(busy_ns) / 1e9
    window_s = (hi - lo) / 1e9

    op_time: Dict[str, float] = {}
    for events in trace["device"].values():
        for name, s, d in events:
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                op_time[name] = op_time.get(name, 0.0) + (b - a) / 1e9
    n_planes = len(trace["device"])
    device_ops = sorted(((n, t / n_planes) for n, t in op_time.items()),
                        key=lambda kv: -kv[1])[:top]

    first_plane = sorted(busy_per_plane)[0]
    gaps = _gaps(busy_per_plane[first_plane], lo, hi)
    labels = _innermost_labels(trace["host"], lo, hi)
    idle_by: Dict[str, float] = {}
    j = 0
    for gs, ge in gaps:
        while j < len(labels) and labels[j][1] <= gs:
            j += 1
        k = j
        while k < len(labels) and labels[k][0] < ge:
            a, b = max(labels[k][0], gs), min(labels[k][1], ge)
            if b > a:
                idle_by[labels[k][2]] = (
                    idle_by.get(labels[k][2], 0.0) + (b - a) / 1e9)
            k += 1
    idle_by_span = sorted(idle_by.items(), key=lambda kv: -kv[1])[:top]
    longest = max((e - s for s, e in gaps), default=0.0) / 1e9

    steps = {n: 0 for n in STEP_SPANS}
    for name, s, _ in trace["host"]:
        if name in steps and lo <= s < hi:
            steps[name] += 1
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s,
        "device_ops": [[n, t] for n, t in device_ops],
        "idle_by_span": [[n, t] for n, t in idle_by_span],
        "longest_gap_s": longest,
        "n_gaps": len(gaps),
        "steps": steps,
        "n_device_planes": n_planes,
    }
