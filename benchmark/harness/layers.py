"""Run every per-layer reader that applies to the cell.

A reader is a module under ``layer_metrics/`` with ``NAME``, ``UNIT``,
``LAYER``, ``MOVES``, ``BETTER``, ``SOURCE`` (what ``BENCHMARK.json`` says of
it) and ``read(record) -> float | None``; an optional
``applies(record) -> bool`` narrows it further.  A metric is reported
only in a cell that reports the end-to-end metric it moves, and only
where its reader finds something to read.  A reader that serves cells of
several kinds gives ``MOVES`` as a dict, one reported name per moved
metric (``catalog.load_layer_metrics``).

In a traced run the window itself is untraced: ``counters``, ``hist``,
``gen_lateness_ms``, ``input_stall_s`` and ``end_to_end`` describe the
window, ``trace`` the traced tail after it (``harness/tracing.py``).

The record a reader gets (keys present where the driver has them):
``window_s``, ``counters`` and ``hist`` (the program's RuntimeMetrics, of
the window alone), ``gen_lateness_ms``, ``input_stall_s``,
``end_to_end``, ``device`` (with ``memory_peak_bytes``), ``trace`` (the
reduced trace, see ``trace_reduce.reduce``), ``model_cfg``,
``runtime_cfg``, ``train_cfg``, ``cell``, ``traffic``, ``config``.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

from benchmark.harness import trace_reduce


def read_all(record: Dict, driver_end_to_end: Dict[str, str],
             metric_modules: Dict[str, object], say: Callable
             ) -> Tuple[Dict, Optional[Dict]]:
    reduced = None
    tracer = record.get("tracer")
    path = tracer.trace_file() if tracer is not None else None
    if path is not None:
        reduced = trace_reduce.reduce(trace_reduce.load(path))
    if reduced is None:
        say({"warning": "no device operation in the traced slice"})
    record["trace"] = reduced
    metrics = {}
    for name, metric in sorted(metric_modules.items()):
        if metric.moves not in driver_end_to_end:
            continue
        mod = metric.module
        applies = getattr(mod, "applies", None)
        if applies is not None and not applies(record):
            continue
        value = mod.read(record)
        if value is None:
            continue
        metrics[name] = {"value": float(value), "unit": mod.UNIT}
        warn = getattr(mod, "warn", None)
        if warn is not None:
            message = warn(float(value))
            if message:
                say({"warning": f"{name}: {message}"})
    return metrics, reduced
