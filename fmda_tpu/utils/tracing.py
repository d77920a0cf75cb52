"""Lightweight tracing/profiling for pipeline stages.

The reference has no tracing (SURVEY.md §5) — its only timing is the
sleep-budget measurement in producer.py:115/147-150.  Here every pipeline
stage can be wrapped in a :class:`StageTimer`; host regions that should
stand beside the device's operations in a JAX profile use :func:`span`
/ :func:`step_annotation` (the profiler's own clock; a flag test when
nothing is being captured), and device-side regions call
``jax.named_scope`` where the work is traced.  The names both kinds use
are one vocabulary: docs/observability.md "Spans and scopes".
"""

from __future__ import annotations

import contextlib
import logging
import threading
import time
from collections import defaultdict
from typing import Dict, Iterator

log = logging.getLogger("fmda_tpu")


class StageTimer:
    """Accumulates wall-clock per named stage; cheap enough for hot loops.

    Thread-safe: one lock around the accumulator writes and the summary
    read.  A timer is shared between writers and readers (the fleet
    gateway's flush path observes stages while ``/metrics`` scrapes and
    ``Application.stage_timings`` read the summary), and a bare
    ``defaultdict`` mutation racing a concurrent ``summary()`` iteration
    is a RuntimeError waiting for load.  The stage body itself runs
    outside the lock — only the two dict updates are serialised.
    """

    def __init__(self) -> None:
        self.totals: Dict[str, float] = defaultdict(float)
        self.counts: Dict[str, int] = defaultdict(int)
        self._lock = threading.Lock()

    @contextlib.contextmanager
    def stage(self, name: str) -> Iterator[None]:
        start = time.perf_counter()
        try:
            yield
        finally:
            elapsed = time.perf_counter() - start
            with self._lock:
                self.totals[name] += elapsed
                self.counts[name] += 1

    def observe(self, name: str, seconds: float) -> None:
        """Record an already-measured duration (callers that time with
        their own clock, e.g. the gateway's multi-point flush path)."""
        with self._lock:
            self.totals[name] += seconds
            self.counts[name] += 1

    def summary(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            return {
                name: {
                    "total_s": total,
                    "count": self.counts[name],
                    "mean_s": total / max(self.counts[name], 1),
                }
                for name, total in self.totals.items()
            }

    def log_summary(self, level: int = logging.INFO) -> None:
        for name, stats in sorted(self.summary().items()):
            log.log(
                level,
                "stage %-24s total=%.4fs count=%d mean=%.6fs",
                name,
                stats["total_s"],
                int(stats["count"]),
                stats["mean_s"],
            )


def span(name: str, **args):
    """A host span on the profiler's own clock — a
    ``jax.profiler.TraceAnnotation`` to enter with ``with``.  It stands
    on its thread's line of a captured profile beside the device's
    operations, with ``args`` as the event's arguments (the trainer's
    pass-level spans carry ``epoch``, so the spans of one epoch share an
    identifier); when no profile is being captured it costs a flag
    test."""
    import jax  # deferred: keep stdlib-only users of this module jax-free

    return jax.profiler.TraceAnnotation(name, **args)


def step_annotation(name: str, step: int):
    """Mark one step (a train step, a pool flush) in a captured profile:
    the ``jax.profiler.StepTraceAnnotation`` itself, to enter with
    ``with``.  Like :func:`span`, a flag test when nothing traces."""
    import jax

    return jax.profiler.StepTraceAnnotation(name, step_num=step)


@contextlib.contextmanager
def device_trace(log_dir: str) -> Iterator[None]:
    """Capture a JAX device profile (TensorBoard/XProf trace) of the
    enclosed region.  Wrap a few steps of a hot loop, not a whole run —
    traces are large.  View with ``tensorboard --logdir <log_dir>``, or
    read the program's spans and scopes out of it with
    ``python benchmark/tools/span_report.py <file.xplane.pb>``."""
    import jax

    with jax.profiler.trace(log_dir):
        yield
