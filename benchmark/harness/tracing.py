"""Trace a short tail after the measured window with the JAX profiler.

A traced run measures its window untraced, exactly as an untraced run
does, and then drives ``TAIL_S`` more seconds of the same traffic with
the profiler on.  Histograms, counters and latencies are read from the
window; only what needs the device's clock (device time per step, idle
share, the breakdown) is read from the tail.  The profiler is never
started or stopped inside the window or while traffic is due: its stop
alone held the serving thread for 2.1 s (my chip run, PR 23), which in
an open loop is a backlog the slice never recovers from.

The Python tracer is off and the host tracer records annotations only
(level 1): the host plane then holds the benchmark's ``bench_*`` spans,
the gateway's ``pool_flush`` and the trainer's ``train``/``eval``, and
not an event per Python call or per runtime dispatch — PR 22's
five-flush capture held 503k host events with the Python tracer on.
"""

from __future__ import annotations

import glob
import os
import shutil
import time
from typing import Optional

import jax

#: Seconds of traffic traced after the window (one burst inside it).
TAIL_S = 3.0


def span(name: str):
    """A host span on the profiler's clock (free when nothing traces)."""
    return jax.profiler.TraceAnnotation(name)


class TailTracer:
    """``start()`` before the tail's traffic, ``stop()`` after it.
    Disabled (``enabled=False``) both do nothing."""

    def __init__(self, enabled: bool, out_dir: str) -> None:
        self.enabled = enabled
        self.out_dir = out_dir
        self._slice = None
        self.start_cost_s = 0.0
        self.stop_cost_s = 0.0

    def start(self) -> None:
        if not self.enabled:
            return
        shutil.rmtree(self.out_dir, ignore_errors=True)
        os.makedirs(self.out_dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        t0 = time.perf_counter()
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        self.start_cost_s = time.perf_counter() - t0
        from benchmark.harness.trace_reduce import SLICE_SPAN

        self._slice = jax.profiler.TraceAnnotation(SLICE_SPAN)
        self._slice.__enter__()

    def stop(self) -> None:
        if self._slice is None:
            return
        self._slice.__exit__(None, None, None)
        self._slice = None
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        self.stop_cost_s = time.perf_counter() - t0

    def trace_file(self) -> Optional[str]:
        files = sorted(glob.glob(os.path.join(
            self.out_dir, "plugins", "profile", "*", "*.xplane.pb")))
        return files[-1] if files else None
