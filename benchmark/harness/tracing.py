"""Trace a short tail after the measured window with the JAX profiler.

A traced run measures its window untraced, exactly as an untraced run
does, and then drives a little more of the same traffic with the
profiler on: ``TAIL_S`` seconds of a serving cell's, a count of train
steps of a training cell's (:class:`StepSlice`).  Histograms, counters
and latencies are read from the window; only what needs the device's
clock (device time per step, idle share, the breakdown) is read from
the tail.  The profiler is never
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
import threading
import time
from typing import Callable, Optional

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
        self._slice_opened = 0.0
        self.start_cost_s = 0.0
        self.slice_s = 0.0
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
        self._slice_opened = time.perf_counter()

    def stop(self) -> None:
        if self._slice is None:
            return
        self._slice.__exit__(None, None, None)
        self._slice = None
        t0 = time.perf_counter()
        self.slice_s = t0 - self._slice_opened
        jax.profiler.stop_trace()
        self.stop_cost_s = time.perf_counter() - t0

    def trace_file(self) -> Optional[str]:
        files = sorted(glob.glob(os.path.join(
            self.out_dir, "plugins", "profile", "*", "*.xplane.pb")))
        return files[-1] if files else None


#: A step-counted slice keeps this many steps of its pass before it and
#: after it: the pass's first steps wait for the pass before to drain,
#: its last are followed by the drain itself.
EDGE_STEPS = 64
#: Steps of the pass kept spare for ``start_trace`` to return in.  It
#: takes 41-49 ms (my chip runs, PR 24 to 26), and the step loop stood
#: still meanwhile in every run so far (the slice opened at step 64 or
#: 65); a loop that runs on through it has this much room.
START_MARGIN_STEPS = 512
#: How often the tracer thread reads the program's step counter.
POLL_S = 0.001


class StepSlice:
    """Trace ``n_steps`` steps of a loop that the calling thread runs
    pass after pass, opened and closed by the program's own step counter
    and never by the clock: the profiler's stop costs a fixed time a
    *traced step* (0.06-0.15 s on the chip machine's host), so a slice
    of seconds grows with the program's speed, and the traced run with
    it.

    ``read_steps()`` is the program's count of steps so far; it may only
    move inside a pass.  :meth:`drive` runs passes on the calling thread
    and the profiler on a thread of its own, which polls the counter:

    - it calls ``tracer.start()`` (``start_trace``, then the slice span)
      once ``EDGE_STEPS`` of a pass are done, while enough remain for
      the profiler's start, the slice and ``EDGE_STEPS`` more;
    - it closes the slice once the counter has advanced by ``n_steps`` +
      1 since ``start()`` returned (the step in flight then began
      outside the slice), so ``n_steps`` whole steps or a few more
      *begin* inside it, and calls ``tracer.stop()`` at once;
    - ``closed`` is set first: the calling thread begins no pass after
      that, ends the one it is in and joins the thread, so nothing runs
      beside the profiler's stop but the rest of one pass.

    A pass too short for all that (``fits`` False: the rehearsal's tiny
    cells) is traced from wherever it is, across passes if need be;
    ``in_one_pass`` says whether the slice lay inside one pass,
    ``EDGE_STEPS`` clear of both its ends.
    """

    def __init__(self, tracer, read_steps: Callable[[], float],
                 n_steps: int, pass_steps: int) -> None:
        self.tracer = tracer
        self.read_steps = read_steps
        self.n_steps = int(n_steps)
        self.pass_steps = int(pass_steps)
        #: the last step of a pass at which ``start_trace`` may be called
        self._last_start = (self.pass_steps - EDGE_STEPS - self.n_steps - 1
                            - START_MARGIN_STEPS)
        self.fits = self._last_start >= EDGE_STEPS
        self.closed = threading.Event()
        self._pass_base: Optional[float] = None
        self._error: Optional[Exception] = None
        #: steps done, since the start of the pass it opened in, when the
        #: slice opened and when it closed
        self.opened_at = self.closed_at = None
        #: what the program counted while the slice was open
        self.traced_steps = 0
        self.in_one_pass = False

    def drive(self, one_pass: Callable[[], None]) -> int:
        """Run ``one_pass()`` on this thread until the slice has closed;
        the number of passes run."""
        thread = threading.Thread(target=self._trace, daemon=True,
                                  name="bench-tail-tracer")
        thread.start()
        passes = 0
        while not self.closed.is_set():
            count = self.read_steps()
            if count == self._pass_base:
                self._error = RuntimeError(
                    "the program's step counter did not move over a whole "
                    "pass: a step-counted slice has nothing to count")
                self.closed.set()
                break
            self._pass_base = count  # how far into the pass the loop is
            one_pass()
            passes += 1
        thread.join()
        if self._error is not None:
            raise self._error
        return passes

    def _wait(self, ready: Callable[[], bool]) -> bool:
        """Poll until ``ready()``; False if the calling thread gave up."""
        while not ready():
            if self.closed.is_set():
                return False
            time.sleep(POLL_S)
        return True

    def _room(self) -> bool:
        base = self._pass_base
        if base is None or not self.fits:
            return base is not None
        return EDGE_STEPS <= self.read_steps() - base <= self._last_start

    def _trace(self) -> None:
        try:
            if not self._wait(self._room):
                return
            self.tracer.start()
            base, opened = self._pass_base, self.read_steps()
            if self._wait(
                    lambda: self.read_steps() > opened + self.n_steps):
                closed = self.read_steps()
                self.traced_steps = int(closed - opened)
                self.opened_at = int(opened - base)
                self.closed_at = int(closed - base)
                self.in_one_pass = (
                    self.opened_at >= EDGE_STEPS
                    and self.closed_at <= self.pass_steps - EDGE_STEPS)
            self.closed.set()
            self.tracer.stop()
        except Exception as e:  # raised again by drive(), on its thread
            self._error = e
        finally:
            self.closed.set()
