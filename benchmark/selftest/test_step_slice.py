"""The step-counted slice of a traced training run
(``harness/tracing.py`` ``StepSlice``) against a fake step loop and a
stubbed profiler: it holds the steps asked for at any step rate, lies
inside one pass where the pass is long enough, and no pass begins once
it has closed."""

import time

import pytest

from benchmark.harness.tracing import EDGE_STEPS, StepSlice

#: steps the polling thread may see late: 1 ms polls of a loop of up to
#: 2,000 steps/s, with room for a loaded test machine
POLL_SLACK = 16


class FakeProfiler:
    """What ``StepSlice`` asks of a ``TailTracer``; ``start`` takes the
    47 ms the chip's ``start_trace`` does."""

    def __init__(self, loop):
        self.loop = loop
        self.starts = self.stops = 0
        self.stopped_during_pass = None

    def start(self):
        self.starts += 1
        time.sleep(0.047)

    def stop(self):
        self.stops += 1
        self.stopped_during_pass = self.loop.passes
        time.sleep(0.05)


class FakeLoop:
    """A trainer's ``fit(epochs=1)``: ``pass_steps`` counted train steps
    at about ``rate`` a second, then an eval pass and an epoch's end
    that count nothing."""

    def __init__(self, rate, pass_steps):
        self.rate, self.pass_steps = rate, pass_steps
        self.count = 0.0
        self.passes = 0
        self.begun_after_close = 0
        self.piece = None

    def one_pass(self):
        self.begun_after_close += self.piece.closed.is_set()
        for _ in range(self.pass_steps):
            time.sleep(1.0 / self.rate)
            self.count += 1.0
        time.sleep(0.02)
        self.passes += 1


def drive(rate, pass_steps, n_steps):
    loop = FakeLoop(rate, pass_steps)
    profiler = FakeProfiler(loop)
    loop.piece = StepSlice(profiler, lambda: loop.count, n_steps, pass_steps)
    passes = loop.piece.drive(loop.one_pass)
    return loop, profiler, passes


@pytest.mark.parametrize("rate", [500, 2000])
def test_the_slice_holds_the_steps_asked_for_at_any_step_rate(rate):
    loop, profiler, passes = drive(rate, pass_steps=1280, n_steps=256)
    piece = loop.piece
    assert profiler.starts == 1 and profiler.stops == 1
    # one more than asked: the step in flight when the slice opened began
    # outside it
    assert 257 <= piece.traced_steps <= 257 + POLL_SLACK
    # inside one pass, clear of its first and last steps
    assert piece.fits and piece.in_one_pass
    assert piece.opened_at >= EDGE_STEPS
    assert piece.closed_at <= 1280 - EDGE_STEPS
    assert piece.closed_at - piece.opened_at == piece.traced_steps
    # no pass began after the slice had closed: the one it closed in was
    # the last, and the profiler's stop began during it
    assert loop.begun_after_close == 0
    assert passes == loop.passes == profiler.stopped_during_pass + 1


def test_a_pass_too_short_is_traced_across_passes_and_says_so():
    loop, profiler, passes = drive(2000, pass_steps=100, n_steps=150)
    piece = loop.piece
    assert not piece.fits and not piece.in_one_pass
    assert profiler.starts == 1 and profiler.stops == 1
    assert 151 <= piece.traced_steps <= 151 + POLL_SLACK
    assert passes >= 2 and loop.begun_after_close == 0


def test_a_counter_that_never_moves_ends_the_run_with_an_error():
    profiler = FakeProfiler(None)
    piece = StepSlice(profiler, lambda: 0.0, 8, 1280)
    with pytest.raises(RuntimeError, match="did not move"):
        piece.drive(lambda: time.sleep(0.01))
    assert profiler.starts == 0 and piece.closed.is_set()


def test_a_profiler_that_fails_ends_the_run_with_its_error():
    loop = FakeLoop(2000, 100)

    class Broken(FakeProfiler):
        def start(self):
            raise OSError("no room for a trace")

    loop.piece = StepSlice(Broken(loop), lambda: loop.count, 8, 100)
    with pytest.raises(OSError, match="no room"):
        loop.piece.drive(loop.one_pass)
    assert loop.begun_after_close == 0
