"""Closed loop at full depth: every round submits one tick for each
session, then pumps until the queue is empty; rounds repeat until the
window's seconds have passed; then ``drain()``.

This is replay of a recorded day and catch-up after an outage: the
producer is never the limit, every flush is the largest bucket, linger
and shedding play no part.  A traced run measures the same untraced
window and then runs rounds for ``TAIL_S`` seconds more with the profiler
on (``benchmark/harness/tracing.py``).  Traffic parameters: ``sessions``.
"""

from __future__ import annotations

import time
from typing import Dict

import numpy as np

from benchmark.harness import schedule as sched
from benchmark.harness.serving import Rig
from benchmark.harness.tracing import TAIL_S, TailTracer, span

END_TO_END = {"ticks_per_s": "ticks/s"}
#: Rows are made for this many rounds at a time and walked on from where
#: the last block ended, so set-up does not depend on how fast the run is.
ROUNDS_PER_BLOCK = 8


def run(ctx) -> Dict:
    rig = Rig(ctx.config, ctx.seed, trace=ctx.trace, parts=ctx.parts)
    n_sessions = int(ctx.traffic["sessions"])
    rig.open_sessions(n_sessions)
    warm_results = rig.warm_buckets()

    t0 = time.perf_counter()
    # a fixed bank of rows, reused round after round: the model sees a
    # different row every tick of a session until the bank wraps
    session_of_tick = np.tile(
        np.arange(n_sessions, dtype=np.int32), ROUNDS_PER_BLOCK)
    bank = sched.walk_rows(rig.sessions, session_of_tick, ctx.seed, stream=1)
    bank = bank.reshape(ROUNDS_PER_BLOCK, n_sessions, -1)
    checked = set(rig.checked_sessions(np.ones(n_sessions, np.int64)))
    ctx.parts["schedule_and_rows"] = time.perf_counter() - t0

    out = drive(rig, bank, checked, ctx, warm_results)
    rig.close()
    return out


def drive(rig: Rig, bank, checked, ctx, warm_results) -> Dict:
    """The window (and, in a traced run, the traced tail after it) through
    an open stack; sessions carry on from whatever the rig served
    before."""
    seconds = ctx.seconds
    n_sessions = bank.shape[1]
    gateway = rig.gateway
    ids = rig.sessions.ids
    sid_index = rig.sid_index
    seq0 = rig.seq0.tolist()
    served = rig.served_so_far(checked, warm_results)
    answered = 0
    last_seq = [s - 1 for s in seq0]
    gaps = 0

    def take(results):
        nonlocal answered, gaps
        for r in results:
            s = sid_index[r.session_id]
            if r.seq != last_seq[s] + 1:
                gaps += 1
            last_seq[s] = r.seq
            if s in served:
                served[s][r.seq] = r.probabilities
        answered += len(results)

    submit, pump, clock = gateway.submit, gateway.pump, time.perf_counter
    batcher = gateway.batcher
    sent = 0
    rounds = 0

    def rounds_for(length_s: float) -> float:
        """Whole rounds until ``length_s`` have passed, then ``drain()``;
        returns the seconds it took."""
        nonlocal sent, rounds
        t_start = clock()
        while clock() - t_start < length_s:
            block = bank[rounds % ROUNDS_PER_BLOCK]
            with span("bench_round"):
                with span("bench_submit"):
                    for i in range(n_sessions):
                        submit(ids[i], block[i])
                sent += n_sessions
                with span("bench_pump"):
                    while len(batcher):
                        take(pump())
            rounds += 1
        with span("bench_drain"):
            take(gateway.drain())
        return clock() - t_start

    ctx.window_begins()
    rig.window_begin()
    elapsed = rounds_for(seconds)
    window = rig.window_end()
    ctx.window_ended()
    window_rounds, window_sent, window_answered = rounds, sent, answered

    c = window["counters"]
    failed = window_sent - window_answered
    # nothing is shed here (the queue bound is above a round), so every
    # row reached the model; a run that shed anyway fails counts_balance
    rows_by_session = {
        s: np.stack([bank[r % ROUNDS_PER_BLOCK, s]
                     for r in range(window_rounds)])
        for s in checked}
    seqs_by_session = {s: [seq0[s] + r for r in range(window_rounds)]
                       for s in checked}
    ref = rig.check_against_reference(rows_by_session, seqs_by_session,
                                      served)
    checks = rig.verdict(c, failed, ref, gaps == 0)
    correct = checks["correct"]

    # the same rounds for a few seconds more, traced (harness/tracing.py)
    tracer = TailTracer(ctx.trace, ctx.trace_dir)
    tail = None
    if ctx.trace:
        tracer.start()
        tail_s = rounds_for(TAIL_S)
        tracer.stop()
        tail = {"ticks_per_s": (answered - window_answered) / tail_s,
                "rounds": rounds - window_rounds,
                "trace_start_cost_s": tracer.start_cost_s,
                "trace_stop_cost_s": tracer.stop_cost_s}
    rig.seq0 = rig.seq0 + rounds
    return {
        "attempted": int(window_sent),
        "failed": int(failed),
        "correct": correct,
        "checks": checks,
        "end_to_end": {"ticks_per_s": window_answered / elapsed},
        "record": {
            "window_s": elapsed,
            "counters": c,
            "hist": window["hist"],
            "model_cfg": rig.model_cfg,
            "runtime_cfg": rig.cfg.runtime,
            "tracer": tracer,
        },
        "notes": {
            "rounds": window_rounds,
            "ticks_sent": int(window_sent),
            "ticks_answered": int(window_answered),
            "window_elapsed_s": elapsed,
            "traced_tail": tail,
        },
    }
