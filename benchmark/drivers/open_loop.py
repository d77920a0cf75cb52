"""Open-loop serving: ticks are sent when they are *due*, whether or not
earlier ones have been answered, and each is timed from its due time.

One thread, as the gateway is built (``submit`` and ``pump`` are not
thread-safe against each other): submit everything due, ``pump()``,
never sleep past the next due time.  A stall anywhere — in the gateway,
on the device, in this loop — therefore lengthens the latency of every
tick that came due meanwhile, which is what a client sees.

A traced run measures the same untraced window and then drives
``TAIL_S`` seconds more of the same traffic (the next seed's schedule)
with the profiler on: see ``benchmark/harness/tracing.py``.

Traffic parameters (the traffic file): ``sessions``,
``rate_ticks_per_s``, ``skew_exponent``, ``hot_session_cap_ticks_per_s``,
``burst_every_s``, ``burst_first_s``, ``burst_reserve_s``,
``burst_sessions_fraction``.  See ``benchmark/harness/schedule.py``.
"""

from __future__ import annotations

import time
import types
from typing import Dict

import numpy as np

from benchmark.harness import schedule as sched
from benchmark.harness.serving import Rig
from benchmark.harness.stats import percentile
from benchmark.harness.tracing import TAIL_S, TailTracer, span

END_TO_END = {"tick_p50_ms": "ms", "tick_p99_ms": "ms"}
#: After the last tick is sent and drained, how long to keep pumping for
#: answers before the rest count as failed.
ANSWER_GRACE_S = 5.0
#: Queue depth is sampled at most this often (the knee sweep reads it).
DEPTH_EVERY_S = 0.005


def run(ctx) -> Dict:
    traffic, seconds = ctx.traffic, ctx.seconds
    rig = Rig(ctx.config, ctx.seed, trace=ctx.trace, parts=ctx.parts)
    rig.open_sessions(int(traffic["sessions"]))
    warm_results = rig.warm_buckets()

    t0 = time.perf_counter()
    plan = sched.make_schedule(traffic, ctx.seed, seconds)
    rows = sched.walk_rows(rig.sessions, plan.session, ctx.seed, stream=1)
    n_sessions = len(rig.sessions.ids)
    index = sched.index_ticks(plan.session, n_sessions, rig.seq0)
    ticks_per_session = np.bincount(plan.session, minlength=n_sessions)
    checked = set(rig.checked_sessions(ticks_per_session))
    if ctx.trace:
        tail_plan = sched.make_schedule(traffic, ctx.seed + 1, TAIL_S)
        tail_rows = sched.walk_rows(rig.sessions, tail_plan.session,
                                    ctx.seed + 1, stream=2)
    ctx.parts["schedule_and_rows"] = time.perf_counter() - t0

    out = drive(rig, plan, rows, index, checked, ctx, warm_results)
    tracer = TailTracer(ctx.trace, ctx.trace_dir)
    if ctx.trace:
        # the window is over and drained: nothing is due while the
        # profiler starts, and the tail is the same traffic, traced
        tail_index = sched.index_ticks(
            tail_plan.session, n_sessions, rig.seq0 + ticks_per_session)
        tail_ctx = types.SimpleNamespace(
            seconds=TAIL_S, window_begins=lambda: None,
            window_ended=lambda: None)
        tracer.start()
        tail = drive(rig, tail_plan, tail_rows, tail_index, set(), tail_ctx,
                     [])
        tracer.stop()
        out["notes"]["traced_tail"] = {
            "ticks_sent": tail["attempted"], "failed": tail["failed"],
            **tail["end_to_end"],
            "gen_lateness_p99_ms_calm":
                tail["notes"]["gen_lateness_p99_ms_calm"],
            "burst_drain_ms": tail["notes"]["burst_drain_ms"],
            "trace_start_cost_s": tracer.start_cost_s,
            "trace_stop_cost_s": tracer.stop_cost_s}
    out["record"]["tracer"] = tracer
    rig.close()
    return out


def drive(rig: Rig, plan, rows, index, checked, ctx, warm_results) -> Dict:
    gateway = rig.gateway
    seconds = ctx.seconds
    n = len(plan)
    due = plan.due
    due_list = due.tolist()
    ids = rig.sessions.ids
    sess_list = plan.session.tolist()
    sid_index = rig.sid_index
    sent_at = np.full(n, np.nan)
    done_at = np.full(n, np.nan)
    last_seq = [-1] * len(ids)
    served = rig.served_so_far(checked, warm_results)
    tick_of = index.tick_of
    problems = {"unknown": 0, "duplicate": 0, "out_of_order": 0}
    depth_t, depth_v = [], []

    def take(results, now):
        for r in results:
            s = sid_index[r.session_id]
            seq = r.seq
            tick = tick_of(s, seq)
            if tick < 0:
                problems["unknown"] += 1
                continue
            if done_at[tick] == done_at[tick]:  # not NaN: answered twice
                problems["duplicate"] += 1
                continue
            if seq <= last_seq[s]:
                problems["out_of_order"] += 1
            last_seq[s] = seq
            done_at[tick] = now
            if s in served:
                served[s][seq] = r.probabilities

    submit, pump, clock = gateway.submit, gateway.pump, time.perf_counter
    batcher = gateway.batcher

    ctx.window_begins()
    rig.window_begin()
    t_start = clock()
    i = 0
    next_depth = 0.0
    while i < n:
        now = clock() - t_start
        if due_list[i] <= now:
            with span("bench_submit"):
                while i < n and due_list[i] <= now:
                    submit(ids[sess_list[i]], rows[i])
                    sent_at[i] = clock() - t_start
                    i += 1
                    if not i & 63:
                        now = clock() - t_start
        with span("bench_pump"):
            results = pump()
        if results:
            take(results, clock() - t_start)
        now = clock() - t_start
        if now >= next_depth:
            depth_t.append(now)
            depth_v.append(len(batcher))
            next_depth = now + DEPTH_EVERY_S
        if i < n:
            gap = due_list[i] - now
            if gap > 3e-4:
                with span("bench_wait"):
                    time.sleep(min(gap - 1e-4, 2e-4))
    # every tick due in the window has been sent; the window runs to its
    # full length (a queue still draining is the run's to answer for)
    with span("bench_drain"):
        take(gateway.drain(), clock() - t_start)
        deadline = max(seconds, clock() - t_start) + ANSWER_GRACE_S
        while np.isnan(done_at).any() and clock() - t_start < deadline:
            results = pump()
            if results:
                take(results, clock() - t_start)
            else:
                time.sleep(1e-3)
    elapsed = clock() - t_start
    window = rig.window_end()
    ctx.window_ended()

    answered = ~np.isnan(done_at)
    latency_ms = (done_at[answered] - due[answered]) * 1e3
    lateness_ms = (sent_at - due) * 1e3
    c = window["counters"]
    failed = int(n - answered.sum())
    attempted = int(n)

    # lateness of the generator outside bursts: steady ticks that came due
    # while no burst was still being drained
    in_drain = np.zeros(n, bool)
    drain_ms = []
    for bt in plan.burst_times:
        members = plan.burst & (due == bt)
        if not answered[members].all():
            end = np.inf
        else:
            end = done_at[members].max()
        drain_ms.append((end - bt) * 1e3)
        in_drain |= (due >= bt) & (due <= end)
    calm = ~in_drain & ~plan.burst
    gen_late = lateness_ms[calm]

    # a shed tick's row never reached the model: the reference skips it
    rows_by_session, seqs_by_session = {}, {}
    for s in checked:
        ticks, seqs = index.ticks_of_session(s)
        keep = answered[ticks]
        rows_by_session[s] = rows[ticks[keep]]
        seqs_by_session[s] = seqs[keep].tolist()
    ref = rig.check_against_reference(rows_by_session, seqs_by_session,
                                      served)
    checks = rig.verdict(
        c, failed, ref, all(v == 0 for v in problems.values()),
        order_problems=problems)
    correct = checks["correct"]

    p50 = percentile(latency_ms, 50.0)
    p99 = percentile(latency_ms, 99.0)
    depth_t_a, depth_v_a = np.asarray(depth_t), np.asarray(depth_v)
    between = np.ones(len(depth_t_a), bool)
    for bt, d in zip(plan.burst_times, drain_ms):
        between &= ~((depth_t_a >= bt) & (depth_t_a <= bt + d / 1e3))

    def depth_in(lo, hi):
        sel = between & (depth_t_a >= lo) & (depth_t_a < hi)
        return float(depth_v_a[sel].mean()) if sel.any() else None

    return {
        "attempted": attempted,
        "failed": failed,
        "correct": correct,
        "checks": checks,
        "end_to_end": {"tick_p50_ms": p50, "tick_p99_ms": p99},
        "record": {
            "window_s": elapsed,
            "counters": c,
            "hist": window["hist"],
            "gen_lateness_ms": gen_late,
            "burst_drain_ms": drain_ms,
            "model_cfg": rig.model_cfg,
            "runtime_cfg": rig.cfg.runtime,
        },
        "notes": {
            "ticks_sent": attempted,
            "ticks_answered": int(answered.sum()),
            "latency_samples": int(answered.sum()),
            "samples_beyond_p99": float(answered.sum() * 0.01),
            "steady_rate_ticks_per_s": plan.steady_rate,
            "bursts": len(plan.burst_times),
            "burst_drain_ms": drain_ms,
            "tick_p90_ms": percentile(latency_ms, 90.0),
            "tick_p999_ms": percentile(latency_ms, 99.9),
            "tick_max_ms": float(latency_ms.max()) if len(latency_ms) else None,
            "gen_lateness_p99_ms_calm": percentile(gen_late, 99.0),
            "gen_lateness_p50_ms_calm": percentile(gen_late, 50.0),
            "queue_depth_mid_window": depth_in(seconds * 0.3, seconds * 0.6),
            "queue_depth_end_window": depth_in(seconds * 0.7, seconds),
            "queue_depth_peak": int(depth_v_a.max()) if len(depth_v_a) else 0,
            "window_elapsed_s": elapsed,
        },
    }
