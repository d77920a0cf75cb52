"""Latency is taken from the time a tick was *due*: a stall in the
gateway lengthens the latency of every tick that came due meanwhile,
though each is submitted only after the stall."""

import time
import types

import numpy as np

from benchmark.harness import catalog, schedule as sched
from benchmark.harness.serving import Rig


class FakeResult:
    def __init__(self, session_id, seq):
        self.session_id, self.seq = session_id, seq
        self.probabilities = np.zeros(4, np.float32)


class StallingGateway:
    """Answers every submitted tick at the next pump; the first pump
    after ``stall_at`` blocks for ``stall_s``."""

    def __init__(self, stall_at, stall_s):
        self.pending, self.seq = [], {}
        self.batcher = self.pending
        self.stall_at, self.stall_s = stall_at, stall_s
        self.t0 = None
        self.metrics = types.SimpleNamespace(counters={})

    def submit(self, sid, row):
        if self.t0 is None:
            self.t0 = time.perf_counter()
        seq = self.seq.get(sid, 0)
        self.seq[sid] = seq + 1
        self.pending.append(FakeResult(sid, seq))

    def pump(self):
        if (self.stall_s and self.t0 is not None
                and time.perf_counter() - self.t0 >= self.stall_at):
            time.sleep(self.stall_s)
            self.stall_s = 0.0
        out, self.pending[:] = list(self.pending), []
        return out

    drain = pump


class FakeRig(Rig):
    """The real rig's bookkeeping over a fake gateway and no model."""

    def __init__(self, gateway, n_sessions):
        self.gateway = gateway
        self.sessions = sched.make_sessions(n_sessions, 3, 1)
        self.sid_index = {s: i for i, s in enumerate(self.sessions.ids)}
        self.seq0 = np.zeros(n_sessions, np.int64)
        self.model_cfg = None
        self.cfg = types.SimpleNamespace(runtime=None)

    def window_begin(self):
        pass

    def window_end(self):
        return {"counters": {}, "hist": {}}

    def compile_facts(self):
        return {"compile_count": 0, "recompiles_after_warmup": 0}

    def check_against_reference(self, rows, seqs, served):
        return {"ok": True, "max_abs_err": 0.0, "compared": 0}


def _drive(stall_s):
    driver = catalog.load_driver("open_loop")
    traffic = {"kind": "open_loop", "sessions": 16, "rate_ticks_per_s": 2000}
    seconds = 1.0
    plan = sched.make_schedule(traffic, 1, seconds)
    rig = FakeRig(StallingGateway(0.4, stall_s), 16)
    rows = sched.walk_rows(rig.sessions, plan.session, 1, stream=1)
    index = sched.index_ticks(plan.session, 16, rig.seq0)
    ctx = types.SimpleNamespace(
        seconds=seconds, trace=False, trace_dir="",
        window_begins=lambda: None, window_ended=lambda: None)
    out = driver.drive(rig, plan, rows, index, set(), ctx, [])
    return plan, out


def test_a_stall_lengthens_later_ticks_latency():
    _, calm = _drive(0.0)
    plan, stalled = _drive(0.2)
    assert calm["failed"] == 0 and stalled["failed"] == 0
    assert calm["attempted"] == stalled["attempted"] == len(plan) == 2000
    # 0.2 s of 1.0 s stalled: a fifth of the ticks came due inside it and
    # waited 100 ms on average, so the 90th percentile is ~100 ms.  Timed
    # from submit, every tick would look as fast as in the calm run.
    assert calm["notes"]["tick_p90_ms"] < 20.0
    assert 60.0 < stalled["notes"]["tick_p90_ms"] < 200.0
    assert stalled["end_to_end"]["tick_p99_ms"] > 150.0
    assert stalled["notes"]["tick_max_ms"] >= 190.0


def test_unanswered_ticks_count_as_failed_and_have_no_latency():
    driver = catalog.load_driver("open_loop")
    traffic = {"kind": "open_loop", "sessions": 16, "rate_ticks_per_s": 2000}
    plan = sched.make_schedule(traffic, 2, 0.5)
    gateway = StallingGateway(0.0, 0.0)
    real_pump = gateway.pump

    def lossy_pump():
        out = real_pump()
        return [r for r in out if not (r.session_id.endswith("3")
                                       and r.seq % 2)]

    gateway.pump = gateway.drain = lossy_pump
    rig = FakeRig(gateway, 16)
    rows = sched.walk_rows(rig.sessions, plan.session, 2, stream=1)
    index = sched.index_ticks(plan.session, 16, rig.seq0)
    ctx = types.SimpleNamespace(
        seconds=0.5, trace=False, trace_dir="",
        window_begins=lambda: None, window_ended=lambda: None)
    driver.ANSWER_GRACE_S = 0.2
    out = driver.drive(rig, plan, rows, index, set(), ctx, [])
    per_session = np.bincount(plan.session, minlength=16)
    dropped = sum(per_session[i] // 2 for i in (3, 13))
    assert out["failed"] == dropped > 0
    assert out["notes"]["latency_samples"] == len(plan) - dropped
    # nothing was counted shed by the (fake) program: the books do not
    # balance, and the run is not correct
    assert out["checks"]["counts_balance"] is False
    assert out["correct"] is False
