"""fmda_tpu.runtime — the dynamic micro-batching serving runtime.

Covers the ISSUE-1 acceptance surface: slot alloc/free/reuse under
generation guards, deadline vs batch-full flushing, padded-bucket compile
stability (no per-request recompilation, asserted via the jit cache-size
hook), visible load-shedding under overload, and the numerical contract —
a multiplexed session is bit-identical to a solo
:class:`~fmda_tpu.serve.streaming.StreamingBiGRU` run at bucket size 1,
and within float32 ulp noise (the same 1e-6 the seed's lockstep-batched
test uses) for batched buckets, where XLA's B>1 matmul codegen differs
from B=1 in reduction order.
"""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fmda_tpu.config import (
    DEFAULT_TOPICS,
    ModelConfig,
    TOPIC_FLEET_PREDICTION,
)
from fmda_tpu.data.normalize import NormParams
from fmda_tpu.runtime import (
    BatcherConfig,
    FleetGateway,
    FleetLoadConfig,
    MicroBatcher,
    PoolExhausted,
    SessionPool,
    StaleSessionError,
    Tick,
    run_fleet_load,
)
from fmda_tpu.runtime.metrics import LatencyHistogram
from fmda_tpu.serve.streaming import StreamingBiGRU
from fmda_tpu.stream import InProcessBus


def _setup(feats=6, hidden=5, window=4, seed=0, cell="gru"):
    cfg = ModelConfig(hidden_size=hidden, n_features=feats, output_size=4,
                      dropout=0.0, bidirectional=False, use_pallas=False,
                      cell=cell)
    from fmda_tpu.models import build_model

    model = build_model(cfg)
    params = model.init({"params": jax.random.PRNGKey(seed)},
                        jnp.zeros((1, window, feats)))["params"]
    return cfg, params


def _norms(n, feats, seed=0):
    rng = np.random.default_rng(seed)
    mins = rng.normal(size=(n, feats)).astype(np.float32)
    maxs = mins + rng.uniform(1.0, 5.0, size=(n, feats)).astype(np.float32)
    return [NormParams(mins[i], maxs[i]) for i in range(n)]


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ---------------------------------------------------------------------------
# session pool: slot lifecycle
# ---------------------------------------------------------------------------


def test_pool_alloc_free_reuse_with_generation_guard():
    cfg, params = _setup()
    pool = SessionPool(cfg, params, capacity=2, window=4)
    a = pool.alloc("a")
    b = pool.alloc("b")
    assert pool.n_active == 2 and pool.n_free == 0
    assert pool.active_mask.sum() == 2
    with pytest.raises(PoolExhausted):
        pool.alloc("c")

    pool.free(a)
    assert pool.n_active == 1 and pool.n_free == 1
    assert not pool.is_live(a)
    # the freed handle is dead for every API, even after slot reuse
    with pytest.raises(StaleSessionError):
        pool.ticks_seen(a)
    c = pool.alloc("c")
    assert c.slot == a.slot  # slot recycled...
    assert c.generation == a.generation + 1  # ...under a new generation
    assert pool.is_live(c) and not pool.is_live(a)
    with pytest.raises(StaleSessionError):
        pool.free(a)
    # double-alloc of a live id is an error, not a silent second slot
    with pytest.raises(ValueError, match="already allocated"):
        pool.alloc("b")
    pool.free(b)
    pool.free(c)
    assert pool.n_active == 0 and pool.n_free == 2


def test_pool_slot_reuse_carries_no_stale_state():
    """A freed-and-reused slot must serve the new session from zeroed
    state: the recycled slot's output stream equals a fresh solo core's,
    bit for bit (bucket size 1)."""
    cfg, params = _setup()
    pool = SessionPool(cfg, params, capacity=1, window=4)
    rng = np.random.default_rng(2)
    rows = rng.normal(size=(5, cfg.n_features)).astype(np.float32)

    a = pool.alloc("a")
    for k in range(3):  # dirty the slot
        pool.step(np.array([a.slot], np.int32), rows[k][None])
    assert pool.ticks_seen(a) == 3
    pool.free(a)

    b = pool.alloc("b")
    solo = StreamingBiGRU(
        cfg, params,
        NormParams(np.zeros(cfg.n_features, np.float32),
                   np.ones(cfg.n_features, np.float32)),
        window=4)
    for k in range(5):
        got = pool.step(np.array([b.slot], np.int32), rows[k][None])[0]
        want = solo.step(rows[k])[0]
        np.testing.assert_array_equal(got, want)
    assert pool.ticks_seen(b) == 5


def test_pool_rejects_bidirectional():
    cfg = ModelConfig(hidden_size=4, n_features=3, output_size=4,
                      bidirectional=True)
    with pytest.raises(ValueError, match="Predictor"):
        SessionPool(cfg, {}, capacity=2, window=4)


# ---------------------------------------------------------------------------
# micro-batcher: flush decisions + ordering
# ---------------------------------------------------------------------------


def _tick(slot, gen=0, t=0.0, seq=0, sid="s"):
    from fmda_tpu.runtime.session_pool import SessionHandle

    return Tick(handle=SessionHandle(f"{sid}{slot}", slot, gen),
                row=np.zeros(3, np.float32), t_enqueue=t, seq=seq)


def test_batcher_flushes_on_batch_full():
    clock = FakeClock()
    b = MicroBatcher(BatcherConfig(bucket_sizes=(2, 4), max_linger_s=10.0),
                     clock=clock)
    b.add(_tick(0))
    b.add(_tick(1))
    b.add(_tick(2))
    assert not b.ready()  # 3 distinct < largest bucket (4), no linger yet
    b.add(_tick(3))
    assert b.ready()  # distinct sessions fill the largest bucket
    assert [t.handle.slot for t in b.take_batch()] == [0, 1, 2, 3]
    assert len(b) == 0


def test_batcher_flushes_on_deadline():
    clock = FakeClock()
    b = MicroBatcher(BatcherConfig(bucket_sizes=(8,), max_linger_s=0.005),
                     clock=clock)
    b.add(_tick(0, t=clock()))
    assert not b.ready()  # neither full nor lingered
    clock.advance(0.004)
    assert not b.ready()
    clock.advance(0.002)  # oldest now 6ms > 5ms budget
    assert b.ready()
    assert len(b.take_batch()) == 1


def test_batcher_one_row_per_session_per_flush():
    """Two rows of one session advance a recurrence — they can never
    share a flush; per-session FIFO order survives the deferral."""
    b = MicroBatcher(BatcherConfig(bucket_sizes=(4,), max_linger_s=0.0))
    b.add(_tick(0, seq=0))
    b.add(_tick(1, seq=0))
    b.add(_tick(0, seq=1))
    b.add(_tick(0, seq=2))
    assert b.distinct_sessions == 2
    first = b.take_batch()
    assert [(t.handle.slot, t.seq) for t in first] == [(0, 0), (1, 0)]
    second = b.take_batch()
    assert [(t.handle.slot, t.seq) for t in second] == [(0, 1)]
    third = b.take_batch()
    assert [(t.handle.slot, t.seq) for t in third] == [(0, 2)]


def test_batcher_bucket_selection():
    b = MicroBatcher(BatcherConfig(bucket_sizes=(2, 8, 32)))
    assert b.bucket_for(1) == 2
    assert b.bucket_for(2) == 2
    assert b.bucket_for(3) == 8
    assert b.bucket_for(32) == 32
    with pytest.raises(ValueError, match="largest bucket"):
        b.bucket_for(33)
    with pytest.raises(ValueError, match="ascending"):
        BatcherConfig(bucket_sizes=(8, 2))


# ---------------------------------------------------------------------------
# compile stability: padded buckets, no per-request recompilation
# ---------------------------------------------------------------------------


def test_padded_buckets_avoid_recompilation():
    """Ragged flush sizes 1..8 over many flushes compile exactly one
    program per configured bucket actually used — never one per request
    size (the compiled-once/dispatch-many contract)."""
    cfg, params = _setup()
    pool = SessionPool(cfg, params, capacity=8, window=4)
    gw = FleetGateway(
        pool,
        batcher_config=BatcherConfig(bucket_sizes=(4, 8), max_linger_s=0.0))
    for i in range(8):
        gw.open_session(f"T{i}")
    rng = np.random.default_rng(0)
    assert pool.compile_count == 0
    buckets_seen = set()
    for round_ in range(12):
        n = 1 + round_ % 8  # flush sizes 1..8
        for i in range(n):
            gw.submit(f"T{i}", rng.normal(size=cfg.n_features))
        res = gw.drain()
        assert len(res) == n
        buckets_seen.add(gw.batcher.bucket_for(n))
    assert buckets_seen == {4, 8}
    assert pool.compile_count == 2  # one program per bucket, ever
    counters = gw.metrics.counters
    assert counters["flushes_bucket_4"] + counters["flushes_bucket_8"] == 12


# ---------------------------------------------------------------------------
# overload: backpressure + visible shedding, no deadlock, no unbounded queue
# ---------------------------------------------------------------------------


def test_small_fleet_flushes_without_linger_wait():
    """A fleet smaller than the largest bucket must not pay max_linger on
    every steady-state flush: once every active session is pending, the
    flush cannot grow, so pump() fires immediately (full_target)."""
    cfg, params = _setup()
    pool = SessionPool(cfg, params, capacity=5, window=4)
    clock = FakeClock()
    gw = FleetGateway(
        pool,
        batcher_config=BatcherConfig(bucket_sizes=(8, 128),
                                     max_linger_s=99.0),
        clock=clock)
    for i in range(5):
        gw.open_session(f"T{i}")
    rng = np.random.default_rng(3)
    for i in range(5):
        gw.submit(f"T{i}", rng.normal(size=cfg.n_features))
    # zero clock advance, linger budget untouched: all 5 pending == all
    # 5 active -> batch-full semantics, one padded bucket-8 flush is
    # DISPATCHED immediately (no linger wait) and stays in flight; the
    # next (idle) pump completes it — the persistent overlap contract
    assert gw.pump() == []
    assert gw.metrics.counters["flushes_bucket_8"] == 1
    assert len(gw.pump()) == 5
    # a PARTIAL round (3 of 5) still waits for the deadline
    for i in range(3):
        gw.submit(f"T{i}", rng.normal(size=cfg.n_features))
    assert gw.pump() == []
    assert gw.metrics.counters["flushes"] == 1  # nothing new dispatched
    clock.advance(100.0)
    assert gw.pump() == []  # deadline flush dispatched, in flight
    assert gw.metrics.counters["flushes"] == 2
    assert len(gw.pump()) == 3


def test_loadgen_respects_backpressure_beyond_queue_bound():
    """Fleets larger than queue_bound drain on saturation instead of
    racing the shedder: every submitted tick is served, none shed."""
    cfg, params = _setup(feats=4, hidden=4, window=3)
    pool = SessionPool(cfg, params, capacity=40, window=3)
    gw = FleetGateway(
        pool,
        batcher_config=BatcherConfig(bucket_sizes=(16,), max_linger_s=99.0),
        queue_bound=10)
    out = run_fleet_load(
        gw, FleetLoadConfig(n_sessions=40, n_ticks=3, duty=1.0, seed=0))
    assert out["ticks_submitted"] == 120
    assert out["ticks_served"] == 120
    assert out["counters"].get("shed_oldest", 0) == 0


def test_overload_sheds_oldest_with_counters():
    cfg, params = _setup()
    pool = SessionPool(cfg, params, capacity=4, window=4)
    clock = FakeClock()
    gw = FleetGateway(
        pool,
        batcher_config=BatcherConfig(bucket_sizes=(4,), max_linger_s=99.0),
        queue_bound=6, clock=clock)
    for i in range(4):
        gw.open_session(f"T{i}")
    rng = np.random.default_rng(1)
    # 20 submits, never pumped: the queue must stay bounded and the
    # overflow must be counted, not silently vanish
    for k in range(20):
        gw.submit(f"T{k % 4}", rng.normal(size=cfg.n_features))
    assert len(gw.batcher) == 6
    assert gw.saturated
    assert gw.metrics.counters["shed_oldest"] == 14
    assert gw.metrics.gauges["queue_depth_peak"] == 6
    # the survivors are the NEWEST ticks (oldest-drop policy) and drain
    # without deadlock: 6 queued ticks over 4 sessions -> 2 flushes
    res = gw.drain()
    assert len(res) == 6
    # submits 14..19 survive: (T2,3) (T3,3) (T0,4) (T1,4) (T2,4) (T3,4)
    assert sorted((r.session_id, r.seq) for r in res) == [
        ("T0", 4), ("T1", 4), ("T2", 3), ("T2", 4), ("T3", 3), ("T3", 4)]
    assert gw.metrics.counters["ticks_served"] == 6
    assert len(gw.batcher) == 0 and not gw.saturated


def test_session_close_drops_queued_ticks_visibly():
    cfg, params = _setup()
    pool = SessionPool(cfg, params, capacity=2, window=4)
    gw = FleetGateway(
        pool, batcher_config=BatcherConfig(bucket_sizes=(2,),
                                           max_linger_s=99.0))
    gw.open_session("a")
    gw.open_session("b")
    gw.submit("a", np.zeros(cfg.n_features, np.float32))
    gw.submit("b", np.zeros(cfg.n_features, np.float32))
    gw.close_session("a")  # frees the slot while a's tick is queued
    res = gw.drain()
    assert [r.session_id for r in res] == ["b"]
    assert gw.metrics.counters["stale_dropped"] == 1
    with pytest.raises(KeyError):
        gw.submit("a", np.zeros(cfg.n_features, np.float32))


def test_submit_copies_caller_row_buffer():
    """A queued tick must not alias the caller's buffer: callers (e.g.
    the load generator's random walk) mutate their row arrays in place
    between submit and flush."""
    cfg, params = _setup()
    pool = SessionPool(cfg, params, capacity=1, window=4)
    gw = FleetGateway(
        pool, batcher_config=BatcherConfig(bucket_sizes=(1,),
                                           max_linger_s=99.0))
    gw.open_session("a")
    solo = StreamingBiGRU(
        cfg, params,
        NormParams(np.zeros(cfg.n_features, np.float32),
                   np.ones(cfg.n_features, np.float32)),
        window=4)
    row = np.random.default_rng(0).normal(
        size=cfg.n_features).astype(np.float32)
    want = solo.step(row)[0]
    gw.submit("a", row)
    row[:] = 1e6  # caller reuses its buffer while the tick is queued
    res = gw.drain()
    np.testing.assert_array_equal(res[0].probabilities, want)


def test_submit_rejects_malformed_row_at_the_submitter():
    """A wrong-shape row must fail at submit(), not blow up a later
    flush and take the batch's other sessions' ticks with it."""
    cfg, params = _setup()  # 6 features
    pool = SessionPool(cfg, params, capacity=2, window=4)
    gw = FleetGateway(
        pool, batcher_config=BatcherConfig(bucket_sizes=(2,),
                                           max_linger_s=99.0))
    gw.open_session("good")
    gw.open_session("bad")
    gw.submit("good", np.zeros(cfg.n_features, np.float32))
    with pytest.raises(ValueError, match="row shape"):
        gw.submit("bad", np.zeros(cfg.n_features + 2, np.float32))
    res = gw.drain()  # the valid tick is unaffected
    assert [r.session_id for r in res] == ["good"]


def test_gateway_rejects_bus_without_fleet_topic():
    """A pre-PR-1 config with an explicit topic list must fail at
    construction, not with a mid-flush KeyError after state advanced."""
    cfg, params = _setup()
    pool = SessionPool(cfg, params, capacity=1, window=4)
    legacy_bus = InProcessBus(("prediction",))
    with pytest.raises(ValueError, match="fleet_prediction"):
        FleetGateway(pool, legacy_bus)


def test_admission_rejection_is_counted():
    cfg, params = _setup()
    pool = SessionPool(cfg, params, capacity=1, window=4)
    gw = FleetGateway(pool)
    gw.open_session("a")
    with pytest.raises(PoolExhausted):
        gw.open_session("b")
    assert gw.metrics.counters["rejected_sessions"] == 1
    assert gw.metrics.gauges["active_sessions"] == 1


# ---------------------------------------------------------------------------
# numerics: multiplexed == solo
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cell", ["gru", "lstm", "ssm"])
def test_multiplexed_bucket1_is_interleaving_exact_and_matches_solo(cell):
    """The multiplexing machinery itself — slot gather/scatter, per-slot
    ring positions, generation bookkeeping, interleaving with OTHER
    sessions' flushes — adds exactly zero numerical change.  Stated as
    the contract the installed compiler keeps:

    - **pool <-> pool is bit-exact**: the same streams through the same
      pool program, interleaved session by session or served one whole
      session after another, give identical bits (the contract
      migration, drain/replay and serial-vs-overlapped rest on);
    - **pool <-> solo holds to a tolerance**: a pool step and a solo
      ``StreamingBiGRU`` step are two compiled programs, and on jax
      0.9.0 XLA-CPU already separates them by 1 ulp (6e-8) for gru and
      ssm at bucket 1.  1e-6 here; on the TPU, where the pool's batched
      buckets multiply at the MXU's default precision and a batch-1
      program does not, ``chip_smoke.py`` measures and bounds it
      (3.7e-4 gru, 7.7e-5 ssm against its 2e-3 — and 0.0 against a solo
      carrier batched like the pool)."""
    feats, window, n = 6, 4, 3
    cfg, params = _setup(feats=feats, cell=cell)
    norms = _norms(n, feats)
    rng = np.random.default_rng(4)
    rows = rng.normal(size=(6, n, feats)).astype(np.float32)

    def serve(order):
        """``order``: (tick, session) pairs in submission order, one
        single-lane flush each; returns {(tick, session): probs}."""
        pool = SessionPool(cfg, params, capacity=n, window=window)
        gw = FleetGateway(
            pool, batcher_config=BatcherConfig(bucket_sizes=(1,),
                                               max_linger_s=0.0))
        for i in range(n):
            gw.open_session(f"T{i}", norms[i])
        out = {}
        for k, i in order:
            gw.submit(f"T{i}", rows[k, i])
            (res,) = gw.drain()
            out[(k, i)] = res.probabilities
        assert pool.compile_count == 1
        return out

    interleaved = serve([(k, i) for k in range(6) for i in range(n)])
    one_by_one = serve([(k, i) for i in range(n) for k in range(6)])
    solos = [StreamingBiGRU(cfg, params, norms[i], window=window)
             for i in range(n)]
    for k in range(6):
        for i in range(n):
            np.testing.assert_array_equal(
                interleaved[(k, i)], one_by_one[(k, i)])
            np.testing.assert_allclose(
                interleaved[(k, i)], solos[i].step(rows[k, i])[0],
                rtol=0, atol=1e-6)


@pytest.mark.parametrize("cell", ["gru", "ssm"])
def test_multiplexed_batched_matches_solo_within_ulp(cell):
    """Batched buckets with ragged per-session duty cycles: every served
    tick matches the solo carrier to float32 ulp noise (1e-6 — the same
    tolerance the seed's lockstep-batched test uses; XLA's B>1 matmul
    reduction order differs from B=1 at the last bit).  This is the
    cross-program contract for every family (see the bucket-1 test
    above for what is bit-exact and what is not)."""
    feats, window, n = 6, 4, 5
    cfg, params = _setup(feats=feats, cell=cell)
    pool = SessionPool(cfg, params, capacity=n, window=window)
    gw = FleetGateway(
        pool, batcher_config=BatcherConfig(bucket_sizes=(2, 8),
                                           max_linger_s=0.0))
    norms = _norms(n, feats, seed=5)
    solos = [StreamingBiGRU(cfg, params, norms[i], window=window)
             for i in range(n)]
    for i in range(n):
        gw.open_session(f"T{i}", norms[i])
    rng = np.random.default_rng(6)
    for _ in range(10):
        ticking = np.flatnonzero(rng.random(n) < 0.7)
        rows = rng.normal(size=(n, feats)).astype(np.float32)
        for i in ticking:
            gw.submit(f"T{i}", rows[i])
        res = gw.drain()
        assert len(res) == len(ticking)
        by_sid = {r.session_id: r.probabilities for r in res}
        for i in ticking:
            np.testing.assert_allclose(
                by_sid[f"T{i}"], solos[i].step(rows[i])[0], atol=1e-6)
    assert pool.compile_count <= 2


def test_64_sessions_through_one_compiled_step():
    """The acceptance headline: >= 64 concurrent sessions, every round
    served by ONE fused batched step (single bucket, compile_count 1)."""
    n, feats, window = 64, 4, 3
    cfg, params = _setup(feats=feats, hidden=4, window=window)
    pool = SessionPool(cfg, params, capacity=n, window=window)
    bus = InProcessBus(DEFAULT_TOPICS)
    gw = FleetGateway(
        pool, bus, batcher_config=BatcherConfig(bucket_sizes=(64,),
                                                max_linger_s=0.0))
    for i in range(n):
        gw.open_session(f"T{i:03d}")
    rng = np.random.default_rng(7)
    rounds = 3
    served = 0
    for k in range(rounds):
        rows = rng.normal(size=(n, feats)).astype(np.float32)
        for i in range(n):
            gw.submit(f"T{i:03d}", rows[i])
        # batch-full -> one flush dispatched per round; under the
        # persistent overlap pipeline each round's pump completes the
        # PREVIOUS round's flush (round k dispatches while k-1 transfers)
        res = gw.pump()
        served += len(res)
        assert len(res) == (0 if k == 0 else n)
    served += len(gw.drain())
    assert served == n * rounds
    # rounds 2..N overlapped the prior round's in-flight flush
    assert gw.metrics.counters["overlapped_flushes"] == rounds - 1
    assert pool.compile_count == 1
    assert gw.metrics.counters["flushes"] == rounds
    assert gw.metrics.counters["ticks_served"] == n * rounds
    # per-session results ride the shared bus topic, keyed by session
    msgs = bus.consumer(TOPIC_FLEET_PREDICTION).poll()
    assert len(msgs) == n * rounds
    per_session = {}
    for m in msgs:
        per_session.setdefault(m.value["session"], []).append(m.value["seq"])
    assert len(per_session) == n
    assert all(seqs == [0, 1, 2] for seqs in per_session.values())


# ---------------------------------------------------------------------------
# overlap pipeline + donation + sharding (ISSUE 3)
# ---------------------------------------------------------------------------


def test_overlap_pipeline_bit_identical_to_serial():
    """The one-deep in-flight pipeline reorders WORK (flush k+1 dispatches
    before flush k's results come home) but not RESULTS: over multi-flush
    pumps, every probability and every bus message is bit-identical to
    the strictly serial gateway."""
    n, feats, window = 10, 6, 4
    cfg, params = _setup(feats=feats)
    norms = _norms(n, feats, seed=9)
    gws = []
    for depth in (0, 1):
        pool = SessionPool(cfg, params, capacity=n, window=window)
        bus = InProcessBus(DEFAULT_TOPICS)
        gw = FleetGateway(
            pool, bus,
            batcher_config=BatcherConfig(bucket_sizes=(4,),
                                         max_linger_s=0.0),
            pipeline_depth=depth)
        for i in range(n):
            gw.open_session(f"T{i}", norms[i])
        gws.append(gw)
    rng = np.random.default_rng(10)
    for _ in range(6):
        ticking = np.flatnonzero(rng.random(n) < 0.8)
        rows = rng.normal(size=(n, feats)).astype(np.float32)
        outs = []
        for gw in gws:
            for i in ticking:
                gw.submit(f"T{i}", rows[i])
            # > bucket-size pending -> multiple flushes per drain: the
            # overlapped gateway genuinely pipelines here
            outs.append(gw.drain())
        serial, overlapped = outs
        assert [(r.session_id, r.seq) for r in serial] == \
            [(r.session_id, r.seq) for r in overlapped]
        for a, b in zip(serial, overlapped):
            np.testing.assert_array_equal(a.probabilities, b.probabilities)
            assert a.labels == b.labels
    assert gws[1].metrics.counters["overlapped_flushes"] > 0
    assert gws[0].metrics.counters.get("overlapped_flushes", 0) == 0
    # the bus transcripts match message for message
    msgs = [gw.bus.consumer(TOPIC_FLEET_PREDICTION).poll() for gw in gws]
    assert [m.value for m in msgs[0]] == [m.value for m in msgs[1]]


def test_pump_failure_never_strands_the_inflight_flush():
    """A completion failure (bus publish error) mid-pump must not strand
    the already-dispatched next flush — its pool-state advance is
    irreversible, so its results are still published on unwind, and the
    failed flush's ticks are counted (flush_results_lost), never silent."""
    n, feats = 4, 6
    cfg, params = _setup(feats=feats)

    class FailOnceBus(InProcessBus):
        def __init__(self, topics):
            super().__init__(topics)
            self.failed = False

        def publish_many(self, topic, values):
            if not self.failed:
                self.failed = True
                raise RuntimeError("transport hiccup")
            return super().publish_many(topic, values)

    pool = SessionPool(cfg, params, capacity=n, window=4)
    bus = FailOnceBus(DEFAULT_TOPICS)
    gw = FleetGateway(
        pool, bus, batcher_config=BatcherConfig(bucket_sizes=(2,),
                                                max_linger_s=0.0))
    for i in range(n):
        gw.open_session(f"T{i}")
    rng = np.random.default_rng(15)
    for i in range(n):
        gw.submit(f"T{i}", rng.normal(size=feats).astype(np.float32))
    # two bucket-2 flushes: flush 2 dispatches, then flush 1's publish
    # blows up; flush 2 must still complete during the unwind
    with pytest.raises(RuntimeError, match="transport hiccup"):
        gw.drain()
    assert gw.metrics.counters["flush_results_lost"] == 2
    assert gw.metrics.counters["ticks_served"] == 2  # flush 2 landed
    msgs = bus.consumer(TOPIC_FLEET_PREDICTION).poll()
    assert [m.value["session"] for m in msgs] == ["T2", "T3"]
    # the gateway stays serviceable and sequences continue
    for i in range(n):
        gw.submit(f"T{i}", rng.normal(size=feats).astype(np.float32))
    res = gw.drain()
    assert sorted((r.session_id, r.seq) for r in res) == [
        (f"T{i}", 1) for i in range(n)]


def test_pool_step_donates_state_in_place():
    """The jitted step donates carry/ring/pos: after a flush the previous
    buffers are consumed (no per-flush copy of the pooled tree), and the
    pool stays fully usable through alloc/free/reset churn — no
    use-after-donate anywhere in the slot lifecycle."""
    cfg, params = _setup()
    pool = SessionPool(cfg, params, capacity=2, window=4)
    a = pool.alloc("a")
    rows = np.random.default_rng(0).normal(
        size=(4, cfg.n_features)).astype(np.float32)
    old_ring, old_pos = pool._ring, pool._pos
    old_carry_leaf = pool._carry[0][0]
    pool.step(np.array([a.slot], np.int32), rows[0][None])
    assert old_ring.is_deleted() and old_pos.is_deleted()
    assert old_carry_leaf.is_deleted()
    # post-donation state supports every host-side operation
    b = pool.alloc("b")
    pool.step(np.array([a.slot, b.slot], np.int32), rows[1:3])
    pool.reset(a)
    pool.free(b)
    c = pool.alloc("c")
    got = pool.step(np.array([c.slot], np.int32), rows[3][None])
    assert np.isfinite(got).all()
    assert pool.ticks_seen(a) == 0 and pool.ticks_seen(c) == 1


def test_generation_guard_rejects_stale_mid_pipeline():
    """A session closed while its ticks are queued across SEVERAL
    pipelined flushes is dropped at each dispatch (counted), and the
    surviving sessions' results stay correct (to the usual batched-bucket
    float32 ulp tolerance — these are bucket-2 flushes)."""
    n, feats, window = 6, 6, 4
    cfg, params = _setup(feats=feats)
    pool = SessionPool(cfg, params, capacity=n, window=window)
    gw = FleetGateway(
        pool, batcher_config=BatcherConfig(bucket_sizes=(2,),
                                           max_linger_s=0.0))
    solos = {}
    for i in range(n):
        gw.open_session(f"T{i}")
        solos[f"T{i}"] = StreamingBiGRU(
            cfg, params,
            NormParams(np.zeros(feats, np.float32),
                       np.ones(feats, np.float32)),
            window=window)
    rng = np.random.default_rng(11)
    # two rounds queued for everyone -> 6 bucket-2 flushes in one drain
    rows = rng.normal(size=(2, n, feats)).astype(np.float32)
    for k in range(2):
        for i in range(n):
            gw.submit(f"T{i}", rows[k, i])
    gw.close_session("T3")  # both queued ticks now stale
    res = gw.drain()
    assert gw.metrics.counters["stale_dropped"] == 2
    assert not any(r.session_id == "T3" for r in res)
    by_key = {(r.session_id, r.seq): r.probabilities for r in res}
    assert len(by_key) == 2 * (n - 1)
    for i in range(n):
        if i == 3:
            continue
        for k in range(2):
            np.testing.assert_allclose(
                by_key[(f"T{i}", k)], solos[f"T{i}"].step(rows[k, i])[0],
                atol=1e-6)


def test_sharded_pool_matches_unsharded():
    """The slot axis sharded over the test harness's 8 virtual CPU
    devices: same outputs as the unsharded pool through alloc/free/reuse
    churn, slot count padded to the shard count, same compile count."""
    import jax as _jax
    from fmda_tpu.config import MeshConfig
    from fmda_tpu.parallel.mesh import build_mesh

    if len(_jax.devices()) < 2:
        pytest.skip("needs the multi-device CPU harness")
    feats, window, cap = 6, 4, 5
    cfg, params = _setup(feats=feats)
    mesh = build_mesh(MeshConfig())
    pool_s = SessionPool(cfg, params, capacity=cap, window=window, mesh=mesh)
    pool_u = SessionPool(cfg, params, capacity=cap, window=window)
    assert pool_s.n_shards == len(_jax.devices())
    assert pool_s.n_slots % pool_s.n_shards == 0
    assert pool_s.n_slots >= cap + 1
    assert pool_u.n_slots == cap + 1
    norms = _norms(cap, feats, seed=12)
    for i in range(cap):
        pool_s.alloc(f"T{i}", norms[i])
        pool_u.alloc(f"T{i}", norms[i])
    rng = np.random.default_rng(13)
    for k in range(5):
        nt = int(rng.integers(1, cap + 1))
        slots = rng.permutation(cap)[:nt].astype(np.int32)
        rows = rng.normal(size=(nt, feats)).astype(np.float32)
        got = pool_s.step(slots, rows)
        want = pool_u.step(slots, rows)
        np.testing.assert_allclose(got, want, atol=1e-6)
    # churn: free + realloc behaves identically
    hs = pool_s.handle_for("T0")
    hu = pool_u.handle_for("T0")
    pool_s.free(hs)
    pool_u.free(hu)
    hs = pool_s.alloc("T9", norms[0])
    hu = pool_u.alloc("T9", norms[0])
    assert hs.slot == hu.slot and hs.generation == hu.generation
    row = rng.normal(size=(1, feats)).astype(np.float32)
    np.testing.assert_allclose(
        pool_s.step(np.array([hs.slot], np.int32), row),
        pool_u.step(np.array([hu.slot], np.int32), row), atol=1e-6)
    assert pool_s.compile_count == pool_u.compile_count


def test_attach_fleet_wires_shard_pool_and_pipeline_config():
    """RuntimeConfig.shard_pool/pipeline_depth flow through
    Application.attach_fleet: the pool comes back sharded over the test
    harness's virtual devices and the gateway serves through it."""
    import dataclasses

    import jax as _jax

    from fmda_tpu.app import Application
    from fmda_tpu.config import FrameworkConfig, RuntimeConfig

    if len(_jax.devices()) < 2:
        pytest.skip("needs the multi-device CPU harness")
    cfg, params = _setup()
    app_cfg = dataclasses.replace(
        FrameworkConfig(),
        runtime=RuntimeConfig(capacity=8, window=4, bucket_sizes=(8,),
                              shard_pool=True, pipeline_depth=0))
    app = Application(app_cfg)
    try:
        gw = app.attach_fleet(cfg, params)
        assert gw.pool.n_shards == len(_jax.devices())
        assert gw.pipeline_depth == 0
        gw.open_session("a")
        gw.submit("a", np.zeros(cfg.n_features, np.float32))
        res = gw.drain()
        assert [r.session_id for r in res] == ["a"]
    finally:
        app.close()


def test_one_device_mesh_takes_unsharded_path_bitwise():
    """A mesh spanning a single device must be indistinguishable from
    mesh=None — same slot layout, bit-identical outputs (the acceptance
    contract for the sharding change)."""
    import jax as _jax
    from fmda_tpu.config import MeshConfig
    from fmda_tpu.parallel.mesh import build_mesh

    feats, window, cap = 6, 4, 3
    cfg, params = _setup(feats=feats)
    mesh1 = build_mesh(MeshConfig(dp=1, sp=1),
                       devices=_jax.devices()[:1])
    pool_m = SessionPool(cfg, params, capacity=cap, window=window,
                         mesh=mesh1)
    pool_n = SessionPool(cfg, params, capacity=cap, window=window)
    assert pool_m.n_shards == 1 and pool_m.n_slots == pool_n.n_slots
    a_m = pool_m.alloc("a")
    a_n = pool_n.alloc("a")
    rng = np.random.default_rng(14)
    for _ in range(4):
        row = rng.normal(size=(1, feats)).astype(np.float32)
        np.testing.assert_array_equal(
            pool_m.step(np.array([a_m.slot], np.int32), row),
            pool_n.step(np.array([a_n.slot], np.int32), row))


# ---------------------------------------------------------------------------
# load generator + metrics + CLI
# ---------------------------------------------------------------------------


def test_run_fleet_load_end_to_end():
    cfg, params = _setup(feats=5, hidden=4, window=3)
    pool = SessionPool(cfg, params, capacity=16, window=3)
    gw = FleetGateway(
        pool, batcher_config=BatcherConfig(bucket_sizes=(16,),
                                           max_linger_s=0.0))
    out = run_fleet_load(
        gw, FleetLoadConfig(n_sessions=16, n_ticks=5, duty=0.8, seed=0))
    assert out["ticks_served"] == out["ticks_submitted"] > 0
    assert out["compile_count"] == 1
    assert out["latency"]["total"]["count"] == out["ticks_served"]
    assert set(out["latency"]) >= {"enqueue_to_dispatch", "device", "total"}
    assert out["ticks_per_s"] > 0


def test_latency_histogram_percentiles():
    h = LatencyHistogram()
    for ms in (1, 1, 1, 1, 1, 1, 1, 1, 1, 100):  # p50 ~1ms, p99+ ~100ms
        h.observe(ms / 1e3)
    s = h.summary()
    assert s["count"] == 10
    assert 0.8 <= s["p50_ms"] <= 1.3  # bin-edge accuracy: ~1 bin width
    assert 80 <= s["max_ms"] <= 101 and 80 <= s["p99_ms"] <= 130
    assert h.percentile(50) <= h.percentile(99)


def test_serve_fleet_cli(capsys):
    from fmda_tpu.cli import main

    assert main(["serve-fleet", "--sessions", "8", "--ticks", "4",
                 "--hidden", "4", "--window", "3",
                 "--bucket-sizes", "8", "--seed", "0"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["sessions"] == 8
    assert out["ticks_served"] == out["ticks_submitted"] == 32
    assert out["compile_count"] == 1
    assert out["counters"]["ticks_served"] == 32


def test_serve_fleet_cli_slo_gate(capsys):
    """The latency-SLO gate: a generous bound passes (exit 0, verdict in
    the JSON), an impossible bound fails with exit 1, and --slo-soft
    downgrades the failure to a reported verdict."""
    from fmda_tpu.cli import main

    args = ["serve-fleet", "--sessions", "4", "--ticks", "2",
            "--hidden", "4", "--window", "3", "--bucket-sizes", "4",
            "--seed", "0"]
    assert main(args + ["--slo-p99-ms", "1e9"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["slo"]["ok"] is True
    assert out["slo"]["p99_ms_bound"] == 1e9

    assert main(args + ["--slo-p99-ms", "1e-9"]) == 1
    out = json.loads(capsys.readouterr().out)
    assert out["slo"]["ok"] is False

    assert main(args + ["--slo-p99-ms", "1e-9", "--slo-soft"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["slo"] == {"p99_ms_bound": 1e-9, "p99_ms": out["slo"]["p99_ms"],
                          "ok": False, "soft": True}


def test_serve_fleet_cli_serial_matches_default(capsys):
    """--serial (pipeline_depth=0) serves the same load to the same
    counts — the CLI-level A/B knob the docs advertise."""
    from fmda_tpu.cli import main

    outs = []
    for extra in ([], ["--serial"]):
        assert main(["serve-fleet", "--sessions", "6", "--ticks", "3",
                     "--hidden", "4", "--window", "3",
                     "--bucket-sizes", "2", "--seed", "0"] + extra) == 0
        outs.append(json.loads(capsys.readouterr().out))
    assert outs[0]["ticks_served"] == outs[1]["ticks_served"] == 18
    assert outs[0]["counters"].get("overlapped_flushes", 0) > 0
    assert outs[1]["counters"].get("overlapped_flushes", 0) == 0


# ---------------------------------------------------------------------------
# columnar result blocks (ISSUE 13 satellite): A/B bit identity
# ---------------------------------------------------------------------------


def test_result_block_dialect_bit_identical_to_per_tick():
    """The same load served twice — per-tick result dicts vs columnar
    result blocks — must put byte-identical information on the bus:
    same sessions/seqs/labels/threshold, probability bits equal."""
    from fmda_tpu.stream import codec

    def run(result_blocks):
        cfg, params = _setup(feats=6, hidden=5, window=4, seed=0)
        pool = SessionPool(cfg, params, capacity=4, window=4)
        bus = InProcessBus(DEFAULT_TOPICS)
        gateway = FleetGateway(
            pool, bus,
            batcher_config=BatcherConfig(bucket_sizes=(4,),
                                         max_linger_s=0.0))
        gateway.result_blocks = result_blocks
        rng = np.random.default_rng(7)
        sids = [f"T{i}" for i in range(4)]
        for i, sid in enumerate(sids):
            mn = rng.normal(size=6).astype(np.float32)
            gateway.open_session(sid, NormParams(mn, mn + 1.0))
        for _ in range(5):
            for sid in sids:
                gateway.submit(sid, rng.normal(size=6).astype(np.float32))
            gateway.pump(force=True)
        gateway.drain()
        flat = []
        for rec in bus.consumer(TOPIC_FLEET_PREDICTION).poll():
            v = rec.value
            if v.get("kind") == "result_block":
                flat.extend(codec.iter_results(v))
            else:
                flat.append(v)
        return flat

    per_tick = run(False)
    blocked = run(True)
    assert len(per_tick) == len(blocked) == 20
    for a, b in zip(per_tick, blocked):
        assert a["session"] == b["session"] and a["seq"] == b["seq"]
        assert a["pred_labels"] == list(b["pred_labels"])
        assert a["prob_threshold"] == b["prob_threshold"]
        assert np.array_equal(
            np.asarray(a["probabilities"], np.float32),
            np.asarray(b["probabilities"], np.float32))


def test_unpackable_result_run_degrades_to_per_tick_counted():
    """A flush the block codec cannot carry (>63-label vocabulary)
    publishes the per-tick dialect instead — counted, never lost (the
    state advance behind the results is irreversible)."""
    cfg, params = _setup(feats=6, hidden=5, window=4)
    pool = SessionPool(cfg, params, capacity=4, window=4)
    bus = InProcessBus(DEFAULT_TOPICS)
    gateway = FleetGateway(
        pool, bus,
        batcher_config=BatcherConfig(bucket_sizes=(4,), max_linger_s=0.0),
        y_fields=tuple(f"lab{i}" for i in range(70)))
    gateway.result_blocks = True
    rng = np.random.default_rng(0)
    for i in range(3):
        gateway.open_session(f"T{i}")
    for i in range(3):
        gateway.submit(f"T{i}", rng.normal(size=6).astype(np.float32))
    results = gateway.pump(force=True)
    assert len(results) == 3
    assert gateway.metrics.counters["result_pack_errors"] == 1
    records = bus.consumer(TOPIC_FLEET_PREDICTION).poll()
    assert len(records) == 3  # per-tick dicts, not a block
    assert all(r.value.get("kind") is None for r in records)
