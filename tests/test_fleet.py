"""fmda_tpu.fleet — the multi-host distributed serving tier (ISSUE 6).

Covers the acceptance surface in-process (router + workers sharing one
InProcessBus, driven deterministically with a fake clock): ownership
hashing, heartbeat membership, and the migration protocol's headline
contract — a session drained from one worker and resumed on another
produces the bit-identical output sequence an unmigrated single-process
gateway produces over the same ticks, with no drop, duplicate, or
reorder.  The cross-process topology itself is exercised by
``test_multihost_topology`` (spawned workers, worker-hosted data
buses).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fmda_tpu.config import (
    DEFAULT_TOPICS,
    FleetTopologyConfig,
    ModelConfig,
    RuntimeConfig,
    TOPIC_FLEET_PREDICTION,
    fleet_topics,
)
from fmda_tpu.data.normalize import NormParams
from fmda_tpu.fleet.hashring import OwnershipTable, hash_session
from fmda_tpu.fleet.membership import Heartbeater, MembershipView
from fmda_tpu.fleet.router import FleetRouter, NoLiveWorkers
from fmda_tpu.fleet.state import (
    decode_array,
    decode_row,
    decode_session_state,
    encode_array,
    encode_row,
    encode_session_state,
)
from fmda_tpu.fleet.worker import FleetWorker
from fmda_tpu.runtime import BatcherConfig, FleetGateway, SessionPool
from fmda_tpu.stream.bus import InProcessBus


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


#: The carried-state cell families the migration/identity contracts are
#: parametrized over (ISSUE 14): every family the SessionPool serves
#: must survive export/import and drain/replay exactly like the GRU.
#: MIGRATION_CASES derives the (wire_format, cell) matrix: every family
#: on the binary (default) wire, plus the JSON fallback dialect for the
#: reference family and the ring-free ssm export — adding a family to
#: CELLS adds its coverage here.
CELLS = ("gru", "lstm", "ssm")
MIGRATION_CASES = ([("binary", c) for c in CELLS]
                   + [("json", "gru"), ("json", "ssm")])


def _setup(feats=6, hidden=5, window=4, seed=0, cell="gru"):
    cfg = ModelConfig(hidden_size=hidden, n_features=feats, output_size=4,
                      dropout=0.0, bidirectional=False, use_pallas=False,
                      cell=cell)
    from fmda_tpu.models import build_model

    params = build_model(cfg).init(
        {"params": jax.random.PRNGKey(seed)},
        jnp.zeros((1, window, feats)))["params"]
    return cfg, params


# ---------------------------------------------------------------------------
# ownership hashing
# ---------------------------------------------------------------------------


def test_hash_session_is_stable_and_bounded():
    assert hash_session("SPY") == hash_session("SPY")
    assert 0 <= hash_session("SPY", 1024) < 1024
    # crc32-based: stable across processes (unlike salted hash())
    assert hash_session("SPY", 1 << 16) == (
        __import__("zlib").crc32(b"SPY") % (1 << 16))


def test_ownership_table_contiguous_cover_and_determinism():
    table = OwnershipTable.derive(3, ["w2", "w0", "w1"], space=1000)
    assert table.version == 3
    assert table.workers == ("w0", "w1", "w2")  # sorted: pure function
    # contiguous, disjoint, covering exactly [0, space)
    lo = 0
    for _w, r_lo, r_hi in table.ranges:
        assert r_lo == lo
        lo = r_hi
    assert lo == 1000
    # remainder spread one point at a time
    sizes = [hi - lo for _w, lo, hi in table.ranges]
    assert sum(sizes) == 1000 and max(sizes) - min(sizes) <= 1
    # every point owned; same derivation from any observer
    assert table.owner_of_point(0) == "w0"
    assert table.owner_of_point(999) == "w2"
    again = OwnershipTable.derive(3, ["w0", "w1", "w2"], space=1000)
    assert again == table
    assert OwnershipTable.from_wire(table.to_wire()) == table


def test_ownership_empty_fleet():
    table = OwnershipTable.derive(1, [], space=100)
    assert table.owner_of("anything") is None


# ---------------------------------------------------------------------------
# membership
# ---------------------------------------------------------------------------


def test_membership_join_heartbeat_reap_goodbye():
    clock = FakeClock()
    view = MembershipView(timeout_s=2.0, clock=clock)
    assert view.observe({"kind": "hello", "worker": "w0",
                         "capacity": 8}) == "join"
    assert view.observe({"kind": "heartbeat", "worker": "w0",
                         "stats": {"ticks_served": 5}}) is None
    assert view.workers["w0"].stats == {"ticks_served": 5}
    clock.advance(1.0)
    assert view.reap() == []
    clock.advance(2.5)
    assert view.reap() == ["w0"]
    assert view.live() == []
    assert "w0" in view.departed  # final stats stay inspectable
    # a heartbeat from a reaped worker re-joins it
    assert view.observe({"kind": "heartbeat", "worker": "w1"}) == "join"
    assert view.observe({"kind": "goodbye", "worker": "w1"}) == "leave"
    assert view.live() == []


def test_membership_leaving_excluded_from_live_but_present():
    clock = FakeClock()
    view = MembershipView(timeout_s=5.0, clock=clock)
    view.observe({"kind": "hello", "worker": "w0"})
    view.observe({"kind": "hello", "worker": "w1"})
    assert view.mark_leaving("w0")
    assert view.live() == ["w1"]
    assert "w0" in view.workers  # still present: drains its sessions
    # goodbye of an already-leaving worker is not a second leave event
    assert view.observe({"kind": "goodbye", "worker": "w0"}) is None


def test_hello_cancelling_leave_rebalances_like_a_join():
    clock = FakeClock()
    view = MembershipView(timeout_s=5.0, clock=clock)
    view.observe({"kind": "hello", "worker": "w0"})
    view.observe({"kind": "hello", "worker": "w1"})
    assert view.mark_leaving("w0")
    assert view.live() == ["w1"]
    # the re-hello re-enters live() — the router must see a join event
    # (rebalance), or w0 stays live but owns no hash range forever
    assert view.observe({"kind": "hello", "worker": "w0"}) == "join"
    assert view.live() == ["w0", "w1"]
    # a heartbeat does NOT cancel a pending leave
    assert view.mark_leaving("w0")
    assert view.observe({"kind": "heartbeat", "worker": "w0"}) is None
    assert view.live() == ["w1"]


def test_heartbeater_cadence_and_announce():
    clock = FakeClock()
    bus = InProcessBus(("fleet_control",))
    hb = Heartbeater(bus, "w7", control_topic="fleet_control",
                     interval_s=1.0, capacity=4, clock=clock,
                     announce={"address": "127.0.0.1:1234"})
    hb.hello({"ticks_served": 0})
    assert not hb.beat()          # not due yet
    clock.advance(1.5)
    assert hb.beat({"ticks_served": 3})
    hb.goodbye()
    msgs = [r.value for r in bus.read("fleet_control", 0)]
    assert [m["kind"] for m in msgs] == ["hello", "heartbeat", "goodbye"]
    assert all(m["worker"] == "w7" for m in msgs)
    # the data-plane address rides EVERY message (re-join after a reap
    # must re-link)
    assert all(m["address"] == "127.0.0.1:1234" for m in msgs)


# ---------------------------------------------------------------------------
# state codec
# ---------------------------------------------------------------------------


def _wire_round_trip(value, fmt):
    """value -> frame bytes -> value, in the given wire format — the
    exact transformation a SocketBus link applies (fmda_tpu.stream
    .codec)."""
    from fmda_tpu.stream import codec

    payload = codec.encode_payload(value, binary=(fmt == "binary"))
    out, was_binary = codec.decode_payload(payload)
    assert was_binary == (fmt == "binary")
    return out


@pytest.mark.parametrize("fmt", ["binary", "json"])
def test_array_and_row_codec_bit_exact(fmt):
    rng = np.random.default_rng(0)
    a = rng.normal(size=(3, 5)).astype(np.float32)
    b = decode_array(_wire_round_trip(encode_array(a), fmt))
    assert b.dtype == a.dtype and np.array_equal(a, b)
    row = rng.normal(size=108).astype(np.float32)
    assert np.array_equal(
        decode_row(_wire_round_trip(encode_row(row), fmt), 108), row)
    with pytest.raises(ValueError, match="shape"):
        decode_row(_wire_round_trip(encode_row(row), fmt), 64)


def test_row_codec_accepts_legacy_base64_wire_form():
    # state exported by a pre-v2 peer still decodes (mixed-version fleet)
    import base64

    rng = np.random.default_rng(0)
    a = rng.normal(size=(3, 5)).astype(np.float32)
    legacy = {"d": a.dtype.str, "sh": list(a.shape),
              "b": base64.b64encode(a.tobytes()).decode("ascii")}
    assert np.array_equal(decode_array(legacy), a)
    row = rng.normal(size=8).astype(np.float32)
    legacy_row = base64.b64encode(row.tobytes()).decode("ascii")
    assert np.array_equal(decode_row(legacy_row, 8), row)


@pytest.mark.parametrize("fmt,cell", MIGRATION_CASES)
def test_session_state_round_trips_through_gateway_bit_exact(fmt, cell):
    cfg, params = _setup(cell=cell)
    pool = SessionPool(cfg, params, capacity=4, window=4)
    gw = FleetGateway(
        pool, None,
        batcher_config=BatcherConfig(bucket_sizes=(2,), max_linger_s=0.0),
        pipeline_depth=0)
    rng = np.random.default_rng(1)
    norm = NormParams(rng.normal(size=6).astype(np.float32),
                      rng.normal(size=6).astype(np.float32) + 3.0)
    gw.open_session("S", norm)
    for _ in range(5):
        gw.submit("S", rng.normal(size=6).astype(np.float32))
        gw.drain()
    state = gw.export_session("S")
    wire = encode_session_state(state)
    # survives the transport's own frame round trip in BOTH formats
    restored = decode_session_state(_wire_round_trip(wire, fmt))
    assert restored["seq"] == state["seq"] == 5
    assert restored["pos"] == state["pos"]
    np.testing.assert_array_equal(restored["ring"], state["ring"])
    for layer_a, layer_b in zip(restored["carry"], state["carry"]):
        for a, b in zip(layer_a, layer_b):
            np.testing.assert_array_equal(a, b)

    # import into a DIFFERENT pool: continues the same stream bit-exact
    pool2 = SessionPool(cfg, params, capacity=4, window=4)
    gw2 = FleetGateway(
        pool2, None,
        batcher_config=BatcherConfig(bucket_sizes=(2,), max_linger_s=0.0),
        pipeline_depth=0)
    gw2.import_session("S", restored)
    row = rng.normal(size=6).astype(np.float32)
    gw.submit("S", row)
    gw2.submit("S", row)
    r1 = gw.drain()[0]
    r2 = gw2.drain()[0]
    assert r1.seq == r2.seq == 5
    np.testing.assert_array_equal(r1.probabilities, r2.probabilities)


def test_ssm_migration_export_measurably_smaller_than_gru():
    """ISSUE 14 acceptance: at equal H (and the production window=30)
    an SSM session's migration payload is a small constant — three
    H-vectors per layer and a zero-width ring — where the GRU export
    hauls a (window, H) ring.  Measured on the actual encoded wire
    frame, not just array nbytes, so header/codec overhead can't hide
    a regression."""
    from fmda_tpu.stream import codec

    window, hidden = 30, 16
    sizes = {}
    for cell in ("gru", "ssm"):
        cfg, params = _setup(hidden=hidden, window=window, cell=cell)
        pool = SessionPool(cfg, params, capacity=2, window=window)
        gw = FleetGateway(
            pool, None,
            batcher_config=BatcherConfig(bucket_sizes=(1,),
                                         max_linger_s=0.0),
            pipeline_depth=0)
        gw.open_session("S")
        rng = np.random.default_rng(0)
        for _ in range(window + 3):  # past one full ring revolution
            gw.submit("S", rng.normal(size=6).astype(np.float32))
            gw.drain()
        state = gw.export_session("S")
        sizes[cell] = len(codec.encode(encode_session_state(state)))
    # "measurably smaller": >= 2x on the wire with margin — at
    # window=30 the raw state ratio is ~(window+1)/3 ≈ 10x, leaving
    # codec overhead plenty of room
    assert sizes["ssm"] * 2 < sizes["gru"], sizes


# ---------------------------------------------------------------------------
# in-process topology helpers
# ---------------------------------------------------------------------------


class CodecRoundTripBus:
    """An InProcessBus front that pushes every published value through
    the wire codec in a fixed format, so the in-process topology tests
    exercise exactly the value transformation a SocketBus link applies
    (binary frames or the JSON fallback)."""

    def __init__(self, inner, fmt):
        self._inner = inner
        self._fmt = fmt

    def publish(self, topic, value):
        return self._inner.publish(topic, _wire_round_trip(value, self._fmt))

    def publish_many(self, topic, values):
        return self._inner.publish_many(
            topic, [_wire_round_trip(v, self._fmt) for v in values])

    def __getattr__(self, name):
        return getattr(self._inner, name)


def _topology(worker_ids, *, feats=6, window=4, capacity=8,
              bucket_sizes=(1,), start=True, all_ids=None, wire=None,
              cell="gru"):
    cfg, params = _setup(feats=feats, window=window, cell=cell)
    clock = FakeClock()
    bus = InProcessBus(
        tuple(DEFAULT_TOPICS) + fleet_topics(all_ids or worker_ids))
    if wire is not None:
        bus = CodecRoundTripBus(bus, wire)
    fleet_cfg = FleetTopologyConfig(
        heartbeat_interval_s=0.0, heartbeat_timeout_s=50.0)
    rc = RuntimeConfig(capacity=capacity, window=window,
                       bucket_sizes=bucket_sizes, max_linger_ms=0.0,
                       pipeline_depth=0)
    workers = {
        w: FleetWorker(w, bus, cfg, params, config=fleet_cfg, runtime=rc,
                       clock=clock, precompile=False)
        for w in worker_ids
    }
    router = FleetRouter(bus, fleet_cfg, n_features=feats, clock=clock)
    if start:
        for w in workers.values():
            w.start()
        router.pump()
    return router, workers, bus, clock, (cfg, params, rc)


def _cycle(router, workers, results_by_session):
    router.pump()
    for w in workers:
        if not w.stopped:
            w.step()
    for res in router.pump():
        results_by_session.setdefault(res.session_id, []).append(res)


# ---------------------------------------------------------------------------
# routing basics
# ---------------------------------------------------------------------------


def test_router_routes_by_ownership_and_preserves_per_session_order():
    router, workers, _bus, _clock, _ = _topology(
        ["w0", "w1"], bucket_sizes=(1, 4))
    assert router.membership.live() == ["w0", "w1"]
    rng = np.random.default_rng(0)
    sids = [f"T{i}" for i in range(6)]
    for sid in sids:
        mn = rng.normal(size=6).astype(np.float32)
        router.open_session(sid, NormParams(mn, mn + 1.0))
    got = {}
    for _ in range(8):
        for sid in sids:
            router.submit(sid, rng.normal(size=6).astype(np.float32))
        _cycle(router, workers.values(), got)
    for _ in range(4):
        _cycle(router, workers.values(), got)
    for sid in sids:
        seqs = [r.seq for r in got[sid]]
        assert seqs == list(range(8)), (sid, seqs)
    # both workers actually own sessions (6 sessions, 2 ranges)
    owners = {router.table.owner_of(sid) for sid in sids}
    assert owners == {"w0", "w1"}
    # ticks landed on the owner's inbox, not broadcast
    assert workers["w0"].pool.n_active + workers["w1"].pool.n_active == 6


def test_open_session_without_workers_rejects_loudly():
    router, _workers, _bus, _clock, _ = _topology([], start=False)
    with pytest.raises(NoLiveWorkers):
        router.open_session("S")
    assert router.metrics.counters["rejected_sessions"] == 1


def test_router_backpressure_saturates_on_inflight_bound():
    router, workers, _bus, _clock, _ = _topology(["w0"])
    router.cfg = FleetTopologyConfig(
        heartbeat_interval_s=0.0, heartbeat_timeout_s=50.0,
        max_inflight_ticks=10)
    router.open_session("S")
    rng = np.random.default_rng(0)
    for _ in range(10):
        router.submit("S", rng.normal(size=6).astype(np.float32))
    assert router.saturated
    got = {}
    for _ in range(12):
        _cycle(router, workers.values(), got)
    assert not router.saturated
    assert [r.seq for r in got["S"]] == list(range(10))


# ---------------------------------------------------------------------------
# live migration: the bit-identity acceptance test
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("wire,cell", MIGRATION_CASES)
def test_live_migration_output_bit_identical_to_unmigrated_run(wire, cell):
    """Kill/drain a worker's ownership mid-stream (here: a second worker
    joins, so half the sessions drain off w0 and resume on w1 with
    carried state + buffered-tick replay) and assert every migrated
    session's output sequence is bit-identical to an unmigrated
    single-process run over the same tick sequence — no dropped,
    duplicated, or reordered ticks.  Bucket size 1 on both sides keeps
    the comparison free of XLA's B>1 reduction-order noise (the same
    discipline the solo-vs-multiplexed identity tests use).
    Parametrized over BOTH wire formats: every routed tick, exported
    state blob, and result crosses the codec (ISSUE 12 bit-identity
    acceptance — binary framing must not perturb a single ulp)."""
    feats, window, n_rounds = 6, 4, 12
    cfg, params = _setup(feats=feats, window=window, cell=cell)
    rng = np.random.default_rng(1)
    sids = [f"T{i}" for i in range(5)]
    norms = {}
    rows = {}
    for sid in sids:
        mn = rng.normal(size=feats).astype(np.float32)
        norms[sid] = NormParams(mn, mn + 2.0)
        rows[sid] = rng.normal(size=(n_rounds, feats)).astype(np.float32)

    # reference: one FleetGateway, strictly serial, bucket 1
    pool = SessionPool(cfg, params, capacity=8, window=window)
    gw = FleetGateway(
        pool, None,
        batcher_config=BatcherConfig(bucket_sizes=(1,), max_linger_s=0.0),
        pipeline_depth=0)
    ref = {sid: [] for sid in sids}
    for sid in sids:
        gw.open_session(sid, norms[sid])
    for r in range(n_rounds):
        for sid in sids:
            gw.submit(sid, rows[sid][r])
            for res in gw.drain():
                ref[res.session_id].append(res.probabilities)

    # topology: w0 alone; w1 joins mid-stream -> live migration with
    # ticks submitted DURING the handoff (exercises the router buffer)
    router, workers, bus, clock, (mcfg, mparams, rc) = _topology(
        ["w0"], all_ids=["w0", "w1"], wire=wire, cell=cell)
    for sid in sids:
        router.open_session(sid, norms[sid])
    got = {}
    live = list(workers.values())
    for r in range(n_rounds):
        if r == 5:
            w1 = FleetWorker(
                "w1", bus, mcfg, mparams,
                config=router.cfg, runtime=rc, clock=clock,
                precompile=False)
            workers["w1"] = w1
            live.append(w1)
            w1.start()
            router.pump()  # hello -> rebalance -> drain markers enqueued
            # submit a round BEFORE the drains/exports are processed:
            # these ticks must buffer at the router and replay in order
            for sid in sids:
                router.submit(sid, rows[sid][r])
            for _ in range(4):
                _cycle(router, live, got)
            continue
        for sid in sids:
            router.submit(sid, rows[sid][r])
        _cycle(router, live, got)
    for _ in range(8):
        _cycle(router, live, got)

    counters = router.metrics.counters
    assert counters["migrations_completed"] >= 1
    assert counters.get("migration_replayed_ticks", 0) >= 1  # buffer used
    assert counters.get("sessions_lost_state", 0) == 0
    migrated = [sid for sid in sids if router.table.owner_of(sid) == "w1"]
    assert migrated  # the rebalance actually moved sessions
    for sid in sids:
        seqs = [r_.seq for r_ in got[sid]]
        assert seqs == list(range(n_rounds)), (sid, seqs)
        for r in range(n_rounds):
            np.testing.assert_array_equal(
                got[sid][r].probabilities, ref[sid][r],
                err_msg=f"{sid} tick {r} diverged after migration")


def test_graceful_leave_migrates_everything_and_stops_the_worker():
    router, workers, _bus, _clock, _ = _topology(["w0", "w1"])
    rng = np.random.default_rng(0)
    sids = [f"T{i}" for i in range(6)]
    for sid in sids:
        router.open_session(sid)
    got = {}
    for _ in range(3):
        for sid in sids:
            router.submit(sid, rng.normal(size=6).astype(np.float32))
        _cycle(router, workers.values(), got)
    router.request_leave("w0")
    for _ in range(10):
        _cycle(router, workers.values(), got)
    assert workers["w0"].stopped          # released once it owned nothing
    assert workers["w0"].pool.n_active == 0
    assert all(router.table.owner_of(sid) == "w1" for sid in sids)
    assert router.metrics.counters.get("sessions_lost_state", 0) == 0
    # the stream keeps flowing afterwards, seqs intact
    for sid in sids:
        router.submit(sid, rng.normal(size=6).astype(np.float32))
    for _ in range(4):
        _cycle(router, workers.values(), got)
    for sid in sids:
        assert [r.seq for r in got[sid]] == list(range(4))


def test_worker_death_reopens_sessions_fresh_and_counted():
    router, workers, _bus, clock, _ = _topology(["w0", "w1"])
    rng = np.random.default_rng(0)
    sids = [f"T{i}" for i in range(6)]
    for sid in sids:
        router.open_session(sid)
    got = {}
    for _ in range(3):
        for sid in sids:
            router.submit(sid, rng.normal(size=6).astype(np.float32))
        _cycle(router, workers.values(), got)
    victim = router.table.owner_of(sids[0])
    survivor = "w1" if victim == "w0" else "w0"
    lost_sids = [s for s in sids if router.table.owner_of(s) == victim]
    # the victim dies silently: stops stepping, no goodbye
    workers[victim].stopped = True
    clock.advance(60.0)                   # past heartbeat_timeout_s=50
    workers[survivor].step()              # survivor beats at the new now
    router.pump()                         # beat observed, victim reaped
    counters = router.metrics.counters
    assert counters["workers_dead"] == 1
    assert counters["sessions_lost_state"] == len(lost_sids)
    assert all(router.table.owner_of(s) == survivor for s in sids)
    # streams continue on the survivor: fresh state but NO seq collision
    for sid in sids:
        router.submit(sid, rng.normal(size=6).astype(np.float32))
    for _ in range(5):
        _cycle(router, [workers[survivor]], got)
    for sid in sids:
        seqs = [r.seq for r in got[sid]]
        assert seqs == sorted(set(seqs)), (sid, seqs)  # no dupes/reorder
        assert seqs[-1] == 3              # the post-death tick answered


def test_sessions_lost_state_counted_once_across_ownerless_gap():
    # owner dies with NO survivor: sessions park ownerless (counted
    # lost once); the later join that finally places them must not
    # count the same loss again
    router, workers, _bus, clock, _ = _topology(["w0", "w1"], start=False)
    workers["w0"].start()
    router.pump()
    sids = [f"T{i}" for i in range(3)]
    for sid in sids:
        router.open_session(sid)
    workers["w0"].stopped = True          # silent death, no goodbye
    clock.advance(60.0)                   # past heartbeat_timeout_s=50
    router.pump()                         # reaped; fleet is empty
    counters = router.metrics.counters
    assert counters["sessions_lost_state"] == len(sids)
    assert all(s.owner is None for s in router._sessions.values())
    workers["w1"].start()                 # a replacement finally joins
    router.pump()
    assert counters["sessions_lost_state"] == len(sids)  # NOT doubled
    assert all(s.owner == "w1" and s.status == "active"
               for s in router._sessions.values())


def test_relink_after_transient_error_resumes_results_offset():
    from fmda_tpu.stream.bus import Record

    class FakeLinkBus:
        """A worker-hosted bus whose link can blip while its retained
        records survive (what a socket error on a live worker means)."""

        def __init__(self):
            self.rows = []
            self.fail = False

        def publish_many(self, topic, values):
            if self.fail:
                raise ConnectionError("link down")

        def read(self, topic, offset):
            if self.fail:
                raise ConnectionError("link down")
            return [Record(topic, o, v) for o, v in self.rows
                    if o >= offset]

        def close(self):
            pass

    clock = FakeClock()
    bus = InProcessBus(tuple(DEFAULT_TOPICS) + fleet_topics(["w0"]))
    link_bus = FakeLinkBus()
    router = FleetRouter(
        bus, FleetTopologyConfig(heartbeat_timeout_s=50.0),
        n_features=4, clock=clock, connect_fn=lambda addr: link_bus)
    bus.publish("fleet_control", {"kind": "hello", "worker": "w0",
                                  "address": "addr:1"})
    router.pump()
    link_bus.rows = [(0, {"session": "X", "seq": 0}),
                     (1, {"session": "X", "seq": 1})]
    assert len(router.pump()) == 2
    assert router._links["w0"].results_offset == 2
    # transient blip: the link drops but the worker's bus survives
    link_bus.fail = True
    router.pump()
    assert "w0" not in router._links
    link_bus.fail = False
    bus.publish("fleet_control", {"kind": "heartbeat", "worker": "w0",
                                  "address": "addr:1"})
    # re-linked at the SAVED offset: the retained rows are not
    # re-delivered as duplicate results
    assert router.pump() == []
    assert router._links["w0"].results_offset == 2
    # a fresh incarnation hellos — its new bus starts EMPTY at offset
    # 0, so the saved resume position must be forgotten (resuming at 2
    # on the new bus would silently skip its first two results)
    link_bus.fail = True
    router.pump()
    link_bus.fail = False
    link_bus.rows = []                    # the restart began a new bus
    bus.publish("fleet_control", {"kind": "hello", "worker": "w0",
                                  "address": "addr:1"})
    router.pump()
    assert router._links["w0"].results_offset == 0
    assert not router._link_resume


# ---------------------------------------------------------------------------
# reconnect storm (loadgen adversarial shape)
# ---------------------------------------------------------------------------


def test_reconnect_storm_on_gateway_counted_and_lossless_at_the_pool():
    from fmda_tpu.runtime import FleetLoadConfig, run_fleet_load
    from fmda_tpu.stream.bus import InProcessBus as Bus

    cfg, params = _setup()
    pool = SessionPool(cfg, params, capacity=16, window=4)
    gw = FleetGateway(
        pool, Bus(DEFAULT_TOPICS),
        batcher_config=BatcherConfig(bucket_sizes=(4, 16),
                                     max_linger_s=0.0))
    out = run_fleet_load(gw, FleetLoadConfig(
        n_sessions=8, n_ticks=30, seed=0,
        storm_every=10, storm_fraction=0.5))
    assert out["sessions_reopened"] == 8  # 2 storms x 4 sessions
    # a reopened session restarts at seq 0 with a fresh slot; nothing
    # crashes and the pool never leaks slots
    assert pool.n_active == 8
    assert out["ticks_served"] > 0


def test_reconnect_storm_through_the_router():
    router, workers, _bus, _clock, _ = _topology(
        ["w0", "w1"], capacity=16, bucket_sizes=(1, 4))
    rng = np.random.default_rng(0)
    sids = [f"T{i}" for i in range(6)]
    for sid in sids:
        router.open_session(sid)
    got = {}
    for r in range(9):
        if r in (3, 6):
            # burst: every session closes and instantly reopens
            for sid in sids:
                router.close_session(sid)
                router.open_session(sid)
        for sid in sids:
            router.submit(sid, rng.normal(size=6).astype(np.float32))
        _cycle(router, workers.values(), got)
    for _ in range(6):
        _cycle(router, workers.values(), got)
    c = router.metrics.counters
    assert c["sessions_closed"] == 12 and c["sessions_opened"] == 18
    # per-incarnation seqs stay ordered; dropped in-flight ticks of dead
    # incarnations are counted, never silently lost
    for sid in sids:
        seqs = [r.seq for r in got[sid]]
        incarnation_starts = [i for i, s in enumerate(seqs) if s == 0]
        assert len(incarnation_starts) >= 1
        for a, b in zip(incarnation_starts, incarnation_starts[1:]):
            chunk = seqs[a:b]
            assert chunk == list(range(len(chunk)))
    total_answered = sum(len(v) for v in got.values())
    dropped = (c.get("inflight_dropped_on_close", 0)
               + c.get("results_missing", 0))
    assert total_answered + dropped >= 9 * 6  # every tick accounted for


# ---------------------------------------------------------------------------
# wire format v2: mixed-version topology (ISSUE 12)
# ---------------------------------------------------------------------------


def test_mixed_wire_format_topology_negotiates_down_and_serves():
    """A binary-capable (wire_format=auto) worker joined to a JSON-
    pinned bus server negotiates down to JSON frames and serves
    correctly end to end — opens, columnar tick blocks (arrays lowered
    to tagged base64 on the JSON link), results — the mixed-version
    fleet acceptance shape.  Real socket, real worker, shared-bus
    topology."""
    from fmda_tpu.fleet.wire import BusServer, SocketBus

    cfg, params = _setup()
    clock = FakeClock()
    inner = InProcessBus(tuple(DEFAULT_TOPICS) + fleet_topics(["w0"]))
    server = BusServer(inner, wire_format="json").start()
    try:
        wbus = SocketBus.connect(server.address, wire_format="auto")
        assert wbus.negotiated_format == "json"  # negotiated DOWN
        fleet_cfg = FleetTopologyConfig(
            heartbeat_interval_s=0.0, heartbeat_timeout_s=50.0)
        rc = RuntimeConfig(capacity=8, window=4, bucket_sizes=(1,),
                           max_linger_ms=0.0, pipeline_depth=0)
        worker = FleetWorker(
            "w0", wbus, cfg, params, config=fleet_cfg, runtime=rc,
            clock=clock, precompile=False)
        router = FleetRouter(inner, fleet_cfg, n_features=6, clock=clock)
        worker.start()
        router.pump()
        assert router.membership.live() == ["w0"]
        rng = np.random.default_rng(0)
        router.open_session("S")
        got = []
        for _ in range(5):
            router.submit("S", rng.normal(size=6).astype(np.float32))
            router.pump()
            worker.step()
            got.extend(router.pump())
        for _ in range(4):
            worker.step()
            got.extend(router.pump())
        assert [r.seq for r in got] == list(range(5))
        assert all(r.probabilities.shape == (4,) for r in got)
        # the JSON link really carried the traffic (no binary frames)
        stats = wbus.frame_stats()
        assert stats["binary"] == 0 and stats["json"] > 0
        assert stats["malformed"] == 0
        wbus.close()
    finally:
        server.stop()


def test_json_link_lowers_payloads_to_pre_v2_shapes():
    """A data link that negotiated down to JSON carries the full pre-v2
    payload dialect — bare-base64 tick rows, no columnar blocks,
    enveloped arrays in opens — so a genuinely old worker parses every
    message (the docs' rolling-upgrade claim, made literal)."""
    from fmda_tpu.fleet.state import decode_array

    class JsonCaptureBus:
        negotiated_format = "json"  # what a pre-v2 peer's link reports

        def __init__(self):
            self.published = []

        def publish_many(self, topic, values):
            self.published.extend(values)

        def read(self, topic, offset):
            return []

        def close(self):
            pass

    clock = FakeClock()
    bus = InProcessBus(tuple(DEFAULT_TOPICS) + fleet_topics(["w0"]))
    link = JsonCaptureBus()
    router = FleetRouter(
        bus, FleetTopologyConfig(heartbeat_timeout_s=50.0),
        n_features=4, clock=clock, connect_fn=lambda addr: link)
    bus.publish("fleet_control", {"kind": "hello", "worker": "w0",
                                  "address": "addr:1"})
    router.pump()
    rng = np.random.default_rng(0)
    mn = rng.normal(size=4).astype(np.float32)
    router.open_session("S", NormParams(mn, mn + 1.0))
    rows = rng.normal(size=(3, 4)).astype(np.float32)
    for r in rows:
        router.submit("S", r)
    router.pump()
    kinds = [m["kind"] for m in link.published]
    assert kinds == ["open", "tick", "tick", "tick"]  # no tick_block
    open_msg = link.published[0]
    x_min = open_msg["norm"]["x_min"]
    assert isinstance(x_min, dict) and set(x_min) == {"d", "sh", "b"}
    np.testing.assert_array_equal(decode_array(x_min), mn)  # bit-exact
    for i, m in enumerate(link.published[1:]):
        assert isinstance(m["row"], str)  # bare base64, old decode_row
        np.testing.assert_array_equal(decode_row(m["row"], 4), rows[i])


def test_binary_link_keeps_columnar_blocks():
    # the lowering is per-link: a binary (or in-process) bus still gets
    # tick blocks
    class BinaryCaptureBus:
        negotiated_format = "binary"

        def __init__(self):
            self.published = []

        def publish_many(self, topic, values):
            self.published.extend(values)

        def read(self, topic, offset):
            return []

        def close(self):
            pass

    clock = FakeClock()
    bus = InProcessBus(tuple(DEFAULT_TOPICS) + fleet_topics(["w0"]))
    link = BinaryCaptureBus()
    router = FleetRouter(
        bus, FleetTopologyConfig(heartbeat_timeout_s=50.0),
        n_features=4, clock=clock, connect_fn=lambda addr: link)
    bus.publish("fleet_control", {"kind": "hello", "worker": "w0",
                                  "address": "addr:1"})
    router.pump()
    router.open_session("S")
    rng = np.random.default_rng(0)
    for _ in range(3):
        router.submit("S", rng.normal(size=4).astype(np.float32))
    router.pump()
    kinds = [m["kind"] for m in link.published]
    assert kinds == ["open", "tick_block"]


def test_shared_bus_pre_v2_peer_gets_legacy_dialect():
    """Broker-mediated mixed-version fleet: the router's own broker
    link may be binary, but a worker whose liveness messages never
    declared v2 capability (no ``wire`` field — a pre-v2 process) must
    receive the pre-v2 payload dialect on the shared bus; a worker
    that declared ``wire: 2`` gets columnar blocks."""
    clock = FakeClock()
    bus = InProcessBus(tuple(DEFAULT_TOPICS) + fleet_topics(["w0", "w1"]))
    router = FleetRouter(
        bus, FleetTopologyConfig(heartbeat_timeout_s=50.0),
        n_features=4, clock=clock)
    # w0: pre-v2 hello (no wire field); w1: v2 hello
    bus.publish("fleet_control", {"kind": "hello", "worker": "w0"})
    bus.publish("fleet_control", {"kind": "hello", "worker": "w1",
                                  "wire": 2})
    router.pump()
    rng = np.random.default_rng(0)
    opened = {"w0": None, "w1": None}
    i = 0
    while not all(opened.values()):  # one session owned by each worker
        sid = f"S{i}"
        i += 1
        owner = router.table.owner_of(sid)
        if opened[owner] is None:
            router.open_session(sid)
            opened[owner] = sid
    for _ in range(3):
        for sid in opened.values():
            router.submit(sid, rng.normal(size=4).astype(np.float32))
    router.pump()
    from fmda_tpu.config import fleet_worker_topic

    w0_msgs = [r.value for r in bus.read(fleet_worker_topic("w0"), 0)]
    w1_msgs = [r.value for r in bus.read(fleet_worker_topic("w1"), 0)]
    assert [m["kind"] for m in w0_msgs] == ["open"] + ["tick"] * 3
    assert all(isinstance(m["row"], str) for m in w0_msgs[1:])  # pre-v2
    assert "tick_block" in [m["kind"] for m in w1_msgs]  # v2 blocks


# ---------------------------------------------------------------------------
# columnar result blocks (ISSUE 13 satellite)
# ---------------------------------------------------------------------------


def test_v2_router_enables_result_blocks_and_matches_every_tick():
    """The open's ``wire: 2`` stamp flips the worker's gateway into
    columnar result publishing; the router expands the blocks and
    matches every routed tick — nothing unmatched, nothing undecodable."""
    router, workers, bus, _clock, _ = _topology(
        ["w0"], bucket_sizes=(4,), capacity=8)
    w = workers["w0"]
    assert w.gateway.result_blocks is False  # until v2 evidence arrives
    rng = np.random.default_rng(5)
    sids = [f"T{i}" for i in range(4)]
    for sid in sids:
        mn = rng.normal(size=6).astype(np.float32)
        router.open_session(sid, NormParams(mn, mn + 1.0))
    got = {}
    for _ in range(3):
        for sid in sids:
            router.submit(sid, rng.normal(size=6).astype(np.float32))
        _cycle(router, workers.values(), got)
    for _ in range(3):
        _cycle(router, workers.values(), got)
    assert w.gateway.result_blocks is True
    assert sorted(got) == sids
    assert all(len(v) == 3 for v in got.values())
    # the wire actually carried columnar blocks, not per-tick dicts
    records = bus.consumer(TOPIC_FLEET_PREDICTION).poll()
    kinds = [r.value.get("kind") for r in records]
    assert "result_block" in kinds
    assert router.metrics.counters.get("results_unmatched", 0) == 0
    assert router.metrics.counters.get("results_undecodable", 0) == 0


def test_pre_v2_router_takeover_downgrades_result_blocks():
    """A worker that enabled columnar result blocks under a v2 router
    rolls the dialect back the moment a pre-v2 router (no ``wire``
    stamp on its control messages) takes over — an old router cannot
    parse blocks, and its every open/drain proves its age."""
    router, workers, _bus, _clock, _ = _topology(["w0"])
    w = workers["w0"]
    w._apply({"kind": "open", "session": "S0", "norm": None, "seq": 0,
              "wire": 2})
    assert w.gateway.result_blocks is True
    # a pre-v2 router's open carries no wire field
    w._apply({"kind": "open", "session": "S1", "norm": None, "seq": 0})
    assert w.gateway.result_blocks is False
    # plain per-tick messages (which v2 routers also send for short
    # runs) are NOT downgrade evidence
    w._apply({"kind": "tick_block", "ids": ["S0"],
              "idx": np.zeros(2, np.int32), "seqs": np.arange(2),
              "rows": np.zeros((2, 6), np.float32)})
    assert w.gateway.result_blocks is True
    w._apply({"kind": "tick", "session": "S0",
              "row": np.zeros(6, np.float32), "seq": 2})
    assert w.gateway.result_blocks is True


def test_membership_rehello_without_metrics_clears_stale_url():
    view = MembershipView(10.0, clock=lambda: 0.0)
    view.observe({"kind": "hello", "worker": "w0",
                  "metrics": "http://127.0.0.1:9"})
    assert view.workers["w0"].metrics == "http://127.0.0.1:9"
    # heartbeats without the field keep the announced URL
    view.observe({"kind": "heartbeat", "worker": "w0"})
    assert view.workers["w0"].metrics == "http://127.0.0.1:9"
    # a replacement incarnation without --metrics-port clears it —
    # the aggregator must not scrape a dead endpoint forever
    view.observe({"kind": "hello", "worker": "w0"})
    assert view.workers["w0"].metrics is None
