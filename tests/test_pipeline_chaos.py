"""Data-plane chaos (ISSUE 10): degraded-mode joins, the write-ahead
warehouse journal, engine crash-replay dedupe, checkpoint-corruption
survival, and the pipeline soak's never-abort gates.

The fast tier-1 surface runs everything in-process and deterministic
(no jax, no subprocesses); the full calibrated soak with the jitted
Predictor attached is the slow-marked test at the bottom.
"""

import json
import os

import numpy as np
import pytest

from fmda_tpu.chaos import FaultEvent, FaultPlan
from fmda_tpu.config import DEFAULT_TOPICS, TOPIC_VIX, WarehouseConfig
from fmda_tpu.stream import InProcessBus, StreamEngine, Warehouse
from fmda_tpu.stream.journal import BufferedWarehouse

from test_stream import _session_messages, _small_features


def _vix_col(wh):
    return wh.x_fields.index("VIX")


def _publish_tick(bus, msgs, i, skip=()):
    """Publish tick ``i``'s messages, withholding the ``skip`` topics."""
    for topic, msg in msgs[4 * i:4 * (i + 1)]:
        if topic not in skip:
            bus.publish(topic, msg)


# ---------------------------------------------------------------------------
# degraded-mode joins
# ---------------------------------------------------------------------------


def test_degraded_join_emits_last_known_values_and_recovers():
    """A side feed going quiet past the staleness deadline stops
    blocking the join: rows emit with the feed's last-known value,
    counted per topic; when the feed resumes, joins are clean again and
    the degraded flag clears."""
    fc = _small_features(get_cot=False)
    bus = InProcessBus(DEFAULT_TOPICS)
    wh = Warehouse(fc, WarehouseConfig(path=":memory:"))
    eng = StreamEngine(bus, wh, fc, staleness_deadline_s=450)
    msgs = _session_messages(6)

    _publish_tick(bus, msgs, 0)           # tick 0: all feeds healthy
    assert eng.step() == 1
    assert eng.degraded_streams() == ()
    for i in (1, 2, 3):                   # vix goes dark
        _publish_tick(bus, msgs, i, skip=(TOPIC_VIX,))
        eng.step()
    # at 5-min tick spacing the watermark age blows through 450s on
    # tick 1 already: every vix-less tick lands with the LAST KNOWN vix
    assert TOPIC_VIX in eng.degraded_streams()
    st = eng.stats
    assert st["degraded_rows"][TOPIC_VIX] == 3
    assert st["degraded_streams"] == [TOPIC_VIX]
    assert len(wh) == 4
    x = wh.fetch(range(1, 5))
    vix = x[:, _vix_col(wh)]
    assert vix[0] == pytest.approx(16.0)          # the real tick-0 value
    assert all(v == pytest.approx(16.0) for v in vix[1:])  # last known
    assert set(eng.degraded_row_timestamps) == {
        msgs[4 * i][1]["Timestamp"] for i in (1, 2, 3)}

    for i in (4, 5):                      # vix recovers
        _publish_tick(bus, msgs, i)
        eng.step()
    assert eng.degraded_streams() == ()   # recovery is automatic
    assert len(wh) == 6
    x = wh.fetch(range(1, 7))
    assert x[4, _vix_col(wh)] == pytest.approx(20.0)  # real value again
    assert x[5, _vix_col(wh)] == pytest.approx(21.0)
    assert eng.stats["degraded_rows"][TOPIC_VIX] == 3  # no new ghosts


def test_degraded_join_with_never_delivered_feed_lands_zeros():
    """A feed that never delivered has no last-known values: once book
    time has advanced past the deadline, rows land with the feature
    absent (fillna 0), instead of stalling the pipeline forever."""
    fc = _small_features(get_cot=False)
    bus = InProcessBus(DEFAULT_TOPICS)
    wh = Warehouse(fc, WarehouseConfig(path=":memory:"))
    eng = StreamEngine(bus, wh, fc, staleness_deadline_s=450)
    msgs = _session_messages(3)
    for i in range(3):
        _publish_tick(bus, msgs, i, skip=(TOPIC_VIX,))
        eng.step()
    assert TOPIC_VIX in eng.degraded_streams()
    assert len(wh) == 3                   # nothing stalled
    x = wh.fetch(range(1, 4))
    assert np.all(x[:, _vix_col(wh)] == 0.0)
    assert eng.stats["degraded_rows"][TOPIC_VIX] == 3


def test_degraded_disabled_by_default_keeps_stall_semantics():
    fc = _small_features(get_cot=False)
    bus = InProcessBus(DEFAULT_TOPICS)
    wh = Warehouse(fc, WarehouseConfig(path=":memory:"))
    eng = StreamEngine(bus, wh, fc)       # no deadline configured
    msgs = _session_messages(3)
    for i in range(3):
        _publish_tick(bus, msgs, i, skip=(TOPIC_VIX,))
        eng.step()
    assert eng.degraded_streams() == ()
    assert len(wh) == 0                   # strict inner join: waiting
    assert eng.stats["pending"] == 3


def test_degraded_mode_forces_python_join_backend():
    """The C++ core has no real-beats-ghost match rule, so a staleness
    deadline forces the (bit-identical) python scheduler, loudly."""
    fc = _small_features(get_cot=False)
    eng = StreamEngine(
        InProcessBus(DEFAULT_TOPICS),
        Warehouse(fc, WarehouseConfig(path=":memory:")), fc,
        join_backend="native", staleness_deadline_s=450)
    assert eng._core is None


def test_degraded_state_checkpoint_round_trip(tmp_path):
    """Ghost events, last-known payloads, and the degraded counters all
    survive a checkpoint/restore — a restart mid-outage resumes in the
    same degraded posture, not a fresh stall."""
    fc = _small_features(get_cot=False)
    bus = InProcessBus(DEFAULT_TOPICS)
    wh = Warehouse(fc, WarehouseConfig(path=":memory:"))
    ckpt = str(tmp_path / "eng.json")
    eng = StreamEngine(bus, wh, fc, checkpoint_path=ckpt,
                       checkpoint_every=100, staleness_deadline_s=450)
    msgs = _session_messages(4)
    _publish_tick(bus, msgs, 0)
    eng.step()
    for i in (1, 2):
        _publish_tick(bus, msgs, i, skip=(TOPIC_VIX,))
        eng.step()
    eng.checkpoint()
    eng2 = StreamEngine(bus, wh, fc, checkpoint_path=ckpt,
                        checkpoint_every=100, staleness_deadline_s=450)
    assert eng2.stats["degraded_rows"] == eng.stats["degraded_rows"]
    assert set(eng2.degraded_row_timestamps) == \
        set(eng.degraded_row_timestamps)
    buf, buf2 = (e._side_streams[TOPIC_VIX] for e in (eng, eng2))
    assert buf2.max_ts == buf.max_ts
    assert buf2.last_payload == buf.last_payload
    assert [(e.ts, e.degraded) for e in buf2.events] == \
        [(e.ts, e.degraded) for e in buf.events]
    # the restored engine keeps serving degraded rows with the same
    # last-known value
    _publish_tick(bus, msgs, 3, skip=(TOPIC_VIX,))
    eng2.step()
    assert len(wh) == 4
    assert wh.fetch([4])[0, _vix_col(wh)] == pytest.approx(16.0)


def test_stream_buffer_restore_round_trip_with_ahead_watermark(tmp_path):
    """_StreamBuffer state round-trips exactly through the checkpoint,
    including a watermark strictly ahead of every buffered event (the
    post-eviction shape) — the restored buffer must not re-derive a
    stale watermark from its surviving events."""
    fc = _small_features(get_cot=False)
    bus = InProcessBus(DEFAULT_TOPICS)
    wh = Warehouse(fc, WarehouseConfig(path=":memory:"))
    ckpt = str(tmp_path / "eng.json")
    eng = StreamEngine(bus, wh, fc, checkpoint_path=ckpt)
    buf = eng._side_streams[TOPIC_VIX]
    from fmda_tpu.stream.engine import _Event

    buf.add(_Event(1000, "a", {"VIX": 1.0}))
    buf.add(_Event(1300, "b", {"VIX": 2.0}))
    buf.evict_before(1200)                # "a" evicted
    buf.max_ts = 2500                     # watermark ahead of events
    eng.checkpoint()
    eng2 = StreamEngine(bus, wh, fc, checkpoint_path=ckpt)
    buf2 = eng2._side_streams[TOPIC_VIX]
    assert buf2.max_ts == 2500            # restored exactly, not 1300
    assert [(e.ts, e.ts_str, e.payload) for e in buf2.events] == \
        [(1300, "b", {"VIX": 2.0})]
    assert buf2.last_payload == {"VIX": 2.0}
    assert buf2.watermark(300) == 2200


# ---------------------------------------------------------------------------
# engine crash-replay + checkpoint corruption
# ---------------------------------------------------------------------------


def test_crash_replay_dedupes_exactly_once_via_has_timestamp(
        tmp_path, monkeypatch):
    """Kill between the warehouse write and the checkpoint: the restart
    rewinds the bus offsets and replays the already-landed rows, which
    must dedupe to exactly-once landing — through the in-memory seed
    for recent rows AND through the indexed ``has_timestamp`` probe for
    rows older than the seed window."""
    fc = _small_features(get_cot=False)
    bus = InProcessBus(DEFAULT_TOPICS)
    wh = Warehouse(fc, WarehouseConfig(path=":memory:"))
    ckpt = str(tmp_path / "eng.json")
    eng = StreamEngine(bus, wh, fc, checkpoint_path=ckpt,
                       checkpoint_every=100)
    eng.checkpoint()                      # durable state: offsets 0
    msgs = _session_messages(2)
    for i in range(2):
        _publish_tick(bus, msgs, i)
        eng.step()
    assert len(wh) == 2
    # SIGKILL here: rows landed, checkpoint still at offsets 0.  The
    # next incarnation replays BOTH ticks.  A 1-entry dedupe seed forces
    # the older tick through the warehouse has_timestamp fallback.
    monkeypatch.setattr(StreamEngine, "_LANDED_SEED_LIMIT", 1)
    probes = []
    orig = wh.has_timestamp
    wh.has_timestamp = lambda ts: (probes.append(ts), orig(ts))[1]
    eng2 = StreamEngine(bus, wh, fc, checkpoint_path=ckpt,
                        checkpoint_every=100)
    assert eng2.step() == 0               # replayed rows deduped
    assert len(wh) == 2                   # exactly-once landing
    assert msgs[0][1]["Timestamp"] in probes  # the indexed probe ran
    sig = bus.consumer("predict_timestamp").poll()
    assert len(sig) == 2                  # no duplicate signals either


def test_corrupt_checkpoint_is_a_counted_fresh_start(tmp_path):
    """A truncated/garbage checkpoint file must not take the engine
    down: counted fresh start, the bad file moved aside, and a leftover
    ``.tmp`` from a mid-checkpoint kill cleaned up."""
    fc = _small_features(get_cot=False)
    bus = InProcessBus(DEFAULT_TOPICS)
    wh = Warehouse(fc, WarehouseConfig(path=":memory:"))
    ckpt = str(tmp_path / "eng.json")
    with open(ckpt, "w") as fh:
        fh.write('{"offsets": {"deep": 3')   # torn mid-write
    with open(ckpt + ".tmp", "w") as fh:
        fh.write("partial")                  # killed mid-checkpoint()
    eng = StreamEngine(bus, wh, fc, checkpoint_path=ckpt)
    assert eng.stats["checkpoint_corrupt"] == 1
    assert not os.path.exists(ckpt + ".tmp")
    assert os.path.exists(ckpt + ".corrupt")  # kept for forensics
    for i, (topic, msg) in enumerate(_session_messages(2)):
        bus.publish(topic, msg)
    assert eng.step() == 2                # fresh start serves normally
    eng.checkpoint()                      # and can checkpoint again
    assert json.load(open(ckpt))["offsets"]


def test_corrupt_checkpoint_halfway_fields_do_not_half_apply(tmp_path):
    """A checkpoint that parses as JSON but fails mid-validation (bad
    buffers section) must leave the engine fully fresh — offsets not
    moved, buffers empty — not half-restored."""
    fc = _small_features(get_cot=False)
    bus = InProcessBus(DEFAULT_TOPICS)
    wh = Warehouse(fc, WarehouseConfig(path=":memory:"))
    ckpt = str(tmp_path / "eng.json")
    with open(ckpt, "w") as fh:
        json.dump({"offsets": {"deep": 7},
                   "buffers": {"vix": {"events": "not-a-list"}}}, fh)
    eng = StreamEngine(bus, wh, fc, checkpoint_path=ckpt)
    assert eng.stats["checkpoint_corrupt"] == 1
    assert eng._consumers["deep"].offset == 0


# ---------------------------------------------------------------------------
# the write-ahead journal
# ---------------------------------------------------------------------------


class _FlakyStore:
    """Minimal warehouse double with a switchable outage."""

    def __init__(self):
        self.rows = []
        self.down = False

    def insert_rows(self, rows):
        if self.down:
            raise ConnectionError("store down")
        self.rows.extend(dict(r) for r in rows)
        return len(rows)

    def has_timestamp(self, ts):
        return any(r["Timestamp"] == ts for r in self.rows)

    def recent_timestamps(self, limit):
        return [r["Timestamp"] for r in self.rows[-limit:]][::-1]

    def close(self):
        pass


def _row(i):
    return {"Timestamp": f"2020-02-07 09:{30 + i:02d}:00", "v": float(i)}


@pytest.mark.parametrize("fmt", ["jsonl", "binary"])
def test_journal_spills_and_backfills_in_order(tmp_path, fmt):
    store = _FlakyStore()
    wh = BufferedWarehouse(store, str(tmp_path / "j.jsonl"), fmt=fmt)
    assert wh.insert_rows([_row(0)]) == 1
    store.down = True
    assert wh.insert_rows([_row(1)]) == 1     # spilled, not raised
    assert wh.insert_rows([_row(2)]) == 1
    assert wh.journal_pending == 2
    assert len(store.rows) == 1
    # dedupe-exactness while spilled: the journal speaks for its rows
    assert wh.has_timestamp(_row(1)["Timestamp"])
    assert _row(2)["Timestamp"] in wh.recent_timestamps(10)
    store.down = False
    assert wh.insert_rows([_row(3)]) == 1     # drains THEN lands
    assert [r["Timestamp"] for r in store.rows] == \
        [_row(i)["Timestamp"] for i in range(4)]  # landing order kept
    stats = wh.journal_stats()
    assert stats["pending"] == 0
    assert stats["spilled_rows"] == 2
    assert stats["backfilled_rows"] == 2
    assert stats["drain_failures"] >= 1


@pytest.mark.parametrize("fmt", ["jsonl", "binary"])
def test_journal_is_durable_and_idempotent_across_restart(tmp_path, fmt):
    """A process restart recovers the journal from disk; a row that
    already landed (crash between store commit and journal compaction)
    is deduped via has_timestamp, never double-landed.  Parametrized
    over both record layouts: the packed-column format's crash-replay
    dedupe must stay exactly as exact as JSONL's (ISSUE 12)."""
    path = str(tmp_path / "j.jsonl")
    store = _FlakyStore()
    wh = BufferedWarehouse(store, path, fmt=fmt)
    store.down = True
    wh.insert_rows([_row(1), _row(2)])
    # crash-replay shape: row 1 secretly made it into the store before
    # the journal could compact
    store.rows.append(_row(1))
    store.down = False
    wh2 = BufferedWarehouse(store, path, fmt=fmt)  # "restarted process"
    assert wh2.journal_stats()["recovered_rows"] == 2
    assert wh2.drain_journal() == 1           # row 2 only
    assert [r["Timestamp"] for r in store.rows] == [
        _row(1)["Timestamp"], _row(2)["Timestamp"]]
    assert wh2.journal_stats()["dedupe_skipped"] == 1
    assert wh2.journal_pending == 0
    # the drained journal file is compacted empty: a third incarnation
    # recovers nothing
    assert BufferedWarehouse(store, path).journal_stats()[
        "recovered_rows"] == 0


def test_journal_overflow_sheds_oldest_counted(tmp_path):
    path = str(tmp_path / "j.jsonl")
    store = _FlakyStore()
    store.down = True
    wh = BufferedWarehouse(store, path, bound=2)
    for i in range(4):
        wh.insert_rows([_row(i)])
    stats = wh.journal_stats()
    assert stats["pending"] == 2
    assert stats["shed_rows"] == 2            # oldest two, counted
    store.down = False
    wh.drain_journal()
    assert [r["Timestamp"] for r in store.rows] == [
        _row(2)["Timestamp"], _row(3)["Timestamp"]]


def test_journal_survives_torn_trailing_line(tmp_path):
    path = str(tmp_path / "j.jsonl")
    with open(path, "w") as fh:
        fh.write(json.dumps(_row(0)) + "\n")
        fh.write('{"Timestamp": "2020-')      # torn mid-write
    store = _FlakyStore()
    wh = BufferedWarehouse(store, path)
    stats = wh.journal_stats()
    assert stats["recovered_rows"] == 1
    assert stats["corrupt_lines"] == 1
    wh.drain_journal()
    assert [r["Timestamp"] for r in store.rows] == [_row(0)["Timestamp"]]


def test_journal_binary_torn_trailing_frame_dropped_counted(tmp_path):
    """The binary layout's mid-write-kill shape: a length prefix whose
    payload never finished is dropped and counted, like a torn JSONL
    line — and the rows before it still recover."""
    import struct as _struct

    from fmda_tpu.stream import codec as _codec

    path = str(tmp_path / "j.bin")
    store = _FlakyStore()
    wh = BufferedWarehouse(store, path, fmt="binary")
    store.down = True
    wh.insert_rows([_row(0), _row(1)])
    with open(path, "ab") as fh:              # torn frame: body cut short
        payload = _codec.encode(_codec.pack_rows([_row(2)]))
        fh.write(_struct.pack(">I", len(payload)) + payload[:-5])
    wh2 = BufferedWarehouse(store, path, fmt="binary")
    stats = wh2.journal_stats()
    assert stats["recovered_rows"] == 2
    assert stats["corrupt_lines"] == 1
    store.down = False
    wh2.drain_journal()
    assert [r["Timestamp"] for r in store.rows] == [
        _row(0)["Timestamp"], _row(1)["Timestamp"]]
    # values survived the packed columns bit-exact
    assert [r["v"] for r in store.rows] == [0.0, 1.0]


def test_journal_mixed_format_recovery_after_config_flip(tmp_path):
    """A journal written as JSONL, then appended in binary after a
    journal_format flip (or vice versa), recovers every row: the reader
    auto-detects per record."""
    path = str(tmp_path / "j.mixed")
    store = _FlakyStore()
    store.down = True
    wh = BufferedWarehouse(store, path, fmt="jsonl")
    wh.insert_rows([_row(0)])
    wh.close()
    store2 = _FlakyStore()
    store2.down = True
    wh2 = BufferedWarehouse(store2, path, fmt="binary")
    assert wh2.journal_stats()["recovered_rows"] == 1
    wh2.insert_rows([_row(1)])
    wh2.close()
    store3 = _FlakyStore()
    wh3 = BufferedWarehouse(store3, path, fmt="jsonl")
    assert wh3.journal_stats()["recovered_rows"] == 2
    wh3.drain_journal()
    assert [r["Timestamp"] for r in store3.rows] == [
        _row(0)["Timestamp"], _row(1)["Timestamp"]]


def test_journal_poison_row_is_dropped_not_wedged(tmp_path):
    """A journaled row the store rejects for a data-shaped reason (it
    spilled before the store ever validated it) is dropped counted —
    it must not wedge every future landing into the journal behind it."""
    class PickyStore(_FlakyStore):
        def insert_rows(self, rows):
            if any("poison" in r for r in rows):
                raise TypeError("bad value")
            return super().insert_rows(rows)

    store = PickyStore()
    wh = BufferedWarehouse(store, str(tmp_path / "j.jsonl"))
    store.down = True
    wh.insert_rows([_row(0)])
    wh.insert_rows([{**_row(1), "poison": True}])
    wh.insert_rows([_row(2)])
    store.down = False
    assert wh.drain_journal() == 2            # good rows around it land
    assert wh.journal_pending == 0
    assert wh.journal_stats()["poison_rows"] == 1
    wh.insert_rows([_row(3)])                 # straight-through again
    assert [r["Timestamp"] for r in store.rows] == [
        _row(i)["Timestamp"] for i in (0, 2, 3)]


def test_journal_all_corrupt_file_compacts_on_recovery(tmp_path):
    """A journal containing only torn lines is compacted at recovery:
    the corruption is counted once, not re-counted by every restart."""
    path = str(tmp_path / "j.jsonl")
    with open(path, "w") as fh:
        fh.write('{"torn')
    store = _FlakyStore()
    assert BufferedWarehouse(store, path).journal_stats()[
        "corrupt_lines"] == 1
    assert BufferedWarehouse(store, path).journal_stats()[
        "corrupt_lines"] == 0


def test_journal_programming_errors_stay_loud(tmp_path):
    """Bad row dicts must raise, not retry forever through the journal."""
    fc = _small_features(get_cot=False)
    inner = Warehouse(fc, WarehouseConfig(path=":memory:"))
    wh = BufferedWarehouse(inner, str(tmp_path / "j.jsonl"))
    with pytest.raises(KeyError, match="unknown feature columns"):
        wh.insert_rows([{"Timestamp": "2020-02-07 09:30:00",
                         "no_such_column": 1.0}])
    assert wh.journal_pending == 0


# ---------------------------------------------------------------------------
# plan generation for the data-plane targets
# ---------------------------------------------------------------------------


def test_pipeline_plan_is_seeded_and_disjoint():
    from fmda_tpu.chaos.pipeline import generate_pipeline_plan

    a = generate_pipeline_plan(5, 30)
    assert a == generate_pipeline_plan(5, 30)     # pure function of seed
    assert a != generate_pipeline_plan(6, 30)
    targets = a.targets
    assert "warehouse.append" in targets
    assert "engine.step" in targets
    assert any(t.startswith("feed:") for t in targets)
    spans = sorted((e.step, e.step + e.duration) for e in a.events)
    for (a0, a1), (b0, b1) in zip(spans, spans[1:]):
        assert a1 < b0                            # one-step gap


# ---------------------------------------------------------------------------
# the pipeline soak (fast deterministic shape)
# ---------------------------------------------------------------------------


_FAST_PLAN = FaultPlan(n_steps=18, seed=99, events=(
    FaultEvent(3, "kill", "feed:vix", duration=5),
    FaultEvent(10, "kill", "warehouse.append", duration=3),
    FaultEvent(15, "kill", "engine.step", duration=2),
))


def test_pipeline_soak_fast_gates_hold():
    """The tier-1 soak: feed outage + warehouse outage + engine kill in
    one deterministic 18-round run (no jax, no subprocesses) — every
    never-abort gate must hold, including raw-row bit-identity against
    the unfaulted replay."""
    from fmda_tpu.chaos.pipeline import run_pipeline_soak

    out = run_pipeline_soak(_FAST_PLAN, rounds=18, probe_rounds=2,
                            compare_unfaulted=True)
    assert out["gates_ok"], json.dumps(out, indent=2, default=str)
    assert out["unaccounted"] == 0
    assert out["degraded_rows"].get("vix", 0) > 0
    assert out["journal"]["spilled_rows"] > 0
    assert out["journal"]["pending"] == 0
    assert out["engine_restarts"] == 1
    assert out["identity"]["clean_rows"] > 0


def test_pipeline_soak_replays_identically_from_one_plan():
    """Two runs of one plan produce identical reports (the reproduction
    recipe contract, end to end through the data plane)."""
    from fmda_tpu.chaos.pipeline import run_pipeline_soak

    kw = dict(rounds=18, probe_rounds=2, compare_unfaulted=False)
    a = run_pipeline_soak(_FAST_PLAN, **kw)
    b = run_pipeline_soak(_FAST_PLAN, **kw)
    assert a == b


@pytest.mark.slow
def test_pipeline_soak_calibrated_with_predictor():
    """The calibrated shape: generated plan, jitted Predictor
    attached, unfaulted-reference identity — the full contract."""
    from fmda_tpu.chaos.pipeline import (
        generate_pipeline_plan, run_pipeline_soak)

    plan = generate_pipeline_plan(0, 30)
    out = run_pipeline_soak(plan, rounds=30, predictor=True,
                            compare_unfaulted=True)
    assert out["gates_ok"], json.dumps(out, indent=2, default=str)
    assert out["gates"]["post_chaos_probes_served"]


# ---------------------------------------------------------------------------
# obs wiring: the feed_degraded / warehouse_journal health checks
# ---------------------------------------------------------------------------


def test_feed_degraded_and_journal_health_checks(tmp_path):
    """The Application surfaces both data-plane degradations on
    /healthz: a stale feed flips ``feed_degraded`` (and recovers), a
    journal backlog flips ``warehouse_journal`` until the drain."""
    import dataclasses

    from fmda_tpu.app import Application
    from fmda_tpu.config import FrameworkConfig

    fc = _small_features(get_cot=False)
    cfg = FrameworkConfig(
        features=fc,
        engine=dataclasses.replace(
            FrameworkConfig().engine, staleness_deadline_s=450),
        warehouse=dataclasses.replace(
            FrameworkConfig().warehouse,
            journal_path=str(tmp_path / "j.jsonl")),
    )
    app = Application(cfg, bus=InProcessBus(DEFAULT_TOPICS))
    try:
        assert isinstance(app.warehouse, BufferedWarehouse)
        msgs = _session_messages(6)
        _publish_tick(app.bus, msgs, 0)
        app.engine.step()
        health = app.observability.health()
        assert health["checks"]["feed_degraded"]["ok"]
        assert health["checks"]["warehouse_journal"]["ok"]
        for i in (1, 2):                  # vix dark -> degraded rows
            _publish_tick(app.bus, msgs, i, skip=(TOPIC_VIX,))
            app.engine.step()
        health = app.observability.health()
        assert not health["checks"]["feed_degraded"]["ok"]
        assert health["status"] == "degraded"
        # the registry exports the degraded series
        snap = app.observability.snapshot()
        series = {(s["name"], s["labels"].get("topic"))
                  for s in snap["counters"]}
        assert ("engine_degraded_rows_total", TOPIC_VIX) in series
        # journal backlog flips its check, drain recovers it
        app.warehouse._spill_locked([{"Timestamp": "x"}], "test")
        assert not app.observability.health()[
            "checks"]["warehouse_journal"]["ok"]
        names = {s["name"] for s in app.observability.snapshot()["gauges"]}
        assert "warehouse_journal_pending" in names
        app.warehouse.drain_journal()
        # vix recovers -> feed_degraded clears
        for i in (3, 4, 5):
            _publish_tick(app.bus, msgs, i)
            app.engine.step()
        health = app.observability.health()
        assert health["checks"]["feed_degraded"]["ok"]
        assert health["checks"]["warehouse_journal"]["ok"]
    finally:
        app.close()
