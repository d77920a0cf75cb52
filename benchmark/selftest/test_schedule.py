"""The schedule and the rows are a pure function of traffic, seed and
seconds, and have the shape the traffic file states."""

import numpy as np
import pytest

from benchmark.harness import schedule as sched

TRAFFIC = {"kind": "open_loop", "sessions": 512, "rate_ticks_per_s": 4000,
           "skew_exponent": 0.8, "hot_session_cap_ticks_per_s": 100,
           "burst_every_s": 2.0, "burst_first_s": 1.0, "burst_reserve_s": 1.0,
           "burst_sessions_fraction": 1.0}


def test_same_seed_same_schedule_and_rows():
    a = sched.make_schedule(TRAFFIC, 7, 9.0)
    b = sched.make_schedule(TRAFFIC, 7, 9.0)
    assert np.array_equal(a.due, b.due)
    assert np.array_equal(a.session, b.session)
    s = sched.make_sessions(512, 5, 7)
    ra = sched.walk_rows(s, a.session, 7, stream=1)
    rb = sched.walk_rows(sched.make_sessions(512, 5, 7), b.session, 7, 1)
    assert np.array_equal(ra, rb)


def test_other_seed_or_seconds_changes_it():
    a = sched.make_schedule(TRAFFIC, 7, 9.0)
    assert not np.array_equal(a.due, sched.make_schedule(TRAFFIC, 8, 9.0).due)
    assert len(sched.make_schedule(TRAFFIC, 7, 11.0)) != len(a)


@pytest.mark.parametrize("seconds,expected", [
    (9.0, [1, 3, 5, 7]), (13.0, [1, 3, 5, 7, 9, 11]), (2.0, [1]),
    (1.5, [])])
def test_bursts_are_placed_from_seconds(seconds, expected):
    assert sched.burst_times(TRAFFIC, seconds).tolist() == expected


def test_amount_of_work_is_fixed_and_sorted():
    for seed in (1, 2, 3):
        p = sched.make_schedule(TRAFFIC, seed, 9.0)
        assert len(p) == 36000  # rate x seconds, bursts included
        assert np.all(np.diff(p.due) >= 0)
        assert p.due[0] >= 0 and p.due[-1] < 9.0
        assert p.burst.sum() == 4 * 512
        for bt in p.burst_times:
            members = p.session[p.burst & (p.due == bt)]
            assert sorted(members.tolist()) == list(range(512))


def test_hot_session_is_capped_and_weights_sum_to_one():
    w = sched.session_weights(4096, 0.8, 100 / 14000)
    assert abs(w.sum() - 1.0) < 1e-12
    assert w.max() <= 100 / 14000 + 1e-12
    assert np.all(np.diff(w) <= 1e-15)  # still ordered by rank
    p = sched.make_schedule(dict(TRAFFIC, sessions=4096,
                                 rate_ticks_per_s=16000), 3, 13.0)
    steady = np.bincount(p.session[~p.burst], minlength=4096) / 13.0
    assert steady.max() < 100 * 1.15  # the cap, within sampling noise


def test_rows_walk_per_session():
    s = sched.make_sessions(4, 3, 1)
    session = np.array([2, 0, 2, 2, 1, 0], np.int32)
    rows = sched.walk_rows(s, session, 1, stream=1)
    rng = np.random.default_rng([1, 3, 1])
    steps = rng.normal(scale=sched.WALK_STEP_SCALE, size=(6, 3))
    walk = s.walk0.astype(np.float64).copy()
    for k, i in enumerate(session):
        walk[i] += steps[k]
        assert np.allclose(rows[k], walk[i], atol=1e-6)


def test_tick_index_finds_ticks_by_session_and_seq():
    session = np.array([2, 0, 2, 2, 1, 0], np.int32)
    idx = sched.index_ticks(session, 4, np.array([5, 0, 1, 0]))
    assert idx.tick_of(2, 1) == 0 and idx.tick_of(2, 3) == 3
    assert idx.tick_of(0, 5) == 1 and idx.tick_of(0, 6) == 5
    assert idx.tick_of(1, 0) == 4
    assert idx.tick_of(0, 7) == -1 and idx.tick_of(3, 0) == -1
