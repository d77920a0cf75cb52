"""The serving tolerance is two-level: tight over a session's first
carried ticks, looser for long carries, so that one flat bound does not
let a drop in precision through where the state is young."""

import types

import numpy as np
import pytest

from benchmark.harness import schedule as sched, serving
from benchmark.reference import serving as reference


def check(err_at, monkeypatch, n_ticks=100):
    """A rig whose served probabilities differ from the reference's by
    ``err_at[j]`` at the session's j-th tick (0 elsewhere)."""
    monkeypatch.setitem(
        reference.BY_CELL, "fake",
        lambda params, rows, mn, mx, window: np.zeros((len(rows), 4)))
    rig = object.__new__(serving.Rig)
    rig.model_cfg = types.SimpleNamespace(cell="fake")
    rig.cfg = types.SimpleNamespace(
        runtime=types.SimpleNamespace(window=30))
    rig.params = None
    rig.sessions = sched.make_sessions(1, 3, 1)
    rig.warm_rows = {0: [np.zeros(3, np.float32)] * 2}  # seqs 0 and 1
    rows = np.zeros((n_ticks - 2, 3), np.float32)
    served = {0: {j: np.full(4, err_at.get(j, 0.0)) for j in range(n_ticks)}}
    return rig.check_against_reference(
        {0: rows}, {0: list(range(2, n_ticks))}, served)


@pytest.mark.parametrize("err_at,ok", [
    ({}, True),
    ({5: 1.9e-3, 60: 7.9e-3}, True),
    ({5: 3e-3}, False),        # within the long-carry bound, state young
    ({29: 3e-3}, False),
    ({30: 3e-3}, True),        # the same error after 30 carried ticks
    ({60: 9e-3}, False)])
def test_tolerance_depends_on_carried_ticks(err_at, ok, monkeypatch):
    out = check(err_at, monkeypatch)
    assert out["ok"] is ok
    assert out["compared"] == 100 and out["compared_short_carry"] == 30
    assert out["max_abs_err"] == pytest.approx(max(err_at.values(), default=0))


def test_nothing_compared_is_not_ok(monkeypatch):
    monkeypatch.setitem(
        reference.BY_CELL, "fake",
        lambda params, rows, mn, mx, window: np.zeros((len(rows), 4)))
    rig = object.__new__(serving.Rig)
    rig.model_cfg = types.SimpleNamespace(cell="fake")
    rig.cfg = types.SimpleNamespace(runtime=types.SimpleNamespace(window=30))
    rig.params, rig.warm_rows = None, {}
    rig.sessions = sched.make_sessions(1, 3, 1)
    assert rig.check_against_reference({}, {}, {})["ok"] is False
