"""The one general traffic generator for serving cells.

Everything a serving run sends is made here, in set-up, from the traffic
file's parameters, ``--seed`` and ``--seconds``; the measured window only
looks it up.  The same seed and seconds give the same schedule and the
same rows, bit for bit, whatever model is served — two cells that share
a traffic file see identical traffic.

Rows follow ``fmda_tpu/runtime/loadgen.py`` (copied; the original is
listed in PERF.md for a later PR to delete): every session has its own
min-max normalisation stats (min ~ N(0,1), range ~ U(1,5)) and its own
random walk over the feature vector (start ~ N(0,1), step ~ N(0, 0.1)).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List

import numpy as np

WALK_STEP_SCALE = 0.1


@dataclass
class Sessions:
    ids: List[str]
    mins: np.ndarray  # (S, F) float32
    maxs: np.ndarray  # (S, F) float32
    walk0: np.ndarray  # (S, F) float32 — each walk's starting point


def make_sessions(n_sessions: int, n_features: int, seed: int) -> Sessions:
    rng = np.random.default_rng([seed, 1])
    mins = rng.normal(0.0, 1.0, (n_sessions, n_features)).astype(np.float32)
    maxs = mins + rng.uniform(
        1.0, 5.0, (n_sessions, n_features)).astype(np.float32)
    walk0 = rng.normal(size=(n_sessions, n_features)).astype(np.float32)
    return Sessions([f"T{i:05d}" for i in range(n_sessions)],
                    mins, maxs, walk0)


def session_weights(n: int, exponent: float, cap_share: float) -> np.ndarray:
    """P(session) proportional to rank^-exponent, no session above
    ``cap_share`` of the arrivals; what the cap takes is spread over the
    rest in proportion (repeat until nothing is over)."""
    w = np.arange(1, n + 1, dtype=np.float64) ** (-exponent)
    w /= w.sum()
    if cap_share * n < 1.0:
        raise ValueError("cap_share too small: the weights cannot sum to 1")
    capped = np.zeros(n, bool)
    while True:
        over = (w > cap_share + 1e-15) & ~capped
        if not over.any():
            return w
        capped |= over
        w[capped] = cap_share
        free = ~capped
        w[free] *= (1.0 - cap_share * capped.sum()) / w[free].sum()


@dataclass
class Schedule:
    due: np.ndarray  # (N,) float64 seconds from the window's start, sorted
    session: np.ndarray  # (N,) int32
    burst: np.ndarray  # (N,) bool — part of a synchronised burst
    burst_times: np.ndarray  # (K,) float64
    steady_rate: float

    def __len__(self) -> int:
        return len(self.due)


def burst_times(traffic: Dict, seconds: float) -> np.ndarray:
    every = float(traffic.get("burst_every_s", 0.0))
    if every <= 0:
        return np.zeros(0)
    first = float(traffic.get("burst_first_s", every / 2))
    # the last burst is early enough to drain inside the window
    last = seconds - float(traffic.get("burst_reserve_s", 1.0))
    return np.arange(first, last + 1e-9, every)


def make_schedule(traffic: Dict, seed: int, seconds: float) -> Schedule:
    """Open-loop arrivals for ``seconds``: a Poisson process conditioned
    on its count (so every seed offers exactly the same amount of work)
    with skewed session choice, plus the synchronised bursts.  The
    file's ``rate_ticks_per_s`` is steady arrivals and bursts together.
    """
    n_sessions = int(traffic["sessions"])
    rate = float(traffic["rate_ticks_per_s"])
    bursts = burst_times(traffic, seconds)
    burst_n = int(round(n_sessions * float(
        traffic.get("burst_sessions_fraction", 1.0))))
    n_total = int(round(rate * seconds))
    n_steady = n_total - len(bursts) * burst_n
    if n_steady <= 0:
        raise ValueError(
            f"rate_ticks_per_s={rate} leaves no steady arrivals beside "
            f"{len(bursts)} bursts of {burst_n}")
    steady_rate = n_steady / seconds
    rng = np.random.default_rng([seed, 2])
    due_s = np.sort(rng.uniform(0.0, seconds, n_steady))
    cap = float(traffic.get("hot_session_cap_ticks_per_s", np.inf))
    w = session_weights(n_sessions, float(traffic.get("skew_exponent", 0.0)),
                        min(1.0, cap / steady_rate))
    # rank r is session perm[r]: which ticker is hot depends on the seed
    perm = rng.permutation(n_sessions)
    sess_s = perm[rng.choice(n_sessions, size=n_steady, p=w)]
    due_b = np.repeat(bursts, burst_n)
    sess_b = np.concatenate(
        [rng.permutation(n_sessions)[:burst_n] for _ in bursts]
    ) if len(bursts) else np.zeros(0, np.int64)
    due = np.concatenate([due_s, due_b])
    session = np.concatenate([sess_s, sess_b]).astype(np.int32)
    burst = np.concatenate([np.zeros(n_steady, bool),
                            np.ones(len(due_b), bool)])
    order = np.argsort(due, kind="stable")
    return Schedule(due[order], session[order], burst[order], bursts,
                    steady_rate)


def walk_rows(sessions: Sessions, session_of_tick: np.ndarray, seed: int,
              stream: int) -> np.ndarray:
    """One row per tick, in tick order: each session's random walk
    advanced once per tick *of that session*.  ``stream`` separates the
    warm-up's rows from the window's."""
    n = len(session_of_tick)
    feats = sessions.walk0.shape[1]
    rng = np.random.default_rng([seed, 3, stream])
    steps = rng.normal(scale=WALK_STEP_SCALE, size=(n, feats))
    if n == 0:
        return np.zeros((0, feats), np.float32)
    order = np.argsort(session_of_tick, kind="stable")
    sorted_sess = session_of_tick[order]
    csum = np.cumsum(steps[order], axis=0)
    new_group = np.r_[True, sorted_sess[1:] != sorted_sess[:-1]]
    first = np.flatnonzero(new_group)
    group = np.cumsum(new_group) - 1
    # the running sum just before each session's first tick
    before = np.vstack([np.zeros((1, feats)), csum[first[1:] - 1]])
    rows_sorted = sessions.walk0[sorted_sess] + (csum - before[group])
    rows = np.empty((n, feats), np.float32)
    rows[order] = rows_sorted.astype(np.float32)
    return rows


class TickIndex:
    """Finds a tick from the (session, seq) of its result.  Plain lists:
    the lookup runs once per result on the serving thread."""

    def __init__(self, session_of_tick: np.ndarray, n_sessions: int,
                 seq0: np.ndarray) -> None:
        order = np.argsort(session_of_tick, kind="stable")
        counts = np.bincount(session_of_tick, minlength=n_sessions)
        self._order_a = order  # tick indices sorted by (session, send order)
        self._order = order.tolist()
        self._start = np.r_[0, np.cumsum(counts)].tolist()
        #: each session's seq at the window's first tick
        self._seq0 = np.asarray(seq0, np.int64).tolist()

    def tick_of(self, session: int, seq: int) -> int:
        """The tick's index, or -1 for a (session, seq) never sent."""
        start = self._start
        k = start[session] + seq - self._seq0[session]
        if not start[session] <= k < start[session + 1]:
            return -1
        return self._order[k]

    def ticks_of_session(self, session: int):
        """(tick indices in send order, their seqs) of one session."""
        ticks = self._order_a[self._start[session]:self._start[session + 1]]
        return ticks, self._seq0[session] + np.arange(len(ticks))


def index_ticks(session_of_tick: np.ndarray, n_sessions: int,
                seq0: np.ndarray) -> TickIndex:
    return TickIndex(session_of_tick, n_sessions, seq0)
