"""Process-wide metrics registry: one vocabulary for the whole pipeline.

The pipeline spans ingest transports, the bus, the streaming engine, the
warehouse, training, and two serving paths — before this module each stage
kept (or skipped) its own ad-hoc counters.  A :class:`MetricsRegistry`
holds every instrument under one namespace:

- :class:`Counter` — monotonic totals (requests, retries, rows landed);
- :class:`Gauge`   — last-observed values (queue depth, pending joins);
- :class:`LatencyHistogram` — fixed log-spaced latency distribution
  (promoted here from ``fmda_tpu.runtime.metrics``, which re-exports it),
  now thread-safe with ``snapshot()``/``merge()`` for cross-thread
  aggregation;
- **collectors** — callables sampled at snapshot time, for state that is
  cheaper to read on scrape than to push on every hot-loop iteration
  (consumer lag, watermark ages, the runtime's whole instrument set).

Export surfaces consume :meth:`MetricsRegistry.snapshot`:
:func:`fmda_tpu.obs.prometheus.render_prometheus` renders the text
exposition, the ``/snapshot`` endpoint and ``python -m fmda_tpu status``
serve/print the JSON form.

Instruments are meant for hot loops (one lock acquisition per update;
a wired replay lands the rows a bare one lands: tests/test_obs.py),
and a registry constructed with ``enabled=False``
hands out shared no-op instruments so a disabled plane costs one
attribute call.
"""

from __future__ import annotations

import math
import threading
from typing import Callable, Dict, List, Optional, Tuple

#: snapshot sample: {"name": str, "labels": {k: v}, ...value fields}
Sample = Dict[str, object]
#: snapshot: {"counters": [Sample], "gauges": [Sample], "histograms": [Sample]}
Snapshot = Dict[str, List[Sample]]

_LabelKey = Tuple[Tuple[str, str], ...]


def _log():
    import logging

    return logging.getLogger("fmda_tpu.obs")


def _label_key(labels: Dict[str, str]) -> _LabelKey:
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


class Counter:
    """Monotonic counter (float deltas allowed — e.g. seconds waited)."""

    __slots__ = ("name", "labels", "_value", "_lock")

    def __init__(self, name: str, labels: Dict[str, str]) -> None:
        self.name = name
        self.labels = dict(labels)
        self._value = 0.0
        self._lock = threading.Lock()

    def inc(self, delta: float = 1.0) -> None:
        with self._lock:
            self._value += delta

    @property
    def value(self) -> float:
        # hot-loop callers poll this between updates and tolerate skew
        # lock-free: GIL-atomic float read
        return self._value

    def sample(self) -> Sample:
        with self._lock:  # scrape reads must not tear against inc()
            return {"name": self.name, "labels": self.labels,
                    "value": self._value}


class Gauge:
    """Last-observed value."""

    __slots__ = ("name", "labels", "_value", "_lock")

    def __init__(self, name: str, labels: Dict[str, str]) -> None:
        self.name = name
        self.labels = dict(labels)
        self._value = 0.0
        self._lock = threading.Lock()

    def set(self, value: float) -> None:
        with self._lock:
            self._value = float(value)

    @property
    def value(self) -> float:
        # lock-free: GIL-atomic float read (see Counter.value)
        return self._value

    def sample(self) -> Sample:
        with self._lock:  # scrape reads must not tear against set()
            return {"name": self.name, "labels": self.labels,
                    "value": self._value}


class LatencyHistogram:
    """Fixed log-spaced latency histogram (1 µs .. ~100 s).

    O(1) observe, percentile estimates from bin edges — accurate to one
    bin width (10 bins/decade), which is plenty for p50/p99 serving
    dashboards and costs no per-observation allocation.  Thread-safe:
    one lock around observe/read, plus :meth:`snapshot`/:meth:`merge`
    so per-thread instances can be aggregated without sharing the lock
    on the hot path.
    """

    #: 10 bins per decade over 8 decades starting at 1 µs.
    BINS_PER_DECADE = 10
    N_BINS = 8 * BINS_PER_DECADE
    _LO_EXP = -6  # 1e-6 s

    def __init__(
        self, name: str = "", labels: Optional[Dict[str, str]] = None
    ) -> None:
        self.name = name
        self.labels = dict(labels or {})
        self.counts = [0] * self.N_BINS
        self.n = 0
        self.total_s = 0.0
        self.max_s = 0.0
        self._lock = threading.Lock()

    def _bin(self, seconds: float) -> int:
        if seconds <= 1e-6:
            return 0
        b = int((math.log10(seconds) - self._LO_EXP) * self.BINS_PER_DECADE)
        return min(max(b, 0), self.N_BINS - 1)

    @classmethod
    def bin_upper_edge(cls, b: int) -> float:
        """Upper edge (seconds) of bin ``b`` — the ``le`` bound exemplar
        export keys on (fmda_tpu.obs.trace sample-linked exemplars)."""
        return 10.0 ** (cls._LO_EXP + (b + 1) / cls.BINS_PER_DECADE)

    def observe(self, seconds: float) -> None:
        b = self._bin(seconds)
        with self._lock:
            self.counts[b] += 1
            self.n += 1
            self.total_s += seconds
            if seconds > self.max_s:
                self.max_s = seconds

    def percentile(self, p: float) -> float:
        """Upper edge of the bin holding the p-th percentile (seconds),
        clamped to the true observed max (the top bin's edge can
        otherwise overshoot it)."""
        with self._lock:
            return self._percentile_locked(p)

    def _percentile_locked(self, p: float) -> float:
        if self.n == 0:
            return 0.0
        target = p / 100.0 * self.n
        seen = 0
        for i, c in enumerate(self.counts):
            seen += c
            if seen >= target:
                edge = 10.0 ** (
                    self._LO_EXP + (i + 1) / self.BINS_PER_DECADE)
                return min(edge, self.max_s)
        return self.max_s

    def summary(self) -> Dict[str, float]:
        with self._lock:
            return {
                "count": self.n,
                "mean_ms": (
                    round(self.total_s / self.n * 1e3, 4) if self.n else 0.0
                ),
                "p50_ms": round(self._percentile_locked(50) * 1e3, 4),
                "p99_ms": round(self._percentile_locked(99) * 1e3, 4),
                "max_ms": round(self.max_s * 1e3, 4),
            }

    # -- cross-thread aggregation -------------------------------------------

    def snapshot(self) -> Dict[str, object]:
        """Consistent copy of the raw state (bin counts + moments) — the
        mergeable form.  Taken under the lock, so a snapshot mid-observe
        never tears (count present in ``counts`` but missing from ``n``)."""
        with self._lock:
            return {
                "counts": list(self.counts),
                "n": self.n,
                "total_s": self.total_s,
                "max_s": self.max_s,
            }

    def merge(self, other) -> "LatencyHistogram":
        """Fold another histogram (or a :meth:`snapshot` dict) into this
        one.  Exact — bin layouts are identical by construction — so N
        per-thread histograms merge into one distribution with no loss
        beyond the shared bin resolution."""
        snap = other.snapshot() if isinstance(other, LatencyHistogram) else other
        if len(snap["counts"]) != self.N_BINS:
            raise ValueError(
                f"cannot merge: {len(snap['counts'])} bins != {self.N_BINS}")
        with self._lock:
            self.counts = [
                a + b for a, b in zip(self.counts, snap["counts"])
            ]
            self.n += snap["n"]
            self.total_s += snap["total_s"]
            self.max_s = max(self.max_s, snap["max_s"])
        return self

    def sample(self) -> Sample:
        with self._lock:
            return {
                "name": self.name,
                "labels": self.labels,
                "count": self.n,
                "sum_s": self.total_s,
                "max_s": self.max_s,
                "p50_s": self._percentile_locked(50),
                "p99_s": self._percentile_locked(99),
                # raw bin counts ride the sample so a scraped /snapshot
                # stays MERGEABLE: the fleet aggregator diffs cumulative
                # snapshots into window distributions and folds them
                # across workers (fmda_tpu.obs.tsdb/aggregate) — the
                # summary quantiles above cannot be merged after the fact
                "counts": list(self.counts),
            }


class _NullInstrument:
    """Shared no-op stand-in handed out by a disabled registry: every
    update is one attribute lookup + a pass, every read is zero."""

    __slots__ = ()
    name = ""
    labels: Dict[str, str] = {}
    value = 0.0
    n = 0

    def inc(self, delta: float = 1.0) -> None:
        pass

    def set(self, value: float) -> None:
        pass

    def observe(self, seconds: float) -> None:
        pass

    def percentile(self, p: float) -> float:
        return 0.0

    def summary(self) -> Dict[str, float]:
        return {}

    def snapshot(self) -> Dict[str, object]:
        return {"counts": [], "n": 0, "total_s": 0.0, "max_s": 0.0}

    def merge(self, other) -> "_NullInstrument":
        return self


_NULL = _NullInstrument()


class MetricsRegistry:
    """Get-or-create instrument store + snapshot-time collectors.

    ``counter``/``gauge``/``histogram`` return the same instrument for
    the same ``(name, labels)`` — callers cache the handle at
    construction and update it lock-cheap on the hot path.  Collectors
    are sampled only inside :meth:`snapshot` (scrape time), the right
    home for state that is derived rather than accumulated.  A registry
    can :meth:`include` other registries, so a per-Application registry
    folds in the process-default one (where module-level instrumentation
    such as the ingest transports lands).
    """

    def __init__(self, *, enabled: bool = True) -> None:
        self.enabled = enabled
        self._lock = threading.Lock()
        self._counters: Dict[Tuple[str, _LabelKey], Counter] = {}
        self._gauges: Dict[Tuple[str, _LabelKey], Gauge] = {}
        self._histograms: Dict[Tuple[str, _LabelKey], LatencyHistogram] = {}
        self._collectors: List[Tuple[str, Callable[[], Snapshot]]] = []
        self._included: List["MetricsRegistry"] = []
        self._process: Optional[str] = None

    def set_process(self, name: Optional[str]) -> None:
        """Stamp every exported sample with a ``process`` label (worker
        id, role) — a multi-process fleet scraped into one Prometheus
        must not collide series names across its workers.  Applied at
        snapshot time over instruments, collectors, AND included
        registries, so the whole process's export is labelled."""
        self._process = name

    # -- instruments ---------------------------------------------------------

    def counter(self, name: str, **labels: str) -> Counter:
        if not self.enabled:
            return _NULL  # type: ignore[return-value]
        key = (name, _label_key(labels))
        with self._lock:
            inst = self._counters.get(key)
            if inst is None:
                inst = self._counters[key] = Counter(name, labels)
        return inst

    def gauge(self, name: str, **labels: str) -> Gauge:
        if not self.enabled:
            return _NULL  # type: ignore[return-value]
        key = (name, _label_key(labels))
        with self._lock:
            inst = self._gauges.get(key)
            if inst is None:
                inst = self._gauges[key] = Gauge(name, labels)
        return inst

    def histogram(self, name: str, **labels: str) -> LatencyHistogram:
        if not self.enabled:
            return _NULL  # type: ignore[return-value]
        key = (name, _label_key(labels))
        with self._lock:
            inst = self._histograms.get(key)
            if inst is None:
                inst = self._histograms[key] = LatencyHistogram(name, labels)
        return inst

    # -- composition ---------------------------------------------------------

    def register_collector(
        self, name: str, fn: Callable[[], Snapshot]
    ) -> None:
        """Register a snapshot-time sampler.  ``fn`` returns a (possibly
        partial) snapshot dict merged into :meth:`snapshot` output.  A
        second registration under the same name replaces the first (an
        Application re-attaching a fleet must not double-report)."""
        if not self.enabled:
            return
        with self._lock:
            self._collectors = [
                (n, f) for n, f in self._collectors if n != name
            ]
            self._collectors.append((name, fn))

    def include(self, other: "MetricsRegistry") -> None:
        """Fold another registry's snapshot into this one's (no copy —
        sampled live at snapshot time)."""
        if not self.enabled or other is self:
            return
        with self._lock:
            if other not in self._included:
                self._included.append(other)

    # -- export --------------------------------------------------------------

    def snapshot(self) -> Snapshot:
        """One consistent-enough view of every instrument + collector.
        ("Enough": each instrument is internally consistent under its own
        lock; cross-instrument skew is inherent to any scrape.)"""
        out: Snapshot = {"counters": [], "gauges": [], "histograms": []}
        if not self.enabled:
            return out
        with self._lock:
            counters = list(self._counters.values())
            gauges = list(self._gauges.values())
            histograms = list(self._histograms.values())
            collectors = list(self._collectors)
            included = list(self._included)
        out["counters"] = [c.sample() for c in counters]
        out["gauges"] = [g.sample() for g in gauges]
        out["histograms"] = [h.sample() for h in histograms]
        for name, fn in collectors:
            try:
                part = fn()
            except Exception:  # noqa: BLE001 — loss-free: one dead
                # component (e.g. a closed warehouse) must not take the
                # whole scrape down; /healthz reports its failure
                _log().warning(
                    "metrics collector %r failed; skipped", name,
                    exc_info=True)
                continue
            for kind in out:
                out[kind].extend(part.get(kind, ()))
        for reg in included:
            part = reg.snapshot()
            for kind in out:
                out[kind].extend(part.get(kind, ()))
        if self._process is not None:
            # rebind, never mutate: instrument samples share the
            # instrument's own labels dict
            for kind in out:
                for s in out[kind]:
                    labels = s.get("labels") or {}
                    if "process" not in labels:
                        s["labels"] = {**labels, "process": self._process}
        return out


#: The process-default registry.  Module-level instrumentation (ingest
#: transports, the trainer) that has no Application handle to receive a
#: registry from reports here; ``Application`` includes it, so one
#: scrape sees the whole process.
_DEFAULT = MetricsRegistry()


def default_registry() -> MetricsRegistry:
    return _DEFAULT
