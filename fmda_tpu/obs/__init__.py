"""fmda_tpu.obs — the unified observability plane.

One metrics vocabulary and one export surface for the whole pipeline
(ROADMAP: the latency-SLO gate needs per-stage telemetry an operator can
scrape).  The pieces:

- :mod:`~fmda_tpu.obs.registry`     — :class:`MetricsRegistry` (counters,
  gauges, :class:`LatencyHistogram` with ``snapshot()``/``merge()``),
  scrape-time collectors, a process-default registry for module-level
  instrumentation;
- :mod:`~fmda_tpu.obs.prometheus`   — text-exposition renderer;
- :mod:`~fmda_tpu.obs.events`       — bounded JSONL event ring;
- :mod:`~fmda_tpu.obs.server`       — stdlib HTTP thread serving
  ``/metrics``, ``/healthz``, ``/snapshot``, ``/events``, ``/trace``;
- :mod:`~fmda_tpu.obs.trace`        — end-to-end tick tracing
  (:class:`Tracer`, in-band bus trace context, Perfetto export);
- :mod:`~fmda_tpu.obs.device`       — device/compiler telemetry: the
  :func:`tracked_jit` compile ledger (per-program compiles, FLOPs,
  unexpected-recompile detection), MFU/roofline gauges, and the
  :class:`DeviceMemoryMonitor` watermark/leak sampler;
- :mod:`~fmda_tpu.obs.pyprof`       — continuous host sampling profiler
  (folded stacks at ``/profile``, flight-recorder bundles);
- :mod:`~fmda_tpu.obs.observability` — the :class:`Observability` handle
  an :class:`~fmda_tpu.app.Application` owns (collectors + health checks
  + endpoint lifecycle).

Architecture and metric vocabulary: docs/observability.md.
"""

from fmda_tpu.obs.aggregate import FleetAggregator, FleetTelemetry
from fmda_tpu.obs.device import (
    CompileLedger,
    DeviceMemoryMonitor,
    TrackedFunction,
    default_ledger,
    default_memory_monitor,
    device_report,
    tracked_jit,
)
from fmda_tpu.obs.events import EventLog, default_epoch_log
from fmda_tpu.obs.observability import (
    Observability,
    engine_families,
    journal_families,
    runtime_families,
    stage_timer_families,
)
from fmda_tpu.obs.prometheus import render_prometheus
from fmda_tpu.obs.pyprof import HostProfiler, default_profiler
from fmda_tpu.obs.registry import (
    Counter,
    Gauge,
    LatencyHistogram,
    MetricsRegistry,
    default_registry,
)
from fmda_tpu.obs.recorder import FlightRecorder
from fmda_tpu.obs.server import MetricsServer
from fmda_tpu.obs.slo import SLOEngine
from fmda_tpu.obs.trace import (
    Span,
    TraceRef,
    Tracer,
    configure_tracing,
    default_tracer,
    tracer_families,
)
from fmda_tpu.obs.tsdb import TimeSeriesStore

__all__ = [
    "CompileLedger",
    "Counter",
    "DeviceMemoryMonitor",
    "EventLog",
    "FleetAggregator",
    "FleetTelemetry",
    "FlightRecorder",
    "Gauge",
    "HostProfiler",
    "LatencyHistogram",
    "MetricsRegistry",
    "MetricsServer",
    "Observability",
    "SLOEngine",
    "Span",
    "TimeSeriesStore",
    "TraceRef",
    "TrackedFunction",
    "Tracer",
    "configure_tracing",
    "default_epoch_log",
    "default_ledger",
    "default_memory_monitor",
    "default_profiler",
    "default_registry",
    "default_tracer",
    "device_report",
    "engine_families",
    "journal_families",
    "render_prometheus",
    "runtime_families",
    "stage_timer_families",
    "tracer_families",
]
