"""fmda_tpu.control — the adaptive control plane beside the router.

Three closed loops read the telemetry plane (``FleetTelemetry``'s
windowed exact p99s and SLO burn rates) and act on the serving fleet:

- :class:`~fmda_tpu.control.controller.BatchingController` steers the
  gateway's linger/bucket knobs toward the ``[slo]`` p99 objective
  (hysteresis deadband + bounded steps; retunes broadcast through the
  router's inbox protocol);
- :class:`~fmda_tpu.control.qos.QosPolicy` makes admission weighted:
  sessions carry a tenant class, and under overload the gateway sheds
  by WFQ fair share with per-class quotas (counted ``quota_shed``)
  instead of global oldest-drop;
- :class:`~fmda_tpu.control.autoscale.Autoscaler` grows the fleet on
  sustained burn and shrinks it on idle through the zero-loss live
  migration (``FleetRouter.request_leave``).

:class:`~fmda_tpu.control.plane.ControlPlane` composes them on one
cadence with a decision ring (``/control``, ``python -m fmda_tpu
status``); :mod:`~fmda_tpu.control.capacity` sweeps sessions × arrival
rate into the capacity-model artifact, and
:mod:`~fmda_tpu.control.elastic` gates a market-open spike through the
autoscaler under the chaos soak's never-abort contract.

Router-role code throughout: numpy + stdlib, no jax on this import
path (the lint gate pins it).  Architecture: docs/control.md.
"""

from fmda_tpu.control.autoscale import Autoscaler, LocalFleetActuator
from fmda_tpu.control.controller import BatchingController
from fmda_tpu.control.plane import ControlPlane
from fmda_tpu.control.qos import QosPolicy

__all__ = [
    "Autoscaler",
    "BatchingController",
    "CAPACITY_SCHEMA",
    "ControlPlane",
    "LocalFleetActuator",
    "QosPolicy",
    "run_capacity_model",
    "run_elastic_soak",
]


def __getattr__(name):  # PEP 562 — the soak entry points load lazily
    if name == "run_elastic_soak":
        from fmda_tpu.control.elastic import run_elastic_soak

        return run_elastic_soak
    if name in ("run_capacity_model", "CAPACITY_SCHEMA"):
        from fmda_tpu.control import capacity

        return getattr(capacity, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
