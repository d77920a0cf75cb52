"""fmda_tpu.chaos — deterministic fault injection for the serving stack.

A seeded :class:`~fmda_tpu.chaos.plan.FaultPlan` schedules
kill/partition/delay/hang/corrupt events on a virtual step clock; the
process-default :class:`~fmda_tpu.chaos.inject.ChaosRuntime` applies
them at named injection points compiled into the fleet transport and
serving loops (one guarded branch when disabled — the tracer's
discipline), :mod:`~fmda_tpu.chaos.wrap` wraps a bus or warehouse
opt-in, and :mod:`~fmda_tpu.chaos.soak` drives the whole local
multi-host topology under a plan, hard-gating the "counted degradation,
never abort" contract end to end (tests/test_chaos.py and
``serve-fleet --role local --chaos-plan``).

Everything except the soak's worker subprocesses is router-role code:
no jax on this import path.  Architecture: docs/chaos.md.
"""

from fmda_tpu.chaos.inject import (
    ChaosFault,
    ChaosRuntime,
    chaos_families,
    configure_chaos,
    default_chaos,
)
from fmda_tpu.chaos.plan import (
    FAULT_KINDS,
    FaultEvent,
    FaultPlan,
    plan_from_config,
)
from fmda_tpu.chaos.wrap import ChaosBus, ChaosWarehouse

__all__ = [
    "FAULT_KINDS",
    "ChaosBus",
    "ChaosFault",
    "ChaosRuntime",
    "ChaosWarehouse",
    "FaultEvent",
    "FaultPlan",
    "chaos_families",
    "configure_chaos",
    "default_chaos",
    "generate_pipeline_plan",
    "plan_from_config",
    "run_chaos_soak",
    "run_pipeline_soak",
]


def __getattr__(name):  # PEP 562 — the soaks pull heavy deps lazily
    if name == "run_chaos_soak":
        from fmda_tpu.chaos.soak import run_chaos_soak

        return run_chaos_soak
    if name in ("run_pipeline_soak", "generate_pipeline_plan"):
        from fmda_tpu.chaos import pipeline

        return getattr(pipeline, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
