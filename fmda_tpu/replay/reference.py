"""The cadence-paced live reference loop — replay's A/B baseline.

Serves the *same* history source through the *same* gateway surface as
:class:`~fmda_tpu.replay.driver.ReplayDriver`, but the way a live feed
would: each round arrives on a wall-clock cadence, rows are submitted
per-tick (no backfill coalescing), and flushes ride the batcher's own
ready/linger logic.  Replay deletes exactly that cadence (how much
faster it is on the chip: not measured, no cell replays), and the
identity tests (tests/test_replay.py) compare their published probabilities
byte for byte (lockstep ``duty=1.0`` sources force identical flush
composition, so float32 reduction order matches and equality is exact).

This module is the one place in ``fmda_tpu.replay`` allowed to touch
the host clock ON PURPOSE: pacing a live simulation is its job.  Every
site carries the ``virtual-clock`` lint hatch saying so.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

import numpy as np

from fmda_tpu.replay.driver import open_replay_sessions


def run_live_reference(
    gateway,
    source,
    *,
    cadence_s: float = 0.0,
    tenant_classes: tuple = (),
    tenant_weights: tuple = (),
    seed: int = 0,
    collect: bool = False,
) -> Dict:
    """Serve ``source`` live-style: one round per ``cadence_s`` of wall
    time (0 = as fast as per-tick submission goes — still slower than
    replay's coalesced blocks), forced flush per round so composition
    matches replay's round-per-flush and bit-identity holds.  Returns
    the run summary; with ``collect`` the per-tick results ride on the
    ``"results"`` key."""
    session_ids = open_replay_sessions(
        gateway, source, tenant_classes=tenant_classes,
        tenant_weights=tenant_weights, seed=seed)
    pool = getattr(gateway, "pool", None)
    results: List = []

    def keep(batch) -> int:
        if collect and batch:
            results.extend(batch)
        return len(batch)

    submitted = 0
    served = 0
    rounds = 0
    # lint: ignore[virtual-clock] live reference loop — wall-clock pacing IS the baseline being measured
    t0 = time.perf_counter()
    next_due = t0
    for batch in source:
        if cadence_s > 0.0:
            # lint: ignore[virtual-clock] live reference loop — paces rounds at the live cadence
            now = time.perf_counter()
            if now < next_due:
                # lint: ignore[virtual-clock] live reference loop — sleeps to the cadence, like a live feed
                time.sleep(next_due - now)
            next_due = max(next_due + cadence_s, now)
        for k, ti in enumerate(batch.tickers):
            sid = session_ids[int(ti)]
            while gateway.saturated:
                drained = gateway.pump(force=True)
                served += keep(drained)
                if not drained and gateway.saturated:
                    # lint: ignore[virtual-clock] live reference loop — GIL yield under backpressure
                    time.sleep(0.002)
            gateway.submit(sid, batch.rows[k])
            submitted += 1
        served += keep(gateway.pump(force=True))
        rounds += 1
    served += keep(gateway.drain())
    # lint: ignore[virtual-clock] telemetry read for the throughput summary
    wall_s = time.perf_counter() - t0

    summary = gateway.metrics.summary()
    out: Dict = {
        "sessions": len(session_ids),
        "rounds": rounds,
        "ticks_submitted": submitted,
        "ticks_served": served,
        "cadence_s": cadence_s,
        "wall_s": round(wall_s, 3),
        "ticks_per_s": round(served / wall_s, 1) if wall_s > 0 else None,
        "compile_count": pool.compile_count if pool is not None else None,
        **summary,
    }
    if collect:
        out["results"] = results
    return out
