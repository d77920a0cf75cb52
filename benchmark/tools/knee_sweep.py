"""Find the knee of an open cell: the highest fixed rate it sustains.

    python benchmark/tools/knee_sweep.py --workload gru_serve_open \\
        --start 10000 --steps 8 [--seconds 8] [--seed 1]

Builds the cell's serving stack once, then offers the cell's own traffic
at a ladder of rates, each 10 % above the last, for ``--seconds`` each,
and prints one line a rate: sent / answered / shed, the queue depth
between bursts at the end of the window against the middle, how late the
generator ran outside bursts, and p50 / p99 from due time.  The knee is
the highest rate at which nothing is shed or left unanswered, every burst
is drained before the next one is due, and the median tick is served
outside the drains (``sustained`` below; the last line printed names the
knee).  The traffic file then takes
0.8 x the lower knee of the cells that share it.  Generator lateness is
printed for judgement and is not part of the rule: the generator shares
the gateway's one thread, so it is late by however long ``pump()`` holds
that thread (PERF.md, findings of PR 23).

Run it on the chip (through the chip tool); on a host it measures the
host.  Results are also written to ``chiprun_out/knee_<workload>.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT_DIR = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, CHECKOUT_DIR)


def sustained(row, burst_every_s: float) -> bool:
    """Nothing shed or unanswered, every burst drained before the next is
    due, and the median tick served outside the drains: p50 below half the
    shortest drain.  (Past the knee the one serving thread never catches up
    between bursts; the backlog then stands in the generator's schedule,
    not in the gateway's queue, and the median tick takes as long as a
    drain.)"""
    if row["shed"] or row["unanswered"] or row["recompiles"]:
        return False
    drains = row["burst_drain_ms"]
    if not drains:
        return bool(row["depth_end"] is not None and row["depth_end"] <= max(
            4.0 * (row["depth_mid"] or 0.0), 50.0))
    if any(d >= burst_every_s * 1e3 for d in drains):
        return False
    return bool(row["p50_ms"] is not None
                and row["p50_ms"] < 0.5 * min(drains))


def offer(rig, driver, traffic, rate: float, seed: int, seconds: float):
    """Offer the cell's traffic at ``rate`` for ``seconds`` through an
    open stack, and return the sweep's row for it.  Sessions carry on
    from whatever the rig served before."""
    import numpy as np

    from benchmark.harness import schedule as sched

    n_sessions = len(rig.sessions.ids)
    ctx = types.SimpleNamespace(
        seconds=seconds, window_begins=lambda: None,
        window_ended=lambda: None)
    t = dict(traffic, rate_ticks_per_s=rate)
    plan = sched.make_schedule(t, seed, seconds)
    rows = sched.walk_rows(rig.sessions, plan.session, seed, stream=1)
    index = sched.index_ticks(plan.session, n_sessions, rig.seq0)
    out = driver.drive(rig, plan, rows, index, set(), ctx, [])
    rig.seq0 = rig.seq0 + np.bincount(plan.session, minlength=n_sessions)
    n, c = out["notes"], out["checks"]
    row = {
        "rate": round(rate, 1),
        "sent": out["attempted"],
        "answered": n["ticks_answered"],
        "shed": c["failed_breakdown"]["shed"],
        "unanswered": out["failed"],
        "depth_mid": n["queue_depth_mid_window"],
        "depth_end": n["queue_depth_end_window"],
        "depth_peak": n["queue_depth_peak"],
        "gen_late_p99_ms": n["gen_lateness_p99_ms_calm"],
        "p50_ms": out["end_to_end"]["tick_p50_ms"],
        "p99_ms": out["end_to_end"]["tick_p99_ms"],
        "burst_drain_ms": [round(d, 1) for d in n["burst_drain_ms"]],
        "recompiles": c["recompiles_after_warmup"],
    }
    row["sustained"] = sustained(row, float(t.get("burst_every_s", 0)))
    return row


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--start", type=float, required=True)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--factor", type=float, default=1.1)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()

    from benchmark.harness import catalog
    from benchmark.harness.serving import Rig

    cell = catalog.find_cell(args.workload)
    config = catalog.load_config(cell.config)
    traffic = catalog.load_traffic(cell.traffic)
    driver = catalog.load_driver(traffic["kind"])

    from fmda_tpu.utils.env import select_backend
    import jax

    select_backend()
    dev = jax.devices()[0]
    print(json.dumps({"platform": dev.platform, "kind": dev.device_kind}),
          flush=True)
    parts = {}
    rig = Rig(config, args.seed, trace=False, parts=parts)
    n_sessions = int(traffic["sessions"])
    rig.open_sessions(n_sessions)
    rig.warm_buckets()
    print(json.dumps({"setup_parts_s": parts, "capacity": rig.cfg.runtime.capacity,
                      "memory": {k: (dev.memory_stats() or {}).get(k) for k in (
                          "bytes_in_use", "peak_bytes_in_use", "bytes_limit")}}),
          flush=True)

    table = []
    rate = args.start
    for step in range(args.steps):
        row = offer(rig, driver, traffic, rate, args.seed + step,
                    args.seconds)
        table.append(row)
        print(json.dumps(row), flush=True)
        rate *= args.factor
    print(json.dumps({"memory_at_end": {
        k: (dev.memory_stats() or {}).get(k)
        for k in ("bytes_in_use", "peak_bytes_in_use")}}), flush=True)
    ok = [r["rate"] for r in table if r["sustained"]]
    knee = max(ok) if ok else None
    print(json.dumps({"knee_ticks_per_s": knee,
                      "highest_rate_tried": table[-1]["rate"]}), flush=True)
    rig.close()
    out_dir = os.path.join(CHECKOUT_DIR, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"knee_{args.workload}.json"), "w") as fh:
        json.dump({"workload": args.workload, "seconds": args.seconds,
                   "device": dev.device_kind, "knee_ticks_per_s": knee,
                   "table": table}, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
