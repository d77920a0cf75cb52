"""One cell, once, in one process.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Selects the backend with the repo's own rule, builds the cell's system
from the seed on the device, warms only the cell's own shapes, measures
for ``--seconds``, checks correctness outside the window, and prints one
JSON object as the last line of standard output.  Everything else worth
a number goes to standard error, one JSON object a line.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
measures the same untraced window, then traces a little more of the
same traffic (seconds of a serving cell's, a count of steps of a
training cell's), and reports the cell's per-layer metrics and the
breakdown (``harness/tracing.py``).  A cell of record needs a TPU and
exits non-zero at once without one.  Any other cell (the rehearsal's
tiny ones under ``benchmark/selftest/``) runs on whatever is pinned, end
to end, and then — not being on a TPU — exits non-zero without a result
line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_T_FIRST_LINE = time.time()

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT_DIR = os.path.dirname(BENCH_DIR)
if CHECKOUT_DIR not in sys.path:
    sys.path.insert(0, CHECKOUT_DIR)

EXIT_NO_TPU = 3
EXIT_TOO_FEW_CHIPS = 4


def process_start_time() -> float:
    """Wall-clock time this process was created (so that interpreter
    start-up is inside ``setup_s``); the first line of this file where
    /proc cannot say."""
    try:
        with open("/proc/self/stat") as fh:
            start_ticks = int(fh.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as fh:
            uptime = float(fh.read().split()[0])
        created = time.time() - uptime + start_ticks / os.sysconf("SC_CLK_TCK")
        if 0 <= _T_FIRST_LINE - created < 60:
            return created
    except (OSError, ValueError, IndexError):
        pass
    return _T_FIRST_LINE


class RunContext:
    """What a driver gets: the cell's data files, the run's arguments, and
    the hooks that mark the measured window."""

    def __init__(self, cell, config, traffic, args, watch, t_created):
        self.cell = cell
        self.config = config
        self.traffic = traffic
        self.seed = args.seed
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.parts = {}
        self.watch = watch
        self.t_created = t_created
        self.setup_s = None
        self.trace_dir = os.path.join(
            CHECKOUT_DIR, ".bench_trace", cell.name)

    def say(self, obj) -> None:
        print(json.dumps(obj, default=_jsonable), file=sys.stderr, flush=True)

    def window_begins(self) -> None:
        self.setup_s = time.time() - self.t_created
        self.watch.in_window = True

    def window_ended(self) -> None:
        self.watch.in_window = False


def _jsonable(o):
    import numpy as np

    if isinstance(o, (np.floating, np.integer)):
        return o.item()
    if isinstance(o, np.ndarray):
        return o.tolist()
    return str(o)


def main(argv=None) -> int:
    t_created = process_start_time()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    t0 = time.perf_counter()
    from benchmark.harness import catalog

    cell = catalog.find_cell(args.workload)
    config = catalog.load_config(cell.config)
    traffic = catalog.load_traffic(cell.traffic)
    driver = catalog.load_driver(traffic["kind"])  # imports jax

    import jax

    from fmda_tpu.utils.env import select_backend

    select_backend()  # the repo's rule; places the compile cache too
    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"
    if cell.of_record and not on_tpu:
        print(f"{cell.name}: a cell of record runs on a TPU; jax found "
              f"{devices[0].platform} ({devices[0].device_kind}). No result.",
              file=sys.stderr)
        return EXIT_NO_TPU
    if on_tpu and len(devices) < cell.chips:
        print(f"{cell.name}: needs {cell.chips} chips, jax found "
              f"{len(devices)}. No result.", file=sys.stderr)
        return EXIT_TOO_FEW_CHIPS

    from benchmark.harness import device as dev

    if on_tpu:
        dev.peaks_for(devices[0].device_kind)  # unknown kind: an error
    watch = dev.CompileWatch()
    ctx = RunContext(cell, config, traffic, args, watch, t_created)
    ctx.parts["imports_and_backend"] = (
        time.perf_counter() - t0 + (_T_FIRST_LINE - t_created))
    ctx.say({"cell": cell.name, "config": cell.config,
             "traffic": cell.traffic, "seed": args.seed,
             "seconds": args.seconds, "trace": args.trace,
             "device": dev.describe(devices),
             "compile_cache_dir": jax.config.jax_compilation_cache_dir})

    out = driver.run(ctx)

    device = dev.describe(devices)
    device["memory_peak_bytes"] = dev.memory_peak_bytes(devices)
    compile_facts = watch.summary()
    correct = bool(out["correct"]
                   and compile_facts["compile_events_in_window"] == 0)
    accounted = sum(ctx.parts.values())
    ctx.parts["other"] = ctx.setup_s - accounted
    ctx.say({"setup_s": ctx.setup_s, "setup_parts_s": ctx.parts})
    ctx.say({"checks": out["checks"], "compile": compile_facts})
    ctx.say({"notes": out["notes"]})

    end_to_end = dict(out["end_to_end"])
    end_to_end["setup_s"] = ctx.setup_s
    units = dict(driver.END_TO_END, setup_s="s")
    result = {"correct": correct, "attempted": int(out["attempted"]),
              "failed": int(out["failed"])}
    if not args.trace:
        missing = [k for k, v in end_to_end.items() if v is None]
        if missing:
            ctx.say({"error": f"no value for {missing}: too few samples"})
            result["correct"] = False
        result["metrics"] = {
            k: {"value": v, "unit": units[k]}
            for k, v in end_to_end.items() if v is not None}
    else:
        from benchmark.harness import layers

        record = dict(out["record"], end_to_end=end_to_end, device=device,
                      cell=cell, traffic=traffic, config=config)
        metrics, reduced = layers.read_all(
            record, driver.END_TO_END, catalog.load_layer_metrics(), ctx.say)
        result["metrics"] = metrics
        ctx.say({"end_to_end_in_traced_run": end_to_end})
        # what the profiler costs: the traced tail against the window
        tail = out["notes"].get("traced_tail") or {}
        bounds = {m["name"]: m["bound"]
                  for m in catalog.load_manifest()["end_to_end"]}
        for k, v in end_to_end.items():
            t = tail.get(k)
            if t and v and abs(t / v - 1.0) > bounds.get(k, 0.1):
                ctx.say({"warning": (
                    f"{k}: {t:.6g} in the traced tail against {v:.6g} in "
                    "the window: beyond the metric's bound, so the tail's "
                    "device numbers describe a perturbed regime")})
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            result["breakdown"] = {
                "device_ops": reduced["device_ops"],
                "idle_gaps": reduced["idle_by_span"],
            }
            ctx.say({"trace": {k: reduced[k] for k in (
                "idle_share", "longest_gap_s", "n_gaps", "steps",
                "n_device_planes")}})
    result["device"] = device
    # what the run cost whoever waits for it, the reference check, the
    # profiler's stop and the reductions of the trace included
    ctx.say({"traced_run_s" if args.trace else "untraced_run_s":
             time.time() - t_created})

    if not on_tpu:
        print(f"{cell.name}: rehearsal on {device['platform']} finished "
              f"(correct={result['correct']}); not a TPU, so no result "
              "line.", file=sys.stderr)
        ctx.say({"rehearsal_result": result})
        return EXIT_NO_TPU
    sys.stderr.flush()
    print(json.dumps(result, default=_jsonable), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
