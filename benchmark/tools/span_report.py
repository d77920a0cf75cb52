"""Print what a profiler trace says of the program's spans and scopes.

    python benchmark/tools/span_report.py <trace.xplane.pb> [--depth N] [--top N]

The trace is one the benchmark's traced tail left under ``.bench_trace/``
or one captured with ``python -m fmda_tpu train --jax-profile DIR`` (then
under ``DIR/plugins/profile/<time>/``).  Printed, for the traced slice
(the whole capture where the benchmark wrote no ``bench_slice``): the
step thread's spans by total and mean; its time under no span; the
device's idle gaps by the step thread's innermost span; device busy time
by compiled program (``jit_train_step`` against ``jit_eval_step``); and,
for each program, busy time by named-scope path cut to ``--depth``
components, with the operations traced under no scope listed by name.
It is the table the five ``train_*`` readers under ``layer_metrics/``
draw from (``harness/program_spans.py``); needs nothing but the file.
"""

from __future__ import annotations

import argparse
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


#: Operations the compiler makes many numbered copies of, listed as one.
_COPIES = re.compile(
    r"^(.*\.clone|(?:copy|slice)-(?:start|done))(?:\.\d+)?$")


def _rows(title, rows, total, top=None):
    print(f"\n{title}")
    rows = sorted(rows, key=lambda kv: -kv[1])
    for label, seconds in rows[:top]:
        print(f"  {seconds:12.6f} s  {100.0 * seconds / total:6.2f} %  "
              f"{label}")
    if top is not None and len(rows) > top:
        rest = sum(s for _, s in rows[top:])
        print(f"  {rest:12.6f} s  {100.0 * rest / total:6.2f} %  "
              f"{len(rows) - top} more, each under "
              f"{100.0 * rows[top][1] / total:.2f} %")


def report(path: str, depth: int = 3, top: int = 12) -> int:
    from benchmark.harness import program_spans as ps

    r = ps.reduce(ps.load(path))
    if r is None:
        print("nothing to read: no program span and no device operation")
        return 1
    window = r["window_s"]
    print(f"slice {window:.6f} s; step thread {r['step_thread']}")

    print("\nstep thread, spans that started in the slice")
    print(f"  {'span':<20}{'count':>8}{'total s':>12}{'mean us':>12}")
    for name, row in sorted(r["spans"].items(),
                            key=lambda kv: -kv[1]["total_s"]):
        print(f"  {name:<20}{row['count']:>8}{row['total_s']:>12.6f}"
              f"{row['mean_s'] * 1e6:>12.1f}")
    if r["self_s"] is not None:
        steps = r["spans"].get("train", {}).get("count", 0)
        per = f", {r['self_s'] / steps * 1e6:.1f} us a train step" \
            if steps else ""
        print(f"  under no program span: {r['self_s']:.6f} s "
              f"({100.0 * r['self_s'] / window:.1f} % of the slice{per})")
    for thread, table in r["other_threads"].items():
        print(f"\nthread {thread}")
        for name, row in sorted(table.items()):
            print(f"  {name:<20}{row['count']:>8}{row['total_s']:>12.6f}"
                  f"{row['mean_s'] * 1e6:>12.1f}")

    if r["busy_s"] is None:
        print("\nno device plane in this trace (a CPU capture)")
        return 0
    busy = r["busy_s"]
    print(f"\ndevice busy {busy:.6f} s, idle "
          f"{100.0 * (1.0 - busy / window):.2f} % of the slice")
    _rows("device idle by the step thread's innermost program span",
          r["idle_by_span"].items(), window - busy)
    _rows("device busy by compiled program", r["busy_by_module"].items(),
          busy)
    for module in sorted(r["busy_by_module"],
                         key=lambda m: -r["busy_by_module"][m]):
        total = r["busy_by_module"][module]
        scoped, unscoped = {}, {}
        for (mod, scope, name), seconds in r["busy_by_scope"].items():
            if mod != module:
                continue
            if scope == ps.NO_SCOPE:
                m = _COPIES.match(name)
                name = m.group(1) + ".*" if m else name
                unscoped[name] = unscoped.get(name, 0.0) + seconds
            else:
                key = "/".join(scope.split("/")[:depth])
                scoped[key] = scoped.get(key, 0.0) + seconds
        _rows(f"{module or '(no program)'}: busy by scope path "
              f"(first {depth} components)", scoped.items(), total)
        named = sum(seconds for key, seconds in scoped.items()
                    if ps.scope_root(key) in ps.STEP_SCOPE_ROOTS)
        if named:
            print(f"  {named:12.6f} s  {100.0 * named / total:6.2f} %  "
                  "under " + ", ".join(ps.STEP_SCOPE_ROOTS)
                  + " (or a gradient transform of one)")
        if unscoped:
            _rows(f"{module or '(no program)'}: traced under no scope, "
                  "by operation", unscoped.items(), total, top=top)
            print(f"  {sum(unscoped.values()):12.6f} s  "
                  f"{100.0 * sum(unscoped.values()) / total:6.2f} %  "
                  "all of them")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--depth", type=int, default=3,
                    help="components of a scope path to group by")
    ap.add_argument("--top", type=int, default=12,
                    help="unscoped operations to list by name")
    args = ap.parse_args()
    return report(args.trace, args.depth, args.top)


if __name__ == "__main__":
    sys.exit(main())
