"""Keep ``BENCHMARK.json`` in step with the files under ``benchmark/``.

    python benchmark/tools/manifest.py --check     # exit 1 on any mismatch
    python benchmark/tools/manifest.py --sync      # add what is missing

``--sync`` adds a ``per_layer`` entry (from the reader's ``UNIT``,
``BETTER``, ``SOURCE``, ``LAYER`` and each name in its ``MOVES``) for every
metric of a reader under ``benchmark/layer_metrics/`` that has none and
moves an end-to-end metric the file lists, and recomputes each per-layer
entry's ``workloads`` (the cells whose driver reports the metric it
moves).  A reader whose moved metric no cell of record reports (the
serving readers, while the serving cells are kept in
``benchmark/cells.json``) has no entry and needs none.  It never touches ``command``, ``paths``, ``run_seconds``,
``configs``, ``workloads``, or an end-to-end entry: those are written by
hand, the bounds from measured spread.  ``--check`` also holds every
name, unit and length to the characters the contract allows.
"""

from __future__ import annotations

import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")

def cells_reporting(manifest, metric_moves, catalog):
    out = []
    for w in manifest["workloads"]:
        traffic = catalog.load_traffic(w["traffic"])
        driver = catalog.load_driver(traffic["kind"])
        if metric_moves in driver.END_TO_END:
            out.append(w["name"])
    return out


def problems(manifest, catalog):
    bad = []
    names = set()
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in manifest[section]:
            if not NAME_RE.match(e["name"]):
                bad.append(f"{section}: bad name {e['name']!r}")
            if (section, e["name"]) in names:
                bad.append(f"{section}: duplicate {e['name']!r}")
            names.add((section, e["name"]))
    metric_names = [m["name"] for m in
                    manifest["end_to_end"] + manifest["per_layer"]]
    if len(metric_names) != len(set(metric_names)):
        bad.append("a metric name is used twice")
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if not UNIT_RE.match(m["unit"]):
            bad.append(f"{m['name']}: bad unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            bad.append(f"{m['name']}: better={m['better']!r}")
        if m["source"] not in SOURCES:
            bad.append(f"{m['name']}: source={m['source']!r}")
    for m in manifest["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"{m['name']}: end-to-end source {m['source']!r}")
        if not 0 < m["bound"] <= 0.1:
            bad.append(f"{m['name']}: bound {m['bound']}")
    readers = catalog.load_layer_metrics()
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for m in manifest["per_layer"]:
        reader = readers.get(m["name"])
        if reader is None:
            bad.append(f"{m['name']}: no reader under layer_metrics/")
            continue
        for key, value in reader.entry().items():
            if m[key] != value:
                bad.append(f"{m['name']}: {key} {m[key]!r} != reader's "
                           f"{value!r}")
        if m["moves"] not in e2e:
            bad.append(f"{m['name']}: moves unknown metric {m['moves']!r}")
        want = cells_reporting(manifest, m["moves"], catalog)
        if sorted(m.get("workloads", [])) != sorted(want):
            bad.append(f"{m['name']}: workloads {m.get('workloads')} != "
                       f"{want}")
    bench_dir = os.path.dirname(HERE)
    own = {n for n, r in readers.items() if r.moves in e2e
           and os.path.dirname(
               os.path.dirname(r.module.__file__)) == bench_dir}
    for n in sorted(own - {m["name"] for m in manifest["per_layer"]}):
        bad.append(f"{n}: reader without a per_layer entry")
    for name, m in e2e.items():
        if name == "setup_s":
            continue
        want = cells_reporting(manifest, name, catalog)
        if sorted(m.get("workloads", [])) != sorted(want):
            bad.append(f"{name}: workloads {m.get('workloads')} != {want}")
    for w in manifest["workloads"]:
        if len(w["why"]) > 200:
            bad.append(f"{w['name']}: why is {len(w['why'])} characters")
        if w["config"] not in {c["name"] for c in manifest["configs"]}:
            bad.append(f"{w['name']}: unknown config {w['config']!r}")
    return bad


def sync(manifest, catalog):
    readers = catalog.load_layer_metrics()
    bench_dir = os.path.dirname(HERE)
    have = {m["name"]: m for m in manifest["per_layer"]}
    e2e = {m["name"] for m in manifest["end_to_end"]}
    for name, reader in sorted(readers.items()):
        if reader.moves not in e2e or os.path.dirname(os.path.dirname(
                reader.module.__file__)) != bench_dir:
            continue
        entry = have.get(name)
        if entry is None:
            entry = reader.entry()
            manifest["per_layer"].append(entry)
        entry["workloads"] = cells_reporting(manifest, reader.moves, catalog)
    return manifest


def main() -> int:
    from benchmark.harness import catalog

    manifest = catalog.load_manifest()
    if "--sync" in sys.argv[1:]:
        manifest = sync(manifest, catalog)
        with open(catalog.MANIFEST_PATH, "w") as fh:
            json.dump(manifest, fh, indent=1)
            fh.write("\n")
    bad = problems(manifest, catalog)
    for line in bad:
        print(line)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
