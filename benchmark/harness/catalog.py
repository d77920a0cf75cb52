"""Find a cell and everything it names, by listing directories.

Nothing here is a registry someone must edit.  A *root* is a directory
laid out like ``benchmark/``::

    configs/<name>.json          one file per configuration
    traffic/<name>.json          one file per traffic mix; its ``kind``
                                 names a module under drivers/
    drivers/<kind>.py            one general driver per kind of traffic
    layer_metrics/<name>.py      one reader per per-layer metric (one
                                 file, one name per end-to-end metric
                                 it moves)
    cells.json                   optional: {"workloads": [...]} — cells
                                 that are not cells of record

The cells of record are the ``workloads`` of ``BENCHMARK.json`` at the
root of the checkout.  Roots are searched in order: ``benchmark/``,
``benchmark/selftest/`` (the rehearsal's tiny cells), then every
directory named in ``FMDA_BENCH_ROOTS`` (``os.pathsep``-separated) — how
a test drops a cell, a traffic file and a metric into a temporary
directory and has them picked up with no edit.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from dataclasses import dataclass
from typing import Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT_DIR = os.path.dirname(BENCH_DIR)
MANIFEST_PATH = os.path.join(CHECKOUT_DIR, "BENCHMARK.json")
ROOTS_ENV = "FMDA_BENCH_ROOTS"


def roots() -> List[str]:
    out = [BENCH_DIR, os.path.join(BENCH_DIR, "selftest")]
    extra = os.environ.get(ROOTS_ENV, "")
    out.extend(p for p in extra.split(os.pathsep) if p)
    return out


def load_manifest(path: str = MANIFEST_PATH) -> dict:
    with open(path) as fh:
        return json.load(fh)


@dataclass(frozen=True)
class Cell:
    name: str
    config: str
    traffic: str
    chips: int
    why: str
    #: True for an entry of BENCHMARK.json's ``workloads``.  A cell of
    #: record runs on a TPU or not at all; any other cell may be
    #: rehearsed on the host, and then prints no result line.
    of_record: bool


def _find(kind_dir: str, filename: str) -> Optional[str]:
    for root in roots():
        path = os.path.join(root, kind_dir, filename)
        if os.path.isfile(path):
            return path
    return None


def find_cell(name: str) -> Cell:
    manifest = load_manifest()
    for w in manifest["workloads"]:
        if w["name"] == name:
            return Cell(w["name"], w["config"], w["traffic"],
                        int(w["chips"]), w["why"], True)
    for root in roots():
        path = os.path.join(root, "cells.json")
        if not os.path.isfile(path):
            continue
        with open(path) as fh:
            for w in json.load(fh)["workloads"]:
                if w["name"] == name:
                    return Cell(w["name"], w["config"], w["traffic"],
                                int(w.get("chips", 1)), w.get("why", ""),
                                False)
    raise SystemExit(f"no cell named {name!r} in BENCHMARK.json or in any "
                     f"cells.json under {roots()}")


def load_config(name: str) -> dict:
    """A configuration's file of sizes.  A cell of record's file is the
    one BENCHMARK.json names; any other is ``configs/<name>.json`` in the
    first root that has it."""
    for c in load_manifest()["configs"]:
        if c["name"] == name:
            with open(os.path.join(CHECKOUT_DIR, c["file"])) as fh:
                return json.load(fh)
    path = _find("configs", name + ".json")
    if path is None:
        raise SystemExit(f"no configuration file for {name!r}")
    with open(path) as fh:
        return json.load(fh)


def load_traffic(name: str) -> dict:
    path = _find("traffic", name + ".json")
    if path is None:
        raise SystemExit(f"no traffic file for {name!r}")
    with open(path) as fh:
        return json.load(fh)


def _load_module(path: str, modname: str):
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def load_driver(kind: str):
    path = _find("drivers", kind + ".py")
    if path is None:
        raise SystemExit(f"no driver module for traffic kind {kind!r}")
    return _load_module(path, f"_bench_driver_{kind}")


@dataclass(frozen=True)
class LayerMetric:
    """One per-layer metric as ``BENCHMARK.json`` lists it: a reader
    module under the name it reports in cells that report ``moves``."""
    name: str
    moves: str
    module: object

    def entry(self) -> dict:
        """The ``per_layer`` entry (without ``workloads``)."""
        mod = self.module
        return {"name": self.name, "unit": mod.UNIT, "better": mod.BETTER,
                "source": mod.SOURCE, "layer": mod.LAYER,
                "moves": self.moves}


def load_layer_metrics() -> Dict[str, LayerMetric]:
    """Every reader under ``layer_metrics/`` of every root, by reported
    name.  A reader's ``MOVES`` is the end-to-end metric it should move,
    or — a per-layer metric being reported only where the metric it moves
    is — a dict from each such metric to the name the reading takes in
    the cells that report it.  No name may be defined twice."""
    found: Dict[str, LayerMetric] = {}
    for i, root in enumerate(roots()):
        d = os.path.join(root, "layer_metrics")
        if not os.path.isdir(d):
            continue
        for fn in sorted(os.listdir(d)):
            if not fn.endswith(".py") or fn.startswith("_"):
                continue
            mod = _load_module(
                os.path.join(d, fn), f"_bench_metric_{i}_{fn[:-3]}")
            moves = mod.MOVES
            if isinstance(moves, str):
                moves = {moves: mod.NAME}
            for moved, name in moves.items():
                if name in found:
                    raise SystemExit(
                        f"per-layer metric {name!r} is defined twice")
                found[name] = LayerMetric(name, moved, mod)
    return found
