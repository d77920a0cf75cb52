"""``BENCHMARK.json`` keeps to the contract's characters and lengths, and
agrees with the files it names."""

import json
import os
import re
import sys

import pytest

from benchmark.harness import catalog

sys.path.insert(0, os.path.join(catalog.BENCH_DIR, "tools"))
import manifest as manifest_tool  # noqa: E402

NAME, UNIT = manifest_tool.NAME_RE, manifest_tool.UNIT_RE
FILE = re.compile(r"^[A-Za-z0-9_.\-/]+$")
M = catalog.load_manifest()


def test_keys_are_exactly_the_contracts():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert M["paths"] == ["benchmark"]
    assert M["command"][-1] == "benchmark/run.py"
    assert 1 <= M["run_seconds"] <= 51
    assert os.path.getsize(catalog.MANIFEST_PATH) < 64 * 1024


def test_entries_have_just_the_allowed_keys():
    for c in M["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in M["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in M["end_to_end"]:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "bound", "source"}
    for m in M["per_layer"]:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves"}


@pytest.mark.parametrize("entry", M["end_to_end"] + M["per_layer"],
                         ids=lambda e: e["name"])
def test_metric_names_and_units(entry):
    assert NAME.match(entry["name"])
    assert UNIT.match(entry["unit"]) and len(entry["unit"]) <= 16
    assert entry["better"] in ("lower", "higher")
    if "layer" in entry:
        assert 1 <= len(entry["layer"]) <= 200 and "\n" not in entry["layer"]


def test_names_lengths_and_one_line_strings():
    for w in M["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["config"])
        assert NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
        assert "\n" not in w["why"] and "\t" not in w["why"]
    for c in M["configs"]:
        assert NAME.match(c["name"]) and FILE.match(c["file"])
        assert c["file"].startswith("benchmark/")
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        assert len(c["reduced"]) <= 16
    assert len({c["file"] for c in M["configs"]}) == len(M["configs"])
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(1 for w in M["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(M["workloads"]) // 4)


def test_every_file_under_paths_is_named_from_allowed_characters():
    for root, dirs, files in os.walk(catalog.BENCH_DIR):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for fn in files:
            rel = os.path.relpath(os.path.join(root, fn), catalog.CHECKOUT_DIR)
            assert FILE.match(rel), rel


def test_manifest_agrees_with_the_files():
    assert manifest_tool.problems(M, catalog) == []


def test_every_cell_reports_setup_another_metric_and_a_layer_metric():
    for w in M["workloads"]:
        e2e = [m["name"] for m in M["end_to_end"]
               if "workloads" not in m or w["name"] in m["workloads"]]
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = [m for m in M["per_layer"]
                 if "workloads" not in m or w["name"] in m["workloads"]]
        assert layer
        for m in layer:
            assert m["moves"] in e2e


def test_the_harness_imports_nothing_from_bench_or_chip_smoke():
    pat = re.compile(r"^\s*(from|import)\s+(bench|chip_smoke)\b", re.M)
    for root, dirs, files in os.walk(catalog.BENCH_DIR):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for fn in files:
            if fn.endswith(".py"):
                with open(os.path.join(root, fn)) as fh:
                    assert not pat.search(fh.read()), fn


def test_config_files_state_source_reduced_assumed_and_guarantees():
    for c in M["configs"]:
        with open(os.path.join(catalog.CHECKOUT_DIR, c["file"])) as fh:
            cfg = json.load(fh)
        for key in ("source", "framework", "reduced", "assumed",
                    "guarantees"):
            assert key in cfg, (c["name"], key)
        assert cfg["reduced"] == c["reduced"]
