"""One measuring system (PR 30): the benchmark of record under
``benchmark/`` is the only yardstick, and nothing in the tree sends a
reader to the phase runner that used to stand at the root.

Two structural locks the deletion leans on:

* no source or prose file names the old runner or its phase flag, outside
  the places that record history (``CHANGES.md``, ``PERF.md`` §6,
  ROADMAP's "Recent" and its struck items) and the file the driver
  writes (``ISSUE.md``);
* every package's ``__all__`` names only what importing it provides, so
  a symbol deleted with its last caller cannot linger as an export.
"""

import importlib
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: spelled in pieces so that this file passes its own check
NEEDLES = ("bench" + ".py", "--" + "phase ")

#: whole files that are history, or the driver's
HISTORY_FILES = {"CHANGES.md", "ISSUE.md"}

#: the benchmark's own note of where it copied a function from
#: (benchmark/ is not this PR's to edit)
ORIGIN_NOTES = {os.path.join("benchmark", "harness", "flops.py")}

#: what building, testing and chip runs leave beside the sources
SKIP_DIRS = {"__pycache__", "chiprun_out", "build", "checkpoints"}

AREAS = ("fmda_tpu", "tests", "benchmark", "docs", "experiments",
         "examples", ".claude", "root")


def _files(area):
    if area == "root":
        return sorted(
            n for n in os.listdir(REPO)
            if n.endswith((".py", ".md"))
            and os.path.isfile(os.path.join(REPO, n)))
    found = []
    for base, dirs, names in os.walk(os.path.join(REPO, area)):
        dirs[:] = sorted(d for d in dirs
                         if d not in SKIP_DIRS and not d.startswith("."))
        found += [os.path.relpath(os.path.join(base, n), REPO)
                  for n in sorted(names) if n.endswith((".py", ".md"))]
    return found


def _without_history(rel, text):
    """``text`` minus the parts of ``rel`` that may name what is gone."""
    if rel == "PERF.md":  # §6 is the findings, PR by PR
        return re.sub(r"(?ms)^## 6\..*?(?=^## 7\.)", "", text)
    if rel == "ROADMAP.md":
        text = re.sub(r"(?ms)^## Recent\n.*\Z", "", text)
        # a struck item: "- ~~**D1. ...", through its indented lines
        return re.sub(r"(?m)^- ~~.*\n(?:[ \t]+.*\n)*", "", text)
    return text


@pytest.mark.parametrize("area", AREAS)
def test_nothing_sends_a_reader_to_the_retired_phase_runner(area):
    files = [f for f in _files(area)
             if f not in HISTORY_FILES and f not in ORIGIN_NOTES]
    assert files, f"no sources found under {area}"
    hits = []
    for rel in files:
        with open(os.path.join(REPO, rel), encoding="utf-8") as fh:
            text = _without_history(rel, fh.read())
        hits += [f"{rel}: {line.strip()[:100]}"
                 for line in text.splitlines()
                 if any(n in line for n in NEEDLES)]
    assert not hits, "\n".join(hits)


def test_the_retired_runner_and_its_records_are_not_in_the_tree():
    for rel in ("bench" + ".py", "tests/test_bench_helpers.py",
                "artifacts/train_throughput.json",
                "artifacts/replay_throughput.json",
                "artifacts/device_ledger.json",
                "artifacts/quality_eval.json"):
        assert not os.path.exists(os.path.join(REPO, rel)), rel


PACKAGES = ["fmda_tpu"] + sorted(
    "fmda_tpu." + d for d in os.listdir(os.path.join(REPO, "fmda_tpu"))
    if os.path.isfile(os.path.join(REPO, "fmda_tpu", d, "__init__.py")))


@pytest.mark.parametrize("package", PACKAGES)
def test_package_all_names_only_what_it_provides(package):
    mod = importlib.import_module(package)
    exported = getattr(mod, "__all__", ())
    missing = [n for n in exported if not hasattr(mod, n)]
    assert not missing, f"{package}.__all__ names {missing}"
    assert len(set(exported)) == len(exported)
    if package == "fmda_tpu.data":
        assert "prefetch_batches" in exported
        assert "prefetch_to_device" not in exported
