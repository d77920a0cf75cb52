"""chip_smoke.py on the CPU: a dry run, never a pass.

The full-width sections are the chip's business (the driver runs the
script there for every PR); tier-1 only pins the contract a CPU run must
keep — the host-side sections run, the device line says what it ran on,
and the script exits non-zero without printing the result line.
"""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_cpu_run_exercises_the_host_side_and_exits_nonzero(
        monkeypatch, capsys):
    # the device-heavy sections stay out of tier-1 (full width only, and
    # the suite has no time to spare): stubbed, they "pass" — the run
    # must fail all the same, because the platform is not a TPU
    for name in ("serving", "profile_fact", "training", "the_loop",
                 "kernels", "four_chips"):
        monkeypatch.setattr(chip_smoke, name, lambda *a, **k: None)
    monkeypatch.setattr(chip_smoke, "_failed", [])
    monkeypatch.setattr(chip_smoke, "_facts", {})

    assert chip_smoke.main() != 0

    lines = capsys.readouterr().out.splitlines()
    device = json.loads(lines[0])  # the device line comes first
    assert device["platform"] == "cpu" and device["count"] >= 1
    assert {"kind", "jax", "jaxlib", "libtpu", "compile_cache_dir"} <= set(
        device)
    assert "ok host path" in " ".join(lines)
    host = json.loads(lines[lines.index("== host path") + 1])
    assert host["bus"] in ("NativeBus", "InProcessBus")
    assert host["join_scheduler"] == "python"
    assert lines[-1].startswith("FAILED: platform is 'cpu'")
    assert not any(line.startswith('{"ok"') for line in lines)


def test_a_failing_section_fails_the_run_and_prints_no_result(
        monkeypatch, capsys):
    def boom():
        raise RuntimeError("section broke")

    monkeypatch.setattr(chip_smoke, "_failed", [])
    chip_smoke.section("broken", boom)
    chip_smoke.section("fine", lambda: None)
    assert chip_smoke._failed == ["broken"]
    out = capsys.readouterr().out
    assert "section broke" in out and "!! broken FAILED" in out
    assert "ok fine" in out
