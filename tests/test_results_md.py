"""Locks for the RESULTS.md section splicer (`experiments/results_md.py`)."""

import os
import sys

EXPERIMENTS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "experiments")
if EXPERIMENTS not in sys.path:
    sys.path.insert(0, EXPERIMENTS)

from results_md import extract_section, replace_section  # noqa: E402

SAMPLE = (
    "# R\n\nbody\n\n## Seed robustness (x)\n\nold table\n\n"
    "## Later section\n\nkeep me\n"
)


class TestResultsMd:
    def test_extract_bounded_at_next_heading(self):
        sec = extract_section(SAMPLE)
        assert sec.startswith("## Seed robustness")
        assert "old table" in sec and "Later" not in sec

    def test_extract_absent(self):
        assert extract_section("# R\nbody\n") == ""

    def test_replace_preserves_separator_and_tail(self):
        out = replace_section(SAMPLE, "## Seed robustness (y)\n\nnew")
        assert "new\n\n## Later section" in out
        assert "old table" not in out and "keep me" in out

    def test_replace_idempotent_single_section(self):
        out = SAMPLE
        for i in range(3):
            out = replace_section(out, f"## Seed robustness run{i}\n\nt{i}")
        assert out.count("## Seed robustness") == 1
        assert "t2" in out and "keep me" in out

    def test_replace_appends_when_absent(self):
        out = replace_section("# R\nbody\n", "## Seed robustness\nz")
        assert out.endswith("## Seed robustness\nz\n")
