"""Locks for bench.py's reporting helpers and the RESULTS.md splicer.

The bench artifact's derived numbers (analytic FLOPs, MFU against the one
published-peaks table — a device kind that is not in it gets no MFU at
all, never an assumed peak) and the experiments' RESULTS.md section
handling are test-locked here.
"""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (REPO, os.path.join(REPO, "experiments")):
    if p not in sys.path:
        sys.path.insert(0, p)

import bench  # noqa: E402
from results_md import extract_section, replace_section  # noqa: E402


class TestMFU:
    def test_bench_and_package_share_the_one_peak_table(self):
        from fmda_tpu.obs.device import DEVICE_PEAKS

        # keyed by jax device_kind; no cpu/interpreter/"tpu" backend rows
        assert DEVICE_PEAKS == {"TPU v5 lite": (197e12, 819e9)}
        est = bench._mfu(197e12, 1.0, "TPU v5 lite")
        assert est == 1.0  # flops/s equal to the published peak

    def test_unknown_kind_has_no_mfu_not_an_assumed_one(self):
        from fmda_tpu.obs.device import DEVICE_PEAKS

        for kind in ("TPU v9 imaginary", "cpu", "tpu", "", None):
            assert DEVICE_PEAKS.get(kind) is None
            assert bench._mfu(1e12, 1.0, kind) is None


class TestModelFlops:
    def test_positive_and_monotone(self):
        base = bench.model_flops_per_step(256, 30, 108, 32)
        assert base > 0
        assert bench.model_flops_per_step(512, 30, 108, 32) > base
        assert bench.model_flops_per_step(256, 60, 108, 32) > base
        assert bench.model_flops_per_step(256, 30, 108, 64) > base

    def test_linear_in_batch(self):
        one = bench.model_flops_per_step(1, 30, 108, 32)
        many = bench.model_flops_per_step(64, 30, 108, 32)
        assert abs(many / one - 64) / 64 < 0.01


class TestPhaseRegistry:
    def test_expected_phases_registered(self):
        expected = {
            "flagship_pallas", "flagship_scan", "flagship_bf16",
            "flagship_wide", "train_e2e", "kernel_sweep", "attn_sweep",
            "longctx", "longctx_attn", "longctx_attn_bf16", "longctx_sp",
            "multiticker", "serving", "torch",
            "tpu_export",
            "replay",
            "replay_throughput",
            "runtime_fleet_smoke",
            "predictor_fleet_smoke",
            "runtime_multihost_smoke",
            "control_capacity_model",
            "runtime_chaos_soak",
            "pipeline_chaos_soak",
            "obs_overhead",
            "obs_aggregate_overhead",
            "trace_overhead",
            "quality_overhead",
            "device_obs_overhead",
            "analysis_lint",
            "wire_codec_bench",
            "train_throughput",
        }
        assert expected == set(bench._PHASES)

    def test_analysis_lint_pins_the_never_abort_rules(self):
        """ISSUE 15 phase-change pin: the analysis_lint phase holds the
        three never-abort analyzers at zero findings outright.  A rule
        added to (or renamed in) the catalog must update this pin — and
        the phase's zero-findings assertion — in the same PR."""
        from fmda_tpu.analysis import rule_catalog

        assert set(bench.NEVER_ABORT_RULES) == {
            "counted-loss", "wire-protocol", "thread-lifecycle"}
        assert set(bench.NEVER_ABORT_RULES) <= set(
            rule_catalog(drift=False))

    def test_replay_throughput_artifact_schema_pinned(self):
        """ISSUE 18 phase-change pin: artifacts/replay_throughput.json
        carries per-cell rows/s, the bit-identity verdict, and the
        hot-swap zero-downtime accounting under exactly these keys —
        downstream dashboards read the artifact, so a key rename must
        update this pin (and the readers) in the same PR."""
        assert tuple(sorted(bench.REPLAY_THROUGHPUT_SCHEMA)) == (
            "buckets", "cadence_s", "cells", "hot_swap", "identity_ok",
            "quiet_host", "rounds", "tickers")

    def test_quality_eval_artifact_schema_pinned(self):
        """ISSUE 19 phase-change pin: artifacts/quality_eval.json
        carries the quality-plane overhead A/B plus the capture
        conservation verdict under exactly these keys —
        ``python -m fmda_tpu quality --artifact`` and CI dashboards
        read it, so a key rename must update this pin (and the
        readers) in the same PR."""
        assert tuple(sorted(bench.QUALITY_EVAL_SCHEMA)) == (
            "budget_pct", "conservation_ok", "disabled_wall_s",
            "enabled_wall_s", "join_wall_s", "joined", "ok",
            "overhead_pct", "quiet_host", "reps", "rounds", "sessions")

    def test_train_throughput_artifact_schema_pinned(self):
        """ISSUE 20 phase-change pin: artifacts/train_throughput.json
        carries the input-pipeline A/B (seed-sync vs pipelined vs
        pipelined+accum samples/s), the compile pins, and the continuous
        fine-tune/hot-swap cell under exactly these keys — the driver
        reads the artifact as the tentpole's evidence, so a key rename
        must update this pin (and the readers) in the same PR."""
        assert tuple(sorted(bench.TRAIN_THROUGHPUT_SCHEMA)) == (
            "accum_speed_ratio", "backend", "batch_size", "cells",
            "compile_ok", "continuous", "device_kind", "epochs",
            "features", "n_devices", "quiet_host", "rows",
            "speedup_vs_seed", "window")

    def test_kernel_sweep_and_fleet_ab_cover_the_ssm_family(self):
        """ISSUE 14 phase-change pin: the kernel sweep races the SSM
        serve-step kernel alongside the GRU scan kernel, and the fleet
        smoke A/Bs the same cell pair at equal H.  A family added to
        the serving tier must be added to both measurement surfaces
        (and to this pin) in the same PR."""
        assert set(bench.KERNEL_SWEEP_FAMILIES) == {"gru", "ssm"}
        assert set(bench.FLEET_AB_CELLS) == {"gru", "ssm"}


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
