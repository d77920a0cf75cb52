"""``run.py`` under ``JAX_PLATFORMS=cpu``: each driver runs end to end on
the tiny cells kept here — found like any other cell, no mode or flag of
``run.py`` — and then exits non-zero without a result line.  A cell of
record refuses at once.  A cell, a traffic file, a configuration and a
layer metric dropped into a temporary directory are picked up with no
edit."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from benchmark.harness import catalog

RUN = os.path.join(catalog.BENCH_DIR, "run.py")


def run_cell(name, *, seconds=2, trace=0, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, RUN, "--workload", name, "--seed", "3",
         "--seconds", str(seconds), "--trace", str(trace)],
        capture_output=True, text=True, env=env, cwd=catalog.CHECKOUT_DIR,
        timeout=600)


def rehearsal_result(proc):
    for line in reversed(proc.stderr.splitlines()):
        if line.startswith('{"rehearsal_result"'):
            return json.loads(line)["rehearsal_result"]
    raise AssertionError("no rehearsal result:\n" + proc.stderr[-3000:])


@pytest.mark.parametrize("cell,metric", [
    ("selftest_gru_open", "tick_p99_ms"),
    ("selftest_ssm_open", "tick_p50_ms"),
    ("selftest_gru_backlog", "ticks_per_s"),
    ("selftest_gru_train", "train_samples_per_s"),
    ("selftest_ssm_train", "train_samples_per_s")])
def test_each_driver_runs_end_to_end_then_prints_no_result(cell, metric):
    proc = run_cell(cell)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""  # no result line without a TPU
    result = rehearsal_result(proc)
    assert result["correct"] is True, proc.stderr[-3000:]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["metrics"][metric]["value"] > 0
    assert result["metrics"]["setup_s"]["value"] > 0
    assert result["device"]["platform"] == "cpu"


def test_a_traced_rehearsal_reports_layer_metrics_only():
    result = rehearsal_result(run_cell("selftest_gru_backlog", trace=1))
    assert "ticks_per_s" not in result["metrics"]
    assert result["metrics"]["backlog_flush_fill"]["value"] == 48.0
    assert result["metrics"]["backlog_padded_lane_share"]["value"] == 25.0
    assert "flush_fill" not in result["metrics"]  # moves an open metric


def notes_of(proc):
    for line in proc.stderr.splitlines():
        if line.startswith('{"notes"'):
            return json.loads(line)["notes"]
    raise AssertionError("no notes:\n" + proc.stderr[-3000:])


@pytest.mark.parametrize("cell", ["selftest_gru_train", "selftest_ssm_train"])
def test_a_traced_training_rehearsal_reports_the_train_names(cell):
    proc = run_cell(cell, trace=1)
    result = rehearsal_result(proc)
    assert result["correct"] is True, proc.stderr[-3000:]
    assert "train_samples_per_s" not in result["metrics"]
    assert result["metrics"]["input_stall_share"]["value"] >= 0.0
    # what the step thread's spans give is read off the TPU too
    for name in ("train_dispatch_us", "train_fold_us",
                 "train_next_batch_us", "train_loop_self_us"):
        assert result["metrics"][name]["value"] > 0.0
    # no peak to hold a rate against off the TPU and no device plane in
    # the trace, and the names that move a serving metric stay out of a
    # training cell
    for name in ("train_mfu", "train_step_dev_ms", "train_device_idle_share",
                 "train_recurrence_dev_share", "device_idle_share"):
        assert name not in result["metrics"]
    # the slice is counted in steps; a 69-step pass has no room for the
    # margins, and the run says so
    notes = notes_of(proc)
    assert notes["trace_steps"] == 6
    assert 7 <= notes["traced_steps"] <= 69
    assert notes["trace_slice_in_one_pass"] is False
    assert 0.0 < notes["trace_slice_s"] < 2.0
    assert notes["tail_epochs"] >= 1
    assert notes["trace_stop_cost_s"] > 0.0
    assert '{"traced_run_s"' in proc.stderr


def test_the_kept_serving_cells_are_found_but_are_not_of_record():
    record = {w["name"] for w in catalog.load_manifest()["workloads"]}
    for name in ("gru_serve_open", "ssm_serve_open", "gru_serve_backlog"):
        cell = catalog.find_cell(name)
        assert name not in record and not cell.of_record
        traffic = catalog.load_traffic(cell.traffic)
        assert catalog.load_driver(traffic["kind"]).END_TO_END
        assert catalog.load_config(cell.config)["framework"]["runtime"]


def test_a_cell_of_record_refuses_at_once_without_a_tpu():
    proc = run_cell(catalog.load_manifest()["workloads"][0]["name"])
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "runs on a TPU" in proc.stderr


def test_dropped_in_files_are_picked_up_without_an_edit(tmp_path):
    for d in ("configs", "traffic", "layer_metrics"):
        (tmp_path / d).mkdir()
    tiny = json.load(open(os.path.join(
        catalog.BENCH_DIR, "selftest", "configs", "tiny_ssm.json")))
    tiny["name"] = "dropped_cfg"
    (tmp_path / "configs" / "dropped_cfg.json").write_text(json.dumps(tiny))
    (tmp_path / "traffic" / "dropped_mix.json").write_text(json.dumps(
        {"kind": "backlog", "sessions": 20}))
    (tmp_path / "cells.json").write_text(json.dumps({"workloads": [
        {"name": "dropped_cell", "config": "dropped_cfg",
         "traffic": "dropped_mix", "chips": 1, "why": "dropped in"}]}))
    (tmp_path / "layer_metrics" / "dropped_metric.py").write_text(
        textwrap.dedent('''
        NAME = "dropped_metric"
        UNIT = "flushes"
        LAYER = "admission queue"
        MOVES = "ticks_per_s"

        def read(record):
            return record["counters"]["flushes"]
        '''))
    # one reader under a name per end-to-end metric it moves: only the
    # name whose moved metric this cell reports appears
    (tmp_path / "layer_metrics" / "dropped_pair.py").write_text(
        textwrap.dedent('''
        NAME = "dropped_pair"
        UNIT = "ticks"
        LAYER = "admission queue"
        MOVES = {"tick_p99_ms": "dropped_pair",
                 "ticks_per_s": "backlog_dropped_pair"}

        def read(record):
            return record["counters"]["ticks_served"]
        '''))
    proc = run_cell("dropped_cell", seconds=1, trace=1,
                    extra_env={catalog.ROOTS_ENV: str(tmp_path)})
    result = rehearsal_result(proc)
    assert result["correct"] is True, proc.stderr[-3000:]
    assert result["metrics"]["dropped_metric"]["value"] > 0
    assert result["metrics"]["dropped_metric"]["unit"] == "flushes"
    assert result["metrics"]["backlog_flush_fill"]["value"] == 20.0
    assert result["metrics"]["backlog_dropped_pair"]["value"] > 0
    assert "dropped_pair" not in result["metrics"]
