"""The four readers of the trainer's epoch account
(``harness/epoch_account.py``) against a hand-built list of records, the
manifest's entries against the readers, and the tiny training cells of
the rehearsal: the epochs the readers pick are the driver's window's."""

import json

import pytest

from benchmark.harness import catalog, epoch_account
from benchmark.selftest.test_rehearsal import rehearsal_result, run_cell

NAMES = ("train_pass_ms_per_step", "eval_pass_ms_per_step",
         "eval_pass_share", "epoch_turnaround_share")


def _epoch(start, *, train_run, eval_run, warm, setup=0.001, steps=(100, 20),
           compiles=0):
    """An epoch of 0.002 s of bookkeeping around its two passes (or one,
    with ``eval_run`` None)."""
    rec = {"kind": "train.epoch", "ts": 0.0, "epoch": 0, "start": start,
           "fit_setup_s": setup, "epoch_end_s": 0.0002, "warm": warm,
           "compiles": compiles,
           "train": {"open_s": 0.0003, "run_s": train_run,
                     "publish_s": 0.0001, "steps": steps[0], "calls": 7,
                     "cache": "hit"}}
    total = setup + 0.0002 + 0.0004 + train_run
    if eval_run is not None:
        rec["eval"] = {"open_s": 0.0003, "run_s": eval_run,
                       "publish_s": 0.0001, "steps": steps[1], "calls": 2,
                       "cache": "hit"}
        total += 0.0004 + eval_run
    rec.update(total_s=total, end=start + total)
    return rec


def _run_of_record():
    """Two set-up epochs, cold (the second compiled nothing, but
    ``mark_warm`` came after it); five window epochs from 10.0 on with
    train passes of 0.10 .. 0.14 s; two tail epochs after the reference
    check, the first of them slow under the profiler."""
    records = [_epoch(1.0, train_run=3.0, eval_run=1.0, warm=False,
                      compiles=2),
               _epoch(6.0, train_run=0.1, eval_run=0.01, warm=False)]
    t = 10.0
    for k in (2, 0, 4, 1, 3):
        records.append(_epoch(t + 1e-5, train_run=0.10 + 0.01 * k,
                              eval_run=0.010 + 0.001 * k, warm=True))
        t = records[-1]["end"]
    window_s = t - 10.0 + 2e-5
    records.append(_epoch(t + 3.0, train_run=0.5, eval_run=0.05, warm=True))
    records.append(_epoch(t + 4.0, train_run=0.1, eval_run=0.01, warm=True))
    return records, window_s


def test_the_windows_epochs_are_picked():
    records, window_s = _run_of_record()
    picked = epoch_account.window_epochs(records, len(records), window_s)
    assert picked == records[2:7]
    # a longer window reaches no further than the tail's slow epoch lets it
    assert epoch_account.window_epochs(
        records, len(records), window_s + 1.0) == records[2:7]
    assert epoch_account.window_epochs(
        records, len(records), window_s + 3.6) == records[2:8]


def test_medians_are_over_the_windows_epochs():
    records, window_s = _run_of_record()
    got = epoch_account.medians(records[2:7])
    # the median epoch of each metric is the one with k == 2
    mid = records[2]
    assert got["train_pass_ms_per_step"] == pytest.approx(1.2)
    assert got["eval_pass_ms_per_step"] == pytest.approx(0.6)
    assert got["eval_pass_share"] == pytest.approx(
        100 * 0.012 / mid["total_s"])
    assert got["epoch_turnaround_share"] == pytest.approx(
        100 * 0.002 / mid["total_s"])
    assert mid["total_s"] == pytest.approx(0.002 + 0.12 + 0.012)


def test_epochs_without_validation_read_no_eval_metric():
    records = [_epoch(1.0 + k, train_run=0.1, eval_run=None, warm=True)
               for k in range(3)]
    got = epoch_account.medians(records)
    assert got["eval_pass_ms_per_step"] is None
    assert got["eval_pass_share"] is None
    assert got["train_pass_ms_per_step"] == pytest.approx(1.0)
    assert got["epoch_turnaround_share"] == pytest.approx(
        100 * 0.0016 / 0.1016)


@pytest.fixture
def ring(monkeypatch):
    """Stand a list of records in for the process's epoch ring."""
    def put(value):
        monkeypatch.setattr(epoch_account, "ring_records", lambda: value)
    return put


@pytest.mark.parametrize("name", NAMES)
def test_each_reader_gives_its_median_of_the_window(name, ring, capsys):
    records, window_s = _run_of_record()
    ring((records, len(records)))
    reader = catalog.load_layer_metrics()[name].module
    rec = {"window_s": window_s, "valid_windows_per_epoch": 1000,
           "end_to_end": {"train_samples_per_s": 5 * 1000 / window_s}}
    assert reader.read(rec) == epoch_account.medians(records[2:7])[name]
    (line,) = [json.loads(ln) for ln in capsys.readouterr().err.splitlines()]
    said = line["epoch_account"]
    assert said["epochs_picked"] == 5
    assert said["epochs_by_headline"] == pytest.approx(5.0)
    assert said["samples_per_s_by_records"] == pytest.approx(
        said["samples_per_s_headline"], rel=1e-3)
    assert said["median_parts"]["train"]["steps"] == 100
    # said once a run, whichever reader asks next
    assert reader.read(rec) is not None
    assert capsys.readouterr().err == ""


@pytest.mark.parametrize("case", ["no_ring", "no_record", "none_warm"])
def test_a_missing_record_gives_none(case, ring, capsys):
    records, window_s = _run_of_record()
    ring({"no_ring": None, "no_record": ([], 0),
          "none_warm": (records[:2], 2)}[case])
    for name in NAMES:
        reader = catalog.load_layer_metrics()[name].module
        assert reader.read({"window_s": window_s}) is None
    assert capsys.readouterr().err == ""


def test_a_window_that_outgrew_the_ring_gives_none_and_a_warning(
        ring, capsys):
    """The ring let go of its oldest records: what is left may begin
    inside the window, so there is no reading, never one from fewer
    epochs."""
    records, window_s = _run_of_record()
    ring((records[3:], len(records)))
    for name in NAMES:
        reader = catalog.load_layer_metrics()[name].module
        assert reader.read({"window_s": window_s}) is None
    lines = [json.loads(ln) for ln in capsys.readouterr().err.splitlines()]
    assert len(lines) == 4 and all(
        "the ring holds 6 of 9 records" in ln["warning"] for ln in lines)


def test_the_program_of_this_commit_has_the_ring():
    records, emitted = epoch_account.ring_records()
    assert emitted >= len(records)


@pytest.mark.parametrize("name", NAMES)
def test_manifest_lists_the_metric_as_its_reader_states_it(name):
    manifest = catalog.load_manifest()
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == name]
    assert entry == {
        "name": name,
        "unit": "ms/step" if name.endswith("per_step") else "%",
        "better": "lower", "source": "program_span", "layer": "epoch loop",
        "moves": "train_samples_per_s",
        "workloads": [w["name"] for w in manifest["workloads"]]}
    reader = catalog.load_layer_metrics()[name]
    assert reader.entry() == {k: v for k, v in entry.items()
                              if k != "workloads"}
    # new entries stand at the end of the list
    assert [m["name"] for m in manifest["per_layer"][-4:]] == list(NAMES)


@pytest.mark.parametrize("cell", ["selftest_gru_train", "selftest_ssm_train"])
def test_traced_training_rehearsal_reports_the_account(cell):
    proc = run_cell(cell, trace=1)
    result = rehearsal_result(proc)
    assert result["correct"] is True
    lines = [json.loads(ln) for ln in proc.stderr.splitlines()
             if ln.startswith("{")]
    (notes,) = [ln["notes"] for ln in lines if "notes" in ln]
    (said,) = [ln["epoch_account"] for ln in lines if "epoch_account" in ln]
    # the readers picked the window's epochs, all of them and no other
    assert said["epochs_picked"] == notes["epochs"]
    assert said["median_parts"]["train"]["steps"] * notes["epochs"] == \
        notes["train_steps"]
    assert said["compiles"] == 0
    # the records alone give the headline, less the driver's own loop
    assert said["samples_per_s_by_records"] == pytest.approx(
        said["samples_per_s_headline"], rel=0.02)
    metrics = result["metrics"]
    for name in NAMES:
        assert metrics[name]["value"] > 0, (name, metrics)
    shares = (metrics["eval_pass_share"]["value"]
              + metrics["epoch_turnaround_share"]["value"])
    assert 0 < shares < 100
