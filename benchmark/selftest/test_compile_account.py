"""The two readers of the compiled train step's memory
(``harness/compile_account.py``) against hand-built compile records, the
manifest's entries against the readers, and the tiny training cells of
the rehearsal: the numbers are the program the window ran, and where the
driver reads the same executable itself (``notes.compiled_step_bytes``)
the two agree to the byte."""

import json

import pytest

from benchmark.harness import catalog, compile_account
from benchmark.selftest.test_kda_token_rehearsal import _root
from benchmark.selftest.test_rehearsal import rehearsal_result, run_cell

NAMES = ("train_step_temp_hbm_mb", "train_step_reserved_hbm_mb")
MEMORY = {"argument_bytes": 7_230_000_000, "output_bytes": 7_230_000_100,
          "alias_bytes": 7_230_000_000, "temp_bytes": 7_740_000_000,
          "code_bytes": 1_000_000, "reserved_bytes": 14_971_000_100,
          "asked": {"s": 0.0004, "lowerings": 0, "backend_compiles": 0}}


def _compile(program, memory=None, **parts):
    return {"program": program, "signature": "()", "compile_s": 60.0,
            "trace_s": 9.6, "lower_s": 3.1, "backend_compile_s": 45.0,
            "rest_s": 2.3, "cache": "miss", "cache_retrieval_s": 0.0,
            "compile_time_saved_s": 0.0, "backend": "tpu",
            "unexpected": False, "cache_size_before": 0, "ts": 0.0,
            "memory": memory, **parts}


class _Ledger:
    def __init__(self, records):
        self._records = records

    def compile_records(self):
        return list(self._records)

    def untracked(self):
        return {"trace_s": 4.0, "by_name": {"decoder_init": {
            "trace_s": 4.0, "lower_s": 0.5, "backend_compile_s": 3.0,
            "events": 3}}}


@pytest.fixture
def ledger(monkeypatch):
    """Stand a list of compile records in for the process's ledger."""
    def put(records):
        monkeypatch.setattr(
            compile_account, "process_ledger",
            lambda: None if records is None else _Ledger(records))
    return put


def _readers():
    return [catalog.load_layer_metrics()[name].module for name in NAMES]


def test_the_readers_give_the_windows_train_step(ledger, capsys):
    """The window's trainer was asked at ``mark_warm``; the single
    program the comparisons compile afterwards was not."""
    ledger([_compile("train_step", MEMORY), _compile("eval_step", MEMORY),
            _compile("train_step", None, unexpected=True, cache="hit")])
    rec = {"window_s": 20.0}
    temp, reserved = _readers()
    assert temp.read(rec) == 7740.0
    assert reserved.read(rec) == 14971.0001
    (line,) = [json.loads(ln) for ln in capsys.readouterr().err.splitlines()]
    said = line["compile_account"]
    assert said["programs"]["train_step"]["compiles"] == 2
    first, second = said["programs"]["train_step"]["each"]
    assert first["trace_s"] + first["lower_s"] + first["backend_compile_s"] \
        + first["rest_s"] == pytest.approx(first["compile_s"])
    assert (first["cache"], second["cache"]) == ("miss", "hit")
    assert said["untracked"]["by_name"]["decoder_init"]["trace_s"] == 4.0
    assert said["window_memory"]["train_step"] == MEMORY
    # said once a run, whichever reader asks next
    assert temp.read(rec) == 7740.0
    assert capsys.readouterr().err == ""


@pytest.mark.parametrize("case", ["no_ledger", "nobody_asked"])
def test_a_program_without_the_account_gives_none(case, ledger, capsys):
    """The parent commit: its ledger keeps no compile records (no line),
    or — a program that never calls ``mark_warm`` — none with a
    ``memory`` (the line, and no number)."""
    ledger({"no_ledger": None,
            "nobody_asked": [_compile("train_step"),
                             _compile("eval_step")]}[case])
    for reader in _readers():
        assert reader.read({"window_s": 20.0}) is None
    said = capsys.readouterr().err
    assert (said == "") == (case == "no_ledger")


def test_the_program_of_this_commit_has_the_ledger():
    assert compile_account.process_ledger() is not None


def test_a_ledger_without_compile_records_is_no_ledger(monkeypatch):
    from fmda_tpu.obs import device

    class Old:
        enabled = True

    monkeypatch.setattr(device, "default_ledger", Old)
    assert compile_account.process_ledger() is None


@pytest.mark.parametrize("name", NAMES)
def test_manifest_lists_the_metric_as_its_reader_states_it(name):
    manifest = catalog.load_manifest()
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == name]
    assert entry == {
        "name": name, "unit": "MB", "better": "lower",
        "source": "program_counter", "layer": "device",
        "moves": "train_samples_per_s",
        "workloads": [w["name"] for w in manifest["workloads"]]}
    reader = catalog.load_layer_metrics()[name]
    assert reader.entry() == {k: v for k, v in entry.items()
                              if k != "workloads"}
    (peak,) = [m for m in manifest["per_layer"]
               if m["name"] == "train_peak_hbm_mb"]
    assert {k: peak[k] for k in ("unit", "layer", "source", "moves")} == {
        k: entry[k] for k in ("unit", "layer", "source", "moves")}


def _lines(proc):
    return [json.loads(ln) for ln in proc.stderr.splitlines()
            if ln.startswith("{")]


def _account_of(proc):
    (said,) = [ln["compile_account"] for ln in _lines(proc)
               if "compile_account" in ln]
    return said


def _check_the_line(said):
    """What every traced run's line has to hold."""
    for name in ("train_step", "eval_step"):
        (first, *later) = said["programs"][name]["each"]
        assert first["trace_s"] > 0 and first["backend_compile_s"] > 0
        assert (first["trace_s"] + first["lower_s"]
                + first["backend_compile_s"] + first["rest_s"]
                ) == pytest.approx(first["compile_s"], abs=1e-4)
        assert first["unexpected"] is False
    cold, *cached = said["setup_epochs"]
    parts = cold["compile_parts"]
    assert cold["compiles"] == 2
    assert 0 < (parts["trace_s"] + parts["lower_s"]
                + parts["backend_compile_s"]) <= cold["total_s"]
    for epoch in cached:
        assert not any(epoch["compile_parts"].values())
    assert said["warm_epochs"] > 0
    assert said["warm_epochs_that_compiled"] == 0
    for held in said["window_memory"].values():
        assert held["asked"]["lowerings"] == 0
        assert held["asked"]["backend_compiles"] == 0
        assert held["asked"]["s"] < 1.0


@pytest.mark.parametrize("cell", ["selftest_gru_train", "selftest_ssm_train"])
def test_a_traced_training_rehearsal_reads_the_steps_memory(cell):
    proc = run_cell(cell, trace=1)
    result = rehearsal_result(proc)
    assert result["correct"] is True, proc.stderr[-3000:]
    said = _account_of(proc)
    _check_the_line(said)
    held = said["window_memory"]["train_step"]
    metrics = result["metrics"]
    assert metrics["train_step_temp_hbm_mb"] == {
        "value": held["temp_bytes"] / 1e6, "unit": "MB"}
    assert metrics["train_step_reserved_hbm_mb"] == {
        "value": held["reserved_bytes"] / 1e6, "unit": "MB"}
    assert 0 < held["temp_bytes"] < held["reserved_bytes"]
    # the four readers of the epoch account still read: nothing foreign
    # went into the epoch ring
    for name in ("train_pass_ms_per_step", "eval_pass_ms_per_step",
                 "eval_pass_share", "epoch_turnaround_share"):
        assert metrics[name]["value"] > 0


def test_an_untraced_rehearsal_says_no_account():
    proc = run_cell("selftest_gru_train", trace=0)
    assert rehearsal_result(proc)["correct"] is True
    assert not [ln for ln in _lines(proc) if "compile_account" in ln]


def test_the_kda_rehearsal_reads_the_program_its_window_ran(tmp_path):
    """``drivers/train_kda_token_epochs.py`` lowers the **single** train
    step itself after the window (``notes.compiled_step_bytes``).  A tiny
    cell's window runs the grouped program (``trainer.group_size``: only
    a step of 256 MB or more runs alone, as every decoder cell of record
    does), so here the two are different executables over the same state:
    what they donate and return agrees to the byte, the arguments differ
    by the group's batches, and the readers give the grouped one's."""
    proc = run_cell("tiny_kda_token_train", trace=1,
                    extra_env=_root(tmp_path))
    result = rehearsal_result(proc)
    assert result["correct"] is True, proc.stderr[-4000:]
    said = _account_of(proc)
    _check_the_line(said)
    held = said["window_memory"]["train_step"]
    (notes,) = [ln["notes"] for ln in _lines(proc) if "notes" in ln]
    theirs = notes["compiled_step_bytes"]
    assert set(theirs) == {"argument", "temp", "output", "alias"}
    assert (held["alias_bytes"], held["output_bytes"]) == (
        theirs["alias"], theirs["output"])
    assert held["argument_bytes"] > theirs["argument"]
    # the single program was compiled by the comparisons, after
    # ``mark_warm``, and nobody asked it anything
    single = said["programs"]["train_step"]["each"][1:]
    assert single and all(c["unexpected"] for c in single)
    metrics = result["metrics"]
    assert metrics["train_step_temp_hbm_mb"]["value"] == (
        held["temp_bytes"] / 1e6) > 0
    assert metrics["train_step_reserved_hbm_mb"]["value"] == (
        held["reserved_bytes"] / 1e6) > 0


def test_the_ledgers_reading_is_the_drivers_to_the_byte():
    """The same executable read both ways: the program's own record of
    the single train step (``TrackedFunction.memory``, from the signature
    kept at its compile) and the driver's ``compiled_step_bytes`` (a
    lowering from the live arguments, through the private ``_jit``)."""
    import jax

    from benchmark.drivers.train_hybrid_token_epochs import (
        compiled_step_bytes)
    from benchmark.harness.token_corpus import make_token_stream
    from benchmark.selftest.test_kda_token_rehearsal import CONFIG, SEQ
    from fmda_tpu.config import config_from_dict
    from fmda_tpu.data.source import TokenArraySource
    from fmda_tpu.train.trainer import Trainer

    cfg = config_from_dict(CONFIG["framework"])
    stream = make_token_stream(10 * SEQ + 1, 256, 5, doc_median_tokens=40.0)
    trainer = Trainer(cfg.model, cfg.train)
    rng = jax.random.PRNGKey(5)
    state, _, dataset = trainer.fit(
        TokenArraySource(stream, 256), rng=rng, epochs=1)
    train, _, _ = dataset.split(cfg.train.val_size, cfg.train.test_size)
    batch = next(iter(trainer._chunk_batches(dataset, train[0])))
    state, _ = trainer.single_step(state, batch, rng)
    compiled = trainer.compile_counts
    theirs = compiled_step_bytes(trainer, state, batch, rng)
    ours = trainer._train_step.memory()
    assert {k: ours[k + "_bytes"] for k in theirs} == theirs
    assert ours["asked"]["backend_compiles"] == 0
    assert trainer.compile_counts == compiled
