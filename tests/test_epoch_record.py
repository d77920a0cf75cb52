"""The epoch accounted for whole (fmda_tpu/train/epoch_account.py):
``Trainer.fit`` / ``fit_multi`` leave one ``train.epoch`` record an epoch
in the process's epoch ring, its parts tile the epoch, its counts are the
program's counters', it costs clock reads a pass and none a step, and the
same boundaries carry spans with the epoch's index on the profiler's
clock (docs/observability.md "Spans and scopes")."""

import glob
import time

import jax
import pytest

from fmda_tpu.obs import events
from fmda_tpu.obs.events import EventLog, default_epoch_log
from fmda_tpu.obs.registry import default_registry
from fmda_tpu.train.trainer import Trainer
from test_spans import _source, _trainer as _window_trainer
from test_token_train import (
    _model as _decoder_model, _source as _token_source,
    _train as _decoder_train)

PARTS = ("open_s", "run_s", "publish_s")


def _trainer(cell="gru", **train):
    """The tiny trainers of tests/test_spans.py (28 train and 20 eval
    steps a pass in groups of 16) and tests/test_token_train.py."""
    if cell == "decoder":
        return Trainer(_decoder_model(), _decoder_train(**train))
    return _window_trainer(cell, **train)


def _counts(phase):
    reg = default_registry()
    return (reg.counter("train_steps_total", phase=phase).value,
            reg.counter("train_step_calls_total", phase=phase).value)


def _records_of(run):
    """The ``train.epoch`` records ``run()`` leaves in the ring."""
    ring = default_epoch_log()
    before = ring.emitted
    run()
    new = ring.tail(ring.emitted - before)
    assert [e["kind"] for e in new] == ["train.epoch"] * len(new)
    return new


def _parts(record):
    return [record["fit_setup_s"], record["epoch_end_s"]] + [
        record[phase][part] for phase in ("train", "eval")
        if phase in record for part in PARTS]


def _run(trainer, how, epochs):
    if how == "fit_multi":
        return trainer.fit_multi(
            {"A": _source(seed=0), "B": _source(seed=1)}, epochs=epochs)
    source = (_token_source() if trainer.model_cfg.cell == "decoder"
              else _source())
    return trainer.fit(source, epochs=epochs)


@pytest.mark.parametrize("cell,how", [
    ("gru", "fit"), ("ssm", "fit"), ("decoder", "fit"),
    ("gru", "fit_multi"), ("ssm", "fit_multi")])
def test_parts_tile_the_epoch_and_counts_are_the_counters(cell, how):
    trainer = _trainer(cell)
    before = {p: _counts(p) for p in ("train", "eval")}
    first, second = _records_of(lambda: _run(trainer, how, 2))
    after = {p: _counts(p) for p in ("train", "eval")}
    for rec in (first, second):
        assert all(v >= 0.0 for v in _parts(rec))
        # each part begins where the one before it ended
        assert abs(sum(_parts(rec)) - rec["total_s"]) < 1e-6
        assert rec["total_s"] == rec["end"] - rec["start"]
        assert rec["warm"] is False
    assert (first["epoch"], second["epoch"]) == (0, 1)
    # fit's set-up belongs to the call's first epoch; the second begins
    # where the first ended
    assert first["fit_setup_s"] > 0.0 and second["fit_setup_s"] == 0.0
    assert second["start"] == first["end"]
    for phase in ("train", "eval"):
        steps = sum(r[phase]["steps"] for r in (first, second))
        calls = sum(r[phase]["calls"] for r in (first, second))
        assert (steps, calls) == (after[phase][0] - before[phase][0],
                                  after[phase][1] - before[phase][1])
        assert first[phase]["steps"] == second[phase]["steps"] > 0
        # the placed-batch cache is fit's; fit_multi asks none
        assert (first[phase]["cache"], second[phase]["cache"]) == (
            ("miss", "hit") if how == "fit" else (None, None))
    # the first epoch compiled both steps, the second nothing
    assert (first["compiles"], second["compiles"]) == (2, 0)


@pytest.mark.parametrize("case", ["val_size_0", "no_batches"])
def test_an_epoch_without_validation_steps_has_no_eval_part(
        case, caplog, monkeypatch):
    """``val_size=0`` with every chunk in training (a continuous
    fine-tune round) runs no validation pass, and validation chunks that
    give no batches a pass without steps.  Either leaves a record with no
    ``eval`` part that still tiles, and raises nothing."""
    if case == "val_size_0":
        trainer = _trainer(val_size=0.0, test_size=0.0)
    else:
        trainer = _trainer()
        batches = trainer.task.batches
        monkeypatch.setattr(
            trainer.task, "batches",
            lambda ds, idx: batches(ds, idx) if idx < 3 else [])
    (rec,) = _records_of(lambda: trainer.fit(_source(), epochs=1))
    assert "eval" not in rec and rec["train"]["steps"] > 0
    assert abs(sum(_parts(rec)) - rec["total_s"]) < 1e-6
    assert ("pass produced no batches" in caplog.text) == (
        case == "no_batches")


def test_the_ring_is_bounded(monkeypatch):
    assert default_epoch_log().capacity >= 4096
    small = EventLog(capacity=3)
    monkeypatch.setattr(events, "_DEFAULT_EPOCHS", small)
    _trainer().fit(_source(), epochs=5)
    assert (len(small), small.emitted) == (3, 5)
    assert [e["epoch"] for e in small.tail()] == [2, 3, 4]


def test_an_epoch_that_compiles_after_mark_warm_says_so():
    """One chunk is all training: no validation pass, so the eval step
    is first compiled by a later fit — after ``mark_warm``, in an epoch
    whose record counts it."""
    trainer = _trainer()
    (cold,) = _records_of(lambda: trainer.fit(_source(n=30), epochs=1))
    assert "eval" not in cold and (cold["warm"], cold["compiles"]) == (
        False, 1)
    trainer.mark_warm()
    recompiled, quiet = _records_of(
        lambda: trainer.fit(_source(), epochs=2))
    assert (recompiled["warm"], recompiled["compiles"]) == (True, 1)
    assert (quiet["warm"], quiet["compiles"]) == (True, 0)
    assert trainer.unexpected_recompiles == 1


def test_the_account_reads_the_clock_once_a_pass_never_a_step(monkeypatch):
    """With a counting clock in place of ``time.perf_counter`` a pass
    reads it twice a pull, as before the account (the stall histogram's),
    and three times more a pass, whatever the number of calls."""
    from fmda_tpu.obs.device import default_ledger

    trainer = _trainer()
    ds = trainer.task.dataset(_source())
    batches = list(trainer._chunk_batches(ds, 0))  # placed, one a call
    state = trainer.init_state(jax.random.PRNGKey(0))
    trainer._run_batches(state, (batches,), None, False)  # compile
    reads = []
    real = time.perf_counter

    def counting():
        reads.append(None)
        return real()

    # the tracked step times its own call where the ledger is on
    monkeypatch.setattr(default_ledger(), "enabled", False)
    monkeypatch.setattr(time, "perf_counter", counting)
    per_pass = []
    for calls in (2, len(batches)):
        del reads[:]
        account = {}
        trainer._run_batches(
            state, (batches[:calls],), None, False, account)
        assert (account["steps"], account["calls"]) == (calls, calls)
        per_pass.append(len(reads) - 2 * (calls + 1))
    assert len(batches) > 2
    assert per_pass == [3, 3]


@pytest.fixture(scope="module")
def profiled_epochs(tmp_path_factory):
    """Two epochs of one fit under the profiler: the step thread's
    pass-level spans as (start, end, name, epoch argument)."""
    from jax.profiler import ProfileData

    trainer = _trainer()
    source = _source()
    state, _, _ = trainer.fit(source, epochs=1)  # epoch 0: compiles
    out = str(tmp_path_factory.mktemp("profile"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(out, profiler_options=opts)
    try:
        trainer.fit(source, epochs=2, initial_state=state)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{out}/plugins/profile/*/*.xplane.pb")
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = sorted(
                (e.start_ns, e.start_ns + e.duration_ns, e.name,
                 dict(e.stats).get("epoch"))
                for e in line.events
                if e.name.startswith("fit_") or "_pass_" in e.name)
            if spans:
                lines.append(spans)
    return lines


def test_pass_level_spans_stand_in_order_with_the_epochs_index(
        profiled_epochs):
    (spans,) = profiled_epochs  # one thread: the step loop's
    one_epoch = [
        "train_pass_open", "train_pass_drain", "train_pass_publish",
        "eval_pass_open", "eval_pass_drain", "eval_pass_publish",
        "fit_epoch_end"]
    assert [n for _, _, n, _ in spans] == ["fit_setup"] + 2 * one_epoch
    # the set-up and the first epoch carry 1 (epoch 0 ran before the
    # capture), the second epoch 2
    assert [e for _, _, _, e in spans] == [1] * 8 + [2] * 7
    for (_, end, a, _), (start, _, b, _) in zip(spans, spans[1:]):
        assert end <= start, (a, b)


def test_the_drain_span_is_the_fetch_alone(monkeypatch):
    """``task.publish`` and ``epoch_metrics`` run under
    ``<phase>_pass_publish``, no longer under the drain."""
    from fmda_tpu.utils import tracing

    trainer = _trainer()
    open_spans, seen = [], []
    real = tracing.span

    class Spy:
        def __init__(self, name, **args):
            self.name, self.inner = name, real(name, **args)

        def __enter__(self):
            open_spans.append(self.name)
            return self.inner.__enter__()

        def __exit__(self, *exc):
            open_spans.pop()
            return self.inner.__exit__(*exc)

    monkeypatch.setattr(tracing, "span", Spy)
    for name in ("publish", "epoch_metrics"):
        method = getattr(trainer.task, name)
        monkeypatch.setattr(
            trainer.task, name,
            lambda *a, _m=method, _n=name: (
                seen.append((_n, tuple(open_spans))), _m(*a))[1])
    real_get = jax.device_get
    monkeypatch.setattr(jax, "device_get", lambda x: (
        seen.append(("device_get", tuple(open_spans))), real_get(x))[1])
    trainer.fit(_source(), epochs=1)
    under = {name: {spans for n, spans in seen if n == name}
             for name in ("device_get", "publish", "epoch_metrics")}
    assert under["device_get"] == {
        ("train_pass_drain",), ("eval_pass_drain",)}
    assert under["publish"] == under["epoch_metrics"] == {
        ("train_pass_publish",), ("eval_pass_publish",)}


def test_span_hands_its_arguments_to_the_annotation():
    from fmda_tpu.utils import tracing

    assert isinstance(tracing.span("x", epoch=3),
                      jax.profiler.TraceAnnotation)


def test_an_applications_events_carry_the_epoch_records():
    """An ``Observability`` mirrors the process's epoch ring, so the
    records are on its ``/events``; the ring itself keeps them too."""
    from fmda_tpu.obs import Observability

    ring = default_epoch_log()
    previous = ring.mirror
    obs = Observability()
    try:
        assert ring.mirror is obs.events
        (rec,) = _records_of(lambda: _trainer().fit(_source(), epochs=1))
        (mirrored,) = [e for e in obs.events.tail()
                       if e["kind"] == "train.epoch"]
        assert mirrored["total_s"] == rec["total_s"]
        assert mirrored["train"] == rec["train"]
        assert '"kind": "train.epoch"' in obs.events.to_jsonl()
    finally:
        obs.close()
        ring.mirror = previous


# ---------------------------------------------------------------------------
# what the epoch's compiles were made of, and what its steps hold (PR 51)
# ---------------------------------------------------------------------------

COMPILE_PARTS = ("trace_s", "lower_s", "backend_compile_s", "cache_hits",
                 "cache_misses", "cache_retrieval_s")


@pytest.mark.parametrize("cell,how", [
    ("gru", "fit"), ("decoder", "fit"), ("gru", "fit_multi")])
def test_compile_parts_are_the_first_epochs_and_warm_epochs_read_zero(
        cell, how):
    trainer = _trainer(cell)
    source = _token_source() if cell == "decoder" else _source()
    out = []
    first, second = _records_of(
        lambda: out.extend(_run(trainer, how, 2) if how == "fit_multi"
                           else trainer.fit(source, epochs=2)))
    trainer.mark_warm()
    # the warm epochs resume, as a benchmark's window does (a fresh
    # ``fit`` initialises a state, and that compiles: the account says so)
    warm = [] if how == "fit_multi" else _records_of(
        lambda: trainer.fit(source, epochs=2, initial_state=out[0],
                            dataset=out[2]))
    assert tuple(first["compile_parts"]) == COMPILE_PARTS
    parts = first["compile_parts"]
    assert parts["trace_s"] > 0 and parts["lower_s"] > 0
    assert parts["backend_compile_s"] > 0
    # the three times lie inside the epoch, on the thread that ran it
    assert (parts["trace_s"] + parts["lower_s"]
            + parts["backend_compile_s"]) <= first["total_s"]
    # no persistent cache under the pinned CPU: neither hit nor miss
    assert (parts["cache_hits"], parts["cache_misses"]) == (0, 0)
    zeros = dict.fromkeys(COMPILE_PARTS, 0)
    for quiet in [second] + warm:
        assert quiet["compiles"] == 0
        assert quiet["compile_parts"] == zeros


def test_a_compile_leaves_nothing_foreign_in_the_epoch_ring():
    """The guard for ``train_pass_ms_per_step``, ``eval_pass_ms_per_step``,
    ``eval_pass_share`` and ``epoch_turnaround_share``: their reader
    refuses a ring whose ``emitted`` exceeds its count of ``train.epoch``
    records, so the ledger's compile records must never land there —
    not even with an event log attached to the ledger."""
    from fmda_tpu.obs.device import default_ledger

    ring, led = default_epoch_log(), default_ledger()
    was_events, led.events = led.events, EventLog()
    emitted, held = ring.emitted, len(ring.tail())
    try:
        trainer = _trainer()
        trainer.fit(_source(), epochs=2)
        trainer.mark_warm()
        compiled = [e for e in led.events.tail()
                    if e["kind"] == "device.compile"]
    finally:
        led.events = was_events
    assert {e["program"] for e in compiled} == {"train_step", "eval_step"}
    new = ring.tail()[held:]
    assert ring.emitted - emitted == len(new) == 2
    assert {e["kind"] for e in ring.tail()} == {"train.epoch"}
    assert ring.emitted == len(ring.tail())


@pytest.mark.parametrize("cell", ["gru", "decoder"])
def test_step_memory_is_asked_at_mark_warm_and_compiles_nothing(cell):
    """``mark_warm`` asks the programs ``fit`` ran what they hold; the
    answer is in the ledger's compile records, which outlive the
    trainer, and asking is never a compile, expected or not."""
    import gc

    from fmda_tpu.obs.device import default_ledger

    seen = []

    def listen(event, duration, fun_name=None, **_kw):
        if event.endswith(("jaxpr_to_mlir_module_duration",
                           "backend_compile_duration")):
            seen.append((event, fun_name))

    trainer = _trainer(cell)
    _run(trainer, "fit", 2)
    counts = trainer.compile_counts
    assert counts == {"train_step": 1, "eval_step": 1}
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        trainer.mark_warm()
        held = trainer.step_memory()
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert seen == []
    assert trainer.compile_counts == counts
    assert trainer.unexpected_recompiles == 0
    again = trainer.step_memory()
    for kind in ("train_step", "eval_step"):
        assert held[kind] is again[kind]
        assert held[kind]["temp_bytes"] > 0
        assert held[kind]["reserved_bytes"] >= held[kind]["argument_bytes"]
        assert held[kind]["asked"]["backend_compiles"] == 0
    # the train step donates its state and totals; the eval step its
    # totals alone
    assert held["train_step"]["alias_bytes"] > held["eval_step"]["alias_bytes"]
    _run(trainer, "fit", 1)
    assert trainer.compile_counts == counts
    assert trainer.unexpected_recompiles == 0
    ring = [r for r in default_ledger().compile_records()
            if r["memory"] is held["train_step"]]
    assert [r["program"] for r in ring] == ["train_step"]
    del trainer
    gc.collect()
    assert default_ledger().compile_records()[-2:] != []
    assert any(r["memory"] is held["eval_step"]
               for r in default_ledger().compile_records())


def test_step_memory_before_any_step_is_none_and_costs_nothing():
    trainer = _trainer()
    trainer.mark_warm()
    assert trainer.step_memory() == {"train_step": None, "eval_step": None}
