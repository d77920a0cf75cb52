"""``kda_intra_dev_share`` (PR 53): the manifest lists the metric as its
reader states it, for the one cell whose model has a delta-rule walk,
and in the ``train_kda_token_epochs`` rehearsal of
``test_kda_token_rehearsal.py``'s tiny cell a traced run reads it where
it reads ``kda_scan_dev_share``, inside which the scope lies."""

from benchmark.harness import catalog
from benchmark.selftest.test_kda_token_rehearsal import CELL, _root
from benchmark.selftest.test_rehearsal import rehearsal_result, run_cell

NAME = "kda_intra_dev_share"


def test_the_manifest_lists_the_metric_as_its_reader_states_it():
    entry = next(m for m in catalog.load_manifest()["per_layer"]
                 if m["name"] == NAME)
    assert entry.pop("workloads") == [CELL]
    assert catalog.load_layer_metrics()[NAME].entry() == entry == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "train_samples_per_s"}


def test_the_reader_gives_nothing_on_a_record_without_the_scope():
    record = {"end_to_end": {"train_samples_per_s": 1.0},
              "device": {"platform": "tpu", "kind": "TPU v5 lite",
                         "count": 1}}
    assert catalog.load_layer_metrics()[NAME].module.read(record) is None


def test_the_reader_takes_the_scope_in_both_directions(monkeypatch):
    """Device seconds by (program, scope path, operation) as
    ``program_spans`` reduces a trace to them: the scope the rule of
    ``ops/pallas_kda.py`` opens in its forward and in its backward, and
    the ``jnp`` form's under the walk's, all inside ``kda_scan``; the
    evaluation step's are not the train step's."""
    from benchmark.harness import program_spans

    step = "jit_train_step"
    fwd = "jvp(forward)/MoEDecoder/block_0/kda_mixer/kda_scan/while/body/"
    bwd = ("transpose(jvp(forward))/MoEDecoder/block_0/"
           "rematted_computation/kda_mixer/kda_scan/while/body/checkpoint/")
    busy = {
        (step, fwd + "checkpoint/kda_intra/forward", "kda_intra_fwd"): 2.0,
        (step, bwd + "kda_intra/forward", "kda_intra_fwd.1"): 2.0,
        (step, bwd + "kda_intra/backward", "kda_intra_bwd"): 4.0,
        (step, fwd + "checkpoint/kda_solve", "fusion.1"): 6.0,
        (step, bwd + "kda_carry", "fusion.2"): 6.0,
        (step, "jvp(forward)/MoEDecoder/block_0/kda_mixer/kda_proj",
         "fusion.3"): 20.0,
        (step, "optimizer", "fusion.4"): 40.0,
        ("jit_eval_step", fwd + "kda_intra", "kda_intra_fwd"): 50.0,
    }
    monkeypatch.setattr(program_spans, "for_record",
                        lambda record: record["program_spans"])
    metrics = catalog.load_layer_metrics()
    record = {"end_to_end": {"train_samples_per_s": 1.0},
              "device": {"platform": "tpu", "kind": "TPU v5 lite",
                         "count": 1},
              "program_spans": {"busy_by_scope": busy}}
    got = {name: metrics[name].module.read(record) for name in (
        NAME, "kda_scan_dev_share", "kda_mixer_dev_share")}
    assert got == {NAME: 10.0, "kda_scan_dev_share": 25.0,
                   "kda_mixer_dev_share": 50.0}


def test_the_rehearsal_reads_the_share_where_it_reads_the_walks(tmp_path):
    """Off a TPU a trace has no device plane, and neither share is read;
    with one, the pairwise products' share lies inside the walks'."""
    proc = run_cell("tiny_kda_token_train", trace=1,
                    extra_env=_root(tmp_path))
    result = rehearsal_result(proc)
    assert result["correct"] is True, proc.stderr[-4000:]
    metrics = result["metrics"]
    assert (NAME in metrics) == ("kda_scan_dev_share" in metrics)
    if NAME in metrics:
        intra, scan = metrics[NAME], metrics["kda_scan_dev_share"]
        assert intra["unit"] == scan["unit"] == "%"
        assert 0.0 < intra["value"] <= scan["value"] <= 100.0
