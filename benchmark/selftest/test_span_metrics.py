"""The five per-layer metrics PR 24 added: one manifest case each, and the
tiny training cells of the rehearsal report the four host-side ones under
``JAX_PLATFORMS=cpu`` (the fifth needs a device plane)."""

import pytest

from benchmark.harness import catalog
from benchmark.selftest.test_rehearsal import rehearsal_result, run_cell

M = catalog.load_manifest()
NEW = {
    "train_dispatch_us": ("us/step", "program_span", "train step"),
    "train_fold_us": ("us/step", "program_span", "train step"),
    "train_next_batch_us": ("us/step", "program_span", "input pipeline"),
    "train_loop_self_us": ("us/step", "program_span", "train step"),
    "train_recurrence_dev_share": ("%", "device_trace", "kernels"),
}
HOST = sorted(n for n in NEW if n.endswith("_us"))


@pytest.mark.parametrize("name", sorted(NEW))
def test_manifest_lists_the_metric_as_its_reader_states_it(name):
    (entry,) = [m for m in M["per_layer"] if m["name"] == name]
    unit, source, layer = NEW[name]
    assert entry == {
        "name": name, "unit": unit, "better": "lower", "source": source,
        "layer": layer, "moves": "train_samples_per_s",
        "workloads": ["gru_train_year", "ssm_train_year"]}
    reader = catalog.load_layer_metrics()[name]
    assert reader.entry() == {k: v for k, v in entry.items()
                              if k != "workloads"}
    # a layer BENCHMARK.json already named is named the same, letter for
    # letter; "kernels" is PERF.md's name for the layer no metric had
    assert layer in {m["layer"] for m in M["per_layer"][:5]} | {"kernels"}
    # new entries stand at the end of the list
    assert [m["name"] for m in M["per_layer"]].index(name) >= 5


@pytest.mark.parametrize("cell", ["selftest_gru_train", "selftest_ssm_train"])
def test_traced_training_rehearsal_reports_the_host_span_metrics(cell):
    result = rehearsal_result(run_cell(cell, trace=1))
    assert result["correct"] is True
    metrics = result["metrics"]
    for name in HOST:
        assert metrics[name]["value"] > 0, (name, metrics)
        assert metrics[name]["unit"] == "us/step"
    # the parts of a step: the jitted call and the fold are most of it,
    # the cached list's next() next to nothing
    assert metrics["train_next_batch_us"]["value"] < \
        metrics["train_dispatch_us"]["value"]
    # no device plane on the CPU: nothing under a device metric's name
    assert "train_recurrence_dev_share" not in metrics
    assert "train_device_idle_share" not in metrics
    # measured where the loop pulls, so no longer a structural zero
    assert metrics["input_stall_share"]["value"] > 0
