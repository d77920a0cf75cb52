"""``drivers/train_token_epochs.py`` rehearsed end to end on the CPU: a
tiny decoder cell, its configuration and its traffic are dropped into a
temporary root (``FMDA_BENCH_ROOTS``) and found with no edit; the run
trains, compares itself with the plain reference
(``reference/moe_decoder.py``) and reports ``correct``; a traced run
reads the per-layer metrics that need no device."""

import json

import pytest

from benchmark.harness import catalog, moe_decoder_flops as flops
from benchmark.harness.token_corpus import (
    make_token_stream, zipf_entropy_nats)
from benchmark.selftest.test_rehearsal import rehearsal_result, run_cell

CONFIG = {"name": "tiny_decoder", "framework": {
    "model": {"cell": "decoder", "hidden_size": 64, "n_heads": 4,
              "n_kv_heads": 2, "head_dim": 16, "vocab_size": 256,
              "layer_layout": [0, 1, 1, 1], "sliding_window": 16,
              "rope_theta": 1500000.0, "moe_experts": 8, "moe_top_k": 2,
              "moe_ffn_size": 32, "experts_held": [2, 4], "loss_chunk": 32,
              "dtype": "float32", "remat": True, "dropout": 0.0},
    "train": {"batch_size": 1, "window": 64, "chunk_size": 64,
              "learning_rate": 0.0001, "clip": 1.0, "val_size": 0.1,
              "test_size": 0.14, "cache_chunks": 32}}}
TRAFFIC = {"kind": "train_token_epochs", "seq_len": 64,
           "sequences_per_step": 1, "train_sequences": 24,
           "val_sequences": 4, "test_sequences": 4, "zipf_exponent": 1.0,
           "doc_median_tokens": 40, "doc_sigma": 1.0, "eod_id": 0,
           "trace_steps": 16}


def _root(tmp_path):
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "cells.json").write_text(json.dumps({"workloads": [{
        "name": "tiny_token_train", "config": "tiny_decoder",
        "traffic": "tiny_packed_tokens"}]}))
    (tmp_path / "configs" / "tiny_decoder.json").write_text(
        json.dumps(CONFIG))
    (tmp_path / "traffic" / "tiny_packed_tokens.json").write_text(
        json.dumps(TRAFFIC))
    return {catalog.ROOTS_ENV: str(tmp_path)}


def test_token_driver_runs_end_to_end_and_agrees_with_the_reference(
        tmp_path):
    proc = run_cell("tiny_token_train", trace=1, extra_env=_root(tmp_path))
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    result = rehearsal_result(proc)
    assert result["correct"] is True, proc.stderr[-3000:]
    assert result["failed"] == 0 and result["attempted"] > 0
    metrics = result["metrics"]
    # what needs no device is read; what needs one is left out, not 0
    assert metrics["moe_expert_load_imbalance"]["value"] > 1.0
    assert metrics["train_dispatch_us"]["value"] > 0
    for name in ("moe_experts_dev_share", "attention_roofline",
                 "moe_train_mfu", "train_mfu"):
        assert name not in metrics
    notes = next(json.loads(line)["notes"]
                 for line in proc.stderr.splitlines()
                 if line.startswith('{"notes"'))
    assert notes["trace_slice_fits_margins"] is False
    # (on the CPU a pass is over before start_trace returns, so the slice
    # crosses passes here; on the chip it opens at step 0 or 1)
    assert notes["traced_steps"] >= 16


@pytest.fixture(scope="module")
def trained_tiny():
    """The tiny configuration trained for an epoch in this process, and
    what ``reference_checks`` needs of the run."""
    import jax

    from fmda_tpu.config import config_from_dict
    from fmda_tpu.data.source import TokenArraySource
    from fmda_tpu.train.trainer import Trainer

    cfg = config_from_dict(CONFIG["framework"])
    stream = make_token_stream(32 * 64 + 1, 256, 5, doc_median_tokens=40.0)
    trainer = Trainer(cfg.model, cfg.train)
    rng = jax.random.PRNGKey(5)
    state, _, dataset = trainer.fit(
        TokenArraySource(stream, 256), rng=rng, epochs=1)
    train, val, _ = dataset.split(cfg.train.val_size, cfg.train.test_size)
    return trainer, state.params, dataset, val, train[0], rng


@pytest.mark.parametrize("reference_kw,agrees", [
    (None, True),
    ({"products_as": "float8_e5m2"}, False),   # one precision lower
    ({"skip_expert": 1}, False)])              # one held expert short
def test_a_deliberately_wrong_reference_is_not_correct(
        trained_tiny, reference_kw, agrees):
    from benchmark.drivers import train_token_epochs as driver

    trainer, params, dataset, val, first, rng = trained_tiny

    class Ctx:
        say = staticmethod(lambda record: None)

    checks = driver.reference_checks(
        Ctx, trainer, [params], dataset, val, first, rng,
        reference_kw=reference_kw)
    failed = [k for k in driver.REFERENCE_DECIDES if not checks[k]]
    assert (not failed) == agrees, (failed, checks["grad_rel_diff_worst"])
    if reference_kw and "skip_expert" in reference_kw:
        # a mean loss hardly sees one expert; its gradient does
        assert "grad_ok" in failed


def test_the_cell_of_record_finds_its_files_and_refuses_off_a_tpu():
    cell = catalog.find_cell("smallthinker_train_8k")
    assert cell.of_record and cell.chips == 1
    config = catalog.load_config(cell.config)
    traffic = catalog.load_traffic(cell.traffic)
    assert catalog.load_driver(traffic["kind"]).END_TO_END == {
        "train_samples_per_s": "samples/s"}
    model, train = config["framework"]["model"], config["framework"]["train"]
    assert (train["window"], train["batch_size"]) == (
        traffic["seq_len"], traffic["sequences_per_step"])
    # every published width, unchanged, and the share as `reduced` has it
    assert (model["hidden_size"], model["n_heads"], model["n_kv_heads"],
            model["head_dim"], model["moe_ffn_size"], model["moe_experts"],
            model["moe_top_k"], model["sliding_window"]) == (
        config["hidden_size"], config["num_attention_heads"],
        config["num_key_value_heads"], config["head_dim"],
        config["moe_ffn_hidden_size"], 64,
        config["moe_num_active_primary_experts"],
        config["sliding_window_size"])
    assert model["layer_layout"] == config["rope_layout"][:4] == \
        config["sliding_window_layout"][:4]
    assert model["experts_held"][1] == config["moe_num_primary_experts"]
    assert model["vocab_size"] == config["vocab_size"]
    manifest = {c["name"]: c for c in catalog.load_manifest()["configs"]}
    assert manifest[cell.config]["reduced"] == config["reduced"]
    proc = run_cell("smallthinker_train_8k")
    assert proc.returncode == 3 and "runs on a TPU" in proc.stderr


def test_the_counting_functions_give_the_issues_arithmetic():
    from fmda_tpu.config import config_from_dict

    mc = config_from_dict(catalog.load_config(
        "smallthinker_21b_a3b_ep4")["framework"]).model
    assert flops.visible_pairs(8192, None) == 8192 * 8193 // 2
    assert flops.visible_pairs(8192, 4096) == (
        4096 * 4097 // 2 + 4096 * 4096)
    assert flops.visible_pairs(64, 4096) == 64 * 65 // 2
    per_token = flops.forward_flops_per_token(mc, 8192, 6 * 16 / 64)
    assert 0.60e9 < per_token < 0.65e9            # ~625 MFLOP a token
    step = flops.train_flops_per_sequence(mc, 8192, 6 * 16 / 64)
    assert 15.0e12 < step < 15.8e12               # ~15.4 TFLOP a step
    assert flops.expert_flops_step(768 * 16, 2560, 768) == (
        3 * 768 * 16 * 6 * 2560 * 768)


def test_the_corpus_is_seeded_zipf_and_packed():
    a = make_token_stream(50_000, 37_984, 2_147_483_900)
    b = make_token_stream(50_000, 37_984, 2_147_483_900)
    c = make_token_stream(50_000, 37_984, 7)
    assert (a == b).all() and not (a == c).all()
    assert a.dtype.name == "int32" and a.min() >= 0 and a.max() < 37_984
    eod = (a == 0).mean()
    assert 1 / 3000 < eod < 1 / 500   # a document about every 1,650 tokens
    counts = sorted((a[a != 0].tolist().count(v) for v in set(
        a[:5000].tolist()) - {0}), reverse=True)
    assert counts[0] > 20 * counts[len(counts) // 2]  # a few ids carry most
    assert 7.0 < zipf_entropy_nats(37_984) < 8.5


def _record(scopes, **extra):
    """A record whose trace reduction is given (``program_spans.for_record``
    keeps it under this key), busy seconds by (program, scope path, op)."""
    return dict({"program_spans": {"busy_by_scope": {
        ("jit_train_step", scope, ""): s for scope, s in scopes.items()}},
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}},
        **extra)


def test_scope_readers_find_components_and_return_none_where_absent():
    from benchmark.harness import scope_shares

    metrics = catalog.load_layer_metrics()
    decoder = _record({
        "jvp(forward)/MoEDecoder.features/block_0/moe_experts": 2.0,
        "transpose(jvp(forward))/MoEDecoder.features/jvp(forward)/"
        "checkpoint/rematted_computation/block_0/moe_experts": 1.0,
        "jvp(forward)/MoEDecoder.features/block_0/moe_dispatch": 0.5,
        "jvp(forward)/MoEDecoder.features/block_1/attention/"
        "attention_window/attention": 3.0,
        "jvp(forward)/MoEDecoder.features/block_1/attention": 1.0,
        "transpose(jvp(loss))/while/body/checkpoint/lm_head": 1.5,
        "jvp(loss)/while/body": 0.5, "optimizer": 0.5})
    read = lambda name, rec: metrics[name].module.read(rec)
    assert read("moe_experts_dev_share", decoder) == 30.0
    assert read("moe_routing_dev_share", decoder) == 5.0
    assert read("attention_dev_share", decoder) == 40.0
    assert read("lm_head_dev_share", decoder) == 20.0
    assert scope_shares.scope_seconds(
        decoder, ("attention_full", "attention_window")) == 3.0
    # a recurrent classifier's step: `loss` and `attention`-free scopes
    classifier = _record({"jvp(forward)/BiGRU/recurrence_fwd/while/body": 3.0,
                          "jvp(loss)": 0.1, "optimizer": 0.2})
    for name in ("moe_experts_dev_share", "moe_routing_dev_share",
                 "attention_dev_share", "lm_head_dev_share",
                 "moe_experts_roofline", "attention_roofline",
                 "moe_train_mfu", "moe_expert_load_imbalance"):
        assert read(name, dict(classifier, end_to_end={
            "train_samples_per_s": 1.0})) is None, name
    # no trace at all
    assert read("attention_dev_share", {"program_spans": None}) is None


def test_rooflines_count_device_steps_and_stay_under_the_peak():
    from fmda_tpu.config import config_from_dict

    mc = config_from_dict(catalog.load_config(
        "smallthinker_21b_a3b_ep4")["framework"]).model
    metrics = catalog.load_layer_metrics()
    moe = {"seq_len": 8192, "sequences_per_step": 1, "experts_held": 16,
           "pairs_per_train_step": [12288.0] * 4}
    # the experts' scope at exactly the compute bound's time: 100 %
    need = 23 * flops.expert_flops_step(4 * 12288, 2560, 768) / 197e12
    rec = _record({"jvp(forward)/x/moe_experts": need,
                   "jvp(forward)/x/attention/attention_full": 1.0},
                  moe=moe, model_cfg=mc, device_train_steps=23.0,
                  trace={"steps": {"train": 24, "eval": 0}})
    got = metrics["moe_experts_roofline"].module.read(rec)
    assert abs(got - 100.0) < 1e-6   # 23 device steps, not 24 annotations
    assert 0 < metrics["attention_roofline"].module.read(rec) < 100.0


def test_device_steps_are_read_from_the_modules_line(tmp_path):
    """A hand-encoded trace: a slice of 10 us on the host plane, three
    runs of the step program on the device's ``XLA Modules`` line (one
    cut in half by the slice's end), one run of another program."""
    from benchmark.harness.scope_shares import program_runs_in_slice

    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)

    def field(num, payload):  # length-delimited
        return varint(num << 3 | 2) + varint(len(payload)) + payload

    def number(num, value):
        return varint(num << 3) + varint(value)

    def plane(name, line_name, metadata, events):
        meta = b"".join(
            field(4, number(1, k) + field(2, number(1, k) + field(
                2, text.encode()))) for k, text in metadata.items())
        evs = b"".join(field(4, number(1, m) + number(2, off * 1000)
                             + number(3, dur * 1000))
                       for m, off, dur in events)
        return field(1, field(2, name.encode()) + field(
            3, field(2, line_name.encode()) + number(3, 0) + evs) + meta)

    # times in ns; the slice is [1000, 11000)
    trace = plane("/host:CPU", "python3", {1: "bench_slice"},
                  [(1, 1000, 10000)])
    trace += plane("/device:TPU:0", "XLA Modules",
                   {1: "jit_train_step(7)", 2: "jit__lambda_(9)"},
                   [(1, 1000, 4000), (2, 5000, 10), (1, 5010, 4000),
                    (1, 9010, 4000)])
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(trace)
    got = program_runs_in_slice(str(path))
    assert abs(got - (2 + 1990 / 4000)) < 1e-9
    assert program_runs_in_slice(str(path), "jit_eval_step") is None
