"""``drivers/train_hybrid_token_epochs.py`` rehearsed end to end on the
CPU: a tiny hybrid decoder cell (state-space layers around an attention
layer, a dense MLP, a tied head, the four multipliers), its
configuration and its traffic are dropped into a temporary root
(``FMDA_BENCH_ROOTS``) and found with no edit; the run trains, compares
itself with the plain reference (``reference/hybrid_decoder.py``) and
reports ``correct``; a traced run reads the per-layer metrics that need
no device; each deliberately wrong reference is not correct; the new
scope readers read a hand-built trace; the counting functions agree
with products counted by brute force."""

import json

import pytest

from benchmark.harness import catalog, hybrid_decoder_flops as flops
from benchmark.harness.token_corpus import make_token_stream
from benchmark.selftest.test_rehearsal import rehearsal_result, run_cell

SEQ, CHUNK = 64, 16
CONFIG = {"name": "tiny_hybrid_decoder", "framework": {
    "model": {"cell": "decoder", "hidden_size": 64, "n_heads": 4,
              "n_kv_heads": 2, "head_dim": 16, "vocab_size": 256,
              "layer_layout": [3, 0, 3], "rms_norm_eps": 1e-05,
              "moe_experts": 0, "ffn_size": 96, "hidden_act": "silu",
              "ssm_heads": 4, "ssm_head_dim": 16, "ssm_state": 8,
              "ssm_conv": 4, "ssm_chunk": CHUNK, "tie_embeddings": True,
              "embedding_multiplier": 12.0, "residual_multiplier": 0.22,
              "attention_multiplier": 0.0625, "logits_scaling": 8.0,
              "loss_chunk": 32, "dtype": "float32", "remat": True,
              "dropout": 0.0},
    "train": {"batch_size": 1, "window": SEQ, "chunk_size": SEQ,
              "learning_rate": 0.00002, "clip": 1.0, "val_size": 0.05,
              "test_size": 0.09, "cache_chunks": 16}}}
TRAFFIC = {"kind": "train_hybrid_token_epochs", "seq_len": SEQ,
           "sequences_per_step": 1, "train_sequences": 8,
           "val_sequences": 1, "test_sequences": 1, "zipf_exponent": 1.0,
           "doc_median_tokens": 40, "doc_sigma": 1.0, "eod_id": 0,
           "setup_epochs": 2, "trace_steps": 6}
NEW_READERS = ("ssm_mixer_dev_share", "ssd_scan_dev_share",
               "ssm_conv_dev_share", "dense_mlp_dev_share",
               "ssd_scan_roofline", "hybrid_attention_roofline",
               "hybrid_train_mfu")


def _root(tmp_path):
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "cells.json").write_text(json.dumps({"workloads": [{
        "name": "tiny_hybrid_token_train", "config": "tiny_hybrid_decoder",
        "traffic": "tiny_packed_docs"}]}))
    (tmp_path / "configs" / "tiny_hybrid_decoder.json").write_text(
        json.dumps(CONFIG))
    (tmp_path / "traffic" / "tiny_packed_docs.json").write_text(
        json.dumps(TRAFFIC))
    return {catalog.ROOTS_ENV: str(tmp_path)}


def test_hybrid_driver_runs_end_to_end_and_agrees_with_the_reference(
        tmp_path):
    proc = run_cell("tiny_hybrid_token_train", trace=1,
                    extra_env=_root(tmp_path))
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    result = rehearsal_result(proc)
    assert result["correct"] is True, proc.stderr[-4000:]
    assert result["failed"] == 0 and result["attempted"] > 0
    metrics = result["metrics"]
    assert metrics["train_dispatch_us"]["value"] > 0
    # what needs a device is left out, not 0; the readers keyed to
    # another family's record stay silent
    for name in ("hybrid_train_mfu", "ssd_scan_roofline",
                 "hybrid_attention_roofline", "moe_train_mfu",
                 "moe_expert_load_imbalance", "sparse_train_mfu",
                 "sparse_keys_kept_share", "train_mfu",
                 "attention_roofline", "moe_experts_roofline"):
        assert name not in metrics, name
    checks = next(json.loads(line)["checks"]
                  for line in proc.stderr.splitlines()
                  if line.startswith('{"checks"'))
    # the split, and what the scans walked: two state-space layers, every
    # position of the one sequence a step, four chunks of sixteen
    assert checks["scan_positions_per_train_step"] == [SEQ, SEQ]
    assert checks["scan_chunks_per_train_step"] == [SEQ // CHUNK] * 2
    assert checks["scans_ok"] is True and checks["loss_fell"] is True
    notes = next(json.loads(line)
                 for line in proc.stderr.splitlines()
                 if line.startswith('{"train_loss_after_setup_epochs"'))
    assert (notes["valid_sequences_per_epoch"],
            notes["train_steps_per_epoch"],
            notes["eval_steps_per_epoch"]) == (8, 8, 1)


@pytest.fixture(scope="module")
def trained_tiny():
    """The tiny configuration trained for an epoch in this process, and
    what ``reference_checks`` needs of the run."""
    import jax

    from fmda_tpu.config import config_from_dict
    from fmda_tpu.data.source import TokenArraySource
    from fmda_tpu.train.trainer import Trainer

    cfg = config_from_dict(CONFIG["framework"])
    stream = make_token_stream(10 * SEQ + 1, 256, 5, doc_median_tokens=40.0)
    trainer = Trainer(cfg.model, cfg.train)
    rng = jax.random.PRNGKey(5)
    state, _, dataset = trainer.fit(
        TokenArraySource(stream, 256), rng=rng, epochs=1)
    train, val, _ = dataset.split(cfg.train.val_size, cfg.train.test_size)
    return trainer, state.params, dataset, val, train[0], rng


@pytest.mark.parametrize("reference_kw,agrees", [
    (None, True),
    ({"products_as": "float8_e5m2"}, False),   # one precision lower
    ({"drop_state_every": CHUNK}, False),      # the carry forgotten
    ({"conv_ahead": 1}, False),                # a future position read
    ({"leave_out": "d_skip"}, False),
    ({"leave_out": "gate"}, False),
    ({"leave_out": "embedding_multiplier"}, False),
    ({"leave_out": "residual_multiplier"}, False),
    ({"leave_out": "attention_multiplier"}, False),
    ({"leave_out": "logits_scaling"}, False)],
    ids=lambda v: "-".join(map(str, v.values())) if isinstance(v, dict)
    else str(v))
def test_a_deliberately_wrong_reference_is_not_correct(
        trained_tiny, reference_kw, agrees):
    """(A state carried in bfloat16 is the one wrong run this size cannot
    show: over 64 positions its error is under the float32 program's own
    distance; the published size's readings are in PERF.md section 6,
    PR 34.)"""
    from benchmark.drivers import train_hybrid_token_epochs as driver

    trainer, params, dataset, val, first, rng = trained_tiny

    class Ctx:
        say = staticmethod(lambda record: None)

    checks = driver.reference_checks(
        Ctx, trainer, [params], dataset, val, first, rng,
        reference_kw=reference_kw)
    failed = [k for k in driver.REFERENCE_DECIDES if not checks[k]]
    assert (not failed) == agrees, (failed, checks["grad_rel_diff_worst"])


def test_every_leaf_of_the_tiny_model_has_a_group():
    from benchmark.drivers import train_hybrid_token_epochs as driver
    from benchmark.reference import hybrid_decoder as ref

    assert set(driver.GRAD_GROUP.values()) == set(driver.GRAD_REL_DIFF)
    assert driver.SSM_LAYOUT == flops.SSM_LAYOUT == ref.SSM_LAYOUT == 3


def test_the_counting_functions_agree_with_products_counted_by_brute_force():
    for seq, heads, p, n, chunk in ((64, 4, 16, 8, 16), (512, 64, 64, 128,
                                                         256)):
        pairs = sum(1 for i in range(chunk) for j in range(i + 1))
        assert flops.chunk_pairs(chunk) == pairs
        chunks = seq // chunk
        want = chunks * (2 * pairs * n          # C B^T, once for the group
                         + 2 * pairs * heads * p  # the pairs' weights x dx
                         + 2 * chunk * heads * p * n   # a chunk's end state
                         + 2 * chunk * heads * p * n)  # carried state x C
        assert flops.scan_flops_fwd(seq, heads, p, n, chunk) == want
        assert flops.scan_flops_step(seq, heads, p, n, chunk) == 3 * want


def test_the_whole_steps_count_is_the_issues_arithmetic():
    from fmda_tpu.config import config_from_dict

    mc = config_from_dict(catalog.load_config(
        "granite_4_0_h_micro_pp4")["framework"]).model
    per_token = flops.forward_flops_per_token(mc, 8192)
    # 9 x 155.5 M + 155.2 M + 51.4 M head = 1.61 GFLOP a token forward
    assert abs(per_token / 1e9 - 1.606) < 0.001
    assert abs(flops.train_flops_per_sequence(mc, 8192) / 1e12 - 39.6) < 0.1
    assert flops.attention_layers(mc) == 1
    # the one attention layer's core is ~2 % of the operations
    cores = flops.attention_cores_flops_step(mc, 8192)
    assert 0.015 < cores / flops.train_flops_per_sequence(mc, 8192) < 0.03


def test_the_cell_of_record_finds_its_files_and_refuses_off_a_tpu():
    cell = catalog.find_cell("granite_h_train_8k")
    assert (cell.config, cell.traffic, cell.chips, cell.of_record) == (
        "granite_4_0_h_micro_pp4", "packed_docs_8k", 1, True)
    traffic = catalog.load_traffic(cell.traffic)
    config = catalog.load_config(cell.config)
    assert traffic["seq_len"] == config["framework"]["train"]["window"]
    assert (traffic["train_sequences"], traffic["val_sequences"],
            traffic["test_sequences"]) == (8, 1, 1)
    assert catalog.load_driver(traffic["kind"]).END_TO_END == {
        "train_samples_per_s": "samples/s"}
    proc = run_cell("granite_h_train_8k")
    assert proc.returncode == 3 and proc.stdout.strip() == ""
    assert "runs on a TPU" in proc.stderr


def test_the_configuration_file_copies_the_catalogs_row():
    import os

    row_path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(row_path):
        pytest.skip("no catalog here")
    with open(row_path) as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "granite-4.0-h-micro")
    config = catalog.load_config("granite_4_0_h_micro_pp4")
    assert config["source"] == row["source_url"]
    differing = [k for k, v in row["config"].items() if config.get(k) != v]
    assert sorted(differing) == sorted(config["reduced"]) == [
        "num_hidden_layers", "vocab_size"]
    model = config["framework"]["model"]
    published = row["config"]
    assert (model["hidden_size"], model["ffn_size"], model["n_heads"],
            model["n_kv_heads"]) == (
        published["hidden_size"], published["intermediate_size"],
        published["num_attention_heads"], published["num_key_value_heads"])
    assert (model["ssm_heads"], model["ssm_head_dim"], model["ssm_state"],
            model["ssm_conv"], model["ssm_chunk"]) == (
        published["mamba_n_heads"], published["mamba_d_head"],
        published["mamba_d_state"], published["mamba_d_conv"],
        published["mamba_chunk_size"])
    assert (model["embedding_multiplier"], model["residual_multiplier"],
            model["attention_multiplier"], model["logits_scaling"]) == (
        published["embedding_multiplier"], published["residual_multiplier"],
        published["attention_multiplier"], published["logits_scaling"])
    layout = [3 if kind == "mamba" else 0
              for kind in published["layer_types"][:10]]
    assert model["layer_layout"] == layout
    assert model["vocab_size"] * 8 == published["vocab_size"]


def _trace_record(busy_by_scope):
    return {"end_to_end": {"train_samples_per_s": 1.0},
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
            "program_spans": {"busy_by_scope": busy_by_scope}}


def test_the_new_scope_readers_read_a_hand_built_trace(monkeypatch):
    """Device seconds by (program, scope path, operation) as
    ``program_spans`` reduces a trace to them: the readers take a scope
    by its component, under the gradient's and the recomputation's
    transforms, in the train step alone."""
    from benchmark.harness import program_spans

    step, fwd = "jit_train_step", "jvp(forward)/MoEDecoder/block_0/"
    bwd = ("transpose(jvp(forward))/MoEDecoder/block_0/"
           "rematted_computation/checkpoint/")
    busy = {
        (step, fwd + "ssm_mixer/ssm_in_proj", "fusion.1"): 2.0,
        (step, fwd + "ssm_mixer/ssm_conv", "fusion.2"): 1.0,
        (step, fwd + "ssm_mixer/ssd_scan/ssd_intra", "fusion.3"): 3.0,
        (step, bwd + "ssm_mixer/ssd_scan/while/body/ssd_out", "while.1"): 1.0,
        (step, bwd + "ssm_mixer/ssm_out_proj", "fusion.4"): 1.0,
        (step, fwd + "dense_mlp", "fusion.5"): 8.0,
        (step, fwd + "attention/attention_full", "flash_fwd"): 2.0,
        (step, "optimizer", "fusion.6"): 2.0,
        ("jit_eval_step", fwd + "ssm_mixer/ssd_scan", "fusion.7"): 50.0,
    }
    monkeypatch.setattr(program_spans, "for_record",
                        lambda record: record["program_spans"])
    metrics = catalog.load_layer_metrics()
    record = _trace_record(busy)
    got = {name: metrics[name].module.read(record) for name in (
        "ssm_mixer_dev_share", "ssd_scan_dev_share", "ssm_conv_dev_share",
        "dense_mlp_dev_share")}
    assert got == {"ssm_mixer_dev_share": 40.0, "ssd_scan_dev_share": 20.0,
                   "ssm_conv_dev_share": 5.0, "dense_mlp_dev_share": 40.0}
    # a program that writes none of the scopes (the parent) reads None
    older = _trace_record({(step, "jvp(forward)/attention", "f"): 1.0})
    for name in NEW_READERS[:4]:
        assert metrics[name].module.read(older) is None, name


def test_new_readers_stay_silent_without_the_drivers_facts():
    metrics = catalog.load_layer_metrics()
    record = {"end_to_end": {"train_samples_per_s": 1.0},
              "device": {"platform": "tpu", "kind": "TPU v5 lite",
                         "count": 1},
              "program_spans": None}
    for name in NEW_READERS:
        assert metrics[name].module.read(dict(record)) is None, name
