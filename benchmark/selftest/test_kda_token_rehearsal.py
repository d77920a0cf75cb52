"""``drivers/train_kda_token_epochs.py`` rehearsed end to end on the CPU:
a tiny Kimi-Linear-shaped cell (delta-rule layers with a decay a channel
beside an unrotated latent layer, a sigmoid router with a selection bias
and a shared expert, a dense and three expert layers), its configuration
and its traffic are dropped into a temporary root (``FMDA_BENCH_ROOTS``)
and found with no edit; the run trains, compares itself with the plain
reference (``reference/kda_decoder.py``) and reports ``correct``; a
traced run reads the per-layer metrics that need no device; each
deliberately wrong reference is not correct; the counting functions
agree with the issue's arithmetic; the configuration's file copies the
catalog's row."""

import json
import os

import numpy as np
import pytest

from benchmark.harness import catalog, kda_decoder_flops as flops
from benchmark.harness.token_corpus import make_token_stream
from benchmark.selftest.test_rehearsal import rehearsal_result, run_cell

SEQ = 64
CELL = "kimi_linear_train_8k"
CONFIG = {"name": "tiny_kda_decoder", "framework": {
    "model": {"cell": "decoder", "hidden_size": 64, "n_heads": 4,
              "vocab_size": 256, "layer_layout": [5, 5, 4, 5],
              "kda_heads": 4, "kda_head_dim": 16, "kda_conv": 4,
              "kda_chunk": 16, "q_lora_rank": 0, "kv_lora_rank": 16,
              "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
              "v_head_dim": 16, "mla_use_nope": True,
              "rms_norm_eps": 1e-05, "moe_experts": 8, "moe_top_k": 2,
              "moe_ffn_size": 32, "experts_held": [0, 4],
              "hidden_act": "silu", "ffn_size": 96, "first_dense_layers": 1,
              "moe_shared_experts": 1, "moe_scoring": "sigmoid",
              "moe_routed_scaling": 2.446, "moe_bias_rate": 0.001,
              "loss_chunk": 32, "dtype": "float32", "remat": True,
              "dropout": 0.0},
    "train": {"batch_size": 1, "window": SEQ, "chunk_size": SEQ,
              "learning_rate": 0.00002, "clip": 1.0, "val_size": 0.05,
              "test_size": 0.09, "cache_chunks": 16}}}
TRAFFIC = {"kind": "train_kda_token_epochs", "seq_len": SEQ,
           "sequences_per_step": 1, "train_sequences": 8,
           "val_sequences": 1, "test_sequences": 1, "zipf_exponent": 1.0,
           "doc_median_tokens": 40, "doc_sigma": 1.0, "eod_id": 0,
           "setup_epochs": 2, "trace_steps": 6}
NEW_READERS = ("kda_mixer_dev_share", "kda_scan_dev_share",
               "kda_scan_roofline", "kda_attention_roofline",
               "kda_train_mfu")
#: The accepted per-layer metrics the cell is listed for: those whose
#: readers need no count of the layers.
LISTED = (
    "input_stall_share", "train_device_idle_share", "train_peak_hbm_mb",
    "train_step_dev_ms", "train_dispatch_us", "train_fold_us",
    "train_loop_self_us", "train_next_batch_us", "moe_experts_dev_share",
    "moe_routing_dev_share", "attention_dev_share", "lm_head_dev_share",
    "dense_mlp_dev_share", "train_pass_ms_per_step",
    "eval_pass_ms_per_step", "eval_pass_share", "epoch_turnaround_share",
    "mla_proj_dev_share", "mla_core_dev_share", "moe_shared_dev_share")
#: ... and those it must not be in: their readers count a latent core a
#: layer of ``layer_layout``, five here where one layer has a core.
NOT_LISTED = ("attention_core_fwd_runs_per_layer", "mla_core_roofline",
              "latent_train_mfu", "mla_train_mfu")


def _root(tmp_path):
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "cells.json").write_text(json.dumps({"workloads": [{
        "name": "tiny_kda_token_train", "config": "tiny_kda_decoder",
        "traffic": "tiny_packed_tokens"}]}))
    (tmp_path / "configs" / "tiny_kda_decoder.json").write_text(
        json.dumps(CONFIG))
    (tmp_path / "traffic" / "tiny_packed_tokens.json").write_text(
        json.dumps(TRAFFIC))
    return {catalog.ROOTS_ENV: str(tmp_path)}


def test_kda_driver_runs_end_to_end_and_agrees_with_the_reference(tmp_path):
    proc = run_cell("tiny_kda_token_train", trace=1,
                    extra_env=_root(tmp_path))
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    result = rehearsal_result(proc)
    assert result["correct"] is True, proc.stderr[-4000:]
    assert result["failed"] == 0 and result["attempted"] > 0
    metrics = result["metrics"]
    assert metrics["train_dispatch_us"]["value"] > 0
    # what needs a device is left out, not 0; the readers keyed to
    # another family's record stay silent
    for name in ("kda_train_mfu", "kda_scan_roofline",
                 "kda_attention_roofline", "mla_train_mfu",
                 "latent_train_mfu", "mla_core_roofline", "hc_mix_roofline",
                 "moe_train_mfu", "moe_expert_load_imbalance",
                 "sparse_train_mfu", "hybrid_train_mfu", "ssd_scan_roofline",
                 "train_mfu", "attention_roofline", "moe_experts_roofline"):
        assert name not in metrics, name
    checks = next(json.loads(line)["checks"]
                  for line in proc.stderr.splitlines()
                  if line.startswith('{"checks"'))
    assert checks["held_pairs_ok"] and checks["bias_ok"]
    assert checks["walk_ok"] and checks["loss_fell"]
    assert checks["moe_pairs_dropped_total"] == 0
    # three expert layers' held pairs a step; three delta-rule layers'
    # walks, four chunks of sixteen each
    assert len(checks["held_pairs_per_step_by_pass"][0]) == 3
    assert checks["kda_positions_per_train_step"] == [SEQ] * 3
    assert checks["kda_chunks_per_train_step"] == [4] * 3
    assert all(v > 0 for v in checks["kda_log_decay_absmax_by_pass"][-1])
    # the selection bias of every expert layer, whatever its mixer
    assert set(checks["grad_quiet_leaves_over_whole"]) == {
        f"block_{i}/router_bias" for i in (1, 2, 3)}
    assert set(checks["grad_rel_diff_worst"]) == {
        "kda", "latent", "dense", "routed", "router"}
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
    ({"decay": "none"}, False),               # g = 0: the plain delta rule
    ({"correction": False}, False),           # S += b k v^T
    ({"qk_norm": False}, False),              # q and k not normalised
    ({"products_as": "float8_e5m2"}, False),  # every product's operands
    ({"skip_shared": True}, False)],          # the shared expert left out
    ids=lambda v: "-".join(map(str, v.values())) if isinstance(v, dict)
    else str(v))
def test_a_deliberately_wrong_reference_is_not_correct(
        trained_tiny, reference_kw, agrees):
    """(One decay a head, rotary in the latent layer and a state in
    bfloat16 are the wrong runs this size cannot place between limits
    read on the chip: at hidden 64 and 64 positions a fresh model's
    decays are near one, its scores near zero.  The published size's
    readings are in PERF.md section 6, PR 49.)"""
    from benchmark.drivers import train_kda_token_epochs as driver

    trainer, params, dataset, val, first, rng = trained_tiny

    class Ctx:
        say = staticmethod(lambda record: None)

    checks = driver.reference_checks(
        Ctx, trainer, [params], dataset, val, first, rng,
        reference_kw=reference_kw)
    failed = [k for k in driver.REFERENCE_DECIDES if not checks[k]]
    assert (not failed) == agrees, (
        failed, checks["grad_rel_diff_worst"], checks["val_loss_abs_err"])


def test_every_leaf_of_the_model_has_a_group():
    import jax

    from benchmark.drivers import train_kda_token_epochs as driver
    from fmda_tpu.config import config_from_dict
    from fmda_tpu.models import build_model

    assert set(driver.GRAD_GROUP.values()) | {"kda", "latent"} == set(
        driver.GRAD_REL_DIFF)
    for framework in (CONFIG["framework"], catalog.load_config(
            "kimi_linear_48b_a3b_ep32")["framework"]):
        mc = config_from_dict(framework).model
        shapes = jax.eval_shape(
            lambda key: build_model(mc).init(
                {"params": key}, jax.numpy.zeros((1, 8), "int32"))["params"],
            jax.random.PRNGKey(0))
        for path, _ in jax.tree_util.tree_leaves_with_path(shapes):
            name = driver._leaf_name(path)
            if not name.endswith("router_bias"):
                assert driver._group(name, mc.layer_layout, 1) in (
                    driver.GRAD_REL_DIFF), name
    layout = (5, 5, 5, 4, 5)
    assert driver._group("block_0/w_up", layout, 1) == "dense"
    assert driver._group("block_1/w_up", layout, 1) == "routed"
    assert driver._group("block_1/router", layout, 1) == "router"
    # wq and wo are both mixers' names: by the kind of the block
    assert driver._group("block_2/wq", layout, 1) == "kda"
    assert driver._group("block_3/wq", layout, 1) == "latent"
    assert driver._group("block_4/a_log", layout, 1) == "kda"
    assert driver._group("block_4/dt_bias", layout, 1) == "kda"
    # the published size's parameter count is the file's
    total = sum(int(np.prod(leaf.shape))
                for leaf in jax.tree.leaves(shapes))
    assert total == 602_434_432


def test_the_counting_functions_give_the_issues_arithmetic():
    from fmda_tpu.config import config_from_dict

    mc = config_from_dict(catalog.load_config(
        "kimi_linear_48b_a3b_ep32")["framework"]).model
    assert (flops.kda_layers(mc), flops.latent_layers(mc),
            flops.dense_layers(mc), flops.expert_layers(mc)) == (4, 1, 1, 4)
    # a delta-rule layer's products: four of 2304 x 4096, two low-rank
    # pairs of 2304 x 128 + 128 x 4096, and 2304 x 32: 78.9 MFLOP a token
    assert flops.kda_projection_flops_fwd_per_token(mc) == 2 * (
        4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32)
    # its walk: 32.5 causal pairs a position x (2 x 128 x 5) + 3 state
    # products of 2 x 128 x 128, a head
    per_token = flops.scan_flops_fwd(8192, 32, 128, 64) / 8192
    assert per_token == 32 * (32.5 * 1280 + 3 * 2 * 128 * 128)
    # the one latent layer's core over the mean span, not five
    assert flops.attention_cores_flops_step(mc, 8192) == 3.5 * 2.0 * (
        8192 * 8193 // 2) * 32 * (192 + 128)
    forward = flops.forward_flops_per_token(mc, 8192, 0.25)
    kda = 4 * (flops.kda_projection_flops_fwd_per_token(mc) + per_token)
    assert 7.6e8 < forward < 7.9e8           # the issue's 778 MFLOP
    assert 0.42 < kda / forward < 0.45       # its 44 %, the largest part
    assert 19.0e12 < flops.train_flops_per_sequence(mc, 8192, 0.25) < 19.6e12
    # the walk's least traffic: a chunk's state written once and read once
    assert flops.scan_bytes_step(8192, 32, 128, 64) == 3.0 * (
        8192 * (3 * 4096 * 2 + 4096 * 4 + 32 * 4 + 4096 * 4)
        + 2.0 * 128 * 4096 * 128 * 4)


def test_the_new_readers_give_nothing_on_a_record_without_the_layer():
    """On the parent's program, and in every other cell, the new readers
    find no ``kda`` record and no ``kda_*`` scope: they return nothing
    and do not raise."""
    metrics = catalog.load_layer_metrics()
    record = {"end_to_end": {"train_samples_per_s": 1.0},
              "device": {"platform": "tpu", "kind": "TPU v5 lite",
                         "count": 1},
              "latent": {"seq_len": 8192}, "mla": {"seq_len": 8192}}
    for name in NEW_READERS:
        assert metrics[name].module.read(dict(record)) is None, name


def test_the_cell_of_record_finds_its_files_and_refuses_off_a_tpu():
    cell = catalog.find_cell(CELL)
    assert (cell.config, cell.traffic, cell.chips, cell.of_record) == (
        "kimi_linear_48b_a3b_ep32", "packed_tokens_8k_kda", 1, True)
    traffic = catalog.load_traffic(cell.traffic)
    config = catalog.load_config(cell.config)
    assert traffic["seq_len"] == config["framework"]["train"]["window"] == 8192
    mla = catalog.load_traffic("packed_tokens_8k_mla")
    for key in ("seq_len", "sequences_per_step", "train_sequences",
                "val_sequences", "test_sequences", "zipf_exponent",
                "doc_median_tokens", "doc_sigma", "eod_id", "setup_epochs",
                "trace_steps"):  # the two latent cells differ in the model
        assert traffic[key] == mla[key], key
    assert catalog.load_driver(traffic["kind"]).END_TO_END == {
        "train_samples_per_s": "samples/s"}
    metrics = catalog.load_layer_metrics()
    manifest = {m["name"]: m for m in catalog.load_manifest()["per_layer"]}
    for name in NEW_READERS:
        assert metrics[name].entry() == {
            k: v for k, v in manifest[name].items() if k != "workloads"}
        assert manifest[name]["workloads"] == [CELL]
    listing = {name for name, m in manifest.items() if CELL in m["workloads"]}
    assert listing == set(LISTED) | set(NEW_READERS)
    assert not listing & set(NOT_LISTED)
    assert all(manifest[name]["workloads"][-1] == CELL for name in listing)
    proc = run_cell(CELL)
    assert proc.returncode == 3 and proc.stdout.strip() == ""
    assert "runs on a TPU" in proc.stderr


def test_the_configuration_file_copies_the_catalogs_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("no catalog beside the guides here")
    with open(path) as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    config = catalog.load_config("kimi_linear_48b_a3b_ep32")
    assert config["source"] == row["source_url"]
    assert sorted(config["reduced"]) == sorted(config["published"])
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value, key
            assert config[key] == config["held_here"][key] != value, key
        else:
            assert config[key] == value, key  # nested groups whole
    model = config["framework"]["model"]
    linear = row["config"]["linear_attn_config"]
    assert row["config"]["q_lora_rank"] is None and model["q_lora_rank"] == 0
    assert row["config"]["mla_use_nope"] is True and model["mla_use_nope"]
    # layers 1..5 (one-based): delta rule but for the fourth
    assert model["layer_layout"] == [
        4 if i in linear["full_attn_layers"] else 5 for i in range(1, 6)]
    assert all(i in linear["kda_layers"] for i in (1, 2, 3, 5))
    assert model["kda_heads"] == linear["num_heads"]
    assert model["kda_head_dim"] == linear["head_dim"]
    assert model["kda_conv"] == linear["short_conv_kernel_size"]
    for ours, theirs in (
            ("hidden_size", "hidden_size"), ("n_heads", "num_attention_heads"),
            ("kv_lora_rank", "kv_lora_rank"),
            ("qk_nope_head_dim", "qk_nope_head_dim"),
            ("qk_rope_head_dim", "qk_rope_head_dim"),
            ("v_head_dim", "v_head_dim"), ("ffn_size", "intermediate_size"),
            ("moe_ffn_size", "moe_intermediate_size"),
            ("moe_top_k", "num_experts_per_token"),
            ("moe_shared_experts", "num_shared_experts"),
            ("moe_routed_scaling", "routed_scaling_factor"),
            ("first_dense_layers", "first_k_dense_replace"),
            ("rope_theta", "rope_theta"), ("rms_norm_eps", "rms_norm_eps"),
            ("hidden_act", "hidden_act")):
        assert model[ours] == row["config"][theirs], ours
    assert model["moe_scoring"] == row["config"]["moe_router_activation_func"]
    assert model["moe_experts"] == row["config"]["num_experts"]
    assert model["experts_held"][1] == config["num_experts"] == 8
    assert model["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert config["num_hidden_layers"] == len(model["layer_layout"]) == 5
