"""``drivers/train_mla_token_epochs.py`` rehearsed end to end on the CPU:
a tiny Moonlight-shaped cell (latent attention with a direct query, a
plain residual, two shared experts, a sigmoid router with a selection
bias and the per-sequence balance term, a dense and two expert layers),
its configuration and its traffic are dropped into a temporary root
(``FMDA_BENCH_ROOTS``) and found with no edit; the run trains, compares
itself with the plain reference (``reference/mla_decoder.py``) and
reports ``correct``; a traced run reads the per-layer metrics that need
no device; each deliberately wrong reference is not correct; the
counting functions agree with the issue's arithmetic; the
configuration's file copies the catalog's row."""

import json
import os

import numpy as np
import pytest

from benchmark.harness import catalog, mla_decoder_flops as flops
from benchmark.harness.token_corpus import make_token_stream
from benchmark.selftest.test_rehearsal import rehearsal_result, run_cell

SEQ = 64
CELL = "moonlight_train_8k"
#: alpha 0.05, not the family's 0.001: at hidden 64 and 64 tokens the
#: router's next-token gradient is large beside a term of 0.001, and the
#: wrong runs of the term have to show in a float32 program's limits.
CONFIG = {"name": "tiny_mla_decoder", "framework": {
    "model": {"cell": "decoder", "hidden_size": 64, "n_heads": 4,
              "vocab_size": 256, "layer_layout": [4, 4, 4],
              "q_lora_rank": 0, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
              "qk_rope_head_dim": 8, "v_head_dim": 16, "rope_theta": 50000.0,
              "rms_norm_eps": 1e-05, "moe_experts": 8, "moe_top_k": 2,
              "moe_ffn_size": 32, "experts_held": [0, 4],
              "hidden_act": "silu", "ffn_size": 96, "first_dense_layers": 1,
              "moe_shared_experts": 2, "moe_scoring": "sigmoid",
              "moe_routed_scaling": 2.446, "moe_bias_rate": 0.001,
              "moe_seq_aux_alpha": 0.05, "loss_chunk": 32,
              "dtype": "float32", "remat": True, "dropout": 0.0},
    "train": {"batch_size": 1, "window": SEQ, "chunk_size": SEQ,
              "learning_rate": 0.00002, "clip": 1.0, "val_size": 0.05,
              "test_size": 0.09, "cache_chunks": 16}}}
TRAFFIC = {"kind": "train_mla_token_epochs", "seq_len": SEQ,
           "sequences_per_step": 1, "train_sequences": 8,
           "val_sequences": 1, "test_sequences": 1, "zipf_exponent": 1.0,
           "doc_median_tokens": 40, "doc_sigma": 1.0, "eod_id": 0,
           "setup_epochs": 2, "trace_steps": 6}
NEW_READERS = ("mla_train_mfu", "moe_balance_dev_share")
#: The accepted per-layer metrics the cell is listed for.
LISTED = (
    "input_stall_share", "train_device_idle_share", "train_peak_hbm_mb",
    "train_step_dev_ms", "train_dispatch_us", "train_fold_us",
    "train_loop_self_us", "train_next_batch_us", "moe_experts_dev_share",
    "moe_routing_dev_share", "attention_dev_share", "lm_head_dev_share",
    "attention_core_fwd_runs_per_layer", "dense_mlp_dev_share",
    "train_pass_ms_per_step", "eval_pass_ms_per_step", "eval_pass_share",
    "epoch_turnaround_share", "mla_proj_dev_share", "mla_core_dev_share",
    "mla_core_roofline", "moe_shared_dev_share")


def _root(tmp_path):
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "cells.json").write_text(json.dumps({"workloads": [{
        "name": "tiny_mla_token_train", "config": "tiny_mla_decoder",
        "traffic": "tiny_packed_tokens"}]}))
    (tmp_path / "configs" / "tiny_mla_decoder.json").write_text(
        json.dumps(CONFIG))
    (tmp_path / "traffic" / "tiny_packed_tokens.json").write_text(
        json.dumps(TRAFFIC))
    return {catalog.ROOTS_ENV: str(tmp_path)}


def test_mla_driver_runs_end_to_end_and_agrees_with_the_reference(tmp_path):
    proc = run_cell("tiny_mla_token_train", trace=1,
                    extra_env=_root(tmp_path))
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    result = rehearsal_result(proc)
    assert result["correct"] is True, proc.stderr[-4000:]
    assert result["failed"] == 0 and result["attempted"] > 0
    metrics = result["metrics"]
    assert metrics["train_dispatch_us"]["value"] > 0
    # what needs a device is left out, not 0; the readers keyed to
    # another family's record stay silent
    for name in ("mla_train_mfu", "latent_train_mfu", "mla_core_roofline",
                 "hc_mix_roofline", "hc_mix_dev_share", "moe_train_mfu",
                 "moe_expert_load_imbalance", "sparse_train_mfu",
                 "hybrid_train_mfu", "train_mfu", "attention_roofline",
                 "moe_experts_roofline"):
        assert name not in metrics, name
    checks = next(json.loads(line)["checks"]
                  for line in proc.stderr.splitlines()
                  if line.startswith('{"checks"'))
    assert checks["held_pairs_ok"] and checks["bias_ok"]
    assert checks["terms_ok"] and checks["val_terms_ok"]
    assert checks["loss_fell"] and checks["moe_pairs_dropped_total"] == 0
    # two expert layers' held pairs a step, about 64 x 2 x 4 / 8 each,
    # and their balance terms a pass, near alpha at a nearly even router
    assert len(checks["held_pairs_per_step_by_pass"][0]) == 2
    assert all(0.04 < term < 0.08
               for terms in checks["seq_aux_loss_by_pass"] for term in terms)
    assert checks["first_step_terms_program"][0] == 0.0  # the dense layer
    assert set(checks["grad_quiet_leaves_over_whole"]) == {
        "block_1/router_bias", "block_2/router_bias"}
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
    ({"balance": "none"}, False),              # the term left out
    ({"balance": "unnormalised"}, False),      # s in place of s'
    ({"products_as": "float8_e5m2"}, False),   # every product's operands
    ({"skip_shared": True}, False)],           # the shared experts left out
    ids=lambda v: "-".join(map(str, v.values())) if isinstance(v, dict)
    else str(v))
def test_a_deliberately_wrong_reference_is_not_correct(
        trained_tiny, reference_kw, agrees):
    """(A query in float8 and a softmax in bfloat16 are the wrong runs
    this size cannot place between limits read on the chip: at hidden 64
    a fresh model's scores are near zero, the softmax is flat whatever
    the query, and the float8 query moves the latent leaves' gradient by
    8e-4.  The published size's readings are in PERF.md section 6,
    PR 46.)"""
    from benchmark.drivers import train_mla_token_epochs as driver

    trainer, params, dataset, val, first, rng = trained_tiny

    class Ctx:
        say = staticmethod(lambda record: None)

    checks = driver.reference_checks(
        Ctx, trainer, [params], dataset, val, first, rng,
        reference_kw=reference_kw)
    failed = [k for k in driver.REFERENCE_DECIDES if not checks[k]]
    assert (not failed) == agrees, (
        failed, checks["grad_rel_diff_worst"], checks["term_rel_diff"])
    if reference_kw and "balance" in reference_kw:
        assert "terms_ok" in failed and "val_terms_ok" in failed


def test_every_leaf_of_the_model_has_a_group():
    import jax

    from benchmark.drivers import train_mla_token_epochs as driver
    from fmda_tpu.config import config_from_dict
    from fmda_tpu.models import build_model

    assert set(driver.GRAD_GROUP.values()) == set(driver.GRAD_REL_DIFF)
    for framework in (CONFIG["framework"], catalog.load_config(
            "moonlight_16b_a3b_ep8")["framework"]):
        mc = config_from_dict(framework).model
        shapes = jax.eval_shape(
            lambda key: build_model(mc).init(
                {"params": key}, jax.numpy.zeros((1, 8), "int32"))["params"],
            jax.random.PRNGKey(0))
        for path, _ in jax.tree_util.tree_leaves_with_path(shapes):
            name = driver._leaf_name(path)
            if not name.endswith("router_bias"):
                assert driver._group(name, 1) in driver.GRAD_REL_DIFF, name
    assert driver._group("block_0/w_up", 1) == "dense"
    assert driver._group("block_1/w_up", 1) == "routed"
    assert driver._group("block_1/router", 1) == "router"
    # the published size's parameter count is the file's
    total = sum(int(np.prod(leaf.shape))
                for leaf in jax.tree.leaves(shapes))
    assert total == 668_890_432


def test_the_counting_functions_give_the_issues_arithmetic():
    from benchmark.harness import latent_decoder_flops as accepted
    from fmda_tpu.config import config_from_dict

    mc = config_from_dict(catalog.load_config(
        "moonlight_16b_a3b_ep8")["framework"]).model
    assert (flops.dense_layers(mc), flops.expert_layers(mc)) == (1, 5)
    # the direct query's product: 2 x 2048 x 3072 = 12.6 MFLOP a token
    # and layer forward, which the accepted count reads as 0
    assert flops.direct_query_flops_fwd_per_token(mc) == 2 * 2048 * 16 * 192
    assert (flops.projection_flops_fwd_per_token(mc)
            - accepted.projection_flops_fwd_per_token(mc)
            ) == 2 * 2048 * 16 * 192
    assert flops.projection_flops_fwd_per_token(mc) == 2 * 13_762_560
    # the core over 8,192 tokens: 343.6 GFLOP a layer forward
    assert abs(flops.core_flops_fwd(8192, mc) / 1e9 - 343.6) < 0.1
    pairs = 8192 * 6 * 8 / 64 / 8192  # held pairs a token and layer: 0.75
    # an expert layer's products a token: attention 13.76 M, the two
    # shared experts 17.30 M, 0.75 of a held expert 6.49 M, the router
    per_token = flops.forward_flops_per_token(mc, 8192, pairs)
    layer = 2 * (13_762_560 + 17_301_504 + 0.75 * 8_650_752 + 2048 * 64)
    assert abs(per_token - (
        5 * layer + 2 * (13_762_560 + 69_206_016) + 2 * 2048 * 20480
        + 6 * flops.core_flops_fwd(8192, mc) / 8192)) < 1.0
    step = flops.train_flops_per_sequence(mc, 8192, pairs)
    assert 22e12 < step < 23e12
    # through a latent the count is the accepted one, lanes apart
    xing = config_from_dict(catalog.load_config(
        "xing4_0_29b_a4b_ep8")["framework"]).model
    assert flops.direct_query_flops_fwd_per_token(xing) == 0.0
    assert flops.train_flops_per_sequence(xing, 4096, 0.0625) == \
        accepted.train_flops_per_sequence(xing, 4096, 0.0625)
    assert flops.balance_ops_fwd_per_token(mc) == 3 * 64 + 2 * 6 * 64
    assert flops.balance_ops_fwd_per_token(xing) == 0.0


def test_the_cell_of_record_finds_its_files_and_refuses_off_a_tpu():
    cell = catalog.find_cell(CELL)
    assert (cell.config, cell.traffic, cell.chips, cell.of_record) == (
        "moonlight_16b_a3b_ep8", "packed_tokens_8k_mla", 1, True)
    traffic = catalog.load_traffic(cell.traffic)
    config = catalog.load_config(cell.config)
    assert traffic["seq_len"] == config["framework"]["train"]["window"] == 8192
    assert traffic["seq_len"] == config["max_position_embeddings"]
    assert (traffic["train_sequences"], traffic["val_sequences"],
            traffic["test_sequences"], traffic["sequences_per_step"],
            traffic["setup_epochs"], traffic["trace_steps"]) == (
        8, 1, 1, 1, 2, 6)
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
                   if r["name"] == "Moonlight-16B-A3B")
    config = catalog.load_config("moonlight_16b_a3b_ep8")
    assert config["source"] == row["source_url"]
    assert sorted(config["reduced"]) == sorted(config["published"])
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value, key
            assert config[key] == config["held_here"][key] != value, key
        else:
            assert config[key] == value, key
    model = config["framework"]["model"]
    assert row["config"]["q_lora_rank"] is None and model["q_lora_rank"] == 0
    assert row["config"]["seq_aux"] is True and model["moe_seq_aux_alpha"] > 0
    assert "rope_scaling" not in row["config"] and "rope_factor" not in model
    for ours, theirs in (
            ("hidden_size", "hidden_size"), ("n_heads", "num_attention_heads"),
            ("kv_lora_rank", "kv_lora_rank"),
            ("qk_nope_head_dim", "qk_nope_head_dim"),
            ("qk_rope_head_dim", "qk_rope_head_dim"),
            ("v_head_dim", "v_head_dim"), ("ffn_size", "intermediate_size"),
            ("moe_ffn_size", "moe_intermediate_size"),
            ("moe_top_k", "num_experts_per_tok"),
            ("moe_shared_experts", "n_shared_experts"),
            ("moe_routed_scaling", "routed_scaling_factor"),
            ("first_dense_layers", "first_k_dense_replace"),
            ("rope_theta", "rope_theta"), ("rms_norm_eps", "rms_norm_eps")):
        assert model[ours] == row["config"][theirs], ours
    assert model["moe_experts"] == row["config"]["n_routed_experts"]
    assert model["experts_held"][1] == config["n_routed_experts"] == 8
    assert model["vocab_size"] * 8 == row["config"]["vocab_size"]
