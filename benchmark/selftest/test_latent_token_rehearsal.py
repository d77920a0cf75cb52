"""``drivers/train_latent_token_epochs.py`` rehearsed end to end on the
CPU: a tiny latent-attention cell (latent attention, four lanes, a
sigmoid router with a shared expert and a selection bias, a dense and
two expert layers), its configuration and its traffic are dropped into a
temporary root (``FMDA_BENCH_ROOTS``) and found with no edit; the run
trains, compares itself with the plain reference
(``reference/latent_decoder.py``) and reports ``correct``; a traced run
reads the per-layer metrics that need no device; each deliberately wrong
reference is not correct; the counting functions agree with the issue's
arithmetic; the configuration's file copies the catalog's row."""

import json
import os

import pytest

from benchmark.harness import catalog, latent_decoder_flops as flops
from benchmark.harness.token_corpus import make_token_stream
from benchmark.selftest.test_rehearsal import rehearsal_result, run_cell

SEQ = 64
#: One Sinkhorn turn, so that the reference with a turn fewer runs none:
#: from a fresh block's logits every turn after the first changes nothing
#: a float32 can show (the matrix is the identity to 6e-6 after one), so
#: a dropped 20th turn moves no output; what holds the program to its
#: stated count is tests/test_latent_decoder.py (a matrix that settles
#: slowly: 20 turns against 19).
CONFIG = {"name": "tiny_latent_decoder", "framework": {
    "model": {"cell": "decoder", "hidden_size": 64, "n_heads": 4,
              "vocab_size": 256, "layer_layout": [4, 4, 4],
              "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
              "qk_rope_head_dim": 8, "v_head_dim": 16, "rope_theta": 10000.0,
              "rope_factor": 64.0, "rope_original_max": 32,
              "rms_norm_eps": 1e-06, "moe_experts": 8, "moe_top_k": 2,
              "moe_ffn_size": 32, "experts_held": [0, 4],
              "hidden_act": "silu", "ffn_size": 96, "first_dense_layers": 1,
              "moe_shared_experts": 1, "moe_scoring": "sigmoid",
              "moe_routed_scaling": 2.0, "moe_bias_rate": 0.001,
              "hc_streams": 4, "hc_sinkhorn_iters": 1, "loss_chunk": 32,
              "dtype": "float32", "remat": True, "dropout": 0.0},
    "train": {"batch_size": 1, "window": SEQ, "chunk_size": SEQ,
              "learning_rate": 0.00002, "clip": 1.0, "val_size": 0.05,
              "test_size": 0.09, "cache_chunks": 16}}}
TRAFFIC = {"kind": "train_latent_token_epochs", "seq_len": SEQ,
           "sequences_per_step": 1, "train_sequences": 8,
           "val_sequences": 1, "test_sequences": 1, "zipf_exponent": 1.0,
           "doc_median_tokens": 40, "doc_sigma": 1.0, "eod_id": 0,
           "setup_epochs": 2, "trace_steps": 6}
NEW_READERS = ("mla_proj_dev_share", "mla_core_dev_share",
               "mla_core_roofline", "hc_mix_dev_share", "hc_mix_roofline",
               "moe_shared_dev_share", "latent_train_mfu")


def _root(tmp_path):
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "cells.json").write_text(json.dumps({"workloads": [{
        "name": "tiny_latent_token_train", "config": "tiny_latent_decoder",
        "traffic": "tiny_packed_tokens"}]}))
    (tmp_path / "configs" / "tiny_latent_decoder.json").write_text(
        json.dumps(CONFIG))
    (tmp_path / "traffic" / "tiny_packed_tokens.json").write_text(
        json.dumps(TRAFFIC))
    return {catalog.ROOTS_ENV: str(tmp_path)}


def test_latent_driver_runs_end_to_end_and_agrees_with_the_reference(
        tmp_path):
    proc = run_cell("tiny_latent_token_train", trace=1,
                    extra_env=_root(tmp_path))
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    result = rehearsal_result(proc)
    assert result["correct"] is True, proc.stderr[-4000:]
    assert result["failed"] == 0 and result["attempted"] > 0
    metrics = result["metrics"]
    assert metrics["train_dispatch_us"]["value"] > 0
    # what needs a device is left out, not 0; the readers keyed to
    # another family's record stay silent
    for name in ("latent_train_mfu", "mla_core_roofline", "hc_mix_roofline",
                 "moe_train_mfu", "moe_expert_load_imbalance",
                 "sparse_train_mfu", "hybrid_train_mfu", "train_mfu",
                 "attention_roofline", "moe_experts_roofline"):
        assert name not in metrics, name
    checks = next(json.loads(line)["checks"]
                  for line in proc.stderr.splitlines()
                  if line.startswith('{"checks"'))
    assert checks["held_pairs_ok"] and checks["hc_sums_ok"]
    assert checks["bias_ok"] and checks["loss_fell"]
    assert checks["moe_pairs_dropped_total"] == 0
    # two expert layers' held pairs a step, about 64 x 2 x 4 / 8 each
    assert len(checks["held_pairs_per_step_by_pass"][0]) == 2
    # the quiet leaves are the ones the driver's note names
    quiet = set(checks["grad_quiet_leaves_over_whole"])
    assert {"block_1/router_bias", "block_2/router_bias",
            "block_2/hc_ffn_p_res", "block_0/hc_attn_b_pre"} <= quiet
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
    ({"sinkhorn_turns_less": 1}, False),       # a Sinkhorn turn fewer
    ({"router": "softmax"}, False),            # the other router's gates
    ({"skip_shared": True}, False)],           # the shared expert left out
    ids=lambda v: "-".join(map(str, v.values())) if isinstance(v, dict)
    else str(v))
def test_a_deliberately_wrong_reference_is_not_correct(
        trained_tiny, reference_kw, agrees):
    """(A Sinkhorn or a softmax in bfloat16 are the wrong runs this size
    and float32 program cannot place between limits read on the chip:
    the published size's readings are in PERF.md section 6, PR 41.)"""
    from benchmark.drivers import train_latent_token_epochs as driver

    trainer, params, dataset, val, first, rng = trained_tiny

    class Ctx:
        say = staticmethod(lambda record: None)

    checks = driver.reference_checks(
        Ctx, trainer, [params], dataset, val, first, rng,
        reference_kw=reference_kw)
    failed = [k for k in driver.REFERENCE_DECIDES if not checks[k]]
    sums_ok = checks["hc_sum_error_reference"] <= driver.HC_SUM_BOUND
    assert (not failed and sums_ok) == agrees, (
        failed, checks["grad_rel_diff_worst"],
        checks["hc_sum_error_reference"])


def test_every_leaf_of_the_model_has_a_group():
    import jax

    from benchmark.drivers import train_latent_token_epochs as driver
    from fmda_tpu.config import config_from_dict
    from fmda_tpu.models import build_model

    assert set(driver.GRAD_GROUP.values()) | {"mixing"} == set(
        driver.GRAD_REL_DIFF)
    mc = config_from_dict(CONFIG["framework"]).model
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


def test_the_counting_functions_give_the_issues_arithmetic():
    from fmda_tpu.config import config_from_dict

    mc = config_from_dict(catalog.load_config(
        "xing4_0_29b_a4b_ep8")["framework"]).model
    assert (flops.dense_layers(mc), flops.expert_layers(mc)) == (1, 4)
    # forward, a token: latent projections 56.8 M, the core at a mean of
    # 2,048 keys 41.9 M, shared expert 22 M, held routed (256 pairs a
    # step: 1/16 a token) 1.4 M... of an expert layer
    assert abs(flops.projection_flops_fwd_per_token(mc) / 1e6 - 56.8) < 0.1
    assert abs(flops.core_flops_fwd(4096, mc) / 4096 / 1e6 - 41.9) < 0.1
    assert flops.mixing_flops_fwd_per_token(mc) == 2 * 14336 * 24
    pairs = 4096 * 4 * 8 / 64 / 4096  # held pairs a token and layer
    per_token = flops.forward_flops_per_token(mc, 4096, pairs)
    assert 0.9e9 < per_token < 1.0e9
    step = flops.train_flops_per_sequence(mc, 4096, pairs)
    assert 11e12 < step < 12.5e12
    # the core over causal pairs by brute force, at a small size
    small = config_from_dict(CONFIG["framework"]).model
    causal = sum(1 for i in range(SEQ) for j in range(i + 1))
    assert flops.core_flops_fwd(SEQ, small) == causal * 4 * (
        2 * (16 + 8) + 2 * 16)
    # the stream's bytes: four lanes read and written a sublayer
    lanes = 4096 * 4 * 3584 * 2
    one = 4096 * 3584 * 2
    assert flops.mixing_bytes_step(mc, 4096) == 2 * 5 * (
        5 * lanes + 4 * one)


def test_the_cell_of_record_finds_its_files_and_refuses_off_a_tpu():
    cell = catalog.find_cell("xing_train_4k")
    assert (cell.config, cell.traffic, cell.chips, cell.of_record) == (
        "xing4_0_29b_a4b_ep8", "packed_tokens_4k", 1, True)
    traffic = catalog.load_traffic(cell.traffic)
    config = catalog.load_config(cell.config)
    assert traffic["seq_len"] == config["framework"]["train"]["window"] == 4096
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
        assert manifest[name]["workloads"] == ["xing_train_4k"]
    proc = run_cell("xing_train_4k")
    assert proc.returncode == 3 and proc.stdout.strip() == ""
    assert "runs on a TPU" in proc.stderr


def test_the_configuration_file_copies_the_catalogs_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("no catalog beside the guides here")
    with open(path) as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "Xing4.0-29B-A4B")
    config = catalog.load_config("xing4_0_29b_a4b_ep8")
    assert config["source"] == row["source_url"]
    assert sorted(config["reduced"]) == sorted(config["published"])
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value, key
            assert config[key] == config["held_here"][key] != value, key
        else:
            assert config[key] == value, key
    model = config["framework"]["model"]
    for ours, theirs in (
            ("hidden_size", "hidden_size"), ("n_heads", "num_attention_heads"),
            ("q_lora_rank", "q_lora_rank"), ("kv_lora_rank", "kv_lora_rank"),
            ("qk_nope_head_dim", "qk_nope_head_dim"),
            ("qk_rope_head_dim", "qk_rope_head_dim"),
            ("v_head_dim", "v_head_dim"), ("ffn_size", "intermediate_size"),
            ("moe_ffn_size", "moe_intermediate_size"),
            ("moe_top_k", "num_experts_per_tok"),
            ("moe_shared_experts", "n_shared_experts"),
            ("moe_routed_scaling", "routed_scaling_factor"),
            ("hc_streams", "hc_mult"),
            ("hc_sinkhorn_iters", "hc_sinkhorn_iters"),
            ("hc_eps", "hc_eps"), ("hc_res_clamp", "mhc_h_res_clamp_max"),
            ("rope_theta", "rope_theta"), ("rms_norm_eps", "rms_norm_eps")):
        assert model[ours] == row["config"][theirs], ours
    assert model["moe_experts"] == config["published"]["n_routed_experts"]
    scaling = row["config"]["rope_scaling"]
    assert (model["rope_factor"], model["rope_beta_fast"],
            model["rope_beta_slow"], model["rope_original_max"]) == (
        scaling["factor"], scaling["beta_fast"], scaling["beta_slow"],
        scaling["original_max_position_embeddings"])
