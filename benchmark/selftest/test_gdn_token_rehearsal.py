"""``drivers/train_gdn_token_epochs.py`` rehearsed end to end on the CPU:
a tiny Olmo-Hybrid-shaped cell (gated-delta-rule layers with one decay a
head, ``b`` in 0..2 and keys and values of different widths beside an
unrotated full-attention layer under whole-width q/k norms, a dense MLP,
the block's norms on the sublayers' outputs), its configuration and its
traffic are dropped into a temporary root (``FMDA_BENCH_ROOTS``) and
found with no edit; the run trains, compares itself with the plain
reference (``reference/gdn_decoder.py``) and reports ``correct``; a
traced run reads the per-layer metrics that need no device; each
deliberately wrong reference is not correct; the counting functions
agree with the issue's arithmetic; the configuration's file copies the
catalog's row."""

import json
import os

import numpy as np
import pytest

from benchmark.harness import catalog, gdn_decoder_flops as flops
from benchmark.harness.token_corpus import make_token_stream
from benchmark.selftest.test_rehearsal import rehearsal_result, run_cell

SEQ = 64
CELL = "olmo_hybrid_train_8k"
CONFIG = {"name": "tiny_gdn_decoder", "framework": {
    "model": {"cell": "decoder", "hidden_size": 64, "n_heads": 4,
              "n_kv_heads": 4, "head_dim": 16, "vocab_size": 256,
              "layer_layout": [6, 6, 6, 0], "gdn_heads": 4,
              "gdn_key_dim": 8, "gdn_value_dim": 16, "gdn_conv": 4,
              "gdn_chunk": 16, "gdn_beta_scale": 2.0, "post_norm": True,
              "qk_norm_whole": True, "rms_norm_eps": 1e-06,
              "moe_experts": 0, "ffn_size": 96, "hidden_act": "silu",
              "loss_chunk": 32, "dtype": "float32", "remat": True,
              "dropout": 0.0},
    "train": {"batch_size": 1, "window": SEQ, "chunk_size": SEQ,
              "learning_rate": 0.00002, "clip": 1.0, "val_size": 0.05,
              "test_size": 0.09, "cache_chunks": 16}}}
TRAFFIC = {"kind": "train_gdn_token_epochs", "seq_len": SEQ,
           "sequences_per_step": 1, "train_sequences": 8,
           "val_sequences": 1, "test_sequences": 1, "zipf_exponent": 1.0,
           "doc_median_tokens": 40, "doc_sigma": 1.0, "eod_id": 0,
           "setup_epochs": 2, "trace_steps": 6}
NEW_READERS = ("gdn_mixer_dev_share", "gdn_scan_dev_share",
               "gdn_scan_roofline", "gdn_attention_roofline",
               "gdn_train_mfu")
#: The accepted per-layer metrics the cell is listed for: those whose
#: readers need no count of the layers and no other family's record.
LISTED = (
    "input_stall_share", "train_device_idle_share", "train_peak_hbm_mb",
    "train_step_dev_ms", "train_dispatch_us", "train_fold_us",
    "train_loop_self_us", "train_next_batch_us", "attention_dev_share",
    "lm_head_dev_share", "dense_mlp_dev_share", "train_pass_ms_per_step",
    "eval_pass_ms_per_step", "eval_pass_share", "epoch_turnaround_share",
    "train_step_temp_hbm_mb", "train_step_reserved_hbm_mb")


def _root(tmp_path):
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "cells.json").write_text(json.dumps({"workloads": [{
        "name": "tiny_gdn_token_train", "config": "tiny_gdn_decoder",
        "traffic": "tiny_packed_tokens"}]}))
    (tmp_path / "configs" / "tiny_gdn_decoder.json").write_text(
        json.dumps(CONFIG))
    (tmp_path / "traffic" / "tiny_packed_tokens.json").write_text(
        json.dumps(TRAFFIC))
    return {catalog.ROOTS_ENV: str(tmp_path)}


def test_gdn_driver_runs_end_to_end_and_agrees_with_the_reference(tmp_path):
    proc = run_cell("tiny_gdn_token_train", trace=1,
                    extra_env=_root(tmp_path))
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    result = rehearsal_result(proc)
    assert result["correct"] is True, proc.stderr[-4000:]
    assert result["failed"] == 0 and result["attempted"] > 0
    metrics = result["metrics"]
    assert metrics["train_dispatch_us"]["value"] > 0
    # what needs a device is left out, not 0; the readers keyed to
    # another family's record stay silent
    for name in ("gdn_train_mfu", "gdn_scan_roofline",
                 "gdn_attention_roofline", "kda_train_mfu",
                 "kda_scan_roofline", "kda_attention_roofline",
                 "hybrid_train_mfu", "hybrid_attention_roofline",
                 "ssd_scan_roofline", "mla_train_mfu", "latent_train_mfu",
                 "moe_train_mfu", "sparse_train_mfu", "train_mfu",
                 "attention_roofline", "moe_experts_roofline"):
        assert name not in metrics, name
    assert '"failed_comparisons"' not in proc.stderr
    checks = next(json.loads(line)["checks"]
                  for line in proc.stderr.splitlines()
                  if line.startswith('{"checks"'))
    assert checks["walk_ok"] and checks["loss_fell"]
    # three delta-rule layers' walks, four chunks of sixteen each
    assert checks["gdn_positions_per_train_step"] == [SEQ] * 3
    assert checks["gdn_chunks_per_train_step"] == [4] * 3
    assert all(v > 0 for v in checks["gdn_log_decay_absmax_by_pass"][-1])
    # the correction overshoots somewhere in every layer
    assert all(1.0 < v <= 2.0 for v in checks["gdn_beta_max_by_pass"][-1])
    assert all(1.0 < v <= 2.0 for v in checks["first_step_gdn_beta_max"][:3])
    # every comparison that decides has its reading beside its limit
    from benchmark.drivers import train_gdn_token_epochs as driver

    assert set(checks["readings_beside_limits"]) == set(
        driver.REFERENCE_DECIDES)
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
    ({"overshoot": False}, False),            # b = sigmoid, not 2 sigmoid
    ({"qk_norm": False}, False),              # q and k not normalised
    ({"gate": "sigmoid"}, False),             # the gate under sigmoid
    ({"pre_norm": True}, False),              # the block pre-norm
    ({"qk_rms": False}, False),               # no q/k RMSNorm
    ({"rotary": True}, False),                # rotary in the attention layer
    ({"products_as": "float8_e5m2"}, False)],  # every product's operands
    ids=lambda v: "-".join(map(str, v.values())) if isinstance(v, dict)
    else str(v))
def test_a_deliberately_wrong_reference_is_not_correct(
        trained_tiny, reference_kw, agrees):
    """(A state in bfloat16 is the wrong run this size cannot place
    between limits read on the chip: over 64 positions its error is under
    the limits.  The published size's readings are in PERF.md section 6,
    PR 54.)"""
    from benchmark.drivers import train_gdn_token_epochs as driver

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

    from benchmark.drivers import train_gdn_token_epochs as driver
    from fmda_tpu.config import config_from_dict
    from fmda_tpu.models import build_model

    assert set(driver.GRAD_GROUP.values()) | set(
        driver.GDN_LEAVES.values()) | {"attention"} == set(
        driver.GRAD_REL_DIFF)
    for framework in (CONFIG["framework"], catalog.load_config(
            "olmo_hybrid_7b_tp2")["framework"]):
        mc = config_from_dict(framework).model
        shapes = jax.eval_shape(
            lambda key: build_model(mc).init(
                {"params": key}, jax.numpy.zeros((1, 8), "int32"))["params"],
            jax.random.PRNGKey(0))
        for path, _ in jax.tree_util.tree_leaves_with_path(shapes):
            assert driver._group(driver._leaf_name(path), mc.layer_layout
                                 ) in driver.GRAD_REL_DIFF, path
    layout = (6, 6, 6, 0)
    # wq, wk, wv and wo are both mixers' names: by the kind of the block
    assert driver._group("block_2/wq", layout) == "gdn_qk"
    assert driver._group("block_2/conv_k", layout) == "gdn_qk"
    assert driver._group("block_2/wo", layout) == "gdn"
    assert driver._group("block_3/wq", layout) == "attention"
    assert driver._group("block_3/q_norm", layout) == "attention"
    for leaf in ("a_log", "dt_bias", "wa", "wb", "conv_v", "o_norm"):
        assert driver._group(f"block_0/{leaf}", layout) == "gdn_small"
    assert driver._group("block_0/w_up", layout) == "mlp"
    assert driver._group("block_3/ln_attn", layout) == "norms"
    assert driver._group("head", layout) == "embed"
    # the published size's parameter count is the file's and the issue's
    total = sum(int(np.prod(leaf.shape))
                for leaf in jax.tree.leaves(shapes))
    assert total == 766_241_946
    assert "766,241,946" in catalog.load_config(
        "olmo_hybrid_7b_tp2")["size_on_the_chip"]


def test_the_counting_functions_give_the_issues_arithmetic():
    from fmda_tpu.config import config_from_dict

    mc = config_from_dict(catalog.load_config(
        "olmo_hybrid_7b_tp2")["framework"]).model
    assert (flops.gdn_layers(mc), flops.attention_layers(mc)) == (3, 1)
    # a delta-rule layer's products: q, k 3840 x 1440, v, gate, output
    # 3840 x 2880, two of 3840 x 15: 88.8 MFLOP a token
    assert flops.gdn_projection_flops_fwd_per_token(mc) == 2 * 3840 * (
        2 * 1440 + 3 * 2880 + 2 * 15)
    # its walk: 32.5 causal pairs a position x 2 x (96 + 96 + 288 + 192)
    # + 3 state products of 2 x 96 x 192, a head: ~3 MFLOP a token
    per_token = flops.scan_flops_fwd(8192, 15, 96, 192, 64) / 8192
    assert per_token == 15 * (32.5 * 2 * 672 + 3 * 2 * 96 * 192)
    pairs = sum(1 for i in range(64) for j in range(i + 1))
    assert flops.chunk_pairs(64) == pairs == 2080
    # the one attention layer's core over the causal triangle, not four
    assert flops.attention_cores_flops_step(mc, 8192) == 3.5 * 4.0 * (
        8192 * 8193 // 2) * 15 * 128
    forward = flops.forward_flops_per_token(mc, 8192)
    mixers = 3 * (flops.gdn_projection_flops_fwd_per_token(mc) + per_token)
    mlps = 4 * 6.0 * 3840 * 11008
    assert 1.45e9 < forward < 1.49e9          # the issue's ~1,470 MFLOP
    assert 0.17 < mixers / forward < 0.20     # its 19 %
    assert 0.68 < mlps / forward < 0.70       # its 69 %
    assert 35e12 < flops.train_flops_per_sequence(mc, 8192) < 37e12
    # the walk's least traffic: a chunk's state written once and read once
    assert flops.scan_bytes_step(8192, 15, 96, 192, 64) == 3.0 * (
        8192 * 15 * ((96 + 96 + 192) * 2 + 8 + 192 * 4)
        + 2.0 * 128 * 15 * 96 * 192 * 4)


def test_the_new_readers_give_nothing_on_a_record_without_the_layer():
    """On the parent's program, and in every other cell, the new readers
    find no ``gdn`` record and no ``gdn_*`` scope: they return nothing
    and do not raise."""
    metrics = catalog.load_layer_metrics()
    record = {"end_to_end": {"train_samples_per_s": 1.0},
              "device": {"platform": "tpu", "kind": "TPU v5 lite",
                         "count": 1},
              "kda": {"seq_len": 8192}, "hybrid": {"seq_len": 8192},
              "program_spans": None}
    for name in NEW_READERS:
        assert metrics[name].module.read(dict(record)) is None, name


def test_the_new_scope_readers_read_a_hand_built_trace(monkeypatch):
    from benchmark.harness import program_spans

    step, fwd = "jit_train_step", "jvp(forward)/MoEDecoder/block_0/"
    bwd = ("transpose(jvp(forward))/MoEDecoder/block_0/"
           "rematted_computation/checkpoint/")
    busy = {
        (step, fwd + "gdn_mixer/gdn_proj", "fusion.1"): 2.0,
        (step, fwd + "gdn_mixer/gdn_scan/kda_intra", "fusion.2"): 1.0,
        (step, bwd + "gdn_mixer/gdn_scan/while/body/kda_solve", "f.3"): 2.0,
        (step, bwd + "gdn_mixer", "fusion.4"): 1.0,   # the block's norm
        (step, fwd + "dense_mlp", "fusion.5"): 10.0,
        (step, fwd + "attention/attention_full", "flash_fwd"): 2.0,
        (step, "optimizer", "fusion.6"): 2.0,
        ("jit_eval_step", fwd + "gdn_mixer/gdn_scan", "fusion.7"): 50.0,
    }
    monkeypatch.setattr(program_spans, "for_record",
                        lambda record: record["program_spans"])
    metrics = catalog.load_layer_metrics()
    record = {"end_to_end": {"train_samples_per_s": 1.0},
              "device": {"platform": "tpu", "kind": "TPU v5 lite",
                         "count": 1},
              "program_spans": {"busy_by_scope": busy}}
    got = {name: metrics[name].module.read(record) for name in (
        "gdn_mixer_dev_share", "gdn_scan_dev_share", "dense_mlp_dev_share",
        "attention_dev_share")}
    assert got == {"gdn_mixer_dev_share": 30.0, "gdn_scan_dev_share": 15.0,
                   "dense_mlp_dev_share": 50.0, "attention_dev_share": 10.0}
    # the accepted delta-rule cell's readers find nothing under gdn_*
    for name in ("kda_mixer_dev_share", "kda_scan_dev_share"):
        assert metrics[name].module.read(record) is None, name


def test_the_cell_of_record_finds_its_files_and_refuses_off_a_tpu():
    cell = catalog.find_cell(CELL)
    assert (cell.config, cell.traffic, cell.chips, cell.of_record) == (
        "olmo_hybrid_7b_tp2", "packed_tokens_8k_gdn", 1, True)
    traffic = catalog.load_traffic(cell.traffic)
    config = catalog.load_config(cell.config)
    assert traffic["seq_len"] == config["framework"]["train"]["window"] == 8192
    docs = catalog.load_traffic("packed_docs_8k")
    for key in ("seq_len", "sequences_per_step", "train_sequences",
                "val_sequences", "test_sequences", "zipf_exponent",
                "doc_median_tokens", "doc_sigma", "eod_id", "setup_epochs",
                "trace_steps"):  # granite_h_train_8k's numbers, key for key
        assert traffic[key] == docs[key], key
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
    # in no list of another family's readers, nor of the one that divides
    # by all the layers (one core over four: 0.25)
    assert "attention_core_fwd_runs_per_layer" not in listing
    assert not [n for n in listing if n.startswith(
        ("kda_", "hybrid_", "ssd_", "ssm_"))]
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
                   if r["name"] == "Olmo-Hybrid-7B")
    config = catalog.load_config("olmo_hybrid_7b_tp2")
    assert config["source"] == row["source_url"]
    assert sorted(config["reduced"]) == sorted(config["published"])
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value, key
            assert config[key] == config["held_here"][key] != value, key
        else:
            assert config[key] == value, key  # nested groups whole
    # no width is among the cuts
    assert not [k for k in config["reduced"] if k.endswith("_dim")
                or "size" in k and k != "vocab_size"]
    model, published = config["framework"]["model"], row["config"]
    assert model["layer_layout"] == [
        6 if kind == "linear_attention" else 0
        for kind in published["layer_types"][:4]] == [6, 6, 6, 0]
    assert published["rope_parameters"] == {"rope_theta": None}
    assert published["linear_allow_neg_eigval"] is True
    assert model["gdn_beta_scale"] == 2.0
    for ours, theirs in (
            ("hidden_size", "hidden_size"), ("ffn_size", "intermediate_size"),
            ("gdn_key_dim", "linear_key_head_dim"),
            ("gdn_value_dim", "linear_value_head_dim"),
            ("gdn_conv", "linear_conv_kernel_dim"),
            ("rms_norm_eps", "rms_norm_eps"), ("hidden_act", "hidden_act")):
        assert model[ours] == published[theirs], ours
    assert model["head_dim"] * published["num_attention_heads"] \
        == published["hidden_size"]
    # half the heads of both mixers, an eighth of the vocabulary
    assert model["gdn_heads"] * 2 == published["linear_num_key_heads"] \
        == published["linear_num_value_heads"]
    assert model["n_heads"] * 2 == published["num_attention_heads"]
    assert model["n_kv_heads"] * 2 == published["num_key_value_heads"]
    assert model["vocab_size"] * 8 == published["vocab_size"]
    assert config["num_hidden_layers"] == len(model["layer_layout"]) == 4
    assert not model.get("tie_embeddings", False)
    assert published["tie_word_embeddings"] is False
