"""``drivers/train_sparse_token_epochs.py`` rehearsed end to end on the
CPU: a tiny learned-sparse decoder cell, its configuration and its
traffic are dropped into a temporary root (``FMDA_BENCH_ROOTS``) and
found with no edit; the run trains, compares itself with the plain
reference (``reference/sparse_decoder.py``) and reports ``correct``; a
traced run reads the per-layer metrics that need no device; a
deliberately wrong reference is not correct; the counting functions
agree with pairs counted by brute force."""

import json

import pytest

from benchmark.harness import catalog, sparse_decoder_flops as flops
from benchmark.harness.token_corpus import make_token_stream
from benchmark.selftest.test_rehearsal import rehearsal_result, run_cell

SEQ, TOPK = 64, 8
CONFIG = {"name": "tiny_sparse_decoder", "framework": {
    "model": {"cell": "decoder", "hidden_size": 64, "n_heads": 4,
              "n_kv_heads": 2, "head_dim": 16, "vocab_size": 256,
              "layer_layout": [2, 2], "rope_theta": 10000000.0,
              "moe_experts": 8, "moe_top_k": 2, "moe_ffn_size": 32,
              "experts_held": [2, 4], "hidden_act": "silu",
              "indexer_heads": 2, "indexer_head_dim": 8,
              "indexer_topk": TOPK, "loss_chunk": 32, "dtype": "float32",
              "remat": True, "dropout": 0.0},
    "train": {"batch_size": 1, "window": SEQ, "chunk_size": SEQ,
              "learning_rate": 0.00002, "clip": 1.0, "val_size": 0.05,
              "test_size": 0.09, "cache_chunks": 16}}}
TRAFFIC = {"kind": "train_sparse_token_epochs", "seq_len": SEQ,
           "sequences_per_step": 1, "train_sequences": 8,
           "val_sequences": 1, "test_sequences": 1, "zipf_exponent": 1.0,
           "doc_median_tokens": 40, "doc_sigma": 1.0, "eod_id": 0,
           "setup_epochs": 2, "trace_steps": 6}


def _root(tmp_path):
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "cells.json").write_text(json.dumps({"workloads": [{
        "name": "tiny_sparse_token_train", "config": "tiny_sparse_decoder",
        "traffic": "tiny_packed_sparse_tokens"}]}))
    (tmp_path / "configs" / "tiny_sparse_decoder.json").write_text(
        json.dumps(CONFIG))
    (tmp_path / "traffic" / "tiny_packed_sparse_tokens.json").write_text(
        json.dumps(TRAFFIC))
    return {catalog.ROOTS_ENV: str(tmp_path)}


def test_sparse_driver_runs_end_to_end_and_agrees_with_the_reference(
        tmp_path):
    proc = run_cell("tiny_sparse_token_train", trace=1,
                    extra_env=_root(tmp_path))
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    result = rehearsal_result(proc)
    assert result["correct"] is True, proc.stderr[-4000:]
    assert result["failed"] == 0 and result["attempted"] > 0
    metrics = result["metrics"]
    # what needs no device is read; what needs one is left out, not 0
    kept = flops.picked_pairs(SEQ, TOPK) / flops.causal_pairs(SEQ)
    assert abs(metrics["sparse_keys_kept_share"]["value"]
               - 100.0 * kept) < 1e-9
    assert metrics["train_dispatch_us"]["value"] > 0
    for name in ("sparse_train_mfu", "sparse_attention_roofline",
                 "sparse_indexer_roofline", "sparse_select_roofline",
                 "moe_train_mfu", "moe_expert_load_imbalance", "train_mfu",
                 "attention_roofline", "moe_experts_roofline"):
        assert name not in metrics, name
    checks = next(json.loads(line)["checks"]
                  for line in proc.stderr.splitlines()
                  if line.startswith('{"checks"'))
    assert checks["val_keys_kept"] == [flops.picked_pairs(SEQ, TOPK)] * 2
    assert checks["window_kept_ok"] is True


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
    ({"topk": TOPK // 2}, False),              # half the keys
    ({"dense_attention": True}, False),        # every causal key
    ({"indexer_relu": False}, False),          # another score
    ({"products_as": "float8_e5m2"}, False),   # one precision lower
    ({"skip_expert": 1}, False)],              # one held expert short
    ids=lambda v: "-".join(v) if isinstance(v, dict) else str(v))
def test_a_deliberately_wrong_reference_is_not_correct(
        trained_tiny, reference_kw, agrees):
    from benchmark.drivers import train_sparse_token_epochs as driver

    trainer, params, dataset, val, first, rng = trained_tiny

    class Ctx:
        say = staticmethod(lambda record: None)

    checks = driver.reference_checks(
        Ctx, trainer, [params], dataset, val, first, rng,
        reference_kw=reference_kw)
    failed = [k for k in driver.REFERENCE_DECIDES if not checks[k]]
    assert (not failed) == agrees, (failed, checks["grad_rel_diff_worst"])
    if reference_kw and set(reference_kw) & {
            "topk", "dense_attention", "indexer_relu"}:
        # the selection's own comparison catches another selection
        assert {"val_selection_ok", "first_selection_ok"} & set(failed)


def test_the_counting_functions_agree_with_pairs_counted_by_brute_force():
    for seq, topk in ((40, 8), (64, 64), (16, 100), (384, 128)):
        causal = sum(1 for t in range(seq) for s in range(t + 1))
        picked = sum(min(t + 1, topk) for t in range(seq))
        assert flops.causal_pairs(seq) == causal
        assert flops.picked_pairs(seq, topk) == picked
        assert flops.indexer_score_flops(seq, 16, 64) == causal * 16 * 64 * 2
        assert flops.sparse_core_flops_fwd(seq, topk, 32, 128) \
            == picked * 32 * 128 * 4
        assert flops.sparse_core_flops_step(seq, topk, 32, 128) \
            == 3.5 * picked * 32 * 128 * 4
        assert flops.select_bytes(seq) == causal * 5
    # the published cell: 31,458,304 of 134,225,920 pairs, 23.44 %
    assert flops.picked_pairs(16384, 2048) == 31_458_304
    assert flops.causal_pairs(16384) == 134_225_920


def test_the_whole_steps_count_holds_the_indexer_once():
    from fmda_tpu.config import config_from_dict

    mc = config_from_dict(catalog.load_config(
        "keye_vl2_30b_a3b_ep8")["framework"]).model
    seq = 16384
    whole = flops.train_flops_per_sequence(mc, seq, 1.0)
    indexer = 4 * flops.indexer_flops_per_sequence(mc, seq)
    rest = 3.0 * seq * flops.forward_flops_per_token(mc, seq, 1.0)
    assert whole == rest + indexer
    # cores over the picked pairs: 23.4 % of what the causal triangle costs
    dense = flops.sparse_core_flops_fwd(seq, seq, mc.n_heads, mc.head_dim)
    assert abs(flops.sparse_core_flops_fwd(
        seq, 2048, mc.n_heads, mc.head_dim) / dense - 0.2344) < 1e-4


def test_the_cell_of_record_finds_its_files_and_refuses_off_a_tpu():
    cell = catalog.find_cell("keye_train_16k")
    assert (cell.config, cell.traffic, cell.chips, cell.of_record) == (
        "keye_vl2_30b_a3b_ep8", "packed_tokens_16k", 1, True)
    traffic = catalog.load_traffic(cell.traffic)
    config = catalog.load_config(cell.config)
    assert traffic["seq_len"] == config["framework"]["train"]["window"]
    assert catalog.load_driver(traffic["kind"]).END_TO_END == {
        "train_samples_per_s": "samples/s"}
    proc = run_cell("keye_train_16k")
    assert proc.returncode == 3 and proc.stdout.strip() == ""
    assert "runs on a TPU" in proc.stderr


def test_new_readers_stay_silent_without_the_drivers_facts():
    metrics = catalog.load_layer_metrics()
    record = {"end_to_end": {"train_samples_per_s": 1.0},
              "device": {"platform": "tpu", "kind": "TPU v5 lite",
                         "count": 1},
              "program_spans": None}
    for name in ("sparse_indexer_dev_share", "sparse_select_dev_share",
                 "sparse_attention_dev_share", "sparse_indexer_roofline",
                 "sparse_select_roofline", "sparse_attention_roofline",
                 "sparse_train_mfu", "sparse_keys_kept_share"):
        assert metrics[name].module.read(dict(record)) is None, name
