"""The decoder family's learned-sparse layer (``layer_layout`` 2) against
its plain reference (benchmark/reference/sparse_decoder.py) at a small
size on the CPU, seeded random weights, float32: logits, loss, gradient
and the selection itself; the dense limit; the reference's deliberately
wrong runs; what a pass counts and publishes; the configuration check
and the CLI.  The published widths are compared on the chip
(benchmark/drivers/train_sparse_token_epochs.py)."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import sparse_decoder as ref  # noqa: E402
from fmda_tpu.config import (  # noqa: E402
    FrameworkConfig, ModelConfig, TrainConfig, config_to_dict)
from fmda_tpu.data.pipeline import Batch  # noqa: E402
from fmda_tpu.data.source import TokenArraySource  # noqa: E402
from fmda_tpu.models import build_model  # noqa: E402
from fmda_tpu.models.decoder import check_decoder_config  # noqa: E402
from fmda_tpu.train.tasks import NextToken, keys_kept_counts  # noqa: E402

SEQ, VOCAB, TOPK = 40, 256, 8
KEPT = sum(min(t + 1, TOPK) for t in range(SEQ))


def small_cfg(**over):
    return ModelConfig(**{**dict(
        cell="decoder", hidden_size=64, n_heads=4, n_kv_heads=2,
        head_dim=16, vocab_size=VOCAB, layer_layout=(2, 2),
        rope_theta=1e7, moe_experts=8, moe_top_k=2, moe_ffn_size=32,
        experts_held=(0, 8), hidden_act="silu", indexer_heads=2,
        indexer_head_dim=8, indexer_topk=TOPK, loss_chunk=16,
        dtype="float32", dropout=0.0), **over})


def _params(cfg, seed=0):
    model = build_model(cfg)
    params = model.init({"params": jax.random.PRNGKey(seed)},
                        jnp.zeros((1, 8), jnp.int32))["params"]
    # wider than the family's N(0, 0.02), so that routing, the indexer
    # and attention matter at hidden 64; norm scales off one
    keys = jax.random.split(jax.random.PRNGKey(seed + 1),
                            len(jax.tree.leaves(params)))
    leaves, tree = jax.tree.flatten(params)
    wide = [1.0 + 0.1 * jax.random.normal(k, l.shape) if l.ndim == 1
            else 0.2 * jax.random.normal(k, l.shape)
            for l, k in zip(leaves, keys)]
    return model, jax.tree.unflatten(tree, wide)


def _ids(seed=3, n=SEQ + 1, batch=2):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, VOCAB, size=(batch, n)).astype(np.int32)
    return jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:])


def _program_picks(model, params, x):
    """(B, layers, T, T) bool: what the program's layers attended over."""
    with jax.default_matmul_precision("highest"):
        _, inter = model.apply({"params": params}, x, method="features",
                               mutable=["intermediates"])
    blocks = inter["intermediates"]
    return np.stack([np.asarray(blocks[f"block_{i}"]["picked"][0]) != 0
                     for i in range(len(blocks))], axis=1)


@pytest.mark.parametrize("held", [(0, 8), (2, 4)])
def test_logits_match_the_reference(held):
    cfg = small_cfg(experts_held=held)
    model, params = _params(cfg)
    x, _ = _ids()
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p, x: model.apply({"params": p}, x))(params, x)
    reference = jax.jit(lambda p, ids: ref.logits(p, ids, cfg))
    for b in range(x.shape[0]):
        np.testing.assert_allclose(got[b], reference(params, x[b]),
                                   rtol=2e-4, atol=2e-4)


def test_the_selection_is_the_references_and_counted():
    cfg = small_cfg()
    model, params = _params(cfg)
    x, _ = _ids()
    got = _program_picks(model, params, x)
    for b in range(x.shape[0]):
        want = jax.jit(lambda p, ids: ref.picks(p, ids, cfg))(params, x[b])
        np.testing.assert_array_equal(got[b], np.asarray(want))
        # handed the program's picks, the reference finds no difference
        dist = jax.jit(lambda p, ids, s: ref.hidden_states(
            p, ids, cfg, selection=s)[3])(params, x[b], jnp.asarray(got[b]))
        assert dist.kept.tolist() == [KEPT] * 2
        assert dist.program_only.tolist() == dist.reference_only.tolist() \
            == [0, 0]
    _, stats = model.apply({"params": params}, x, method="features")
    assert keys_kept_counts(stats["sparse_keys_kept"]) == [2 * KEPT] * 2
    assert stats["sparse_query_rows"].tolist() == [2 * SEQ] * 2


def _program_loss_and_grads(cfg, params, batch):
    model = build_model(cfg)
    task = NextToken(cfg, TrainConfig(batch_size=2, window=SEQ))

    def program_loss(p):
        return task.loss(p, task.forward(model, p, batch, None), batch)[0]

    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(program_loss))(params)


@pytest.mark.parametrize("remat", [False, True, "replay"])
def test_loss_and_gradients_match_the_reference(remat):
    """``"replay"``: the reference here is the program itself without
    recomputation.  A block's replay keeps what it names and remakes the
    rest from the block's input: loss and every gradient leaf are the
    same bits with ``remat`` on and off."""
    layout = (2, 2) if remat == "replay" else small_cfg().layer_layout
    kw = dict(experts_held=(4, 4), layer_layout=layout)
    cfg = small_cfg(remat=bool(remat), **kw)
    _, params = _params(cfg)
    x, y = _ids()
    mask = jnp.ones(x.shape, jnp.float32).at[1, 30:].set(0.0)
    batch = Batch(x, y, mask)
    got, got_grads = _program_loss_and_grads(cfg, params, batch)
    if remat == "replay":
        want, want_grads = _program_loss_and_grads(
            small_cfg(remat=False, **kw), params, batch)
        np.testing.assert_array_equal(got, want)
        for (path, g), w in zip(
                jax.tree_util.tree_leaves_with_path(got_grads),
                jax.tree.leaves(want_grads)):
            np.testing.assert_array_equal(g, w, err_msg=str(path))
        return
    want, want_grads = jax.jit(lambda p: ref.loss_and_grads(
        p, x, y, mask, cfg, remat=remat))(params)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got_grads),
                            jax.tree.leaves(want_grads)):
        scale = float(jnp.abs(w).max()) + 1e-12
        assert float(jnp.abs(g - w).max()) <= 2e-4 * scale + 1e-7, path
    # the next-token loss sends the indexer nothing, here and there
    for tree in (got_grads, want_grads):
        for name in ("wq_idx", "wk_idx", "ww_idx"):
            assert float(jnp.abs(tree["block_1"][name]).max()) == 0.0
    assert float(jnp.abs(got_grads["block_1"]["q_norm"]).max()) > 0.0


def test_with_topk_at_least_t_the_layer_attends_over_the_causal_past():
    cfg = small_cfg(indexer_topk=SEQ)
    model, params = _params(cfg)
    x, _ = _ids(batch=1)
    assert _program_picks(model, params, x)[0].tolist() == [
        np.tril(np.ones((SEQ, SEQ), bool)).tolist()] * 2
    with jax.default_matmul_precision("highest"):
        got = model.apply({"params": params}, x)[0]
    want = jax.jit(lambda p, ids: ref.logits(
        p, ids, cfg, dense_attention=True))(params, x[0])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("kw", [
    {"topk": TOPK // 2}, {"dense_attention": True}, {"indexer_relu": False},
    {"skip_expert": 1}, {"products_as": "float8_e5m2"}],
    ids=lambda kw: next(iter(kw)))
def test_the_references_wrong_runs_move_its_loss_and_gradient(kw):
    """The deliberately wrong runs the benchmark's ``correct`` must
    catch, each against the reference as it should be."""
    cfg = small_cfg(experts_held=(2, 4))
    _, params = _params(cfg)
    x, y = _ids(batch=1)
    mask = jnp.ones(x.shape, jnp.float32)
    loss, grads = jax.jit(lambda p: ref.loss_and_grads(
        p, x, y, mask, cfg))(params)
    wrong_loss, wrong = jax.jit(lambda p: ref.loss_and_grads(
        p, x, y, mask, cfg, **kw))(params)
    assert np.isfinite(float(wrong_loss)) and wrong_loss != loss
    for name in ("wq", "wo", "w_up"):
        g, w = grads["block_1"][name], wrong["block_1"][name]
        rel = float(jnp.linalg.norm(w - g) / jnp.linalg.norm(g))
        assert 0.01 < rel < 2.0, (name, rel)
    if set(kw) & {"topk", "dense_attention", "indexer_relu"}:
        # and the selection itself differs from the right one's
        right = ref.picks(params, x[0], cfg)
        dist = ref.hidden_states(params, x[0], cfg, selection=right,
                                 **kw)[3]
        assert int(dist.program_only.sum() + dist.reference_only.sum()) > 0


def test_the_layerwise_backward_is_the_whole_graphs():
    cfg = small_cfg(experts_held=(2, 4))
    model, params = _params(cfg)
    x, y = _ids()
    mask = jnp.ones(x.shape, jnp.float32).at[1, 30:].set(0.0)
    given = jnp.asarray(_program_picks(model, params, x))
    want, want_grads = jax.jit(lambda p: ref.loss_and_grads(
        p, x, y, mask, cfg, selection=given))(params)
    layerwise = ref.Layerwise(cfg)
    got, got_grads, pairs, dist = layerwise.loss_and_grads(
        params, x, y, mask, selection=given)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert dist.kept.tolist() == [2 * KEPT] * 2
    assert dist.program_only.tolist() == [0, 0]
    # one sequence's loss through the same programs, under the given
    # picks and under the reference's own (the same picks here)
    for attend_given in (True, False):
        one, one_pairs, _ = layerwise.loss(
            params, x[0], y[0], mask[0], given[0], attend_given)
        np.testing.assert_allclose(
            one, ref.loss(params, x[0], y[0], mask[0], cfg), rtol=1e-6)
    np.testing.assert_array_equal(
        pairs, sum(np.asarray(ref.hidden_states(params, x[b], cfg)[1])
                   for b in range(2)))
    assert jax.tree.structure(got_grads) == jax.tree.structure(want_grads)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got_grads),
                            jax.tree.leaves(want_grads)):
        scale = float(jnp.abs(w).max()) + 1e-12
        assert float(np.abs(g - w).max()) <= 1e-5 * scale, path


def test_a_pass_publishes_what_the_selection_counted():
    from fmda_tpu.obs.registry import default_registry
    from fmda_tpu.train.trainer import Trainer

    cfg = small_cfg(layer_layout=(1, 2), sliding_window=16)
    tc = TrainConfig(batch_size=2, window=SEQ, chunk_size=2 * SEQ,
                     learning_rate=1e-3, val_size=0.2, test_size=0.2,
                     cache_chunks=8, seed=0)
    ids = np.random.default_rng(0).integers(0, VOCAB, 10 * SEQ + 1)
    reg = default_registry()

    def read(name, layer):
        return reg.counter(name, layer=str(layer), phase="train").value

    before = [read("sparse_keys_kept_total", 1),
              read("sparse_query_rows_total", 1),
              read("sparse_keys_kept_total", 0)]
    trainer = Trainer(cfg, tc)
    _, hist, dataset = trainer.fit(TokenArraySource(ids, VOCAB), epochs=1)
    train, _, _ = dataset.split(tc.val_size, tc.test_size)
    n_seq = sum(len(dataset.sequences(i)[0]) for i in train)
    # padded sequences are selected over too: whole batches are counted
    n_rows = 2 * sum(len(trainer.task.batches(dataset, i)) for i in train)
    assert n_rows >= n_seq
    assert read("sparse_keys_kept_total", 1) - before[0] == n_rows * KEPT
    assert read("sparse_query_rows_total", 1) - before[1] == n_rows * SEQ
    # the window layer publishes neither
    assert read("sparse_keys_kept_total", 0) == before[2]
    assert np.isfinite(hist["train"][0].loss)


@pytest.mark.parametrize("over,named", [
    (dict(indexer_topk=0), "indexer_topk"),
    (dict(indexer_heads=0), "indexer_heads"),
    (dict(indexer_head_dim=0), "indexer_head_dim"),
    (dict(indexer_head_dim=7), "indexer_head_dim"),
    (dict(hidden_act="gelu"), "hidden_act"),
    (dict(n_heads=6, n_kv_heads=2, use_pallas=True), "divides 128"),
    (dict(layer_layout=(2, 4)), "layer_layout"),
])
def test_config_errors_name_the_field(over, named):
    with pytest.raises(ValueError, match=named):
        check_decoder_config(small_cfg(**over))


def test_a_model_without_a_sparse_layer_needs_no_indexer():
    check_decoder_config(small_cfg(
        layer_layout=(0, 1), indexer_topk=0, indexer_heads=0,
        indexer_head_dim=0))


def test_cli_train_takes_the_benchmark_configurations_framework_block(
        tmp_path, capsys):
    """``python -m fmda_tpu train --tokens`` accepts the ``framework``
    block of benchmark/configs/keye_vl2_30b_a3b_ep8.json as written: the
    file's own keys parse, and a copy cut to test size trains."""
    from fmda_tpu.cli import main
    from fmda_tpu.config import config_from_dict

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "benchmark", "configs", "keye_vl2_30b_a3b_ep8.json")) as fh:
        framework = json.load(fh)["framework"]
    full = config_from_dict(framework)
    check_decoder_config(full.model)
    assert (full.model.indexer_topk, full.model.hidden_act) == (2048, "silu")
    assert set(full.model.layer_layout) == {2}
    small = small_cfg()
    framework["model"].update({
        k: v for k, v in config_to_dict(FrameworkConfig(model=small))[
            "model"].items() if k in framework["model"]})
    framework["train"].update(window=SEQ, chunk_size=SEQ, epochs=1)
    cfg_path, tokens = tmp_path / "cfg.json", tmp_path / "tokens.npy"
    cfg_path.write_text(json.dumps(framework))
    np.save(tokens, np.random.default_rng(0).integers(0, VOCAB, 10 * SEQ + 1))
    rc = main(["train", "--config", str(cfg_path), "--platform", "cpu",
               "--tokens", str(tokens),
               "--checkpoint-dir", str(tmp_path / "ckpt")])
    out = capsys.readouterr()
    assert rc == 0, out.err
    assert "trained 1 epochs" in out.out


def test_the_adam_step_a_leaf_at_a_time_is_the_whole_trees():
    """What the chip's comparison uses so as not to hold three trees of
    the model's size: ``clip_scale`` and ``first_adam_leaf`` give
    ``first_adam_step``'s clipped gradients and changes."""
    rng = np.random.default_rng(0)
    grads = {"a": rng.normal(size=(5, 7)).astype(np.float32),
             "b": {"c": rng.normal(size=(3,)).astype(np.float32),
                   "idle": np.zeros((4,), np.float32)}}
    for clip in (1e-2, 1e3):  # clipping, and not
        want_g, want_d = ref.first_adam_step(
            grads, learning_rate=1e-3, clip=clip)
        scale = ref.clip_scale(grads, clip)
        for g, wg, wd in zip(jax.tree.leaves(grads), jax.tree.leaves(want_g),
                             jax.tree.leaves(want_d)):
            got_g, got_d = ref.first_adam_leaf(g, scale, 1e-3)
            np.testing.assert_array_equal(got_g, wg)
            np.testing.assert_array_equal(got_d, wd)
