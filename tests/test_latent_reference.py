"""The latent-attention reference's own pieces that the benchmark's
``correct`` leans on (benchmark/reference/latent_decoder.py): its
deliberately wrong runs move its loss and gradient, and its backward
written out a layer at a time is the whole graph's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_latent_decoder import _ids, _params, ref, small_cfg


@pytest.fixture(scope="module")
def right():
    """The right reference's loss and gradients, and what gave them."""
    cfg = small_cfg()
    _, params = _params(cfg)
    x, y = _ids()
    mask = jnp.ones(x.shape, jnp.float32)
    return (params, x, y, mask, cfg), ref.loss_and_grads(
        params, x, y, mask, cfg)


@pytest.mark.parametrize("wrong", [
    dict(products_as="float8_e5m2"), dict(sinkhorn_as="bfloat16"),
    dict(softmax_as="bfloat16"), dict(sinkhorn_turns_less=19),
    dict(router="softmax"), dict(skip_shared=True)],
    ids=lambda w: next(iter(w)))
def test_the_references_wrong_runs_move_its_loss_and_gradient(right, wrong):
    given, (loss, grads) = right
    moved, wrong_grads = ref.loss_and_grads(*given, **wrong)
    assert abs(float(moved) - float(loss)) > 1e-5
    g, w = grads["block_1"]["wo"], wrong_grads["block_1"]["wo"]
    assert float(jnp.linalg.norm(w - g) / jnp.linalg.norm(g)) > 1e-3


def test_the_references_layerwise_backward_is_the_whole_graphs():
    cfg = small_cfg()
    _, params = _params(cfg)
    x, y = _ids()
    mask = jnp.ones(x.shape, jnp.float32).at[0, :5].set(0.0)
    want, want_grads = ref.loss_and_grads(params, x, y, mask, cfg)
    got, got_grads = ref.loss_and_grads_by_layer(params, x, y, mask, cfg)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got_grads),
                            jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=1e-6,
                                   err_msg=str(path))
