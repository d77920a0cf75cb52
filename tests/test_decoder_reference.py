"""The plain reference's own pieces that the benchmark's ``correct`` leans
on (benchmark/reference/moe_decoder.py): its two deliberately wrong runs
move its gradient, and its backward written out a layer at a time is the
whole graph's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_decoder import _ids, _params, ref, small_cfg


@pytest.mark.parametrize("kw,moved", [
    ({"skip_expert": 1}, ("w_gate", "w_up", "w_down")),
    ({"products_as": "float8_e5m2"}, ("wq", "wo", "head", "w_up"))])
def test_the_references_wrong_runs_move_its_gradient(kw, moved):
    """The two deliberately wrong runs the benchmark's ``correct`` must
    catch: a held expert left out, and every product's operands rounded
    one precision lower (value only; cotangents pass)."""
    cfg = small_cfg(experts_held=(2, 4))
    _, params = _params(cfg)
    x, y = _ids(batch=1)
    mask = jnp.ones(x.shape, jnp.float32)
    loss, grads = jax.jit(lambda p: ref.loss_and_grads(
        p, x, y, mask, cfg))(params)
    wrong_loss, wrong = jax.jit(lambda p: ref.loss_and_grads(
        p, x, y, mask, cfg, **kw))(params)
    assert np.isfinite(float(wrong_loss)) and wrong_loss != loss
    for name in moved:
        g, w = grads["block_1"].get(name), wrong["block_1"].get(name)
        if g is None:
            g, w = grads[name], wrong[name]
        rel = float(jnp.linalg.norm(w - g) / jnp.linalg.norm(g))
        assert 0.02 < rel < 2.0, (name, rel)
    if "skip_expert" in kw:  # the skipped expert's rows get no gradient
        assert float(jnp.abs(wrong["block_1"]["w_up"][1]).max()) == 0.0
        assert float(jnp.abs(grads["block_1"]["w_up"][1]).max()) > 0.0


def test_the_layerwise_backward_is_the_whole_graphs():
    cfg = small_cfg(experts_held=(2, 4))
    _, params = _params(cfg)
    x, y = _ids()
    mask = jnp.ones(x.shape, jnp.float32).at[1, 40:].set(0.0)
    want, want_grads = jax.jit(lambda p: ref.loss_and_grads(
        p, x, y, mask, cfg))(params)
    got, got_grads = ref.loss_and_grads_by_layer(params, x, y, mask, cfg)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert jax.tree.structure(got_grads) == jax.tree.structure(want_grads)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got_grads),
                            jax.tree.leaves(want_grads)):
        scale = float(jnp.abs(w).max()) + 1e-12
        assert float(np.abs(g - w).max()) <= 1e-5 * scale, path
