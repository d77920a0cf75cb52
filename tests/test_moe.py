"""The routed-expert layer (ops/moe.py) and its grouped-product kernels
(ops/pallas_moe.py, under the Pallas interpreter): the share test, no
pair dropped under a skewed routing, kernels against the jnp path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fmda_tpu.ops import moe
from fmda_tpu.ops.pallas_moe import _column_tile, grouped_matmul

T, D, F, E, K = 64, 32, 16, 8, 2


def _weights(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return dict(
        h=jax.random.normal(ks[0], (T, D)),
        u=jax.random.normal(ks[1], (T, D)),
        router=jax.random.normal(ks[2], (D, E)),
        w_gate=0.2 * jax.random.normal(ks[3], (E, D, F)),
        w_up=0.2 * jax.random.normal(ks[4], (E, D, F)),
        w_down=0.2 * jax.random.normal(ks[5], (E, F, D)))


def _dense(w, first, count, top_k=K):
    """The layer as its equations read: a loop over the held experts."""
    p = jax.nn.softmax(w["h"] @ w["router"], -1)
    top, idx = jax.lax.top_k(p, top_k)
    g = top / top.sum(-1, keepdims=True)
    out = jnp.zeros_like(w["u"])
    for e in range(first, first + count):
        ge = jnp.sum(jnp.where(idx == e, g, 0.0), -1)
        out += ge[:, None] * (
            (jax.nn.relu(w["u"] @ w["w_gate"][e]) * (w["u"] @ w["w_up"][e]))
            @ w["w_down"][e])
    return out


def _layer(w, first, count, impl="jnp", top_k=K):
    gates, experts = moe.route(w["h"], w["router"], top_k)
    held = slice(first, first + count)
    return moe.expert_layer(
        w["u"], gates, experts, w["w_gate"][held], w["w_up"][held],
        w["w_down"][held], experts_held=(first, count), impl=impl)


def test_the_shares_of_four_chips_add_up_to_the_uncut_layer():
    """The share test: four chips hold two experts each, every one routes
    over all eight; what every chip computes alike (the router, the
    gates' normalisation) is counted once, and the four partial outputs
    add up to the uncut layer's."""
    w = _weights()
    with jax.default_matmul_precision("highest"):
        whole = _dense(w, 0, E)
        parts = [_layer(w, first, 2)[0] for first in (0, 2, 4, 6)]
        uncut, plan = _layer(w, 0, E)
    np.testing.assert_allclose(sum(parts), whole, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(uncut, whole, rtol=1e-5, atol=1e-6)
    assert int(plan.group_sizes.sum()) == T * K  # every pair, once


@pytest.mark.parametrize("impl", ["jnp", "interpret"])
@pytest.mark.parametrize("held", [(0, 8), (2, 2), (4, 4)])
def test_layer_and_gradients_match_the_dense_loop(impl, held):
    w = _weights(1)
    keys = sorted(w)
    f = lambda *a: jnp.sum(_dense(dict(zip(keys, a)), *held) ** 2)
    g = lambda *a: jnp.sum(_layer(dict(zip(keys, a)), *held, impl)[0] ** 2)
    args = [w[k] for k in keys]
    with jax.default_matmul_precision("highest"):
        want = jax.value_and_grad(f, range(len(keys)))(*args)
        got = jax.value_and_grad(g, range(len(keys)))(*args)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(
            a, b, rtol=1e-4, atol=1e-5 * float(jnp.abs(b).max()) + 1e-7)


@pytest.mark.parametrize("impl", ["jnp", "interpret"])
def test_no_pair_dropped_when_one_expert_takes_most_tokens(impl):
    """A router biased so that expert 3 is in every token's top-2: its
    group is the whole sequence, five tiles where the average is one,
    and every pair is still computed."""
    w = _weights(2)
    w["router"] = w["router"].at[:, 3].set(0.0)
    w["h"] = jnp.abs(w["h"])
    w["router"] = w["router"] - 1.0
    w["router"] = w["router"].at[:, 3].set(5.0)
    with jax.default_matmul_precision("highest"):
        out, plan = _layer(w, 2, 4, impl)
        want = _dense(w, 2, 4)
    assert int(plan.group_sizes[1]) == T       # expert 3 = held index 1
    assert int(plan.dropped) == 0
    assert int(plan.n_used[0]) >= T // moe.default_row_tile(T * K)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)


def test_the_plan_places_every_held_pair_in_its_experts_rows():
    experts = jnp.asarray(
        np.random.default_rng(0).integers(0, E, size=(T, K)), jnp.int32)
    tile = 16
    plan = moe.plan_dispatch(experts, (2, 4), tile)
    held = (experts >= 2) & (experts < 6)
    np.testing.assert_array_equal(plan.pair_held, held)
    assert int(plan.row_valid.sum()) == int(held.sum())
    rows = np.asarray(plan.pair_row)[np.asarray(held)]
    assert len(set(rows.tolist())) == len(rows)            # one row a pair
    owner = np.asarray(plan.tile_expert)[rows // tile] + 2
    np.testing.assert_array_equal(owner, np.asarray(experts)[np.asarray(held)])
    back = np.asarray(plan.row_pair)[rows]                  # and back again
    np.testing.assert_array_equal(
        back, np.flatnonzero(np.asarray(held).reshape(-1)))
    assert plan.row_pair.shape[0] == moe.layout_rows(T * K, 4, tile)


def test_an_expert_with_no_pair_gets_a_zero_gradient_not_garbage():
    w = _weights(3)
    w["h"] = jnp.abs(w["h"])
    w["router"] = w["router"].at[:, 5].set(-50.0)  # never chosen
    g = jax.grad(lambda wg: jnp.sum(_layer(
        {**w, "w_gate": wg}, 4, 4, "interpret")[0] ** 2))(w["w_gate"])
    assert float(jnp.abs(g[5]).max()) == 0.0
    assert bool(jnp.isfinite(g).all())


def test_grouped_matmul_kernels_match_the_jnp_path_in_bfloat16():
    rng = np.random.default_rng(0)
    tile, n_tiles, k, n, n_exp = 16, 6, 128, 256, 3
    x = jnp.asarray(rng.normal(size=(tile * n_tiles, k)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(n_exp, k, n)), jnp.float32)
    tile_expert = jnp.asarray([0, 0, 1, 2, 2, 2], jnp.int32)
    n_used = jnp.asarray([4], jnp.int32)  # the last two tiles are skipped

    def f(impl):
        def loss(x, w):
            y = grouped_matmul(x, w, tile_expert, n_used, tile, impl)
            return jnp.sum(y.astype(jnp.float32) ** 2), y
        return jax.value_and_grad(loss, (0, 1), has_aux=True)(x, w)

    (la, ya), (dxa, dwa) = f("jnp")
    (lb, yb), (dxb, dwb) = f("interpret")
    np.testing.assert_allclose(ya.astype(np.float32), yb.astype(np.float32),
                               rtol=2e-2, atol=2e-2)
    assert float(jnp.abs(yb[4 * tile:]).max()) == 0.0  # skipped: zeros
    np.testing.assert_allclose(dxa.astype(np.float32),
                               dxb.astype(np.float32), rtol=5e-2, atol=1.0)
    np.testing.assert_allclose(dwa, dwb, rtol=2e-2, atol=2.0)
    assert dwb.dtype == jnp.float32 and dwb.shape == w.shape


def test_column_tiles_divide_and_fit():
    assert _column_tile(2560, 768) == 384
    assert _column_tile(768, 2560) == 1280
    assert _column_tile(32, 16) == 16
