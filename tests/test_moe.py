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


def _dense_given(w, gates, experts, first, count, act="relu"):
    """The layer as its equations read, for a routing that is given: a
    loop over the held experts."""
    fn = {"relu": jax.nn.relu, "silu": jax.nn.silu}[act]
    out = jnp.zeros_like(w["u"])
    for e in range(first, first + count):
        ge = jnp.sum(jnp.where(experts == e, gates, 0.0), -1)
        out += ge[:, None] * (
            (fn(w["u"] @ w["w_gate"][e]) * (w["u"] @ w["w_up"][e]))
            @ w["w_down"][e])
    return out


def _dense(w, first, count, top_k=K, act="relu"):
    """... and with the routing computed as its equations read."""
    p = jax.nn.softmax(w["h"] @ w["router"], -1)
    top, idx = jax.lax.top_k(p, top_k)
    return _dense_given(w, top / top.sum(-1, keepdims=True), idx, first,
                        count, act)


def _layer(w, first, count, impl="jnp", top_k=K, act="relu"):
    gates, experts = moe.route(w["h"], w["router"], top_k)
    held = slice(first, first + count)
    return moe.expert_layer(
        w["u"], gates, experts, w["w_gate"][held], w["w_up"][held],
        w["w_down"][held], experts_held=(first, count), n_experts=E,
        impl=impl, act=act)


@pytest.mark.parametrize("act", ["relu", "silu"])
@pytest.mark.parametrize("shares", [4, 8])
def test_the_shares_of_four_chips_add_up_to_the_uncut_layer(shares, act):
    """The share test: the chips of a group (four, or eight) hold their
    part of the eight experts each, every one routes over all eight;
    what every chip computes alike (the router, the gates'
    normalisation) is counted once, and the partial outputs add up to
    the uncut layer's, whatever the gate's activation."""
    w = _weights()
    each = E // shares
    with jax.default_matmul_precision("highest"):
        whole = _dense(w, 0, E, act=act)
        parts = [_layer(w, first, each, act=act)[0]
                 for first in range(0, E, each)]
        uncut, laid = _layer(w, 0, E, act=act)
    assert len(parts) == shares
    if act == "silu":  # and the activation is really another layer
        assert float(jnp.abs(whole - _dense(w, 0, E)).max()) > 1e-3
    np.testing.assert_allclose(sum(parts), whole, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(uncut, whole, rtol=1e-5, atol=1e-6)
    assert int(laid.expert_pairs.sum()) == T * K  # every pair, once


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
        out, laid = _layer(w, 2, 4, impl)
        want = _dense(w, 2, 4)
    assert int(laid.expert_pairs[1]) == T      # expert 3 = held index 1
    assert int(laid.dropped) == 0
    assert int(laid.row_tiles_used) >= T // moe.default_row_tile(T * K)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)


def test_the_plan_places_every_held_pair_in_its_experts_rows():
    experts = jnp.asarray(
        np.random.default_rng(0).integers(0, E, size=(T, K)), jnp.int32)
    tile = 16
    plan = moe.plan_dispatch(experts, (2, 4), E, tile)
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
    assert plan.row_pair.shape[0] == moe.layout_rows(T * K, 4, E, tile)


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


# -- the row passes: bounded at n_used, one pass for both cotangents ---------

def _routing(kind):
    """``(experts (T, K), experts_held)`` for the routings a row pass must
    be right under; ``K`` distinct experts a token."""
    t = np.arange(T)
    if kind == "even":
        return np.stack([t % E, (t + 1) % E], 1), (2, 4)
    if kind == "one_expert_takes_most":
        return np.stack([np.full(T, 3), np.where(t % 8 == 0, 4, 7)], 1), (2, 4)
    if kind == "an_expert_without_a_pair":
        return np.stack([2 + t % 2, 5 + t % 3], 1), (2, 4)  # never expert 4
    if kind == "every_pair_held":
        # 17, 17, 33 and 61 pairs: 2 + 2 + 3 + 4 = 11 of the layout's 12
        # tiles, the most a routing can use (the layout allows a spare
        # tile a group, and the groups' remainders cannot all be one)
        pairs = [(3, 2)] * 33 + [(3, 0)] * 14 + [(3, 1)] * 14 + [(0, 1)] * 3
        return np.asarray(pairs), (0, 4)
    if kind == "no_pair_held":
        return np.stack([t % 2, 6 + t % 2], 1), (2, 4)
    if kind == "the_last_turn_moved_back":
        # six held experts with 17, 17, 17, 17, 17 and 43 pairs: 13 of 14
        # tiles, 224 rows that turns of 64 do not divide, so the fourth
        # turn starts at row 160 and visits rows 160-191 again
        pairs = ([(6, 1)] * 9 + [(6, 2)] * 9 + [(6, 3)] * 9 + [(6, 4)] * 8
                 + [(6, 5)] * 8 + [(1, 2)] * 4 + [(1, 3)] * 4 + [(2, 4)] * 4
                 + [(3, 5)] * 4 + [(4, 5)] * 5)
        return np.asarray(pairs), (1, 6)
    raise ValueError(kind)


ROUTINGS = ["even", "one_expert_takes_most", "an_expert_without_a_pair",
            "every_pair_held", "no_pair_held", "the_last_turn_moved_back"]


def _plan(kind):
    experts, held = _routing(kind)
    experts = jnp.asarray(experts, jnp.int32)
    return experts, held, moe.plan_dispatch(
        experts, held, E, moe.default_row_tile(T * K))


def _plain_gather(u, plan):
    return jnp.where(plan.row_valid[:, None], u[plan.row_pair // K], 0)


def _plain_combine(y, gates, plan):
    out = jnp.zeros((T, y.shape[1]), jnp.float32)
    for s in range(K):
        w = jnp.where(plan.pair_held[:, s], gates[:, s], 0.0)
        out = out + w[:, None] * y[plan.pair_row[:, s]]
    return out


def test_the_routings_are_what_their_names_say():
    tile = moe.default_row_tile(T * K)
    sizes = {kind: np.asarray(_plan(kind)[2].group_sizes)
             for kind in ROUTINGS}
    used = {kind: int(_plan(kind)[2].n_used[0]) for kind in ROUTINGS}
    n_tiles = moe.layout_tiles(T * K, 4, E)
    assert sizes["one_expert_takes_most"][1] == T
    assert sizes["an_expert_without_a_pair"][2] == 0
    assert sizes["every_pair_held"].sum() == T * K
    assert used["every_pair_held"] == n_tiles - 1 == 11
    assert sizes["no_pair_held"].sum() == 0 and used["no_pair_held"] == 4
    assert used["even"] < n_tiles
    assert used["the_last_turn_moved_back"] == 13
    assert moe.layout_tiles(T * K, 6, E) * tile == 224
    assert 224 % (moe._TILES_A_TURN * tile) != 0
    assert all(_plan(kind)[2].tile == tile for kind in ROUTINGS)


@pytest.mark.parametrize("kind", ROUTINGS)
def test_gather_rows_and_its_transpose_match_the_plain_gather(kind):
    _, _, plan = _plan(kind)
    ks = jax.random.split(jax.random.PRNGKey(4), 2)
    u = jax.random.normal(ks[0], (T, D))
    d_rows = jax.random.normal(ks[1], (plan.row_pair.shape[0], D))
    got, vjp = jax.vjp(lambda u: moe.gather_rows(u, plan), u)
    want, plain_vjp = jax.vjp(lambda u: _plain_gather(u, plan), u)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_allclose(vjp(d_rows)[0], plain_vjp(d_rows)[0],
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("kind", ROUTINGS)
def test_combine_rows_and_its_backward_match_the_plain_sum(kind):
    _, _, plan = _plan(kind)
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    y = jax.random.normal(ks[0], (plan.row_pair.shape[0], D))
    gates = jax.nn.softmax(jax.random.normal(ks[1], (T, K)), -1)
    d_out = jax.random.normal(ks[2], (T, D))
    got, vjp = jax.vjp(lambda y, g: moe.combine_rows(y, g, plan), y, gates)
    want, plain_vjp = jax.vjp(lambda y, g: _plain_combine(y, g, plan),
                              y, gates)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    (d_y, d_gates), (want_y, want_gates) = vjp(d_out), plain_vjp(d_out)
    np.testing.assert_allclose(d_y, want_y, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(d_gates, want_gates, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("impl", ["jnp", "interpret"])
@pytest.mark.parametrize("kind", ROUTINGS)
def test_layer_gradients_match_the_dense_loop_under_a_given_routing(
        kind, impl):
    experts, (first, count), _ = _plan(kind)
    w = _weights(6)
    gates = jax.nn.softmax(w["h"][:, :K], -1)
    held = slice(first, first + count)
    keys = ["u", "w_gate", "w_up", "w_down"]

    def layer(gates, *a):
        p = dict(zip(keys, a))
        return jnp.sum(moe.expert_layer(
            p["u"], gates, experts, p["w_gate"][held], p["w_up"][held],
            p["w_down"][held], experts_held=(first, count), n_experts=E,
            impl=impl)[0] ** 2)

    def dense(gates, *a):
        return jnp.sum(_dense_given(
            dict(zip(keys, a)), gates, experts, first, count) ** 2)

    args = [gates] + [w[k] for k in keys]
    with jax.default_matmul_precision("highest"):
        want = jax.value_and_grad(dense, range(len(args)))(*args)
        got = jax.value_and_grad(layer, range(len(args)))(*args)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert bool(jnp.isfinite(a).all())
        np.testing.assert_allclose(
            a, b, rtol=1e-4, atol=1e-5 * float(jnp.abs(b).max()) + 1e-7)


@pytest.mark.parametrize("kind", ["even", "one_expert_takes_most",
                                  "no_pair_held"])
def test_rows_past_the_used_tiles_are_never_read(kind):
    """Poison: NaN in every row past ``n_used * tile`` of what the row
    passes are handed changes nothing, and nothing they return is NaN."""
    _, _, plan = _plan(kind)
    rows = plan.row_pair.shape[0]
    past = (jnp.arange(rows) >= plan.n_used[0] * plan.tile)[:, None]
    assert bool(past.any())
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    y = jax.random.normal(ks[0], (rows, D))
    gates = jax.nn.softmax(jax.random.normal(ks[1], (T, K)), -1)
    d_out = jax.random.normal(ks[2], (T, D))
    d_rows = jax.random.normal(ks[3], (rows, D))

    def both(y, d_rows):
        out, vjp = jax.vjp(lambda y, g: moe.combine_rows(y, g, plan),
                           y, gates)
        d_u = jax.vjp(lambda u: moe.gather_rows(u, plan),
                      jnp.zeros((T, D)))[1](d_rows)[0]
        return (out,) + vjp(d_out) + (d_u,)

    clean = both(y, d_rows)
    poisoned = both(jnp.where(past, jnp.nan, y),
                    jnp.where(past, jnp.nan, d_rows))
    for a, b in zip(clean, poisoned):
        assert bool(jnp.isfinite(b).all())
        np.testing.assert_array_equal(a, b)
    assert float(jnp.abs(jnp.where(past, clean[1], 0.0)).max()) == 0.0


@pytest.mark.parametrize("held,rounds", [((2, 4), [1, 1]), ((3, 2), [1, 2])])
def test_one_compiled_program_serves_routings_of_different_n_used(
        held, rounds):
    """... and of different numbers of rounds: a chip with two of the
    eight experts lays 64 of the 128 pairs out a round, and the second
    routing brings it 72."""
    w = _weights(8)
    gates = jax.nn.softmax(w["h"][:, :K], -1)
    first, count = held
    mine = slice(first, first + count)

    @jax.jit
    def step(experts, gates, u):
        def loss(gates, u):
            m, laid = moe.expert_layer(
                u, gates, experts, w["w_gate"][mine], w["w_up"][mine],
                w["w_down"][mine], experts_held=held, n_experts=E)
            return jnp.sum(m ** 2), laid
        (value, laid), grads = jax.value_and_grad(
            loss, (0, 1), has_aux=True)(gates, u)
        return value, laid, grads

    seen = []
    for kind in ("even", "one_expert_takes_most"):
        experts = jnp.asarray(_routing(kind)[0], jnp.int32)
        with jax.default_matmul_precision("highest"):
            value, laid, _ = step(experts, gates, w["u"])
            want = jnp.sum(_dense_given(w, gates, experts, *held) ** 2)
        np.testing.assert_allclose(value, want, rtol=1e-4, atol=1e-6)
        seen.append((int(laid.row_tiles_used), int(laid.layout_rounds)))
    assert seen[0][0] != seen[1][0]
    assert [r for _, r in seen] == rounds
    assert step._cache_size() == 1


# -- the layout: twice the even share, in rounds ------------------------------

def test_the_layout_is_twice_the_even_share_and_the_uncut_layers_is_whole():
    """The four expert cells' shapes (pairs a call, experts held, the
    router's width), and a chip that holds every expert or half of them:
    the layout of every pair, as it was."""
    assert [moe.layout_tiles(*cell) for cell in (
        (16384 * 8, 16, 128), (8192 * 6, 8, 64), (4096 * 4, 8, 64),
        (8192 * 6, 16, 64))] == [144, 56, 24, 112]
    for pairs, count in ((8192 * 6, 16), (16384 * 8, 16), (T * K, 4)):
        whole = -(-pairs // moe.default_row_tile(pairs)) + count
        assert moe.layout_tiles(pairs, count, count) == whole
        assert moe.layout_tiles(pairs, count, 2 * count) == whole
    assert moe.round_pairs(T * K, 2, E, 16) == 64
    assert moe.round_pairs(100, 2, 3, 16) == 112  # whole tiles, all pairs


def _given(experts, held, seed=9):
    """Inputs of a layer under a routing that is given, and the dense
    loop's output on them."""
    w = _weights(seed)
    gates = jax.nn.softmax(w["h"][:, :K], -1)

    def dense(u, gates, w_gate, w_up, w_down):
        return _dense_given(
            dict(u=u, w_gate=w_gate, w_up=w_up, w_down=w_down), gates,
            experts, *held)

    return (w["u"], gates, w["w_gate"], w["w_up"], w["w_down"]), dense


def _layer_and_gradients(experts, held, n_experts, impl):
    """Output, what was laid out, and the gradients of the output's
    squared sum in all five inputs."""
    args, _ = _given(experts, held)
    mine = slice(held[0], sum(held))

    def loss(u, gates, w_gate, w_up, w_down):
        m, laid = moe.expert_layer(
            u, gates, experts, w_gate[mine], w_up[mine], w_down[mine],
            experts_held=held, n_experts=n_experts, impl=impl)
        return jnp.sum(m ** 2), (m, laid)

    with jax.default_matmul_precision("highest"):
        (_, (m, laid)), grads = jax.value_and_grad(
            loss, range(5), has_aux=True)(*args)
    return m, laid, grads


@pytest.mark.parametrize("impl", ["jnp", "interpret"])
@pytest.mark.parametrize("kind", ROUTINGS)
def test_one_round_is_the_layout_of_every_pair_bit_for_bit(kind, impl):
    """The smallest layout that holds a routing in one round (the widest
    router of which this chip's share is still enough) against the
    layout of every pair (``n_experts = count``: a share of one): the
    same tiles with the same contents, fewer empty ones behind them, so
    the output and all five gradients are equal to the bit."""
    experts, held, _ = _plan(kind)
    held_pairs = int(((experts >= held[0]) & (experts < sum(held))).sum())
    tile = moe.default_row_tile(T * K)
    widest = max(n for n in range(held[1], 65) if moe.round_pairs(
        T * K, held[1], n, tile) >= held_pairs)
    small = _layer_and_gradients(experts, held, widest, impl)
    whole = _layer_and_gradients(experts, held, held[1], impl)
    assert int(small[1].layout_rounds) == int(whole[1].layout_rounds) == 1
    assert int(small[1].dropped) == 0
    if held_pairs <= T * K - tile:  # ... and the layout is smaller
        assert moe.layout_tiles(T * K, held[1], widest) < moe.layout_tiles(
            T * K, held[1], held[1])
    for a, b in zip(jax.tree.leaves(small), jax.tree.leaves(whole)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("impl", ["jnp", "interpret"])
def test_a_routing_that_overflows_the_layout_takes_rounds_and_is_exact(impl):
    """``one_expert_takes_most`` on a chip that holds experts 3 and 4 of
    eight: 72 held pairs where a round lays 64 out, so the layer runs
    two rounds, drops nothing, and its output and gradients are the
    dense loop's; on a chip that holds expert 3 alone (32 a round), two
    rounds of the one group cut in halves."""
    experts = jnp.asarray(_routing("one_expert_takes_most")[0], jnp.int32)
    for held in ((3, 2), (3, 1)):
        m, laid, grads = _layer_and_gradients(experts, held, E, impl)
        assert int(laid.layout_rounds) == 2
        assert int(laid.dropped) == 0
        assert int(laid.expert_pairs[0]) == T
        args, dense = _given(experts, held)
        with jax.default_matmul_precision("highest"):
            want = dense(*args)
            want_grads = jax.grad(
                lambda *a: jnp.sum(dense(*a) ** 2), range(5))(*args)
        np.testing.assert_allclose(m, want, rtol=1e-5, atol=1e-6)
        for a, b in zip(grads, want_grads):
            assert bool(jnp.isfinite(a).all())
            np.testing.assert_allclose(
                a, b, rtol=1e-4, atol=1e-5 * float(jnp.abs(b).max()) + 1e-7)


@pytest.mark.parametrize("with_bias", [False, True])
def test_sigmoid_routing_is_the_written_out_top_k_with_ties(with_bias):
    """``route(scoring="sigmoid")`` against a top-k written out by hand:
    each expert's score its own sigmoid, the k largest of score + bias
    chosen one at a time, a tie going to the lower expert, the gates the
    chosen experts' *unbiased* scores over their sum, times the scale.
    Columns 1, 4 and 6 of the router are equal, so every token has a
    three-way tie somewhere in its order, and the bias breaks or makes
    others."""
    w = _weights(3)
    router = np.array(w["router"])
    router[:, 4] = router[:, 6] = router[:, 1]
    h = np.asarray(w["h"])
    bias = np.array([0.0, 0.3, -0.2, 0.0, 0.3, 0.1, 0.0, -0.4],
                    np.float32) if with_bias else None
    gates, experts = moe.route(
        jnp.asarray(h), jnp.asarray(router), 3, scoring="sigmoid",
        bias=None if bias is None else jnp.asarray(bias), scale=2.0)
    scores = np.asarray(jax.nn.sigmoid(jnp.asarray(h) @ jnp.asarray(router)))
    chosen_on = scores + (0.0 if bias is None else bias)
    for t in range(T):
        left, picked = chosen_on[t].copy(), []
        for _ in range(3):
            best = int(np.flatnonzero(left == left.max())[0])  # the lowest
            picked.append(best)
            left[best] = -np.inf
        assert list(np.asarray(experts[t])) == picked, t
        want = 2.0 * scores[t, picked] / scores[t, picked].sum()
        np.testing.assert_allclose(gates[t], want, rtol=1e-6)
    tied = np.isin(np.asarray(experts), (1, 4, 6)).sum(axis=1)
    assert (tied >= 2).any()  # ties were among the chosen
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 2.0, rtol=1e-6)
    # the load over ALL the experts, held or not
    load = moe.router_load(experts, E)
    assert load.shape == (E,) and int(load.sum()) == T * 3
    assert int(load[2]) == int((np.asarray(experts) == 2).sum())


def test_the_selection_bias_gets_no_gradient_and_the_router_does():
    w = _weights(1)
    bias = jnp.linspace(-0.1, 0.1, E)

    def total(router, bias):
        gates, _ = moe.route(w["h"], router, K, scoring="sigmoid", bias=bias,
                             scale=2.0)
        return jnp.sum(gates * jnp.arange(1.0, K + 1))

    g_router, g_bias = jax.grad(total, (0, 1))(w["router"], bias)
    assert not np.asarray(g_bias).any()
    assert np.abs(np.asarray(g_router)).max() > 0

