"""A routed-expert layer that is told which experts it holds.

The usual cut of a mixture-of-experts model over chips is expert
parallelism: every chip of a group routes every token over *all* the
experts (the router keeps its published width), and computes the part of
the layer's output that its own experts give.  :func:`expert_layer` is
that part, for one chip::

    p   = softmax(h @ W_r)                      over all E experts
    S_t = top-k of p_t ;  g_te = p_te / sum_{e' in S_t} p_te'
    m_t = sum_{e in S_t, e held} g_te * ( act(u_t @ Wg_e) * (u_t @ Wu_e) ) @ Wd_e

(``act``: relu, or silu where the model's configuration says so).

``experts_held = (first, count)`` names the held experts
``first .. first + count - 1``; the gates are normalised over the whole
top-k, held or not, so the parts of all the chips of a group add up to
the uncut layer (tests/test_moe.py, the share test).  On one chip the
layer runs without its exchange: nothing here stands in for the absent
chips, and what their experts would have added is left out.

No capacity factor and nothing dropped: every (token, expert) pair that
lands on a held expert is computed.  Shapes stay static because the row
layout is sized for the worst routing (all ``T * k`` pairs held) and the
grouped products skip the tiles no pair fell into
(:mod:`fmda_tpu.ops.pallas_moe`).  The steps, each under its scope
(docs/observability.md "Spans and scopes"):

- ``moe_route``: router product, softmax, top-k, gate normalisation
  (and, where the model declares it, ``moe_seq_aux``: the router's
  per-sequence balance term, :func:`seq_balance_term`);
- ``moe_dispatch``: sort the held pairs by expert, pad each group to
  whole row tiles, gather the token rows into that layout;
- ``moe_experts``: the three grouped products and the gated unit between;
- ``moe_combine``: gather each token's rows back and sum them by gate.

Both gathers have hand-written transposes that are gathers too (a row
belongs to one pair, a pair to one row), so neither direction scatters
wide rows.  The passes over the *row* layout (:func:`gather_rows`
forward, :func:`combine_rows` backward) walk the first ``plan.n_used``
row tiles, a few tiles a turn, and no further: the layout is sized for
every pair landing here, the groups fill it from row 0 without a gap,
and nothing reads a row past them (the grouped products skip those
tiles, a held pair's row lies below ``n_used * tile``, an unheld pair's
is row 0), so the rest of a row buffer is the zeros it was made of.  The combine's backward makes
one such pass for both of its cotangents: ``d_y`` is the token's
cotangent row times the gate, and the gate's own gradient is the dot of
the same two rows, taken while both are at hand and gathered back to
(T, k) as scalars.  The passes over *tokens* (``combine_rows`` forward,
``gather_rows`` backward) still gather one (T, D) block a slot.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp


class Plan(NamedTuple):
    """Where each held (token, expert) pair sits in the grouped row
    layout, for one call of the layer.  ``R`` rows in tiles of ``tile``;
    pairs are numbered ``token * k + slot``."""

    row_pair: jax.Array     # (R,) int32: the pair a row carries (0: padding)
    row_valid: jax.Array    # (R,) bool
    pair_row: jax.Array     # (T, k) int32: the row of a pair (0: not held)
    pair_held: jax.Array    # (T, k) bool
    tile_expert: jax.Array  # (R / tile,) int32: held expert of a row tile
    n_used: jax.Array       # (1,) int32: row tiles that hold a group
    group_sizes: jax.Array  # (count,) int32: pairs on each held expert
    dropped: jax.Array      # () int32: held pairs left without a row (0)

    @property
    def tile(self) -> int:
        """Rows a tile (static: the layout's rows over its tiles)."""
        return self.row_pair.shape[0] // self.tile_expert.shape[0]


def default_row_tile(n_pairs: int) -> int:
    """Rows a tile: 256 at real sizes (an MXU-friendly product per grid
    step), 16 where the whole call is smaller than that."""
    return 256 if n_pairs >= 4096 else 16


def layout_rows(n_pairs: int, count: int, tile: int) -> int:
    """Rows of the grouped layout: every pair held, each of the ``count``
    groups padded by up to a tile, an empty group keeping one."""
    return (-(-n_pairs // tile) + count) * tile


def layout_tiles(n_pairs: int, count: int) -> int:
    """Row tiles of the layout a call of ``n_pairs`` pairs gets."""
    tile = default_row_tile(n_pairs)
    return layout_rows(n_pairs, count, tile) // tile


def route(h: jax.Array, w_router: jax.Array, top_k: int, *,
          scoring: str = "softmax", bias: jax.Array = None,
          scale: float = 1.0, with_scores: bool = False):
    """``(gates (T, k) float32, experts (T, k) int32)``: softmax over
    all experts in float32, the ``top_k`` largest, their probabilities
    renormalised to sum to one.

    ``scoring="sigmoid"``: each expert's score is its own sigmoid; the
    ``top_k`` are chosen on ``score + bias`` (``bias`` (E,): a selection
    bias no gradient reaches, None for none), the gates are the chosen
    experts' *unbiased* scores over their sum, times ``scale``.  Ties go
    to the lower expert, as :func:`jax.lax.top_k` breaks them.

    ``with_scores``: a third answer, the scores of all experts (T, E)
    float32 the choice was made from (unbiased: what
    :func:`seq_balance_term` reads)."""
    with jax.named_scope("moe_route"):
        logits = jnp.dot(h, w_router.astype(h.dtype),
                         preferred_element_type=jnp.float32)
        if scoring == "sigmoid":
            scores = jax.nn.sigmoid(logits)
            chosen_on = scores if bias is None else (
                scores + jax.lax.stop_gradient(bias.astype(jnp.float32)))
            _, experts = jax.lax.top_k(chosen_on, top_k)
            top = jnp.take_along_axis(scores, experts, axis=-1)
            gates = scale * top / jnp.sum(top, axis=-1, keepdims=True)
        else:
            scores = jax.nn.softmax(logits, axis=-1)
            top, experts = jax.lax.top_k(scores, top_k)
            gates = top / jnp.sum(top, axis=-1, keepdims=True)
        experts = experts.astype(jnp.int32)
        return (gates, experts, scores) if with_scores else (gates, experts)


def seq_balance_term(scores: jax.Array, experts: jax.Array, n_seq: int,
                     alpha: float) -> jax.Array:
    """The router's balance term of each of ``n_seq`` sequences, (n_seq,)
    float32, from :func:`route`'s ``scores`` (n_seq * T, E) and
    ``experts`` (n_seq * T, k), over ALL ``E`` experts, held or not::

        s'[t, e] = scores[t, e] / sum_e' scores[t, e']   P_e = mean_t s'[t, e]
        f_e = E / (k T) * #{t : e chosen for t}
        alpha * sum_e f_e P_e                            (alpha at an even router)

    ``f`` is a count (the choice as made, on the biased score): the
    gradient reaches the scores through ``P`` alone.  Under the scope
    ``moe_seq_aux``, beside ``moe_route``."""
    with jax.named_scope("moe_seq_aux"):
        n_experts, top_k = scores.shape[-1], experts.shape[-1]
        chosen = jnp.sum(
            experts.reshape(n_seq, -1)[:, :, None]
            == jnp.arange(n_experts, dtype=jnp.int32)[None, None, :],
            axis=1, dtype=jnp.int32)                           # (n_seq, E)
        per_seq = scores.shape[0] // n_seq
        share = scores / jnp.sum(scores, axis=-1, keepdims=True)
        mean_share = jnp.mean(
            share.reshape(n_seq, per_seq, n_experts), axis=1)
        often = chosen.astype(jnp.float32) * (
            n_experts / (top_k * per_seq))
        return alpha * jnp.sum(often * mean_share, axis=-1)


def router_load(experts: jax.Array, n_experts: int) -> jax.Array:
    """Pairs each of the router's ``n_experts`` outputs received, held
    here or not: (E,) int32 from ``experts`` (T, k)."""
    return jnp.sum(
        experts.reshape(-1)[:, None]
        == jnp.arange(n_experts, dtype=jnp.int32)[None, :],
        axis=0, dtype=jnp.int32)


def plan_dispatch(experts: jax.Array, experts_held: Tuple[int, int],
                  tile: int) -> Plan:
    """The row layout for this routing (integers only, no gradient)."""
    first, count = experts_held
    t, k = experts.shape
    n_pairs = t * k
    rows = layout_rows(n_pairs, count, tile)
    local = experts.reshape(-1) - first
    held = (local >= 0) & (local < count)
    key = jnp.where(held, local, count)  # the rest sort behind
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    sizes = jnp.sum(
        key[:, None] == jnp.arange(count, dtype=jnp.int32)[None, :],
        axis=0, dtype=jnp.int32)
    starts = jnp.cumsum(sizes) - sizes            # in the sorted pairs
    tiles = jnp.maximum(-(-sizes // tile), 1)     # an empty group keeps one
    tile_ends = jnp.cumsum(tiles)
    row_starts = (tile_ends - tiles) * tile       # in the row layout
    n_tiles = rows // tile
    # the expert of each row tile; tiles past the last group repeat it
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_ends, jnp.arange(n_tiles), side="right"),
        count - 1).astype(jnp.int32)
    # rows -> pairs
    r = jnp.arange(rows, dtype=jnp.int32)
    e_of_row = tile_expert[r // tile]
    offset = r - row_starts[e_of_row]
    row_valid = (offset < sizes[e_of_row]) & (r // tile < tile_ends[-1])
    sorted_at = jnp.clip(starts[e_of_row] + offset, 0, n_pairs - 1)
    row_pair = jnp.where(row_valid, order[sorted_at], 0)
    # pairs -> rows: a pair's rank among the sorted pairs, then its row
    rank = jnp.zeros((n_pairs,), jnp.int32).at[order].set(
        jnp.arange(n_pairs, dtype=jnp.int32), unique_indices=True)
    e_of_pair = jnp.clip(local, 0, count - 1)
    row_of_pair = row_starts[e_of_pair] + rank - starts[e_of_pair]
    placed = held & (row_of_pair < rows)
    return Plan(
        row_pair=row_pair, row_valid=row_valid,
        pair_row=jnp.where(placed, row_of_pair, 0).reshape(t, k),
        pair_held=placed.reshape(t, k),
        tile_expert=tile_expert,
        n_used=tile_ends[-1:].astype(jnp.int32),
        group_sizes=sizes,
        dropped=jnp.sum(held & ~placed, dtype=jnp.int32))


def _sum_rows_of_pairs(x: jax.Array, plan: Plan, weights: jax.Array
                       ) -> jax.Array:
    """``out[t] = sum over t's held pairs of weights[t, slot] * x[row of
    the pair]``, (T, D) float32: one gather of (T, D) rows a slot."""
    out = jnp.zeros((plan.pair_row.shape[0], x.shape[1]), jnp.float32)
    for s in range(plan.pair_row.shape[1]):
        w = jnp.where(plan.pair_held[:, s], weights[:, s], 0.0)
        out = out + w[:, None] * x[plan.pair_row[:, s]].astype(jnp.float32)
    return out


#: Row tiles a turn of a row pass moves.  A turn is a handful of small
#: operations (index slices, a gather, an in-place update) that cost the
#: chip ~14 us however few rows they move: at the published shapes with
#: 62 of 208 tiles used, ``gather_rows`` took 1.80 ms at one tile a turn
#: (the unbounded pass: 1.93) and 1.36 ms at two, four, eight or sixteen
#: (PERF.md section 6, PR 31).
_TILES_A_TURN = 4


def _over_used_rows(plan: Plan, body, init):
    """``carry = body(at, n, carry)`` for turns of ``n`` rows from row
    ``at`` that together cover the ``plan.n_used`` row tiles holding a
    group, and at most ``_TILES_A_TURN - 1`` tiles behind them.  The
    layout's last turn is moved back to end with the layout, so a turn
    may visit rows again; a body writes a row from its index alone.  The
    bound is the routing's, so this is a ``while``: it is only called
    from the hand-written sides of a ``custom_vjp``, never
    differentiated through."""
    rows, tile = plan.row_pair.shape[0], plan.tile
    n = min(_TILES_A_TURN * tile, rows)
    turns = (plan.n_used[0] * tile + n - 1) // n
    return jax.lax.fori_loop(
        0, turns,
        lambda i, carry: body(jnp.minimum(i * n, rows - n), n, carry), init)


@jax.custom_vjp
def gather_rows(u: jax.Array, plan: Plan) -> jax.Array:
    """``rows[r] = u[token of row r]`` (zeros on padding rows, and on
    the rows past the used tiles, which are not visited)."""
    k = plan.pair_row.shape[1]

    def one_turn(at, n, rows):
        pair = jax.lax.dynamic_slice(plan.row_pair, (at,), (n,))
        valid = jax.lax.dynamic_slice(plan.row_valid, (at,), (n,))
        return jax.lax.dynamic_update_slice(
            rows, jnp.where(valid[:, None], u[pair // k], 0), (at, 0))

    return _over_used_rows(
        plan, one_turn,
        jnp.zeros((plan.row_pair.shape[0], u.shape[1]), u.dtype))


def _gather_rows_fwd(u, plan):
    return gather_rows(u, plan), plan


def _gather_rows_bwd(plan, d_rows):
    ones = jnp.ones(plan.pair_row.shape, jnp.float32)
    return _sum_rows_of_pairs(d_rows, plan, ones).astype(d_rows.dtype), None


gather_rows.defvjp(_gather_rows_fwd, _gather_rows_bwd)


@jax.custom_vjp
def combine_rows(y: jax.Array, gates: jax.Array, plan: Plan) -> jax.Array:
    """``m[t] = sum over t's held pairs of gate * y[row of the pair]``,
    float32 sum, in ``y``'s dtype."""
    return _sum_rows_of_pairs(y, plan, gates).astype(y.dtype)


def _combine_rows_fwd(y, gates, plan):
    return combine_rows(y, gates, plan), (y, gates, plan)


def _combine_rows_bwd(residuals, d_out):
    y, gates, plan = residuals
    k = gates.shape[1]
    flat_gates = gates.reshape(-1)

    def one_turn(at, n, carry):
        # g: the cotangent row of each row's token, float32; times the
        # row's gate it is d_y, dotted with y's own row the gate's gradient
        d_y, row_dot = carry
        pair = jax.lax.dynamic_slice(plan.row_pair, (at,), (n,))
        valid = jax.lax.dynamic_slice(plan.row_valid, (at,), (n,))
        g = d_out[pair // k].astype(jnp.float32)
        gate = jnp.where(valid, flat_gates[pair], 0.0)
        y_rows = jax.lax.dynamic_slice(y, (at, 0), (n, y.shape[1]))
        d_y = jax.lax.dynamic_update_slice(
            d_y, (gate[:, None] * g).astype(y.dtype), (at, 0))
        row_dot = jax.lax.dynamic_update_slice(
            row_dot, jnp.sum(g * y_rows.astype(jnp.float32), axis=-1), (at,))
        return d_y, row_dot

    d_y, row_dot = _over_used_rows(
        plan, one_turn,
        (jnp.zeros_like(y), jnp.zeros((y.shape[0],), jnp.float32)))
    d_gates = jnp.where(plan.pair_held, row_dot[plan.pair_row], 0.0)
    return d_y, d_gates.astype(gates.dtype), None


combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)


#: The gate's activation by its name in a model's configuration.
ACTIVATIONS = {"relu": jax.nn.relu, "silu": jax.nn.silu}


def kernel_impl(use_pallas: bool) -> str:
    """``"pallas"`` where the grouped-product kernels were asked for and
    can run (a TPU backend), else ``"jnp"`` with the fallback counted."""
    if not use_pallas:
        return "jnp"
    if jax.default_backend() == "tpu":
        return "pallas"
    from fmda_tpu.ops.dispatch import count_kernel_fallback

    count_kernel_fallback("decoder", "backend")
    return "jnp"


def expert_layer(
    u: jax.Array,
    gates: jax.Array,
    experts: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    *,
    experts_held: Tuple[int, int],
    impl: str = "jnp",
    act: str = "relu",
) -> Tuple[jax.Array, Plan]:
    """The held experts' part of the layer's output, and the plan it was
    computed under (whose ``group_sizes`` and ``dropped`` the caller
    counts).

    ``u`` (T, D) in the compute dtype; ``gates``/``experts`` (T, k) from
    :func:`route`; ``w_gate``/``w_up`` (count, D, F) and ``w_down``
    (count, F, D), float32, the held experts' matrices in order.  ``act``
    is the gate's activation, ``"relu"`` or ``"silu"`` (the grouped
    products are the same).
    """
    from fmda_tpu.ops.pallas_moe import grouped_matmul

    t, k = experts.shape
    tile = default_row_tile(t * k)
    with jax.named_scope("moe_dispatch"):
        plan = jax.tree.map(
            jax.lax.stop_gradient,
            plan_dispatch(experts, experts_held, tile))
        rows = gather_rows(u, plan)
    with jax.named_scope("moe_experts"):
        tables = (plan.tile_expert, plan.n_used, tile, impl)
        gate = grouped_matmul(rows, w_gate, *tables)
        up = grouped_matmul(rows, w_up, *tables)
        y = grouped_matmul(ACTIVATIONS[act](gate) * up, w_down, *tables)
    with jax.named_scope("moe_combine"):
        return combine_rows(y, gates, plan), plan
