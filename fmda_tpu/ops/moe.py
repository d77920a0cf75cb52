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
layout is sized for twice the even share of the call's pairs
(:func:`round_pairs`; every pair where the chip holds every expert) and
is filled in rounds: a round lays out the next ``P`` held pairs, sorted
by expert, the grouped products skip the tiles no pair fell into
(:mod:`fmda_tpu.ops.pallas_moe`), and the layer's output is the float32
sum of its rounds.  A routing near the even share, every step of a cell
of record, is one round; one expert taking every token is
``ceil(T * k / P)`` rounds and still exact.  The steps, each under its
scope (docs/observability.md "Spans and scopes"), the last three once a
round:

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
twice the even share, a round's groups fill it from row 0 without a gap,
and nothing reads a row past them (the grouped products skip those
tiles, a held pair's row lies below ``n_used * tile``, an unheld pair's
is row 0), so the rest of a row buffer is the zeros it was made of.  The combine's backward makes
one such pass for both of its cotangents: ``d_y`` is the token's
cotangent row times the gate, and the gate's own gradient is the dot of
the same two rows, taken while both are at hand and gathered back to
(T, k) as scalars.  The passes over *tokens* (``combine_rows`` forward,
``gather_rows`` backward) still gather one (T, D) block a slot.

The number of rounds is the routing's, so the rounds are a ``while``,
and a ``while`` lives in hand-written sides only: :func:`expert_layer`
is one ``custom_vjp`` whose forward rule keeps its inputs and the sorted
pairs, and whose backward rule takes ``jax.vjp`` of one round's function
(the pieces above, with their own rules) round by round and adds the
cotangents up.  Under ``remat`` the replay's forward is dead and the
backward makes the forward's arrays once, the count of kernel runs a
recomputed layer had before; a model trained WITHOUT ``remat`` pays a
second forward of the layer in backward (no preset does).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

#: The layer's output by name: a block's recomputation whose policy saves
#: it (:data:`fmda_tpu.models.decoder.REPLAY_KEEPS`) does not run the
#: layer's forward to have it again.  Only a block that reads the value
#: in backward keeps anything (the lanes' mixing: the output's product
#: with the written stream's gradient is the write weights' gradient); a
#: plain residual does not, and its replay's forward is dead code.
EXPERT_OUT = "moe_expert_out"


class Plan(NamedTuple):
    """Where each held (token, expert) pair of one round sits in the
    grouped row layout.  ``R`` rows in tiles of ``tile``; pairs are
    numbered ``token * k + slot``."""

    row_pair: jax.Array     # (R,) int32: the pair a row carries (0: padding)
    row_valid: jax.Array    # (R,) bool
    pair_row: jax.Array     # (T, k) int32: the row of a pair (0: not here)
    pair_held: jax.Array    # (T, k) bool: held, and laid out in this round
    tile_expert: jax.Array  # (R / tile,) int32: held expert of a row tile
    n_used: jax.Array       # (1,) int32: row tiles that hold a group
    group_sizes: jax.Array  # (count,) int32: the round's pairs an expert

    @property
    def tile(self) -> int:
        """Rows a tile (static: the layout's rows over its tiles)."""
        return self.row_pair.shape[0] // self.tile_expert.shape[0]


class SortedPairs(NamedTuple):
    """A call's pairs sorted by held expert, the pairs no held expert
    got behind them: what every round's :class:`Plan` is cut from
    (integers, made once a call)."""

    order: jax.Array   # (T * k,) int32: the pairs, sorted
    rank: jax.Array    # (T * k,) int32: a pair's place in ``order``
    expert: jax.Array  # (T * k,) int32: a pair's held expert (clipped)
    held: jax.Array    # (T * k,) bool
    sizes: jax.Array   # (count,) int32: pairs on each held expert


class Laid(NamedTuple):
    """What a call of :func:`expert_layer` laid out, for its caller to
    count (the names are ``models.decoder.EXPERT_COUNTS``')."""

    expert_pairs: jax.Array    # (count,) int32: pairs on each held expert
    dropped: jax.Array         # () int32: held pairs no round computed (0)
    row_tiles_used: jax.Array  # () int32: tiles that held a group, all rounds
    layout_rounds: jax.Array   # () int32: rounds of the layout (1: all fitted)


def default_row_tile(n_pairs: int) -> int:
    """Rows a tile: 256 at real sizes (an MXU-friendly product per grid
    step), 16 where the whole call is smaller than that."""
    return 256 if n_pairs >= 4096 else 16


#: Even shares of a call's pairs a round of the layout holds.  A chip
#: that holds ``count`` of the router's ``n_experts`` gets ``count /
#: n_experts`` of the pairs from an even router; the benchmark's cells of
#: record refuse a run whose held pairs leave 0.6-1.6 of that share
#: (benchmark/drivers ``HELD_PAIRS_BAND``), so at two shares a routing
#: they admit is one round with room in every layer, and the empty rows
#: every buffer, fill and element-wise pass is paid on are a half of the
#: layout, not the seven eighths a layout for every pair leaves at an
#: eighth of the experts (PERF.md section 6, PR 48).
_SHARES_A_ROUND = 2


def round_pairs(n_pairs: int, count: int, n_experts: int, tile: int) -> int:
    """Pairs a round lays out, in whole tiles: ``_SHARES_A_ROUND`` even
    shares of the call's ``n_pairs``, all of them where that is fewer (a
    chip that holds half the experts, or all: the uncut layer)."""
    share = -(-n_pairs * count // n_experts)
    return -(-min(n_pairs, _SHARES_A_ROUND * share) // tile) * tile


def layout_rows(n_pairs: int, count: int, n_experts: int, tile: int) -> int:
    """Rows of the grouped layout: a round's pairs, each of the ``count``
    groups padded by up to a tile, an empty group keeping one."""
    return round_pairs(n_pairs, count, n_experts, tile) + count * tile


def layout_tiles(n_pairs: int, count: int, n_experts: int) -> int:
    """Row tiles of the layout a call of ``n_pairs`` pairs gets."""
    tile = default_row_tile(n_pairs)
    return layout_rows(n_pairs, count, n_experts, tile) // tile


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


def sort_pairs(experts: jax.Array, experts_held: Tuple[int, int]
               ) -> SortedPairs:
    """This routing's pairs by held expert (integers only, no gradient)."""
    first, count = experts_held
    n_pairs = experts.size
    local = experts.reshape(-1) - first
    held = (local >= 0) & (local < count)
    key = jnp.where(held, local, count)  # the rest sort behind
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    sizes = jnp.sum(
        key[:, None] == jnp.arange(count, dtype=jnp.int32)[None, :],
        axis=0, dtype=jnp.int32)
    rank = jnp.zeros((n_pairs,), jnp.int32).at[order].set(
        jnp.arange(n_pairs, dtype=jnp.int32), unique_indices=True)
    return SortedPairs(order, rank, jnp.clip(local, 0, count - 1), held,
                       sizes)


def n_rounds(sizes: jax.Array, pairs: int) -> jax.Array:
    """Rounds of ``pairs`` pairs that lay out groups of ``sizes``: () int32,
    one where nothing is held."""
    return jnp.maximum((jnp.sum(sizes) + pairs - 1) // pairs, 1)


def plan_round(pairs_sorted: SortedPairs, round_, pairs: int, tile: int,
               shape: Tuple[int, int]) -> Plan:
    """The row layout of round ``round_`` (a traced number or a Python
    one): the held pairs of ranks ``[round_ * pairs, (round_ + 1) *
    pairs)`` among the sorted, each expert's part of them a contiguous
    group.  ``shape`` is the routing's ``(T, k)``."""
    order, rank, e_of_pair, held, all_sizes = pairs_sorted
    count, n_pairs = all_sizes.shape[0], order.shape[0]
    rows = pairs + count * tile
    low = round_ * pairs
    ends = jnp.cumsum(all_sizes)
    # each group's part in this round: where it starts among the sorted
    # pairs, and how many it is (an expert of another round: none)
    starts = jnp.maximum(ends - all_sizes, low)
    sizes = jnp.maximum(jnp.minimum(ends, low + pairs) - starts, 0)
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
    here = held & (rank >= low) & (rank < low + pairs)
    row_of_pair = row_starts[e_of_pair] + rank - starts[e_of_pair]
    placed = here & (row_of_pair < rows)
    return Plan(
        row_pair=row_pair, row_valid=row_valid,
        pair_row=jnp.where(placed, row_of_pair, 0).reshape(shape),
        pair_held=placed.reshape(shape),
        tile_expert=tile_expert,
        n_used=tile_ends[-1:].astype(jnp.int32),
        group_sizes=sizes)


def plan_dispatch(experts: jax.Array, experts_held: Tuple[int, int],
                  n_experts: int, tile: int, round_: int = 0) -> Plan:
    """The row layout of one round of this routing (integers only, no
    gradient)."""
    pairs = round_pairs(experts.size, experts_held[1], n_experts, tile)
    return plan_round(sort_pairs(experts, experts_held), round_, pairs, tile,
                      experts.shape)


def _sum_rows_of_pairs(x: jax.Array, plan: Plan, weights: jax.Array,
                       out: jax.Array = None) -> jax.Array:
    """``out[t] += sum over t's held pairs of weights[t, slot] * x[row of
    the pair]``, (T, D) float32 (from zeros without ``out``): one gather
    of (T, D) rows a slot."""
    if out is None:
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


class _Layout(NamedTuple):
    """What of a call is static: the tile, a round's pairs, the kernels
    and the gate's activation."""

    tile: int
    pairs: int
    impl: str
    act: str


def _round(layout: _Layout, plan: Plan, combine, u, gates, w_gate, w_up,
           w_down):
    """One round's function of the layer's inputs: the rows of ``plan``
    gathered, through the held experts, and back to tokens by
    ``combine(y, gates, plan)``."""
    from fmda_tpu.ops.pallas_moe import grouped_matmul

    with jax.named_scope("moe_dispatch"):
        rows = gather_rows(u, plan)
    with jax.named_scope("moe_experts"):
        tables = (plan.tile_expert, plan.n_used, layout.tile, layout.impl)
        gate = grouped_matmul(rows, w_gate, *tables)
        up = grouped_matmul(rows, w_up, *tables)
        y = grouped_matmul(ACTIVATIONS[layout.act](gate) * up, w_down,
                           *tables)
    with jax.named_scope("moe_combine"):
        return combine(y, gates, plan)


def _over_rounds(layout: _Layout, pairs_sorted: SortedPairs, shape, body,
                 init):
    """``carry = body(plan, carry)`` for the plan of each round of the
    routing, in order.  The count is the routing's, so this is a
    ``while``, called from the hand-written sides of
    :func:`_rounds_summed` alone (:func:`_sum_of_rounds`,
    :func:`_cotangents_of_rounds`)."""
    rounds = n_rounds(pairs_sorted.sizes, layout.pairs)

    def one_round(state):
        i, carry = state
        with jax.named_scope("moe_dispatch"):
            plan = plan_round(pairs_sorted, i, layout.pairs, layout.tile,
                              shape)
        return i + 1, body(plan, carry)

    return jax.lax.while_loop(
        lambda state: state[0] < rounds, one_round,
        (jnp.zeros((), jnp.int32), init))[1]


# Both loops are a ``jax.jit`` of their own: a model's layers call them at
# one signature, so each is traced and lowered once a program, not once a
# layer and pass (a Pallas call site costs the chip machine's host about
# a second of every set-up: PERF.md section 6, PR 42's review round).

@functools.partial(jax.jit, static_argnums=(0,))
def _sum_of_rounds(layout: _Layout, u, gates, w_gate, w_up, w_down,
                   pairs_sorted: SortedPairs):
    """``(m, row tiles used, rows filled)``: the float32 sum of the
    rounds' combines in ``u``'s dtype, and what the rounds laid out."""

    def one_round(plan, carry):
        m, used, filled = carry
        m = _round(
            layout, plan,
            lambda y, gates, plan: _sum_rows_of_pairs(y, plan, gates, m),
            u, gates, w_gate, w_up, w_down)
        return (m, used + plan.n_used[0],
                filled + jnp.sum(plan.row_valid, dtype=jnp.int32))

    zero = jnp.zeros((), jnp.int32)
    m, used, filled = _over_rounds(
        layout, pairs_sorted, gates.shape, one_round,
        (jnp.zeros(u.shape, jnp.float32), zero, zero))
    return m.astype(u.dtype), used, filled


@functools.partial(jax.jit, static_argnums=(0,))
def _cotangents_of_rounds(layout: _Layout, d_m, pairs_sorted: SortedPairs,
                          *primals):
    """The cotangents of ``primals`` (``u``, ``gates`` and the three
    weight stacks) for the output's ``d_m``: each round's own, from
    ``jax.vjp`` of the round's function, summed in float32."""

    def one_round(plan, sums):
        # the round's own arrays are made here, once: the forward rule
        # kept none of them
        _, vjp = jax.vjp(
            functools.partial(_round, layout, plan, combine_rows), *primals)
        return [a + d.astype(jnp.float32) for a, d in zip(sums, vjp(d_m))]

    sums = _over_rounds(
        layout, pairs_sorted, primals[1].shape, one_round,
        [jnp.zeros(x.shape, jnp.float32) for x in primals])
    return tuple(a.astype(x.dtype) for a, x in zip(sums, primals))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _rounds_summed(layout: _Layout, u, gates, w_gate, w_up, w_down,
                   pairs_sorted: SortedPairs):
    """:func:`_sum_of_rounds` with its hand-written backward: the forward
    rule keeps the inputs, the backward rule makes each round again."""
    return _sum_of_rounds(layout, u, gates, w_gate, w_up, w_down,
                          pairs_sorted)


def _rounds_summed_fwd(layout, *inputs):
    return _sum_of_rounds(layout, *inputs), inputs


def _rounds_summed_bwd(layout, inputs, cotangents):
    *primals, pairs_sorted = inputs
    return _cotangents_of_rounds(
        layout, cotangents[0], pairs_sorted, *primals) + (None,)


_rounds_summed.defvjp(_rounds_summed_fwd, _rounds_summed_bwd)


def expert_layer(
    u: jax.Array,
    gates: jax.Array,
    experts: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    *,
    experts_held: Tuple[int, int],
    n_experts: int,
    impl: str = "jnp",
    act: str = "relu",
) -> Tuple[jax.Array, Laid]:
    """The held experts' part of the layer's output, and what the call
    laid out (which the caller counts).

    ``u`` (T, D) in the compute dtype; ``gates``/``experts`` (T, k) from
    :func:`route`, over the router's ``n_experts`` outputs; ``w_gate``/
    ``w_up`` (count, D, F) and ``w_down`` (count, F, D), float32, the
    held experts' matrices in order.  ``act`` is the gate's activation,
    ``"relu"`` or ``"silu"`` (the grouped products are the same).
    """
    tile = default_row_tile(experts.size)
    layout = _Layout(
        tile, round_pairs(experts.size, experts_held[1], n_experts, tile),
        impl, act)
    with jax.named_scope("moe_dispatch"):
        pairs_sorted = sort_pairs(experts, experts_held)
    m, used, filled = _rounds_summed(
        layout, u, gates, w_gate, w_up, w_down, pairs_sorted)
    sizes = pairs_sorted.sizes
    return checkpoint_name(m, EXPERT_OUT), Laid(
        expert_pairs=sizes, dropped=jnp.sum(sizes) - filled,
        row_tiles_used=used, layout_rounds=n_rounds(sizes, layout.pairs))
