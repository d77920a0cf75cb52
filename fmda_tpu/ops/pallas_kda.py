"""The delta-rule walk's pairwise products inside a chunk (Pallas TPU).

:func:`fmda_tpu.ops.kda._pairwise` makes, for a group of chunks, ``A_ij =
sum_c k_ic k_jc exp(G_ic - G_jc)`` (``j < i``) and ``B_ij`` the same with
``q_i`` (``j <= i``).  A decay a channel makes the factor under the sum a
``(rows, rows, K)`` tensor on every diagonal sub-block; as ``jnp`` that
tensor, its masked exponent, its products and their cotangents are XLA's
to place, a group of chunks at a time (134 MB an array a turn of the
walk at 32 heads of 128), and the scope was 128 ms of a 559 ms step
(``PERF.md`` section 6, PR 53: 59 ms here).  Here a chunk-head's ``q``,
``k`` and ``G`` (three ``(C, K)`` tiles) enter VMEM once and ``A``,
``B`` (two ``(C, C)``) leave it; the decays exist as values of a kernel
only, a column of a sub-block (``(8, K)`` registers) at a time.

- ``kda_intra_fwd`` — the numbers of ``_pairwise``, by the same rules:
  every exponent a difference ``G_i - G_j`` with ``j <= i``, the exponent
  masked and not the result, no division by a decay.  A sub-block's rows
  against the positions before it are one product on the MXU, ``[k_i;
  q_i] exp(G_i - G_r)`` by ``k_j exp(G_r - G_j)`` through the sub-block's
  first row ``r``, operands rounded once to ``dtype``; against its own
  rows the sums over ``K`` are float32 on the vector unit, operands
  unrounded.
- ``kda_intra_bwd`` — ``dq``, ``dk``, ``dG`` from ``(dA, dB)`` and the
  same three tiles, in one sweep over the same grid, the decays made
  again.  With ``dk^i`` what a position's key gathers as the row of a
  pair and ``dk^j`` as its column, ``dk = dk^i + dk^j`` and ``dG = k
  (dk^i - dk^j) + q dq``: an exponent's cotangent is its pair's product,
  which both sides already hold.  (What the sub-block's first row would
  gather through both of its factors cancels but for rounding, and is
  left out.)  ``dA`` is masked to strictly lower and ``dB`` to lower
  here, whatever arrives.

``K`` is on the lanes and a sub-block's rows on the sublanes, so a sum
over ``K`` is a reduction across lanes, one a register, and a
cotangent's column is spread across the lanes, one a register
(``PERF.md`` section 6, PR 53, has what each costs).  A kernel walks its
block's sub-blocks in one rolled loop and unrolls a sub-block's sixteen
columns; backward takes each row's cotangents on its own sub-block's
columns as a ``(C, 16)`` input of its own (:func:`_own_block`), so that
a column is a static lane whatever the sub-block.  Each entry point is
a ``jax.jit`` of its own: a step calls the forward three times a layer
and jax traces the kernel's body once a path to it (the turn, the turn
made again by the block's replay and by backward), not once a layer;
the body's size in operations is set-up time on every run, warm cache
or not (unrolled over the sub-blocks too it read +5 s of ``trace_s``,
and 32 ms for the scope: nothing but latency paces these kernels, and a
rolled turn gives the scheduler a quarter of the independent work).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from fmda_tpu.compat import CompilerParams
from fmda_tpu.ops.kda import _largest_divisor

#: Chunk-heads a grid step takes at most (its three tiles are 96 KB at a
#: chunk of 64 by 128).
HEADS_A_STEP = 8
#: Rows of a float32 register: a sub-block is walked by pieces of these
#: (and its rounded operands fill whole packed registers, of twice these).
_ROWS = 8
_LANES = 128


def fits(chunk: int, sub: int, k: int) -> bool:
    """Whether the kernels take chunks of ``chunk`` positions in
    sub-blocks of ``sub`` rows at ``k`` key channels a head: whole lanes,
    whole packed registers."""
    return k % _LANES == 0 and sub % (2 * _ROWS) == 0 and chunk % sub == 0


def _row(ref, n, i):
    """Row ``i`` of chunk-head ``n``, (1, K)."""
    return ref[n, pl.ds(i, 1), :]


def _piece(ref, n, i):
    """Rows ``i .. i + 8`` of chunk-head ``n``, ``i`` a multiple of 8."""
    return ref[n, pl.ds(pl.multiple_of(i, _ROWS), _ROWS), :]


def _decay(g_ref, n, r, j, p):
    """``exp(G_i - G_j)`` for the eight rows ``i`` of piece ``p`` of the
    sub-block at ``r`` against its row ``j``, zero where ``i < j``."""
    span = _piece(g_ref, n, r + p * _ROWS) - _row(g_ref, n, r + j)
    if p == j // _ROWS:  # the piece that holds j: the rows above it
        rows = jax.lax.broadcasted_iota(jnp.int32, span.shape, 0)
        span = jnp.where(rows >= j % _ROWS, span, -jnp.inf)
    return jnp.exp(span)


def _through_first_row(q_ref, k_ref, g_ref, n, r, sub, dtype):
    """The two operands of a sub-block's product with the positions
    before its first row ``r``: ``[k_i; q_i] exp(G_i - G_r)`` (2 sub, K)
    and ``k_j exp(G_r - G_j)`` (C, K), zero from ``r`` on (all of it for
    the chunk's first sub-block), in ``dtype``; and both decay factors,
    float32."""
    first = _row(g_ref, n, r)
    rows = pl.ds(r, sub)
    within = jnp.exp(g_ref[n, rows, :] - first)
    left = jnp.concatenate(
        [k_ref[n, rows, :] * within, q_ref[n, rows, :] * within], 0)
    before = jax.lax.broadcasted_iota(jnp.int32, g_ref.shape[1:], 0) < r
    reach = jnp.exp(jnp.where(before, first - g_ref[n], -jnp.inf))
    return (left.astype(dtype), (k_ref[n] * reach).astype(dtype), within,
            reach)


def _sub_block(t, chunk: int, sub: int):
    """Turn ``t`` of a loop over a block's sub-blocks: the chunk-head and
    the sub-block's first row."""
    s = chunk // sub
    return t // s, pl.multiple_of((t % s) * sub, sub)


def _forward_kernel(q_ref, k_ref, g_ref, a_ref, b_ref, *, sub: int, dtype):
    f32 = jnp.float32
    heads, chunk, _ = q_ref.shape
    pieces = [slice(p * _ROWS, (p + 1) * _ROWS) for p in range(sub // _ROWS)]
    lane = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, chunk), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, chunk), 0)

    def sub_block(t, carry):
        n, r = _sub_block(t, chunk, sub)
        left, right, _, _ = _through_first_row(
            q_ref, k_ref, g_ref, n, r, sub, dtype)
        off = jax.lax.dot_general(left, right, (((1,), (1,)), ((), ())),
                                  preferred_element_type=f32)  # (2 sub, C)
        a = [off[rows] for rows in pieces]
        b = [off[sub:][rows] for rows in pieces]
        for j in range(sub):
            k_j = _row(k_ref, n, r + j)
            for p in range(j // _ROWS, len(pieces)):
                at = r + p * _ROWS
                decayed = k_j * _decay(g_ref, n, r, j, p)
                here = lane == r + j
                a[p] = jnp.where(here, jnp.sum(
                    _piece(k_ref, n, at) * decayed, -1, keepdims=True), a[p])
                b[p] = jnp.where(here, jnp.sum(
                    _piece(q_ref, n, at) * decayed, -1, keepdims=True), b[p])
        for p in range(len(pieces)):
            at = pl.multiple_of(r + p * _ROWS, _ROWS)
            a_ref[n, pl.ds(at, _ROWS), :] = jnp.where(
                lane < row + at, a[p], 0.0)
            b_ref[n, pl.ds(at, _ROWS), :] = b[p]
        return carry

    jax.lax.fori_loop(0, heads * (chunk // sub), sub_block, 0)


def _backward_kernel(q_ref, k_ref, g_ref, da_ref, db_ref, da_own_ref,
                     db_own_ref, dq_ref, dk_ref, dg_ref, *, sub: int, dtype):
    f32 = jnp.float32
    heads, chunk, width = q_ref.shape
    pieces = [slice(p * _ROWS, (p + 1) * _ROWS) for p in range(sub // _ROWS)]
    lane = jax.lax.broadcasted_iota(jnp.int32, (sub, chunk), 1)
    own_lane = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, sub), 1)
    own_row = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, sub), 0)

    def sub_block(t, before):
        # dk_ref holds dk^i and dg_ref dk^j until the head's last lines
        n, r = _sub_block(t, chunk, sub)
        rows = pl.ds(r, sub)
        left, right, within, reach = _through_first_row(
            q_ref, k_ref, g_ref, n, r, sub, dtype)
        ct = jnp.concatenate(
            [jnp.where(lane < r, x[n, rows, :], 0.0)
             for x in (da_ref, db_ref)], 0).astype(dtype)      # (2 sub, C)
        d_left = jax.lax.dot_general(ct, right, (((1,), (0,)), ((), ())),
                                     preferred_element_type=f32)  # (2 sub, K)
        d_right = jax.lax.dot_general(ct, left, (((0,), (0,)), ((), ())),
                                      preferred_element_type=f32)  # (C, K)
        before = jnp.where(r == 0, 0.0, before) + d_right * reach
        dk_i = [d_left[rows_] * within[rows_] for rows_ in pieces]
        dq_i = [d_left[sub:][rows_] * within[rows_] for rows_ in pieces]
        at = [r + p * _ROWS for p in range(len(pieces))]
        da = [jnp.where(own_lane < own_row + p * _ROWS,
                        _piece(da_own_ref, n, i), 0.0)
              for p, i in enumerate(at)]
        db = [jnp.where(own_lane <= own_row + p * _ROWS,
                        _piece(db_own_ref, n, i), 0.0)
              for p, i in enumerate(at)]
        for j in range(sub):
            k_j = _row(k_ref, n, r + j)
            gathered = None
            for p in range(j // _ROWS, len(pieces)):
                decay = _decay(g_ref, n, r, j, p)
                da_j, db_j = da[p][:, j:j + 1], db[p][:, j:j + 1]
                pair = (da_j * _piece(k_ref, n, at[p])
                        + db_j * _piece(q_ref, n, at[p])) * decay
                gathered = pair if gathered is None else gathered + pair
                decayed = k_j * decay
                dk_i[p] = dk_i[p] + da_j * decayed
                dq_i[p] = dq_i[p] + db_j * decayed
            dg_ref[n, pl.ds(r + j, 1), :] = jnp.sum(gathered, 0, keepdims=True)
        for p, i in enumerate(at):
            i = pl.multiple_of(i, _ROWS)
            dk_ref[n, pl.ds(i, _ROWS), :] = dk_i[p]
            dq_ref[n, pl.ds(i, _ROWS), :] = dq_i[p]

        @pl.when(r == chunk - sub)
        def _last():
            as_row, as_column = dk_ref[n], dg_ref[n] + before
            dk_ref[n] = as_row + as_column
            dg_ref[n] = (k_ref[n] * (as_row - as_column)
                         + q_ref[n] * dq_ref[n])

        return before

    jax.lax.fori_loop(0, heads * (chunk // sub), sub_block,
                      jnp.zeros((chunk, width), f32))


def _call(kernel, name, inputs, out_widths, *, sub, dtype, interpret):
    """``kernel`` over the chunk-heads of ``inputs`` (each (..., C, w)
    float32), :data:`HEADS_A_STEP` a grid step: float32 outputs (..., C,
    w) for ``w`` in ``out_widths``."""
    lead, chunk = inputs[0].shape[:-2], inputs[0].shape[-2]
    n = math.prod(lead)
    heads = _largest_divisor(n, HEADS_A_STEP)

    def spec(width):
        return pl.BlockSpec((heads, chunk, width), lambda i: (i, 0, 0))

    out = pl.pallas_call(
        functools.partial(kernel, sub=sub, dtype=dtype),
        name=name,
        grid=(n // heads,),
        in_specs=[spec(x.shape[-1]) for x in inputs],
        out_specs=[spec(w) for w in out_widths],
        out_shape=[jax.ShapeDtypeStruct((n, chunk, w), jnp.float32)
                   for w in out_widths],
        compiler_params=CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*(x.reshape((n,) + x.shape[-2:]) for x in inputs))
    return tuple(o.reshape(lead + o.shape[-2:]) for o in out)


@functools.partial(jax.jit, static_argnames=("sub", "dtype", "interpret"))
def forward(q, k, gc, *, sub: int, dtype, interpret: bool):
    """``q`` (already scaled), ``k``, ``gc`` (..., C, K) float32 -> ``A``,
    ``B`` (..., C, C) float32 (module docstring)."""
    chunk = q.shape[-2]
    return _call(_forward_kernel, "kda_intra_fwd", (q, k, gc),
                 (chunk, chunk), sub=sub, dtype=dtype, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("sub", "dtype", "interpret"))
def backward(q, k, gc, da, db, *, sub: int, dtype, interpret: bool):
    """The three inputs of :func:`forward` and its outputs' cotangents
    -> ``dq``, ``dk``, ``dgc`` (..., C, K) float32."""
    width = q.shape[-1]
    return _call(_backward_kernel, "kda_intra_bwd",
                 (q, k, gc, da, db, _own_block(da, sub), _own_block(db, sub)),
                 (width,) * 3, sub=sub, dtype=dtype, interpret=interpret)


def _own_block(x, sub: int):
    """(..., C, C) -> (..., C, sub): each row's entries on the columns of
    its own sub-block."""
    lead, chunk = x.shape[:-2], x.shape[-1]
    blocks = x.reshape(lead + (chunk // sub, sub, chunk // sub, sub))
    own = jnp.moveaxis(jnp.diagonal(blocks, axis1=-4, axis2=-2), -1, -3)
    return own.reshape(lead + (chunk, sub))


def _scoped_forward(sub: int, dtype, interpret: bool, q, k, gc):
    with jax.named_scope("kda_intra"):
        return forward(q, k, gc, sub=sub, dtype=dtype, interpret=interpret)


#: ``_pairwise(q, k, gc, sub, dtype)`` of :mod:`fmda_tpu.ops.kda` by the
#: kernels, ``pairwise(sub, dtype, interpret, q, k, gc)``, under the
#: walk's ``kda_intra`` scope in both directions; backward keeps the
#: three inputs, which the walk's turn holds anyway.
pairwise = jax.custom_vjp(_scoped_forward, nondiff_argnums=(0, 1, 2))


def _pairwise_fwd(sub, dtype, interpret, q, k, gc):
    return _scoped_forward(sub, dtype, interpret, q, k, gc), (q, k, gc)


def _pairwise_bwd(sub, dtype, interpret, kept, cotangents):
    with jax.named_scope("kda_intra"):
        return backward(*kept, *cotangents, sub=sub, dtype=dtype,
                        interpret=interpret)


pairwise.defvjp(_pairwise_fwd, _pairwise_bwd)
