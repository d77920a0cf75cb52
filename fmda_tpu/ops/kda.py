"""The delta rule with a decay, in chunks: the sequence mixer of a
``decoder`` layer of ``layer_layout`` 5 (a decay a channel, Kimi Delta
Attention) and 6 (one decay a head, a gated delta rule; models/decoder.py).

One head carries a ``(K, V)`` state (``q``, ``k``: ``(T, H, K)``; ``v``:
``(T, H, V)``, ``V`` its own width; ``g``: float32 log-decays, never
positive, ``(T, H, K)`` or ``(T, H)``; ``b``: ``(T, H)``, 0..1 or, where
the correction may overshoot, 0..2).  **The shape of** ``g`` **says which
rule**, a shape the code observes and no option: ``(T, H, K)`` decays the
state by a vector, a factor a key channel (below as written); ``(T, H)``
by one factor a head, ``Diag(exp(g_t))`` then ``exp(g_t) I``.  Every
position decays the state and then corrects it by a rank-one step towards
``v_t`` along ``k_t``::

    S_t = Diag(exp(g_t)) S_{t-1} ;  S_t += b_t k_t (v_t - S_t^T k_t)^T      S_{-1} = 0
    o_t = scale * S_t^T q_t

:func:`kda_stepwise` is that recurrence as written, a ``lax.scan`` over
positions in float32: the form the tests hold the chunked one to.  It
keeps a ``(B, H, K, V)`` state a position in backward (17 GB at 8,192
positions of 32 heads of 128 x 128), so training runs :func:`kda_scan`,
the same numbers in chunks of ``chunk`` positions.  With ``G_i`` the
sum of ``g`` from a chunk's first position to its ``i``-th and ``S``
the state entering the chunk::

    kda_intra   A_ij = sum_c k_ic k_jc exp(G_ic - G_jc)   (j < i) ;  B_ij the same with q_i (j <= i)
    kda_solve   (I + Diag(b) A) [u0 | w] = Diag(b) [V | K * exp(G)]      unit lower triangular
    kda_carry   u = u0 - w S ;  S' = Diag(exp(G_last)) S + (K * exp(G_last - G))^T u
    kda_out     o = scale * ((q * exp(G)) S + B u)

(``u_i`` is the correction position ``i`` writes: ``b_i (v_i - S_i^T
k_i)`` of the recurrence; the solve gives all of a chunk's at once, and
splits off what the entering state adds, so that everything but
``kda_carry`` and ``kda_out`` is independent of the walk.)  **The solve
is float32 products, and substitution, not a power series.**  With ``L
= I + Diag(b) A`` the inverse is made by halving, ``inv([[L11, 0],
[L21, L22]]) = [[X, 0], [-Y L21 X, Y]]`` with ``X = inv(L11)``, ``Y =
inv(L22)``, from diagonal blocks of :data:`SOLVE_ROWS` rows upward, and
``[u0 | w]`` is one product with it on the MXU
(:func:`_unit_lower_solve`; backward is two more); the halving's own
products, of blocks of 4 to 32 rows, run on the vector unit with the
group's systems on the minor axis.  ``(I + N)^-1 = (I - N)(I + N^2)(I
+ N^4)...`` is the same inverse in exact arithmetic and is used inside a
block of four rows only: where neighbouring keys agree the powers of
``N`` grow binomially before they cancel, and float32 loses the answer
(the numbers are at :data:`SOLVE_ROWS`); substitution keeps every
intermediate at the size of the answer.  **Every
exponent is a difference** ``G_i - G_j`` **with** ``j <= i``**, never
positive, and no term divides by a decay**: at the published
initialisation ``g`` reaches -1.6 a position, a decay underflows inside a
chunk of 64, and a form that multiplies by ``exp(-G)`` gives inf times 0.
A decay a channel makes the pairwise factor of ``A`` a ``(chunk, chunk,
K)`` tensor; it is made only where it must be.  A chunk is taken in
sub-blocks of :data:`SUB_ROWS` rows: for ``i`` in a sub-block whose first
row is ``r`` and ``j`` before ``r``, ``exp(G_i - G_j) = exp(G_i - G_r)
exp(G_r - G_j)``, both factors at most one, so the off-diagonal
sub-blocks are plain products on the MXU and the ``(SUB_ROWS, SUB_ROWS,
K)`` tensor exists on the diagonal sub-blocks alone.  **Where it exists
is** ``kda_scan``**'s** ``impl``: as ``"jnp"`` (:func:`_pairwise`: the
CPU's path, the tests' yardstick, and what runs where the kernels refuse
a shape) it is an array of a group of chunks, as are its masked exponent
and its products before their sums over ``K``; as ``"pallas"`` or
``"interpret"`` (:mod:`fmda_tpu.ops.pallas_kda`) a chunk-head's ``q``,
``k`` and ``G`` enter VMEM once, ``A`` and ``B`` leave it, and the
tensor is a kernel's values, forward and, made again, in a backward
written by hand.  The kernels take ``K`` a multiple of 128 lanes and
chunks of whole sub-blocks (``pallas_kda.fits``); a refusal is counted
(``decoder:kda_shape``).  :data:`SUB_ROWS` is the same on both paths:
inside a sub-block the sums are float32 on unrounded operands, outside
it products of operands rounded to ``dtype``, so another sub-block is
another rounding.

**With one decay a head the decays leave the sums over** ``K``: ``A_ij
= (k_i . k_j) exp(G_i - G_j)``, ``B_ij = (q_i . k_j) exp(G_i - G_j)``,
so ``kda_intra`` is two plain ``(C, K) x (K, C)`` products in ``dtype``
with float32 accumulation and one masked ``(C, C)`` float32 exponent a
head (:func:`_pairwise_a_head`; the factor is ``ops/ssd.py``'s
:func:`~fmda_tpu.ops.ssd.pairwise_decays`, the matrix a state-space
layer's scan weighs its pairs by), whatever ``impl`` and whatever ``K``
(there is no tensor for a kernel to keep out of HBM).  The solve, the
carry and the output are the same code: ``exp(G)``, ``exp(G_last - G)``
and ``exp(G_last)`` are then scalars a row.  ``b`` up to 2 changes
nothing in the algebra: ``I + Diag(b) A`` stays unit lower triangular,
its entries double, and substitution solves it as before (the case
``keys_alike_beta_two`` of tests/test_kda.py).

The chunks are walked once, in order, as ``ops/ssd.py`` walks its own: a
``lax.scan`` over groups of :data:`CHUNK_GROUP` chunks carries the state,
a turn does all four parts for its group (the carry unrolled over the
group's chunks) under ``jax.checkpoint``, so that backward keeps of the
walk the state at each group's edge and makes the rest again a group at
a time.  ``G``, its exponentials, the solve and the carried state are
float32; the products take operands in ``dtype`` (each rounded once,
after its float32 decay factor) and accumulate in float32.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from fmda_tpu.ops.dispatch import count_kernel_fallback
from fmda_tpu.ops.ssd import pairwise_decays

#: Chunks a turn of the walk takes (512 positions at a chunk of 64: what
#: exists a group at a time is then 8 MB a float32 array of 32 heads of
#: 128).  On the chip, value and gradient of one layer's walk at 8,192
#: positions read 77.6 ms at 8, 83.3 at 16 and 100.6 at 32 (PERF.md
#: section 6, PR 49).
CHUNK_GROUP = 8
#: Rows of a sub-block: the pairwise decays exist as a tensor on the
#: ``(SUB_ROWS, SUB_ROWS)`` diagonal sub-blocks of a chunk only.
SUB_ROWS = 16
#: Rows of the diagonal blocks the chunk's solve inverts by powers of
#: their strictly lower part, ``(I - N)(I + N^2)``; above them blocks are
#: merged by substitution.  Where a chunk's keys all but agree (``k_0 +
#: 0.05 noise``, ``b`` 1: float32 against a float64 solve of 64 rows, CPU
#: run) powers over all 64 rows read 7e+10, over blocks of 16 rows
#: 4.8e-4, of 8 rows 1.0e-6, of 4 or 2 rows 4e-7, XLA's solve 3.3e-7:
#: ``N^8`` of 16 such rows reaches 6,435 before the series cancels it.
SOLVE_ROWS = 4


def kda_stepwise(q, k, v, g, b, *, scale=None
                 ) -> Tuple[jax.Array, jax.Array]:
    """The recurrence as written, one position at a time, float32:
    ``q`` / ``k`` (B, T, H, K), ``g`` (B, T, H, K) or, one decay a head,
    (B, T, H), ``v`` (B, T, H, V), ``b`` (B, T, H) -> ``(o (B, T, H, V),
    the state after the last position (B, H, K, V))``."""
    f32 = jnp.float32
    q, k, v, g, b = (x.astype(f32) for x in (q, k, v, g, b))
    if g.ndim == 3:  # one decay a head: every channel's
        g = g[..., None]
    batch, _, h, dk = q.shape
    scale = dk ** -0.5 if scale is None else scale

    def step(state, at):
        q_t, k_t, v_t, g_t, b_t = at
        state = jnp.exp(g_t)[..., None] * state
        miss = v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t)
        state = state + jnp.einsum("bh,bhk,bhv->bhkv", b_t, k_t, miss)
        return state, scale * jnp.einsum("bhkv,bhk->bhv", state, q_t)

    state, o = jax.lax.scan(
        step, jnp.zeros((batch, h, dk, v.shape[-1]), f32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, b)))
    return jnp.moveaxis(o, 0, 1), state


def _largest_divisor(n: int, most: int) -> int:
    """The largest divisor of ``n`` that is at most ``most``."""
    d = min(most, n)
    while n % d:
        d -= 1
    return d


def _block_diagonal(blocks: jax.Array) -> jax.Array:
    """(..., s, R, R) -> (..., s R, s R) with the blocks on the diagonal."""
    s, r = blocks.shape[-3], blocks.shape[-1]
    full = jnp.einsum("...sij,st->...sitj", blocks,
                      jnp.eye(s, dtype=blocks.dtype))
    return full.reshape(blocks.shape[:-3] + (s * r, s * r))


def _pairwise(q, k, gc, sub: int, dtype):
    """``(A, B)`` of the module docstring for a group of chunks: ``q``
    (already scaled), ``k``, ``gc`` (B, G, H, C, K) float32 -> two
    (B, G, H, C, C) float32, ``A`` strictly lower triangular and ``B``
    lower triangular."""
    f32 = jnp.float32
    chunk = q.shape[-2]
    s = chunk // sub
    by_sub = lambda x: x.reshape(x.shape[:-2] + (s, sub) + x.shape[-1:])
    qs, ks, gs = by_sub(q), by_sub(k), by_sub(gc)
    # rows of a sub-block against the positions before it: through the
    # sub-block's first row r, exp(G_i - G_r) exp(G_r - G_j), both <= 1
    first = gs[..., :1, :]                                # (.., s, 1, K)
    within = jnp.exp(gs - first)
    before = (jnp.arange(chunk)[None, :]
              < (jnp.arange(s) * sub)[:, None])           # (s, C): j < r
    reach = first - gc[..., None, :, :]                   # (.., s, C, K)
    # the exponent is masked, not the result: no masked slot overflows
    k_right = (k[..., None, :, :] * jnp.exp(
        jnp.where(before[..., None], reach, -jnp.inf))).astype(dtype)
    a, b = (jnp.einsum("...srk,...sjk->...srj", (x * within).astype(dtype),
                       k_right, preferred_element_type=f32
                       ).reshape(x.shape[:-3] + (chunk, chunk))
            for x in (ks, qs))
    # ... and against its own rows: the one place the pairwise decays are
    # a tensor, (.., s, R, R, K)
    lower = jnp.tril(jnp.ones((sub, sub), bool))          # j <= i
    span = gs[..., :, None, :] - gs[..., None, :, :]
    decayed = ks[..., None, :, :] * jnp.exp(
        jnp.where(lower[..., None], span, -jnp.inf))
    a = a + _block_diagonal(jnp.sum(ks[..., :, None, :] * decayed, -1))
    b = b + _block_diagonal(jnp.sum(qs[..., :, None, :] * decayed, -1))
    return a * jnp.tril(jnp.ones((chunk, chunk), f32), -1), b


def _pairwise_a_head(q, k, gc, dtype):
    """``(A, B)`` where a head has ONE decay: ``q`` (already scaled),
    ``k`` (B, G, H, C, K), ``gc`` (B, G, H, C) float32 -> two (B, G, H,
    C, C) float32.  The decays leave the sums over ``K``: two plain
    products in ``dtype`` and one masked ``(C, C)`` exponent a head
    (``ops/ssd.py``'s)."""
    f32 = jnp.float32
    chunk = q.shape[-2]
    decays = pairwise_decays(gc, jnp.tril(jnp.ones((chunk, chunk), bool)))
    k_n = k.astype(dtype)
    a, b = (jnp.einsum("...ik,...jk->...ij", x, k_n,
                       preferred_element_type=f32) * decays
            for x in (k_n, q.astype(dtype)))
    return a * jnp.tril(jnp.ones((chunk, chunk), f32), -1), b


def _product(x, y):
    """``x @ y`` of float32 operands at float32's precision: the chip's
    default for them is one bfloat16 pass, so every pass is asked for."""
    return jnp.matmul(x, y, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _by_system(x, y):
    """``(i, j, N) x (j, k, N) -> (i, k, N)``: a product a system with the
    systems on the minor axis, multiplied and summed on the vector unit
    (a block of 4 to 32 rows fills a sixteenth of an MXU tile or less;
    here every lane works whatever the block).  On the chip one layer's
    walk at 8,192 positions reads 8.39 ms forward and 44.30 with its
    gradient this way, 11.21 and 49.14 with every level as two masked
    ``(64, 64)`` products on the MXU, 18.96 and 77.58 with XLA's
    triangular solve (PERF.md section 6, PR 50)."""
    return jnp.sum(x[:, :, None, :] * y[None, :, :, :], axis=1)


def _halved(n):
    """``(I + n)^-1`` for ``n`` (rows, rows, N) strictly lower triangular,
    ``rows`` a power of two times :data:`SOLVE_ROWS` at most: both
    halves' inverses in one call, side by side on the minor axis, then
    the block under them."""
    rows, _, systems = n.shape
    if rows <= SOLVE_ROWS:
        eye = jnp.eye(rows, dtype=n.dtype)[..., None]
        # exact: a strictly lower block of four rows has a zero fourth power
        return _by_system(eye - n, eye + _by_system(n, n))
    half = rows // 2
    both = _halved(jnp.concatenate([n[:half, :half], n[half:, half:]], -1))
    upper, lower = both[..., :systems], both[..., systems:]
    below = -_by_system(lower, _by_system(n[half:, :half], upper))
    return jnp.concatenate([
        jnp.concatenate([upper, jnp.zeros_like(upper)], 1),
        jnp.concatenate([below, lower], 1)], 0)


def _unit_lower_inverse(n):
    """``(I + n)^-1`` for ``n`` (..., C, C) float32, strictly lower
    triangular, by halving (module docstring; :func:`_halved`), with the
    systems moved to the minor axis for it and ``C`` padded to a power of
    two times :data:`SOLVE_ROWS` with rows of the identity."""
    rows = n.shape[-1]
    size = SOLVE_ROWS
    while size < rows:
        size *= 2
    by_system = jnp.moveaxis(n.reshape((-1, rows, rows)), 0, -1)
    by_system = jnp.pad(by_system, ((0, size - rows),) * 2 + ((0, 0),))
    inv = _halved(by_system)[:rows, :rows]
    return jnp.moveaxis(inv, -1, 0).reshape(n.shape)


@jax.custom_vjp
def _unit_lower_solve(n, r):
    """``x`` of ``(I + n) x = r``: :func:`_unit_lower_inverse` and one
    product.  Backward is two products from the inverse and ``x``, which
    forward keeps, and not the transform of the halving."""
    return _unit_lower_solve_fwd(n, r)[0]


def _unit_lower_solve_fwd(n, r):
    inv = _unit_lower_inverse(n)
    x = _product(inv, r)
    return x, (inv, x)


def _unit_lower_solve_bwd(kept, dx):
    inv, x = kept
    dr = _product(jnp.swapaxes(inv, -1, -2), dx)
    return -jnp.tril(_product(dr, jnp.swapaxes(x, -1, -2)), -1), dr


_unit_lower_solve.defvjp(_unit_lower_solve_fwd, _unit_lower_solve_bwd)


def kda_scan(q, k, v, g, b, *, chunk: int, dtype=jnp.float32, scale=None,
             impl: str = "jnp") -> Tuple[jax.Array, jax.Array, jax.Array]:
    """:func:`kda_stepwise` in chunks of ``chunk`` positions (module
    docstring): ``(o (B, T, H, V) float32, the state after the last
    position (B, H, K, V) float32, the largest |G| inside a chunk ()
    float32)``.  ``g`` is (B, T, H, K), a decay a channel, or (B, T, H),
    one a head: ``kda_intra`` is then :func:`_pairwise_a_head` whatever
    ``impl``, and the decays of the other three parts scalars a row.  A
    length that is no multiple of ``chunk`` is padded with
    positions that neither decay nor correct the state (``g`` and ``b``
    zero).  ``impl`` says where ``kda_intra``'s pairwise decays live:
    ``"jnp"`` in arrays (:func:`_pairwise`), ``"pallas"`` or
    ``"interpret"`` in the kernels of :mod:`fmda_tpu.ops.pallas_kda`,
    where those take the shapes; a refusal is counted
    (``decoder:kda_shape``) and runs the ``jnp`` form."""
    f32 = jnp.float32
    batch, t, h, dk = q.shape
    dv = v.shape[-1]
    a_head = g.ndim == 3
    scale = dk ** -0.5 if scale is None else scale
    pad = -t % chunk
    if pad:
        q, k, v, g, b = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, b))
    n_chunks = (t + pad) // chunk
    group = _largest_divisor(n_chunks, CHUNK_GROUP)
    sub = _largest_divisor(chunk, SUB_ROWS)
    if impl != "jnp" and not a_head:
        from fmda_tpu.ops import pallas_kda  # Pallas: where it is asked for

        if not pallas_kda.fits(chunk, sub, dk):
            count_kernel_fallback("decoder", "kda_shape")
            impl = "jnp"

    def walk(state, at):
        """A group of chunks in order from the ``state`` (B, H, K, V)
        before it: the state after it, the group's ``o`` (B, G, H, C, V)
        and the largest |G| among its chunks."""
        q, k, v, g, b = at                                # (B, G, H, C, .)
        q32, k32 = q.astype(f32) * scale, k.astype(f32)
        b = b.astype(f32)[..., None]
        # over the chunk's positions; never positive
        gc = jnp.cumsum(g.astype(f32), axis=-1 if a_head else -2)
        if a_head:  # (B, G, H, C, 1): a scalar a row from here on
            gc = gc[..., None]
        from_start = jnp.exp(gc)
        if a_head:
            with jax.named_scope("kda_intra"):
                a_kk, a_qk = _pairwise_a_head(q32, k32, gc[..., 0], dtype)
        elif impl == "jnp":
            with jax.named_scope("kda_intra"):
                a_kk, a_qk = _pairwise(q32, k32, gc, sub, dtype)
        else:  # the scope is opened inside the rule's two directions
            a_kk, a_qk = pallas_kda.pairwise(
                sub, dtype, impl == "interpret", q32, k32, gc)
        with jax.named_scope("kda_solve"):
            # what each position writes, but for the entering state's part
            solved = _unit_lower_solve(
                b * a_kk,
                b * jnp.concatenate([v.astype(f32), k32 * from_start], -1))
            u0, w = solved[..., :dv], solved[..., dv:].astype(dtype)
        q_in = (q32 * from_start).astype(dtype)
        to_end = (k32 * jnp.exp(gc[..., -1:, :] - gc)).astype(dtype)
        through = jnp.exp(gc[..., -1, :])                 # (B, G, H, K)
        a_qk = a_qk.astype(dtype)
        out = []
        for c in range(q.shape[1]):
            before = state.astype(dtype)
            with jax.named_scope("kda_carry"):
                u = u0[:, c] - jnp.einsum(
                    "bhck,bhkv->bhcv", w[:, c], before,
                    preferred_element_type=f32)
                u_n = u.astype(dtype)
                state = through[:, c, :, :, None] * state + jnp.einsum(
                    "bhck,bhcv->bhkv", to_end[:, c], u_n,
                    preferred_element_type=f32)
            with jax.named_scope("kda_out"):
                out.append(
                    jnp.einsum("bhck,bhkv->bhcv", q_in[:, c], before,
                               preferred_element_type=f32)
                    + jnp.einsum("bhcj,bhjv->bhcv", a_qk[:, c], u_n,
                                 preferred_element_type=f32))
        return state, (jnp.stack(out, 1), jnp.max(jnp.abs(gc[..., -1, :])))

    def grouped(x):  # (B, T, H, ...) -> (T / (G C), B, G, H, C, ...)
        x = x.reshape((batch, n_chunks // group, group, chunk) + x.shape[2:])
        return jnp.moveaxis(jnp.swapaxes(x, 3, 4), 1, 0)

    start = jnp.zeros((batch, h, dk, dv), f32)
    args = tuple(grouped(x) for x in (q, k, v, g, b))
    if group == n_chunks:
        state, (o, absmax) = walk(start, tuple(x[0] for x in args))
        o = o[None]
    else:
        state, (o, absmax) = jax.lax.scan(jax.checkpoint(walk), start, args)
    # (turns, B, G, H, C, V) -> (B, T, H, V)
    o = jnp.swapaxes(jnp.moveaxis(o, 0, 1), 3, 4).reshape(
        batch, t + pad, h, dv)[:, :t]
    return o, state, jax.lax.stop_gradient(jnp.max(absmax))
