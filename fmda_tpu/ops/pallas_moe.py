"""Grouped matrix products over the experts a chip holds (Pallas TPU).

The expert layer (:mod:`fmda_tpu.ops.moe`) lays the (token, expert)
pairs that landed on held experts out as *rows grouped by expert*, each
group padded to whole row tiles, so that a tile of ``tile`` rows belongs
to exactly one expert.  Two tables ride ahead of the grid as scalar
prefetch: ``tile_expert[i]``, the held expert of row tile ``i``, and
``n_used``, how many tiles hold rows at all — the layout is sized for
twice the even share of a call's pairs (and filled in rounds: a grid is
one round's), and the tiles past ``n_used`` are skipped.

- ``moe_gmm`` — ``y[tile i] = x[tile i] @ w[tile_expert[i]]`` (or
  ``@ w[...]^T``: the same kernel gives the backward's ``dx``).  Grid
  ``(tiles,)``; the weight block's index changes only where the expert
  does, so an expert's matrix is fetched once for all its tiles.
  Skipped tiles write zeros and re-reference the last used blocks
  (nothing is fetched for them).
- ``moe_tgmm`` — ``dw[e] = sum over e's tiles of x[tile]^T @ dy[tile]``,
  float32.  Grid ``(column tiles, tiles)``; the output block stays in
  VMEM while consecutive tiles share an expert and is zeroed at each
  expert's first tile.  Every held expert owns at least one tile (an
  empty expert gets one tile of zero rows), so every block is written.

Outside a TPU (the CPU tests, the reference path of ``use_pallas=False``)
:func:`grouped_matmul` computes the same products by gathering one
weight matrix per tile — fine at test sizes, never used at real ones.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fmda_tpu.compat import CompilerParams

#: Scoped VMEM the kernels may use: one expert matrix at the published
#: widths is 3.9 MB in bfloat16 and is double-buffered beside the row
#: tiles and the float32 product (v5e: 128 MiB physical, 16 MiB default).
_VMEM_LIMIT = 64 * 1024 * 1024
#: Budget for ``moe_tgmm``'s float32 output block (double-buffered).
_TGMM_BLOCK_BYTES = 4 * 1024 * 1024


def _gmm_kernel(tile_expert, n_used, x_ref, w_ref, o_ref, *,
                transpose_rhs: bool):
    i = pl.program_id(0)

    @pl.when(i < n_used[0])
    def _compute():
        dims = (((1,), (1,)), ((), ())) if transpose_rhs else (
            ((1,), (0,)), ((), ()))
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], w_ref[0], dims,
            preferred_element_type=jnp.float32).astype(o_ref.dtype)

    @pl.when(i >= n_used[0])
    def _skip():
        o_ref[...] = jnp.zeros_like(o_ref)


def _gmm_pallas(x, w, tile_expert, n_used, *, tile: int,
                transpose_rhs: bool, interpret: bool):
    rows, k = x.shape
    n = w.shape[1] if transpose_rhs else w.shape[2]

    def x_index(i, te, nu):  # a skipped tile re-references the last used
        return (jnp.minimum(i, nu[0] - 1), 0)

    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_rhs=transpose_rhs),
        name="moe_gmm",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows // tile,),
            in_specs=[
                pl.BlockSpec((tile, k), x_index),
                pl.BlockSpec((1,) + w.shape[1:],
                             lambda i, te, nu: (te[i], 0, 0)),
            ],
            out_specs=pl.BlockSpec((tile, n), lambda i, te, nu: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        compiler_params=CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(tile_expert, n_used, x, w)


def _tgmm_kernel(tile_expert, n_used, x_ref, dy_ref, o_ref):
    i = pl.program_id(1)
    used = i < n_used[0]
    first = (i == 0) | (tile_expert[jnp.maximum(i - 1, 0)]
                        != tile_expert[i])

    @pl.when(used & first)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(used)
    def _accumulate():
        o_ref[0] += jax.lax.dot_general(
            x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _column_tile(k: int, n: int) -> int:
    """Widest column tile of an (k, n) float32 block inside the budget:
    a multiple of 128 that divides n (n itself where it fits)."""
    if k * n * 4 <= _TGMM_BLOCK_BYTES or n % 128 != 0:
        return n
    best = 128
    for tn in range(128, n, 128):
        if n % tn == 0 and k * tn * 4 <= _TGMM_BLOCK_BYTES:
            best = tn
    return best


def _tgmm_pallas(x, dy, tile_expert, n_used, *, tile: int, n_experts: int,
                 interpret: bool):
    rows, k = x.shape
    n = dy.shape[1]
    tn = _column_tile(k, n)

    def row_index(j, i, te, nu):
        return (jnp.minimum(i, nu[0] - 1), 0)

    return pl.pallas_call(
        _tgmm_kernel,
        name="moe_tgmm",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // tn, rows // tile),
            in_specs=[
                pl.BlockSpec((tile, k), row_index),
                pl.BlockSpec((tile, tn), lambda j, i, te, nu: (
                    jnp.minimum(i, nu[0] - 1), j)),
            ],
            out_specs=pl.BlockSpec(
                (1, k, tn), lambda j, i, te, nu: (te[i], 0, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((n_experts, k, n), jnp.float32),
        compiler_params=CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(tile_expert, n_used, x, dy)


def _gmm(x, w, tile_expert, n_used, *, tile, transpose_rhs, impl):
    if impl != "jnp":
        return _gmm_pallas(x, w, tile_expert, n_used, tile=tile,
                           transpose_rhs=transpose_rhs,
                           interpret=impl == "interpret")
    tiles = x.reshape(-1, tile, x.shape[1])
    eq = "itk,ink->itn" if transpose_rhs else "itk,ikn->itn"
    y = jnp.einsum(eq, tiles, w[tile_expert],
                   preferred_element_type=jnp.float32)
    used = (jnp.arange(tiles.shape[0]) < n_used[0])[:, None, None]
    return jnp.where(used, y, 0.0).astype(x.dtype).reshape(x.shape[0], -1)


def _tgmm(x, dy, tile_expert, n_used, *, tile, n_experts, impl):
    if impl != "jnp":
        return _tgmm_pallas(x, dy, tile_expert, n_used, tile=tile,
                            n_experts=n_experts,
                            interpret=impl == "interpret")
    n_tiles = x.shape[0] // tile
    used = jnp.arange(n_tiles) < n_used[0]
    owner = (tile_expert[:, None] == jnp.arange(n_experts)[None, :]) \
        & used[:, None]
    return jnp.einsum(
        "ie,itk,itn->ekn", owner.astype(jnp.float32),
        x.reshape(n_tiles, tile, -1), dy.reshape(n_tiles, tile, -1),
        preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def grouped_matmul(x, w, tile_expert, n_used, tile: int, impl: str):
    """``y[tile i] = x[tile i] @ w[tile_expert[i]]`` over rows grouped by
    expert (see the module docstring).

    ``x`` (rows, K) in the compute dtype, ``w`` (E, K, N) float32 — the
    parameters as the trainer keeps them: the product runs on their cast
    to ``x``'s dtype with float32 accumulation, and ``w``'s cotangent
    comes back float32 straight from the kernel's accumulator.
    ``impl``: ``"pallas"``, ``"interpret"`` (the kernels under the Pallas
    interpreter) or ``"jnp"``.
    """
    return _gmm(x, w.astype(x.dtype), tile_expert, n_used, tile=tile,
                transpose_rhs=False, impl=impl)


def _grouped_matmul_fwd(x, w, tile_expert, n_used, tile, impl):
    w_c = w.astype(x.dtype)
    y = _gmm(x, w_c, tile_expert, n_used, tile=tile, transpose_rhs=False,
             impl=impl)
    return y, (x, w_c, tile_expert, n_used)


def _grouped_matmul_bwd(tile, impl, residuals, dy):
    x, w_c, tile_expert, n_used = residuals
    dx = _gmm(dy, w_c, tile_expert, n_used, tile=tile, transpose_rhs=True,
              impl=impl)
    dw = _tgmm(x, dy, tile_expert, n_used, tile=tile,
               n_experts=w_c.shape[0], impl=impl)
    return dx, dw, None, None


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)
