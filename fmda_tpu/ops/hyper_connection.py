"""A residual stream of several lanes, mixed by learned, input-dependent
coefficients: manifold-constrained hyper-connections.

A plain residual block keeps one stream and adds a sublayer's output to
it.  Here the stream is ``n`` lanes wide (``X``: ``(B, T, n, d)``), and
each sublayer ``F`` reads a mix of the lanes, writes its output to the
lanes by weights of its own, and the lanes themselves are remixed by a
matrix that Sinkhorn's iteration keeps doubly stochastic (rows and
columns summing to one: the remix neither grows nor shrinks what the
lanes carry)::

    x~    = vec(X_t) / sqrt(mean(vec(X_t)^2) + eps)        over all n*d, no scale
    Hpre  = sigmoid(a_pre  * (x~ @ P_pre)  + b_pre)                    (n,)
    Hpost = 2 * sigmoid(a_post * (x~ @ P_post) + b_post)               (n,)
    M0    = exp(clip(a_res * mat(x~ @ P_res) + b_res, -c, c))          (n, n)
    Hres  = iters x { rows / (row sums + hc_eps) ; columns / (column sums + hc_eps) }
    u_t   = sum_i Hpre[i] X_t[i]                                       (d,)
    y_t   = F(u)_t
    X'_t[i] = sum_j Hres[i, j] X_t[j] + Hpost[i] y_t

:func:`coefficients` is the first five lines (scope ``hc_coeff``),
:func:`read` the sixth (``hc_pre``), :func:`write` the last
(``hc_post_res``); the caller puts all three under ``hyper_conn``
(docs/observability.md "Spans and scopes").  The three products run in
the stream's dtype with float32 accumulation, as one product against
the three matrices side by side; the norm being scale-free, its factor
multiplies the product's ``n + n + n*n`` outputs instead of the
``n * d`` inputs.  Everything after is float32: the coefficients,
every turn of Sinkhorn's iteration, and the two mixes' sums (their
results return to the stream's dtype).  All ``iters`` turns run whatever
the matrix: there is no test for convergence.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp


class Coefficients(NamedTuple):
    """One sublayer's mixing coefficients, float32, the lanes' axes
    leading: a coefficient is then a ``(B, T)`` array with the tokens on
    the fast axis, and Sinkhorn's sums over four lanes are elementwise
    adds of such arrays (a trailing ``(4, 4)`` would be padded to a whole
    tile a token)."""

    pre: jax.Array   # (n, B, T)
    post: jax.Array  # (n, B, T)
    res: jax.Array   # (n, n, B, T): X'[i] takes res[i, j] of X[j]


def sinkhorn(m: jax.Array, iters: int, eps: float) -> jax.Array:
    """``iters`` turns of rows over their sums, then columns over theirs
    (each sum plus ``eps``), of a positive ``m`` (n, n, ...), float32:
    row ``i`` is ``m[i, :]``, the matrix's two axes lead."""
    m = m.astype(jnp.float32)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
    return m


def sum_error(res: jax.Array) -> jax.Array:
    """The largest distance of a row sum or a column sum of ``res``
    (n, n, ...) from one: () float32."""
    rows = jnp.abs(jnp.sum(res, axis=1) - 1.0)
    cols = jnp.abs(jnp.sum(res, axis=0) - 1.0)
    return jnp.maximum(jnp.max(rows), jnp.max(cols))


def coefficients(x: jax.Array, p_pre: jax.Array, p_post: jax.Array,
                 p_res: jax.Array, a: Tuple[jax.Array, jax.Array, jax.Array],
                 b: Tuple[jax.Array, jax.Array, jax.Array], *,
                 norm_eps: float, iters: int, eps: float, clamp: float
                 ) -> Coefficients:
    """The coefficients of one sublayer from the stream ``x`` (B, T, n,
    d): ``p_pre`` / ``p_post`` (n*d, n) and ``p_res`` (n*d, n*n)
    float32, ``a`` the three scalar gains, ``b`` the three offsets
    ((n,), (n,), (n, n))."""
    bsz, t, n, d = x.shape
    f32 = jnp.float32
    with jax.named_scope("hc_coeff"):
        flat = x.reshape(bsz, t, n * d)
        inv_rms = jax.lax.rsqrt(jnp.mean(
            jnp.square(flat.astype(f32)), axis=-1, keepdims=True) + norm_eps)
        z = jnp.moveaxis(inv_rms * jnp.dot(
            flat, jnp.concatenate([p_pre, p_post, p_res], axis=1)
            .astype(x.dtype), preferred_element_type=f32), -1, 0)
        pre = jax.nn.sigmoid(a[0] * z[:n] + b[0][:, None, None])
        post = 2.0 * jax.nn.sigmoid(a[1] * z[n:2 * n] + b[1][:, None, None])
        logits = (a[2] * z[2 * n:].reshape(n, n, bsz, t)
                  + b[2][:, :, None, None])
        res = sinkhorn(jnp.exp(jnp.clip(logits, -clamp, clamp)), iters, eps)
        return Coefficients(pre, post, res)


def read(x: jax.Array, pre: jax.Array) -> jax.Array:
    """``u[t] = sum_i pre[i, t] x[t, i]``: (B, T, d) in ``x``'s dtype,
    the sum in float32 (written out lane by lane: elementwise work, not
    a product for the matrix unit, which would round the coefficients)."""
    with jax.named_scope("hc_pre"):
        x32 = x.astype(jnp.float32)
        return sum(pre[i][..., None] * x32[:, :, i]
                   for i in range(x.shape[2])).astype(x.dtype)


def write(x: jax.Array, y: jax.Array, post: jax.Array, res: jax.Array
          ) -> jax.Array:
    """``x'[t, i] = sum_j res[i, j, t] x[t, j] + post[i, t] y[t]``: (B, T,
    n, d) in ``x``'s dtype, the sums in float32, lane by lane."""
    with jax.named_scope("hc_post_res"):
        n = x.shape[2]
        x32, y32 = x.astype(jnp.float32), y.astype(jnp.float32)
        return jnp.stack([
            sum(res[i, j][..., None] * x32[:, :, j] for j in range(n))
            + post[i][..., None] * y32 for i in range(n)],
            axis=2).astype(x.dtype)
