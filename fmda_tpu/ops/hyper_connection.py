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
(``hc_post_res``); :func:`around` puts all three under ``hyper_conn``
(docs/observability.md "Spans and scopes").  The three products run in
the stream's dtype with float32 accumulation, as one product against
the three matrices side by side; the norm being scale-free, its factor
multiplies the product's ``n + n + n*n`` outputs instead of the
``n * d`` inputs.  Everything after is float32: the coefficients,
every turn of Sinkhorn's iteration, and the two mixes' sums (their
results return to the stream's dtype).  All ``iters`` turns run whatever
the matrix: there is no test for convergence.

**The backward is written by hand** (PR 42) where the kernels of
:mod:`fmda_tpu.ops.pallas_hyper_connection` take the stream.
:func:`around` runs a sublayer inside its mixing: the same three
functions forward, under one differentiation rule cut in two where the
sublayer runs.  The half after the sublayer reads the written stream's
gradient ``g'``, the stream and the sublayer's output once
(``hc_bwd_leave``) and gives ``dy[t] = sum_i Hpost[i] g'[i]``,
``dHpost[i] = <g'[i], y>`` and ``dHres[i, j] = <g'[i], x[j]>``; it
hands ``g'`` itself back as the gradient of what the first half carried
to it, which is why the carried stream never leaves :func:`around`:
that is not a gradient, and a second reader would add to it as if it
were.  The half before the sublayer runs after the sublayer's own
backward has made ``du``: ``dHpre[i] = <du, x[i]>`` (``hc_bwd_pre``),
XLA's float32 backward of the sigmoids and of every Sinkhorn turn down
to the normalised product's gradient ``dz`` (24 numbers a token), and
then the stream's gradient in one walk (``hc_bwd_enter``)::

    dX_t[j] = sum_i Hres[i, j] g'[i] + Hpre[j] du + inv_rms * (dz @ P^T)[j]
              - inv_rms^2 * <dz, z> / (n d) * X_t[j]

summed in float32 and rounded to the stream's dtype once, with the
three matrices' gradient ``X^T (inv_rms dz)`` from the same read.
**Both of those products take ``inv_rms dz`` rounded to the stream's
dtype**, as the forward product takes its operands: products in the
stream's dtype, float32 accumulation.  (Autodiff hands the two
transposes a float32 ``dz`` beside bfloat16 operands, which the TPU's
matrix unit takes in bfloat16 at the default precision too; against
autodiff at ``"highest"`` the rounding does not show beside the
bfloat16 roundings of ``dy`` and ``du``: tests/test_hyper_connection.py
has the readings.)  Neither half keeps or writes a float32 array of the
stream's size: the rule's residuals are the stream and the sublayer's
output in their own dtype and per-token float32 numbers (``z``, the
norm's factor and the coefficients).

What differentiates a sublayer's mixing is ``impl``
(:func:`backward_impl`): ``"pallas"`` on a TPU where the stream is
whole blocks (``hidden`` a multiple of 128, a token count that a tile
of 512, 256 or 128 divides) is the rule above; ``"jnp"`` elsewhere
(another backend: ``decoder:backend``; a refused shape:
``decoder:hc_shape``, ops/dispatch.py) is XLA's autodiff of the three
functions as they stand.  The rule's sums as ``jnp`` expressions cost
on the chip what autodiff costs (the coefficient product's transpose
leaves the matrix unit as a float32 array of the stream's size whatever
is written around it) and were not kept; nor were kernels for
Sinkhorn's turns, which took 6 ms a step off the scope and cost the
cell 1 % (``PERF.md`` §6, PR 42).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from fmda_tpu.ops import pallas_hyper_connection as pallas_hc
from fmda_tpu.ops.dispatch import count_kernel_fallback


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


def _side_by_side(p_pre, p_post, p_res, dtype) -> jax.Array:
    """The three matrices as one (n*d, n + n + n*n) operand."""
    return jnp.concatenate([p_pre, p_post, p_res], axis=1).astype(dtype)


def _normalised_product(x: jax.Array, p: jax.Array, norm_eps: float
                        ) -> Tuple[jax.Array, jax.Array]:
    """``z`` (k, B, T) float32: the scale-free norm of all lanes of ``x``
    (B, T, n, d) against ``p`` (n*d, k) in ``x``'s dtype; and the norm's
    factor ``inv_rms`` (B, T)."""
    bsz, t, n, d = x.shape
    f32 = jnp.float32
    flat = x.reshape(bsz, t, n * d)
    inv_rms = jax.lax.rsqrt(jnp.mean(
        jnp.square(flat.astype(f32)), axis=-1, keepdims=True) + norm_eps)
    z = jnp.moveaxis(
        inv_rms * jnp.dot(flat, p, preferred_element_type=f32), -1, 0)
    return z, inv_rms[..., 0]


def _logits(z: jax.Array, a, b) -> jax.Array:
    """What the sigmoids and the exponential take, (n + n + n*n, B, T)
    float32, from the normalised product ``z`` of that shape."""
    n = b[0].shape[0]
    return jnp.concatenate([
        a[0] * z[:n] + b[0][:, None, None],
        a[1] * z[n:2 * n] + b[1][:, None, None],
        a[2] * z[2 * n:] + b[2].reshape(n * n)[:, None, None]])


def _from_logits(logits: jax.Array, n: int, iters: int, eps: float,
                 clamp: float) -> Coefficients:
    """The coefficients from their logits: the sigmoids, the clipped
    exponential and Sinkhorn's turns."""
    tokens = logits.shape[1:]
    res = sinkhorn(jnp.exp(jnp.clip(
        logits[2 * n:].reshape((n, n) + tokens), -clamp, clamp)), iters, eps)
    return Coefficients(jax.nn.sigmoid(logits[:n]),
                        2.0 * jax.nn.sigmoid(logits[n:2 * n]), res)


@functools.partial(jax.jit, static_argnames=("iters", "eps", "clamp"))
def _from_product(z, a, b, *, iters, eps, clamp) -> Coefficients:
    """The coefficients from the normalised product ``z``.  A ``jax.jit``
    of its own, and its backward another: a step calls them once a
    sublayer, and inside one trace the call sites then share one traced
    body of ``iters`` turns (traced one by one they are seconds of every
    set-up on a slow host, as the kernels' bodies were: ``PERF.md`` §6,
    PR 42)."""
    return _from_logits(_logits(z, a, b), b[0].shape[0], iters, eps, clamp)


@functools.partial(jax.jit, static_argnames=("iters", "eps", "clamp"))
def _from_product_bwd(z, a, b, d: Coefficients, *, iters, eps, clamp):
    """``(dz, da, db)`` from the coefficients' gradient ``d``: XLA's
    autodiff of :func:`_from_product`, every turn in float32."""
    return jax.vjp(functools.partial(
        _from_product, iters=iters, eps=eps, clamp=clamp), z, a, b)[1](d)


def _coefficients(x, p_pre, p_post, p_res, a, b, norm_eps, iters, eps, clamp):
    """:func:`coefficients`, and what their backward reads beside them:
    the normalised product ``z`` and the norm's factor."""
    z, inv_rms = _normalised_product(
        x, _side_by_side(p_pre, p_post, p_res, x.dtype), norm_eps)
    return (_from_product(z, tuple(a), tuple(b), iters=iters, eps=eps,
                          clamp=clamp), z, inv_rms)


def coefficients(x: jax.Array, p_pre: jax.Array, p_post: jax.Array,
                 p_res: jax.Array, a: Tuple[jax.Array, jax.Array, jax.Array],
                 b: Tuple[jax.Array, jax.Array, jax.Array], *,
                 norm_eps: float, iters: int, eps: float, clamp: float
                 ) -> Coefficients:
    """The coefficients of one sublayer from the stream ``x`` (B, T, n,
    d): ``p_pre`` / ``p_post`` (n*d, n) and ``p_res`` (n*d, n*n)
    float32, ``a`` the three scalar gains, ``b`` the three offsets
    ((n,), (n,), (n, n))."""
    with jax.named_scope("hc_coeff"):
        return _coefficients(
            x, p_pre, p_post, p_res, a, b, norm_eps, iters, eps, clamp)[0]


def _lanes32(x: jax.Array):
    """The lanes of ``x`` (B, T, n, d), each (B, T, d) float32: a lane
    is widened where it is used (one float32 copy of the whole stream,
    shared by the norm, the read and the write, was 0.36 ms a sublayer
    and pass through HBM)."""
    return [x[:, :, i].astype(jnp.float32) for i in range(x.shape[2])]


def read(x: jax.Array, pre: jax.Array) -> jax.Array:
    """``u[t] = sum_i pre[i, t] x[t, i]``: (B, T, d) in ``x``'s dtype,
    the sum in float32 (written out lane by lane: elementwise work, not
    a product for the matrix unit, which would round the coefficients)."""
    with jax.named_scope("hc_pre"):
        x32 = _lanes32(x)
        return sum(pre[i][..., None] * x32[i]
                   for i in range(x.shape[2])).astype(x.dtype)


def write(x: jax.Array, y: jax.Array, post: jax.Array, res: jax.Array
          ) -> jax.Array:
    """``x'[t, i] = sum_j res[i, j, t] x[t, j] + post[i, t] y[t]``: (B, T,
    n, d) in ``x``'s dtype, the sums in float32, lane by lane."""
    with jax.named_scope("hc_post_res"):
        n = x.shape[2]
        x32, y32 = _lanes32(x), y.astype(jnp.float32)
        return jnp.stack([
            sum(res[i, j][..., None] * x32[j] for j in range(n))
            + post[i][..., None] * y32 for i in range(n)],
            axis=2).astype(x.dtype)


def _tokens_last(x: jax.Array) -> jax.Array:
    """(B, T, ..., d) -> (B, ..., d, T): how the kernels take the stream
    (ops/pallas_hyper_connection.py)."""
    return jnp.moveaxis(x, 1, -1)


def _eight(partial: jax.Array) -> jax.Array:
    """A kernel's sums (B, k, 8, T) -> (k, B, T)."""
    return jnp.moveaxis(jnp.sum(partial, axis=2), 1, 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _enter(static, x, p_pre, p_post, p_res, a, b):
    return _enter_fwd(static, x, p_pre, p_post, p_res, a, b)[0]


def _enter_fwd(static, x, p_pre, p_post, p_res, a, b):
    with jax.named_scope("hc_coeff"):
        mix, z, inv_rms = _coefficients(x, p_pre, p_post, p_res, a, b,
                                        *static[:-1])
    return ((read(x, mix.pre), mix.post, mix.res, x),
            (x, p_pre, p_post, p_res, a, b, z, inv_rms, mix.pre, mix.res))


def _enter_bwd(static, saved, cts):
    _, iters, eps, clamp, interpret = static
    x, p_pre, p_post, p_res, a, b, z, inv_rms, pre, res = saved
    du, dpost, dres, g = cts  # g: what _leave_bwd handed back, g' itself
    n, d = x.shape[2:]
    xt, dut = _tokens_last(x), _tokens_last(du)
    with jax.named_scope("hc_pre"):
        dpre = _eight(pallas_hc.bwd_pre(xt, dut, interpret=interpret))
    with jax.named_scope("hc_coeff"):
        dz, da, db = _from_product_bwd(
            z, a, b, Coefficients(dpre, dpost, dres), iters=iters, eps=eps,
            clamp=clamp)
        # z = inv_rms * (flat @ p), inv_rms = (mean(flat^2) + eps)^-1/2;
        # the product's gradient enters its two transposes as the
        # product's operands do, in the stream's dtype
        dzraw = (inv_rms * dz).astype(x.dtype)                   # (k, B, T)
        norm = -jnp.square(inv_rms) * jnp.sum(dz * z, axis=0) / (n * d)
        p = _side_by_side(p_pre, p_post, p_res, x.dtype)         # (n*d, k)
        width = p.shape[1]
        pad = -width % pallas_hc.K_ALIGN
        dxt, dp = pallas_hc.bwd_enter(
            _tokens_last(g), xt, dut,
            jnp.moveaxis(res.reshape((n * n,) + z.shape[1:]), 0, 1),
            jnp.moveaxis(pre, 0, 1),
            jnp.pad(jnp.moveaxis(dzraw, 0, 1), ((0, 0), (0, pad), (0, 0))),
            norm[:, None],
            jnp.pad(p.reshape(n, d, width), ((0, 0), (0, 0), (0, pad))),
            interpret=interpret)
        dp = dp[:, :, :width].reshape(n * d, width)
    return (jnp.moveaxis(dxt, -1, 1), dp[:, :n].astype(p_pre.dtype),
            dp[:, n:2 * n].astype(p_post.dtype),
            dp[:, 2 * n:].astype(p_res.dtype), da, db)


_enter.defvjp(_enter_fwd, _enter_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _leave(interpret, carried, y, post, res):
    return write(carried, y, post, res)


def _leave_fwd(interpret, carried, y, post, res):
    return write(carried, y, post, res), (carried, y, post, res)


def _leave_bwd(interpret, saved, g):
    x, y, post, res = saved
    with jax.named_scope("hc_post_res"):
        dyt, dpost, dres = pallas_hc.bwd_leave(
            _tokens_last(g), _tokens_last(x), _tokens_last(y),
            jnp.moveaxis(post, 0, 1), interpret=interpret)
    # the stream's gradient waits for du: _enter_bwd takes g' from here
    return (g, jnp.moveaxis(dyt, -1, 1), _eight(dpost),
            _eight(dres).reshape(res.shape))


_leave.defvjp(_leave_fwd, _leave_bwd)


def backward_impl(impl: str, d: int, t: int) -> str:
    """``impl`` (``"pallas"``, ``"interpret"`` or ``"jnp"``) where the
    kernels take a stream ``d`` wide over ``t`` tokens, else ``"jnp"``
    with the refusal counted (``decoder:hc_shape``)."""
    if impl == "jnp" or pallas_hc.fits(d, t):
        return impl
    count_kernel_fallback("decoder", "hc_shape")
    return "jnp"


def around(fn: Callable[[jax.Array], Tuple[jax.Array, Any]], x: jax.Array,
           p_pre: jax.Array, p_post: jax.Array, p_res: jax.Array,
           a: Tuple[jax.Array, jax.Array, jax.Array],
           b: Tuple[jax.Array, jax.Array, jax.Array], *,
           norm_eps: float, iters: int, eps: float, clamp: float,
           impl: str = "jnp") -> Tuple[jax.Array, Any, jax.Array]:
    """One sublayer inside its mixing: for ``c = coefficients(x, ...)``
    and ``y, out = fn(read(x, c.pre))``, the written stream ``write(x, y,
    c.post, c.res)``, ``out`` and ``c.res``; both halves of the mixing
    under ``hyper_conn``.  ``impl`` is :func:`backward_impl`'s answer:
    ``"jnp"`` differentiates the three functions as they stand; the
    kernels' rule (module docstring) is cut where ``fn`` runs, and what
    its first half carries to its second never leaves this function."""
    kw = dict(norm_eps=norm_eps, iters=iters, eps=eps, clamp=clamp)
    if impl == "jnp":
        with jax.named_scope("hyper_conn"):
            mix = coefficients(x, p_pre, p_post, p_res, a, b, **kw)
            u = read(x, mix.pre)
        y, out = fn(u)
        with jax.named_scope("hyper_conn"):
            return write(x, y, mix.post, mix.res), out, mix.res
    interpret = impl == "interpret"
    with jax.named_scope("hyper_conn"):
        u, post, res, carried = _enter(
            tuple(kw.values()) + (interpret,), x, p_pre, p_post, p_res,
            tuple(a), tuple(b))
    y, out = fn(u)
    with jax.named_scope("hyper_conn"):
        return _leave(interpret, carried, y, post, res), out, res
