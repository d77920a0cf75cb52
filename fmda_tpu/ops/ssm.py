"""Gated linear-recurrence (SSM) sequence ops — the O(1)-state family.

The GRU/LSTM carried-state cores pay a dense ``h @ W_hh`` matmul per
tick, and their pooled head drags a ``(window, H)`` ring of per-step
hiddens through every state export.  This module implements the dual
form the state-space-duality papers describe (PAPERS.md: "Compiler-First
State Space Duality and Portable O(1) Autoregressive Caching"): a
**diagonal, input-gated linear recurrence** whose transition is
elementwise, so the two modes of one parameterisation are

- **parallel (training/backtest) mode** — the whole window at once via
  :func:`jax.lax.associative_scan` (:func:`ssm_scan_parallel`): the
  first-order recurrence ``s_t = a_t * s_{t-1} + u_t`` composes
  associatively as ``(a, u) ∘ (a', u') = (a·a', a'·u + u')``, so XLA
  tiles the window as a log-depth tree instead of a length-T loop;
- **recurrent (serving) mode** — one O(1), matmul-free, gather-free
  elementwise step per tick (:func:`ssm_cell_step`), carrying a
  constant-size ``(s, ema_fast, ema_slow)`` cache of three H-vectors:
  no ring, no windowed pooling state, nothing sized by ``window``.

Cell math (gates packed ``[z, v, g]`` along the leading axis of
``w_ih (3H, F)``, mirroring the torch-style packing of the sibling
families)::

    zp, vp, gp = split(x @ W_ih^T + b_ih)       # one big MXU matmul
    a_t  = sigmoid(zp + a_base)                 # per-channel decay (0,1)
    s_t  = a_t * s_{t-1} + (1 - a_t) * vp       # diagonal state update
    h_t  = s_t * silu(gp) + d * vp              # gated output + feedthrough

``a_base`` is a per-channel learned decay offset initialised so the
zero-input decay spans ``ModelConfig.ssm_decay_range`` (the LRU-style
long-memory ring init); ``d`` is a learned skip.  The pooling the other
families' ring head provides (max/mean over the trailing window) is
replaced by two exponential moving averages of ``h`` at learned
per-channel rates (``rho_f`` fast, ``rho_s`` slow) — themselves
first-order linear recurrences, so they are parallel-scannable in
training and O(1) in serving, and the head keeps the protocol's
``Dense(3H -> n_classes)`` shape over ``[h_last, ema_fast, ema_slow]``.

**Duality contract** (documented tolerance, pinned in
tests/test_ssm.py): :func:`ssm_scan` (the sequential ``lax.scan``
reference) runs op-for-op the math of repeated :func:`ssm_cell_step`;
within one compiled program that is bit-exact, across separately
compiled programs XLA's elementwise fusion order differs at the last
bit (~1 ulp — the same caveat the solo-vs-batched GRU tests carry).
:func:`ssm_scan_parallel` additionally reassociates the decay products
into a log-depth tree, so train mode matches serve mode to ~1e-5
absolute in float32 over protocol-length windows.  Train in parallel
mode, serve from the recurrent cache, and the duality test holds on
shared parameters — that is the point of the family.  The contracts
that must be *bit*-exact (multiplexed-vs-solo serving, migration
export/import) compare serve mode against serve mode and stay exact.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from fmda_tpu.ops.dispatch import count_kernel_fallback
from fmda_tpu.ops.gru import recurrence_scope


class SSMWeights(NamedTuple):
    """One direction's parameters.  ``w_ih``/``b_ih`` follow the sibling
    families' packed-gate convention; the rest are per-channel vectors
    (the diagonal transition is the family's defining constraint)."""

    w_ih: jax.Array  # (3H, F) packed [z, v, g]
    b_ih: jax.Array  # (3H,)
    a_base: jax.Array  # (H,) decay offset: a = sigmoid(zp + a_base)
    d: jax.Array  # (H,) feedthrough/skip coefficient
    rho_f: jax.Array  # (H,) fast head-EMA rate pre-activation
    rho_s: jax.Array  # (H,) slow head-EMA rate pre-activation


#: Cell-carry arity of the serving cache: (s, ema_fast, ema_slow).
N_CARRY = 3
#: Packed gates in ``w_ih``: [z (decay), v (candidate), g (output gate)].
N_GATES = 3


def ssm_input_projection(x: jax.Array, weights: SSMWeights) -> jax.Array:
    """All-timestep input projection: (B, T, F) -> (B, T, 3H) — the one
    MXU-shaped matmul of the family, computed outside the recurrence
    exactly like the GRU/LSTM projection split."""
    with jax.named_scope("input_projection"):
        return jnp.einsum("btf,gf->btg", x, weights.w_ih) + weights.b_ih


def _split_gates(xp: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    hidden = xp.shape[-1] // 3
    return (xp[..., :hidden], xp[..., hidden : 2 * hidden],
            xp[..., 2 * hidden :])


def ssm_gates(
    xp: jax.Array, s: jax.Array, a_base: jax.Array, d: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """One state update from a precomputed projection: ``xp (B, 3H)``,
    ``s (B, H)`` -> ``(h, s_new)``.  Pure VPU work — no matmul, no
    gather: the per-tick cost the family exists to delete."""
    zp, vp, gp = _split_gates(xp)
    a = jax.nn.sigmoid(zp + a_base)
    s_new = a * s + (1.0 - a) * vp
    h = s_new * jax.nn.silu(gp) + d * vp
    return h, s_new


def ssm_cell_step(
    xp: jax.Array, carry: Tuple[jax.Array, ...], w: SSMWeights
) -> Tuple[jax.Array, Tuple[jax.Array, ...]]:
    """The O(1) serving step: advance the ``(s, ema_f, ema_s)`` cache by
    one tick.  This is the function the carried-state serving cores and
    the session pool dispatch per flush (via
    :func:`fmda_tpu.serve.streaming._recurrent_cell_ops`)."""
    s, ef, es = carry
    h, s_new = ssm_gates(xp, s, w.a_base, w.d)
    rf = jax.nn.sigmoid(w.rho_f)
    rs = jax.nn.sigmoid(w.rho_s)
    ef_new = rf * ef + (1.0 - rf) * h
    es_new = rs * es + (1.0 - rs) * h
    return h, (s_new, ef_new, es_new)


def ssm_scan(
    xp: jax.Array,
    carry: Tuple[jax.Array, ...],
    w: SSMWeights,
    *,
    reverse: bool = False,
) -> Tuple[Tuple[jax.Array, ...], jax.Array]:
    """Sequential reference scan: ``lax.scan`` over
    :func:`ssm_cell_step` — op-for-op the serving step's math, ticked
    over the window (ulp-exact to stepped serving within one compiled
    program; see the module duality note).  Returns (carry_last, hs)
    with hs (B, T, H)."""

    def step(c, xp_t):
        h, c_new = ssm_cell_step(xp_t, c, w)
        return c_new, h

    with recurrence_scope(reverse):
        xs = jnp.swapaxes(xp, 0, 1)  # (T, B, 3H)
        carry_last, hs = jax.lax.scan(
            step, tuple(carry), xs, reverse=reverse)
        return carry_last, jnp.swapaxes(hs, 0, 1)


def linear_scan_parallel(
    a: jax.Array, u: jax.Array, x0: Optional[jax.Array] = None
) -> jax.Array:
    """All prefixes of ``x_t = a_t * x_{t-1} + u_t`` over axis 1 via
    :func:`jax.lax.associative_scan` (log-depth tree, the training-mode
    layout).  ``a``/``u`` are (B, T, H); ``x0`` (B, H) folds a carried
    initial state in exactly (``x_t`` gains ``prod(a_1..t) * x0``).
    ``u`` may carry further axes that ``a`` broadcasts over (``a`` (B, T,
    H, 1) against ``u`` (B, T, H, M): one scalar decay a head over a
    matrix-valued state)."""

    def combine(c1, c2):
        a1, u1 = c1
        a2, u2 = c2
        return a1 * a2, a2 * u1 + u2

    a_cum, x = jax.lax.associative_scan(combine, (a, u), axis=1)
    if x0 is not None:
        x = x + a_cum * x0[:, None, :]
    return x


def ssm_scan_parallel(
    xp: jax.Array,
    w: SSMWeights,
    s0: Optional[jax.Array] = None,
    *,
    reverse: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Parallel (training/backtest) mode over a whole window: returns
    (hs, s_last) with hs (B, T, H).  Matches :func:`ssm_scan` to float
    tolerance (documented above), not bit — the associative tree
    reassociates the decay products."""
    with recurrence_scope(reverse):
        if reverse:
            xp = jnp.flip(xp, axis=1)
        zp, vp, gp = _split_gates(xp)
        a = jax.nn.sigmoid(zp + w.a_base)
        s = linear_scan_parallel(a, (1.0 - a) * vp, s0)
        hs = s * jax.nn.silu(gp) + w.d * vp
        s_last = s[:, -1]
        if reverse:
            hs = jnp.flip(hs, axis=1)
        return hs, s_last


def ema_pool_parallel(
    hs: jax.Array, rho: jax.Array, ema0: Optional[jax.Array] = None
) -> jax.Array:
    """Final value of the head EMA ``e_t = r * e_{t-1} + (1-r) * h_t``
    (``r = sigmoid(rho)``, per channel) over a window, in parallel mode.
    Returns (B, H) — the train-mode twin of the serving cache's
    ``ema_fast``/``ema_slow`` entries."""
    with jax.named_scope("head"):
        r = jax.nn.sigmoid(rho)
        a = jnp.broadcast_to(r, hs.shape)
        e = linear_scan_parallel(a, (1.0 - r) * hs, ema0)
        return e[:, -1]


def ssm_pallas_available() -> bool:
    """True when the fused Pallas serve-step kernel can run compiled on
    this backend (interpret mode runs anywhere and is dispatched
    explicitly by tests)."""
    try:
        from fmda_tpu.ops import pallas_ssm  # noqa: F401
    except ImportError:
        return False
    return jax.default_backend() == "tpu"


def select_ssm_step_fn(
    use_pallas: bool,
    *,
    shape: Optional[Tuple[int, int]] = None,
    itemsize: int = 4,
):
    """The kernel-vs-jnp choice for the O(1) serve step, mirroring
    :func:`fmda_tpu.ops.gru.select_scan_fn`: the fused kernel runs when
    requested, on a TPU backend, and inside its VMEM envelope; anything
    else falls back to :func:`ssm_cell_step` — **counted**, never
    silent (``fmda_tpu.ops.dispatch.kernel_fallbacks``), so a serving
    config that asked for the kernel and didn't get it leaves a signal.

    ``shape=(batch, hidden)`` gates the per-shape VMEM feasibility.
    """
    if not use_pallas:
        return ssm_cell_step
    if not ssm_pallas_available():
        count_kernel_fallback("ssm", "backend")
        return ssm_cell_step
    from fmda_tpu.ops import pallas_ssm

    if shape is not None and not pallas_ssm.kernel_supported(
        shape[0], shape[1], itemsize
    ):
        count_kernel_fallback("ssm", "vmem")
        return ssm_cell_step
    return pallas_ssm.ssm_cell_step_pallas
