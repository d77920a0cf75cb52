"""GRU sequence ops, designed TPU-first.

The recurrence is split the way the hardware wants it (not the way the
reference's ``nn.GRU`` black box hides it, biGRU_model.py:54-56):

1. **Input projection** ``x @ W_ih^T + b_ih`` for *all* timesteps at once —
   one large ``(B*T, F) x (F, 3H)`` matmul that XLA tiles onto the MXU.
2. **Recurrent scan** over time via :func:`jax.lax.scan` (or the fused Pallas
   kernel in :mod:`fmda_tpu.ops.pallas_gru`), which only carries the small
   ``h @ W_hh^T`` matmul and the fused gate elementwise ops.

Gate math follows the standard (torch-compatible) GRU convention so that
behavior parity with the reference model can be tested weight-for-weight:

    r_t = sigmoid(W_ir x_t + b_ir + W_hr h_{t-1} + b_hr)
    z_t = sigmoid(W_iz x_t + b_iz + W_hz h_{t-1} + b_hz)
    n_t = tanh(W_in x_t + b_in + r_t * (W_hn h_{t-1} + b_hn))
    h_t = (1 - z_t) * n_t + z_t * h_{t-1}

with gates packed in ``[r, z, n]`` order along the leading axis of
``W_ih (3H, F)`` / ``W_hh (3H, H)``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class GRUWeights(NamedTuple):
    """One direction's parameters, torch-layout."""

    w_ih: jax.Array  # (3H, F)
    w_hh: jax.Array  # (3H, H)
    b_ih: jax.Array  # (3H,)
    b_hh: jax.Array  # (3H,)


def input_projection(x: jax.Array, weights: GRUWeights) -> jax.Array:
    """All-timestep input projection: (B, T, F) -> (B, T, 3H)."""
    with jax.named_scope("input_projection"):
        return jnp.einsum("btf,gf->btg", x, weights.w_ih) + weights.b_ih


def recurrence_scope(reverse: bool):
    """The named scope every family's recurrence runs under, whichever
    implementation was selected (``lax.scan``, associative scan, Pallas
    kernel): ``recurrence_fwd`` / ``recurrence_rev``.  Metadata only —
    a profile's device operations carry it in their scope path
    (docs/observability.md "Spans and scopes")."""
    return jax.named_scope("recurrence_rev" if reverse else "recurrence_fwd")


def gru_gates(
    xp_t: jax.Array, h: jax.Array, w_hh: jax.Array, b_hh: jax.Array
) -> jax.Array:
    """One fused gate step: precomputed input proj + hidden proj -> new h."""
    hidden = h.shape[-1]
    hp = jnp.einsum("bh,gh->bg", h, w_hh) + b_hh
    r = jax.nn.sigmoid(xp_t[..., :hidden] + hp[..., :hidden])
    z = jax.nn.sigmoid(xp_t[..., hidden : 2 * hidden] + hp[..., hidden : 2 * hidden])
    n = jnp.tanh(xp_t[..., 2 * hidden :] + r * hp[..., 2 * hidden :])
    return (1.0 - z) * n + z * h


def gru_scan(
    xp: jax.Array,
    h0: jax.Array,
    w_hh: jax.Array,
    b_hh: jax.Array,
    *,
    reverse: bool = False,
    mask: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Scan the recurrence over time.

    Args:
      xp: (B, T, 3H) precomputed input projections.
      h0: (B, H) initial hidden state.
      w_hh, b_hh: recurrent weights, torch layout.
      reverse: scan from t=T-1 down to 0 (the backward direction of a
        bidirectional GRU); outputs stay in input time order.
      mask: optional (B, T) validity mask; masked steps carry the previous
        hidden state through unchanged, giving a correct "last valid hidden"
        for padded batches (the reference assumes full windows and divides
        by the constant length, biGRU_model.py:130).

    Returns:
      (h_last, hs): final carry (B, H) and per-step hiddens (B, T, H).
    """

    def step(h, inputs):
        if mask is None:
            xp_t = inputs
            h_new = gru_gates(xp_t, h, w_hh, b_hh)
        else:
            xp_t, m_t = inputs
            h_new = gru_gates(xp_t, h, w_hh, b_hh)
            h_new = jnp.where(m_t[:, None], h_new, h)
        return h_new, h_new

    xs = jnp.swapaxes(xp, 0, 1)  # (T, B, 3H): scan over leading axis
    if mask is not None:
        inputs = (xs, jnp.swapaxes(mask, 0, 1))
    else:
        inputs = xs
    h_last, hs = jax.lax.scan(step, h0, inputs, reverse=reverse)
    return h_last, jnp.swapaxes(hs, 0, 1)


def pallas_scan_available() -> bool:
    """True when the fused Pallas scan kernel can run on this backend."""
    try:
        from fmda_tpu.ops import pallas_gru  # noqa: F401
    except ImportError:
        return False
    return jax.default_backend() == "tpu"


def select_scan_fn(
    use_pallas: bool,
    mask: Optional[jax.Array] = None,
    *,
    shape: Optional[Tuple[int, int, int]] = None,
    itemsize: int = 4,
):
    """The canonical kernel-vs-lax.scan choice, shared by every caller
    (single-device :func:`gru_layer` and the sequence-parallel path) so
    the kernel's support envelope is gated in exactly one place: the
    fused kernel runs when requested, unmasked, and on a TPU backend;
    anything else falls back to :func:`gru_scan` — with the fallback
    **counted** per reason in :mod:`fmda_tpu.ops.dispatch` (a config
    that asked for the kernel and silently serves the reference scan
    was invisible before a third cell family made it a real bug class).

    ``shape=(batch, seq_len, hidden)`` additionally gates on the
    kernel's per-shape VMEM feasibility
    (:func:`fmda_tpu.ops.pallas_gru.kernel_supported`): at MXU-sized
    hidden widths the kernel's resident weights + f32 accumulators
    outgrow VMEM, and the per-step matmul is large enough that
    ``lax.scan`` is the right path — so ``use_pallas=True`` means "fused
    kernel where it fits, scan where it doesn't", selected automatically
    per shape at trace time (shapes are static under jit)."""
    if not use_pallas:
        return gru_scan
    from fmda_tpu.ops.dispatch import count_kernel_fallback

    if mask is not None:
        count_kernel_fallback("gru", "masked")
        return gru_scan
    if not pallas_scan_available():
        count_kernel_fallback("gru", "backend")
        return gru_scan
    from fmda_tpu.ops import pallas_gru

    if shape is not None and not pallas_gru.kernel_supported(
        shape[0], shape[1], shape[2], itemsize
    ):
        count_kernel_fallback("gru", "vmem")
        return gru_scan
    return pallas_gru.gru_scan_pallas


def gru_layer(
    x: jax.Array,
    weights: GRUWeights,
    h0: Optional[jax.Array] = None,
    *,
    reverse: bool = False,
    mask: Optional[jax.Array] = None,
    use_pallas: bool = False,
    remat: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Full single-direction GRU layer: projection + scan.

    ``use_pallas=True`` requests the fused Pallas TPU kernel for the scan;
    it silently falls back to :func:`gru_scan` when the kernel is unavailable
    (non-TPU backend) or unsupported for the given options.

    ``remat=True`` wraps the scan in :func:`jax.checkpoint`: backward
    recomputes the recurrence instead of storing per-step gate
    intermediates — the HBM-for-FLOPs trade for long-context windows.

    Returns (h_last, hs) with hs: (B, T, H).
    """
    batch = x.shape[0]
    hidden = weights.w_hh.shape[-1]
    if h0 is None:
        h0 = jnp.zeros((batch, hidden), dtype=x.dtype)
    xp = input_projection(x, weights)
    scan_fn = select_scan_fn(
        use_pallas, mask,
        shape=(batch, x.shape[1], hidden), itemsize=x.dtype.itemsize)
    with recurrence_scope(reverse):
        if scan_fn is not gru_scan:
            # The Pallas kernel pair already rematerialises: the backward
            # kernel stores only the forward outputs (hs) and recomputes
            # the gates in-VMEM per step, so `remat` is inherently
            # satisfied.
            return scan_fn(
                xp, h0, weights.w_hh, weights.b_hh, reverse=reverse)
        if remat:
            return jax.checkpoint(
                functools.partial(gru_scan, reverse=reverse, mask=mask)
            )(xp, h0, weights.w_hh, weights.b_hh)
        return gru_scan(
            xp, h0, weights.w_hh, weights.b_hh, reverse=reverse, mask=mask)
