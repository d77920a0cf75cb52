"""LSTM sequence ops, TPU-first — the second recurrent cell family.

Same hardware-oriented split as :mod:`fmda_tpu.ops.gru` (one large
all-timestep input projection for the MXU, then a small carried scan):
the reference is GRU-only (biGRU_model.py:54-56), but a torch user is one
argument away from ``nn.LSTM``, so the framework offers the same swap via
``ModelConfig(cell="lstm")``.

Gate math follows the torch-compatible LSTM convention so parity with
``torch.nn.LSTM`` is testable weight-for-weight:

    i_t = sigmoid(W_ii x_t + b_ii + W_hi h_{t-1} + b_hi)
    f_t = sigmoid(W_if x_t + b_if + W_hf h_{t-1} + b_hf)
    g_t = tanh   (W_ig x_t + b_ig + W_hg h_{t-1} + b_hg)
    o_t = sigmoid(W_io x_t + b_io + W_ho h_{t-1} + b_ho)
    c_t = f_t * c_{t-1} + i_t * g_t
    h_t = o_t * tanh(c_t)

with gates packed in ``[i, f, g, o]`` order along the leading axis of
``W_ih (4H, F)`` / ``W_hh (4H, H)`` (torch layout).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from fmda_tpu.ops.gru import recurrence_scope


class LSTMWeights(NamedTuple):
    """One direction's parameters, torch-layout."""

    w_ih: jax.Array  # (4H, F)
    w_hh: jax.Array  # (4H, H)
    b_ih: jax.Array  # (4H,)
    b_hh: jax.Array  # (4H,)


def lstm_input_projection(x: jax.Array, weights: LSTMWeights) -> jax.Array:
    """All-timestep input projection: (B, T, F) -> (B, T, 4H)."""
    with jax.named_scope("input_projection"):
        return jnp.einsum("btf,gf->btg", x, weights.w_ih) + weights.b_ih


def lstm_gates(
    xp_t: jax.Array,
    h: jax.Array,
    c: jax.Array,
    w_hh: jax.Array,
    b_hh: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """One fused gate step -> (h_new, c_new)."""
    hidden = h.shape[-1]
    hp = jnp.einsum("bh,gh->bg", h, w_hh) + b_hh
    s = xp_t + hp
    i = jax.nn.sigmoid(s[..., :hidden])
    f = jax.nn.sigmoid(s[..., hidden : 2 * hidden])
    g = jnp.tanh(s[..., 2 * hidden : 3 * hidden])
    o = jax.nn.sigmoid(s[..., 3 * hidden :])
    c_new = f * c + i * g
    return o * jnp.tanh(c_new), c_new


def lstm_scan(
    xp: jax.Array,
    h0: jax.Array,
    c0: jax.Array,
    w_hh: jax.Array,
    b_hh: jax.Array,
    *,
    reverse: bool = False,
    mask: Optional[jax.Array] = None,
) -> Tuple[Tuple[jax.Array, jax.Array], jax.Array]:
    """Scan the LSTM recurrence over time.

    Args:
      xp: (B, T, 4H) precomputed input projections.
      h0, c0: (B, H) initial hidden / cell state.
      w_hh, b_hh: recurrent weights, torch layout.
      reverse: scan from t=T-1 down to 0; outputs stay in input time order.
      mask: optional (B, T) validity mask; masked steps carry (h, c)
        through unchanged (same padded-batch semantics as
        :func:`fmda_tpu.ops.gru.gru_scan`).

    Returns:
      ((h_last, c_last), hs) with hs: (B, T, H).
    """

    def step(carry, inputs):
        h, c = carry
        if mask is None:
            xp_t = inputs
            h_new, c_new = lstm_gates(xp_t, h, c, w_hh, b_hh)
        else:
            xp_t, m_t = inputs
            h_new, c_new = lstm_gates(xp_t, h, c, w_hh, b_hh)
            keep = m_t[:, None]
            h_new = jnp.where(keep, h_new, h)
            c_new = jnp.where(keep, c_new, c)
        return (h_new, c_new), h_new

    xs = jnp.swapaxes(xp, 0, 1)  # (T, B, 4H)
    if mask is not None:
        inputs = (xs, jnp.swapaxes(mask, 0, 1))
    else:
        inputs = xs
    (h_last, c_last), hs = jax.lax.scan(step, (h0, c0), inputs, reverse=reverse)
    return (h_last, c_last), jnp.swapaxes(hs, 0, 1)


def lstm_pallas_available() -> bool:
    """True when the fused Pallas LSTM kernel can run on this backend."""
    try:
        from fmda_tpu.ops import pallas_lstm  # noqa: F401
    except ImportError:
        return False
    return jax.default_backend() == "tpu"


def select_lstm_scan_fn(
    use_pallas: bool,
    mask: Optional[jax.Array] = None,
    *,
    shape: Optional[Tuple[int, int, int]] = None,
    itemsize: int = 4,
):
    """The kernel-vs-lax.scan choice, mirroring
    :func:`fmda_tpu.ops.gru.select_scan_fn`: the fused kernel runs when
    requested, unmasked, on a TPU backend, and — when
    ``shape=(batch, seq_len, hidden)`` is given — inside the kernel's
    VMEM feasibility envelope; anything else falls back to
    :func:`lstm_scan`, counted per reason in
    :mod:`fmda_tpu.ops.dispatch` (never silent)."""
    if not use_pallas:
        return lstm_scan
    from fmda_tpu.ops.dispatch import count_kernel_fallback

    if mask is not None:
        count_kernel_fallback("lstm", "masked")
        return lstm_scan
    if not lstm_pallas_available():
        count_kernel_fallback("lstm", "backend")
        return lstm_scan
    from fmda_tpu.ops import pallas_lstm

    if shape is not None and not pallas_lstm.kernel_supported(
        shape[0], shape[1], shape[2], itemsize
    ):
        count_kernel_fallback("lstm", "vmem")
        return lstm_scan
    return pallas_lstm.lstm_scan_pallas


def lstm_layer(
    x: jax.Array,
    weights: LSTMWeights,
    h0: Optional[jax.Array] = None,
    c0: Optional[jax.Array] = None,
    *,
    reverse: bool = False,
    mask: Optional[jax.Array] = None,
    use_pallas: bool = False,
    remat: bool = False,
) -> Tuple[Tuple[jax.Array, jax.Array], jax.Array]:
    """Full single-direction LSTM layer: projection + scan.

    ``use_pallas=True`` requests the fused Pallas TPU kernel (silent
    fallback to :func:`lstm_scan` off-TPU or with a mask).  ``remat=True``
    wraps the scan in :func:`jax.checkpoint` (the same HBM-for-FLOPs trade
    as the GRU layer's long-context path).

    Returns ((h_last, c_last), hs) with hs: (B, T, H).
    """
    batch = x.shape[0]
    hidden = weights.w_hh.shape[-1]
    if h0 is None:
        h0 = jnp.zeros((batch, hidden), dtype=x.dtype)
    if c0 is None:
        c0 = jnp.zeros((batch, hidden), dtype=x.dtype)
    xp = lstm_input_projection(x, weights)
    scan_fn = select_lstm_scan_fn(
        use_pallas, mask,
        shape=(batch, x.shape[1], hidden), itemsize=x.dtype.itemsize)
    with recurrence_scope(reverse):
        if scan_fn is not lstm_scan:
            # the Pallas pair already rematerialises (backward recomputes
            # the gates in-VMEM from hs/cs), so `remat` is inherently
            # satisfied
            return scan_fn(xp, h0, c0, weights.w_hh, weights.b_hh,
                           reverse=reverse)
        if remat:
            return jax.checkpoint(
                functools.partial(lstm_scan, reverse=reverse, mask=mask)
            )(xp, h0, c0, weights.w_hh, weights.b_hh)
        return lstm_scan(
            xp, h0, c0, weights.w_hh, weights.b_hh, reverse=reverse,
            mask=mask,
        )
