"""Plain references for the trained models: the forward pass and the
weighted loss, float32 at ``highest`` precision, no dropout (evaluation
mode), no scan kernels, no associative scan.

``gru``: the published bidirectional GRU, from ``biGRU_model.py:108-137``
as ``fmda_tpu/models/bigru.py`` states it: both directions over the whole
window, outputs summed over the directions, concat [sum of final hiddens,
max over time, mean over time] through ``Dense(3H -> C)``.

``ssm``: the gated diagonal recurrence of ``fmda_tpu/models/ssm.py`` and
``ops/ssm.py``, ticked one step at a time (the program trains it with a
log-depth associative scan): per direction ``a = sigmoid(z + a_base)``,
``s = a*s + (1-a)*v``, ``h = s*silu(g) + d*v``; outputs summed over the
directions; concat [forward's last + backward's first, fast EMA, slow EMA
of the summed outputs at the forward direction's learned rates] through
``Dense(3H -> C)``.

Loss, both: ``BCEWithLogits(weight, pos_weight)`` averaged over the valid
examples' elements.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _gru_direction(x, w_ih, w_hh, b_ih, b_hh, reverse: bool):
    hidden = w_hh.shape[1]
    gi_all = jnp.einsum("btf,gf->tbg", x, w_ih) + b_ih

    def step(h, gi):
        gh = h @ w_hh.T + b_hh
        r = jax.nn.sigmoid(gi[:, :hidden] + gh[:, :hidden])
        z = jax.nn.sigmoid(gi[:, hidden:2 * hidden]
                           + gh[:, hidden:2 * hidden])
        n = jnp.tanh(gi[:, 2 * hidden:] + r * gh[:, 2 * hidden:])
        h_new = (1.0 - z) * n + z * h
        return h_new, h_new

    h0 = jnp.zeros((x.shape[0], hidden), jnp.float32)
    h_last, hs = jax.lax.scan(step, h0, gi_all, reverse=reverse)
    return h_last, jnp.swapaxes(hs, 0, 1)


def bigru_logits(params, x):
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    x = jnp.asarray(x, jnp.float32)
    with jax.default_matmul_precision("highest"):
        hf, out_f = _gru_direction(
            x, p["weight_ih_l0"], p["weight_hh_l0"],
            p["bias_ih_l0"], p["bias_hh_l0"], reverse=False)
        hb, out_b = _gru_direction(
            x, p["weight_ih_l0_reverse"], p["weight_hh_l0_reverse"],
            p["bias_ih_l0_reverse"], p["bias_hh_l0_reverse"], reverse=True)
        out = out_f + out_b
        concat = jnp.concatenate(
            [hf + hb, out.max(axis=1), out.mean(axis=1)], axis=-1)
        return concat @ p["linear"]["kernel"] + p["linear"]["bias"]


def _ssm_direction(x, w_ih, b_ih, a_base, d, reverse: bool):
    hidden = a_base.shape[0]
    xp_all = jnp.einsum("btf,gf->tbg", x, w_ih) + b_ih

    def step(s, xp):
        z, v, g = (xp[:, :hidden], xp[:, hidden:2 * hidden],
                   xp[:, 2 * hidden:])
        a = jax.nn.sigmoid(z + a_base)
        s_new = a * s + (1.0 - a) * v
        return s_new, s_new * jax.nn.silu(g) + d * v

    s0 = jnp.zeros((x.shape[0], hidden), jnp.float32)
    _, hs = jax.lax.scan(step, s0, xp_all, reverse=reverse)
    return hs  # (T, B, H), in time order either way


def _ema_last(hs, rho):
    r = jax.nn.sigmoid(rho)
    e0 = jnp.zeros(hs.shape[1:], jnp.float32)
    e_last, _ = jax.lax.scan(
        lambda e, h: (r * e + (1.0 - r) * h, None), e0, hs)
    return e_last


def ssm_logits(params, x):
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    x = jnp.asarray(x, jnp.float32)
    with jax.default_matmul_precision("highest"):
        out_f = _ssm_direction(
            x, p["weight_ih_l0"], p["bias_ih_l0"], p["a_base_l0"],
            p["d_l0"], reverse=False)
        out_b = _ssm_direction(
            x, p["weight_ih_l0_reverse"], p["bias_ih_l0_reverse"],
            p["a_base_l0_reverse"], p["d_l0_reverse"], reverse=True)
        out = out_f + out_b
        concat = jnp.concatenate(
            [out_f[-1] + out_b[0], _ema_last(out, p["rho_f_l0"]),
             _ema_last(out, p["rho_s_l0"])], axis=-1)
        return concat @ p["linear"]["kernel"] + p["linear"]["bias"]


BY_CELL = {"gru": bigru_logits, "ssm": ssm_logits}


def weighted_bce(logits, y, weight, pos_weight, mask):
    """Mean over the valid examples' elements of
    ``-w * (pw * y * log p + (1 - y) * log(1 - p))``."""
    log_p = -jnp.logaddexp(0.0, -logits)
    log_not_p = -jnp.logaddexp(0.0, logits)
    per = -(pos_weight * y * log_p + (1.0 - y) * log_not_p) * weight
    m = mask[:, None]
    return jnp.sum(per * m) / jnp.maximum(jnp.sum(m) * per.shape[-1], 1.0)


def eval_loss(params, batches, weight, pos_weight, cell: str = "gru"
              ) -> float:
    """The trainer's validation loss: the mean over batches of each
    batch's masked mean loss."""
    logits = BY_CELL[cell]
    # the weights are arguments, not constants: the program is the same
    # for every seed, so the persistent cache holds it after one run
    fn = jax.jit(lambda p, x, y, m, w, pw: weighted_bce(
        logits(p, x), y, w, pw, m))
    total = 0.0
    n = 0
    for b in batches:
        total += float(fn(params, b.x, b.y, b.mask, weight, pos_weight))
        n += 1
    return total / max(n, 1)

