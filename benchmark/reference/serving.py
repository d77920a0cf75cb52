"""Plain references for the served carriers: one session, one row at a
time, in float32 ``jax.numpy`` at ``highest`` matmul precision.  No pool,
no batching, no ring buffer arithmetic, no kernels.

Written from the equations in ``fmda_tpu/ops/gru.py``, ``ops/ssm.py``
and the heads in ``fmda_tpu/models/common.py`` — not by calling them.
Departures from the published bidirectional model: the served carrier is
its forward direction only (``bidirectional=False``), because a carried
state cannot see the future (``runtime/session_pool.py``); pooling runs
over the trailing ``window`` hidden states, or over as many as the
session has seen.

Parameters are the program's own seeded tree (torch layout: gates packed
along the leading axis of ``weight_ih_l0 (3H, F)``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _normalise(rows, x_min, x_max):
    return (rows - x_min) / (x_max - x_min)


def gru_probabilities(params, rows, x_min, x_max, window: int) -> np.ndarray:
    """(n, F) rows of one session -> (n, C) probabilities, tick by tick.

        r = sigmoid(W_ir x + b_ir + W_hr h + b_hr)
        z = sigmoid(W_iz x + b_iz + W_hz h + b_hz)
        n = tanh(W_in x + b_in + r * (W_hn h + b_hn))
        h' = (1 - z) * n + z * h
        logits = [h', max(last w h'), mean(last w h')] @ K + b
    """
    w_ih = jnp.asarray(params["weight_ih_l0"], jnp.float32)
    w_hh = jnp.asarray(params["weight_hh_l0"], jnp.float32)
    b_ih = jnp.asarray(params["bias_ih_l0"], jnp.float32)
    b_hh = jnp.asarray(params["bias_hh_l0"], jnp.float32)
    kernel = jnp.asarray(params["linear"]["kernel"], jnp.float32)
    bias = jnp.asarray(params["linear"]["bias"], jnp.float32)
    hidden = w_hh.shape[1]
    with jax.default_matmul_precision("highest"):
        x = _normalise(jnp.asarray(rows, jnp.float32),
                       jnp.asarray(x_min, jnp.float32),
                       jnp.asarray(x_max, jnp.float32))

        def step(h, x_t):
            gi = w_ih @ x_t + b_ih
            gh = w_hh @ h + b_hh
            r = jax.nn.sigmoid(gi[:hidden] + gh[:hidden])
            z = jax.nn.sigmoid(gi[hidden:2 * hidden] + gh[hidden:2 * hidden])
            n = jnp.tanh(gi[2 * hidden:] + r * gh[2 * hidden:])
            h_new = (1.0 - z) * n + z * h
            return h_new, h_new

        _, hs = jax.lax.scan(step, jnp.zeros(hidden, jnp.float32), x)
        hs = np.asarray(hs)
        out = []
        for t in range(len(hs)):
            tail = hs[max(0, t - window + 1): t + 1]
            concat = np.concatenate([hs[t], tail.max(0), tail.mean(0)])
            out.append(concat)
        logits = jnp.asarray(np.stack(out)) @ kernel + bias
        return np.asarray(jax.nn.sigmoid(logits))


def ssm_probabilities(params, rows, x_min, x_max, window: int) -> np.ndarray:
    """The gated diagonal recurrence with two EMA heads (``window`` plays
    no part: the state is three H-vectors).

        a = sigmoid(zp + a_base);  s' = a * s + (1 - a) * vp
        h = s' * silu(gp) + d * vp
        ef' = rf * ef + (1 - rf) * h;  es' likewise  (r = sigmoid(rho))
        logits = [h, ef', es'] @ K + b
    """
    del window
    w_ih = jnp.asarray(params["weight_ih_l0"], jnp.float32)
    b_ih = jnp.asarray(params["bias_ih_l0"], jnp.float32)
    a_base = jnp.asarray(params["a_base_l0"], jnp.float32)
    d = jnp.asarray(params["d_l0"], jnp.float32)
    rf = jax.nn.sigmoid(jnp.asarray(params["rho_f_l0"], jnp.float32))
    rs = jax.nn.sigmoid(jnp.asarray(params["rho_s_l0"], jnp.float32))
    kernel = jnp.asarray(params["linear"]["kernel"], jnp.float32)
    bias = jnp.asarray(params["linear"]["bias"], jnp.float32)
    hidden = a_base.shape[0]
    with jax.default_matmul_precision("highest"):
        x = _normalise(jnp.asarray(rows, jnp.float32),
                       jnp.asarray(x_min, jnp.float32),
                       jnp.asarray(x_max, jnp.float32))

        def step(carry, x_t):
            s, ef, es = carry
            xp = w_ih @ x_t + b_ih
            zp, vp, gp = (xp[:hidden], xp[hidden:2 * hidden],
                          xp[2 * hidden:])
            a = jax.nn.sigmoid(zp + a_base)
            s = a * s + (1.0 - a) * vp
            h = s * (gp * jax.nn.sigmoid(gp)) + d * vp
            ef = rf * ef + (1.0 - rf) * h
            es = rs * es + (1.0 - rs) * h
            return (s, ef, es), jnp.concatenate([h, ef, es])

        zeros = jnp.zeros(hidden, jnp.float32)
        _, concat = jax.lax.scan(step, (zeros, zeros, zeros), x)
        return np.asarray(jax.nn.sigmoid(concat @ kernel + bias))


BY_CELL = {"gru": gru_probabilities, "ssm": ssm_probabilities}
