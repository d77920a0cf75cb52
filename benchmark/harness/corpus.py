"""A seeded training corpus made in bulk: one ticker's joined feature
table and its four movement labels, as arrays.

The columns are mixtures of a few latent market states (price, momentum,
order-book imbalance, volatility) and a dozen slow random walks, so that
they are correlated, drift like prices do and carry some signal about the
labels; the labels are thresholds on the price's move
over the next ``lead`` bars, as the program's target view defines them
(two sizes of up-move, two of down-move).  Values matter only for the
loss being finite and learnable: a train step's cost does not depend on
them.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

N_WALKS = 12


def make_corpus(rows: int, n_features: int, seed: int, *, lead: int = 6
                ) -> Tuple[np.ndarray, np.ndarray]:
    rng = np.random.default_rng([seed, 4])
    noise = rng.standard_normal((rows, 4)).astype(np.float32)
    momentum = _ar1(noise[:, 0], 0.97)
    imbalance = _ar1(noise[:, 1], 0.90)
    vol = np.exp(0.3 * _ar1(noise[:, 2], 0.995) * np.sqrt(1 - 0.995 ** 2))
    ret = 0.55 * momentum * np.sqrt(1 - 0.97 ** 2) \
        + 0.22 * imbalance * np.sqrt(1 - 0.90 ** 2) + 0.35 * vol * noise[:, 3]
    price = 330.0 + np.cumsum(ret, dtype=np.float64).astype(np.float32)
    # a dozen slow random walks beside the four states, so that the columns
    # drift apart; every column is one mixture of the sixteen
    walks = np.cumsum(0.05 * rng.standard_normal(
        (N_WALKS, rows), dtype=np.float32), axis=1, dtype=np.float32)
    latent = np.concatenate(
        [np.stack([0.05 * price, momentum, imbalance, vol]), walks]).T
    mix = rng.normal(0.0, 1.0, (latent.shape[1], n_features))
    x = latent @ mix.astype(np.float32)
    fwd = np.zeros(rows, np.float32)
    fwd[:-lead] = price[lead:] - price[:-lead]
    sigma = float(fwd[:-lead].std())
    y = np.stack([fwd > 0.5 * sigma, fwd > 1.0 * sigma,
                  fwd < -0.5 * sigma, fwd < -1.0 * sigma],
                 axis=1).astype(np.float32)
    return x.astype(np.float32), y


def _ar1(eps: np.ndarray, phi: float) -> np.ndarray:
    """x_t = phi * x_{t-1} + eps_t, exactly, in O(n) numpy: within blocks
    by the closed form, between blocks by carrying the last value."""
    n = len(eps)
    block = 64
    pad = (-n) % block
    e = np.concatenate([eps, np.zeros(pad, eps.dtype)]).reshape(-1, block)
    powers = phi ** np.arange(block, dtype=np.float64)
    # within a block: x_k = sum_j phi^(k-j) e_j  (lower-triangular Toeplitz)
    idx = np.arange(block)
    tri = np.where(idx[:, None] >= idx[None, :],
                   phi ** (idx[:, None] - idx[None, :]).astype(np.float64),
                   0.0)
    local = e.astype(np.float64) @ tri.T
    out = np.empty_like(local)
    carry = 0.0
    decay = powers * phi  # phi^(k+1): what the previous block's end adds
    for b in range(len(local)):
        out[b] = local[b] + carry * decay
        carry = out[b, -1]
    return out.reshape(-1)[:n].astype(np.float32)
