"""Losses: the window classifiers' weighted BCE with its class-imbalance
weighting, and the token decoder's next-token cross-entropy.

The reference trains with ``BCEWithLogitsLoss(weight=[N/n_c],
pos_weight=[(N-n_c)/n_c])`` (training notebook cells 13-16, 29).  The same
math here, as a pure jnp function with optional padded-example masking
(fixed-shape batches on TPU pad the tail; padded rows must not contribute).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def class_weights(label_counts: np.ndarray, n_examples: int) -> Tuple[np.ndarray, np.ndarray]:
    """Per-class (weight, pos_weight) from positive-label counts.

    weight_c = N / n_c;  pos_weight_c = (N - n_c) / n_c  (notebook cells 13-16).
    """
    counts = np.asarray(label_counts, np.float64)
    weight = n_examples / counts
    pos_weight = (n_examples - counts) / counts
    return weight.astype(np.float32), pos_weight.astype(np.float32)


def weighted_bce_with_logits(
    logits: jax.Array,
    targets: jax.Array,
    *,
    weight: Optional[jax.Array] = None,
    pos_weight: Optional[jax.Array] = None,
    example_mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Mean weighted binary cross-entropy on logits (torch semantics).

    ``l = -w * [ pw * y * log(sigmoid(x)) + (1-y) * log(1 - sigmoid(x)) ]``
    reduced by mean over all (valid) elements; numerically stable via
    log-sigmoid.
    """
    targets = targets.astype(logits.dtype)
    log_p = jax.nn.log_sigmoid(logits)
    log_not_p = jax.nn.log_sigmoid(-logits)
    pw = pos_weight if pos_weight is not None else 1.0
    per_elem = -(pw * targets * log_p + (1.0 - targets) * log_not_p)
    if weight is not None:
        per_elem = per_elem * weight
    if example_mask is None:
        return jnp.mean(per_elem)
    m = example_mask.astype(per_elem.dtype)[:, None]
    denom = jnp.maximum(jnp.sum(m) * per_elem.shape[-1], 1.0)
    return jnp.sum(per_elem * m) / denom


def weighted_bce_sums(
    logits: jax.Array,
    targets: jax.Array,
    *,
    weight: Optional[jax.Array] = None,
    pos_weight: Optional[jax.Array] = None,
    example_mask: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Unnormalized (loss_sum, element_count) for gradient accumulation.

    The masked mean above is ``sum / max(valid_rows * n_classes, 1)`` — a
    *global* normalizer, so a K-way microbatch split cannot just average
    per-microbatch means (partial tail masks would skew it).  Accumulating
    these sums and counts across microbatches and dividing once recovers
    the full-batch loss (and, by linearity of the gradient, the
    full-batch gradient) exactly up to float re-association
    (docs/training.md "Accumulation math").
    """
    targets = targets.astype(logits.dtype)
    log_p = jax.nn.log_sigmoid(logits)
    log_not_p = jax.nn.log_sigmoid(-logits)
    pw = pos_weight if pos_weight is not None else 1.0
    per_elem = -(pw * targets * log_p + (1.0 - targets) * log_not_p)
    if weight is not None:
        per_elem = per_elem * weight
    if example_mask is None:
        n = float(per_elem.shape[0] * per_elem.shape[-1])
        return jnp.sum(per_elem), jnp.asarray(n, per_elem.dtype)
    m = example_mask.astype(per_elem.dtype)[:, None]
    return jnp.sum(per_elem * m), jnp.sum(m) * per_elem.shape[-1]


def chunked_next_token_loss(
    hidden: jax.Array,
    head: jax.Array,
    targets: jax.Array,
    mask: jax.Array,
    *,
    chunk: int,
    logits_scaling: float = 1.0,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``(loss_sum, n_tokens, n_correct)`` of softmax cross-entropy over
    the vocabulary, taken ``chunk`` tokens at a time.

    ``hidden`` (N, D) in the compute dtype, ``head`` (D, V) float32,
    ``targets`` (N,) int32, ``mask`` (N,) — 0 for a token that does not
    count.  The logits of one chunk exist at a time (``chunk * V``
    float32, recomputed in backward) and never ``N * V``; the sum is the
    one taken over all tokens at once, up to float re-association.  The
    head is cast inside each chunk, so its cotangent accumulates over
    the chunks in float32.  ``chunk`` is lowered to a divisor of N.
    The logits are divided by ``logits_scaling`` (1.0: not an operation).
    """
    n = hidden.shape[0]
    chunk = max(1, min(chunk, n))
    while n % chunk:
        chunk -= 1

    @jax.checkpoint
    def one(head, h, y, m):
        with jax.named_scope("lm_head"):
            logits = jnp.dot(h, head.astype(h.dtype),
                             preferred_element_type=jnp.float32)
            if logits_scaling != 1.0:
                logits = logits / logits_scaling
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
        keep = m > 0
        hit = keep & (jnp.argmax(logits, axis=-1) == y)
        return (jnp.sum(jnp.where(keep, lse - picked, 0.0)),
                jnp.sum(hit, dtype=jnp.int32))

    def body(carry, xs):
        s, c = one(head, *xs)
        return (carry[0] + s, carry[1] + c), None

    split = lambda a: a.reshape((n // chunk, chunk) + a.shape[1:])
    (loss_sum, correct), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)),
        (split(hidden), split(targets), split(mask)))
    return loss_sum, jnp.sum(mask > 0, dtype=jnp.int32), correct
