"""Shared pieces of the recurrent model families.

Both :class:`~fmda_tpu.models.bigru.BiGRU` and
:class:`~fmda_tpu.models.bilstm.BiLSTM` use the reference's input dropout
(biGRU_model.py:87-94) and pool-concat head (biGRU_model.py:108-137);
keeping those here means a fix to the masked-pooling or head math lands in
every cell family at once.  These helpers create flax submodules, so they
must be called from inside a module's ``@nn.compact`` ``__call__``.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from fmda_tpu.config import ModelConfig


def input_dropout(
    cfg: ModelConfig, x: jax.Array, *, deterministic: bool
) -> jax.Array:
    """Input dropout: spatial variant zeroes whole feature channels across
    time (torch Dropout2d on (B, F, T), biGRU_model.py:87-94)."""
    if cfg.spatial_dropout:
        return nn.Dropout(cfg.dropout, broadcast_dims=(1,))(
            x, deterministic=deterministic
        )
    return nn.Dropout(cfg.dropout)(x, deterministic=deterministic)


def pool_concat_logits(
    cfg: ModelConfig,
    last_hidden: jax.Array,
    out_sum: jax.Array,
    *,
    mask: Optional[jax.Array],
    seq_len: int,
    compute_dtype,
) -> jax.Array:
    """The pool-concat head (biGRU_model.py:108-137): max-pool and
    mean-pool over the direction-summed per-step outputs, concatenated
    with the summed final hidden state into ``Dense(3H -> n_classes)``.

    With a mask, pooling covers only valid steps (the reference assumes
    full windows and divides by the constant length); logits are always
    returned in float32.
    """
    with jax.named_scope("head"):
        if mask is None:
            max_pool = jnp.max(out_sum, axis=1)
            avg_pool = jnp.sum(out_sum, axis=1) / jnp.asarray(
                seq_len, dtype=compute_dtype
            )
        else:
            m = mask[..., None].astype(compute_dtype)
            neg = jnp.asarray(jnp.finfo(compute_dtype).min, compute_dtype)
            max_pool = jnp.max(jnp.where(m > 0, out_sum, neg), axis=1)
            denom = jnp.maximum(jnp.sum(m, axis=1), 1.0)
            avg_pool = jnp.sum(out_sum * m, axis=1) / denom

        concat = jnp.concatenate([last_hidden, max_pool, avg_pool], axis=-1)
        scale = 1.0 / jnp.sqrt(3 * cfg.hidden_size)
        logits = nn.Dense(
            cfg.output_size,
            name="linear",
            kernel_init=_torch_uniform_init(scale),
            bias_init=_torch_uniform_init(scale),
        )(concat)
        return logits.astype(jnp.float32)


def ema_concat_logits(
    cfg: ModelConfig,
    last_hidden: jax.Array,
    ema_fast: jax.Array,
    ema_slow: jax.Array,
) -> jax.Array:
    """The SSM family's head: the protocol's ``Dense(3H -> n_classes)``
    shape with the window pools replaced by the two learned-rate EMAs —
    the O(1)-cache twin of :func:`pool_concat_logits` (max/mean need the
    ring the family exists to delete; the EMAs are linear recurrences,
    so they parallel-scan in training and carry as two H-vectors in
    serving).  Serve-side twin: ``fmda_tpu.serve.streaming
    .ema_head_logits`` reads the same ``linear`` params — concat order
    ``[h_last, ema_fast, ema_slow]`` is part of that contract."""
    with jax.named_scope("head"):
        concat = jnp.concatenate(
            [last_hidden, ema_fast, ema_slow], axis=-1)
        scale = 1.0 / jnp.sqrt(3 * cfg.hidden_size)
        logits = nn.Dense(
            cfg.output_size,
            name="linear",
            kernel_init=_torch_uniform_init(scale),
            bias_init=_torch_uniform_init(scale),
        )(concat)
        return logits.astype(jnp.float32)


def _torch_uniform_init(scale: float):
    """torch's default U(-1/sqrt(fan), 1/sqrt(fan)) init (the reference
    never re-initialises, so its training recipe assumes this)."""

    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(
            key, shape, dtype, minval=-scale, maxval=scale
        )

    return init
