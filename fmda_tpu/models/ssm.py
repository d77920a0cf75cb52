"""Gated linear-recurrence (SSM) price-movement classifier (Flax).

The fourth cell family behind ``ModelConfig(cell="ssm")`` and the
training-mode half of the family's **dual form** (PAPERS.md:
"Compiler-First State Space Duality and Portable O(1) Autoregressive
Caching"): this module computes each window with the parallel
associative scan (:func:`fmda_tpu.ops.ssm.ssm_scan_parallel` — a
log-depth tree XLA tiles freely, the scan-friendly training layout),
while serving advances the *same parameters* one tick at a time from a
constant-size ``(s, ema_fast, ema_slow)`` cache
(:mod:`fmda_tpu.serve.streaming`, :mod:`fmda_tpu.runtime.session_pool`).
The two modes agree to documented float tolerance on shared parameters
(the duality test in tests/test_ssm.py).

Protocol shape mirrors the sibling families — spatial input dropout,
stacked optionally-bidirectional recurrence, inter-layer dropout, a
``Dense(3H -> n_classes)`` head over three H-vectors — with two
deliberate differences, both forced by the O(1)-cache contract:

- the recurrence is a **diagonal input-gated linear scan** (no
  ``h @ W_hh`` matmul per step: the transition is elementwise, which is
  what makes the parallel mode associative and the serve step
  matmul-free);
- the head pools with two **learned-rate EMAs** of the output sequence
  instead of windowed max/mean (``models.common.ema_concat_logits``):
  max over a trailing window cannot be carried in O(1) state, EMAs are
  linear recurrences and can.

Parameter names follow the torch-ish ``weight_ih_l0`` convention for
the projection (so the serve-side ``_layer_weights`` dispatch reads all
families uniformly) plus per-channel vectors ``a_base_l0`` (decay
offset, LRU-style init spread over ``cfg.ssm_decay_range``), ``d_l0``
(feedthrough), and ``rho_f_l0``/``rho_s_l0`` (head-EMA rates, init from
``cfg.ssm_ema_init``); ``_reverse`` suffixes for the backward direction.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from fmda_tpu.config import ModelConfig
from fmda_tpu.models.common import (
    _torch_uniform_init,
    ema_concat_logits,
    input_dropout,
)
from fmda_tpu.ops.ssm import (
    SSMWeights,
    ema_pool_parallel,
    linear_scan_parallel,
    ssm_input_projection,
    ssm_scan_parallel,
)


class SSMState(NamedTuple):
    """Carried training-mode state for chunked streaming: per-layer
    diagonal state plus the last layer's head EMAs (each the forward
    direction — a bidirectional backward carry would need the future,
    same restriction as the sibling families)."""

    s: jax.Array  # (n_layers, B, H)
    ema_fast: jax.Array  # (B, H)
    ema_slow: jax.Array  # (B, H)


def _logit(p: float) -> float:
    import math

    return math.log(p / (1.0 - p))


def _decay_offset_init(lo: float, hi: float):
    """Per-channel decay offsets spread so ``sigmoid(a_base)`` is
    uniform in [lo, hi] — the long-memory ring init."""

    def init(key, shape, dtype=jnp.float32):
        u = jax.random.uniform(key, shape, dtype, minval=lo, maxval=hi)
        return jnp.log(u / (1.0 - u))

    return init


def _const_init(value: float):
    def init(key, shape, dtype=jnp.float32):
        del key
        return jnp.full(shape, value, dtype)

    return init


class GatedSSM(nn.Module):
    """See module docstring. ``cfg.n_features`` must be resolved."""

    cfg: ModelConfig

    def _direction_weights(
        self, layer: int, reverse: bool, in_dim: int
    ) -> SSMWeights:
        cfg = self.cfg
        h = cfg.hidden_size
        suffix = f"l{layer}" + ("_reverse" if reverse else "")
        scale = 1.0 / jnp.sqrt(h)
        lo, hi = cfg.ssm_decay_range
        ema_f, ema_s = cfg.ssm_ema_init
        return SSMWeights(
            w_ih=self.param(f"weight_ih_{suffix}",
                            _torch_uniform_init(scale), (3 * h, in_dim)),
            b_ih=self.param(f"bias_ih_{suffix}",
                            _torch_uniform_init(scale), (3 * h,)),
            a_base=self.param(f"a_base_{suffix}",
                              _decay_offset_init(lo, hi), (h,)),
            d=self.param(f"d_{suffix}", _torch_uniform_init(scale), (h,)),
            rho_f=self.param(f"rho_f_{suffix}",
                             _const_init(_logit(ema_f)), (h,)),
            rho_s=self.param(f"rho_s_{suffix}",
                             _const_init(_logit(ema_s)), (h,)),
        )

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        state: Optional[SSMState] = None,
        *,
        deterministic: bool = True,
        mask: Optional[jax.Array] = None,
        return_state: bool = False,
    ):
        """Forward pass; same contract as :meth:`BiGRU.__call__`.

        ``mask`` marks valid steps of padded windows.  The linear
        recurrence carries the previous state through masked steps
        unchanged (decay forced to 1, input to 0) and the head EMAs
        skip them, so padded batches match their unpadded twins.
        """
        cfg = self.cfg
        assert cfg.n_features is not None, "ModelConfig.n_features unresolved"
        n_dirs = 2 if cfg.bidirectional else 1
        if state is not None and cfg.bidirectional:
            raise ValueError(
                "carried SSMState requires bidirectional=False; "
                "re-scan the full window for bidirectional models"
            )
        compute_dtype = jnp.dtype(cfg.dtype)
        x = x.astype(compute_dtype)
        x = input_dropout(cfg, x, deterministic=deterministic)

        layer_input = x
        s_finals = []  # forward-direction per-layer final states
        out_sum = None
        last_hidden = None
        last_w_fwd = None
        for layer in range(cfg.n_layers):
            in_dim = cfg.n_features if layer == 0 else cfg.hidden_size * n_dirs
            dir_outputs = []
            dir_finals = []
            for d in range(n_dirs):
                reverse = d == 1
                w = self._direction_weights(layer, reverse, in_dim)
                w = SSMWeights(*(p.astype(compute_dtype) for p in w))
                if not reverse:
                    last_w_fwd = w
                xp = ssm_input_projection(layer_input, w)
                if mask is not None:
                    # masked steps are identities of the recurrence:
                    # decay 1 (zp + a_base -> +inf), candidate/output 0
                    m = mask[..., None].astype(compute_dtype)
                    h_ = cfg.hidden_size
                    big = jnp.asarray(30.0, compute_dtype)  # sigmoid≈1
                    zp = jnp.where(m > 0, xp[..., :h_], big - w.a_base)
                    rest = xp[..., h_:] * m
                    xp = jnp.concatenate([zp, rest], axis=-1)
                s0 = (state.s[layer].astype(compute_dtype)
                      if (state is not None and not reverse) else None)
                hs, s_last = ssm_scan_parallel(xp, w, s0, reverse=reverse)
                dir_outputs.append(hs)
                dir_finals.append(s_last)
            if not cfg.bidirectional:
                s_finals.append(dir_finals[0])
            layer_output = (
                jnp.concatenate(dir_outputs, axis=-1)
                if n_dirs == 2 else dir_outputs[0]
            )
            out_sum = (dir_outputs[0] + dir_outputs[1]
                       if n_dirs == 2 else dir_outputs[0])
            if n_dirs == 2:
                # forward's newest step + backward's output at t=0 (its
                # own scan end) — the direction-summed "final hidden"
                last_hidden = dir_outputs[0][:, -1] + dir_outputs[1][:, 0]
            else:
                last_hidden = out_sum[:, -1]
            if cfg.n_layers > 1 and layer < cfg.n_layers - 1:
                layer_output = nn.Dropout(cfg.dropout)(
                    layer_output, deterministic=deterministic
                )
            layer_input = layer_output

        # Head: EMAs of the direction-summed output sequence at the last
        # layer's forward-direction learned rates — the train-mode twin
        # of the serving cache's (ema_fast, ema_slow) entries.
        ef0 = (state.ema_fast.astype(compute_dtype)
               if state is not None else None)
        es0 = (state.ema_slow.astype(compute_dtype)
               if state is not None else None)
        if mask is not None:
            # masked steps must not decay the EMAs: carry them through
            m = mask[..., None].astype(compute_dtype)
            rf = jax.nn.sigmoid(last_w_fwd.rho_f)
            rs = jax.nn.sigmoid(last_w_fwd.rho_s)
            af = jnp.where(m > 0, jnp.broadcast_to(rf, out_sum.shape), 1.0)
            as_ = jnp.where(m > 0, jnp.broadcast_to(rs, out_sum.shape), 1.0)
            with jax.named_scope("head"):
                ema_fast = linear_scan_parallel(
                    af, (1.0 - af) * out_sum, ef0)[:, -1]
                ema_slow = linear_scan_parallel(
                    as_, (1.0 - as_) * out_sum, es0)[:, -1]
            # the "last hidden" of a padded window reads the last VALID
            # forward step (+ the backward scan end, which already sits
            # at t=0 — the reversed scan crossed the padding first)
            idx = jnp.maximum(
                jnp.sum(mask.astype(jnp.int32), axis=1) - 1, 0)
            fwd_last = jnp.take_along_axis(
                dir_outputs[0], idx[:, None, None], axis=1)[:, 0]
            last_hidden = (fwd_last + dir_outputs[1][:, 0]
                           if n_dirs == 2 else fwd_last)
        else:
            ema_fast = ema_pool_parallel(out_sum, last_w_fwd.rho_f, ef0)
            ema_slow = ema_pool_parallel(out_sum, last_w_fwd.rho_s, es0)

        logits = ema_concat_logits(self.cfg, last_hidden, ema_fast, ema_slow)

        if return_state:
            if cfg.bidirectional:
                raise ValueError(
                    "return_state requires bidirectional=False (the "
                    "backward direction cannot be carried)")
            return logits, SSMState(
                s=jnp.stack(s_finals), ema_fast=ema_fast,
                ema_slow=ema_slow)
        return logits


def init_ssm(
    cfg: ModelConfig, rng: jax.Array, batch: int = 1, seq_len: int = 8
) -> Tuple[GatedSSM, dict]:
    """Convenience constructor: build the module and initialise params."""
    model = GatedSSM(cfg)
    dummy = jnp.zeros((batch, seq_len, cfg.n_features), jnp.float32)
    params = model.init({"params": rng}, dummy)
    return model, params
