"""Plain reference for the ``decoder`` family's learned-sparse layer:
forward, loss and gradients of Keye-VL-2.0-30B-A3B's language model in
straightforward ``jax.numpy``, float32, every product under
``jax.default_matmul_precision("highest")``.  No kernel, no counting
selection, no mask from the program unless it is handed one: the index
scores of a block of query rows against every key, ``lax.top_k`` over
the masked score row, attention by an explicit mask, a dense loop over
the held experts.  It imports nothing of ``fmda_tpu`` (the helpers it
shares with ``reference/moe_decoder.py`` are that file's); it reads the
program's parameter tree (names below) and a record of sizes.

One layer (x: residual stream ``(T, 2048)``; all 48 layers alike;
source: the catalog's ``config`` and ``described_as`` for
Keye-VL-2.0-30B-A3B, ``sa_config`` for the indexer)::

    h   = RMSNorm(x; eps)
    q, k, v = h @ wq, h @ wk, h @ wv                 32 query heads on 4 kv heads of 128
    q, k = RMSNorm_head(q; q_norm), RMSNorm_head(k; k_norm); rotary on q, k (theta 1e7)
    qI  = rotary(h @ wq_idx)  (16 heads of 64);  kI = rotary(h @ wk_idx)  (1 head of 64)
    w   = h @ ww_idx  (16)
    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])          s <= t
    S_t = the min(t + 1, 2048) keys s <= t with the largest I[t, s], ties to the lower s
    a_t = softmax_{s in S_t}(q_t . k_s / sqrt(128)) v_s ;   x1 = x + a @ wo
    u   = RMSNorm(x1; eps)
    p   = softmax(u @ router) over 128 ;  S = top-8 ;  g_e = p_e / sum_{S} p    (norm_topk_prob)
    m   = sum_{e in S, e held} g_e * (silu(u @ w_gate[e]) * (u @ w_up[e])) @ w_down[e]
    x2  = x1 + m

then a final RMSNorm, the untied ``head`` and next-token cross-entropy
(mean over the tokens whose mask is 1).

Departures from the published description, each shared with the program:

1. **The share.**  Only the experts ``experts_held = (first, count)`` are
   summed (16 of 128 here); the router keeps its 128 outputs and the
   gates are normalised over the whole top-8.  The vocabulary is the
   held slice (18,992 rows).  4 of the 48 layers.
2. **Language model only, text positions.**  The vision tower is not
   built; on text the three M-RoPE axes carry one position, so rotary is
   the ordinary one (half-split pairs ``(i, i + d/2)``).
3. **QK-norm** (an RMSNorm per head on q and k before rotary) is the
   base family's convention, not a key of the catalog's row.
4. **The indexer reads h**, carries the same rotary on its 64 dims, has
   no norm on ``kI``; ``q_chunk_size`` / ``kv_chunk_size`` are read as
   tiling, not as a change to ``S_t``.
5. **The indexer is not trained**: the top-k is piecewise constant, the
   next-token loss sends it no gradient, and the alignment term of the
   mechanism's published recipe is not among the row's keys.  The
   gradient of ``wq_idx``, ``wk_idx``, ``ww_idx`` is zero here too.
6. ``-0.0`` and ``+0.0`` are one score (a sum of weighted relus is often
   exactly zero, of either sign).

The reference can be made deliberately wrong, for the comparison that
decides ``correct`` to catch: ``products_as`` (every operand of every
product rounded to a narrower type), ``skip_expert``, ``topk`` (another
count of keys), ``dense_attention`` (every causal key), and
``indexer_relu=False``.  ``selection`` hands it the program's own picks
(``(layers, T, T)`` masks): attention then runs over those, and the
reference's own top-k is only compared with them
(:class:`SelectionDistance`).

Parameter tree (the program's, float32): ``embed (V, D)``; ``block_<i>``:
``ln_attn (D,)``, ``wq (D, 32*128)``, ``wk``/``wv (D, 4*128)``,
``q_norm``/``k_norm (128,)``, ``wq_idx (D, 16*64)``, ``wk_idx (D, 64)``,
``ww_idx (D, 16)``, ``wo (32*128, D)``, ``ln_moe (D,)``, ``router
(D, 128)``, ``w_gate``/``w_up (count, D, F)``, ``w_down (count, F, D)``;
``ln_final (D,)``; ``head (D, V)``.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from benchmark.reference.moe_decoder import (  # noqa: F401  (re-exported)
    _narrower, _rms_norm, _rotary, first_adam_step)

#: Query rows scored against all keys at a time (32 heads x 256 x 16,384
#: float32 scores are 0.5 GB, and a backward holds a handful of them).
QUERY_BLOCK = 256


class SelectionDistance(NamedTuple):
    """One layer's picks against the reference's own top-k, summed over
    the query rows: the keys kept by whoever chose, those the program
    took and the reference did not (and the reverse), and the farthest
    that any such key's reference score lies from the reference's last
    kept score of its row, in units of the row's score spread (the
    standard deviation of its causal scores)."""

    kept: jax.Array        # () int32
    program_only: jax.Array  # () int32
    reference_only: jax.Array  # () int32
    worst_gap: jax.Array   # () float32


def _blocks(t: int) -> int:
    return t // QUERY_BLOCK if t > QUERY_BLOCK and t % QUERY_BLOCK == 0 else 1


def _select(p: Dict, h_n, cfg, topk: int, narrow, indexer_relu: bool,
            dense: bool, given, attend_given=True):
    """The keys each query attends over, (T, T) bool, a block of query
    rows at a time: the reference's own top-k, or ``given`` where there
    is one and ``attend_given`` (a bool, or a traced one: one compiled
    program then serves both), and the distance between the two."""
    t = h_n.shape[0]
    hi, di = cfg.indexer_heads, cfg.indexer_head_dim
    q_idx = _rotary((h_n @ narrow(p["wq_idx"])).reshape(t, hi, di)
                    .transpose(1, 0, 2), cfg.rope_theta)
    k_idx = _rotary((h_n @ narrow(p["wk_idx"]))[None], cfg.rope_theta)[0]
    w_idx = h_n @ narrow(p["ww_idx"])
    k_idx = narrow(k_idx)
    col = jnp.arange(t)[None, :]
    k_eff = min(topk, t)

    def block(q_blk, w_blk, rows, given_blk):
        row = rows[:, None]
        causal = col <= row
        if dense:
            picked = causal
            scores = jnp.zeros(causal.shape, jnp.float32)
            last = jnp.zeros(rows.shape, jnp.float32)
        else:
            s = jnp.einsum("hqd,kd->hqk", narrow(q_blk), k_idx)
            if indexer_relu:
                s = jax.nn.relu(s)
            scores = jnp.sum(w_blk.T[:, :, None] * s, axis=0)
            scores = jnp.where(scores == 0.0, 0.0, scores)
            top, _ = jax.lax.top_k(
                jnp.where(causal, scores, -jnp.inf), k_eff)
            # the row's picks are top_k's first `want` indices; written
            # as a mask without a scatter: every score above the last
            # kept one, and of those equal to it the lowest columns
            # (top_k's own order among equals) up to the count
            want = jnp.minimum(rows + 1, topk)
            last = jnp.take_along_axis(top, want[:, None] - 1, axis=1)[:, 0]
            above = scores > last[:, None]
            equal = causal & (scores == last[:, None])
            short = want - jnp.sum(causal & above, axis=-1)
            picked = causal & (above | (
                equal & (jnp.cumsum(equal, axis=-1) <= short[:, None])))
        if given_blk is None:
            zero = jnp.zeros((), jnp.int32)
            return picked, SelectionDistance(
                jnp.sum(picked, dtype=jnp.int32), zero, zero,
                jnp.zeros((), jnp.float32))
        given_blk = given_blk != 0
        n = jnp.sum(causal, axis=-1)
        mean = jnp.sum(jnp.where(causal, scores, 0.0), axis=-1) / n
        spread = jnp.sqrt(jnp.sum(jnp.where(
            causal, (scores - mean[:, None]) ** 2, 0.0), axis=-1) / n)
        gap = jnp.abs(scores - last[:, None]) / jnp.maximum(
            spread, 1e-30)[:, None]
        differ = given_blk != picked
        return jnp.where(attend_given, given_blk, picked), SelectionDistance(
            jnp.sum(given_blk, dtype=jnp.int32),
            jnp.sum(given_blk & ~picked, dtype=jnp.int32),
            jnp.sum(picked & ~given_blk, dtype=jnp.int32),
            jnp.max(jnp.where(differ, gap, 0.0)))

    n = _blocks(t)
    rows = jnp.arange(t)
    if n == 1:
        return block(q_idx, w_idx, rows, given)
    blk = t // n
    xs = (q_idx.reshape(hi, n, blk, di).transpose(1, 0, 2, 3),
          w_idx.reshape(n, blk, hi), rows.reshape(n, blk))
    if given is None:
        picked, dist = jax.lax.map(lambda a: block(*a, None), xs)
    else:
        picked, dist = jax.lax.map(
            lambda a: block(*a), xs + (given.reshape(n, blk, t),))
    return picked.reshape(t, t), SelectionDistance(
        jnp.sum(dist.kept), jnp.sum(dist.program_only),
        jnp.sum(dist.reference_only), jnp.max(dist.worst_gap))


def _attention(q, k, v, picked, remat: bool, narrow=lambda a: a):
    """q (N, T, d), k/v (G, T, d), picked (T, T) bool -> (N, T, d):
    softmax over the picked keys, a block of query rows at a time."""
    n, t, d = q.shape
    group = n // k.shape[0]
    k, v = jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0)
    k, v = narrow(k), narrow(v)

    def block(q_blk, keep):
        s = jnp.einsum("nqd,nkd->nqk", narrow(q_blk), k) / jnp.sqrt(
            jnp.float32(d))
        s = jnp.where(keep[None], s, -jnp.inf)
        return jnp.einsum(
            "nqk,nkd->nqd", narrow(jax.nn.softmax(s, axis=-1)), v)

    nb = _blocks(t)
    if nb == 1:
        return block(q, picked)
    if remat:
        block = jax.checkpoint(block)
    blk = t // nb
    out = jax.lax.map(
        lambda xs: block(*xs),
        (q.reshape(n, nb, blk, d).transpose(1, 0, 2, 3),
         picked.reshape(nb, blk, t)))
    return out.transpose(1, 0, 2, 3).reshape(n, t, d)


def _layer(p: Dict, x, cfg, *, skip_expert: Optional[int] = None,
           remat: bool = False, products_as: Optional[str] = None,
           topk: Optional[int] = None, dense_attention: bool = False,
           indexer_relu: bool = True, given=None, attend_given=True):
    """One layer on one sequence x (T, D) -> (x2, pairs per held expert,
    the picks attention ran over (T, T) bool, their distance from the
    reference's own)."""
    n, g, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    first, count = cfg.experts_held
    t = x.shape[0]
    narrow = _narrower(products_as)
    h = _rms_norm(x, p["ln_attn"], cfg.rms_norm_eps)
    h_n = narrow(h)

    def heads(w, n_heads):
        return (h_n @ narrow(w)).reshape(t, n_heads, hd).transpose(1, 0, 2)

    q, k, v = heads(p["wq"], n), heads(p["wk"], g), heads(p["wv"], g)
    q = _rotary(_rms_norm(q, p["q_norm"], cfg.rms_norm_eps), cfg.rope_theta)
    k = _rotary(_rms_norm(k, p["k_norm"], cfg.rms_norm_eps), cfg.rope_theta)
    picked, distance = _select(
        jax.lax.stop_gradient(p), jax.lax.stop_gradient(h_n), cfg,
        cfg.indexer_topk if topk is None else topk, narrow, indexer_relu,
        dense_attention, given, attend_given)
    a = _attention(q, k, v, picked, remat, narrow)
    x1 = x + narrow(a.transpose(1, 0, 2).reshape(t, n * hd)) @ narrow(p["wo"])

    u = _rms_norm(x1, p["ln_moe"], cfg.rms_norm_eps)
    u_e = narrow(u)
    probs = jax.nn.softmax(u_e @ narrow(p["router"]), axis=-1)
    top, chosen = jax.lax.top_k(probs, cfg.moe_top_k)
    gates = top / jnp.sum(top, axis=-1, keepdims=True)

    def add_expert(m, held):
        """The next held expert, densely, the gate a mask."""
        e, w_gate, w_up, w_down = held
        on_e = chosen == first + e                      # (T, k)
        gate_e = jnp.sum(jnp.where(on_e, gates, 0.0), axis=-1)
        if skip_expert is not None:
            gate_e = jnp.where(e == skip_expert, 0.0, gate_e)
        y = narrow(jax.nn.silu(u_e @ narrow(w_gate))
                   * (u_e @ narrow(w_up))) @ narrow(w_down)
        return m + gate_e[:, None] * y, jnp.sum(on_e, dtype=jnp.int32)

    m, pairs = jax.lax.scan(
        add_expert, jnp.zeros_like(x1),
        (jnp.arange(count), p["w_gate"], p["w_up"], p["w_down"]))
    return x1 + m, pairs, picked, distance


def hidden_states(params: Dict, ids, cfg, *, selection=None,
                  remat: bool = False, **kw):
    """ids (T,) -> (final-normed hidden (T, D), pairs (layers, count),
    picks (layers, T, T) bool, :class:`SelectionDistance` of (layers,)
    leaves).  ``selection`` (layers, T, T): attend over these picks
    instead of the reference's own.  ``kw``: the deliberately wrong runs
    (module docstring)."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][ids]
        pairs, picks, dists = [], [], []
        for i in range(len(cfg.layer_layout)):
            given = None if selection is None else selection[i]
            layer = lambda p, x, g: _layer(p, x, cfg, remat=remat, given=g,
                                           **kw)
            if remat:
                layer = jax.checkpoint(layer)
            x, layer_pairs, picked, dist = layer(
                params[f"block_{i}"], x, given)
            pairs.append(layer_pairs)
            picks.append(picked)
            dists.append(dist)
        return (_rms_norm(x, params["ln_final"], cfg.rms_norm_eps),
                jnp.stack(pairs), jnp.stack(picks),
                jax.tree.map(lambda *a: jnp.stack(a), *dists))


def _head_logits(params: Dict, hidden, products_as: Optional[str] = None,
                 **_):
    narrow = _narrower(products_as)
    return narrow(hidden) @ narrow(params["head"])


def _nll(lg, targets, keep):
    nll = jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(
        lg, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(keep, nll, 0.0))


def logits(params: Dict, ids, cfg, **kw):
    """ids (T,) -> (T, V) float32."""
    with jax.default_matmul_precision("highest"):
        hidden = hidden_states(params, ids, cfg, **kw)[0]
        return _head_logits(params, hidden, **kw)


def loss_pairs_and_selection(params: Dict, ids, targets, mask, cfg, **kw):
    """Mean next-token cross-entropy over the masked tokens of one
    sequence, the pairs each held expert of each layer received, and the
    selection's :class:`SelectionDistance` a layer."""
    with jax.default_matmul_precision("highest"):
        hidden, pairs, _, dist = hidden_states(params, ids, cfg, **kw)
        keep = mask > 0
        total = _nll(_head_logits(params, hidden, **kw), targets, keep)
        return total / jnp.maximum(jnp.sum(keep), 1), pairs, dist


def loss(params: Dict, ids, targets, mask, cfg, **kw):
    return loss_pairs_and_selection(params, ids, targets, mask, cfg, **kw)[0]


def picks(params: Dict, ids, cfg, **kw):
    """The reference's own selection, (layers, T, T) bool."""
    return hidden_states(params, ids, cfg, **kw)[2]


def batch_loss(params: Dict, x, y, mask, cfg, *, selection=None, **kw):
    """The trainer's step loss on a batch (B, T): the mean over all the
    batch's masked tokens.  ``selection`` (B, layers, T, T)."""
    total = count = 0.0
    for i in range(x.shape[0]):
        n_i = jnp.sum(mask[i] > 0)
        given = None if selection is None else selection[i]
        total = total + loss(params, x[i], y[i], mask[i], cfg,
                             selection=given, **kw) * n_i
        count = count + n_i
    return total / jnp.maximum(count, 1)


def loss_and_grads(params: Dict, x, y, mask, cfg, *, remat: bool = True,
                   **kw):
    """``(loss, gradients)`` of :func:`batch_loss`, float32."""
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda p: batch_loss(p, x, y, mask, cfg, remat=remat, **kw)
        )(params)


def clip_scale(grads: Dict, clip: float) -> float:
    """The factor that clips ``grads`` to a global norm of ``clip``."""
    norm = sum(float((g * g).sum()) for g in jax.tree.leaves(grads)) ** 0.5
    return min(1.0, clip / norm)


def first_adam_leaf(g, scale: float, learning_rate: float,
                    eps: float = 1e-8):
    """:func:`first_adam_step` for one leaf, given the tree's
    :func:`clip_scale`: ``(the clipped gradient, the parameter's
    change)``.  A leaf at a time, so that a comparison on the device
    never holds three trees of a model's size."""
    clipped = g * scale
    return clipped, -learning_rate * clipped / (abs(clipped) + eps)


class Layerwise:
    """The reference a layer at a time, for the comparison on the chip:
    one compiled forward of a layer, one compiled vector-Jacobian product
    of a layer, the head's loss and gradient, the embedding's rows.  At
    the published widths no more than one layer is compiled or held at a
    time, so the comparison stays under the memory the training itself
    peaks at, and every comparison of a run (validation loss under the
    program's selection and under the reference's own, the first step's
    loss and gradients) runs through the same four programs: nothing is
    compiled twice.  The same numbers as :func:`loss_and_grads`
    (tests/test_sparse_decoder.py).  ``kw``: the deliberately wrong runs
    (module docstring)."""

    def __init__(self, cfg, **kw) -> None:
        self.cfg, self.kw = cfg, dict(kw)

        def layer_all(p, h, given, attend_given):
            x2, pairs, _, dist = _layer(
                p, h, cfg, remat=True, given=given,
                attend_given=attend_given, **self.kw)
            return x2, pairs, dist

        def in_highest(fn):
            def run(*args):
                with jax.default_matmul_precision("highest"):
                    return fn(*args)
            return jax.jit(run)

        def head_loss(ln_final, head, h, targets, keep, count):
            hidden = _rms_norm(h, ln_final, cfg.rms_norm_eps)
            return _nll(_head_logits({"head": head}, hidden, **self.kw),
                        targets, keep) / count

        self.forward = in_highest(layer_all)
        self.backward = in_highest(
            lambda p, h, given, ct: jax.vjp(
                lambda p, h: layer_all(p, h, given, True)[0], p, h)[1](ct))
        self.head_grad = in_highest(
            jax.value_and_grad(head_loss, argnums=(0, 1, 2)))
        self.embed_grad = jax.jit(
            lambda table, ids, ct: jnp.zeros_like(table).at[ids].add(ct))

    def _forward(self, params, ids, given, attend_given):
        """Layer inputs (the last: the final layer's output), and what
        each layer counted, as host arrays."""
        import numpy as np

        inputs, counted = [params["embed"][ids]], []
        for i in range(len(self.cfg.layer_layout)):
            x2, pairs, dist = self.forward(
                params[f"block_{i}"], inputs[-1],
                None if given is None else given[i], attend_given)
            inputs.append(x2)
            counted.append((np.asarray(pairs),
                            jax.tree.map(np.asarray, dist)))
        pairs = np.stack([p for p, _ in counted])
        dist = SelectionDistance(*(np.stack(leaf) for leaf in zip(
            *(d for _, d in counted))))
        return inputs, pairs, dist

    def loss(self, params, ids, targets, mask, selection=None,
             attend_given: bool = True):
        """One sequence: ``(mean loss, pairs (layers, count),
        SelectionDistance)``.  ``selection`` (layers, T, T): the picks to
        compare the reference's own with, and, with ``attend_given``, to
        attend over."""
        keep = mask > 0
        inputs, pairs, dist = self._forward(
            params, ids, selection, jnp.asarray(attend_given))
        part, _ = self.head_grad(
            params["ln_final"], params["head"], inputs.pop(), targets,
            keep, jnp.maximum(jnp.sum(keep), 1))
        return float(part), pairs, dist

    def loss_and_grads(self, params, x, y, mask, selection=None):
        """A batch (B, T): ``(loss, gradients (left on the device: a
        tree of the model's size is a minute through the host), pairs,
        SelectionDistance)``: forward keeping each layer's input, the
        head's gradient, then each layer's vector-Jacobian product from
        the last to the first, the embedding's rows last; the batch's
        sequences one after the other and summed (the worst gap their
        largest).  ``selection`` (B, layers, T, T)."""
        import numpy as np

        n_layers = len(self.cfg.layer_layout)
        count = jnp.maximum(jnp.sum(mask > 0), 1)
        total, grads, counts = 0.0, None, []
        for b, (ids, targets, keep) in enumerate(zip(x, y, mask > 0)):
            given = None if selection is None else selection[b]
            inputs, pairs, dist = self._forward(
                params, ids, given, jnp.asarray(True))
            counts.append((pairs, dist))
            part, (g_ln, g_head, ct) = self.head_grad(
                params["ln_final"], params["head"], inputs.pop(), targets,
                keep, count)
            one = {"ln_final": g_ln, "head": g_head}
            for i in reversed(range(n_layers)):
                g_block, ct = self.backward(
                    params[f"block_{i}"], inputs.pop(),
                    None if given is None else given[i], ct)
                one[f"block_{i}"] = g_block
            one["embed"] = self.embed_grad(params["embed"], ids, ct)
            total = total + float(part)
            grads = one if grads is None else jax.tree.map(
                lambda a, b: a + b, grads, one)
        dists = [d for _, d in counts]
        dist = SelectionDistance(
            sum(d.kept for d in dists), sum(d.program_only for d in dists),
            sum(d.reference_only for d in dists),
            np.max([d.worst_gap for d in dists], axis=0))
        return (total, {k: grads[k] for k in params},
                sum(p for p, _ in counts), dist)
