"""Multi-head self-attention ops, written blockwise so the same math runs
single-device or ring-sharded over the ``sp`` mesh axis.

The reference has no attention anywhere — its one model is a torch
``nn.GRU`` (biGRU_model.py:54-56) and its long-context story is "make the
sliding window longer" (sql_pytorch_dataloader.py:8-18).  Attention is the
framework's second long-context path: where the GRU's sequence parallelism
is inherently serial across time shards (parallel/seq_parallel.py — the
carry must travel the ring), attention over the same windows has NO serial
dependency, so sequence shards compute concurrently and only the K/V blocks
travel the ring (parallel/ring_attention.py).

Everything is built from one primitive, :func:`online_attention_block`:
a numerically-stable streaming-softmax accumulation step (the flash/ring
attention recurrence).  Computing attention over K/V blocks b = 1..n::

    m_b = max(m_{b-1}, rowmax(S_b))                 # running max
    l_b = l_{b-1} * exp(m_{b-1} - m_b) + rowsum(exp(S_b - m_b))
    o_b = o_{b-1} * exp(m_{b-1} - m_b) + exp(S_b - m_b) @ V_b

and ``o_n / l_n`` equals softmax(S) @ V exactly (in exact arithmetic) no
matter how the key axis was blocked — which is precisely what lets the
ring pass blocks around devices and still match the single-device result.
All accumulation is float32 regardless of the I/O dtype; logits are scaled
by 1/sqrt(d_head), or by the ``scale`` a caller states.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

#: The names an attention core gives what its backward reads and a
#: recomputation would have to run the core again for: its output, and
#: (the kernels alone) its rows' logsumexp.  A ``jax.checkpoint`` policy
#: that saves these names (:data:`fmda_tpu.models.decoder.REPLAY_KEEPS`)
#: replays a block without the core's forward; anywhere else the names
#: are identities.
CORE_OUT = "attention_core_out"
CORE_LSE = "attention_core_lse"


class OnlineSoftmaxState(NamedTuple):
    """Running streaming-softmax accumulators, all float32.

    Shapes (B = batch, Tq = local query length, N = heads, D = d_head):
    ``m``: (B, N, Tq) running row max; ``l``: (B, N, Tq) running row sum;
    ``o``: (B, N, Tq, D) unnormalized output accumulator.
    """

    m: jax.Array
    l: jax.Array
    o: jax.Array


def init_online_state(
    batch: int, n_heads: int, q_len: int, d_head: int
) -> OnlineSoftmaxState:
    return OnlineSoftmaxState(
        m=jnp.full((batch, n_heads, q_len), -jnp.inf, jnp.float32),
        l=jnp.zeros((batch, n_heads, q_len), jnp.float32),
        o=jnp.zeros((batch, n_heads, q_len, d_head), jnp.float32),
    )


def online_attention_block(
    state: OnlineSoftmaxState,
    q: jax.Array,  # (B, N, Tq, D)
    k: jax.Array,  # (B, N, Tk, D)
    v: jax.Array,  # (B, N, Tk, D)
    mask: Optional[jax.Array] = None,  # (Tq, Tk) or (B, 1|N, Tq, Tk), True=keep
    scale: Optional[float] = None,
) -> OnlineSoftmaxState:
    """Fold one K/V block into the running softmax state.

    The QK^T matmul runs on the MXU in the input dtype with f32
    accumulation; everything after is f32 VPU work.  Fully-masked rows are
    safe: the running max stays finite only once a row sees a real key, and
    :func:`finalize_online_state` guards the l=0 case.  ``scale``
    multiplies the scores; None is ``1 / sqrt(d_head)``.
    """
    if scale is None:
        scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
    s = jnp.einsum(
        "bnqd,bnkd->bnqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if mask is not None:
        s = jnp.where(mask, s, -jnp.inf)

    m_new = jnp.maximum(state.m, jnp.max(s, axis=-1))
    # rows that have seen no unmasked key yet keep m=-inf; exp(-inf - -inf)
    # is nan, so pin the correction for those rows to 0
    m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    corr = jnp.where(
        jnp.isneginf(state.m), 0.0, jnp.exp(state.m - m_safe))
    p = jnp.exp(jnp.where(jnp.isneginf(s), -jnp.inf, s - m_safe[..., None]))
    l_new = state.l * corr + jnp.sum(p, axis=-1)
    o_new = state.o * corr[..., None] + jnp.einsum(
        "bnqk,bnkd->bnqd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return OnlineSoftmaxState(m=m_new, l=l_new, o=o_new)


def finalize_online_state(
    state: OnlineSoftmaxState, dtype
) -> jax.Array:
    """Normalize the accumulator into attention output (B, N, Tq, D).
    Rows that saw only masked keys (l == 0) come out as zeros."""
    l = jnp.where(state.l == 0.0, 1.0, state.l)
    return (state.o / l[..., None]).astype(dtype)


def merge_softmax_segments(
    o1: jax.Array,  # (..., T, D) — normalized attention over key set S1
    lse1: jax.Array,  # (..., T) — logsumexp of S1's scores
    o2: jax.Array,
    lse2: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """Exactly combine two *normalized* attention results over disjoint
    key segments into the result over their union.

    With ``o_i = softmax(S_i) @ V_i`` and ``lse_i = logsumexp(S_i)``,
    the unnormalized numerator of segment i is ``o_i * exp(lse_i)``, so::

        m   = max(lse1, lse2)
        a_i = exp(lse_i - m)
        o   = (o1*a1 + o2*a2) / (a1 + a2)
        lse = m + log(a1 + a2)

    This is the segment-level form of the same online-softmax identity
    :func:`online_attention_block` applies blockwise — it lets ring
    attention fold one fused flash-kernel call per ring step
    (each returning (o, lse) for its K/V block) with O(T*D) elementwise
    work, no score materialisation.  Empty segments are represented by a
    large-negative finite lse (the flash kernel's -1e30 sentinel): their
    weight underflows to exactly 0, and merging two empty segments
    yields o = 0 without NaNs (which -inf arithmetic would produce).
    """
    m = jnp.maximum(lse1, lse2)
    a1 = jnp.exp(lse1 - m)
    a2 = jnp.exp(lse2 - m)
    denom = a1 + a2
    o = (o1 * a1[..., None] + o2 * a2[..., None]) / denom[..., None]
    return o, m + jnp.log(denom)


def flash_available() -> bool:
    """True when the fused Pallas flash-attention kernel can run here."""
    try:
        from fmda_tpu.ops import pallas_attention  # noqa: F401
    except ImportError:
        return False
    return jax.default_backend() == "tpu"


def flash_dispatch(
    tq: int, tk: int, d_head: int,
    *,
    use_flash: bool,
    has_mask: bool = False,
    d_value: Optional[int] = None,
) -> bool:
    """THE dispatch decision :func:`mha` makes — exposed so callers that
    *report* the executed path ask this function instead of
    re-implementing the gate and silently
    drifting from it.  ``has_mask`` means an arbitrary mask array; the
    causal triangle and a causal window are the kernel's own.
    ``d_value`` is the values' width where it is not ``d_head``."""
    if not (use_flash and not has_mask and flash_available()):
        return False
    from fmda_tpu.ops import pallas_attention

    return pallas_attention.flash_supported(tq, tk, d_head, d_value)


#: Query rows the non-kernel path scores at a time once the sequence is
#: longer than this: the scores then cost (B, N, 512, Tk) float32 and
#: never (B, N, Tq, Tk) — 7.5 GB at 28 heads x 8192 x 8192.
FALLBACK_QUERY_BLOCK = 512


def visibility_mask(
    q_pos: jax.Array, k_pos: jax.Array, *, causal: bool,
    window: Optional[int],
) -> Optional[jax.Array]:
    """(Tq, Tk) bool keep-mask from positions: key ``j`` is visible to
    query ``i`` iff ``j <= i`` (causal) and ``i - j < window`` (a causal
    window); None where everything is visible."""
    if not causal and window is None:
        return None
    rel = q_pos[:, None] - k_pos[None, :]
    keep = rel >= 0
    if window is not None:
        keep = keep & (rel < window)
    return keep


def mha(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    window: Optional[int] = None,
    mask: Optional[jax.Array] = None,
    use_flash: bool = False,
    scale: Optional[float] = None,
) -> jax.Array:
    """Single-device multi-head attention via the same online-softmax
    primitive the ring path uses, so the sharded and unsharded paths are
    the *same numerics* by construction.

    ``use_flash=True`` requests the fused Pallas flash kernel
    (:mod:`fmda_tpu.ops.pallas_attention`) on TPU backends — same math,
    but the (T, T) scores never leave VMEM instead of costing
    (B, N, T, T) f32 of HBM traffic.  The flag is the family's
    ``ModelConfig.use_pallas`` (same opt-in convention as the GRU/LSTM
    kernels: the default path stays the one exercised everywhere, and a
    kernel regression can always be ruled out from config).  The kernel
    takes the causal triangle, a causal ``window`` and grouped-query
    heads itself; anything outside its envelope (an arbitrary ``mask``
    array, ragged Tq/Tk, T not a multiple of 128, non-TPU backend) takes
    the jnp path below, which scores ``FALLBACK_QUERY_BLOCK`` query rows
    at a time once the sequence is longer than that (each block
    recomputed in backward), so no path materialises (B, N, Tq, Tk).

    Args:
      q: (B, N, Tq, D).  k: (B, G, Tk, D), v: (B, G, Tk, Dv) with
        ``N % G == 0`` — each run of ``N / G`` consecutive query heads
        shares one key/value head (G == N: plain multi-head attention).
        Values may be narrower or wider than queries and keys
        (``Dv != D``: latent attention scores 192 wide, reads 128).
      causal: apply a lower-triangular causal mask (needed for streaming
        serving where position t must not see the future).
      window: a causal window — key j is visible to query i iff
        ``0 <= i - j < window``; implies ``causal``.
      mask: optional extra mask, (Tq, Tk) or broadcastable (B, N, Tq, Tk).
      use_flash: opt into the fused kernel where supported.
      scale: what the scores are multiplied by before the softmax (a
        model's stated attention multiplier); None is ``1 / sqrt(D)``.

    Returns (B, N, Tq, Dv) in q's dtype.
    """
    tq, tk = q.shape[-2], k.shape[-2]
    causal = causal or window is not None
    # the attn family's place in the scope vocabulary: where the
    # recurrent families have recurrence_fwd/_rev
    with jax.named_scope("attention"):
        if flash_dispatch(tq, tk, q.shape[-1], use_flash=use_flash,
                          has_mask=mask is not None, d_value=v.shape[-1]):
            from fmda_tpu.ops import pallas_attention

            return pallas_attention.flash_attention(
                q, k, v, causal=causal, window=window, scale=scale)
        group = q.shape[1] // k.shape[1]
        if group > 1:  # the kernel indexes; this path repeats
            k, v = (jnp.repeat(x, group, axis=1) for x in (k, v))
        # suffix alignment: query i sits at global position tk - tq + i,
        # so a short query block against a longer K/V history
        # (streaming) sees its full past, not just the first i keys
        q_pos = tk - tq + jnp.arange(tq)
        k_pos = jnp.arange(tk)

        def attend(q_blk, pos_blk, mask_blk):
            full_mask = visibility_mask(
                pos_blk, k_pos, causal=causal, window=window)
            if mask_blk is not None:
                full_mask = (mask_blk if full_mask is None
                             else full_mask & mask_blk)
            state = init_online_state(
                q_blk.shape[0], q_blk.shape[1], q_blk.shape[2],
                v.shape[3])
            state = online_attention_block(state, q_blk, k, v, full_mask,
                                           scale)
            return finalize_online_state(state, q.dtype)

        blk = FALLBACK_QUERY_BLOCK
        if tq <= blk or tq % blk != 0:
            return checkpoint_name(attend(q, q_pos, mask), CORE_OUT)
        # one block of query rows at a time, recomputed in backward
        n_blk = tq // blk
        q_blocks = jnp.moveaxis(
            q.reshape(q.shape[:2] + (n_blk, blk, q.shape[-1])), 2, 0)
        pos_blocks = q_pos.reshape(n_blk, blk)
        if mask is None:
            out = jax.lax.map(
                lambda xs: jax.checkpoint(attend)(xs[0], xs[1], None),
                (q_blocks, pos_blocks))
        else:
            rows = jnp.broadcast_to(
                mask, jnp.broadcast_shapes(mask.shape, (tq, tk)))
            mask_blocks = jnp.moveaxis(rows.reshape(
                rows.shape[:-2] + (n_blk, blk, tk)), -3, 0)
            out = jax.lax.map(
                lambda xs: jax.checkpoint(attend)(*xs),
                (q_blocks, pos_blocks, mask_blocks))
        return checkpoint_name(
            jnp.moveaxis(out, 0, 2).reshape(q.shape[:3] + v.shape[3:]),
            CORE_OUT)


def split_heads(x: jax.Array, n_heads: int) -> jax.Array:
    """(B, T, N*D) -> (B, N, T, D)."""
    b, t, nd = x.shape
    return x.reshape(b, t, n_heads, nd // n_heads).transpose(0, 2, 1, 3)


def merge_heads(x: jax.Array) -> jax.Array:
    """(B, N, T, D) -> (B, T, N*D)."""
    b, n, t, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, n * d)
