"""Fused Pallas flash-attention kernel vs the jnp online-softmax path.

Mirrors the GRU kernel's coverage ladder (tests/test_pallas_gru.py):
interpret-mode numerical parity (values AND gradients, causal and not,
f32 and bf16), Mosaic TPU lowering via jax.export without hardware, and
an on-device parity test gated on a reachable TPU.
"""

import functools

import numpy as np
import pytest

import jax
# jax.export is a real submodule on every supported jax, but older
# releases only expose it as a `jax` attribute after an explicit import
import jax.export  # noqa: F401
import jax.numpy as jnp

from fmda_tpu.ops.attention import mha
from fmda_tpu.ops.pallas_attention import (
    _BLOCK,
    flash_attention,
    flash_supported,
)


def _qkv(batch=2, heads=2, seq=2 * _BLOCK, d_head=16, key=0, dtype=None):
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    shape = (batch, heads, seq, d_head)
    q = jax.random.normal(ks[0], shape)
    k = jax.random.normal(ks[1], shape)
    v = jax.random.normal(ks[2], shape)
    if dtype is not None:
        q, k, v = (x.astype(dtype) for x in (q, k, v))
    return q, k, v


class TestFlashSupported:
    def test_envelope(self):
        assert flash_supported(1024, 1024, 32)
        assert flash_supported(128, 128, 8)
        assert not flash_supported(30, 30, 8)        # flagship window
        assert not flash_supported(128, 256, 8)      # ragged streaming
        assert not flash_supported(1024, 1024, 1024)  # VMEM

    def test_direct_call_raises_outside_envelope(self):
        q, k, v = _qkv(seq=32)
        with pytest.raises(ValueError, match="flash_supported"):
            flash_attention(q, k, v)


@pytest.mark.parametrize("causal", [False, True])
def test_forward_parity(causal):
    q, k, v = _qkv()
    ref = mha(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_forward_parity_single_block():
    """T == one block: the grid degenerates to a single K step."""
    q, k, v = _qkv(seq=_BLOCK)
    ref = mha(q, k, v)
    out = flash_attention(q, k, v, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_gradient_parity(causal):
    q, k, v = _qkv(d_head=8)

    def loss(fn):
        def f(q_, k_, v_):
            o = fn(q_, k_, v_)
            return jnp.sum(o * jnp.cos(o))  # non-trivial cotangent

        return jax.grad(f, argnums=(0, 1, 2))

    ref = loss(lambda a, b, c: mha(a, b, c, causal=causal))(q, k, v)
    out = loss(lambda a, b, c: flash_attention(
        a, b, c, causal=causal, interpret=True))(q, k, v)
    for g_out, g_ref, name in zip(out, ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(g_out), np.asarray(g_ref), atol=5e-5, rtol=5e-5,
            err_msg=f"d{name} mismatch (causal={causal})")


def test_bf16_close_to_f32_reference():
    """bf16 I/O with f32 accumulation tracks the f32 reference within
    bf16 tolerance — catches low-precision accumulator bugs."""
    q, k, v = _qkv()
    ref = mha(q, k, v)
    out = flash_attention(
        *(x.astype(jnp.bfloat16) for x in (q, k, v)), interpret=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("causal", [False, True])
def test_with_lse_matches_reference_logsumexp(causal):
    """The (o, lse) variant: lse must equal logsumexp of the scaled
    (masked) scores row-wise — the contract merge_softmax_segments
    relies on."""
    from fmda_tpu.ops.pallas_attention import flash_attention_with_lse

    q, k, v = _qkv(d_head=8)
    o, lse = flash_attention_with_lse(q, k, v, causal=causal,
                                      interpret=True)
    s = jnp.einsum("bnqd,bnkd->bnqk", q, k) / jnp.sqrt(
        jnp.asarray(q.shape[-1], jnp.float32))
    if causal:
        t = q.shape[-2]
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    ref_lse = jax.scipy.special.logsumexp(s, axis=-1)
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(ref_lse), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(
        np.asarray(o), np.asarray(mha(q, k, v, causal=causal)),
        atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_with_lse_gradient_parity_including_lse_cotangent(causal):
    """Gradients when the loss touches BOTH outputs — the dlse term the
    ring merge differentiates through (bwd folds it as delta - dlse)."""
    from fmda_tpu.ops.pallas_attention import flash_attention_with_lse

    q, k, v = _qkv(d_head=8)

    def ref_loss(q_, k_, v_):
        s = jnp.einsum("bnqd,bnkd->bnqk", q_, k_) / jnp.sqrt(
            jnp.asarray(q_.shape[-1], jnp.float32))
        if causal:
            t = q_.shape[-2]
            s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        o = jnp.einsum("bnqk,bnkd->bnqd", jax.nn.softmax(s, axis=-1), v_)
        lse = jax.scipy.special.logsumexp(s, axis=-1)
        return jnp.sum(o * jnp.cos(o)) + jnp.sum(jnp.sin(lse))

    def pal_loss(q_, k_, v_):
        o, lse = flash_attention_with_lse(q_, k_, v_, causal=causal,
                                          interpret=True)
        return jnp.sum(o * jnp.cos(o)) + jnp.sum(jnp.sin(lse))

    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    g_pal = jax.grad(pal_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_pal, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5,
            err_msg=f"d{name} mismatch (causal={causal})")


def test_mha_dispatch_stays_on_jnp_path_off_tpu():
    """On this (CPU) CI the dispatch must not touch the kernel; the jnp
    path remains the executed one."""
    q, k, v = _qkv()
    out = mha(q, k, v)  # would raise inside pallas_call on CPU if taken
    assert out.shape == q.shape


def _grouped(q, k, v, group):
    """The same queries on ``heads / group`` key-value heads."""
    return q, k[:, ::group], v[:, ::group]


@pytest.mark.parametrize("group", [1, 2])
def test_mosaic_lowering_via_export(group):
    """The kernel lowers through the real Mosaic TPU pass (no hardware
    needed): value + grad, both causal settings, both dtypes, one query
    head and two a forward grid step."""
    q, k, v = _grouped(
        *_qkv(batch=1, heads=2, seq=2 * _BLOCK, d_head=8), group)

    for causal in (False, True):
        for dtype in (jnp.float32, jnp.bfloat16):
            args = tuple(x.astype(dtype) for x in (q, k, v))

            def train_like(q_, k_, v_, _c=causal):
                def f(a, b, c):
                    o = flash_attention(a, b, c, causal=_c)
                    return jnp.sum(o.astype(jnp.float32) ** 2)

                return jax.grad(f, argnums=(0, 1, 2))(q_, k_, v_)

            exported = jax.export.export(
                jax.jit(train_like), platforms=["tpu"])(*args)
            assert "tpu" in exported.platforms


@pytest.mark.parametrize("group", [1, 2])
def test_mosaic_lowering_with_lse_via_export(group):
    """The ring fold's kernel program — (o, lse) outputs with gradients
    through BOTH (the dlse-folded backward) — lowers through the real
    Mosaic TPU pass."""
    from fmda_tpu.ops.pallas_attention import flash_attention_with_lse

    q, k, v = _grouped(
        *_qkv(batch=1, heads=2, seq=2 * _BLOCK, d_head=8), group)

    for causal in (False, True):
        def train_like(q_, k_, v_, _c=causal):
            def f(a, b, c):
                o, lse = flash_attention_with_lse(a, b, c, causal=_c)
                return jnp.sum(o ** 2) + jnp.sum(lse ** 2)

            return jax.grad(f, argnums=(0, 1, 2))(q_, k_, v_)

        exported = jax.export.export(
            jax.jit(train_like), platforms=["tpu"])(q, k, v)
        assert "tpu" in exported.platforms


def test_flash_on_tpu_device():
    """On-device parity vs the jnp path — runs only when a TPU is
    actually reachable (skipped on the CPU-forced CI mesh)."""
    if jax.default_backend() != "tpu":
        pytest.skip("no TPU backend in this environment")
    q, k, v = _qkv(d_head=8)

    def loss(fn):
        def f(q_, k_, v_):
            return jnp.sum(fn(q_, k_, v_) ** 2)

        return jax.jit(jax.grad(f, argnums=(0, 1, 2)))

    # mask=() forces the jnp path in mha? no — pass mask=None but call
    # the online path directly to avoid the dispatch picking the kernel
    from fmda_tpu.ops import attention as A

    def jnp_mha(q_, k_, v_):
        state = A.init_online_state(
            q_.shape[0], q_.shape[1], q_.shape[2], q_.shape[3])
        state = A.online_attention_block(state, q_, k_, v_, None)
        return A.finalize_online_state(state, q_.dtype)

    g_pal = loss(lambda a, b, c: flash_attention(a, b, c))(q, k, v)
    g_ref = loss(jnp_mha)(q, k, v)
    for a, b in zip(g_pal, g_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)


# -- grouped-query heads and the causal window (PR 28) -------------------------


def _masked_reference(q, k, v, window):
    """Explicit-mask softmax attention with repeated K/V heads."""
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    t, d = q.shape[-2], q.shape[-1]
    s = jnp.einsum("bnqd,bnkd->bnqk", q, k) / np.sqrt(d)
    rel = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    keep = rel >= 0
    if window is not None:
        keep = keep & (rel < window)
    s = jnp.where(keep, s, -jnp.inf)
    return jnp.einsum("bnqk,bnkd->bnqd", jax.nn.softmax(s, -1), v)


def _gqa_qkv(seq, heads=4, kv_heads=2, d_head=16, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    return (jax.random.normal(ks[0], (1, heads, seq, d_head)),
            jax.random.normal(ks[1], (1, kv_heads, seq, d_head)),
            jax.random.normal(ks[2], (1, kv_heads, seq, d_head)))


@pytest.mark.parametrize("seq,window", [
    (1024, None),   # 512-wide blocks, causal, grouped heads
    (1024, 300),    # the band ends inside a block
    (1024, 512),    # the band ends on a block edge
    (384, 100),     # 128-wide blocks, three of them
])
def test_gqa_window_kernel_matches_masked_attention_fwd_and_bwd(seq, window):
    from fmda_tpu.ops.pallas_attention import block_for

    assert block_for(1024) == 512 and block_for(384) == 128
    q, k, v = _gqa_qkv(seq)
    want = jax.value_and_grad(
        lambda *a: jnp.sum(_masked_reference(*a, window) ** 2), (0, 1, 2))
    got = jax.value_and_grad(
        lambda *a: jnp.sum(flash_attention(
            *a, causal=True, window=window, interpret=True) ** 2), (0, 1, 2))
    with jax.default_matmul_precision("highest"):
        w, g = want(q, k, v), got(q, k, v)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(w)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("seq,window", [(1024, None), (1024, 300),
                                        (384, 100)])
def test_the_kept_lse_is_a_column_and_both_outputs_gradients_hold(
        seq, window):
    """The backward's residual logsumexp is one float32 a head and row
    (the kernel's 128 equal lanes are rebuilt from it), and the
    gradients through both outputs, grouped heads and a window are the
    blockwise path's with a plain logsumexp beside it."""
    from fmda_tpu.ops.pallas_attention import flash_attention_with_lse

    q, k, v = _gqa_qkv(seq)
    heads = q.shape[1]

    def ref_lse(q_, k_):
        k_ = jnp.repeat(k_, heads // k_.shape[1], axis=1)
        s = jnp.einsum("bnqd,bnkd->bnqk", q_, k_) / np.sqrt(q_.shape[-1])
        rel = jnp.arange(seq)[:, None] - jnp.arange(seq)[None, :]
        keep = (rel >= 0) if window is None else (rel >= 0) & (rel < window)
        return jax.scipy.special.logsumexp(
            jnp.where(keep, s, -jnp.inf), axis=-1)

    def loss(o, lse):
        return jnp.sum(o * jnp.cos(o)) + jnp.sum(jnp.sin(lse))

    def want(q_, k_, v_):  # mha scores 512 query rows at a time here
        return loss(mha(q_, k_, v_, causal=True, window=window),
                    ref_lse(q_, k_))

    def got(q_, k_, v_):
        return loss(*flash_attention_with_lse(
            q_, k_, v_, causal=True, window=window, interpret=True))

    with jax.default_matmul_precision("highest"):
        w = jax.value_and_grad(want, (0, 1, 2))(q, k, v)
        g = jax.value_and_grad(got, (0, 1, 2))(q, k, v)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(w)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)
    # what the backward closes over: the rule's residuals
    kept = [x.shape for x in jax.tree.leaves(jax.vjp(got, q, k, v)[1])]
    assert (heads, seq) in kept            # the column
    assert (heads, seq, 128) not in kept   # never the tile


def test_window_at_least_the_sequence_is_plain_causal():
    q, k, v = _gqa_qkv(256)
    a = flash_attention(q, k, v, causal=True, interpret=True)
    b = flash_attention(q, k, v, window=256, interpret=True)
    np.testing.assert_array_equal(a, b)


def test_query_heads_must_be_a_multiple_of_kv_heads():
    q, k, v = _gqa_qkv(128, heads=4, kv_heads=3)
    with pytest.raises(ValueError, match="query heads"):
        flash_attention(q, k, v, interpret=True)


@pytest.mark.parametrize("window", [None, 200])
def test_mha_fallback_is_blockwise_and_matches_with_mask_window_and_gqa(
        window):
    """Past FALLBACK_QUERY_BLOCK query rows the non-kernel path scores a
    block of rows at a time; same numbers, with a causal window, grouped
    heads and an arbitrary mask array."""
    from fmda_tpu.ops.attention import FALLBACK_QUERY_BLOCK, mha

    seq = 2 * FALLBACK_QUERY_BLOCK
    q, k, v = _gqa_qkv(seq, d_head=8)
    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(lambda *a: jnp.sum(mha(
            *a, causal=True, window=window) ** 2), (0, 1, 2))(q, k, v)
        want = jax.value_and_grad(lambda *a: jnp.sum(_masked_reference(
            *a, window) ** 2), (0, 1, 2))(q, k, v)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)
    # an arbitrary mask array rides through the blocks too
    keep = jax.random.bernoulli(jax.random.PRNGKey(9), 0.7, (seq, seq))
    keep = keep | jnp.eye(seq, dtype=bool)
    lowered = jax.jit(lambda *a: mha(*a, mask=keep)).lower(q, k, v).as_text()
    assert f"{seq}x{seq}xf32" not in lowered  # no (T, T) scores anywhere
    with jax.default_matmul_precision("highest"):
        a = mha(q, k, v, mask=keep)
        kk, vv = jnp.repeat(k, 2, 1), jnp.repeat(v, 2, 1)
        s = jnp.where(keep, jnp.einsum("bnqd,bnkd->bnqk", q, kk)
                      / np.sqrt(8), -jnp.inf)
        b = jnp.einsum("bnqk,bnkd->bnqd", jax.nn.softmax(s, -1), vv)
    np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("kernel", [False, True], ids=["jnp", "kernel"])
@pytest.mark.parametrize("seq", [384, 1024])
def test_the_latent_core_is_plain_attention_on_the_joined_queries_and_keys(
        seq, kernel):
    """Latent attention's core: a score is a 16-wide product per head
    plus an 8-wide product against ONE rotary key shared by all the
    heads, values are 12 wide.  Through ``mha``'s jnp path and through
    the kernels (interpreter), with their value width of its own, it is
    plain causal attention written out on the joined 24-wide queries
    and keys, forward and every gradient, the shared key's summed over
    the heads."""
    heads, dn, dr, dv, scale = 4, 16, 8, 12, 0.27
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    qn = jax.random.normal(ks[0], (1, heads, seq, dn))
    qr = jax.random.normal(ks[1], (1, heads, seq, dr))
    kn = jax.random.normal(ks[2], (1, heads, seq, dn))
    kr = jax.random.normal(ks[3], (1, 1, seq, dr))  # one head
    v = jax.random.normal(ks[4], (1, heads, seq, dv))

    def joined(qn, qr, kn, kr):
        return (jnp.concatenate([qn, qr], -1), jnp.concatenate(
            [kn, jnp.broadcast_to(kr, (1, heads, seq, dr))], -1))

    def core(qn, qr, kn, kr, v):
        q, k = joined(qn, qr, kn, kr)
        if kernel:
            return flash_attention(q, k, v, causal=True, scale=scale,
                                   interpret=True)
        return mha(q, k, v, causal=True, scale=scale)

    def written_out(qn, qr, kn, kr, v):
        s = (jnp.einsum("bnqd,bnkd->bnqk", qn, kn)
             + jnp.einsum("bnqd,bkd->bnqk", qr, kr[:, 0])) * scale
        keep = jnp.arange(seq)[:, None] >= jnp.arange(seq)[None, :]
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        return jnp.einsum("bnqk,bnkd->bnqd", p, v)

    args = (qn, qr, kn, kr, v)
    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(
            lambda *a: jnp.sum(core(*a) ** 2), (0, 1, 2, 3, 4))(*args)
        want = jax.value_and_grad(
            lambda *a: jnp.sum(written_out(*a) ** 2), (0, 1, 2, 3, 4))(*args)
        assert core(*args).shape == (1, heads, seq, dv)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=3e-4, atol=3e-4)
    assert got[1][3].shape == (1, 1, seq, dr)


def test_grouped_heads_take_a_value_width_of_their_own():
    """N query heads on G key-value heads with values wider than the
    scores' width: the kernels against the jnp path, both gradients."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (2, 4, 256, 16))
    k = jax.random.normal(ks[1], (2, 2, 256, 16))
    v = jax.random.normal(ks[2], (2, 2, 256, 40))
    run = lambda fn: jax.value_and_grad(
        lambda *a: jnp.sum(fn(*a) ** 2), (0, 1, 2))(q, k, v)
    with jax.default_matmul_precision("highest"):
        got = run(lambda *a: flash_attention(*a, causal=True, window=100,
                                             interpret=True))
        want = run(lambda *a: mha(*a, causal=True, window=100))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


# -- the forward's wide key block and grouped grid step (PR 44) ----------------


def _reference_with_lse(q, k, v, *, causal, window, scale):
    """Explicit-mask softmax attention and its row logsumexp, K/V heads
    repeated; a row that sees no key gives o = 0 and lse = -1e30."""
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    t = q.shape[-2]
    s = jnp.einsum("bnqd,bnkd->bnqk", q, k) * (
        scale if scale is not None else 1.0 / np.sqrt(q.shape[-1]))
    if causal or window is not None:
        rel = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
        keep = rel >= 0
        if window is not None:
            keep = keep & (rel < window)
        s = jnp.where(keep, s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(jnp.isfinite(m), jnp.exp(s - jnp.where(
        jnp.isfinite(m), m, 0.0)), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bnqk,bnkd->bnqd", p / jnp.where(l == 0, 1.0, l), v)
    lse = jnp.where(l == 0, -1e30, jnp.where(jnp.isfinite(m), m, 0.0)
                    + jnp.log(jnp.where(l == 0, 1.0, l)))[..., 0]
    return o, lse


def _both_outputs(fn, q, k, v):
    """Outputs and the gradients of a loss through both of them."""
    def loss(q_, k_, v_):
        o, lse = fn(q_, k_, v_)
        return jnp.sum(o * jnp.cos(o)) + jnp.sum(jnp.sin(lse)), (o, lse)

    with jax.default_matmul_precision("highest"):
        (_, outs), grads = jax.value_and_grad(
            loss, (0, 1, 2), has_aux=True)(q, k, v)
    return outs + grads


#: heads, kv heads, D, Dv, causal, window, scale: the three cells' head
#: layouts at 2,048 tokens, where the forward's key block is 1,024 wide
WIDE_BLOCK_CASES = {
    "group7_d128_window_shorter": (7, 1, 128, 128, True, 512, None),
    "group7_d128_window_equal": (7, 1, 128, 128, True, 1024, None),
    "group7_d128_window_no_multiple": (7, 1, 128, 128, True, 1300, None),
    "group4_d64_causal": (8, 2, 64, 64, True, None, None),
    "group1_scores192_values128_scale": (2, 2, 192, 128, True, None, 0.0721),
    "not_causal_lse_cotangent": (4, 2, 64, 64, False, None, None),
}


@pytest.mark.parametrize("case", sorted(WIDE_BLOCK_CASES))
def test_wide_key_block_and_grouped_step_match_masked_attention(case):
    """Forward, ``lse`` and the gradients through both outputs where the
    forward runs ``(512, 1024)`` blocks with a key-value head's query
    heads in one grid step (interpreter)."""
    from fmda_tpu.ops.pallas_attention import (
        flash_attention_with_lse, fwd_blocks_for)

    heads, kv_heads, d, dv, causal, window, scale = WIDE_BLOCK_CASES[case]
    seq = 2048
    assert fwd_blocks_for(seq) == (512, 1024)
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (1, heads, seq, d))
    k = jax.random.normal(ks[1], (1, kv_heads, seq, d))
    v = jax.random.normal(ks[2], (1, kv_heads, seq, dv))
    kw = dict(causal=causal, window=window, scale=scale)
    got = _both_outputs(lambda *a: flash_attention_with_lse(
        *a, interpret=True, **kw), q, k, v)
    want = _both_outputs(
        lambda *a: _reference_with_lse(*a, **kw), q, k, v)
    for a, b, name in zip(got, want, ("o", "lse", "dq", "dk", "dv")):
        np.testing.assert_allclose(a, b, rtol=3e-4, atol=3e-4,
                                   err_msg=f"{case}: {name}")


@pytest.mark.parametrize("window", [100, 0])
def test_rows_whose_block_holds_no_visible_key_leave_the_state_alone(
        window):
    """Masked scores are ``-inf`` under a finite running maximum.  At a
    window of 100 a query block's first in-band key block (1,024 wide)
    holds no key most of its rows can see: those rows' state must pass
    through it untouched.  At a window of 0 no row sees any key: every
    row reports ``lse == -1e30`` and ``o == 0``, and the gradients are
    finite (zero)."""
    from fmda_tpu.ops.pallas_attention import flash_attention_with_lse

    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (1, 4, 2048, 16))
    k = jax.random.normal(ks[1], (1, 2, 2048, 16))
    v = jax.random.normal(ks[2], (1, 2, 2048, 16))
    got = _both_outputs(lambda *a: flash_attention_with_lse(
        *a, window=window, interpret=True), q, k, v)
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in got)
    if window == 0:
        o, lse, *grads = got
        np.testing.assert_array_equal(lse, np.full(lse.shape, -1e30, "f4"))
        for x in (o, *grads):
            np.testing.assert_array_equal(x, np.zeros(x.shape, "f4"))
        return
    want = _both_outputs(lambda *a: _reference_with_lse(
        *a, causal=True, window=window, scale=None), q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("seq,pair", [
    (8192, (512, 1024)), (4096, (512, 1024)), (2048, (512, 1024)),
    (1536, (512, 512)),   # 1,024 does not divide it: the square block
    (1024, (512, 1024)), (768, (256, 256)), (384, (128, 128)),
])
def test_forward_block_pair_follows_the_length(seq, pair):
    from fmda_tpu.ops.pallas_attention import block_for, fwd_blocks_for

    assert fwd_blocks_for(seq) == pair
    assert pair[0] == block_for(seq)  # the backward's, and the query's


@pytest.mark.parametrize("seq,heads,d,dv,itemsize,mib,ok", [
    (8192, 7, 128, 128, 2, 27.9375, True),     # smallthinker_train_8k
    (8192, 1, 192, 128, 2, 26.0625, True),     # moonlight_train_8k
    (4096, 1, 192, 128, 2, 16.0625, True),     # xing_train_4k
    (8192, 4, 64, 64, 2, 14.5, True),      # granite_h_train_8k
    (16384, 1, 128, 128, 4, 54.8125, True),   # the gate's count: inside
    (20480, 1, 128, 128, 4, 66.8125, False),  # and past the 64 MiB stated
    (32768, 1, 128, 128, 2, 69.5625, False),   # bfloat16 does not save it
])
def test_backward_residents_by_count_and_the_bound_they_set(
        seq, heads, d, dv, itemsize, mib, ok):
    """What ``flash_bwd`` keeps in VMEM, counted from the shape: the four
    cells' sizes, and the two sides of the bound ``flash_supported``
    learns from the count (one head a step, four-byte elements), said in
    ``attention:backward_not_resident`` where it refuses."""
    from fmda_tpu.ops import pallas_attention as pa
    from fmda_tpu.ops.dispatch import kernel_fallbacks, reset_kernel_fallbacks

    resident = pa._bwd_resident_bytes(seq, heads, d, dv, itemsize)
    assert resident == mib * 2 ** 20
    assert (resident <= pa._VMEM_LIMIT) == ok
    reset_kernel_fallbacks()
    assert flash_supported(seq, seq, d, dv) == (
        pa._bwd_resident_bytes(seq, 1, d, dv, 4) <= pa._VMEM_LIMIT)
    assert kernel_fallbacks() == (
        {} if seq <= 16384 else {"attention:backward_not_resident": 1})
    reset_kernel_fallbacks()
    # the rest of the gate is what it was, and counts nothing
    for t in (128, 384, 1536, 100, 8200):
        for tk in (t, 2 * t):
            for width in (8, 64, 192, 512, 513):
                assert flash_supported(t, tk, width) == (
                    t == tk and t % 128 == 0 and width <= 512), (t, tk, width)
    assert kernel_fallbacks() == {}


@pytest.mark.parametrize("group,d,dv,itemsize,heads", [
    (7, 128, 128, 2, 7),    # smallthinker_train_8k: the whole group
    (4, 64, 64, 2, 4),      # granite_h_train_8k
    (1, 192, 128, 2, 1),    # xing_train_4k, the attn family, ring steps
    (32, 128, 128, 2, 8),   # one key-value head for all: eight at a time
    (12, 128, 128, 2, 6),   # the largest divisor under the cap
    (8, 512, 512, 4, 4),    # wide float32 heads: what VMEM holds
])
def test_heads_a_forward_step(group, d, dv, itemsize, heads):
    from fmda_tpu.ops.pallas_attention import heads_a_step

    assert heads_a_step(group, 512, d, dv, itemsize) == heads


def test_a_group_wider_than_a_step_is_walked_in_parts():
    """Sixteen query heads on one key-value head: two grid steps of
    eight, each on the same keys."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (1, 16, 256, 16))
    k = jax.random.normal(ks[1], (1, 1, 256, 16))
    v = jax.random.normal(ks[2], (1, 1, 256, 16))
    with jax.default_matmul_precision("highest"):
        got = flash_attention(q, k, v, causal=True, interpret=True)
        want = _masked_reference(q, k, v, None)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("heads,kv_heads,seq,counted", [
    (7, 1, 2048, {}),                                   # group 7, wide block
    (2, 2, 1024, {}),                                   # group 1
    (4, 2, 384, {"attention:narrow_key_block": 1}),     # square blocks of 128
    (16, 1, 256, {"attention:narrow_key_block": 1,
                  "attention:group_in_parts": 1}),      # two steps of eight
])
def test_trace_time_counters_say_what_a_shape_did_not_get(
        heads, kv_heads, seq, counted):
    """``attention:narrow_key_block`` where 1,024 does not divide the
    length, ``attention:group_in_parts`` where a grid step does not hold
    the group: one tick a traced kernel, nothing at the cells' layouts."""
    from fmda_tpu.ops.dispatch import kernel_fallbacks, reset_kernel_fallbacks
    from fmda_tpu.ops.pallas_attention import _fwd_impl

    reset_kernel_fallbacks()
    shape = lambda n: jax.ShapeDtypeStruct((n, seq, 16), jnp.float32)
    # a fresh trace: eval_shape of the undecorated function
    jax.eval_shape(functools.partial(
        _fwd_impl.__wrapped__, causal=True, window=None, interpret=True),
        shape(heads), shape(kv_heads), shape(kv_heads))
    assert kernel_fallbacks() == counted
    reset_kernel_fallbacks()


# -- the backward in one sweep (PR 47) -----------------------------------------

#: heads, kv heads, seq, D, Dv, causal, window
ONE_SWEEP_CASES = {
    # three query blocks of 128 add seven heads each into a key block's rows
    "group7_three_query_blocks": (7, 1, 384, 16, 16, True, None),
    "group7_not_causal": (7, 1, 384, 16, 16, False, None),
    # one block: zeroed, added to and written in the same grid step
    "one_block_group1": (2, 2, 128, 16, 16, True, None),
    "one_block_group4_not_causal": (4, 1, 128, 16, 16, False, None),
    # a key block's rows are zeroed by its own query block and the later
    # query blocks, past the window's low edge, never add to them
    "window_inside_a_block": (4, 2, 512, 16, 16, True, 60),
    "window_of_a_block_and_a_half": (4, 2, 512, 16, 16, True, 192),
    # dk is D wide, dv Dv, both summed over a group
    "group4_values_wider": (4, 1, 256, 16, 40, True, None),
    "group4_values_narrower_window": (8, 2, 256, 24, 8, True, 100),
    # sixteen heads on one key-value head: two grid steps of eight add
    # into the one scratch, the second writes it
    "group16_in_two_parts": (16, 1, 256, 16, 16, True, None),
    "group32_in_four_parts_two_kv_heads": (32, 2, 128, 8, 8, True, 50),
}


@pytest.mark.parametrize("case", sorted(ONE_SWEEP_CASES))
def test_one_sweep_backward_matches_masked_attention(case):
    """dQ, dK and dV of ``flash_bwd`` (interpreter) through both outputs
    against explicit-mask attention with repeated key-value heads."""
    from fmda_tpu.ops.pallas_attention import flash_attention_with_lse

    heads, kv_heads, seq, d, dv, causal, window = ONE_SWEEP_CASES[case]
    ks = jax.random.split(jax.random.PRNGKey(13), 3)
    q = jax.random.normal(ks[0], (2, heads, seq, d))
    k = jax.random.normal(ks[1], (2, kv_heads, seq, d))
    v = jax.random.normal(ks[2], (2, kv_heads, seq, dv))
    kw = dict(causal=causal, window=window, scale=None)
    got = _both_outputs(lambda *a: flash_attention_with_lse(
        *a, interpret=True, **kw), q, k, v)
    want = _both_outputs(
        lambda *a: _reference_with_lse(*a, **kw), q, k, v)
    for a, b, name in zip(got, want, ("o", "lse", "dq", "dk", "dv")):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4,
                                   err_msg=f"{case}: {name}")


@pytest.mark.parametrize("group,seq,d,dv,itemsize,heads", [
    (7, 8192, 128, 128, 2, 7),     # smallthinker_train_8k: the whole group
    (4, 8192, 64, 64, 2, 4),       # granite_h_train_8k
    (1, 8192, 192, 128, 2, 1),     # moonlight_train_8k; xing_train_4k at 4,096
    (16, 2048, 128, 128, 2, 8),    # eight unrolled heads at most
    (12, 2048, 128, 128, 2, 6),    # the largest divisor under the cap
    (8, 2048, 512, 512, 4, 4),     # wide float32 heads: what VMEM holds
    (8, 16384, 128, 128, 4, 4),    # near the bound the scratches leave less
])
def test_heads_a_backward_step(group, seq, d, dv, itemsize, heads):
    from fmda_tpu.ops.pallas_attention import bwd_heads_a_step

    assert bwd_heads_a_step(group, seq, d, dv, itemsize) == heads


@pytest.mark.parametrize("heads,kv_heads,seq,counted", [
    (7, 1, 2048, {}),                                   # group 7
    (2, 2, 1024, {}),                                   # group 1
    (4, 2, 384, {}),                                    # square blocks anyway
    (16, 1, 256, {"attention:group_in_parts": 1}),      # two steps of eight
])
def test_the_backward_says_when_a_group_is_walked_in_parts(
        heads, kv_heads, seq, counted):
    from fmda_tpu.ops.dispatch import kernel_fallbacks, reset_kernel_fallbacks
    from fmda_tpu.ops.pallas_attention import _bwd_impl

    reset_kernel_fallbacks()
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    jax.eval_shape(functools.partial(
        _bwd_impl.__wrapped__, causal=True, window=None, interpret=True),
        shape(heads, seq, 16), shape(kv_heads, seq, 16),
        shape(kv_heads, seq, 16), shape(heads, seq, 16), shape(heads, seq),
        shape(heads, seq, 16))
    assert kernel_fallbacks() == counted
    reset_kernel_fallbacks()
