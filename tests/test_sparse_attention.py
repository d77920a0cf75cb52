"""Learned-sparse attention's three steps (fmda_tpu/ops/sparse_attention.py
and its kernels under the Pallas interpreter): the selection is exactly
``lax.top_k``'s, ties included; with ``topk >= T`` the layer is causal
attention; the kernels give what the ``jax.numpy`` path gives, forward
and backward."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fmda_tpu.ops import pallas_sparse_attention as kernels
from fmda_tpu.ops import sparse_attention as sa
from fmda_tpu.ops.attention import mha

B, HI, DI = 2, 2, 8
N, G, D = 4, 2, 16


def _indexer(t, seed=0, ties=True):
    """Indexer inputs; quantised to halves so that equal scores abound."""
    rng = np.random.default_rng(seed)
    q = rng.normal(size=(B, HI, t, DI))
    k = rng.normal(size=(B, t, DI))
    w = rng.normal(size=(B, t, HI))
    if ties:
        q, k, w = (np.round(a * 2) / 2 for a in (q, k, w))
    return tuple(jnp.asarray(a, jnp.float32) for a in (q, k, w))


def _top_k_mask(q, k, w, topk):
    """The selection by ``lax.top_k`` over the masked score row (ties to
    the lower index are ``top_k``'s own rule)."""
    t = k.shape[1]
    col, row = jnp.arange(t)[None], jnp.arange(t)[:, None]
    out = np.zeros((B, t, t), bool)
    for b in range(B):
        s = sa.index_scores_block(q[b], k[b], w[b])
        s = jnp.where(s == 0.0, 0.0, s)  # -0.0 and +0.0 are one score
        _, idx = jax.lax.top_k(jnp.where(col <= row, s, -jnp.inf),
                               min(topk, t))
        idx = np.asarray(idx)
        for i in range(t):
            out[b, i, idx[i, :min(i + 1, topk)]] = True
    return out


def _qkv(t, seed=1, n=N, g=G):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(B, n, t, D)), jnp.float32),
            jnp.asarray(rng.normal(size=(B, g, t, D)), jnp.float32),
            jnp.asarray(rng.normal(size=(B, g, t, D)), jnp.float32))


@pytest.mark.parametrize("t,topk", [(40, 8), (128, 24), (1024, 96)])
@pytest.mark.parametrize("ties", [True, False])
def test_the_selection_is_exactly_top_k(t, topk, ties):
    q, k, w = _indexer(t, ties=ties)
    got = np.asarray(sa.select_keys(q, k, w, topk)[0]) != 0
    want = _top_k_mask(q, k, w, topk)
    if ties:  # the construction really makes equal scores
        s = np.asarray(sa.index_scores_block(q[0], k[0], w[0]))
        assert any(len(np.unique(s[i, :i + 1])) < i + 1 for i in range(t))
    np.testing.assert_array_equal(got, want)
    kept = got.sum(axis=-1)
    np.testing.assert_array_equal(
        kept, np.broadcast_to(np.minimum(np.arange(t) + 1, topk), (B, t)))
    assert not np.triu(got, 1).any()  # nothing above the diagonal


@pytest.mark.parametrize("t,topk", [(128, 24), (256, 300), (4096, 64)])
def test_the_selection_kernels_pick_what_the_jnp_path_picks(t, topk):
    """Index-score and counting-selection kernels under the interpreter;
    4,096 rows are two score chunks and two column tiles."""
    q, k, w = _indexer(t, seed=2)
    want, kept = sa.select_keys(q[:1], k[:1], w[:1], topk)
    got, got_kept = sa.select_keys(q[:1], k[:1], w[:1], topk,
                                    interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(got_kept.sum()) == int(kept.sum()) == int(
        np.minimum(np.arange(t) + 1, topk).sum())


def test_with_topk_at_least_t_the_layer_is_causal_attention():
    t = 64
    q, k, w = _indexer(t)
    picked, _ = sa.select_keys(q, k, w, t)
    np.testing.assert_array_equal(
        np.asarray(picked) != 0, np.broadcast_to(np.tril(np.ones((t, t), bool)),
                                                 (B, t, t)))
    qq, kk, vv = _qkv(t)
    with jax.default_matmul_precision("highest"):
        got = sa.sparse_mha(qq, kk, vv, picked)
        want = mha(qq, kk, vv, causal=True)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def _first_block_unpicked(picked, width=256):
    """Every row past the first ``width`` keys loses its picks among
    them (a row of the forward's first wide key block sees none: its
    maximum stays the floor and its sum 0 until a later block), and
    keeps its own position."""
    t = picked.shape[-1]
    row, col = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    return jnp.where((row >= width) & (col < width), 0, picked) | (
        row == col).astype(picked.dtype)


def _with_an_empty_row(picked, row=5):
    """One query with no key at all: ``o`` 0, ``lse`` the floor, and no
    gradient through it."""
    return picked.at[:, row].set(0)


@pytest.mark.parametrize("t,topk,blocks,fwd_blocks,edit,group,kv_heads", [
    (128, 16, (32, 128), None, None, 2, 2),
    (256, 24, (64, 128), None, None, 2, 2),
    (512, 600, (128, 256), None, None, 2, 2),
    # the forward's pair is its own: a key block wider than half the
    # sequence over a backward of narrower ones
    (512, 48, (128, 128), (128, 512), None, 2, 2),
    (512, 600, (64, 256), (128, 512), None, 2, 2),
    (1024, 96, None, None, None, 2, 2),    # the pairs that follow from T
    (512, 48, (128, 128), (128, 256), _first_block_unpicked, 2, 2),
    (512, 48, (64, 128), (128, 512), _with_an_empty_row, 2, 2),
    # the one backward sweep: a key block's rows of the sequence-long
    # dk / dv scratch added to by eight query blocks, eight heads each
    (512, 48, (64, 128), None, None, 8, 1),
    (256, 300, (32, 256), None, None, 8, 1),  # a sequence of one key block
    # key-value heads back to back: a head's dk / dv rows hold only what
    # that head put there (each key block zeroed where the head first
    # sees it), an empty query row among them
    (384, 32, (128, 128), None, _with_an_empty_row, 2, 4),
])
def test_the_attention_kernels_match_the_jnp_path(t, topk, blocks,
                                                  fwd_blocks, edit, group,
                                                  kv_heads):
    """Forward, dQ, dK, dV of the masked flash kernels against ``mha``
    under the same mask; a query block shorter than a key block, a row
    whose first key blocks hold no pick, grouped heads; the backward's
    one sweep with dk / dv held for the whole sequence."""
    q, k, w = _indexer(t, seed=3, ties=False)
    picked, _ = sa.select_keys(q, k, w, topk)
    if edit is not None:
        picked = edit(picked)
    qq, kk, vv = _qkv(t, n=group * kv_heads, g=kv_heads)

    def via(fn):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v))), (0, 1, 2))

    with jax.default_matmul_precision("highest"):
        want = via(lambda q, k, v: sa.sparse_mha(q, k, v, picked))(
            qq, kk, vv)
        got = via(lambda q, k, v: kernels.sparse_attention(
            q, k, v, picked, blocks=blocks, fwd_blocks=fwd_blocks,
            interpret=True))(qq, kk, vv)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(
            a, b, rtol=1e-4, atol=1e-5 * float(jnp.abs(b).max()) + 1e-6)


@pytest.mark.parametrize("edit", [
    None, functools.partial(_first_block_unpicked, width=1024),
    _with_an_empty_row])
def test_the_forwards_own_blocks_change_only_the_order_of_float32_sums(edit):
    """``o`` and the packed ``lse`` tile of the forward's default pair
    (a 1,024-key block, the row sum a lane tile wide until the end)
    against the backward's pair in the same kernel: the same float32
    mathematics, summed in another order."""
    t = 2048
    assert kernels.fwd_blocks_for(t) == (256, 1024)
    assert kernels.blocks_for(t) == (256, 512)
    assert kernels.fwd_blocks_for(512) == kernels.blocks_for(512)
    assert kernels.fwd_blocks_for(1536) == kernels.blocks_for(1536)
    q, k, w = _indexer(t, seed=4, ties=False)
    picked, _ = sa.select_keys(q[:1], k[:1], w[:1], 96)
    if edit is not None:
        picked = edit(picked)
    qq, kk, vv = _qkv(t)  # one sequence, as _fwd_impl takes it
    qq, kk, vv = qq[0].reshape(G, N // G, t, D), kk[0], vv[0]
    with jax.default_matmul_precision("highest"):
        o, lse = kernels._fwd_impl(qq, kk, vv, picked,
                                   blocks=kernels.fwd_blocks_for(t),
                                   interpret=True)
        o_ref, lse_ref = kernels._fwd_impl(qq, kk, vv, picked,
                                           blocks=kernels.blocks_for(t),
                                           interpret=True)
    np.testing.assert_allclose(lse, lse_ref, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(o, o_ref, rtol=1e-5, atol=1e-6)
    if edit is _with_an_empty_row:
        assert not np.asarray(o)[:, :, 5].any()
        assert (np.asarray(lse)[:, 5] == kernels._NEG).all()


def test_the_kernels_gate_names_what_they_cannot_tile():
    assert kernels.sparse_supported(16384, 8, 128)
    # the backward keeps a key-value head's dk and dv for the whole
    # sequence in VMEM: 57.75 MiB by count at 16,384 x 128 with float32
    # blocks, inside the kernels' limit, and 105.75 at 32,768
    assert kernels._bwd_resident_bytes(16384, 8, 128) == 57.75 * 2 ** 20
    assert not kernels.sparse_supported(32768, 8, 128)
    assert kernels.sparse_supported(32768, 8, 64)        # half the rows' width
    assert not kernels.sparse_supported(16384, 3, 128)   # 128 % group
    assert not kernels.sparse_supported(1000, 8, 128)    # T % 128
    with pytest.raises(ValueError, match="sparse_supported"):
        kernels.sparse_attention(*_qkv(40), jnp.ones((B, 40, 40), jnp.int8))
    assert not sa.kernels_dispatch(16384, 8, 128, use_kernels=True)  # CPU
