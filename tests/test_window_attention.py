"""Sliding-window attention (ops/flash_attention.py ``window=``): the
Pallas forward kernel (interpret mode) and the blockwise path against an
explicit ``[S, S]`` mask at lengths above the window, forward and
gradient; and the band's key blocks are the only ones visited."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.ops.flash_attention import (_band_blocks, _band_q_blocks,
                                            _flash_bwd, _flash_fwd,
                                            blockwise_attention,
                                            flash_attention, make_flash_attn)


def _qkv(S=256, H=4, Hkv=2, D=32, B=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (B, S, H, D)),
            jax.random.normal(ks[1], (B, S, Hkv, D)),
            jax.random.normal(ks[2], (B, S, Hkv, D)))


def _explicit(q, k, v, window):
    B, S, H, D = q.shape
    g = H // k.shape[2]
    k, v = jnp.repeat(k, g, 2), jnp.repeat(v, g, 2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    mask = i >= j
    if window is not None:
        mask = mask & (i - j < window)
    p = jax.nn.softmax(jnp.where(mask, s, -1e30), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


# a window off the block grid, one on it, one wider than most of the
# sequence, and none (the causal band)
WINDOWS = [96, 64, 200, None]


@pytest.mark.parametrize("window", WINDOWS)
def test_kernel_matches_the_explicit_mask(window):
    q, k, v = _qkv()
    with jax.default_matmul_precision("highest"):
        got = _flash_fwd(q, k, v, True, 64, 64, interpret=True,
                         window=window)
        want = _explicit(q, k, v, window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("blocks", [(64, 64), (128, 32), (32, 128)])
@pytest.mark.parametrize("window", WINDOWS)
def test_blockwise_matches_the_explicit_mask(window, blocks):
    q, k, v = _qkv()
    bq, bk = blocks
    with jax.default_matmul_precision("highest"):
        got = blockwise_attention(q, k, v, True, block_k=bk, window=window,
                                  block_q=bq)
        want = _explicit(q, k, v, window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("window", WINDOWS)
def test_gradient_matches_the_explicit_mask(window):
    """``flash_attention``'s backward (the blockwise path, query blocks
    outside) against the gradient through the explicit mask."""
    q, k, v = _qkv()

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    with jax.default_matmul_precision("highest"):
        got = jax.grad(loss(lambda q, k, v: flash_attention(
            q, k, v, True, 64, 64, window)), (0, 1, 2))(q, k, v)
        want = jax.grad(loss(lambda q, k, v: _explicit(q, k, v, window)),
                        (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("blocks", [(64, 64), (128, 32), (32, 128)])
@pytest.mark.parametrize("window", WINDOWS)
def test_backward_kernels_match_the_explicit_mask(window, blocks):
    """The Pallas dK/dV and dQ kernels (interpret mode), from the
    forward kernel's output and row logsumexp, against the explicit
    mask's vjp: grouped heads summed into their key head, unequal tiles."""
    q, k, v = _qkv()
    do = jax.random.normal(jax.random.PRNGKey(9), q.shape)
    bq, bk = blocks
    with jax.default_matmul_precision("highest"):
        out, lse = _flash_fwd(q, k, v, True, bq, bk, interpret=True,
                              window=window, with_lse=True)
        got = _flash_bwd(q, k, v, out, lse, do, True, bq, bk, window,
                         interpret=True)
        _, vjp = jax.vjp(lambda q, k, v: _explicit(q, k, v, window),
                         q, k, v)
        want = vjp(do)
    assert lse.shape == (2, 4, 256, 128)
    np.testing.assert_allclose(np.asarray(lse[..., 0]),
                               np.asarray(lse[..., 77]))
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-5)


def test_band_is_as_wide_as_the_window_not_the_sequence():
    # 8192 tokens in blocks of 512: a window of 1024 touches 4 key
    # blocks a query block at most, the causal band all 16
    assert _band_blocks(8192, 512, 512, 1024) == 4
    assert _band_blocks(8192, 512, 512, None) == 16
    assert _band_blocks(256, 64, 64, 200) == 4      # never more than nk
    assert _band_q_blocks(8192, 512, 512, 1024) == 4
    assert _band_q_blocks(8192, 512, 512, None) == 16


def test_key_blocks_outside_the_band_are_not_read():
    """Keys outside every query's window may hold anything, NaN even:
    a path that multiplied them by zero would spread it."""
    q, k, v = _qkv(S=256)
    window = 64
    # query block 3 (rows 192..255) sees keys 129..255: poison 0..63
    k = k.at[:, :64].set(jnp.nan)
    v = v.at[:, :64].set(jnp.nan)
    for out in (
            _flash_fwd(q, k, v, True, 64, 64, interpret=True,
                       window=window),
            blockwise_attention(q, k, v, True, block_k=64, window=window,
                                block_q=64)):
        assert np.all(np.isfinite(np.asarray(out[:, 128:])))


def test_a_window_needs_causal_and_binds_as_attn_impl():
    q, k, v = _qkv(S=64)
    with pytest.raises(ValueError):
        blockwise_attention(q, k, v, causal=False, window=16)
    with pytest.raises(ValueError):
        _flash_fwd(q, k, v, False, 16, 16, interpret=True, window=16)
    with jax.default_matmul_precision("highest"):
        out = make_flash_attn(block_q=16, block_k=16, window=16)(q, k, v)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(_explicit(q, k, v, 16)),
                                   rtol=1e-5, atol=1e-5)


# head size 64, half a lane tile, four query heads a key head (the
# LFM2 configuration's attention), beside the 128 the kernels met first
@pytest.mark.parametrize("D, H, Hkv, window", [
    (64, 8, 2, None), (64, 8, 2, 96), (128, 8, 2, None)])
def test_kernels_match_blockwise_attention_at_the_head_size(D, H, Hkv,
                                                            window):
    """The Pallas forward, dK/dV and dQ kernels (interpret mode) against
    ``blockwise_attention`` and its vjp, forward and backward."""
    q, k, v = _qkv(S=256, H=H, Hkv=Hkv, D=D, seed=4)
    do = jax.random.normal(jax.random.PRNGKey(5), q.shape)
    with jax.default_matmul_precision("highest"):
        out, lse = _flash_fwd(q, k, v, True, 64, 64, interpret=True,
                              window=window, with_lse=True)
        got = _flash_bwd(q, k, v, out, lse, do, True, 64, 64, window,
                         interpret=True)
        want_out, vjp = jax.vjp(
            lambda q, k, v: blockwise_attention(
                q, k, v, causal=True, block_k=64, window=window,
                block_q=64), q, k, v)
        want = vjp(do)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want_out),
                               rtol=1e-5, atol=1e-5)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-5)
    # the scale is the head's own: 1 / 8 at 64
    flat = _explicit(q, k, v, window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(flat),
                               rtol=1e-5, atol=1e-5)
