"""Masked attention (ops/flash_attention.py ``window=``,
``diffusion_block=``): the Pallas kernels (interpret mode) and the
blockwise path against an explicit ``[S, S]`` mask at lengths above the
window, forward and gradient; and the tiles the mask touches are the
only ones visited."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.ops.flash_attention import (_flash_bwd, _flash_fwd, _step,
                                            _tiles, blockwise_attention,
                                            flash_attention, make_flash_attn)


def _qkv(S=256, H=4, Hkv=2, D=32, B=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (B, S, H, D)),
            jax.random.normal(ks[1], (B, S, Hkv, D)),
            jax.random.normal(ks[2], (B, S, Hkv, D)))


def _dense_mask(S, window=None, diffusion_block=None):
    """[S, S] bool, written out pair by pair."""
    i, j = np.arange(S)[:, None], np.arange(S)[None, :]
    if diffusion_block is None:
        mask = i >= j
        return mask if window is None else mask & (i - j < window)
    half = S // 2
    q_noised, k_noised = i < half, j < half
    qb, kb = i % half // diffusion_block, j % half // diffusion_block
    return ((q_noised & k_noised & (qb == kb))       # its own noised block
            | (q_noised & ~k_noised & (kb < qb))     # clean blocks before it
            | (~q_noised & ~k_noised & (kb <= qb)))  # clean, causal by block


def _explicit(q, k, v, window, diffusion_block=None):
    B, S, H, D = q.shape
    g = H // k.shape[2]
    k, v = jnp.repeat(k, g, 2), jnp.repeat(v, g, 2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    mask = jnp.asarray(_dense_mask(S, window, diffusion_block))
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
    # 8192 tokens in blocks of 512: a window of 1024 touches 3 key
    # blocks a query block at most (a tile's first query sees 1023 keys
    # back: into the tile two before), the causal band all 16
    def steps(S, bq, bk, window):
        tiles = _tiles(S, bq, bk, True, window, None)
        return tiles.key_steps, tiles.query_steps

    assert steps(8192, 512, 512, 1024) == (3, 3)
    assert steps(8192, 512, 512, 1000) == (3, 3)
    assert steps(8192, 512, 512, 1026) == (4, 4)    # off the grid: one more
    assert steps(8192, 512, 512, None) == (16, 16)
    assert steps(256, 64, 64, 200) == (4, 4)        # never more than nk
    # the block-diffusion mask at the benchmark's shapes: a noised tile
    # its own and the 16 clean ones, a clean key tile all 32 query tiles
    tiles = _tiles(16384, 512, 512, True, None, 4)
    assert (tiles.key_steps, tiles.query_steps) == (17, 32)


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


# (S, diffusion block, (block_q, block_k)): one tile a half and several;
# block lengths 1, 4 and the tile's own; unequal tiles; a block longer
# than a tile and one no tile is a multiple of
DIFFUSION = [(128, 4, (64, 64)), (256, 1, (32, 32)), (256, 4, (32, 32)),
             (256, 32, (32, 32)), (256, 4, (64, 32)), (256, 4, (32, 64)),
             (256, 64, (32, 32)), (192, 12, (32, 32)), (128, 64, (64, 64))]


def _walked(tiles):
    """[nq, nk] bool: the tiles the query walk visits, and the same by
    the key walk, each tile at most once."""
    by_query = np.zeros((tiles.nq, tiles.nk), int)
    for i in range(tiles.nq):
        for t in range(tiles.key_steps):
            j, live = _step(tiles.key_tiles(i), t)
            by_query[i, int(j)] += bool(live)
    by_key = np.zeros((tiles.nq, tiles.nk), int)
    for j in range(tiles.nk):
        for t in range(tiles.query_steps):
            i, live = _step(tiles.query_tiles(j), t)
            by_key[int(i), j] += bool(live)
    assert by_query.max() <= 1 and by_key.max() <= 1
    return by_query.astype(bool), by_key.astype(bool)


@pytest.mark.parametrize("S, window, block, blocks", [
    (256, None, None, (64, 64)), (256, 96, None, (32, 64)),
    (256, 64, None, (64, 32))] + [(S, None, b, bl) for S, b, bl in DIFFUSION])
def test_the_walks_visit_exactly_the_tiles_the_mask_touches(S, window,
                                                            block, blocks):
    """Query walk and key walk against the dense mask cut into tiles: a
    tile is visited if and only if the mask lets a pair of it through,
    and the in-tile predicate is the dense mask's tile."""
    bq, bk = blocks
    tiles = _tiles(S, bq, bk, True, window, block)
    dense = _dense_mask(S, window, block)
    touched = dense.reshape(tiles.nq, bq, tiles.nk, bk).any((1, 3))
    by_query, by_key = _walked(tiles)
    np.testing.assert_array_equal(by_query, touched)
    np.testing.assert_array_equal(by_key, touched)
    assert tiles.key_steps == touched.sum(1).max()
    assert tiles.query_steps == touched.sum(0).max()
    for i, j in zip(*np.nonzero(touched)):
        np.testing.assert_array_equal(
            np.asarray(tiles.seen(i, j)),
            dense[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk])
    if block is not None:
        # L^2 + L B pairs a row and head: a quarter of all pairs and the
        # diagonal blocks
        assert dense.sum() == (S // 2) ** 2 + (S // 2) * block


@pytest.mark.parametrize("S, block, blocks", DIFFUSION)
def test_block_diffusion_matches_the_explicit_mask(S, block, blocks):
    """The three parts of the mask (a noised block sees itself and the
    clean blocks before it, a clean block the clean blocks up to itself,
    nothing a noised block of another index): kernels (interpret mode),
    blockwise walk and the public entry against the dense mask, forward
    and backward."""
    q, k, v = _qkv(S=S, B=1, seed=2)
    do = jax.random.normal(jax.random.PRNGKey(3), q.shape)
    bq, bk = blocks
    with jax.default_matmul_precision("highest"):
        want_out, vjp = jax.vjp(
            lambda q, k, v: _explicit(q, k, v, None, block), q, k, v)
        want = vjp(do)
        out, lse = _flash_fwd(q, k, v, True, bq, bk, interpret=True,
                              with_lse=True, diffusion_block=block)
        kernels = _flash_bwd(q, k, v, out, lse, do, True, bq, bk,
                             interpret=True, diffusion_block=block)
        walk, walk_vjp = jax.vjp(
            lambda q, k, v: blockwise_attention(
                q, k, v, True, block_k=bk, block_q=bq,
                diffusion_block=block), q, k, v)
        entry, entry_vjp = jax.vjp(
            lambda q, k, v: flash_attention(q, k, v, True, bq, bk, None,
                                            block), q, k, v)
    for got in (out, walk, entry):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want_out),
                                   rtol=1e-5, atol=1e-5)
    for grads in (kernels, walk_vjp(do), entry_vjp(do)):
        for g, w in zip(grads, want):
            assert g.shape == w.shape
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-4, atol=1e-5)


def test_tiles_the_block_diffusion_mask_does_not_touch_are_not_read():
    """The clean keys after a tile's last block may hold anything, NaN
    even, and so may the noised keys of other tiles: no query of the
    first tiles sees them."""
    q, k, v = _qkv(S=256, B=1)
    # tiles of 32: noised tiles 0-3, clean tiles 4-7. Noised tile 1 and
    # clean tile 5 see noised tile 1 (the first only) and clean 4, 5
    poison = np.r_[0:32, 64:128, 192:256]
    k = k.at[:, poison].set(jnp.nan)
    v = v.at[:, poison].set(jnp.nan)
    for out in (
            _flash_fwd(q, k, v, True, 32, 32, interpret=True,
                       diffusion_block=4),
            blockwise_attention(q, k, v, True, block_k=32, block_q=32,
                                diffusion_block=4)):
        assert np.all(np.isfinite(np.asarray(out[:, 32:64])))
        assert np.all(np.isfinite(np.asarray(out[:, 160:192])))


def test_a_half_the_tiles_or_the_blocks_do_not_divide_is_refused():
    q, k, v = _qkv(S=192)
    for bad in (dict(block_q=64, block_k=64, diffusion_block=4),   # 96 / 64
                dict(block_q=32, block_k=32, diffusion_block=5),   # 96 / 5
                dict(block_q=32, block_k=32, diffusion_block=4, window=8)):
        with pytest.raises(ValueError):
            blockwise_attention(q, k, v, True, **bad)
    with pytest.raises(ValueError):
        _flash_fwd(q, k, v, True, 64, 64, interpret=True, diffusion_block=4)
    with pytest.raises(ValueError):     # an odd sequence has no halves
        blockwise_attention(q[:, :191], k[:, :191], v[:, :191], True,
                            block_k=32, diffusion_block=1)


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
