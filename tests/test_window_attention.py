"""Masked attention (ops/flash_attention.py ``window=``,
``diffusion_block=``): the Pallas kernels (interpret mode) and the
blockwise path against an explicit ``[S, S]`` mask at lengths above the
window, forward and gradient; the tiles the mask touches are the only
ones visited; and the kernels' work lists hold those tiles, each once
and in order, and change no bit of a plain fold's result."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.ops.flash_attention import (_flash_bwd, _flash_fwd, _step,
                                            _tiles, blockwise_attention,
                                            flash_attention, latent_attention,
                                            make_flash_attn,
                                            publish_walk_sizes, walk_sizes)


def _qkv(S=256, H=4, Hkv=2, D=32, B=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (B, S, H, D)),
            jax.random.normal(ks[1], (B, S, Hkv, D)),
            jax.random.normal(ks[2], (B, S, Hkv, D)))


def _dense_mask(S, window=None, diffusion_block=None, rows=None):
    """[S, S] bool, written out pair by pair (``rows``: those query
    positions only)."""
    i = (np.arange(S) if rows is None else rows)[:, None]
    j = np.arange(S)[None, :]
    if diffusion_block is None:
        mask = i >= j
        return mask if window is None else mask & (i - j < window)
    half = S // 2
    q_noised, k_noised = i < half, j < half
    qb, kb = i % half // diffusion_block, j % half // diffusion_block
    return ((q_noised & k_noised & (qb == kb))       # its own noised block
            | (q_noised & ~k_noised & (kb < qb))     # clean blocks before it
            | (~q_noised & ~k_noised & (kb <= qb)))  # clean, causal by block


def _explicit(q, k, v, window, diffusion_block=None, shared=None):
    """``shared``: ``(q_r [B,S,H,Dr], k_r [B,S,1,Dr])``, a second part of
    the score against ONE key head under every query head."""
    B, S, H, D = q.shape
    g = H // k.shape[2]
    k, v = jnp.repeat(k, g, 2), jnp.repeat(v, g, 2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k)
    if shared is not None:
        s = s + jnp.einsum("bqhd,bkd->bhqk", shared[0], shared[1][:, :, 0])
        D += shared[0].shape[-1]
    s = s / np.sqrt(D)
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


# (S, diffusion block, (block_q, block_k)): one tile a half and several;
# block lengths 1, 4 and the tile's own; unequal tiles; a block longer
# than a tile and one no tile is a multiple of
DIFFUSION = [(128, 4, (64, 64)), (256, 1, (32, 32)), (256, 4, (32, 32)),
             (256, 32, (32, 32)), (256, 4, (64, 32)), (256, 4, (32, 64)),
             (256, 64, (32, 32)), (192, 12, (32, 32)), (128, 64, (64, 64))]


def _dense_tiles(S, window, block, bq, bk):
    """([nq, nk] bool, the same): the tiles of the dense mask that hold
    a pair it lets through, and those it lets through whole; a row of
    tiles at a time (the cells' masks are 2 ** 28 pairs)."""
    rows = [_dense_mask(S, window, block, np.arange(i, i + bq))
            .reshape(bq, S // bk, bk) for i in range(0, S, bq)]
    return (np.stack([r.any((0, 2)) for r in rows]),
            np.stack([r.all((0, 2)) for r in rows]))


# (S, window, diffusion block, (block_q, block_k), query heads a key
# head): the shapes the walks' test has, and the three cells' (SDAR's
# two copies of 8,192 positions, Mellum's causal and window layers,
# LFM2's four heads a group), tile 512
LISTS = [(256, None, None, (64, 64), 2), (256, 96, None, (32, 64), 2),
         (256, 64, None, (64, 32), 1), (256, 200, None, (64, 64), 4)] \
    + [(S, None, b, bl, 2) for S, b, bl in DIFFUSION] \
    + [(16384, None, 4, (512, 512), 8), (8192, None, None, (512, 512), 8),
       (8192, 1024, None, (512, 512), 8), (8192, None, None, (512, 512), 4)]


def _runs(held):
    """(first, last) flags of the runs of equal entries of ``held``."""
    edge = held[1:] != held[:-1]
    return np.r_[True, edge], np.r_[edge, True]


@pytest.mark.parametrize("S, window, block, blocks, groups", LISTS)
def test_the_work_lists_hold_exactly_the_live_tiles(S, window, block,
                                                    blocks, groups):
    """The kernels' grid axes: the query walk holds exactly the tiles
    the dense mask touches, each once, by query tile and then key tile
    ascending; the key walk the same tiles by key tile, head of the
    group and query tile; ``first`` / ``last`` bracket each output
    tile's run; ``tested`` is set where the dense tile holds a barred
    pair and only there; no item is dead."""
    bq, bk = blocks
    tiles = _tiles(S, bq, bk, True, window, block)
    touched, whole = _dense_tiles(S, window, block, bq, bk)
    grid = np.indices(touched.shape)
    np.testing.assert_array_equal(tiles.whole(*grid), whole)

    walk = tiles.query_walk
    want_q, want_k = np.nonzero(touched)         # row-major: by i, then j
    np.testing.assert_array_equal(walk.q_tile, want_q)
    np.testing.assert_array_equal(walk.k_tile, want_k)
    first, last = _runs(want_q)
    np.testing.assert_array_equal(walk.first, first)
    np.testing.assert_array_equal(walk.last, last)
    np.testing.assert_array_equal(walk.tested, ~whole[want_q, want_k])
    assert not walk.head.any()
    assert first.sum() == last.sum() == tiles.nq    # no tile without a run

    # the key walk is the query walk transposed, once a head
    key = tiles.key_walk(groups)
    t_k, t_q = np.nonzero(touched.T)             # by j, then i
    order = np.lexsort((np.tile(t_q, groups), np.repeat(np.arange(groups),
                                                        t_k.size),
                        np.tile(t_k, groups)))
    np.testing.assert_array_equal(key.k_tile, np.tile(t_k, groups)[order])
    np.testing.assert_array_equal(key.q_tile, np.tile(t_q, groups)[order])
    np.testing.assert_array_equal(
        key.head, np.repeat(np.arange(groups), t_k.size)[order])
    first, last = _runs(key.k_tile)
    np.testing.assert_array_equal(key.first, first)
    np.testing.assert_array_equal(key.last, last)
    np.testing.assert_array_equal(key.tested, ~whole[key.q_tile, key.k_tile])
    assert first.sum() == tiles.nk
    assert all(c.dtype == np.int32 for c in (*walk, *key))
    # what a rectangular grid would have walked
    assert tiles.key_steps == touched.sum(1).max()
    assert tiles.query_steps == touched.sum(0).max()
    if window is None and block is None:
        # latent attention walks the causal mask's lists, entry for entry
        latent = _tiles(S, bq, bk, True, None, None, True)
        assert latent.scope == "bps.attn.mla" != tiles.scope
        for mine, its in ((walk, latent.query_walk),
                          (key, latent.key_walk(groups))):
            for a, b in zip(mine, its):
                np.testing.assert_array_equal(a, b)


# (scope, S, window, diffusion block, query heads a key head): the
# kernels' calls in the three cells, tile 512
CELLS = [("bps.attn.blockdiff", 16384, None, 4, 8),     # sdar-30b-a3b
         ("bps.attn.full", 8192, None, None, 8),        # mellum2-12b
         ("bps.attn.window", 8192, 1024, None, 8),
         ("bps.attn.full", 8192, None, None, 4),        # lfm2-8b-a1b
         ("bps.attn.mla", 8192, None, None, 1)]         # joyai-llm-flash


@pytest.mark.parametrize("scope, S, window, block, groups", CELLS)
def test_the_gauges_count_the_dense_masks_tiles(scope, S, window, block,
                                                groups):
    """``walk_sizes`` at a cell's shape, and the gauges
    ``publish_walk_sizes`` sets from it: work items, those that take the
    in-tile test and the steps of a rectangular grid as long as the
    longest walk, a row and head under the query walk and a row and key
    head under the key walk, each counted here on the DENSE mask's tiles
    (SDAR: 288 items where the grid had 544 steps, 2,304 where it had
    8,192)."""
    from byteps_tpu.core.state import get_state

    touched, whole = _dense_tiles(S, window, block, 512, 512)
    live, tested = int(touched.sum()), int((touched & ~whole).sum())
    assert 0 < tested < live < touched.size
    want = {"items/query_walk": live, "tested_items/query_walk": tested,
            "rect_steps/query_walk":
            touched.shape[0] * int(touched.sum(1).max()),
            "items/key_walk": groups * live,
            "tested_items/key_walk": groups * tested,
            "rect_steps/key_walk":
            touched.shape[1] * groups * int(touched.sum(0).max())}
    assert want["items/query_walk"] < want["rect_steps/query_walk"]
    want = {f"attention/{scope}/{name}": n for name, n in want.items()}
    # latent attention: the causal mask's lists under a scope of its own
    latent = scope == "bps.attn.mla"
    assert walk_sizes(S, groups, 512, 512, window, block, latent) == want
    registry = get_state().metrics
    for name in want:
        registry.gauge(name).set(-1)
    publish_walk_sizes(S, groups, 512, 512, window, block, latent)
    gauges = registry.instruments()[1]
    assert {name: gauges[name].value for name in want} == want


def test_each_kernels_grid_is_as_long_as_its_list():
    """No dead step: the three kernels' grids end at their lists' last
    items."""
    q, k, v = _qkv(S=256, B=1)                    # 4 heads on 2
    tiles = _tiles(256, 32, 32, True, None, 4)

    def grids(fn, *args):
        return [eqn.params["grid_mapping"].grid
                for eqn in jax.make_jaxpr(fn)(*args).eqns
                if eqn.primitive.name == "pallas_call"]

    fwd = functools.partial(_flash_fwd, causal=True, block_q=32, block_k=32,
                            interpret=True, with_lse=True, diffusion_block=4)
    assert grids(fwd, q, k, v) == [(1, 4, tiles.query_walk.first.size)]
    out, lse = fwd(q, k, v)
    assert grids(
        lambda *a: _flash_bwd(*a, True, 32, 32, interpret=True,
                              diffusion_block=4), q, k, v, out, lse, q) \
        == [(1, 2, tiles.key_walk(2).first.size),
            (1, 4, tiles.query_walk.first.size)]
    assert tiles.query_walk.first.size < tiles.nq * tiles.key_steps


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
# LFM2 configuration's attention), beside the 128 the kernels met first;
# and latent attention's shape (JoyAI-LLM-Flash's at a quarter and at
# an eighth): a score in two parts, ``D`` a head its own and ``Dr``
# against ONE key head shared by all, over values of ``Dv``, a width
# that is neither ``D`` nor ``D + Dr``; unequal tiles
@pytest.mark.parametrize("D, H, Hkv, window, latent, blocks", [
    (64, 8, 2, None, None, (64, 64)), (64, 8, 2, 96, None, (64, 64)),
    (128, 8, 2, None, None, (64, 64)),
    (32, 8, 8, None, (16, 32), (64, 64)), (16, 4, 4, None, (8, 24), (32, 64)),
    (16, 4, 4, None, (8, 24), (128, 32)), (32, 4, 2, None, (16, 8), (64, 64))])
def test_kernels_match_blockwise_attention_at_the_head_size(
        D, H, Hkv, window, latent, blocks):
    """The Pallas forward, dK/dV and dQ kernels (interpret mode) against
    ``blockwise_attention`` and its vjp, forward and backward, and
    against the explicit mask; ``latent`` ``(Dr, Dv)``: the score's
    shared part and the values' width (the shared key's gradient is then
    the sum over the heads: one ``[B, S, 1, Dr]`` cotangent)."""
    bq, bk = blocks
    q, k, v = _qkv(S=256, H=H, Hkv=Hkv, D=D, seed=4)
    shared = None
    if latent is not None:
        Dr, Dv = latent
        ks = jax.random.split(jax.random.PRNGKey(6), 3)
        v = jax.random.normal(ks[0], (*k.shape[:3], Dv))
        shared = (jax.random.normal(ks[1], (*q.shape[:3], Dr)),
                  jax.random.normal(ks[2], (q.shape[0], 256, 1, Dr)))
    do = jax.random.normal(jax.random.PRNGKey(5), (*q.shape[:3], v.shape[-1]))

    def blockwise(q, k, v, *shared):
        return blockwise_attention(
            q, k, v, causal=True, block_k=bk, window=window, block_q=bq,
            shared=shared or None)

    with jax.default_matmul_precision("highest"):
        out, lse = _flash_fwd(q, k, v, True, bq, bk, interpret=True,
                              window=window, with_lse=True, shared=shared)
        got = _flash_bwd(q, k, v, out, lse, do, True, bq, bk, window,
                         interpret=True, shared=shared)
        want_out, vjp = jax.vjp(blockwise, q, k, v, *(shared or ()))
        want = vjp(do)
        flat, flat_vjp = jax.vjp(
            lambda q, k, v, *shared: _explicit(q, k, v, window,
                                               shared=shared or None),
            q, k, v, *(shared or ()))
        flat_grads = flat_vjp(do)
    assert out.shape == do.shape and len(got) == (3 if shared is None else 5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want_out),
                               rtol=1e-5, atol=1e-5)
    for g, w, f in zip(got, want, flat_grads):
        assert g.shape == w.shape == f.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(g), np.asarray(f),
                                   rtol=1e-4, atol=2e-5)
    # the scale is the score's own: 1 / 8 at 64, 1 / sqrt(D + Dr)
    np.testing.assert_allclose(np.asarray(out), np.asarray(flat),
                               rtol=1e-5, atol=1e-5)
    if shared is None:
        return
    # the shared key is ONE head's array, and so is its gradient
    assert got[4].shape == (q.shape[0], 256, 1, latent[0])
    # ``latent_attention`` off the TPU: the blockwise walk both ways
    with jax.default_matmul_precision("highest"):
        again, vjp = jax.vjp(
            lambda q, q_r, k, k_r, v: latent_attention(q, q_r, k, k_r, v,
                                                       bq, bk),
            q, shared[0], k, shared[1], v)
        grads = vjp(do)
    np.testing.assert_allclose(np.asarray(again), np.asarray(flat),
                               rtol=1e-5, atol=1e-5)
    for g, f in zip(grads, (flat_grads[i] for i in (0, 3, 1, 4, 2))):
        np.testing.assert_allclose(np.asarray(g), np.asarray(f),
                                   rtol=1e-4, atol=2e-5)


# --------------------------------------------------------------------- #
# the work lists and the body without the in-tile test change no bit
# --------------------------------------------------------------------- #

_dot = functools.partial(jax.lax.dot_general,
                         preferred_element_type=jnp.float32)
_ROWS, _COLS, _BOTH_ROWS = (((1,), (0,)), ((), ())), \
    (((1,), (1,)), ((), ())), (((0,), (0,)), ((), ()))


@jax.jit
def _fold_tile(q, k, v, seen, m, l, acc, scale):
    """One tile of the plain forward fold, the in-tile test applied."""
    s = jnp.where(seen, _dot(q, k, _COLS) * scale, -1e30)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m - m_new)
    return (m_new, l * corr + jnp.sum(p, axis=-1, keepdims=True),
            acc * corr + _dot(p.astype(v.dtype), v, _ROWS))


@jax.jit
def _grad_tile(q, k, v, o, do, lse, seen, scale):
    """(p, ds) of one tile of the plain backward, the test applied."""
    s = jnp.where(seen, _dot(q, k, _COLS) * scale, -1e30)
    p = jnp.exp(s - lse)
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1, keepdims=True)
    return p, p * (_dot(do, v, _COLS) - delta) * scale


def _plain_fold(q, k, v, do, bq, bk, window, block):
    """(out, row logsumexp, dq, dk, dv) by loops over rows, heads and
    the tiles the DENSE mask touches, ascending, ``seen`` (the dense
    mask's tile) applied on every one: float32 state, the kernels'
    arithmetic written out a tile at a time, nothing of ``_Tiles``."""
    B, S, H, D = q.shape
    g = H // k.shape[2]
    scale = 1.0 / np.sqrt(D)
    dense = _dense_mask(S, window, block)
    nq, nk = S // bq, S // bk
    live = dense.reshape(nq, bq, nk, bk).any((1, 3))

    def rows(i, n):
        return slice(i * n, (i + 1) * n)

    def seen(i, j):
        return jnp.asarray(dense[rows(i, bq), rows(j, bk)])

    out = np.zeros(q.shape, np.float32)
    lse = np.zeros((B, H, S, 1), np.float32)
    for b, h, i in np.ndindex(B, H, nq):
        m, l, acc = jnp.full((bq, 1), -1e30), jnp.zeros((bq, 1)), \
            jnp.zeros((bq, D))
        for j in np.flatnonzero(live[i]):
            m, l, acc = _fold_tile(q[b, rows(i, bq), h],
                                   k[b, rows(j, bk), h // g],
                                   v[b, rows(j, bk), h // g], seen(i, j),
                                   m, l, acc, scale)
        out[b, rows(i, bq), h] = acc / jnp.maximum(l, 1e-30)
        lse[b, h, rows(i, bq)] = m + jnp.log(jnp.maximum(l, 1e-30))
    out = jnp.asarray(out).astype(q.dtype)

    def grads(b, h, i, j):
        return _grad_tile(q[b, rows(i, bq), h], k[b, rows(j, bk), h // g],
                          v[b, rows(j, bk), h // g], out[b, rows(i, bq), h],
                          do[b, rows(i, bq), h],
                          jnp.asarray(lse[b, h, rows(i, bq)]), seen(i, j),
                          scale)

    dq = np.zeros(q.shape, np.float32)
    dk, dv = np.zeros(k.shape, np.float32), np.zeros(v.shape, np.float32)
    for b, h, i in np.ndindex(B, H, nq):
        acc = jnp.zeros((bq, D))
        for j in np.flatnonzero(live[i]):
            kb = k[b, rows(j, bk), h // g]
            acc = acc + _dot(grads(b, h, i, j)[1].astype(kb.dtype), kb, _ROWS)
        dq[b, rows(i, bq), h] = acc
    # a key tile: the group's heads in order, a head's query tiles in order
    for b, kvh, j in np.ndindex(B, k.shape[2], nk):
        dk_acc, dv_acc = jnp.zeros((bk, D)), jnp.zeros((bk, D))
        for h in range(kvh * g, kvh * g + g):
            for i in np.flatnonzero(live[:, j]):
                p, ds = grads(b, h, i, j)
                qt, dot_ = q[b, rows(i, bq), h], do[b, rows(i, bq), h]
                dv_acc = dv_acc + _dot(p.astype(dot_.dtype), dot_, _BOTH_ROWS)
                dk_acc = dk_acc + _dot(ds.astype(qt.dtype), qt, _BOTH_ROWS)
        dk[b, rows(j, bk), kvh], dv[b, rows(j, bk), kvh] = dk_acc, dv_acc
    return (out, jnp.asarray(lse[..., 0]), jnp.asarray(dq).astype(q.dtype),
            jnp.asarray(dk).astype(k.dtype), jnp.asarray(dv).astype(v.dtype))


@pytest.mark.parametrize("window, block, blocks", [
    (None, 4, (32, 32)), (96, None, (32, 64)), (None, None, (64, 64))])
def test_the_lists_and_the_untested_body_change_no_bit(window, block, blocks):
    """Output, row logsumexp and the three gradients of the kernels
    (interpret mode; two query heads a key head) are BITWISE those of a
    plain fold over the dense mask's live tiles that applies the
    in-tile test on every tile: leaving the test out where the mask
    lets a tile through whole, and walking a list, move nothing.

    At head size 16, whose scale 1/4 is a power of two. XLA's CPU
    backend contracts ``dot * scale - max`` into one fused multiply-add
    where no select stands between the two, a rounding the chip's
    vector unit does not make (on the v5e the kernels equal the
    rectangular-grid ones bit for bit at head sizes 64 and 128: PERF.md,
    PR 40); with an exact product the contraction moves nothing and the
    comparison holds the algorithm alone."""
    bq, bk = blocks
    q, k, v = _qkv(S=256, B=1, H=4, Hkv=2, D=16, seed=6)
    do = jax.random.normal(jax.random.PRNGKey(7), q.shape)
    out, lse = _flash_fwd(q, k, v, True, bq, bk, interpret=True,
                          window=window, with_lse=True,
                          diffusion_block=block)
    got = (out, lse[..., 0]) + _flash_bwd(
        q, k, v, out, lse, do, True, bq, bk, window, interpret=True,
        diffusion_block=block)
    tiles = _tiles(256, bq, bk, True, window, block)
    assert 0 < tiles.query_walk.tested.sum() < tiles.query_walk.tested.size
    for name, g, w in zip(("out", "lse", "dq", "dk", "dv"), got,
                          _plain_fold(q, k, v, do, bq, bk, window, block)):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert bool(jnp.array_equal(g, w)), name
