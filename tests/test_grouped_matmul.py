"""The grouped product kernels of ``ops/grouped_matmul.py`` (interpret
mode: the kernels' own code on the CPU) against ``jax.lax.ragged_dot``
and against a float32 dense product a group; the expert layer with the
kernels against the layer with ``ragged_dot``; the tile function at the
benchmark's shapes; the two counters against a hand count. Real widths
for a described v5e: ``tests/test_chip_compile.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.models import moe
from byteps_tpu.ops import grouped_matmul as gm

ROWS, G = 1024, 4
TILE = 128

# loads of four groups over 1024 rows
LOADS = {
    "even": [256, 256, 256, 256],
    "one_group_at_six_times_the_mean": [48, 48, 768, 160],
    "empty_groups": [0, 300, 0, 724],
    "sum_under_the_rows": [100, 37, 0, 261],
    "group_ends_off_the_row_tile": [130, 126, 257, 511],
    "one_pair": [0, 0, 1, 0],
    "no_pair": [0, 0, 0, 0],
}
# (K, N): N an odd multiple of a lane tile, and the wider contraction
WIDTHS = [(256, 384), (384, 128)]


def _operands(K, N, dtype, seed=0):
    key = jax.random.PRNGKey(seed)
    lhs = jax.random.normal(key, (ROWS, K), jnp.float32).astype(dtype)
    rhs = (jax.random.normal(jax.random.fold_in(key, 1), (G, K, N),
                             jnp.float32) / np.sqrt(K)).astype(dtype)
    g = jax.random.normal(jax.random.fold_in(key, 2), (ROWS, N),
                          jnp.float32).astype(dtype)
    return lhs, rhs, g


def _dense(op, lhs, rhs, g, sizes):
    """The product a group in float32 numpy, rows past the groups
    zero."""
    lhs, rhs, g = (np.asarray(a, np.float32) for a in (lhs, rhs, g))
    ends = np.cumsum(sizes)
    out = {"fwd": np.zeros((ROWS, rhs.shape[2]), np.float32),
           "dlhs": np.zeros(lhs.shape, np.float32),
           "drhs": np.zeros(rhs.shape, np.float32)}[op]
    for e, (lo, hi) in enumerate(zip(ends - sizes, ends)):
        if op == "fwd":
            out[lo:hi] = lhs[lo:hi] @ rhs[e]
        elif op == "dlhs":
            out[lo:hi] = g[lo:hi] @ rhs[e].T
        else:
            out[e] = lhs[lo:hi].T @ g[lo:hi]
    return out


def _kernel(op, lhs, rhs, g, sizes, tile=TILE):
    if op == "fwd":
        return gm._gmm(lhs, rhs, sizes, tile, interpret=True)
    if op == "dlhs":
        return gm._gmm(g, rhs, sizes, tile, transpose_rhs=True,
                       interpret=True)
    return gm._tgmm(lhs, g, sizes, tile, interpret=True)


def _ragged(op, lhs, rhs, g, sizes):
    if op == "fwd":
        return jax.lax.ragged_dot(lhs, rhs, sizes)
    _, vjp = jax.vjp(lambda l, r: jax.lax.ragged_dot(l, r, sizes), lhs, rhs)
    return vjp(g)[op == "drhs"]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("K,N", WIDTHS, ids=lambda v: str(v))
@pytest.mark.parametrize("loads", LOADS)
@pytest.mark.parametrize("op", ["fwd", "dlhs", "drhs"])
def test_a_kernel_is_the_product_a_group(op, loads, K, N, dtype):
    """Each kernel on the rows of its groups (every row of the
    transposed product's output) is ``ragged_dot`` and the float32
    dense product a group, to the operands' rounding; what the rows
    past the groups hold is the caller's to mask."""
    sizes = np.array(LOADS[loads], np.int32)
    total = int(sizes.sum())
    lhs, rhs, g = _operands(K, N, dtype)
    live = (jnp.arange(ROWS) < total)[:, None]
    got = np.asarray(_kernel(op, lhs, rhs, g, jnp.asarray(sizes)),
                     np.float32)
    ragged = np.asarray(_ragged(op, lhs, rhs, jnp.where(live, g, 0),
                                jnp.asarray(sizes)), np.float32)
    dense = _dense(op, lhs, rhs, g, sizes)
    keep = slice(None) if op == "drhs" else slice(0, total)
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(got[keep], dense[keep], **tol)
    np.testing.assert_allclose(got[keep], ragged[keep], **tol)
    if op == "drhs":
        empty = sizes == 0
        assert not got[empty].any()


@pytest.mark.parametrize("tile", [128, 256, 512])
@pytest.mark.parametrize("op", ["fwd", "dlhs", "drhs"])
def test_rows_past_the_groups_are_never_read_back(op, tile):
    """Not-a-numbers in every operand's rows past the last group reach
    no row of a group and no group's sum, whatever the row tile: those
    rows share tiles with the last group's."""
    sizes = np.array(LOADS["sum_under_the_rows"], np.int32)
    total = int(sizes.sum())
    lhs, rhs, g = _operands(256, 384, jnp.float32)
    dense = _dense(op, lhs, rhs, g, sizes)
    past = (jnp.arange(ROWS) >= total)[:, None]
    got = np.asarray(_kernel(op, jnp.where(past, jnp.nan, lhs), rhs,
                             jnp.where(past, jnp.nan, g),
                             jnp.asarray(sizes), tile))
    keep = slice(None) if op == "drhs" else slice(0, total)
    np.testing.assert_allclose(got[keep], dense[keep], rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("loads", LOADS)
@pytest.mark.parametrize("tile,visit_empty", [(128, False), (256, True),
                                              (512, False)])
def test_work_items_are_the_tiles_each_group_touches(loads, tile,
                                                     visit_empty):
    """``work_items`` against an enumeration by hand: a group's items
    are the row tiles its rows lie in, in order; a tile two groups
    share appears under each; an empty group has one item only where
    the transposed product asks for it; ``visited_rows`` is the items'
    rows."""
    sizes = np.array(LOADS[loads], np.int32)
    ends = np.cumsum(sizes)
    want = []
    for e, (lo, hi) in enumerate(zip(ends - sizes, ends)):
        if hi > lo:
            want += [(e, t) for t in range(lo // tile, (hi - 1) // tile + 1)]
        elif visit_empty:
            want.append((e, min(lo // tile, ROWS // tile - 1)))
    offsets, group_of, tile_of, count = gm.work_items(
        jnp.asarray(sizes), ROWS, tile, visit_empty)
    assert group_of.shape == tile_of.shape == (ROWS // tile + G - 1,)
    assert int(count) == len(want)
    assert list(zip(np.asarray(group_of)[:len(want)].tolist(),
                    np.asarray(tile_of)[:len(want)].tolist())) == want
    np.testing.assert_array_equal(np.asarray(offsets), [0, *ends])
    if not visit_empty:
        assert int(gm.visited_rows(jnp.asarray(sizes), ROWS, tile)) \
            == len(want) * tile


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation of a jaxpr, inner jaxprs too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


def test_every_kernel_call_sits_under_the_scope():
    """The product and both kernels of its backward are BOUND under
    ``ragged-dot.bps``: Mosaic names a call's HLO instruction by the
    innermost scope at that moment, and the benchmark's readers sum the
    families that start with ``ragged-dot`` (a scope left before the
    call is made names the kernel by whatever encloses it)."""
    lhs, rhs, g = _operands(256, 384, jnp.float32)
    sizes = jnp.asarray(LOADS["even"], jnp.int32)

    def both_ways(lhs, rhs, g):
        out, vjp = jax.vjp(
            lambda l, r: gm._product(l, r, sizes, TILE, True), lhs, rhs)
        return out, vjp(g)

    calls = list(_pallas_calls(jax.make_jaxpr(both_ways)(lhs, rhs, g).jaxpr))
    assert len(calls) == 3
    for eqn in calls:
        innermost = eqn.source_info.name_stack.stack[-1]
        assert getattr(innermost, "name", None) == gm.SCOPE, innermost


def test_the_tiles_at_the_two_cells_shapes(monkeypatch):
    """``row_tile`` and ``slab_columns`` at the benchmark's shapes (a
    slice's compact buffer and its full-size one, Mellum's 2304 x 896
    and LFM2's 2048 x 1792 both ways), pinned: 256 rows, an expert's
    weights whole in VMEM; off the TPU, and for widths or rows the
    tiles do not divide, the product is ``ragged_dot``'s."""
    assert gm.row_tile(16384, 2304, 896) is None              # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for rows, K, N in ((16384, 2304, 896), (65536, 2304, 896),
                       (16384, 2048, 1792), (32768, 2048, 1792)):
        assert gm.row_tile(rows, K, N) == gm.row_tile(rows, N, K) == 256
        assert gm.slab_columns(K, N) == N and gm.slab_columns(N, K) == K
    # Mixtral's expert does not fit: columns in tiles, the depth whole
    assert gm.slab_columns(4096, 14336) == 1024
    assert gm.row_tile(128 * 3, 128, 256) == 128
    assert gm.row_tile(16384, 2304, 900) is None
    assert gm.row_tile(16384 + 16, 2304, 896) is None


@pytest.fixture
def kernels_on_the_cpu(monkeypatch):
    """The dispatch as on the chip, the kernels interpreted."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    product = gm._product
    monkeypatch.setattr(
        gm, "_product",
        lambda lhs, rhs, sizes, tile, interpret: product(
            lhs, rhs, sizes, tile, True))


def _layer(held=2, seed=3):
    """One layer holding experts 2 .. 2 + held - 1 of 16 at lane-tile
    widths, 512 tokens in two slices, two experts a token."""
    cfg = moe.MoEConfig(vocab_size=64, dim=128, n_layers=1, n_heads=2,
                        n_kv_heads=2, n_experts=16, top_k=2,
                        expert_hidden=256, max_seq_len=256, remat=False)
    p = {k: v[0] for k, v in moe.init_params(
        jax.random.PRNGKey(seed), cfg)["blocks"].items()}
    p = {"router": p["router"],
         **{k: p[k][2:2 + held] for k in moe.EXPERT_LEAVES}}
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, 256, cfg.dim),
                          jnp.float32)
    return cfg, p, x


def _run_layer(cfg, p, x):
    def loss(p_, x_):
        out, st = moe.moe_layer(x_, p_, cfg.top_k, jnp.float32, first=2,
                                chunk=256)
        return jnp.sum(out * jnp.cos(out)), (out, st)

    (_, (out, st)), grads = jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True)(p, x)
    return out, st, grads


@pytest.mark.parametrize("held", [2, 8], ids=["compact", "full_size"])
def test_the_layer_is_the_same_with_the_kernels_and_with_ragged_dot(
        held, request):
    """``moe_layer``'s output and every gradient, through the compact
    buffer (2 of 16 held) and through the full-size one (8 of 16: one
    buffer), with the package's kernels and with ``ragged_dot``."""
    cfg, p, x = _layer(held)
    want_out, want_st, want_grads = _run_layer(cfg, p, x)
    assert int(want_st["kernel_slices"]) == 0
    assert int(want_st["kernel_tile_rows"]) == 0
    request.getfixturevalue("kernels_on_the_cpu")
    out, st, grads = _run_layer(cfg, p, x)
    assert int(st["kernel_slices"]) == 2
    assert int(st["compact_slices"]) == (2 if held == 2 else 0)
    np.testing.assert_array_equal(np.asarray(st["load"]),
                                  np.asarray(want_st["load"]))
    assert int(st["dropped"]) == 0
    np.testing.assert_allclose(np.asarray(out), np.asarray(want_out),
                               rtol=1e-4, atol=1e-5)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        top = np.abs(np.asarray(b)).max()
        assert top > 0
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5 * top)


@pytest.mark.parametrize("held", [2, 8], ids=["compact", "full_size"])
def test_the_kernels_counters_against_a_hand_count(held, kernels_on_the_cpu):
    """``kernel_slices`` is every slice (with ``compact_slices +
    full_slices`` the engagement share, 1.0); ``kernel_tile_rows`` is,
    a slice, the row tiles its groups touch in the buffer the layer
    walked, times the tile's rows, counted here from the router's own
    choice."""
    cfg, p, x = _layer(held)
    out, st = moe.moe_layer(x, p, cfg.top_k, jnp.float32, first=2, chunk=256)
    _, idx, _ = moe.route(x.reshape(-1, cfg.dim), p["router"], cfg.top_k)
    pairs = 256 * cfg.top_k
    rows = moe.compact_rows(pairs, held, cfg.n_experts)
    assert rows == (128 if held == 2 else pairs)
    tile = gm.row_tile(rows, cfg.dim, cfg.expert_hidden)
    want = 0
    for chosen in np.asarray(idx).reshape(2, pairs) - 2:
        sizes = np.array([(chosen == e).sum() for e in range(held)])
        assert sizes.sum() <= rows
        ends = np.cumsum(sizes)
        want += tile * sum((hi - 1) // tile - lo // tile + 1
                           for lo, hi in zip(ends - sizes, ends) if hi > lo)
    assert int(st["kernel_slices"]) == 2 \
        == int(st["compact_slices"]) + int(st["full_slices"])
    assert int(st["kernel_tile_rows"]) == want
    assert want >= int(st["load"].sum())
