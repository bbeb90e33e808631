"""Grouped matrix product for the expert layer (models/moe.py): rows
sorted by group, one weight matrix a group.

``grouped_matmul(lhs [rows, K], rhs [G, K, N], group_sizes [G])`` gives
``lhs[start_g:end_g] @ rhs[g]`` for every group ``g`` (its rows are the
``group_sizes[g]`` after those of the groups before it). Rows past the
last group belong to no group and are left unwritten: whoever calls
masks them, as after ``jax.lax.ragged_dot``.

On the TPU the product and both transposes of its backward are Pallas
kernels of this package, after ``jax.experimental.pallas.ops.tpu
.megablox``: the groups' offsets are scalar prefetch, the grid walks
work items (row tile, group), a row tile that two groups share is
visited once for each under a row mask, and row tiles past the last
group are never visited. What differs is the tiling, for groups of some
hundreds to a few thousand rows against weights of a few megabytes
(``row_tile``, ``slab_columns``): the whole contraction and as many columns as fit
sit in VMEM, so a group's weights are read once and reused by its row
tiles, and no partial sum is carried through a K loop. bfloat16 operands
go to the MXU, sums are float32 over the whole contraction, the output
is in the operands' type: the arithmetic of ``ragged_dot``.

Off the TPU (tier-1, the CPU mesh), and for shapes the tiling does not
cover, the call IS ``jax.lax.ragged_dot``, as ``ops/flash_attention.py``
falls back to its blockwise path.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

# Every call of the kernels sits under this ``jax.named_scope``: Mosaic
# names the call's HLO instruction by it, and a reduced device trace
# sums the grouped products by the family name ``ragged-dot`` (XLA's own
# kernel is ``ragged-dot-none.<n>``), so the name has to begin with it
# (docs/timeline.md "Device scopes").
SCOPE = "ragged-dot.bps"

_LANES = 128
# one group's [K, columns] slab of bfloat16 weights held in VMEM (twice:
# the next group's is fetched under the current one's products)
_SLAB_BYTES = 8 << 20
_VMEM_LIMIT = 64 << 20
# columns of one MXU call inside a kernel: the float32 product of a row
# tile by that many columns is what a kernel holds besides its blocks
_CHUNK = 4 * _LANES


def _divisor(n: int, most: int) -> int:
    """The largest multiple of a lane tile that divides ``n`` and is at
    most ``most`` (``n`` is a multiple of a lane tile)."""
    units = n // _LANES
    return _LANES * max(u for u in range(1, units + 1)
                        if units % u == 0 and u * _LANES <= max(most, _LANES))


def slab_columns(depth: int, width: int) -> int:
    """Columns of a group's weights held in VMEM at once, the whole
    contraction ``depth`` deep: all ``width`` where that is at most
    ``_SLAB_BYTES`` of bfloat16 (both cells' experts), else the largest
    lane-tile divisor that is; the grid then walks the column tiles
    outermost, re-reading the rows for each."""
    return _divisor(width, _SLAB_BYTES // (2 * depth))


def row_tile(rows: int, K: int, N: int) -> Optional[int]:
    """The kernels' row tile for ``[rows, K] x [G, K, N]``, from the
    static shapes alone, or None where the product is ``ragged_dot``'s:
    off the TPU, widths that are no multiple of a lane tile, or rows
    that neither tile divides.

    256 rows where they divide the buffer's: measured on the chip at
    both sparse cells' shapes (PERF.md section 6, PR 32), tiles of 128,
    256 and 512 rows lie within 5 % of each other for groups of 700 to
    8,000 rows (a larger tile feeds the MXU longer per weight tile, a
    smaller one wastes less on the tiles two groups share), and 256 is
    the best or within 3 % of it at every load the cells see."""
    if jax.default_backend() != "tpu" or K % _LANES or N % _LANES:
        return None
    return next((t for t in (256, 128) if rows % t == 0), None)


@functools.partial(jax.jit, static_argnames=("rows", "tile", "visit_empty"))
def work_items(group_sizes: jnp.ndarray, rows: int, tile: int,
               visit_empty: bool = False):
    """The grid's work items for row tiles of ``tile`` rows: (offsets
    [G + 1], group_of [items], tile_of [items], count). Item ``i <
    count`` is row tile ``tile_of[i]`` under group ``group_of[i]``'s
    mask; a group's items are consecutive and in row order, a tile two
    groups share appears once for each. ``items`` is the static bound
    ``rows / tile + G - 1``; ``count`` is what the routing needs.
    ``visit_empty``: an empty group gets one item (the transposed
    product has that group's output to zero)."""
    G = group_sizes.shape[0]
    tiles = rows // tile
    ends = jnp.minimum(jnp.cumsum(group_sizes.astype(jnp.int32)), rows)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    starts, sizes = offsets[:-1], ends - offsets[:-1]
    first = jnp.minimum(starts // tile, tiles - 1)
    n = jnp.where(sizes > 0, (ends - 1) // tile - first + 1,
                  1 if visit_empty else 0)
    item_end = jnp.cumsum(n)
    i = jnp.arange(tiles + G - 1, dtype=jnp.int32)
    group_of = jnp.minimum(
        jnp.sum(item_end[None, :] <= i[:, None], axis=1), G - 1)
    tile_of = jnp.minimum(first[group_of] + i - (item_end - n)[group_of],
                          tiles - 1)
    return (offsets, group_of.astype(jnp.int32), tile_of.astype(jnp.int32),
            item_end[-1])


def visited_rows(group_sizes: jnp.ndarray, rows: int, tile: int
                 ) -> jnp.ndarray:
    """Rows of the row tiles the product visits for these groups (int32
    scalar): beside ``sum(group_sizes)`` it is the tiles' occupancy."""
    return work_items(group_sizes, rows, tile)[3] * tile


def _group_rows(offsets, group_of, tile_of, i, tile: int, width: int):
    """[tile, width] bool: the rows of work item ``i``'s tile that lie
    in its group."""
    g = group_of[i]
    row = tile_of[i] * tile + jax.lax.broadcasted_iota(
        jnp.int32, (tile, width), 0)
    return (row >= offsets[g]) & (row < offsets[g + 1])


def _zeroed(x, keep):
    """``x`` in float32 with the rows outside ``keep`` zeroed (a select:
    whatever those rows hold never reaches a sum)."""
    return jnp.where(keep, x.astype(jnp.float32), 0.0)


def _call(kernel, operands, grid, in_specs, out_specs, out_shape, interpret,
          scratch_shapes=()):
    """The ``pallas_call`` of both kernels, made under ``SCOPE``: the
    work items' three vectors as scalar prefetch, the column tiles
    outermost, the items in order inside."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    with jax.named_scope(SCOPE):
        return pl.pallas_call(
            kernel, out_shape=out_shape,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3, grid=grid, in_specs=in_specs,
                out_specs=out_specs, scratch_shapes=scratch_shapes),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=interpret)(*operands)


def _row_block(n, i, offsets, group_of, tile_of):
    """Index of an operand's [tile, whole width] block for work item
    ``i``."""
    return tile_of[i], 0


def _row_col_block(n, i, offsets, group_of, tile_of):
    return tile_of[i], n


def _gmm_kernel(offsets, group_of, tile_of, lhs_ref, rhs_ref, out_ref, *,
                tile: int, chunk: int, transpose_rhs: bool):
    """One work item: the row tile times the group's weights, the whole
    contraction at once, the group's rows stored and the others kept
    (a tile's items are consecutive, so it is still in VMEM)."""
    import jax.experimental.pallas as pl

    keep = _group_rows(offsets, group_of, tile_of, pl.program_id(1), tile,
                       chunk)
    lhs = lhs_ref[...]
    for c in range(0, out_ref.shape[1], chunk):
        if transpose_rhs:
            w, dims = rhs_ref[c:c + chunk, :], (((1,), (1,)), ((), ()))
        else:
            w, dims = rhs_ref[:, c:c + chunk], (((1,), (0,)), ((), ()))
        acc = jax.lax.dot_general(lhs, w, dims,
                                  preferred_element_type=jnp.float32)
        out_ref[:, c:c + chunk] = jnp.where(
            keep, acc, out_ref[:, c:c + chunk].astype(jnp.float32)
        ).astype(out_ref.dtype)


# jitted, as megablox's are (and ``work_items`` above): a step program
# holds some hundred calls of a dozen shapes, and each shape's kernel is
# then traced and lowered once, not once a call (set-up time)
@functools.partial(jax.jit,
                   static_argnames=("tile", "transpose_rhs", "interpret"))
def _gmm(lhs, rhs, group_sizes, tile: int, transpose_rhs: bool = False,
         interpret: bool = False):
    """``lhs [rows, K] x rhs [G, K, N] -> [rows, N]`` a group, or with
    ``transpose_rhs`` ``lhs [rows, N] x rhs [G, K, N]^T -> [rows, K]``
    (the product's transpose by its weights, the weights read as they
    lie)."""
    import jax.experimental.pallas as pl

    rows, depth = lhs.shape
    width = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    cols = slab_columns(depth, width)
    offsets, group_of, tile_of, count = work_items(group_sizes, rows, tile)
    if transpose_rhs:
        rhs_spec = pl.BlockSpec(
            (None, cols, depth), lambda n, i, off, g, t: (g[i], n, 0))
    else:
        rhs_spec = pl.BlockSpec(
            (None, depth, cols), lambda n, i, off, g, t: (g[i], 0, n))
    return _call(
        functools.partial(_gmm_kernel, tile=tile,
                          chunk=_divisor(cols, _CHUNK),
                          transpose_rhs=transpose_rhs),
        (offsets, group_of, tile_of, lhs, rhs.astype(lhs.dtype)),
        (width // cols, count),
        [pl.BlockSpec((tile, depth), _row_block), rhs_spec],
        pl.BlockSpec((tile, cols), _row_col_block),
        jax.ShapeDtypeStruct((rows, width), lhs.dtype), interpret)


def _tgmm_kernel(offsets, group_of, tile_of, lhs_ref, rhs_ref, out_ref,
                 acc_ref, *, tile: int, chunk: int):
    """One work item of the transposed product: the group's rows of the
    tile, ``lhs^T rhs``, added to the group's float32 sum, which is
    zeroed at the group's first item and written at its last."""
    import jax.experimental.pallas as pl

    i, last = pl.program_id(1), pl.num_programs(1) - 1
    g = group_of[i]

    @pl.when((i == 0) | (group_of[jnp.maximum(i - 1, 0)] != g))
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(offsets[g + 1] > offsets[g])
    def _add():
        keep = functools.partial(_group_rows, offsets, group_of, tile_of, i,
                                 tile)
        # transposed in float32, as megablox does
        lhs_t = _zeroed(lhs_ref[...], keep(lhs_ref.shape[1])).swapaxes(
            0, 1).astype(lhs_ref.dtype)
        keep_rhs = keep(chunk)
        for c in range(0, acc_ref.shape[1], chunk):
            rhs = _zeroed(rhs_ref[:, c:c + chunk], keep_rhs)
            acc_ref[:, c:c + chunk] += jax.lax.dot_general(
                lhs_t, rhs.astype(rhs_ref.dtype), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when((i == last) | (group_of[jnp.minimum(i + 1, last)] != g))
    def _write():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _tgmm(lhs, rhs, group_sizes, tile: int, interpret: bool = False):
    """``lhs [rows, K]^T rhs [rows, N] -> [G, K, N]`` a group (the
    product's transpose by its rows): float32 sums over a group's rows,
    written in the operands' type; an empty group's output is zero."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, K = lhs.shape
    N = rhs.shape[1]
    cols = slab_columns(K, N)
    offsets, group_of, tile_of, count = work_items(
        group_sizes, rows, tile, visit_empty=True)
    return _call(
        functools.partial(_tgmm_kernel, tile=tile,
                          chunk=_divisor(cols, _CHUNK)),
        (offsets, group_of, tile_of, lhs, rhs),
        (N // cols, count),
        [pl.BlockSpec((tile, K), _row_block),
         pl.BlockSpec((tile, cols), _row_col_block)],
        pl.BlockSpec((None, K, cols), lambda n, i, off, g, t: (g[i], 0, n)),
        jax.ShapeDtypeStruct((group_sizes.shape[0], K, N), lhs.dtype),
        interpret, scratch_shapes=[pltpu.VMEM((K, cols), jnp.float32)])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _product(lhs, rhs, group_sizes, tile: int, interpret: bool):
    return _gmm(lhs, rhs, group_sizes, tile, interpret=interpret)


def _product_fwd(lhs, rhs, group_sizes, tile, interpret):
    return (_gmm(lhs, rhs, group_sizes, tile, interpret=interpret),
            (lhs, rhs, group_sizes))


def _product_bwd(tile, interpret, res, g):
    lhs, rhs, group_sizes = res
    g = g.astype(lhs.dtype)
    d_lhs = _gmm(g, rhs, group_sizes, tile, transpose_rhs=True,
                 interpret=interpret)
    d_rhs = _tgmm(lhs, g, group_sizes, tile, interpret=interpret)
    return d_lhs, d_rhs.astype(rhs.dtype), None


_product.defvjp(_product_fwd, _product_bwd)


def grouped_matmul(lhs: jnp.ndarray, rhs: jnp.ndarray,
                   group_sizes: jnp.ndarray) -> jnp.ndarray:
    """``lhs [rows, K] x rhs [G, K, N] -> [rows, N]``, each group's rows
    by its own matrix; differentiable in ``lhs`` and ``rhs``. See the
    module's head for which kernel runs where."""
    tile = row_tile(lhs.shape[0], *rhs.shape[1:])
    if tile is None:
        return jax.lax.ragged_dot(lhs, rhs, group_sizes)
    return _product(lhs, rhs, group_sizes, tile, False)
