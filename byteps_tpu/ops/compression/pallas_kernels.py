"""Pallas TPU kernels for the hot codec paths.

The reference's codecs are CPU OpenMP loops over host shared memory
(byteps/common/compressor/impl/*.cc); here the pack/unpack runs on the TPU's
vector unit so compressed push_pull never leaves the device (SURVEY.md §2.2
TPU note). The jnp implementations in codecs.py remain the reference
semantics (and the CPU-test path); these kernels are drop-in replacements
dispatched on TPU.

Layout: Mosaic cannot reshape the lane (last, 128-wide) dimension, so onebit
packs sign bits across the *sublane* dimension: input viewed as rows of 128
lanes; 32 consecutive rows fold into one uint32 row. Element i lives at
row i//128, lane i%128; its bit is bit (row % 32) of word
[row//32, lane]. Pack and unpack share this layout, so decompressed values
are identical to the jnp codec's (+/-scale per element) even though the
word order on the wire differs; the C++ PS mirror must use this same layout
when summing payloads natively.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_FOLD = 32                      # rows folded into one uint32 row
_BLOCK_WORD_ROWS = 8            # uint32 rows per grid step
_BLOCK_ROWS = _FOLD * _BLOCK_WORD_ROWS  # = 256 input rows per grid step


def _onebit_pack_kernel(x_ref, bits_ref):
    x = x_ref[:]                                    # (256, 128) f32
    signs = (x >= 0).astype(jnp.int32)
    grouped = signs.reshape(_BLOCK_WORD_ROWS, _FOLD, _LANES)
    # Mosaic has no unsigned reductions: accumulate in int32 (distinct
    # powers of two; the 1<<31 wraparound is benign) and bitcast after.
    weights = (jnp.int32(1) << jax.lax.broadcasted_iota(
        jnp.int32, (1, _FOLD, 1), 1))
    packed = jnp.sum(grouped * weights, axis=1, dtype=jnp.int32)
    bits_ref[:] = pltpu.bitcast(packed, jnp.uint32)


def _onebit_unpack_kernel(bits_ref, scale_ref, out_ref):
    bits = pltpu.bitcast(bits_ref[:], jnp.int32)    # (8, 128)
    expanded = bits[:, None, :] >> jax.lax.broadcasted_iota(
        jnp.int32, (1, _FOLD, 1), 1)
    on = (expanded & 1).astype(jnp.float32)         # (8, 32, 128)
    signs = on * 2.0 - 1.0
    out_ref[:] = signs.reshape(_BLOCK_ROWS, _LANES) * scale_ref[0]


def _padded_rows(n: int) -> int:
    rows = (n + _LANES - 1) // _LANES
    return (rows + _BLOCK_ROWS - 1) // _BLOCK_ROWS * _BLOCK_ROWS


@functools.partial(jax.jit, static_argnums=(1,))
def onebit_pack(x: jnp.ndarray, interpret: bool = False):
    """Flat f32 [n] -> bits uint32[(rows//32) * 128] (scaling is the
    caller's job — see OnebitCodec).

    Sign convention matches OnebitCodec/onebit.cc:34-66; padding elements
    are 0 -> bit 1, sliced away by unpack.
    """
    n = x.shape[0]
    rows = _padded_rows(n)
    padded = jnp.zeros((rows * _LANES,), jnp.float32).at[:n].set(x)
    x2d = padded.reshape(rows, _LANES)

    bits = pl.pallas_call(
        _onebit_pack_kernel,
        out_shape=jax.ShapeDtypeStruct((rows // _FOLD, _LANES), jnp.uint32),
        grid=(rows // _BLOCK_ROWS,),
        in_specs=[pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((_BLOCK_WORD_ROWS, _LANES), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(x2d)
    return bits.reshape(-1)


@functools.partial(jax.jit, static_argnums=(2, 3))
def onebit_unpack(bits: jnp.ndarray, scale: jnp.ndarray, n: int,
                  interpret: bool = False) -> jnp.ndarray:
    """(bits, scale, n) -> flat f32 [n] of +/-scale (inverts onebit_pack)."""
    word_rows = bits.shape[0] // _LANES
    bits2d = bits.reshape(word_rows, _LANES)
    rows = word_rows * _FOLD
    scale_arr = jnp.full((1,), scale, jnp.float32)

    out = pl.pallas_call(
        _onebit_unpack_kernel,
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), jnp.float32),
        grid=(word_rows // _BLOCK_WORD_ROWS,),
        in_specs=[
            pl.BlockSpec((_BLOCK_WORD_ROWS, _LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(bits2d, scale_arr)
    return out.reshape(-1)[:n]


# ------------------------------------------------------------------ #
# counter-based RNG codecs: dithering + randomk
#
# The per-element cost of these codecs is the murmur3 counter RNG plus
# the quantization arithmetic (reference: impl/dithering.cc:25-80,
# impl/randomk.cc:24-60 — OpenMP host loops). Here both fuse into one
# VPU pass: the uniform is derived in-register from the element's global
# index (rng.py np_uniform_parallel semantics, bit-exact), so compress
# reads x once and writes the levels once — no separate RNG pass or
# materialized uniforms in HBM.
# ------------------------------------------------------------------ #

_GOLDEN = 0x9E3779B1  # counter stride, must match rng.np_uniform_parallel


def _kernel_uniform(gidx_u32):
    """murmur3-finalizer uniform in [0,1) from a uint32 counter; bit-exact
    with rng.jnp_uniform_parallel because it calls the same rng helper
    (base already folded into the counter by the caller)."""
    from .rng import mm3_finalize
    h = mm3_finalize(gidx_u32)
    # Mosaic has no uint32->f32 cast; the top-24-bit value fits int32, so
    # bitcast and convert from there (exact for [0, 2^24))
    h24 = pltpu.bitcast(h >> jnp.uint32(8), jnp.int32)
    return h24.astype(jnp.float32) / float(1 << 24)


def _global_counter(base_u32, block_rows: int):
    """uint32 counter i*GOLDEN + base for each element of this grid block
    (row-major global element index)."""
    rid = jax.lax.broadcasted_iota(jnp.uint32, (block_rows, _LANES), 0)
    lid = jax.lax.broadcasted_iota(jnp.uint32, (block_rows, _LANES), 1)
    gidx = (jnp.uint32(pl.program_id(0)) * jnp.uint32(block_rows) + rid) \
        * jnp.uint32(_LANES) + lid
    return gidx * jnp.uint32(_GOLDEN) + base_u32


def _dither_linear_kernel(x_ref, fparams_ref, base_ref, out_ref):
    x = x_ref[:]
    norm, s = fparams_ref[0], fparams_ref[1]
    u = _kernel_uniform(_global_counter(base_ref[0], _BLOCK_ROWS))
    # identical op order to DitheringCodec.compress (linear) so levels
    # stay bit-equal: scaled = |x|/norm; pos = scaled*s; stochastic round
    pos = (jnp.abs(x) / norm) * s
    floor = jnp.floor(pos)
    level = floor + (u < (pos - floor)).astype(jnp.float32)
    level = jnp.minimum(level, s)
    out_ref[:] = (jnp.sign(x) * level).astype(jnp.int8)


def _dither_natural_kernel(x_ref, fparams_ref, base_ref, out_ref):
    x = x_ref[:]
    norm = fparams_ref[0]
    u = _kernel_uniform(_global_counter(base_ref[0], _BLOCK_ROWS))
    scaled = jnp.abs(x) / norm
    safe = jnp.maximum(scaled, 1e-30)
    j = jnp.clip(jnp.floor(-jnp.log2(safe)), 0.0, 30.0)
    low = jnp.exp2(-j - 1.0)
    high = jnp.exp2(-j)
    frac = (scaled - low) / (high - low)
    exp = jnp.where(u < frac, j, j + 1.0)
    # literal 2^-31: a scalar jnp.exp2 constant trips Mosaic's math-dialect
    # lowering (it expects a vector operand)
    level = jnp.where(scaled < jnp.float32(2.0 ** -31), 0.0, exp + 1.0)
    level = jnp.clip(level, 0.0, 126.0)
    out_ref[:] = (jnp.sign(x) * level).astype(jnp.int8)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def dithering_levels(x: jnp.ndarray, norm: jnp.ndarray, base: jnp.ndarray,
                     s: int, partition: str = "linear",
                     interpret: bool = False) -> jnp.ndarray:
    """Fused stochastic quantization: flat f32 [n] -> int8 signed levels
    [n]. ``norm`` is the (max or l2) scale computed by the caller; ``base``
    is the uint32 RNG base (seed-state low word XOR step) so the uniforms
    bit-match jnp_uniform_parallel(seed, n, mix=step)."""
    n = x.shape[0]
    rows = _padded_rows(n)
    padded = jnp.zeros((rows * _LANES,), jnp.float32).at[:n].set(x)
    x2d = padded.reshape(rows, _LANES)
    fparams = jnp.stack([norm.astype(jnp.float32),
                         jnp.float32(s)])
    base_arr = jnp.asarray(base, jnp.uint32).reshape(1)
    kernel = (_dither_linear_kernel if partition == "linear"
              else _dither_natural_kernel)

    levels = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), jnp.int8),
        grid=(rows // _BLOCK_ROWS,),
        in_specs=[
            pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(x2d, fparams, base_arr)
    return levels.reshape(-1)[:n]


def _randomk_hash_kernel(base_ref, out_ref):
    """Raw murmur3 hash per lane (bitcast to int32 for VMEM); the caller
    takes ``% size`` in plain XLA — keeping the mod outside the kernel
    avoids relying on Mosaic uint32 remainder support while preserving
    the full 32-bit index range (a float-uniform derivation caps
    distinct indices at 2^24, wrong for size > 16.7M)."""
    from .rng import mm3_finalize

    h = mm3_finalize(_global_counter(base_ref[0], _BLOCK_ROWS))
    out_ref[:] = pltpu.bitcast(h, jnp.int32)


@functools.partial(jax.jit, static_argnums=(2, 3))
def randomk_indices(base: jnp.ndarray, size: jnp.ndarray, k: int,
                    interpret: bool = False):
    """k pseudo-random indices in [0, size) from the counter RNG —
    bit-exact with RandomkCodec._indices / HostRandomk.indices. ``base``
    is the uint32 RNG base (rng.uniform_base(seed, step)); ``size`` the
    uncompressed element count."""
    rows = _padded_rows(k)
    base_arr = jnp.asarray(base, jnp.uint32).reshape(1)
    h = pl.pallas_call(
        _randomk_hash_kernel,
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), jnp.int32),
        grid=(rows // _BLOCK_ROWS,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(base_arr)
    hu = h.reshape(-1)[:k].astype(jnp.uint32)
    return (hu % jnp.asarray(size).astype(jnp.uint32)).astype(jnp.int32)
