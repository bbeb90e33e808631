"""Pallas kernel parity tests (interpret mode on the CPU mesh; the compiled
path is exercised on real TPU by chip_smoke.py)."""

import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.ops.compression.pallas_kernels import (
    onebit_pack, onebit_unpack,
)


@pytest.mark.parametrize("n", [100, 32768, 40000])
def test_onebit_pallas_roundtrip(n):
    x = np.random.RandomState(n).randn(n).astype(np.float32)
    bits = onebit_pack(jnp.asarray(x), True)
    out = np.asarray(onebit_unpack(bits, jnp.float32(2.5), n, True))
    golden = np.where(x >= 0, 2.5, -2.5).astype(np.float32)
    np.testing.assert_allclose(out, golden)


def test_onebit_pallas_all_negative():
    x = -np.ones(1000, np.float32)
    bits = onebit_pack(jnp.asarray(x), True)
    out = np.asarray(onebit_unpack(bits, jnp.float32(1.0), 1000, True))
    np.testing.assert_allclose(out, x)


from byteps_tpu.ops.compression.pallas_kernels import (  # noqa: E402
    dithering_levels, randomk_indices,
)
from byteps_tpu.ops.compression.codecs import (  # noqa: E402
    DitheringCodec, RandomkCodec,
)
from byteps_tpu.ops.compression.rng import (  # noqa: E402
    np_uniform_parallel, uniform_base,
)


def _base(seed, step):
    return jnp.asarray(uniform_base(seed, step))


@pytest.mark.parametrize("n", [100, 4096, 50000])
@pytest.mark.parametrize("step", [0, 7])
def test_dithering_linear_pallas_bit_parity(n, step):
    """Fused kernel levels == the jnp codec's levels bit-for-bit (both use
    the same counter RNG and op order)."""
    x = np.random.RandomState(n + step).randn(n).astype(np.float32)
    codec = DitheringCodec(size=n, s=64, seed=11, use_pallas=False)
    want = np.asarray(codec.compress(jnp.asarray(x), step=step)["levels"])
    norm = jnp.maximum(jnp.max(jnp.abs(jnp.asarray(x))), 1e-30)
    got = np.asarray(dithering_levels(
        jnp.asarray(x), norm, _base(11, step), 64, "linear", True))
    np.testing.assert_array_equal(got, want)


def test_dithering_natural_pallas_parity():
    """Natural partition: powers-of-two levels; interpret mode shares
    XLA's transcendentals with the jnp path, so levels match exactly."""
    n = 3000
    x = np.random.RandomState(3).randn(n).astype(np.float32)
    codec = DitheringCodec(size=n, s=64, seed=5, partition="natural",
                           use_pallas=False)
    want = np.asarray(codec.compress(jnp.asarray(x), step=2)["levels"])
    norm = jnp.maximum(jnp.max(jnp.abs(jnp.asarray(x))), 1e-30)
    got = np.asarray(dithering_levels(
        jnp.asarray(x), norm, _base(5, 2), 64, "natural", True))
    exact = (got == want).mean()
    assert exact >= 0.999, exact  # ulp slack at log2 boundaries


@pytest.mark.parametrize("k,size", [(32, 512), (1000, 1 << 20)])
def test_randomk_indices_pallas_bit_parity(k, size):
    """Kernel indices == RandomkCodec._indices == numpy golden."""
    codec = RandomkCodec(size=size, k=k, seed=7, use_pallas=False)
    for step in (0, 3):
        want = np.asarray(codec._indices(step))
        got = np.asarray(randomk_indices(
            _base(7, step), jnp.int32(size), k, True))
        np.testing.assert_array_equal(got, want)
        # and against the numpy golden model directly
        from byteps_tpu.ops.compression.rng import np_index_parallel
        gold = np_index_parallel(7, k, size, mix=step)
        np.testing.assert_array_equal(got, gold)


def test_dithering_codec_roundtrip_quality_pallas_kernel():
    """decompress(kernel levels) is a valid unbiased-ish quantization of x
    (sanity on the full codec path with the kernel payload)."""
    n = 8192
    x = np.random.RandomState(0).randn(n).astype(np.float32)
    codec = DitheringCodec(size=n, s=64, seed=1, use_pallas=False)
    norm = jnp.maximum(jnp.max(jnp.abs(jnp.asarray(x))), 1e-30)
    levels = dithering_levels(jnp.asarray(x), norm, _base(1, 0), 64,
                              "linear", True)
    out = np.asarray(codec.decompress(
        {"levels": levels, "norm": np.float32(norm)}))
    err = np.abs(out - x)
    assert err.max() <= float(norm) / 64 + 1e-6
