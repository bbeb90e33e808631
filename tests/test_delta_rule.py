"""The gated delta rule with a decay a channel (ops/delta_rule.py): the
chunk algebra against the recurrence a position at a time in float32,
output and all five gradients, over chunk sizes, lengths that are and
are not whole chunks, write strengths 0 and 1 and decays from 1e-4 to 20
a step (no overflow, no NaN); the Pallas kernels (interpret mode)
against the scan of the same chunk, bit for bit in float32; what the
module publishes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.ops import delta_rule as D


def _operands(seed, B, T, H, d_k, d_v, g_low, g_high, beta=None,
              dtype=jnp.float32):
    """q and k a head at unit length (q scaled as the model scales it),
    ``-g`` log-uniform in [g_low, g_high] a channel, a cotangent."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (B, T, H, d_k))
    k = jax.random.normal(ks[1], (B, T, H, d_k))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d_k ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, T, H, d_v))
    g = -jnp.exp(jax.random.uniform(
        ks[3], (B, T, H, d_k), minval=np.log(g_low), maxval=np.log(g_high)))
    b = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H))) \
        if beta is None else jnp.full((B, T, H), beta)
    do = jax.random.normal(ks[5], (B, T, H, d_v))
    return tuple(a.astype(dtype) for a in (q, k, v)), g, b, do.astype(dtype)


def _value_and_grads(fn, operands, do):
    return jax.value_and_grad(
        lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * do),
        argnums=(0, 1, 2, 3, 4))(*operands)


CASES = {
    # T, chunk, g_low, g_high, beta
    "whole-chunks": (48, 16, 1e-4, 1.0, None),
    "ragged-tail": (37, 8, 0.1, 5.0, None),
    "shorter-than-a-chunk": (11, 16, 1e-2, 1.0, None),
    "one-chunk-of-64": (64, 64, 1e-4, 20.0, None),
    "beta-one": (40, 16, 1e-3, 2.0, 1.0),
    "beta-zero": (40, 16, 1e-3, 2.0, 0.0),
    "decay-20-a-step": (32, 32, 19.0, 20.0, None),
    "weak-to-strong": (96, 32, 1e-4, 20.0, None),
    "chunk-of-2": (9, 2, 1e-2, 3.0, None),
}


@pytest.mark.parametrize("case", CASES)
def test_the_chunk_algebra_is_the_recurrence(case):
    T, C, g_low, g_high, beta = CASES[case]
    (q, k, v), g, b, do = _operands(len(case), 2, T, 2, 16, 24, g_low,
                                    g_high, beta)
    got = D.delta_rule(q, k, v, g, b, C)
    want = D.recurrence(q, k, v, g, b)
    assert got.shape == want.shape == (2, T, 2, 24)
    np.testing.assert_allclose(got, want, atol=2e-6)
    loss, grads = _value_and_grads(
        lambda *a: D.delta_rule(*a, C), (q, k, v, g, b), do)
    ref_loss, ref_grads = _value_and_grads(D.recurrence, (q, k, v, g, b), do)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5, atol=1e-5)
    for name, a, w in zip("q k v g beta".split(), grads, ref_grads):
        assert np.all(np.isfinite(a)), name
        scale = float(jnp.max(jnp.abs(w))) + 1e-30
        np.testing.assert_allclose(a / scale, w / scale, atol=5e-6,
                                   err_msg=name)
    if beta == 0.0:
        # nothing is ever written: the state stays zero
        assert not np.any(got) and not np.any(grads[0])


def test_the_strongest_decay_leaves_only_the_positions_own_write():
    """``g = -20`` a step: what a position reads is what it wrote
    itself, ``beta (q . k) v``, to float32's last digits; no exponent
    is ever positive, so nothing overflows on the way."""
    (q, k, v), g, b, _ = _operands(5, 1, 64, 2, 16, 16, 19.99, 20.0)
    got = D.delta_rule(q, k, v, g, b, 64)
    own = b[..., None] * jnp.sum(q * k, -1, keepdims=True) * v
    np.testing.assert_allclose(got, own, atol=1e-7)


def test_a_chunk_is_a_power_of_two():
    (q, k, v), g, b, _ = _operands(1, 1, 12, 1, 8, 8, 0.1, 1.0)
    with pytest.raises(ValueError, match="power of two"):
        D.delta_rule(q, k, v, g, b, 12)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_the_kernels_are_the_scan_of_the_same_chunk(dtype):
    """Interpret mode, lane-wide heads: the forward's output and kept
    states and the backward's five gradients against the scan path's
    (float32: bit for bit but for the order of two additions)."""
    B, T, H, d, C = 1, 64, 2, 128, 32
    (q, k, v), g, b, do = _operands(7, B, T, H, d, d, 1e-3, 2.0, dtype=dtype)
    o, states = D._kernel_fwd(q, k, v, g, b, C, True, interpret=True)
    want, want_states = D._scan_fwd(q, k, v, g, b, C)
    assert o.dtype == dtype and states.shape == (B, H, T // C, d, d)
    np.testing.assert_allclose(
        o.astype(jnp.float32), want, atol=1e-6 if dtype == jnp.float32
        else 2e-3)
    np.testing.assert_array_equal(states,
                                  want_states.transpose(1, 2, 0, 3, 4))
    none = D._kernel_fwd(q, k, v, g, b, C, False, interpret=True)
    assert none[1] is None
    np.testing.assert_array_equal(none[0], o)
    grads = D._kernel_bwd(q, k, v, g, b, states, do, C, interpret=True)
    wants = D._scan_bwd(q, k, v, g, b, want_states, do, C)
    for name, a, w in zip("q k v g beta".split(), grads, wants):
        assert a.shape == w.shape and a.dtype == w.dtype, name
        scale = float(jnp.max(jnp.abs(w.astype(jnp.float32))))
        np.testing.assert_allclose(
            a.astype(jnp.float32) / scale, w.astype(jnp.float32) / scale,
            atol=1e-6, err_msg=name)


def test_bf16_operands_stay_near_the_float32_recurrence():
    """The model's compute type: products of bf16 operands accumulate
    in float32, the state, the decay and the write strength stay
    float32."""
    (q, k, v), g, b, do = _operands(9, 2, 128, 2, 16, 16, 1e-3, 1.0,
                                    dtype=jnp.bfloat16)
    do = do.astype(jnp.float32)
    f32 = tuple(a.astype(jnp.float32) for a in (q, k, v))
    loss, grads = _value_and_grads(D.delta_rule, (q, k, v, g, b), do)
    want, wants = _value_and_grads(D.recurrence, (*f32, g, b), do)
    assert grads[3].dtype == grads[4].dtype == jnp.float32
    assert grads[0].dtype == jnp.bfloat16
    for a, w in zip(grads, wants):
        gap = jnp.linalg.norm(a.astype(jnp.float32) - w) / jnp.linalg.norm(w)
        assert float(gap) < 0.02


def test_the_sizes_it_counts_and_publishes():
    from byteps_tpu.core.state import get_state

    # the benchmark cell: 2 rows x 32 heads x 128 chunks a layer
    assert D.chunk_steps(2, 8192, 32) == 8192
    assert D.chunk_steps(1, 65, 2, 64) == 4
    assert D.state_bytes(32, 128, 128) == 2 * 1024 * 1024
    D.publish_sizes(64, 32, 128, 128)
    gauges = get_state().metrics.instruments()[1]
    assert gauges["kda/chunk"].value == 64
    assert gauges["kda/state_bytes"].value == 2 * 1024 * 1024
    assert D.SCOPE == "bps.attn.kda"
