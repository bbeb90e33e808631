"""MoE / expert parallelism: routed layer vs a brute-force per-token oracle,
and the ep-sharded all_to_all path vs the unsharded path."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from byteps_tpu.models import moe
from byteps_tpu.parallel import sharding as sh
from byteps_tpu.parallel.mesh import EP_AXIS, make_mesh


def _cfg(**kw):
    cfg = moe.MoEConfig.tiny(vocab_size=64, seq=16)
    # fp32: comparisons are exact (the layer drops nothing by design)
    return dataclasses.replace(cfg, dtype=jnp.float32, **kw)


def _layer0(params):
    """One layer's params (blocks are stacked on the leading [L] dim)."""
    return {k: v[0] for k, v in params["blocks"].items()}


def test_moe_layer_matches_per_token_oracle():
    cfg = _cfg()
    params = moe.init_params(jax.random.PRNGKey(0), cfg)
    p = _layer0(params)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.dim),
                          jnp.float32)
    out, stats = moe.moe_layer(x, p, cfg.top_k, cfg.dtype)
    aux = stats["aux"]

    # oracle: every token goes through its top-k experts densely
    xf = np.asarray(x, np.float64).reshape(-1, cfg.dim)
    logits = xf @ np.asarray(p["router"], np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    expect = np.zeros_like(xf)
    for t in range(xf.shape[0]):
        top = np.argsort(-probs[t])[:cfg.top_k]
        gates = probs[t][top] / probs[t][top].sum()
        for g, e in zip(gates, top):
            h = xf[t]
            gate = h @ np.asarray(p["w_gate"][e], np.float64)
            up = h @ np.asarray(p["w_up"][e], np.float64)
            silu = gate / (1 + np.exp(-gate))
            expect[t] += g * ((silu * up) @ np.asarray(p["w_down"][e],
                                                      np.float64))
    np.testing.assert_allclose(
        np.asarray(out).reshape(-1, cfg.dim), expect, rtol=1e-4, atol=1e-5)
    assert np.isfinite(float(aux))


def test_moe_ep_matches_unsharded(devices):
    cfg = _cfg()
    params = moe.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.asarray(
        np.random.RandomState(2).randint(0, cfg.vocab_size, (8, 17)),
        jnp.int32)
    dense = moe.loss_fn(params, {"tokens": tokens}, cfg)

    mesh = make_mesh({EP_AXIS: 4}, devices[:4])
    specs = sh.moe_param_specs()

    def step(p, t):
        # tokens stay replicated over ep; experts are sharded -> the
        # all_to_all dispatch path runs, but the math must not change
        loss = moe.loss_fn(p, {"tokens": t}, cfg, ep_axis=EP_AXIS)
        return jax.lax.pmean(loss, EP_AXIS)

    f = shard_map(step, mesh=mesh, in_specs=(specs, P()), out_specs=P(),
                  check_vma=False)
    out = jax.jit(f)(params, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               rtol=1e-5, atol=1e-6)


def test_moe_ep_grads_flow(devices):
    """Gradients through the all_to_all dispatch are finite and the expert
    grads land sharded (each device only owns its experts' slices)."""
    cfg = _cfg()
    params = moe.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.asarray(
        np.random.RandomState(3).randint(0, cfg.vocab_size, (4, 17)),
        jnp.int32)
    mesh = make_mesh({EP_AXIS: 4}, devices[:4])
    specs = sh.moe_param_specs()

    def grads(p, t):
        # the ep training contract: grad the LOCAL loss, then
        # ep_grad_correction turns the per-device partials into the
        # global-mean gradient
        g = jax.grad(lambda q: moe.loss_fn(
            q, {"tokens": t}, cfg, ep_axis=EP_AXIS))(p)
        return moe.ep_grad_correction(g, EP_AXIS)

    f = shard_map(grads, mesh=mesh, in_specs=(specs, P()),
                  out_specs=specs, check_vma=False)
    g = jax.jit(f)(params, tokens)
    for leaf in jax.tree.leaves(g):
        assert np.all(np.isfinite(np.asarray(leaf)))
    # against the unsharded oracle
    g0 = jax.grad(lambda q: moe.loss_fn(q, {"tokens": tokens}, cfg))(params)
    np.testing.assert_allclose(
        np.asarray(g["blocks"]["w_down"]), np.asarray(g0["blocks"]["w_down"]),
        rtol=5e-4, atol=1e-6)


def _oracle(x, p, top_k, held):
    """Every token through its top-k experts densely, float64; only the
    terms of the experts in ``held`` are summed."""
    xf = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    logits = xf @ np.asarray(p["router"], np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    expect = np.zeros_like(xf)
    for t in range(xf.shape[0]):
        top = np.argsort(-probs[t], kind="stable")[:top_k]
        gates = probs[t][top] / probs[t][top].sum()
        for g, e in zip(gates, top):
            if e not in held:
                continue
            j = e - held[0]
            gate = xf[t] @ np.asarray(p["w_gate"][j], np.float64)
            up = xf[t] @ np.asarray(p["w_up"][j], np.float64)
            expect[t] += g * ((gate / (1 + np.exp(-gate)) * up)
                              @ np.asarray(p["w_down"][j], np.float64))
    return expect


def _share(p, first, n):
    """One layer's params as the device holding experts
    ``first .. first + n - 1`` has them: the whole router, its experts."""
    out = dict(p)
    for name in moe.EXPERT_LEAVES:
        out[name] = p[name][first:first + n]
    return out


def test_shares_of_one_layer_add_up_to_the_uncut_layer():
    """16 experts over 4 shares: each share routes over all 16, sums
    only its own experts' terms, and the four parts add up to the whole
    layer's output."""
    cfg = _cfg(n_experts=16, top_k=4)
    p = _layer0(moe.init_params(jax.random.PRNGKey(0), cfg))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.dim),
                          jnp.float32)
    whole, stats = moe.moe_layer(x, p, cfg.top_k, cfg.dtype)
    assert int(jnp.sum(stats["load"])) == 2 * 8 * 4
    assert int(stats["dropped"]) == 0
    parts, pairs = [], 0
    for first in range(0, 16, 4):
        part, st = moe.moe_layer(x, _share(p, first, 4), cfg.top_k,
                                 cfg.dtype, first=first)
        np.testing.assert_allclose(
            np.asarray(part).reshape(-1, cfg.dim),
            _oracle(x, _share(p, first, 4), cfg.top_k,
                    list(range(first, first + 4))), rtol=1e-4, atol=1e-5)
        parts.append(part)
        pairs += int(jnp.sum(st["load"]))
    assert pairs == 2 * 8 * 4          # every pair lives in one share
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(whole).reshape(-1, cfg.dim),
        _oracle(x, p, cfg.top_k, list(range(16))), rtol=1e-4, atol=1e-5)


def _uneven_router(cfg, favourite):
    """A router that sends every token to ``favourite`` first and never
    to expert ``favourite + 1``."""
    r = np.zeros((cfg.dim, cfg.n_experts), np.float32)
    r[0, favourite] = 50.0
    r[0, favourite + 1] = -50.0
    return jnp.asarray(r)


def test_uneven_routing_drops_nothing():
    """One held expert gets every token and another none: nothing is
    dropped (there is no capacity), and the result is the oracle's."""
    cfg = _cfg(n_experts=8, top_k=2)
    p = _layer0(moe.init_params(jax.random.PRNGKey(0), cfg))
    p["router"] = p["router"] * 0.01 + _uneven_router(cfg, 2)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(4), (2, 8, cfg.dim),
                                  jnp.float32)) + 0.1
    held = _share(p, 2, 4)                     # experts 2, 3, 4, 5
    out, stats = moe.moe_layer(x, held, cfg.top_k, cfg.dtype, first=2)
    load = np.asarray(stats["load"])
    assert load[0] == 16 and load[1] == 0      # all tokens; none
    assert int(stats["dropped"]) == 0
    np.testing.assert_allclose(
        np.asarray(out).reshape(-1, cfg.dim),
        _oracle(x, held, cfg.top_k, [2, 3, 4, 5]), rtol=1e-4, atol=1e-5)


def test_chunked_layer_is_the_layer():
    """Walking the tokens in slices changes no output, no load and no
    gradient."""
    cfg = _cfg(n_experts=8, top_k=2)
    p = _share(_layer0(moe.init_params(jax.random.PRNGKey(0), cfg)), 0, 4)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 8, cfg.dim),
                          jnp.float32)

    def run(chunk):
        def f(x, p):
            out, st = moe.moe_layer(x, p, cfg.top_k, cfg.dtype, chunk=chunk)
            return jnp.sum(out ** 2), st
        (val, st), g = jax.value_and_grad(f, argnums=(0, 1),
                                          has_aux=True)(x, p)
        return val, st, g

    v0, s0, g0 = run(None)
    v1, s1, g1 = run(4)
    np.testing.assert_allclose(float(v1), float(v0), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(s1["load"]),
                                  np.asarray(s0["load"]))
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g0)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_a_slice_that_does_not_divide_the_tokens_is_refused():
    cfg = _cfg(n_experts=8, top_k=2)
    p = _share(_layer0(moe.init_params(jax.random.PRNGKey(0), cfg)), 0, 4)
    x = jnp.ones((2, 8, cfg.dim), jnp.float32)
    with pytest.raises(ValueError, match="16 tokens do not divide"):
        moe.moe_layer(x, p, cfg.top_k, cfg.dtype, chunk=5)


def test_dropped_counts_pairs_outside_their_experts_group(monkeypatch):
    """The counter is not 0 by construction: it holds the router's
    choice against the rows the grouped product is told to give each
    expert, so a sort that does not group the pairs shows in it."""
    cfg = _cfg(n_experts=8, top_k=2)
    p = _share(_layer0(moe.init_params(jax.random.PRNGKey(0), cfg)), 0, 4)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 8, cfg.dim),
                          jnp.float32)
    _, sound = moe.moe_layer(x, p, cfg.top_k, cfg.dtype)
    assert int(sound["dropped"]) == 0 and int(jnp.sum(sound["load"])) > 4
    monkeypatch.setattr(moe.jnp, "argsort", lambda key, stable=True:
                        jnp.arange(key.shape[0]))
    _, broken = moe.moe_layer(x, p, cfg.top_k, cfg.dtype)
    assert 0 < int(broken["dropped"]) <= int(jnp.sum(broken["load"]))


@pytest.mark.parametrize("router_dtype, within", [
    (jnp.float32, True), (jnp.bfloat16, False)])
def test_the_router_computes_in_float32(router_dtype, within):
    """``correct`` cannot see the router's precision (norms and losses
    hardly move with it: PERF.md section 7), so it is held here: the
    probabilities of bfloat16 activations against float32 weights, to
    float32's rounding against a float64 oracle; a bfloat16 router is
    a hundred times further off and flips choices."""
    cfg = _cfg(n_experts=64, top_k=8)
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (512, cfg.dim), jnp.float32).astype(
        jnp.bfloat16)
    w = jax.random.normal(jax.random.fold_in(key, 1),
                          (cfg.dim, 64), jnp.float32) * 0.2
    with jax.default_matmul_precision("highest"):
        gates, idx, probs = moe.route(x, w, 8, router_dtype)
    logits = np.asarray(x, np.float64) @ np.asarray(w, np.float64)
    want = np.exp(logits - logits.max(-1, keepdims=True))
    want /= want.sum(-1, keepdims=True)
    gap = np.abs(np.asarray(probs, np.float64) - want).max()
    same = np.array_equal(np.sort(np.asarray(idx), -1),
                          np.sort(np.argsort(-want, -1)[:, :8], -1))
    assert (gap < 1e-6) == within and same == within
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 1.0, rtol=1e-6)


def test_uneven_routing_through_the_exchange(devices):
    """The same skew on an ``ep`` mesh: every pair of every device goes
    to one peer, whose buffers hold them all."""
    cfg = _cfg(n_experts=8, top_k=2)
    params = moe.init_params(jax.random.PRNGKey(0), cfg)
    p = _layer0(params)
    p["router"] = p["router"] * 0.01 + _uneven_router(cfg, 2)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(6), (2, 8, cfg.dim),
                                  jnp.float32)) + 0.1
    mesh = make_mesh({EP_AXIS: 4}, devices[:4])
    specs = {k: (P(EP_AXIS) if k in moe.EXPERT_LEAVES else P())
             for k in p}

    def layer(p, x):
        out, st = moe.moe_layer(x, p, cfg.top_k, cfg.dtype,
                                ep_axis=EP_AXIS)
        return out, st["load"]

    out, load = jax.jit(shard_map(
        layer, mesh=mesh, in_specs=(specs, P()),
        out_specs=(P(), P(EP_AXIS)), check_vma=False))(p, x)
    # x is replicated: each of the 4 devices sends its 16 tokens' pairs
    assert np.asarray(load).sum() == 4 * 2 * 8 * 2
    assert np.asarray(load)[2] == 4 * 16 and np.asarray(load)[3] == 0
    np.testing.assert_allclose(
        np.asarray(out).reshape(-1, cfg.dim),
        _oracle(x, p, cfg.top_k, list(range(8))), rtol=1e-4, atol=1e-5)


# --------------------------------------------------------------------- #
# the compact sorted buffer and its full-size fallback
# --------------------------------------------------------------------- #

def _parent_grouped_ffn(x, key, k, w_gate, w_up, w_down, dtype):
    """``grouped_ffn`` as it stood before the compact buffer (PR 28),
    copied: what the full-size path is held to, bit for bit."""
    n_held = w_gate.shape[0]
    N = key.shape[0]
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    inv = jnp.zeros((N,), jnp.int32).at[order].set(
        jnp.arange(N, dtype=jnp.int32))
    load = jnp.sum(key[:, None] == jnp.arange(n_held)[None, :],
                   axis=0, dtype=jnp.int32)
    routed = (jnp.arange(N) < jnp.sum(load))[:, None]
    xs = jnp.where(routed, moe._rows_in_order(x, order, inv, k), 0)
    gate = jax.lax.ragged_dot(xs, w_gate.astype(dtype), load)
    up = jax.lax.ragged_dot(xs, w_up.astype(dtype), load)
    h = jnp.where(routed, jax.nn.silu(gate) * up, 0)
    ys = jnp.where(routed,
                   jax.lax.ragged_dot(h, w_down.astype(dtype), load), 0)
    y = moe._rows_in_order(ys, inv, order, 1)
    end = jnp.cumsum(load)
    group = jnp.sum(inv[:, None] >= end[None, :], axis=1)
    held = key < n_held
    dropped = jnp.sum(held & (group != key), dtype=jnp.int32)
    return y, load, dropped


def _parent_held_chunk(x, gates, idx, first, w_gate, w_up, w_down, dtype):
    """``_held_chunk`` of PR 28, copied."""
    T, k = idx.shape
    n_held = w_gate.shape[0]
    local = idx - first
    here = (local >= 0) & (local < n_held)
    key = jnp.where(here, local, n_held).reshape(-1)
    y, load, dropped = _parent_grouped_ffn(x, key, k, w_gate, w_up, w_down,
                                           dtype)
    w = jnp.where(here, gates, 0.0)
    out = jnp.einsum("tkd,tk->td", y.reshape(T, k, -1), w.astype(dtype),
                     preferred_element_type=jnp.float32)
    return out.astype(dtype), load, dropped


def _slice(top_k, held_pairs=None, tokens=32, n_experts=16, n_held=2,
           seed=11):
    """One slice's inputs on a device that holds ``n_held`` of
    ``n_experts``: x [T, d], gates/idx [T, k] and the expert leaves.
    The router's choice is random, or hand-made so that exactly
    ``held_pairs`` pairs go to held experts (the first tokens' first
    choices, as many a token as there are held experts)."""
    cfg = _cfg(n_experts=n_experts, top_k=top_k)
    p = _share(_layer0(moe.init_params(jax.random.PRNGKey(0), cfg)),
               0, n_held)
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (tokens, cfg.dim), jnp.float32)
    if held_pairs is None:
        gates, idx, _ = moe.route(x, p["router"], top_k)
    else:
        a_token = min(top_k, n_held)
        idx = np.tile(np.arange(n_held, n_held + top_k), (tokens, 1))
        for pair in range(held_pairs):
            idx[pair // a_token, pair % a_token] = pair % a_token
        assert (idx < n_held).sum() == held_pairs
        idx = jnp.asarray(idx, jnp.int32)
        gates = jax.nn.softmax(jax.random.normal(
            jax.random.fold_in(key, 1), (tokens, top_k)), axis=-1)
    return x, gates, idx, [p[name] for name in moe.EXPERT_LEAVES]


def _value_and_grads(fn, x, gates, leaves):
    """(output, load, dropped) and the gradients of a fixed projection
    of the output with respect to x, the gates and the expert leaves."""
    cot = jax.random.normal(jax.random.PRNGKey(5), x.shape, x.dtype)

    def f(x, gates, leaves):
        out, load, dropped = fn(x, gates, *leaves)
        return jnp.sum(out * cot), (out, load, dropped)

    (_, aux), grads = jax.value_and_grad(f, argnums=(0, 1, 2),
                                         has_aux=True)(x, gates, leaves)
    return aux, jax.tree.leaves(grads)


@pytest.mark.parametrize("top_k", [2, 4])
def test_compact_buffer_against_the_full_one(top_k):
    """One slice under the bound, through both buffers: output, load,
    dropped and the gradients with respect to x, the gates and the
    three expert leaves. The products see the same rows in the same
    groups, so the leaves' gradients agree bit for bit; a token's terms
    are summed in sorted order on one path and in pair order on the
    other, and a gate's gradient is a sum over the width by another
    operation: float32's rounding apart."""
    x, gates, idx, leaves = _slice(top_k)
    N = idx.size
    C = moe.compact_rows(N, 2, 16)
    held = int((np.asarray(idx) < 2).sum())
    assert 0 < held <= C == N // 4

    def through(rows):
        return _value_and_grads(
            lambda x, g, *w: moe._held_chunk(x, g, idx, 0, *w, jnp.float32,
                                             rows), x, gates, leaves)

    (out_c, load_c, drop_c), grads_c = through(C)
    (out_f, load_f, drop_f), grads_f = through(N)
    np.testing.assert_array_equal(np.asarray(load_c), np.asarray(load_f))
    assert int(drop_c) == int(drop_f) == 0 and int(load_c.sum()) == held
    np.testing.assert_allclose(np.asarray(out_c), np.asarray(out_f),
                               rtol=1e-6, atol=1e-7)
    for a, b in zip(grads_c[:2], grads_f[:2]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)
    for a, b in zip(grads_c[2:], grads_f[2:]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and both are the layer: the float64 oracle of the held terms
    want = np.zeros(x.shape, np.float64)
    xf = np.asarray(x, np.float64)
    for t in range(x.shape[0]):
        for g, e in zip(np.asarray(gates)[t], np.asarray(idx)[t]):
            if e < 2:
                gate = xf[t] @ np.asarray(leaves[0][e], np.float64)
                up = xf[t] @ np.asarray(leaves[1][e], np.float64)
                want[t] += g * ((gate / (1 + np.exp(-gate)) * up)
                                @ np.asarray(leaves[2][e], np.float64))
    np.testing.assert_allclose(np.asarray(out_c), want, rtol=1e-4, atol=1e-5)


def test_the_full_buffer_is_the_parents_arithmetic():
    """The full-size path is PR 28's ``grouped_ffn`` and ``_held_chunk``
    unchanged: same outputs and same gradients, bit for bit, for the
    layer's own path and for ``grouped_ffn`` (the exchange's)."""
    x, gates, idx, leaves = _slice(4)
    N = idx.size
    got, got_grads = _value_and_grads(
        lambda x, g, *w: moe._held_chunk(x, g, idx, 0, *w, jnp.float32, N),
        x, gates, leaves)
    want, want_grads = _value_and_grads(
        lambda x, g, *w: _parent_held_chunk(x, g, idx, 0, *w, jnp.float32),
        x, gates, leaves)
    for a, b in zip(jax.tree.leaves((got, got_grads)),
                    jax.tree.leaves((want, want_grads))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    key = jnp.where(idx < 2, idx, 2).reshape(-1)
    for a, b in zip(moe.grouped_ffn(x, key, 4, *leaves, jnp.float32),
                    _parent_grouped_ffn(x, key, 4, *leaves, jnp.float32)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _held_layer(x, gates, idx, leaves, n=1):
    """``moe._held`` on a device holding experts 0, 1 of 16: the walk of
    ``n`` slices behind the conditional."""
    return moe._held(x, gates, idx, 0, 16, *leaves, jnp.float32, n)


@pytest.mark.parametrize("held_pairs", [16, 17, 64])
def test_a_slice_at_the_bound_and_one_pair_over(held_pairs):
    """Exactly ``compact_rows`` held pairs go through the compact
    buffer, one more (and every pair) through the full-size one; both
    compute every pair (a buffer too small would show as pairs
    dropped) and give the parent's result."""
    C = moe.compact_rows(32 * 2, 2, 16)
    assert C == 16
    x, gates, idx, leaves = _slice(2, held_pairs=held_pairs)
    out, load, dropped, compact = _held_layer(x, gates, idx, leaves)
    assert int(compact) == (held_pairs <= C)
    assert int(load.sum()) == held_pairs and int(dropped) == 0
    want, want_load, _ = _parent_held_chunk(x, gates, idx, 0, *leaves,
                                            jnp.float32)
    np.testing.assert_array_equal(np.asarray(load), np.asarray(want_load))
    if held_pairs > C:
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    else:
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-6, atol=1e-7)


def test_every_pair_to_held_experts_takes_the_full_buffer():
    """A router that sends every token's every choice to the two held
    experts: twice the compact buffer's rows, so the layer walks its
    slices through the full-size one, drops nothing and returns the
    parent's result, gradients included (to float32's rounding: the
    walk is a loop here and unrolled there; bit for bit is
    ``test_the_full_buffer_is_the_parents_arithmetic``)."""
    cfg = _cfg(n_experts=16, top_k=2)
    p = _share(_layer0(moe.init_params(jax.random.PRNGKey(0), cfg)), 0, 2)
    r = np.zeros((cfg.dim, 16), np.float32)
    r[0, :2] = 50.0
    p["router"] = p["router"] * 0.01 + jnp.asarray(r)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(4), (2, 16, cfg.dim),
                                  jnp.float32)) + 0.1

    def layer(x, p):
        out, st = moe.moe_layer(x, p, 2, jnp.float32, chunk=16)
        return jnp.sum(out ** 2), (out, st)

    (_, (out, st)), grads = jax.value_and_grad(layer, argnums=(0, 1),
                                               has_aux=True)(x, p)
    assert moe.compact_rows(16 * 2, 2, 16) == 16
    assert int(st["compact_slices"]) == 0 and int(st["full_slices"]) == 2
    assert int(st["load"].sum()) == 2 * 16 * 2 and int(st["dropped"]) == 0

    def parent(x, p):
        xf = x.reshape(-1, cfg.dim)
        gates, idx, _ = moe.route(xf, p["router"], 2)
        outs = [_parent_held_chunk(xf[s:s + 16], gates[s:s + 16],
                                   idx[s:s + 16], 0, p["w_gate"], p["w_up"],
                                   p["w_down"], jnp.float32)[0]
                for s in range(0, 32, 16)]
        out = jnp.concatenate(outs).reshape(x.shape)
        return jnp.sum(out ** 2), out

    (_, want), want_grads = jax.value_and_grad(parent, argnums=(0, 1),
                                               has_aux=True)(x, p)
    for a, b in zip(jax.tree.leaves((out, grads)),
                    jax.tree.leaves((want, want_grads))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(out).reshape(-1, cfg.dim),
        _oracle(x, p, 2, [0, 1]), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("n_held, conditional", [
    (16, False), (8, False), (4, True), (2, True)])
def test_the_conditional_is_there_only_where_a_compact_buffer_is(
        n_held, conditional):
    """Where every expert is held (the Mixtral model on one device), or
    half of them, the compact buffer would be the full-size one: the
    choice is made at trace time and the program holds no
    conditional."""
    cfg = _cfg(n_experts=16, top_k=2)
    p = _share(_layer0(moe.init_params(jax.random.PRNGKey(0), cfg)),
               0, n_held)
    x = jnp.ones((2, 16, cfg.dim), jnp.float32)
    assert (moe.compact_rows(32, n_held, 16) < 32) == conditional

    def layer(x, p):
        return moe.moe_layer(x, p, 2, jnp.float32, chunk=16)[0]

    text = jax.jit(jax.grad(lambda x, p: jnp.sum(layer(x, p)))).lower(
        x, p).as_text()
    assert ("stablehlo.case" in text or "stablehlo.if" in text) \
        == conditional
    _, st = moe.moe_layer(x, p, 2, jnp.float32, chunk=16)
    if not conditional:
        assert int(st["compact_slices"]) == 0 and int(st["full_slices"]) == 2


@pytest.mark.parametrize("chunk, favourite", [
    (None, None), (16, None), (32, None), (16, 0), (None, 0)])
def test_every_slice_is_counted_on_one_buffer_or_the_other(chunk, favourite):
    """``compact_slices + full_slices`` is the number of slices, under
    an ordinary routing (compact) and under one that overfills a slice
    (full)."""
    cfg = _cfg(n_experts=16, top_k=2)
    p = _share(_layer0(moe.init_params(jax.random.PRNGKey(0), cfg)), 0, 2)
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 32, cfg.dim),
                          jnp.float32)
    if favourite is not None:
        r = np.zeros((cfg.dim, 16), np.float32)
        r[0, :2] = 50.0
        p["router"] = p["router"] * 0.01 + jnp.asarray(r)
        x = jnp.abs(x) + 0.1
    _, st = moe.moe_layer(x, p, 2, jnp.float32, chunk=chunk)
    slices = 1 if chunk is None else 64 // chunk
    compact, full = int(st["compact_slices"]), int(st["full_slices"])
    assert compact + full == slices
    assert (compact, full) == ((0, slices) if favourite is not None
                               else (slices, 0))
    assert int(st["dropped"]) == 0


def test_dropped_sees_a_compact_buffer_cut_short():
    """On the compact path the counter also holds the sort against the
    buffer's rows: a bound that lets more pairs in than it has rows
    for shows as pairs dropped."""
    x, gates, idx, leaves = _slice(2, held_pairs=20)
    _, load, dropped = moe._held_chunk(x, gates, idx, 0, *leaves,
                                       jnp.float32, 16)
    assert int(load.sum()) == 20 and int(dropped) == 4


# ------------------------------------------------------------------ #
# sigmoid routing under a selection bias (models/lfm2.py's router), and
# the guard that softmax top-k is what it was
# ------------------------------------------------------------------ #

def _router_inputs(T=384, d=64, E=32, scale=0.2, seed=5):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (T, d), jnp.float32).astype(jnp.bfloat16)
    w = jax.random.normal(jax.random.fold_in(key, 1), (d, E),
                          jnp.float32) * scale
    bias = jax.random.uniform(jax.random.fold_in(key, 2), (E,),
                              minval=-0.1, maxval=0.1)
    return x, w, bias


@pytest.mark.parametrize("scale", [1.0, 2.5])
def test_sigmoid_router_selects_with_the_bias_and_weighs_without_it(scale):
    """Against a float64 loop over the tokens: the k experts are the
    largest of ``sigmoid + bias``, the weights are the sigmoids alone
    over their sum plus 1e-6, times the scale."""
    k = 4
    x, w, bias = _router_inputs()
    with jax.default_matmul_precision("highest"):
        gates, idx, scores = moe.route(
            x, w, k, score="sigmoid", select_bias=bias, norm_eps=1e-6,
            scale=scale)
    s = 1.0 / (1.0 + np.exp(-(np.asarray(x, np.float64)
                              @ np.asarray(w, np.float64))))
    b = np.asarray(bias, np.float64)
    np.testing.assert_allclose(np.asarray(scores), s, rtol=0, atol=1e-6)
    moved = 0
    for t in range(x.shape[0]):
        sel = np.argsort(-(s[t] + b), kind="stable")[:k]
        assert sorted(sel) == sorted(np.asarray(idx[t]).tolist()), t
        chosen = s[t][np.asarray(idx[t])]
        np.testing.assert_allclose(
            np.asarray(gates[t]), chosen / (chosen.sum() + 1e-6) * scale,
            rtol=1e-5)
        moved += len(set(sel) - set(np.argsort(-s[t], kind="stable")[:k]))
    # the weights are NOT those of a router that weighs with the bias
    biased = np.take_along_axis(s + b, np.asarray(idx), -1)
    assert np.abs(np.asarray(gates) - biased / biased.sum(-1, keepdims=True)
                  * scale).max() > 1e-3
    assert 0 < moved < x.shape[0] * k
    assert int(moe.bias_moved_pairs(scores, idx)) == moved
    # the sum is short of the scale by the 1e-6 in the divisor
    total = np.asarray(gates, np.float64).sum(-1)
    assert np.all(total < scale) and np.all(total > scale * (1 - 1e-5))


def test_a_zero_bias_moves_no_pair_and_the_bias_gets_no_gradient():
    x, w, bias = _router_inputs()
    gates, idx, scores = moe.route(x, w, 4, score="sigmoid",
                                   select_bias=jnp.zeros_like(bias),
                                   norm_eps=1e-6)
    plain = moe.route(x, w, 4, score="sigmoid", norm_eps=1e-6)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(plain[1]))
    np.testing.assert_array_equal(np.asarray(gates), np.asarray(plain[0]))
    assert int(moe.bias_moved_pairs(scores, idx)) == 0

    def loss(b, w_):
        g, _, _ = moe.route(x, w_, 4, score="sigmoid", select_bias=b,
                            norm_eps=1e-6)
        return jnp.sum(g * jnp.arange(1.0, 5.0))

    g_bias, g_w = jax.grad(loss, (0, 1))(bias, w)
    assert not np.any(np.asarray(g_bias)) and np.any(np.asarray(g_w))
    with pytest.raises(ValueError, match="softmax"):
        moe.route(x, w, 4, score="tanh")


@pytest.mark.parametrize("router_dtype, within", [
    (jnp.float32, True), (jnp.bfloat16, False)])
def test_the_sigmoid_router_computes_in_float32(router_dtype, within):
    """As ``test_the_router_computes_in_float32`` holds the softmax
    router: the scores of bfloat16 activations against float32 weights
    to float32's rounding against a float64 oracle, the selection the
    oracle's; a bfloat16 router is far off and flips choices."""
    x, w, bias = _router_inputs(T=512, d=64, E=32)
    with jax.default_matmul_precision("highest"):
        _, idx, scores = moe.route(x, w, 4, router_dtype, score="sigmoid",
                                   select_bias=bias, norm_eps=1e-6)
    want = 1.0 / (1.0 + np.exp(-(np.asarray(x, np.float64)
                                 @ np.asarray(w, np.float64))))
    gap = np.abs(np.asarray(scores, np.float64) - want).max()
    same = np.array_equal(
        np.sort(np.asarray(idx), -1),
        np.sort(np.argsort(-(want + np.asarray(bias, np.float64)),
                           -1)[:, :4], -1))
    assert (gap < 1e-6) == within and same == within


def _route_as_pr26_wrote_it(x_flat, router_w, top_k,
                            router_dtype=jnp.float32):
    """``moe.route`` before it had a score function or a bias (PRs 26 to
    29), kept here as the yardstick of the guard below."""
    with jax.named_scope("bps.moe.route"):
        logits = jnp.matmul(x_flat.astype(router_dtype),
                            router_w.astype(router_dtype),
                            preferred_element_type=router_dtype)
        probs = jax.nn.softmax(logits, axis=-1).astype(jnp.float32)
        gates, idx = jax.lax.top_k(probs, top_k)
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return gates, idx.astype(jnp.int32), probs


@pytest.mark.parametrize("what", ["route", "layer_and_gradient"])
def test_softmax_routing_is_operation_for_operation_what_it_was(
        what, monkeypatch):
    """The Mellum guard: ``route`` with its defaults, and ``moe_layer``
    as ``models/mellum.py`` calls it (a held share, slices, the float32
    router) with its gradient, trace to the same jaxpr as with the
    router PR 26 wrote: the new arguments cost the sparse decoder's
    program no operation."""
    x, w, _ = _router_inputs()
    if what == "route":
        def new():
            return jax.make_jaxpr(
                lambda a, b: moe.route(a, b, 8, jnp.float32))(x, w)

        old = jax.make_jaxpr(
            lambda a, b: _route_as_pr26_wrote_it(a, b, 8, jnp.float32))(x, w)
        assert str(new()) == str(old)
        return
    cfg = _cfg(n_experts=8, top_k=2)
    p = _layer0(moe.init_params(jax.random.PRNGKey(0), cfg))
    p = {k: (v[2:6] if k in moe.EXPERT_LEAVES else v) for k, v in p.items()}
    xs = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.dim),
                           jnp.float32)

    def as_mellum_calls_it(p_, x_):
        out, st = moe.moe_layer(x_, p_, cfg.top_k, jnp.float32, first=2,
                                ep_axis=None, chunk=16,
                                router_dtype=jnp.float32)
        return jnp.sum(out), (st["load"], st["dropped"],
                              st["compact_slices"], st["full_slices"])

    def trace():
        return str(jax.make_jaxpr(jax.value_and_grad(
            as_mellum_calls_it, argnums=(0, 1), has_aux=True))(p, xs))

    new = trace()
    monkeypatch.setattr(moe, "route",
                        lambda *a, **kw: _route_as_pr26_wrote_it(*a))
    assert new == trace()
