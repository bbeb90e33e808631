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
