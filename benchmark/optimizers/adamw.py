"""Adam with decoupled weight decay (Loshchilov & Hutter 2019,
algorithm 2), bias-corrected, the decay applied to every leaf."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def make_tx(hyper: dict):
    import optax

    return optax.adamw(hyper["lr"], b1=hyper["b1"], b2=hyper["b2"],
                       eps=hyper["eps"], weight_decay=hyper["weight_decay"])


def first_gradient(hyper: dict, opt_state, params0):
    """Adam's first moment after one step is (1 - b1) g."""
    import optax

    adam = next(s for s in jax.tree.leaves(
        opt_state, is_leaf=lambda s: isinstance(s, optax.ScaleByAdamState))
        if isinstance(s, optax.ScaleByAdamState))
    return jax.tree.map(lambda m: m / (1.0 - hyper["b1"]), adam.mu)


def reference_init(params):
    return {"mu": jax.tree.map(jnp.zeros_like, params),
            "nu": jax.tree.map(jnp.zeros_like, params),
            "count": jnp.zeros((), jnp.int32)}


def reference_update(params, state, grads, *, lr, b1, b2, eps, weight_decay):
    count = state["count"] + 1
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["mu"], grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g,
                      state["nu"], grads)
    c1 = 1 - b1 ** count.astype(jnp.float32)
    c2 = 1 - b2 ** count.astype(jnp.float32)

    def leaf(p, m, v):
        return p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps)
                         + weight_decay * p)

    return (jax.tree.map(leaf, params, mu, nu),
            {"mu": mu, "nu": nu, "count": count})
