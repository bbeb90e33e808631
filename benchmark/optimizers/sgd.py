"""SGD with momentum and L2 weight decay added to the gradient
(Simonyan & Zisserman 2014, section 3.1)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def make_tx(hyper: dict):
    import optax

    return optax.chain(
        optax.add_decayed_weights(hyper["weight_decay"]),
        optax.sgd(hyper["lr"], momentum=hyper["momentum"]))


def first_gradient(hyper: dict, opt_state, params0):
    """The momentum trace after one step is g + weight_decay * p0."""
    import optax

    trace = next(s for s in jax.tree.leaves(
        opt_state, is_leaf=lambda s: isinstance(s, optax.TraceState))
        if isinstance(s, optax.TraceState))
    return jax.tree.map(lambda t, p: t - hyper["weight_decay"] * p,
                        trace.trace, params0)


def reference_init(params):
    return {"trace": jax.tree.map(jnp.zeros_like, params)}


def reference_update(params, state, grads, *, lr, momentum, weight_decay):
    trace = jax.tree.map(lambda t, g, p: momentum * t + g + weight_decay * p,
                         state["trace"], grads, params)
    return (jax.tree.map(lambda p, t: p - lr * t, params, trace),
            {"trace": trace})
