"""The reference's first steps: loss and gradients in blocks of rows (so
that float32 activations fit beside the state), the optimizer written
out in ``optimizers/<kind>.py``. What it returns is what ``correct`` compares.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..optimizers import load as load_optimizer
from .common import leaf_norms


class Reference:
    """Built once per (model, configuration, operand precision); its
    programs take the seed's key as an argument, so one compilation
    serves every seed."""

    def __init__(self, model, cfg: dict, rows: int, operand=None):
        optimizer, hyper = load_optimizer(cfg["optimizer"])
        opt_init = optimizer.reference_init
        opt_update = optimizer.reference_update
        self.model, self.rows = model, rows
        self.n_steps = cfg["check"]["steps"]
        self.rows_per_block = cfg["check"]["reference_rows_per_block"]

        def block_grads(params, block):
            return jax.value_and_grad(
                lambda p: model.nll_sum(p, block, cfg, operand),
                has_aux=True)(params)

        def apply(params, state, gsum, count):
            grads = jax.tree.map(lambda g: g / count, gsum)
            params, state = opt_update(params, state, grads, **hyper)
            return params, state, leaf_norms(grads)

        def init(key):
            params = model.init_params(key, cfg)
            return params, opt_init(params)

        self._block_grads = jax.jit(block_grads)
        self._accumulate = jax.jit(
            lambda acc, g: jax.tree.map(jnp.add, acc, g), donate_argnums=0)
        self._apply = jax.jit(apply, donate_argnums=(0, 1, 2))
        self._init = jax.jit(init)
        self._make_batch = jax.jit(
            lambda key, i: model.make_batch(key, i, rows, cfg))
        self._delta = jax.jit(lambda p, key: leaf_norms(jax.tree.map(
            jnp.subtract, p, model.init_params(key, cfg))))

    def steps(self, key) -> dict:
        """Trains the configuration's ``check.steps`` steps from the
        seed's key on batches 0, 1, ... Returns host floats: ``losses``,
        ``grad_norms`` (per leaf, of the first step's gradient) and
        ``delta_norms`` (per leaf, of the parameters' change over all
        the steps)."""
        params, state = self._init(key)
        losses, grad_norms = [], None
        for step in range(self.n_steps):
            batch = self._make_batch(key, step)
            gsum, total, count = None, 0.0, 0
            for start in range(0, self.rows, self.rows_per_block):
                stop = min(self.rows, start + self.rows_per_block)
                (s, n), g = self._block_grads(
                    params, self.model.slice_rows(batch, start, stop))
                gsum = g if gsum is None else self._accumulate(gsum, g)
                total, count = total + s, count + n
            losses.append(total / count)
            params, state, norms = self._apply(
                params, state, gsum, jnp.asarray(count, jnp.float32))
            if step == 0:
                grad_norms = norms
        delta = self._delta(params, key)
        return {"losses": [float(x) for x in losses],
                "grad_norms": [float(x) for x in grad_norms],
                "delta_norms": [float(x) for x in delta]}
