"""One file per optimizer, found by the ``kind`` a configuration's
``optimizer`` states: ``optimizers/<kind>.py`` has

- ``make_tx(hyper)``: the optax transform the program is given;
- ``first_gradient(hyper, opt_state, params0)``: the way back from that
  transform's state after one step to the gradient it was handed (what
  ``correct`` compares with the reference's);
- ``reference_init(params)`` and ``reference_update(params, state,
  grads, **hyper)``: the same optimizer written out in ``jax.numpy`` for
  the plain reference, with nothing of optax or the program in them.

``hyper`` is the configuration's ``optimizer`` without its ``kind``.
"""

from __future__ import annotations

import importlib


def load(optimizer: dict):
    """(module, hyper) for a configuration's ``optimizer``."""
    module = importlib.import_module(
        f"benchmark.optimizers.{optimizer['kind']}")
    return module, {k: v for k, v in optimizer.items() if k != "kind"}
