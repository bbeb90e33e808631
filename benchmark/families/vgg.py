"""Family ``vgg``: binds the program's ``models/vgg.py`` to the
benchmark's seeded weights, batches, FLOP count and plain reference."""

from __future__ import annotations

import jax.numpy as jnp

from ..reference import vgg as reference  # noqa: F401  (the harness reads it)


def program_loss(cfg: dict):
    """``loss_fn(params, batch)`` through the program's own model code."""
    from byteps_tpu.models import vgg

    pc = vgg.VGGConfig(
        plan=tuple(cfg["plan"]), fc_width=cfg["fc_width"],
        n_classes=cfg["n_classes"], image_size=cfg["image_size"],
        dtype=jnp.dtype(cfg["compute_dtype"]),
        param_dtype=jnp.dtype(cfg["param_dtype"]))
    return lambda params, batch: vgg.loss_fn(params, batch, pc)
