"""What every plain reference shares: the seeded key, the
lower-precision hook of the control, and the per-leaf norms the
comparison reads. Imports nothing of the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def seed_key(seed: int) -> jax.Array:
    """The run's key. ``--seed`` may exceed 31 bits. The key is an
    argument of every seeded program, never a constant in it, so one
    compiled program serves every seed."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def fp8_operand(x: jax.Array) -> jax.Array:
    """The control's precision: a matmul operand rounded to float8
    (e4m3) under one per-tensor scale, the recipe fp8 training uses, the
    gradient passed straight through. The nearest precision below the
    bf16 the configurations state."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale
    return x + jax.lax.stop_gradient(q - x)


def leaf_norms(tree) -> jax.Array:
    """The l2 norm of every leaf, in flatten order, as one f32 vector."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])
