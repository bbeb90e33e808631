"""VGG (Simonyan & Zisserman 2014, configuration D) cross-entropy loss
in plain ``jax.numpy``/``lax`` convolutions, float32 at ``highest``
precision: the reference the timed path is held to, with the seeded
weights and batches both are given. Imports nothing of the program.

Follows the paper: 3x3 convolutions padded to keep the extent, ReLU,
2x2 max-pooling after each stage, 4096-4096-1000 classifier. Departure,
the program's: no dropout on the classifier (rate 0). Weights are drawn
normal with variance 2/fan-in, biases 0.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import HIGHEST


def _fc_shapes(cfg: dict):
    pools = sum(1 for c in cfg["plan"] if c == "M")
    last = [c for c in cfg["plan"] if c != "M"][-1]
    spatial = cfg["image_size"] // (2 ** pools)
    w = cfg["fc_width"]
    return [(last * spatial * spatial, w), (w, w), (w, cfg["n_classes"])]


def init_params(key, cfg: dict):
    keys = iter(jax.random.split(jax.random.fold_in(key, 0), len(cfg["plan"]) + 3))
    params = {}
    cin = 3
    for i, c in enumerate(cfg["plan"]):
        if c == "M":
            continue
        params[f"conv{i}"] = {
            "w": jax.random.normal(next(keys), (3, 3, cin, c), jnp.float32)
            * math.sqrt(2.0 / (9 * cin)),
            "b": jnp.zeros((c,), jnp.float32)}
        cin = c
    for j, (fin, fout) in enumerate(_fc_shapes(cfg)):
        params[f"fc{j}"] = {
            "w": jax.random.normal(next(keys), (fin, fout), jnp.float32)
            * math.sqrt(2.0 / fin),
            "b": jnp.zeros((fout,), jnp.float32)}
    return params


def make_batch(key, index, rows: int, cfg: dict) -> dict:
    """Batch ``index`` of the seed: normal pixels of the configuration's
    standard deviation, uniform classes."""
    k_x, k_y = jax.random.split(jax.random.fold_in(key, 1000 + index))
    n = cfg["image_size"]
    return {"x": jax.random.normal(k_x, (rows, n, n, 3), jnp.float32)
            * cfg["pixel_std"],
            "y": jax.random.randint(k_y, (rows,), 0, cfg["n_classes"],
                                    jnp.int32)}


def nll_sum(params, batch, cfg: dict, operand=None):
    """(sum of the rows' negative log-likelihoods, their count).
    ``operand`` rounds both operands of every convolution and matrix
    product (the control's lower precision)."""
    q_ = operand or (lambda a: a)
    h = batch["x"]
    for i, c in enumerate(cfg["plan"]):
        if c == "M":
            h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max,
                                      (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
            continue
        p = params[f"conv{i}"]
        h = jax.lax.conv_general_dilated(
            q_(h), q_(p["w"]), (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)
        h = jnp.maximum(h + p["b"], 0.0)
    h = h.reshape(h.shape[0], -1)
    for j in range(3):
        p = params[f"fc{j}"]
        h = jnp.matmul(q_(h), q_(p["w"]), precision=HIGHEST) + p["b"]
        if j < 2:
            h = jnp.maximum(h, 0.0)
    logp = jax.nn.log_softmax(h, -1)
    picked = jnp.take_along_axis(logp, batch["y"][:, None], -1)[:, 0]
    return -jnp.sum(picked), jnp.asarray(picked.shape[0], jnp.int32)


def slice_rows(batch: dict, start: int, stop: int) -> dict:
    return {k: v[start:stop] for k, v in batch.items()}


def model_flops_per_step(rows: int, cfg: dict) -> float:
    """Convolution and matrix-product FLOPs of one training step
    (forward + backward, nothing recomputed counted): 2 per
    multiply-add, backward twice the forward. A 3x3 convolution costs
    9 cin cout multiply-adds per output position. The first layer has
    no input gradient to compute, so its backward costs once the
    forward."""
    macs, first = 0, 0
    cin, n = 3, cfg["image_size"]
    for c in cfg["plan"]:
        if c == "M":
            n //= 2
            continue
        macs += 9 * cin * c * n * n
        first = first or 9 * cin * c * n * n
        cin = c
    macs += sum(fin * fout for fin, fout in _fc_shapes(cfg))
    return 2.0 * (3.0 * macs - first) * rows
