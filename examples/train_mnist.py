"""Distributed MNIST-style training — BASELINE config 1 parity.

Mirrors the reference's example/pytorch/train_mnist_byteps.py: init the
framework, broadcast initial parameters, wrap the optimizer so gradients
are push_pulled across the dp axis, train, report accuracy. Uses synthetic
data so the example runs hermetically (no dataset download in the image).

Run (single host, 8-way virtual mesh):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/train_mnist.py
Distributed (PS): launch a server role via `python -m byteps_tpu.launcher`
with DMLC_* env, then run this under a worker role.
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

import os
import sys

# runnable as `python examples/<name>.py` from anywhere
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import byteps_tpu as bps
from byteps_tpu.callbacks import (
    BroadcastGlobalVariablesCallback, CallbackList, MetricAverageCallback,
)
from byteps_tpu.jax import distributed_optimizer
from byteps_tpu.models import mlp
from byteps_tpu.parallel.mesh import DP_AXIS


def synthetic_mnist(n=4096, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.rand(n, 784).astype(np.float32)
    w = rng.randn(784, 10).astype(np.float32)
    y = (x @ w).argmax(-1).astype(np.int32)  # learnable labels
    return x, y


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--lr", type=float, default=0.05)
    args = ap.parse_args()

    bps.init()
    from byteps_tpu.core.state import get_state
    mesh = get_state().mesh
    ndev = mesh.shape.get(DP_AXIS, 1)

    cfg = mlp.MLPConfig()
    params = mlp.init_params(jax.random.PRNGKey(0), cfg)
    tx = distributed_optimizer(optax.sgd(args.lr), axis=DP_AXIS)
    x, y = synthetic_mnist()

    def local_step(p, o, bx, by):
        loss, g = jax.value_and_grad(
            lambda q: mlp.loss_fn(q, {"x": bx, "y": by}, cfg))(p)
        u, o = tx.update(g, o, p)   # tx psums over dp internally
        return optax.apply_updates(p, u), o, jax.lax.pmean(loss, DP_AXIS)

    step = jax.jit(jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(P(), P(), P(DP_AXIS), P(DP_AXIS)),
        out_specs=(P(), P(), P()), check_vma=False))

    cbs = CallbackList([BroadcastGlobalVariablesCallback(0),
                        MetricAverageCallback()])
    train_state = {"params": params, "metrics": {}}
    cbs.on_train_begin(train_state)
    params = train_state["params"]
    opt = tx.init(params)

    from byteps_tpu.data import ShardedDataset, prefetch_to_device

    # per-worker sharded + device-prefetched input pipeline: every worker
    # sees a disjoint slice per epoch, and batch N+1 transfers while batch
    # N computes (byteps_tpu.data)
    loader = ShardedDataset({"x": x, "y": y}, args.batch_size * ndev,
                            seed=0)
    for epoch in range(args.epochs):
        cbs.on_epoch_begin(epoch, train_state)
        losses = []
        for batch in prefetch_to_device(loader.epoch(epoch)):
            params, opt, loss = step(params, opt, batch["x"], batch["y"])
            losses.append(float(loss))
        acc = float(mlp.accuracy(params, {"x": jnp.asarray(x),
                                          "y": jnp.asarray(y)}, cfg))
        train_state["metrics"] = {"loss": float(np.mean(losses)),
                                  "acc": acc}
        cbs.on_epoch_end(epoch, train_state)
        if bps.rank() == 0:
            m = train_state["metrics"]
            print(f"epoch {epoch}: loss={m['loss']:.4f} acc={m['acc']:.3f}")

    bps.shutdown()


if __name__ == "__main__":
    main()
