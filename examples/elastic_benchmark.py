"""Elastic training demo — suspend/resume mid-run.

Mirrors example/pytorch/elastic_benchmark_byteps.py:124-133: train, call
bps.suspend(), rewrite the topology, bps.resume(), keep training — tensor
keys stay stable across the restart because the registry re-declares names
in their original order (reference: global.cc:431-436).

    python examples/elastic_benchmark.py        # single worker, no PS
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
from byteps_tpu.models import mlp
from byteps_tpu.parallel.mesh import DP_AXIS


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps-before", type=int, default=20)
    ap.add_argument("--steps-after", type=int, default=20)
    args = ap.parse_args()

    bps.init()
    from byteps_tpu.core.state import get_state
    cfg = mlp.MLPConfig(in_dim=64, hidden=(128,), n_classes=10)
    params = mlp.init_params(jax.random.PRNGKey(0), cfg)
    tx = optax.sgd(0.05)
    opt = tx.init(params)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(512, 64), jnp.float32)
    y = jnp.asarray(rng.randint(0, 10, 512), jnp.int32)

    def make_step():
        mesh = get_state().mesh

        def local_step(p, o, bx, by):
            loss, g = jax.value_and_grad(
                lambda q: mlp.loss_fn(q, {"x": bx, "y": by}, cfg))(p)
            g = jax.lax.pmean(g, DP_AXIS)
            u, o = tx.update(g, o, p)
            return optax.apply_updates(p, u), o, jax.lax.pmean(loss, DP_AXIS)

        return jax.jit(jax.shard_map(
            local_step, mesh=mesh,
            in_specs=(P(), P(), P(DP_AXIS), P(DP_AXIS)),
            out_specs=(P(), P(), P()), check_vma=False))

    step = make_step()
    loss = jnp.zeros(())
    for i in range(args.steps_before):
        params, opt, loss = step(params, opt, x, y)
    print(f"[elastic] before suspend: step={args.steps_before} "
          f"loss={float(loss):.4f}")

    # --- elastic transition (operations.cc:96-119) ---
    cfgc = get_state().config
    bps.suspend()
    bps.resume(num_workers=max(1, cfgc.num_workers),
               num_servers=cfgc.num_servers)
    step = make_step()  # mesh may have changed; recompile

    for i in range(args.steps_after):
        params, opt, loss = step(params, opt, x, y)
    print(f"[elastic] after resume: step="
          f"{args.steps_before + args.steps_after} loss={float(loss):.4f}")
    bps.shutdown()


if __name__ == "__main__":
    main()
