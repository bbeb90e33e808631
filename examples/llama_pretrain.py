"""Flagship example: Llama pretraining over a dp x tp x sp mesh with
checkpointing and optional compressed push_pull.

Composes the framework end to end (BASELINE configs 3/4 shape):
- GSPMD tier: Megatron tp sharding rules + sequence-parallel batch
  (parallel/sharding.py), XLA inserts the collectives
- gradient sync: in-jit psum over dp (ICI) — or, with --ps, the two-phase
  DCN PS path with optional codec compression (jax/train.py)
- checkpoint: orbax + broadcast-on-restore (utils/checkpoint.py)

    python examples/llama_pretrain.py --size tiny --steps 10
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

import os
import sys

# runnable as `python examples/<name>.py` from anywhere
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import byteps_tpu as bps
from byteps_tpu.models import llama
from byteps_tpu.parallel import sharding as sh
from byteps_tpu.parallel.mesh import DP_AXIS, TP_AXIS, make_mesh
from byteps_tpu.utils.checkpoint import Checkpointer
from byteps_tpu.utils.jax_compat import setup_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", default="tiny", choices=["tiny", "small"])
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--fsdp", action="store_true",
                    help="shard params + optimizer state over dp "
                         "(ZeRO-3, composes with --tp)")
    ap.add_argument("--ps", action="store_true",
                    help="route gradients through the DCN PS")
    ap.add_argument("--compression", default=None,
                    help="codec name for --ps, e.g. onebit")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--health-assert", action="store_true",
                    help="arm the training-health plane (BYTEPS_HEALTH) "
                         "and exit nonzero on ANY anomaly event — the "
                         "dryrun numerics gate the staleness/convergence "
                         "harness reuses (docs/observability.md "
                         "\"Training-health plane\")")
    args = ap.parse_args()
    if args.health_assert:
        # before init(): the config snapshot and the (possibly
        # in-process) servers both read it at construction. Forced, not
        # setdefault — an ambient BYTEPS_HEALTH=0 must not turn the
        # gate into one that silently cannot fail.
        os.environ["BYTEPS_HEALTH"] = "1"
    if args.fsdp and args.ps:
        raise SystemExit(
            "--fsdp and --ps are mutually exclusive: the PS train step "
            "works on replicated params (grads leave the device for the "
            "server), so ZeRO-3 sharding would silently be undone after "
            "the first step. Use --fsdp on the GSPMD tier, or --ps.")

    setup_compile_cache()
    bps.init()
    devices = jax.devices()
    dp = len(devices) // args.tp
    mesh = make_mesh({DP_AXIS: dp, TP_AXIS: args.tp}, devices)

    cfg = (llama.LlamaConfig.tiny() if args.size == "tiny"
           else llama.LlamaConfig.small())
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    tx = optax.adamw(3e-4, weight_decay=0.01)
    opt = tx.init(params)

    pspecs = sh.llama_param_specs(None)
    if args.fsdp:
        # ZeRO-3: dp lands on each large leaf's first free divisible dim,
        # on top of the Megatron TP rules (docs/running.md "FSDP")
        pspecs = sh.fsdp_param_specs(params, axis_size=dp,
                                     base_specs=pspecs)
    pshard = sh.to_shardings(mesh, pspecs)
    oshard = sh.to_shardings(mesh, sh.mirror_opt_specs(tx, params, pspecs))
    bshard = NamedSharding(mesh, P(DP_AXIS))
    params = jax.tree.map(jax.device_put, params, pshard)
    opt = jax.tree.map(jax.device_put, opt, oshard)

    if args.ps:
        from byteps_tpu.jax.train import make_ps_train_step
        comp = {"compressor": args.compression, "ef": "vanilla"} \
            if args.compression else None
        step = make_ps_train_step(
            lambda p, b: llama.loss_fn(p, b, cfg), tx, mesh,
            compression=comp)
    else:
        def train_step(p, o, b):
            loss, g = jax.value_and_grad(
                lambda q: llama.loss_fn(q, b, cfg))(p)
            u, o = tx.update(g, o, p)
            return optax.apply_updates(p, u), o, loss

        jstep = jax.jit(train_step,
                        in_shardings=(pshard, oshard, {"tokens": bshard}),
                        out_shardings=(pshard, oshard,
                                       NamedSharding(mesh, P())))

        def step(p, o, b):
            return jstep(p, o, b)

    ckpt = Checkpointer(args.ckpt, every_steps=10) if args.ckpt else None
    rng = np.random.RandomState(0)
    S = min(cfg.max_seq_len, 256)
    t0 = time.perf_counter()
    for i in range(args.steps):
        toks = jnp.asarray(
            rng.randint(0, cfg.vocab_size, (args.batch * dp, S + 1)),
            jnp.int32)
        with mesh:
            params, opt, loss = step(params, opt, {"tokens": toks})
        if ckpt:
            ckpt.maybe_save(i + 1, {"params": params, "opt_state": opt})
        if bps.rank() == 0 and (i % 5 == 0 or i == args.steps - 1):
            print(f"step {i}: loss={float(loss):.4f}")
    dt = time.perf_counter() - t0
    tok_s = args.steps * args.batch * dp * S / dt
    if bps.rank() == 0:
        print(f"throughput: {tok_s:,.0f} tokens/s "
              f"(mesh dp={dp} tp={args.tp})")
    if args.health_assert:
        from byteps_tpu.core.state import get_state
        plane = get_state().health
        if plane is None or not plane.enabled:
            # armed-proof: a gate that could not arm (e.g.
            # BYTEPS_METRICS=0 disabled the plane) must FAIL, never
            # report a vacuous clean run
            print("HEALTH ASSERT FAILED: health plane did not arm",
                  file=sys.stderr)
            bps.shutdown()
            raise SystemExit(2)
        # engaged-proof: the plane must have OBSERVED gradient rounds
        # (collection rides the PS train step's drain) — an all-zero
        # counter read from a path that never collected is not a clean
        # verdict, it is no verdict
        if not any(r.get("grad_norm") is not None
                   for r in bps.get_step_reports()):
            print("HEALTH ASSERT FAILED: the health plane never "
                  "observed a gradient round — run with --ps (the "
                  "collection rides the DCN PS train step)",
                  file=sys.stderr)
            bps.shutdown()
            raise SystemExit(2)
        anomalies = _health_anomalies()
        if anomalies:
            print(f"HEALTH ASSERT FAILED: {anomalies}", file=sys.stderr)
            bps.shutdown()
            raise SystemExit(2)
        print("health assert: no anomaly events")
    bps.shutdown()


def _health_anomalies() -> dict:
    """Nonzero training-health anomaly counters (core/health.py):
    nonfinite rounds, explosion/collapse/drift events — the
    --health-assert gate. Empty dict = numerically clean run."""
    counters = bps.get_metrics().get("counters", {})
    return {k: v for k, v in counters.items()
            if k in ("health/nonfinite_rounds", "health/explode_events",
                     "health/collapse_events", "health/drift_events")
            and v}


if __name__ == "__main__":
    main()
