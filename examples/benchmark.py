"""Synthetic throughput benchmark — the reference's benchmark vehicle.

Mirrors example/pytorch/benchmark_byteps.py:110-140: repeated timed batches,
per-iter throughput lines, mean +- 1.96 sigma summary, scaled totals.
Models: mlp | resnet50 | vgg16 | bert | llama | moe (byteps_tpu.models zoo).

The timed step exercises the REAL communication path, exactly like the
reference (benchmark_byteps.py push_pulls every gradient via
DistributedOptimizer): gradients ride the in-jit mesh collective
(distributed_optimizer inside make_train_step), and when a DCN PS is
configured (DMLC_NUM_SERVER > 0) the step is make_ps_train_step — local
ICI reduce, then the pipelined PUSH/PULL of every gradient through the
server. ``--no-comm`` restores the old compute-only step for A/B-ing the
communication overhead.

    python examples/benchmark.py --model llama --num-iters 5
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

# runnable as `python examples/<name>.py` from anywhere
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)  # noqa: E402 — before the byteps_tpu import

import byteps_tpu as bps
from byteps_tpu.models import bert, llama, mlp, moe, resnet, vgg
from byteps_tpu.utils.jax_compat import setup_compile_cache


def build(model: str, batch_size: int, tiny: bool = False):
    """``tiny``: swap every model for its smoke-scale config — CI hosts
    can't turn the real configs' FLOPs over (bert-large fwd+bwd on one
    CPU core is minutes per batch), and a smoke only checks the path."""
    rng = np.random.RandomState(0)
    key = jax.random.PRNGKey(0)
    if model == "mlp":
        cfg = mlp.MLPConfig()
        params = mlp.init_params(key, cfg)
        batch = {"x": jnp.asarray(rng.rand(batch_size, 784), jnp.float32),
                 "y": jnp.asarray(rng.randint(0, 10, batch_size), jnp.int32)}
        return params, batch, lambda p, b: mlp.loss_fn(p, b, cfg)
    if model == "resnet50":
        cfg = resnet.ResNetConfig.tiny() if tiny \
            else resnet.ResNetConfig.resnet50()
        params, bn_state = resnet.init_params(key, cfg)
        sz = 64 if tiny else 224  # global-pooled: any size is valid
        batch = {"x": jnp.asarray(rng.rand(batch_size, sz, sz, 3),
                                  jnp.float32),
                 "y": jnp.asarray(rng.randint(0, cfg.n_classes, batch_size),
                                  jnp.int32)}
        # throughput-only: BN runs in train mode against the initial
        # running stats every step (same FLOPs as real training; the
        # stat update is deliberately not threaded through the timing
        # loop)
        def loss(p, b):
            l, _ = resnet.loss_fn(p, bn_state, b, cfg)
            return l

        return params, batch, loss
    if model == "vgg16":
        # the reference's bandwidth-stress vehicle (138M params dominated
        # by fc layers; its largest reported wins, docs/performance.md:9)
        cfg = vgg.VGGConfig.tiny() if tiny else vgg.VGGConfig.vgg16()
        params = vgg.init_params(key, cfg)
        sz = cfg.image_size  # the fc stack is sized for it (flatten)
        batch = {"x": jnp.asarray(rng.rand(batch_size, sz, sz, 3),
                                  jnp.float32),
                 "y": jnp.asarray(rng.randint(0, cfg.n_classes, batch_size),
                                  jnp.int32)}
        return params, batch, lambda p, b: vgg.loss_fn(p, b, cfg)
    if model == "bert":
        cfg = bert.BertConfig.tiny() if tiny \
            else bert.BertConfig.bert_large()
        params = bert.init_params(key, cfg)
        seq = min(128, cfg.max_seq_len)
        toks = rng.randint(0, cfg.vocab_size, (batch_size, seq))
        labels = np.where(rng.rand(batch_size, seq) < 0.15,
                          rng.randint(0, cfg.vocab_size, (batch_size, seq)),
                          -1)
        batch = {"tokens": jnp.asarray(toks, jnp.int32),
                 "labels": jnp.asarray(labels, jnp.int32)}
        return params, batch, lambda p, b: bert.loss_fn(p, b, cfg)
    if model == "llama":
        cfg = llama.LlamaConfig.tiny() if tiny \
            else llama.LlamaConfig.small()
        params = llama.init_params(key, cfg)
        toks = rng.randint(0, cfg.vocab_size,
                           (batch_size, (cfg.max_seq_len if tiny else 1024)
                            + 1))
        batch = {"tokens": jnp.asarray(toks, jnp.int32)}
        return params, batch, lambda p, b: llama.loss_fn(p, b, cfg)
    if model == "moe":
        cfg = moe.MoEConfig.tiny() if tiny else moe.MoEConfig.small()
        params = moe.init_params(key, cfg)
        toks = rng.randint(0, cfg.vocab_size,
                           (batch_size, (cfg.max_seq_len if tiny else 512)
                            + 1))
        batch = {"tokens": jnp.asarray(toks, jnp.int32)}
        return params, batch, lambda p, b: moe.loss_fn(p, b, cfg)
    raise SystemExit(f"unknown model {model}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="llama",
                    choices=["mlp", "resnet50", "vgg16", "bert", "llama", "moe"])
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--num-warmup-batches", type=int, default=3)
    ap.add_argument("--num-batches-per-iter", type=int, default=5)
    ap.add_argument("--num-iters", type=int, default=5)
    ap.add_argument("--tiny", action="store_true",
                    help="smoke-scale model configs (CI hosts)")
    ap.add_argument("--no-comm", action="store_true",
                    help="compute-only step (no gradient push_pull) for "
                         "A/B-ing the communication overhead")
    ap.add_argument("--health-assert", action="store_true",
                    help="arm the training-health plane (BYTEPS_HEALTH) "
                         "and exit nonzero on ANY anomaly event — the "
                         "dryrun numerics gate (covers the bert/llama "
                         "zoo; docs/observability.md)")
    args = ap.parse_args()
    if args.health_assert:
        # before init(): config snapshot + in-process servers read it.
        # Forced, not setdefault — an ambient BYTEPS_HEALTH=0 must not
        # turn the gate into one that silently cannot fail.
        os.environ["BYTEPS_HEALTH"] = "1"

    setup_compile_cache()
    bps.init()

    def log(s):
        if bps.rank() == 0:
            print(s, flush=True)

    params, batch, loss_fn = build(args.model, args.batch_size, args.tiny)
    tx = optax.adam(1e-3)

    from byteps_tpu.core.state import get_state
    from byteps_tpu.jax import distributed_optimizer
    from byteps_tpu.jax.train import make_ps_train_step, make_train_step

    state = get_state()
    if args.no_comm:
        comm = "none (--no-comm)"
        opt = tx.init(params)

        def train_step(p, o, b):
            loss, g = jax.value_and_grad(loss_fn)(p, b)
            u, o = tx.update(g, o, p)
            return optax.apply_updates(p, u), o, loss

        stepj = jax.jit(train_step, donate_argnums=(0, 1))
    elif state.ps_client is not None:
        # DCN PS tier: every gradient leaves the chip and rides the
        # pipelined PUSH/PULL through the server (the reference vehicle's
        # actual dataflow, benchmark_byteps.py:110-140)
        comm = "DCN PS (pipelined push_pull)"
        opt = tx.init(params)
        stepj = make_ps_train_step(loss_fn, tx, state.mesh)
    else:
        # in-jit mesh collective: distributed_optimizer's psum rides ICI;
        # batch is sharded on dp inside make_train_step (each device gets
        # batch/n_dev rows — per-worker batch semantics preserved)
        comm = "mesh collective (psum in-jit)"
        dtx = distributed_optimizer(tx)
        opt = dtx.init(params)
        stepj = make_train_step(loss_fn, dtx, state.mesh)

    log(f"Model: {args.model}")
    log(f"Batch size: {args.batch_size}")
    log(f"Number of workers: {bps.size()}")
    log(f"Comm path: {comm}")

    log("Running warmup...")
    loss = None
    for _ in range(args.num_warmup_batches):
        params, opt, loss = stepj(params, opt, batch)
    if loss is not None:
        jax.block_until_ready(loss)

    log("Running benchmark...")
    img_secs = []
    for it in range(args.num_iters):
        t0 = time.perf_counter()
        for _ in range(args.num_batches_per_iter):
            params, opt, loss = stepj(params, opt, batch)
        jax.block_until_ready(loss)
        dt = time.perf_counter() - t0
        img_sec = args.batch_size * args.num_batches_per_iter / dt
        log(f"Iter #{it}: {img_sec:.1f} img/sec per worker")
        img_secs.append(img_sec)

    mean, conf = np.mean(img_secs), 1.96 * np.std(img_secs)
    log(f"Img/sec per worker: {mean:.1f} +-{conf:.1f}")
    log(f"Total img/sec on {bps.size()} worker(s): "
        f"{bps.size() * mean:.1f} +-{bps.size() * conf:.1f}")
    if args.health_assert:
        plane = get_state().health
        if plane is None or not plane.enabled:
            # armed-proof: a gate that could not arm must FAIL, never
            # report a vacuous clean run
            print("HEALTH ASSERT FAILED: health plane did not arm",
                  file=sys.stderr)
            bps.shutdown()
            raise SystemExit(2)
        # engaged-proof: collection rides the DCN PS train step's
        # drain — --no-comm and mesh-collective runs never collect,
        # and an all-zero counter read there is no verdict at all
        if not any(r.get("grad_norm") is not None
                   for r in bps.get_step_reports()):
            print("HEALTH ASSERT FAILED: the health plane never "
                  "observed a gradient round — needs the DCN PS comm "
                  "path (DMLC_NUM_SERVER>=1, not --no-comm)",
                  file=sys.stderr)
            bps.shutdown()
            raise SystemExit(2)
        counters = bps.get_metrics().get("counters", {})
        anomalies = {
            k: v for k, v in counters.items()
            if k in ("health/nonfinite_rounds", "health/explode_events",
                     "health/collapse_events", "health/drift_events")
            and v}
        if anomalies:
            print(f"HEALTH ASSERT FAILED: {anomalies}", file=sys.stderr)
            bps.shutdown()
            raise SystemExit(2)
        log("health assert: no anomaly events")
    bps.shutdown()


if __name__ == "__main__":
    main()
