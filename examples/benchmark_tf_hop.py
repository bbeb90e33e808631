"""Measure the TF adapter's framework-boundary cost (the py_function hop).

The reference registers a native ``BytepsPushPull`` AsyncOpKernel
(reference: byteps/tensorflow/ops.cc:167-231) so graph-mode comm ops run
without touching Python. This rebuild lowers the TF surface through
``tf.py_function`` (docstring divergence, byteps_tpu/tensorflow/__init__.py)
— each comm op re-enters Python, serializing on the GIL and paying an
eager-tensor->numpy hop. This harness puts a number on that divergence:
a ResNet-50-shaped gradient set (~161 tensors,
~25.5M params) is pushed through a loopback PS server three ways:

  raw       — numpy arrays straight into the core scheduler
              (byteps_tpu.push_pull_async): the floor every adapter
              shares; no TF anywhere.
  eager     — the tape's actual arrangement: eager tf tensors through
              submit-all-then-drain (_eager-style push_pull_async +
              synchronize), paying .numpy() + tf.constant per tensor.
  graph     — one tf.function whose body holds an independent
              py_function push_pull per tensor (what
              DistributedGradientTape builds under tf.function).
  graph1    — the batched alternative: a SINGLE py_function that
              submits all tensors then drains (the
              broadcast_global_variables arrangement) — what the
              adapter switches to if the per-tensor hop costs >10%.

Run: python examples/benchmark_tf_hop.py [--steps 5]
Prints one JSON line with per-path seconds/step and overhead vs raw.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def resnet50_grad_shapes():
    """The conv/bn/fc parameter shapes of ResNet-50 (bottleneck v1):
    ~161 tensors, ~25.5M params — the reference's own benchmark model
    family (example/pytorch/benchmark_byteps.py --model resnet50)."""
    shapes = [(7, 7, 3, 64), (64,), (64,)]  # stem conv + bn
    cfg = [(3, 64, 256), (4, 128, 512), (6, 256, 1024), (3, 512, 2048)]
    in_ch = 64
    for blocks, mid, out in cfg:
        for b in range(blocks):
            shapes += [(1, 1, in_ch, mid), (mid,), (mid,),
                       (3, 3, mid, mid), (mid,), (mid,),
                       (1, 1, mid, out), (out,), (out,)]
            if b == 0:  # projection shortcut
                shapes += [(1, 1, in_ch, out), (out,), (out,)]
            in_ch = out
    shapes += [(2048, 1000), (1000,)]  # fc
    return shapes


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=5)
    args = p.parse_args(argv)

    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from byteps_tpu.config import Config
    from byteps_tpu.core.state import GlobalState
    from byteps_tpu.server import run_server
    from byteps_tpu.utils.net import free_port

    port = free_port()
    os.environ.update({
        "DMLC_NUM_WORKER": "1", "DMLC_NUM_SERVER": "1",
        "DMLC_PS_ROOT_URI": "127.0.0.1", "DMLC_PS_ROOT_PORT": str(port),
        "BYTEPS_FORCE_DISTRIBUTED": "1",
    })
    server = threading.Thread(
        target=run_server, args=(port, Config(num_workers=1, num_servers=1)),
        daemon=True)
    server.start()
    GlobalState._instance = None
    import byteps_tpu as bps

    bps.init()
    import tensorflow as tf

    from byteps_tpu import tensorflow as bptf

    rng = np.random.RandomState(0)
    shapes = resnet50_grad_shapes()
    grads_np = [rng.randn(*s).astype(np.float32) for s in shapes]
    nparams = sum(g.size for g in grads_np)
    grads_tf = [tf.constant(g) for g in grads_np]

    def timed(fn) -> float:
        fn()  # warmup: init-push barriers, traces, jit
        t0 = time.perf_counter()
        for _ in range(args.steps):
            fn()
        return (time.perf_counter() - t0) / args.steps

    # --- raw: numpy -> core scheduler (the non-TF floor) ---------------
    def raw_step():
        hs = [bps.push_pull_async(g, f"raw/{i}", average=False)
              for i, g in enumerate(grads_np)]
        for h in hs:
            bps.synchronize(h, timeout=300)

    t_raw = timed(raw_step)

    # --- eager: tf tensors, submit-all-then-drain (tape arrangement) ---
    def eager_step():
        hs = [bptf.push_pull_async(g, f"eager/{i}", average=False)
              for i, g in enumerate(grads_tf)]
        for h in hs:
            bptf.synchronize(h)

    t_eager = timed(eager_step)

    # --- graph: per-tensor py_function ops inside one tf.function ------
    @tf.function
    def graph_step_fn():
        return [bptf.push_pull(g, name=f"graph/{i}", average=False)
                for i, g in enumerate(grads_tf)]

    t_graph = timed(lambda: graph_step_fn())

    # --- graph1: the adapter's PRODUCTION batched boundary — one
    # py_function submitting everything, then ONE GIL-releasing batched
    # wait before the convert loop (_graph_batch_push_pull; measured
    # here so the number tracks the shipped code, not a lookalike) -----
    @tf.function
    def graph1_step_fn():
        return bptf._graph_batch_push_pull(
            [(f"graph1/{i}", g) for i, g in enumerate(grads_tf)],
            bptf.Compression.none)

    t_graph1 = timed(lambda: graph1_step_fn())

    bps.shutdown()
    server.join(timeout=20)

    def pct(t):
        return round((t / t_raw - 1.0) * 100, 1)

    print(json.dumps({
        "n_tensors": len(grads_np), "n_params": int(nparams),
        "steps": args.steps,
        "raw_s": round(t_raw, 4),
        "eager_s": round(t_eager, 4), "eager_overhead_pct": pct(t_eager),
        "graph_s": round(t_graph, 4), "graph_overhead_pct": pct(t_graph),
        "graph1_s": round(t_graph1, 4),
        "graph1_overhead_pct": pct(t_graph1),
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
