#!/usr/bin/env python3
"""Records ``tiny_ps.xplane.pb``, the small trace the tier-1 test of
``program_spans`` reads: two ``bench.step`` spans inside one
``bench.window``, each one step of a small MLP through the program's
``make_ps_train_step`` and a loopback server in this process, so the
host lines hold the program's ``bps.`` spans (train thread, XLA's
callback thread, export router, push pool, completion reactor) beside
the chip's ``XLA Ops``. Run on the chip; writes to ``chiprun_out/``.
Kept so that the data file can be made again.

    python benchmark/data/record_tiny_ps_trace.py
"""

import glob
import os
import shutil
import socket
import sys
import tempfile
import threading

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import byteps_tpu as bps
    from benchmark.program_spans import attribute
    from benchmark.trace_reduce import describe
    from byteps_tpu.config import Config
    from byteps_tpu.core.state import get_state
    from byteps_tpu.jax.train import make_ps_train_step
    from byteps_tpu.models import mlp
    from byteps_tpu.parallel.mesh import DP_AXIS, make_mesh
    from byteps_tpu.server import run_server

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    os.environ.update({
        "DMLC_ROLE": "worker", "DMLC_NUM_WORKER": "1",
        "DMLC_NUM_SERVER": "1", "DMLC_PS_ROOT_URI": "127.0.0.1",
        "DMLC_PS_ROOT_PORT": str(port), "BYTEPS_FORCE_DISTRIBUTED": "1",
        # every leaf rides its own key, so every leaf streams
        "BYTEPS_FUSION_BYTES": "0"})
    server = threading.Thread(
        target=run_server,
        args=(port, Config(num_workers=1, num_servers=1)), daemon=True)
    server.start()
    bps.init(mesh=make_mesh({DP_AXIS: 1}, jax.devices()[:1]))

    cfg = mlp.MLPConfig(in_dim=512, hidden=(512, 512), n_classes=16)
    params = mlp.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)
    batch = {"x": jnp.asarray(rng.rand(64, 512), jnp.float32),
             "y": jnp.asarray(rng.randint(0, 16, 64), jnp.int32)}
    tx = optax.adam(1e-3)
    opt = tx.init(params)
    step = make_ps_train_step(lambda p, b: mlp.loss_fn(p, b, cfg), tx,
                              get_state().mesh)
    for _ in range(3):
        params, opt, loss = step(params, opt, batch)
        jax.block_until_ready((params, opt, loss))

    out = tempfile.mkdtemp(prefix="tiny-ps-trace-")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1  # user annotations only: a small file
    jax.profiler.start_trace(out, profiler_options=options)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.step"):
                params, opt, loss = step(params, opt, batch)
                jax.block_until_ready((params, opt, loss))
    jax.profiler.stop_trace()
    reports = bps.get_step_reports()[-2:]
    bps.shutdown()
    server.join(timeout=10)
    (path,) = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    dest = os.path.join(REPO, "chiprun_out", "tiny_ps.xplane.pb")
    shutil.copy(path, dest)
    shutil.rmtree(out, ignore_errors=True)
    print(f"{dest}: {os.path.getsize(dest)} bytes; device "
          f"{jax.devices()[0].device_kind}")
    for row in describe(dest):
        print(row)
    keys = ("compute_ms", "dispatch_ms", "export_tap_span_ms",
            "export_router_busy_ms", "export_materialize_ms",
            "export_submit_ms", "export_router_wait_max_ms")
    for r in reports:
        print("StepReport: " + ", ".join(f"{k}={r[k]!r}" for k in keys))
    a = attribute(dest)
    for k, v in a.items():
        print(f"{k} = {v!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
