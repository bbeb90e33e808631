#!/usr/bin/env python3
"""Records ``tiny.xplane.pb``, the small trace the tier-1 test of
``trace_reduce`` reads: three ``bench.step`` spans inside one
``bench.window``, each a few small matrix products on the chip with the
host asleep between them. Run on the chip; writes to ``chiprun_out/``.
Kept so that the data file can be made again.

    python benchmark/data/record_tiny_trace.py
"""

import glob
import os
import shutil
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)


def main() -> int:
    import jax
    import jax.numpy as jnp

    from benchmark.trace_reduce import describe, reduce_trace

    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((512, 512), jnp.bfloat16)
    f(x).block_until_ready()
    out = tempfile.mkdtemp(prefix="tiny-trace-")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=options)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
                time.sleep(0.002)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    dest = os.path.join(REPO, "chiprun_out", "tiny.xplane.pb")
    shutil.copy(path, dest)
    shutil.rmtree(out, ignore_errors=True)
    print(f"{dest}: {os.path.getsize(dest)} bytes")
    for row in describe(dest):
        print(row)
    r = reduce_trace(dest)
    print(f"window_s={r.window_s!r} busy_s={r.busy_s!r} "
          f"ops={r.op_seconds[:5]!r} gaps={r.gaps[:4]!r} "
          f"spans={ {k: len(v) for k, v in r.spans.items()} }")
    return 0


if __name__ == "__main__":
    sys.exit(main())
