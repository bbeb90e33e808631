"""Multi-process runtime tests: real OS processes, the reference's MetaTest
shape (tests/meta_test.py:27-86 — all roles on one machine over loopback)
upgraded to the JAX world:

- global-mesh mode: 2 processes x 4 virtual CPU chips rendezvous through
  jax.distributed (the scheduler-rendezvous analogue, global.cc:283-297)
  and build ONE 8-device mesh; push_pull is an XLA collective over the
  gloo/DCN transport.
- PS mode: 2 worker processes each keep a LOCAL 4-device mesh and sum
  across processes through the DCN PS — the reference's NCCL-intra +
  ps-lite-inter split (docs/architecture.md "General Workflow").
- launcher MetaTest: server + worker as separate OS processes spawned via
  the launcher (bpslaunch analogue), exercising fork/env/socket lifecycle.

Subprocesses configure their own jax (4 CPU devices each) — the parent's
conftest does not apply to them.
"""

import os
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# distinct port blocks per pytest run; each test uses its own sub-block
_PORT_BASE = 21000 + (os.getpid() % 1000)


def _spawn_one(code: str, env: dict):
    """Spawn `code` in a fresh interpreter with a clean jax environment."""
    e = {**os.environ,
         # wedges (e.g. a stale server from a crashed run holding the
         # port) must fail fast, not eat the subprocess timeout
         "BYTEPS_CLIENT_TIMEOUT_S": "120",
         **env,
         "PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", "")}
    # the parent conftest's XLA_FLAGS would force 8 devices; drop it
    e.pop("XLA_FLAGS", None)
    e.pop("JAX_PLATFORMS", None)
    return subprocess.Popen(
        [sys.executable, "-c", code], env=e, cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)


def _finish(procs, timeout=420):  # generous: cold XLA/gloo compile is slow
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
            out += "\n<TIMEOUT>"
        outs.append(out)
    return outs


def _reap(*procs):
    """Kill any still-running subprocess (failure-path cleanup: a leaked
    server keeps LISTENing on its port and can wedge later runs)."""
    for p in procs:
        if p is not None and p.poll() is None:
            p.kill()
            try:
                p.communicate(timeout=10)
            except Exception:
                pass


_GLOBAL_MESH = r"""
import os
from byteps_tpu.utils.jax_compat import force_cpu
force_cpu(4)
import jax
import numpy as np
import byteps_tpu as bps

pid = int(os.environ["PROC_ID"])
bps.init()
assert jax.process_count() == 2, jax.process_count()
assert bps.size() == 2 and bps.rank() == pid, (bps.size(), bps.rank())
from byteps_tpu.core.state import get_state
mesh = get_state().mesh
assert mesh.devices.size == 8, mesh  # global mesh spans both processes

# each process contributes (pid+1) on its 4 local devices: the 8-device
# sum is 4*1 + 4*2 = 12
x = np.full((4, 16), float(pid + 1), np.float32)
out = np.asarray(bps.push_pull(x, stacked=True, average=False, name="g"))
assert np.allclose(out, 12.0), out[:3]
out = np.asarray(bps.push_pull(x, stacked=True, average=True, name="g"))
assert np.allclose(out, 1.5), out[:3]
bps.shutdown()
print("GLOBAL_MESH_OK", pid)
"""


def test_global_mesh_two_processes():
    coord = _PORT_BASE + 100
    procs = [_spawn_one(_GLOBAL_MESH, {
        "BYTEPS_NUM_PROCESS": "2",
        "BYTEPS_PROCESS_ID": str(i),
        "BYTEPS_COORD_PORT": str(coord),
        "PROC_ID": str(i),
    }) for i in range(2)]
    try:
        outs = _finish(procs)
        if any("Multiprocess computations aren't implemented on the CPU "
               "backend" in o for o in outs):
            # capability skip, not an xfail: this jaxlib's CPU backend
            # refuses to COMPILE cross-process collectives (the XLA:CPU
            # runtime has no inter-process transfer layer), so global-mesh
            # mode is unrunnable here by construction. Any other failure
            # mode still fails the test — the skip keys on the exact
            # backend error string.
            pytest.skip(
                "jaxlib CPU backend cannot compile multi-process "
                "collectives (XlaRuntimeError: 'Multiprocess computations "
                "aren't implemented on the CPU backend'); global-mesh "
                "mode needs an accelerator or a jaxlib with CPU "
                "cross-process collective support")
        for i, (p, out) in enumerate(zip(procs, outs)):
            assert p.returncode == 0, f"proc {i} failed:\n{out[-3000:]}"
            assert f"GLOBAL_MESH_OK {i}" in out, out[-2000:]
    finally:
        _reap(*procs)


_PS_WORKER = r"""
import os
from byteps_tpu.utils.jax_compat import force_cpu
force_cpu(4)
import jax
import numpy as np
import byteps_tpu as bps

pid = int(os.environ["PROC_ID"])
bps.init()
assert jax.process_count() == 2
from byteps_tpu.core.state import get_state
st = get_state()
assert st.mesh.devices.size == 4, st.mesh   # LOCAL mesh (PS mode)
assert st.ps_client is not None

# local ICI sum = 4*(pid+1); PS sums across the 2 workers -> 12
x = np.full((4, 8), float(pid + 1), np.float32)
out = np.asarray(bps.push_pull(x, stacked=True, average=False, name="g"))
assert np.allclose(out, 12.0), out[:3]

# a 3-round training-loop shape: both workers stay consistent
w = np.zeros(8, np.float32)
for step in range(3):
    g = np.full((4, 8), float(pid + 1 + step), np.float32)
    gsum = np.asarray(bps.push_pull(g, stacked=True, average=False,
                                    name="grad/w"))
    w -= 0.1 * gsum
print("W_DIGEST", pid, float(w.sum()))
bps.shutdown()
print("PS_WORKER_OK", pid)
"""


def test_ps_mode_two_processes():
    ps_port = _PORT_BASE + 200
    coord = _PORT_BASE + 210
    srv_env = {**os.environ,
               "DMLC_NUM_WORKER": "2", "DMLC_NUM_SERVER": "1",
               "DMLC_PS_ROOT_PORT": str(ps_port), "JAX_PLATFORMS": "cpu",
               "PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", "")}
    srv = subprocess.Popen([sys.executable, "-m", "byteps_tpu.server"],
                           env=srv_env, cwd=REPO, stdout=subprocess.PIPE,
                           stderr=subprocess.STDOUT, text=True)
    time.sleep(1.0)
    workers = []
    try:
        workers = [_spawn_one(_PS_WORKER, {
            "BYTEPS_NUM_PROCESS": "2", "BYTEPS_PROCESS_ID": str(i),
            "BYTEPS_COORD_PORT": str(coord),
            "DMLC_NUM_WORKER": "2", "DMLC_NUM_SERVER": "1",
            "DMLC_WORKER_ID": str(i),
            "DMLC_PS_ROOT_PORT": str(ps_port),
            "BYTEPS_FORCE_DISTRIBUTED": "1",
            "PROC_ID": str(i),
        }) for i in range(2)]
        outs = _finish(workers)
        digests = {}
        for i, (p, out) in enumerate(zip(workers, outs)):
            assert p.returncode == 0, f"worker {i} failed:\n{out[-3000:]}"
            assert f"PS_WORKER_OK {i}" in out, out[-2000:]
            for line in out.splitlines():
                if line.startswith("W_DIGEST"):
                    digests[i] = float(line.split()[2])
        assert digests[0] == digests[1], digests  # weights stayed consistent
        srv.wait(timeout=20)
        assert srv.returncode == 0
    finally:
        _reap(srv, *workers)


_LAUNCH_TRAIN = (
    # the launcher's children inherit JAX_PLATFORMS=cpu from the test
    # environment (tests/conftest.py)
    "import numpy as np, byteps_tpu as bps;"
    "bps.init();"
    "x = np.arange(16, dtype=np.float32);"
    "out = np.asarray(bps.push_pull(x, name='t', average=False));"
    "assert out.shape == (16,), out.shape;"
    "bps.shutdown();"
    "print('LAUNCH_WORKER_OK')"
)


def test_launcher_metatest_roles():
    """The reference MetaTest shape via the launcher: server role + worker
    role as real OS processes over loopback (launch.py:241-249 analogue)."""
    port = _PORT_BASE + 300
    common = {"DMLC_NUM_WORKER": "1", "DMLC_NUM_SERVER": "1",
              "DMLC_PS_ROOT_PORT": str(port), "JAX_PLATFORMS": "cpu",
              "BYTEPS_CLIENT_TIMEOUT_S": "120",
              "PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", "")}
    srv = subprocess.Popen(
        [sys.executable, "-m", "byteps_tpu.launcher"],
        env={**os.environ, **common, "DMLC_ROLE": "server"},
        cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    try:
        time.sleep(1.0)
        wrk = subprocess.run(
            [sys.executable, "-m", "byteps_tpu.launcher",
             sys.executable, "-c", _LAUNCH_TRAIN],
            env={**os.environ, **common, "DMLC_ROLE": "worker",
                 "BYTEPS_FORCE_DISTRIBUTED": "1"},
            cwd=REPO, capture_output=True, text=True, timeout=420)
        assert wrk.returncode == 0, wrk.stdout[-2000:] + wrk.stderr[-2000:]
        assert "LAUNCH_WORKER_OK" in wrk.stdout
        out, _ = srv.communicate(timeout=30)
        assert srv.returncode == 0, out[-2000:]
    finally:
        _reap(srv)
