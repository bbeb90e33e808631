"""CPU-side checks of the chip smoke (chip_smoke.py) and of what it rests
on: with no TPU it fails and prints no result line; its tiny-size
rehearsal (the same code, CPU only, a control-flow check) reaches the
comparison; the compile-cache helper places the cache as documented; an
unknown device kind has no peak. The smoke itself runs on the chip, never
here.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(argv, cwd=REPO, script=SMOKE, timeout=300, **env):
    return subprocess.run(
        [sys.executable, script, *argv], cwd=cwd, capture_output=True,
        text=True, timeout=timeout,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **env})


def _no_result_line(stdout: str) -> bool:
    return not any(ln.lstrip().startswith("{") and '"ok"' in ln
                   for ln in stdout.splitlines())


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]],
                         ids=["one-chip", "four-chips"])
def test_smoke_fails_without_tpu(argv):
    r = _run(argv)
    assert r.returncode != 0
    assert _no_result_line(r.stdout), r.stdout[-500:]
    assert "no accelerator" in r.stderr


def test_smoke_fails_alone_in_a_directory(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo it must fail, not pass vacuously."""
    script = shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    r = _run([], cwd=str(tmp_path), script=str(script))
    assert r.returncode != 0
    assert _no_result_line(r.stdout), r.stdout[-500:]


@pytest.mark.parametrize("chips", [1, 4])
def test_rehearsal_reaches_comparison(chips):
    """The smoke's own code at a tiny size: server child, bps.init(),
    PS step vs fused control, engagement counters, comparison, shutdown
    (with --chips 4 the reduce-scatter/shard-export path on four virtual
    CPU devices). A rehearsal never prints the contract's result line."""
    r = _run(["--rehearse", "--chips", str(chips)],
             XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}")
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert _no_result_line(r.stdout)
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] == "reached comparison"
    assert last["device"] == {"platform": "cpu", "kind": "cpu",
                              "count": chips}
    assert "params after" in r.stdout and "server child exited 0" in r.stdout
    assert ("export: shard_leaves=" in r.stdout) == (chips == 4)


@pytest.mark.parametrize("env,want_dir,want_update", [
    # placed from outside: jax reads the variable itself, code sets nothing
    ({"JAX_COMPILATION_CACHE_DIR": "/somewhere/else"}, "/somewhere/else",
     False),
    # pinned to the CPU mesh: no cache at all
    ({"JAX_PLATFORMS": "cpu"}, None, False),
    # otherwise: <checkout>/.jax_cache, a fixed path
    ({}, os.path.join(REPO, ".jax_cache"), True),
], ids=["env-placed", "cpu-pinned", "default"])
def test_compile_cache_helper(monkeypatch, env, want_dir, want_update):
    import jax

    from byteps_tpu.utils import jax_compat

    for k in ("JAX_COMPILATION_CACHE_DIR", "JAX_PLATFORMS"):
        monkeypatch.delenv(k, raising=False)
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    assert jax_compat.setup_compile_cache() == want_dir
    assert updates == ([("jax_compilation_cache_dir", want_dir)]
                       if want_update else [])


def test_detect_peak_raises_on_unknown_kind():
    from byteps_tpu.core.ledger import detect_peak

    with pytest.raises(ValueError, match="no peak known"):
        detect_peak("quantum-accelerator-9000", env={})
    with pytest.raises(ValueError, match="no peak known"):
        detect_peak("", env={})
    # an override must cover BOTH components of an unknown device
    with pytest.raises(ValueError):
        detect_peak("quantum-accelerator-9000",
                    env={"BYTEPS_PEAK_FLOPS": "1e15"})
    assert detect_peak("quantum-accelerator-9000",
                       env={"BYTEPS_PEAK_FLOPS": "1e15",
                            "BYTEPS_PEAK_BW_GBPS": "2000"}) \
        == (1e15, 2000.0, "env")


_SERVER_ROLE = r"""
import threading, numpy as np
from byteps_tpu.config import Config
from byteps_tpu.core.registry import TensorRegistry
from byteps_tpu.core.types import DataType
from byteps_tpu.server import run_server
from byteps_tpu.server.client import PSClient
from byteps_tpu.utils.net import free_port

port = free_port()
cfg = Config(num_workers=1, num_servers=1)
t = threading.Thread(target=run_server, args=(port, cfg), daemon=True)
t.start()
c = PSClient([f"127.0.0.1:{port}"], worker_id=0)
x = np.arange(4096, dtype=np.float32)
ctx = TensorRegistry(cfg).init_tensor("g", x.nbytes, DataType.FLOAT32)
out = c.push_pull(ctx, x.copy(), average=False, num_workers=1)
assert np.array_equal(out, x)
c.close()
t.join(timeout=20)
assert not t.is_alive()
from jax._src import xla_bridge
assert not xla_bridge.backends_are_initialized(), "server role touched a device"
print("SERVER_ROLE_OK")
"""


def test_server_role_initialises_no_backend():
    """One process per chip: the server role (Config, run_server, the
    native library, a full push_pull round and shutdown) must never
    initialise a JAX backend — on the chip machine it would take the
    chip from the worker."""
    r = subprocess.run([sys.executable, "-c", _SERVER_ROLE], cwd=REPO,
                       capture_output=True, text=True, timeout=120,
                       env={**os.environ, "PYTHONPATH": REPO})
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "SERVER_ROLE_OK" in r.stdout
