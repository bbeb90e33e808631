"""Examples are part of the product surface (the reference ships its
example/ scripts as the de-facto benchmark + system tests, SURVEY §4):
smoke them as real subprocesses the way a user runs them, pinned to the
CPU platform (a child inherits neither conftest's config updates nor a
usable TPU on CI)."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# force_cpu pins the 8-device CPU mesh before the script's first device
# query (the environment's JAX_PLATFORMS=cpu alone would give it one
# device). The script path + its args arrive as real argv (no string
# templating).
_PIN = ("from byteps_tpu.utils.jax_compat import force_cpu; force_cpu(8); "
        "import runpy, sys; sys.argv = sys.argv[1:]; "
        "runpy.run_path(sys.argv[0], run_name='__main__')")


def _run_example(name: str, argv: list, timeout: int = 420):
    path = os.path.join(REPO, "examples", name)
    return subprocess.run(
        [sys.executable, "-c", _PIN, path, *argv], cwd=REPO,
        capture_output=True, text=True, timeout=timeout,
        env={**os.environ, "PYTHONPATH":
             REPO + os.pathsep + os.environ.get("PYTHONPATH", "")})


def test_llama_pretrain_tiny_runs():
    r = _run_example("llama_pretrain.py",
                     ["--size", "tiny", "--steps", "3", "--batch", "8"])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]


def test_llama_pretrain_fsdp_tp():
    r = _run_example("llama_pretrain.py",
                     ["--size", "tiny", "--steps", "2", "--batch", "4",
                      "--fsdp", "--tp", "2"])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    # the inert combination must refuse, not silently un-shard
    r = _run_example("llama_pretrain.py",
                     ["--steps", "1", "--fsdp", "--ps"])
    assert r.returncode != 0
    assert "mutually exclusive" in r.stdout + r.stderr


def test_llama_pretrain_health_assert():
    """The dryrun numerics gate (docs/observability.md): a clean tiny
    PS run under --health-assert exits zero naming the verdict, and a
    run whose code path can never collect (no PS) FAILS loudly instead
    of passing vacuously — a gate that cannot fail is no gate."""
    import socket

    # negative first (cheap): without --ps the plane never observes a
    # gradient round — the engaged-proof must refuse the clean verdict
    r = _run_example("llama_pretrain.py",
                     ["--size", "tiny", "--steps", "1", "--batch", "4",
                      "--health-assert"])
    assert r.returncode != 0
    assert "never observed a gradient round" in r.stdout + r.stderr
    # positive: loopback PS (server subprocess + worker example run)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = {**os.environ,
           "PYTHONPATH": REPO + os.pathsep
           + os.environ.get("PYTHONPATH", ""),
           "DMLC_NUM_WORKER": "1", "DMLC_NUM_SERVER": "1",
           "DMLC_PS_ROOT_URI": "127.0.0.1",
           "DMLC_PS_ROOT_PORT": str(port),
           "BYTEPS_FORCE_DISTRIBUTED": "1"}
    srv = subprocess.Popen(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, %r); "
         "from byteps_tpu.config import Config; "
         "from byteps_tpu.server import run_server; "
         "run_server(%d, Config(num_workers=1, num_servers=1))"
         % (REPO, port)],
        cwd=REPO, env=env)
    try:
        r = subprocess.run(
            [sys.executable, "-c", _PIN,
             os.path.join(REPO, "examples", "llama_pretrain.py"),
             "--size", "tiny", "--steps", "2", "--batch", "4", "--ps",
             "--health-assert"],
            cwd=REPO, capture_output=True, text=True, timeout=420,
            env=env)
        assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
        assert "health assert: no anomaly events" in r.stdout
        srv.wait(timeout=30)  # worker shutdown stops the server
    finally:
        if srv.poll() is None:
            srv.kill()


def test_train_mnist_runs():
    r = _run_example("train_mnist.py", ["--epochs", "1",
                                        "--batch-size", "64"])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "accuracy" in r.stdout.lower() or "loss" in r.stdout.lower(), \
        r.stdout[-500:]


def test_tf_train_runs():
    r = _run_example("tf_train.py", [])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "final loss" in r.stdout


@pytest.mark.slow  # >30s: tier-1 headroom (runs in the full suite)
def test_torch_train_all_frontends():
    """The torch-adapter example family (reference train_mnist_byteps +
    benchmark_byteps_ddp + benchmark_cross_barrier_byteps in one script):
    all three frontends run and report a final loss."""
    for fe in ("optimizer", "ddp", "cross_barrier"):
        r = _run_example("torch_train.py", ["--frontend", fe,
                                            "--steps", "6"])
        assert r.returncode == 0, (fe, r.stdout[-2000:] + r.stderr[-2000:])
        assert "final loss" in r.stdout, (fe, r.stdout[-500:])


def _run_example_over_ps(name: str, argv: list, extra_env: dict = None):
    """Run one example through a REAL loopback PS: DMLC env + a server
    subprocess whose lifetime brackets the run (worker shutdown stops
    it). Shared by every adapter-over-PS example test."""
    from byteps_tpu.utils.net import free_port

    port = free_port()
    env = {**os.environ,
           "DMLC_NUM_WORKER": "1", "DMLC_NUM_SERVER": "1",
           "DMLC_PS_ROOT_URI": "127.0.0.1",
           "DMLC_PS_ROOT_PORT": str(port),
           "BYTEPS_FORCE_DISTRIBUTED": "1",
           "PYTHONPATH": REPO + os.pathsep
           + os.environ.get("PYTHONPATH", ""),
           **(extra_env or {})}
    srv = subprocess.Popen(
        [sys.executable, "-m", "byteps_tpu.server"],
        env={**env, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
        stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT)
    try:
        path = os.path.join(REPO, "examples", name)
        r = subprocess.run(
            [sys.executable, "-c", _PIN, path, *argv],
            cwd=REPO, capture_output=True, text=True, timeout=420,
            env=env)
        if r.returncode == 0:
            srv.wait(timeout=30)  # worker shutdown stops the server
        return r
    finally:
        if srv.poll() is None:
            srv.kill()


@pytest.mark.slow  # >30s: tier-1 headroom (runs in the full suite)
def test_torch_train_distributed_ps():
    """The torch example through the loopback PS: this is where
    CrossBarrier's poller/drain path and the DistributedOptimizer's PS
    submits actually execute — the single-worker run above never enters
    them."""
    for fe in ("optimizer", "cross_barrier"):
        r = _run_example_over_ps("torch_train.py",
                                 ["--frontend", fe, "--steps", "6"])
        assert r.returncode == 0, \
            (fe, r.stdout[-2000:] + r.stderr[-2000:])
        assert "final loss" in r.stdout, (fe, r.stdout[-500:])


@pytest.mark.slow  # >30s: tier-1 headroom (runs in the full suite)
def test_benchmark_model_zoo_tiny():
    """examples/benchmark.py --tiny across the model zoo (the reference's
    benchmark vehicle covers its zoo the same way); bert has a dedicated
    smoke in test_bert_ps.py — this covers the rest."""
    for model in ("mlp", "resnet50", "vgg16", "moe", "llama"):
        r = _run_example(
            "benchmark.py",
            ["--model", model, "--tiny", "--num-iters", "1",
             "--num-warmup-batches", "1", "--num-batches-per-iter", "2",
             "--batch-size", "8"])
        assert r.returncode == 0, \
            (model, r.stdout[-2000:] + r.stderr[-2000:])
        assert "img/sec" in r.stdout, (model, r.stdout[-500:])


@pytest.mark.slow  # >30s: tier-1 headroom (runs in the full suite)
def test_tf1_train_runs():
    """The v1 Session example (MonitoredTrainingSession + broadcast hook
    + v1 DistributedOptimizer) trains."""
    r = _run_example("tf1_train.py", ["--steps", "30"])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "final loss" in r.stdout, r.stdout[-500:]


def _first_and_final_loss(stdout: str):
    import re
    first = re.search(r"step\s+0 loss ([\d.]+)", stdout)
    final = re.search(r"final loss ([\d.]+)", stdout)
    assert first and final, stdout[-500:]
    return float(first.group(1)), float(final.group(1))


def test_mxnet_train_runs():
    """The mxnet-adapter example family (reference train_mnist_byteps +
    train_gluon_mnist_byteps): both frontends run (against the NDArray
    shim — mxnet is not in the image) and loss descends."""
    for fe in ("trainer", "optimizer"):
        r = _run_example("mxnet_train.py", ["--frontend", fe,
                                            "--steps", "15"])
        assert r.returncode == 0, (fe, r.stdout[-2000:] + r.stderr[-2000:])
        first, final = _first_and_final_loss(r.stdout)
        assert final < first, (fe, r.stdout[-500:])


def test_mxnet_train_compressed_ps():
    """The gluon trainer example through a REAL loopback PS with the
    onebit codec — the compression_params path only engages when a PS is
    configured."""
    r = _run_example_over_ps(
        "mxnet_train.py", ["--compression", "onebit", "--steps", "10"],
        extra_env={"BYTEPS_MIN_COMPRESS_BYTES": "0"})
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    first, final = _first_and_final_loss(r.stdout)
    assert final < first, r.stdout[-500:]
