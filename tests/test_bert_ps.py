"""BERT over the PS path — the BASELINE config-3 headline vehicle
(reference README.md:34-40: BERT-large ~90% scaling at 256 GPUs) given a
test vehicle at tiny dims: MLM training through make_ps_train_step must
converge, with and without wire compression, and the examples/benchmark.py
--model bert smoke must run. The dryrun side lives in
__graft_entry__._dryrun_bert_dp_tp (dp x tp Megatron layout)."""

import os
import subprocess
import sys
import threading

import numpy as np
import optax
import pytest

from byteps_tpu.config import Config
from byteps_tpu.server import run_server

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PORT = [20800]


@pytest.fixture()
def ps_env(monkeypatch):
    """One worker + one server on loopback, force-distributed (the
    test_ps_integration pattern)."""
    from byteps_tpu.core.state import GlobalState

    port = _PORT[0]
    _PORT[0] += 1
    monkeypatch.setenv("DMLC_NUM_WORKER", "1")
    monkeypatch.setenv("DMLC_NUM_SERVER", "1")
    monkeypatch.setenv("DMLC_PS_ROOT_URI", "127.0.0.1")
    monkeypatch.setenv("DMLC_PS_ROOT_PORT", str(port))
    monkeypatch.setenv("BYTEPS_FORCE_DISTRIBUTED", "1")
    server = threading.Thread(
        target=run_server,
        args=(port, Config(num_workers=1, num_servers=1)), daemon=True)
    server.start()

    GlobalState._instance = None
    import byteps_tpu as bps
    bps.init()
    yield bps
    bps.shutdown()
    server.join(timeout=10)
    GlobalState._instance = None


def _mlm_batch(cfg, B=8, seed=0):
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    tokens = rng.randint(0, cfg.vocab_size, (B, cfg.max_seq_len))
    labels = np.where(rng.rand(B, cfg.max_seq_len) < 0.15, tokens, -100)
    return {"tokens": jnp.asarray(tokens, jnp.int32),
            "labels": jnp.asarray(labels, jnp.int32)}


def _train_bert(ps_env, steps=12, **ps_kwargs):
    import jax
    from byteps_tpu.core.state import get_state
    from byteps_tpu.jax.train import make_ps_train_step
    from byteps_tpu.models import bert

    cfg = bert.BertConfig.tiny(vocab_size=64, seq=16)
    params = bert.init_params(jax.random.PRNGKey(0), cfg)
    tx = optax.adam(2e-3)
    opt = tx.init(params)
    step = make_ps_train_step(
        lambda p, b: bert.loss_fn(p, b, cfg), tx, get_state().mesh,
        **ps_kwargs)
    batch = _mlm_batch(cfg)
    losses = []
    for _ in range(steps):
        params, opt, loss = step(params, opt, batch)
        losses.append(float(loss))
    return losses


def test_bert_trains_over_ps(ps_env):
    losses = _train_bert(ps_env)
    assert np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0] * 0.7, losses


def test_bert_trains_over_ps_compressed(ps_env):
    """BASELINE config 4 shape (compressed wire) on the BERT vehicle —
    host codec tier so the numpy/native codec stack is what runs."""
    losses = _train_bert(
        ps_env, compression={"compressor": "onebit", "ef": "vanilla"},
        min_compress_bytes=0, device_compress=False)
    assert np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0] * 0.8, losses


def test_benchmark_bert_smoke():
    """examples/benchmark.py --model bert runs end-to-end (the
    reference-format synthetic throughput vehicle)."""
    pin = ("from byteps_tpu.utils.jax_compat import force_cpu; "
           "force_cpu(8); "
           "import runpy, sys; sys.argv = sys.argv[1:]; "
           "runpy.run_path(sys.argv[0], run_name='__main__')")
    r = subprocess.run(
        [sys.executable, "-c", pin,
         os.path.join(REPO, "examples", "benchmark.py"),
         "--model", "bert", "--tiny", "--num-iters", "2",
         "--num-warmup-batches", "1", "--batch-size", "8"],
        cwd=REPO, capture_output=True, text=True, timeout=420,
        env={**os.environ, "PYTHONPATH":
             REPO + os.pathsep + os.environ.get("PYTHONPATH", "")})
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "img/sec" in r.stdout or "examples/sec" in r.stdout or \
        "Total img/sec" in r.stdout, r.stdout[-800:]


def test_multichip_envelope_bounded():
    """Dryrun envelope guard: the dryrun's worst case — every phase running to its full
    per-phase timeout — must fit HALF the driver window, so phase growth
    without budget fails here, in tier-1, instead of silently pushing a
    future driver round past its kill deadline. Also pins the phase
    list to the functions that actually exist (a renamed/removed phase
    fn breaks the product silently otherwise)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "__graft_entry__",
        os.path.join(os.path.dirname(__file__), "..", "__graft_entry__.py"))
    g = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(g)
    phases = g._DRYRUN_PHASES
    assert len(phases) >= 7  # the envelope covers the real suite
    worst_case = len(phases) * g.DRYRUN_PHASE_TIMEOUT_S
    assert worst_case <= g.DRYRUN_DRIVER_WINDOW_S / 2, (
        f"{len(phases)} dryrun phases x {g.DRYRUN_PHASE_TIMEOUT_S:.0f}s "
        f"= {worst_case:.0f}s worst case exceeds half the "
        f"{g.DRYRUN_DRIVER_WINDOW_S:.0f}s driver window — trim a phase "
        f"or grow the budget DELIBERATELY")
    # the re-exec child's hard timeout mirrors the same half-window
    for name, fn in phases:
        assert callable(fn), name
