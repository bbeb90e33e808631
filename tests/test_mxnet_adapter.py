"""byteps_tpu.mxnet adapter: KVStore-style optimizer + gluon trainer over
the DCN PS (reference: byteps/mxnet/__init__.py, tests/test_mxnet.py —
push_pull is identity at size 1, sums across workers, and the trainer
pre-scales so the sum IS the average).

MXNet itself is not in the image; _fake_mxnet provides the exact
NDArray/optimizer/gluon surface the adapter duck-types against.
"""

import struct
import threading

import numpy as np
import pytest

import _fake_mxnet

from byteps_tpu.config import Config
from byteps_tpu.core.registry import TensorRegistry
from byteps_tpu.core.types import DataType
from byteps_tpu.server import run_server
from byteps_tpu.server.client import PSClient

_PORT = [23800]


def _fresh_state():
    from byteps_tpu.core.state import GlobalState
    GlobalState._instance = None


@pytest.fixture()
def mx():
    return _fake_mxnet.install()


@pytest.fixture()
def bpm(mx, bps):
    """MXNet adapter over the plain (no-PS) initialized core."""
    import byteps_tpu.mxnet as bpm_mod
    bpm_mod.parameter_index = 0
    bpm_mod.ops.reset_declarations()
    yield bpm_mod
    bpm_mod.ops.reset_declarations()


def _ps_env(monkeypatch, port, num_workers=1, worker_id=0):
    monkeypatch.setenv("DMLC_NUM_WORKER", str(num_workers))
    monkeypatch.setenv("DMLC_NUM_SERVER", "1")
    monkeypatch.setenv("DMLC_WORKER_ID", str(worker_id))
    monkeypatch.setenv("DMLC_PS_ROOT_URI", "127.0.0.1")
    monkeypatch.setenv("DMLC_PS_ROOT_PORT", str(port))
    monkeypatch.setenv("BYTEPS_FORCE_DISTRIBUTED", "1")


@pytest.fixture()
def bpm_ps(mx, monkeypatch, tmp_path):
    """MXNet adapter over a 1-worker loopback PS (full distributed path).
    cwd is a tmp dir so the trainer's lr.s lands there."""
    port = _PORT[0]
    _PORT[0] += 1
    monkeypatch.chdir(tmp_path)
    _ps_env(monkeypatch, port)
    server = threading.Thread(
        target=run_server,
        args=(port, Config(num_workers=1, num_servers=1)), daemon=True)
    server.start()
    _fresh_state()
    import byteps_tpu.mxnet as bpm_mod
    bpm_mod.parameter_index = 0
    bpm_mod.ops.reset_declarations()
    bpm_mod.init()
    yield bpm_mod
    bpm_mod.shutdown()
    server.join(timeout=10)
    _fresh_state()


def test_push_pull_identity_single_worker(bpm, mx):
    x = np.random.RandomState(0).randn(32).astype(np.float32)
    t = mx.nd.array(x)
    bpm.byteps_declare_tensor("mx_t0")
    out = bpm.byteps_push_pull(t, name="mx_t0", is_average=True)
    np.testing.assert_allclose(out.asnumpy(), x, rtol=1e-6)


def test_push_pull_requires_name(bpm, mx):
    with pytest.raises(ValueError):
        bpm.byteps_push_pull(mx.nd.zeros((4,)))


def test_async_poll_synchronize(bpm, mx):
    t = mx.nd.array(np.ones(8, np.float32))
    h = bpm.byteps_push_pull_async(t, name="mx_async")
    assert bpm.poll(h)
    out = bpm.synchronize(h)
    np.testing.assert_allclose(out.asnumpy(), 1.0)


def test_distributed_optimizer_sync_via_ps(bpm_ps, mx):
    """Sync mode: grads are push_pulled (identity at 1 worker) then the
    wrapped optimizer applies them — weights match plain SGD."""
    lr = 0.1
    opt = bpm_ps.DistributedOptimizer(mx.optimizer.SGD(learning_rate=lr))
    w = mx.nd.array(np.ones(16, np.float32))
    g = mx.nd.array(np.full(16, 0.5, np.float32))
    opt.update(0, w, g, opt.create_state(0, w))
    np.testing.assert_allclose(w.asnumpy(), 1.0 - lr * 0.5, rtol=1e-6)
    # delegation surface
    assert opt.learning_rate == lr
    opt.set_learning_rate(0.05)
    assert opt._optimizer.learning_rate == 0.05


def test_trainer_step_via_ps(bpm_ps, mx):
    """One trainer step at batch_size=4: the gradient is pre-scaled by
    1/(batch*size) and summed (identity here), so weights move by
    lr * g/4; lr.s carries the current learning rate."""
    lr = 0.2
    p0 = mx.gluon.Parameter("w0", np.ones(8, np.float32))
    p1 = mx.gluon.Parameter("w1", np.full(4, 2.0, np.float32))
    trainer = bpm_ps.DistributedTrainer(
        [p0, p1], "sgd", {"learning_rate": lr})
    p0._grad[0][:] = np.full(8, 4.0, np.float32)
    p1._grad[0][:] = np.full(4, 8.0, np.float32)
    trainer.step(4)
    np.testing.assert_allclose(p0._data[0].asnumpy(), 1.0 - lr * 1.0,
                               rtol=1e-6)
    np.testing.assert_allclose(p1._data[0].asnumpy(), 2.0 - lr * 2.0,
                               rtol=1e-6)
    with open("lr.s", "rb") as f:
        assert struct.unpack("d", f.read(8))[0] == lr


def test_trainer_two_worker_average(mx, monkeypatch, tmp_path):
    """Worker 0 = the gluon trainer; worker 1 = a raw PSClient replaying
    the same declaration order. The trainer's pre-scaled sum equals the
    cross-worker average of per-example gradients."""
    port = _PORT[0]
    _PORT[0] += 1
    monkeypatch.chdir(tmp_path)
    _ps_env(monkeypatch, port, num_workers=2, worker_id=0)
    server = threading.Thread(
        target=run_server,
        args=(port, Config(num_workers=2, num_servers=1)), daemon=True)
    server.start()
    _fresh_state()
    import byteps_tpu.mxnet as bpm
    bpm.parameter_index = 0
    bpm.ops.reset_declarations()
    bpm.init()
    try:
        w0 = np.ones(8, np.float32)
        g0 = np.full(8, 2.0, np.float32)
        g1 = np.full(8, 6.0, np.float32)
        batch = 2
        lr = 0.1

        # worker 1: same names, same order -> same keys
        reg = TensorRegistry(Config(num_workers=2, num_servers=1))
        c1 = PSClient([f"127.0.0.1:{port}"], worker_id=1)
        res = {}

        def w1():
            pctx = reg.init_tensor("parameter_0", w0.nbytes,
                                   DataType.FLOAT32)
            res["param"] = c1.push_pull(pctx, np.zeros_like(w0),
                                        average=False, num_workers=2)
            gctx = reg.init_tensor("gradient_0", g1.nbytes,
                                   DataType.FLOAT32)
            res["grad"] = c1.push_pull(gctx, g1 / (batch * 2),
                                       average=False, num_workers=2)

        th = threading.Thread(target=w1, daemon=True)
        th.start()

        p = mx.gluon.Parameter("w", w0)
        trainer = bpm.DistributedTrainer([p], "sgd",
                                         {"learning_rate": lr})
        p._grad[0][:] = g0
        trainer.step(batch)
        th.join(timeout=60)
        assert not th.is_alive()

        mean_grad = (g0 / batch + g1 / batch) / 2
        np.testing.assert_allclose(res["param"], w0, rtol=1e-6)
        np.testing.assert_allclose(res["grad"], mean_grad, rtol=1e-5)
        np.testing.assert_allclose(p._data[0].asnumpy(),
                                   w0 - lr * mean_grad, rtol=1e-5)
        c1.close()
    finally:
        bpm.shutdown()
        server.join(timeout=10)
        _fresh_state()


def test_distributed_optimizer_async_mode(mx, monkeypatch):
    """BYTEPS_ENABLE_ASYNC: the optimizer seeds the server store with the
    PRE-update weights, pushes the weight delta, and pulls authoritative
    weights — so the first step yields w0 - lr*g, not a bare delta
    (regression: unseeded async lost the initial weights)."""
    port = _PORT[0]
    _PORT[0] += 1
    _ps_env(monkeypatch, port, num_workers=2, worker_id=0)
    monkeypatch.setenv("BYTEPS_ENABLE_ASYNC", "1")
    server = threading.Thread(
        target=run_server,
        args=(port, Config(num_workers=2, num_servers=1,
                           enable_async=True)), daemon=True)
    server.start()
    _fresh_state()
    import byteps_tpu.mxnet as bpm
    bpm.parameter_index = 0
    bpm.ops.reset_declarations()
    bpm.init()
    try:
        lr = 0.1
        w0 = np.arange(16, dtype=np.float32)
        g = np.full(16, 2.0, np.float32)

        reg = TensorRegistry(Config(num_workers=2, num_servers=1))
        c1 = PSClient([f"127.0.0.1:{port}"], worker_id=1)

        def w1():
            ctx = reg.init_tensor("weight_5", w0.nbytes, DataType.FLOAT32)
            c1.init_weights(ctx, w0.copy())   # init barrier participant
            c1.push_delta_pull_weights(ctx, np.zeros_like(w0))

        th = threading.Thread(target=w1, daemon=True)
        th.start()

        opt = bpm.DistributedOptimizer(mx.optimizer.SGD(learning_rate=lr))
        w = mx.nd.array(w0.copy())
        opt.update(5, w, mx.nd.array(g), None)
        th.join(timeout=60)
        assert not th.is_alive()
        np.testing.assert_allclose(w.asnumpy(), w0 - lr * g, rtol=1e-5)
        c1.close()
    finally:
        bpm.shutdown()
        server.join(timeout=10)
        _fresh_state()


def test_broadcast_parameters_two_workers(mx, monkeypatch):
    """broadcast_parameters: non-root pushes zeros, so everyone ends up
    with the root's values."""
    port = _PORT[0]
    _PORT[0] += 1
    _ps_env(monkeypatch, port, num_workers=2, worker_id=0)
    server = threading.Thread(
        target=run_server,
        args=(port, Config(num_workers=2, num_servers=1)), daemon=True)
    server.start()
    _fresh_state()
    import byteps_tpu.mxnet as bpm
    bpm.parameter_index = 0
    bpm.ops.reset_declarations()
    bpm.init()
    try:
        vals = np.arange(16, dtype=np.float32)
        t = mx.nd.array(vals)

        reg = TensorRegistry(Config(num_workers=2, num_servers=1))
        c1 = PSClient([f"127.0.0.1:{port}"], worker_id=1)
        res = {}

        def w1():
            ctx = reg.init_tensor("broadcast_parameter_0", vals.nbytes,
                                  DataType.FLOAT32)
            res["w1"] = c1.push_pull(ctx, np.zeros_like(vals),
                                     average=False, num_workers=2)

        th = threading.Thread(target=w1, daemon=True)
        th.start()
        bpm.broadcast_parameters({"w": t}, root_rank=0)
        th.join(timeout=60)
        assert not th.is_alive()
        np.testing.assert_allclose(t.asnumpy(), vals)
        np.testing.assert_allclose(res["w1"], vals)
        c1.close()
    finally:
        bpm.shutdown()
        server.join(timeout=10)
        _fresh_state()


def test_compression_params_routing(bpm_ps, mx, monkeypatch):
    """compression_params sets byteps_* attributes, strips momentum/wd
    from the optimizer (the comm stack owns them), and builds the
    nag(wdmom(none)) intra stack — the reference's contract
    (mxnet/__init__.py:236-317)."""
    monkeypatch.setenv("BYTEPS_MIN_COMPRESS_BYTES", "0")
    params = [mx.gluon.Parameter("a", np.ones(64, np.float32)),
              mx.gluon.Parameter("b", np.ones(8, np.float32))]
    trainer = bpm_ps.DistributedTrainer(
        params, "sgd",
        {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4},
        compression_params={"compressor": "onebit", "scaling": True,
                            "ef": "vanilla", "momentum": "nesterov"})
    for p in params:
        assert p.byteps_compressor_type == "onebit"
        assert p.byteps_ef_type == "vanilla"
        assert p.byteps_momentum_type == "nesterov"
        assert p.byteps_compressor_onebit_scaling == "True"
        assert p.byteps_momentum_mu == 0.9
    # stripped from the optimizer
    assert trainer._optimizer.momentum == 0.0
    assert trainer._optimizer.wd == 0.0
    from byteps_tpu.mxnet.compression import (NagAdapter,
                                              WeightDecayMomentumAdapter)
    stack = trainer._intra_compressors["a"]
    assert isinstance(stack, NagAdapter)
    assert isinstance(stack.compressor, WeightDecayMomentumAdapter)
    # a full step runs through the compressed PS path
    params[0]._grad[0][:] = np.random.RandomState(0).randn(64).astype(
        np.float32)
    params[1]._grad[0][:] = np.random.RandomState(1).randn(8).astype(
        np.float32)
    trainer.step(1)
    from byteps_tpu.mxnet import ops as mxops
    assert "gradient_0" in mxops._comp_regs  # codec tier engaged
    assert not np.allclose(params[0]._data[0].asnumpy(), 1.0)


def test_trainer_compressed_randomk_roundtrip(bpm_ps, mx, monkeypatch):
    """randomk+EF through the real server codec mirror: training signal
    survives (EF accumulates what the sparsifier drops)."""
    monkeypatch.setenv("BYTEPS_MIN_COMPRESS_BYTES", "0")
    p = mx.gluon.Parameter("w", np.zeros(32, np.float32))
    trainer = bpm_ps.DistributedTrainer(
        [p], "sgd", {"learning_rate": 0.5},
        compression_params={"compressor": "randomk", "k": 8, "seed": 3})
    g = np.random.RandomState(2).randn(32).astype(np.float32)
    moved = np.zeros(32, np.float32)
    for _ in range(8):
        p._grad[0][:] = g
        before = p._data[0].asnumpy()
        trainer.step(1)
        moved += before - p._data[0].asnumpy()
    # over 8 steps the randomk samples cover most coordinates; the total
    # movement must correlate strongly with the true gradient direction
    cos = np.dot(moved, g) / (np.linalg.norm(moved) * np.linalg.norm(g))
    assert cos > 0.5


def test_nag_adapter_math(mx):
    """NAG wrapper recurrence: m <- mu*(m+g); g <- g+m (below threshold
    only)."""
    from byteps_tpu.mxnet.compression import Compression, NoneCompressor
    mu = 0.9
    nag = Compression.nag(NoneCompressor(), mu, threshold=1000)
    g = np.full(4, 1.0, np.float32)
    mom = np.zeros(4, np.float32)
    for _ in range(3):
        t, ctx = nag.compress(mx.nd.array(g))
        out = nag.decompress(t, ctx).asnumpy()
        mom = mu * (mom + g)
        np.testing.assert_allclose(out, g + mom, rtol=1e-6)


def test_wdmom_adapter_math(mx):
    """wd-momentum wrapper: m <- mu*(m + wd*x); g <- g + m + wd*x (above
    threshold)."""
    from byteps_tpu.mxnet.compression import Compression, NoneCompressor
    mu, wd = 0.9, 0.01
    wdm = Compression.wdmom(NoneCompressor(), mu, wd, threshold=0)
    x = np.full(4, 2.0, np.float32)
    g = np.full(4, 1.0, np.float32)
    mom = np.zeros(4, np.float32)
    for _ in range(3):
        t, ctx = wdm.compress(mx.nd.array(g))
        out = wdm.decompress(t, ctx, x=mx.nd.array(x)).asnumpy()
        mom = mu * (mom + wd * x)
        np.testing.assert_allclose(out, g + mom + wd * x, rtol=1e-5)


def test_fp16_compressor(mx):
    from byteps_tpu.mxnet.compression import Compression
    x = mx.nd.array(np.random.RandomState(0).randn(16).astype(np.float32))
    c, ctx = Compression.fp16.compress(x)
    assert c.dtype == np.float16
    out = Compression.fp16.decompress(c, ctx)
    assert out.dtype == np.float32
    np.testing.assert_allclose(out.asnumpy(), x.asnumpy(), atol=1e-2)


def test_distributed_trainer_unwraps_distributed_optimizer(bpm_ps, mx):
    with pytest.warns(UserWarning):
        trainer = bpm_ps.DistributedTrainer(
            [mx.gluon.Parameter("w", np.ones(4, np.float32))],
            bpm_ps.DistributedOptimizer(
                mx.optimizer.SGD(learning_rate=0.1)))
    assert not isinstance(trainer._optimizer, bpm_ps.DistributedOptimizer)
