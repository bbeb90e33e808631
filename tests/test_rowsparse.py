"""Row-sparse push_pull: the reference reserves kRowSparsePushPull
(common.h:267-271, server.h:39-41) but never implements it; here it is a
real op — workers push only the nonzero rows of embedding-style gradients,
the server scatter-adds into the dense store, pulls return the dense
aggregate."""

import threading

import numpy as np
import pytest

from byteps_tpu.config import Config
from byteps_tpu.core.registry import TensorRegistry
from byteps_tpu.core.types import DataType, RequestType, get_command_type
from byteps_tpu.server import run_server
from byteps_tpu.server.client import PSClient

_PORT = [27400]


def _server(num_workers, **cfgkw):
    port = _PORT[0]
    _PORT[0] += 1
    t = threading.Thread(
        target=run_server,
        args=(port, Config(num_workers=num_workers, num_servers=1, **cfgkw)),
        daemon=True)
    t.start()
    return port, t


def _ctx(name, rows, width, num_workers, partition_bytes=None):
    kw = dict(num_workers=num_workers, num_servers=1)
    if partition_bytes:
        kw["partition_bytes"] = partition_bytes
    reg = TensorRegistry(Config(**kw))
    return reg.init_tensor(name, rows * width * 4, DataType.FLOAT32,
                           align_bytes=width * 4)


def _sparse_grad(rng, rows, width, nnz):
    g = np.zeros((rows, width), np.float32)
    ids = rng.choice(rows, nnz, replace=False)
    g[ids] = rng.randn(nnz, width).astype(np.float32)
    return g


def test_two_workers_sparse_sum():
    rows, width = 64, 16
    port, t = _server(2)
    addr = [f"127.0.0.1:{port}"]
    c0, c1 = PSClient(addr, worker_id=0), PSClient(addr, worker_id=1)
    ctx0 = _ctx("emb", rows, width, 2)
    ctx1 = _ctx("emb", rows, width, 2)
    rng = np.random.RandomState(0)
    g0 = _sparse_grad(rng, rows, width, 7)
    g1 = _sparse_grad(rng, rows, width, 9)   # overlapping rows likely
    res = {}

    def w(c, ctx, g, tag):
        res[tag] = c.push_pull_rowsparse(ctx, g, average=False,
                                         num_workers=2)

    th = threading.Thread(target=w, args=(c1, ctx1, g1, "w1"), daemon=True)
    th.start()
    w(c0, ctx0, g0, "w0")
    th.join(timeout=30)
    assert not th.is_alive()
    want = g0 + g1
    np.testing.assert_allclose(res["w0"], want, rtol=1e-6)
    np.testing.assert_allclose(res["w1"], want, rtol=1e-6)
    c0.close()
    c1.close()
    t.join(timeout=10)


def test_sparse_multi_partition_row_alignment():
    """Partitions land on row boundaries (align_bytes) and per-partition
    local ids are remapped correctly."""
    rows, width = 256, 32            # 32KB total
    port, t = _server(1)
    c = PSClient([f"127.0.0.1:{port}"], worker_id=0)
    ctx = _ctx("emb", rows, width, 1, partition_bytes=8192)  # 4 partitions
    assert len(ctx.partitions) > 1
    for p in ctx.partitions:
        assert p.offset % (width * 4) == 0
        assert p.length % (width * 4) == 0
    rng = np.random.RandomState(1)
    g = _sparse_grad(rng, rows, width, 40)
    out = c.push_pull_rowsparse(ctx, g, average=False, num_workers=1)
    np.testing.assert_allclose(out, g, rtol=1e-6)
    # second round: different sparsity pattern (exercises re-zeroing)
    g2 = _sparse_grad(rng, rows, width, 3)
    out2 = c.push_pull_rowsparse(ctx, g2, average=False, num_workers=1)
    np.testing.assert_allclose(out2, g2, rtol=1e-6)
    c.close()
    t.join(timeout=10)


def test_sparse_and_dense_pushes_mix_in_one_round():
    """A round may mix sparse and dense pushes: scatter-add composes with
    the dense first-copy/sum protocol."""
    rows, width = 32, 8
    port, t = _server(2)
    addr = [f"127.0.0.1:{port}"]
    c0, c1 = PSClient(addr, worker_id=0), PSClient(addr, worker_id=1)
    ctx0 = _ctx("mix", rows, width, 2)
    ctx1 = _ctx("mix", rows, width, 2)
    rng = np.random.RandomState(2)
    g_sparse = _sparse_grad(rng, rows, width, 5)
    g_dense = rng.randn(rows, width).astype(np.float32)
    res = {}

    def w_sparse():
        res["s"] = c0.push_pull_rowsparse(ctx0, g_sparse, average=False,
                                          num_workers=2)

    def w_dense():
        res["d"] = c1.push_pull(ctx1, g_dense.reshape(-1).copy(),
                                average=False, num_workers=2)

    th = threading.Thread(target=w_dense, daemon=True)
    th.start()
    w_sparse()
    th.join(timeout=30)
    assert not th.is_alive()
    want = g_sparse + g_dense
    np.testing.assert_allclose(res["s"], want, rtol=1e-6)
    np.testing.assert_allclose(res["d"].reshape(rows, width), want,
                               rtol=1e-6)
    c0.close()
    c1.close()
    t.join(timeout=10)


def test_sparse_bad_ids_rejected():
    """Out-of-range row ids error-reply without corrupting the store."""
    rows, width = 16, 8
    port, t = _server(1)
    c = PSClient([f"127.0.0.1:{port}"], worker_id=0)
    ctx = _ctx("bad", rows, width, 1)
    c.ensure_init(ctx, rows * width * 4)
    cmd = get_command_type(RequestType.ROW_SPARSE_PUSH_PULL,
                           DataType.FLOAT32)
    payload = b"".join((
        np.uint32(1).tobytes(), np.uint32(width).tobytes(),
        np.int32(rows + 5).tobytes(),            # out of range
        np.ones(width, np.float32).tobytes(),
    ))
    with pytest.raises(RuntimeError, match="push failed"):
        c.zpush(0, ctx.partitions[0].key, np.frombuffer(payload, np.uint8),
                cmd)
    # the store still works with a valid round
    g = _sparse_grad(np.random.RandomState(3), rows, width, 2)
    out = c.push_pull_rowsparse(ctx, g, average=False, num_workers=1)
    np.testing.assert_allclose(out, g, rtol=1e-6)
    c.close()
    t.join(timeout=10)


def test_rowsparse_public_api(monkeypatch):
    """bps.push_pull_rowsparse end-to-end through init()."""
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
    try:
        g = _sparse_grad(np.random.RandomState(4), 128, 16, 10)
        out = np.asarray(bps.push_pull_rowsparse(g, "emb/table",
                                                 average=False))
        np.testing.assert_allclose(out, g, rtol=1e-6)
    finally:
        bps.shutdown()
        server.join(timeout=10)
        GlobalState._instance = None


def test_rowsparse_through_scheduler_multipartition(monkeypatch):
    """The public API rides the priority pipeline; multiple row-aligned
    partitions fan out as scheduled tasks with prebuilt sparse payloads."""
    from byteps_tpu.core.state import GlobalState

    port = _PORT[0]
    _PORT[0] += 1
    monkeypatch.setenv("DMLC_NUM_WORKER", "1")
    monkeypatch.setenv("DMLC_NUM_SERVER", "1")
    monkeypatch.setenv("DMLC_PS_ROOT_URI", "127.0.0.1")
    monkeypatch.setenv("DMLC_PS_ROOT_PORT", str(port))
    monkeypatch.setenv("BYTEPS_FORCE_DISTRIBUTED", "1")
    monkeypatch.setenv("BYTEPS_PARTITION_BYTES", "8192")
    server = threading.Thread(
        target=run_server,
        args=(port, Config(num_workers=1, num_servers=1)), daemon=True)
    server.start()
    GlobalState._instance = None
    import byteps_tpu as bps
    bps.init()
    try:
        from byteps_tpu.core.state import get_state
        assert get_state().scheduler is not None
        rows, width = 256, 32       # 32KB -> 4 partitions at 8KB
        g = _sparse_grad(np.random.RandomState(5), rows, width, 30)
        out = np.asarray(bps.push_pull_rowsparse(g, "emb/big",
                                                 average=False))
        np.testing.assert_allclose(out, g, rtol=1e-6)
        ctx = get_state().registry.init_tensor(
            "emb/big", rows * width * 4, None, align_bytes=width * 4)
        assert len(ctx.partitions) > 1
        # second round with a different pattern
        g2 = _sparse_grad(np.random.RandomState(6), rows, width, 4)
        out2 = np.asarray(bps.push_pull_rowsparse(g2, "emb/big",
                                                  average=False))
        np.testing.assert_allclose(out2, g2, rtol=1e-6)
    finally:
        bps.shutdown()
        server.join(timeout=10)
        GlobalState._instance = None


def test_ps_train_step_rowsparse_params(monkeypatch):
    """make_ps_train_step(rowsparse_params=("embed",)): the embedding
    gradient travels row-sparse and training still converges to the same
    trajectory as the dense path (1 worker => both are exact)."""
    import jax
    import jax.numpy as jnp
    import optax

    from byteps_tpu.core.state import GlobalState
    from byteps_tpu.jax.train import make_ps_train_step
    from byteps_tpu.models import llama

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
    try:
        from byteps_tpu.core.state import get_state
        import dataclasses
        cfg = dataclasses.replace(llama.LlamaConfig.tiny(vocab_size=64),
                                  dtype=jnp.float32)
        tx = optax.sgd(0.1)

        def run(**kw):
            params = llama.init_params(jax.random.PRNGKey(0), cfg)
            opt = tx.init(params)
            step = make_ps_train_step(
                lambda p, b: llama.loss_fn(p, b, cfg), tx,
                get_state().mesh, **kw)
            toks = jnp.asarray(np.arange(8 * 33).reshape(8, 33) % 13,
                               jnp.int32)
            for _ in range(3):
                params, opt, loss = step(params, opt, {"tokens": toks})
            return params, float(loss)

        p_dense, l_dense = run()
        p_sparse, l_sparse = run(rowsparse_params=("embed", "lm_head"))
        assert np.isclose(l_dense, l_sparse, rtol=1e-5)
        for a, b in zip(jax.tree.leaves(p_dense), jax.tree.leaves(p_sparse)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)
    finally:
        bps.shutdown()
        server.join(timeout=10)
        GlobalState._instance = None
