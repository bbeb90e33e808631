"""Server-side failure detection (beyond the reference, which has none —
SURVEY.md §5.3): when every connection of a worker dies, the server fails
parked requests immediately so survivors error out in milliseconds instead
of wedging until their client timeout."""

import threading
import time

import numpy as np
import pytest

from byteps_tpu.config import Config
from byteps_tpu.server import run_server
from byteps_tpu.server.client import PSClient
from byteps_tpu.core.registry import TensorRegistry
from byteps_tpu.core.types import DataType

_PORT = [28100]


def _server(num_workers):
    port = _PORT[0]
    _PORT[0] += 1
    t = threading.Thread(
        target=run_server,
        args=(port, Config(num_workers=num_workers, num_servers=1)),
        daemon=True)
    t.start()
    return port, t


def _ctx(name, n, num_workers):
    reg = TensorRegistry(Config(num_workers=num_workers, num_servers=1))
    return reg.init_tensor(name, n * 4, DataType.FLOAT32)


def test_survivor_fails_fast_when_peer_dies(monkeypatch):
    """Worker A pushes and pulls (parks: B hasn't pushed); B disconnects
    without pushing; A's pull must error out well before the 60s client
    timeout."""
    monkeypatch.setenv("BYTEPS_CLIENT_TIMEOUT_S", "60")
    port, t = _server(2)
    addr = [f"127.0.0.1:{port}"]
    c0 = PSClient(addr, worker_id=0)
    c1 = PSClient(addr, worker_id=1)
    n = 1024
    ctx0 = _ctx("g", n, 2)
    ctx1 = _ctx("g", n, 2)
    x = np.ones(n, np.float32)

    result = {}

    def worker_a():
        t0 = time.monotonic()
        try:
            # init barrier inside push_pull; then PUSH; PULL parks on B
            c0.push_pull(ctx0, x.copy(), average=False, num_workers=2)
            result["outcome"] = "completed"
        except RuntimeError:
            result["outcome"] = "error"
        result["elapsed"] = time.monotonic() - t0

    th = threading.Thread(target=worker_a, daemon=True)
    th.start()
    c1.ensure_init(ctx1, n * 4)   # completes the init barrier with A
    time.sleep(1.0)               # A's pull is parked waiting on B's push
    c1.close(shutdown_servers=False)   # B vanishes (elastic/crash)
    th.join(timeout=30)
    assert not th.is_alive(), "survivor still wedged after peer death"
    assert result["outcome"] == "error"
    assert result["elapsed"] < 15, result   # ms-scale in practice, << 60s
    c0.close()
    # B never sent SHUTDOWN, so the (daemon) server thread stays up by
    # design: nothing to wait for
    t.join(timeout=0.5)


def test_round_rearms_after_departure(monkeypatch):
    """After a departure dropped a half-complete round, a fresh pair of
    workers (elastic resume) completes a new round correctly."""
    monkeypatch.setenv("BYTEPS_CLIENT_TIMEOUT_S", "60")
    port, t = _server(2)
    addr = [f"127.0.0.1:{port}"]
    n = 256
    c0 = PSClient(addr, worker_id=0)
    c1 = PSClient(addr, worker_id=1)
    ctx = _ctx("g", n, 2)
    x = np.full(n, 2.0, np.float32)

    fail = {}

    def worker_a():
        try:
            c0.push_pull(ctx, x.copy(), average=False, num_workers=2)
        except RuntimeError:
            fail["a"] = True

    th = threading.Thread(target=worker_a, daemon=True)
    th.start()
    c1.ensure_init(ctx, n * 4)            # complete the init barrier
    time.sleep(0.8)
    c1.close(shutdown_servers=False)      # kill the round
    th.join(timeout=30)
    assert fail.get("a"), "survivor should have errored"

    # elastic resume: worker 1 reconnects; a full round now works and the
    # dropped partial sum must NOT leak into the new aggregate
    c1b = PSClient(addr, worker_id=1)
    res = {}

    def w(c, tag):
        res[tag] = c.push_pull(ctx, x.copy(), average=False, num_workers=2)

    th0 = threading.Thread(target=w, args=(c0, "a"), daemon=True)
    th0.start()
    w(c1b, "b")
    th0.join(timeout=30)
    np.testing.assert_allclose(res["a"], 2 * x, rtol=1e-6)
    np.testing.assert_allclose(res["b"], 2 * x, rtol=1e-6)
    c0.close()
    c1b.close()
    t.join(timeout=10)


def test_clean_shutdown_is_not_a_departure(capfd):
    """Workers exiting via SHUTDOWN must not trigger departure handling
    (no spurious 'worker departed' on every normal multi-worker exit)."""
    port, t = _server(2)
    addr = [f"127.0.0.1:{port}"]
    c0 = PSClient(addr, worker_id=0)
    c1 = PSClient(addr, worker_id=1)
    n = 64
    ctx0 = _ctx("g", n, 2)
    ctx1 = _ctx("g", n, 2)
    x = np.ones(n, np.float32)
    res = {}

    def w(c, ctx, tag):
        res[tag] = c.push_pull(ctx, x.copy(), average=False, num_workers=2)

    th = threading.Thread(target=w, args=(c1, ctx1, "b"), daemon=True)
    th.start()
    w(c0, ctx0, "a")
    th.join(timeout=30)
    c0.close()                      # clean SHUTDOWN + close, staggered
    time.sleep(0.5)
    c1.close()
    t.join(timeout=10)
    err = capfd.readouterr().err
    assert "departed" not in err, err
