"""Colocated shm (IPC) transport tests.

The loopback PS tests already ride the shm transport implicitly (every
127.0.0.1 connection upgrades, tests/test_ps.py); these tests pin the
transport-specific contracts: the upgrade actually engages, the TCP
fallback works when disabled, both transports agree numerically, failure
detection still fires through the silent-TCP liveness signal, and the shm
segments are unlinked (no /dev/shm litter).

Reference: ps-lite's colocated IPC shortcut, enabled by BYTEPS_ENABLE_IPC
(docs/best-practice.md:32).
"""

import os
import threading

import numpy as np
import pytest

from byteps_tpu.config import Config
from byteps_tpu.core.types import DataType, RequestType, get_command_type
from byteps_tpu.server import run_server
from byteps_tpu.server.client import PSClient

from test_ps import start_servers

CMD_F32 = get_command_type(RequestType.DEFAULT_PUSH_PULL, DataType.FLOAT32)


def _shm_names():
    try:
        return {n for n in os.listdir("/dev/shm") if n.startswith("bps-ipc-")}
    except FileNotFoundError:
        return set()


def test_ipc_upgrade_engages_and_unlinks():
    before = _shm_names()
    addrs, threads = start_servers(1, num_workers=1)
    c = PSClient(addrs, worker_id=0)
    assert c.ipc_conns > 0  # loopback => every stripe conn upgrades
    # handshake unlinks the name immediately: nothing new in /dev/shm
    assert _shm_names() <= before
    x = np.arange(4096, dtype=np.float32)
    c.init_key(0, 3, np.zeros_like(x), CMD_F32)
    c.zpush(0, 3, x, CMD_F32)
    out = np.empty_like(x)
    c.zpull(0, 3, out, CMD_F32)
    np.testing.assert_array_equal(out, x)
    c.close()
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()
    assert _shm_names() <= before


def test_ipc_disabled_falls_back_to_tcp(monkeypatch):
    monkeypatch.setenv("BYTEPS_ENABLE_IPC", "0")
    addrs, threads = start_servers(1, num_workers=1)
    c = PSClient(addrs, worker_id=0)
    assert c.ipc_conns == 0
    x = np.linspace(-1, 1, 1000).astype(np.float32)
    c.init_key(0, 5, np.zeros_like(x), CMD_F32)
    c.zpush(0, 5, x, CMD_F32)
    out = np.empty_like(x)
    c.zpull(0, 5, out, CMD_F32)
    np.testing.assert_array_equal(out, x)
    c.close()
    for t in threads:
        t.join(timeout=10)


def test_ipc_two_workers_sum_matches_tcp(monkeypatch):
    """Same 2-worker aggregation, once over shm and once over TCP: the
    transports must be numerically indistinguishable."""
    results = {}
    for label, env in (("ipc", None), ("tcp", "0")):
        if env is None:
            monkeypatch.delenv("BYTEPS_ENABLE_IPC", raising=False)
        else:
            monkeypatch.setenv("BYTEPS_ENABLE_IPC", env)
        addrs, threads = start_servers(1, num_workers=2)
        cs = [PSClient(addrs, worker_id=w) for w in range(2)]
        want_ipc = env is None
        assert all((c.ipc_conns > 0) == want_ipc for c in cs)
        rng = np.random.RandomState(7)
        xs = [rng.randn(8192).astype(np.float32) for _ in range(2)]
        # init blocks until BOTH workers' init pushes arrive: parallel
        its = [threading.Thread(
            target=lambda c=c: c.init_key(0, 11, np.zeros_like(xs[0]),
                                          CMD_F32)) for c in cs]
        for t in its:
            t.start()
        for t in its:
            t.join(timeout=60)
        outs = [np.empty_like(xs[0]) for _ in range(2)]

        def round_trip(w):
            cs[w].zpush(0, 11, xs[w], CMD_F32)
            cs[w].zpull(0, 11, outs[w], CMD_F32)

        ts = [threading.Thread(target=round_trip, args=(w,))
              for w in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        results[label] = outs[0].copy()
        np.testing.assert_array_equal(outs[0], outs[1])
        for c in cs:
            c.close()
        for t in threads:
            t.join(timeout=10)
    np.testing.assert_array_equal(results["ipc"], results["tcp"])


def test_ipc_large_message_exceeds_ring(monkeypatch):
    """Messages larger than the ring stream through in chunks (byte-stream
    semantics, not datagram): a 1MB payload over a 64KB ring."""
    monkeypatch.setenv("BYTEPS_IPC_RING_BYTES", str(64 << 10))
    addrs, threads = start_servers(1, num_workers=1)
    c = PSClient(addrs, worker_id=0)
    assert c.ipc_conns > 0
    x = np.random.RandomState(0).randn(1 << 18).astype(np.float32)  # 1MB
    c.init_key(0, 21, np.zeros_like(x), CMD_F32)
    c.zpush(0, 21, x, CMD_F32)
    out = np.empty_like(x)
    c.zpull(0, 21, out, CMD_F32)
    np.testing.assert_array_equal(out, x)
    c.close()
    for t in threads:
        t.join(timeout=10)


def test_ipc_failure_detection_still_fires():
    """Worker death must still be observed through the silent TCP fd: a
    surviving worker's parked pull errors out instead of wedging."""
    addrs, threads = start_servers(1, num_workers=2)
    c0 = PSClient(addrs, worker_id=0)
    c1 = PSClient(addrs, worker_id=1)
    assert c0.ipc_conns > 0 and c1.ipc_conns > 0
    x = np.ones(1024, np.float32)

    def init(c):
        c.init_key(0, 31, np.zeros_like(x), CMD_F32)

    t0 = threading.Thread(target=init, args=(c0,))
    t1 = threading.Thread(target=init, args=(c1,))
    t0.start(); t1.start(); t0.join(30); t1.join(30)

    c0.zpush(0, 31, x, CMD_F32)
    err = []

    def pull():
        out = np.empty_like(x)
        try:
            c0.zpull(0, 31, out, CMD_F32)  # parks: worker 1 never pushes
        except RuntimeError as e:
            err.append(e)

    t = threading.Thread(target=pull)
    t.start()
    import time
    time.sleep(0.3)
    c1.close(shutdown_servers=False)  # die without SHUTDOWN
    t.join(timeout=30)
    assert not t.is_alive() and err, "parked pull must fail fast"
    c0.close()
    # worker 1 died without SHUTDOWN: the (daemon) server thread stays
    # up by design, so there is nothing to wait for
    for th in threads:
        th.join(timeout=0.5)
