"""DCN parameter-server tests: all roles on localhost over loopback TCP —
the reference's MetaTest pattern (tests/meta_test.py:27-86), with servers on
background threads instead of subprocesses (the native Run loop releases
the GIL).

Covers: init-push barrier, sync aggregation (first-copy/sum/all-recv),
parked pulls, multi-server key sharding via the registry, async mode,
barrier, multi-round training-loop shape, and elastic reconnect.
"""

import threading
import time

import numpy as np
import pytest

from byteps_tpu.config import Config
from byteps_tpu.core.registry import TensorRegistry
from byteps_tpu.core.types import DataType, RequestType, get_command_type
from byteps_tpu.server import run_server
from byteps_tpu.server.client import PSClient

_NEXT_PORT = [19350]


def start_servers(n_servers: int, num_workers: int, async_mode: bool = False,
                  schedule: bool = False):
    """Spawn n servers on fresh loopback ports; returns (addrs, threads)."""
    import os
    base = _NEXT_PORT[0]
    _NEXT_PORT[0] += n_servers
    cfgkw = dict(num_workers=num_workers, enable_async=async_mode,
                 server_enable_schedule=schedule, num_servers=n_servers)
    threads = []
    for i in range(n_servers):
        cfg = Config(**cfgkw)
        t = threading.Thread(target=run_server, args=(base + i, cfg),
                             daemon=True)
        t.start()
        threads.append(t)
    addrs = [f"127.0.0.1:{base + i}" for i in range(n_servers)]
    return addrs, threads


CMD_F32 = get_command_type(RequestType.DEFAULT_PUSH_PULL, DataType.FLOAT32)


def test_single_worker_roundtrip():
    addrs, threads = start_servers(1, num_workers=1)
    c = PSClient(addrs, worker_id=0)
    x = np.arange(100, dtype=np.float32)
    c.init_key(0, 7, np.zeros_like(x), CMD_F32)
    c.zpush(0, 7, x, CMD_F32)
    out = np.empty_like(x)
    c.zpull(0, 7, out, CMD_F32)
    np.testing.assert_array_equal(out, x)
    c.close()
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()


def test_server_throttle_caps_bandwidth(monkeypatch):
    """BYTEPS_SERVER_THROTTLE_MBPS (the scaling-rule evidence knob,
    docs/best-practice.md) pins the server's payload rate to the cap:
    a 4MB round trip through a 20MB/s server must take ~0.4s/round
    (2x4MB through one bucket), where the unthrottled loopback moves
    GB/s. Asserts both sides: slower than half the wire would allow
    unthrottled, and not pathologically slower than the cap predicts."""
    # NOTE: the env must stay set until the server thread CONSTRUCTS the
    # native Server (the Throttle ctor reads it); monkeypatch restores
    # it at test end, after the server is long up
    monkeypatch.setenv("BYTEPS_SERVER_THROTTLE_MBPS", "20")
    addrs, threads = start_servers(1, num_workers=1)
    c = PSClient(addrs, worker_id=0)
    x = np.random.RandomState(0).randn(1 << 20).astype(np.float32)  # 4MB
    c.init_key(0, 7, np.zeros_like(x), CMD_F32)
    out = np.empty_like(x)
    c.zpush(0, 7, x, CMD_F32)
    c.zpull(0, 7, out, CMD_F32)  # warmup: drains the 50ms burst credit
    t0 = time.perf_counter()
    rounds = 2
    for _ in range(rounds):
        c.zpush(0, 7, x, CMD_F32)
        c.zpull(0, 7, out, CMD_F32)
    dt = time.perf_counter() - t0
    np.testing.assert_allclose(out, x, rtol=1e-5)
    expected = rounds * 2 * x.nbytes / 20e6  # ~0.84s
    assert dt > expected * 0.5, f"throttle not binding: {dt:.3f}s"
    assert dt < expected * 3.0, f"throttle overshooting: {dt:.3f}s"
    c.close()
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()


def test_throttled_servers_scale_bandwidth(monkeypatch):
    """The scaling-rule evidence pair (docs/best-practice.md): with the
    server made the bottleneck by construction (throttle sleeps its
    threads), splitting the key space over TWO equally-throttled
    servers must take materially LESS wall time than one — the
    min(server bw, worker bw) doubling, core-independent. Generous
    bounds: the 2srv wall must be under 0.75x the 1srv wall (ideal
    0.5x), and the 1srv wall must be within its cap's predicted range.

    Each configuration times BEST-OF-2 rounds: on a loaded shared
    host, scheduler jitter hitting the
    two wall() calls asymmetrically can push a single draw past the
    0.75x bound — the per-rep spread here has measured >50%; the best
    round is the capability number the rule speaks about."""
    monkeypatch.setenv("BYTEPS_SERVER_THROTTLE_MBPS", "25")
    x = [np.random.RandomState(i).randn(1 << 19).astype(np.float32)
         for i in range(8)]  # 8 x 2MB keys, placed explicitly below

    def wall(n_servers: int) -> float:
        addrs, threads = start_servers(n_servers, num_workers=1)
        c = PSClient(addrs, worker_id=0)
        srv = [i % n_servers for i in range(len(x))]  # even key split
        for i, g in enumerate(x):
            c.init_key(srv[i], 7 + i, np.zeros_like(g), CMD_F32)

        def one_round():
            # two client threads, keys split between them (the pipeline
            # scheduler's shape): with 2 servers each thread's keys live
            # on its own server, so the two token buckets drain in
            # parallel; with 1 server both threads share one bucket —
            # which is exactly the rule under test. Futures, not bare
            # threads: a zpush/zpull error must FAIL the test, not
            # silently shorten the timed round (same hazard the
            # two-client test below documents)
            import concurrent.futures

            def drain(tid):
                out = np.empty_like(x[0])
                for i, g in enumerate(x):
                    if i % 2 != tid:
                        continue
                    c.zpush(srv[i], 7 + i, g, CMD_F32)
                    c.zpull(srv[i], 7 + i, out, CMD_F32)

            with concurrent.futures.ThreadPoolExecutor(2) as ex:
                for f in [ex.submit(drain, t) for t in range(2)]:
                    f.result(timeout=60)

        one_round()  # warmup: drains burst credit, init barrier
        dt = float("inf")
        for _ in range(2):  # best-of-2: see docstring
            t0 = time.perf_counter()
            one_round()
            dt = min(dt, time.perf_counter() - t0)
        c.close()
        for t in threads:
            t.join(timeout=10)
        return dt

    one = wall(1)
    two = wall(2)
    # 16MB payload x 2 dirs / 25MB/s = ~1.28s expected for 1 server:
    # bounded BOTH ways so an overshooting throttle (which would also
    # inflate `one` and trivially satisfy the ratio) fails loudly
    expected = sum(g.nbytes for g in x) * 2 / 25e6
    assert one > expected * 0.4, f"throttle not binding: {one:.3f}s"
    assert one < expected * 3.0, f"throttle overshooting: {one:.3f}s"
    assert two < one * 0.75, (f"2 throttled servers did not scale: "
                              f"1srv {one:.3f}s vs 2srv {two:.3f}s")


def test_two_workers_sum_and_parked_pull():
    addrs, threads = start_servers(1, num_workers=2)
    c0 = PSClient(addrs, worker_id=0)
    c1 = PSClient(addrs, worker_id=1)
    x0 = np.full(64, 1.5, np.float32)
    x1 = np.full(64, 2.0, np.float32)

    t_init = threading.Thread(
        target=lambda: c1.init_key(0, 3, np.zeros_like(x1), CMD_F32))
    t_init.start()
    c0.init_key(0, 3, np.zeros_like(x0), CMD_F32)  # blocks till both arrive
    t_init.join(timeout=10)
    assert not t_init.is_alive()

    # worker 0 pushes and pulls immediately: the pull must PARK until
    # worker 1's push completes the round
    out0 = np.empty_like(x0)
    done0 = threading.Event()

    def w0():
        c0.zpush(0, 3, x0, CMD_F32)
        c0.zpull(0, 3, out0, CMD_F32)
        done0.set()

    th = threading.Thread(target=w0)
    th.start()
    time.sleep(0.3)
    assert not done0.is_set()          # parked: round incomplete
    c1.zpush(0, 3, x1, CMD_F32)        # completes the round
    assert done0.wait(timeout=10)
    np.testing.assert_allclose(out0, x0 + x1)
    out1 = np.empty_like(x1)
    c1.zpull(0, 3, out1, CMD_F32)
    np.testing.assert_allclose(out1, x0 + x1)
    c0.close()
    c1.close()


@pytest.mark.parametrize("dtype_name", ["float16", "bfloat16", "uint16"])
def test_two_workers_16bit_sum(dtype_name):
    """fp16/bf16/u16 summation on the server: the second worker's push hits
    sum_into (the first is a COPY_FIRST memcpy), which the reference handles
    with an AVX F16C convert-add-convert path (cpu_reducer.cc:59-120). Sums
    must match numpy's same-dtype arithmetic bit-for-bit (both do f32
    accumulate + round-to-nearest-even per element)."""
    import ml_dtypes

    if dtype_name == "float16":
        npdt, wire_dt = np.float16, DataType.FLOAT16
    elif dtype_name == "bfloat16":
        npdt, wire_dt = ml_dtypes.bfloat16, DataType.BFLOAT16
    else:
        npdt, wire_dt = np.uint16, DataType.UINT16
    cmd = get_command_type(RequestType.DEFAULT_PUSH_PULL, wire_dt)

    addrs, threads = start_servers(1, num_workers=2)
    c0 = PSClient(addrs, worker_id=0)
    c1 = PSClient(addrs, worker_id=1)
    rng = np.random.RandomState(7)
    if dtype_name == "uint16":
        x0 = rng.randint(0, 30000, 512).astype(np.uint16)
        x1 = rng.randint(0, 30000, 512).astype(np.uint16)
        expect = (x0 + x1).view(np.uint16)
    else:
        # include subnormals, large values, and exact-halfway cases
        x0 = (rng.randn(512) * 100).astype(npdt)
        x1 = (rng.randn(512) * 100).astype(npdt)
        x0[:4] = [npdt(6e-8), npdt(-6e-8), npdt(0), npdt(65000.0 if
                  dtype_name == "float16" else 3e38)]
        x1[:4] = [npdt(6e-8), npdt(6e-8), npdt(-0.0), npdt(65000.0 if
                  dtype_name == "float16" else 3e38)]
        # expectation mirrors the server's arithmetic (f32 accumulate,
        # then round to the wire dtype); errstate silences the DESIGNED
        # overflow of lane 3 (65000+65000 > f16 max -> inf on both sides)
        with np.errstate(over="ignore"):
            expect = (x0.astype(np.float32)
                      + x1.astype(np.float32)).astype(npdt)
        # prove the comparison isn't inf==inf throughout: exactly the
        # overflow lane is inf, every other lane is finite
        as_f32 = expect.astype(np.float32)
        assert not np.isfinite(as_f32[3])
        assert np.isfinite(np.delete(as_f32, 3)).all()

    wire0 = x0.view(np.uint16)
    wire1 = x1.view(np.uint16)
    t = threading.Thread(
        target=lambda: c1.init_key(0, 5, np.zeros(512, np.uint16), cmd))
    t.start()
    c0.init_key(0, 5, np.zeros(512, np.uint16), cmd)
    t.join(timeout=10)

    t = threading.Thread(target=lambda: c1.zpush(0, 5, wire1, cmd))
    t.start()
    c0.zpush(0, 5, wire0, cmd)
    t.join(timeout=10)
    out = np.empty(512, np.uint16)
    c0.zpull(0, 5, out, cmd)
    np.testing.assert_array_equal(out, expect.view(np.uint16))
    c0.close()
    c1.close()
    for th in threads:
        th.join(timeout=10)
        assert not th.is_alive()


def test_unknown_dtype_rejected_at_init():
    """An out-of-enum wire dtype must be error-replied at init (before a
    store exists) — otherwise a later steady-state push would no-op in
    sum_into and silently publish un-summed data."""
    addrs, threads = start_servers(1, num_workers=1)
    c = PSClient(addrs, worker_id=0)
    bad_cmd = get_command_type(RequestType.DEFAULT_PUSH_PULL, 99)
    with pytest.raises(RuntimeError):
        c.init_key(0, 11, np.zeros(16, np.float32), bad_cmd)
    # the server survives and still serves valid traffic
    c.init_key(0, 12, np.zeros(16, np.float32), CMD_F32)
    c.zpush(0, 12, np.ones(16, np.float32), CMD_F32)
    out = np.empty(16, np.float32)
    c.zpull(0, 12, out, CMD_F32)
    np.testing.assert_allclose(out, 1.0)
    c.close()


def test_multi_server_partitioned_tensor():
    """A 100KB tensor partitioned into 4KB keys spread across 3 servers
    through the registry's hashing, push_pulled at the tensor level."""
    addrs, threads = start_servers(3, num_workers=1)
    reg = TensorRegistry(Config(num_servers=3, partition_bytes=4096))
    ctx = reg.init_tensor("grad/w", nbytes=100_000, dtype=DataType.FLOAT32)
    assert len(ctx.partitions) == 25
    assert len({p.server for p in ctx.partitions}) > 1  # actually spread

    c = PSClient(addrs, worker_id=0)
    x = np.random.RandomState(0).randn(25_000).astype(np.float32)
    c.init_tensor(ctx, np.zeros_like(x))
    out = c.push_pull(ctx, x, average=False)
    np.testing.assert_array_equal(out, x)
    # second round (steady state reuses stores)
    out2 = c.push_pull(ctx, x * 2, average=False)
    np.testing.assert_array_equal(out2, x * 2)
    c.close()


def test_async_mode_accumulates():
    addrs, threads = start_servers(1, num_workers=1, async_mode=True)
    c = PSClient(addrs, worker_id=0)
    x = np.ones(32, np.float32)
    c.init_key(0, 1, np.zeros_like(x), CMD_F32)
    out = np.empty_like(x)
    # async: every push adds into the authoritative store; pulls answer
    # immediately (server.cc:315-319,380-382)
    c.zpush(0, 1, x, CMD_F32)
    c.zpull(0, 1, out, CMD_F32)
    np.testing.assert_allclose(out, 1.0)
    c.zpush(0, 1, x, CMD_F32)
    c.zpull(0, 1, out, CMD_F32)
    np.testing.assert_allclose(out, 2.0)
    c.close()


def test_barrier_releases_all_workers():
    addrs, threads = start_servers(1, num_workers=2)
    c0 = PSClient(addrs, worker_id=0)
    c1 = PSClient(addrs, worker_id=1)
    reached = []

    def wait(c, i):
        c.barrier()
        reached.append(i)

    t0 = threading.Thread(target=wait, args=(c0, 0))
    t0.start()
    time.sleep(0.3)
    assert reached == []               # barrier holds until all arrive
    wait(c1, 1)
    t0.join(timeout=10)
    assert sorted(reached) == [0, 1]
    c0.close()
    c1.close()


def test_training_loop_shape_two_workers():
    """Simulated 2-worker data-parallel loop: each round both workers push
    local grads, pull the sum, apply the same update — weights stay
    identical (the consistency the reference's whole pipeline exists to
    provide)."""
    addrs, threads = start_servers(2, num_workers=2)
    reg = TensorRegistry(Config(num_servers=2, partition_bytes=4096))
    ctx = reg.init_tensor("w", nbytes=40_000, dtype=DataType.FLOAT32)
    c0 = PSClient(addrs, worker_id=0)
    c1 = PSClient(addrs, worker_id=1)
    w0 = np.zeros(10_000, np.float32)
    w1 = np.zeros(10_000, np.float32)
    # JOIN the init barrier via futures (a fixed sleep raced it on
    # loaded hosts, and a bare Thread swallowed exceptions — join()
    # does not re-raise; future.result() does): both inits return only
    # after every worker's init push arrived
    import concurrent.futures

    with concurrent.futures.ThreadPoolExecutor(2) as pool:
        futs = [pool.submit(c.init_tensor, ctx, np.zeros_like(w0))
                for c in (c0, c1)]
        for f in futs:
            f.result(timeout=30)

    rng = np.random.RandomState(0)
    for step in range(3):
        g0 = rng.randn(10_000).astype(np.float32)
        g1 = rng.randn(10_000).astype(np.float32)
        res = {}

        def worker(c, g, tag):
            res[tag] = c.push_pull(ctx, g, average=True, num_workers=2)

        ta = threading.Thread(target=worker, args=(c0, g0, "a"))
        tb = threading.Thread(target=worker, args=(c1, g1, "b"))
        ta.start(); tb.start(); ta.join(10); tb.join(10)
        expected = (g0 + g1) / 2
        np.testing.assert_allclose(res["a"], expected, rtol=1e-6)
        np.testing.assert_allclose(res["b"], expected, rtol=1e-6)
        w0 -= 0.1 * res["a"]
        w1 -= 0.1 * res["b"]
    np.testing.assert_array_equal(w0, w1)
    c0.close()
    c1.close()


def test_elastic_reconnect():
    """Suspend-style disconnect (servers stay up) then reconnect and keep
    using the same keys (global.cc:431-436 resume semantics)."""
    addrs, threads = start_servers(1, num_workers=1)
    c = PSClient(addrs, worker_id=0)
    x = np.ones(16, np.float32)
    c.init_key(0, 5, np.zeros_like(x), CMD_F32)
    c.zpush(0, 5, x, CMD_F32)
    out = np.empty_like(x)
    c.zpull(0, 5, out, CMD_F32)
    c.close(shutdown_servers=False)    # suspend: servers keep running

    c2 = PSClient(addrs, worker_id=0)  # resume
    c2.zpush(0, 5, x * 3, CMD_F32)
    out2 = np.empty_like(x)
    c2.zpull(0, 5, out2, CMD_F32)
    np.testing.assert_allclose(out2, 3.0)
    c2.close()


def test_async_push_roundtrip_and_reject():
    """zpush_async: (a) the happy path round-trips like zpush (the pull
    is the synchronization — per-key FIFO via key-affine conns); (b) a
    server-rejected async push poisons the connection so the paired pull
    fails PROMPTLY (bounded seconds), not after the 600s client timeout:
    the server never counted the push, so the round could otherwise
    never complete."""
    # (the 600s default client timeout is latched process-wide on first
    # request — the <30s assertion below is what proves fail-fast)
    addrs, threads = start_servers(1, num_workers=1)
    c = PSClient(addrs, worker_id=0)
    x = np.arange(256, dtype=np.float32)
    c.init_key(0, 9, np.zeros_like(x), CMD_F32)
    c.zpush_async(0, 9, x, CMD_F32)
    out = np.empty_like(x)
    c.zpull(0, 9, out, CMD_F32)
    np.testing.assert_array_equal(out, x)

    # rejected push: a steady-state PUSH with a length that does not
    # match the store is error-ACKed by the server
    bad = np.zeros(7, np.float32)
    c.zpush_async(0, 9, bad, CMD_F32)
    t0 = time.time()
    with pytest.raises(RuntimeError):
        c.zpull(0, 9, out, CMD_F32)
    assert time.time() - t0 < 30, "poisoned conn did not fail fast"
    c.close()
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()
