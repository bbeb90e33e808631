"""Compressed DCN push_pull: worker host codecs <-> C++ server mirror.

The reference tests codecs by comparing the real C++ path against a numpy
golden model with shared seeded RNG (tests/test_onebit.py etc.,
tests/utils.py:31-51); same here — byteps_tpu.ops.compression.host IS the
golden model and the server must reproduce it on the aggregate."""

import threading

import numpy as np
import pytest

from byteps_tpu.config import Config
from byteps_tpu.core.registry import TensorRegistry
from byteps_tpu.core.types import DataType
from byteps_tpu.ops.compression import host
from byteps_tpu.server import run_server
from byteps_tpu.server.client import PSClient
from byteps_tpu.server.compressed import CompressedTensor

_PORT = [22800]


def _server(num_workers, **cfgkw):
    port = _PORT[0]
    _PORT[0] += 1
    t = threading.Thread(
        target=run_server,
        args=(port, Config(num_workers=num_workers, num_servers=1, **cfgkw)),
        daemon=True)
    t.start()
    return port, t


def _ctx(name, nbytes, num_workers, partition_bytes=None):
    kw = dict(num_workers=num_workers, num_servers=1)
    if partition_bytes:
        kw["partition_bytes"] = partition_bytes
    reg = TensorRegistry(Config(**kw))
    return reg.init_tensor(name, nbytes, DataType.FLOAT32)


def _two_worker_roundtrip(kwargs, x0, x1, partition_bytes=None):
    num_workers = 2
    port, t = _server(num_workers)
    addr = [f"127.0.0.1:{port}"]
    c0 = PSClient(addr, worker_id=0)
    c1 = PSClient(addr, worker_id=1)
    ct0 = CompressedTensor(c0, _ctx("g", x0.nbytes, 2, partition_bytes),
                           kwargs, 2)
    ct1 = CompressedTensor(c1, _ctx("g", x1.nbytes, 2, partition_bytes),
                           kwargs, 2)
    res = {}

    def w(ct, x, tag):
        res[tag] = ct.push_pull(x, average=False)

    th = threading.Thread(target=w, args=(ct1, x1, "w1"), daemon=True)
    th.start()
    w(ct0, x0, "w0")
    th.join(timeout=30)
    assert not th.is_alive()
    c0.close()
    c1.close()
    t.join(timeout=10)
    return res["w0"], res["w1"]


def _golden_aggregate(kwargs, xs, n):
    """What the server should produce: decompress each worker's payload,
    sum, recompress (step 0), decompress."""
    payloads = []
    for x in xs:
        c = host.make_host_codec(kwargs, n)
        payloads.append(c.compress(x, step=0))
    dec = host.make_host_codec(kwargs, n)
    s = sum(dec.decompress(np.frombuffer(p, np.uint8)) for p in payloads)
    wire = host.make_host_codec(kwargs, n).compress(s, step=0)
    return dec.decompress(np.frombuffer(wire, np.uint8))


def test_onebit_two_workers():
    n = 1000
    rng = np.random.RandomState(0)
    x0 = rng.randn(n).astype(np.float32)
    x1 = rng.randn(n).astype(np.float32)
    out0, out1 = _two_worker_roundtrip({"compressor": "onebit"}, x0, x1)
    want = _golden_aggregate({"compressor": "onebit"}, [x0, x1], n)
    np.testing.assert_allclose(out0, want, rtol=1e-6)
    np.testing.assert_allclose(out1, want, rtol=1e-6)


def test_topk_two_workers():
    n = 512
    rng = np.random.RandomState(1)
    x0 = rng.randn(n).astype(np.float32)
    x1 = rng.randn(n).astype(np.float32)
    kw = {"compressor": "topk", "k": "32"}
    out0, out1 = _two_worker_roundtrip(kw, x0, x1)
    want = _golden_aggregate(kw, [x0, x1], n)
    np.testing.assert_array_equal(out0, want)
    np.testing.assert_array_equal(out1, want)


def test_randomk_two_workers():
    n = 512
    rng = np.random.RandomState(2)
    x0 = rng.randn(n).astype(np.float32)
    x1 = rng.randn(n).astype(np.float32)
    kw = {"compressor": "randomk", "k": "32", "seed": "7"}
    out0, out1 = _two_worker_roundtrip(kw, x0, x1)
    want = _golden_aggregate(kw, [x0, x1], n)
    np.testing.assert_array_equal(out0, want)
    np.testing.assert_array_equal(out1, want)


def test_dithering_linear_two_workers():
    n = 800
    rng = np.random.RandomState(3)
    x0 = rng.randn(n).astype(np.float32)
    x1 = rng.randn(n).astype(np.float32)
    kw = {"compressor": "dithering", "s": "64", "seed": "11"}
    out0, _ = _two_worker_roundtrip(kw, x0, x1)
    want = _golden_aggregate(kw, [x0, x1], n)
    # linear partition + max norm: all-f32 ops, identical formulas ->
    # bit-exact across numpy and the C++ server
    np.testing.assert_array_equal(out0, want)


def test_dithering_natural_single_worker_mirror():
    """Single worker: the server decompresses exact power-of-two level
    values and requantizes them; that round trip is level-preserving, so
    the output must equal the worker's own decompressed payload — modulo
    rare libm-vs-numpy ulp differences at log2 boundaries."""
    n = 800
    rng = np.random.RandomState(4)
    x0 = rng.randn(n).astype(np.float32)
    kw = {"compressor": "dithering", "s": "64", "seed": "11",
          "partition_type": "natural"}
    port, t = _server(1)
    c = PSClient([f"127.0.0.1:{port}"], worker_id=0)
    ct = CompressedTensor(c, _ctx("g", x0.nbytes, 1), kw, 1)
    out = ct.push_pull(x0, average=False)
    want = _golden_aggregate(kw, [x0], n)
    exact = out == want
    assert exact.mean() >= 0.99, f"only {exact.mean():.3f} exact"
    # any ulp-flip moves one natural level = a factor of 2
    bad = ~exact
    ratio = np.abs(out[bad]) / np.maximum(np.abs(want[bad]), 1e-30)
    assert np.all((ratio > 0.45) & (ratio < 2.2))
    c.close()
    t.join(timeout=10)


def test_partitioned_compressed_roundtrip():
    # tensor large enough to split into multiple partitions; each partition
    # gets its own codec instance and server-side mirror
    n = 8192
    rng = np.random.RandomState(5)
    x0 = rng.randn(n).astype(np.float32)
    x1 = rng.randn(n).astype(np.float32)
    kw = {"compressor": "onebit"}
    out0, _ = _two_worker_roundtrip(kw, x0, x1, partition_bytes=8192)
    # golden per partition (8192 bytes = 2048 f32)
    ctx = _ctx("g", x0.nbytes, 2, partition_bytes=8192)
    assert len(ctx.partitions) > 1
    want = np.empty_like(x0)
    for p in ctx.partitions:
        lo, hi = p.offset // 4, (p.offset + p.length) // 4
        want[lo:hi] = _golden_aggregate(kw, [x0[lo:hi], x1[lo:hi]], hi - lo)
    np.testing.assert_allclose(out0, want, rtol=1e-6)


def test_ef_onebit_unbiases_constant_gradient():
    """Error feedback makes the time-average of compressed gradients
    converge to the true gradient (error_feedback.cc:22-43 semantics)."""
    n = 256
    port, t = _server(1)
    c = PSClient([f"127.0.0.1:{port}"], worker_id=0)
    kw = {"compressor": "onebit", "ef": "vanilla"}
    ct = CompressedTensor(c, _ctx("g", n * 4, 1), kw, 1)
    g = np.linspace(-1.0, 2.0, n).astype(np.float32)
    acc = np.zeros(n, np.float32)
    steps = 250
    for _ in range(steps):
        acc += ct.push_pull(g, average=False)
    mean = acc / steps
    # without EF the onebit mean would be sign(g)*L1mean (one of two
    # constants, max error ~1.0 here); with EF the running mean tracks g
    # with O(scale/steps) bias plus a bounded oscillation
    err = np.abs(mean - g)
    assert err.max() < 0.25, err.max()
    assert err.mean() < 0.05, err.mean()
    c.close()
    t.join(timeout=10)


def test_comp_init_rejected_on_async_server():
    port, t = _server(1, enable_async=True)
    c = PSClient([f"127.0.0.1:{port}"], worker_id=0)
    ctx = _ctx("g", 64 * 4, 1)
    c.init_tensor(ctx, np.zeros(64 * 4, np.uint8).view(np.float32))
    with pytest.raises(RuntimeError, match="comp_init"):
        c.comp_init(0, ctx.partitions[0].key, "compressor=onebit;n=64")
    c.close()
    t.join(timeout=10)


def test_comp_init_requires_initialized_store():
    port, t = _server(1)
    c = PSClient([f"127.0.0.1:{port}"], worker_id=0)
    with pytest.raises(RuntimeError, match="comp_init"):
        c.comp_init(0, 424242, "compressor=onebit;n=64")
    c.close()
    t.join(timeout=10)


def test_dense_push_rejected_on_compressed_key():
    from byteps_tpu.server.compressed import CMD_F32
    port, t = _server(1)
    c = PSClient([f"127.0.0.1:{port}"], worker_id=0)
    ctx = _ctx("g", 64 * 4, 1)
    ct = CompressedTensor(c, ctx, {"compressor": "onebit"}, 1)
    ct.push_pull(np.ones(64, np.float32))
    with pytest.raises(RuntimeError, match="push failed"):
        c.zpush(0, ctx.partitions[0].key, np.zeros(256, np.uint8), CMD_F32)
    c.close()
    t.join(timeout=10)


def test_compressed_ps_training(monkeypatch):
    """End to end: make_ps_train_step(compression=...) trains through the
    compressed wire + server mirror (BASELINE config-4 dataflow)."""
    import jax
    import jax.numpy as jnp
    import optax

    from byteps_tpu.core.state import GlobalState
    from byteps_tpu.jax.train import make_ps_train_step
    from byteps_tpu.models import mlp

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
        state = get_state()
        cfg = mlp.MLPConfig(in_dim=8, hidden=(16,), n_classes=4)
        params = mlp.init_params(jax.random.PRNGKey(0), cfg)
        tx = optax.sgd(0.1)
        opt = tx.init(params)
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(32, 8), jnp.float32)
        y = jnp.asarray(rng.randint(0, 4, 32), jnp.int32)
        # device_compress=False pins the HOST-numpy codec tier (the
        # device tier's e2e lives in test_device_compress.py)
        step = make_ps_train_step(
            lambda p, b: mlp.loss_fn(p, b, cfg), tx, state.mesh,
            compression={"compressor": "onebit", "ef": "vanilla"},
            min_compress_bytes=0, device_compress=False)
        losses = []
        for _ in range(25):
            params, opt, loss = step(params, opt, {"x": x, "y": y})
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.8, losses
        # elastic: suspend closes the PS client; the step must re-key its
        # compressed registry to the resumed client, not push on the
        # destroyed handle
        bps.suspend()
        bps.resume(num_workers=1, num_servers=1)
        params, opt, loss = step(params, opt, {"x": x, "y": y})
        assert float(loss) < losses[0]
    finally:
        bps.shutdown()
        server.join(timeout=10)
        GlobalState._instance = None


def test_dithering_level_bound_invariant():
    """|level| <= s for every implementation on adversarial inputs (huge
    dynamic range, denormals, single dominant element). The linear-path
    clamp guards the int8 cast at s=127 against any norm that rounds
    below max|x|; no crafted float32 input reliably triggers that rounding
    through np.linalg.norm, so the invariant is pinned property-style
    across host, jnp, and the C++ server instead."""
    import jax.numpy as jnp
    from byteps_tpu.ops.compression.codecs import DitheringCodec

    n = 64
    cases = [
        np.asarray([3.4e38] + [1e-40] * (n - 1), np.float32),
        np.asarray([1.0] * n, np.float32),
        np.concatenate([[7.3], np.full(n - 1, 1e-6)]).astype(np.float32),
    ]
    for norm_t in ("max", "l2"):
        for x in cases:
            h = host.HostDithering(n=n, s=127, normalize=norm_t, seed=1)
            wire = np.frombuffer(h.compress(x, 0), np.uint8)
            lv = wire[:n].view(np.int8)
            assert np.abs(lv.astype(np.int32)).max() <= 127
            assert np.all(np.isfinite(h.decompress(wire)))
            j = DitheringCodec(size=n, s=127, normalize=norm_t, seed=1)
            jlv = np.asarray(j.compress(jnp.asarray(x))["levels"])
            assert np.abs(jlv.astype(np.int32)).max() <= 127

    # server-side: push an all-dominant vector through the C++ mirror
    port, t = _server(1)
    c = PSClient([f"127.0.0.1:{port}"], worker_id=0)
    kw = {"compressor": "dithering", "s": "127", "normalize_type": "l2"}
    ct = CompressedTensor(c, _ctx("g", n * 4, 1), kw, 1)
    out = ct.push_pull(cases[2], average=False)
    assert np.all(np.isfinite(out))
    assert np.sign(out[0]) >= 0
    c.close()
    t.join(timeout=10)


def test_host_matches_jax_codecs():
    """The host wire codecs and the portable jnp codecs must agree — the
    on-device compressor's output is what actually hits the wire."""
    import jax.numpy as jnp
    from byteps_tpu.ops.compression.codecs import (
        DitheringCodec, OnebitCodec, RandomkCodec, TopkCodec,
    )

    n = 300
    x = np.random.RandomState(7).randn(n).astype(np.float32)

    hb = host.HostOnebit(n=n)
    jb = OnebitCodec(size=n, use_pallas=False)
    jp = jb.compress(jnp.asarray(x))
    wire = np.frombuffer(hb.compress(x), np.uint8)
    np.testing.assert_array_equal(wire[:-4].view(np.uint32),
                                  np.asarray(jp["bits"]))
    np.testing.assert_allclose(wire[-4:].view(np.float32)[0],
                               float(jp["scale"]), rtol=1e-6)

    hk = host.HostRandomk(n=n, k=16, seed=3)
    jk = RandomkCodec(size=n, k=16, seed=3)
    np.testing.assert_array_equal(hk.indices(step=5),
                                  np.asarray(jk._indices(5)))

    ht = host.HostTopk(n=n, k=16)
    jt = TopkCodec(size=n, k=16)
    jpk = jt.compress(jnp.asarray(x))
    assert set(np.asarray(jpk["indices"]).tolist()) == \
        set(ht.select(x, 16).tolist())

    hd = host.HostDithering(n=n, s=32, seed=9)
    jd = DitheringCodec(size=n, s=32, seed=9)
    jpd = jd.compress(jnp.asarray(x), step=2)
    hwire = np.frombuffer(hd.compress(x, step=2), np.uint8)
    np.testing.assert_array_equal(hwire[:n].view(np.int8),
                                  np.asarray(jpd["levels"]))


def test_compressed_through_scheduler_pipeline(monkeypatch):
    """Compressed tensors ride the priority-scheduled pipeline (COMPRESS ->
    PUSH -> PULL -> DECOMPRESS stages, the reference's scheduled-queue
    splice, operations.cc:199-204): submit via the async registry path and
    check bit-parity with the blocking path's golden."""
    from byteps_tpu.core.state import GlobalState
    from byteps_tpu.server.compressed import CompressedRegistry

    port = _PORT[0]
    _PORT[0] += 1
    monkeypatch.setenv("DMLC_NUM_WORKER", "1")
    monkeypatch.setenv("DMLC_NUM_SERVER", "1")
    monkeypatch.setenv("DMLC_PS_ROOT_URI", "127.0.0.1")
    monkeypatch.setenv("DMLC_PS_ROOT_PORT", str(port))
    # small credit: partitions are admitted through the credit gate
    monkeypatch.setenv("BYTEPS_SCHEDULING_CREDIT", str(16384))
    server = threading.Thread(
        target=run_server,
        args=(port, Config(num_workers=1, num_servers=1)), daemon=True)
    server.start()
    GlobalState._instance = None
    import byteps_tpu as bps
    bps.init()
    try:
        from byteps_tpu.core.state import get_state
        state = get_state()
        assert state.scheduler is not None
        n = 4096  # multiple partitions at the default 4MB? no — force small
        kw = {"compressor": "onebit"}
        reg = CompressedRegistry(state.ps_client, 1, kw)
        rng = np.random.RandomState(0)
        xs = [rng.randn(n).astype(np.float32) for _ in range(4)]
        handles = [reg.push_pull_async(state, f"cg{i}", x, average=False)
                   for i, x in enumerate(xs)]
        for i, (hd, x) in enumerate(zip(handles, xs)):
            out = bps.synchronize(hd, timeout=60)
            want = _golden_aggregate(kw, [x], n)
            np.testing.assert_allclose(out, want, rtol=1e-6,
                                       err_msg=f"tensor cg{i}")
        # stateful codec across rounds: EF keeps per-partition state and
        # the round counter must advance through the scheduler path too
        kw2 = {"compressor": "randomk", "k": "64", "seed": "5"}
        reg2 = CompressedRegistry(state.ps_client, 1, kw2)
        x = rng.randn(n).astype(np.float32)
        h0 = reg2.push_pull_async(state, "rk", x, average=False)
        out0 = bps.synchronize(h0, timeout=60)
        h1 = reg2.push_pull_async(state, "rk", x, average=False)
        out1 = bps.synchronize(h1, timeout=60)
        # different rounds select different indices -> different outputs
        assert not np.array_equal(out0, out1)
        ct = reg2.get(state, "rk", x)
        assert ct.step == 2
    finally:
        bps.shutdown()
        server.join(timeout=10)
        GlobalState._instance = None


def test_dense_rounds_then_compression_same_key():
    """A key that ran dense rounds and then installs a compressor must
    keep working: the dense ALL_RECV publishes the accumulator by moving
    it out, and the compressed first-recv must re-size it, not memcpy
    into a moved-out buffer (regression: heap corruption)."""
    n = 1024
    port, t = _server(1)
    c = PSClient([f"127.0.0.1:{port}"], worker_id=0)
    ctx = _ctx("g", n * 4, 1)
    rng = np.random.RandomState(8)
    x = rng.randn(n).astype(np.float32)
    # dense rounds first (same keys the compressor will reuse)
    c.init_tensor(ctx, np.zeros(n, np.float32))
    out = c.push_pull(ctx, x.copy(), average=False)
    np.testing.assert_allclose(out, x, rtol=1e-6)
    # now install compression on the SAME key and run compressed rounds
    kw = {"compressor": "onebit"}
    ct = CompressedTensor(c, ctx, kw, 1)
    out = ct.push_pull(x, average=False)
    want = _golden_aggregate(kw, [x], n)
    np.testing.assert_allclose(out, want, rtol=1e-6)
    out2 = ct.push_pull(x, average=False)  # second round exercises steal
    np.testing.assert_allclose(out2, want, rtol=1e-6)
    c.close()
    t.join(timeout=10)


def test_randomk_skewed_steps_degrades_correctly():
    """The server's randomk wire-form fast path requires the round's
    payloads to share indices; workers whose per-tensor round counters
    are skewed (elastic resume) ship DIFFERENT index vectors, and the
    server must fall back to dense aggregation — the aggregate is then
    the sum of each worker's own scatter, exactly like the generic
    path."""
    from byteps_tpu.core.types import RequestType, get_command_type

    n, k = 512, 32
    port, t = _server(2)
    addr = [f"127.0.0.1:{port}"]
    c0 = PSClient(addr, worker_id=0)
    c1 = PSClient(addr, worker_id=1)
    ctx0 = _ctx("skew", n * 4, 2)
    ctx1 = _ctx("skew", n * 4, 2)
    key = ctx0.partitions[0].key
    codec = host.HostRandomk(n=n, k=k, seed=7)
    kw = codec.kwargs_wire()

    def init(c, ctx):
        c.init_tensor(ctx, np.zeros(n, np.float32))
        c.comp_init(0, key, kw)

    ths = [threading.Thread(target=init, args=p)
           for p in ((c0, ctx0), (c1, ctx1))]
    for th in ths:
        th.start()
    for th in ths:
        th.join(60)

    rng = np.random.RandomState(0)
    xs = [rng.randn(n).astype(np.float32) for _ in range(2)]
    steps = [3, 9]  # skewed round counters -> different index vectors
    wires = [codec.compress(xs[i], step=steps[i]) for i in range(2)]
    assert not np.array_equal(codec.indices(3), codec.indices(9))
    cmd = get_command_type(RequestType.COMPRESSED_PUSH_PULL,
                           DataType.FLOAT32)
    outs = [np.empty(n, np.float32) for _ in range(2)]

    def roundtrip(w):
        buf = np.frombuffer(wires[w], np.uint8)
        c = (c0, c1)[w]
        c.zpush(0, key, buf, cmd)
        # pull the DENSE aggregate (not the recompressed wire): the
        # degraded round published the sum of both scatters
        dense_cmd = get_command_type(RequestType.DEFAULT_PUSH_PULL,
                                     DataType.FLOAT32)
        c.zpull(0, key, outs[w], dense_cmd)

    ths = [threading.Thread(target=roundtrip, args=(w,)) for w in range(2)]
    for th in ths:
        th.start()
    for th in ths:
        th.join(60)

    want = codec.decompress(wires[0]) + codec.decompress(wires[1])
    np.testing.assert_allclose(outs[0], want, rtol=1e-6)
    np.testing.assert_array_equal(outs[0], outs[1])
    c0.close()
    c1.close()
    t.join(timeout=15)


def test_varint_codec_roundtrip_property():
    """Vectorized LEB128 helpers: encode->decode is identity across the
    gap-size spectrum (1-byte through 4-byte varints)."""
    from byteps_tpu.ops.compression.host import (
        _varint_decode, _varint_encode,
    )

    rng = np.random.RandomState(0)
    vals = np.concatenate([
        rng.randint(1, 127, 50), rng.randint(128, 1 << 14, 50),
        rng.randint(1 << 14, 1 << 21, 20), rng.randint(1 << 21, 1 << 28, 5),
        [1, 127, 128, 16383, 16384, (1 << 28) - 1],
    ]).astype(np.int64)
    enc = _varint_encode(vals)
    dec, used = _varint_decode(enc, len(vals))
    assert used == len(enc)
    np.testing.assert_array_equal(dec, vals)
    # trailing garbage is not consumed
    dec2, used2 = _varint_decode(np.concatenate([enc, [5, 5]]), len(vals))
    np.testing.assert_array_equal(dec2, vals)
    assert used2 == len(enc)


def test_dithering_varint_wire_bit_exact_and_small():
    """index_coding=varint: decompress(compress(x)) is BIT-EXACT with the
    dense wire's result, and the wire is much smaller than n at low s on
    gradient-like (heavy-tailed) data — the reference's coded sparse
    dithering claim (impl/dithering.cc:25-80)."""
    n = 20000
    rng = np.random.RandomState(0)
    x = (rng.randn(n) ** 3).astype(np.float32)  # heavy tail: most levels 0
    dense = host.HostDithering(n=n, s=7, seed=4)
    sparse = host.HostDithering(n=n, s=7, seed=4, index_coding="varint")
    wd = dense.compress(x, step=3)
    ws = sparse.compress(x, step=3)
    assert len(ws) < n // 4, (len(ws), n)          # wire << n
    assert len(ws) <= sparse.wire_bytes()          # inside the bound
    np.testing.assert_array_equal(sparse.decompress(np.frombuffer(ws, np.uint8)),
                                  dense.decompress(np.frombuffer(wd, np.uint8)))
    # dense data (low sparsity) still round-trips, just without the win
    xd = rng.randn(256).astype(np.float32)
    s2 = host.HostDithering(n=256, s=127, seed=1, index_coding="varint")
    d2 = host.HostDithering(n=256, s=127, seed=1)
    np.testing.assert_array_equal(
        s2.decompress(np.frombuffer(s2.compress(xd, 0), np.uint8)),
        d2.decompress(np.frombuffer(d2.compress(xd, 0), np.uint8)))


def test_dithering_varint_two_workers():
    """The C++ server speaks the varint wire: decompress, sum, recompress
    (variable-length reply) — aggregate matches the numpy golden."""
    n = 4000
    rng = np.random.RandomState(6)
    x0 = (rng.randn(n) ** 3).astype(np.float32)
    x1 = (rng.randn(n) ** 3).astype(np.float32)
    kw = {"compressor": "dithering", "s": "7", "seed": "11",
          "index_coding": "varint"}
    out0, out1 = _two_worker_roundtrip(kw, x0, x1)
    want = _golden_aggregate(kw, [x0, x1], n)
    np.testing.assert_array_equal(out0, want)
    np.testing.assert_array_equal(out1, want)


def test_dithering_varint_through_scheduler(monkeypatch):
    """Variable-length replies ride the pipelined scheduler path (the
    PULL stage must use the actual reply length, not the bound)."""
    from byteps_tpu.core.state import GlobalState
    from byteps_tpu.server.compressed import CompressedRegistry

    port = _PORT[0]
    _PORT[0] += 1
    monkeypatch.setenv("DMLC_NUM_WORKER", "1")
    monkeypatch.setenv("DMLC_NUM_SERVER", "1")
    monkeypatch.setenv("DMLC_PS_ROOT_URI", "127.0.0.1")
    monkeypatch.setenv("DMLC_PS_ROOT_PORT", str(port))
    server = threading.Thread(
        target=run_server,
        args=(port, Config(num_workers=1, num_servers=1)), daemon=True)
    server.start()
    GlobalState._instance = None
    import byteps_tpu as bps
    bps.init()
    try:
        from byteps_tpu.core.state import get_state
        state = get_state()
        n = 4096
        kw = {"compressor": "dithering", "s": "7", "seed": "2",
              "index_coding": "varint"}
        reg = CompressedRegistry(state.ps_client, 1, kw)
        rng = np.random.RandomState(1)
        x = (rng.randn(n) ** 3).astype(np.float32)
        hd = reg.push_pull_async(state, "vd", x, average=False)
        out = bps.synchronize(hd, timeout=60)
        want = _golden_aggregate(kw, [x], n)
        np.testing.assert_array_equal(out, want)
    finally:
        bps.shutdown()
        server.join(timeout=10)
        GlobalState._instance = None
