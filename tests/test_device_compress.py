"""On-device compression for the PS path (jax/device_compression.py).

SURVEY §7's "the D2H moves *compressed* bytes" promise: the codec stack
runs inside XLA, the scheduler receives wire-sized payloads, and the
pull reply is decompressed on device. These tests pin (a) wire-format
parity with the host/numpy tier (the C++ server must not be able to
tell the tiers apart), (b) the transfer-size claim itself, and (c) end
to end training through the loopback server."""

import threading

import numpy as np
import pytest

from byteps_tpu.config import Config
from byteps_tpu.ops.compression import host
from byteps_tpu.server import run_server

_PORT = [23900]


def _golden_aggregate(kwargs, xs, n):
    payloads = []
    for x in xs:
        c = host.make_host_codec(kwargs, n)
        payloads.append(c.compress(x, step=0))
    dec = host.make_host_codec(kwargs, n)
    s = sum(dec.decompress(np.frombuffer(p, np.uint8)) for p in payloads)
    wire = host.make_host_codec(kwargs, n).compress(s, step=0)
    return dec.decompress(np.frombuffer(wire, np.uint8))


@pytest.mark.parametrize("kw", [
    {"compressor": "onebit"},
    {"compressor": "topk", "k": "16"},
    {"compressor": "randomk", "k": "16", "seed": "3"},
    {"compressor": "dithering", "s": "32", "seed": "9"},
])
def test_wire_serialization_matches_host_codec(kw):
    """payload_to_wire(jnp payload) must be byte-compatible with the
    host codec's wire (scalar scale/norm may differ by an ulp; all
    index/level/bit lanes must be identical)."""
    import jax.numpy as jnp

    from byteps_tpu.jax.device_compression import (
        _portable, payload_to_wire, wire_to_payload,
    )
    from byteps_tpu.ops.compression import make_compressor

    n = 300
    x = np.random.RandomState(7).randn(n).astype(np.float32)
    codec = _portable(make_compressor(kw, n).codec)
    payload = codec.compress(jnp.asarray(x), step=4)
    wire = payload_to_wire(codec,
                           {k: np.asarray(v) for k, v in payload.items()})
    hwire = np.frombuffer(
        host.make_host_codec(kw, n).compress(x, step=4), np.uint8)
    assert wire.nbytes == hwire.nbytes == \
        host.make_host_codec(kw, n).wire_bytes()
    # scalar tail (scale/norm) may differ by an ulp between np and jnp
    # reductions; everything else must be bit-identical
    body = slice(None)
    if kw["compressor"] in ("onebit", "dithering"):
        body = slice(0, wire.nbytes - 4)
        np.testing.assert_allclose(
            wire[-4:].copy().view(np.float32),
            hwire[-4:].copy().view(np.float32), rtol=1e-6)
    np.testing.assert_array_equal(wire[body], hwire[body])
    # parse -> device decompress must equal the host decompress
    parsed = wire_to_payload(codec, n, wire)
    dev = np.asarray(codec.decompress(
        {k: jnp.asarray(v) for k, v in parsed.items()}))
    hostd = host.make_host_codec(kw, n).decompress(hwire)
    np.testing.assert_allclose(dev, hostd, rtol=1e-6)


def _with_ps(monkeypatch, body, **cfgkw):
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
        args=(port, Config(num_workers=1, num_servers=1, **cfgkw)),
        daemon=True)
    server.start()
    GlobalState._instance = None
    import byteps_tpu as bps
    bps.init()
    try:
        from byteps_tpu.core.state import get_state
        body(bps, get_state())
    finally:
        bps.shutdown()
        server.join(timeout=10)
        GlobalState._instance = None


@pytest.mark.parametrize("kw", [
    {"compressor": "onebit"},
    {"compressor": "randomk", "k": "32", "seed": "5"},
])
def test_device_roundtrip_matches_golden(monkeypatch, kw):
    """DeviceCompressor through the real scheduler + C++ server equals
    the host-tier golden aggregate."""
    import jax.numpy as jnp

    from byteps_tpu.jax.device_compression import DeviceCompressor

    n = 4096

    def body(bps, state):
        dc = DeviceCompressor(state.ps_client, 1, kw)
        rng = np.random.RandomState(0)
        x = rng.randn(n).astype(np.float32)
        out = dc.push_pull_leaves(state, ["dt"], [jnp.asarray(x)],
                                  average=False)[0]
        want = _golden_aggregate(kw, [x], n)
        np.testing.assert_allclose(np.asarray(out), want, rtol=1e-6)
        # second round advances the per-tensor round counter (stateful
        # codecs + the server's sync completed_rounds)
        out1 = dc.push_pull_leaves(state, ["dt"], [jnp.asarray(x)],
                                   average=False)[0]
        assert dc._plans["dt"].step == 2
        if kw["compressor"] == "randomk":
            # different rounds draw different indices
            assert not np.array_equal(np.asarray(out), np.asarray(out1))

    _with_ps(monkeypatch, body)


def test_d2h_payload_is_wire_sized(monkeypatch):
    """The device->host hop must carry
    ~wire_bytes(), not dense f32. Asserts the jitted compress output's
    total nbytes is the wire size (1/32 of dense for onebit bits +
    4 scale bytes per partition)."""
    import jax.numpy as jnp

    from byteps_tpu.jax.device_compression import DeviceCompressor

    n = 1 << 20  # 4 MB dense

    def body(bps, state):
        dc = DeviceCompressor(state.ps_client, 1, {"compressor": "onebit"})
        plan = dc.plan(state, "big", n)
        compress_fn, _decompress_fn, spec = dc._get_fns([plan], True)
        packed, _states = compress_fn(
            [jnp.ones(n, jnp.float32)], [plan.states], jnp.int32(0))
        # the D2H hop is now 1-2 dtype-bucketed buffers (not one array
        # per partition payload) and their total is exactly wire-sized
        assert len(packed) <= 2, list(packed)
        total = sum(np.asarray(v).nbytes for v in packed.values())
        dense = n * 4
        assert total == plan.wire_bytes(), (total, plan.wire_bytes())
        assert total < dense / 25, (total, dense)
        # host views must reassemble into the per-partition wire layout
        payloads = spec.unpack_np({k: np.asarray(v)
                                   for k, v in packed.items()})
        assert len(payloads[0]) == len(plan.ctx.partitions)
        assert set(payloads[0][0]) == {"bits", "scale"}

    _with_ps(monkeypatch, body)


def test_device_compressed_training_and_elastic(monkeypatch):
    """make_ps_train_step default path is now device compression: loss
    decreases, EF state lives on device, and suspend/resume re-keys the
    device compressor to the new client."""
    import jax
    import jax.numpy as jnp
    import optax

    from byteps_tpu.jax.train import make_ps_train_step
    from byteps_tpu.models import mlp

    def body(bps, state):
        cfg = mlp.MLPConfig(in_dim=8, hidden=(16,), n_classes=4)
        params = mlp.init_params(jax.random.PRNGKey(0), cfg)
        tx = optax.sgd(0.1)
        opt = tx.init(params)
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(32, 8), jnp.float32)
        y = jnp.asarray(rng.randint(0, 4, 32), jnp.int32)
        step = make_ps_train_step(
            lambda p, b: mlp.loss_fn(p, b, cfg), tx, state.mesh,
            compression={"compressor": "onebit", "ef": "vanilla"},
            min_compress_bytes=0)
        losses = []
        for _ in range(25):
            params, opt, loss = step(params, opt, {"x": x, "y": y})
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.8, losses
        bps.suspend()
        bps.resume(num_workers=1, num_servers=1)
        params, opt, loss = step(params, opt, {"x": x, "y": y})
        assert float(loss) < losses[0]

    _with_ps(monkeypatch, body)


def test_device_vs_host_tier_parity(monkeypatch):
    """Same gradient, same server: the device tier and the host tier must
    produce the same aggregate (the server cannot tell them apart)."""
    import jax.numpy as jnp

    from byteps_tpu.jax.device_compression import DeviceCompressor
    from byteps_tpu.server.compressed import CompressedRegistry

    n = 2048
    kw = {"compressor": "randomk", "k": "64", "seed": "11"}

    def body(bps, state):
        rng = np.random.RandomState(3)
        x = rng.randn(n).astype(np.float32)
        dc = DeviceCompressor(state.ps_client, 1, kw)
        dev = np.asarray(dc.push_pull_leaves(
            state, ["p"], [jnp.asarray(x)], average=False)[0])
        reg = CompressedRegistry(state.ps_client, 1, kw)
        hostout = reg.push_pull(state, "q", x, average=False)
        # both ran round 0 of their own tensors with the same seed ->
        # identical indices, identical values, bit-identical result
        np.testing.assert_array_equal(dev, hostout)

    _with_ps(monkeypatch, body)


def test_zero_size_leaf_passes_through(monkeypatch):
    """A pytree with a 0-element leaf (e.g. an optional bias of shape
    (0,)) must not crash the device-compressed round: zero-size leaves
    carry no data and pass through unchanged while the rest of the tree
    still aggregates (round-4 review regression)."""
    import jax.numpy as jnp

    from byteps_tpu.jax.device_compression import DeviceCompressor

    def body(bps, state):
        dc = DeviceCompressor(state.ps_client, 1,
                              {"compressor": "onebit"})
        lf = jnp.asarray(np.random.RandomState(0).randn(512), jnp.float32)
        empty = jnp.zeros((0,), jnp.float32)
        out = dc.push_pull_leaves(state, ["zlive", "zempty"],
                                  [lf, empty], average=False)
        assert out[1].shape == (0,)
        # the live leaf still went through the codec (onebit: sign*scale)
        assert np.asarray(out[0]).shape == (512,)
        assert np.sign(np.asarray(out[0])).tolist() == \
            np.sign(np.asarray(lf)).tolist()

    _with_ps(monkeypatch, body)
