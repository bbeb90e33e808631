"""Streamed gradient export + sharded optimizer apply
(BYTEPS_STREAM_EXPORT / BYTEPS_SHARDED_APPLY, jax/train.py +
jax/optim.py): numerics parity of stream-export on vs off vs the
single-process baseline (dense, fused-bucket and compression-enabled
configs), of the route that unset chooses against both, that it taps
nothing on a mesh or on one device, bitwise parity of the sharded apply against the fused optax
apply for adam/sgd, the non-separable fallback, export-stage telemetry
(streamed-leaf counters + time-to-first-push), and production-order
priority pinning end to end."""

import contextlib
import os
import threading

import numpy as np
import optax
import pytest

from byteps_tpu.config import Config
from byteps_tpu.server import run_server

_PORT = [23600]


@contextlib.contextmanager
def _ps_env(extra_env: dict = None):
    from byteps_tpu.core.state import GlobalState

    port = _PORT[0]
    _PORT[0] += 1
    env = {
        "DMLC_NUM_WORKER": "1", "DMLC_NUM_SERVER": "1",
        "DMLC_PS_ROOT_URI": "127.0.0.1", "DMLC_PS_ROOT_PORT": str(port),
        "BYTEPS_FORCE_DISTRIBUTED": "1", **(extra_env or {}),
    }
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    server = threading.Thread(
        target=run_server,
        args=(port, Config(num_workers=1, num_servers=1)), daemon=True)
    server.start()
    GlobalState._instance = None
    import byteps_tpu as bps
    bps.init()
    try:
        yield bps
    finally:
        bps.shutdown()
        server.join(timeout=10)
        GlobalState._instance = None
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _setup():
    import jax
    import jax.numpy as jnp

    from byteps_tpu.models import mlp

    cfg = mlp.MLPConfig(in_dim=64, hidden=(48, 32), n_classes=10)
    params = mlp.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)
    batch = {"x": jnp.asarray(rng.rand(32, 64), jnp.float32),
             "y": jnp.asarray(rng.randint(0, 10, 32), jnp.int32)}
    return cfg, params, batch


def _run_steps(params, batch, cfg, steps=3, tx=None, mesh=None,
               losses=None, **kw):
    import jax
    import jax.numpy as jnp

    from byteps_tpu.core.state import get_state
    from byteps_tpu.jax.train import make_ps_train_step
    from byteps_tpu.models import mlp

    params = jax.tree.map(jnp.array, params)  # private copy (donation)
    tx = tx or optax.adam(1e-2)
    opt = tx.init(params)
    step = make_ps_train_step(lambda p, b: mlp.loss_fn(p, b, cfg), tx,
                              mesh or get_state().mesh, **kw)
    for _ in range(steps):
        params, opt, loss = step(params, opt, batch)
        if losses is not None:
            losses.append(np.asarray(loss))
    return ([np.asarray(x) for x in jax.tree.leaves(params)],
            float(loss))


def _local_steps(params, batch, cfg, steps=3, tx=None):
    import jax

    from byteps_tpu.models import mlp

    tx = tx or optax.adam(1e-2)
    p, o = params, tx.init(params)

    def local(p, o, b):
        loss, g = jax.value_and_grad(lambda q: mlp.loss_fn(q, b, cfg))(p)
        u, o = tx.update(g, o, p)
        return optax.apply_updates(p, u), o, loss

    lj = jax.jit(local)
    for _ in range(steps):
        p, o, _ = lj(p, o, batch)
    return [np.asarray(x) for x in jax.tree.leaves(p)]


# --------------------------------------------------------------------- #
# parity: stream on vs off vs single-process baseline
# --------------------------------------------------------------------- #


# fusion 0 = every leaf rides its own key -> all stream ("dense");
# fusion 4096 = weights stream, biases ride the fused bucket
# ("fused-bucket"); the compression config exercises the host codec
# tier under streaming
@pytest.mark.parametrize("fusion,kw", [
    ("0", {}),
    ("4096", {}),
    ("0", dict(compression={"compressor": "onebit", "ef": "vanilla"},
               min_compress_bytes=0, device_compress=False)),
], ids=["dense", "fused-bucket", "onebit"])
def test_stream_on_off_parity(fusion, kw):
    """Stream-export on and off produce IDENTICAL params after 3 steps
    (the tap changes WHEN bytes leave the device, never what is
    computed), and both track the single-process baseline."""
    cfg, params, batch = _setup()
    with _ps_env({"BYTEPS_STREAM_EXPORT": "1",
                  "BYTEPS_FUSION_BYTES": fusion}) as bps:
        on, _ = _run_steps(params, batch, cfg, **kw)
        stats = bps.get_arena_stats()
        assert stats["export_streamed_leaves"] > 0, \
            "streaming never engaged — the on-arm is vacuous"
        assert stats["export_checkouts"] > 0
    with _ps_env({"BYTEPS_STREAM_EXPORT": "0",
                  "BYTEPS_FUSION_BYTES": fusion}) as bps:
        off, _ = _run_steps(params, batch, cfg, **kw)
        assert bps.get_arena_stats()["export_streamed_leaves"] == 0
    for a, b in zip(on, off):
        np.testing.assert_array_equal(a, b)
    if not kw:  # lossless transports also track the local baseline
        base = _local_steps(params, batch, cfg)
        for a, b in zip(on, base):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("fusion,kw", [
    ("0", {}),
    ("4096", {}),
    ("0", dict(compression={"compressor": "onebit", "ef": "vanilla"},
               min_compress_bytes=0, device_compress=False)),
], ids=["dense", "fused-bucket", "onebit"])
def test_unset_route_parity_with_taps_and_without(fusion, kw):
    """Nobody set BYTEPS_STREAM_EXPORT: whole leaves leave as outputs of
    the untapped backward. Every loss and every parameter is bitwise
    what ``=1`` (whole leaves tapped) and ``=0`` give; no leaf streams
    (these leaves are too small to shard), the same bytes are counted
    as whole-leaf exports, and the same keys are declared: a whole leaf
    between two bucket members closes the bucket on no route, so the
    bucket's digest is one."""
    cfg, params, batch = _setup()
    got = {}
    for arm in (None, "1", "0"):
        env = {"BYTEPS_FUSION_BYTES": fusion}
        if arm is not None:
            env["BYTEPS_STREAM_EXPORT"] = arm
        with _ps_env(env) as bps:
            from byteps_tpu.core.state import get_state

            assert get_state().config.stream_export is {
                None: None, "1": True, "0": False}[arm]
            losses = []
            leaves, _ = _run_steps(params, batch, cfg, losses=losses, **kw)
            stats = bps.get_arena_stats()
            got[arm] = (leaves, losses, stats["export_streamed_leaves"],
                        stats["export_fallback_leaves"],
                        bps.get_metrics()["counters"]["export/whole_bytes"],
                        sorted(c.name for c in
                               get_state().registry.contexts_in_order()))
    n_leaves = len(got[None][0])
    assert got[None][2] == 0 and got["0"][2] == 0 and got["1"][2] > 0
    assert got[None][3] == 3 * n_leaves
    assert got[None][4] == got["1"][4] == got["0"][4] > 0
    assert got[None][5] == got["1"][5] == got["0"][5]
    assert any(n.startswith("fused/") for n in got[None][5]) == (
        fusion != "0")
    for arm in ("1", "0"):
        for a, b in zip(got[None][0], got[arm][0]):
            np.testing.assert_array_equal(a, b)
        for a, b in zip(got[None][1], got[arm][1]):
            np.testing.assert_array_equal(a, b)


def _export_plan_run(env, mesh_devices=None, steps=3):
    """PS steps under ``env``; what the export plan did, read from the
    counters and the last step's spans."""
    import jax
    from jax.sharding import Mesh

    from byteps_tpu.utils import tracing

    cfg, params, batch = _setup()
    with _ps_env(env) as bps:
        from byteps_tpu.core.state import get_state

        mesh = None if mesh_devices is None else Mesh(
            np.array(jax.devices()[:mesh_devices]), ("dp",))
        leaves, _ = _run_steps(params, batch, cfg, steps=steps, mesh=mesh)
        spans = get_state().profiler.last_spans()
        ctr = bps.get_metrics()["counters"]
        out = {
            "leaves": leaves, "report": bps.get_step_reports()[-1],
            "arena": bps.get_arena_stats(),
            "shard_bytes": ctr.get("export/shard_bytes", 0),
            "whole_bytes": ctr.get("export/whole_bytes", 0),
            "device_bytes": {int(k.rsplit("/", 1)[1]): v
                             for k, v in ctr.items()
                             if k.startswith("export/device_bytes/")},
            "taps": sorted(sp[4]["leaf"] for sp in spans
                           if sp[0] == tracing.EXPORT_TAP),
            "ingests": sorted(
                (sp[4]["leaf"], sp[4].get("dev", -1),
                 sp[4]["cause"].split(":")[0], sp[1].rsplit("_", 1)[0])
                for sp in spans if sp[0] == tracing.EXPORT_INGEST),
            "order": dict(get_state().scheduler.export_order()),
        }
    return out


def test_unset_plan_on_the_mesh_taps_nothing(monkeypatch):
    """Eight devices, nobody set BYTEPS_STREAM_EXPORT: the weights shard
    exactly as ``=1`` shards them (same per-device bytes, exactly even;
    same shard keys at their parent's production-order priority; same
    parameters) and each device's shard leaves as a program output,
    claimed on the train thread in flatten then mesh-device order
    beside the biases' whole leaves; no ``io_callback`` is planted and
    no export pool takes part (asked for with ``=1``, every leaf is
    tapped)."""
    import jax
    import jax.experimental

    planted = []
    real = jax.experimental.io_callback

    def spy(*a, **kw):
        planted.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(jax.experimental, "io_callback", spy)
    env = {"BYTEPS_FUSION_BYTES": "0", "BYTEPS_SHARD_MIN_BYTES": "1024"}
    unset = _export_plan_run(env)
    assert planted == []
    asked = _export_plan_run({**env, "BYTEPS_STREAM_EXPORT": "1"})
    train = threading.current_thread().name.rsplit("_", 1)[0]
    ndims = [x.ndim for x in jax.tree.leaves(_setup()[1])]
    weights = [i for i, n in enumerate(ndims) if n == 2]
    biases = [i for i, n in enumerate(ndims) if n == 1]
    assert len(weights) == len(biases) == 3
    assert len(planted) >= len(weights + biases)
    # nothing streams; the shard plan and its bytes are ``=1``'s
    assert unset["report"]["streamed_leaves"] == 0
    assert unset["report"]["fallback_leaves"] == len(weights + biases)
    assert asked["report"]["streamed_leaves"] == len(weights + biases)
    assert unset["arena"]["export_streamed_leaves"] == 0
    assert unset["arena"]["export_shard_leaves"] == \
        asked["arena"]["export_shard_leaves"] == 3 * len(weights)
    assert unset["shard_bytes"] == asked["shard_bytes"] > 0
    assert unset["whole_bytes"] == asked["whole_bytes"] > 0
    per_dev = [unset["device_bytes"][d] for d in range(1, 8)]
    assert len(set(per_dev)) == 1 and per_dev[0] * 8 == unset["shard_bytes"]
    assert unset["device_bytes"] == asked["device_bytes"]
    assert unset["taps"] == []
    assert sorted(set(asked["taps"])) == sorted(weights + biases)
    # one ingest a (weight, device) and one a bias, all outputs, all on
    # the thread that claims; the taps' ran on the devices' workers
    assert unset["ingests"] == sorted(
        [(w, d, "out", train) for w in weights for d in range(8)]
        + [(b, -1, "out", train) for b in biases])
    tapped = [m for m in asked["ingests"] if m[0] in weights]
    assert [m[:3] for m in tapped] == [
        (w, d, "tap") for w in weights for d in range(8)]
    assert all(m[3].startswith("bps-export-d") for m in tapped)
    # the shards' keys keep a production order (the claim's: flatten
    # order); a whole leaf on the output route is in none
    assert len(asked["order"]) > len(unset["order"]) > 0
    assert set(unset["order"]) <= set(asked["order"])
    for a, b in zip(unset["leaves"], asked["leaves"]):
        np.testing.assert_array_equal(a, b)


def test_unset_on_one_device_builds_no_tapped_program(monkeypatch):
    """A one-device mesh has nothing to shard, so nothing is tapped:
    the program that runs is ``grad_fn``, and no ``io_callback`` is ever
    planted (asked for with ``=1``, it is)."""
    import jax.experimental

    planted = []
    real = jax.experimental.io_callback

    def spy(*a, **kw):
        planted.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(jax.experimental, "io_callback", spy)
    env = {"BYTEPS_FUSION_BYTES": "0", "BYTEPS_SHARD_MIN_BYTES": "1024"}
    unset = _export_plan_run(env, mesh_devices=1)
    assert planted == []
    assert unset["taps"] == [] and unset["shard_bytes"] == 0
    assert unset["report"]["streamed_leaves"] == 0
    assert unset["report"]["fallback_leaves"] == 6
    assert [m[2] for m in unset["ingests"]] == ["out"] * 6
    assert unset["order"] == {}
    asked = _export_plan_run({**env, "BYTEPS_STREAM_EXPORT": "1"},
                             mesh_devices=1)
    assert len(planted) == 6 and asked["report"]["streamed_leaves"] == 6
    assert unset["whole_bytes"] == asked["whole_bytes"] > 0
    for a, b in zip(unset["leaves"], asked["leaves"]):
        np.testing.assert_array_equal(a, b)


def test_sharded_apply_on_off_parity():
    """BYTEPS_SHARDED_APPLY on vs off: identical params after 3 steps
    through the live PS path (per-leaf updates are bitwise the fused
    chain for adam)."""
    cfg, params, batch = _setup()
    with _ps_env({"BYTEPS_SHARDED_APPLY": "1"}):
        on, _ = _run_steps(params, batch, cfg)
    with _ps_env({"BYTEPS_SHARDED_APPLY": "0"}):
        off, _ = _run_steps(params, batch, cfg)
    for a, b in zip(on, off):
        np.testing.assert_array_equal(a, b)


# --------------------------------------------------------------------- #
# sharded apply: bitwise vs fused, separability detection
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("mk_tx", [
    lambda: optax.adam(1e-3),
    lambda: optax.sgd(0.1),
    lambda: optax.sgd(0.1, momentum=0.9),
], ids=["adam", "sgd", "sgd-momentum"])
def test_sharded_apply_bitwise_vs_fused(mk_tx):
    """make_sharded_apply's per-leaf updates match the jitted fused
    optax apply BITWISE over multiple steps (same elementwise op
    sequence per leaf; the shared count increments identically)."""
    import jax
    import jax.numpy as jnp

    from byteps_tpu.jax.optim import make_sharded_apply

    tx = mk_tx()
    rng = np.random.RandomState(0)
    params = {"w": jnp.asarray(rng.randn(16, 8).astype(np.float32)),
              "b": jnp.asarray(rng.randn(8).astype(np.float32)),
              "nested": {"v": jnp.asarray(
                  rng.randn(4, 4).astype(np.float32))}}
    grads = jax.tree.map(
        lambda p: jnp.asarray(rng.randn(*p.shape).astype(np.float32)),
        params)
    st = tx.init(params)
    sa = make_sharded_apply(tx, params, st, donate=False)
    assert sa is not None, "elementwise chain not detected separable"

    def fused(p, s, g):
        u, s = tx.update(g, s, p)
        return optax.apply_updates(p, u), s

    fj = jax.jit(fused)
    pf, sf = params, st
    for _ in range(3):
        pf, sf = fj(pf, sf, grads)

    p_leaves = jax.tree.leaves(params)
    g_leaves = jax.tree.leaves(grads)
    ss = st
    for _ in range(3):
        res, newp = [], []
        for i in range(len(p_leaves)):
            np_, parts = sa.apply_leaf(p_leaves[i], ss, i, g_leaves[i])
            newp.append(np_)
            res.append(parts)
        p_leaves, ss = newp, sa.merge(ss, res)
    for a, b in zip(p_leaves, jax.tree.leaves(pf)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(ss), jax.tree.leaves(sf)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sharded_apply_rejects_non_separable():
    """Global-norm clipping mixes leaves: the probe must detect it and
    return None (the train step then keeps the fused apply), and the
    PS train step must still train correctly through the fallback."""
    import jax.numpy as jnp

    from byteps_tpu.jax.optim import make_sharded_apply

    tx = optax.chain(optax.clip_by_global_norm(1.0), optax.adam(1e-2))
    params = {"w": jnp.ones((4, 4)), "b": jnp.ones((4,))}
    assert make_sharded_apply(tx, params, tx.init(params)) is None

    cfg, params, batch = _setup()
    with _ps_env({"BYTEPS_SHARDED_APPLY": "1"}):
        got, loss = _run_steps(params, batch, cfg, tx=tx)
    assert np.isfinite(loss)
    base = _local_steps(params, batch, cfg, tx=tx)
    for a, b in zip(got, base):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


# --------------------------------------------------------------------- #
# telemetry + production-order priority
# --------------------------------------------------------------------- #


def test_export_telemetry_and_production_priority():
    """The export-stage counters prove the overlap engaged (streamed
    leaves counted, TTFP recorded, arena export leases tagged), and
    the scheduler's pinned priorities come from measured first-export
    ordinals for every streamed key."""
    cfg, params, batch = _setup()
    with _ps_env({"BYTEPS_STREAM_EXPORT": "1",
                  "BYTEPS_FUSION_BYTES": "0"}) as bps:
        from byteps_tpu.core.state import get_state

        _run_steps(params, batch, cfg, steps=3)
        stats = bps.get_arena_stats()
        n_leaves = 6  # 3 layers x (w, b)
        assert stats["export_rounds"] == 3
        # every leaf streams every round (fusion off, no rowsparse)
        assert stats["export_streamed_leaves"] == 3 * n_leaves
        assert stats["export_fallback_leaves"] == 0
        assert stats["export_checkouts"] == 3 * n_leaves
        assert stats["export_ttfp_ms"] is not None
        assert stats["export_ttfp_ms"] > 0
        sched = get_state().scheduler
        order = sched.export_order()
        assert len(order) == n_leaves
        assert sorted(order.values()) == list(range(n_leaves))
        # the pinned priority of every streamed key IS -ordinal
        for key, o in order.items():
            assert sched._key_priority[key] == -o
    # stream off: counters stay flat, TTFP still measured (the loop's
    # first submit), so the bench can A/B both arms
    with _ps_env({"BYTEPS_STREAM_EXPORT": "0",
                  "BYTEPS_FUSION_BYTES": "0"}) as bps:
        _run_steps(params, batch, cfg, steps=2)
        stats = bps.get_arena_stats()
        assert stats["export_streamed_leaves"] == 0
        assert stats["export_fallback_leaves"] > 0
        assert stats["export_ttfp_ms"] is not None


def test_stream_rowsparse_leaves_fall_back():
    """rowsparse-routed leaves are excluded from streaming (the host
    row-sparse path needs the dense host rows) but the round's other
    leaves still stream — and numerics match the non-streamed run."""
    cfg, params, batch = _setup()
    kw = dict(rowsparse_params=("w0",))
    with _ps_env({"BYTEPS_STREAM_EXPORT": "1",
                  "BYTEPS_FUSION_BYTES": "0"}) as bps:
        on, _ = _run_steps(params, batch, cfg, **kw)
        stats = bps.get_arena_stats()
        assert stats["export_streamed_leaves"] > 0
        assert stats["export_fallback_leaves"] > 0  # the rowsparse leaf
    with _ps_env({"BYTEPS_STREAM_EXPORT": "0",
                  "BYTEPS_FUSION_BYTES": "0"}):
        off, _ = _run_steps(params, batch, cfg, **kw)
    for a, b in zip(on, off):
        np.testing.assert_array_equal(a, b)
