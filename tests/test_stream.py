"""Gradient export + sharded optimizer apply (jax/train.py +
jax/optim.py; BYTEPS_SHARDED_APPLY): every leaf leaves the chip as an
output of the backward (dense, fused-bucket and host-codec configs:
keys, counters, the bucket's digest, the single-process baseline), no
program of the package plants a host callback on a mesh or on one
device, bitwise parity of the sharded apply against the fused optax
apply for adam/sgd, and the non-separable fallback."""

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
# the one route: every leaf an output of the backward
# --------------------------------------------------------------------- #


# fusion 0 = every leaf rides its own key ("dense"); fusion 4096 = the
# weights ride keys of their own, the biases the fused bucket
# ("fused-bucket"); the compression config exercises the host codec
# tier
@pytest.mark.parametrize("fusion,kw", [
    ("0", {}),
    ("4096", {}),
    ("0", dict(compression={"compressor": "onebit", "ef": "vanilla"},
               min_compress_bytes=0, device_compress=False)),
], ids=["dense", "fused-bucket", "onebit"])
def test_output_route_parity(fusion, kw):
    """Every leaf leaves as an output of the backward (these leaves are
    too small to shard): each is counted once a step, its bytes as a
    whole-leaf export; the declared keys are the leaves' own and, under
    a fusion size, one bucket whose digest is its members' names and
    sizes alone (a whole leaf between two members does not close it);
    the lossless transports track the single-process baseline."""
    import hashlib

    import jax

    cfg, params, batch = _setup()
    with _ps_env({"BYTEPS_FUSION_BYTES": fusion}) as bps:
        from byteps_tpu.core.state import get_state

        losses = []
        leaves, _ = _run_steps(params, batch, cfg, losses=losses, **kw)
        stats = bps.get_arena_stats()
        whole_bytes = bps.get_metrics()["counters"]["export/whole_bytes"]
        keys = sorted(c.name for c in
                      get_state().registry.contexts_in_order())
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    names = ["grad/" + "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                                for k in path) for path, _ in flat]
    sizes = [int(np.prod(x.shape)) for _, x in flat]
    assert stats["export_leaves"] == 3 * len(leaves)
    assert stats["export_rounds"] == 3 and stats["export_ttfp_ms"] > 0
    assert stats["export_shard_leaves"] == 0
    assert whole_bytes == 3 * sum(x.nbytes for x in leaves)
    members = [(n, z) for n, z in zip(names, sizes) if z * 4 < int(fusion)]
    want = [n for n, z in zip(names, sizes) if (n, z) not in members]
    if members:
        # two whole leaves (w0, w1) lie between the bucket's members
        assert [n for n, _ in members] == [
            "grad/b0", "grad/b1", "grad/b2", "grad/w2"]
        want.append("fused/" + hashlib.sha1(";".join(
            f"{n}:{z}" for n, z in members).encode()).hexdigest()[:12])
    assert keys == sorted(want)
    assert all(np.isfinite(x) for x in losses) and losses[-1] < losses[0]
    if not kw:  # lossless transports also track the local baseline
        base = _local_steps(params, batch, cfg)
        for a, b in zip(leaves, base):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


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
            "ingests": sorted(
                (sp[4]["leaf"], sp[4].get("dev", -1),
                 sp[4]["cause"].split(":")[0], sp[1].rsplit("_", 1)[0])
                for sp in spans if sp[0] == tracing.EXPORT_INGEST),
            "order": dict(get_state().scheduler.export_order()),
        }
    return out


def _spy_on_io_callback(monkeypatch):
    import jax.experimental

    planted = []
    real = jax.experimental.io_callback

    def spy(*a, **kw):
        planted.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(jax.experimental, "io_callback", spy)
    return planted


def test_the_plan_on_the_mesh_plants_no_host_callback(monkeypatch):
    """Eight devices: the weights shard (per-device bytes exactly even,
    shard keys at their parent's production-order priority) and each
    device's shard leaves as a program output, claimed on the train
    thread in flatten then mesh-device order beside the biases' whole
    leaves; no ``io_callback`` is planted by any program of the
    package; the parameters are bitwise what
    ``BYTEPS_LOCAL_SHARD_EXPORT=0`` (every leaf whole) gives."""
    import jax

    planted = _spy_on_io_callback(monkeypatch)
    env = {"BYTEPS_FUSION_BYTES": "0", "BYTEPS_SHARD_MIN_BYTES": "1024"}
    plan = _export_plan_run(env)
    whole = _export_plan_run({**env, "BYTEPS_LOCAL_SHARD_EXPORT": "0"})
    assert planted == []
    train = threading.current_thread().name.rsplit("_", 1)[0]
    ndims = [x.ndim for x in jax.tree.leaves(_setup()[1])]
    weights = [i for i, n in enumerate(ndims) if n == 2]
    biases = [i for i, n in enumerate(ndims) if n == 1]
    assert len(weights) == len(biases) == 3
    assert plan["report"]["streamed_leaves"] == 0
    assert plan["report"]["fallback_leaves"] == len(weights + biases)
    assert plan["arena"]["export_shard_leaves"] == 3 * len(weights)
    assert whole["arena"]["export_shard_leaves"] == 0
    assert plan["shard_bytes"] > 0 and whole["shard_bytes"] == 0
    assert plan["shard_bytes"] + plan["whole_bytes"] == whole["whole_bytes"]
    per_dev = [plan["device_bytes"][d] for d in range(1, 8)]
    assert len(set(per_dev)) == 1 and per_dev[0] * 8 == plan["shard_bytes"]
    # one ingest a (weight, device) and one a bias, all outputs, all on
    # the thread that claims
    assert plan["ingests"] == sorted(
        [(w, d, "out", train) for w in weights for d in range(8)]
        + [(b, -1, "out", train) for b in biases])
    assert whole["ingests"] == sorted(
        (i, -1, "out", train) for i in weights + biases)
    # the shards' keys keep a production order (the claim's: flatten
    # order), one ordinal a leaf; a whole leaf is in none
    assert sorted(set(plan["order"].values())) == list(range(len(weights)))
    assert len(plan["order"]) == len(weights) * (8 + 1)
    assert whole["order"] == {}
    for a, b in zip(plan["leaves"], whole["leaves"]):
        np.testing.assert_array_equal(a, b)


def test_one_device_plants_no_host_callback(monkeypatch):
    """A one-device mesh has nothing to shard: the program that runs is
    ``grad_fn``, no ``io_callback`` is ever planted, and
    ``BYTEPS_LOCAL_SHARD_EXPORT=0`` changes nothing."""
    planted = _spy_on_io_callback(monkeypatch)
    env = {"BYTEPS_FUSION_BYTES": "0", "BYTEPS_SHARD_MIN_BYTES": "1024"}
    plan = _export_plan_run(env, mesh_devices=1)
    whole = _export_plan_run({**env, "BYTEPS_LOCAL_SHARD_EXPORT": "0"},
                             mesh_devices=1)
    assert planted == []
    assert plan["shard_bytes"] == 0
    assert plan["report"]["streamed_leaves"] == 0
    assert plan["report"]["fallback_leaves"] == 6
    assert [m[2] for m in plan["ingests"]] == ["out"] * 6
    assert plan["order"] == {}
    assert plan["whole_bytes"] == whole["whole_bytes"] > 0
    assert plan["ingests"] == whole["ingests"]
    for a, b in zip(plan["leaves"], whole["leaves"]):
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
