"""Locality-sharded export/import (BYTEPS_LOCAL_SHARD_EXPORT,
jax/train.py + jax/optim.py make_shard_apply + core/registry.py shard
subranges): bitwise parity of shard-export on vs off vs the
single-process baseline for dense, fused-bucket and
compression-fallback configs; odd (non-divisible) shapes with padding;
the export plan's rule, clause by clause, without a server
(``jax/train.py _export_plan``); the pad-threshold and local_size==1
fallbacks; shard keys sharing the parent's production ordinal; each
device's shard a program output (bitwise the whole leaves, no host
callback in any backward program, a failed claim cleaned up after);
and a slow mixed-traffic churn asserting no arena-lease or handle
leaks under per-shard checkouts.

Bitwise parity relies on the conftest's
``--xla_cpu_enable_fast_math=false`` pin: XLA CPU fast-math
reassociates FMA contraction per shape, which would put 1-ULP noise on
exactly the property these tests guard (TPU codegen has no such
reassociation)."""

import contextlib
import os
import threading

import numpy as np
import optax
import pytest

from byteps_tpu.config import Config
from byteps_tpu.server import run_server

_PORT = [23700]


@contextlib.contextmanager
def _ps_env(extra_env: dict = None):
    from byteps_tpu.core.state import GlobalState

    port = _PORT[0]
    _PORT[0] += 1
    env = {
        "DMLC_NUM_WORKER": "1", "DMLC_NUM_SERVER": "1",
        "DMLC_PS_ROOT_URI": "127.0.0.1", "DMLC_PS_ROOT_PORT": str(port),
        "BYTEPS_FORCE_DISTRIBUTED": "1",
        # the mlp fixture's weights are 1-48KB: drop the shard floor so
        # they shard on the 8-device mesh
        "BYTEPS_SHARD_MIN_BYTES": "1024",
        **(extra_env or {}),
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


def _run_steps(params, batch, cfg, steps=3, tx=None, mesh=None, **kw):
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
    jax.block_until_ready(jax.tree.leaves(params))
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
# the export plan's rule, without a server
# --------------------------------------------------------------------- #

# names and host-side stand-ins of a gradient tree: two leaves large
# enough to shard, one row-sparse by name, one that pads 1 of 7
_PLAN_LEAVES = {"grad/w": (64, 48), "grad/embed": (64, 48),
                "grad/small": (48,), "grad/frag": (7,)}
_PLAN_DEFAULTS = dict(axis="dp", fusion_bytes=0, shard_min_bytes=1024,
                      local_shard=True, rowsparse_params=None,
                      host_codec=False, scheduler_running=True)


@pytest.mark.parametrize("change,mesh_shape,want", [
    ({}, (8,), ["grad/w", "grad/embed"]),
    ({"shard_min_bytes": 16384}, (8,), []),
    ({"shard_min_bytes": 0}, (8,), ["grad/w", "grad/embed", "grad/small"]),
    ({"rowsparse_params": ("embed",)}, (8,), ["grad/w"]),
    ({"host_codec": True}, (8,), []),
    ({}, (2, 4), []),
    ({}, (1,), []),
    ({"shard_min_bytes": 0, "fusion_bytes": 16384}, (8,), []),
    ({"scheduler_running": False}, (8,), []),
    ({"local_shard": False}, (8,), []),
], ids=["default", "below-shard-min-bytes", "padding-over-an-eighth",
        "rowsparse-name", "host-codec", "two-mesh-axes", "axis-of-one",
        "bucket-member", "no-scheduler", "knob-off"])
def test_export_plan(change, mesh_shape, want):
    """``_export_plan`` alone: which leaves shard, and why not. A leaf
    shards when it rides a dense key of its own, is at least
    ``max(fusion_bytes, shard_min_bytes)`` and pads by at most 1/8
    (``grad/frag``: 1 of 7 elements on 8 shards, whatever the floor);
    nothing shards under a host codec, without a scheduler, on two mesh
    axes or on an axis of one device."""
    import jax
    from jax.sharding import Mesh

    from byteps_tpu.jax.train import ExportPlan, _export_plan

    n = int(np.prod(mesh_shape))
    mesh = Mesh(np.array(jax.devices()[:n]).reshape(mesh_shape),
                ("dp", "tp")[:len(mesh_shape)])
    names = list(_PLAN_LEAVES)
    leaves = [np.zeros(shape, np.float32) for shape in _PLAN_LEAVES.values()]
    plan = _export_plan(names, leaves, mesh=mesh,
                        **{**_PLAN_DEFAULTS, **change})
    assert [names[i] for i in plan.shard_set] == want
    if not want:
        assert plan == ExportPlan()
        return
    assert plan.n_shard == 8
    for i, (size, shard_len, dtype) in zip(plan.shard_set, plan.layouts):
        assert size == leaves[i].size and dtype == np.float32
        assert shard_len * 8 >= size > (shard_len - 1) * 8
    assert hash(plan) == hash(_export_plan(
        names, leaves, mesh=mesh, **{**_PLAN_DEFAULTS, **change}))


# --------------------------------------------------------------------- #
# parity: shard on vs off vs single-process baseline, per codec class
# --------------------------------------------------------------------- #


# fusion 0 = every leaf rides its own key (all weights shard, biases
# export whole); fusion 4096 = biases ride the fused bucket while the
# weights shard ("fused-bucket"); the compression config must FALL BACK
# entirely — the codec unit is the declared key, so host-compressed
# rounds keep whole-leaf keys ("compressed-fallback")
@pytest.mark.parametrize("fusion,kw,want_shards", [
    ("0", {}, True),
    ("4096", {}, True),
    ("0", dict(compression={"compressor": "onebit", "ef": "vanilla"},
               min_compress_bytes=0, device_compress=False), False),
], ids=["dense", "fused-bucket", "compressed-fallback"])
def test_shard_on_off_parity(fusion, kw, want_shards):
    """Shard-export on and off produce IDENTICAL params after 3 steps —
    reduce-scatter + per-shard PS exchange + shard update + all-gather
    is bitwise the psum + whole-leaf exchange + full-leaf update — and
    the lossless configs track the single-process baseline."""
    cfg, params, batch = _setup()
    with _ps_env({"BYTEPS_FUSION_BYTES": fusion}) as bps:
        on, _ = _run_steps(params, batch, cfg,
                           local_shard_export=True, **kw)
        stats = bps.get_arena_stats()
        if want_shards:
            assert stats["export_shard_leaves"] > 0, \
                "shard export never engaged — the on-arm is vacuous"
            assert stats["shard_checkouts"] > 0
        else:
            assert stats["export_shard_leaves"] == 0, \
                "host-compressed leaves must keep whole-leaf keys"
    with _ps_env({"BYTEPS_FUSION_BYTES": fusion}) as bps:
        off, _ = _run_steps(params, batch, cfg,
                            local_shard_export=False, **kw)
        assert bps.get_arena_stats()["export_shard_leaves"] == 0
    for a, b in zip(on, off):
        np.testing.assert_array_equal(a, b)
    if not kw:  # lossless transports also track the local baseline
        base = _local_steps(params, batch, cfg)
        for a, b in zip(on, base):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_odd_shapes_pad_parity():
    """Non-divisible leaves (350 = 8*44 - 2, 1000 = 8*125) shard with
    padding and stay bitwise identical to the whole-leaf path: the pad
    travels the wire as zeros and is trimmed before the update's result
    re-enters the params."""
    import jax
    import jax.numpy as jnp

    from byteps_tpu.core.state import get_state
    from byteps_tpu.jax.train import make_ps_train_step

    rng = np.random.RandomState(0)
    params = {"odd": jnp.asarray(rng.randn(50, 7).astype(np.float32)),
              "even": jnp.asarray(rng.randn(1000).astype(np.float32)),
              "tiny": jnp.asarray(rng.randn(16).astype(np.float32))}
    batch = {"x": jnp.asarray(rng.rand(32, 50), np.float32)}

    def loss_fn(p, b):
        return (jnp.mean((b["x"] @ p["odd"]) ** 2)
                + jnp.sum(p["even"] ** 2) * 1e-3
                + jnp.sum(p["tiny"] ** 2) * 1e-3)

    tx = optax.adam(1e-2)

    def run(shard):
        p = jax.tree.map(jnp.array, params)
        opt = tx.init(p)
        step = make_ps_train_step(loss_fn, tx, get_state().mesh,
                                  local_shard_export=shard)
        for _ in range(3):
            p, opt, _ = step(p, opt, batch)
        jax.block_until_ready(jax.tree.leaves(p))
        return [np.asarray(x) for x in jax.tree.leaves(p)]

    with _ps_env({"BYTEPS_FUSION_BYTES": "0"}) as bps:
        on = run(True)
        assert bps.get_arena_stats()["export_shard_leaves"] > 0
    with _ps_env({"BYTEPS_FUSION_BYTES": "0"}):
        off = run(False)
    for a, b in zip(on, off):
        np.testing.assert_array_equal(a, b)


def test_pad_threshold_falls_back():
    """A leaf whose padding would exceed 1/8 of its size keeps the
    whole-leaf path (with 8 shards that can only happen to sub-56-elem
    leaves, so the floor is dropped to expose the gate)."""
    import jax
    import jax.numpy as jnp

    from byteps_tpu.core.state import get_state
    from byteps_tpu.jax.train import make_ps_train_step

    rng = np.random.RandomState(0)
    # 1024 elems: shards cleanly; 7 elems: pad 1, 8*1 > 7 -> fallback
    params = {"big": jnp.asarray(rng.randn(1024).astype(np.float32)),
              "frag": jnp.asarray(rng.randn(7).astype(np.float32))}
    batch = {"x": jnp.asarray(rng.rand(8, 4), np.float32)}

    def loss_fn(p, b):
        return (jnp.sum(p["big"] ** 2) + jnp.sum(p["frag"] ** 2)
                + 0.0 * jnp.sum(b["x"]))

    tx = optax.sgd(1e-2)
    env = {"BYTEPS_FUSION_BYTES": "0", "BYTEPS_SHARD_MIN_BYTES": "8"}
    with _ps_env(env) as bps:
        p = jax.tree.map(jnp.array, params)
        opt = tx.init(p)
        step = make_ps_train_step(loss_fn, tx, get_state().mesh)
        for _ in range(2):
            p, opt, _ = step(p, opt, batch)
        stats = bps.get_arena_stats()
        # exactly ONE leaf per step sharded (big); frag exported whole
        assert stats["export_shard_leaves"] == 2
        assert stats["export_leaves"] == 4


def test_local_size_one_degenerate_is_whole_leaf():
    """A single-device mesh has no locality axis: shard on must equal
    shard off byte-for-byte AND never declare a shard key."""
    import jax

    cfg, params, batch = _setup()
    from jax.sharding import Mesh

    def run(shard):
        mesh1 = Mesh(np.array(jax.devices()[:1]), ("dp",))
        return _run_steps(params, batch, cfg, mesh=mesh1,
                          local_shard_export=shard)[0]

    with _ps_env() as bps:
        on = run(True)
        assert bps.get_arena_stats()["export_shard_leaves"] == 0
        from byteps_tpu.core.state import get_state
        assert not any("@shard" in n
                       for n in get_state().registry._contexts)
    with _ps_env():
        off = run(False)
    for a, b in zip(on, off):
        np.testing.assert_array_equal(a, b)


def test_shard_keys_share_parent_production_ordinal():
    """All shard subranges of one leaf are ONE production event: they
    share the parent's first-export ordinal, so the queue's
    key-ascending tie-break keeps a leaf's shards adjacent instead of
    interleaving racing devices' fires across leaves."""
    cfg, params, batch = _setup()
    with _ps_env({"BYTEPS_FUSION_BYTES": "0"}) as bps:
        from byteps_tpu.core.state import get_state

        _run_steps(params, batch, cfg, local_shard_export=True)
        state = get_state()
        order = state.scheduler.export_order()
        reg = state.registry
        by_parent = {}
        for name in list(reg._contexts):
            if "@shard" not in name:
                continue
            parent = name.split("@shard")[0]
            ctx = reg.get(name)
            if ctx.declared_key in order:
                by_parent.setdefault(parent, set()).add(
                    order[ctx.declared_key])
        assert by_parent, "no shard keys reached the scheduler"
        for parent, ordinals in by_parent.items():
            assert len(ordinals) == 1, \
                f"{parent}: shards carry ordinals {ordinals}"
        # distinct leaves still get distinct ordinals
        all_ords = [next(iter(o)) for o in by_parent.values()]
        assert len(set(all_ords)) == len(all_ords)


def test_shard_apply_unavailable_still_shards_wire():
    """A per-leaf-separable but NOT shard-separable transform
    (block-RMS clipping mixes elements within a leaf) keeps the
    whole-leaf UPDATE while the wire still moves shards — and stays
    bitwise with the whole-leaf path."""
    cfg, params, batch = _setup()
    tx = optax.chain(optax.clip_by_block_rms(1.0), optax.sgd(1e-2))
    with _ps_env({"BYTEPS_FUSION_BYTES": "0"}) as bps:
        on, _ = _run_steps(params, batch, cfg, tx=tx,
                           local_shard_export=True)
        assert bps.get_arena_stats()["export_shard_leaves"] > 0
    with _ps_env({"BYTEPS_FUSION_BYTES": "0"}):
        off, _ = _run_steps(params, batch, cfg, tx=tx,
                            local_shard_export=False)
    for a, b in zip(on, off):
        np.testing.assert_array_equal(a, b)


# --------------------------------------------------------------------- #
# on a mesh: shard leaves as per-device outputs
# --------------------------------------------------------------------- #


def _route_run(shard, steps=4):
    """``steps`` PS steps on the 8-device mesh with
    BYTEPS_LOCAL_SHARD_EXPORT unset or "0": parameters, every loss, the
    declared keys and the export counters."""
    cfg, params, batch = _setup()
    env = {"BYTEPS_FUSION_BYTES": "0"}
    if not shard:
        env["BYTEPS_LOCAL_SHARD_EXPORT"] = "0"
    with _ps_env(env) as bps:
        import jax
        import jax.numpy as jnp

        from byteps_tpu.core.state import get_state
        from byteps_tpu.jax.train import make_ps_train_step
        from byteps_tpu.models import mlp

        p = jax.tree.map(jnp.array, params)
        tx = optax.adam(1e-2)
        opt = tx.init(p)
        step = make_ps_train_step(lambda q, b: mlp.loss_fn(q, b, cfg), tx,
                                  get_state().mesh)
        losses = []
        for _ in range(steps):
            p, opt, loss = step(p, opt, batch)
            losses.append(np.asarray(loss))
        ctr = bps.get_metrics()["counters"]
        return {
            "leaves": [np.asarray(x) for x in jax.tree.leaves(p)],
            "losses": losses,
            "keys": sorted(c.name for c in
                           get_state().registry.contexts_in_order()),
            "shard_bytes": ctr.get("export/shard_bytes", 0),
            "whole_bytes": ctr.get("export/whole_bytes", 0),
            "push_bytes": ctr.get("wire/push_bytes", 0),
            "device_bytes": {k: v for k, v in ctr.items()
                             if k.startswith("export/device_bytes/")},
            "arena": bps.get_arena_stats(),
            "report": bps.get_step_reports()[-1],
        }


def test_shards_on_a_mesh_are_bitwise_the_whole_leaves():
    """Eight devices: the weights reduce-scatter and each device's shard
    leaves as a program output. Every loss and every parameter over
    four steps is bitwise what ``BYTEPS_LOCAL_SHARD_EXPORT=0`` (no
    shard plan, whole leaves) gives; the shard keys, the shard bytes
    and each device's bytes are the plan's."""
    plan, off = _route_run(True), _route_run(False)
    for a, b in zip(plan["leaves"], off["leaves"]):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(plan["losses"], off["losses"]):
        np.testing.assert_array_equal(a, b)
    shard_keys = [k for k in plan["keys"] if "@shard" in k]
    assert len(shard_keys) == 3 * 8
    assert [k for k in plan["keys"] if "@shard" not in k] == off["keys"]
    assert plan["shard_bytes"] > 0 and off["shard_bytes"] == 0
    assert sorted(plan["device_bytes"]) == [
        f"export/device_bytes/{d}" for d in range(8)]
    assert len({plan["device_bytes"][f"export/device_bytes/{d}"]
                for d in range(1, 8)}) == 1
    # a device's share is exactly an eighth of the sharded leaves'
    # bytes, which are the bytes the whole-leaf step exported beyond
    # what both steps export whole; the wire carries the same bytes
    assert plan["device_bytes"]["export/device_bytes/7"] * 8 \
        == plan["shard_bytes"]
    assert plan["shard_bytes"] == off["whole_bytes"] - plan["whole_bytes"]
    assert off["device_bytes"] == {
        "export/device_bytes/0": off["whole_bytes"]}
    assert plan["push_bytes"] == off["push_bytes"]
    n_leaves = len(plan["leaves"])
    assert plan["arena"]["export_shard_leaves"] == 4 * 3
    assert plan["arena"]["export_leaves"] == 4 * n_leaves
    assert plan["report"]["streamed_leaves"] == 0
    assert plan["report"]["fallback_leaves"] == n_leaves


@pytest.mark.parametrize("devices,shard", [(1, False), (8, True), (8, False)],
                         ids=["one-device", "mesh-sharded", "mesh-whole"])
def test_no_backward_program_holds_a_host_callback(devices, shard):
    """The backward programs a PS step runs, picked as ``step`` picks
    them from the plan: ``grad_fn`` (``_psum_backward``) where nothing
    shards, the reduce-scatter backward where leaves do. Neither holds
    a host callback (so the persistent compile cache can serve it), and
    only the shard leaves' outputs are sharded."""
    import jax
    from jax.sharding import Mesh

    from byteps_tpu.jax import train
    from byteps_tpu.models import mlp

    cfg, params, batch = _setup()
    mesh = Mesh(np.array(jax.devices()[:devices]), ("dp",))
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    plan = train._export_plan(
        [str(path) for path, _ in flat], [x for _, x in flat], mesh=mesh,
        **{**_PLAN_DEFAULTS, "local_shard": shard or devices == 1})
    assert bool(plan.shard_set) == shard
    assert plan.shard_set == (tuple(
        i for i, (_, x) in enumerate(flat) if x.ndim == 2) if shard else ())
    loss = train._loss_and_stats(lambda p, b: mlp.loss_fn(p, b, cfg))
    fn = train._scatter_backward(loss, mesh, "dp", plan.shard_set,
                                 len(flat)) \
        if plan.shard_set else train._psum_backward(loss, mesh, "dp")
    text = fn.lower(params, batch).as_text()
    assert "callback" not in text
    assert ("reduce_scatter" in text) == shard
    (_, _), grads = fn(params, batch)
    for i, g in enumerate(jax.tree.leaves(grads)):
        assert (len(g.sharding.spec) == 1) == (i in plan.shard_set)


@pytest.mark.parametrize("shard", [True, False],
                         ids=["shard", "whole-leaf"])
def test_a_failed_claim_abandons_leases_and_discards_handles(
        shard, monkeypatch):
    """One device's shard of the second weight (or, with nothing
    sharded, the fifth whole leaf) cannot be submitted: the step raises
    that error, the leaves already on the wire leave no handle behind,
    and every staging slot of the round is abandoned (dropped from the
    table, never recycled under a late writer)."""
    import time

    from byteps_tpu.server import client as client_mod

    cfg, params, batch = _setup()
    real = client_mod.get_or_init_ctx
    seen = []

    # the second shard leaf's third device; the fifth whole leaf
    nth = 11 if shard else 5

    def failing(state, name, flat):
        if ("@shard" in name) == shard:
            seen.append(name)
            if len(seen) == nth:
                raise RuntimeError("submit refused for this test")
        return real(state, name, flat)

    env = {"BYTEPS_FUSION_BYTES": "0"}
    if not shard:
        env["BYTEPS_LOCAL_SHARD_EXPORT"] = "0"
    with _ps_env(env) as bps:
        from byteps_tpu.core.state import get_state

        monkeypatch.setattr(client_mod, "get_or_init_ctx", failing)
        with pytest.raises(RuntimeError, match="submit refused"):
            _run_steps(params, batch, cfg, steps=1)
        monkeypatch.setattr(client_mod, "get_or_init_ctx", real)
        assert len(seen) == nth
        state = get_state()
        deadline = time.time() + 30
        while time.time() < deadline and state.handles._handles:
            time.sleep(0.05)
        assert not state.handles._handles, \
            f"leaked handles: {list(state.handles._handles)[:8]}"
        with state.arena._mu:
            busy = [k for k, sl in state.arena._slots.items() if sl.busy]
        assert not busy, f"leaked busy arena slots: {busy[:8]}"
        assert bps.get_arena_stats()["export_rounds"] == 0


# --------------------------------------------------------------------- #
# churn: no lease/handle leaks under per-shard checkouts
# --------------------------------------------------------------------- #


@pytest.mark.slow
def test_mixed_traffic_churn_no_leaks():
    """Many rounds of mixed traffic — sharded weights, fused-bucket
    biases, a rowsparse-routed embedding — then drain the deferred
    releases and assert: no busy arena slots, no live handles, and the
    per-shard checkout counter actually moved (the leases under test
    existed)."""
    import time

    import jax
    import jax.numpy as jnp

    from byteps_tpu.core.state import get_state
    from byteps_tpu.jax.train import make_ps_train_step

    rng = np.random.RandomState(0)
    params = {"w1": jnp.asarray(rng.randn(64, 48).astype(np.float32)),
              "w2": jnp.asarray(rng.randn(48, 32).astype(np.float32)),
              "b1": jnp.asarray(rng.randn(48).astype(np.float32)),
              "embed": jnp.asarray(rng.randn(64, 16).astype(np.float32)),
              "odd": jnp.asarray(rng.randn(50, 7).astype(np.float32))}
    batch = {"x": jnp.asarray(rng.rand(32, 64), np.float32),
             "ids": jnp.asarray(rng.randint(0, 8, 32), np.int32)}

    def loss_fn(p, b):
        h = jnp.tanh(b["x"] @ p["w1"] + p["b1"])
        e = jnp.take(p["embed"], b["ids"], axis=0)
        return (jnp.mean((h @ p["w2"]) ** 2) + jnp.mean(e * e)
                + jnp.sum(p["odd"] ** 2) * 1e-3)

    tx = optax.adam(1e-3)
    with _ps_env({"BYTEPS_FUSION_BYTES": "1024"}) as bps:
        state = get_state()
        p = jax.tree.map(jnp.array, params)
        opt = tx.init(p)
        step = make_ps_train_step(loss_fn, tx, state.mesh,
                                  rowsparse_params=("embed",),
                                  local_shard_export=True)
        for _ in range(25):
            p, opt, _ = step(p, opt, batch)
        jax.block_until_ready(jax.tree.leaves(p))
        stats = bps.get_arena_stats()
        assert stats["export_shard_leaves"] > 0
        assert stats["shard_checkouts"] > 0
        # the deferred releases ride the release worker: give it a
        # bounded beat to observe the last round's import readiness
        deadline = time.time() + 30
        while time.time() < deadline:
            with state.arena._mu:
                busy = [k for k, s in state.arena._slots.items()
                        if s.busy]
            if not busy and not state.handles._handles:
                break
            time.sleep(0.1)
        assert not busy, f"leaked busy arena slots: {busy[:8]}"
        assert not state.handles._handles, \
            f"leaked handles: {list(state.handles._handles)[:8]}"
