"""Locality-sharded export/import (BYTEPS_LOCAL_SHARD_EXPORT,
jax/train.py + jax/optim.py make_shard_apply + core/registry.py shard
subranges): bitwise parity of shard-export on vs off vs the
single-process baseline for dense, fused-bucket and
compression-fallback configs; odd (non-divisible) shapes with padding;
the pad-threshold and local_size==1 fallbacks; shard keys sharing the
parent's production ordinal; the route BYTEPS_STREAM_EXPORT unset
chooses on a mesh (each device's shard a program output: bitwise the
tapped shards and the whole leaves, no host callback in the program,
a failed claim cleaned up after); and a slow mixed-traffic churn
asserting no arena-lease or handle leaks under per-shard checkouts.

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


# unset, nothing is tapped: the shard leaf leaves as per-device outputs,
# the fragment as a whole one; asked for, both are tapped
@pytest.mark.parametrize("stream,streamed", [(None, 0), ("1", 4)],
                         ids=["unset", "taps-asked"])
def test_pad_threshold_falls_back(stream, streamed):
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
    if stream is not None:
        env["BYTEPS_STREAM_EXPORT"] = stream
    with _ps_env(env) as bps:
        p = jax.tree.map(jnp.array, params)
        opt = tx.init(p)
        step = make_ps_train_step(loss_fn, tx, get_state().mesh)
        for _ in range(2):
            p, opt, _ = step(p, opt, batch)
        stats = bps.get_arena_stats()
        # exactly ONE leaf per step sharded (big), on either route; frag
        # exported whole; tapped only where taps were asked for
        assert stats["export_shard_leaves"] == 2
        assert stats["export_streamed_leaves"] == streamed
        assert stats["export_fallback_leaves"] == 4 - streamed


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


def test_broken_taps_raise(monkeypatch):
    """A tapped backward that cannot be built is an ERROR, never a quiet
    switch to the post-jit export: a run-time fallback would hide a
    device or compile fault behind a slower step, and a worker that
    changed its export path alone could change its PS key set (whole
    leaf vs shard subranges) and stall every peer's aggregation. The
    export path is decided by configuration only."""
    import jax
    import jax.experimental

    cfg, params, batch = _setup()

    def _dead_tap(*a, **k):
        raise RuntimeError("io_callback disabled for this test")

    # taps are planted only where they are asked for
    with _ps_env({"BYTEPS_FUSION_BYTES": "0",
                  "BYTEPS_STREAM_EXPORT": "1"}) as bps:
        monkeypatch.setattr(jax.experimental, "io_callback", _dead_tap)
        with pytest.raises(RuntimeError, match="io_callback disabled"):
            _run_steps(params, batch, cfg, local_shard_export=True)
        # nothing left the worker, and no staging slot stayed leased
        stats = bps.get_arena_stats()
        assert stats["export_streamed_leaves"] == 0
        assert bps.get_metrics()["counters"]["export/shard_bytes"] == 0


# --------------------------------------------------------------------- #
# the route unset chooses on a mesh: shard leaves as per-device outputs
# --------------------------------------------------------------------- #


def _route_run(arm, steps=4):
    """``steps`` PS steps on the 8-device mesh with BYTEPS_STREAM_EXPORT
    unset (``None``), "1" or "0": parameters, every loss, the declared
    keys and the export counters."""
    cfg, params, batch = _setup()
    env = {"BYTEPS_FUSION_BYTES": "0"}
    if arm is not None:
        env["BYTEPS_STREAM_EXPORT"] = arm
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
            "device_bytes": {k: v for k, v in ctr.items()
                             if k.startswith("export/device_bytes/")},
            "arena": bps.get_arena_stats(),
            "report": bps.get_step_reports()[-1],
        }


def test_unset_on_a_mesh_is_bitwise_the_taps_and_the_whole_leaves():
    """Nobody set BYTEPS_STREAM_EXPORT, eight devices: the weights
    reduce-scatter and each device's shard leaves as a program output.
    Every loss and every parameter over four steps is bitwise what
    ``=1`` (the same shards, tapped) and ``=0`` (no shard plan, whole
    leaves) give; against ``=1`` the declared keys, the shard bytes and
    each device's bytes are the same, and no leaf counts as streamed."""
    unset, asked, off = _route_run(None), _route_run("1"), _route_run("0")
    for other in (asked, off):
        for a, b in zip(unset["leaves"], other["leaves"]):
            np.testing.assert_array_equal(a, b)
        for a, b in zip(unset["losses"], other["losses"]):
            np.testing.assert_array_equal(a, b)
    assert unset["keys"] == asked["keys"]
    assert any("@shard" in k for k in unset["keys"])
    assert not any("@shard" in k for k in off["keys"])
    assert unset["shard_bytes"] == asked["shard_bytes"] > 0
    assert off["shard_bytes"] == 0
    assert unset["device_bytes"] == asked["device_bytes"]
    assert sorted(unset["device_bytes"]) == [
        f"export/device_bytes/{d}" for d in range(8)]
    n_leaves = len(unset["leaves"])
    assert unset["arena"]["export_shard_leaves"] == \
        asked["arena"]["export_shard_leaves"] == 4 * 3
    assert unset["arena"]["export_streamed_leaves"] == 0
    assert unset["report"]["streamed_leaves"] == 0
    assert unset["report"]["fallback_leaves"] == n_leaves
    assert asked["report"]["streamed_leaves"] == n_leaves


@pytest.mark.parametrize("tapped", [False, True],
                         ids=["unset", "taps-asked"])
def test_the_scatter_backward_holds_a_host_callback_only_where_asked(tapped):
    """The program a mesh runs under unset: the reduce-scatter backward
    with no host callback in it (so the persistent compile cache can
    serve it) and no step tag among its arguments; the same builder
    with taps planted holds one a tapped leaf."""
    import jax
    from jax.experimental import io_callback

    from byteps_tpu.core.state import get_state
    from byteps_tpu.jax import train
    from byteps_tpu.models import mlp

    cfg, params, batch = _setup()
    shard_set = tuple(i for i, x in enumerate(jax.tree.leaves(params))
                      if x.ndim == 2)
    n_leaves = len(jax.tree.leaves(params))

    def plant(i, step_tag, idx, g):
        io_callback(lambda *a: None, None, step_tag, idx, g, ordered=False)

    with _ps_env():
        mesh = get_state().mesh
        fn = train._scatter_backward(
            train._loss_and_stats(lambda p, b: mlp.loss_fn(p, b, cfg)),
            mesh, "dp", shard_set, n_leaves,
            tapped=shard_set if tapped else (),
            plant=plant if tapped else None)
        args = (params, batch)
        if tapped:
            args = (np.int32(1),) + args
        text = fn.lower(*args).as_text()
        (_, _), grads = fn(*args)
    assert ("callback" in text) == tapped
    assert "reduce_scatter" in text
    for i, g in enumerate(grads):
        assert (len(g.sharding.spec) == 1) == (i in shard_set)


@pytest.mark.parametrize("stream", [None, "1"], ids=["unset", "taps-asked"])
def test_a_failed_shard_claim_abandons_leases_and_discards_handles(
        stream, monkeypatch):
    """One device's shard of the second weight cannot be submitted: the
    step raises that error on either route, the shards already on the
    wire leave no handle behind, and every staging slot of the round is
    abandoned (dropped from the table, never recycled under a late
    writer)."""
    import time

    from byteps_tpu.server import client as client_mod

    cfg, params, batch = _setup()
    real = client_mod.get_or_init_ctx
    seen = []

    def failing(state, name, flat):
        if "@shard" in name:
            seen.append(name)
            if len(seen) == 11:  # the second shard leaf's third device
                raise RuntimeError("shard submit refused for this test")
        return real(state, name, flat)

    env = {"BYTEPS_FUSION_BYTES": "0"}
    if stream is not None:
        env["BYTEPS_STREAM_EXPORT"] = stream
    with _ps_env(env) as bps:
        from byteps_tpu.core.state import get_state

        monkeypatch.setattr(client_mod, "get_or_init_ctx", failing)
        with pytest.raises(RuntimeError, match="shard submit refused"):
            _run_steps(params, batch, cfg, steps=1)
        monkeypatch.setattr(client_mod, "get_or_init_ctx", real)
        assert len(seen) >= 11
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
