"""Cross-barrier bounded-staleness pipelining (BYTEPS_CROSS_BARRIER /
BYTEPS_STALENESS, the PR 16 tentpole): the server's round-window gate
(a stamped fold up to W rounds ahead is parked and re-dispatched at
publish, never mis-summed; beyond W it error-replies loudly), SIGKILL
failover mid-window recovering bitwise via replay epochs, determinism
of the window bookkeeping across independent server instances, the
staleness-0 bitwise parity contract, and staleness-1 convergence with
the carry engaged end to end through make_ps_train_step."""

import contextlib
import os
import signal
import threading
import time

import numpy as np
import optax
import pytest

from byteps_tpu.config import Config
from byteps_tpu.core.types import DataType, RequestType, get_command_type
from byteps_tpu.server import run_server
from byteps_tpu.server.client import PSClient

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PORT = [24800]

CMD_F32 = get_command_type(RequestType.DEFAULT_PUSH_PULL, DataType.FLOAT32)


def _epoch(round_no: int, attempt: int = 0) -> int:
    return (round_no << 16) | attempt


def _windowed_server(num_workers=1, staleness="1"):
    """An in-process server with the staleness window armed. The native
    ctor reads BYTEPS_CROSS_BARRIER/BYTEPS_STALENESS per instance, so
    the env must stay set until the server has actually constructed —
    the listening port accepting connections proves it has."""
    from byteps_tpu.utils.net import wait_port

    env = {"BYTEPS_CROSS_BARRIER": "1", "BYTEPS_STALENESS": staleness}
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        port = _PORT[0]
        _PORT[0] += 1
        t = threading.Thread(
            target=run_server,
            args=(port, Config(num_workers=num_workers, num_servers=1)),
            daemon=True)
        t.start()
        wait_port(port, 60)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return port, t


def _init_key(c0, c1, key, n, server=0):
    th = threading.Thread(
        target=c0.init_key, args=(server, key, np.zeros(n, np.float32),
                                  CMD_F32), daemon=True)
    th.start()
    c1.init_key(server, key, np.zeros(n, np.float32), CMD_F32)
    th.join(timeout=15)
    assert not th.is_alive()


# --------------------------------------------------------------------- #
# window gate: defer within W, loud reject beyond W
# --------------------------------------------------------------------- #


@pytest.mark.chaos
def test_window_defers_ahead_round_then_publishes_in_order():
    """A stamped fold ONE round ahead of the open round (the exact
    shape the cross-barrier carry produces when one worker enters step
    k+1 while a peer still drains step k) is PARKED, the open round
    publishes its true sum untouched, and the deferred fold is
    re-dispatched into its own round — both rounds bitwise exact."""
    port, t = _windowed_server(num_workers=2)
    addr = [f"127.0.0.1:{port}"]
    c0 = PSClient(addr, worker_id=0)
    c1 = PSClient(addr, worker_id=1)
    n = 256
    key = 5
    x0 = np.arange(n, dtype=np.float32)
    x1 = np.full(n, 7.0, np.float32)
    _init_key(c0, c1, key, n)

    # round 1 completes normally
    c0.zpush(0, key, x0, CMD_F32, epoch=_epoch(1))
    c1.zpush(0, key, x1, CMD_F32, epoch=_epoch(1))
    out = np.empty(n, np.float32)
    c0.zpull(0, key, out, CMD_F32, exact=True)
    c1.zpull(0, key, out, CMD_F32, exact=True)
    np.testing.assert_array_equal(out, x0 + x1)

    # w0 folds round 2, then races ahead with round 3 while round 2 is
    # still open — within window 1 this DEFERS (the pre-window gate
    # error-replied it); the push's reply only lands when round 3
    # publishes, so it rides a background thread
    c0.zpush(0, key, x0 * 2, CMD_F32, epoch=_epoch(2))
    err = []

    def _ahead():
        try:
            c0.zpush(0, key, x0 * 3, CMD_F32, epoch=_epoch(3))
        except Exception as e:  # noqa: BLE001 - assert below
            err.append(e)

    th = threading.Thread(target=_ahead, daemon=True)
    th.start()
    time.sleep(0.3)  # the ahead fold reaches the server and parks
    # round 2 completes: its aggregate must be EXACTLY round 2's sum.
    # Pull it from w1 — w0 is a round AHEAD (its deferred fold already
    # applied at publish), so w0's unstamped pull correctly parks until
    # round 3 publishes rather than handing it round 2's bytes.
    c1.zpush(0, key, x1 * 2, CMD_F32, epoch=_epoch(2))
    c1.zpull(0, key, out, CMD_F32, exact=True)
    np.testing.assert_array_equal(out, (x0 + x1) * 2)
    # w1 joins round 3; the deferred w0 fold completes it
    c1.zpush(0, key, x1 * 3, CMD_F32, epoch=_epoch(3))
    th.join(timeout=15)
    assert not th.is_alive() and not err, err
    c0.zpull(0, key, out, CMD_F32, exact=True)
    c1.zpull(0, key, out, CMD_F32, exact=True)
    np.testing.assert_array_equal(out, (x0 + x1) * 3)

    stats = c0.server_stats(0)
    assert stats["window_deferred"] >= 1, stats
    assert stats.get("window_rejected", 0) == 0, stats

    c0.close()
    c1.close()
    t.join(timeout=10)


@pytest.mark.chaos
def test_beyond_window_rejected_loudly_aggregate_untouched():
    """A stamped fold BEYOND window W error-replies with a round_skew
    flight event and the open round's aggregate is untouched — skew
    past the staleness bound stays a loud, attributable failure, never
    a silent mis-sum (the invariant the window generalizes, not
    weakens)."""
    port, t = _windowed_server(num_workers=2)
    addr = [f"127.0.0.1:{port}"]
    c0 = PSClient(addr, worker_id=0)
    c1 = PSClient(addr, worker_id=1)
    n = 256
    key = 6
    x0 = np.arange(n, dtype=np.float32)
    x1 = np.full(n, 5.0, np.float32)
    _init_key(c0, c1, key, n)

    # w0 opens round 2; its round-4 push is TWO ahead — beyond W=1
    c0.zpush(0, key, x0 * 2, CMD_F32, epoch=_epoch(2))
    with pytest.raises(RuntimeError):
        c0.zpush(0, key, x0 * 4, CMD_F32, epoch=_epoch(4))
    evs = c1.drain_flight(0)
    assert any(e["kind"] == "round_skew" for e in evs), evs
    stats = c0.server_stats(0)
    assert stats["window_rejected"] >= 1, stats

    # the open round still completes with its true sum
    c1.zpush(0, key, x1 * 2, CMD_F32, epoch=_epoch(2))
    out = np.empty(n, np.float32)
    c0.zpull(0, key, out, CMD_F32, exact=True)
    np.testing.assert_array_equal(out, (x0 + x1) * 2)

    c0.close()
    c1.close()
    t.join(timeout=10)


@pytest.mark.chaos
def test_window_bookkeeping_deterministic_across_stacks():
    """Two independent server instances fed the identical skewed
    sequence produce bitwise-identical aggregates AND identical window
    bookkeeping (deferred/rejected counts) — the window state machine
    is a pure function of the fold sequence, with no timing or
    allocation dependence."""
    results = []
    for _ in range(2):
        port, t = _windowed_server(num_workers=2)
        addr = [f"127.0.0.1:{port}"]
        c0 = PSClient(addr, worker_id=0)
        c1 = PSClient(addr, worker_id=1)
        n = 128
        key = 7
        x0 = np.arange(n, dtype=np.float32)
        x1 = np.full(n, 3.0, np.float32)
        _init_key(c0, c1, key, n)
        c0.zpush(0, key, x0, CMD_F32, epoch=_epoch(1))
        c1.zpush(0, key, x1, CMD_F32, epoch=_epoch(1))
        out = np.empty(n, np.float32)
        c0.zpull(0, key, out, CMD_F32, exact=True)
        # deferred ahead-fold, then an out-of-window reject, then the
        # open round completes and the deferred round follows
        c0.zpush(0, key, x0 * 2, CMD_F32, epoch=_epoch(2))
        th = threading.Thread(
            target=c0.zpush,
            args=(0, key, x0 * 3, CMD_F32),
            kwargs={"epoch": _epoch(3)}, daemon=True)
        th.start()
        time.sleep(0.3)
        with pytest.raises(RuntimeError):
            c0.zpush(0, key, x0 * 9, CMD_F32, epoch=_epoch(9))
        c1.zpush(0, key, x1 * 2, CMD_F32, epoch=_epoch(2))
        r2 = np.empty(n, np.float32)
        c1.zpull(0, key, r2, CMD_F32, exact=True)  # w0 is a round ahead
        c1.zpush(0, key, x1 * 3, CMD_F32, epoch=_epoch(3))
        th.join(timeout=15)
        assert not th.is_alive()
        r3 = np.empty(n, np.float32)
        c0.zpull(0, key, r3, CMD_F32, exact=True)
        c1.zpull(0, key, r3, CMD_F32, exact=True)
        stats = c0.server_stats(0)
        results.append((r2.tobytes(), r3.tobytes(),
                        stats["window_deferred"],
                        stats["window_rejected"]))
        c0.close()
        c1.close()
        t.join(timeout=10)
    assert results[0] == results[1]
    np.testing.assert_array_equal(
        np.frombuffer(results[0][1], np.float32),
        np.arange(128, dtype=np.float32) * 3 + 9.0)


@pytest.mark.chaos
def test_sigkill_mid_window_recovers_bitwise_via_replay():
    """SIGKILL the server while a deferred fold is parked mid-window:
    both workers re-home the key to a fresh (also windowed) server and
    replay their rounds with bumped attempts — every round's aggregate
    is bitwise the true sum, exactly the PR 6 replay-epoch contract
    extended across the open window."""
    import subprocess
    import sys

    from byteps_tpu.utils.net import free_port, wait_port

    port_a = free_port()
    code = (f"from byteps_tpu.server import run_server; "
            f"from byteps_tpu.config import Config; "
            f"run_server({port_a}, Config(num_workers=2, num_servers=2))")
    env = {**os.environ,
           "PYTHONPATH": REPO + os.pathsep + os.environ.get(
               "PYTHONPATH", ""),
           "BYTEPS_CROSS_BARRIER": "1", "BYTEPS_STALENESS": "1"}
    proc = subprocess.Popen([sys.executable, "-c", code], env=env)
    port_b, tb = _windowed_server(num_workers=2)
    wait_port(port_a, 60)
    addrs = [f"127.0.0.1:{port_a}", f"127.0.0.1:{port_b}"]
    c0 = PSClient(addrs, worker_id=0)
    c1 = PSClient(addrs, worker_id=1)
    n = 256
    key = 8
    x0 = np.arange(n, dtype=np.float32)
    x1 = np.full(n, 4.0, np.float32)
    try:
        _init_key(c0, c1, key, n, server=0)
        c0.zpush(0, key, x0, CMD_F32, epoch=_epoch(1))
        c1.zpush(0, key, x1, CMD_F32, epoch=_epoch(1))
        out = np.empty(n, np.float32)
        c0.zpull(0, key, out, CMD_F32, exact=True)
        c1.zpull(0, key, out, CMD_F32, exact=True)

        # open round 2 (w0 folded) and park w0's round-3 fold in the
        # window... then the server dies with the window populated
        c0.zpush(0, key, x0 * 2, CMD_F32, epoch=_epoch(2))
        th = threading.Thread(
            target=_push_quiet, args=(c0, 0, key, x0 * 3, _epoch(3)),
            daemon=True)
        th.start()
        time.sleep(0.3)
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=10)
        time.sleep(0.3)
        assert c0.server_dead(0) and c1.server_dead(0)
        th.join(timeout=15)

        # re-home to the survivor and replay rounds 2 and 3 with
        # bumped attempts — the fresh windowed store folds each round
        # exactly once
        _init_key(c0, c1, key, n, server=1)
        c0.zpush(1, key, x0 * 2, CMD_F32, epoch=_epoch(2, attempt=1))
        c1.zpush(1, key, x1 * 2, CMD_F32, epoch=_epoch(2))
        c0.zpull(1, key, out, CMD_F32, exact=True)
        np.testing.assert_array_equal(out, (x0 + x1) * 2)
        c0.zpush(1, key, x0 * 3, CMD_F32, epoch=_epoch(3, attempt=1))
        c1.zpush(1, key, x1 * 3, CMD_F32, epoch=_epoch(3))
        c0.zpull(1, key, out, CMD_F32, exact=True)
        c1.zpull(1, key, out, CMD_F32, exact=True)
        np.testing.assert_array_equal(out, (x0 + x1) * 3)
        # a replay of a folded round is deduped, never re-folded
        c0.zpush(1, key, x0 * 3, CMD_F32, epoch=_epoch(3, attempt=2))
        c0.zpull(1, key, out, CMD_F32, exact=True)
        np.testing.assert_array_equal(out, (x0 + x1) * 3)
    finally:
        c0.close()
        c1.close()
        if proc.poll() is None:
            proc.kill()
        proc.wait(timeout=10)
        tb.join(timeout=10)


def _push_quiet(client, server, key, arr, epoch):
    try:
        client.zpush(server, key, arr, CMD_F32, epoch=epoch)
    except Exception:  # noqa: BLE001 - server death races the reply
        pass


# --------------------------------------------------------------------- #
# JAX train-step contracts: staleness-0 bitwise, staleness-1 engaged
# --------------------------------------------------------------------- #


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


def _setup(hidden=(48, 32)):
    import jax
    import jax.numpy as jnp

    from byteps_tpu.models import mlp

    cfg = mlp.MLPConfig(in_dim=64, hidden=hidden, n_classes=10)
    params = mlp.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)
    batch = {"x": jnp.asarray(rng.rand(32, 64), jnp.float32),
             "y": jnp.asarray(rng.randint(0, 10, 32), jnp.int32)}
    return cfg, params, batch


def _run_steps(params, batch, cfg, steps=4, flush=False, **kw):
    import jax
    import jax.numpy as jnp

    from byteps_tpu.core.state import get_state
    from byteps_tpu.jax.train import make_ps_train_step
    from byteps_tpu.models import mlp

    params = jax.tree.map(jnp.array, params)  # private copy (donation)
    tx = optax.adam(1e-2)
    opt = tx.init(params)
    step = make_ps_train_step(lambda p, b: mlp.loss_fn(p, b, cfg), tx,
                              get_state().mesh, **kw)
    losses = []
    for _ in range(steps):
        params, opt, loss = step(params, opt, batch)
        losses.append(float(loss))
    if flush:
        params, opt = step.flush(params, opt)
    return ([np.asarray(x) for x in jax.tree.leaves(params)], losses)


# the pinned staleness-0 parity matrix: dense (every leaf its own key),
# fused-bucket (biases ride the bucket), host-compressed, fused apply
# (sharded_apply off — the no-sa arm the carry gate must not disturb)
@pytest.mark.parametrize("fusion,kw", [
    ("0", {}),
    ("4096", {}),
    ("0", dict(compression={"compressor": "onebit", "ef": "vanilla"},
               min_compress_bytes=0, device_compress=False)),
    ("0", dict(sharded_apply=False)),
], ids=["dense", "fused-bucket", "onebit", "fused-apply"])
def test_staleness0_bitwise_identical(fusion, kw):
    """BYTEPS_CROSS_BARRIER with staleness 0 is the synchronous path
    BITWISE: the scheduler window is 0, the carry gate never arms, and
    every transport drains exactly as before."""
    cfg, params, batch = _setup()
    with _ps_env({"BYTEPS_FUSION_BYTES": fusion}):
        base, _ = _run_steps(params, batch, cfg)
    with _ps_env({"BYTEPS_FUSION_BYTES": fusion,
                  "BYTEPS_CROSS_BARRIER": "1",
                  "BYTEPS_STALENESS": "0"}):
        xb, _ = _run_steps(params, batch, cfg)
    for a, b in zip(base, xb):
        np.testing.assert_array_equal(a, b)


def test_staleness1_carry_engages_and_converges():
    """At staleness 1 the carry actually engages (carried-leaf counter
    nonzero), training stays
    finite and converges, and ``flush`` folds the outstanding tail so
    the final trees are complete."""
    from byteps_tpu.core.state import get_state

    cfg, params, batch = _setup(hidden=(256, 256, 256))
    # slow the server so the tail of the drain is genuinely pending
    # when the front-of-model leaves land — on an unthrottled loopback
    # every reply can already be in the ready queue at release time and
    # the carry (correctly) has nothing to do. Shard export off: shard
    # subranges keep the synchronous drain by design, and this test
    # needs whole-leaf tail keys for the carry to have something to
    # take.
    with _ps_env({"BYTEPS_FUSION_BYTES": "256",
                  "BYTEPS_CROSS_BARRIER": "1",
                  "BYTEPS_STALENESS": "1",
                  "BYTEPS_LOCAL_SHARD_EXPORT": "0",
                  "BYTEPS_CHAOS_SLOW_SERVER": "10",
                  # bandwidth throttle: serving time scales with bytes,
                  # so the big carry-half weights lag the tiny biases
                  "BYTEPS_SERVER_THROTTLE_MBPS": "100"}):
        state = get_state()
        assert getattr(state.scheduler, "xb_window", 0) == 1
        leaves, losses = _run_steps(params, batch, cfg, steps=12,
                                    flush=True)
        carried = state.metrics.counter("barrier/carried_leaves").value
        drained = state.metrics.counter("barrier/carry_drained").value
    assert carried > 0, "cross-barrier carry never engaged"
    # every carried round is eventually drained (in-step or by flush)
    assert drained <= carried
    for leaf in leaves:
        assert np.isfinite(leaf).all()
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_staleness1_flush_is_idempotent():
    """flush() after flush() (and on a run that carried nothing) is the
    identity — callers can flush at every checkpoint cut safely."""
    import jax

    from byteps_tpu.core.state import get_state
    from byteps_tpu.jax.train import make_ps_train_step
    from byteps_tpu.models import mlp

    cfg, params, batch = _setup(hidden=(256, 256))
    with _ps_env({"BYTEPS_FUSION_BYTES": "256",
                  "BYTEPS_CROSS_BARRIER": "1",
                  "BYTEPS_STALENESS": "1"}):
        tx = optax.adam(1e-2)
        import jax.numpy as jnp
        params = jax.tree.map(jnp.array, params)
        opt = tx.init(params)
        step = make_ps_train_step(
            lambda p, b: mlp.loss_fn(p, b, cfg), tx, get_state().mesh)
        for _ in range(4):
            params, opt, _ = step(params, opt, batch)
        params, opt = step.flush(params, opt)
        p2, o2 = step.flush(params, opt)
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------------- #
# convergence parity: the llama dryrun at staleness 1, health green
# --------------------------------------------------------------------- #

_PIN = ("from byteps_tpu.utils.jax_compat import force_cpu; force_cpu(8); "
        "import runpy, sys; sys.argv = sys.argv[1:]; "
        "runpy.run_path(sys.argv[0], run_name='__main__')")


@pytest.mark.slow
def test_llama_dryrun_staleness1_health_assert_green():
    """The ISSUE's convergence-parity acceptance arm: the llama
    pretrain dryrun trained THROUGH the cross-barrier window at
    staleness 1 (worker AND server armed — the server reads the env
    per instance) finishes with ``--health-assert`` green: no
    divergence sentinel, no nonfinite leaf, no round_skew flight event
    anywhere in the run."""
    import socket
    import subprocess
    import sys

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = {**os.environ,
           "PYTHONPATH": REPO + os.pathsep
           + os.environ.get("PYTHONPATH", ""),
           "DMLC_NUM_WORKER": "1", "DMLC_NUM_SERVER": "1",
           "DMLC_PS_ROOT_URI": "127.0.0.1",
           "DMLC_PS_ROOT_PORT": str(port),
           "BYTEPS_FORCE_DISTRIBUTED": "1",
           "BYTEPS_CROSS_BARRIER": "1",
           "BYTEPS_STALENESS": "1"}
    srv = subprocess.Popen(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, %r); "
         "from byteps_tpu.config import Config; "
         "from byteps_tpu.server import run_server; "
         "run_server(%d, Config(num_workers=1, num_servers=1))"
         % (REPO, port)],
        cwd=REPO, env=env)
    try:
        r = subprocess.run(
            [sys.executable, "-c", _PIN,
             os.path.join(REPO, "examples", "llama_pretrain.py"),
             "--size", "tiny", "--steps", "4", "--batch", "4", "--ps",
             "--health-assert"],
            cwd=REPO, capture_output=True, text=True, timeout=420,
            env=env)
        assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
        assert "health assert: no anomaly events" in r.stdout
        srv.wait(timeout=30)  # worker shutdown stops the server
    finally:
        if srv.poll() is None:
            srv.kill()
