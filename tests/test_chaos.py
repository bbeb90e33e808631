"""Fault-tolerant elastic PS fleet (docs/fault-tolerance.md): bounded
wire retry with exponential backoff, (round, attempt)-epoch idempotent
replay, live key migration off a dead server, and the BYTEPS_CHAOS_*
fault-injection knobs.

The protocol-level pieces (replay dedup, registry migration, the retry
engine) test in-process; anything that depends on BYTEPS_CLIENT_TIMEOUT_S
runs in a SUBPROCESS (the native timeout is latched per process at first
use, so an in-process test would inherit whatever an earlier test
latched); the churn test SIGKILLs a real server subprocess mid-training.
"""

import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from byteps_tpu.config import Config
from byteps_tpu.core.registry import TensorRegistry
from byteps_tpu.core.types import DataType, RequestType, get_command_type
from byteps_tpu.server import run_server
from byteps_tpu.server.client import PSClient

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PORT = [27300]

CMD_F32 = get_command_type(RequestType.DEFAULT_PUSH_PULL, DataType.FLOAT32)


def _epoch(round_no: int, attempt: int = 0) -> int:
    return (round_no << 16) | attempt


def _server_thread(num_workers=1):
    port = _PORT[0]
    _PORT[0] += 1
    t = threading.Thread(
        target=run_server,
        args=(port, Config(num_workers=num_workers, num_servers=1)),
        daemon=True)
    t.start()
    return port, t


def _spawn_server_proc(port, num_workers=1, num_servers=1, extra_env=None):
    """A REAL server process (SIGKILL-able, chaos-knob-able)."""
    code = (f"from byteps_tpu.server import run_server; "
            f"from byteps_tpu.config import Config; "
            f"run_server({port}, Config(num_workers={num_workers}, "
            f"num_servers={num_servers}))")
    env = {**os.environ,
           "PYTHONPATH": REPO + os.pathsep + os.environ.get(
               "PYTHONPATH", ""),
           **(extra_env or {})}
    return subprocess.Popen([sys.executable, "-c", code], env=env)


def _wait_ports(ports, timeout=60):
    """Block until every port accepts connections: the server processes
    pay a cold jax import before they bind, which can outlast the native
    client's own 10s connect-retry window."""
    from byteps_tpu.utils.net import wait_port

    deadline = time.monotonic() + timeout
    for port in ports:
        wait_port(port, max(1.0, deadline - time.monotonic()))


# --------------------------------------------------------------------- #
# idempotent replay: the (round, attempt) epoch dedup
# --------------------------------------------------------------------- #


@pytest.mark.chaos
def test_replayed_push_never_double_counts():
    """THE double-count scenario the epoch stamp exists for: worker 0's
    round-1 push is replayed (its reply was lost); without dedup the
    duplicate would be folded as worker 1's contribution and the round
    would publish 2*w0 — with it, the aggregate is exactly w0 + w1."""
    port, t = _server_thread(num_workers=2)
    addr = [f"127.0.0.1:{port}"]
    c0 = PSClient(addr, worker_id=0)
    c1 = PSClient(addr, worker_id=1)
    n = 512
    x0 = np.arange(n, dtype=np.float32)
    x1 = np.full(n, 10.0, np.float32)
    key = 3

    th = threading.Thread(
        target=c0.init_key, args=(0, key, np.zeros(n, np.float32), CMD_F32),
        daemon=True)
    th.start()
    c1.init_key(0, key, np.zeros(n, np.float32), CMD_F32)  # init barrier
    th.join(timeout=15)
    assert not th.is_alive()

    c0.zpush(0, key, x0, CMD_F32, epoch=_epoch(1))
    c0.zpush(0, key, x0, CMD_F32, epoch=_epoch(1, attempt=1))  # replay
    time.sleep(0.3)  # both w0 pushes are folded (or deduped) server-side
    c1.zpush(0, key, x1, CMD_F32, epoch=_epoch(1))

    out0 = np.empty(n, np.float32)
    out1 = np.empty(n, np.float32)
    c0.zpull(0, key, out0, CMD_F32, exact=True)
    c1.zpull(0, key, out1, CMD_F32, exact=True)
    np.testing.assert_array_equal(out0, x0 + x1)  # NOT 2*x0 (no w1 fold)
    np.testing.assert_array_equal(out1, x0 + x1)

    # a NEW round folds normally (dedup compares rounds, not presence)
    c0.zpush(0, key, x0 * 2, CMD_F32, epoch=_epoch(2))
    c1.zpush(0, key, x1 * 2, CMD_F32, epoch=_epoch(2))
    c0.zpull(0, key, out0, CMD_F32, exact=True)
    np.testing.assert_array_equal(out0, 2 * (x0 + x1))

    # BOTH workers SHUTDOWN: a 2-worker server counts shutdowns against
    # num_workers — one would leave a live server thread leaked into
    # the rest of the suite (and a 10s join timeout here)
    c0.close()
    c1.close()
    t.join(timeout=10)


@pytest.mark.chaos
def test_unstamped_push_keeps_legacy_semantics():
    """epoch=0 (legacy callers / blocking client) must keep positional
    counting: for one worker each unstamped push is its own round."""
    port, t = _server_thread(num_workers=1)
    c = PSClient([f"127.0.0.1:{port}"], worker_id=0)
    n = 64
    x = np.ones(n, np.float32)
    c.init_key(0, 5, np.zeros(n, np.float32), CMD_F32)
    c.zpush(0, 5, x, CMD_F32)          # round 1 (unstamped)
    c.zpush(0, 5, x * 3, CMD_F32)      # round 2 (unstamped)
    out = np.empty(n, np.float32)
    c.zpull(0, 5, out, CMD_F32, exact=True)
    np.testing.assert_array_equal(out, x * 3)  # latest round's aggregate
    c.close()
    t.join(timeout=10)


# --------------------------------------------------------------------- #
# registry: live key migration
# --------------------------------------------------------------------- #


def _registry(num_servers, partition_bytes=4096):
    return TensorRegistry(Config(num_workers=1, num_servers=num_servers,
                                 partition_bytes=partition_bytes))


def test_migrate_server_retargets_and_rebalances():
    reg = _registry(3)
    for i in range(6):
        reg.init_tensor(f"m{i}", 3 * 4096, DataType.FLOAT32)  # 3 parts
    before = reg.server_loads()
    assert sum(before) == 6 * 3 * 4096
    v0 = reg.routing_version
    migrated = reg.migrate_server(1)
    assert migrated, "server 1 owned nothing — partitioning changed?"
    assert reg.routing_version == v0 + 1
    assert reg.dead_servers() == [1]
    loads = reg.server_loads()
    assert loads[1] == 0
    assert sum(loads) == sum(before)  # bytes conserved, just re-homed
    for ctx in reg.contexts_in_order():
        for p in ctx.partitions:
            assert p.server != 1
    # NEW declarations avoid the dead server too
    ctx = reg.init_tensor("post_death", 8 * 4096, DataType.FLOAT32)
    assert all(p.server != 1 for p in ctx.partitions)
    # idempotent: a second migrate of the same server moves nothing
    assert reg.migrate_server(1) == []


def test_migrate_server_is_deterministic_across_workers():
    """Two independent registries with the same declaration history must
    migrate every key to the same survivor — workers observe a death
    independently and may never diverge on routing."""
    regs = [_registry(4) for _ in range(2)]
    for reg in regs:
        for i in range(5):
            reg.init_tensor(f"d{i}", 2 * 4096, DataType.FLOAT32)
    for reg in regs:
        reg.migrate_server(2)
    tables = []
    for reg in regs:
        tables.append([(p.key, p.server)
                       for ctx in reg.contexts_in_order()
                       for p in ctx.partitions])
    assert tables[0] == tables[1]


def test_migrate_last_survivor_raises():
    reg = _registry(2)
    reg.init_tensor("x", 4096, DataType.FLOAT32)
    reg.migrate_server(0)
    with pytest.raises(RuntimeError, match="no surviving server"):
        reg.migrate_server(1)


# --------------------------------------------------------------------- #
# scheduler retry engine (fake client: deterministic, no network)
# --------------------------------------------------------------------- #


class _FlakyClient:
    """supports_fused client whose wire fails the first ``fail_n`` sends
    (send-time exception), then succeeds by echoing the payload."""

    supports_fused = True

    def __init__(self, fail_n):
        self.fail_n = fail_n
        self.calls = 0

    def ensure_init(self, ctx, nbytes):
        pass

    def zpushpull_async(self, server, key, data, out, cmd, on_done,
                        epoch=0):
        self.calls += 1
        if self.calls <= self.fail_n:
            raise RuntimeError("injected wire failure")
        out[:] = np.asarray(data).view(np.uint8)
        on_done(len(out), None)


def _mk_ctx(name="t", nbytes=256):
    reg = _registry(1, partition_bytes=1 << 20)
    return reg.init_tensor(name, nbytes, DataType.FLOAT32)


def test_scheduler_retries_then_succeeds(monkeypatch):
    from byteps_tpu.core.scheduler import Handle, PipelineScheduler

    monkeypatch.setenv("BYTEPS_WIRE_RETRY", "3")
    monkeypatch.setenv("BYTEPS_WIRE_BACKOFF_MS", "5")
    client = _FlakyClient(fail_n=2)
    sched = PipelineScheduler(client)
    try:
        ctx = _mk_ctx()
        x = np.arange(64, dtype=np.float32)
        h = Handle(0, "t")
        sched.submit(ctx, x, h, average=False, num_workers=1)
        out = h.wait(timeout=20)
        np.testing.assert_array_equal(out, x)
        assert client.calls == 3  # 2 failures + 1 success
    finally:
        sched.stop()


def test_scheduler_retry_budget_fails_fast_with_clear_error(monkeypatch):
    from byteps_tpu.core.scheduler import Handle, PipelineScheduler

    monkeypatch.setenv("BYTEPS_WIRE_RETRY", "2")
    monkeypatch.setenv("BYTEPS_WIRE_BACKOFF_MS", "5")
    client = _FlakyClient(fail_n=10**9)  # permanently failing wire
    sched = PipelineScheduler(client)
    try:
        ctx = _mk_ctx("dead")
        h = Handle(0, "dead")
        t0 = time.monotonic()
        sched.submit(ctx, np.ones(64, np.float32), h, average=False,
                     num_workers=1)
        with pytest.raises(RuntimeError, match="after 3 attempts"):
            h.wait(timeout=30)
        assert time.monotonic() - t0 < 10, "retry budget not bounded"
        assert client.calls == 3
    finally:
        sched.stop()


def test_scheduler_programming_errors_do_not_retry(monkeypatch):
    from byteps_tpu.core.scheduler import Handle, PipelineScheduler

    monkeypatch.setenv("BYTEPS_WIRE_RETRY", "5")

    class _BadClient(_FlakyClient):
        def zpushpull_async(self, *a, **kw):
            self.calls += 1
            raise ValueError("caller bug")

    client = _BadClient(fail_n=0)
    sched = PipelineScheduler(client)
    try:
        ctx = _mk_ctx("bug")
        h = Handle(0, "bug")
        sched.submit(ctx, np.ones(8, np.float32), h, average=False,
                     num_workers=1)
        with pytest.raises(ValueError, match="caller bug"):
            h.wait(timeout=20)
        assert client.calls == 1  # no retry burned on a ValueError
    finally:
        sched.stop()


# --------------------------------------------------------------------- #
# chaos drop-reply idempotence (subprocess: the native client timeout is
# latched per process, and the drop knob is read per server instance)
# --------------------------------------------------------------------- #

_DROP_SCRIPT = r"""
import os, sys, threading
sys.path.insert(0, os.environ["BPS_REPO"])
import numpy as np
from byteps_tpu.config import Config
from byteps_tpu.core.state import GlobalState
from byteps_tpu.server import run_server
from byteps_tpu.utils.net import free_port

port = free_port()
os.environ.update({
    "DMLC_NUM_WORKER": "1", "DMLC_NUM_SERVER": "1",
    "DMLC_PS_ROOT_URI": "127.0.0.1", "DMLC_PS_ROOT_PORT": str(port),
    "BYTEPS_FORCE_DISTRIBUTED": "1",
})
# the server instance reads the drop knob at construction
server = threading.Thread(
    target=run_server, args=(port, Config(num_workers=1, num_servers=1)),
    daemon=True)
server.start()
GlobalState._instance = None
import byteps_tpu as bps
bps.init()
rng = np.random.RandomState(3)
grads = [rng.randn(1024).astype(np.float32) for _ in range(4)]
for r in range(4):
    hs = [bps.push_pull_async(g * (r + 1), f"g{i}", average=False)
          for i, g in enumerate(grads)]
    for h, g in zip(hs, grads):
        out = bps.synchronize(h, timeout=60)
        # 1 worker: the aggregate IS the pushed tensor — bitwise, even
        # though replies were dropped and pushes replayed along the way
        assert np.array_equal(out, g * (r + 1)), (r, "double-counted?")
snap = bps.get_metrics()
retries = int(snap["counters"].get("wire/retries", 0))
assert retries > 0, "chaos produced no retries - knob dead?"
assert int(snap["counters"].get("wire/server_failovers", 0)) == 0
bps.shutdown()
server.join(timeout=15)
print("DROP_OK retries=", retries)
"""


@pytest.mark.chaos
def test_dropped_replies_retry_bitwise_identical():
    """Forced reply drops + epoch-stamped retries produce bitwise-exact
    aggregates (the acceptance idempotence proof)."""
    env = {**os.environ,
           "BPS_REPO": REPO,
           "BYTEPS_CLIENT_TIMEOUT_S": "2",
           "BYTEPS_WIRE_RETRY": "5",
           "BYTEPS_WIRE_BACKOFF_MS": "25",
           "BYTEPS_CHAOS_DROP_REPLY_RATE": "0.3",
           "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run([sys.executable, "-c", _DROP_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=240)
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out[-4000:]
    assert "DROP_OK" in out, out[-4000:]
    assert "dedup: replayed push" in out, \
        "no server-side dedup fired - replay path untested?"


# --------------------------------------------------------------------- #
# multi-worker partial-reply window (PR-6 documented limitation, now
# guarded): after a migration, a worker that consumed round N's reply
# pushes N+1 while a worker whose reply was lost re-pushes N — the
# server must never silently sum the two rounds into one aggregate.
# --------------------------------------------------------------------- #


@pytest.mark.chaos
def test_round_skew_rejected_never_missummed():
    """The round-alignment gate (native RoundAligned): a sync-mode
    stamped fold carrying a different round than the one that opened
    the aggregation round is REJECTED with an error reply (and a
    round_skew flight event) — the silent cross-round mis-sum the
    partial-reply window used to produce is now a loud, attributable
    failure."""
    port, t = _server_thread(num_workers=2)
    addr = [f"127.0.0.1:{port}"]
    c0 = PSClient(addr, worker_id=0)
    c1 = PSClient(addr, worker_id=1)
    n = 256
    key = 9
    x0 = np.arange(n, dtype=np.float32)
    x1 = np.full(n, 5.0, np.float32)

    th = threading.Thread(
        target=c0.init_key, args=(0, key, np.zeros(n, np.float32),
                                  CMD_F32), daemon=True)
    th.start()
    c1.init_key(0, key, np.zeros(n, np.float32), CMD_F32)
    th.join(timeout=15)
    assert not th.is_alive()

    # aligned round folds normally
    c0.zpush(0, key, x0, CMD_F32, epoch=_epoch(1))
    c1.zpush(0, key, x1, CMD_F32, epoch=_epoch(1))
    out = np.empty(n, np.float32)
    c0.zpull(0, key, out, CMD_F32, exact=True)
    np.testing.assert_array_equal(out, x0 + x1)

    # the partial-reply-window shape: w1 opens round 2, w0 (which
    # "consumed" round 2 elsewhere) pushes round 3 into the SAME
    # positional round — must be rejected, not summed
    c1.zpush(0, key, x1 * 2, CMD_F32, epoch=_epoch(2))
    with pytest.raises(RuntimeError):
        c0.zpush(0, key, x0 * 2, CMD_F32, epoch=_epoch(3))
    # the guard recorded the skew on the flight plane
    evs = c1.drain_flight(0)
    assert any(e["kind"] == "round_skew" for e in evs), evs
    # w0 re-sending the ALIGNED round still completes it correctly —
    # the gate rejects skew, it never poisons the round
    c0.zpush(0, key, x0 * 2, CMD_F32, epoch=_epoch(2))
    c0.zpull(0, key, out, CMD_F32, exact=True)
    np.testing.assert_array_equal(out, (x0 + x1) * 2)

    c0.close()  # both workers SHUTDOWN: the 2-worker server exits
    c1.close()
    t.join(timeout=10)


@pytest.mark.chaos
def test_benign_window_migration_recovers_bitwise():
    """The DOMINANT window (2-worker subprocess drill, satellite 1):
    the server dies mid-round — neither worker consumed the round —
    and both re-push the SAME round on the adoptive server. The
    replay-epoch machinery covers this case exactly: both folds apply
    once on the fresh store, the aggregate is bitwise the true sum,
    and a later replay of the same round is deduped."""
    from byteps_tpu.utils.net import free_port

    port_a = free_port()
    # victim: a REAL process (SIGKILL-able); survivor: in-process
    proc = _spawn_server_proc(port_a, num_workers=2, num_servers=2)
    port_b, tb = _server_thread(num_workers=2)
    _wait_ports([port_a, port_b])
    addrs = [f"127.0.0.1:{port_a}", f"127.0.0.1:{port_b}"]
    c0 = PSClient(addrs, worker_id=0)
    c1 = PSClient(addrs, worker_id=1)
    n = 512
    key = 4
    x0 = np.arange(n, dtype=np.float32)
    x1 = np.full(n, 3.0, np.float32)
    try:
        th = threading.Thread(
            target=c0.init_key, args=(0, key, np.zeros(n, np.float32),
                                      CMD_F32), daemon=True)
        th.start()
        c1.init_key(0, key, np.zeros(n, np.float32), CMD_F32)
        th.join(timeout=15)
        assert not th.is_alive()

        # round 1 completes on the victim
        c0.zpush(0, key, x0, CMD_F32, epoch=_epoch(1))
        c1.zpush(0, key, x1, CMD_F32, epoch=_epoch(1))
        out = np.empty(n, np.float32)
        c0.zpull(0, key, out, CMD_F32, exact=True)
        c1.zpull(0, key, out, CMD_F32, exact=True)

        # round 2: w0's push folds on the victim... which then dies
        # before the round completes — the benign (mid-round) window
        c0.zpush(0, key, x0 * 2, CMD_F32, epoch=_epoch(2))
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=10)
        time.sleep(0.3)  # EOF propagates to every striped conn
        assert c0.server_dead(0) and c1.server_dead(0)

        # "migration": both workers re-home the key to the survivor
        # (index 1) — fresh store via the init barrier, then BOTH
        # re-push round 2 (w0's retry chain still holds the payload)
        th = threading.Thread(
            target=c0.init_key, args=(1, key, np.zeros(n, np.float32),
                                      CMD_F32), daemon=True)
        th.start()
        c1.init_key(1, key, np.zeros(n, np.float32), CMD_F32)
        th.join(timeout=15)
        assert not th.is_alive()
        c0.zpush(1, key, x0 * 2, CMD_F32, epoch=_epoch(2, attempt=1))
        c1.zpush(1, key, x1 * 2, CMD_F32, epoch=_epoch(2))
        c0.zpull(1, key, out, CMD_F32, exact=True)
        np.testing.assert_array_equal(out, (x0 + x1) * 2)  # TRUE sum
        c1.zpull(1, key, out, CMD_F32, exact=True)
        np.testing.assert_array_equal(out, (x0 + x1) * 2)

        # and a replayed round-2 push on the adoptive server is
        # deduped (answered, never re-folded): round 3 still exact
        c0.zpush(1, key, x0 * 2, CMD_F32, epoch=_epoch(2, attempt=2))
        c0.zpush(1, key, x0 * 3, CMD_F32, epoch=_epoch(3))
        c1.zpush(1, key, x1 * 3, CMD_F32, epoch=_epoch(3))
        c0.zpull(1, key, out, CMD_F32, exact=True)
        np.testing.assert_array_equal(out, (x0 + x1) * 3)
    finally:
        # both workers send SHUTDOWN so the 2-worker survivor exits
        # (the dead victim's shutdown request fails fast on dead conns)
        c0.close()
        c1.close()
        if proc.poll() is None:
            proc.kill()
        proc.wait(timeout=10)
        tb.join(timeout=10)


# --------------------------------------------------------------------- #
# THE churn test: SIGKILL one of two servers mid-training
# --------------------------------------------------------------------- #


@pytest.mark.chaos
def test_server_churn_failover_numerics(tmp_path):
    """Acceptance churn test: with 2 loopback server PROCESSES, SIGKILL
    one mid-run. The run completes without restart, every round's
    aggregate matches the no-churn expectation bitwise (1 worker: the
    aggregate IS the pushed tensor — the migration design re-inits and
    re-pushes on the survivor, so no summation reorders),
    ``wire/server_failovers`` >= 1, and no handles or arena leases
    leak."""
    from byteps_tpu.core.state import GlobalState
    from byteps_tpu.utils.net import free_port

    ports = []
    while len(ports) < 2:
        p = free_port()
        if p not in ports:
            ports.append(p)
    env_keys = {
        "DMLC_NUM_WORKER": "1", "DMLC_NUM_SERVER": "2",
        "DMLC_PS_ROOT_URI": "127.0.0.1",
        "DMLC_PS_ROOT_PORT": str(ports[0]),
        "BYTEPS_SERVER_HOSTS": ",".join(f"127.0.0.1:{p}" for p in ports),
        "BYTEPS_FORCE_DISTRIBUTED": "1",
        "BYTEPS_WIRE_BACKOFF_MS": "25",
    }
    saved = {k: os.environ.get(k) for k in env_keys}
    os.environ.update(env_keys)
    procs = [_spawn_server_proc(p, num_workers=1, num_servers=2)
             for p in ports]
    bps = None
    try:
        _wait_ports(ports)
        GlobalState._instance = None
        import byteps_tpu as bps
        bps.init()
        from byteps_tpu.core.state import get_state
        state = get_state()

        rng = np.random.RandomState(11)
        grads = [rng.randn(2048).astype(np.float32) for _ in range(8)]
        # host-compressed leaf riding the same churn (PR-6 limitation
        # closed: COMP_INIT state used to die with the server and
        # compressed keys failed over with a hard error; the retry path
        # now re-installs the compressor on the adoptive server).
        # lossless tier: failover numerics stay BITWISE comparable.
        from byteps_tpu.server.compressed import CompressedRegistry
        comp_reg = CompressedRegistry(state.ps_client, 1,
                                      {"compressor": "lossless"})
        cgrad = rng.randn(4096).astype(np.float32)

        def run_round(r):
            hs = [bps.push_pull_async(g * (r + 1), f"churn{i}",
                                      average=False)
                  for i, g in enumerate(grads)]
            ch = comp_reg.push_pull_async(state, "churn_comp",
                                          cgrad * (r + 1), average=False)
            out = [np.array(bps.synchronize(h, timeout=120)) for h in hs]
            cout = np.array(bps.synchronize(ch, timeout=120))
            return out, cout

        # warm rounds: declare keys, init barrier, steady state
        for r in range(2):
            res, cres = run_round(r)
            for g, o in zip(grads, res):
                np.testing.assert_array_equal(o, g * (r + 1))
            np.testing.assert_array_equal(cres, cgrad * (r + 1))

        # pick a victim that actually owns keys, and confirm BOTH
        # servers hold some (otherwise the kill proves nothing)
        owners = {p.server
                  for ctx in state.registry.contexts_in_order()
                  for p in ctx.partitions}
        assert owners == {0, 1}, f"keys not spread: {owners}"
        victim = 1

        # mid-round kill: submit first (compressed leaf included),
        # SIGKILL while in flight
        hs = [bps.push_pull_async(g * 3.0, f"churn{i}", average=False)
              for i, g in enumerate(grads)]
        ch = comp_reg.push_pull_async(state, "churn_comp", cgrad * 3.0,
                                      average=False)
        os.kill(procs[victim].pid, signal.SIGKILL)
        procs[victim].wait(timeout=10)
        for g, h in zip(grads, hs):
            np.testing.assert_array_equal(
                np.array(bps.synchronize(h, timeout=120)), g * 3.0)
        # the compressed leaf survives the death like the dense ones:
        # its retry re-init-pushes AND re-COMP_INITs on the survivor
        np.testing.assert_array_equal(
            np.array(bps.synchronize(ch, timeout=120)), cgrad * 3.0)

        # training continues: later rounds all route to the survivor
        for r in range(3, 5):
            res, cres = run_round(r)
            for g, o in zip(grads, res):
                np.testing.assert_array_equal(o, g * (r + 1))
            np.testing.assert_array_equal(cres, cgrad * (r + 1))

        snap = bps.get_metrics()
        assert snap["counters"]["wire/server_failovers"] >= 1
        assert snap["counters"]["registry/migrations"] >= 1
        assert snap["counters"]["wire/retries"] >= 1
        assert state.registry.dead_servers() == [victim]
        for ctx in state.registry.contexts_in_order():
            for p in ctx.partitions:
                assert p.server != victim

        # flight recorder captured the failover CAUSALLY (PR 12): the
        # worker ring holds retry -> failover -> per-key migration
        # events in timestamp order, key-matched to the routing table
        from byteps_tpu.core import flight as flight_mod
        evs = flight_mod.get_recorder().events()
        kinds = [e["kind"] for e in evs]
        assert "wire_retry" in kinds, kinds
        assert "server_failover" in kinds, kinds
        assert "key_migration" in kinds, kinds
        ts = [e["ts_ns"] for e in evs]
        assert ts == sorted(ts), "flight events out of causal order"
        fo = next(e for e in evs if e["kind"] == "server_failover")
        first_retry = next(e["ts_ns"] for e in evs
                           if e["kind"] == "wire_retry")
        assert fo["ts_ns"] >= first_retry, \
            "failover recorded before the retry that triggered it"
        assert fo["key"] == victim  # failover names the dead server
        migrated_keys = {e["key"] for e in evs
                         if e["kind"] == "key_migration"}
        assert migrated_keys, "no per-key migration events"
        live_keys = {p.key for ctx in state.registry.contexts_in_order()
                     for p in ctx.partitions}
        assert migrated_keys <= live_keys, \
            "migration events name keys the registry does not know"
        # and the merged dump (worker + surviving server) is written,
        # valid JSON, and stays causally ordered after clock alignment
        import json as _json
        dump_path = bps.dump_flight_record(
            str(tmp_path / "churn-flight.json"))
        assert dump_path and os.path.exists(dump_path)
        with open(dump_path) as f:
            doc = _json.load(f)
        merged_ts = [e["ts_ns"] for e in doc["merged"]]
        assert merged_ts == sorted(merged_ts)
        assert any(e["kind"] == "server_failover" for e in doc["merged"])

        # zero leaks: handles cleared, no busy arena slots (poll
        # briefly — the completion-ordered drain releases leases at the
        # next checkout boundary)
        deadline = time.monotonic() + 10
        busy = handles = None
        while time.monotonic() < deadline:
            with state.arena._mu:
                busy = [k for k, s in state.arena._slots.items()
                        if s.busy]
            handles = dict(state.handles._handles)
            if not busy and not handles:
                break
            time.sleep(0.1)
        assert not busy, f"leaked arena leases: {busy[:8]}"
        assert not handles, f"leaked handles: {list(handles)[:8]}"
    finally:
        try:
            if bps is not None:
                bps.shutdown()
        except Exception:
            pass
        GlobalState._instance = None
        for p in procs:
            if p.poll() is None:
                p.kill()
            p.wait(timeout=10)
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


@pytest.mark.chaos
def test_dead_fleet_fails_fast(tmp_path):
    """Permanently-dead fleet: every server gone -> a submit fails with
    a clear bounded error well inside the retry x backoff budget — no
    hang (the fail-fast guard riding alongside
    test_failure_detection.py's worker-death semantics). The error
    additionally POINTS AT the flight-record dump (PR 12): the operator
    starts from the causal timeline, not log archaeology."""
    from byteps_tpu.core.state import GlobalState
    from byteps_tpu.utils.net import free_port

    port = free_port()
    env_keys = {
        "DMLC_NUM_WORKER": "1", "DMLC_NUM_SERVER": "1",
        "DMLC_PS_ROOT_URI": "127.0.0.1", "DMLC_PS_ROOT_PORT": str(port),
        "BYTEPS_FORCE_DISTRIBUTED": "1",
        "BYTEPS_WIRE_RETRY": "2", "BYTEPS_WIRE_BACKOFF_MS": "25",
        "BYTEPS_FLIGHT_DIR": str(tmp_path / "flight"),
    }
    saved = {k: os.environ.get(k) for k in env_keys}
    os.environ.update(env_keys)
    proc = _spawn_server_proc(port, num_workers=1, num_servers=1)
    bps = None
    try:
        _wait_ports([port])
        GlobalState._instance = None
        import byteps_tpu as bps
        bps.init()
        x = np.ones(512, np.float32)
        out = bps.synchronize(bps.push_pull_async(x, "ff", average=False),
                              timeout=60)
        np.testing.assert_array_equal(out, x)

        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=10)
        time.sleep(0.3)  # EOF propagates to every striped conn

        t0 = time.monotonic()
        h = bps.push_pull_async(x * 2, "ff", average=False)
        with pytest.raises((RuntimeError, TimeoutError)) as ei:
            bps.synchronize(h, timeout=60)
        elapsed = time.monotonic() - t0
        assert elapsed < 30, f"dead fleet took {elapsed:.1f}s to fail"
        msg = str(ei.value)
        assert ("attempts" in msg or "fleet is gone" in msg
                or "dead" in msg), msg
        # the fail-fast error names the flight dump, and the dump holds
        # the retry trail that led to the verdict
        assert "flight record dumped to" in msg, msg
        dump_path = msg.rsplit("flight record dumped to ", 1)[1].strip()
        assert os.path.exists(dump_path), dump_path
        import json as _json
        with open(dump_path) as f:
            doc = _json.load(f)
        kinds = [e["kind"] for e in doc["worker"]["events"]]
        assert "wire_retry" in kinds, kinds
        assert "round_failed" in kinds, kinds
    finally:
        try:
            if bps is not None:
                bps.shutdown()
        except Exception:
            pass
        GlobalState._instance = None
        if proc.poll() is None:
            proc.kill()
        proc.wait(timeout=10)
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
