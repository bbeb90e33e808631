"""Elastic fleet, part 2 (docs/fault-tolerance.md "Elasticity"):
runtime server scale-up join, graceful drain, gray-failure eviction,
and the sensor-driven autoscaler loop.

Protocol-level pieces (rebalance plans, the autoscaler controller) test
pure and in-process; the join/drain/eviction drills run against real
in-process native servers (the chaos knobs are read per Server
instance, so a slow straggler and a healthy peer coexist in one test
process). The heavier partial-reply-window subprocess drill lives in
test_chaos.py next to the other churn tests.
"""

import os
import threading
import time

import numpy as np
import pytest

from byteps_tpu.config import Config
from byteps_tpu.core.autoscaler import (
    AutoscaleController, AutoscalerPlane, Decision, FleetSample,
)
from byteps_tpu.core.registry import TensorRegistry
from byteps_tpu.core.types import DataType

_PORT = [28300]


def _registry(num_servers, partition_bytes=4096):
    return TensorRegistry(Config(num_workers=1, num_servers=num_servers,
                                 partition_bytes=partition_bytes))


# --------------------------------------------------------------------- #
# registry: the version-fenced rebalance plan engine
# --------------------------------------------------------------------- #


def test_plan_join_moves_fair_share_to_newcomer():
    reg = _registry(2)
    for i in range(8):
        reg.init_tensor(f"j{i}", 3 * 4096, DataType.FLOAT32)
    total = sum(reg.server_loads())
    new = reg.add_server()
    assert new == 2
    plan = reg.plan_join(new)
    assert plan.kind == "join" and plan.server == 2
    v0 = reg.routing_version
    moved = reg.rebalance(plan)
    assert moved == plan.keys()
    assert reg.routing_version == v0 + 1
    loads = reg.server_loads()
    assert sum(loads) == total  # bytes conserved, just re-homed
    # the newcomer holds roughly its fair share (within one partition)
    assert loads[2] >= total // 3 - 3 * 4096
    assert loads[2] > 0
    # moved partitions actually point at the newcomer
    moved_set = set(moved)
    for ctx in reg.contexts_in_order():
        for p in ctx.partitions:
            if p.key in moved_set:
                assert p.server == 2


def test_plan_join_is_deterministic_across_workers():
    """Two independent registries with the same declaration history
    must compute the identical join plan — workers re-route with no
    coordination message, exactly like crash migration."""
    regs = [_registry(2) for _ in range(2)]
    for reg in regs:
        for i in range(6):
            reg.init_tensor(f"d{i}", 2 * 4096, DataType.FLOAT32)
        reg.add_server()
    plans = [reg.plan_join(2) for reg in regs]
    assert plans[0] == plans[1]
    for reg, plan in zip(regs, plans):
        reg.rebalance(plan)
    tables = [[(p.key, p.server)
               for ctx in reg.contexts_in_order()
               for p in ctx.partitions] for reg in regs]
    assert tables[0] == tables[1]


def test_rebalance_rejects_stale_plan():
    reg = _registry(2)
    reg.init_tensor("x", 4 * 4096, DataType.FLOAT32)
    new = reg.add_server()
    plan = reg.plan_join(new)
    reg.migrate_server(0)  # routing changed under the plan
    with pytest.raises(RuntimeError, match="stale rebalance plan"):
        reg.rebalance(plan)


def test_plan_drain_is_migrate_with_retirement():
    """Drain and crash migration are ONE code path: the drain plan's
    moves match what migrate_server would do, plus retirement."""
    reg_a = _registry(3)
    reg_b = _registry(3)
    for reg in (reg_a, reg_b):
        for i in range(5):
            reg.init_tensor(f"m{i}", 2 * 4096, DataType.FLOAT32)
    plan = reg_a.plan_drain(1)
    assert plan.retire and plan.kind == "drain"
    moved_a = reg_a.rebalance(plan)
    moved_b = reg_b.migrate_server(1)
    assert moved_a == moved_b  # same keys, same engine
    tables = [[(p.key, p.server) for ctx in r.contexts_in_order()
               for p in ctx.partitions] for r in (reg_a, reg_b)]
    assert tables[0] == tables[1]  # same destinations too
    assert reg_a.dead_servers() == [1]
    assert reg_a.server_loads()[1] == 0
    # a drained server is out of NEW assignments too
    ctx = reg_a.init_tensor("post", 8 * 4096, DataType.FLOAT32)
    assert all(p.server != 1 for p in ctx.partitions)


def test_plan_drain_last_survivor_raises():
    reg = _registry(2)
    reg.init_tensor("x", 4096, DataType.FLOAT32)
    reg.migrate_server(0)
    with pytest.raises(RuntimeError, match="no other surviving"):
        reg.plan_drain(1)


def test_redeclare_bumps_routing_version():
    reg = _registry(2)
    reg.init_tensor("x", 4 * 4096, DataType.FLOAT32)
    v0 = reg.routing_version
    reg.redeclare_all(Config(num_workers=1, num_servers=1,
                             partition_bytes=4096))
    assert reg.routing_version == v0 + 1
    for ctx in reg.contexts_in_order():
        assert all(p.server == 0 for p in ctx.partitions)


# --------------------------------------------------------------------- #
# autoscaler controller: pure, deterministic, hysteresis
# --------------------------------------------------------------------- #


def _pull_bound(step, alive=1, per_server=None):
    return FleetSample(step=step, compute_ms=10.0, pull_ms=40.0,
                       per_server=per_server or {}, num_alive=alive)


def _idle(step, alive=2, per_server=None):
    return FleetSample(step=step, compute_ms=10.0, pull_ms=1.0,
                       per_server=per_server or {}, num_alive=alive)


def _balanced(step, alive=2, per_server=None):
    return FleetSample(step=step, compute_ms=10.0, pull_ms=10.0,
                       per_server=per_server or {}, num_alive=alive)


def test_controller_add_after_hysteresis():
    c = AutoscaleController(up_steps=3, cooldown=5)
    ds = [c.observe(_pull_bound(s)) for s in range(1, 4)]
    assert [d.action for d in ds] == ["hold", "hold", "add"]
    # cooldown: even under continued pressure, no immediate second add
    ds = [c.observe(_pull_bound(s, alive=2)) for s in range(4, 9)]
    assert all(d.action == "hold" for d in ds)


def test_controller_drain_after_idle_streak():
    c = AutoscaleController(down_steps=4, cooldown=2)
    ds = [c.observe(_idle(s)) for s in range(1, 5)]
    assert [d.action for d in ds] == ["hold", "hold", "hold", "drain"]
    # never drain below min_servers
    c2 = AutoscaleController(down_steps=2, min_servers=1)
    ds = [c2.observe(_idle(s, alive=1)) for s in range(1, 6)]
    assert all(d.action == "hold" for d in ds)


def test_controller_never_flaps_under_thresholds():
    """Signals inside the hysteresis band (neither pull-bound by the
    ratio nor idle) must never produce a decision, however long the
    run."""
    c = AutoscaleController()
    for s in range(1, 200):
        assert c.observe(_balanced(s)).action == "hold"


def test_controller_evicts_the_straggler():
    c = AutoscaleController(evict_factor=4.0, evict_steps=3)
    sig = {0: 2.0, 1: 2.2, 2: 50.0}  # server 2: 25x the median
    ds = [c.observe(_balanced(s, alive=3, per_server=sig))
          for s in range(1, 4)]
    assert [d.action for d in ds] == ["hold", "hold", "evict"]
    assert ds[-1].server == 2
    # an interrupted streak resets: 2 bad steps, 1 good, 2 bad -> hold
    c2 = AutoscaleController(evict_factor=4.0, evict_steps=3)
    seq = [sig, sig, {0: 2.0, 1: 2.2, 2: 2.1}, sig, sig]
    ds = [c2.observe(_balanced(s + 1, alive=3, per_server=ps))
          for s, ps in enumerate(seq)]
    assert all(d.action == "hold" for d in ds)


def test_controller_evict_noise_floor():
    """Sub-millisecond deltas on an idle fleet are measurement noise,
    not gray failure — even at a huge ratio over the median."""
    c = AutoscaleController(evict_factor=2.0, evict_steps=1)
    sig = {0: 0.001, 1: 0.0005, 2: 0.9}
    for s in range(1, 10):
        assert c.observe(
            _balanced(s, alive=3, per_server=sig)).action == "hold"


def test_controller_two_stack_determinism():
    """THE aggregation-safety property (acceptance): two independent
    controller stacks fed the identical signal sequence emit the
    identical decision sequence — same shape as the codec-plane
    two-stack test."""
    def sequence():
        out = []
        for s in range(1, 40):
            if s % 7 < 3:
                out.append(_pull_bound(s, alive=2,
                                       per_server={0: 3.0, 1: 3.3}))
            elif s % 7 < 5:
                out.append(_idle(s, alive=2,
                                 per_server={0: 2.0, 1: 40.0}))
            else:
                out.append(_balanced(s, alive=2,
                                     per_server={0: 2.0, 1: 40.0}))
        return out

    stacks = [AutoscaleController(up_steps=2, down_steps=3,
                                  evict_factor=4.0, evict_steps=2,
                                  cooldown=4) for _ in range(2)]
    decisions = [[c.observe(s) for s in sequence()] for c in stacks]
    assert decisions[0] == decisions[1]
    # and the sequence actually contains non-hold decisions (the test
    # must not pass vacuously on an all-hold run)
    assert any(not d.hold for d in decisions[0])


def test_straggler_signal_is_per_request_not_per_load():
    """Load imbalance must never read as gray failure: a healthy
    server handling 10x the requests (10x the ABSOLUTE stage time,
    equal per-request latency) gets signal ≈ its peers'; a true
    straggler (same request count, 50x the time) stands out."""
    plane = AutoscalerPlane.__new__(AutoscalerPlane)
    plane._mu = threading.Lock()
    plane._base = {}

    def sweep(values):
        plane._sweep_per_server = lambda: {
            s: {"queue_ns": q, "reply_ns": r, "queue_count": n}
            for s, (q, r, n) in values.items()}
        return plane._straggler_signal()

    # baseline tick: first sighting contributes NO signal (cumulative-
    # since-boot counters are not a step delta)
    assert sweep({0: (10**9, 10**9, 100), 1: (10**9, 10**9, 100)}) == {}
    # busy-but-healthy: server 0 does 10x the requests at the same
    # 2ms/request latency -> signals within noise of each other
    sig = sweep({0: (10**9 + 100 * 10 ** 6, 10**9 + 100 * 10**6, 200),
                 1: (10**9 + 10 * 10 ** 6, 10**9 + 10 * 10**6, 110)})
    assert abs(sig[0] - sig[1]) < 0.01, sig
    # true straggler: same request count, 50x the per-request time
    sig = sweep({0: (10**9 + 300 * 10**6, 10**9 + 300 * 10**6, 300),
                 1: (10**9 + 1010 * 10**6, 10**9 + 1010 * 10**6, 120)})
    assert sig[1] > 20 * sig[0], sig
    # a server that served nothing this window has no latency evidence
    sig = sweep({0: (10**9 + 400 * 10**6, 10**9 + 400 * 10**6, 350),
                 1: (10**9 + 1010 * 10**6, 10**9 + 1010 * 10**6, 120)})
    assert sig[1] == 0.0


def test_retirement_survives_resume_crash_verdicts_do_not():
    """A drained/evicted slot (config.retired_servers, the env
    round-trip) stays masked through redeclare_all; a crash verdict
    resets — a restarted server may re-use its index."""
    reg = TensorRegistry(Config(num_workers=1, num_servers=3,
                                partition_bytes=4096))
    for i in range(4):
        reg.init_tensor(f"rr{i}", 2 * 4096, DataType.FLOAT32)
    reg.migrate_server(1)  # crash verdict
    assert reg.dead_servers() == [1]
    # resume with index 2 RETIRED (drained earlier, env-carried)
    reg.redeclare_all(Config(num_workers=1, num_servers=3,
                             partition_bytes=4096,
                             retired_servers=(2,)))
    assert reg.dead_servers() == [2]  # crash reset, retirement kept
    for ctx in reg.contexts_in_order():
        for p in ctx.partitions:
            assert p.server != 2


def test_decision_is_frozen_value():
    d = Decision(1, "evict", 2, "r")
    with pytest.raises(Exception):
        d.action = "hold"


# --------------------------------------------------------------------- #
# live fleet drills: join / drain / gray-failure eviction
# --------------------------------------------------------------------- #


def _start_server(port, num_workers=1, env=None):
    """In-process server thread; chaos/throttle knobs are read per
    Server instance at construction, so a scoped env mutation taints
    exactly one server. When ``env`` is given, the restore waits for
    the port to ACCEPT — the Server (and its Chaos) constructs before
    it binds, so an accepting port proves the knobs were read (a fixed
    sleep raced thread-start latency under full-suite load)."""
    from byteps_tpu.server import run_server

    prior = {}
    if env:
        prior = {k: os.environ.get(k) for k in env}
        os.environ.update(env)
    try:
        t = threading.Thread(
            target=run_server,
            args=(port, Config(num_workers=num_workers, num_servers=1)),
            daemon=True)
        t.start()
        if env:
            _wait_port(port)
    finally:
        for k, v in prior.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return t


def _wait_port(port, timeout=60):
    from byteps_tpu.utils.net import wait_port

    wait_port(port, timeout)


def _ports(n):
    from byteps_tpu.utils.net import free_port

    ports = []
    while len(ports) < n:
        p = free_port()
        if p not in ports:
            ports.append(p)
    return ports


class _Fleet:
    """Scoped loopback fleet: N in-process servers + an initialized bps
    worker, with env save/restore, plus runtime growth."""

    def __init__(self, num_servers, extra_env=None):
        self.ports = _ports(num_servers)
        self.threads = []
        self.env = {
            "DMLC_NUM_WORKER": "1",
            "DMLC_NUM_SERVER": str(num_servers),
            "DMLC_PS_ROOT_URI": "127.0.0.1",
            "DMLC_PS_ROOT_PORT": str(self.ports[0]),
            "BYTEPS_SERVER_HOSTS": ",".join(
                f"127.0.0.1:{p}" for p in self.ports),
            "BYTEPS_FORCE_DISTRIBUTED": "1",
            # drain/evict exports this; scope it so a draining test
            # never leaks retirements into the rest of the suite
            "BYTEPS_RETIRED_SERVERS": "",
            **(extra_env or {}),
        }
        self.prior = {k: os.environ.get(k) for k in self.env}

    def __enter__(self):
        from byteps_tpu.core.state import GlobalState

        os.environ.update(self.env)
        for p in self.ports:
            self.threads.append(_start_server(p))
        for p in self.ports:
            _wait_port(p)
        GlobalState._instance = None
        import byteps_tpu as bps
        bps.init()
        self.bps = bps
        return bps

    def grow(self, env=None):
        """Start ONE more in-process server (runtime scale-up target);
        returns its address."""
        port = _ports(1)[0]
        self.threads.append(_start_server(port, env=env))
        _wait_port(port)
        self.ports.append(port)
        return f"127.0.0.1:{port}"

    def __exit__(self, *exc):
        from byteps_tpu.core.state import GlobalState

        try:
            self.bps.shutdown()
        except Exception:  # noqa: BLE001 - teardown best-effort
            pass
        GlobalState._instance = None
        for t in self.threads:
            t.join(timeout=20)
        for k, v in self.prior.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _rounds(bps, grads, lo, hi, prefix="el"):
    for r in range(lo, hi):
        hs = [bps.push_pull_async(g * (r + 1), f"{prefix}{i}",
                                  average=False)
              for i, g in enumerate(grads)]
        for g, h in zip(grads, hs):
            out = np.array(bps.synchronize(h, timeout=120))
            np.testing.assert_array_equal(out, g * (r + 1))


@pytest.mark.chaos
def test_join_then_drain_roundtrip_bitwise(tmp_path):
    """Scale up then scale back down, live: a runtime-started server
    joins (version-fenced rebalance moves keys TO it), training
    continues bitwise; a drain moves them back out and retires it,
    training still bitwise. Counters + flight events pin the
    lifecycle."""
    from byteps_tpu.core import flight as flight_mod
    from byteps_tpu.core.state import get_state

    fleet = _Fleet(1)
    with fleet as bps:
        state = get_state()
        rng = np.random.RandomState(3)
        grads = [rng.randn(2048).astype(np.float32) for _ in range(6)]
        _rounds(bps, grads, 0, 2)

        idx = bps.add_server(fleet.grow())
        assert idx == 1
        v_join = state.registry.routing_version
        loads = state.registry.server_loads()
        assert loads[1] > 0, "join moved no keys to the newcomer"
        _rounds(bps, grads, 2, 5)

        moved = bps.drain_server(1)
        assert moved, "drain moved nothing back"
        assert state.registry.dead_servers() == [1]
        assert state.registry.server_loads()[1] == 0
        assert state.registry.routing_version > v_join
        _rounds(bps, grads, 5, 7)

        snap = bps.get_metrics()
        assert snap["counters"]["registry/joins"] == 1
        assert snap["counters"]["registry/drains"] == 1
        assert snap["counters"]["server/evictions"] == 0
        # the drained server latched its advisory flag (DRAIN_REQ ACK)
        fleet_snap = bps.get_fleet_metrics()["fleet"]
        assert fleet_snap["server"]["1"]["draining"] >= 1
        # flight: join precedes drain precedes the per-key migrations
        evs = flight_mod.get_recorder().events()
        kinds = [e["kind"] for e in evs]
        assert "server_join" in kinds and "server_drain" in kinds
        assert kinds.index("server_join") < kinds.index("server_drain")
        mig = [i for i, k in enumerate(kinds) if k == "key_migration"]
        assert mig and min(mig) > kinds.index("server_drain")
        # drain does NOT terminate the server process; fleet teardown's
        # SHUTDOWN (sent to every connected server) releases it


@pytest.mark.chaos
def test_gray_failure_eviction_drill(tmp_path):
    """THE acceptance drill: under BYTEPS_CHAOS_SLOW_SERVER the
    deterministic detector evicts the straggler within the pinned step
    budget, training completes with bitwise parity, and the flight
    record shows the detect -> drain(evict) -> migrate chain in causal
    order."""
    from byteps_tpu.core import flight as flight_mod
    from byteps_tpu.core.state import get_state

    evict_steps = 3
    fleet = _Fleet(1, extra_env={
        "BYTEPS_AUTOSCALE": "act",
        "BYTEPS_AUTOSCALE_EVICT_STEPS": str(evict_steps),
        "BYTEPS_AUTOSCALE_EVICT_FACTOR": "4",
        "BYTEPS_FLIGHT_DIR": str(tmp_path / "flight")})
    with fleet as bps:
        state = get_state()
        plane = bps.get_autoscaler()
        assert plane is not None
        rng = np.random.RandomState(9)
        grads = [rng.randn(2048).astype(np.float32) for _ in range(6)]
        _rounds(bps, grads, 0, 1, prefix="gray")  # declare + init
        # the straggler joins at runtime with a persistent 40ms/request
        # injected delay (read per Server instance — the healthy server
        # is untouched); the join rebalance hands it real keys
        bps.add_server(
            fleet.grow(env={"BYTEPS_CHAOS_SLOW_SERVER": "40"}))
        assert state.registry.server_loads()[1] > 0

        evicted_at = None
        budget = evict_steps + 4  # pinned step budget for detection
        for r in range(budget):
            _rounds(bps, grads, r, r + 1, prefix="gray")
            d = plane.tick()  # the step-boundary sensor tick
            if d.action == "evict":
                evicted_at = r
                break
        assert evicted_at is not None, (
            f"detector did not evict within {budget} steps: "
            f"{plane.decisions()}")
        assert evicted_at <= budget - 1
        # the straggler is gone from the routing table; training
        # completes bitwise on the survivor
        assert state.registry.dead_servers() == [1]
        assert state.registry.server_loads()[1] == 0
        _rounds(bps, grads, budget, budget + 2, prefix="gray")

        snap = bps.get_metrics()
        assert snap["counters"]["server/evictions"] == 1
        assert snap["counters"]["registry/drains"] == 1
        assert snap["counters"]["autoscale/decisions"] >= 1
        assert snap["autoscale"]["last"]["action"] == "evict"
        assert snap["autoscale"]["last"]["server"] == 1

        # flight record: detect -> evict(drain) -> per-key migration,
        # causally ordered in one timeline (satellite: the chaos-suite
        # assertion pinning detect→drain→migrate order)
        evs = flight_mod.get_recorder().events()
        kinds = [e["kind"] for e in evs]
        assert "autoscale_decision" in kinds
        assert "server_evict" in kinds
        i_detect = kinds.index("autoscale_decision")
        i_evict = kinds.index("server_evict")
        mig = [i for i, k in enumerate(kinds) if k == "key_migration"]
        assert i_detect < i_evict, "evict recorded before its decision"
        assert mig and min(mig) > i_evict, \
            "migration recorded before the evict"
        ts = [e["ts_ns"] for e in evs]
        assert ts == sorted(ts), "flight events out of causal order"
        ev = evs[i_evict]
        assert ev["key"] == 1  # the evict names the straggler
        # and the merged dump (worker + servers) stays causally sorted
        import json
        dump_path = bps.dump_flight_record(
            str(tmp_path / "gray-flight.json"))
        assert dump_path and os.path.exists(dump_path)
        with open(dump_path) as f:
            doc = json.load(f)
        merged_ts = [e["ts_ns"] for e in doc["merged"]]
        assert merged_ts == sorted(merged_ts)


@pytest.mark.chaos
def test_resume_with_different_num_servers_rebuilds_routing():
    """Satellite: bps.resume with a DIFFERENT num_servers must rebuild
    routing against the new topology (never a stale assignment table),
    with bitwise parity across the suspend/resume cycle."""
    from byteps_tpu.core.state import get_state
    from byteps_tpu.server.client import PSClient

    fleet = _Fleet(2)
    with fleet as bps:
        state = get_state()
        rng = np.random.RandomState(17)
        grads = [rng.randn(4096).astype(np.float32) for _ in range(6)]
        _rounds(bps, grads, 0, 2, prefix="rs")
        owners = {p.server for ctx in state.registry.contexts_in_order()
                  for p in ctx.partitions}
        assert owners == {0, 1}, f"keys not spread: {owners}"
        v0 = state.registry.routing_version

        bps.suspend()
        bps.resume(num_workers=1, num_servers=1)
        state = get_state()
        assert state.config.num_servers == 1
        # the WHOLE table was rebuilt: no partition may still target
        # the departed server, and the fence advanced
        for ctx in state.registry.contexts_in_order():
            for p in ctx.partitions:
                assert p.server == 0
        assert state.registry.routing_version > v0
        assert state.registry.dead_servers() == []
        # bitwise parity across the cycle (1 worker: aggregate == push)
        _rounds(bps, grads, 2, 4, prefix="rs")

        # resume trimmed the host list to the new count
        assert os.environ["BYTEPS_SERVER_HOSTS"].count(",") == 0

        # growing past the known host list must be a CLEAR error, not a
        # stale-table reconnect
        bps.suspend()
        with pytest.raises(ValueError, match="names only 1"):
            bps.resume(num_workers=1, num_servers=2)
        bps.resume(num_workers=1, num_servers=1)

        # release the abandoned server-1 thread: the resumed 1-server
        # client will never send it the SHUTDOWN it waits for
        PSClient([f"127.0.0.1:{fleet.ports[1]}"], worker_id=0).close()


def test_join_probe_validates_worker_count():
    """A newcomer running a different num_workers must be refused at
    the handshake — routing keys to it would wedge every round. The
    refused index is RETIRED, not leaked: the native conn table cannot
    shrink, so the slot is accounted for and a LATER (correct) join
    still aligns instead of wedging on a table mismatch."""
    from byteps_tpu.core.state import get_state
    from byteps_tpu.server.client import PSClient

    fleet = _Fleet(1)
    with fleet as bps:
        state = get_state()
        port = _ports(1)[0]
        _start_server(port, num_workers=2)  # fleet runs 1
        _wait_port(port)
        rng = np.random.RandomState(4)
        grads = [rng.randn(1024).astype(np.float32) for _ in range(4)]
        _rounds(bps, grads, 0, 1, prefix="jp")
        with pytest.raises(RuntimeError, match="num_workers"):
            bps.add_server(f"127.0.0.1:{port}")
        # the refused slot is retired unused: registry/config cover it
        # (matching the un-shrinkable native table) but nothing ever
        # routes there
        assert state.config.num_servers == 2
        assert state.registry.dead_servers() == [1]
        assert state.registry.server_loads()[1] == 0
        # a subsequent CORRECT join realigns at the next index and
        # works — the one-bad-probe wedge the rollback exists for
        idx = bps.add_server(fleet.grow())
        assert idx == 2
        assert state.registry.server_loads()[2] > 0
        _rounds(bps, grads, 1, 3, prefix="jp")
        # release the 2-worker server: it needs a second SHUTDOWN on
        # top of the one fleet teardown's client will send it
        PSClient([f"127.0.0.1:{port}"], worker_id=1).close()


def test_observer_wiring_drives_autoscaler_tick():
    """StepProfiler.add_observer delivers each finished report on the
    train thread — the autoscaler's sensor tap."""
    from byteps_tpu.core.metrics import StepProfiler

    seen = []
    prof = StepProfiler(window=4)
    prof.add_observer(seen.append)
    b = prof.begin_step()
    r = prof.end_step(b)
    assert seen == [r]
    # a raising observer must not kill the step
    prof.add_observer(lambda _r: (_ for _ in ()).throw(RuntimeError()))
    b = prof.begin_step()
    r2 = prof.end_step(b)
    assert r2 is not None and seen[-1] is r2
