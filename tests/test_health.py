"""Training-health plane tests (core/health.py + the native in-fold
statistics pass, docs/observability.md "Training-health plane").

Pins the PR's acceptance surface:

- the in-fold statistics are BITWISE-neutral: aggregates with
  BYTEPS_HEALTH on vs off compare equal as raw bits across dense f32
  (fused last-fold kernel), bf16, rowsparse and fused-PUSHPULL traffic;
- the statistics themselves are correct (sum-of-squares / abs-max over
  FINITE elements, NaN/Inf counted) on both the publish-scan and the
  fused multi-worker path, served by the HEALTH_PULL wire op and the
  in-process ``server.key_health`` mirror;
- the detector is a pure clockless hysteresis machine: two stacks fed
  identical signals emit identical verdicts (incl. the fidelity-drift →
  codec de-escalation chain), warmup never fires, cooldowns don't flap;
- injected-NaN chaos (BYTEPS_CHAOS_NAN_LEAF) shows detect →
  flight-event → (guard on) bounded fail-fast with "flight record
  dumped", and guard-off training continues with
  ``health/nonfinite_rounds`` counting.
"""

import contextlib
import os
import threading

import numpy as np
import pytest

from byteps_tpu.config import Config
from byteps_tpu.core.codec_plane import CodecController, CodecPlan, \
    RoundSignal
from byteps_tpu.core.health import HealthDetector, HealthSignal
from byteps_tpu.core.metrics import StepReport, classify_step
from byteps_tpu.core.registry import TensorRegistry
from byteps_tpu.core.types import DataType, RequestType, get_command_type
from byteps_tpu.server import (
    _STAT_SLOTS, key_health, native_stat_slot_names, run_server,
)
from byteps_tpu.server.client import PSClient

CMD_F32 = get_command_type(RequestType.DEFAULT_PUSH_PULL,
                           DataType.FLOAT32)
CMD_BF16 = get_command_type(RequestType.DEFAULT_PUSH_PULL,
                            DataType.BFLOAT16)

_PORT = [21370]


def _start_server(num_workers: int, health: bool, monkeypatch):
    """One loopback server with BYTEPS_HEALTH latched at construction
    (the native pass reads the env per Server instance). Returns its
    address; connecting a client proves construction finished, so the
    caller may flip the env afterwards for the next server."""
    port = _PORT[0]
    _PORT[0] += 1
    monkeypatch.setenv("BYTEPS_HEALTH", "1" if health else "0")
    cfg = Config(num_workers=num_workers, num_servers=1)
    t = threading.Thread(target=run_server, args=(port, cfg),
                         daemon=True)
    t.start()
    return f"127.0.0.1:{port}", t


# --------------------------------------------------------------------- #
# detector unit (pure hysteresis machine)
# --------------------------------------------------------------------- #


def _sig(step, gn=None, nf=0, drift=None):
    return HealthSignal(step=step, grad_norm=gn, nonfinite_leaves=nf,
                        fidelity_drift=drift)


def test_detector_nonfinite_fires_every_round():
    d = HealthDetector()
    assert d.observe(_sig(1, gn=1.0, nf=2)) == ("nonfinite",)
    assert d.observe(_sig(2, gn=1.0, nf=1)) == ("nonfinite",)
    assert d.observe(_sig(3, gn=1.0)) == ()


def test_detector_warmup_never_fires():
    d = HealthDetector(streak=1)
    # fewer than 4 trailing samples: no baseline, no explode/collapse
    for s in range(3):
        assert d.observe(_sig(s, gn=10.0 ** s)) == ()


def test_detector_explosion_streak_and_cooldown():
    d = HealthDetector(window=16, explode_ratio=10.0, streak=2,
                       cooldown=3)
    for s in range(6):
        assert d.observe(_sig(s, gn=1.0)) == ()
    # first crossing clocks the streak, second fires
    assert d.observe(_sig(6, gn=50.0)) == ()
    assert d.observe(_sig(7, gn=50.0)) == ("explode",)
    # cooldown: the still-exploded rounds stay silent, then re-fire
    fired = [d.observe(_sig(8 + i, gn=50.0)) for i in range(8)]
    assert ("explode",) in fired
    assert fired.count(("explode",)) <= 2  # no per-round flapping


def test_detector_collapse():
    d = HealthDetector(window=8, collapse_ratio=0.01, streak=2)
    for s in range(6):
        assert d.observe(_sig(s, gn=1.0)) == ()
    assert d.observe(_sig(6, gn=1e-5)) == ()
    assert d.observe(_sig(7, gn=1e-5)) == ("collapse",)


def test_detector_drift():
    d = HealthDetector(drift_frac=0.1, streak=2)
    assert d.observe(_sig(1, gn=1.0, drift=0.5)) == ()
    assert d.observe(_sig(2, gn=1.0, drift=0.5)) == ("drift",)
    # below threshold resets the streak
    assert d.observe(_sig(3, gn=1.0, drift=0.01)) == ()


def test_detector_nonfinite_rounds_never_enter_window():
    """A poisoned round's (meaningless) norm must not inflate the
    trailing median — the next honest explosion still fires."""
    d = HealthDetector(window=8, explode_ratio=10.0, streak=1,
                       cooldown=0)
    for s in range(6):
        d.observe(_sig(s, gn=1.0))
    assert d.observe(_sig(6, gn=1000.0, nf=3)) == ("nonfinite",)
    # had 1000.0 entered the window the median would still be 1.0, but
    # a few more poisoned rounds would shift it — pin directly:
    assert 1000.0 not in d._norms
    assert d.observe(_sig(7, gn=15.0)) == ("explode",)


def test_detector_two_stack_determinism():
    """Identical signal sequences -> identical verdict sequences (the
    aggregation-safety property the codec veto rests on)."""
    seq = []
    rng = np.random.RandomState(7)
    for s in range(60):
        gn = float(abs(rng.randn())) + 0.5
        if s in (20, 21, 22):
            gn *= 100.0
        nf = 1 if s == 35 else 0
        drift = 0.4 if s in (45, 46) else 0.0
        seq.append(_sig(s, gn=gn, nf=nf, drift=drift))
    a = HealthDetector(streak=2, cooldown=4)
    b = HealthDetector(streak=2, cooldown=4)
    va = [a.observe(s) for s in seq]
    vb = [b.observe(s) for s in seq]
    assert va == vb
    assert any(v for v in va)  # the sequence exercised real firings


# --------------------------------------------------------------------- #
# native in-fold statistics + HEALTH_PULL
# --------------------------------------------------------------------- #


def test_infold_stats_single_worker_scan(monkeypatch):
    """Single-worker dense round: the adopt path publishes via the
    read-only scan; sumsq/absmax cover finite elements only and the
    NaN is COUNTED, not folded into the norm."""
    addr, _ = _start_server(1, health=True, monkeypatch=monkeypatch)
    c = PSClient([addr], worker_id=0)
    x = np.zeros(100, np.float32)
    x[0], x[1], x[2] = 3.0, -4.0, np.nan
    c.init_key(0, 7, np.zeros_like(x), CMD_F32)
    c.zpush(0, 7, x, CMD_F32)
    out = np.empty_like(x)
    c.zpull(0, 7, out, CMD_F32)
    rec = key_health(7)
    assert rec is not None
    assert rec["round"] == 1 and rec["elems"] == 100
    assert rec["sumsq"] == pytest.approx(25.0)
    assert rec["absmax"] == pytest.approx(4.0)
    assert rec["nonfinite"] == 1
    # wire surface agrees with the in-process mirror
    wrec = c.health_pull(0, 7)
    assert wrec == rec
    # unknown key: None, never a zeroed record
    assert c.health_pull(0, 999) is None
    c.close()


def _init2(w0, w1, key, z, cmd):
    """Two-worker init: the init reply is withheld until BOTH workers'
    init pushes arrive (global barrier), so the calls must overlap."""
    t = threading.Thread(target=w0.init_key, args=(0, key, z, cmd),
                         daemon=True)
    t.start()
    w1.init_key(0, key, z, cmd)
    t.join(timeout=30)
    assert not t.is_alive()


def test_infold_stats_fused_multiworker(monkeypatch):
    """Two-worker dense round: the LAST fold runs the fused stat
    kernel — statistics describe the post-aggregation sum."""
    addr, _ = _start_server(2, health=True, monkeypatch=monkeypatch)
    c0 = PSClient([addr], worker_id=0)
    c1 = PSClient([addr], worker_id=1)
    rng = np.random.RandomState(0)
    a = rng.randn(4097).astype(np.float32)
    b = rng.randn(4097).astype(np.float32)
    z = np.zeros_like(a)
    _init2(c0, c1, 11, z, CMD_F32)
    c0.zpush(0, 11, a, CMD_F32)
    c1.zpush(0, 11, b, CMD_F32)
    out = np.empty_like(a)
    c0.zpull(0, 11, out, CMD_F32)
    agg = a + b
    np.testing.assert_array_equal(out, agg)
    rec = key_health(11)
    assert rec is not None and rec["nonfinite"] == 0
    assert rec["elems"] == 4097
    assert rec["sumsq"] == pytest.approx(
        float(np.dot(agg.astype(np.float64), agg.astype(np.float64))),
        rel=1e-10)
    assert rec["absmax"] == pytest.approx(
        float(np.abs(agg).max()), rel=1e-7)
    c0.close()
    c1.close()


def test_key_health_none_when_off(monkeypatch):
    addr, _ = _start_server(1, health=False, monkeypatch=monkeypatch)
    c = PSClient([addr], worker_id=0)
    x = np.ones(32, np.float32)
    c.init_key(0, 5, np.zeros_like(x), CMD_F32)
    c.zpush(0, 5, x, CMD_F32)
    out = np.empty_like(x)
    c.zpull(0, 5, out, CMD_F32)
    assert key_health(5) is None
    assert c.health_pull(0, 5) is None
    c.close()


def test_stat_slots_appended():
    names = native_stat_slot_names()
    assert names == list(_STAT_SLOTS)
    assert names[-9:] == ["tx_batches", "tx_msgs", "rx_batches",
                          "rx_msgs", "stripe_segs", "stripe_bytes",
                          "fused_decode_folds", "reg_blocks",
                          "reg_miss"]
    assert names[-13:-9] == ["health_rounds", "health_nonfinite",
                             "window_deferred", "window_rejected"]


def _bf16(x: np.ndarray) -> np.ndarray:
    return (np.ascontiguousarray(x, np.float32).view(np.uint32)
            >> 16).astype(np.uint16)


def test_aggregate_parity_health_on_off(monkeypatch):
    """BITWISE-neutrality: identical traffic against a health-on and a
    health-off server publishes identical aggregates — dense f32
    (multi-worker: the fused stat kernel wrote the bits), bf16
    (publish scan), rowsparse, and fused PUSHPULL — NaN/Inf payload
    lanes included (uint comparisons)."""
    addr_on, _ = _start_server(2, health=True, monkeypatch=monkeypatch)
    con0 = PSClient([addr_on], worker_id=0)  # proves server A built
    addr_off, _ = _start_server(2, health=False,
                                monkeypatch=monkeypatch)
    con1 = PSClient([addr_on], worker_id=1)
    coff0 = PSClient([addr_off], worker_id=0)
    coff1 = PSClient([addr_off], worker_id=1)
    rng = np.random.RandomState(3)

    def dense_round(key, cmd, a, b, view):
        outs = []
        for w0, w1 in ((con0, con1), (coff0, coff1)):
            z = np.zeros_like(a)
            _init2(w0, w1, key, z, cmd)
            w0.zpush(0, key, a, cmd)
            w1.zpush(0, key, b, cmd)
            out = np.empty_like(a)
            w0.zpull(0, key, out, cmd)
            outs.append(out.view(view))
        np.testing.assert_array_equal(outs[0], outs[1])

    # dense f32 with special lanes (NaN/Inf/subnormal)
    a = rng.randn(1025).astype(np.float32)
    b = rng.randn(1025).astype(np.float32)
    a[0], a[1], a[2] = np.nan, np.inf, np.float32(1e-42)
    dense_round(100, CMD_F32, a, b, np.uint32)
    # bf16 (widen-fold-narrow; publish scan on the health server)
    dense_round(101, CMD_BF16, _bf16(rng.randn(513) * 8),
                _bf16(rng.randn(513) * 8), np.uint16)
    # fused PUSHPULL: reply IS the aggregate
    fouts = []
    fpay = [rng.randn(256).astype(np.float32) for _ in range(2)]
    for w0, w1 in ((con0, con1), (coff0, coff1)):
        z = np.zeros(256, np.float32)
        _init2(w0, w1, 102, z, CMD_F32)
        res = {}
        evs = []
        for wi, w in enumerate((w0, w1)):
            out = np.empty(256 * 4, np.uint8)
            ev = threading.Event()
            w.zpushpull_async(
                0, 102, fpay[wi], out, CMD_F32,
                (lambda n, err, o=out, i=wi, e=ev:
                 (res.__setitem__(i, bytes(o)), e.set())),
                epoch=(1 << 16))
            evs.append(ev)
        for ev in evs:
            assert ev.wait(60)
        fouts.append(res[0])
    assert fouts[0] == fouts[1]
    # rowsparse: scatter-add rows, dense publish scan
    souts = []
    g = np.zeros((64, 8), np.float32)
    g[3] = rng.randn(8)
    g[40] = rng.randn(8)
    for tag, w0, w1 in (("on", con0, con1), ("off", coff0, coff1)):
        reg = TensorRegistry(Config(num_workers=2, num_servers=1))
        ctx = reg.init_tensor(f"emb-{tag}", 64 * 8 * 4,
                              DataType.FLOAT32, align_bytes=32)
        zt = np.zeros(64 * 8, np.float32)
        it = threading.Thread(target=w0.init_tensor, args=(ctx, zt),
                              daemon=True)
        it.start()
        w1.init_tensor(ctx, zt)
        it.join(timeout=30)
        assert not it.is_alive()
        r = {}
        ths = [threading.Thread(
            target=lambda w=w, i=i: r.__setitem__(
                i, w.push_pull_rowsparse(ctx, g, average=False,
                                         num_workers=2)))
            for i, w in enumerate((w0, w1))]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=60)
        souts.append(r[0].view(np.uint32).copy())
    np.testing.assert_array_equal(souts[0], souts[1])
    # the health-on server actually took statistics on this traffic
    rec = key_health(100)
    assert rec is not None and rec["nonfinite"] >= 1  # the NaN/Inf lanes
    for c in (con0, con1, coff0, coff1):
        c.close()


# --------------------------------------------------------------------- #
# codec-plane numerics veto (deterministic, two-stack)
# --------------------------------------------------------------------- #


def _perf(step, pull=50.0, compute=5.0, degraded=False):
    return RoundSignal(step=step, compute_ms=compute, pull_ms=pull,
                       degraded=degraded)


def test_controller_veto_blocks_escalation():
    c = CodecController(ladder=("dense", "lossless", "onebit"),
                        up_rounds=1, pull_ratio=1.0)
    plan = CodecPlan()
    # degraded rounds can never escalate, however PULL-bound
    for s in range(5):
        assert c.decide(plan, _perf(s, degraded=True)) is None
    assert plan.rung == 0
    # healthy pressure escalates as before
    assert c.decide(plan, _perf(6)) == "lossless"


def test_controller_veto_forces_deescalation_to_safe_rung():
    c = CodecController(ladder=("dense", "lossless", "onebit"),
                        up_rounds=1, pull_ratio=1.0)
    plan = CodecPlan(rung=2)  # on the lossy rung
    assert c.decide(plan, _perf(1, degraded=True)) == "lossless"
    assert plan.rung == 1
    # already safe: hold (no further forced move, no escalation)
    assert c.decide(plan, _perf(2, degraded=True)) is None
    assert plan.rung == 1


def test_controller_veto_jumps_to_dense_without_lossless():
    c = CodecController(ladder=("dense", "onebit"), up_rounds=1,
                        pull_ratio=1.0)
    plan = CodecPlan(rung=1)
    assert c.decide(plan, _perf(1, degraded=True)) == "dense"
    assert plan.rung == 0


def test_controller_veto_all_lossy_ladder_holds():
    """An all-lossy ladder has no numerics-safe rung: the veto blocks
    escalation but must NOT re-return the same tier every degraded
    round (switch-per-round spam with no effect)."""
    c = CodecController(ladder=("onebit", "randomk"), up_rounds=1,
                        pull_ratio=1.0)
    plan = CodecPlan(rung=1)
    for s in range(4):
        assert c.decide(plan, _perf(s, degraded=True)) is None
    assert plan.rung == 1  # held, never thrashed


def test_health_plane_refuses_to_arm_without_metrics():
    """BYTEPS_HEALTH=1 with BYTEPS_METRICS=0 would be per-step cost
    with the detector (and NaN guard) never running — the plane must
    refuse to arm rather than silently degrade."""
    from byteps_tpu.core.health import HealthPlane
    from byteps_tpu.core.metrics import MetricsRegistry
    cfg = Config(num_workers=1, num_servers=0, health=True,
                 metrics_on=False)
    plane = HealthPlane(cfg, MetricsRegistry(enabled=False))
    assert plane.enabled is False
    assert plane.begin_collect(4) is None


def test_drift_to_deescalation_two_stack():
    """The acceptance chain, two independent stacks: fidelity-drift
    signals -> detector verdict -> degraded RoundSignal -> controller
    de-escalates off the lossy rung — identical on both stacks, and
    pinned to land on ``lossless``."""
    def run_stack():
        det = HealthDetector(streak=2, cooldown=4)
        ctl = CodecController(ladder=("dense", "lossless", "onebit"),
                              up_rounds=1, pull_ratio=1.0)
        plan = CodecPlan(rung=2)
        out = []
        for s in range(10):
            drift = 0.5 if s >= 4 else 0.0
            flags = det.observe(_sig(s, gn=1.0, drift=drift))
            tier = ctl.decide(plan, _perf(s, degraded=bool(flags)))
            out.append((flags, tier, plan.rung))
        return out
    a, b = run_stack(), run_stack()
    assert a == b
    # the drift verdict fired and forced the plan off onebit
    assert any(f == ("drift",) for f, _, _ in a)
    assert ("drift",) in [f for f, t, _ in a if t == "lossless"] \
        or any(t == "lossless" for _, t, _ in a)
    assert a[-1][2] == 1  # parked on the numerics-safe lossless rung


def test_round_signal_degraded_from_report():
    r = StepReport(step=3, health_flags=("explode",))
    assert RoundSignal.from_report(r).degraded is True
    r2 = StepReport(step=4, health_flags=())
    assert RoundSignal.from_report(r2).degraded is False
    r3 = StepReport(step=5)  # health pass off
    assert RoundSignal.from_report(r3).degraded is False


def test_classify_step_health_verdict():
    r = StepReport(step=1, wall_ms=10.0, compute_ms=8.0,
                   grad_norm=0.031, update_ratio_p95=2.1e-4,
                   nonfinite_leaves=0, health_flags=())
    msg = classify_step(r)
    assert "health: grad_norm 0.031" in msg
    assert "update p95" in msg
    r2 = StepReport(step=2, wall_ms=10.0, compute_ms=8.0,
                    grad_norm=0.03, nonfinite_leaves=3,
                    health_flags=("nonfinite",))
    msg2 = classify_step(r2)
    assert "HEALTH nonfinite" in msg2 and "3 nonfinite leaves" in msg2


def test_archive_record_gains_health_fields():
    from byteps_tpu.core.ledger import EfficiencyLedger
    r = StepReport(step=9, wall_ms=5.0, grad_norm=0.5,
                   update_ratio_p95=1e-3, nonfinite_leaves=0)
    rec = EfficiencyLedger._archive_record(r)
    assert rec["grad_norm"] == 0.5
    assert rec["update_ratio_p95"] == pytest.approx(1e-3)
    assert rec["nonfinite_leaves"] == 0


# --------------------------------------------------------------------- #
# loopback PS end-to-end: fields, chaos, guard
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


def _train_rounds(steps=3, **kw):
    import jax
    import jax.numpy as jnp
    import optax

    from byteps_tpu.core.state import get_state
    from byteps_tpu.jax.train import make_ps_train_step
    from byteps_tpu.models import mlp

    cfg = mlp.MLPConfig(in_dim=64, hidden=(48, 32), n_classes=10)
    params = mlp.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)
    batch = {"x": jnp.asarray(rng.rand(32, 64), jnp.float32),
             "y": jnp.asarray(rng.randint(0, 10, 32), jnp.int32)}
    tx = optax.adam(1e-2)
    opt = tx.init(params)
    step = make_ps_train_step(lambda p, b: mlp.loss_fn(p, b, cfg), tx,
                              get_state().mesh, **kw)
    for _ in range(steps):
        params, opt, loss = step(params, opt, batch)
    return float(loss)


def test_loopback_health_end_to_end():
    """The acceptance run: BYTEPS_HEALTH=1 lands non-null grad_norm /
    update_ratio_p95, zero nonfinite leaves, a healthy () verdict, the
    health verdict in the diagnosis, live gauges, and nonzero in-fold
    stat slots on the server."""
    with _ps_env({"BYTEPS_HEALTH": "1"}) as bps:
        _train_rounds(steps=4)
        reports = bps.get_step_reports()
        assert len(reports) == 4
        last = reports[-1]
        assert last["grad_norm"] is not None and last["grad_norm"] > 0
        assert last["update_ratio_p95"] is not None
        assert last["update_ratio_p95"] > 0
        assert last["nonfinite_leaves"] == 0
        assert last["health_flags"] == ()
        m = bps.get_metrics()
        assert "health" in m["steps"]["last_diagnosis"]
        assert m["gauges"]["health/grad_norm"] == pytest.approx(
            last["grad_norm"])
        assert m["counters"]["health/nonfinite_rounds"] == 0
        # the native in-fold pass engaged: stat slots nonzero (fleet-
        # scoped: STATS_PULL against THIS run's server, immune to any
        # not-yet-reaped server from another test)
        fleet = m["fleet"]["server"]["0"]
        assert fleet["health_rounds"] > 0
        assert fleet["health_nonfinite"] == 0
        assert m["server"]["health_rounds"] >= fleet["health_rounds"]


def test_health_off_fields_none():
    with _ps_env() as bps:
        _train_rounds(steps=2)
        last = bps.get_step_reports()[-1]
        assert last["grad_norm"] is None
        assert last["nonfinite_leaves"] is None
        assert last["health_flags"] is None
        # fleet-scoped (STATS_PULL against THIS run's server): the
        # summed in-process `server` section could see another test's
        # not-yet-reaped server
        fleet = bps.get_metrics()["fleet"]["server"]["0"]
        assert fleet["health_rounds"] == 0


def test_chaos_nan_detect_flight_and_continue(tmp_path):
    """Guard OFF: the injected NaN is detected (nonfinite round +
    flight event, chaos-injection BEFORE detection in the causal
    record) and training CONTINUES — health/nonfinite_rounds counts."""
    with _ps_env({"BYTEPS_HEALTH": "1",
                  "BYTEPS_FUSION_BYTES": "0",
                  "BYTEPS_FLIGHT_DIR": str(tmp_path / "fl"),
                  "BYTEPS_CHAOS_NAN_LEAF": "grad/@2"}) as bps:
        _train_rounds(steps=5)  # no raise: guard off
        reports = bps.get_step_reports()
        assert len(reports) == 5
        assert any((r["nonfinite_leaves"] or 0) > 0 for r in reports)
        m = bps.get_metrics()
        assert m["counters"]["health/nonfinite_rounds"] >= 1
        # server side saw the poisoned aggregate too
        assert m["server"]["health_nonfinite"] >= 1
        from byteps_tpu.core import flight
        evs = flight.get_recorder().events()
        kinds = [e["kind"] for e in evs]
        assert "chaos_nan_injected" in kinds
        assert "health_nonfinite" in kinds
        # causality: injection recorded before detection
        assert kinds.index("chaos_nan_injected") \
            < kinds.index("health_nonfinite")


def test_chaos_nan_guard_failfast(tmp_path):
    """Guard ON: detect → flight events → bounded fail-fast naming the
    dumped flight record — never a silently poisoned run."""
    with _ps_env({"BYTEPS_HEALTH": "1", "BYTEPS_NAN_GUARD": "1",
                  "BYTEPS_FUSION_BYTES": "0",
                  "BYTEPS_FLIGHT_DIR": str(tmp_path / "fl"),
                  "BYTEPS_CHAOS_NAN_LEAF": "grad/@3"}) as bps:
        with pytest.raises(RuntimeError, match="BYTEPS_NAN_GUARD"):
            _train_rounds(steps=6)
        reports = bps.get_step_reports()
        assert any((r["nonfinite_leaves"] or 0) > 0 for r in reports)
        assert bps.get_metrics()["counters"][
            "health/nonfinite_rounds"] >= 1
        from byteps_tpu.core import flight
        kinds = [e["kind"] for e in flight.get_recorder().events()]
        assert "health_nonfinite" in kinds
    # the error names the dump and the dump exists
    dumps = list((tmp_path / "fl").glob("*.json"))
    assert dumps, "nan-guard did not dump a flight record"


def test_chaos_nan_guard_error_names_dump(tmp_path):
    """The raised error carries the _fatal_wire_error contract string
    (pinned separately so a reword can't silently drop the pointer)."""
    with _ps_env({"BYTEPS_HEALTH": "1", "BYTEPS_NAN_GUARD": "1",
                  "BYTEPS_FUSION_BYTES": "0",
                  "BYTEPS_FLIGHT_DIR": str(tmp_path / "fl"),
                  "BYTEPS_CHAOS_NAN_LEAF": "grad/@4"}):
        with pytest.raises(RuntimeError,
                           match="flight record dumped to"):
            _train_rounds(steps=7)
