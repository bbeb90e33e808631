"""Time-series plane (core/timeseries.py) + byteps-top console
(tools/top.py): sampler two-stack determinism, bounded memory (ring
cap + series-count cap), None-skip semantics, counter-delta seeding,
the one-way sweep breaker, the pinned SIGTERM term-hook order
(timeseries → archive), JSONL dump/rehydrate through the console's
post-mortem path, the ``--once`` frame schema pin, the LANE-IMBALANCE
verdict trip/no-trip, the ``_TS_STEP_FIELDS`` / ``_STRIPE_REC_FIELDS``
runtime manifest parity, and a loopback e2e with striping + staleness
engaged (slow)."""

import contextlib
import dataclasses
import json
import os
import threading

import numpy as np
import optax
import pytest

from byteps_tpu.config import Config
from byteps_tpu.core import timeseries as ts_mod
from byteps_tpu.core.metrics import (
    MetricsRegistry, StepProfiler, StepReport, classify_step,
)
from byteps_tpu.core.timeseries import TimeSeriesPlane, _TS_STEP_FIELDS
from byteps_tpu.server import run_server
from byteps_tpu.tools import top

_PORT = [24760]  # test_ledger.py counts up from 24700 in another worker


def _report(step, **kw):
    kw.setdefault("wall_ms", 10.0 + step)
    kw.setdefault("compute_ms", 7.0)
    return StepReport(step=step, **kw)


# --------------------------------------------------------------------- #
# unit tier: recorder semantics
# --------------------------------------------------------------------- #


def test_two_stack_determinism():
    """Clockless contract: two recorders fed the same reports produce
    IDENTICAL series — nothing sampled reads a wall clock."""
    def feed(plane):
        for s in range(1, 8):
            plane.observe(_report(
                s, mfu=0.1 * s,
                lane_bytes=((0, 1, 1000 * s), (0, 2, 400 * s)),
                staleness_lag=1, carry_drain_ms=0.5 * s))
        return plane.series()

    a = feed(TimeSeriesPlane(points=64))
    b = feed(TimeSeriesPlane(points=64))
    assert a == b
    assert "step/wall_ms" in a and "step/mfu" in a
    assert a["stripe/s0/lane1/seg_bytes"]["values"][-1] == 7000.0
    assert a["stripe/s0/lane2/seg_bytes"]["steps"] == list(range(1, 8))
    assert a["step/staleness_lag"]["values"] == [1.0] * 7


def test_ring_bounded_drop_oldest():
    plane = TimeSeriesPlane(points=16)
    for s in range(1, 41):
        plane.observe(_report(s))
    ser = plane.series()["step/wall_ms"]
    assert len(ser["values"]) == 16
    assert ser["steps"] == list(range(25, 41))  # oldest 24 dropped
    # the ring never grows past cap regardless of write count
    snap = plane.snapshot(tail=8)
    assert snap["points"] == 16 and snap["steps"] == 40
    assert len(snap["series"]["step/wall_ms"]["values"]) == 8


def test_series_count_capped_and_counted():
    plane = TimeSeriesPlane(points=16)
    plane.MAX_SERIES = 3  # instance shadow: force the cap
    plane.observe(_report(1, mfu=0.3, grad_norm=1.0, pull_wait_ms=2.0))
    snap = plane.snapshot()
    assert snap["series_count"] == 3
    assert snap["dropped_series"] > 0
    # a capped name never records later either
    plane.observe(_report(2, mfu=0.3, grad_norm=1.0, pull_wait_ms=2.0))
    assert plane.snapshot()["series_count"] == 3


def test_none_fields_skipped_not_zeroed():
    plane = TimeSeriesPlane(points=16)
    plane.observe(_report(1))                 # mfu None here
    plane.observe(_report(2, mfu=0.5))
    ser = plane.series()
    assert ser["step/mfu"]["steps"] == [2]    # no zero for step 1
    assert ser["step/wall_ms"]["steps"] == [1, 2]


def test_counter_deltas_seeded_and_gauges_sampled():
    reg = MetricsRegistry()
    c = reg.counter("wire/push_bytes")
    g = reg.gauge("wire/inflight")
    plane = TimeSeriesPlane(points=16, registry=reg)
    c.inc(100)
    g.set(3)
    plane.observe(_report(1))   # seeds the counter base — no delta yet
    c.inc(250)
    g.set(5)
    plane.observe(_report(2))
    ser = plane.series()
    assert ser["counter/wire/push_bytes"]["steps"] == [2]
    assert ser["counter/wire/push_bytes"]["values"] == [250.0]
    assert ser["gauge/wire/inflight"]["values"] == [3.0, 5.0]


def test_breaker_trips_one_way(monkeypatch):
    monkeypatch.setattr(ts_mod, "_BREAKER_BUDGET_S", -1.0)
    plane = TimeSeriesPlane(points=16)
    for s in range(1, 4):       # three consecutive over-budget sweeps
        plane.observe(_report(s))
    assert plane.snapshot()["breaker_tripped"] is True
    before = plane.series()["step/wall_ms"]["steps"]
    plane.observe(_report(4))   # tripped: silently a no-op
    assert plane.series()["step/wall_ms"]["steps"] == before


def test_disabled_plane_records_nothing():
    plane = TimeSeriesPlane(points=16, enabled=False)
    plane.observe(_report(1))
    assert plane.series() == {}
    assert plane.dump_jsonl(reason="x") is None


def test_ts_step_fields_manifest_is_live():
    """Runtime half of the byteps-lint _TS_ manifest rule: every
    sampled name is a real StepReport field (a rename would silently
    kill its series)."""
    fields = {f.name for f in dataclasses.fields(StepReport)}
    missing = [n for n in _TS_STEP_FIELDS if n not in fields]
    assert not missing, missing


# --------------------------------------------------------------------- #
# SIGTERM term-hook chain: pinned order
# --------------------------------------------------------------------- #


def test_term_hooks_run_in_pinned_order():
    from byteps_tpu.core import flight

    saved = list(flight._term_hooks)
    del flight._term_hooks[:]
    ran = []
    try:
        # registration order is archive FIRST — the order pin, not
        # registration order, must decide execution order
        flight.add_term_hook(lambda: ran.append("archive"),
                             order=flight.TERM_ORDER_ARCHIVE)
        flight.add_term_hook(lambda: ran.append("timeseries"),
                             order=flight.TERM_ORDER_TIMESERIES)
        flight.add_term_hook(lambda: 1 / 0,
                             order=flight.TERM_ORDER_TIMESERIES)
        flight.run_term_hooks()   # the raising hook must not break it
    finally:
        flight._term_hooks[:] = saved
    assert ran == ["timeseries", "archive"]


# --------------------------------------------------------------------- #
# dump artifact + byteps-top
# --------------------------------------------------------------------- #


def test_dump_jsonl_roundtrip_through_top(tmp_path):
    plane = TimeSeriesPlane(points=16, dump_dir=str(tmp_path))
    for s in range(1, 6):
        plane.observe(_report(s, lane_bytes=((0, 1, 100),)))
    path = plane.dump_jsonl(reason="test")
    assert path and os.path.basename(path).startswith("timeseries-")
    with open(path) as f:
        header = json.loads(f.readline())
    assert header["kind"] == "timeseries" and header["reason"] == "test"
    # the console's post-mortem path: artifact detect -> rehydrate
    snap = top.load_snapshot(file=path)
    ts = snap["timeseries"]
    assert ts["series"]["step/wall_ms"]["values"] == \
        plane.series()["step/wall_ms"]["values"]
    assert ts["series"]["stripe/s0/lane1/seg_bytes"]["steps"] == \
        [1, 2, 3, 4, 5]
    frame = top.build_frame(snap)
    assert "byteps-top" in frame and "stripe/s0/lane1/seg_bytes" in frame


def test_term_dump_lands_in_dump_dir(tmp_path):
    plane = TimeSeriesPlane(points=16, dump_dir=str(tmp_path))
    plane.observe(_report(1))
    plane.term_dump()
    assert os.path.exists(
        os.path.join(str(tmp_path), f"timeseries-{os.getpid()}.jsonl"))


def test_once_frame_schema_pinned():
    """The --once machine-readable frame: CI consumers key on these
    exact top-level names — additions are fine elsewhere, these keys
    must not move."""
    plane = TimeSeriesPlane(points=16)
    plane.observe(_report(1, pull_p95_ms=30.0, compute_ms=5.0))
    snap = {"timeseries": plane.snapshot(),
            "steps": {"last": plane and _report(
                1, pull_p95_ms=30.0, compute_ms=5.0).as_dict()},
            "flight": {"events": 2, "dropped": 0},
            "fleet": {"server": {"0": {}}, "source": "wire"}}
    frame = top.once_frame(snap)
    assert set(frame) == {
        "schema", "steps", "series_count", "breaker_tripped",
        "verdict", "series", "health_flags", "flight", "fleet"}
    assert frame["schema"] == "byteps-top/1"
    assert frame["verdict"] and "-bound" in frame["verdict"]
    assert frame["series"]["step/wall_ms"] == {
        "points": 1, "last": 11.0, "min": 11.0, "max": 11.0}
    assert frame["flight"]["events"] == 2
    assert frame["fleet"]["servers"] == 1


# --------------------------------------------------------------------- #
# per-stripe lane attribution: fields + verdict
# --------------------------------------------------------------------- #


def test_lane_fields_lower_median_two_lanes():
    fields = StepProfiler._lane_fields(
        {(0, 1): 0, (0, 2): 0}, {(0, 1): 800, (0, 2): 200})
    assert fields["lane_count"] == 2
    assert fields["lane_share_max"] == pytest.approx(0.8)
    assert fields["lane_share_min"] == pytest.approx(0.2)
    # LOWER median: a 2-lane pair can still trip the 2x bar
    assert fields["lane_share_median"] == pytest.approx(0.2)
    assert fields["lane_max_id"] == 1 and fields["lane_min_id"] == 2
    assert fields["lane_server"] == 0
    assert set(fields["lane_bytes"]) == {(0, 1, 800), (0, 2, 200)}


def test_lane_imbalance_verdict_trips_and_names_lane():
    r = _report(1, lane_count=2, lane_share_max=0.8,
                lane_share_min=0.2, lane_share_median=0.2,
                lane_max_id=1, lane_min_id=2, lane_server=0)
    msg = classify_step(r)
    assert "LANE-IMBALANCE" in msg
    assert "lane 2 slowest" in msg and "server 0" in msg


def test_lane_imbalance_verdict_quiet_when_balanced():
    r = _report(1, lane_count=2, lane_share_max=0.55,
                lane_share_min=0.45, lane_share_median=0.45,
                lane_max_id=1, lane_min_id=2, lane_server=0)
    assert "LANE-IMBALANCE" not in classify_step(r)
    # single lane can never trip (no pair to skew against)
    r1 = _report(2, lane_count=1, lane_share_max=1.0,
                 lane_share_min=1.0, lane_share_median=1.0,
                 lane_max_id=1, lane_min_id=1, lane_server=0)
    assert "LANE-IMBALANCE" not in classify_step(r1)


def test_stripe_manifest_matches_native_layout():
    """Runtime half of the wire_layout lint: the LOADED .so's field
    manifest must equal the Python parser's mirror."""
    from byteps_tpu.server import (
        _STRIPE_REC_FIELDS, native_stripe_field_names,
    )

    names = native_stripe_field_names()
    if not names:
        pytest.skip("stale .so without the stripe-field manifest ABI")
    assert tuple(names) == _STRIPE_REC_FIELDS


# --------------------------------------------------------------------- #
# integration tier: a real loopback PS run feeds the plane
# --------------------------------------------------------------------- #


@contextlib.contextmanager
def _ps_env(extra_env=None):
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


def _train_rounds(steps=3, hidden=(48, 32), **kw):
    import jax
    import jax.numpy as jnp

    from byteps_tpu.core.state import get_state
    from byteps_tpu.jax.train import make_ps_train_step
    from byteps_tpu.models import mlp

    cfg = mlp.MLPConfig(in_dim=64, hidden=hidden, n_classes=10)
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
    return step, params, opt


def test_e2e_plane_rides_real_train_steps(tmp_path):
    with _ps_env() as bps:
        _train_rounds(steps=3)
        ts = bps.get_timeseries()
        assert ts["enabled"] is True
        assert ts["series"]["step/wall_ms"]["steps"] == [1, 2, 3]
        assert len(ts["series"]["counter/wire/push_requests"]
                   ["values"]) == 2  # first observe seeds the base
        # prefix/tail filters
        sub = bps.get_timeseries(prefix="step/", tail=1)
        assert all(n.startswith("step/") for n in sub["series"])
        assert len(sub["series"]["step/wall_ms"]["values"]) == 1
        # the snapshot section serves the same plane
        snap = bps.get_metrics()
        assert snap["timeseries"]["steps"] == 3
        assert snap["timeseries"]["breaker_tripped"] is False
        # --once over the local snapshot: live verdict, live series
        frame = top.once_frame(snap)
        assert frame["schema"] == "byteps-top/1"
        assert frame["steps"] == 3 and frame["verdict"]


def test_e2e_timeseries_off_disarms_surface():
    with _ps_env({"BYTEPS_TIMESERIES": "0"}) as bps:
        _train_rounds(steps=2)
        assert bps.get_timeseries() == {"enabled": False}
        assert bps.get_metrics()["timeseries"]["enabled"] is False


def test_e2e_stripe_and_staleness_series_engaged():
    """The engaged-proof: striped data conns (IPC off,
    2 lanes, >=2MB leaves) + bounded staleness under the slow-server
    knob must land nonzero per-lane stripe series AND staleness-lag
    series, and STRIPE_PULL must answer over the wire."""
    import jax.numpy as jnp

    from byteps_tpu.core.state import get_state
    from byteps_tpu.jax.train import make_ps_train_step

    env = {"BYTEPS_ENABLE_IPC": "0", "BYTEPS_WIRE_STRIPES": "2",
           "BYTEPS_CROSS_BARRIER": "1", "BYTEPS_STALENESS": "1",
           "BYTEPS_CHAOS_SLOW_SERVER": "5",
           "BYTEPS_LOCAL_SHARD_EXPORT": "0"}
    with _ps_env(env) as bps:
        rng = np.random.RandomState(0)
        params = {f"w{i}": jnp.asarray(
            rng.randn(768, 768), jnp.float32) for i in range(2)}

        def loss_fn(p, b):
            h = jnp.tanh(b @ p["w0"])
            return jnp.mean((h @ p["w1"]) ** 2)

        tx = optax.adam(1e-3)
        opt = tx.init(params)
        batch = jnp.asarray(rng.randn(16, 768), jnp.float32)
        step = make_ps_train_step(loss_fn, tx, get_state().mesh)
        for _ in range(5):
            params, opt, loss = step(params, opt, batch)
        float(loss)
        if hasattr(step, "flush"):
            params, opt = step.flush(params, opt)
        series = bps.get_timeseries()["series"]
        stripe = {n: s for n, s in series.items()
                  if n.startswith("stripe/")}
        assert stripe, sorted(series)
        assert any(sum(s["values"]) > 0 for s in stripe.values())
        assert any(n in series for n in (
            "step/staleness_lag", "step/carry_drain_ms",
            "step/carried_leaves")), sorted(series)
        # the wire half: STRIPE_PULL answers with per-conn records
        client = get_state()._fleet_client()
        assert client is not None
        recs = client.stripe_stats(0, timeout_s=5)
        assert recs and {"conn", "seg_bytes"} <= set(recs[0])
        assert any(r["seg_bytes"] > 0 for r in recs)
