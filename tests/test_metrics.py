"""Unified metrics registry + per-step pipeline profiler
(core/metrics.py): counter/gauge/histogram correctness under concurrent
writers, StepReport assembly for a real make_ps_train_step step (stream
export on and off), Prometheus text exposition, the stall-detector
classification on synthetic PULL-bound vs COMPUTE-bound reports, the
docs-schema liveness guard, the frozen-registry (BYTEPS_METRICS=0)
behavior, and the MetricAverageCallback shared-deadline fix."""

import contextlib
import os
import re
import threading

import numpy as np
import optax
import pytest

from byteps_tpu.config import Config
from byteps_tpu.core.metrics import (
    Histogram, MetricsRegistry, StepProfiler, StepReport, classify_step,
    prometheus_text,
)
from byteps_tpu.server import run_server

_PORT = [24100]


# --------------------------------------------------------------------- #
# unit tier: instruments under concurrent writers
# --------------------------------------------------------------------- #


def _hammer(n_threads, fn):
    threads = [threading.Thread(target=fn) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()


def test_counter_concurrent_writers_exact():
    reg = MetricsRegistry()
    c = reg.counter("c")
    _hammer(8, lambda: [c.inc() for _ in range(5000)])
    assert c.value == 8 * 5000


def test_histogram_concurrent_writers_consistent():
    reg = MetricsRegistry()
    h = reg.histogram("h")
    _hammer(8, lambda: [h.record(v) for v in (3, 100, 5000, 1 << 20)])
    s = h.snapshot()
    assert s["count"] == 8 * 4
    assert sum(s["buckets"]) == s["count"]
    assert s["min"] == 3 and s["max"] == 1 << 20
    assert s["sum"] == 8 * (3 + 100 + 5000 + (1 << 20))


def test_gauge_set_max_and_lazy_fn():
    reg = MetricsRegistry()
    g = reg.gauge("g")
    g.set_max(5)
    g.set_max(3)
    assert g.value == 5
    lazy = reg.gauge("lazy")
    lazy.set_fn(lambda: 42)
    assert lazy.value == 42
    assert reg.snapshot()["gauges"]["lazy"] == 42


def test_histogram_percentiles_log2_bounds():
    h = Histogram("h")
    for _ in range(99):
        h.record(10)     # bucket 4, upper bound 15
    h.record(100000)     # bucket 17, upper bound 131071
    assert h.percentile(0.5) == 15.0
    assert h.percentile(0.99) == 15.0
    s = h.snapshot()
    assert s["p50"] == 15.0
    assert s["p99"] == 15.0
    # the outlier decides the extreme tail (100000 -> bucket 17)
    assert h.percentile(1.0) == (1 << 17) - 1


def test_registry_get_or_create_is_stable():
    reg = MetricsRegistry()
    assert reg.counter("x") is reg.counter("x")
    assert reg.histogram("y") is reg.histogram("y")
    assert reg.gauge("z") is reg.gauge("z")


def test_disabled_registry_freezes_instruments():
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("c")
    h = reg.histogram("h")
    g = reg.gauge("g")
    c.inc(10)
    h.record(100)
    g.set(5)
    assert c.value == 0
    assert h.snapshot()["count"] == 0
    assert g.value == 0
    # the snapshot surface itself still works
    snap = reg.snapshot()
    assert snap["enabled"] is False and "counters" in snap


# --------------------------------------------------------------------- #
# Prometheus exposition
# --------------------------------------------------------------------- #


def test_prometheus_text_format():
    reg = MetricsRegistry()
    reg.counter("wire/push_bytes").inc(128)
    reg.gauge("scheduler/queue_depth").set(5)
    h = reg.histogram("scheduler/pull_us/dense")
    h.record(12000)
    h.record(41000)
    reg.section("arena", lambda: {"slots_live": 3, "enabled": True})
    txt = prometheus_text(reg)
    assert "# TYPE byteps_wire_push_bytes counter\n" \
           "byteps_wire_push_bytes 128" in txt
    assert "# TYPE byteps_scheduler_queue_depth gauge" in txt
    assert "# TYPE byteps_scheduler_pull_us_dense histogram" in txt
    # cumulative buckets end at +Inf == count
    assert 'byteps_scheduler_pull_us_dense_bucket{le="+Inf"} 2' in txt
    assert "byteps_scheduler_pull_us_dense_count 2" in txt
    assert "byteps_scheduler_pull_us_dense_sum 53000" in txt
    # sections flatten to gauges; bools become 0/1
    assert "byteps_arena_slots_live 3" in txt
    assert "byteps_arena_enabled 1" in txt
    # every non-comment line is "name value" with a sane metric name
    for line in txt.strip().splitlines():
        if line.startswith("#"):
            continue
        name, _, value = line.partition(" ")
        assert re.fullmatch(r"[a-zA-Z_][a-zA-Z0-9_]*(\{[^}]*\})?", name), line
        float(value)


def test_prometheus_http_endpoint():
    import json
    import urllib.request

    from byteps_tpu.core.metrics import start_http_server

    reg = MetricsRegistry()
    reg.counter("c").inc(7)
    srv = start_http_server(reg, 0)  # ephemeral port
    try:
        port = srv.server_address[1]
        txt = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
        assert "byteps_c 7" in txt
        snap = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/", timeout=10).read().decode())
        assert snap["counters"]["c"] == 7
    finally:
        srv.shutdown()
        srv.server_close()


# --------------------------------------------------------------------- #
# stall detector
# --------------------------------------------------------------------- #


def test_classify_pull_bound():
    r = StepReport(step=1, wall_ms=60, compute_ms=12.0, pull_p95_ms=41.0,
                   push_p95_ms=2.0, queue_depth_peak=37)
    msg = classify_step(r)
    assert msg.startswith("PULL-bound")
    assert "pull p95 41.0ms" in msg and "compute 12.0ms" in msg
    assert "queue depth peaked 37" in msg


def test_classify_compute_bound():
    r = StepReport(step=2, wall_ms=60, compute_ms=50.0, pull_p95_ms=4.0,
                   push_p95_ms=2.0)
    msg = classify_step(r)
    assert msg.startswith("COMPUTE-bound")
    assert "compute wall 50.0ms" in msg


def test_classify_push_and_update_bound():
    assert classify_step(StepReport(
        compute_ms=1.0, push_p95_ms=30.0)).startswith("PUSH-bound")
    assert classify_step(StepReport(
        compute_ms=1.0, h2d_update_p95_ms=9.0)).startswith("UPDATE-bound")


def test_profiler_ring_and_stall_counters():
    p = StepProfiler(window=2)
    for i in range(3):
        b = p.begin_step()
        b.stage_sample("PULL", 0.010 * (i + 1))
        b.queue_depth(i)
        b.credit_stall()
        b.mark("export_done")
        b.mark("drain_done")
        p.end_step(b, ttfp_ms=1.0, leaves=3)
    reports = p.reports()
    assert len(reports) == 2, "ring must cap at the window"
    assert [r.step for r in reports] == [2, 3]
    last = reports[-1]
    assert last.credit_stalls == 1 and last.queue_depth_peak == 2
    assert last.pull_p95_ms == pytest.approx(30.0, rel=0.01)
    snap = p.snapshot()
    assert snap["count"] == 2 and snap["last"]["step"] == 3
    assert "last_diagnosis" in snap


def test_profiler_disabled_returns_none():
    p = StepProfiler(enabled=False)
    assert p.begin_step() is None
    assert p.end_step(None) is None
    assert p.reports() == []


# --------------------------------------------------------------------- #
# integration tier: a real PS train step feeds the whole plane
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


def test_step_report_assembly_real_step():
    # fusion off so leaves ride their own keys
    with _ps_env({"BYTEPS_FUSION_BYTES": "0"}) as bps:
        _train_rounds(steps=3)
        m = bps.get_metrics()
        steps = m["steps"]
        assert steps["count"] == 3
        last = steps["last"]
        assert last["step"] == 3
        assert last["wall_ms"] > 0
        assert last["compute_ms"] > 0
        assert last["drain_ms"] >= 0
        assert last["ttfp_ms"] is not None and last["ttfp_ms"] > 0
        # mlp: 3 layers x (w, b), every one an output of the backward
        assert last["streamed_leaves"] == 0
        assert last["fallback_leaves"] == 6
        # the scheduler fed per-stage samples for this step
        assert last["pull_p95_ms"] is not None
        assert last["push_p95_ms"] is not None
        assert last["queue_depth_peak"] >= 1
        assert "last_diagnosis" in steps and "-bound" in \
            steps["last_diagnosis"]
        # wire layer counted the traffic (fused default: one PUSHPULL
        # message per partition round trip instead of a push+pull pair)
        assert (m["counters"]["wire/push_requests"]
                + m["counters"]["wire/pushpull_requests"]) > 0
        assert m["counters"]["wire/pull_bytes"] > 0
        assert m["counters"]["wire/errors"] == 0
        # registry byte total mirrors the telemetry surface
        assert m["counters"]["pushpull/bytes_total"] > 0
        # per-stage histograms populated for the dense class
        assert m["histograms"]["scheduler/pull_us/dense"]["count"] > 0
        assert m["histograms"]["step/h2d_update_us"]["count"] > 0
        # reports surface, oldest first
        reports = bps.get_step_reports()
        assert [r["step"] for r in reports] == [1, 2, 3]


def test_metrics_off_freezes_but_snapshot_works():
    with _ps_env({"BYTEPS_METRICS": "0"}) as bps:
        _train_rounds(steps=2)
        m = bps.get_metrics()
        assert m["enabled"] is False
        assert m["steps"]["count"] == 0, "profiler must not assemble"
        assert m["counters"].get("wire/push_requests", 0) == 0
        # the deprecated alias still reads the live arena counters
        assert bps.get_arena_stats()["slots_live"] >= 0


def test_arena_stats_alias_matches_metrics_section():
    with _ps_env() as bps:
        _train_rounds(steps=2)
        alias = bps.get_arena_stats()
        section = bps.get_metrics()["arena"]
        assert alias == section


def test_compression_ratio_counters():
    with _ps_env() as bps:
        _train_rounds(steps=2, compression={"compressor": "onebit"},
                      min_compress_bytes=1, device_compress=False)
        m = bps.get_metrics()
        pre = m["counters"]["compress/bytes_pre"]
        post = m["counters"]["compress/bytes_post"]
        assert pre > 0 and 0 < post < pre, (pre, post)
        assert m["histograms"][
            "scheduler/compress_us/compressed"]["count"] > 0


def test_metrics_port_serves_through_init_lifecycle():
    import urllib.request

    from byteps_tpu.utils.net import free_port

    port = free_port()
    with _ps_env({"BYTEPS_METRICS_PORT": str(port)}) as bps:
        _train_rounds(steps=1)
        txt = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
        assert "byteps_wire_push_requests" in txt
    # shutdown() stopped the server
    with pytest.raises(Exception):
        urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics",
                               timeout=2)


# --------------------------------------------------------------------- #
# docs-schema liveness guard (the docs can't rot silently)
# --------------------------------------------------------------------- #


def _documented_schema():
    doc = os.path.join(os.path.dirname(__file__), "..", "docs",
                       "observability.md")
    with open(doc) as f:
        text = f.read()
    m = re.search(r"```schema\n(.*?)```", text, re.S)
    assert m, "docs/observability.md lost its ```schema block"
    return [ln.strip() for ln in m.group(1).splitlines() if ln.strip()]


def _resolve(snap, path):
    parts = path.split(".")
    cur = snap
    for i, p in enumerate(parts):
        if isinstance(cur, dict) and p in cur:
            cur = cur[p]
            continue
        rest = ".".join(parts[i:])
        assert isinstance(cur, dict) and rest in cur, \
            f"documented key {path!r} missing from get_metrics()"
        return cur[rest]
    return cur


def test_documented_schema_is_live():
    keys = _documented_schema()
    assert len(keys) > 30, "schema block suspiciously small"
    with _ps_env() as bps:
        _train_rounds(steps=2)
        snap = bps.get_metrics()
        for path in keys:
            _resolve(snap, path)


# --------------------------------------------------------------------- #
# MetricAverageCallback shared deadline (satellite fix)
# --------------------------------------------------------------------- #


def test_metric_average_shared_deadline(bps, monkeypatch):
    """The PS-tier drain must spend ONE shared BYTEPS_METRIC_TIMEOUT_S
    across all metrics, not a full timeout each: each synchronize gets
    the REMAINING time, so the captured timeouts strictly decrease."""
    import time

    import byteps_tpu as bps_mod
    from byteps_tpu import callbacks as cbs
    from byteps_tpu.core.state import get_state

    monkeypatch.setattr(get_state(), "scheduler", object())
    monkeypatch.setenv("BYTEPS_METRIC_TIMEOUT_S", "5")
    handles = iter(range(100))
    monkeypatch.setattr(bps_mod, "push_pull_async",
                        lambda v, name, average=True: next(handles))
    seen = []

    def fake_sync(h, timeout=None):
        seen.append(timeout)
        time.sleep(0.05)  # each wait consumes shared budget
        return np.asarray([2.0], np.float32)

    monkeypatch.setattr(bps_mod, "synchronize", fake_sync)
    state = {"metrics": {"a": 1.0, "b": 2.0, "c": 3.0}}
    cbs.MetricAverageCallback().on_epoch_end(0, state)
    assert state["metrics"] == {"a": 2.0, "b": 2.0, "c": 2.0}
    assert len(seen) == 3
    assert all(t is not None and t <= 5.0 for t in seen)
    assert seen[0] > seen[1] > seen[2], \
        f"timeouts must shrink toward the shared deadline: {seen}"
