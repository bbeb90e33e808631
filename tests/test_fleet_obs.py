"""Fleet-wide observability plane (PR 12): NTP-style clock-offset
estimation under synthetic skew, the crash flight recorder's ring
semantics, the fused worker+server Chrome trace (clock-aligned,
rid-linked), `bps.get_fleet_metrics()` / the labeled Prometheus fleet
series, classify_step's server attribution, and the slot-layout
runtime manifest — with a SUBPROCESS-server integration tier proving
the whole plane works when the server is genuinely out-of-process
(the black-box case the plane exists for)."""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from byteps_tpu.config import Config
from byteps_tpu.core.flight import FlightRecorder
from byteps_tpu.core.metrics import (
    MetricsRegistry, StepReport, classify_step, prometheus_text,
    server_attribution,
)
from byteps_tpu.utils.tracing import Tracer, estimate_clock_offset

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------------------- #
# clock-offset estimation under synthetic skew (satellite: error bound)
# --------------------------------------------------------------------- #


def _echo(true_offset_ns, send_delay_ns, recv_delay_ns, t0):
    """Synthesize one probe: the server's clock reads client_clock +
    true_offset; the request takes send_delay on the way out and
    recv_delay on the way back."""
    t1 = t0 + send_delay_ns + true_offset_ns
    t2 = t1 + 1000  # 1us of server handling
    t3 = (t2 - true_offset_ns) + recv_delay_ns
    return (t0, t1, t2, t3)


def test_offset_symmetric_delay_is_exact():
    # symmetric path delay: the classic estimate is exact
    off, err = estimate_clock_offset(
        [_echo(5_000_000, 20_000, 20_000, t0=10**9)])
    assert off == 5_000_000
    assert err <= 20_001 + 1  # rtt/2 + handling share


def test_offset_asymmetric_delay_stays_inside_bound():
    # fully asymmetric: all 40us of rtt on one leg. The estimate is
    # biased by (send-recv)/2 but must stay inside the reported bound.
    true = -3_000_000
    off, err = estimate_clock_offset([_echo(true, 40_000, 0, t0=10**9)])
    assert off != true  # asymmetry biases the single estimate...
    assert abs(off - true) <= err, (off, err)  # ...within the bound


def test_offset_jittered_rtt_min_probe_wins():
    # jittered rtt: the min-rtt probe decides; the winning probe's
    # bound covers the truth even though jittery probes are way off
    true = 7_777_000
    rng = np.random.RandomState(0)
    samples = []
    for i in range(20):
        jit = int(rng.randint(0, 500_000))
        d_out = 10_000 + jit + int(rng.randint(0, jit + 1))
        d_back = 10_000 + int(rng.randint(0, jit + 1))
        samples.append(_echo(true, d_out, d_back, t0=10**9 + i * 10**6))
    samples.append(_echo(true, 9_000, 9_000, t0=2 * 10**9))  # clean
    off, err = estimate_clock_offset(samples)
    assert abs(off - true) <= err
    assert err <= 9_002  # the clean probe's envelope, not the jitter's


def test_offset_rejects_empty_and_broken_probes():
    with pytest.raises(ValueError):
        estimate_clock_offset([])
    with pytest.raises(ValueError):
        # negative rtt on every probe (clock stepped mid-echo)
        estimate_clock_offset([(100, 0, 10**9, 50)])


# --------------------------------------------------------------------- #
# flight recorder ring semantics
# --------------------------------------------------------------------- #


def test_flight_ring_bounded_drop_oldest():
    rec = FlightRecorder(capacity=16, enabled=True)
    for i in range(40):
        rec.record("k", key=i)
    evs = rec.events()
    assert len(evs) == 16
    assert [e["key"] for e in evs] == list(range(24, 40))  # oldest gone
    snap = rec.snapshot()
    assert snap["events"] == 40 and snap["dropped"] == 24
    assert snap["capacity"] == 16
    ts = [e["ts_ns"] for e in evs]
    assert ts == sorted(ts)


def test_flight_disabled_is_a_noop():
    rec = FlightRecorder(capacity=16, enabled=False)
    rec.record("k")
    assert rec.events() == []
    assert rec.snapshot()["events"] == 0


def test_flight_dump_merges_and_aligns(tmp_path):
    from byteps_tpu.core import flight as flight_mod
    rec = flight_mod.configure(capacity=64, enabled=True,
                               dump_dir=str(tmp_path))
    rec.record("wire_retry", key=3, detail="attempt=1")
    # a server whose clock runs 1ms AHEAD: its event at local+1ms
    # happened 0.5ms after the worker's, and alignment must order it so
    worker_ts = rec.events()[0]["ts_ns"]
    flight_mod.set_server_collector(lambda: [{
        "server": 0, "offset_ns": 1_000_000,
        "events": [{"ts_ns": worker_ts + 1_000_000 + 500_000,
                    "kind": "chaos_drop", "key": 3, "rid": 9,
                    "sender": 0, "detail": 0}],
    }])
    try:
        path = flight_mod.dump(str(tmp_path / "f.json"), reason="test")
        with open(path) as f:
            doc = json.load(f)
        assert doc["reason"] == "test"
        assert [e["source"] for e in doc["merged"]] == \
            ["worker", "server0"]
        delta = doc["merged"][1]["ts_ns"] - doc["merged"][0]["ts_ns"]
        assert delta == 500_000  # offset removed, causal gap preserved
    finally:
        flight_mod.set_server_collector(None)
        flight_mod.configure(enabled=False)


# --------------------------------------------------------------------- #
# fused trace assembly (synthetic collector: no wire needed)
# --------------------------------------------------------------------- #


def _cfg(tmp_path):
    return Config(trace_on=True, trace_start_step=0, trace_end_step=100,
                  trace_dir=str(tmp_path))


def test_fused_dump_aligns_and_links(tmp_path):
    tr = Tracer(_cfg(tmp_path))
    tr.step()
    # the request's two spans as utils/tracing.py span records them:
    # the send (2 ms, ending now) and, later, the completion; the flow
    # link must start in the send
    t_done = time.perf_counter() + 0.004
    tr.record("bps.wire.done", "t0", t_done, t_done + 0.0001,
              {"key": 7, "rid": 42})
    t_send = time.perf_counter()
    time.sleep(0.002)
    tr.record("bps.wire.send", "t0", t_send, time.perf_counter(),
              {"key": 7, "rid": 42, "server": 0})
    # synthetic server record INSIDE the worker span, on a server clock
    # 2s ahead of ours
    offset = 2 * 10**9
    now = time.monotonic_ns()
    t0 = now - 1_500_000 + offset  # 1.5ms ago, server clock
    rec = {"key": 7, "t0": t0, "t1": t0 + 100_000, "t2": t0 + 300_000,
           "t3": t0 + 900_000, "rid": 42, "sender": 0, "op": 11,
           "kind": 0}
    rep = {"key": 0, "t0": t0 + 1_200_000, "t1": 0, "t2": 0, "t3": 0,
           "rid": 42, "sender": 0, "kind": 1, "op": 7}
    tr.set_server_collector(lambda: [
        {"server": 0, "offset_ns": offset, "err_ns": 1500,
         "records": [rec, rep]}])
    path = tr.dump(str(tmp_path / "fused.json"))
    with open(path) as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    srv = [e for e in evs if e.get("cat") == "server"]
    names = {e["name"] for e in srv}
    assert names == {"recv", "queue-wait", "fold", "reply"}
    # clock alignment: mapped server ts sits inside the worker span
    wspan = next(e for e in evs if e.get("ph") == "X"
                 and e["name"] == "bps.wire.send")
    assert wspan["args"]["rid"] == 42
    recv = next(e for e in srv if e["name"] == "recv")
    assert wspan["ts"] <= recv["ts"] <= wspan["ts"] + wspan["dur"]
    # server rows are their own pid, named via metadata
    metas = {e["pid"]: e["args"]["name"] for e in evs
             if e.get("ph") == "M"}
    assert metas[recv["pid"]] == "bps-server 0"
    assert recv["pid"] != wspan["pid"]
    # rid flow link: a start on the worker span, a finish server-side
    flows = [e for e in evs if e.get("cat") == "bps-rid"]
    assert {e["ph"] for e in flows} == {"s", "f"}
    assert all(e["id"] == 42 for e in flows)
    start = next(e for e in flows if e["ph"] == "s")
    assert wspan["ts"] <= start["ts"] <= wspan["ts"] + wspan["dur"]
    assert doc["metadata"]["rid_flow_links"] == 1


def test_fused_dump_without_servers_still_writes(tmp_path):
    tr = Tracer(_cfg(tmp_path))
    tr.step()
    t0 = time.perf_counter()
    tr.record("bps.wire.push", "t0", t0, t0 + 0.001, {"key": 1})
    path = tr.dump(str(tmp_path / "fused.json"))
    with open(path) as f:
        doc = json.load(f)
    assert doc["metadata"]["rid_flow_links"] == 0


def test_fused_dump_nothing_returns_none(tmp_path):
    tr = Tracer(Config(trace_on=False, trace_dir=str(tmp_path)))
    assert tr.dump(str(tmp_path / "x.json")) is None


# --------------------------------------------------------------------- #
# classify_step server attribution
# --------------------------------------------------------------------- #


def _pull_bound(**kw):
    return StepReport(step=1, wall_ms=90.0, compute_ms=10.0,
                      pull_p95_ms=70.0, pull_wait_ms=5.0, **kw)


def test_classify_without_probe_is_unchanged():
    msg = classify_step(_pull_bound())
    assert msg.startswith("PULL-bound:")
    assert "queue-wait" not in msg


def test_classify_splits_pull_bound_queue_wait():
    r = _pull_bound(pull_total_ms=120.0, server_recv_ms=2.0,
                    server_queue_ms=80.0, server_fold_ms=10.0,
                    server_reply_ms=3.0)
    msg = classify_step(r)
    assert msg.startswith("PULL-bound/queue-wait-bound:")
    assert "server queue-wait 80.0ms" in msg
    sub, queue, fold, wire = server_attribution(r)
    assert sub == "queue-wait-bound"
    assert queue == 80.0 and fold == 10.0
    assert wire == pytest.approx(2.0 + 3.0 + 25.0)  # recv+reply+residual


def test_classify_splits_pull_bound_wire():
    # a throttled transport: the server accounts recv/reply walls and
    # the residual rides the network — all three land on "wire"
    r = _pull_bound(pull_total_ms=200.0, server_recv_ms=60.0,
                    server_queue_ms=5.0, server_fold_ms=8.0,
                    server_reply_ms=50.0)
    msg = classify_step(r)
    assert msg.startswith("PULL-bound/wire-bound:"), msg


def test_classify_splits_pull_bound_fold():
    r = _pull_bound(pull_total_ms=100.0, server_recv_ms=1.0,
                    server_queue_ms=4.0, server_fold_ms=90.0,
                    server_reply_ms=2.0)
    assert classify_step(r).startswith("PULL-bound/fold-bound:")


def test_compute_bound_never_attributes():
    r = StepReport(step=1, wall_ms=50.0, compute_ms=45.0,
                   pull_p95_ms=2.0, pull_total_ms=10.0,
                   server_queue_ms=9.0, server_fold_ms=0.5,
                   server_recv_ms=0.1, server_reply_ms=0.1)
    assert classify_step(r).startswith("COMPUTE-bound:")


# --------------------------------------------------------------------- #
# Prometheus fleet labels (unit: synthetic section)
# --------------------------------------------------------------------- #


def test_prometheus_fleet_labeled_series():
    reg = MetricsRegistry()
    reg.section("fleet", lambda: {
        "workers": 1, "servers": 2, "source": "wire",
        "server": {"0": {"fold_ms": 1.5, "queue_wait_ms": 0.25},
                   "1": {"fold_ms": 4.0, "queue_wait_ms": 2.0}}})
    text = prometheus_text(reg)
    assert 'byteps_fleet_fold_ms{server="0"} 1.5' in text
    assert 'byteps_fleet_fold_ms{server="1"} 4.0' in text
    assert 'byteps_fleet_queue_wait_ms{server="1"} 2.0' in text
    # the scalar fleet keys flatten like any section; strings skipped
    assert "byteps_fleet_servers 2" in text
    assert "wire" not in text.split("byteps_fleet_servers")[0].split(
        "byteps_fleet")[-1]


# --------------------------------------------------------------------- #
# slot-layout manifest: the LOADED .so agrees with the Python mirror
# --------------------------------------------------------------------- #


def test_native_stat_slot_manifest_matches_mirror():
    from byteps_tpu.server import _STAT_SLOTS, native_stat_slot_names
    names = native_stat_slot_names()
    assert names, "stat-name ABI missing from the built .so"
    assert tuple(names) == _STAT_SLOTS


# --------------------------------------------------------------------- #
# integration: SUBPROCESS server — the out-of-process fleet the plane
# exists for (trace fusion within the rtt envelope, wire fleet metrics,
# the labeled Prometheus scrape)
# --------------------------------------------------------------------- #


def _wait_ports(ports, timeout=60):
    import socket

    deadline = time.monotonic() + timeout
    for port in ports:
        while True:
            try:
                with socket.create_connection(("127.0.0.1", port),
                                              timeout=1):
                    break
            except OSError:
                if time.monotonic() > deadline:
                    raise RuntimeError(f"server on :{port} never came up")
                time.sleep(0.2)


@pytest.mark.slow
def test_subprocess_fleet_trace_metrics_prometheus(tmp_path):
    """One subprocess-server run proving the acceptance criteria
    end-to-end: the fused trace contains clock-aligned server-stage
    spans rid-linked to worker spans and landing within the measured
    rtt envelope of their worker parents; get_fleet_metrics() returns
    the out-of-process server's registry section over the wire; and
    the Prometheus endpoint serves it with a server label."""
    from byteps_tpu.core.state import GlobalState
    from byteps_tpu.utils.net import free_port

    port = free_port()
    metrics_port = free_port()
    env_keys = {
        "DMLC_NUM_WORKER": "1", "DMLC_NUM_SERVER": "1",
        "DMLC_PS_ROOT_URI": "127.0.0.1", "DMLC_PS_ROOT_PORT": str(port),
        "BYTEPS_FORCE_DISTRIBUTED": "1",
        "BYTEPS_TRACE_ON": "1", "BYTEPS_TRACE_START_STEP": "0",
        "BYTEPS_TRACE_END_STEP": "1000000000",
        "BYTEPS_TRACE_DIR": str(tmp_path),
        "BYTEPS_METRICS_PORT": str(metrics_port),
    }
    saved = {k: os.environ.get(k) for k in env_keys}
    os.environ.update(env_keys)
    code = (f"from byteps_tpu.server import run_server; "
            f"from byteps_tpu.config import Config; "
            f"run_server({port}, Config(num_workers=1, num_servers=1))")
    proc = subprocess.Popen(
        [sys.executable, "-c", code],
        env={**os.environ, "BYTEPS_TRACE_SAMPLE": "1",
             "PYTHONPATH": REPO + os.pathsep + os.environ.get(
                 "PYTHONPATH", "")})
    bps = None
    try:
        _wait_ports([port])
        GlobalState._instance = None
        import byteps_tpu as bps
        bps.init()
        from byteps_tpu.core.state import get_state
        state = get_state()

        rng = np.random.RandomState(2)
        grads = [rng.randn(8192).astype(np.float32) for _ in range(4)]
        for r in range(3):
            hs = [bps.push_pull_async(g * (r + 1), f"fo{i}",
                                      average=False)
                  for i, g in enumerate(grads)]
            for h, g in zip(hs, grads):
                np.testing.assert_array_equal(
                    np.array(bps.synchronize(h, timeout=60)),
                    g * (r + 1))

        # -- fleet metrics over the wire --------------------------------
        fm = bps.get_fleet_metrics()
        assert fm["fleet"]["source"] == "wire"
        srv0 = fm["fleet"]["server"]["0"]
        assert srv0["fold_count"] > 0
        assert srv0["trace_records"] > 0, "server never sampled a span"

        # -- Prometheus: the same fleet, labeled ------------------------
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{metrics_port}/metrics",
            timeout=10).read().decode()
        assert 'byteps_fleet_fold_count{server="0"}' in body, \
            body[:2000]

        # -- fused trace: aligned + rid-linked + inside the envelope ----
        probe = state.ps_client.clock_probe(0)
        assert probe is not None
        _off, err_ns = probe
        path = bps.dump_fused_trace(str(tmp_path / "fused.json"))
        with open(path) as f:
            doc = json.load(f)
        evs = doc["traceEvents"]
        assert doc["metadata"]["rid_flow_links"] > 0, \
            "no rid flow links fused"
        wspans = {e["args"]["rid"]: e for e in evs
                  if e.get("ph") == "X"
                  and isinstance(e.get("args"), dict)
                  and e["args"].get("rid")}
        srv_spans = [e for e in evs if e.get("cat") == "server"
                     and e.get("ph") == "X"]
        assert srv_spans, "no server stage spans in the fused trace"
        # every rid-matched server span must land within the measured
        # rtt envelope of its worker parent: the server's work happens
        # strictly inside the worker's submit->completion window, so
        # after clock alignment only the offset error + a small
        # bookkeeping slack can leak past the edges
        margin_us = err_ns / 1e3 + 2000.0
        matched = 0
        for e in srv_spans:
            w = wspans.get(e["args"]["rid"])
            if w is None:
                continue
            matched += 1
            assert e["ts"] >= w["ts"] - margin_us, (e, w, err_ns)
            assert e["ts"] + e["dur"] <= w["ts"] + w["dur"] + margin_us, \
                (e, w, err_ns)
        assert matched > 0, "no server span matched a worker rid"
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


# --------------------------------------------------------------------- #
# in-process: per-step server attribution lands on real StepReports
# --------------------------------------------------------------------- #


def test_step_report_carries_server_attribution():
    """A real loopback PS round under the profiler: the StepReport's
    server-attribution fields are populated from the in-process fleet
    probe (deltas over the step), and classify_step accepts them."""
    from byteps_tpu.core.state import GlobalState
    from byteps_tpu.server import run_server as _run
    from byteps_tpu.utils.net import free_port

    port = free_port()
    env_keys = {
        "DMLC_NUM_WORKER": "1", "DMLC_NUM_SERVER": "1",
        "DMLC_PS_ROOT_URI": "127.0.0.1", "DMLC_PS_ROOT_PORT": str(port),
        "BYTEPS_FORCE_DISTRIBUTED": "1",
    }
    saved = {k: os.environ.get(k) for k in env_keys}
    os.environ.update(env_keys)
    server = threading.Thread(
        target=_run, args=(port, Config(num_workers=1, num_servers=1)),
        daemon=True)
    server.start()
    GlobalState._instance = None
    bps = None
    try:
        import byteps_tpu as bps
        bps.init()
        from byteps_tpu.core.state import get_state
        state = get_state()
        g = np.random.RandomState(0).randn(65536).astype(np.float32)
        for r in range(2):
            b = state.profiler.begin_step()
            out = bps.synchronize(
                bps.push_pull_async(g, "attr0", average=False),
                timeout=60)
            np.testing.assert_array_equal(out, g)
            rep = state.profiler.end_step(b)
        assert rep is not None
        # the in-process probe ran: fields are numbers, not None
        assert rep.server_fold_ms is not None
        assert rep.server_queue_ms is not None
        assert rep.pull_total_ms is not None
        assert rep.server_fold_ms >= 0.0
        classify_step(rep)  # must not raise with the new fields
    finally:
        try:
            if bps is not None:
                bps.shutdown()
        except Exception:
            pass
        GlobalState._instance = None
        server.join(timeout=15)
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
