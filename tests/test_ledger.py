"""Step efficiency ledger (core/ledger.py): cost-analysis extraction
with the no-backend fallback, overlap-fraction math on synthetic span
timelines, the device-kind peak table with env override, archive JSONL
round-trip + SIGTERM flush, and the loopback PS end-to-end: non-null
``mfu``/``overlap_frac``/``wire_efficiency`` in ``get_step_reports()``
with the efficiency verdict in ``classify_step``."""

import contextlib
import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import optax
import pytest

from byteps_tpu.config import Config
from byteps_tpu.core import flight
from byteps_tpu.core.ledger import (
    EfficiencyLedger, PerfArchive, detect_peak, extract_cost, jit_cost,
    overlap_fraction, roofline_fraction,
)
from byteps_tpu.core.metrics import MetricsRegistry, StepReport, \
    classify_step
from byteps_tpu.server import run_server

REPO = os.path.join(os.path.dirname(__file__), "..")
_PORT = [24700]


# --------------------------------------------------------------------- #
# peak table
# --------------------------------------------------------------------- #


def test_peak_table_device_kinds():
    for kind, want_f, want_bw in (("TPU v5 lite", 197e12, 819.0),
                                  ("TPU v5e", 197e12, 819.0),
                                  ("TPU v5p", 459e12, 2765.0),
                                  ("TPU v4", 275e12, 1228.0)):
        f, bw, src = detect_peak(kind, env={})
        assert (f, bw, src) == (want_f, want_bw, "table"), kind
    # "v5 lite" must win over the shorter "v5p"-style patterns — the
    # longest-substring-first contract
    f, _, _ = detect_peak("tpu V5 LITE", env={})
    assert f == 197e12


def test_peak_cpu_nominal():
    """The CPU row exists for the CPU-mesh tests' pricing path only; the
    unknown-kind error is pinned in tests/test_chip_smoke.py."""
    f, bw, src = detect_peak("cpu", env={})
    assert src == "cpu-nominal"
    assert f == (os.cpu_count() or 1) * 5e10


def test_peak_env_override_wins():
    f, bw, src = detect_peak("TPU v4",
                             env={"BYTEPS_PEAK_FLOPS": "123e12",
                                  "BYTEPS_PEAK_BW_GBPS": "555"})
    assert (f, bw, src) == (123e12, 555.0, "env")
    # garbage override degrades to the table, never raises
    f, _, src = detect_peak("TPU v4", env={"BYTEPS_PEAK_FLOPS": "nan?"})
    assert (f, src) == (275e12, "table")


# --------------------------------------------------------------------- #
# cost-analysis extraction (version tolerance)
# --------------------------------------------------------------------- #


def test_extract_cost_real_jit():
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda x: (x @ x).sum())
    c = jit_cost(fn, jnp.ones((64, 64), jnp.float32))
    assert c is not None and c["flops"] > 2 * 64 ** 3 * 0.9
    assert c.get("bytes_accessed", 0) > 0


class _Lowered:
    def __init__(self, ca):
        self._ca = ca

    def cost_analysis(self):
        if isinstance(self._ca, Exception):
            raise self._ca
        return self._ca


def test_extract_cost_shapes_and_failures():
    # the installed jax returns one dict, keyed "bytes accessed"
    c = extract_cost(_Lowered({"flops": 10.0, "bytes accessed": 4.0}))
    assert c == {"flops": 10.0, "bytes_accessed": 4.0}
    # a non-dict result (no list shape on this jax) is not a cost
    assert extract_cost(_Lowered([{"flops": 10.0}])) is None
    # dict without usable keys -> None, not {}
    assert extract_cost(_Lowered({"transcendentals": 3.0})) is None
    # raising backend -> None
    assert extract_cost(_Lowered(RuntimeError("no cost model"))) is None
    # NaN / zero placeholders are not costs
    assert extract_cost(_Lowered({"flops": float("nan")})) is None
    assert extract_cost(_Lowered({"flops": 0.0})) is None
    # non-lowerable callable -> None (the no-backend fallback path)
    assert jit_cost(object()) is None


# --------------------------------------------------------------------- #
# overlap / roofline math
# --------------------------------------------------------------------- #


def test_overlap_fraction_synthetic_timelines():
    # all wire inside compute -> fully hidden
    assert overlap_fraction([(0.1, 0.2), (0.3, 0.5)], 1.0) == 1.0
    # all wire after compute -> nothing hidden
    assert overlap_fraction([(2.0, 3.0)], 1.0) == 0.0
    # half the (single) span under compute
    assert overlap_fraction([(0.5, 1.5)], 1.0) == pytest.approx(0.5)
    # overlapping spans union-merge: [0,2] ∪ [1,3] = [0,3], 2/3 hidden
    assert overlap_fraction([(0.0, 2.0), (1.0, 3.0)], 2.0) == \
        pytest.approx(2.0 / 3.0)
    # no spans / degenerate spans -> None, never 0
    assert overlap_fraction([], 1.0) is None
    assert overlap_fraction([(1.0, 1.0)], 1.0) is None


def test_roofline_fraction():
    # intensity 10 FLOP/B x 100 GB/s = 1e12 attainable of 2e12 peak
    assert roofline_fraction(1000.0, 100.0, 2e12, 100.0) == \
        pytest.approx(0.5)
    # compute-bound shape caps at 1.0
    assert roofline_fraction(1e9, 1.0, 1e12, 100.0) == 1.0
    assert roofline_fraction(None, 100.0, 1e12, 100.0) is None
    assert roofline_fraction(1000.0, None, 1e12, 100.0) is None


# --------------------------------------------------------------------- #
# ledger pricing (unit: injected counters, no PS)
# --------------------------------------------------------------------- #


def _ledger(metrics=None, **cfg_kw):
    return EfficiencyLedger(Config(**cfg_kw), metrics)


def test_step_efficiency_fields():
    reg = MetricsRegistry()
    led = _ledger(reg, peak_flops=1e9, peak_bw_gbps=100.0)
    led.register_step_cost(flops=5e6, bytes_accessed=1e6,
                           ideal_wire_bytes=1000, source="xla")
    base = led.wire_bytes_total()
    reg.counter("wire/push_bytes").inc(1000)
    reg.counter("wire/pull_bytes").inc(1000)
    eff = led.step_efficiency(wall_s=0.01, compute_end_s=0.004,
                              wire_spans=[(0.002, 0.006)],
                              wire_base=base)
    assert eff["achieved_flops"] == pytest.approx(5e8)
    assert eff["mfu"] == pytest.approx(0.5)
    assert eff["overlap_frac"] == pytest.approx(0.5)
    assert eff["wire_bytes"] == 2000
    assert eff["wire_efficiency"] == pytest.approx(0.5)
    # intensity 5 FLOP/B x 100 GB/s = 5e11 >> 1e9 peak -> roofline 1.0
    assert eff["roofline_frac"] == 1.0
    # a report carrying these fields names the efficiency verdict
    r = StepReport(step=1, wall_ms=10.0, compute_ms=4.0,
                   mfu=eff["mfu"], roofline_frac=eff["roofline_frac"],
                   overlap_frac=eff["overlap_frac"],
                   wire_efficiency=eff["wire_efficiency"])
    msg = classify_step(r)
    assert "MFU 0.50 of 1.00 roofline" in msg
    assert "overlap 50%" in msg and "wire 2.0x ideal" in msg


def test_ledger_disabled_prices_nothing():
    led = _ledger(MetricsRegistry(), ledger=False)
    assert led.enabled is False
    led.register_step_cost(flops=1e6, ideal_wire_bytes=10)
    assert led.step_efficiency(0.01, 0.004, [(0.0, 0.01)], 0) == {}
    assert led.snapshot()["enabled"] is False


def test_missing_cost_model_degrades_per_field():
    """No cost analysis: MFU stays None but overlap/wire still price
    (the acceptance's 'never silently 0' contract)."""
    reg = MetricsRegistry()
    led = _ledger(reg)
    led.register_step_cost(flops=None, ideal_wire_bytes=100,
                           source="none")
    reg.counter("wire/push_bytes").inc(100)
    reg.counter("wire/pull_bytes").inc(100)
    eff = led.step_efficiency(0.01, 0.004, [(0.0, 0.002)], 0)
    assert "achieved_flops" not in eff and "mfu" not in eff
    assert eff["overlap_frac"] == 1.0
    assert eff["wire_efficiency"] == pytest.approx(0.5)


def test_monolithic_round_prices_no_overlap():
    """Device-compressed tier: export_done lands AFTER the wire, so
    spans would fabricate overlap_frac == 1.0 — a monolithic builder
    must price overlap as None while MFU/wire figures still land."""
    from byteps_tpu.core.metrics import StepProfiler

    reg = MetricsRegistry()
    led = _ledger(reg, peak_flops=1e9)
    led.register_step_cost(flops=1e6, ideal_wire_bytes=100,
                           source="xla")
    prof = StepProfiler(ledger=led)
    b = prof.begin_step()
    b.wire_span(b.t0 + 0.001, b.t0 + 0.002)
    b.monolithic = True
    b.mark("export_done")
    reg.counter("wire/push_bytes").inc(100)
    reg.counter("wire/pull_bytes").inc(100)
    r = prof.end_step(b)
    assert r.overlap_frac is None
    assert r.mfu is not None and r.wire_efficiency is not None
    # the same spans WITHOUT the monolithic latch price normally
    b2 = prof.begin_step()
    b2.wire_span(b2.t0 + 0.001, b2.t0 + 0.002)
    b2.mark("export_done")
    assert prof.end_step(b2).overlap_frac is not None


# --------------------------------------------------------------------- #
# efficiency_drop flight events
# --------------------------------------------------------------------- #


def test_efficiency_drop_flight_event():
    flight.configure(capacity=64, enabled=True)
    try:
        reg = MetricsRegistry()
        led = _ledger(reg, eff_drop_frac=0.25, eff_drop_window=8)
        # healthy plateau: window fills, nothing fires
        for i in range(6):
            led.on_step(StepReport(step=i + 1, mfu=0.40,
                                   overlap_frac=0.6))
        assert not [e for e in flight.get_recorder().events()
                    if e["kind"] == "efficiency_drop"]
        # a >25% cliff on mfu fires exactly one event for that metric
        led.on_step(StepReport(step=7, mfu=0.25, overlap_frac=0.6))
        drops = [e for e in flight.get_recorder().events()
                 if e["kind"] == "efficiency_drop"]
        assert len(drops) == 1 and "mfu" in drops[0]["detail"]
        assert drops[0]["key"] == 7  # the step number rides the event
        assert reg.counter("ledger/efficiency_drops").value == 1
        # warmup can't fire: < 4 samples in a fresh window
        led2 = _ledger(reg, eff_drop_frac=0.25, eff_drop_window=8)
        for i in range(3):
            led2.on_step(StepReport(step=i + 1, mfu=0.5))
        led2.on_step(StepReport(step=4, mfu=0.01))
        drops = [e for e in flight.get_recorder().events()
                 if e["kind"] == "efficiency_drop"]
        assert len(drops) == 1  # still only the first ledger's event
    finally:
        flight.configure(enabled=False)


# --------------------------------------------------------------------- #
# perf archive
# --------------------------------------------------------------------- #


def test_archive_jsonl_roundtrip(tmp_path):
    arch = PerfArchive(str(tmp_path), flush_steps=4)
    for i in range(10):
        arch.append({"step": i + 1, "wall_ms": 1.5 * (i + 1),
                     "mfu": 0.3})
    # buffered I/O: two flush boundaries passed, the tail is in memory
    with open(arch.path) as f:
        assert len(f.read().strip().splitlines()) == 8
    arch.flush()
    with open(arch.path) as f:
        lines = [json.loads(ln) for ln in f.read().strip().splitlines()]
    assert [r["step"] for r in lines] == list(range(1, 11))
    assert lines[4]["wall_ms"] == pytest.approx(7.5)
    assert arch.stats() == {"records": 10, "dropped": 0}


def test_archive_sigterm_flush(tmp_path):
    """SIGTERM must flush the buffered tail alongside the flight dump
    (the flight handler's term hooks). Run in a subprocess so the real
    signal path — handler, hooks, chain to default — is exercised; the
    script never imports jax, so this stays fast."""
    script = f"""
import os, signal, sys, time
sys.path.insert(0, {REPO!r})
from byteps_tpu.core import flight
from byteps_tpu.core.ledger import PerfArchive
flight.configure(capacity=16, enabled=True, dump_dir={str(tmp_path)!r})
flight.install_signal_handler()
arch = PerfArchive({str(tmp_path)!r}, flush_steps=1000)  # never auto
flight.add_term_hook(lambda: arch.flush(lock_timeout=1.0))  # prod shape
for i in range(7):
    arch.append({{"step": i + 1, "mfu": 0.4}})
print("READY", arch.path, flush=True)
time.sleep(30)
"""
    proc = subprocess.Popen([sys.executable, "-c", script],
                            stdout=subprocess.PIPE, text=True)
    try:
        line = proc.stdout.readline().split()
        assert line and line[0] == "READY"
        path = line[1]
        assert not os.path.exists(path) or \
            os.path.getsize(path) == 0  # nothing flushed yet
        proc.send_signal(signal.SIGTERM)
        proc.wait(timeout=20)
    finally:
        if proc.poll() is None:
            proc.kill()
    with open(path) as f:
        recs = [json.loads(ln) for ln in f.read().strip().splitlines()]
    assert [r["step"] for r in recs] == list(range(1, 8))


# --------------------------------------------------------------------- #
# loopback PS end-to-end (the acceptance run)
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


def test_loopback_ledger_end_to_end(tmp_path):
    """The acceptance run: a loopback PS train carries non-null
    ``mfu``/``overlap_frac``/``wire_efficiency``, classify_step emits
    the efficiency verdict, get_ledger() names the cost source, and
    the perf archive holds one record per step after shutdown."""
    arch_dir = str(tmp_path / "perf")
    with _ps_env({"BYTEPS_PERF_ARCHIVE": arch_dir}) as bps:
        _train_rounds(steps=4)
        reports = bps.get_step_reports()
        assert len(reports) == 4
        last = reports[-1]
        assert last["mfu"] is not None and last["mfu"] > 0
        assert last["overlap_frac"] is not None
        assert 0.0 <= last["overlap_frac"] <= 1.0
        assert last["wire_efficiency"] is not None
        assert last["wire_efficiency"] > 0
        assert last["achieved_flops"] > 0
        assert last["wire_bytes"] > 0
        # ideal = every leaf once each way; actual dense wire carries
        # at least that, so efficiency can't exceed ~1 on this run
        assert last["wire_efficiency"] <= 1.01
        diag = bps.get_metrics()["steps"]["last_diagnosis"]
        assert "MFU" in diag and "overlap" in diag and "ideal" in diag
        led = bps.get_ledger()
        assert led["enabled"] is True and led["source"] == "xla"
        assert led["model_flops"] > 0 and led["ideal_wire_bytes"] > 0
        assert led["peak_flops"] > 0
        assert led["archive_records"] == 4
        # instrument mirror: last-step gauges + Prometheus face
        m = bps.get_metrics()
        assert m["gauges"]["ledger/mfu"] == pytest.approx(last["mfu"])
        arch_path = led["archive_path"]
    # shutdown flushed the tail
    with open(arch_path) as f:
        recs = [json.loads(ln) for ln in f.read().strip().splitlines()]
    assert [r["step"] for r in recs] == [1, 2, 3, 4]
    assert recs[-1]["mfu"] is not None and recs[-1]["wall_ms"] > 0


def test_ledger_re_engages_after_resume():
    """suspend/resume replaces state.ledger; a step closure built
    BEFORE the cycle must re-register its cost model on the fresh
    instance (the cache is keyed on the ledger identity, not just the
    plan) — found by the verify drive: post-resume MFU read None."""
    import jax
    import jax.numpy as jnp

    from byteps_tpu.core.state import get_state
    from byteps_tpu.jax.train import make_ps_train_step
    from byteps_tpu.models import mlp

    with _ps_env() as bps:
        cfg = mlp.MLPConfig(in_dim=64, hidden=(48, 32), n_classes=10)
        params = mlp.init_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.RandomState(0)
        batch = {"x": jnp.asarray(rng.rand(32, 64), jnp.float32),
                 "y": jnp.asarray(rng.randint(0, 10, 32), jnp.int32)}
        tx = optax.adam(1e-2)
        opt = tx.init(params)
        step = make_ps_train_step(
            lambda p, b: mlp.loss_fn(p, b, cfg), tx, get_state().mesh)
        for _ in range(2):
            params, opt, _ = step(params, opt, batch)
        assert bps.get_step_reports()[-1]["mfu"] is not None
        bps.suspend()
        bps.resume(num_workers=1, num_servers=1)
        for _ in range(2):
            params, opt, _ = step(params, opt, batch)
        last = bps.get_step_reports()[-1]
        assert last["mfu"] is not None
        assert last["wire_efficiency"] is not None


def test_loopback_ledger_off_leaves_fields_none():
    with _ps_env({"BYTEPS_LEDGER": "0"}) as bps:
        _train_rounds(steps=2)
        last = bps.get_step_reports()[-1]
        assert last["mfu"] is None
        assert last["overlap_frac"] is None
        assert last["wire_efficiency"] is None
        assert bps.get_ledger()["enabled"] is False
        # the verdict gracefully omits the efficiency clause
        assert "MFU" not in bps.get_metrics()["steps"]["last_diagnosis"]
