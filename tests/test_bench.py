"""Orchestrator-level tests for bench.py's phase schedule.

The real phases are exercised elsewhere (loopback PS tests, train tests);
here the subprocess runner is stubbed so the SCHEDULE itself is testable
in milliseconds: the two device phases run once each, first, and
independently of each other; a phase that does not land makes the run
exit non-zero; the budget gate and the partial snapshots hold. The device
phases' own no-TPU failure is checked on real children.
"""

from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def bench(monkeypatch):
    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(os.path.dirname(__file__), "..", "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.delenv("BENCH_BUDGET_S", raising=False)
    return mod


def run_main(bench, monkeypatch, capsys, phase_script):
    """Drive bench.main() with a scripted _run_phase; returns the final
    JSON line, the calls made and main()'s exit code.
    ``phase_script(name, calls)`` -> (result|None, err|None)."""
    calls = []

    def fake_run_phase(name, timeout_s):
        out = phase_script(name, calls)
        calls.append(name)
        return out

    monkeypatch.setattr(bench, "_run_phase", fake_run_phase)
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    rc = bench.main()
    line = capsys.readouterr().out.strip().splitlines()[-1]
    return json.loads(line), calls, rc


def test_healthy_run_lands_everything(bench, monkeypatch, capsys):
    def script(name, calls):
        if name == "train":
            return {"value": 100000.0, "mfu": 0.4,
                    "train_variant": "remat"}, None
        if name == "pushpull_tpu":
            return {"pushpull_onebit_tpu_gbps": 9.0,
                    "pushpull_dense_tpu_gbps": 4.0}, None
        if name == "pushpull":
            return {"pushpull_dense_gbps": 3.0,
                    "pushpull_onebit_gbps": 3.3,
                    "pushpull_randomk_gbps": 3.7}, None
        if name == "pushpull_2srv":
            return {"pushpull_dense_2srv_gbps": 2.7}, None
        if name == "pushpull_throttled":
            return {"pushpull_throttled_1srv_gbps": 0.1,
                    "pushpull_throttled_2srv_gbps": 0.2,
                    "throttle_mbps": 100.0}, None
        if name == "arena_ab":
            return {"arena_on_step_ms": 5.0,
                    "arena_off_step_ms": 6.5}, None
        if name == "metrics_ab":
            return {"metrics_on_step_ms": 5.1,
                    "metrics_off_step_ms": 5.0,
                    "metrics_overhead_pct": 2.0}, None
        if name == "trace_ab":
            return {"trace_on_step_ms": 5.05,
                    "trace_off_step_ms": 5.0,
                    "trace_overhead_pct": 1.0,
                    "trace_server_records": 96,
                    "trace_rid_links": 24}, None
        if name == "ledger_ab":
            return {"ledger_on_step_ms": 5.08,
                    "ledger_off_step_ms": 5.0,
                    "ledger_overhead_pct": 1.6,
                    "ledger_mfu": 0.31,
                    "ledger_overlap_frac": 0.62,
                    "ledger_wire_efficiency": 0.52,
                    "ledger_cost_source": "xla",
                    "ledger_verdict_named": True}, None
        if name == "health_ab":
            return {"health_on_step_ms": 5.06,
                    "health_off_step_ms": 5.0,
                    "health_overhead_pct": 1.2,
                    "health_grad_norm": 0.031,
                    "health_update_ratio_p95": 2.1e-4,
                    "health_nonfinite_leaves": 0,
                    "health_infold_rounds": 48,
                    "health_verdict_named": True}, None
        if name == "barrier_ab":
            return {"barrier_on_step_ms": 3.4,
                    "barrier_off_step_ms": 4.6,
                    "barrier_speedup": 1.353,
                    "barrier_overlap_on_frac": 0.71,
                    "barrier_overlap_off_frac": 0.12,
                    "barrier_carried_leaves": 96,
                    "barrier_carry_drained": 96,
                    "barrier_sync_carried_leaves": 0}, None
        if name == "wire_ab":
            return {"wire_fused_step_ms": 3.6,
                    "wire_twoop_step_ms": 4.1,
                    "wire_fused_requests": 72,
                    "wire_twoop_requests": 144,
                    "wire_request_ratio": 0.5,
                    "wire_half_proof": True}, None
        if name == "fold_ab":
            return {"fold_simd_gbps": 6.1,
                    "fold_scalar_gbps": 3.2,
                    "fold_simd_tier": 3,
                    "fold_bytes_per_arm": 805306368,
                    "fold_bytes_equal": True,
                    "fold_direct_recvs": 96,
                    "fold_oob_msgs": 120}, None
        if name == "shard_ab":
            return {"shard_on_step_ms": 3.9,
                    "shard_off_step_ms": 4.2,
                    "shard_local_size": 8,
                    "shard_bytes_per_device_on": 3145728,
                    "shard_bytes_per_device_off": 25165824,
                    "shard_reduction_ratio": 8.0,
                    "shard_counter_proof": True}, None
        if name == "scaling":
            return {"scaling_efficiency_2w": 0.45}, None
        if name == "churn_ab":
            return {"churn_ab_identical": True,
                    "churn_ab_chaos_retries": 7,
                    "churn_ab_clean_retries": 0,
                    "churn_ab_drop_rate": 0.25,
                    "churn_ab_idempotent_proof": True}, None
        if name == "scaleup_ab":
            return {"scaleup_before_step_ms": 320.0,
                    "scaleup_after_step_ms": 180.0,
                    "scaleup_ratio": 0.5625,
                    "scaleup_joins": 1,
                    "scaleup_newcomer_bytes": 16777216,
                    "scaleup_identical": True,
                    "scaleup_proof": True}, None
        if name == "codec_adapt_ab":
            return {"codec_adapt_throttled_switches": 2,
                    "codec_adapt_unthrottled_switches": 0,
                    "codec_adapt_wire_bytes": 100,
                    "codec_dense_wire_bytes": 400,
                    "codec_adapt_wire_reduction": 0.25,
                    "codec_lossless_bytes_post": 12345,
                    "codec_lossless_bitwise": True,
                    "codec_tag_mismatch_rejected": True,
                    "codec_adapt_proof": True}, None
        if name == "stripe_ab":
            return {"stripe_ab_legacy_gbps": 1.87,
                    "stripe_ab_ring_gbps": 1.89,
                    "stripe_ab_striped_gbps": 1.83,
                    "stripe_ab_speedup": 0.98,
                    "stripe_ab_segs": 4096,
                    "stripe_ab_msgs_per_batch": 1.23,
                    "stripe_ab_conservation": True,
                    "stripe_ab_throttled_dense_gbps": 0.02,
                    "stripe_ab_throttled_lossless_gbps": 0.042,
                    "stripe_ab_lossless_gain": 2.09,
                    "stripe_ab_throttle_mbps": 20.0}, None
        if name == "ts_ab":
            return {"ts_on_step_ms": 5.02,
                    "ts_off_step_ms": 5.0,
                    "ts_overhead_pct": 0.4,
                    "ts_series_count": 72,
                    "ts_stripe_lane_points": 48,
                    "ts_staleness_points": 20,
                    "ts_engaged_proof": True}, None
        raise AssertionError(name)

    out, calls, rc = run_main(bench, monkeypatch, capsys, script)
    assert rc == 0
    assert out["value"] == 100000.0
    assert out["churn_ab_idempotent_proof"] is True
    assert out["churn_ab_chaos_retries"] == 7
    # never-landed driver keys run FIRST: the throttled pair and scaling
    # ahead of the long raw pushpull phases that used to starve them out
    # of overrun rounds
    cpu_calls = [c for c in calls if c not in ("train", "pushpull_tpu")]
    assert cpu_calls[:10] == ["pushpull_throttled", "scaling", "churn_ab",
                              "scaleup_ab", "codec_adapt_ab", "stripe_ab",
                              "fold_ab", "ledger_ab", "health_ab",
                              "ts_ab"]
    assert out["stripe_ab_conservation"] is True
    assert out["stripe_ab_lossless_gain"] == 2.09
    assert out["stripe_ab_segs"] == 4096
    assert out["scaleup_proof"] is True
    assert out["scaleup_joins"] == 1
    assert out["scaleup_newcomer_bytes"] == 16777216
    assert out["codec_adapt_proof"] is True
    assert out["codec_adapt_throttled_switches"] == 2
    assert out["codec_adapt_unthrottled_switches"] == 0
    assert out["codec_lossless_bitwise"] is True
    assert out["codec_tag_mismatch_rejected"] is True
    assert out["metrics_on_step_ms"] == 5.1
    assert out["metrics_overhead_pct"] == 2.0
    assert out["ledger_on_step_ms"] == 5.08
    assert out["ledger_overhead_pct"] == 1.6
    assert out["ledger_mfu"] == 0.31
    assert out["ledger_overlap_frac"] == 0.62
    assert out["ledger_wire_efficiency"] == 0.52
    assert out["health_on_step_ms"] == 5.06
    assert out["health_overhead_pct"] == 1.2
    assert out["health_grad_norm"] == 0.031
    assert out["health_infold_rounds"] == 48
    assert out["trace_on_step_ms"] == 5.05
    assert out["trace_overhead_pct"] == 1.0
    assert out["trace_server_records"] == 96
    assert out["trace_rid_links"] == 24
    assert out["barrier_on_step_ms"] == 3.4
    assert out["barrier_overlap_on_frac"] == 0.71
    assert out["barrier_carried_leaves"] == 96
    assert out["wire_fused_step_ms"] == 3.6
    assert out["wire_request_ratio"] == 0.5
    assert out["fold_simd_gbps"] == 6.1
    assert out["fold_bytes_equal"] is True
    assert out["shard_on_step_ms"] == 3.9
    assert out["shard_reduction_ratio"] == 8.0
    assert out["pushpull_throttled_2srv_gbps"] == 0.2
    assert out["arena_on_step_ms"] == 5.0
    assert out["vs_baseline"] == round(100000.0 / 51810.0, 4)
    assert out["pushpull_onebit_tpu_gbps"] == 9.0
    assert "phase_errors" not in out
    # the two device phases up front, once each, then the CPU phases
    assert calls[:2] == ["train", "pushpull_tpu"]
    assert calls.count("train") == 1 and calls.count("pushpull_tpu") == 1
    assert len(calls) == len(set(calls))  # nothing is retried


def test_tpu_wire_decoupled_from_train_failure(bench, monkeypatch, capsys):
    """Train fails (e.g. OOM): the device-tier wire number must land
    anyway, train is NOT retried, and the run exits non-zero — a failed
    device phase fails the run."""
    def script(name, calls):
        if name == "train":
            return None, "rc=1"
        if name == "pushpull_tpu":
            return {"pushpull_onebit_tpu_gbps": 8.5,
                    "pushpull_dense_tpu_gbps": 4.2}, None
        return {}, None

    out, calls, rc = run_main(bench, monkeypatch, capsys, script)
    assert rc != 0
    assert out["value"] is None and out["mfu"] is None
    assert out["pushpull_onebit_tpu_gbps"] == 8.5
    assert out["phase_errors"] == {"train": "rc=1"}
    assert calls.count("pushpull_tpu") == 1
    assert calls.count("train") == 1


@pytest.mark.parametrize("phase", ["train", "pushpull_tpu"])
def test_device_phase_fails_without_tpu(phase):
    """A device phase's child, on a machine where JAX finds no TPU
    (here: pinned to the CPU), exits non-zero and prints no result line
    — a CPU run is never published under a device-named key."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--phase", phase],
        cwd=REPO, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0, r.stdout[-500:]
    assert "BENCH_PHASE_RESULT" not in r.stdout
    assert "device phase needs a TPU" in r.stderr


def test_scaling_summary_estimator(bench):
    """The scaling estimator's contract: headline = best WITHIN-rep
    ratio (never a cross-rep pairing), spread/reps keys derived from
    pairs only, and the list-maxima fallback when no rep completed both
    configs."""
    # three clean interleaved reps on a 1-core host (cap = 0.5)
    out = bench._scaling_summary(
        pairs=[(100.0, 90.0), (110.0, 88.0), (105.0, 94.0)],
        t1s=[100.0, 110.0, 105.0], tns=[90.0, 88.0, 94.0],
        workers=2, cores=1)
    # per-rep ratios: 0.45, 0.4, 0.4476 -> best 0.45
    assert out["scaling_efficiency_2w"] == 0.45
    assert out["scaling_vs_core_cap"] == 0.9
    assert out["scaling_vs_cap_reps"] == [0.9, 0.8, 0.8952]
    assert out["scaling_spread"] == round((0.45 - 0.4) / 0.5, 4)
    # asymmetric failures: rep2 lost its t1, rep3 lost its tn — the one
    # complete pair decides the headline; the stray 120.0 t1 and 99.0 tn
    # (which a zip over the flat lists would have married into a bogus
    # 99/(2*120) or 120-based ratio) must NOT combine
    out = bench._scaling_summary(
        pairs=[(100.0, 90.0)],
        t1s=[100.0, 120.0], tns=[90.0, 99.0],
        workers=2, cores=1)
    assert out["scaling_efficiency_2w"] == 0.45
    assert "scaling_vs_cap_reps" not in out  # single pair: no band
    # no complete pair at all: fall back to the ratio of list maxima
    out = bench._scaling_summary(
        pairs=[], t1s=[100.0], tns=[80.0], workers=2, cores=1)
    assert out["scaling_efficiency_2w"] == 0.4
    # degenerate: zero t1 measurements guard the division
    out = bench._scaling_summary(
        pairs=[(0.0, 50.0)], t1s=[0.0], tns=[50.0], workers=2, cores=1)
    assert out["scaling_efficiency_2w"] == 0.0


def test_budget_gate_skips_everything_when_spent(bench, monkeypatch,
                                                 capsys):
    """Envelope regression: with no budget left, NO phase may launch
    (the CPU phases once ran to their full deadlines regardless), the
    final JSON line still parses with the skips recorded, and the run
    exits non-zero — nothing landed."""
    monkeypatch.setenv("BENCH_BUDGET_S", "1")

    def script(name, calls):
        raise AssertionError(f"phase {name!r} launched on a spent budget")

    out, calls, rc = run_main(bench, monkeypatch, capsys, script)
    assert calls == [] and rc != 0
    assert out["value"] is None
    skipped = {k: v for k, v in out["phase_errors"].items()
               if v == "skipped-budget"}
    assert set(skipped) == {"train", "pushpull_tpu",
                            "pushpull", "pushpull_2srv",
                            "pushpull_throttled", "churn_ab",
                            "scaleup_ab", "codec_adapt_ab", "stripe_ab",
                            "fold_ab", "ledger_ab", "health_ab",
                            "ts_ab", "arena_ab", "metrics_ab",
                            "trace_ab", "barrier_ab",
                            "wire_ab", "shard_ab", "scaling"}


def test_multichip_envelope_bounded():
    """Dryrun envelope guard: the dryrun's worst case — every phase running to its full
    per-phase timeout — must fit HALF the driver window, so phase growth
    without budget fails here, in tier-1, instead of silently pushing a
    future driver round past its kill deadline. Also pins the phase
    list to the functions that actually exist (a renamed/removed phase
    fn breaks the product silently otherwise)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "__graft_entry__",
        os.path.join(os.path.dirname(__file__), "..", "__graft_entry__.py"))
    g = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(g)
    phases = g._DRYRUN_PHASES
    assert len(phases) >= 7  # the envelope covers the real suite
    worst_case = len(phases) * g.DRYRUN_PHASE_TIMEOUT_S
    assert worst_case <= g.DRYRUN_DRIVER_WINDOW_S / 2, (
        f"{len(phases)} dryrun phases x {g.DRYRUN_PHASE_TIMEOUT_S:.0f}s "
        f"= {worst_case:.0f}s worst case exceeds half the "
        f"{g.DRYRUN_DRIVER_WINDOW_S:.0f}s driver window — trim a phase "
        f"or grow the budget DELIBERATELY")
    # the re-exec child's hard timeout mirrors the same half-window
    for name, fn in phases:
        assert callable(fn), name


def test_partial_snapshots_survive_a_kill(bench, monkeypatch, capsys):
    """Every phase flushes the current snapshot as a 'partial'-tagged
    JSON line: an external SIGKILL at ANY point between phases leaves
    the last snapshot as the final parseable line (a single end-of-run
    print once lost a whole round's numbers)."""
    def script(name, calls):
        if name == "train":
            return {"value": 90000.0, "mfu": 0.38,
                    "train_variant": "remat"}, None
        if name == "pushpull_tpu":
            return {"pushpull_dense_tpu_gbps": 4.0}, None
        if name == "pushpull":
            return {"pushpull_dense_gbps": 3.0}, None
        return {}, None

    calls2 = []
    monkeypatch.setattr(bench, "_run_phase",
                        lambda n, t: (script(n, calls2),
                                      calls2.append(n))[0])
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    bench.main()
    lines = [json.loads(ln)
             for ln in capsys.readouterr().out.strip().splitlines()
             if ln.startswith("{")]
    assert len(lines) > 2
    assert lines[-1].get("partial") is None          # final: untagged
    assert all(ln.get("partial") for ln in lines[:-1])
    # snapshots accumulate: the headline already rides a mid-run line
    assert any(ln.get("value") == 90000.0 for ln in lines[:-1])


def test_bench_parent_never_imports_jax():
    """One process per chip: bench.py's orchestrating parent is
    stdlib-only, so it can never hold the chip its phase children need."""
    code = ("import sys, runpy; sys.argv = ['bench.py'];"
            "mod = runpy.run_path('bench.py', run_name='bench');"
            "rc = mod['main']();"
            "assert rc != 0;"  # spent budget: nothing landed
            "bad = [m for m in sys.modules if m == 'jax' or "
            "m.startswith(('jax.', 'byteps_tpu'))];"
            "assert not bad, bad; print('PARENT_CLEAN')")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True, timeout=60,
                       env={**os.environ, "BENCH_BUDGET_S": "0"})
    assert r.returncode == 0, r.stdout[-1000:] + r.stderr[-1000:]
    assert "PARENT_CLEAN" in r.stdout
