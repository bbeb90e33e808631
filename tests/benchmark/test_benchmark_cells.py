"""Each cell's command, end to end at tiny size on the CPU through the
rehearsal switch, which can never print a result line; and the runner
refusing to measure without a TPU."""

import os
import shutil
import subprocess
import sys

import pytest

from bench_helpers import BENCH, MANIFEST, last_line, manifest, run_python

CELLS = [w["name"] for w in manifest()["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_end_to_end(cell):
    proc = run_python(["benchmark/run.py", "--workload", cell, "--seed",
                       "2147483777", "--seconds", "0.3", "--trace", "1",
                       "--rehearse"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    end = last_line(proc)
    assert "rehearsal reached the result: correct=True" in end, \
        proc.stdout[-3000:]
    assert not end.startswith("{"), "a rehearsal printed a result line"
    out = proc.stdout
    assert "step walls ms:" in out and "set-up breakdown s:" in out
    if ".ps." in cell:
        assert "engagement: streamed leaves" in out
        assert "(DIFFER)" not in out
        assert "server child exited 0" in out


def test_step_ms_is_the_whole_window_over_its_steps_stalls_included():
    """The walls are contiguous (a step's clock starts where the last
    one's stopped), so the window over its steps is their mean: one
    stalled step moves ``step_ms`` by its whole length."""
    import time

    from benchmark import run

    calls = []

    def step(params, opt, batch):
        calls.append(batch)
        time.sleep(0.12 if len(calls) == 3 else 0.01)
        return params, opt, 0.0

    _, _, _, walls, window_s = run.measure_window(step, 0, 0, [0, 1], 0.3)
    assert calls[:4] == [0, 1, 0, 1]
    assert sum(walls) == pytest.approx(window_s * 1e3, rel=1e-9)
    assert max(walls) >= 120 and window_s >= 0.3
    step_ms = window_s * 1e3 / len(walls)
    assert step_ms > 1.5 * sorted(walls)[len(walls) // 2]


def test_runner_refuses_to_measure_without_a_tpu():
    proc = run_python(["benchmark/run.py", "--workload", CELLS[0], "--seed",
                       "1", "--seconds", "0.2", "--trace", "0"])
    assert proc.returncode != 0
    assert "No CPU fallback" in proc.stderr
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())


def test_unknown_workload_is_refused_by_name():
    proc = run_python(["benchmark/run.py", "--workload", "nope.fused.1chip",
                       "--seed", "1", "--seconds", "1", "--rehearse"])
    assert proc.returncode != 0 and "no workload" in proc.stderr


def test_benchmark_alone_without_the_program_prints_no_result(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    its paths there is no system under test: non-zero exit, no line."""
    shutil.copy(MANIFEST, tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "XLA_FLAGS")}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "0.2", "--trace", "0", "--rehearse"],
        cwd=tmp_path, env=env, text=True, capture_output=True, timeout=120)
    assert proc.returncode != 0
    assert "byteps_tpu" in proc.stderr
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())
