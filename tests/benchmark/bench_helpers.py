"""Shared by the benchmark's tier-1 tests: where things are, and how a
cell's command is run at tiny size on the CPU (the rehearsal switch)."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
MANIFEST = os.path.join(REPO, "BENCHMARK.json")
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def manifest() -> dict:
    with open(MANIFEST) as f:
        return json.load(f)


def child_env(**extra) -> dict:
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update({"JAX_PLATFORMS": "cpu",
                "PYTHONPATH": os.pathsep.join(
                    [REPO] + [p for p in env.get("PYTHONPATH", "").split(
                        os.pathsep) if p])})
    env.update(extra)
    return env


def run_python(code_or_args, timeout=240, cwd=REPO, **env):
    """A child Python with the CPU pinned; returns the finished process
    (stdout and stderr as text)."""
    args = [sys.executable] + (["-c", code_or_args]
                               if isinstance(code_or_args, str)
                               else list(code_or_args))
    return subprocess.run(args, cwd=cwd, env=child_env(**env), text=True,
                          capture_output=True, timeout=timeout)


def last_line(proc) -> str:
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    return lines[-1] if lines else ""
