#!/usr/bin/env bash
# Pre-PR gate (README.md "Before you send a PR"): the three checks a
# change must clear, in increasing cost order, with one summary at the
# end. Run from anywhere; the repo root is derived from this script.
#
#   1. byteps-lint   — static invariants (docs/static-analysis.md)
#   2. sanitize tier — TSAN/ASAN loopback stress incl. slow bursts
#                      (tests/test_sanitize.py)
#   3. tier-1        — the full non-slow test suite as the driver runs
#                      it: six workers, a file on one worker, 1,470 s
#                      (README.md "Tests")
#
# Every stage runs even if an earlier one fails (a PR author wants the
# whole picture in one pass); the exit code is nonzero if ANY failed.

set -u
ROOT="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"
cd "$ROOT"

declare -a NAMES=() RESULTS=()
overall=0

run_stage() {
  local name="$1"; shift
  echo
  echo "=== [$name] $*"
  local t0=$SECONDS
  if "$@"; then
    RESULTS+=("PASS $((SECONDS - t0))s")
  else
    RESULTS+=("FAIL $((SECONDS - t0))s")
    overall=1
  fi
  NAMES+=("$name")
}

run_stage "byteps-lint" python -m byteps_tpu.tools.lint

# byteps-top CI smoke: one --once frame over a synthetic timeseries
# JSONL artifact must print schema byteps-top/1 with live series — the
# console's whole read path (artifact detect → rehydrate → frame)
run_stage "top-smoke" env JAX_PLATFORMS=cpu python - <<'PY'
import json, os, subprocess, sys, tempfile
art = os.path.join(tempfile.mkdtemp(prefix="bps-top-smoke-"),
                   "timeseries-1.jsonl")
with open(art, "w") as f:
    f.write(json.dumps({"kind": "timeseries", "reason": "smoke",
                        "pid": 1, "points": 512, "steps": 3,
                        "series_count": 1, "dropped_series": 0}) + "\n")
    f.write(json.dumps({"name": "step/wall_ms", "steps": [1, 2, 3],
                        "values": [10.0, 11.0, 9.5]}) + "\n")
out = subprocess.run(
    [sys.executable, "-m", "byteps_tpu.tools.top", "--once",
     "--file", art], capture_output=True, text=True, timeout=120)
frame = json.loads(out.stdout)
assert out.returncode == 0, out.stderr
assert frame["schema"] == "byteps-top/1", frame
assert frame["series"]["step/wall_ms"]["points"] == 3, frame
print("[top-smoke] ok:", json.dumps(frame)[:120], "...")
PY

# advisory (never fails the gate): curated clang-tidy over ps.cc when
# the tool is installed — this is the ONLY place it runs, so the lazy
# import-time native build stays a pure -Werror compile
python - <<'PY'
from byteps_tpu.native.build import clang_tidy
import shutil
if shutil.which("clang-tidy") is None:
    print("[clang-tidy] not installed; skipping (advisory)")
else:
    report = clang_tidy()
    print(report if report else "[clang-tidy] clean")
PY

# slow markers included: the sanitize tier IS the slow TSAN/ASAN burst
# plus the fast Waiter-pool smoke; it builds its own instrumented libs
run_stage "sanitize" env JAX_PLATFORMS=cpu \
  python -m pytest tests/test_sanitize.py -q -m '' \
  -p no:cacheprovider

# --ignore=test_sanitize.py: stage 2 is authoritative for that file;
# without it tier-1 would re-run the non-slow TSAN smoke it contains
run_stage "tier-1" bash -c "
  set -o pipefail
  timeout -k 10 1470 env JAX_PLATFORMS=cpu \
    python -m pytest tests/ -q -m 'not slow' \
    --ignore=tests/test_sanitize.py \
    --continue-on-collection-errors -p no:cacheprovider \
    -p xdist -n 6 --dist loadfile -p no:randomly"

echo
echo "=== pre-PR gate summary"
for i in "${!NAMES[@]}"; do
  printf '  %-12s %s\n' "${NAMES[$i]}" "${RESULTS[$i]}"
done
if [ "$overall" -eq 0 ]; then
  echo "  ALL CHECKS PASSED"
else
  echo "  GATE FAILED"
fi
exit "$overall"
