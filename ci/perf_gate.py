#!/usr/bin/env python
"""Noise-aware perf regression gate — the first machine check that a
PR didn't quietly give back a measured win (PR 11's 1.9 GB/s class).

Compares a candidate — a driver artifact (``{"parsed": ...}``), a raw
``bench.py`` result line, or a step-ledger perf archive
(``perf-*.jsonl``, ``BYTEPS_PERF_ARCHIVE``) — against a committed
baseline (``ci/perf_baseline.json``) whose per-key SAMPLE LISTS carry
the run-to-run history. The statistics are deliberately robust:

- center   = median of the baseline samples (median-of-reps: a
  candidate list of reps is collapsed to ITS median too);
- spread   = MAD scaled to sigma (1.4826 x median absolute deviation)
  — the history IS the noise model, so a key that historically swings
  26 % between rounds (loopback GB/s on a shared 1-core host does)
  needs a far bigger drop to trip than a tight one;
- verdict  = regression iff the candidate is WORSE than the center by
  more than ``max(rel_floor x |center|, noise_k x sigma)`` in that
  key's bad direction — per-key directionality ("gbps up" and
  "step_ms down" are both wins) from an explicit table plus suffix
  rules; keys with no known direction are skipped, never guessed.

A null/missing candidate value reads as ``missing`` (a wedged round
must not be reported as a perf loss), and improvements past the same
threshold are reported symmetrically.

Wired into ``ci/checks.sh`` as an ADVISORY stage (prints, never fails
the pre-PR gate) and into ``bench.py --baseline`` (verdict rides the
result JSON as ``perf_gate``). Stdlib-only by contract: the bench
parent process never imports jax, and neither may this.

Usage:
    python ci/perf_gate.py --baseline ci/perf_baseline.json \\
        --candidate RESULT.json [--rel-floor 0.10] [--noise-k 3.0]

Exit codes: 0 = no regressions, 1 = regression(s), 2 = usage error.
"""

from __future__ import annotations

import json
import sys
from typing import List, Optional

# Keys whose better-direction a suffix rule would get wrong (or miss).
DIRECTION_OVERRIDES = {
    "value": "higher",                    # tokens/s headline
    "vs_baseline": "higher",
    "mfu": "higher",
    "scaling_efficiency_2w": "higher",
    "scaling_vs_core_cap": "higher",
    "wire_request_ratio": "lower",        # fused/two-op message ratio
    "scaleup_ratio": "lower",             # after/before step wall
    "shard_reduction_ratio": "higher",    # whole-leaf/shard bytes
    "codec_adapt_wire_reduction": "lower",  # adaptive/dense wire bytes
    "overlap_frac": "higher",
    "wire_efficiency": "higher",
    "ledger_mfu": "higher",
    "ledger_overlap_frac": "higher",
    "ledger_wire_efficiency": "higher",
    "achieved_flops": "higher",
    "wire_bytes": "lower",
    # training-health archive keys (core/health.py): a gradient norm
    # has NO better-direction — an explicit None pins it skipped so a
    # future suffix rule can never misread a healthy optimization
    # change as a perf regression; update_ratio_p95 likewise (and its
    # _efficiency-adjacent spelling must not hit a suffix rule).
    # nonfinite_leaves IS directional: any growth is a poisoned run.
    "grad_norm": None,
    "update_ratio_p95": None,
    "fidelity_drift": None,
    "nonfinite_leaves": "lower",
    # cross-barrier pipelining (bench.py barrier_ab): the step-wall and
    # overlap keys ride the suffix rules (_step_ms lower, _frac
    # higher); the engaged-proof counters are directional — a drop to
    # zero means the carry silently disengaged (the win evaporates),
    # and the sync arm carrying ANYTHING is a staleness-0 contract
    # violation.
    "barrier_speedup": "higher",
    "barrier_carried_leaves": "higher",
    "barrier_carry_drained": "higher",
    "barrier_sync_carried_leaves": "lower",
    # cross-host wire plane (bench.py stripe_ab): the five *_gbps keys
    # ride the suffix rule; the ratios and engaged-proof counters are
    # directional — stripe_ab_segs dropping to zero means the striper
    # silently disengaged, msgs_per_batch falling to 1.0 means the
    # reply ring stopped coalescing (the syscall win evaporates), and
    # lossless_gain under 1.0 means decompress-on-the-fabric no longer
    # beats raw bytes under the same wire cap.
    "stripe_ab_speedup": "higher",
    "stripe_ab_segs": "higher",
    "stripe_ab_msgs_per_batch": "higher",
    "stripe_ab_lossless_gain": "higher",
}
# (suffix, direction) checked in order after the overrides; the first
# match wins. "_ms" covers every step-wall key; "_pct" the overhead
# A/Bs; throughput families end in _gbps / tokens_per_sec.
SUFFIX_RULES = (
    ("_gbps", "higher"),
    ("_tokens_per_sec", "higher"),
    ("_step_ms", "lower"),
    ("_ms", "lower"),
    ("_overhead_pct", "lower"),
    ("_frac", "higher"),
    ("_efficiency", "higher"),
)


def direction_for(key: str) -> Optional[str]:
    """"higher" / "lower" = which way is better; None = unknown (the
    key is skipped — a guessed direction would flag wins as losses)."""
    if key in DIRECTION_OVERRIDES:
        return DIRECTION_OVERRIDES[key]
    if key.startswith("tokens_per_sec"):
        return "higher"
    for suffix, d in SUFFIX_RULES:
        if key.endswith(suffix):
            return d
    return None


def median(xs: List[float]) -> float:
    s = sorted(xs)
    n = len(s)
    mid = n // 2
    return s[mid] if n % 2 else (s[mid - 1] + s[mid]) / 2.0


def mad(xs: List[float]) -> float:
    """Median absolute deviation (unscaled)."""
    m = median(xs)
    return median([abs(x - m) for x in xs])


def load_baseline(path: str) -> dict:
    with open(path) as f:
        doc = json.load(f)
    if not isinstance(doc, dict) or "keys" not in doc:
        raise ValueError(f"{path}: not a perf baseline (no 'keys')")
    return doc


def load_candidate(path: str) -> dict:
    """Candidate metrics from any of the three shapes:

    - ``*.jsonl`` — a step-ledger perf archive: each numeric key
      collapses to the median over its records (median-of-steps);
    - a driver artifact — ``{"parsed": {...}}`` wrapper: the parsed
      result (a null parse yields an empty candidate — every key then
      reads ``missing``, never ``regression``);
    - a raw bench result line / arbitrary flat JSON dict.
    """
    if path.endswith(".jsonl"):
        per_key: dict = {}
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                rec = json.loads(line)
                for k, v in rec.items():
                    if isinstance(v, (int, float)) \
                            and not isinstance(v, bool):
                        per_key.setdefault(k, []).append(float(v))
        return {k: median(vs) for k, vs in per_key.items()}
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, dict) and "parsed" in doc:
        doc = doc["parsed"] or {}
    if not isinstance(doc, dict):
        raise ValueError(f"{path}: candidate is not a JSON object")
    return doc


def compare(candidate: dict, baseline: dict, rel_floor: float = 0.10,
            noise_k: float = 3.0) -> dict:
    """Per-key verdicts for every baseline key. A key regresses iff
    its candidate value is worse than the baseline median by more than
    ``max(rel_floor x |median|, noise_k x 1.4826 x MAD)``."""
    rows = []
    for key, spec in sorted(baseline.get("keys", {}).items()):
        samples = [float(s) for s in spec.get("samples", [])
                   if isinstance(s, (int, float))
                   and not isinstance(s, bool)]
        if not samples:
            continue
        d = spec.get("direction") or direction_for(key)
        if d not in ("higher", "lower"):
            rows.append({"key": key, "verdict": "skipped",
                         "reason": "unknown direction"})
            continue
        v = candidate.get(key)
        if isinstance(v, list):
            vs = [float(x) for x in v
                  if isinstance(x, (int, float))
                  and not isinstance(x, bool)]
            v = median(vs) if vs else None
        if not isinstance(v, (int, float)) or isinstance(v, bool):
            rows.append({"key": key, "verdict": "missing"})
            continue
        center = median(samples)
        sigma = 1.4826 * mad(samples)
        threshold = max(rel_floor * abs(center), noise_k * sigma)
        delta = (center - v) if d == "higher" else (v - center)
        if delta > threshold:
            verdict = "regression"
        elif -delta > threshold:
            verdict = "improvement"
        else:
            verdict = "pass"
        rows.append({"key": key, "verdict": verdict,
                     "value": float(v), "median": center,
                     "sigma": round(sigma, 6),
                     "threshold": round(threshold, 6),
                     "direction": d, "n_samples": len(samples)})
    regressions = [r for r in rows if r["verdict"] == "regression"]
    return {"rows": rows, "regressions": regressions,
            "ok": not regressions,
            "checked": sum(1 for r in rows
                           if r["verdict"] in ("pass", "regression",
                                               "improvement")),
            "rel_floor": rel_floor, "noise_k": noise_k}


def summarize(report: dict) -> dict:
    """Compact form for embedding in a bench result line."""
    return {
        "ok": report["ok"],
        "checked": report["checked"],
        "regressions": [
            {"key": r["key"], "value": r["value"],
             "median": r["median"], "threshold": r["threshold"]}
            for r in report["regressions"]],
        "improvements": [r["key"] for r in report["rows"]
                         if r["verdict"] == "improvement"],
        "missing": [r["key"] for r in report["rows"]
                    if r["verdict"] == "missing"],
    }


def format_report(report: dict) -> str:
    lines = []
    for r in report["rows"]:
        if r["verdict"] in ("skipped", "missing"):
            lines.append(f"  [{r['verdict']:>11}] {r['key']}")
            continue
        lines.append(
            f"  [{r['verdict']:>11}] {r['key']}: {r['value']:g} vs "
            f"median {r['median']:g} "
            f"(threshold {r['threshold']:g}, {r['direction']} is "
            f"better, n={r['n_samples']})")
    verdict = "OK" if report["ok"] else \
        f"{len(report['regressions'])} REGRESSION(S)"
    lines.append(f"perf-gate: {verdict} "
                 f"({report['checked']} key(s) checked, "
                 f"rel_floor={report['rel_floor']:g}, "
                 f"noise_k={report['noise_k']:g})")
    return "\n".join(lines)


def main(argv: List[str]) -> int:
    args = {}
    flags = {"--baseline": "baseline", "--candidate": "candidate",
             "--rel-floor": "rel_floor", "--noise-k": "noise_k"}
    i = 0
    while i < len(argv):
        if argv[i] in flags and i + 1 < len(argv):
            args[flags[argv[i]]] = argv[i + 1]
            i += 2
            continue
        sys.stderr.write(f"perf_gate: unknown/incomplete arg "
                         f"{argv[i]!r}\n{__doc__.splitlines()[0]}\n")
        return 2
    if "baseline" not in args or "candidate" not in args:
        sys.stderr.write(
            "usage: perf_gate.py --baseline FILE --candidate FILE "
            "[--rel-floor F] [--noise-k K]\n")
        return 2
    try:
        baseline = load_baseline(args["baseline"])
        candidate = load_candidate(args["candidate"])
    except (OSError, ValueError, json.JSONDecodeError) as e:
        sys.stderr.write(f"perf_gate: {e}\n")
        return 2
    report = compare(candidate, baseline,
                     rel_floor=float(args.get("rel_floor", 0.10)),
                     noise_k=float(args.get("noise_k", 3.0)))
    print(format_report(report))
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
