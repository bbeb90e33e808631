"""byteps-top — the fleet's live console (``python -m byteps_tpu.tools.top``).

Renders the SAME snapshot surface everything else reads — the unified
metrics snapshot with its ``timeseries`` / ``steps`` / ``fleet`` /
``health`` / ``flight`` sections — as a terminal dashboard: per-series
sparklines (step walls, per-server per-stripe-lane wire bytes,
counter deltas), the ``classify_step`` bound-stage verdict with the
LANE-IMBALANCE annotation, health flags and flight-ring pressure.
Stdlib only (ANSI escapes, ``urllib``); no curses dependency, no
third-party TUI.

Three snapshot sources, one renderer:

- ``--url http://127.0.0.1:<port>/`` — the JSON endpoint
  ``BYTEPS_METRICS_PORT`` serves (the remote / out-of-process view);
  defaults to that env var's port when set.
- ``--file path`` — a dumped snapshot JSON, or a ``timeseries-*.jsonl``
  SIGTERM/shutdown artifact (post-mortem mode: the console
  renders a dead run's tail).
- ``--local`` — ``bps.get_metrics()`` in this process (debugging a
  live training process from a REPL / the same interpreter).

``--once`` prints one machine-readable JSON frame and exits — the CI
smoke (ci/checks.sh) and test surface; its keys are pinned by
``tests/test_timeseries.py``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Dict, Optional

__all__ = ["main", "build_frame", "once_frame", "load_snapshot"]

_SPARK = " ▁▂▃▄▅▆▇█"
_BOLD, _DIM, _RESET = "\x1b[1m", "\x1b[2m", "\x1b[0m"
_RED, _YEL, _GRN = "\x1b[31m", "\x1b[33m", "\x1b[32m"


def sparkline(values, width: int = 24) -> str:
    """Fixed-width unicode sparkline, right-aligned to the newest
    point; constant scale per series (min..max of the shown tail)."""
    vals = [float(v) for v in values][-width:]
    if not vals:
        return " " * width
    lo, hi = min(vals), max(vals)
    span = hi - lo
    out = []
    for v in vals:
        if span <= 0:
            out.append(_SPARK[1] if hi > 0 else _SPARK[0])
        else:
            idx = 1 + int((v - lo) / span * (len(_SPARK) - 2))
            out.append(_SPARK[min(idx, len(_SPARK) - 1)])
    return "".join(out).rjust(width)


def _fmt(v) -> str:
    if v is None:
        return "-"
    f = float(v)
    if f != f:  # NaN
        return "nan"
    if abs(f) >= 1e9:
        return f"{f / 1e9:.2f}G"
    if abs(f) >= 1e6:
        return f"{f / 1e6:.2f}M"
    if abs(f) >= 1e3:
        return f"{f / 1e3:.1f}k"
    if f == int(f):
        return str(int(f))
    return f"{f:.3g}"


# ------------------------------------------------------------------- #
# snapshot sources
# ------------------------------------------------------------------- #


def _snapshot_from_jsonl(lines) -> dict:
    """Rehydrate a ``timeseries-*.jsonl`` dump artifact into the
    snapshot shape the renderer reads (timeseries section only)."""
    header: dict = {}
    series: Dict[str, dict] = {}
    for line in lines:
        line = line.strip()
        if not line:
            continue
        doc = json.loads(line)
        if doc.get("kind") == "timeseries":
            header = doc
        elif "name" in doc:
            series[doc["name"]] = {"steps": doc.get("steps", []),
                                   "values": doc.get("values", [])}
    return {"timeseries": {
        "enabled": True,
        "points": header.get("points", 0),
        "steps": header.get("steps", 0),
        "series_count": len(series),
        "dropped_series": header.get("dropped_series", 0),
        "breaker_tripped": False,
        "series": series,
    }, "_artifact": {"reason": header.get("reason"),
                     "pid": header.get("pid")}}


def load_snapshot(url: Optional[str] = None, file: Optional[str] = None,
                  local: bool = False) -> dict:
    """Fetch one snapshot dict from whichever source was selected."""
    if local:
        import byteps_tpu as bps
        return bps.get_metrics()
    if file:
        with open(file) as f:
            first = f.readline()
            rest = f.read()
        text = first + rest
        if first.lstrip().startswith("{") and '"kind": "timeseries"' \
                in first:
            return _snapshot_from_jsonl(text.splitlines())
        return json.loads(text)
    if url:
        from urllib.request import urlopen
        with urlopen(url, timeout=5) as resp:
            return json.loads(resp.read().decode())
    raise ValueError("no snapshot source: pass --url, --file or --local")


# ------------------------------------------------------------------- #
# frame assembly
# ------------------------------------------------------------------- #


def _verdict(snap: dict) -> Optional[str]:
    """The classify_step bound-stage verdict for the last step:
    the steps section carries it precomputed (``last_diagnosis``);
    artifacts that only have the raw report dict get it recomputed
    through the real classifier."""
    steps = snap.get("steps") or {}
    v = steps.get("last_diagnosis")
    if v:
        return v
    last = steps.get("last")
    if not last:
        return None
    try:
        from ..core.metrics import StepReport, classify_step
        known = {f.name for f in
                 __import__("dataclasses").fields(StepReport)}
        kwargs = {k: v for k, v in last.items() if k in known}
        if kwargs.get("lane_bytes") is not None:
            kwargs["lane_bytes"] = tuple(
                tuple(e) for e in kwargs["lane_bytes"])
        return classify_step(StepReport(**kwargs))
    except Exception:  # noqa: BLE001 - a partial artifact: no verdict
        return None


def _series_groups(ts: dict):
    """(group_title, [(name, steps, values)]) buckets in render order:
    step walls first, then the per-stripe wire lanes, then counter
    deltas / gauges."""
    series = ts.get("series") or {}
    groups = [("step", []), ("stripe", []), ("counter", []),
              ("gauge", [])]
    by_prefix = dict(groups)
    for name in sorted(series):
        prefix = name.split("/", 1)[0]
        bucket = by_prefix.get(prefix)
        if bucket is None:
            continue
        s = series[name]
        bucket.append((name, s.get("steps", []), s.get("values", [])))
    return [(title, rows) for title, rows in groups if rows]


def build_frame(snap: dict, width: int = 100) -> str:
    """One rendered text frame (ANSI) from a snapshot dict."""
    ts = snap.get("timeseries") or {}
    lines = []
    art = snap.get("_artifact")
    src = f" artifact[{art['reason']}] pid={art['pid']}" if art else ""
    trip = ts.get("breaker_tripped")
    head = (f"{_BOLD}byteps-top{_RESET}  steps={ts.get('steps', 0)} "
            f"series={ts.get('series_count', 0)} "
            f"ring={ts.get('points', 0)}{src}")
    if trip:
        head += f" {_RED}[recorder breaker TRIPPED]{_RESET}"
    if ts.get("dropped_series"):
        head += f" {_YEL}dropped={ts['dropped_series']}{_RESET}"
    lines.append(head)
    verdict = _verdict(snap)
    if verdict:
        if "LANE-IMBALANCE" in verdict or "HEALTH" in verdict:
            color = _RED
        elif verdict.startswith("COMPUTE"):
            color = _GRN  # compute-bound is the healthy steady state
        else:
            color = _YEL  # wire/queue/server-bound: worth a look
        lines.append(f"{color}{verdict}{_RESET}")
    # health + flight annotations ride the same frame
    last = (snap.get("steps") or {}).get("last") or {}
    flags = last.get("health_flags")
    if flags:
        lines.append(f"{_RED}HEALTH: {','.join(flags)}{_RESET}")
    flight = snap.get("flight") or {}
    if flight:
        lines.append(
            f"{_DIM}flight: events={flight.get('events', 0)} "
            f"dropped={flight.get('dropped', 0)}{_RESET}")
    fleet = snap.get("fleet") or {}
    if fleet.get("server"):
        lines.append(f"{_DIM}fleet: {len(fleet['server'])} server(s) "
                     f"via {fleet.get('source')}{_RESET}")
    name_w = max(28, width - 44)
    for title, rows in _series_groups(ts):
        lines.append(f"{_BOLD}-- {title} {'-' * (width - len(title) - 4)}"
                     f"{_RESET}")
        for name, _steps, values in rows:
            tail = values[-1] if values else None
            lines.append(f"{name[:name_w]:<{name_w}} "
                         f"{sparkline(values)} "
                         f"{_fmt(tail):>8} n={len(values)}")
    if not ts:
        lines.append(f"{_DIM}(no timeseries section in snapshot — is "
                     f"BYTEPS_TIMESERIES on?){_RESET}")
    return "\n".join(lines)


def once_frame(snap: dict) -> dict:
    """The ``--once`` machine-readable frame (schema pinned by
    tests/test_timeseries.py): fixed top-level keys, per-series
    last/min/max/points."""
    ts = snap.get("timeseries") or {}
    series = {}
    for name, s in (ts.get("series") or {}).items():
        values = s.get("values") or []
        series[name] = {
            "points": len(values),
            "last": values[-1] if values else None,
            "min": min(values) if values else None,
            "max": max(values) if values else None,
        }
    last = (snap.get("steps") or {}).get("last") or {}
    return {
        "schema": "byteps-top/1",
        "steps": ts.get("steps", 0),
        "series_count": ts.get("series_count", len(series)),
        "breaker_tripped": bool(ts.get("breaker_tripped", False)),
        "verdict": _verdict(snap),
        "series": series,
        "health_flags": list(last.get("health_flags") or []),
        "flight": {"events": (snap.get("flight") or {}).get("events", 0),
                   "dropped": (snap.get("flight") or {}).get("dropped",
                                                             0)},
        "fleet": {"servers": len((snap.get("fleet") or {})
                                 .get("server") or {}),
                  "source": (snap.get("fleet") or {}).get("source")},
    }


# ------------------------------------------------------------------- #
# entry point
# ------------------------------------------------------------------- #


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m byteps_tpu.tools.top",
        description="live fleet console over the byteps_tpu metrics "
                    "snapshot (timeseries/steps/fleet sections)")
    ap.add_argument("--url", default=None,
                    help="snapshot JSON endpoint (default: "
                         "http://127.0.0.1:$BYTEPS_METRICS_PORT/ "
                         "when that env var is set)")
    ap.add_argument("--file", default=None,
                    help="snapshot JSON or timeseries-*.jsonl artifact")
    ap.add_argument("--local", action="store_true",
                    help="read bps.get_metrics() in-process")
    ap.add_argument("--once", action="store_true",
                    help="print one machine-readable JSON frame and "
                         "exit (CI smoke)")
    ap.add_argument("--interval", type=float, default=2.0,
                    help="refresh period in seconds (live mode)")
    ap.add_argument("--width", type=int, default=100)
    args = ap.parse_args(argv)
    url = args.url
    if url is None and not args.file and not args.local:
        port = os.environ.get("BYTEPS_METRICS_PORT", "")
        if port and port != "0":
            url = f"http://127.0.0.1:{port}/"
        else:
            ap.error("no source: pass --url/--file/--local (or set "
                     "BYTEPS_METRICS_PORT)")
    if args.once:
        try:
            snap = load_snapshot(url=url, file=args.file,
                                 local=args.local)
        except Exception as e:  # noqa: BLE001 - CI smoke wants 1 line
            print(json.dumps({"schema": "byteps-top/1", "error": str(e)}))
            return 1
        print(json.dumps(once_frame(snap)))
        return 0
    try:
        while True:
            try:
                snap = load_snapshot(url=url, file=args.file,
                                     local=args.local)
                frame = build_frame(snap, width=args.width)
            except Exception as e:  # noqa: BLE001 - source flaps: show
                frame = f"{_RED}snapshot source error: {e}{_RESET}"
            # home + clear-below keeps the frame flicker-free
            sys.stdout.write("\x1b[H\x1b[J" + frame + "\n")
            sys.stdout.flush()
            if args.file:
                return 0  # artifacts are static: render once
            time.sleep(args.interval)
    except KeyboardInterrupt:
        return 0


if __name__ == "__main__":
    sys.exit(main())
