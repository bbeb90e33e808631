#!/usr/bin/env python3
"""Who was doing what while the chip sat idle: the program's own spans
(``bps.``-prefixed host events of an ``.xplane.pb``: name, thread line,
start, end, arguments; ``byteps_tpu/utils/tracing.py span`` writes them
into whichever profiler session is open) beside ``trace_reduce``'s idle
gaps. Each gap is named per thread by the deepest program span that
covers most of it: for a ``backward_export`` gap, what XLA's callback
thread, the export router and the train thread were each doing.

    python benchmark/program_spans.py <file.xplane.pb> [gaps]

Reads the file with nothing but JAX. A trace of a program without such
spans gives empty lists, never an error. Checked in tier-1 against
``data/tiny_ps.xplane.pb``, recorded on a v5e. ``run.py breakdown()``
does not call this yet (PERF.md section 7).
"""

from __future__ import annotations

import os
import sys
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmark.trace_reduce import Interval, reduce_trace  # noqa: E402

PROGRAM_PREFIX = "bps."
# which thread a line is, from the spans only that thread runs, in the
# order tried (an export worker also runs submit; the router also routes)
ROLE_OF = (("bps.step.", "train"), ("bps.export.tap", "callback"),
           ("bps.export.route", "router"), ("bps.export.ingest", "export"),
           ("bps.wire.done", "reactor"), ("bps.wire.send", "send"),
           ("bps.wire.", "wire"), ("bps.codec.", "codec"))
MOST = 0.5


@dataclass
class ProgramSpan:
    name: str
    line: int                 # the thread: index of its line in the file
    start: float              # seconds, the trace's clock
    end: float
    args: Dict[str, object] = field(default_factory=dict)
    depth: int = 0            # spans of the same line around this one
    thread: str = ""          # role, set by ``name_threads``


def read_program_spans(path: str) -> List[ProgramSpan]:
    from jax.profiler import ProfileData

    spans: List[ProgramSpan] = []
    n_line = 0
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            n_line += 1
            for ev in line.events:
                if not ev.name.startswith(PROGRAM_PREFIX):
                    continue
                args = dict(ev.stats)
                if args.get("dropped"):
                    continue  # a duplicate tap fire: nobody's work
                s = ev.start_ns * 1e-9
                spans.append(ProgramSpan(
                    ev.name, n_line, s, s + ev.duration_ns * 1e-9, args))
    nest(spans)
    name_threads(spans)
    return spans


def nest(spans: Sequence[ProgramSpan]) -> None:
    """``depth`` of each span: how many spans of its own line hold it."""
    by_line: Dict[int, List[ProgramSpan]] = {}
    for sp in spans:
        by_line.setdefault(sp.line, []).append(sp)
    for line in by_line.values():
        open_ends: List[float] = []
        for sp in sorted(line, key=lambda s: (s.start, -s.end)):
            while open_ends and sp.start >= open_ends[-1]:
                open_ends.pop()
            sp.depth = len(open_ends)
            open_ends.append(sp.end)


def name_threads(spans: Sequence[ProgramSpan]) -> Dict[int, str]:
    """A name for each line from what it runs: ``train``, ``callback``,
    ``router``, ``export-d<k>`` (a per-device worker, by its spans'
    ``dev``), ``send``, ``reactor``; numbered where several lines share
    one (XLA's callback threads, the push pool)."""
    names_on: Dict[int, set] = {}
    devs_on: Dict[int, set] = {}
    for sp in spans:
        names_on.setdefault(sp.line, set()).add(sp.name)
        if sp.name == "bps.export.ingest" and "dev" in sp.args:
            devs_on.setdefault(sp.line, set()).add(sp.args["dev"])
    routed = any("bps.export.route" in n for n in names_on.values())
    role: Dict[int, str] = {}
    for line, names in names_on.items():
        for prefix, r in ROLE_OF:
            if any(n.startswith(prefix) for n in names):
                role[line] = r
                break
        if role[line] == "export":
            devs = sorted(devs_on.get(line, ()))
            role[line] = (f"export-d{devs[0]}" if routed and len(devs) == 1
                          else "router")
    shared: Dict[str, List[int]] = {}
    for line, r in sorted(role.items()):
        shared.setdefault(r, []).append(line)
    for r, lines in shared.items():
        if len(lines) > 1:
            for k, line in enumerate(lines):
                role[line] = f"{r}-{k}"
    for sp in spans:
        sp.thread = role[sp.line]
    return role


def covered(spans: Sequence[ProgramSpan], gap: Interval
            ) -> Dict[str, Dict[str, Tuple[float, int]]]:
    """thread -> span name -> (share of the gap inside spans of that
    name, their depth). Spans of one name on one thread never overlap,
    so the shares of one depth add up to at most 1."""
    lo, hi = gap
    out: Dict[str, Dict[str, Tuple[float, int]]] = {}
    for sp in spans:
        c = min(sp.end, hi) - max(sp.start, lo)
        if c <= 0:
            continue
        share, depth = out.setdefault(sp.thread, {}).get(sp.name, (0.0, 0))
        out[sp.thread][sp.name] = (share + c / (hi - lo),
                                   max(depth, sp.depth))
    return out


def name_gap_by_thread(spans: Sequence[ProgramSpan], gap: Interval
                       ) -> Dict[str, Tuple[str, float]]:
    """thread -> (the deepest span name that covers most of the gap, its
    share); where none covers most of it, ``mostly_idle`` with the share
    of the gap the thread spent outside every program span."""
    out = {}
    inside = covered(spans, gap)
    for thread in sorted({sp.thread for sp in spans}):
        shares = inside.get(thread, {})
        most = [(depth, share, name) for name, (share, depth)
                in shares.items() if share >= MOST]
        if most:
            depth, share, name = max(most)
            out[thread] = (name, share)
        else:
            busy = sum(s for s, d in shares.values() if d == 0)
            out[thread] = ("mostly_idle", 1.0 - busy)
    return out


def pair_wire(spans: Sequence[ProgramSpan]
              ) -> List[Tuple[int, ProgramSpan, ProgramSpan]]:
    """(rid, send, done) of each request both of whose spans are in the
    trace: the time from ``send.end`` to ``done.start`` is the request's
    time in flight and at the server."""
    sends = {sp.args.get("rid"): sp for sp in spans
             if sp.name == "bps.wire.send"}
    return [(sp.args["rid"], sends[sp.args["rid"]], sp) for sp in spans
            if sp.name == "bps.wire.done" and sp.args.get("rid") in sends
            and sp.args.get("rid")]


def totals(spans: Sequence[ProgramSpan], window: Optional[Interval] = None
           ) -> List[Tuple[str, str, int, float]]:
    """(thread, span name, events, seconds) inside the window, the
    threads in order of their busiest span."""
    acc: Dict[Tuple[str, str], List[float]] = {}
    for sp in spans:
        lo, hi = window or (sp.start, sp.end)
        if sp.end < lo or sp.start > hi:
            continue
        row = acc.setdefault((sp.thread, sp.name), [0, 0.0])
        row[0] += 1
        row[1] += min(sp.end, hi) - max(sp.start, lo)
    return sorted(((t, n, int(k), s) for (t, n), (k, s) in acc.items()),
                  key=lambda r: (r[0], -r[3]))


def attribute(path: str, n_gaps: int = 5) -> dict:
    """The reduction of one traced window: its longest idle gaps, each
    named by thread; the seconds per thread and span name; the wire's
    pairs."""
    reduced = reduce_trace(path)
    spans = read_program_spans(path)
    (lo, hi), = reduced.spans["bench.window"][:1]
    pairs = pair_wire(spans)
    return {
        "window_s": reduced.window_s,
        "idle_share": reduced.idle_share,
        "threads": sorted({sp.thread for sp in spans}),
        "gaps": [{"start_s": g[0] - lo, "seconds": g[1] - g[0],
                  "by_thread": name_gap_by_thread(spans, g)}
                 for g in reduced.gaps[:n_gaps]],
        "totals": totals(spans, (lo, hi)),
        "wire_pairs": len(pairs),
        "wire_in_flight_s": sorted(d.start - s.end for _, s, d in pairs),
    }


def main(argv: Sequence[str]) -> int:
    if not 2 <= len(argv) <= 3:
        print(__doc__)
        return 2
    out = attribute(argv[1], int(argv[2]) if len(argv) == 3 else 5)
    print(f"window {out['window_s']:.6f} s, idle share "
          f"{out['idle_share']:.4f}, threads {out['threads']}")
    for g in out["gaps"]:
        print(f"gap at +{g['start_s']:.6f} s, {g['seconds']:.6f} s:")
        for thread, (name, share) in sorted(g["by_thread"].items()):
            print(f"    {thread:12s} {name} ({100 * share:.1f} %)")
    print("seconds inside the window, by thread and span:")
    for thread, name, k, s in out["totals"]:
        print(f"    {thread:12s} {name:24s} {k:6d} events {s:.6f} s")
    fl = out["wire_in_flight_s"]
    if fl:
        print(f"wire: {out['wire_pairs']} requests paired by rid; send's "
              f"end to done's start: median {fl[len(fl) // 2]:.6f} s, "
              f"max {fl[-1]:.6f} s, sum {sum(fl):.6f} s")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
