"""From the profiler's ``.xplane.pb`` to the numbers the benchmark
reports: the seconds in which an operation ran on each device (the union
of the operations' intervals), the idle gaps between them, the time per
operation name, and the benchmark's own host spans on the same clock.
Reads the file with nothing but JAX. Checked in tier-1 against
``data/tiny.xplane.pb``, recorded on a v5e.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
_SUFFIX = re.compile(r"[.\-_]?\d+$")

Interval = Tuple[float, float]  # (start, end), seconds


@dataclass
class Reduced:
    window_s: float
    busy_s: Dict[int, float]                  # device ordinal -> seconds
    op_seconds: List[Tuple[str, float]]       # summed over devices, sorted
    gaps: List[Interval]                      # device 0's idle gaps, longest first
    spans: Dict[str, List[Interval]] = field(default_factory=dict)

    @property
    def busy_mean_s(self) -> float:
        return sum(self.busy_s.values()) / len(self.busy_s)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_mean_s / self.window_s


def op_family(name: str) -> str:
    """``fusion.123`` -> ``fusion``: one row per kind of operation."""
    base = name.split(" = ")[0].lstrip("%")
    while True:
        cut = _SUFFIX.sub("", base)
        if cut == base or not cut:
            return base
        base = cut


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def self_times(ops: Sequence[Tuple[str, float, float]]
               ) -> List[Tuple[str, float]]:
    """(name, seconds) per event with the time of the events nested in
    it taken out: a ``while`` holds its body's operations on the same
    line, and its own time is what they leave."""
    out: List[List] = []
    stack: List[int] = []          # indices into ``out`` of open events
    ends: List[float] = []
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and s >= ends[-1]:
            stack.pop()
            ends.pop()
        if stack:
            out[stack[-1]][1] -= min(e, ends[-1]) - s
        out.append([name, e - s])
        stack.append(len(out) - 1)
        ends.append(e)
    return [(n, max(d, 0.0)) for n, d in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def reduce_trace(path: str, window_span: str = SPAN_PREFIX + "window"
                 ) -> Reduced:
    """The window is the host span named ``window_span``; only what lies
    inside it is counted. A trace without that span, or without a device
    operation inside it, is an error: the traced run drove no device."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    spans: Dict[str, List[Interval]] = {}
    device_ops: Dict[int, List[Tuple[str, float, float]]] = {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m:
                if line.name != OPS_LINE:
                    continue
                ops = device_ops.setdefault(int(m.group(1)), [])
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    ops.append((ev.name, s, s + ev.duration_ns * 1e-9))
            elif plane.name.startswith("/host:"):
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = ev.start_ns * 1e-9
                        spans.setdefault(ev.name, []).append(
                            (s, s + ev.duration_ns * 1e-9))
    if window_span not in spans:
        raise ValueError(f"{path}: no host span {window_span!r}")
    lo, hi = spans[window_span][0]
    busy: Dict[int, float] = {}
    per_op: Dict[str, float] = {}
    gaps: List[Interval] = []
    for dev, ops in sorted(device_ops.items()):
        inside = clip([(s, e) for _, s, e in ops], lo, hi)
        merged = union(inside)
        busy[dev] = sum(e - s for s, e in merged)
        for name, d in self_times(
                [(n, max(s, lo), min(e, hi)) for n, s, e in ops
                 if min(e, hi) > max(s, lo)]):
            fam = op_family(name)
            per_op[fam] = per_op.get(fam, 0.0) + d
        if dev == min(device_ops):
            edges = [lo] + [t for iv in merged for t in iv] + [hi]
            gaps = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    if not busy or max(busy.values()) <= 0.0:
        raise ValueError(f"{path}: no device operation inside the window")
    gaps.sort(key=lambda g: g[0] - g[1])
    return Reduced(
        window_s=hi - lo, busy_s=busy,
        op_seconds=sorted(per_op.items(), key=lambda kv: -kv[1]),
        gaps=gaps, spans={k: sorted(v) for k, v in spans.items()})


def describe(path: str) -> List[str]:
    """One row per line of every plane: how many events, how long in
    all, and a few names. For looking at a trace before trusting a
    reduction of it."""
    from jax.profiler import ProfileData

    rows = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            events = list(line.events)
            if not events:
                continue
            total = sum(e.duration_ns for e in events) * 1e-9
            names = sorted({op_family(e.name) for e in events})[:6]
            rows.append(f"{plane.name} | {line.name} | {len(events)} events "
                        f"| {total:.6f} s | {names}")
    return rows


def name_gap(gap: Interval, named: Sequence[Tuple[str, Interval]]) -> str:
    """The benchmark's span that covers most of the gap."""
    best, cover = "outside_step", 0.0
    for name, (s, e) in named:
        c = min(e, gap[1]) - max(s, gap[0])
        if c > cover:
            best, cover = name, c
    return best
