"""Chrome-trace communication timeline + fleet-fused dump.

Reference: BYTEPS_TRACE_ON dumps per-(tensor, stage) spans to
``trace_dir/<local_rank>/comm.json`` in Chrome trace-event format
(byteps/common/global.cc:448-564, docs/timeline.md). We reproduce the same
file format. Every span goes through ONE primitive, ``span``, which
also enters a ``jax.profiler.TraceAnnotation`` (so the span is in any
open profiler session's device trace) and feeds the open step's
StepReport (core/metrics.py).

Beyond the reference: ``Tracer.dump()`` emits ONE fused timeline — the
worker's wire spans plus every server's wire-sampled stage spans
(recv → queue-wait → fold → reply, drained over the TRACE_DRAIN control
op), clock-aligned via NTP-style offset estimation
(``estimate_clock_offset``) and rid-linked with Chrome flow events, so
a slow round is attributable to a specific server stage on a single
timeline (docs/timeline.md).
"""

from __future__ import annotations

import json
import os
import re
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from jax.profiler import TraceAnnotation as _trace_me

from ..config import Config

# synthetic pid base for server rows in the fused timeline (worker
# events keep the real os.getpid(); Chrome/Perfetto group rows by pid)
_SERVER_PID_BASE = 1000000


def estimate_clock_offset(
        samples: Sequence[Tuple[int, int, int, int]]) -> Tuple[int, int]:
    """NTP-style clock-offset estimate from request/reply timestamp
    echoes. Each sample is ``(t0, t1, t2, t3)``: client send, server
    recv, server send, client recv — t0/t3 on the client's steady
    clock, t1/t2 on the server's. For one sample the classic estimate
    is ``offset = ((t1 - t0) + (t2 - t3)) / 2`` with the true offset
    guaranteed inside ``± rtt/2`` where ``rtt = (t3-t0) - (t2-t1)``
    (the bound is tight under asymmetric path delay — one direction
    may consume the whole rtt). Across samples the MINIMUM-rtt probe
    carries the tightest bound, so that one decides.

    Returns ``(offset_ns, err_bound_ns)`` with
    ``server_clock - offset ≈ client_clock``.
    """
    if not samples:
        raise ValueError("estimate_clock_offset needs >= 1 sample")
    best = None
    for t0, t1, t2, t3 in samples:
        rtt = (t3 - t0) - (t2 - t1)
        if rtt < 0:
            continue  # nonsensical echo (clock step mid-probe): skip
        off = ((t1 - t0) + (t2 - t3)) // 2
        if best is None or rtt < best[1]:
            best = (off, rtt)
    if best is None:
        raise ValueError("every probe had negative rtt — broken echoes")
    # bound: half the round trip, plus 1ns so a zero-rtt synthetic
    # sample still reports a nonzero, honest uncertainty
    return int(best[0]), int(best[1] // 2 + 1)


# --------------------------------------------------------------------- #
# the span primitive: one begin/end path for every program span
# --------------------------------------------------------------------- #

# Fixed span names (PERF.md section 3 lists them with the thread each
# runs on and the StepReport field or metric that reads it). The
# identifiers of one span (step, leaf, key, rid, bytes, cause) are its
# ARGUMENTS, never part of its name.
STEP_DISPATCH = "bps.step.dispatch"
STEP_CLAIM = "bps.step.claim"
STEP_BACKWARD_WAIT = "bps.step.backward_wait"
STEP_BACKWARD_PROGRAM = "bps.step.backward_program"
STEP_DRAIN = "bps.step.drain"
STEP_HOST_CPU = "bps.step.host_cpu"
EXPORT_INGEST = "bps.export.ingest"
EXPORT_MATERIALIZE = "bps.export.materialize"
EXPORT_SUBMIT = "bps.export.submit"
EXPORT_BUCKET_MEMBER = "bps.export.bucket_member"
WIRE_SEND = "bps.wire.send"
WIRE_DONE = "bps.wire.done"
WIRE_PUSH = "bps.wire.push"
WIRE_PULL = "bps.wire.pull"
CODEC_COMPRESS = "bps.codec.compress"
CODEC_DECOMPRESS = "bps.codec.decompress"
APPLY_H2D_UPDATE = "bps.apply.h2d_update"
APPLY_ALLGATHER = "bps.apply.allgather"
APPLY_BEGIN = "bps.apply.begin"
APPLY_FINISH = "bps.apply.finish"
APPLY_ASSEMBLE = "bps.apply.assemble"

_get_state = None  # core.state.get_state, imported on first use (cycle)


class span:
    """One program span, begun and ended on ONE thread:

        with span(EXPORT_INGEST, step=tag, leaf=i) as sp:
            ...
            sp.set(partitions=n)     # what is only known inside

    (``start()``/``stop()`` where a ``with`` block does not fit; ``stop``
    is idempotent.) Always on, it lands in three places from this one
    call:

    - a ``jax.profiler.TraceAnnotation`` named ``stage`` with the
      arguments as its metadata: a flag test when no profiler session is
      open, and an event on this thread's host line, on the clock of the
      ``XLA Ops`` lines, of whichever session is (the benchmark's traced
      window, ``BYTEPS_JAX_PROFILER_DIR``, an operator's own
      ``jax.profiler.trace``);
    - ``(stage, thread, start, end, args)`` on ``time.perf_counter`` in
      the open step's ``_StepBuilder`` (``state.profiler.current()``),
      which ``end_step`` reduces into the StepReport's export fields;
      nothing with ``BYTEPS_METRICS=0`` (no builder);
    - a Chrome ``comm.json`` event (row ``tid``, default the thread's
      name) where a ``Tracer`` exists and its step window is open.
    """

    __slots__ = ("stage", "tid", "args", "t0", "t1", "_ann")

    def __init__(self, stage: str, tid: Optional[str] = None, **args):
        self.stage = stage
        self.tid = tid
        self.args = args
        self.t0: Optional[float] = None
        self.t1: Optional[float] = None
        self._ann = None

    def __enter__(self) -> "span":
        if _trace_me.is_enabled():
            self._ann = _trace_me(self.stage, **self.args)
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    start = __enter__

    def set(self, **args) -> None:
        self.args.update(args)
        if self._ann is not None:
            self._ann.set_metadata(**args)

    def __exit__(self, *exc) -> bool:
        if self.t0 is None or self.t1 is not None:
            return False
        self.t1 = t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        global _get_state
        if _get_state is None:
            from ..core.state import get_state
            _get_state = get_state
        state = _get_state()
        builder = state.profiler.current()
        tracer = state.tracer
        if builder is not None or tracer is not None:
            thread = threading.current_thread().name
            if builder is not None:
                builder.add_span(self.stage, thread, self.t0, t1, self.args)
            if tracer is not None:
                tracer.record(self.stage, self.tid or thread, self.t0, t1,
                              self.args)
        return False

    def stop(self) -> None:
        self.__exit__(None, None, None)


# --------------------------------------------------------------------- #
# who burned the CPU: the process's threads, by name
# --------------------------------------------------------------------- #

_TRAILING_NUMBER = re.compile(r"[-_/:. ]*\d+$")


def thread_cpu_ms(root: str = "/proc/self/task") -> Dict[str, float]:
    """CPU milliseconds (user + system, since each started) of every
    thread of this process, summed by thread name with its trailing
    number cut (``bps-push_3`` -> ``bps-push``): the kernel's
    ``<root>/<tid>/stat``, in clock ticks of 10 ms. A thread Python
    started goes by its Python name (this interpreter does not hand it
    to the kernel); every other by the name its maker gave it (the
    runtime's pools), or the process's where it gave none (the native
    client's). ``{}`` where there is no such tree (not Linux)."""
    python_names = {t.native_id: t.name for t in threading.enumerate()}
    ms_per_tick = 1e3 / os.sysconf("SC_CLK_TCK")
    out: Dict[str, float] = {}
    try:
        tids = os.listdir(root)
    except OSError:
        return out
    for tid in tids:
        # three system calls a thread: on the chip machines, whose /proc
        # is slow, ``open()``'s six and a glob's one more tripled a
        # reading
        try:
            fd = os.open(f"{root}/{tid}/stat", os.O_RDONLY)
        except OSError:
            continue  # the thread ended between the listing and the read
        try:
            stat = os.read(fd, 1024).decode()
        finally:
            os.close(fd)
        # "<tid> (<name, which may hold spaces and brackets>) <state> ..."
        # : utime and stime are the 12th and 13th fields after the name
        name = stat[stat.index("(") + 1:stat.rindex(")")]
        fields = stat[stat.rindex(")") + 2:].split()
        name = _TRAILING_NUMBER.sub("", python_names.get(int(tid), name)) \
            or name
        out[name] = out.get(name, 0.0) \
            + (int(fields[11]) + int(fields[12])) * ms_per_tick
    return out


def cpu_ms_by_thread(before: Dict[str, float], after: Dict[str, float],
                     top: int = 8) -> Dict[str, float]:
    """The ``top`` thread names that used most CPU between two
    ``thread_cpu_ms`` readings, with their milliseconds."""
    used = {name: ms - before.get(name, 0.0) for name, ms in after.items()}
    return dict(sorted(((n, ms) for n, ms in used.items() if ms > 0),
                       key=lambda kv: -kv[1])[:top])


class Tracer:
    """The Chrome-trace half: ``comm.json`` events of the spans that end
    inside the step window (``BYTEPS_TRACE_START_STEP``..``END_STEP``),
    and the fused fleet dump. Spans reach it through ``span`` alone."""

    def __init__(self, config: Config):
        self._config = config
        self._lock = threading.Lock()
        self._events: List[dict] = []
        self._step = 0
        # one origin on both clocks: spans are timed on perf_counter,
        # server stamps arrive on the steady (monotonic) clock
        self._t0_ns = time.monotonic_ns()
        self._t0_pc = time.perf_counter()
        # fused-dump hook (core/state.py): () -> [{"server": idx,
        # "offset_ns": o, "err_ns": e, "records": [TraceRec dicts]}]
        self._server_collector: Optional[Callable[[], list]] = None

    def active(self) -> bool:
        """Whether the step window is open."""
        return (self._config.trace_on and
                self._config.trace_start_step <= self._step <= self._config.trace_end_step)

    def step(self) -> None:
        do_flush = False
        with self._lock:
            self._step += 1
            if self._step == self._config.trace_end_step + 1:
                do_flush = True
        if do_flush:
            self.flush()

    def record(self, stage: str, tid: str, t0: float, t1: float,
               args: dict) -> None:
        """One finished span (``span.__exit__``): a complete (``ph:
        "X"``) event on row ``tid`` when the step window is open
        (reference: core_loops.cc:69-91). The wire stage's ``rid``
        argument is what the fused dump flow-links on."""
        if not self.active():
            return
        ev = {
            "name": stage, "cat": "comm", "ph": "X",
            "ts": (t0 - self._t0_pc) * 1e6, "dur": (t1 - t0) * 1e6,
            "pid": os.getpid(), "tid": tid, "args": dict(args),
        }
        with self._lock:
            self._events.append(ev)

    def flush(self, path: Optional[str] = None) -> Optional[str]:
        """Dump comm.json (reference: global.cc:448-564)."""
        with self._lock:
            if not self._events:
                return None
            out_dir = path or os.path.join(
                self._config.trace_dir, str(self._config.local_rank))
            os.makedirs(out_dir, exist_ok=True)
            out_path = os.path.join(out_dir, "comm.json")
            with open(out_path, "w") as f:
                json.dump({"traceEvents": self._events,
                           "displayTimeUnit": "ms"}, f)
            return out_path

    # ---------------------------------------------------------------- #
    # fused fleet timeline (docs/timeline.md)
    # ---------------------------------------------------------------- #

    def set_server_collector(self, fn: Callable[[], list]) -> None:
        """Install the fleet hook dump() drains server spans through:
        ``fn()`` returns one entry per server — ``{"server": idx,
        "offset_ns": o, "err_ns": e, "records": [...]}`` with records
        in the TRACE_DRAIN wire shape (server/__init__.py
        ``_TRACE_REC_FIELDS``). Wired by core/state.py at init; tests
        may install synthetic collectors."""
        self._server_collector = fn

    def _server_us(self, server_ns: int, offset_ns: int) -> float:
        """Map a server steady-clock ns stamp onto this tracer's
        microsecond timeline: subtract the estimated offset
        (server_clock - offset ≈ client_clock), then rebase on t0."""
        return ((server_ns - offset_ns) - self._t0_ns) / 1e3

    def dump(self, path: Optional[str] = None) -> Optional[str]:
        """Emit ONE Chrome trace fusing the worker's comm spans with
        every server's wire-sampled stage spans: servers land on their
        own synthetic pid rows (process_name metadata names them),
        each sampled request renders as recv → queue-wait → fold spans
        (plus a reply span once its aggregate left), clock-aligned via
        the collector's NTP-style offsets, and rid-linked to the worker
        span that carries the same rid with Chrome flow events — a slow
        round reads as a single arrow from the worker's PUSHPULL span
        into the server stage that ate the time.

        Writes ``<trace_dir>/<local_rank>/fused.json`` (or ``path``)
        and returns it; returns None when there is nothing at all to
        dump (no worker events AND no server records)."""
        with self._lock:
            # a recorded event is never touched again: a shallow copy of
            # the list is a frozen snapshot
            events = list(self._events)
        fused: List[dict] = [{
            "name": "process_name", "ph": "M", "pid": os.getpid(),
            "args": {"name": f"bps-worker rank "
                             f"{self._config.local_rank}"},
        }]
        fused += events
        # worker spans by rid: the flow arrows start inside the span
        # that put the request on the wire (its completion's span,
        # bps.wire.done, carries the same rid)
        rid_spans = {e["args"]["rid"]: e for e in events
                     if e.get("ph") == "X" and e["name"] != WIRE_DONE
                     and e["args"].get("rid")}
        flows = 0
        collected = self._server_collector() if self._server_collector \
            else []
        for entry in collected or []:
            idx = int(entry.get("server", 0))
            off = int(entry.get("offset_ns", 0))
            pid = _SERVER_PID_BASE + idx
            fused.append({
                "name": "process_name", "ph": "M", "pid": pid,
                "args": {"name": f"bps-server {idx}",
                         "clock_offset_ns": off,
                         "clock_err_ns": int(entry.get("err_ns", 0))}})
            # reply events joined to their request span by (rid, sender)
            replies = {}
            for rec in entry.get("records", []):
                if rec.get("kind") == 1:
                    replies[(rec["rid"], rec["sender"])] = rec
            for rec in entry.get("records", []):
                if rec.get("kind") != 0:
                    continue
                tid = f"key {rec['key']}"
                args = {"rid": rec["rid"], "sender": rec["sender"],
                        "op": rec["op"], "key": rec["key"]}
                stages = (("recv", rec["t0"], rec["t1"]),
                          ("queue-wait", rec["t1"], rec["t2"]),
                          ("fold", rec["t2"], rec["t3"]))
                for sname, a, b in stages:
                    if not a or b < a:
                        continue  # PULLs skip recv; clamp bad stamps
                    fused.append({
                        "name": sname, "cat": "server", "ph": "X",
                        "ts": self._server_us(a, off),
                        "dur": max((b - a) / 1e3, 0.001),
                        "pid": pid, "tid": tid, "args": args})
                rep = replies.pop((rec["rid"], rec["sender"]), None)
                if rep is not None:
                    if rep["t0"] >= rec["t3"]:
                        # parked round: the wait + the aggregate send
                        fused.append({
                            "name": "reply", "cat": "server", "ph": "X",
                            "ts": self._server_us(rec["t3"], off),
                            "dur": max((rep["t0"] - rec["t3"]) / 1e3,
                                       0.001),
                            "pid": pid, "tid": tid, "args": args})
                    else:
                        # same-invocation reply (round completed inside
                        # THIS handler): the send instant sits inside
                        # the fold span — render a thin marker so the
                        # reply leg is visible either way
                        fused.append({
                            "name": "reply", "cat": "server", "ph": "X",
                            "ts": self._server_us(rep["t0"], off),
                            "dur": 0.001,
                            "pid": pid, "tid": tid, "args": args})
                # rid flow link: worker span -> this request's first
                # server stage (Chrome binds flow ends to the slice
                # enclosing ts on that pid/tid row)
                wspan = rid_spans.get(rec["rid"])
                if wspan is not None:
                    t_anchor = rec["t1"] if not rec["t0"] else rec["t0"]
                    fused.append({
                        "name": "rid", "cat": "bps-rid", "ph": "s",
                        "id": rec["rid"],
                        "ts": wspan["ts"] + 0.001,
                        "pid": wspan["pid"], "tid": wspan["tid"]})
                    fused.append({
                        "name": "rid", "cat": "bps-rid", "ph": "f",
                        "bp": "e", "id": rec["rid"],
                        "ts": self._server_us(t_anchor, off) + 0.001,
                        "pid": pid, "tid": tid})
                    flows += 1
        if not events and not collected:
            return None
        out_path = path
        if out_path is None:
            out_dir = os.path.join(self._config.trace_dir,
                                   str(self._config.local_rank))
            os.makedirs(out_dir, exist_ok=True)
            out_path = os.path.join(out_dir, "fused.json")
        else:
            parent = os.path.dirname(os.path.abspath(out_path))
            os.makedirs(parent, exist_ok=True)
        with open(out_path, "w") as f:
            json.dump({"traceEvents": fused, "displayTimeUnit": "ms",
                       "metadata": {"rid_flow_links": flows}}, f)
        return out_path
