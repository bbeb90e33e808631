"""Process-wide byteps_tpu state: the TPU analogue of BytePSGlobal.

The reference's global singleton (byteps/common/global.{h,cc}) owns rank/size,
the NCCL manager, 12 scheduled queues, ready tables, shm and the PS
connection. Here the same role shrinks to: config snapshot, tensor registry,
the device mesh, the (optional) DCN PS client, telemetry, and the trace
recorder — because XLA's compiled dataflow replaces the hand-built pipeline
for everything that stays on-device.

Lifecycle mirrors the reference C ABI (operations.cc:34-129):
``init -> [declare/push_pull]* -> suspend -> resume -> shutdown``.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Optional

import jax

from ..config import Config
from ..parallel import mesh as mesh_lib
from ..utils.logging import log, refresh_level, bps_check
from .metrics import MetricsRegistry, StepProfiler
from .registry import TensorRegistry


class _Telemetry:
    """push_pull byte-rate telemetry (reference: global.cc:697-752).

    Aggregates bytes of finished push_pulls into ~10-second MB/s samples,
    surfaced by ``bps.get_pushpull_speed()``.
    """

    WINDOW_SEC = 10.0

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._window_start = time.monotonic()   # guarded-by: _lock
        self._window_bytes = 0                  # guarded-by: _lock
        # (timestamp, MB/s)
        self._last_sample = (0.0, 0.0)          # guarded-by: _lock
        self.enabled = True  # BYTEPS_TELEMETRY_ON; set by GlobalState.init
        # registry mirror (core/metrics.py), set by GlobalState.init:
        # every recorded byte also lands on the unified counter surface
        self._wire_counter = None

    def attach_metrics(self, metrics) -> None:
        self._wire_counter = metrics.counter("pushpull/bytes_total")

    def record(self, nbytes: int) -> None:
        if self._wire_counter is not None:
            self._wire_counter.inc(int(nbytes))
        if not self.enabled:
            return
        with self._lock:
            now = time.monotonic()
            self._window_bytes += nbytes
            elapsed = now - self._window_start
            if elapsed >= self.WINDOW_SEC:
                mbps = self._window_bytes / elapsed / 1e6
                self._last_sample = (now, mbps)
                self._window_start = now
                self._window_bytes = 0

    def record_round_trip(self, nbytes: int) -> None:
        """THE adapter byte-accounting entry point for a symmetric
        push+pull round trip (``nbytes`` each way): one definition
        behind one registry counter, so the mxnet/tf/jax async adapters
        can't drift apart in how they count wire bytes (they used to
        hand-roll ``record(nbytes * 2)`` each)."""
        self.record(int(nbytes) * 2)

    def speed(self) -> tuple:
        with self._lock:
            return self._last_sample

    # --- host staging arena surface (core/arena.py) ------------------- #

    def attach_arena(self, arena) -> None:
        self._arena = arena

    def arena_stats(self) -> dict:
        """Live staging-arena counters (slots live, bytes pinned,
        allocations avoided, checkout conflicts) merged with the
        export-stage counters below; zeros before init."""
        arena = getattr(self, "_arena", None)
        if arena is None:
            from .arena import StagingArena
            stats = StagingArena(enabled=False).stats()
        else:
            stats = arena.stats()
        stats.update(self.export_stats())
        return stats

    # --- export stage counters (jax/train.py) ------------------------- #

    def record_export(self, leaves: int, ttfp_s: Optional[float],
                      shard_leaves: int = 0) -> None:
        """One PS train round's export accounting: how many gradient
        leaves left the chip (outputs of the backward, claimed by the
        train thread), how many of them as per-device shards, and the
        round's time-to-first-push (first submit entering the
        scheduler, measured from the backward's dispatch). Cumulative
        counters + the last round's TTFP let tests and chip_smoke.py
        assert the plan ran as often as it says."""
        with self._lock:
            self._export_leaves = \
                getattr(self, "_export_leaves", 0) + int(leaves)
            self._export_rounds = getattr(self, "_export_rounds", 0) + 1
            # leaves that left the device as per-device reduce-scatter
            # shards (BYTEPS_LOCAL_SHARD_EXPORT); the shard A/B asserts
            # this engaged instead of silently riding the whole-leaf
            # path
            self._export_shard_leaves = \
                getattr(self, "_export_shard_leaves", 0) + int(shard_leaves)
            if ttfp_s is not None:
                self._export_ttfp_ms = ttfp_s * 1e3

    def export_stats(self) -> dict:
        with self._lock:
            return {
                "export_leaves": getattr(self, "_export_leaves", 0),
                "export_rounds": getattr(self, "_export_rounds", 0),
                "export_shard_leaves": getattr(
                    self, "_export_shard_leaves", 0),
                "export_ttfp_ms": getattr(self, "_export_ttfp_ms", None),
            }


class GlobalState:
    """Singleton holding all process-wide framework state."""

    _instance: Optional["GlobalState"] = None
    _instance_lock = threading.Lock()

    def __init__(self) -> None:
        self.config: Config = Config()
        self.registry: Optional[TensorRegistry] = None
        self.mesh = None
        self.initialized = False
        self.suspended = False
        self.telemetry = _Telemetry()
        # unified metrics registry + per-step pipeline profiler
        # (core/metrics.py); replaced fresh at init() so counters start
        # clean per lifecycle, like the arena
        self.metrics = MetricsRegistry()
        self.profiler = StepProfiler()
        self._metrics_server = None  # BYTEPS_METRICS_PORT http server
        self.tracer = None           # set lazily by utils.tracing
        self._jax_profiling = False  # jax.profiler trace active
        self.ps_client = None        # set by server.client when PS configured
        self.scheduler = None        # PipelineScheduler over ps_client
        self.handles = None          # HandleManager for the async API
        self.codec_plane = None      # adaptive codec plane (codec_plane.py)
        self.autoscaler = None       # autoscaler plane (autoscaler.py)
        self.ledger = None           # step efficiency ledger (ledger.py)
        self.health = None           # training-health plane (health.py)
        self.timeseries = None       # time-series plane (timeseries.py)
        # server spawn hook for the autoscaler's acting "add" path:
        # fn(index) -> "host:port" of a freshly-started server (or None
        # to decline); survives re-init (operator wiring, not lifecycle
        # state)
        self.server_spawn_hook = None
        self.flight = None           # crash flight recorder (flight.py)
        # persistent host staging arena (core/arena.py); replaced with an
        # enabled instance at init() when BYTEPS_STAGING_ARENA is on —
        # a disabled arena hands out fresh buffers with identical
        # semantics, so callers never need to branch on it
        from .arena import StagingArena
        self.arena = StagingArena(enabled=False)
        self._version: Dict[str, int] = {}
        self._lock = threading.Lock()

    # ------------------------------------------------------------------ #

    @classmethod
    def get(cls) -> "GlobalState":
        with cls._instance_lock:
            if cls._instance is None:
                cls._instance = GlobalState()
            return cls._instance

    def init(self, config: Optional[Config] = None, mesh=None,
             lazy: bool = False) -> None:
        with self._lock:
            if self.initialized and not self.suspended:
                if config is not None or mesh is not None:
                    log.warning(
                        "init() called with explicit config/mesh while "
                        "already initialized — arguments ignored; call "
                        "shutdown() first to re-initialize")
                return
            refresh_level()
            self.config = config or Config.from_env()
            self.telemetry.enabled = self.config.telemetry_on
            # fresh arena per init: counters start clean, and a resumed
            # worker with a new topology never reuses stale-sized slots
            from .arena import StagingArena
            self.arena = StagingArena(enabled=self.config.staging_arena)
            self.telemetry.attach_arena(self.arena)
            # fresh metrics plane per init (counters clean per
            # lifecycle, like the arena); live sections collect the
            # arena/export counters at snapshot time — one source of
            # truth, no double accounting
            self.metrics = MetricsRegistry(enabled=self.config.metrics_on)
            self.telemetry.attach_metrics(self.metrics)
            self.metrics.section("arena", self.telemetry.arena_stats)
            # per-stage server data-plane counters (recv → queue-wait →
            # fold → reply; native/ps.cc StageStats): live-collected
            # from servers running IN THIS PROCESS (the loopback
            # test/bench topology); fixed keys reading 0 when the fleet
            # is remote, so the documented schema resolves everywhere
            from ..server import stage_section
            self.metrics.section("server", stage_section)
            # fleet section: per-server registry snapshots — over the
            # STATS_PULL control op when a fleet-capable client is
            # connected (subprocess/remote servers stop being black
            # boxes), in-process mirror otherwise (docs/observability
            # .md "fleet"); bps.get_fleet_metrics() and the Prometheus
            # endpoint both read this one section
            self.metrics.section("fleet", self._fleet_section)
            # fresh breakers per init (per-step probe + snapshot sweep
            # + the per-lane stripe probe)
            self._fleet_probe_tripped = False
            self._fleet_section_tripped = False
            self._lane_probe_tripped = False
            # crash flight recorder (core/flight.py): bounded event
            # ring armed per lifecycle; events flow in from the fault
            # paths module-level (no plumbing), the dump merges every
            # server's native ring via the collector below
            from . import flight as flight_mod
            self.flight = flight_mod.configure(
                capacity=self.config.flight_ring,
                enabled=self.config.flight_recorder,
                dump_dir=self.config.flight_dir)
            self.metrics.section("flight", self.flight.snapshot)
            flight_mod.set_server_collector(self._collect_server_flight)
            # step efficiency ledger (core/ledger.py): fresh per
            # lifecycle like the metrics plane — the train layer
            # registers each plan's cost model on it, the profiler
            # prices every step against it, and its observer hook
            # drives the perf archive + efficiency_drop flight events
            from .ledger import EfficiencyLedger, register_ledger_metrics
            register_ledger_metrics(self.metrics)
            self.ledger = EfficiencyLedger(self.config, self.metrics)
            self.metrics.section("ledger", self.ledger.snapshot)
            # time-series plane (core/timeseries.py): bounded per-step
            # history rings riding the profiler observer chain; its
            # snapshot is the `timeseries` section (what byteps-top and
            # the HTTP endpoint render), its JSONL dump rides the
            # SIGTERM hook chain pinned FIRST (timeseries → archive →
            # flight dump)
            from .timeseries import TimeSeriesPlane
            self.timeseries = TimeSeriesPlane(
                points=self.config.ts_points,
                enabled=self.config.timeseries and self.config.metrics_on,
                registry=self.metrics,
                dump_dir=self.config.flight_dir)
            self.metrics.section("timeseries", self.timeseries.snapshot)
            if (self.config.flight_recorder or self.ledger.archive_enabled
                    or self.timeseries.enabled):
                flight_mod.install_signal_handler()
            if self.timeseries.enabled:
                flight_mod.add_term_hook(
                    self.timeseries.term_dump,
                    order=flight_mod.TERM_ORDER_TIMESERIES)
            if self.ledger.archive_enabled:
                # the archive flushes on SIGTERM alongside the flight
                # dump (one handler, hooks run first; term_flush uses a
                # bounded lock acquire — the signal may have landed on
                # the thread that holds the archive lock mid-append)
                flight_mod.add_term_hook(self.ledger.term_flush)
            # codec-plane instruments exist on every deployment (the
            # docs/observability.md schema guard resolves them), whether
            # or not the adaptive plane itself is enabled below
            from .codec_plane import register_codec_metrics
            register_codec_metrics(self.metrics)
            # training-health plane (core/health.py, BYTEPS_HEALTH):
            # instruments are eager like the codec family; the plane
            # itself is constructed per lifecycle (fresh detector
            # streaks) and observes steps only when enabled
            from .health import HealthPlane, register_health_metrics
            register_health_metrics(self.metrics)
            self.health = HealthPlane(self.config, self.metrics)
            # elastic-lifecycle instruments too (registry/joins,
            # registry/drains, autoscale/decisions, server/evictions):
            # eagerly created so healthy static fleets export documented
            # zeros, exactly like the wire/retries family
            from .autoscaler import register_autoscale_metrics
            register_autoscale_metrics(self.metrics)
            # cross-barrier carry counters (jax/train.py): eager zeros
            # on sync deployments — the perf gate reads "sync arm
            # carried 0" as a contract, not a missing key
            self.metrics.counter("barrier/carried_leaves")
            self.metrics.counter("barrier/carry_drained")
            # Multi-process topology: rendezvous at the coordination
            # service (the reference's ps::StartPS + barrier,
            # global.cc:283-297) before any device query.
            if (self.config.num_processes > 1
                    and self.config.role == "worker"):
                from ..parallel import distributed as dist_mod
                dist_mod.ensure_initialized(self.config)
                # identity defaults follow the process grid when DMLC_*
                # was not set (global-mesh mode has no "workers")
                if self.config.num_workers <= 1:
                    import dataclasses as _dc
                    pid, pcount = dist_mod.process_identity()
                    self.config = _dc.replace(
                        self.config, num_workers=pcount, worker_id=pid)
            if self.registry is None:
                self.registry = TensorRegistry(self.config)
                self.registry.attach_arena(self.arena)
            else:
                # re-init (elastic resume or shutdown->init with new env):
                # keep declaration order so keys stay stable
                # (global.cc:431-436), but rebind the new config.
                self.registry.attach_arena(self.arena)
                self.registry.redeclare_all(self.config)
            # PS mode with multiple processes: the mesh stays local to
            # this process (ICI collectives intra-process; the DCN PS sums
            # across processes — the reference's NCCL-intra + ps-lite-inter
            # split). Global-mesh mode: one mesh over every process's
            # devices, XLA collectives all the way.
            if mesh is not None:
                self.mesh = mesh
            else:
                local_only = (jax.process_count() > 1
                              and self.config.num_servers > 0
                              and self.config.role == "worker")
                devices = jax.local_devices() if local_only else None
                self.mesh = mesh_lib.make_mesh(
                    self.config.parsed_mesh() or None, devices)
            if self.config.trace_on and self.tracer is None:
                # the Chrome comm.json half only: the program's spans
                # reach any open profiler session without it
                # (utils/tracing.py span)
                from ..utils.tracing import Tracer
                self.tracer = Tracer(self.config)
            # per-step pipeline profiler rides the same lifecycle as the
            # registry
            self.profiler = StepProfiler(
                window=self.config.step_report_window,
                enabled=self.config.metrics_on,
                stall_diag=self.config.stall_diag,
                fleet_probe=self._fleet_stage_probe,
                lane_probe=self._lane_probe,
                ledger=self.ledger)
            self.metrics.section("steps", self.profiler.snapshot)
            if self.health is not None and self.health.enabled:
                # FIRST observer: the detector stamps health_flags on
                # the report before the ledger archives it and before
                # any later observer (autoscaler) — and before the
                # codec plane's lazy ingest reads the ring next round
                self.profiler.add_observer(self.health.on_step)
            if self.ledger is not None and self.ledger.enabled:
                # archive append + efficiency-drop detection per
                # finished step, on the train thread like the
                # autoscaler's sensor tap
                self.profiler.add_observer(self.ledger.on_step)
            if self.timeseries is not None and self.timeseries.enabled:
                # LAST of the init-time observer trio: the recorder
                # samples the report AFTER the health plane stamped
                # health_flags and the ledger priced it, so archived
                # fields land in the series final
                self.profiler.add_observer(self.timeseries.observe)
            if self.tracer is not None:
                # fused-timeline hook: Tracer.dump() drains every
                # server's wire-sampled span ring + clock offset
                # through this (docs/timeline.md)
                self.tracer.set_server_collector(
                    self._collect_server_traces)
            if self.config.jax_profiler_dir and not self._jax_profiling:
                # device (XLA) trace for TensorBoard/Perfetto alongside
                # the Chrome comm timeline (SURVEY §5.1 TPU note); the
                # program's spans are in it as in any other session
                try:
                    jax.profiler.start_trace(self.config.jax_profiler_dir)
                    self._jax_profiling = True
                except Exception as e:  # noqa: BLE001 - profiling is aux
                    log.warning("jax.profiler.start_trace failed: %s", e)
            if (self.config.num_servers > 0
                    and self.config.role == "worker"
                    and jax.process_count() > 1):
                # PS mode must use a process-local mesh: a process-spanning
                # mesh already sums across workers via XLA, and the PS
                # round trip would sum the same values AGAIN (silent 2x
                # gradients). Catches explicitly-passed meshes that bypass
                # the local_only selection above.
                me = jax.process_index()
                if any(d.process_index != me
                       for d in self.mesh.devices.flat):
                    raise ValueError(
                        "PS mode (num_servers > 0) requires a process-local "
                        "mesh; the given mesh spans multiple processes, "
                        "which would double-sum gradients (XLA collective "
                        "+ PS). Use jax.local_devices() for the mesh, or "
                        "set num_servers=0 for global-mesh mode.")
            if (not lazy and self.ps_client is None
                    and self.config.num_servers > 0
                    and self.config.role == "worker"):
                from ..server.client import connect_from_config
                self.ps_client = connect_from_config(self.config)
                self.ps_client.attach_metrics(self.metrics)
                from .scheduler import HandleManager, PipelineScheduler
                self.scheduler = PipelineScheduler(
                    self.ps_client,
                    credit_bytes=self.config.scheduling_credit,
                    telemetry=self.telemetry,
                    config=self.config, arena=self.arena,
                    metrics=self.metrics, profiler=self.profiler,
                    registry=self.registry)
                self.handles = HandleManager()
                if self.config.codec_adapt:
                    # adaptive codec control plane: resolves each
                    # eligible leaf's wire codec per round from the
                    # StepReport signal (core/codec_plane.py)
                    from .codec_plane import CodecPlane
                    self.codec_plane = CodecPlane(
                        self.ps_client, self.registry, self.metrics,
                        self.profiler, self.config.num_workers,
                        scheduler=self.scheduler, config=self.config)
                    self.scheduler.attach_codec_plane(self.codec_plane)
                    # live plan table in the snapshot (name -> tier/
                    # epoch/rung); absent when the plane is off — the
                    # schema guard only pins the codec/* instruments
                    self.metrics.section(
                        "codec_plans", self.codec_plane.plan_snapshot)
                autoscale_mode = (self.config.autoscale or "").strip()
                if autoscale_mode not in ("", "0", "off", "false", "no"):
                    # sensor-driven fleet-size control loop
                    # (core/autoscaler.py): consumes each finished
                    # StepReport on the train thread; "act" applies
                    # evict/drain through core/elastic.py, anything
                    # else is advisory (metrics + flight events)
                    from .autoscaler import AutoscalerPlane
                    mode = "act" if autoscale_mode == "act" else "advise"
                    self.autoscaler = AutoscalerPlane(self, mode=mode)
                    self.profiler.add_observer(self.autoscaler.on_step)
                    self.metrics.section("autoscale",
                                         self.autoscaler.snapshot)
            if self.config.metrics_port > 0 and self._metrics_server is None:
                from .metrics import start_http_server
                try:
                    self._metrics_server = start_http_server(
                        lambda: self.metrics, self.config.metrics_port)
                    log.info("metrics endpoint on 127.0.0.1:%d/metrics",
                             self.config.metrics_port)
                except Exception as e:  # noqa: BLE001 - metrics are aux
                    log.warning("metrics HTTP server failed to start: %s",
                                e)
            self.initialized = True
            self.suspended = False
            log.info("byteps_tpu initialized: rank=%d size=%d devices=%d mesh=%s",
                     self.rank(), self.size(), len(jax.devices()),
                     dict(self.mesh.shape))

    def shutdown(self) -> None:
        with self._lock:
            self._stop_scheduler()
            if self.ps_client is not None:
                try:
                    self.ps_client.close()
                except Exception:  # noqa: BLE001 - best-effort teardown
                    pass
                self.ps_client = None
            if self._metrics_server is not None:
                try:
                    self._metrics_server.shutdown()
                    self._metrics_server.server_close()
                except Exception:  # noqa: BLE001 - best-effort teardown
                    pass
                self._metrics_server = None
            if self.tracer is not None:
                self.tracer.flush()
            if self._jax_profiling:
                try:
                    jax.profiler.stop_trace()
                except Exception as e:  # noqa: BLE001
                    log.warning("jax.profiler.stop_trace failed: %s", e)
                self._jax_profiling = False
            if self.ledger is not None:
                try:
                    self.ledger.close()  # flush the perf archive tail
                except Exception:  # noqa: BLE001 - best-effort teardown
                    pass
            if self.timeseries is not None:
                try:
                    # the shutdown half of the SIGTERM artifact (empty
                    # planes write nothing)
                    self.timeseries.dump_jsonl(reason="shutdown",
                                               lock_timeout=1.0)
                except Exception:  # noqa: BLE001 - best-effort teardown
                    pass
            # free the pinned staging bytes (slots are rebuilt lazily
            # by the next init's first submissions)
            self.arena.reset()
            self.initialized = False
            self.suspended = False

    # ------------------------------------------------------------------ #
    # fleet observability plane (docs/observability.md "fleet",
    # docs/timeline.md fused timeline)
    # ------------------------------------------------------------------ #

    def _fleet_client(self):
        """The PS client iff it speaks the observability control ops
        (None otherwise — the fleet surfaces then cover in-process
        servers only)."""
        client = self.ps_client
        if client is not None and getattr(client, "supports_fleet",
                                          False):
            return client
        return None

    def _fleet_section(self) -> dict:
        """The ``fleet`` snapshot section: one derived per-stage stats
        dict per reachable server, keyed by server index. Wire
        (STATS_PULL) when a fleet-capable client is connected — the
        SAME surface for in-process, subprocess and remote servers —
        with the in-process mirror as the fallback so a server-role
        process still self-reports.

        Snapshot callers (``get_metrics()``, every Prometheus scrape)
        must stay cheap even against a wedged fleet: each pull is
        bounded at 1s, and the first sweep that exceeds 2.5s trips a
        lifecycle breaker that drops the wire path (local mirror /
        empty thereafter, one log line) — same discipline as the
        per-step probe's breaker."""
        from ..server import derive_stage_section, per_server_stats
        servers: dict = {}
        source = "none"
        client = None if getattr(self, "_fleet_section_tripped", False) \
            else self._fleet_client()
        if client is not None:
            t0 = time.monotonic()
            for s in range(self.config.num_servers):
                try:
                    raw = client.server_stats(s, timeout_s=1)
                except Exception:  # noqa: BLE001 - dead server: skip
                    raw = None
                if raw is not None:
                    servers[str(s)] = derive_stage_section(raw)
            elapsed = time.monotonic() - t0
            if elapsed > 2.5:
                self._fleet_section_tripped = True
                log.warning(
                    "fleet snapshot sweep took %.1fs — dropping the "
                    "wire path for this lifecycle (in-process mirror "
                    "only)", elapsed)
            if servers:
                source = "wire"
        if not servers:
            for i, raw in enumerate(per_server_stats()):
                servers[str(i)] = derive_stage_section(raw)
            if servers:
                source = "local"
        return {"workers": max(1, self.config.num_workers),
                "servers": len(servers), "source": source,
                "server": servers}

    def _fleet_stage_probe(self):
        """Per-step server-attribution probe (StepProfiler): cumulative
        per-stage ns summed over the fleet, or None when no server is
        reachable. In-process mirror first — a ctypes read, cheap
        enough for every step boundary — the
        wire op only when the fleet is genuinely out-of-process.

        The wire path runs ON THE TRAIN THREAD (step boundaries), so
        it is belt-and-braces bounded: 1s per-request timeout, and a
        one-way breaker — the first sweep that takes >250ms (a wedged-
        but-connected server, a congested control path) disables wire
        probing for the rest of this lifecycle with one log line.
        Attribution then reads None; the measurement plane must never
        become the cost it measures."""
        from ..server import stage_stats
        raw = stage_stats()
        keys = ("recv_ns", "queue_ns", "fold_ns", "reply_ns")
        if raw.get("live"):
            return {k: raw[k] for k in keys}
        if getattr(self, "_fleet_probe_tripped", False):
            return None
        client = self._fleet_client()
        if client is None:
            return None
        t0 = time.monotonic()
        tot = dict.fromkeys(keys, 0)
        seen = False
        for s in range(self.config.num_servers):
            try:
                st = client.server_stats(s, timeout_s=1)
            except Exception:  # noqa: BLE001 - dead server: skip
                st = None
            if st is None:
                continue
            seen = True
            for k in keys:
                tot[k] += st[k]
        elapsed = time.monotonic() - t0
        if elapsed > 0.25:
            self._fleet_probe_tripped = True
            log.warning(
                "fleet stage probe took %.0fms — disabling per-step "
                "server attribution for this lifecycle (fleet metrics "
                "snapshots are unaffected)", elapsed * 1e3)
        return tot if seen else None

    def _lane_probe(self):
        """Per-step stripe-lane probe (StepProfiler): cumulative
        seg bytes per data connection, ``{(server, lane_id): bytes}``,
        or None when no server is reachable. Same two-tier shape as
        the stage probe: the in-process mirror is a ctypes sweep
        (cheap every step), the STRIPE_PULL wire op runs on the train
        thread only until its own 250ms one-way breaker trips."""
        from ..server import per_conn_stripe_stats
        local = per_conn_stripe_stats()
        if any(local):
            return {(i, rec["conn"]): rec["seg_bytes"]
                    for i, recs in enumerate(local) for rec in recs}
        if getattr(self, "_lane_probe_tripped", False):
            return None
        client = self._fleet_client()
        if client is None:
            return None
        t0 = time.monotonic()
        out = {}
        for s in range(self.config.num_servers):
            try:
                recs = client.stripe_stats(s, timeout_s=1)
            except Exception:  # noqa: BLE001 - dead server: skip
                continue
            for rec in recs:
                out[(s, rec["conn"])] = rec["seg_bytes"]
        elapsed = time.monotonic() - t0
        if elapsed > 0.25:
            self._lane_probe_tripped = True
            log.warning(
                "stripe lane probe took %.0fms — disabling per-lane "
                "wire attribution for this lifecycle", elapsed * 1e3)
        return out or None

    def _sweep_fleet(self, drain_name: str, payload_key: str,
                     probes: int) -> list:
        """THE per-server drain+probe sweep behind both dump hooks:
        drain each server's ring (``drain_name``: ``drain_trace`` /
        ``drain_flight``), clock-probe it, and assemble the
        ``{server, offset_ns, err_ns, <payload_key>}`` entries the
        fusers consume. Best-effort per server — a dead one
        contributes nothing. One definition so a breaker / probe
        tweak / elastic-index fix lands in both dumps at once."""
        client = self._fleet_client()
        if client is None:
            return []
        out = []
        for s in range(self.config.num_servers):
            try:
                probe = client.clock_probe(s, probes=probes,
                                           timeout_s=2)
                recs = getattr(client, drain_name)(s, timeout_s=2)
            except Exception:  # noqa: BLE001 - dead server: skip
                continue
            if not recs:
                continue
            off, err = probe if probe is not None else (0, 0)
            out.append({"server": s, "offset_ns": off, "err_ns": err,
                        payload_key: recs})
        return out

    def _collect_server_traces(self) -> list:
        """Tracer.dump() hook: every server's wire-sampled span records
        plus its estimated clock offset (utils/tracing.py)."""
        return self._sweep_fleet("drain_trace", "records", probes=8)

    def _collect_server_flight(self) -> list:
        """flight.dump() hook: every server's flight-ring snapshot plus
        its clock offset, for the merged causal timeline."""
        return self._sweep_fleet("drain_flight", "events", probes=4)

    def suspend(self) -> None:
        """Elastic suspend (operations.cc:114-119): tear down comm state but
        keep the declared-tensor table so resume re-assigns identical keys."""
        with self._lock:
            bps_check(self.initialized, "suspend() before init()")
            self._stop_scheduler()
            if self.ps_client is not None:
                try:
                    # leave servers running for resume
                    self.ps_client.close(shutdown_servers=False)
                except Exception:  # noqa: BLE001
                    pass
                self.ps_client = None
            self.initialized = False
            self.suspended = True

    def resume(self, num_workers: int, num_servers: int,
               global_rank: Optional[int] = None) -> None:
        """Elastic resume with a new topology (common/__init__.py:75-81).

        A resume may change ``num_servers``: ``redeclare_all`` rebuilds
        the WHOLE routing table against the new count (fresh
        partition→server assignment, load table reset, routing_version
        bumped) — never a stale assignment table. An explicit
        ``BYTEPS_SERVER_HOSTS`` list is trimmed to the new count when
        shrinking (the surviving prefix keeps its indices); growing past
        the known list is an error — name the new hosts, or grow a LIVE
        fleet with ``bps.add_server`` instead."""
        import os
        # validate BEFORE any env mutation: a refused resume must leave
        # the process env exactly as it found it (a half-written
        # topology would poison every later Config.from_env reader)
        hosts = os.environ.get("BYTEPS_SERVER_HOSTS", "")
        addrs = [h.strip() for h in hosts.split(",") if h.strip()]
        if hosts and num_servers > 0 and len(addrs) < num_servers:
            raise ValueError(
                f"resume(num_servers={num_servers}) but "
                f"BYTEPS_SERVER_HOSTS names only {len(addrs)} "
                f"server(s) — set the full host list before resuming, "
                f"or join live servers with bps.add_server()")
        os.environ["DMLC_NUM_WORKER"] = str(num_workers)
        os.environ["DMLC_NUM_SERVER"] = str(num_servers)
        if hosts and num_servers > 0 and len(addrs) > num_servers:
            os.environ["BYTEPS_SERVER_HOSTS"] = ",".join(
                addrs[:num_servers])
        if global_rank is not None:
            os.environ["BYTEPS_GLOBAL_RANK"] = str(global_rank)
        # init() re-establishes the PS client that suspend() closed.
        self.init(Config.from_env())

    def _stop_scheduler(self) -> None:
        if self.scheduler is not None:
            try:
                self.scheduler.stop()
            except Exception:  # noqa: BLE001
                pass
            self.scheduler = None
            self.handles = None
        # the plane holds client/scheduler refs; plan STATE stays on the
        # registry so a resume continues where the ladder left off
        self.codec_plane = None
        # controller streaks are lifecycle state: a resumed fleet must
        # re-prove its conditions against the new topology
        self.autoscaler = None

    # ------------------------------------------------------------------ #
    # identity (communicator.cc:60-96)
    # ------------------------------------------------------------------ #

    def rank(self) -> int:
        c = self.config
        if c.global_rank is not None:
            return c.global_rank
        return c.worker_id * c.local_size + c.local_rank

    def size(self) -> int:
        c = self.config
        return max(1, c.num_workers) * max(1, c.local_size)

    def local_rank(self) -> int:
        return self.config.local_rank

    def local_size(self) -> int:
        return self.config.local_size

    def is_distributed(self) -> bool:
        return self.config.num_workers > 1 or self.config.force_distributed

    # ------------------------------------------------------------------ #

    def next_version(self, name: str) -> int:
        with self._lock:
            v = self._version.get(name, 0)
            self._version[name] = v + 1
            return v


def get_state() -> GlobalState:
    return GlobalState.get()
