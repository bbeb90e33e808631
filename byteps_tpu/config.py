"""Typed configuration for byteps_tpu, sourced from environment variables.

The reference framework is configured purely through environment variables
(reference: docs/env.md; byteps/common/global.cc:134-176). We keep env-var
compatibility for every knob that still has meaning on TPU, and expose them
through one frozen dataclass so the rest of the framework never touches
``os.environ`` directly.

Identity/topology vars (DMLC_*, BYTEPS_LOCAL_RANK, ...) keep their reference
names (reference: byteps/common/communicator.cc:60-96) so existing launch
tooling carries over. GPU/PCIe-only knobs (BYTEPS_PCIE_SWITCH_SIZE, NCCL
rings, NUMA pinning of GPU workers) are intentionally absent — on TPU one
process owns all local chips and intra-slice reduction is an XLA collective,
so that whole axis of configuration disappears.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional


def _env_int(name: str, default: int) -> int:
    v = os.environ.get(name)
    return int(v) if v not in (None, "") else default


def _env_bool(name: str, default: bool = False) -> bool:
    v = os.environ.get(name)
    if v is None or v == "":
        return default
    # case-insensitive, and "no" counts as false — an operator explicitly
    # disabling a flag (OFF/No) must not silently enable it
    return v.lower() not in ("0", "false", "off", "no")


def _env_str(name: str, default: str) -> str:
    v = os.environ.get(name)
    return v if v not in (None, "") else default


# Default partition size: 4 MB, same as the reference
# (byteps/common/global.cc:42,134-144).
DEFAULT_PARTITION_BYTES = 4096000
# Page size used to round partition lengths (global.cc:140-144).
PAGE_SIZE = 4096
# Minimum tensor size eligible for compression (global.cc:43).
DEFAULT_MIN_COMPRESS_BYTES = 1024000
# Gradient bucket fusion threshold (rebuild addition, see Config).
DEFAULT_FUSION_BYTES = 2097152
# Minimum leaf size eligible for locality-sharded export (see Config):
# below this the per-shard key overhead (scheduler admission, handle,
# wire round trip, H2D dispatch — all flat per key, times local_size)
# outweighs the divided D2H/wire bytes.
DEFAULT_SHARD_MIN_BYTES = 65536


@dataclasses.dataclass(frozen=True)
class Config:
    """Snapshot of all byteps_tpu configuration, read once at init()."""

    # --- identity / topology (reference: communicator.cc:60-96) ---
    role: str = "worker"                  # DMLC_ROLE: worker | server | scheduler
    worker_id: int = 0                    # DMLC_WORKER_ID
    num_workers: int = 1                  # DMLC_NUM_WORKER
    num_servers: int = 0                  # DMLC_NUM_SERVER
    scheduler_uri: str = "127.0.0.1"      # DMLC_PS_ROOT_URI
    scheduler_port: int = 9000            # DMLC_PS_ROOT_PORT
    local_rank: int = 0                   # BYTEPS_LOCAL_RANK (process on host)
    local_size: int = 1                   # BYTEPS_LOCAL_SIZE
    global_rank: Optional[int] = None     # BYTEPS_GLOBAL_RANK override
    force_distributed: bool = False       # BYTEPS_FORCE_DISTRIBUTED

    # --- partitioning / scheduling (global.cc:134-176, scheduled_queue.cc) ---
    partition_bytes: int = DEFAULT_PARTITION_BYTES
    scheduling_credit: int = 0            # BYTEPS_SCHEDULING_CREDIT (0 = off)
    server_enable_schedule: bool = False  # BYTEPS_SERVER_ENABLE_SCHEDULE
    key_hash_fn: str = "djb2"             # BYTEPS_KEY_HASH_FN
    enable_mixed_mode: bool = False       # BYTEPS_ENABLE_MIXED_MODE
    mixed_mode_bound: int = 101           # BYTEPS_MIXED_MODE_BOUND

    # --- compression ---
    min_compress_bytes: int = DEFAULT_MIN_COMPRESS_BYTES

    # --- adaptive codec control plane (rebuild addition;
    # core/codec_plane.py — "Compressed Communication: Adaptive Methods
    # and System", arxiv 2105.07829). On: leaves whose caller expressed
    # no codec opinion have their wire codec resolved PER ROUND from the
    # live StepReport signal, walking the dense -> lossless -> onebit
    # ladder with hysteresis (escalate when PULL-bound, de-escalate when
    # the wire recovers); every push carries a codec tag the server
    # validates per round, so plan skew fails loudly instead of
    # mis-folding. Off (default): the pre-plane static behavior. The
    # plane's tuning knobs (BYTEPS_CODEC_LADDER / _UP_ROUNDS /
    # _DOWN_ROUNDS / _PULL_RATIO / _PIN / _MIN_BYTES, docs/env.md) are
    # read by the plane itself at construction. ---
    codec_adapt: bool = False             # BYTEPS_CODEC_ADAPT

    # --- host staging arena (rebuild addition; the reference's cpubuff
    # discipline, operations.cc:283-414: staging buffers allocated once
    # at InitTensor and reused zero-copy). On: the PS train step's
    # gradient-sized host buffers (scheduler out slots, fused-bucket
    # concat slots, compressed reply scratch) persist across rounds in
    # core/arena.py with versioned checkout; off: fresh allocation per
    # round (the pre-arena behavior; numerics identical). ---
    staging_arena: bool = True            # BYTEPS_STAGING_ARENA

    # --- sharded optimizer apply (rebuild addition; PAPERS.md "Automatic
    # Cross-Replica Sharding of Weight Update": the weight update
    # decomposes per-shard). On: the PS train step's monolithic apply jit
    # is split into per-leaf jitted partial updates (jax/optim.py
    # make_sharded_apply) issued from the completion-ordered drain, so
    # UPDATE(k) overlaps PULL(k+1); transforms that are not per-leaf
    # separable (global-norm clipping etc.) are detected and fall back
    # to the fused apply. Off: one fused apply jit after the last pull
    # (the pre-split behavior; numerics identical). ---
    sharded_apply: bool = True            # BYTEPS_SHARDED_APPLY

    # --- locality-sharded export/import (rebuild addition; BytePS's
    # hierarchical strategy: the intra-machine reduce puts only
    # 1/local_size of each tensor on the inter-machine wire,
    # core_loops.cc:216-268, layered with the weight-update sharding of
    # "Automatic Cross-Replica Sharding of Weight Update" (PAPERS.md)).
    # On: the PS train step reduce-SCATTERS eligible gradient leaves
    # instead of psum'ing them, each local device exports ONLY its own
    # 1/local_size shard (a per-device program output), each shard
    # rides its own PS key spread across servers, the drain imports
    # shard k back into the device that owns it, the optimizer update
    # runs on the shard alone, and a jitted all-gather rebuilds
    # replicated params — dividing per-device D2H/H2D and per-key wire
    # bytes by local_size. Leaves below shard_min_bytes, non-divisible
    # leaves past the pad threshold, rowsparse/compressed/bucket-fused
    # leaves and single-device meshes fall back to the whole-leaf path
    # (numerics bitwise identical). ---
    local_shard_export: bool = True       # BYTEPS_LOCAL_SHARD_EXPORT
    shard_min_bytes: int = DEFAULT_SHARD_MIN_BYTES  # BYTEPS_SHARD_MIN_BYTES

    # --- gradient bucket fusion (rebuild addition; the reference only
    # SPLITS large tensors at partition_bytes — small-tensor fusion is
    # the inverse cure for the same disease: per-key round-trip overhead
    # (~0.3ms/key measured on loopback) dominating at sub-MB sizes.
    # Leaves below this fuse into <=4MB concatenated buckets (DDP/
    # horovod-style, far smaller than their 25/64MB defaults so
    # backward-order priority scheduling keeps most of its effect).
    # 0 disables. ---
    fusion_bytes: int = DEFAULT_FUSION_BYTES  # BYTEPS_FUSION_BYTES

    # --- fused wire op (rebuild addition; THC, arxiv 2302.08545: the PS
    # exchange is ONE aggregation round trip). On: the scheduler's PUSH
    # and PULL stages collapse into a single non-blocking WIRE stage —
    # one fused PUSHPULL message per partition per round (half the
    # request messages), with the reply landed by a completion reactor
    # (one thread per client, O(connections)) instead of a thread parked
    # in recv per in-flight partition. Off: the two-op push+pull path
    # (required against servers that predate the PUSHPULL op; numerics
    # identical either way). ---
    fused_pushpull: bool = True           # BYTEPS_FUSED_PUSHPULL

    # --- cross-barrier bounded-staleness pipelining (rebuild addition;
    # the reference's cross_barrier torch hook, docs/cross-barrier.md,
    # generalized to the JAX step). On: the train step releases step
    # k+1's forward as soon as the FRONT-of-model leaves of step k have
    # imported and applied; the tail leaves' PULL→H2D→UPDATE drains
    # across the step boundary, overlapping the next step's compute —
    # what production-order priority was built for. staleness bounds
    # the pipeline: at most staleness+1 rounds of one key in flight
    # worker-side, and the server parks (never folds) stamped rounds up
    # to `staleness` ahead of the accepting one (native RoundGate
    # window). staleness=0 with cross_barrier on degenerates to the
    # synchronous path bit-for-bit. Numerics at staleness>=1 are the
    # bounded-staleness lineage (PAPERS.md 2105.07829): tail leaves see
    # a one-step-stale param/optimizer base; the health plane +
    # BYTEPS_NAN_GUARD are the convergence guard. ---
    cross_barrier: bool = False           # BYTEPS_CROSS_BARRIER
    staleness: int = 1                    # BYTEPS_STALENESS

    # --- fault tolerance (rebuild addition; docs/fault-tolerance.md).
    # A failed wire exchange (fused PUSHPULL or two-op push/pull) no
    # longer hard-fails the round: the scheduler retries the partition
    # with exponential backoff, re-routing to a surviving server when
    # the native client reports the assigned one dead (registry
    # migrate_server). wire_retry = retry attempts AFTER the first
    # (0 restores fail-on-first-error); wire_backoff_ms = initial
    # backoff, doubling per attempt, capped at 2000ms. Replayed pushes
    # are (round, attempt)-stamped so the server folds each round at
    # most once per worker (idempotent retry). ---
    wire_retry: int = 2                   # BYTEPS_WIRE_RETRY
    wire_backoff_ms: float = 50.0         # BYTEPS_WIRE_BACKOFF_MS

    # --- async / elastic (server.cc:434-436) ---
    enable_async: bool = False            # BYTEPS_ENABLE_ASYNC
    # Sensor-driven autoscaler control loop (core/autoscaler.py,
    # docs/fault-tolerance.md "Elasticity"): "" = off, "advise" (or any
    # truthy value) = decisions surface via metrics + flight events
    # only, "act" = evict/drain decisions apply through core/elastic.py
    # and add decisions call the registered spawn hook (single-worker
    # topologies only — multi-worker fleets force advisory mode, an
    # external operator applies decisions fleet-wide). Tuning knobs
    # (BYTEPS_AUTOSCALE_{UP_STEPS,DOWN_STEPS,EVICT_FACTOR,EVICT_STEPS,
    # COOLDOWN,MIN_SERVERS,MAX_SERVERS}) are read by the plane itself.
    autoscale: str = ""                   # BYTEPS_AUTOSCALE
    # Server indices retired from assignment (drained/evicted/abandoned
    # joins) — exported by core/elastic.py so the retirement SURVIVES a
    # suspend/resume: the native conn table and the positional host
    # list cannot shrink, and a resume that resurrected a drained slot
    # would route keys to a server the operator may have stopped.
    # Comma-separated indices; cleared by the operator when composing a
    # genuinely fresh topology.
    retired_servers: tuple = ()           # BYTEPS_RETIRED_SERVERS

    # --- server (server.cc:412-456) ---
    server_engine_threads: int = 4        # BYTEPS_SERVER_ENGINE_THREAD

    # --- debug / trace (global.cc:113-124,703-704) ---
    trace_on: bool = False                # BYTEPS_TRACE_ON
    trace_start_step: int = 10            # BYTEPS_TRACE_START_STEP
    trace_end_step: int = 20              # BYTEPS_TRACE_END_STEP
    trace_dir: str = "./traces"           # BYTEPS_TRACE_DIR
    # non-empty -> jax.profiler.start_trace(dir) at init, stop at
    # shutdown: device (XLA) trace for TensorBoard/Perfetto, with the
    # host comm spans mirrored in as TraceAnnotations (SURVEY §5.1 note)
    jax_profiler_dir: str = ""            # BYTEPS_JAX_PROFILER_DIR
    # --- fleet observability plane (rebuild addition; docs/timeline.md
    # fused timeline + docs/observability.md "fleet"). trace_sample:
    # the server records every Nth data request's recv→queue-wait→fold
    # →reply span tuple into a native ring (0 = off) drained by the
    # TRACE_DRAIN control op and fused — clock-aligned and rid-linked —
    # into the worker's Chrome trace by Tracer.dump(). trace_ring
    # bounds that ring. flight_recorder arms the bounded structured
    # event ring (worker ring here, native ring on every server; ring
    # capacity flight_ring) dumped on SIGTERM / fatal wire errors or
    # via bps.dump_flight_record() into flight_dir. ---
    trace_sample: int = 0                 # BYTEPS_TRACE_SAMPLE
    trace_ring: int = 4096                # BYTEPS_TRACE_RING
    flight_recorder: bool = True          # BYTEPS_FLIGHT_RECORDER
    flight_ring: int = 2048               # BYTEPS_FLIGHT_RING
    flight_dir: str = "./flight"          # BYTEPS_FLIGHT_DIR
    telemetry_on: bool = True             # BYTEPS_TELEMETRY_ON
    debug_sample_tensor: str = ""         # BYTEPS_DEBUG_SAMPLE_TENSOR

    # --- metrics / observability (rebuild addition; core/metrics.py:
    # the unified registry + per-step pipeline profiler every perf PR
    # reports against). metrics_on=0 turns every instrument op into a
    # flag check; metrics_port > 0 serves a
    # stdlib Prometheus text endpoint on 127.0.0.1; stall_diag logs a
    # one-line per-step bound-stage diagnosis from the StepReport ring
    # (window = step_report_window). ---
    metrics_on: bool = True               # BYTEPS_METRICS
    metrics_port: int = 0                 # BYTEPS_METRICS_PORT (0 = off)
    stall_diag: bool = False              # BYTEPS_STALL_DIAG
    step_report_window: int = 64          # BYTEPS_STEP_REPORTS
    # --- time-series plane (rebuild addition; core/timeseries.py,
    # docs/observability.md "Time-series plane"). timeseries=1 arms the
    # fixed-ring per-step recorder riding the StepProfiler observer
    # hook (counter deltas / gauges / StepReport + ledger fields +
    # per-stripe wire and per-leaf staleness series); ts_points bounds
    # every series ring. bps.get_timeseries() / `byteps_tpu.tools.top`
    # read it; a JSONL artifact rides SIGTERM/shutdown. ---
    timeseries: bool = True               # BYTEPS_TIMESERIES
    ts_points: int = 512                  # BYTEPS_TS_POINTS

    # --- step efficiency ledger (rebuild addition; core/ledger.py,
    # docs/observability.md "Step efficiency ledger"). On: the train
    # layer registers each plan's XLA cost-analysis FLOPs/bytes + ideal
    # exchange bytes, and every StepReport is priced in MFU / roofline /
    # overlap-fraction / wire-efficiency terms against the device-kind
    # peak table (peak_flops/peak_bw_gbps override auto-detection);
    # perf_archive appends a compact JSONL efficiency record per step
    # (flushed every perf_flush_steps, at shutdown and on SIGTERM);
    # eff_drop_frac/_window drive the efficiency_drop flight event
    # (mfu/overlap falling below the trailing-window median). ---
    # --- training-health plane (rebuild addition; core/health.py +
    # native/ps.cc in-fold statistics, docs/observability.md
    # "Training-health plane"). health=1 arms BOTH halves: the server's
    # fused in-fold sum-of-squares/abs-max/NaN-Inf pass (read natively
    # per Server instance) and the worker's drain tap + hysteresis
    # detector (nonfinite / explode / collapse / fidelity-drift);
    # nan_guard upgrades a nonfinite round to a fail-fast that dumps
    # the flight record. The detector knobs mirror the codec
    # controller's clockless streak/threshold shape. ---
    health: bool = False                  # BYTEPS_HEALTH
    nan_guard: bool = False               # BYTEPS_NAN_GUARD
    health_window: int = 16               # BYTEPS_HEALTH_WINDOW
    health_explode_ratio: float = 10.0    # BYTEPS_HEALTH_EXPLODE_RATIO
    health_collapse_ratio: float = 0.01   # BYTEPS_HEALTH_COLLAPSE_RATIO
    health_streak: int = 2                # BYTEPS_HEALTH_STREAK
    health_drift_frac: float = 0.1        # BYTEPS_HEALTH_DRIFT_FRAC
    health_drift_keys: int = 8            # BYTEPS_HEALTH_DRIFT_KEYS

    ledger: bool = True                   # BYTEPS_LEDGER
    peak_flops: float = 0.0               # BYTEPS_PEAK_FLOPS (0 = auto)
    peak_bw_gbps: float = 0.0             # BYTEPS_PEAK_BW_GBPS (0 = auto)
    perf_archive: str = ""                # BYTEPS_PERF_ARCHIVE ("" = off)
    perf_flush_steps: int = 32            # BYTEPS_PERF_FLUSH_STEPS
    eff_drop_frac: float = 0.25           # BYTEPS_EFF_DROP_FRAC
    eff_drop_window: int = 16             # BYTEPS_EFF_DROP_WINDOW

    # --- multi-process runtime (SURVEY §2.4: scheduler rendezvous ->
    # jax.distributed coordination service) ---
    num_processes: int = 1                # BYTEPS_NUM_PROCESS
    process_id: int = 0                   # BYTEPS_PROCESS_ID (default: worker_id)
    coord_port: int = 0                   # BYTEPS_COORD_PORT (0 = scheduler_port + 512)

    # --- TPU-specific (new) ---
    mesh_shape: str = ""                  # BYTEPS_TPU_MESH e.g. "dp=8" or "dp=4,tp=2"
    use_psum_scatter: bool = True         # hierarchical RS+AG instead of one psum

    @staticmethod
    def from_env() -> "Config":
        return Config(
            role=_env_str("DMLC_ROLE", "worker"),
            worker_id=_env_int("DMLC_WORKER_ID", 0),
            num_workers=_env_int("DMLC_NUM_WORKER", 1),
            num_servers=_env_int("DMLC_NUM_SERVER", 0),
            scheduler_uri=_env_str("DMLC_PS_ROOT_URI", "127.0.0.1"),
            scheduler_port=_env_int("DMLC_PS_ROOT_PORT", 9000),
            local_rank=_env_int("BYTEPS_LOCAL_RANK", 0),
            local_size=_env_int("BYTEPS_LOCAL_SIZE", 1),
            global_rank=(int(os.environ["BYTEPS_GLOBAL_RANK"])
                         if os.environ.get("BYTEPS_GLOBAL_RANK") else None),
            force_distributed=_env_bool("BYTEPS_FORCE_DISTRIBUTED"),
            partition_bytes=_env_int("BYTEPS_PARTITION_BYTES",
                                     DEFAULT_PARTITION_BYTES),
            scheduling_credit=_env_int("BYTEPS_SCHEDULING_CREDIT", 0),
            server_enable_schedule=_env_bool("BYTEPS_SERVER_ENABLE_SCHEDULE"),
            key_hash_fn=_env_str("BYTEPS_KEY_HASH_FN", "djb2"),
            enable_mixed_mode=_env_bool("BYTEPS_ENABLE_MIXED_MODE"),
            mixed_mode_bound=_env_int("BYTEPS_MIXED_MODE_BOUND", 101),
            codec_adapt=_env_bool("BYTEPS_CODEC_ADAPT"),
            min_compress_bytes=_env_int("BYTEPS_MIN_COMPRESS_BYTES",
                                        DEFAULT_MIN_COMPRESS_BYTES),
            staging_arena=_env_bool("BYTEPS_STAGING_ARENA", True),
            sharded_apply=_env_bool("BYTEPS_SHARDED_APPLY", True),
            local_shard_export=_env_bool("BYTEPS_LOCAL_SHARD_EXPORT", True),
            shard_min_bytes=_env_int("BYTEPS_SHARD_MIN_BYTES",
                                     DEFAULT_SHARD_MIN_BYTES),
            fusion_bytes=_env_int("BYTEPS_FUSION_BYTES",
                                  DEFAULT_FUSION_BYTES),
            fused_pushpull=_env_bool("BYTEPS_FUSED_PUSHPULL", True),
            cross_barrier=_env_bool("BYTEPS_CROSS_BARRIER"),
            staleness=max(0, min(8, _env_int("BYTEPS_STALENESS", 1))),
            wire_retry=_env_int("BYTEPS_WIRE_RETRY", 2),
            wire_backoff_ms=float(
                _env_str("BYTEPS_WIRE_BACKOFF_MS", "50")),
            enable_async=_env_bool("BYTEPS_ENABLE_ASYNC"),
            autoscale=_env_str("BYTEPS_AUTOSCALE", "").strip().lower(),
            retired_servers=tuple(
                int(tok) for tok in
                _env_str("BYTEPS_RETIRED_SERVERS", "").split(",")
                if tok.strip()),
            server_engine_threads=_env_int("BYTEPS_SERVER_ENGINE_THREAD", 4),
            trace_on=_env_bool("BYTEPS_TRACE_ON"),
            trace_start_step=_env_int("BYTEPS_TRACE_START_STEP", 10),
            trace_end_step=_env_int("BYTEPS_TRACE_END_STEP", 20),
            trace_dir=_env_str("BYTEPS_TRACE_DIR", "./traces"),
            jax_profiler_dir=_env_str("BYTEPS_JAX_PROFILER_DIR", ""),
            trace_sample=_env_int("BYTEPS_TRACE_SAMPLE", 0),
            trace_ring=_env_int("BYTEPS_TRACE_RING", 4096),
            flight_recorder=_env_bool("BYTEPS_FLIGHT_RECORDER", True),
            flight_ring=_env_int("BYTEPS_FLIGHT_RING", 2048),
            flight_dir=_env_str("BYTEPS_FLIGHT_DIR", "./flight"),
            telemetry_on=_env_bool("BYTEPS_TELEMETRY_ON", True),
            debug_sample_tensor=_env_str("BYTEPS_DEBUG_SAMPLE_TENSOR", ""),
            metrics_on=_env_bool("BYTEPS_METRICS", True),
            metrics_port=_env_int("BYTEPS_METRICS_PORT", 0),
            stall_diag=_env_bool("BYTEPS_STALL_DIAG"),
            step_report_window=_env_int("BYTEPS_STEP_REPORTS", 64),
            timeseries=_env_bool("BYTEPS_TIMESERIES", True),
            ts_points=max(16, _env_int("BYTEPS_TS_POINTS", 512)),
            health=_env_bool("BYTEPS_HEALTH"),
            nan_guard=_env_bool("BYTEPS_NAN_GUARD"),
            health_window=_env_int("BYTEPS_HEALTH_WINDOW", 16),
            health_explode_ratio=float(
                _env_str("BYTEPS_HEALTH_EXPLODE_RATIO", "10")),
            health_collapse_ratio=float(
                _env_str("BYTEPS_HEALTH_COLLAPSE_RATIO", "0.01")),
            health_streak=_env_int("BYTEPS_HEALTH_STREAK", 2),
            health_drift_frac=float(
                _env_str("BYTEPS_HEALTH_DRIFT_FRAC", "0.1")),
            health_drift_keys=_env_int("BYTEPS_HEALTH_DRIFT_KEYS", 8),
            ledger=_env_bool("BYTEPS_LEDGER", True),
            peak_flops=float(_env_str("BYTEPS_PEAK_FLOPS", "0")),
            peak_bw_gbps=float(_env_str("BYTEPS_PEAK_BW_GBPS", "0")),
            perf_archive=_env_str("BYTEPS_PERF_ARCHIVE", ""),
            perf_flush_steps=_env_int("BYTEPS_PERF_FLUSH_STEPS", 32),
            eff_drop_frac=float(_env_str("BYTEPS_EFF_DROP_FRAC", "0.25")),
            eff_drop_window=_env_int("BYTEPS_EFF_DROP_WINDOW", 16),
            num_processes=_env_int("BYTEPS_NUM_PROCESS", 1),
            process_id=_env_int("BYTEPS_PROCESS_ID",
                                _env_int("DMLC_WORKER_ID", 0)),
            coord_port=_env_int("BYTEPS_COORD_PORT", 0),
            mesh_shape=_env_str("BYTEPS_TPU_MESH", ""),
            use_psum_scatter=_env_bool("BYTEPS_USE_PSUM_SCATTER", True),
        )

    def parsed_mesh(self) -> dict:
        """Parse BYTEPS_TPU_MESH ("dp=4,tp=2") into an ordered axis dict."""
        if not self.mesh_shape:
            return {}
        out = {}
        for part in self.mesh_shape.split(","):
            k, _, v = part.partition("=")
            out[k.strip()] = int(v)
        return out
