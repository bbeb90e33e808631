"""byteps_tpu — a TPU-native distributed training framework with the
capabilities of BytePS.

Public API mirrors the reference's Horovod-compatible surface
(reference: byteps/common/__init__.py, byteps/torch/__init__.py):

    import byteps_tpu as bps
    bps.init()
    out = bps.push_pull(tensor, name="grad0")
    bps.rank(), bps.size(), bps.local_rank(), bps.local_size()
    bps.suspend(); bps.resume(num_workers, num_servers)
    bps.shutdown()

plus the JAX adapter in ``byteps_tpu.jax`` (DistributedOptimizer,
broadcast_parameters), Pallas compression codecs in
``byteps_tpu.ops.compression``, model zoo in ``byteps_tpu.models``, the DCN
parameter server in ``byteps_tpu.server``, and parallelism utilities
(mesh/ring attention/pipeline) in ``byteps_tpu.parallel``.
"""

from __future__ import annotations

from typing import Optional

from .config import Config
from .core.state import get_state
from .core.types import DataType, QueueType, Status
from .ops.push_pull import push_pull, broadcast

__version__ = "0.4.0"  # keep in sync with pyproject.toml

__all__ = [
    "init", "shutdown", "suspend", "resume",
    "rank", "size", "local_rank", "local_size",
    "push_pull", "push_pull_async", "poll", "synchronize", "broadcast",
    "declare_tensor", "profiler_step",
    "get_pushpull_speed", "get_metrics", "get_step_reports",
    "get_arena_stats", "get_fleet_metrics", "get_ledger",
    "get_timeseries",
    "dump_flight_record", "dump_fused_trace",
    "Config", "DataType", "QueueType", "Status",
]


def init(config: Optional[Config] = None, mesh=None, lazy: bool = False) -> None:
    """Initialize the framework (reference: byteps_init / byteps_lazy_init,
    operations.cc:34-94). Reads env config, builds the device mesh, and (when
    DMLC_NUM_SERVER > 0 and role is worker) connects the DCN PS client."""
    get_state().init(config, mesh=mesh, lazy=lazy)


def shutdown() -> None:
    get_state().shutdown()


def suspend() -> None:
    get_state().suspend()


def resume(num_workers: int, num_servers: int,
           global_rank: Optional[int] = None) -> None:
    get_state().resume(num_workers, num_servers, global_rank)


def add_server(address: Optional[str] = None) -> int:
    """Elastic scale-up join (docs/fault-tolerance.md "Elasticity"):
    bring a server STARTED AT RUNTIME into the live fleet — native
    connect, JOIN_PROBE handshake, then a deterministic version-fenced
    rebalance moves key subranges onto it and re-routes this worker
    without restart. ``address`` defaults to the consecutive-port
    convention (``scheduler_uri:scheduler_port + index``). Returns the
    new server index. Call from the training thread between rounds
    (multi-worker fleets: every worker must join the same server at the
    same round boundary — the plans are deterministic, so no further
    coordination is needed)."""
    from .core import elastic
    return elastic.join_server(get_state(), address)


def drain_server(server: int) -> list:
    """Graceful elastic scale-down: quiesce ``server``'s keys, migrate
    them to the survivors through the same plan engine crash-migration
    uses, retire it from assignment, and collect its drain ACK. Returns
    the migrated keys. The server process itself is left running (it
    holds nothing afterwards) — stop it at leisure."""
    from .core import elastic
    return elastic.drain_server(get_state(), server)


def set_server_spawn_hook(fn) -> None:
    """Register the autoscaler's ``add`` actuator: ``fn(index) ->
    "host:port"`` must start a PS server (same num_workers as the
    fleet) and return its address — or None to decline. Only consulted
    in ``BYTEPS_AUTOSCALE=act`` mode (read at decision time, so the
    registration order vs init doesn't matter); survives re-init."""
    get_state().server_spawn_hook = fn


def get_autoscaler():
    """The live autoscaler plane (None unless BYTEPS_AUTOSCALE is on):
    ``decisions()`` lists every non-hold decision, ``tick()`` drives
    the loop explicitly for eager (non-train-step) workloads."""
    return get_state().autoscaler


def rank() -> int:
    return get_state().rank()


def size() -> int:
    return get_state().size()


def local_rank() -> int:
    return get_state().local_rank()


def local_size() -> int:
    return get_state().local_size()


def declare_tensor(name: str, dtype: DataType = DataType.FLOAT32):
    """Pre-declare a tensor name so its key is assigned deterministically
    (reference: byteps_declare_tensor, operations.cc:420-427)."""
    return get_state().registry.declare(name, dtype)


def get_pushpull_speed() -> tuple:
    """(timestamp, MB/s) of recent push_pull traffic
    (reference: operations.cc:131-136, global.cc:697-752)."""
    return get_state().telemetry.speed()


def get_metrics() -> dict:
    """Structured snapshot of the unified metrics registry
    (core/metrics.py; schema in docs/observability.md):

    - ``counters`` — monotonic totals (wire requests/bytes, compression
      pre/post bytes, scheduler credit stalls, push_pull byte totals);
    - ``gauges`` — last-write values (scheduler queue depth);
    - ``histograms`` — fixed-log2-bucket latency distributions
      (per-stage per-key-class scheduler latencies, admission wait,
      per-leaf H2D+UPDATE drain spans) with count/sum/min/max/p50/p95/
      p99;
    - ``arena`` — the staging-arena + export stage counters
      (identical keys to ``get_arena_stats()``);
    - ``steps`` — the per-step pipeline profiler: ring-buffer window,
      the last ``StepReport`` and its stall diagnosis.

    ``BYTEPS_METRICS=0`` freezes the instruments (hot paths become a
    flag check); the snapshot still returns with zeroed values.
    """
    state = get_state()
    return state.metrics.snapshot()


def get_fleet_metrics() -> dict:
    """The fleet-wide metrics snapshot: the worker's full
    ``get_metrics()`` registry with the ``fleet`` section populated —
    one per-stage stats dict PER SERVER (keyed by server index), pulled
    over the STATS_PULL control op when the servers are out-of-process
    (subprocess/remote fleets stop being black boxes) and from the
    in-process mirror otherwise. ``fleet.source`` says which path
    answered (``wire`` / ``local`` / ``none``). The same section backs
    the Prometheus endpoint's ``byteps_fleet_*{server="<idx>"}``
    series, so scraping and calling can never disagree
    (docs/observability.md)."""
    return get_metrics()


def get_ledger() -> dict:
    """The step efficiency ledger's snapshot (core/ledger.py;
    docs/observability.md "Step efficiency ledger"): the registered
    cost model (XLA cost-analysis FLOPs/bytes, ideal exchange bytes,
    ``source``), the resolved device peak (``peak_flops`` /
    ``peak_bw_gbps`` / ``peak_source``), the cost model's attainable-
    MFU ``roofline_frac``, and the perf archive's path + record
    counters (``BYTEPS_PERF_ARCHIVE``). Identical to
    ``get_metrics()["ledger"]``; the per-STEP efficiency fields
    (``mfu``, ``overlap_frac``, ``wire_efficiency``) ride each
    ``StepReport`` — see ``get_step_reports()``."""
    state = get_state()
    if state.ledger is None:
        return {"enabled": False}
    return state.ledger.snapshot()


def get_timeseries(prefix: str = "", tail: Optional[int] = None) -> dict:
    """The time-series plane's full rings (core/timeseries.py;
    docs/observability.md "Time-series plane"): every per-step series
    — ``step/<field>`` StepReport scalars, ``stripe/s<i>/lane<j>/
    seg_bytes`` per-connection wire bytes, ``counter/<name>`` deltas
    and ``gauge/<name>`` values — as ``{name: {"steps": [...],
    "values": [...]}}``, oldest first, ``BYTEPS_TS_POINTS`` deep.
    ``prefix`` filters by series name, ``tail`` bounds the points per
    series. The bounded-tail variant of the same data is the
    ``timeseries`` section of ``get_metrics()`` — what ``python -m
    byteps_tpu.tools.top`` renders. ``{"enabled": False}`` before
    ``init()`` or with BYTEPS_TIMESERIES=0."""
    state = get_state()
    if state.timeseries is None or not state.timeseries.enabled:
        return {"enabled": False}
    return {"enabled": True,
            "series": state.timeseries.series(prefix=prefix, tail=tail)}


def dump_flight_record(path: Optional[str] = None) -> Optional[str]:
    """Write the merged crash flight record (worker event ring + every
    reachable server's ring, clock-aligned into one causal timeline) as
    JSON; returns the path, or None when the recorder is off
    (``BYTEPS_FLIGHT_RECORDER=0``) and no server has events. Also fired
    automatically on SIGTERM and on fatal wire errors — the fail-fast
    error message names the dump (docs/fault-tolerance.md)."""
    from .core import flight
    return flight.dump(path=path, reason="api")


def dump_fused_trace(path: Optional[str] = None) -> Optional[str]:
    """Emit the fused fleet Chrome trace (docs/timeline.md): the
    worker's comm spans plus every server's wire-sampled stage spans
    (``BYTEPS_TRACE_SAMPLE``), clock-aligned and rid-linked on one
    timeline. Returns the written path, or None when tracing never
    produced events (tracer off, sample 0)."""
    tracer = get_state().tracer
    if tracer is None:
        return None
    return tracer.dump(path=path)


def get_step_reports() -> list:
    """The last N ``StepReport``s (BYTEPS_STEP_REPORTS window) from the
    per-step pipeline profiler, oldest first — the raw material of the
    stall diagnosis (core/metrics.py classify_step)."""
    return [r.as_dict() for r in get_state().profiler.reports()]


def get_arena_stats() -> dict:
    """Host staging arena counters (core/arena.py): slots live, bytes
    pinned, allocations avoided, checkout conflicts, fresh fallbacks —
    plus the export stage counters (jax/train.py): ``export_leaves``
    (gradient leaves that left the chip: outputs of the backward,
    copied by the runtime and claimed by the train thread),
    ``export_shard_leaves`` (those that left as per-device shards: the
    leaves the plan shards on a mesh, none on one device) and
    ``export_ttfp_ms`` (the last round's time-to-first-push). The
    steady-state PS train step should show ``allocs_avoided`` growing
    and ``slot_allocs`` flat after warmup.

    Deprecated alias: this is ``get_metrics()["arena"]`` — the unified
    registry snapshot is the maintained surface; the keys here are
    stable for existing callers."""
    return get_state().telemetry.arena_stats()


def profiler_step() -> None:
    """Advance the Chrome-trace step counter (train steps built via
    byteps_tpu.jax.train call this automatically)."""
    tracer = get_state().tracer
    if tracer is not None:
        tracer.step()


def _rowsparse_submit(state, name: str, host2d, average: bool,
                      handle, out=None) -> None:
    """THE single rowsparse submit sequence (row-aligned declare +
    scheduler enqueue), shared by push_pull_rowsparse, the torch adapter
    and the jax PS train step so the semantics can't drift. ``out``:
    optional arena-staged flat f32 result buffer."""
    import numpy as np

    from .core.types import DataType

    host2d = np.ascontiguousarray(host2d, np.float32)
    ctx = state.registry.init_tensor(name, host2d.nbytes, DataType.FLOAT32,
                                     align_bytes=host2d.shape[1] * 4)
    state.scheduler.submit_rowsparse(
        ctx, host2d, handle, average, state.config.num_workers,
        version=state.next_version(name), out=out)


def push_pull_rowsparse(tensor, name: str, average: bool = True):
    """Row-sparse PS push_pull for embedding-style gradients: ``tensor``
    is a dense [rows, width] f32 gradient whose rows are mostly zero
    (how embedding grads come out of jax/torch autograd); only the
    nonzero rows travel on the wire — [nrows][width][ids][rows] — and
    the server scatter-adds them into the dense store
    (kRowSparsePushPull: the request type the reference reserves,
    common.h:267-271, but never implements). Returns the dense
    cross-worker sum (mean when ``average``) of shape [rows, width].

    Requires the DCN PS. Partitions are row-aligned automatically.
    """
    import numpy as np

    state = get_state()
    if state.ps_client is None:
        raise RuntimeError("push_pull_rowsparse requires a connected PS "
                           "(DMLC_NUM_SERVER > 0)")
    host = np.ascontiguousarray(tensor, dtype=np.float32)
    if host.ndim != 2:
        raise ValueError(f"expected [rows, width], got shape {host.shape}")
    from .core.types import DataType
    if state.scheduler is not None and state.handles is not None:
        # ride the priority pipeline like dense/compressed traffic; the
        # scheduler records true wire-byte telemetry per partition
        # (_rowsparse_submit declares the tensor itself)
        handle = state.handles.allocate(name)
        _rowsparse_submit(state, name, host, average, handle)
        return state.handles.wait_and_clear(handle.id)
    ctx = state.registry.init_tensor(name, host.nbytes, DataType.FLOAT32,
                                     align_bytes=host.shape[1] * 4)
    out = state.ps_client.push_pull_rowsparse(
        ctx, host, average=average, num_workers=state.config.num_workers)
    # actual wire traffic: sparse push (headers + ids + nonzero rows) up,
    # dense pull down — NOT the dense size both ways
    nnz = int(np.any(host != 0, axis=1).sum())
    push_wire = 8 * len(ctx.partitions) + nnz * (4 + host.shape[1] * 4)
    state.telemetry.record(push_wire + out.nbytes)
    return out


def push_pull_async(tensor, name: str, average: bool = True,
                    priority: Optional[int] = None, out=None) -> int:
    """Asynchronous PS push_pull: returns an int handle immediately; the
    partitions flow through the priority-scheduled pipeline. Horovod-style
    async surface (reference: byteps_torch_push_pull_async_*,
    torch/ops.py:157-174 + handle_manager).

    Requires the DCN PS (num_servers > 0). The input is the local (host)
    value; the result (sum or mean across workers) is retrieved with
    ``synchronize(handle)``. ``priority=None`` follows the key's pinned
    priority — the layer-order default -declared_key, unless the key was
    first exported by the streamed train step, which pins its measured
    production-order priority. An explicit value overrides on FIRST
    submission only (higher = sooner); later differing values warn once
    and are ignored (the cross-round reorder guard).
    ``out``: optional preallocated flat result buffer (host staging
    arena) — the caller must not recycle it before the handle resolves.
    """
    import numpy as np

    state = get_state()
    if state.scheduler is None:
        raise RuntimeError("push_pull_async requires a connected PS "
                           "(DMLC_NUM_SERVER > 0)")
    host = np.ascontiguousarray(tensor)
    flat = host.reshape(-1)
    from .server.client import get_or_init_ctx
    ctx = get_or_init_ctx(state, name, flat)
    handle = state.handles.allocate(name)
    handle._shape = host.shape
    state.scheduler.submit(ctx, flat, handle, average,
                           state.config.num_workers,
                           version=state.next_version(name),
                           priority=priority, out=out)
    return handle.id


def poll(handle: int) -> bool:
    """True when the async push_pull behind ``handle`` finished
    (reference: PollHandle, torch/ops.cc:129-135)."""
    return get_state().handles.poll(handle)


def synchronize(handle: int, timeout: float = None):
    """Block until the async push_pull completes; returns the reduced
    array (reference: WaitAndClear, torch/__init__.py:160-176)."""
    state = get_state()
    h = state.handles.get(handle)
    out = state.handles.wait_and_clear(handle, timeout)
    return out.reshape(getattr(h, "_shape", out.shape))
