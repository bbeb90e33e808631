"""DCN parameter-server worker client.

The ps-lite ZPush/ZPull surface (reference: ps::KVWorker<char>, used from
byteps/common/core_loops.cc:571,609) over the native TCP client in
byteps_tpu/native/ps.cc. Per-partition push/pull runs on a thread pool in
priority order — the worker-side seed of the reference's PUSH/PULL pipeline
stages (core_loops.cc:538-618) — with partitions of one tensor fanned out
across servers by the registry's key->server assignment.

Beyond the reference surface: ``zpushpull_async`` — the fused PUSHPULL
wire op (one message per aggregation round trip, the THC shape) whose
replies are drained by a single **completion-reactor** thread off the
native completion queue, so in-flight requests are unbounded by thread
count (O(connections) threads total).
"""

from __future__ import annotations

import concurrent.futures
import ctypes
import os
import struct
import threading
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from ..config import Config
from ..core.types import (
    DataType, Partition, RequestType, TensorContext, get_command_type,
    trunc_divide_inplace,
)
from ..native.build import build
from ..utils.logging import log

# Python mirror of the native wire header (native/ps.cc MsgHeader).
# The transport itself is native — these constants exist so the Python
# side can NAME the contract (tests, tooling, debugging captures) and
# so byteps-lint's wire-layout rule can diff both sides statically: a
# header or magic change that lands on only one side fails the lint
# (the 36B->40B / 0xB17E5001->0xB17E5002 drift class). Keep field
# order identical to the struct: magic, op, flags, sender, rid, key,
# cmd, len, epoch, codec — little-endian, packed. 0xB17E5003 added the
# kFlagSeg striped-segment frame (MsgHeader + 32B SegHdr + chunk): a
# peer speaking the pre-stripe magic must be rejected at accept, not
# fed reassembly frames it would misparse as oversized payloads.
WIRE_MAGIC = 0xB17E5003
WIRE_HEADER_FMT = "<IBBHIQIIQI"
WIRE_HEADER_BYTES = 40
assert struct.calcsize(WIRE_HEADER_FMT) == WIRE_HEADER_BYTES

# Observability control ops (native/ps.cc enum Op; machine-checked by
# byteps-lint's slot-layout check against the enum). Header-only
# requests the server answers INLINE from the conn loop — stats/trace/
# flight pulls and the NTP-style clock echo never queue behind folds.
WIRE_CTRL_OPS = {
    "STATS_PULL": 12,
    "TRACE_DRAIN": 13,
    "FLIGHT_DRAIN": 14,
    "CLOCK_PROBE": 15,
    "JOIN_PROBE": 16,
    "DRAIN_REQ": 17,
    "HEALTH_PULL": 18,
    "STRIPE_PULL": 19,
}

# Control-pull reply size limits (native/ps.cc enum CtrlLimits, also
# lint-checked): the reply buffers below are sized from these, and a
# reply larger than its buffer is drained-not-delivered by the native
# recv loop — a silent empty drain, exactly the drift class the
# machine check exists to prevent.
WIRE_CTRL_LIMITS = {
    "kCtrlDrainBatch": 1024,
    "kCtrlFlightDrainMax": 4096,
    "kCtrlStripeMax": 64,
}


def _load_lib() -> ctypes.CDLL:
    lib = ctypes.CDLL(build())
    lib.bps_client_create.restype = ctypes.c_void_p
    lib.bps_client_create.argtypes = [ctypes.c_char_p, ctypes.c_int]
    lib.bps_client_init_key.restype = ctypes.c_int
    lib.bps_client_init_key.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.c_uint64, ctypes.c_void_p,
        ctypes.c_uint32, ctypes.c_uint32]
    # push ops carry a trailing (round << 16 | attempt) epoch stamp for
    # server-side replay dedup (idempotent retry; docs/fault-tolerance.md)
    # plus a (plan_epoch << 8 | codec_id) adaptive-codec wire tag the
    # server validates per round (0 = untagged; docs/compression.md)
    epoch_argtypes = lib.bps_client_init_key.argtypes + [
        ctypes.c_uint64, ctypes.c_uint32]
    lib.bps_client_push.restype = ctypes.c_int
    lib.bps_client_push.argtypes = epoch_argtypes
    lib.bps_client_push_async.restype = ctypes.c_int
    lib.bps_client_push_async.argtypes = epoch_argtypes
    lib.bps_client_pull.restype = ctypes.c_int
    lib.bps_client_pull.argtypes = lib.bps_client_init_key.argtypes
    if hasattr(lib, "bps_client_pushpull_async"):
        # guarded: a stale .so predating the fused PUSHPULL op must
        # still load so supports_fused can return False and the
        # scheduler falls back to the two-op path (version skew)
        lib.bps_client_pushpull_async.restype = ctypes.c_int
        lib.bps_client_pushpull_async.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_uint64,
            ctypes.c_void_p, ctypes.c_uint32, ctypes.c_uint32,
            ctypes.c_void_p, ctypes.c_uint32, ctypes.c_uint64,
            ctypes.c_uint64, ctypes.c_uint32]
        lib.bps_client_cq_poll.restype = ctypes.c_int
        lib.bps_client_cq_poll.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_uint32), ctypes.c_int, ctypes.c_int]
        lib.bps_client_cq_depth.restype = ctypes.c_int
        lib.bps_client_cq_depth.argtypes = [ctypes.c_void_p]
        lib.bps_client_cq_abort.restype = None
        lib.bps_client_cq_abort.argtypes = [ctypes.c_void_p]
    lib.bps_client_comp_init.restype = ctypes.c_int
    lib.bps_client_comp_init.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.c_uint64, ctypes.c_char_p]
    if hasattr(lib, "bps_client_server_dead"):
        # guarded like the fused op: a stale .so predating the probe
        # must still load (server_dead() then conservatively reports
        # False and failover never triggers — the pre-elastic behavior)
        lib.bps_client_server_dead.restype = ctypes.c_int
        lib.bps_client_server_dead.argtypes = [ctypes.c_void_p,
                                               ctypes.c_int]
    if hasattr(lib, "bps_client_transport_stats"):
        # guarded like the probes above (stale-.so version skew)
        lib.bps_client_transport_stats.restype = ctypes.c_int
        lib.bps_client_transport_stats.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64),
            ctypes.c_int]
    if hasattr(lib, "bps_client_pushpull_async2"):
        # fused op reporting the wire rid back (the trace-plane flow
        # link); guarded — a stale .so degrades to rid-less tracing
        lib.bps_client_pushpull_async2.restype = ctypes.c_int
        lib.bps_client_pushpull_async2.argtypes = (
            lib.bps_client_pushpull_async.argtypes
            + [ctypes.POINTER(ctypes.c_uint32)])
    if hasattr(lib, "bps_client_ctrl"):
        # observability control plane (stats/trace/flight pulls + the
        # clock probe); guarded — supports_fleet reads False on a
        # stale .so and the fleet surfaces degrade to local-only
        lib.bps_client_ctrl.restype = ctypes.c_int
        lib.bps_client_ctrl.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_int, ctypes.c_void_p,
            ctypes.c_uint32, ctypes.c_int]
        lib.bps_client_clock_probe.restype = ctypes.c_int
        lib.bps_client_clock_probe.argtypes = [
            ctypes.c_void_p, ctypes.c_int,
            ctypes.POINTER(ctypes.c_uint64), ctypes.c_int]
    if hasattr(lib, "bps_client_ctrl_key"):
        # keyed control pull (HEALTH_PULL, the training-health plane);
        # guarded — health_pull reads None on a stale .so
        lib.bps_client_ctrl_key.restype = ctypes.c_int
        lib.bps_client_ctrl_key.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_int,
            ctypes.c_uint64, ctypes.c_void_p, ctypes.c_uint32,
            ctypes.c_int]
    if hasattr(lib, "bps_client_stripe_bytes"):
        # striped wire plane (BYTEPS_WIRE_STRIPES): per-conn TX byte
        # ledger + the stripe-death test hook; guarded — a stale .so
        # reports no stripe instruments and never stripes
        lib.bps_client_stripe_bytes.restype = ctypes.c_int
        lib.bps_client_stripe_bytes.argtypes = [
            ctypes.c_void_p, ctypes.c_int,
            ctypes.POINTER(ctypes.c_uint64), ctypes.c_int]
        lib.bps_client_kill_stripe.restype = ctypes.c_int
        lib.bps_client_kill_stripe.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_int]
    if hasattr(lib, "bps_client_add_server"):
        # runtime scale-up (elastic fleet); guarded — a stale .so simply
        # cannot grow its fleet and add_server() raises a clear error
        lib.bps_client_add_server.restype = ctypes.c_int
        lib.bps_client_add_server.argtypes = [ctypes.c_void_p,
                                              ctypes.c_char_p]
    lib.bps_client_barrier.argtypes = [ctypes.c_void_p]
    lib.bps_client_barrier.restype = ctypes.c_int
    lib.bps_client_ipc_conns.argtypes = [ctypes.c_void_p]
    lib.bps_client_ipc_conns.restype = ctypes.c_int
    lib.bps_client_total_conns.argtypes = [ctypes.c_void_p]
    lib.bps_client_total_conns.restype = ctypes.c_int
    lib.bps_client_shutdown.argtypes = [ctypes.c_void_p]
    lib.bps_client_shutdown.restype = ctypes.c_int
    lib.bps_client_destroy.argtypes = [ctypes.c_void_p]
    return lib


def server_addresses(config: Config) -> List[str]:
    """Server endpoints: explicit BYTEPS_SERVER_HOSTS="h:p,h:p,..." or the
    scheduler URI with consecutive ports (root_port + server_id). The list
    length must equal num_servers — the registry assigns partitions to
    server indices [0, num_servers) and those index the native connection
    table unchecked."""
    hosts = os.environ.get("BYTEPS_SERVER_HOSTS", "")
    if hosts:
        addrs = [h.strip() for h in hosts.split(",") if h.strip()]
        if len(addrs) != config.num_servers:
            raise ValueError(
                f"BYTEPS_SERVER_HOSTS has {len(addrs)} entries but "
                f"DMLC_NUM_SERVER={config.num_servers}")
        return addrs
    return [f"{config.scheduler_uri}:{config.scheduler_port + i}"
            for i in range(config.num_servers)]


def get_or_init_ctx(state, name: str, host: np.ndarray) -> TensorContext:
    """Registry get-or-init for a host tensor. Always goes through
    init_tensor: it is idempotent for an unchanged size and re-partitions
    on resize (stale partitions would slice the wrong byte ranges)."""
    return state.registry.init_tensor(name, host.nbytes,
                                      DataType.from_np(host.dtype))


def build_rowsparse_payload(p: Partition, nz: np.ndarray,
                            host2d: np.ndarray) -> np.ndarray:
    """One partition's row-sparse push payload
    ([u32 nrows][u32 width][i32 local_ids][f32 rows]) — THE single wire
    producer, shared by the blocking client path and the scheduler's
    pipelined path (the server parser is ps.cc DoPushSparse). Raises if
    the partition does not land on row boundaries."""
    width = host2d.shape[1]
    row_bytes = width * 4
    if p.offset % row_bytes or p.length % row_bytes:
        raise ValueError(
            f"partition {p.index} not row-aligned; declare with "
            f"init_tensor(..., align_bytes={row_bytes})")
    lo = p.offset // row_bytes
    hi = (p.offset + p.length) // row_bytes
    sel = nz[(nz >= lo) & (nz < hi)]
    payload = b"".join((
        np.uint32(len(sel)).tobytes(),
        np.uint32(width).tobytes(),
        (sel - lo).astype(np.int32).tobytes(),
        np.ascontiguousarray(host2d[sel]).tobytes(),
    ))
    return np.frombuffer(payload, np.uint8)


def ps_round_trip(state, name: str, host: np.ndarray,
                  average: bool, priority: Optional[int] = None,
                  out: Optional[np.ndarray] = None) -> np.ndarray:
    """Shared get-or-declare + server round-trip for one flat host tensor:
    used by both the eager push_pull PS tier and make_ps_train_step.

    Fans the partitions out through the priority-scheduled pipeline when
    one is running (so eager callers get the same credit/priority semantics
    and PUSH/PULL stage overlap as the async API), falling back to the
    client's blocking fan-out otherwise. ``out``: optional arena-staged
    flat result buffer (the caller owns its reuse window)."""
    ctx = get_or_init_ctx(state, name, host)
    host = np.ascontiguousarray(host)
    if state.scheduler is not None and state.handles is not None:
        handle = state.handles.allocate(name)
        state.scheduler.submit(ctx, host, handle, average,
                               state.config.num_workers,
                               version=state.next_version(name),
                               priority=priority, out=out)
        # scheduler records telemetry per-partition on completion
        return state.handles.wait_and_clear(handle.id)
    res = state.ps_client.push_pull(
        ctx, host, average=average, num_workers=state.config.num_workers,
        out=out)
    state.telemetry.record_round_trip(host.nbytes)
    return res


class PSClient:
    """Blocking-per-call, thread-safe ZPush/ZPull client; one native
    connection per server, multiplexed by request id."""

    def __init__(self, servers: Sequence[str], worker_id: int,
                 num_threads: int = 8):
        self._lib = _load_lib()
        csv = ",".join(servers).encode()
        self._handle = self._lib.bps_client_create(csv, worker_id)
        if not self._handle:
            raise RuntimeError(
                f"failed to connect to PS servers {servers!r}")
        self._servers = list(servers)
        n_ipc = self._lib.bps_client_ipc_conns(self._handle)
        if n_ipc:
            log.info("PS client: %d/%d connections upgraded to shm IPC "
                     "transport (BYTEPS_ENABLE_IPC)", n_ipc,
                     self._lib.bps_client_total_conns(self._handle))
        self._pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=num_threads, thread_name_prefix="bps-pushpull")
        self._closed = False
        self._lock = threading.Lock()
        # key -> store length this client has init-pushed on the server
        # (server-side initialization is per-store, distinct from registry
        # declaration; a resize needs a fresh init push)
        self._inited_keys: dict = {}   # guarded-by: _lock
        # wire-layer instrument refs (core/metrics.py), attached by
        # GlobalState.init after connect; None = uninstrumented (direct
        # construction in tests/benches)
        self._m_push_req = self._m_push_bytes = None
        self._m_pull_req = self._m_pull_bytes = None
        self._m_pushpull_req = self._m_errors = None
        self._m_inflight = self._m_inflight_peak = self._m_cq_depth = None
        self._m_stripe_segs = self._m_stripe_bytes = None
        # fused PUSHPULL completion reactor: ticket -> (callback,
        # reply-buffer ref). The buffer ref is load-bearing — the native
        # recv loop writes through its pointer until the ticket's
        # completion record is drained, so it must not be collectable.
        self._fused_mu = threading.Lock()
        self._fused: dict = {}         # guarded-by: _fused_mu
        self._next_ticket = 1          # guarded-by: _fused_mu
        self._reactor: Optional[threading.Thread] = None
        self._reactor_started = False  # guarded-by: _lock
        # outstanding wire requests awaiting a server reply (fused
        # requests + blocking pulls): THE concurrency the reactor model
        # unlocks — two-op mode caps it at the pull-pool thread count,
        # fused mode at scheduling credit
        self._inflight = 0             # guarded-by: _lock
        self._inflight_peak = 0        # guarded-by: _lock

    def attach_metrics(self, metrics) -> None:
        """Cache wire counters off the registry: every ZPush/ZPull
        request and its payload bytes land on the unified surface
        (``wire/*`` — request counts, bytes each way, failed requests;
        the native transport has no app-level retry, so ``wire/errors``
        is the retry-pressure signal). Fused PUSHPULL requests count
        under ``wire/pushpull_requests`` (one per partition per round —
        half the request messages of the two-op push+pull pair);
        ``wire/inflight`` / ``wire/inflight_peak`` gauge outstanding
        wire requests, ``wire/cq_depth`` the undrained completion-queue
        backlog."""
        self._m_push_req = metrics.counter("wire/push_requests")
        self._m_push_bytes = metrics.counter("wire/push_bytes")
        self._m_pull_req = metrics.counter("wire/pull_requests")
        self._m_pull_bytes = metrics.counter("wire/pull_bytes")
        self._m_pushpull_req = metrics.counter("wire/pushpull_requests")
        self._m_errors = metrics.counter("wire/errors")
        self._m_inflight = metrics.gauge("wire/inflight")
        self._m_inflight_peak = metrics.gauge("wire/inflight_peak")
        self._m_cq_depth = metrics.gauge("wire/cq_depth")
        # striped-wire ledger (BYTEPS_WIRE_STRIPES): cumulative segments
        # and payload bytes fanned across the data conns, refreshed by
        # the completion reactor each poll batch — zeros mean the
        # striper never engaged (payloads under 2 chunks, shm transport,
        # or stripes pinned to 1)
        self._m_stripe_segs = metrics.gauge("wire/stripe_segs")
        self._m_stripe_bytes = metrics.gauge("wire/stripe_bytes")

    def _inflight_add(self, d: int) -> None:
        # gauge writes INSIDE the lock: set() calls from two threads must
        # land in counter order, or a delayed stale set could leave the
        # gauge nonzero after the last request drained
        with self._lock:
            self._inflight += d
            cur = self._inflight
            if cur > self._inflight_peak:
                self._inflight_peak = cur
            if self._m_inflight is not None:
                self._m_inflight.set(cur)
                self._m_inflight_peak.set_max(cur)

    @property
    def inflight_peak(self) -> int:
        """Max simultaneously outstanding wire requests (proof surface
        for the reactor model: fused mode sustains more in-flight
        partitions than the pull pool has threads)."""
        with self._lock:
            return self._inflight_peak

    def _check_server(self, server: int) -> None:
        # the native connection table is indexed UNCHECKED — an
        # out-of-range index from a stale/corrupt partition assignment
        # would read garbage or segfault the whole worker, so reject it
        # here, before anything touches the wire
        if not 0 <= server < len(self._servers):
            raise ValueError(
                f"server index {server} out of range "
                f"[0, {len(self._servers)}) — stale partition table?")

    @property
    def ipc_conns(self) -> int:
        """Connections riding the colocated shm transport (0 = all TCP)."""
        if self._closed:
            raise RuntimeError("PSClient is closed")
        return int(self._lib.bps_client_ipc_conns(self._handle))

    def transport_stats(self) -> dict:
        """Client-side transport counters: shm-upgraded vs total
        connections, and how many messages rode the zero-copy
        descriptor (out-of-band arena) tier each direction —
        ``oob_sent`` counts large pushes whose payload the server folds
        IN PLACE from the shared arena, ``oob_recvd`` counts aggregate
        replies copied once from the arena straight into the caller's
        buffer. Zeros (with conns populated) when the transport is TCP
        or the payloads are below the descriptor threshold; all zeros
        on a stale native lib predating the ABI. ``stripe_segs`` /
        ``stripe_bytes`` count fused PUSHPULL traffic the client split
        across the BYTEPS_WIRE_STRIPES data connections (segments and
        payload bytes; framing overhead is 72B per segment — the
        byte-conservation identity tests/test_wire_stripe.py asserts is
        ``sum(stripe_conn_bytes()) == stripe_bytes + 72*stripe_segs``)."""
        if self._closed:
            raise RuntimeError("transport_stats on a closed PSClient")
        out = {"ipc_conns": 0, "total_conns": 0, "oob_sent": 0,
               "oob_recvd": 0, "stripe_segs": 0, "stripe_bytes": 0}
        if not hasattr(self._lib, "bps_client_transport_stats"):
            return out
        buf = (ctypes.c_uint64 * 6)()
        n = self._lib.bps_client_transport_stats(self._handle, buf, 6)
        for i, k in enumerate(("ipc_conns", "total_conns", "oob_sent",
                               "oob_recvd", "stripe_segs",
                               "stripe_bytes")):
            if i < n:
                out[k] = int(buf[i])
        return out

    def stripe_conn_bytes(self, server: int) -> List[int]:
        """Cumulative TX bytes per connection of one server's group
        (slot 0 is the control lane — always 0 stripe traffic). Sums
        to ``stripe_bytes + 72*stripe_segs`` when only striped traffic
        has flowed: the per-stripe half of the conservation proof.
        Empty list on a stale native lib."""
        self._check_server(server)
        if self._closed:
            raise RuntimeError("stripe_conn_bytes on a closed PSClient")
        if not hasattr(self._lib, "bps_client_stripe_bytes"):
            return []
        buf = (ctypes.c_uint64 * 16)()
        n = self._lib.bps_client_stripe_bytes(self._handle, server,
                                              buf, 16)
        if n < 0:
            return []
        return [int(buf[i]) for i in range(n)]

    def kill_stripe(self, server: int, idx: int) -> bool:
        """TEST HOOK: hard-kill one connection of a server's group
        (socket shutdown) to exercise single-stripe-death failover —
        the striper drops the dead conn from its live set and the
        request completes on the surviving stripes. False on a stale
        native lib or bad index."""
        self._check_server(server)
        if not hasattr(self._lib, "bps_client_kill_stripe"):
            return False
        return self._lib.bps_client_kill_stripe(
            self._handle, server, idx) == 0

    # ------------------------------------------------------------ #
    # fleet observability control plane (docs/observability.md):
    # stats/trace/flight pulls + the clock probe — the wire ops that
    # make an out-of-process server as measurable as an in-process one
    # ------------------------------------------------------------ #

    @property
    def supports_fleet(self) -> bool:
        """True when the loaded native library speaks the observability
        control ops (False only under stale-.so version skew — the
        fleet surfaces then degrade to in-process servers only)."""
        return hasattr(self._lib, "bps_client_ctrl")

    def _ctrl(self, server: int, op: str, cap: int,
              timeout_s: int = 5) -> Optional[bytes]:
        """One bounded control pull; returns the reply payload or None
        (unsupported ABI / failed request). The per-request timeout is
        deliberate: a wedged server costs a metrics poll ``timeout_s``
        seconds, never the data plane's BYTEPS_CLIENT_TIMEOUT_S."""
        self._check_server(server)
        if self._closed:
            raise RuntimeError("control pull on a closed PSClient")
        if not self.supports_fleet:
            return None
        buf = (ctypes.c_uint8 * cap)()
        n = self._lib.bps_client_ctrl(
            self._handle, server, WIRE_CTRL_OPS[op], buf, cap, timeout_s)
        if n < 0:
            return None
        return bytes(buf[:n])

    def server_stats(self, server: int,
                     timeout_s: int = 5) -> Optional[dict]:
        """One remote server's full per-stage registry snapshot (the
        same slot vector as the in-process ``bps_server_stats`` mirror,
        by construction — STATS_PULL answers from one definition).
        None when the server is unreachable or the ABI is stale."""
        raw = self._ctrl(server, "STATS_PULL", 64 * 8, timeout_s)
        if raw is None or len(raw) % 8:
            return None
        from . import parse_stat_slots
        return parse_stat_slots(raw)

    def drain_trace(self, server: int, timeout_s: int = 5,
                    max_batches: int = 64) -> List[dict]:
        """Drain (destructively) the server's wire-sampled trace ring:
        a list of record dicts (``kind`` 0 = request span with recv/
        enqueue/dequeue/done server-clock ns, 1 = reply send). Loops
        full batches so one call empties the ring."""
        from . import TRACE_REC_BYTES, TRACE_REC_FMT, _TRACE_REC_FIELDS
        out: List[dict] = []
        batch_cap = WIRE_CTRL_LIMITS["kCtrlDrainBatch"] * TRACE_REC_BYTES
        for _ in range(max_batches):
            raw = self._ctrl(server, "TRACE_DRAIN", batch_cap, timeout_s)
            if not raw or len(raw) % TRACE_REC_BYTES:
                break
            out += [dict(zip(_TRACE_REC_FIELDS, rec))
                    for rec in struct.iter_unpack(TRACE_REC_FMT, raw)]
            if len(raw) < batch_cap:
                break
        return out

    def drain_flight(self, server: int, timeout_s: int = 5) -> List[dict]:
        """Snapshot the server's flight-recorder ring (non-destructive:
        a poll never steals the events a later crash dump needs); kinds
        resolve to names via ``FLIGHT_KIND_NAMES``."""
        from . import (
            FLIGHT_KIND_NAMES, FLIGHT_REC_BYTES, FLIGHT_REC_FMT,
            _FLIGHT_REC_FIELDS,
        )
        raw = self._ctrl(
            server, "FLIGHT_DRAIN",
            WIRE_CTRL_LIMITS["kCtrlFlightDrainMax"] * FLIGHT_REC_BYTES,
            timeout_s)
        if not raw or len(raw) % FLIGHT_REC_BYTES:
            return []
        out = []
        for rec in struct.iter_unpack(FLIGHT_REC_FMT, raw):
            d = dict(zip(_FLIGHT_REC_FIELDS, rec))
            d.pop("pad", None)
            d["kind"] = FLIGHT_KIND_NAMES.get(d["kind"], str(d["kind"]))
            out.append(d)
        return out

    def stripe_stats(self, server: int,
                     timeout_s: int = 5) -> List[dict]:
        """One remote server's per-conn / per-data-lane wire counters
        (the time-series plane's de-aggregated stripe source): a list
        of ``_STRIPE_REC_FIELDS`` dicts, one per live connection there,
        counters cumulative since accept. Empty when the server is
        unreachable or the ABI is stale. The in-process mirror
        (``server.per_conn_stripe_stats``) answers from the same
        StripeSlots vector, by construction."""
        from . import STRIPE_REC_BYTES, parse_stripe_recs
        raw = self._ctrl(
            server, "STRIPE_PULL",
            WIRE_CTRL_LIMITS["kCtrlStripeMax"] * STRIPE_REC_BYTES,
            timeout_s)
        if raw is None:
            return []
        return parse_stripe_recs(raw)

    def health_pull(self, server: int, key: int,
                    timeout_s: int = 5) -> Optional[dict]:
        """Per-key POST-AGGREGATION health statistics (the training-
        health plane, docs/observability.md): the server's in-fold
        pass (BYTEPS_HEALTH) computes sum-of-squares / abs-max /
        nonfinite counts of each published aggregate, and this keyed
        control pull fetches the last round's record —
        ``{key, round, sumsq, absmax, nonfinite, elems}``. None when
        the key is unknown there, the server runs with the pass off,
        or the ABI is stale. Bounded like every control pull: a wedged
        server costs ``timeout_s`` seconds, never the data plane's
        budget."""
        self._check_server(server)
        if self._closed:
            raise RuntimeError("control pull on a closed PSClient")
        if not hasattr(self._lib, "bps_client_ctrl_key"):
            return None
        from . import HEALTH_REC_BYTES, parse_health_rec
        buf = (ctypes.c_uint8 * HEALTH_REC_BYTES)()
        n = self._lib.bps_client_ctrl_key(
            self._handle, server, WIRE_CTRL_OPS["HEALTH_PULL"],
            int(key), buf, HEALTH_REC_BYTES, timeout_s)
        if n != HEALTH_REC_BYTES:
            return None
        return parse_health_rec(bytes(buf))

    def clock_probe(self, server: int, probes: int = 8,
                    timeout_s: int = 5) -> Optional[tuple]:
        """Estimate ``server``'s steady-clock offset NTP-style from
        request/reply timestamp echoes: ``probes`` round trips, keep
        the minimum-RTT sample (utils/tracing.py estimate_clock_offset).
        Returns (offset_ns, err_bound_ns) where
        ``server_clock - offset ≈ this process's clock``, or None when
        unsupported/unreachable."""
        self._check_server(server)
        if self._closed or not self.supports_fleet:
            return None
        buf = (ctypes.c_uint64 * 4)()
        samples = []
        for _ in range(max(1, probes)):
            if self._lib.bps_client_clock_probe(
                    self._handle, server, buf, timeout_s) != 0:
                continue
            samples.append((int(buf[0]), int(buf[1]), int(buf[2]),
                            int(buf[3])))
        if not samples:
            return None
        from ..utils.tracing import estimate_clock_offset
        return estimate_clock_offset(samples)

    # ------------------------------------------------------------ #
    # per-server health (the elastic/failover plane)
    # ------------------------------------------------------------ #

    def server_dead(self, server: int) -> bool:
        """True when EVERY striped native connection to ``server`` is
        dead (transport EOF after a crash/SIGKILL, or poisoned) — the
        worker-side server-death verdict. Driven by the native recv
        loops / completion reactor conn-death path, so it flips within
        milliseconds of the TCP EOF (the shm-ring transport polls the
        paired TCP fd for liveness at 5ms granularity). False for
        in-range healthy servers and when the loaded native lib
        predates the probe (version skew: failover simply never
        triggers)."""
        if self._closed or not 0 <= server < len(self._servers):
            return True
        if not hasattr(self._lib, "bps_client_server_dead"):
            return False
        return bool(self._lib.bps_client_server_dead(self._handle, server))

    def dead_servers(self) -> List[int]:
        """Indices of servers whose every connection is dead."""
        return [s for s in range(len(self._servers)) if self.server_dead(s)]

    # ------------------------------------------------------------ #
    # elastic fleet: runtime scale-up join + graceful drain
    # (core/elastic.py drives these; docs/fault-tolerance.md)
    # ------------------------------------------------------------ #

    @property
    def servers(self) -> List[str]:
        """The live server address list (grows on :meth:`add_server`)."""
        with self._lock:
            return list(self._servers)

    @property
    def supports_elastic(self) -> bool:
        """True when the loaded native library can grow its connection
        table at runtime (False only under stale-.so version skew)."""
        return hasattr(self._lib, "bps_client_add_server")

    def add_server(self, address: str) -> int:
        """Connect this client to a NEW server at runtime and return its
        index (== the previous server count). The native side publishes
        the fully-connected striped conn group atomically, so in-flight
        traffic to existing servers never races the growth. The caller
        must run :meth:`join_probe` before routing keys to the index."""
        with self._lock:
            if self._closed:
                raise RuntimeError("add_server on a closed PSClient")
        if not self.supports_elastic:
            raise RuntimeError(
                "native library predates runtime scale-up "
                "(bps_client_add_server missing) — rebuild the native "
                "lib to grow the fleet at runtime")
        idx = self._lib.bps_client_add_server(self._handle,
                                              address.encode())
        if idx < 0:
            raise RuntimeError(
                f"failed to connect new PS server at {address!r}")
        with self._lock:
            # the native index is authoritative; the Python list exists
            # for range checks and re-connect bookkeeping
            while len(self._servers) <= idx:
                self._servers.append(address)
            self._servers[idx] = address
        log.info("PS client: joined server %d at %s", idx, address)
        return idx

    def join_probe(self, server: int,
                   timeout_s: int = 5) -> Optional[dict]:
        """Scale-up join handshake: ask ``server`` for its worker count
        and draining state (JOIN_PROBE control op). Returns
        ``{"num_workers", "draining"}`` or None (unreachable / stale
        ABI). The caller validates ``num_workers`` against its own
        config BEFORE the registry routes key subranges there — a
        mismatched newcomer would wedge every aggregation round."""
        raw = self._ctrl(server, "JOIN_PROBE", 16, timeout_s)
        if raw is None or len(raw) != 16:
            return None
        nw, draining = struct.unpack("<QQ", raw)
        return {"num_workers": int(nw), "draining": bool(draining)}

    def drain_req(self, server: int,
                  timeout_s: int = 5) -> Optional[dict]:
        """Graceful-drain ACK (DRAIN_REQ control op): latch the server's
        advisory draining flag and collect ``{"keys_held",
        "draining"}``. Called AFTER the registry migrated the server's
        keys away; best-effort — a dead/stale server returns None and
        the drain proceeds regardless (the flag is forensic, not a
        correctness gate)."""
        raw = self._ctrl(server, "DRAIN_REQ", 16, timeout_s)
        if raw is None or len(raw) != 16:
            return None
        held, draining = struct.unpack("<QQ", raw)
        return {"keys_held": int(held), "draining": bool(draining)}

    def invalidate_init(self, keys) -> None:
        """Forget that ``keys`` were init-pushed: after a key migrates to
        a different server (registry ``migrate_server``), the adoptive
        server has no store for it yet — the next ``ensure_init`` must
        re-init-push there instead of trusting this client's cache (which
        only records key→length, not which server holds the store)."""
        with self._lock:
            for k in keys:
                self._inited_keys.pop(k, None)

    # ------------------------------------------------------------ #
    # raw per-key ops (ZPush/ZPull)
    # ------------------------------------------------------------ #

    def init_key(self, server: int, key: int, data: np.ndarray,
                 cmd: int) -> None:
        self._check_server(server)
        buf = np.ascontiguousarray(data)
        rc = self._lib.bps_client_init_key(
            self._handle, server, key, buf.ctypes.data, buf.nbytes, cmd)
        if rc != 0:
            raise RuntimeError(f"init_key failed key={key}")

    def zpush(self, server: int, key: int, data: np.ndarray,
              cmd: int, epoch: int = 0, codec: int = 0) -> None:
        """``epoch``: optional (round << 16 | attempt) replay-dedup stamp
        — the server folds a given (key, sender, round) at most once, so
        a retried push after a dropped reply never double-counts
        (docs/fault-tolerance.md). 0 = unstamped (legacy semantics).
        ``codec``: optional (plan_epoch << 8 | codec_id) adaptive-codec
        wire tag — the server latches the first fold's tag per round and
        loudly rejects disagreeing folds (docs/compression.md). 0 =
        untagged, no validation."""
        self._check_server(server)
        data = np.ascontiguousarray(data)  # .ctypes.data of a strided
        rc = self._lib.bps_client_push(   # view points at the base buffer
            self._handle, server, key, data.ctypes.data, data.nbytes, cmd,
            epoch, codec)
        if self._m_push_req is not None:
            self._m_push_req.inc()
            self._m_push_bytes.inc(data.nbytes)
        if rc != 0:
            if self._m_errors is not None:
                self._m_errors.inc()
            raise RuntimeError(f"push failed key={key}")

    def zpush_async(self, server: int, key: int, data: np.ndarray,
                    cmd: int, epoch: int = 0, codec: int = 0) -> None:
        """Fire-and-forget push: returns once the payload is on the wire
        (the native send copies it into the socket/ring, so ``data`` may
        be reused immediately). The ACK drains in the background; a
        server reject poisons the connection and surfaces on the paired
        zpull. Removes the ACK round-trip from the pipeline's critical
        path — the pull is the only synchronization, matching ps-lite's
        asynchronous ZPush. ``epoch``: replay-dedup stamp, ``codec``:
        adaptive wire tag (see zpush)."""
        self._check_server(server)
        data = np.ascontiguousarray(data)
        rc = self._lib.bps_client_push_async(
            self._handle, server, key, data.ctypes.data, data.nbytes, cmd,
            epoch, codec)
        if self._m_push_req is not None:
            self._m_push_req.inc()
            self._m_push_bytes.inc(data.nbytes)
        if rc != 0:
            if self._m_errors is not None:
                self._m_errors.inc()
            raise RuntimeError(f"async push failed key={key}")

    def zpull(self, server: int, key: int, out: np.ndarray,
              cmd: int, exact: bool = False) -> int:
        """Pull into ``out``; returns the ACTUAL reply length (equal to
        out.nbytes for dense/fixed formats, possibly shorter for
        variable-length wires like varint-coded dithering).

        ``exact=True``: the caller means ``out`` as the whole reply
        (dense pulls) — a SHORTER reply then raises instead of leaving
        the tail of ``out`` unwritten garbage (stale partitioning after
        a tensor resize). A reply LONGER than ``out`` always fails: the
        native side drains it whole — the byte stream stays
        message-aligned, so the connection survives — and reports the
        mismatch instead of truncating."""
        self._check_server(server)
        if not out.flags["C_CONTIGUOUS"]:
            # the native side writes through .ctypes.data — a strided
            # view would silently receive bytes at the wrong offsets
            raise ValueError("zpull requires a C-contiguous output array")
        self._inflight_add(1)
        try:
            rc = self._lib.bps_client_pull(
                self._handle, server, key, out.ctypes.data, out.nbytes, cmd)
        finally:
            self._inflight_add(-1)
        if self._m_pull_req is not None:
            self._m_pull_req.inc()
        if rc < 0:
            if self._m_errors is not None:
                self._m_errors.inc()
            raise RuntimeError(
                f"pull failed key={key} (server error, reply larger than "
                f"the {out.nbytes}-byte output view, or connection lost)")
        if exact and rc != out.nbytes:
            if self._m_errors is not None:
                self._m_errors.inc()
            raise RuntimeError(
                f"pull reply for key={key} is {rc} bytes, expected exactly "
                f"{out.nbytes} — stale partitioning after a tensor resize?")
        if self._m_pull_bytes is not None:
            self._m_pull_bytes.inc(rc)  # actual reply length
        return rc

    # ------------------------------------------------------------ #
    # fused PUSHPULL + completion reactor
    # ------------------------------------------------------------ #

    @property
    def supports_fused(self) -> bool:
        """True when the loaded native library has the fused PUSHPULL op
        (always, for in-tree builds; False only under version skew)."""
        return hasattr(self._lib, "bps_client_pushpull_async")

    def zpushpull_async(self, server: int, key: int, data: np.ndarray,
                        out: np.ndarray, cmd: int,
                        on_done: Callable[[int, Optional[Exception]], None],
                        epoch: int = 0, codec: int = 0,
                        rid_out: Optional[ctypes.c_uint32] = None) -> int:
        """Fused push+pull in ONE wire round trip: push ``data``, and
        when the server's aggregation round completes, the aggregate
        lands in ``out`` and ``on_done(reply_len, error)`` runs on the
        completion-reactor thread (keep it tiny or hand off). Returns
        the moment the request is on the wire — no thread parks for the
        aggregation wait, so in-flight partitions are bounded by
        scheduling credit, not pool size. ``out`` must stay alive until
        ``on_done`` fires (the registration table pins it). ``epoch``:
        replay-dedup stamp (see zpush) — a retried fused request with
        the same round is answered from the round's aggregate without
        re-folding the payload. ``codec``: adaptive wire tag (see
        zpush).

        Returns the request's wire rid (0 on a native lib predating the
        reporting ABI) — the id server-side trace spans carry, which the
        fused timeline uses to flow-link worker and server spans. The
        native send writes it into ``rid_out`` (the caller's cell)
        before the request is on the wire, so ``on_done`` can read it
        there even when the reply beats this call's return."""
        self._check_server(server)
        if not out.flags["C_CONTIGUOUS"]:
            raise ValueError(
                "zpushpull_async requires a C-contiguous reply buffer")
        if self._closed:
            raise RuntimeError("zpushpull_async on a closed PSClient")
        data = np.ascontiguousarray(data)
        with self._fused_mu:
            ticket = self._next_ticket
            self._next_ticket += 1
            # register BEFORE the send: the reply can complete (and the
            # reactor dispatch) before the native call returns
            self._fused[ticket] = (on_done, out)
        self._ensure_reactor()
        self._inflight_add(1)
        rid = rid_out if rid_out is not None else ctypes.c_uint32(0)
        if hasattr(self._lib, "bps_client_pushpull_async2"):
            rc = self._lib.bps_client_pushpull_async2(
                self._handle, server, key, data.ctypes.data, data.nbytes,
                cmd, out.ctypes.data, out.nbytes, ticket, epoch, codec,
                ctypes.byref(rid))
        else:
            rc = self._lib.bps_client_pushpull_async(
                self._handle, server, key, data.ctypes.data, data.nbytes,
                cmd, out.ctypes.data, out.nbytes, ticket, epoch, codec)
        if self._m_pushpull_req is not None:
            self._m_pushpull_req.inc()
            self._m_push_bytes.inc(data.nbytes)
        if rc != 0:
            # rc != 0 means the native side still OWNED the waiter when
            # the send failed (a fail-all sweep that claimed it first
            # reports success and fails the ticket through the queue
            # instead) — so exactly one of {this raise, the reactor
            # callback} fires. The pop guard keeps it that way even if
            # a stray record raced us.
            with self._fused_mu:
                owned = self._fused.pop(ticket, None) is not None
            if not owned:
                return int(rid.value)  # reactor already owns the failure
            self._inflight_add(-1)
            if self._m_errors is not None:
                self._m_errors.inc()
            raise RuntimeError(
                f"fused pushpull failed to send key={key} "
                f"(connection poisoned or lost)")
        return int(rid.value)

    def _ensure_reactor(self) -> None:
        # double-checked locking: the flag only ever flips False->True,
        # so the lock-free fast path can at worst take the slow path
        # once more — keeping the lock off every post-startup send
        if self._reactor_started:  # bps-lint: disable=guarded-by
            return
        with self._lock:
            if self._reactor_started:
                return
            self._reactor = threading.Thread(
                target=self._reactor_loop, name="bps-cq-reactor",
                daemon=True)
            self._reactor_started = True
            self._reactor.start()

    def _reactor_loop(self) -> None:
        """THE receive-completion thread: drains the native completion
        queue in batches and resolves per-ticket callbacks. One thread
        regardless of how many partitions are in flight — the
        O(connections) half of the reactor model (the per-connection
        recv loops are native)."""
        max_n = 128
        tickets = (ctypes.c_uint64 * max_n)()
        statuses = (ctypes.c_int32 * max_n)()
        lens = (ctypes.c_uint32 * max_n)()
        while True:
            n = self._lib.bps_client_cq_poll(
                self._handle, tickets, statuses, lens, max_n, 250)
            if n < 0:
                return  # queue closed and drained: teardown
            if self._m_cq_depth is not None:
                self._m_cq_depth.set(
                    self._lib.bps_client_cq_depth(self._handle))
            if (self._m_stripe_segs is not None
                    and hasattr(self._lib,
                                "bps_client_transport_stats")):
                tbuf = (ctypes.c_uint64 * 6)()
                tn = self._lib.bps_client_transport_stats(
                    self._handle, tbuf, 6)
                if tn >= 6:
                    self._m_stripe_segs.set(int(tbuf[4]))
                    self._m_stripe_bytes.set(int(tbuf[5]))
            for i in range(n):
                with self._fused_mu:
                    entry = self._fused.pop(int(tickets[i]), None)
                if entry is None:
                    # already failed locally (close() / send-failure
                    # raise): that path decremented inflight — doing it
                    # again here would underflow the gauge
                    continue
                self._inflight_add(-1)
                cb, _out = entry
                status = int(statuses[i])
                err = None
                if status == -2:
                    err = TimeoutError(
                        "fused pushpull timed out waiting for the "
                        "aggregation round (BYTEPS_CLIENT_TIMEOUT_S)")
                elif status != 0:
                    err = RuntimeError(
                        "fused pushpull failed (server error reply, "
                        "oversized reply, or connection lost)")
                elif self._m_pull_bytes is not None:
                    self._m_pull_bytes.inc(int(lens[i]))
                try:
                    cb(int(lens[i]), err)
                except Exception:  # noqa: BLE001 - must not kill reactor
                    log.exception(
                        "fused completion callback raised (ticket %d)",
                        int(tickets[i]))

    def _stop_reactor(self) -> None:
        """Teardown half-step: fail outstanding fused requests into the
        queue, close it, and join the reactor so no native callback can
        run after the client handle is freed."""
        with self._lock:
            started = self._reactor_started
        if not started:
            return
        try:
            self._lib.bps_client_cq_abort(self._handle)
        except Exception:  # noqa: BLE001 - best-effort teardown
            pass
        if self._reactor is not None:
            self._reactor.join(timeout=10)
        # anything the reactor didn't get to (it died, or records were
        # dropped after close): resolve with an error so waiters raise
        # instead of hanging
        with self._fused_mu:
            leftovers = list(self._fused.items())
            self._fused.clear()
        for ticket, (cb, _out) in leftovers:
            try:
                cb(0, RuntimeError("PSClient closed with the fused "
                                   "request still in flight"))
            except Exception:  # noqa: BLE001
                log.exception("fused teardown callback raised (ticket %d)",
                              ticket)

    def comp_init(self, server: int, key: int, kwargs_wire: str) -> None:
        """Install a server-side compressor for ``key`` (the reference's
        in-band kCompressedPushPull kwargs push, operations.cc:396-408)."""
        self._check_server(server)
        rc = self._lib.bps_client_comp_init(
            self._handle, server, key, kwargs_wire.encode())
        if rc != 0:
            raise RuntimeError(
                f"comp_init failed key={key} kwargs={kwargs_wire!r} "
                f"(is the store init-pushed as dense f32, sync mode?)")

    def barrier(self) -> None:
        if self._lib.bps_client_barrier(self._handle) != 0:
            raise RuntimeError("barrier failed")

    # ------------------------------------------------------------ #
    # tensor-level push_pull over partitions
    # ------------------------------------------------------------ #

    def init_tensor(self, ctx: TensorContext, flat: np.ndarray) -> None:
        """Blocking initial push of every partition — acts as the per-key
        init barrier (reference: operations.cc:283-414)."""
        cmd = get_command_type(RequestType.DEFAULT_PUSH_PULL, ctx.dtype)
        view = flat.view(np.uint8)
        futures = [
            self._pool.submit(self.init_key, p.server, p.key,
                              view[p.offset:p.offset + p.length], cmd)
            for p in ctx.partitions
        ]
        for f in futures:
            f.result()
        with self._lock:
            self._inited_keys.update(
                {p.key: p.length for p in ctx.partitions})

    def ensure_init(self, ctx: TensorContext, nbytes: int) -> None:
        """Init-push any partition of ctx this client hasn't initialized on
        the server at its current length (registry declaration alone doesn't
        allocate the server store; a resized tensor re-inits). Only the
        missing partitions are pushed — every worker derives the same
        ``missing`` set from the shared registry partitioning, so the
        per-key init barrier still converges."""
        total = sum(p.length for p in ctx.partitions)
        if nbytes != total:
            # the partitioning drives everything below; a caller whose
            # byte count disagrees has a stale ctx (resize without
            # re-declare) and would init the wrong store lengths
            raise ValueError(
                f"ensure_init: caller nbytes={nbytes} != partitioned "
                f"total {total} for {ctx.name!r} — re-declare the tensor "
                f"(registry.init_tensor) after a resize")
        with self._lock:
            missing = [p for p in ctx.partitions
                       if self._inited_keys.get(p.key) != p.length]
        if not missing:
            return
        cmd = get_command_type(RequestType.DEFAULT_PUSH_PULL, ctx.dtype)
        futures = [
            self._pool.submit(self.init_key, p.server, p.key,
                              np.zeros(p.length, np.uint8), cmd)
            for p in missing
        ]
        for f in futures:
            f.result()
        with self._lock:
            self._inited_keys.update({p.key: p.length for p in missing})

    def _round_trip(self, ctx: TensorContext, in_flat: np.ndarray,
                    out_flat: np.ndarray) -> None:
        """Concurrent per-partition push-then-pull against the assigned
        servers (the PUSH/PULL stage pair, core_loops.cc:538-618)."""
        cmd = get_command_type(RequestType.DEFAULT_PUSH_PULL,
                               DataType.from_np(in_flat.dtype))
        in_view = in_flat.view(np.uint8)
        out_view = out_flat.view(np.uint8)

        def one(p: Partition):
            self.zpush(p.server, p.key,
                       in_view[p.offset:p.offset + p.length], cmd)
            self.zpull(p.server, p.key,
                       out_view[p.offset:p.offset + p.length], cmd,
                       exact=True)  # dense: a short reply is an error

        futures = [self._pool.submit(one, p) for p in ctx.partitions]
        for f in futures:
            f.result()

    def push_pull_rowsparse(self, ctx: TensorContext, host2d: np.ndarray,
                            average: bool = True,
                            num_workers: Optional[int] = None) -> np.ndarray:
        """Row-sparse aggregation round (the op the reference reserves as
        kRowSparsePushPull but leaves unimplemented): push only the NONZERO
        rows of a [R, W] f32 gradient — [u32 nrows][u32 W][i32 ids]
        [f32 rows] per partition — the server scatter-adds them into the
        dense store, and the pull returns the dense aggregate. The tensor
        must be declared with row-aligned partitions
        (init_tensor(..., align_bytes=W*4))."""
        if self._closed:
            raise RuntimeError("push_pull_rowsparse on a closed PSClient")
        host2d = np.ascontiguousarray(host2d, np.float32)
        rows, width = host2d.shape
        row_bytes = width * 4
        self.ensure_init(ctx, host2d.nbytes)
        cmd_sparse = get_command_type(RequestType.ROW_SPARSE_PUSH_PULL,
                                      DataType.FLOAT32)
        cmd_dense = get_command_type(RequestType.DEFAULT_PUSH_PULL,
                                     DataType.FLOAT32)
        nz = np.flatnonzero(np.any(host2d != 0, axis=1)).astype(np.int32)
        out = np.empty(rows * width, np.float32)

        def one(p: Partition):
            buf = build_rowsparse_payload(p, nz, host2d)
            self.zpush(p.server, p.key, buf, cmd_sparse)
            dst = out.view(np.uint8)[p.offset:p.offset + p.length]
            self.zpull(p.server, p.key, dst, cmd_dense, exact=True)

        futures = [self._pool.submit(one, p) for p in ctx.partitions]
        for f in futures:
            f.result()
        if average and num_workers and num_workers > 1:
            out /= num_workers
        return out.reshape(rows, width)

    def push_pull(self, ctx: TensorContext, flat: np.ndarray,
                  average: bool = True,
                  num_workers: Optional[int] = None,
                  out: Optional[np.ndarray] = None) -> np.ndarray:
        """Partitioned push+pull of one tensor; returns the summed
        (averaged) flat array. ``out``: optional preallocated result
        buffer (host staging arena); ignored on any mismatch."""
        if self._closed:
            raise RuntimeError("push_pull on a closed PSClient")
        dtype = flat.dtype
        self.ensure_init(ctx, flat.nbytes)
        from ..core.arena import usable_staging
        if not usable_staging(out, dtype, flat.nbytes):
            out = np.empty_like(flat)
        self._round_trip(ctx, flat, out)
        if average and num_workers and num_workers > 1:
            if np.issubdtype(dtype, np.integer):
                # truncation toward zero (the reference's C++
                # div_(size)); shared helper — exact incl. INT_MIN
                trunc_divide_inplace(out, num_workers)
            else:
                out /= num_workers
        return out

    def init_weights(self, ctx: TensorContext, flat: np.ndarray) -> None:
        """Async-mode bootstrap: init-push the worker's initial weights so
        the server's authoritative copy starts from them (the reference
        seeds the async store with the first init push,
        server.cc:266-295,434-436). Blocks until every worker has
        init-pushed (the per-key barrier); the first arrival's values win."""
        self.init_tensor(ctx, flat)

    def push_delta_pull_weights(self, ctx: TensorContext,
                                delta: np.ndarray) -> np.ndarray:
        """Asynchronous data parallelism (BYTEPS_ENABLE_ASYNC): push this
        worker's weight DELTA — the server folds it straight into the
        authoritative weights — and pull the current weights back, with no
        cross-worker aggregation barrier (reference: server.cc:315-319,
        torch/__init__.py:188-216). Requires the server to run in async
        mode; no averaging (each worker's delta applies in full)."""
        if self._closed:
            raise RuntimeError("push_delta_pull_weights on a closed PSClient")
        out = np.empty_like(delta)
        self._round_trip(ctx, delta, out)
        return out

    def close(self, shutdown_servers: bool = True) -> None:
        """``shutdown_servers=False`` = elastic suspend: drop the
        connections but leave servers running for resume (the reference's
        Finalize-without-terminate path, global.cc:319-403)."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
        # drain in-flight partition tasks BEFORE freeing the native client —
        # wait=False would leave pool threads calling into freed memory
        self._pool.shutdown(wait=True)
        # fail + drain fused completions and JOIN the reactor before the
        # native handle goes away (a reactor poll on a freed handle is a
        # use-after-free)
        self._stop_reactor()
        if shutdown_servers:
            try:
                self._lib.bps_client_shutdown(self._handle)
            except Exception:  # noqa: BLE001
                pass
        self._lib.bps_client_destroy(self._handle)


def connect_from_config(config: Config) -> PSClient:
    servers = server_addresses(config)
    if not servers:
        raise RuntimeError("num_servers > 0 but no server addresses")
    rank = (config.global_rank if config.global_rank is not None
            else config.worker_id * config.local_size + config.local_rank)
    log.info("connecting PS client: servers=%s worker=%d", servers, rank)
    return PSClient(servers, rank)
