"""Host-side pipeline scheduler for the DCN PS path.

TPU re-grounding of the reference's core pipeline (byteps/common/
core_loops.cc, scheduled_queue.cc, ready_table.cc): on GPU the 12-stage
host-thread pipeline exists because every stage (NCCL, D2H, compress, push)
must be hand-overlapped; on TPU, XLA owns everything on-device, so the host
pipeline shrinks to the stages that actually cross the DCN boundary:

    EXPORT (device->host) -> WIRE (fused PUSHPULL) -> IMPORT (host->device)

(the two-op PUSH -> PULL pair remains as the BYTEPS_FUSED_PUSHPULL=0 /
old-server fallback) with per-partition tasks, priority scheduling and
credit-based admission exactly as the reference's worker side does it:

- ``ScheduledQueue``: tasks ordered by (priority desc, key asc)
  (scheduled_queue.cc:82-102), admitted while the in-flight byte credit
  lasts (BYTEPS_SCHEDULING_CREDIT, scheduled_queue.cc:33-45,136-149);
  ``report_finish`` returns credit.
- ``PipelineScheduler``: one thread pool per comm stage; a task finishing a
  stage proceeds to the next queue, and the per-tensor atomic counter fires
  the completion callback when the last partition lands (FinishOrProceed,
  core_loops.cc:31-137).
- ``HandleManager``: integer handles for the async API
  (reference: byteps/torch/handle_manager.cc, ops.py:48-85).

Priority convention matches the reference: priority = -declared_key so
earlier-declared (front-of-model) tensors win ties in the backward flush
(tensorflow/ops.cc:155-158); higher value = more urgent.
"""

from __future__ import annotations

import ctypes
import heapq
import itertools
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from ..utils import tracing
from ..utils.logging import log
from .types import Partition, TensorContext, trunc_divide_inplace

# Credit default when scheduling is off: effectively unlimited
# (the reference uses 32 GB, scheduled_queue.cc:33-45).
UNLIMITED_CREDIT = 32 << 30


class ScheduledQueue:
    """Priority + credit gated task queue (scheduled_queue.cc)."""

    def __init__(self, credit_bytes: int = 0, metrics=None, profiler=None,
                 window: int = 0):
        # credit_bytes <= 0 -> scheduling disabled -> huge credit
        self._credit = (credit_bytes if credit_bytes > 0
                        else UNLIMITED_CREDIT)  # guarded-by: _cv|_mu
        self._capacity = self._credit
        self._scheduling = credit_bytes > 0
        self._mu = threading.Lock()
        self._cv = threading.Condition(self._mu)
        # _cv wraps _mu, so holding either guards the same state
        self._heap: List = []          # guarded-by: _cv|_mu
        self._counter = itertools.count()
        self._stopped = False          # guarded-by: _cv|_mu
        # in-flight task count per key: same-key tasks are serialized —
        # overlapping push_pulls of one tensor must not interleave their
        # PUSH/PULL into the same server aggregation round — EXCEPT
        # under the cross-barrier staleness credit (window > 0), where
        # up to window+1 SUCCESSIVE rounds of one dense fused key may be
        # in flight at once: each carries its own round stamp, and the
        # server's RoundGate window parks (never mis-sums) the round
        # that arrives ahead. Submission order is preserved by seq, so
        # round k always admits before round k+1 of the same key.
        self._inflight: Dict[int, int] = {}  # guarded-by: _cv|_mu
        # staleness credit (BYTEPS_STALENESS, plumbed by the pipeline
        # scheduler ONLY for fused-pushpull dense traffic): bound on
        # extra same-key rounds admitted while one is in flight
        self._window = max(0, int(window))
        # measurement plane (core/metrics.py); None when metrics off —
        # instrument refs cached here so the hot path never takes the
        # registry lock
        self._profiler = profiler
        # set by _pop_admissible_locked
        self._credit_blocked = False   # guarded-by: _cv|_mu
        if metrics is not None:
            self._depth_gauge = metrics.gauge("scheduler/queue_depth")
            self._admit_hist = metrics.histogram(
                "scheduler/admission_wait_us")
            self._stall_ctr = metrics.counter("scheduler/credit_stalls")
        else:
            self._depth_gauge = self._admit_hist = self._stall_ctr = None

    def add_task(self, task: "PartitionTask") -> None:
        with self._cv:
            if self._stopped:
                raise RuntimeError("scheduler stopped")
            task.enqueue_t = time.perf_counter()
            # (priority desc, key asc): negate priority for the min-heap;
            # seq keeps same-key tasks in submission order
            heapq.heappush(self._heap,
                           (-task.priority, task.key, next(self._counter),
                            task))
            depth = len(self._heap)
            self._cv.notify()
        if self._depth_gauge is not None:
            self._depth_gauge.set(depth)
            prof = self._profiler.current() if self._profiler else None
            if prof is not None:
                prof.queue_depth(depth)

    def get_task(self) -> Optional["PartitionTask"]:
        """Block until a task is admitted (enough credit, key not already
        in flight) or stop()."""
        stall_counted = False
        with self._cv:
            while True:
                if self._stopped:
                    return None
                task = self._pop_admissible_locked()
                if task is not None:
                    self._credit -= task.nbytes
                    self._inflight[task.key] = \
                        self._inflight.get(task.key, 0) + 1
                    depth = len(self._heap)
                    break
                if (self._credit_blocked and not stall_counted
                        and self._stall_ctr is not None):
                    # one stall EPISODE per blocked admission attempt,
                    # not one per 0.1s poll of the same starvation
                    stall_counted = True
                    self._stall_ctr.inc()
                    prof = self._profiler.current() if self._profiler \
                        else None
                    if prof is not None:
                        prof.credit_stall()
                self._cv.wait(timeout=0.1)
        task.admit_t = time.perf_counter()
        if self._admit_hist is not None:
            self._depth_gauge.set(depth)
            if task.enqueue_t is not None:
                self._admit_hist.record_seconds(
                    task.admit_t - task.enqueue_t)
        return task

    def _pop_admissible_locked(self) -> Optional["PartitionTask"]:
        """Pop the highest-priority admissible task. In-flight keys are
        skipped (their next task runs when the current one finishes)
        unless the staleness window grants them extra same-key credit —
        plain (uncompressed) tasks only, whose round-stamped folds the
        server's window gate can park without mis-summing; a
        credit-starved head blocks admission entirely — lower-priority
        tasks must not overtake it just because they're smaller
        (scheduled_queue.cc:136-149 admits strictly in order)."""
        skipped: List = []
        found = None
        self._credit_blocked = False
        while self._heap:
            item = heapq.heappop(self._heap)
            t = item[3]
            limit = 1 + (self._window if t.stack is None else 0)
            if self._inflight.get(t.key, 0) >= limit:
                skipped.append(item)
                continue
            # a task larger than the whole capacity must still run once
            # credit is fully restored, or it stalls the queue forever
            if t.nbytes <= self._credit or self._credit >= self._capacity:
                found = t
            else:
                skipped.append(item)
                self._credit_blocked = True
            break
        for item in skipped:
            heapq.heappush(self._heap, item)
        return found

    def report_finish(self, task: "PartitionTask") -> None:
        with self._cv:
            self._credit += task.nbytes
            n = self._inflight.get(task.key, 0) - 1
            if n > 0:
                self._inflight[task.key] = n
            else:
                self._inflight.pop(task.key, None)
            self._cv.notify_all()

    def stop(self) -> None:
        """Stop and return the tasks that never ran (callers fail them).
        The flag flip and the drain are atomic so an add_task racing with
        stop either lands before the drain or raises."""
        with self._cv:
            self._stopped = True
            tasks = [item[3] for item in self._heap]
            self._heap.clear()
            self._cv.notify_all()
        for task in tasks:
            task.group.partition_done(
                RuntimeError("scheduler stopped before task ran"))

    @property
    def pending(self) -> int:
        with self._mu:
            return len(self._heap)

    def keys_idle(self, keys) -> bool:
        """True when none of ``keys`` is queued or in flight — the
        quiescence probe the adaptive codec plane uses before
        re-installing a leaf's server-side codec (a COMP_INIT racing an
        in-flight round of the same key would reset the server's round
        state under it)."""
        with self._mu:
            ks = set(keys)
            if ks & self._inflight.keys():
                return False
            return not any(item[1] in ks for item in self._heap)


class PartitionTask:
    """One partition of one push_pull — the reference's TensorTableEntry
    (common.h:221-264) reduced to the DCN stages. ``stack`` (a host codec
    stack, ops/compression/host.py) marks a compressed partition: it then
    flows COMPRESS -> PUSH -> PULL -> DECOMPRESS instead of PUSH -> PULL,
    exactly as the reference splices compression into the scheduled queue
    list (operations.cc:199-204)."""

    __slots__ = ("ctx", "partition", "priority", "version", "in_view",
                 "out_view", "group", "cmd", "stack", "step", "wire",
                 "cmd_pull", "pull_len", "push_len", "lease", "enqueue_t",
                 "admit_t", "round_no", "attempt", "codec")

    def __init__(self, ctx, partition, priority, version, in_view, out_view,
                 group, cmd, stack=None, step=0, wire=None, cmd_pull=None,
                 pull_len=None):
        self.ctx: TensorContext = ctx
        self.partition: Partition = partition
        self.priority = priority
        self.version = version
        self.in_view = in_view     # np.uint8 view of this partition's input
        self.out_view = out_view   # np.uint8 view of the output slot
        self.group: "TaskGroup" = group
        self.cmd = cmd             # PUSH command word
        self.stack = stack         # host codec stack or None (dense)
        self.step = step           # compression round (seeds randomk/dither)
        self.wire = wire           # prebuilt/compressed push payload
        self.cmd_pull = cmd if cmd_pull is None else cmd_pull
        self.pull_len = pull_len   # reply bytes when not dense (telemetry)
        self.push_len = None       # actual pushed bytes (set by _do_push)
        self.lease = None          # arena lease for reply scratch (if any)
        self.enqueue_t = None      # admission-wait clock (metrics)
        self.admit_t = None        # when the queue admitted it
        self.round_no = 0          # per-key submission ordinal (epoch stamp)
        self.attempt = 0           # wire retries of this round so far
        # adaptive-codec wire tag (plan_epoch << 8 | codec_id): the
        # server latches the first fold's tag per round and loudly
        # rejects disagreeing folds. 0 = untagged (static configs).
        self.codec = 0

    @property
    def epoch(self) -> int:
        """Wire replay-dedup stamp: (round << 16) | attempt. The server
        folds each (key, sender, round) at most once, so a retried push
        after a dropped reply never double-counts (native/ps.cc
        IsReplay; docs/fault-tolerance.md). round_no == 0 (direct task
        construction in tests/benches) sends 0 = unstamped."""
        if not self.round_no:
            return 0
        return (self.round_no << 16) | (self.attempt & 0xFFFF)

    @property
    def key(self) -> int:
        return self.partition.key

    @property
    def nbytes(self) -> int:
        return self.partition.length


class TaskGroup:
    """Per-tensor completion tracking: the shared atomic counter + callback
    of the reference's partition fan-out (operations.cc:140-180)."""

    def __init__(self, ctx: TensorContext, total: int,
                 callback: Callable[[Optional[Exception]], None]):
        self.ctx = ctx
        self._remaining = total        # guarded-by: _mu
        self._mu = threading.Lock()
        self._callback = callback
        self._error: Optional[Exception] = None  # guarded-by: _mu

    def partition_done(self, err: Optional[Exception] = None) -> None:
        with self._mu:
            if err is not None and self._error is None:
                self._error = err
            self._remaining -= 1
            fire = self._remaining == 0
            # capture the error inside the lock: the old read of
            # self._error at the callback site below was outside it
            # (benign only because fire implies no more writers —
            # byteps-lint guarded-by made the assumption explicit)
            final_err = self._error
        if fire:
            try:
                self._callback(final_err)
            except Exception:  # noqa: BLE001 - then re-raised
                # a completion-callback bug must be LOUD: swallowed (the
                # stage pools drop future exceptions), it strands the
                # waiter until its timeout with no diagnostic at all —
                # exactly how a 4-line closure bug once became a silent
                # 30s hang
                log.exception(
                    "completion callback for %r raised; the waiting "
                    "handle may never resolve", self.ctx.name)
                raise


class Handle:
    """Async completion handle (HandleManager parity)."""

    def __init__(self, hid: int, name: str):
        self.id = hid
        self.name = name
        self._ev = threading.Event()
        self._err: Optional[Exception] = None
        self.result: Optional[np.ndarray] = None
        self._cb_mu = threading.Lock()
        self._cbs: List[Callable[[], None]] = []  # guarded-by: _cb_mu

    def done(self) -> bool:
        return self._ev.is_set()

    def add_done_callback(self, fn: Callable[[], None]) -> None:
        """Run ``fn()`` when the handle completes (immediately if it
        already has). Powers the completion-ordered IMPORT drain in
        make_ps_train_step: the H2D of tensor k starts the moment its
        pull lands, instead of behind every earlier waiter. Callbacks
        run on the completing scheduler thread — keep them tiny."""
        with self._cb_mu:
            if not self._ev.is_set():
                self._cbs.append(fn)
                return
        fn()

    def wait(self, timeout: Optional[float] = None) -> np.ndarray:
        if not self._ev.wait(timeout):
            raise TimeoutError(f"push_pull {self.name!r} timed out")
        if self._err is not None:
            raise self._err
        return self.result

    def _finish(self, result, err) -> None:
        self.result = result
        self._err = err
        with self._cb_mu:
            self._ev.set()
            cbs, self._cbs = self._cbs, []
        for fn in cbs:
            try:
                fn()
            except Exception:  # noqa: BLE001 - must not poison completion
                log.exception("handle done-callback for %r raised",
                              self.name)


class HandleManager:
    """int handle allocation + poll/wait (torch/handle_manager.cc:22,
    ops.py:48-85)."""

    def __init__(self) -> None:
        self._mu = threading.Lock()
        self._next = 0                           # guarded-by: _mu
        self._handles: Dict[int, Handle] = {}    # guarded-by: _mu

    def allocate(self, name: str) -> Handle:
        with self._mu:
            h = Handle(self._next, name)
            self._handles[h.id] = h
            self._next += 1
            return h

    def get(self, hid: int) -> Handle:
        with self._mu:
            try:
                return self._handles[hid]
            except KeyError:
                raise KeyError(f"unknown or already-synchronized handle "
                               f"{hid}") from None

    def poll(self, hid: int) -> bool:
        # a cleared id reports done (the reference PollHandle contract,
        # torch/handle_manager.cc): poll loops racing a synchronize()
        # elsewhere must terminate, not crash. Ids that were never
        # allocated (>= the high-water mark) are caller bugs, not
        # completions — raising keeps done-when-cleared for real ids only
        with self._mu:
            if hid < 0 or hid >= self._next:
                raise KeyError(f"handle {hid} was never allocated")
            h = self._handles.get(hid)
        return True if h is None else h.done()

    def discard(self, hid: int) -> None:
        """Abandon a handle without retrieving its result — for callers
        that treat a wait timeout as fatal and will never retry. Without
        this the Handle (and its gradient-sized result buffer) stays in
        the table for the life of the process."""
        with self._mu:
            self._handles.pop(hid, None)

    def wait_and_clear(self, hid: int, timeout=None) -> np.ndarray:
        h = self.get(hid)
        try:
            out = h.wait(timeout)
        except Exception as e:
            # drop the handle ONLY when the raised exception is the
            # handle's own stored error: that round is over, and a
            # leaked entry would pin gradient-sized buffers via the
            # error traceback's frames for the life of the process. A
            # wait TimeoutError must keep the handle — the completion
            # may race the deadline (done() flipping true just after
            # wait() returned False), and popping then would silently
            # drop a successful result the caller's retry could fetch.
            if h._err is e:
                with self._mu:
                    self._handles.pop(hid, None)
            raise
        with self._mu:
            self._handles.pop(hid, None)
        return out


class PipelineScheduler:
    """Stage-pipelined push/pull over the PS client.

    The priority queue decides admission order and the credit bounds
    in-flight bytes; once admitted, a partition flows through independent
    per-stage thread pools with continuation passing. Default (fused,
    BYTEPS_FUSED_PUSHPULL):

        [COMPRESS ->] WIRE [-> DECOMPRESS]

    — WIRE submits ONE fused PUSHPULL message and returns its thread to
    the pool; the reply lands via the client's completion reactor, which
    runs DECOMPRESS/finish. No thread parks per in-flight key, so
    concurrent partitions are bounded by scheduling credit, not pool
    size. Two-op fallback (old servers / BYTEPS_FUSED_PUSHPULL=0):

        [COMPRESS ->] PUSH -> PULL [-> DECOMPRESS]

    — the PULL of partition k overlaps the PUSH of partition k+1 (the
    reference runs PUSH and PULL as separate stage loops with callbacks,
    core_loops.cc:538-618). Either way codec work never blocks a network
    thread (COMPRESS/DECOMPRESS spliced into the pipeline as in
    operations.cc:199-204) and credit is held from admission until the
    reply (and DECOMPRESS, if any) completes.
    """

    def __init__(self, client, num_threads: int = 8,
                 credit_bytes: int = 0, telemetry=None,
                 config=None, arena=None, metrics=None, profiler=None,
                 registry=None):
        import concurrent.futures
        import os

        self._client = client
        # tensor registry (core/registry.py) for live key migration on
        # server death; None = no failover (re-routing needs the shared
        # routing table)
        self._registry = registry
        # Fused PUSHPULL (BYTEPS_FUSED_PUSHPULL, default on): PUSH and
        # PULL collapse into ONE non-blocking WIRE stage — submit the
        # fused op, return the thread to the pool, and run the finish
        # (or DECOMPRESS) from the client's completion-reactor callback.
        # In-flight partitions are then bounded by scheduling credit,
        # not by pull-pool thread count. Requires the client to speak
        # the fused op (old servers / fake test clients fall back to
        # the two-op path).
        if config is not None:
            fused_flag = getattr(config, "fused_pushpull", True)
        else:
            fused_flag = os.environ.get(
                "BYTEPS_FUSED_PUSHPULL", "1").lower() not in (
                "0", "false", "off", "no")
        self._fused = bool(fused_flag) and getattr(
            client, "supports_fused", False)
        # Cross-barrier staleness credit (BYTEPS_CROSS_BARRIER /
        # BYTEPS_STALENESS): the carried drain in jax/train.py submits
        # step k+1's push_pull for a leaf whose step-k round may still
        # be in flight, so the queue must admit up to window+1 rounds of
        # one key. Fused-only: on the two-op path a pipelined PULL could
        # read the PREVIOUS round's aggregate (the fused op's reply is
        # round-stamped and parked server-side; a bare PULL is not).
        xb_window = 0
        if (self._fused and config is not None
                and getattr(config, "cross_barrier", False)):
            xb_window = max(0, int(getattr(config, "staleness", 0)))
        self.xb_window = xb_window  # read by the train step's carry gate
        self._queue = ScheduledQueue(credit_bytes, metrics=metrics,
                                     profiler=profiler, window=xb_window)
        self._telemetry = telemetry
        self._config = config
        # measurement plane (core/metrics.py): per-(stage, key-class)
        # latency histograms cached locally so a stage completion is one
        # dict lookup + one histogram record, never the registry lock;
        # compression ratio counters accumulate pre/post wire bytes
        self._metrics = metrics
        self._profiler = profiler
        # REAL violation found at guarded-by introduction: two stage
        # pool threads racing _stage_done's get-then-insert could both
        # miss and both insert (benign on CPython only because the
        # registry hands back the same Histogram for one name). The
        # dedicated lock makes the cache safe by construction; the
        # registry lock stays off this path as before.
        self._stage_mu = threading.Lock()
        self._stage_hists: Dict[tuple, Any] = {}  # guarded-by: _stage_mu
        if metrics is not None:
            self._comp_pre = metrics.counter("compress/bytes_pre")
            self._comp_post = metrics.counter("compress/bytes_post")
            # lossless tier's own byte accounting (codec plane evidence:
            # codec/lossless_ratio = post/pre)
            self._lossless_pre = metrics.counter(
                "codec/lossless_bytes_pre")
            self._lossless_post = metrics.counter(
                "codec/lossless_bytes_post")
        else:
            self._comp_pre = self._comp_post = None
            self._lossless_pre = self._lossless_post = None
        # persistent host staging arena (core/arena.py): reply scratch
        # for compressed pulls checks out of it instead of np.empty per
        # round; None = allocate fresh (the pre-arena behavior)
        self._arena = arena
        n_codec = min(8, max(2, (os.cpu_count() or 4) // 2))
        self._push_pool = concurrent.futures.ThreadPoolExecutor(
            num_threads, thread_name_prefix="bps-push")
        self._pull_pool = concurrent.futures.ThreadPoolExecutor(
            num_threads, thread_name_prefix="bps-pull")
        self._codec_pool = concurrent.futures.ThreadPoolExecutor(
            n_codec, thread_name_prefix="bps-codec")
        self._inflight = 0  # guarded-by: _inflight_mu|_inflight_cv
        self._inflight_mu = threading.Lock()
        self._inflight_cv = threading.Condition(self._inflight_mu)
        # per-key pinned priority (see _pin_priority)
        self._prio_mu = threading.Lock()
        self._key_priority: Dict[int, int] = {}  # guarded-by: _prio_mu
        self._prio_warned: set = set()           # guarded-by: _prio_mu
        # measured production order (see production_priority): the n-th
        # key to first cross the export boundary gets ordinal n
        self._export_ordinal = 0                 # guarded-by: _prio_mu
        self._export_order: Dict[int, int] = {}  # guarded-by: _prio_mu
        # ---- fault tolerance (docs/fault-tolerance.md) ---------------- #
        # bounded wire retry with exponential backoff: a failed wire
        # exchange (fused PUSHPULL or two-op push/pull) is retried up to
        # wire_retry times, its replayed push (round, attempt)-stamped so
        # the server never double-counts; when the native client reports
        # the partition's server dead, the retry first migrates the dead
        # server's keys to survivors (registry.migrate_server) and
        # re-inits them there. wire_retry = 0 restores fail-fast.
        if config is not None:
            self._retry_max = max(0, int(getattr(config, "wire_retry", 2)))
            self._backoff_ms = max(
                1.0, float(getattr(config, "wire_backoff_ms", 50.0)))
        else:
            self._retry_max = max(
                0, int(os.environ.get("BYTEPS_WIRE_RETRY", "2")))
            self._backoff_ms = max(1.0, float(
                os.environ.get("BYTEPS_WIRE_BACKOFF_MS", "50")))
        self._backoff_cap_ms = 2000.0
        self._stopping = False
        # per-declared-key submission ordinal: the ROUND half of the
        # epoch stamp. Scheduler-owned (not the caller's `version`) so
        # dedup never depends on callers passing monotonic versions.
        self._round_seq: Dict[int, int] = {}     # guarded-by: _prio_mu
        # pending backoff timers: task-id -> (timer, task); stop() fails
        # them so no handle waits on a retry that will never fire
        self._retry_mu = threading.Lock()
        self._pending_retries: Dict[int, tuple] = {}  # guarded-by: _retry_mu
        # servers already failed over (migrate once per death); the
        # failover lock is held across a whole migration so concurrent
        # failing partitions only ever see a fully-applied routing table
        self._failover_mu = threading.Lock()
        self._migrated_servers: set = set()  # guarded-by: _failover_mu
        if metrics is not None:
            # created eagerly (not on first event) so the observability
            # schema resolves 0-valued counters on healthy fleets
            self._m_retries = metrics.counter("wire/retries")
            self._m_failovers = metrics.counter("wire/server_failovers")
            self._m_migrations = metrics.counter("registry/migrations")
        else:
            self._m_retries = self._m_failovers = self._m_migrations = None
        # adaptive codec plane (core/codec_plane.py), attached after
        # construction by GlobalState.init when BYTEPS_CODEC_ADAPT is on
        # (the plane needs a scheduler reference for its quiescence
        # probe, so neither can own the other at construction time)
        self._codec_plane = None
        self._dispatcher = threading.Thread(
            target=self._dispatch, name="bps-sched-dispatch", daemon=True)
        self._dispatcher.start()

    def attach_codec_plane(self, plane) -> None:
        self._codec_plane = plane

    def keys_idle(self, keys) -> bool:
        """Quiescence probe for the codec plane: no queued, in-flight,
        or backoff-parked task touches any of ``keys``."""
        with self._retry_mu:
            if any(t.key in set(keys)
                   for _, t in self._pending_retries.values()):
                return False
        return self._queue.keys_idle(keys)

    def _next_round(self, ctx: TensorContext) -> int:
        with self._prio_mu:
            r = self._round_seq.get(ctx.declared_key, 0) + 1
            self._round_seq[ctx.declared_key] = r
            return r

    def production_priority(self, ctx: TensorContext,
                            parent: Optional[TensorContext] = None) -> int:
        """Priority from MEASURED production order: the n-th distinct key
        to first cross the export boundary gets ordinal n and priority
        ``-n``, so the first gradient XLA actually produces is served
        first. The reference ASSUMES "last layer first" via the static
        -declared_key convention (tensorflow/ops.cc:155-158); the PS
        train step's shard submission (jax/train.py submit_shard) calls
        this instead, in the order its claim loop reaches the leaves.
        The assignment pins the key's priority (see _pin_priority) —
        later submissions of the same key reuse it, keeping cross-round
        admission order stable.

        ``parent``: the logical tensor a shard subrange belongs to
        (locality-sharded export). All shard keys of one leaf are ONE
        production event — the leaf's reduce-scatter completes on every
        local device at the same collective — so they share the
        parent's ordinal; the queue's key-ascending tie-break then
        keeps a leaf's shards adjacent in admission order instead of
        interleaving them with another leaf's."""
        with self._prio_mu:
            pr = self._key_priority.get(ctx.declared_key)
            if pr is None:
                anchor = ctx.declared_key if parent is None \
                    else parent.declared_key
                o = self._export_order.get(anchor)
                if o is None:
                    o = self._export_ordinal
                    self._export_ordinal += 1
                    self._export_order[anchor] = o
                if ctx.declared_key != anchor:
                    self._export_order[ctx.declared_key] = o
                    # pin the PARENT too: if its whole-leaf key ever
                    # submits later (shard plan change, broken-tap
                    # fallback), it must ride the measured ordinal, not
                    # the static -declared_key default
                    self._key_priority.setdefault(anchor, -o)
                pr = self._key_priority[ctx.declared_key] = -o
            return pr

    def export_order(self) -> Dict[int, int]:
        """declared_key -> first-export ordinal snapshot (telemetry /
        tests: proves priorities came from production order)."""
        with self._prio_mu:
            return dict(self._export_order)

    def _pin_priority(self, ctx: TensorContext,
                      priority: Optional[int]) -> int:
        """The first submission's priority is PINNED per key. The queue
        pops by (priority desc, submission order), so two queued rounds
        of one tensor carrying different priorities would be admitted in
        priority order, not round order — and the server counts pushes
        positionally per worker per key, so the swap would silently sum
        round N+1's payload into round N across workers. The reference's
        priority is static per key by construction (-declared_key,
        tensorflow/ops.cc:155-158) and a shard key's is
        static by the production_priority pin above; an explicit
        per-call value sticks on first use, and later differing values
        warn ONCE then are silently ignored (same guard
        server/compressed.py applies to compressed rounds).
        ``priority=None`` means "no opinion": it seeds the layer-order
        default -declared_key only when nothing is pinned yet, and
        otherwise follows the pin silently — a fallback-path submission
        of a production-pinned key must not trip the mismatch warning."""
        with self._prio_mu:
            pinned = self._key_priority.get(ctx.declared_key)
            if pinned is None:
                pinned = -ctx.declared_key if priority is None else priority
                self._key_priority[ctx.declared_key] = pinned
                return pinned
            warn = (priority is not None and pinned != priority
                    and ctx.declared_key not in self._prio_warned)
            if warn:
                self._prio_warned.add(ctx.declared_key)
        if warn:
            # once per key — a caller passing per-round priorities would
            # otherwise flood the submit hot path every step
            log.warning(
                "tensor %r: per-round priority %d ignored; %d was pinned "
                "at first submission (cross-round reorder guard; "
                "further mismatches for this tensor are silent)",
                ctx.name, priority, pinned)
        return pinned

    # ---- stage plumbing ------------------------------------------------ #

    def _dispatch(self) -> None:
        """Admission loop: the only consumer of the scheduled queue, so
        credit+priority order is decided in one place; admitted tasks are
        handed to the first stage pool and flow via continuations."""
        while True:
            task = self._queue.get_task()
            if task is None:
                return
            with self._inflight_mu:
                self._inflight += 1
            if task.stack is not None:
                self._submit_stage(self._codec_pool, self._do_compress, task)
            elif self._fused:
                self._submit_stage(self._push_pool, self._do_wire, task)
            else:
                self._submit_stage(self._push_pool, self._do_push, task)

    def _submit_stage(self, pool, fn, task) -> None:
        try:
            fut = pool.submit(fn, task)
        except RuntimeError as e:  # pool shut down mid-flight
            self._finish(task, e)
            return

        def _on_done(f):
            if f.cancelled():
                self._finish(task, RuntimeError("scheduler stopped"))

        fut.add_done_callback(_on_done)

    def _span(self, task, stage):
        return f"{stage}.{task.partition.index}"

    @staticmethod
    def _trace_span(stage: str, task, **args) -> "tracing.span":
        """One scheduler stage of one partition as a program span
        (utils/tracing.py): the stage is the span's fixed name, the
        partition its arguments, the tensor its Chrome-trace row."""
        return tracing.span(stage, tid=task.ctx.name, key=task.key,
                            part=task.partition.index, **args)

    @staticmethod
    def _key_class(task) -> str:
        """Traffic class for per-class stage metrics: "compressed" rides
        the host codec stages, "wire" is a prebuilt payload (device-
        compressed or rowsparse), "dense" everything else."""
        if task.stack is not None:
            return "compressed"
        if task.wire is not None:
            return "wire"
        return "dense"

    def _stage_done(self, task, stage: str, t0: float) -> None:
        """One stage completion's measurement: per-(stage, class) log2
        latency histogram + the active StepReport's stage sample."""
        if self._metrics is None:
            return
        dt = time.perf_counter() - t0
        key = (stage, self._key_class(task))
        with self._stage_mu:
            h = self._stage_hists.get(key)
            if h is None:
                h = self._metrics.histogram(
                    f"scheduler/{stage.lower()}_us/{key[1]}")
                self._stage_hists[key] = h
        h.record_seconds(dt)
        prof = self._profiler.current() if self._profiler else None
        if prof is not None:
            prof.stage_sample(stage, dt)
            if stage == "PULL":
                # the efficiency ledger's overlap timeline: a PULL
                # sample spans submit→completion (wire + aggregation
                # wait on both the fused and two-op paths), so the
                # interval is the step's wire occupancy
                prof.wire_span(t0, t0 + dt)

    # ---- bounded retry + server failover ------------------------------ #

    @staticmethod
    def _retryable(err: Exception) -> bool:
        """Wire-layer failures retry (server error replies, dropped
        replies / ticket timeouts, connection death, send failures);
        programming errors (bad buffers, stale shapes -> ValueError
        etc.) fail the round immediately."""
        return isinstance(err, (RuntimeError, TimeoutError, OSError))

    def _fail_or_retry(self, task: PartitionTask, err: Exception) -> None:
        """A wire stage failed: retry the partition's whole exchange
        with exponential backoff (the replayed push is epoch-stamped, so
        the server folds it at most once), re-routing via the registry
        when the assigned server is dead; after the retry budget, fail
        the round with a clear bounded-time error."""
        from . import flight
        if (self._stopping or task.attempt >= self._retry_max
                or not self._retryable(err)):
            if task.attempt > 0 and self._retryable(err):
                budget_ms = sum(
                    min(self._backoff_ms * (2 ** a), self._backoff_cap_ms)
                    for a in range(task.attempt))
                err = self._fatal_wire_error(task, RuntimeError(
                    f"push_pull {task.ctx.name!r} key={task.key} failed "
                    f"after {task.attempt + 1} attempts over "
                    f"~{budget_ms:.0f}ms of backoff "
                    f"(BYTEPS_WIRE_RETRY={self._retry_max}, "
                    f"BYTEPS_WIRE_BACKOFF_MS={self._backoff_ms:g}): "
                    f"{err}"))
            self._finish(task, err)
            return
        task.attempt += 1
        flight.record("wire_retry", key=task.key,
                      detail=f"{task.ctx.name} attempt={task.attempt} "
                             f"server={task.partition.server} err={err}")
        if self._m_retries is not None:
            self._m_retries.inc()
        # the reply scratch may be half-written garbage: abandon it so
        # the retry checks out a fresh buffer (never recycle a slot a
        # late writer could still touch)
        if task.lease is not None:
            task.lease.abandon()
            task.lease = None
        delay = min(self._backoff_ms * (2 ** (task.attempt - 1)),
                    self._backoff_cap_ms) / 1000.0
        log.warning(
            "push_pull %r key=%d: wire attempt %d failed (%s); retrying "
            "in %.0fms (%d/%d)", task.ctx.name, task.key, task.attempt,
            err, delay * 1e3, task.attempt, self._retry_max)

        def _fire():
            with self._retry_mu:
                if self._pending_retries.pop(id(task), None) is None:
                    return  # stop() claimed it and failed the task
            try:
                self._prepare_retry(task)
            except Exception as e:  # noqa: BLE001 - forwarded to waiter
                # the dead-fleet fail-fast lands HERE (migrate_server
                # raising "fleet is gone"): it must carry the flight-
                # dump pointer like the retry-budget exhaustion does
                self._finish(task, self._fatal_wire_error(task, e))
                return
            entry = self._do_wire if self._fused else self._do_push
            self._submit_stage(self._push_pool, entry, task)

        timer = threading.Timer(delay, _fire)
        timer.daemon = True
        with self._retry_mu:
            if self._stopping:
                self._finish(task, RuntimeError(
                    "scheduler stopped with the retry pending"))
                return
            self._pending_retries[id(task)] = (timer, task)
        timer.start()

    def _fatal_wire_error(self, task: PartitionTask,
                          err: Exception) -> Exception:
        """A round is about to fail for good (retry budget exhausted,
        or the whole fleet is gone): record it, dump the flight record
        (best-effort — a dead fleet still dumps the worker's half of
        the causal timeline), and return the error with the dump path
        appended so the operator starts from the timeline instead of
        log archaeology (docs/fault-tolerance.md)."""
        from . import flight
        flight.record("round_failed", key=task.key,
                      detail=f"{task.ctx.name} "
                             f"attempts={task.attempt + 1} err={err}")
        try:
            dump_path = flight.dump(reason="wire-fail-fast")
        except Exception:  # noqa: BLE001 - never mask the real error
            dump_path = None
        if not dump_path:
            return err
        return RuntimeError(
            f"{err} — flight record dumped to {dump_path}")

    def _prepare_retry(self, task: PartitionTask) -> None:
        """Pre-flight for a retry: when the native client reports the
        partition's assigned server dead, migrate the dead server's keys
        to survivors (once per death, shared routing table) and re-init
        the re-homed keys there; the retried send then targets the
        mutated Partition.server. Raises when no survivor exists — the
        permanently-dead-fleet fail-fast."""
        srv = task.partition.server
        probe = getattr(self._client, "server_dead", None)
        if probe is not None and probe(srv):
            self._failover_server(srv)
            if task.partition.server == srv:
                # migrate_server raises when the whole fleet is dead;
                # equal server here means migration was unavailable
                raise RuntimeError(
                    f"server {srv} is dead and key migration is "
                    f"unavailable (no registry attached) — cannot "
                    f"re-route key {task.key}")
        # Seed any not-yet-initialized store on the (possibly re-homed)
        # server before re-sending: INIT_PUSH doubles as the state sync
        # (allocation + init barrier across workers; converges because
        # every worker observes the same death on its own retry path).
        # Unconditional — a SIBLING task's failover may have migrated
        # this tensor's keys and invalidated their init cache while this
        # task was backing off, in which case its partition already
        # points at a survivor whose store doesn't exist yet (the probe
        # above then reads "alive" and the dead-server branch never
        # runs). A fully-cached tensor makes this a dict lookup.
        ensure = getattr(self._client, "ensure_init", None)
        if (ensure is not None
                and getattr(task.ctx, "nbytes", 0)
                and task.ctx.nbytes == sum(p.length
                                           for p in task.ctx.partitions)):
            ensure(task.ctx, task.ctx.nbytes)
        if task.stack is not None:
            # host-compressed key: the server-side codec (COMP_INIT
            # state) died with the server — re-install it on the
            # (possibly re-homed) store before replaying the wire, so
            # compressed keys survive a server death exactly like dense
            # keys (this used to be a hard "not supported" error).
            # Idempotent when the store already has the same cfg (the
            # server applies a matching COMP_INIT as a no-op), so the
            # non-migrated retry paths pay one small RPC, not a reset.
            comp_init = getattr(self._client, "comp_init", None)
            if comp_init is not None:
                comp_init(task.partition.server, task.key,
                          task.stack.kwargs_wire())

    def _failover_server(self, srv: int) -> None:
        # the lock is held across the WHOLE migration: a second failing
        # partition of the same dead server blocks here until the
        # routing table is fully re-targeted, so its post-call
        # partition.server read never observes a half-applied migration
        from . import flight
        with self._failover_mu:
            if srv in self._migrated_servers or self._registry is None:
                return
            migrated = self._registry.migrate_server(srv)
            self._migrated_servers.add(srv)
            if not migrated:
                return
            invalidate = getattr(self._client, "invalidate_init", None)
            if invalidate is not None:
                # the adoptive servers have no stores for the migrated
                # keys: the next ensure_init must re-init-push them there
                invalidate(migrated)
            flight.record("server_failover", key=srv,
                          detail=f"server={srv} migrated_keys="
                                 f"{len(migrated)}")
            for k in migrated:
                flight.record("key_migration", key=k,
                              detail=f"from_server={srv}")
            if self._m_failovers is not None:
                self._m_failovers.inc()
                self._m_migrations.inc(len(migrated))
        log.warning(
            "scheduler: server %d declared dead; %d key(s) migrated to "
            "survivors, re-routing in-flight retries", srv, len(migrated))

    def _do_compress(self, task: PartitionTask) -> None:
        sp = self._trace_span(tracing.CODEC_COMPRESS, task).start()
        t0 = time.perf_counter()
        try:
            from ..server.compressed import compress_partition
            task.wire = compress_partition(task.stack, task.in_view,
                                           task.step)
        except Exception as e:  # noqa: BLE001 - forwarded to waiter
            self._finish(task, e)
            return
        finally:
            sp.stop()  # in finally: no dangling span on error
            self._stage_done(task, "COMPRESS", t0)
        if self._fused:
            self._submit_stage(self._push_pool, self._do_wire, task)
        else:
            self._submit_stage(self._push_pool, self._do_push, task)

    def _do_wire(self, task: PartitionTask) -> None:
        """The fused WIRE stage (BYTEPS_FUSED_PUSHPULL): one PUSHPULL
        message replaces the PUSH send + blocking PULL pair. The stage
        thread only BUILDS the request and hands it to the wire — the
        reply lands in the (arena-leased) buffer from the client's
        native recv loop, and the completion reactor runs the
        continuation (DECOMPRESS/finish). Stage accounting moves onto
        completion timestamps: the PUSH sample is the send wall, the
        PULL sample is submit→completion (exactly what the blocking
        pull used to measure: wire + server aggregation wait).

        On the trace the exchange is TWO same-thread spans that share
        the request's wire ``rid``: ``bps.wire.send`` here (up to the
        native send returning) and ``bps.wire.done`` around the
        completion on the reactor; a reader pairs them, and what lies
        between is the request's time in flight."""
        wait = (task.admit_t - task.enqueue_t) * 1e6 \
            if task.admit_t is not None and task.enqueue_t is not None \
            else 0.0
        with self._trace_span(
                tracing.WIRE_SEND, task, admit_wait_us=round(wait, 1),
                cause=f"submit:{task.ctx.declared_key}") as sp:
            self._wire_send(task, sp)

    def _wire_send(self, task: PartitionTask, sp: "tracing.span") -> None:
        name = task.ctx.name
        span = self._span(task, "PUSHPULL")
        try:
            buf = task.wire if task.wire is not None else task.in_view
            task.push_len = len(buf)  # actual bytes (varint wires vary)
            sp.set(bytes=task.push_len)
            if (self._config is not None and task.stack is None
                    and task.in_view is not None):
                from ..utils.logging import debug_sample
                debug_sample(self._config, name, span,
                             task.in_view, task.ctx.dtype.np_dtype)
            # reply staging (the old _do_pull's buffer selection):
            # compressed tasks land the wire reply in arena scratch,
            # everything else straight into the caller's output view
            if task.stack is not None:
                wb = task.stack.wire_bytes()
                if self._arena is not None:
                    task.lease = self._arena.checkout(
                        f"pull:{task.key}", wb)
                    reply = task.lease.buf
                else:
                    reply = np.empty(wb, np.uint8)
            else:
                reply = task.out_view
        except Exception as e:  # noqa: BLE001 - forwarded to waiter
            self._finish(task, e)
            return
        # dense/rowsparse replies are the whole partition — a short
        # reply must fail, not leave the output tail unwritten; wire
        # (device-compressed) and codec replies are variable-length
        exact = task.stack is None and task.pull_len is None
        # the request's wire rid, written by the native send BEFORE the
        # request is on the wire: on a loopback fleet the reply can
        # complete (and the reactor run on_done) before the send has
        # even returned here, and the completion's span still reads it
        rid_cell = ctypes.c_uint32(0)
        t0 = time.perf_counter()

        def _complete_dense(t: PartitionTask) -> None:
            # runs on a pull-pool thread (idle in fused mode): the
            # per-tensor finish work — debug sampling and, on the last
            # partition, the averaging divide + handle done-callbacks —
            # must not serialize on the single reactor thread
            if (t.pull_len is None and self._config is not None):
                try:
                    from ..utils.logging import debug_sample
                    debug_sample(self._config, name, span,
                                 t.out_view, t.ctx.dtype.np_dtype)
                except Exception as e:  # noqa: BLE001
                    self._finish(t, e)
                    return
            self._finish(t, None)

        def on_done(got: int, err) -> None:
            with self._trace_span(tracing.WIRE_DONE, task,
                                  rid=rid_cell.value):
                _complete(got, err)

        def _complete(got: int, err) -> None:
            self._stage_done(task, "PULL", t0)
            if err is None and exact and got != len(reply):
                err = RuntimeError(
                    f"fused pushpull reply for {name!r} key={task.key} is "
                    f"{got} bytes, expected {len(reply)}")
            if err is not None:
                # a failed ticket no longer hard-fails the round: retry
                # with backoff (epoch-stamped replay, so the server never
                # double-counts), failing over to a surviving server when
                # this one is dead
                self._fail_or_retry(task, err)
                return
            if task.stack is not None:
                task.wire = reply[:got]  # variable-length wires (varint)
                self._submit_stage(self._codec_pool, self._do_decompress,
                                   task)
                return
            self._submit_stage(self._pull_pool, _complete_dense, task)

        # a client without the rid_out, codec and/or epoch kwargs (fake
        # test clients, stale builds) degrades one kwarg at a time: no
        # cell means the completion's span reads rid 0, an untagged
        # push just skips server validation, an unstamped one falls
        # back to positional counting
        kwargs = {"epoch": task.epoch, "codec": task.codec,
                  "rid_out": rid_cell}
        try:
            for without in (None, "rid_out", "codec", "epoch"):
                kwargs.pop(without, None)
                try:
                    rid = self._client.zpushpull_async(
                        task.partition.server, task.key, buf, reply,
                        task.cmd, on_done, **kwargs)
                    break
                except TypeError:
                    if not kwargs:
                        raise
        except Exception as e:  # noqa: BLE001
            self._fail_or_retry(task, e)
            return
        # the id server-side trace spans carry, which the fused
        # timeline flow-links on (docs/timeline.md). Fake/stale clients
        # report none.
        sp.set(rid=rid if isinstance(rid, int) else 0,
               server=task.partition.server)
        # send wall only — the request is on the wire and this thread is
        # free; the aggregation wait shows up in the PULL sample above
        self._stage_done(task, "PUSH", t0)

    def _do_push(self, task: PartitionTask) -> None:
        name = task.ctx.name
        span = self._span(task, "PUSH")
        try:
            buf = task.wire if task.wire is not None else task.in_view
            task.push_len = len(buf)  # actual bytes (varint wires vary)
            if (self._config is not None and task.stack is None
                    and task.in_view is not None):
                from ..utils.logging import debug_sample
                debug_sample(self._config, name, span,
                             task.in_view, task.ctx.dtype.np_dtype)
        except Exception as e:  # noqa: BLE001
            self._finish(task, e)
            return
        sp = self._trace_span(tracing.WIRE_PUSH, task,
                              bytes=task.push_len).start()
        t0 = time.perf_counter()
        try:
            # async push: the payload hits the wire and the stage ends —
            # no ACK round-trip on the critical path (the pull is the
            # synchronization; per-key FIFO via the client's key-affine
            # conns). A server reject poisons the conn and surfaces as
            # the pull's error. The PUSH span therefore measures send
            # time only; aggregation wait shows up in PULL. The epoch
            # stamp makes a retried push idempotent server-side.
            try:
                self._client.zpush_async(task.partition.server, task.key,
                                         buf, task.cmd, epoch=task.epoch,
                                         codec=task.codec)
            except TypeError:  # codec/epoch-less client (fakes, stale
                try:           # builds): degrade one kwarg at a time
                    self._client.zpush_async(
                        task.partition.server, task.key, buf, task.cmd,
                        epoch=task.epoch)
                except TypeError:
                    self._client.zpush_async(task.partition.server,
                                             task.key, buf, task.cmd)
        except Exception as e:  # noqa: BLE001
            self._fail_or_retry(task, e)
            return
        finally:
            sp.stop()
            self._stage_done(task, "PUSH", t0)
        self._submit_stage(self._pull_pool, self._do_pull, task)

    def _do_pull(self, task: PartitionTask) -> None:
        name = task.ctx.name
        span = self._span(task, "PULL")
        sp = self._trace_span(tracing.WIRE_PULL, task).start()
        t0 = time.perf_counter()
        try:
            if task.stack is not None:
                wb = task.stack.wire_bytes()
                if self._arena is not None:
                    # per-key persistent reply scratch: same-key
                    # serialization means the previous round's lease is
                    # back by the time this one pulls (a conflict falls
                    # back to a fresh buffer inside the arena)
                    task.lease = self._arena.checkout(
                        f"pull:{task.key}", wb)
                    reply = task.lease.buf
                else:
                    reply = np.empty(wb, np.uint8)
                got = self._client.zpull(task.partition.server, task.key,
                                         reply, task.cmd_pull)
                task.wire = reply[:got]  # variable-length wires (varint)
            else:
                # dense/rowsparse replies must fill the whole view; wire
                # (device-compressed) replies are pull_len-sized
                self._client.zpull(task.partition.server, task.key,
                                   task.out_view, task.cmd_pull,
                                   exact=task.pull_len is None)
        except Exception as e:  # noqa: BLE001
            # retry replays the WHOLE exchange from the push stage: the
            # epoch stamp dedups the replayed push, and a pull that
            # failed because a peer departure aborted the round
            # (pull_abort error-ACK) needs the re-push anyway
            self._fail_or_retry(task, e)
            return
        finally:
            sp.stop()
            self._stage_done(task, "PULL", t0)
        if (task.stack is None and task.pull_len is None
                and self._config is not None):
            # pull_len set = device-compressed wire reply: NOT dense
            # dtype data, sampling it would misparse (or raise on
            # non-4-byte-aligned dithering replies and fail the round)
            try:
                from ..utils.logging import debug_sample
                debug_sample(self._config, name, span,
                             task.out_view, task.ctx.dtype.np_dtype)
            except Exception as e:  # noqa: BLE001
                self._finish(task, e)
                return
        if task.stack is not None:
            self._submit_stage(self._codec_pool, self._do_decompress, task)
        else:
            self._finish(task, None)

    def _do_decompress(self, task: PartitionTask) -> None:
        sp = self._trace_span(tracing.CODEC_DECOMPRESS, task).start()
        t0 = time.perf_counter()
        try:
            from ..server.compressed import decompress_partition
            decompress_partition(task.stack, task.wire, task.out_view)
        except Exception as e:  # noqa: BLE001
            self._finish(task, e)
            return
        finally:
            sp.stop()
            self._stage_done(task, "DECOMPRESS", t0)
        self._finish(task, None)

    def _finish(self, task: PartitionTask, err: Optional[Exception]) -> None:
        if task.lease is not None:
            # reply scratch is fully consumed by now (DECOMPRESS wrote
            # the result into out_view; telemetry below reads only
            # lengths). Release BEFORE report_finish: the moment the
            # key leaves the in-flight set, the next same-key task can
            # be admitted and reach its own checkout — a still-held
            # lease there would conflict into a fresh allocation. On
            # error the wire may be half-written garbage — abandon so
            # the slot is never recycled under a late writer.
            if err is None:
                task.lease.release()
            else:
                task.lease.abandon()
            task.lease = None
        self._queue.report_finish(task)
        if self._telemetry:
            if task.stack is not None:
                # ACTUAL lengths, not wire_bytes() (only an upper bound
                # for variable-length varint wires): push_len captured at
                # send; the reply overwrote task.wire, sliced to length
                sent = task.push_len if task.push_len is not None \
                    else task.stack.wire_bytes()
                recvd = len(task.wire) if task.wire is not None \
                    else task.stack.wire_bytes()
                self._telemetry.record(sent + recvd)
                if self._comp_pre is not None:
                    # dense-equivalent bytes vs actual wire bytes, both
                    # directions: post/pre is the achieved wire ratio
                    self._comp_pre.inc(task.nbytes * 2)
                    self._comp_post.inc(sent + recvd)
                    if getattr(task.stack, "lossless", False):
                        self._lossless_pre.inc(task.nbytes * 2)
                        self._lossless_post.inc(sent + recvd)
            elif task.wire is not None:
                # prebuilt payload up; reply is dense unless pull_len says
                # otherwise (device-compressed pulls are wire-sized)
                down = task.pull_len if task.pull_len is not None \
                    else task.nbytes
                self._telemetry.record(len(task.wire) + down)
            else:
                self._telemetry.record_round_trip(task.nbytes)
        with self._inflight_mu:
            self._inflight -= 1
            if self._inflight == 0:
                self._inflight_cv.notify_all()
        task.group.partition_done(err)

    # ---- submission ---------------------------------------------------- #

    def submit(self, ctx: TensorContext, flat_in: np.ndarray,
               handle: Handle, average: bool, num_workers: int,
               version: int = 0, priority: Optional[int] = None,
               comp=None, out: Optional[np.ndarray] = None) -> None:
        """Enqueue all partitions of one tensor; fills ``handle`` when the
        last partition completes. ``priority=None`` uses the layer-order
        default -declared_key (tensorflow/ops.cc:155-158); an explicit
        value overrides it (higher = sooner).

        ``comp``: a server.compressed.CompressedTensor — its partitions
        then carry per-partition codec stacks through the COMPRESS/
        DECOMPRESS stages (sub-min-compress-bytes partitions stay dense),
        and the compression round counter seeds the stateful codecs.

        ``out``: preallocated flat result buffer (host staging arena
        integration, core/arena.py) — the pull lands in it and the
        handle resolves to it; the caller must not recycle it until the
        handle resolves AND it is done reading the result. A mismatched
        buffer is ignored (correctness never depends on staging).
        """
        from .types import DataType, RequestType, get_command_type

        # adaptive codec plane: when the caller expressed no codec
        # opinion and a plane is attached, the wire codec is resolved
        # HERE — per round, at wire-stage entry — from the leaf's live
        # plan (core/codec_plane.py). The returned tags ride the wire
        # header so the server can reject cross-worker plan skew loudly.
        tag_comp = tag_dense = 0
        if comp is None and self._codec_plane is not None:
            comp, tag_comp, tag_dense = self._codec_plane.resolve(
                ctx, flat_in)
        if comp is not None:
            step = comp.begin_round()  # installs codecs on first call
            flat_in = np.ascontiguousarray(flat_in, np.float32)
        else:
            step = 0
            self._client.ensure_init(ctx, flat_in.nbytes)
        cmd = get_command_type(RequestType.DEFAULT_PUSH_PULL,
                               DataType.from_np(flat_in.dtype))
        cmd_comp = get_command_type(
            RequestType.COMPRESSED_PUSH_PULL,
            DataType.from_np(flat_in.dtype)) if comp is not None else cmd
        from .arena import usable_staging
        if not usable_staging(out, flat_in.dtype, flat_in.nbytes):
            out = np.empty_like(flat_in)
        in_view = flat_in.view(np.uint8)
        out_view = out.view(np.uint8)

        def on_complete(err: Optional[Exception]) -> None:
            if err is None and average and num_workers > 1:
                if np.issubdtype(out.dtype, np.integer):
                    # truncation toward zero (reference div_(size));
                    # in-place so ``out`` is never rebound — an
                    # assignment here would make it a LOCAL of this
                    # closure and break the _finish line below
                    trunc_divide_inplace(out, num_workers)
                else:
                    np.divide(out, num_workers, out=out)
            handle._finish(out if err is None else None, err)

        group = TaskGroup(ctx, len(ctx.partitions), on_complete)
        priority = self._pin_priority(ctx, priority)
        round_no = self._next_round(ctx)
        for i, p in enumerate(ctx.partitions):
            stack = comp.stacks[i] if comp is not None else None
            task = PartitionTask(
                ctx, p, priority, version,
                in_view[p.offset:p.offset + p.length],
                out_view[p.offset:p.offset + p.length],
                group, cmd_comp if stack is not None else cmd,
                stack=stack, step=step)
            task.round_no = round_no
            # plane-governed rounds tag every partition (sub-floor
            # partitions of a compressed leaf stay dense and say so)
            task.codec = tag_dense if stack is None else tag_comp
            try:
                self._queue.add_task(task)
            except RuntimeError as e:
                # scheduler stopped mid-submit: fail this partition so the
                # handle resolves with an error instead of hanging
                group.partition_done(e)

    def submit_wire(self, ctx: TensorContext, wires: List[np.ndarray],
                    reply_lens: List[int], cmds: List[int], handle: Handle,
                    version: int = 0, priority: Optional[int] = None,
                    reply_bufs: Optional[List[np.ndarray]] = None) -> None:
        """Prebuilt-wire push_pull for device-compressed tensors
        (jax/device_compression.py): partition i pushes ``wires[i]`` with
        ``cmds[i]`` and pulls ``reply_lens[i]`` raw bytes; the handle
        resolves to the list of reply buffers. No host codec stages —
        compress and decompress run inside the worker's XLA programs, so
        the pipeline here is pure PUSH -> PULL with the usual priority,
        credit and same-key serialization semantics.

        ``reply_bufs``: caller-owned (arena-staged) per-partition reply
        buffers, reused round over round instead of fresh np.empty; a
        mismatched list is ignored."""
        from .arena import usable_staging
        if (reply_bufs is not None and len(reply_bufs) == len(reply_lens)
                and all(usable_staging(b, np.dtype(np.uint8), rl)
                        for b, rl in zip(reply_bufs, reply_lens))):
            replies = list(reply_bufs)
        else:
            replies = [np.empty(rl, np.uint8) for rl in reply_lens]

        def on_complete(err: Optional[Exception]) -> None:
            handle._finish(replies if err is None else None, err)

        group = TaskGroup(ctx, len(ctx.partitions), on_complete)
        priority = self._pin_priority(ctx, priority)
        round_no = self._next_round(ctx)
        for i, p in enumerate(ctx.partitions):
            task = PartitionTask(
                ctx, p, priority, version, None, replies[i], group,
                cmds[i], wire=wires[i], cmd_pull=cmds[i],
                pull_len=reply_lens[i])
            task.round_no = round_no
            try:
                self._queue.add_task(task)
            except RuntimeError as e:
                group.partition_done(e)

    def submit_rowsparse(self, ctx: TensorContext, host2d: np.ndarray,
                         handle: Handle, average: bool, num_workers: int,
                         version: int = 0, priority: Optional[int] = None,
                         out: Optional[np.ndarray] = None) -> None:
        """Row-sparse push_pull through the priority pipeline: per
        row-aligned partition, the nonzero rows become a prebuilt sparse
        push payload ([nrows][width][ids][rows]) and the pull is dense —
        same credit/priority semantics as dense and compressed traffic.
        ``out``: optional arena-staged flat f32 result buffer (see
        ``submit``)."""
        from ..server.client import build_rowsparse_payload
        from .types import DataType, RequestType, get_command_type

        host2d = np.ascontiguousarray(host2d, np.float32)
        rows, width = host2d.shape
        self._client.ensure_init(ctx, host2d.nbytes)
        cmd_sparse = get_command_type(RequestType.ROW_SPARSE_PUSH_PULL,
                                      DataType.FLOAT32)
        cmd_dense = get_command_type(RequestType.DEFAULT_PUSH_PULL,
                                     DataType.FLOAT32)
        nz = np.flatnonzero(np.any(host2d != 0, axis=1)).astype(np.int32)
        from .arena import usable_staging
        if not usable_staging(out, np.dtype(np.float32), rows * width * 4):
            out = np.empty(rows * width, np.float32)
        out_view = out.view(np.uint8)

        def on_complete(err: Optional[Exception]) -> None:
            if err is None and average and num_workers > 1:
                np.divide(out, num_workers, out=out)
            handle._finish(out.reshape(rows, width) if err is None else None,
                           err)

        group = TaskGroup(ctx, len(ctx.partitions), on_complete)
        priority = self._pin_priority(ctx, priority)
        round_no = self._next_round(ctx)
        for p in ctx.partitions:
            try:
                wire = build_rowsparse_payload(p, nz, host2d)
            except ValueError as e:
                group.partition_done(e)
                continue
            task = PartitionTask(
                ctx, p, priority, version, None,
                out_view[p.offset:p.offset + p.length],
                group, cmd_sparse, wire=wire, cmd_pull=cmd_dense)
            task.round_no = round_no
            try:
                self._queue.add_task(task)
            except RuntimeError as e:
                group.partition_done(e)

    def stop(self) -> None:
        # stop() atomically flips the flag and fails queued-but-unstarted
        # tasks, so outstanding synchronize() callers get an error instead
        # of waiting forever; then cancel not-yet-running stage work (the
        # done-callback fails their tasks) and give in-flight network calls
        # a bounded grace to drain before the caller frees the client.
        self._stopping = True
        # fail tasks parked in backoff timers: exactly one of {this pop,
        # the timer's fire} claims each entry, so a racing fire either
        # already removed it (and proceeds) or finds it gone (and exits)
        with self._retry_mu:
            pending = list(self._pending_retries.values())
            self._pending_retries.clear()
        for timer, task in pending:
            timer.cancel()
            self._finish(task, RuntimeError(
                "scheduler stopped with the wire retry still pending"))
        self._queue.stop()
        self._dispatcher.join(timeout=5)
        for pool in (self._codec_pool, self._push_pool, self._pull_pool):
            pool.shutdown(wait=False, cancel_futures=True)
        with self._inflight_cv:
            self._inflight_cv.wait_for(lambda: self._inflight == 0,
                                       timeout=5)
