"""Persistent host staging arena for the DCN PS path.

The reference allocates its host-side staging buffers ONCE at InitTensor
(``cpubuff``, byteps/common/operations.cc:283-414) and reuses them
zero-copy for the life of the process; our PS tier used to re-allocate
gradient-sized host memory every step (``np.empty_like`` per tensor in
``PipelineScheduler.submit``, ``np.concatenate`` per fused bucket, fresh
reply buffers in ``submit_wire``). This module is the cpubuff analogue:
per staging key, an aligned slot allocated at first checkout and reused
every round.

Correctness NEVER depends on the arena. Every checkout is versioned: a
slot can only be handed out while it is free; if round N's pull is still
writing into it when round N+1 checks out (``checkout_conflicts``), or
the arena is disabled (``BYTEPS_STAGING_ARENA=0``), the caller gets a
fresh untracked allocation with identical semantics. A caller that hits
an error mid-round ``abandon()``s its leases — the slot is dropped from
the table (an in-flight pull keeps the buffer alive through its own
references) and the next checkout allocates a new one.

Telemetry (``StagingArena.stats()``, surfaced via
``state.telemetry.arena_stats()``): slots live, bytes pinned,
allocations avoided, checkout conflicts, fresh fallbacks — the counters
the zero-steady-state-allocation test asserts on.
"""

from __future__ import annotations

import threading
from typing import Dict, Optional

import numpy as np

# 64-byte slot alignment: cache-line aligned for the memcpy-heavy
# fill/drain paths and DMA-friendly on PCIe-attached hosts.
SLOT_ALIGN = 64


def usable_staging(out: Optional[np.ndarray], dtype, nbytes: int) -> bool:
    """THE acceptance rule for a caller-provided staging buffer: exact
    dtype and byte length, C-contiguous — anything else and the callee
    falls back to a fresh ``np.empty`` (correctness never depends on
    staging). One definition shared by the dense, rowsparse, wire and
    blocking-client paths so the fallback rule can never diverge."""
    return (out is not None and out.dtype == dtype
            and out.nbytes == nbytes and out.flags["C_CONTIGUOUS"])


def _aligned_empty(nbytes: int, align: int = SLOT_ALIGN) -> np.ndarray:
    """Uninitialized uint8 buffer whose data pointer is align-rounded
    (np.empty gives 16-byte alignment at best). The slice keeps the raw
    allocation alive via .base."""
    raw = np.empty(nbytes + align, np.uint8)
    off = (-raw.ctypes.data) % align
    return raw[off:off + nbytes]


class _Slot:
    __slots__ = ("buf", "busy", "version")

    def __init__(self, buf: np.ndarray):
        self.buf = buf
        self.busy = False
        self.version = 0


class ArenaLease:
    """One checkout of one staging buffer. ``buf`` is a C-contiguous
    uint8 array of exactly the requested size; ``array(dtype)`` is the
    typed flat view most callers want. ``fresh`` marks an untracked
    fallback allocation (disabled arena or checkout conflict) — its
    release is a no-op."""

    __slots__ = ("_arena", "key", "buf", "fresh", "_version", "_open")

    def __init__(self, arena: Optional["StagingArena"], key: str,
                 buf: np.ndarray, fresh: bool, version: int = 0):
        self._arena = arena
        self.key = key
        self.buf = buf
        self.fresh = fresh
        self._version = version
        self._open = True

    def array(self, dtype) -> np.ndarray:
        """Flat typed view of the whole slot (slot sizes are always a
        multiple of the staged dtype's itemsize by construction)."""
        return self.buf.view(dtype)

    def release(self) -> None:
        """Return the slot for reuse. Only call when nothing can still
        read or write the buffer (pull drained AND the H2D import of its
        contents completed)."""
        if not self._open:
            return
        self._open = False
        if not self.fresh and self._arena is not None:
            self._arena._release(self.key, self._version)

    def abandon(self) -> None:
        """Error-path release: drop the slot from the table instead of
        recycling it — an in-flight writer may still own the buffer, so
        it must never be handed out again. The memory is freed when the
        last reference (this lease / the in-flight task) dies."""
        if not self._open:
            return
        self._open = False
        if not self.fresh and self._arena is not None:
            self._arena._abandon(self.key, self._version)


class StagingArena:
    """Thread-safe key -> persistent staging slot table."""

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self._mu = threading.Lock()
        self._slots: Dict[str, _Slot] = {}  # guarded-by: _mu
        # counters (see module docstring); all guarded by _mu:
        # slot_allocs = tracked slots created (incl. resizes),
        # allocs_avoided = checkouts served from an existing slot,
        # conflicts = slot busy -> fresh fallback, fresh = untracked
        # allocations handed out, resizes = slot dropped for a size change
        self._slot_allocs = 0       # guarded-by: _mu
        self._allocs_avoided = 0    # guarded-by: _mu
        self._conflicts = 0         # guarded-by: _mu
        self._fresh = 0             # guarded-by: _mu
        self._resizes = 0           # guarded-by: _mu
        # per-stage checkout counters (tag="shard": the per-shard
        # result-slot leases, jax/train.py) — proves which pipeline
        # stage the staged bytes serve
        self._tag_checkouts: Dict[str, int] = {}  # guarded-by: _mu

    # ------------------------------------------------------------------ #

    def checkout(self, key: str, nbytes: int,
                 tag: Optional[str] = None) -> ArenaLease:
        """Lease the persistent slot for ``key`` (allocating it on first
        use), or a fresh untracked buffer when the arena is disabled or
        the slot is still leased (conflict). ``tag`` attributes the
        checkout to a pipeline stage in ``stats()`` (e.g. "shard" for
        the per-shard result slots)."""
        nbytes = int(nbytes)
        if not self.enabled:
            with self._mu:
                self._fresh += 1
                if tag is not None:
                    self._tag_checkouts[tag] = \
                        self._tag_checkouts.get(tag, 0) + 1
            return ArenaLease(self, key, _aligned_empty(nbytes), fresh=True)
        with self._mu:
            if tag is not None:
                self._tag_checkouts[tag] = \
                    self._tag_checkouts.get(tag, 0) + 1
            slot = self._slots.get(key)
            if slot is not None and slot.busy:
                self._conflicts += 1
                self._fresh += 1
                return ArenaLease(self, key, _aligned_empty(nbytes),
                                  fresh=True)
            if slot is not None and slot.buf.nbytes != nbytes:
                self._resizes += 1
                slot = None
            if slot is None:
                slot = _Slot(_aligned_empty(nbytes))
                self._slots[key] = slot
                self._slot_allocs += 1
            else:
                self._allocs_avoided += 1
            slot.busy = True
            slot.version += 1
            return ArenaLease(self, key, slot.buf, fresh=False,
                              version=slot.version)

    def _release(self, key: str, version: int) -> None:
        with self._mu:
            slot = self._slots.get(key)
            # version guard: ignore a stale release after the slot was
            # resized/invalidated and re-leased under the same key
            if slot is not None and slot.version == version:
                slot.busy = False

    def _abandon(self, key: str, version: int) -> None:
        with self._mu:
            slot = self._slots.get(key)
            if slot is not None and slot.version == version:
                del self._slots[key]

    def invalidate_prefix(self, prefix: str) -> None:
        """Drop every FREE slot whose key starts with ``prefix`` (a
        tensor was re-partitioned/resized, so its staged sizes are
        stale). Busy slots are left for their lease to resolve; the size
        check at their next checkout retires them."""
        with self._mu:
            for k in [k for k, s in self._slots.items()
                      if k.startswith(prefix) and not s.busy]:
                del self._slots[k]

    def reset(self) -> None:
        """Drop every slot (shutdown path — frees the pinned bytes)."""
        with self._mu:
            self._slots.clear()

    # ------------------------------------------------------------------ #

    def slot_keys(self) -> list:
        with self._mu:
            return sorted(self._slots)

    def stats(self) -> dict:
        with self._mu:
            return {
                "enabled": self.enabled,
                "slots_live": len(self._slots),
                "bytes_pinned": sum(s.buf.nbytes
                                    for s in self._slots.values()),
                "slot_allocs": self._slot_allocs,
                "allocs_avoided": self._allocs_avoided,
                "checkout_conflicts": self._conflicts,
                "fresh_allocs": self._fresh,
                "resizes": self._resizes,
                # per-shard result-slot leases (tag="shard"): the
                # locality-sharded export path checks out one slot per
                # (leaf, local device) instead of one whole-leaf slot —
                # this counter is how the shard churn test proves the
                # per-shard lease discipline engaged
                "shard_checkouts": self._tag_checkouts.get("shard", 0),
            }
