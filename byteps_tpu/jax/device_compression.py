"""On-device codec execution for the DCN PS path.

SURVEY §7's stage list specifies "COMPRESS (on-device) — the D2H moves
*compressed* bytes". The host-codec path (server/compressed.py) brings
every gradient to the host as dense f32 — 32x the wire bytes for onebit
— and compresses in numpy. This module instead runs the full
momentum -> error-feedback -> codec stack INSIDE one jitted program, so
only wire-sized payload arrays cross device->host, and the aggregated
reply crosses host->device wire-sized and is decompressed on device
(where the Pallas/XLA unpack is effectively free next to the optimizer
pass).

Wire-format parity: the payload arrays serialize to exactly the
ops/compression/host.py layouts — the C++ server cannot tell which
worker tier produced a push. Onebit uses the portable u32-LE bit layout
(codecs.py's jnp path; the Pallas sublane-folded layout is NOT wire
format). Randomk/dithering counter-RNG streams are bit-exact across
np/jnp (tests/test_compression.py), so the server's homomorphic randomk
fast path keeps working.

The transport is the same priority-scheduled pipeline as the host path
(PartitionTask with a prebuilt wire, scheduler.submit_wire): per-4MB
partitions, per-key serialization, credit admission, PUSH/PULL overlap.
Reference splice point: operations.cc:199-204 (COMPRESS/DECOMPRESS as
scheduled-queue stages); here the COMPRESS stage is the XLA program
itself.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.types import (
    DataType, RequestType, TensorContext, get_command_type,
)
from ..ops.compression import make_compressor
from ..ops.compression.codecs import (
    Codec, DitheringCodec, OnebitCodec, RandomkCodec, TopkCodec,
)
from ..ops.compression.feedback import CompressorStack

CMD_COMP_F32 = get_command_type(RequestType.COMPRESSED_PUSH_PULL,
                                DataType.FLOAT32)
CMD_F32 = get_command_type(RequestType.DEFAULT_PUSH_PULL, DataType.FLOAT32)


def _portable(codec: Codec) -> Codec:
    """Wire-layout codec variant: onebit's Pallas kernel uses a
    sublane-folded word order that is not the wire format, so the PS
    tier always runs the portable jnp path for it."""
    import dataclasses
    if isinstance(codec, OnebitCodec) and codec.use_pallas:
        return dataclasses.replace(codec, use_pallas=False)
    return codec


def payload_to_wire(codec: Optional[Codec], payload: Dict[str, np.ndarray],
                    ) -> np.ndarray:
    """Serialize one partition's (host-fetched) payload arrays into the
    host.py wire layout. ``codec=None`` = dense partition (raw f32)."""
    if codec is None:
        return np.ascontiguousarray(payload["raw"]).view(np.uint8)
    if isinstance(codec, OnebitCodec):
        bits = np.ascontiguousarray(payload["bits"], np.uint32)
        scale = np.float32(payload["scale"])
        return np.frombuffer(bits.tobytes() + scale.tobytes(), np.uint8)
    if isinstance(codec, (TopkCodec, RandomkCodec)):
        idx = np.ascontiguousarray(payload["indices"], np.int32)
        val = np.ascontiguousarray(payload["values"], np.float32)
        if isinstance(codec, TopkCodec):
            # the host wire writes topk indices ASCENDING (host.py
            # HostTopk.select); lax.top_k emits |x|-descending order.
            # Randomk stays in RNG generation order — the server re-draws
            # the same stream for its homomorphic fast path.
            order = np.argsort(idx, kind="stable")
            idx = idx[order]
            val = val[order]
        return np.frombuffer(idx.tobytes() + val.tobytes(), np.uint8)
    if isinstance(codec, DitheringCodec):
        lv = np.ascontiguousarray(payload["levels"], np.int8)
        norm = np.float32(payload["norm"])
        return np.frombuffer(lv.tobytes() + norm.tobytes(), np.uint8)
    raise TypeError(f"no wire serializer for {type(codec).__name__}")


def wire_to_payload(codec: Optional[Codec], n: int,
                    reply: np.ndarray) -> Dict[str, np.ndarray]:
    """Parse one partition's reply bytes into the payload-array dict the
    jnp codec's decompress consumes (zero-copy views where possible)."""
    raw = np.frombuffer(reply, np.uint8)
    if codec is None:
        return {"raw": raw.view(np.float32)}
    if isinstance(codec, OnebitCodec):
        return {"bits": raw[:-4].view(np.uint32),
                "scale": raw[-4:].view(np.float32)[0]}
    if isinstance(codec, (TopkCodec, RandomkCodec)):
        k = codec.k
        return {"indices": raw[: 4 * k].view(np.int32),
                "values": raw[4 * k:].view(np.float32)}
    if isinstance(codec, DitheringCodec):
        return {"levels": raw[:n].view(np.int8),
                "norm": raw[n: n + 4].view(np.float32)[0]}
    raise TypeError(f"no wire parser for {type(codec).__name__}")


class _PackSpec:
    """Static packing plan for a payload pytree: one flat buffer per
    dtype, with per-leaf (bucket, offset, size, shape) slots. Built once
    per jitted-fn cache key from ``jax.eval_shape`` of the compress
    program, so the slot order is exactly the tree-flatten order both
    the device and host sides use."""

    def __init__(self, treedef, leaf_meta):
        self.treedef = treedef
        self.leaf_meta = leaf_meta          # [(dtype_name, shape, size, off)]

    @classmethod
    def from_structs(cls, payload_structs):
        flat, treedef = jax.tree_util.tree_flatten(payload_structs)
        offsets: Dict[str, int] = {}
        meta = []
        for s in flat:
            dt = np.dtype(s.dtype).name
            size = int(np.prod(s.shape)) if s.shape else 1
            off = offsets.get(dt, 0)
            offsets[dt] = off + size
            meta.append((dt, tuple(s.shape), size, off))
        return cls(treedef, meta)

    def pack(self, payloads) -> Dict[str, jnp.ndarray]:
        """In-jit: payload pytree -> {dtype: flat buffer}."""
        flat = self.treedef.flatten_up_to(payloads)
        buckets: Dict[str, list] = {}
        for (dt, _, _, _), leaf in zip(self.leaf_meta, flat):
            buckets.setdefault(dt, []).append(jnp.ravel(leaf))
        return {dt: (v[0] if len(v) == 1 else jnp.concatenate(v))
                for dt, v in buckets.items()}

    def unpack_np(self, packed: Dict[str, np.ndarray]):
        """Host: fetched {dtype: buffer} -> payload pytree of np views."""
        leaves = []
        for dt, shape, size, off in self.leaf_meta:
            v = packed[dt][off: off + size].reshape(shape)
            leaves.append(v)
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    def pack_np(self, payloads) -> Dict[str, np.ndarray]:
        """Host: reply payload pytree (np views) -> {dtype: buffer} for
        a couple of H2D uploads. Always returns buffers INDEPENDENT of
        the inputs (np.concatenate copies; the single-bucket case copies
        explicitly): the views may alias arena reply slots that are
        recycled the moment the caller releases them, which must not
        race the async upload."""
        flat = self.treedef.flatten_up_to(payloads)
        buckets: Dict[str, list] = {}
        for (dt, _, _, _), leaf in zip(self.leaf_meta, flat):
            buckets.setdefault(dt, []).append(
                np.ravel(np.asarray(leaf, dtype=dt)))
        return {dt: np.concatenate(v) if len(v) > 1 else v[0].copy()
                for dt, v in buckets.items()}

    def unpack_jnp(self, packed: Dict[str, jnp.ndarray]):
        """In-jit: uploaded {dtype: buffer} -> payload pytree."""
        leaves = []
        for dt, shape, size, off in self.leaf_meta:
            leaves.append(packed[dt][off: off + size].reshape(shape))
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    @staticmethod
    def for_payloads(plans: List["_LeafPlan"]):
        """Payload structure via eval_shape of a structural twin of the
        compress program (leaf VALUES don't matter, only shapes)."""
        payload_structs = []
        for p in plans:
            pl = []
            for (q, stack, st) in zip(p.ctx.partitions, p.stacks, p.states):
                pn = q.length // 4
                if stack is None:
                    pl.append({"raw": jax.ShapeDtypeStruct((pn,),
                                                           jnp.float32)})
                    continue
                payload, _ = jax.eval_shape(
                    lambda x, s, stk=stack: stk.compress(x, s, 0),
                    jax.ShapeDtypeStruct((pn,), jnp.float32),
                    jax.tree.map(
                        lambda a: jax.ShapeDtypeStruct(
                            jnp.shape(a), jnp.result_type(a)), st))
                pl.append(payload)
            payload_structs.append(pl)
        return _PackSpec.from_structs(payload_structs)


class _LeafPlan:
    """Per-tensor device-compression plan: partition layout, per-partition
    device codec stacks + EF/momentum state, and the host base codecs
    used only for server kwargs/wire sizes."""

    def __init__(self, name: str, ctx: TensorContext, kwargs: Dict[str, str],
                 min_compress_bytes: int):
        from ..ops.compression.host import make_host_codec

        self.name = name
        self.ctx = ctx
        self.n = (ctx.partitions[-1].offset + ctx.partitions[-1].length) // 4
        # ef/momentum run on device (the server mirrors only the base
        # codec); index_coding is a host-tier wire option — the device
        # payload stays dense int8 (XLA needs static shapes), so the
        # server must not be told to expect the varint wire
        if kwargs.get("index_coding", "dense") != "dense":
            from ..utils.logging import log
            log.warning(
                "compression index_coding=%r is a host-tier wire option; "
                "the device tier ships dense int8 levels (XLA static "
                "shapes). Pass device_compress=False to make_ps_train_step "
                "to use the coded sparse wire.", kwargs["index_coding"])
        base_kwargs = {k: v for k, v in kwargs.items()
                       if k not in ("ef", "momentum", "momentum_mu",
                                    "index_coding")}
        self.stacks: List[Optional[CompressorStack]] = []
        self.codecs: List[Optional[Codec]] = []   # portable base codecs
        self.host_base = []                       # kwargs_wire providers
        self.states: List[Dict[str, Any]] = []    # device EF/momentum state
        for p in ctx.partitions:
            pn = p.length // 4
            if p.length < max(min_compress_bytes, 8):
                self.stacks.append(None)
                self.codecs.append(None)
                self.host_base.append(None)
                self.states.append({})
            else:
                stack = make_compressor(kwargs, pn)
                stack = CompressorStack(codec=_portable(stack.codec),
                                        use_ef=stack.use_ef,
                                        momentum_mu=stack.momentum_mu)
                self.stacks.append(stack)
                self.codecs.append(stack.codec)
                self.host_base.append(make_host_codec(base_kwargs, pn))
                self.states.append(stack.init_state(pn))
        self.step = 0
        self.priority = -ctx.declared_key
        self.installed = False

    def reply_len(self, i: int) -> int:
        hb = self.host_base[i]
        return self.ctx.partitions[i].length if hb is None else \
            hb.wire_bytes()

    def wire_bytes(self) -> int:
        return sum(self.reply_len(i) for i in range(len(self.ctx.partitions)))


class DeviceCompressor:
    """Whole-tree on-device compress/decompress around the scheduled PS
    pipeline. One instance per (client, kwargs) — holds device-resident
    EF/momentum state per tensor partition across steps."""

    def __init__(self, client, num_workers: int, kwargs: Dict[str, str],
                 min_compress_bytes: int = 0):
        self.client = client
        self.num_workers = num_workers
        self.kwargs = dict(kwargs)
        self.min_compress_bytes = min_compress_bytes
        self._plans: Dict[str, _LeafPlan] = {}
        self._fns: Dict[Tuple, Tuple] = {}
        self._lock = threading.Lock()

    # ---- planning / server install ------------------------------------ #

    def plan(self, state, name: str, n_elems: int) -> _LeafPlan:
        with self._lock:
            p = self._plans.get(name)
            if p is None or p.n != n_elems:
                ctx = state.registry.init_tensor(name, n_elems * 4,
                                                 DataType.FLOAT32)
                p = _LeafPlan(name, ctx, self.kwargs,
                              self.min_compress_bytes)
                self._plans[name] = p
            return p

    def _install(self, plan: _LeafPlan) -> None:
        """Dense init-push (allocates the store + init barrier), then the
        in-band per-key codec kwargs (operations.cc:396-408)."""
        with self._lock:
            if plan.installed:
                return
            # per-partition zeros (ensure_init): the transient allocation
            # is bounded by partition_bytes, not the whole tensor
            self.client.ensure_init(plan.ctx, plan.n * 4)
            for p, hb in zip(plan.ctx.partitions, plan.host_base):
                if hb is not None:
                    self.client.comp_init(p.server, p.key, hb.kwargs_wire())
            plan.installed = True

    # ---- jitted whole-tree codec programs ------------------------------ #

    def _get_fns(self, plans: List[_LeafPlan], average: bool):
        key = (tuple((p.name, p.n) for p in plans), average)
        fns = self._fns.get(key)
        if fns is not None:
            return fns
        # static per-partition codec structure, closed over (hashable
        # frozen dataclasses); dynamic state/payloads flow as pytrees
        stacks = [p.stacks for p in plans]
        codecs = [p.codecs for p in plans]
        parts = [[(q.offset // 4, q.length // 4) for q in p.ctx.partitions]
                 for p in plans]
        nw = self.num_workers

        def compress(leaves, states, step):
            payloads, new_states = [], []
            for leaf, st_list, stk_list, part in zip(
                    leaves, states, stacks, parts):
                flat = leaf.reshape(-1).astype(jnp.float32)
                pl, ns = [], []
                for (off, pn), stack, st in zip(part, stk_list, st_list):
                    x = jax.lax.dynamic_slice_in_dim(flat, off, pn)
                    if stack is None:
                        pl.append({"raw": x})
                        ns.append(st)
                    else:
                        payload, st2 = stack.compress(x, st, step)
                        pl.append(payload)
                        ns.append(st2)
                payloads.append(pl)
                new_states.append(ns)
            return payloads, new_states

        def decompress(replies):
            flats = []
            for reps, cd_list, part in zip(replies, codecs, parts):
                chunks = []
                for payload, codec in zip(reps, cd_list):
                    if codec is None:
                        chunks.append(payload["raw"])
                    else:
                        chunks.append(codec.decompress(payload))
                flat = chunks[0] if len(chunks) == 1 \
                    else jnp.concatenate(chunks)
                if average and nw > 1:
                    flat = flat / nw
                flats.append(flat)
            return flats

        # ---- transfer packing -------------------------------------- #
        # The payload tree has 2 leaves PER PARTITION (e.g. onebit bits +
        # scale): fetching each individually costs a blocking readback,
        # and the per-transfer latency then dominates the round. Pack
        # all leaves into ONE buffer per dtype inside the jitted program
        # so each direction moves 1-2 arrays regardless of partition
        # count — also the right DMA shape on PCIe-attached hosts.
        spec = _PackSpec.for_payloads(plans)

        def compress_packed(leaves, states, step):
            payloads, new_states = compress(leaves, states, step)
            return spec.pack(payloads), new_states

        def decompress_packed(packed):
            return decompress(spec.unpack_jnp(packed))

        fns = (jax.jit(compress_packed, donate_argnums=(1,)),
               jax.jit(decompress_packed), spec)
        self._fns[key] = fns
        return fns

    # ---- the round-trip ------------------------------------------------ #

    def push_pull_leaves(self, state, names: List[str], leaves: List,
                         average: bool = True) -> List:
        """Compress on device, push/pull wire bytes through the priority
        pipeline, decompress the aggregate on device. ``leaves``: device
        arrays (any float dtype/shape); returns device arrays of the same
        shapes/dtypes. Blocking (the internal pipeline overlaps)."""
        # zero-size leaves carry no data: pass them through unchanged (a
        # padded 1-element plan would trace a size-1 dynamic_slice of a
        # 0-element array and crash the step at compile time)
        live = [(i, nm, lf)
                for i, (nm, lf) in enumerate(zip(names, leaves))
                if int(np.prod(lf.shape))]
        if len(live) < len(leaves):
            out = list(leaves)
            if live:
                sub = self.push_pull_leaves(
                    state, [nm for _, nm, _ in live],
                    [lf for _, _, lf in live], average)
                for (i, _, _), r in zip(live, sub):
                    out[i] = r
            return out
        plans = [self.plan(state, nm, int(np.prod(lf.shape)))
                 for nm, lf in zip(names, leaves)]
        for p in plans:
            self._install(p)
        compress_fn, decompress_fn, spec = self._get_fns(plans, average)

        states = [p.states for p in plans]
        # one compression round for the whole tree: all partitions of a
        # tensor share the round number (seeds randomk/dithering and
        # matches the server's completed_rounds in sync mode)
        steps = [p.step for p in plans]
        if len(set(steps)) != 1:
            # re-planned subset; realign on the max (server tolerates
            # skipped seeds — the round counter only seeds RNG streams)
            step0 = max(steps)
            for p in plans:
                p.step = step0
        step0 = plans[0].step
        packed, new_states = compress_fn(leaves, states, jnp.int32(step0))
        for p, ns in zip(plans, new_states):
            p.states = ns
            p.step += 1
        # ONE wire-sized buffer per payload dtype crosses device->host
        # (1-2 transfers total — the whole point of this path); the
        # per-partition payload dicts below are zero-copy views into it
        for v in packed.values():
            if hasattr(v, "copy_to_host_async"):
                v.copy_to_host_async()
        packed_np = {k: np.asarray(v) for k, v in packed.items()}
        payloads = spec.unpack_np(packed_np)

        # reply buffers check out of the persistent staging arena
        # (core/arena.py) instead of np.empty per round; leases are
        # released once pack_np below has copied the payloads out, or
        # abandoned if the round errors with pulls possibly mid-flight
        arena = getattr(state, "arena", None)
        leases: List = []
        handles = []
        try:
            for plan, pl in zip(plans, payloads):
                wires = []
                for i, (payload, codec) in enumerate(zip(pl, plan.codecs)):
                    wires.append(payload_to_wire(codec, payload))
                reply_lens = [plan.reply_len(i) for i in range(len(wires))]
                reply_bufs = None
                if arena is not None:
                    ls = [arena.checkout(f"{plan.name}:reply:{i}", rl)
                          for i, rl in enumerate(reply_lens)]
                    leases.extend(ls)
                    reply_bufs = [lease.buf for lease in ls]
                handle = state.handles.allocate(plan.name)
                state.scheduler.submit_wire(
                    plan.ctx, wires, reply_lens,
                    [CMD_F32 if c is None else CMD_COMP_F32
                     for c in plan.codecs],
                    handle, version=state.next_version(plan.name),
                    priority=plan.priority, reply_bufs=reply_bufs)
                handles.append(handle)

            replies_np = [state.handles.wait_and_clear(h.id)
                          for h in handles]
            replies = []
            for plan, reps in zip(plans, replies_np):
                parsed = []
                for i, (rep, codec) in enumerate(zip(reps, plan.codecs)):
                    pn = plan.ctx.partitions[i].length // 4
                    parsed.append(wire_to_payload(codec, pn, rep))
                replies.append(parsed)
            # mirror of the push side: host-concatenate the reply payloads
            # into one buffer per dtype (cheap memcpy) so the host->device
            # hop is 1-2 uploads, then slice them back apart inside the
            # jitted decompress. pack_np COPIES, so the arena reply slots
            # are idle from here on.
            packed_replies = spec.pack_np(replies)
        except BaseException:
            for lease in leases:
                lease.abandon()
            raise
        for lease in leases:
            lease.release()
        flats = decompress_fn(packed_replies)
        return [f.reshape(lf.shape).astype(lf.dtype)
                for f, lf in zip(flats, leaves)]
