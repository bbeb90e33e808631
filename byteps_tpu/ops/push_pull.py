"""push_pull: gradient summation over the device mesh.

This is the TPU-native core of the framework. The reference implements
push_pull as a 12-stage host-thread pipeline: NCCL ReduceScatter inside the
machine, ZPush/ZPull to parameter servers between machines, NCCL AllGather
back out (reference: byteps/common/core_loops.cc:190-268,538-618). On TPU the
intra-slice part compiles into the XLA program:

- ``psum_tree``            — one-shot allreduce (lax.psum over the dp axis)
- ``reduce_scatter_tree``  — each device ends up owning 1/N of every gradient
  (the analogue of the reference's "each GPU owns 1/local_size of every
  partition" layout, core_loops.cc:216-268)
- ``all_gather_tree``      — rebuild full params from shards (BROADCAST stage)

These are meant to be called *inside* ``shard_map`` / ``pjit`` where the mesh
axis name is bound; XLA then schedules the collectives asynchronously and
overlaps them with compute — which is exactly the pipelining the reference
builds by hand with priority queues and stage threads.

The eager, Horovod-style ``push_pull(x)`` entry point (one call per tensor,
used by the adapter API and tests) wraps the same collectives in a cached
jitted shard_map over the global mesh.

Cross-slice (DCN) aggregation goes through byteps_tpu.server instead — see
that module; this one is pure ICI.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from ..core.state import get_state
from ..core.types import DataType
from ..parallel.mesh import DP_AXIS


# ---------------------------------------------------------------------- #
# in-jit collectives (call inside shard_map/pjit)
# ---------------------------------------------------------------------- #

def psum_tree(tree: Any, axis: str = DP_AXIS, average: bool = True) -> Any:
    """Sum (or mean) every leaf across ``axis``. The REDUCE+PUSH+PULL+
    BROADCAST pipeline collapsed into one XLA allreduce. Integer leaves keep
    their dtype under averaging (truncating, like the reference's post-hoc
    ``div_(size)`` on int tensors, torch/ops.cc:78-90)."""
    summed = jax.lax.psum(tree, axis_name=axis)
    if average:
        n = jax.lax.axis_size(axis)

        def avg(g):
            if jnp.issubdtype(g.dtype, jnp.integer):
                # lax.div truncates toward zero like the reference's C++
                # div_(size) — floor division would skew every negative
                # element by one
                return jax.lax.div(g, jnp.asarray(n, g.dtype))
            return g / n

        summed = jax.tree.map(avg, summed)
    return summed


def pmean_tree(tree: Any, axis: str = DP_AXIS) -> Any:
    return psum_tree(tree, axis, average=True)


def _scatter_leaf(g: jnp.ndarray, axis: str, average: bool) -> jnp.ndarray:
    """ReduceScatter one leaf along its leading dim; pads to make the leading
    dim divisible by the axis size (the reference pads partitions to page
    multiples for the same reason, global.cc:140-144)."""
    n = jax.lax.axis_size(axis)
    flat = g.reshape(-1)
    pad = (-flat.shape[0]) % n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    out = jax.lax.psum_scatter(flat.reshape(n, -1), axis_name=axis,
                               scatter_dimension=0, tiled=False)
    if average:
        if jnp.issubdtype(out.dtype, jnp.integer):
            # keep int dtype + truncating semantics, matching psum_tree
            # (true division would silently promote shards to float and
            # make the scatter/gather pair disagree with the allreduce
            # path on int tensors)
            out = jax.lax.div(out, jnp.asarray(n, out.dtype))
        else:
            out = out / n
    return out


def shard_layout(size: int, num_shards: int) -> tuple:
    """THE shard sizing rule for the locality-sharded export path:
    ``(shard_len, pad)`` such that ``shard_len * num_shards ==
    size + pad`` — identical to the padding ``_scatter_leaf`` applies
    inside the compiled program, so the host-side import plan
    (per-shard key sizes, H2D shapes, trim) can never disagree with the
    device-side reduce-scatter layout."""
    shard_len = (size + num_shards - 1) // num_shards
    return shard_len, shard_len * num_shards - size


def scatter_leaf(g: jnp.ndarray, axis: str = DP_AXIS,
                 average: bool = True) -> jnp.ndarray:
    """Public single-leaf ReduceScatter (the locality-sharded export
    tap reduce-scatters individual eligible leaves while the rest of
    the tree rides one psum)."""
    return _scatter_leaf(g, axis, average)


def reduce_scatter_tree(tree: Any, axis: str = DP_AXIS,
                        average: bool = True) -> Any:
    """ReduceScatter every leaf: afterwards each device holds a flat 1/N shard
    of the summed gradient. Pairs with ``all_gather_tree`` and enables
    sharded (ZeRO-1 style) optimizer updates, the TPU upgrade of the
    reference's owns-1/N-of-each-partition layout."""
    return jax.tree.map(lambda g: _scatter_leaf(g, axis, average), tree)


def all_gather_tree(shard_tree: Any, shapes: Any, axis: str = DP_AXIS) -> Any:
    """Inverse of reduce_scatter_tree: gather flat shards and restore original
    leaf shapes (the ICI_BCAST stage)."""

    def gather(shard, orig):
        full = jax.lax.all_gather(shard, axis_name=axis, axis=0, tiled=False)
        size = int(np.prod(orig.shape)) if orig.shape else 1
        return full.reshape(-1)[:size].reshape(orig.shape).astype(orig.dtype)

    return jax.tree.map(gather, shard_tree, shapes)


# ---------------------------------------------------------------------- #
# eager Horovod-style API
# ---------------------------------------------------------------------- #

@functools.lru_cache(maxsize=64)
def _mesh_spans_processes(mesh: Mesh) -> bool:
    """True when the mesh contains devices of more than one process
    (global-mesh multi-process mode, parallel/distributed.py). Cached —
    it's a pure function of the mesh and sits on the eager hot path."""
    if jax.process_count() == 1:
        return False
    me = jax.process_index()
    return any(d.process_index != me for d in mesh.devices.flat)


def _local_stack(tensor, mesh: Mesh, axis: str, stacked: bool, what: str):
    """Assemble a process-spanning global array from this process's local
    contribution: with ``stacked`` the input carries one slice per LOCAL
    device; otherwise the local value is replicated onto the local devices.
    Only the flat all-``axis`` mesh is supported eagerly — structured
    layouts use the in-jit collectives directly."""
    if tuple(mesh.axis_names) != (axis,):
        raise ValueError(
            f"multi-process eager {what} supports only a flat ('{axis}',) "
            f"mesh, got {mesh.axis_names}")
    n_local = sum(1 for d in mesh.devices.flat
                  if d.process_index == jax.process_index())
    xl = np.asarray(tensor)
    if stacked:
        if xl.ndim == 0 or xl.shape[0] != n_local:
            raise ValueError(
                f"stacked {what} expects leading dim {n_local} (local "
                f"devices on '{axis}'), got shape {xl.shape}")
    else:
        xl = np.broadcast_to(xl, (n_local,) + xl.shape)
    from ..parallel.distributed import global_batch
    return global_batch(mesh, np.ascontiguousarray(xl), axis=axis)


@functools.lru_cache(maxsize=512)
def _cached_push_pull(mesh: Mesh, shape, dtype, average: bool, axis: str):
    """Build and cache a jitted shard_map that sums a (n_dev, *shape) stacked
    input over ``axis`` and returns the replicated (*shape) result."""

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=P(axis), out_specs=P())
    def _pp(x):
        # in_specs=P(axis) with leading dim == axis size -> local block (1, *s)
        return psum_tree(x.reshape(x.shape[1:]), axis=axis, average=average)

    return jax.jit(_pp)


@functools.lru_cache(maxsize=512)
def _cached_push_pull_replicated(mesh: Mesh, shape, dtype, average: bool,
                                 axis: str):
    """Unstacked variant: the input is the replicated value every device
    contributes (in_specs=P()), so the eager path never materializes an
    n_devices-times-larger stacked copy just to reshard it."""

    @functools.partial(shard_map, mesh=mesh, in_specs=P(), out_specs=P())
    def _pp(x):
        return psum_tree(x, axis=axis, average=average)

    return jax.jit(_pp)


def push_pull(tensor, name: Optional[str] = None, average: bool = True,
              axis: str = DP_AXIS, priority: Optional[int] = None,
              stacked: bool = False):
    """Horovod-compatible eager push_pull.

    With ``stacked=True``, ``tensor`` carries one slice per mesh device on
    the leading dim (shape ``(n_devices, *s)``) — the single-controller
    analogue of "each worker contributes its own value". With the default
    ``stacked=False``, ``tensor`` (shape ``(*s)``) is the value every device
    contributes. Either way returns the sum (mean when ``average``) of shape
    ``(*s)``, replicated — the contract of the reference's framework-level
    ``byteps.push_pull`` (reference: byteps/torch/__init__.py:139,
    ops.py:157-174). The flag is explicit because shape inference here is a
    silent-corruption hazard (a replicated tensor whose dim 0 happens to
    equal the mesh size).
    """
    state = get_state()
    if not state.initialized:
        raise RuntimeError("byteps_tpu.init() must be called before push_pull")
    mesh = state.mesh
    n = mesh.shape.get(axis, 1)

    replicated = False
    if _mesh_spans_processes(mesh):
        # Global-mesh multi-process mode: this process contributes values
        # for its own devices; the global array is assembled across
        # processes (each worker feeds its minibatch) and the collective
        # rides ICI/DCN via XLA.
        x = _local_stack(tensor, mesh, axis, stacked, "push_pull")
    else:
        x = jnp.asarray(tensor)
        if stacked:
            if x.ndim == 0 or x.shape[0] != n:
                raise ValueError(
                    f"stacked push_pull expects leading dim {n} (mesh "
                    f"'{axis}' size), got shape {x.shape}")
        else:
            # the replicated value feeds a P()-in_specs shard_map
            # directly — no n_devices-times stacked copy is built
            replicated = True

    out_shape = tuple(x.shape) if replicated else tuple(x.shape[1:])
    if int(np.prod(out_shape)) == 0:
        # zero-element tensors carry no data: skip the collectives and
        # the PS tier entirely (init_tensor rejects zero-size
        # declarations, and the sum of nothing is nothing)
        return jnp.zeros(out_shape, x.dtype)

    if name is not None:
        state.registry.init_tensor(
            name, int(np.prod(out_shape)) * x.dtype.itemsize,
            DataType.from_np(x.dtype))
        from ..utils.logging import debug_sample
        # pass the raw array: debug_sample only materializes (np.asarray →
        # device sync + D2H) after its needle check, keeping the hot
        # collective path free of forced transfers when sampling is off
        debug_sample(state.config, name, "INPUT", tensor)
    if replicated:
        fn = _cached_push_pull_replicated(mesh, out_shape, str(x.dtype),
                                          average, axis)
    else:
        fn = _cached_push_pull(mesh, out_shape, str(x.dtype), average, axis)
    out = fn(x)
    state.telemetry.record(out.nbytes * n)

    if state.ps_client is not None:
        # distributed tier: ICI-reduced value round-trips through the DCN
        # PS for cross-worker summation (REDUCE -> PUSH -> PULL ->
        # BROADCAST, docs/architecture.md "General Workflow")
        if name is None:
            raise ValueError(
                "push_pull over the PS requires a tensor name (stable keys "
                "must match across workers; operations.cc:420-427)")
        from ..server.client import ps_round_trip
        host = np.asarray(out).reshape(-1)
        out = jnp.asarray(
            ps_round_trip(state, name, host, average,
                          priority=priority).reshape(out.shape))

    if name is not None:
        from ..utils.logging import debug_sample
        debug_sample(state.config, name, "OUTPUT", out)
    return out


def broadcast(tensor, root_rank: int = 0, name: Optional[str] = None,
              axis: str = DP_AXIS, stacked: bool = False):
    """Broadcast the root device's value to all devices.

    ``stacked=True``: ``tensor`` is ``(n_devices, *s)`` per-device values and
    the root's slice wins. ``stacked=False`` (default): ``tensor`` is the
    local value (already replicated under single-controller JAX); the
    collective still runs, asserting device agreement and keeping parity
    with the multi-process path. Implemented the way the reference
    implements broadcast_parameters — zero the non-root contributions, then
    push_pull(sum) (reference: byteps/torch/__init__.py:261-293).
    """
    state = get_state()
    if not state.initialized:
        raise RuntimeError("byteps_tpu.init() must be called before broadcast")
    mesh = state.mesh
    n = mesh.shape.get(axis, 1)
    if _mesh_spans_processes(mesh):
        # same local-stack contract as multi-process push_pull; root_rank
        # indexes the GLOBAL device order on the axis
        x = _local_stack(tensor, mesh, axis, stacked, "broadcast")
        out = _cached_broadcast(mesh, root_rank % n, axis)(x)
    else:
        x = jnp.asarray(tensor)
        if stacked:
            if x.ndim == 0 or x.shape[0] != n:
                raise ValueError(
                    f"stacked broadcast expects leading dim {n} (mesh "
                    f"'{axis}' size), got shape {x.shape}")
            out = _cached_broadcast(mesh, root_rank % n, axis)(x)
        else:
            # replicated input: no n-times stacked copy (see push_pull)
            out = _cached_broadcast_replicated(mesh, root_rank % n, axis)(x)

    if state.ps_client is not None and state.config.num_workers > 1:
        # cross-worker tier: the reference's broadcast IS zero-non-root +
        # push_pull(sum) (torch/__init__.py:261-293). root_rank is global:
        # worker root_rank // n holds the source copy.
        if name is None:
            raise ValueError(
                "broadcast over the PS requires a tensor name")
        from ..server.client import ps_round_trip
        root_worker = root_rank // n
        host = np.asarray(out).reshape(-1)
        if state.config.worker_id != root_worker:
            host = np.zeros_like(host)
        out = jnp.asarray(
            ps_round_trip(state, "bcast/" + name, host,
                          average=False).reshape(out.shape))
    return out


@functools.lru_cache(maxsize=64)
def _cached_broadcast(mesh: Mesh, root_rank: int, axis: str):
    @functools.partial(shard_map, mesh=mesh, in_specs=P(axis), out_specs=P())
    def _bcast(v):
        local = v.reshape(v.shape[1:])
        idx = jax.lax.axis_index(axis)
        contrib = jnp.where(idx == root_rank, local, jnp.zeros_like(local))
        return jax.lax.psum(contrib, axis_name=axis)

    return jax.jit(_bcast)


@functools.lru_cache(maxsize=64)
def _cached_broadcast_replicated(mesh: Mesh, root_rank: int, axis: str):
    """Unstacked variant (replicated input, in_specs=P()): the collective
    still runs — asserting device agreement and keeping parity with the
    stacked path — without building an n-times stacked copy first."""

    @functools.partial(shard_map, mesh=mesh, in_specs=P(), out_specs=P())
    def _bcast(v):
        idx = jax.lax.axis_index(axis)
        contrib = jnp.where(idx == root_rank, v, jnp.zeros_like(v))
        return jax.lax.psum(contrib, axis_name=axis)

    return jax.jit(_bcast)
