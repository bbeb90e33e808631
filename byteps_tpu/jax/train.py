"""Sharded train-step construction.

The reference's training loop shape — backward, per-tensor push_pull hooks,
optimizer step on the worker (reference: byteps/torch/__init__.py:142-216,
docs/architecture.md "General Workflow") — becomes here a single compiled
function: shard_map over the mesh, batch sharded on ``dp``, gradients
cross-replica-summed by the distributed optimizer, update applied inside the
same program so XLA overlaps the gradient collectives with remaining
backward compute (the pipelining BytePS builds with host threads).

Two flavors:

- ``make_train_step``: replicated params/optimizer state, psum allreduce.
- ``make_zero_train_step``: ReduceScatter gradients, keep optimizer state
  sharded 1/N per device, AllGather updated params — the TPU upgrade of the
  reference's "each GPU owns 1/local_size of every partition" hierarchical
  layout (core_loops.cc:216-268) that also cuts optimizer memory by N.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.experimental.layout import Format, Layout
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import chain as chain_mod
from ..ops.push_pull import psum_tree, reduce_scatter_tree, all_gather_tree
from ..parallel.mesh import DP_AXIS
from ..utils import tracing


def _loss_and_stats(loss_fn: Callable) -> Callable:
    """``loss_fn(params, batch)`` may return the scalar loss or ``(loss,
    stats)``; either way the step programs differentiate ``(loss,
    stats)``. ``stats`` is a small pytree (a dict by counter name) of
    device scalars or arrays the model counted on the chip, per-expert
    token counts say. They leave the chip as one more output of the
    step program beside the loss, SUMMED over the data-parallel axis,
    so every statistic must be additive across data shards: a count,
    never a maximum or a mean (those are derived from the counts by
    whoever reads the counters). They are added to the metrics
    registry's counters after the step (``_fold_stats``): no host
    callback enters the program, so it stays servable from the
    persistent compile cache. A scalar loss gives an empty ``stats``
    and costs nothing."""

    def fn(params, batch):
        out = loss_fn(params, batch)
        return out if isinstance(out, tuple) else (out, {})

    return fn


def _fold_stats(stats) -> None:
    """Add one step's device statistics to the registry: the scalar at
    path ``a/b`` goes into counter ``a/b``, element ``[i, j]`` of an
    array there into counter ``a/b/i/j``. Reads the values to the host,
    so the step they belong to must be done or nothing else may wait on
    this thread."""
    if not stats:
        return
    from ..core.state import get_state

    registry = get_state().metrics
    for path, leaf in jax.tree_util.tree_flatten_with_path(stats)[0]:
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
        for index, value in np.ndenumerate(np.asarray(leaf)):
            registry.counter("/".join([name, *map(str, index)])).inc(
                value.item())


def make_train_step(
    loss_fn: Callable,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    axis: str = DP_AXIS,
    grads_transform: Optional[Callable] = None,
    donate: bool = True,
    extra_batch_axes: Tuple[str, ...] = (),
    opt_specs: Any = None,
):
    """Build a jitted SPMD train step.

    ``loss_fn(params, batch) -> scalar`` (or ``(scalar, stats)``, see
    ``_loss_and_stats``: the statistics of step k reach the registry
    once step k + 1 is dispatched, ``step.fold_stats()`` folds the last
    step's) computed on the local batch shard;
    ``tx`` should be ``byteps_tpu.jax.distributed_optimizer(...)`` so the
    gradient push_pull happens inside its update (or pass a plain optax tx
    plus ``grads_transform=lambda g: psum_tree(g, axis)``).

    Returns ``step(params, opt_state, batch) -> (params, opt_state, loss)``.
    Batch leaves are sharded on their leading dim over ``axis`` (+
    ``extra_batch_axes``, e.g. ("sp",) to also shard sequence).

    ``opt_specs``: PartitionSpec pytree for the optimizer state; REQUIRED
    (via byteps_tpu.jax.init_opt_state) when ``tx`` carries per-replica
    compression state (EF/momentum) — those leaves are device-varying and
    must be declared sharded, not replicated.
    """
    batch_spec = P((axis,) + tuple(extra_batch_axes)) \
        if extra_batch_axes else P(axis)
    if opt_specs is None:
        opt_specs = P()

    loss_and_stats = _loss_and_stats(loss_fn)

    def step(params, opt_state, batch):
        (loss, stats), grads = jax.value_and_grad(
            loss_and_stats, has_aux=True)(params, batch)
        if grads_transform is not None:
            grads = grads_transform(grads)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        loss = jax.lax.pmean(loss, axis)
        stats = jax.tree.map(lambda x: jax.lax.psum(x, axis), stats)
        return params, opt_state, loss, stats

    smapped = jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(), opt_specs, batch_spec),
        out_specs=(P(), opt_specs, P(), P()),
        check_vma=False,
    )
    donate_argnums = (0, 1) if donate else ()
    jitted = jax.jit(smapped, donate_argnums=donate_argnums)
    return _with_tracer_tick(jitted, stats=True)


def _with_tracer_tick(jitted, stats: bool = False):
    """Tick the Chrome-trace step counter per training step (the reference
    counts steps to window tracing between BYTEPS_TRACE_START/END_STEP,
    global.cc:113-124). ``stats``: the program's fourth output is the
    step's device statistics; they are taken off the result and folded
    into the registry one step late, after the next dispatch, so the
    host never waits on the step it has just queued."""
    import functools as _functools

    from ..core.state import get_state

    waiting: list = []

    def fold_stats():
        while waiting:
            _fold_stats(waiting.pop())

    @_functools.wraps(jitted)
    def stepper(*args, **kw):
        tracer = get_state().tracer
        if tracer is not None:
            tracer.step()
        out = jitted(*args, **kw)
        if not stats:
            return out
        fold_stats()
        if out[3]:
            waiting.append(out[3])
        return out[:3]

    # keep access to the underlying jitted fn (e.g. for AOT lowering)
    stepper.jitted = jitted
    stepper.fold_stats = fold_stats
    return stepper


def _zero_state_specs(params, tx: optax.GradientTransformation, mesh: Mesh,
                      axis: str):
    """Opt-state partition specs for the ZeRO layout: array leaves are flat
    1/N shards -> P(axis); scalar leaves (e.g. adam's count) replicate."""
    import numpy as np

    n = mesh.shape[axis]

    def shard_shape(p):
        size = int(np.prod(p.shape)) if p.shape else 1
        padded = size + (-size % n)
        return jax.ShapeDtypeStruct((padded // n,), p.dtype)

    shard_params = jax.tree.map(shard_shape, params)
    opt_shapes = jax.eval_shape(tx.init, shard_params)
    specs = jax.tree.map(lambda s: P() if s.ndim == 0 else P(axis), opt_shapes)
    return specs


def make_zero_train_step(
    loss_fn: Callable,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    params_example: Any,
    axis: str = DP_AXIS,
    donate: bool = True,
):
    """ZeRO-1-style step: optimizer state lives sharded (flat 1/N per
    device); gradients ReduceScatter instead of allreduce; params AllGather
    after the shard update. Cuts optimizer memory by N and replaces the
    allreduce with RS+AG, each half the bytes.

    Use ``init_zero_state(params, tx, mesh, axis)`` for the initial optimizer
    state. Params stay replicated between steps. ``params_example`` (a pytree
    of arrays or ShapeDtypeStructs) fixes the optimizer-state structure.
    """
    opt_specs = _zero_state_specs(params_example, tx, mesh, axis)

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        grad_shards = reduce_scatter_tree(grads, axis=axis, average=True)
        param_shards = reduce_scatter_tree(params, axis=axis, average=True)
        updates, opt_state = tx.update(grad_shards, opt_state, param_shards)
        param_shards = optax.apply_updates(param_shards, updates)
        params = all_gather_tree(param_shards, params, axis=axis)
        loss = jax.lax.pmean(loss, axis)
        return params, opt_state, loss

    smapped = jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(), opt_specs, P(axis)),
        out_specs=(P(), opt_specs, P()),
        check_vma=False,
    )
    donate_argnums = (0, 1) if donate else ()
    return _with_tracer_tick(jax.jit(smapped, donate_argnums=donate_argnums))


_COMP_POOL = None
_rowsparse_warned: set = set()  # names warned about dense fallback
_chaos_nan_fired: set = set()   # BYTEPS_CHAOS_NAN_LEAF specs consumed


def _chaos_nan_poison(spec: str, name: str, flat, step_no: int):
    """``BYTEPS_CHAOS_NAN_LEAF="<substr>[@<step>]"``: poison the first
    matching leaf's push with one NaN at/after ``<step>`` (default 3),
    ONCE per process per spec value — the chaos harness for the
    training-health plane's detect → flight-event → guard causality
    (core/health.py, tests/test_health.py). Returns the payload to
    push (a poisoned copy, or ``flat`` untouched)."""
    sub, _, at = spec.partition("@")
    try:
        at_step = int(at) if at else 3
    except ValueError:
        at_step = 3
    if spec in _chaos_nan_fired or step_no < at_step \
            or not sub or sub not in name:
        return flat
    _chaos_nan_fired.add(spec)
    poisoned = np.array(flat, copy=True)
    poisoned.reshape(-1)[0] = np.nan
    from ..core import flight
    flight.record("chaos_nan_injected",
                  detail=f"{name} step={step_no} spec={spec}")
    from ..utils.logging import log
    log.warning("CHAOS: injected NaN into %r push at step %d "
                "(BYTEPS_CHAOS_NAN_LEAF=%s)", name, step_no, spec)
    return poisoned


_RELEASE_POOL = None


def _release_pool():
    """Deferred arena-release worker: its tasks block on import
    readiness, which the train thread does not wait for at a step's
    end."""
    global _RELEASE_POOL
    if _RELEASE_POOL is None:
        import concurrent.futures
        _RELEASE_POOL = concurrent.futures.ThreadPoolExecutor(
            1, thread_name_prefix="bps-release")
    return _RELEASE_POOL


def _comp_pool():
    """Shared tensor-level fan-out pool for compressed push_pull. Must be
    distinct from the client's partition pool (a tensor task blocks on
    partition tasks — sharing one pool could deadlock) and shared across
    step functions so rebuilding a step never accumulates executors."""
    global _COMP_POOL
    if _COMP_POOL is None:
        import concurrent.futures
        _COMP_POOL = concurrent.futures.ThreadPoolExecutor(
            max_workers=8, thread_name_prefix="bps-comp")
    return _COMP_POOL


def _route_rowsparse(name: str, leaf, state, rowsparse_params) -> bool:
    """One routing predicate for BOTH compression tiers: a leaf matching
    ``rowsparse_params`` rides the row-sparse wire only when it is 2D
    and a scheduler is running; mismatches warn once and fall back to
    the tier's dense/compressed path."""
    if not (rowsparse_params and any(s in name for s in rowsparse_params)):
        return False
    if getattr(leaf, "ndim", None) == 2 and state.scheduler is not None:
        return True
    if name not in _rowsparse_warned:
        from ..utils.logging import log
        _rowsparse_warned.add(name)
        log.warning(
            "rowsparse_params matched %r but the gradient is not 2D "
            "(shape %s) or no scheduler is running — using the dense "
            "path", name, getattr(leaf, "shape", None))
    return False


def _device_compressed_round(state, client, comp_state, compression,
                             min_compress_bytes, rowsparse_params, names,
                             leaves, treedef):
    """One gradient round on the device-compressed tier: leaves matching
    ``rowsparse_params`` ride the host row-sparse path (the row payload
    needs the dense host rows anyway); everything else compresses inside
    XLA and crosses device->host wire-sized
    (device_compression.DeviceCompressor)."""
    import numpy as np

    from .device_compression import DeviceCompressor

    if comp_state["client"] is not client or comp_state["device"] is None:
        mcb = min_compress_bytes
        if mcb is None:
            mcb = getattr(state.config, "min_compress_bytes", 0)
        comp_state["device"] = DeviceCompressor(
            client, state.config.num_workers, compression, mcb)
        comp_state["client"] = client
        comp_state["registry"] = None  # host tier rebuilt on demand
    dc = comp_state["device"]

    sparse = {}
    dev_idx = []
    for i, (name, leaf) in enumerate(zip(names, leaves)):
        if _route_rowsparse(name, leaf, state, rowsparse_params):
            sparse[i] = None
        else:
            dev_idx.append(i)
    from .. import _rowsparse_submit
    for i in sparse:
        h = np.asarray(leaves[i]).astype(np.float32, copy=False)
        handle = state.handles.allocate(names[i])
        _rowsparse_submit(state, names[i], h, True, handle)
        sparse[i] = (handle, leaves[i].dtype)
    results = [None] * len(leaves)
    if dev_idx:
        out = dc.push_pull_leaves(state, [names[i] for i in dev_idx],
                                  [leaves[i] for i in dev_idx])
        for i, o in zip(dev_idx, out):
            results[i] = o
    for i, (handle, dt) in sparse.items():
        results[i] = np.asarray(
            state.handles.wait_and_clear(handle.id)).astype(dt, copy=False)
    return treedef.unflatten(results)


def _reduce_loss(pair, axis: str):
    """``(loss, stats)`` of one data shard -> the mean loss and the
    summed statistics over ``axis``."""
    loss, stats = pair
    return (jax.lax.pmean(loss, axis),
            jax.tree.map(lambda x: jax.lax.psum(x, axis), stats))


def _psum_backward(loss_and_stats: Callable, mesh: Mesh, axis: str):
    """The backward of a PS step whose export plan shards no leaf
    (``make_ps_train_step``'s ``grad_fn``): every gradient leaf psum'd
    over ``axis``, a replicated output."""

    def local_grads(params, batch):
        # ``loss`` is the pair (loss, stats) from here to the step's
        # end, where the statistics are folded into the registry
        loss, grads = jax.value_and_grad(
            loss_and_stats, has_aux=True)(params, batch)
        grads = psum_tree(grads, axis=axis, average=True)
        return _reduce_loss(loss, axis), grads

    return jax.jit(jax.shard_map(
        local_grads, mesh=mesh, in_specs=(P(), P(axis)),
        out_specs=(P(), P()), check_vma=False))


def _scatter_backward(loss_and_stats: Callable, mesh: Mesh, axis: str,
                      shard_set, n_leaves: int):
    """The backward of a PS step whose export plan shards leaves:
    identical math to ``_psum_backward``, with the leaves in
    ``shard_set`` (BYTEPS_LOCAL_SHARD_EXPORT) riding
    ``reduce_scatter`` instead of the psum. The program returns those
    leaves as flat padded ``P(axis)``-sharded outputs, so each device
    holds only ITS 1/local_size shard and only that ever crosses
    device->host per device — BytePS's hierarchical "the intra-machine
    reduce puts 1/local_size on the wire". The remaining leaves keep
    the exact whole-leaf path (one psum over their subtree, replicated
    output), so disabling sharding per leaf is bitwise-invisible. The
    program is ``fn(params, batch)`` and holds no host callback, so the
    persistent compile cache can serve it."""
    from ..ops.push_pull import scatter_leaf

    shard_set = frozenset(shard_set)

    def local(params, batch):
        loss, grads = jax.value_and_grad(
            loss_and_stats, has_aux=True)(params, batch)
        leaves = jax.tree.leaves(grads)
        # ONE psum over the whole-leaf subtree (identical reduction
        # grouping to _psum_backward's full-tree psum), RS per shard leaf
        whole_idx = [i for i in range(len(leaves)) if i not in shard_set]
        whole = psum_tree([leaves[i] for i in whole_idx],
                          axis=axis, average=True)
        whole_map = dict(zip(whole_idx, whole))
        outs = tuple(scatter_leaf(leaves[i], axis=axis, average=True)
                     if i in shard_set else whole_map[i]
                     for i in range(len(leaves)))
        return _reduce_loss(loss, axis), outs

    out_specs = (P(), tuple(P(axis) if i in shard_set else P()
                            for i in range(n_leaves)))
    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=(P(), P(axis)),
                                 out_specs=out_specs, check_vma=False))


def _shapes(tree):
    """What a jit keys its traces on: the tree's structure and each
    leaf's shape and type."""
    leaves, treedef = jax.tree.flatten(tree)
    return treedef, tuple((np.shape(a), str(getattr(a, "dtype", type(a))))
                          for a in leaves)


@dataclasses.dataclass
class _CutBackward:
    """The programs of a backward cut at a chain's links
    (``_cut_backward``), each a jit over the mesh, and which gradient
    leaves each computes: ``leaves[k]`` are link ``k``'s flatten
    indices in the parameter tree, in the order of its program's
    gradient outputs. A leaf under several links is SHARED (a tied
    embedding): the programs run last link first, each that reads the
    leaf but the last to run keeps its term on the chip (``held``), an
    input of the next that reads it (``taken``), and the last to run
    hands the sum over."""

    chain: Any
    leaves: Dict[int, Tuple[int, ...]]
    forward: Optional[Callable] = None
    last: Optional[Callable] = None
    # link -> its program; a run's: one a distinct kind of its layers
    # (``chain.Run.layer_kinds``), by kind
    pulls: Dict[int, Any] = dataclasses.field(default_factory=dict)
    # link -> the gradient outputs its programs hand over under a pinned
    # layout in a step (a layer's program runs once a layer)
    pinned: Dict[int, int] = dataclasses.field(default_factory=dict)
    # XLA's cost analysis summed over a step's programs (the ledger's)
    cost: Dict[str, float] = dataclasses.field(default_factory=dict)

    @functools.cached_property
    def shared(self) -> Dict[int, Tuple[int, ...]]:
        """Flatten index of a shared leaf -> the links that read it,
        ascending."""
        readers: Dict[int, list] = {}
        for k, found in sorted(self.leaves.items()):
            for i in found:
                readers.setdefault(i, []).append(k)
        return {i: tuple(ks) for i, ks in readers.items() if len(ks) > 1}

    def held(self, k: int) -> Tuple[int, ...]:
        """The leaves whose term link ``k``'s program keeps on the chip:
        an earlier link, whose program runs later, reads them too."""
        return tuple(i for i in self.leaves[k]
                     if i in self.shared and self.shared[i][0] < k)

    def taken(self, k: int) -> Tuple[int, ...]:
        """The leaves whose term so far is an input of link ``k``'s
        program: a later link, whose program has run, read them."""
        return tuple(i for i in self.leaves[k]
                     if i in self.shared and self.shared[i][-1] > k)

    @property
    def outputs_pinned(self) -> int:
        """Gradient outputs a step's programs hand over under a pinned
        layout."""
        return sum(self.pinned.values())

    @property
    def programs(self) -> int:
        """Programs a step runs: the forward, the last link's, one a
        layer of a run and one a link before."""
        return 2 + sum(getattr(ln, "depth", 1)
                       for ln in self.chain.links[:-1])


def _chain_leaves(ch, params, paths=None
                  ) -> Optional[Dict[int, Tuple[int, ...]]]:
    """Link index -> the flatten indices in ``params`` of the leaves the
    link picks, in the order it picks them (a leaf under several links
    is in each one's: ``_CutBackward.shared``); None where ``ch`` cannot
    be cut over this tree (``chain.Chain.cover``) or a link's leaves are
    not the tree's own under its keys, in the tree's order. ``paths``:
    ``params`` flattened with paths, where the caller has it."""
    if paths is None:
        paths = jax.tree_util.tree_flatten_with_path(params)[0]
    leaves = ch.cover(params, paths)
    if leaves is None:
        return None
    for k, ln in enumerate(ch.links):
        picked = jax.tree.leaves(ln.pick(params))
        if len(picked) != len(leaves[k]) or any(
                a is not paths[i][1] for a, i in zip(picked, leaves[k])):
            return None
    return leaves


def _cut_backward(ch, mesh: Mesh, axis: str, leaves) -> _CutBackward:
    """The backward of a PS step whose loss is the chain ``ch``, as one
    program a link (``ops/chain.py``): ``forward(params, batch) ->
    (kept, stats)``, ``last(p, kept[-1], batch) -> (((loss, stats),
    cotangent), gradients)`` and, for each link ``k`` before the last,
    ``pulls[k](p, kept[k], batch, cotangent) -> (cotangent,
    gradients)``, a run's ``pulls[k][kind](p, layer, kept[k], ...)``,
    one program a distinct kind of its layers (one in all where they
    have no kinds), shared by the layers of that kind through the traced
    index: ``_psum_backward``'s mathematics link by link, every
    gradient psum'd over ``axis`` where it is computed. A carry is a
    data shard's own value: between programs it is a ``P(axis)`` array
    with the device as its leading dimension. Every program that has
    gradients returns them LAST and takes their parameters FIRST, which
    is what ``_row_major_outputs`` looks at. ``leaves``:
    ``_chain_leaves``.

    A shared leaf (``_CutBackward.shared``): the program of a link that
    ``held`` it returns its term in the leaf's place among the
    gradients, a data shard's own (as a carry is: nothing is psum'd
    yet); the program of a link that has ``taken`` it has one argument
    more, the terms so far, LAST, and adds its own to each; the program
    that runs last of those that read the leaf psums the sum and returns
    it as the leaf's gradient, as ``_psum_backward`` does the sum the
    one program makes. A link that reads no shared leaf has the program
    it had."""
    rep, loc = P(), P(axis)
    cut = _CutBackward(ch, leaves)

    def lift(tree):
        return jax.tree.map(lambda a: a[None], tree)

    def drop(tree):
        return jax.tree.map(lambda a: a[0], tree)

    def mean(grads):
        return psum_tree(grads, axis=axis, average=True)

    def program(fn, in_specs, out_specs):
        return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                     out_specs=out_specs, check_vma=False))

    def handed(k, g_p, terms=()):
        """Link ``k``'s gradients as its program returns them: the terms
        so far added where taken, the others psum'd; where a term is
        held it stays a shard's own, and the gradients are a tuple, one
        a leaf in the order of ``leaves[k]`` (``handed_specs`` gives a
        leaf its own spec)."""
        flat, tree = jax.tree.flatten(g_p)
        at = {i: n for n, i in enumerate(leaves[k])}
        for i, term in zip(cut.taken(k), terms):
            flat[at[i]] = drop(term) + flat[at[i]]
        keep = {at[i] for i in cut.held(k)}
        whole = iter(mean([g for n, g in enumerate(flat) if n not in keep]))
        flat = [lift(g) if n in keep else next(whole)
                for n, g in enumerate(flat)]
        return tuple(flat) if keep else tree.unflatten(flat)

    def handed_specs(k):
        return tuple(loc if i in cut.held(k) else rep
                     for i in leaves[k]) if cut.held(k) else rep

    def forward(params, batch):
        kept, stats = ch.forward(params, batch)
        return lift(kept), jax.tree.map(
            lambda x: jax.lax.psum(x, axis), stats)

    def last(p, carry, batch):
        loss, stats, g_carry, g_p = ch.last(p, drop(carry), batch)
        return (_reduce_loss((loss, stats), axis), lift(g_carry)), \
            handed(len(ch.links) - 1, g_p)

    def pull(k):
        if isinstance(ch.links[k], chain_mod.Run):
            def layer_of(kind):
                def layer(p, j, inputs, batch, ct):
                    g_x, g_p = ch.pull_layer(k, p, j, drop(inputs), batch,
                                             drop(ct), kind)
                    return lift(g_x), mean(g_p)
                return program(layer, (rep, rep, loc, loc, loc), (loc, rep))
            return {kind: layer_of(kind)
                    for kind in dict.fromkeys(ch.links[k].layer_kinds)}

        def whole(p, carry, batch, ct, *terms):
            g_carry, g_p = ch.pull_link(k, p, drop(carry), batch, drop(ct))
            return lift(g_carry), handed(k, g_p, *terms)
        return program(
            whole, (rep, loc, loc, loc) + ((loc,) if cut.taken(k) else ()),
            (loc, handed_specs(k)))

    cut.forward = program(forward, (rep, loc), (loc, rep))
    cut.last = program(last, (rep, loc, loc),
                       ((rep, loc), handed_specs(len(ch.links) - 1)))
    cut.pulls = {k: pull(k) for k in range(len(ch.links) - 1)}
    return cut


def _dispatch_cut(cut: _CutBackward, params, batch):
    """Dispatch every program of a cut backward, all at once and
    asynchronously, in the order they run -> ((loss, stats), programs):
    one ``(links, layer, ready, outputs)`` a program in the order the
    programs END, a pure function of the chain: the links it runs (a
    label), the layer of a run it is (or None), an output of it to wait
    on, and the gradient outputs it HANDS OVER by flatten index (a
    shared leaf's term that a program keeps on the chip goes to the next
    program that reads the leaf and is in no one's; the sum is in the
    last such program's).

    (The runtime finds a program's temporaries when the program is
    ENQUEUED: where two programs' do not fit beside the step's state the
    second enqueue blocks until the first has ended, and the train
    thread sits here instead of claiming. PERF.md section 6, PR 43, has
    the step that did, and section 7 what was tried.)"""
    links = cut.chain.links
    last = len(links) - 1
    kept, stats = cut.forward(params, batch)
    programs = [(f"0-{last - 1}", None, jax.tree.leaves(kept)[-1], {})]
    # a shared leaf's term so far, from the program that held it to the
    # next that reads the leaf
    terms: Dict[int, Any] = {}

    def ran(k, layer, ct, grads):
        grads = dict(zip(cut.leaves[k], jax.tree.leaves(grads)))
        ready = (list(grads.values()) or jax.tree.leaves(ct))[0]
        for i in cut.held(k):
            terms[i] = grads.pop(i)
        programs.append((str(k), layer, ready, grads))

    ((loss, last_stats), ct), grads = cut.last(
        links[last].pick(params), kept[last], batch)
    ran(last, None, ct, grads)
    for k in reversed(range(last)):
        p = links[k].pick(params)
        if isinstance(links[k], chain_mod.Run):
            for j, kind in reversed(list(enumerate(links[k].layer_kinds))):
                ct, grads = cut.pulls[k][kind](p, np.int32(j), kept[k],
                                               batch, ct)
                ran(k, j, ct, grads)
        else:
            taken = tuple(terms.pop(i) for i in cut.taken(k))
            ct, grads = cut.pulls[k](p, kept[k], batch, ct,
                                     *((taken,) if taken else ()))
            ran(k, None, ct, grads)
    return (loss, chain_mod.add_stats(dict(stats), last_stats)), programs


class _Layers:
    """A stacked leaf that a cut backward hands over a layer at a time
    (``parts[j]``: layer ``j``'s ``[1, ...]`` output) and the plan keeps
    whole: what the claim loop takes for the leaf. ``np.asarray`` puts
    it together on the host, where the loop would have waited for the
    leaf's transfer."""

    def __init__(self, parts: list):
        self.parts = parts

    @property
    def ndim(self) -> int:
        return self.parts[0].ndim

    @property
    def nbytes(self) -> int:
        return sum(part.nbytes for part in self.parts)

    def __array__(self, dtype=None, copy=None):
        return np.concatenate([np.asarray(part) for part in self.parts])


def _row_major(fmt) -> bool:
    """Whether a ``Format`` (or none at all: a host array) orders the
    dimensions major-to-minor, the order the wire carries."""
    order = getattr(getattr(fmt, "layout", None), "major_to_minor", None)
    return order is None or tuple(order) == tuple(range(len(order)))


def _row_major_outputs(backward, args, own, mesh: Mesh):
    """``backward`` (the jit of ``_psum_backward`` or
    ``_scatter_backward``) with the layout the wire needs on the
    gradient outputs in ``own`` (flatten indices: the whole leaves on a
    key of their own). ``np.asarray`` of an output has the strides of
    the DEVICE's dimension order, and the runtime's default order is
    not major-to-minor where another packs the tiles better (on the
    v5e a float32 ``[2048, 18992]`` is minor in its FIRST dimension):
    such a leaf reaches the claim loop as a view that is not
    C-contiguous, and one host core transposes it before a partition is
    enqueued. An output pinned to major-to-minor is re-laid by a copy
    on the chip, at HBM speed, inside the program.

    Which outputs: a gradient has its parameter's shape and type, so
    the default layout of both is the one the parameter lives in, and
    the first program built is already the one that runs (a default
    program compiled only to be looked at would stay loaded beside it:
    0.23 GiB of the chip on SDAR's cell). The compiled program's output
    layouts are then read, and an output of ``own`` that is out of
    order all the same (a parameter that came from the host, or in a
    layout of the caller's) is pinned in a second build. Returns the
    jit to run, compiled by this look (the call that follows compiles
    nothing again), and the number of outputs pinned; with none the jit
    is ``backward`` itself and its program text is what it was. A
    pinned jit wraps the same traced function: nothing is traced
    twice."""
    leaves = jax.tree.leaves(args[0])
    # outputs are ((loss, stats), gradients)
    gradients = jax.tree.structure(backward.trace(*args).out_info[1])
    whole = NamedSharding(mesh, P())
    pins = {i for i in own
            if not _row_major(getattr(leaves[i], "format", None))}
    while True:
        fn = backward if not pins else jax.jit(
            backward.__wrapped__,
            out_shardings=(None, gradients.unflatten(
                Format(Layout(major_to_minor=tuple(range(leaf.ndim))), whole)
                if i in pins else None for i, leaf in enumerate(leaves))))
        formats = jax.tree.leaves(
            fn.lower(*args).compile().output_formats[1])
        missed = {i for i in own if not _row_major(formats[i])} - pins
        if not missed:
            return fn, len(pins)
        pins |= missed


def _pin_cut_outputs(cut: _CutBackward, params, batch, own, mesh: Mesh,
                     axis: str) -> None:
    """``_row_major_outputs`` for each program of ``cut`` that has
    gradients, last link first, as the step will run them: the outputs
    in ``own`` (flatten indices of ``params``; a piece is its leaf's
    layer) are pinned major-to-minor, a shared leaf's on the program
    that hands its sum over (a term kept on the chip keeps the layout
    the compiler gives it). Nothing runs: a program's carries, cotangent
    and terms are described by the shapes the program before it returns,
    each a ``P(axis)`` array. The programs' costs by XLA's analysis are
    added up into ``cut.cost`` on the way (the step's ledger reads
    them: the one program a cut step does not run is not lowered for
    its cost)."""
    from ..core.ledger import extract_cost

    local = NamedSharding(mesh, P(axis))
    links = cut.chain.links

    def described(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=local), tree)

    # a shared leaf's term so far, described (``_dispatch_cut``'s)
    terms: Dict[int, Any] = {}

    def count(fn, args, times=1):
        """A program's cost, ``times`` a step, into ``cut.cost`` (the
        lowering is the one the look made, or the run's: it is kept)."""
        for name, value in (extract_cost(fn.lower(*args)) or {}).items():
            cut.cost[name] = cut.cost.get(name, 0.0) + times * value

    def pin(k, program, *args, times=1):
        """``program`` of link ``k``, which a step runs ``times``."""
        p = links[k].pick(params)
        taken = tuple(terms.pop(i) for i in cut.taken(k))
        args = args + ((taken,) if taken else ())
        held = cut.held(k)
        fn, pinned = _row_major_outputs(
            program, (p, *args),
            [n for n, i in enumerate(cut.leaves[k])
             if i in own and i not in held], mesh)
        cut.pinned[k] = cut.pinned.get(k, 0) + times * pinned
        count(fn, (p, *args), times)
        carried, grads = fn.trace(p, *args).out_info
        grads = dict(zip(cut.leaves[k], jax.tree.leaves(grads)))
        terms.update((i, described(grads[i])) for i in held)
        return fn, carried

    count(cut.forward, (params, batch))
    kept = described(cut.forward.trace(params, batch).out_info[0])
    cut.last, (_, ct) = pin(len(links) - 1, cut.last, kept[-1], batch)
    for k in reversed(range(len(links) - 1)):
        ct = described(ct)
        if isinstance(links[k], chain_mod.Run):
            # a kind's program once, in the order the step first runs them
            kinds = links[k].layer_kinds
            for kind in dict.fromkeys(reversed(kinds)):
                cut.pulls[k][kind], carried = pin(
                    k, cut.pulls[k][kind], np.int32(0), kept[k], batch, ct,
                    times=kinds.count(kind))
            ct = carried
        else:
            cut.pulls[k], ct = pin(k, cut.pulls[k], kept[k], batch, ct)


@dataclasses.dataclass(frozen=True)
class ExportPlan:
    """How a PS step's gradient leaves leave the chip. Every leaf is an
    output of the backward; the leaves in ``shard_set`` (flatten
    indices, ascending) leave as ``n_shard`` flat per-device shards of
    ``layouts[k] = (size, shard_len, dtype)`` under subrange keys of
    their own; the leaves in ``pieces`` (``(flatten index, depth)``: the
    stacked leaves of a chain's runs, where the backward is cut at the
    chain's links) leave as ``depth`` pieces, layer ``j``'s ``[1, ...]``
    slice an output of layer ``j``'s program, under subrange keys of
    their own; every other leaf as one replicated array (on a key of its
    own, in a fusion bucket or row-sparse: the claim loop's business,
    not the plan's). A plan holds shards or pieces, never both: the
    backward is cut only where ``pieces`` is not empty."""

    shard_set: Tuple[int, ...] = ()
    n_shard: int = 0
    layouts: Tuple[Tuple[int, int, Any], ...] = ()
    pieces: Tuple[Tuple[int, int], ...] = ()


def _export_plan(names, leaves, *, mesh: Mesh, axis: str, fusion_bytes: int,
                 shard_min_bytes: int, local_shard: bool, rowsparse_params,
                 host_codec: bool, scheduler_running: bool,
                 stacked: Optional[Dict[int, int]] = None) -> ExportPlan:
    """The rule that decides which leaves shard (BYTEPS_LOCAL_SHARD_EXPORT)
    and which leave as pieces, from configuration, topology and the
    loss's chain alone: the set of PS keys a worker pushes, subranges
    included, has to be the same pure function on every worker, or the
    key sets would diverge and stall every peer's aggregation. Nothing
    shards and nothing is cut without a running scheduler or under a
    host codec (the codec unit is the declared key: a per-shard codec
    would reset EF/momentum state per device). Leaves shard on a mesh of
    one axis of more than one device, where ``local_shard``: one does
    when it rides a dense key of its own (not row-sparse by name, not
    empty, not a bucket member under ``fusion_bytes``), is worth
    ``n_shard`` extra key round trips (``shard_min_bytes``) and pads by
    at most 1/8 of its size. ``stacked`` (flatten index -> depth) are
    the stacked leaves of the runs of a chain that can be cut
    (``chain.Chain.cuts``): where no leaf shards, each of them that the
    same rules would let ride keys of its own leaves as ``depth``
    pieces; the others stay whole (a norm of kilobytes is put together
    on the host and rides its bucket)."""
    if not scheduler_running or host_codec:
        return ExportPlan()

    floor = max(fusion_bytes, shard_min_bytes)

    def own_key(name, leaf):
        if rowsparse_params and any(s in name for s in rowsparse_params):
            return False
        nbytes = getattr(leaf, "nbytes", 0)
        return nbytes != 0 and nbytes >= floor

    n_shard = int(mesh.shape.get(axis, 1)) \
        if local_shard and len(mesh.axis_names) == 1 else 1
    shard_set, layouts = [], []
    if n_shard > 1:
        from ..ops.push_pull import shard_layout

        for i, (name, leaf) in enumerate(zip(names, leaves)):
            if not own_key(name, leaf):
                continue
            size = int(np.prod(leaf.shape)) if leaf.shape else 1
            shard_len, pad = shard_layout(size, n_shard)
            if pad * 8 > size:
                continue  # padding beyond 1/8: not worth the wire
            shard_set.append(i)
            layouts.append((size, shard_len, np.dtype(leaf.dtype)))
    if shard_set:
        return ExportPlan(tuple(shard_set), n_shard, tuple(layouts))
    return ExportPlan(pieces=tuple(
        (i, depth) for i, depth in sorted((stacked or {}).items())
        if depth > 1 and own_key(names[i], leaves[i])))


def _declare_shard_keys(registry, names, leaves, plan: ExportPlan,
                        stale) -> dict:
    """Realise a changed plan in the registry: declare each shard
    leaf's and each piece leaf's subrange keys, in flatten order (every
    worker flattens the same tree, so the declared keys agree across
    workers), and the parent name as the production-order anchor all of
    a leaf's shards share; then free the names in ``stale`` the plan no
    longer holds (a leaf resized, the knob flipped, the mesh changed:
    dead keys must not skew later least-loaded assignments). Returns
    leaf index -> sizing, subrange names and parent context."""
    from ..core.types import DataType

    info: Dict[int, dict] = {}
    declared: set = set()
    for i, (size, shard_len, dt) in zip(plan.shard_set, plan.layouts):
        dtype = DataType.from_np(dt)
        ctxs = registry.declare_shards(
            names[i], shard_len * dt.itemsize, plan.n_shard, dtype)
        info[i] = {"n": plan.n_shard, "shard_len": shard_len, "size": size,
                   "dtype": dt, "names": [c.name for c in ctxs],
                   "parent": registry.declare(names[i], dtype)}
    for i, depth in plan.pieces:
        ctxs = registry.declare_shards(
            names[i], leaves[i].nbytes // depth, depth,
            DataType.from_np(np.dtype(leaves[i].dtype)))
        info[i] = {"n": depth, "names": [c.name for c in ctxs]}
    for entry in info.values():
        declared.update(entry["names"])
    for name in stale - declared:
        registry.free(name)
    return info


def make_ps_train_step(
    loss_fn: Callable,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    axis: str = DP_AXIS,
    compression: Optional[dict] = None,
    min_compress_bytes: Optional[int] = None,
    rowsparse_params: Optional[Tuple[str, ...]] = None,
    device_compress: Optional[bool] = None,
    sharded_apply: Optional[bool] = None,
    local_shard_export: Optional[bool] = None,
):
    """Three-stage COMPUTE → PUSH → UPDATE train step for the DCN PS
    path — the reference's actual architecture (docs/architecture.md
    "General Workflow") with BOTH of its pipeline overlaps: the compiled
    program reduces gradients over the local slice (ICI psum == the NCCL
    ReduceScatter tier); gradients exit to host as outputs of that
    program, copied by the runtime; the PS client push_pulls each
    declared tensor across workers in priority order (the PUSH/PULL
    stages over DCN); and the optimizer update is applied per leaf from
    the completion-ordered drain, so UPDATE(k) overlaps PULL(k+1)
    (servers only sum — the update stays on the worker).

    The way off the chip is one: every gradient leaf is an OUTPUT of a
    backward program, on every topology, and no program holds a host
    callback (so the persistent compile cache serves them). A leaf on a
    whole-leaf key (dense or host-compressed), a bucket member
    (sub-BYTEPS_FUSION_BYTES), a rowsparse or device-compressed leaf is
    one replicated array; a shard leaf of the export plan
    (``_export_plan``; ``local_shard_export`` below) is one flat
    1/local_size shard a device. ``copy_to_host_async()`` is issued on
    each right after dispatch, in flatten order and, within a shard
    leaf, in mesh-device order; the train thread's claim loop takes
    each with ``np.asarray`` and submits it, a shard under its subrange
    key at its parent's production-order priority. With no shard leaf
    the program that runs is ``grad_fn``, else the reduce-scatter
    backward (``_scatter_backward``). (On the v5e a program output
    reaches the host at 3.0-4.5 GB/s, a host callback's operand at
    0.4-1.0: PERF.md section 6, PRs 24, 25 and 27.)

    How MANY programs the backward is depends on how the loss is
    written. A program's outputs exist for the host only when the
    program has ended, so behind one program the chip idles while every
    byte crosses. A loss written as a chain of links (``ops/chain.py``:
    an embedding, a run of rematerialised layers, a head) is found
    behind whatever closure wraps it (the chain registers itself during
    ``grad_fn``'s first trace at a batch's shapes) and, where the plan shards nothing and
    no host codec is set, its backward runs as one program a link and a
    layer, last first (``_cut_backward``: the same mathematics and
    FLOPs, all dispatched at once). The train thread waits for them in
    the order they end and claims what each hands over while the next
    runs: a whole leaf as above (a run of ONE layer's leaves are such:
    the piece that is its leaf), a deeper run's stacked leaf as ``depth``
    PIECES (layer ``j``'s slice under a subrange key of its own, pulled
    into its slice of one leaf-sized slot; the leaf is imported and
    applied whole when its last piece has landed). Bucket members and
    row-sparse leaves are claimed once the last program has ended, in
    flatten order, so buckets, digests and keys are the one-program
    step's; so are the bytes pushed. A leaf that lies under SEVERAL
    links (an embedding that is also the head; read off the links' keys)
    is summed on the device: each program that reads it but the last to
    run keeps its term on the chip for the next, and the last hands the
    sum over, once, on the leaf's one-program key. Nothing selects this
    but what the step observes (a chain whose links cover the tree's
    leaves, remat on its runs, a plan without shards, a running
    scheduler, no host codec); counters ``export/backward_programs``,
    ``export/piece_bytes``, ``export/under_backward_bytes``,
    ``export/shared_leaves`` and ``export/shared_carry_bytes`` say how
    often it engages.

    ``sharded_apply`` (BYTEPS_SHARDED_APPLY, default on): split the
    monolithic apply jit into per-leaf donated partial updates
    (jax.optim.make_sharded_apply) issued the moment each pull lands.
    Transforms that are not per-leaf separable (global-norm clipping)
    are detected at build time and keep the fused apply; the fused path
    is also the arena-release barrier owner, so with sharding on the
    lease release defers to the next step's start instead of a
    block_until_ready at the end of this one. Failure contract: per-leaf
    updates donate INCREMENTALLY during the drain, so a PS error
    mid-round leaves params/opt_state partially invalidated on backends
    that honor donation — treat a raised step like the donated fused
    apply's mid-apply failure and restart from a checkpoint rather than
    retrying with the same trees.

    ``local_shard_export`` (BYTEPS_LOCAL_SHARD_EXPORT, default on):
    the hierarchical exchange — reduce-scatter → push shard → update
    shard → all-gather. Eligible leaves are reduce-SCATTERED instead of
    psum'd, so each local device holds and exports only its own flat
    1/local_size shard (a per-device program output); each shard rides
    its own PS key, spread across servers by the registry's
    load-balanced assignment; the completion-ordered drain imports
    shard k back into the device that owns it (1/local_size H2D per
    device instead of the full aggregated leaf to every device), runs
    the optimizer update on the shard alone (jax/optim.py
    make_shard_apply; shard-separability verified by probe), and a
    jitted all-gather rebuilds the replicated params and state.
    Per-device D2H/H2D and per-key wire bytes divide by local_size.
    Which leaves shard is ``_export_plan``'s rule; the others keep the
    whole-leaf path — numerics bitwise identical either way.

    ``compression``: string-kwargs dict for the codec registry (e.g.
    ``{"compressor": "onebit", "ef": "vanilla"}``) — gradients then ride
    the wire compressed with the C++ server decompress/sum/recompress
    mirror (reference: BASELINE config 4 path; server.cc:92-118). EF and
    momentum state live worker-side per tensor. ``min_compress_bytes``
    gates small tensors onto the dense path (BYTEPS_MIN_COMPRESS_BYTES).

    ``device_compress`` (default on whenever ``compression`` is set and
    the scheduler is running): run the momentum->EF->codec stack inside
    the compiled step (jax/device_compression.py), so the device->host
    hop carries the wire-sized payload — SURVEY §7's "the D2H moves
    *compressed* bytes" — instead of dense f32 that is then compressed
    in numpy; the pull reply is decompressed back on device. EF state
    lives on device and, like the host path's, resets on
    suspend/resume. Set False to force the host-numpy codec tier.

    ``rowsparse_params``: substrings of gradient names (e.g.
    ``("embed",)``) whose 2D gradients travel row-sparse — only nonzero
    rows on the push wire (bps.push_pull_rowsparse; embedding gradients
    are mostly zero rows). Takes precedence over ``compression`` for the
    matching leaves.

    ``loss_fn`` may return ``(loss, stats)`` (``_loss_and_stats``): the
    statistics are outputs of the backward beside the loss and are in
    the registry's counters when the step returns.

    Returns ``step(params, opt_state, batch) -> (params, opt_state, loss)``;
    reads the PS client + registry from the global state at call time, so
    it composes with suspend/resume.
    """
    import numpy as np

    from ..core.state import get_state

    import time as _time

    # registry is keyed to the client that created it: suspend/resume
    # replaces state.ps_client, and a cached registry would then push on a
    # destroyed native handle with a stale worker count
    comp_state = {"registry": None, "client": None, "device": None}
    # the export plan as last realised ("key": the gradient tree and
    # the plan): the backward it runs and how many of its outputs it pins
    # row-major (``_row_major_outputs``), leaf index -> sizing/names of
    # the shard or piece leaves (their declared subrange names are freed
    # when the plan changes); "tag" counts this closure's PS rounds.
    # By the shapes of ``params`` and ``batch`` (``_shapes``), as a jit
    # caches its traces: "chains", the loss's chain as collected for
    # them (None: no chain, or more than one), and "cuts", the programs
    # of the plan's cut backward (``_CutBackward``) built from it
    plan_cache: dict = {"key": None, "backward": None, "pinned": 0,
                        "tag": 0, "shard_info": {}, "chains": {},
                        "cuts": {}}
    # sharded-apply build cache (keyed by params+opt_state structure;
    # sa None = transform not separable -> fused apply; ssa None =
    # not SHARD-separable -> gather gradients, full-leaf apply)
    sa_state: dict = {"sa": None, "key": None, "ssa": None,
                      "ssa_key": None, "gather": None}
    # deferred arena releases from sharded rounds: (leases, imported)
    pending: list = []
    # cross-barrier pipelining state (BYTEPS_CROSS_BARRIER): "carry" is
    # the previous step's still-in-flight tail — per-leaf waiters plus
    # the exact (param, param_parts, shared) base their stale apply
    # must chain from; "over" maps leaf index -> (new_param,
    # new_pparts) produced by a carried apply, consumed as the base of
    # that leaf's NEXT apply (or folded in by ``flush``); "par" is the
    # step parity that keeps two live rounds of one key on disjoint
    # arena slots. All touched from the step thread only.
    xb_state: dict = {"carry": None, "over": {}, "par": 0, "seq": 0}

    loss_and_stats = _loss_and_stats(loss_fn)
    grad_fn = _psum_backward(loss_and_stats, mesh, axis)
    # what a shard leaf's imported shards assemble into
    shard_sharding = NamedSharding(mesh, P(axis))

    def _finish(params, opt_state, pair):
        """The step's result: the statistics go to the registry (the
        step has waited for its gradients, so they are there), the loss
        to the caller."""
        loss, stats = pair
        _fold_stats(stats)
        return params, opt_state, loss

    def apply_updates_fn(params, opt_state, grads):
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    apply_fn = jax.jit(apply_updates_fn, donate_argnums=(0, 1))

    def step(params, opt_state, batch):
        state = get_state()
        client = state.ps_client
        shapes = _shapes((params, batch))
        if shapes not in plan_cache["chains"]:
            # the loss's chain, if it is written as one: ``grad_fn``'s
            # FIRST trace at these shapes, under the collector a called
            # chain registers with. A loss with no chain pays nothing
            # here (the ledger's lowering, the layout look and the run
            # below are served from that trace). At a chain's first
            # call the trace is abandoned (a cut step never runs
            # ``grad_fn``: its whole trace and lowering would be set-up
            # spent on a program that is not run) and the chain's calls
            # are counted over the loss's forward alone. One chain,
            # called once, is a loss that can be cut; anything else
            # runs as one program. Collected anew for new shapes (an
            # epoch's last batch), as the one program is traced anew: a
            # loss may build its chain from what it sees of the batch.
            with chain_mod.collecting(first=True) as found:
                grad_fn.trace(params, batch)
            if found:
                with chain_mod.collecting() as found:
                    jax.eval_shape(loss_and_stats, params, batch)
            plan_cache["chains"][shapes] = \
                found[0] if len(found) == 1 else None
        # drain the previous sharded round's deferred arena releases
        # FIRST: the imported arrays' readiness proves the host staging
        # was consumed (their H2D completed), and releasing before this
        # round's checkouts keeps the steady state conflict-free — the
        # old end-of-step block_until_ready barrier, moved off the
        # critical path (by now the wait is ~zero)
        if pending:
            try:
                for pl, arrs in pending:
                    try:
                        jax.block_until_ready([a for a in arrs
                                               if a is not None])
                    except Exception:  # noqa: BLE001 - failed imports
                        # surfaced step N's async failure here at step
                        # N+1's start: never recycle the slots, and
                        # never re-raise the SAME failure on every
                        # later call of this closure
                        for lease in pl:
                            lease.abandon()
                        continue
                    for lease in pl:
                        lease.release()
            finally:
                del pending[:]
        if client is None:
            loss, grads = grad_fn(params, batch)
            params, opt_state = apply_fn(params, opt_state, grads)
            return _finish(params, opt_state, loss)
        # who burned the CPU, by thread, in the BYTEPS_TRACE_ON window
        # only (a reading takes milliseconds): one reading here and one
        # after end_step, both outside the report's compute_ms and
        # drain_ms, and one table over the whole step between them
        by_thread = state.tracer is not None and state.tracer.active()
        cpu_at = tracing.thread_cpu_ms() if by_thread else None
        # per-step pipeline profile (core/metrics.py): the scheduler's
        # stage threads feed samples into this builder; end_step below
        # closes it into the StepReport ring (+ stall diagnosis when
        # BYTEPS_STALL_DIAG=1). None when metrics are off.
        prof = state.profiler.begin_step()
        # the round's tag: the ``step`` argument of every span of this
        # step
        plan_cache["tag"] += 1
        tag = plan_cache["tag"]
        if prof is not None:
            prof.round_tag = tag
        # names/shapes come from the params tree (value_and_grad gives
        # gradients the identical structure), so the whole export plan
        # exists BEFORE the backward is dispatched
        paths, treedef = jax.tree_util.tree_flatten_with_path(params)
        names, p_leaves = [], []
        for path, leaf in paths:
            names.append("grad/" + "/".join(
                str(getattr(k, "key", getattr(k, "idx", k)))
                for k in path))
            p_leaves.append(leaf)
        # ---- step efficiency ledger (core/ledger.py): register this
        # plan's cost model ONCE per gradient-tree shape — XLA cost
        # analysis of the compiled grad + apply units (lowering only:
        # nothing executes, donated args stay live) plus the plan's
        # ideal exchange bytes (each leaf crosses the wire once each
        # way), so end_step prices every step in MFU / roofline /
        # wire-efficiency terms. A backend without a cost model
        # registers the wire sizes alone (MFU stays None, never 0).
        # ``cut``: the programs of a cut backward, whose costs
        # ``_pin_cut_outputs`` added up; else ``grad_fn`` is lowered.
        ledger = getattr(state, "ledger", None)

        def register_cost(cut=None):
            if ledger is None or not ledger.enabled:
                return
            cost_key = (treedef, cut is not None, tuple(
                (tuple(np.shape(pl)), str(getattr(pl, "dtype", "")))
                for pl in p_leaves))
            # keyed on the LEDGER INSTANCE too: suspend/resume replaces
            # state.ledger, and a plan-key-only cache would leave the
            # fresh ledger with no cost model (post-resume MFU None)
            if (plan_cache.get("cost_key") == cost_key
                    and plan_cache.get("cost_ledger") is ledger):
                return
            plan_cache["cost_key"] = cost_key
            plan_cache["cost_ledger"] = ledger
            from ..core import ledger as ledger_mod
            flops = acc_bytes = None
            for part in (cut.cost if cut is not None
                         else ledger_mod.jit_cost(grad_fn, params, batch),
                         ledger_mod.jit_cost(apply_fn, params,
                                             opt_state, params)):
                if part:
                    if part.get("flops"):
                        flops = (flops or 0.0) + part["flops"]
                    if part.get("bytes_accessed"):
                        acc_bytes = (acc_bytes or 0.0) \
                            + part["bytes_accessed"]
            ledger.register_step_cost(
                flops=flops, bytes_accessed=acc_bytes,
                ideal_wire_bytes=2 * sum(
                    int(getattr(pl, "nbytes", 0))
                    for pl in p_leaves),
                source="xla" if flops else "none")

        use_device = (compression is not None
                      and device_compress is not False
                      and state.scheduler is not None)
        if use_device:
            register_cost()
            with tracing.span(tracing.STEP_DISPATCH, step=tag):
                loss, grads = grad_fn(params, batch)
            grads = _device_compressed_round(
                state, client, comp_state, compression,
                min_compress_bytes, rowsparse_params, names,
                jax.tree.leaves(grads), treedef)
            if prof is not None:
                # device tier: the round is monolithic (compute + wire
                # inside one helper), so compute_ms covers through the
                # round and the apply is the tail; overlap_frac must
                # price as None — export_done lands AFTER the wire
                # here, so spans would fabricate "perfect overlap"
                prof.monolithic = True
                prof.mark("export_done")
                prof.mark("drain_done")
            params, opt_state = apply_fn(params, opt_state, grads)
            state.profiler.end_step(prof, leaves=len(names))
            return _finish(params, opt_state, loss)
        # ---- training-health collection (core/health.py,
        # BYTEPS_HEALTH): per-leaf gradient statistics accumulate off
        # the drain as each pulled aggregate lands; the param-norm
        # program (one tiny jit, len(names) floats D2H) feeds the
        # update-to-param ratios. Host tier only — the
        # device-compressed round never materializes the aggregate
        # host-side, so its health fields stay None, never a wrong 0.
        hplane = getattr(state, "health", None)
        # prof gates too: the detector/guard run from end_step's
        # observer hook, so without an open step report the collection
        # would be cost with no consumer (HealthPlane also refuses to
        # arm under BYTEPS_METRICS=0 — this is the per-step mirror)
        hc = hplane.begin_collect(len(names)) \
            if hplane is not None and prof is not None else None
        if hc is not None:
            pnorm_key = plan_cache.get("pnorm_key")
            # identity-or-equality: PyTreeDef.__ne__ rejects None
            if pnorm_key is None or pnorm_key != treedef:
                def _pnorms(leaves):
                    return jnp.sqrt(jnp.asarray(
                        [jnp.sum(jnp.square(x.astype(jnp.float32)))
                         for x in leaves]))
                plan_cache["pnorm_fn"] = jax.jit(_pnorms)
                plan_cache["pnorm_key"] = treedef
            try:
                hc.param_norms_dev = plan_cache["pnorm_fn"](
                    list(p_leaves))
            except Exception:  # noqa: BLE001 - ratios degrade to None
                hc.param_norms_dev = None
        # chaos harness: BYTEPS_CHAOS_NAN_LEAF poisons one matching
        # leaf's push mid-run (see _chaos_nan_poison)
        chaos_nan = os.environ.get("BYTEPS_CHAOS_NAN_LEAF") or None
        # ---- host tier: dense D2H, codecs in numpy ----
        reg = None
        mcb = min_compress_bytes
        if mcb is None:
            mcb = getattr(state.config, "min_compress_bytes", 0)
        if compression is not None:
            if comp_state["client"] is not client:
                from ..server.compressed import CompressedRegistry
                comp_state["registry"] = CompressedRegistry(
                    client, state.config.num_workers, compression, mcb)
                comp_state["client"] = client
            reg = comp_state["registry"]
        # one submit-as-ready loop for all three transports: dense or
        # compressed partitions enter the priority-scheduled pipeline
        # (compressed ones through COMPRESS/DECOMPRESS stages,
        # operations.cc:199-204); the no-scheduler fallbacks overlap
        # on a pool / run blocking.
        import byteps_tpu as bps

        # Persistent host staging (core/arena.py, the reference's
        # cpubuff discipline): result slots and fused-bucket concat slots
        # check out of the arena instead of np.empty per step; every
        # lease is released only after the
        # imports below complete (or abandoned on error — correctness
        # never depends on a slot surviving).
        arena = state.arena
        leases: list = []

        def checkout(key, nbytes, dtype, tag=None):
            lease = arena.checkout(key, nbytes, tag=tag)
            leases.append(lease)
            return lease.array(dtype)

        # export-plane instruments (registered every round so they are
        # present in the snapshot even when no leaf shards — the docs
        # schema guard runs a dense whole-leaf step): whole-leaf
        # exports are one device's replicated buffer crossing D2H, so
        # they account to device 0; shard exports account to the
        # device that owns the shard. The shard A/B's hard proof is
        # the ratio between these per-device counters.
        metrics = state.metrics
        exp_shard_ctr = metrics.counter("export/shard_bytes")
        exp_whole_ctr = metrics.counter("export/whole_bytes")
        exp_dev0_ctr = metrics.counter("export/device_bytes/0")
        # outputs this step's backward hands over under a pinned layout,
        # and the bytes the train thread re-laid all the same (a claimed
        # array that was not C-contiguous: expected 0)
        exp_pinned_ctr = metrics.counter("export/pinned_layout_leaves")
        exp_relayout_ctr = metrics.counter("export/host_relayout_bytes")
        # how often the cut backward engages: the programs this step's
        # backward ran as (1 where nothing is cut), the bytes that left
        # as pieces and the bytes whose submit ended before the
        # backward did (over the step's bytes: the share of the export
        # that is hidden under the chip's own work)
        exp_programs_ctr = metrics.counter("export/backward_programs")
        exp_piece_ctr = metrics.counter("export/piece_bytes")
        exp_under_ctr = metrics.counter("export/under_backward_bytes")
        # the leaves whose gradient was summed over several programs on
        # the chip (a leaf under several links of the chain: a tied
        # embedding) and the bytes of their terms held between programs
        exp_shared_ctr = metrics.counter("export/shared_leaves")
        exp_carry_ctr = metrics.counter("export/shared_carry_bytes")
        ag_hist = metrics.histogram("step/allgather_us")

        # time-to-first-push: wall from the backward's dispatch to the
        # first submission entering the scheduler (telemetry:
        # export_ttfp_ms)
        round_t0 = _time.perf_counter()
        first_push = [None]

        # bytes submitted so far: what has left by ``backward_done`` is
        # the step's ``export/under_backward_bytes``
        sent = [0]

        def mark_first_push():
            if first_push[0] is None:
                first_push[0] = _time.perf_counter() - round_t0

        def submit_sparse(name, h2d, out_dtype):
            from .. import _rowsparse_submit
            mark_first_push()
            sent[0] += h2d.nbytes
            handle = state.handles.allocate(name)
            obuf = checkout(f"{name}:out", h2d.size * 4, np.float32)
            _rowsparse_submit(state, name,
                              h2d.astype(np.float32, copy=False),
                              True, handle, out=obuf)
            return (lambda: state.handles.wait_and_clear(
                handle.id).astype(out_dtype, copy=False)), handle

        def submit(name, flat, priority=None, tag=None, out=None):
            """Returns (finish, notifier): ``finish()`` yields the
            reduced array (non-blocking once ``notifier`` — a Handle
            or Future with add_done_callback, or None for an already
            complete result — has fired). ``out``: where the dense
            scheduled pull lands, in place of a slot of the key's own
            (a piece's slice of its leaf's slot)."""
            if chaos_nan is not None:
                flat = _chaos_nan_poison(
                    chaos_nan, name, flat,
                    prof.step if prof is not None else 0)
            mark_first_push()
            sent[0] += flat.nbytes
            if reg is not None:
                flat = flat.astype(np.float32, copy=False)
                if state.scheduler is not None:
                    obuf = checkout(f"{name}:out", flat.nbytes,
                                    np.float32, tag=tag)
                    hd = reg.push_pull_async(state, name, flat, True,
                                             priority=priority, out=obuf)
                    return (lambda: bps.synchronize(hd),
                            state.handles.get(hd))
                fut = _comp_pool().submit(
                    reg.push_pull, state, name, flat, True)
                return fut.result, fut
            if state.scheduler is not None:
                # carry-eligible keys alternate arena slots by step
                # parity: with cross-barrier staleness the step-k slot
                # can still be awaiting its pull when step k+1 checks
                # the same key out, and a conflicting checkout would
                # fall back to a fresh allocation every step
                okey = (f"{name}:out~x{xb_par}"
                        if name in xb_carry_names else f"{name}:out")
                obuf = out if out is not None else checkout(
                    okey, flat.nbytes, flat.dtype, tag=tag)
                hd = bps.push_pull_async(flat, name, average=True,
                                         priority=priority, out=obuf)
                return (lambda: bps.synchronize(hd),
                        state.handles.get(hd))
            from ..server.client import ps_round_trip
            obuf = checkout(f"{name}:out", flat.nbytes, flat.dtype)
            res = ps_round_trip(state, name, flat, average=True,
                                out=obuf)
            return (lambda: res), None

        def submit_shard(i, dev, flat):
            """Shard-side submit (on the train thread's claim loop):
            device ``dev``'s 1/local_size shard of leaf ``i``
            rides its own subrange key at the PARENT leaf's
            production-order priority (all shards of one leaf are one
            production event), with its own per-shard arena result
            slot (tag="shard" in the arena counters)."""
            from ..server.client import get_or_init_ctx
            info = plan_cache["shard_info"][i]
            name = info["names"][dev]
            with tracing.span(tracing.EXPORT_SUBMIT, tid=name, step=tag,
                              leaf=i, dev=dev, bytes=flat.nbytes,
                              contiguous=flat.flags.c_contiguous) as sp:
                ctx = get_or_init_ctx(state, name, flat)
                sp.set(key=ctx.declared_key,
                       partitions=len(ctx.partitions))
                pr = state.scheduler.production_priority(
                    ctx, parent=info["parent"])
                exp_shard_ctr.inc(flat.nbytes)
                metrics.counter(f"export/device_bytes/{dev}").inc(
                    flat.nbytes)
                return submit(name, flat, priority=pr, tag="shard")

        # Bucket fusion (BYTEPS_FUSION_BYTES; the group-push cure):
        # per-key cost (scheduler admission, handle, two syscall
        # round-trips, server queue hop) is flat, so sub-threshold
        # leaves — biases, norms, small projections — fuse into one
        # concatenated key per dtype run and are sliced back after
        # the round. The bucket name is a content-stable digest of
        # (member names, sizes): every worker flattens the same tree
        # in the same order, so all workers aggregate the same
        # bucket; a changed model topology changes the digest and
        # cleanly declares a new key. Codec granularity for a fused
        # bucket is the bucket (matching the reference, where the
        # codec unit is the partition, not the layer).
        #
        # Interaction rules:
        # - bucket cap <= partition_bytes: a bucket must stay ONE
        #   key, or the partitioner re-splits it and re-adds the
        #   round trip fusion exists to remove;
        # - with compression on and min_compress_bytes > 0, only
        #   sub-mcb leaves fuse and the bucket stays < mcb, so
        #   tensors the gate kept full-precision (biases, norms)
        #   are NOT quantized via the fused key (mcb == 0 means the
        #   user asked for everything compressed — buckets too);
        # - sub-fusion leaves never shard: a bucket is a cross-leaf
        #   artifact of whole leaves, concatenated on the host.
        fusion = getattr(state.config, "fusion_bytes", 0)
        bucket_cap = min(4 << 20,
                         getattr(state.config, "partition_bytes",
                                 4 << 20))
        if reg is not None and mcb > 0:
            fusion = min(fusion, mcb)
            bucket_cap = min(bucket_cap, mcb - 1)
        waiters = []   # (slot_or_slots, finisher, notifier)
        bucket: list = []  # [(slot, name, flat_f-contig host array)]
        bucket_bytes = 0

        def flush_bucket():
            nonlocal bucket, bucket_bytes
            if not bucket:
                return
            if len(bucket) == 1:
                slot, name, h = bucket[0]
                waiters.append((slot, *submit(name, h.reshape(-1))))
            else:
                import hashlib
                digest = hashlib.sha1(";".join(
                    f"{n}:{h.size}" for _, n, h in bucket)
                    .encode()).hexdigest()[:12]
                # concatenate into the bucket's PERSISTENT arena
                # slot (np.concatenate would allocate the fused
                # buffer fresh every step). With compression on the
                # wire is f32, so fill as f32 and skip the astype
                # copy submit() would otherwise make.
                bdt = np.dtype(np.float32) if reg is not None \
                    else bucket[0][2].dtype
                total = sum(h.size for _, _, h in bucket)
                fused = checkout(f"fused/{digest}:in",
                                 total * bdt.itemsize, bdt)
                off = 0
                for _, _, h in bucket:
                    fused[off:off + h.size] = h.reshape(-1)
                    off += h.size
                slots = [s for s, _, _ in bucket]
                sizes = [h.size for _, _, h in bucket]
                w, notifier = submit(f"fused/{digest}", fused)

                def finish(w=w, sizes=sizes):
                    out = w()
                    outs = np.split(out, np.cumsum(sizes)[:-1])
                    return outs

                waiters.append((slots, finish, notifier))
            bucket, bucket_bytes = [], 0

        # ---- the export plan (``_export_plan``): which leaves leave as
        # per-device shards; realised when the tree or the plan changes
        ch = plan_cache["chains"][shapes]
        link_leaves = _chain_leaves(ch, params, paths) \
            if ch is not None else None
        # the stacked leaves of the chain's runs, by depth
        stacked = {} if link_leaves is None else {
            i: ln.depth for k, ln in enumerate(ch.links)
            if isinstance(ln, chain_mod.Run) for i in link_leaves[k]}
        plan = _export_plan(
            names, p_leaves, mesh=mesh, axis=axis, fusion_bytes=fusion,
            shard_min_bytes=getattr(state.config, "shard_min_bytes", 65536),
            local_shard=local_shard_export if local_shard_export is not None
            else getattr(state.config, "local_shard_export", True),
            rowsparse_params=rowsparse_params, host_codec=reg is not None,
            scheduler_running=state.scheduler is not None,
            stacked=stacked)
        shard_set, n_shard = plan.shard_set, plan.n_shard
        # the leaves on a key of their own leave the chip in the wire's
        # order, a piece too (bucket members are copied into their
        # bucket's slot anyway, a shard is flat; a run's leaf the plan
        # keeps whole is put together on the host: its layers' outputs
        # come as they come)
        own = {i for i, pl in enumerate(p_leaves)
               if i not in shard_set and getattr(pl, "nbytes", 0) >= fusion}
        if plan.pieces:
            # (a run of ONE layer hands its leaves over whole, from its
            # one program: they ride their own keys like a link's)
            own -= {i for i, depth in stacked.items() if depth > 1} \
                - dict(plan.pieces).keys()
        if plan_cache["key"] != (treedef, plan):
            stale = {n for info in plan_cache["shard_info"].values()
                     for n in info["names"]}
            plan_cache["shard_info"] = _declare_shard_keys(
                state.registry, names, p_leaves, plan, stale)
            # another plan's cut programs are dead
            plan_cache["cuts"] = {}
            if not plan.pieces:
                backward = _scatter_backward(
                    loss_and_stats, mesh, axis, shard_set, len(names)) \
                    if shard_set else grad_fn
                plan_cache["backward"], plan_cache["pinned"] = \
                    _row_major_outputs(backward, (params, batch),
                                       sorted(own), mesh)
            plan_cache["key"] = (treedef, plan)
        if plan.pieces and shapes not in plan_cache["cuts"]:
            # the cut programs run the links of the chain collected at
            # THESE shapes
            cut = _cut_backward(ch, mesh, axis, link_leaves)
            _pin_cut_outputs(cut, params, batch, own, mesh, axis)
            plan_cache["cuts"][shapes] = cut
        register_cost(plan_cache["cuts"][shapes] if plan.pieces else None)

        # ---- sharded-apply build (cached per tree structure) ----
        sharded_cfg = sharded_apply if sharded_apply is not None \
            else getattr(state.config, "sharded_apply", True)
        sa = None
        if sharded_cfg:
            skey = (treedef, jax.tree.structure(opt_state))
            if sa_state["key"] != skey:
                from .optim import make_sharded_apply
                sa_state["sa"] = make_sharded_apply(tx, params, opt_state)
                sa_state["key"] = skey
            sa = sa_state["sa"]  # None -> not separable -> fused apply
        # shard-mapped apply for shard-exported leaves: update runs on
        # the 1/local_size shard each device just imported, then the
        # gather jit rebuilds replicated params/state. ssa None (not
        # shard-separable, e.g. block-norm scaling) -> the drain
        # gathers the gradient instead and applies full-leaf.
        ssa = None
        if shard_set and sa is not None:
            ssa_key = (sa_state["key"], n_shard)
            if sa_state["ssa_key"] != ssa_key:
                from .optim import make_shard_apply
                sa_state["ssa"] = make_shard_apply(
                    tx, params, opt_state, mesh, axis, n_shard, base=sa)
                sa_state["ssa_key"] = ssa_key
            ssa = sa_state["ssa"]
        if shard_set and sa_state["gather"] is None:
            from .optim import LeafGather
            sa_state["gather"] = LeafGather(mesh, axis)

        # ---- cross-barrier bounded staleness (BYTEPS_CROSS_BARRIER /
        # BYTEPS_STALENESS, the PR 16 tentpole): instead of barriering
        # on the full drain, the step releases once the front-of-model
        # leaves (a flatten-order prefix — what the next forward reads
        # first) have imported; the tail leaves' PULL→H2D→UPDATE is
        # carried across the step boundary and drained after the NEXT
        # step's export, overlapping its compute. Carry-eligible leaves
        # are the plain dense whole-leaf keys only: bucket members,
        # shard subranges, rowsparse and host-compressed keys keep the
        # synchronous drain (their codec/assembly state is not
        # round-windowed). Requires the per-leaf sharded apply (the
        # carried update is a single-leaf chain) and the scheduler's
        # staleness credit (window > 0 implies fused pushpull, whose
        # replies are round-stamped server-side).
        xb_window = getattr(state.scheduler, "xb_window", 0) \
            if state.scheduler is not None else 0
        xb_on = bool(xb_window > 0 and sa is not None and reg is None)
        xb_over = xb_state["over"]
        # step ordinal for staleness-lag attribution: the carry records
        # the seq it was created at, the drain reports how many step
        # boundaries the tail actually crossed (1 at steady state)
        xb_state["seq"] += 1
        xb_carry_set: set = set()
        if xb_on:
            xb_state["par"] ^= 1
            shard_planned = set(shard_set) | dict(plan.pieces).keys()
            rel_n = max(1, (len(names) + 1) // 2)
            for i, nm in enumerate(names):
                if i < rel_n:
                    continue
                nb = getattr(p_leaves[i], "nbytes", 0)
                if nb == 0 or nb < fusion or i in shard_planned:
                    continue
                if rowsparse_params and any(s in nm
                                            for s in rowsparse_params):
                    continue
                xb_carry_set.add(i)
        xb_carry_names = {names[i] for i in xb_carry_set}
        xb_par = xb_state["par"]

        # ---- dispatch the backward: the scatter backward where the plan
        # shards leaves, the chain's programs where it has pieces, all at
        # once (``_dispatch_cut``), else ``grad_fn``
        cut = plan_cache["cuts"][shapes] if plan.pieces else None
        programs: list = []
        with tracing.span(tracing.STEP_DISPATCH, step=tag):
            if cut is None:
                loss, grads = plan_cache["backward"](params, batch)
            else:
                loss, programs = _dispatch_cut(cut, params, batch)
        # the train thread's two phases as spans: ``claim`` from here to
        # the export_done mark, ``drain`` from there to drain_done
        phase = tracing.span(tracing.STEP_CLAIM, step=tag).start()
        g_leaves = jax.tree.leaves(grads) if cut is None else []
        # per-leaf shard import state (BYTEPS_LOCAL_SHARD_EXPORT):
        # shard k of leaf i lands on the device that owns it the moment
        # its pull completes; when the last shard of a leaf lands, the
        # shards assemble into one P(axis)-sharded array and the
        # shard update + all-gather dispatch
        active_shard = plan_cache["shard_info"] if shard_set else {}
        shard_parts: Dict[int, list] = {}
        shard_left: Dict[int, int] = {}
        axis_devs = list(mesh.devices.flat)
        # a piece leaf's import state: its pieces are pulled into slices
        # of ONE leaf-sized arena slot, and the leaf lands whole when
        # the last of them has (the apply stays a leaf at a time)
        piece_info = plan_cache["shard_info"] if plan.pieces else {}
        piece_slot: Dict[int, np.ndarray] = {}
        piece_left: Dict[int, int] = {}

        def device_parts(leaf):
            by_dev = {s.device: s.data for s in leaf.addressable_shards}
            return [by_dev[d] for d in axis_devs]

        def claim_shards(i, name, parts):
            """A shard leaf: each device's flat shard is claimed and
            submitted (``submit_shard``), in mesh-device order."""
            shard_parts[i] = [None] * len(parts)
            shard_left[i] = len(parts)
            for dev, part in enumerate(parts):
                nb = part.nbytes
                with tracing.span(tracing.EXPORT_INGEST, tid=name, step=tag,
                                  leaf=i, dev=dev, bytes=nb,
                                  cause=f"out:{i}/{dev}"):
                    with tracing.span(tracing.EXPORT_MATERIALIZE, step=tag,
                                      leaf=i, bytes=nb):
                        h = np.asarray(part)
                    w = submit_shard(i, dev, h.reshape(-1))
                waiters.append((("shard", i, dev), *w))

        def in_wire_order(h, nb):
            """The guard: a backend that ignored the plan's pin. The
            copy is the train thread's, one core's, and is counted."""
            if h.flags.c_contiguous:
                return h
            exp_relayout_ctr.inc(nb)
            return np.ascontiguousarray(h)

        def claim_piece(i, j, leaf):
            """Layer ``j``'s piece of leaf ``i``, an output of layer
            ``j``'s program: claimed and submitted under its subrange
            key, at its own place in the production order; its pull
            lands in its slice of the leaf's slot."""
            from ..server.client import get_or_init_ctx
            info = piece_info[i]
            name, nb = info["names"][j], leaf.nbytes
            if i not in piece_slot:
                piece_slot[i] = checkout(
                    f"{names[i]}:out", p_leaves[i].nbytes,
                    np.dtype(leaf.dtype))
                piece_left[i] = info["n"]
            exp_whole_ctr.inc(nb)
            exp_dev0_ctr.inc(nb)
            exp_piece_ctr.inc(nb)
            with tracing.span(tracing.EXPORT_INGEST, tid=names[i], step=tag,
                              leaf=i, layer=j, bytes=nb,
                              cause=f"out:{i}/{j}"):
                with tracing.span(tracing.EXPORT_MATERIALIZE, step=tag,
                                  leaf=i, bytes=nb):
                    h = np.asarray(leaf)
                with tracing.span(tracing.EXPORT_SUBMIT, tid=name, step=tag,
                                  leaf=i, layer=j, bytes=nb,
                                  contiguous=h.flags.c_contiguous) as sp:
                    flat = in_wire_order(h, nb).reshape(-1)
                    ctx = get_or_init_ctx(state, name, flat)
                    sp.set(key=ctx.declared_key,
                           partitions=len(ctx.partitions))
                    w = submit(
                        name, flat,
                        priority=state.scheduler.production_priority(ctx),
                        out=piece_slot[i][j * flat.size:
                                          (j + 1) * flat.size])
            waiters.append((("piece", i, j), *w))

        def claim_leaf(i, name, leaf):
            """One whole leaf, in its turn: a shard leaf's per-device
            shards, a bucket member, or a leaf on a key of its own
            (dense or row-sparse)."""
            nonlocal bucket_bytes
            if i in out_shards:
                claim_shards(i, name, out_shards[i])
                return
            nb = leaf.nbytes
            exp_whole_ctr.inc(nb)
            exp_dev0_ctr.inc(nb)
            sparse = _route_rowsparse(name, leaf, state, rowsparse_params)
            if not sparse and nb < fusion:
                # bucket member: a cross-leaf artifact, submitted
                # by whichever later leaf flushes the bucket
                with tracing.span(tracing.EXPORT_BUCKET_MEMBER,
                                  step=tag, leaf=i, bytes=nb):
                    h = np.asarray(leaf)
                if bucket and (bucket[0][2].dtype != h.dtype
                               or bucket_bytes + nb > bucket_cap):
                    flush_bucket()
                bucket.append((i, name, h))
                bucket_bytes += nb
                return
            # a leaf on a key of its own: ``cause`` names the
            # program output it is
            with tracing.span(tracing.EXPORT_INGEST, tid=name,
                              step=tag, leaf=i, bytes=nb,
                              cause=f"out:{i}"):
                with tracing.span(tracing.EXPORT_MATERIALIZE,
                                  step=tag, leaf=i, bytes=nb):
                    # ready-or-wait for THIS leaf's transfer
                    h = np.asarray(leaf)
                if sparse:
                    flush_bucket()
                # a whole-leaf key does not close the bucket: its
                # members, and so its digest, depend on the tree
                # and the fusion size alone
                with tracing.span(tracing.EXPORT_SUBMIT, tid=name,
                                  step=tag, leaf=i, bytes=nb,
                                  contiguous=h.flags.c_contiguous) as sp:
                    h = in_wire_order(h, nb)
                    if sparse:
                        # non-f32 grads upcast for the wire, cast
                        # back
                        w = submit_sparse(name, h, h.dtype)
                    else:
                        w = submit(name, h.reshape(-1))
                        ctx = state.registry.get(name)
                        if ctx is not None:
                            # what the wire's sends name as their
                            # cause
                            sp.set(key=ctx.declared_key,
                                   partitions=len(ctx.partitions))
            waiters.append((i, *w))

        # what is claimed once the backward has ended, in flatten order:
        # every leaf of a one-program backward; of a cut one the bucket
        # members and the row-sparse leaves (a bucket's members, and so
        # its digest and key, are the one-program step's) and a run's
        # leaves the plan keeps whole
        late: Dict[int, Any] = {}

        # a cut backward's programs whose end has been seen, in the order
        # they end; ``backward_ended`` once the last one's has
        seen = [0, False]
        # the train thread's own CPU when the claim starts, and what of
        # it went into the waits for the backward since
        waited_cpu = [0.0, 0.0]

        def backward_wait(ready):
            at = _time.thread_time()
            ready.block_until_ready()
            waited_cpu[1] += _time.thread_time() - at

        def programs_ended(upto):
            """Wait for the programs up to ``upto``, each under a span
            of its own."""
            for n in range(seen[0], upto + 1):
                label, _, ready, outs = programs[n]
                with tracing.span(
                        tracing.STEP_BACKWARD_PROGRAM, step=tag, index=n,
                        links=label,
                        bytes=sum(leaf.nbytes for leaf in outs.values())):
                    backward_wait(ready)
            seen[0] = max(seen[0], upto + 1)

        def backward_ended():
            """The backward has ended on the device: the wait's span
            ends, ``backward_done`` is marked, and what has been
            submitted by now left under the backward. The mark's thread
            CPU is the claim's start and the waits for the backward, so
            that ``claim_thread_cpu_ms`` (from there to ``export_done``)
            is what the train thread worked in claiming whenever it
            claimed: behind one program the mark's own reading, on a
            cut step the claims under the backward too."""
            if not seen[1]:
                seen[1] = True
                wait.stop()
                if prof is not None:
                    prof.mark("backward_done")
                    prof.thread_cpu_marks["backward_done"] = sum(waited_cpu)
                exp_under_ctr.inc(sent[0])

        def claim_program(n):
            """What program ``n`` hands over and can leave at once: a
            layer's pieces, a dense leaf on a key of its own (a link's,
            or the whole leaf that a run of one layer's program hands
            over: the piece that is its leaf). Between
            two of them the train thread looks whether the LAST program
            has ended meanwhile (it need not wait for it: the claims
            before it take longer than a short last program), so that
            ``backward_done`` is the chip's, not the claim's."""
            _, layer, _, outs = programs[n]
            handed = list(outs.items())
            # a claimed output's place on the chip is free as soon as
            # the wire has its bytes: no reference is kept here
            outs.clear()
            for i, leaf in handed:
                if i in piece_info:
                    claim_piece(i, layer, leaf)
                elif layer is not None and stacked[i] > 1:
                    late.setdefault(i, _Layers([None] * len(
                        p_leaves[i]))).parts[layer] = leaf
                    continue
                elif leaf.nbytes < fusion or _route_rowsparse(
                        names[i], leaf, state, rowsparse_params):
                    late[i] = leaf
                    continue
                else:
                    claim_leaf(i, names[i], leaf)
                if not seen[1] and programs[-1][2].is_ready():
                    programs_ended(len(programs) - 1)
                    backward_ended()

        # start the D2H copies of the leaves now, all of them: a
        # one-program backward's in flatten order, a shard leaf's
        # per-device arrays in mesh-device order (a cut backward's
        # start in the claim loop, in the order its programs end; a
        # pure function of the plan either way: every worker issues and
        # claims them alike). What an np.asarray of an
        # output below can cost is the wait for ITS transfer and no
        # more: it returns a view of the buffer the runtime filled, in
        # the output's own dimension order, and that order is the
        # wire's, because the plan pinned every output that would have
        # come otherwise (``_row_major_outputs``; a shard is flat). It
        # never costs a copy: the train thread moves no leaf's bytes.
        # The TPU runtime works on the copies side by side (it de-tiles
        # each on host threads). A program's outputs exist for the host
        # only when the program has ended, so behind ONE program the
        # chip idles while the host's cores move every byte, and the
        # large copies finish close together, late in the claim; a
        # bounded window of copies in flight there does overlap the
        # PUSH with the transfers but slows the transfers by as much,
        # on one host's cores (PERF.md section 6, PR 25). Behind a CUT
        # backward the runtime moves program k's outputs while program
        # k + 1 runs, on cores the backward leaves idle, and the train
        # thread claims and submits them meanwhile (PERF.md section 6,
        # PR 42). A cut backward's copies are NOT all issued here: a
        # program's outputs start to cross when the program before it
        # has been seen to end (``copy_outputs`` in the claim loop
        # below), so at most the outputs being claimed and the next
        # program's cross side by side. Where the chip sets the pace
        # every program's copy is issued while the program still runs,
        # as before; where the host does (LFM2: 4.7 GB of gradients a
        # chip-second), all the later programs' outputs issued at once
        # cross together and land together, late, and the wire has
        # nothing to push meanwhile (PERF.md section 6, PR 44).
        out_shards: Dict[int, list] = {}
        for i, leaf in enumerate(g_leaves):
            if i in active_shard:
                out_shards[i] = device_parts(leaf)
                for part in out_shards[i]:
                    part.copy_to_host_async()
            elif hasattr(leaf, "copy_to_host_async"):
                leaf.copy_to_host_async()

        def copy_outputs(n):
            """Start the D2H copies of what program ``n`` of a cut
            backward hands over (the forward, program 0, hands nothing
            over)."""
            if n < len(programs):
                for leaf in programs[n][3].values():
                    leaf.copy_to_host_async()

        exp_pinned_ctr.inc(plan_cache["pinned"] if cut is None
                           else cut.outputs_pinned)
        exp_programs_ctr.inc(max(1, len(programs)))
        if cut is not None:
            exp_shared_ctr.inc(len(cut.shared))
            exp_carry_ctr.inc(sum(p_leaves[i].nbytes for i in cut.shared))

        imported: list = [None] * len(names)
        new_params: list = [None] * len(names)
        apply_parts: list = [None] * len(names)
        wait = tracing.span(tracing.STEP_BACKWARD_WAIT, step=tag)
        try:
            # the claim starts by waiting for the backward to end on the
            # device. ONE program (the loss is an output of it): the
            # first np.asarray below would have waited as long, and
            # from here on a materialize is a wait for a transfer and
            # nothing else. A cut backward: program by program in the
            # order they end, and what each hands over is claimed and
            # submitted while the next runs; the wait's span and
            # ``backward_done`` end where the LAST program has ended
            # (``backward_ended``), whatever the train thread claimed
            # meanwhile
            wait.start()
            waited_cpu[0] = _time.thread_time()
            if cut is None:
                backward_wait(loss[0])
                late.update(enumerate(g_leaves))
                backward_ended()
            for n in range(len(programs)):
                programs_ended(n)
                if n == len(programs) - 1:
                    backward_ended()
                copy_outputs(n + 1)
                claim_program(n)
            for i in sorted(late):
                claim_leaf(i, names[i], late[i])
            flush_bucket()
            if prof is not None:
                # every leaf is now off the device and submitted (each
                # np.asarray above blocked on ITS leaf): the compute +
                # export wall of this step's report
                prof.mark("export_done", thread_cpu=True)
                phase.set(behind_backward_ms=(
                    prof.marks["export_done"]
                    - prof.marks["backward_done"]) * 1e3)
            phase.stop()
            phase = tracing.span(tracing.STEP_DRAIN, step=tag).start()
            # ---- carried drain (BYTEPS_CROSS_BARRIER): the PREVIOUS
            # step's tail rounds land here, AFTER this step's backward
            # has been dispatched and its exports submitted — their
            # PULL wait overlaps this step's compute, which is the
            # whole point. Each carried apply chains from the exact
            # base captured at carry time (never the live opt_state,
            # which has moved on) via the non-donating apply_with, and
            # its result becomes this step's base for the same leaf
            # (``xb_over``). Health stats tap into THIS step's
            # collector: one tap per leaf per step at steady state, so
            # the per-round detectors see divergence within one step.
            prev_carry = xb_state["carry"]
            xb_state["carry"] = None
            xb_drained = 0
            xb_drain_ms = xb_lag = None
            if prev_carry is not None:
                t_xb = _time.perf_counter()
                try:
                    for (s, fin, _nt, bp, bpp, bsh) in \
                            prev_carry["entries"]:
                        piece = fin()
                        if hc is not None:
                            hc.leaf(s, piece)
                        arr = jax.device_put(
                            piece.reshape(np.shape(bp)))
                        npar, nparts = prev_carry["sa"].apply_with(
                            bp, bpp, bsh, arr)
                        xb_over[s] = (npar, nparts[0])
                        prev_carry["imported"].append(arr)
                except BaseException:
                    # a failed carried pull loses step k's update for
                    # this leaf: same contract as a mid-drain failure
                    # of the donated apply — abandon, surface, restart
                    # from a checkpoint
                    for lease in prev_carry["leases"]:
                        lease.abandon()
                    for (_s, _f, nt, *_rest) in prev_carry["entries"]:
                        if hasattr(nt, "id"):
                            state.handles.discard(nt.id)
                    raise
                centry = (prev_carry["leases"], prev_carry["imported"])
                pending.append(centry)

                def _xb_release(entry=centry):
                    try:
                        jax.block_until_ready([a for a in entry[1]
                                               if a is not None])
                    except Exception:  # noqa: BLE001 - failed imports:
                        for lease in entry[0]:  # never recycle
                            lease.abandon()
                        return
                    for lease in entry[0]:
                        lease.release()

                _release_pool().submit(_xb_release)
                metrics.counter("barrier/carry_drained").inc(
                    len(prev_carry["entries"]))
                xb_drained = len(prev_carry["entries"])
                xb_drain_ms = (_time.perf_counter() - t_xb) * 1e3
                xb_lag = xb_state["seq"] - prev_carry.get(
                    "step", xb_state["seq"] - 1)
            # param shapes, not gradient-output shapes: a shard-planned
            # leaf's program output is the flat padded sharded layout,
            # but everything imported/applied below is leaf-shaped
            shapes = [np.shape(pl) for pl in p_leaves]
            # Completion-ordered drain — IMPORT + UPDATE: issue the
            # async H2D device_put for each leaf THE MOMENT its pull
            # lands (XLA overlaps the import of tensor k with the DCN
            # PULL of tensor k+1 — the mirror of the export
            # above; reference: COPYH2D as its own pipeline stage,
            # core_loops.cc:620-648), and with the sharded apply, its
            # per-leaf optimizer update right behind it — UPDATE(k)
            # overlaps PULL(k+1), the tail of the COMPUTE/PUSH/UPDATE
            # pipeline.
            import queue as _queue

            ready: "_queue.Queue" = _queue.Queue()
            with tracing.span(tracing.APPLY_BEGIN, step=tag,
                              waiters=len(waiters)):
                for wi, (_, _, notifier) in enumerate(waiters):
                    if notifier is None:
                        ready.put(wi)
                    else:
                        notifier.add_done_callback(
                            lambda *_a, wi=wi: ready.put(wi))

                sa_round = sa.begin(opt_state) if sa is not None else None
            # per-leaf PULL→H2D→UPDATE drain spans (the ISSUE's
            # measurement of the import half of the pipeline): each
            # land() is one leaf's H2D issue + sharded-update dispatch
            h2d_hist = state.metrics.histogram("step/h2d_update_us")

            def land(s, piece):
                with tracing.span(tracing.APPLY_H2D_UPDATE, tid=names[s],
                                  step=tag, leaf=s) as sp:
                    if hc is not None:
                        # health tap: stats off the drain
                        hc.leaf(s, piece)
                    arr = jax.device_put(piece.reshape(shapes[s]))
                    imported[s] = arr
                    if sa_round is not None:
                        ov = xb_over.pop(s, None) if xb_over else None
                        if ov is not None:
                            # this leaf's previous round was carried:
                            # chain from the carried apply's result, not
                            # the (one-step-stale) tree slices
                            new_params[s], apply_parts[s] = \
                                sa.apply_with(ov[0], ov[1],
                                              sa_round.slice(s)[1], arr)
                        else:
                            new_params[s], apply_parts[s] = \
                                sa_round.apply(p_leaves[s], s, arr)
                dt = sp.t1 - sp.t0
                h2d_hist.record_seconds(dt)
                if prof is not None:
                    prof.stage_sample("H2D_UPDATE", dt)

            def land_shard(s, dev, piece):
                # import shard `dev` of leaf `s` onto the device that
                # owns it — 1/local_size of the H2D the whole-leaf
                # import moved, overlapped with the remaining pulls
                info = active_shard[s]
                parts = shard_parts[s]
                with tracing.span(tracing.APPLY_H2D_UPDATE, tid=names[s],
                                  step=tag, leaf=s, dev=dev) as sp:
                    if hc is not None:
                        # shard pieces sum into the leaf
                        hc.leaf(s, piece)
                    parts[dev] = jax.device_put(piece, axis_devs[dev])
                    shard_left[s] -= 1
                dt = sp.t1 - sp.t0
                h2d_hist.record_seconds(dt)
                if prof is not None:
                    prof.stage_sample("H2D_UPDATE", dt)
                if shard_left[s]:
                    return
                # last shard landed: assemble the P(axis)-sharded
                # gradient, run the update on the shards, and dispatch
                # the all-gather that rebuilds the replicated leaves
                with tracing.span(tracing.APPLY_ASSEMBLE, tid=names[s],
                                  step=tag, leaf=s):
                    garr = jax.make_array_from_single_device_arrays(
                        (info["n"] * info["shard_len"],),
                        shard_sharding, parts)
                imported[s] = garr
                with tracing.span(tracing.APPLY_ALLGATHER, tid=names[s],
                                  step=tag, leaf=s) as sp:
                    if ssa is not None and sa_round is not None:
                        pparts, shared = sa_round.slice(s)
                        new_sh, npp_sh, n_shared = ssa.apply(
                            p_leaves[s], pparts, shared, garr)
                        fulls = ssa.gather((new_sh, *npp_sh),
                                           [p_leaves[s], *pparts])
                        new_params[s] = fulls[0]
                        apply_parts[s] = (list(fulls[1:]), n_shared)
                    else:
                        # transform not shard-separable (or fused
                        # apply): gather the GRADIENT instead and apply
                        # full-leaf — the D2H/wire/H2D savings stand,
                        # only the update FLOPs stay replicated
                        tmpl = jax.ShapeDtypeStruct(shapes[s],
                                                    info["dtype"])
                        full = sa_state["gather"]((garr,), [tmpl])[0]
                        imported[s] = full
                        if sa_round is not None:
                            new_params[s], apply_parts[s] = \
                                sa_round.apply(p_leaves[s], s, full)
                dt = sp.t1 - sp.t0
                ag_hist.record_seconds(dt)
                if prof is not None:
                    prof.stage_sample("ALLGATHER", dt)

            def land_piece(s, j, piece):
                # the pull wrote piece ``j`` into its slice of the
                # leaf's slot (a reply that came in another buffer, a
                # retry's, is copied there: correctness never depends on
                # staging); the leaf lands whole with its last piece
                flat = piece.reshape(-1)
                view = piece_slot[s][j * flat.size:(j + 1) * flat.size]
                if not np.may_share_memory(view, flat):
                    view[:] = flat
                piece_left[s] -= 1
                if not piece_left[s]:
                    land(s, piece_slot[s])

            def _dispatch(wi):
                slot, finish, _ = waiters[wi]
                # the landed waiter's result: a leaf, a device's shard
                # or a bucket split back into its members
                with tracing.span(tracing.APPLY_FINISH, step=tag,
                                  waiter=wi):
                    got = finish()
                if isinstance(slot, list):
                    for s, piece in zip(slot, got):
                        land(s, piece)
                elif isinstance(slot, tuple) and slot[0] == "piece":
                    land_piece(slot[1], slot[2], got)
                elif isinstance(slot, tuple):
                    land_shard(slot[1], slot[2], got)
                else:
                    land(slot, got)

            # cross-barrier release condition: every NON-carryable
            # waiter must land this step (front-of-model leaves,
            # buckets, shards, rowsparse); carry-eligible tail leaves
            # land if their pull has already fired, and are otherwise
            # carried across the step boundary. With the window off
            # this is exactly the old "drain everything" loop.
            xb_carry_wi = {wi for wi, (sl, _f, _n) in enumerate(waiters)
                           if isinstance(sl, int) and sl in xb_carry_set}
            must_land = len(waiters) - len(xb_carry_wi)
            done_wi: set = set()
            landed_req = 0
            while landed_req < must_land:
                t_wait = _time.perf_counter()
                wi = ready.get()
                if prof is not None:
                    # time the drain sat blocked waiting for a pull to
                    # land — the direct "PULL is the bottleneck" signal
                    prof.add_pull_wait(_time.perf_counter() - t_wait)
                _dispatch(wi)
                done_wi.add(wi)
                if wi not in xb_carry_wi:
                    landed_req += 1
            # opportunistic: a carry-eligible pull that already fired
            # costs nothing to drain now
            while xb_carry_wi:
                try:
                    wi = ready.get_nowait()
                except _queue.Empty:
                    break
                _dispatch(wi)
                done_wi.add(wi)
            xb_pend = sorted(xb_carry_wi - done_wi)
            if xb_pend:
                centries = []
                for wi in xb_pend:
                    s, fin, notif = waiters[wi]
                    pparts, shared = sa_round.slice(s)
                    ov = xb_over.pop(s, None)
                    bp = ov[0] if ov is not None else p_leaves[s]
                    bpp = ov[1] if ov is not None else pparts
                    centries.append((s, fin, notif, bp, bpp, shared))
                    # the step returns the freshest APPLIED value for a
                    # carried leaf — at most one step behind — and its
                    # stale state slices; the carry's base_override
                    # chain keeps the true state, and ``flush`` folds
                    # the final values in at end of run
                    new_params[s] = bp
                    apply_parts[s] = (bpp, shared)
                ckeys = {f"{names[s]}:out~x{xb_par}"
                         for (s, *_rest) in centries}
                # the carried leaves' result slots stay leased until
                # the carried drain consumes them next step — they must
                # NOT ride this step's deferred release
                cleases = [lz for lz in leases if lz.key in ckeys]
                leases[:] = [lz for lz in leases if lz.key not in ckeys]
                xb_state["carry"] = {"entries": centries,
                                     "leases": cleases,
                                     "imported": [], "sa": sa,
                                     "step": xb_state["seq"]}
                metrics.counter("barrier/carried_leaves").inc(
                    len(centries))
            if sa is None:
                # fused apply: wait for the H2D transfers (apply_fn
                # needs them anyway) so the arena slots are provably
                # idle before release
                jax.block_until_ready([x for x in imported
                                       if x is not None])
            if prof is not None:
                prof.mark("drain_done")
                phase.set(pull_wait_ms=prof.pull_wait_s * 1e3)
            phase.stop()
        except BaseException:
            wait.stop()
            phase.stop()
            # a failed round (submission OR drain) may leave pulls
            # mid-flight into these slots: abandon (drop from the
            # table) instead of recycling them under a late writer.
            # The not-yet-drained sibling handles must not pin their
            # gradient-sized result buffers in the handle table for
            # the life of the process either (the same leak class
            # the TF graph tier discards against).
            # a raised step voids the cross-barrier chain: overrides
            # reference buffers from the failed round, and a restarted
            # run must not apply them onto checkpoint-restored trees
            xbc = xb_state["carry"]
            xb_state["carry"] = None
            if xbc is not None:
                for lease in xbc["leases"]:
                    lease.abandon()
                for (_s, _f, nt, *_rest) in xbc["entries"]:
                    if hasattr(nt, "id"):
                        state.handles.discard(nt.id)
            xb_state["over"].clear()
            for lease in leases:
                lease.abandon()
            for _, _, notifier in waiters:
                if hasattr(notifier, "id"):
                    state.handles.discard(notifier.id)
            raise
        state.telemetry.record_export(len(names), first_push[0],
                                      shard_leaves=len(out_shards))
        if sa is not None:
            # UPDATEs are already in flight; the end-of-step barrier is
            # gone. The leases release on whichever fires first: the
            # release worker (as soon as the imports are ready — covers
            # the LAST step of a run and a rebuilt step closure, which
            # would otherwise pin the slots forever and conflict a new
            # closure's checkouts into fresh allocations) or the next
            # step's deterministic drain (release() is idempotent, so
            # double-firing is harmless).
            entry = (list(leases), imported)
            pending.append(entry)

            def _release_when_ready(entry=entry):
                try:
                    jax.block_until_ready([a for a in entry[1]
                                           if a is not None])
                except Exception:  # noqa: BLE001 - failed imports:
                    for lease in entry[0]:    # never recycle the slots
                        lease.abandon()
                    return
                for lease in entry[0]:
                    lease.release()

            _release_pool().submit(_release_when_ready)
            params = treedef.unflatten(new_params)
            opt_state = sa.merge(opt_state, apply_parts)
        else:
            for lease in leases:
                lease.release()
            grads = treedef.unflatten(imported)
            params, opt_state = apply_fn(params, opt_state, grads)
        # training-health finalize: close the step's per-leaf stats
        # into the StepReport fields (incl. the bounded HEALTH_PULL
        # fidelity sweep); the HealthPlane observer inside end_step
        # then runs the detector, and with BYTEPS_NAN_GUARD a
        # nonfinite round raises HERE — after the flight events and
        # counters landed, never before (detect → record → fail-fast)
        health_fields = None
        if hc is not None:
            try:
                health_fields = hplane.finalize(hc, names, state)
            except Exception:  # noqa: BLE001 - diagnostics never kill
                health_fields = None          # the step
        # cross-barrier staleness fields for the StepReport and its
        # time-series: drained-tail size/wall, effective staleness and
        # the depth still deferred into the NEXT step (None when the
        # cross-barrier plane is off — the series simply skip)
        xb_fields = None
        if xb_on:
            _c = xb_state["carry"]
            xb_fields = {
                "carried_leaves": xb_drained,
                "carry_drain_ms": xb_drain_ms,
                "staleness_lag": xb_lag,
                "window_depth": len(_c["entries"]) if _c else 0,
            }
        state.profiler.end_step(
            prof,
            ttfp_ms=first_push[0] * 1e3 if first_push[0] is not None
            else None,
            leaves=len(names), health=health_fields, xb=xb_fields)
        if by_thread:
            # the step's report is closed: the second reading (the span
            # is its cost) and the table over everything between the two
            with tracing.span(tracing.STEP_HOST_CPU, step=tag) as sp:
                sp.set(cpu_ms_by_thread=tracing.cpu_ms_by_thread(
                    cpu_at, tracing.thread_cpu_ms()))
        if hplane is not None:
            hplane.raise_if_fatal()
        return _finish(params, opt_state, loss)

    def flush(params, opt_state):
        """Drain the cross-barrier carry and fold every outstanding
        override into ``(params, opt_state)`` — call once after the
        LAST step of a run (a checkpoint cut counts). Without
        BYTEPS_CROSS_BARRIER (or with nothing carried) this returns
        its arguments unchanged."""
        carry = xb_state["carry"]
        xb_state["carry"] = None
        over = xb_state["over"]
        if carry is not None:
            try:
                for (s, fin, _nt, bp, bpp, bsh) in carry["entries"]:
                    piece = fin()
                    arr = jax.device_put(piece.reshape(np.shape(bp)))
                    npar, nparts = carry["sa"].apply_with(
                        bp, bpp, bsh, arr)
                    over[s] = (npar, nparts[0])
                    carry["imported"].append(arr)
                jax.block_until_ready(carry["imported"])
            except BaseException:
                for lease in carry["leases"]:
                    lease.abandon()
                raise
            for lease in carry["leases"]:
                lease.release()
        if not over:
            return params, opt_state
        sa = sa_state["sa"]
        leaves, tdef = jax.tree_util.tree_flatten(params)
        rnd = sa.begin(opt_state)
        results = []
        for s in range(len(leaves)):
            pp, sh = rnd.slice(s)
            ov = over.pop(s, None)
            if ov is not None:
                leaves[s] = ov[0]
                pp = ov[1]
            results.append((pp, sh))
        return tdef.unflatten(leaves), sa.merge(opt_state, results)

    # tick the Chrome-trace step counter: the PUSH/PULL/COMPRESS spans the
    # scheduler records are windowed by step (BYTEPS_TRACE_START/END_STEP)
    stepper = _with_tracer_tick(step)
    stepper.flush = flush
    return stepper


def make_async_ps_train_step(
    loss_fn: Callable,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    axis: str = DP_AXIS,
):
    """Asynchronous data-parallel train step (the reference's
    BYTEPS_ENABLE_ASYNC mode, torch/__init__.py:188-216, server.cc:315-319):
    each worker updates its params locally, pushes the weight DELTA to the
    PS — which folds it into the authoritative weights with no aggregation
    barrier — and pulls the current weights back. Workers never wait for
    each other; staleness is the accepted tradeoff.

    The server must run with BYTEPS_ENABLE_ASYNC=1. On the first step each
    worker init-pushes its initial weights (first arrival seeds the
    authoritative copy — start workers from identical or broadcast params).

    Returns ``step(params, opt_state, batch) -> (params, opt_state, loss)``.
    Without a PS configured, degrades to plain local (single-worker) SGD.
    """
    import numpy as np

    from ..core.state import get_state
    from ..server.client import get_or_init_ctx

    def local_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        grads = psum_tree(grads, axis=axis, average=True)
        updates, opt_state = tx.update(grads, opt_state, params)
        new_params = optax.apply_updates(params, updates)
        delta = jax.tree.map(jnp.subtract, new_params, params)
        loss = jax.lax.pmean(loss, axis)
        return loss, delta, opt_state

    local_fn = jax.jit(jax.shard_map(
        local_step, mesh=mesh, in_specs=(P(), P(), P(axis)),
        out_specs=(P(), P(), P()), check_vma=False))

    # seeding is keyed to the client that received it: suspend/resume
    # replaces state.ps_client with fresh (unseeded) servers, and a stale
    # `seeded` set would skip init_weights — the pull would then return
    # bare deltas and silently destroy the model (the sync paths carry
    # the same client-keyed guard on their compression registry)
    seed_state = {"client": None, "names": set()}

    def step(params, opt_state, batch):
        state = get_state()
        client = state.ps_client
        loss, delta, opt_state = local_fn(params, opt_state, batch)
        if client is None:
            params = jax.tree.map(jnp.add, params, delta)
            return params, opt_state, loss
        if seed_state["client"] is not client:
            seed_state["client"] = client
            seed_state["names"] = set()
        seeded = seed_state["names"]
        paths, treedef = jax.tree_util.tree_flatten_with_path(params)
        deltas = jax.tree.leaves(delta)
        leaves = []
        for (path, leaf), d in zip(paths, deltas):
            name = "asyncw/" + "/".join(
                str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
            host_w = np.asarray(leaf).reshape(-1)
            ctx = get_or_init_ctx(state, name, host_w)
            if name not in seeded:
                client.init_weights(ctx, host_w)
                seeded.add(name)
            leaves.append((ctx, leaf, np.asarray(d).reshape(-1)))

        # overlap the per-leaf round trips (they'd otherwise serialize the
        # step on sum-of-RTTs) on the shared tensor-level pool — NOT
        # client._pool (these calls block on client-pool futures and
        # would deadlock it), and not a per-step executor (spawn/join of
        # 16 threads every step on the hot path)
        def one(item):
            ctx, leaf, d = item
            out = client.push_delta_pull_weights(ctx, d)
            state.telemetry.record_round_trip(out.nbytes)
            return jnp.asarray(out.reshape(leaf.shape))

        pulled = list(_comp_pool().map(one, leaves))
        params = treedef.unflatten(pulled)
        return params, opt_state, loss

    return _with_tracer_tick(step)


def init_zero_state(params, tx: optax.GradientTransformation, mesh: Mesh,
                    axis: str = DP_AXIS):
    """Initialize optimizer state over flat 1/N param shards (matches
    make_zero_train_step's layout)."""
    opt_specs = _zero_state_specs(params, tx, mesh, axis)

    def init(params_):
        shards = reduce_scatter_tree(params_, axis=axis, average=True)
        return tx.init(shards)

    return jax.jit(jax.shard_map(
        init, mesh=mesh, in_specs=(P(),), out_specs=opt_specs,
        check_vma=False))(params)
