"""Optimizer-pass machinery: hand-fused steps and the sharded apply.

``fused_adam_step`` computes mu/nu/bias-correction/param-new in ONE
elementwise expression per leaf — the best case a fused (XLA- or
Pallas-lowered) optimizer pass can reach, vs optax.adam's chain of
per-transform tree passes. Numerics validated bit-close to optax
(max |Δparam| ≈ 1e-7 after 5 steps on the tiny llama config:
tests/test_train.py).

``make_sharded_apply`` splits an optax transformation into per-leaf
jitted partial updates for the PS train step's tail overlap
(BYTEPS_SHARDED_APPLY): UPDATE(k) is issued from the
completion-ordered drain the moment leaf k's pull lands, overlapping
PULL(k+1) — the worker-side form of "Automatic Cross-Replica Sharding
of Weight Update in Data-Parallel Training" (PAPERS.md), where the
weight update decomposes cleanly per shard. Transforms that are NOT
per-leaf separable (global-norm clipping, masked/multi-transform
label trees) are detected by a numeric probe at build time and the
caller falls back to the fused apply.

Reference context: the reference leaves optimizer fusion to the
framework (torch fused adam etc.); here it is an A/B lever for the
optimizer pass.
"""

from __future__ import annotations

from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp


def fused_adam_step(loss_fn, lr=1e-3, b1=0.9, b2=0.999, eps=1e-8,
                    mu_dtype=jnp.bfloat16):
    """Build ``(init, step)`` for a fully hand-fused adam train step.

    ``loss_fn(params, batch) -> scalar``; ``step(params, opt_state,
    batch) -> (params, opt_state, loss)`` with every per-leaf update in
    a single fused expression. ``mu_dtype=bfloat16`` halves the first
    moment's HBM traffic (optax.adam's ``mu_dtype``); nu
    stays f32 (variance needs the range).
    """

    def init(params):
        return {"mu": jax.tree.map(
                    lambda p: jnp.zeros(p.shape, mu_dtype), params),
                "nu": jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params),
                "count": jnp.zeros((), jnp.int32)}

    def step(p, o, batch):
        loss, g = jax.value_and_grad(lambda q: loss_fn(q, batch))(p)
        c = o["count"] + 1
        cf = c.astype(jnp.float32)
        bc1, bc2 = 1.0 - b1 ** cf, 1.0 - b2 ** cf

        def leaf(pl, m, v, gl):
            gf = gl.astype(jnp.float32)
            m2 = b1 * m.astype(jnp.float32) + (1.0 - b1) * gf
            v2 = b2 * v + (1.0 - b2) * gf * gf
            new = pl - lr * (m2 / bc1) / (jnp.sqrt(v2 / bc2) + eps)
            return new, m2.astype(mu_dtype), v2

        tup = jax.tree.map(leaf, p, o["mu"], o["nu"], g)
        is_t = lambda x: isinstance(x, tuple)  # noqa: E731
        return (jax.tree.map(lambda x: x[0], tup, is_leaf=is_t),
                {"mu": jax.tree.map(lambda x: x[1], tup, is_leaf=is_t),
                 "nu": jax.tree.map(lambda x: x[2], tup, is_leaf=is_t),
                 "count": c}, loss)

    return init, step


# --------------------------------------------------------------------- #
# sharded optimizer apply (BYTEPS_SHARDED_APPLY)
# --------------------------------------------------------------------- #


class ShardedApply:
    """Per-leaf partial updates over one optax transformation.

    Built by :func:`make_sharded_apply` (which verifies per-leaf
    separability first — use it, not this constructor). The optimizer
    state is analysed once into nodes that mirror the params tree
    ("param nodes": adam's mu/nu, momentum traces — sliced per leaf)
    and nodes that don't (shared scalars like adam's count — passed to
    every leaf update, never donated, identical across leaves by
    separability). ``apply_leaf`` runs the whole transform chain on a
    single leaf with the param and its param-node state slices donated;
    ``merge`` reassembles the full optimizer state from the per-leaf
    results.
    """

    def __init__(self, tx, params_treedef, state_top_treedef,
                 node_kinds: List[bool], donate: bool = True):
        self._ptd = params_treedef
        self._std = state_top_treedef
        self._kinds = node_kinds

        def leaf_update(param, param_parts, shared_parts, grad):
            nodes, pi, si = [], 0, 0
            for is_param in self._kinds:
                if is_param:
                    nodes.append(param_parts[pi])
                    pi += 1
                else:
                    nodes.append(shared_parts[si])
                    si += 1
            state_i = jax.tree.unflatten(self._std, nodes)
            import optax
            updates, new_state = tx.update(grad, state_i, param)
            new_param = optax.apply_updates(param, updates)
            out_nodes = self._std.flatten_up_to(new_state)
            n_pparts = [n for n, k in zip(out_nodes, self._kinds) if k]
            n_shared = [n for n, k in zip(out_nodes, self._kinds) if not k]
            return new_param, n_pparts, n_shared

        # donate the param and its param-node state slices (per-leaf
        # buffers); shared scalars are read by EVERY leaf update, so
        # donating them would hand leaf 0 the buffer leaf 1 still needs
        self._jit = jax.jit(leaf_update,
                            donate_argnums=(0, 1) if donate else ())
        # non-donating twin for the cross-barrier carried drain: a
        # carried leaf's base param is ALSO the value the previous step
        # returned to the caller (it rides the next forward while its
        # update is still in flight), so donating it would invalidate a
        # buffer the user's tree still references
        self._jit_keep = jax.jit(leaf_update)

    # -- state plumbing ------------------------------------------------ #

    def begin(self, opt_state) -> "_ShardedRound":
        """Pre-flatten the state ONCE for a whole round of per-leaf
        applies. ``apply_leaf`` below re-flattens per call — O(leaves²)
        per step for the drain's hot loop — so the train step's
        completion-ordered drain goes through a round instead."""
        return _ShardedRound(self, opt_state)

    def slice_leaf(self, opt_state, i: int) -> Tuple[list, list]:
        """(param_parts, shared_parts) views of ``opt_state`` for params
        leaf ``i`` — no copies, just tree surgery."""
        return _ShardedRound(self, opt_state).slice(i)

    def apply_leaf(self, param_leaf, opt_state, i: int, grad_leaf):
        """One leaf's full update chain: returns
        ``(new_param_leaf, (param_parts_i, shared_parts_i))``. Issue it
        the moment leaf ``i``'s gradient lands; jax dispatch is async,
        so the update computes while later pulls are still in flight.
        Convenience form (re-flattens the state per call) — hot loops
        use ``begin(opt_state)`` + ``round.apply``."""
        return _ShardedRound(self, opt_state).apply(param_leaf, i,
                                                    grad_leaf)

    def apply_with(self, param_leaf, pparts, shared, grad_leaf):
        """Explicit-base apply: update from caller-supplied
        ``(param_parts, shared_parts)`` instead of slicing a live
        opt_state. The cross-barrier carried drain needs this — when a
        tail leaf's step-k gradient lands AFTER step k+1 has begun, its
        base state is the snapshot captured at step k (the live
        opt_state has moved on), so the carry hands that snapshot back
        in. Returns ``(new_param_leaf, (param_parts, shared_parts))``
        like ``_ShardedRound.apply``. Never donates: the base buffers
        are shared with the caller's (stale) params/opt_state trees."""
        new_p, n_pparts, n_shared = self._jit_keep(param_leaf, pparts,
                                                   shared, grad_leaf)
        return new_p, (n_pparts, n_shared)

    def merge(self, opt_state_template, results: List[Tuple[list, list]]):
        """Reassemble the full optimizer state from every leaf's
        ``(param_parts, shared_parts)``. ``opt_state_template`` supplies
        only the tree STRUCTURE (its buffers may already be donated).
        Shared nodes are taken from leaf 0 — separability (verified at
        build) means every leaf computed the same value."""
        nodes, pi, si = [], 0, 0
        for is_param in self._kinds:
            if is_param:
                nodes.append(jax.tree.unflatten(
                    self._ptd, [r[0][pi] for r in results]))
                pi += 1
            else:
                nodes.append(results[0][1][si])
                si += 1
        return jax.tree.unflatten(self._std, nodes)


class _ShardedRound:
    """One round's pre-flattened view of the optimizer state: the
    param-shaped nodes' leaf lists and the shared scalars, computed
    once, indexed per leaf — the drain's per-leaf work drops from
    O(leaves) tree traversal to O(param nodes) list indexing."""

    __slots__ = ("_sa", "_pnode_leaves", "_shared")

    def __init__(self, sa: ShardedApply, opt_state):
        nodes = sa._std.flatten_up_to(opt_state)
        self._sa = sa
        self._pnode_leaves = [jax.tree.leaves(nd)
                              for nd, k in zip(nodes, sa._kinds) if k]
        self._shared = [nd for nd, k in zip(nodes, sa._kinds) if not k]

    def slice(self, i: int) -> Tuple[list, list]:
        return [pl[i] for pl in self._pnode_leaves], list(self._shared)

    def apply(self, param_leaf, i: int, grad_leaf):
        pparts, shared = self.slice(i)
        new_p, n_pparts, n_shared = self._sa._jit(param_leaf, pparts,
                                                  shared, grad_leaf)
        return new_p, (n_pparts, n_shared)


def _probe_separable(tx, params_treedef) -> bool:
    """Numeric separability probe on tiny surrogate params sharing the
    real tree structure: the fused ``tx.update`` restricted to each leaf
    must equal the per-leaf update built from sliced state. Global-norm
    clipping, masked label trees and friends either mismatch or raise —
    both mean "not separable"."""
    import numpy as np
    import optax

    n = params_treedef.num_leaves
    rng = np.random.RandomState(0)
    pp = jax.tree.unflatten(params_treedef, [
        jnp.asarray(rng.randn(2, 3).astype(np.float32)) for _ in range(n)])
    gg = jax.tree.unflatten(params_treedef, [
        jnp.asarray(rng.randn(2, 3).astype(np.float32)) for _ in range(n)])
    state0 = tx.init(pp)
    fused_u, fused_s = tx.update(gg, state0, pp)
    std, kinds = _analyze_state(state0, params_treedef)
    if std is None:
        return False
    sa = ShardedApply(tx, params_treedef, std, kinds, donate=False)
    p_leaves = jax.tree.leaves(pp)
    g_leaves = jax.tree.leaves(gg)
    fu_leaves = jax.tree.leaves(
        jax.tree.map(optax.apply_updates, pp, fused_u))
    results = []
    for i in range(n):
        new_p, parts = sa.apply_leaf(p_leaves[i], state0, i, g_leaves[i])
        if not np.allclose(np.asarray(new_p), np.asarray(fu_leaves[i]),
                           rtol=1e-6, atol=1e-7):
            return False
        results.append(parts)
    merged = sa.merge(state0, results)
    for a, b in zip(jax.tree.leaves(merged), jax.tree.leaves(fused_s)):
        if np.asarray(a).shape != np.asarray(b).shape or \
                not np.allclose(np.asarray(a), np.asarray(b),
                                rtol=1e-6, atol=1e-7):
            return False
    return True


def _analyze_state(opt_state, params_treedef):
    """Split the state's top-level nodes into params-shaped trees vs
    shared leaves. Returns (top_treedef, kinds) or (None, None) when the
    layout can't be decomposed (a node partially overlaps the params
    structure)."""
    def is_param_node(x):
        try:
            return jax.tree.structure(x) == params_treedef
        except Exception:  # noqa: BLE001 - unflattenable exotic node
            return False

    try:
        top = jax.tree.structure(opt_state, is_leaf=is_param_node)
        nodes = top.flatten_up_to(opt_state)
    except Exception:  # noqa: BLE001
        return None, None
    kinds = [is_param_node(nd) for nd in nodes]
    # a non-param node containing arrays the size of params would be
    # silently shared (wrong); require non-param nodes to be single
    # leaves (scalar counts, hyperparams), not containers
    for nd, k in zip(nodes, kinds):
        if not k and jax.tree.structure(nd).num_leaves not in (0, 1):
            return None, None
    return top, kinds


# --------------------------------------------------------------------- #
# shard-mapped optimizer apply (BYTEPS_LOCAL_SHARD_EXPORT)
# --------------------------------------------------------------------- #


class LeafGather:
    """Cached jitted all-gathers: flat P(axis)-sharded arrays back to
    replicated leaves shaped/typed like the given templates. One jit per
    ((shape, dtype), ...) signature — two leaves can share a shard shape
    but trim to different sizes (padding), so the trim is part of the
    cache key, not data. Shared by :class:`ShardApply` (params + state
    nodes after the shard update) and the train step's gradient-gather
    fallback (shard-exported leaves whose transform cannot shard)."""

    def __init__(self, mesh, axis: str):
        self._mesh = mesh
        self._axis = axis
        self._cache: dict = {}

    def __call__(self, shards, templates):
        from jax.sharding import PartitionSpec as P

        meta = tuple((tuple(t.shape), jnp.dtype(t.dtype).name)
                     for t in templates)
        fn = self._cache.get(meta)
        if fn is None:
            axis = self._axis

            def body(flats):
                outs = []
                for sh, (shape, dtype) in zip(flats, meta):
                    full = jax.lax.all_gather(
                        sh, axis_name=axis, axis=0,
                        tiled=False).reshape(-1)
                    size = 1
                    for d in shape:
                        size *= d
                    outs.append(full[:size].reshape(shape).astype(dtype))
                return tuple(outs)

            fn = jax.jit(jax.shard_map(
                body, mesh=self._mesh, in_specs=(P(axis),),
                out_specs=P(), check_vma=False))
            self._cache[meta] = fn
        return fn(tuple(shards))


class ShardApply:
    """Per-leaf update over 1/N shards, compiled as a shard_map.

    The locality-sharded import path lands each leaf's PS-aggregated
    gradient as a sharded jax.Array (shard k on the device that owns
    it); this class runs the optimizer update ON THE SHARD ONLY — each
    device slices its 1/N of the (replicated) param and param-shaped
    state nodes by ``axis_index``, applies the full transform chain to
    the slice, and emits sharded results — then a separate jitted
    all-gather (:meth:`gather`) rebuilds replicated params and state so
    the step's external contract (replicated trees in, replicated trees
    out) is unchanged. Per-device H2D and update FLOPs divide by N.

    Built by :func:`make_shard_apply`, which layers a SHARD-granularity
    separability probe on top of the per-leaf one: per-leaf separable
    transforms that mix elements WITHIN a leaf (block-norm clipping)
    pass the leaf probe but fail here and fall back to the full-leaf
    sharded apply. State plumbing (slice/merge) is shared with the base
    :class:`ShardedApply` so mixed rounds — some leaves sharded, some
    whole — merge through one code path."""

    def __init__(self, tx, base: ShardedApply, mesh, axis: str):
        from jax.sharding import PartitionSpec as P

        self.base = base
        self._axis = axis
        std, kinds = base._std, base._kinds

        def leaf_update_shard(param, pparts, shared, grad_shard):
            # inside shard_map: grad_shard is THIS device's flat shard;
            # param/pparts are replicated and sliced to the matching
            # subrange — the same padded layout as ops.push_pull.
            # shard_layout, so shard k of the gradient meets shard k of
            # the param bit-for-bit
            n = jax.lax.axis_size(axis)
            shard_len = grad_shard.shape[0]
            idx = jax.lax.axis_index(axis)

            def slice_shard(x):
                flat = x.reshape(-1)
                pad = shard_len * n - flat.shape[0]
                if pad:
                    flat = jnp.pad(flat, (0, pad))
                return jax.lax.dynamic_slice(flat, (idx * shard_len,),
                                             (shard_len,))

            p_sh = slice_shard(param)
            pparts_sh = [slice_shard(x) for x in pparts]
            nodes, pi, si = [], 0, 0
            for is_param in kinds:
                if is_param:
                    nodes.append(pparts_sh[pi])
                    pi += 1
                else:
                    nodes.append(shared[si])
                    si += 1
            state_i = jax.tree.unflatten(std, nodes)
            import optax
            updates, new_state = tx.update(grad_shard, state_i, p_sh)
            new_p = optax.apply_updates(p_sh, updates)
            out_nodes = std.flatten_up_to(new_state)
            n_pparts = [nd for nd, k in zip(out_nodes, kinds) if k]
            n_shared = [nd for nd, k in zip(out_nodes, kinds) if not k]
            return new_p, n_pparts, n_shared

        # no donation: replicated inputs cannot alias sharded outputs,
        # and the donation warning would fire per leaf per step
        self._jit = jax.jit(jax.shard_map(
            leaf_update_shard, mesh=mesh,
            in_specs=(P(), P(), P(), P(axis)),
            out_specs=(P(axis), P(axis), P()), check_vma=False))

        self._gatherer = LeafGather(mesh, axis)

    def apply(self, param_leaf, pparts, shared, grad_sharded):
        """One leaf's shard update. ``grad_sharded`` is the flat padded
        P(axis)-sharded gradient; ``pparts``/``shared`` come from the
        shared ``ShardedApply`` round's ``slice(i)``. Returns
        ``(new_param_shard, new_pparts_shards, new_shared)`` — the
        first two still sharded (feed :meth:`gather`)."""
        return self._jit(param_leaf, pparts, shared, grad_sharded)

    def gather(self, shards, templates):
        """All-gather flat shards back to replicated leaves shaped/typed
        like ``templates``; returns a tuple aligned with ``shards``.
        The BROADCAST half of the hierarchical exchange — dispatched
        asynchronously, so the gather of leaf k overlaps the PULL of
        leaf k+1."""
        return self._gatherer(shards, templates)


def _probe_shard_separable(tx, params_treedef, num_shards: int) -> bool:
    """SHARD-granularity separability probe: the per-leaf update
    restricted to each padded 1/N subrange must equal the subrange of
    the full-leaf update. Per-LEAF separable transforms that mix
    elements within a leaf — block-RMS/block-norm scaling — pass the
    base probe but must fail here (a shard's RMS is not the leaf's).
    Emulated eagerly on tiny surrogates with plain slicing, no mesh."""
    import numpy as np
    import optax

    from ..ops.push_pull import shard_layout

    n_leaves = params_treedef.num_leaves
    rng = np.random.RandomState(1)
    pp = jax.tree.unflatten(params_treedef, [
        jnp.asarray(rng.randn(2, 3).astype(np.float32))
        for _ in range(n_leaves)])
    gg = jax.tree.unflatten(params_treedef, [
        jnp.asarray(rng.randn(2, 3).astype(np.float32))
        for _ in range(n_leaves)])
    state0 = tx.init(pp)
    std, kinds = _analyze_state(state0, params_treedef)
    if std is None:
        return False
    nodes = std.flatten_up_to(state0)
    pnode_leaves = [jax.tree.leaves(nd)
                    for nd, k in zip(nodes, kinds) if k]
    shared = [nd for nd, k in zip(nodes, kinds) if not k]
    full_u, full_s = tx.update(gg, state0, pp)
    full_new = jax.tree.map(optax.apply_updates, pp, full_u)
    fn_leaves = jax.tree.leaves(full_new)
    p_leaves, g_leaves = jax.tree.leaves(pp), jax.tree.leaves(gg)

    def pad_flat(x, total):
        flat = np.asarray(x).reshape(-1)
        return np.pad(flat, (0, total - flat.size))

    shard_len, _ = shard_layout(p_leaves[0].size, num_shards)
    total = shard_len * num_shards
    for i in range(n_leaves):
        pf = pad_flat(p_leaves[i], total)
        gf = pad_flat(g_leaves[i], total)
        parts_f = [pad_flat(pl[i], total) for pl in pnode_leaves]
        got = np.empty(total, np.float32)
        for k in range(num_shards):
            lo, hi = k * shard_len, (k + 1) * shard_len
            nds, pi, si = [], 0, 0
            for is_param in kinds:
                if is_param:
                    nds.append(jnp.asarray(parts_f[pi][lo:hi]))
                    pi += 1
                else:
                    nds.append(shared[si])
                    si += 1
            state_i = jax.tree.unflatten(std, nds)
            try:
                u, _ = tx.update(jnp.asarray(gf[lo:hi]), state_i,
                                 jnp.asarray(pf[lo:hi]))
            except Exception:  # noqa: BLE001 - shape-dependent: fused
                return False
            got[lo:hi] = np.asarray(
                optax.apply_updates(jnp.asarray(pf[lo:hi]), u))
        want = np.asarray(fn_leaves[i]).reshape(-1)
        if not np.array_equal(got[:want.size], want):
            return False
    return True


def make_shard_apply(tx, params, opt_state, mesh, axis: str,
                     num_shards: int,
                     base: Optional[ShardedApply] = None
                     ) -> Optional["ShardApply"]:
    """Build the shard-mapped per-leaf apply for the locality-sharded
    import path, or None when the transform cannot decompose to shard
    granularity (the caller then gathers gradients and keeps the
    full-leaf apply). Requires a prior :func:`make_sharded_apply`
    success (``base``); additionally verifies that every param-shaped
    state leaf matches its param leaf's SHAPE on the real trees (a
    factored/covariance state would slice the wrong subranges) and that
    the update is shard-separable (see :func:`_probe_shard_separable`).
    """
    if base is None:
        base = make_sharded_apply(tx, params, opt_state, donate=False)
    if base is None:
        return None
    p_leaves = jax.tree.leaves(params)
    try:
        nodes = base._std.flatten_up_to(opt_state)
    except Exception:  # noqa: BLE001 - structure drifted: fused
        return None
    for nd, k in zip(nodes, base._kinds):
        if not k:
            continue
        for pl, sl in zip(p_leaves, jax.tree.leaves(nd)):
            if tuple(getattr(sl, "shape", ())) != tuple(pl.shape):
                return None
    try:
        if not _probe_shard_separable(tx, base._ptd, num_shards):
            return None
    except Exception:  # noqa: BLE001 - probe failures mean "no shard"
        return None
    try:
        return ShardApply(tx, base, mesh, axis)
    except Exception:  # noqa: BLE001 - build failures mean "no shard"
        return None


def make_sharded_apply(tx, params, opt_state,
                       donate: bool = True) -> Optional[ShardedApply]:
    """Build per-leaf partial updates for ``tx``, or return None when
    the transform chain is not per-leaf separable (the caller then keeps
    the fused apply).

    ``params`` / ``opt_state`` fix the REAL tree structures (the probe
    itself runs on tiny surrogates, so a large model costs nothing to
    verify). Separability is verified numerically, not assumed from the
    transform names: anything whose update mixes leaves — global-norm
    clipping, cross-leaf masking — fails the probe and falls back.
    """
    params_treedef = jax.tree.structure(params)
    std, kinds = _analyze_state(opt_state, params_treedef)
    if std is None:
        return None
    try:
        if not _probe_separable(tx, params_treedef):
            return None
    except Exception:  # noqa: BLE001 - probe failures mean "fused"
        return None
    # structural round-trip on the REAL state: slice + merge must
    # reproduce it exactly (guards probe/real structure divergence,
    # e.g. shape-dependent factored states)
    try:
        sa = ShardedApply(tx, params_treedef, std, kinds, donate=donate)
        n = params_treedef.num_leaves
        results = [sa.slice_leaf(opt_state, i) for i in range(n)]
        merged = sa.merge(opt_state, results)
        if jax.tree.structure(merged) != jax.tree.structure(opt_state):
            return None
        for a, b in zip(jax.tree.leaves(merged),
                        jax.tree.leaves(opt_state)):
            if a is not b:
                return None
    except Exception:  # noqa: BLE001
        return None
    return sa
