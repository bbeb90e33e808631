"""A loss written as a chain of links.

A model's loss is usually one function of the whole parameter tree, and
its backward one program: every gradient exists for the host only when
that program has ended. Written as a CHAIN the loss says where it can be
cut: a list of links, each a function ``(its parameters, carry, batch)
-> (carry, additive statistics)`` and the rule that picks its parameters
out of the tree. The first link's carry is ``None``, the last link's is
the loss. Two kinds of link:

- ``Link(fn, keys)``: the whole leaves under ``keys`` (one key or
  several) of the parameter tree;
- ``Run(block, key, depth, ...)``: a run of ``depth`` like layers whose
  leaves are stacked on a leading axis under ONE key, declared ONCE:
  ``block(layer j's parameters, carry, consts)`` is one layer. Layers
  that are alike in their leaves and unlike in something STATIC (a mask,
  a rotary table: window, window, window, full) are one run too: it
  declares ``kinds``, a label a layer, and the block takes its layer's
  label last.

A KEY says where in the tree a link's leaves lie: a top-level name
(``"embed"``) or a path, a name and the positions below it (``("runs",
2)``: ``params["runs"][2]``, a run that lives inside a list). Several
keys are a list of such (``("final_norm", "embed")``, ``[("runs", 0),
"final_norm"]``); a tuple that holds a position is ONE path, and a path
of names alone is written as a list of one (``[("extra", "inner")]``).
A link is handed exactly its subtrees, in the shape of the tree above
them (``Link.pick``: ``{"runs": {2: ...}}``, a list's positions as a
dict's keys), so ``fn`` reads ``p["runs"][2]`` as it would read the
whole tree.

A LEAF MAY LIE UNDER SEVERAL LINKS: an embedding matrix that is also the
head is read by the first link and by the last, and its gradient is the
sum of the two uses'. Which leaves are shared is read off the links'
keys, nothing else says so. A run's stacked leaf cannot be shared (its
layers' programs hand it over a layer at a time). What a shared leaf
costs a step that cuts the backward: the term of the first program that
reads it (the last link's; the parameter's shape, in the gradient's
type) stays on the chip, an input of the next program that reads the
leaf, until the LAST program that reads it has added its own and hands
the sum over, once; that leaf's bytes leave behind that program and no
earlier.

``Chain(links)`` is a plain ``loss_fn(params, batch) -> (loss, stats)``.
Called as any loss is called it composes the links, and a run
is the ``lax.scan`` over its stacked leaves (under ``jax.checkpoint``
where ``remat``): the program a model that scans its layers has without
this module. ``jax/train.py make_ps_train_step`` finds the chain behind
whatever closure the loss is wrapped in (a chain that is called
registers itself with the collector of ``collecting()``) and, where
every run is rematerialised, runs the backward as one program a link
and a layer, last first (``forward``, ``last``, ``pull_layer``,
``pull_link`` below are those programs' bodies on one data shard), so
that each program's gradients cross to the host while the next runs.
The FLOPs are the uncut backward's: a rematerialised layer's forward is
computed again in its backward either way.

Carries are pytrees of floating arrays (they are differentiated).
Statistics are additive counts (``jax/train.py _loss_and_stats``): links
that count under one name add up (``add_stats``: the runs of a model
with several each count their layers' pairs).

A link is a function of its three arguments and of nothing else that
changes from call to call: what it needs of the batch (a row count, a
length) it reads from ``batch`` INSIDE ``fn``, not from the scope that
built it. The cut programs are traced from the links one at a time, and
a link that closed over the first batch's shape would carry it into a
later trace. (The step also collects the chain anew for every new shape
of ``params`` and ``batch``, as ``jax.jit`` traces a loss anew, so a
loss that builds its chain inside the call, as ``models/sdar.py`` does,
is held to the shapes it was built for.)

This module needs jax alone: a model file imports it without the step
makers.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import (Any, Callable, Dict, Hashable, List, Optional, Tuple,
                    Union)

import jax
import jax.numpy as jnp

# where a link's leaves lie: a top-level name, or a name and the steps
# (names, positions) below it
Key = Union[str, Tuple[Union[str, int], ...]]

_collector: contextvars.ContextVar = contextvars.ContextVar(
    "bps_chain_collector", default=None)


class _Called(Exception):
    """Ends a ``collecting(first=True)`` block at the first chain's
    call."""


@contextlib.contextmanager
def collecting(first: bool = False):
    """The chains called inside the block, in call order (each call
    counts: a loss that calls one chain twice has used its parameters
    twice). ``first``: the block ENDS at the first chain's call (what it
    was doing is abandoned there): whether a loss calls a chain at all,
    at the price of its trace up to that call."""
    found: List["Chain"] = []
    token = _collector.set((found, first))
    try:
        yield found
    except _Called:
        pass
    finally:
        _collector.reset(token)


def add_stats(stats: Dict[str, Any], more: Dict[str, Any]) -> Dict[str, Any]:
    """``more`` counted into ``stats``: a name both hold is their sum."""
    for name, value in more.items():
        stats[name] = stats[name] + value if name in stats else value
    return stats


def _path(key: Key) -> tuple:
    return (key,) if isinstance(key, str) else key


def period(kinds) -> int:
    """The shortest period of the pattern ``kinds`` that divides its
    length: a scan over a run of unlike layers holds one block for each
    layer of a period and is compiled once, however many periods the
    run is deep."""
    n = len(kinds)
    for p in range(1, n + 1):
        if n % p == 0 and all(kinds[i] == kinds[i % p] for i in range(n)):
            return p
    return n


def _steps(path) -> tuple:
    """A leaf's path (``tree_flatten_with_path``) as the steps a key is
    written in: a dict's names, a list's positions."""
    return tuple(getattr(entry, "key", getattr(entry, "idx", None))
                 for entry in path)


@dataclasses.dataclass(frozen=True)
class Link:
    """``fn(params, carry, batch) -> (carry, stats)`` over the whole
    leaves under ``keys`` (the module's text says what a key is)."""

    fn: Callable
    keys: Tuple[Key, ...]

    def __post_init__(self):
        keys = self.keys
        if isinstance(keys, str):
            keys = (keys,)
        elif any(isinstance(step, int) for step in keys):
            keys = (tuple(keys),)  # ONE path: a name, positions below it
        else:
            keys = tuple(key if isinstance(key, str) else tuple(key)
                         for key in keys)
        paths = [_path(key) for key in keys]
        if any(a[:len(b)] == b for n, a in enumerate(paths)
               for m, b in enumerate(paths) if n != m):
            raise ValueError(f"a link's keys lie apart in the tree: {keys}")
        object.__setattr__(self, "keys", keys)

    def pick(self, params) -> Dict[Any, Any]:
        """The subtrees under ``keys``, in the shape of the tree above
        them: ``{"embed": ...}``, ``{"runs": {2: ...}}``. Raises (a
        lookup's error) where a key names no subtree."""
        out: Dict[Any, Any] = {}
        for key in self.keys:
            *above, name = _path(key)
            node, sub = out, params
            for step in above:
                node, sub = node.setdefault(step, {}), sub[step]
            node[name] = sub[name]
        return out

    def covers(self, steps: tuple) -> bool:
        """Whether the leaf at ``steps`` (``_steps``) lies under a key."""
        return any(steps[:len(path)] == path
                   for path in map(_path, self.keys))

    def __call__(self, p, carry, batch):
        return self.fn(p, carry, batch)


@dataclasses.dataclass(frozen=True)
class Run(Link):
    """``depth`` like layers, stacked under the ONE key:
    ``fn(layer, carry, consts)`` is one of them, ``consts(batch)`` what
    all of them read and none changes (computed once a program, outside
    the scan: a rotary table), ``each(batch)`` what layer ``j`` ALONE
    reads and is no parameter (a buffer a layer: a router's selection
    bias), stacked on a leading axis of ``depth`` and scanned beside the
    leaves: the block is then ``fn(layer, carry, consts, layer j's
    slice)``; ``stats(stacked)`` turns the layers' statistics, stacked
    on a leading axis, into the run's.

    ``kinds``: ``depth`` hashable labels, layer ``j``'s STATIC kind
    (what Python decides while the block is traced: a mask, which rotary
    table); the block is then ``fn(layer, carry, consts[, the layer's
    slice], kind)``. Such a run is a scan over the ``period`` of its
    kinds, one block a layer of the period in its body (a scan over the
    layers where all are of one kind); a cut backward runs one
    executable a distinct kind, the layer's index traced."""

    depth: int = 1
    remat: bool = True
    unroll: int = 1
    consts: Optional[Callable] = None
    stats: Optional[Callable] = None
    each: Optional[Callable] = None
    kinds: Optional[Tuple[Hashable, ...]] = None

    def __post_init__(self):
        super().__post_init__()
        if self.kinds is not None:
            object.__setattr__(self, "kinds", tuple(self.kinds))

    @property
    def layer_kinds(self) -> tuple:
        """A kind a layer: ``kinds``, or None ``depth`` times."""
        return (None,) * self.depth if self.kinds is None else self.kinds

    def _consts(self, batch):
        return batch if self.consts is None else self.consts(batch)

    def _each(self, batch):
        return None if self.each is None else self.each(batch)

    def stacked(self, p):
        """The run's stacked leaves in ``p`` (the whole tree, or what
        ``pick`` made of it)."""
        for step in _path(self.keys[0]):
            p = p[step]
        return p

    def scan(self, stacked, carry, consts, keep: bool = False, each=None,
             kinds=None):
        """The run over ``stacked`` (any depth; ``each``: the layers'
        own slices, as deep; ``kinds``: those layers' kinds, the run's
        own where not given) -> (carry, the layers' stacked
        statistics); ``keep``: the layers' INPUT carries, stacked,
        beside the statistics."""
        kinds = self.kinds if kinds is None else tuple(kinds)
        depth = jax.tree.leaves(stacked)[0].shape[0]
        if kinds is not None and len(kinds) != depth:
            raise ValueError(
                f"a run of {depth} layers has {len(kinds)} kinds: {kinds}")

        def block_of(*kind):
            def block(x, p, consts, *own):
                return self.fn(p, x, consts, *own, *kind)
            return jax.checkpoint(block) if self.remat else block

        def step(block, x, layer):
            p, *own = (layer,) if each is None else layer
            y, st = block(x, p, consts, *own)
            return y, ((x, st) if keep else st)

        xs = stacked if each is None else (stacked, each)
        span = 1 if kinds is None else period(kinds)
        if span == 1:
            block = block_of(*(() if kinds is None else kinds[:1]))

            def body(x, layer):
                return step(block, x, layer)

            return jax.lax.scan(body, carry, xs,
                                unroll=min(self.unroll, depth))

        # layers of unlike kinds: a scan over the periods, a block of
        # its own kind for each layer of one
        of = {kind: block_of(kind) for kind in dict.fromkeys(kinds[:span])}
        blocks = [of[kind] for kind in kinds[:span]]

        def body(x, layers):
            outs = []
            for j, block in enumerate(blocks):
                x, out = step(block, x,
                              jax.tree.map(lambda a: a[j], layers))
                outs.append(out)
            return x, jax.tree.map(lambda *a: jnp.stack(a), *outs)

        carry, outs = jax.lax.scan(
            body, carry,
            jax.tree.map(lambda a: a.reshape(depth // span, span,
                                             *a.shape[1:]), xs),
            unroll=min(self.unroll, depth // span))
        return carry, jax.tree.map(
            lambda a: a.reshape(depth, *a.shape[2:]), outs)

    def __call__(self, p, carry, batch):
        carry, stacked = self.scan(self.stacked(p), carry,
                                   self._consts(batch),
                                   each=self._each(batch))
        return carry, stacked if self.stats is None else self.stats(stacked)


@dataclasses.dataclass(frozen=True)
class Chain:
    links: Tuple[Link, ...]

    def __post_init__(self):
        object.__setattr__(self, "links", tuple(self.links))

    def __call__(self, params, batch):
        found, first = _collector.get() or (None, False)
        if found is not None:
            found.append(self)
            if first:
                raise _Called
        carry, stats = None, {}
        for ln in self.links:
            carry, st = ln(ln.pick(params), carry, batch)
            add_stats(stats, st)
        return carry, stats

    # ---- what a step that cuts the backward needs ------------------- #

    def cover(self, params, paths=None
              ) -> Optional[Dict[int, Tuple[int, ...]]]:
        """Link index -> the flatten indices in ``params`` of the leaves
        under the link's keys, ascending; None where the backward cannot
        run a link at a time at the uncut FLOPs. It can where there is
        more than one link, every key names a subtree, every LEAF of
        ``params`` lies under one link or under several whole links (not
        under a run and another link: a run's stacked leaf is handed
        over a layer at a time), and every run is rematerialised, as
        deep as its leaves and, where its layers have kinds, as deep as
        those. ``paths``: ``params`` flattened with paths, where the
        caller has it."""
        if len(self.links) < 2:
            return None
        try:
            for ln in self.links:
                ln.pick(params)
        except (KeyError, IndexError, TypeError):
            return None  # a key that names no subtree
        if paths is None:
            paths = jax.tree_util.tree_flatten_with_path(params)[0]
        steps = [_steps(path) for path, _ in paths]
        under = {k: tuple(i for i, at in enumerate(steps) if ln.covers(at))
                 for k, ln in enumerate(self.links)}
        readers: Dict[int, List[int]] = {}
        for k, found in under.items():
            for i in found:
                readers.setdefault(i, []).append(k)
        if len(readers) != len(paths) or any(
                len(ks) > 1 and any(isinstance(self.links[k], Run)
                                    for k in ks)
                for ks in readers.values()):
            return None
        if not all(ln.remat and all(
                paths[i][1].ndim >= 1 and paths[i][1].shape[0] == ln.depth
                for i in under[k])
                and (ln.kinds is None or len(ln.kinds) == ln.depth)
                for k, ln in enumerate(self.links) if isinstance(ln, Run)):
            return None
        return under

    def cuts(self, params) -> bool:
        """Whether the backward can run a link at a time (``cover``)."""
        return self.cover(params) is not None

    def forward(self, params, batch):
        """Through every link but the last, keeping no residual:
        (``kept``, one entry a link: its input carry, a run's layers'
        input carries stacked; the statistics of the links run)."""
        carry, kept, stats = None, [], {}
        for ln in self.links[:-1]:
            if isinstance(ln, Run):
                plain = dataclasses.replace(ln, remat=False)
                carry, (inputs, st) = plain.scan(
                    ln.stacked(params), carry, ln._consts(batch), keep=True,
                    each=ln._each(batch))
                kept.append(inputs)
                add_stats(stats, st if ln.stats is None else ln.stats(st))
            else:
                kept.append(carry)
                carry, st = ln(ln.pick(params), carry, batch)
                add_stats(stats, st)
        kept.append(carry)
        return kept, stats

    def last(self, p, carry, batch):
        """The last link at its kept input: (loss, its statistics, the
        cotangent of its input, its gradients)."""
        (loss, stats), (g_p, g_carry) = jax.value_and_grad(
            lambda p, c: self.links[-1](p, c, batch), argnums=(0, 1),
            has_aux=True)(p, carry)
        return loss, stats, g_carry, g_p

    def pull_link(self, k: int, p, carry, batch, ct):
        """Link ``k`` at its kept input, pulled back by ``ct``: (the
        cotangent of its input, its gradients)."""
        _, vjp = jax.vjp(lambda p, c: self.links[k](p, c, batch)[0],
                         p, carry)
        g_p, g_carry = vjp(ct)
        return g_carry, g_p

    def pull_layer(self, k: int, p, j, inputs, batch, ct, kind=None):
        """Layer ``j`` (traced) of run ``k`` (``p``: its stacked leaves
        as picked) at its kept input ``inputs[j]``, pulled back by
        ``ct``: (the cotangent of its input, the layer's gradients, each
        ``[1, ...]``, in ``p``'s structure). ``kind`` (static): the
        layer's, where the run's layers have ``kinds``; ``j`` is then
        any layer of that kind. What is differentiated is the run's scan
        over ONE layer, not the bare block, so that a kernel inside is
        named as in the uncut program."""
        ln = self.links[k]
        kinds = None if ln.kinds is None else (kind,)

        def layer(tree):
            return jax.tree.map(
                lambda a: jax.lax.dynamic_slice_in_dim(a, j, 1, 0), tree)

        one, each = layer(p), ln._each(batch)
        each = None if each is None else layer(each)
        x = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, j, 0, keepdims=False),
            inputs)
        consts = ln._consts(batch)
        _, vjp = jax.vjp(
            lambda p, c: ln.scan(ln.stacked(p), c, consts, each=each,
                                 kinds=kinds)[0],
            one, x)
        g_one, g_x = vjp(ct)
        return g_x, g_one
