"""A loss written as a chain (ops/chain.py) and the PS step that cuts its
backward at the links (jax/train.py ``_cut_backward``): the chain
called plainly is the scan it replaces, bit for bit (``models/sdar.py``
against the parent's scan, kept here as its reference); a cut PS step
against a local server is the one-program step bit for bit, pushes the
same bytes and counts its programs, its pieces and the bytes that left
under the backward; each of the plan's rules keeps the backward one
program; the pieces' keys are a pure function of the tree; the
programs of the cut name their kernels' scope as the uncut one does; a
chain of SEVERAL runs of unlike blocks, runs of one layer among them and
a buffer a layer beside the leaves (``models/kimi.py``), is cut a
program a layer, and a run of one layer hands its leaves over whole; a
chain whose runs live inside a LIST and whose embedding is also its head
(a toy shaped like ``models/lfm2.py`` and ``models/joyai.py``) is cut
too: a link's key may be a path, and the leaf under two links leaves
once, its two terms summed on the device; a run whose layers are of
unlike static KINDS under one stacked key (a toy, then
``models/mellum.py``'s window, window, window, full against the
composition it replaced) is a period scan called plainly and is cut
into one executable a kind; and a run WITHOUT kinds is the run it was:
the cut programs of the five chained configurations that declare none
trace to the parent commit's jaxprs. All of it in this one file: xdist
hands out the files with the most tests first, and a late file of few
slow tests is what the suite's limit cannot afford."""

import collections
import dataclasses
import hashlib
import importlib
import itertools
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from byteps_tpu.ops import chain
from byteps_tpu.jax.train import (ExportPlan, _chain_leaves, _cut_backward,
                                  _declare_shard_keys, _dispatch_cut,
                                  _export_plan, make_ps_train_step,
                                  make_train_step)
from byteps_tpu.models import kimi, llama, mellum, sdar
from byteps_tpu.ops.push_pull import psum_tree

from benchmark.layers._cell import _overlay
from test_export_spans import _ps_env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# every leaf of the tiny models but the norms rides keys of its own
ENV = {"BYTEPS_FUSION_BYTES": "1024", "BYTEPS_SHARD_MIN_BYTES": "1024"}
# the servers' ports, this file's own (``_ps_env``)
PORTS = itertools.count(25600)


# --------------------------------------------------------------------- #
# models/sdar.py as a chain against the scan it was
# --------------------------------------------------------------------- #


def _parents_loss(params, batch, cfg):
    """``models/sdar.py loss_fn`` as it stood before it was a chain."""
    clean, noise, rates = (batch["tokens"], batch["noise_mask"],
                           batch["rates"])
    rows, n = clean.shape
    tokens = jnp.concatenate(
        [jnp.where(noise, cfg.mask_id, clean), clean], axis=1)
    rope = tuple(jnp.concatenate([t, t]) for t in llama.rope_cache(cfg, n))
    x = params["embed"].astype(cfg.dtype)[tokens]
    block = jax.checkpoint(sdar._block, static_argnums=(3, 4)) \
        if cfg.remat else sdar._block
    x, stats = jax.lax.scan(
        lambda x, p: block(x, p, rope, cfg, None), x, params["blocks"],
        unroll=min(sdar.LAYER_UNROLL, cfg.n_layers))
    x = llama._rmsnorm(x, params["final_norm"], cfg.norm_eps)
    stats = {name: v if v.ndim == 2 else jnp.sum(v)
             for name, v in stats.items()}
    logits = (x[:, :n] @ params["lm_head"].astype(cfg.dtype)
              ).astype(jnp.float32)
    nll = jax.scipy.special.logsumexp(logits, axis=-1) \
        - jnp.take_along_axis(logits, clean[..., None], axis=-1)[..., 0]
    weight = noise / jnp.repeat(rates.astype(jnp.float32),
                                cfg.block_length, axis=1)
    stats["diffusion/masked_tokens"] = jnp.sum(noise, dtype=jnp.int32)
    return jnp.sum(weight * nll) / (rows * n), stats


def _sdar(remat=True, n_layers=3, seed=3):
    cfg = dataclasses.replace(sdar.SDARConfig.tiny(), remat=remat,
                              n_layers=n_layers)
    key = jax.random.PRNGKey(seed)
    params = sdar.init_params(key, cfg)
    # norms off one, so that their gradients are no accident
    params = jax.tree.map(
        lambda a: a + 0.1 * jax.random.normal(key, a.shape, a.dtype)
        if a.ndim <= 2 and a.shape[-1] <= 32 else a, params)
    rows, n = 2, 32
    ks = jax.random.split(key, 3)
    batch = {"tokens": jax.random.randint(ks[0], (rows, n), 0, 63),
             "noise_mask": jax.random.bernoulli(ks[1], 0.6, (rows, n)),
             "rates": jax.random.uniform(ks[2], (rows, n // 4),
                                         minval=0.45, maxval=0.95)}
    return cfg, params, batch


def _assert_trees_equal(got, want):
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("remat", [True, False], ids=["remat", "no-remat"])
def test_the_chain_called_plainly_is_the_scan_it_replaces(remat):
    cfg, params, batch = _sdar(remat)
    (loss, stats), grads = jax.jit(jax.value_and_grad(
        lambda p, b: sdar.loss_fn(p, b, cfg), has_aux=True))(params, batch)
    (want, want_stats), want_grads = jax.jit(jax.value_and_grad(
        lambda p, b: _parents_loss(p, b, cfg), has_aux=True))(params, batch)
    assert float(loss) == float(want) and float(loss) > 0
    _assert_trees_equal(stats, want_stats)
    assert stats["moe/expert_load"].shape == (3, 4)
    _assert_trees_equal(grads, want_grads)
    assert all(np.any(np.asarray(g)) for g in jax.tree.leaves(grads))


def test_a_called_chain_registers_with_the_collector_and_nowhere_else():
    cfg, params, batch = _sdar()

    def trace():  # a function of its own each time: no trace is cached
        jax.eval_shape(lambda p, b: sdar.loss_fn(p, b, cfg), params, batch)

    with chain.collecting() as found:
        jax.eval_shape(jax.value_and_grad(
            lambda p, b: sdar.loss_fn(p, b, cfg), has_aux=True),
            params, batch)
    (ch,) = found
    assert [type(ln).__name__ for ln in ch.links] == ["Link", "Run", "Link"]
    assert [ln.keys for ln in ch.links] == [
        ("embed",), ("blocks",), ("final_norm", "lm_head")]
    assert ch.cuts(params)
    # outside a collector a call leaves no trace; the collector is this
    # context's alone
    trace()
    assert len(found) == 1
    with chain.collecting() as outer:
        with chain.collecting() as inner:
            trace()
        assert len(inner) == 1 and outer == []


def test_a_collector_may_end_its_block_at_the_first_chain_called():
    """``collecting(first=True)``: what the block was doing is abandoned
    at the first chain's call (the step asks whether a loss calls a
    chain at all without tracing its whole backward); a block that calls
    none runs to its end."""
    cfg, params, batch = _sdar()
    after = []

    def trace():
        jax.eval_shape(jax.value_and_grad(
            lambda p, b: sdar.loss_fn(p, b, cfg), has_aux=True),
            params, batch)
        after.append("ran on")

    with chain.collecting(first=True) as found:
        trace()
    assert len(found) == 1 and after == []
    with chain.collecting(first=True) as found:
        jax.eval_shape(lambda p, b: _parents_loss(p, b, cfg), params, batch)
        after.append("no chain")
    assert found == [] and after == ["no chain"]
    # and no collector is left behind
    trace()
    assert after == ["no chain", "ran on"]


def _chain_of(cfg):
    return chain.Chain([chain.Link(None, "embed"), sdar._layers(cfg, None),
                        chain.Link(None, ["final_norm", "lm_head"])])


@pytest.mark.parametrize("why,change", [
    ("a run that keeps its residuals",
     lambda cfg, p: (dataclasses.replace(cfg, remat=False), p)),
    ("a leaf under no link",
     lambda cfg, p: (cfg, {**p, "extra": jnp.zeros(3)})),
    ("a link with no leaf",
     lambda cfg, p: (cfg, {k: v for k, v in p.items() if k != "embed"})),
    ("a run deeper than it says",
     lambda cfg, p: (dataclasses.replace(cfg, n_layers=2), p)),
    ("a tree with no keys", lambda cfg, p: (cfg, list(p.values()))),
    # (a leaf under several links is fine only where each is a WHOLE
    # link: ``_toy`` below is cut)
    ("a run's stacked leaf under two links",
     lambda cfg, p: (cfg, p, chain.Chain([
         chain.Link(None, "embed"), sdar._layers(cfg, None),
         chain.Link(None, ["final_norm", "lm_head", "blocks"])]))),
    ("a leaf of a run's under a link beside it",
     lambda cfg, p: (cfg, p, chain.Chain([
         chain.Link(None, ["embed", ("blocks", "wq")]),
         sdar._layers(cfg, None),
         chain.Link(None, ["final_norm", "lm_head"])]))),
    ("a path that names no subtree",
     lambda cfg, p: (cfg, p, chain.Chain([
         chain.Link(None, "embed"),
         dataclasses.replace(sdar._layers(cfg, None), keys=("blocks", 3)),
         chain.Link(None, ["final_norm", "lm_head"])]))),
    ("a path that ends inside a leaf",
     lambda cfg, p: (cfg, p, chain.Chain([
         chain.Link(None, ("embed", 0)), sdar._layers(cfg, None),
         chain.Link(None, ["final_norm", "lm_head"])]))),
    ("a run with fewer kinds than layers",
     lambda cfg, p: (cfg, p, chain.Chain([
         chain.Link(None, "embed"),
         dataclasses.replace(sdar._layers(cfg, None), kinds=("a", "b")),
         chain.Link(None, ["final_norm", "lm_head"])]))),
], ids=lambda v: v.replace(" ", "-") if isinstance(v, str) else "")
def test_what_a_chain_cannot_be_cut_over(why, change):
    cfg, params, _ = _sdar()
    assert _chain_of(cfg).cuts(params)
    cfg, changed, *other = change(cfg, params)
    ch = other[0] if other else _chain_of(cfg)
    assert not ch.cuts(changed), why
    assert _chain_leaves(ch, changed) is None
    # one link is nothing to cut
    assert not chain.Chain([chain.Link(None, tuple(params))]).cuts(params)


def test_a_links_keys_are_one_name_or_several_and_a_chains_links_any_list():
    one, two = chain.Link(None, "embed"), chain.Link(None, ["a", "b"])
    assert one.keys == ("embed",) and two.keys == ("a", "b")
    layers = chain.Run(None, "blocks", 3, remat=False)
    assert layers.keys == ("blocks",) and layers.depth == 3
    assert dataclasses.replace(layers, remat=True).keys == ("blocks",)
    assert chain.Chain([one, layers, two]).links == (one, layers, two)
    assert one.pick({"embed": 1, "other": 2}) == {"embed": 1}


def test_a_key_may_be_a_path_and_a_link_is_handed_its_subtrees():
    """A name and the positions below it; a tuple that holds a position
    is ONE path, a path of names alone a list of one."""
    run = chain.Run(None, ("runs", 2), 3)
    assert run.keys == (("runs", 2),) and run.depth == 3
    assert dataclasses.replace(run, remat=False).keys == (("runs", 2),)
    two = chain.Link(None, [("runs", 0), "final_norm"])
    assert two.keys == (("runs", 0), "final_norm")
    assert chain.Link(None, [("extra", "inner")]).keys == (
        ("extra", "inner"),)
    tree = {"embed": 1, "runs": [{"w": 2}, {"w": 3}, {"w": 4}],
            "final_norm": 5, "extra": {"inner": 6, "other": 7}}
    # in the shape of the tree above them: ``fn`` reads p["runs"][2]
    assert run.pick(tree) == {"runs": {2: {"w": 4}}}
    assert run.stacked(tree) is tree["runs"][2]
    assert run.stacked(run.pick(tree)) is tree["runs"][2]
    assert two.pick(tree) == {"runs": {0: {"w": 2}}, "final_norm": 5}
    assert chain.Link(None, [("extra", "inner"), ("runs", 1), ("runs", 0)]
                      ).pick(tree) == {"extra": {"inner": 6},
                                       "runs": {1: {"w": 3}, 0: {"w": 2}}}
    # and flattens in the tree's order
    assert jax.tree.leaves(chain.Link(
        None, [("runs", 1), ("extra", "inner"), ("runs", 0), "embed"]
    ).pick(tree)) == [1, 6, 2, 3]
    for missing in (("runs", 3), "head", [("extra", "none")]):
        with pytest.raises((KeyError, IndexError)):
            chain.Link(None, missing).pick(tree)
    # a link's keys lie apart: none is above another
    with pytest.raises(ValueError):
        chain.Link(None, ["runs", ("runs", 1)])
    with pytest.raises(ValueError):
        chain.Link(None, ["embed", "embed"])


def test_the_links_leaves_are_runs_of_the_trees_flatten_order():
    cfg, params, batch = _sdar()
    with chain.collecting() as found:
        jax.eval_shape(lambda p, b: sdar.loss_fn(p, b, cfg), params, batch)
    leaves = _chain_leaves(found[0], params)
    # blocks (12 stacked leaves), embed, final_norm, lm_head
    assert leaves == {0: (12,), 1: tuple(range(12)), 2: (13, 14)}


# --------------------------------------------------------------------- #
# the cut programs against the one program, no server
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("devices", [1, 2], ids=["1dev", "2dev"])
def test_the_cut_programs_give_the_one_programs_loss_stats_and_gradients(
        devices):
    from byteps_tpu.jax.train import _loss_and_stats, _psum_backward

    cfg, params, batch = _sdar()
    mesh = Mesh(np.array(jax.devices()[:devices]), ("dp",))
    loss = lambda p, b: sdar.loss_fn(p, b, cfg)  # noqa: E731
    with chain.collecting() as found:
        whole = _psum_backward(_loss_and_stats(loss), mesh, "dp")
        (want, want_stats), want_grads = whole(params, batch)
    cut = _cut_backward(found[0], mesh, "dp",
                        _chain_leaves(found[0], params))
    # forward, head, three layers, embedding
    assert cut.programs == 6
    (got, stats), programs = _dispatch_cut(cut, params, batch)
    assert [(links, layer) for links, layer, _, _ in programs] == [
        ("0-1", None), ("2", None), ("1", 2), ("1", 1), ("1", 0),
        ("0", None)]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    _assert_trees_equal(stats, want_stats)
    flat = jax.tree.leaves(want_grads)
    seen = set()
    for _, layer, ready, outs in programs:
        for i, g in outs.items():
            w = np.asarray(flat[i])
            if layer is not None:
                assert g.shape == (1,) + w.shape[1:]
                w = w[layer:layer + 1]
            np.testing.assert_array_equal(np.asarray(g), w, err_msg=str(i))
            seen.add((i, layer))
    assert len(seen) == 3 + 12 * 3


# --------------------------------------------------------------------- #
# PS steps against a local server
# --------------------------------------------------------------------- #

COUNTERS = ("export/backward_programs", "export/piece_bytes",
            "export/under_backward_bytes", "export/whole_bytes",
            "wire/push_bytes", "export/shared_leaves",
            "export/shared_carry_bytes")


def _run_ps(loss, params, batch, steps=3, devices=1, env=None, port=None,
            **kw):
    """``steps`` PS steps of adam -> (params, opt state, losses, the
    counters' growth, the last step's spans and reports). ``port``: the
    server's, one of the caller's own where another file calls."""
    from byteps_tpu.core.state import get_state

    tx = optax.adam(1e-2)
    mesh = Mesh(np.array(jax.devices()[:devices]), ("dp",))
    params = jax.tree.map(jnp.array, params)  # the step donates its own
    with _ps_env({**ENV, **(env or {})}, port=port or next(PORTS)) as bps:
        step = make_ps_train_step(loss, tx, mesh, **kw)
        before = bps.get_metrics()["counters"]
        opt, losses = tx.init(params), []
        for _ in range(steps):
            params, opt, value = step(params, opt, batch)
            losses.append(float(value))
        jax.block_until_ready((params, opt))
        after = bps.get_metrics()["counters"]
        out = {"params": params, "opt": opt, "losses": losses,
               "ledger": bps.get_ledger(),
               "grew": {c: after.get(c, 0) - before.get(c, 0)
                        for c in COUNTERS},
               "spans": get_state().profiler.last_spans(),
               "reports": bps.get_step_reports()[-steps:],
               "keys": {c.name: c.declared_key
                        for c in get_state().registry.contexts_in_order()}}
    return out


@pytest.fixture(scope="module")
def cut_and_whole():
    """Three steps of the chained loss (cut: four programs and three
    layers') and of the scan it replaces (no chain: one program)."""
    cfg, params, batch = _sdar()
    n_bytes = sum(leaf.nbytes for leaf in jax.tree.leaves(params))
    cut = _run_ps(lambda p, b: sdar.loss_fn(p, b, cfg), params, batch)
    whole = _run_ps(lambda p, b: _parents_loss(p, b, cfg), params, batch)
    return cut, whole, n_bytes, params


def test_a_cut_step_is_the_one_program_step_bit_for_bit(cut_and_whole):
    cut, whole, _, _ = cut_and_whole
    assert cut["losses"] == whole["losses"]
    assert cut["losses"][-1] < cut["losses"][0]
    _assert_trees_equal(cut["params"], whole["params"])
    _assert_trees_equal(cut["opt"], whole["opt"])


def test_a_cut_step_pushes_every_gradient_byte_once_and_counts_itself(
        cut_and_whole):
    cut, whole, n_bytes, params = cut_and_whole
    for run in (cut, whole):
        assert run["grew"]["wire/push_bytes"] == 3 * n_bytes
        assert run["grew"]["export/whole_bytes"] == 3 * n_bytes
    # forward, head, three layers, embedding; one where nothing is cut
    assert cut["grew"]["export/backward_programs"] == 3 * 6
    assert whole["grew"]["export/backward_programs"] == 3
    # the eight weights of a layer leave as pieces, the four norms whole
    blocks = params["blocks"]
    pieces = sum(v.nbytes for k, v in blocks.items() if "norm" not in k)
    assert cut["grew"]["export/piece_bytes"] == 3 * pieces
    assert whole["grew"]["export/piece_bytes"] == 0
    # what left before the last program was seen to have ended: the
    # head's leaf at least (it is claimed before anyone looks), at most
    # all but the last program's own leaf and what waits for the end,
    # the norms and final_norm
    late = params["embed"].nbytes + params["final_norm"].nbytes \
        + sum(v.nbytes for k, v in blocks.items() if "norm" in k)
    assert 3 * params["lm_head"].nbytes \
        <= cut["grew"]["export/under_backward_bytes"] <= 3 * (n_bytes - late)
    assert whole["grew"]["export/under_backward_bytes"] == 0
    # StepReport keeps the two counts the benchmark subscripts
    for run in (cut, whole):
        assert run["reports"][-1]["fallback_leaves"] == 15
        assert run["reports"][-1]["streamed_leaves"] == 0


def test_a_cut_steps_keys_are_the_one_program_steps_and_the_pieces(
        cut_and_whole):
    cut, whole, _, _ = cut_and_whole
    piece_names = {n for n in cut["keys"] if "@shard" in n}
    assert len(piece_names) == 8 * 3
    assert {n.split("@")[1] for n in piece_names} == {
        "shard0of3", "shard1of3", "shard2of3"}
    # the bucket of the five norms has the one-program step's digest, the
    # whole leaves their names
    fused = {n for n in cut["keys"] if n.startswith("fused/")}
    assert fused and fused == {n for n in whole["keys"]
                               if n.startswith("fused/")}
    for name in ("grad/embed", "grad/lm_head"):
        assert name in cut["keys"] and name in whole["keys"]


def test_the_pieces_keys_are_the_same_on_two_workers():
    """Declared from the plan alone, in flatten order, before any leaf
    is claimed: two registries that realise the same plan agree."""
    from byteps_tpu.config import Config
    from byteps_tpu.core.registry import TensorRegistry

    cfg, params, _ = _sdar()
    paths = jax.tree_util.tree_flatten_with_path(params)[0]
    names = ["grad/" + "/".join(str(k.key) for k in path)
             for path, _ in paths]
    leaves = [leaf for _, leaf in paths]
    plan = _export_plan(
        names, leaves, mesh=Mesh(np.array(jax.devices()[:1]), ("dp",)),
        axis="dp", fusion_bytes=1024, shard_min_bytes=1024,
        local_shard=True, rowsparse_params=None, host_codec=False,
        scheduler_running=True, stacked={i: 3 for i in range(12)})
    assert plan.shard_set == () and plan.n_shard == 0
    assert [i for i, _ in plan.pieces] == [4, 5, 6, 7, 8, 9, 10, 11]
    keys = []
    for _ in range(2):
        registry = TensorRegistry(Config(num_workers=2, num_servers=1))
        registry.declare("something/else")
        info = _declare_shard_keys(registry, names, leaves, plan, set())
        keys.append({n: registry.get(n).declared_key
                     for i in info for n in info[i]["names"]})
        assert [registry.get(n).partitions[0].length
                for n in info[4]["names"]] == [leaves[4].nbytes // 3] * 3
    assert keys[0] == keys[1] and len(keys[0]) == 24
    # a plan that has no piece frees them
    registry.declare("grad/embed")
    _declare_shard_keys(registry, names, leaves, ExportPlan(), set(keys[0]))
    assert not any(registry.is_declared(n) for n in keys[0])


def _twice(cfg):
    def loss(p, b):
        a, stats = sdar.loss_fn(p, b, cfg)
        return (a + sdar.loss_fn(p, b, cfg)[0]) / 2, stats
    return loss


@pytest.mark.parametrize("rule", [
    "remat off", "a host codec", "a plan that shards", "no chain",
    "a chain called twice", "every leaf under the fusion size"])
def test_each_rule_of_the_plan_keeps_the_backward_one_program(rule):
    cfg, params, batch = _sdar(remat=rule != "remat off")
    loss = lambda p, b: sdar.loss_fn(p, b, cfg)  # noqa: E731
    kw, env, devices = {}, {}, 1
    if rule == "a host codec":
        kw = {"compression": {"compressor": "onebit", "ef": "vanilla"},
              "device_compress": False}
    elif rule == "a plan that shards":
        devices = 2
        batch = jax.tree.map(lambda a: jnp.concatenate([a, a]), batch)
    elif rule == "no chain":
        loss = lambda p, b: _parents_loss(p, b, cfg)  # noqa: E731
    elif rule == "a chain called twice":
        loss = _twice(cfg)
    elif rule == "every leaf under the fusion size":
        env = {"BYTEPS_FUSION_BYTES": str(1 << 20)}
    out = _run_ps(loss, params, batch, steps=2, devices=devices, env=env,
                  **kw)
    assert out["grew"]["export/backward_programs"] == 2
    assert out["grew"]["export/piece_bytes"] == 0
    assert out["grew"]["export/under_backward_bytes"] == 0
    assert not any("@shard" in n and "of3" in n for n in out["keys"])
    assert out["losses"][1] < out["losses"][0]


def test_on_a_mesh_that_shards_nothing_the_cut_step_is_the_one_programs():
    """Two devices, ``local_shard_export`` off: the programs run over the
    mesh, carries a device's own, gradients psum'd link by link."""
    cfg, params, batch = _sdar()
    batch = jax.tree.map(lambda a: jnp.concatenate([a, a[::-1]]), batch)
    cut = _run_ps(lambda p, b: sdar.loss_fn(p, b, cfg), params, batch,
                  devices=2, local_shard_export=False)
    whole = _run_ps(lambda p, b: _parents_loss(p, b, cfg), params, batch,
                    devices=2, local_shard_export=False)
    assert cut["grew"]["export/backward_programs"] == 3 * 6
    assert whole["grew"]["export/backward_programs"] == 3
    assert cut["losses"] == whole["losses"]
    _assert_trees_equal(cut["params"], whole["params"])
    _assert_trees_equal(cut["opt"], whole["opt"])


def _rows(batch, rows):
    return jax.tree.map(lambda a: a[:rows], batch)


def test_a_cut_step_with_another_row_count_is_the_one_program_steps():
    """An epoch's last batch: the links are traced anew at its shapes
    and read their row count from it (a head that kept the first
    batch's would scale the loss and every gradient by the old one)."""
    cfg, params, batch = _sdar()
    batch = jax.tree.map(lambda a: jnp.concatenate([a, a[::-1], a]), batch)
    batches = [batch, _rows(batch, 2), batch]

    def run(loss):
        from byteps_tpu.core.state import get_state

        tx = optax.adam(1e-2)
        mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
        with _ps_env(ENV, port=next(PORTS)) as bps:
            step = make_ps_train_step(loss, tx, mesh)
            p, opt = jax.tree.map(jnp.array, params), tx.init(params)
            before = bps.get_metrics()["counters"]
            losses = []
            for b in batches:
                p, opt, value = step(p, opt, b)
                losses.append(float(value))
            jax.block_until_ready((p, opt))
            programs = bps.get_metrics()["counters"][
                "export/backward_programs"] - before.get(
                    "export/backward_programs", 0)
            keys = [c.name for c in get_state().registry.contexts_in_order()]
        return p, opt, losses, programs, keys

    cut = run(lambda p, b: sdar.loss_fn(p, b, cfg))
    whole = run(lambda p, b: _parents_loss(p, b, cfg))
    # every step cut, the short one too, on the pieces' keys declared once
    assert cut[3] == 3 * 6 and whole[3] == 3
    assert len([n for n in cut[4] if "@shard" in n]) == 8 * 3
    assert cut[2] == whole[2]
    _assert_trees_equal(cut[:2], whole[:2])


def test_a_loss_that_is_a_chain_at_some_shapes_only_is_cut_at_those():
    """The chain is collected for each new shape of the batch, as the
    one program is traced: a loss that calls no chain on a short batch
    runs that batch as one program, and the next full one cut again."""
    cfg, params, batch = _sdar()
    batch = jax.tree.map(lambda a: jnp.concatenate([a, a[::-1]]), batch)

    def loss(p, b):
        if b["tokens"].shape[0] < 4:
            return _parents_loss(p, b, cfg)
        return sdar.loss_fn(p, b, cfg)

    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    tx = optax.adam(1e-2)
    with _ps_env(ENV, port=next(PORTS)) as bps:
        step = make_ps_train_step(loss, tx, mesh)
        p, opt = jax.tree.map(jnp.array, params), tx.init(params)
        grew = []
        for b in (batch, _rows(batch, 2), batch):
            before = bps.get_metrics()["counters"].get(
                "export/backward_programs", 0)
            p, opt, value = step(p, opt, b)
            grew.append(bps.get_metrics()["counters"][
                "export/backward_programs"] - before)
        jax.block_until_ready((p, opt))
    assert grew == [6, 1, 6] and np.isfinite(float(value))


def test_the_fused_step_runs_the_chain_as_the_scan():
    cfg, params, batch = _sdar()
    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    tx = optax.adam(1e-2)

    def run(loss):
        step = make_train_step(
            loss, tx, mesh, donate=False,
            grads_transform=lambda g: psum_tree(g, axis="dp", average=True))
        p, o, value = step(params, tx.init(params), batch)
        step.fold_stats()
        return p, o, float(value)

    got = run(lambda p, b: sdar.loss_fn(p, b, cfg))
    want = run(lambda p, b: _parents_loss(p, b, cfg))
    assert got[2] == want[2]
    _assert_trees_equal(got[:2], want[:2])


# --------------------------------------------------------------------- #
# several runs of unlike blocks, runs of one layer (models/kimi.py)
# --------------------------------------------------------------------- #


def _kimi(seed=5):
    """The benchmark cell's five layers at test size, rematerialised:
    runs of depths 1, 2, 1, 1 between the embedding and the head, a
    selection bias a sparse layer beside the leaves."""
    cfg = dataclasses.replace(kimi.KimiConfig.tiny(), remat=True)
    key = jax.random.PRNGKey(seed)
    params = kimi.init_params(key, cfg)
    tokens = jax.random.randint(key, (4, 33), 0, cfg.vocab_size)
    bias = jax.random.uniform(key, (4, cfg.n_experts), minval=-0.1,
                              maxval=0.1)
    return cfg, params, {"tokens": tokens}, bias


def _unchained(cfg, bias):
    """``kimi.loss_fn`` link by link, with no chain called: what no
    step can cut."""
    def loss(params, batch):
        carry, stats = None, {}
        for ln in kimi._chain(cfg, bias, None).links:
            carry, st = ln(ln.pick(params), carry, batch)
            chain.add_stats(stats, st)
        return carry, stats
    return loss


def _assert_trees_close(got, want, rtol=2e-6, atol=0.0):
    """To float32's last digits. XLA:CPU compiles this model's head
    alone and inside the whole backward to programs that differ in the
    last bit (the mean's scalar moved through a product), and every
    cotangent behind it inherits that; SDAR's head, weighed a position,
    compiles alike, and its steps are compared bit for bit above."""
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        w = np.asarray(w)
        np.testing.assert_allclose(
            np.asarray(g), w, rtol=rtol,
            atol=max(atol, rtol * float(np.abs(w).max()) if w.size else 0),
            err_msg=jax.tree_util.keystr(path))


def test_links_that_count_under_one_name_add_up():
    stats = chain.add_stats({"a": 1, "b": np.array([1, 2])},
                            {"b": np.array([3, 4]), "c": 5})
    assert stats["a"] == 1 and stats["c"] == 5
    np.testing.assert_array_equal(stats["b"], [4, 6])


def test_a_run_scans_what_each_layer_alone_reads_beside_its_leaves():
    """``each``: layer ``j`` is handed row ``j``; the run cut at layer
    ``j`` pulls back through that row alone."""
    run = chain.Run(lambda p, x, _, row: (x * p["w"] + row, {}), "w3", 3,
                    each=lambda batch: batch["rows"])
    params = {"w3": {"w": jnp.array([2.0, 3.0, 5.0])}}
    batch = {"rows": jnp.array([0.5, 0.25, 0.125])}
    out, _ = run(params, jnp.float32(1.0), batch)
    assert float(out) == ((1 * 2 + 0.5) * 3 + 0.25) * 5 + 0.125
    ch = chain.Chain([chain.Link(None, "a"), run, chain.Link(None, "b")])
    g_x, g_p = ch.pull_layer(1, params, 1, jnp.array([1.0, 2.5, 7.75]),
                             batch, jnp.float32(1.0))
    assert float(g_x) == 3.0
    np.testing.assert_array_equal(g_p["w3"]["w"], [2.5])


@pytest.mark.parametrize("devices", [1, 2], ids=["1dev", "2dev"])
def test_a_chain_of_unlike_runs_is_cut_a_program_a_layer(devices):
    from byteps_tpu.jax.train import _loss_and_stats, _psum_backward

    cfg, params, batch, bias = _kimi()
    if devices == 2:
        batch = {"tokens": jnp.concatenate([batch["tokens"]] * 2)[:4]}
    mesh = Mesh(np.array(jax.devices()[:devices]), ("dp",))
    loss = lambda p, b: kimi.loss_fn(p, b, cfg, bias)  # noqa: E731
    with chain.collecting() as found:
        whole = _psum_backward(_loss_and_stats(loss), mesh, "dp")
        (want, want_stats), want_grads = whole(params, batch)
    leaves = _chain_leaves(found[0], params)
    # embed, final_norm and lm_head, then the runs, in flatten order
    assert [len(leaves[k]) for k in range(6)] == [1, 21, 25, 14, 25, 2]
    cut = _cut_backward(found[0], mesh, "dp", leaves)
    # forward, head, five layers, embedding
    assert cut.programs == 8
    (got, stats), programs = _dispatch_cut(cut, params, batch)
    assert [(links, layer) for links, layer, _, _ in programs] == [
        ("0-4", None), ("5", None), ("4", 0), ("3", 0), ("2", 1), ("2", 0),
        ("1", 0), ("0", None)]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    _assert_trees_equal(stats, want_stats)
    assert stats["moe/expert_load"].shape == (4, 4)
    assert int(stats["kda/chunk_steps"]) > 0
    flat = jax.tree.leaves(want_grads)
    seen = set()
    for _, layer, _, outs in programs:
        for i, g in outs.items():
            w = np.asarray(flat[i])
            if layer is not None:
                assert g.shape == (1,) + w.shape[1:]
                w = w[layer:layer + 1]
            _assert_trees_close(g, w)
            seen.add((i, layer))
    assert len(seen) == 3 + 21 + 2 * 25 + 14 + 25


def test_a_cut_step_over_unlike_runs_is_the_one_program_step():
    """Three steps, a short batch between two full ones: losses,
    parameters and optimizer state the one-program step's to float32's
    last digits (``_assert_trees_close`` says why not to the bit);
    eight programs a step; the run of two layers leaves as pieces, the
    runs of one hand their leaves over whole, on the one-program step's
    keys, and under the backward."""
    cfg, params, batch, bias = _kimi()
    batches = [batch, _rows(batch, 2), batch]
    n_bytes = sum(leaf.nbytes for leaf in jax.tree.leaves(params))

    def run(loss):
        from byteps_tpu.core.state import get_state

        tx = optax.adam(1e-2)
        mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
        with _ps_env(ENV, port=next(PORTS)) as bps:
            step = make_ps_train_step(loss, tx, mesh)
            p, opt = jax.tree.map(jnp.array, params), tx.init(params)
            before = bps.get_metrics()["counters"]
            losses = []
            for b in batches:
                p, opt, value = step(p, opt, b)
                losses.append(float(value))
            jax.block_until_ready((p, opt))
            after = bps.get_metrics()["counters"]
            keys = [c.name for c in get_state().registry.contexts_in_order()]
            spans = get_state().profiler.last_spans()
        grew = {c: after.get(c, 0) - before.get(c, 0) for c in COUNTERS}
        return p, opt, losses, grew, keys, spans

    cut = run(lambda p, b: kimi.loss_fn(p, b, cfg, bias))
    whole = run(_unchained(cfg, bias))
    np.testing.assert_allclose(cut[2], whole[2], rtol=1e-6)
    assert cut[2][-1] < cut[2][0]
    # (adam divides by a gradient's own size: where a gradient is
    # rounding alone, a last digit of it is a visible share of one
    # update of 1e-2; a hundredth of an update is the floor here)
    _assert_trees_close(cut[:2], whole[:2], rtol=2e-4, atol=1e-4)
    assert cut[3]["export/backward_programs"] == 3 * 8
    assert whole[3]["export/backward_programs"] == 3
    for side in (cut, whole):
        assert side[3]["wire/push_bytes"] == 3 * n_bytes
        assert side[3]["export/whole_bytes"] == 3 * n_bytes
    # the pieces: the run of two layers' leaves over the fusion size
    own = lambda a: a.nbytes >= 1024  # noqa: E731
    pieces = sum(a.nbytes for a in jax.tree.leaves(params["run01"])
                 if own(a))
    assert cut[3]["export/piece_bytes"] == 3 * pieces > 0
    assert whole[3]["export/piece_bytes"] == 0
    piece_names = {n for n in cut[4] if "@shard" in n}
    assert piece_names and all("run01" in n and n.endswith("of2")
                               for n in piece_names)
    # a run of one layer: its leaves on the one-program step's keys
    for name in ("grad/run00/op/wq", "grad/run02/op/wkv_b",
                 "grad/run03/ffn/w_gate", "grad/embed", "grad/lm_head"):
        assert name in cut[4] and name in whole[4], name
    assert {n for n in cut[4] if n.startswith("fused/")} == \
        {n for n in whole[4] if n.startswith("fused/")}
    # what left before the last program was seen to have ended: the
    # head's leaf at least (a tiny backward ends under the first claims)
    assert 3 * params["lm_head"].nbytes \
        <= cut[3]["export/under_backward_bytes"] <= 3 * n_bytes
    assert whole[3]["export/under_backward_bytes"] == 0
    # a span a program; a whole leaf of a run of ONE layer is claimed
    # when its program has ended, before the wait for the next program
    # (it does not wait for the backward's end as a kept-whole leaf of a
    # deeper run does)
    programs = sorted((s for s in cut[5]
                       if s[0] == "bps.step.backward_program"),
                      key=lambda s: s[2])
    assert [s[4]["links"] for s in programs] == [
        "0-4", "5", "4", "3", "2", "2", "1", "0"]
    index = {jax.tree_util.keystr(path): i for i, (path, _) in enumerate(
        jax.tree_util.tree_leaves_with_path(params))}
    ingests = {s[4]["leaf"]: s[2] for s in cut[5]
               if s[0] == "bps.export.ingest"}

    def claimed(run, group, leaf):
        return ingests[index[f"['{run}']['{group}']['{leaf}']"]]

    assert programs[2][3] <= claimed("run03", "op", "wq") <= programs[3][2]
    assert programs[3][3] <= claimed("run02", "op", "wkv_b") \
        <= programs[4][2]
    # (the last program's end is looked for between the claims)
    assert programs[6][3] <= claimed("run00", "ffn", "w1")


# --------------------------------------------------------------------- #
# runs inside a list, an embedding that is also the head (the shape of
# models/lfm2.py's and models/joyai.py's trees)
# --------------------------------------------------------------------- #


def _toy(seed=9, remat=True):
    """``{"embed", "runs": [run of 1, run of 3], "final_norm", "extra":
    {...}}``: the lookup reads ``embed`` and so does the head, whose link
    holds ``final_norm`` and the ``extra`` module beside it. Every leaf
    but the norms and a bias is over ``ENV``'s fusion size."""
    V, d = 48, 16
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)

    def run(key, n):
        k1, k2 = jax.random.split(key)
        return {"w": jax.random.normal(k1, (n, d, d)) * 0.3,
                "norm": 1 + 0.1 * jax.random.normal(k2, (n, d))}

    params = {"embed": jax.random.normal(ks[0], (V, d)) * 0.5,
              "runs": [run(ks[1], 1), run(ks[2], 3)],
              "final_norm": 1 + 0.1 * jax.random.normal(ks[3], (d,)),
              "extra": {"proj": jax.random.normal(ks[4], (d, d)) * 0.3,
                        "bias": 0.1 * jax.random.normal(ks[5], (V,))}}
    batch = {"tokens": jax.random.randint(ks[6], (4, 9), 0, V)}

    def norm(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5) * w

    def embed(p, _, batch):
        return p["embed"][batch["tokens"][:, :-1]], {}

    def block(p, x, scale):
        y = x + scale * jnp.tanh(norm(x, p["norm"]) @ p["w"])
        return y, {"toy/layers": jnp.int32(1),
                   "toy/positive": jnp.sum(y > 0, dtype=jnp.int32)}

    def head(p, x, batch):
        # the extra module, then the tied head
        x = jnp.tanh(norm(x, p["final_norm"]) @ p["extra"]["proj"])
        logits = x @ p["embed"].T + p["extra"]["bias"]
        targets = batch["tokens"][:, 1:]
        nll = jax.scipy.special.logsumexp(logits, axis=-1) \
            - jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        return jnp.sum(nll) / targets.size, {}

    def total(stacked):
        return {name: jnp.sum(v) for name, v in stacked.items()}

    ch = chain.Chain([
        chain.Link(embed, "embed"),
        *(chain.Run(block, ("runs", i), n, remat=remat, stats=total,
                    consts=lambda batch: jnp.float32(0.5))
          for i, n in enumerate((1, 3))),
        chain.Link(head, ["final_norm", "extra", "embed"])])
    return ch, params, batch


def _plainly(ch):
    """The chain's links composed with no chain called: the one-program
    backward, kept as the reference."""
    def loss(params, batch):
        carry, stats = None, {}
        for ln in ch.links:
            carry, st = ln(ln.pick(params), carry, batch)
            chain.add_stats(stats, st)
        return carry, stats
    return loss


def test_a_chain_over_a_list_of_runs_and_a_tied_leaf_can_be_cut():
    ch, params, batch = _toy()
    assert ch.cuts(params)
    # flatten order: embed, extra/bias, extra/proj, final_norm, then
    # each run's norm and w; embed under the first link and the last
    leaves = _chain_leaves(ch, params)
    assert leaves == {0: (0,), 1: (4, 5), 2: (6, 7), 3: (0, 1, 2, 3)}
    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    cut = _cut_backward(ch, mesh, "dp", leaves)
    assert cut.shared == {0: (0, 3)}
    # the head's program keeps its term, the lookup's takes it
    assert [cut.held(k) for k in range(4)] == [(), (), (), (0,)]
    assert [cut.taken(k) for k in range(4)] == [(0,), (), (), ()]
    # forward, head, 3 + 1 layers, embedding
    assert cut.programs == 7
    # called as any loss it registers, and is what its links compose to
    with chain.collecting() as found:
        loss, stats = jax.jit(ch)(params, batch)
    assert found == [ch] and int(stats["toy/layers"]) == 4
    want, want_stats = jax.jit(_plainly(ch))(params, batch)
    assert float(loss) == float(want)
    _assert_trees_equal(stats, want_stats)
    # remat off on a run, as ever, keeps the backward one program
    assert not _toy(remat=False)[0].cuts(params)


@pytest.mark.parametrize("devices", [1, 2], ids=["1dev", "2dev"])
def test_the_cut_programs_sum_a_shared_leafs_terms_on_the_device(devices):
    """Loss, statistics and every gradient the one-program backward's;
    the head's program hands over no output for ``embed``, the
    lookup's hands over the sum."""
    from byteps_tpu.jax.train import (_loss_and_stats, _pin_cut_outputs,
                                      _psum_backward)

    ch, params, batch = _toy()
    mesh = Mesh(np.array(jax.devices()[:devices]), ("dp",))
    whole = _psum_backward(_loss_and_stats(_plainly(ch)), mesh, "dp")
    (want, want_stats), want_grads = whole(params, batch)
    cut = _cut_backward(ch, mesh, "dp", _chain_leaves(ch, params))
    # as the step builds them: the layouts looked at, the terms described
    _pin_cut_outputs(cut, params, batch, {0, 2, 5, 7}, mesh, "dp")
    (got, stats), programs = _dispatch_cut(cut, params, batch)
    assert [(links, layer, sorted(outs)) for links, layer, _, outs
            in programs] == [
        ("0-2", None, []), ("3", None, [1, 2, 3]), ("2", 2, [6, 7]),
        ("2", 1, [6, 7]), ("2", 0, [6, 7]), ("1", 0, [4, 5]),
        ("0", None, [0])]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    _assert_trees_equal(stats, want_stats)
    flat = jax.tree.leaves(want_grads)
    for _, layer, _, outs in programs:
        for i, g in outs.items():
            w = np.asarray(flat[i])
            if layer is not None and i in (6, 7):
                w = w[layer:layer + 1]
            _assert_trees_equal(np.asarray(g).reshape(w.shape), w)
    # both uses are in the sum: neither term alone is the gradient
    lookup = jax.grad(lambda e: _plainly(ch)(
        {**params, "embed": e}, batch)[0])
    head_only = jax.grad(lambda e: ch.links[-1](
        {**ch.links[-1].pick(params), "embed": e},
        jax.lax.stop_gradient(ch.forward(params, batch)[0][-1]), batch)[0])
    assert float(jnp.abs(lookup(params["embed"])
                         - head_only(params["embed"])).max()) > 1e-4
    assert float(jnp.abs(head_only(params["embed"])).max()) > 1e-4


@pytest.fixture(scope="module")
def toy_cut_and_whole():
    ch, params, batch = _toy()
    n_bytes = sum(leaf.nbytes for leaf in jax.tree.leaves(params))
    return (_run_ps(ch, params, batch), _run_ps(_plainly(ch), params, batch),
            n_bytes, params)


def test_a_cut_step_with_a_shared_leaf_is_the_one_program_step(
        toy_cut_and_whole):
    cut, whole, _, _ = toy_cut_and_whole
    assert cut["losses"] == whole["losses"]
    assert cut["losses"][-1] < cut["losses"][0]
    _assert_trees_equal(cut["params"], whole["params"])
    _assert_trees_equal(cut["opt"], whole["opt"])


def test_a_shared_leaf_is_pushed_once_on_its_one_program_key(
        toy_cut_and_whole):
    cut, whole, n_bytes, params = toy_cut_and_whole
    for run in (cut, whole):
        assert run["grew"]["wire/push_bytes"] == 3 * n_bytes
        assert run["grew"]["export/whole_bytes"] == 3 * n_bytes
    assert cut["grew"]["export/backward_programs"] == 3 * 7
    assert whole["grew"]["export/backward_programs"] == 3
    # the ledger prices a cut step by the programs it runs (the one
    # program it does not run is not lowered for its cost)
    for run in (cut, whole):
        assert run["ledger"]["source"] == "xla"
        assert run["ledger"]["model_flops"] > 0
    assert cut["ledger"]["model_flops"] > whole["ledger"]["model_flops"]
    # one leaf a step summed over two programs, its term held between
    assert cut["grew"]["export/shared_leaves"] == 3
    assert cut["grew"]["export/shared_carry_bytes"] \
        == 3 * params["embed"].nbytes
    assert whole["grew"]["export/shared_leaves"] == 0
    assert whole["grew"]["export/shared_carry_bytes"] == 0
    # the run of three's weight leaves as pieces, the run of one's whole
    assert cut["grew"]["export/piece_bytes"] \
        == 3 * params["runs"][1]["w"].nbytes
    assert {n for n in cut["keys"] if "@shard" in n} == {
        f"grad/runs/1/w@shard{j}of3" for j in range(3)}
    # every other key is the one-program step's, the tied leaf's and
    # the bucket's among them
    assert {n for n in cut["keys"] if "@shard" not in n} \
        == set(whole["keys"]) - {"grad/runs/1/w"}
    for name in ("grad/embed", "grad/runs/0/w", "grad/extra/proj"):
        assert name in cut["keys"] and name in whole["keys"], name
    assert {n for n in cut["keys"] if n.startswith("fused/")} == \
        {n for n in whole["keys"] if n.startswith("fused/")}
    # ONE ingest of the tied leaf a step, after the LAST program (the
    # lookup's, which hands the sum over) has ended
    ingests = [s for s in cut["spans"] if s[0] == "bps.export.ingest"
               and s[4]["leaf"] == 0]
    programs = sorted((s for s in cut["spans"]
                       if s[0] == "bps.step.backward_program"),
                      key=lambda s: s[2])
    assert [s[4]["links"] for s in programs] == [
        "0-2", "3", "2", "2", "2", "1", "0"]
    assert len(ingests) == 1 and ingests[0][4]["cause"] == "out:0"
    assert ingests[0][4]["bytes"] == params["embed"].nbytes
    assert ingests[0][2] >= programs[-1][3]
    # the head's program's span counts what it hands over: not the term
    handed = params["final_norm"].nbytes + params["extra"]["proj"].nbytes \
        + params["extra"]["bias"].nbytes
    assert programs[1][4]["bytes"] == handed
    assert programs[-1][4]["bytes"] == params["embed"].nbytes


# --------------------------------------------------------------------- #
# a run whose layers are of unlike static kinds under one stacked key
# --------------------------------------------------------------------- #

KINDS = [("a", "b"), ("a", "a", "a", "b"), ("a", "a", "b", "a", "a", "b")]


def _kinded(kinds, remat=True, d=8, V=11, seed=9):
    """A chain whose one run holds layers of two kinds (the kind picks
    the block's nonlinearity and which of the run's two constants it
    reads, as a mask and a rotary table are picked), a row a layer
    beside the leaves; and the period scan such a model had without
    ``kinds`` (``models/mellum.py forward_hidden`` before PR 50): the
    reference."""
    n = len(kinds)
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    params = {"embed": jax.random.normal(ks[0], (V, d)) * 0.5,
              "blocks": {"w": jax.random.normal(ks[1], (n, d, d)) * 0.3,
                         "norm": 1 + 0.1 * jax.random.normal(ks[2], (n, d))},
              "head": jax.random.normal(ks[3], (d, V)) * 0.5}
    batch = {"tokens": jax.random.randint(ks[4], (4, 9), 0, V)}
    rows = 0.1 * np.asarray(jax.random.normal(ks[5], (n, d)))

    def embed(p, _, batch):
        return p["embed"][batch["tokens"][:, :-1]], {}

    def block(p, x, scales, row, kind):
        h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5) \
            * p["norm"]
        act = jnp.tanh if kind == "a" else jnp.sin
        y = x + scales[kind] * act(h @ p["w"] + row)
        return y, {"toy/positive": jnp.sum(y > 0, dtype=jnp.int32),
                   "toy/mean": jnp.mean(y, axis=(0, 1))}

    def head(p, x, batch):
        logits = x @ p["head"]
        targets = batch["tokens"][:, 1:]
        nll = jax.scipy.special.logsumexp(logits, axis=-1) \
            - jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        return jnp.sum(nll) / targets.size, {}

    def scales(batch):
        return {"a": jnp.float32(0.5), "b": jnp.float32(0.25)}

    def total(stacked):
        return {name: v if v.ndim == 2 else jnp.sum(v)
                for name, v in stacked.items()}

    ch = chain.Chain([
        chain.Link(embed, "embed"),
        chain.Run(block, "blocks", n, remat=remat, kinds=kinds,
                  consts=scales, each=lambda batch: jnp.asarray(rows),
                  stats=total),
        chain.Link(head, "head")])

    def period_scan(params, batch):
        span = chain.period(kinds)
        x, _ = embed(params, None, batch)
        consts = scales(batch)
        one = jax.checkpoint(block, static_argnums=(4,)) if remat else block

        def body(x, layers):
            stats = []
            for j in range(span):
                p, row = jax.tree.map(lambda a: a[j], layers)
                x, st = one(p, x, consts, row, kinds[j])
                stats.append(st)
            return x, jax.tree.map(lambda *a: jnp.stack(a), *stats)

        x, stats = jax.lax.scan(body, x, jax.tree.map(
            lambda a: a.reshape(n // span, span, *a.shape[1:]),
            (params["blocks"], jnp.asarray(rows))))
        loss, _ = head(params, x, batch)
        return loss, {name: v.reshape(n, -1) if v.ndim == 3 else jnp.sum(v)
                      for name, v in stats.items()}

    return ch, params, batch, period_scan


W, F = mellum.SLIDING, mellum.FULL


@pytest.mark.parametrize("kinds, want", [
    ("ab", 2), ("aaab", 4), ("aabaab", 3), ("aaaaaa", 1), ("abb", 3),
    ("a", 1), ("abab", 2), ("abababab", 2), ("aab", 3), ("abcabc", 3),
    ("abcab", 5), ("aaabaaab", 4), ("aaabaaba", 8), ("abba", 4),
    ((W, W, W, F) * 7, 4), ((W, F, W, F), 2), ((F,) * 6, 1),
    ((W, F, F), 3), ((1, 2, 1, 2), 2), ((("w", 1024), ("f", None)) * 2, 2)],
    ids=lambda v: None if isinstance(v, int) else "".join(map(str, v))[:24])
def test_a_pattern_of_kinds_has_a_shortest_period(kinds, want):
    """The shortest period that DIVIDES the length (a pattern that
    repeats but for its tail does not repeat), of any hashable labels."""
    assert chain.period(tuple(kinds)) == want
    assert len(kinds) % want == 0
    assert mellum._period is chain.period


def _bare(kinds, depth=None, remat=True):
    """A chain of ``embed``, a run of ``len(kinds)`` layers (or
    ``depth``) and ``head`` over a tree of shapes: what ``cover`` and
    ``_cut_backward`` look at, nothing traced."""
    n = len(kinds) if depth is None else depth
    tree = {"embed": jax.ShapeDtypeStruct((5, 4), jnp.float32),
            "blocks": {"w": jax.ShapeDtypeStruct((n, 4, 4), jnp.float32)},
            "head": jax.ShapeDtypeStruct((4, 5), jnp.float32)}
    return chain.Chain([
        chain.Link(None, "embed"),
        chain.Run(None, "blocks", n, remat=remat, kinds=kinds),
        chain.Link(None, "head")]), tree


@pytest.mark.parametrize("kinds, depth, cuts", [
    (None, 4, True), ("aaab", None, True), ("abab", None, True),
    ("aaaa", None, True), ("a", None, True), (["x", "y"], None, True),
    ("aaab", 3, False), ("aaab", 5, False), ("ab", 4, False),
    ((), 2, False)], ids=str)
def test_cover_asks_of_a_run_with_kinds_that_they_are_as_deep(
        kinds, depth, cuts):
    ch, tree = _bare(kinds, depth)
    assert ch.cuts(tree) == cuts
    assert (_chain_leaves(ch, tree) is not None) == cuts
    # remat off keeps any run whole, kinds or none
    assert not _bare(kinds, depth, remat=False)[0].cuts(tree)
    run = ch.links[1]
    assert run.kinds is None or isinstance(run.kinds, tuple)
    assert len(run.layer_kinds) == (run.depth if kinds is None
                                    else len(kinds))


@pytest.mark.parametrize("kinds, executables", [
    (None, [None]), ("aaab", ["a", "b"]), ("baaa", ["b", "a"]),
    ("abab", ["a", "b"]), ("aaaa", ["a"]), ("abc", ["a", "b", "c"]),
    ("abcabc", ["a", "b", "c"]), ((W, W, W, F), [W, F]),
    ((W, W, W, F) * 2, [W, F]), ((F, W), [F, W])], ids=str)
def test_a_cut_backward_builds_one_layer_program_a_distinct_kind(
        kinds, executables):
    """However deep the run: the table is keyed by kind in the order
    the kinds first appear; the step's count of programs is the
    depth's alone (forward, head, a program a layer, lookup)."""
    depth = 4 if kinds is None else None
    ch, tree = _bare(kinds, depth)
    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    cut = _cut_backward(ch, mesh, "dp", _chain_leaves(ch, tree))
    assert list(cut.pulls[1]) == executables
    assert callable(cut.pulls[0]) and set(cut.pulls) == {0, 1}
    assert cut.programs == ch.links[1].depth + 3
    assert cut.shared == {} and cut.outputs_pinned == 0


@pytest.mark.parametrize("kinds, remat", [
    *((kinds, True) for kinds in KINDS), (KINDS[1], False)],
    ids=lambda v: "".join(v) if isinstance(v, tuple) else f"remat-{v}")
def test_a_run_with_kinds_called_plainly_is_the_period_scan(kinds, remat):
    ch, params, batch, period_scan = _kinded(kinds, remat)
    (loss, stats), grads = jax.jit(jax.value_and_grad(
        ch, has_aux=True))(params, batch)
    (want, want_stats), want_grads = jax.jit(jax.value_and_grad(
        period_scan, has_aux=True))(params, batch)
    assert float(loss) == float(want) and float(loss) > 0
    _assert_trees_equal(stats, want_stats)
    assert stats["toy/mean"].shape == (len(kinds), 8)
    _assert_trees_equal(grads, want_grads)
    assert all(np.any(np.asarray(g)) for g in jax.tree.leaves(grads))
    # the kept carries are the layers' inputs, one a layer
    kept, _ = ch.forward(params, batch)
    assert kept[1].shape == (len(kinds), 4, 8, 8)
    # remat off, as ever, keeps the backward one program
    assert ch.cuts(params) == remat
    with pytest.raises(ValueError, match="kinds"):
        ch.links[1].scan(params["blocks"], kept[1][0], None, kinds=("a",))


@pytest.mark.parametrize("kinds, devices", [
    (KINDS[1], 1), (KINDS[1], 2), (KINDS[2], 1)],
    ids=lambda v: "".join(v) if isinstance(v, tuple) else f"{v}dev")
def test_the_cut_programs_over_unlike_kinds_give_the_one_programs(
        kinds, devices):
    """Forward, head, a program a layer, embedding: ``depth + 3``
    programs a step, the layers of a kind
    through ONE executable (the layer's index is traced, its kind is
    not); loss, statistics and every gradient the one program's."""
    from byteps_tpu.jax.train import (_loss_and_stats, _pin_cut_outputs,
                                      _psum_backward)

    ch, params, batch, _ = _kinded(kinds)
    n = len(kinds)
    mesh = Mesh(np.array(jax.devices()[:devices]), ("dp",))
    whole = _psum_backward(_loss_and_stats(_plainly(ch)), mesh, "dp")
    (want, want_stats), want_grads = whole(params, batch)
    cut = _cut_backward(ch, mesh, "dp", _chain_leaves(ch, params))
    assert cut.programs == n + 3
    # one executable a distinct kind, whatever the depth
    assert list(cut.pulls[1]) == ["a", "b"]
    # as the step builds them: each kind's layouts looked at, its cost
    # counted once a layer of the kind; ``w`` rides keys of its own
    _pin_cut_outputs(cut, params, batch, {1}, mesh, "dp")
    assert cut.cost["flops"] > 0 and cut.outputs_pinned == 0
    (got, stats), programs = _dispatch_cut(cut, params, batch)
    assert [(links, layer) for links, layer, _, _ in programs] == [
        ("0-1", None), ("2", None), *(("1", j) for j in reversed(range(n))),
        ("0", None)]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    _assert_trees_equal(stats, want_stats)
    flat = jax.tree.leaves(want_grads)
    seen = set()
    for _, layer, _, outs in programs:
        for i, g in outs.items():
            w = np.asarray(flat[i])
            if layer is not None:
                assert g.shape == (1,) + w.shape[1:]
                w = w[layer:layer + 1]
            np.testing.assert_array_equal(np.asarray(g), w, err_msg=str(i))
            seen.add((i, layer))
    assert len(seen) == 2 + 2 * n
    # a layer's program run at the other kind is another function
    p = ch.links[1].pick(params)
    kept, _ = ch.forward(params, batch)
    ct = jnp.ones_like(kept[1][0])
    last = n - 1
    right, wrong = (ch.pull_layer(1, p, last, kept[1], batch, ct, kind)[0]
                    for kind in (kinds[last], "a"))
    assert float(jnp.abs(right - wrong).max()) > 1e-3


# --------------------------------------------------------------------- #
# models/mellum.py as a chain: window, window, window, full under one
# stacked key, against the composition it replaced (kept here)
# --------------------------------------------------------------------- #


def _mellums_parent(params, batch, cfg, ep_axis=None):
    """``mellum.loss_fn`` as it stood before it was a chain: the period
    scan written out in ``forward_hidden``, the head after it."""
    inputs, targets = llama.split_batch(batch)
    kinds = tuple(cfg.layer_types[:cfg.n_layers])
    period = mellum._period(kinds)
    ropes = mellum.rope_tables(cfg, inputs.shape[1])
    x = params["embed"].astype(cfg.dtype)[inputs]
    block = jax.checkpoint(mellum._block, static_argnums=(3, 4, 5)) \
        if cfg.remat else mellum._block

    def body(x, layers):
        stats = []
        for j in range(period):
            p = jax.tree.map(lambda a: a[j], layers)
            x, st = block(x, p, ropes, cfg, kinds[j], ep_axis)
            stats.append(st)
        return x, jax.tree.map(lambda *a: jnp.stack(a), *stats)

    stacked = jax.tree.map(
        lambda a: a.reshape(cfg.n_layers // period, period, *a.shape[1:]),
        params["blocks"])
    x, stats = jax.lax.scan(body, x, stacked)
    x = llama._rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = x @ params["lm_head"].astype(cfg.dtype)
    return llama.next_token_xent(logits, targets), {
        name: v.reshape(cfg.n_layers, -1) if v.ndim == 3 else jnp.sum(v)
        for name, v in stats.items()}


def _mellum(remat=True, held=4, kinds=None, seed=11):
    kinds = kinds or (mellum.SLIDING,) * 3 + (mellum.FULL,)
    cfg = dataclasses.replace(
        mellum.MellumConfig.tiny(), remat=remat, n_experts_held=held,
        n_layers=len(kinds), layer_types=kinds)
    key = jax.random.PRNGKey(seed)
    params = mellum.init_params(key, cfg)
    # norms off one, so that their gradients are no accident
    params = jax.tree.map(
        lambda a: a + 0.1 * jax.random.normal(key, a.shape, a.dtype)
        if a.shape[-1] == cfg.dim and a.ndim <= 2 else a, params)
    batch = {"tokens": jax.random.randint(key, (2, 33), 0, cfg.vocab_size)}
    return cfg, params, batch


def test_mellums_loss_as_a_chain_is_the_composition_it_replaced():
    """Window, window, window, full, remat on, four of eight experts
    held; two held: the PS steps below."""
    held = 4
    cfg, params, batch = _mellum(held=held)
    (loss, stats), grads = jax.jit(jax.value_and_grad(
        lambda p, b: mellum.loss_fn(p, b, cfg), has_aux=True))(params, batch)
    (want, want_stats), want_grads = jax.jit(jax.value_and_grad(
        lambda p, b: _mellums_parent(p, b, cfg), has_aux=True))(params, batch)
    assert float(loss) == float(want) and float(loss) > 0
    _assert_trees_equal(stats, want_stats)
    assert stats["moe/expert_load"].shape == (cfg.n_layers, held)
    _assert_trees_equal(grads, want_grads)
    assert all(np.any(np.asarray(g)) for g in jax.tree.leaves(grads))
    hidden, _ = mellum.forward_hidden(params, batch["tokens"][:, :-1], cfg)
    assert hidden.shape == (2, 32, cfg.dim)


def _canonical(text):
    """A lowered program as the multiset of its operations, the values'
    and the private functions' numbering left out: two programs that
    hold the same operations in another order read alike."""
    lines = collections.Counter()
    for line in text.splitlines():
        line = re.sub(r"%[\w#:]+", "%", line)
        lines[re.sub(r"@([A-Za-z_]+?)_\d+\b", r"@\1", line).strip()] += 1
    return lines


def test_the_fused_steps_program_is_the_compositions_but_for_five_sums():
    """Lowered for a rematerialised period of four, the chain called
    plainly holds the operations the composition it replaced holds, one
    for one (the targets' slice stands later in the text: the head
    reads the batch itself), except that the five counts a layer are
    summed over ``[layers]`` where they were summed over ``[periods,
    period]``: a reshape of four integers each, nothing of the model."""
    cfg, params, batch = _mellum()

    def lowered(loss):
        return _canonical(jax.jit(jax.value_and_grad(
            lambda p, b: loss(p, b, cfg), has_aux=True)).lower(
                params, batch).as_text())

    got, want = lowered(mellum.loss_fn), lowered(_mellums_parent)
    assert sum(want.values()) > 3000
    gone, new = want - got, got - want
    assert sum(gone.values()) == 5 and sum(new.values()) == 10
    assert all("stablehlo.reduce" in line and "tensor<1x4xi32>" in line
               for line in gone)
    assert all(("stablehlo.reduce" in line or "stablehlo.reshape" in line)
               and "tensor<4xi32>" in line for line in new)


@pytest.fixture(scope="module")
def mellum_cut_and_whole():
    """Two steps of the chained loss (cut) and of the composition it
    replaced (no chain: one program), rematerialised, two of eight
    experts held."""
    cfg, params, batch = _mellum(held=2)
    n_bytes = sum(leaf.nbytes for leaf in jax.tree.leaves(params))
    cut = _run_ps(lambda p, b: mellum.loss_fn(p, b, cfg), params, batch,
                  steps=2)
    whole = _run_ps(lambda p, b: _mellums_parent(p, b, cfg), params, batch,
                    steps=2)
    return cut, whole, n_bytes, params


def test_mellums_cut_step_is_its_one_program_step_bit_for_bit(
        mellum_cut_and_whole):
    cut, whole, _, _ = mellum_cut_and_whole
    assert cut["losses"] == whole["losses"]
    assert cut["losses"][-1] < cut["losses"][0]
    _assert_trees_equal(cut["params"], whole["params"])
    _assert_trees_equal(cut["opt"], whole["opt"])


def test_mellums_cut_step_pushes_every_byte_once_under_its_keys(
        mellum_cut_and_whole):
    cut, whole, n_bytes, params = mellum_cut_and_whole
    for run in (cut, whole):
        assert run["grew"]["wire/push_bytes"] == 2 * n_bytes
        assert run["grew"]["export/whole_bytes"] == 2 * n_bytes
    # forward, head, four layers, embedding; one where nothing is cut
    assert cut["grew"]["export/backward_programs"] == 2 * 7
    assert whole["grew"]["export/backward_programs"] == 2
    programs = sorted((s for s in cut["spans"]
                       if s[0] == "bps.step.backward_program"),
                      key=lambda s: s[2])
    assert [s[4]["links"] for s in programs] == [
        "0-1", "2", "1", "1", "1", "1", "0"]
    # the eight weights of a layer leave as pieces, the two norms whole
    blocks = params["blocks"]
    pieces = {k: v for k, v in blocks.items() if "norm" not in k}
    assert cut["grew"]["export/piece_bytes"] \
        == 2 * sum(v.nbytes for v in pieces.values())
    assert cut["grew"]["export/under_backward_bytes"] \
        >= 2 * params["lm_head"].nbytes
    assert whole["grew"]["export/piece_bytes"] == 0
    # the keys: a piece a layer of each stacked weight, whatever the
    # layer's kind; every other key the one-program step's
    assert {n for n in cut["keys"] if "@shard" in n} == {
        f"grad/blocks/{k}@shard{j}of4" for k in pieces for j in range(4)}
    assert {n for n in cut["keys"] if "@shard" not in n} \
        == set(whole["keys"]) - {f"grad/blocks/{k}" for k in pieces}
    assert {n for n in cut["keys"] if n.startswith("fused/")} == \
        {n for n in whole["keys"] if n.startswith("fused/")} != set()


# --------------------------------------------------------------------- #
# a run without kinds is the run it was: every program of the cut
# backward that runs a run's code, for each chained configuration that
# declares none (the
# benchmark's rehearsal sizes, remat on) traces to the jaxpr it traced
# to at the parent commit ``dc8d088``. ``PARENTS`` holds that commit's
# digests, taken there by this ``cut_program_texts`` (the parent's
# ``cut.pulls[k]`` was the one program, not a table of one)
# --------------------------------------------------------------------- #

# sha256 (16 digits) of each program's jaxpr, addresses left out
PARENTS = {
    "sdar-30b-a3b": {
        "forward": "15970e952ced98eb", "link1": "19dee5ce0fb56e26",
    },
    "lfm2-8b-a1b": {
        "forward": "d02fb6b69f0ca25a", "link3": "5bde70ef05674c03",
        "link2": "9246f0427c7d6d72", "link1": "51ec8e4f94790c57",
    },
    "joyai-llm-flash": {
        "forward": "911ad3086b7036ef", "link2": "5d4d30fc03a183aa",
        "link1": "75368e18364b7027",
    },
    "kimi-linear-48b-a3b": {
        "forward": "ec1e75c7c20ec62c", "link4": "de0a876e71151ddf",
        "link3": "2268a13a4bab3ea8", "link2": "09a0a0c6fef77329",
        "link1": "f12b69637be4b0ce",
    },
    "trinity-mini": {
        "forward": "3da576728337a740", "link4": "2fcc52687a97d6ac",
        "link3": "9b2f18c088962fce", "link2": "68d1a2503b73073e",
        "link1": "773dfabfccc0c4da",
    },
}


def cut_program_texts(loss, params, batch):
    """Label -> jaxpr text of the programs ``_cut_backward`` builds for
    the chain ``loss`` calls that run a RUN's code (``Run.scan``,
    ``Chain.forward``, ``Chain.pull_layer``): the forward and each
    run's layer programs, traced on the shapes the step hands them, on
    one device. (The last link's program and a whole link's go through
    none of it, and tracing a head costs as much as the rest.)"""
    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    # the loss's trace is abandoned where it calls its chain
    with chain.collecting(first=True) as found:
        jax.eval_shape(loss, params, batch)
    (ch,) = found
    cut = _cut_backward(ch, mesh, "dp", _chain_leaves(ch, params))
    local = NamedSharding(mesh, P("dp"))

    def text(traced):
        return re.sub(r"0x[0-9a-f]+", "", str(traced.jaxpr))

    traced = cut.forward.trace(params, batch)
    out = {"forward": text(traced)}
    for k, ln in enumerate(ch.links):
        if not isinstance(ln, chain.Run):
            continue
        # the layers' kept inputs [device, depth, ...]; a layer's
        # cotangent has its carry's shape
        kept = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=local), traced.out_info[0][k])
        ct = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape[:1] + a.shape[2:], a.dtype, sharding=local), kept)
        for kind, fn in cut.pulls[k].items():
            label = f"link{k}" if kind is None else f"link{k}.{kind}"
            out[label] = text(fn.trace(ln.pick(params), np.int32(0), kept,
                                       batch, ct))
    return out


def _rehearsal(name):
    """A configuration at its rehearsal sizes, rematerialised: the
    program's loss, its weights' shapes and two rows'."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           name + ".json")) as f:
        cfg = json.load(f)
    cfg = _overlay(cfg, cfg["rehearse"])
    cfg.update(remat=True)
    family = importlib.import_module(f"benchmark.families.{cfg['family']}")
    key = jax.random.PRNGKey(7)
    # shapes alone: nothing runs
    return (family.program_loss(cfg),
            jax.eval_shape(lambda: family.reference.init_params(key, cfg)),
            jax.eval_shape(lambda: family.reference.make_batch(
                key, 0, 2, cfg)))


@pytest.mark.parametrize("name", sorted(PARENTS))
def test_a_run_without_kinds_is_cut_into_the_parents_programs(name):
    texts = cut_program_texts(*_rehearsal(name))
    got = {label: hashlib.sha256(t.encode()).hexdigest()[:16]
           for label, t in texts.items()}
    assert got == PARENTS[name]


def test_mellums_run_is_cut_into_a_program_a_kind():
    """The same walk over the one configuration that declares kinds:
    forward, head, ONE program for the three window layers, one for the
    full layer, the lookup; the two layer programs differ."""
    texts = cut_program_texts(*_rehearsal("mellum2-12b"))
    assert list(texts) == [
        "forward", "link1.sliding_attention", "link1.full_attention"]
    assert texts["link1.sliding_attention"] != texts["link1.full_attention"]
