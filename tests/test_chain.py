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
once, its two terms summed on the device."""

import dataclasses
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

from byteps_tpu.ops import chain
from byteps_tpu.jax.train import (ExportPlan, _chain_leaves, _cut_backward,
                                  _declare_shard_keys, _dispatch_cut,
                                  _export_plan, make_ps_train_step,
                                  make_train_step)
from byteps_tpu.models import kimi, llama, sdar
from byteps_tpu.ops.push_pull import psum_tree

from test_export_spans import _ps_env

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


def _run_ps(loss, params, batch, steps=3, devices=1, env=None, **kw):
    """``steps`` PS steps of adam -> (params, opt state, losses, the
    counters' growth, the last step's spans and reports)."""
    from byteps_tpu.core.state import get_state

    tx = optax.adam(1e-2)
    mesh = Mesh(np.array(jax.devices()[:devices]), ("dp",))
    params = jax.tree.map(jnp.array, params)  # the step donates its own
    with _ps_env({**ENV, **(env or {})}, port=next(PORTS)) as bps:
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
