"""models/joyai.py's loss as a chain (``ops/chain.py``): against the
composition it replaced (kept here as the reference) loss, statistics
and every gradient to the bit; the chain's keys and the leaves they
cover; and through ``make_ps_train_step`` with a loopback server the
backward cut at the links against the one-program step. A file of its
own beside ``test_joyai.py`` so that the two run on two workers."""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_export_spans import _ps_env
from test_joyai import (_config, _one_device_mesh, _state, family, joyai,
                        make_ps_train_step, reference)

# this file's loopback servers (``test_joyai.py`` counts from 25410 and
# may run at once in another process)
PORTS = itertools.count(25460)


# ------------------------------------------------------------------ #
# the loss as a chain against the composition it replaced
# ------------------------------------------------------------------ #

def _parents_run(x, p, bias, rope, cfg, ffn):
    """``joyai._run`` as it stood: the block under ``jax.checkpoint``
    with static arguments, scanned over ``{"p", "bias"}``."""
    block = jax.checkpoint(joyai._block, static_argnums=(4, 5, 6)) \
        if cfg.remat else joyai._block

    def body(x, layer):
        return block(x, layer["p"], layer.get("bias"), rope, cfg, ffn, None)

    layers = {"p": p} if bias is None else {"p": p, "bias": bias}
    return jax.lax.scan(body, x, layers)


def _parents_summed(stats):
    stats = [st for st in stats if st]
    if not stats:
        return {}
    joined = jax.tree.map(lambda *a: jnp.concatenate(a), *stats)
    return {name: v if v.ndim == 2 else jnp.sum(v)
            for name, v in joined.items()}


def _parents_bias(cfg, expert_bias):
    if expert_bias is None:
        return jnp.zeros((cfg.n_sparse_layers + cfg.n_mtp, cfg.n_experts),
                         jnp.float32)
    return jax.lax.stop_gradient(expert_bias)


def _parents_hidden(params, tokens, cfg, expert_bias=None):
    """``joyai.forward_hidden`` as it stood before the loss was a chain:
    the lookup and the runs' scans, the statistics a list a run."""
    bias = _parents_bias(cfg, expert_bias)
    rope = joyai.rope_cache(cfg, tokens.shape[1])
    x = params["embed"].astype(cfg.dtype)[tokens]
    stats = []
    for (ffn, n), p in zip(cfg.runs(), params["runs"]):
        rows = bias[:cfg.n_sparse_layers] if ffn == joyai.SPARSE else None
        x, st = _parents_run(x, p, rows, rope, cfg, ffn)
        stats.append(st)
    return x, stats


def _parents_loss(params, batch, cfg, expert_bias=None):
    """``joyai.loss_fn`` as it stood: that walk, the main head, then the
    module and the second pass over the same head."""
    inputs, targets = joyai.L.split_batch(batch)
    rows, S = inputs.shape
    h, stats = _parents_hidden(params, inputs, cfg, expert_bias)
    loss = joyai.head_nll(h, params["final_norm"], params["head"], targets,
                          cfg) / (rows * S)
    if not cfg.n_mtp:
        return loss, _parents_summed(stats)
    p, dt, eps = params["mtp"], cfg.dtype, cfg.norm_eps
    with jax.named_scope("bps.mtp"):
        nxt = params["embed"].astype(dt)[targets]
        x = jnp.concatenate(
            [joyai.L._rmsnorm(h, p["norm_h"], eps),
             joyai.L._rmsnorm(nxt, p["norm_e"], eps)], axis=-1) \
            @ p["proj"].astype(dt)
    x, st = _parents_run(x, p["block"],
                         _parents_bias(cfg, expert_bias)[-1][None],
                         joyai.rope_cache(cfg, S), cfg, joyai.SPARSE)
    with jax.named_scope("bps.mtp"):
        nll = joyai.head_nll(x, p["final_norm"], params["head"],
                             jnp.roll(targets, -1, axis=1), cfg, last=1)
    stats = _parents_summed(stats + [st])
    stats["mtp/predicted_tokens"] = jnp.asarray(rows * (S - 1), jnp.int32)
    stats["mtp/nll_sum"] = nll
    return loss + cfg.mtp_weight * nll / (rows * (S - 1)), stats


def _assert_trees_equal(got, want):
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("n_mtp", [1, 0], ids=["module", "no-module"])
@pytest.mark.parametrize("remat", [True, False], ids=["remat", "no-remat"])
@pytest.mark.parametrize("biased", [True, False], ids=["bias", "no-bias"])
def test_the_loss_as_a_chain_is_the_composition_it_replaced(biased, remat,
                                                            n_mtp):
    """Loss, every statistic (the ``[sparse layers + module, n_held]``
    load row for row, the module's block last; ``mtp/*``) and every
    gradient, to the bit; ``forward_hidden``'s output too."""
    import dataclasses

    cfg = _config(remat=remat)
    params, batch = _state(cfg)
    pc = dataclasses.replace(family.program_config(cfg), n_mtp=n_mtp)
    bias = reference.expert_bias(cfg) if biased else None
    if not n_mtp:
        params = {k: v for k, v in params.items() if k != "mtp"}
        bias = None if bias is None else bias[:-1]
    (loss, stats), grads = jax.jit(jax.value_and_grad(
        lambda p, b: joyai.loss_fn(p, b, pc, bias), has_aux=True))(
            params, batch)
    (want, want_stats), want_grads = jax.jit(jax.value_and_grad(
        lambda p, b: _parents_loss(p, b, pc, bias), has_aux=True))(
            params, batch)
    assert float(loss) == float(want) and float(loss) > 0
    _assert_trees_equal(stats, want_stats)
    load = np.asarray(stats["moe/expert_load"])
    # (under the bias a layer may send the two held experts nothing)
    assert load.shape == (2 + n_mtp, 2) and load[-1].sum() > 0
    assert ("mtp/nll_sum" in stats) == bool(n_mtp)
    _assert_trees_equal(grads, want_grads)
    assert all(np.any(np.asarray(g)) for g in jax.tree.leaves(grads))
    hidden, hidden_stats = jax.jit(
        lambda p, t: joyai.forward_hidden(p, t, pc, bias))(
            params, batch["inputs"])
    want_hidden, runs_stats = jax.jit(
        lambda p, t: _parents_hidden(p, t, pc, bias))(
            params, batch["inputs"])
    np.testing.assert_array_equal(np.asarray(hidden), np.asarray(want_hidden))
    assert hidden.shape == (2, cfg["seq_len"], cfg["hidden_size"])
    # the runs' rows of the table; the module's row is the last link's
    np.testing.assert_array_equal(
        np.asarray(hidden_stats["moe/expert_load"]),
        np.concatenate([np.asarray(runs_stats[1]["moe/expert_load"]),
                        np.zeros((n_mtp, 2), load.dtype)]))


def test_the_chain_names_the_runs_inside_the_list_and_embed_under_two_links():
    import dataclasses

    from byteps_tpu.jax.train import _chain_leaves
    from byteps_tpu.ops import chain

    cfg = _config(remat=True)
    params, batch = _state(cfg)
    with chain.collecting() as found:
        jax.eval_shape(family.program_loss(cfg), params, batch)
    (ch,) = found
    assert [ln.keys for ln in ch.links] == [
        ("embed",), (("runs", 0),), (("runs", 1),),
        ("final_norm", "head", "mtp", "embed")]
    assert [getattr(ln, "depth", None) for ln in ch.links] == [
        None, 1, 2, None]
    assert ch.cuts(params)
    leaves = _chain_leaves(ch, params)
    names = [jax.tree_util.keystr(p)
             for p, _ in jax.tree_util.tree_leaves_with_path(params)]
    # embed (flatten index 0) under the lookup and under the last link,
    # beside the norm, the head and the module's 20 leaves
    assert leaves[0] == (0,) and names[0] == "['embed']"
    assert leaves[3] == tuple(range(23))
    assert [names[i] for i in leaves[3][:3]] == [
        "['embed']", "['final_norm']", "['head']"]
    assert all(names[i].startswith("['mtp']") for i in leaves[3][3:])
    assert all(names[i].startswith("['runs'][0]") for i in leaves[1])
    assert all(names[i].startswith("['runs'][1]") for i in leaves[2])
    assert sorted(i for found in leaves.values() for i in found) \
        == [0] + list(range(51))
    # without a module the last link is the head alone: nothing shared
    pc = dataclasses.replace(family.program_config(cfg), n_mtp=0)
    bare = {k: v for k, v in params.items() if k != "mtp"}
    with chain.collecting() as found:
        jax.eval_shape(lambda p, b: joyai.loss_fn(p, b, pc), bare, batch)
    assert found[0].links[-1].keys == ("final_norm", "head")
    assert sorted(i for found in _chain_leaves(found[0], bare).values()
                  for i in found) == list(range(31))
    # remat off (the rehearsal's own setting): one program, as ever
    off = _config()
    with chain.collecting() as found:
        jax.eval_shape(family.program_loss(off), params, batch)
    assert not found[0].cuts(params)
    # a bias without the module's row is refused where the chain is built
    with pytest.raises(ValueError, match="prediction module"):
        joyai.loss_fn(params, batch, family.program_config(cfg),
                      reference.expert_bias(cfg)[:-1])


def test_the_ps_step_cuts_the_backward_and_is_the_one_program_step(
        monkeypatch):
    """The file's own test-scale configuration (a dense layer, a run of
    two sparse ones, the module in the last link), remat on: 2 + layers
    + 1 programs a step, ``embed``'s two terms summed on the device and
    pushed once, the run of two as pieces; losses, parameters and
    optimizer state the one-program step's (the composition above, which
    registers no chain) to float32's last digits."""
    import optax

    cfg = _config(remat=True)
    params, batch = _state(cfg)
    pc = family.program_config(cfg)
    bias = reference.expert_bias(cfg)
    n_bytes = sum(leaf.nbytes for leaf in jax.tree.leaves(params))
    # every weight of the tiny model on a key of its own
    monkeypatch.setenv("BYTEPS_FUSION_BYTES", "1024")
    monkeypatch.setenv("BYTEPS_SHARD_MIN_BYTES", "1024")
    names = ("export/backward_programs", "export/shared_leaves",
             "export/shared_carry_bytes", "export/piece_bytes",
             "export/whole_bytes", "wire/push_bytes",
             "mtp/predicted_tokens")

    def run(loss):
        from byteps_tpu.core.state import get_state

        tx = optax.adam(1e-2)
        with _ps_env(port=next(PORTS)) as bps:
            step = make_ps_train_step(loss, tx, _one_device_mesh())
            p, opt = jax.tree.map(jnp.array, params), tx.init(params)
            before = bps.get_metrics()["counters"]
            losses = []
            for _ in range(3):
                p, opt, value = step(p, opt, batch)
                losses.append(float(value))
            jax.block_until_ready((p, opt))
            after = bps.get_metrics()["counters"]
            keys = {c.name for c in get_state().registry.contexts_in_order()}
        loads = {n: after[n] - before.get(n, 0) for n in after
                 if n.startswith("moe/expert_load/")}
        return p, opt, losses, {n: after.get(n, 0) - before.get(n, 0)
                                for n in names}, keys, loads

    cut = run(lambda p, b: joyai.loss_fn(p, b, pc, bias))
    whole = run(lambda p, b: _parents_loss(p, b, pc, bias))
    # forward, the last link, three layers, the lookup
    assert cut[3]["export/backward_programs"] == 3 * (2 + 3 + 1)
    assert whole[3]["export/backward_programs"] == 3
    assert cut[3]["export/shared_leaves"] == 3
    assert cut[3]["export/shared_carry_bytes"] == 3 * params["embed"].nbytes
    assert whole[3]["export/shared_leaves"] == 0
    for side in (cut, whole):
        assert side[3]["wire/push_bytes"] == 3 * n_bytes
        assert side[3]["export/whole_bytes"] == 3 * n_bytes
        assert side[3]["mtp/predicted_tokens"] == 3 * 2 * (32 - 1)
    pieces = sum(a.nbytes for a in jax.tree.leaves(params["runs"][1])
                 if a.nbytes >= 1024)
    assert cut[3]["export/piece_bytes"] == 3 * pieces > 0
    assert all(n.startswith("grad/runs/1/") and n.endswith("of2")
               for n in cut[4] if "@shard" in n)
    assert not any("@shard" in n for n in whole[4])
    for name in ("grad/embed", "grad/head", "grad/mtp/proj",
                 "grad/mtp/block/ffn/w_gate", "grad/runs/0/ffn/w1"):
        assert name in cut[4] and name in whole[4], name
    # the load by (layer, expert), the module's block the last layer
    assert cut[5] == whole[5] and {
        f"moe/expert_load/{l}/{e}" for l in range(3) for e in range(2)
    } <= set(cut[5]) and sum(cut[5][f"moe/expert_load/2/{e}"]
                             for e in range(2)) > 0
    # to float32's last digits, not to the bit: XLA:CPU compiles
    # ``head_nll`` alone and inside the whole backward to programs that
    # differ in the last bit (the mean's scalar moved through a product;
    # ``tests/test_chain.py _assert_trees_close`` has Kimi's case of the
    # same head), and every cotangent behind it inherits that
    assert cut[2][0] == whole[2][0] and cut[2][-1] < cut[2][0]
    np.testing.assert_allclose(cut[2], whole[2], rtol=1e-6)
    # (adam divides by a gradient's own size: where a gradient is
    # rounding alone, a last digit of it is a visible share of one
    # update of 1e-2; a hundredth of an update is the floor here)
    assert jax.tree.structure(cut[:2]) == jax.tree.structure(whole[:2])
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(cut[:2]),
                            jax.tree.leaves(whole[:2])):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4,
                                   atol=1e-4,
                                   err_msg=jax.tree_util.keystr(path))
