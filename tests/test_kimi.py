"""models/kimi.py against the benchmark's plain reference
(benchmark/reference/kimi.py, which imports nothing of the program and
holds no chunk algebra) at small sizes with seeded random weights: loss
and every gradient leaf; latent attention without rotary against dense
attention; the 32 expert-parallel shares of one sparse layer, the
shared expert counted once, add up to the uncut layer; the statistics;
the steps through ``make_train_step`` and ``make_ps_train_step``; more
than one group is refused; the counts of parameters, FLOPs and bytes
against hand counts."""

import functools
import itertools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from benchmark.families import kimi as family
from benchmark.layers._cell import _overlay
from benchmark.optimizers import load as load_optimizer
from benchmark.reference import kimi as reference
from byteps_tpu.jax.train import make_ps_train_step, make_train_step
from byteps_tpu.models import kimi, moe
from byteps_tpu.ops import chain
from byteps_tpu.ops.flash_attention import latent_attention
from byteps_tpu.ops.push_pull import psum_tree

from test_export_spans import _ps_env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the servers' ports, this file's own (``_ps_env``)
PORTS = itertools.count(25750)


def _file():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "kimi-linear-48b-a3b.json")) as f:
        return json.load(f)


def _config(**over):
    """The benchmark configuration at its rehearsal sizes, in float32:
    KDA + dense, two KDA + sparse, MLA + sparse, KDA + sparse; experts 2
    and 3 of 8 held."""
    cfg = _file()
    cfg = _overlay(cfg, cfg["rehearse"])
    cfg.update({"compute_dtype": "float32", "first_expert_held": 2,
                "seq_len": 40, "remat": False, **over})
    return cfg


def _state(cfg, rows=2, seed=7):
    key = jax.random.PRNGKey(seed)
    params, batch = jax.jit(lambda key: (
        reference.init_params(key, cfg),
        reference.make_batch(key, 0, rows, cfg)))(key)
    # norms and the gate's bias off their start, so that their
    # gradients are no accident
    bump = jax.random.normal(key, (64,))
    return jax.tree_util.tree_map_with_path(
        lambda path, a: a + 0.1 * jnp.resize(bump, a.shape)
        if "norm" in jax.tree_util.keystr(path)
        or "g_bias" in jax.tree_util.keystr(path) else a, params), batch


def _reference_loss(cfg):
    def loss(params, batch):
        with jax.default_matmul_precision("highest"):
            total, count = reference.nll_sum(params, batch, cfg)
        return total / count
    return loss


def _assert_leaves_close(got, want, rtol=2e-3):
    got = dict(jax.tree_util.tree_leaves_with_path(got))
    for path, w in jax.tree_util.tree_leaves_with_path(want):
        np.testing.assert_allclose(
            np.asarray(got[path]), np.asarray(w), rtol=rtol,
            atol=1e-6 + 1e-4 * float(jnp.abs(w).max()),
            err_msg=jax.tree_util.keystr(path))


def test_the_tree_is_the_chains_links_and_the_programs_init_its_shape():
    cfg = _config()
    runs = [("kda", "dense", 1), ("kda", "sparse", 2), ("mla", "sparse", 1),
            ("kda", "sparse", 1)]
    assert reference.layer_runs(cfg) == runs
    pc = family.program_config(cfg)
    assert pc.runs() == runs
    assert (pc.n_layers, pc.n_sparse_layers) == (5, 4)
    want = jax.eval_shape(lambda: reference.init_params(
        jax.random.PRNGKey(0), cfg))
    got = jax.eval_shape(lambda: kimi.init_params(jax.random.PRNGKey(0), pc))
    assert sorted(want) == ["embed", "final_norm", "lm_head", "run00",
                            "run01", "run02", "run03"]
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert jax.tree.map(lambda a: a.shape, got) == \
        jax.tree.map(lambda a: a.shape, want)
    # every leaf of a run is stacked on its depth; no bias is a leaf
    for i, (_, _, n) in enumerate(runs):
        assert {a.shape[0] for a in jax.tree.leaves(want[f"run{i:02d}"])} \
            == {n}
    names = [jax.tree_util.keystr(p)
             for p, _ in jax.tree_util.tree_leaves_with_path(want)]
    assert len(names) == 88 and not any("expert_bias" in n for n in names)
    assert sum(x.size for x in jax.tree.leaves(want)) == \
        reference.param_count(cfg)
    # the published pattern: every fourth layer and the last
    ops = kimi.published_ops()
    assert [i + 1 for i, op in enumerate(ops) if op == "mla"] == \
        _file()["linear_attn_config"]["full_attn_layers"]
    assert [i + 1 for i, op in enumerate(ops) if op == "kda"] == \
        _file()["linear_attn_config"]["kda_layers"]


@pytest.mark.parametrize("held, seq", [(4, 40), (2, 33)],
                         ids=["half-held", "quarter-held-ragged"])
def test_loss_and_every_leafs_gradient_match_the_reference(held, seq):
    """The chunk algebra inside the model against the recurrence inside
    the reference (a length that is no multiple of the chunk too); half
    the experts held walks the full-size sorted buffer, a quarter the
    compact one."""
    cfg = _config(num_experts_held=held, seq_len=seq)
    params, batch = _state(cfg)
    with jax.default_matmul_precision("highest"):
        (loss, stats), grads = jax.jit(jax.value_and_grad(
            family.program_loss(cfg), has_aux=True))(params, batch)
    want, want_grads = jax.jit(jax.value_and_grad(_reference_loss(cfg)))(
        params, batch)
    np.testing.assert_allclose(loss, want, rtol=2e-6)
    _assert_leaves_close(grads, want_grads)
    assert all(np.any(np.asarray(g)) for g in jax.tree.leaves(grads))
    # the statistics: four sparse layers' loads, the KDA layers' steps
    assert stats["moe/expert_load"].shape == (4, held)
    assert np.all(np.asarray(stats["moe/expert_load"]).sum(1) > 0)
    assert int(stats["moe/dropped_pairs"]) == 0
    assert int(stats["moe/compact_slices"]) + int(stats["moe/full_slices"]) \
        == 4
    assert int(stats["kda/chunk_steps"]) == 4 * 2 * 2 * 1
    assert int(stats["kda/tokens"]) == 4 * 2 * seq
    assert 0 < int(stats["moe/bias_moved_pairs"]) < 4 * 2 * seq * 2


def test_the_chain_covers_the_tree_and_its_runs_count_under_one_name():
    cfg = _config()
    params, batch = _state(cfg)
    loss = family.program_loss({**cfg, "remat": True})
    with chain.collecting() as found:
        jax.eval_shape(lambda p, b: loss(p, b), params, batch)
    (ch,) = found
    assert [ln.keys for ln in ch.links] == [
        ("embed",), ("run00",), ("run01",), ("run02",), ("run03",),
        ("final_norm", "lm_head")]
    assert [getattr(ln, "depth", None) for ln in ch.links] == \
        [None, 1, 2, 1, 1, None]
    assert ch.cuts(params)
    # without remat the backward stays one program
    with chain.collecting() as found:
        jax.eval_shape(lambda p, b: family.program_loss(
            {**cfg, "remat": False})(p, b), params, batch)
    assert not found[0].cuts(params)


def test_latent_attention_without_rotary_is_dense_attention():
    """``latent_attention`` as the model calls it, the 64-column parts
    unrotated: the softmax of ``(q_n . k_n + q_r . k_r) / sqrt(192)``
    under a causal mask, ONE ``k_r`` under every head."""
    B, S, H, dn, dr, dv = 2, 48, 3, 16, 8, 16
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    q_n, k_n = (jax.random.normal(k, (B, S, H, dn)) for k in ks[:2])
    q_r = jax.random.normal(ks[2], (B, S, H, dr))
    k_r = jax.random.normal(ks[3], (B, S, 1, dr))
    v = jax.random.normal(ks[4], (B, S, H, dv))
    with jax.default_matmul_precision("highest"):
        got = latent_attention(q_n, q_r, k_n, k_r, v, 16, 16)
        s = (jnp.einsum("bqhd,bkhd->bhqk", q_n, k_n)
             + jnp.einsum("bqhd,bkd->bhqk", q_r, k_r[:, :, 0])) \
            / np.sqrt(dn + dr)
        causal = np.tril(np.ones((S, S), bool))
        want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(
            jnp.where(causal, s, -1e30), -1), v)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_the_32_shares_with_the_shared_expert_once_add_up_to_the_layer():
    """Expert parallel 32 over 256 experts at top-8, as the deployment,
    at this model's scale 2.446: the routed parts the 32 chips compute
    and the shared expert counted ONCE add up to what the uncut
    reference gives for the whole layer."""
    E, k, d, f, T, shares = 256, 8, 32, 16, 64, 32
    cfg = {"num_experts": E, "num_experts_per_token": k,
           "routed_scaling_factor": 2.446, "first_expert_held": 0}
    ks = jax.random.split(jax.random.PRNGKey(3), 9)
    whole = {"router": jax.random.normal(ks[0], (d, E)) * 0.3,
             "w_gate": jax.random.normal(ks[1], (E, d, f)) * 0.2,
             "w_up": jax.random.normal(ks[2], (E, d, f)) * 0.2,
             "w_down": jax.random.normal(ks[3], (E, f, d)) * 0.2,
             "shared_gate": jax.random.normal(ks[4], (d, f)) * 0.2,
             "shared_up": jax.random.normal(ks[5], (d, f)) * 0.2,
             "shared_down": jax.random.normal(ks[6], (f, d)) * 0.2}
    u = jax.random.normal(ks[7], (1, T, d))
    bias = jax.random.uniform(ks[8], (E,), minval=-0.1, maxval=0.1)
    routing = dict(score="sigmoid", select_bias=bias, norm_eps=1e-20,
                   scale=2.446)
    held = E // shares

    @functools.partial(jax.jit, static_argnums=1)
    def share(i, with_shared):
        p = {name: w if name == "router" or name.startswith("shared")
             else jax.lax.dynamic_slice_in_dim(w, i * held, held)
             for name, w in whole.items()
             if with_shared or not name.startswith("shared")}
        return moe.moe_layer(u, p, k, jnp.float32, first=i * held, **routing)

    with jax.default_matmul_precision("highest"):
        want = reference.sparse_ffn(u[0], whole, bias, cfg, reference._mm())
        total, pairs = 0.0, 0
        for i in range(shares):
            out, st = share(i, with_shared=i == 0)
            total = total + out[0]
            pairs += int(st["load"].sum())
            assert int(st["dropped"]) == 0
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    assert pairs == T * k


def test_the_gates_are_float32_and_the_filter_reads_no_other_row():
    cfg = _config(compute_dtype="bfloat16")
    pc = family.program_config(cfg)
    params, _ = _state(cfg)
    p = jax.tree.map(lambda a: a[0], params["run00"]["op"])
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 64), jnp.bfloat16)
    g, beta = kimi.kda_gates(u, p, pc)
    assert g.dtype == beta.dtype == jnp.float32
    assert g.shape == (2, 12, 2, 16) and beta.shape == (2, 12, 2)
    assert float(g.max()) < 0 and 0 < float(beta.min()) \
        and float(beta.max()) < 1
    # a row's first positions see zeros before them, not the row above
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 9, 32))
    got = kimi.short_conv_silu(x, p["conv_q"])
    alone = kimi.short_conv_silu(x[1:], p["conv_q"])
    np.testing.assert_array_equal(got[1:], alone)
    want = reference.conv_silu(x[1], p["conv_q"])
    np.testing.assert_allclose(got[1], want, rtol=1e-6, atol=1e-7)


def test_more_than_one_group_is_refused():
    with pytest.raises(ValueError, match="group"):
        kimi.KimiConfig(n_group=2)
    with pytest.raises(ValueError, match="kda or mla"):
        kimi.KimiConfig(layer_ops=("kda", "rope"))
    cfg = _config()
    with pytest.raises(ValueError, match="group"):
        reference.init_params(jax.random.PRNGKey(0),
                              {**cfg, "num_expert_group": 2})
    with pytest.raises(ValueError, match="rotates nothing"):
        family.program_loss({**cfg, "mla_use_nope": False})


def test_the_counts_against_hand_counts():
    cfg = _file()
    kda = 3 * 2304 * 4096 + 3 * 4 * 4096 + 2 * (2304 * 128 + 128 * 4096) \
        + 4096 + 32 + 4096 + 2304 * 32 + 128 + 4096 * 2304
    mla = 2304 * 32 * 192 + 2304 * 576 + 512 + 512 * 32 * 256 + 4096 * 2304
    dense = 3 * 2304 * 9216
    sparse = 2304 * 256 + 3 * 2304 * 1024 + 8 * 3 * 2304 * 1024
    assert (kda, mla, dense, sparse) == (39_518_368, 29_114_880, 63_700_992,
                                         64_290_816)
    total = (kda + dense) + 3 * (kda + sparse) + (mla + sparse) \
        + 5 * 2 * 2304 + 2 * 20480 * 2304 + 2304
    assert total == reference.param_count(cfg) == 602_449_792
    assert 4 * total == 2_409_799_168
    assert "602,449,792" in cfg["deployment"]
    # the delta rule's need: 21 d_k d_v FLOPs and 3080 bytes a position
    # and head, 16,384 positions x 32 heads a layer
    flops, nbytes = reference.kda_step_cost(2, cfg)
    assert flops == 21 * 128 * 128 * 16384 * 32
    assert nbytes == (8 * 256 + 2 * 512 + 8) * 16384 * 32
    assert 0.0019 < nbytes / 819e9 < 0.0020 and flops / 197e12 < 0.001
    assert reference.kda_layers(cfg) == 4
    # an even router's pairs: a quarter of a pair a token and layer
    assert reference.expected_pairs_per_token(cfg) == 0.25
    per_token = 4 * (4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096)
                     + 2304 * 32) \
        + (mla - 512) + dense + 4 * (2304 * 256 + 3.75 * 2304 * 1024) \
        + 2304 * 20480
    want = 6.0 * (16384 * per_token
                  + 32 * 320 * (8192 * 8193 // 2) * 2) + 4 * flops
    assert reference.model_flops_per_step(2, cfg) == pytest.approx(want)


def _one_device_mesh():
    return Mesh(np.array(jax.devices()[:1]), ("dp",))


@pytest.mark.parametrize("maker", ["fused", "ps"])
def test_three_optimizer_steps_match_the_reference(maker):
    """AdamW as the configuration states it, three steps on three
    batches, through ``make_train_step`` and through ``bps.init()`` ->
    ``make_ps_train_step`` -> a loopback server (remat on: the backward
    is cut): each loss and the norm of every leaf's change; the
    ``moe/*`` and ``kda/*`` counters are in the registry."""
    cfg = _config(remat=True)
    cfg["optimizer"] = {**cfg["optimizer"], "lr": 0.01}
    key = jax.random.PRNGKey(11)
    params = reference.init_params(key, cfg)
    batches = [reference.make_batch(key, i, 2, cfg) for i in range(3)]
    optimizer, hyper = load_optimizer(cfg["optimizer"])
    state = optimizer.reference_init(params)
    grad = jax.jit(jax.value_and_grad(_reference_loss(cfg)))
    want, want_losses = params, []
    for batch in batches:
        loss, grads = grad(want, batch)
        want, state = optimizer.reference_update(want, state, grads, **hyper)
        want_losses.append(float(loss))
    start = jax.tree.map(np.asarray, params)
    tx = optimizer.make_tx(hyper)
    loss_fn = family.program_loss(cfg)
    mesh = _one_device_mesh()

    def run(step, opt, p):
        losses = []
        with jax.default_matmul_precision("highest"):
            for batch in batches:
                p, opt, loss = step(p, opt, batch)
                losses.append(float(loss))
        return losses, p

    if maker == "fused":
        step = make_train_step(
            loss_fn, tx, mesh, donate=False,
            grads_transform=lambda g: psum_tree(g, axis="dp", average=True))
        losses, got = run(step, tx.init(params), params)
        step.fold_stats()
    else:
        with _ps_env(port=next(PORTS)) as bps:
            step = make_ps_train_step(loss_fn, tx, mesh)
            before = bps.get_metrics()["counters"]
            losses, got = run(step, tx.init(params), params)
            after = bps.get_metrics()["counters"]

        def delta(name):
            return after[name] - before.get(name, 0)

        assert delta("kda/chunk_steps") == 3 * 4 * 2 * 2 * 1
        assert delta("kda/tokens") == 3 * 4 * 2 * 40
        assert delta("moe/dropped_pairs") == 0
        assert 0 < delta("moe/bias_moved_pairs") < 3 * 4 * 2 * 40 * 2
        names = {k for k in after if k.startswith("moe/expert_load/")}
        assert {f"moe/expert_load/{l}/{e}" for l in range(4)
                for e in range(2)} <= names
    np.testing.assert_allclose(losses, want_losses, rtol=2e-5)
    change = jax.tree.map(lambda a, b: np.asarray(a) - b, got, start)
    want_change = jax.tree.map(lambda a, b: np.asarray(a) - b, want, start)
    got_flat = dict(jax.tree_util.tree_leaves_with_path(change))
    for path, w in jax.tree_util.tree_leaves_with_path(want_change):
        name = jax.tree_util.keystr(path)
        assert np.abs(w).max() > 0, name
        np.testing.assert_allclose(np.linalg.norm(got_flat[path]),
                                   np.linalg.norm(w), rtol=3e-2,
                                   err_msg=name)


def test_the_scopes_are_in_the_program():
    """``bps.attn.kda`` is where the kernels are called: off the TPU the
    scan runs outside it, and the shifted products keep
    ``bps.conv.short``; no host callback enters the program."""
    cfg = _config()
    params, batch = _state(cfg)
    text = jax.jit(family.program_loss(cfg)).lower(params, batch).as_text(
        debug_info=True)
    assert "bps.conv.short" in text and "callback" not in text
