"""models/afmoe.py against the benchmark's plain reference
(benchmark/reference/afmoe.py, which imports nothing of the program) at
small sizes with seeded random weights: loss and every gradient leaf, at
a tolerance a bfloat16 router or a dropped gate fails; each departure
from a plain decoder on its own (the gate, rotary on the sliding layers
only, the head norms, the four norms, the scaled embedding, selection
under the bias with weights without it, the scale); the 16
expert-parallel shares of one sparse layer, the shared expert counted
once, add up to the uncut layer; the statistics; the steps through
``make_ps_train_step`` and a loopback server (the chain's links, the
backward's programs and the pieces' keys); what the configuration
refuses; the counts of parameters, FLOPs and bytes against hand
counts."""

import dataclasses
import functools
import itertools
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from benchmark.families import afmoe as family
from benchmark.layers._cell import _overlay
from benchmark.optimizers import load as load_optimizer
from benchmark.reference import afmoe as reference
from byteps_tpu.jax.train import make_ps_train_step
from byteps_tpu.models import afmoe, llama, moe
from byteps_tpu.ops import chain

from test_export_spans import _ps_env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the servers' ports, this file's own (``_ps_env``)
PORTS = itertools.count(25800)
# every leaf of the tiny model but the norms rides a key of its own
ENV = {"BYTEPS_FUSION_BYTES": "1024", "BYTEPS_SHARD_MIN_BYTES": "1024"}
SLIDING, FULL = afmoe.SLIDING, afmoe.FULL
RUNS = [((SLIDING, "dense"), 1), ((SLIDING, "sparse"), 1),
        ((FULL, "sparse"), 1), ((SLIDING, "sparse"), 2)]


def _file():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "trinity-mini.json")) as f:
        return json.load(f)


def _config(**over):
    """The benchmark configuration at its rehearsal sizes, in float32:
    dense + sliding, sparse + sliding, sparse + full, two sparse +
    sliding; a window of 8 under 40 positions; experts 2 and 3 of 8
    held."""
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
    # the norms off their start, so that their gradients are no accident
    bump = jax.random.normal(key, (64,))
    return jax.tree_util.tree_map_with_path(
        lambda path, a: a + 0.1 * jnp.resize(bump, a.shape)
        if "norm" in jax.tree_util.keystr(path) else a, params), batch


def _reference_loss(cfg):
    def loss(params, batch):
        with jax.default_matmul_precision("highest"):
            total, count = reference.nll_sum(params, batch, cfg)
        return total / count
    return loss


def _program(cfg):
    @jax.jit
    def run(params, batch):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(family.program_loss(cfg),
                                      has_aux=True)(params, batch)
    return run


def _worst_gap(got, want):
    """The widest gap between two trees' leaves, as a share of the
    wanted leaf's largest entry."""
    return max(float(jnp.abs(g - w).max() / jnp.abs(w).max())
               for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)))


def test_the_tree_is_the_chains_links_and_the_programs_init_its_shape():
    cfg = _config()
    assert reference.layer_runs(cfg) == RUNS
    pc = family.program_config(cfg)
    assert pc.runs() == RUNS
    assert (pc.n_layers, pc.n_sparse_layers) == (5, 4)
    want = jax.eval_shape(lambda: reference.init_params(
        jax.random.PRNGKey(0), cfg))
    got = jax.eval_shape(lambda: afmoe.init_params(jax.random.PRNGKey(0), pc))
    assert sorted(want) == ["embed", "final_norm", "lm_head", "runs"]
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert jax.tree.map(lambda a: a.shape, got) == \
        jax.tree.map(lambda a: a.shape, want)
    # every leaf of a run is stacked on its depth; no bias is a leaf
    for run, (_, n) in zip(want["runs"], RUNS):
        assert {a.shape[0] for a in jax.tree.leaves(run)} == {n}
    names = [jax.tree_util.keystr(p)
             for p, _ in jax.tree_util.tree_leaves_with_path(want)]
    assert len(names) == 71 and not any("expert_bias" in n for n in names)
    # six projections and four norms a layer's attention: the gate's too
    assert sorted(want["runs"][2]["attn"]) == [
        "k_norm", "norm", "post_norm", "q_norm", "wg", "wk", "wo", "wq",
        "wv"]
    assert sum(x.size for x in jax.tree.leaves(want)) == \
        reference.param_count(cfg)
    # the published pattern: every fourth layer attends to everything;
    # the cell's five are published layers 1-5
    published = afmoe.published_layers()
    assert list(published) == _file()["published"]["layer_types"]
    assert list(published[1:6]) == _file()["layer_types"]


@pytest.fixture(scope="module")
def compared():
    """One compiled step of the program and of the reference, a quarter
    of the experts held (the compact sorted buffer), a length the window
    does not divide."""
    cfg = _config(seq_len=37)
    params, batch = _state(cfg)
    lowered = _program(cfg).lower(params, batch)
    (loss, stats), grads = lowered.compile()(params, batch)
    ref_grad = jax.jit(jax.value_and_grad(_reference_loss(cfg)))
    want, want_grads = ref_grad(params, batch)
    return types.SimpleNamespace(
        cfg=cfg, params=params, batch=batch, loss=loss, stats=stats,
        grads=grads, want=want, want_grads=want_grads, ref_grad=ref_grad,
        text=lowered.as_text(debug_info=True))


# The comparison's tolerance: both sides are float32 at ``highest``, so
# what is left is the order of the sums (the blockwise softmax against
# the dense one, the sorted grouped products against the loop, a row's
# cross-entropy at a time): 1e-6 of a leaf's largest entry is read, 2e-5
# leaves room for another order on another machine. A bfloat16 router
# reads 0.6 (it selects other experts) and a dropped gate 1.0 (``W_g``
# gets no gradient) on the same leaves (the test below).
GAP = 2e-5


def test_loss_and_every_leafs_gradient_match_the_reference(compared):
    c = compared
    np.testing.assert_allclose(c.loss, c.want, rtol=2e-6)
    assert jax.tree.structure(c.grads) == jax.tree.structure(c.want_grads)
    assert _worst_gap(c.grads, c.want_grads) < GAP
    assert all(np.any(np.asarray(g)) for g in jax.tree.leaves(c.grads))


def test_the_statistics_count_the_loads_and_the_masks_triples(compared):
    cfg, stats = compared.cfg, compared.stats
    rows, S = compared.batch["inputs"].shape
    assert stats["moe/expert_load"].shape == (4, 2)
    assert np.asarray(stats["moe/expert_load"]).sum() > 0
    assert int(stats["moe/dropped_pairs"]) == 0
    # a quarter of the experts held: every sparse layer's one slice
    # walks the compact buffer
    assert (int(stats["moe/compact_slices"]),
            int(stats["moe/full_slices"])) == (4, 0)
    assert 0 < int(stats["moe/bias_moved_pairs"]) < 4 * rows * S * 2
    # the masks' (query, key, head) triples, by hand: 4 heads; under the
    # window of 8 a row of 37 has 36 + 29 * 8 pairs, four such layers;
    # the causal mask 37 * 38 / 2, one layer
    assert afmoe.band_pairs(S, 8) == 36 + 29 * 8 == reference.band_pairs(S, 8)
    assert afmoe.band_pairs(S) == 703 == reference.band_pairs(S)
    assert float(stats["attn/window_pairs"]) == rows * 4 * 4 * 268
    assert float(stats["attn/full_pairs"]) == rows * 4 * 1 * 703
    want = reference.mask_triples_per_step(rows, cfg)
    assert want == {SLIDING: float(stats["attn/window_pairs"]),
                    FULL: float(stats["attn/full_pairs"])}
    # the cell's own counts pass 32 bits, so the statistic is float32;
    # both are exact there
    big = reference.mask_triples_per_step(4, _file())
    assert big == {SLIDING: 4 * 32 * 4 * 14_681_088,
                   FULL: 4 * 32 * 33_558_528}
    assert big[SLIDING] > 2 ** 32
    assert all(float(np.float32(v)) == v for v in big.values())


@pytest.mark.parametrize("fault", ["bfloat16-router", "no-gate"])
def test_the_tolerance_fails_a_bfloat16_router_and_a_dropped_gate(
        compared, monkeypatch, fault):
    cfg, want = compared.cfg, compared.want
    if fault == "no-gate":
        monkeypatch.setattr(afmoe, "gated_output",
                            lambda o, u, wg, dtype: o)
    else:
        cfg = {**cfg, "router_dtype": "bfloat16"}
    (loss, _), grads = _program(cfg)(compared.params, compared.batch)
    assert _worst_gap(grads, compared.want_grads) > 20 * GAP
    if fault == "no-gate":
        assert abs(float(loss) - float(want)) > 1e-4 * float(want)


# --------------------------------------------------------------------- #
# each departure on its own
# --------------------------------------------------------------------- #

def _layer(cfg, run=1, seed=3, rows=1):
    """(program configuration, one layer's leaves of ``run``, a normed
    input ``[rows, S, d]``, the sliding layers' table)."""
    pc = family.program_config(cfg)
    params, _ = _state(cfg)
    p = jax.tree.map(lambda a: a[0], params["runs"][run])
    u = jax.random.normal(jax.random.PRNGKey(seed),
                          (rows, cfg["seq_len"], pc.dim))
    return pc, p, u, llama.rope_cache(pc, cfg["seq_len"])


@functools.partial(jax.jit, static_argnums=(3, 4))
def _attn(u, p, rope, pc, kind):
    with jax.default_matmul_precision("highest"):
        return afmoe._attention(u, p, rope, pc, kind)


def _plain_attention(u, p, cfg, kind):
    """Ungated attention of one row written out with the reference's
    pieces: head norms, rotation on a sliding layer, a dense mask."""
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    S, eps = u.shape[0], cfg["rms_norm_eps"]
    q = reference._rmsnorm((u @ p["wq"]).reshape(S, nh, hd), p["q_norm"], eps)
    k = reference._rmsnorm((u @ p["wk"]).reshape(S, nkv, hd), p["k_norm"],
                           eps)
    v = (u @ p["wv"]).reshape(S, nkv, hd)
    if kind == SLIDING:
        cos, sin = reference.rope_table(cfg, S)
        q, k = reference._rotate(q, cos, sin), reference._rotate(k, cos, sin)
    k, v = (jnp.repeat(a, nh // nkv, axis=1) for a in (k, v))
    s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
    seen = reference.mask(S, kind, cfg["sliding_window"])
    probs = jax.nn.softmax(jnp.where(seen, s, -1e30), -1)
    return jnp.einsum("hqk,khd->qhd", probs, v).reshape(S, nh * hd) @ p["wo"]


def test_a_zeroed_gate_halves_the_attention_output():
    """``sigmoid(0) = 1/2`` on every column: with ``W_g`` zeroed the
    layer gives half the ungated attention (written out here), and with
    its own ``W_g`` it does not."""
    cfg, kind = _config(), SLIDING
    pc, p, u, rope = _layer(cfg)
    zeroed = _attn(u, {**p["attn"], "wg": jnp.zeros_like(p["attn"]["wg"])},
                   rope, pc, kind)
    gated = _attn(u, p["attn"], rope, pc, kind)
    with jax.default_matmul_precision("highest"):
        plain = jax.jit(lambda u, p: _plain_attention(u, p, cfg, kind))(
            u[0], p["attn"])
        want = jax.jit(lambda u, p: reference.attention(
            u, p, cfg, kind, reference._mm()))(u[0], p["attn"])
    np.testing.assert_allclose(zeroed[0], 0.5 * plain, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(gated[0], want, rtol=1e-5, atol=1e-7)
    assert float(jnp.abs(gated[0] - 0.5 * plain).max()) > \
        1e-3 * float(jnp.abs(plain).max())


def test_only_the_sliding_layers_know_a_position():
    """A full layer has no positional term: with the positions before
    the last shuffled, the last position's output is the same sum in
    another order. A sliding layer rotates by position, so the same
    shuffle changes it (the window here is the whole row, so the mask is
    the same causal one)."""
    cfg = _config(sliding_window=64)
    pc, p, u, rope = _layer(cfg)
    S = u.shape[1]
    order = np.concatenate([np.random.RandomState(0).permutation(S - 1),
                            [S - 1]])
    last = {kind: [_attn(x, p["attn"], rope, pc, kind)[0, -1]
                   for x in (u, u[:, order])]
            for kind in (SLIDING, FULL)}
    np.testing.assert_allclose(*last[FULL], rtol=1e-5, atol=1e-7)
    assert float(jnp.abs(last[SLIDING][0] - last[SLIDING][1]).max()) > \
        1e-2 * float(jnp.abs(last[SLIDING][0]).max())
    # and the table given is read by no full layer
    moved = _attn(u, p["attn"], llama.rope_cache(pc, S, offset=5), pc, FULL)
    np.testing.assert_array_equal(moved[0, -1], last[FULL][0])


def test_the_head_norms_make_the_scores_blind_to_the_projections_scale():
    """q and k are normalised a head before anything reads them: three
    times ``W_q`` and a third of ``W_k`` give the same layer (up to the
    eps under the root, set small here); the norms' weights do not
    cancel."""
    cfg = _config(rms_norm_eps=1e-12)
    pc, p, u, rope = _layer(cfg)
    a = p["attn"]
    want = _attn(u, a, rope, pc, SLIDING)
    scaled = _attn(u, {**a, "wq": 3 * a["wq"], "wk": a["wk"] / 3}, rope, pc,
                   SLIDING)
    weighed = _attn(u, {**a, "q_norm": 3 * a["q_norm"]}, rope, pc, SLIDING)
    np.testing.assert_allclose(scaled, want, rtol=1e-4, atol=1e-7)
    assert float(jnp.abs(weighed - want).max()) > \
        1e-2 * float(jnp.abs(want).max())
    # each head is normalised on its own, in float32
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 5, 4, 16), jnp.bfloat16)
    got = afmoe.head_norm_rope(x, jnp.ones((16,)), None, 1e-12)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(jnp.mean(got * got, -1), 1.0, rtol=1e-5)


def test_every_sublayer_is_normed_before_and_after():
    """``h = x + N_2(Attn(N_1(x)))``, ``y = h + N_4(FFN(N_3(h)))``: with
    the FFN's output norm at zero a block adds ``N_2(Attn(.))``, whose
    every token has the root mean square of the norm's weight (one
    here), whatever the size of ``W_o``; with both at zero the block is
    the identity; ten times the input changes what is added by nothing
    but the eps."""
    cfg = _config(rms_norm_eps=1e-12)
    pc = family.program_config(cfg)
    params = reference.init_params(jax.random.PRNGKey(7), cfg)
    p = jax.tree.map(lambda a: a[0], params["runs"][0])
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 40, 64))
    rope = llama.rope_cache(pc, 40)
    kind = RUNS[0][0]

    @jax.jit
    def block(x, p):
        with jax.default_matmul_precision("highest"):
            return afmoe._block(x, p, None, rope, pc, kind, None)[0]

    def without(p, *groups):
        return {g: {**p[g], "post_norm": jnp.zeros_like(p[g]["post_norm"])}
                if g in groups else p[g] for g in p}

    np.testing.assert_array_equal(block(x, without(p, "attn", "ffn")), x)
    added = block(x, without(p, "ffn")) - x
    np.testing.assert_allclose(jnp.sqrt(jnp.mean(added * added, -1)), 1.0,
                               rtol=1e-4)
    big = {**p, "attn": {**p["attn"], "wo": 7 * p["attn"]["wo"]}}
    np.testing.assert_allclose(block(x, without(big, "ffn")) - x, added,
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(block(10 * x, without(p, "ffn")) - 10 * x,
                               added, rtol=1e-3, atol=1e-5)
    # the FFN's two norms alike: N_4's output has the weight's size
    added = block(x, without(p, "attn")) - x
    np.testing.assert_allclose(jnp.sqrt(jnp.mean(added * added, -1)), 1.0,
                               rtol=1e-4)
    # against the reference's block, both sublayers on
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda x, p: reference.block(
            x, p, None, cfg, kind, reference._mm()))(x[0], p)
    np.testing.assert_allclose(block(x, p)[0], want, rtol=1e-5, atol=1e-6)


def test_the_embedding_is_scaled_by_the_root_of_the_width():
    """A model of no layer: what the lookup hands the first block is the
    embedding's rows times ``sqrt(hidden_size)``, 8 here, ``sqrt(2048)``
    in the cell; the head is scaled by nothing."""
    cfg = _config(num_hidden_layers=0, layer_types=[])
    pc = family.program_config(cfg)
    assert pc.runs() == []
    params, batch = _state(cfg)
    x, stats = afmoe.forward_hidden(params, batch["inputs"], pc)
    np.testing.assert_array_equal(x, params["embed"][batch["inputs"]] * 8.0)
    assert stats == {} and reference.embed_scale(cfg) == 8.0
    assert reference.embed_scale(_file()) == np.sqrt(2048)
    with jax.default_matmul_precision("highest"):
        loss, _ = afmoe.loss_fn(params, batch, pc)
        want = _reference_loss(cfg)(params, batch)
        # by hand: the final norm and the head over the scaled rows
        h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5) \
            * params["final_norm"]
        logp = jax.nn.log_softmax(h @ params["lm_head"], -1)
        by_hand = -jnp.mean(jnp.take_along_axis(
            logp, batch["targets"][..., None], -1))
    np.testing.assert_allclose(loss, want, rtol=2e-6)
    np.testing.assert_allclose(loss, by_hand, rtol=2e-6)


def test_the_router_selects_under_the_bias_and_weighs_without_it():
    """``route_scale`` 2.826 over the eight selected sigmoid scores'
    sum; a bias that lifts the weakest expert into the selection moves
    its index in and leaves its weight the plain score's share."""
    T, d, E, k = 24, 16, 128, 8
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    x = jax.random.normal(ks[0], (T, d))
    w = jax.random.normal(ks[1], (d, E)) * 0.3
    scores = jax.nn.sigmoid(jnp.matmul(x, w, precision="highest"))
    weakest = int(jnp.argmin(scores[0]))
    bias = jnp.zeros((E,)).at[weakest].set(1.0)
    with jax.default_matmul_precision("highest"):
        gates, idx, probs = moe.route(
            x, w, k, score="sigmoid", select_bias=bias, norm_eps=1e-20,
            scale=2.826)
        plain, plain_idx, _ = moe.route(x, w, k, score="sigmoid",
                                        norm_eps=1e-20, scale=2.826)
    want, want_idx = reference.route(scores, bias, k, 2.826)
    np.testing.assert_array_equal(idx, want_idx)
    np.testing.assert_allclose(gates, want, rtol=1e-6)
    np.testing.assert_allclose(gates.sum(-1), 2.826, rtol=1e-6)
    assert weakest in np.asarray(idx[0]) \
        and weakest not in np.asarray(plain_idx[0])
    slot = int(np.argmax(np.asarray(idx[0]) == weakest))
    picked = np.asarray(scores[0])[np.asarray(idx[0])]
    np.testing.assert_allclose(gates[0, slot],
                               2.826 * picked[slot] / picked.sum(), rtol=1e-6)
    # a pair a token whose own eight did not hold that expert already
    outside = int(np.sum(~np.any(np.asarray(plain_idx) == weakest, axis=1)))
    assert 0 < int(moe.bias_moved_pairs(probs, idx)) == outside <= T
    assert int(moe.bias_moved_pairs(probs, plain_idx)) == 0


def test_the_16_shares_with_the_shared_expert_once_add_up_to_the_layer():
    """Expert parallel 16 over 128 experts at top-8, as the deployment,
    at this model's scale 2.826: the routed parts the 16 chips compute
    and the shared expert counted ONCE add up to what the uncut
    reference gives for the whole layer."""
    E, k, d, f, T, shares = 128, 8, 32, 16, 64, 16
    cfg = {"num_experts": E, "num_experts_per_tok": k, "route_scale": 2.826,
           "first_expert_held": 0}
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
                   scale=2.826)
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


# --------------------------------------------------------------------- #
# the chain and the steps
# --------------------------------------------------------------------- #

def test_the_chain_covers_the_tree_and_cuts_where_a_run_is_deeper_than_one():
    cfg = _config()
    params, batch = _state(cfg)
    loss = family.program_loss({**cfg, "remat": True})
    with chain.collecting() as found:
        jax.eval_shape(lambda p, b: loss(p, b), params, batch)
    (ch,) = found
    assert [ln.keys for ln in ch.links] == [
        ("embed",), (("runs", 0),), (("runs", 1),), (("runs", 2),),
        (("runs", 3),), ("final_norm", "lm_head")]
    assert [getattr(ln, "depth", None) for ln in ch.links] == \
        [None, 1, 1, 1, 2, None]
    assert ch.cuts(params)
    # without remat the backward stays one program
    with chain.collecting() as found:
        jax.eval_shape(lambda p, b: family.program_loss(
            {**cfg, "remat": False})(p, b), params, batch)
    assert not found[0].cuts(params)
    # the bias is an argument: one row a sparse layer, or refused
    pc = family.program_config(cfg)
    with pytest.raises(ValueError, match="a row a sparse layer"):
        afmoe.loss_fn(params, batch, pc, jnp.zeros((5, 8)))


def _one_device_mesh():
    return Mesh(np.array(jax.devices()[:1]), ("dp",))


def test_three_optimizer_steps_through_the_server_match_the_reference(
        compared):
    """AdamW as the configuration states it, three steps on three
    batches through ``bps.init()`` -> ``make_ps_train_step`` -> a
    loopback server (remat on: the backward is cut): each loss and the
    norm of every leaf's change; the counters are in the registry; eight
    backward programs a step, the run of two layers leaving as pieces,
    the runs of one on the one-program step's keys. (``make_train_step``
    runs the same loss as the control of the cell's rehearsal.)"""
    from byteps_tpu.core.state import get_state

    cfg, ref_grad = {**compared.cfg, "remat": True}, compared.ref_grad
    cfg["optimizer"] = {**cfg["optimizer"], "lr": 0.01}
    key = jax.random.PRNGKey(11)
    params = reference.init_params(key, cfg)
    batches = [reference.make_batch(key, i, 2, cfg) for i in range(3)]
    optimizer, hyper = load_optimizer(cfg["optimizer"])
    state = optimizer.reference_init(params)
    want, want_losses = params, []
    for batch in batches:
        loss, grads = ref_grad(want, batch)
        want, state = optimizer.reference_update(want, state, grads, **hyper)
        want_losses.append(float(loss))
    start = jax.tree.map(np.asarray, params)
    tx = optimizer.make_tx(hyper)
    with _ps_env(ENV, port=next(PORTS)) as bps:
        step = make_ps_train_step(family.program_loss(cfg), tx,
                                  _one_device_mesh())
        before = bps.get_metrics()["counters"]
        got, opt, losses = params, tx.init(params), []
        with jax.default_matmul_precision("highest"):
            for batch in batches:
                got, opt, loss = step(got, opt, batch)
                losses.append(float(loss))
        after = bps.get_metrics()["counters"]
        keys = [c.name for c in get_state().registry.contexts_in_order()]
        spans = get_state().profiler.last_spans()

    def delta(name):
        return after[name] - before.get(name, 0)

    triples = reference.mask_triples_per_step(2, cfg)
    assert delta("attn/window_pairs") == 3 * triples[SLIDING]
    assert delta("attn/full_pairs") == 3 * triples[FULL]
    assert delta("moe/dropped_pairs") == 0
    assert 0 < delta("moe/bias_moved_pairs") < 3 * 4 * 2 * 37 * 2
    names = {k for k in after if k.startswith("moe/expert_load/")}
    assert {f"moe/expert_load/{l}/{e}" for l in range(4)
            for e in range(2)} <= names
    # the forward, the head, the run of two a layer at a time, the
    # three runs of one, the lookup
    assert delta("export/backward_programs") == 3 * 8
    n_bytes = sum(leaf.nbytes for leaf in jax.tree.leaves(params))
    assert delta("wire/push_bytes") == 3 * n_bytes
    pieces = sum(a.nbytes for a in jax.tree.leaves(params["runs"][3])
                 if a.nbytes >= 1024)
    assert delta("export/piece_bytes") == 3 * pieces > 0
    piece_names = {n for n in keys if "@shard" in n}
    assert piece_names and all(n.startswith("grad/runs/3/")
                               and n.endswith("of2") for n in piece_names)
    for name in ("grad/runs/0/attn/wg", "grad/runs/1/ffn/w_gate",
                 "grad/runs/2/attn/wq", "grad/embed", "grad/lm_head"):
        assert name in keys, name
    programs = sorted((s for s in spans
                       if s[0] == "bps.step.backward_program"),
                      key=lambda s: s[2])
    assert [s[4]["links"] for s in programs] == [
        "0-4", "5", "4", "4", "3", "2", "1", "0"]
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


def test_the_scopes_are_in_the_program_and_the_host_is_not(compared):
    """``bps.attn.gate`` holds the gate's projection, sigmoid and
    product; the router and the shared expert keep theirs; no host
    callback enters the program."""
    text = compared.text
    for scope in ("bps.attn.gate", "bps.moe.route", "bps.moe.shared"):
        assert scope in text, scope
    assert "callback" not in text


def test_what_the_configuration_refuses():
    for over in (dict(groups=(2, 1, 1, 1)), dict(groups=(1, 1, 1, 4)),
                 dict(score_func="softmax"),
                 dict(tie_word_embeddings=True),
                 dict(layer_types=(SLIDING, "conv"))):
        with pytest.raises(ValueError):
            afmoe.AfmoeConfig(**over)
    cfg = _config()
    for key in ("n_group", "topk_group", "num_expert_groups",
                "num_limited_groups"):
        with pytest.raises(ValueError, match="group"):
            family.program_loss({**cfg, key: 2})
        with pytest.raises(ValueError, match="group"):
            reference.init_params(jax.random.PRNGKey(0), {**cfg, key: 2})
    with pytest.raises(ValueError, match="sigmoid"):
        family.program_loss({**cfg, "score_func": "softmax"})
    with pytest.raises(ValueError, match="tied"):
        family.program_loss({**cfg, "tie_word_embeddings": True})
    with pytest.raises(ValueError, match="sqrt"):
        family.program_loss({**cfg, "mup_enabled": False})
    with pytest.raises(ValueError, match="untied"):
        reference.nll_sum(None, None, {**cfg, "route_norm": False})
    # the tiny preset is the cell's five layers
    tiny = afmoe.AfmoeConfig.tiny()
    assert tiny.runs() == RUNS and dataclasses.replace(
        tiny, remat=True).n_sparse_layers == 4


def test_the_counts_against_hand_counts():
    cfg = _file()
    attn = 3 * 2048 * 4096 + 2 * 2048 * 512 + 2 * 128 + 2 * 2048
    dense = 3 * 2048 * 6144 + 2 * 2048
    sparse = 2048 * 128 + 3 * 2048 * 1024 + 8 * 3 * 2048 * 1024 + 2 * 2048
    assert (attn, dense, sparse) == (27_267_328, 37_752_832, 56_889_344)
    total = 5 * attn + dense + 4 * sparse + 2 * 25024 * 2048 + 2048
    assert total == reference.param_count(cfg) == 504_147_200
    assert 4 * total == 2_016_588_800
    assert "504,147,200" in cfg["deployment"]
    # an even router's pairs: half a pair a token and sparse layer
    assert reference.expected_pairs_per_token(cfg) == 0.5
    assert reference.sparse_layers(cfg) == 4
    # a sparse layer: the router, the shared expert's three products and
    # half a pair's three
    per_token = 5 * (3 * 2048 * 4096 + 2 * 2048 * 512) + 3 * 2048 * 6144 \
        + 4 * (2048 * 128 + 1.5 * 3 * 2048 * 1024) + 2048 * 25024
    band = 4 * 14_681_088 + 33_558_528
    want = 6.0 * (32768 * per_token + 4 * 32 * band * 2 * 128)
    assert reference.model_flops_per_step(4, cfg) == pytest.approx(want)
    # the held experts' products at an even router's 65,536 pairs a
    # step: 2.47 TFLOP against 2.1 GB, the FLOPs decide (12.6 ms)
    flops, nbytes = reference.expert_products_cost(65536.0, cfg)
    assert flops == 18 * 65536 * 2048 * 1024
    assert nbytes == 3 * (2 * 65536 * (2 * 3072 + 3072)
                          + 2 * 4 * 8 * 3 * 2048 * 1024)
    assert flops / 197e12 > nbytes / 819e9
