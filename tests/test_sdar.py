"""models/sdar.py against the benchmark's plain reference
(benchmark/reference/sdar.py, which imports nothing of the program) at
small sizes with seeded random weights: the block-diffusion loss, every
gradient leaf and three optimizer steps, fused and through the PS step
with a loopback server; the eight expert-parallel shares of one layer
add up to the uncut layer; the reference's explicit mask against its
three parts written out by hand; a batch with nothing masked has loss 0;
the FLOP and byte counts against hand counts."""

import contextlib
import dataclasses
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from benchmark.families import sdar as family
from benchmark.layers._cell import _overlay
from benchmark.optimizers import load as load_optimizer
from benchmark.reference import sdar as reference
from byteps_tpu.config import Config
from byteps_tpu.jax.train import make_ps_train_step, make_train_step
from byteps_tpu.models import moe, sdar
from byteps_tpu.ops.push_pull import psum_tree
from byteps_tpu.server import run_server

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PORT = [25350]


def _file():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "sdar-30b-a3b.json")) as f:
        return json.load(f)


def _config(**over):
    """The benchmark configuration at its rehearsal sizes, in float32:
    4 layers, experts 2-5 of 8 held, blocks of 4, 64 tokens a row."""
    cfg = _file()
    cfg = _overlay(cfg, cfg["rehearse"])
    cfg.update({"compute_dtype": "float32", "first_expert_held": 2, **over})
    return cfg


def _state(cfg, rows=2, seed=7):
    key = jax.random.PRNGKey(seed)
    return (reference.init_params(key, cfg),
            reference.make_batch(key, 0, rows, cfg))


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


def test_the_programs_own_init_has_the_references_tree():
    cfg = _config()
    pc = family.program_config(cfg)
    want = jax.eval_shape(lambda: reference.init_params(
        jax.random.PRNGKey(0), cfg))
    got = jax.eval_shape(lambda: sdar.init_params(jax.random.PRNGKey(0), pc))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert jax.tree.map(lambda a: a.shape, got) == \
        jax.tree.map(lambda a: a.shape, want)
    assert (pc.block_length, pc.mask_id) == (4, 255)
    # none given: the last row held
    assert dataclasses.replace(pc, mask_token_id=None).mask_id == 255


def test_the_file_has_every_published_width_and_the_programs_count():
    """456,346,624 parameters by the program's own tree at the file's
    sizes; every key of the catalog's row as published but the three
    cut."""
    cfg = _file()
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "max_position_embeddings": 32768,
        "max_window_layers": 48, "mlp_only_layers": [],
        "model_type": "sdar_moe", "moe_intermediate_size": 768,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 128, "num_experts_per_tok": 8,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False}
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts_held",
                              "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_experts_held"],
            cfg["vocab_size"]) == (4, 16, 18992)
    assert cfg["published"]["vocab_size"] == 8 * cfg["vocab_size"]
    assert cfg["published"]["num_experts"] == 8 * cfg["num_experts_held"]
    assert {"block_length", "noise", "mask_token_id", "logit_shift",
            "qk_norm", "seq_len", "batch_per_chip"} <= set(cfg["assumed"])
    assert (cfg["block_length"], cfg["mask_token_id"]) == (4, 18991)
    shapes = jax.eval_shape(lambda: sdar.init_params(
        jax.random.PRNGKey(0), family.program_config(cfg)))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) \
        == 456_346_624
    layer = sum(int(np.prod(a.shape[1:]))
                for a in jax.tree.leaves(shapes["blocks"]))
    assert layer == 18_874_368 + 256 + 4_096 + 262_144 + 75_497_472


@pytest.mark.parametrize("seq, block", [(8, 4), (16, 1), (12, 4), (8, 8)])
def test_the_references_mask_is_its_three_parts(seq, block):
    """``block_diffusion_mask`` against the three parts, a pair at a
    time."""
    got = np.asarray(reference.block_diffusion_mask(seq, block))
    assert got.shape == (2 * seq, 2 * seq)
    for p in range(2 * seq):
        for r in range(2 * seq):
            bp, br = p % seq // block, r % seq // block
            if p < seq and r < seq:
                want = bp == br              # its own noised block
            elif p < seq:
                want = br < bp               # the clean blocks before it
            elif r < seq:
                want = False                 # no clean query, a noised key
            else:
                want = br <= bp              # clean, causal by blocks
            assert got[p, r] == want, (p, r)
    assert got.sum() == reference.mask_pairs(seq, block)


def test_the_batch_is_drawn_from_the_key():
    cfg = _config()
    key = jax.random.PRNGKey(3)
    a, b = (reference.make_batch(key, i, 4, cfg) for i in (0, 1))
    assert a["tokens"].shape == (4, 64) and a["rates"].shape == (4, 16)
    assert a["noise_mask"].dtype == bool
    assert int(a["tokens"].max()) < cfg["mask_token_id"]
    assert float(a["rates"].min()) >= 0.45 and float(a["rates"].max()) <= 0.95
    assert not np.array_equal(np.asarray(a["tokens"]),
                              np.asarray(b["tokens"]))
    again = reference.make_batch(key, 0, 4, cfg)
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(again[k]))
    # about 0.7 of the tokens masked
    assert 0.5 < float(a["noise_mask"].mean()) < 0.9


@pytest.mark.parametrize("held, compact", [(4, False), (2, True)])
def test_loss_and_every_leafs_gradient_match_the_reference(held, compact):
    """Half the experts held: the full-size sorted buffer is the only
    one; a quarter: a layer's one slice fits the compact buffer."""
    cfg = _config(num_experts_held=held)
    params, batch = _state(cfg)
    with jax.default_matmul_precision("highest"):
        (loss, stats), grads = jax.value_and_grad(
            family.program_loss(cfg), has_aux=True)(params, batch)
    want, want_grads = jax.value_and_grad(_reference_loss(cfg))(params, batch)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
    _assert_leaves_close(grads, want_grads)
    # 2 rows x 128 positions x 4 layers x top-2, a share of them held
    load = np.asarray(stats["moe/expert_load"])
    assert load.shape == (4, held) and 0 < load.sum() < 2 * 128 * 4 * 2
    assert int(stats["moe/dropped_pairs"]) == 0
    slices = (int(stats["moe/compact_slices"]), int(stats["moe/full_slices"]))
    assert sum(slices) == 4 and (slices[0] > 0) == compact
    assert int(stats["diffusion/masked_tokens"]) \
        == int(batch["noise_mask"].sum())


def test_tracing_the_program_publishes_the_attention_walks():
    """Where the model is traced it sets the sizes of its attention
    kernels' work lists as gauges (``ops/flash_attention.py
    publish_walk_sizes``), once a program, no output of the step: the
    six of each scope, for the mask and grouping the model runs
    (``walk_sizes`` itself is held to the dense mask in
    tests/test_window_attention.py)."""
    from byteps_tpu.core.state import get_state
    from byteps_tpu.ops.flash_attention import walk_sizes

    cfg = _config()
    params, batch = _state(cfg)
    registry = get_state().metrics
    groups = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    want = walk_sizes(2 * cfg["seq_len"], groups, sdar.ATTN_BLOCK,
                      sdar.ATTN_BLOCK, diffusion_block=cfg["block_length"])
    assert len(want) == 6
    assert want["attention/bps.attn.blockdiff/items/query_walk"] == 3
    for name in want:
        registry.gauge(name).set(-1)
    jax.eval_shape(family.program_loss(cfg), params, batch)
    gauges = registry.instruments()[1]
    assert {name: gauges[name].value for name in want} == want

def test_remat_and_tiles_change_nothing(monkeypatch):
    cfg = _config()
    params, batch = _state(cfg)

    def grads(c):
        return jax.grad(lambda p: family.program_loss(c)(p, batch)[0])(params)

    base = grads(cfg)
    monkeypatch.setattr(sdar, "ATTN_BLOCK", 16)
    monkeypatch.setattr(sdar.mellum, "EXPERT_SLICE", 64)
    monkeypatch.setattr(sdar, "LAYER_UNROLL", 1)
    other = grads({**cfg, "remat": True})
    for a, b in zip(jax.tree.leaves(base), jax.tree.leaves(other)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-7)


def test_nothing_masked_is_loss_zero_and_no_masked_token():
    cfg = _config()
    params, batch = _state(cfg)
    batch = {**batch, "noise_mask": jnp.zeros_like(batch["noise_mask"])}
    (loss, stats), grads = jax.value_and_grad(
        family.program_loss(cfg), has_aux=True)(params, batch)
    assert float(loss) == 0.0
    assert int(stats["diffusion/masked_tokens"]) == 0
    assert all(not np.any(np.asarray(g)) for g in jax.tree.leaves(grads))
    total, count = reference.nll_sum(params, batch, cfg)
    assert float(total) == 0.0 and int(count) == 2 * 64


def test_only_the_noised_half_is_scored_and_the_clean_half_sees_no_noise():
    """The loss does not move with the ids at unmasked positions' noised
    copy being what they are... it does with the clean ids a masked
    position may see; and the clean half's hidden states do not depend on
    which tokens are masked."""
    cfg = _config()
    params, batch = _state(cfg)
    pc = family.program_config(cfg)
    clean, noise = batch["tokens"], batch["noise_mask"]

    def hidden(noise):
        noised = jnp.where(noise, pc.mask_id, clean)
        return sdar.forward_hidden(
            params, jnp.concatenate([noised, clean], 1), pc)[0]

    with jax.default_matmul_precision("highest"):
        a, b = hidden(noise), hidden(~noise)
    # no clean query sees a noised key
    np.testing.assert_allclose(np.asarray(a[:, 64:]), np.asarray(b[:, 64:]),
                               rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(a[:, :64] - b[:, :64]).max()) > 1e-3
    # a rate twice as high halves a masked position's weight
    loss = family.program_loss(cfg)
    half = loss(params, {**batch, "rates": batch["rates"] * 2})[0]
    np.testing.assert_allclose(float(half) * 2, float(loss(params, batch)[0]),
                               rtol=1e-5)
    with pytest.raises(ValueError):
        loss(params, {**batch, "rates": batch["rates"][:, :8]})


# ------------------------------------------------------------------ #
# the share
# ------------------------------------------------------------------ #

def test_the_eight_shares_of_a_layer_add_up_to_the_whole_layer():
    """Expert parallel 8 over 128 experts at top-8, as the deployment:
    the outputs the eight chips compute (experts 0-15, 16-31, ...,
    every position routed over all 128) add up to what the uncut
    reference gives for the whole layer."""
    E, k, d, f, T = 128, 8, 32, 24, 96
    cfg = {**_config(), "num_experts": E, "num_experts_held": E,
           "num_experts_per_tok": k, "hidden_size": d,
           "moe_intermediate_size": f, "num_hidden_layers": 1,
           "first_expert_held": 0}
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    whole = {"router": jax.random.normal(ks[0], (d, E)) * 0.3,
             "w_gate": jax.random.normal(ks[1], (E, d, f)) * 0.2,
             "w_up": jax.random.normal(ks[2], (E, d, f)) * 0.2,
             "w_down": jax.random.normal(ks[3], (E, f, d)) * 0.2}
    u = jax.random.normal(ks[4], (1, T, d))

    def uncut(x):
        """The reference's equations for the whole layer, written out:
        softmax over all experts, the 8 largest renormalised, every
        expert's SwiGLU term."""
        probs = jax.nn.softmax(x @ whole["router"], -1)
        gates, idx = jax.lax.top_k(probs, k)
        gates = gates / gates.sum(-1, keepdims=True)
        y = 0.0
        for e in range(E):
            w_e = jnp.sum(jnp.where(idx == e, gates, 0.0), -1)
            h = jax.nn.silu(x @ whole["w_gate"][e]) * (x @ whole["w_up"][e])
            y = y + w_e[:, None] * (h @ whole["w_down"][e])
        return y

    with jax.default_matmul_precision("highest"):
        want = uncut(u[0])
        total, pairs = 0.0, 0
        for share in range(8):
            held = {name: w if name == "router"
                    else w[share * 16:(share + 1) * 16]
                    for name, w in whole.items()}
            out, st = moe.moe_layer(u, held, k, jnp.float32,
                                    first=share * 16)
            total = total + out[0]
            pairs += int(st["load"].sum())
            assert int(st["dropped"]) == 0
            # what one share gives alone is not the layer
            assert float(jnp.abs(out[0] - want).max()) > 1e-3
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    # every pair is computed on exactly one chip
    assert pairs == T * k


def test_the_references_share_is_the_programs():
    """The reference given share 3 of 4 (experts 6-7 of 8) against the
    program given the same."""
    cfg = _config(num_experts_held=2, first_expert_held=6)
    params, batch = _state(cfg)
    with jax.default_matmul_precision("highest"):
        got = family.program_loss(cfg)(params, batch)[0]
    np.testing.assert_allclose(
        float(got), float(_reference_loss(cfg)(params, batch)), rtol=1e-5)


# ------------------------------------------------------------------ #
# the counts
# ------------------------------------------------------------------ #

def test_model_flops_and_attention_cost_against_hand_counts():
    cfg = _file()
    L, B, d, V, layers = 8192, 4, 2048, 18992, 4
    pairs = L * L + L * B
    assert reference.mask_pairs(L, B) == pairs == 67_141_632
    # a quarter of all pairs of 2 L positions and the diagonal blocks;
    # half of what a causal walk over 2 L would let through
    assert pairs == (2 * L) ** 2 // 4 + L * B
    assert abs(pairs / ((2 * L) * (2 * L + 1) // 2) - 0.5) < 1e-3
    per_position = layers * (2 * d * 4096 + 2 * d * 512 + d * 128
                             + 8 * 16 / 128 * 3 * d * 768)
    rows = 2
    macs = per_position * rows * 2 * L + d * V * rows * L \
        + layers * 2 * 32 * 128 * pairs * rows
    assert reference.model_flops_per_step(rows, cfg) == 6.0 * macs
    flops, nbytes = reference.attention_step_cost(rows, cfg)
    assert flops == 7 * 2.0 * 32 * 128 * pairs * rows
    positions = rows * 2 * L
    q_like, kv_like, lse = (2 * positions * 4096, 2 * positions * 512,
                            4 * positions * 32)
    # forward q, out, k, v, lse; backward q, out, dout, dq, k, v, dk, dv, lse
    assert nbytes == 6 * q_like + 6 * kv_like + 2 * lse
    # FLOP bound: a layer's 7.7 TFLOP at 197 TFLOP/s against 1.75 GB at
    # 819 GB/s
    assert flops / 197e12 > 10 * nbytes / 819e9


# ------------------------------------------------------------------ #
# three optimizer steps through the step makers
# ------------------------------------------------------------------ #

@contextlib.contextmanager
def _ps_env():
    from byteps_tpu.core.state import GlobalState

    port = _PORT[0]
    _PORT[0] += 1
    env = {"DMLC_NUM_WORKER": "1", "DMLC_NUM_SERVER": "1",
           "DMLC_PS_ROOT_URI": "127.0.0.1", "DMLC_PS_ROOT_PORT": str(port),
           "BYTEPS_FORCE_DISTRIBUTED": "1"}
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    server = threading.Thread(
        target=run_server,
        args=(port, Config(num_workers=1, num_servers=1)), daemon=True)
    server.start()
    GlobalState._instance = None
    import byteps_tpu as bps
    bps.init()
    try:
        yield bps
    finally:
        bps.shutdown()
        server.join(timeout=10)
        GlobalState._instance = None
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _one_device_mesh():
    return Mesh(np.array(jax.devices()[:1]), ("dp",))


def _reference_steps(cfg, params, batches):
    """The configuration's optimizer written out, on the reference's
    gradients: (losses, parameters after the steps)."""
    optimizer, hyper = load_optimizer(cfg["optimizer"])
    state = optimizer.reference_init(params)
    grad = jax.jit(jax.value_and_grad(_reference_loss(cfg)))
    losses = []
    for batch in batches:
        loss, grads = grad(params, batch)
        params, state = optimizer.reference_update(params, state, grads,
                                                   **hyper)
        losses.append(float(loss))
    return losses, params


@pytest.mark.parametrize("maker", ["fused", "ps"])
def test_three_optimizer_steps_match_the_reference(maker):
    """AdamW as the configuration states it, three steps on three
    batches, through ``make_train_step`` and through ``bps.init()`` ->
    ``make_ps_train_step`` -> a loopback server: each loss and every
    leaf of the parameters after the third step; the ``moe/*`` counters
    and ``diffusion/masked_tokens`` are in the registry."""
    cfg = _config()
    cfg["optimizer"] = {**cfg["optimizer"], "lr": 0.01}
    key = jax.random.PRNGKey(11)
    params = reference.init_params(key, cfg)
    batches = [reference.make_batch(key, i, 2, cfg) for i in range(3)]
    masked = sum(int(b["noise_mask"].sum()) for b in batches)
    want_losses, want = _reference_steps(cfg, params, batches)
    start = jax.tree.map(np.asarray, params)
    optimizer, hyper = load_optimizer(cfg["optimizer"])
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
        with _ps_env() as bps:
            step = make_ps_train_step(loss_fn, tx, mesh)
            before = bps.get_metrics()["counters"]
            losses, got = run(step, tx.init(params), params)
            after = bps.get_metrics()["counters"]
        assert after["diffusion/masked_tokens"] \
            - before.get("diffusion/masked_tokens", 0) == masked
        assert after["moe/dropped_pairs"] \
            - before.get("moe/dropped_pairs", 0) == 0
        # [layer, held expert]: four layers of four
        names = {k for k in after if k.startswith("moe/expert_load/")}
        assert {f"moe/expert_load/{l}/{e}" for l in range(4)
                for e in range(4)} <= names
    np.testing.assert_allclose(losses, want_losses, rtol=2e-5)
    # what is compared is the parameters' CHANGE: AdamW's first steps
    # move every element by about lr whatever the gradient's size
    delta = jax.tree.map(lambda a, b: np.asarray(a) - b, got, start)
    want_delta = jax.tree.map(lambda a, b: np.asarray(a) - b, want, start)
    got_flat = dict(jax.tree_util.tree_leaves_with_path(delta))
    for path, w in jax.tree_util.tree_leaves_with_path(want_delta):
        name = jax.tree_util.keystr(path)
        g = got_flat[path]
        assert np.abs(w).max() > 0, name
        # a gradient element near zero may change its sign between two
        # sound computations and AdamW turns the sign into a whole step:
        # the norms agree, and all but a few elements
        np.testing.assert_allclose(np.linalg.norm(g), np.linalg.norm(w),
                                   rtol=2e-2, err_msg=name)
        off = np.abs(g - w) > 0.05 * np.abs(w).max()
        assert off.mean() < 0.02, (name, off.mean())


def test_no_host_callback_enters_the_step_program():
    cfg = _config()
    params, batch = _state(cfg)
    loss_fn = family.program_loss(cfg)
    import optax
    step = make_train_step(loss_fn, optax.sgd(0.1), _one_device_mesh())
    text = step.jitted.lower(params, optax.sgd(0.1).init(params),
                             batch).as_text()
    assert "callback" not in text and "host_transfer" not in text
    names = {jax.tree_util.keystr(p)
             for p, _ in jax.tree_util.tree_leaves_with_path(params)}
    assert len(names) == 15
