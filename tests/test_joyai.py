"""models/joyai.py against the benchmark's plain reference
(benchmark/reference/joyai.py, which imports nothing of the program) at
small sizes with seeded random weights: loss, every gradient leaf and
three optimizer steps, fused and through the PS step with a loopback
server; the 32 expert-parallel shares of one sparse layer, the shared
expert counted once, add up to the uncut layer; a weight of zero leaves
the main loss alone; the embedding's and the head's gradients are the
sums of their two uses'; more than one group is refused; the counts of
FLOPs and bytes against hand counts."""

import contextlib
import functools
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from benchmark.families import joyai as family
from benchmark.layers._cell import _overlay
from benchmark.optimizers import load as load_optimizer
from benchmark.reference import joyai as reference
from byteps_tpu.config import Config
from byteps_tpu.jax.train import make_ps_train_step, make_train_step
from byteps_tpu.models import joyai, moe
from byteps_tpu.ops.push_pull import psum_tree
from byteps_tpu.server import run_server

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PORT = [25410]


def _file():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "joyai-llm-flash.json")) as f:
        return json.load(f)


def _config(**over):
    """The benchmark configuration at its rehearsal sizes, in float32:
    a dense layer, two sparse ones and the module, experts 2 and 3 of 8
    held."""
    cfg = _file()
    cfg = _overlay(cfg, cfg["rehearse"])
    cfg.update(compute_dtype="float32", first_expert_held=2, seq_len=32,
               **over)
    return cfg


def _state(cfg, rows=2, seed=7):
    key = jax.random.PRNGKey(seed)
    return jax.jit(lambda key: (reference.init_params(key, cfg),
                                reference.make_batch(key, 0, rows, cfg)))(key)


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


def test_the_tree_has_the_runs_and_the_module_and_the_programs_init_its_shape():
    cfg = _config()
    assert reference.layer_runs(cfg) == [("dense", 1), ("sparse", 2)]
    pc = family.program_config(cfg)
    assert pc.runs() == reference.layer_runs(cfg)
    assert (pc.n_layers, pc.n_sparse_layers, pc.n_mtp) == (3, 2, 1)
    want = jax.eval_shape(lambda: reference.init_params(
        jax.random.PRNGKey(0), cfg))
    got = jax.eval_shape(lambda: joyai.init_params(jax.random.PRNGKey(0), pc))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert jax.tree.map(lambda a: a.shape, got) == \
        jax.tree.map(lambda a: a.shape, want)
    # the embedding and the head are in the tree once
    names = [jax.tree_util.keystr(p)
             for p, _ in jax.tree_util.tree_leaves_with_path(want)]
    assert sum("embed" in n for n in names) == 1
    assert sum("'head'" in n for n in names) == 1
    assert len(names) == 51 and not any("bias" in n for n in names)
    assert sum(x.size for x in jax.tree.leaves(want)) == \
        reference.param_count(cfg)


def test_the_file_holds_the_published_widths_and_the_programs_count():
    """Every width as published, the three cuts named, and the count of
    parameters the deployment states is the program's own."""
    cfg = _file()
    assert set(cfg["reduced"]) == {"num_hidden_layers", "num_experts_held",
                                   "vocab_size"}
    widths = dict(hidden_size=2048, num_attention_heads=32, q_lora_rank=1536,
                  kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
                  v_head_dim=128, intermediate_size=7168,
                  moe_intermediate_size=768, n_routed_experts=256,
                  num_experts_per_tok=8, n_shared_experts=1,
                  routed_scaling_factor=2.5, rope_theta=32000000,
                  rms_norm_eps=1e-6, num_nextn_predict_layers=1,
                  first_k_dense_replace=1)
    assert {k: cfg[k] for k in widths} == widths
    assert (cfg["num_hidden_layers"], cfg["num_experts_held"],
            cfg["vocab_size"]) == (5, 8, 16160)
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    shapes = jax.eval_shape(lambda: joyai.init_params(
        jax.random.PRNGKey(0), family.program_config(cfg)))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    assert n == reference.param_count(cfg) == 491_696_128
    assert "491,696,128" in cfg["deployment"]
    # three expert leaves of [4, 8, 2048, 768] beside norms of 2-8 KB
    assert shapes["runs"][1]["ffn"]["w_gate"].shape == (4, 8, 2048, 768)
    assert shapes["mtp"]["proj"].shape == (4096, 2048)


@pytest.mark.parametrize("held, compact", [(4, False), (2, True)])
def test_loss_and_every_leafs_gradient_match_the_reference(held, compact):
    """Half the experts held: the full-size sorted buffer is the only
    one; a quarter: a slice may fit the compact buffer."""
    cfg = _config(num_experts_held=held)
    params, batch = _state(cfg)
    with jax.default_matmul_precision("highest"):
        (loss, stats), grads = jax.jit(jax.value_and_grad(
            family.program_loss(cfg), has_aux=True))(params, batch)
    want, want_grads = jax.jit(jax.value_and_grad(_reference_loss(cfg)))(
        params, batch)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
    _assert_leaves_close(grads, want_grads)
    # two sparse layers and the module's block as one more layer
    load = np.asarray(stats["moe/expert_load"])
    assert load.shape == (3, held) and 0 < load.sum() < 2 * 32 * 3 * 2
    assert int(stats["moe/dropped_pairs"]) == 0
    slices = (int(stats["moe/compact_slices"]), int(stats["moe/full_slices"]))
    assert sum(slices) == 3 and (compact or slices[0] == 0)
    assert 0 < int(stats["moe/bias_moved_pairs"]) < 2 * 32 * 3 * 2
    # the module has a target at every position of a row but its last
    assert int(stats["mtp/predicted_tokens"]) == 2 * (32 - 1)
    assert float(stats["mtp/nll_sum"]) > 0


def test_tracing_the_program_publishes_the_latent_walks():
    from byteps_tpu.core.state import get_state
    from byteps_tpu.ops.flash_attention import walk_sizes

    cfg = _config()
    params, batch = _state(cfg)
    registry = get_state().metrics
    want = walk_sizes(cfg["seq_len"], 1, joyai.ATTN_BLOCK, joyai.ATTN_BLOCK,
                      latent=True)
    assert len(want) == 6
    assert all(name.startswith("attention/bps.attn.mla/") for name in want)
    # the causal mask's lists under another name
    plain = walk_sizes(cfg["seq_len"], 1, joyai.ATTN_BLOCK, joyai.ATTN_BLOCK)
    assert sorted(want.values()) == sorted(plain.values())
    for name in want:
        registry.gauge(name).set(-1)
    jax.eval_shape(family.program_loss(cfg), params, batch)
    gauges = registry.instruments()[1]
    assert {name: gauges[name].value for name in want} == want


def test_remat_and_tiles_change_nothing(monkeypatch):
    cfg = _config()
    params, batch = _state(cfg)

    def grads(c):
        return jax.jit(jax.grad(
            lambda p: family.program_loss(c)(p, batch)[0]))(params)

    base = grads(cfg)
    monkeypatch.setattr(joyai, "ATTN_BLOCK", 32)
    monkeypatch.setattr(joyai, "EXPERT_SLICE", 32)
    other = grads({**cfg, "remat": True})
    for a, b in zip(jax.tree.leaves(base), jax.tree.leaves(other)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-7)


def test_a_weight_of_zero_leaves_the_main_loss_alone():
    """``L = L_main + lambda L_mtp``: at zero the loss is the model's
    without a module, the module's leaves get no gradient, and the
    statistics still count its positions."""
    cfg = _config()
    params, batch = _state(cfg)
    bias = reference.expert_bias(cfg)
    pc = family.program_config(cfg)
    import dataclasses
    zero = dataclasses.replace(pc, mtp_weight=0.0)
    none = dataclasses.replace(pc, n_mtp=0)
    with jax.default_matmul_precision("highest"):
        (loss, stats), grads = jax.jit(jax.value_and_grad(
            lambda p: joyai.loss_fn(p, batch, zero, bias), has_aux=True))(
                params)
        main, main_stats = jax.jit(
            lambda p: joyai.loss_fn(p, batch, none, bias[:-1]))(
                {k: v for k, v in params.items() if k != "mtp"})
        full, _ = jax.jit(lambda p: joyai.loss_fn(p, batch, pc, bias))(params)
    assert float(loss) == float(main)
    assert not any(np.any(np.asarray(g)) for g in jax.tree.leaves(grads["mtp"]))
    assert "mtp/nll_sum" not in main_stats
    assert np.asarray(main_stats["moe/expert_load"]).shape == (2, 2)
    # and with the weight the loss is the two parts' weighted sum
    mtp = float(stats["mtp/nll_sum"]) / int(stats["mtp/predicted_tokens"])
    np.testing.assert_allclose(float(full), float(main) + 0.3 * mtp,
                               rtol=1e-6)


def test_the_embeddings_and_the_heads_gradients_are_the_sums_of_two_uses():
    """One leaf each, used by the main model and by the module: with the
    module's copies told apart, the shared gradient is the sum."""
    cfg = _config()
    params, batch = _state(cfg)
    pc = family.program_config(cfg)
    bias = reference.expert_bias(cfg)
    inputs, targets = batch["inputs"], batch["targets"]
    rows, S = inputs.shape

    def apart(embed, head, embed2, head2):
        p = {**params, "embed": embed}
        h, _ = joyai.forward_hidden(p, inputs, pc, bias)
        main = joyai.head_nll(h, p["final_norm"], head, targets, pc)
        x, _ = joyai.mtp_hidden({**params, "embed": embed2}, h, targets, pc,
                                bias[-1], None)
        mtp = joyai.head_nll(x, p["mtp"]["final_norm"], head2,
                             jnp.roll(targets, -1, axis=1), pc, last=1)
        return main / (rows * S) + pc.mtp_weight * mtp / (rows * (S - 1))

    with jax.default_matmul_precision("highest"):
        shared = jax.jit(jax.grad(
            lambda p: joyai.loss_fn(p, batch, pc, bias)[0]))(params)
        parts = jax.jit(jax.grad(apart, (0, 1, 2, 3)))(
            params["embed"], params["head"], params["embed"], params["head"])
    for g in parts:
        assert float(jnp.abs(g).max()) > 0
    np.testing.assert_allclose(np.asarray(shared["embed"]),
                               np.asarray(parts[0] + parts[2]),
                               rtol=1e-5, atol=1e-9)
    np.testing.assert_allclose(np.asarray(shared["head"]),
                               np.asarray(parts[1] + parts[3]),
                               rtol=1e-5, atol=1e-9)


def test_more_than_one_group_or_module_is_refused():
    with pytest.raises(ValueError, match="group"):
        joyai.JoyAIConfig(n_group=8)
    with pytest.raises(ValueError, match="depth 1"):
        joyai.JoyAIConfig(n_mtp=2)
    cfg = _config(n_group=4)
    with pytest.raises(ValueError, match="group"):
        family.program_loss(cfg)
    with pytest.raises(ValueError, match="group"):
        reference.init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="rotates interleaved"):
        family.program_loss(_config(rope_interleave=False))


def test_the_rotation_keeps_every_score_of_the_interleaved_one():
    """The program keeps the rotated pairs as [even ; odd] columns, the
    reference writes them back in place: the same permutation of the
    queries' and the key's columns, so every dot product is the same."""
    cfg = _config()
    pc = family.program_config(cfg)
    S, dr = 16, cfg["qk_rope_head_dim"]
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    q = jax.random.normal(ks[0], (1, S, 4, dr))
    k = jax.random.normal(ks[1], (1, S, 1, dr))
    got_q, got_k = (joyai.rotate_pairs(x, *joyai.rope_cache(pc, S))
                    for x in (q, k))
    want_q, want_k = (reference.rotate_interleaved(
        x[0], *reference.rope_table(cfg, S)) for x in (q, k))
    np.testing.assert_allclose(
        np.asarray(jnp.einsum("qhd,kd->hqk", got_q[0], got_k[0, :, 0])),
        np.asarray(jnp.einsum("qhd,kd->hqk", want_q, want_k[:, 0])),
        rtol=1e-5, atol=1e-6)
    # position 0 turns nothing; a later one does
    np.testing.assert_allclose(np.asarray(want_q[0]), np.asarray(q[0, 0]),
                               rtol=1e-6)
    assert float(jnp.abs(want_q[5] - q[0, 5]).max()) > 1e-3


# ------------------------------------------------------------------ #
# the share
# ------------------------------------------------------------------ #

def test_the_32_shares_with_the_shared_expert_once_add_up_to_the_layer():
    """Expert parallel 32 over 256 experts at top-8, as the deployment:
    the routed parts the 32 chips compute (experts 0-7, 8-15, ...: every
    token routed over all 256) and the shared expert, which every chip
    computes alike, counted ONCE, add up to what the uncut reference
    gives for the whole layer."""
    E, k, d, f, T, shares = 256, 8, 32, 16, 64, 32
    cfg = {"n_routed_experts": E, "num_experts_per_tok": k,
           "routed_scaling_factor": 2.5, "first_expert_held": 0}
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
                   scale=2.5)
    held = E // shares

    @functools.partial(jax.jit, static_argnums=1)
    def share(i, with_shared):
        # one program for every share: its first expert is an operand
        p = {name: w if name == "router" or name.startswith("shared")
             else jax.lax.dynamic_slice_in_dim(w, i * held, held)
             for name, w in whole.items()
             if with_shared or not name.startswith("shared")}
        return moe.moe_layer(u, p, k, jnp.float32, first=i * held, **routing)

    with jax.default_matmul_precision("highest"):
        want = reference.sparse_ffn(u[0], whole, bias, cfg, reference._mm())
        shared = reference.shared_expert(u[0], whole, reference._mm())
        total, pairs = 0.0, 0
        for i in range(shares):
            out, st = share(i, with_shared=i == 0)
            total = total + out[0]
            pairs += int(st["load"].sum())
            assert int(st["dropped"]) == 0
        # a chip's own output holds the shared expert whole
        alone, _ = share(3, with_shared=True)
        routed, _ = share(3, with_shared=False)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(alone[0] - routed[0]),
                               np.asarray(shared), rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(shared).max()) > 1e-3
    # every pair is computed on exactly one chip
    assert pairs == T * k


def test_a_layer_without_shared_leaves_is_the_layer_it_was():
    """``moe_layer`` adds the shared expert only where its leaves are:
    the other families' calls trace to the same program."""
    E, k, d, f, T = 8, 2, 16, 8, 32
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    p = {"router": jax.random.normal(ks[0], (d, E)),
         "w_gate": jax.random.normal(ks[1], (E, d, f)),
         "w_up": jax.random.normal(ks[2], (E, d, f)),
         "w_down": jax.random.normal(ks[3], (E, f, d))}
    u = jax.random.normal(ks[4], (1, T, d))
    text = str(jax.make_jaxpr(
        lambda u, p: moe.moe_layer(u, p, k, jnp.float32)[0])(u, p))
    assert "bps.moe.shared" not in jax.jit(
        lambda u, p: moe.moe_layer(u, p, k, jnp.float32)[0]).lower(
            u, p).as_text(debug_info=True)
    zero = {**p, "shared_gate": jnp.zeros((d, f)),
            "shared_up": jnp.zeros((d, f)), "shared_down": jnp.zeros((f, d))}
    np.testing.assert_array_equal(
        np.asarray(moe.moe_layer(u, p, k, jnp.float32)[0]),
        np.asarray(moe.moe_layer(u, zero, k, jnp.float32)[0]))
    assert "shared" not in text


def test_the_scopes_are_in_the_program():
    cfg = _config()
    params, batch = _state(cfg)
    text = jax.jit(lambda p, b: family.program_loss(cfg)(p, b)[0]).lower(
        params, batch).as_text(debug_info=True)
    for scope in ("bps.moe.shared", "bps.mtp", "bps.moe.route"):
        assert any(scope in ln for ln in text.splitlines()), scope


# ------------------------------------------------------------------ #
# the counts
# ------------------------------------------------------------------ #

def test_the_flop_and_byte_counts_against_hand_counts():
    cfg = _file()
    rows, S = 2, 8192
    pairs = S * (S + 1) // 2
    # one block's latent attention, a step: 7 products over the causal
    # pairs and 32 heads, four of them 192 wide and three 128
    flops, nbytes = reference.attention_step_cost(rows, cfg)
    assert flops == 2.0 * 32 * (4 * 192 + 3 * 128) * pairs * rows
    tokens = rows * S
    # bf16: q and dq 32 x 192, k and dk 32 x 128, the ONE rotary key and
    # its gradient 64, v, o, do, dv 32 x 128; the logsumexp f32 twice
    assert nbytes == 2.0 * tokens * (2 * 6144 + 2 * 4096 + 2 * 64
                                     + 4 * 4096) + 2 * 4.0 * tokens * 32
    assert reference.attention_blocks(cfg) == 6
    # the whole step, multiply-adds a token: five latent projections a
    # block
    attn = 2048 * 1536 + 1536 * 32 * 192 + 2048 * 576 + 512 * 32 * 256 \
        + 4096 * 2048
    assert attn == 26_345_472
    dense = attn + 3 * 2048 * 7168
    sparse = attn + 2048 * 256 + 3 * 2048 * 768 * (1 + 8 * 8 / 256)
    per_token = dense + 5 * sparse + 2 * 2048 * 2048 + 2 * 2048 * 16160
    macs = tokens * per_token + 6 * 32 * (192 + 128) * pairs * rows
    assert reference.model_flops_per_step(rows, cfg) == \
        pytest.approx(6.0 * macs, rel=1e-12)
    # the second head pass and the module are counted
    less = reference.model_flops_per_step(
        rows, {**cfg, "vocab_size": cfg["vocab_size"] // 2})
    assert reference.model_flops_per_step(rows, cfg) - less == \
        pytest.approx(6.0 * tokens * 2 * 2048 * 8080, rel=1e-9)


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
    leaf of the parameters after the third step; the ``moe/*`` and
    ``mtp/*`` counters are in the registry."""
    cfg = _config()
    cfg["optimizer"] = {**cfg["optimizer"], "lr": 0.01}
    key = jax.random.PRNGKey(11)
    params = reference.init_params(key, cfg)
    batches = [reference.make_batch(key, i, 2, cfg) for i in range(3)]
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

        def delta(name):
            return after[name] - before.get(name, 0)

        assert delta("mtp/predicted_tokens") == 3 * 2 * (32 - 1)
        assert delta("mtp/nll_sum") > 0
        assert 0 < delta("moe/bias_moved_pairs") < 3 * 2 * 32 * 3 * 2
        assert delta("moe/dropped_pairs") == 0
        # [sparse layer, held expert]: two layers and the module's block
        names = {k for k in after if k.startswith("moe/expert_load/")}
        assert {f"moe/expert_load/{l}/{e}" for l in range(3)
                for e in range(2)} <= names
    np.testing.assert_allclose(losses, want_losses, rtol=2e-5)
    # what is compared is the parameters' CHANGE (as tests/test_lfm2.py)
    change = jax.tree.map(lambda a, b: np.asarray(a) - b, got, start)
    want_change = jax.tree.map(lambda a, b: np.asarray(a) - b, want, start)
    got_flat = dict(jax.tree_util.tree_leaves_with_path(change))
    for path, w in jax.tree_util.tree_leaves_with_path(want_change):
        name = jax.tree_util.keystr(path)
        g = got_flat[path]
        assert np.abs(w).max() > 0, name
        np.testing.assert_allclose(np.linalg.norm(g), np.linalg.norm(w),
                                   rtol=2e-2, err_msg=name)
        off = np.abs(g - w) > 0.05 * np.abs(w).max()
        assert off.mean() < 0.02, (name, off.mean())


def test_no_host_callback_enters_the_step_program():
    cfg = _config()
    params, batch = _state(cfg)
    import optax
    step = make_train_step(family.program_loss(cfg), optax.sgd(0.1),
                           _one_device_mesh())
    text = step.jitted.lower(params, optax.sgd(0.1).init(params),
                             batch).as_text()
    assert "callback" not in text and "host_transfer" not in text
