"""models/lfm2.py against the benchmark's plain reference
(benchmark/reference/lfm2.py, which imports nothing of the program) at
small sizes with seeded random weights: loss, every gradient leaf and
three optimizer steps, fused and through the PS step with a loopback
server; the four expert-parallel shares of one sparse layer add up to
the uncut layer; the short convolution is causal to the bit, leaks
nothing across batch rows and equals a per-position loop; the tied
leaf's gradient is the sum of its two uses'; the loss written as a chain
(``ops/chain.py``) is the composition it replaced, kept here as its
reference, and the PS step cuts its backward a program a layer, the tied
leaf's two terms summed on the device."""

import contextlib
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from benchmark.families import lfm2 as family
from benchmark.layers._cell import _overlay
from benchmark.optimizers import load as load_optimizer
from benchmark.reference import lfm2 as reference
from byteps_tpu.config import Config
from byteps_tpu.jax.train import make_ps_train_step, make_train_step
from byteps_tpu.models import lfm2, moe
from byteps_tpu.ops.push_pull import psum_tree
from byteps_tpu.server import run_server

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PORT = [25150]


def _config(**over):
    """The benchmark configuration at its rehearsal sizes, in float32:
    published layers 1 to 5 (conv + dense; attention, conv, conv, conv +
    sparse), experts 2 and 3 of 8 held."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "lfm2-8b-a1b.json")) as f:
        cfg = json.load(f)
    cfg = _overlay(cfg, cfg["rehearse"])
    cfg.update(compute_dtype="float32", first_expert_held=2, **over)
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


def test_the_tree_has_a_group_a_run_and_the_programs_own_init_has_its_shape():
    cfg = _config()
    assert reference.layer_runs(cfg) == [
        (("conv", "dense"), 1), (("full_attention", "sparse"), 1),
        (("conv", "sparse"), 3)]
    pc = family.program_config(cfg)
    assert pc.runs() == reference.layer_runs(cfg)
    assert (pc.n_layers, pc.n_sparse_layers) == (5, 4)
    want = jax.eval_shape(lambda: reference.init_params(
        jax.random.PRNGKey(0), cfg))
    got = jax.eval_shape(lambda: lfm2.init_params(jax.random.PRNGKey(0), pc))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert jax.tree.map(lambda a: a.shape, got) == \
        jax.tree.map(lambda a: a.shape, want)
    # the published pattern: 18 convolutions, 6 attention layers
    kinds = lfm2.LFM2Config().layer_types
    assert (kinds.count(lfm2.CONV), kinds.count(lfm2.FULL)) == (18, 6)


@pytest.mark.parametrize("held, compact", [(4, False), (2, True)])
def test_loss_and_every_leafs_gradient_match_the_reference(held, compact):
    """Half the experts held: the full-size sorted buffer is the only
    one; a quarter: every sparse layer's one slice fits the compact
    buffer."""
    cfg = _config(num_experts_held=held)
    params, batch = _state(cfg)
    with jax.default_matmul_precision("highest"):
        (loss, stats), grads = jax.value_and_grad(
            family.program_loss(cfg), has_aux=True)(params, batch)
    want, want_grads = jax.value_and_grad(_reference_loss(cfg))(params, batch)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
    _assert_leaves_close(grads, want_grads)
    # 2 rows x 64 tokens x 4 sparse layers x top-2, a share of them held
    load = np.asarray(stats["moe/expert_load"])
    assert load.shape == (4, held) and 0 < load.sum() < 2 * 64 * 4 * 2
    assert int(stats["moe/dropped_pairs"]) == 0
    # a slice a sparse layer; at this size the bias outweighs the scores'
    # spread, so a layer may overflow the compact buffer: the full-size
    # one is the exact fallback, and both are held to the reference here
    slices = (int(stats["moe/compact_slices"]), int(stats["moe/full_slices"]))
    assert sum(slices) == 4 and (slices[0] > 0) == compact
    assert 0 < int(stats["moe/bias_moved_pairs"]) < 2 * 64 * 4 * 2


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
    want = walk_sizes(cfg["seq_len"], groups, lfm2.ATTN_BLOCK,
                      lfm2.ATTN_BLOCK)
    assert len(want) == 6
    assert all(name.startswith("attention/bps.attn.full/") for name in want)
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
    monkeypatch.setattr(lfm2, "ATTN_BLOCK", 32)
    monkeypatch.setattr(lfm2, "EXPERT_SLICE", 32)
    other = grads({**cfg, "remat": True})
    for a, b in zip(jax.tree.leaves(base), jax.tree.leaves(other)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-7)


def test_the_bias_selects_and_a_zero_bias_is_plain_top_k():
    """A model given the file's bias differs from one given none, and
    with none no pair is moved."""
    cfg = _config()
    params, batch = _state(cfg)
    pc = family.program_config(cfg)
    loss, stats = family.program_loss(cfg)(params, batch)
    plain, plain_stats = lfm2.loss_fn(params, batch, pc)
    assert abs(float(loss) - float(plain)) > 1e-7
    assert int(stats["moe/bias_moved_pairs"]) > 0
    assert int(plain_stats["moe/bias_moved_pairs"]) == 0
    bias = reference.expert_bias(cfg)
    assert bias.shape == (4, 8) and float(jnp.abs(bias).max()) <= 0.1
    np.testing.assert_array_equal(np.asarray(bias),
                                  np.asarray(reference.expert_bias(cfg)))
    assert not np.any(np.asarray(
        reference.expert_bias({**cfg, "use_expert_bias": False})))


def test_the_tied_leafs_gradient_is_the_sum_of_its_two_uses():
    """One leaf, two uses: with the head's copy and the lookup's copy
    told apart, the tied gradient is their gradients' sum."""
    cfg = _config()
    params, batch = _state(cfg)
    pc = family.program_config(cfg)
    bias = reference.expert_bias(cfg)

    def untied(embed, head):
        inputs, targets = batch["inputs"], batch["targets"]
        x, _ = lfm2.forward_hidden({**params, "embed": embed}, inputs, pc,
                                   bias)
        logits = jnp.einsum("bsd,vd->bsv", x, head)
        return lfm2.L.next_token_xent(logits, targets)

    with jax.default_matmul_precision("highest"):
        tied = jax.grad(lambda p: lfm2.loss_fn(p, batch, pc, bias)[0])(
            params)["embed"]
        g_lookup, g_head = jax.grad(untied, (0, 1))(params["embed"],
                                                    params["embed"])
    assert float(jnp.abs(g_lookup).max()) > 0 < float(jnp.abs(g_head).max())
    np.testing.assert_allclose(np.asarray(tied),
                               np.asarray(g_lookup + g_head),
                               rtol=1e-5, atol=1e-9)


# ------------------------------------------------------------------ #
# the loss as a chain against the composition it replaced
# ------------------------------------------------------------------ #

def _parents_hidden(params, tokens, cfg, expert_bias=None):
    """``lfm2.forward_hidden`` as it stood before the loss was a chain:
    the lookup and the runs' scans."""
    if expert_bias is None:
        expert_bias = jnp.zeros((cfg.n_sparse_layers, cfg.n_experts),
                                jnp.float32)
    rope = lfm2.L.rope_cache(cfg, tokens.shape[1])
    x = params["embed"].astype(cfg.dtype)[tokens]
    block = jax.checkpoint(lfm2._block, static_argnums=(4, 5, 6)) \
        if cfg.remat else lfm2._block
    stats, sparse_seen = [], 0
    for (kind, n), p in zip(cfg.runs(), params["runs"]):
        bias = None
        if kind[1] == lfm2.SPARSE:
            bias = jax.lax.stop_gradient(
                expert_bias[sparse_seen:sparse_seen + n])
            sparse_seen += n

        def body(x, layer, kind=kind):
            return block(x, layer["p"], layer.get("bias"), rope, cfg, kind,
                         None)

        layers = {"p": p} if bias is None else {"p": p, "bias": bias}
        x, st = jax.lax.scan(body, x, layers)
        if st:
            stats.append(st)
    x = lfm2.L._rmsnorm(x, params["final_norm"], cfg.norm_eps)
    stats = jax.tree.map(lambda *a: jnp.concatenate(a), *stats) \
        if stats else {}
    return x, {name: v if v.ndim == 2 else jnp.sum(v)
               for name, v in stats.items()}


def _parents_loss(params, batch, cfg, expert_bias=None):
    """``lfm2.loss_fn`` as it stood: that walk, then the tied head."""
    inputs, targets = lfm2.L.split_batch(batch)
    x, stats = _parents_hidden(params, inputs, cfg, expert_bias)
    logits = jnp.einsum("bsd,vd->bsv", x, params["embed"].astype(cfg.dtype))
    return lfm2.L.next_token_xent(logits, targets), stats


def _assert_trees_equal(got, want):
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("remat", [True, False], ids=["remat", "no-remat"])
@pytest.mark.parametrize("biased", [True, False], ids=["bias", "no-bias"])
def test_the_loss_as_a_chain_is_the_composition_it_replaced(biased, remat):
    """Loss, every statistic (the ``[sparse layers, n_held]`` load by
    row) and every gradient, to the bit; ``forward_hidden`` too."""
    cfg = _config(remat=remat)
    params, batch = _state(cfg)
    pc = family.program_config(cfg)
    bias = reference.expert_bias(cfg) if biased else None
    (loss, stats), grads = jax.jit(jax.value_and_grad(
        lambda p, b: lfm2.loss_fn(p, b, pc, bias), has_aux=True))(
            params, batch)
    (want, want_stats), want_grads = jax.jit(jax.value_and_grad(
        lambda p, b: _parents_loss(p, b, pc, bias), has_aux=True))(
            params, batch)
    assert float(loss) == float(want) and float(loss) > 0
    _assert_trees_equal(stats, want_stats)
    load = np.asarray(stats["moe/expert_load"])
    assert load.shape == (4, 2) and all(load.sum(axis=1) > 0)
    _assert_trees_equal(grads, want_grads)
    assert all(np.any(np.asarray(g)) for g in jax.tree.leaves(grads))
    hidden = jax.jit(lambda p, t: lfm2.forward_hidden(p, t, pc, bias))(
        params, batch["inputs"])
    _assert_trees_equal(hidden, jax.jit(
        lambda p, t: _parents_hidden(p, t, pc, bias))(
            params, batch["inputs"]))
    assert hidden[0].shape == (2, cfg["seq_len"], cfg["hidden_size"])


def test_the_chain_names_the_runs_inside_the_list_and_the_tied_leaf_twice():
    from byteps_tpu.jax.train import _chain_leaves
    from byteps_tpu.ops import chain

    cfg = _config(remat=True)
    params, batch = _state(cfg)
    with chain.collecting() as found:
        jax.eval_shape(family.program_loss(cfg), params, batch)
    (ch,) = found
    assert [ln.keys for ln in ch.links] == [
        ("embed",), (("runs", 0),), (("runs", 1),), (("runs", 2),),
        ("final_norm", "embed")]
    assert [getattr(ln, "depth", None) for ln in ch.links] == [
        None, 1, 1, 3, None]
    assert ch.cuts(params)
    leaves = _chain_leaves(ch, params)
    # embed (flatten index 0) under the lookup and under the head
    assert leaves[0] == (0,) and leaves[4] == (0, 1)
    assert sorted(i for found in leaves.values() for i in found) \
        == [0] + list(range(31))
    # remat off (the rehearsal's own setting): one program, as ever
    off = _config()
    with chain.collecting() as found:
        jax.eval_shape(family.program_loss(off), params, batch)
    assert not found[0].cuts(params)


def test_the_ps_step_cuts_the_backward_and_is_the_one_program_step(
        monkeypatch):
    """The file's own test-scale configuration (runs of 1, 1 and 3
    layers, as the cell's), remat on: 2 + layers + 1 programs a step,
    the tied leaf's terms summed on the device and pushed once, the run
    of three as pieces; losses, parameters and optimizer state the
    one-program step's (the composition above, which registers no
    chain)."""
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
             "export/whole_bytes", "wire/push_bytes")

    def run(loss):
        from byteps_tpu.core.state import get_state

        tx = optax.adam(1e-2)
        with _ps_env() as bps:
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
        return p, opt, losses, {n: after.get(n, 0) - before.get(n, 0)
                                for n in names}, keys

    cut = run(lambda p, b: lfm2.loss_fn(p, b, pc, bias))
    whole = run(lambda p, b: _parents_loss(p, b, pc, bias))
    # forward, head, five layers, embedding
    assert cut[3]["export/backward_programs"] == 3 * (2 + 5 + 1)
    assert whole[3]["export/backward_programs"] == 3
    assert cut[3]["export/shared_leaves"] == 3
    assert cut[3]["export/shared_carry_bytes"] == 3 * params["embed"].nbytes
    assert whole[3]["export/shared_leaves"] == 0
    for side in (cut, whole):
        assert side[3]["wire/push_bytes"] == 3 * n_bytes
        assert side[3]["export/whole_bytes"] == 3 * n_bytes
    pieces = sum(a.nbytes for a in jax.tree.leaves(params["runs"][2])
                 if a.nbytes >= 1024)
    assert cut[3]["export/piece_bytes"] == 3 * pieces > 0
    assert all(n.startswith("grad/runs/2/") and n.endswith("of3")
               for n in cut[4] if "@shard" in n)
    for name in ("grad/embed", "grad/runs/0/ffn/w1", "grad/runs/1/op/wq"):
        assert name in cut[4] and name in whole[4], name
    # to the bit: XLA:CPU compiles these links alone as it does inside
    # the one program
    assert cut[2] == whole[2] and cut[2][-1] < cut[2][0]
    _assert_trees_equal(cut[:2], whole[:2])


# ------------------------------------------------------------------ #
# the share
# ------------------------------------------------------------------ #

def test_the_four_shares_of_a_sparse_layer_add_up_to_the_whole_layer():
    """Expert parallel 4 over 32 experts at top-4, as the deployment:
    the outputs the four chips compute (experts 0-7, 8-15, 16-23,
    24-31, every token routed over all 32) add up to what the uncut
    reference gives for the whole layer."""
    E, k, d, f, T = 32, 4, 32, 24, 96
    cfg = {"num_experts": E, "num_experts_per_tok": k,
           "routed_scaling_factor": 1, "first_expert_held": 0}
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    whole = {"router": jax.random.normal(ks[0], (d, E)) * 0.3,
             "w_gate": jax.random.normal(ks[1], (E, d, f)) * 0.2,
             "w_up": jax.random.normal(ks[2], (E, d, f)) * 0.2,
             "w_down": jax.random.normal(ks[3], (E, f, d)) * 0.2}
    u = jax.random.normal(ks[4], (1, T, d))
    bias = jax.random.uniform(ks[5], (E,), minval=-0.1, maxval=0.1)
    with jax.default_matmul_precision("highest"):
        want = reference.sparse_ffn(u[0], whole, bias, cfg, reference._mm())
        total, pairs, moved = 0.0, 0, []
        for share in range(4):
            held = {name: w if name == "router"
                    else w[share * 8:(share + 1) * 8]
                    for name, w in whole.items()}
            out, st = moe.moe_layer(
                u, held, k, jnp.float32, first=share * 8, score="sigmoid",
                select_bias=bias, norm_eps=1e-6)
            total = total + out[0]
            pairs += int(st["load"].sum())
            moved.append(int(st["bias_moved"]))
            assert int(st["dropped"]) == 0
            # what one share gives alone is not the layer
            assert float(jnp.abs(out[0] - want).max()) > 1e-3
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    # every pair is computed on exactly one chip; every chip routes alike
    assert pairs == T * k and len(set(moved)) == 1 and moved[0] > 0


# ------------------------------------------------------------------ #
# the gated short convolution
# ------------------------------------------------------------------ #

def _conv_inputs(B=3, S=24, d=16, taps=3, seed=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (*(jax.random.normal(k, (B, S, d)) for k in ks[:3]),
            jax.random.normal(ks[3], (taps, d)))


@pytest.mark.parametrize("taps", [3, 4])
def test_short_conv_equals_a_per_position_loop(taps):
    b, c, x, kernel = _conv_inputs(taps=taps)
    got = np.asarray(lfm2.gated_short_conv(b, c, x, kernel))
    z = np.asarray(b, np.float64) * np.asarray(x, np.float64)
    k = np.asarray(kernel, np.float64)
    want = np.zeros_like(z)
    B, S, d = z.shape
    for row in range(B):
        for t in range(S):
            for j in range(taps):
                src = t - (taps - 1) + j
                if src >= 0:
                    want[row, t] += k[j] * z[row, src]
    want *= np.asarray(c, np.float64)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # and the reference's three shifted products, a row at a time
    for row in range(B):
        ref = c[row] * reference.short_conv(b[row] * x[row], kernel)
        np.testing.assert_allclose(got[row], np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)


def test_short_conv_is_causal_to_the_bit_and_rows_do_not_leak():
    b, c, x, kernel = _conv_inputs()
    conv = jax.jit(lfm2.gated_short_conv)
    base = np.asarray(conv(b, c, x, kernel))
    t = 11
    # another token at position t of row 1, in all three inputs
    moved = [a.at[1, t].set(a[1, t] * -3.0 + 1.0) for a in (b, c, x)]
    other = np.asarray(conv(*moved, kernel))
    # outputs before t, and every other row, are the same bits
    np.testing.assert_array_equal(other[1, :t], base[1, :t])
    np.testing.assert_array_equal(other[0], base[0])
    np.testing.assert_array_equal(other[2], base[2])
    # the filter reaches two positions on and no further
    assert np.all(other[1, t:t + 3] != base[1, t:t + 3])
    np.testing.assert_array_equal(other[1, t + 3:], base[1, t + 3:])
    # a row's first outputs see zeros before it, not the row above
    alone = np.asarray(conv(b[1:2], c[1:2], x[1:2], kernel))
    np.testing.assert_array_equal(alone[0], base[1])


def test_short_conv_sits_under_its_scope_and_the_projections_do_not():
    cfg = _config()
    params, batch = _state(cfg)
    text = jax.jit(lambda p, b: family.program_loss(cfg)(p, b)[0]).lower(
        params, batch).as_text(debug_info=True)
    scoped = [ln for ln in text.splitlines() if "bps.conv.short" in ln]
    assert scoped and not any("dot_general" in ln for ln in scoped)
    assert any("bps.moe.route" in ln for ln in text.splitlines())


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
    are in the registry, the bias's among them."""
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
        moved = after["moe/bias_moved_pairs"] \
            - before.get("moe/bias_moved_pairs", 0)
        assert 0 < moved < 3 * 2 * 64 * 4 * 2
        assert after["moe/dropped_pairs"] \
            - before.get("moe/dropped_pairs", 0) == 0
        # [sparse layer, held expert]: four layers of two
        names = {k for k in after if k.startswith("moe/expert_load/")}
        assert {f"moe/expert_load/{l}/{e}" for l in range(4)
                for e in range(2)} <= names
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


def test_no_host_callback_enters_the_step_program_and_the_bias_is_no_leaf():
    cfg = _config()
    params, batch = _state(cfg)
    loss_fn = family.program_loss(cfg)
    import optax
    step = make_train_step(loss_fn, optax.sgd(0.1), _one_device_mesh())
    text = step.jitted.lower(params, optax.sgd(0.1).init(params),
                             batch).as_text()
    assert "callback" not in text and "host_transfer" not in text
    # a buffer: no leaf of the parameters, so no gradient, no optimizer
    # state, no push
    names = {jax.tree_util.keystr(p)
             for p, _ in jax.tree_util.tree_leaves_with_path(params)}
    assert len(names) == 31 and not any("bias" in n for n in names)
    n = sum(x.size for x in jax.tree.leaves(params))
    assert n == reference.param_count(cfg)
