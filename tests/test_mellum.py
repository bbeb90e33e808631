"""models/mellum.py against the benchmark's plain reference
(benchmark/reference/mellum.py, which imports nothing of the program) at
small sizes with seeded random weights: loss and per-leaf gradients,
fused and through the PS step with a loopback server; the rotary tables
against the written-out formula; ``(loss, stats)`` through both step
makers, a scalar loss still accepted. (The loss as a chain and its cut
backward: ``tests/test_chain.py``.)"""

import contextlib
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

from benchmark.families import mellum as family
from benchmark.layers._cell import _overlay
from benchmark.reference import mellum as reference
from byteps_tpu.config import Config
from byteps_tpu.jax.train import make_ps_train_step, make_train_step
from byteps_tpu.models import mellum, mlp
from byteps_tpu.ops.push_pull import psum_tree
from byteps_tpu.server import run_server

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PORT = [24950]


def _config(**over):
    """The benchmark configuration at its rehearsal sizes, in float32:
    the window (16) shorter than the sequence (64), and experts 2 to 5
    of 8 held."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "mellum2-12b.json")) as f:
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


@pytest.mark.parametrize("held, compact", [(4, False), (2, True)])
def test_loss_and_every_leafs_gradient_match_the_reference(held, compact):
    """Half the experts held: the full-size sorted buffer is the only
    one; a quarter: every layer's one slice fits the compact buffer."""
    cfg = _config(num_experts_held=held)
    params, batch = _state(cfg)
    with jax.default_matmul_precision("highest"):
        (loss, stats), grads = jax.value_and_grad(
            family.program_loss(cfg), has_aux=True)(params, batch)
    want, want_grads = jax.value_and_grad(_reference_loss(cfg))(params, batch)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
    got = dict(jax.tree_util.tree_leaves_with_path(grads))
    for path, w in jax.tree_util.tree_leaves_with_path(want_grads):
        np.testing.assert_allclose(
            np.asarray(got[path]), np.asarray(w), rtol=2e-3,
            atol=1e-6 + 1e-4 * float(jnp.abs(w).max()), err_msg=str(path))
    # 2 rows x 64 tokens x 4 layers x top-2, about held / 8 of them held
    load = np.asarray(stats["moe/expert_load"])
    assert load.shape == (4, held) and 0 < load.sum() < 2 * 64 * 4 * 2
    assert int(stats["moe/dropped_pairs"]) == 0
    # one slice a layer, four layers
    assert (int(stats["moe/compact_slices"]), int(stats["moe/full_slices"])) \
        == ((4, 0) if compact else (0, 4))


def test_window_layers_differ_from_full_ones():
    """The window is shorter than the sequence, so a model whose sliding
    layers were given the causal mask would not pass the test above."""
    cfg = _config()
    params, batch = _state(cfg)
    wide = family.program_loss({**cfg, "sliding_window": 64})
    loss, _ = family.program_loss(cfg)(params, batch)
    assert abs(float(wide(params, batch)[0]) - float(loss)) > 1e-6


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
    want = {**walk_sizes(cfg["seq_len"], groups, mellum.ATTN_BLOCK,
                         mellum.ATTN_BLOCK),
            **walk_sizes(cfg["seq_len"], groups, mellum.ATTN_BLOCK,
                         mellum.ATTN_BLOCK, cfg["sliding_window"])}
    assert len(want) == 12
    assert sum(name.startswith("attention/bps.attn.window/")
               for name in want) == 6
    for name in want:
        registry.gauge(name).set(-1)
    jax.eval_shape(family.program_loss(cfg), params, batch)
    gauges = registry.instruments()[1]
    assert {name: gauges[name].value for name in want} == want

def test_remat_and_chunks_change_nothing(monkeypatch):
    cfg = _config()
    params, batch = _state(cfg)
    def grads(c):
        return jax.grad(lambda p: family.program_loss(c)(p, batch)[0])(params)

    base = grads(cfg)
    # the program's tiles shrunk under the sizes: 2 blocks, 4 slices
    monkeypatch.setattr(mellum, "ATTN_BLOCK", 32)
    monkeypatch.setattr(mellum, "EXPERT_SLICE", 32)
    other = grads({**cfg, "remat": True})
    for a, b in zip(jax.tree.leaves(base), jax.tree.leaves(other)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-7)


def test_rope_tables_match_the_written_out_formula():
    """YaRN as ISSUE 26 writes it: ``inv_freq_d = (1 - r_d) theta^(-2d/hd)
    / factor + r_d theta^(-2d/hd)``, ``r_d`` one minus the ramp between
    the correction dimensions; cos and sin times the attention factor.
    At the published sizes."""
    cfg = mellum.MellumConfig()
    hd, theta, factor, L0 = 128, 500000.0, 16.0, 8192
    d = np.arange(64)
    plain = theta ** (-2.0 * d / hd)

    def corr(beta):
        return hd * np.log(L0 / (2 * np.pi * beta)) / (2 * np.log(theta))

    low, high = np.floor(corr(32.0)), np.ceil(corr(1.0))
    r = 1.0 - np.clip((d - low) / (high - low), 0, 1)
    inv_freq = (1 - r) * plain / factor + r * plain
    np.testing.assert_allclose(mellum.yarn_inv_freq(cfg), inv_freq,
                               rtol=1e-12)
    # the fast dimensions keep their frequency, the slow ones are
    # stretched by the factor, and the ramp lies strictly between
    assert r[0] == 1 and r[-1] == 0 and 0 < r[int(low) + 1] < 1
    tables = mellum.rope_tables(cfg, 512)
    t = np.arange(512)[:, None]
    for kind, freqs, scale in (
            (mellum.SLIDING, plain, 1.0),
            (mellum.FULL, inv_freq, 1.2772588722239782)):
        cos, sin = tables[kind]
        np.testing.assert_allclose(np.asarray(cos), np.cos(t * freqs) * scale,
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(sin), np.sin(t * freqs) * scale,
                                   rtol=1e-6, atol=1e-6)
    # and the reference's own, written from the configuration's file
    with open(os.path.join(REPO, "benchmark", "configs",
                           "mellum2-12b.json")) as f:
        ref_tables = reference.rope_tables(json.load(f), 512)
    for kind in tables:
        for a, b in zip(tables[kind], ref_tables[kind]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)


def test_layer_scan_body_is_one_period():
    assert mellum._period((mellum.SLIDING,) * 3 + (mellum.FULL,)) == 4
    assert mellum._period(((mellum.SLIDING,) * 3 + (mellum.FULL,)) * 7) == 4
    assert mellum._period((mellum.FULL,) * 6) == 1
    assert mellum._period((mellum.SLIDING, mellum.FULL, mellum.FULL)) == 3


# ------------------------------------------------------------------ #
# (loss, stats) through the step makers
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


def _counters(bps):
    return {k: v for k, v in bps.get_metrics()["counters"].items()
            if k.startswith("moe/")}


def _one_device_mesh():
    return Mesh(np.array(jax.devices()[:1]), ("dp",))


@pytest.mark.parametrize("held", [4, 2])
def test_ps_step_matches_the_reference_and_folds_the_statistics(
        held, monkeypatch):
    """Loss and the first applied gradient through ``bps.init()`` ->
    ``make_ps_train_step`` -> a loopback server, against the reference;
    the ``moe/*`` counters are in the registry when the step returns:
    the load, and the slices by the sorted buffer they went through
    (two slices a layer; the full-size one where half the experts are
    held, the compact one where a quarter are)."""
    monkeypatch.setattr(mellum, "EXPERT_SLICE", 64)
    cfg = _config(num_experts_held=held)
    params, batch = _state(cfg)
    want, want_grads = jax.value_and_grad(_reference_loss(cfg))(params, batch)
    loss_fn = family.program_loss(cfg)
    # the step donates its parameters: what is compared is kept first
    start = jax.tree.map(np.asarray, params)
    _, stats = loss_fn(params, batch)
    with _ps_env() as bps:
        lr = 0.5
        step = make_ps_train_step(loss_fn, optax.sgd(lr), _one_device_mesh())
        before = _counters(bps)
        programs = bps.get_metrics()["counters"].get(
            "export/backward_programs", 0)
        with jax.default_matmul_precision("highest"):
            new, _, loss = step(params, optax.sgd(lr).init(params), batch)
        after = _counters(bps)
        # the loss is a chain, but its run keeps its residuals (remat
        # off at these sizes): the backward stays one program
        assert bps.get_metrics()["counters"]["export/backward_programs"] \
            - programs == 1
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
    for (path, p0), p1, g in zip(
            jax.tree_util.tree_leaves_with_path(start),
            jax.tree.leaves(new), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(
            (np.asarray(p0) - np.asarray(p1)) / lr, np.asarray(g), rtol=2e-3,
            atol=1e-6 + 1e-4 * float(jnp.abs(g).max()), err_msg=str(path))
    # element [layer, expert] of the load lands in a counter of its own
    for (layer, expert), pairs in np.ndenumerate(
            np.asarray(stats["moe/expert_load"])):
        name = f"moe/expert_load/{layer}/{expert}"
        assert after[name] - before.get(name, 0) == pairs, name
    assert after["moe/dropped_pairs"] - before.get("moe/dropped_pairs", 0) == 0
    slices = {name: after[f"moe/{name}_slices"]
              - before.get(f"moe/{name}_slices", 0)
              for name in ("compact", "full")}
    assert slices == ({"compact": 0, "full": 8} if held == 4
                      else {"compact": 8, "full": 0})


def test_no_host_callback_enters_the_step_programs():
    cfg = _config()
    params, batch = _state(cfg)
    loss_fn = family.program_loss(cfg)
    step = make_train_step(loss_fn, optax.sgd(0.1), _one_device_mesh())
    text = step.jitted.lower(params, optax.sgd(0.1).init(params),
                             batch).as_text()
    assert "callback" not in text and "host_transfer" not in text


def test_fused_step_folds_the_statistics_one_step_late():
    """``make_train_step``: the statistics of step k reach the registry
    after step k + 1 is dispatched, so the host never waits on the step
    it has just queued; ``fold_stats`` folds the last."""
    from byteps_tpu.core.state import get_state

    cfg = _config(num_experts_held=2)
    params, batch = _state(cfg)
    loss_fn = family.program_loss(cfg)
    tx = optax.sgd(0.0)
    mesh = _one_device_mesh()
    step = make_train_step(
        loss_fn, tx, mesh,
        grads_transform=lambda g: psum_tree(g, axis="dp", average=True),
        donate=False)
    (want, stats), _ = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
    pairs = int(np.asarray(stats["moe/expert_load"])[0, 0])
    assert pairs > 0
    routed = get_state().metrics.counter("moe/expert_load/0/0")
    start = routed.value
    by_buffer = [get_state().metrics.counter(f"moe/{name}_slices")
                 for name in ("compact", "full")]
    slices = sum(c.value for c in by_buffer)
    opt = tx.init(params)
    _, _, loss = step(params, opt, batch)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-6)
    assert routed.value == start                  # not yet
    step(params, opt, batch)
    assert routed.value - start == pairs
    step.fold_stats()
    assert routed.value - start == 2 * pairs
    # a slice a layer, four layers, two steps: each on one buffer
    assert sum(c.value for c in by_buffer) - slices == 2 * 4


@pytest.mark.parametrize("maker", ["fused", "ps"])
def test_a_scalar_loss_is_still_accepted(maker):
    cfg = mlp.MLPConfig(in_dim=16, hidden=(8,), n_classes=4)
    params = mlp.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)
    batch = {"x": jnp.asarray(rng.rand(8, 16), jnp.float32),
             "y": jnp.asarray(rng.randint(0, 4, 8), jnp.int32)}

    def loss_fn(p, b):
        return mlp.loss_fn(p, b, cfg)

    tx = optax.sgd(0.1)
    want = float(loss_fn(params, batch))
    if maker == "fused":
        step = make_train_step(loss_fn, tx, _one_device_mesh(), donate=False)
        out = step(params, tx.init(params), batch)
        step.fold_stats()
    else:
        with _ps_env():
            step = make_ps_train_step(loss_fn, tx, _one_device_mesh())
            out = step(params, tx.init(params), batch)
    assert len(out) == 3
    np.testing.assert_allclose(float(out[2]), want, rtol=1e-6)

