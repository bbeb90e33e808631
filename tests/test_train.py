"""End-to-end training tests on the 8-device CPU mesh: the framework's
equivalent of the reference's example-as-system-test pattern
(tests/test_tensorflow_keras.py, example/pytorch/train_mnist_byteps.py).

Checks: loss decreases through distributed_optimizer; plain-psum and ZeRO
steps agree; tiny llama trains.
"""

import gc

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

from byteps_tpu.core.state import get_state
from byteps_tpu.jax import distributed_optimizer
from byteps_tpu.jax.train import (
    make_ps_train_step, make_train_step, make_zero_train_step,
    init_zero_state,
)
from byteps_tpu.models import mlp, llama

from test_chain import ENV, _plainly, _toy
from test_export_spans import _ps_env


def synthetic_classification(n=256, dim=784, classes=10, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, dim).astype(np.float32)
    w = rng.randn(dim, classes).astype(np.float32)
    y = np.argmax(x @ w, axis=-1).astype(np.int32)
    return {"x": x, "y": y}


def test_mlp_trains(bps):
    mesh = get_state().mesh
    cfg = mlp.MLPConfig(in_dim=784, hidden=(64,), n_classes=10)
    params = mlp.init_params(jax.random.PRNGKey(0), cfg)
    tx = distributed_optimizer(optax.sgd(0.1))
    step = make_train_step(lambda p, b: mlp.loss_fn(p, b, cfg), tx, mesh)
    opt_state = tx.init(params)
    batch = synthetic_classification()

    losses = []
    for _ in range(20):
        params, opt_state, loss = step(params, opt_state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.7, losses
    acc = float(mlp.accuracy(params, batch, cfg))
    assert acc > 0.5, acc


def test_zero_step_matches_plain(bps):
    """ZeRO (RS + sharded update + AG) must match plain psum allreduce."""
    mesh = get_state().mesh
    cfg = mlp.MLPConfig(in_dim=32, hidden=(16,), n_classes=4)
    params0 = mlp.init_params(jax.random.PRNGKey(1), cfg)
    batch = synthetic_classification(n=64, dim=32, classes=4, seed=1)
    loss = lambda p, b: mlp.loss_fn(p, b, cfg)

    tx_plain = distributed_optimizer(optax.sgd(0.05))
    step_plain = make_train_step(loss, tx_plain, mesh, donate=False)
    p_plain, s_plain = params0, tx_plain.init(params0)

    tx_zero = optax.sgd(0.05)  # grads already averaged by reduce_scatter
    step_zero = make_zero_train_step(loss, tx_zero, mesh, params0, donate=False)
    p_zero = params0
    s_zero = init_zero_state(params0, tx_zero, mesh)

    for _ in range(3):
        p_plain, s_plain, l_plain = step_plain(p_plain, s_plain, batch)
        p_zero, s_zero, l_zero = step_zero(p_zero, s_zero, batch)

    for a, b in zip(jax.tree.leaves(p_plain), jax.tree.leaves(p_zero)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)
    assert abs(float(l_plain) - float(l_zero)) < 1e-5


def test_tiny_llama_trains(bps):
    mesh = get_state().mesh
    cfg = llama.LlamaConfig.tiny(vocab_size=64, seq=32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    tx = distributed_optimizer(optax.adam(1e-2))
    step = make_train_step(lambda p, b: llama.loss_fn(p, b, cfg), tx, mesh)
    opt_state = tx.init(params)

    rng = np.random.RandomState(0)
    # learnable structure: token t+1 = (t + 1) % 17
    start = rng.randint(0, 17, size=(16, 1))
    seq = (start + np.arange(33)[None, :]) % 17
    batch = {"tokens": seq.astype(np.int32)}

    losses = []
    for _ in range(30):
        params, opt_state, loss = step(params, opt_state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])


def test_fused_adam_matches_optax(bps):
    """byteps_tpu.jax.optim.fused_adam_step must track optax.adam: same
    loss trajectory and params within float tolerance after 5 steps."""
    from byteps_tpu.jax.optim import fused_adam_step

    cfg = llama.LlamaConfig.tiny(vocab_size=64, seq=16)
    p0 = llama.init_params(jax.random.PRNGKey(0), cfg)
    tok = jnp.asarray(
        np.random.RandomState(0).randint(0, 64, (2, 17)), jnp.int32)
    loss_fn = lambda q, t: llama.loss_fn(q, {"tokens": t}, cfg)  # noqa: E731

    init, step = fused_adam_step(loss_fn, mu_dtype=jnp.float32)
    tx = optax.adam(1e-3)

    def ref_step(p, o, t):
        loss, g = jax.value_and_grad(lambda q: loss_fn(q, t))(p)
        u, o = tx.update(g, o, p)
        return optax.apply_updates(p, u), o, loss

    pa, oa = jax.tree.map(jnp.copy, p0), init(p0)
    pb, ob = jax.tree.map(jnp.copy, p0), tx.init(p0)
    stepj, refj = jax.jit(step), jax.jit(ref_step)
    for _ in range(5):
        pa, oa, la = stepj(pa, oa, tok)
        pb, ob, lb = refj(pb, ob, tok)
    assert abs(float(la) - float(lb)) < 1e-3
    for x, y in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   atol=5e-5)
    # the production mu_dtype (bf16) still trains: loss decreases
    init16, step16 = fused_adam_step(loss_fn)
    p, o = jax.tree.map(jnp.copy, p0), init16(p0)
    s16 = jax.jit(step16)
    losses = []
    for _ in range(8):
        p, o, loss = s16(p, o, tok)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_llama_forward_shapes(bps):
    cfg = llama.LlamaConfig.tiny(vocab_size=64, seq=16)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits = llama.forward(params, tokens, cfg)
    assert logits.shape == (2, 16, 64)
    # logits stay in the compute dtype; loss does the fp32 math
    assert logits.dtype == cfg.dtype
    n = llama.param_count(params)
    assert n > 0


def test_llama_causality(bps):
    """Changing a future token must not affect past logits."""
    cfg = llama.LlamaConfig.tiny(vocab_size=32, seq=8)
    params = llama.init_params(jax.random.PRNGKey(2), cfg)
    t1 = jnp.array([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
    t2 = t1.at[0, 6].set(20)
    l1 = llama.forward(params, t1, cfg)
    l2 = llama.forward(params, t2, cfg)
    np.testing.assert_allclose(np.asarray(l1[0, :6]), np.asarray(l2[0, :6]),
                               atol=1e-5)
    assert not np.allclose(np.asarray(l1[0, 6:]), np.asarray(l2[0, 6:]))


@pytest.mark.parametrize("cut", [False, True], ids=["one-program", "cut"])
def test_a_steps_device_arrays_die_with_the_step(cut):
    """With the collector off, no more device arrays are alive at the
    end of step 6 than at the end of step 3: a step's gradients, pieces
    and carried terms are locals of ``step`` and die with the call. A
    step whose per-step state sits in objects that refer to themselves
    keeps them until the collector runs, which on a chip is a step's
    gradients held beside the next step's (PR 46's first version:
    ``RESOURCE_EXHAUSTED`` on two cells, ROADMAP.md queue 3 item 4)."""
    ch, params, batch = _toy()
    loss = ch if cut else _plainly(ch)
    tx = optax.adam(1e-2)
    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    params = jax.tree.map(jnp.array, params)  # the step donates its own
    # a port of this file's own: test_chain.py's servers run beside it
    with _ps_env(ENV, port=26000 + cut) as bps:
        step = make_ps_train_step(loss, tx, mesh)
        opt, alive = tx.init(params), []
        before = bps.get_metrics()["counters"]
        gc.collect()
        gc.disable()
        try:
            for _ in range(6):
                params, opt, value = step(params, opt, batch)
                jax.block_until_ready((params, opt, value))
                alive.append(len(jax.live_arrays()))
        finally:
            gc.enable()
        after = bps.get_metrics()["counters"]
    programs = after["export/backward_programs"] \
        - before.get("export/backward_programs", 0)
    assert programs == 6 * (7 if cut else 1)
    assert alive[5] <= alive[2], alive
