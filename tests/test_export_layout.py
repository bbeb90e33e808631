"""A claimed whole leaf reaches ``scheduler.submit`` as a flat row-major
view of the buffer the runtime filled (jax/train.py
``_row_major_outputs`` and the claim loop's guard): ``np.asarray`` of a
program output has the strides of the device's dimension order, and a
runtime may prefer another than major-to-minor. The CPU's never does of
itself, so either a stand-in ``_psum_backward`` hands the step a
backward whose DEFAULT puts one leaf out in another order (the plan has
to see that in the compiled program and pin the output in a second
build), or the parameter itself lives in another order (the plan pins
its gradient in the first build and never compiles the default
program). Where the pin is defeated the train thread's copy has to be
counted and named. Whatever the order and the route, what the server
hands back is bit for bit what the runtime's array holds, read in
row-major order."""

import numpy as np
import optax
import pytest

from byteps_tpu.utils import tracing

from test_export_spans import _ps_env

# leaf -> the dimension order the stand-in compiler prefers for its
# gradient; "parameter-lives-transposed" leaves the compiler alone and
# places the parameter in that order
ORDERS = {"default": {}, "transposed-2d": {"w2": (1, 0)},
          "permuted-4d": {"w4": (2, 0, 3, 1)},
          "parameter-lives-transposed": {}}
LIVES = {"w2": (1, 0)}
# own-key: every leaf on a key of its own, the plan's pin in force;
# defeated: the same with the pin taken away (a backend that ignores
# it); bucket: every leaf a member of one fusion bucket
ROUTES = {"own-key": "0", "defeated": "0", "bucket": "65536"}


def _params():
    rng = np.random.RandomState(7)
    return {"b": rng.randn(10).astype(np.float32),
            "w2": rng.randn(6, 10).astype(np.float32),
            "w4": rng.randn(2, 3, 4, 5).astype(np.float32)}


def _loss(p, batch):
    import jax.numpy as jnp
    h = jnp.tanh(batch @ p["w2"] + p["b"])
    return jnp.mean(h ** 2) + jnp.mean(jnp.sin(p["w4"]) * h[0, 0])


@pytest.mark.parametrize("route", sorted(ROUTES))
@pytest.mark.parametrize("order", sorted(ORDERS))
def test_a_leaf_reaches_the_wire_row_major_whatever_the_chip_prefers(
        order, route, monkeypatch):
    import jax
    import jax.numpy as jnp
    from jax.experimental.layout import Format, Layout
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from byteps_tpu.core.state import get_state
    from byteps_tpu.jax import train

    preferred = ORDERS[order]
    lives = LIVES if order == "parameter-lives-transposed" else {}
    real_backward, real_pin = train._psum_backward, train._row_major_outputs
    default_compiled = []

    class Default:
        """The backward as the compiler would have it by default (or
        its lowering); says when that program is compiled or run."""

        def __init__(self, fn):
            self.fn = fn

        def __getattr__(self, name):
            return getattr(self.fn, name)

        def lower(self, *args):
            return Default(self.fn.lower(*args))

        def compile(self):
            default_compiled.append("compile")
            return self.fn.compile()

        def __call__(self, *args):
            default_compiled.append("call")
            return self.fn(*args)

    def compiler_prefers(loss_and_stats, mesh, axis):
        fn = real_backward(loss_and_stats, mesh, axis)
        rep = NamedSharding(mesh, P())
        return Default(jax.jit(fn.__wrapped__, out_shardings=(None, {
            k: Format(Layout(major_to_minor=preferred[k]), rep)
            if k in preferred else None for k in _params()})))

    outputs = {}

    def pin(backward, args, own, mesh):
        fn, n = (backward, 0) if route == "defeated" \
            else real_pin(backward, args, own, mesh)
        if route == "own-key" and lives:
            # pinned in the first build: the default program was never
            # made, so it is not loaded beside the one that runs
            assert n == len(lives) and default_compiled == []

        def run(params, batch):
            loss, grads = fn(params, batch)
            outputs.update(grads)
            return loss, grads

        return run, n

    monkeypatch.setattr(train, "_psum_backward", compiler_prefers)
    monkeypatch.setattr(train, "_row_major_outputs", pin)

    with _ps_env({"BYTEPS_FUSION_BYTES": ROUTES[route]}) as bps:
        state = get_state()
        handed, pushed, pulled = {}, {}, {}
        submit = state.scheduler.submit

        def recording_submit(ctx, flat_in, handle, *a, out=None, **kw):
            handed[ctx.name] = flat_in
            pushed[ctx.name] = flat_in.copy()
            pulled[ctx.name] = out
            return submit(ctx, flat_in, handle, *a, out=out, **kw)

        monkeypatch.setattr(state.scheduler, "submit", recording_submit)
        mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
        tx = optax.sgd(0.1)
        step = train.make_ps_train_step(_loss, tx, mesh)
        rep = NamedSharding(mesh, P())
        params = {k: jax.device_put(v, Format(Layout(
            major_to_minor=lives[k]), rep)) if k in lives
            else jnp.asarray(v) for k, v in _params().items()}
        batch = jnp.asarray(
            np.random.RandomState(1).randn(8, 6).astype(np.float32))
        before = dict(bps.get_metrics()["counters"])
        jax.block_until_ready(step(params, tx.init(params), batch))
        after = bps.get_metrics()["counters"]
        spans = [sp for sp in state.profiler.last_spans()
                 if sp[0] == tracing.EXPORT_SUBMIT]

    def moved(name):
        return after.get(name, 0) - before.get(name, 0)

    runtime = {k: np.asarray(v) for k, v in outputs.items()}
    for k, order_k in preferred.items():
        # the stand-in did what a compiler does: the runtime's array is
        # out of order unless the plan pinned it
        assert tuple(outputs[k].format.layout.major_to_minor) == (
            order_k if route != "own-key" else tuple(range(len(order_k))))
        assert runtime[k].flags.c_contiguous == (route == "own-key")
    # the transport is the identity, bit for bit, on every key
    assert pushed and set(pushed) == set(pulled)
    for name, sent in pushed.items():
        assert sent.flags.c_contiguous and sent.ndim == 1
        assert pulled[name].tobytes() == sent.tobytes(), name
    if route == "bucket":
        # one fused key: the members in flatten order, each row-major
        (sent,) = pushed.values()
        want = np.concatenate([runtime[k].ravel() for k in sorted(runtime)])
        assert sent.tobytes() == want.tobytes()
        assert moved("export/pinned_layout_leaves") == 0
        assert moved("export/host_relayout_bytes") == 0
        assert spans == []
        return
    assert sorted(pushed) == [f"grad/{k}" for k in sorted(runtime)]
    contiguous = {sp[4]["leaf"]: sp[4]["contiguous"] for sp in spans}
    relaid = 0
    for leaf, (k, arr) in enumerate(sorted(runtime.items())):
        name = f"grad/{k}"
        assert pushed[name].tobytes() == arr.ravel().tobytes(), k
        copied = route == "defeated" and k in preferred
        relaid += arr.nbytes * copied
        # no copy: what the scheduler got IS the runtime's buffer
        assert np.shares_memory(handed[name], arr) == (not copied), k
        assert contiguous[leaf] == (not copied), k
    assert moved("export/host_relayout_bytes") == relaid
    assert moved("export/pinned_layout_leaves") == (
        len(preferred) + len(lives) if route == "own-key" else 0)
