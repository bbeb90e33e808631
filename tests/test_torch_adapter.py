"""byteps_tpu.torch adapter: Horovod-style surface over the DCN PS
(reference: byteps/torch/__init__.py, tests/test_mxnet.py semantics —
push_pull is identity at size 1 and averages across workers)."""

import threading

import numpy as np
import pytest
import torch

from byteps_tpu.config import Config
from byteps_tpu.core.registry import TensorRegistry
from byteps_tpu.core.types import DataType
from byteps_tpu.server import run_server
from byteps_tpu.server.client import PSClient

_PORT = [21800]


def _fresh_state():
    from byteps_tpu.core.state import GlobalState
    GlobalState._instance = None


@pytest.fixture()
def bpt(bps):
    """Torch adapter over the plain (no-PS) initialized core."""
    import byteps_tpu.torch as bpt_mod
    yield bpt_mod


@pytest.fixture()
def bpt_ps(monkeypatch):
    """Torch adapter over a 1-worker loopback PS (full distributed path)."""
    port = _PORT[0]
    _PORT[0] += 1
    monkeypatch.setenv("DMLC_NUM_WORKER", "1")
    monkeypatch.setenv("DMLC_NUM_SERVER", "1")
    monkeypatch.setenv("DMLC_PS_ROOT_URI", "127.0.0.1")
    monkeypatch.setenv("DMLC_PS_ROOT_PORT", str(port))
    monkeypatch.setenv("BYTEPS_FORCE_DISTRIBUTED", "1")
    server = threading.Thread(
        target=run_server,
        args=(port, Config(num_workers=1, num_servers=1)), daemon=True)
    server.start()
    _fresh_state()
    import byteps_tpu.torch as bpt_mod
    bpt_mod.init()
    yield bpt_mod
    bpt_mod.shutdown()
    server.join(timeout=10)
    _fresh_state()


def _toy_problem(seed=0):
    g = torch.Generator().manual_seed(seed)
    model = torch.nn.Sequential(
        torch.nn.Linear(8, 16), torch.nn.ReLU(), torch.nn.Linear(16, 1))
    x = torch.randn(64, 8, generator=g)
    y = x.sum(dim=1, keepdim=True)
    return model, x, y


def _train(model, x, y, opt, steps=30):
    losses = []
    for _ in range(steps):
        opt.zero_grad()
        loss = torch.nn.functional.mse_loss(model(x), y)
        loss.backward()
        opt.step()
        losses.append(float(loss.detach()))
    return losses


def test_push_pull_identity_single_worker(bpt):
    x = torch.randn(4, 5)
    out = bpt.push_pull(x, name="t_id")
    torch.testing.assert_close(out, x)
    # in-place variant
    y = x.clone()
    bpt.push_pull_inplace(y, name="t_id2")
    torch.testing.assert_close(y, x)


def test_push_pull_requires_name(bpt):
    with pytest.raises(ValueError, match="name"):
        bpt.push_pull_async(torch.randn(3))


def test_async_poll_synchronize(bpt):
    x = torch.randn(16)
    want = x.clone()
    h = bpt.push_pull_async(x, name="t_async")
    bpt.synchronize(h)
    torch.testing.assert_close(x, want)


def test_distributed_optimizer_trains(bpt):
    model, x, y = _toy_problem()
    opt = bpt.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=0.05),
        named_parameters=model.named_parameters())
    assert isinstance(opt, torch.optim.SGD)   # dynamic-subclass contract
    losses = _train(model, x, y, opt)
    assert losses[-1] < losses[0] * 0.5, losses


def test_distributed_optimizer_grad_accumulation(bpt):
    model, x, y = _toy_problem()
    opt = bpt.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=0.05),
        named_parameters=model.named_parameters(),
        backward_passes_per_step=2)
    losses = []
    for _ in range(20):
        opt.zero_grad()
        for _ in range(2):
            loss = torch.nn.functional.mse_loss(model(x), y)
            loss.backward()
        opt.step()
        losses.append(float(loss.detach()))
    assert losses[-1] < losses[0] * 0.5, losses


def test_broadcast_noop_single_worker(bpt):
    model, _, _ = _toy_problem()
    before = {k: v.clone() for k, v in model.state_dict().items()}
    bpt.broadcast_parameters(model.state_dict(), root_rank=0)
    for k, v in model.state_dict().items():
        torch.testing.assert_close(v, before[k])
    assert bpt.broadcast_object({"a": 1}, root_rank=0) == {"a": 1}


def test_distributed_optimizer_trains_via_ps(bpt_ps):
    model, x, y = _toy_problem()
    opt = bpt_ps.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=0.05),
        named_parameters=model.named_parameters())
    losses = _train(model, x, y, opt)
    assert losses[-1] < losses[0] * 0.5, losses


def test_fp16_compression_via_ps(bpt_ps):
    model, x, y = _toy_problem()
    opt = bpt_ps.DistributedOptimizer(
        torch.optim.Adam(model.parameters(), lr=0.01),
        named_parameters=model.named_parameters(),
        compression=bpt_ps.Compression.fp16)
    losses = _train(model, x, y, opt)
    assert losses[-1] < losses[0] * 0.5, losses


def test_broadcast_object_via_ps(bpt_ps):
    obj = {"step": 7, "arr": [1.0, 2.0, 3.0]}
    assert bpt_ps.broadcast_object(obj, root_rank=0) == obj


def test_broadcast_optimizer_state_via_ps(bpt_ps):
    model, x, y = _toy_problem()
    opt = torch.optim.Adam(model.parameters(), lr=0.01)
    _train(model, x, y, opt, steps=3)
    before = {k: {kk: (vv.clone() if torch.is_tensor(vv) else vv)
                  for kk, vv in st.items()}
              for k, st in opt.state_dict()["state"].items()}
    bpt_ps.broadcast_optimizer_state(opt, root_rank=0)
    after = opt.state_dict()
    assert after["param_groups"][0]["lr"] == 0.01
    # at 1 worker the broadcast is identity: the warm Adam moments must
    # SURVIVE the round trip intact (a no-op or state-corrupting
    # broadcast both fail here)
    assert set(after["state"]) == set(before)
    for k, st in before.items():
        for kk, vv in st.items():
            got = after["state"][k][kk]
            if torch.is_tensor(vv):
                assert torch.allclose(got.float(), vv.float(),
                                      rtol=1e-6), (k, kk)
                assert not torch.equal(vv, torch.zeros_like(vv)) or \
                    kk == "step"
            else:
                assert got == vv, (k, kk)


def test_ddp_wrapper_via_ps(bpt_ps):
    model, x, y = _toy_problem()
    # plain-backward reference on an identical copy: at 1 worker
    # push_pull is identity, so synced grads must EQUAL the local ones
    # (catches a sync_gradients that silently fails to write back)
    import copy

    ref = copy.deepcopy(model)
    loss_ref = torch.nn.functional.mse_loss(ref(x), y)
    loss_ref.backward()
    ddp = bpt_ps.DistributedDataParallel(model)
    loss = torch.nn.functional.mse_loss(ddp(x), y)
    loss.backward()
    ddp.sync_gradients()
    for p, pr in zip(model.parameters(), ref.parameters()):
        assert p.grad is not None
        assert torch.allclose(p.grad, pr.grad, rtol=1e-5, atol=1e-7)


def test_two_worker_mean(monkeypatch):
    """Worker 0 = the torch adapter; worker 1 = a raw PSClient on a thread.
    push_pull must return the cross-worker mean (the reference's
    test_byteps_push_pull sum semantics, tests/test_mxnet.py:60-125)."""
    port = _PORT[0]
    _PORT[0] += 1
    monkeypatch.setenv("DMLC_NUM_WORKER", "2")
    monkeypatch.setenv("DMLC_NUM_SERVER", "1")
    monkeypatch.setenv("DMLC_WORKER_ID", "0")
    monkeypatch.setenv("DMLC_PS_ROOT_URI", "127.0.0.1")
    monkeypatch.setenv("DMLC_PS_ROOT_PORT", str(port))
    monkeypatch.setenv("BYTEPS_FORCE_DISTRIBUTED", "1")
    server = threading.Thread(
        target=run_server,
        args=(port, Config(num_workers=2, num_servers=1)), daemon=True)
    server.start()
    _fresh_state()
    import byteps_tpu.torch as bpt_mod
    bpt_mod.init()
    try:
        x0 = np.random.RandomState(0).randn(128).astype(np.float32)
        x1 = np.random.RandomState(1).randn(128).astype(np.float32)

        reg = TensorRegistry(Config(num_workers=2, num_servers=1))
        c1 = PSClient([f"127.0.0.1:{port}"], worker_id=1)
        res = {}

        def w1():
            ctx = reg.init_tensor("t2w", x1.nbytes, DataType.FLOAT32)
            res["w1"] = c1.push_pull(ctx, x1, average=True, num_workers=2)

        th = threading.Thread(target=w1, daemon=True)
        th.start()
        out = bpt_mod.push_pull(torch.from_numpy(x0.copy()), name="t2w")
        th.join(timeout=30)
        assert not th.is_alive()
        want = (x0 + x1) / 2
        np.testing.assert_allclose(out.numpy(), want, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(res["w1"], want, rtol=1e-5, atol=1e-6)
        c1.close()
    finally:
        bpt_mod.shutdown()
        server.join(timeout=10)
        _fresh_state()


def test_sparse_embedding_gradients(bpt_ps):
    """nn.Embedding(sparse=True) gradients ride the row-sparse wire; the
    optimizer sees the aggregated DENSE gradient and training matches a
    plain torch run (1 worker => identity aggregation)."""
    import numpy as np

    def build(seed):
        torch.manual_seed(seed)
        return torch.nn.Sequential(
            torch.nn.Embedding(50, 8, sparse=True),
            torch.nn.Flatten(),
            torch.nn.Linear(8 * 4, 5))

    ids = torch.from_numpy(
        np.random.RandomState(0).randint(0, 50, (16, 4)))
    y = torch.from_numpy(np.random.RandomState(1).randint(0, 5, 16))

    ref = build(3)
    # plain torch: sparse grads need dense optim only for SGD w/o momentum
    ro = torch.optim.SGD(ref.parameters(), lr=0.1)
    for _ in range(4):
        ro.zero_grad()
        torch.nn.functional.cross_entropy(ref(ids), y).backward()
        ro.step()

    model = build(3)
    opt = bpt_ps.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=0.1),
        named_parameters=model.named_parameters())
    for _ in range(4):
        opt.zero_grad()
        torch.nn.functional.cross_entropy(model(ids), y).backward()
        opt.step()
        assert model[0].weight.grad is None or \
            not model[0].weight.grad.is_sparse  # replaced with dense

    for (n1, p1), (n2, p2) in zip(ref.named_parameters(),
                                  model.named_parameters()):
        np.testing.assert_allclose(p1.detach().numpy(),
                                   p2.detach().numpy(),
                                   rtol=2e-5, atol=2e-5, err_msg=n1)


def test_bf16_push_pull_roundtrip(bpt_ps):
    """bfloat16 tensors must reach the wire (DataType.BFLOAT16) instead
    of crashing in .numpy() — round-4 review regression. Bit-exact
    through the 1-worker PS sum."""
    x = torch.randn(257, dtype=torch.float32).to(torch.bfloat16)
    out = bpt_ps.push_pull(x.clone(), average=True, name="bf16t")
    assert out.dtype == torch.bfloat16
    assert torch.equal(out, x)


def test_bf16_optimizer_grad_hook(bpt_ps):
    """A bf16 model trains through the grad-hook path (the hook exports
    grads host-side; bf16 previously raised inside backward)."""
    model = torch.nn.Linear(8, 4).to(torch.bfloat16)
    opt = bpt_ps.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=0.05),
        named_parameters=model.named_parameters())
    x = torch.randn(16, 8).to(torch.bfloat16)
    loss0 = None
    for _ in range(5):
        opt.zero_grad()
        loss = model(x).square().mean()
        loss.backward()
        opt.step()
        loss0 = loss0 if loss0 is not None else float(loss)
    assert float(loss) < loss0
