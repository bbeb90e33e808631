"""The program's spans on the gradient-export path (utils/tracing.py
``span``; jax/train.py, core/scheduler.py, core/metrics.py): every span
of PERF.md's table is recorded on the thread the table names, with the
round's tag as its ``step``, on one device (whole leaves) and on a mesh
(the weights as one shard a device); the StepReport's export fields are
reduced from them and hold their identities; nothing is read where no
leaf rides a key of its own or metrics are off; and a profiler session
opened by anybody holds the spans on its host lines, ``bps.wire.send``
and ``bps.wire.done`` pairing by ``rid``."""

import contextlib
import glob
import os
import threading

import numpy as np
import optax
import pytest

from byteps_tpu.config import Config
from byteps_tpu.core.metrics import (StepProfiler, _StepBuilder,
                                     export_span_fields)
from byteps_tpu.server import run_server
from byteps_tpu.utils import tracing

_PORT = [24650]

EXPORT_FIELDS = ("dispatch_ms", "export_router_busy_ms",
                 "export_materialize_ms", "export_submit_ms")
# what the tap route's spans fed, gone with it
TAP_FIELDS = ("export_tap_span_ms", "export_router_wait_max_ms")


@contextlib.contextmanager
def _ps_env(extra_env: dict = None, port: int = None):
    """``port``: one of the caller's own. The counter starts at the same
    number in every process, and under xdist the files that share it
    run side by side."""
    from byteps_tpu.core.state import GlobalState

    if port is None:
        port = _PORT[0]
        _PORT[0] += 1
    env = {
        "DMLC_NUM_WORKER": "1", "DMLC_NUM_SERVER": "1",
        "DMLC_PS_ROOT_URI": "127.0.0.1", "DMLC_PS_ROOT_PORT": str(port),
        "BYTEPS_FORCE_DISTRIBUTED": "1", **(extra_env or {}),
    }
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


def _stepper(devices=None, **kw):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from byteps_tpu.core.state import get_state
    from byteps_tpu.jax.train import make_ps_train_step
    from byteps_tpu.models import mlp

    cfg = mlp.MLPConfig(in_dim=64, hidden=(48, 32), n_classes=10)
    params = mlp.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)
    batch = {"x": jnp.asarray(rng.rand(32, 64), jnp.float32),
             "y": jnp.asarray(rng.randint(0, 10, 32), jnp.int32)}
    tx = optax.adam(1e-2)
    mesh = get_state().mesh if devices is None else Mesh(
        np.array(jax.devices()[:devices]), ("dp",))
    step = make_ps_train_step(lambda p, b: mlp.loss_fn(p, b, cfg), tx,
                              mesh, **kw)
    state = [params, tx.init(params)]

    def run(n=1):
        for _ in range(n):
            p, o, loss = step(state[0], state[1], batch)
            jax.block_until_ready((p, o, loss))
            state[:] = [p, o]

    return run, len(jax.tree.leaves(params))


# whole-leaf: one device, every leaf a whole output (what the one-chip
# cells run); shard: the eight-device mesh, the weights reduce-scatter
# and leave as one flat shard a device, the biases stay whole (what a
# whole host runs)
ENV = {"BYTEPS_FUSION_BYTES": "0", "BYTEPS_SHARD_MIN_BYTES": "1024"}
MODES = {"whole-leaf": 1, "shard": 8}


@pytest.fixture(scope="module", params=sorted(MODES))
def exported(request):
    """Three PS steps in one mode; the last step's spans, every report
    and the engagement counters."""
    from byteps_tpu.core.state import get_state

    with _ps_env(ENV) as bps:
        run, n_leaves = _stepper(devices=MODES[request.param])
        run(3)
        out = {"mode": request.param,
               "spans": get_state().profiler.last_spans(),
               "reports": bps.get_step_reports()[-3:],
               "arena": bps.get_arena_stats(), "n_leaves": n_leaves}
    return out


def _by_stage(spans):
    out = {}
    for sp in spans:
        out.setdefault(sp[0], []).append(sp)
    return out


def test_every_span_runs_on_the_thread_the_table_names(exported):
    by = _by_stage(exported["spans"])
    train = threading.current_thread().name
    for stage in (tracing.STEP_DISPATCH, tracing.STEP_CLAIM,
                  tracing.STEP_DRAIN, tracing.APPLY_H2D_UPDATE,
                  tracing.EXPORT_INGEST, tracing.EXPORT_MATERIALIZE,
                  tracing.EXPORT_SUBMIT):
        assert by[stage], stage
        assert {sp[1] for sp in by[stage]} == {train}, stage
    if exported["mode"] == "shard":
        assert by[tracing.APPLY_ALLGATHER]
        assert {sp[1] for sp in by[tracing.APPLY_ALLGATHER]} == {train}
    else:
        assert tracing.APPLY_ALLGATHER not in by
    # no span of the package runs on a thread of XLA's
    assert {sp[1] for sp in exported["spans"] if not sp[1].startswith("bps-")
            } == {train}
    assert all(sp[1].startswith("bps-push")
               for sp in by[tracing.WIRE_SEND])
    assert {sp[1] for sp in by[tracing.WIRE_DONE]} == {"bps-cq-reactor"}


def test_step_is_the_round_tag_and_one_ingest_per_leaf_or_shard(exported):
    by = _by_stage(exported["spans"])
    tag = by[tracing.STEP_DISPATCH][0][4]["step"]
    assert tag == 3  # the third PS round of this closure
    for stage in (tracing.STEP_CLAIM, tracing.STEP_DRAIN,
                  tracing.EXPORT_INGEST, tracing.EXPORT_MATERIALIZE,
                  tracing.EXPORT_SUBMIT, tracing.APPLY_H2D_UPDATE):
        assert {sp[4]["step"] for sp in by[stage]} == {tag}, stage
    ingests = by[tracing.EXPORT_INGEST]
    # one ingest a whole leaf, one a (leaf, device) of a sharded leaf,
    # in flatten then mesh-device order; each names the program output
    # it is as its cause
    devices = MODES[exported["mode"]]
    sharded = [3, 4, 5] if devices > 1 else []  # the 2-D leaves
    assert exported["arena"]["export_shard_leaves"] == 3 * len(sharded)
    want = [(i, d) for i in range(exported["n_leaves"])
            for d in (range(devices) if i in sharded else [None])]
    assert [(sp[4]["leaf"], sp[4].get("dev")) for sp in ingests] == want
    assert [sp[4]["cause"] for sp in ingests] == [
        f"out:{i}" if d is None else f"out:{i}/{d}" for i, d in want]
    assert all(sp[4]["bytes"] > 0 for sp in ingests)
    # children nest inside their ingest, inside the claim
    (claim,) = by[tracing.STEP_CLAIM]
    assert all(claim[2] <= sp[2] and sp[3] <= claim[3] for sp in ingests)
    for stage in (tracing.EXPORT_MATERIALIZE, tracing.EXPORT_SUBMIT):
        assert len(by[stage]) == len(want), stage
        for child in by[stage]:
            assert any(p[2] <= child[2] and child[3] <= p[3]
                       for p in ingests)
    assert all(sp[4]["partitions"] >= 1 and sp[4]["key"] >= 0
               for sp in by[tracing.EXPORT_SUBMIT])


def test_wire_send_and_done_pair_by_rid(exported):
    by = _by_stage(exported["spans"])
    sends = {sp[4]["rid"]: sp for sp in by[tracing.WIRE_SEND]}
    dones = {sp[4]["rid"]: sp for sp in by[tracing.WIRE_DONE]}
    assert sends and 0 not in sends
    assert set(sends) == set(dones)
    submits = {f"submit:{sp[4]['key']}" for sp in by[tracing.EXPORT_SUBMIT]}
    for rid, send in sends.items():
        assert send[4]["key"] == dones[rid][4]["key"]
        assert send[4]["bytes"] > 0 and send[4]["admit_wait_us"] >= 0
        assert send[4]["cause"] in submits
        assert send[2] <= dones[rid][3]


def test_the_export_fields_hold_their_identities_on_every_report(exported):
    for r in exported["reports"]:
        assert r["streamed_leaves"] == 0
        assert r["fallback_leaves"] == exported["n_leaves"]
        for f in EXPORT_FIELDS:
            assert r[f] is not None and r[f] >= 0, (f, r)
        assert not set(TAP_FIELDS) & set(r)
        eps = 1e-6
        assert (r["export_materialize_ms"] + r["export_submit_ms"]
                <= r["export_router_busy_ms"] + eps)
        assert r["export_router_busy_ms"] <= r["compute_ms"] + eps
        assert r["dispatch_ms"] <= r["compute_ms"] + eps


def test_fields_are_none_on_a_step_with_no_leaf_on_a_key_of_its_own():
    # under the fusion size every leaf is a bucket member: no ingest
    with _ps_env() as bps:
        from byteps_tpu.core.state import get_state

        run, _ = _stepper()
        run(2)
        r = bps.get_step_reports()[-1]
        assert r["streamed_leaves"] == 0 and r["compute_ms"] > 0
        assert all(r[f] is None for f in EXPORT_FIELDS), r
        by = _by_stage(get_state().profiler.last_spans())
        # the train thread's spans are there all the same; no export's
        assert by[tracing.STEP_DISPATCH] and by[tracing.STEP_CLAIM]
        assert tracing.EXPORT_INGEST not in by


def test_no_builder_and_no_report_with_metrics_off():
    with _ps_env({"BYTEPS_METRICS": "0",
                  "BYTEPS_FUSION_BYTES": "0"}) as bps:
        from byteps_tpu.core.state import get_state

        run, _ = _stepper()
        run(2)
        assert bps.get_step_reports() == []
        assert get_state().profiler.last_spans() == []
        assert bps.get_arena_stats()["export_leaves"] > 0


def test_the_fused_step_takes_no_span(bps):
    """``client is None``: the fused control's path through the PS step
    closure opens no builder and records nothing."""
    from byteps_tpu.core.state import get_state

    run, _ = _stepper()
    run(2)
    assert get_state().ps_client is None
    assert get_state().profiler.last_spans() == []
    assert bps.get_step_reports() == []


def test_an_open_profiler_session_holds_the_spans(tmp_path):
    """A session opened by the caller, with no BYTEPS_* tracing setting:
    the host lines of its .xplane.pb hold the program's spans with
    their arguments, ``send`` and ``done`` pairing by rid."""
    import jax
    from jax.profiler import ProfileData

    with _ps_env({"BYTEPS_FUSION_BYTES": "0",
                  "BYTEPS_LOCAL_SHARD_EXPORT": "0"}):
        from byteps_tpu.core.state import get_state

        assert get_state().tracer is None
        assert not get_state().config.jax_profiler_dir
        run, n_leaves = _stepper()
        run(1)  # compile outside the session
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            run(2)
        finally:
            jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("bps."):
                    events.setdefault(ev.name, []).append(
                        (li, dict(ev.stats)))
    for stage in (tracing.STEP_DISPATCH, tracing.STEP_CLAIM,
                  tracing.STEP_DRAIN,
                  tracing.EXPORT_INGEST, tracing.EXPORT_MATERIALIZE,
                  tracing.EXPORT_SUBMIT, tracing.WIRE_SEND,
                  tracing.WIRE_DONE, tracing.APPLY_H2D_UPDATE):
        assert events.get(stage), f"no {stage} event in the session"
    ingests = [a for _, a in events[tracing.EXPORT_INGEST]
               if not a.get("dropped")]
    assert len(ingests) == 2 * n_leaves
    assert {a["step"] for a in ingests} == {2, 3}
    assert all(a["cause"].startswith("out:") for a in ingests)
    # ingests on one line (the train thread's), sends on other lines
    # than dones
    assert len({li for li, a in events[tracing.EXPORT_INGEST]}) == 1
    send_rids = [a["rid"] for _, a in events[tracing.WIRE_SEND]]
    done_rids = [a["rid"] for _, a in events[tracing.WIRE_DONE]]
    assert send_rids and 0 not in send_rids
    assert sorted(send_rids) == sorted(done_rids)
    assert not ({li for li, _ in events[tracing.WIRE_SEND]}
                & {li for li, _ in events[tracing.WIRE_DONE]})


# --------------------------------------------------------------------- #
# the fields and the counters, by what the plan shards
# --------------------------------------------------------------------- #


# one device (what the one-chip cells run); the eight-device mesh with
# the shard plan off (every leaf whole) and on (the weights as one flat
# shard a device: what a whole host runs)
@pytest.mark.parametrize("shard,devices", [(True, 1), (False, 8), (True, 8)],
                         ids=["one-device", "off", "mesh"])
def test_every_byte_is_counted_where_it_left(shard, devices):
    env = dict(ENV)
    if not shard:
        env["BYTEPS_LOCAL_SHARD_EXPORT"] = "0"
    with _ps_env(env) as bps:
        from byteps_tpu.core.state import get_state

        run, n_leaves = _stepper(devices=devices)
        run(3)
        reports = bps.get_step_reports()[-3:]
        spans = get_state().profiler.last_spans()
        ctr = bps.get_metrics()["counters"]
        shard_leaves = bps.get_arena_stats()["export_shard_leaves"]
    for r in reports:
        assert r["streamed_leaves"] == 0
        assert r["fallback_leaves"] == n_leaves
        assert all(r[f] is not None and r[f] >= 0 for f in EXPORT_FIELDS)
    by = _by_stage(spans)
    ingests = by[tracing.EXPORT_INGEST]
    sharded = [3, 4, 5] if shard and devices > 1 else []
    assert shard_leaves == 3 * len(sharded)
    assert len(ingests) == n_leaves + (devices - 1) * len(sharded)
    # every byte of every step is counted, a whole leaf's as a
    # whole-leaf export and a shard's to the device that held it
    assert ctr["export/whole_bytes"] + ctr.get("export/shard_bytes", 0) \
        == 3 * sum(sp[4]["bytes"] for sp in ingests)
    for d in range(1, devices):
        assert ctr.get(f"export/device_bytes/{d}", 0) == 3 * sum(
            sp[4]["bytes"] for sp in ingests if sp[4].get("dev") == d)
    assert bool(by.get(tracing.APPLY_ALLGATHER)) == bool(sharded)
    assert by[tracing.WIRE_SEND] and by[tracing.WIRE_DONE]


# --------------------------------------------------------------------- #
# the reduction alone
# --------------------------------------------------------------------- #


def _sp(stage, thread, t0, t1, **args):
    return (stage, thread, t0, t1, args)


def test_reduction_sums_the_ingests_of_this_round_only():
    spans = [
        _sp("bps.step.dispatch", "main", 0.0, 0.010, step=5),
        # the round before's ingest, ended while this step was open
        _sp("bps.export.materialize", "main", 0.002, 0.003, step=4),
        _sp("bps.export.ingest", "main", 0.002, 0.004, step=4,
            cause="out:9"),
        _sp("bps.export.materialize", "main", 0.030, 0.050, step=5),
        _sp("bps.export.submit", "main", 0.050, 0.055, step=5),
        _sp("bps.export.ingest", "main", 0.030, 0.060, step=5,
            cause="out:1/0", dev=0),
        _sp("bps.export.materialize", "main", 0.062, 0.066, step=5),
        _sp("bps.export.ingest", "main", 0.062, 0.068, step=5,
            cause="out:1/1", dev=1),
    ]
    f = export_span_fields(spans, 5)
    assert not set(TAP_FIELDS) & set(f)
    assert f["dispatch_ms"] == pytest.approx(10.0)
    assert f["export_router_busy_ms"] == pytest.approx(36.0)
    assert f["export_materialize_ms"] == pytest.approx(24.0)
    assert f["export_submit_ms"] == pytest.approx(5.0)
    assert export_span_fields(spans, 6) == {}
    assert export_span_fields([], None) == {}


def test_reduction_of_a_step_has_four_fields_and_none_without_an_ingest():
    spans = [
        _sp("bps.step.dispatch", "main", 0.0, 0.004, step=7),
        _sp("bps.export.materialize", "main", 0.010, 0.030, step=7),
        _sp("bps.export.submit", "main", 0.030, 0.031, step=7),
        _sp("bps.export.ingest", "main", 0.010, 0.032, step=7,
            cause="out:0"),
        _sp("bps.export.materialize", "main", 0.032, 0.040, step=7),
        _sp("bps.export.submit", "main", 0.041, 0.043, step=7),
        _sp("bps.export.ingest", "main", 0.032, 0.044, step=7,
            cause="out:3"),
    ]
    f = export_span_fields(spans, 7)
    assert sorted(f) == sorted(EXPORT_FIELDS)
    assert f["dispatch_ms"] == pytest.approx(4.0)
    assert f["export_router_busy_ms"] == pytest.approx(34.0)
    assert f["export_materialize_ms"] == pytest.approx(28.0)
    assert f["export_submit_ms"] == pytest.approx(3.0)
    # a step whose leaves are all bucket members: a dispatch, no ingest
    assert export_span_fields(spans[:1], 7) == {}


def test_end_step_keeps_the_spans_and_none_means_none():
    prof = StepProfiler()
    b = prof.begin_step()
    assert isinstance(b, _StepBuilder)
    b.round_tag = 1
    b.add_span("bps.step.dispatch", "main", b.t0, b.t0 + 0.001,
               {"step": 1})
    b.mark("export_done")
    r = prof.end_step(b)
    assert all(getattr(r, f) is None for f in EXPORT_FIELDS)
    assert not any(hasattr(r, f) for f in TAP_FIELDS)
    assert [sp[0] for sp in prof.last_spans()] == ["bps.step.dispatch"]
