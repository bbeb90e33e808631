"""What the claim and the drain waited for (jax/train.py's
``bps.step.backward_wait`` span, the ``backward_done`` mark, the drain's
named parts and the CPU clocks; core/metrics.py ``step_path_fields``;
utils/tracing.py ``thread_cpu_ms``): the reduction on hand-made spans
and marks; real PS steps on one device and on the mesh, with a tree
whose first leaf is a bucket member and one whose first leaf rides a key
of its own, hold the span's place, the new StepReport fields and their
identities; a monolithic round and ``BYTEPS_METRICS=0`` read nothing;
the threads' CPU is read from a ``/proc`` tree, once before a step's
report opens and once after it has closed, inside the
``BYTEPS_TRACE_ON`` window only; and the per-device ingest gauges are
gone."""

import json
import os
import threading
import time

import numpy as np
import optax
import pytest

from byteps_tpu.core.metrics import StepProfiler, step_path_fields
from byteps_tpu.utils import tracing

from test_export_spans import _by_stage, _ps_env

PATH_FIELDS = ("backward_wait_ms", "export_behind_backward_ms",
               "drain_land_ms", "drain_finish_ms",
               "wire_tail_after_claim_ms", "claim_thread_cpu_ms")
ALL_FIELDS = PATH_FIELDS + ("export_bucket_member_ms",)
# the grain of the CPU clocks, and of float sums of perf_counter
EPS = 1e-6
CPU_GRAIN_MS = 20.0


# --------------------------------------------------------------------- #
# the reduction alone
# --------------------------------------------------------------------- #


def _sp(stage, t0, t1, **args):
    return (stage, "main", t0, t1, args)


MARKS = {"backward_done": 0.110, "export_done": 0.400, "drain_done": 0.520}
THREAD_CPU = {"backward_done": 1.0, "export_done": 1.004}
WIRE = [(0.150, 0.300), (0.390, 0.470)]
# the drain: two waiters collected and landed, and the round before's
LAND = [_sp("bps.apply.begin", 0.400, 0.401, step=7, waiters=2),
        _sp("bps.apply.finish", 0.410, 0.412, step=7, waiter=0),
        _sp("bps.apply.h2d_update", 0.412, 0.432, step=7, leaf=0),
        _sp("bps.apply.finish", 0.440, 0.441, step=7, waiter=1),
        _sp("bps.apply.h2d_update", 0.441, 0.456, step=7, leaf=1),
        _sp("bps.apply.h2d_update", 0.001, 0.002, step=6, leaf=1)]
GATHER = [_sp("bps.apply.assemble", 0.456, 0.458, step=7, leaf=2),
          _sp("bps.apply.allgather", 0.458, 0.465, step=7, leaf=2)]
HEAD = [_sp("bps.step.dispatch", 0.001, 0.004, step=7),
        # the round before's wait, ended while this step was open
        _sp("bps.step.backward_wait", 0.0, 0.0005, step=6),
        _sp("bps.step.backward_wait", 0.008, 0.110, step=7)]
WHOLE = [_sp("bps.export.materialize", 0.111, 0.200, step=7, leaf=0),
         _sp("bps.export.ingest", 0.111, 0.202, step=7, cause="out:0")]
MEMBERS = [_sp("bps.export.bucket_member", 0.111, 0.113, step=7, leaf=0),
           _sp("bps.export.bucket_member", 0.113, 0.114, step=7, leaf=1),
           _sp("bps.export.bucket_member", 0.050, 0.060, step=6, leaf=1)]
SHARDS = [_sp("bps.export.materialize", 0.111 + 0.01 * d, 0.120 + 0.01 * d,
              step=7, leaf=2)
          for d in range(4)]


@pytest.mark.parametrize("spans,land_ms,members_ms", [
    (HEAD + WHOLE + LAND, 35.0, None),
    (HEAD + MEMBERS + WHOLE + LAND, 35.0, 3.0),
    # a shard leaf's assembly and all-gather are the train thread
    # inside land_shard too
    (HEAD + SHARDS + LAND + GATHER, 44.0, None),
], ids=["whole-leaf-first", "bucket-first", "shards-on-a-mesh"])
def test_reduction_of_a_round_that_marked_the_backwards_end(
        spans, land_ms, members_ms):
    f = step_path_fields(spans, 7, MARKS, THREAD_CPU, WIRE)
    assert f["backward_wait_ms"] == pytest.approx(102.0)
    assert f["export_behind_backward_ms"] == pytest.approx(290.0)
    assert f["drain_land_ms"] == pytest.approx(land_ms)
    assert f["drain_finish_ms"] == pytest.approx(3.0)
    # the last completion 70 ms after the last submission
    assert f["wire_tail_after_claim_ms"] == pytest.approx(70.0)
    assert f["claim_thread_cpu_ms"] == pytest.approx(4.0)
    if members_ms is None:
        assert "export_bucket_member_ms" not in f
    else:  # this round's members only
        assert f["export_bucket_member_ms"] == pytest.approx(members_ms)
    assert set(f) <= set(ALL_FIELDS)


def test_reduction_of_a_monolithic_round_is_empty():
    # the device-compressed tier marks export_done and drain_done after
    # its one helper and never the backward's end
    marks = {"export_done": 0.4, "drain_done": 0.4}
    assert step_path_fields(HEAD[:1] + LAND, 7, marks, {}, WIRE) == {}
    assert step_path_fields([], None, {}, {}, []) == {}


def test_a_wire_that_ended_under_the_claim_has_no_tail_and_none_reads_none():
    f = step_path_fields(HEAD, 7, MARKS, THREAD_CPU, WIRE[:1])
    assert f["wire_tail_after_claim_ms"] == 0.0
    assert f["drain_land_ms"] == 0.0 and f["drain_finish_ms"] == 0.0
    # no scheduler: no wire span, nothing to read; a mark without the
    # thread's clock: no CPU to read
    f = step_path_fields(HEAD, 7, MARKS, {}, [])
    assert "wire_tail_after_claim_ms" not in f
    assert "claim_thread_cpu_ms" not in f


def test_end_step_fills_the_fields_from_the_builders_marks():
    prof = StepProfiler()
    b = prof.begin_step()
    b.round_tag = 1
    b.add_span(tracing.STEP_BACKWARD_WAIT, "main", b.t0, b.t0 + 0.002,
               {"step": 1})
    b.mark("backward_done", thread_cpu=True)
    b.wire_span(b.t0, b.t0 + 3600.0)
    b.mark("export_done", thread_cpu=True)
    b.add_span(tracing.APPLY_FINISH, "main", b.t0, b.t0 + 0.001,
               {"step": 1})
    b.add_span(tracing.APPLY_H2D_UPDATE, "main", b.t0, b.t0 + 0.004,
               {"step": 1})
    b.mark("drain_done")
    assert sorted(b.thread_cpu_marks) == ["backward_done", "export_done"]
    # the step's own CPU: all the process's threads, begin to end
    spin = b.cpu0
    while time.process_time() - spin < 0.03:
        pass
    r = prof.end_step(b)
    assert 30.0 <= r.step_cpu_ms <= r.wall_ms * (os.cpu_count() or 1) \
        + CPU_GRAIN_MS
    assert r.backward_wait_ms == pytest.approx(2.0)
    assert r.drain_land_ms == pytest.approx(4.0)
    assert r.drain_finish_ms == pytest.approx(1.0)
    assert r.wire_tail_after_claim_ms > 3.5e6
    assert r.export_bucket_member_ms is None
    for f in PATH_FIELDS:
        assert getattr(r, f) is not None and getattr(r, f) >= 0, f


# --------------------------------------------------------------------- #
# real PS steps
# --------------------------------------------------------------------- #


def _stepper(first, devices, **kw):
    """A two-layer classifier whose flatten order starts with a leaf on
    a key of its own (``first="big"``: a_w1, b_b1, c_w2, d_b2) or with a
    bucket member (``first="bucket"``: a_b1, b_w1, c_b2, d_w2)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from byteps_tpu.jax.train import make_ps_train_step

    w1, b1, w2, b2 = ("a_w1", "b_b1", "c_w2", "d_b2") if first == "big" \
        else ("b_w1", "a_b1", "d_w2", "c_b2")
    rng = np.random.RandomState(0)
    params = {w1: jnp.asarray(rng.randn(64, 48) * 0.1, jnp.float32),
              b1: jnp.zeros((48,), jnp.float32),
              w2: jnp.asarray(rng.randn(48, 16) * 0.1, jnp.float32),
              b2: jnp.zeros((16,), jnp.float32)}
    batch = {"x": jnp.asarray(rng.rand(32, 64), jnp.float32),
             "y": jnp.asarray(rng.randint(0, 16, 32), jnp.int32)}

    def loss_fn(p, b):
        h = jnp.tanh(b["x"] @ p[w1] + p[b1])
        logits = h @ p[w2] + p[b2]
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, b["y"]).mean()

    tx = optax.adam(1e-2)
    mesh = Mesh(np.array(jax.devices()[:devices]), ("dp",))
    step = make_ps_train_step(loss_fn, tx, mesh, **kw)
    state = [params, tx.init(params)]

    def run(n=1):
        for _ in range(n):
            p, o, loss = step(state[0], state[1], batch)
            jax.block_until_ready((p, o, loss))
            state[:] = [p, o]

    return run


# the weights (12 KB and 3 KB) ride keys of their own, the biases (192
# and 64 B) are bucket members; on the mesh the weights shard
ENV = {"BYTEPS_FUSION_BYTES": "1024", "BYTEPS_SHARD_MIN_BYTES": "1024"}


@pytest.fixture(scope="module", params=[
    ("big", 1), ("bucket", 1), ("big", 8), ("bucket", 8)],
    ids=["big-first-1dev", "bucket-first-1dev", "big-first-mesh",
         "bucket-first-mesh"])
def stepped(request):
    """Three PS steps; the last step's spans and every report."""
    from byteps_tpu.core.state import get_state

    first, devices = request.param
    with _ps_env(ENV) as bps:
        run = _stepper(first, devices)
        run(3)
        out = {"first": first, "devices": devices,
               "spans": get_state().profiler.last_spans(),
               "reports": bps.get_step_reports()[-3:],
               "gauges": bps.get_metrics()["gauges"]}
    return out


def test_one_backward_wait_a_step_on_the_train_thread_before_any_leaf(
        stepped):
    by = _by_stage(stepped["spans"])
    (wait,) = by[tracing.STEP_BACKWARD_WAIT]
    (claim,) = by[tracing.STEP_CLAIM]
    (dispatch,) = by[tracing.STEP_DISPATCH]
    assert wait[1] == threading.current_thread().name
    assert wait[4] == {"step": 3}
    assert dispatch[3] <= wait[2] and claim[2] <= wait[2]
    assert wait[3] <= claim[3]
    # no leaf is touched before the wait has ended: every materialize
    # from then on is a wait for a transfer and nothing else
    leaves = by[tracing.EXPORT_MATERIALIZE] \
        + by[tracing.EXPORT_BUCKET_MEMBER]
    assert min(sp[2] for sp in leaves) >= wait[3]
    first = min(leaves, key=lambda sp: sp[2])
    assert first[0] == (tracing.EXPORT_MATERIALIZE
                        if stepped["first"] == "big"
                        else tracing.EXPORT_BUCKET_MEMBER)
    assert first[4]["leaf"] == 0


def test_bucket_members_have_a_span_of_their_own_and_no_ingest(stepped):
    by = _by_stage(stepped["spans"])
    members = by[tracing.EXPORT_BUCKET_MEMBER]
    train = threading.current_thread().name
    bias_leaves = [1, 3] if stepped["first"] == "big" else [0, 2]
    assert [sp[4]["leaf"] for sp in members] == bias_leaves
    assert [sp[4]["bytes"] for sp in members] == [192, 64]
    assert {sp[1] for sp in members} == {train}
    assert {sp[4]["step"] for sp in members} == {3}
    ingests = by[tracing.EXPORT_INGEST]
    assert not {sp[4]["leaf"] for sp in ingests} & set(bias_leaves)
    assert not any(p[2] <= m[2] and m[3] <= p[3]
                   for m in members for p in ingests)
    # a weight: one ingest on one device, one a device on the mesh
    assert len(ingests) == 2 * stepped["devices"]
    assert len(by[tracing.EXPORT_MATERIALIZE]) == len(ingests)


def _assert_identities(r, cut=False):
    for f in ALL_FIELDS:
        assert r[f] is not None and r[f] >= 0, (f, r)
    # the step's time before the dispatch and between the dispatch
    # and the wait (the D2H copies' issue)
    rest = (r["compute_ms"] - r["dispatch_ms"] - r["backward_wait_ms"]
            - r["export_behind_backward_ms"])
    assert -EPS <= rest <= r["compute_ms"]
    # leaves are claimed behind a one-program backward; a cut one's also
    # while it runs, inside the wait
    assert (r["export_materialize_ms"] + r["export_bucket_member_ms"]
            <= r["export_behind_backward_ms"]
            + (r["backward_wait_ms"] if cut else 0.0) + EPS)
    assert (r["pull_wait_ms"] + r["drain_land_ms"]
            + r["drain_finish_ms"] <= r["drain_ms"] + EPS)
    assert r["wire_tail_after_claim_ms"] \
        <= r["drain_ms"] + r["tail_ms"] + EPS
    # the train thread's CPU in claiming, wherever it claimed: a cut
    # step's claims under the backward count too
    assert r["claim_thread_cpu_ms"] \
        <= r["export_behind_backward_ms"] \
        + (r["backward_wait_ms"] if cut else 0.0) + CPU_GRAIN_MS
    assert 0 <= r["claim_thread_cpu_ms"] \
        <= r["step_cpu_ms"] + CPU_GRAIN_MS


def test_the_fields_hold_their_identities_on_every_report(stepped):
    for r in stepped["reports"]:
        _assert_identities(r)
        if stepped["devices"] > 1:
            assert r["allgather_ms"] > 0
            assert r["drain_land_ms"] >= r["allgather_ms"] - EPS


@pytest.fixture(scope="module")
def cut_stepped():
    """Three PS steps of a loss written as a chain (``models/sdar.py``
    at test sizes: the embedding, a run of three layers, the head), its
    backward cut at the links: the last step's spans, every report."""
    import jax
    from jax.sharding import Mesh

    from byteps_tpu.core.state import get_state
    from byteps_tpu.jax.train import make_ps_train_step
    from byteps_tpu.models import sdar
    from test_chain import _sdar

    cfg, params, batch = _sdar()
    tx = optax.adam(1e-2)
    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    # a port of test_chain.py's own range: ``_ps_env``'s counter is
    # another process's too under xdist
    with _ps_env(ENV, port=25590) as bps:
        step = make_ps_train_step(
            lambda p, b: sdar.loss_fn(p, b, cfg), tx, mesh)
        opt = tx.init(params)
        for _ in range(3):
            params, opt, loss = step(params, opt, batch)
            jax.block_until_ready((params, opt, loss))
        out = {"spans": get_state().profiler.last_spans(),
               "reports": bps.get_step_reports()[-3:]}
    return out


def test_a_cut_step_holds_the_identities(cut_stepped):
    for r in cut_stepped["reports"]:
        _assert_identities(r, cut=True)
        # the head's program ends first: the first push is under the
        # backward
        assert r["ttfp_ms"] \
            <= r["compute_ms"] - r["export_behind_backward_ms"] + EPS


def test_a_cut_steps_wait_holds_a_span_a_program_and_the_early_claims(
        cut_stepped):
    by = _by_stage(cut_stepped["spans"])
    (wait,), (claim,) = by[tracing.STEP_BACKWARD_WAIT], by[tracing.STEP_CLAIM]
    assert wait[4] == {"step": 3} and claim[2] <= wait[2] <= wait[3]
    programs = by[tracing.STEP_BACKWARD_PROGRAM]
    # the forward, the head, three layers, the embedding: in the order
    # they end, each inside the wait, the last one ending it
    assert [sp[4]["index"] for sp in programs] == list(range(6))
    assert [sp[4]["links"] for sp in programs] \
        == ["0-1", "2", "1", "1", "1", "0"]
    assert all(set(sp[4]) == {"step", "index", "links", "bytes"}
               and sp[4]["step"] == 3 for sp in programs)
    assert programs[0][4]["bytes"] == 0 and programs[2][4]["bytes"] > 0
    assert len({sp[4]["bytes"] for sp in programs[2:5]}) == 1
    assert {sp[1] for sp in programs} == {threading.current_thread().name}
    assert wait[2] <= programs[0][2]
    assert all(a[3] <= b[2] for a, b in zip(programs, programs[1:]))
    assert programs[-1][3] <= wait[3] <= programs[-1][3] + 0.05
    # what a program hands over is claimed once it has ended: the head's
    # lm_head, then a layer's eight pieces, last layer first, then the
    # embedding; the wait ends between two claims, wherever the train
    # thread saw that the last program had ended, never before the
    # head's leaf was claimed
    ingests = sorted(by[tracing.EXPORT_INGEST], key=lambda sp: sp[2])
    assert [sp[4].get("layer") for sp in ingests] \
        == [None] + [2] * 8 + [1] * 8 + [0] * 8 + [None]
    assert [sp[4]["cause"] for sp in ingests[:1] + ingests[-1:]] \
        == ["out:14", "out:12"]
    assert {sp[4]["cause"] for sp in ingests[1:9]} \
        == {f"out:{i}/2" for i in range(4, 12)}
    for n, group in ((1, ingests[:1]), (2, ingests[1:9]),
                     (3, ingests[9:17]), (4, ingests[17:25]),
                     (5, ingests[25:])):
        assert all(programs[n][3] <= sp[2] for sp in group)
    assert ingests[0][3] <= wait[3] <= ingests[-1][2]
    assert not any(sp[2] < wait[3] < sp[3] for sp in ingests)
    # behind the backward, after the embedding: the bucket members in
    # flatten order
    late = ingests[-1:]
    members = by[tracing.EXPORT_BUCKET_MEMBER]
    assert [sp[4]["leaf"] for sp in members] == [0, 1, 2, 3, 13]
    assert all(sp[2] >= late[0][3] for sp in members)


def test_the_claim_and_the_drain_say_what_they_waited_for(stepped):
    by = _by_stage(stepped["spans"])
    (claim,), (drain,) = by[tracing.STEP_CLAIM], by[tracing.STEP_DRAIN]
    r = stepped["reports"][-1]
    assert claim[4]["behind_backward_ms"] == pytest.approx(
        r["export_behind_backward_ms"])
    assert drain[4]["pull_wait_ms"] == pytest.approx(r["pull_wait_ms"])
    assert set(claim[4]) == {"step", "behind_backward_ms"}
    assert set(drain[4]) == {"step", "pull_wait_ms"}
    # the threads' CPU is read in the BYTEPS_TRACE_ON window only
    assert tracing.STEP_HOST_CPU not in by


def test_the_drain_names_its_parts_by_span(stepped):
    """Set-up once, a finish a waiter, a land a leaf or a device's
    shard, an assembly and an all-gather a shard leaf: all on the train
    thread, inside the drain, none inside another."""
    by = _by_stage(stepped["spans"])
    (drain,), (begin,) = by[tracing.STEP_DRAIN], by[tracing.APPLY_BEGIN]
    n = stepped["devices"]
    # two weights (a waiter a device on the mesh) and the biases' bucket
    waiters = 2 * n + 1
    assert begin[4] == {"step": 3, "waiters": waiters}
    finishes = by[tracing.APPLY_FINISH]
    assert sorted(sp[4]["waiter"] for sp in finishes) \
        == list(range(waiters))
    lands = by[tracing.APPLY_H2D_UPDATE]
    assert len(lands) == 2 * n + 2  # each weight or shard, each bias
    gathers = by.get(tracing.APPLY_ALLGATHER, [])
    assembles = by.get(tracing.APPLY_ASSEMBLE, [])
    assert len(gathers) == len(assembles) == (2 if n > 1 else 0)
    parts = sorted([begin] + finishes + lands + gathers + assembles,
                   key=lambda sp: sp[2])
    assert {sp[1] for sp in parts} == {threading.current_thread().name}
    assert drain[2] <= parts[0][2] and parts[-1][3] <= drain[3]
    assert all(a[3] <= b[2] for a, b in zip(parts, parts[1:]))
    for asm, gat in zip(assembles, gathers):
        assert asm[4]["leaf"] == gat[4]["leaf"] and asm[3] <= gat[2]
    r = stepped["reports"][-1]
    ms = lambda sps: sum(sp[3] - sp[2] for sp in sps) * 1e3  # noqa: E731
    assert r["drain_finish_ms"] == pytest.approx(ms(finishes))
    assert r["drain_land_ms"] == pytest.approx(
        ms(lands + gathers + assembles))
    # allgather_ms keeps its meaning: the all-gather spans alone
    if n > 1:
        assert r["allgather_ms"] == pytest.approx(ms(gathers))


def test_no_device_has_an_ingest_gauge(stepped):
    assert not [g for g in stepped["gauges"] if "worker_ingests" in g]
    docs = os.path.join(os.path.dirname(__file__), "..", "docs")
    for name in ("observability.md", "timeline.md"):
        with open(os.path.join(docs, name)) as f:
            assert "worker_ingests" not in f.read(), name


def test_a_monolithic_round_reads_none():
    """The device-compressed tier: compute and wire inside one helper."""
    with _ps_env() as bps:
        run = _stepper("big", 1, compression={"compressor": "onebit",
                                              "ef": "vanilla"})
        run(2)
        r = bps.get_step_reports()[-1]
    assert r["compute_ms"] > 0 and r["dispatch_ms"] is None
    assert all(r[f] is None for f in ALL_FIELDS), r
    # the process's CPU over a step is any step's
    assert r["step_cpu_ms"] >= 0


def test_metrics_off_leaves_the_wait_and_no_report():
    with _ps_env({"BYTEPS_METRICS": "0", **ENV}) as bps:
        from byteps_tpu.core.state import get_state

        run = _stepper("bucket", 1)
        run(2)
        assert bps.get_step_reports() == []
        assert get_state().profiler.last_spans() == []
        assert bps.get_arena_stats()["export_leaves"] > 0


# --------------------------------------------------------------------- #
# who burned the CPU
# --------------------------------------------------------------------- #


def _proc_tree(root, threads):
    for tid, (name, utime, stime) in threads.items():
        os.makedirs(os.path.join(root, str(tid)))
        with open(os.path.join(root, str(tid), "stat"), "w") as f:
            f.write(f"{tid} ({name}) S 1 1 1 0 -1 4194368 3 0 0 0 {utime} "
                    f"{stime} 0 0 20 0 9 0 100 0 0\n")


def test_thread_cpu_is_summed_by_name_with_the_trailing_number_cut(
        tmp_path):
    me = threading.current_thread()
    ticks = 1e3 / os.sysconf("SC_CLK_TCK")
    _proc_tree(str(tmp_path), {
        # a thread Python started goes by its Python name
        me.native_id: ("python3", 7, 3),
        501: ("TpuHostTransfer", 100, 20), 502: ("TpuHostTransfer", 50, 0),
        503: ("tf_XLAEigen/17", 4, 0), 504: ("pool (a) 2", 1, 1),
        505: ("python3", 0, 2)})
    os.makedirs(tmp_path / "506")  # ended between listing and reading
    got = tracing.thread_cpu_ms(str(tmp_path))
    assert got == {
        tracing._TRAILING_NUMBER.sub("", me.name): 10 * ticks,
        "TpuHostTransfer": 170 * ticks, "tf_XLAEigen": 4 * ticks,
        "pool (a)": 2 * ticks, "python": 2 * ticks}
    assert tracing.thread_cpu_ms(str(tmp_path / "nowhere")) == {}


def test_the_eight_threads_that_used_most_between_two_readings():
    before = {f"t{k}": 100.0 for k in range(12)}
    after = {f"t{k}": 100.0 + 10.0 * k for k in range(12)}
    after["new"] = 35.0
    got = tracing.cpu_ms_by_thread(before, after)
    assert list(got.items()) == [
        ("t11", 110.0), ("t10", 100.0), ("t9", 90.0), ("t8", 80.0),
        ("t7", 70.0), ("t6", 60.0), ("t5", 50.0), ("t4", 40.0)]
    assert tracing.cpu_ms_by_thread(before, after, top=1) == {"t11": 110.0}
    assert tracing.cpu_ms_by_thread(after, after) == {}


def test_the_threads_cpu_is_one_table_a_step_inside_the_trace_window_only(
        tmp_path, monkeypatch):
    # every reading finds the main thread 30 ms further on
    readings = iter(range(100))
    monkeypatch.setattr(
        tracing, "thread_cpu_ms",
        lambda: {"MainThread": 30.0 * next(readings), "idle": 5.0})
    with _ps_env({"BYTEPS_TRACE_ON": "1", "BYTEPS_TRACE_START_STEP": "2",
                  "BYTEPS_TRACE_END_STEP": "2",
                  "BYTEPS_TRACE_DIR": str(tmp_path), **ENV}) as bps:
        from byteps_tpu.core.state import get_state

        run = _stepper("bucket", 1)
        run(3)
        by = _by_stage(get_state().profiler.last_spans())
        reports = bps.get_step_reports()[-3:]
    # the third step lies outside the window: no reading, no span
    assert tracing.STEP_HOST_CPU not in by
    assert next(readings) == 2  # two readings, one step
    with open(tmp_path / "0" / "comm.json") as f:
        events = json.load(f)["traceEvents"]
    (table,) = [e for e in events if e["name"] == tracing.STEP_HOST_CPU]
    assert table["args"] == {"step": 2,
                             "cpu_ms_by_thread": {"MainThread": 30.0}}
    # the readings lie outside the step's report: before its claim and
    # after its drain, and no phase's span carries a table
    (claim,) = [e for e in events if e["name"] == tracing.STEP_CLAIM]
    (drain,) = [e for e in events if e["name"] == tracing.STEP_DRAIN]
    assert drain["ts"] + drain["dur"] <= table["ts"]
    assert "cpu_ms_by_thread" not in claim["args"]
    assert "cpu_ms_by_thread" not in drain["args"]
    assert {e["args"]["step"] for e in events
            if e["name"] == tracing.STEP_BACKWARD_WAIT} == {2}
    assert all(r["step_cpu_ms"] >= 0 for r in reports)
