"""From a trace's ``bps.`` host events to who was doing what in an idle
gap (``benchmark/program_spans.py``): on synthetic spans, and on the
small PS trace recorded on a v5e that is kept beside it
(``benchmark/data/tiny_ps.xplane.pb``)."""

import os

import pytest

from bench_helpers import BENCH

from benchmark import program_spans as ps
from benchmark.program_spans import ProgramSpan

TINY_PS = os.path.join(BENCH, "data", "tiny_ps.xplane.pb")
TINY = os.path.join(BENCH, "data", "tiny.xplane.pb")


def _spans():
    rows = [
        # line 1, the train thread
        ("bps.step.dispatch", 1, 0.00, 0.10, {}),
        ("bps.step.claim", 1, 0.10, 2.00, {}),
        ("bps.step.drain", 1, 2.00, 2.10, {}),
        ("bps.apply.h2d_update", 1, 2.01, 2.02, {"leaf": 0}),
        # lines 2 and 3, two of XLA's callback threads
        ("bps.export.tap", 2, 0.30, 0.3001, {"leaf": 0, "seq": 1}),
        ("bps.export.tap", 3, 1.20, 1.2001, {"leaf": 1, "seq": 2}),
        # line 4, the router: two ingests, busy most of the time
        ("bps.export.ingest", 4, 0.31, 1.10, {"leaf": 0, "dev": 0}),
        ("bps.export.materialize", 4, 0.32, 1.00, {}),
        ("bps.export.submit", 4, 1.00, 1.09, {}),
        ("bps.export.ingest", 4, 1.21, 1.95, {"leaf": 1, "dev": 0}),
        ("bps.export.materialize", 4, 1.22, 1.90, {}),
        ("bps.export.submit", 4, 1.90, 1.94, {}),
        # lines 5 and 6: the push pool and the reactor
        ("bps.wire.send", 5, 1.10, 1.11, {"rid": 7, "key": 1}),
        ("bps.wire.send", 5, 1.95, 1.96, {"rid": 8, "key": 2}),
        ("bps.wire.done", 6, 1.30, 1.3001, {"rid": 7, "key": 1}),
        ("bps.wire.done", 6, 1.99, 1.9901, {"rid": 0, "key": 2}),
    ]
    spans = [ProgramSpan(n, line, s, e, a) for n, line, s, e, a in rows]
    ps.nest(spans)
    ps.name_threads(spans)
    return spans


def test_depth_is_the_number_of_spans_around_one_on_its_line():
    depth = {(sp.name, sp.start): sp.depth for sp in _spans()}
    assert depth[("bps.step.drain", 2.00)] == 0
    assert depth[("bps.apply.h2d_update", 2.01)] == 1
    assert depth[("bps.export.ingest", 0.31)] == 0
    assert depth[("bps.export.materialize", 0.32)] == 1
    assert depth[("bps.export.submit", 1.90)] == 1


def test_threads_are_named_by_what_they_run():
    threads = {sp.line: sp.thread for sp in _spans()}
    assert threads == {1: "train", 2: "callback-0", 3: "callback-1",
                       4: "router", 5: "send", 6: "reactor"}


def test_per_device_workers_are_named_by_their_device():
    spans = [ProgramSpan("bps.export.route", 1, 0.0, 0.1, {"dev": 0}),
             ProgramSpan("bps.export.route", 1, 0.1, 0.2, {"dev": 1}),
             ProgramSpan("bps.export.ingest", 2, 0.1, 0.5, {"dev": 0}),
             ProgramSpan("bps.export.ingest", 3, 0.2, 0.6, {"dev": 1})]
    assert ps.name_threads(spans) == {1: "router", 2: "export-d0",
                                      3: "export-d1"}


def test_a_gap_is_named_per_thread_by_the_deepest_span_covering_most():
    spans = _spans()
    by = ps.name_gap_by_thread(spans, (0.30, 1.98))
    assert by["train"] == ("bps.step.claim", pytest.approx(1.0))
    # the ingests cover 91 % of the gap, their materialize 82 %: the
    # deeper one names it
    assert by["router"] == ("bps.export.materialize",
                            pytest.approx(1.36 / 1.68))
    # a thread that only blinks inside the gap was mostly idle
    assert by["callback-0"][0] == "mostly_idle"
    assert by["callback-0"][1] == pytest.approx(1 - 0.0001 / 1.68)
    assert by["send"] == ("mostly_idle", pytest.approx(1 - 0.02 / 1.68))
    # the drain's gap: another answer from the same threads
    by = ps.name_gap_by_thread(spans, (2.0, 2.1))
    assert by["train"] == ("bps.step.drain", pytest.approx(1.0))
    assert by["router"] == ("mostly_idle", pytest.approx(1.0))


def test_wire_pairs_by_rid_and_leaves_the_unpaired_out():
    (pair,) = ps.pair_wire(_spans())
    rid, send, done = pair
    assert rid == 7 and done.start - send.end == pytest.approx(0.19)


def test_totals_clip_to_the_window():
    rows = {(t, n): (k, s) for t, n, k, s in
            ps.totals(_spans(), (1.0, 2.05))}
    assert rows[("router", "bps.export.ingest")] == (
        2, pytest.approx(0.10 + 0.74))
    assert rows[("train", "bps.step.drain")] == (1, pytest.approx(0.05))
    assert ("train", "bps.step.dispatch") not in rows


def test_a_trace_of_a_program_without_spans_reads_as_empty():
    assert ps.read_program_spans(TINY) == []
    out = ps.attribute(TINY)
    assert out["threads"] == [] and out["totals"] == []
    assert out["wire_pairs"] == 0
    assert all(g["by_thread"] == {} for g in out["gaps"])


def test_recorded_ps_trace_names_its_gaps_as_read_on_the_chip():
    # the values record_tiny_ps_trace.py printed on the v5e when it was
    # made: two steps of a six-leaf MLP through make_ps_train_step
    out = ps.attribute(TINY_PS)
    assert out["window_s"] == pytest.approx(0.057409546, rel=1e-6)
    assert out["idle_share"] == pytest.approx(0.87484425, rel=1e-6)
    assert out["threads"] == ["callback-0", "callback-1", "reactor",
                              "router", "send-0", "send-1", "train"]
    # the two longest gaps are the two steps' exports: the train thread
    # waits in claim, every other thread is mostly idle (the leaves are
    # small: the time is XLA's, between taps)
    for gap, seconds, claim in zip(out["gaps"], (0.016985114, 0.015528257),
                                   (0.94840482, 0.96495479)):
        assert gap["seconds"] == pytest.approx(seconds, rel=1e-6)
        by = gap["by_thread"]
        assert by["train"] == ("bps.step.claim", pytest.approx(claim))
        assert all(name == "mostly_idle" for thread, (name, _)
                   in by.items() if thread != "train")
        assert 0.7 < by["router"][1] < 0.8
    # a gap late in the second step: the drain's imports
    assert out["gaps"][4]["by_thread"]["train"] == (
        "bps.apply.h2d_update", pytest.approx(0.94689114))
    rows = {(t, n): (k, s) for t, n, k, s in out["totals"]}
    assert rows[("router", "bps.export.ingest")] == (
        12, pytest.approx(0.007548701, rel=1e-6))
    assert rows[("router", "bps.export.materialize")][0] == 12
    assert rows[("train", "bps.step.dispatch")] == (
        2, pytest.approx(0.004746649, rel=1e-6))
    assert rows[("callback-0", "bps.export.tap")][0] \
        + rows[("callback-1", "bps.export.tap")][0] == 12
    # twelve requests, each send paired with its done by rid
    assert out["wire_pairs"] == 12
    assert out["wire_in_flight_s"][0] == pytest.approx(0.00021406, rel=1e-4)
    assert out["wire_in_flight_s"][-1] == pytest.approx(0.002677, rel=1e-4)


def test_recorded_ps_trace_spans_carry_their_arguments_and_nest():
    spans = ps.read_program_spans(TINY_PS)
    assert len(spans) == 90
    ingests = [sp for sp in spans if sp.name == "bps.export.ingest"]
    taps = {f"tap:{sp.args['seq']}" for sp in spans
            if sp.name == "bps.export.tap"}
    assert {sp.args["step"] for sp in ingests} == {4, 5}
    assert all(sp.args["cause"] in taps and sp.depth == 0
               and sp.thread == "router" and sp.args["queued_us"] > 0
               for sp in ingests)
    assert all(sp.depth == 1 for sp in spans if sp.name in (
        "bps.export.materialize", "bps.export.submit",
        "bps.apply.h2d_update"))
    submits = {f"submit:{sp.args['key']}" for sp in spans
               if sp.name == "bps.export.submit"}
    assert {sp.args["cause"] for sp in spans
            if sp.name == "bps.wire.send"} == submits
    # inside bench.step, on the clock of the XLA Ops lines
    from benchmark.trace_reduce import reduce_trace

    steps = reduce_trace(TINY_PS).spans["bench.step"]
    assert all(any(s <= sp.start and sp.end <= e for s, e in steps)
               for sp in spans if sp.thread in ("train", "router"))
