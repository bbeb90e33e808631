"""The reduction from the profiler's trace to busy, idle, per-operation
time and named gaps: on synthetic intervals, and on the small trace
recorded on a v5e that is kept beside it (``benchmark/data``)."""

import os

import pytest

from bench_helpers import BENCH

from benchmark import trace_reduce as tr

TINY = os.path.join(BENCH, "data", "tiny.xplane.pb")


def test_union_merges_overlaps_and_nesting():
    assert tr.union([(0, 2), (1, 3), (5, 6), (5.2, 5.4), (3, 3.5)]) == \
        [(0, 3.5), (5, 6)]
    assert tr.union([]) == []


def test_self_times_take_nested_events_out_of_their_parent():
    ops = [("while", 0.0, 10.0), ("fusion.1", 1.0, 3.0),
           ("fusion.2", 4.0, 9.0), ("copy", 5.0, 6.0), ("tail", 10.0, 11.0)]
    got = dict()
    for name, d in tr.self_times(ops):
        got[name] = got.get(name, 0.0) + d
    assert got == {"while": 3.0, "fusion.1": 2.0, "fusion.2": 4.0,
                   "copy": 1.0, "tail": 1.0}
    assert sum(got.values()) == 11.0


def test_clip_keeps_only_what_lies_inside():
    assert tr.clip([(0, 2), (3, 5), (6, 7)], 1, 4) == [(1, 2), (3, 4)]


@pytest.mark.parametrize("name,family", [
    ("fusion.123", "fusion"), ("%fusion.12 = f32[8]{0} fusion(...)", "fusion"),
    ("convert_reduce_fusion", "convert_reduce_fusion"),
    ("copy-start.4", "copy-start"), ("broadcast.79.clone", "broadcast.79.clone"),
    ("while", "while"), ("io_callback.3.1", "io_callback"), ("7", "7"),
])
def test_op_family_strips_numeric_suffixes(name, family):
    assert tr.op_family(name) == family


def test_name_gap_takes_the_span_that_covers_most():
    named = [("backward_export", (0.0, 2.0)), ("drain", (2.0, 2.5))]
    assert tr.name_gap((0.1, 1.9), named) == "backward_export"
    assert tr.name_gap((1.9, 2.4), named) == "drain"
    assert tr.name_gap((3.0, 4.0), named) == "outside_step"


def test_recorded_trace_reduces_to_the_numbers_read_on_the_chip():
    # the values record_tiny_trace.py printed on the v5e when it was made
    r = tr.reduce_trace(TINY)
    assert r.window_s == pytest.approx(0.01068216, rel=1e-6)
    assert set(r.busy_s) == {0}
    assert r.busy_s[0] == pytest.approx(7.139e-06, rel=1e-3)
    assert r.busy_mean_s == r.busy_s[0]
    assert r.idle_share == pytest.approx(1 - 7.139e-06 / 0.01068216, rel=1e-9)
    assert r.op_seconds[0][0] == "fusion"
    assert r.op_seconds[0][1] == pytest.approx(7.106e-06, rel=1e-3)
    assert {k: len(v) for k, v in r.spans.items()} == {
        "bench.window": 1, "bench.step": 3}
    # three steps with the host asleep in each: the three longest gaps
    # are a few milliseconds each, and every one lies inside a step
    named = [("in_step", iv) for iv in r.spans["bench.step"]]
    for gap in r.gaps[:3]:
        assert 0.002 < gap[1] - gap[0] < 0.006
        assert tr.name_gap(gap, named) == "in_step"
    # busy and the gaps together make the window
    assert r.busy_s[0] + sum(e - s for s, e in r.gaps) == \
        pytest.approx(r.window_s, rel=1e-9)


def test_a_trace_without_the_window_span_is_an_error():
    with pytest.raises(ValueError, match="no host span"):
        tr.reduce_trace(TINY, window_span="bench.absent")


def test_describe_lists_the_device_lines():
    rows = tr.describe(TINY)
    assert any(row.startswith("/device:TPU:0 | XLA Ops | 9 events")
               for row in rows)
