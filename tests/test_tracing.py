"""The span primitive and the Chrome-trace Tracer (utils/tracing.py):
one call is a fixed-name profiler annotation carrying the identifiers
as arguments, a record in the open step, and (inside the Tracer's step
window) a comm.json event; the annotation closes on every exit, a
window that ends under an open span loses the event and nothing else,
and the dump stays valid JSON."""

import threading

import pytest

from byteps_tpu.config import Config
from byteps_tpu.core.metrics import StepProfiler
from byteps_tpu.utils import tracing


class _FakeAnnotation:
    instances = []
    enabled = True

    def __init__(self, name, **kw):
        self.name, self.kw = name, dict(kw)
        self.entered = self.exited = 0
        _FakeAnnotation.instances.append(self)

    @staticmethod
    def is_enabled():
        return _FakeAnnotation.enabled

    def set_metadata(self, **kw):
        self.kw.update(kw)

    def __enter__(self):
        self.entered += 1
        return self

    def __exit__(self, *exc):
        self.exited += 1
        return False


@pytest.fixture()
def fake_annotation(monkeypatch):
    _FakeAnnotation.instances = []
    _FakeAnnotation.enabled = True
    monkeypatch.setattr(tracing, "_trace_me", _FakeAnnotation)
    yield _FakeAnnotation


@pytest.fixture()
def open_step(monkeypatch):
    """A bare state with one open step, in place of the global one."""
    class _State:
        profiler = StepProfiler()
        tracer = None

    state = _State()
    monkeypatch.setattr(tracing, "_get_state", lambda: state)
    builder = state.profiler.begin_step()
    yield state, builder


def test_span_annotation_has_a_fixed_name_and_carries_the_arguments(
        fake_annotation, open_step):
    _, builder = open_step
    with tracing.span(tracing.WIRE_SEND, tid="grad/w", key=7) as sp:
        sp.set(rid=41)
    (ann,) = fake_annotation.instances
    assert ann.name == "bps.wire.send"  # no tensor, no partition in it
    assert ann.kw == {"key": 7, "rid": 41}
    assert (ann.entered, ann.exited) == (1, 1)
    ((stage, thread, t0, t1, args),) = builder.spans
    assert stage == "bps.wire.send" and t1 >= t0
    assert thread == threading.current_thread().name
    assert args == {"key": 7, "rid": 41}


def test_span_with_no_session_costs_no_annotation(fake_annotation,
                                                  open_step):
    _, builder = open_step
    fake_annotation.enabled = False
    with tracing.span(tracing.EXPORT_INGEST, leaf=1):
        pass
    assert fake_annotation.instances == []
    assert [sp[0] for sp in builder.spans] == ["bps.export.ingest"]


def test_span_closes_its_annotation_when_the_body_raises_and_once(
        fake_annotation, open_step):
    _, builder = open_step
    sp = tracing.span(tracing.STEP_CLAIM, step=1).start()
    with pytest.raises(RuntimeError):
        with tracing.span(tracing.EXPORT_SUBMIT, step=1):
            raise RuntimeError("stage exploded")
    sp.stop()
    sp.stop()  # idempotent: the error path stops what the body stopped
    outer, inner = fake_annotation.instances
    assert (inner.entered, inner.exited) == (1, 1)
    assert (outer.entered, outer.exited) == (1, 1)
    assert [s[0] for s in builder.spans] == ["bps.export.submit",
                                             "bps.step.claim"]


def test_spans_reach_the_chrome_trace_inside_the_step_window(
        fake_annotation, open_step, tmp_path):
    """What replaced ``Tracer.begin/end`` and its counter events: a span
    that ENDS inside the window is a complete event on its tensor's
    row, one that ends after it is not, and the annotation closes
    either way."""
    import json

    state, _ = open_step
    state.tracer = tr = tracing.Tracer(Config(
        trace_on=True, trace_start_step=0, trace_end_step=2,
        trace_dir=str(tmp_path)))
    tr.step()
    with tracing.span(tracing.WIRE_PULL, tid="good", key=1):
        pass
    with pytest.raises(RuntimeError):
        with tracing.span(tracing.WIRE_PUSH, tid="bad", key=2):
            raise RuntimeError("stage exploded")
    late = tracing.span(tracing.WIRE_PUSH, tid="straddles", key=3).start()
    for _ in range(3):
        tr.step()  # step 4 > trace_end_step: flushed, window closed
    late.stop()
    assert [a.exited for a in fake_annotation.instances] == [1, 1, 1]
    with open(tr.flush(path=str(tmp_path / "late"))) as f:
        events = json.load(f)["traceEvents"]
    assert [(e["tid"], e["name"], e["ph"]) for e in events] == [
        ("good", "bps.wire.pull", "X"), ("bad", "bps.wire.push", "X")]
    assert events[0]["args"] == {"key": 1} and events[0]["dur"] >= 0


def test_flush_with_no_events_returns_none(fake_annotation, open_step,
                                           tmp_path):
    state, builder = open_step
    state.tracer = tr = tracing.Tracer(Config(
        trace_on=True, trace_start_step=5, trace_end_step=6,
        trace_dir=str(tmp_path)))
    with tracing.span(tracing.WIRE_PUSH, tid="t"):  # outside the window
        pass
    assert tr.flush() is None
    assert tr.dump(str(tmp_path / "fused.json")) is None
    assert len(builder.spans) == 1  # the step has it all the same
