"""byteps-lint (byteps_tpu/tools/lint, docs/static-analysis.md).

Two layers:

- fixture proofs: every rule fires on a seeded violation (including a
  deliberately skewed wire-header constant and a mis-documented
  BYTEPS_* default), stays quiet on the known-good twin, and honors
  per-line suppression;
- the real repo: ``run_lint(REPO)`` must be CLEAN with all six rules
  active — the PR gate ci/checks.sh runs — and the full-repo pass must
  stay under 30 s so it can live inside tier-1.

The CLI contract (exit codes 0/1/2, ``path:line: [rule] message``) is
pinned here because ci/checks.sh and editor integrations parse it.
"""

import os
import re
import subprocess
import sys
import textwrap
import time

import pytest

from byteps_tpu.tools.lint import all_rules, run_lint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _write_tree(root, files):
    for rel, content in files.items():
        path = os.path.join(root, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write(textwrap.dedent(content))
    return str(root)


def _rules_hit(findings):
    return {f.rule for f in findings}


# --------------------------------------------------------------------- #
# wire-layout
# --------------------------------------------------------------------- #

_CC_GOOD = """
    static constexpr uint32_t kMagic = 0xB17E5002;
    enum WireCodec : uint8_t {
      kCodecUntagged = 0,
      kCodecDense = 1,
      kCodecLossless = 2,
    };
    #pragma pack(push, 1)
    struct MsgHeader {
      uint32_t magic;
      uint8_t op;
      uint8_t flags;
      uint16_t sender;
      uint32_t rid;
      uint64_t key;
      uint32_t cmd;
      uint32_t len;
      uint64_t epoch;
      uint32_t codec;
    };
    #pragma pack(pop)
    static_assert(sizeof(MsgHeader) == 40, "header layout");
"""

_PY_MIRROR_GOOD = """
    WIRE_MAGIC = 0xB17E5002
    WIRE_HEADER_FMT = "<IBBHIQIIQI"
    WIRE_HEADER_BYTES = 40
    WIRE_CODEC_IDS = {"dense": 1, "lossless": 2}
"""


def test_wire_layout_clean_fixture(tmp_path):
    root = _write_tree(tmp_path, {
        "native/ps.cc": _CC_GOOD,
        "server/client.py": _PY_MIRROR_GOOD,
    })
    assert run_lint(root, ["wire-layout"]) == []


def test_wire_layout_skewed_header_size(tmp_path):
    # THE drift class: the native header grew (36 -> 40) and the Python
    # header-size constant was not updated
    root = _write_tree(tmp_path, {
        "native/ps.cc": _CC_GOOD,
        "server/client.py": _PY_MIRROR_GOOD.replace(
            "WIRE_HEADER_BYTES = 40", "WIRE_HEADER_BYTES = 36"),
    })
    findings = run_lint(root, ["wire-layout"])
    assert len(findings) == 1
    assert "36" in findings[0].message and "40" in findings[0].message
    assert findings[0].path == os.path.join("server", "client.py")


def test_wire_layout_magic_skew(tmp_path):
    root = _write_tree(tmp_path, {
        "native/ps.cc": _CC_GOOD,
        "server/client.py": _PY_MIRROR_GOOD.replace(
            "WIRE_MAGIC = 0xB17E5002", "WIRE_MAGIC = 0xB17E5001"),
    })
    findings = run_lint(root, ["wire-layout"])
    assert len(findings) == 1
    assert "0xb17e5001" in findings[0].message.lower()


def test_wire_layout_field_order_skew(tmp_path):
    # epoch/codec swapped relative to the struct declaration
    root = _write_tree(tmp_path, {
        "native/ps.cc": _CC_GOOD,
        "server/client.py": _PY_MIRROR_GOOD.replace(
            '"<IBBHIQIIQI"', '"<IBBHIQIIIQ"'),
    })
    findings = run_lint(root, ["wire-layout"])
    assert any("field order" in f.message for f in findings)


def test_wire_layout_native_assert_vs_fields(tmp_path):
    # the struct grew a field but the static_assert was left behind:
    # caught on the native side alone
    cc = _CC_GOOD.replace("uint32_t codec;",
                          "uint32_t codec;\n      uint32_t extra;")
    root = _write_tree(tmp_path, {
        "native/ps.cc": cc,
        "server/client.py": _PY_MIRROR_GOOD,
    })
    findings = run_lint(root, ["wire-layout"])
    assert any("static_assert" in f.message and "44" in f.message
               for f in findings)


def test_wire_layout_codec_id_skew(tmp_path):
    root = _write_tree(tmp_path, {
        "native/ps.cc": _CC_GOOD,
        "server/client.py": _PY_MIRROR_GOOD.replace(
            '"lossless": 2', '"lossless": 3'),
    })
    findings = run_lint(root, ["wire-layout"])
    assert len(findings) == 1
    assert "kCodecLossless" in findings[0].message


def test_wire_layout_missing_mirror(tmp_path):
    # a tree with a native header but no Python mirror is a finding,
    # not a silent pass — the rule must never be vacuous
    root = _write_tree(tmp_path, {"native/ps.cc": _CC_GOOD})
    findings = run_lint(root, ["wire-layout"])
    assert any("mirror" in f.message for f in findings)


def test_wire_layout_suppression(tmp_path):
    root = _write_tree(tmp_path, {
        "native/ps.cc": _CC_GOOD,
        "server/client.py": _PY_MIRROR_GOOD.replace(
            "WIRE_HEADER_BYTES = 40",
            "WIRE_HEADER_BYTES = 36  # bps-lint: disable=wire-layout"),
    })
    assert run_lint(root, ["wire-layout"]) == []


# --------------------------------------------------------------------- #
# wire-layout: slot/record-layout manifests (the _STAT_SLOTS contract,
# machine-checked instead of comment-enforced) + control-op ids
# --------------------------------------------------------------------- #

_CC_SLOTS = _CC_GOOD + """
    static const char* const kStatSlotNames[] = {
        "recv_ns", "recv_count", "fold_ns"};
    enum Op : uint8_t {
      PUSH = 2,
      STATS_PULL = 12,
      TRACE_DRAIN = 13,
    };
    enum CtrlLimits : uint32_t {
      kCtrlDrainBatch = 1024,
    };
    #pragma pack(push, 1)
    struct TraceRec {
      uint64_t key;
      uint64_t t0;
      uint32_t rid;
      uint16_t sender;
      uint8_t op;
      uint8_t kind;
    };
    #pragma pack(pop)
    static_assert(sizeof(TraceRec) == 24, "trace record layout");
    static const char* const kTraceRecFields[] = {
        "key", "t0", "rid", "sender", "op", "kind"};
"""

_PY_SLOTS = _PY_MIRROR_GOOD + """
    _STAT_SLOTS = ("recv_ns", "recv_count", "fold_ns")
    TRACE_REC_FMT = "<QQIHBB"
    _TRACE_REC_FIELDS = ("key", "t0", "rid", "sender", "op", "kind")
    WIRE_CTRL_OPS = {"STATS_PULL": 12, "TRACE_DRAIN": 13}
    WIRE_CTRL_LIMITS = {"kCtrlDrainBatch": 1024}
"""


def test_slot_layout_clean_fixture(tmp_path):
    root = _write_tree(tmp_path, {
        "native/ps.cc": _CC_SLOTS,
        "server/client.py": _PY_SLOTS,
    })
    assert run_lint(root, ["wire-layout"]) == []


def test_slot_layout_renamed_slot(tmp_path):
    # the historical class: a slot renamed/retyped native-side with the
    # Python mirror (which PARSES the wire vector) left behind
    root = _write_tree(tmp_path, {
        "native/ps.cc": _CC_SLOTS.replace('"fold_ns"', '"fold_bytes"'),
        "server/client.py": _PY_SLOTS,
    })
    findings = run_lint(root, ["wire-layout"])
    assert len(findings) == 1
    assert "slot 2" in findings[0].message
    assert "fold_ns" in findings[0].message
    assert "fold_bytes" in findings[0].message


def test_slot_layout_truncated_mirror(tmp_path):
    # native appended a slot, mirror not extended: append-only violated
    root = _write_tree(tmp_path, {
        "native/ps.cc": _CC_SLOTS.replace(
            '"fold_ns"};', '"fold_ns", "fold_bytes"};'),
        "server/client.py": _PY_SLOTS,
    })
    findings = run_lint(root, ["wire-layout"])
    assert len(findings) == 1
    assert "3 vs 4 slots" in findings[0].message


def test_slot_layout_reordered_mirror_fails_both_directions(tmp_path):
    # a REORDER is a violation even with identical membership (the
    # vector is positional), and the missing-native direction fires too
    root = _write_tree(tmp_path, {
        "native/ps.cc": _CC_SLOTS,
        "server/client.py": _PY_SLOTS.replace(
            '("recv_ns", "recv_count", "fold_ns")',
            '("recv_count", "recv_ns", "fold_ns")'),
    })
    findings = run_lint(root, ["wire-layout"])
    assert len(findings) == 1 and "slot 0" in findings[0].message
    # native manifest without any Python mirror: loud, never vacuous
    root2 = _write_tree(tmp_path / "two", {
        "native/ps.cc": _CC_SLOTS,
        "server/client.py": _PY_SLOTS.replace(
            '_STAT_SLOTS = ("recv_ns", "recv_count", "fold_ns")', ""),
    })
    findings = run_lint(root2, ["wire-layout"])
    assert any("_STAT_SLOTS" in f.message and "mirror" in f.message
               for f in findings)


def test_trace_rec_fmt_size_skew(tmp_path):
    # the record struct grew native-side; the struct-format mirror that
    # PARSES the drained ring bytes still packs the old size
    root = _write_tree(tmp_path, {
        "native/ps.cc": _CC_SLOTS.replace(
            "uint64_t t0;", "uint64_t t0;\n      uint64_t t1;").replace(
            "sizeof(TraceRec) == 24", "sizeof(TraceRec) == 32").replace(
            '"key", "t0",', '"key", "t0", "t1",'),
        "server/client.py": _PY_SLOTS.replace(
            '_TRACE_REC_FIELDS = ("key", "t0",',
            '_TRACE_REC_FIELDS = ("key", "t0", "t1",'),
    })
    findings = run_lint(root, ["wire-layout"])
    assert len(findings) == 1
    assert "TRACE_REC_FMT packs 24" in findings[0].message
    assert "32" in findings[0].message


def test_ctrl_op_id_skew(tmp_path):
    root = _write_tree(tmp_path, {
        "native/ps.cc": _CC_SLOTS,
        "server/client.py": _PY_SLOTS.replace(
            '"TRACE_DRAIN": 13', '"TRACE_DRAIN": 14'),
    })
    findings = run_lint(root, ["wire-layout"])
    assert len(findings) == 1
    assert "TRACE_DRAIN" in findings[0].message
    assert "unknown op" in findings[0].message


def test_ctrl_limit_skew(tmp_path):
    # the server grew its drain batch; the client buffer mirror would
    # under-size and replies would drain silently empty
    root = _write_tree(tmp_path, {
        "native/ps.cc": _CC_SLOTS.replace("kCtrlDrainBatch = 1024",
                                          "kCtrlDrainBatch = 4096"),
        "server/client.py": _PY_SLOTS,
    })
    findings = run_lint(root, ["wire-layout"])
    assert len(findings) == 1
    assert "kCtrlDrainBatch" in findings[0].message
    assert "silently empty" in findings[0].message


# training-health record (PR 15): the HEALTH_PULL reply's fixed-width
# HealthRec rides the same slot-manifest machinery as the trace/flight
# records — manifest + struct size diffed against the Python mirror
_CC_HEALTH = _CC_SLOTS + """
    #pragma pack(push, 1)
    struct HealthRec {
      uint64_t key;
      uint64_t round;
      uint64_t sumsq_bits;
      uint64_t absmax_bits;
      uint64_t nonfinite;
      uint64_t elems;
    };
    #pragma pack(pop)
    static_assert(sizeof(HealthRec) == 48, "health record layout");
    static const char* const kHealthRecFields[] = {
        "key", "round", "sumsq_bits", "absmax_bits", "nonfinite",
        "elems"};
"""

_PY_HEALTH = _PY_SLOTS + """
    HEALTH_REC_FMT = "<QQQQQQ"
    _HEALTH_REC_FIELDS = ("key", "round", "sumsq_bits", "absmax_bits",
                          "nonfinite", "elems")
"""


def test_health_rec_clean_fixture(tmp_path):
    root = _write_tree(tmp_path, {
        "native/ps.cc": _CC_HEALTH,
        "server/client.py": _PY_HEALTH,
    })
    assert run_lint(root, ["wire-layout"]) == []


def test_health_rec_renamed_field(tmp_path):
    # the drift class: a field renamed native-side while the Python
    # parser (which reassembles the double bit patterns) lags
    root = _write_tree(tmp_path, {
        "native/ps.cc": _CC_HEALTH.replace('"sumsq_bits"',
                                           '"sumsq"'),
        "server/client.py": _PY_HEALTH,
    })
    findings = run_lint(root, ["wire-layout"])
    assert len(findings) == 1
    assert "_HEALTH_REC_FIELDS" in findings[0].message
    assert "sumsq" in findings[0].message


def test_health_rec_fmt_size_skew(tmp_path):
    # the record grew native-side; the struct-format mirror that sizes
    # the client's reply buffer still packs the old 48 bytes
    root = _write_tree(tmp_path, {
        "native/ps.cc": _CC_HEALTH.replace(
            "uint64_t elems;", "uint64_t elems;\n      uint64_t rsvd;"
        ).replace("sizeof(HealthRec) == 48",
                  "sizeof(HealthRec) == 56").replace(
            '"nonfinite",\n        "elems"};',
            '"nonfinite",\n        "elems", "rsvd"};'),
        "server/client.py": _PY_HEALTH.replace(
            '"nonfinite", "elems")', '"nonfinite", "elems", "rsvd")'),
    })
    findings = run_lint(root, ["wire-layout"])
    assert len(findings) == 1
    assert "HEALTH_REC_FMT packs 48" in findings[0].message
    assert "56" in findings[0].message


# --------------------------------------------------------------------- #
# guarded-by
# --------------------------------------------------------------------- #

_LOCKS_FIXTURE = """
    import threading

    class Sched:
        def __init__(self):
            self._mu = threading.Lock()
            self._cv = threading.Condition(self._mu)
            self._state = {}     # guarded-by: _mu|_cv
            self._plain = 0      # unannotated: never checked

        def good(self):
            with self._mu:
                return dict(self._state)

        def good_cv(self):
            with self._cv:
                self._state[1] = 2

        def good_nested_lambda(self):
            with self._cv:
                return (lambda: len(self._state))()

        def bad(self):
            return self._state.get(1)

        def bad_closure_defined_under_lock(self):
            with self._mu:
                def later():
                    # runs on an unknown thread AFTER the with exits:
                    # lexical nesting must not count as holding
                    return self._state
                return later

        def suppressed(self):
            # documented racy read
            return len(self._state)  # bps-lint: disable=guarded-by

        def _drain_locked(self):
            return self._state.popitem()

        def unrelated(self):
            return self._plain
"""


def test_guarded_by_fixture(tmp_path):
    root = _write_tree(tmp_path, {"sched.py": _LOCKS_FIXTURE})
    findings = run_lint(root, ["guarded-by"])
    lines = sorted(f.line for f in findings)
    msgs = [f.message for f in findings]
    assert len(findings) == 2, findings
    assert all("Sched._state" in m for m in msgs)
    assert any("bad()" in m for m in msgs)
    assert any("later()" in m for m in msgs)
    assert lines == sorted(lines)


def test_guarded_by_annotation_above_and_wrapped(tmp_path):
    root = _write_tree(tmp_path, {"m.py": """
        import threading

        class C:
            def __init__(self):
                self._mu = threading.Lock()
                # guarded-by: _mu
                self._above = []
                self._wrapped = (1 +
                                 2)  # guarded-by: _mu

            def bad(self):
                return self._above, self._wrapped
    """})
    findings = run_lint(root, ["guarded-by"])
    assert {m for f in findings for m in [f.message.split(" is ")[0]]} \
        == {"C._above", "C._wrapped"}


_MIXED_LOCKS_FIXTURE = """
    import threading

    class Plane:
        def __init__(self):
            self._mu = threading.Lock()
            self._ingest_mu = threading.Lock()
            self._tensors = {}    # guarded-by: _mu
            self._last = 0        # guarded-by: _ingest_mu

        def _unannotated_locked(self):
            # which lock does the caller hold? the class mixes two, so
            # the bare *_locked convention must NOT exempt this
            return self._tensors

        def _annotated_locked(self):  # caller-holds: _mu
            return self._tensors

        # caller-holds: _mu
        def _above_style_locked(self):
            return self._tensors

        def _wrong_lock_locked(self):  # caller-holds: _mu
            # annotated for _mu but touches _ingest_mu state: the exact
            # wrong-side-of-the-lock class the rule exists for
            return self._last
"""


def test_guarded_by_locked_convention_not_blanket(tmp_path):
    # In a class with MULTIPLE lock groups, *_locked alone is no longer
    # an exemption: the caller-held lock must be named, and a
    # caller-holds annotation only covers attributes under THAT lock.
    root = _write_tree(tmp_path, {"plane.py": _MIXED_LOCKS_FIXTURE})
    findings = run_lint(root, ["guarded-by"])
    by_fn = {}
    for f in findings:
        m = re.search(r"but (\w+)\(\)", f.message)
        by_fn.setdefault(m.group(1), []).append(f.message)
    assert set(by_fn) == {"_unannotated_locked", "_wrong_lock_locked"}, \
        findings
    assert "caller-holds" in by_fn["_unannotated_locked"][0]  # the hint
    assert "Plane._last" in by_fn["_wrong_lock_locked"][0]


def test_guarded_by_locked_single_group_stays_exempt(tmp_path):
    # With ONE lock family in the class the convention is unambiguous:
    # unannotated *_locked methods keep working (the common case —
    # registry/scheduler — must not need annotation churn). The family
    # is the INTERSECTION of the attrs' alternatives, so mixing '_mu'
    # with '_mu|_cv' (a Condition and the Lock it wraps) still counts
    # as one family.
    root = _write_tree(tmp_path, {"m.py": """
        import threading

        class Q:
            def __init__(self):
                self._mu = threading.Lock()
                self._cv = threading.Condition(self._mu)
                self._heap = []   # guarded-by: _mu|_cv
                self._n = 0       # guarded-by: _mu|_cv
                self._closed = False  # guarded-by: _mu

            def _pop_locked(self):
                self._n -= 1
                if not self._closed:
                    return self._heap.pop()
    """})
    assert run_lint(root, ["guarded-by"]) == []


def test_guarded_by_orphaned_annotation_is_a_finding(tmp_path):
    # An annotation the rule cannot bind to an attribute guards
    # NOTHING — silently dropping it would disarm the protection the
    # author believes they added.
    root = _write_tree(tmp_path, {"m.py": """
        import threading

        # guarded-by: _mu

        class C:
            def __init__(self):
                self._mu = threading.Lock()
                # guarded-by: _mu

                self._orphan = 0

            def bad(self):
                return self._orphan
    """})
    findings = run_lint(root, ["guarded-by"])
    assert len(findings) == 2, findings
    assert all("guards nothing" in f.message for f in findings)
    assert {f.line for f in findings} == {4, 9}


# --------------------------------------------------------------------- #
# device-thread
# --------------------------------------------------------------------- #

_TAP_FIXTURE = """
    import functools
    import numpy as np
    from jax.experimental import io_callback

    def build(pool, holder):
        def _good_tap(i, step_arr, arr):
            pool.submit(ingest, i, step_arr, arr)

        def _bad_tap(i, step_arr, arr):
            v = np.asarray(arr)          # materializes on device thread
            holder["f"].result()         # blocks on a future
            return int(step_arr)         # materializes a scalar

        def program(x):
            io_callback(functools.partial(_good_tap, 0), None, x, x)
            io_callback(_bad_tap, None, x, x)

        def ingest(i, step_arr, arr):
            # runs on the pool worker, NOT the device thread: int() and
            # asarray() here are the correct place and must not flag
            return int(step_arr), np.asarray(arr)

        return program
"""


def test_device_thread_fixture(tmp_path):
    root = _write_tree(tmp_path, {"taps.py": _TAP_FIXTURE})
    findings = run_lint(root, ["device-thread"])
    assert len(findings) == 3, findings
    assert all("_bad_tap" in f.message for f in findings)
    kinds = " ".join(f.message for f in findings)
    assert "np.asarray" in kinds
    assert ".result()" in kinds
    assert "int()" in kinds


def test_device_thread_lock_and_queue_get(tmp_path):
    root = _write_tree(tmp_path, {"taps.py": """
        from jax.experimental import io_callback

        def build(q, mu):
            def _tap(i, arr):
                with mu:
                    pass
                q.get(timeout=1)

            def program(x):
                io_callback(_tap, None, x)

            return program
    """})
    findings = run_lint(root, ["device-thread"])
    msgs = " ".join(f.message for f in findings)
    assert "acquires lock" in msgs and ".get()" in msgs


def test_device_thread_benign_joins_not_flagged(tmp_path):
    # str.join / os.path.join are not Thread.join: args or a literal
    # receiver mean "not the blocking shape"; a bare thread.join() is
    root = _write_tree(tmp_path, {"taps.py": """
        import os
        from jax.experimental import io_callback

        def build(pool, thread):
            def _tap(i, arr):
                name = "/".join(["a", "b"])
                path = os.path.join("a", "b")
                pool.submit(name, path, arr)

            def _bad_tap(i, arr):
                thread.join()

            def program(x):
                io_callback(_tap, None, x)
                io_callback(_bad_tap, None, x)

            return program
    """})
    findings = run_lint(root, ["device-thread"])
    assert len(findings) == 1, findings
    assert "_bad_tap" in findings[0].message
    assert ".join()" in findings[0].message


def test_device_thread_suppression(tmp_path):
    root = _write_tree(tmp_path, {"taps.py": """
        from jax.experimental import io_callback

        def build(pool):
            def _tap(i, arr):
                return int(i)  # bps-lint: disable=device-thread

            def program(x):
                io_callback(_tap, None, x)

            return program
    """})
    assert run_lint(root, ["device-thread"]) == []


def test_device_thread_method_and_lambda_taps_scanned(tmp_path):
    # self._tap and lambda callbacks must be resolved and scanned, not
    # skipped: a refactor from a nested def to a bound method must not
    # take the tap out of the rule's sight.
    root = _write_tree(tmp_path, {"taps.py": """
        import functools
        from jax.experimental import io_callback

        class Exporter:
            def _bad_tap(self, i, arr):
                return arr.item()

            def program(self, x):
                io_callback(functools.partial(self._bad_tap, 0), None, x)
                io_callback(lambda arr: arr.tolist(), None, x)
    """})
    findings = run_lint(root, ["device-thread"])
    msgs = " ".join(f.message for f in findings)
    assert len(findings) == 2, findings
    assert "_bad_tap" in msgs and ".item()" in msgs
    assert "<lambda>" in msgs and ".tolist()" in msgs


def test_device_thread_unresolvable_tap_is_a_finding(tmp_path):
    # Fail closed: a callback the rule cannot scan (imported name,
    # factory-call result) is a finding at the registration site —
    # never a vacuous pass — and suppressible there with a WHY.
    root = _write_tree(tmp_path, {"taps.py": """
        from jax.experimental import io_callback
        from elsewhere import imported_tap

        def build(make_tap):
            def program(x):
                io_callback(imported_tap, None, x)
                io_callback(make_tap(), None, x)
                # reviewed: the factory returns a pure enqueue closure
                io_callback(make_tap(), None, x)  # bps-lint: disable=device-thread

            return program
    """})
    findings = run_lint(root, ["device-thread"])
    assert len(findings) == 2, findings
    msgs = " ".join(f.message for f in findings)
    assert "'imported_tap' is not defined in this module" in msgs
    assert "cannot be resolved" in msgs


def test_device_thread_keyword_callback_and_deferred_lambda(tmp_path):
    # callback= keyword registration is a registration (fail closed on
    # it too); a lambda BUILT inside the tap body runs later on a
    # worker thread, exactly like a nested def, and must not flag.
    root = _write_tree(tmp_path, {"taps.py": """
        from jax.experimental import io_callback
        from elsewhere import imported_tap

        def build(pool, q):
            def _tap(i, arr):
                pool.submit(lambda: q.get())
                q.get(block=False)

            def program(x):
                io_callback(_tap, None, x)
                io_callback(callback=imported_tap,
                            result_shape_dtypes=None)

            return program
    """})
    findings = run_lint(root, ["device-thread"])
    assert len(findings) == 1, findings
    assert "'imported_tap' is not defined in this module" \
        in findings[0].message


def test_device_thread_inline_lambdas_still_scanned(tmp_path):
    # Only lambdas handed to a DEFERRAL site run later; a sorted key=
    # or an immediately-invoked lambda executes on the device thread
    # and must flag like inline code.
    root = _write_tree(tmp_path, {"taps.py": """
        from jax.experimental import io_callback

        def build(handles, mu):
            def _tap(i, arr):
                best = min(handles, key=lambda h: h.result())
                (lambda: mu.acquire())()

            def program(x):
                io_callback(_tap, None, x)

            return program
    """})
    findings = run_lint(root, ["device-thread"])
    msgs = " ".join(f.message for f in findings)
    assert len(findings) == 2, findings
    assert ".result()" in msgs and ".acquire()" in msgs


def test_guarded_by_conflicting_annotations_are_a_finding(tmp_path):
    # A re-annotation naming a DIFFERENT lock is author error; an
    # identical re-annotation (reassignment site) is fine. The FIRST
    # annotation stays enforced (union would accept either lock —
    # weaker than either annotation alone), so the _cv-held access to
    # the _mu-guarded attr also fires.
    root = _write_tree(tmp_path, {"m.py": """
        import threading

        class C:
            def __init__(self):
                self._mu = threading.Lock()
                self._cv = threading.Condition(self._mu)
                self._heap = []   # guarded-by: _mu
                self._same = 0    # guarded-by: _mu

            def reset(self):
                with self._cv:
                    self._heap = []   # guarded-by: _cv
                with self._mu:
                    self._same = 0    # guarded-by: _mu
    """})
    findings = run_lint(root, ["guarded-by"])
    msgs = " ".join(f.message for f in findings)
    assert len(findings) == 2, findings
    assert "conflicting" in msgs and "C._heap" in msgs
    assert "without holding the lock" in msgs


# --------------------------------------------------------------------- #
# env-sync
# --------------------------------------------------------------------- #

_ENV_CONFIG = """
    DEFAULT_FOO_BYTES = 4096000

    def _env_int(name, default):
        return default

    def _env_bool(name, default=False):
        return default

    def from_env():
        return (_env_int("BYTEPS_FOO_BYTES", DEFAULT_FOO_BYTES),
                _env_int("BYTEPS_BAR", 7),
                _env_bool("BYTEPS_BAZ"))
"""

_ENV_DOC = """
    # Environment variables

    | Variable | Default | Meaning |
    |---|---|---|
    | `BYTEPS_FOO_BYTES` | 4096000 | partition size |
    | `BYTEPS_BAR` | 7 | bar knob |
    | `BYTEPS_BAZ` | 0 | baz switch |
"""


def test_env_sync_clean_fixture(tmp_path):
    root = _write_tree(tmp_path, {
        "config.py": _ENV_CONFIG,
        "docs/env.md": _ENV_DOC,
    })
    assert run_lint(root, ["env-sync"]) == []


def test_env_sync_undocumented_read(tmp_path):
    root = _write_tree(tmp_path, {
        "config.py": _ENV_CONFIG + (
            "    SECRET = _env_int(\"BYTEPS_UNDOCUMENTED\", 1)\n"),
        "docs/env.md": _ENV_DOC,
    })
    findings = run_lint(root, ["env-sync"])
    assert len(findings) == 1
    assert "BYTEPS_UNDOCUMENTED" in findings[0].message
    assert findings[0].path == "config.py"


def test_env_sync_stale_doc_row(tmp_path):
    root = _write_tree(tmp_path, {
        "config.py": _ENV_CONFIG,
        "docs/env.md": _ENV_DOC + (
            "| `BYTEPS_REMOVED_KNOB` | 1 | nothing reads this |\n"),
    })
    findings = run_lint(root, ["env-sync"])
    assert len(findings) == 1
    assert "BYTEPS_REMOVED_KNOB" in findings[0].message
    assert findings[0].path.endswith("env.md")


def test_env_sync_misdocumented_default(tmp_path):
    # acceptance fixture: a deliberately mis-documented BYTEPS_* default
    root = _write_tree(tmp_path, {
        "config.py": _ENV_CONFIG,
        "docs/env.md": _ENV_DOC.replace(
            "| `BYTEPS_FOO_BYTES` | 4096000 |",
            "| `BYTEPS_FOO_BYTES` | 4194304 |"),
    })
    findings = run_lint(root, ["env-sync"])
    assert len(findings) == 1
    assert "4194304" in findings[0].message
    assert "4096000" in findings[0].message


def test_env_sync_bool_default_mismatch(tmp_path):
    root = _write_tree(tmp_path, {
        "config.py": _ENV_CONFIG,
        "docs/env.md": _ENV_DOC.replace(
            "| `BYTEPS_BAZ` | 0 |", "| `BYTEPS_BAZ` | 1 |"),
    })
    findings = run_lint(root, ["env-sync"])
    assert len(findings) == 1 and "BYTEPS_BAZ" in findings[0].message


def test_env_sync_docstring_mention_is_not_a_read(tmp_path):
    # a knob quoted only in a docstring must not count as read: the
    # stale table row fires (direction 2) and no undocumented-read
    # false positive appears (direction 1)
    root = _write_tree(tmp_path, {
        "config.py": _ENV_CONFIG + (
            '\n    def helper():\n'
            '        """Quotes "BYTEPS_GHOST_KNOB" without reading it."""\n'
            '        return None\n'),
        "docs/env.md": _ENV_DOC + (
            "| `BYTEPS_GHOST_KNOB` | 1 | only a docstring quotes it |\n"),
    })
    findings = run_lint(root, ["env-sync"])
    assert len(findings) == 1
    assert "BYTEPS_GHOST_KNOB" in findings[0].message
    assert "nothing in the code reads it" in findings[0].message


def test_env_sync_native_getenv(tmp_path):
    # native getenv() reads are scanned too (the chaos/IPC knob class)
    root = _write_tree(tmp_path, {
        "config.py": _ENV_CONFIG,
        "native/ps.cc": 'int f() { return getenv("BYTEPS_NATIVE_ONLY") '
                        '!= 0; }\n',
        "docs/env.md": _ENV_DOC,
    })
    findings = run_lint(root, ["env-sync"])
    assert len(findings) == 1
    assert "BYTEPS_NATIVE_ONLY" in findings[0].message


# --------------------------------------------------------------------- #
# metrics-schema
# --------------------------------------------------------------------- #

_METRICS_CODE = """
    def wire(metrics):
        metrics.counter("wire/push_requests")
        metrics.gauge("wire/inflight")
        for tier in ("dense", "onebit"):
            metrics.gauge(f"codec/active/{tier}")
"""

_METRICS_DOC = """
    # Observability

    ```schema
    counters.wire/push_requests
    gauges.wire/inflight
    gauges.codec/active/dense
    ```
"""


def test_metrics_schema_clean_fixture(tmp_path):
    root = _write_tree(tmp_path, {
        "wire.py": _METRICS_CODE,
        "docs/observability.md": _METRICS_DOC,
    })
    assert run_lint(root, ["metrics-schema"]) == []


def test_metrics_schema_undocumented_instrument(tmp_path):
    root = _write_tree(tmp_path, {
        "wire.py": _METRICS_CODE.replace(
            'metrics.gauge("wire/inflight")',
            'metrics.gauge("wire/inflight")\n'
            '        metrics.counter("wire/new_thing")'),
        "docs/observability.md": _METRICS_DOC,
    })
    findings = run_lint(root, ["metrics-schema"])
    assert len(findings) == 1
    assert "wire/new_thing" in findings[0].message
    assert findings[0].path == "wire.py"


def test_metrics_schema_dead_doc_entry(tmp_path):
    root = _write_tree(tmp_path, {
        "wire.py": _METRICS_CODE,
        "docs/observability.md": _METRICS_DOC.replace(
            "counters.wire/push_requests",
            "counters.wire/push_requests\n"
            "counters.wire/ghost_counter"),
    })
    findings = run_lint(root, ["metrics-schema"])
    assert len(findings) == 1
    assert "wire/ghost_counter" in findings[0].message
    assert findings[0].path.endswith("observability.md")


def test_metrics_schema_kind_mismatch(tmp_path):
    # documented as a counter, created as a gauge: both directions fire
    root = _write_tree(tmp_path, {
        "wire.py": _METRICS_CODE,
        "docs/observability.md": _METRICS_DOC.replace(
            "gauges.wire/inflight", "counters.wire/inflight"),
    })
    findings = run_lint(root, ["metrics-schema"])
    assert len(findings) == 2
    assert all("wire/inflight" in f.message for f in findings)


def test_metrics_schema_tracer_calls_ignored(tmp_path):
    root = _write_tree(tmp_path, {
        "wire.py": _METRICS_CODE + (
            "\n\ndef trace(tracer):\n"
            '    tracer.counter("bps:queue_depth", {})\n'),
        "docs/observability.md": _METRICS_DOC,
    })
    assert run_lint(root, ["metrics-schema"]) == []


# --------------------------------------------------------------------- #
# doc-paths
# --------------------------------------------------------------------- #

_DOC_TREE = {
    "byteps_tpu/jax/train.py": "x = 1\ny = 2\nz = 3\n",
    "byteps_tpu/native/ps.cc": "// wire\n",
    "benchmark/run.py": "pass\n",
    ".gitignore": "_scratch/\n*.pyc\n",
}

_DOC_GOOD = """
    Run `python3 benchmark/run.py --workload <cell>` and read
    `jax/train.py:2`, `byteps_tpu/jax/train.py` and `ps.cc`; a user's
    `python train.py` is theirs. The reference's
    `byteps/common/global.cc:42`, a placeholder `<dir>/0/comm.json`, a
    glob `*.py` and the git-ignored `_scratch/probe.py` are out of scope.
"""


def test_doc_paths_clean_fixture(tmp_path):
    root = _write_tree(tmp_path, {**_DOC_TREE, "README.md": _DOC_GOOD,
                                  "docs/guide.md": _DOC_GOOD})
    assert run_lint(root, ["doc-paths"]) == []


def test_doc_paths_stale_path(tmp_path):
    # THE drift class: the script went, the documents kept citing it:
    # by its bare name, inside a command, by a path from the root and by
    # one from the package
    root = _write_tree(tmp_path, {
        **_DOC_TREE,
        "README.md": "See `oldscript.py`.\n",
        "docs/guide.md": "Run `python oldscript.py --phase wire_ab`,\n"
                         "then `benchmark/gone.py` and `jax/gone.py`.\n"})
    findings = run_lint(root, ["doc-paths"])
    assert [(f.path, f.line) for f in findings] == [
        ("README.md", 1), (os.path.join("docs", "guide.md"), 1),
        (os.path.join("docs", "guide.md"), 2),
        (os.path.join("docs", "guide.md"), 2)]
    assert all("oldscript.py" in f.message for f in findings[:2])
    assert "benchmark/gone.py" in findings[2].message
    assert "jax/gone.py" in findings[3].message
    assert _rules_hit(findings) == {"doc-paths"}


def test_doc_paths_path_with_a_line_number(tmp_path):
    root = _write_tree(tmp_path, {
        **_DOC_TREE,
        "docs/guide.md": "`jax/train.py:3` and `ps.cc:584` hold,\n"
                         "`jax/gone.py:40` and `gone.cc:7` do not.\n"})
    findings = run_lint(root, ["doc-paths"])
    assert [f.line for f in findings] == [2, 2]
    assert "`jax/gone.py`" in findings[0].message
    assert "`gone.cc`" in findings[1].message


def test_doc_paths_suppression(tmp_path):
    root = _write_tree(tmp_path, {
        **_DOC_TREE,
        "docs/guide.md": "<!-- bps-lint: disable=doc-paths -->\n"
                         "PR 47 deleted `oldscript.py`.\n"
                         "\n"
                         "It is still `oldscript.py` here.\n"})
    findings = run_lint(root, ["doc-paths"])
    assert [f.line for f in findings] == [4]


_DOCUMENTS = ["README.md"] + sorted(
    os.path.join("docs", f) for f in os.listdir(os.path.join(REPO, "docs"))
    if f.endswith(".md"))


@pytest.fixture(scope="module")
def stale_citations():
    """document -> its findings, from ONE pass over the real tree."""
    by_document = {}
    for f in run_lint(REPO, ["doc-paths"]):
        by_document.setdefault(f.path, []).append(f.format())
    return by_document


@pytest.mark.parametrize("document", _DOCUMENTS)
def test_every_file_a_document_cites_exists(document, stale_citations):
    """One case a document of the real tree: what keeps a deleted script
    from living on in the documents (every one of them that cited the
    second benchmark's script failed here until PR 47 deleted it)."""
    assert stale_citations.get(document, []) == []


# --------------------------------------------------------------------- #
# CLI contract
# --------------------------------------------------------------------- #

def _run_cli(*args):
    return subprocess.run(
        [sys.executable, "-m", "byteps_tpu.tools.lint", *args],
        capture_output=True, text=True, cwd=REPO, timeout=120)


def test_cli_clean_exit_zero(tmp_path):
    root = _write_tree(tmp_path, {
        "native/ps.cc": _CC_GOOD,
        "server/client.py": _PY_MIRROR_GOOD,
    })
    proc = _run_cli("--root", root)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "byteps-lint: clean (6 rule(s) run)" in proc.stdout


def test_cli_findings_exit_one_and_format(tmp_path):
    root = _write_tree(tmp_path, {
        "native/ps.cc": _CC_GOOD,
        "server/client.py": _PY_MIRROR_GOOD.replace(
            "WIRE_HEADER_BYTES = 40", "WIRE_HEADER_BYTES = 36"),
    })
    proc = _run_cli("--root", root)
    assert proc.returncode == 1
    # pinned finding format: path:line: [rule] message
    assert re.search(
        r"^server[/\\]client\.py:\d+: \[wire-layout\] ", proc.stdout, re.M)
    assert re.search(r"byteps-lint: 1 finding\(s\)", proc.stdout)


def test_cli_unknown_rule_exit_two(tmp_path):
    proc = _run_cli("--root", str(tmp_path), "--rules", "nonsense")
    assert proc.returncode == 2
    assert "unknown rule" in proc.stderr


def test_cli_list_names_all_rules():
    proc = _run_cli("--list")
    assert proc.returncode == 0
    for rule in ("wire-layout", "guarded-by", "device-thread",
                 "env-sync", "metrics-schema", "doc-paths"):
        assert rule in proc.stdout


# --------------------------------------------------------------------- #
# the real repo
# --------------------------------------------------------------------- #

def test_rule_registry_has_at_least_five_rules():
    assert len(all_rules()) >= 5
    assert len({r.name for r in all_rules()}) == len(all_rules())


def test_real_repo_is_clean_and_fast():
    """THE gate: every invariant rule passes over the live tree, and
    the full pass stays well under the 30 s budget that keeps it
    viable inside tier-1 and ci/checks.sh."""
    t0 = time.perf_counter()
    findings = run_lint(REPO)
    elapsed = time.perf_counter() - t0
    assert findings == [], "\n".join(f.format() for f in findings)
    assert elapsed < 30.0, f"full-repo lint took {elapsed:.1f}s"


def test_real_repo_guarded_by_is_not_vacuous():
    """The lock-discipline rule only means something if the hot-path
    classes actually carry annotations — a refactor that drops them
    all would silently disarm the rule."""
    from byteps_tpu.tools.lint.base import Project
    from byteps_tpu.tools.lint.locks import _class_annotations

    project = Project(REPO)
    annotated = {}
    for path in project.py_files():
        tree = project.tree(path)
        if tree is None:
            continue
        for cls, attrs in _class_annotations(project, path, tree,
                                             []).items():
            annotated[cls] = annotated.get(cls, 0) + len(attrs)
    for cls in ("ScheduledQueue", "PipelineScheduler", "TensorRegistry",
                "MetricsRegistry", "PSClient", "CodecPlane"):
        assert annotated.get(cls), f"{cls} lost its guarded-by annotations"
