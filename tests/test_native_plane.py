"""Native data-plane tests: SIMD fold parity, zero-copy recv tiers,
per-stage server stats, and byte-balanced engine placement.

The wire-rate rebuild of ``native/ps.cc`` added three things this file
pins down:

- **SIMD fold** (``BYTEPS_SIMD``): runtime-dispatched AVX-512/AVX2
  accumulate kernels whose contract is BITWISE identity with the scalar
  loops — fp32 elementwise, bf16 widen-fold-narrow. Checked both at the
  kernel level (``bps_fold_probe`` over odd lengths and
  NaN/inf/subnormal payloads) and end-to-end (a scalar-forced server vs
  an auto server must publish identical aggregates for dense fp32/bf16,
  rowsparse and fused traffic).
- **Zero-copy recv tiers**: TCP/ring payloads land straight in the
  key's reserved buffer (``direct_recvs``), shm payloads >= 64KB ride
  the descriptor ring and are folded in place from the shared arena
  (``oob_msgs``), and single-worker fused dense replies come back as an
  8-byte echo descriptor instead of a payload copy.
- **Stage stats + engine balance**: recv/queue/fold/reply accounting
  over the C ABI, and key->engine placement driven by CUMULATIVE folded
  bytes (the old assignment-time-only accounting tied on equal init
  lengths and could co-locate a new heavy key with the hot engine).
"""

import ctypes
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from byteps_tpu.config import Config
from byteps_tpu.core.registry import TensorRegistry
from byteps_tpu.core.types import DataType, RequestType, get_command_type
from byteps_tpu.native.build import build
from byteps_tpu.server import engine_stats, run_server, stage_stats
from byteps_tpu.server.client import PSClient

from test_ps import start_servers

CMD_F32 = get_command_type(RequestType.DEFAULT_PUSH_PULL, DataType.FLOAT32)
CMD_BF16 = get_command_type(RequestType.DEFAULT_PUSH_PULL,
                            DataType.BFLOAT16)


def _lib():
    lib = ctypes.CDLL(build())
    lib.bps_simd_best.restype = ctypes.c_int
    lib.bps_fold_probe.restype = ctypes.c_int
    lib.bps_fold_probe.argtypes = [
        ctypes.c_int, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_uint64,
        ctypes.c_int]
    return lib


def _bf16(x: np.ndarray) -> np.ndarray:
    """Truncate f32 -> bf16 bit patterns (test inputs only)."""
    return (np.ascontiguousarray(x, np.float32).view(np.uint32)
            >> 16).astype(np.uint16)


# --------------------------------------------------------------------- #
# kernel-level parity (bps_fold_probe)
# --------------------------------------------------------------------- #


def _special_f32(n: int, seed: int) -> np.ndarray:
    x = np.random.RandomState(seed).randn(n).astype(np.float32)
    if n >= 8:
        x[0] = np.nan
        x[1] = np.inf
        x[2] = -np.inf
        x[3] = np.float32(1e-42)   # subnormal
        x[4] = np.float32(-0.0)
        x[5] = np.float32(3.4e38)  # near-max: exercises overflow rounding
    return x


@pytest.mark.parametrize("tier", [2, 3])
@pytest.mark.parametrize("n", [1, 7, 8, 15, 16, 17, 31, 33, 255, 4097,
                               100003])
def test_fold_parity_f32(tier, n):
    lib = _lib()
    if lib.bps_simd_best() < tier:
        pytest.skip(f"tier {tier} unsupported on this host/build")
    a = _special_f32(n, seed=n)
    b = _special_f32(n, seed=n + 1)
    d_s, d_v = a.copy(), a.copy()
    assert lib.bps_fold_probe(0, d_s.ctypes.data, b.ctypes.data,
                              n * 4, 0) == 0
    assert lib.bps_fold_probe(0, d_v.ctypes.data, b.ctypes.data,
                              n * 4, tier) == tier
    # BITWISE: NaN payloads compare equal as uint32, never as float
    np.testing.assert_array_equal(d_s.view(np.uint32),
                                  d_v.view(np.uint32))


@pytest.mark.parametrize("tier", [2, 3])
@pytest.mark.parametrize("n", [1, 7, 15, 16, 17, 31, 32, 33, 255, 4097,
                               65537])
def test_fold_parity_bf16(tier, n):
    lib = _lib()
    if lib.bps_simd_best() < tier:
        pytest.skip(f"tier {tier} unsupported on this host/build")
    rng = np.random.RandomState(n)
    a = _bf16(rng.randn(n) * 8)
    b = _bf16(rng.randn(n) * 8)
    if n >= 8:
        # quiet/signaling NaN patterns, +-inf, subnormal, -0.0: the
        # widen-fold-narrow kernels must reproduce float_to_bf16's NaN
        # quieting and round-to-nearest-even EXACTLY
        a[0] = 0x7FC0
        a[1] = 0x7F81
        a[2] = 0x7F80
        a[3] = 0xFF80
        a[4] = 0x0001
        a[5] = 0x8000
        b[0] = 0x0001
        b[2] = 0xFF80  # inf + -inf -> NaN, both arms identically
    d_s, d_v = a.copy(), a.copy()
    assert lib.bps_fold_probe(7, d_s.ctypes.data, b.ctypes.data,
                              n * 2, 0) == 0
    assert lib.bps_fold_probe(7, d_v.ctypes.data, b.ctypes.data,
                              n * 2, tier) == tier
    np.testing.assert_array_equal(d_s, d_v)


def test_fold_probe_rejects_unsupported_tier():
    lib = _lib()
    x = np.zeros(8, np.float32)
    # 99 is no tier; must refuse rather than silently run some kernel
    assert lib.bps_fold_probe(0, x.ctypes.data, x.ctypes.data, 32,
                              99) == -1


# --------------------------------------------------------------------- #
# end-to-end SIMD-vs-scalar parity (dense/bf16/rowsparse/fused)
# --------------------------------------------------------------------- #


def _two_worker_aggregates(monkeypatch, simd: str) -> dict:
    """Run a 2-worker aggregation round over every fold path against a
    fresh server under BYTEPS_SIMD=``simd``; returns the pulled
    aggregate bytes per path."""
    monkeypatch.setenv("BYTEPS_SIMD", simd)
    addrs, threads = start_servers(1, num_workers=2)
    cs = [PSClient(addrs, worker_id=w) for w in range(2)]
    rng = np.random.RandomState(7)
    out: dict = {}

    dense = [_special_f32(3001, seed=w) for w in range(2)]
    big = [rng.randn(32768).astype(np.float32) for _ in range(2)]  # OOB
    bf = [_bf16(rng.randn(4097) * 4) for _ in range(2)]
    fused = [rng.randn(8193).astype(np.float32) for _ in range(2)]

    regs = [TensorRegistry(Config(num_workers=2, num_servers=1))
            for _ in range(2)]
    rs_ctx = [r.init_tensor("rs", 64 * 32 * 4, DataType.FLOAT32,
                            align_bytes=32 * 4) for r in regs]
    rs_grad = np.zeros((64, 32), np.float32)
    rs_grad[5] = 1.5
    rs_grad[40] = -2.25

    def init_all(w):
        c = cs[w]
        c.init_key(0, 1, np.zeros_like(dense[0]), CMD_F32)
        c.init_key(0, 2, np.zeros_like(big[0]), CMD_F32)
        c.init_key(0, 3, np.zeros(4097, np.uint16), CMD_BF16)
        c.init_key(0, 4, np.zeros_like(fused[0]), CMD_F32)

    its = [threading.Thread(target=init_all, args=(w,)) for w in range(2)]
    for t in its:
        t.start()
    for t in its:
        t.join(60)

    res = [dict() for _ in range(2)]

    def rounds(w):
        c = cs[w]
        for key, arr, cmd, out_dt in ((1, dense[w], CMD_F32, np.float32),
                                      (2, big[w], CMD_F32, np.float32),
                                      (3, bf[w], CMD_BF16, np.uint16)):
            c.zpush(0, key, arr, cmd)
            buf = np.empty(arr.shape, out_dt)
            c.zpull(0, key, buf, cmd, exact=True)
            res[w][key] = buf
        done = threading.Event()
        fout = np.empty(fused[w].nbytes, np.uint8)
        c.zpushpull_async(0, 4, fused[w], fout, CMD_F32,
                          lambda n, err, d=done: d.set())
        assert done.wait(60), "fused completion never fired"
        res[w][4] = fout.copy()
        res[w]["rs"] = c.push_pull_rowsparse(rs_ctx[w], rs_grad,
                                            average=False)

    ts = [threading.Thread(target=rounds, args=(w,)) for w in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(120)
    for key in (1, 2, 3, 4, "rs"):
        np.testing.assert_array_equal(
            np.asarray(res[0][key]).view(np.uint8),
            np.asarray(res[1][key]).view(np.uint8))
        out[key] = np.asarray(res[0][key]).tobytes()
    # the arm ran the kernels it names: a "scalar" arm on a vector tier
    # would make the parity below vacuous
    tier = cs[0].server_stats(0)["simd_tier"]
    assert (tier == 0) == (simd == "scalar"), (simd, tier)
    for c in cs:
        c.close()
    for t in threads:
        t.join(timeout=10)
    return out


def test_server_simd_vs_scalar_bitwise(monkeypatch):
    """The whole server fold surface — dense fp32 (specials included),
    an OOB-sized dense key, bf16, the fused PUSHPULL path and the
    rowsparse row folds — must publish BITWISE identical aggregates
    whether the fold runs vectorized or scalar."""
    lib = _lib()
    if lib.bps_simd_best() == 0:
        pytest.skip("no SIMD tier on this host/build")
    scalar = _two_worker_aggregates(monkeypatch, "scalar")
    simd = _two_worker_aggregates(monkeypatch, "auto")
    assert set(scalar) == set(simd)
    for key in scalar:
        assert scalar[key] == simd[key], f"path {key!r} diverged"


# --------------------------------------------------------------------- #
# zero-copy recv tiers + stage stats
# --------------------------------------------------------------------- #


def test_direct_recv_tier_engages_on_tcp(monkeypatch):
    """Dense steady-state pushes over TCP land straight in the key's
    reserved buffer (the recv-into-accumulator tier): direct_recvs
    advances and numerics are unchanged."""
    monkeypatch.setenv("BYTEPS_ENABLE_IPC", "0")
    before = stage_stats()["direct_recvs"]
    addrs, threads = start_servers(1, num_workers=1)
    c = PSClient(addrs, worker_id=0)
    assert c.ipc_conns == 0
    x = np.arange(4096, dtype=np.float32)
    c.init_key(0, 5, np.zeros_like(x), CMD_F32)
    out = np.empty_like(x)
    for _ in range(4):
        c.zpush(0, 5, x, CMD_F32)
        c.zpull(0, 5, out, CMD_F32, exact=True)
    np.testing.assert_array_equal(out, x)
    # the init push creates the store; every steady push after it is
    # direct-eligible (sync, dense, matching length)
    assert stage_stats()["direct_recvs"] - before >= 4
    c.close()
    for t in threads:
        t.join(timeout=10)


def test_oob_descriptor_tier_and_echo(monkeypatch):
    """Payloads >= 64KB over the shm transport ride the descriptor
    ring: the server folds them in place from the arena (oob_msgs), and
    the single-worker fused dense reply comes back as an echo
    descriptor (client oob_recvd advances, bytes exact)."""
    before = stage_stats()["oob_msgs"]  # leaked-server history cancels
    addrs, threads = start_servers(1, num_workers=1)
    c = PSClient(addrs, worker_id=0)
    assert c.ipc_conns > 0
    x = np.random.RandomState(3).randn(1 << 16).astype(np.float32)  # 256KB
    c.init_key(0, 6, np.zeros_like(x), CMD_F32)
    done = threading.Event()
    out = np.empty(x.nbytes, np.uint8)
    c.zpushpull_async(0, 6, x, out, CMD_F32,
                      lambda n, err, d=done: d.set())
    assert done.wait(60)
    np.testing.assert_array_equal(out.view(np.float32), x)
    st = c.transport_stats()
    assert st["oob_sent"] >= 1, st   # push rode the descriptor tier
    assert st["oob_recvd"] >= 1, st  # reply came back as a descriptor
    assert stage_stats()["oob_msgs"] - before >= 1
    # blocking pull of the same key: served from pub, still exact
    out2 = np.empty_like(x)
    c.zpull(0, 6, out2, CMD_F32, exact=True)
    np.testing.assert_array_equal(out2, x)
    c.close()
    for t in threads:
        t.join(timeout=10)


def test_oob_arena_wrap_and_reclaim(monkeypatch):
    """A tiny arena forces the block ring to wrap and reclaim many
    times over a burst of descriptor-tier messages; every round trip
    stays exact (the version-fence: blocks are immutable until the
    consumer releases, retries allocate fresh)."""
    monkeypatch.setenv("BYTEPS_IPC_ARENA_BYTES", str(256 << 10))
    addrs, threads = start_servers(1, num_workers=1)
    c = PSClient(addrs, worker_id=0)
    assert c.ipc_conns > 0
    rng = np.random.RandomState(11)
    n = 24 * 1024  # 96KB: descriptor-tier, several blocks per arena lap
    c.init_key(0, 8, np.zeros(n, np.float32), CMD_F32)
    out = np.empty(n, np.float32)
    for i in range(20):
        x = rng.randn(n).astype(np.float32)
        c.zpush(0, 8, x, CMD_F32)
        c.zpull(0, 8, out, CMD_F32, exact=True)
        np.testing.assert_array_equal(out, x)
    assert c.transport_stats()["oob_sent"] >= 20
    c.close()
    for t in threads:
        t.join(timeout=10)


def test_stage_stats_live_and_accounted():
    """The per-stage counters move with traffic and fold_bytes accounts
    exactly the payload bytes folded.
    Delta-based throughout: in the full suite, earlier test files leave
    daemon server threads parked in bps_server_run forever, so the
    aggregate registry is never empty — but those stragglers have no
    clients left, so their counters are static and cancel in deltas."""
    before = stage_stats()
    addrs, threads = start_servers(1, num_workers=1)
    c = PSClient(addrs, worker_id=0)
    x = np.ones(2048, np.float32)
    c.init_key(0, 9, np.zeros_like(x), CMD_F32)
    out = np.empty_like(x)
    for _ in range(5):
        c.zpush(0, 9, x, CMD_F32)
        c.zpull(0, 9, out, CMD_F32, exact=True)
    after = stage_stats()
    assert after["fold_count"] - before["fold_count"] == 5
    assert after["fold_bytes"] - before["fold_bytes"] == 5 * x.nbytes
    assert after["queue_count"] > before["queue_count"]
    assert after["reply_count"] - before["reply_count"] >= 5
    assert after["live"] > before["live"]
    assert after["engine_threads"] >= 1
    c.close()
    for t in threads:
        t.join(timeout=10)


# --------------------------------------------------------------------- #
# byte-balanced engine placement
# --------------------------------------------------------------------- #


def test_engine_placement_balances_by_cumulative_bytes():
    """The one-hot pathology: equal-sized keys fill the engines, ONE of
    them then carries almost all the traffic, and a new heavy key
    arrives. With assignment-time-only accounting every engine looked
    equally loaded (the init lengths tied), so the newcomer landed on
    the first — the same engine as the hot key — and the two serialized
    on one thread. Placement by cumulative queued bytes must put the
    newcomer elsewhere: the hot engine's byte counter stays flat while
    another engine absorbs the new key's traffic."""
    # earlier test files leak daemon servers that stay registered for
    # the whole session; OUR server is the row appended after this point
    idx = len(engine_stats())
    addrs, threads = start_servers(1, num_workers=1)  # 4 engine threads
    c = PSClient(addrs, worker_id=0)
    tiny = np.ones(256, np.float32)  # 1KB
    for key in range(4):
        c.init_key(0, key, np.zeros_like(tiny), CMD_F32)
    out = np.empty_like(tiny)
    # one-hot traffic: key 0 carries ~300x the bytes of its peers
    for _ in range(300):
        c.zpush(0, 0, tiny, CMD_F32)
        c.zpull(0, 0, out, CMD_F32)
    snap = engine_stats()
    assert len(snap) > idx and len(snap[idx]) >= 2, (idx, snap)
    hot = int(np.argmax(snap[idx]))
    hot_before = snap[idx][hot]
    # the newcomer: a heavy key, init + traffic
    big = np.ones(1 << 18, np.float32)  # 1MB
    c.init_key(0, 99, np.zeros_like(big), CMD_F32)
    bout = np.empty_like(big)
    for _ in range(3):
        c.zpush(0, 99, big, CMD_F32)
        c.zpull(0, 99, bout, CMD_F32)
    hot_after = engine_stats()[idx][hot]
    # the hot engine must NOT have absorbed the ~4MB of new-key traffic
    assert hot_after - hot_before < big.nbytes, (
        f"new heavy key landed on the hot engine "
        f"({hot_after - hot_before} bytes grew on engine {hot})")
    c.close()
    for t in threads:
        t.join(timeout=10)


# --------------------------------------------------------------------- #
# shm descriptor tier under the PR 6 chaos knobs
# --------------------------------------------------------------------- #

# Subprocess (the native client timeout is latched per process and the
# chaos knobs are read per server instance): descriptor-tier payloads
# with forced reply drops + injected delay. A dropped reply means the
# scheduler replays the push with its idempotent epoch stamp — for the
# OOB tier that is a FRESH arena block while the server may still hold
# (or have echoed) the previous one, so the block release/reclaim
# machinery and the replay dedup race exactly where the zero-copy fast
# path lives. Aggregates must stay bitwise exact throughout.
_SHM_CHAOS_SCRIPT = r"""
import os, sys, threading
sys.path.insert(0, os.environ["BPS_REPO"])
import numpy as np
from byteps_tpu.config import Config
from byteps_tpu.server import run_server
from byteps_tpu.utils.net import free_port

port = free_port()
os.environ.update({
    "DMLC_NUM_WORKER": "1", "DMLC_NUM_SERVER": "1",
    "DMLC_PS_ROOT_URI": "127.0.0.1", "DMLC_PS_ROOT_PORT": str(port),
    "BYTEPS_FORCE_DISTRIBUTED": "1",
})
server = threading.Thread(
    target=run_server, args=(port, Config(num_workers=1, num_servers=1)),
    daemon=True)
server.start()
import byteps_tpu as bps
bps.init()
rng = np.random.RandomState(5)
# 128KB per tensor: descriptor-tier (>= 64KB), several blocks live at
# once across the 4 in-flight keys inside the small arena
grads = [rng.randn(32768).astype(np.float32) for _ in range(4)]
for r in range(4):
    hs = [bps.push_pull_async(g * (r + 1), f"big{i}", average=False)
          for i, g in enumerate(grads)]
    for h, g in zip(hs, grads):
        out = bps.synchronize(h, timeout=60)
        assert np.array_equal(out, g * (r + 1)), (r, "oob double-fold?")
snap = bps.get_metrics()
retries = int(snap["counters"].get("wire/retries", 0))
oob = int(snap["server"]["oob_msgs"])
assert retries > 0, "chaos produced no retries - knob dead?"
assert oob > 0, "no descriptor-tier traffic - shm fast path not engaged?"
# flight recorder (PR 12): the server ring holds the chaos injections,
# the worker ring the retries they forced — key-matched and in causal
# order (server thread shares this process's steady clock, so the
# timestamps compare directly: a drop must precede some retry)
from byteps_tpu.core import flight as flight_mod
from byteps_tpu.core.state import get_state
state = get_state()
drops = [e for e in state.ps_client.drain_flight(0)
         if e["kind"] == "chaos_drop"]
assert drops, "server flight ring recorded no chaos_drop events"
wevs = flight_mod.get_recorder().events()
retry_evs = [e for e in wevs if e["kind"] == "wire_retry"]
assert retry_evs, "worker flight ring recorded no wire_retry events"
wts = [e["ts_ns"] for e in wevs]
assert wts == sorted(wts), "worker flight events out of causal order"
assert min(d["ts_ns"] for d in drops) < max(r["ts_ns"] for r in retry_evs), \
    "no chaos drop precedes any retry - causality broken?"
# rid/key-matched: the dropped replies name partition keys the worker
# actually retried
drop_keys = {d["key"] for d in drops if d["key"]}
retry_keys = {r["key"] for r in retry_evs}
assert drop_keys & retry_keys, (drop_keys, retry_keys)
bps.shutdown()
server.join(timeout=15)
print("SHM_CHAOS_OK retries=", retries, "oob=", oob,
      "drops=", len(drops), "flight_retries=", len(retry_evs))
"""


@pytest.mark.chaos
def test_shm_oob_round_trip_under_chaos():
    """Zero-copy shm large-message round trip under the PR 6 chaos
    knobs: 30% dropped replies (echo descriptors included) + 2ms
    injected delay over a deliberately tiny arena (forces wrap +
    reclaim while replays are in flight). Every aggregate bitwise
    exact, with the descriptor tier proven engaged."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ,
           "BPS_REPO": repo,
           "BYTEPS_CLIENT_TIMEOUT_S": "2",
           "BYTEPS_WIRE_RETRY": "5",
           "BYTEPS_WIRE_BACKOFF_MS": "25",
           "BYTEPS_CHAOS_DROP_REPLY_RATE": "0.3",
           "BYTEPS_CHAOS_DELAY_MS": "2",
           "BYTEPS_IPC_ARENA_BYTES": str(1 << 20),
           "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run([sys.executable, "-c", _SHM_CHAOS_SCRIPT],
                          env=env, capture_output=True, text=True,
                          timeout=240)
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out[-4000:]
    assert "SHM_CHAOS_OK" in out, out[-4000:]
