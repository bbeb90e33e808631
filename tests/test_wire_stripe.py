"""Striped cross-host wire plane (PR 17): connection striping, batched
submission rings, decompress-on-the-fabric.

The BYTEPS_WIRE_STRIPES / BYTEPS_WIRE_RING / BYTEPS_STRIPE_CHUNK_BYTES
knobs are latched per process in the native lib, so the parity matrix
runs each arm in a fresh subprocess over REAL loopback TCP
(BYTEPS_ENABLE_IPC=0 — the shm descriptor tier would bypass the wire
entirely) and compares result hashes across arms:

- bitwise parity stripes on/off across dense fused-PUSHPULL (striped),
  two-worker fused aggregation, bf16, rowsparse and lossless traffic;
- out-of-order reassembly: a 8 KB stripe chunk splits each 1 MB
  payload into ~128 segments interleaved over 4 TCP conns, so segment
  arrival order at the server is scheduler-dependent — the per-(sender,
  key) seq gate must still deliver rounds in order;
- short-write recovery: BYTEPS_SOCK_BUF_BYTES=64 KB (the clamp floor)
  forces partial sendmsg() completions on every multi-segment batch;
- replay-epoch dedup: a retried fused round (same round, bumped
  attempt) is answered from the aggregate, never re-folded;
- single-stripe death: killing one data conn degrades stripe width,
  not the request — the group only dies when all striped conns die;
- fused decode A/B: BYTEPS_FUSED_DECODE on/off is bitwise identical
  for the lossless tier (decode-into-accumulator vs decode-then-fold),
  proven by the `fused_decode_folds` stage counter.
"""

import hashlib
import json
import os
import subprocess
import sys
import threading

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# One full traffic battery in a child process; prints result hashes +
# wire counters as JSON so the parent can diff arms bitwise without
# shipping arrays across the pipe.
_BATTERY = r"""
import hashlib, json, os, sys, threading
sys.path.insert(0, os.environ["BPS_REPO"])
import numpy as np
from byteps_tpu.config import Config
from byteps_tpu.core.registry import TensorRegistry
from byteps_tpu.core.types import DataType, RequestType, get_command_type
from byteps_tpu.server import run_server, stage_stats
from byteps_tpu.server.client import PSClient
from byteps_tpu.server.compressed import CompressedTensor
from byteps_tpu.utils.net import free_port, wait_port

port = free_port()
cfg = Config(num_workers=2, num_servers=1)
server = threading.Thread(target=run_server, args=(port, cfg), daemon=True)
server.start()
wait_port(port)
addr = [f"127.0.0.1:{port}"]
c0 = PSClient(addr, worker_id=0)
c1 = PSClient(addr, worker_id=1)
CMD = get_command_type(RequestType.DEFAULT_PUSH_PULL, DataType.FLOAT32)
res = {}

def fused(c, key, x, out, epoch):
    done = threading.Event(); err = [None]
    def cb(n, e):
        err[0] = e; done.set()
    c.zpushpull_async(0, key, x, out, CMD, cb, epoch=epoch)
    assert done.wait(120), "fused pushpull timed out"
    if err[0]:
        raise err[0]

rng = np.random.RandomState(11)
n = 262144  # 1 MB payload: ~128 segments at the 8 KB test chunk
x0 = rng.randn(n).astype(np.float32)

def init_both(key, zero, cmd):
    # the init push is the per-key init barrier: both workers must be
    # in it at once or the first blocks forever
    t = threading.Thread(target=c1.init_key, args=(0, key, zero, cmd))
    t.start()
    c0.init_key(0, key, zero, cmd)
    t.join(timeout=60)
    assert not t.is_alive(), "init barrier wedged"

# --- dense fused PUSHPULL, 3 rounds (2 workers; both must fold for
# ALL_RECV, f32 a+b is commutative so the sum is order-independent) ---
z = np.zeros_like(x0)
init_both(5, z, CMD)
acc = hashlib.sha256()
for r in range(1, 4):
    xa = (x0 * r).astype(np.float32)
    xb = (x0 + r).astype(np.float32)
    oa, ob = np.empty_like(xa), np.empty_like(xb)
    tb = threading.Thread(target=fused, args=(c1, 5, xb, ob, r << 16))
    tb.start()
    fused(c0, 5, xa, oa, r << 16)
    tb.join(timeout=120)
    want = xa + xb
    assert np.array_equal(oa, want), f"dense round {r} w0 parity"
    assert np.array_equal(ob, want), f"dense round {r} w1 parity"
    acc.update(oa.tobytes())
res["dense"] = acc.hexdigest()

# --- replay-epoch dedup across stripes: retry of round 4 (attempt 1)
# must answer from the aggregate, never double-fold ---
xa = (x0 * 4).astype(np.float32)
xb = (x0 + 4).astype(np.float32)
oa, ob = np.empty_like(xa), np.empty_like(xb)
tb = threading.Thread(target=fused, args=(c1, 5, xb, ob, 4 << 16))
tb.start()
fused(c0, 5, xa, oa, 4 << 16)
tb.join(timeout=120)
o2 = np.empty_like(xa)
fused(c0, 5, xa, o2, (4 << 16) | 1)  # replayed attempt
want = xa + xb
assert np.array_equal(oa, want) and np.array_equal(o2, want), \
    "replayed striped round double-counted"
res["replay"] = hashlib.sha256(o2.tobytes()).hexdigest()

# --- bf16 two-op (regression guard: the multi-conn group must not
# disturb non-striped traffic) ---
import ml_dtypes
CMD_BF = get_command_type(RequestType.DEFAULT_PUSH_PULL,
                          DataType.BFLOAT16)
xh = (rng.randn(65536) * 100).astype(ml_dtypes.bfloat16)
zb = np.zeros_like(xh)
init_both(6, zb, CMD_BF)
c0.zpush(0, 6, xh, CMD_BF)
c1.zpush(0, 6, xh, CMD_BF)
ob = np.empty_like(xh)
c0.zpull(0, 6, ob, CMD_BF)
want_bf = (xh.astype(np.float32) * 2).astype(ml_dtypes.bfloat16)
assert ob.tobytes() == want_bf.tobytes(), "bf16 parity"
res["bf16"] = hashlib.sha256(ob.tobytes()).hexdigest()

# --- rowsparse (two-op wire) ---
reg = TensorRegistry(cfg)
W, R = 64, 512
ctx = reg.init_tensor("emb", R * W * 4, DataType.FLOAT32,
                      align_bytes=W * 4)
g = np.zeros((R, W), np.float32)
idx = rng.choice(R, 40, replace=False)
g[idx] = rng.randn(40, W)

def rs(c, out):
    out.append(c.push_pull_rowsparse(ctx, g, average=False))

r1 = []
tb = threading.Thread(target=rs, args=(c1, r1))
tb.start()
o_rs = c0.push_pull_rowsparse(ctx, g, average=False)
tb.join(timeout=120)
assert np.array_equal(o_rs, g * 2), "rowsparse parity"
res["rowsparse"] = hashlib.sha256(np.ascontiguousarray(o_rs)
                                  .tobytes()).hexdigest()

# --- lossless codec (DoPushCompressed: fused decode-into-fold when
# BYTEPS_FUSED_DECODE=1, the default) ---
nl = 131072
ctx_l = reg.init_tensor("lz", nl * 4, DataType.FLOAT32)
ct0 = CompressedTensor(c0, ctx_l, {"compressor": "lossless"}, 2)
ct1 = CompressedTensor(c1, ctx_l, {"compressor": "lossless"}, 2)
xl = rng.randn(nl).astype(np.float32)
xl[:4] = [np.float32("nan"), np.float32("inf"), -0.0, 1e-42]
r2 = []
tb = threading.Thread(
    target=lambda: r2.append(ct1.push_pull(xl, average=False)))
tb.start()
o_l = ct0.push_pull(xl, average=False)
tb.join(timeout=120)
want_l = xl + xl
assert np.asarray(o_l).tobytes() == want_l.tobytes(), "lossless parity"
res["lossless"] = hashlib.sha256(np.asarray(o_l).tobytes()).hexdigest()

# --- wire counters: the proof surface the parent asserts on ---
st = stage_stats()
res["stats"] = {k: int(st[k]) for k in (
    "stripe_segs", "stripe_bytes", "tx_batches", "tx_msgs",
    "rx_batches", "rx_msgs", "fused_decode_folds", "reg_blocks",
    "reg_miss")}
res["transport"] = c0.transport_stats()
res["transport1"] = c1.transport_stats()
res["conn_bytes"] = c0.stripe_conn_bytes(0)
res["conn_bytes1"] = c1.stripe_conn_bytes(0)
c0.close()
c1.close()
server.join(timeout=20)
print("BATTERY " + json.dumps(res))
"""

# Single-stripe death: kill one data conn between rounds; the striper
# must drop it from the live set and complete on the survivors.
_DEATH = r"""
import json, os, sys, threading, time
sys.path.insert(0, os.environ["BPS_REPO"])
import numpy as np
from byteps_tpu.config import Config
from byteps_tpu.core.types import DataType, RequestType, get_command_type
from byteps_tpu.server import run_server
from byteps_tpu.server.client import PSClient
from byteps_tpu.utils.net import free_port, wait_port

port = free_port()
cfg = Config(num_workers=1, num_servers=1)
server = threading.Thread(target=run_server, args=(port, cfg), daemon=True)
server.start()
wait_port(port)
c = PSClient([f"127.0.0.1:{port}"], worker_id=0)
CMD = get_command_type(RequestType.DEFAULT_PUSH_PULL, DataType.FLOAT32)

def fused(key, x, out, epoch):
    done = threading.Event(); err = [None]
    def cb(n, e):
        err[0] = e; done.set()
    c.zpushpull_async(0, key, x, out, CMD, cb, epoch=epoch)
    assert done.wait(120), "fused pushpull timed out"
    if err[0]:
        raise err[0]

rng = np.random.RandomState(3)
x = rng.randn(262144).astype(np.float32)
c.init_key(0, 9, np.zeros_like(x), CMD)
out = np.empty_like(x)
fused(9, x, out, 1 << 16)
assert np.array_equal(out, x), "pre-kill parity"
segs_before = c.transport_stats()["stripe_segs"]
assert segs_before > 0, "striper never engaged before the kill"

# kill a NON-control data conn (conn 0 is the control lane) and let
# the server's conn loop observe the close (StripeReset, gate resync)
assert c.kill_stripe(0, 2), "kill hook failed"
time.sleep(0.3)

for r in range(2, 5):
    xr = (x * r).astype(np.float32)
    fused(9, xr, out, r << 16)
    assert np.array_equal(out, xr), f"post-kill round {r} parity"
segs_after = c.transport_stats()["stripe_segs"]
assert segs_after > segs_before, "post-kill rounds stopped striping"
# control lane survived the data-conn death
assert c.server_stats(0) is not None, "control lane died with the stripe"
c.close()
server.join(timeout=20)
print("DEATH_OK " + json.dumps({"segs": segs_after}))
"""


def _run_child(script, extra_env, timeout=300):
    env = {
        **os.environ,
        "BPS_REPO": REPO,
        "JAX_PLATFORMS": "cpu",
        "BYTEPS_ENABLE_IPC": "0",  # real TCP or the wire is bypassed
        **extra_env,
    }
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=timeout)
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out[-4000:]
    return out


def _battery(extra_env):
    out = _run_child(_BATTERY, extra_env)
    line = [ln for ln in out.splitlines() if ln.startswith("BATTERY ")]
    assert line, out[-4000:]
    return json.loads(line[-1][len("BATTERY "):])


_STRIPED_ENV = {
    "BYTEPS_WIRE_STRIPES": "4",
    "BYTEPS_STRIPE_CHUNK_BYTES": "8192",   # ~128 segs per 1MB payload
    "BYTEPS_SOCK_BUF_BYTES": "65536",      # clamp floor: short writes
}

_LEGS = ("dense", "replay", "bf16", "rowsparse", "lossless")


def test_stripe_parity_matrix():
    """Bitwise parity stripes on vs off across the traffic matrix, with
    out-of-order reassembly (8 KB chunks over 4 conns) and short-write
    recovery (64 KB socket buffers) riding the striped arm — plus the
    counter proofs that the striped arm actually striped and the
    control arm actually didn't."""
    striped = _battery(_STRIPED_ENV)
    plain = _battery({"BYTEPS_WIRE_STRIPES": "1"})

    for leg in _LEGS:
        assert striped[leg] == plain[leg], \
            f"stripes on/off disagree bitwise on the {leg} leg"

    # striped arm: the wire actually striped, and conservation holds —
    # client payload bytes + 72 B/segment framing == per-conn TX sums,
    # and the server reassembled every segment the clients sent
    for w in ("transport", "transport1"):
        t = striped[w]
        assert t["stripe_segs"] > 0, f"{w}: striper never engaged"
        conn = striped["conn_bytes" if w == "transport" else
                       "conn_bytes1"]
        assert conn[0] == 0, "control lane carried stripe traffic"
        assert sum(conn) == t["stripe_bytes"] + 72 * t["stripe_segs"], \
            "per-conn TX ledger violates byte conservation"
    sent_segs = (striped["transport"]["stripe_segs"]
                 + striped["transport1"]["stripe_segs"])
    sent_bytes = (striped["transport"]["stripe_bytes"]
                  + striped["transport1"]["stripe_bytes"])
    assert striped["stats"]["stripe_segs"] == sent_segs
    assert striped["stats"]["stripe_bytes"] == sent_bytes
    # ring + fused-decode instruments live on the striped arm
    s = striped["stats"]
    assert s["tx_batches"] > 0 and s["tx_msgs"] >= s["tx_batches"]
    assert s["rx_batches"] > 0 and s["rx_msgs"] > 0
    assert s["fused_decode_folds"] > 0, \
        "lossless folds never took the fused decode path"
    assert s["reg_blocks"] > 0, "no transport-registered blocks"

    # control arm: a 1-stripe group must never emit segments
    assert plain["transport"]["stripe_segs"] == 0
    assert plain["transport1"]["stripe_segs"] == 0
    assert plain["stats"]["stripe_segs"] == 0


def test_single_stripe_death_fails_over():
    """Killing one data conn mid-run degrades stripe width, never the
    request: later rounds still stripe over the survivors bitwise, and
    the control lane stays answerable."""
    out = _run_child(_DEATH, _STRIPED_ENV, timeout=240)
    assert "DEATH_OK" in out, out[-4000:]


def test_wire_ring_off_parity():
    """BYTEPS_WIRE_RING=0 (per-message blocking send/recv, the legacy
    wire) is bitwise identical to the batched default."""
    ringless = _battery({**_STRIPED_ENV, "BYTEPS_WIRE_RING": "0"})
    striped = _battery(_STRIPED_ENV)
    for leg in _LEGS:
        assert ringless[leg] == striped[leg], \
            f"wire ring on/off disagree bitwise on the {leg} leg"
    # the ring-off arm must not count ring batches on the rx side
    assert ringless["stats"]["rx_batches"] == 0
    assert striped["stats"]["rx_batches"] > 0


def _nasty_f32(n, seed):
    x = np.random.RandomState(seed).randn(n).astype(np.float32)
    x[:6] = [np.float32("nan"), np.float32("inf"),
             np.float32("-inf"), -0.0, 1e-42, -1e-42]
    return x


def test_fused_decode_bitwise_ab():
    """Decompress-on-the-fabric A/B (in-process: BYTEPS_FUSED_DECODE is
    read per server instance): decode-into-accumulator and
    decode-then-fold produce bitwise-identical lossless aggregates, and
    the stage counter proves which path ran."""
    import threading as th

    from byteps_tpu.config import Config
    from byteps_tpu.core.registry import TensorRegistry
    from byteps_tpu.core.types import DataType
    from byteps_tpu.server import run_server
    from byteps_tpu.server.client import PSClient
    from byteps_tpu.server.compressed import CompressedTensor
    from byteps_tpu.utils.net import free_port, wait_port

    n = 65536
    x = _nasty_f32(n, seed=5)
    outs, folds = {}, {}
    for flag in ("0", "1"):
        os.environ["BYTEPS_FUSED_DECODE"] = flag
        try:
            port = free_port()
            cfg = Config(num_workers=1, num_servers=1)
            t = th.Thread(target=run_server, args=(port, cfg),
                          daemon=True)
            t.start()
            wait_port(port)
            c = PSClient([f"127.0.0.1:{port}"], worker_id=0)
            reg = TensorRegistry(cfg)
            ctx = reg.init_tensor(f"ab{flag}", n * 4, DataType.FLOAT32)
            ct = CompressedTensor(c, ctx, {"compressor": "lossless"}, 1)
            for r in range(2):
                out = ct.push_pull(x * (r + 1), average=False)
            outs[flag] = np.asarray(out).tobytes()
            st = c.server_stats(0)
            folds[flag] = st["fused_decode_folds"] if st else None
            c.close()
            t.join(timeout=20)
        finally:
            os.environ.pop("BYTEPS_FUSED_DECODE", None)
    assert outs["0"] == outs["1"], \
        "fused decode changed lossless aggregate bits"
    assert folds["1"] and folds["1"] > 0, \
        "BYTEPS_FUSED_DECODE=1 never took the fused path"
    assert folds["0"] == 0, \
        "BYTEPS_FUSED_DECODE=0 still took the fused path"
