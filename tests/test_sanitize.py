"""Sanitizer tier for the native PS (SURVEY.md §5.2: the reference ships
no race detection; this build adds it).

Builds ps.cc under -fsanitize=thread and runs a concurrent loopback stress
(two clients hammering overlapping keys: dense, compressed, parked pulls,
barrier) in a subprocess with the TSAN runtime preloaded. Any data race
makes TSAN print a WARNING and exit nonzero (halt_on_error)."""

import os
import subprocess
import sys

import pytest

_STRESS = r"""
import threading, numpy as np
import os, sys
sys.path.insert(0, os.environ["BPS_REPO"])
from byteps_tpu.config import Config
from byteps_tpu.core.registry import TensorRegistry
from byteps_tpu.core.types import DataType, RequestType, get_command_type
from byteps_tpu.server import run_server
from byteps_tpu.server.client import PSClient
from byteps_tpu.server.compressed import CompressedTensor

PORT = int(os.environ["BPS_STRESS_PORT"])
cfg = Config(num_workers=2, num_servers=1)
server = threading.Thread(target=run_server, args=(PORT, cfg), daemon=True)
server.start()

CMD = get_command_type(RequestType.DEFAULT_PUSH_PULL, DataType.FLOAT32)
addr = [f"127.0.0.1:{PORT}"]
clients = [PSClient(addr, worker_id=w) for w in range(2)]

def reg():
    return TensorRegistry(Config(num_workers=2, num_servers=1))

def worker(w):
    r = reg()
    c = clients[w]
    rng = np.random.RandomState(w)
    # dense tensors (multi-partition) + compressed tensor, interleaved
    ctxs = [r.init_tensor(f"t{i}", 3000 * 4, DataType.FLOAT32)
            for i in range(4)]
    for ctx in ctxs:
        c.init_tensor(ctx, np.zeros(3000, np.float32))
    ct = CompressedTensor(c, r.init_tensor("comp", 2048 * 4, DataType.FLOAT32),
                          {"compressor": "onebit", "ef": "vanilla"}, 2)
    # dedicated keys for the fault-tolerance wire paths: epoch-stamped
    # pushes with a deliberate REPLAY (server-side last_round dedup) and
    # the fused PUSHPULL op carrying the same stamps
    rctx = r.init_tensor("replay", 1024 * 4, DataType.FLOAT32)
    c.init_tensor(rctx, np.zeros(1024, np.float32))
    fctx = r.init_tensor("fusedep", 1024 * 4, DataType.FLOAT32)
    c.init_tensor(fctx, np.zeros(1024, np.float32))
    # bounded-staleness window key (BYTEPS_STALENESS=1 in the test
    # env): worker 0 pushes one round AHEAD of the open round every
    # step, so DeferFold's payload copy, WindowPublishLocked's
    # pub_hist ring + selective parked-pull scan and the out-of-lock
    # RedispatchDeferred all race the data plane under the sanitizer
    wctx = r.init_tensor("window", 1024 * 4, DataType.FLOAT32)
    c.init_tensor(wctx, np.zeros(1024, np.float32))
    # descriptor-tier key (>= 64KB): over the shm transport the payload
    # rides the ring as an 8-byte descriptor and the server folds it IN
    # PLACE from the shared arena — worker 0's push lands in the key's
    # accumulator (zero-copy first fold), worker 1's goes through the
    # per-engine fold SCRATCH, and the test env's small arena forces the
    # block ring to wrap+reclaim while both workers race. The perf-PR
    # additions (SIMD fold, OOB descriptors, buffer pool) are all inside
    # this loop's shadow under the sanitizer.
    octx = r.init_tensor("oob", 24 * 1024 * 4, DataType.FLOAT32)
    c.init_tensor(octx, np.zeros(24 * 1024, np.float32))
    for step in range(15):
        for ctx in ctxs:
            x = rng.randn(3000).astype(np.float32)
            c.push_pull(ctx, x, average=True, num_workers=2)
        # training-health leg (BYTEPS_HEALTH=1 in the test env): the
        # fused in-fold stat kernel ran on the folds above; the keyed
        # HEALTH_PULL control op races the data plane inline on the
        # conn loop, and both workers read the same KeyStore hstat
        # the engines publish under ks.mu
        hp = ctxs[step % len(ctxs)].partitions[0]
        c.health_pull(hp.server, hp.key, timeout_s=5)
        ct.push_pull(rng.randn(2048).astype(np.float32))
        # descriptor-tier round: arena in-place fold + fold scratch +
        # block reclaim, raced by both workers every step
        c.push_pull(octx, rng.randn(24 * 1024).astype(np.float32),
                    average=True, num_workers=2)
        # async-push path (detached waiters drain in RecvLoop while the
        # paired pull waits on the same key-affine conn): the round-4
        # concurrency addition, stressed under the sanitizer like the
        # rest of the protocol
        actx = ctxs[step % len(ctxs)]
        for p in actx.partitions:
            c.zpush_async(p.server, p.key,
                          rng.randn(p.length // 4).astype(np.float32), CMD)
        for p in actx.partitions:
            out = np.empty(p.length // 4, np.float32)
            c.zpull(p.server, p.key, out, CMD)
        # replay/dedup path (round 6 fault-tolerance addition): each
        # worker pushes its epoch-stamped contribution TWICE — the
        # server must fold it once (last_round dedup) and both engine
        # threads race on the same KeyStore's last_round vector
        ep = (step + 1) << 16
        rp = rctx.partitions[0]
        rbuf = rng.randn(1024).astype(np.float32)
        c.zpush(rp.server, rp.key, rbuf, CMD, epoch=ep)
        c.zpush(rp.server, rp.key, rbuf, CMD, epoch=ep | 1)  # replay
        rout = np.empty(1024, np.float32)
        c.zpull(rp.server, rp.key, rout, CMD)
        # fused PUSHPULL with the same stamp: parked fused replies +
        # the completion reactor under the sanitizer
        fp = fctx.partitions[0]
        fdone = threading.Event()
        fout = np.empty(1024 * 4, np.uint8)
        c.zpushpull_async(fp.server, fp.key,
                          rng.randn(1024).astype(np.float32), fout, CMD,
                          lambda n, err, d=fdone: d.set(), epoch=ep)
        assert fdone.wait(60), "fused completion never fired"
        # staleness-window round: both workers fold round step+1; w0
        # then BLOCKS on a deliberately ahead round step+2 fold — it
        # parks in the window, w1's aligned fold publishes and the
        # redispatch replies it (the blocking wait also fences w0 to
        # skew <= 1, keeping every fold inside window W). Next step's
        # own push of that round is then epoch-deduped (last_round
        # raced by both engines).
        wp = wctx.partitions[0]
        wbuf = np.ones(1024, np.float32)
        c.zpush(wp.server, wp.key, wbuf, CMD, epoch=(step + 1) << 16)
        if w == 0:
            c.zpush(wp.server, wp.key, wbuf, CMD, epoch=(step + 2) << 16)
        # Waiter-lifecycle burst (the PR-6 TSAN finding's minimal
        # repro, promoted): tight concurrent BLOCKING request loops on
        # shared striped conns churn Waiter completions across threads
        # — before the per-conn Waiter pool + explicitly-initialized
        # pthread primitives, heap/address reuse of completed Waiters
        # reported "double lock of a destroyed mutex" within seconds
        bctx = ctxs[(step + 1) % len(ctxs)]
        for bp in bctx.partitions:
            for _ in range(3):
                c.zpush(bp.server, bp.key,
                        rng.randn(bp.length // 4).astype(np.float32),
                        CMD)
                small = np.empty(bp.length // 4, np.float32)
                c.zpull(bp.server, bp.key, small, CMD)
        c.barrier()

threads = [threading.Thread(target=worker, args=(w,)) for w in range(2)]
for t in threads: t.start()

# Elastic leg (PR 13), CONCURRENT with the stress above: a second
# server starts at runtime and both clients AddServer it — the atomic
# conn-group publish (fixed array + release-store count) races the
# live recv loops, reactor sweeps and ServerDead probes under the
# sanitizer; then the new JOIN_PROBE / DRAIN_REQ control ops run
# inline on the conn loop while data traffic flows.
from byteps_tpu.utils.net import wait_port
PORT2 = int(os.environ["BPS_STRESS_PORT2"])
server2 = threading.Thread(target=run_server,
                           args=(PORT2, Config(num_workers=2,
                                               num_servers=1)),
                           daemon=True)
server2.start()
wait_port(PORT2)
assert clients[0].add_server(f"127.0.0.1:{PORT2}") == 1
assert clients[1].add_server(f"127.0.0.1:{PORT2}") == 1
probe = clients[0].join_probe(1)
assert probe and probe["num_workers"] == 2 and not probe["draining"]
ez = np.zeros(1024, np.float32)
it = threading.Thread(target=clients[0].init_key,
                      args=(1, 777, ez, CMD), daemon=True)
it.start()
clients[1].init_key(1, 777, ez, CMD)
it.join(timeout=30)
assert not it.is_alive()
for w in range(2):
    clients[w].zpush(1, 777, np.ones(1024, np.float32), CMD,
                     epoch=(1 << 16))
eout = np.empty(1024, np.float32)
clients[0].zpull(1, 777, eout, CMD)
assert (eout == 2.0).all()
ack = clients[0].drain_req(1)
assert ack and ack["draining"] and ack["keys_held"] >= 1
stats = clients[1].server_stats(1)
assert stats and stats["draining"] == 1

for t in threads: t.join()
# the staleness window was armed (BYTEPS_STALENESS=1 rides the test
# env) and its bookkeeping slots published; whether a given run
# actually deferred is a scheduling race — the POINT of running it
# under the sanitizer — so only the no-reject invariant is hard
wstats = clients[0].server_stats(0)
assert "window_deferred" in wstats, wstats
assert wstats["window_rejected"] == 0, wstats
clients[0].close()  # both workers SHUTDOWN: both servers exit cleanly
clients[1].close()
server.join(timeout=20)
server2.join(timeout=20)
print("STRESS_OK")
"""


# Minimal deterministic repro of the PR-6 TSAN finding (the Waiter-pool
# regression class): tight concurrent BLOCKING push/pull loops from 4
# threads sharing one client's striped conns churn Waiter completions
# across threads. Before the per-conn Waiter pool + explicitly
# pthread-initialized Mu/Cv wrappers (PR 7 fix), heap/address reuse of
# completed Waiters produced ~510 "double lock of a destroyed mutex"
# reports within seconds of exactly this loop — so a regression fires
# fast and deterministically. Kept SMALL (4 threads x 60 rounds, one
# small key each + one shared contended key) so the whole test — TSAN
# build included, content-hash-cached across the session — fits the
# tier-1 budget; the full protocol burst stays in the slow tier above.
_WAITER_SMOKE = r"""
import threading, numpy as np
import os, sys
sys.path.insert(0, os.environ["BPS_REPO"])
from byteps_tpu.config import Config
from byteps_tpu.core.registry import TensorRegistry
from byteps_tpu.core.types import DataType, RequestType, get_command_type
from byteps_tpu.server import run_server
from byteps_tpu.server.client import PSClient

PORT = int(os.environ["BPS_STRESS_PORT"])
cfg = Config(num_workers=1, num_servers=1)
server = threading.Thread(target=run_server, args=(PORT, cfg), daemon=True)
server.start()

CMD = get_command_type(RequestType.DEFAULT_PUSH_PULL, DataType.FLOAT32)
client = PSClient([f"127.0.0.1:{PORT}"], worker_id=0)
reg = TensorRegistry(cfg)
ctxs = [reg.init_tensor(f"w{t}", 256 * 4, DataType.FLOAT32)
        for t in range(4)]
shared = reg.init_tensor("shared", 256 * 4, DataType.FLOAT32)
for ctx in ctxs + [shared]:
    client.init_tensor(ctx, np.zeros(256, np.float32))

def worker(t):
    rng = np.random.RandomState(t)
    own = ctxs[t].partitions[0]
    sp = shared.partitions[0]
    out = np.empty(256, np.float32)
    for _ in range(60):
        client.zpush(own.server, own.key,
                     rng.randn(256).astype(np.float32), CMD)
        client.zpull(own.server, own.key, out, CMD)
        # shared-key contention: Waiters of different threads complete
        # interleaved on the same striped conns
        client.zpush(sp.server, sp.key, np.ones(256, np.float32), CMD)
        client.zpull(sp.server, sp.key, out, CMD)

threads = [threading.Thread(target=worker, args=(t,)) for t in range(4)]
for t in threads: t.start()
for t in threads: t.join()
client.close()
server.join(timeout=20)
print("SMOKE_OK")
"""


# Striped-wire stress (PR 17): forced-TCP clients (BYTEPS_ENABLE_IPC=0)
# with 4 data stripes and an 8 KB chunk race multi-segment fused
# PUSHPULL reassembly + the reply tx rings against inline control ops
# (STATS_PULL / JOIN_PROBE / HEALTH_PULL on the never-queued conn-0
# lane), a mid-run single-stripe kill (server-side StripeReset + seq
# gate resync racing live segment writes), and an elastic join/drain.
_STRIPE_STRESS = r"""
import threading, time, numpy as np
import os, sys
sys.path.insert(0, os.environ["BPS_REPO"])
from byteps_tpu.config import Config
from byteps_tpu.core.types import DataType, RequestType, get_command_type
from byteps_tpu.server import run_server
from byteps_tpu.server.client import PSClient
from byteps_tpu.utils.net import wait_port

PORT = int(os.environ["BPS_STRESS_PORT"])
cfg = Config(num_workers=2, num_servers=1)
server = threading.Thread(target=run_server, args=(PORT, cfg), daemon=True)
server.start()
wait_port(PORT)
CMD = get_command_type(RequestType.DEFAULT_PUSH_PULL, DataType.FLOAT32)
addr = [f"127.0.0.1:{PORT}"]
clients = [PSClient(addr, worker_id=w) for w in range(2)]

N = 48 * 1024  # 192 KB -> ~24 segments per push at the 8 KB chunk
zero = np.zeros(N, np.float32)
its = []
for key in (300, 301, 302):
    t = threading.Thread(target=clients[1].init_key,
                         args=(0, key, zero, CMD), daemon=True)
    t.start()
    clients[0].init_key(0, key, zero, CMD)
    its.append(t)
for t in its:
    t.join(timeout=30)
    assert not t.is_alive(), "init barrier wedged"

def fused(c, key, x, out, epoch):
    done = threading.Event(); err = [None]
    def cb(n, e):
        err[0] = e; done.set()
    c.zpushpull_async(0, key, x, out, CMD, cb, epoch=epoch)
    assert done.wait(120), "fused pushpull timed out"
    if err[0]:
        raise err[0]

def worker(w):
    c = clients[w]
    out = np.empty(N, np.float32)
    for step in range(1, 11):
        ep = step << 16
        # sync mode: a round completes only when BOTH workers folded,
        # so both workers push every key; worker w contributes
        # (w+1)*step -> aggregate 3*step, asserted bitwise (multi-
        # segment reassembly from two senders interleaves on the same
        # engine threads)
        for key in (300, 301, 302):
            fused(c, key,
                  np.full(N, float(w + 1) * step, np.float32), out, ep)
            assert (out == 3.0 * step).all(), (w, step, key)
        # control ops race the striped data plane on the conn-0 lane
        st = c.server_stats(0)
        assert st is not None and st["stripe_segs"] > 0
        c.join_probe(0)
        c.health_pull(0, 300, timeout_s=5)
        if step == 5 and w == 0:
            # kill one of our data conns mid-run: the server's conn
            # loop races StripeReset/gate-resync with worker 1's live
            # segments; our next rounds stripe over the survivors
            assert c.kill_stripe(0, 2)
            time.sleep(0.2)

ths = [threading.Thread(target=worker, args=(w,)) for w in range(2)]
for t in ths: t.start()

# elastic leg, CONCURRENT with the striped stress: a second server
# joins at runtime, both clients build a striped conn group to it and
# run a striped round there, then a drain — the group publish and the
# JOIN_PROBE/DRAIN_REQ control ops race live stripe reassembly
PORT2 = int(os.environ["BPS_STRESS_PORT2"])
server2 = threading.Thread(target=run_server,
                           args=(PORT2, Config(num_workers=2,
                                               num_servers=1)),
                           daemon=True)
server2.start()
wait_port(PORT2)
assert clients[0].add_server(f"127.0.0.1:{PORT2}") == 1
assert clients[1].add_server(f"127.0.0.1:{PORT2}") == 1
ez = np.zeros(N, np.float32)
it = threading.Thread(target=clients[0].init_key,
                      args=(1, 400, ez, CMD), daemon=True)
it.start()
clients[1].init_key(1, 400, ez, CMD)
it.join(timeout=30)
assert not it.is_alive()

def efused(c, x, out):
    done = threading.Event(); err = [None]
    def cb(n, e):
        err[0] = e; done.set()
    c.zpushpull_async(1, 400, x, out, CMD, cb, epoch=(1 << 16))
    assert done.wait(120)
    if err[0]:
        raise err[0]

eo0 = np.empty(N, np.float32)
eo1 = np.empty(N, np.float32)
et = threading.Thread(target=efused,
                      args=(clients[1], np.full(N, 2.0, np.float32), eo1))
et.start()
efused(clients[0], np.full(N, 1.0, np.float32), eo0)
et.join(timeout=120)
assert (eo0 == 3.0).all() and (eo1 == 3.0).all(), "elastic striped sum"
ack = clients[0].drain_req(1)
assert ack and ack["draining"]

for t in ths: t.join()

for c in clients:
    ts = c.transport_stats()
    assert ts["stripe_segs"] > 0, "striper never engaged under stress"
clients[0].close()
clients[1].close()
server.join(timeout=20)
server2.join(timeout=20)
print("STRIPE_STRESS_OK")
"""


# glibc's dynamic-TLS teardown (_dl_deallocate_tls freeing a joined
# thread's DTV block) is a known TSAN false positive for thread_local
# in dlopen'd objects — see ci/tsan.supp for the full story
_TSAN_SUPP = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "ci", "tsan.supp")

_TIERS = {
    # mode -> (runtime lib, options env var, options, error marker)
    "thread": ("libtsan.so", "TSAN_OPTIONS",
               f"halt_on_error=1 exitcode=66 suppressions={_TSAN_SUPP}",
               "WARNING: ThreadSanitizer"),
    # leak detection would see the whole long-lived interpreter (numpy,
    # CPython arenas) — scope ASAN to memory-safety errors
    "address": ("libasan.so", "ASAN_OPTIONS",
                "detect_leaks=0 halt_on_error=1 exitcode=66",
                "ERROR: AddressSanitizer"),
}


@pytest.mark.slow
@pytest.mark.parametrize("mode", sorted(_TIERS))
def test_sanitized_loopback_stress(tmp_path, mode):
    """The concurrent loopback stress under TSAN (races) and ASAN (heap
    overflow / use-after-free) against the server stores, shm ring
    transport, and codec mirror."""
    from byteps_tpu.utils.net import free_port

    lib_name, opts_var, opts, marker = _TIERS[mode]
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    runtime = subprocess.run(
        ["g++", f"-print-file-name={lib_name}"], capture_output=True,
        text=True).stdout.strip()
    if not os.path.isabs(runtime) or not os.path.exists(runtime):
        pytest.skip(f"{lib_name} not available")

    script = tmp_path / "stress.py"
    script.write_text(_STRESS)
    port1 = free_port()
    port2 = free_port()
    while port2 == port1:
        port2 = free_port()
    env = {
        **os.environ,
        "BPS_REPO": repo,
        "BPS_STRESS_PORT": str(port1),
        # elastic leg: the runtime-joined second server
        "BPS_STRESS_PORT2": str(port2),
        "BYTEPS_SANITIZE": mode,
        "LD_PRELOAD": runtime,
        opts_var: opts,
        # small arena: the stress's 96KB descriptor-tier rounds wrap
        # and reclaim the block ring many times under the sanitizer
        "BYTEPS_IPC_ARENA_BYTES": str(512 << 10),
        # training-health leg: the in-fold stat pass (fused last-fold
        # kernel + publish scans) and the HEALTH_PULL control op run
        # under the sanitizer with both workers racing
        "BYTEPS_HEALTH": "1",
        # staleness-window leg: both stress servers construct with
        # window 1 so worker 0's deliberately ahead folds park in
        # DeferFold and redispatch at publish instead of rejecting
        "BYTEPS_STALENESS": "1",
        # jax under sanitizers is hopeless; the stress uses numpy only
        "JAX_PLATFORMS": "cpu",
    }
    # build the sanitized lib first (outside LD_PRELOAD; g++ subprocesses
    # under a preloaded runtime work but are slower)
    subprocess.run(
        [sys.executable, "-c",
         "import sys, os; sys.path.insert(0, os.environ['BPS_REPO']); "
         "from byteps_tpu.native.build import build; build(verbose=True)"],
        env={**os.environ, "BPS_REPO": repo, "BYTEPS_SANITIZE": mode},
        check=True, capture_output=True, timeout=300)

    proc = subprocess.run([sys.executable, str(script)], env=env,
                          capture_output=True, text=True, timeout=480)
    out = proc.stdout + proc.stderr
    assert marker not in out, out[-4000:]
    assert proc.returncode == 0, out[-4000:]
    assert "STRESS_OK" in out, out[-4000:]


@pytest.mark.slow
@pytest.mark.parametrize("mode", sorted(_TIERS))
def test_sanitized_stripe_stress(tmp_path, mode):
    """The striped cross-host wire plane under TSAN/ASAN: forced-TCP
    multi-segment fused traffic from two workers (reassembly + seq
    gates + reply tx rings + fused lossless decode paths all in the
    loop's shadow) raced against inline control ops, a mid-run
    single-stripe kill, and an elastic join/drain."""
    from byteps_tpu.utils.net import free_port

    lib_name, opts_var, opts, marker = _TIERS[mode]
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    runtime = subprocess.run(
        ["g++", f"-print-file-name={lib_name}"], capture_output=True,
        text=True).stdout.strip()
    if not os.path.isabs(runtime) or not os.path.exists(runtime):
        pytest.skip(f"{lib_name} not available")

    subprocess.run(
        [sys.executable, "-c",
         "import sys, os; sys.path.insert(0, os.environ['BPS_REPO']); "
         "from byteps_tpu.native.build import build; build(verbose=True)"],
        env={**os.environ, "BPS_REPO": repo, "BYTEPS_SANITIZE": mode},
        check=True, capture_output=True, timeout=300)

    script = tmp_path / "stripe_stress.py"
    script.write_text(_STRIPE_STRESS)
    port1 = free_port()
    port2 = free_port()
    while port2 == port1:
        port2 = free_port()
    env = {
        **os.environ,
        "BPS_REPO": repo,
        "BPS_STRESS_PORT": str(port1),
        "BPS_STRESS_PORT2": str(port2),
        "BYTEPS_SANITIZE": mode,
        "LD_PRELOAD": runtime,
        opts_var: opts,
        # the striped plane needs the real TCP wire; 4 data stripes at
        # an 8 KB chunk turn every 192 KB push into ~24 raced segments
        "BYTEPS_ENABLE_IPC": "0",
        "BYTEPS_WIRE_STRIPES": "4",
        "BYTEPS_STRIPE_CHUNK_BYTES": "8192",
        "BYTEPS_SOCK_BUF_BYTES": "65536",
        "BYTEPS_HEALTH": "1",
        "JAX_PLATFORMS": "cpu",
    }
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          capture_output=True, text=True, timeout=480)
    out = proc.stdout + proc.stderr
    assert marker not in out, out[-4000:]
    assert proc.returncode == 0, out[-4000:]
    assert "STRIPE_STRESS_OK" in out, out[-4000:]


def test_tsan_waiter_pool_smoke(tmp_path):
    """Fast deterministic TSAN smoke (NOT slow — runs inside tier-1):
    the PR-6 Waiter-pool minimal repro. A regression in the per-conn
    Waiter pool or the pthread-initialized Mu/Cv wrappers reports
    "double lock of a destroyed mutex" within seconds of this loop,
    so the class is caught by the tier-1 gate instead of only by
    the slow sanitize burst. The TSAN build is content-hash-cached
    (~6 s cold on the 2-core box); the stress itself is ~4 threads x
    60 blocking rounds."""
    from byteps_tpu.utils.net import free_port

    lib_name, opts_var, opts, marker = _TIERS["thread"]
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    runtime = subprocess.run(
        ["g++", f"-print-file-name={lib_name}"], capture_output=True,
        text=True).stdout.strip()
    if not os.path.isabs(runtime) or not os.path.exists(runtime):
        pytest.skip(f"{lib_name} not available")

    subprocess.run(
        [sys.executable, "-c",
         "import sys, os; sys.path.insert(0, os.environ['BPS_REPO']); "
         "from byteps_tpu.native.build import build; build()"],
        env={**os.environ, "BPS_REPO": repo, "BYTEPS_SANITIZE": "thread"},
        check=True, capture_output=True, timeout=300)

    script = tmp_path / "waiter_smoke.py"
    script.write_text(_WAITER_SMOKE)
    env = {
        **os.environ,
        "BPS_REPO": repo,
        "BPS_STRESS_PORT": str(free_port()),
        "BYTEPS_SANITIZE": "thread",
        "LD_PRELOAD": runtime,
        opts_var: opts,
        "JAX_PLATFORMS": "cpu",
    }
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          capture_output=True, text=True, timeout=240)
    out = proc.stdout + proc.stderr
    assert marker not in out, out[-4000:]
    assert proc.returncode == 0, out[-4000:]
    assert "SMOKE_OK" in out, out[-4000:]
