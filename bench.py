"""Benchmark entry point — prints ONE JSON line.

Primary metric: flagship-model (Llama ~125M) training throughput on the
available device: full train step (fwd + bwd + adam), bf16 compute, remat,
donated buffers. Mirrors the reference's synthetic-throughput vehicle
(example/pytorch/benchmark_byteps.py:25-31,110-140: mean over repeated
timed batches).

Extra keys in the same line:

- ``mfu`` — model-FLOPs utilization: achieved model FLOP/s (6*matmul
  params + causal attention term) over the chip's bf16 peak
  (BASELINE.md "maximize" north-star; the reference reports relative
  speedups only, docs/performance.md:5-11).
- ``scaling_efficiency_2w`` — throughput(2 workers)/(2 x throughput(1))
  across real worker OS processes through the loopback PS (the
  reference's headline metric shape, README.md:34-40; under-reported on
  a 1-core host — a regression tracker, not an absolute).
  ``scaling_vs_cap_reps`` / ``scaling_spread`` report the per-rep
  ratios and their max-min: the shared-host noise band, so a single
  draw (0.88 one round, 0.97 another) is readable as estimator noise
  rather than a protocol regression.
- ``pushpull_dense_gbps`` / ``pushpull_onebit_gbps`` /
  ``pushpull_randomk_gbps`` — the push_pull
  micro north-star (BASELINE.md "maximize GB/s/chip"): a 256MB gradient
  set through the full pipelined PS path (priority scheduler -> native
  TCP client -> C++ server on loopback), reported as gradient
  bytes x 2 / wall; the onebit/randomk figures are EFFECTIVE rates
  (dense-equivalent bytes moved per second while the wire carries 1/32
  resp. 1/50 the volume), both on the HOST codec tier riding the C ABI
  native codec. Reference vehicle: benchmark_byteps.py push_pulls every
  gradient; here the loopback server stands in for the DCN tier.
- ``pushpull_dense_2srv_gbps`` — the same dense round with keys sharded
  over two servers: raw-throughput form of the scaling story; ~1.0x on
  a 1-core host (documented caveat), approaches 2x with cores to back
  it.
- ``pushpull_throttled_1srv_gbps`` / ``pushpull_throttled_2srv_gbps`` —
  the CORE-INDEPENDENT form of BASELINE's scaling rule (throughput ∝
  min(server bw, worker bw)): the server is made the bottleneck by
  construction (BYTEPS_SERVER_THROTTLE_MBPS sleeps its threads, so the
  cap binds even on 1 core) — 1 throttled server reads ~the throttle,
  2 throttled servers splitting the keys read ~2x it.
- ``stripe_ab_legacy_gbps`` / ``stripe_ab_ring_gbps`` /
  ``stripe_ab_striped_gbps`` — the cross-host wire plane A/B'd between
  two real OS processes over loopback TCP (non-shm): the retired
  per-message path vs batched submission rings vs rings + striped data
  connections, with hard byte-conservation and batch-counter proofs
  per arm; ``stripe_ab_throttled_{dense,lossless}_gbps`` replay the
  codec story on the new plane under a server-side wire cap (the
  lossless tier's fused decode-into-fold must move more
  dense-equivalent bytes than dense under the same cap).
- ``pushpull_dense_tpu_gbps`` / ``pushpull_onebit_tpu_gbps`` /
  ``pushpull_randomk_tpu_gbps`` — the device tier (grads start on
  chip; the codec compresses ON chip so the D2H hop moves wire-sized
  bytes — 1/32 for onebit, ~1/50 for randomk); runs whether or not the
  train phase landed.
- ``arena_on_step_ms`` / ``arena_off_step_ms`` — steady-state PS train
  step wall with the persistent host staging arena
  (BYTEPS_STAGING_ARENA, core/arena.py) on vs off, plus the arena
  counters (allocs avoided / bytes pinned / conflicts) proving the
  zero-allocation steady state.
- ``ledger_on_step_ms`` / ``ledger_off_step_ms`` — steady-state PS
  train step wall with the step efficiency ledger (BYTEPS_LEDGER,
  core/ledger.py) pricing every step vs off, plus the engaged-proof
  (``ledger_mfu`` / ``ledger_overlap_frac`` /
  ``ledger_wire_efficiency`` non-null from the ON arm's last
  StepReport). ``--baseline FILE`` additionally runs the noise-aware
  perf regression gate (ci/perf_gate.py) over the final snapshot and
  attaches its verdict as ``perf_gate``.
- ``health_on_step_ms`` / ``health_off_step_ms`` — steady-state PS
  train step wall with the training-health plane (BYTEPS_HEALTH,
  core/health.py + the native in-fold statistics pass) on vs off,
  plus the engaged-proof (``health_grad_norm`` non-null from the ON
  arm's last StepReport, ``health_infold_rounds`` nonzero from the
  server's stat slots). Acceptance bar: ``health_overhead_pct`` <= 2.

The train phase A/Bs four variants per capture — remat, selective
remat, chunked-vocab xent, and a hand-fused adam (one elementwise
kernel per leaf; the driver-side experiment for the "optimizer pass"
MFU suspect) — and reports each as ``tokens_per_sec_<variant>``.

``vs_baseline`` compares against a recorded naive-fp32 single-chip
measurement of the same workload on the same v5e hardware (51,810
tokens/s at B=16/S=1024 with fp32 activations + remat + log_softmax loss,
2026-07-29) — the "untuned implementation" anchor, since the reference's
published numbers (README.md:9) are V100-cluster scaling efficiencies
with no single-chip equivalent.

Process model: the parent process is stdlib-only (it never imports
jax, so it never holds the chip), and every phase runs in its OWN
subprocess + process group with a hard deadline — one process for each
chip at a time.

- the device phases (``train``, ``pushpull_tpu``) run ONCE each, first.
  Their children use JAX's default backend and fail when that is not a
  TPU: a device-named number is never computed on the CPU.
- every other phase is a CPU-loopback phase whose child pins the CPU
  platform as its first jax call; its keys are CPU figures whatever
  machine runs them.
- a failed phase leaves its keys ``null`` and its error under
  ``phase_errors``, and the run exits NON-ZERO: exit code 0 means every
  phase ran and landed.
- the whole schedule is budget-gated (BENCH_BUDGET_S, default 2100 s):
  each launch checks ``remaining()`` and caps its deadline at the
  leftover window, so the worst case is about the budget plus one phase
  deadline; a phase skipped for budget is recorded as
  ``skipped-budget`` (and counts as not landed). The snapshot JSON is
  also flushed after every phase (tagged ``"partial": true``) and on
  SIGTERM, so an external kill at any point leaves the last snapshot as
  the final parseable line.

Tuning applied vs the anchor: bf16 activations/logits, logsumexp-form
cross entropy (llama.next_token_xent), B=16 batch (MXU utilization),
donated buffers, head_dim=128 attention layout (identical params/FLOPs;
hd=64 wastes half of each 128-lane register tile — measured +40%), bf16
adam first moment. Measured-but-rejected: Pallas flash attention AND
jax's production splash-attention kernel (74.0k vs 100.3k tok/s — XLA's
fused dense attention wins at S=1024 on v5e; Pallas attention pays off
past S≈4k, docs/performance.md), scan unroll, B=32, S=2048@B=8,
dots_saveable remat, noremat (now OOMs, see variants below).
Ceiling context: bare bf16 matmuls at this model's shapes (K=768) reach
112-148 TF/s on v5e (not the 197 headline, which needs K>=4096), so the
shape-mix-achievable MFU is ~0.6-0.75; we measure ~0.34 end-to-end with
the remainder going to attention softmax HBM traffic, rmsnorm/rope VPU
work, remat recompute and the optimizer pass.
"""

from __future__ import annotations

import contextlib
import json
import os
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

# Naive-fp32 anchor measured on v5e-1 (see module docstring).
BASELINE_TOKENS_PER_SEC = 51810.0

_MARK = "BENCH_PHASE_RESULT "


def _best_of(fn, nbytes: int, steps: int) -> float:
    """Warmup call (init-push / comp_init handshake, jit compiles,
    allocation), then best per-round GB/s over ``steps`` timed rounds:
    the capability number, robust to single-core scheduler jitter on
    shared CI hosts (per-round spread there can exceed 50%). Counted as
    gradient bytes x 2 (push + pull) per second."""
    fn()
    best_dt = float("inf")
    for _ in range(steps):
        t0 = time.perf_counter()
        fn()
        best_dt = min(best_dt, time.perf_counter() - t0)
    return nbytes * 2 / best_dt / 1e9

# ---------------------------------------------------------------------------
# Phase bodies (run inside `python bench.py --phase NAME` children).
# jax is imported lazily so the orchestrating parent never touches it.
# ---------------------------------------------------------------------------


def _setup_device_backend():
    """The accelerator backend of a device phase, with the persistent
    compilation cache placed by the shared helper (repeated runs in one
    checkout skip the fresh compiles). A device phase that finds no TPU
    FAILS: its numbers carry device-named keys, and a CPU run must never
    be published under them."""
    import jax

    from byteps_tpu.utils.jax_compat import setup_compile_cache

    setup_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"device phase needs a TPU; JAX reports {len(jax.devices())} "
            f"x {dev.platform!r}")
    return jax


def _device_id(jax) -> dict:
    """The device a result ran on, as JAX reports it."""
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def _force_cpu():
    """CPU-loopback phases pin the CPU platform before their first
    device query, so they never take the chip from a device phase and
    measure the same thing on every machine."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    return jax


def _cpu_put(x):
    """Commit a CPU phase's input explicitly to cpu:0, so nothing about
    the process's default backend can decide where it lands."""
    import jax

    return jax.device_put(x, jax.devices("cpu")[0])


def model_flops_per_token(cfg, S: int) -> float:
    """Model FLOPs per trained token: 6 x matmul params (fwd 2 + bwd 4)
    plus the causal attention score/value term (QK^T + AV are each
    2*S*d fwd per token; causal halves the useful work; x3 for bwd)."""
    d, L = cfg.dim, cfg.n_layers
    hd, nh, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    per_layer = (d * nh * hd          # wq
                 + 2 * d * nkv * hd   # wk, wv
                 + nh * hd * d        # wo
                 + 3 * d * cfg.hidden_dim)  # w1, w3, w2
    mat = L * per_layer + d * cfg.vocab_size  # + lm_head
    attn = L * 6 * S * d  # 12*S*d full, /2 causal
    return 6.0 * mat + attn


def phase_train(B: int = 16, S: int = 1024, steps: int = 10) -> dict:
    jax = _setup_device_backend()
    import dataclasses

    import jax.numpy as jnp
    import numpy as np
    import optax

    from byteps_tpu.core.ledger import detect_peak, extract_cost
    from byteps_tpu.models import llama

    # bf16 peak from the ledger's device-kind table (core/ledger.py;
    # docs/performance.md "Chip peak table"); a device kind in no row
    # raises. BYTEPS_PEAK_FLOPS overrides for odd hardware.
    peak_flops, _, peak_source = detect_peak(jax.devices()[0].device_kind)

    tokens = None
    step_flops = {}  # variant -> XLA cost-analysis FLOPs per step

    def fused_adam_for(cfg):
        """Hand-fused adam over this cfg's loss (shared implementation:
        byteps_tpu.jax.optim.fused_adam_step, validated bit-close to
        optax). A/B'd against the optax chain on the real chip by the
        driver itself: if the optimizer pass is a real MFU cost, this
        variant wins; if not, it retires the 'optimizer pass' suspect
        from the ceiling analysis (docs/performance.md)."""
        from byteps_tpu.jax.optim import fused_adam_step

        init, step = fused_adam_step(
            lambda q, t: llama.loss_fn(q, {"tokens": t}, cfg))
        return init, step

    def measure_cfg(cfg, make_opt=None, tag=None) -> float:
        nonlocal tokens
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        if tokens is None:
            tokens = jnp.asarray(
                np.random.RandomState(0).randint(0, cfg.vocab_size,
                                                 (B, S + 1)), jnp.int32)
        if make_opt is not None:
            opt_init, step = make_opt(cfg)
            opt = opt_init(params)
        else:
            # bf16 first moment: halves adam's m-state HBM traffic; v
            # stays f32 (variance needs the range); ~+1% step on v5e
            tx = optax.adam(1e-3, mu_dtype=jnp.bfloat16)
            opt = tx.init(params)

            def step(p, o, t):
                loss, g = jax.value_and_grad(
                    lambda p_: llama.loss_fn(p_, {"tokens": t}, cfg))(p)
                u, o = tx.update(g, o, p)
                return optax.apply_updates(p, u), o, loss

        stepj = jax.jit(step, donate_argnums=(0, 1))
        if tag is not None:
            # XLA's own cost model for this variant's whole step
            # (lowering only — before the warmup calls donate the
            # buffers); feeds the MFU numerator when available
            c = extract_cost(stepj.lower(params, opt, tokens))
            if c and c.get("flops"):
                step_flops[tag] = c["flops"]
        for _ in range(3):
            params, opt, loss = stepj(params, opt, tokens)
        jax.block_until_ready(loss)
        t0 = time.perf_counter()
        for _ in range(steps):
            params, opt, loss = stepj(params, opt, tokens)
        jax.block_until_ready(loss)
        return B * S * steps / (time.perf_counter() - t0)

    cfg = llama.LlamaConfig.small(vocab_size=32000)
    # selective remat: save matmul outputs, recompute only elementwise
    # (measured +1.7% over full remat on v5e; compiles where noremat's
    # HBM estimate does not)
    cfg_dots = dataclasses.replace(
        cfg, remat_policy="dots_with_no_batch_dims_saveable")
    # every variant is a uniform (config, make_opt_or_None) pair
    variants = {"remat": (cfg, None),
                "remat_dots_nb": (cfg_dots, None),
                # chunked-vocab xent OVER remat: the [B,S,V] logits never
                # resident at once (llama.chunked_next_token_xent) — the
                # HBM-traffic candidate, A/B'd on real hardware every
                # round (98.6k vs the winner's 100.3-101.1k across
                # same-day runs, 2026-07-31 — close enough to keep
                # watching). The former noremat/chunked-noremat
                # variants are gone: with the bf16-mu adam state donated
                # alongside, noremat's saved activations now exceed v5e
                # HBM (RESOURCE_EXHAUSTED at compile, ~30s of budget per
                # attempt) — measured, not hypothetical
                "chunked8": (dataclasses.replace(cfg, xent_chunks=8),
                             None),
                # hand-fused adam OVER THE WINNING remat policy (same
                # cfg as remat_dots_nb, so the pairwise delta isolates
                # the optimizer pass): the driver-side A/B for the
                # 'optimizer pass' MFU suspect
                "fused_adam": (cfg_dots, fused_adam_for)}
    # a variant that fails (compile, OOM, kernel) fails the phase: a
    # headline picked among the survivors would hide a device fault
    results = {name: measure_cfg(c, make_opt=make_opt, tag=name)
               for name, (c, make_opt) in variants.items()}
    best = max(results, key=results.get)
    tps = results[best]
    # MFU numerator: the winning variant's XLA cost-analysis FLOPs per
    # token when the backend has a cost model, the analytic formula
    # otherwise (version-tolerant fallback — the ledger's discipline)
    if step_flops.get(best):
        fpt, mfu_source = step_flops[best] / (B * S), "xla"
    else:
        fpt, mfu_source = model_flops_per_token(cfg, S), "analytic"
    mfu = tps * fpt / peak_flops
    out = {"value": round(tps, 1), "mfu": round(mfu, 4),
           "train_variant": best, "mfu_source": mfu_source,
           "peak_flops": peak_flops, "peak_source": peak_source,
           "device": _device_id(jax)}
    for name, v in results.items():
        out[f"tokens_per_sec_{name}"] = round(v, 1)
    return out


@contextlib.contextmanager
def _loopback_ps(num_servers: int):
    """Shared scaffolding for the CPU-forced pushpull phases: N loopback
    C++ servers on INDEPENDENTLY verified free ports (free_port()+1 may
    be taken on shared hosts; BYTEPS_SERVER_HOSTS lifts the
    consecutive-port assumption), DMLC_*/BYTEPS_* env, a fresh
    GlobalState, bps.init(). Yields the initialized ``byteps_tpu``
    module; teardown shuts the worker down and joins the servers. One
    definition so a rendezvous/teardown fix lands in every phase at
    once.

    ``bench.py --trace-dir DIR`` (BENCH_TRACE_DIR in phase children):
    ANY phase riding this scaffolding also captures the fused fleet
    Chrome trace (worker spans + wire-sampled server stage spans,
    clock-aligned + rid-linked; docs/timeline.md) and drops it next to
    the JSON result as ``DIR/<phase>[.N].trace.json`` at teardown."""
    _force_cpu()
    import threading

    from byteps_tpu.config import Config
    from byteps_tpu.core.state import GlobalState
    from byteps_tpu.server import run_server
    from byteps_tpu.utils.net import free_port

    trace_dir = os.environ.get("BENCH_TRACE_DIR")
    if trace_dir:
        # full-window worker tracing + server wire sampling, unless the
        # phase itself pinned the knobs (trace_ab owns its own arms)
        os.environ.setdefault("BYTEPS_TRACE_ON", "1")
        os.environ.setdefault("BYTEPS_TRACE_START_STEP", "0")
        os.environ.setdefault("BYTEPS_TRACE_END_STEP", "1000000000")
        os.environ.setdefault("BYTEPS_TRACE_SAMPLE", "4")

    ports = []
    while len(ports) < num_servers:
        p = free_port()
        if p not in ports:
            ports.append(p)
    cfg = Config(num_workers=1, num_servers=num_servers)
    os.environ.update({
        "DMLC_NUM_WORKER": "1",
        "DMLC_NUM_SERVER": str(num_servers),
        "DMLC_PS_ROOT_URI": "127.0.0.1",
        "DMLC_PS_ROOT_PORT": str(ports[0]),
        "BYTEPS_SERVER_HOSTS": ",".join(f"127.0.0.1:{p}"
                                        for p in ports),
        "BYTEPS_FORCE_DISTRIBUTED": "1",
    })
    servers = []
    for p in ports:
        t = threading.Thread(target=run_server, args=(p, cfg),
                             daemon=True)
        t.start()
        servers.append(t)
    GlobalState._instance = None
    import byteps_tpu as bps
    bps.init()
    try:
        yield bps
    finally:
        if trace_dir:
            try:
                # BEFORE shutdown: the drain + clock probes need the
                # live client. Several _loopback_ps per phase (A/B
                # arms) each get their own numbered artifact.
                phase = os.environ.get("BENCH_PHASE", "phase")
                os.makedirs(trace_dir, exist_ok=True)
                path = os.path.join(trace_dir, f"{phase}.trace.json")
                n = 1
                while os.path.exists(path):
                    path = os.path.join(trace_dir,
                                        f"{phase}.{n}.trace.json")
                    n += 1
                out = bps.dump_fused_trace(path)
                if out:
                    sys.stderr.write(f"[bench] fused trace: {out}\n")
            except Exception as e:  # noqa: BLE001 - aux artifact
                sys.stderr.write(f"[bench] fused-trace dump failed: "
                                 f"{e!r}\n")
        if trace_dir:
            try:
                # per-phase time-series artifact beside the trace: the
                # same JSONL the SIGTERM hook dumps, renderable
                # post-hoc with `python -m byteps_tpu.tools.top --file`
                from byteps_tpu.core.state import get_state
                ts = get_state().timeseries
                if ts is not None:
                    phase = os.environ.get("BENCH_PHASE", "phase")
                    path = os.path.join(trace_dir,
                                        f"{phase}.timeseries.jsonl")
                    n = 1
                    while os.path.exists(path):
                        path = os.path.join(
                            trace_dir, f"{phase}.{n}.timeseries.jsonl")
                        n += 1
                    out = ts.dump_jsonl(path=path, reason="bench")
                    if out:
                        sys.stderr.write(f"[bench] timeseries: {out}\n")
            except Exception as e:  # noqa: BLE001 - aux artifact
                sys.stderr.write(f"[bench] timeseries dump failed: "
                                 f"{e!r}\n")
        bps.shutdown()
        for t in servers:
            t.join(timeout=20)


def _make_grads(total_bytes: int, n_tensors: int):
    import numpy as np

    per = total_bytes // n_tensors // 4
    rng = np.random.RandomState(0)
    return [rng.randn(per).astype(np.float32) for _ in range(n_tensors)]


def phase_pushpull(total_bytes: int = 256 << 20, n_tensors: int = 16,
                   steps: int = 3) -> dict:
    """push_pull GB/s/chip through the full worker pipeline against a
    loopback C++ server: 256MB of f32 gradients, 4MB partitions, priority
    scheduling, counted as gradient bytes x 2 (push + pull) per second.
    Dense wire + onebit/randomk effective rates. Host-CPU only.

    onebit rides the HOST codec tier (CompressedRegistry -> the C ABI
    native codec, ops/compression/native.py): one fused AVX2 pass per
    compress, wire-form publish on the server — the production host path
    for a CPU worker, and the tier where the 1/32 wire saving must beat
    the dense memcpy wire (it loses when the codec is numpy-bound, the
    round-3 finding). The device tier gets its own phase
    (phase_pushpull_tpu) where compress rides the chip."""
    with _loopback_ps(1) as bps:
        from byteps_tpu.server.compressed import CompressedRegistry

        grads = _make_grads(total_bytes, n_tensors)
        nbytes = sum(g.nbytes for g in grads)

        def best_of(fn) -> float:
            return _best_of(fn, nbytes, steps)

        def round_trip():
            hs = [bps.push_pull_async(g, f"bench_g{i}", average=False)
                  for i, g in enumerate(grads)]
            for h in hs:
                bps.synchronize(h, timeout=300)

        dense_gbps = best_of(round_trip)

        state = bps.core.state.get_state()

        def comp_fn(kwargs, prefix):
            reg = CompressedRegistry(state.ps_client, 1, kwargs)

            def comp_round():
                hs = [reg.push_pull_async(state, f"{prefix}{i}", g,
                                          average=False)
                      for i, g in enumerate(grads)]
                for h in hs:
                    bps.synchronize(h, timeout=300)

            return comp_round

        onebit_gbps = best_of(
            comp_fn({"compressor": "onebit"}, "bench_c"))
        # randomk via the same host tier: the server's wire-form
        # (homomorphic) fast path — O(k) summation per push instead of
        # O(n)
        randomk_gbps = best_of(
            comp_fn({"compressor": "randomk", "k": "0.01"}, "bench_r"))
        return {"pushpull_dense_gbps": round(dense_gbps, 3),
                "pushpull_onebit_gbps": round(onebit_gbps, 3),
                "pushpull_randomk_gbps": round(randomk_gbps, 3)}


def _dense_round_gbps(bps, grads, prefix: str, steps: int) -> float:
    nbytes = sum(g.nbytes for g in grads)

    def round_trip():
        hs = [bps.push_pull_async(g, f"{prefix}{i}", average=False)
              for i, g in enumerate(grads)]
        for h in hs:
            bps.synchronize(h, timeout=300)

    return _best_of(round_trip, nbytes, steps)


def phase_pushpull_2srv(total_bytes: int = 256 << 20, n_tensors: int = 16,
                        steps: int = 3) -> dict:
    """Dense push_pull with the key space sharded over TWO loopback
    servers — the raw-throughput form of BASELINE's scaling rule
    (throughput ∝ min(server bw, sum worker bw), reference
    docs/best-practice.md:41-44): on a multi-core host the aggregate rate
    should approach 2x the 1-server phase because each server owns half
    the keys. Loopback caveat: on a 1-core CI host, both servers, the
    worker and the codec share the core, so the ratio reads ~1.0 there —
    the CORE-INDEPENDENT form is phase_pushpull_throttled."""
    with _loopback_ps(2) as bps:
        grads = _make_grads(total_bytes, n_tensors)
        gbps = _dense_round_gbps(bps, grads, "bench2_g", steps)
        return {"pushpull_dense_2srv_gbps": round(gbps, 3)}


def phase_pushpull_throttled(total_bytes: int = 64 << 20,
                             n_tensors: int = 8, steps: int = 2,
                             throttle_mbps: float = 100.0) -> dict:
    """The reference's scaling rule — throughput ∝ min(server bw, worker
    bw), docs/best-practice.md:41-44 — made measurable on ANY host,
    including the 1-core CI box where the raw 2srv phase proves nothing
    (all processes contend for the same core).

    The trick: BYTEPS_SERVER_THROTTLE_MBPS makes the SERVER the
    bottleneck by construction — its token bucket SLEEPS the serving
    thread, yielding the core — so the measurement is the protocol's
    response to server bandwidth, not to host CPU. One server capped at
    T: the worker's effective rate reads ~T. Two servers, each capped at
    T, splitting the key space: ~2T. The pair of keys demonstrates the
    rule; the ratio (≈2x) is the evidence the raw-throughput phase
    cannot produce here."""
    def measure(num_servers: int) -> float:
        with _loopback_ps(num_servers) as bps:
            grads = _make_grads(total_bytes, n_tensors)
            return _dense_round_gbps(bps, grads, f"thr{num_servers}_g",
                                     steps)

    # scope the throttle to this phase's servers: under the orchestrator
    # each phase is its own subprocess, but an in-process caller (tests
    # importing bench, future phase reordering inside one child) must
    # not inherit a lingering cap on every later loopback server
    prior = os.environ.get("BYTEPS_SERVER_THROTTLE_MBPS")
    os.environ["BYTEPS_SERVER_THROTTLE_MBPS"] = str(throttle_mbps)
    try:
        one = measure(1)
        two = measure(2)
    finally:
        if prior is None:
            del os.environ["BYTEPS_SERVER_THROTTLE_MBPS"]
        else:
            os.environ["BYTEPS_SERVER_THROTTLE_MBPS"] = prior
    return {"pushpull_throttled_1srv_gbps": round(one, 3),
            "pushpull_throttled_2srv_gbps": round(two, 3),
            "throttle_mbps": throttle_mbps}


def phase_churn_ab(n_tensors: int = 6, elems: int = 4096,
                   rounds: int = 5, drop_rate: float = 0.25) -> dict:
    """Idempotence-under-chaos A/B (docs/fault-tolerance.md): the SAME
    deterministic push_pull schedule runs against (a) a server that
    deterministically drops ``drop_rate`` of its aggregate replies
    (BYTEPS_CHAOS_DROP_REPLY_RATE — every dropped reply forces a client
    ticket timeout + an epoch-stamped retry) and (b) a clean server.
    Evidence is exact, not wall-clock: every aggregation result must be
    BITWISE identical across the two arms (a replayed push that
    double-counted would read 2x), and the ``wire/retries`` counter must
    be >0 in the chaos arm and ==0 in the clean arm — proof the chaos
    actually exercised the replay path rather than silently not firing.
    """
    _force_cpu()
    import numpy as np

    # short ticket expiry so each dropped reply costs ~2s, not the 600s
    # default; latched per process at first native use, which is why
    # this runs in the phase child (fresh process), set before any
    # client exists. Extra retry budget: with several keys in flight a
    # retry's reply can itself be dropped by the deterministic
    # accumulator, so give the budget headroom over the expectation.
    # Scoped save/restore like phase_pushpull_throttled: an in-process
    # caller running several phases must not leak the 2s timeout / 5x
    # retry budget into measurements of the default config (the native
    # timeout stays latched for THIS process either way, but the knob
    # must not escape into spawned children or later Config reads).
    _scoped = {"BYTEPS_CLIENT_TIMEOUT_S": "2", "BYTEPS_WIRE_RETRY": "5"}
    _prior_env = {k: os.environ.get(k) for k in _scoped}
    os.environ.update(_scoped)

    def run_arm(rate: float):
        prior = os.environ.get("BYTEPS_CHAOS_DROP_REPLY_RATE")
        if rate > 0:
            os.environ["BYTEPS_CHAOS_DROP_REPLY_RATE"] = str(rate)
        try:
            with _loopback_ps(1) as bps:
                rng = np.random.RandomState(7)
                grads = [rng.randn(elems).astype(np.float32)
                         for _ in range(n_tensors)]
                out = []
                for r in range(rounds):
                    hs = [bps.push_pull_async(g * (r + 1), f"churn_g{i}",
                                              average=False)
                          for i, g in enumerate(grads)]
                    out.append([np.array(bps.synchronize(h, timeout=120))
                                for h in hs])
                snap = bps.get_metrics()
                retries = int(snap["counters"].get("wire/retries", 0))
                return out, retries
        finally:
            if prior is None:
                os.environ.pop("BYTEPS_CHAOS_DROP_REPLY_RATE", None)
            else:
                os.environ["BYTEPS_CHAOS_DROP_REPLY_RATE"] = prior

    try:
        chaos_out, chaos_retries = run_arm(drop_rate)
        clean_out, clean_retries = run_arm(0.0)
    finally:
        for k, v in _prior_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    identical = all(
        np.array_equal(a, b)
        for ra, rb in zip(chaos_out, clean_out) for a, b in zip(ra, rb))
    return {"churn_ab_identical": bool(identical),
            "churn_ab_chaos_retries": chaos_retries,
            "churn_ab_clean_retries": clean_retries,
            "churn_ab_drop_rate": drop_rate,
            # the headline proof bit: chaos produced retries AND the
            # aggregates stayed bitwise equal to the clean run
            "churn_ab_idempotent_proof": bool(identical
                                              and chaos_retries > 0
                                              and clean_retries == 0)}


def phase_scaleup_ab(n_tensors: int = 8, elems: int = 1 << 20,
                     rounds: int = 5,
                     throttle_mbps: float = 300.0) -> dict:
    """Elastic scale-up churn bench (docs/fault-tolerance.md
    "Elasticity"): run a deterministic push_pull schedule against ONE
    throttled loopback server, then start a SECOND server process-less
    (thread) mid-run, `bps.add_server` it into the live fleet, and keep
    training without restart. Evidence:

    - HARD counter proof the join engaged: ``registry/joins`` == 1 and
      the newcomer holds key bytes (``registry.server_loads()[1]`` > 0);
    - bitwise aggregate parity THROUGH the join (1 worker: every round's
      aggregate equals the pushed tensor — a re-homed key that lost or
      double-folded a round would read wrong);
    - per-step wall steps DOWN after the join: both servers read the
      same ``BYTEPS_SERVER_THROTTLE_MBPS`` cap, so the fleet's
      aggregate bandwidth doubles and the wire-bound step wall must
      drop measurably.
    """
    _force_cpu()
    import statistics
    import threading as _threading

    import numpy as np

    from byteps_tpu.config import Config
    from byteps_tpu.server import run_server
    from byteps_tpu.utils.net import free_port, wait_port

    # scoped throttle BEFORE any server constructs (read per Server
    # instance, so BOTH the initial and the runtime-joined server are
    # capped — the before/after wall ratio measures fleet size, not a
    # faster second server); _loopback_ps owns the rest of the
    # scaffolding (env, rendezvous, teardown, --trace-dir artifacts)
    prior = os.environ.get("BYTEPS_SERVER_THROTTLE_MBPS")
    os.environ["BYTEPS_SERVER_THROTTLE_MBPS"] = str(throttle_mbps)
    server2 = None
    try:
        with _loopback_ps(1) as bps:
            from byteps_tpu.core.state import get_state
            state = get_state()
            rng = np.random.RandomState(5)
            grads = [rng.randn(elems).astype(np.float32)
                     for _ in range(n_tensors)]

            identical = True

            def run_round(r):
                nonlocal identical
                t0 = time.perf_counter()
                hs = [bps.push_pull_async(g * (r + 1), f"su_g{i}",
                                          average=False)
                      for i, g in enumerate(grads)]
                outs = [np.array(bps.synchronize(h, timeout=180))
                        for h in hs]
                dt = (time.perf_counter() - t0) * 1e3
                for g, o in zip(grads, outs):
                    if not np.array_equal(o, g * (r + 1)):
                        identical = False
                return dt

            run_round(0)  # warmup: declare + init barrier, untimed
            before = [run_round(1 + r) for r in range(rounds)]

            # the scale-up: a server started at RUNTIME joins the fleet
            port2 = free_port()
            server2 = _threading.Thread(
                target=run_server,
                args=(port2, Config(num_workers=1, num_servers=1)),
                daemon=True)
            server2.start()
            wait_port(port2)
            new_idx = bps.add_server(f"127.0.0.1:{port2}")
            run_round(1 + rounds)  # warmup: seed the newcomer's stores
            after = [run_round(2 + rounds + r) for r in range(rounds)]

            snap = bps.get_metrics()
            joins = int(snap["counters"].get("registry/joins", 0))
            newcomer_bytes = state.registry.server_loads()[new_idx]
            before_ms = statistics.median(before)
            after_ms = statistics.median(after)
            return {
                "scaleup_before_step_ms": round(before_ms, 2),
                "scaleup_after_step_ms": round(after_ms, 2),
                "scaleup_ratio": round(after_ms / before_ms, 4)
                if before_ms else None,
                "scaleup_joins": joins,
                "scaleup_newcomer_bytes": int(newcomer_bytes),
                "scaleup_identical": bool(identical),
                # the headline proof bit: the join engaged (counter +
                # key residency), numerics held bitwise, and the wall
                # stepped down
                "scaleup_proof": bool(identical and joins == 1
                                      and newcomer_bytes > 0
                                      and after_ms < before_ms),
            }
    finally:
        # the joined server got its SHUTDOWN from _loopback_ps's
        # teardown (the client sends one to every connected server)
        if server2 is not None:
            server2.join(timeout=20)
        if prior is None:
            os.environ.pop("BYTEPS_SERVER_THROTTLE_MBPS", None)
        else:
            os.environ["BYTEPS_SERVER_THROTTLE_MBPS"] = prior


def _codec_train_run(bps, steps: int, layers: int = 4):
    """One deterministic PS train run for the codec-plane A/B: mixed
    4MB + bias leaves through make_ps_train_step, returning (params,
    wire bytes moved, metrics snapshot). Same model/data on every call
    — arm differences come only from env."""
    import jax.numpy as jnp
    import numpy as np
    import optax

    from byteps_tpu.core.state import get_state
    from byteps_tpu.jax.train import make_ps_train_step

    rng = np.random.RandomState(0)
    params = {f"w{i}": _cpu_put(rng.randn(1024, 1024).astype(np.float32))
              for i in range(layers)}
    params.update({f"b{i}": _cpu_put(rng.randn(1024).astype(np.float32))
                   for i in range(layers)})
    batch = _cpu_put(rng.randn(32, 1024).astype(np.float32))

    def loss_fn(p, b):
        h = b
        for i in range(layers):
            h = jnp.tanh(h @ p[f"w{i}"] + p[f"b{i}"])
        return jnp.mean(h * h)

    tx = optax.sgd(1e-3)
    opt = tx.init(params)
    step = make_ps_train_step(loss_fn, tx, get_state().mesh)
    for _ in range(steps):
        params, opt, loss = step(params, opt, batch)
        float(loss)
    snap = bps.get_metrics()
    wire = (snap["counters"].get("wire/push_bytes", 0)
            + snap["counters"].get("wire/pull_bytes", 0))
    host = {k: np.asarray(v) for k, v in params.items()}
    return host, wire, snap


def phase_codec_adapt_ab(steps: int = 10) -> dict:
    """Adaptive codec control plane A/B (core/codec_plane.py) with HARD
    counter evidence, four arms on the loopback PS:

    1. throttled (BYTEPS_SERVER_THROTTLE_MBPS) + BYTEPS_CODEC_ADAPT=1 —
       the profiler classifies the steps PULL-bound, the plane walks the
       ladder: ``codec/switches`` must be > 0 and the run's wire bytes
       must undercut arm 2's;
    2. throttled + adapt off — the dense wire-byte baseline;
    3. unthrottled + adapt on — COMPUTE-bound steps: the plane must NOT
       switch (zero ``codec/switches``);
    4. BYTEPS_CODEC_PIN=lossless vs dense — identical seeds, final
       params BITWISE equal: the lossless tier end-to-end proof.

    Plus a codec-tag mismatch injected at the server (a push tagged
    ``lossless`` against a dense store): must be rejected with a loud
    error, and the store's aggregate must be untouched — never a silent
    mis-fold."""
    _force_cpu()
    import numpy as np

    scoped_keys = ("BYTEPS_CODEC_ADAPT", "BYTEPS_CODEC_PIN",
                   "BYTEPS_SERVER_THROTTLE_MBPS", "BYTEPS_CODEC_UP_ROUNDS",
                   "BYTEPS_CODEC_PULL_RATIO")
    prior = {k: os.environ.get(k) for k in scoped_keys}

    def run(adapt: bool, throttle_mbps: float = 0.0, pin: str = "",
            n_steps: int = steps):
        os.environ["BYTEPS_CODEC_ADAPT"] = "1" if adapt else "0"
        if pin:
            os.environ["BYTEPS_CODEC_PIN"] = pin
        else:
            os.environ.pop("BYTEPS_CODEC_PIN", None)
        if throttle_mbps > 0:
            os.environ["BYTEPS_SERVER_THROTTLE_MBPS"] = str(throttle_mbps)
        else:
            os.environ.pop("BYTEPS_SERVER_THROTTLE_MBPS", None)
        # escalate promptly in the short throttled window; the pull
        # signal must dominate compute clearly before any switch
        os.environ["BYTEPS_CODEC_UP_ROUNDS"] = "2"
        os.environ["BYTEPS_CODEC_PULL_RATIO"] = "1.5"
        with _loopback_ps(1) as bps:
            params, wire, snap = _codec_train_run(bps, n_steps)
            return (params, wire,
                    int(snap["counters"].get("codec/switches", 0)),
                    snap["counters"].get("codec/lossless_bytes_post", 0))

    def tag_mismatch_probe() -> bool:
        """Direct wire probe: a push tagged ``lossless`` against a dense
        store must error-reply (LOUD) and leave the aggregate
        untouched."""
        with _loopback_ps(1) as bps:
            from byteps_tpu.core.state import get_state
            from byteps_tpu.core.types import (
                DataType, RequestType, get_command_type)
            state = get_state()
            cmd = get_command_type(RequestType.DEFAULT_PUSH_PULL,
                                   DataType.FLOAT32)
            g = np.arange(512, dtype=np.float32)
            out = np.asarray(bps.synchronize(
                bps.push_pull_async(g, "tagprobe", average=False)))
            ctx = state.registry.get("tagprobe")
            p = ctx.partitions[0]
            rejected = False
            try:
                state.ps_client.zpush(p.server, p.key, g * 7, cmd,
                                      epoch=(99 << 16),
                                      codec=(1 << 8) | 2)  # lossless tag
            except RuntimeError:
                rejected = True
            buf = np.empty(512, np.float32)
            state.ps_client.zpull(p.server, p.key, buf, cmd)
            # the mis-tagged payload must NOT have folded: the published
            # aggregate is still round 1's
            return rejected and np.array_equal(buf, out)

    try:
        _, adapt_wire, adapt_switches, lossless_post = run(
            True, throttle_mbps=60.0)
        _, dense_wire, _, _ = run(False, throttle_mbps=60.0)
        _, _, clean_switches, _ = run(True, throttle_mbps=0.0)
        pin_params, _, _, _ = run(True, pin="lossless", n_steps=4)
        dense_params, _, _, _ = run(False, n_steps=4)
        bitwise = all(
            pin_params[k].tobytes() == dense_params[k].tobytes()
            for k in pin_params)
        mismatch_rejected = tag_mismatch_probe()
    finally:
        for k, v in prior.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    reduction = adapt_wire / dense_wire if dense_wire else None
    return {
        "codec_adapt_throttled_switches": adapt_switches,
        "codec_adapt_unthrottled_switches": clean_switches,
        "codec_adapt_wire_bytes": int(adapt_wire),
        "codec_dense_wire_bytes": int(dense_wire),
        "codec_adapt_wire_reduction": round(reduction, 4)
        if reduction is not None else None,
        "codec_lossless_bytes_post": int(lossless_post),
        "codec_lossless_bitwise": bool(bitwise),
        "codec_tag_mismatch_rejected": bool(mismatch_rejected),
        # the headline proof bit: the plane escalated under throttle and
        # cut wire bytes, held still unthrottled, the lossless tier is
        # bitwise, and a mis-tagged fold is rejected loudly
        "codec_adapt_proof": bool(
            adapt_switches > 0 and clean_switches == 0
            and reduction is not None and reduction < 0.9
            and bitwise and mismatch_rejected),
    }


def phase_arena_ab(steps: int = 6) -> dict:
    """A/B the persistent host staging arena (core/arena.py,
    BYTEPS_STAGING_ARENA) on the PS train step's steady state: the same
    model/batch trained through the loopback PS with the arena on vs
    off, reporting best-of step wall for each. The arena removes every
    gradient-sized host allocation after warmup (scheduler out slots,
    fused-bucket concat, reply staging) and the drain is
    completion-ordered either way — so the delta isolates the allocator
    traffic. Host-CPU only; also publishes the arena counters so the
    zero-steady-state-allocation claim is auditable from the JSON."""
    import gc

    def run(enabled: bool):
        os.environ["BYTEPS_STAGING_ARENA"] = "1" if enabled else "0"
        with _loopback_ps(1) as bps:
            import jax
            import jax.numpy as jnp
            import numpy as np
            import optax

            from byteps_tpu.core.state import get_state
            from byteps_tpu.jax.train import make_ps_train_step

            rng = np.random.RandomState(0)
            # mixed sizes on purpose: 4MB leaves ride their own keys,
            # sub-fusion leaves exercise the fused-bucket slot.
            # _cpu_put: explicit cpu:0 placement (see its docstring)
            params = {f"w{i}": _cpu_put(
                rng.randn(1024, 1024).astype(np.float32))
                for i in range(4)}
            params.update({f"b{i}": _cpu_put(
                rng.randn(1024).astype(np.float32)) for i in range(4)})
            batch = _cpu_put(rng.randn(32, 1024).astype(np.float32))

            def loss_fn(p, b):
                h = b
                for i in range(4):
                    h = jnp.tanh(h @ p[f"w{i}"] + p[f"b{i}"])
                return jnp.mean(h * h)

            tx = optax.sgd(1e-3)
            opt = tx.init(params)
            step = make_ps_train_step(loss_fn, tx, get_state().mesh)
            for _ in range(2):  # warmup: init-push, jit, slot allocs
                params, opt, loss = step(params, opt, batch)
            float(loss)
            best = float("inf")
            for _ in range(steps):
                gc.collect()  # level the allocator field between rounds
                t0 = time.perf_counter()
                params, opt, loss = step(params, opt, batch)
                float(loss)
                best = min(best, time.perf_counter() - t0)
            return best * 1e3, bps.get_arena_stats()

    prior = os.environ.get("BYTEPS_STAGING_ARENA")
    try:
        on_ms, stats = run(True)
        off_ms, _ = run(False)
    finally:
        if prior is None:
            os.environ.pop("BYTEPS_STAGING_ARENA", None)
        else:
            os.environ["BYTEPS_STAGING_ARENA"] = prior
    return {"arena_on_step_ms": round(on_ms, 2),
            "arena_off_step_ms": round(off_ms, 2),
            "arena_allocs_avoided": stats["allocs_avoided"],
            "arena_bytes_pinned": stats["bytes_pinned"],
            "arena_checkout_conflicts": stats["checkout_conflicts"]}


def phase_metrics_ab(steps: int = 6, reps: int = 3) -> dict:
    """A/B the unified metrics registry (core/metrics.py,
    BYTEPS_METRICS) on the PS train step's steady state: the same
    model/batch trained through the loopback PS with the registry
    recording vs frozen (``BYTEPS_METRICS=0`` turns every instrument op
    into a flag check), reporting best-of step wall for each arm plus
    the overhead as a percentage. The acceptance bar is overhead <= 2%
    of step wall with metrics on in the default config. INTERLEAVED
    reps (the phase_scaling lesson): host-load drift lands on both arms;
    best-of over all reps per arm is the capability number. Host-CPU
    only. Also publishes the last StepReport's stage walls so the
    profiler's own output is auditable from the phase JSON."""
    import gc

    def run(enabled: bool, walls: list):
        os.environ["BYTEPS_METRICS"] = "1" if enabled else "0"
        with _loopback_ps(1) as bps:
            import jax.numpy as jnp
            import numpy as np
            import optax

            from byteps_tpu.core.state import get_state
            from byteps_tpu.jax.train import make_ps_train_step

            rng = np.random.RandomState(0)
            # mixed sizes (the arena_ab layout): 4MB leaves ride their
            # own keys through every instrumented stage, biases keep
            # the fused-bucket path in the measurement
            params = {f"w{i}": _cpu_put(
                rng.randn(1024, 1024).astype(np.float32))
                for i in range(4)}
            params.update({f"b{i}": _cpu_put(
                rng.randn(1024).astype(np.float32)) for i in range(4)})
            batch = _cpu_put(rng.randn(32, 1024).astype(np.float32))

            def loss_fn(p, b):
                h = b
                for i in range(4):
                    h = jnp.tanh(h @ p[f"w{i}"] + p[f"b{i}"])
                return jnp.mean(h * h)

            tx = optax.sgd(1e-3)
            opt = tx.init(params)
            step = make_ps_train_step(loss_fn, tx, get_state().mesh)
            for _ in range(2):  # warmup: init-push, jit, slot allocs
                params, opt, loss = step(params, opt, batch)
            float(loss)
            for _ in range(steps):
                gc.collect()
                t0 = time.perf_counter()
                params, opt, loss = step(params, opt, batch)
                float(loss)
                walls.append(time.perf_counter() - t0)
            return bps.get_metrics()

    prior = os.environ.get("BYTEPS_METRICS")
    on_walls, off_walls, snap = [], [], None
    try:
        for _ in range(reps):
            snap = run(True, on_walls)
            run(False, off_walls)
    finally:
        if prior is None:
            os.environ.pop("BYTEPS_METRICS", None)
        else:
            os.environ["BYTEPS_METRICS"] = prior
    on_ms = min(on_walls) * 1e3
    off_ms = min(off_walls) * 1e3
    last = (snap.get("steps") or {}).get("last") or {}
    return {"metrics_on_step_ms": round(on_ms, 2),
            "metrics_off_step_ms": round(off_ms, 2),
            "metrics_overhead_pct": round(
                (on_ms - off_ms) / off_ms * 100.0, 2) if off_ms else None,
            "metrics_last_step_report": {
                k: (round(v, 3) if isinstance(v, float) else v)
                for k, v in last.items()}}


def phase_trace_ab(steps: int = 6, reps: int = 3) -> dict:
    """A/B the fleet observability trace plane (BYTEPS_TRACE_SAMPLE +
    BYTEPS_TRACE_ON; docs/timeline.md): the same model/batch trained
    through the loopback PS with full worker tracing + every-8th-
    request server wire sampling vs both off, INTERLEAVED reps
    (host-load drift lands on both arms), best-of step wall per arm.
    The acceptance bar is sampling overhead <= 2% of step wall. The ON
    arm also proves the plane ENGAGED (not vacuously cheap): the
    server's trace ring must hold records (drained over the wire
    control op) and the fused dump must carry rid flow links."""
    import gc
    import json as _json
    import tempfile

    def run(enabled: bool, walls: list, proof: dict):
        os.environ["BYTEPS_TRACE_ON"] = "1" if enabled else "0"
        os.environ["BYTEPS_TRACE_START_STEP"] = "0"
        os.environ["BYTEPS_TRACE_END_STEP"] = "1000000000"
        os.environ["BYTEPS_TRACE_SAMPLE"] = "8" if enabled else "0"
        with _loopback_ps(1) as bps:
            import jax.numpy as jnp
            import numpy as np
            import optax

            from byteps_tpu.core.state import get_state
            from byteps_tpu.jax.train import make_ps_train_step

            rng = np.random.RandomState(0)
            # the metrics_ab layout: 4MB leaves ride their own keys
            # through every traced stage, biases keep the fused bucket
            params = {f"w{i}": _cpu_put(
                rng.randn(1024, 1024).astype(np.float32))
                for i in range(4)}
            params.update({f"b{i}": _cpu_put(
                rng.randn(1024).astype(np.float32)) for i in range(4)})
            batch = _cpu_put(rng.randn(32, 1024).astype(np.float32))

            def loss_fn(p, b):
                h = b
                for i in range(4):
                    h = jnp.tanh(h @ p[f"w{i}"] + p[f"b{i}"])
                return jnp.mean(h * h)

            tx = optax.sgd(1e-3)
            opt = tx.init(params)
            step = make_ps_train_step(loss_fn, tx, get_state().mesh)
            for _ in range(2):  # warmup: init-push, jit, slot allocs
                params, opt, loss = step(params, opt, batch)
            float(loss)
            for _ in range(steps):
                gc.collect()
                t0 = time.perf_counter()
                params, opt, loss = step(params, opt, batch)
                float(loss)
                walls.append(time.perf_counter() - t0)
            if enabled and not proof:
                state = get_state()
                st = state.ps_client.server_stats(0, timeout_s=5)
                proof["server_records"] = int(
                    st["trace_records"]) if st else 0
                tmp = os.path.join(tempfile.mkdtemp(prefix="bpstr"),
                                   "fused.json")
                out = bps.dump_fused_trace(tmp)
                links = 0
                if out:
                    with open(out) as f:
                        links = _json.load(f).get(
                            "metadata", {}).get("rid_flow_links", 0)
                proof["rid_links"] = int(links)

    keys = ("BYTEPS_TRACE_ON", "BYTEPS_TRACE_START_STEP",
            "BYTEPS_TRACE_END_STEP", "BYTEPS_TRACE_SAMPLE")
    prior = {k: os.environ.get(k) for k in keys}
    on_walls, off_walls, proof = [], [], {}
    try:
        for _ in range(reps):
            run(True, on_walls, proof)
            run(False, off_walls, {})
    finally:
        for k, v in prior.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    on_ms = min(on_walls) * 1e3
    off_ms = min(off_walls) * 1e3
    return {"trace_on_step_ms": round(on_ms, 2),
            "trace_off_step_ms": round(off_ms, 2),
            "trace_overhead_pct": round(
                (on_ms - off_ms) / off_ms * 100.0, 2) if off_ms else None,
            "trace_server_records": proof.get("server_records"),
            "trace_rid_links": proof.get("rid_links")}


def phase_ledger_ab(steps: int = 6, reps: int = 3) -> dict:
    """A/B the step efficiency ledger (core/ledger.py, BYTEPS_LEDGER)
    on the PS train step's steady state: the same model/batch trained
    through the loopback PS with the ledger pricing every step (cost-
    model lowering, wire-span overlap accounting, wire byte deltas,
    observer archive hook) vs BYTEPS_LEDGER=0, INTERLEAVED reps
    (host-load drift lands on both arms), best-of step wall per arm.
    The acceptance bar is overhead <= 2% of step wall. The ON arm also
    proves the ledger ENGAGED (not vacuously cheap): the last
    StepReport must carry non-null ``mfu``/``overlap_frac``/
    ``wire_efficiency`` and the step diagnosis must name the
    efficiency verdict."""
    import gc

    def run(enabled: bool, walls: list, proof: dict):
        os.environ["BYTEPS_LEDGER"] = "1" if enabled else "0"
        with _loopback_ps(1) as bps:
            import jax.numpy as jnp
            import numpy as np
            import optax

            from byteps_tpu.core.state import get_state
            from byteps_tpu.jax.train import make_ps_train_step

            rng = np.random.RandomState(0)
            # the metrics_ab layout: 4MB leaves ride their own keys
            # through every priced stage, biases keep the fused bucket
            params = {f"w{i}": _cpu_put(
                rng.randn(1024, 1024).astype(np.float32))
                for i in range(4)}
            params.update({f"b{i}": _cpu_put(
                rng.randn(1024).astype(np.float32)) for i in range(4)})
            batch = _cpu_put(rng.randn(32, 1024).astype(np.float32))

            def loss_fn(p, b):
                h = b
                for i in range(4):
                    h = jnp.tanh(h @ p[f"w{i}"] + p[f"b{i}"])
                return jnp.mean(h * h)

            tx = optax.sgd(1e-3)
            opt = tx.init(params)
            step = make_ps_train_step(loss_fn, tx, get_state().mesh)
            for _ in range(2):  # warmup: init-push, jit, cost lowering
                params, opt, loss = step(params, opt, batch)
            float(loss)
            for _ in range(steps):
                gc.collect()
                t0 = time.perf_counter()
                params, opt, loss = step(params, opt, batch)
                float(loss)
                walls.append(time.perf_counter() - t0)
            if enabled and not proof:
                last = bps.get_step_reports()[-1]
                proof["mfu"] = last["mfu"]
                proof["overlap_frac"] = last["overlap_frac"]
                proof["wire_efficiency"] = last["wire_efficiency"]
                led = bps.get_ledger()
                proof["source"] = led.get("source")
                diag = bps.get_metrics()["steps"].get(
                    "last_diagnosis", "")
                proof["verdict"] = "MFU" in diag

    prior = os.environ.get("BYTEPS_LEDGER")
    on_walls, off_walls, proof = [], [], {}
    try:
        for _ in range(reps):
            run(True, on_walls, proof)
            run(False, off_walls, {})
    finally:
        if prior is None:
            os.environ.pop("BYTEPS_LEDGER", None)
        else:
            os.environ["BYTEPS_LEDGER"] = prior
    on_ms = min(on_walls) * 1e3
    off_ms = min(off_walls) * 1e3
    return {"ledger_on_step_ms": round(on_ms, 2),
            "ledger_off_step_ms": round(off_ms, 2),
            "ledger_overhead_pct": round(
                (on_ms - off_ms) / off_ms * 100.0, 2) if off_ms else None,
            "ledger_mfu": proof.get("mfu"),
            "ledger_overlap_frac": proof.get("overlap_frac"),
            "ledger_wire_efficiency": proof.get("wire_efficiency"),
            "ledger_cost_source": proof.get("source"),
            "ledger_verdict_named": proof.get("verdict")}


def phase_health_ab(steps: int = 6, reps: int = 3) -> dict:
    """A/B the training-health plane (core/health.py + the native
    in-fold statistics pass, BYTEPS_HEALTH) on the PS train step's
    steady state: the same model/batch trained through the loopback PS
    with the fused in-fold stats + drain tap + detector running vs
    BYTEPS_HEALTH=0, INTERLEAVED reps (host-load drift lands on both
    arms), best-of step wall per arm. The acceptance bar is overhead
    <= 2% of step wall. The ON arm also proves the plane ENGAGED (not
    vacuously cheap): the last StepReport must carry a non-null
    ``grad_norm``/``update_ratio_p95`` with zero nonfinite leaves, the
    server's in-fold stat slots (``health_rounds``) must be nonzero,
    and the step diagnosis must name the health verdict."""
    import gc

    def run(enabled: bool, walls: list, proof: dict):
        os.environ["BYTEPS_HEALTH"] = "1" if enabled else "0"
        with _loopback_ps(1) as bps:
            import jax.numpy as jnp
            import numpy as np
            import optax

            from byteps_tpu.core.state import get_state
            from byteps_tpu.jax.train import make_ps_train_step

            rng = np.random.RandomState(0)
            # the metrics_ab layout: 4MB leaves ride their own keys
            # through the drain tap, biases keep the fused bucket
            params = {f"w{i}": _cpu_put(
                rng.randn(1024, 1024).astype(np.float32))
                for i in range(4)}
            params.update({f"b{i}": _cpu_put(
                rng.randn(1024).astype(np.float32)) for i in range(4)})
            batch = _cpu_put(rng.randn(32, 1024).astype(np.float32))

            def loss_fn(p, b):
                h = b
                for i in range(4):
                    h = jnp.tanh(h @ p[f"w{i}"] + p[f"b{i}"])
                return jnp.mean(h * h)

            tx = optax.sgd(1e-3)
            opt = tx.init(params)
            step = make_ps_train_step(loss_fn, tx, get_state().mesh)
            for _ in range(2):  # warmup: init-push, jit, pnorm build
                params, opt, loss = step(params, opt, batch)
            float(loss)
            for _ in range(steps):
                gc.collect()
                t0 = time.perf_counter()
                params, opt, loss = step(params, opt, batch)
                float(loss)
                walls.append(time.perf_counter() - t0)
            if enabled and not proof:
                last = bps.get_step_reports()[-1]
                proof["grad_norm"] = last["grad_norm"]
                proof["update_ratio_p95"] = last["update_ratio_p95"]
                proof["nonfinite_leaves"] = last["nonfinite_leaves"]
                srv = bps.get_metrics().get("server", {})
                proof["infold_rounds"] = srv.get("health_rounds")
                diag = bps.get_metrics()["steps"].get(
                    "last_diagnosis", "")
                proof["verdict"] = "health" in diag.lower()

    prior = os.environ.get("BYTEPS_HEALTH")
    on_walls, off_walls, proof = [], [], {}
    try:
        for _ in range(reps):
            run(True, on_walls, proof)
            run(False, off_walls, {})
    finally:
        if prior is None:
            os.environ.pop("BYTEPS_HEALTH", None)
        else:
            os.environ["BYTEPS_HEALTH"] = prior
    on_ms = min(on_walls) * 1e3
    off_ms = min(off_walls) * 1e3
    return {"health_on_step_ms": round(on_ms, 2),
            "health_off_step_ms": round(off_ms, 2),
            "health_overhead_pct": round(
                (on_ms - off_ms) / off_ms * 100.0, 2) if off_ms else None,
            "health_grad_norm": proof.get("grad_norm"),
            "health_update_ratio_p95": proof.get("update_ratio_p95"),
            "health_nonfinite_leaves": proof.get("nonfinite_leaves"),
            "health_infold_rounds": proof.get("infold_rounds"),
            "health_verdict_named": proof.get("verdict")}


def phase_wire_ab(steps: int = 6, reps: int = 3) -> dict:
    """A/B the fused PUSHPULL wire op (BYTEPS_FUSED_PUSHPULL,
    native/ps.cc PUSHPULL + the completion-reactor client) on the PS
    train step's steady state: the same model/batch trained through the
    loopback PS with the fused single-message round trip vs the two-op
    push+pull pair, INTERLEAVED reps (host-load drift lands on both
    arms), best-of step wall per arm.

    Wall-clock on a 2-core loopback box flakes — both arms move the
    same bytes through the same CPUs — so the phase ALSO carries a
    DETERMINISTIC proof from the ``wire/*`` counters: fused mode must
    send exactly HALF the request messages per round (one PUSHPULL vs a
    push + a pull per partition), asserted hard; payload bytes must
    match both ways. The JSON reports both walls, both message counts
    and the ratio."""
    import gc

    def run(fused: bool, walls: list):
        os.environ["BYTEPS_FUSED_PUSHPULL"] = "1" if fused else "0"
        with _loopback_ps(1) as bps:
            import jax.numpy as jnp
            import numpy as np
            import optax

            from byteps_tpu.core.state import get_state
            from byteps_tpu.jax.train import make_ps_train_step

            rng = np.random.RandomState(0)
            # the metrics_ab layout: 4MB leaves ride their own keys,
            # biases keep the fused-bucket path in the measurement
            params = {f"w{i}": _cpu_put(
                rng.randn(1024, 1024).astype(np.float32))
                for i in range(4)}
            params.update({f"b{i}": _cpu_put(
                rng.randn(1024).astype(np.float32)) for i in range(4)})
            batch = _cpu_put(rng.randn(32, 1024).astype(np.float32))

            def loss_fn(p, b):
                h = b
                for i in range(4):
                    h = jnp.tanh(h @ p[f"w{i}"] + p[f"b{i}"])
                return jnp.mean(h * h)

            tx = optax.sgd(1e-3)
            opt = tx.init(params)
            step = make_ps_train_step(loss_fn, tx, get_state().mesh)
            for _ in range(2):  # warmup: init-push, jit, slot allocs
                params, opt, loss = step(params, opt, batch)
            float(loss)
            for _ in range(steps):
                gc.collect()
                t0 = time.perf_counter()
                params, opt, loss = step(params, opt, batch)
                float(loss)
                walls.append(time.perf_counter() - t0)
            return bps.get_metrics()["counters"]

    prior = os.environ.get("BYTEPS_FUSED_PUSHPULL")
    on_walls, off_walls = [], []
    c_on = c_off = None
    try:
        for _ in range(reps):
            c_on = run(True, on_walls)
            c_off = run(False, off_walls)
    finally:
        if prior is None:
            os.environ.pop("BYTEPS_FUSED_PUSHPULL", None)
        else:
            os.environ["BYTEPS_FUSED_PUSHPULL"] = prior
    fused_msgs = c_on["wire/pushpull_requests"] + \
        c_on["wire/push_requests"] + c_on["wire/pull_requests"]
    twoop_msgs = c_off["wire/pushpull_requests"] + \
        c_off["wire/push_requests"] + c_off["wire/pull_requests"]
    # the deterministic wire-efficiency proof (counters from the LAST
    # rep of each arm — identical round counts by construction)
    assert c_off["wire/pushpull_requests"] == 0, c_off
    assert c_on["wire/push_requests"] == 0, c_on
    assert fused_msgs * 2 == twoop_msgs, (fused_msgs, twoop_msgs)
    assert c_on["wire/push_bytes"] == c_off["wire/push_bytes"], \
        (c_on, c_off)
    return {"wire_fused_step_ms": round(min(on_walls) * 1e3, 2),
            "wire_twoop_step_ms": round(min(off_walls) * 1e3, 2),
            "wire_fused_requests": int(fused_msgs),
            "wire_twoop_requests": int(twoop_msgs),
            "wire_request_ratio": round(fused_msgs / twoop_msgs, 4),
            "wire_half_proof": True}


# --------------------------------------------------------------------------
# Cross-host wire-rate A/B (PR 17): batched submission rings + striped
# data connections + decompress-on-the-fabric. The BYTEPS_WIRE_RING /
# BYTEPS_WIRE_STRIPES knobs are LATCHED per process in the native lib,
# so unlike the in-process env flips above, every arm runs as a fresh
# server SUBPROCESS + worker SUBPROCESS pair over real loopback TCP
# (BYTEPS_ENABLE_IPC=0 — the shm descriptor tier would bypass the wire
# entirely). Two real OS processes per arm is also exactly the shape
# the acceptance criterion names ("2-process TCP (non-shm) bench arm").
# --------------------------------------------------------------------------

_STRIPE_SRV = r"""
import os, sys
sys.path.insert(0, os.environ["BPS_REPO"])
from byteps_tpu.config import Config
from byteps_tpu.server import run_server
run_server(int(os.environ["BPS_PORT"]), Config(num_workers=1,
                                               num_servers=1))
"""

_STRIPE_WRK = r"""
import json, os, sys, threading, time
sys.path.insert(0, os.environ["BPS_REPO"])
import numpy as np
from byteps_tpu.config import Config
from byteps_tpu.core.registry import TensorRegistry
from byteps_tpu.core.types import DataType, RequestType, get_command_type
from byteps_tpu.server.client import PSClient
from byteps_tpu.server.compressed import CompressedTensor
from byteps_tpu.utils.net import wait_port

port = int(os.environ["BPS_PORT"])
mode = os.environ["BPS_STRIPE_MODE"]          # dense | lossless
total = int(os.environ["BPS_STRIPE_BYTES"])
steps = int(os.environ["BPS_STRIPE_STEPS"])
nt = int(os.environ["BPS_STRIPE_NT"])
wait_port(port)
c = PSClient([f"127.0.0.1:{port}"], worker_id=0)
CMD = get_command_type(RequestType.DEFAULT_PUSH_PULL, DataType.FLOAT32)
n = total // (4 * nt)
rng = np.random.RandomState(7)
res = {}

def dense_round(keys, xs, outs, epoch):
    # one bench round = every key's fused PUSHPULL in flight at once
    # (the steady-state shape: the reply ring sees concurrent replies
    # to batch, the striper sees every key's segments interleaved)
    done = threading.Event(); left = [len(keys)]; err = [None]
    lock = threading.Lock()
    def cb(name, e):
        with lock:
            if e is not None and err[0] is None:
                err[0] = e
            left[0] -= 1
            if left[0] == 0:
                done.set()
    for k, x, o in zip(keys, xs, outs):
        c.zpushpull_async(0, k, x, o, CMD, cb, epoch=epoch)
    assert done.wait(300), "fused round timed out"
    if err[0]:
        raise err[0]

if mode == "dense":
    keys = list(range(100, 100 + nt))
    xs = [rng.randn(n).astype(np.float32) for _ in keys]
    outs = [np.empty_like(x) for x in xs]
    for k, x in zip(keys, xs):
        c.init_key(0, k, np.zeros_like(x), CMD)
    dense_round(keys, xs, outs, 1 << 16)      # warmup + parity check
    for x, o in zip(xs, outs):
        assert np.array_equal(o, x), "single-worker fused parity"
    best = float("inf")
    for s in range(steps):
        t0 = time.perf_counter()
        dense_round(keys, xs, outs, (s + 2) << 16)
        best = min(best, time.perf_counter() - t0)
else:
    # lossless EFFECTIVE rate: low-entropy payload (a 16-value
    # lattice) so the zlib byte-plane codec shrinks the wire bytes the
    # server throttle actually charges for; GB/s counts the
    # dense-equivalent bytes moved, as the onebit/randomk figures do
    reg = TensorRegistry(Config(num_workers=1, num_servers=1))
    lattice = np.linspace(-1.0, 1.0, 16).astype(np.float32)
    cts, xs = [], []
    for i in range(nt):
        ctx = reg.init_tensor(f"sl{i}", n * 4, DataType.FLOAT32)
        cts.append(CompressedTensor(c, ctx, {"compressor": "lossless"},
                                    1))
        xs.append(rng.choice(lattice, size=n).astype(np.float32))
    for ct, x in zip(cts, xs):                # warmup + parity check
        o = np.asarray(ct.push_pull(x, average=False))
        assert o.tobytes() == x.tobytes(), "lossless parity"
    best = float("inf")
    for s in range(steps):
        t0 = time.perf_counter()
        for ct, x in zip(cts, xs):
            ct.push_pull(x, average=False)
        best = min(best, time.perf_counter() - t0)
res["gbps"] = (total * 2 / best) / 1e9

res["transport"] = c.transport_stats()
res["conn_bytes"] = c.stripe_conn_bytes(0)
srv = c.server_stats(0)   # fetched OVER THE WIRE from the server proc
res["server"] = {k: int(srv[k]) for k in (
    "tx_batches", "tx_msgs", "rx_batches", "rx_msgs", "stripe_segs",
    "stripe_bytes", "fused_decode_folds", "reg_blocks", "reg_miss")}
c.close()
print("STRIPE_WRK " + json.dumps(res), flush=True)
"""


def phase_stripe_ab(total_bytes: int = 64 << 20, n_tensors: int = 64,
                    steps: int = 3, reps: int = 2,
                    chunk_bytes: int = 64 << 10,
                    throttle_mbps: float = 20.0) -> dict:
    """A/B the PR-17 cross-host wire plane on the raw fused-PUSHPULL
    loop between two real OS processes over loopback TCP, three dense
    arms INTERLEAVED (host-load drift lands on all of them), best GB/s
    per arm, fresh process pair per run so every counter is per-arm:

    - ``legacy``  — BYTEPS_WIRE_RING=0, stripes off: the per-message
      send/recv path this PR retires;
    - ``ring``    — batched submission/completion rings, single data
      conn: the syscall-batching win in isolation;
    - ``striped`` — rings + BYTEPS_WIRE_STRIPES=4 data conns with
      stripe-aware reassembly: the full plane.

    On a 1-core host the three dense walls read within noise of each
    other — the copies, not the syscalls, set the wall, so the batching
    and striping wins need cores/NIC queues to back them (the
    pushpull_dense_2srv_gbps caveat, same shape). The A/B therefore
    rests on HARD deterministic proofs from the wire counters, checked
    on EVERY run: the striped arm must conserve bytes exactly across
    its conns (sum(per-conn tx) == stripe payload + 72B framing x
    segments, control lane untouched at 0) and the SERVER's reassembly
    counters — fetched over the wire from the other process — must
    mirror the client's split; ring arms must show every reply riding
    a tx batch (tx_batches > 0, legacy pinned to 0: the per-message
    path is RETIRED, not merely preferred — and under the 64-leaf
    concurrent round at least one sendmsg must have coalesced several
    replies); non-striped arms must count zero segments.

    A throttled pair (BYTEPS_SERVER_THROTTLE_MBPS, server-side, so the
    cap binds even on 1 core) then replays the codec story on the new
    plane: the lossless tier's decompress-on-the-fabric path
    (fused_decode_folds > 0, decode straight into the accumulator)
    must move MORE dense-equivalent GB/s than the dense tier under the
    same wire cap."""
    from byteps_tpu.utils.net import free_port

    def run(tag: str, knobs: dict, mode: str, nbytes: int, nt: int,
            throttle: float = 0.0) -> dict:
        port = free_port()
        env = {**os.environ, "BPS_REPO": REPO, "BPS_PORT": str(port),
               "JAX_PLATFORMS": "cpu",
               "BYTEPS_ENABLE_IPC": "0",
               "BYTEPS_STRIPE_CHUNK_BYTES": str(chunk_bytes),
               **knobs}
        env.pop("BYTEPS_SERVER_THROTTLE_MBPS", None)
        if throttle:
            env["BYTEPS_SERVER_THROTTLE_MBPS"] = str(throttle)
        srv = subprocess.Popen([sys.executable, "-c", _STRIPE_SRV],
                               env=env, cwd=REPO)
        try:
            wrk = subprocess.run(
                [sys.executable, "-c", _STRIPE_WRK],
                env={**env, "BPS_STRIPE_MODE": mode,
                     "BPS_STRIPE_BYTES": str(nbytes),
                     "BPS_STRIPE_NT": str(nt),
                     "BPS_STRIPE_STEPS": str(steps)},
                capture_output=True, text=True, timeout=180.0, cwd=REPO)
        finally:
            srv.kill()
            srv.wait()
        assert wrk.returncode == 0, \
            (tag, (wrk.stdout + wrk.stderr)[-4000:])
        for line in reversed(wrk.stdout.splitlines()):
            if line.startswith("STRIPE_WRK "):
                return json.loads(line[len("STRIPE_WRK "):])
        raise AssertionError(f"{tag}: no worker result line")

    def check(tag: str, r: dict, striped: bool, ring: bool,
              lossless: bool) -> None:
        tr, sc = r["transport"], r["server"]
        segs, sbytes = tr["stripe_segs"], tr["stripe_bytes"]
        if striped:
            conn = r["conn_bytes"]
            assert segs > 0, (tag, tr)
            assert conn and conn[0] == 0, (tag, conn)
            assert sum(conn) == sbytes + 72 * segs, (tag, conn, tr)
            assert sc["stripe_segs"] == segs, (tag, sc, tr)
            assert sc["stripe_bytes"] == sbytes, (tag, sc, tr)
        else:
            assert segs == 0 and sbytes == 0, (tag, tr)
        if ring:
            assert sc["tx_batches"] > 0, (tag, sc)
            assert sc["tx_msgs"] >= sc["tx_batches"], (tag, sc)
            assert sc["rx_batches"] > 0, (tag, sc)
        else:
            assert sc["tx_batches"] == 0, (tag, sc)
            assert sc["rx_batches"] == 0, (tag, sc)
        if lossless:
            assert sc["fused_decode_folds"] > 0, (tag, sc)
        else:
            assert sc["fused_decode_folds"] == 0, (tag, sc)

    arms = {
        "legacy": {"BYTEPS_WIRE_RING": "0", "BYTEPS_WIRE_STRIPES": "1"},
        "ring": {"BYTEPS_WIRE_RING": "1", "BYTEPS_WIRE_STRIPES": "1"},
        "striped": {"BYTEPS_WIRE_RING": "1", "BYTEPS_WIRE_STRIPES": "4"},
    }
    best = {name: 0.0 for name in arms}
    last: dict = {}
    for _ in range(reps):
        for name, knobs in arms.items():
            r = run(name, knobs, "dense", total_bytes, n_tensors)
            check(name, r, striped=(name == "striped"),
                  ring=(name != "legacy"), lossless=False)
            best[name] = max(best[name], r["gbps"])
            last[name] = r

    # throttled pair on the full plane (16MB set in 8 leaves: 2MB
    # clears the 2x-chunk striping floor, and the cap, not the host,
    # sets the wall). Lossless rides the two-op compressed wire — its
    # zero stripe segments double as the never-stripes regression guard.
    thr_bytes, thr_nt = 16 << 20, 8
    thr_dense = thr_lossless = 0.0
    for _ in range(reps):
        rd = run("thr_dense", arms["striped"], "dense", thr_bytes,
                 thr_nt, throttle_mbps)
        check("thr_dense", rd, striped=True, ring=True, lossless=False)
        thr_dense = max(thr_dense, rd["gbps"])
        rl = run("thr_lossless", arms["striped"], "lossless", thr_bytes,
                 thr_nt, throttle_mbps)
        check("thr_lossless", rl, striped=False, ring=True,
              lossless=True)
        thr_lossless = max(thr_lossless, rl["gbps"])

    # coalescing evidence from the dense concurrent round: 4 rounds x
    # 64 in-flight replies — if every one of those ~256 replies went
    # out as a solo batch, the ring never coalesced and the syscall
    # story is hollow (the throttled arms run only 8 leaves, so the
    # pin sits on the dense arms where the pressure is real)
    for name in ("ring", "striped"):
        sc = last[name]["server"]
        assert sc["tx_msgs"] > sc["tx_batches"], (name, sc)
    sc = last["striped"]["server"]
    return {
        "stripe_ab_legacy_gbps": round(best["legacy"], 3),
        "stripe_ab_ring_gbps": round(best["ring"], 3),
        "stripe_ab_striped_gbps": round(best["striped"], 3),
        "stripe_ab_speedup": round(best["striped"] / best["legacy"], 3),
        "stripe_ab_segs": sc["stripe_segs"],
        "stripe_ab_msgs_per_batch": round(
            sc["tx_msgs"] / max(1, sc["tx_batches"]), 2),
        "stripe_ab_conservation": True,
        "stripe_ab_throttled_dense_gbps": round(thr_dense, 3),
        "stripe_ab_throttled_lossless_gbps": round(thr_lossless, 3),
        "stripe_ab_lossless_gain": round(
            thr_lossless / max(thr_dense, 1e-9), 3),
        "stripe_ab_throttle_mbps": throttle_mbps,
    }


def phase_fold_ab(total_bytes: int = 96 << 20, n_tensors: int = 8,
                  steps: int = 3, reps: int = 2) -> dict:
    """A/B the native data plane's SIMD fold (BYTEPS_SIMD,
    native/ps.cc runtime-dispatched AVX-512/AVX2 vs the scalar loop)
    on the raw dense pushpull loop against a loopback server —
    INTERLEAVED reps (host-load drift lands on both arms), best-of
    GB/s per arm, fresh server per run so the counters are per-arm.

    Wall-clock on a 1-2 core loopback box flakes, so the phase ALSO
    carries a HARD deterministic proof from the server's per-stage
    counters (`server.fold_bytes`, bps_server_stats): both arms must
    fold EXACTLY the same payload bytes — same tensors, same rounds —
    asserted hard, so a faster wall can never come from silently
    folding less. The JSON reports both walls, the active SIMD tier,
    the zero-copy tier engagement (direct_recvs / oob_msgs), and the
    refreshed dense GB/s from the zero-copy path."""
    def run(simd: bool, out: dict) -> float:
        os.environ["BYTEPS_SIMD"] = "auto" if simd else "scalar"
        with _loopback_ps(1) as bps:
            grads = _make_grads(total_bytes, n_tensors)
            gbps = _dense_round_gbps(bps, grads,
                                     "fold" + ("s" if simd else "x"),
                                     steps)
            srv = bps.get_metrics()["server"]
            arm = out.setdefault("simd" if simd else "scalar", {})
            # fresh server per run: end-state counters are this run's
            arm["fold_bytes"] = int(srv["fold_bytes"])
            arm["tier"] = int(srv["simd_tier"])
            arm["direct_recvs"] = int(srv["direct_recvs"])
            arm["oob_msgs"] = int(srv["oob_msgs"])
            return gbps

    prior = os.environ.get("BYTEPS_SIMD")
    arms: dict = {}
    simd_gbps, scalar_gbps = [], []
    try:
        for _ in range(reps):
            simd_gbps.append(run(True, arms))
            scalar_gbps.append(run(False, arms))
    finally:
        if prior is None:
            os.environ.pop("BYTEPS_SIMD", None)
        else:
            os.environ["BYTEPS_SIMD"] = prior
    # HARD equal-work proof: identical tensors and rounds per arm
    assert arms["simd"]["fold_bytes"] == arms["scalar"]["fold_bytes"], \
        arms
    assert arms["scalar"]["tier"] == 0, arms
    return {"fold_simd_gbps": round(max(simd_gbps), 3),
            "fold_scalar_gbps": round(max(scalar_gbps), 3),
            "fold_simd_tier": arms["simd"]["tier"],
            "fold_bytes_per_arm": arms["simd"]["fold_bytes"],
            "fold_bytes_equal": True,
            "fold_direct_recvs": arms["simd"]["direct_recvs"],
            "fold_oob_msgs": arms["simd"]["oob_msgs"]}


def phase_shard_ab(steps: int = 6, reps: int = 3) -> dict:
    """A/B the locality-sharded export/import path
    (BYTEPS_LOCAL_SHARD_EXPORT, jax/train.py): reduce-scatter → push
    shard → update shard → all-gather vs the whole-leaf psum path, on
    an 8-virtual-device CPU mesh through the loopback PS. INTERLEAVED
    reps, best-of step wall per arm.

    Wall-clock on a shared CPU box flakes, so the phase carries a HARD
    DETERMINISTIC proof from the ``export/*`` + ``wire/*`` counters
    (the wire_ab pattern): with shard export on, the bytes any single
    device exports for the shard-eligible leaves must be EXACTLY
    1/local_size of what the whole-leaf arm exports from its one
    device — the weight leaves are sized divisible by local_size so
    the equalities are integer-exact — while total wire payload bytes
    match both ways (shards re-concatenate to the same leaves). All
    counters are deltas taken after warmup, so init-push traffic and
    compile noise never enter the proof."""
    import gc

    # the virtual 8-device mesh must exist BEFORE jax initializes its
    # CPU backend in this child (the phase subprocess is fresh, so this
    # cannot leak into other phases); on 1 device there is no locality
    # axis and the A/B would be vacuous
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()

    def run(enabled: bool, walls: list):
        os.environ["BYTEPS_LOCAL_SHARD_EXPORT"] = "1" if enabled else "0"
        with _loopback_ps(1) as bps:
            import jax.numpy as jnp
            import numpy as np
            import optax

            from byteps_tpu.core.state import get_state
            from byteps_tpu.jax.train import make_ps_train_step

            local_size = int(get_state().mesh.shape.get("dp", 1))
            rng = np.random.RandomState(0)
            # 4MB weight leaves, element counts divisible by the mesh
            # size (1024*1024 % 8 == 0): the per-shard keys carry zero
            # padding, so the counter equalities below are exact;
            # biases keep the fused-bucket (whole-leaf) path in the
            # same round. UNcommitted placement (jnp.asarray, not
            # _cpu_put): an array committed to cpu:0 is rejected by the
            # 8-device shard_map, and this child already CPU-forced the
            # whole process — the mixed-backend hazard _cpu_put guards
            # against cannot arise here
            params = {f"w{i}": jnp.asarray(
                rng.randn(1024, 1024).astype(np.float32))
                for i in range(4)}
            params.update({f"b{i}": jnp.asarray(
                rng.randn(1024).astype(np.float32)) for i in range(4)})
            batch = jnp.asarray(rng.randn(32, 1024).astype(np.float32))

            def loss_fn(p, b):
                h = b
                for i in range(4):
                    h = jnp.tanh(h @ p[f"w{i}"] + p[f"b{i}"])
                return jnp.mean(h * h)

            tx = optax.adam(1e-3)
            opt = tx.init(params)
            step = make_ps_train_step(loss_fn, tx, get_state().mesh)
            for _ in range(2):  # warmup: init-push, jit, slot allocs
                params, opt, loss = step(params, opt, batch)
            float(loss)
            c0 = dict(bps.get_metrics()["counters"])
            s0 = bps.get_arena_stats()["export_shard_leaves"]
            for _ in range(steps):
                gc.collect()
                t0 = time.perf_counter()
                params, opt, loss = step(params, opt, batch)
                float(loss)
                walls.append(time.perf_counter() - t0)
            c1 = dict(bps.get_metrics()["counters"])
            delta = {k: c1.get(k, 0) - c0.get(k, 0) for k in c1}
            delta["_shard_leaves"] = (
                bps.get_arena_stats()["export_shard_leaves"] - s0)
            delta["_local_size"] = local_size
            return delta

    prior = os.environ.get("BYTEPS_LOCAL_SHARD_EXPORT")
    on_walls, off_walls = [], []
    d_on = d_off = None
    try:
        for _ in range(reps):
            d_on = run(True, on_walls)
            d_off = run(False, off_walls)
    finally:
        if prior is None:
            os.environ.pop("BYTEPS_LOCAL_SHARD_EXPORT", None)
        else:
            os.environ["BYTEPS_LOCAL_SHARD_EXPORT"] = prior
    n = d_on["_local_size"]
    shard_bytes = d_on.get("export/shard_bytes", 0)
    # bytes the eligible (weight) leaves exported in the whole-leaf arm
    # = its whole-leaf exports minus the shared bucket traffic (the
    # on-arm's whole bytes ARE exactly that bucket traffic)
    eligible_off = (d_off.get("export/whole_bytes", 0)
                    - d_on.get("export/whole_bytes", 0))
    per_dev_on = d_on.get("export/device_bytes/%d" % (n - 1), 0)
    per_dev_off = d_off.get("export/device_bytes/0", 0)
    # ---- the hard proof ----
    assert d_on["_shard_leaves"] > 0, "shard export never engaged"
    assert d_off.get("export/shard_bytes", 0) == 0, d_off
    # total exported bytes for the eligible leaves match across arms
    # (shards re-concatenate to the leaves; zero padding by sizing)
    assert shard_bytes == eligible_off, (shard_bytes, eligible_off)
    # a single device's shard exports are EXACTLY 1/local_size of the
    # whole-leaf arm's single-device exports for the same leaves
    assert per_dev_on * n == shard_bytes, (per_dev_on, n, shard_bytes)
    # the whole-leaf arm put everything on one device
    assert per_dev_off == d_off.get("export/whole_bytes", 0), d_off
    # same payload bytes on the wire either way
    assert d_on.get("wire/push_bytes", 0) == \
        d_off.get("wire/push_bytes", 0), (d_on, d_off)
    return {"shard_on_step_ms": round(min(on_walls) * 1e3, 2),
            "shard_off_step_ms": round(min(off_walls) * 1e3, 2),
            "shard_local_size": n,
            "shard_bytes_per_device_on": int(per_dev_on),
            "shard_bytes_per_device_off": int(per_dev_off),
            "shard_reduction_ratio": round(per_dev_off / per_dev_on, 2)
            if per_dev_on else None,
            "shard_counter_proof": True,
            "shard_leaves_per_arm": int(d_on["_shard_leaves"])}


def phase_barrier_ab(steps: int = 8, reps: int = 4,
                     slow_ms: int = 10) -> dict:
    """A/B cross-barrier bounded-staleness pipelining
    (BYTEPS_CROSS_BARRIER + BYTEPS_STALENESS, jax/train.py +
    core/scheduler.py + the server's round window) on the PS train
    step: the same model/batch trained with staleness 1 vs the
    synchronous barrier, INTERLEAVED reps, best-of step wall per arm.
    Staleness 1 releases the next step's forward once the front-of-
    model leaves have imported; the tail leaves' PULL→H2D→UPDATE is
    carried across the step boundary and drained under the NEXT step's
    compute, so the end-of-step barrier no longer pays the straggling
    tail. Host-CPU only.

    The server runs under BYTEPS_CHAOS_SLOW_SERVER: the chaos knob
    SLEEPS the serving thread per request, making wire+server time a
    genuinely non-CPU resource (the slow-straggler deployment the
    bounded-staleness window exists for), so the A/B measures barrier
    removal rather than core time-slicing. Two engaged-proofs ride the
    result: the carried-leaf counters must be nonzero (the carry
    actually crossed the step boundary — not a vacuous win) and the
    ledger's ``overlap_frac`` must be strictly UP vs the sync arm (the
    carried drain really ran under compute)."""
    import gc

    def run(enabled: bool, shared: dict):
        os.environ["BYTEPS_CROSS_BARRIER"] = "1" if enabled else "0"
        os.environ["BYTEPS_STALENESS"] = "1" if enabled else "0"
        with _loopback_ps(1) as bps:
            import jax.numpy as jnp
            import numpy as np
            import optax

            from byteps_tpu.core.state import get_state
            from byteps_tpu.jax.train import make_ps_train_step

            rng = np.random.RandomState(0)
            # whole-leaf weights above the fusion threshold: the
            # back-half of the flatten order is carry-eligible; biases
            # ride the fused bucket, which keeps the synchronous drain
            # (exactly the mixed layout a real model presents)
            params = {f"w{i}": _cpu_put(
                rng.randn(768, 768).astype(np.float32))
                for i in range(6)}
            params.update({f"b{i}": _cpu_put(
                rng.randn(768).astype(np.float32)) for i in range(6)})
            batch = _cpu_put(rng.randn(32, 768).astype(np.float32))

            def loss_fn(p, b):
                h = b
                for i in range(6):
                    h = jnp.tanh(h @ p[f"w{i}"] + p[f"b{i}"])
                return jnp.mean(h * h)

            tx = optax.adam(1e-3)
            opt = tx.init(params)
            step = make_ps_train_step(loss_fn, tx, get_state().mesh)
            for _ in range(2):  # warmup: init-push, jit, slot allocs
                params, opt, loss = step(params, opt, batch)
            float(loss)
            for _ in range(steps):
                gc.collect()
                t0 = time.perf_counter()
                params, opt, loss = step(params, opt, batch)
                float(loss)
                shared["walls"].append(time.perf_counter() - t0)
            if hasattr(step, "flush"):  # fold the outstanding carry
                params, opt = step.flush(params, opt)
            m = get_state().metrics
            shared["carried"] += m.counter(
                "barrier/carried_leaves").value
            shared["drained"] += m.counter(
                "barrier/carry_drained").value
            for rep in bps.get_step_reports():
                if rep.get("overlap_frac") is not None:
                    shared["overlaps"].append(rep["overlap_frac"])

    saved = {k: os.environ.get(k) for k in (
        "BYTEPS_CROSS_BARRIER", "BYTEPS_STALENESS",
        "BYTEPS_CHAOS_SLOW_SERVER", "BYTEPS_LOCAL_SHARD_EXPORT")}
    # slow server = the straggler regime; shard export off so the tail
    # keys stay whole-leaf (shard subranges keep the sync drain by
    # design and would leave the carry nothing to take)
    os.environ["BYTEPS_CHAOS_SLOW_SERVER"] = str(slow_ms)
    os.environ["BYTEPS_LOCAL_SHARD_EXPORT"] = "0"
    # INTERLEAVED reps (the phase_scaling lesson): host-load drift on a
    # shared box otherwise lands on one arm only and decides the A/B
    on = {"walls": [], "overlaps": [], "carried": 0, "drained": 0}
    off = {"walls": [], "overlaps": [], "carried": 0, "drained": 0}
    try:
        for _ in range(reps):
            run(True, on)
            run(False, off)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    on_ms = min(on["walls"]) * 1e3
    off_ms = min(off["walls"]) * 1e3
    ov_on = max(on["overlaps"]) if on["overlaps"] else None
    ov_off = max(off["overlaps"]) if off["overlaps"] else None
    return {"barrier_on_step_ms": round(on_ms, 2),
            "barrier_off_step_ms": round(off_ms, 2),
            "barrier_speedup": round(off_ms / on_ms, 3) if on_ms else
            None,
            "barrier_overlap_on_frac": round(ov_on, 4)
            if ov_on is not None else None,
            "barrier_overlap_off_frac": round(ov_off, 4)
            if ov_off is not None else None,
            "barrier_carried_leaves": on["carried"],
            "barrier_carry_drained": on["drained"],
            "barrier_sync_carried_leaves": off["carried"]}


def phase_ts_ab(steps: int = 6, reps: int = 4, slow_ms: int = 5) -> dict:
    """A/B the time-series plane (core/timeseries.py,
    BYTEPS_TIMESERIES) on the PS train step with BOTH de-aggregated
    sources engaged in BOTH arms: BYTEPS_WIRE_STRIPES=2 (per-lane
    stripe series from the STRIPE_PULL/in-process lane probe) and
    cross-barrier staleness 1 under the slow-server chaos knob (the
    staleness-lag series actually carries). ONE loopback process, the
    recorder toggled per interleaved block (plane.enabled — the off
    arm degrades the observer to its one-attribute early return, the
    same cost class BYTEPS_TIMESERIES=0 buys): separate-process arms
    measured 8% run-to-run drift in the SAME arm, an order of
    magnitude above the recorder's real cost. Best-of step wall per
    arm; the acceptance bar is overhead <= 2%. Engaged-proof: the on
    arm must show nonzero per-stripe lane points AND nonzero
    staleness-lag points — a recorder that pays 0% because it
    recorded nothing is not a result. Host-CPU only.

    Estimator: block order ALTERNATES per rep (on/off, off/on, ... —
    process warm-up drift must not systematically favor the
    second-run arm) and the overhead is PAIRED — each rep differences
    its two adjacent block medians, the result is the median of those
    per-rep deltas — so slow machine-load drift cancels pairwise. An
    unpaired min over a chaos-jittered distribution is an extreme
    statistic whose own variance (±5% measured) dwarfs the recorder's
    ~0.1ms real cost."""
    import gc

    saved = {k: os.environ.get(k) for k in (
        "BYTEPS_TIMESERIES", "BYTEPS_CROSS_BARRIER", "BYTEPS_STALENESS",
        "BYTEPS_CHAOS_SLOW_SERVER", "BYTEPS_LOCAL_SHARD_EXPORT",
        "BYTEPS_WIRE_STRIPES", "BYTEPS_ENABLE_IPC")}
    # both arms identical except the recorder flag: stripes pinned to 2
    # data lanes over REAL TCP (the shm loopback upgrade never stripes
    # — the stripe_ab lesson), staleness 1 under the slow-server regime
    # (the carry genuinely crosses the step boundary), shard export off
    # so the tail keys stay whole-leaf (carry-eligible)
    os.environ["BYTEPS_TIMESERIES"] = "1"
    os.environ["BYTEPS_ENABLE_IPC"] = "0"
    os.environ["BYTEPS_WIRE_STRIPES"] = "2"
    os.environ["BYTEPS_CROSS_BARRIER"] = "1"
    os.environ["BYTEPS_STALENESS"] = "1"
    os.environ["BYTEPS_CHAOS_SLOW_SERVER"] = str(slow_ms)
    os.environ["BYTEPS_LOCAL_SHARD_EXPORT"] = "0"
    on_blocks: list = []   # one list of walls per on-block
    off_blocks: list = []
    stats = {"series_count": 0, "stripe_points": 0,
             "staleness_points": 0}
    try:
        with _loopback_ps(1) as bps:
            import jax.numpy as jnp
            import numpy as np
            import optax

            from byteps_tpu.core.state import get_state
            from byteps_tpu.jax.train import make_ps_train_step

            rng = np.random.RandomState(0)
            # the barrier_ab layout: whole-leaf weights above both the
            # fusion threshold AND two stripe chunks (768*768*4 =
            # 2.25MB >= 2MB), so the back half of the flatten order is
            # carry-eligible and every w-leaf stripes across the 2
            # data lanes; biases ride the fused bucket
            params = {f"w{i}": _cpu_put(
                rng.randn(768, 768).astype(np.float32))
                for i in range(6)}
            params.update({f"b{i}": _cpu_put(
                rng.randn(768).astype(np.float32)) for i in range(6)})
            batch = _cpu_put(rng.randn(32, 768).astype(np.float32))

            def loss_fn(p, b):
                h = b
                for i in range(6):
                    h = jnp.tanh(h @ p[f"w{i}"] + p[f"b{i}"])
                return jnp.mean(h * h)

            tx = optax.adam(1e-3)
            opt = tx.init(params)
            step = make_ps_train_step(loss_fn, tx, get_state().mesh)
            for _ in range(2):  # warmup: init-push, jit, slot allocs
                params, opt, loss = step(params, opt, batch)
            float(loss)
            plane = get_state().timeseries
            for rep in range(reps):  # INTERLEAVED blocks, same process
                order = (True, False) if rep % 2 == 0 else (False, True)
                for enabled in order:
                    plane.enabled = enabled
                    walls: list = []
                    (on_blocks if enabled else off_blocks).append(walls)
                    for _ in range(steps):
                        gc.collect()
                        t0 = time.perf_counter()
                        params, opt, loss = step(params, opt, batch)
                        float(loss)
                        walls.append(time.perf_counter() - t0)
            plane.enabled = True
            if hasattr(step, "flush"):  # fold the outstanding carry
                params, opt = step.flush(params, opt)
            ts = bps.get_timeseries()
            series = ts.get("series") or {}
            stats["series_count"] = len(series)
            stats["stripe_points"] = sum(
                len(s["values"]) for n, s in series.items()
                if n.startswith("stripe/"))
            stats["staleness_points"] = sum(
                len(s["values"]) for n, s in series.items()
                if n in ("step/staleness_lag", "step/carry_drain_ms"))
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    def med(vals):
        s = sorted(vals)
        n = len(s)
        return (s[n // 2] if n % 2 else
                (s[n // 2 - 1] + s[n // 2]) / 2.0)

    # paired per-rep deltas: each rep's on-block median minus its
    # temporally adjacent off-block median, then the median delta
    deltas = [med(a) - med(b) for a, b in zip(on_blocks, off_blocks)]
    off_ms = med([w for blk in off_blocks for w in blk]) * 1e3
    delta_ms = med(deltas) * 1e3
    on_ms = off_ms + delta_ms
    return {"ts_on_step_ms": round(on_ms, 2),
            "ts_off_step_ms": round(off_ms, 2),
            "ts_overhead_pct": round(
                delta_ms / off_ms * 100.0, 2) if off_ms else None,
            "ts_series_count": stats["series_count"],
            "ts_stripe_lane_points": stats["stripe_points"],
            "ts_staleness_points": stats["staleness_points"],
            "ts_engaged_proof": bool(stats["stripe_points"] > 0
                                     and stats["staleness_points"] > 0)}


def phase_pushpull_tpu(total_bytes: int = 64 << 20, n_tensors: int = 16,
                       steps: int = 3) -> dict:
    """The PS-worker-on-a-TPU-host measurement the CPU-forced phase
    cannot make: gradients START on the accelerator, the device tier
    compresses ON CHIP, and the D2H hop into the loopback server moves
    wire-sized bytes (SURVEY §7's stage list). Effective GB/s counted in
    dense-equivalent bytes, like the CPU phase. Fails when JAX finds no
    TPU, and when any tier fails.

    All tiers use FRESHLY COMPUTED device gradients (a jitted producer
    re-executed per round): a host-ORIGIN array can be served from the
    runtime's host-side copy without crossing the accelerator link, so
    pushing one would measure that copy, not the device tier — and would
    flatter dense against onebit, whose payloads are always freshly
    computed."""
    import threading

    jax = _setup_device_backend()
    import jax.numpy as jnp
    import numpy as np

    from byteps_tpu.config import Config
    from byteps_tpu.core.state import GlobalState
    from byteps_tpu.jax.device_compression import DeviceCompressor
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
    GlobalState._instance = None
    import byteps_tpu as bps
    bps.init()
    try:
        per = total_bytes // n_tensors // 4
        rng = np.random.RandomState(0)
        base = [jnp.asarray(rng.randn(per).astype(np.float32))
                for i in range(n_tensors)]
        jax.block_until_ready(base)
        nbytes = total_bytes
        state = bps.core.state.get_state()

        # fresh output buffers every round: the scalar argument varies so
        # nothing — XLA or the runtime's host-copy cache — can alias the
        # result back to the host-origin constants
        make = jax.jit(lambda s: [c + s for c in base])
        ctr = [0]

        def fresh_grads():
            ctr[0] += 1
            return make(jnp.float32(ctr[0] * 1e-6))

        def best_of(fn) -> float:
            return _best_of(fn, nbytes, steps)

        # dense device tier: D2H the full freshly-computed f32 gradient,
        # dense wire — the same-phase comparison anchor. Start every
        # copy before the first blocking read so the anchor is not
        # penalized n_tensors round-trip latencies the packed path
        # avoids — the ratio should measure wire bytes, not choreography
        def dense_round():
            gs = fresh_grads()
            for g in gs:
                if hasattr(g, "copy_to_host_async"):
                    g.copy_to_host_async()
            hs = [bps.push_pull_async(np.asarray(g), f"tdense_{i}",
                                      average=False)
                  for i, g in enumerate(gs)]
            for h in hs:
                bps.synchronize(h, timeout=300)

        dense_gbps = best_of(dense_round)

        def comp_tier(kwargs, prefix):
            dc = DeviceCompressor(state.ps_client, 1, kwargs)
            names = [f"{prefix}_{i}" for i in range(n_tensors)]

            def dev_round():
                out = dc.push_pull_leaves(state, names, fresh_grads(),
                                          average=False)
                jax.block_until_ready(out)

            return best_of(dev_round)

        out = {"pushpull_dense_tpu_gbps": round(dense_gbps, 3),
               "device": _device_id(jax)}
        for key, kwargs, prefix in (
                ("pushpull_onebit_tpu_gbps",
                 {"compressor": "onebit"}, "tbench"),
                # randomk on chip: ~1/50 the D2H bytes (k=1% of elements
                # at 8B each — 4B idx + 4B val — vs 4B/elem dense) + the
                # server's O(k) homomorphic sum
                ("pushpull_randomk_tpu_gbps",
                 {"compressor": "randomk", "k": "0.01"}, "trk")):
            out[key] = round(comp_tier(kwargs, prefix), 3)
        return out
    finally:
        bps.shutdown()
        server.join(timeout=20)


def phase_scaling(workers: int = 2, steps: int = 200) -> dict:
    """Scaling efficiency tn/(n*t1) across REAL worker OS processes
    through the loopback PS (the reference's headline metric shape,
    README.md:34-40) — reuses the examples/benchmark_scaling.py harness
    (whose worker template forces the CPU platform itself; on multi-core
    hosts each worker is pinned to its own core).

    Interpretation keys, so the ratio is meaningful on ANY host: on a
    host with fewer cores than workers the WORKER-compute-bound cap is
    cores/workers (1 core, 2 workers -> 0.5) regardless of how good the
    PS is; ``scaling_vs_core_cap`` divides that cap out — the share of
    the worker-compute ceiling actually delivered. The residual folds
    together PS protocol cost AND server CPU contention (the server
    process is not counted in the cap; on hosts with cores >= workers+1
    the workers are pinned to their own cores and the residual is
    protocol cost alone)."""
    _force_cpu()
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "benchmark_scaling",
        os.path.join(REPO, "examples", "benchmark_scaling.py"))
    bs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bs)
    args = bs.build_args([], workers=workers, steps=steps)

    # Estimator (measured attribution, docs/performance.md "scaling
    # residual"): per-worker CPU per step is FLAT 1w->2w and server cost
    # is linear, so the protocol itself delivers ~0.98-1.0 of the core
    # cap; what ate 15-17% in earlier rounds was the estimator — a 10-
    # step (~50-90ms) timed window on a shared 1-core host, sampled
    # sequentially (t1 runs, then tn runs) so host-load drift hit the
    # two configs unequally. Fix: a 200-step steady-state window,
    # INTERLEAVED 1w/Nw reps (drift lands on both configs), best-of-3
    # per config (the ratio of best-of capability numbers is the stable
    # quantity). A transient run failure (worker rendezvous hiccup
    # raises SystemExit) costs that rep only, not the phase.
    t1s, tns, pairs = [], [], []
    for rep in range(3):
        rep_vals = {}
        for cfg_key, vals, fn in (
                ("t1", t1s, lambda: bs.run_config(1, args)),
                ("tn", tns, lambda: bs.run_config(workers, args))):
            try:
                v = fn()
            except (Exception, SystemExit) as e:
                # SystemExit: worker rendezvous hiccup costs the rep
                # only. KeyboardInterrupt deliberately NOT caught — the
                # operator must be able to stop the remaining reps.
                sys.stderr.write(f"[bench] scaling run failed: {e}\n")
                continue
            vals.append(v)
            rep_vals[cfg_key] = v
        # a pair is only a pair when BOTH configs of THIS rep ran:
        # zip-pairing the flat lists would marry rep i's t1 to rep j's
        # tn after asymmetric failures — a cross-load-era ratio, the
        # exact artifact the interleaving exists to remove
        if "t1" in rep_vals and "tn" in rep_vals:
            pairs.append((rep_vals["t1"], rep_vals["tn"]))
    if not t1s or not tns:
        raise RuntimeError("all scaling runs failed")
    try:
        cores = len(os.sched_getaffinity(0))
    except AttributeError:
        cores = os.cpu_count() or 1
    return _scaling_summary(pairs, t1s, tns, workers, cores)


def _scaling_summary(pairs, t1s, tns, workers: int, cores: int) -> dict:
    """Pure estimator over phase_scaling's measurements (unit-tested in
    test_bench.py).

    The headline is the ratio WITHIN each interleaved rep (its t1 and tn
    ran back to back, so load drift lands on both), then best-of over
    reps — the same capability philosophy as _best_of. The former
    ratio-of-best-of-config form could pair a t1 and tn from DIFFERENT
    load eras, re-admitting exactly the drift the interleaving removes
    (measured: rep ratios 0.89-0.98 in one run while ratio-of-maxes
    read 0.89). ``pairs`` holds only reps where BOTH configs ran.

    Per-rep ratios expose the HOST-NOISE floor: on a shared 1-core host
    the same binary spreads ~0.89-0.98 run to run, so a single draw
    must not decide a round — scaling_spread (max-min of per-rep
    efficiency / core cap) is the honesty key: a captured 0.89 with
    spread 0.09 is the estimator's noise band, not a protocol
    regression."""
    eff_reps = [b / (workers * a) for a, b in pairs if a > 0]
    if eff_reps:
        eff = max(eff_reps)
    else:  # no rep completed both configs: fall back to list maxima
        eff = max(tns) / (workers * max(t1s)) if max(t1s) > 0 else 0.0
    cap = min(1.0, cores / workers)
    out = {"scaling_efficiency_2w": round(eff, 4),
           "scaling_host_cores": cores,
           "scaling_core_cap": round(cap, 4),
           "scaling_vs_core_cap": round(eff / cap, 4) if cap else None}
    if cap and len(eff_reps) > 1:
        out["scaling_vs_cap_reps"] = [round(e / cap, 4) for e in eff_reps]
        out["scaling_spread"] = round(
            (max(eff_reps) - min(eff_reps)) / cap, 4)
    return out


_PHASES = {
    "train": phase_train,
    "pushpull": phase_pushpull,
    "pushpull_2srv": phase_pushpull_2srv,
    "pushpull_throttled": phase_pushpull_throttled,
    "churn_ab": phase_churn_ab,
    "scaleup_ab": phase_scaleup_ab,
    "codec_adapt_ab": phase_codec_adapt_ab,
    "arena_ab": phase_arena_ab,
    "metrics_ab": phase_metrics_ab,
    "trace_ab": phase_trace_ab,
    "ledger_ab": phase_ledger_ab,
    "health_ab": phase_health_ab,
    "barrier_ab": phase_barrier_ab,
    "ts_ab": phase_ts_ab,
    "wire_ab": phase_wire_ab,
    "stripe_ab": phase_stripe_ab,
    "fold_ab": phase_fold_ab,
    "shard_ab": phase_shard_ab,
    "pushpull_tpu": phase_pushpull_tpu,
    "scaling": phase_scaling,
}


def _child_main(name: str) -> None:
    """Run one phase and print its result as a marked JSON line. An
    internal watchdog dumps stacks just before the parent's deadline so
    a hang is diagnosable from stderr, not only from the timeout."""
    import faulthandler
    import threading

    budget = float(os.environ.get("BENCH_CHILD_WATCHDOG_S", "0"))
    if budget > 0:
        def _fire():
            sys.stderr.write(f"[bench] watchdog: phase {name!r} made no "
                             f"progress in {budget:.0f}s; dumping stacks\n")
            faulthandler.dump_traceback(file=sys.stderr)
            os._exit(3)

        wd = threading.Timer(budget, _fire)
        wd.daemon = True
        wd.start()
    # name the phase for aux artifacts (--trace-dir's fused traces)
    os.environ["BENCH_PHASE"] = name
    result = _PHASES[name]()
    print(_MARK + json.dumps(result), flush=True)
    # Do not rely on clean interpreter teardown (daemon server threads
    # are still blocked in native code); the result line is already out.
    sys.stdout.flush()
    os._exit(0)


# ---------------------------------------------------------------------------
# Orchestrating parent: stdlib only, hard deadlines, partial results.
# ---------------------------------------------------------------------------


# pid of the phase child currently running, for the SIGTERM handler:
# the driver's `timeout` signals only the parent, and an orphaned child
# group would keep burning the host after the snapshot is flushed
_CURRENT_CHILD = [None]


def _run_phase(name: str, timeout_s: float):
    """Run a phase child in its own process group; on deadline kill the
    whole group (phase children may spawn worker/server grandchildren).
    Returns (result_dict | None, error | None)."""
    t0 = time.time()
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--phase", name],
        stdout=subprocess.PIPE, text=True, start_new_session=True, cwd=REPO,
        env={**os.environ,
             "BENCH_CHILD_WATCHDOG_S": str(max(timeout_s - 20.0, 30.0))})
    _CURRENT_CHILD[0] = proc.pid
    try:
        out, _ = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            proc.kill()
        out, _ = proc.communicate()
        sys.stderr.write(f"[bench] phase {name!r} hit the {timeout_s:.0f}s "
                         f"deadline; killed\n")
        return None, "timeout"
    finally:
        _CURRENT_CHILD[0] = None
    dt = time.time() - t0
    if proc.returncode != 0:
        sys.stderr.write(f"[bench] phase {name!r} exited rc="
                         f"{proc.returncode} after {dt:.0f}s\n")
        return None, f"rc={proc.returncode}"
    for line in reversed((out or "").splitlines()):
        if line.startswith(_MARK):
            sys.stderr.write(f"[bench] phase {name!r} ok in {dt:.0f}s\n")
            return json.loads(line[len(_MARK):]), None
    return None, "no-result-line"


def _perf_gate_summary(baseline_path: str, candidate: dict) -> dict:
    """Noise-aware comparison of this run against a committed baseline
    (ci/perf_gate.py, loaded by path — it is stdlib-only, so the
    parent keeps its never-imports-jax guarantee). Advisory: the
    verdict rides the JSON under ``perf_gate``; the bench exit code is
    unchanged either way."""
    import importlib.util
    try:
        spec = importlib.util.spec_from_file_location(
            "perf_gate", os.path.join(REPO, "ci", "perf_gate.py"))
        pg = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(pg)
        baseline = pg.load_baseline(baseline_path)
        report = pg.compare(candidate, baseline)
        sys.stderr.write(pg.format_report(report) + "\n")
        return pg.summarize(report)
    except Exception as e:  # noqa: BLE001 - advisory, never fatal
        return {"error": f"{type(e).__name__}: {e}"}


def main() -> int:
    # --trace-dir DIR: every phase riding _loopback_ps also emits its
    # fused fleet Chrome trace (docs/timeline.md) next to the JSON
    # result, as DIR/<phase>[.N].trace.json. Exported through the env
    # so phase CHILDREN (separate processes) inherit it.
    # --baseline FILE: after the run, compare the final snapshot
    # against a committed perf baseline with the noise-aware gate
    # (ci/perf_gate.py) and attach the verdict as ``perf_gate``.
    argv = list(sys.argv)
    if "--trace-dir" in argv:
        i = argv.index("--trace-dir")
        if i + 1 >= len(argv):
            sys.stderr.write("bench.py: --trace-dir needs a directory\n")
            sys.exit(2)
        os.environ["BENCH_TRACE_DIR"] = os.path.abspath(argv[i + 1])
        del argv[i:i + 2]
        sys.argv = argv
    baseline_path = None
    if "--baseline" in argv:
        i = argv.index("--baseline")
        if i + 1 >= len(argv):
            sys.stderr.write("bench.py: --baseline needs a JSON file\n")
            sys.exit(2)
        baseline_path = os.path.abspath(argv[i + 1])
        del argv[i:i + 2]
        sys.argv = argv
    if len(sys.argv) >= 3 and sys.argv[1] == "--phase":
        _child_main(sys.argv[2])
        return 0

    t_start = time.time()
    # wall budget of the whole schedule (the caller's window)
    budget_s = float(os.environ.get("BENCH_BUDGET_S", "2100"))

    result = {
        "metric": "llama125m_train_tokens_per_sec",
        "value": None,
        "unit": "tokens/s",
        "vs_baseline": None,
        "mfu": None,
        "pushpull_dense_gbps": None,
        "pushpull_onebit_gbps": None,
        "pushpull_randomk_gbps": None,
        "pushpull_dense_2srv_gbps": None,
        "pushpull_throttled_1srv_gbps": None,
        "pushpull_throttled_2srv_gbps": None,
        "arena_on_step_ms": None,
        "arena_off_step_ms": None,
        "metrics_on_step_ms": None,
        "metrics_off_step_ms": None,
        "metrics_overhead_pct": None,
        "trace_on_step_ms": None,
        "trace_off_step_ms": None,
        "trace_overhead_pct": None,
        "trace_server_records": None,
        "trace_rid_links": None,
        "ledger_on_step_ms": None,
        "ledger_off_step_ms": None,
        "ledger_overhead_pct": None,
        "ledger_mfu": None,
        "ledger_overlap_frac": None,
        "ledger_wire_efficiency": None,
        "health_on_step_ms": None,
        "health_off_step_ms": None,
        "health_overhead_pct": None,
        "health_grad_norm": None,
        "health_infold_rounds": None,
        "barrier_on_step_ms": None,
        "barrier_off_step_ms": None,
        "barrier_speedup": None,
        "barrier_overlap_on_frac": None,
        "barrier_overlap_off_frac": None,
        "barrier_carried_leaves": None,
        "barrier_carry_drained": None,
        "ts_on_step_ms": None,
        "ts_off_step_ms": None,
        "ts_overhead_pct": None,
        "ts_series_count": None,
        "ts_stripe_lane_points": None,
        "ts_staleness_points": None,
        "ts_engaged_proof": None,
        "wire_fused_step_ms": None,
        "wire_twoop_step_ms": None,
        "wire_request_ratio": None,
        "fold_simd_gbps": None,
        "fold_scalar_gbps": None,
        "fold_simd_tier": None,
        "fold_bytes_equal": None,
        "shard_on_step_ms": None,
        "shard_off_step_ms": None,
        "shard_reduction_ratio": None,
        "scaling_efficiency_2w": None,
        "churn_ab_identical": None,
        "churn_ab_chaos_retries": None,
        "churn_ab_clean_retries": None,
        "churn_ab_idempotent_proof": None,
        "scaleup_before_step_ms": None,
        "scaleup_after_step_ms": None,
        "scaleup_ratio": None,
        "scaleup_joins": None,
        "scaleup_newcomer_bytes": None,
        "scaleup_identical": None,
        "scaleup_proof": None,
        "codec_adapt_throttled_switches": None,
        "codec_adapt_unthrottled_switches": None,
        "codec_adapt_wire_reduction": None,
        "codec_lossless_bitwise": None,
        "codec_tag_mismatch_rejected": None,
        "codec_adapt_proof": None,
        "stripe_ab_legacy_gbps": None,
        "stripe_ab_ring_gbps": None,
        "stripe_ab_striped_gbps": None,
        "stripe_ab_speedup": None,
        "stripe_ab_segs": None,
        "stripe_ab_msgs_per_batch": None,
        "stripe_ab_conservation": None,
        "stripe_ab_throttled_dense_gbps": None,
        "stripe_ab_throttled_lossless_gbps": None,
        "stripe_ab_lossless_gain": None,
    }
    errors = {}

    def remaining() -> float:
        return budget_s - (time.time() - t_start)

    # Envelope-proofing: (a) after every phase the CURRENT snapshot is
    # printed as a JSON line tagged "partial" — an external SIGKILL still
    # leaves the last snapshot as the final parseable line; (b) a SIGTERM
    # handler flushes one last snapshot, kills the running phase child's
    # process group, and exits with the signal's code.
    def _snapshot(final: bool = False) -> dict:
        snap = dict(result)
        if errors:
            snap["phase_errors"] = dict(errors)
        if not final:
            snap["partial"] = True
        return snap

    def _flush_partial() -> None:
        print(json.dumps(_snapshot()), flush=True)

    def _on_term(signum, frame):
        sys.stderr.write("[bench] SIGTERM: flushing partial results\n")
        print(json.dumps(_snapshot()), flush=True)
        child = _CURRENT_CHILD[0]
        if child is not None:
            try:
                os.killpg(child, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
        os._exit(128 + signum)  # a killed run did not succeed

    try:
        signal.signal(signal.SIGTERM, _on_term)
    except ValueError:  # not the main thread (in-process test harness)
        pass

    # Schedule: the two device phases first, once each (train is the
    # headline; the device-tier wire phase runs whether or not train
    # landed — a train OOM must not also cost the compression story).
    # Then the CPU-loopback phases, the keys that have never landed in
    # a driver artifact ahead of the long raw pushpull phases.
    for name, timeout_s in (
                            ("train", 440.0),
                            ("pushpull_tpu", 360.0),
                            # throttled pair: ~13s of timed work at the
                            # default 100MB/s cap + 3 server launches
                            ("pushpull_throttled", 180.0),
                            # scaling deadline sized for 6 server+worker
                            # launches (3 interleaved 1w/2w reps,
                            # 200-step windows, best-of-3 per config)
                            ("scaling", 900.0),
                            # chaos idempotence A/B: reply-drop +
                            # epoch-dedup'd retries vs clean, bitwise
                            # equality + retry-counter proof
                            ("churn_ab", 240.0),
                            # elastic scale-up churn: add a server
                            # MID-RUN (runtime join + version-fenced
                            # rebalance), bitwise parity through the
                            # join, wall steps down, counter-proven key
                            # residency on the newcomer — in the
                            # runs-first group (new driver key)
                            ("scaleup_ab", 240.0),
                            # adaptive-codec A/B: ladder escalation
                            # under throttle (switch + wire-byte counter
                            # proof), zero switches unthrottled,
                            # lossless bitwise parity, loud tag-mismatch
                            # rejection — in the runs-first group (a key
                            # that has never landed in a driver
                            # artifact)
                            ("codec_adapt_ab", 300.0),
                            # cross-host wire-plane A/B: per-message
                            # legacy vs batched rings vs rings+striped
                            # conns, 2-process TCP arms with the
                            # byte-conservation + batch counter proofs,
                            # plus the throttled lossless-vs-dense
                            # effective-rate pair — in the runs-first
                            # group (new driver key)
                            ("stripe_ab", 300.0),
                            # SIMD-fold A/B: vectorized vs scalar
                            # server fold on the zero-copy dense path,
                            # with the equal-fold_bytes counter proof —
                            # in the runs-first group (new driver key)
                            ("fold_ab", 240.0),
                            # efficiency-ledger A/B: cost-model pricing
                            # + perf archive on vs BYTEPS_LEDGER=0,
                            # <=2% overhead bar with the engaged-proof
                            # (non-null mfu/overlap/wire-efficiency) —
                            # in the runs-first group (new driver key)
                            ("ledger_ab", 240.0),
                            # training-health A/B: in-fold stats +
                            # drain tap + detector on vs BYTEPS_HEALTH
                            # =0, <=2% overhead bar with the engaged-
                            # proof (non-null grad_norm, nonzero
                            # in-fold health_rounds slot) — in the
                            # runs-first group (new driver key)
                            ("health_ab", 240.0),
                            # time-series-plane A/B: per-step recorder
                            # + stripe-lane/staleness series on vs
                            # BYTEPS_TIMESERIES=0, <=2% overhead bar
                            # with the engaged-proof (nonzero per-lane
                            # + staleness points) — in the runs-first
                            # group (new driver key)
                            ("ts_ab", 240.0),
                            ("pushpull", 420.0),
                            ("pushpull_2srv", 240.0),
                            # staging-arena A/B: two short loopback
                            # train runs (arena on vs off)
                            ("arena_ab", 240.0),
                            # metrics-registry A/B: instrumented vs
                            # frozen (BYTEPS_METRICS=0) step wall — the
                            # <=2% observability-overhead guard
                            ("metrics_ab", 240.0),
                            # fleet-trace A/B: full worker tracing +
                            # server wire sampling vs off — the <=2%
                            # sampling-overhead guard, plus the
                            # engaged-proof (server trace records +
                            # rid flow links in the fused dump)
                            ("trace_ab", 240.0),
                            # cross-barrier bounded-staleness A/B:
                            # staleness 1 vs the sync barrier under the
                            # slow-server chaos knob, with the carried-
                            # leaf counter + overlap_frac engaged-proof
                            # — in the runs-first group (new driver
                            # key)
                            ("barrier_ab", 240.0),
                            # fused PUSHPULL wire-op A/B: one message
                            # vs push+pull pair, plus the deterministic
                            # half-the-request-messages counter proof
                            ("wire_ab", 240.0),
                            # locality-shard A/B: reduce-scatter +
                            # per-device shard export vs whole-leaf,
                            # with the per-device-bytes / local_size
                            # counter proof on an 8-device CPU mesh
                            ("shard_ab", 240.0)):
        # budget gate: skip when the budget is spent, and never grant
        # a deadline past the window
        if remaining() < 45.0:
            errors[name] = "skipped-budget"
            continue
        r, err = _run_phase(name, min(timeout_s,
                                      max(30.0, remaining() - 10.0)))
        if err is None:
            result.update(r)
        else:
            errors[name] = err
        _flush_partial()

    if result["value"] is not None:
        result["vs_baseline"] = round(result["value"]
                                      / BASELINE_TOKENS_PER_SEC, 4)
    if baseline_path:
        result["perf_gate"] = _perf_gate_summary(baseline_path, result)
    print(json.dumps(_snapshot(final=True)), flush=True)
    if errors:
        sys.stderr.write(f"[bench] phases that did not land: "
                         f"{sorted(errors)}\n")
    # exit code 0 means every phase ran and landed
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
