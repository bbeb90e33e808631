#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that byteps_tpu still starts on the chip.

Drives the system's main path once, through the entry points a deployment
uses, at the full published width and depth of BERT-large (24 layers,
hidden 1024, 16 heads, FFN 4096, vocab 30,522; sequence 128, BERT's
phase-1 pre-training shape):

- a server role started as ``python -m byteps_tpu.server`` with the
  ``DMLC_*`` environment (a child process that stays off JAX devices);
- a worker (this process, the ONLY one that touches the chip) that calls
  ``bps.init()``, builds ``make_ps_train_step(loss_fn, tx, mesh)`` and
  takes a few steps on a fixed seeded batch;
- beside it, as the control, the fused in-jit step (``make_train_step``)
  takes the same steps from the same seed. With one worker the server's
  sum is the identity, so the loss after every step and the parameters
  after the last must agree between the two (tolerances below).

It also runs every Pallas kernel the package ships, compiled (never
``interpret=True``), at one BERT-large leaf's size resp. real attention
widths, against its portable reference.

    python chip_smoke.py            # one chip: kernels + PS step vs control
    python chip_smoke.py --chips 4  # four chips: ONLY the sharded PS step
                                    # (reduce-scatter -> per-device shard
                                    # export -> PS -> shard apply ->
                                    # all-gather) and its fused-psum control
    python chip_smoke.py --rehearse # the same code at a tiny size on
                                    # whatever platform JAX has (kernels
                                    # interpreted): a control-flow check
                                    # that can never print the result line

Without ``--rehearse`` it fails (non-zero exit, no result line) unless JAX
reports TPU devices. Any failed phase raises. Its last line of standard
output is the contract's one JSON object; everything else worth reading
(device, native build wall, compile walls, step walls, both loss lists,
the engagement counters) is printed on earlier lines. Walls are
observations on the named device, not benchmark metrics.
"""

from __future__ import annotations

import argparse
import faulthandler
import json
import os
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

SEED = 0
STEPS = 6
SEQ = 128
# per-chip batch, sized from compiled.memory_analysis() of both step
# programs against one v5e chip's 16 GB (see CHANGES.md, PR 21)
BATCH_PER_CHIP = 32
# BERT's published pre-training optimizer (Devlin et al. 2018, section
# A.2): Adam, lr 1e-4, beta 0.9/0.999, L2 weight decay 0.01; epsilon 1e-6
# as in the released optimization.py
LR, EPS, WEIGHT_DECAY = 1e-4, 1e-6, 0.01

# Tolerances. The two runs execute the same model code on the same batch
# from the same parameters, and with one worker the PS transport (export ->
# D2H -> wire -> server sum -> arena -> H2D) is the identity: on the v5e
# (PR 21) every byte it moved came back bitwise equal. What differs is
# the compiled program. The control is ONE
# fused program (backward, psum and Adam update); the PS step is a
# backward program plus update programs. XLA accumulates the f32 sums of
# the bf16 matmuls in another order, and rounds bf16 intermediates at
# other points, when it fuses differently:
#
# - one chip: fused control vs PS differ in one gradient leaf
#   (blocks/w_in) by about one f32 ulp, everything else bitwise;
# - four chips: the fused control differs from the SAME backward and
#   update run as separate programs with no PS at all by 4.4e-2 of one
#   Adam step (bf16-level noise in small gradients, where Adam takes a
#   full +-lr step of either sign), while those separate programs and
#   the full sharded PS path agree to 6.6e-7 (one ulp).
#
# Each later step amplifies the difference: a last-bit change of a
# weight now and then flips its bf16 rounding, and the activations
# behind it move. Hence two checks, both as the RMS parameter difference
# over the WORST block of BLOCK consecutive elements (one 4 MB wire
# partition: a slot or partition that carried a wrong gradient shows up
# whole in one block), as a fraction of the distance Adam can move an
# element:
#
# - after step 1, before any amplification: <= 20% of one Adam step
#   (measured 1.6e-6 on one chip, 4.4e-2 on four; a block fed a wrong
#   gradient reads about 1.4, random signs against the control's);
# - after STEPS steps: <= 10% of STEPS Adam steps (measured 7.2e-3 on
#   one chip, 1.0e-2 on four; a block fed a wrong gradient for one step
#   reads >= 0.2).
BLOCK = 1_024_000
STEP1_TOL = 0.2
FINAL_TOL = 0.1
# the loss is a mean over the masked tokens of a bf16 forward: the same
# amplification reaches it at about 6e-5 relative (measured)
LOSS_RTOL = 1e-3


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# --------------------------------------------------------------------- #
# server child
# --------------------------------------------------------------------- #


class ServerChild:
    """``python -m byteps_tpu.server`` as a deployment starts it. The
    child's environment pins ``JAX_PLATFORMS=cpu``: the chip belongs to
    the worker process alone, and the server role needs no device."""

    def __init__(self, port: int):
        self.port = port
        self._log = tempfile.TemporaryFile(mode="w+")
        env = {**os.environ,
               "DMLC_ROLE": "server", "DMLC_NUM_WORKER": "1",
               "DMLC_NUM_SERVER": "1", "DMLC_PS_ROOT_URI": "127.0.0.1",
               "DMLC_PS_ROOT_PORT": str(port), "JAX_PLATFORMS": "cpu",
               "PYTHONPATH": REPO + os.pathsep
               + os.environ.get("PYTHONPATH", "")}
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "byteps_tpu.server"], cwd=REPO, env=env,
            stdout=self._log, stderr=subprocess.STDOUT)

    def wait_listening(self, timeout_s: float = 60.0) -> float:
        from byteps_tpu.utils.net import wait_port

        t0 = time.perf_counter()
        try:
            wait_port(self.port, timeout_s)
        except RuntimeError as e:
            raise RuntimeError(
                f"{e} (server rc={self.proc.poll()}):\n{self.tail()}")
        return time.perf_counter() - t0

    def tail(self, n: int = 4000) -> str:
        self._log.flush()
        self._log.seek(0)
        return self._log.read()[-n:]

    def wait_exit(self, timeout_s: float = 30.0) -> int:
        """The server exits 0 by itself once its worker sent SHUTDOWN."""
        return self.proc.wait(timeout=timeout_s)

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=10)
        self._log.close()


# --------------------------------------------------------------------- #
# compile-cache / compile-wall accounting (jax.monitoring)
# --------------------------------------------------------------------- #


class CompileLog:
    """Per-program backend compile walls and persistent-cache hit counts,
    from jax.monitoring's own events."""

    def __init__(self):
        from jax import monitoring

        self.walls: list = []   # (fun_name, seconds)
        self.requests = 0
        self.hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.walls.append((kw.get("fun_name", "?"), secs))

    def _event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def report(self, since: int, floor_s: float = 0.5) -> None:
        """Log every compile from index ``since`` on that took at least
        ``floor_s``."""
        for name, secs in self.walls[since:]:
            if secs >= floor_s:
                log(f"  compile wall {secs:7.2f} s  {name}")


# --------------------------------------------------------------------- #
# Pallas kernels vs their portable references
# --------------------------------------------------------------------- #


def check_kernels(rehearse: bool) -> None:
    """Every Pallas kernel the package ships, compiled for the device (or
    interpreted, in a rehearsal), against its portable jnp reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from byteps_tpu.ops import flash_attention as fa
    from byteps_tpu.ops.compression import pallas_kernels as pk
    from byteps_tpu.ops.compression.codecs import (DitheringCodec,
                                                   OnebitCodec)
    from byteps_tpu.ops.compression.rng import (jnp_index_parallel,
                                                uniform_base)

    interp = rehearse
    # one BERT-large block leaf: wq is [24, 1024, 1024] f32
    n = 4096 * 8 if rehearse else 24 * 1024 * 1024
    x = jax.random.normal(jax.random.PRNGKey(SEED), (n,), jnp.float32)

    def timed(label, fn):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn())
        log(f"  kernel {label}: first call {time.perf_counter() - t0:.2f} s "
            f"({'interpreted' if interp else 'compiled'})")
        return out

    # onebit: pack+unpack must reproduce sign(x)*scale bit for bit, and
    # agree with the portable codec's decompressed values (the two word
    # layouts differ; the decoded values must not)
    scale = jnp.mean(jnp.abs(x))
    bits = timed("onebit_pack", lambda: pk.onebit_pack(x, interp))
    got = timed("onebit_unpack",
                lambda: pk.onebit_unpack(bits, scale, n, interp))
    ref_codec = OnebitCodec(size=n, use_pallas=False)
    ref = ref_codec.decompress(ref_codec.compress(x))
    if not bool(jnp.array_equal(got, ref)):
        raise AssertionError("onebit pack/unpack != portable codec")

    # dithering: identical levels (same counter RNG, same op order)
    base = jnp.asarray(uniform_base(SEED, 3))
    for partition in ("linear", "natural"):
        codec = DitheringCodec(size=n, partition=partition, seed=SEED,
                               use_pallas=False)
        want = codec.compress(x, step=3)
        lv = timed(f"dithering_levels[{partition}]",
                   lambda: pk.dithering_levels(
                       x, want["norm"], base, codec.s, partition, interp))
        bad = int(jnp.sum(lv != want["levels"]))
        if bad:
            raise AssertionError(
                f"dithering[{partition}]: {bad}/{n} levels differ from "
                f"the portable codec")

    # randomk: identical indices (integer hash)
    k = max(32768, n // 100)
    idx = timed("randomk_indices",
                lambda: pk.randomk_indices(base, jnp.int32(n), k, interp))
    want_idx = jnp_index_parallel(SEED, k, n, mix=3)
    if not bool(jnp.array_equal(idx, want_idx)):
        raise AssertionError("randomk_indices != jnp_index_parallel")

    # flash attention forward at head dim 64 (BERT/MHA) and 128 (GQA)
    shapes = [((1, 128, 4, 64), 4), ((1, 128, 4, 128), 2)] if rehearse \
        else [((2, 1024, 16, 64), 16), ((2, 1024, 6, 128), 2)]
    for (B, S, H, D), hkv in shapes:
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(SEED + D), 3)
        q = jax.random.normal(kq, (B, S, H, D), jnp.bfloat16)
        kx = jax.random.normal(kk, (B, S, hkv, D), jnp.bfloat16)
        vx = jax.random.normal(kv, (B, S, hkv, D), jnp.bfloat16)
        blk = min(512, S)
        out = timed(f"flash_fwd[hd={D},hkv={hkv}]",
                    lambda: fa._flash_fwd(q, kx, vx, True, blk, blk,
                                          interpret=interp))
        want_o = fa.blockwise_attention(q, kx, vx, causal=True, block_k=blk)
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                    - want_o.astype(jnp.float32))))
        # both round the output to bf16 (2^-8 relative) from f32 sums
        if not (np.isfinite(err) and err <= 3e-2):
            raise AssertionError(
                f"flash_fwd hd={D}: max |err| {err} vs blockwise")
        log(f"  flash_fwd[hd={D}] max |err| vs blockwise = {err:.3e}")
    # forward with the row logsumexp and the two backward kernels (dK/dV,
    # dQ), grouped heads, against the blockwise path and its vjp: a
    # sliding window at head size 128 (the sparse decoder's), and the
    # causal band at head size 64 with four query heads a key head (the
    # sparse hybrid decoder's); and the block-diffusion mask over a
    # noised and a clean copy of a row at head size 128, blocks of 4
    # (the block-diffusion decoder's)
    cases = [(1, 128, 4, 128, 2, 48, None), (1, 128, 8, 64, 2, None, None),
             (1, 128, 4, 128, 2, None, 4)] if rehearse \
        else [(2, 2048, 8, 128, 2, 640, None), (2, 2048, 32, 64, 8, None, None),
              (2, 4096, 8, 128, 1, None, 4)]
    for B, S, H, D, hkv, win, blk_diff in cases:
        blk = min(512, S) if not rehearse else 32
        kq, kk, kv, kg = jax.random.split(jax.random.PRNGKey(SEED + 7 + D), 4)
        q = jax.random.normal(kq, (B, S, H, D), jnp.bfloat16)
        kx = jax.random.normal(kk, (B, S, hkv, D), jnp.bfloat16)
        vx = jax.random.normal(kv, (B, S, hkv, D), jnp.bfloat16)
        g = jax.random.normal(kg, (B, S, H, D), jnp.bfloat16)
        tag = f"hd={D},window={win},diffusion_block={blk_diff}"
        out, lse = timed(f"flash_fwd[{tag}]", lambda: fa._flash_fwd(
            q, kx, vx, True, blk, blk, interpret=interp, window=win,
            with_lse=True, diffusion_block=blk_diff))
        grads = timed(f"flash_bwd[{tag}]", lambda: fa._flash_bwd(
            q, kx, vx, out, lse, g, True, blk, blk, win, interpret=interp,
            diffusion_block=blk_diff))
        want_o, vjp = jax.vjp(lambda a, b, c: fa.blockwise_attention(
            a, b, c, causal=True, block_k=blk, window=win, block_q=blk,
            diffusion_block=blk_diff), q, kx, vx)
        for name, got, want in zip(("out", "dq", "dk", "dv"),
                                   (out,) + tuple(grads),
                                   (want_o,) + vjp(g)):
            got, want = got.astype(jnp.float32), want.astype(jnp.float32)
            # bf16 results of f32 sums; dk and dv sum a group's heads
            err = float(jnp.max(jnp.abs(got - want))
                        / jnp.maximum(jnp.max(jnp.abs(want)), 1e-6))
            if not (np.isfinite(err) and err <= 3e-2):
                raise AssertionError(
                    f"flash[{tag}] {name}: max |err| {err} of the largest "
                    f"entry vs blockwise")
            log(f"  flash[{tag}] {name}: max |err| = {err:.3e} of the "
                f"largest entry")
    # latent attention (the latent-attention decoder's): a score of 128
    # a head plus 64 against ONE rotary key head shared by all, over
    # values of 128; the shared key's gradient is the sum over the heads
    B, S, H, dn, dr, dv = (1, 128, 4, 128, 64, 128) if rehearse \
        else (2, 2048, 32, 128, 64, 128)
    blk = 32 if rehearse else 512
    keys = jax.random.split(jax.random.PRNGKey(SEED + 41), 6)
    q, q_r, kx, k_r, vx, g = (
        jax.random.normal(key, shape, jnp.bfloat16) for key, shape in zip(
            keys, ((B, S, H, dn), (B, S, H, dr), (B, S, H, dn), (B, S, 1, dr),
                   (B, S, H, dv), (B, S, H, dv))))
    out, lse = timed("flash_fwd[mla]", lambda: fa._flash_fwd(
        q, kx, vx, True, blk, blk, interpret=interp, with_lse=True,
        shared=(q_r, k_r)))
    grads = timed("flash_bwd[mla]", lambda: fa._flash_bwd(
        q, kx, vx, out, lse, g, True, blk, blk, interpret=interp,
        shared=(q_r, k_r)))
    want_o, vjp = jax.vjp(lambda a, b, c, d, e: fa.blockwise_attention(
        a, b, c, block_k=blk, block_q=blk, shared=(d, e)),
        q, kx, vx, q_r, k_r)
    for name, got, want in zip(("out", "dq", "dk", "dv", "dq_r", "dk_r"),
                               (out,) + tuple(grads), (want_o,) + vjp(g)):
        got, want = got.astype(jnp.float32), want.astype(jnp.float32)
        err = float(jnp.max(jnp.abs(got - want))
                    / jnp.maximum(jnp.max(jnp.abs(want)), 1e-6))
        if got.shape != want.shape or not (np.isfinite(err) and err <= 3e-2):
            raise AssertionError(
                f"flash[mla] {name}: max |err| {err} of the largest entry "
                f"vs blockwise")
        log(f"  flash[mla] {name}: max |err| = {err:.3e} of the largest "
            f"entry")
    if not rehearse:
        # the public entry must engage the kernel on this platform
        txt = jax.jit(lambda a: fa.flash_attention(a, a, a)).lower(
            jnp.zeros((1, 512, 2, 64), jnp.bfloat16)).compile().as_text()
        if "tpu_custom_call" not in txt:
            raise AssertionError(
                "flash_attention did not lower to the Pallas kernel")
        if not OnebitCodec(size=n)._pallas_active():
            raise AssertionError("OnebitCodec did not select the kernel")
    log("kernels: all matched their portable references")


# --------------------------------------------------------------------- #
# the train phases
# --------------------------------------------------------------------- #


def model_and_batch(rehearse: bool, n_chips: int):
    import jax.numpy as jnp
    import numpy as np

    from byteps_tpu.models import bert

    if rehearse:
        # smallest shape that still has whole-leaf (>= 2 MiB) and
        # bucket-fused leaves under the default thresholds
        cfg = bert.BertConfig(vocab_size=2048, dim=256, n_layers=2,
                              n_heads=4, ffn_dim=1024, max_seq_len=128,
                              remat=False)
        seq, per_chip = 32, 4
    else:
        cfg = bert.BertConfig.bert_large()
        seq, per_chip = SEQ, BATCH_PER_CHIP
    B = per_chip * n_chips
    rng = np.random.RandomState(SEED)
    tokens = rng.randint(0, cfg.vocab_size, (B, seq))
    labels = np.where(rng.rand(B, seq) < 0.15, tokens, -100)
    batch = {"tokens": jnp.asarray(tokens, jnp.int32),
             "labels": jnp.asarray(labels, jnp.int32)}
    return cfg, batch


def run_steps(label: str, step, params, opt, batch, clog: CompileLog):
    """STEPS steps, each timed around block_until_ready (walls are
    logged). Returns the losses, host copies of the parameters after
    step 1 and after the last step, and the final (params, opt) on
    device."""
    import jax
    import numpy as np

    losses, walls, first = [], [], None
    mark = len(clog.walls)
    for i in range(STEPS):
        t0 = time.perf_counter()
        params, opt, loss = step(params, opt, batch)
        jax.block_until_ready((params, opt, loss))
        walls.append(time.perf_counter() - t0)
        losses.append(float(loss))
        if i == 0:
            log(f"{label}: first step wall {walls[0]:.2f} s "
                f"(compilation included)")
            clog.report(mark)
            first = jax.tree.map(np.asarray, params)
    log(f"{label} losses: {losses}")
    log(f"{label} step walls s: {[round(w, 3) for w in walls]}")
    return losses, first, jax.tree.map(np.asarray, params), params, opt


def train_phases(args, devices, clog: CompileLog) -> None:
    import jax
    import numpy as np
    import optax

    import byteps_tpu as bps
    from byteps_tpu.core.state import get_state
    from byteps_tpu.jax.train import make_ps_train_step, make_train_step
    from byteps_tpu.models import bert
    from byteps_tpu.native.build import build
    from byteps_tpu.ops.push_pull import psum_tree
    from byteps_tpu.parallel.mesh import DP_AXIS, make_mesh
    from byteps_tpu.utils.net import free_port

    n = args.chips
    # ---- native server library, built from ps.cc on first use -------- #
    t0 = time.perf_counter()
    lib = build()
    log(f"native build wall {time.perf_counter() - t0:.2f} s -> "
        f"{os.path.basename(lib)}")

    port = free_port()
    server = ServerChild(port)
    try:
        log(f"server child pid {server.proc.pid} listening on :{port} "
            f"after {server.wait_listening():.2f} s")
        os.environ.update({
            "DMLC_ROLE": "worker", "DMLC_NUM_WORKER": "1",
            "DMLC_NUM_SERVER": "1", "DMLC_PS_ROOT_URI": "127.0.0.1",
            "DMLC_PS_ROOT_PORT": str(port),
            # one worker: without this the launcher convention would
            # treat the job as non-distributed and skip the PS
            "BYTEPS_FORCE_DISTRIBUTED": "1",
        })
        mesh = make_mesh({DP_AXIS: n}, devices[:n])
        bps.init(mesh=mesh)
        state = get_state()
        if state.ps_client is None or state.scheduler is None:
            raise AssertionError("bps.init() connected no PS client")
        log(f"client transport: {state.ps_client.transport_stats()}")

        cfg, batch = model_and_batch(args.rehearse, n)

        def loss_fn(p, b):
            return bert.loss_fn(p, b, cfg)

        tx = optax.adamw(LR, eps=EPS, weight_decay=WEIGHT_DECAY)
        init_host = jax.tree.map(
            np.asarray, bert.init_params(jax.random.PRNGKey(SEED), cfg))
        n_params = sum(int(v.size) for v in jax.tree.leaves(init_host))
        log(f"model: BERT {cfg.n_layers}x{cfg.dim} ffn {cfg.ffn_dim} vocab "
            f"{cfg.vocab_size}, {n_params / 1e6:.1f} M params, batch "
            f"{batch['tokens'].shape[0]} x seq {batch['tokens'].shape[1]}, "
            f"{STEPS} steps, mesh dp={n}")

        def fresh():
            from jax.sharding import NamedSharding, PartitionSpec as P
            rep = NamedSharding(mesh, P())
            p = jax.device_put(init_host, rep)
            return p, jax.device_put(tx.init(p), rep)

        # ---- control: the fused in-jit step -------------------------- #
        ctl_step = make_train_step(
            loss_fn, tx, mesh,
            grads_transform=lambda g: psum_tree(g, axis=DP_AXIS,
                                                average=True))
        p, o = fresh()
        mark = len(clog.walls)
        mem = ctl_step.jitted.lower(p, o, batch).compile().memory_analysis()
        clog.report(mark)
        report_memory("control step program", mem, devices[0])
        ctl_losses, ctl_first, ctl_last, p, o = run_steps(
            "control (make_train_step)", ctl_step, p, o, batch, clog)
        del p, o

        # ---- the PS step --------------------------------------------- #
        ps_step = make_ps_train_step(loss_fn, tx, mesh)
        p, o = fresh()
        snaps = []  # counter snapshots after every step

        def counted_step(p_, o_, b_):
            out = ps_step(p_, o_, b_)
            jax.block_until_ready(out)
            snaps.append(counters(bps))
            return out

        ps_losses, ps_first, ps_last, p, o = run_steps(
            "PS (make_ps_train_step)", counted_step, p, o, batch, clog)
        check_sharding_spans((p, o), n)
        check_engagement(bps, state, snaps, init_host, n)
        del p, o

        # ---- comparison ---------------------------------------------- #
        # every comparison prints before any of them fails the run
        failed = [msg for msg in (
            compare_losses(ctl_losses, ps_losses),
            compare_params("after step 1", ctl_first, ps_first, 1,
                           STEP1_TOL),
            compare_params(f"after step {STEPS}", ctl_last, ps_last, STEPS,
                           FINAL_TOL)) if msg]
        if failed:
            raise AssertionError("; ".join(failed))

        bps.shutdown()
        rc = server.wait_exit()
        if rc != 0:
            raise AssertionError(
                f"server exited rc={rc}:\n{server.tail()}")
        log("server child exited 0 after SHUTDOWN")
    except BaseException:
        sys.stderr.write(f"[chip_smoke] server log tail:\n{server.tail()}\n")
        raise
    finally:
        server.kill()


def report_memory(label: str, mem, device) -> None:
    """memory_analysis() of a compiled program against the device's
    limit; a program that cannot fit is an error here, not an OOM later."""
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    stats = device.memory_stats() or {}
    limit = stats.get("bytes_limit")
    log(f"{label}: args {mem.argument_size_in_bytes / 2**30:.2f} GiB, out "
        f"{mem.output_size_in_bytes / 2**30:.2f}, temp "
        f"{mem.temp_size_in_bytes / 2**30:.2f}, aliased "
        f"{mem.alias_size_in_bytes / 2**30:.2f} -> {need / 2**30:.2f} GiB"
        + (f" of {limit / 2**30:.2f} GiB" if limit else ""))
    if limit and need > limit:
        raise AssertionError(f"{label} needs {need} B > device {limit} B")


def counters(bps) -> dict:
    """One snapshot of the counters check_engagement reads."""
    m = bps.get_fleet_metrics()
    c = m["counters"]
    fleet = m["fleet"]
    return {
        "arena": dict(m["arena"]),  # == bps.get_arena_stats()
        "pushpull_requests": c.get("wire/pushpull_requests", 0),
        "push_bytes": c.get("wire/push_bytes", 0),
        "fleet_source": fleet["source"],
        "fold_bytes": sum(s["fold_bytes"] for s in fleet["server"].values()),
        "oob_msgs": sum(s.get("oob_msgs", 0)
                        for s in fleet["server"].values()),
        "whole_bytes": c.get("export/whole_bytes", 0),
        "shard_bytes": c.get("export/shard_bytes", 0),
        "device_bytes": {k.rsplit("/", 1)[1]: v for k, v in c.items()
                         if k.startswith("export/device_bytes/")},
    }


def check_sharding_spans(tree, n: int) -> None:
    """Every parameter and optimizer-state leaf must live on all ``n``
    devices after the PS step: an import that landed on the first device
    only, and stayed there, would show here."""
    import jax

    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        devs = leaf.sharding.device_set
        if len(devs) != n:
            raise AssertionError(
                f"{jax.tree_util.keystr(path)} spans {len(devs)} of {n} "
                f"devices: {leaf.sharding}")
    log(f"every parameter and optimizer-state leaf spans {n} device(s)")


def check_engagement(bps, state, snaps, init_host, n: int) -> None:
    """The engagement counters: every leaf left as an output of the
    backward, a leaf the plan shards across the ``n`` chips as one flat
    shard a chip (none on one chip), the arena
    served every checkout, the wire carried fused PUSHPULLs and the
    server folded exactly the bytes that were pushed."""
    import jax

    from byteps_tpu.ops.push_pull import shard_layout

    reports = bps.get_step_reports()
    last = reports[-1]
    fusion = state.config.fusion_bytes
    floor = max(fusion, state.config.shard_min_bytes)
    leaves = jax.tree.leaves(init_host)
    # jax/train.py's shard plan: large enough, and padded by an eighth
    # at most
    sharded = [] if n == 1 else [
        v for v in leaves if v.nbytes >= floor
        and shard_layout(v.size, n)[1] * 8 <= v.size]
    grad_bytes = sum(v.nbytes for v in leaves)
    log(f"last StepReport: leaves={last['fallback_leaves']} "
        f"ttfp_ms={last.get('ttfp_ms')} of "
        f"{len(leaves)} leaves, {len(sharded)} sharded over {n} chip(s) "
        f"(>= {floor} B)")
    stages = ("wall_ms", "compute_ms", "drain_ms", "tail_ms",
              "pull_wait_ms", "allgather_ms", "push_p95_ms", "pull_p95_ms",
              "h2d_update_p95_ms", "server_recv_ms", "server_queue_ms",
              "server_fold_ms", "server_reply_ms")
    log("last StepReport host-clock walls (observations, not metrics): "
        + ", ".join(f"{k}={last[k]:.1f}" for k in stages
                    if last.get(k) is not None))
    if last["fallback_leaves"] != len(leaves):
        raise AssertionError(
            f"the StepReport counts {last['fallback_leaves']} leaves, the "
            f"tree holds {len(leaves)}")
    first, end = snaps[0], snaps[-1]
    a0, a1 = first["arena"], end["arena"]
    log(f"arena after step 1: {a0}")
    log(f"arena after step {len(snaps)}: {a1}")
    grew = a1["export_shard_leaves"] - a0["export_shard_leaves"]
    if grew != len(sharded) * (len(snaps) - 1):
        raise AssertionError(
            f"export_shard_leaves grew {grew}, want "
            f"{len(sharded) * (len(snaps) - 1)}")
    for key in ("fresh_allocs", "checkout_conflicts"):
        if a1[key] != 0:
            raise AssertionError(f"arena {key} = {a1[key]}, want 0")
    if a1["slot_allocs"] != a0["slot_allocs"]:
        raise AssertionError(
            f"arena slots still being allocated after warm-up: "
            f"{a0['slot_allocs']} -> {a1['slot_allocs']}")
    if end["pushpull_requests"] <= 0:
        raise AssertionError("wire/pushpull_requests == 0")
    if end["fleet_source"] != "wire":
        raise AssertionError(
            f"server stats came from {end['fleet_source']!r}, not over "
            f"the wire from the server process")
    steady = len(snaps) - 1
    d_fold = end["fold_bytes"] - first["fold_bytes"]
    d_push = end["push_bytes"] - first["push_bytes"]
    log(f"wire: pushpull_requests={end['pushpull_requests']} "
        f"push_bytes/step={d_push / steady:.0f} server "
        f"fold_bytes/step={d_fold / steady:.0f} gradient bytes="
        f"{grad_bytes} server oob_msgs={end['oob_msgs']}")
    if n == 1:
        # whole leaves: every gradient byte crosses once, unpadded
        if not d_fold == d_push == steady * grad_bytes:
            raise AssertionError(
                f"server fold_bytes {d_fold} / pushed {d_push} / "
                f"gradient {steady * grad_bytes} bytes disagree over "
                f"{steady} steps")
    else:
        # shard keys are padded to a multiple of n elements
        if d_fold != d_push or d_push < steady * grad_bytes:
            raise AssertionError(
                f"server fold_bytes {d_fold} != pushed {d_push} (gradient "
                f"{steady * grad_bytes}) over {steady} steps")
        # a chip's share of the plan: its flat shard of every sharded
        # leaf, padding included, every step
        shard_step = sum(shard_layout(v.size, n)[0] * v.dtype.itemsize
                         for v in sharded)
        check_shard_engagement(end, a1, n, shard_step * len(snaps))


def check_shard_engagement(end: dict, arena: dict, n: int,
                           want: int) -> None:
    """Four-chip form: leaves left the devices as per-device shards, and
    each device exported exactly the ``want`` shard bytes the plan gives
    it (device 0 also carries the whole-leaf and bucket exports)."""
    log(f"export: shard_leaves={arena['export_shard_leaves']} "
        f"shard_bytes={end['shard_bytes']} whole_bytes="
        f"{end['whole_bytes']} device_bytes={end['device_bytes']}")
    if arena["export_shard_leaves"] <= 0:
        raise AssertionError("export_shard_leaves == 0")
    per_dev = dict(end["device_bytes"])
    per_dev["0"] = per_dev.get("0", 0) - end["whole_bytes"]
    if end["shard_bytes"] != want * n:
        raise AssertionError(
            f"export/shard_bytes = {end['shard_bytes']}, want {want * n}")
    if sorted(per_dev) != [str(d) for d in range(n)] or \
            any(v != want for v in per_dev.values()):
        raise AssertionError(
            f"per-device shard export bytes uneven: {per_dev}, want "
            f"{want} each")


def compare_losses(ctl_losses, ps_losses) -> str:
    """Returns what disagrees, or '' when the losses agree."""
    import numpy as np

    if not (np.isfinite(ctl_losses).all() and np.isfinite(ps_losses).all()):
        return "non-finite loss"
    rel = [abs(a - b) / abs(a) for a, b in zip(ctl_losses, ps_losses)]
    log(f"loss |rel diff| per step: {[f'{r:.2e}' for r in rel]} "
        f"(tolerance {LOSS_RTOL:.0e})")
    if max(rel) > LOSS_RTOL:
        return f"losses disagree: {rel}"
    if not min(ctl_losses[1:]) < ctl_losses[0]:
        return f"control loss never fell: {ctl_losses}"
    return ""


def compare_params(when: str, ctl, ps, steps: int, tol: float) -> str:
    """RMS |p_ps - p_ctl| over the worst BLOCK-element block of each
    leaf, as a fraction of ``LR * steps`` — the distance Adam can move an
    element in that many steps (see the tolerances above). Returns what
    disagrees, or '' when the parameters agree."""
    import jax
    import numpy as np

    rows = []
    for (path, c), s in zip(jax.tree_util.tree_flatten_with_path(ctl)[0],
                            jax.tree.leaves(ps)):
        name = jax.tree_util.keystr(path)
        if s.shape != c.shape or not np.isfinite(s).all():
            return f"{name}: bad PS params {when}"
        d2 = np.square(s.astype(np.float64).ravel()
                       - c.astype(np.float64).ravel())
        edges = np.arange(0, d2.size, BLOCK)
        sizes = np.diff(np.append(edges, d2.size))
        worst = float(np.sqrt((np.add.reduceat(d2, edges) / sizes).max()))
        rows.append((worst / (LR * steps), float(np.sqrt(d2.max())), name))
    rows.sort(reverse=True)
    for frac, mx, name in rows[:6]:
        log(f"  {when}: {name}: worst-block RMS diff = {frac:.2e} of "
            f"lr*{steps}, max |diff| = {mx:.2e}")
    log(f"params {when}: worst block {rows[0][0]:.2e} of lr*{steps} "
        f"(tolerance {tol:.0e})")
    if rows[0][0] > tol:
        return (f"parameters disagree between PS and control {when}: "
                f"{rows[0][2]} worst-block RMS diff {rows[0][0]:.2e} of "
                f"lr*{steps} > {tol:.0e}")
    return ""


# --------------------------------------------------------------------- #


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded PS step and its "
                         "fused-psum control on a 4-device mesh")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any platform, kernels interpreted; "
                         "prints no result line")
    args = ap.parse_args(argv)
    # a hang (protocol bug, dead callback) must end as a failure with
    # stacks inside the caller's time limit, not as a silent timeout
    faulthandler.dump_traceback_later(1150, exit=True)
    t_start = time.perf_counter()

    import jax

    from byteps_tpu.utils.jax_compat import setup_compile_cache

    cache_dir = setup_compile_cache()
    clog = CompileLog()
    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    log(f"device: {device}; jax {jax.__version__}; compile cache "
        f"{cache_dir}")
    if not args.rehearse and dev.platform != "tpu":
        sys.stderr.write(
            f"[chip_smoke] no accelerator: JAX reports "
            f"{len(devices)} x {dev.platform!r}; this smoke runs on a TPU "
            f"only (see --rehearse for the CPU control-flow check)\n")
        return 1
    if len(devices) < args.chips or \
            (args.chips == 4 and len(devices) != 4 and not args.rehearse):
        sys.stderr.write(
            f"[chip_smoke] --chips {args.chips} needs that many devices, "
            f"JAX reports {len(devices)}\n")
        return 1

    if args.chips == 1:
        check_kernels(args.rehearse)
    train_phases(args, devices, clog)
    log((f"compile cache {cache_dir}: {clog.hits} hits of {clog.requests} "
         f"requests" if cache_dir else "no persistent compile cache")
        + f"; {len(clog.walls)} programs, "
        f"{sum(s for _, s in clog.walls):.1f} s total backend compile wall")
    log(f"total wall {time.perf_counter() - t_start:.1f} s")
    faulthandler.cancel_dump_traceback_later()
    if args.rehearse:
        print(json.dumps({"rehearsal": "reached comparison",
                          "device": device}), flush=True)
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
