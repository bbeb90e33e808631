#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json``:

    python benchmark/run.py --workload <config>.<traffic> --seed <n> \
        --seconds <s> --trace <0|1>

A new process per run. It finds the cell's configuration, traffic mix,
model family and per-layer readers by name (``configs/``, ``traffic/``,
``families/``, ``layers/``), builds the train step through the program's
own entry points, drives it from the seed through its first steps (the
steps ``correct`` compares), warms up, measures whole steps for
``--seconds`` with nothing but steps inside the clock, and then, outside
the clock, reads counters, memory, (``--trace 1``) a traced window and
the fused control, and compares with the plain reference.

It fails (non-zero exit, no result line) unless JAX reports TPU devices,
as many as the cell asks. ``--rehearse`` runs the same code at the tiny
sizes the data files give, on whatever JAX has, and never prints a
result line. The last line of standard output is the contract's one JSON
object; per-step walls, the set-up breakdown, the engagement counters
and every number compared are printed on earlier lines.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
# first, whatever else PYTHONPATH holds: this checkout's files
sys.path.insert(0, REPO)

from benchmark import correct as correct_mod  # noqa: E402
from benchmark.server_child import ServerChild, free_port, split_cores  # noqa: E402

CACHE_DIR = os.path.join(REPO, ".jax_cache")
TRACED_STEPS = 3
CONTROL_STEPS = 10


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


# --------------------------------------------------------------------- #
# the cell, from data
# --------------------------------------------------------------------- #


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def overlay(base: dict, tiny: dict) -> dict:
    out = dict(base)
    for k, v in tiny.items():
        out[k] = overlay(base[k], v) if isinstance(v, dict) \
            and isinstance(base.get(k), dict) else v
    return out


def load_cell(manifest_path: str, workload: str, rehearse: bool) -> dict:
    manifest = load_json(manifest_path)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in {manifest_path}; "
                         f"it has {sorted(cells)}")
    cell = cells[workload]
    files = {c["name"]: c["file"] for c in manifest["configs"]}
    config = load_json(os.path.dirname(os.path.abspath(manifest_path)),
                       files[cell["config"]])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    if traffic["chips"] != cell["chips"]:
        raise SystemExit(f"{workload}: the cell asks {cell['chips']} chips, "
                         f"its traffic file {traffic['chips']}")
    if rehearse:
        config = overlay(config, config.get("rehearse", {}))
        traffic = overlay(traffic, traffic.get("rehearse", {}))

    def mine(metric: dict) -> bool:
        return workload in metric.get("workloads", [workload])

    return {"name": workload, "cell": cell, "config": config,
            "traffic": traffic,
            "end_to_end": [m for m in manifest["end_to_end"] if mine(m)],
            "per_layer": [m for m in manifest["per_layer"] if mine(m)]}


# --------------------------------------------------------------------- #
# the run
# --------------------------------------------------------------------- #


class Marks:
    """Set-up breakdown: seconds since the last mark, by name."""

    def __init__(self):
        self.rows = []
        self._t = T_START

    def mark(self, name: str) -> None:
        now = time.perf_counter()
        self.rows.append((name, now - self._t))
        self._t = now


class CompileLog:
    """Backend compile seconds and persistent-cache hits, from
    jax.monitoring's own events."""

    def __init__(self):
        from jax import monitoring

        self.seconds = 0.0
        self.programs = 0
        self.requests = 0
        self.hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.programs += 1

    def _event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def __str__(self) -> str:
        return (f"{self.programs} programs, {self.seconds:.1f} s backend "
                f"compile, {self.hits} cache hits of {self.requests} "
                f"requests")


def log_memory(label: str, devices) -> None:
    import jax

    stats = devices[0].memory_stats() or {}
    live = sum(x.nbytes for x in jax.live_arrays())
    log(f"memory {label}: in use "
        f"{stats.get('bytes_in_use', 0) / 2**30:.3f} GiB, peak "
        f"{stats.get('peak_bytes_in_use', 0) / 2**30:.3f}, limit "
        f"{stats.get('bytes_limit', 0) / 2**30:.3f}; live arrays "
        f"{live / 2**30:.3f} GiB")


def device_peak_bytes(device) -> int:
    """The most of one chip's memory that was taken at once. The TPU
    runtime keeps compiled programs' temporaries in a reserved region of
    its own, outside ``bytes_in_use`` (``bytes_reservable_limit`` is
    ``bytes_limit`` less what is in use), so the peak is the buffers'
    peak plus that region's."""
    stats = device.memory_stats() or {}
    return (stats.get("peak_bytes_in_use", 0)
            + stats.get("peak_bytes_reserved", 0))


def flat_counters(bps) -> dict:
    m = bps.get_fleet_metrics()
    out = dict(m["counters"])
    out.update({f"arena/{k}": v for k, v in m["arena"].items()
                if isinstance(v, (int, float))})
    servers = m.get("fleet", {}).get("server", {})
    out["server/fold_bytes"] = sum(s.get("fold_bytes", 0)
                                   for s in servers.values())
    return out


def place_compile_cache() -> None:
    """JAX's persistent cache where ``JAX_COMPILATION_CACHE_DIR`` says,
    else at a fixed path in the checkout (the path is part of the
    cache's key); the many sub-second programs of a run are kept too."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def connect_worker(server: ServerChild, mesh):
    """This process as the one worker of ``server``: the ``DMLC_*``
    environment a launcher would give it, then ``bps.init()``."""
    import byteps_tpu as bps
    from byteps_tpu.core.state import get_state

    server.wait_listening()
    os.environ.update({
        "DMLC_ROLE": "worker", "DMLC_NUM_WORKER": "1",
        "DMLC_NUM_SERVER": "1", "DMLC_PS_ROOT_URI": "127.0.0.1",
        "DMLC_PS_ROOT_PORT": str(server.port),
        # one worker: the launcher convention would otherwise treat
        # the job as non-distributed and skip the PS
        "BYTEPS_FORCE_DISTRIBUTED": "1"})
    bps.init(mesh=mesh)
    if get_state().ps_client is None or get_state().scheduler is None:
        raise RuntimeError("bps.init() connected no PS client")
    return bps


class Program:
    """The system under test for one cell: the program's train step on
    the cell's mesh, and the seeded programs that feed and read it. The
    key is an argument of each, so one compilation serves every seed."""

    def __init__(self, config, traffic, mesh, wrap_step=None):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from benchmark.optimizers import load as load_optimizer
        from benchmark.reference.common import leaf_norms
        from byteps_tpu.jax.train import make_ps_train_step, make_train_step
        from byteps_tpu.ops.push_pull import psum_tree
        from byteps_tpu.parallel.mesh import DP_AXIS

        family = importlib.import_module(
            f"benchmark.families.{config['family']}")
        self.model = model = family.reference
        self.config = config
        loss_fn = family.program_loss(config)
        optimizer, hyper = load_optimizer(config["optimizer"])
        tx = optimizer.make_tx(hyper)
        self.rows = rows = config["batch_per_chip"] * traffic["chips"]
        self.n_check = config["check"]["steps"]
        if traffic["batches"] < self.n_check:
            raise SystemExit("the traffic file gives fewer batches than "
                             "the configuration checks steps")

        def fresh_state(key):
            p = model.init_params(key, config)
            return p, tx.init(p)

        self.init = jax.jit(fresh_state,
                            out_shardings=NamedSharding(mesh, P()))
        self.make_batch = jax.jit(
            lambda key, i: model.make_batch(key, i, rows, config),
            out_shardings=NamedSharding(mesh, P(DP_AXIS)))
        self.first_norms = jax.jit(lambda o, key: leaf_norms(
            optimizer.first_gradient(hyper, o,
                                     model.init_params(key, config))))
        self.delta_norms = jax.jit(lambda p, key: leaf_norms(jax.tree.map(
            jax.numpy.subtract, p, model.init_params(key, config))))

        def fused_step():
            return make_train_step(
                loss_fn, tx, mesh,
                grads_transform=lambda g: psum_tree(g, axis=DP_AXIS,
                                                    average=True))

        self.fused_step = fused_step
        # ``ps_step``: the traffic file's arguments of the program's own
        # ``make_ps_train_step`` (a codec, say); none is its defaults
        step = make_ps_train_step(loss_fn, tx, mesh,
                                  **traffic.get("ps_step", {})) \
            if traffic["path"] == "ps" else fused_step()
        self.step = wrap_step(step) if wrap_step is not None else step

    def timed_step(self, p, o, batch):
        import jax

        t0 = time.perf_counter()
        p, o, loss = self.step(p, o, batch)
        jax.block_until_ready((p, o, loss))
        return p, o, loss, (time.perf_counter() - t0) * 1e3

    def first_steps(self, key, params, opt, batches, marks=None):
        """The steps ``correct`` compares, through the window's own call
        and feed: losses, the first gradient's per-leaf norms (from the
        optimizer's state after one step) and the parameters' change
        over all of them, as device arrays."""
        losses, walls, out = [], [], {}
        for i in range(self.n_check):
            params, opt, loss, wall = self.timed_step(params, opt, batches[i])
            losses.append(loss)
            walls.append(wall)
            if i == 0:
                out["grad_norms"] = self.first_norms(opt, key)
                if marks:
                    marks.mark("first_step_compile")
        out["delta_norms"] = self.delta_norms(params, key)
        out["losses"] = losses
        return params, opt, out, walls


def to_host(program: dict) -> dict:
    return {k: [float(x) for x in v] for k, v in program.items()}


def run_cell(args, wrap_step=None, transport=None) -> dict:
    """Everything but the look for a chip's result line. ``wrap_step``
    and ``transport`` exist for the tests that break the timed path and
    see ``correct`` come out false."""
    marks = Marks()
    spec = load_cell(args.manifest, args.workload, args.rehearse)
    config, traffic = spec["config"], spec["traffic"]
    chips, path = traffic["chips"], traffic["path"]
    log(f"cell {spec['name']}: family {config['family']}, path {path}, "
        f"{chips} chip(s), batch {config['batch_per_chip']}/chip, "
        f"seed {args.seed}, window {args.seconds} s, trace {args.trace}"
        + (", REHEARSAL (tiny sizes; no result line)" if args.rehearse
           else ""))

    worker_cores, server_cores = split_cores(traffic.get("placement"))
    if worker_cores:
        os.sched_setaffinity(0, worker_cores)
        log(f"placement: worker on cores {worker_cores}, server child on "
            f"{server_cores}")
    else:
        log(f"placement: none; {len(os.sched_getaffinity(0))} cores shared")

    server = None
    if path == "ps":
        from byteps_tpu.native.build import build

        lib = build()
        marks.mark("native_build")
        server = ServerChild(free_port(), server_cores)
        log(f"native library {os.path.basename(lib)}; server child pid "
            f"{server.proc.pid} on :{server.port}")
    try:
        return _run(args, spec, marks, server, wrap_step, transport)
    except BaseException:
        if server is not None:
            sys.stderr.write(f"[bench] server log tail:\n{server.tail()}\n")
        raise
    finally:
        if server is not None:
            server.kill()


def _run(args, spec, marks, server, wrap_step, transport) -> dict:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    config, traffic = spec["config"], spec["traffic"]
    chips, path = traffic["chips"], traffic["path"]
    seed = args.seed

    if not args.rehearse:
        place_compile_cache()
    compiles = CompileLog()
    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    log(f"device: {device}; jax {jax.__version__}")
    if not args.rehearse and (dev.platform != "tpu" or len(devices) < chips):
        raise SystemExit(
            f"[bench] this cell measures on {chips} TPU chip(s); JAX reports "
            f"{len(devices)} x {dev.platform!r}. No CPU fallback "
            f"(--rehearse is the control-flow check).")
    if len(devices) < chips:
        raise SystemExit(f"[bench] rehearsal of {chips} chips needs that many "
                         f"devices; JAX reports {len(devices)}")
    peaks = load_json(HERE, "peaks.json")
    if dev.device_kind in peaks:
        peak_flops = peaks[dev.device_kind]["bf16_flops_per_s"]
    elif args.rehearse:
        peak_flops = float("nan")
    else:
        raise SystemExit(f"[bench] no peaks for device kind "
                         f"{dev.device_kind!r} in peaks.json")
    marks.mark("jax_start")

    from byteps_tpu.parallel.mesh import DP_AXIS, make_mesh

    mesh = make_mesh({DP_AXIS: chips}, devices[:chips])
    bps = None
    if path == "ps":
        bps = connect_worker(server, mesh)
        marks.mark("server_start")

    # ---- the model, its state and its batches, from the seed --------- #
    from benchmark.reference.common import seed_key

    key = seed_key(seed)
    prog = Program(config, traffic, mesh, wrap_step)
    model, rows, step = prog.model, prog.rows, prog.step
    marks.mark("build_step")
    params, opt = prog.init(key)
    jax.block_until_ready((params, opt))
    marks.mark("init_state")
    batches = [prog.make_batch(key, i) for i in range(traffic["batches"])]
    jax.block_until_ready((params, opt, batches))
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    flops = model.model_flops_per_step(rows, config)
    log(f"model: {n_params / 1e6:.1f} M parameters in "
        f"{len(jax.tree.leaves(params))} leaves, global batch {rows}, "
        f"{flops / 1e12:.3f} model TFLOP per step")
    marks.mark("init_batches")

    # ---- the first steps: what ``correct`` compares ------------------- #
    n_check = config["check"]["steps"]
    params, opt, program, check_walls = prog.first_steps(
        key, params, opt, batches, marks)
    marks.mark("check_steps")
    warm_walls = []
    for i in range(traffic["warmup_steps"]):
        params, opt, _, wall = prog.timed_step(
            params, opt, batches[(n_check + i) % len(batches)])
        warm_walls.append(wall)
    jax.block_until_ready(program)
    marks.mark("warmup")
    log("first steps' walls ms (compile in the first): "
        + " ".join(f"{w:.1f}" for w in check_walls)
        + " | warm-up: " + " ".join(f"{w:.1f}" for w in warm_walls))

    # ---- the window: steps and nothing else --------------------------- #
    before = flat_counters(bps) if bps else {}
    compiled_before = compiles.programs
    setup_s = time.perf_counter() - T_START
    params, opt, loss, walls, window_s = measure_window(
        step, params, opt, batches, args.seconds)
    # ---- outside the clock ------------------------------------------- #
    peak_bytes = max(device_peak_bytes(d) for d in devices[:chips])
    after = flat_counters(bps) if bps else {}
    steps = len(walls)
    # every step and every stall of the window: its length over its
    # whole steps
    step_ms = window_s * 1e3 / steps
    log("set-up breakdown s: " + ", ".join(
        f"{k}={v:.2f}" for k, v in marks.rows) + f"; setup_s={setup_s:.2f}"
        f"; compiles so far: {compiles}; compiled inside the window: "
        f"{compiles.programs - compiled_before}")
    log(f"memory stats after the window: {devices[0].memory_stats()}")
    log(f"window: {steps} whole steps in {window_s:.3f} s; step_ms "
        f"(window over steps) {step_ms:.3f}; median "
        f"{statistics.median(walls):.3f}; min {min(walls):.3f}; max "
        f"{max(walls):.3f}; {rows * steps / window_s:.4f} samples/s; peak "
        f"{peak_bytes / 2**30:.4f} GiB")
    log("step walls ms: " + " ".join(f"{w:.2f}" for w in walls))
    reports, checks = [], {}
    if bps:
        reports = bps.get_step_reports()[-steps:]
        pushed, folded = engagement(reports, before, after, steps, log)
        # the timed path's own wire: every gradient element of every
        # step of the window went out in the configuration's wire type,
        # and the server folded those bytes
        wire_bytes = n_params * np.dtype(config["wire_dtype"]).itemsize
        checks.update(correct_mod.compare_wire(
            pushed, folded, steps, wire_bytes, config["limits"]))

    values = {"step_ms": step_ms, "peak_hbm_gib": peak_bytes / 2**30,
              "setup_s": setup_s}
    device["memory_peak_bytes"] = peak_bytes
    result = {"correct": False, "attempted": steps, "failed": 0,
              "metrics": {}, "device": device}

    # ---- the traced window (--trace 1) -------------------------------- #
    reduced, traced_reports = None, []
    if args.trace:
        reduced, traced_reports, params, opt = traced_window(
            step, params, opt, batches, bps, args.rehearse)

    # ---- correct: transport, then the reference ----------------------- #
    if bps:
        leaves = [np.asarray(x) for x in jax.tree.leaves(params)
                  if x.dtype == np.float32]
        send = transport or (lambda xs: ps_round_trip(bps, xs))
        back = send(leaves)
        differing = sum(correct_mod.transport_mismatch(a, b)
                        for a, b in zip(leaves, back))
        checks["transport_blocks_differing"] = (
            float(differing),
            float(config["limits"]["transport_blocks_differing"]))
        del leaves, back
    program = to_host(program)
    log(f"program losses of the first {n_check} steps: {program['losses']}")
    del params, opt, loss
    log_memory("after the window's state is dropped", devices)
    if bps:
        bps.shutdown()
        rc = server.wait_exit()
        if rc != 0:
            raise RuntimeError(f"server exited rc={rc}:\n{server.tail()}")
        log("server child exited 0 after SHUTDOWN")

    control_ms = None
    if args.trace and path == "ps":
        control_ms = time_control(prog.fused_step(), prog.init, key, batches)
    # the step's closures keep device buffers of their last round
    del batches, step
    prog.step = None
    gc.collect()
    log_memory("before the reference", devices)

    t_ref = time.perf_counter()
    from benchmark.reference.train import Reference

    reference = Reference(model, config, rows).steps(key)
    log(f"reference losses: {reference['losses']} "
        f"({time.perf_counter() - t_ref:.1f} s)")
    checks.update(correct_mod.compare_training(program, reference,
                                               config["limits"]))
    result["correct"] = correct_mod.verdict(checks, log)
    log(f"compiles in all: {compiles}")

    # ---- the line ----------------------------------------------------- #
    if args.trace:
        from benchmark.layer_api import LayerContext, load_readers

        ctx = LayerContext(
            steps=steps, window_s=window_s, step_ms=step_ms, walls_ms=walls,
            global_batch=rows, chips=chips, reports=reports,
            counters_before=before, counters_after=after,
            flops_per_step=flops, peak_flops_per_chip=peak_flops,
            trace=reduced, traced_steps=TRACED_STEPS if reduced else 0,
            control_step_ms=control_ms)
        readers = load_readers()
        for m in spec["per_layer"]:
            value = readers[m["name"]](ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        if reduced is not None:
            device["busy_s"] = reduced.busy_mean_s
            device["window_s"] = reduced.window_s
            result["breakdown"] = breakdown(reduced, traced_reports)
    else:
        for m in spec["end_to_end"]:
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    return result


def measure_window(step, params, opt, batches, seconds):
    """Whole steps until ``seconds`` have passed, each wall taken around
    ``block_until_ready``; nothing else is inside the clock. Returns the
    state, the last loss (on the device), the walls in ms and the time
    from the first step's start to the last step's end."""
    import jax

    walls = []
    t0 = t_end = time.perf_counter()
    while t_end - t0 < seconds:
        ts = t_end
        batch = batches[len(walls) % len(batches)]
        params, opt, loss = step(params, opt, batch)
        jax.block_until_ready((params, opt, loss))
        t_end = time.perf_counter()
        walls.append((t_end - ts) * 1e3)
    return params, opt, loss, walls, t_end - t0


def engagement(reports, before, after, steps, log):
    """The proof that the PS path ran as a deployment's does: leaves
    streamed out of the backward, none fell back but the bucket-fused
    ones, and the server folded exactly the bytes that were pushed.
    Returns the window's (bytes pushed, bytes the server folded)."""
    last = reports[-1]
    pushed = after.get("wire/push_bytes", 0) - before.get("wire/push_bytes", 0)
    folded = after["server/fold_bytes"] - before["server/fold_bytes"]
    log(f"engagement: streamed leaves {last['streamed_leaves']}, fallback "
        f"leaves {last['fallback_leaves']}, pushed {pushed} B, server "
        f"folded {folded} B over {steps} steps"
        f"{'' if pushed == folded else '  (DIFFER)'}; pushpull requests "
        f"{after.get('wire/pushpull_requests', 0) - before.get('wire/pushpull_requests', 0)}")
    keys = ("wall_ms", "compute_ms", "drain_ms", "tail_ms", "ttfp_ms",
            "pull_wait_ms", "allgather_ms", "pull_p95_ms",
            "server_fold_ms", "server_queue_ms")
    log("last StepReport: " + ", ".join(
        f"{k}={last[k]:.1f}" for k in keys if last.get(k) is not None))
    return pushed, folded


def ps_round_trip(bps, leaves):
    """Each leaf once through the parameter server: with one worker the
    sum is the leaf itself."""
    handles = [bps.push_pull_async(x, name=f"bench.identity.{i}",
                                   average=False)
               for i, x in enumerate(leaves)]
    return [bps.synchronize(h) for h in handles]


def traced_window(step, params, opt, batches, bps, rehearse):
    """``TRACED_STEPS`` steps under the profiler, the benchmark's own
    spans around the calls into the program."""
    import glob

    import jax

    from benchmark.trace_reduce import (SPAN_PREFIX, describe,
                                        reduce_trace)

    # under TMPDIR; removed below
    out = tempfile.mkdtemp(prefix="bench-trace-")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    try:
        jax.profiler.start_trace(out, profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation(SPAN_PREFIX + "window"):
                for i in range(TRACED_STEPS):
                    with jax.profiler.TraceAnnotation(SPAN_PREFIX + "step"):
                        params, opt, loss = step(params, opt,
                                                 batches[i % len(batches)])
                        jax.block_until_ready((params, opt, loss))
        finally:
            jax.profiler.stop_trace()
        files = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if len(files) != 1:
            raise RuntimeError(f"expected one .xplane.pb, found {files}")
        for row in describe(files[0]):
            if row.startswith("/device:"):
                log("trace " + row)
        try:
            reduced = reduce_trace(files[0])
        except ValueError as e:
            if not rehearse:
                raise
            log(f"rehearsal: no device trace to reduce ({e})")
            reduced = None
    finally:
        shutil.rmtree(out, ignore_errors=True)
    traced = bps.get_step_reports()[-TRACED_STEPS:] if bps else []
    if reduced is not None:
        log(f"traced window {reduced.window_s:.3f} s, busy "
            f"{reduced.busy_s} s, idle share {reduced.idle_share:.4f}")
    return reduced, traced, params, opt


def breakdown(reduced, traced) -> dict:
    """The ten device operations with most time, and the five longest
    idle gaps, each named by what the host was doing: inside a traced
    step, the program's StepReport of that step splits the span into
    ``backward_export`` (dispatch until the last leaf is off the device)
    and ``drain``."""
    from benchmark.trace_reduce import SPAN_PREFIX, name_gap

    named = []
    for k, (s, e) in enumerate(reduced.spans.get(SPAN_PREFIX + "step", [])):
        r = traced[k] if k < len(traced) else None
        if r and r.get("compute_ms") is not None:
            mid = min(e, s + r["compute_ms"] / 1e3)
            named += [("backward_export", (s, mid)), ("drain", (mid, e))]
        else:
            named.append(("in_step", (s, e)))
    return {
        "device_ops": [[n, t] for n, t in reduced.op_seconds[:10]],
        "idle_gaps": [[name_gap(g, named), g[1] - g[0]]
                      for g in reduced.gaps[:5]]}


def time_control(step, init, key, batches) -> float:
    """The fused in-jit step on the same model and batches: median wall
    of ``CONTROL_STEPS`` steps after two untimed ones."""
    import jax

    params, opt = init(key)
    walls = []
    for i in range(CONTROL_STEPS + 2):
        t0 = time.perf_counter()
        params, opt, loss = step(params, opt, batches[i % len(batches)])
        jax.block_until_ready((params, opt, loss))
        walls.append((time.perf_counter() - t0) * 1e3)
    del params, opt
    log("control (make_train_step) walls ms: "
        + " ".join(f"{w:.2f}" for w in walls))
    return statistics.median(walls[2:])


def main(argv=None, wrap_step=None, transport=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any platform; prints no result line")
    ap.add_argument("--manifest",
                    default=os.path.join(REPO, "BENCHMARK.json"))
    args = ap.parse_args(argv)
    # a hang (protocol bug, dead callback) ends as a failure with stacks
    # inside the first run's time limit, not as a silent timeout
    faulthandler.dump_traceback_later(1150, exit=True)
    result = run_cell(args, wrap_step, transport)
    faulthandler.cancel_dump_traceback_later()
    log(f"total wall {time.perf_counter() - T_START:.1f} s")
    if args.rehearse:
        log(f"rehearsal reached the result: correct={result['correct']} "
            f"metrics={sorted(result['metrics'])}")
        return 0
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
