#!/usr/bin/env python3
"""Reads, on the chip and at a cell's own size, the two numbers every
limit of ``correct`` is set from: what sound runs of the program give
against the reference over many seeds, and what the control gives, the
reference computed in the nearest precision below the configuration's
(``common.fp8_operand``). One process, one compilation; no measured
window (training's readings need none). The benchmark's runs never call
this; PERF.md holds the readings and the limits set from them.

    python benchmark/calibrate.py --workload <name> --seeds 12 [--first-seed n]
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
# first, whatever else PYTHONPATH holds: this checkout's files
sys.path.insert(0, REPO)


def main(argv=None) -> int:
    from benchmark import run
    from benchmark.correct import compare_training

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2_200_000_001)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--manifest",
                    default=os.path.join(REPO, "BENCHMARK.json"))
    args = ap.parse_args(argv)
    spec = run.load_cell(args.manifest, args.workload, args.rehearse)
    config, traffic = spec["config"], spec["traffic"]

    server = None
    if traffic["path"] == "ps":
        from byteps_tpu.native.build import build

        build()
        server = run.ServerChild(run.free_port())
    try:
        import jax

        from benchmark.reference.common import fp8_operand, seed_key
        from benchmark.reference.train import Reference
        from byteps_tpu.parallel.mesh import DP_AXIS, make_mesh

        if not args.rehearse:
            if jax.devices()[0].platform != "tpu":
                raise SystemExit("calibrate reads on the chip only")
            run.place_compile_cache()
        mesh = make_mesh({DP_AXIS: traffic["chips"]},
                         jax.devices()[:traffic["chips"]])
        bps = run.connect_worker(server, mesh) if server else None
        prog = run.Program(config, traffic, mesh)
        loose = {k: float("inf") for k in config["limits"]}
        model, n_rows = prog.model, prog.rows
        seeds = [args.first_seed + 7919 * n for n in range(args.seeds)]
        programs = []
        for seed in seeds:
            key = seed_key(seed)
            params, opt = prog.init(key)
            batches = [prog.make_batch(key, i) for i in range(prog.n_check)]
            params, opt, program, _ = prog.first_steps(key, params, opt,
                                                       batches)
            programs.append(run.to_host(program))
            del params, opt, batches
        if bps is not None:
            bps.shutdown()
            server.wait_exit()
        # the program's executables keep their reserved memory while
        # they are loaded: drop them before the reference is built
        prog.step = None
        del prog
        gc.collect()
        # one reference at a time: each keeps its programs' reserved
        # memory while they are loaded
        reference = Reference(model, config, n_rows)
        refs = [reference.steps(seed_key(seed)) for seed in seeds]
        del reference
        gc.collect()
        rows = [{"seed": seed, "program": {
            k: v[0] for k, v in compare_training(program, ref, loose).items()}}
            for seed, program, ref in zip(seeds, programs, refs)]
        for row in rows:
            print("calibrate " + json.dumps(row), flush=True)
        # the rounding hooks cost memory: the control takes fewer rows at
        # a time, which changes no sum
        small = {**config, "check": {**config["check"],
                                     "reference_rows_per_block": 4}}
        control = Reference(model, small, n_rows, fp8_operand)
        for row, ref in list(zip(rows, refs))[:args.control_seeds]:
            row["control"] = {
                k: v[0] for k, v in compare_training(
                    control.steps(seed_key(row["seed"])), ref, loose).items()}
            print("calibrate " + json.dumps(row), flush=True)
        for name in rows[0]["program"]:
            sound = [r["program"][name] for r in rows]
            control = [r["control"][name] for r in rows if "control" in r]
            print(f"calibrate {args.workload} {name}: sound largest "
                  f"{max(sound):.6g} (smallest {min(sound):.6g}, "
                  f"{len(sound)} seeds); control smallest "
                  f"{min(control):.6g} (largest {max(control):.6g}, "
                  f"{len(control)} seeds)", flush=True)
    finally:
        if server is not None:
            server.kill()
    return 0


if __name__ == "__main__":
    sys.exit(main())
