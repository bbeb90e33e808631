"""A later PR adds a configuration, a traffic mix, a model family, an
optimizer and a per-layer metric as new files and manifest entries only. Done here in a
temporary copy of the benchmark: the harness must find all four."""

import json
import os
import shutil

from bench_helpers import BENCH, MANIFEST, REPO, last_line, run_python


def test_new_cell_family_and_metric_are_found_by_name(tmp_path):
    root = tmp_path / "copy"
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = root / "benchmark"
    with open(MANIFEST) as f:
        manifest = json.load(f)
    before = {p: (bench / p).read_bytes() for p in
              ("run.py", "layer_api.py", "correct.py", "trace_reduce.py",
               "optimizers/__init__.py", "reference/train.py",
               "reference/common.py")}

    # a family: its program binding and its plain reference
    (bench / "families" / "toy.py").write_text(
        "from ..reference import toy as reference  # noqa: F401\n"
        "from .vgg import program_loss  # noqa: F401\n")
    (bench / "reference" / "toy.py").write_text(
        "from .vgg import *  # noqa: F401,F403\n"
        "from .vgg import (init_params, make_batch, model_flops_per_step,\n"
        "                  nll_sum, slice_rows)\n")
    # an optimizer: the transform, the way back to its first gradient,
    # and its plain reference
    (bench / "optimizers" / "toy_sgd.py").write_text(
        "from .sgd import (first_gradient, make_tx, reference_init,  # noqa\n"
        "                  reference_update)\n")
    # a configuration of that family with that optimizer
    with open(os.path.join(BENCH, "configs", "vgg16.json")) as f:
        cfg = json.load(f)
    cfg = {**cfg, **cfg["rehearse"], "family": "toy", "source": "a test",
           "optimizer": {**cfg["optimizer"], "kind": "toy_sgd"}}
    (bench / "configs" / "toy-net.json").write_text(json.dumps(cfg))
    # a traffic mix
    (bench / "traffic" / "fused-cold.1chip.json").write_text(json.dumps({
        "path": "fused", "chips": 1, "batches": 3, "warmup_steps": 0,
        "placement": None}))
    # a per-layer metric
    (bench / "layers" / "toy.py").write_text(
        "METRICS = {'toy.steps_counted': lambda ctx: float(ctx.steps),\n"
        "           'toy.nothing_to_read': lambda ctx: None}\n")
    cell = "toy-net.fused-cold.1chip"
    manifest["configs"].append({
        "name": "toy-net", "source": "a test", "reduced": [],
        "file": "benchmark/configs/toy-net.json", "why": "a test"})
    manifest["workloads"].append({
        "name": cell, "config": "toy-net", "traffic": "fused-cold.1chip",
        "chips": 1, "why": "a test"})
    for name in ("toy.steps_counted", "toy.nothing_to_read"):
        manifest["per_layer"].append({
            "name": name, "unit": "count", "better": "higher",
            "source": "program_counter", "layer": "toy", "moves": "step_ms",
            "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))

    proc = run_python([str(bench / "run.py"), "--workload", cell, "--seed",
                       "2147484001", "--seconds", "0.2", "--trace", "1",
                       "--rehearse"], cwd=str(root))
    assert proc.returncode == 0, proc.stderr[-3000:]
    end = last_line(proc)
    assert "rehearsal reached the result: correct=True" in end, \
        proc.stdout[-3000:]
    # the new reader's metric is in the line, the one that found nothing
    # to read is left out, and so are other cells' metrics
    assert "'toy.steps_counted'" in end
    assert "toy.nothing_to_read" not in end
    assert "worker.compute_ms" not in end
    assert "step_ms (window over steps)" in proc.stdout
    # no file the benchmark already had was edited to get there
    assert before == {p: (bench / p).read_bytes() for p in before}
    assert not os.path.exists(os.path.join(REPO, "benchmark", "layers",
                                           "toy.py"))
