"""``correct`` has to come out false when the thing it guards breaks:
a transport that rounds to bf16, a partition swapped for another's, a
step that returns its state unchanged, a step whose own wire carries
fewer bytes than the configuration's wire type (a lower-precision or
compressed push), a reference computed in the precision below the
configuration's (the control)."""

import json
import os
import shutil

import numpy as np
import pytest

from bench_helpers import BENCH, MANIFEST, last_line, run_python

from benchmark import correct


def leaves(seed=0):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal(n).astype(np.float32)
            for n in (3 * correct.BLOCK + 17, 4096, 2 * correct.BLOCK)]


def mismatch(sent, back):
    return sum(correct.transport_mismatch(a, b) for a, b in zip(sent, back))


def test_identity_transport_passes():
    sent = leaves()
    assert mismatch(sent, [x.copy() for x in sent]) == 0


def test_bf16_rounded_transport_fails():
    import jax.numpy as jnp

    sent = leaves()
    back = [np.asarray(jnp.asarray(x).astype(jnp.bfloat16)
                       .astype(jnp.float32)) for x in sent]
    # every block of every leaf differs
    assert mismatch(sent, back) == 4 + 1 + 2
    rows = {"transport_blocks_differing": (float(mismatch(sent, back)), 0.0)}
    assert correct.verdict(rows, log=lambda m: None) is False


def test_swapped_partition_fails_in_exactly_the_blocks_swapped():
    sent = leaves()
    back = [x.copy() for x in sent]
    b = correct.BLOCK
    back[0][b:2 * b], back[2][:b] = sent[2][:b].copy(), sent[0][b:2 * b].copy()
    assert mismatch(sent, back) == 2


def test_wrong_dtype_or_size_counts_every_block():
    sent = leaves()[:1]
    assert mismatch(sent, [sent[0].astype(np.float16)]) == 4
    assert mismatch(sent, [sent[0][:-1]]) == 4


def test_worst_leaf_gap_floors_small_leaves_at_the_median():
    # a leaf whose gradient is all but zero is measured against the
    # median leaf, not against itself
    want = [1.0, 2.0, 1e-9]
    assert correct.worst_leaf_gap([1.0, 2.0, 2e-9], want) < 1e-8
    assert correct.worst_leaf_gap([1.1, 2.0, 1e-9], want) == pytest.approx(0.1)


def test_compare_training_reads_each_number_and_nan_fails():
    ref = {"losses": [10.0, 9.0, 8.0], "grad_norms": [1.0, 2.0, 3.0],
           "delta_norms": [0.1, 0.2, 0.3]}
    limits = {"loss_rel_gap": 1e-2, "grad_norm_gap": 1e-2,
              "delta_norm_gap": 1e-2}
    assert correct.verdict(correct.compare_training(ref, ref, limits),
                           log=lambda m: None)
    # the state came back unchanged: no change in the parameters
    still = dict(ref, delta_norms=[0.0, 0.0, 0.0])
    rows = correct.compare_training(still, ref, limits)
    assert rows["delta_norm_gap"][0] == pytest.approx(1.0)
    assert not correct.verdict(rows, log=lambda m: None)
    # a part of the batch left out moves the loss
    part = dict(ref, losses=[10.3, 9.0, 8.0])
    assert not correct.verdict(
        correct.compare_training(part, ref, limits), log=lambda m: None)
    nan = dict(ref, losses=[float("nan"), 9.0, 8.0])
    assert not correct.verdict(
        correct.compare_training(nan, ref, limits), log=lambda m: None)


WIRE_LIMITS = {"wire_bytes_per_step_gap": 0, "server_fold_bytes_gap": 0}


@pytest.mark.parametrize("pushed,folded,failed", [
    (19 * 4000, 19 * 4000, None),                       # f32, all folded
    (19 * 2000, 19 * 2000, "wire_bytes_per_step_gap"),  # a bf16 wire
    (19 * 4000 - 4, 19 * 4000 - 4, "wire_bytes_per_step_gap"),
    (19 * 4000, 18 * 4000, "server_fold_bytes_gap"),    # a step not folded
])
def test_the_step_s_own_wire_is_held_to_the_configuration_s_type(
        pushed, folded, failed):
    rows = correct.compare_wire(pushed, folded, steps=19, wire_bytes=4000,
                                limits=WIRE_LIMITS)
    bad = [k for k, (value, limit) in rows.items() if value > limit]
    assert bad == ([failed] if failed else [])
    assert correct.verdict(rows, log=lambda m: None) is (failed is None)


def test_a_run_with_the_program_s_codec_on_its_wire_reports_not_correct(
        tmp_path):
    """The program's own lower-precision path (``make_ps_train_step``'s
    ``compression``), switched on by a traffic file in a temporary copy:
    the timed step pushes fewer bytes than the configuration's float32
    wire, and the run says so."""
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    traffic = tmp_path / "benchmark" / "traffic"
    mix = json.loads((traffic / "ps.1chip.json").read_text())
    mix["ps_step"] = {"compression": {"compressor": "onebit",
                                      "ef": "vanilla"},
                      "min_compress_bytes": 0}
    (traffic / "ps-onebit.1chip.json").write_text(json.dumps(mix))
    with open(MANIFEST) as f:
        manifest = json.load(f)
    cell = "bert-large.ps-onebit.1chip"
    manifest["workloads"].append({
        "name": cell, "config": "bert-large", "traffic": "ps-onebit.1chip",
        "chips": 1, "why": "a test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    proc = run_python([str(tmp_path / "benchmark" / "run.py"), "--workload",
                       cell, "--seed", "2147484777", "--seconds", "0.2",
                       "--trace", "0", "--rehearse"], cwd=str(tmp_path))
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "rehearsal reached the result: correct=False" in last_line(proc)
    rows = [ln for ln in proc.stdout.splitlines() if "correct: " in ln]
    assert any("wire_bytes_per_step_gap" in ln and ln.endswith("FAILED")
               for ln in rows), rows
    # the check's own round trip, a pass of its own, does not see it
    assert any("transport_blocks_differing = 0 " in ln and ln.endswith("ok")
               for ln in rows), rows


BROKEN_RUN = """
import sys
from benchmark import run

def half_batch(step):
    import jax
    def broken(params, opt, batch):
        half = jax.tree.map(lambda x: x.at[x.shape[0] // 2:].set(
            x[:x.shape[0] // 2]), batch)
        return step(params, opt, half)
    return broken

def bf16_wire(leaves):
    import jax.numpy as jnp, numpy as np
    return [np.asarray(jnp.asarray(x).astype(jnp.bfloat16)
                       .astype(jnp.float32)) for x in leaves]

kind = sys.argv[1]
wl = "bert-large.ps.1chip" if kind == "bf16_wire" else "vgg16.ps.1chip"
sys.exit(run.main(
    ["--workload", wl, "--seed", "2147483999", "--seconds", "0.2",
     "--trace", "0", "--rehearse"],
    wrap_step=half_batch if kind == "half_batch" else None,
    transport=bf16_wire if kind == "bf16_wire" else None))
"""


@pytest.mark.parametrize("kind,failed", [
    ("half_batch", "loss_rel_gap"),
    ("bf16_wire", "transport_blocks_differing"),
])
def test_a_run_with_the_timed_path_broken_reports_not_correct(kind, failed):
    """Skips only the harness's look for a chip (the rehearsal switch)
    and drives the rest of a run with the path broken underneath."""
    proc = run_python(["-c", BROKEN_RUN, kind])
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "rehearsal reached the result: correct=False" in last_line(proc)
    assert any(f"correct: {failed} = " in ln and ln.endswith("FAILED")
               for ln in proc.stdout.splitlines()), proc.stdout[-3000:]
    assert not last_line(proc).startswith("{")


def test_the_control_fails_and_the_reference_passes_at_test_size():
    """The control is the reference computed in float8 operands, the
    precision below the configurations' bf16. At the size a test can
    hold it must fail one of the numbers under the rehearsal's own
    limits (which the bf16 program passes: test_benchmark_cells)."""
    code = """
import json, sys
import jax
from benchmark import run
from benchmark.correct import compare_training
from benchmark.reference.common import fp8_operand, seed_key
from benchmark.reference.train import Reference
from benchmark.reference import vgg
spec = run.load_cell(run.os.path.join(run.REPO, "BENCHMARK.json"),
                     "vgg16.ps.1chip", True)
cfg = spec["config"]
out = []
reference, control = Reference(vgg, cfg, 4), Reference(vgg, cfg, 4, fp8_operand)
for seed in (11, 2147483659, 3000000019):
    key = seed_key(seed)
    rows = compare_training(control.steps(key), reference.steps(key),
                            cfg["limits"])
    out.append({k: v for k, v in rows.items()})
print(json.dumps(out))
"""
    proc = run_python(code)
    assert proc.returncode == 0, proc.stderr[-3000:]
    for rows in json.loads(last_line(proc)):
        assert any(value > limit for value, limit in rows.values()), rows
