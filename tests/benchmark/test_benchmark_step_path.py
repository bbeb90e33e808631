"""The seven per-layer metrics that read what the claim and the drain
waited for (``benchmark/layers/step_path.py``): each has a manifest
entry of the agreed shape, at the manifest's end, and a reader; each
layer is a row of PERF.md's list of layers; a rehearsal of a PS cell
gives each a number that holds the step's identities, the fused cell
none; and a program whose StepReports lack the fields (the parent
commit's) gives nothing to read and no error."""

import json
import os
import re

import pytest

from bench_helpers import REPO, manifest, run_python

from benchmark.layer_api import LayerContext, load_readers
from benchmark.layers import step_path

# name -> (source, layer, the StepReport field it reads)
ENTRIES = {
    "worker.backward_wait_ms":
        ("program_span", "worker step", "backward_wait_ms"),
    "export.behind_backward_ms":
        ("program_span", "export", "export_behind_backward_ms"),
    "export.train_thread_cpu_ms":
        ("program_counter", "export", "claim_thread_cpu_ms"),
    "host.step_cpu_ms": ("program_counter", "host", "step_cpu_ms"),
    "apply.pull_wait_ms":
        ("program_span", "import and apply", "pull_wait_ms"),
    "apply.land_ms": ("program_span", "import and apply", "drain_land_ms"),
    "wire.tail_after_claim_ms":
        ("program_span", "scheduler and wire", "wire_tail_after_claim_ms"),
}
NAMES = list(ENTRIES)

METRICS_OF_A_REHEARSAL = """
import json, sys
from benchmark import run

args = run.argparse.Namespace(
    workload=sys.argv[1], seed=2147483999, seconds=0.3, trace=1,
    rehearse=True, manifest=run.os.path.join(run.REPO, "BENCHMARK.json"))
result = run.run_cell(args)
print(json.dumps({k: v["value"] for k, v in result["metrics"].items()}))
"""


def _ps_cells():
    return [w["name"] for w in manifest()["workloads"]
            if w["traffic"].startswith("ps.")]


def test_the_seven_entries_close_the_manifest_in_the_agreed_order():
    entries = manifest()["per_layer"][-len(NAMES):]
    assert [e["name"] for e in entries] == NAMES
    assert len(_ps_cells()) == 6


@pytest.mark.parametrize("name", NAMES)
def test_an_entry_has_the_agreed_shape_and_its_reader(name):
    (entry,) = [e for e in manifest()["per_layer"] if e["name"] == name]
    source, layer, _ = ENTRIES[name]
    # every PS cell, in the manifest's order
    assert entry == {"name": name, "unit": "ms", "better": "lower",
                     "source": source, "layer": layer, "moves": "step_ms",
                     "workloads": _ps_cells()}
    assert load_readers()[name] is step_path.METRICS[name]
    assert sorted(step_path.METRICS) == sorted(NAMES)


def test_each_layer_is_a_row_of_the_list_of_layers():
    with open(os.path.join(REPO, "PERF.md")) as f:
        text = f.read()
    section = text[text.index("## 3. Layers"):text.index("## 4. Cells")]
    rows = {m.group(1) for m in re.finditer(r"^\| ([^|]+?) \|", section,
                                            re.M)}
    for name, (_, layer, field) in ENTRIES.items():
        assert layer in rows, layer
        assert f"`{name}`" in section and f"`{field}`" in section, name


def _ctx(reports):
    return LayerContext(
        steps=len(reports), window_s=1.0, step_ms=1.0, walls_ms=[1.0],
        global_batch=1, chips=1, reports=reports, counters_before={},
        counters_after={}, flops_per_step=0.0, peak_flops_per_chip=1.0)


@pytest.mark.parametrize("name", NAMES)
def test_a_reader_takes_the_median_of_its_field(name):
    field = ENTRIES[name][2]
    reports = [{"compute_ms": 9.0, field: v} for v in (5.0, 1.0, 3.0, 80.0,
                                                       2.0)]
    # the train thread's CPU moves in ticks of 10 ms on some kernels:
    # its reader takes the window's mean
    want = 18.2 if name == "export.train_thread_cpu_ms" else 3.0
    assert step_path.METRICS[name](_ctx(reports)) == pytest.approx(want)
    # a monolithic round's report holds None: not counted
    reports.append({"compute_ms": 9.0, field: None})
    assert step_path.METRICS[name](_ctx(reports)) == pytest.approx(want)


def test_the_mean_resolves_a_clock_of_ticks_where_the_median_cannot():
    ticks = [{"claim_thread_cpu_ms": v}
             for v in (10.0, 0.0, 10.0, 10.0, 20.0, 0.0, 10.0, 10.0)]
    assert step_path.METRICS["export.train_thread_cpu_ms"](_ctx(ticks)) \
        == pytest.approx(8.75)


def test_an_older_program_gives_nothing_to_read_but_its_pull_wait():
    # the parent's StepReports have had pull_wait_ms since PR 3 and none
    # of the other six fields
    old = [{"compute_ms": 9.0, "drain_ms": 4.0, "pull_wait_ms": 1.5}]
    for name in NAMES:
        got = step_path.METRICS[name](_ctx(old))
        assert got == (1.5 if name == "apply.pull_wait_ms" else None), name
        assert step_path.METRICS[name](_ctx([])) is None


@pytest.mark.parametrize("cell,reads", [
    ("bert-large.ps.1chip", True), ("bert-large.fused.1chip", False)])
def test_a_rehearsal_prints_the_seven_on_a_ps_cell_and_none_on_a_fused(
        cell, reads):
    proc = run_python(["-c", METRICS_OF_A_REHEARSAL, cell])
    assert proc.returncode == 0, proc.stderr[-3000:]
    values = json.loads(proc.stdout.splitlines()[-1])
    if not reads:
        assert not set(NAMES) & set(values)
        return
    for name in NAMES:
        assert isinstance(values[name], float) and values[name] >= 0, name
    # medians of one window's steps: the identities hold for each step,
    # and for the medians up to the steps' scatter
    assert (values["export.dispatch_ms"] + values["worker.backward_wait_ms"]
            + values["export.behind_backward_ms"]
            <= values["worker.compute_ms"] * 1.05)
    assert (values["apply.pull_wait_ms"] + values["apply.land_ms"]
            <= values["apply.drain_ms"] * 1.05)
