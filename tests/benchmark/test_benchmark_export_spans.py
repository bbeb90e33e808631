"""The six per-layer metrics that read the program's export spans
(``benchmark/layers/export_spans.py``): each has a manifest entry of
the agreed shape and a reader; a rehearsal run of a PS cell whose leaves
stream gives each a number, the fused cell none; and a program whose StepReports lack the
fields (the parent commit's) gives nothing to read and no error."""

import json

import pytest

from bench_helpers import manifest, run_python

from benchmark.layer_api import LayerContext, load_readers
from benchmark.layers import export_spans

NAMES = ["export.dispatch_ms", "export.tap_span_ms",
         "export.router_busy_ms", "export.materialize_ms",
         "export.submit_ms", "export.router_wait_max_ms"]
PS_CELLS = ["bert-large.ps.1chip", "vgg16.ps.1chip"]

METRICS_OF_A_REHEARSAL = """
import json, sys
from benchmark import run

args = run.argparse.Namespace(
    workload=sys.argv[1], seed=2147483888, seconds=0.3, trace=1,
    rehearse=True, manifest=run.os.path.join(run.REPO, "BENCHMARK.json"))
result = run.run_cell(args)
print(json.dumps({k: v["value"] for k, v in result["metrics"].items()}))
"""


def test_the_six_entries_close_the_manifest_and_each_has_a_reader():
    entries = manifest()["per_layer"][-6:]
    assert [e["name"] for e in entries] == NAMES
    for e in entries:
        assert e == {"name": e["name"], "unit": "ms", "better": "lower",
                     "source": "program_span", "layer": "export",
                     "moves": "step_ms", "workloads": PS_CELLS}
    assert sorted(export_spans.METRICS) == sorted(NAMES)
    readers = load_readers()
    assert all(readers[n] is export_spans.METRICS[n] for n in NAMES)


# at rehearsal size every VGG leaf is under the fusion threshold, so none
# streams: a PS step that reads nothing, like the fused cell's
@pytest.mark.parametrize("cell,reads", [
    ("bert-large.ps.1chip", True), ("vgg16.ps.1chip", False),
    ("bert-large.fused.1chip", False)])
def test_a_rehearsal_reads_a_number_where_leaves_stream_and_none_elsewhere(
        cell, reads):
    proc = run_python(["-c", METRICS_OF_A_REHEARSAL, cell])
    assert proc.returncode == 0, proc.stderr[-3000:]
    values = json.loads(proc.stdout.splitlines()[-1])
    if not reads:
        assert not set(NAMES) & set(values)
        return
    for name in NAMES:
        assert isinstance(values[name], float) and values[name] >= 0, name
    assert values["export.dispatch_ms"] <= values["worker.compute_ms"]
    assert values["export.router_busy_ms"] <= values["worker.compute_ms"]


def _ctx(reports):
    return LayerContext(
        steps=len(reports), window_s=1.0, step_ms=1.0, walls_ms=[1.0],
        global_batch=1, chips=1, reports=reports, counters_before={},
        counters_after={}, flops_per_step=0.0, peak_flops_per_chip=1.0)


def test_readers_take_the_median_and_read_nothing_from_an_older_program():
    new = [{"compute_ms": 9.0, "dispatch_ms": d, "export_tap_span_ms": 4.0,
            "export_router_busy_ms": 3.0, "export_materialize_ms": 1.0,
            "export_submit_ms": 0.5, "export_router_wait_max_ms": 2.0}
           for d in (1.0, 7.0, 2.0)]
    assert export_spans.METRICS["export.dispatch_ms"](_ctx(new)) == 2.0
    assert export_spans.METRICS["export.submit_ms"](_ctx(new)) == 0.5
    # the parent's StepReports: no such keys; a step with no streamed
    # leaf: the keys, holding None
    for old in ([{"compute_ms": 9.0}], [dict(new[0], dispatch_ms=None)],
                []):
        assert export_spans.METRICS["export.dispatch_ms"](_ctx(old)) is None
