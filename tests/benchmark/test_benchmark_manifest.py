"""BENCHMARK.json against the contract's static rules and against the
files it names: a later PR that adds an entry without its file, or a
metric without a reader, fails here and not on the chip."""

import json
import os
import re

import pytest

from bench_helpers import BENCH, REPO, manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def one_line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level_keys_and_limits():
    m = manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= m["run_seconds"] <= 51 and isinstance(m["run_seconds"], int)
    assert 1 <= len(m["paths"]) <= 16
    for path in m["paths"]:
        assert os.path.isdir(os.path.join(REPO, path))
    assert len(m["command"]) <= 32 and all(one_line(w) for w in m["command"])
    program = m["command"][1]
    assert any(program.startswith(p + "/") for p in m["paths"])
    # the full check with 24 cells fits its budget
    runs = 2 + 14 * 24
    assert runs * (m["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_configs_and_cells_name_files_that_exist():
    m = manifest()
    configs = {c["name"]: c for c in m["configs"]}
    assert len(configs) == len(m["configs"])
    used = set()
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert w["chips"] in (1, 4) and one_line(w["why"])
        used.add(w["config"])
        traffic = os.path.join(BENCH, "traffic", w["traffic"] + ".json")
        with open(traffic) as f:
            data = json.load(f)
        assert data["chips"] == w["chips"]
        # every key of a traffic file is one the runner reads
        assert set(data) <= {"path", "chips", "batches", "warmup_steps",
                             "placement", "ps_step", "rehearse"}
    assert used == set(configs), "a configuration no cell uses"
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(1 for w in m["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(m["workloads"]) // 4)
    for c in configs.values():
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and one_line(c["source"])
        assert any(c["file"].startswith(p + "/") for p in m["paths"])
        with open(os.path.join(REPO, c["file"])) as f:
            data = json.load(f)
        assert data["reduced"] == c["reduced"] and data["source"] == c["source"]
        family = data["family"]
        assert os.path.isfile(os.path.join(BENCH, "families", family + ".py"))
        assert os.path.isfile(os.path.join(BENCH, "reference", family + ".py"))
        assert set(data["limits"]) == {"loss_rel_gap", "grad_norm_gap",
                                       "delta_norm_gap",
                                       "transport_blocks_differing",
                                       "wire_bytes_per_step_gap",
                                       "server_fold_bytes_gap"}
        kind = data["optimizer"]["kind"]
        assert os.path.isfile(os.path.join(BENCH, "optimizers", kind + ".py"))


def test_metrics_are_well_formed_and_every_one_has_a_reader():
    from benchmark.layer_api import load_readers

    m = manifest()
    cells = {w["name"] for w in m["workloads"]}
    e2e = {}
    for metric in m["end_to_end"]:
        assert set(metric) - {"workloads"} == {"name", "unit", "better",
                                               "bound", "source"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0 < metric["bound"] <= 0.1
        e2e[metric["name"]] = set(metric.get("workloads", cells))
    assert "setup_s" in e2e and e2e["setup_s"] == cells
    readers = load_readers()
    layers = set()
    for metric in m["per_layer"]:
        assert set(metric) - {"workloads"} == {"name", "unit", "better",
                                               "source", "layer", "moves"}
        assert metric["source"] in SOURCES and one_line(metric["layer"])
        layers.add(metric["layer"])
        assert metric["name"] in readers, f"no reader for {metric['name']}"
        # each of its cells reports the end-to-end metric it should move
        assert set(metric.get("workloads", cells)) <= e2e[metric["moves"]]
    names = [x["name"] for x in m["end_to_end"] + m["per_layer"]]
    assert len(set(names)) == len(names)
    for metric in m["end_to_end"] + m["per_layer"]:
        assert NAME.match(metric["name"]), metric["name"]
        assert UNIT.match(metric["unit"]), metric["unit"]
        assert metric["better"] in ("lower", "higher")
        assert set(metric.get("workloads", cells)) <= cells
    # every cell reports a per-layer metric and an end-to-end one
    for cell in cells:
        assert any(cell in x.get("workloads", cells) for x in m["per_layer"])
        assert sum(cell in v for v in e2e.values()) >= 2
    # PERF.md's list of layers has each layer by the same name
    with open(os.path.join(REPO, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:
        assert f"| {layer} |" in perf, f"PERF.md lists no layer {layer!r}"


@pytest.mark.parametrize("kind", ["TPU v5 lite"])
def test_peaks_table_has_the_chip_with_its_source(kind):
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    assert "Google Cloud" in peaks["source"]
    assert peaks[kind]["bf16_flops_per_s"] == 197e12
    assert peaks[kind]["hbm_bytes_per_s"] == 819e9


def test_files_under_paths_are_named_from_a_name_s_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in manifest()["paths"]:
        for root, dirs, files in os.walk(os.path.join(REPO, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(root, f), REPO)
                assert ok.match(rel), rel
