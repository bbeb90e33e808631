"""The readers ``benchmark/layers/moe.py`` and ``attention.py`` add: the
``moe/*`` counters on hand-made snapshots, the kernels' device time and
roofline shares on a synthetic reduced trace that holds their operation
families, the counts they divide by, nothing read (and nothing raised)
where the program has no such counter or kernel, and an error, not a
silent null, where the kernel is there and the cell's configuration or
chip cannot be found."""

import json
import os

import pytest

from bench_helpers import BENCH, MANIFEST, manifest

from benchmark.layer_api import LayerContext, load_readers
from benchmark.layers import _cell
from benchmark.reference import mellum as reference
from benchmark.trace_reduce import Reduced, op_family

CELL = "mellum2-12b.ps.1chip"
NEW = ("experts.routed_pairs_per_step", "experts.load_max_over_mean",
       "experts.dropped_pairs", "experts.grouped_mm_roofline_pct",
       "attention.window_roofline_pct", "attention.full_roofline_pct",
       "attention.device_ms", "experts.device_ms")
PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9


def _config():
    with open(os.path.join(BENCH, "configs", "mellum2-12b.json")) as f:
        return json.load(f)


def _ctx(trace=None, before=None, after=None, steps=10):
    return LayerContext(
        steps=steps, window_s=10.0, step_ms=1000.0, walls_ms=[1000.0] * steps,
        global_batch=4, chips=1, reports=[], counters_before=before or {},
        counters_after=after or {}, flops_per_step=4e13,
        peak_flops_per_chip=PEAK_FLOPS, trace=trace,
        traced_steps=3 if trace is not None else 0)


def _trace(**seconds):
    ops = sorted(seconds.items(), key=lambda kv: -kv[1])
    return Reduced(window_s=3.0, busy_s={0: 2.0},
                   op_seconds=[(k.replace("_", "."), v) if k.startswith("bps")
                               else (k.replace("_", "-"), v) for k, v in ops],
                   gaps=[])


@pytest.fixture()
def running(monkeypatch):
    monkeypatch.setattr(_cell, "running_config", _config)
    monkeypatch.setattr(_cell, "peak_bytes_per_s", lambda: PEAK_BYTES)


def _load_counters(per_step, steps, start=7):
    """Snapshots of ``moe/expert_load/<layer>/<expert>``: ``per_step`` is
    the [layer][expert] load of one step."""
    before = {f"moe/expert_load/{l}/{e}": start
              for l, row in enumerate(per_step) for e in range(len(row))}
    after = {f"moe/expert_load/{l}/{e}": start + steps * pairs
             for l, row in enumerate(per_step) for e, pairs in enumerate(row)}
    return before, after


def test_manifest_lists_the_new_metrics_for_the_new_cell_only():
    rows = {m["name"]: m for m in manifest()["per_layer"]}
    readers = load_readers()
    for name in NEW:
        assert rows[name]["workloads"] == [CELL] and name in readers
        assert rows[name]["moves"] == "step_ms"
    assert {rows[n]["layer"] for n in NEW} == {"experts", "attention"}


def test_kernel_names_are_families_of_the_reduced_trace():
    """A Pallas call's HLO instruction is named by the scope around it,
    XLA's grouped product by itself; ``op_family`` strips the number."""
    assert op_family("%bps.attn.window.3 = bf16[4,32,8192,128]{3,2,1,0} "
                     "custom-call(...)") == "bps.attn.window"
    assert op_family("%bps.attn.full = bf16[1] custom-call()") == \
        "bps.attn.full"
    assert op_family("%ragged-dot-none.12 = f32[1] custom-call()") == \
        "ragged-dot-none"


def test_counters_on_hand_made_snapshots():
    """Routed pairs and the imbalance are derived here from the one
    additive statistic, the load of each held (layer, expert)."""
    readers = load_readers()
    before, after = _load_counters([[4000, 6000], [1000, 5000]], steps=10)
    ctx = _ctx(before={**before, "moe/dropped_pairs": 0, "wire/x": 1},
               after={**after, "moe/dropped_pairs": 0, "wire/x": 99})
    assert readers["experts.routed_pairs_per_step"](ctx) == 16000
    assert readers["experts.dropped_pairs"](ctx) == 0
    assert readers["experts.load_max_over_mean"](ctx) == \
        pytest.approx(6000 / 4000)


def test_a_program_without_the_counters_or_kernels_reads_nothing(running):
    """The parent of PR 26, or any other cell: no ``moe/*`` counter, no
    such operation in the trace; every reader returns None."""
    readers = load_readers()
    bare = _ctx(trace=_trace(fusion=1.0), before={"wire/push_bytes": 1},
                after={"wire/push_bytes": 9})
    for name in NEW:
        assert readers[name](bare) is None, name
    for name in NEW:
        assert readers[name](_ctx()) is None, name


def test_device_time_and_roofline_shares(running):
    readers = load_readers()
    cfg = _config()
    pairs = 131072.0
    # 3 traced steps: 90 ms of grouped products, 240 + 360 ms of kernels
    trace = _trace(ragged_dot_none=0.089, ragged_dot_metadata=0.001,
                   bps_attn_window=0.240, bps_attn_full=0.360, fusion=1.5)
    before, after = _load_counters([[pairs / 2, pairs / 2]], steps=10)
    ctx = _ctx(trace=trace, before=before, after=after)
    assert readers["experts.device_ms"](ctx) == pytest.approx(30.0)
    assert readers["attention.device_ms"](ctx) == pytest.approx(200.0)
    flops, nbytes = reference.expert_products_cost(pairs, cfg)
    want = 100 * max(flops / PEAK_FLOPS, nbytes / PEAK_BYTES) / 0.030
    assert readers["experts.grouped_mm_roofline_pct"](ctx) == \
        pytest.approx(want)
    for name, kind, seconds, layers in (
            ("attention.window_roofline_pct", "sliding_attention", 0.080, 3),
            ("attention.full_roofline_pct", "full_attention", 0.120, 1)):
        flops, nbytes = reference.attention_step_cost(4, cfg, kind)
        want = 100 * layers * max(flops / PEAK_FLOPS,
                                  nbytes / PEAK_BYTES) / seconds
        got = readers[name](ctx)
        assert got == pytest.approx(want) and 0 < got < 100


def test_counts_of_operations_and_bytes():
    cfg = _config()
    S, W = cfg["seq_len"], cfg["sliding_window"]
    assert reference.band_pairs(4) == 10 and reference.band_pairs(4, 2) == 7
    assert reference.band_pairs(S) == S * (S + 1) // 2
    # the window layer's count is of the band only: under a quarter
    assert reference.band_pairs(S, W) == W * (W + 1) // 2 + (S - W) * W
    assert reference.band_pairs(S, W) < reference.band_pairs(S) / 3.7
    # what a step needs: 2 products forward, 5 backward, over the band;
    # nothing the program recomputes, bytes once
    flops, nbytes = reference.attention_step_cost(4, cfg, "full_attention")
    assert flops == 7 * 2 * 32 * 128 * reference.band_pairs(S) * 4
    assert nbytes == 4 * S * (2 * 128 * (6 * 32 + 6 * 4) + 2 * 4 * 32)
    wflops, _ = reference.attention_step_cost(4, cfg, "sliding_attention")
    assert wflops / flops == reference.band_pairs(S, W) / \
        reference.band_pairs(S)
    assert flops / nbytes > 240                 # compute-bound on a v5e
    # 8 of 64 experts held, 8 a token: one pair a token and layer
    assert reference.expected_pairs_per_token(cfg) == 1.0
    flops, nbytes = reference.expert_products_cost(1000.0, cfg)
    assert flops == 3 * 2 * 1000 * 3 * 2304 * 896
    total = reference.model_flops_per_step(4, cfg)
    per_token = total / (4 * S)
    assert 1.1e9 < per_token < 1.3e9           # ISSUE 26: 1.6 GFLOP with remat


def test_running_config_is_found_from_the_command_line():
    cfg = _cell.running_config(["--workload", CELL, "--manifest", MANIFEST])
    assert cfg["hidden_size"] == 2304 and cfg["seq_len"] == 8192
    tiny = _cell.running_config(["--workload", CELL, "--manifest", MANIFEST,
                                 "--rehearse", "--seed", "3"])
    assert tiny["hidden_size"] == 64 and tiny["head_dim"] == 16
    assert tiny["rope_parameters"]["full_attention"]["beta_fast"] == 32


def test_a_kernel_in_the_trace_and_no_configuration_is_an_error():
    """Found its kernel, cannot size the work: the reader raises (this
    process's command line names no cell; a CPU has no row in
    ``peaks.json``) and never prints a null roofline."""
    with pytest.raises(LookupError, match="names no cell"):
        _cell.running_config(["--workload", "no.such"])
    with pytest.raises(LookupError):
        _cell.running_config([])
    with pytest.raises(KeyError):
        _cell.peak_bytes_per_s()
    readers = load_readers()
    before, after = _load_counters([[500.0]], steps=10)
    ctx = _ctx(trace=_trace(ragged_dot_none=0.09, bps_attn_full=0.3),
               before=before, after=after)
    for name in ("experts.grouped_mm_roofline_pct",
                 "attention.full_roofline_pct"):
        with pytest.raises(LookupError):
            readers[name](ctx)


def test_the_configuration_keeps_every_published_number():
    """Every number of the catalog's ``config`` is in the file under the
    same key, changed only where ``reduced`` says; the two layer lists
    are whole; what is assumed is listed."""
    cfg = _config()
    published = {
        "head_dim": 128, "hidden_size": 2304, "intermediate_size": 7168,
        "max_position_embeddings": 131072, "max_window_layers": 0,
        "moe_intermediate_size": 896, "num_attention_heads": 32,
        "num_experts": 64, "num_experts_per_tok": 8,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "sliding_window": 1024}
    for k, v in published.items():
        assert cfg[k] == v, k
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts_held",
                              "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_experts_held"],
            cfg["vocab_size"]) == (4, 8, 12288)
    assert cfg["published"]["num_hidden_layers"] == 28
    assert len(cfg["layer_types"]) == len(cfg["mlp_layer_types"]) == 28
    assert cfg["layer_types"][:4] == ["sliding_attention"] * 3 + \
        ["full_attention"]
    assert cfg["rope_parameters"]["full_attention"] == {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782}
    for item in ("qk_norm", "multi_token_prediction", "router_aux_loss",
                 "dropout", "optimizer", "init"):
        assert item in cfg["assumed"], item
    assert "8 chips" in cfg["deployment"]
    # the program's tile sizes are the program's: no key for them here
    assert "program" not in cfg and "program" not in cfg["assumed"]
    assert cfg["check"]["steps"] == 3
