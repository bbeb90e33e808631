"""What the ``kimi`` family brings to the benchmark: the readers of
``benchmark/layers/kimi.py`` on hand-made snapshots and a synthetic
reduced trace (None where there is nothing to read), the existing
readers the new cell joined counting this configuration rightly, the
manifest's entries by membership, and the configuration's file against
the catalog's published numbers."""

import json
import os

import pytest

from bench_helpers import BENCH, MANIFEST, manifest

from benchmark.layer_api import LayerContext, load_readers
from benchmark.layers import _cell
from benchmark.reference import kimi as reference
from benchmark.trace_reduce import Reduced

CELL = "kimi-linear-48b-a3b.ps.1chip"
CONFIG = "kimi-linear-48b-a3b"
NEW = ("attention.kda_device_ms", "attention.kda_roofline_pct",
       "attention.kda_chunk_steps_per_step")
JOINED = ("worker.compute_ms", "worker.ttfp_ms", "worker.centre_step_ms",
          "export.mb_per_step", "export.gbps", "staging.slot_allocs",
          "wire.requests_per_step", "wire.pull_p95_ms", "server.fold_ms",
          "server.queue_ms", "apply.drain_ms", "control.fused_step_ms",
          "kernels.busy_mfu_pct", "device.idle_pct", "export.dispatch_ms",
          "export.router_busy_ms", "export.materialize_ms",
          "export.submit_ms", "worker.backward_wait_ms",
          "export.behind_backward_ms", "export.train_thread_cpu_ms",
          "host.step_cpu_ms", "apply.pull_wait_ms", "apply.land_ms",
          "wire.tail_after_claim_ms", "experts.routed_pairs_per_step",
          "experts.load_max_over_mean", "experts.dropped_pairs",
          "experts.device_ms", "experts.bias_moved_pairs_per_step",
          "attention.mla_device_ms")
# their readers count blocks or experts by another family's keys or in
# ``num_hidden_layers`` layers (one is dense here), or read what no
# program has
NOT_JOINED = ("mtp.predicted_tokens_per_step", "attention.mla_roofline_pct",
              "experts.grouped_mm_roofline_pct",
              "experts.sparse_mm_roofline_pct", "attention.device_ms",
              "attention.window_roofline_pct", "attention.full_roofline_pct",
              "attention.blockdiff_device_ms", "export.tap_span_ms",
              "export.router_wait_max_ms")
PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9


def _config():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


def _ctx(trace=None, before=None, after=None, steps=10):
    return LayerContext(
        steps=steps, window_s=10.0, step_ms=1000.0, walls_ms=[1000.0] * steps,
        global_batch=2, chips=1, reports=[], counters_before=before or {},
        counters_after=after or {}, flops_per_step=2e13,
        peak_flops_per_chip=PEAK_FLOPS, trace=trace,
        traced_steps=3 if trace is not None else 0)


def _trace(**seconds):
    return Reduced(window_s=3.0, busy_s={0: 2.0}, gaps=[],
                   op_seconds=[(k.replace("_", "."), v) if k.startswith("bps")
                               else (k.replace("_", "-"), v)
                               for k, v in seconds.items()])


@pytest.fixture()
def running(monkeypatch):
    monkeypatch.setattr(_cell, "running_config", _config)
    monkeypatch.setattr(_cell, "peak_bytes_per_s", lambda: PEAK_BYTES)


def test_the_manifest_has_the_configuration_the_cell_and_the_metrics():
    """Membership, not position or exact lists: later PRs append."""
    m = manifest()
    configs = {c["name"]: c for c in m["configs"]}
    assert configs[CONFIG]["file"] == f"benchmark/configs/{CONFIG}.json"
    assert configs[CONFIG]["source"] == _config()["source"]
    assert set(configs[CONFIG]["reduced"]) == set(_config()["reduced"])
    assert len(configs[CONFIG]["why"]) <= 200
    cells = {w["name"]: w for w in m["workloads"]}
    assert (cells[CELL]["config"], cells[CELL]["traffic"],
            cells[CELL]["chips"]) == (CONFIG, "ps.1chip", 1)
    assert len(cells[CELL]["why"]) <= 200
    # one cell of this configuration, and one cell on four chips as before
    assert [w["name"] for w in m["workloads"] if w["config"] == CONFIG] \
        == [CELL]
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    rows = {x["name"]: x for x in m["per_layer"]}
    readers = load_readers()
    for name in NEW + JOINED:
        assert CELL in rows[name]["workloads"], name
        assert rows[name]["moves"] == "step_ms" and name in readers, name
    for name in NOT_JOINED:
        assert CELL not in rows[name]["workloads"], name
    for name in NEW:
        assert rows[name]["layer"] == "attention"
        assert rows[name]["workloads"] == [CELL]
        assert set(rows[name]) == {"name", "unit", "better", "source",
                                   "layer", "moves", "workloads"}
    for name in NEW[:2]:
        assert rows[name]["source"] == "device_trace"
    assert (rows[NEW[0]]["unit"], rows[NEW[0]]["better"]) == ("ms", "lower")
    assert (rows[NEW[1]]["unit"], rows[NEW[1]]["better"]) == ("%", "higher")
    assert (rows[NEW[2]]["unit"], rows[NEW[2]]["better"],
            rows[NEW[2]]["source"]) == ("count", "higher", "program_counter")
    # the cell reports setup_s, another end-to-end metric and a layer's
    assert all("workloads" not in e or CELL in e["workloads"]
               for e in m["end_to_end"])


def test_chunk_steps_on_hand_made_snapshots():
    reader = load_readers()["attention.kda_chunk_steps_per_step"]
    ctx = _ctx(before={"kda/chunk_steps": 500, "wire/x": 1},
               after={"kda/chunk_steps": 500 + 10 * 32768, "wire/x": 9})
    # 2 rows x 32 heads x 128 chunks of 64, four KDA layers
    assert reader(ctx) == 32768 == 4 * 2 * 32 * (8192 // 64)
    assert reader(_ctx(after={"kda/chunk_steps": 70})) == 7


def test_a_program_without_the_counter_or_the_kernel_reads_nothing(running):
    """The parent of this PR under the benchmark as this PR leaves it,
    or another family's cell: no ``kda/chunk_steps``, no
    ``bps.attn.kda`` in the trace; the readers return None and do not
    raise."""
    readers = load_readers()
    bare = _ctx(trace=_trace(fusion=1.0, bps_attn_full=0.3,
                             bps_attn_mla=0.2),
                before={"wire/push_bytes": 1}, after={"wire/push_bytes": 9})
    for name in NEW:
        assert readers[name](bare) is None, name
        assert readers[name](_ctx()) is None, name


def test_the_kernels_time_and_their_share_of_the_roofline(running):
    readers = load_readers()
    cfg = _config()
    # three traced steps: the kernels' family (two instruction names of
    # it) and, beside it, what the prefix must not match
    ctx = _ctx(trace=_trace(bps_attn_kda=0.6, bps_attn_kda_=0.3,
                            bps_attn_mla=0.4, fusion=1.5,
                            jvp_bps_attn_kda_=0.5))
    assert readers["attention.kda_device_ms"](ctx) == pytest.approx(300.0)
    assert readers["attention.mla_device_ms"](ctx) == \
        pytest.approx(400.0 / 3)
    flops, nbytes = reference.kda_step_cost(2, cfg)
    want = 100 * 4 * max(flops / PEAK_FLOPS, nbytes / PEAK_BYTES) / 0.3
    got = readers["attention.kda_roofline_pct"](ctx)
    assert got == pytest.approx(want) and 0 < got < 100
    # by hand: four KDA layers, 16,384 positions x 32 heads each; a
    # position and head needs 21 x 128 x 128 FLOPs (0.9 ms a layer at the
    # peak) and 3080 bytes (1.97 ms a layer): the bytes decide, 7.9 ms a
    # step of need
    by_hand = 4 * 16384 * 32 * 3080
    assert 4 * nbytes == by_hand and 0.0078 < by_hand / PEAK_BYTES < 0.0080
    assert 4 * flops / PEAK_FLOPS < by_hand / PEAK_BYTES
    assert got == pytest.approx(100 * by_hand / PEAK_BYTES / 0.3)


def test_the_joined_expert_readers_count_this_configuration_rightly():
    """The loads of four sparse layers of eight experts, summed by
    ``layers/moe.py``; the bias's counter by ``layers/lfm2.py``; no
    configuration is asked of either."""
    pairs = 16000.0
    before = {f"moe/expert_load/{l}/{e}": 5
              for l in range(4) for e in range(8)}
    after = {k: 5 + 10 * pairs / 32 for k in before}
    after.update({"moe/bias_moved_pairs": 31000, "moe/dropped_pairs": 0})
    before.update({"moe/bias_moved_pairs": 1000, "moe/dropped_pairs": 0})
    ctx = _ctx(trace=_trace(ragged_dot_bps=0.300, fusion=1.5),
               before=before, after=after)
    readers = load_readers()
    assert readers["experts.routed_pairs_per_step"](ctx) == \
        pytest.approx(pairs)
    assert readers["experts.load_max_over_mean"](ctx) == pytest.approx(1.0)
    assert readers["experts.dropped_pairs"](ctx) == 0
    assert readers["experts.device_ms"](ctx) == pytest.approx(100.0)
    assert readers["experts.bias_moved_pairs_per_step"](ctx) == 3000
    # an even router's pairs a step, as the FLOP count has them: a
    # quarter of a pair a token and sparse layer
    cfg = _config()
    assert reference.expected_pairs_per_token(cfg) == 0.25
    assert reference.expected_pairs_per_token(cfg) * 2 * 8192 * 4 == 16384


def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog's ``config`` is in the file with its
    published value, changed only where ``reduced`` says (the nested
    group copied whole); what is assumed is listed."""
    cfg = _config()
    published = {
        "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
        "linear_attn_config": {
            "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
            "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18,
                           19, 21, 22, 23, 25, 26],
            "num_heads": 32, "short_conv_kernel_size": 4},
        "mla_use_nope": True, "model_max_length": 1048576,
        "model_type": "kimi_linear", "moe_intermediate_size": 1024,
        "moe_layer_freq": 1, "moe_renormalize": True,
        "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
        "num_expert_group": 1, "num_experts": 256,
        "num_experts_per_token": 8, "num_hidden_layers": 27,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 0,
        "num_shared_experts": 1, "q_lora_rank": None,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
        "vocab_size": 163840}
    for k, v in published.items():
        if k in cfg["reduced"]:
            assert cfg["published"][k] == v and cfg[k] != v, k
        else:
            assert cfg[k] == v, k
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts_held",
                              "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_experts_held"],
            cfg["vocab_size"]) == (5, 8, 20480)
    assert cfg["published"]["num_experts"] == 256
    # the layers held: the leading dense layer and one whole period
    assert reference.layer_ops(cfg) == ["kda", "kda", "kda", "mla", "kda"]
    assert reference.layer_runs(cfg) == [
        ("kda", "dense", 1), ("kda", "sparse", 2), ("mla", "sparse", 1),
        ("kda", "sparse", 1)]
    for item in ("kda_gate_rank", "g_bias", "A_log", "dt_bias", "conv_init",
                 "l2_eps", "expert_bias", "gate_sum_eps", "float32",
                 "router_aux_loss", "seq_len", "batch_per_chip", "optimizer",
                 "init", "dropout", "remat"):
        assert item in cfg["assumed"], item
    assert "Muon" in cfg["assumed"]["optimizer"]
    assert "32 chips" in cfg["deployment"] and "0-7" in cfg["deployment"]
    assert "0-20479" in cfg["deployment"] and "1-5" in cfg["deployment"]
    assert cfg["kda_gate_rank"] == 128
    assert cfg["expert_bias"] == {"distribution": "uniform", "low": -0.1,
                                  "high": 0.1, "seed": 43}
    assert (cfg["seq_len"], cfg["batch_per_chip"]) == (8192, 2)
    assert (cfg["compute_dtype"], cfg["param_dtype"], cfg["wire_dtype"],
            cfg["router_dtype"], cfg["state_dtype"], cfg["remat"]) == (
        "bfloat16", "float32", "float32", "float32", "float32", True)
    # the sparse decoders' optimizer
    with open(os.path.join(BENCH, "configs", "joyai-llm-flash.json")) as f:
        assert cfg["optimizer"] == json.load(f)["optimizer"]
    # no width is among the cuts
    assert not {"hidden_size", "intermediate_size", "moe_intermediate_size",
                "head_dim", "num_experts_per_token", "kv_lora_rank",
                "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                "linear_attn_config"} & set(cfg["reduced"])


def test_the_catalog_row_is_the_files_source():
    """Where the guide's catalog is installed: its row's every number."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    (row,) = [r for r in rows if r["name"] == "Kimi-Linear-48B-A3B-Instruct"]
    cfg = _config()
    assert cfg["source"] == row["source_url"]
    for k, v in row["config"].items():
        if k in cfg["reduced"]:
            assert cfg["published"][k] == v, k
        else:
            assert cfg[k] == v, k


def test_running_config_is_found_and_the_reference_imports_no_program():
    cfg = _cell.running_config(["--workload", CELL, "--manifest", MANIFEST])
    assert cfg["hidden_size"] == 2304 and cfg["family"] == "kimi"
    tiny = _cell.running_config(["--workload", CELL, "--manifest", MANIFEST,
                                 "--rehearse"])
    assert (tiny["hidden_size"], tiny["linear_attn_config"]["head_dim"],
            tiny["linear_attn_config"]["short_conv_kernel_size"],
            tiny["num_hidden_layers"]) == (64, 16, 4, 5)
    with open(os.path.join(BENCH, "reference", "kimi.py")) as f:
        source = f.read()
    assert "byteps_tpu" not in source
    # no chunk algebra: the recurrence a position at a time
    assert "lax.scan(step" in source and "tril" not in source
    # the readers import nothing of the program either: they are laid
    # over a parent that lacks the family
    with open(os.path.join(BENCH, "layers", "kimi.py")) as f:
        assert "byteps_tpu" not in f.read()
