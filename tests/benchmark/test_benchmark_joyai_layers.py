"""What the ``joyai`` family brings to the benchmark: the readers of
``benchmark/layers/joyai.py`` on hand-made snapshots and a synthetic
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
from benchmark.reference import joyai as reference
from benchmark.trace_reduce import Reduced

CELL = "joyai-llm-flash.ps.1chip"
CONFIG = "joyai-llm-flash"
NEW = ("attention.mla_device_ms", "attention.mla_roofline_pct",
       "mtp.predicted_tokens_per_step")
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
          "experts.device_ms", "experts.bias_moved_pairs_per_step")
# their readers count experts in ``num_hidden_layers`` layers (one is
# dense here and the module adds one), or read what no program has
NOT_JOINED = ("experts.grouped_mm_roofline_pct",
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
    cells = {w["name"]: w for w in m["workloads"]}
    assert (cells[CELL]["config"], cells[CELL]["traffic"],
            cells[CELL]["chips"]) == (CONFIG, "ps.1chip", 1)
    assert len(cells[CELL]["why"]) <= 200
    # one cell on four chips, as before
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    rows = {x["name"]: x for x in m["per_layer"]}
    readers = load_readers()
    for name in NEW + JOINED:
        assert CELL in rows[name]["workloads"], name
        assert rows[name]["moves"] == "step_ms" and name in readers, name
    for name in NOT_JOINED:
        assert CELL not in rows[name]["workloads"], name
    for name in NEW[:2]:
        assert (rows[name]["layer"], rows[name]["source"]) == \
            ("attention", "device_trace")
    assert rows[NEW[1]]["unit"] == "%" and rows[NEW[1]]["better"] == "higher"
    assert rows[NEW[2]]["source"] == "program_counter"
    # the cell reports setup_s, another end-to-end metric and a layer's
    assert all("workloads" not in e or CELL in e["workloads"]
               for e in m["end_to_end"])


def test_predicted_tokens_on_hand_made_snapshots():
    reader = load_readers()["mtp.predicted_tokens_per_step"]
    ctx = _ctx(before={"mtp/predicted_tokens": 500, "wire/x": 1},
               after={"mtp/predicted_tokens": 500 + 10 * 16382, "wire/x": 9})
    assert reader(ctx) == 16382 == 2 * (8192 - 1)
    # first step of a process: no earlier snapshot of the counter
    assert reader(_ctx(after={"mtp/predicted_tokens": 70})) == 7


def test_a_program_without_the_counter_or_the_kernel_reads_nothing(running):
    """The parent of this PR under the benchmark as this PR leaves it,
    or another family's cell: no ``mtp/predicted_tokens``, no
    ``bps.attn.mla`` in the trace; the readers return None and do not
    raise."""
    readers = load_readers()
    bare = _ctx(trace=_trace(fusion=1.0, bps_attn_full=0.3,
                             bps_attn_blockdiff=0.2),
                before={"wire/push_bytes": 1}, after={"wire/push_bytes": 9})
    for name in NEW:
        assert readers[name](bare) is None, name
        assert readers[name](_ctx()) is None, name


def test_the_kernels_time_and_their_share_of_the_roofline(running):
    readers = load_readers()
    cfg = _config()
    # three traced steps: the kernels' family and, beside it, what the
    # prefix must not match
    ctx = _ctx(trace=_trace(bps_attn_mla=1.8, bps_attn_full=0.4, fusion=1.5,
                            jvp_bps_attn_mla_=0.5))
    assert readers["attention.mla_device_ms"](ctx) == pytest.approx(600.0)
    flops, nbytes = reference.attention_step_cost(2, cfg)
    want = 100 * 6 * max(flops / PEAK_FLOPS, nbytes / PEAK_BYTES) / 0.6
    got = readers["attention.mla_roofline_pct"](ctx)
    assert got == pytest.approx(want) and 0 < got < 100
    # by hand: six blocks (five layers and the module's), 7 products over
    # the causal pairs of 2 rows and 32 heads, four 192 wide and three
    # 128: 29.7 TFLOP, 0.151 s at the peak; the bytes are far below
    by_hand = 6 * 2 * 32 * (4 * 192 + 3 * 128) * (8192 * 8193 // 2) * 2
    assert 6 * flops == by_hand and 0.150 < by_hand / PEAK_FLOPS < 0.152
    assert 6 * nbytes / PEAK_BYTES < 0.01
    assert got == pytest.approx(100 * by_hand / PEAK_FLOPS / 0.6)


def test_the_joined_expert_readers_count_this_configuration_rightly():
    """The loads of five sparse blocks of eight (four layers and the
    module's as one more), summed by ``layers/moe.py``; the bias's
    counter by ``layers/lfm2.py``; no configuration is asked of either."""
    pairs = 30000.0
    before = {f"moe/expert_load/{l}/{e}": 5
              for l in range(5) for e in range(8)}
    after = {k: 5 + 10 * pairs / 40 for k in before}
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
    # quarter of a pair a token and sparse block
    cfg = _config()
    assert reference.expected_pairs_per_token(cfg) == 0.25
    assert reference.expected_pairs_per_token(cfg) * 2 * 8192 * 5 == 20480


def test_parameters_and_gradient_bytes_by_hand():
    cfg = _config()
    attn = 26_347_520                   # with the two latent norms
    assert attn == 2048 * 1536 + 1536 + 1536 * 6144 + 2048 * 576 + 512 \
        + 512 * 8192 + 4096 * 2048
    dense = attn + 3 * 2048 * 7168 + 2 * 2048
    sparse = attn + 2048 * 256 + 4 * 3 * 2048 * 768 + 8 * 3 * 2048 * 768 \
        - 3 * 3 * 2048 * 768 + 2 * 2048
    assert (dense, sparse) == (70_391_808, 69_343_232)
    module = 4096 * 2048 + 3 * 2048 + sparse
    total = dense + 4 * sparse + 2 * 16160 * 2048 + 2048 + module
    assert module == 77_737_984
    assert total == reference.param_count(cfg) == 491_696_128
    # float32 on the wire: 1.967 GB a step each way
    assert 4 * total == 1_966_784_512


def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog's ``config`` is in the file with its
    published value, changed only where ``reduced`` says; what is
    assumed is listed."""
    cfg = _config()
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 7168, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
        "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
        "n_routed_experts": 256, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_hidden_layers": 40,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
        "q_lora_rank": 1536, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_interleave": True, "rope_scaling": None,
        "rope_theta": 32000000, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
        "vocab_size": 129280}
    for k, v in published.items():
        if k in cfg["reduced"]:
            assert cfg["published"][k] == v and cfg[k] != v, k
        else:
            assert cfg[k] == v, k
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts_held",
                              "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_experts_held"],
            cfg["vocab_size"]) == (5, 8, 16160)
    assert cfg["published"]["n_routed_experts"] == 256
    for item in ("mtp_loss_weight", "nextn_is_sparse", "mtp_projection",
                 "mtp_positions", "expert_bias", "gate_sum_eps",
                 "router_aux_loss", "seq_len", "batch_per_chip", "optimizer",
                 "init", "dropout", "remat"):
        assert item in cfg["assumed"], item
    assert "32 chips" in cfg["deployment"] and "0-7" in cfg["deployment"]
    assert "0-16159" in cfg["deployment"] and "0-4" in cfg["deployment"]
    assert cfg["mtp_loss_weight"] == 0.3
    assert cfg["expert_bias"] == {"distribution": "uniform", "low": -0.1,
                                  "high": 0.1, "seed": 41}
    assert (cfg["seq_len"], cfg["batch_per_chip"]) == (8192, 2)
    assert (cfg["compute_dtype"], cfg["param_dtype"], cfg["wire_dtype"],
            cfg["router_dtype"], cfg["remat"]) == (
        "bfloat16", "float32", "float32", "float32", True)
    # the sparse decoders' optimizer
    with open(os.path.join(BENCH, "configs", "lfm2-8b-a1b.json")) as f:
        assert cfg["optimizer"] == json.load(f)["optimizer"]
    # no width is among the cuts
    assert not {"hidden_size", "intermediate_size", "moe_intermediate_size",
                "head_dim", "num_experts_per_tok", "q_lora_rank",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim"} & set(cfg["reduced"])


def test_running_config_is_found_and_the_reference_imports_no_program():
    cfg = _cell.running_config(["--workload", CELL, "--manifest", MANIFEST])
    assert cfg["hidden_size"] == 2048 and cfg["family"] == "joyai"
    tiny = _cell.running_config(["--workload", CELL, "--manifest", MANIFEST,
                                 "--rehearse"])
    assert (tiny["hidden_size"], tiny["qk_rope_head_dim"],
            tiny["num_nextn_predict_layers"]) == (64, 8, 1)
    with open(os.path.join(BENCH, "reference", "joyai.py")) as f:
        source = f.read()
    assert "byteps_tpu" not in source
    # the readers import nothing of the program either: they are laid
    # over a parent that lacks the family
    with open(os.path.join(BENCH, "layers", "joyai.py")) as f:
        assert "byteps_tpu" not in f.read()
