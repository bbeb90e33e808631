"""What the ``sdar`` family brings to the benchmark: the readers of
``benchmark/layers/sdar.py`` on hand-made snapshots and a synthetic
reduced trace, the existing readers the new cell joined counting this
configuration rightly, the manifest's entries by membership, and the
configuration's file against the catalog's published numbers."""

import json
import os

import pytest

from bench_helpers import BENCH, MANIFEST, manifest

from benchmark.layer_api import LayerContext, load_readers
from benchmark.layers import _cell
from benchmark.reference import mellum as mellum_reference
from benchmark.reference import sdar as reference
from benchmark.trace_reduce import Reduced

CELL = "sdar-30b-a3b.ps.1chip"
CONTROL = "vgg16.fused.1chip"
NEW = ("attention.blockdiff_device_ms", "attention.blockdiff_roofline_pct",
       "diffusion.masked_tokens_per_step")
JOINED = ("worker.compute_ms", "worker.ttfp_ms", "worker.centre_step_ms",
          "export.mb_per_step", "export.gbps", "staging.slot_allocs",
          "wire.requests_per_step", "wire.pull_p95_ms", "server.fold_ms",
          "server.queue_ms", "apply.drain_ms", "control.fused_step_ms",
          "kernels.busy_mfu_pct", "device.idle_pct", "export.dispatch_ms",
          "export.router_busy_ms", "export.materialize_ms",
          "export.submit_ms", "experts.routed_pairs_per_step",
          "experts.load_max_over_mean", "experts.dropped_pairs",
          "experts.device_ms", "experts.grouped_mm_roofline_pct")
NOT_JOINED = ("export.tap_span_ms", "export.router_wait_max_ms",
              "attention.device_ms", "attention.window_roofline_pct",
              "attention.full_roofline_pct",
              "experts.sparse_mm_roofline_pct",
              "experts.bias_moved_pairs_per_step")
PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9
D, FE, L, B = 2048, 768, 8192, 4


def _config():
    with open(os.path.join(BENCH, "configs", "sdar-30b-a3b.json")) as f:
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


def test_the_manifest_has_the_configuration_the_cells_and_the_metrics():
    """Membership, not position: later PRs append."""
    m = manifest()
    configs = {c["name"]: c for c in m["configs"]}
    assert configs["sdar-30b-a3b"]["source"] == \
        "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json"
    assert configs["sdar-30b-a3b"]["reduced"] == [
        "num_hidden_layers", "num_experts_held", "vocab_size"]
    assert configs["sdar-30b-a3b"]["file"] == \
        "benchmark/configs/sdar-30b-a3b.json"
    cells = {w["name"]: w for w in m["workloads"]}
    assert {k: cells[CELL][k] for k in ("config", "traffic", "chips")} == {
        "config": "sdar-30b-a3b", "traffic": "ps.1chip", "chips": 1}
    assert {k: cells[CONTROL][k] for k in ("config", "traffic", "chips")} \
        == {"config": "vgg16", "traffic": "fused.1chip", "chips": 1}
    assert all(len(cells[c]["why"]) <= 200 for c in (CELL, CONTROL))
    rows = {x["name"]: x for x in m["per_layer"]}
    readers = load_readers()
    for name in NEW:
        assert CELL in rows[name]["workloads"] and name in readers
        assert rows[name]["moves"] == "step_ms"
    assert rows["attention.blockdiff_roofline_pct"]["unit"] == "%"
    assert rows["attention.blockdiff_device_ms"]["layer"] == "attention"
    assert (rows["diffusion.masked_tokens_per_step"]["source"],
            rows["attention.blockdiff_device_ms"]["source"]) == \
        ("program_counter", "device_trace")
    for name in JOINED:
        assert CELL in rows[name]["workloads"], name
    for name in NOT_JOINED:
        assert CELL not in rows[name]["workloads"], name
    reported = [x["name"] for x in m["per_layer"]
                if CELL in x.get("workloads", [CELL])]
    assert sorted(reported) == sorted(JOINED + NEW)
    # the fused control reports what the other fused cell does
    fused = [x["name"] for x in m["per_layer"]
             if "bert-large.fused.1chip" in x["workloads"]]
    assert fused and all(CONTROL in rows[name]["workloads"] for name in fused)
    assert sorted(x["name"] for x in m["per_layer"]
                  if CONTROL in x["workloads"]) == sorted(fused)


def test_masked_tokens_on_hand_made_snapshots():
    reader = load_readers()["diffusion.masked_tokens_per_step"]
    ctx = _ctx(before={"diffusion/masked_tokens": 500, "wire/x": 1},
               after={"diffusion/masked_tokens": 500 + 10 * 11469,
                      "wire/x": 9})
    assert reader(ctx) == 11469
    # first step of a process: no earlier snapshot of the counter
    assert reader(_ctx(after={"diffusion/masked_tokens": 70})) == 7


def test_a_program_without_the_counter_or_the_kernel_reads_nothing(running):
    """The parent of this PR under the benchmark as this PR leaves it,
    or another family's cell: no ``diffusion/masked_tokens``, no
    ``bps.attn.blockdiff`` in the trace; the readers return None and do
    not raise."""
    readers = load_readers()
    bare = _ctx(trace=_trace(fusion=1.0, bps_attn_full=0.3,
                             bps_attn_window=0.2),
                before={"wire/push_bytes": 1}, after={"wire/push_bytes": 9})
    for name in NEW:
        assert readers[name](bare) is None, name
        assert readers[name](_ctx()) is None, name


def test_the_kernels_time_and_their_share_of_the_roofline(running):
    readers = load_readers()
    cfg = _config()
    # three traced steps: the kernels' family and, beside it, what the
    # prefix must not match
    ctx = _ctx(trace=_trace(bps_attn_blockdiff=1.8, bps_attn_full=0.4,
                            fusion=1.5, jvp_bps_attn_blockdiff_=0.5))
    assert readers["attention.blockdiff_device_ms"](ctx) == \
        pytest.approx(600.0)
    flops, nbytes = reference.attention_step_cost(2, cfg)
    want = 100 * 4 * max(flops / PEAK_FLOPS, nbytes / PEAK_BYTES) / 0.6
    got = readers["attention.blockdiff_roofline_pct"](ctx)
    assert got == pytest.approx(want) and 0 < got < 100
    # by hand: four layers of 7 products over L^2 + L B pairs a row and
    # head, 2 rows, 32 heads of 128: 30.8 TFLOP, 0.156 s at the peak
    by_hand = 4 * 7 * 2 * 32 * 128 * (L * L + L * B) * 2
    assert 4 * flops == by_hand and 0.155 < by_hand / PEAK_FLOPS < 0.158
    assert got == pytest.approx(100 * by_hand / PEAK_FLOPS / 0.6)
    # the other families' attention readers find nothing under their
    # scopes' names... but `bps.attn.full` is here: they are not asked
    # (the cell is on none of their lists)


def test_the_joined_expert_readers_count_this_configuration_rightly(running):
    """``layers/moe.py``'s roofline reader sizes the work from
    ``hidden_size``, ``moe_intermediate_size``, ``num_experts_held`` and
    ``num_hidden_layers``: every layer held is sparse here."""
    cfg = _config()
    assert cfg["mlp_only_layers"] == [] and cfg["decoder_sparse_step"] == 1
    pairs = 96000.0
    loads = [[pairs / 64] * 16] * 4
    before = {f"moe/expert_load/{l}/{e}": 5
              for l in range(4) for e in range(16)}
    after = {f"moe/expert_load/{l}/{e}": 5 + 10 * loads[l][e]
             for l in range(4) for e in range(16)}
    ctx = _ctx(trace=_trace(ragged_dot_bps=0.300, fusion=1.5),
               before=before, after=after)
    readers = load_readers()
    assert readers["experts.routed_pairs_per_step"](ctx) == \
        pytest.approx(pairs)
    assert readers["experts.load_max_over_mean"](ctx) == pytest.approx(1.0)
    assert readers["experts.device_ms"](ctx) == pytest.approx(100.0)
    flops, nbytes = mellum_reference.expert_products_cost(pairs, cfg)
    assert flops == 3 * 2 * pairs * 3 * D * FE
    assert nbytes == 3 * (2 * pairs * (2 * (D + FE) + FE + D)
                          + 2 * 4 * 16 * 3 * D * FE)
    want = 100 * max(flops / PEAK_FLOPS, nbytes / PEAK_BYTES) / 0.1
    got = readers["experts.grouped_mm_roofline_pct"](ctx)
    assert got == pytest.approx(want) and 0 < got < 100
    # an even router's pairs a step, as the FLOP count has them
    assert reference.expected_pairs_per_token(cfg) * 2 * 2 * L * 4 == 131072


def test_parameters_and_gradient_bytes_by_hand():
    import jax

    cfg = _config()
    attn = 2 * D * 32 * 128 + 2 * D * 4 * 128
    layer = attn + 2 * 128 + 2 * D + D * 128 + 16 * 3 * D * FE
    want = 4 * layer + 2 * 18992 * D + D
    assert (attn, layer) == (18_874_368, 94_638_336)
    assert want == 456_346_624 and round(4 * want / 1e9, 3) == 1.825
    # 24 bytes a parameter on the PS path
    assert round(24 * want / 2**30, 2) == 10.2
    shapes = jax.eval_shape(lambda k: reference.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    leaves = jax.tree.leaves(shapes)
    assert sum(x.size for x in leaves) == want and len(leaves) == 15
    # the smallest expert slabs yet: 2048 x 768 in bf16
    assert 2 * D * FE == 3_145_728
    assert shapes["blocks"]["w_gate"].shape == (4, 16, D, FE)
    assert shapes["embed"].shape == (18992, D)
    assert shapes["lm_head"].shape == (D, 18992)
    assert 18992 % 128 and 18992 * 8 == 151936


def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog's ``config`` is in the file with its
    published value, changed only where ``reduced`` says; what is
    assumed is listed."""
    cfg = _config()
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "max_position_embeddings": 32768,
        "max_window_layers": 48, "mlp_only_layers": [],
        "model_type": "sdar_moe", "moe_intermediate_size": 768,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 48, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    for k, v in published.items():
        if k in cfg["reduced"]:
            assert cfg["published"][k] == v and cfg[k] != v, k
        else:
            assert cfg[k] == v, k
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts_held",
                              "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_experts_held"],
            cfg["vocab_size"]) == (4, 16, 18992)
    assert cfg["published"]["num_experts"] == 128
    for item in ("block_length", "noise", "mask_token_id", "logit_shift",
                 "qk_norm", "router_aux_loss", "seq_len", "batch_per_chip",
                 "optimizer", "init", "dropout", "remat"):
        assert item in cfg["assumed"], item
    assert "8 chips" in cfg["deployment"] and "0-15" in cfg["deployment"]
    assert cfg["noise"] == {"rate_low": 0.45, "rate_high": 0.95}
    assert (cfg["block_length"], cfg["mask_token_id"]) == (4, 18991)
    assert (cfg["seq_len"], cfg["batch_per_chip"]) == (8192, 2)
    assert (cfg["compute_dtype"], cfg["param_dtype"], cfg["wire_dtype"],
            cfg["router_dtype"], cfg["remat"]) == (
        "bfloat16", "float32", "float32", "float32", True)
    # the two decoders' optimizer
    with open(os.path.join(BENCH, "configs", "mellum2-12b.json")) as f:
        assert cfg["optimizer"] == json.load(f)["optimizer"]
    # no width is among the cuts, no seed is in the file
    assert not {"hidden_size", "intermediate_size", "moe_intermediate_size",
                "head_dim", "num_experts_per_tok"} & set(cfg["reduced"])
    assert "seed" not in json.dumps({k: v for k, v in cfg.items()
                                     if k != "assumed"})


def test_running_config_is_found_and_the_reference_imports_no_program():
    cfg = _cell.running_config(["--workload", CELL, "--manifest", MANIFEST])
    assert cfg["hidden_size"] == 2048 and cfg["family"] == "sdar"
    tiny = _cell.running_config(["--workload", CELL, "--manifest", MANIFEST,
                                 "--rehearse"])
    assert (tiny["hidden_size"], tiny["mask_token_id"],
            tiny["block_length"]) == (64, 255, 4)
    with open(os.path.join(BENCH, "reference", "sdar.py")) as f:
        source = f.read()
    assert "byteps_tpu" not in source
    for name in ("layers", "families"):
        with open(os.path.join(BENCH, name, "sdar.py")) as f:
            assert "benchmark.reference.mellum" not in f.read()
