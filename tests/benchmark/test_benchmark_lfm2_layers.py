"""What the ``lfm2`` family brings to the benchmark: the readers of
``benchmark/layers/lfm2.py`` on hand-made snapshots and a synthetic
reduced trace, the counts of ``benchmark/reference/lfm2.py`` against
hand arithmetic at the published widths (507.8 M parameters, 2.03 GB of
float32 gradients a step), the existing readers the new cell joined
counting this configuration rightly, and the configuration's file
against the catalog's published numbers."""

import json
import os

import pytest

from bench_helpers import BENCH, MANIFEST, manifest

from benchmark.layer_api import LayerContext, load_readers
from benchmark.layers import _cell
from benchmark.reference import lfm2 as reference
from benchmark.reference import mellum as mellum_reference
from benchmark.trace_reduce import Reduced

CELL = "lfm2-8b-a1b.ps.1chip"
NEW = ("experts.sparse_mm_roofline_pct", "experts.bias_moved_pairs_per_step")
JOINED = ("worker.compute_ms", "worker.ttfp_ms", "worker.centre_step_ms",
          "export.mb_per_step", "export.gbps", "staging.slot_allocs",
          "wire.requests_per_step", "wire.pull_p95_ms", "server.fold_ms",
          "server.queue_ms", "apply.drain_ms", "control.fused_step_ms",
          "kernels.busy_mfu_pct", "device.idle_pct", "export.dispatch_ms",
          "export.router_busy_ms", "export.materialize_ms",
          "export.submit_ms", "experts.routed_pairs_per_step",
          "experts.load_max_over_mean", "experts.dropped_pairs",
          "experts.device_ms", "attention.device_ms",
          "attention.full_roofline_pct")
PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9
D, F, FE, V, HD = 2048, 7168, 1792, 16384, 64


def _config():
    with open(os.path.join(BENCH, "configs", "lfm2-8b-a1b.json")) as f:
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


def _loads(per_step, steps=10, start=3):
    before = {f"moe/expert_load/{l}/{e}": start
              for l, row in enumerate(per_step) for e in range(len(row))}
    after = {f"moe/expert_load/{l}/{e}": start + steps * pairs
             for l, row in enumerate(per_step) for e, pairs in enumerate(row)}
    return before, after


def test_the_manifest_has_the_configuration_the_cell_and_its_metrics():
    m = manifest()
    assert m["configs"][-1]["name"] == "lfm2-8b-a1b"
    assert m["configs"][-1]["source"] == \
        "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json"
    assert m["workloads"][-1] == {
        "name": CELL, "config": "lfm2-8b-a1b", "traffic": "ps.1chip",
        "chips": 1, "why": m["workloads"][-1]["why"]}
    rows = {x["name"]: x for x in m["per_layer"]}
    readers = load_readers()
    # the family's own two close the list, for this cell alone
    assert [x["name"] for x in m["per_layer"][-2:]] == list(NEW)
    for name in NEW:
        assert rows[name]["workloads"] == [CELL] and name in readers
        assert (rows[name]["layer"], rows[name]["moves"]) == \
            ("experts", "step_ms")
    # every existing metric whose reader is right for it, at the end of
    # its list; the two whose reader would count this file wrongly or
    # finds no such kernel, not
    for name in JOINED:
        assert rows[name]["workloads"][-1] == CELL, name
    for name in ("experts.grouped_mm_roofline_pct",
                 "attention.window_roofline_pct", "export.tap_span_ms",
                 "export.router_wait_max_ms"):
        assert CELL not in rows[name]["workloads"], name
    reported = [x["name"] for x in m["per_layer"]
                if CELL in x.get("workloads", [CELL])]
    assert sorted(reported) == sorted(JOINED + NEW)


def test_bias_moved_pairs_on_hand_made_snapshots():
    reader = load_readers()["experts.bias_moved_pairs_per_step"]
    ctx = _ctx(before={"moe/bias_moved_pairs": 1000, "wire/x": 1},
               after={"moe/bias_moved_pairs": 1000 + 10 * 52000,
                      "wire/x": 9})
    assert reader(ctx) == 52000
    # first step of a process: no earlier snapshot of the counter
    assert reader(_ctx(after={"moe/bias_moved_pairs": 70})) == 7


def test_a_program_without_the_counter_or_the_kernel_reads_nothing(running):
    """The parent of this PR under the benchmark as this PR leaves it,
    or another family's cell: no ``moe/bias_moved_pairs``, no grouped
    product in the trace; the readers return None and do not raise."""
    readers = load_readers()
    bare = _ctx(trace=_trace(fusion=1.0), before={"wire/push_bytes": 1},
                after={"wire/push_bytes": 9})
    for name in NEW:
        assert readers[name](bare) is None, name
        assert readers[name](_ctx()) is None, name
    # Mellum's program: loads and the kernel, no bias counter
    before, after = _loads([[500.0, 700.0]])
    mellum = _ctx(trace=_trace(ragged_dot_none=0.09), before=before,
                  after=after)
    assert readers["experts.bias_moved_pairs_per_step"](mellum) is None


def test_the_sparse_products_share_counts_weights_in_the_sparse_layers(
        running):
    readers = load_readers()
    cfg = _config()
    pairs = 72000.0
    before, after = _loads([[pairs / 8] * 8])
    ctx = _ctx(trace=_trace(ragged_dot_none=0.230, ragged_dot_metadata=0.001,
                            fusion=1.5), before=before, after=after)
    seconds = 0.231 / 3
    flops, nbytes = reference.expert_products_cost(pairs, cfg)
    want = 100 * max(flops / PEAK_FLOPS, nbytes / PEAK_BYTES) / seconds
    got = readers["experts.sparse_mm_roofline_pct"](ctx)
    assert got == pytest.approx(want) and 0 < got < 100
    # by hand: three products forward, each twice more backward
    assert flops == 3 * 2 * pairs * 3 * D * FE
    assert nbytes == 3 * (2 * pairs * (2 * (D + FE) + FE + D)
                          + 2 * 4 * 8 * 3 * D * FE)
    # the reader the cell did NOT join counts expert weights in five
    # layers where four have them
    _, mellum_bytes = mellum_reference.expert_products_cost(pairs, cfg)
    assert mellum_bytes - nbytes == 3 * 2 * 1 * 8 * 3 * D * FE
    assert reference.sparse_layers(cfg) == 4


def test_the_joined_readers_count_this_configuration_rightly(running):
    """``layers/attention.py`` counts attention layers in
    ``layer_types[:num_hidden_layers]``: one for this file, as among the
    layers held (published layers 1 to 5); ``attention_step_cost`` reads
    ``head_dim`` 64 and no window."""
    cfg = _config()
    kinds = reference.layer_kinds(cfg)
    held = [op for op, _ in kinds]
    assert held == ["conv", "full_attention", "conv", "conv", "conv"]
    assert [ffn for _, ffn in kinds] == ["dense"] + ["sparse"] * 4
    assert cfg["layer_types"][:cfg["num_hidden_layers"]].count(
        "full_attention") == held.count("full_attention") == 1
    assert cfg["sliding_window"] is None and cfg["head_dim"] == HD
    S = cfg["seq_len"]
    flops, nbytes = mellum_reference.attention_step_cost(
        2, cfg, "full_attention")
    assert flops == 7 * 2 * 32 * HD * (S * (S + 1) // 2) * 2
    assert nbytes == 2 * S * (2 * HD * (6 * 32 + 6 * 8) + 2 * 4 * 32)
    readers = load_readers()
    ctx = _ctx(trace=_trace(bps_attn_full=0.150, jvp_bps_attn_full_=0.060))
    # the forward's family alone is what the prefix matches
    want = 100 * max(flops / PEAK_FLOPS, nbytes / PEAK_BYTES) / 0.050
    assert readers["attention.full_roofline_pct"](ctx) == pytest.approx(want)
    assert readers["attention.window_roofline_pct"](ctx) is None


def test_parameters_and_gradient_bytes_by_hand():
    import jax

    cfg = _config()
    conv = D + D * 3 * D + 3 * D + D * D              # norm, in, filter, out
    attn = D + 2 * D * 32 * HD + 2 * D * 8 * HD + 2 * HD
    dense = D + 3 * D * F
    sparse = D + D * 32 + 8 * 3 * D * FE
    want = V * D + D + (conv + dense) + (attn + sparse) + 3 * (conv + sparse)
    assert reference.param_count(cfg) == want == 507_820_160
    assert round(4 * want / 1e9, 2) == 2.03
    shapes = jax.eval_shape(lambda k: reference.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    leaves = jax.tree.leaves(shapes)
    assert sum(x.size for x in leaves) == want and len(leaves) == 31
    sizes = sorted(4 * x.size for x in leaves)
    # from a head norm's 256 bytes to three stacked layers' experts
    assert sizes[0] == 256 and sizes[-1] == 4 * 3 * 8 * D * FE == 352_321_536
    assert shapes["runs"][2]["ffn"]["w_gate"].shape == (3, 8, D, FE)
    # the embedding is the head: one leaf over the rows held
    assert shapes["embed"].shape == (V, D) and "lm_head" not in shapes
    # both leading dense layers, published: 24 bytes a parameter
    both = want + conv + dense
    assert round(both / 1e6, 1) == 568.6 and 24 * both > 13.6e9


def test_model_flops_by_hand():
    cfg = _config()
    S, rows = cfg["seq_len"], 2
    assert reference.expected_pairs_per_token(cfg) == 1.0
    assert reference.causal_pairs(4) == 10
    per_token = (4 * (3 * D * D + D * D)              # four conv operators
                 + 2 * D * 32 * HD + 2 * D * 8 * HD   # attention's four
                 + 3 * D * F                          # the dense FFN
                 + 4 * (D * 32 + 1.0 * 3 * D * FE)    # routers, held experts
                 + D * V)                             # the tied head
    macs = per_token * rows * S + 2 * 32 * HD * (S * (S + 1) // 2) * rows
    assert reference.model_flops_per_step(rows, cfg) == 6.0 * macs
    # ISSUE 30's count: about 432 MFLOP a token forward
    forward = reference.model_flops_per_step(rows, cfg) / 3 / (rows * S)
    assert 425e6 < forward < 440e6


def test_the_configuration_keeps_every_published_number():
    """Every number of the catalog's ``config`` is in the file under the
    same key, changed only where ``reduced`` says; the layer list is
    whole; what is assumed is listed."""
    cfg = _config()
    published = {
        "conv_L_cache": 3, "hidden_size": 2048, "intermediate_size": 7168,
        "max_position_embeddings": 128000, "moe_intermediate_size": 1792,
        "norm_eps": 1e-05, "num_attention_heads": 32, "num_experts": 32,
        "num_experts_per_tok": 4, "num_key_value_heads": 8,
        "rope_theta": 1000000, "routed_scaling_factor": 1}
    for k, v in published.items():
        assert cfg[k] == v, k
    assert (cfg["conv_bias"], cfg["norm_topk_prob"], cfg["use_expert_bias"],
            cfg["model_type"]) == (False, True, True, "lfm2_moe")
    assert cfg["reduced"] == ["num_hidden_layers", "num_dense_layers",
                              "num_experts_held", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"],
            cfg["num_experts_held"], cfg["vocab_size"]) == (5, 1, 8, 16384)
    assert cfg["published"]["num_hidden_layers"] == 24
    assert cfg["published"]["num_dense_layers"] == 2
    assert cfg["published"]["vocab_size"] == 65536
    assert len(cfg["layer_types"]) == 24
    assert (cfg["layer_types"].count("conv"),
            cfg["layer_types"].count("full_attention")) == (18, 6)
    assert cfg["first_layer_held"] == 1 and cfg["tie_word_embeddings"]
    for item in ("tie_word_embeddings", "head_dim", "qk_norm", "expert_bias",
                 "seq_len", "optimizer", "init", "dropout", "remat"):
        assert item in cfg["assumed"], item
    assert "4 chips" in cfg["deployment"] and "do not fit" in cfg["deployment"]
    assert cfg["expert_bias"] == {"distribution": "uniform", "low": -0.1,
                                  "high": 0.1, "seed": 30}
    assert (cfg["seq_len"], cfg["batch_per_chip"]) == (8192, 2)
    # no width is among the cuts
    assert not {"hidden_size", "intermediate_size", "moe_intermediate_size",
                "head_dim", "num_experts_per_tok"} & set(cfg["reduced"])


def test_running_config_is_found_and_the_reference_imports_no_program():
    cfg = _cell.running_config(["--workload", CELL, "--manifest", MANIFEST])
    assert cfg["hidden_size"] == 2048 and cfg["family"] == "lfm2"
    tiny = _cell.running_config(["--workload", CELL, "--manifest", MANIFEST,
                                 "--rehearse"])
    assert tiny["hidden_size"] == 64 and len(tiny["layer_types"]) == 24
    with open(os.path.join(BENCH, "reference", "lfm2.py")) as f:
        source = f.read()
    assert "byteps_tpu" not in source and "lax.conv" not in source
