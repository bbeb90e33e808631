"""What the ``afmoe`` family brings to the benchmark: the readers of
``benchmark/layers/afmoe.py`` on hand-made snapshots and a synthetic
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
from benchmark.reference import afmoe as reference
from benchmark.reference import mellum
from benchmark.trace_reduce import Reduced

CELL = "trinity-mini.ps.1chip"
CONFIG = "trinity-mini"
NEW = ("attention.window_device_ms", "attention.full_device_ms",
       "attention.band_pairs_per_step", "experts.held_mm_roofline_pct")
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
          "attention.device_ms", "attention.window_roofline_pct",
          "attention.full_roofline_pct")
# their readers count experts in ``num_hidden_layers`` layers (one is
# dense here) or by another family's keys, or read what no program has
NOT_JOINED = ("experts.grouped_mm_roofline_pct",
              "experts.sparse_mm_roofline_pct", "attention.mla_device_ms",
              "attention.mla_roofline_pct", "attention.kda_device_ms",
              "attention.kda_roofline_pct",
              "attention.kda_chunk_steps_per_step",
              "attention.blockdiff_device_ms",
              "mtp.predicted_tokens_per_step", "export.tap_span_ms",
              "export.router_wait_max_ms")
PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9


def _config():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


def _ctx(trace=None, before=None, after=None, steps=10):
    return LayerContext(
        steps=steps, window_s=10.0, step_ms=1000.0, walls_ms=[1000.0] * steps,
        global_batch=4, chips=1, reports=[], counters_before=before or {},
        counters_after=after or {}, flops_per_step=7e13,
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
    assert len(configs[CONFIG]["source"]) == 66
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
        assert rows[name]["workloads"][0] == CELL
        assert set(rows[name]) == {"name", "unit", "better", "source",
                                   "layer", "moves", "workloads"}
    assert [rows[n]["layer"] for n in NEW] == ["attention"] * 3 + ["experts"]
    assert [(rows[n]["unit"], rows[n]["better"], rows[n]["source"])
            for n in NEW] == [
        ("ms", "lower", "device_trace"), ("ms", "lower", "device_trace"),
        ("count", "higher", "program_counter"),
        ("%", "higher", "device_trace")]
    # the cell reports setup_s, another end-to-end metric and a layer's
    assert all("workloads" not in e or CELL in e["workloads"]
               for e in m["end_to_end"])


def test_band_pairs_on_hand_made_snapshots():
    reader = load_readers()["attention.band_pairs_per_step"]
    window, full = 4 * 32 * 4 * 14_681_088, 4 * 32 * 33_558_528
    ctx = _ctx(before={"attn/window_pairs": 7.0, "attn/full_pairs": 5.0,
                       "wire/x": 1},
               after={"attn/window_pairs": 7.0 + 10 * window,
                      "attn/full_pairs": 5.0 + 10 * full, "wire/x": 9})
    # 4 rows x 32 heads x (four windows of 2048 + one causal mask) over
    # 8192 positions
    assert reader(ctx) == window + full == sum(
        reference.mask_triples_per_step(4, _config()).values())
    assert reference.band_pairs(8192, 2048) == 14_681_088 \
        == mellum.band_pairs(8192, 2048)
    assert reference.band_pairs(8192) == 33_558_528
    # one of the two alone is no reading
    assert reader(_ctx(after={"attn/window_pairs": 70.0})) is None


def test_a_program_without_the_counters_or_the_kernels_reads_nothing(running):
    """The parent of this PR under the benchmark as this PR leaves it,
    or another family's cell: no ``attn/*`` counter, no window or full
    kernel and no grouped product in the trace; the readers return None
    and do not raise."""
    readers = load_readers()
    bare = _ctx(trace=_trace(fusion=1.0, bps_attn_mla=0.2,
                             bps_attn_kda=0.3),
                before={"wire/push_bytes": 1}, after={"wire/push_bytes": 9})
    for name in NEW:
        assert readers[name](bare) is None, name
        assert readers[name](_ctx()) is None, name
    # the grouped products without the load counters, and the counters
    # without the products: no share either way
    loads = {f"moe/expert_load/{l}/{e}": 100.0
             for l in range(4) for e in range(8)}
    assert readers["experts.held_mm_roofline_pct"](
        _ctx(trace=_trace(ragged_dot_bps=0.3))) is None
    assert readers["experts.held_mm_roofline_pct"](
        _ctx(trace=_trace(fusion=0.3), after=loads)) is None


def test_each_kernels_time_apart_and_the_joined_shares(running):
    readers = load_readers()
    cfg = _config()
    # three traced steps: two instruction names of each scope and,
    # beside them, what a prefix must not match
    ctx = _ctx(trace=_trace(bps_attn_window=0.30, bps_attn_window_=0.15,
                            bps_attn_full=0.21, bps_attn_full_=0.09,
                            bps_attn_mla=0.4, fusion=1.5,
                            jvp_bps_attn_window_=0.5))
    assert readers["attention.window_device_ms"](ctx) == pytest.approx(150.0)
    assert readers["attention.full_device_ms"](ctx) == pytest.approx(100.0)
    assert readers["attention.device_ms"](ctx) == pytest.approx(250.0)
    # the joined shares count the five HELD layers' kinds from the
    # file's ``layer_types``: four windows, one full
    assert cfg["layer_types"][:cfg["num_hidden_layers"]].count(
        "sliding_attention") == 4
    for kind, name, seconds in (
            ("sliding_attention", "attention.window_roofline_pct", 0.15),
            ("full_attention", "attention.full_roofline_pct", 0.10)):
        layers = cfg["layer_types"].count(kind)
        flops, nbytes = mellum.attention_step_cost(4, cfg, kind)
        want = 100 * layers * max(flops / PEAK_FLOPS,
                                  nbytes / PEAK_BYTES) / seconds
        got = readers[name](ctx)
        assert got == pytest.approx(want) and 0 < got < 100, name
    # by hand: a window layer's seven products over 14.68 M pairs, 32
    # heads of 128 and 4 rows: 3.37 TFLOP, 17.1 ms at the peak; the full
    # layer's over 33.56 M: 7.70 TFLOP, 39.1 ms
    flops, _ = mellum.attention_step_cost(4, cfg, "sliding_attention")
    assert flops == 14 * 32 * 128 * 14_681_088 * 4
    assert 0.0170 < flops / PEAK_FLOPS < 0.0172
    flops, _ = mellum.attention_step_cost(4, cfg, "full_attention")
    assert 0.0390 < flops / PEAK_FLOPS < 0.0392


def test_the_held_experts_share_counts_the_four_sparse_layers(running):
    """The loads of four sparse layers of eight experts, summed by
    ``layers/moe.py``; the grouped products' need with the weights of
    the layers that HAVE experts (four, not ``num_hidden_layers``)."""
    pairs = 65536.0
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
    cfg = _config()
    flops, nbytes = reference.expert_products_cost(pairs, cfg)
    got = readers["experts.held_mm_roofline_pct"](ctx)
    assert got == pytest.approx(
        100 * max(flops / PEAK_FLOPS, nbytes / PEAK_BYTES) / 0.1)
    assert 0 < got < 100
    # the weights of four layers' eight experts, not five's
    assert nbytes == 3 * (2 * pairs * 9216 + 2 * 4 * 8 * 3 * 2048 * 1024)
    _, five = mellum.expert_products_cost(pairs, cfg)
    assert five - nbytes == 3 * 2 * 8 * 3 * 2048 * 1024
    # an even router's pairs a step, as the FLOP count has them: half a
    # pair a token and sparse layer
    assert reference.expected_pairs_per_token(cfg) * 4 * 8192 * 4 == pairs


def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog's ``config`` is in the file with its
    published value, changed only where ``reduced`` says (the layer
    list is the five held layers' kinds and is named so); what is
    assumed is listed."""
    cfg = _config()
    published = {
        "global_attn_every_n_layers": 4, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144,
        "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 8,
        "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
        "model_type": "afmoe", "moe_intermediate_size": 1024,
        "mup_enabled": True, "n_group": 1, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_expert_groups": 1, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 32,
        "num_key_value_heads": 4, "num_limited_groups": 1,
        "num_shared_experts": 1, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
        "route_scale": 2.826, "score_func": "sigmoid",
        "sliding_window": 2048, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_mm": True, "vocab_size": 200192}
    for k, v in published.items():
        if k in cfg["reduced"]:
            assert cfg["published"][k] == v and cfg[k] != v, k
        else:
            assert cfg[k] == v, k
    assert cfg["reduced"] == ["num_hidden_layers", "num_dense_layers",
                              "layer_types", "num_experts_held",
                              "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"],
            cfg["num_experts_held"], cfg["vocab_size"]) == (5, 1, 8, 25024)
    assert cfg["published"]["num_experts"] == 128
    # the floors: a whole period and four layers behind the dense one, 8
    # experts, an eighth of the vocabulary
    assert cfg["vocab_size"] * 8 == 200192
    # the layers held: published layers 1-5 counted from zero
    assert cfg["layer_types"] == published["layer_types"][1:6] == [
        "sliding_attention", "sliding_attention", "full_attention",
        "sliding_attention", "sliding_attention"]
    assert reference.layer_runs(cfg) == [
        (("sliding_attention", "dense"), 1),
        (("sliding_attention", "sparse"), 1),
        (("full_attention", "sparse"), 1),
        (("sliding_attention", "sparse"), 2)]
    for item in ("attention_gate", "qk_norm", "rotary_by_layer_kind",
                 "four_norms", "mup", "expert_bias", "gate_sum_eps",
                 "shared_expert", "router_aux_loss", "float32", "seq_len",
                 "batch_per_chip", "optimizer", "init", "dropout", "remat"):
        assert item in cfg["assumed"], item
    assert "16 chips" in cfg["deployment"] and "0-7" in cfg["deployment"]
    assert "0-25023" in cfg["deployment"] and "1-5" in cfg["deployment"]
    assert "705 M" in cfg["deployment"]
    assert cfg["expert_bias"] == {"distribution": "uniform", "low": -0.1,
                                  "high": 0.1, "seed": 47}
    assert (cfg["seq_len"], cfg["batch_per_chip"]) == (8192, 4)
    assert (cfg["compute_dtype"], cfg["param_dtype"], cfg["wire_dtype"],
            cfg["router_dtype"], cfg["remat"]) == (
        "bfloat16", "float32", "float32", "float32", True)
    # the sparse decoders' optimizer
    with open(os.path.join(BENCH, "configs",
                           "kimi-linear-48b-a3b.json")) as f:
        assert cfg["optimizer"] == json.load(f)["optimizer"]
    # no width is among the cuts
    assert not {"hidden_size", "intermediate_size", "moe_intermediate_size",
                "head_dim", "num_experts_per_tok", "sliding_window",
                "num_attention_heads", "num_key_value_heads"} \
        & set(cfg["reduced"])


def test_the_catalog_row_is_the_files_source():
    """Where the guide's catalog is installed: its row's every number."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    (row,) = [r for r in rows if r["name"] == "Trinity-Mini"]
    cfg = _config()
    assert cfg["source"] == row["source_url"]
    for k, v in row["config"].items():
        if k in cfg["reduced"]:
            assert cfg["published"][k] == v, k
        else:
            assert cfg[k] == v, k


def test_running_config_is_found_and_the_reference_imports_no_program():
    cfg = _cell.running_config(["--workload", CELL, "--manifest", MANIFEST])
    assert cfg["hidden_size"] == 2048 and cfg["family"] == "afmoe"
    tiny = _cell.running_config(["--workload", CELL, "--manifest", MANIFEST,
                                 "--rehearse"])
    assert (tiny["hidden_size"], tiny["head_dim"], tiny["sliding_window"],
            tiny["seq_len"], tiny["num_experts"], tiny["num_experts_held"],
            tiny["num_hidden_layers"]) == (64, 16, 8, 32, 8, 2, 5)
    with open(os.path.join(BENCH, "reference", "afmoe.py")) as f:
        source = f.read()
    assert "byteps_tpu" not in source
    # no kernel: dense scores under a mask written out, a loop over the
    # experts held
    assert "jnp.where(\n            seen" in source and "lax.scan(one" in source
    assert "pallas" not in source and "ragged" not in source
    # the readers import nothing of the program either: they are laid
    # over a parent that lacks the family; and the family's file imports
    # the program inside its functions, so the parent fails at once there
    with open(os.path.join(BENCH, "layers", "afmoe.py")) as f:
        assert "byteps_tpu" not in f.read()
    with open(os.path.join(BENCH, "families", "afmoe.py")) as f:
        top = f.read().split("def program_config")[0]
    assert "byteps_tpu" not in top
