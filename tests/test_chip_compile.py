"""Ask the TPU compiler, with no chip attached, whether every Pallas
kernel the package ships compiles at real widths, and whether the
backward a whole v5e host runs under the PS step does.

The TPU's compiler is installed beside the CPU backend and compiles for a
chip that is described, not attached (``jax.experimental.topologies``):
what it refuses here (a slice not aligned to the tiling, too much fast
memory, an op Mosaic cannot lower) it would refuse on the chip, at no
chip time. Interpret-mode tests cannot see any of that. One case per
kernel x shape, about two seconds each; skipped where the topology
cannot be described. Nothing runs, so results are checked elsewhere
(tests/test_pallas*.py in interpret mode, chip_smoke.py on the chip).
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

os.environ.setdefault("TPU_LOG_DIR", "disabled")

# one BERT-large block leaf ([24, 1024, 1024] f32) — the size the codecs
# meet on the model chip_smoke.py trains
LEAF = 24 * 1024 * 1024


@pytest.fixture(scope="module")
def v5e_host():
    """The four described chips of one v5e host (``v5e:2x2``)."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler installed
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e!r}")
    return topo.devices


@pytest.fixture(scope="module")
def v5e(v5e_host):
    """One described v5e chip (device kind ``TPU v5 lite``)."""
    return SingleDeviceSharding(v5e_host[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without a chip (the next one warns), so the
    cache is off around these cases."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _onebit_pack(sh):
    from byteps_tpu.ops.compression.pallas_kernels import onebit_pack
    return onebit_pack.lower(_sds((LEAF,), jnp.float32, sh), False)


def _onebit_unpack(sh):
    from byteps_tpu.ops.compression.pallas_kernels import (_LANES,
                                                           _padded_rows,
                                                           onebit_unpack)
    words = _padded_rows(LEAF) * _LANES // 32
    return onebit_unpack.lower(_sds((words,), jnp.uint32, sh),
                               _sds((), jnp.float32, sh), LEAF, False)


def _dithering(partition):
    def lower(sh):
        from byteps_tpu.ops.compression.pallas_kernels import \
            dithering_levels
        return dithering_levels.lower(
            _sds((LEAF,), jnp.float32, sh), _sds((), jnp.float32, sh),
            _sds((), jnp.uint32, sh), 127, partition, False)
    return lower


def _randomk(sh):
    from byteps_tpu.ops.compression.pallas_kernels import randomk_indices
    return randomk_indices.lower(_sds((), jnp.uint32, sh),
                                 _sds((), jnp.int32, sh), LEAF // 100, False)


def _flash(shape, hkv, window=None, diffusion_block=None):
    def lower(sh):
        from byteps_tpu.ops.flash_attention import _flash_fwd
        B, S, H, D = shape
        q = _sds(shape, jnp.bfloat16, sh)
        kv = _sds((B, S, hkv, D), jnp.bfloat16, sh)
        return jax.jit(
            lambda q_, k_, v_: _flash_fwd(q_, k_, v_, True, 512, 512,
                                          window=window,
                                          diffusion_block=diffusion_block)
        ).lower(q, kv, kv)
    return lower


def _flash_bwd(shape, hkv, window=None, diffusion_block=None):
    def lower(sh):
        from byteps_tpu.ops.flash_attention import _flash_bwd as bwd
        B, S, H, D = shape
        q = _sds(shape, jnp.bfloat16, sh)
        kv = _sds((B, S, hkv, D), jnp.bfloat16, sh)
        lse = _sds((B, H, S, 128), jnp.float32, sh)
        return jax.jit(
            lambda q_, k_, v_, o_, l_, g_: bwd(
                q_, k_, v_, o_, l_, g_, True, 512, 512, window,
                diffusion_block=diffusion_block)
        ).lower(q, kv, kv, q, lse, q)
    return lower


def _latent(backward):
    """The latent-attention shape of JoyAI-LLM-Flash's cell: 2 rows of
    8192, 32 heads, a score of 128 a head plus 64 against ONE shared
    rotary key head, values of 128."""
    def lower(sh):
        from byteps_tpu.ops import flash_attention as fa
        B, S, H = 2, 8192, 32
        head = _sds((B, S, H, 128), jnp.bfloat16, sh)
        q_r = _sds((B, S, H, 64), jnp.bfloat16, sh)
        k_r = _sds((B, S, 1, 64), jnp.bfloat16, sh)
        if not backward:
            return jax.jit(
                lambda q, qr, k, kr, v: fa._flash_fwd(
                    q, k, v, True, 512, 512, shared=(qr, kr))
            ).lower(head, q_r, head, k_r, head)
        lse = _sds((B, H, S, 128), jnp.float32, sh)
        return jax.jit(
            lambda q, qr, k, kr, v, o, l, g: fa._flash_bwd(
                q, k, v, o, l, g, True, 512, 512, shared=(qr, kr))
        ).lower(head, q_r, head, k_r, head, head, lse, head)
    return lower


def _grouped_products(sh, k=8, d=2304, h=896):
    """The held experts' products at a benchmark's sparse decoder's
    widths (Mellum's by default): 8 experts of ``d`` x ``h`` over one
    slice's sorted pair buffer (8192 tokens x ``k`` pairs)."""
    from byteps_tpu.models import moe
    T, H = 8192, 8
    return jax.jit(
        lambda x, key, wg, wu, wd: moe.grouped_ffn(
            x, key, k, wg, wu, wd, jnp.bfloat16)
    ).lower(_sds((T, d), jnp.bfloat16, sh), _sds((T * k,), jnp.int32, sh),
            _sds((H, d, h), jnp.float32, sh), _sds((H, d, h), jnp.float32, sh),
            _sds((H, h, d), jnp.float32, sh))


def _grouped_kernels(sh, rows=16384, d=2304, h=896):
    """The package's grouped product with both transposes of its
    backward (``ops/grouped_matmul.py``: the kernels themselves, which
    the dispatch reaches on the TPU only) at a sparse decoder's widths:
    a slice's compact buffer against 8 held experts."""
    from byteps_tpu.ops import grouped_matmul as gm

    def both_ways(lhs, rhs, sizes, g):
        out, vjp = jax.vjp(
            lambda l, r: gm._product(l, r, sizes, 256, False), lhs, rhs)
        return out, vjp(g)

    return jax.jit(both_ways).lower(
        _sds((rows, d), jnp.bfloat16, sh), _sds((8, d, h), jnp.bfloat16, sh),
        _sds((8,), jnp.int32, sh), _sds((rows, h), jnp.bfloat16, sh))


@pytest.mark.parametrize("lower", [
    pytest.param(_onebit_pack, id="onebit_pack-bert_leaf"),
    pytest.param(_onebit_unpack, id="onebit_unpack-bert_leaf"),
    pytest.param(_dithering("linear"), id="dithering_linear-bert_leaf"),
    pytest.param(_dithering("natural"), id="dithering_natural-bert_leaf"),
    pytest.param(_randomk, id="randomk_indices-bert_leaf_1pct"),
    pytest.param(_flash((2, 1024, 16, 64), 16), id="flash_fwd-mha_hd64"),
    pytest.param(_flash((2, 1024, 6, 128), 2), id="flash_fwd-gqa_hd128"),
    pytest.param(_flash((1, 8192, 32, 128), 4, window=1024),
                 id="flash_fwd-window1024_8k_gqa32x4"),
    pytest.param(_flash((1, 8192, 32, 128), 4), id="flash_fwd-full_8k_gqa32x4"),
    pytest.param(_flash_bwd((1, 8192, 32, 128), 4, window=1024),
                 id="flash_bwd-window1024_8k_gqa32x4"),
    pytest.param(_flash_bwd((1, 8192, 32, 128), 4),
                 id="flash_bwd-full_8k_gqa32x4"),
    pytest.param(_grouped_products, id="grouped_ffn-8x2304x896"),
    # LFM2-8B-A1B's widths: heads of 64, four query heads a key head;
    # 8 experts of 2048 x 1792 at 4 pairs a token
    pytest.param(_flash((1, 8192, 32, 64), 8), id="flash_fwd-full_8k_gqa32x8_hd64"),
    pytest.param(_flash_bwd((1, 8192, 32, 64), 8),
                 id="flash_bwd-full_8k_gqa32x8_hd64"),
    pytest.param(lambda sh: _grouped_products(sh, k=4, d=2048, h=1792),
                 id="grouped_ffn-8x2048x1792"),
    pytest.param(_grouped_kernels, id="grouped_kernels-16384x8x2304x896"),
    pytest.param(lambda sh: _grouped_kernels(sh, rows=65536),
                 id="grouped_kernels-65536x8x2304x896"),
    pytest.param(lambda sh: _grouped_kernels(sh, d=2048, h=1792),
                 id="grouped_kernels-16384x8x2048x1792"),
    pytest.param(lambda sh: _grouped_kernels(sh, rows=32768, d=2048, h=1792),
                 id="grouped_kernels-32768x8x2048x1792"),
    # SDAR-30B-A3B's cell: 2 rows of 8192 tokens, each a noised and a
    # clean copy (16,384 positions) under the block-diffusion mask at
    # block length 4, 32 query and 4 key heads of 128
    pytest.param(_flash((2, 16384, 32, 128), 4, diffusion_block=4),
                 id="flash_fwd-blockdiff4_2x16k_gqa32x4"),
    pytest.param(_flash_bwd((2, 16384, 32, 128), 4, diffusion_block=4),
                 id="flash_bwd-blockdiff4_2x16k_gqa32x4"),
    # JoyAI-LLM-Flash's cell: latent attention, 192-wide scores in two
    # parts over 128-wide values, one shared rotary key head of 64
    pytest.param(_latent(False), id="flash_fwd-mla_2x8k_32x128+64_shared"),
    pytest.param(_latent(True), id="flash_bwd-mla_2x8k_32x128+64_shared"),
])
def test_kernel_compiles_for_v5e(v5e, lower):
    compiled = lower(v5e).compile()
    # the kernel itself must be in the program, not a portable rewrite
    assert "tpu_custom_call" in compiled.as_text()


def test_untapped_scatter_backward_compiles_for_a_v5e_host(v5e_host):
    """The program a four-chip mesh runs: BERT-large's widths (two of
    its 24 layers: the full depth compiles in 100 s here), batch 64 a
    chip, the plan's shard leaves reduce-scattered and returned as flat
    ``P(dp)`` outputs. The TPU's
    compiler takes it, no host callback or host transfer is in it (so
    the persistent cache can serve it), and a chip's outputs hold a
    quarter of every shard leaf."""
    import dataclasses

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from byteps_tpu.config import Config
    from byteps_tpu.jax import train
    from byteps_tpu.models import bert
    from byteps_tpu.ops.push_pull import shard_layout

    n = len(v5e_host)
    mesh = Mesh(np.array(v5e_host), ("dp",))
    cfg = dataclasses.replace(bert.BertConfig.bert_large(), n_layers=2)
    rep, dp = NamedSharding(mesh, P()), NamedSharding(mesh, P("dp"))
    params = jax.tree.map(
        lambda v: _sds(v.shape, v.dtype, rep),
        jax.eval_shape(lambda: bert.init_params(jax.random.PRNGKey(0), cfg)))
    leaves = jax.tree.leaves(params)
    # jax/train.py's shard plan under the default thresholds
    floor = max(Config().fusion_bytes, Config().shard_min_bytes)
    nbytes = [v.size * v.dtype.itemsize for v in leaves]
    shard_set = tuple(
        i for i, v in enumerate(leaves)
        if nbytes[i] >= floor and shard_layout(v.size, n)[1] * 8 <= v.size)
    assert 0 < len(shard_set) < len(leaves)
    batch = {k: _sds((64 * n, 128), jnp.int32, dp)
             for k in ("tokens", "labels")}
    fn = train._scatter_backward(
        train._loss_and_stats(lambda p, b: bert.loss_fn(p, b, cfg)),
        mesh, "dp", shard_set, len(leaves))
    compiled = fn.lower(params, batch).compile()
    text = compiled.as_text()
    # a tap would be a host transfer or a callback custom call (the
    # bare words also occur in the instructions' source locations)
    assert "is_host_transfer=true" not in text
    assert not [t for t in re.findall(r'custom_call_target="([^"]+)"', text)
                if "callback" in t.lower()]
    assert "all-reduce" in text or "reduce-scatter" in text
    # a device's outputs: its quarter of each shard leaf, every other
    # leaf whole, the loss
    out = compiled.memory_analysis().output_size_in_bytes
    want = sum(b // n if i in shard_set else b for i, b in enumerate(nbytes))
    assert want <= out <= want + (1 << 20)


def test_the_plan_pins_the_output_a_v5e_would_hand_over_out_of_order(v5e_host):
    """The v5e keeps a float32 ``[2048, 18992]`` (SDAR's head: a minor
    dimension that is no multiple of a lane tile) minor in its FIRST
    dimension, and a program output's default layout is the device's:
    ``np.asarray`` of that gradient is a transposed view and one host
    core copies 155 MB into order (``PERF.md`` 6, PR 38).
    ``_row_major_outputs`` sees it in the compiled backward and pins
    that output alone; the well-shaped leaf beside it keeps its
    default, and a tree of such leaves keeps its program."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from byteps_tpu.jax import train

    mesh = Mesh(np.array(v5e_host[:1]), ("dp",))
    rep, dp = NamedSharding(mesh, P()), NamedSharding(mesh, P("dp"))
    params = {"head": _sds((2048, 18992), jnp.float32, rep),
              "proj": _sds((2048, 1024), jnp.float32, rep)}
    batch = _sds((64, 2048), jnp.float32, dp)

    def loss(p, x):
        return (jnp.mean((x @ p["head"]) ** 2)
                + jnp.mean(jnp.tanh(x @ p["proj"])))

    backward = train._psum_backward(train._loss_and_stats(loss), mesh, "dp")

    def orders(fn):
        formats = fn.lower(params, batch).compile().output_formats[1]
        return {k: tuple(f.layout.major_to_minor)
                for k, f in formats.items()}

    assert orders(backward) == {"head": (1, 0), "proj": (0, 1)}
    pinned, n = train._row_major_outputs(
        backward, (params, batch), [0, 1], mesh)
    assert n == 1 and pinned is not backward
    assert orders(pinned) == {"head": (0, 1), "proj": (0, 1)}
    # nothing to pin: the backward itself, compiled by the look
    same, n = train._row_major_outputs(
        backward, (params, batch), [1], mesh)
    assert n == 0 and same is backward


def _computations(text):
    """{name: body lines} of a compiled module's HLO text."""
    out, name = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            name = head.group(1)
            out[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            out[name].append(line)
    return out


def _reached(comps, root):
    """The text of ``root`` and of every computation it calls."""
    seen, todo = set(), [root]
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        body = "\n".join(comps[name])
        todo += re.findall(
            r"(?:calls|to_apply|body|condition|true_computation|"
            r"false_computation)=%?([\w.\-]+)", body)
        for group in re.findall(r"branch_computations=\{([^}]*)\}", body):
            todo += [n.strip().lstrip("%") for n in group.split(",")]
    return "\n".join("\n".join(comps[n]) for n in seen)


def test_the_sparse_decoders_block_walks_a_compact_buffer_on_a_v5e(
        v5e, monkeypatch):
    """One block of the benchmark's sparse decoder, forward and backward
    under its remat, at published widths (4 rows of 8192 tokens, 8 of 64
    experts held, 8 a token): the expert layer is a conditional of two
    walks, one whose grouped products see the compact buffer's 16,384
    rows a slice and which holds no array of a slice's 65,536 pairs by
    the hidden or the expert width, one that is the full-size path."""
    import dataclasses

    from byteps_tpu.models import mellum, moe

    # the attention kernels, as on the chip (the process's backend is
    # the CPU here)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(mellum.MellumConfig(), n_experts_held=8,
                              n_layers=1, layer_types=(mellum.SLIDING,))
    rows, seq = 4, 8192
    pairs = mellum.EXPERT_SLICE * cfg.top_k
    compact = moe.compact_rows(pairs, 8, cfg.n_experts)
    assert (pairs, compact) == (65536, 16384)
    layer = jax.tree.map(
        lambda v: _sds(v.shape[1:], v.dtype, v5e),
        jax.eval_shape(lambda: mellum.init_params(
            jax.random.PRNGKey(0), cfg))["blocks"])
    block = jax.checkpoint(mellum._block, static_argnums=(3, 4, 5))

    def loss(x, p):
        out, stats = block(x, p, mellum.rope_tables(cfg, seq), cfg,
                           mellum.SLIDING, None)
        return jnp.sum(out.astype(jnp.float32)), stats

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)
                   ).lower(_sds((rows, seq, cfg.dim), jnp.bfloat16, v5e),
                           layer).compile().as_text()
    comps = _computations(text)
    full_rows = re.compile(r"\[%d,(?:%d|%d)\]" % (
        pairs, cfg.dim, cfg.expert_hidden))
    compact_rows = re.compile(r"\[%d,(?:%d|%d)\]" % (
        compact, cfg.dim, cfg.expert_hidden))
    walks = []
    for lines in comps.values():
        for line in lines:
            if " conditional(" not in line:
                continue
            names = re.findall(r"(?:true|false)_computation=%?([\w.\-]+)",
                               line) or [
                n.strip().lstrip("%") for n in re.search(
                    r"branch_computations=\{([^}]*)\}", line).group(1)
                .split(",")]
            branches = [_reached(comps, n) for n in names]
            if any("ragged-dot" in b for b in branches):
                walks.append(branches)
    # the forward's conditional and the backward's
    assert len(walks) >= 2
    for branches in walks:
        kinds = sorted((bool(full_rows.search(b)),
                        bool(compact_rows.search(b))) for b in branches)
        assert kinds == [(False, True), (True, False)], kinds
    assert "tpu_custom_call" in text
    # every grouped product is the package's kernel, none XLA's own: a
    # trace that held both would divide all the needed FLOPs by part of
    # the time (``benchmark/layers/moe.py`` sums the ``ragged-dot``
    # family)
    assert "ragged-dot.bps" in text and "ragged-dot-none" not in text


def test_the_hybrid_decoders_blocks_compile_for_a_v5e(v5e, monkeypatch):
    """The three kinds of block of the benchmark's sparse hybrid decoder
    (``models/lfm2.py`` at LFM2-8B-A1B's published widths, 2 rows of 8192
    tokens, 8 of 32 experts held, 4 a token), forward and backward under
    their remat: the gated short convolution is fusions (no convolution
    custom call, no kernel of this package), the attention block holds
    the Pallas kernels at head size 64, the sparse FFN the grouped
    products over a compact buffer of 16,384 rows a slice of 32,768
    pairs."""
    import dataclasses

    from byteps_tpu.models import lfm2, moe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(
        lfm2.LFM2Config(), vocab_size=16384, n_experts_held=8,
        layer_types=(lfm2.CONV, lfm2.FULL, lfm2.CONV), n_dense_layers=1)
    assert cfg.runs() == [((lfm2.CONV, lfm2.DENSE), 1),
                          ((lfm2.FULL, lfm2.SPARSE), 1),
                          ((lfm2.CONV, lfm2.SPARSE), 1)]
    rows, seq = 2, 8192
    pairs = lfm2.EXPERT_SLICE * cfg.top_k
    assert (pairs, moe.compact_rows(pairs, 8, cfg.n_experts)) == (32768, 16384)
    runs = jax.tree.map(
        lambda v: _sds(v.shape[1:], v.dtype, v5e),
        jax.eval_shape(lambda: lfm2.init_params(
            jax.random.PRNGKey(0), cfg))["runs"])
    block = jax.checkpoint(lfm2._block, static_argnums=(4, 5, 6))
    x = _sds((rows, seq, cfg.dim), jnp.bfloat16, v5e)
    bias = _sds((cfg.n_experts,), jnp.float32, v5e)
    texts = {}
    for (kind, _), p in zip(cfg.runs(), runs):
        def loss(x_, p_, b_, kind=kind):
            out, stats = block(x_, p_, b_ if kind[1] == lfm2.SPARSE else None,
                               lfm2.L.rope_cache(cfg, seq), cfg, kind, None)
            return jnp.sum(out.astype(jnp.float32)), stats

        texts[kind] = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)).lower(
                x, p, bias).compile().as_text()
    dense, attn, sparse = (texts[kind] for kind, _ in cfg.runs())
    # the convolution block with the dense FFN: XLA's own programs only
    assert "tpu_custom_call" not in dense and "ragged-dot" not in dense
    assert "bps.attn.full" in attn and "tpu_custom_call" in attn
    for text in (attn, sparse):
        assert "ragged-dot" in text and " conditional(" in text
        assert re.search(r"\[16384,(?:2048|1792)\]", text)
        assert "ragged-dot.bps" in text and "ragged-dot-none" not in text


def test_the_block_diffusion_decoders_block_compiles_for_a_v5e(
        v5e, monkeypatch):
    """One block of the benchmark's block-diffusion decoder
    (``models/sdar.py`` at SDAR-30B-A3B's published widths, 2 rows of
    8192 tokens as 16,384 positions each, 16 of 128 experts held, 8 a
    token), forward and backward under its remat: the attention is the
    Pallas kernels under ``bps.attn.blockdiff`` and under no other
    attention scope, the sparse FFN the package's grouped products over
    a compact buffer of 16,384 rows a slice of 65,536 pairs."""
    import dataclasses

    from byteps_tpu.models import moe, sdar

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(sdar.SDARConfig(), vocab_size=18992,
                              n_experts_held=16, n_layers=1)
    rows, seq = 2, 8192
    pairs = sdar.mellum.EXPERT_SLICE * cfg.top_k
    assert (pairs, moe.compact_rows(pairs, 16, cfg.n_experts)) \
        == (65536, 16384)
    layer = jax.tree.map(
        lambda v: _sds(v.shape[1:], v.dtype, v5e),
        jax.eval_shape(lambda: sdar.init_params(
            jax.random.PRNGKey(0), cfg))["blocks"])
    block = jax.checkpoint(sdar._block, static_argnums=(3, 4))
    rope = tuple(_sds((2 * seq, cfg.head_dim // 2), jnp.float32, v5e)
                 for _ in range(2))

    def loss(x, p, rope):
        out, stats = block(x, p, rope, cfg, None)
        return jnp.sum(out.astype(jnp.float32)), stats

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)
                   ).lower(_sds((rows, 2 * seq, cfg.dim), jnp.bfloat16, v5e),
                           layer, rope).compile().as_text()
    assert "tpu_custom_call" in text
    # the forward (with the row logsumexp; once more where the remat's
    # copy is not merged with it), dK/dV and dQ, each instruction named
    # by the scope alone
    assert len(set(re.findall(r"%(bps\.attn\.blockdiff[\w.]*) = ", text))) \
        >= 3
    assert "bps.attn.full" not in text and "bps.attn.window" not in text
    assert "ragged-dot.bps" in text and "ragged-dot-none" not in text
    assert re.search(r"\[16384,(?:2048|768)\]", text)


def test_a_cut_backwards_layer_program_names_its_kernels_by_scope_alone(
        v5e_host, monkeypatch):
    """The program a PS step runs once a layer where the loss is a chain
    (``ops/chain.py``; ``jax/train.py _cut_backward``), at SDAR-30B-A3B's
    published widths over two stacked layers: it differentiates the
    run's scan over ONE layer, so its Pallas calls carry their scope's
    name and no transform's (``jvp_bps.attn...`` is what a bare block
    would give, and the benchmark's readers find ``bps.attn.blockdiff``
    and ``ragged-dot.bps`` by name); its gradient outputs are one
    layer's ``[1, ...]`` slices."""
    import dataclasses

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from byteps_tpu.jax import train
    from byteps_tpu.ops import chain
    from byteps_tpu.models import sdar

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(sdar.SDARConfig(), vocab_size=18992,
                              n_experts_held=16, n_layers=2)
    rows, seq = 2, 8192
    mesh = Mesh(np.array(v5e_host[:1]), ("dp",))
    rep, dp = NamedSharding(mesh, P()), NamedSharding(mesh, P("dp"))
    params = jax.tree.map(
        lambda v: _sds(v.shape, v.dtype, rep),
        jax.eval_shape(lambda: sdar.init_params(jax.random.PRNGKey(0), cfg)))
    batch = {"tokens": _sds((rows, seq), jnp.int32, dp),
             "noise_mask": _sds((rows, seq), jnp.bool_, dp),
             "rates": _sds((rows, seq // cfg.block_length), jnp.float32, dp)}
    with chain.collecting() as found:
        jax.eval_shape(lambda p, b: sdar.loss_fn(p, b, cfg), params, batch)
    (ch,) = found
    cut = train._cut_backward(ch, mesh, "dp",
                              train._chain_leaves(ch, params))
    assert cut.programs == 5
    carry = _sds((1, rows, 2 * seq, cfg.dim), cfg.dtype, dp)
    inputs = _sds((1, cfg.n_layers, rows, 2 * seq, cfg.dim), cfg.dtype, dp)
    blocks = ch.links[1].pick(params)
    compiled = cut.pulls[1][None].lower(blocks, np.int32(1), inputs, batch,
                                  carry).compile()
    text = compiled.as_text()
    names = set(re.findall(r"%([\w.\-]*(?:bps\.attn|ragged-dot)[\w.\-]*) = ",
                           text))
    assert names and all(
        re.fullmatch(r"(bps\.attn\.blockdiff|ragged-dot\.bps)(\.\d+)*", n)
        for n in names), sorted(names)
    # the forward run again, dK/dV and dQ; the three grouped products
    # and their transposes
    assert len({n for n in names if n.startswith("bps.attn")}) == 3
    assert len({n for n in names if n.startswith("ragged-dot")}) >= 9
    g_carry, g_blocks = jax.eval_shape(
        cut.pulls[1][None], blocks, np.int32(1), inputs, batch, carry)
    assert g_carry.shape == carry.shape
    assert jax.tree.map(lambda g: g.shape, g_blocks) == jax.tree.map(
        lambda p: (1,) + p.shape[1:], blocks)


def test_the_delta_rules_kernels_compile_at_the_cells_shape(v5e, monkeypatch):
    """``ops/delta_rule.py``'s forward and backward kernels (the
    backward's body is ``jax.vjp`` of the chunk, traced into the kernel:
    what Mosaic is asked to lower is decided there) at Kimi-Linear's
    widths, 2 rows of 8192 positions, 32 heads of 128, bfloat16, as the
    TPU's path takes them (bfloat16 products, no widening)."""
    from byteps_tpu.ops import delta_rule

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    wide = _sds((2, 8192, 32, 128), jnp.bfloat16, v5e)
    g = _sds((2, 8192, 32, 128), jnp.float32, v5e)
    beta = _sds((2, 8192, 32), jnp.float32, v5e)
    compiled = jax.jit(jax.grad(
        lambda *a: jnp.sum(delta_rule.delta_rule(*a).astype(jnp.float32)),
        argnums=(0, 1, 2, 3, 4))).lower(wide, wide, wide, g, beta).compile()
    text = compiled.as_text()
    # the forward that keeps the chunks' states, and the backward
    assert text.count("tpu_custom_call") >= 2
    assert set(re.findall(r"%([\w.\-]*bps\.attn[\w.\-]*) = ", text)), text[:200]
    grads = jax.eval_shape(jax.grad(
        lambda *a: jnp.sum(delta_rule.delta_rule(*a).astype(jnp.float32)),
        argnums=(0, 1, 2, 3, 4)), wide, wide, wide, g, beta)
    assert [x.dtype for x in grads] == [jnp.bfloat16] * 3 + [jnp.float32] * 2


def test_a_cut_backwards_program_of_a_run_of_one_names_its_kernels_by_scope(
        v5e_host, monkeypatch):
    """``models/kimi.py`` at Kimi-Linear's published widths: the program
    of a run of ONE layer (KDA and a sparse FFN) differentiates the
    run's scan over that layer, so the delta rule's two kernels are
    ``bps.attn.kda`` and the grouped products ``ragged-dot.bps`` by name;
    its gradient outputs are the run's whole leaves."""
    import dataclasses

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from byteps_tpu.jax import train
    from byteps_tpu.models import kimi
    from byteps_tpu.ops import chain

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(
        kimi.KimiConfig(), vocab_size=2048, n_experts_held=8,
        layer_ops=("kda", "kda", "kda", "mla", "kda"))
    rows, seq = 2, 8192
    mesh = Mesh(np.array(v5e_host[:1]), ("dp",))
    rep, dp = NamedSharding(mesh, P()), NamedSharding(mesh, P("dp"))
    params = jax.tree.map(
        lambda v: _sds(v.shape, v.dtype, rep),
        jax.eval_shape(lambda: kimi.init_params(jax.random.PRNGKey(0), cfg)))
    batch = {"tokens": _sds((rows, seq + 1), jnp.int32, dp)}
    with chain.collecting() as found:
        jax.eval_shape(lambda p, b: kimi.loss_fn(p, b, cfg), params, batch)
    (ch,) = found
    cut = train._cut_backward(ch, mesh, "dp",
                              train._chain_leaves(ch, params))
    assert cut.programs == 8
    carry = _sds((1, rows, seq, cfg.dim), cfg.dtype, dp)
    inputs = _sds((1, 1, rows, seq, cfg.dim), cfg.dtype, dp)
    run = ch.links[4].pick(params)
    compiled = cut.pulls[4][None].lower(run, np.int32(0), inputs, batch,
                                  carry).compile()
    names = set(re.findall(r"%([\w.\-]*(?:bps\.attn|ragged-dot)[\w.\-]*) = ",
                           compiled.as_text()))
    assert names and all(
        re.fullmatch(r"(bps\.attn\.kda|ragged-dot\.bps)(\.\d+)*", n)
        for n in names), sorted(names)
    # the forward run again (the block's remat), once more with its
    # states kept (the operator's own checkpoint), and the backward
    assert len({n for n in names if n.startswith("bps.attn")}) == 3
    g_carry, g_run = jax.eval_shape(
        cut.pulls[4][None], run, np.int32(0), inputs, batch, carry)
    assert g_carry.shape == carry.shape
    assert jax.tree.map(lambda g: g.shape, g_run) == jax.tree.map(
        lambda p: p.shape, run)


@pytest.mark.parametrize("link, layer, kernel", [
    (4, 1, "bps.attn.window"), (3, 0, "bps.attn.full")],
    ids=["window-piece-of-two", "full-run-of-one"])
def test_the_window_and_full_kernels_compile_behind_a_cut_backward(
        v5e_host, monkeypatch, link, layer, kernel):
    """``models/afmoe.py`` at Trinity-Mini's published widths, 4 rows of
    8192: the program of one layer of the run of TWO sparse + sliding
    layers (a piece: its gradient outputs are that layer's slices) and
    of the run of ONE sparse + full layer differentiate the run's scan,
    so the attention kernels are ``bps.attn.window`` / ``bps.attn.full``
    and the grouped products ``ragged-dot.bps`` by name: the forward run
    again under the block's remat, once more under the row's own
    checkpoint (the attention sublayer walks the rows there), dK/dV and
    dQ."""
    import dataclasses

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from byteps_tpu.jax import train
    from byteps_tpu.models import afmoe
    from byteps_tpu.ops import chain

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(
        afmoe.AfmoeConfig(), vocab_size=2048, n_experts_held=8,
        n_dense_layers=1, layer_types=afmoe.published_layers()[1:6])
    rows, seq = 4, 8192
    mesh = Mesh(np.array(v5e_host[:1]), ("dp",))
    rep, dp = NamedSharding(mesh, P()), NamedSharding(mesh, P("dp"))
    params = jax.tree.map(
        lambda v: _sds(v.shape, v.dtype, rep),
        jax.eval_shape(lambda: afmoe.init_params(jax.random.PRNGKey(0), cfg)))
    batch = {"tokens": _sds((rows, seq + 1), jnp.int32, dp)}
    with chain.collecting() as found:
        jax.eval_shape(lambda p, b: afmoe.loss_fn(p, b, cfg), params, batch)
    (ch,) = found
    assert [getattr(ln, "depth", None) for ln in ch.links] == \
        [None, 1, 1, 1, 2, None]
    cut = train._cut_backward(ch, mesh, "dp",
                              train._chain_leaves(ch, params))
    assert cut.programs == 8
    depth = ch.links[link].depth
    carry = _sds((1, rows, seq, cfg.dim), cfg.dtype, dp)
    inputs = _sds((1, depth, rows, seq, cfg.dim), cfg.dtype, dp)
    run = ch.links[link].pick(params)
    compiled = cut.pulls[link][None].lower(run, np.int32(layer), inputs, batch,
                                     carry).compile()
    names = set(re.findall(r"%([\w.\-]*(?:bps\.attn|ragged-dot)[\w.\-]*) = ",
                           compiled.as_text()))
    assert names and all(
        re.fullmatch(rf"({re.escape(kernel)}|ragged-dot\.bps)(\.\d+)*", n)
        for n in names), sorted(names)
    assert len({n for n in names if n.startswith("bps.attn")}) == 4
    assert len({n for n in names if n.startswith("ragged-dot")}) >= 9
    g_carry, g_run = jax.eval_shape(
        cut.pulls[link][None], run, np.int32(layer), inputs, batch, carry)
    assert g_carry.shape == carry.shape
    assert jax.tree.map(lambda g: g.shape, g_run) == jax.tree.map(
        lambda p: (1,) + p.shape[1:], run)
