"""Kimi-Linear-style hybrid sparse decoder (``model_type: kimi_linear``):
layers whose operator is Kimi Delta Attention (KDA: a gated delta rule
with a decay a channel over a recurrent state a head) three times out of
four and multi-head latent attention WITHOUT any positional term on the
fourth, a dense SwiGLU in the leading layers and, in the rest, a
sparse-expert layer of which this device may hold a share beside a
shared expert, the experts chosen by a sigmoid router that selects with
a bias and weighs without it, an untied head.

A file of its own beside ``joyai.py`` because its layers differ in TWO
dimensions (operator KDA or MLA, FFN dense or sparse) and because no
other family holds a state longer than a filter. What is shared is
imported: ``llama``'s RMSNorm and batch split, ``moe.moe_layer`` (the
held-experts layer, its router and the shared expert),
``ops.flash_attention.latent_attention``, ``joyai``'s dense FFN and its
row-at-a-time head;
the recurrence is ``ops.delta_rule``. The loss is an ``ops.chain.Chain``:
the embedding, one ``Run`` a stretch of like blocks (a stretch of one
too: a kernel's instruction keeps its scope's name only inside a scan),
then the final norm, the head and the loss. The parameter tree's
top-level keys are the chain's links: ``embed``, ``run00``, ``run01``,
..., ``final_norm``, ``lm_head``; a run's leaves are stacked on a leading
axis under ``{"op": ..., "ffn": ...}``.

Equations (``x`` is ``[tokens, dim]``, ``eps = norm_eps``, ``H`` heads
of ``d = kda_head_dim``):

- block: ``x += Op(RMSNorm(x))``, ``x += FFN(RMSNorm(x))``;
- ``Op``, KDA: ``q = SiLU(Conv(u W_q))``, ``k``, ``v`` alike, ``Conv``
  one causal filter of ``conv_kernel`` taps a channel, zero before a
  row's first position (``short_conv_silu``); a head's ``q <- q / |q|
  d^(-1/2)``, ``k <- k / |k|`` (``|x| = sqrt(sum x^2 + 1e-6)``); in
  float32 the decay ``g = -exp(A_log_h) softplus(W_fu (W_fd u) +
  dt_bias)`` (``[H, d]`` a token) and the write strength ``beta =
  sigmoid(u W_beta)`` (``[H]``); ``o = delta_rule(q, k, v, g, beta)``;
  ``Op = concat_h(RMSNorm_h(o) sigmoid(W_gu (W_gd u) + b_g)) W_o`` with
  one weight ``[d]`` for every head's norm. No positional term;
- ``Op``, MLA without rotary: a head's ``[q_n (nope_dim) ; q_r
  (rope_dim)] = u W_q`` (no query latent); ``[c_kv (kv_lora_rank) ; k_r
  (rope_dim)] = u W_kva``, a head's ``[k_n ; v (v_dim)] = RMSNorm(c_kv)
  W_kvb``; ONE ``k_r`` under all heads; nothing is rotated; ``s = (q_n .
  k_n + q_r . k_r) / sqrt(nope_dim + rope_dim)``, causal softmax, ``Op =
  concat(softmax(s) v) W_o``;
- ``FFN``: ``joyai.py``'s (dense: ``W_2(silu(W_1 u) * W_3 u)``; sparse:
  ``moe.moe_layer``, sigmoid scores in float32, the ``top_k`` largest of
  ``s + expert_bias``, weights ``s`` without the bias over their sum
  plus 1e-20 times ``routed_scaling``, plus the shared expert);
- head: ``logits = RMSNorm(h) W_head``, the mean cross-entropy of
  ``t_{i+1}`` at position ``i``.

``expert_bias`` ``[sparse layers, n_experts]`` is a buffer: an argument
of ``loss_fn`` beside the parameters, no gradient, never on the wire; a
run reads its layers' rows (``chain.Run``'s ``each``).

``loss_fn`` returns ``(loss, stats)``: ``joyai.py``'s ``moe/*`` (each
sparse run counts its layers' rows of ``moe/expert_load``; the runs'
counts add up, ``chain.add_stats``) and ``kda/chunk_steps`` (rows x
heads x chunks, summed over the KDA layers: the sequential steps the
operator's time scales with) and ``kda/tokens`` (positions through it).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import chain, delta_rule
from ..ops.flash_attention import latent_attention, publish_walk_sizes
from . import llama as L
from . import moe
from .joyai import (ATTN_BLOCK, EXPERT_SLICE, GATE_SUM_EPS, _dense_ffn,
                    head_nll)

KDA, MLA = "kda", "mla"
DENSE, SPARSE = "dense", "sparse"

# added to a head's sum of squares before q and k are normalised (the
# released kernels'; the config has no key for it)
L2_EPS = 1e-6


def published_ops(n_layers: int = 27, period: int = 4) -> Tuple[str, ...]:
    """The published pattern: every ``period``-th layer and the last are
    latent attention, the others KDA."""
    return tuple(MLA if (i + 1) % period == 0 or i + 1 == n_layers else KDA
                 for i in range(n_layers))


@dataclasses.dataclass(frozen=True)
class KimiConfig:
    vocab_size: int = 163840         # rows of the vocabulary held here
    dim: int = 2304
    layer_ops: Tuple[str, ...] = published_ops()   # the layers held
    n_dense_layers: int = 1          # leading held layers with a dense FFN
    kda_heads: int = 32
    kda_head_dim: int = 128          # a head's keys and its values
    conv_kernel: int = 4             # ``short_conv_kernel_size``
    gate_rank: int = 128             # of the decay's and the output gate's
    n_heads: int = 32                # latent attention's
    kv_lora_rank: int = 512
    nope_dim: int = 128              # ``qk_nope_head_dim``
    rope_dim: int = 64               # ``qk_rope_head_dim`` (not rotated)
    v_dim: int = 128                 # ``v_head_dim``
    dense_hidden: int = 9216
    n_experts: int = 256             # the router's outputs
    n_experts_held: int = 256        # expert leaves' leading dim
    first_expert: int = 0            # the first held expert's index
    top_k: int = 8
    expert_hidden: int = 1024        # the shared expert's width too
    n_group: int = 1
    routed_scaling: float = 2.446
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    router_dtype: Any = jnp.float32  # float32, outside a precision control
    remat: bool = True

    def __post_init__(self):
        if self.n_group != 1:
            raise ValueError(
                f"n_group={self.n_group}: group-limited selection is not "
                f"written (one group has nothing to limit)")
        if set(self.layer_ops) - {KDA, MLA}:
            raise ValueError(f"layer_ops {self.layer_ops}: {KDA} or {MLA}")

    @property
    def n_layers(self) -> int:
        return len(self.layer_ops)

    @property
    def n_sparse_layers(self) -> int:
        return self.n_layers - min(self.n_dense_layers, self.n_layers)

    def runs(self) -> List[Tuple[str, str, int]]:
        """The layers held as stretches of like blocks: (operator, FFN
        kind, layers), in order."""
        runs: List[Tuple[str, str, int]] = []
        for i, op in enumerate(self.layer_ops):
            ffn = DENSE if i < self.n_dense_layers else SPARSE
            if runs and runs[-1][:2] == (op, ffn):
                runs[-1] = (op, ffn, runs[-1][2] + 1)
            else:
                runs.append((op, ffn, 1))
        return runs

    @staticmethod
    def tiny(vocab_size: int = 64) -> "KimiConfig":
        """Test-scale: the benchmark cell's five layers (KDA + dense,
        two KDA + sparse, MLA + sparse, KDA + sparse), a share of the
        experts."""
        return KimiConfig(
            vocab_size=vocab_size, dim=32, layer_ops=(KDA, KDA, KDA, MLA, KDA),
            kda_heads=2, kda_head_dim=16, gate_rank=8, n_heads=2,
            kv_lora_rank=16, nope_dim=16, rope_dim=8, v_dim=16,
            dense_hidden=48, n_experts=8, n_experts_held=4, top_k=2,
            expert_hidden=24, remat=False, dtype=jnp.float32)


def run_key(i: int) -> str:
    return f"run{i:02d}"


def _shapes(cfg: KimiConfig) -> Dict[str, Dict[str, tuple]]:
    d, nh, rank = cfg.dim, cfg.n_heads, cfg.gate_rank
    wide = cfg.kda_heads * cfg.kda_head_dim
    F, f, E, H = (cfg.dense_hidden, cfg.expert_hidden, cfg.n_experts,
                  cfg.n_experts_held)
    return {
        KDA: {"wq": (d, wide), "wk": (d, wide), "wv": (d, wide),
              "f_down": (d, rank), "f_up": (rank, wide),
              "g_down": (d, rank), "g_up": (rank, wide),
              "w_beta": (d, cfg.kda_heads), "wo": (wide, d)},
        MLA: {"wq": (d, nh * (cfg.nope_dim + cfg.rope_dim)),
              "wkv_a": (d, cfg.kv_lora_rank + cfg.rope_dim),
              "wkv_b": (cfg.kv_lora_rank, nh * (cfg.nope_dim + cfg.v_dim)),
              "wo": (nh * cfg.v_dim, d)},
        DENSE: {"w1": (d, F), "w3": (d, F), "w2": (F, d)},
        SPARSE: {"router": (d, E), "w_gate": (H, d, f), "w_up": (H, d, f),
                 "w_down": (H, f, d), "shared_gate": (d, f),
                 "shared_up": (d, f), "shared_down": (f, d)},
    }


def init_params(rng: jax.Array, cfg: KimiConfig) -> Dict[str, Any]:
    """Normal(0, 0.02) matrices, norms at one; KDA's filters uniform in
    +-1/sqrt(taps), ``A_log = log(uniform(1, 16))`` a head, ``dt_bias``
    the inverse softplus of a step log-uniform in [1e-3, 0.1] a channel,
    ``g_bias`` zero (the released layer's start). A run's layers are
    stacked."""
    d, pd = cfg.dim, cfg.param_dtype
    wide = cfg.kda_heads * cfg.kda_head_dim
    shapes = _shapes(cfg)

    def dense(key, shape):
        return jax.random.normal(key, shape, pd) * 0.02

    def group(key, kind, n):
        keys = jax.random.split(key, len(shapes[kind]) + 5)
        out = {"norm": jnp.ones((n, d), pd)}
        out.update({name: dense(k, (n, *shape)) for k, (name, shape)
                    in zip(keys, sorted(shapes[kind].items()))})
        if kind == MLA:
            out["kv_norm"] = jnp.ones((n, cfg.kv_lora_rank), pd)
        if kind == KDA:
            bound = cfg.conv_kernel ** -0.5
            for k, name in zip(keys[-5:-2], ("conv_q", "conv_k", "conv_v")):
                out[name] = jax.random.uniform(
                    k, (n, cfg.conv_kernel, wide), pd, -bound, bound)
            out["A_log"] = jnp.log(jax.random.uniform(
                keys[-2], (n, cfg.kda_heads), pd, 1.0, 16.0))
            step = jnp.exp(jax.random.uniform(
                keys[-1], (n, wide), pd, np.log(1e-3), np.log(0.1)))
            out["dt_bias"] = step + jnp.log(-jnp.expm1(-step))
            out["g_bias"] = jnp.zeros((n, wide), pd)
            out["o_norm"] = jnp.ones((n, cfg.kda_head_dim), pd)
        return out

    k_embed, k_head, k_runs = jax.random.split(rng, 3)
    params = {"embed": dense(k_embed, (cfg.vocab_size, d)),
              "lm_head": dense(k_head, (d, cfg.vocab_size)),
              "final_norm": jnp.ones((d,), pd)}
    for i, (op, ffn, n) in enumerate(cfg.runs()):
        k_op, k_ffn = jax.random.split(jax.random.fold_in(k_runs, i))
        params[run_key(i)] = {"op": group(k_op, op, n),
                              "ffn": group(k_ffn, ffn, n)}
    return params


# --------------------------------------------------------------------- #
# the operators
# --------------------------------------------------------------------- #

def short_conv_silu(x: jnp.ndarray, kernel: jnp.ndarray) -> jnp.ndarray:
    """``silu(conv(x))``: x ``[B, S, d]``, ``kernel`` ``[L, d]``, one
    causal filter a channel, ``conv(x)_t = sum_j kernel[j] * x[t - (L -
    1) + j]`` with ``x`` zero before position 0 of ITS row
    (``lfm2.gated_short_conv``'s shifted products, ungated), in float32
    whatever the operand's type: one fusion."""
    taps, S = kernel.shape[0], x.shape[1]
    with jax.named_scope("bps.conv.short"):
        padded = jnp.pad(x.astype(jnp.float32),
                         ((0, 0), (taps - 1, 0), (0, 0)))
        k = kernel.astype(jnp.float32)
        conv = sum(k[j] * jax.lax.slice_in_dim(padded, j, j + S, axis=1)
                   for j in range(taps))
        return jax.nn.silu(conv).astype(x.dtype)


def _unit_heads(x: jnp.ndarray, scale: float) -> jnp.ndarray:
    """Every head ``[..., d]`` of x at length ``scale``, in float32."""
    xf = x.astype(jnp.float32)
    return (xf * (scale * jax.lax.rsqrt(
        jnp.sum(xf * xf, axis=-1, keepdims=True) + L2_EPS))).astype(x.dtype)


def _f32_product(a, w, dt):
    """``a @ w`` with the operands in the compute type and the result
    float32 (a gate's logits)."""
    return jnp.matmul(a, w.astype(dt), preferred_element_type=jnp.float32)


def kda_gates(u, p, cfg: KimiConfig):
    """(g ``[B, S, H, d]``, beta ``[B, S, H]``), float32: the log decay a
    channel and the write strength a head."""
    B, S, _ = u.shape
    H, hd, dt = cfg.kda_heads, cfg.kda_head_dim, cfg.dtype
    low = (u @ p["f_down"].astype(dt))
    rate = jax.nn.softplus(_f32_product(low, p["f_up"], dt)
                           + p["dt_bias"].astype(jnp.float32))
    g = -jnp.exp(p["A_log"].astype(jnp.float32))[:, None] \
        * rate.reshape(B, S, H, hd)
    return g, jax.nn.sigmoid(_f32_product(u, p["w_beta"], dt))


def _kda(u, p, cfg: KimiConfig):
    """``Op(u)`` of a KDA layer; u [B, S, d], p the layer's ``op``
    leaves."""
    B, S, _ = u.shape
    H, hd, dt = cfg.kda_heads, cfg.kda_head_dim, cfg.dtype

    # float32 inside; the backward keeps the bf16 projection and runs
    # the filter and the norm again (``sdar.py``'s lesson)
    def filtered(x, kernel, scale):
        x = short_conv_silu(x, kernel).reshape(B, S, H, hd)
        return x if scale is None else _unit_heads(x, scale)

    filtered = jax.checkpoint(filtered, static_argnums=(2,))
    q = filtered(u @ p["wq"].astype(dt), p["conv_q"], hd ** -0.5)
    k = filtered(u @ p["wk"].astype(dt), p["conv_k"], 1.0)
    v = filtered(u @ p["wv"].astype(dt), p["conv_v"], None)
    # (the gates' float32 logits, [B, S, H d], are made again in the
    # backward from the low-rank bf16 product: 0.25 GiB a KDA layer of
    # the benchmark's cell; TPU compiler, PR 43)
    g, beta = jax.checkpoint(lambda u, p: kda_gates(u, p, cfg))(u, p)
    # the kernels sit under ``bps.attn.kda`` (ops/delta_rule.py)
    delta_rule.publish_sizes(delta_rule.CHUNK, H, hd, hd)
    o = delta_rule.delta_rule(q, k, v, g, beta)

    @jax.checkpoint
    def gated(o, low, g_up, g_bias, o_norm):
        gate = jax.nn.sigmoid(_f32_product(low, g_up, dt)
                              + g_bias.astype(jnp.float32))
        return (L._rmsnorm(o, o_norm, cfg.norm_eps).astype(jnp.float32)
                .reshape(B, S, H * hd) * gate).astype(dt)

    return gated(o, u @ p["g_down"].astype(dt), p["g_up"], p["g_bias"],
                 p["o_norm"]) @ p["wo"].astype(dt)


def _mla(u, p, cfg: KimiConfig):
    """Latent attention without a positional term; u [B, S, d]."""
    B, S, _ = u.shape
    nh, dt = cfg.n_heads, cfg.dtype
    dn, dv, rank = cfg.nope_dim, cfg.v_dim, cfg.kv_lora_rank
    q = (u @ p["wq"].astype(dt)).reshape(B, S, nh, dn + cfg.rope_dim)
    latent = u @ p["wkv_a"].astype(dt)
    c_kv = L._rmsnorm(latent[..., :rank], p["kv_norm"], cfg.norm_eps)
    kv = (c_kv @ p["wkv_b"].astype(dt)).reshape(B, S, nh, dn + dv)
    # the kernels sit under ``bps.attn.mla`` (ops/flash_attention.py);
    # ONE unrotated key head under all the query heads: [B, S, 1, dr]
    publish_walk_sizes(S, 1, ATTN_BLOCK, ATTN_BLOCK, latent=True)
    o = latent_attention(q[..., :dn], q[..., dn:], kv[..., :dn],
                         latent[..., None, rank:], kv[..., dn:], ATTN_BLOCK,
                         ATTN_BLOCK)
    return o.reshape(B, S, nh * dv) @ p["wo"].astype(dt)


_OPS = {KDA: _kda, MLA: _mla}


def _block(x, p, bias, cfg: KimiConfig, op: str, ffn: str, ep_axis):
    """One block with operator ``op`` and FFN kind ``ffn``; p: one
    layer's ``{"op", "ffn"}`` leaves, ``bias`` its row of the expert
    bias (None on a dense layer). Returns (x, the layer's additive
    statistics by counter name)."""
    B, S, _ = x.shape
    # the operator under a checkpoint of its own inside the block's:
    # the block's backward then holds the FFN's intermediates and the
    # operator's one after the other, not both (a KDA + sparse layer's
    # program 3.51 -> 2.72 GiB of temporaries at the benchmark's cell,
    # which is what lets two programs' temporaries fit beside the step's
    # state: TPU compiler, PR 43), for one more forward of the operator
    def operator(x, p_op):
        h = L._rmsnorm(x, p_op["norm"], cfg.norm_eps)
        return x + _OPS[op](h, p_op, cfg)

    x = (jax.checkpoint(operator) if cfg.remat else operator)(x, p["op"])
    stats = {}
    if op == KDA:
        stats = {"kda/chunk_steps": jnp.asarray(delta_rule.chunk_steps(
            B, S, cfg.kda_heads), jnp.int32),
            "kda/tokens": jnp.asarray(B * S, jnp.int32)}
    h = L._rmsnorm(x, p["ffn"]["norm"], cfg.norm_eps)
    if ffn == DENSE:
        return x + _dense_ffn(h, p["ffn"], cfg), stats
    out, st = moe.moe_layer(
        h, p["ffn"], cfg.top_k, cfg.dtype, first=cfg.first_expert,
        ep_axis=ep_axis, chunk=EXPERT_SLICE, router_dtype=cfg.router_dtype,
        score="sigmoid", select_bias=bias, norm_eps=GATE_SUM_EPS,
        scale=cfg.routed_scaling)
    return x + out, {**stats,
                     "moe/expert_load": st["load"],
                     "moe/dropped_pairs": st["dropped"],
                     "moe/compact_slices": st["compact_slices"],
                     "moe/full_slices": st["full_slices"],
                     "moe/kernel_slices": st["kernel_slices"],
                     "moe/kernel_tile_rows": st["kernel_tile_rows"],
                     "moe/bias_moved_pairs": st["bias_moved"]}


def _runs(cfg: KimiConfig, expert_bias, ep_axis) -> List[chain.Run]:
    """A ``chain.Run`` a stretch of like blocks. A sparse run reads its
    layers' rows of the bias and counts them into its rows of
    ``moe/expert_load`` ``[sparse layers, n_held]``, zero elsewhere: the
    runs' tables add up to the step's. The other counts are summed over
    a run's layers. (A link closes over nothing that is traced: the
    bias's rows are cut, and its gradient stopped, where the link
    runs.)"""
    shape = (cfg.n_sparse_layers, cfg.n_experts)
    if expert_bias is not None and expert_bias.shape != shape:
        raise ValueError(f"expert_bias {expert_bias.shape}: a row a sparse "
                         f"layer, {shape}")
    runs, layer = [], 0
    for i, (op, ffn, n) in enumerate(cfg.runs()):
        first = layer - min(cfg.n_dense_layers, cfg.n_layers)
        layer += n

        def block(p, x, _, *row, op=op, ffn=ffn):
            return _block(x, p, *(row or (None,)), cfg, op, ffn, ep_axis)

        runs.append(chain.Run(
            block, run_key(i), n, remat=cfg.remat, consts=lambda batch: (),
            stats=lambda stacked, first=first: moe.run_stats(
                stacked, first, cfg.n_sparse_layers),
            each=None if ffn == DENSE else
            (lambda batch, first=first, n=n: moe.bias_rows(
                expert_bias, cfg.n_experts, first, n))))
    return runs


# --------------------------------------------------------------------- #
# forward and loss
# --------------------------------------------------------------------- #

def _chain(cfg: KimiConfig, expert_bias, ep_axis) -> chain.Chain:
    def embed(p, _, batch):
        return p["embed"].astype(cfg.dtype)[L.split_batch(batch)[0]], {}

    def head(p, x, batch):
        # a link reads what it needs of the batch from ``batch``: the
        # cut step traces it on its own
        targets = L.split_batch(batch)[1]
        return head_nll(x, p["final_norm"], p["lm_head"], targets,
                        cfg) / targets.size, {}

    return chain.Chain((
        chain.Link(embed, "embed"), *_runs(cfg, expert_bias, ep_axis),
        chain.Link(head, ("final_norm", "lm_head"))))


def forward_hidden(params: Dict[str, Any], tokens: jnp.ndarray,
                   cfg: KimiConfig, expert_bias: Optional[jnp.ndarray] = None,
                   ep_axis: Optional[str] = None):
    """tokens [B, S] -> (the last block's output [B, S, d], BEFORE the
    final norm; the layers' statistics)."""
    links = _chain(cfg, expert_bias, ep_axis).links[:-1]
    batch = {"inputs": tokens, "targets": tokens}
    x, stats = None, {}
    for ln in links:
        x, st = ln(ln.pick(params), x, batch)
        chain.add_stats(stats, st)
    return x, stats


def loss_fn(params: Dict[str, Any], batch: Dict[str, jnp.ndarray],
            cfg: KimiConfig, expert_bias: Optional[jnp.ndarray] = None,
            ep_axis: Optional[str] = None):
    """(the mean next-token cross-entropy over the vocabulary held, the
    step's statistics: ``moe/*`` as ``joyai.loss_fn``'s, ``kda/chunk_steps``
    and ``kda/tokens``; all are counts, so they add up across data
    shards as the step makers need).
    batch: ``{"tokens"}`` (shifted here) or pre-shifted ``{"inputs",
    "targets"}``.

    Written as a chain (``ops/chain.py``): any step maker runs it as one
    program; ``make_ps_train_step`` cuts its backward at the links: the
    head's program, one a layer, the embedding's."""
    return _chain(cfg, expert_bias, ep_axis)(params, batch)
