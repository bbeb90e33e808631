"""Mellum-style sparse decoder (``model_type: mellum``): sliding-window
and full attention mixed by a per-layer pattern, each kind with its own
rotary table (plain on sliding layers, YaRN on full ones), grouped-query
heads whose size is a number of its own (not ``dim // n_heads``), and a
sparse-expert FFN in every layer of which this device may hold a share.

A file of its own beside ``llama.py`` and ``moe.py`` because the block
differs from both in every sublayer's wiring (two rope tables chosen by
layer kind, a window, a head size detached from the hidden size, held
experts); what is shared is imported: ``llama``'s RMSNorm, rotary
application and cross-entropy, ``moe.moe_layer`` (the held-experts
layer), ``ops.flash_attention`` (window and full).

Equations (``x`` is ``[tokens, dim]``), from the published ``config``:

- block ``l``: ``h = x + Attn_l(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``;
  no biases, untied embedding and head, final RMSNorm, next-token
  cross-entropy over the rows of the vocabulary held here;
- ``Attn_l``: ``n_heads`` query heads and ``n_kv_heads`` key/value heads
  of ``head_dim``, rotary on q and k, ``softmax(q k^T / sqrt(head_dim) +
  mask_l) v``; ``mask_l`` is causal for ``full_attention`` and causal with
  ``i - j < sliding_window`` for ``sliding_attention``;
- rotary: ``rope_tables``;
- ``MoE``: ``moe.moe_layer`` (float32 softmax over all experts, top-k,
  renormalised; the held experts' SwiGLU terms summed).

``loss_fn`` returns ``(loss, stats)``: the ``moe/*`` statistics leave the
chip beside the loss (``jax/train.py _loss_and_stats``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import chain
from ..ops.flash_attention import flash_attention, publish_walk_sizes
from . import llama as L
from . import moe

SLIDING, FULL = "sliding_attention", "full_attention"

# The program's own tiles, no part of the model: flash attention's query
# and key blocks (clamped to the sequence), and the tokens the expert
# layer sorts and multiplies at a time (its pair buffer has ``top_k``
# rows a token; more tokens than a slice must divide into slices).
ATTN_BLOCK = 512
EXPERT_SLICE = 8192


@dataclasses.dataclass(frozen=True)
class MellumConfig:
    vocab_size: int = 98304          # rows of the vocabulary held here
    dim: int = 2304
    n_layers: int = 28
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    n_experts: int = 64              # the router's outputs
    n_experts_held: int = 64         # expert leaves' leading dim
    first_expert: int = 0            # the first held expert's index
    top_k: int = 8
    expert_hidden: int = 896
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL) * 7
    sliding_window: int = 1024
    rope_theta: float = 500000.0
    # YaRN, full-attention layers only
    yarn_factor: float = 16.0
    yarn_original_len: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.2772588722239782
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    router_dtype: Any = jnp.float32  # float32, outside a precision control
    remat: bool = True

    @staticmethod
    def tiny(vocab_size: int = 64, seq: int = 32) -> "MellumConfig":
        """Test-scale: a window shorter than the sequence, a period of
        two kinds, a share of the experts."""
        return MellumConfig(
            vocab_size=vocab_size, dim=32, n_layers=4, n_heads=4,
            n_kv_heads=2, head_dim=16, n_experts=8, n_experts_held=4,
            top_k=2, expert_hidden=24,
            layer_types=(SLIDING, FULL, SLIDING, FULL),
            sliding_window=seq // 4, yarn_original_len=seq // 2,
            yarn_factor=4.0, remat=False, dtype=jnp.float32)


def init_params(rng: jax.Array, cfg: MellumConfig) -> Dict[str, Any]:
    """Normal(0, 0.02) weights, norms at one, layers stacked."""
    d, h, E, H = cfg.dim, cfg.expert_hidden, cfg.n_experts, \
        cfg.n_experts_held
    q, kv, Ln = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim, \
        cfg.n_layers
    ks = jax.random.split(rng, 10)

    def dense(key, shape):
        return jax.random.normal(key, shape, cfg.param_dtype) * 0.02

    def ones(shape):
        return jnp.ones(shape, cfg.param_dtype)

    return {
        "embed": dense(ks[0], (cfg.vocab_size, d)),
        "blocks": {
            "attn_norm": ones((Ln, d)),
            "wq": dense(ks[1], (Ln, d, q)), "wk": dense(ks[2], (Ln, d, kv)),
            "wv": dense(ks[3], (Ln, d, kv)), "wo": dense(ks[4], (Ln, q, d)),
            "mlp_norm": ones((Ln, d)),
            "router": dense(ks[5], (Ln, d, E)),
            "w_gate": dense(ks[6], (Ln, H, d, h)),
            "w_up": dense(ks[7], (Ln, H, d, h)),
            "w_down": dense(ks[8], (Ln, H, h, d)),
        },
        "final_norm": ones((d,)),
        "lm_head": dense(ks[9], (d, cfg.vocab_size)),
    }


# --------------------------------------------------------------------- #
# rotary tables
# --------------------------------------------------------------------- #

def _plain_inv_freq(cfg: MellumConfig) -> np.ndarray:
    return cfg.rope_theta ** (
        -np.arange(0, cfg.head_dim, 2, dtype=np.float64) / cfg.head_dim)


def yarn_inv_freq(cfg: MellumConfig) -> np.ndarray:
    """YaRN's frequencies: below the correction dimension of
    ``beta_fast`` the plain ones (extrapolation), above that of
    ``beta_slow`` the plain ones over ``factor`` (interpolation), a
    linear ramp between; the correction dimensions are floored and
    ceiled as the published implementation does."""
    hd, base = cfg.head_dim, cfg.rope_theta
    plain = _plain_inv_freq(cfg)

    def correction_dim(rotations):
        return hd * math.log(cfg.yarn_original_len
                             / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.yarn_beta_slow)), hd - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(hd // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    return plain / cfg.yarn_factor * (1.0 - keep) + plain * keep


def rope_tables(cfg: MellumConfig, seq_len: int) -> Dict[str, tuple]:
    """(cos, sin) [S, head_dim / 2] float32 per layer kind: plain
    ``theta^(-2d / head_dim)`` on sliding layers; YaRN on full layers,
    cos and sin scaled by ``attention_factor``."""
    t = np.arange(seq_len, dtype=np.float64)
    out = {}
    for kind, inv_freq, factor in (
            (SLIDING, _plain_inv_freq(cfg), 1.0),
            (FULL, yarn_inv_freq(cfg), cfg.yarn_attention_factor)):
        freqs = np.outer(t, inv_freq)
        out[kind] = (jnp.asarray(np.cos(freqs) * factor, jnp.float32),
                     jnp.asarray(np.sin(freqs) * factor, jnp.float32))
    return out


def _rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """Rotation in float32 (positions run to thousands of radians),
    result in the activations' type."""
    return L.apply_rope(x.astype(jnp.float32), cos, sin).astype(x.dtype)


# --------------------------------------------------------------------- #
# forward
# --------------------------------------------------------------------- #

def _block(x, p, ropes, cfg: MellumConfig, kind: str, ep_axis):
    """One decoder block of layer kind ``kind``; p: one layer's params.
    Returns (x, the layer's additive statistics by counter name)."""
    B, S, d = x.shape
    nh, nkv, hd, dt = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.dtype
    cos, sin = ropes[kind]
    h = L._rmsnorm(x, p["attn_norm"], cfg.norm_eps)
    q = _rope((h @ p["wq"].astype(dt)).reshape(B, S, nh, hd), cos, sin)
    k = _rope((h @ p["wk"].astype(dt)).reshape(B, S, nkv, hd), cos, sin)
    v = (h @ p["wv"].astype(dt)).reshape(B, S, nkv, hd)
    # the kernels sit under ``bps.attn.window`` / ``bps.attn.full``
    # (ops/flash_attention.py ``_scope``)
    window = cfg.sliding_window if kind == SLIDING else None
    publish_walk_sizes(S, nh // nkv, ATTN_BLOCK, ATTN_BLOCK, window)
    attn = flash_attention(q, k, v, True, ATTN_BLOCK, ATTN_BLOCK, window)
    x = x + attn.reshape(B, S, nh * hd) @ p["wo"].astype(dt)
    return moe_sublayer(x, p, cfg, ep_axis)


def moe_sublayer(x, p, cfg, ep_axis):
    """``x + MoE(RMSNorm(x))`` of one layer's params ``p``, and the
    layer's additive statistics by counter name (``models/sdar.py``'s
    block ends in it too)."""
    h = L._rmsnorm(x, p["mlp_norm"], cfg.norm_eps)
    ffn, st = moe.moe_layer(h, p, cfg.top_k, cfg.dtype,
                            first=cfg.first_expert, ep_axis=ep_axis,
                            chunk=EXPERT_SLICE,
                            router_dtype=cfg.router_dtype)
    return x + ffn, {"moe/expert_load": st["load"],
                     "moe/dropped_pairs": st["dropped"],
                     "moe/compact_slices": st["compact_slices"],
                     "moe/full_slices": st["full_slices"],
                     "moe/kernel_slices": st["kernel_slices"],
                     "moe/kernel_tile_rows": st["kernel_tile_rows"]}


# the layer pattern's shortest period, where a run can use it
_period = chain.period


def _layers(cfg: MellumConfig, ep_axis) -> chain.Run:
    """The run of ``n_layers`` blocks over ``params["blocks"]``, layer
    ``j`` of kind ``layer_types[j]`` (its mask, its rotary table): a
    scan over the periods of the pattern, one block a layer of a period
    in its body, compiled once however many periods the model is deep.
    Its statistics: the load ``[layers, n_held]``, the other counts
    summed over the layers."""
    return chain.Run(
        lambda p, x, ropes, kind: _block(x, p, ropes, cfg, kind, ep_axis),
        "blocks", cfg.n_layers, remat=cfg.remat,
        kinds=tuple(cfg.layer_types[:cfg.n_layers]),
        consts=lambda batch: rope_tables(
            cfg, L.split_batch(batch)[0].shape[1]),
        stats=lambda stats: {name: v if v.ndim == 2 else jnp.sum(v)
                             for name, v in stats.items()})


def forward_hidden(params: Dict[str, Any], tokens: jnp.ndarray,
                   cfg: MellumConfig, ep_axis: Optional[str] = None):
    """tokens [B, S] -> (final normed hidden [B, S, d], the step's
    statistics: the load [layers, n_held], the other counts summed over
    the layers)."""
    layers = _layers(cfg, ep_axis)
    x, stats = layers.scan(
        params["blocks"], params["embed"].astype(cfg.dtype)[tokens],
        rope_tables(cfg, tokens.shape[1]))
    return (L._rmsnorm(x, params["final_norm"], cfg.norm_eps),
            layers.stats(stats))


def loss_fn(params: Dict[str, Any], batch: Dict[str, jnp.ndarray],
            cfg: MellumConfig, ep_axis: Optional[str] = None):
    """(next-token cross-entropy over the vocabulary held, the step's
    statistics: ``moe/expert_load`` [layers, n_held], the pairs each
    held expert computed, ``moe/dropped_pairs``, and the expert slices
    by the sorted buffer they ran on, ``moe/compact_slices`` and
    ``moe/full_slices``; all are counts, so they add up across data
    shards as the step makers need).
    batch: ``{"tokens"}`` (shifted here) or pre-shifted ``{"inputs",
    "targets"}``.

    Written as a chain (``ops/chain.py``): the embedding, the run of
    blocks, then the final norm, the head and the loss. Any step maker
    runs it as the one program it was; ``make_ps_train_step`` cuts its
    backward at the links, the sliding layers through one program and
    the full ones through another."""
    def embed(p, _, batch):
        return p["embed"].astype(cfg.dtype)[L.split_batch(batch)[0]], {}

    def head(p, x, batch):
        x = L._rmsnorm(x, p["final_norm"], cfg.norm_eps)
        logits = x @ p["lm_head"].astype(cfg.dtype)
        return L.next_token_xent(logits, L.split_batch(batch)[1]), {}

    return chain.Chain((
        chain.Link(embed, "embed"), _layers(cfg, ep_axis),
        chain.Link(head, ("final_norm", "lm_head"))))(params, batch)
