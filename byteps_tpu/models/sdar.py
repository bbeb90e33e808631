"""SDAR-style block-diffusion sparse decoder (``model_type: sdar_moe``):
a sparse-expert decoder of which this device may hold a share, TRAINED
as a block-diffusion model: every row goes through every layer twice
over, a noised copy (tokens replaced by the mask token) followed by the
clean copy, under a mask of three parts, and the loss is the
cross-entropy of the clean token at the masked positions of the noised
copy only, with no shift.

A file of its own beside ``mellum.py`` because what it trains is another
objective under another mask: the batch is (clean ids, a noise mask, a
rate a block), ``2 L`` positions run where a row has ``L`` tokens, the
rotary position is not the index in the sequence, the head runs over
half the positions and the loss has weights and a denominator of its
own. What is shared is imported: ``mellum``'s parameter tree and its
sparse half of a block (``moe.moe_layer``), ``lfm2``'s RMSNorm of each
q and k head before the rotary, ``llama``'s RMSNorm and rotary table,
``ops.flash_attention`` (its block-diffusion mask).

Equations (``x`` is ``[positions, dim]``), from the published ``config``
and, where it has no key, the family's modelling code:

- block ``l``: ``h = x + Attn(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``;
  no biases, untied embedding and head, a final RMSNorm;
- the ``2 L`` positions of a row: ``p < L`` noised, ``p >= L`` clean, at
  ``n(p) = p mod L`` of the row, in block ``b(p) = n(p) // block_length``;
  the noised half holds ``mask_token_id`` where the batch's noise mask is
  set and the clean id elsewhere, the clean half the clean ids;
- ``Attn``: ``n_heads`` query and ``n_kv_heads`` key/value heads of
  ``head_dim``; q and k RMS-normalised over each head (one weight
  ``[head_dim]`` each, shared by the heads), then the plain rotary at
  ``n(p)``, then ``softmax(q k^T / sqrt(head_dim) + M) v``; ``M`` lets
  ``(p, r)`` through where both are noised and ``b(p) == b(r)``, or ``p``
  is noised, ``r`` clean and ``b(r) < b(p)``, or both are clean and
  ``b(r) <= b(p)``;
- ``MoE``: ``moe.moe_layer`` (float32 softmax over all experts, top-k,
  renormalised; the held experts' SwiGLU terms summed);
- loss of a shard of rows: ``sum_rows sum_{i < L, masked} (1 / t_{b(i)})
  CE(logits_i, x0_i) / (rows L)`` with the head over the noised half
  only; ``t_b`` is the rate block ``b`` was noised at.

``loss_fn`` returns ``(loss, stats)``: the ``moe/*`` statistics and
``diffusion/masked_tokens`` leave the chip beside the loss
(``jax/train.py _loss_and_stats``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ..ops import chain
from ..ops.flash_attention import flash_attention, publish_walk_sizes
from . import llama as L
from . import mellum
from .lfm2 import _head_norm_rope
from .mellum import ATTN_BLOCK

# The program's own, no part of the model: layers a step of the layer
# scan. A backward scan keeps every layer's gradients in the loop's carry
# and copies them out at its end (a second copy of 4 layers' 1.41 GiB:
# TPU compiler, PR 36); a depth that one step holds has no loop and
# writes them where they go.
LAYER_UNROLL = 4


@dataclasses.dataclass(frozen=True)
class SDARConfig:
    vocab_size: int = 151936         # rows of the vocabulary held here
    dim: int = 2048
    n_layers: int = 48
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    n_experts: int = 128             # the router's outputs
    n_experts_held: int = 128        # expert leaves' leading dim
    first_expert: int = 0            # the first held expert's index
    top_k: int = 8
    expert_hidden: int = 768
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-6
    block_length: int = 4            # tokens a diffusion block
    mask_token_id: Optional[int] = None   # none: the last row held
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    router_dtype: Any = jnp.float32  # float32, outside a precision control
    remat: bool = True

    @property
    def mask_id(self) -> int:
        return self.vocab_size - 1 if self.mask_token_id is None \
            else self.mask_token_id

    @staticmethod
    def tiny(vocab_size: int = 64) -> "SDARConfig":
        """Test-scale: a share of the experts, blocks of 4."""
        return SDARConfig(
            vocab_size=vocab_size, dim=32, n_layers=2, n_heads=4,
            n_kv_heads=2, head_dim=16, n_experts=8, n_experts_held=4,
            top_k=2, expert_hidden=24, remat=False, dtype=jnp.float32)


def init_params(rng: jax.Array, cfg: SDARConfig) -> Dict[str, Any]:
    """``mellum``'s tree (normal(0, 0.02) weights, norms at one, layers
    stacked) and the two head norms of every layer."""
    params = mellum.init_params(rng, cfg)
    ones = jnp.ones((cfg.n_layers, cfg.head_dim), cfg.param_dtype)
    params["blocks"].update(q_norm=ones, k_norm=ones)
    return params


def _block(x, p, rope, cfg: SDARConfig, ep_axis):
    """One decoder block over a batch of ``[noised ; clean]`` rows; p:
    one layer's params. Returns (x, the layer's additive statistics by
    counter name)."""
    B, S, d = x.shape
    nh, nkv, hd, dt = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.dtype
    cos, sin = rope
    h = L._rmsnorm(x, p["attn_norm"], cfg.norm_eps)
    q = (h @ p["wq"].astype(dt)).reshape(B, S, nh, hd)
    k = (h @ p["wk"].astype(dt)).reshape(B, S, nkv, hd)
    v = (h @ p["wv"].astype(dt)).reshape(B, S, nkv, hd)

    # float32 inside; the backward keeps the bf16 operand and runs the
    # chain again (the float32 copies of 2 x 16,384 positions' q cost
    # 1.4 GiB at the block's peak: TPU compiler, PR 36)
    @jax.checkpoint
    def head_norm_rope(x, w):
        return _head_norm_rope(x, w, cos, sin, cfg.norm_eps).astype(dt)

    q, k = head_norm_rope(q, p["q_norm"]), head_norm_rope(k, p["k_norm"])
    # the kernels sit under ``bps.attn.blockdiff`` (ops/flash_attention.py)
    publish_walk_sizes(S, nh // nkv, ATTN_BLOCK, ATTN_BLOCK,
                       diffusion_block=cfg.block_length)
    attn = flash_attention(q, k, v, True, ATTN_BLOCK, ATTN_BLOCK, None,
                           cfg.block_length)
    x = x + attn.reshape(B, S, nh * hd) @ p["wo"].astype(dt)
    return mellum.moe_sublayer(x, p, cfg, ep_axis)


def _rope(cfg: SDARConfig, half: int):
    """Plain ``theta^(-2d / head_dim)`` at ``n(p)``: llama's table of a
    row's positions, once for each copy."""
    return tuple(jnp.concatenate([t, t]) for t in L.rope_cache(cfg, half))


def _layers(cfg: SDARConfig, ep_axis) -> chain.Run:
    """The run of ``n_layers`` blocks over ``params["blocks"]``: a scan,
    so that a kernel's instruction carries its scope's name alone
    (PERF.md section 3). Its statistics: the load ``[layers, n_held]``,
    the other counts summed over the layers."""
    return chain.Run(
        lambda p, x, rope: _block(x, p, rope, cfg, ep_axis), "blocks",
        cfg.n_layers, remat=cfg.remat, unroll=LAYER_UNROLL,
        consts=lambda batch: _rope(cfg, batch["tokens"].shape[1]),
        stats=lambda stats: {name: v if v.ndim == 2 else jnp.sum(v)
                             for name, v in stats.items()})


def forward_hidden(params: Dict[str, Any], tokens: jnp.ndarray,
                   cfg: SDARConfig, ep_axis: Optional[str] = None):
    """tokens [B, 2 L], each row its noised copy then its clean copy ->
    (final normed hidden [B, 2 L, d], the step's statistics)."""
    layers = _layers(cfg, ep_axis)
    x, stats = layers.scan(
        params["blocks"], params["embed"].astype(cfg.dtype)[tokens],
        _rope(cfg, tokens.shape[1] // 2))
    return (L._rmsnorm(x, params["final_norm"], cfg.norm_eps),
            layers.stats(stats))


def loss_fn(params: Dict[str, Any], batch: Dict[str, jnp.ndarray],
            cfg: SDARConfig, ep_axis: Optional[str] = None):
    """(the block-diffusion loss over the vocabulary held, the step's
    statistics: ``mellum.loss_fn``'s ``moe/*`` and
    ``diffusion/masked_tokens``, the positions the loss was taken at;
    all are counts, so they add up across data shards as the step makers
    need).
    batch: ``tokens`` [rows, L] clean ids, ``noise_mask`` [rows, L] bool
    (the tokens replaced by the mask token in the noised copy) and
    ``rates`` [rows, L / block_length] (the rate each block was noised
    at: a masked position's loss is weighed by its inverse).

    Written as a chain (``ops/chain.py``): the embedding, the run of
    blocks, then the final norm, the head and the loss. Any step maker
    runs it as the one program it was; ``make_ps_train_step`` cuts its
    backward at the links."""
    rows, n = batch["tokens"].shape
    if n % cfg.block_length \
            or batch["rates"].shape != (rows, n // cfg.block_length):
        raise ValueError(
            f"rows of {n} tokens in blocks of {cfg.block_length} need "
            f"rates [{rows}, {n // cfg.block_length}], got "
            f"{batch['rates'].shape}")

    def embed(p, _, batch):
        clean = batch["tokens"]
        noised = jnp.where(batch["noise_mask"], cfg.mask_id, clean)
        tokens = jnp.concatenate([noised, clean], axis=1)
        return p["embed"].astype(cfg.dtype)[tokens], {}

    def head(p, x, batch):
        # a link reads what it needs of the batch from ``batch``: the
        # cut step traces it on its own
        clean, noise = batch["tokens"], batch["noise_mask"]
        rows, n = clean.shape
        x = L._rmsnorm(x, p["final_norm"], cfg.norm_eps)
        # the head over the noised half only; no shift: position i
        # predicts the clean token AT i
        logits = (x[:, :n] @ p["lm_head"].astype(cfg.dtype)
                  ).astype(jnp.float32)
        nll = jax.scipy.special.logsumexp(logits, axis=-1) \
            - jnp.take_along_axis(logits, clean[..., None], axis=-1)[..., 0]
        weight = noise / jnp.repeat(batch["rates"].astype(jnp.float32),
                                    cfg.block_length, axis=1)
        return jnp.sum(weight * nll) / (rows * n), {
            "diffusion/masked_tokens": jnp.sum(noise, dtype=jnp.int32)}

    return chain.Chain((
        chain.Link(embed, "embed"), _layers(cfg, ep_axis),
        chain.Link(head, ("final_norm", "lm_head"))))(params, batch)
