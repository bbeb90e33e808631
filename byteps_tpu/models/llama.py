"""Llama-3-style decoder-only transformer, TPU-first.

This is the framework's flagship model (BASELINE.json config 4: Llama-3-8B
with compressed push_pull). The reference framework has no model zoo of its
own — its models come from the example/ scripts — so this module is
green-field TPU design: pure-functional params pytree (composes directly with
shard_map/pjit and optax), bfloat16 activations for the MXU, RoPE, grouped-
query attention, RMSNorm, SwiGLU, and optional ring attention over a
sequence-parallel mesh axis (byteps_tpu.parallel.ring_attention).

Tensor-parallel sharding rules (applied via NamedSharding in
byteps_tpu.parallel.sharding): attention QKV/O and MLP in/out projections
shard over the ``tp`` axis in the Megatron pattern (column- then row-
parallel), embeddings shard over vocab.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    hidden_dim: int = 14336          # SwiGLU inner dim
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16        # activation/compute dtype (MXU-friendly)
    param_dtype: Any = jnp.float32   # master weights
    remat: bool = True               # jax.checkpoint each block
    # jax.checkpoint_policies name, e.g. "dots_with_no_batch_dims_saveable"
    # (save projection outputs, recompute elementwise + attention einsums);
    # None = full recompute. The policy trades activation memory back
    # for recompute at larger scale.
    remat_policy: Optional[str] = None
    # > 0: loss_fn computes the cross entropy per vocab chunk under a
    # nothing-saveable checkpoint, so the [B, S, V] logits are never
    # resident at once — trades an extra lm_head matmul in bwd for the
    # logits' HBM round-trips. Vocab must divide evenly or the dense
    # path is used.
    xent_chunks: int = 0

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @staticmethod
    def llama3_8b() -> "LlamaConfig":
        return LlamaConfig()

    @staticmethod
    def tiny(vocab_size: int = 256, seq: int = 128) -> "LlamaConfig":
        """Test-scale config: same code path, toy sizes."""
        return LlamaConfig(vocab_size=vocab_size, dim=64, n_layers=2,
                           n_heads=4, n_kv_heads=2, hidden_dim=128,
                           max_seq_len=seq, remat=False)

    @staticmethod
    def small(vocab_size: int = 32000) -> "LlamaConfig":
        """~125M benchmark config that fits one chip comfortably.

        head_dim = 128 (6 heads), not the GPT-2-ish 64 (12 heads): the
        TPU vector registers are 128 lanes wide, so hd=64 attention wastes
        half of every lane-dim tile and measured 40% slower end-to-end on
        v5e; parameter shapes and FLOPs are identical either way (wq is
        (768, 768) and kv (768, 256) under both layouts). CAUTION: because
        the shapes are identical, a checkpoint trained under the previous
        12-head layout restores without error but is misinterpreted —
        retrain or restore with an explicit LlamaConfig(n_heads=12,
        n_kv_heads=4)."""
        return LlamaConfig(vocab_size=vocab_size, dim=768, n_layers=12,
                           n_heads=6, n_kv_heads=2, hidden_dim=2048,
                           max_seq_len=2048)


# --------------------------------------------------------------------- #
# init
# --------------------------------------------------------------------- #

def init_params(rng: jax.Array, cfg: LlamaConfig) -> Dict[str, Any]:
    """Initialize the parameter pytree. Layer params are stacked on a leading
    [n_layers] dim so the whole decoder runs as one lax.scan — one compiled
    block instead of n_layers copies (XLA-friendly, fast compiles)."""
    k_emb, k_blk, k_out = jax.random.split(rng, 3)
    d, h = cfg.dim, cfg.hidden_dim
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    L = cfg.n_layers

    def norm_init(*shape):
        return jnp.ones(shape, cfg.param_dtype)

    def dense_init(key, shape, scale=None):
        fan_in = shape[-2] if len(shape) >= 2 else shape[0]
        scale = scale if scale is not None else 1.0 / np.sqrt(fan_in)
        return (jax.random.normal(key, shape, cfg.param_dtype) * scale)

    ks = jax.random.split(k_blk, 7)
    block = {
        "attn_norm": norm_init(L, d),
        "wq": dense_init(ks[0], (L, d, nh * hd)),
        "wk": dense_init(ks[1], (L, d, nkv * hd)),
        "wv": dense_init(ks[2], (L, d, nkv * hd)),
        "wo": dense_init(ks[3], (L, nh * hd, d)),
        "mlp_norm": norm_init(L, d),
        "w_gate": dense_init(ks[4], (L, d, h)),
        "w_up": dense_init(ks[5], (L, d, h)),
        "w_down": dense_init(ks[6], (L, h, d)),
    }
    return {
        "embed": dense_init(k_emb, (cfg.vocab_size, d), scale=0.02),
        "blocks": block,
        "final_norm": norm_init(d),
        "lm_head": dense_init(k_out, (d, cfg.vocab_size)),
    }


def param_count(params: Dict[str, Any]) -> int:
    return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))


# --------------------------------------------------------------------- #
# forward
# --------------------------------------------------------------------- #

def _rmsnorm(x: jnp.ndarray, w: jnp.ndarray, eps: float) -> jnp.ndarray:
    # compute in fp32 for stability, cast back
    xf = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * rms).astype(x.dtype) * w.astype(x.dtype)


def rope_cache(cfg: LlamaConfig, seq_len: int,
               offset: int = 0) -> tuple:
    hd = cfg.head_dim
    inv_freq = 1.0 / (cfg.rope_theta ** (np.arange(0, hd, 2) / hd))
    t = np.arange(offset, offset + seq_len)
    freqs = np.outer(t, inv_freq)                      # [S, hd/2]
    return (jnp.asarray(np.cos(freqs), jnp.float32),
            jnp.asarray(np.sin(freqs), jnp.float32))


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """x: [B, S, H, hd]; rotate pairs (even, odd interleave as halves)."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    cos = cos[None, :, None, :].astype(x.dtype)
    sin = sin[None, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v, cfg: LlamaConfig, attn_impl=None):
    """Causal GQA attention. q:[B,S,nh,hd] k,v:[B,S,nkv,hd].

    ``attn_impl``: optional override, e.g. a ring-attention callable bound to
    a sequence-parallel axis (parallel/ring_attention.py).
    """
    if attn_impl is not None:
        return attn_impl(q, k, v)
    B, S, nh, hd = q.shape
    groups = nh // k.shape[2]
    k = jnp.repeat(k, groups, axis=2)
    v = jnp.repeat(v, groups, axis=2)
    scale = 1.0 / np.sqrt(hd)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    mask = jnp.tril(jnp.ones((S, S), bool))
    logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def attn_sublayer(x, p, cos, sin, cfg: LlamaConfig, attn_impl=None):
    """Pre-norm attention sublayer with residual: x + Attn(RMSNorm(x)).
    Shared by the dense block here and the MoE block (models/moe.py)."""
    B, S, d = x.shape
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = cfg.dtype

    h = _rmsnorm(x, p["attn_norm"], cfg.norm_eps)
    q = (h @ p["wq"].astype(dt)).reshape(B, S, nh, hd)
    k = (h @ p["wk"].astype(dt)).reshape(B, S, nkv, hd)
    v = (h @ p["wv"].astype(dt)).reshape(B, S, nkv, hd)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    attn = _attention(q, k, v, cfg, attn_impl)
    return x + attn.reshape(B, S, nh * hd) @ p["wo"].astype(dt)


def next_token_xent(logits: jnp.ndarray, targets: jnp.ndarray) -> jnp.ndarray:
    """Mean next-token cross-entropy. logits [B, S, V] (any float dtype —
    math runs in fp32), targets [B, S]. The single loss definition shared
    by llama/moe/pp paths.

    Uses the logsumexp form rather than log_softmax: log_softmax would
    materialize a full [B, S, V] fp32 normalized array only to gather one
    element per token, a pure HBM-bandwidth tax; logsumexp reduces to
    [B, S] and the fp32 cast fuses into the reduction."""
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    picked = jnp.take_along_axis(lf, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


def chunked_next_token_xent(hidden: jnp.ndarray, lm_head: jnp.ndarray,
                            targets: jnp.ndarray,
                            n_chunks: int) -> jnp.ndarray:
    """Mean next-token cross-entropy WITHOUT materializing [B, S, V]:
    per vocab chunk, project + logsumexp + pick under a nothing-saveable
    checkpoint, then combine the per-chunk partials (logsumexp over
    chunks; the picked logit lives in exactly one chunk, -inf in the
    rest, so a max recovers it). Trades one extra lm_head matmul in the
    backward for the logits' HBM round-trips. Identical math to
    next_token_xent (a test asserts closeness)."""
    import functools

    V = lm_head.shape[1]
    Vc = V // n_chunks

    @functools.partial(jax.checkpoint,
                       policy=jax.checkpoint_policies.nothing_saveable)
    def chunk_lse_pick(h, Wc, base):
        logits = (h @ Wc.astype(h.dtype)).astype(jnp.float32)  # [B,S,Vc]
        lse_c = jax.scipy.special.logsumexp(logits, -1)
        inrange = (targets >= base) & (targets < base + Vc)
        loc = jnp.clip(targets - base, 0, Vc - 1)
        picked_c = jnp.where(
            inrange,
            jnp.take_along_axis(logits, loc[..., None], -1)[..., 0],
            -jnp.inf)
        return lse_c, picked_c

    Wr = lm_head.reshape(lm_head.shape[0], n_chunks, Vc)
    lses, picks = [], []
    for c in range(n_chunks):
        lse_c, picked_c = chunk_lse_pick(hidden, Wr[:, c], c * Vc)
        lses.append(lse_c)
        picks.append(picked_c)
    lse = jax.scipy.special.logsumexp(jnp.stack(lses, 0), 0)
    picked = jnp.max(jnp.stack(picks, 0), 0)
    return jnp.mean(lse - picked)


def split_batch(batch: Dict[str, jnp.ndarray]) -> tuple:
    """(inputs, targets) from either a pre-shifted {'inputs','targets'}
    batch or a raw {'tokens'} batch (shifted here)."""
    if "inputs" in batch:
        return batch["inputs"], batch["targets"]
    return batch["tokens"][:, :-1], batch["tokens"][:, 1:]


def _block(x, p, cos, sin, cfg: LlamaConfig, attn_impl=None):
    """One decoder block; p holds this layer's (unstacked) params."""
    dt = cfg.dtype
    x = attn_sublayer(x, p, cos, sin, cfg, attn_impl)
    h = _rmsnorm(x, p["mlp_norm"], cfg.norm_eps)
    gate = jax.nn.silu(h @ p["w_gate"].astype(dt))
    up = h @ p["w_up"].astype(dt)
    x = x + (gate * up) @ p["w_down"].astype(dt)
    return x


def forward_hidden(params: Dict[str, Any], tokens: jnp.ndarray,
                   cfg: LlamaConfig, attn_impl=None,
                   sp_axis: Optional[str] = None) -> jnp.ndarray:
    """The trunk: tokens [B, S] int32 -> final normed hidden [B, S, d]
    (cfg.dtype). ``forward`` adds the lm_head projection; chunked-vocab
    consumers (chunked_next_token_xent) project per chunk themselves.

    ``sp_axis``: when running inside shard_map with the sequence sharded
    over that mesh axis (ring attention), RoPE must use *global* positions:
    the cache covers S * axis_size positions and each device slices its
    chunk at axis_index * S.
    """
    B, S = tokens.shape
    x = params["embed"].astype(cfg.dtype)[tokens]
    if sp_axis is not None and attn_impl is None:
        # local dense attention would silently never cross shard
        # boundaries; ring attention over the same axis is the only
        # correct default here
        from ..parallel.ring_attention import make_ring_attn
        attn_impl = make_ring_attn(axis=sp_axis, causal=True)
    if sp_axis is not None:
        n_sp = jax.lax.axis_size(sp_axis)
        cos_full, sin_full = rope_cache(cfg, S * n_sp)
        start = jax.lax.axis_index(sp_axis) * S
        cos = jax.lax.dynamic_slice_in_dim(cos_full, start, S, axis=0)
        sin = jax.lax.dynamic_slice_in_dim(sin_full, start, S, axis=0)
    else:
        cos, sin = rope_cache(cfg, S)

    blk = params["blocks"]

    def body(x, layer_params):
        fn = _block
        if cfg.remat:
            policy = (getattr(jax.checkpoint_policies, cfg.remat_policy)
                      if cfg.remat_policy else None)
            fn = jax.checkpoint(_block, static_argnums=(4, 5),
                                policy=policy)
        # attn_impl is closed over (static); layer params come from scan
        return fn(x, layer_params, cos, sin, cfg, attn_impl), None

    x, _ = jax.lax.scan(body, x, blk)
    return _rmsnorm(x, params["final_norm"], cfg.norm_eps)


def forward(params: Dict[str, Any], tokens: jnp.ndarray, cfg: LlamaConfig,
            attn_impl=None, sp_axis: Optional[str] = None) -> jnp.ndarray:
    """tokens [B, S] int32 -> logits [B, S, vocab] (cfg.dtype). See
    forward_hidden for the trunk and the sp_axis contract."""
    x = forward_hidden(params, tokens, cfg, attn_impl, sp_axis)
    # logits stay in cfg.dtype: materializing [B, S, V] fp32 costs ~2x the
    # HBM traffic of the whole lm_head matmul; consumers cast into their
    # fp32 reductions (next_token_xent), where the cast fuses
    return x @ params["lm_head"].astype(cfg.dtype)


def forward_pp(params: Dict[str, Any], tokens: jnp.ndarray, cfg: LlamaConfig,
               *, num_microbatches: int, pp_axis: str = "pp") -> jnp.ndarray:
    """Pipeline-parallel forward. Call INSIDE shard_map with
    ``params['blocks']`` leaves sharded on their leading [n_layers] dim over
    ``pp_axis`` (each stage holds n_layers/P layers) and everything else
    replicated. Returns logits valid ONLY on the last stage (zeros
    elsewhere); see loss_fn_pp for the masked-psum loss."""
    from ..parallel.pipeline import pipeline_forward

    B, S = tokens.shape
    cos, sin = rope_cache(cfg, S)
    x = params["embed"].astype(cfg.dtype)[tokens]

    def layer_fn(h, p_layer):
        return _block(h, p_layer, cos, sin, cfg, None)

    out = pipeline_forward(x, params["blocks"], layer_fn,
                           num_microbatches=num_microbatches, axis=pp_axis,
                           remat=cfg.remat)
    h = _rmsnorm(out, params["final_norm"], cfg.norm_eps)
    return h @ params["lm_head"].astype(cfg.dtype)


def loss_fn_pp(params: Dict[str, Any], batch: Dict[str, jnp.ndarray],
               cfg: LlamaConfig, *, num_microbatches: int,
               pp_axis: str = "pp") -> jnp.ndarray:
    """Pipeline-parallel next-token loss, replicated across stages.

    Gradient contract: blocks grads come out stage-local (sharded over
    ``pp_axis``); grads of the pp-replicated leaves (embed, final_norm,
    lm_head) are per-stage partials — psum them over ``pp_axis``
    (parallel.pipeline.replicated_grad_correction) before use.
    """
    from ..parallel.pipeline import last_stage_value

    inputs, targets = split_batch(batch)
    logits = forward_pp(params, inputs, cfg,
                        num_microbatches=num_microbatches, pp_axis=pp_axis)
    return last_stage_value(next_token_xent(logits, targets), pp_axis)


def loss_fn(params: Dict[str, Any], batch: Dict[str, jnp.ndarray],
            cfg: LlamaConfig, attn_impl=None,
            sp_axis: Optional[str] = None) -> jnp.ndarray:
    """Next-token cross-entropy.

    batch: {"tokens": [B, S]} — predicts tokens[:, 1:] from tokens[:, :-1];
    or pre-shifted {"inputs", "targets"} (required under sequence
    parallelism, where the shift must happen before sharding).
    """
    if "inputs" not in batch and sp_axis is not None:
        raise ValueError(
            "sequence parallelism requires a pre-shifted batch "
            "({'inputs', 'targets'}): shifting a sharded 'tokens' "
            "locally would gap the global sequence")
    inputs, targets = split_batch(batch)
    if cfg.xent_chunks > 0 and cfg.vocab_size % cfg.xent_chunks == 0:
        hidden = forward_hidden(params, inputs, cfg, attn_impl, sp_axis)
        loss = chunked_next_token_xent(hidden, params["lm_head"], targets,
                                       cfg.xent_chunks)
    else:
        logits = forward(params, inputs, cfg, attn_impl, sp_axis)
        loss = next_token_xent(logits, targets)
    if sp_axis is not None:
        loss = jax.lax.pmean(loss, sp_axis)
    return loss
