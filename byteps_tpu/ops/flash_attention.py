"""Flash attention for long sequences (S >= ~4k).

The dense attention in models/llama.py materializes [B, H, S, S] scores;
XLA fuses the softmax well enough that at S=1024 on v5e it beats a
hand-written kernel (measured, docs/performance.md "rejected" table).
The quadratic HBM term wins at longer S, so long-context runs get:

- ``blockwise_attention`` — jnp ``lax.scan`` over KV blocks with the
  streaming-softmax fold (the same math as ring attention's per-step
  fold, parallel/ring_attention.py:35-52, with the ring replaced by a
  local block loop). Differentiable by construction (XLA AD through the
  scan; jax.checkpoint per block bounds the residency at
  O(S * block_k)), runs on any backend — the portable reference
  semantics and the autodiff path.
- ``flash_attention`` — Pallas TPU forward kernel (one [block_q, hd]
  output tile per grid step, online softmax across the K grid, causal
  blocks skipped) with a ``jax.custom_vjp`` whose backward recomputes
  through ``blockwise_attention`` — fwd pays zero S^2 HBM, bwd trades
  FLOPs for memory exactly like the remat the model already runs.
  Falls back to ``blockwise_attention`` off-TPU.

Green-field component (the reference has no attention kernels at all —
it is a communication library; SURVEY §5.7 long-context is TPU-side
design). Interface matches models.llama ``attn_impl``:
q [B,S,H,D], k/v [B,S,Hkv,D] (GQA), causal, scale 1/sqrt(D).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

_NEG_INF = -1e30

# the streaming-softmax fold is THE subtle math here — one definition,
# shared with the ring (same shape contract; ring_attention.py:35-52)
from ..parallel.ring_attention import _block_attn_accum as _fold  # noqa: E402,E501


def blockwise_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        causal: bool = True, block_k: int = 512,
                        remat: bool = True) -> jnp.ndarray:
    """Exact attention streaming over KV blocks: peak residency
    O(S * block_k) instead of O(S^2). q [B,S,H,D], k/v [B,S,Hkv,D]."""
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    groups = H // Hkv
    block_k = min(block_k, S)
    if S % block_k:
        raise ValueError(f"S={S} not divisible by block_k={block_k}")
    nk = S // block_k
    scale = 1.0 / np.sqrt(D)
    q32 = q.astype(jnp.float32)
    # [nk, B, bk, Hkv, D] so scan carries one block per step. KV stay in
    # COMPACT Hkv heads and original dtype here: a whole-sequence GQA
    # repeat (+fp32 cast) before the scan would multiply KV residency by
    # (H/Hkv)*(32/16) in HBM — on the backward-recompute path this module
    # exists to keep small. The per-block expand happens in body (same
    # arrangement as ring_attention.body).
    ks = k.reshape(B, nk, block_k, Hkv, D).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(B, nk, block_k, Hkv, D).transpose(1, 0, 2, 3, 4)
    qpos = jnp.arange(S)
    kpos_blk = jnp.arange(block_k)

    def body(carry, blk):
        m, l, o = carry
        j, kb, vb = blk
        kb = kb.astype(jnp.float32)
        vb = vb.astype(jnp.float32)
        if groups > 1:
            kb = jnp.repeat(kb, groups, axis=2)
            vb = jnp.repeat(vb, groups, axis=2)
        if causal:
            mask = qpos[:, None] >= (j * block_k + kpos_blk)[None, :]
        else:
            mask = None
        m, l, o = _fold(q32, kb, vb, mask, m, l, o, scale)
        return (m, l, o), None

    fold_fn = body
    if remat:
        fold_fn = jax.checkpoint(body)

    m0 = jnp.full((B, H, S), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, S), jnp.float32)
    o0 = jnp.zeros((B, S, H, D), jnp.float32)
    (m, l, o), _ = jax.lax.scan(
        fold_fn, (m0, l0, o0), (jnp.arange(nk), ks, vs))
    l = jnp.maximum(l, 1e-30)
    return (o / l.transpose(0, 2, 1)[..., None]).astype(q.dtype)


# --------------------------------------------------------------------- #
# Pallas forward kernel
# --------------------------------------------------------------------- #


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                      *, block_q: int, block_k: int, nk: int, scale: float,
                      causal: bool):
    """Grid (B, H, nq, nk) — innermost nk sequential ("arbitrary"):
    scratch carries the online softmax state across k blocks for one
    [block_q, D] output tile."""
    import jax.experimental.pallas as pl

    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # causal: block j contributes only when its first key position is
    # <= the tile's last query position (j >= 0 == always, kept traced)
    live = (j * block_k <= i * block_q + block_q - 1) if causal \
        else (j >= 0)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)       # [bq, D]
        kb = k_ref[0, 0].astype(jnp.float32)      # [bk, D]
        vb = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]
        if causal:
            qpos = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        m_prev = m_ref[:, :1]                     # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)            # [bq, 1]
        l_new = l_ref[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p, vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[:] / l).astype(o_ref.dtype)


def _flash_fwd(q, k, v, causal: bool, block_q: int, block_k: int,
               interpret: bool = False):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, H, D = q.shape
    Hkv = k.shape[2]
    groups = H // Hkv
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    if S % block_q or S % block_k:
        raise ValueError(f"S={S} not divisible by blocks "
                         f"({block_q}, {block_k})")
    nq, nk = S // block_q, S // block_k
    scale = 1.0 / np.sqrt(D)

    # [B,H,S,D] layout: one (b, h, tile) per grid step
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _flash_fwd_kernel, block_q=block_q, block_k=block_k, nk=nk,
        scale=scale, causal=causal)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j, g=groups: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j, g=groups: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),    # acc
            pltpu.VMEM((block_q, 128), jnp.float32),  # running max
            pltpu.VMEM((block_q, 128), jnp.float32),  # running denom
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)  # back to [B,S,H,D]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal: bool = True, block_q: int = 512,
                    block_k: int = 512):
    """Pallas flash attention forward (TPU), blockwise-recompute
    backward. Off-TPU (tests, CPU mesh) the forward also runs the
    portable blockwise path, so behavior is uniform."""
    if jax.default_backend() == "tpu":
        return _flash_fwd(q, k, v, causal, block_q, block_k)
    return blockwise_attention(q, k, v, causal=causal, block_k=block_k)


def _flash_vjp_fwd(q, k, v, causal, block_q, block_k):
    out = flash_attention(q, k, v, causal, block_q, block_k)
    return out, (q, k, v)


def _flash_vjp_bwd(causal, block_q, block_k, res, g):
    q, k, v = res
    # recompute through the differentiable blockwise path: same fold
    # math, so gradients are exact for the same function
    _, vjp = jax.vjp(
        lambda q_, k_, v_: blockwise_attention(
            q_, k_, v_, causal=causal, block_k=block_k), q, k, v)
    return vjp(g)


flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def make_flash_attn(causal: bool = True, block_q: int = 512,
                    block_k: int = 512, pallas: Optional[bool] = None):
    """Bind as a models.llama ``attn_impl``. ``pallas=False`` forces the
    jnp blockwise path even on TPU (A/B-ing the kernel)."""

    def impl(q, k, v):
        if pallas is False:
            return blockwise_attention(q, k, v, causal=causal,
                                       block_k=block_k)
        return flash_attention(q, k, v, causal, block_q, block_k)

    return impl
