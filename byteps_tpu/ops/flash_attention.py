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
  blocks skipped) with a ``jax.custom_vjp`` whose backward is two more
  Pallas kernels (``_flash_bwd``: dK/dV per key tile, dQ per query
  tile; scores recomputed from the saved row logsumexp, never in HBM).
  The blockwise backward's f32 score tiles went through HBM a dozen
  times a fold: 2.4 s of a 3.0 s step at 8k tokens on the v5e (PERF.md,
  PR 26). Off-TPU both directions run ``blockwise_attention``.

Green-field component (the reference has no attention kernels at all —
it is a communication library; SURVEY §5.7 long-context is TPU-side
design). Interface matches models.llama ``attn_impl``:
q [B,S,H,D], k/v [B,S,Hkv,D] (GQA), causal, scale 1/sqrt(D).

``window=W`` (causal only) is sliding-window attention: query ``i`` sees
keys ``j`` with ``0 <= i - j < W``. Both paths then visit only the key
blocks that touch the band: the kernel's inner grid axis is as long as
the band is wide, not as long as the sequence, and the blockwise path
(``block_q`` given, which the kernel's backward does) walks query
blocks and folds, per query block, the key blocks between the band's
first and last, skipping the dead ones.
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


def _band_blocks(S: int, block_q: int, block_k: int,
                 window: Optional[int]) -> int:
    """How many key blocks one query block's band can touch."""
    nk = S // block_k
    if window is None:
        return nk
    return min(nk, (window + block_q - 2) // block_k + 2)


def _band_edges(i, block_q: int, block_k: int, nk: int, causal: bool,
                window: Optional[int]):
    """(first, last) key block that query block ``i`` can see."""
    lo = 0 if window is None else \
        jnp.maximum(0, i * block_q - window + 1) // block_k
    hi = (i * block_q + block_q - 1) // block_k if causal else nk - 1
    return lo, hi


def _band_mask(qpos, kpos, causal: bool, window: Optional[int]):
    """[Sq, Sk] bool from position vectors, or None where all is seen."""
    if not causal:
        return None
    mask = qpos[:, None] >= kpos[None, :]
    if window is not None:
        mask = mask & (qpos[:, None] - kpos[None, :] < window)
    return mask


def _banded_attention(q, k, v, causal: bool, window: Optional[int],
                      block_q: int, block_k: int, remat: bool):
    """Query blocks outside, key blocks inside: a query block folds only
    the key blocks of its band (``lax.cond`` skips the ones past its
    last), so a causal layer does half the score work of the all-pairs
    scan and a window layer ``(W + block_q) / S`` of it. Same fold, same
    result."""
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    groups = H // Hkv
    nq, nk = S // block_q, S // block_k
    n_inner = _band_blocks(S, block_q, block_k, window)
    scale = 1.0 / np.sqrt(D)
    kpos_blk = jnp.arange(block_k)
    qpos_blk = jnp.arange(block_q)

    def one_q_block(i, qb):
        qb = qb.astype(jnp.float32)                   # [B, bq, H, D]
        qpos = i * block_q + qpos_blk
        lo, hi = _band_edges(i, block_q, block_k, nk, causal, window)

        def fold(carry, t):
            j = lo + t

            def live(carry):
                start = jnp.minimum(j, nk - 1) * block_k
                kb = jax.lax.dynamic_slice_in_dim(k, start, block_k, 1)
                vb = jax.lax.dynamic_slice_in_dim(v, start, block_k, 1)
                kb = kb.astype(jnp.float32)
                vb = vb.astype(jnp.float32)
                if groups > 1:
                    kb = jnp.repeat(kb, groups, axis=2)
                    vb = jnp.repeat(vb, groups, axis=2)
                mask = _band_mask(qpos, start + kpos_blk, causal, window)
                return _fold(qb, kb, vb, mask, *carry, scale)

            return jax.lax.cond(j <= hi, live, lambda c: c, carry), None

        m0 = jnp.full((B, H, block_q), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, block_q), jnp.float32)
        o0 = jnp.zeros((B, block_q, H, D), jnp.float32)
        (m, l, o), _ = jax.lax.scan(
            jax.checkpoint(fold) if remat else fold, (m0, l0, o0),
            jnp.arange(n_inner))
        l = jnp.maximum(l, 1e-30)
        return (o / l.transpose(0, 2, 1)[..., None]).astype(q.dtype)

    fn = jax.checkpoint(one_q_block) if remat else one_q_block
    qs = q.reshape(B, nq, block_q, H, D).transpose(1, 0, 2, 3, 4)
    out = jax.lax.map(lambda a: fn(*a), (jnp.arange(nq), qs))
    return out.transpose(1, 0, 2, 3, 4).reshape(B, S, H, D)


def blockwise_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        causal: bool = True, block_k: int = 512,
                        remat: bool = True, window: Optional[int] = None,
                        block_q: Optional[int] = None) -> jnp.ndarray:
    """Exact attention streaming over KV blocks: peak residency
    O(S * block_k) instead of O(S^2). q [B,S,H,D], k/v [B,S,Hkv,D].
    ``window`` (see the module's head) or ``block_q`` selects the walk
    over query blocks that leaves out the key blocks outside the band."""
    B, S, H, D = q.shape
    if window is not None and not causal:
        raise ValueError("a window needs causal=True")
    if window is not None or block_q is not None:
        block_q = min(block_q or block_k, S)
        block_k = min(block_k, S)
        if S % block_q or S % block_k:
            raise ValueError(f"S={S} not divisible by blocks "
                             f"({block_q}, {block_k})")
        return _banded_attention(q, k, v, causal, window, block_q,
                                 block_k, remat)
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


def _scores(q, kb, i, j, *, block_q: int, block_k: int, scale: float,
            causal: bool, window: Optional[int]):
    """[bq, bk] f32 scores of query tile ``i`` against key tile ``j``,
    masked to the band. Operands go to the MXU in the type they came in
    (bf16 stays bf16); the scores are f32."""
    s = jax.lax.dot_general(
        q, kb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kpos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        seen = qpos >= kpos
        if window is not None:
            seen = seen & (qpos - kpos < window)
        s = jnp.where(seen, s, _NEG_INF)
    return s


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, *rest,
                      block_q: int, block_k: int, nk: int, n_inner: int,
                      scale: float, causal: bool, window: Optional[int],
                      with_lse: bool):
    """Grid (B, H, nq, n_inner) — innermost sequential ("arbitrary"):
    scratch carries the online softmax state across the band's k blocks
    for one [block_q, D] output tile. Step ``t`` of tile ``i`` holds key
    block ``first(i) + t``; ``n_inner`` is the widest band in blocks
    (all ``nk`` of them without a window)."""
    import jax.experimental.pallas as pl

    # with the row logsumexp asked for (the backward's residual), it is
    # one more output before the scratch
    lse_ref = rest[0] if with_lse else None
    acc_ref, m_ref, l_ref = rest[-3:]
    i = pl.program_id(2)
    t = pl.program_id(3)
    lo, hi = _band_edges(i, block_q, block_k, nk, causal, window)
    j = lo + t

    @pl.when(t == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # block j contributes only while it is not past the band's last:
    # its first key position <= the tile's last query position (the
    # band's first block is where the walk starts)
    live = j <= hi

    @pl.when(live)
    def _compute():
        # softmax state and the accumulator are f32
        vb = v_ref[0, 0]
        s = _scores(q_ref[0, 0], k_ref[0, 0], i, j, block_q=block_q,
                    block_k=block_k, scale=scale, causal=causal,
                    window=window)                # [bq, bk]
        m_prev = m_ref[:, :1]                     # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)            # [bq, 1]
        l_new = l_ref[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(t == n_inner - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[:] / l).astype(o_ref.dtype)
        if lse_ref is not None:
            # every lane holds its row's value, as the scratch does
            lse_ref[0, 0] = m_ref[:] + jnp.log(jnp.maximum(l_ref[:], 1e-30))


_LANES = 128    # a row statistic is kept once a lane, [rows, 128]


def _query_walk_specs(pl, block_q: int, block_k: int, D: int, groups: int,
                      nk: int, causal: bool, window: Optional[int]):
    """Block specs of a grid (B, H, nq, band) that holds one query tile
    and walks its band's key tiles (the forward kernel and the dQ
    kernel): ``q_tile(width)`` for a query-side operand, ``kv_tile`` for
    k and v of the head's group."""
    def q_tile(width):
        return pl.BlockSpec((1, 1, block_q, width),
                            lambda b, h, i, t: (b, h, i, 0))

    def kv_block(b, h, i, t):
        # the band's t-th key block; past the band's last the index
        # stays there, so a dead step fetches nothing new
        lo, hi = _band_edges(i, block_q, block_k, nk, causal, window)
        return b, h // groups, jnp.minimum(lo + t, hi), 0

    return q_tile, pl.BlockSpec((1, 1, block_k, D), kv_block)


def _scope(window: Optional[int]) -> str:
    """The ``jax.named_scope`` around each kernel's call: Mosaic names
    the call's HLO instruction by it, which is how a reduced device
    trace tells these kernels from the step's fusions (docs/timeline.md
    "Device scopes")."""
    return "bps.attn.window" if window is not None else "bps.attn.full"


def _flash_fwd(q, k, v, causal: bool, block_q: int, block_k: int,
               interpret: bool = False, window: Optional[int] = None,
               with_lse: bool = False):
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
    if window is not None and not causal:
        raise ValueError("a window needs causal=True")
    nq, nk = S // block_q, S // block_k
    n_inner = _band_blocks(S, block_q, block_k, window)
    scale = 1.0 / np.sqrt(D)

    # [B,H,S,D] layout: one (b, h, tile) per grid step
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _flash_fwd_kernel, block_q=block_q, block_k=block_k, nk=nk,
        n_inner=n_inner, scale=scale, causal=causal, window=window,
        with_lse=with_lse)
    q_tile, kv_tile = _query_walk_specs(pl, block_q, block_k, D, groups, nk,
                                        causal, window)
    out_specs = [q_tile(D)]
    out_shape = [jax.ShapeDtypeStruct((B, H, S, D), q.dtype)]
    if with_lse:
        out_specs.append(q_tile(_LANES))
        out_shape.append(jax.ShapeDtypeStruct((B, H, S, _LANES),
                                              jnp.float32))
    with jax.named_scope(_scope(window)):
        out = pl.pallas_call(
            kernel,
            grid=(B, H, nq, n_inner),
            in_specs=[q_tile(D), kv_tile, kv_tile],
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=[
                pltpu.VMEM((block_q, D), jnp.float32),       # acc
                pltpu.VMEM((block_q, _LANES), jnp.float32),  # running max
                pltpu.VMEM((block_q, _LANES), jnp.float32),  # running denom
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel",
                                     "arbitrary")),
            interpret=interpret,
        )(qt, kt, vt)
    o = out[0].transpose(0, 2, 1, 3)  # back to [B,S,H,D]
    return (o, out[1]) if with_lse else o


# --------------------------------------------------------------------- #
# Pallas backward kernels
# --------------------------------------------------------------------- #


def _band_q_blocks(S: int, block_q: int, block_k: int,
                   window: Optional[int]) -> int:
    """How many query blocks can see one key block."""
    nq = S // block_q
    if window is None:
        return nq
    return min(nq, (window + block_k - 2) // block_q + 2)


def _band_q_edges(j, block_q: int, block_k: int, nq: int, causal: bool,
                  window: Optional[int]):
    """(first, last) query block that can see key block ``j``."""
    lo = (j * block_k) // block_q if causal else 0
    hi = nq - 1 if window is None else \
        jnp.minimum(nq - 1, (j * block_k + block_k + window - 2) // block_q)
    return lo, hi


def _softmax_grad(q, kb, vb, o, do, lse, i, j, **band):
    """(p, ds), both [bq, bk] f32: the tile's probabilities recomputed
    from the saved row logsumexp, and the scores' cotangent times the
    scale, ``p * (do v^T - rowsum(o * do)) * scale``."""
    s = _scores(q, kb, i, j, **band)
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(
        do, vb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1, keepdims=True)
    return p, p * (dp - delta) * band["scale"]


def _flash_dkv_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                      dk_ref, dv_ref, dk_acc, dv_acc, *, nq: int,
                      n_inner: int, groups: int, **band):
    """Grid (B, Hkv, nk, groups * n_inner) — innermost sequential: one
    key tile's dK and dV, summed over the query heads that share the
    key head and over the query tiles of its band."""
    import jax.experimental.pallas as pl

    j = pl.program_id(2)
    t = pl.program_id(3)
    lo, hi = _band_q_edges(j, band["block_q"], band["block_k"], nq,
                           band["causal"], band["window"])
    i = lo + t % n_inner

    @pl.when(t == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(i <= hi)
    def _compute():
        q, do = q_ref[0, 0], do_ref[0, 0]
        p, ds = _softmax_grad(q, k_ref[0, 0], v_ref[0, 0], o_ref[0, 0], do,
                              lse_ref[0, 0][:, :1], i, j, **band)
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(t == groups * n_inner - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref,
                     dq_acc, *, nk: int, n_inner: int, **band):
    """Grid (B, H, nq, n_inner) — the forward's walk: one query tile's
    dQ, summed over the key tiles of its band."""
    import jax.experimental.pallas as pl

    i = pl.program_id(2)
    t = pl.program_id(3)
    lo, hi = _band_edges(i, band["block_q"], band["block_k"], nk,
                         band["causal"], band["window"])
    j = lo + t

    @pl.when(t == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(j <= hi)
    def _compute():
        kb = k_ref[0, 0]
        _, ds = _softmax_grad(q_ref[0, 0], kb, v_ref[0, 0], o_ref[0, 0],
                              do_ref[0, 0], lse_ref[0, 0][:, :1], i, j,
                              **band)
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(kb.dtype), kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(t == n_inner - 1)
    def _finish():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd(q, k, v, o, lse, do, causal: bool, block_q: int,
               block_k: int, window: Optional[int] = None,
               interpret: bool = False):
    """(dq, dk, dv) of ``_flash_fwd`` from its output, its row
    logsumexp ([B,H,S,128], every lane the row's value) and the
    output's cotangent. Two kernels: dK/dV per key tile (the query
    heads of a group folded into the walk, so the sums are complete and
    [B,Hkv,S,D]) and dQ per query tile; both walk only the band."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, H, D = q.shape
    Hkv = k.shape[2]
    groups = H // Hkv
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    nq, nk = S // block_q, S // block_k
    band = dict(block_q=block_q, block_k=block_k, scale=1.0 / np.sqrt(D),
                causal=causal, window=window)
    qt, kt, vt, ot, dot_ = (a.transpose(0, 2, 1, 3) for a in (q, k, v, o, do))
    sequential = pltpu.CompilerParams(dimension_semantics=(
        "parallel", "parallel", "parallel", "arbitrary"))

    # ---- dK, dV: key tile j of key head g; step t walks the group's
    # query heads and, within one, the band's query tiles ------------- #
    n_q = _band_q_blocks(S, block_q, block_k, window)

    def q_side(width):
        def index(b, g, j, t):
            lo, hi = _band_q_edges(j, block_q, block_k, nq, causal, window)
            return (b, g * groups + t // n_q,
                    jnp.minimum(lo + t % n_q, hi), 0)
        return pl.BlockSpec((1, 1, block_q, width), index)

    k_side = pl.BlockSpec((1, 1, block_k, D), lambda b, g, j, t: (b, g, j, 0))
    with jax.named_scope(_scope(window)):
        dk, dv = pl.pallas_call(
            functools.partial(_flash_dkv_kernel, nq=nq, n_inner=n_q,
                              groups=groups, **band),
            grid=(B, Hkv, nk, groups * n_q),
            in_specs=[q_side(D), k_side, k_side, q_side(D), q_side(D),
                      q_side(_LANES)],
            out_specs=[k_side, k_side],
            out_shape=[jax.ShapeDtypeStruct((B, Hkv, S, D), k.dtype),
                       jax.ShapeDtypeStruct((B, Hkv, S, D), v.dtype)],
            scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                            pltpu.VMEM((block_k, D), jnp.float32)],
            compiler_params=sequential, interpret=interpret,
        )(qt, kt, vt, ot, dot_, lse)

    # ---- dQ: the forward's walk -------------------------------------- #
    n_k = _band_blocks(S, block_q, block_k, window)
    q_tile, kv_tile = _query_walk_specs(pl, block_q, block_k, D, groups, nk,
                                        causal, window)
    with jax.named_scope(_scope(window)):
        dq = pl.pallas_call(
            functools.partial(_flash_dq_kernel, nk=nk, n_inner=n_k, **band),
            grid=(B, H, nq, n_k),
            in_specs=[q_tile(D), kv_tile, kv_tile, q_tile(D), q_tile(D),
                      q_tile(_LANES)],
            out_specs=q_tile(D),
            out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
            scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
            compiler_params=sequential, interpret=interpret,
        )(qt, kt, vt, ot, dot_, lse)
    return tuple(a.transpose(0, 2, 1, 3) for a in (dq, dk, dv))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = True, block_q: int = 512,
                    block_k: int = 512, window: Optional[int] = None):
    """Pallas flash attention forward (TPU), blockwise-recompute
    backward. Off-TPU (tests, CPU mesh) the forward also runs the
    portable blockwise path, so behavior is uniform. ``window``: see
    the module's head."""
    if jax.default_backend() == "tpu":
        return _flash_fwd(q, k, v, causal, block_q, block_k,
                          window=window)
    return blockwise_attention(q, k, v, causal=causal, block_k=block_k,
                               window=window, block_q=block_q)


def _flash_vjp_fwd(q, k, v, causal, block_q, block_k, window):
    if jax.default_backend() == "tpu":
        out, lse = _flash_fwd(q, k, v, causal, block_q, block_k,
                              window=window, with_lse=True)
        return out, (q, k, v, out, lse)
    out = flash_attention(q, k, v, causal, block_q, block_k, window)
    return out, (q, k, v, None, None)


def _flash_vjp_bwd(causal, block_q, block_k, window, res, g):
    q, k, v, out, lse = res
    if lse is not None:
        return _flash_bwd(q, k, v, out, lse, g, causal, block_q, block_k,
                          window)
    # off-TPU: recompute through the differentiable blockwise path,
    # query blocks outside so the key blocks outside the band are left
    # out: same fold math, so gradients are exact for the same function
    _, vjp = jax.vjp(
        lambda q_, k_, v_: blockwise_attention(
            q_, k_, v_, causal=causal, block_k=block_k, window=window,
            block_q=block_q), q, k, v)
    return vjp(g)


flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def make_flash_attn(causal: bool = True, block_q: int = 512,
                    block_k: int = 512, pallas: Optional[bool] = None,
                    window: Optional[int] = None):
    """Bind as a models.llama ``attn_impl``. ``pallas=False`` forces the
    jnp blockwise path even on TPU (A/B-ing the kernel)."""

    def impl(q, k, v):
        if pallas is False:
            return blockwise_attention(q, k, v, causal=causal,
                                       block_k=block_k, window=window)
        return flash_attention(q, k, v, causal, block_q, block_k, window)

    return impl
