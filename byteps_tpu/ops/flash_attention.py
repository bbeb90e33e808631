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

``diffusion_block=B`` is the mask block-diffusion training runs under:
the sequence is a row's noised copy followed by its clean copy (``S``
is twice the row), position ``p`` is noised where ``p < S / 2``, lies at
``n(p) = p mod S / 2`` of the row and in block ``n(p) // B``. A noised
query sees the noised keys of its own block and the clean keys of the
blocks before it; a clean query sees the clean keys of the blocks up to
its own; nothing sees a noised key of another block. That is no band:
a noised query tile visits its own tile and the clean tiles up to it.

One description of a mask on a tile grid, ``_Tiles``, says which key
tiles a query tile visits, which query tiles a key tile, and which
pairs inside a tile are seen; the forward, dK/dV and dQ kernels and the
blockwise walk all take their walk from it, whatever the mask.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

_NEG_INF = -1e30

# the streaming-softmax fold is THE subtle math here — one definition,
# shared with the ring (same shape contract; ring_attention.py:35-52)
from ..parallel.ring_attention import _block_attn_accum as _fold  # noqa: E402,E501


@dataclasses.dataclass(frozen=True)
class _Tiles:
    """A mask on a grid of ``block_q`` x ``block_k`` tiles over ``S``
    positions. ``key_tiles(i)`` / ``query_tiles(j)``: the tiles a query
    tile / a key tile has pairs with, as one or two intervals ``(first,
    last)`` of tile indices in ascending order, an empty one where
    ``last < first``; the index may be traced (a program id) or an
    array of indices. ``seen(i, j)``: the ``[block_q, block_k]`` pairs
    of tile ``(i, j)`` the mask lets through, or None where it lets
    all through."""

    S: int
    block_q: int
    block_k: int
    causal: bool = True
    window: Optional[int] = None
    diffusion_block: Optional[int] = None

    def __post_init__(self):
        S, B = self.S, self.diffusion_block
        if self.window is not None and not self.causal:
            raise ValueError("a window needs causal=True")
        if B is not None and (self.window is not None or not self.causal):
            raise ValueError("the block-diffusion mask takes no window and "
                             "is causal by blocks")
        # what the tiles must divide: the sequence, or each of its halves
        tiled, odd = (S, 0) if B is None else divmod(S, 2)
        if odd or tiled % self.block_q or tiled % self.block_k:
            raise ValueError(
                f"S={S} not divisible by blocks ({self.block_q}, "
                f"{self.block_k})" + ("" if B is None else
                                      ": each half of the sequence is tiled"))
        if B is not None and tiled % B:
            raise ValueError(f"a half of {tiled} positions does not divide "
                             f"into blocks of {B}")

    @property
    def nq(self) -> int:
        return self.S // self.block_q

    @property
    def nk(self) -> int:
        return self.S // self.block_k

    @property
    def scope(self) -> str:
        """The ``jax.named_scope`` around each kernel's call: Mosaic
        names the call's HLO instruction by it, which is how a reduced
        device trace tells these kernels from the step's fusions
        (docs/timeline.md "Device scopes")."""
        if self.diffusion_block is not None:
            return "bps.attn.blockdiff"
        return "bps.attn.window" if self.window is not None \
            else "bps.attn.full"

    # ---- which tiles ------------------------------------------------- #

    def key_tiles(self, i):
        bq, bk, B = self.block_q, self.block_k, self.diffusion_block
        if B is None:
            lo = 0 if self.window is None else \
                jnp.maximum(0, i * bq - self.window + 1) // bk
            hi = (i * bq + bq - 1) // bk if self.causal else self.nk - 1
            return [(lo, hi)]
        hq, hk = self.nq // 2, self.nk // 2
        noised = i < hq
        first = jnp.where(noised, i, i - hq) * bq     # of the tile, in a row
        b_lo, b_hi = first // B, (first + bq - 1) // B   # its blocks
        # a noised tile: the noised keys of its own blocks
        own = b_lo * B // bk
        own_last = jnp.minimum((b_hi * B + B - 1) // bk, hk - 1)
        # the clean keys of the blocks before its last (noised), up to
        # its last (clean); before block 0 there is none (-1 // bk)
        clean_last = jnp.where(noised, b_hi * B - 1, b_hi * B + B - 1) // bk
        return [(own, jnp.where(noised, own_last, own - 1)),
                (hk, hk + jnp.minimum(clean_last, hk - 1))]

    def query_tiles(self, j):
        bq, bk, B = self.block_q, self.block_k, self.diffusion_block
        if B is None:
            lo = (j * bk) // bq if self.causal else 0
            hi = self.nq - 1 if self.window is None else jnp.minimum(
                self.nq - 1, (j * bk + bk + self.window - 2) // bq)
            return [(lo, hi)]
        hq, hk = self.nq // 2, self.nk // 2
        noised = j < hk
        first = jnp.where(noised, j, j - hk) * bk
        b_lo, b_hi = first // B, (first + bk - 1) // B
        # noised queries: of its own blocks (a noised key tile), of the
        # blocks after its first (a clean one)
        lo = jnp.where(noised, b_lo * B, b_lo * B + B) // bq
        hi = jnp.where(noised,
                       jnp.minimum((b_hi * B + B - 1) // bq, hq - 1), hq - 1)
        # clean queries, of its first block and after: a clean key tile's
        clean = hq + b_lo * B // bq
        return [(lo, hi), (clean, jnp.where(noised, clean - 1, 2 * hq - 1))]

    @functools.cached_property
    def key_steps(self) -> int:
        """The longest walk of a query tile over its key tiles."""
        return _longest(self.key_tiles, self.nq)

    @functools.cached_property
    def query_steps(self) -> int:
        """The longest walk of a key tile over its query tiles."""
        return _longest(self.query_tiles, self.nk)

    # ---- which pairs of a tile --------------------------------------- #

    def seen(self, i, j):
        bq, bk, B = self.block_q, self.block_k, self.diffusion_block
        if B is None:
            if not self.causal:
                return None
            qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            seen = qpos >= kpos
            if self.window is not None:
                seen = seen & (qpos - kpos < self.window)
            return seen
        half = self.S // 2

        def coded(pos):
            """(twice its block's index plus one where the position is
            clean, the same with a clean position off the scale)"""
            clean = pos >= half
            twice = 2 * jax.lax.div(pos - jnp.where(clean, half, 0),
                                    jnp.int32(B))
            return twice + clean.astype(jnp.int32), clean, twice

        # a column of query codes against a row of key codes. The three
        # parts in two order tests: ``k <= q`` is "block before" for a
        # clean key under a noised query, "block up to" clean under
        # clean and noised under noised; ``k2 >= q2`` adds "not before"
        # for noised under noised, bars a noised key from a clean query
        # and bars no clean key
        off = jnp.int32(2 ** 30)
        q1, q_clean, q_twice = coded(
            i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0))
        k1, k_clean, k_twice = coded(
            j * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1))
        q2 = jnp.where(q_clean, off - 1, q_twice)
        k2 = jnp.where(k_clean, off, k_twice)
        return (k1 <= q1) & (k2 >= q2)


def _longest(tiles_of, n: int) -> int:
    """The most tiles any of ``n`` walks visits: a size of the grid, so
    it is computed now, whatever trace this is called under."""
    with jax.ensure_compile_time_eval():
        return int(jnp.max(sum(jnp.maximum(hi - lo + 1, 0)
                               for lo, hi in tiles_of(jnp.arange(n)))))


def _step(intervals, t):
    """(tile, live) at step ``t`` of a walk through ``intervals`` in
    order: past the walk's last tile the index stays there, so a dead
    step fetches nothing new, and ``live`` is false."""
    (lo, hi), *more = intervals
    if not more:
        return jnp.minimum(lo + t, hi), lo + t <= hi
    (lo2, hi2), = more
    n1 = jnp.maximum(hi - lo + 1, 0)
    n = n1 + jnp.maximum(hi2 - lo2 + 1, 0)
    at = jnp.minimum(t, n - 1)
    return jnp.where(at < n1, lo + at, lo2 + at - n1), t < n


def _tiled_attention(q, k, v, tiles: _Tiles, remat: bool):
    """Query blocks outside, key blocks inside: a query block folds only
    the key blocks the mask gives it (``lax.cond`` skips the steps past
    its walk's last), so a causal layer does half the score work of the
    all-pairs scan, a window layer ``(W + block_q) / S`` of it and a
    block-diffusion layer a quarter. Same fold, same result."""
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    groups = H // Hkv
    block_q, block_k = tiles.block_q, tiles.block_k
    scale = 1.0 / np.sqrt(D)

    def one_q_block(i, qb):
        qb = qb.astype(jnp.float32)                   # [B, bq, H, D]
        walk = tiles.key_tiles(i)

        def fold(carry, t):
            j, live = _step(walk, t)

            def fold_tile(carry):
                start = j * block_k
                kb = jax.lax.dynamic_slice_in_dim(k, start, block_k, 1)
                vb = jax.lax.dynamic_slice_in_dim(v, start, block_k, 1)
                kb = kb.astype(jnp.float32)
                vb = vb.astype(jnp.float32)
                if groups > 1:
                    kb = jnp.repeat(kb, groups, axis=2)
                    vb = jnp.repeat(vb, groups, axis=2)
                return _fold(qb, kb, vb, tiles.seen(i, j), *carry, scale)

            return jax.lax.cond(live, fold_tile, lambda c: c, carry), None

        m0 = jnp.full((B, H, block_q), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, block_q), jnp.float32)
        o0 = jnp.zeros((B, block_q, H, D), jnp.float32)
        (m, l, o), _ = jax.lax.scan(
            jax.checkpoint(fold) if remat else fold, (m0, l0, o0),
            jnp.arange(tiles.key_steps))
        l = jnp.maximum(l, 1e-30)
        return (o / l.transpose(0, 2, 1)[..., None]).astype(q.dtype)

    fn = jax.checkpoint(one_q_block) if remat else one_q_block
    qs = q.reshape(B, tiles.nq, block_q, H, D).transpose(1, 0, 2, 3, 4)
    out = jax.lax.map(lambda a: fn(*a), (jnp.arange(tiles.nq), qs))
    return out.transpose(1, 0, 2, 3, 4).reshape(B, S, H, D)


def blockwise_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        causal: bool = True, block_k: int = 512,
                        remat: bool = True, window: Optional[int] = None,
                        block_q: Optional[int] = None,
                        diffusion_block: Optional[int] = None
                        ) -> jnp.ndarray:
    """Exact attention streaming over KV blocks: peak residency
    O(S * block_k) instead of O(S^2). q [B,S,H,D], k/v [B,S,Hkv,D].
    ``window``, ``diffusion_block`` (see the module's head) or
    ``block_q`` selects the walk over query blocks that leaves out the
    key blocks the mask does not touch."""
    B, S, H, D = q.shape
    if window is not None or block_q is not None \
            or diffusion_block is not None:
        return _tiled_attention(
            q, k, v, _tiles(S, block_q or block_k, block_k, causal, window,
                            diffusion_block), remat)
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


def _tiles(S: int, block_q: int, block_k: int, causal: bool,
           window: Optional[int], diffusion_block: Optional[int]) -> _Tiles:
    """The mask on the tile grid the callers ask for, a tile never
    longer than what it tiles (the sequence; a half of it under the
    block-diffusion mask)."""
    most = S if diffusion_block is None else max(S // 2, 1)
    return _Tiles(S, min(block_q, most), min(block_k, most), causal, window,
                  diffusion_block)


def _scores(q, kb, i, j, *, tiles: _Tiles, scale: float):
    """[bq, bk] f32 scores of query tile ``i`` against key tile ``j``,
    masked as ``tiles`` says. Operands go to the MXU in the type they
    came in (bf16 stays bf16); the scores are f32."""
    s = jax.lax.dot_general(
        q, kb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    seen = tiles.seen(i, j)
    return s if seen is None else jnp.where(seen, s, _NEG_INF)


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, *rest, tiles: _Tiles,
                      scale: float, with_lse: bool):
    """Grid (B, H, nq, key_steps) — innermost sequential ("arbitrary"):
    scratch carries the online softmax state across one query tile's
    key tiles for one [block_q, D] output tile. Step ``t`` of tile ``i``
    holds the ``t``-th key tile of its walk; ``key_steps`` is the
    longest walk (all ``nk`` tiles under the causal mask)."""
    import jax.experimental.pallas as pl

    # with the row logsumexp asked for (the backward's residual), it is
    # one more output before the scratch
    lse_ref = rest[0] if with_lse else None
    acc_ref, m_ref, l_ref = rest[-3:]
    i = pl.program_id(2)
    t = pl.program_id(3)
    j, live = _step(tiles.key_tiles(i), t)

    @pl.when(t == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # a step past the walk's last tile holds that tile again and
    # contributes nothing
    @pl.when(live)
    def _compute():
        # softmax state and the accumulator are f32
        vb = v_ref[0, 0]
        s = _scores(q_ref[0, 0], k_ref[0, 0], i, j, tiles=tiles,
                    scale=scale)                  # [bq, bk]
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

    @pl.when(t == tiles.key_steps - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[:] / l).astype(o_ref.dtype)
        if lse_ref is not None:
            # every lane holds its row's value, as the scratch does
            lse_ref[0, 0] = m_ref[:] + jnp.log(jnp.maximum(l_ref[:], 1e-30))


_LANES = 128    # a row statistic is kept once a lane, [rows, 128]


def _query_walk_specs(pl, tiles: _Tiles, D: int, groups: int):
    """Block specs of a grid (B, H, nq, key_steps) that holds one query
    tile and walks its key tiles (the forward kernel and the dQ kernel):
    ``q_tile(width)`` for a query-side operand, ``kv_tile`` for k and v
    of the head's group."""
    def q_tile(width):
        return pl.BlockSpec((1, 1, tiles.block_q, width),
                            lambda b, h, i, t: (b, h, i, 0))

    def kv_block(b, h, i, t):
        return b, h // groups, _step(tiles.key_tiles(i), t)[0], 0

    return q_tile, pl.BlockSpec((1, 1, tiles.block_k, D), kv_block)


def _flash_fwd(q, k, v, causal: bool, block_q: int, block_k: int,
               interpret: bool = False, window: Optional[int] = None,
               with_lse: bool = False,
               diffusion_block: Optional[int] = None):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, H, D = q.shape
    Hkv = k.shape[2]
    groups = H // Hkv
    tiles = _tiles(S, block_q, block_k, causal, window, diffusion_block)
    scale = 1.0 / np.sqrt(D)

    # [B,H,S,D] layout: one (b, h, tile) per grid step
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(_flash_fwd_kernel, tiles=tiles, scale=scale,
                               with_lse=with_lse)
    q_tile, kv_tile = _query_walk_specs(pl, tiles, D, groups)
    out_specs = [q_tile(D)]
    out_shape = [jax.ShapeDtypeStruct((B, H, S, D), q.dtype)]
    if with_lse:
        out_specs.append(q_tile(_LANES))
        out_shape.append(jax.ShapeDtypeStruct((B, H, S, _LANES),
                                              jnp.float32))
    with jax.named_scope(tiles.scope):
        out = pl.pallas_call(
            kernel,
            grid=(B, H, tiles.nq, tiles.key_steps),
            in_specs=[q_tile(D), kv_tile, kv_tile],
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=[
                pltpu.VMEM((tiles.block_q, D), jnp.float32),       # acc
                pltpu.VMEM((tiles.block_q, _LANES), jnp.float32),  # max
                pltpu.VMEM((tiles.block_q, _LANES), jnp.float32),  # denom
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


def _softmax_grad(q, kb, vb, o, do, lse, i, j, *, tiles: _Tiles,
                  scale: float):
    """(p, ds), both [bq, bk] f32: the tile's probabilities recomputed
    from the saved row logsumexp, and the scores' cotangent times the
    scale, ``p * (do v^T - rowsum(o * do)) * scale``."""
    s = _scores(q, kb, i, j, tiles=tiles, scale=scale)
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(
        do, vb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1, keepdims=True)
    return p, p * (dp - delta) * scale


def _flash_dkv_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                      dk_ref, dv_ref, dk_acc, dv_acc, *, groups: int,
                      tiles: _Tiles, scale: float):
    """Grid (B, Hkv, nk, groups * query_steps) — innermost sequential:
    one key tile's dK and dV, summed over the query heads that share the
    key head and over the query tiles that see it."""
    import jax.experimental.pallas as pl

    j = pl.program_id(2)
    t = pl.program_id(3)
    i, live = _step(tiles.query_tiles(j), t % tiles.query_steps)

    @pl.when(t == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(live)
    def _compute():
        q, do = q_ref[0, 0], do_ref[0, 0]
        p, ds = _softmax_grad(q, k_ref[0, 0], v_ref[0, 0], o_ref[0, 0], do,
                              lse_ref[0, 0][:, :1], i, j, tiles=tiles,
                              scale=scale)
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(t == groups * tiles.query_steps - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref,
                     dq_acc, *, tiles: _Tiles, scale: float):
    """Grid (B, H, nq, key_steps) — the forward's walk: one query tile's
    dQ, summed over the key tiles it sees."""
    import jax.experimental.pallas as pl

    i = pl.program_id(2)
    t = pl.program_id(3)
    j, live = _step(tiles.key_tiles(i), t)

    @pl.when(t == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(live)
    def _compute():
        kb = k_ref[0, 0]
        _, ds = _softmax_grad(q_ref[0, 0], kb, v_ref[0, 0], o_ref[0, 0],
                              do_ref[0, 0], lse_ref[0, 0][:, :1], i, j,
                              tiles=tiles, scale=scale)
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(kb.dtype), kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(t == tiles.key_steps - 1)
    def _finish():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd(q, k, v, o, lse, do, causal: bool, block_q: int,
               block_k: int, window: Optional[int] = None,
               interpret: bool = False,
               diffusion_block: Optional[int] = None):
    """(dq, dk, dv) of ``_flash_fwd`` from its output, its row
    logsumexp ([B,H,S,128], every lane the row's value) and the
    output's cotangent. Two kernels: dK/dV per key tile (the query
    heads of a group folded into the walk, so the sums are complete and
    [B,Hkv,S,D]) and dQ per query tile; both walk only the tiles the
    mask touches."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, H, D = q.shape
    Hkv = k.shape[2]
    groups = H // Hkv
    tiles = _tiles(S, block_q, block_k, causal, window, diffusion_block)
    block_q, block_k = tiles.block_q, tiles.block_k
    mask = dict(tiles=tiles, scale=1.0 / np.sqrt(D))
    qt, kt, vt, ot, dot_ = (a.transpose(0, 2, 1, 3) for a in (q, k, v, o, do))
    sequential = pltpu.CompilerParams(dimension_semantics=(
        "parallel", "parallel", "parallel", "arbitrary"))

    # ---- dK, dV: key tile j of key head g; step t walks the group's
    # query heads and, within one, the query tiles that see the tile --- #
    n_q = tiles.query_steps

    def q_side(width):
        def index(b, g, j, t):
            return (b, g * groups + t // n_q,
                    _step(tiles.query_tiles(j), t % n_q)[0], 0)
        return pl.BlockSpec((1, 1, block_q, width), index)

    k_side = pl.BlockSpec((1, 1, block_k, D), lambda b, g, j, t: (b, g, j, 0))
    with jax.named_scope(tiles.scope):
        dk, dv = pl.pallas_call(
            functools.partial(_flash_dkv_kernel, groups=groups, **mask),
            grid=(B, Hkv, tiles.nk, groups * n_q),
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
    q_tile, kv_tile = _query_walk_specs(pl, tiles, D, groups)
    with jax.named_scope(tiles.scope):
        dq = pl.pallas_call(
            functools.partial(_flash_dq_kernel, **mask),
            grid=(B, H, tiles.nq, tiles.key_steps),
            in_specs=[q_tile(D), kv_tile, kv_tile, q_tile(D), q_tile(D),
                      q_tile(_LANES)],
            out_specs=q_tile(D),
            out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
            scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
            compiler_params=sequential, interpret=interpret,
        )(qt, kt, vt, ot, dot_, lse)
    return tuple(a.transpose(0, 2, 1, 3) for a in (dq, dk, dv))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal: bool = True, block_q: int = 512,
                    block_k: int = 512, window: Optional[int] = None,
                    diffusion_block: Optional[int] = None):
    """Pallas flash attention forward (TPU), blockwise-recompute
    backward. Off-TPU (tests, CPU mesh) the forward also runs the
    portable blockwise path, so behavior is uniform. ``window``,
    ``diffusion_block``: see the module's head."""
    if jax.default_backend() == "tpu":
        return _flash_fwd(q, k, v, causal, block_q, block_k,
                          window=window, diffusion_block=diffusion_block)
    return blockwise_attention(q, k, v, causal=causal, block_k=block_k,
                               window=window, block_q=block_q,
                               diffusion_block=diffusion_block)


def _flash_vjp_fwd(q, k, v, causal, block_q, block_k, window,
                   diffusion_block):
    if jax.default_backend() == "tpu":
        out, lse = _flash_fwd(q, k, v, causal, block_q, block_k,
                              window=window, with_lse=True,
                              diffusion_block=diffusion_block)
        return out, (q, k, v, out, lse)
    out = flash_attention(q, k, v, causal, block_q, block_k, window,
                          diffusion_block)
    return out, (q, k, v, None, None)


def _flash_vjp_bwd(causal, block_q, block_k, window, diffusion_block, res,
                   g):
    q, k, v, out, lse = res
    if lse is not None:
        return _flash_bwd(q, k, v, out, lse, g, causal, block_q, block_k,
                          window, diffusion_block=diffusion_block)
    # off-TPU: recompute through the differentiable blockwise path,
    # query blocks outside so the key blocks the mask does not touch are
    # left out: same fold math, so gradients are exact for the same
    # function
    _, vjp = jax.vjp(
        lambda q_, k_, v_: blockwise_attention(
            q_, k_, v_, causal=causal, block_k=block_k, window=window,
            block_q=block_q, diffusion_block=diffusion_block), q, k, v)
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
