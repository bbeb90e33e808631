"""Flash attention for long sequences (S >= ~4k).

The dense attention in models/llama.py materializes [B, H, S, S] scores;
XLA fuses the softmax well enough that at S=1024 on v5e it beats a
hand-written kernel.
The quadratic HBM term wins at longer S, so long-context runs get:

- ``blockwise_attention`` — jnp ``lax.scan`` over KV blocks with the
  streaming-softmax fold (the same math as ring attention's per-step
  fold, parallel/ring_attention.py:35-52, with the ring replaced by a
  local block loop). Differentiable by construction (XLA AD through the
  scan; jax.checkpoint per block bounds the residency at
  O(S * block_k)), runs on any backend — the portable reference
  semantics and the autodiff path.
- ``flash_attention`` — Pallas TPU forward kernel (one [block_q, hd]
  output tile held while its key tiles pass, online softmax across
  them) with a ``jax.custom_vjp`` whose backward is two more Pallas
  kernels (``_flash_bwd``: dK/dV per key tile, dQ per query tile;
  scores recomputed from the saved row logsumexp, never in HBM). The
  blockwise backward's f32 score tiles went through HBM a dozen times
  a fold: 2.4 s of a 3.0 s step at 8k tokens on the v5e (PERF.md,
  PR 26). Off-TPU both directions run ``blockwise_attention``.

Green-field component (the reference has no attention kernels at all —
it is a communication library; SURVEY §5.7 long-context is TPU-side
design). Interface matches models.llama ``attn_impl``:
q [B,S,H,D], k/v [B,S,Hkv,D] (GQA), causal, scale 1/sqrt(D).

``window=W`` (causal only) is sliding-window attention: query ``i`` sees
keys ``j`` with ``0 <= i - j < W``. Both paths then visit only the key
blocks that touch the band: the kernels' work lists hold the band's
tiles, not the sequence's, and the blockwise path
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

``latent_attention`` is the causal mask over a score in two parts
(multi-head latent attention, not absorbed): each query head's own
``D``-wide product with its key head plus a ``Dr``-wide product against
ONE rotary key head shared by every query head of the row, scaled ``1 /
sqrt(D + Dr)``, mixing values of a width of their own. The shared key
is one ``[B, S, 1, Dr]`` operand of every kernel (its block index has
no head in it) and is never repeated a head in HBM; its gradient leaves
the dK/dV kernel a key head and is summed over them once. The same
kernels, walks and lists as the causal mask's, under
``bps.attn.mla``.

One description of a mask on a tile grid, ``_Tiles``, says which key
tiles a query tile visits, which query tiles a key tile, which pairs
inside a tile are seen and which tiles are seen whole; the forward,
dK/dV and dQ kernels and the blockwise walk all take their walk from
it, whatever the mask.

The kernels' grid is (rows, heads, WORK ITEMS): the masks are static,
so the live tiles are listed when the call is traced (``_Walk``: by
query tile for the forward and dQ, by key tile and head of the group
for dK/dV), handed to the kernel as scalar-prefetched int32 columns,
and the index maps read the tile indices from them. No grid step is
dead (a rectangular grid as long as the longest walk spends half the
causal mask's steps and more of the block-diffusion mask's past the
walks' ends), an item's flags say where an output tile's run begins and
ends, and a tile the mask lets through whole takes a body without the
in-tile test. Neither moves a bit: a plain fold that tests every tile
gives the same output, logsumexp and gradients
(tests/test_window_attention.py; on the v5e against the rectangular
grid's kernels, PERF.md, PR 40).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

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
    all through; ``whole(i, j)``: whether that is every pair of the
    tile. ``query_walk`` / ``key_walk(groups)``: the live tiles as the
    kernels' work lists (``_Walk``)."""

    S: int
    block_q: int
    block_k: int
    causal: bool = True
    window: Optional[int] = None
    diffusion_block: Optional[int] = None
    # the score has a second part against one key head shared by all
    # (``latent_attention``): changes no tile and no list, names the scope
    latent: bool = False

    def __post_init__(self):
        S, B = self.S, self.diffusion_block
        if self.latent and (B is not None or self.window is not None
                            or not self.causal):
            raise ValueError("latent attention runs under the causal mask")
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
        if self.latent:
            return "bps.attn.mla"
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

    # ---- the walks, as lists of work items --------------------------- #

    @property
    def query_walk(self) -> "_Walk":
        """The live tiles by query tile, a query tile's by key tile
        ascending: the forward and the dQ kernel's grid axis."""
        return _walk(self, 1, True)

    def key_walk(self, groups: int) -> "_Walk":
        """The live tiles by key tile, a key tile's once for each of
        the ``groups`` query heads that share its key head, a head's by
        query tile ascending: the dK/dV kernel's grid axis."""
        return _walk(self, groups, False)

    @property
    def key_steps(self) -> int:
        """The longest walk of a query tile over its key tiles."""
        return int(np.bincount(self.query_walk.q_tile).max())

    @property
    def query_steps(self) -> int:
        """The longest walk of a key tile over its query tiles."""
        return int(np.bincount(self.query_walk.k_tile).max())

    def whole(self, i, j):
        """Whether the mask lets every pair of tile ``(i, j)`` through
        (``seen`` is then all true and the kernels leave it out);
        indices or arrays of them."""
        bq, bk, B = self.block_q, self.block_k, self.diffusion_block
        i, j = np.asarray(i), np.asarray(j)
        if B is None:
            if not self.causal:
                return np.ones(np.broadcast(i, j).shape, bool)
            # the tile's first query against its last key, and under a
            # window its last query against its first key
            whole = i * bq >= j * bk + bk - 1
            if self.window is not None:
                whole &= i * bq + bq - 1 - j * bk < self.window
            return whole
        hq, hk = self.nq // 2, self.nk // 2
        q_noised, k_noised = i < hq, j < hk
        q_first, k_first = (i - hq * ~q_noised) * bq, (j - hk * ~k_noised) * bk
        q_lo, q_hi = q_first // B, (q_first + bq - 1) // B     # its blocks
        k_lo, k_hi = k_first // B, (k_first + bk - 1) // B
        # noised under noised: one block both; a clean key under a
        # noised query: every block of the keys before the queries';
        # clean under clean: up to them. A noised key under a clean
        # query is never seen
        return np.where(
            k_noised, q_noised & (q_lo == q_hi) & (k_lo == k_hi)
            & (q_lo == k_lo),
            np.where(q_noised, k_hi < q_lo, k_hi <= q_lo))

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


class _Walk(NamedTuple):
    """A kernel's inner grid axis: one work item a live tile (under
    the key walk, a live tile and query head of the group), as int32
    columns the kernel gets by scalar prefetch. ``first`` / ``last``:
    the item opens / closes the run of its output tile (the query tile
    under the query walk, the key tile under the key walk); ``tested``:
    the tile holds a pair the mask bars, so its scores take the
    in-tile test. No item is dead."""

    q_tile: np.ndarray
    k_tile: np.ndarray
    first: np.ndarray
    last: np.ndarray
    tested: np.ndarray
    head: np.ndarray        # within the group; 0 under the query walk


_COLUMNS = len(_Walk._fields)   # a kernel's first arguments: the walk


@functools.lru_cache(maxsize=None)
def _walk(tiles: _Tiles, heads: int, by_query: bool) -> _Walk:
    """The work items of the output tiles (query tiles if ``by_query``,
    else key tiles), each tile's walk once for each of ``heads``. The
    masks are static, so the lists are made now, whatever trace this is
    called under, and once a process for a mask and shape (a ``_Tiles``
    hashes by its fields); the columns are shared, so read-only."""
    tiles_of, n = (tiles.key_tiles, tiles.nq) if by_query \
        else (tiles.query_tiles, tiles.nk)
    with jax.ensure_compile_time_eval():
        intervals = [(np.broadcast_to(np.asarray(lo), (n,)),
                      np.broadcast_to(np.asarray(hi), (n,)))
                     for lo, hi in tiles_of(jnp.arange(n))]
    held, walked, head = [], [], []
    for a in range(n):
        run = np.concatenate([np.arange(lo[a], hi[a] + 1)
                              for lo, hi in intervals])
        walked.append(np.tile(run, heads))
        head.append(np.repeat(np.arange(heads), run.size))
        held.append(np.full(run.size * heads, a))
    held, walked, head = (np.concatenate(c).astype(np.int32)
                          for c in (held, walked, head))
    q_tile, k_tile = (held, walked) if by_query else (walked, held)
    edge = np.flatnonzero(np.diff(held)) + 1      # where the runs change
    first = np.zeros(held.size, np.int32)
    last = np.zeros(held.size, np.int32)
    first[np.r_[0, edge]] = 1
    last[np.r_[edge - 1, held.size - 1]] = 1
    walk = _Walk(q_tile, k_tile, first, last,
                 (~tiles.whole(q_tile, k_tile)).astype(np.int32), head)
    for column in walk:
        column.flags.writeable = False
    return walk


def _step(intervals, t):
    """(tile, live) at step ``t`` of a walk through ``intervals`` in
    order, for the blockwise path's scan of ``key_steps`` steps: past
    the walk's last tile the index stays there and ``live`` is false."""
    (lo, hi), *more = intervals
    if not more:
        return jnp.minimum(lo + t, hi), lo + t <= hi
    (lo2, hi2), = more
    n1 = jnp.maximum(hi - lo + 1, 0)
    n = n1 + jnp.maximum(hi2 - lo2 + 1, 0)
    at = jnp.minimum(t, n - 1)
    return jnp.where(at < n1, lo + at, lo2 + at - n1), t < n


def _tiled_attention(q, k, v, tiles: _Tiles, remat: bool, shared=None):
    """Query blocks outside, key blocks inside: a query block folds only
    the key blocks the mask gives it (``lax.cond`` skips the steps past
    its walk's last), so a causal layer does half the score work of the
    all-pairs scan, a window layer ``(W + block_q) / S`` of it and a
    block-diffusion layer a quarter. Same fold, same result.
    ``shared``: ``(q_r [B,S,H,Dr], k_r [B,S,1,Dr])``, the score's second
    part (``latent_attention``): the queries' columns are joined to
    ``q`` once, the one shared key head to each key TILE as it is
    folded, so the transpose sums its gradient over the heads."""
    if shared is not None:
        q = jnp.concatenate([q, shared[0]], axis=-1)
    B, S, H, D = q.shape                              # D: the score's width
    Hkv, Dv = k.shape[2], v.shape[-1]
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
                if shared is not None:
                    kr = jax.lax.dynamic_slice_in_dim(
                        shared[1], start, block_k, 1).astype(jnp.float32)
                    kb = jnp.concatenate([kb, jnp.broadcast_to(
                        kr, (*kb.shape[:3], kr.shape[-1]))], axis=-1)
                if groups > 1:
                    kb = jnp.repeat(kb, groups, axis=2)
                    vb = jnp.repeat(vb, groups, axis=2)
                return _fold(qb, kb, vb, tiles.seen(i, j), *carry, scale)

            return jax.lax.cond(live, fold_tile, lambda c: c, carry), None

        m0 = jnp.full((B, H, block_q), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, block_q), jnp.float32)
        o0 = jnp.zeros((B, block_q, H, Dv), jnp.float32)
        (m, l, o), _ = jax.lax.scan(
            jax.checkpoint(fold) if remat else fold, (m0, l0, o0),
            jnp.arange(tiles.key_steps))
        l = jnp.maximum(l, 1e-30)
        return (o / l.transpose(0, 2, 1)[..., None]).astype(q.dtype)

    fn = jax.checkpoint(one_q_block) if remat else one_q_block
    qs = q.reshape(B, tiles.nq, block_q, H, D).transpose(1, 0, 2, 3, 4)
    out = jax.lax.map(lambda a: fn(*a), (jnp.arange(tiles.nq), qs))
    return out.transpose(1, 0, 2, 3, 4).reshape(B, S, H, Dv)


def blockwise_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        causal: bool = True, block_k: int = 512,
                        remat: bool = True, window: Optional[int] = None,
                        block_q: Optional[int] = None,
                        diffusion_block: Optional[int] = None,
                        shared=None) -> jnp.ndarray:
    """Exact attention streaming over KV blocks: peak residency
    O(S * block_k) instead of O(S^2). q [B,S,H,D], k/v [B,S,Hkv,D].
    ``window``, ``diffusion_block`` (see the module's head) or
    ``block_q`` selects the walk over query blocks that leaves out the
    key blocks the mask does not touch; so does ``shared`` (``(q_r,
    k_r)``: ``latent_attention``'s score in two parts, v of a width of
    its own)."""
    B, S, H, D = q.shape
    if window is not None or block_q is not None \
            or diffusion_block is not None or shared is not None:
        return _tiled_attention(
            q, k, v, _tiles(S, block_q or block_k, block_k, causal, window,
                            diffusion_block, shared is not None), remat,
            shared)
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
           window: Optional[int], diffusion_block: Optional[int],
           latent: bool = False) -> _Tiles:
    """The mask on the tile grid the callers ask for, a tile never
    longer than what it tiles (the sequence; a half of it under the
    block-diffusion mask)."""
    most = S if diffusion_block is None else max(S // 2, 1)
    return _Tiles(S, min(block_q, most), min(block_k, most), causal, window,
                  diffusion_block, latent)


def _scores(q, kb, i, j, *, tiles: _Tiles, scale: float, tested: bool,
            shared=None):
    """[bq, bk] f32 scores of query tile ``i`` against key tile ``j``,
    masked as ``tiles`` says where the tile is ``tested``, as they are
    where the mask lets the whole tile through. Operands go to the MXU
    in the type they came in (bf16 stays bf16); the scores are f32.
    ``shared``: the tiles ``(q_r, k_r)`` of the score's second part."""
    s = jax.lax.dot_general(
        q, kb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if shared is not None:
        s = s + jax.lax.dot_general(
            *shared, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    s = s * scale
    seen = tiles.seen(i, j) if tested else None
    return s if seen is None else jnp.where(seen, s, _NEG_INF)


def _shared_refs(refs, tiles: _Tiles):
    """(the refs ``(q_r, k_r)`` of the score's second part or None, the
    refs after them): under ``tiles.latent`` they follow a kernel's
    other inputs."""
    return (tuple(refs[:2]), refs[2:]) if tiles.latent else (None, refs)


def _tile_of(shared):
    return None if shared is None else tuple(r[0, 0] for r in shared)


def _item(pl, walk):
    """What a kernel reads of its work item, the grid's last axis:
    (the ``_Walk`` columns' entries, ``on_tile``). ``on_tile(body)``
    runs ``body(tested)`` for the item's tile: with the in-tile test
    where the tile holds a barred pair, without it where it does not."""
    t = pl.program_id(2)
    item = _Walk(*(column[t] for column in walk))

    def on_tile(body):
        pl.when(item.tested == 1)(lambda: body(True))
        pl.when(item.tested == 0)(lambda: body(False))

    return item, on_tile


def _flash_fwd_kernel(*refs, tiles: _Tiles, scale: float, with_lse: bool):
    """Grid (B, H, items of the query walk) — innermost sequential
    ("arbitrary"): scratch carries the online softmax state across one
    query tile's key tiles for one [block_q, D] output tile, from the
    tile's first item to its last."""
    import jax.experimental.pallas as pl

    walk, (q_ref, k_ref, v_ref, *rest) = refs[:_COLUMNS], refs[_COLUMNS:]
    shared, (o_ref, *rest) = _shared_refs(rest, tiles)
    # with the row logsumexp asked for (the backward's residual), it is
    # one more output before the scratch
    lse_ref = rest[0] if with_lse else None
    acc_ref, m_ref, l_ref = rest[-3:]
    item, on_tile = _item(pl, walk)

    @pl.when(item.first == 1)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    @on_tile
    def _compute(tested):
        # softmax state and the accumulator are f32
        vb = v_ref[0, 0]
        s = _scores(q_ref[0, 0], k_ref[0, 0], item.q_tile, item.k_tile,
                    tiles=tiles, scale=scale, tested=tested,
                    shared=_tile_of(shared))                  # [bq, bk]
        m_prev = m_ref[:]                         # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)            # [bq, 1]
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new
        l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=-1, keepdims=True)

    @pl.when(item.last == 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)
                       ).astype(o_ref.dtype)
        if lse_ref is not None:
            # every lane gets its row's value: spread here, once a query
            # tile, and not with the state once an item
            lse_ref[0, 0] = jnp.broadcast_to(
                m_ref[:] + jnp.log(jnp.maximum(l_ref[:], 1e-30)),
                lse_ref.shape[2:])


_LANES = 128    # a row statistic is kept once a lane, [rows, 128]


def _walk_call(kernel, walk: _Walk, operands, grid, in_specs, out_specs,
               out_shape, scratch_shapes, tiles: _Tiles, interpret: bool):
    """The ``pallas_call`` of all three kernels, made under the mask's
    scope: the walk's columns as scalar prefetch, a grid (rows, heads,
    work items) with the items in order (``ops/grouped_matmul.py``
    hands its work items over the same way)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    with jax.named_scope(tiles.scope):
        return pl.pallas_call(
            kernel, out_shape=out_shape,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(walk),
                grid=(*grid, len(walk.q_tile)), in_specs=in_specs,
                out_specs=out_specs, scratch_shapes=scratch_shapes),
            compiler_params=pltpu.CompilerParams(dimension_semantics=(
                "parallel", "parallel", "arbitrary")),
            interpret=interpret)(*map(jnp.asarray, walk), *operands)


def _query_walk_specs(pl, tiles: _Tiles, groups: int):
    """Block specs of a grid (B, H, items of the query walk) that holds
    a query tile while its items last and fetches each item's key tile
    (the forward kernel and the dQ kernel): ``q_tile(width)`` for a
    query-side operand, ``kv_tile(width)`` for k and v of the head's
    group; ``kv_tile(width, shared=True)`` for the one key head every
    query head shares."""
    def q_tile(width):
        return pl.BlockSpec(
            (1, 1, tiles.block_q, width),
            lambda b, h, t, *walk: (b, h, _Walk(*walk).q_tile[t], 0))

    def kv_tile(width, shared=False):
        return pl.BlockSpec(
            (1, 1, tiles.block_k, width),
            lambda b, h, t, *walk: (b, 0 if shared else h // groups,
                                    _Walk(*walk).k_tile[t], 0))

    return q_tile, kv_tile


def _flash_fwd(q, k, v, causal: bool, block_q: int, block_k: int,
               interpret: bool = False, window: Optional[int] = None,
               with_lse: bool = False,
               diffusion_block: Optional[int] = None, shared=None):
    """``shared``: ``(q_r [B,S,H,Dr], k_r [B,S,1,Dr])``, the score's
    second part (``latent_attention``); v may then be of another width
    than q and k, and the output is of v's."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, H, D = q.shape
    Hkv, Dv = k.shape[2], v.shape[-1]
    groups = H // Hkv
    tiles = _tiles(S, block_q, block_k, causal, window, diffusion_block,
                   shared is not None)
    Dr = shared[0].shape[-1] if tiles.latent else 0
    scale = 1.0 / np.sqrt(D + Dr)

    # [B,H,S,D] layout: one (b, h, tile) per grid step
    operands = tuple(a.transpose(0, 2, 1, 3) for a in (q, k, v, *(shared or ())))

    kernel = functools.partial(_flash_fwd_kernel, tiles=tiles, scale=scale,
                               with_lse=with_lse)
    q_tile, kv_tile = _query_walk_specs(pl, tiles, groups)
    in_specs = [q_tile(D), kv_tile(D), kv_tile(Dv)]
    if tiles.latent:
        in_specs += [q_tile(Dr), kv_tile(Dr, shared=True)]
    out_specs = [q_tile(Dv)]
    out_shape = [jax.ShapeDtypeStruct((B, H, S, Dv), q.dtype)]
    if with_lse:
        out_specs.append(q_tile(_LANES))
        out_shape.append(jax.ShapeDtypeStruct((B, H, S, _LANES),
                                              jnp.float32))
    out = _walk_call(
        kernel, tiles.query_walk, operands, (B, H), in_specs, out_specs,
        out_shape,
        [pltpu.VMEM((tiles.block_q, Dv), jnp.float32),      # acc
         pltpu.VMEM((tiles.block_q, 1), jnp.float32),       # max
         pltpu.VMEM((tiles.block_q, 1), jnp.float32)],      # denom
        tiles, interpret)
    o = out[0].transpose(0, 2, 1, 3)  # back to [B,S,H,Dv]
    return (o, out[1]) if with_lse else o


# --------------------------------------------------------------------- #
# Pallas backward kernels
# --------------------------------------------------------------------- #


def _softmax_grad(q, kb, vb, o, do, lse, item: _Walk, tested: bool, *,
                  tiles: _Tiles, scale: float, shared=None):
    """(p, ds), both [bq, bk] f32: the tile's probabilities recomputed
    from the saved row logsumexp, and the scores' cotangent times the
    scale, ``p * (do v^T - rowsum(o * do)) * scale``."""
    s = _scores(q, kb, item.q_tile, item.k_tile, tiles=tiles, scale=scale,
                tested=tested, shared=shared)
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(
        do, vb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1, keepdims=True)
    return p, p * (dp - delta) * scale


def _flash_dkv_kernel(*refs, tiles: _Tiles, scale: float):
    """Grid (B, Hkv, items of the key walk) — innermost sequential: one
    key tile's dK and dV, summed over the query heads that share the
    key head and, a head, over the query tiles that see it."""
    import jax.experimental.pallas as pl

    walk, (q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, *rest) = \
        refs[:_COLUMNS], refs[_COLUMNS:]
    shared, rest = _shared_refs(rest, tiles)
    # outputs, then as many accumulators: dK, dV and, of the score's
    # second part, this key head's share of the shared key's gradient
    outs, accs = rest[:len(rest) // 2], rest[len(rest) // 2:]
    dk_acc, dv_acc = accs[:2]
    item, on_tile = _item(pl, walk)

    @pl.when(item.first == 1)
    def _init():
        for acc in accs:
            acc[:] = jnp.zeros_like(acc)

    @on_tile
    def _compute(tested):
        q, do = q_ref[0, 0], do_ref[0, 0]
        p, ds = _softmax_grad(q, k_ref[0, 0], v_ref[0, 0], o_ref[0, 0], do,
                              lse_ref[0, 0][:, :1], item, tested,
                              tiles=tiles, scale=scale,
                              shared=_tile_of(shared))
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if shared is not None:
            qr = shared[0][0, 0]
            accs[2][:] += jax.lax.dot_general(
                ds.astype(qr.dtype), qr, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(item.last == 1)
    def _finish():
        for out, acc in zip(outs, accs):
            out[0, 0] = acc[:].astype(out.dtype)


def _flash_dq_kernel(*refs, tiles: _Tiles, scale: float):
    """Grid (B, H, items of the query walk) — the forward's: one query
    tile's dQ, summed over the key tiles it sees."""
    import jax.experimental.pallas as pl

    walk, (q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, *rest) = \
        refs[:_COLUMNS], refs[_COLUMNS:]
    shared, rest = _shared_refs(rest, tiles)
    # dQ and, of the score's second part, dQ_r; then as many accumulators
    outs, accs = rest[:len(rest) // 2], rest[len(rest) // 2:]
    item, on_tile = _item(pl, walk)

    @pl.when(item.first == 1)
    def _init():
        for acc in accs:
            acc[:] = jnp.zeros_like(acc)

    @on_tile
    def _compute(tested):
        kb = k_ref[0, 0]
        _, ds = _softmax_grad(q_ref[0, 0], kb, v_ref[0, 0], o_ref[0, 0],
                              do_ref[0, 0], lse_ref[0, 0][:, :1], item,
                              tested, tiles=tiles, scale=scale,
                              shared=_tile_of(shared))
        accs[0][:] += jax.lax.dot_general(
            ds.astype(kb.dtype), kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if shared is not None:
            kr = shared[1][0, 0]
            accs[1][:] += jax.lax.dot_general(
                ds.astype(kr.dtype), kr, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(item.last == 1)
    def _finish():
        for out, acc in zip(outs, accs):
            out[0, 0] = acc[:].astype(out.dtype)


def _flash_bwd(q, k, v, o, lse, do, causal: bool, block_q: int,
               block_k: int, window: Optional[int] = None,
               interpret: bool = False,
               diffusion_block: Optional[int] = None, shared=None):
    """(dq, dk, dv) of ``_flash_fwd`` from its output, its row
    logsumexp ([B,H,S,128], every lane the row's value) and the
    output's cotangent. Two kernels: dK/dV per key tile (the query
    heads of a group folded into the walk, so the sums are complete and
    [B,Hkv,S,D]) and dQ per query tile; both walk only the tiles the
    mask touches. With ``shared`` (``_flash_fwd``'s) also (dq_r, dk_r):
    the dK/dV kernel gives each key head's float32 share of the shared
    key's gradient, [B,Hkv,S,Dr], summed over the heads here, once."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, H, D = q.shape
    Hkv, Dv = k.shape[2], v.shape[-1]
    groups = H // Hkv
    tiles = _tiles(S, block_q, block_k, causal, window, diffusion_block,
                   shared is not None)
    block_q, block_k = tiles.block_q, tiles.block_k
    Dr = shared[0].shape[-1] if tiles.latent else 0
    mask = dict(tiles=tiles, scale=1.0 / np.sqrt(D + Dr))
    operands = tuple(a.transpose(0, 2, 1, 3) for a in (q, k, v, o, do)) \
        + (lse,) + tuple(a.transpose(0, 2, 1, 3) for a in (shared or ()))

    def struct(heads, width, dtype):
        return jax.ShapeDtypeStruct((B, heads, S, width), dtype)

    # ---- dK, dV: key head g; an item is a key tile, a query head of
    # the group and a query tile that sees the key tile ----------------- #
    def q_side(width):
        def index(b, g, t, *walk):
            walk = _Walk(*walk)
            return b, g * groups + walk.head[t], walk.q_tile[t], 0
        return pl.BlockSpec((1, 1, block_q, width), index)

    def k_side(width, shared=False):
        return pl.BlockSpec(
            (1, 1, block_k, width),
            lambda b, g, t, *walk: (b, 0 if shared else g,
                                    _Walk(*walk).k_tile[t], 0))

    in_specs = [q_side(D), k_side(D), k_side(Dv), q_side(Dv), q_side(Dv),
                q_side(_LANES)]
    out_specs = [k_side(D), k_side(Dv)]
    out_shape = [struct(Hkv, D, k.dtype), struct(Hkv, Dv, v.dtype)]
    scratch = [pltpu.VMEM((block_k, D), jnp.float32),
               pltpu.VMEM((block_k, Dv), jnp.float32)]
    if tiles.latent:
        in_specs += [q_side(Dr), k_side(Dr, shared=True)]
        out_specs.append(k_side(Dr))
        out_shape.append(struct(Hkv, Dr, jnp.float32))
        scratch.append(pltpu.VMEM((block_k, Dr), jnp.float32))
    dk, dv, *dkr = _walk_call(
        functools.partial(_flash_dkv_kernel, **mask), tiles.key_walk(groups),
        operands, (B, Hkv), in_specs, out_specs, out_shape, scratch, tiles,
        interpret)
    if tiles.latent:
        dkr = [jnp.sum(dkr[0], axis=1, keepdims=True).astype(shared[1].dtype)]

    # ---- dQ: the forward's walk -------------------------------------- #
    q_tile, kv_tile = _query_walk_specs(pl, tiles, groups)
    in_specs = [q_tile(D), kv_tile(D), kv_tile(Dv), q_tile(Dv), q_tile(Dv),
                q_tile(_LANES)]
    out_specs, out_shape = [q_tile(D)], [struct(H, D, q.dtype)]
    scratch = [pltpu.VMEM((block_q, D), jnp.float32)]
    if tiles.latent:
        in_specs += [q_tile(Dr), kv_tile(Dr, shared=True)]
        out_specs.append(q_tile(Dr))
        out_shape.append(struct(H, Dr, shared[0].dtype))
        scratch.append(pltpu.VMEM((block_q, Dr), jnp.float32))
    dq, *dqr = _walk_call(
        functools.partial(_flash_dq_kernel, **mask), tiles.query_walk,
        operands, (B, H), in_specs, out_specs, out_shape, scratch, tiles,
        interpret)
    return tuple(a.transpose(0, 2, 1, 3) for a in (dq, dk, dv, *dqr, *dkr))


def walk_sizes(S: int, groups: int, block_q: int, block_k: int,
               window: Optional[int] = None,
               diffusion_block: Optional[int] = None,
               latent: bool = False) -> dict:
    """What the kernels' work lists of this causal mask and shape hold,
    by gauge name:
    ``attention/<scope>/{items,tested_items,rect_steps}/{query,key}_walk``
    (the walk's work items a row and head, a row and key head under
    the key walk whose ``groups`` query heads are in its list; those
    that take the in-tile test; the steps a rectangular grid as long as
    the longest walk would make)."""
    tiles = _tiles(S, block_q, block_k, True, window, diffusion_block, latent)
    sizes = {}
    for walk, name, rect in (
            (tiles.query_walk, "query_walk", tiles.nq * tiles.key_steps),
            (tiles.key_walk(groups), "key_walk",
             tiles.nk * groups * tiles.query_steps)):
        for kind, value in (("items", walk.tested.size),
                            ("tested_items", int(walk.tested.sum())),
                            ("rect_steps", rect)):
            # a joined name, as ``jax/train.py _fold_stats`` makes the
            # step's statistics': a model's own instrument, none of
            # docs/observability.md's schema of a dense step
            sizes["/".join(("attention", tiles.scope, kind, name))] = value
    return sizes


def publish_walk_sizes(S: int, groups: int, block_q: int, block_k: int,
                       window: Optional[int] = None,
                       diffusion_block: Optional[int] = None,
                       latent: bool = False) -> None:
    """Set ``walk_sizes`` of this mask and shape as gauges in the
    process's metrics registry. Properties of the mask alone: a model
    calls this where it is traced, once a program."""
    from ..core.state import get_state

    registry = get_state().metrics
    for name, value in walk_sizes(S, groups, block_q, block_k, window,
                                  diffusion_block, latent).items():
        registry.gauge(name).set(value)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal: bool = True, block_q: int = 512,
                    block_k: int = 512, window: Optional[int] = None,
                    diffusion_block: Optional[int] = None):
    """Pallas flash attention (TPU): the forward kernel, and a backward
    of two more (dK/dV and dQ, scores recomputed from the saved row
    logsumexp). Off-TPU (tests, CPU mesh) both directions run the
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def latent_attention(q, q_r, k, k_r, v, block_q: int = 512,
                     block_k: int = 512):
    """Causal attention over a score in two parts (multi-head latent
    attention as it is trained, the projections not absorbed): ``s =
    (q . k + q_r . k_r) / sqrt(D + Dr)``, ``o = softmax(s) v``. q, k
    ``[B,S,H,D]`` a head its own; q_r ``[B,S,H,Dr]``; k_r ``[B,S,1,Dr]``,
    ONE (rotary) key head under every query head; v ``[B,S,H,Dv]``, and
    the output is ``[B,S,H,Dv]``. The kernels and the blockwise walk
    are ``flash_attention``'s, on the causal mask's lists, under
    ``bps.attn.mla``; k_r's gradient is the sum over the heads."""
    shared = (q_r, k_r)
    if jax.default_backend() == "tpu":
        return _flash_fwd(q, k, v, True, block_q, block_k, shared=shared)
    return blockwise_attention(q, k, v, block_k=block_k, block_q=block_q,
                               shared=shared)


def _latent_vjp_fwd(q, q_r, k, k_r, v, block_q, block_k):
    if jax.default_backend() == "tpu":
        out, lse = _flash_fwd(q, k, v, True, block_q, block_k,
                              with_lse=True, shared=(q_r, k_r))
        return out, (q, q_r, k, k_r, v, out, lse)
    out = latent_attention(q, q_r, k, k_r, v, block_q, block_k)
    return out, (q, q_r, k, k_r, v, None, None)


def _latent_vjp_bwd(block_q, block_k, res, g):
    q, q_r, k, k_r, v, out, lse = res
    if lse is not None:
        dq, dk, dv, dqr, dkr = _flash_bwd(
            q, k, v, out, lse, g, True, block_q, block_k,
            shared=(q_r, k_r))
        return dq, dqr, dk, dkr, dv
    # off-TPU: through the differentiable blockwise path, as
    # ``_flash_vjp_bwd``
    _, vjp = jax.vjp(
        lambda q_, qr_, k_, kr_, v_: blockwise_attention(
            q_, k_, v_, block_k=block_k, block_q=block_q,
            shared=(qr_, kr_)), q, q_r, k, k_r, v)
    return vjp(g)


latent_attention.defvjp(_latent_vjp_fwd, _latent_vjp_bwd)


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
