"""Mixtral-style sparse Mixture-of-Experts transformer with expert
parallelism over the ``ep`` mesh axis, and the expert layer other sparse
models (models/mellum.py) are built from.

The reference has no MoE / expert parallelism (SURVEY.md §2.8 — absent);
this is green-field TPU design:

- the router scores every token against ALL experts (float32 softmax,
  top-k, the k weights renormalised to sum to one);
- the layer is TOLD which experts it holds (``first`` and the leading
  dim of its expert leaves) and computes exactly the (token, expert)
  pairs routed to those: no capacity, so no pair is ever dropped,
  whatever the imbalance. What absent experts would add is left out of
  the sum, and nothing stands in for it;
- the held experts' three products are ONE grouped matrix product each
  (``ops/grouped_matmul.py grouped_matmul``) over the pairs sorted by
  expert. On the TPU that is a Pallas kernel family of this package
  (product, its transpose by the weights, its transpose by the rows),
  tiled for groups of some hundreds of rows against an expert's whole
  weights in VMEM: XLA:TPU's own ``ragged_dot`` kernel took a
  millisecond a call on a tenth of a millisecond of work at such
  groups (PERF.md section 6, PR 32). Off the TPU (tier-1, the CPU
  mesh) the same call is ``jax.lax.ragged_dot``;
- work follows the pairs routed here, not tokens x experts: shapes
  stay static, so the sorted pair buffer has a static number of rows,
  and that number is sized for the pairs this device can be expected
  to hold (``compact_rows``: twice what an even router sends to
  ``n_held`` of ``E`` experts). The held pairs sort first, so the
  buffer is the head of the sort: token rows gathered, the products,
  SwiGLU, the gate weighting and the sum back into the tokens all run
  on its rows, and rows past the held pairs are masked. A layer one of
  whose slices holds more pairs than that walks its slices through a
  buffer with a row for EVERY pair (the worst routing) instead, chosen
  by ``lax.cond`` on the router's own count: the bound costs speed
  there, never a pair. Where half the experts or more are held the two
  buffers are the same size and only the full-size path is traced.
  ``chunk`` bounds either buffer by walking the tokens in slices;
- expert parallelism = the experts dim sharded over ``ep``: the same
  grouped product sits between two ``lax.all_to_all`` calls (pairs to
  their expert's owner, results back); on one device it runs without
  the exchange;
- a layer may carry a SHARED expert (leaves ``shared_gate`` /
  ``shared_up`` / ``shared_down``): a dense SwiGLU every token passes,
  added to the held experts' sum. It is replicated, not a share: when
  the shares of a deployment are added up it counts once;
- attention/embedding reuse the Llama building blocks (models/llama.py).

The Mixtral model below adds the Switch load-balancing auxiliary loss.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.grouped_matmul import grouped_matmul, row_tile, visited_rows
from . import llama as L


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    n_experts: int = 8
    top_k: int = 2
    expert_hidden: int = 14336
    router_aux_weight: float = 0.01
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    def as_llama(self) -> L.LlamaConfig:
        """The attention-relevant subset as a LlamaConfig (for reusing the
        llama block helpers)."""
        return L.LlamaConfig(
            vocab_size=self.vocab_size, dim=self.dim, n_layers=self.n_layers,
            n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
            hidden_dim=self.expert_hidden, max_seq_len=self.max_seq_len,
            rope_theta=self.rope_theta, norm_eps=self.norm_eps,
            dtype=self.dtype, param_dtype=self.param_dtype, remat=False)

    @staticmethod
    def tiny(vocab_size: int = 256, seq: int = 64) -> "MoEConfig":
        return MoEConfig(vocab_size=vocab_size, dim=64, n_layers=2,
                         n_heads=4, n_kv_heads=2, n_experts=4, top_k=2,
                         expert_hidden=128, max_seq_len=seq, remat=False)

    @staticmethod
    def small(vocab_size: int = 32000) -> "MoEConfig":
        """Mixtral-flavored benchmark config at ~125M-active scale."""
        return MoEConfig(vocab_size=vocab_size, dim=768, n_layers=12,
                         n_heads=12, n_kv_heads=4, n_experts=8, top_k=2,
                         expert_hidden=2048, max_seq_len=2048)


# --------------------------------------------------------------------- #
# init
# --------------------------------------------------------------------- #

def init_params(rng: jax.Array, cfg: MoEConfig) -> Dict[str, Any]:
    k_emb, k_blk, k_out = jax.random.split(rng, 3)
    d, h, E = cfg.dim, cfg.expert_hidden, cfg.n_experts
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    Ln = cfg.n_layers

    def dense_init(key, shape, scale=None):
        fan_in = shape[-2] if len(shape) >= 2 else shape[0]
        scale = scale if scale is not None else 1.0 / np.sqrt(fan_in)
        return jax.random.normal(key, shape, cfg.param_dtype) * scale

    ks = jax.random.split(k_blk, 9)
    block = {
        "attn_norm": jnp.ones((Ln, d), cfg.param_dtype),
        "wq": dense_init(ks[0], (Ln, d, nh * hd)),
        "wk": dense_init(ks[1], (Ln, d, nkv * hd)),
        "wv": dense_init(ks[2], (Ln, d, nkv * hd)),
        "wo": dense_init(ks[3], (Ln, nh * hd, d)),
        "mlp_norm": jnp.ones((Ln, d), cfg.param_dtype),
        "router": dense_init(ks[4], (Ln, d, E), scale=0.02),
        "w_gate": dense_init(ks[5], (Ln, E, d, h)),
        "w_up": dense_init(ks[6], (Ln, E, d, h)),
        "w_down": dense_init(ks[7], (Ln, E, h, d)),
    }
    return {
        "embed": dense_init(k_emb, (cfg.vocab_size, d), scale=0.02),
        "blocks": block,
        "final_norm": jnp.ones((d,), cfg.param_dtype),
        "lm_head": dense_init(k_out, (d, cfg.vocab_size)),
    }


def param_count(params: Dict[str, Any]) -> int:
    return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))


# --------------------------------------------------------------------- #
# routing + expert layer
# --------------------------------------------------------------------- #

def route(x_flat: jnp.ndarray, router_w: jnp.ndarray, top_k: int,
          router_dtype: Any = jnp.float32, score: str = "softmax",
          select_bias: Optional[jnp.ndarray] = None,
          norm_eps: float = 0.0, scale: float = 1.0):
    """Top-k routing over ALL experts. x_flat [T, d], router_w [d, E].
    Returns (gates [T, k] f32, renormalised to sum to one; idx [T, k]
    int32; probs [T, E] f32: every expert's score). ``router_dtype`` is
    the matmul's and the score function's type: float32, always,
    outside a precision control.

    ``score``: ``"softmax"`` over the experts, or an independent
    ``"sigmoid"`` an expert. ``select_bias`` [E] is added to the scores
    for the SELECTION only: the k experts are the largest of ``score +
    select_bias``, their gates are the scores without it (a balancing
    buffer, no parameter: it gets no gradient). The gates are divided
    by their sum plus ``norm_eps`` and multiplied by ``scale``. The
    defaults are softmax top-k renormalised, operation for operation
    what this function was before it had these arguments."""
    if score not in ("softmax", "sigmoid"):
        raise ValueError(f"score {score!r}: 'softmax' or 'sigmoid'")
    with jax.named_scope("bps.moe.route"):
        logits = jnp.matmul(x_flat.astype(router_dtype),
                            router_w.astype(router_dtype),
                            preferred_element_type=router_dtype)
        probs = (jax.nn.softmax(logits, axis=-1) if score == "softmax"
                 else jax.nn.sigmoid(logits)).astype(jnp.float32)
        if select_bias is None:
            gates, idx = jax.lax.top_k(probs, top_k)
        else:
            _, idx = jax.lax.top_k(
                probs + jax.lax.stop_gradient(
                    select_bias.astype(jnp.float32)), top_k)
            gates = jnp.take_along_axis(probs, idx, axis=-1)
        total = jnp.sum(gates, axis=-1, keepdims=True)
        gates = gates / (total + norm_eps if norm_eps else total)
        if scale != 1.0:
            gates = gates * scale
    return gates, idx.astype(jnp.int32), probs


def bias_moved_pairs(probs: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """The selection bias at work: the (token, slot) pairs of ``idx``
    [T, k] whose expert is not among the token's ``k`` largest of
    ``probs`` [T, E] alone. int32 scalar; 0 where no bias was added."""
    _, plain = jax.lax.top_k(probs, idx.shape[-1])
    kept = jnp.any(idx[:, :, None] == plain[:, None, :], axis=-1)
    return jnp.sum(~kept, dtype=jnp.int32)


def bias_rows(expert_bias, n_experts: int, first: int, n: int):
    """Rows ``first .. first + n - 1`` of a selection bias ``[sparse
    layers, n_experts]``, without a gradient; none is zeros: what a run
    of ``n`` sparse layers scans beside its leaves (``ops/chain.py
    Run(each=)``)."""
    if expert_bias is None:
        return jnp.zeros((n, n_experts), jnp.float32)
    return jax.lax.stop_gradient(expert_bias[first:first + n])


def run_stats(stacked: Dict[str, Any], first: int, n_sparse: int):
    """The statistics of a run's layers, stacked on a leading axis, as
    the run's share of a step's: ``moe/expert_load`` in rows ``first
    ..`` of the ``[n_sparse, n_held]`` table, zero elsewhere (the runs'
    tables add up to the step's); every other count summed over the
    layers."""
    out = {name: jnp.sum(v, axis=0) for name, v in stacked.items()
           if name != "moe/expert_load"}
    if "moe/expert_load" in stacked:
        load = stacked["moe/expert_load"]
        out["moe/expert_load"] = jnp.zeros(
            (n_sparse, load.shape[1]), load.dtype
        ).at[first:first + load.shape[0]].set(load)
    return out


def switch_aux_loss(probs: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Switch aux loss: E * sum_e f_e * p_e (f = token fraction routed
    to e on the primary choice, p = mean router prob)."""
    E = probs.shape[-1]
    prime = jax.nn.one_hot(idx[:, 0], E, dtype=jnp.float32)
    return E * jnp.sum(jnp.mean(prime, axis=0) * jnp.mean(probs, axis=0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rows_in_order(x, order, inv, k):
    """``x[order // k]``: row ``r`` of the result is the token of the
    ``r``-th pair in sorted order (pairs are token-major, ``k`` a
    token). ``order`` is a permutation and ``inv`` its inverse, so the
    transpose is a gather too (never a scatter-add): un-sort the
    cotangent and sum each token's ``k`` rows."""
    return x[order // k]


def _rows_in_order_fwd(x, order, inv, k):
    return x[order // k], (inv, x.shape[0])


def _rows_in_order_bwd(k, res, g):
    inv, T = res
    return g[inv].reshape(T, k, g.shape[-1]).sum(axis=1), None, None


_rows_in_order.defvjp(_rows_in_order_fwd, _rows_in_order_bwd)


def _sorted_pairs(key: jnp.ndarray, n_held: int):
    """The pairs of ``key`` ([N] int32, ``n_held`` where a pair's expert
    is not held here) sorted by held expert. Returns (order, inv, load):
    ``order[r]`` is the pair in sorted row ``r`` (the sort is stable and
    ``n_held`` sorts last, so the first ``sum(load)`` rows are exactly
    the held pairs, grouped by expert), ``inv`` its inverse, ``load``
    [n_held] int32 the pairs per held expert."""
    N = key.shape[0]
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    inv = jnp.zeros((N,), jnp.int32).at[order].set(
        jnp.arange(N, dtype=jnp.int32))
    load = jnp.sum(key[:, None] == jnp.arange(n_held)[None, :],
                   axis=0, dtype=jnp.int32)
    return order, inv, load


def _dropped_pairs(key, inv, load, rows):
    """The router's choice against the product's groups: a held pair is
    computed where its sorted row lies among the rows the grouped
    product is told to give that pair's expert, inside the ``rows`` the
    buffer that ran had. Counts the held pairs of which that is not
    true."""
    n_held = load.shape[0]
    end = jnp.cumsum(load)
    group = jnp.sum(inv[:, None] >= end[None, :], axis=1)
    held = key < n_held
    return jnp.sum(held & ((group != key) | (inv >= rows)), dtype=jnp.int32)


def _full_rows(x, order, inv, load, k, w_gate, w_up, w_down, dtype):
    """The products over a buffer with a row for EVERY pair (the worst
    routing). Returns y [N, d] in pair order, zero rows where the
    pair's expert is not held."""
    N = order.shape[0]
    # rows past the routed pairs belong to no group: the grouped
    # product may leave them unwritten, so they are masked on the
    # way in, between the products and on the way out (the
    # cotangents with them)
    routed = (jnp.arange(N) < jnp.sum(load))[:, None]
    xs = jnp.where(routed, _rows_in_order(x, order, inv, k), 0)
    gate = grouped_matmul(xs, w_gate.astype(dtype), load)
    up = grouped_matmul(xs, w_up.astype(dtype), load)
    h = jnp.where(routed, jax.nn.silu(gate) * up, 0)
    ys = jnp.where(routed,
                   grouped_matmul(h, w_down.astype(dtype), load), 0)
    # back to pair order: again a permutation, k = 1
    return _rows_in_order(ys, inv, order, 1)


def grouped_ffn(x: jnp.ndarray, key: jnp.ndarray, k: int, w_gate, w_up,
                w_down, dtype
                ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """SwiGLU of the held experts over the pairs routed to them, on the
    full-size buffer.

    x [T, d]; ``key`` [T * k] int32, token-major: the held expert (0 ..
    n_held - 1) pair ``t * k + c`` is routed to, or ``n_held`` where
    that pair's expert is not held here. Returns (y [T * k, d] in pair
    order, zero rows where ``key == n_held``; load [n_held] int32, the
    pairs per held expert; dropped: the held pairs whose sorted row
    lies outside their expert's group). Nothing is dropped: the sorted
    buffer has a row for every pair."""
    with jax.named_scope("bps.moe.experts"):
        order, inv, load = _sorted_pairs(key, w_gate.shape[0])
        y = _full_rows(x, order, inv, load, k, w_gate, w_up, w_down, dtype)
        dropped = _dropped_pairs(key, inv, load, key.shape[0])
    return y, load, dropped


# The compact buffer has rows for this many times the pairs an even
# router sends here: an ordinary slice fits it with room to spare, at a
# quarter of the full buffer's rows where an eighth of the experts is
# held. A layer with a slice that holds more takes the full-size
# buffer: the bound costs speed there, never a pair. One compact size,
# not a ladder of them: every size is one more copy of the grouped
# products' kernels in the step program (PERF.md section 6, PR 29).
_COMPACT_OVER_EVEN = 2
_ROW_TILE = 16          # rows of one packed bfloat16 tile


def compact_rows(n_pairs: int, n_held: int, n_experts: int) -> int:
    """Rows of the compact sorted buffer for a slice of ``n_pairs``
    pairs on a device that holds ``n_held`` of ``n_experts``: a static
    function of what the layer is told. ``n_pairs`` (no compact
    buffer) where half the experts or more are held."""
    even = -(-n_pairs * n_held // n_experts)
    rows = -(-_COMPACT_OVER_EVEN * even // _ROW_TILE) * _ROW_TILE
    return min(n_pairs, rows)


def _full_out(x, w, order, inv, load, w_gate, w_up, w_down, dtype):
    """One slice's output through the full-size buffer: x [T, d], w
    [T, k] (the gates, 0 where the expert is not held) -> [T, d]."""
    T, k = w.shape
    y = _full_rows(x, order, inv, load, k, w_gate, w_up, w_down, dtype)
    out = jnp.einsum("tkd,tk->td", y.reshape(T, k, -1), w.astype(dtype),
                     preferred_element_type=jnp.float32)
    return out.astype(dtype)


def _compact_out(x, w, head, load, w_gate, w_up, w_down, dtype):
    """The same output through the compact buffer, for a slice that
    holds no more pairs than it has rows: ``head`` [C] is the head of
    the sort, the held pairs first and by expert. Token rows gathered,
    the three products, the gate weighting and the sum back into the
    tokens all run on ``C`` rows; only index vectors are ``T * k``
    long."""
    T, k = w.shape
    tok = head // k
    held = (jnp.arange(head.shape[0]) < jnp.sum(load))[:, None]
    # masked as on the full-size path: rows past the held pairs are in
    # no group
    xs = jnp.where(held, x[tok], 0)
    gate = grouped_matmul(xs, w_gate.astype(dtype), load)
    up = grouped_matmul(xs, w_up.astype(dtype), load)
    h = jnp.where(held, jax.nn.silu(gate) * up, 0)
    ys = jnp.where(held,
                   grouped_matmul(h, w_down.astype(dtype), load), 0)
    # the gates' product and sum in float32, as the full path's einsum
    gate_of = w.reshape(-1)[head].astype(dtype).astype(jnp.float32)
    out = jax.ops.segment_sum(ys.astype(jnp.float32) * gate_of[:, None],
                              tok, num_segments=T)
    return out.astype(dtype)


def _held_here(idx, first, n_held):
    """[T, k] bool: the router's choices that fall on the ``n_held``
    experts from ``first``, and their index among those."""
    local = idx - first
    return (local >= 0) & (local < n_held), local


def _held_chunk(x, gates, idx, first, w_gate, w_up, w_down, dtype, rows):
    """The held experts' part of the layer's output for one slice of
    tokens, through a sorted buffer of ``rows`` rows (every pair's, or
    ``compact_rows``): x [T, d], gates/idx [T, k]. Returns (y [T, d],
    load, dropped)."""
    T, k = idx.shape
    n_held = w_gate.shape[0]
    here, local = _held_here(idx, first, n_held)
    key = jnp.where(here, local, n_held).reshape(-1)
    w = jnp.where(here, gates, 0.0)
    leaves = (w_gate, w_up, w_down, dtype)
    with jax.named_scope("bps.moe.experts"):
        order, inv, load = _sorted_pairs(key, n_held)
        out = _full_out(x, w, order, inv, load, *leaves) if rows == T * k \
            else _compact_out(x, w, order[:rows], load, *leaves)
        dropped = _dropped_pairs(key, inv, load, rows)
    return out, load, dropped


def _held_slices(x, gates, idx, w_gate, w_up, w_down, *, first, dtype, n,
                 rows):
    """The held experts' part of the layer for all its tokens, walked
    in ``n`` slices, each through a sorted buffer of ``rows`` rows: x
    [T, d], gates/idx [T, k]. Returns (y [T, d], load, dropped)."""
    T, d = x.shape
    held = (first, w_gate, w_up, w_down, dtype, rows)
    if n == 1:
        return _held_chunk(x, gates, idx, *held)

    @jax.checkpoint
    def one(args):
        return _held_chunk(*args, *held)

    out, loads, drops = jax.lax.map(one, (
        x.reshape(n, T // n, d), gates.reshape(n, T // n, -1),
        idx.reshape(n, T // n, -1)))
    return out.reshape(T, d), jnp.sum(loads, axis=0), jnp.sum(drops)


def _held(x, gates, idx, first, n_experts, w_gate, w_up, w_down, dtype, n):
    """``_held_slices`` through the buffer the routing allows. The
    sorted buffer is sized for the pairs this device can be expected to
    hold (``compact_rows``); a layer with a slice that holds more walks
    its slices through the full-size one, chosen by the count of held
    pairs in the router's choice. Returns (y, load, dropped, the
    slices that went through the compact buffer: ``n`` or 0).

    The conditional sits around the walk, not inside it: the expert
    leaves' cotangents then leave a branch as the walk's finished sums;
    a conditional a slice returns them a slice, 0.2 GB of float32
    beside the sums they are added to. Each branch is a
    ``jax.checkpoint`` of its own: a differentiated ``cond`` returns
    both branches' residuals and zero-fills the untaken one's; this
    way the residuals are the operands."""
    T, k = idx.shape
    n_held = w_gate.shape[0]
    N = T // n * k
    C = compact_rows(N, n_held, n_experts)
    walk = functools.partial(_held_slices, first=first, dtype=dtype, n=n)
    operands = (x, gates, idx, w_gate, w_up, w_down)
    if C == N:                              # one path, at trace time
        return *walk(*operands, rows=N), jnp.zeros((), jnp.int32)
    pairs = jnp.sum(_held_here(idx, first, n_held)[0].reshape(n, -1), axis=1)
    fits = jnp.all(pairs <= C)
    return *jax.lax.cond(
        fits, jax.checkpoint(functools.partial(walk, rows=C)),
        jax.checkpoint(functools.partial(walk, rows=N)), *operands), \
        n * fits.astype(jnp.int32)


def _kernel_stats(loads, rows, d, h):
    """The grouped products' kernel at work on slices whose held pairs
    are ``loads`` [slices, n_held], each through a sorted buffer of
    ``rows`` rows: (the slices whose products ran in the package's
    kernel: all or, where ``row_tile`` leaves the shape to
    ``ragged_dot``, none; the rows of the row tiles it visited)."""
    tile = row_tile(rows, d, h)
    if tile is None:
        return jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32)
    visited = jax.vmap(lambda load: visited_rows(load, rows, tile))
    return jnp.int32(loads.shape[0]), jnp.sum(visited(loads))


def _exchanged(x, gates, idx, w_gate, w_up, w_down, dtype, ep_axis):
    """The same grouped product between two ``all_to_all`` calls: the
    experts dim is sharded over ``ep_axis`` (this device holds experts
    ``[me * E_local, (me + 1) * E_local)``), every pair travels to its
    expert's owner and its result comes back. Buffers have room for the
    worst routing (every pair to one peer): nothing is dropped."""
    T, k = idx.shape
    d = x.shape[-1]
    E_local = w_gate.shape[0]
    n_peers = jax.lax.axis_size(ep_axis)
    N = T * k
    C = T * min(k, E_local)                 # rows a peer can get from me
    flat = idx.reshape(-1)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    inv = jnp.zeros((N,), jnp.int32).at[order].set(
        jnp.arange(N, dtype=jnp.int32))
    eid = flat[order]                       # sorted global expert ids
    peer = eid // E_local
    sent = jnp.sum(peer[:, None] == jnp.arange(n_peers)[None, :], axis=0)
    first_row = jnp.cumsum(sent) - sent     # my sorted pairs, by peer
    slot = jnp.arange(N) - first_row[peer]  # row in that peer's buffer
    rows = _rows_in_order(x, order, inv, k)                   # [N, d]
    send = jnp.zeros((n_peers, C, d), x.dtype).at[peer, slot].set(rows)
    send_key = jnp.full((n_peers, C), E_local, jnp.int32).at[
        peer, slot].set(eid - peer * E_local)
    recv = jax.lax.all_to_all(send, ep_axis, 0, 0, tiled=True)
    recv_key = jax.lax.all_to_all(send_key, ep_axis, 0, 0, tiled=True)
    y, load, dropped = grouped_ffn(recv.reshape(n_peers * C, d),
                                   recv_key.reshape(-1), 1, w_gate, w_up,
                                   w_down, dtype)
    back = jax.lax.all_to_all(y.reshape(n_peers, C, d), ep_axis, 0, 0,
                              tiled=True)
    y_pairs = _rows_in_order(back[peer, slot], inv, order, 1)  # [N, d]
    out = jnp.einsum("tkd,tk->td", y_pairs.reshape(T, k, d),
                     gates.astype(dtype),
                     preferred_element_type=jnp.float32)
    return out.astype(dtype), load, dropped


def shared_expert(x_flat: jnp.ndarray, p: Dict[str, jnp.ndarray],
                  dtype: Any) -> jnp.ndarray:
    """The shared expert of a layer: ``W_down(silu(W_gate x) * W_up x)``
    on every token, x_flat [T, d] -> [T, d]. Three dense products
    (fusions under ``bps.moe.shared``: the HLO ``op_name`` carries the
    scope), no routing, no statistic."""
    with jax.named_scope("bps.moe.shared"):
        x = x_flat.astype(dtype)
        h = jax.nn.silu(x @ p["shared_gate"].astype(dtype)) \
            * (x @ p["shared_up"].astype(dtype))
        return h @ p["shared_down"].astype(dtype)


def moe_layer(x: jnp.ndarray, p: Dict[str, jnp.ndarray], top_k: int,
              dtype: Any, first: int = 0, ep_axis: Optional[str] = None,
              chunk: Optional[int] = None,
              router_dtype: Any = jnp.float32, **routing
              ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """The MoE FFN of the experts this device holds.

    x: [B, S, d]. ``p`` holds ONE layer's params: ``router`` [d, E] over
    all ``E`` experts, and the expert leaves (w_gate/w_up/w_down) of the
    ``n_held`` experts ``first .. first + n_held - 1``. Every token is
    routed over all ``E``; the output is the renormalised-weight sum of
    the held experts' terms only (all of the layer where every expert
    is held). With ``ep_axis`` set (call inside shard_map) the expert
    leaves carry the device's ``E / P`` and pairs travel by
    ``all_to_all``. ``chunk``: tokens per slice of the grouped product
    (bounds the sorted pair buffer at ``chunk * top_k`` rows); it must
    divide the tokens where there are more of them than a slice.
    ``routing``: ``route``'s ``score``, ``select_bias``, ``norm_eps``
    and ``scale``; none is softmax top-k renormalised. Where ``p`` has
    ``shared_gate`` / ``shared_up`` / ``shared_down`` ([d, h], [d, h],
    [h, d]) the shared expert's SwiGLU of every token is added to the
    output (the same on every device of a deployment: a caller that
    adds shares up hands it to one of them).

    Returns (output [B, S, d], stats): ``load`` [n_held] int32 (pairs
    per held expert in the grouped product), ``dropped`` (pairs the
    router sent to a held expert whose sorted row lies outside that
    expert's group of the product: 0, there is no capacity; it checks
    the sort's bookkeeping, the arithmetic is ``correct``'s to check),
    ``compact_slices`` and ``full_slices`` (the layer's slices, all on
    one counter or the other: walked through the compact sorted buffer,
    ``compact_rows``, or through the full-size one, as every slice is
    where half the experts or more are held), ``kernel_slices`` (the
    slices whose grouped products ran in the package's kernel: all of
    them on the TPU, none off it) and ``kernel_tile_rows`` (the rows of
    the row tiles it visited: beside the loads' sum, the tiles'
    occupancy), ``aux`` (the Switch
    balancing loss, for models that use it; softmax routing only), and
    with a ``select_bias`` ``bias_moved`` (``bias_moved_pairs``).
    """
    B, S, d = x.shape
    T = B * S
    x_flat = x.reshape(T, d)
    gates, idx, probs = route(x_flat, p["router"], top_k, router_dtype,
                              **routing)
    w = (p["w_gate"], p["w_up"], p["w_down"])
    n_held, _, hidden = w[0].shape
    if ep_axis is not None:
        n = 1
        out, load, dropped = _exchanged(x_flat, gates, idx, *w, dtype,
                                        ep_axis)
        compact = jnp.zeros((), jnp.int32)
        # the exchange's buffer is one slice
        kernel = _kernel_stats(
            load[None], jax.lax.axis_size(ep_axis) * T * min(top_k, n_held),
            d, hidden)
    else:
        if chunk is None or chunk >= T:
            n = 1
        elif T % chunk:
            raise ValueError(
                f"{T} tokens do not divide into slices of {chunk}")
        else:
            n = T // chunk
        n_experts = p["router"].shape[-1]
        out, load, dropped, compact = _held(
            x_flat, gates, idx, first, n_experts, *w, dtype, n)
        # the tiles the kernel visited in the buffer the layer walked,
        # from each slice's own loads
        here, local = _held_here(idx, first, n_held)
        loads = jnp.sum(jnp.where(here, local, n_held).reshape(n, -1, 1)
                        == jnp.arange(n_held), axis=1, dtype=jnp.int32)
        N = T // n * top_k
        kernel = jax.tree.map(
            functools.partial(jnp.where, compact > 0),
            _kernel_stats(loads, compact_rows(N, n_held, n_experts), d,
                          hidden),
            _kernel_stats(loads, N, d, hidden))
    if "shared_gate" in p:
        out = out + shared_expert(x_flat, p, dtype)
    stats = {"load": load, "dropped": dropped, "compact_slices": compact,
             "full_slices": n - compact, "kernel_slices": kernel[0],
             "kernel_tile_rows": kernel[1]}
    if routing.get("score", "softmax") == "softmax":
        stats["aux"] = switch_aux_loss(probs, idx)
    if routing.get("select_bias") is not None:
        stats["bias_moved"] = bias_moved_pairs(probs, idx)
    return out.reshape(B, S, d), stats


# --------------------------------------------------------------------- #
# full model
# --------------------------------------------------------------------- #

def _moe_block(x, p, cos, sin, cfg: MoEConfig,
               ep_axis: Optional[str]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Attention (llama's shared sublayer) + MoE FFN. p: one layer's
    params."""
    x = L.attn_sublayer(x, p, cos, sin, cfg.as_llama())
    h = L._rmsnorm(x, p["mlp_norm"], cfg.norm_eps)
    ffn, stats = moe_layer(h, p, cfg.top_k, cfg.dtype, ep_axis=ep_axis)
    return x + ffn, stats["aux"]


def forward(params: Dict[str, Any], tokens: jnp.ndarray, cfg: MoEConfig,
            ep_axis: Optional[str] = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """tokens [B, S] -> (logits [B, S, vocab] in cfg.dtype, mean aux
    loss); next_token_xent does its math in fp32 (llama.py)."""
    B, S = tokens.shape
    cos, sin = L.rope_cache(cfg.as_llama(), S)
    x = params["embed"].astype(cfg.dtype)[tokens]

    def body(x, layer_params):
        fn = _moe_block
        if cfg.remat:
            fn = jax.checkpoint(_moe_block, static_argnums=(4, 5))
        x, aux = fn(x, layer_params, cos, sin, cfg, ep_axis)
        return x, aux

    x, auxes = jax.lax.scan(body, x, params["blocks"])
    x = L._rmsnorm(x, params["final_norm"], cfg.norm_eps)
    # cfg.dtype logits; next_token_xent does the fp32 math (llama.py)
    return x @ params["lm_head"].astype(cfg.dtype), jnp.mean(auxes)


EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def ep_grad_correction(grads: Dict[str, Any], axis: str) -> Dict[str, Any]:
    """Turn per-device ``jax.grad(local loss)`` output into the gradient of
    the global (device-mean) loss under expert parallelism.

    Expert leaves already carry the cross-device sum — the transpose of the
    dispatch ``all_to_all`` routes every peer's cotangents back to the
    expert's owner — so they only need the 1/P mean scaling. Every other
    leaf is a local partial and gets the standard DP pmean.
    """

    def fix(path, leaf):
        keys = {getattr(k, "key", None) for k in path}
        if keys & set(EXPERT_LEAVES):
            return leaf / jax.lax.axis_size(axis)
        return jax.lax.pmean(leaf, axis)

    return jax.tree_util.tree_map_with_path(fix, grads)


def loss_fn(params: Dict[str, Any], batch: Dict[str, jnp.ndarray],
            cfg: MoEConfig, ep_axis: Optional[str] = None) -> jnp.ndarray:
    """Next-token cross-entropy + router aux loss."""
    inputs, targets = L.split_batch(batch)
    logits, aux = forward(params, inputs, cfg, ep_axis)
    return (L.next_token_xent(logits, targets)
            + cfg.router_aux_weight * aux)
