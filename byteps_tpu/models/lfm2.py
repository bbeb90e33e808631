"""LFM2-style sparse hybrid decoder (``model_type: lfm2_moe``): the
operator of a layer is a gated short convolution or grouped-query
attention by a per-layer pattern, its FFN a dense SwiGLU in the leading
layers and a sparse-expert layer of which this device may hold a share
in the rest, the experts chosen by a sigmoid router that SELECTS with a
bias and WEIGHS without it, and one embedding matrix that is also the
head.

A file of its own beside ``mellum.py`` because no two kinds of layer
hold the same leaves: the parameter tree is a list of RUNS, the maximal
stretches of consecutive layers of one (operator, FFN) kind, each run's
leaves stacked ``[layers of the run, ...]``; the layer walk follows the
runs and scans each (``lax.scan``), so a kind of block is compiled once
a run however long the run is. What is shared is
imported: ``llama``'s RMSNorm, rotary application and cross-entropy,
``moe.moe_layer`` (the held-experts layer and its router),
``ops.flash_attention``.

Equations (``x`` is ``[tokens, dim]``, no bias anywhere), from the
published ``config`` and, where it has no key, the published modelling
code:

- block ``l``: ``h = x + Op_l(RMSNorm(x))``, ``y = h + FFN_l(RMSNorm(h))``;
  a final RMSNorm, ``logits = x E^T`` with ``E`` the embedding (tied),
  next-token cross-entropy over the rows of the vocabulary held here;
- ``Op_l``, ``"conv"`` (``gated_short_conv``): ``[B, C, X] = split_3(u W_in)``,
  ``z = B * X``, ``c_t = sum_j k_j z_{t - (L - 1) + j}`` with one filter
  ``k`` ``[L, dim]`` a channel (depthwise, causal: ``z`` is zero before a
  row's first position), ``Op = (C * c) W_out``;
- ``Op_l``, ``"full_attention"``: ``n_heads`` query and ``n_kv_heads``
  key/value heads of ``head_dim``; q and k RMS-normalised over each head
  (one weight ``[head_dim]`` each, shared by the heads), then the plain
  rotary, then ``softmax(q k^T / sqrt(head_dim) + causal) v``;
- ``FFN_l``, dense (the first ``n_dense_layers`` layers held):
  ``W_2(silu(W_1 u) * W_3 u)``; sparse: ``moe.moe_layer`` with ``s =
  sigmoid(u W_r)`` in float32 over all experts, the ``top_k`` largest of
  ``s + expert_bias`` selected, their weights ``s`` without the bias,
  divided by their sum plus 1e-6 and scaled; the held experts' SwiGLU
  terms summed. No auxiliary loss.

``expert_bias`` ``[sparse layers, n_experts]`` is a buffer, not a
parameter: it is an argument of ``loss_fn`` beside the parameters, gets
no gradient and no decay and never travels. (The published buffer is
moved by a balancing rule between steps; no such rule runs here.)

``loss_fn`` returns ``(loss, stats)``: the ``moe/*`` statistics leave the
chip beside the loss (``jax/train.py _loss_and_stats``). It is written
as a chain (``ops/chain.py``): the lookup, a ``chain.Run`` a run under
``("runs", i)``, the final norm and the head, ``embed`` under the first
link and the last; ``make_ps_train_step`` cuts its backward at the
links.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import chain
from ..ops.flash_attention import flash_attention, publish_walk_sizes
from . import llama as L
from . import moe

CONV, FULL = "conv", "full_attention"
DENSE, SPARSE = "dense", "sparse"

# The program's own tiles, no part of the model (as ``mellum.py``'s)
ATTN_BLOCK = 512
EXPERT_SLICE = 8192

# added to the sum the top-k weights are divided by (the published
# modelling code's; the config has no key for it)
GATE_SUM_EPS = 1e-6

_PUBLISHED_LAYERS = (CONV, CONV, FULL, CONV, CONV, CONV, FULL, CONV, CONV,
                     CONV, FULL, CONV, CONV, CONV, FULL, CONV, CONV, CONV,
                     FULL, CONV, CONV, FULL, CONV, CONV)


@dataclasses.dataclass(frozen=True)
class LFM2Config:
    vocab_size: int = 65536          # rows of the vocabulary held here
    dim: int = 2048
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 64
    layer_types: Tuple[str, ...] = _PUBLISHED_LAYERS   # the layers held
    n_dense_layers: int = 2          # leading held layers with a dense FFN
    dense_hidden: int = 7168
    n_experts: int = 32              # the router's outputs
    n_experts_held: int = 32         # expert leaves' leading dim
    first_expert: int = 0            # the first held expert's index
    top_k: int = 4
    expert_hidden: int = 1792
    conv_kernel: int = 3             # ``conv_L_cache``
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-5
    routed_scaling: float = 1.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    router_dtype: Any = jnp.float32  # float32, outside a precision control
    remat: bool = True

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def n_sparse_layers(self) -> int:
        return self.n_layers - min(self.n_dense_layers, self.n_layers)

    def kinds(self) -> List[Tuple[str, str]]:
        """(operator, FFN) kind of every layer held, in order."""
        return [(op, DENSE if i < self.n_dense_layers else SPARSE)
                for i, op in enumerate(self.layer_types)]

    def runs(self) -> List[Tuple[Tuple[str, str], int]]:
        """The layer pattern as runs: ((operator, FFN) kind, layers)."""
        return [(kind, len(list(group)))
                for kind, group in itertools.groupby(self.kinds())]


def init_params(rng: jax.Array, cfg: LFM2Config) -> Dict[str, Any]:
    """Normal(0, 0.02) weights, norms at one; a run's layers stacked."""
    d, hd = cfg.dim, cfg.head_dim
    q, kv = cfg.n_heads * hd, cfg.n_kv_heads * hd
    F, f, E, H = (cfg.dense_hidden, cfg.expert_hidden, cfg.n_experts,
                  cfg.n_experts_held)

    def dense(key, shape):
        return jax.random.normal(key, shape, cfg.param_dtype) * 0.02

    def ones(shape):
        return jnp.ones(shape, cfg.param_dtype)

    shapes = {
        CONV: {"w_in": (d, 3 * d), "kernel": (cfg.conv_kernel, d),
               "w_out": (d, d)},
        FULL: {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d)},
        DENSE: {"w1": (d, F), "w3": (d, F), "w2": (F, d)},
        SPARSE: {"router": (d, E), "w_gate": (H, d, f), "w_up": (H, d, f),
                 "w_down": (H, f, d)},
    }
    norms = {CONV: {}, FULL: {"q_norm": (hd,), "k_norm": (hd,)},
             DENSE: {}, SPARSE: {}}

    def group(key, kind, n):
        keys = jax.random.split(key, len(shapes[kind]))
        out = {"norm": ones((n, d))}
        out.update({name: dense(k, (n, *shape)) for k, (name, shape)
                    in zip(keys, sorted(shapes[kind].items()))})
        out.update({name: ones((n, *shape))
                    for name, shape in norms[kind].items()})
        return out

    k_embed, k_runs = jax.random.split(rng)
    runs = []
    for i, ((op, ffn), n) in enumerate(cfg.runs()):
        k_op, k_ffn = jax.random.split(jax.random.fold_in(k_runs, i))
        runs.append({"op": group(k_op, op, n), "ffn": group(k_ffn, ffn, n)})
    return {"embed": dense(k_embed, (cfg.vocab_size, d)), "runs": runs,
            "final_norm": ones((d,))}


# --------------------------------------------------------------------- #
# operators
# --------------------------------------------------------------------- #

def gated_short_conv(b: jnp.ndarray, c: jnp.ndarray, x: jnp.ndarray,
                     kernel: jnp.ndarray) -> jnp.ndarray:
    """``c * conv(b * x)``: b, c, x ``[B, S, d]``, ``kernel`` ``[L, d]``,
    one causal filter a channel, ``conv(z)_t = sum_j kernel[j] *
    z[t - (L - 1) + j]`` with ``z`` zero before position 0 of ITS row
    (the shift pads each row of the batch, so nothing crosses rows).
    The whole chain is elementwise but for the shifts, and is computed
    in float32 whatever the operands' type: one fusion."""
    taps, S = kernel.shape[0], x.shape[1]
    with jax.named_scope("bps.conv.short"):
        z = b.astype(jnp.float32) * x.astype(jnp.float32)
        padded = jnp.pad(z, ((0, 0), (taps - 1, 0), (0, 0)))
        k = kernel.astype(jnp.float32)
        conv = sum(k[j] * jax.lax.slice_in_dim(padded, j, j + S, axis=1)
                   for j in range(taps))
        return (c.astype(jnp.float32) * conv).astype(x.dtype)


def _conv_op(u, p, rope, cfg: LFM2Config):
    dt = cfg.dtype
    b, c, x = jnp.split(u @ p["w_in"].astype(dt), 3, axis=-1)
    return gated_short_conv(b, c, x, p["kernel"]) @ p["w_out"].astype(dt)


def _head_norm_rope(x, w, cos, sin, eps):
    """RMSNorm over each head, then the rotation, both in float32
    (positions run to thousands of radians); x [B, S, heads, hd]."""
    x = L._rmsnorm(x.astype(jnp.float32), w.astype(jnp.float32), eps)
    return L.apply_rope(x, cos, sin)


def _attn_op(u, p, rope, cfg: LFM2Config):
    B, S, _ = u.shape
    nh, nkv, hd, dt = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.dtype
    cos, sin = rope
    q = (u @ p["wq"].astype(dt)).reshape(B, S, nh, hd)
    k = (u @ p["wk"].astype(dt)).reshape(B, S, nkv, hd)
    v = (u @ p["wv"].astype(dt)).reshape(B, S, nkv, hd)
    q = _head_norm_rope(q, p["q_norm"], cos, sin, cfg.norm_eps).astype(dt)
    k = _head_norm_rope(k, p["k_norm"], cos, sin, cfg.norm_eps).astype(dt)
    # the kernels sit under ``bps.attn.full`` (ops/flash_attention.py)
    publish_walk_sizes(S, nh // nkv, ATTN_BLOCK, ATTN_BLOCK)
    attn = flash_attention(q, k, v, True, ATTN_BLOCK, ATTN_BLOCK, None)
    return attn.reshape(B, S, nh * hd) @ p["wo"].astype(dt)


_OPS = {CONV: _conv_op, FULL: _attn_op}


def _dense_ffn(u, p, cfg: LFM2Config):
    dt = cfg.dtype
    h = jax.nn.silu(u @ p["w1"].astype(dt)) * (u @ p["w3"].astype(dt))
    return h @ p["w2"].astype(dt)


def _block(x, p, bias, rope, cfg: LFM2Config, kind, ep_axis):
    """One decoder block of ``kind`` (operator, FFN); p: one layer's
    ``{"op", "ffn"}`` leaves, ``bias`` its row of the expert bias (None
    on a dense layer). Returns (x, the layer's additive statistics by
    counter name: none on a dense layer)."""
    op, ffn = kind
    h = L._rmsnorm(x, p["op"]["norm"], cfg.norm_eps)
    x = x + _OPS[op](h, p["op"], rope, cfg)
    h = L._rmsnorm(x, p["ffn"]["norm"], cfg.norm_eps)
    if ffn == DENSE:
        return x + _dense_ffn(h, p["ffn"], cfg), {}
    out, st = moe.moe_layer(
        h, p["ffn"], cfg.top_k, cfg.dtype, first=cfg.first_expert,
        ep_axis=ep_axis, chunk=EXPERT_SLICE, router_dtype=cfg.router_dtype,
        score="sigmoid", select_bias=bias, norm_eps=GATE_SUM_EPS,
        scale=cfg.routed_scaling)
    return x + out, {"moe/expert_load": st["load"],
                     "moe/dropped_pairs": st["dropped"],
                     "moe/compact_slices": st["compact_slices"],
                     "moe/full_slices": st["full_slices"],
                     "moe/kernel_slices": st["kernel_slices"],
                     "moe/kernel_tile_rows": st["kernel_tile_rows"],
                     "moe/bias_moved_pairs": st["bias_moved"]}


# --------------------------------------------------------------------- #
# forward and loss: a chain of links (``ops/chain.py``)
# --------------------------------------------------------------------- #

def _runs(cfg: LFM2Config, expert_bias, ep_axis) -> List[chain.Run]:
    """A ``chain.Run`` a run of ``cfg.runs()``, its stacked leaves under
    ``params["runs"][i]``. A run of one layer is a scan of one step too:
    one walk, and a kernel's instruction is named alike in every run. A
    sparse run reads its layers' rows of the bias and counts them into
    its rows of ``moe/expert_load`` ``[sparse layers, n_held]``, zero
    elsewhere: the runs' tables add up to the step's. A scalar a layer
    is summed over a run's layers. (A link closes over nothing that is
    traced: the bias's rows are cut, and its gradient stopped, where the
    link runs.)"""
    runs, first = [], 0
    for i, (kind, n) in enumerate(cfg.runs()):
        sparse = kind[1] == SPARSE

        def block(p, x, rope, *row, kind=kind):
            return _block(x, p, *(row or (None,)), rope, cfg, kind, ep_axis)

        # plain ``theta^(-2d / head_dim)``: llama's table, read from
        # this configuration's ``head_dim`` and ``rope_theta``, made
        # once a program
        runs.append(chain.Run(
            block, ("runs", i), n, remat=cfg.remat,
            consts=lambda batch: L.rope_cache(
                cfg, L.split_batch(batch)[0].shape[1]),
            stats=lambda stacked, first=first: moe.run_stats(
                stacked, first, cfg.n_sparse_layers),
            each=(lambda batch, first=first, n=n: moe.bias_rows(
                expert_bias, cfg.n_experts, first, n)) if sparse else None))
        first += n if sparse else 0
    return runs


def _chain(cfg: LFM2Config, expert_bias, ep_axis) -> chain.Chain:
    """The loss as links: the lookup, a run a stretch of like layers,
    the final norm and the head. ``embed`` lies under the first link AND
    the last (the head is the embedding itself): a step that cuts the
    backward sums its two terms on the chip."""
    def embed(p, _, batch):
        return p["embed"].astype(cfg.dtype)[L.split_batch(batch)[0]], {}

    def head(p, x, batch):
        # a link reads what it needs of the batch from ``batch``: the
        # cut step traces it on its own
        x = L._rmsnorm(x, p["final_norm"], cfg.norm_eps)
        logits = jnp.einsum("bsd,vd->bsv", x, p["embed"].astype(cfg.dtype))
        return L.next_token_xent(logits, L.split_batch(batch)[1]), {}

    return chain.Chain((
        chain.Link(embed, "embed"), *_runs(cfg, expert_bias, ep_axis),
        chain.Link(head, ("final_norm", "embed"))))


def forward_hidden(params: Dict[str, Any], tokens: jnp.ndarray,
                   cfg: LFM2Config, expert_bias: Optional[jnp.ndarray] = None,
                   ep_axis: Optional[str] = None):
    """tokens [B, S] -> (final normed hidden [B, S, d], the step's
    statistics: the load [sparse layers, n_held], the other counts
    summed over the sparse layers). ``expert_bias`` [sparse layers,
    n_experts]; none is zeros."""
    batch = {"inputs": tokens, "targets": tokens}
    x, stats = None, {}
    for ln in _chain(cfg, expert_bias, ep_axis).links[:-1]:
        x, st = ln(ln.pick(params), x, batch)
        chain.add_stats(stats, st)
    return L._rmsnorm(x, params["final_norm"], cfg.norm_eps), stats


def loss_fn(params: Dict[str, Any], batch: Dict[str, jnp.ndarray],
            cfg: LFM2Config, expert_bias: Optional[jnp.ndarray] = None,
            ep_axis: Optional[str] = None):
    """(next-token cross-entropy over the vocabulary held, the step's
    statistics: ``moe/expert_load`` [sparse layers, n_held], the pairs
    each held expert computed, ``moe/dropped_pairs``, the expert slices
    by the sorted buffer they ran on, ``moe/compact_slices`` and
    ``moe/full_slices``, and ``moe/bias_moved_pairs``, the pairs whose
    expert the selection bias put among a token's ``top_k``; all are
    counts, so they add up across data shards as the step makers need).
    The head is the embedding itself: one leaf, whose gradient is the
    sum of its two uses'.
    batch: ``{"tokens"}`` (shifted here) or pre-shifted ``{"inputs",
    "targets"}``.

    Written as a chain (``ops/chain.py``): any step maker runs it as one
    program; ``make_ps_train_step`` cuts its backward at the links: the
    head's program, one a layer, the embedding's."""
    return _chain(cfg, expert_bias, ep_axis)(params, batch)
