"""Trinity-style sparse decoder (``model_type: afmoe``): sliding-window
and full attention mixed by a per-layer pattern, ROTATED on the sliding
layers and without any positional term on the full ones, every layer's
attention output multiplied by a sigmoid gate of the layer's input
before the output projection, an RMSNorm a head on q and k, FOUR norms
a block (before and after each sublayer), the embedding scaled by
``sqrt(dim)``, a dense SwiGLU in the leading layers and, in the rest, a
sparse-expert layer of which this device may hold a share beside a
shared expert, the experts chosen by a sigmoid router that selects with
a bias and weighs without it, an untied head.

A file of its own beside ``mellum.py`` (which shares the three-to-one
window pattern and the head counts) because every sublayer is wired
differently: a sixth projection a layer, rotary by layer kind, two
norms a sublayer, another router. What is shared is imported:
``llama``'s RMSNorm, rotary table and batch split, ``moe.moe_layer``
(the held-experts layer, its router and the shared expert),
``ops.flash_attention`` (window and full), ``joyai``'s dense FFN and
its row-at-a-time head. The loss is an ``ops.chain.Chain``: the lookup,
one ``Run`` a stretch of like blocks (a stretch of one too: a kernel's
instruction keeps its scope's name only inside a scan), then the final
norm, the head and the loss. The parameter tree's top-level keys are
the chain's links: ``embed``; ``runs``, a list, a run's leaves stacked
on a leading axis under ``{"attn": ..., "ffn": ...}``; ``final_norm``,
``lm_head``.

Equations (``x`` is ``[tokens, dim]``, ``N`` an RMSNorm with
``norm_eps`` and a weight of its own, no bias anywhere):

- ``x_0 = E[ids] * sqrt(dim)`` (``mup_enabled``);
- block: ``h = x + N_2(Attn(N_1(x)))``, ``y = h + N_4(FFN(N_3(h)))``;
- ``Attn``, ``u = N_1(x)``: ``n_heads`` query and ``n_kv_heads``
  key/value heads of ``head_dim``; q and k RMS-normalised over each head
  (one weight ``[head_dim]`` each, shared by the heads); on a
  ``sliding_attention`` layer both are then rotated (the plain
  ``theta^(-2d / head_dim)``, ``llama.apply_rope``'s halves) and the
  mask is causal with ``i - j < sliding_window``; on a
  ``full_attention`` layer nothing is rotated and the mask is causal;
  ``o = softmax(q k^T / sqrt(head_dim) + mask) v``; ``Attn = (concat(o)
  * sigmoid(u W_g)) W_o`` with ``W_g`` ``[dim, n_heads head_dim]``;
- ``FFN``, dense: ``W_2(silu(W_1 u) * W_3 u)``; sparse:
  ``moe.moe_layer``, sigmoid scores in float32, the ``top_k`` largest of
  ``s + expert_bias``, weights ``s`` without the bias over their sum
  plus 1e-20 times ``route_scale``, plus the shared expert;
- head: ``logits = RMSNorm(y_L) W_head``, the mean cross-entropy of
  ``t_{i+1}`` at position ``i``.

``expert_bias`` ``[sparse layers, n_experts]`` is a buffer: an argument
of ``loss_fn`` beside the parameters, no gradient, never on the wire; a
run reads its layers' rows (``chain.Run``'s ``each``).

``loss_fn`` returns ``(loss, stats)``: ``joyai.py``'s ``moe/*`` (each
sparse run counts its layers' rows of ``moe/expert_load``; the runs'
counts add up, ``chain.add_stats``) and ``attn/window_pairs`` /
``attn/full_pairs``, the (query, key, head) triples inside the masks of
the step's sliding and full layers: the numbers the two kernels' time
scales with. They are float32: a step of four 8192-token rows counts
7.5e9 under the window, past what 32 bits hold.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import chain
from ..ops.flash_attention import flash_attention, publish_walk_sizes
from . import llama as L
from . import moe
from .joyai import (ATTN_BLOCK, EXPERT_SLICE, GATE_SUM_EPS, _dense_ffn,
                    head_nll)

SLIDING, FULL = "sliding_attention", "full_attention"
DENSE, SPARSE = "dense", "sparse"

# the statistic each kind of layer counts its mask's triples under
PAIRS = {SLIDING: "attn/window_pairs", FULL: "attn/full_pairs"}


def published_layers(n_layers: int = 32, period: int = 4
                     ) -> Tuple[str, ...]:
    """The published pattern: every ``period``-th layer attends to the
    whole prefix, the others to a window
    (``global_attn_every_n_layers``)."""
    return tuple(FULL if (i + 1) % period == 0 else SLIDING
                 for i in range(n_layers))


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int = 200192         # rows of the vocabulary held here
    dim: int = 2048
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    layer_types: Tuple[str, ...] = published_layers()   # the layers held
    n_dense_layers: int = 2          # leading held layers with a dense FFN
    dense_hidden: int = 6144
    n_experts: int = 128             # the router's outputs
    n_experts_held: int = 128        # expert leaves' leading dim
    first_expert: int = 0            # the first held expert's index
    top_k: int = 8
    expert_hidden: int = 1024        # the shared expert's width too
    sliding_window: int = 2048
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    route_scale: float = 2.826
    score_func: str = "sigmoid"
    # the four group counts of the published config, all one: a flat
    # top-k (n_group, topk_group, num_expert_groups, num_limited_groups)
    groups: Tuple[int, int, int, int] = (1, 1, 1, 1)
    tie_word_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    router_dtype: Any = jnp.float32  # float32, outside a precision control
    remat: bool = True

    def __post_init__(self):
        if any(g != 1 for g in self.groups):
            raise ValueError(
                f"group counts {self.groups}: group-limited selection is "
                f"not written (one group has nothing to limit)")
        if self.score_func != "sigmoid":
            raise ValueError(f"score_func {self.score_func!r}: the router "
                             f"scores with a sigmoid an expert")
        if self.tie_word_embeddings:
            raise ValueError("tied embeddings: the head is a leaf of its own")
        if set(self.layer_types) - {SLIDING, FULL}:
            raise ValueError(
                f"layer_types {self.layer_types}: {SLIDING} or {FULL}")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def n_sparse_layers(self) -> int:
        return self.n_layers - min(self.n_dense_layers, self.n_layers)

    def window(self, kind: str) -> Optional[int]:
        """The mask's window on a layer of ``kind``: none on a full one."""
        return self.sliding_window if kind == SLIDING else None

    def runs(self) -> List[Tuple[Tuple[str, str], int]]:
        """The layers held as runs: ((attention kind, FFN kind), layers)
        of each maximal stretch of like blocks, in order."""
        kinds = [(kind, DENSE if i < self.n_dense_layers else SPARSE)
                 for i, kind in enumerate(self.layer_types)]
        return [(kind, len(list(group)))
                for kind, group in itertools.groupby(kinds)]

    @staticmethod
    def tiny(vocab_size: int = 64, seq: int = 32) -> "AfmoeConfig":
        """Test-scale: the benchmark cell's five layers (dense +
        sliding, sparse + sliding, sparse + full, two sparse + sliding),
        a window shorter than the sequence, a share of the experts."""
        return AfmoeConfig(
            vocab_size=vocab_size, dim=32, n_heads=4, n_kv_heads=2,
            head_dim=16, layer_types=(SLIDING, SLIDING, FULL, SLIDING,
                                      SLIDING),
            n_dense_layers=1, dense_hidden=48, n_experts=8,
            n_experts_held=4, top_k=2, expert_hidden=24,
            sliding_window=seq // 4, remat=False, dtype=jnp.float32)


def _shapes(cfg: AfmoeConfig) -> Dict[str, Dict[str, tuple]]:
    d, hd = cfg.dim, cfg.head_dim
    q, kv = cfg.n_heads * hd, cfg.n_kv_heads * hd
    F, f, E, H = (cfg.dense_hidden, cfg.expert_hidden, cfg.n_experts,
                  cfg.n_experts_held)
    return {
        "attn": {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wg": (d, q),
                 "wo": (q, d)},
        DENSE: {"w1": (d, F), "w3": (d, F), "w2": (F, d)},
        SPARSE: {"router": (d, E), "w_gate": (H, d, f), "w_up": (H, d, f),
                 "w_down": (H, f, d), "shared_gate": (d, f),
                 "shared_up": (d, f), "shared_down": (f, d)},
    }


def init_params(rng: jax.Array, cfg: AfmoeConfig) -> Dict[str, Any]:
    """Normal(0, 0.02) matrices, norms at one; a run's layers stacked.
    Every sublayer has the norm of its input (``norm``) and of its
    output (``post_norm``), attention the two head norms besides."""
    d, pd = cfg.dim, cfg.param_dtype
    shapes = _shapes(cfg)

    def dense(key, shape):
        return jax.random.normal(key, shape, pd) * 0.02

    def group(key, kind, n):
        keys = jax.random.split(key, len(shapes[kind]))
        out = {"norm": jnp.ones((n, d), pd), "post_norm": jnp.ones((n, d), pd)}
        out.update({name: dense(k, (n, *shape)) for k, (name, shape)
                    in zip(keys, sorted(shapes[kind].items()))})
        if kind == "attn":
            out.update(q_norm=jnp.ones((n, cfg.head_dim), pd),
                       k_norm=jnp.ones((n, cfg.head_dim), pd))
        return out

    k_embed, k_head, k_runs = jax.random.split(rng, 3)
    runs = []
    for i, ((_, ffn), n) in enumerate(cfg.runs()):
        k_attn, k_ffn = jax.random.split(jax.random.fold_in(k_runs, i))
        runs.append({"attn": group(k_attn, "attn", n),
                     "ffn": group(k_ffn, ffn, n)})
    return {"embed": dense(k_embed, (cfg.vocab_size, d)), "runs": runs,
            "final_norm": jnp.ones((d,), pd),
            "lm_head": dense(k_head, (d, cfg.vocab_size))}


# --------------------------------------------------------------------- #
# the block
# --------------------------------------------------------------------- #

def band_pairs(seq_len: int, window: Optional[int] = None) -> int:
    """(query, key) pairs one sequence's mask lets through: causal, and
    within ``window`` where one is given."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def gated_output(o, u, wg, dtype):
    """``o * sigmoid(u W_g)``: o ``[B, S, heads * head_dim]``, the
    attention's concatenated head outputs, u ``[B, S, d]`` the layer's
    normed input. The logits and the product are float32 (a fusion after
    the projection); under ``jax.checkpoint`` where the block calls it,
    so that the backward keeps ``o`` and makes the logits again."""
    with jax.named_scope("bps.attn.gate"):
        logits = jnp.matmul(u, wg.astype(dtype),
                            preferred_element_type=jnp.float32)
        return (o.astype(jnp.float32) * jax.nn.sigmoid(logits)).astype(dtype)


def head_norm_rope(x, w, rope, eps):
    """RMSNorm over each head of x ``[B, S, heads, head_dim]``, then,
    where the layer rotates (``rope`` a (cos, sin) table, else None),
    the rotation; both in float32 (positions run to thousands of
    radians)."""
    x = L._rmsnorm(x.astype(jnp.float32), w.astype(jnp.float32), eps)
    return x if rope is None else L.apply_rope(x, *rope)


def _attention(u, p, rope, cfg: AfmoeConfig, kind: str):
    """``Attn(u)`` of a layer of ``kind``; u [B, S, d] the normed input,
    p the layer's ``attn`` leaves, ``rope`` the sliding layers' table."""
    B, S, _ = u.shape
    nh, nkv, hd, dt = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.dtype
    q = (u @ p["wq"].astype(dt)).reshape(B, S, nh, hd)
    k = (u @ p["wk"].astype(dt)).reshape(B, S, nkv, hd)
    v = (u @ p["wv"].astype(dt)).reshape(B, S, nkv, hd)
    rope = rope if kind == SLIDING else None

    # float32 inside; the backward keeps the bf16 projection and runs
    # the norm and the rotation again (``sdar.py``'s lesson)
    @jax.checkpoint
    def normed(x, w):
        return head_norm_rope(x, w, rope, cfg.norm_eps).astype(dt)

    q, k = normed(q, p["q_norm"]), normed(k, p["k_norm"])
    # the kernels sit under ``bps.attn.window`` / ``bps.attn.full``
    # (ops/flash_attention.py ``_scope``)
    window = cfg.window(kind)
    publish_walk_sizes(S, nh // nkv, ATTN_BLOCK, ATTN_BLOCK, window)
    o = flash_attention(q, k, v, True, ATTN_BLOCK, ATTN_BLOCK, window)
    gated = jax.checkpoint(gated_output, static_argnums=(3,))
    return gated(o.reshape(B, S, nh * hd), u, p["wg"], dt) \
        @ p["wo"].astype(dt)


def _block(x, p, bias, rope, cfg: AfmoeConfig, kind, ep_axis):
    """One block of ``kind`` (attention kind, FFN kind); p: one layer's
    ``{"attn", "ffn"}`` leaves, ``bias`` its row of the expert bias
    (None on a dense layer). Returns (x, the layer's additive statistics
    by counter name)."""
    attn, ffn = kind
    B, S, _ = x.shape
    eps = cfg.norm_eps

    def attended(x, p_attn, rope):
        u = L._rmsnorm(x, p_attn["norm"], eps)
        return x + L._rmsnorm(_attention(u, p_attn, rope, cfg, attn),
                              p_attn["post_norm"], eps)

    # under remat the attention sublayer runs a ROW at a time, each row
    # under a checkpoint of its own inside the block's: the block's
    # backward then holds one row's float32 copies of q, of the gate's
    # logits and of their cotangents (0.5 GiB each at four 8192-token
    # rows) and the FFN's intermediates one after the other, not all at
    # once (a sparse + sliding layer's program 4.52 -> 2.25 GiB of
    # temporaries at the benchmark's cell, which is what lets two
    # programs' temporaries fit beside the step's state: TPU compiler,
    # PR 49), for one more forward of the sublayer
    if cfg.remat:
        x = jax.lax.map(jax.checkpoint(
            lambda row: attended(row[None], p["attn"], rope)[0]), x)
    else:
        x = attended(x, p["attn"], rope)
    stats = {PAIRS[attn]: jnp.asarray(
        float(B * cfg.n_heads * band_pairs(S, cfg.window(attn))),
        jnp.float32)}
    u = L._rmsnorm(x, p["ffn"]["norm"], eps)
    if ffn == DENSE:
        out = _dense_ffn(u, p["ffn"], cfg)
    else:
        out, st = moe.moe_layer(
            u, p["ffn"], cfg.top_k, cfg.dtype, first=cfg.first_expert,
            ep_axis=ep_axis, chunk=EXPERT_SLICE,
            router_dtype=cfg.router_dtype, score="sigmoid",
            select_bias=bias, norm_eps=GATE_SUM_EPS, scale=cfg.route_scale)
        stats.update({"moe/expert_load": st["load"],
                      "moe/dropped_pairs": st["dropped"],
                      "moe/compact_slices": st["compact_slices"],
                      "moe/full_slices": st["full_slices"],
                      "moe/kernel_slices": st["kernel_slices"],
                      "moe/kernel_tile_rows": st["kernel_tile_rows"],
                      "moe/bias_moved_pairs": st["bias_moved"]})
    return x + L._rmsnorm(out, p["ffn"]["post_norm"], eps), stats


# --------------------------------------------------------------------- #
# forward and loss: a chain of links (``ops/chain.py``)
# --------------------------------------------------------------------- #

def _runs(cfg: AfmoeConfig, expert_bias, ep_axis) -> List[chain.Run]:
    """A ``chain.Run`` a run of ``cfg.runs()``, its stacked leaves under
    ``params["runs"][i]``. A sparse run reads its layers' rows of the
    bias and counts them into its rows of ``moe/expert_load`` ``[sparse
    layers, n_held]``, zero elsewhere: the runs' tables add up to the
    step's. A scalar a layer is summed over a run's layers. (A link
    closes over nothing that is traced: the bias's rows are cut, and its
    gradient stopped, where the link runs.)"""
    shape = (cfg.n_sparse_layers, cfg.n_experts)
    if expert_bias is not None and expert_bias.shape != shape:
        raise ValueError(f"expert_bias {expert_bias.shape}: a row a sparse "
                         f"layer, {shape}")
    runs, first = [], 0
    for i, (kind, n) in enumerate(cfg.runs()):
        sparse = kind[1] == SPARSE

        def block(p, x, rope, *row, kind=kind):
            return _block(x, p, *(row or (None,)), rope, cfg, kind, ep_axis)

        # plain ``theta^(-2d / head_dim)``: llama's table, made once a
        # program; a full-attention run reads none
        runs.append(chain.Run(
            block, ("runs", i), n, remat=cfg.remat,
            consts=(lambda batch: L.rope_cache(
                cfg, L.split_batch(batch)[0].shape[1]))
            if kind[0] == SLIDING else (lambda batch: ()),
            stats=lambda stacked, first=first: moe.run_stats(
                stacked, first, cfg.n_sparse_layers),
            each=(lambda batch, first=first, n=n: moe.bias_rows(
                expert_bias, cfg.n_experts, first, n)) if sparse else None))
        first += n if sparse else 0
    return runs


def _chain(cfg: AfmoeConfig, expert_bias, ep_axis) -> chain.Chain:
    scale = math.sqrt(cfg.dim)

    def embed(p, _, batch):
        # scaled in the parameters' type, then rounded once
        return (p["embed"][L.split_batch(batch)[0]] * scale
                ).astype(cfg.dtype), {}

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
                   cfg: AfmoeConfig, expert_bias: Optional[jnp.ndarray] = None,
                   ep_axis: Optional[str] = None):
    """tokens [B, S] -> (the last block's output [B, S, d], BEFORE the
    final norm; the layers' statistics)."""
    batch = {"inputs": tokens, "targets": tokens}
    x, stats = None, {}
    for ln in _chain(cfg, expert_bias, ep_axis).links[:-1]:
        x, st = ln(ln.pick(params), x, batch)
        chain.add_stats(stats, st)
    return x, stats


def loss_fn(params: Dict[str, Any], batch: Dict[str, jnp.ndarray],
            cfg: AfmoeConfig, expert_bias: Optional[jnp.ndarray] = None,
            ep_axis: Optional[str] = None):
    """(the mean next-token cross-entropy over the vocabulary held, the
    step's statistics: ``moe/*`` as ``joyai.loss_fn``'s and the masks'
    ``attn/window_pairs`` and ``attn/full_pairs``; all are counts, so
    they add up across data shards as the step makers need).
    batch: ``{"tokens"}`` (shifted here) or pre-shifted ``{"inputs",
    "targets"}``.

    Written as a chain (``ops/chain.py``): any step maker runs it as one
    program; ``make_ps_train_step`` cuts its backward at the links: the
    head's program, one a layer of a run deeper than one, one a run of
    one layer, the lookup's."""
    return _chain(cfg, expert_bias, ep_axis)(params, batch)
