"""JoyAI-LLM-Flash-style sparse decoder (``model_type: joyai_llm_flash``,
the DeepSeek-V3 layer at other numbers): multi-head LATENT attention on
every layer, a dense SwiGLU in the leading layers and, in the rest, a
sparse-expert layer of which this device may hold a share beside a
SHARED expert every token passes, the experts chosen by a sigmoid router
that selects with a bias and weighs without it, an untied head, and a
multi-token-prediction module that uses the embedding and the head a
second time.

A file of its own beside ``lfm2.py`` because no other family holds these
leaves; what is shared is imported: ``llama``'s RMSNorm and batch split,
``moe.moe_layer`` (the held-experts layer, its router and the shared
expert), ``ops.flash_attention.latent_attention``. The parameter tree is
``lfm2.py``'s list of RUNS (a dense run, a sparse run, each run's leaves
stacked and scanned, a run of one layer too) and the module under
``mtp`` with a run of its own.

Equations (``x`` is ``[tokens, dim]``, no bias anywhere, ``eps =
norm_eps``):

- block: ``x += Attn(RMSNorm(x))``, ``x += FFN(RMSNorm(x))``;
- ``Attn`` (``n_heads`` heads): ``c_q = RMSNorm(u W_qa)`` (``q_lora_rank``),
  a head's query ``[q_nope (nope_dim) ; q_rope (rope_dim)] = c_q W_qb``;
  ``[c_kv (kv_lora_rank) ; k_r (rope_dim)] = u W_kva``, ``c_kv =
  RMSNorm(c_kv)``, a head's ``[k_nope (nope_dim) ; v (v_dim)] = c_kv
  W_kvb``; ``q_rope`` and the ONE ``k_r`` of all heads rotated by
  position, pairs ``(2i, 2i + 1)`` (``rope_interleave``); ``s = (q_nope .
  k_nope + q_rope . k_rope) / sqrt(nope_dim + rope_dim)``, causal
  softmax, ``o = softmax(s) v``, ``Attn = concat(o) W_o``. The
  projections are not absorbed (training). The rotated halves are kept
  as ``[even columns ; odd columns]``: the same permutation of q_rope's
  and k_rope's columns, so no score moves;
- ``FFN``, dense: ``W_2(silu(W_1 u) * W_3 u)``; sparse: ``moe.moe_layer``
  with ``s = sigmoid(u W_r)`` in float32 over all experts, the ``top_k``
  largest of ``s + expert_bias`` selected, their weights ``s`` without
  the bias over their sum plus 1e-20, times ``routed_scaling``; the held
  experts' SwiGLU terms summed, plus the shared expert's;
- head: ``logits = RMSNorm(h_L) W_head``, ``L_main`` the mean
  cross-entropy of token ``t_{i+1}`` at position ``i``;
- the module (depth 1): ``h' = [RMSNorm_h(h_L at i) ; RMSNorm_e(Emb(t_{i+1}))]
  W_eh``, one sparse block on ``h'``, a norm of its own, the SAME head
  and the SAME embedding, ``L_mtp`` the mean cross-entropy of ``t_{i+2}``;
  ``L = L_main + mtp_weight * L_mtp``.

``expert_bias`` ``[sparse layers + 1, n_experts]`` (the module's block is
the last row) is a buffer, as ``lfm2.py``'s: an argument of ``loss_fn``
beside the parameters, no gradient, never on the wire. Group-limited
selection is not written: ``n_group`` 1 has nothing to limit, more is
refused.

``loss_fn`` returns ``(loss, stats)``: ``lfm2.py``'s ``moe/*`` statistics
(the module's block as one more layer of the load) and ``mtp/*``. It is
written as a chain (``ops/chain.py``): the lookup under ``embed``, a
``chain.Run`` a run under ``("runs", i)``, and ONE last link that holds
both heads and the prediction module (``final_norm``, ``head``, ``mtp``
and ``embed`` again: the module looks ``t_{i+1}`` up in the same
matrix). ``embed`` so lies under the first link and the last;
``make_ps_train_step`` cuts the backward at the links and sums the
leaf's two terms on the chip.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import chain
from ..ops.flash_attention import latent_attention, publish_walk_sizes
from . import llama as L
from . import moe

DENSE, SPARSE = "dense", "sparse"

# The program's own tiles, no part of the model (as ``lfm2.py``'s)
ATTN_BLOCK = 512
EXPERT_SLICE = 8192

# added to the sum the top-k weights are divided by (the published
# modelling code's; the config has no key for it)
GATE_SUM_EPS = 1e-20


@dataclasses.dataclass(frozen=True)
class JoyAIConfig:
    vocab_size: int = 129280         # rows of the vocabulary held here
    dim: int = 2048
    n_layers: int = 40               # the layers held, the module apart
    n_dense_layers: int = 1          # leading held layers with a dense FFN
    n_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    nope_dim: int = 128              # ``qk_nope_head_dim``
    rope_dim: int = 64               # ``qk_rope_head_dim``
    v_dim: int = 128                 # ``v_head_dim``
    dense_hidden: int = 7168
    n_experts: int = 256             # the router's outputs
    n_experts_held: int = 256        # expert leaves' leading dim
    first_expert: int = 0            # the first held expert's index
    top_k: int = 8
    expert_hidden: int = 768         # the shared expert's width too
    n_group: int = 1
    routed_scaling: float = 2.5
    n_mtp: int = 1                   # ``num_nextn_predict_layers``: 0 or 1
    mtp_weight: float = 0.3          # lambda, the module's loss's weight
    rope_theta: float = 32000000.0
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    router_dtype: Any = jnp.float32  # float32, outside a precision control
    remat: bool = True

    def __post_init__(self):
        if self.n_group != 1:
            raise ValueError(
                f"n_group={self.n_group}: group-limited selection is not "
                f"written (one group has nothing to limit)")
        if self.n_mtp not in (0, 1):
            raise ValueError(f"n_mtp={self.n_mtp}: one prediction module "
                             f"(depth 1) or none")
        if self.rope_dim % 2:
            raise ValueError("the rotary columns are rotated in pairs")

    @property
    def n_sparse_layers(self) -> int:
        return self.n_layers - min(self.n_dense_layers, self.n_layers)

    def runs(self) -> List[Tuple[str, int]]:
        """The layers held as runs: (FFN kind, layers), none empty."""
        dense = min(self.n_dense_layers, self.n_layers)
        return [(kind, n) for kind, n in
                ((DENSE, dense), (SPARSE, self.n_layers - dense)) if n]


def init_params(rng: jax.Array, cfg: JoyAIConfig) -> Dict[str, Any]:
    """Normal(0, 0.02) weights, norms at one; a run's layers stacked.
    ``embed`` and ``head`` appear once: the module uses both again."""
    d, nh = cfg.dim, cfg.n_heads
    F, f, E, H = (cfg.dense_hidden, cfg.expert_hidden, cfg.n_experts,
                  cfg.n_experts_held)
    shapes = {
        "attn": {"wq_a": (d, cfg.q_lora_rank),
                 "wq_b": (cfg.q_lora_rank, nh * (cfg.nope_dim + cfg.rope_dim)),
                 "wkv_a": (d, cfg.kv_lora_rank + cfg.rope_dim),
                 "wkv_b": (cfg.kv_lora_rank, nh * (cfg.nope_dim + cfg.v_dim)),
                 "wo": (nh * cfg.v_dim, d)},
        DENSE: {"w1": (d, F), "w3": (d, F), "w2": (F, d)},
        SPARSE: {"router": (d, E), "w_gate": (H, d, f), "w_up": (H, d, f),
                 "w_down": (H, f, d), "shared_gate": (d, f),
                 "shared_up": (d, f), "shared_down": (f, d)},
    }
    norms = {"attn": {"q_norm": (cfg.q_lora_rank,),
                      "kv_norm": (cfg.kv_lora_rank,)}, DENSE: {}, SPARSE: {}}

    def dense(key, shape):
        return jax.random.normal(key, shape, cfg.param_dtype) * 0.02

    def ones(shape):
        return jnp.ones(shape, cfg.param_dtype)

    def group(key, kind, n):
        keys = jax.random.split(key, len(shapes[kind]))
        out = {"norm": ones((n, d))}
        out.update({name: dense(k, (n, *shape)) for k, (name, shape)
                    in zip(keys, sorted(shapes[kind].items()))})
        out.update({name: ones((n, *shape))
                    for name, shape in norms[kind].items()})
        return out

    def run(key, ffn, n):
        k_attn, k_ffn = jax.random.split(key)
        return {"attn": group(k_attn, "attn", n), "ffn": group(k_ffn, ffn, n)}

    k_embed, k_head, k_runs, k_mtp = jax.random.split(rng, 4)
    params = {"embed": dense(k_embed, (cfg.vocab_size, d)),
              "head": dense(k_head, (d, cfg.vocab_size)),
              "runs": [run(jax.random.fold_in(k_runs, i), ffn, n)
                       for i, (ffn, n) in enumerate(cfg.runs())],
              "final_norm": ones((d,))}
    if cfg.n_mtp:
        k_proj, k_block = jax.random.split(k_mtp)
        params["mtp"] = {"norm_h": ones((d,)), "norm_e": ones((d,)),
                         "proj": dense(k_proj, (2 * d, d)),
                         "block": run(k_block, SPARSE, 1),
                         "final_norm": ones((d,))}
    return params


# --------------------------------------------------------------------- #
# the block
# --------------------------------------------------------------------- #

def rope_cache(cfg: JoyAIConfig, seq_len: int):
    """(cos, sin), each ``[S, rope_dim / 2]`` float32: pair ``i`` turns
    by ``position * theta^(-2i / rope_dim)``; position = index."""
    inv_freq = cfg.rope_theta ** (
        -np.arange(0, cfg.rope_dim, 2, dtype=np.float64) / cfg.rope_dim)
    angle = np.outer(np.arange(seq_len, dtype=np.float64), inv_freq)
    return (jnp.asarray(np.cos(angle), jnp.float32),
            jnp.asarray(np.sin(angle), jnp.float32))


def rotate_pairs(x: jnp.ndarray, cos: jnp.ndarray,
                 sin: jnp.ndarray) -> jnp.ndarray:
    """The interleaved rotary in float32 (positions run to thousands of
    radians): x ``[B, S, heads, rope_dim]``, columns ``(2i, 2i + 1)`` a
    pair. Returns ``[rotated even columns ; rotated odd columns]``: the
    pairs' members apart, in one order for queries and keys."""
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, 2)
    even, odd = pairs[..., 0], pairs[..., 1]
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([even * cos - odd * sin,
                            odd * cos + even * sin], axis=-1)


def _latent_attn(u, p, rope, cfg: JoyAIConfig):
    """``Attn(u)`` of the module's head; u [B, S, d], p a layer's
    ``attn`` leaves."""
    B, S, _ = u.shape
    nh, dt, eps = cfg.n_heads, cfg.dtype, cfg.norm_eps
    dn, dr, dv, rank = cfg.nope_dim, cfg.rope_dim, cfg.v_dim, cfg.kv_lora_rank

    # float32 inside; the backward keeps the bf16 operand and runs the
    # rotation again (``sdar.py``'s lesson)
    @jax.checkpoint
    def rotated(x):
        return rotate_pairs(x, *rope).astype(dt)

    c_q = L._rmsnorm(u @ p["wq_a"].astype(dt), p["q_norm"], eps)
    q = (c_q @ p["wq_b"].astype(dt)).reshape(B, S, nh, dn + dr)
    latent = u @ p["wkv_a"].astype(dt)                   # [B, S, rank + dr]
    c_kv = L._rmsnorm(latent[..., :rank], p["kv_norm"], eps)
    kv = (c_kv @ p["wkv_b"].astype(dt)).reshape(B, S, nh, dn + dv)
    # ONE rotary key head under all the query heads: [B, S, 1, dr]
    k_rope = rotated(latent[..., None, rank:])
    # the kernels sit under ``bps.attn.mla`` (ops/flash_attention.py)
    publish_walk_sizes(S, 1, ATTN_BLOCK, ATTN_BLOCK, latent=True)
    o = latent_attention(q[..., :dn], rotated(q[..., dn:]), kv[..., :dn],
                         k_rope, kv[..., dn:], ATTN_BLOCK, ATTN_BLOCK)
    return o.reshape(B, S, nh * dv) @ p["wo"].astype(dt)


def _dense_ffn(u, p, cfg: JoyAIConfig):
    dt = cfg.dtype
    h = jax.nn.silu(u @ p["w1"].astype(dt)) * (u @ p["w3"].astype(dt))
    return h @ p["w2"].astype(dt)


def _block(x, p, bias, rope, cfg: JoyAIConfig, ffn, ep_axis):
    """One decoder block with FFN kind ``ffn``; p: one layer's ``{"attn",
    "ffn"}`` leaves, ``bias`` its row of the expert bias (None on a
    dense layer). Returns (x, the layer's additive statistics by counter
    name: none on a dense layer)."""
    h = L._rmsnorm(x, p["attn"]["norm"], cfg.norm_eps)
    x = x + _latent_attn(h, p["attn"], rope, cfg)
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


def head_nll(x, norm, head, targets, cfg: JoyAIConfig, last: int = 0):
    """The sum of the cross-entropies of ``targets`` [B, S] under
    ``RMSNorm(x) W_head``, the last ``last`` positions of every row
    left out. A row at a time under ``jax.checkpoint``: a row's float32
    logits ([S, vocabulary held]) live while it is folded and are made
    again in the backward, so two head passes never hold two logit
    blocks."""
    keep = jnp.arange(x.shape[1]) < x.shape[1] - last

    @jax.checkpoint
    def row(args):
        x_row, t_row = args
        logits = (L._rmsnorm(x_row, norm, cfg.norm_eps)
                  @ head.astype(cfg.dtype)).astype(jnp.float32)
        nll = jax.scipy.special.logsumexp(logits, axis=-1) \
            - jnp.take_along_axis(logits, t_row[:, None], axis=-1)[:, 0]
        return jnp.sum(jnp.where(keep, nll, 0.0))

    return jnp.sum(jax.lax.map(row, (x, targets)))


# --------------------------------------------------------------------- #
# forward and loss: a chain of links (``ops/chain.py``)
# --------------------------------------------------------------------- #

def _layers(cfg: JoyAIConfig, ffn, ep_axis, key, depth, **more) -> chain.Run:
    """``depth`` like layers of FFN kind ``ffn``, their leaves stacked
    under ``key``, scanned (a run of one layer too: a kernel's
    instruction is named alike in every run); the rotary table is made
    from the batch once a program."""
    def block(p, x, rope, *row):
        return _block(x, p, *(row or (None,)), rope, cfg, ffn, ep_axis)

    return chain.Run(
        block, key, depth, remat=cfg.remat,
        consts=lambda batch: rope_cache(
            cfg, L.split_batch(batch)[0].shape[1]), **more)


def _runs(cfg: JoyAIConfig, expert_bias, ep_axis) -> List[chain.Run]:
    """A ``chain.Run`` a run of ``cfg.runs()``, its stacked leaves under
    ``params["runs"][i]``. A sparse run reads its layers' rows of the
    bias and counts them into its rows of ``moe/expert_load`` ``[sparse
    layers + the module's block, n_held]``, zero elsewhere: the runs'
    tables and the module's add up to the step's. A scalar a layer is
    summed over a run's layers. (A link closes over nothing that is
    traced: the bias's rows are cut, and its gradient stopped, where
    the link runs.)"""
    rows = cfg.n_sparse_layers + cfg.n_mtp
    runs, first = [], 0
    for i, (ffn, n) in enumerate(cfg.runs()):
        sparse = ffn == SPARSE
        runs.append(_layers(
            cfg, ffn, ep_axis, ("runs", i), n,
            stats=lambda stacked, first=first: moe.run_stats(
                stacked, first, rows),
            each=(lambda batch, first=first, n=n: moe.bias_rows(
                expert_bias, cfg.n_experts, first, n)) if sparse else None))
        first += n if sparse else 0
    return runs


def mtp_hidden(params: Dict[str, Any], h: jnp.ndarray, targets: jnp.ndarray,
               cfg: JoyAIConfig, bias: jnp.ndarray, ep_axis: Optional[str]):
    """The prediction module's block output: position ``i`` joins ``h_L``
    at ``i`` with the embedding of ``t_{i+1}`` (``targets`` at ``i``: the
    SAME embedding leaf) and passes one sparse block, positions as in
    the main model. ``bias``: the block's row ``[n_experts]``. Returns
    (x [B, S, d], the block's statistics, ``[1, ...]`` each)."""
    p, dt, eps = params["mtp"], cfg.dtype, cfg.norm_eps
    with jax.named_scope("bps.mtp"):
        nxt = params["embed"].astype(dt)[targets]
        x = jnp.concatenate([L._rmsnorm(h, p["norm_h"], eps),
                             L._rmsnorm(nxt, p["norm_e"], eps)], axis=-1) \
            @ p["proj"].astype(dt)
    return _layers(cfg, SPARSE, ep_axis, ("mtp", "block"), 1).scan(
        p["block"], x, rope_cache(cfg, h.shape[1]), each=bias[None])


def _chain(cfg: JoyAIConfig, expert_bias, ep_axis) -> chain.Chain:
    """The loss as links: the lookup, a run a stretch of like layers,
    and a last link that holds the final norm, the main head's
    cross-entropy AND the prediction module (its block the last row of
    the bias and of the load, its norm, the second pass over the same
    ``head``, the lookup of ``t_{i+1}`` in the same ``embed``).
    ``embed`` lies under the first link and the last: a step that cuts
    the backward sums its two terms on the chip. Without a module the
    last link is the head alone."""
    rows = cfg.n_sparse_layers + cfg.n_mtp
    if expert_bias is not None and expert_bias.shape != (rows, cfg.n_experts):
        raise ValueError(
            f"expert_bias {expert_bias.shape}: a row a sparse layer and one "
            f"for the prediction module's block, ({rows}, {cfg.n_experts})")

    def embed(p, _, batch):
        return p["embed"].astype(cfg.dtype)[L.split_batch(batch)[0]], {}

    def heads(p, h, batch):
        # a link reads what it needs of the batch from ``batch``: the
        # cut step traces it on its own
        inputs, targets = L.split_batch(batch)
        n, S = inputs.shape
        loss = head_nll(h, p["final_norm"], p["head"], targets,
                        cfg) / (n * S)
        if not cfg.n_mtp:
            return loss, {}
        bias = moe.bias_rows(expert_bias, cfg.n_experts,
                             cfg.n_sparse_layers, 1)[0]
        x, st = mtp_hidden(p, h, targets, cfg, bias, ep_axis)
        with jax.named_scope("bps.mtp"):
            # the target of position i is the main target of i + 1; the
            # roll's wrapped last entry is masked
            nll = head_nll(x, p["mtp"]["final_norm"], p["head"],
                           jnp.roll(targets, -1, axis=1), cfg, last=1)
        stats = moe.run_stats(st, cfg.n_sparse_layers, rows)
        stats["mtp/predicted_tokens"] = jnp.asarray(n * (S - 1), jnp.int32)
        stats["mtp/nll_sum"] = nll
        return loss + cfg.mtp_weight * nll / (n * (S - 1)), stats

    keys = ("final_norm", "head") + (("mtp", "embed") if cfg.n_mtp else ())
    return chain.Chain((
        chain.Link(embed, "embed"), *_runs(cfg, expert_bias, ep_axis),
        chain.Link(heads, keys)))


def forward_hidden(params: Dict[str, Any], tokens: jnp.ndarray,
                   cfg: JoyAIConfig, expert_bias: Optional[jnp.ndarray] = None,
                   ep_axis: Optional[str] = None):
    """tokens [B, S] -> (the last block's output ``h_L`` [B, S, d],
    BEFORE the final norm: the head and the prediction module each norm
    it themselves; the layers' statistics as a step's: the load in the
    leading rows of ``[sparse layers + the module's block, n_held]``,
    the other counts summed over the layers)."""
    batch = {"inputs": tokens, "targets": tokens}
    x, stats = None, {}
    for ln in _chain(cfg, expert_bias, ep_axis).links[:-1]:
        x, st = ln(ln.pick(params), x, batch)
        chain.add_stats(stats, st)
    return x, stats


def loss_fn(params: Dict[str, Any], batch: Dict[str, jnp.ndarray],
            cfg: JoyAIConfig, expert_bias: Optional[jnp.ndarray] = None,
            ep_axis: Optional[str] = None):
    """(``L_main + mtp_weight * L_mtp`` over the vocabulary held, the
    step's statistics: ``lfm2.loss_fn``'s ``moe/*`` with the module's
    block as the last layer of ``moe/expert_load``, ``mtp/predicted_tokens``,
    the positions ``L_mtp`` was taken at, and ``mtp/nll_sum``, the sum of
    their cross-entropies; all add up across data shards as the step
    makers need). The embedding and the head are one leaf each and used
    twice: their gradients are the sums of both uses'.
    batch: ``{"tokens"}`` (shifted here) or pre-shifted ``{"inputs",
    "targets"}``; a row of ``S`` inputs gives ``S`` main positions and
    ``S - 1`` of the module (position ``i`` predicts ``t_{i+2}``, the
    target after its own: the last has none).

    Written as a chain (``ops/chain.py``, ``_chain``): any step maker
    runs it as one program; ``make_ps_train_step`` cuts its backward at
    the links: the program of the last link (both head passes and the
    module: ``head``, ``mtp`` and the norms leave behind it, ``embed``'s
    term stays on the chip), one a layer, and the lookup's, which adds
    its own term to ``embed``'s and hands the sum over once."""
    return _chain(cfg, expert_bias, ep_axis)(params, batch)
