"""Mellum 2 (``model_type: mellum``) next-token loss in plain
``jax.numpy``, float32 at ``highest`` matmul precision: the reference the
timed path is held to, with the seeded weights and batches both are
given, and the counts of operations and bytes the per-layer rooflines
divide by. Imports nothing of the program.

Written from the published ``config.json``; ``x`` is ``[tokens, hidden]``:

- block ``l``: ``h = x + Attn_l(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``,
  no biases, untied embedding and head, a final RMSNorm, cross-entropy of
  the next token over the rows of the vocabulary held;
- ``Attn_l``: 32 query heads and 4 key/value heads of 128 (each shared by
  8 query heads), rotary on q and k, ``softmax(q k^T / sqrt(128) + mask_l)
  v``, with an explicit ``[S, S]`` mask: causal on ``full_attention``
  layers, causal and ``i - j < sliding_window`` on ``sliding_attention``;
- rotary (``rope_tables``): ``theta^(-2d/128)`` on sliding layers; YaRN on
  full layers;
- ``MoE``: ``p = softmax(x Wr)`` over all experts, the ``num_experts_per_tok``
  largest renormalised to sum to one, ``y = sum_e w_e (silu(x Wg_e) * (x
  Wu_e)) Wd_e`` as a loop over the experts HELD (``num_experts_held``
  from ``first_expert_held``): what the absent experts would add is left
  out here as in the program.

Rows, heads and experts are walked one at a time under
``jax.checkpoint`` so that a block of 8192-token rows fits beside the
state whatever its height: the same sums, less memory.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .common import HIGHEST

SLIDING, FULL = "sliding_attention", "full_attention"


def _sizes(cfg: dict):
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    return (cfg["hidden_size"], nh, nkv, hd, cfg["moe_intermediate_size"],
            cfg["num_experts"], cfg["num_experts_held"],
            cfg["num_hidden_layers"], cfg["vocab_size"])


def init_params(key, cfg: dict):
    """Seeded weights, normal(0, 0.02), norms at one, layers stacked on a
    leading axis, the experts held on the next. Jittable."""
    d, nh, nkv, hd, f, E, H, L, V = _sizes(cfg)
    keys = jax.random.split(jax.random.fold_in(key, 0), 10)

    def dense(key, shape):
        return jax.random.normal(key, shape, jnp.float32) * 0.02

    return {
        "embed": dense(keys[0], (V, d)),
        "blocks": {
            "attn_norm": jnp.ones((L, d)),
            "wq": dense(keys[1], (L, d, nh * hd)),
            "wk": dense(keys[2], (L, d, nkv * hd)),
            "wv": dense(keys[3], (L, d, nkv * hd)),
            "wo": dense(keys[4], (L, nh * hd, d)),
            "mlp_norm": jnp.ones((L, d)),
            "router": dense(keys[5], (L, d, E)),
            "w_gate": dense(keys[6], (L, H, d, f)),
            "w_up": dense(keys[7], (L, H, d, f)),
            "w_down": dense(keys[8], (L, H, f, d)),
        },
        "final_norm": jnp.ones((d,)),
        "lm_head": dense(keys[9], (d, V)),
    }


def make_batch(key, index, rows: int, cfg: dict) -> dict:
    """Batch ``index`` of the seed: ``seq_len + 1`` ids a row, uniform
    over the rows of the vocabulary held, as inputs and next tokens."""
    tokens = jax.random.randint(
        jax.random.fold_in(key, 1000 + index), (rows, cfg["seq_len"] + 1),
        0, cfg["vocab_size"], jnp.int32)
    return {"inputs": tokens[:, :-1], "targets": tokens[:, 1:]}


def slice_rows(batch: dict, start: int, stop: int) -> dict:
    return {k: v[start:stop] for k, v in batch.items()}


# --------------------------------------------------------------------- #
# rotary tables, written out
# --------------------------------------------------------------------- #

def yarn_inv_freq(section: dict, head_dim: int) -> np.ndarray:
    """``(1 - r_d) theta^(-2d/hd) / factor + r_d theta^(-2d/hd)``; ``r_d``
    is one minus the linear ramp over ``d`` between the correction
    dimensions of ``beta_fast`` and ``beta_slow`` at the original length
    (``hd ln(L / (2 pi beta)) / (2 ln theta)``, floored and ceiled),
    clipped to [0, 1]."""
    theta, L0 = section["rope_theta"], \
        section["original_max_position_embeddings"]
    d = np.arange(head_dim // 2, dtype=np.float64)
    plain = theta ** (-2.0 * d / head_dim)
    low, high = (head_dim * math.log(L0 / (2 * math.pi * beta))
                 / (2 * math.log(theta))
                 for beta in (section["beta_fast"], section["beta_slow"]))
    low, high = max(math.floor(low), 0), min(math.ceil(high), head_dim - 1)
    if low == high:
        high += 0.001
    r = 1.0 - np.clip((d - low) / (high - low), 0.0, 1.0)
    return (1.0 - r) * plain / section["factor"] + r * plain


def rope_tables(cfg: dict, seq_len: int) -> dict:
    """layer kind -> (cos, sin), each ``[S, head_dim / 2]`` float32."""
    hd = cfg["head_dim"]
    t = np.arange(seq_len, dtype=np.float64)
    sliding = cfg["rope_parameters"][SLIDING]
    full = cfg["rope_parameters"][FULL]
    plain = sliding["rope_theta"] ** (
        -2.0 * np.arange(hd // 2, dtype=np.float64) / hd)
    tables = {}
    for kind, inv_freq, factor in (
            (SLIDING, plain, 1.0),
            (FULL, yarn_inv_freq(full, hd), full["attention_factor"])):
        angle = t[:, None] * inv_freq[None, :]
        tables[kind] = (jnp.asarray(np.cos(angle) * factor, jnp.float32),
                        jnp.asarray(np.sin(angle) * factor, jnp.float32))
    return tables


def _rotate(x, cos, sin):
    """x ``[B, S, heads, hd]``: the two halves of a head rotated as a
    pair (the published ``rotate_half`` convention)."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


# --------------------------------------------------------------------- #
# the loss
# --------------------------------------------------------------------- #

def nll_sum(params, batch, cfg: dict, operand=None):
    """(sum of the positions' negative log-likelihoods, their count) for
    one block of rows. ``operand`` rounds both operands of every matrix
    product, the router's among them (the control's lower precision)."""
    q_ = operand or (lambda a: a)

    def mm(a, b):
        return jnp.matmul(q_(a), q_(b), precision=HIGHEST)

    d, nh, nkv, hd, f, E, H, L, V = _sizes(cfg)
    rows, S = batch["inputs"].shape
    B = 1                         # a row at a time, see ``one_row``
    eps, k = cfg["rms_norm_eps"], cfg["num_experts_per_tok"]
    first = cfg.get("first_expert_held", 0)
    kinds = cfg["layer_types"][:L]
    ropes = rope_tables(cfg, S)
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    masks = {FULL: i >= j,
             SLIDING: (i >= j) & (i - j < cfg["sliding_window"])}

    def attention(q, kk, v, mask):
        """One head at a time: q, kk, v ``[heads, B, S, hd]``."""
        @jax.checkpoint
        def head(qkv):
            qh, kh, vh = qkv
            logits = jnp.einsum("bqd,bkd->bqk", q_(qh), q_(kh),
                                precision=HIGHEST) / math.sqrt(hd)
            probs = jax.nn.softmax(jnp.where(mask, logits, -1e30), -1)
            return jnp.einsum("bqk,bkd->bqd", q_(probs), q_(vh),
                              precision=HIGHEST)
        return jax.lax.map(head, (q, kk, v))

    def experts(x, gates, idx, p):
        """x ``[T, d]``: a loop over the experts held."""
        @jax.checkpoint
        def one(y, e_w):
            e, wg, wu, wd = e_w
            w_e = jnp.sum(jnp.where(idx == first + e, gates, 0.0), -1)
            h = jax.nn.silu(mm(x, wg)) * mm(x, wu)
            return y + w_e[:, None] * mm(h, wd), None
        y, _ = jax.lax.scan(one, jnp.zeros_like(x), (
            jnp.arange(H), p["w_gate"], p["w_up"], p["w_down"]))
        return y

    def block(x, p, kind):
        cos, sin = ropes[kind]
        h = _rmsnorm(x, p["attn_norm"], eps)
        q = _rotate(mm(h, p["wq"]).reshape(B, S, nh, hd), cos, sin)
        kk = _rotate(mm(h, p["wk"]).reshape(B, S, nkv, hd), cos, sin)
        v = mm(h, p["wv"]).reshape(B, S, nkv, hd)
        kk, v = (jnp.repeat(a, nh // nkv, axis=2) for a in (kk, v))
        attn = attention(*(a.transpose(2, 0, 1, 3) for a in (q, kk, v)),
                         masks[kind])
        attn = attn.transpose(1, 2, 0, 3).reshape(B, S, nh * hd)
        x = x + mm(attn, p["wo"])
        h = _rmsnorm(x, p["mlp_norm"], eps).reshape(B * S, d)
        probs = jax.nn.softmax(mm(h, p["router"]), -1)
        gates, idx = jax.lax.top_k(probs, k)
        gates = gates / jnp.sum(gates, -1, keepdims=True)
        return x + experts(h, gates, idx, p).reshape(B, S, d)

    @jax.checkpoint
    def one_row(row):
        inputs, targets = (a[None] for a in row)
        x = params["embed"][inputs]
        for layer in range(L):
            p = jax.tree.map(lambda a: a[layer], params["blocks"])
            x = jax.checkpoint(block, static_argnums=2)(x, p, kinds[layer])
        x = _rmsnorm(x, params["final_norm"], eps)
        logp = jax.nn.log_softmax(mm(x, params["lm_head"]), -1)
        picked = jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]
        return -jnp.sum(picked)

    nll = jax.lax.map(one_row, (batch["inputs"], batch["targets"]))
    return jnp.sum(nll), jnp.asarray(rows * S, jnp.int32)


# --------------------------------------------------------------------- #
# counts: the model's FLOPs, and each kernel's operations and bytes
# --------------------------------------------------------------------- #

def band_pairs(seq_len: int, window=None) -> int:
    """(query, key) pairs one sequence's mask lets through: causal, and
    within ``window`` where one is given."""
    i = np.arange(seq_len, dtype=np.int64) + 1
    return int(np.sum(i if window is None else np.minimum(i, window)))


def expected_pairs_per_token(cfg: dict) -> float:
    """Pairs routed to the experts held, per token and layer, if the
    router spread its choices evenly over all experts."""
    return cfg["num_experts_per_tok"] * cfg["num_experts_held"] \
        / cfg["num_experts"]


def model_flops_per_step(rows: int, cfg: dict) -> float:
    """Matrix-product FLOPs of one training step (forward + backward,
    nothing recomputed counted): 2 per multiply-add, backward twice the
    forward. Per token and layer: the four attention projections, the
    router, the held experts' three products for the pairs an even
    router sends here; scores and mix over the mask's band only; the
    head over the vocabulary held."""
    d, nh, nkv, hd, f, E, H, L, V = _sizes(cfg)
    S = cfg["seq_len"]
    kinds = cfg["layer_types"][:L]
    per_token = L * (2 * d * nh * hd + 2 * d * nkv * hd + d * E
                     + expected_pairs_per_token(cfg) * 3 * d * f) + d * V
    band = sum(band_pairs(S, cfg["sliding_window"] if kind == SLIDING
                          else None) for kind in kinds)
    macs = per_token * rows * S + 2 * nh * hd * band * rows
    return 3.0 * 2.0 * macs


def expert_products_cost(pairs: float, cfg: dict):
    """(FLOPs, bytes) the held experts' three grouped products need for
    ``pairs`` (token, expert) pairs in all layers together, forward and
    backward (each product's two transposes), bf16 operands: a product
    reads its rows and writes its result once, and every layer's held
    experts' weights are read once a product and written once as a
    gradient."""
    d, f, H, L = (cfg["hidden_size"], cfg["moe_intermediate_size"],
                  cfg["num_experts_held"], cfg["num_hidden_layers"])
    flops = 3.0 * 2.0 * pairs * 3 * d * f
    rows_bytes = 2.0 * pairs * (2 * (d + f) + (f + d))   # in + out, bf16
    weight_bytes = 2.0 * L * H * 3 * d * f
    return flops, 3.0 * (rows_bytes + weight_bytes)


def attention_step_cost(rows: int, cfg: dict, kind: str):
    """(FLOPs, bytes) a training step needs of one attention layer of
    ``kind``, however the program's kernels split or repeat the work:
    two products forward (scores, mix) and five backward (scores again,
    since no kernel keeps them; dP, dV, dK, dQ). A product is 2 FLOPs
    per multiply-add over the (query, key) pairs the mask lets through
    (the window layer's count is of its band only), per query head and
    head dimension. Bytes, each tensor across HBM once in bf16: forward
    reads q, k, v and writes the output; backward reads those four and
    the output's cotangent and writes dq, dk, dv; the row logsumexp
    (f32, one a query and head) is written once and read once."""
    nh, nkv, hd, S = (cfg["num_attention_heads"],
                      cfg["num_key_value_heads"], cfg["head_dim"],
                      cfg["seq_len"])
    band = band_pairs(S, cfg["sliding_window"] if kind == SLIDING else None)
    flops = 7 * 2.0 * nh * hd * band * rows
    q_like, kv_like = 2.0 * rows * S * nh * hd, 2.0 * rows * S * nkv * hd
    lse = 4.0 * rows * S * nh
    forward = 2 * q_like + 2 * kv_like + lse        # q, out; k, v
    backward = 4 * q_like + 4 * kv_like + lse       # q, out, dout, dq; ...
    return flops, forward + backward
