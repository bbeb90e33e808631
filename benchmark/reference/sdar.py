"""SDAR (``model_type: sdar_moe``) block-diffusion training loss in plain
``jax.numpy``, float32 at ``highest`` matmul precision: the reference the
timed path is held to, with the seeded weights and batches both are
given, and the counts of operations and bytes the per-layer rooflines
divide by. Imports nothing of the program.

Written from the published ``config.json`` and, where it has no key, from
the family's modelling code and the block-diffusion training recipe
(arXiv:2503.09573); ``x`` is ``[positions, hidden]``. A row of ``L``
tokens ``x_0`` runs as ``2 L`` positions, its noised copy ``x_t`` then
the clean copy: ``p < L`` is noised, ``p >= L`` clean, ``n(p) = p mod L``
its place in the row, ``b(p) = n(p) // B`` its block (``B`` =
``block_length``).

- input ids: the noised half holds ``mask_token_id`` where the batch's
  noise mask is set and ``x_0`` elsewhere, the clean half ``x_0``;
- block ``l``: ``h = x + Attn(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``,
  no biases, a final RMSNorm, untied embedding and head;
- ``Attn``: ``q = x Wq`` (32 heads of 128), ``k = x Wk``, ``v = x Wv`` (4
  heads of 128, each shared by 8 query heads); RMSNorm over each q and
  each k head (one weight ``[128]`` each, shared by the heads) before the
  rotary; rotary ``theta = 1e6``, no scaling, at position ``n(p)``;
  ``softmax(q k^T / sqrt(128) + M) v``; ``Wo``. ``M[p, r] = 0`` where
  (both noised and ``b(p) == b(r)``) or (``p`` noised, ``r`` clean and
  ``b(r) < b(p)``) or (both clean and ``b(r) <= b(p)``), else minus
  infinity: no clean query sees a noised key (``block_diffusion_mask``,
  the explicit ``[2 L, 2 L]`` array, made a block of query rows at a
  time);
- ``MoE``: ``p = softmax(x Wr)`` over all 128 experts in float32, the 8
  largest renormalised to sum to one, ``sum_e w_e (silu(x Wg_e) * (x
  Wu_e)) Wd_e`` as a loop over the experts HELD (``num_experts_held``
  from ``first_expert_held``): what the absent experts would add is left
  out here as in the program;
- loss of a block of rows: ``sum = sum_rows sum_{i < L, masked} (1 /
  t_{b(i)}) CE(logits_i, x_0[i])`` with the head over the noised half
  only and no shift, ``count = rows L``; the step's loss is ``sum /
  count``.

Departures from the source, each under ``assumed`` in the configuration's
file: the block length and the noise (``make_batch``: a rate ``t_b`` a
block, uniform in ``noise.rate_low .. noise.rate_high``, each token of
the block masked independently with probability ``t_b``, weight ``1 /
t_b``), which the release does not give; ``mask_token_id`` inside the
vocabulary slice; the q/k norm, for which the config has no key; no
router auxiliary loss.

Rows, layers, heads, blocks of queries, experts and blocks of the head's
positions are walked one at a time (``lax.map`` / ``lax.scan``) under
``jax.checkpoint`` so that a block of rows of 2 x 8192 positions fits
beside the state, in the float8 control too (a Python loop over the
layers left a zero-padded copy of every layer's gradient to be summed:
3.5 GiB more, TPU compiler, PR 36): the same sums, less memory.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .common import HIGHEST

# queries a walk step of one head's attention (its scores are
# [QUERY_BLOCK, 2 L] float32) and positions a walk step of the output
# head (its logits [QUERY_BLOCK, vocabulary])
QUERY_BLOCK = 2048


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
            "q_norm": jnp.ones((L, hd)),
            "k_norm": jnp.ones((L, hd)),
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
    """Batch ``index`` of the seed: ``seq_len`` clean ids a row, uniform
    over the rows of the vocabulary held below the mask token's; a rate
    a block, uniform between the configuration's two; the noise mask,
    each token masked independently at its block's rate. All from the
    run's key."""
    S, B = cfg["seq_len"], cfg["block_length"]
    k_ids, k_rate, k_mask = jax.random.split(
        jax.random.fold_in(key, 1000 + index), 3)
    tokens = jax.random.randint(k_ids, (rows, S), 0, cfg["mask_token_id"],
                                jnp.int32)
    rates = jax.random.uniform(
        k_rate, (rows, S // B), jnp.float32, cfg["noise"]["rate_low"],
        cfg["noise"]["rate_high"])
    noise = jax.random.uniform(k_mask, (rows, S)) \
        < jnp.repeat(rates, B, axis=1)
    return {"tokens": tokens, "noise_mask": noise, "rates": rates}


def slice_rows(batch: dict, start: int, stop: int) -> dict:
    return {k: v[start:stop] for k, v in batch.items()}


def block_diffusion_mask(seq_len: int, block_length: int,
                         queries=None) -> jax.Array:
    """``M`` as ``[2 L, 2 L]`` bool, true where a query (row) sees a key
    (column); with ``queries`` (positions), those rows of it. The
    reference makes it a block of queries at a time on the device: the
    whole of it is 268 MB at 2 x 8192 positions, and the integers it is
    made from four times that."""
    r = jnp.arange(2 * seq_len)
    p = r if queries is None else queries
    qn, kn = (p < seq_len)[:, None], (r < seq_len)[None, :]
    qb = (p % seq_len // block_length)[:, None]
    kb = (r % seq_len // block_length)[None, :]
    return ((qn & kn & (qb == kb)) | (qn & ~kn & (kb < qb))
            | (~qn & ~kn & (kb <= qb)))


def rope_table(cfg: dict, seq_len: int):
    """(cos, sin), each ``[2 L, head_dim / 2]`` float32: position
    ``n(p)``, so a row's table once for each copy."""
    hd = cfg["head_dim"]
    inv_freq = float(cfg["rope_theta"]) ** (
        -2.0 * np.arange(hd // 2, dtype=np.float64) / hd)
    angle = np.tile(np.arange(seq_len, dtype=np.float64), 2)[:, None] \
        * inv_freq[None, :]
    return (jnp.asarray(np.cos(angle), jnp.float32),
            jnp.asarray(np.sin(angle), jnp.float32))


def _rotate(x, cos, sin):
    """x ``[positions, heads, hd]``: the two halves of a head rotated as
    a pair (the published ``rotate_half`` convention)."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


# --------------------------------------------------------------------- #
# the loss
# --------------------------------------------------------------------- #

def nll_sum(params, batch, cfg: dict, operand=None):
    """(the weighted sum of the masked positions' negative
    log-likelihoods, ``rows * seq_len``) for one block of rows.
    ``operand`` rounds both operands of every matrix product, the
    router's among them (the control's lower precision)."""
    q_ = operand or (lambda a: a)

    def mm(a, b):
        return jnp.matmul(q_(a), q_(b), precision=HIGHEST)

    d, nh, nkv, hd, f, E, H, L, V = _sizes(cfg)
    rows, S = batch["tokens"].shape
    B, S2 = cfg["block_length"], 2 * S
    eps, k = cfg["rms_norm_eps"], cfg["num_experts_per_tok"]
    first = cfg.get("first_expert_held", 0)
    cos, sin = rope_table(cfg, S)
    qblk = QUERY_BLOCK if S2 % QUERY_BLOCK == 0 else S2
    hblk = QUERY_BLOCK if S % QUERY_BLOCK == 0 else S

    def attention(q, kk, v):
        """One head and one block of queries at a time: q, kk, v
        ``[heads, 2 L, hd]``."""
        @jax.checkpoint
        def head(qkv):
            qh, kh, vh = qkv

            @jax.checkpoint
            def queries(block):
                qb, first = block
                seen = block_diffusion_mask(S, B, first + jnp.arange(qblk))
                logits = jnp.einsum("qd,kd->qk", q_(qb), q_(kh),
                                    precision=HIGHEST) / math.sqrt(hd)
                probs = jax.nn.softmax(jnp.where(seen, logits, -1e30), -1)
                return jnp.einsum("qk,kd->qd", q_(probs), q_(vh),
                                  precision=HIGHEST)
            return jax.lax.map(queries, (
                qh.reshape(-1, qblk, hd),
                jnp.arange(0, S2, qblk))).reshape(S2, hd)
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

    def block(x, p):
        """x ``[2 L, d]``."""
        h = _rmsnorm(x, p["attn_norm"], eps)
        q = _rmsnorm(mm(h, p["wq"]).reshape(S2, nh, hd), p["q_norm"], eps)
        kk = _rmsnorm(mm(h, p["wk"]).reshape(S2, nkv, hd), p["k_norm"], eps)
        q, kk = _rotate(q, cos, sin), _rotate(kk, cos, sin)
        v = mm(h, p["wv"]).reshape(S2, nkv, hd)
        kk, v = (jnp.repeat(a, nh // nkv, axis=1) for a in (kk, v))
        attn = attention(*(a.transpose(1, 0, 2) for a in (q, kk, v)))
        x = x + mm(attn.transpose(1, 0, 2).reshape(S2, nh * hd), p["wo"])
        h = _rmsnorm(x, p["mlp_norm"], eps)
        probs = jax.nn.softmax(mm(h, p["router"]), -1)
        gates, idx = jax.lax.top_k(probs, k)
        gates = gates / jnp.sum(gates, -1, keepdims=True)
        return x + experts(h, gates, idx, p)

    @jax.checkpoint
    def one_row(row):
        clean, noise, rates = row
        ids = jnp.concatenate(
            [jnp.where(noise, cfg["mask_token_id"], clean), clean])
        x = params["embed"][ids]
        x, _ = jax.lax.scan(lambda x, p: (jax.checkpoint(block)(x, p), None),
                            x, params["blocks"])
        x = _rmsnorm(x[:S], params["final_norm"], eps)     # the noised half
        weight = jnp.where(noise, 1.0 / jnp.repeat(rates, B), 0.0)

        @jax.checkpoint
        def head(block):
            """A block of positions at a time: its logits are
            ``[hblk, vocabulary]`` float32."""
            xb, ids, w = block
            logp = jax.nn.log_softmax(mm(xb, params["lm_head"]), -1)
            return -jnp.sum(
                w * jnp.take_along_axis(logp, ids[:, None], -1)[:, 0])
        return jnp.sum(jax.lax.map(head, (
            x.reshape(-1, hblk, d), clean.reshape(-1, hblk),
            weight.reshape(-1, hblk))))

    nll = jax.lax.map(one_row, (batch["tokens"], batch["noise_mask"],
                                batch["rates"]))
    return jnp.sum(nll), jnp.asarray(rows * S, jnp.int32)


# --------------------------------------------------------------------- #
# counts: the model's FLOPs, and each kernel's operations and bytes
# --------------------------------------------------------------------- #

def mask_pairs(seq_len: int, block_length: int) -> int:
    """(query, key) pairs one row's mask lets through, ``L^2 + L B``: a
    noised query of block ``b`` sees its block's ``B`` noised keys and
    ``b B`` clean ones, a clean query ``(b + 1) B`` clean ones."""
    return seq_len * seq_len + seq_len * block_length


def expected_pairs_per_token(cfg: dict) -> float:
    """Pairs routed to the experts held, per position and layer, if the
    router spread its choices evenly over all experts."""
    return cfg["num_experts_per_tok"] * cfg["num_experts_held"] \
        / cfg["num_experts"]


def model_flops_per_step(rows: int, cfg: dict) -> float:
    """Matrix-product FLOPs of one training step (forward + backward,
    nothing recomputed counted): 2 per multiply-add, backward twice the
    forward. Per POSITION (two a token) and layer: the four attention
    projections, the router, the held experts' three products for the
    pairs an even router sends here; scores and mix over the pairs the
    mask lets through; the head over the noised half (one position a
    token) and the vocabulary held."""
    d, nh, nkv, hd, f, E, H, L, V = _sizes(cfg)
    S = cfg["seq_len"]
    per_position = L * (2 * d * nh * hd + 2 * d * nkv * hd + d * E
                        + expected_pairs_per_token(cfg) * 3 * d * f)
    macs = per_position * rows * 2 * S + d * V * rows * S \
        + L * 2 * nh * hd * mask_pairs(S, cfg["block_length"]) * rows
    return 3.0 * 2.0 * macs


def attention_step_cost(rows: int, cfg: dict):
    """(FLOPs, bytes) a training step needs of ONE layer's attention,
    however the program's kernels split or repeat the work: two products
    forward (scores, mix) and five backward (scores again, since no
    kernel keeps them; dP, dV, dK, dQ), seven in all. A product is 2
    FLOPs per multiply-add over the ``L^2 + L B`` pairs a row the mask
    lets through, per query head and head dimension. Bytes, each tensor
    of the ``2 L`` positions across HBM once in bf16: forward reads q,
    k, v and writes the output; backward reads those four and the
    output's cotangent and writes dq, dk, dv; the row logsumexp (f32,
    one a query and head) is written once and read once."""
    nh, nkv, hd, S = (cfg["num_attention_heads"],
                      cfg["num_key_value_heads"], cfg["head_dim"],
                      cfg["seq_len"])
    flops = 7 * 2.0 * nh * hd * mask_pairs(S, cfg["block_length"]) * rows
    q_like, kv_like = (2.0 * rows * 2 * S * nh * hd,
                       2.0 * rows * 2 * S * nkv * hd)
    lse = 4.0 * rows * 2 * S * nh
    forward = 2 * q_like + 2 * kv_like + lse        # q, out; k, v
    backward = 4 * q_like + 4 * kv_like + lse       # q, out, dout, dq; ...
    return flops, forward + backward
