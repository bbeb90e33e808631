"""BERT (Devlin et al. 2018) masked-LM loss in plain ``jax.numpy``,
float32 at ``highest`` matmul precision: the reference the timed path is
held to, with the seeded weights and batches both are given. Imports
nothing of the program.

Follows the paper and the released ``modeling.py``: learned position
embeddings, post-LN residual blocks, the tanh form of GELU, the MLM head
(dense, GELU, LayerNorm, decoder tied to the token embedding, bias).
Departures, both the program's: no segment embedding is added (the
pre-training batch here has one segment), and no dropout (rate 0).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import HIGHEST

def init_params(key, cfg: dict):
    """Seeded weights, normal(0, 0.02) as published, LayerNorm at 1/0,
    layers stacked on a leading axis. Jittable: made on the device."""
    d, f, L = cfg["hidden_size"], cfg["intermediate_size"], \
        cfg["num_hidden_layers"]
    V, P = cfg["vocab_size"], cfg["max_position_embeddings"]
    keys = jax.random.split(jax.random.fold_in(key, 0), 10)

    def dense(key, shape):
        return jax.random.normal(key, shape, jnp.float32) * 0.02

    ones, zeros = jnp.ones, jnp.zeros
    blocks = {
        "wq": dense(keys[0], (L, d, d)), "bq": zeros((L, d)),
        "wk": dense(keys[1], (L, d, d)), "bk": zeros((L, d)),
        "wv": dense(keys[2], (L, d, d)), "bv": zeros((L, d)),
        "wo": dense(keys[3], (L, d, d)), "bo": zeros((L, d)),
        "ln1_g": ones((L, d)), "ln1_b": zeros((L, d)),
        "w_in": dense(keys[4], (L, d, f)), "b_in": zeros((L, f)),
        "w_out": dense(keys[5], (L, f, d)), "b_out": zeros((L, d)),
        "ln2_g": ones((L, d)), "ln2_b": zeros((L, d)),
    }
    return {
        "tok_embed": dense(keys[6], (V, d)),
        "pos_embed": dense(keys[7], (P, d)),
        "type_embed": dense(keys[8], (cfg["type_vocab_size"], d)),
        "embed_ln_g": ones((d,)), "embed_ln_b": zeros((d,)),
        "blocks": blocks,
        "mlm_dense": dense(keys[9], (d, d)), "mlm_bias": zeros((d,)),
        "mlm_ln_g": ones((d,)), "mlm_ln_b": zeros((d,)),
        "mlm_out_bias": zeros((V,)),
    }


def make_batch(key, index, rows: int, cfg: dict) -> dict:
    """Batch ``index`` of the seed: uniform tokens, 15 % of positions
    masked for prediction (label -100 elsewhere). Every row differs."""
    k_tok, k_mask = jax.random.split(jax.random.fold_in(key, 1000 + index))
    shape = (rows, cfg["seq_len"])
    tokens = jax.random.randint(k_tok, shape, 0, cfg["vocab_size"], jnp.int32)
    masked = jax.random.uniform(k_mask, shape) < 0.15
    return {"tokens": tokens, "labels": jnp.where(masked, tokens, -100)}


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _layernorm(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def nll_sum(params, batch, cfg: dict, operand=None):
    """(sum of the masked positions' negative log-likelihoods, their
    count) for one block of rows. ``operand`` rounds both operands of
    every matrix product (the control's lower precision)."""
    q_ = operand or (lambda a: a)

    def mm(a, b):
        return jnp.matmul(q_(a), q_(b), precision=HIGHEST)

    tokens, labels = batch["tokens"], batch["labels"]
    B, S = tokens.shape
    nh = cfg["num_attention_heads"]
    d = cfg["hidden_size"]
    hd = d // nh
    eps = cfg["layer_norm_eps"]
    x = params["tok_embed"][tokens] + params["pos_embed"][None, :S]
    x = _layernorm(x, params["embed_ln_g"], params["embed_ln_b"], eps)

    def block(x, p):
        q = (mm(x, p["wq"]) + p["bq"]).reshape(B, S, nh, hd)
        k = (mm(x, p["wk"]) + p["bk"]).reshape(B, S, nh, hd)
        v = (mm(x, p["wv"]) + p["bv"]).reshape(B, S, nh, hd)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q_(q), q_(k),
                            precision=HIGHEST) / math.sqrt(hd)
        probs = jax.nn.softmax(logits, -1)
        attn = jnp.einsum("bhqk,bkhd->bqhd", q_(probs), q_(v),
                          precision=HIGHEST).reshape(B, S, d)
        x = _layernorm(x + mm(attn, p["wo"]) + p["bo"],
                       p["ln1_g"], p["ln1_b"], eps)
        h = mm(_gelu(mm(x, p["w_in"]) + p["b_in"]), p["w_out"]) + p["b_out"]
        return _layernorm(x + h, p["ln2_g"], p["ln2_b"], eps), None

    x, _ = jax.lax.scan(block, x, params["blocks"])
    h = _gelu(mm(x, params["mlm_dense"]) + params["mlm_bias"])
    h = _layernorm(h, params["mlm_ln_g"], params["mlm_ln_b"], eps)
    logits = mm(h, params["tok_embed"].T) + params["mlm_out_bias"]
    valid = labels >= 0
    logp = jax.nn.log_softmax(logits, -1)
    picked = jnp.take_along_axis(
        logp, jnp.where(valid, labels, 0)[..., None], -1)[..., 0]
    return -jnp.sum(picked * valid), jnp.sum(valid)


def slice_rows(batch: dict, start: int, stop: int) -> dict:
    return {k: v[start:stop] for k, v in batch.items()}


def model_flops_per_step(rows: int, cfg: dict) -> float:
    """Matrix-product FLOPs of one training step (forward + backward,
    nothing recomputed counted): 2 per multiply-add, backward twice the
    forward. Per token and layer: QKV and output projections 4 d^2, FFN
    2 d f, attention scores and mix 2 S d; then the MLM head's dense
    d^2 and the tied decoder d V over every position, as the program
    computes them."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    S, L, V = cfg["seq_len"], cfg["num_hidden_layers"], cfg["vocab_size"]
    macs_per_token = L * (4 * d * d + 2 * d * f + 2 * S * d) + d * d + d * V
    return 3.0 * 2.0 * macs_per_token * rows * S
