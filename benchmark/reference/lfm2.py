"""LFM2-MoE (``model_type: lfm2_moe``) next-token loss in plain
``jax.numpy``, float32 at ``highest`` matmul precision: the reference the
timed path is held to, with the seeded weights and batches both are
given, and the counts of operations and bytes the per-layer rooflines
divide by. Imports nothing of the program.

Written from the published ``config.json`` and, where it has no key
(the head norms, the tied head), the published modelling code; ``x`` is
``[tokens, hidden]``, ``eps = norm_eps``, no bias anywhere:

- block ``l``: ``h = x + Op_l(RMSNorm(x))``, ``y = h + FFN_l(RMSNorm(h))``;
  after the last block one more RMSNorm, ``logits = x E^T`` with ``E``
  the embedding itself (tied), cross-entropy of the next token over the
  rows of the vocabulary held;
- ``Op_l`` for ``layer_types[l] == "conv"``: ``[B, C, X] = split_3(u
  W_in)``, ``z = B * X``, ``c_t = k_0 z_{t-2} + k_1 z_{t-1} + k_2 z_t``
  (``conv_L_cache`` 3; one filter a channel; ``z`` zero before a row's
  first position), ``Op = (C * c) W_out``: three shifted products, no
  convolution primitive;
- ``Op_l`` for ``"full_attention"``: 32 query heads and 8 key/value heads
  of 64 (each shared by 4 query heads); q and k RMS-normalised over the
  64 of each head, then rotated (``theta^(-2d/64)``, the two halves of
  a head paired), ``softmax(q k^T / 8 + causal) v`` with an explicit
  ``[S, S]`` mask;
- ``FFN_l`` for the first ``num_dense_layers`` layers held: ``W_2(silu(W_1
  u) * W_3 u)``; otherwise ``s = sigmoid(u W_r)`` over all experts, ``sel
  = top_k(s + b_l)``, ``w = s[sel] / (sum(s[sel]) + 1e-6) *
  routed_scaling_factor``, ``y = sum_e w_e W_2e(silu(W_1e u) * W_3e u)``
  as a loop over the experts HELD.

Departures from the published description, and nothing else: (1) the
share: the layers from ``first_layer_held``, the experts
``first_expert_held ..`` of each sparse layer and the first
``vocab_size`` rows of the one tied matrix are all there is; what the
absent experts would add is left out, here as in the program. (2) The
expert bias ``b`` is a fixed buffer drawn from the seed the
configuration states under ``expert_bias`` (uniform), not the published
zero start moved by a balancing rule the config does not give; it is no
parameter: ``expert_bias(cfg)`` makes it, for the program too.

The parameter tree follows the layer pattern: ``runs`` is a list, one
entry a maximal stretch of consecutive layers of one (operator, FFN)
kind, its leaves stacked on a leading axis. Rows, heads and experts are
walked one at a time under ``jax.checkpoint`` so that a block of
8192-token rows fits beside the state: the same sums, less memory.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .common import HIGHEST

CONV, FULL = "conv", "full_attention"
DENSE, SPARSE = "dense", "sparse"
GATE_SUM_EPS = 1e-6


def layer_kinds(cfg: dict) -> list:
    """(operator, FFN) kind of each layer held, in order: the published
    ``layer_types`` from ``first_layer_held``; the first
    ``num_dense_layers`` of those have the dense FFN."""
    first = cfg.get("first_layer_held", 0)
    ops = cfg["layer_types"][first:first + cfg["num_hidden_layers"]]
    return [(op, DENSE if i < cfg["num_dense_layers"] else SPARSE)
            for i, op in enumerate(ops)]


def layer_runs(cfg: dict) -> list:
    """[(kind, layers)]: the maximal stretches of one kind."""
    runs = []
    for kind in layer_kinds(cfg):
        if runs and runs[-1][0] == kind:
            runs[-1][1] += 1
        else:
            runs.append([kind, 1])
    return [(kind, n) for kind, n in runs]


def sparse_layers(cfg: dict) -> int:
    return sum(ffn == SPARSE for _, ffn in layer_kinds(cfg))


def _leaf_shapes(cfg: dict) -> dict:
    """kind -> {leaf: shape of one layer's}; an int is the length of a norm weight."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    F, f = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    E, H = cfg["num_experts"], cfg["num_experts_held"]
    return {
        CONV: {"w_in": (d, 3 * d), "kernel": (cfg["conv_L_cache"], d),
               "w_out": (d, d)},
        FULL: {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
               "q_norm": hd, "k_norm": hd},
        DENSE: {"w1": (d, F), "w3": (d, F), "w2": (F, d)},
        SPARSE: {"router": (d, E), "w_gate": (H, d, f), "w_up": (H, d, f),
                 "w_down": (H, f, d)},
    }


def init_params(key, cfg: dict):
    """Seeded weights, normal(0, 0.02), norms at one. Jittable."""
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    shapes = _leaf_shapes(cfg)
    count = iter(range(10_000))

    def group(kind, n):
        out = {"norm": jnp.ones((n, d))}
        for name, shape in shapes[kind].items():
            out[name] = jnp.ones((n, shape)) if isinstance(shape, int) \
                else jax.random.normal(
                    jax.random.fold_in(key, next(count)), (n, *shape),
                    jnp.float32) * 0.02
        return out

    embed = jax.random.normal(jax.random.fold_in(key, next(count)), (V, d),
                              jnp.float32) * 0.02
    return {"embed": embed,
            "runs": [{"op": group(op, n), "ffn": group(ffn, n)}
                     for (op, ffn), n in layer_runs(cfg)],
            "final_norm": jnp.ones((d,))}


def expert_bias(cfg: dict):
    """``[sparse layers, num_experts]`` float32: the selection bias of
    every sparse layer held, uniform between the bounds and from the
    seed the configuration states; zeros where it states none."""
    shape = (sparse_layers(cfg), cfg["num_experts"])
    spec = cfg.get("expert_bias")
    if not cfg.get("use_expert_bias", False) or spec is None:
        return jnp.zeros(shape, jnp.float32)
    return jax.random.uniform(jax.random.PRNGKey(spec["seed"]), shape,
                              jnp.float32, spec["low"], spec["high"])


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
# the layers, written out
# --------------------------------------------------------------------- #

def rope_table(cfg: dict, seq_len: int):
    """(cos, sin), each ``[S, head_dim / 2]`` float32."""
    hd = cfg["head_dim"]
    inv_freq = float(cfg["rope_theta"]) ** (
        -2.0 * np.arange(hd // 2, dtype=np.float64) / hd)
    angle = np.arange(seq_len, dtype=np.float64)[:, None] * inv_freq[None, :]
    return (jnp.asarray(np.cos(angle), jnp.float32),
            jnp.asarray(np.sin(angle), jnp.float32))


def _rotate(x, cos, sin):
    """x ``[S, heads, hd]``: the two halves of a head rotated as a pair
    (the published ``rotate_half`` convention)."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def short_conv(z, kernel):
    """z ``[S, d]``, kernel ``[L, d]``: ``c_t = sum_j kernel[j] z_{t - (L -
    1) + j}``, ``z`` zero before position 0; the shifted products written
    out one by one."""
    taps = kernel.shape[0]
    out = kernel[taps - 1] * z
    for back in range(1, taps):
        shifted = jnp.concatenate([jnp.zeros_like(z[:back]), z[:-back]], 0)
        out = out + kernel[taps - 1 - back] * shifted
    return out


def route(scores, bias, k: int, scale: float):
    """scores ``[T, E]`` (sigmoid), bias ``[E]``: (weights [T, k], idx
    [T, k]). The selection sees the bias, the weights do not."""
    _, idx = jax.lax.top_k(scores + bias, k)
    w = jnp.take_along_axis(scores, idx, -1)
    return w / (jnp.sum(w, -1, keepdims=True) + GATE_SUM_EPS) * scale, idx


def _mm(operand=None):
    """The matrix product of the reference: float32 at ``highest``,
    both operands through ``operand`` (the control's rounding) first."""
    q_ = operand or (lambda a: a)
    return lambda a, b: jnp.matmul(q_(a), q_(b), precision=HIGHEST)


def conv_op(u, p, cfg: dict, mm):
    """The gated short convolution of one row: u ``[S, d]``."""
    b, c, x = jnp.split(mm(u, p["w_in"]), 3, axis=-1)
    return mm(c * short_conv(b * x, p["kernel"]), p["w_out"])


def attn_op(u, p, cfg: dict, mm):
    """Grouped-query causal attention of one row, a head at a time."""
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    S, eps = u.shape[0], cfg["norm_eps"]
    cos, sin = rope_table(cfg, S)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    q = _rmsnorm(mm(u, p["wq"]).reshape(S, nh, hd), p["q_norm"], eps)
    kk = _rmsnorm(mm(u, p["wk"]).reshape(S, nkv, hd), p["k_norm"], eps)
    v = mm(u, p["wv"]).reshape(S, nkv, hd)
    q, kk = _rotate(q, cos, sin), _rotate(kk, cos, sin)
    kk, v = (jnp.repeat(a, nh // nkv, axis=1) for a in (kk, v))

    @jax.checkpoint
    def head(qkv):
        qh, kh, vh = qkv                           # [S, hd] each
        probs = jax.nn.softmax(jnp.where(
            causal, mm(qh, kh.T) / math.sqrt(hd), -1e30), -1)
        return mm(probs, vh)

    out = jax.lax.map(head, tuple(a.transpose(1, 0, 2) for a in (q, kk, v)))
    return mm(out.transpose(1, 0, 2).reshape(S, nh * hd), p["wo"])


def dense_ffn(u, p, cfg: dict, mm):
    return mm(jax.nn.silu(mm(u, p["w1"])) * mm(u, p["w3"]), p["w2"])


def sparse_ffn(u, p, bias, cfg: dict, mm):
    """The sparse layer's output from the experts HELD (``p``'s expert
    leaves are experts ``first_expert_held ..``): u ``[T, d]``, ``bias``
    ``[E]``. Every token is routed over all the experts."""
    first = cfg.get("first_expert_held", 0)
    w, idx = route(jax.nn.sigmoid(mm(u, p["router"])), bias,
                   cfg["num_experts_per_tok"],
                   float(cfg.get("routed_scaling_factor", 1.0)))

    @jax.checkpoint
    def one(y, e_w):
        e, wg, wu, wd = e_w
        w_e = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)
        h = jax.nn.silu(mm(u, wg)) * mm(u, wu)
        return y + w_e[:, None] * mm(h, wd), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        jnp.arange(p["w_gate"].shape[0]), p["w_gate"], p["w_up"],
        p["w_down"]))
    return y


def block(x, p, bias, cfg: dict, kind, mm):
    """One decoder block on one row: x ``[S, d]``."""
    op, ffn = kind
    eps = cfg["norm_eps"]
    u = _rmsnorm(x, p["op"]["norm"], eps)
    x = x + (conv_op if op == CONV else attn_op)(u, p["op"], cfg, mm)
    u = _rmsnorm(x, p["ffn"]["norm"], eps)
    return x + (dense_ffn(u, p["ffn"], cfg, mm) if ffn == DENSE
                else sparse_ffn(u, p["ffn"], bias, cfg, mm))


def nll_sum(params, batch, cfg: dict, operand=None):
    """(sum of the positions' negative log-likelihoods, their count) for
    one block of rows. ``operand`` rounds both operands of every matrix
    product, the router's among them (the control's lower precision)."""
    mm = _mm(operand)
    rows, S = batch["inputs"].shape
    biases = jax.lax.stop_gradient(expert_bias(cfg))
    one_block = jax.checkpoint(
        lambda x, p, bias, kind: block(x, p, bias, cfg, kind, mm),
        static_argnums=3)

    @jax.checkpoint
    def one_row(row):
        inputs, targets = row
        x = params["embed"][inputs]
        sparse_seen = 0
        for (kind, n), run in zip(layer_runs(cfg), params["runs"]):
            for i in range(n):
                p = jax.tree.map(lambda a: a[i], run)
                bias = None
                if kind[1] == SPARSE:
                    bias = biases[sparse_seen]
                    sparse_seen += 1
                x = one_block(x, p, bias, kind)
        x = _rmsnorm(x, params["final_norm"], cfg["norm_eps"])
        logp = jax.nn.log_softmax(mm(x, params["embed"].T), -1)
        picked = jnp.take_along_axis(logp, targets[:, None], -1)[:, 0]
        return -jnp.sum(picked)

    nll = jax.lax.map(one_row, (batch["inputs"], batch["targets"]))
    return jnp.sum(nll), jnp.asarray(rows * S, jnp.int32)


# --------------------------------------------------------------------- #
# counts: parameters, the model's FLOPs, the grouped products' cost
# --------------------------------------------------------------------- #

def param_count(cfg: dict) -> int:
    """Parameters held, from the leaves' shapes."""
    d = cfg["hidden_size"]
    shapes = _leaf_shapes(cfg)

    def group(kind):
        return d + sum(s if isinstance(s, int) else int(np.prod(s))
                       for s in shapes[kind].values())

    return cfg["vocab_size"] * d + d + sum(
        group(op) + group(ffn) for op, ffn in layer_kinds(cfg))


def causal_pairs(seq_len: int) -> int:
    """(query, key) pairs one sequence's causal mask lets through."""
    return seq_len * (seq_len + 1) // 2


def expected_pairs_per_token(cfg: dict) -> float:
    """Pairs routed to the experts held, per token and sparse layer, if
    the router spread its choices evenly over all experts."""
    return cfg["num_experts_per_tok"] * cfg["num_experts_held"] \
        / cfg["num_experts"]


def model_flops_per_step(rows: int, cfg: dict) -> float:
    """Matrix-product FLOPs of one training step (forward + backward,
    nothing recomputed counted): 2 per multiply-add, backward twice the
    forward. Per token: a conv operator's two projections (the filter's
    ``L`` multiply-adds a channel and the gates are no matrix product
    and are not counted), an attention operator's four, the dense FFN's
    three, a sparse layer's router and the held experts' three products
    for the pairs an even router sends here, the tied head over the
    vocabulary held; scores and mix over the causal pairs."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    S = cfg["seq_len"]
    per_token = {
        CONV: d * 3 * d + d * d,
        FULL: 2 * d * q + 2 * d * kv,
        DENSE: 3 * d * cfg["intermediate_size"],
        SPARSE: d * cfg["num_experts"] + expected_pairs_per_token(cfg)
        * 3 * d * cfg["moe_intermediate_size"],
    }
    kinds = layer_kinds(cfg)
    macs = rows * S * (sum(per_token[op] + per_token[ffn]
                           for op, ffn in kinds) + d * cfg["vocab_size"])
    macs += sum(op == FULL for op, _ in kinds) * 2 * q * causal_pairs(S) * rows
    return 3.0 * 2.0 * macs


def expert_products_cost(pairs: float, cfg: dict):
    """(FLOPs, bytes) the held experts' three grouped products need for
    ``pairs`` (token, expert) pairs in all SPARSE layers together,
    forward and backward (each product's two transposes), bf16 operands:
    a product reads its rows and writes its result once, and every
    sparse layer's held experts' weights are read once a product and
    written once as a gradient. The dense layers hold no expert."""
    d, f, H = (cfg["hidden_size"], cfg["moe_intermediate_size"],
               cfg["num_experts_held"])
    flops = 3.0 * 2.0 * pairs * 3 * d * f
    rows_bytes = 2.0 * pairs * (2 * (d + f) + (f + d))   # in + out, bf16
    weight_bytes = 2.0 * sparse_layers(cfg) * H * 3 * d * f
    return flops, 3.0 * (rows_bytes + weight_bytes)
