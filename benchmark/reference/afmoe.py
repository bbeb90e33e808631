"""Trinity-Mini (``model_type: afmoe``) next-token loss in plain
``jax.numpy``, float32 at ``highest`` matmul precision: the reference the
timed path is held to, with the seeded weights and batches both are
given, and the counts of operations and bytes the per-layer rooflines
divide by. Imports nothing of the program.

Written from the published ``config.json`` and, where it has no key, the
released ``afmoe`` layer as its authors describe it (gated attention,
QK-norm, NoPE on global layers, sandwich norms, sigmoid routing under a
balancing bias, muP); ``x`` is ``[tokens, hidden]``, ``N(x) = x /
sqrt(mean(x^2) + rms_norm_eps) * w`` with a weight of its own wherever
it stands, no bias anywhere:

- ``x_0 = E[ids] * sqrt(hidden_size)`` (``mup_enabled``);
- block: ``h = x + N_2(Attn(N_1(x)))``, ``y = h + N_4(FFN(N_3(h)))``;
- ``Attn``, ``u = N_1(x)``: 32 query heads and 4 key/value heads of 128
  (each shared by 8 query heads); ``q_h <- N_128(q_h)``, ``k_h <-
  N_128(k_h)`` (one weight ``[128]`` each a layer); on a
  ``sliding_attention`` layer q and k are then rotated (``theta^(-2d /
  128)``, the two halves of a head paired, all 128 columns) and the
  explicit ``[S, S]`` mask is causal with ``i - j < sliding_window``; on
  a ``full_attention`` layer nothing is rotated and the mask is causal;
  ``o = softmax(q k^T / sqrt(128) + mask) v``; ``Attn = (concat_h(o) *
  sigmoid(u W_g)) W_o``;
- ``FFN`` of the first ``num_dense_layers`` layers held: ``W_2(silu(W_1
  u) * W_3 u)``; of the others ``s = sigmoid(u W_r)`` over all
  ``num_experts``, ``sel = top_k(s + b)``, ``w = s[sel] / (sum(s[sel]) +
  1e-20) * route_scale``, ``FFN = sum_e w_e E_e(u) + Shared(u)``, the sum
  a loop over the experts HELD, ``E_e`` and ``Shared`` SwiGLUs of
  ``moe_intermediate_size``;
- head: ``logits = N(y_L) W_head``, the cross-entropy of ``t_{i+1}`` at
  position ``i`` over the rows of the vocabulary held.

Departures from the published description, and nothing else: (1) the
share: the layers ``layer_types`` lists (the configuration's file holds
the kinds of the layers held, in order), the experts
``first_expert_held ..`` of each sparse layer and the first
``vocab_size`` rows of the embedding and columns of the head are all
there is; what the absent experts would add is left out, here as in the
program; the shared expert is whole. (2) ``b`` is a fixed buffer drawn
from the seed the configuration states under ``expert_bias`` (uniform),
one row a sparse layer; it is no parameter: ``expert_bias(cfg)`` makes
it, for the program too. (3) What the config has no key for is under
``assumed`` in the configuration's file.

The parameter tree follows the layer pattern: ``runs`` is a list, one
entry a maximal stretch of consecutive layers of one (attention, FFN)
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

SLIDING, FULL = "sliding_attention", "full_attention"
DENSE, SPARSE = "dense", "sparse"
GATE_SUM_EPS = 1e-20


def layer_kinds(cfg: dict) -> list:
    """(attention, FFN) kind of each layer held, in order: the first
    ``num_dense_layers`` of them have the dense FFN."""
    return [(kind, DENSE if i < cfg["num_dense_layers"] else SPARSE)
            for i, kind in enumerate(
                cfg["layer_types"][:cfg["num_hidden_layers"]])]


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


def _check(cfg: dict) -> None:
    if any(cfg[k] != 1 for k in ("n_group", "topk_group",
                                 "num_expert_groups", "num_limited_groups")):
        raise ValueError("group-limited selection is not written: one "
                         "group has nothing to limit")
    if cfg["score_func"] != "sigmoid" or not cfg["route_norm"] \
            or cfg["num_shared_experts"] != 1 or cfg["tie_word_embeddings"]:
        raise ValueError("sigmoid scores, the top-k weights normalised, "
                         "one shared expert and an untied head")


def _leaf_shapes(cfg: dict) -> dict:
    """kind -> {leaf: shape of one layer's}; an int is the length of a
    norm weight. Every sublayer has the norm of its input (``norm``) and
    of its output (``post_norm``)."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    F, f = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    E, H = cfg["num_experts"], cfg["num_experts_held"]
    norms = {"norm": d, "post_norm": d}
    return {
        "attn": {**norms, "wq": (d, q), "wk": (d, kv), "wv": (d, kv),
                 "wg": (d, q), "wo": (q, d), "q_norm": hd, "k_norm": hd},
        DENSE: {**norms, "w1": (d, F), "w3": (d, F), "w2": (F, d)},
        SPARSE: {**norms, "router": (d, E), "w_gate": (H, d, f),
                 "w_up": (H, d, f), "w_down": (H, f, d),
                 "shared_gate": (d, f), "shared_up": (d, f),
                 "shared_down": (f, d)},
    }


def init_params(key, cfg: dict):
    """Seeded weights, normal(0, 0.02), norms at one. Jittable."""
    _check(cfg)
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    shapes = _leaf_shapes(cfg)
    count = iter(range(10_000))

    def normal(shape):
        return jax.random.normal(jax.random.fold_in(key, next(count)), shape,
                                 jnp.float32) * 0.02

    def group(kind, n):
        return {name: jnp.ones((n, shape)) if isinstance(shape, int)
                else normal((n, *shape))
                for name, shape in shapes[kind].items()}

    return {"embed": normal((V, d)), "lm_head": normal((d, V)),
            "runs": [{"attn": group("attn", n), "ffn": group(ffn, n)}
                     for (_, ffn), n in layer_runs(cfg)],
            "final_norm": jnp.ones((d,))}


def expert_bias(cfg: dict):
    """``[sparse layers, num_experts]`` float32: the selection bias of
    every sparse layer held, uniform between the bounds and from the
    seed the configuration states; zeros where it states none."""
    shape = (sparse_layers(cfg), cfg["num_experts"])
    spec = cfg.get("expert_bias")
    if spec is None:
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


def mask(seq_len: int, kind: str, window: int):
    """The ``[S, S]`` mask of a layer of ``kind``, written out: query
    ``i`` sees key ``j <= i``, and on a sliding layer only ``i - j <
    window``."""
    i, j = jnp.arange(seq_len)[:, None], jnp.arange(seq_len)[None, :]
    return (i >= j) & (i - j < window) if kind == SLIDING else i >= j


def attention(u, p, cfg: dict, kind: str, mm):
    """Gated grouped-query attention of one row, a head at a time: u
    ``[S, d]`` the layer's normed input."""
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    S, eps = u.shape[0], cfg["rms_norm_eps"]
    seen = mask(S, kind, cfg["sliding_window"])
    q = _rmsnorm(mm(u, p["wq"]).reshape(S, nh, hd), p["q_norm"], eps)
    kk = _rmsnorm(mm(u, p["wk"]).reshape(S, nkv, hd), p["k_norm"], eps)
    v = mm(u, p["wv"]).reshape(S, nkv, hd)
    if kind == SLIDING:
        cos, sin = rope_table(cfg, S)
        q, kk = _rotate(q, cos, sin), _rotate(kk, cos, sin)
    kk, v = (jnp.repeat(a, nh // nkv, axis=1) for a in (kk, v))

    @jax.checkpoint
    def head(qkv):
        qh, kh, vh = qkv                           # [S, hd] each
        probs = jax.nn.softmax(jnp.where(
            seen, mm(qh, kh.T) / math.sqrt(hd), -1e30), -1)
        return mm(probs, vh)

    out = jax.lax.map(head, tuple(a.transpose(1, 0, 2) for a in (q, kk, v)))
    out = out.transpose(1, 0, 2).reshape(S, nh * hd)
    return mm(out * jax.nn.sigmoid(mm(u, p["wg"])), p["wo"])


def swiglu(u, w_gate, w_up, w_down, mm):
    return mm(jax.nn.silu(mm(u, w_gate)) * mm(u, w_up), w_down)


def routed_experts(u, p, bias, cfg: dict, mm, first=None):
    """The routed part of a sparse layer from the experts HELD (``p``'s
    expert leaves are experts ``first ..``, the configuration's
    ``first_expert_held`` if not given): u ``[T, d]``, ``bias`` ``[E]``.
    Every token is routed over all the experts."""
    first = cfg.get("first_expert_held", 0) if first is None else first
    w, idx = route(jax.nn.sigmoid(mm(u, p["router"])), bias,
                   cfg["num_experts_per_tok"], float(cfg["route_scale"]))

    @jax.checkpoint
    def one(y, e_w):
        e, wg, wu, wd = e_w
        w_e = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)
        return y + w_e[:, None] * swiglu(u, wg, wu, wd, mm), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        jnp.arange(p["w_gate"].shape[0]), p["w_gate"], p["w_up"],
        p["w_down"]))
    return y


def shared_expert(u, p, mm):
    return swiglu(u, p["shared_gate"], p["shared_up"], p["shared_down"], mm)


def sparse_ffn(u, p, bias, cfg: dict, mm):
    return routed_experts(u, p, bias, cfg, mm) + shared_expert(u, p, mm)


def block(x, p, bias, cfg: dict, kind, mm):
    """One decoder block on one row, four norms: x ``[S, d]``."""
    attn, ffn = kind
    eps = cfg["rms_norm_eps"]
    a, f = p["attn"], p["ffn"]
    u = _rmsnorm(x, a["norm"], eps)
    x = x + _rmsnorm(attention(u, a, cfg, attn, mm), a["post_norm"], eps)
    u = _rmsnorm(x, f["norm"], eps)
    out = swiglu(u, f["w1"], f["w3"], f["w2"], mm) if ffn == DENSE \
        else sparse_ffn(u, f, bias, cfg, mm)
    return x + _rmsnorm(out, f["post_norm"], eps)


def embed_scale(cfg: dict) -> float:
    return math.sqrt(cfg["hidden_size"]) if cfg["mup_enabled"] else 1.0


def nll_sum(params, batch, cfg: dict, operand=None):
    """(sum of the positions' negative log-likelihoods, their count) for
    one block of rows. ``operand`` rounds both operands of every matrix
    product, the router's among them (the control's lower precision)."""
    _check(cfg)
    mm = _mm(operand)
    rows, S = batch["inputs"].shape
    biases = jax.lax.stop_gradient(expert_bias(cfg))
    one_block = jax.checkpoint(
        lambda x, p, bias, kind: block(x, p, bias, cfg, kind, mm),
        static_argnums=3)

    @jax.checkpoint
    def one_row(row):
        inputs, targets = row
        x = params["embed"][inputs] * embed_scale(cfg)
        sparse_seen = 0
        for (kind, n), run in zip(layer_runs(cfg), params["runs"]):
            for i in range(n):
                p = jax.tree.map(lambda a: a[i], run)
                bias = None
                if kind[1] == SPARSE:
                    bias = biases[sparse_seen]
                    sparse_seen += 1
                x = one_block(x, p, bias, kind)
        x = _rmsnorm(x, params["final_norm"], cfg["rms_norm_eps"])
        logp = jax.nn.log_softmax(mm(x, params["lm_head"]), -1)
        picked = jnp.take_along_axis(logp, targets[:, None], -1)[:, 0]
        return -jnp.sum(picked)

    nll = jax.lax.map(one_row, (batch["inputs"], batch["targets"]))
    return jnp.sum(nll), jnp.asarray(rows * S, jnp.int32)


# --------------------------------------------------------------------- #
# counts: parameters, the model's FLOPs, the masks' and the experts' work
# --------------------------------------------------------------------- #

def param_count(cfg: dict) -> int:
    """Parameters held, from the leaves' shapes."""
    d = cfg["hidden_size"]
    shapes = _leaf_shapes(cfg)

    def group(kind):
        return sum(s if isinstance(s, int) else int(np.prod(s))
                   for s in shapes[kind].values())

    return 2 * cfg["vocab_size"] * d + d + sum(
        group("attn") + group(ffn) for _, ffn in layer_kinds(cfg))


def band_pairs(seq_len: int, window=None) -> int:
    """(query, key) pairs one sequence's mask lets through: causal, and
    within ``window`` where one is given."""
    i = np.arange(seq_len, dtype=np.int64) + 1
    return int(np.sum(i if window is None else np.minimum(i, window)))


def mask_triples_per_step(rows: int, cfg: dict) -> dict:
    """layer kind -> the (query, key, head) triples inside the masks of
    a step's layers of that kind: what the program counts as
    ``attn/window_pairs`` and ``attn/full_pairs``."""
    kinds = [kind for kind, _ in layer_kinds(cfg)]
    S, nh = cfg["seq_len"], cfg["num_attention_heads"]
    return {kind: rows * nh * kinds.count(kind) * band_pairs(
        S, cfg["sliding_window"] if kind == SLIDING else None)
        for kind in (SLIDING, FULL)}


def expected_pairs_per_token(cfg: dict) -> float:
    """Pairs routed to the experts held, per token and sparse layer, if
    the router spread its choices evenly over all experts."""
    return cfg["num_experts_per_tok"] * cfg["num_experts_held"] \
        / cfg["num_experts"]


def model_flops_per_step(rows: int, cfg: dict) -> float:
    """Matrix-product FLOPs of one training step (forward + backward,
    nothing recomputed counted): 2 per multiply-add, backward twice the
    forward. Per token and layer: attention's five projections (q, k, v,
    the gate, the output's); a dense FFN's three products or, in a
    sparse layer, the router, the shared expert's three and the held
    experts' three for the pairs an even router sends here; the head
    over the vocabulary held. Scores and mix over the mask's band
    only."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    f, S = cfg["moe_intermediate_size"], cfg["seq_len"]
    per_token = {
        DENSE: 3 * d * cfg["intermediate_size"],
        SPARSE: d * cfg["num_experts"] + 3 * d * f
        + expected_pairs_per_token(cfg) * 3 * d * f,
    }
    kinds = layer_kinds(cfg)
    macs = rows * S * (sum(3 * d * q + 2 * d * kv + per_token[ffn]
                           for _, ffn in kinds) + d * cfg["vocab_size"])
    macs += 2 * hd * sum(mask_triples_per_step(rows, cfg).values())
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
