"""JoyAI-LLM-Flash (``model_type: joyai_llm_flash``, the DeepSeek-V3
layer at other numbers) training loss in plain ``jax.numpy``, float32 at
``highest`` matmul precision: the reference the timed path is held to,
with the seeded weights and batches both are given, and the counts of
operations and bytes the per-layer rooflines divide by. Imports nothing
of the program.

Written from the published ``config.json`` and, for the prediction
module, the DeepSeek-V3 report (section 2.2); ``x`` is ``[tokens,
hidden]``, ``RMSNorm(x) = x / sqrt(mean(x^2) + rms_norm_eps) * w``, no
bias anywhere:

- block: ``x += Attn(RMSNorm(x))``, ``x += FFN(RMSNorm(x))``;
- ``Attn`` (latent attention, 32 heads): ``c_q = RMSNorm(u W_qa)``
  (``q_lora_rank``), a head's ``[q_nope (qk_nope_head_dim) ; q_rope
  (qk_rope_head_dim)] = c_q W_qb``; ``[c_kv (kv_lora_rank) ; k_r
  (qk_rope_head_dim)] = u W_kva``, ``c_kv = RMSNorm(c_kv)``, a head's
  ``[k_nope ; v (v_head_dim)] = c_kv W_kvb``. ``q_rope`` and ``k_r`` are
  rotated by position (= index), pairs ``(2i, 2i + 1)`` by ``position *
  rope_theta^(-2i / qk_rope_head_dim)`` (``rope_interleave``), written
  back in place; ``k_r`` is ONE key head under all the query heads.
  ``s = (q_nope . k_nope + q_rope . k_rope) / sqrt(qk_nope_head_dim +
  qk_rope_head_dim)``, an explicit ``[S, S]`` causal mask, ``o =
  softmax(s) v``, ``Attn = concat(o) W_o``. Nothing is absorbed;
- ``FFN`` of the first ``first_k_dense_replace`` layers: ``W_2(silu(W_1 u)
  * W_3 u)``; of the others ``scores = sigmoid(u W_r)`` over all
  ``n_routed_experts``, ``sel = top_k(scores + b)`` (``topk_method:
  noaux_tc`` with one group), ``w = scores[sel] / (sum(scores[sel]) +
  1e-20) * routed_scaling_factor``, ``y = sum_e w_e E_e(u) + Shared(u)``,
  the sum a loop over the experts HELD, ``E_e`` and ``Shared`` SwiGLUs of
  ``moe_intermediate_size``;
- head: ``logits = RMSNorm(h_L) W_head``; ``L_main`` the mean
  cross-entropy of ``t_{i+1}`` at position ``i``;
- the prediction module (depth 1): ``h' = [RMSNorm_h(h_L at i) ;
  RMSNorm_e(Emb(t_{i+1}))] W_eh``, one sparse block on ``h'``, a norm of
  its own, the SAME ``W_head`` and the SAME ``Emb``; ``L_mtp`` the mean
  cross-entropy of ``t_{i+2}``; ``L = L_main + mtp_loss_weight * L_mtp``.

Departures from the published description, and nothing else: (1) the
share: layers ``0 .. num_hidden_layers - 1`` and the module, the experts
``first_expert_held ..`` of each sparse layer and the first
``vocab_size`` rows of the embedding and columns of the head are all
there is; what the absent experts would add is left out, here as in the
program; the shared expert is whole. (2) ``b``
(``e_score_correction_bias``) is a fixed buffer drawn from the seed the
configuration states under ``expert_bias`` (uniform), one row a sparse
layer and the last for the module's block; it is no parameter:
``expert_bias(cfg)`` makes it, for the program too. (3) What the config
has no key for is under ``assumed`` in the configuration's file: the
module's loss weight, that its block is sparse, the order ``[h ; e]``
under ``W_eh``, that ``h_L`` is taken before the final norm. (4) A batch
row holds ``seq_len + 1`` ids, so the module has a target at every
position but a row's last: ``seq_len - 1`` of them.

The parameter tree: ``runs`` is a list, the dense layers then the sparse
ones, a run's leaves stacked on a leading axis; the module under
``mtp`` with a run of one block. Rows, heads and experts are walked one
at a time under ``jax.checkpoint`` so that a block of 8192-token rows
fits beside the state: the same sums, less memory.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .common import HIGHEST

DENSE, SPARSE = "dense", "sparse"
GATE_SUM_EPS = 1e-20


def layer_runs(cfg: dict) -> list:
    """[(FFN kind, layers)] of the layers held: the leading
    ``first_k_dense_replace`` dense, the rest sparse; none empty."""
    dense = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    return [(kind, n) for kind, n in
            ((DENSE, dense), (SPARSE, cfg["num_hidden_layers"] - dense)) if n]


def sparse_layers(cfg: dict) -> int:
    """Sparse layers of the main model held (the module's block apart)."""
    return sum(n for kind, n in layer_runs(cfg) if kind == SPARSE)


def _check(cfg: dict) -> None:
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        raise ValueError("group-limited selection is not written: one "
                         "group has nothing to limit")
    if cfg["num_nextn_predict_layers"] != 1 or cfg["n_shared_experts"] != 1:
        raise ValueError("one prediction module and one shared expert")


def _leaf_shapes(cfg: dict) -> dict:
    """kind -> {leaf: shape of one layer's}; an int is the length of a
    norm weight."""
    d, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    F, f = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    E, H = cfg["n_routed_experts"], cfg["num_experts_held"]
    return {
        "attn": {"wq_a": (d, qr), "q_norm": qr, "wq_b": (qr, nh * (dn + dr)),
                 "wkv_a": (d, kvr + dr), "kv_norm": kvr,
                 "wkv_b": (kvr, nh * (dn + dv)), "wo": (nh * dv, d)},
        DENSE: {"w1": (d, F), "w3": (d, F), "w2": (F, d)},
        SPARSE: {"router": (d, E), "w_gate": (H, d, f), "w_up": (H, d, f),
                 "w_down": (H, f, d), "shared_gate": (d, f),
                 "shared_up": (d, f), "shared_down": (f, d)},
    }


def init_params(key, cfg: dict):
    """Seeded weights, normal(0, 0.02), norms at one. Jittable. The
    embedding and the head are one leaf each."""
    _check(cfg)
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    shapes = _leaf_shapes(cfg)
    count = iter(range(10_000))

    def normal(shape):
        return jax.random.normal(jax.random.fold_in(key, next(count)), shape,
                                 jnp.float32) * 0.02

    def group(kind, n):
        out = {"norm": jnp.ones((n, d))}
        for name, shape in shapes[kind].items():
            out[name] = jnp.ones((n, shape)) if isinstance(shape, int) \
                else normal((n, *shape))
        return out

    def run(ffn, n):
        return {"attn": group("attn", n), "ffn": group(ffn, n)}

    return {"embed": normal((V, d)), "head": normal((d, V)),
            "runs": [run(ffn, n) for ffn, n in layer_runs(cfg)],
            "final_norm": jnp.ones((d,)),
            "mtp": {"norm_h": jnp.ones((d,)), "norm_e": jnp.ones((d,)),
                    "proj": normal((2 * d, d)), "block": run(SPARSE, 1),
                    "final_norm": jnp.ones((d,))}}


def expert_bias(cfg: dict):
    """``[sparse layers + 1, n_routed_experts]`` float32: the selection
    bias of every sparse layer held and, last, of the module's block,
    uniform between the bounds and from the seed the configuration
    states; zeros where it states none."""
    shape = (sparse_layers(cfg) + 1, cfg["n_routed_experts"])
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
    """(cos, sin), each ``[S, qk_rope_head_dim / 2]`` float32."""
    dr = cfg["qk_rope_head_dim"]
    inv_freq = float(cfg["rope_theta"]) ** (
        -2.0 * np.arange(dr // 2, dtype=np.float64) / dr)
    angle = np.arange(seq_len, dtype=np.float64)[:, None] * inv_freq[None, :]
    return (jnp.asarray(np.cos(angle), jnp.float32),
            jnp.asarray(np.sin(angle), jnp.float32))


def rotate_interleaved(x, cos, sin):
    """x ``[S, heads, dr]``: columns ``(2i, 2i + 1)`` are a pair, turned
    by the position's angle ``i`` and written back where they were."""
    even, odd = x[..., 0::2], x[..., 1::2]
    cos, sin = cos[:, None, :], sin[:, None, :]
    turned = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], -1)
    return turned.reshape(x.shape)


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


def latent_attention(u, p, cfg: dict, mm):
    """Latent attention of one row, a head at a time: u ``[S, d]``."""
    nh, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    dn, dr, dv, kvr = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                       cfg["v_head_dim"], cfg["kv_lora_rank"])
    S = u.shape[0]
    cos, sin = rope_table(cfg, S)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    c_q = _rmsnorm(mm(u, p["wq_a"]), p["q_norm"], eps)
    q = mm(c_q, p["wq_b"]).reshape(S, nh, dn + dr)
    latent = mm(u, p["wkv_a"])
    c_kv = _rmsnorm(latent[:, :kvr], p["kv_norm"], eps)
    kv = mm(c_kv, p["wkv_b"]).reshape(S, nh, dn + dv)
    q_rope = rotate_interleaved(q[..., dn:], cos, sin)
    k_rope = rotate_interleaved(latent[:, None, kvr:], cos, sin)[:, 0]

    @jax.checkpoint
    def head(args):
        qn, qr, kn, vh = args                  # [S, dn], [S, dr], ...
        s = (mm(qn, kn.T) + mm(qr, k_rope.T)) / math.sqrt(dn + dr)
        return mm(jax.nn.softmax(jnp.where(causal, s, -1e30), -1), vh)

    out = jax.lax.map(head, tuple(a.transpose(1, 0, 2) for a in (
        q[..., :dn], q_rope, kv[..., :dn], kv[..., dn:])))
    return mm(out.transpose(1, 0, 2).reshape(S, nh * dv), p["wo"])


def swiglu(u, w_gate, w_up, w_down, mm):
    return mm(jax.nn.silu(mm(u, w_gate)) * mm(u, w_up), w_down)


def routed_experts(u, p, bias, cfg: dict, mm, first=None):
    """The routed part of a sparse layer from the experts HELD (``p``'s
    expert leaves are experts ``first ..``, the configuration's
    ``first_expert_held`` if not given): u ``[T, d]``, ``bias`` ``[E]``.
    Every token is routed over all the experts."""
    first = cfg.get("first_expert_held", 0) if first is None else first
    w, idx = route(jax.nn.sigmoid(mm(u, p["router"])), bias,
                   cfg["num_experts_per_tok"],
                   float(cfg["routed_scaling_factor"]))

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


def block(x, p, bias, cfg: dict, ffn, mm):
    """One decoder block on one row: x ``[S, d]``."""
    eps = cfg["rms_norm_eps"]
    u = _rmsnorm(x, p["attn"]["norm"], eps)
    x = x + latent_attention(u, p["attn"], cfg, mm)
    u = _rmsnorm(x, p["ffn"]["norm"], eps)
    if ffn == DENSE:
        return x + swiglu(u, p["ffn"]["w1"], p["ffn"]["w3"], p["ffn"]["w2"],
                          mm)
    return x + sparse_ffn(u, p["ffn"], bias, cfg, mm)


def _head_nll(x, norm, head, targets, cfg: dict, mm):
    """The cross-entropies of ``targets`` [S] under ``RMSNorm(x) W_head``."""
    logp = jax.nn.log_softmax(
        mm(_rmsnorm(x, norm, cfg["rms_norm_eps"]), head), -1)
    return -jnp.take_along_axis(logp, targets[:, None], -1)[:, 0]


def _run_blocks(x, run, bias, cfg: dict, ffn, mm):
    """A run's layers one after the other (a scan over its stacked
    leaves, each block under ``jax.checkpoint``): x ``[S, d]``, ``bias``
    ``[layers of the run, E]`` or None."""
    one_block = jax.checkpoint(
        lambda x, p, b: block(x, p, b, cfg, ffn, mm))
    layers = {"p": run} if bias is None else {"p": run, "bias": bias}
    x, _ = jax.lax.scan(
        lambda x, layer: (one_block(x, layer["p"], layer.get("bias")), None),
        x, layers)
    return x


def row_losses(params, inputs, targets, cfg: dict, mm):
    """(sum of the main cross-entropies of one row, sum of the
    module's): inputs, targets ``[S]``."""
    biases = jax.lax.stop_gradient(expert_bias(cfg))
    eps, S = cfg["rms_norm_eps"], inputs.shape[0]
    x = params["embed"][inputs]
    for (ffn, n), run in zip(layer_runs(cfg), params["runs"]):
        x = _run_blocks(x, run, biases[:-1] if ffn == SPARSE else None, cfg,
                        ffn, mm)
    # the module: h_L before the final norm at i, with Emb(t_{i+1})
    m = params["mtp"]
    joined = jnp.concatenate(
        [_rmsnorm(x, m["norm_h"], eps),
         _rmsnorm(params["embed"][targets], m["norm_e"], eps)], -1)
    y = _run_blocks(mm(joined, m["proj"]), m["block"], biases[-1:], cfg,
                    SPARSE, mm)
    # The two head passes one after the other (a scan over the pair, so
    # that one block of logits lives at a time). The module's position
    # i predicts t_{i+2}, the main target of i + 1; the last position of
    # a row has none and is left out of its sum
    keep = jnp.stack([jnp.ones((S,), bool), jnp.arange(S) < S - 1])
    head_nll = jax.checkpoint(
        lambda x, norm, t: _head_nll(x, norm, params["head"], t, cfg, mm))
    _, sums = jax.lax.scan(
        lambda _, a: (None, jnp.sum(jnp.where(a[3], head_nll(*a[:3]), 0.0))),
        None, (jnp.stack([x, y]),
               jnp.stack([params["final_norm"], m["final_norm"]]),
               jnp.stack([targets, jnp.roll(targets, -1)]), keep))
    return sums[0], sums[1]


def nll_sum(params, batch, cfg: dict, operand=None):
    """(``count`` times the loss of one block of rows, ``count``), with
    ``count`` the block's main positions: the harness divides the sums
    of a step's blocks, so the module's sum is weighed ``mtp_loss_weight
    * S / (S - 1)`` here (its mean is over ``S - 1`` positions a row).
    ``operand`` rounds both operands of every matrix product, the
    router's among them (the control's lower precision)."""
    _check(cfg)
    mm = _mm(operand)
    rows, S = batch["inputs"].shape
    one_row = jax.checkpoint(
        lambda row: row_losses(params, *row, cfg, mm))
    main, mtp = jax.lax.map(one_row, (batch["inputs"], batch["targets"]))
    weight = float(cfg["mtp_loss_weight"]) * S / (S - 1)
    return jnp.sum(main) + weight * jnp.sum(mtp), \
        jnp.asarray(rows * S, jnp.int32)


# --------------------------------------------------------------------- #
# counts: parameters, the model's FLOPs, the attention kernels' cost
# --------------------------------------------------------------------- #

def _group_size(cfg: dict, kind: str) -> int:
    """Parameters of one layer's group of ``kind`` with its block norm."""
    return cfg["hidden_size"] + sum(
        s if isinstance(s, int) else int(np.prod(s))
        for s in _leaf_shapes(cfg)[kind].values())


def param_count(cfg: dict) -> int:
    """Parameters held, from the leaves' shapes."""
    d = cfg["hidden_size"]
    layers = sum(n * (_group_size(cfg, "attn") + _group_size(cfg, ffn))
                 for ffn, n in layer_runs(cfg))
    module = 2 * d * d + 3 * d + _group_size(cfg, "attn") \
        + _group_size(cfg, SPARSE)
    return 2 * cfg["vocab_size"] * d + d + layers + module


def causal_pairs(seq_len: int) -> int:
    """(query, key) pairs one sequence's causal mask lets through."""
    return seq_len * (seq_len + 1) // 2


def expected_pairs_per_token(cfg: dict) -> float:
    """Pairs routed to the experts held, per token and sparse layer, if
    the router spread its choices evenly over all experts."""
    return cfg["num_experts_per_tok"] * cfg["num_experts_held"] \
        / cfg["n_routed_experts"]


def model_flops_per_step(rows: int, cfg: dict) -> float:
    """Matrix-product FLOPs of one training step (forward + backward,
    nothing recomputed counted): 2 per multiply-add, backward twice the
    forward. Per token and layer: latent attention's five projections
    (the two down, the two up, the output's); a dense FFN's three
    products or, in a sparse layer, the router, the shared expert's
    three and the held experts' three for the pairs an even router sends
    here. The module: its projection of ``2 d`` to ``d`` and one sparse
    block. The head over the vocabulary held, TWICE (the main pass and
    the module's). Scores over ``qk_nope + qk_rope`` and mix over
    ``v_head_dim``, a head, over the causal pairs, in every block."""
    d, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    f, S = cfg["moe_intermediate_size"], cfg["seq_len"]
    attn = d * qr + qr * nh * (dn + dr) + d * (kvr + dr) \
        + kvr * nh * (dn + dv) + nh * dv * d
    per_token = {
        DENSE: attn + 3 * d * cfg["intermediate_size"],
        SPARSE: attn + d * cfg["n_routed_experts"] + 3 * d * f
        + expected_pairs_per_token(cfg) * 3 * d * f,
    }
    blocks = dict(layer_runs(cfg))
    blocks[SPARSE] = blocks.get(SPARSE, 0) + 1           # the module's
    macs = rows * S * (sum(n * per_token[ffn] for ffn, n in blocks.items())
                       + 2 * d * d + 2 * d * cfg["vocab_size"])
    macs += sum(blocks.values()) * nh * (dn + dr + dv) * causal_pairs(S) * rows
    return 3.0 * 2.0 * macs


def attention_blocks(cfg: dict) -> int:
    """The blocks with latent attention a step runs: the layers held
    and the module's."""
    return cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"]


def attention_step_cost(rows: int, cfg: dict):
    """(FLOPs, bytes) a training step needs of ONE block's latent
    attention, however the program's kernels split or repeat the work:
    two products forward (scores, mix) and five backward (scores again,
    since no kernel keeps them; dP, dV, dK, dQ), seven in all, 2 FLOPs a
    multiply-add over the causal pairs and a head. Four of them run over
    the score's width ``qk_nope + qk_rope`` (scores twice, dQ, dK),
    three over ``v_head_dim`` (mix, dP, dV). Bytes, each tensor across
    HBM ONCE in bf16: q, the per-head keys, the one rotary key, v, the
    output, its cotangent and the four gradients (dq, dk, the rotary
    key's, dv); the row logsumexp (f32, one a query and head) once each
    way."""
    nh, S = cfg["num_attention_heads"], cfg["seq_len"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    flops = 2.0 * nh * (4 * (dn + dr) + 3 * dv) * causal_pairs(S) * rows
    per_position = 2 * nh * (dn + dr) + 2 * nh * dn + 2 * dr + 4 * nh * dv
    return flops, 2.0 * rows * S * per_position + 2 * 4.0 * rows * S * nh
