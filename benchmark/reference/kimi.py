"""Kimi-Linear (``model_type: kimi_linear``) training loss in plain
``jax.numpy``, float32 at ``highest`` matmul precision: the reference
the timed path is held to, with the seeded weights and batches both are
given, and the counts of operations and bytes the per-layer rooflines
divide by. Imports nothing of the program, and holds NO chunk algebra:
Kimi Delta Attention is its recurrence, a position at a time.

Written from the published ``config.json`` and the Kimi Linear report
(arXiv:2510.26692, section 3); ``x`` is ``[tokens, hidden]``,
``RMSNorm(x) = x / sqrt(mean(x^2) + rms_norm_eps) * w``, ``H`` heads of
``d = linear_attn_config.head_dim``:

- block: ``x += Op(RMSNorm(x))``, ``x += FFN(RMSNorm(x))``; the
  operator of published layer ``n`` (counted from ONE) is KDA where
  ``n`` is in ``linear_attn_config.kda_layers`` and latent attention
  where it is in ``full_attn_layers``;
- KDA: ``q = SiLU(Conv(u W_q))``, ``k = SiLU(Conv(u W_k))``, ``v =
  SiLU(Conv(u W_v))``, ``Conv`` one causal filter of
  ``short_conv_kernel_size`` taps a channel (depthwise, no bias, zero
  before the row's first position; tap ``j`` weighs position ``t - (L -
  1) + j``). A head: ``q <- q / |q| d^(-1/2)``, ``k <- k / |k|`` with
  ``|x| = sqrt(sum x^2 + 1e-6)``. Decay ``g = -exp(A_log_h) softplus(
  W_fu (W_fd u) + dt_bias)``, ``[H, d]`` a token, ``alpha = exp(g)``;
  write strength ``beta = sigmoid(u W_beta)``, ``[H]``. State ``S_h``
  ``[d, d]``, zero at the row's start: ``S' = Diag(alpha_t) S_{t-1}``,
  ``S_t = S' + beta_t k_t (v_t - S'^T k_t)^T``, ``o_t = S_t^T q_t``.
  ``Op = concat_h(RMSNorm_h(o_t) sigmoid(W_gu (W_gd u) + b_g)) W_o``
  (``RMSNorm_h`` over a head's ``d`` with one weight ``[d]``). No
  positional term;
- latent attention, ``mla_use_nope``: a head's ``[q_n (qk_nope_head_dim)
  ; q_r (qk_rope_head_dim)] = u W_q`` (``q_lora_rank`` null: no query
  latent); ``[c_kv (kv_lora_rank) ; k_r (qk_rope_head_dim)] = u W_kva``,
  a head's ``[k_n ; v (v_head_dim)] = RMSNorm(c_kv) W_kvb``; ONE ``k_r``
  under all the query heads; NOTHING is rotated; ``s = (q_n . k_n + q_r
  . k_r) / sqrt(qk_nope_head_dim + qk_rope_head_dim)``, an explicit
  causal mask, ``o = softmax(s) v``, ``Op = concat(o) W_o``;
- ``FFN`` of the first ``first_k_dense_replace`` layers: ``W_2(silu(W_1
  u) * W_3 u)``; of the others ``scores = sigmoid(u W_r)`` over all
  ``num_experts``, ``sel = top_k(scores + b)`` (one group: a flat top-k),
  ``w = scores[sel] / (sum(scores[sel]) + 1e-20) *
  routed_scaling_factor``, ``y = sum_e w_e E_e(u) + Shared(u)``, the sum
  a loop over the experts HELD, ``E_e`` and ``Shared`` SwiGLUs of
  ``moe_intermediate_size``;
- head: ``logits = RMSNorm(h) W_head``; the loss is the mean
  cross-entropy of ``t_{i+1}`` at position ``i``.

Departures from the published description, and nothing else: (1) the
share: published layers ``1 .. num_hidden_layers``, the experts
``first_expert_held ..`` of each sparse layer and the first
``vocab_size`` rows of the embedding and columns of the head are all
there is; what the absent experts would add is left out, here as in the
program; the shared expert is whole. (2) ``b`` (the released gate's
``e_score_correction_bias``) is a fixed buffer drawn from the seed the
configuration states under ``expert_bias`` (uniform), one row a sparse
layer; it is no parameter: ``expert_bias(cfg)`` makes it, for the
program too. (3) What the config has no key for is under ``assumed`` in
the configuration's file: the gates' rank, ``b_g``, how ``A_log``,
``dt_bias`` and the filters are seeded, the two ``1e-6`` and ``1e-20``.

The parameter tree: ``embed``, ``lm_head``, ``final_norm`` and one entry
``run<nn>`` a stretch of like layers (operator and FFN kind), its leaves
stacked on a leading axis under ``op`` and ``ffn``. What keeps a block
of 8192-token rows beside the parameters, two moments and two sets of
gradients (the same sums, less memory; TPU compiler, PR 43: 2.2 GiB of
temporaries a row where the plain nesting took 7.05 and did not fit): a
block at a time under ``jax.checkpoint`` with the rows one after the
other INSIDE it (the backward's loop over rows carries one layer's
gradients, not the tree's), operator and FFN apart; an operator ``HEAD_GROUP`` heads at a time (its
projections' columns, its recurrence or its scores, its rows of the
output projection); the recurrence in segments (a two-level checkpoint:
its backward holds a state a segment and a segment's states, not 8192);
latent attention's queries, the dense FFN and the head's logits
``HEAD_BLOCK`` positions at a time; the held experts one at a time.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .common import HIGHEST

KDA, MLA = "kda", "mla"
DENSE, SPARSE = "dense", "sparse"
GATE_SUM_EPS = 1e-20
L2_EPS = 1e-6
# positions a segment of the recurrence's two-level checkpoint
SEGMENT = 64
# positions a block of the head's logits, of the dense FFN and of latent
# attention's queries
HEAD_BLOCK = 512
# heads a walk of an operator
HEAD_GROUP = 4


def layer_ops(cfg: dict) -> list:
    """The operator of each layer held: published layers ``1 ..
    num_hidden_layers``, by the config's two lists."""
    lin = cfg["linear_attn_config"]
    ops = []
    for n in range(1, cfg["num_hidden_layers"] + 1):
        if (n in lin["kda_layers"]) == (n in lin["full_attn_layers"]):
            raise ValueError(f"layer {n}: in one of kda_layers and "
                             f"full_attn_layers")
        ops.append(KDA if n in lin["kda_layers"] else MLA)
    return ops


def layer_runs(cfg: dict) -> list:
    """[(operator, FFN kind, layers)]: the layers held as stretches of
    like blocks, in order."""
    runs = []
    for i, op in enumerate(layer_ops(cfg)):
        ffn = DENSE if i < cfg["first_k_dense_replace"] else SPARSE
        if runs and runs[-1][:2] == (op, ffn):
            runs[-1] = (op, ffn, runs[-1][2] + 1)
        else:
            runs.append((op, ffn, 1))
    return runs


def run_key(i: int) -> str:
    return f"run{i:02d}"


def sparse_layers(cfg: dict) -> int:
    return sum(n for _, ffn, n in layer_runs(cfg) if ffn == SPARSE)


def kda_layers(cfg: dict) -> int:
    return layer_ops(cfg).count(KDA)


def _check(cfg: dict) -> None:
    if cfg["num_expert_group"] != 1 or cfg["topk_group"] != 1:
        raise ValueError("group-limited selection is not written: one "
                         "group has nothing to limit")
    if cfg["num_shared_experts"] != 1 or cfg["num_nextn_predict_layers"]:
        raise ValueError("one shared expert and no prediction module")
    if cfg["q_lora_rank"] is not None or not cfg["mla_use_nope"]:
        raise ValueError("latent attention with a direct query projection "
                         "and no rotary")


def _leaf_shapes(cfg: dict) -> dict:
    """kind -> {leaf: shape of one layer's}; an int is the length of a
    norm weight."""
    d, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    lin = cfg["linear_attn_config"]
    wide, rank = lin["num_heads"] * lin["head_dim"], cfg["kda_gate_rank"]
    taps = lin["short_conv_kernel_size"]
    kvr = cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    F, f = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    E, H = cfg["num_experts"], cfg["num_experts_held"]
    return {
        KDA: {"wq": (d, wide), "wk": (d, wide), "wv": (d, wide),
              "conv_q": (taps, wide), "conv_k": (taps, wide),
              "conv_v": (taps, wide), "f_down": (d, rank),
              "f_up": (rank, wide), "A_log": (lin["num_heads"],),
              "dt_bias": (wide,), "w_beta": (d, lin["num_heads"]),
              "g_down": (d, rank), "g_up": (rank, wide), "g_bias": (wide,),
              "o_norm": lin["head_dim"], "wo": (wide, d)},
        MLA: {"wq": (d, nh * (dn + dr)), "wkv_a": (d, kvr + dr),
              "kv_norm": kvr, "wkv_b": (kvr, nh * (dn + dv)),
              "wo": (nh * dv, d)},
        DENSE: {"w1": (d, F), "w3": (d, F), "w2": (F, d)},
        SPARSE: {"router": (d, E), "w_gate": (H, d, f), "w_up": (H, d, f),
                 "w_down": (H, f, d), "shared_gate": (d, f),
                 "shared_up": (d, f), "shared_down": (f, d)},
    }


def init_params(key, cfg: dict):
    """Seeded weights. Matrices normal(0, 0.02), norms at one; KDA's
    filters uniform in +-1/sqrt(taps) (a depthwise filter's usual
    start), ``A_log = log(uniform(1, 16))`` a head, ``dt_bias`` the
    inverse softplus of a step log-uniform in [1e-3, 0.1] a channel,
    ``g_bias`` zero (the released layer's start; the configuration's
    ``assumed`` says so). Jittable."""
    _check(cfg)
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    shapes = _leaf_shapes(cfg)
    taps = cfg["linear_attn_config"]["short_conv_kernel_size"]
    count = iter(range(10_000))

    def draw(fn, *args, **kw):
        return fn(jax.random.fold_in(key, next(count)), *args, **kw)

    def normal(shape):
        return draw(jax.random.normal, shape, jnp.float32) * 0.02

    def uniform(shape, low, high):
        return draw(jax.random.uniform, shape, jnp.float32, low, high)

    def leaf(name, shape, n):
        if isinstance(shape, int):
            return jnp.ones((n, shape))
        if name.startswith("conv_"):
            return uniform((n, *shape), -taps ** -0.5, taps ** -0.5)
        if name == "A_log":
            return jnp.log(uniform((n, *shape), 1.0, 16.0))
        if name == "dt_bias":
            step = jnp.exp(uniform((n, *shape), math.log(1e-3),
                                   math.log(0.1)))
            return step + jnp.log(-jnp.expm1(-step))
        if name == "g_bias":
            return jnp.zeros((n, *shape))
        return normal((n, *shape))

    def group(kind, n):
        out = {"norm": jnp.ones((n, d))}
        out.update({name: leaf(name, shape, n)
                    for name, shape in shapes[kind].items()})
        return out

    params = {"embed": normal((V, d)), "lm_head": normal((d, V)),
              "final_norm": jnp.ones((d,))}
    for i, (op, ffn, n) in enumerate(layer_runs(cfg)):
        params[run_key(i)] = {"op": group(op, n), "ffn": group(ffn, n)}
    return params


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

def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _mm(operand=None):
    """The matrix product of the reference: float32 at ``highest``,
    both operands through ``operand`` (the control's rounding) first."""
    q_ = operand or (lambda a: a)
    return lambda a, b: jnp.matmul(q_(a), q_(b), precision=HIGHEST)


def conv_silu(x, kernel):
    """``silu(conv(x))`` of one row: x ``[S, c]``, kernel ``[L, c]``;
    position ``t`` reads ``t - (L - 1) .. t``, zeros before the row."""
    taps, S = kernel.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1])), x])
    return jax.nn.silu(sum(kernel[j] * padded[j:j + S]
                           for j in range(taps)))


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def _largest_divisor(n: int, cap: int) -> int:
    """The largest divisor of ``n`` that is at most ``cap``: the size of
    a walk's step that leaves no ragged tail."""
    return max(m for m in range(1, min(n, cap) + 1) if n % m == 0)


def delta_recurrence(q, k, v, g, beta):
    """The gated delta rule of one row, a position at a time: q, k, g
    ``[S, H, d_k]``, v ``[S, H, d_v]``, beta ``[S, H]`` -> o ``[S, H,
    d_v]``. The state ``[H, d_k, d_v]`` is float32 and starts at zero."""
    S, H, d_k = q.shape

    def step(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = state * jnp.exp(g_t)[..., None]
        read = jnp.einsum("hkv,hk->hv", state, k_t, precision=HIGHEST)
        state = state + k_t[..., None] * (b_t[:, None] * (v_t - read)
                                          )[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t,
                                 precision=HIGHEST)

    @jax.checkpoint
    def segment(state, xs):
        return jax.lax.scan(step, state, xs)

    n = _largest_divisor(S, SEGMENT)
    xs = tuple(a.reshape(S // n, n, *a.shape[1:]) for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(segment,
                        jnp.zeros((H, d_k, v.shape[-1]), jnp.float32), xs)
    return o.reshape(S, H, v.shape[-1])


def _head_groups(heads: int):
    """(heads a group, groups): heads do not meet before the output
    projection, so an operator walks them ``HEAD_GROUP`` at a time."""
    n = _largest_divisor(heads, HEAD_GROUP)
    return n, heads // n


def _by_group(w, groups: int):
    """``[..., groups * c]`` -> ``[groups, ..., c]``: a group's columns."""
    return jnp.moveaxis(w.reshape(*w.shape[:-1], groups, -1), -2, 0)


def kda(u, p, cfg: dict, mm):
    """Kimi Delta Attention of one row: u ``[S, d]``. A group of heads
    at a time under ``jax.checkpoint`` (its projections' columns, its
    filters, its gates, its recurrence, its rows of the output
    projection): the same sums, a group's float32 intermediates at a
    time."""
    lin, eps = cfg["linear_attn_config"], cfg["rms_norm_eps"]
    hd, S = lin["head_dim"], u.shape[0]
    n, groups = _head_groups(lin["num_heads"])
    mine = {name: _by_group(p[name], groups) for name in (
        "wq", "wk", "wv", "conv_q", "conv_k", "conv_v", "f_up", "dt_bias",
        "A_log", "w_beta", "g_up", "g_bias")}
    mine["wo"] = p["wo"].reshape(groups, n * hd, -1)
    low_f, low_g = mm(u, p["f_down"]), mm(u, p["g_down"])

    @jax.checkpoint
    def group(w):
        def heads(name, kernel):
            return conv_silu(mm(u, w[name]), w[kernel]).reshape(S, n, hd)

        q = _unit(heads("wq", "conv_q")) * hd ** -0.5
        k = _unit(heads("wk", "conv_k"))
        rate = jax.nn.softplus(mm(low_f, w["f_up"]) + w["dt_bias"])
        g = -jnp.exp(w["A_log"])[:, None] * rate.reshape(S, n, hd)
        beta = jax.nn.sigmoid(mm(u, w["w_beta"]))
        o = delta_recurrence(q, k, heads("wv", "conv_v"), g, beta)
        gate = jax.nn.sigmoid(mm(low_g, w["g_up"]) + w["g_bias"])
        return mm(_rmsnorm(o, p["o_norm"], eps).reshape(S, n * hd) * gate,
                  w["wo"])

    return jnp.sum(jax.lax.map(group, mine), 0)


def latent_attention(u, p, cfg: dict, mm):
    """Latent attention without rotary of one row: a group of heads at
    a time, a head at a time inside it, its queries in blocks against
    every key under an explicit causal mask."""
    nh, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    dn, dr, dv, kvr = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                       cfg["v_head_dim"], cfg["kv_lora_rank"])
    S, rows = u.shape[0], _block_rows(u.shape[0])
    n, groups = _head_groups(nh)
    first = jnp.arange(0, S, rows)
    latent = mm(u, p["wkv_a"])
    c_kv = _rmsnorm(latent[:, :kvr], p["kv_norm"], eps)
    k_r = latent[:, kvr:]

    @jax.checkpoint
    def head(args):
        qn, qr, kn, vh = args

        @jax.checkpoint
        def block(args):        # a block of queries against every key
            qn_b, qr_b, start = args
            s = (mm(qn_b, kn.T) + mm(qr_b, k_r.T)) / math.sqrt(dn + dr)
            seen = (start + jnp.arange(rows))[:, None] \
                >= jnp.arange(S)[None, :]
            return mm(jax.nn.softmax(jnp.where(seen, s, -1e30), -1), vh)

        return jax.lax.map(block, (qn.reshape(S // rows, rows, dn),
                                   qr.reshape(S // rows, rows, dr), first)
                           ).reshape(S, dv)

    @jax.checkpoint
    def group(w):
        q = mm(u, w["wq"]).reshape(S, n, dn + dr)
        kv = mm(c_kv, w["wkv_b"]).reshape(S, n, dn + dv)
        out = jax.lax.map(head, tuple(a.transpose(1, 0, 2) for a in (
            q[..., :dn], q[..., dn:], kv[..., :dn], kv[..., dn:])))
        return mm(out.transpose(1, 0, 2).reshape(S, n * dv), w["wo"])

    return jnp.sum(jax.lax.map(group, {
        "wq": _by_group(p["wq"], groups),
        "wkv_b": _by_group(p["wkv_b"], groups),
        "wo": p["wo"].reshape(groups, n * dv, -1)}), 0)


_OPS = {KDA: kda, MLA: latent_attention}


def swiglu(u, w_gate, w_up, w_down, mm):
    return mm(jax.nn.silu(mm(u, w_gate)) * mm(u, w_up), w_down)


def _block_rows(S: int) -> int:
    return _largest_divisor(S, HEAD_BLOCK)


def dense_ffn(u, p, mm):
    """The dense SwiGLU, ``HEAD_BLOCK`` positions at a time."""
    S, n = u.shape[0], _block_rows(u.shape[0])
    some = jax.checkpoint(lambda u_b: swiglu(u_b, p["w1"], p["w3"], p["w2"],
                                             mm))
    return jax.lax.map(some, u.reshape(S // n, n, -1)).reshape(u.shape)


def route(scores, bias, k: int, scale: float):
    """scores ``[T, E]`` (sigmoid), bias ``[E]``: (weights [T, k], idx
    [T, k]). The selection sees the bias, the weights do not."""
    _, idx = jax.lax.top_k(scores + bias, k)
    w = jnp.take_along_axis(scores, idx, -1)
    return w / (jnp.sum(w, -1, keepdims=True) + GATE_SUM_EPS) * scale, idx


def routed_experts(u, p, bias, cfg: dict, mm, first=None):
    """The routed part of a sparse layer from the experts HELD (``p``'s
    expert leaves are experts ``first ..``, the configuration's
    ``first_expert_held`` if not given): u ``[T, d]``, ``bias`` ``[E]``.
    Every token is routed over all the experts."""
    first = cfg.get("first_expert_held", 0) if first is None else first
    w, idx = route(jax.nn.sigmoid(mm(u, p["router"])), bias,
                   cfg["num_experts_per_token"],
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


def block(x, p, bias, cfg: dict, op, ffn, mm):
    """One decoder block on one row: x ``[S, d]``. The operator and the
    FFN each under a ``jax.checkpoint`` of its own, so that the backward
    holds one half's intermediates at a time."""
    eps = cfg["rms_norm_eps"]

    @jax.checkpoint
    def operator(x, p):
        return x + _OPS[op](_rmsnorm(x, p["norm"], eps), p, cfg, mm)

    @jax.checkpoint
    def feed_forward(x, p, bias):
        u = _rmsnorm(x, p["norm"], eps)
        if ffn == DENSE:
            return x + dense_ffn(u, p, mm)
        return x + sparse_ffn(u, p, bias, cfg, mm)

    return feed_forward(operator(x, p["op"]), p["ffn"], bias)


def head_nll(x, norm, head, targets, cfg: dict, mm):
    """The sum of the cross-entropies of ``targets`` ``[S]`` under
    ``RMSNorm(x) W_head``, ``HEAD_BLOCK`` positions at a time (a block's
    float32 logits live while it is summed and are made again in the
    backward: the same sum)."""
    S, n = x.shape[0], _block_rows(x.shape[0])

    @jax.checkpoint
    def some(args):
        x_b, t_b = args
        logp = jax.nn.log_softmax(
            mm(_rmsnorm(x_b, norm, cfg["rms_norm_eps"]), head), -1)
        return -jnp.sum(jnp.take_along_axis(logp, t_b[:, None], -1))

    return jnp.sum(jax.lax.map(some, (x.reshape(S // n, n, -1),
                                      targets.reshape(S // n, n))))


def nll_sum(params, batch, cfg: dict, operand=None):
    """(the sum of the cross-entropies of one block of rows, their
    count). ``operand`` rounds both operands of every matrix product,
    the router's and the gates' among them (the control's lower
    precision); the recurrence's own arithmetic stays float32.

    The rows go through a layer together, one after the other INSIDE
    the layer's ``jax.checkpoint``: the backward's loop over rows then
    carries one layer's gradients, not the whole tree's beside a row's
    (5.6 GiB of temporaries for two rows when the rows were the outer
    loop: TPU compiler, PR 43)."""
    _check(cfg)
    mm = _mm(operand)
    rows, S = batch["inputs"].shape
    biases = jax.lax.stop_gradient(expert_bias(cfg))
    x = params["embed"][batch["inputs"]]                 # [rows, S, d]
    sparse = 0
    for i, (op, ffn, n) in enumerate(layer_runs(cfg)):
        run = params[run_key(i)]
        for j in range(n):
            bias = None
            if ffn == SPARSE:
                bias, sparse = biases[sparse], sparse + 1
            x = jax.checkpoint(
                lambda x, p, b, op=op, ffn=ffn: jax.lax.map(
                    lambda row: block(row, p, b, cfg, op, ffn, mm), x))(
                x, jax.tree.map(lambda a: a[j], run), bias)
    head = jax.checkpoint(lambda x, norm, w, t: jnp.sum(jax.lax.map(
        lambda row: head_nll(row[0], norm, w, row[1], cfg, mm), (x, t))))
    return head(x, params["final_norm"], params["lm_head"],
                batch["targets"]), jnp.asarray(rows * S, jnp.int32)


# --------------------------------------------------------------------- #
# counts: parameters, the model's FLOPs, the delta rule's cost
# --------------------------------------------------------------------- #

def _group_size(cfg: dict, kind: str) -> int:
    """Parameters of one layer's group of ``kind`` with its block norm."""
    return cfg["hidden_size"] + sum(
        s if isinstance(s, int) else int(np.prod(s))
        for s in _leaf_shapes(cfg)[kind].values())


def param_count(cfg: dict) -> int:
    """Parameters held, from the leaves' shapes."""
    d = cfg["hidden_size"]
    return 2 * cfg["vocab_size"] * d + d + sum(
        n * (_group_size(cfg, op) + _group_size(cfg, ffn))
        for op, ffn, n in layer_runs(cfg))


def causal_pairs(seq_len: int) -> int:
    return seq_len * (seq_len + 1) // 2


def expected_pairs_per_token(cfg: dict) -> float:
    """Pairs routed to the experts held, per token and sparse layer, if
    the router spread its choices evenly over all experts."""
    return cfg["num_experts_per_token"] * cfg["num_experts_held"] \
        / cfg["num_experts"]


# multiply-adds and the like of the recurrence a position and head, in
# units of d_k d_v FLOPs: the state decayed (1), read by k (2), written
# (2), read by q (2)
KDA_FORWARD = 7


def kda_step_cost(rows: int, cfg: dict):
    """(FLOPs, bytes) a training step NEEDS of ONE layer's delta rule,
    whatever implements it: the recurrence's own arithmetic (a position
    and head ``KDA_FORWARD d_k d_v`` forward and twice that backward,
    nothing recomputed, no chunk algebra) and every operand and every
    gradient across HBM once: q, k, v, o and their four cotangents in
    bf16, the decay and its gradient in float32 (``d_k`` a position and
    head), the write strength and its gradient (one float32 each). Fixed
    by the configuration, not by a chunk."""
    lin = cfg["linear_attn_config"]
    H, hd = lin["num_heads"], lin["head_dim"]
    positions = rows * cfg["seq_len"] * H
    flops = 3.0 * KDA_FORWARD * hd * hd * positions
    return flops, positions * (8 * 2 * hd + 2 * 4 * hd + 2 * 4.0)


def model_flops_per_step(rows: int, cfg: dict) -> float:
    """FLOPs of one training step (forward + backward, nothing
    recomputed counted): 2 per multiply-add, backward twice the
    forward. Per token: a KDA layer's projections (q, k, v, the output's,
    the two low-rank gates, the write strength) or latent attention's
    four; a dense FFN's three products or, in a sparse layer, the
    router, the shared expert's three and the held experts' three for
    the pairs an even router sends here; the head over the vocabulary
    held. Latent attention's scores over ``qk_nope + qk_rope`` and mix
    over ``v_head_dim``, a head, over the causal pairs; the delta rule
    at ``kda_step_cost``'s arithmetic."""
    d, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    lin = cfg["linear_attn_config"]
    wide, rank = lin["num_heads"] * lin["head_dim"], cfg["kda_gate_rank"]
    kvr = cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    f, S = cfg["moe_intermediate_size"], cfg["seq_len"]
    per_token = {
        KDA: 4 * d * wide + 2 * (d * rank + rank * wide)
        + d * lin["num_heads"],
        MLA: d * nh * (dn + dr) + d * (kvr + dr) + kvr * nh * (dn + dv)
        + nh * dv * d,
        DENSE: 3 * d * cfg["intermediate_size"],
        SPARSE: d * cfg["num_experts"] + 3 * d * f
        + expected_pairs_per_token(cfg) * 3 * d * f,
    }
    runs = layer_runs(cfg)
    macs = rows * S * (sum(n * (per_token[op] + per_token[ffn])
                           for op, ffn, n in runs)
                       + d * cfg["vocab_size"])
    mla = sum(n for op, _, n in runs if op == MLA)
    macs += mla * nh * (dn + dr + dv) * causal_pairs(S) * rows
    return 3.0 * 2.0 * macs + kda_layers(cfg) * kda_step_cost(rows, cfg)[0]
