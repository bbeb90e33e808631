"""The gated delta rule with a decay a CHANNEL (Kimi Delta Attention's
recurrence), chunkwise, forward and backward.

A head holds a state ``S`` ``[d_k, d_v]`` (float32, zero at a row's
start). A position decays every row of it by its own factor, corrects
what the state returns for the position's key towards its value, and
reads it with the query::

    S' = Diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

``g <= 0`` is the log of the decay (``[d_k]`` a position and head),
``beta`` in [0, 1] the write strength. ``delta_rule(q, k, v, g, beta)``
takes ``q, k [B, T, H, d_k]``, ``v [B, T, H, d_v]``, ``g [B, T, H,
d_k]`` float32 and ``beta [B, T, H]`` float32 and returns ``o [B, T, H,
d_v]``; ``T`` need not be a multiple of the chunk (the tail is padded
with positions that write nothing and decay nothing).

**The chunk algebra** (``_chunk``, one chunk of ``C`` positions of one
head, the ONLY place the mathematics is written). With ``G_t`` the sum
of ``g`` over the chunk's positions up to ``t``, ``u_t = beta_t (v_t -
S'^T k_t)`` the corrected write and ``S_0`` the chunk's entry state::

    A[t, j]   = beta_t sum_c k_tc k_jc exp(G_tc - G_jc)      (j < t)
    (I + A) U = beta (V - (K exp G) S_0)
    O         = (Q exp G) S_0 + (Aq + diag(q . k)) U
    Aq[t, j]  = sum_c q_tc k_jc exp(G_tc - G_jc)             (j < t)
    S_C       = Diag(exp G_C) S_0 + (K exp(G_C - G))^T U

The decay is a channel's, so ``A`` is not a product of a decayed ``K``
with an un-decayed one unless ``exp(-G_j)`` is formed, which overflows
under a strong decay. Here every pair ``(t, j)`` is scaled relative to a
PIVOT between the two: the chunk is halved again and again (``C/2``,
``C/4``, ..., 1), a pair belongs to the level at which ``t`` falls in the
upper and ``j`` in the lower half of one segment, and both sides are
decayed to that segment's middle, ``exp(G_t - G_m)`` and ``exp(G_m -
G_j)``: every exponent is a sum of ``g`` and so never positive, whatever
the decay (tested down to ``g = -20`` a step). A level is one product of
two ``[C, d_k]`` operands under a mask; the sums of ``g`` of all levels
are one product with a constant 0/1 matrix, computed exactly (the
float32 operand split into three bfloat16 terms: the decay is float32
by the model). ``(I + A)^-1`` is built by the same halving: two inverted
diagonal blocks join as ``T - T A_h T``, which is exact and has no
cancellation. Products take the operands' type (bfloat16 in a model,
float32 at ``highest`` where the operands are float32) and accumulate in
float32; the state, the decays and the gates stay float32.

**Two programs of one algebra.** Off the TPU ``delta_rule`` is a
``lax.scan`` over the chunks of ``_chunk`` mapped over rows and heads,
and its backward the reverse scan of ``jax.vjp(_chunk)`` from the kept
chunk-entry states. On the TPU both are Pallas kernels under ONE scope,
``bps.attn.kda``: grid (rows, heads, chunks), the state (forward) or its
cotangent (backward) in VMEM while a row's chunks are walked in order;
the backward kernel's body is ``jax.vjp(_chunk)`` too, traced into the
kernel, so the chunk's forward is computed again inside it and no
derivative is written by hand. The forward keeps every chunk's entry
state for the backward (``[B, H, T / C, d_v, d_k]`` float32) when it is
differentiated and none when it is not.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

SCOPE = "bps.attn.kda"
# positions a chunk (the released kernels' choice); a power of two
CHUNK = 64
# heads a grid step of the kernels: independent chains of small products
# for the scheduler to interleave
HEADS_PER_STEP = 2
_HIGHEST = jax.lax.Precision.HIGHEST
_F32 = jnp.float32


def chunk_steps(rows: int, seq_len: int, heads: int,
                chunk: int = CHUNK) -> int:
    """Sequential chunk steps of one layer and pass: what the family's
    time scales with."""
    return rows * heads * (-(-seq_len // chunk))


def state_bytes(heads: int, d_k: int, d_v: int) -> int:
    """One row's recurrent state of one layer, float32."""
    return 4 * heads * d_k * d_v


def publish_sizes(chunk: int, heads: int, d_k: int, d_v: int) -> None:
    """``kda/chunk`` and ``kda/state_bytes`` as gauges in the process's
    metrics registry, set where a model is traced (as
    ``flash_attention.publish_walk_sizes``)."""
    from ..core.state import get_state

    registry = get_state().metrics
    for name, value in (("kda/chunk", chunk),
                        ("kda/state_bytes", state_bytes(heads, d_k, d_v))):
        registry.gauge(name).set(value)


# --------------------------------------------------------------------- #
# the chunk
# --------------------------------------------------------------------- #

def _dot(a, b, dims=((1,), (0,))):
    """``a`` and ``b`` contracted over ``dims``, float32 out; float32
    operands at ``highest``. Off the TPU the operands are widened first
    (XLA:CPU has no bfloat16 product that accumulates in float32): the
    same products of the same rounded operands."""
    if jax.default_backend() != "tpu":
        a, b = a.astype(_F32), b.astype(_F32)
    return jax.lax.dot_general(
        a, b, (dims, ((), ())), preferred_element_type=_F32,
        precision=_HIGHEST if a.dtype == _F32 else None)


_NT = ((1,), (1,))     # a b^T
_TN = ((0,), (0,))     # a^T b


def _split3(x):
    """A float32 array as three bfloat16 terms whose sum is the array
    (8 + 8 + 8 bits of mantissa)."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(_F32)
    mid = rest.astype(jnp.bfloat16)
    return hi, mid, (rest - mid.astype(_F32)).astype(jnp.bfloat16)


@jax.custom_vjp
def _span_sums(spans, g):
    """``spans @ g`` for a constant 0/1 matrix ``spans`` (bfloat16),
    exactly: three single-pass products that accumulate in float32."""
    return sum(_dot(spans, term) for term in _split3(g))


def _span_sums_fwd(spans, g):
    return _span_sums(spans, g), spans


def _span_sums_bwd(spans, ct):
    return None, sum(_dot(spans, term, _TN) for term in _split3(ct))


_span_sums.defvjp(_span_sums_fwd, _span_sums_bwd)


def _halvings(C: int):
    """The chunk's constant masks, from iotas (a kernel cannot close
    over arrays): ``(row >= col, row == col, levels)``; a level of half
    ``h`` (1, 2, ..., C / 2) is ``(pair, span)``, both ``[C, C]`` bool:
    ``pair[t, j]``: ``t`` in the upper and ``j`` in the lower half of
    one segment of ``2 h``; ``span[t, i]``: ``i`` lies between ``t`` and
    the middle of ``t``'s segment, on ``t``'s side (``middle < i <= t``
    for an upper ``t``, ``t < i <= middle`` for a lower one)."""
    if C < 2 or C & (C - 1):
        raise ValueError(f"a chunk of {C}: a power of two, 2 or more")
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    levels = []
    for bit in range(C.bit_length() - 1):
        h = 1 << bit
        same = (row >> (bit + 1)) == (col >> (bit + 1))
        up_r, up_c = (row & h) != 0, (col & h) != 0
        levels.append((
            same & up_r & ~up_c,
            same & ((up_r & up_c & (col <= row))
                    | (~up_r & ~up_c & (col > row)))))
    return row >= col, row == col, levels


def _chunk(q, k, v, g, beta, st):
    """One chunk of one head. q, k ``[C, d_k]``, v ``[C, d_v]`` (the
    model's compute type), g ``[C, d_k]`` float32, beta ``[1, C]``
    float32, st ``[d_v, d_k]`` float32 (the entry state, TRANSPOSED, so
    that a channel's decay scales a column). Returns (o ``[C, d_v]``
    float32, the exit state)."""
    C, mm = q.shape[0], q.dtype
    d_v = v.shape[1]
    lower, eye, levels = _halvings(C)
    qf, kf, vf = q.astype(_F32), k.astype(_F32), v.astype(_F32)
    # every sum of g the chunk needs, one exact product: the prefix sums
    # G first, then each level's sums towards its pivots
    spans = jnp.concatenate(
        [lower] + [span for _, span in levels]).astype(jnp.bfloat16)
    sums = _span_sums(spans, g)
    G, G_last = sums[:C], sums[C - 1:C]
    beta_col = jnp.sum(jnp.where(eye, beta, 0.0), axis=1, keepdims=True)

    # A and Aq, a level at a time: both sides decayed to the pivot
    akk = aqk = jnp.zeros((C, C), _F32)
    for n, (pair, _) in enumerate(levels):
        e = jnp.exp(sums[(n + 1) * C:(n + 2) * C])
        kh = (kf * e).astype(mm)
        both = _dot(jnp.concatenate([kh, (qf * e).astype(mm)]), kh, _NT)
        akk = akk + jnp.where(pair, both[:C], 0.0)
        aqk = aqk + jnp.where(pair, both[C:], 0.0)
    a = beta_col * akk

    # (I + A)^-1: blocks of one are inverted; two inverted blocks of h
    # join under their off-diagonal block A_h as T - T A_h T
    t = jnp.where(eye, 1.0, 0.0) - jnp.where(levels[0][0], a, 0.0)
    for pair, _ in levels[1:]:
        tm = t.astype(mm)
        t = t - _dot(_dot(tm, jnp.where(pair, a, 0.0).astype(mm)).astype(mm),
                     tm)

    e = jnp.exp(G)
    kg, qg = kf * e, qf * e
    w = _dot(t.astype(mm),
             (beta_col * jnp.concatenate([vf, kg], axis=1)).astype(mm))
    s = st.astype(mm)
    u = w[:, :d_v] - _dot(w[:, d_v:].astype(mm), s, _NT)
    o = _dot(qg.astype(mm), s, _NT) + _dot(aqk.astype(mm), u.astype(mm)) \
        + jnp.sum(qf * kf, axis=1, keepdims=True) * u
    kd = kf * jnp.exp(G_last - G)
    return o, st * jnp.exp(G_last) + _dot(u.astype(mm), kd.astype(mm), _TN)


# --------------------------------------------------------------------- #
# off the TPU: a scan over the chunks
# --------------------------------------------------------------------- #

def _chunked(a, C: int):
    """``[B, T, H, d]`` -> ``[T / C, B, H, C, d]``."""
    B, T, H, d = a.shape
    return a.reshape(B, T // C, C, H, d).transpose(1, 0, 3, 2, 4)


def _unchunked(a):
    n, B, H, C, d = a.shape
    return a.transpose(1, 0, 3, 2, 4).reshape(B, n * C, H, d)


def _beta_rows(beta, C: int):
    """``[B, T, H]`` -> ``[T / C, B, H, 1, C]``."""
    B, T, H = beta.shape
    return beta.reshape(B, T // C, C, H).transpose(1, 0, 3, 2)[:, :, :, None]


_heads_chunk = jax.vmap(jax.vmap(_chunk))


def _scan_fwd(q, k, v, g, beta, C: int):
    """(o ``[B, T, H, d_v]`` float32, the chunks' entry states ``[T / C,
    B, H, d_v, d_k]``)."""
    B, _, H, d_k = q.shape

    def body(st, xs):
        o, nxt = _heads_chunk(*xs, st)
        return nxt, (o, st)

    xs = (*(_chunked(a, C) for a in (q, k, v, g)), _beta_rows(beta, C))
    _, (o, states) = jax.lax.scan(
        body, jnp.zeros((B, H, v.shape[-1], d_k), _F32), xs)
    return _unchunked(o), states


def _scan_bwd(q, k, v, g, beta, states, do, C: int):
    B, T, H, d_k = q.shape

    def body(dst, xs):
        *operands, st, do_c = xs
        _, vjp = jax.vjp(_heads_chunk, *operands, st)
        *grads, dst = vjp((do_c, dst))
        return dst, grads

    xs = (*(_chunked(a, C) for a in (q, k, v, g)), _beta_rows(beta, C),
          states, _chunked(do.astype(_F32), C))
    _, (dq, dk, dv, dg, db) = jax.lax.scan(
        body, jnp.zeros((B, H, v.shape[-1], d_k), _F32), xs, reverse=True)
    return (*(_unchunked(a) for a in (dq, dk, dv, dg)),
            db[:, :, :, 0].transpose(1, 0, 3, 2).reshape(B, T, H))


# --------------------------------------------------------------------- #
# on the TPU: the same chunk inside two kernels
# --------------------------------------------------------------------- #

def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, *rest, heads: int,
                d_k: int, d_v: int, keep: bool):
    import jax.experimental.pallas as pl

    st_ref = rest[-1]
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _():
        st_ref[...] = jnp.zeros_like(st_ref)

    for h in range(heads):
        ck, cv = slice(h * d_k, (h + 1) * d_k), slice(h * d_v, (h + 1) * d_v)
        st = st_ref[h]
        if keep:
            rest[0][0, h, 0] = st
        o, st = _chunk(q_ref[0, :, ck], k_ref[0, :, ck], v_ref[0, :, cv],
                       g_ref[0, :, ck], b_ref[0, h, pl.ds(c, 1), :], st)
        o_ref[0, :, cv] = o.astype(o_ref.dtype)
        st_ref[h] = st


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s_ref, do_ref, dq_ref,
                dk_ref, dv_ref, dg_ref, db_ref, dst_ref, *, heads: int,
                d_k: int, d_v: int, chunks: int):
    import jax.experimental.pallas as pl

    step = pl.program_id(2)
    c = chunks - 1 - step

    @pl.when(step == 0)
    def _():
        dst_ref[...] = jnp.zeros_like(dst_ref)

    for h in range(heads):
        ck, cv = slice(h * d_k, (h + 1) * d_k), slice(h * d_v, (h + 1) * d_v)
        _, vjp = jax.vjp(
            _chunk, q_ref[0, :, ck], k_ref[0, :, ck], v_ref[0, :, cv],
            g_ref[0, :, ck], b_ref[0, h, pl.ds(c, 1), :], s_ref[0, h, 0])
        dq, dk, dv, dg, db, dst = vjp(
            (do_ref[0, :, cv].astype(_F32), dst_ref[h]))
        dq_ref[0, :, ck] = dq
        dk_ref[0, :, ck] = dk
        dv_ref[0, :, cv] = dv
        dg_ref[0, :, ck] = dg
        db_ref[0, h, pl.ds(c, 1), :] = db
        dst_ref[h] = dst


def _heads_per_step(H: int) -> int:
    return HEADS_PER_STEP if H % HEADS_PER_STEP == 0 else 1


def _flat(a):
    """``[B, T, H, d]`` -> ``[B, T, H d]``: a head's columns are a
    lane-aligned slice."""
    return a.reshape(*a.shape[:2], -1)


def _beta_blocks(beta, C: int):
    """``[B, T, H]`` -> ``[B, H, T / C, C]``: a head's write strengths,
    a chunk a row."""
    B, T, H = beta.shape
    return beta.transpose(0, 2, 1).reshape(B, H, T // C, C)


def _specs(pl, C: int, hb: int, d_k: int, d_v: int, chunks: int, at):
    """Block specs of a grid (B, H / hb, chunks) whose step ``s`` works
    on chunk ``at(s)``: a chunk's ``hb`` heads of a ``d_k``-wide or
    ``d_v``-wide operand, the heads' write strengths (all chunks: it
    stays while the row is walked) and the chunk's entry states."""
    def tile(width):
        return pl.BlockSpec((1, C, hb * width),
                            lambda b, h, s: (b, at(s), h))

    betas = pl.BlockSpec((1, hb, chunks, C), lambda b, h, s: (b, h, 0, 0))
    states = pl.BlockSpec((1, hb, 1, d_v, d_k),
                          lambda b, h, s: (b, h, at(s), 0, 0))
    return tile(d_k), tile(d_v), betas, states


def _kernel_fwd(q, k, v, g, beta, C: int, keep: bool,
                interpret: bool = False):
    """(o ``[B, T, H, d_v]`` of v's type, the chunks' entry states ``[B,
    H, T / C, d_v, d_k]`` float32 or None)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, H, d_k = q.shape
    d_v, chunks, hb = v.shape[-1], T // C, _heads_per_step(H)
    wide_k, wide_v, betas, states = _specs(pl, C, hb, d_k, d_v, chunks,
                                           lambda s: s)
    out_shape = [jax.ShapeDtypeStruct((B, T, H * d_v), v.dtype)]
    out_specs = [wide_v]
    if keep:
        out_shape.append(
            jax.ShapeDtypeStruct((B, H, chunks, d_v, d_k), _F32))
        out_specs.append(states)
    with jax.named_scope(SCOPE):
        out = pl.pallas_call(
            functools.partial(_fwd_kernel, heads=hb, d_k=d_k, d_v=d_v,
                              keep=keep),
            out_shape=out_shape, grid=(B, H // hb, chunks),
            in_specs=[wide_k, wide_k, wide_v, wide_k, betas],
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((hb, d_v, d_k), _F32)],
            compiler_params=pltpu.CompilerParams(dimension_semantics=(
                "parallel", "parallel", "arbitrary")),
            interpret=interpret)(
                _flat(q), _flat(k), _flat(v), _flat(g),
                _beta_blocks(beta, C))
    return out[0].reshape(B, T, H, d_v), out[1] if keep else None


def _kernel_bwd(q, k, v, g, beta, states, do, C: int,
                interpret: bool = False):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, H, d_k = q.shape
    d_v, chunks, hb = v.shape[-1], T // C, _heads_per_step(H)
    wide_k, wide_v, betas, at_states = _specs(
        pl, C, hb, d_k, d_v, chunks, lambda s: chunks - 1 - s)
    with jax.named_scope(SCOPE):
        dq, dk, dv, dg, db = pl.pallas_call(
            functools.partial(_bwd_kernel, heads=hb, d_k=d_k, d_v=d_v,
                              chunks=chunks),
            out_shape=[jax.ShapeDtypeStruct((B, T, H * d_k), q.dtype),
                       jax.ShapeDtypeStruct((B, T, H * d_k), k.dtype),
                       jax.ShapeDtypeStruct((B, T, H * d_v), v.dtype),
                       jax.ShapeDtypeStruct((B, T, H * d_k), _F32),
                       jax.ShapeDtypeStruct((B, H, chunks, C), _F32)],
            grid=(B, H // hb, chunks),
            in_specs=[wide_k, wide_k, wide_v, wide_k, betas, at_states,
                      wide_v],
            out_specs=[wide_k, wide_k, wide_v, wide_k, betas],
            scratch_shapes=[pltpu.VMEM((hb, d_v, d_k), _F32)],
            compiler_params=pltpu.CompilerParams(dimension_semantics=(
                "parallel", "parallel", "arbitrary")),
            interpret=interpret)(
                _flat(q), _flat(k), _flat(v), _flat(g),
                _beta_blocks(beta, C), states, _flat(do))
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            dg.reshape(g.shape), db.reshape(B, H, T).transpose(0, 2, 1))


# --------------------------------------------------------------------- #
# the operator
# --------------------------------------------------------------------- #

def _on_kernels(q, v, C: int) -> bool:
    """The kernels take lane-wide heads and sublane-whole chunks; every
    other shape, and every backend but the TPU, takes the scan."""
    return jax.default_backend() == "tpu" and q.shape[-1] % 128 == 0 \
        and v.shape[-1] % 128 == 0 and C % 16 == 0


def _padded(q, k, v, g, beta, C: int) -> Tuple:
    """The operands with ``T`` brought up to whole chunks: the tail
    writes nothing (``beta`` 0) and decays nothing (``g`` 0)."""
    pad = -q.shape[1] % C
    if not pad:
        return q, k, v, g, beta
    return tuple(jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                 for a in (q, k, v, g, beta))


def _forward(q, k, v, g, beta, C: int, keep: bool):
    T = q.shape[1]
    operands = _padded(q, k, v, g.astype(_F32), beta.astype(_F32), C)
    if _on_kernels(q, v, C):
        o, states = _kernel_fwd(*operands, C, keep)
    else:
        o, states = _scan_fwd(*operands, C)
    return o[:, :T].astype(v.dtype), states


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def delta_rule(q, k, v, g, beta, chunk: int = CHUNK):
    """The gated delta rule of the module's docstring, ``chunk``
    positions at a time."""
    return _forward(q, k, v, g, beta, chunk, False)[0]


def _delta_rule_fwd(q, k, v, g, beta, chunk):
    o, states = _forward(q, k, v, g, beta, chunk, True)
    return o, (q, k, v, g, beta, states)


def _delta_rule_bwd(chunk, res, do):
    q, k, v, g, beta, states = res
    T = q.shape[1]
    operands = _padded(q, k, v, g.astype(_F32), beta.astype(_F32), chunk)
    do = jnp.pad(do, ((0, 0), (0, -T % chunk), (0, 0), (0, 0)))
    bwd = _kernel_bwd if _on_kernels(q, v, chunk) else _scan_bwd
    grads = bwd(*operands, states, do, chunk)
    return tuple(d[:, :T].astype(a.dtype)
                 for d, a in zip(grads, (q, k, v, g, beta)))


delta_rule.defvjp(_delta_rule_fwd, _delta_rule_bwd)


def recurrence(q, k, v, g, beta):
    """The same function a position at a time in float32 (``lax.scan``
    over ``T``): what the chunk algebra is tested against. Not a path of
    any program."""
    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x                 # [B, H, d], b [B, H]
        S = S * jnp.exp(g_t)[..., None]
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t,
                                               precision=_HIGHEST))
        S = S + k_t[..., None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t, precision=_HIGHEST)

    B, _, H, d_k = q.shape
    xs = tuple(jnp.moveaxis(a.astype(_F32), 1, 0) for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((B, H, d_k, v.shape[-1]), _F32), xs)
    return jnp.moveaxis(o, 0, 1)
