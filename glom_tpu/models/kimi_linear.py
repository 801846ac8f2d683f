"""Kimi Linear (`model_type: kimi_linear`; moonshotai's
Kimi-Linear-48B-A3B-Instruct is the defaults): pre-norm residual layers `h +=
Mixer(RMSNorm(h)); h += MLP(RMSNorm(h))`, a final RMSNorm, an untied head,
next-token cross-entropy. By the published layer number
(`utils/config.KimiLinearConfig`):

  mixer
     K  Kimi Delta Attention over H heads of size D, u the normed input,
        conv a causal depthwise convolution over positions without bias:
          q = l2norm(silu(conv(u Wq)))  k = l2norm(silu(conv(u Wk)))
          v = silu(conv(u Wv))
          g = -exp(A_log[h]) * softplus((u Wf1) Wf2 + dt_bias)   a log-decay
              a key channel, <= 0;  beta = sigmoid(u Wb) a head
          S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
          o_t = S_t^T (q_t D^-1/2),  S a D x D matrix a head, S_0 = 0
          y = (RMSNorm_head(o_t) * sigmoid((u Wg1) Wg2)) Wo
        The recurrence runs in chunks (`kda_chunked`).
     A  latent attention without positions: q = u Wq a head of `qk_nope +
        qk_rope` dimensions; [c, kr] = u Wkva, a latent and a key part the
        heads share; [kn, v] = RMSNorm(c) Wkvb a head; k = [kn, kr]; causal
        softmax attention at (qk_nope + qk_rope)^-1/2, one query head a KV
        head; the out-projection. In training the latent is expanded and the
        heads attend one by one (`hybrid_lm.blocked_attention`).
  MLP
     D  dense SwiGLU.
     E  a router over all the experts (float32 sigmoid scores, the k largest,
        `w_k = s_k / sum s_k * routed_scaling_factor`), the terms of the
        SwiGLU experts held here, and the shared SwiGLU expert.

The stack (embedding, per-layer recomputation, blocked loss), the blocked
attention core, the causal convolution and the whole routed part are
`hybrid_lm`'s. XLA but for the latent attention's scores, which run in
`kernels/flash_attention.py` where the shapes tile and the device is a TPU.
Parameters are float32; with a compute dtype the residual stream and the
matrix products run in it; the norms' statistics, the router, the softmaxes,
the loss and everything inside `kda_chunked` (decays, the in-chunk solve, the
state) in float32. Every device op sits under one of
`tracing.spans.KIMI_DEVICE_PHASES`.
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from glom_tpu.models import hybrid_lm
from glom_tpu.models.hybrid_lm import (
    _cast,
    _mm,
    blocked_attention,
    causal_conv,
    count_shapes,
    init_tree,
    next_token_loss,
    rms_norm,
    run_stack,
)
from glom_tpu.models.laguna import swiglu, swiglu_backward_staged
from glom_tpu.utils.config import KimiLinearConfig

COUNTERS = hybrid_lm.STACK_COUNTERS + ("attn_key_blocks_full", "kda_chunks",
                                       "kda_log_decay_min", "kda_forward_kept",
                                       "swiglu_backward_staged")
# The sub-chunk and the segment are the fastest of those tried on a v5e at the benchmark's
# size (16,384 positions, 32 heads of 128: 122-133 ms a layer forward, recomputed and
# backward at segments of 1-4 chunks and sub-chunks of 8; 174 at sub-chunks of 4, 148 at 16;
# 269 at segments of 32: PERF.md section 6, PR 40): small segments keep a segment's arrays
# near the core, and the pairwise decays cost their sub-chunk's length a position.
KDA_CHUNK = 64      # positions of one chunk of the delta rule (the family's)
KDA_SUBCHUNK = 8    # positions whose pairwise decays are formed as differences
KDA_SEGMENT = 4     # chunks that go through `kda_chunked` between two kept states
L2_EPS = 1e-6
# `kda_chunked` computes in float32, its products at the chip's full precision.
# The two types a lower precision would hurt most have a name each, so that the
# benchmark's control can show that its limits notice (control_kimi.py): the
# state carried from chunk to chunk, and the in-chunk solve.
SCAN_DTYPE = jnp.float32
SCAN_PRECISION = jax.lax.Precision.HIGHEST
SCAN_STATE_DTYPE = jnp.float32
SCAN_SOLVE_DTYPE = jnp.float32
# What recomputes each half of `kda_mixer` on its own. The cell's step needs it for a right
# gradient and not for its memory alone (PERF.md section 7, trap 18): it has a name so that
# benchmark/repro_kimi_halves.py can take it away and show the fault again.
recomputed_half = jax.checkpoint
# the seeded draws of the recurrence's parameters (the family's)
TIME_STEP_MIN, TIME_STEP_MAX = 1e-3, 1e-1


# ----------------------------------------------------------------- parameters


def layer_shapes(mixer: str, mlp_kind: str, cfg: KimiLinearConfig) -> dict:
    """{leaf: shape} of one layer of mixer `mixer` and MLP kind `mlp_kind`."""
    d = cfg.hidden_size
    if mixer == "K":
        h, dk = cfg.linear_num_heads, cfg.linear_head_dim
        w, kern = h * dk, cfg.short_conv_kernel_size
        shapes = {"norm1": (d,), "q": (d, w), "k": (d, w), "v": (d, w),
                  "conv_q": (w, kern), "conv_k": (w, kern), "conv_v": (w, kern),
                  "f1": (d, dk), "f2": (dk, w), "dt_bias": (w,), "A_log": (h,),
                  "beta": (d, h), "g1": (d, dk), "g2": (dk, w), "onorm": (dk,), "o": (w, d)}
    else:
        h, lat = cfg.num_attention_heads, cfg.kv_lora_rank
        qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        shapes = {"norm1": (d,), "q": (d, h * qk), "kva": (d, lat + cfg.qk_rope_head_dim),
                  "kv_norm": (lat,), "kvb": (lat, h * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                  "o": (h * cfg.v_head_dim, d)}
    shapes["norm2"] = (d,)
    if mlp_kind == "D":
        f = cfg.intermediate_size
        return {**shapes, "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}
    e, f = cfg.num_experts, cfg.moe_intermediate_size
    fs = f * cfg.num_shared_experts
    return {**shapes, "router": (d, cfg.num_experts_total),
            "e_gate": (e, d, f), "e_up": (e, d, f), "e_down": (e, f, d),
            "s_gate": (d, fs), "s_up": (d, fs), "s_down": (fs, d)}


def param_shapes(cfg: KimiLinearConfig) -> dict:
    d, v = cfg.hidden_size, cfg.vocab_size
    return {"embed": (v, d),
            "layers": tuple(layer_shapes(m, f, cfg) for m, f in cfg.kinds),
            "final_norm": (d,), "head": (d, v)}


def init_leaf(key, name: str, shape, cfg: KimiLinearConfig):
    """One leaf's initial value, float32: norms one; matrices normal with std
    0.02, the out-projections (`o`, every `*_down`) scaled by 1/sqrt(2 x
    layers of the published stack); the convolutions uniform in +-1/sqrt(kernel);
    `A_log` the log of uniform(1, 16) and `dt_bias` the inverse softplus of a
    log-uniform time step, as the other recurrences here draw them."""
    if "norm" in name:
        return jnp.ones(shape, jnp.float32)
    if name.startswith("conv_"):
        bound = cfg.short_conv_kernel_size ** -0.5
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    if name == "dt_bias":
        lo, hi = math.log(TIME_STEP_MIN), math.log(TIME_STEP_MAX)
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32) * (hi - lo) + lo)
        return dt + jnp.log(-jnp.expm1(-dt))  # softplus's inverse
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    std = 0.02
    if name == "o" or name.endswith("_down"):
        std /= math.sqrt(2.0 * cfg.num_hidden_layers_total)
    return std * jax.random.normal(key, shape, jnp.float32)


@functools.partial(jax.jit, static_argnums=(1,))
def init_kimi_linear(key: jax.Array, cfg: KimiLinearConfig):
    return init_tree(key, param_shapes(cfg), init_leaf, cfg)


# ------------------------------------------------------- the delta rule in chunks


def _dot(eq, a, b):
    return jnp.einsum(eq, a, b, precision=SCAN_PRECISION, preferred_element_type=SCAN_DTYPE)


def _decayed(x, log_decay):
    """x * exp(log_decay): every caller's exponent is a difference that is
    never positive."""
    return x * jnp.exp(log_decay)


@jax.checkpoint
def _diagonal_block(rows, keys, cum):
    """One sub-chunk's pairs: rows [R, S, D] (R kinds of row: the queries and
    the keys), keys [S, D] and the cumulative log-decays cum [S, D] ->
    sum_c rows[r, i, c] keys[j, c] exp(cum[i, c] - cum[j, c]) for j <= i, 0
    above the diagonal: the decay formed as a difference, which is never
    positive, and not as a product of two exponentials, one of which
    overflows. Recomputed in the backward pass, so that the [S, S, D] decays
    are never kept."""
    s = keys.shape[0]
    lower = jnp.tril(jnp.ones((s, s), bool))[:, :, None]
    decay = jnp.exp(jnp.where(lower, cum[:, None, :] - cum[None, :, :], -jnp.inf))
    return jnp.sum(rows[:, :, None, :] * (keys[None, :, :] * decay)[None], axis=-1)


def decayed_products(q, k, cum):
    """The in-chunk products of the delta rule: q, k and the inclusive
    cumulative log-decays cum, each [N, C, D] (N chunks of C positions) ->
    [N, 2, C, C] float32, for j <= i
        out[n, 0, i, j] = sum_c q[n, i, c] k[n, j, c] exp(cum[n, i, c] - cum[n, j, c])
        out[n, 1, i, j] = the same with k[n, i] in q's place
    and 0 above the diagonal. No exponent is ever positive: inside a
    sub-chunk of KDA_SUBCHUNK positions the decays are formed pair by pair as
    differences (`_diagonal_block`); between a sub-chunk and those before it
    they are factored about the later sub-chunk's first position r, exp(cum_i
    - cum_r) * exp(cum_r - cum_j) with j < r <= i, both factors at most 1, so
    that the pairs are a matrix product."""
    n, c, d = q.shape
    s = KDA_SUBCHUNK if c % KDA_SUBCHUNK == 0 else c
    subs = c // s
    rows = jnp.stack([q, k], axis=1)                                  # [N, 2, C, D]
    blocked = lambda x: x.reshape(*x.shape[:-2], subs, s, d)
    diag = jax.vmap(_diagonal_block)(
        jnp.moveaxis(blocked(rows), 2, 1).reshape(n * subs, 2, s, d),
        blocked(k).reshape(n * subs, s, d), blocked(cum).reshape(n * subs, s, d),
    ).reshape(n, subs, 2, s, s)
    out = []
    for i in range(subs):
        first = i * s
        parts = [diag[:, i]]                                          # [N, 2, S, S]
        if i:
            ref = cum[:, first:first + 1]                             # [N, 1, D]
            late = _decayed(rows[:, :, first:first + s], (cum[:, first:first + s] - ref)[:, None])
            early = _decayed(k[:, :first], ref - cum[:, :first])
            parts.insert(0, _dot("nrid,njd->nrij", late, early))
        if first + s < c:
            parts.append(jnp.zeros((n, 2, s, c - first - s), SCAN_DTYPE))
        out.append(jnp.concatenate(parts, axis=-1))
    return jnp.concatenate(out, axis=-2)


def unit_lower_inverse(strict):
    """(I + N)^-1 for N [..., C, C] strictly lower triangular, by halves:
    [[A, 0], [B, D]]^-1 = [[A^-1, 0], [-D^-1 B A^-1, D^-1]], the two halves'
    inverses found the same way (together, as one batch, where they are of one
    size) down to single rows. That is forward substitution in blocks, as
    stable as it is row by row, in log2(C) rounds of two products. (The
    product form (I - N)(I + N^2)(I + N^4)... is no fewer products and
    cancels where keys repeat.) In SCAN_SOLVE_DTYPE; float32 out."""
    dot = functools.partial(jnp.einsum, "...ij,...jk->...ik", precision=SCAN_PRECISION,
                            preferred_element_type=SCAN_SOLVE_DTYPE)

    def inverse(n):
        c = n.shape[-1]
        if c == 1:
            return jnp.ones_like(n)
        half = c // 2
        first, second, below = n[..., :half, :half], n[..., half:, half:], n[..., half:, :half]
        if 2 * half == c:
            first, second = inverse(jnp.stack([first, second]))
        else:
            first, second = inverse(first), inverse(second)
        top = jnp.concatenate([first, jnp.zeros(first.shape[:-1] + (c - half,), n.dtype)], axis=-1)
        bottom = jnp.concatenate([-dot(second, dot(below, first)), second], axis=-1)
        return jnp.concatenate([top, bottom], axis=-2)

    return inverse(strict.astype(SCAN_SOLVE_DTYPE)).astype(SCAN_DTYPE)


def _kda_segment(state, q, k, v, g, beta):
    """A run of whole chunks, all heads at once: the state entering it [N, D,
    D] and q, k, v, g [N, Z, C, D], beta [N, Z, C] (N rows of heads, Z chunks
    of C positions) -> (the state leaving it, o [N, Z, C, D] in q's type, the most
    negative cumulative log-decay inside a chunk). `kda_chunked` has the
    algebra."""
    n, z, chunk, d = q.shape
    dtype = q.dtype
    q, k, v, g, beta = (x.reshape(n * z, *x.shape[2:]).astype(SCAN_DTYPE)
                        for x in (q, k, v, g, beta))
    cum = jnp.cumsum(g, axis=1)                                        # G, <= 0
    qk = decayed_products(q, k, cum)
    strictly = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    a = jnp.where(strictly, qk[:, 1], 0.0) * beta[:, :, None]
    solve = unit_lower_inverse(a) * beta[:, None, :]                    # T
    w = _dot("nij,njd->nid", solve, _decayed(k, cum))
    u = _dot("nij,njd->nid", solve, v)
    last = cum[:, -1:]
    to_end = _decayed(k, last - cum)

    by_chunk = lambda x: jnp.moveaxis(x.reshape(n, z, *x.shape[1:]), 1, 0)

    def step(state, inp):
        w_z, u_z, to_end_z, keep = inp
        pseudo = u_z - _dot("nid,nde->nie", w_z, state.astype(SCAN_DTYPE))
        after = keep[:, :, None] * state + _dot("nid,nie->nde", to_end_z, pseudo)
        return after.astype(state.dtype), (state, pseudo)

    state, (entering, pseudo) = jax.lax.scan(
        step, state, (by_chunk(w), by_chunk(u), by_chunk(to_end), by_chunk(jnp.exp(last[:, 0]))))
    entering = jnp.moveaxis(entering, 0, 1).reshape(n * z, d, d).astype(SCAN_DTYPE)
    pseudo = jnp.moveaxis(pseudo, 0, 1).reshape(n * z, chunk, d)
    o = (_dot("nid,nde->nie", _decayed(q, cum), entering)
         + _dot("nij,nje->nie", qk[:, 0], pseudo))
    return state, o.reshape(n, z, chunk, d).astype(dtype), jnp.min(last)


def kda_chunked(q, k, v, g, beta):
    """The gated delta rule with a decay a key channel,
        S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T,
        o_t = S_t^T q_t,   S_0 = 0,
    by chunks of KDA_CHUNK positions. q, k, v [B, T, H, D] (q already
    scaled), g [B, T, H, D] float32 (<= 0), beta [B, T, H] float32 -> (o [B,
    T, H, D] in q's type, the most negative cumulative log-decay inside any
    chunk). With G the inclusive cumulative sum of g inside a chunk and S the
    state entering it,
        S_i = Diag(exp(G_i)) (S + sum_{j<=i} (k_j * exp(-G_j)) U_j^T)
        U   = T V - T (K * exp(G)) S,   T = (I + Diag(beta) A)^-1 Diag(beta),
        A_ij = sum_c k_i[c] k_j[c] exp(G_i[c] - G_j[c])  for j < i
    (the chunk's rank-one updates folded into the two matrices `w` = T (K *
    exp(G)) and `u` = T V: the WY form), so a chunk's outputs and the state it
    leaves are matrix products,
        o_i = (q_i * exp(G_i)) S + sum_{j<=i} B_ij U_j,   B as A with q_i for k_i
        S'  = Diag(exp(G_last)) S + sum_j (k_j * exp(G_last - G_j)) U_j^T.
    Every decay is exp of a difference that is never positive
    (`decayed_products`): exp(-G_j) alone overflows float32 after a few
    positions of a fast channel. One sequential pass over the chunks carries
    S [B x H, D, D], all heads in every step (a step is a few small products:
    its latency, not its size, is what a pass costs); everything else is
    batched over the chunks. The pass goes a segment of KDA_SEGMENT chunks at
    a time, each segment recomputed in the backward pass: what the backward
    of the chunked form wants kept (some thirty float32 arrays of the inputs'
    size: 7.7 GB at 16,384 tokens) is held for one segment at a time, beside
    the states at the segments' edges. A length that is no whole number of
    segments is padded with steps of k = 0, beta = 0, g = 0, which leave the
    state as it is. All in float32 at SCAN_PRECISION.

    The pass over the segments has a derivative of its own (`jax.custom_vjp`
    over the arrays in the segments' layout). Its forward keeps the state
    entering every segment [segments, B x H, D, D] in SCAN_STATE_DTYPE under
    the name `hybrid_lm.KDA_KEPT_STATES`, and o as it is returned carries
    `hybrid_lm.KDA_KEPT_OUTPUT`; its backward goes over the segments from last
    to first, `jax.vjp` of `_kda_segment` at the kept entering state and the
    segment's slices, fed the cotangent of its o and of the state it leaves
    (what `jax.checkpoint` of a segment under `lax.scan` would build). So a
    layer recomputed under a policy that saves the two names
    (`hybrid_lm.run_stack`) rebuilds q, k, v, g, beta and reads the rest: the
    forward pass over the segments is not run a second time. The second
    result has no gradient."""
    bsz, t, h, d = q.shape
    chunk = min(KDA_CHUNK, -(-t // KDA_SUBCHUNK) * KDA_SUBCHUNK)
    segments = -(-t // (chunk * KDA_SEGMENT))
    z = -(-t // (chunk * segments))                  # chunks a segment
    pad = segments * z * chunk - t

    def layout(x):   # [B, T, H, ...] -> [segments, B x H, Z, C, ...]: heads before positions
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = jnp.moveaxis(x.reshape(bsz, segments, z, chunk, *x.shape[2:]), 4, 1)
        return jnp.moveaxis(x.reshape(bsz * h, segments, z, chunk, *x.shape[5:]), 1, 0)

    def forward(*xs):
        def segment(state, xs):
            leaving, o, lowest = _kda_segment(state, *xs)
            return leaving, (state, o, lowest)

        _, (entering, o, lowest) = jax.lax.scan(
            segment, jnp.zeros((bsz * h, d, d), SCAN_STATE_DTYPE), xs)
        return (o, jnp.min(lowest)), (checkpoint_name(entering, hybrid_lm.KDA_KEPT_STATES), xs)

    def backward(kept, cotangents):
        entering, xs = kept

        def segment(d_leaving, inp):
            state, xs, d_o = inp
            _, pull = jax.vjp(lambda state, *xs: _kda_segment(state, *xs)[:2], state, *xs)
            d_state, *d_xs = pull((d_leaving, d_o))
            return d_state, tuple(d_xs)

        _, d_xs = jax.lax.scan(segment, jnp.zeros_like(entering[0]),
                               (entering, xs, cotangents[0]), reverse=True)
        return d_xs

    # functions of this call's own: no earlier trace at other constants is found
    over_segments = jax.custom_vjp(lambda *xs: forward(*xs)[0])
    over_segments.defvjp(forward, backward)
    o, lowest = over_segments(*(layout(x) for x in (q, k, v, g, beta)))
    o = jnp.moveaxis(o, 0, 1).reshape(bsz, h, segments * z * chunk, d)
    return checkpoint_name(jnp.moveaxis(o, 1, 2)[:, :t], hybrid_lm.KDA_KEPT_OUTPUT), lowest


# ------------------------------------------------------------------ the mixers


def l2norm(x):
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.sum(jnp.square(x32), axis=-1, keepdims=True) + L2_EPS)
            ).astype(x.dtype)


def _low_rank(u, w1, w2, dtype):
    """(u W1) W2 in float32: the decay's and the gate's maps."""
    return _mm(_mm(u, _cast(w1, dtype)).astype(u.dtype), _cast(w2, dtype))


def kda_mixer(p, x_in, cfg: KimiLinearConfig, dtype):
    """The layer's input [B, T, d] -> (the mixer's output [B, T, d], the most
    negative cumulative log-decay inside any chunk). What comes before the
    delta rule and what comes after it are recomputed on their own in the
    backward pass (`kda_chunked` recomputes its segments itself): of a layer
    that is recomputed whole, the mixer's part then keeps q, k, v, g, beta and
    o while the layer's second half goes backward, not the projections' and
    the decays' float32 intermediates (a dozen arrays of 0.13-0.27 GB at
    16,384 tokens, which lay beside the routed part's full rung at the step's
    peak). Without the two the cell's step computed a wrong gradient and a
    right loss (`recomputed_half`). Of the first forward pass the recomputed
    layer keeps the two arrays `kda_chunked` names (`run_stack`): o, which
    `after` reads, and the states entering the segments, which the delta
    rule's backward reads; 0.27 GB a layer at 16,384 tokens, for one pass
    over the segments that is not run again."""
    h, dk = cfg.linear_num_heads, cfg.linear_head_dim
    bsz, t = x_in.shape[:2]
    by_head = lambda x: x.reshape(bsz, t, h, dk)

    @recomputed_half
    def before(p, x_in):
        with jax.named_scope("kda_in"):
            u = rms_norm(x_in, p["norm1"], cfg.rms_norm_eps)

            def branch(name):
                x = _mm(u, _cast(p[name], dtype)).astype(u.dtype)
                return by_head(jax.nn.silu(causal_conv(x, _cast(p["conv_" + name], dtype), 0)))

            q, k, v = l2norm(branch("q")) * dk ** -0.5, l2norm(branch("k")), branch("v")
            g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
                by_head(_low_rank(u, p["f1"], p["f2"], dtype) + p["dt_bias"]))
            beta = jax.nn.sigmoid(_mm(u, _cast(p["beta"], dtype)))
        return u, q, k, v, g, beta

    @recomputed_half
    def after(p, u, o):
        with jax.named_scope("kda_out"):
            gate = jax.nn.sigmoid(by_head(_low_rank(u, p["g1"], p["g2"], dtype))).astype(u.dtype)
            y = rms_norm(o, p["onorm"], cfg.rms_norm_eps) * gate
            return _mm(y.reshape(bsz, t, h * dk), _cast(p["o"], dtype)).astype(u.dtype)

    u, q, k, v, g, beta = before(p, x_in)
    with jax.named_scope("kda_scan"):
        o, log_decay_min = kda_chunked(q, k, v, g, beta)
    return after(p, u, o), log_decay_min


def mla_mixer(p, x_in, cfg: KimiLinearConfig, dtype):
    """The layer's input [B, T, d] -> (the attention's output [B, T, d], key
    blocks multiplied, 1 where the kernels ran). Nothing is rotated: the
    `qk_rope_head_dim` part of a query and the key part the heads share are
    plain dimensions."""
    h, lat, nope = cfg.num_attention_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim
    rope, dv = cfg.qk_rope_head_dim, cfg.v_head_dim
    bsz, t = x_in.shape[:2]
    with jax.named_scope("latent_attention"):
        u = rms_norm(x_in, p["norm1"], cfg.rms_norm_eps)
        q = _mm(u, _cast(p["q"], dtype)).astype(u.dtype).reshape(bsz, t, h, 1, nope + rope)
        c, shared = jnp.split(_mm(u, _cast(p["kva"], dtype)).astype(u.dtype), [lat], axis=-1)
        kv = _mm(rms_norm(c, p["kv_norm"], cfg.rms_norm_eps), _cast(p["kvb"], dtype))
        kn, v = jnp.split(kv.astype(u.dtype).reshape(bsz, t, h, nope + dv), [nope], axis=-1)
        k = jnp.concatenate(
            [kn, jnp.broadcast_to(shared[:, :, None, :], (bsz, t, h, rope))], axis=-1)
        a, key_blocks, on_kernels = blocked_attention(q, k, v)
        out = _mm(a.reshape(bsz, t, h * dv), _cast(p["o"], dtype)).astype(u.dtype)
    return out, key_blocks, on_kernels


def mlp(kind: str, p, x, cfg: KimiLinearConfig, dtype):
    """The layer's second half: x [B, T, d] -> (its output, the routed
    part's counters and `swiglu_calls`, the router's choices or None)."""
    if kind == "D":
        with jax.named_scope("dense_mlp"):
            u2 = rms_norm(x, p["norm2"], cfg.rms_norm_eps)
            return swiglu(u2, p["w_gate"], p["w_up"], p["w_down"], dtype), {"swiglu_calls": 1}, None
    with jax.named_scope("moe_router"):
        u2 = rms_norm(x, p["norm2"], cfg.rms_norm_eps).reshape(-1, x.shape[-1])
    routed, counters, top_i = hybrid_lm.moe_routed(
        p, u2, cfg, dtype, family=hybrid_lm.SWIGLU, rung_loads=(cfg.moe_rung_loads,))
    with jax.named_scope("moe_shared"):
        shared = swiglu(u2, p["s_gate"], p["s_up"], p["s_down"], dtype)
    return (routed + shared).reshape(x.shape), {**counters, "swiglu_calls": 1}, top_i


def layer(mixer: str, mlp_kind: str, p, x, cfg: KimiLinearConfig, dtype):
    """One layer: (x, the layer's counters, the router's choices or None)."""
    if mixer == "K":
        out, log_decay_min = kda_mixer(p, x, cfg, dtype)
        mixed = {"kda_log_decay_min": log_decay_min}
    else:
        out, key_blocks, on_kernels = mla_mixer(p, x, cfg, dtype)
        mixed = {"attn_key_blocks_full": key_blocks, "attn_on_kernels": on_kernels}
    x = x + out
    out, counters, top_i = mlp(mlp_kind, p, x, cfg, dtype)
    return x + out, {**counters, **mixed}, top_i


# ------------------------------------------------------------------ the stack


def hidden_states(params, ids, cfg: KimiLinearConfig, *, compute_dtype=None, remat: bool = True):
    """ids [B, T] -> (the last layer's output [B, T, d], one counters dict a
    layer, the routers' choices [E layers, B * T, k])."""

    def held(mixer, mlp_kind):
        def f(p, x, side):
            x, c, top_i = layer(mixer, mlp_kind, p, x, cfg, compute_dtype)
            return x, side, (c, top_i)
        return f

    x, aux = run_stack(params, ids, [held(m, f) for m, f in cfg.kinds],
                       compute_dtype=compute_dtype, remat=remat)
    return x, [c for c, _ in aux], [top_i for _, top_i in aux if top_i is not None]


def routing_choices(params, ids, cfg: KimiLinearConfig, *, compute_dtype=None):
    """The experts every token chose in every `E` layer: [layers, B * T, k]."""
    return jnp.stack(hidden_states(params, ids, cfg, compute_dtype=compute_dtype,
                                   remat=False)[2])


def kda_chunks(cfg: KimiLinearConfig, bsz: int, t: int) -> int:
    """Chunks of the delta rule a step, over the KDA layers held."""
    return bsz * -(-t // KDA_CHUNK) * sum(m == "K" for m, _ in cfg.kinds)


def lm_loss(params, ids, cfg: KimiLinearConfig, *, compute_dtype=None,
            remat: bool = True) -> Tuple[jnp.ndarray, dict]:
    """Next-token cross-entropy over the vocabulary rows held here
    (`hybrid_lm.next_token_loss`). Returns (loss, counters): the routed
    part's four over the `E` layers (`hybrid_lm.merge_counters`), the chunks
    of the delta rule over the KDA layers, the most negative cumulative
    log-decay inside any of them, the key blocks the latent layers multiplied,
    `hybrid_lm.forward_kept`, and `kda_forward_kept`: the KDA layers whose
    recomputation reads the delta rule's kept output and states and does not
    run its forward pass again (all of them under `remat`, which is what
    recomputes; none without), and `laguna.swiglu_backward_staged`."""
    x, counted, _ = hidden_states(params, ids, cfg, compute_dtype=compute_dtype, remat=remat)
    with jax.named_scope("lm_head_loss"):
        h = rms_norm(x, params["final_norm"], cfg.rms_norm_eps).reshape(-1, x.shape[-1])
        loss = next_token_loss(h, _cast(params["head"], compute_dtype), ids)
    with jax.named_scope("step_metrics"):
        counters = hybrid_lm.merge_counters(counted)
        counters["attn_forward_kept"] = hybrid_lm.forward_kept(counted, remat)
        counters["attn_key_blocks_full"] = jnp.float32(
            sum(c.get("attn_key_blocks_full", 0) for c in counted))
        counters["kda_chunks"] = jnp.float32(kda_chunks(cfg, *ids.shape))
        counters["kda_forward_kept"] = jnp.float32(
            sum(m == "K" for m, _ in cfg.kinds) if remat else 0)
        counters["swiglu_backward_staged"] = swiglu_backward_staged(counted)
        decays = [c["kda_log_decay_min"] for c in counted if "kda_log_decay_min" in c]
        if decays:
            counters["kda_log_decay_min"] = jnp.min(jnp.stack(decays))
    return loss, counters


def param_count(cfg: KimiLinearConfig) -> int:
    return count_shapes(param_shapes(cfg))
