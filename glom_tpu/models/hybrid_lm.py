"""A hybrid language model: a stack of pre-norm residual layers,
`x <- x + Mixer(RMSNorm(x))`, each with one of three mixers by the config's
pattern — `M` Mamba-2 (the chunked SSD form), `*` grouped-query causal
attention, `E` a latent mixture of experts with one shared expert — then a
final norm, an untied head and next-token cross-entropy. The layer equations
are the published `nemotron_h` ones; `utils/config.HybridLMConfig` holds the
shapes.

The model is told what it holds of each layer (a chip's share of a
deployment that divides a layer over several chips): which experts, how many
heads, how many rows of the vocabulary. An `E` layer routes over all the
experts the router scores, and computes the terms of the experts held here
for the tokens that chose them: a sort of the token-expert pairs by expert,
group sizes as uneven as the routing makes them, one grouped product over
the experts held (`lax.ragged_dot`), a weighted combine. The rows are the
smallest rung that holds this step's pairs, of a short static ladder chosen
on the device (`row_rungs`); the last rung is the worst imbalance (every
token choosing every held expert), so no pair is ever dropped; what the
absent experts would have added is left out. That routed part (`moe_routed`)
is also `models/laguna.py`'s: what a group's rows go through and whether a
latent step wraps it is its `family` (LATENT_RELU2 here, SWIGLU there).

XLA, but for the attention's scores: where the shapes tile and the device is
a TPU, `blocked_attention` runs the Pallas kernels of
`kernels/flash_attention.py`. The route's name stayed `vjp_path: lm_xla`.
Parameters are float32; with a compute dtype the residual stream and the
matrix products run in it, the router, the recurrences' decays, the norms'
statistics, the softmaxes and the loss in float32. Every device op sits
under one of `tracing.spans.LM_DEVICE_PHASES`.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Tuple

import jax
import jax.numpy as jnp

from glom_tpu.kernels import flash_attention
from glom_tpu.utils.config import HybridLMConfig

Params = Any  # {"embed", "layers": (one dict a layer), "final_norm", "head"}
ATTN_QUERY_BLOCK = 1024
ATTN_KEY_BLOCK = 128  # the unit `blocked_attention` counts the keys it multiplies in
LOSS_ROW_BLOCK = 2048
ROW_TILE = 1024  # the experts' rows come in multiples of this
RUNG_LOADS = (2,)  # the small row counts, in balanced loads (`row_rungs`)
ROUTED_COUNTERS = ("moe_pairs_here", "moe_rows_computed", "moe_rows_full_share",
                   "moe_max_expert_load")
STACK_COUNTERS = ROUTED_COUNTERS + ("attn_forward_kept",)  # what Laguna's and Kimi Linear's add to
COUNTERS = STACK_COUNTERS + ("shared_backward_staged",)
# What a recomputed layer keeps of its first forward pass beside its inputs (`run_stack`):
# the `checkpoint_name`s of the attention kernel's output and log-sum-exp rows, and of the
# delta rule's output and of the states entering its segments (`kimi_linear.kda_chunked`,
# which imports this module and reads its two names here).
KDA_KEPT_OUTPUT, KDA_KEPT_STATES = "kda_scan_out", "kda_scan_states"
KEPT_NAMES = (flash_attention.KEPT_OUTPUT, flash_attention.KEPT_LSE,
              KDA_KEPT_OUTPUT, KDA_KEPT_STATES)


# ----------------------------------------------------------------- parameters


def mamba_in_width(cfg: HybridLMConfig) -> int:
    """Columns of the in-projection: z, x, B, C, dt."""
    return 2 * cfg.mamba_inner + 2 * cfg.n_groups * cfg.ssm_state_size + cfg.mamba_num_heads


def layer_shapes(kind: str, cfg: HybridLMConfig) -> dict:
    """{leaf: shape} of one layer of mixer `kind`."""
    d = cfg.hidden_size
    if kind == "M":
        return {
            "norm": (d,), "in_proj": (d, mamba_in_width(cfg)),
            "conv_w": (cfg.mamba_conv_dim, cfg.conv_kernel), "conv_b": (cfg.mamba_conv_dim,),
            "dt_bias": (cfg.mamba_num_heads,), "A_log": (cfg.mamba_num_heads,),
            "D": (cfg.mamba_num_heads,), "gnorm": (cfg.mamba_inner,),
            "out_proj": (cfg.mamba_inner, d),
        }
    if kind == "*":
        q = cfg.num_attention_heads * cfg.head_dim
        kv = cfg.num_key_value_heads * cfg.head_dim
        return {"norm": (d,), "q": (d, q), "k": (d, kv), "v": (d, kv), "o": (q, d)}
    e, lat, f = cfg.n_routed_experts, cfg.moe_latent_size, cfg.moe_intermediate_size
    fs = cfg.moe_shared_expert_intermediate_size
    return {
        "norm": (d,), "router": (d, cfg.n_routed_experts_total),
        "down": (d, lat), "up": (lat, d), "w1": (e, lat, f), "w2": (e, f, lat),
        "s1": (d, fs), "s2": (fs, d),
    }


def param_shapes(cfg: HybridLMConfig) -> dict:
    d, v = cfg.hidden_size, cfg.vocab_size
    return {
        "embed": (v, d),
        "layers": tuple(layer_shapes(kind, cfg) for kind in cfg.pattern),
        "final_norm": (d,),
        "head": (d, v),
    }


def _is_shape(s) -> bool:
    return isinstance(s, tuple) and all(isinstance(i, int) for i in s)


def init_leaf(key, name: str, shape, cfg: HybridLMConfig):
    """One leaf's initial value, float32. Matrices are normal with std 0.02,
    the out-projections of the Mamba-2 and attention mixers scaled by
    1/sqrt(2 x layers of the published stack) (`rescale_prenorm_residual`);
    norms are ones; the conv and the recurrence's `dt_bias`, `A_log`, `D`
    as the Mamba-2 reference initialises them."""
    if name in ("norm", "gnorm", "final_norm", "D"):
        return jnp.ones(shape, jnp.float32)
    if name in ("conv_w", "conv_b"):
        bound = cfg.conv_kernel ** -0.5
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    if name == "dt_bias":
        lo, hi = math.log(cfg.time_step_min), math.log(cfg.time_step_max)
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32) * (hi - lo) + lo)
        dt = jnp.maximum(dt, cfg.time_step_floor)
        return dt + jnp.log(-jnp.expm1(-dt))  # softplus's inverse
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    std = 0.02
    if name in ("out_proj", "o"):
        std /= math.sqrt(2.0 * cfg.num_hidden_layers_total)
    return std * jax.random.normal(key, shape, jnp.float32)


def init_tree(key: jax.Array, shapes, leaf, cfg):
    """A tree of shapes -> the tree of `leaf(key_i, name, shape, cfg)`, the
    leaf's name being the last key on its path."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=_is_shape)
    return jax.tree_util.tree_unflatten(treedef, [
        leaf(jax.random.fold_in(key, i), path[-1].key, shape, cfg)
        for i, (path, shape) in enumerate(leaves)])


@functools.partial(jax.jit, static_argnums=(1,))
def init_hybrid_lm(key: jax.Array, cfg: HybridLMConfig) -> Params:
    return init_tree(key, param_shapes(cfg), init_leaf, cfg)


# --------------------------------------------------------------------- pieces


def _cast(w, dtype):
    return w if dtype is None else w.astype(dtype)


def rms_norm(x, weight, eps: float):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    return (y * weight).astype(x.dtype)


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def _mm(a, b):
    """a [..., k] @ b [k, n], float32 accumulation, in the operands' type."""
    return jnp.einsum("...k,kn->...n", a, b, preferred_element_type=jnp.float32)


def _mm_back(d, b):
    """`_mm`'s cotangent to a: d [..., n] @ b [k, n]^T."""
    return jnp.einsum("...n,kn->...k", d, b, preferred_element_type=jnp.float32)


def _mm_onto(a, d):
    """`_mm`'s cotangent to b: a [..., k]^T d [..., n], summed over the rows."""
    return jnp.einsum("...k,...n->kn", a, d, preferred_element_type=jnp.float32)


# -------------------------------------------------------------------- Mamba-2


def causal_conv(x, w, b):
    """Depthwise causal conv over time: x [B, T, C], w [C, K], b [C];
    out[t] = b + sum_j w[:, j] x[t - (K - 1) + j]."""
    k = w.shape[1]
    t = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return b + sum(xp[:, j:j + t] * w[:, j] for j in range(k))


def ssd_chunked(x, dt, a, b, c, chunk: int):
    """The selective state-space recurrence
        h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,   y_t = h_t C_t
    by chunks (the SSD form of Mamba-2): within a chunk the quadratic,
    attention-like form; between chunks the states, carried by their decays.
    x [B, T, G, R, P] (G groups of R heads, head size P), dt [B, T, G, R]
    float32, a [G, R] float32 (negative), b and c [B, T, G, N]. A length that
    is no multiple of the chunk is padded with steps of dt = 0, which neither
    decay the state nor add to it. Returns y [B, T, G, R, P] in x's type.
    Inside, heads come before positions ([B, chunks, G, R, Q, ...]) so that
    the arrays' last two dimensions are the large ones."""
    bsz, t = x.shape[:2]
    pad = -t % chunk
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                       for v in (x, dt, b, c))
    nc = (t + pad) // chunk
    dtype = x.dtype
    x = x.reshape(bsz, nc, chunk, *x.shape[2:]).transpose(0, 1, 3, 4, 2, 5)   # b z g r q p
    dt = dt.reshape(bsz, nc, chunk, *dt.shape[2:]).transpose(0, 1, 3, 4, 2)   # b z g r q
    b = b.reshape(bsz, nc, chunk, *b.shape[2:]).transpose(0, 1, 3, 2, 4)      # b z g q n
    c = c.reshape(bsz, nc, chunk, *c.shape[2:]).transpose(0, 1, 3, 2, 4)
    cs = jnp.cumsum(dt * a[:, :, None], axis=-1)   # inclusive, within the chunk, <= 0
    # within the chunk: y_i += sum_{j<=i} (C_i . B_j) exp(cs_i - cs_j) dt_j x_j
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(lower, cs[..., :, None] - cs[..., None, :], -jnp.inf))
    cb = jnp.einsum("bzgin,bzgjn->bzgij", c, b, preferred_element_type=jnp.float32)
    w = (cb[:, :, :, None] * decay * dt[..., None, :]).astype(dtype)
    y = jnp.einsum("bzgrij,bzgrjp->bzgrip", w, x, preferred_element_type=jnp.float32)
    # the state each chunk adds: S_z = sum_j exp(cs_last - cs_j) dt_j x_j B_j^T
    to_end = (jnp.exp(cs[..., -1:] - cs) * dt).astype(dtype)
    s = jnp.einsum("bzgrjp,bzgjn->bzgrpn", x * to_end[..., None], b,
                   preferred_element_type=jnp.float32)
    # the state entering chunk z: h_z = sum_{k<z} exp(the decays of the chunks between) S_k
    total = jnp.moveaxis(cs[..., -1], 1, -1)       # [B, G, R, nc]
    run = jnp.cumsum(total, axis=-1)
    before = jnp.tril(jnp.ones((nc, nc), bool), -1)
    carry = jnp.exp(jnp.where(before, (run - total)[..., :, None] - run[..., None, :], -jnp.inf))
    h = jnp.einsum("bgrzk,bkgrpn->bzgrpn", carry, s)
    # what the entering state gives position i: exp(cs_i) C_i . h_z
    off = jnp.einsum("bzgin,bzgrpn->bzgrip", c, h.astype(dtype),
                     preferred_element_type=jnp.float32)
    y = y + off * jnp.exp(cs)[..., None]
    y = y.transpose(0, 1, 4, 2, 3, 5).reshape(bsz, nc * chunk, *y.shape[2:4], y.shape[-1])
    return y[:, :t].astype(dtype)


def mamba_mixer(p, x_in, cfg: HybridLMConfig, dtype):
    """The layer's input [B, T, d] -> the mixer's output [B, T, d]; the heads
    held here are whole groups."""
    g, n, pdim = cfg.n_groups, cfg.ssm_state_size, cfg.mamba_head_dim
    r = cfg.mamba_num_heads // g
    di = cfg.mamba_inner
    bsz, t = x_in.shape[:2]
    with jax.named_scope("mamba_in"):
        u = rms_norm(x_in, p["norm"], cfg.layer_norm_epsilon)
        zxbcdt = _mm(u, _cast(p["in_proj"], dtype)).astype(u.dtype)
        z, xbc, dt = jnp.split(zxbcdt, [di, di + cfg.mamba_conv_dim], axis=-1)
        xbc = jax.nn.silu(causal_conv(xbc, _cast(p["conv_w"], dtype), _cast(p["conv_b"], dtype)))
        x, b, c = jnp.split(xbc, [di, di + g * n], axis=-1)
        x = x.reshape(bsz, t, g, r, pdim)
        b, c = b.reshape(bsz, t, g, n), c.reshape(bsz, t, g, n)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"]).reshape(bsz, t, g, r)
        a = -jnp.exp(p["A_log"]).reshape(g, r)
    with jax.named_scope("ssd_scan"):
        y = ssd_chunked(x, dt, a, b, c, cfg.chunk_size)
        y = y + _cast(p["D"], dtype).reshape(g, r, 1) * x
    with jax.named_scope("mamba_out"):
        # gated RMSNorm by group: y * silu(z), normalised within each group
        y = (y.reshape(bsz, t, di) * jax.nn.silu(z)).reshape(bsz, t, g, di // g)
        y = rms_norm(y, p["gnorm"].reshape(g, di // g), cfg.layer_norm_epsilon)
        return _mm(y.reshape(bsz, t, di), _cast(p["out_proj"], dtype)).astype(u.dtype)


# ------------------------------------------------------------------ attention


def _attend(q, k, v, first: int, key_first: int = 0, window=None):
    """One block of queries against the keys at or before them, and with a
    `window` no more than window - 1 before. q [B, tq, G, R, D] at positions
    first..first+tq, k [B, tk, G, D] and v [B, tk, G, Dv] at
    key_first..key_first+tk."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqgrd,bkgd->bgrqk", q, k, preferred_element_type=jnp.float32) * scale
    qpos = first + jnp.arange(q.shape[1])[:, None]
    kpos = jnp.arange(key_first, key_first + k.shape[1])[None, :]
    seen = kpos <= qpos
    if window is not None:
        seen &= kpos > qpos - window
    s = jnp.where(seen, s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bgrqk,bkgd->bqgrd", pr, v, preferred_element_type=jnp.float32
                      ).astype(q.dtype)


def blocked_attention(q, k, v, window=None):
    """Causal attention, a block of queries at a time against the keys the
    block can see: those up to its end and, with a `window`, from window - 1
    before its first on. The keys a block cannot see are multiplied neither
    here nor in the backward pass; no [T, T] array is kept. q [B, T, G, R,
    D], k [B, T, G, D], v [B, T, Gv, Dv] (KV head g reads value head g // (G /
    Gv)) -> ([B, T, G, R, Dv], the key blocks of ATTN_KEY_BLOCK keys that
    were multiplied, summed over the query blocks, and 1 where the kernels
    ran, else 0).

    Where the shapes tile (`flash_attention.tiles`) and the device is a TPU,
    the blocks are the Pallas kernels' tiles and the scores never leave VMEM;
    otherwise the blocks are ATTN_QUERY_BLOCK queries through XLA, each
    recomputed in the backward pass."""
    t, g = q.shape[1], k.shape[2]
    tiled = flash_attention.tiles(t, q.shape[3], q.shape[4], v.shape[-1], window)
    if tiled and flash_attention.on_tpu():
        tq, tk = tiled
        out = flash_attention.flash_attention(q, k, v, window, tq=tq, tk=tk)
        return out, flash_attention.key_blocks(t, tq, tk, window, ATTN_KEY_BLOCK), 1
    if v.shape[2] != g:
        v = jnp.repeat(v, g // v.shape[2], axis=2)
    out, key_blocks = [], 0
    for first in range(0, t, ATTN_QUERY_BLOCK):
        last = min(t, first + ATTN_QUERY_BLOCK)
        low = 0 if window is None else max(0, first - window + 1)
        block = jax.checkpoint(functools.partial(
            _attend, first=first, key_first=low, window=window))
        out.append(block(q[:, first:last], k[:, low:last], v[:, low:last]))
        key_blocks += -(-(last - low) // ATTN_KEY_BLOCK)
    return jnp.concatenate(out, axis=1), key_blocks, 0


def attention_mixer(p, x_in, cfg: HybridLMConfig, dtype):
    """Grouped-query causal attention, no positions, no bias, by
    `blocked_attention`: (the mixer's output, 1 where the kernels ran)."""
    g, dh = cfg.num_key_value_heads, cfg.head_dim
    r = cfg.num_attention_heads // g
    bsz, t = x_in.shape[:2]
    with jax.named_scope("attention"):
        u = rms_norm(x_in, p["norm"], cfg.layer_norm_epsilon)
        q = _mm(u, _cast(p["q"], dtype)).astype(u.dtype).reshape(bsz, t, g, r, dh)
        k = _mm(u, _cast(p["k"], dtype)).astype(u.dtype).reshape(bsz, t, g, dh)
        v = _mm(u, _cast(p["v"], dtype)).astype(u.dtype).reshape(bsz, t, g, dh)
        o, _, on_kernels = blocked_attention(q, k, v)
        o = _mm(o.reshape(bsz, t, g * r * dh), _cast(p["o"], dtype)).astype(u.dtype)
    return o, on_kernels


# ---------------------------------------------------- latent mixture of experts


def route(p, u2, cfg):
    """The router: u2 [N, d] -> the chosen experts [N, k] (of all the router
    scores) and their weights [N, k]. Scores are sigmoids of a float32
    product; the k largest are chosen (one group, so plain top-k; the
    selection bias is zero), and weigh s_k / sum_k s_k * the scaling factor."""
    logits = jnp.dot(u2.astype(jnp.float32), p["router"],
                     precision=jax.lax.Precision.HIGHEST)
    top_s, top_i = jax.lax.top_k(jax.nn.sigmoid(logits), cfg.num_experts_per_tok)
    return top_i, top_s / jnp.sum(top_s, axis=-1, keepdims=True) * cfg.routed_scaling_factor


def row_rungs(n: int, cfg, loads=RUNG_LOADS) -> Tuple[int, ...]:
    """The row counts the routed experts' part may run at for n tokens,
    ascending. The last is the full count, n * min(k, experts held) +
    experts held rounded up to ROW_TILE: room for every pair whatever the
    imbalance, and a row of room inside every expert's group, so that the
    grouped product never meets an empty group. Before it, `loads` times the
    pairs a balanced router sends here (n * k * experts held / experts
    scored), rounded up likewise, where that is fewer rows than the full
    count: a share that holds most of the experts has one rung."""
    k, e = cfg.num_experts_per_tok, cfg.n_routed_experts
    # rounded up: the TPU's grouped product tiles its rows, and a count that
    # is no multiple of the tile is tiled by 8 (65,544 rows took 70 times
    # 65,536's time on a v5e)
    tile = lambda rows: -(-rows // ROW_TILE) * ROW_TILE
    full = tile(n * min(k, e) + e)  # past `dispatch`'s n * k + e rows where k <= e: padded
    balanced = n * k * e / cfg.n_routed_experts_total
    small = sorted({tile(math.ceil(load * balanced)) for load in loads})
    return tuple(rows for rows in small if rows < full) + (full,)


def dispatch(top_i, cfg):
    """Sort the token-expert pairs by expert, those of the experts held here
    first, each expert's group closed by its row of room. Returns, for all
    N * k + experts held rows of the sorted order (the first
    sum(group_sizes) are what the experts held compute; the caller keeps a
    rung's worth): the pair each row is (`pair`, an index into the flattened
    [N * k]), whether the row is a pair of an expert held (`valid`), and the
    sizes of the experts' groups of rows [E], the rows of room counted in."""
    n, k = top_i.shape
    e = cfg.n_routed_experts
    local = top_i - cfg.expert_offset
    key = jnp.where((local >= 0) & (local < e), local, e).reshape(-1)
    key = jnp.concatenate([key, jnp.arange(e, dtype=key.dtype)])  # the rows of room
    # jnp.argsort's own sort, keeping the sorted keys it drops
    key, row = jax.lax.sort_key_val(key, jnp.arange(key.shape[0], dtype=jnp.int32),
                                    is_stable=True)
    valid = (key < e) & (row < n * k)
    group_sizes = jnp.sum(key[:, None] == jnp.arange(e)[None, :], axis=0, dtype=jnp.int32)
    return jnp.minimum(row, n * k - 1), valid, group_sizes


def relu2_experts(x, valid, group_sizes, w1, w2):
    """A group's rows through its expert, W2 relu2(W1 x): the latent
    mixture's."""
    h = jax.lax.ragged_dot(x, w1.astype(x.dtype), group_sizes)
    return jax.lax.ragged_dot(relu2(jnp.where(valid, h, 0)), w2.astype(x.dtype), group_sizes)


def swiglu_experts(x, valid, group_sizes, w_gate, w_up, w_down):
    """A group's rows through its expert, W_down (silu(W_gate x) * W_up x)."""
    gate = jax.lax.ragged_dot(x, w_gate.astype(x.dtype), group_sizes)
    up = jax.lax.ragged_dot(x, w_up.astype(x.dtype), group_sizes)
    h = jax.nn.silu(jnp.where(valid, gate, 0)) * jnp.where(valid, up, 0)
    return jax.lax.ragged_dot(h, w_down.astype(x.dtype), group_sizes)


def expert_rows(rows: int, k: int, experts, diff, order):
    """The part of the routed experts whose arrays have a row a pair, at a
    static count of `rows` that holds sum(group_sizes): gather, the grouped
    products, weights, scatter back and, where there is a latent step, its
    up-projection. `experts(x, valid, group_sizes, *weights)` is what a
    group's rows go through (`relu2_experts`, `swiglu_experts`); `diff` = (v
    [N, width], top_w [N, k], the experts' weights, and the latent
    up-projection or None; the parameters multiply in v's type); `order` is
    `dispatch`'s."""
    v, top_w, weights, up = diff
    pair, valid, group_sizes = order
    with jax.named_scope("moe_dispatch"):
        if rows > pair.shape[0]:   # the full count's rounding up, where k <= experts held
            pair, valid = (jnp.pad(a, (0, rows - a.shape[0])) for a in (pair, valid))
        pair, valid = pair[:rows], valid[:rows, None]
        token = pair // k
        x = jnp.where(valid, v[token], 0)
    with jax.named_scope("moe_experts"):
        # The rows past the last group are room, and the TPU's grouped
        # product leaves them unwritten, forward and backward (NaN among
        # them): they are zeroed by a select wherever they come out, before
        # anything multiplies them.
        y = experts(x, valid, group_sizes, *weights)
    with jax.named_scope("moe_combine"):
        # masked before it is weighted: a product with an unwritten row would
        # carry its NaN into the weights' gradient, whatever the cotangent
        y = jnp.where(valid, y, 0) * top_w.reshape(-1)[pair][:, None]
        routed = jax.ops.segment_sum(y, token, num_segments=v.shape[0]).astype(v.dtype)
        return routed if up is None else _mm(routed, up.astype(v.dtype)).astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def on_the_ladder(rungs, k, experts, rung, diff, order):
    """`expert_rows` at rungs[rung], chosen on the device. Differentiable in
    `diff`; what the backward pass keeps is the operands, which no rung
    sizes: it chooses the rung again and differentiates `expert_rows` inside
    the branch. (Differentiating the `switch` itself makes every branch
    return every branch's intermediates, the full rung's among them, as
    zeros where it did not run.)"""
    return jax.lax.switch(
        rung, [functools.partial(expert_rows, rows, k, experts) for rows in rungs], diff, order)


def _ladder_fwd(rungs, k, experts, rung, diff, order):
    return on_the_ladder(rungs, k, experts, rung, diff, order), (rung, diff, order)


def _ladder_bwd(rungs, k, experts, kept, g):
    rung, diff, order = kept

    def pull(rows):
        return lambda diff, order, g: jax.vjp(
            lambda diff: expert_rows(rows, k, experts, diff, order), diff)[1](g)[0]

    return None, jax.lax.switch(rung, [pull(rows) for rows in rungs], diff, order, g), None


on_the_ladder.defvjp(_ladder_fwd, _ladder_bwd)

# What a family's routed experts are: the function a group's rows go through,
# the names of its weights in the layer's parameters, and the latent down- and
# up-projections that wrap it, or None.
LATENT_RELU2 = (relu2_experts, ("w1", "w2"), ("down", "up"))
SWIGLU = (swiglu_experts, ("e_gate", "e_up", "e_down"), None)


def moe_routed(p, u2, cfg, dtype, choices=None, family=LATENT_RELU2, rung_loads=RUNG_LOADS):
    """The routed experts' part, for the experts held here: u2 [N, d] ->
    ([N, d], counters, the router's choices). `choices` (top_i, weights)
    replaces the router's. `family` says what the experts are (LATENT_RELU2,
    SWIGLU); `cfg` is any configuration with the router's and the share's
    counts (`route`, `dispatch`, `row_rungs`). The row-sized part runs at the
    smallest of `row_rungs` that holds this step's pairs and the rows of room;
    `rung_loads` is for a job whose router is known to be out of balance (a
    configuration's to say, not a family's: `LagunaConfig.moe_rung_loads`)."""
    experts, names, latent = family
    k = cfg.num_experts_per_tok
    rungs = row_rungs(u2.shape[0], cfg, rung_loads)
    with jax.named_scope("moe_router"):
        top_i, top_w = route(p, u2, cfg) if choices is None else choices
    with jax.named_scope("moe_dispatch"):
        v = u2 if latent is None else _mm(u2, _cast(p[latent[0]], dtype)).astype(u2.dtype)
        order = _, valid, group_sizes = dispatch(top_i, cfg)
        # the first rung that holds the pairs and the rows of room
        rung = jnp.sum(jnp.sum(group_sizes) > jnp.asarray(rungs[:-1], jnp.int32),
                       dtype=jnp.int32)
    diff = (v, top_w, tuple(p[name] for name in names), None if latent is None else p[latent[1]])
    if len(rungs) == 1:
        out = expert_rows(rungs[0], k, experts, diff, order)
    else:
        out = on_the_ladder(rungs, k, experts, rung, diff, order)
    with jax.named_scope("step_metrics"):
        counters = {
            "moe_pairs_here": jnp.sum(valid).astype(jnp.float32),
            "moe_rows_computed": jnp.asarray(rungs, jnp.float32)[rung],
            "moe_rows_full_share": (rung == len(rungs) - 1).astype(jnp.float32),
            "moe_max_expert_load": (jnp.max(group_sizes) - 1).astype(jnp.float32),
        }
    return out, counters, top_i


def merge_counters(counted) -> dict:
    """One dict a layer, an expert layer's holding ROUTED_COUNTERS -> each
    the mean over the expert layers, the fullest expert's load the maximum;
    {} where there is no expert layer."""
    merged = {}
    counters = [c for c in counted if "moe_pairs_here" in c]
    if counters:
        for name in ROUTED_COUNTERS:
            vals = jnp.stack([c[name] for c in counters])
            merged[name] = jnp.max(vals) if name == "moe_max_expert_load" else jnp.mean(vals)
    return merged


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def relu2_mlp(u, w1, w2, dtype):
    """`relu(u W1)^2 W2` in u's type: the up product leaves pre in float32, h
    = relu2(pre) is rounded to u's type for the down product. The weights are
    cast to `dtype` at each product.

    Its derivative is its own, of the shape of `laguna.swiglu`'s. Kept: u,
    the weights and pre in float32, which is what autodiff kept (under
    `run_stack`'s recomputation the up product runs again and the down
    product falls out). The backward holds every operand of its four products
    as an ARRAY in u's type, behind `lax.optimization_barrier`s: dy as it
    arrives; then dh = dy W2^T, rounded as autodiff rounded it, and ONE
    float32 elementwise pass over pre and dh that writes h and dpre = dh * 2
    relu(pre). dW2 = h^T dy, du = dpre W1^T and dW1 = u^T dpre read those.
    Without the barrier the compiler fuses relu2, its derivative and the cast
    into each of the three as its operand's producer and evaluates them from
    float32 pre once for every output tile that crosses a row (PERF.md
    section 7, trap 20). A default-precision product rounds a float32 operand
    to bfloat16 on the chip anyway, so the products multiply what they
    multiplied; in float32 nothing is rounded and the gradients are
    autodiff's."""
    return _relu2_mlp_fwd(u, w1, w2, dtype)[0]


def _relu2_mlp_fwd(u, w1, w2, dtype):
    pre = _mm(u, _cast(w1, dtype))
    out = _mm(relu2(pre).astype(u.dtype), _cast(w2, dtype)).astype(u.dtype)
    return out, (u, w1, w2, pre)


def _relu2_mlp_bwd(dtype, kept, dy):
    u, w1, w2, pre = kept
    dy = jax.lax.optimization_barrier(dy)
    dh = _mm_back(dy, _cast(w2, dtype)).astype(u.dtype)
    h, pull = jax.vjp(relu2, pre)
    staged = (h, *pull(dh.astype(jnp.float32)))
    h, dpre = jax.lax.optimization_barrier(tuple(a.astype(u.dtype) for a in staged))
    return (_mm_back(dpre, _cast(w1, dtype)).astype(u.dtype), _mm_onto(u, dpre).astype(w1.dtype),
            _mm_onto(h, dy).astype(w2.dtype))


relu2_mlp.defvjp(_relu2_mlp_fwd, _relu2_mlp_bwd)


def moe_shared(p, u2, dtype):
    with jax.named_scope("moe_shared"):
        return relu2_mlp(u2, p["s1"], p["s2"], dtype)


def shared_backward_staged(counted):
    """The records' `shared_backward_staged`: the `E` layers of the step,
    each of whose shared expert reads staged operands in its backward
    (`relu2_mlp`). `counted` is one dict a layer, holding `relu2_mlp_calls`
    where the layer called it."""
    return jnp.float32(sum(c.get("relu2_mlp_calls", 0) for c in counted))


def moe_mixer(p, x_in, cfg: HybridLMConfig, dtype):
    with jax.named_scope("moe_router"):
        u2 = rms_norm(x_in, p["norm"], cfg.layer_norm_epsilon).reshape(-1, x_in.shape[-1])
    routed, counters, top_i = moe_routed(p, u2, cfg, dtype)
    out = (routed + moe_shared(p, u2, dtype)).reshape(x_in.shape)
    return out, {**counters, "relu2_mlp_calls": 1}, top_i


# ------------------------------------------------------------------ the stack


def layer(kind: str, p, x, cfg: HybridLMConfig, dtype):
    """x <- x + Mixer(RMSNorm(x)). Returns (x, the layer's counters or {},
    the router's choices or None)."""
    if kind == "M":
        return x + mamba_mixer(p, x, cfg, dtype), {}, None
    if kind == "*":
        out, on_kernels = attention_mixer(p, x, cfg, dtype)
        return x + out, {"attn_on_kernels": on_kernels}, None
    out, counters, top_i = moe_mixer(p, x, cfg, dtype)
    return x + out, counters, top_i


def run_stack(params: Params, ids, layers, *, compute_dtype=None, remat: bool = True,
              side=None, passes: int = 1, close=None):
    """The stack every language-model family here shares: the embedding's
    rows of `ids` [B, T], then the layers in order, each recomputed whole in
    the backward pass under `remat`. `layers` holds one function a layer,
    `f(p, x, side) -> (x, side, aux)`: `side` is what a layer hands the
    layers after it beside the residual stream. A recomputed layer takes it
    as an input, so its gradient flows back to the layer that made it.
    Returns (the last layer's output [B, T, d], every layer's aux).

    A looped model (`models/ouro.py`) gives `close(x) -> x`, which ends a
    pass, and runs the same layers `passes` times as a `lax.scan` over one
    body of the layers and `close`: the next pass starts from what `close`
    returns, and the first value returned is then the `passes` closed states
    [passes, B, T, d] instead of the last layer's output. Every application
    reads the SAME entry of `params["layers"]` (a looped weight is one leaf),
    is recomputed on its own, and leaves an aux of its own (`passes` x layers
    in all, in the order run; scalars). The scan is what adds a looped
    weight's gradient up in place, pass by pass: unrolled, the compiler keeps
    every pass's float32 partial until one fusion adds them (Ouro's step for
    a v5e: 8.63 GB of temporaries unrolled, four partials of 1.64 GB among
    them; PERF.md section 6, PR 46), and compiles the layers `passes` times.

    A recomputed layer keeps its inputs and what carries one of KEPT_NAMES.
    Where its attention ran the kernels, the two arrays `flash_attention`
    names: the forward kernel's output (B x heads x T x Dv in the compute
    dtype) and its log-sum-exp rows, which are all the backward kernel reads
    of the forward's. Keeping them costs the bytes a second run of the kernel
    would write again, and saves the run: the recomputation rebuilds q, k, v
    and everything round the kernel, and the kernel's call falls out of the
    backward's program as dead code (`forward_kept` counts the layers). The
    XLA loop names nothing, so nothing of it is kept. Where its mixer is the
    delta rule (`kimi_linear.kda_chunked`), the rule's output (B x T x heads
    x D in the compute dtype) and the float32 states entering its segments,
    as many bytes again: the pass over the segments, which the recomputation
    would run for these two alone, falls out the same way. A family that
    names nothing keeps nothing."""
    with jax.named_scope("embed"):
        x = _cast(params["embed"][ids], compute_dtype)
    keep = jax.checkpoint_policies.save_only_these_names(*KEPT_NAMES)

    def one_pass(x, side):
        aux = []
        for f, p in zip(layers, params["layers"]):
            x, side, a = (jax.checkpoint(f, policy=keep) if remat else f)(p, x, side)
            aux.append(a)
        return x, side, aux

    if close is None:
        x, _, aux = one_pass(x, side)
        return x, aux

    def looped(carry, _):
        x, side, aux = one_pass(*carry)
        # recomputed like a layer: else the scan keeps `close`'s float32 intermediates, a pass each
        x = (jax.checkpoint(close) if remat else close)(x)
        return (x, side), (x, aux)

    _, (closed, aux) = jax.lax.scan(looped, (x, side), None, length=passes)
    at = lambda t: lambda a: jax.tree_util.tree_map(lambda v: v[t], a)
    return closed, [a for t in range(passes) for a in map(at(t), aux)]


def forward_kept(counted, remat: bool):
    """The records' `attn_forward_kept`: the attention layers of the step
    whose forward kernel's output the layer's recomputation reads and does
    not rebuild. `counted` is one dict a layer, an attention layer's holding
    `blocked_attention`'s flag under `attn_on_kernels`; without `remat`
    nothing is recomputed, and on the XLA loop nothing is kept."""
    return jnp.float32(sum(c.get("attn_on_kernels", 0) for c in counted) if remat else 0)


def hidden_states(params: Params, ids, cfg: HybridLMConfig, *, compute_dtype=None,
                  remat: bool = True):
    """ids [B, T] -> (the last layer's output [B, T, d], one counters dict a
    layer, the routers' choices [E layers, B * T, k])."""

    def held(kind):
        def f(p, x, side):
            x, c, top_i = layer(kind, p, x, cfg, compute_dtype)
            return x, side, (c, top_i)
        return f

    x, aux = run_stack(params, ids, [held(kind) for kind in cfg.pattern],
                       compute_dtype=compute_dtype, remat=remat)
    return x, [c for c, _ in aux], [top_i for _, top_i in aux if top_i is not None]


def routing_choices(params: Params, ids, cfg: HybridLMConfig, *, compute_dtype=None):
    """The experts every token chose in every `E` layer: [layers, B * T, k]."""
    return jnp.stack(hidden_states(params, ids, cfg, compute_dtype=compute_dtype,
                                   remat=False)[2])


def _block_nll(h, head, targets):
    """targets [rows], or [rows, K] for K heads side by side in the head's columns."""
    logits = _mm(h, head)                          # float32 [rows, V] or [rows, K V]
    if targets.ndim == 2:
        logits = logits.reshape(h.shape[0], targets.shape[1], -1)
    lse = jax.nn.logsumexp(logits, axis=-1)
    return lse - jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]


def next_token_loss(h, head, ids, pred_heads: int = 1, weights=None):
    """Next-token cross-entropy of the normed hidden states h [B * T, d]
    under the head [d, V] (the vocabulary rows held here): float32 logits, a
    block of LOSS_ROW_BLOCK rows at a time, each block recomputed in the
    backward pass; the mean over the B * (T - 1) positions that have a next
    token. With `pred_heads` K > 1 the head is [d, K V], K heads side by
    side, head m of position t predicting the token at t + 1 + m: the mean
    over the heads and the positions that have such a token, every head
    weighing the same.

    With `weights` [P * B * T] float32 (one head), h [P * B * T, d] holds P states of
    every position, one set after another (a looped model's passes), and each
    state's cross-entropy is multiplied by its weight before the sum; the
    divisor stays B * (T - 1), so unit weights on one set are the plain mean.
    The weights are differentiated like h (an exit distribution learns through
    them). The blocks are then the trips of one `lax.scan`, which adds the
    head's gradient up in place: the unrolled loop's block-sized partials (a
    head's size each) all wait for one fusion to add them, which four or
    eight blocks can afford and a looped model's P times as many cannot."""
    bsz, t = ids.shape
    shifted = [jnp.roll(ids, -(1 + m), axis=1) for m in range(pred_heads)]
    if pred_heads == 1:
        targets = shifted[0].reshape(-1)
        has_next = jnp.tile(jnp.arange(t) < t - 1, bsz)
    else:
        targets = jnp.stack(shifted, axis=-1).reshape(-1, pred_heads)
        has_next = jnp.tile(jnp.arange(t)[:, None] + jnp.arange(pred_heads) < t - 1, (bsz, 1))
    count = bsz * sum(t - 1 - m for m in range(pred_heads))
    if weights is not None:
        assert pred_heads == 1, "weights are one head's"
        return _weighed_blocks(h, head, targets, has_next, weights) / count
    total = jnp.zeros((), jnp.float32)
    for first in range(0, bsz * t, LOSS_ROW_BLOCK):
        rows = slice(first, min(bsz * t, first + LOSS_ROW_BLOCK))
        nll = jax.checkpoint(_block_nll)(h[rows], head, targets[rows])
        total = total + jnp.sum(jnp.where(has_next[rows], nll, 0.0))
    return total / count


def _weighed_blocks(h, head, targets, has_next, weights):
    """sum of weights * cross-entropy over the rows of h [P * N, d] whose
    position has a next token (`targets`, `has_next` [N]: one head), a block
    of LOSS_ROW_BLOCK rows a trip of one scan; rows of padding weigh zero."""
    sets = h.shape[0] // targets.shape[0]
    weights = jnp.where(jnp.tile(has_next, sets), weights, 0.0)
    pad = -h.shape[0] % LOSS_ROW_BLOCK
    blocks = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
        -1, LOSS_ROW_BLOCK, *a.shape[1:])

    def add_block(total, block):
        h_rows, t_rows, w_rows = block
        return total + jnp.sum(jax.checkpoint(_block_nll)(h_rows, head, t_rows) * w_rows), None

    return jax.lax.scan(add_block, jnp.zeros((), jnp.float32),
                        (blocks(h), blocks(jnp.tile(targets, sets)), blocks(weights)))[0]


def lm_loss(params: Params, ids, cfg: HybridLMConfig, *, compute_dtype=None,
            remat: bool = True) -> Tuple[jnp.ndarray, dict]:
    """Next-token cross-entropy over the vocabulary rows held here
    (`next_token_loss`). Returns (loss, counters): pairs routed to the
    experts held, rows of the rung the grouped product ran at and whether
    that was the full one, each the mean over the `E` layers, the fullest
    expert's load over all of them, `forward_kept` and
    `shared_backward_staged`."""
    x, counted, _ = hidden_states(params, ids, cfg, compute_dtype=compute_dtype, remat=remat)
    with jax.named_scope("lm_head_loss"):
        h = rms_norm(x, params["final_norm"], cfg.layer_norm_epsilon).reshape(
            -1, x.shape[-1])
        loss = next_token_loss(h, _cast(params["head"], compute_dtype), ids)
    with jax.named_scope("step_metrics"):
        counters = merge_counters(counted)
        counters["attn_forward_kept"] = forward_kept(counted, remat)
        counters["shared_backward_staged"] = shared_backward_staged(counted)
    return loss, counters


def count_shapes(shapes) -> int:
    return sum(math.prod(s) for s in jax.tree_util.tree_leaves(shapes, is_leaf=_is_shape))


def param_count(cfg: HybridLMConfig) -> int:
    return count_shapes(param_shapes(cfg))
