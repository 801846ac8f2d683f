"""Laguna (`model_type: laguna`; poolside's Laguna-XS.2 is the defaults):
pre-norm residual layers `h += Attn(RMSNorm(h)); h += MLP(RMSNorm(h))`, a
final RMSNorm, an untied head, next-token cross-entropy. By the published
layer index (`utils/config.LagunaConfig`):

  attention  q, k, v = u Wq, u Wk, u Wv without bias or q/k norm, over G KV
     heads of size D; rotary positions on q and k; causal softmax attention
     of H query heads, head h reading KV head h // (H / G); a gate a head,
     `a_h <- a_h * sigmoid(u Wg)_h`; the out-projection.
     F  full: H = `num_attention_heads`; the first `partial_rotary_factor` of
        a head's dimensions rotated (halves paired), by YaRN's frequencies,
        cos and sin times `yarn_attention_factor`.
     S  sliding: H = `num_sliding_attention_heads`; a query at t sees keys
        t - window < j <= t; every dimension rotated, default frequencies.
  MLP
     D  dense SwiGLU, `(silu(u W_gate) * (u W_up)) W_down`.
     E  a router over all the experts (float32 sigmoid scores, the k largest,
        `w_k = s_k / sum s_k * moe_routed_scaling_factor`), the terms of the
        SwiGLU experts held here, and one shared SwiGLU expert.

The stack (embedding, per-layer recomputation, blocked loss), the blocked
attention core and the whole routed part (router, sort, row ladder, grouped
products, combine) are `hybrid_lm`'s; only what a group's rows go through
differs (`hybrid_lm.SWIGLU`). XLA but for the attentions' scores, which run
in `kernels/flash_attention.py` where the shapes tile and the device is a
TPU. Parameters are float32; with a compute dtype the residual stream and
the matrix products run in it, the norms' statistics, the rotation, the
router, the softmaxes and the loss in float32. Every device op sits under
one of `tracing.spans.LAGUNA_DEVICE_PHASES`.
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from glom_tpu.models import hybrid_lm
from glom_tpu.models.hybrid_lm import (
    _cast,
    _mm,
    _mm_back,
    _mm_onto,
    blocked_attention,
    count_shapes,
    init_tree,
    next_token_loss,
    rms_norm,
    run_stack,
)
from glom_tpu.utils.config import LagunaConfig

COUNTERS = hybrid_lm.STACK_COUNTERS + ("attn_key_blocks_window", "attn_key_blocks_full",
                                       "swiglu_backward_staged")
ATTENTION_SCOPE = {"S": "window_attention", "F": "full_attention"}


# ----------------------------------------------------------------- parameters


def layer_shapes(attention: str, mlp: str, cfg: LagunaConfig) -> dict:
    """{leaf: shape} of one layer of attention kind `attention` and MLP kind
    `mlp`."""
    d, heads = cfg.hidden_size, cfg.heads(attention)
    q, kv = heads * cfg.head_dim, cfg.num_key_value_heads * cfg.head_dim
    shapes = {"norm1": (d,), "q": (d, q), "k": (d, kv), "v": (d, kv), "gate": (d, heads),
              "o": (q, d), "norm2": (d,)}
    if mlp == "D":
        f = cfg.intermediate_size
        return {**shapes, "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}
    e, f, fs = cfg.num_experts, cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size
    return {**shapes, "router": (d, cfg.num_experts_total),
            "e_gate": (e, d, f), "e_up": (e, d, f), "e_down": (e, f, d),
            "s_gate": (d, fs), "s_up": (d, fs), "s_down": (fs, d)}


def param_shapes(cfg: LagunaConfig) -> dict:
    d, v = cfg.hidden_size, cfg.vocab_size
    return {"embed": (v, d),
            "layers": tuple(layer_shapes(a, m, cfg) for a, m in cfg.kinds),
            "final_norm": (d,), "head": (d, v)}


def init_leaf(key, name: str, shape, cfg: LagunaConfig):
    """One leaf's initial value, float32: norms one, matrices normal with std
    0.02, the out-projections (attention `o`, every `*_down`) scaled by
    1/sqrt(2 x layers of the published stack)."""
    if name.startswith("norm") or name == "final_norm":
        return jnp.ones(shape, jnp.float32)
    std = 0.02
    if name == "o" or name.endswith("_down"):
        std /= math.sqrt(2.0 * cfg.num_hidden_layers_total)
    return std * jax.random.normal(key, shape, jnp.float32)


@functools.partial(jax.jit, static_argnums=(1,))
def init_laguna(key: jax.Array, cfg: LagunaConfig):
    return init_tree(key, param_shapes(cfg), init_leaf, cfg)


# --------------------------------------------------------------------- rotary


def rope_frequencies(attention: str, cfg: LagunaConfig) -> Tuple[np.ndarray, float]:
    """(inverse frequencies [rotated dimensions / 2] float32, the factor on
    cos and sin) of a layer of kind `attention`. `S`: theta^(-2j / dim), 1.
    `F`: YaRN over the rotated dimensions, as the `transformers` library's
    `yarn` type computes it: the extrapolated frequency f_e = theta^(-2j /
    dim) where a dimension turns more than `yarn_beta_fast` times within the
    original length, the interpolated f_e / `yarn_factor` where fewer than
    `yarn_beta_slow`, a linear ramp over the dimensions between; and
    `yarn_attention_factor`."""
    dim = cfg.rotary_dim(attention)
    j = np.arange(dim // 2, dtype=np.float64)
    if attention == "S":
        return (cfg.rope_theta_sliding ** (-2.0 * j / dim)).astype(np.float32), 1.0
    theta, length = cfg.rope_theta_full, cfg.yarn_original_max_position_embeddings
    extrapolated = theta ** (-2.0 * j / dim)
    turns_at = lambda turns: dim * math.log(length / (turns * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(turns_at(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(turns_at(cfg.yarn_beta_slow)), dim - 1)
    ramp = np.clip((j - low) / max(high - low, 0.001), 0.0, 1.0)
    freq = extrapolated / cfg.yarn_factor * ramp + extrapolated * (1.0 - ramp)
    return freq.astype(np.float32), cfg.yarn_attention_factor


def rotary_tables(freq: np.ndarray, factor: float, dim: int, length: int):
    """(cos, sin [length, D] float32, the factor folded in; the signed
    permutation P [D, D] with (x @ P)[i] = -x[i + half], (x @ P)[i + half] =
    x[i] over the rotated dimensions) for inverse frequencies `freq` [rotated
    dimensions / 2] in a head of `dim`. Over the dimensions that pass
    untouched cos = 1, sin = 0 and P is zero. sin is equal on paired
    dimensions and P^T = -P."""
    half = freq.shape[0]
    rotated = np.arange(dim) < 2 * half
    paired = np.zeros(dim, np.float32)
    paired[:half] = paired[half:2 * half] = freq
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * paired           # [length, D]
    cos = jnp.where(rotated, jnp.cos(angle) * factor, 1.0)
    sin = jnp.where(rotated, jnp.sin(angle) * factor, 0.0)
    perm = np.zeros((dim, dim), np.float32)
    i = np.arange(half)
    perm[i + half, i], perm[i, i + half] = -1.0, 1.0
    return cos, sin, perm


def rope_tables(attention: str, cfg: LagunaConfig, length: int):
    """`rotary_tables` of a layer of kind `attention`."""
    return rotary_tables(*rope_frequencies(attention, cfg), cfg.head_dim, length)


def _rotate(x, tables, transposed: bool):
    """x * cos + (x @ P) * sin (P^T where `transposed`) in one pass: x's type
    in and out, float32 between; `tables(length)` gives cos, sin and P. The
    product is exact in any type: each of its outputs is one input times +-1."""
    with jax.named_scope("rope"):
        cos, sin, perm = tables(x.shape[1])
        shape = (x.shape[1],) + (1,) * (x.ndim - 3) + (x.shape[-1],)
        # float32 operands at the chip's default precision would be rounded to bfloat16
        turned = jnp.matmul(x, jnp.asarray(perm.T if transposed else perm, x.dtype),
                            precision=None if x.dtype == jnp.bfloat16 else "highest",
                            preferred_element_type=jnp.float32)
        return (x.astype(jnp.float32) * cos.reshape(shape)
                + turned * sin.reshape(shape)).astype(x.dtype)


def rotate_by(x, tables):
    """Rotate x [B, T, ..., D] at positions 0..T-1 by `tables(T)`
    (`rotary_tables`): one pass, `x * cos + (x @ P) * sin` over whole heads,
    no slice and no concatenate of a head's parts, float32 inside, x's type in
    and out. Its transpose is the same pass with P^T on the cotangent, summed
    in float32 and rounded once."""

    @jax.custom_vjp
    def turn(x):
        return _rotate(x, tables, False)

    # (dy * sin) @ P^T = (dy @ P^T) * sin: sin is equal on paired dimensions
    turn.defvjp(lambda x: (_rotate(x, tables, False), None),
                lambda _, dy: (_rotate(dy, tables, True),))
    return turn(x)


def rope(x, attention: str, cfg: LagunaConfig):
    """`rotate_by` the tables of a layer of kind `attention`: of the first
    `rotary_dim` dimensions of a head, dimension i pairs with i + rotary_dim /
    2; the rest pass untouched."""
    return rotate_by(x, functools.partial(rope_tables, attention, cfg))


# ------------------------------------------------------------------ the layer


def head_gate(p, u, dtype):
    """The gate a query head on the attention's output: sigmoid(u Wg) [B, T,
    heads], from the layer's normed input."""
    return jax.nn.sigmoid(_mm(u, _cast(p["gate"], dtype))).astype(u.dtype)


def attention_mixer(attention: str, p, x_in, cfg: LagunaConfig, dtype):
    """The layer's input [B, T, d] -> (the attention's output [B, T, d], key
    blocks multiplied, 1 where the kernels ran)."""
    g, dh, heads = cfg.num_key_value_heads, cfg.head_dim, cfg.heads(attention)
    bsz, t = x_in.shape[:2]
    with jax.named_scope(ATTENTION_SCOPE[attention]):
        u = rms_norm(x_in, p["norm1"], cfg.rms_norm_eps)
        q = _mm(u, _cast(p["q"], dtype)).astype(u.dtype).reshape(bsz, t, g, heads // g, dh)
        k = _mm(u, _cast(p["k"], dtype)).astype(u.dtype).reshape(bsz, t, g, dh)
        v = _mm(u, _cast(p["v"], dtype)).astype(u.dtype).reshape(bsz, t, g, dh)
        q, k = rope(q, attention, cfg), rope(k, attention, cfg)
        a, key_blocks, on_kernels = blocked_attention(
            q, k, v, cfg.sliding_window if attention == "S" else None)
        with jax.named_scope("attn_gate"):
            a = a * head_gate(p, u, dtype).reshape(bsz, t, g, heads // g, 1)
        out = _mm(a.reshape(bsz, t, heads * dh), _cast(p["o"], dtype)).astype(u.dtype)
    return out, key_blocks, on_kernels


def _gated(gate, up):
    return jax.nn.silu(gate) * up


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def swiglu(u, w_gate, w_up, w_down, dtype):
    """`(silu(u W_gate) * (u W_up)) W_down` in u's type: gate and up leave
    their products in float32, h = silu(gate) * up is rounded to u's type
    for the down product. The weights are cast to `dtype` at each product.

    Its derivative is its own (`jax.custom_vjp`). Kept: u, the weights, gate
    and up in float32, which is what autodiff kept (under `run_stack`'s
    recomputation the two products that make them run again and the down
    product falls out). The backward holds every operand of its six products
    as an ARRAY in u's type, behind `lax.optimization_barrier`s: dy as it
    arrives (in EvaByte the float32 stream's cotangent, rounded); then dh =
    dy W_down^T, rounded as autodiff rounded it, and ONE float32 elementwise
    pass over gate, up and dh that writes h, dgate = dh * up * silu'(gate)
    and dup = dh * silu(gate). The three weights' gradients and du's two
    products read those. Without the barriers the compiler fuses each
    expression into every product as its operand's producer and evaluates it
    from float32 once for every output tile that crosses a row: two to five
    times an operand's bytes, and a `silu`, a tile (PERF.md section 7, trap
    20). A default-precision product rounds a float32 operand to bfloat16 on
    the chip anyway, so the products multiply what they multiplied; in
    float32 nothing is rounded and the gradients are autodiff's."""
    return _swiglu_fwd(u, w_gate, w_up, w_down, dtype)[0]


def _swiglu_fwd(u, w_gate, w_up, w_down, dtype):
    gate, up = _mm(u, _cast(w_gate, dtype)), _mm(u, _cast(w_up, dtype))
    out = _mm(_gated(gate, up).astype(u.dtype), _cast(w_down, dtype)).astype(u.dtype)
    return out, (u, w_gate, w_up, w_down, gate, up)


def _swiglu_bwd(dtype, kept, dy):
    u, w_gate, w_up, w_down, gate, up = kept
    back = lambda d, w: _mm_back(d, _cast(w, dtype))
    dy = jax.lax.optimization_barrier(dy)
    dh = back(dy, w_down).astype(u.dtype)
    h, pull = jax.vjp(_gated, gate, up)
    staged = (h, *pull(dh.astype(jnp.float32)))
    h, dgate, dup = jax.lax.optimization_barrier(tuple(a.astype(u.dtype) for a in staged))
    du = (back(dgate, w_gate) + back(dup, w_up)).astype(u.dtype)
    return (du, _mm_onto(u, dgate).astype(w_gate.dtype), _mm_onto(u, dup).astype(w_up.dtype),
            _mm_onto(h, dy).astype(w_down.dtype))


swiglu.defvjp(_swiglu_fwd, _swiglu_bwd)


def swiglu_backward_staged(counted):
    """The records' `swiglu_backward_staged`: the `swiglu` calls of the step,
    each of which reads staged operands in its backward. `counted` is one
    dict a layer, holding `swiglu_calls` where the layer called it."""
    return jnp.float32(sum(c.get("swiglu_calls", 0) for c in counted))


def mlp(kind: str, p, x, cfg: LagunaConfig, dtype):
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


def layer(attention: str, mlp_kind: str, p, x, cfg: LagunaConfig, dtype):
    """One layer: (x, the layer's counters, the router's choices or None)."""
    out, key_blocks, on_kernels = attention_mixer(attention, p, x, cfg, dtype)
    x = x + out
    out, counters, top_i = mlp(mlp_kind, p, x, cfg, dtype)
    name = "attn_key_blocks_window" if attention == "S" else "attn_key_blocks_full"
    return x + out, {**counters, name: key_blocks, "attn_on_kernels": on_kernels}, top_i


# ------------------------------------------------------------------ the stack


def hidden_states(params, ids, cfg: LagunaConfig, *, compute_dtype=None, remat: bool = True):
    """ids [B, T] -> (the last layer's output [B, T, d], one counters dict a
    layer, the routers' choices [E layers, B * T, k])."""

    def held(attention, mlp_kind):
        def f(p, x, side):
            x, c, top_i = layer(attention, mlp_kind, p, x, cfg, compute_dtype)
            return x, side, (c, top_i)
        return f

    x, aux = run_stack(params, ids, [held(a, m) for a, m in cfg.kinds],
                       compute_dtype=compute_dtype, remat=remat)
    return x, [c for c, _ in aux], [top_i for _, top_i in aux if top_i is not None]


def routing_choices(params, ids, cfg: LagunaConfig, *, compute_dtype=None):
    """The experts every token chose in every `E` layer: [layers, B * T, k]."""
    return jnp.stack(hidden_states(params, ids, cfg, compute_dtype=compute_dtype,
                                   remat=False)[2])


def lm_loss(params, ids, cfg: LagunaConfig, *, compute_dtype=None,
            remat: bool = True) -> Tuple[jnp.ndarray, dict]:
    """Next-token cross-entropy over the vocabulary rows held here
    (`hybrid_lm.next_token_loss`). Returns (loss, counters): the routed
    part's four over the `E` layers (`hybrid_lm.merge_counters`), the key
    blocks the window layers and the full layers multiplied this step,
    `hybrid_lm.forward_kept` and `swiglu_backward_staged`."""
    x, counted, _ = hidden_states(params, ids, cfg, compute_dtype=compute_dtype, remat=remat)
    with jax.named_scope("lm_head_loss"):
        h = rms_norm(x, params["final_norm"], cfg.rms_norm_eps).reshape(-1, x.shape[-1])
        loss = next_token_loss(h, _cast(params["head"], compute_dtype), ids)
    with jax.named_scope("step_metrics"):
        counters = hybrid_lm.merge_counters(counted)
        counters["attn_forward_kept"] = hybrid_lm.forward_kept(counted, remat)
        counters["swiglu_backward_staged"] = swiglu_backward_staged(counted)
        for name in ("attn_key_blocks_window", "attn_key_blocks_full"):
            counters[name] = jnp.float32(sum(c.get(name, 0) for c in counted))
    return loss, counters


def param_count(cfg: LagunaConfig) -> int:
    return count_shapes(param_shapes(cfg))
