"""Ouro (`model_type: ouro`; ByteDance's Ouro-2.6B is the defaults; "Scaling
Latent Reasoning via Looped Language Models", arXiv:2510.25741): a looped
language model. ONE stack of L layers runs `total_ut_steps` = T_max times
over the same weights; a layer is a sandwich of four norms round causal
attention and a dense SwiGLU; the final norm closes every pass; an exit gate
reads each pass's closed state. With `N(x; g) = x * rsqrt(mean(x^2) + eps) *
g`, H heads of D over G KV heads, no bias anywhere
(`utils/config.OuroConfig`):

    h(0) = E[ids]
    for t = 1..T_max:                            the SAME L layers' weights at every t
        x = h(t-1)
        for l = 0..L-1:
            u = N(x; g1_l);  q, k, v = u Wq, u Wk, u Wv;  q, k rotated (halves
                paired, every dimension, positions 0..T-1 at every t)
            a = causal softmax(q k^T / sqrt(D)) v;   x = x + N(a Wo; g2_l)
            m = Wdown(silu(Wgate N(x; g3_l)) * Wup N(x; g3_l));   x = x + N(m; g4_l)
        h(t) = N(x; g_f)         what the head reads AND what pass t + 1 starts from
        z(t) = h(t) W_head (float32);   lambda_t = sigmoid(h(t) . w + b)    a scalar a position
    p(1) = lambda_1;  p(t) = lambda_t prod_{j<t}(1 - lambda_j), t < T_max;
    p(T_max) = prod_{j<T_max}(1 - lambda_j)                                  (sums to 1)
    loss = mean over the positions i that have a next token of
           sum_t p_i(t) CE(z_i(t), ids_{i+1}) - beta H(p_i),   H(p) = -sum_t p(t) log p(t)

Keys and values are made anew from each pass's stream; pass t + 1 reads
nothing of pass t but h(t). The gate's w and b are `gate_w` [d] and `gate_b`
[1] in the parameters; the last pass's lambda is read by nothing.

The stack is `hybrid_lm.run_stack` with `passes` and `close` (a `lax.scan`
over one body of the L layers and the closing norm): a looped weight is one
leaf of the parameters and every one of the T_max x L layer applications is
recomputed on its own, reading the attention kernel's kept output where the
kernels ran. The loss is `hybrid_lm.next_token_loss` over the passes' states,
each position of each weighed by p(t). XLA but for the attention's scores
(`hybrid_lm.blocked_attention`: `kernels/flash_attention.py` where the shapes
tile and the device is a TPU). Parameters are float32; with a compute dtype
the stream and the products run in it, the norms' statistics, the rotation,
the gate, the exit distribution and the loss in float32. Every device op sits
under one of `tracing.spans.OURO_DEVICE_PHASES`.
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp

from glom_tpu.models.evabyte import rope  # every dimension of a head, halves paired
from glom_tpu.models.hybrid_lm import (
    _cast,
    _mm,
    blocked_attention,
    count_shapes,
    forward_kept,
    init_tree,
    next_token_loss,
    rms_norm,
    run_stack,
)
from glom_tpu.models.laguna import swiglu, swiglu_backward_staged
from glom_tpu.utils.config import OuroConfig

COUNTERS = ("ut_steps", "layer_applications", "attn_forward_kept", "attn_key_blocks_full",
            "exit_entropy", "exit_mass_last", "swiglu_backward_staged")


# ----------------------------------------------------------------- parameters


def layer_shapes(cfg: OuroConfig) -> dict:
    d, f = cfg.hidden_size, cfg.intermediate_size
    q, kv = cfg.num_attention_heads * cfg.head_dim, cfg.num_key_value_heads * cfg.head_dim
    return {"norm1": (d,), "q": (d, q), "k": (d, kv), "v": (d, kv), "o": (q, d), "norm2": (d,),
            "norm3": (d,), "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d), "norm4": (d,)}


def param_shapes(cfg: OuroConfig) -> dict:
    d, v = cfg.hidden_size, cfg.vocab_size
    return {"embed": (v, d),
            "layers": tuple(layer_shapes(cfg) for _ in range(cfg.num_hidden_layers)),
            "final_norm": (d,), "head": (d, v), "gate_w": (d,), "gate_b": (1,)}


def init_leaf(key, name: str, shape, cfg: OuroConfig):
    """One leaf's initial value, float32: norms one, the gate's bias zero,
    matrices (and the gate's direction) normal with std 0.02, the
    out-projections (`o`, `w_down`) scaled by 1/sqrt(2 x layers of the
    published stack)."""
    if name.startswith("norm") or name == "final_norm":
        return jnp.ones(shape, jnp.float32)
    if name == "gate_b":
        return jnp.zeros(shape, jnp.float32)
    std = 0.02
    if name in ("o", "w_down"):
        std /= math.sqrt(2.0 * cfg.num_hidden_layers_total)
    return std * jax.random.normal(key, shape, jnp.float32)


@functools.partial(jax.jit, static_argnums=(1,))
def init_ouro(key: jax.Array, cfg: OuroConfig):
    return init_tree(key, param_shapes(cfg), init_leaf, cfg)


# ------------------------------------------------------------------ the layer


def layer(p, x, cfg: OuroConfig, dtype):
    """One application of one layer: (x, the application's counters)."""
    g, dh = cfg.num_key_value_heads, cfg.head_dim
    r = cfg.num_attention_heads // g
    bsz, t = x.shape[:2]
    eps = cfg.rms_norm_eps
    with jax.named_scope("ouro_in"):
        u = rms_norm(x, p["norm1"], eps)
        by_head = lambda name, *heads: _mm(u, _cast(p[name], dtype)).astype(u.dtype).reshape(
            bsz, t, *heads, dh)
        q, k, v = rope(by_head("q", g, r), cfg), rope(by_head("k", g), cfg), by_head("v", g)
    with jax.named_scope("full_attention"):
        a, key_blocks, on_kernels = blocked_attention(q, k, v)
    with jax.named_scope("ouro_out"):
        out = _mm(a.reshape(bsz, t, g * r * dh), _cast(p["o"], dtype)).astype(u.dtype)
    with jax.named_scope("sandwich_norm"):
        x = x + rms_norm(out, p["norm2"], eps)
    with jax.named_scope("dense_mlp"):
        m = swiglu(rms_norm(x, p["norm3"], eps), p["w_gate"], p["w_up"], p["w_down"], dtype)
    with jax.named_scope("sandwich_norm"):
        x = x + rms_norm(m, p["norm4"], eps)
    return x, {"attn_key_blocks_full": key_blocks, "attn_on_kernels": on_kernels,
               "swiglu_calls": 1}


# ------------------------------------------------------------------- the loop


def hidden_states(params, ids, cfg: OuroConfig, *, compute_dtype=None, remat: bool = True):
    """ids [B, T] -> (the closed states h(t) [total_ut_steps, B, T, d], one
    counters dict a layer application, in the order run)."""

    def held(p, x, side):
        x, c = layer(p, x, cfg, compute_dtype)
        return x, side, c

    def close(x):
        with jax.named_scope("ut_close"):
            return rms_norm(x, params["final_norm"], cfg.rms_norm_eps)

    # the loop's own ops (the copies that stack a pass's kept arrays for the backward pass, the
    # trip count) open no scope inside the scan: they count with what ends a pass
    with jax.named_scope("ut_close"):
        return run_stack(params, ids, [held] * cfg.num_hidden_layers,
                         compute_dtype=compute_dtype, remat=remat, passes=cfg.total_ut_steps,
                         close=close)


def exit_distribution(gate_logits):
    """The gate's logits [T_max, ...] float32 (lambda = their sigmoid) ->
    (log p, the same shape): a position leaves at pass t with lambda_t of
    what has not left before, and the last pass takes what is left (its own
    lambda is not read). In logarithms: log(1 - sigmoid(z)) = log_sigmoid(-z)."""
    stays = jnp.cumsum(jax.nn.log_sigmoid(-gate_logits), axis=0)       # log prod_{j<=t}(1 - l_j)
    before = jnp.concatenate([jnp.zeros_like(stays[:1]), stays[:-1]])  # log prod_{j<t}
    return jnp.concatenate([before[:-1] + jax.nn.log_sigmoid(gate_logits[:-1]), before[-1:]])


def gate_logits(params, closed):
    """h(t) . w + b of every closed state [T_max, B, T, d]: [T_max, B * T] float32."""
    h = closed.reshape(closed.shape[0], -1, closed.shape[-1]).astype(jnp.float32)
    return jnp.sum(h * params["gate_w"], axis=-1) + params["gate_b"][0]


def logits(params, ids, cfg: OuroConfig, *, compute_dtype=None):
    """[T_max, B, T, V] float32: for the tests of causality."""
    closed, _ = hidden_states(params, ids, cfg, compute_dtype=compute_dtype, remat=False)
    return _mm(closed, _cast(params["head"], compute_dtype))


def exit_weighed_loss(params, closed, ids, cfg: OuroConfig, compute_dtype=None):
    """The closed states h(t) [T_max, B, T, d] -> (loss, the exit
    distribution's mean entropy, its mean mass on the last pass), each mean
    over the positions that have a next token: the passes' cross-entropies
    (`hybrid_lm.next_token_loss` over all the passes' states, `weights` p(t)) less
    `exit_entropy_beta` times the entropy."""
    bsz, t = ids.shape
    has_next = jnp.tile(jnp.arange(t) < t - 1, bsz)
    mean = lambda a: jnp.sum(jnp.where(has_next, a, 0.0)) / (bsz * (t - 1))
    with jax.named_scope("exit_gate"):
        log_p = exit_distribution(gate_logits(params, closed))
        p = jnp.exp(log_p)
        entropy = mean(-jnp.sum(p * log_p, axis=0))
    with jax.named_scope("lm_head_loss"):
        loss = next_token_loss(closed.reshape(-1, closed.shape[-1]),
                               _cast(params["head"], compute_dtype), ids, weights=p.reshape(-1))
    with jax.named_scope("exit_gate"):
        return loss - cfg.exit_entropy_beta * entropy, entropy, mean(p[-1])


def lm_loss(params, ids, cfg: OuroConfig, *, compute_dtype=None,
            remat: bool = True) -> Tuple[jnp.ndarray, dict]:
    """`exit_weighed_loss` of the loop's closed states. Returns (loss,
    counters): the passes, the layer applications, those whose recomputation
    reads the attention forward kernel's kept output
    (`hybrid_lm.forward_kept`), the key blocks they multiplied, the mean
    entropy of the exit distribution and its mean mass on the last pass, and
    `laguna.swiglu_backward_staged`."""
    closed, counted = hidden_states(params, ids, cfg, compute_dtype=compute_dtype, remat=remat)
    loss, entropy, mass_last = exit_weighed_loss(params, closed, ids, cfg, compute_dtype)
    with jax.named_scope("step_metrics"):
        counters = {
            "ut_steps": jnp.float32(len(closed)),
            "layer_applications": jnp.float32(len(counted)),
            "attn_forward_kept": forward_kept(counted, remat),
            "attn_key_blocks_full": jnp.float32(sum(c["attn_key_blocks_full"] for c in counted)),
            "exit_entropy": entropy,
            "exit_mass_last": mass_last,
            "swiglu_backward_staged": swiglu_backward_staged(counted),
        }
    return loss, counters


def param_count(cfg: OuroConfig) -> int:
    return count_shapes(param_shapes(cfg))
