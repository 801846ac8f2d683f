"""EvaByte (`model_type: evabyte`, `attention_class: eva`; EvaByte's 6.5B
byte-level model is the defaults): pre-norm residual layers over a float32
residual stream, a final norm, `num_pred_heads` byte-prediction heads.
With d the hidden size, heads of D, W the window, c the chunk, s = D^-1/2,
no bias anywhere (`utils/config.EvaByteConfig`):

    u  = x * rsqrt(mean(x^2) + eps) * (1 + w1)            norm_add_unit_offset
    q, k, v = u Wq, u Wk, u Wv   by head; q and k rotated over all D dimensions
    chunk j = positions c j .. c j + c - 1, head h, learned phi_h, mu_h in R^D:
        a_i = s (k_i . phi_h),  w = softmax_i(a)            i in chunk j
        khat_j = sum_i w_i k_i + mu_h      vhat_j = sum_i w_i v_i
    query t, n = t // W:  L(t) = {i : n W <= i <= t},  C(t) = {j : c j < n W}
        o_t = (sum_L e^{s q_t.k_i} v_i + sum_C e^{s q_t.khat_j} vhat_j)
            / (sum_L e^{s q_t.k_i}     + sum_C e^{s q_t.khat_j})
    x  = x + o Wo                          the add and the stream in float32
    x  = x + Wdown(silu(Wgate u2) * (Wup u2)),   u2 the same norm with w2
    after the stack: h = norm_f(x); logits = h Whead, float32, [T, K, V]
    loss = mean over heads m < K and positions t with t + 1 + m < T of
           CE(logits[t, m], ids[t + 1 + m])

EVA attention is exact softmax attention inside aligned windows of W
positions joined, under one normaliser, with one learned summary key and
value for every chunk of c positions of every window before the query's: not
a sliding window (a window's first query sees one key of its own and every
earlier chunk's summary), and a summary never expires. The summaries are made
of the rotated keys and are not rotated again; a query sees no summary of its
own window.

The stack (embedding, per-layer recomputation with the attention kernel's
kept output, blocked loss) is `hybrid_lm`'s; the stream is float32 because
this family hands `run_stack` no compute type and casts at each product
itself (`fp32_skip_add`: the trainer has no knob for it). XLA but for the
attention's scores: where the shapes tile and the device is a TPU both key
segments run through `kernels/flash_attention.py` under its `Aligned` mask,
in one online softmax; otherwise the XLA loop below, a block of queries at a
time. The summariser is XLA with float32 statistics. Parameters are float32;
with a compute dtype the products run in it. Every device op sits under one
of `tracing.spans.EVABYTE_DEVICE_PHASES`.
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from glom_tpu.kernels import flash_attention
from glom_tpu.models.hybrid_lm import (
    ATTN_KEY_BLOCK,
    ATTN_QUERY_BLOCK,
    _cast,
    _mm,
    count_shapes,
    init_tree,
    forward_kept,
    next_token_loss,
    rms_norm,
    run_stack,
)
from glom_tpu.models.laguna import rotary_tables, rotate_by, swiglu, swiglu_backward_staged
from glom_tpu.utils.config import EvaByteConfig

COUNTERS = ("attn_forward_kept", "attn_key_blocks_local", "attn_key_blocks_summary",
            "eva_summary_keys", "lm_pred_heads", "swiglu_backward_staged")
INIT_STD = 0.01275


# ----------------------------------------------------------------- parameters


def layer_shapes(cfg: EvaByteConfig) -> dict:
    d, f = cfg.hidden_size, cfg.intermediate_size
    h, dh = cfg.num_attention_heads, cfg.head_dim
    return {"norm1": (d,), "q": (d, h * dh), "k": (d, h * dh), "v": (d, h * dh),
            "phi": (h, dh), "mu": (h, dh), "o": (h * dh, d),
            "norm2": (d,), "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}


def param_shapes(cfg: EvaByteConfig) -> dict:
    d, v = cfg.hidden_size, cfg.vocab_size
    return {"embed": (v, d),
            "layers": tuple(layer_shapes(cfg) for _ in range(cfg.num_hidden_layers)),
            "final_norm": (d,), "head": (d, cfg.num_pred_heads * v)}


def init_leaf(key, name: str, shape, cfg: EvaByteConfig):
    """One leaf's initial value, float32: the norms' weights zero (they
    multiply by 1 + weight), matrices normal with std INIT_STD, the
    out-projections (`o`, `w_down`) scaled by 1/sqrt(2 x layers of the
    published stack), the summariser's `phi` and `mu` normal clipped to +-1."""
    if name.startswith("norm") or name == "final_norm":
        return jnp.zeros(shape, jnp.float32)
    if name in ("phi", "mu"):
        return jnp.clip(jax.random.normal(key, shape, jnp.float32), -1.0, 1.0)
    std = INIT_STD
    if name in ("o", "w_down"):
        std /= math.sqrt(2.0 * cfg.num_hidden_layers_total)
    return std * jax.random.normal(key, shape, jnp.float32)


@functools.partial(jax.jit, static_argnums=(1,))
def init_evabyte(key: jax.Array, cfg: EvaByteConfig):
    return init_tree(key, param_shapes(cfg), init_leaf, cfg)


# --------------------------------------------------------------------- pieces


def offset_norm(x, weight, eps: float, dtype):
    """RMSNorm that multiplies by 1 + weight: float32 statistics on the
    float32 stream, the result in the compute type."""
    return _cast(rms_norm(x, 1.0 + weight, eps), dtype)


def rope(x, cfg: EvaByteConfig):
    """Rotate x [B, T, H, D] at positions 0..T-1: every dimension of a head,
    dimension i paired with i + D / 2 (`laguna.rotate_by`'s one pass)."""
    dim = cfg.head_dim
    freq = (cfg.rope_theta ** (-2.0 * np.arange(dim // 2, dtype=np.float64) / dim)).astype(
        np.float32)
    return rotate_by(x, functools.partial(rotary_tables, freq, 1.0, dim))


def summarise(k, v, phi, mu, cfg: EvaByteConfig):
    """The chunks' summaries: k, v [B, T, H, D] (k rotated) -> (khat, vhat
    [B, T // c, H, D]) in k's type. One softmax a chunk a head of the scaled
    products of its c keys with `phi`, which weighs the keys and the values
    alike; `mu` is added to the weighted keys. Float32 inside. Positions past
    the last whole chunk have no summary (no query of this row would see it)."""
    bsz, t, h, d = k.shape
    c = cfg.chunk_size
    n = t // c
    f32 = jnp.float32
    kc = k[:, :n * c].reshape(bsz, n, c, h, d).astype(f32)
    vc = v[:, :n * c].reshape(bsz, n, c, h, d).astype(f32)
    a = jnp.sum(kc * phi, axis=-1) * d ** -0.5                     # [B, n, c, H]
    w = jax.nn.softmax(a, axis=2)[..., None]
    return ((jnp.sum(w * kc, axis=2) + mu).astype(k.dtype),
            jnp.sum(w * vc, axis=2).astype(v.dtype))


def _attend_block(q, k, v, khat, vhat, first: int):
    """One block of queries of one window, the block's first query at
    position `first` of the window, against the window's keys up to the
    block's end (k, v [B, first + tq, H, D]) and the summaries of the windows
    before (khat, vhat [B, n, H, D], every one seen), in one softmax."""
    scale = q.shape[-1] ** -0.5
    keys, values = jnp.concatenate([k, khat], axis=1), jnp.concatenate([v, vhat], axis=1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, keys, preferred_element_type=jnp.float32) * scale
    qpos = first + jnp.arange(q.shape[1])[:, None]
    kpos = jnp.arange(keys.shape[1])[None, :]
    s = jnp.where((kpos <= qpos) | (kpos >= k.shape[1]), s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", pr, values, preferred_element_type=jnp.float32
                      ).astype(q.dtype)


def eva_attention(q, k, v, khat, vhat, cfg: EvaByteConfig):
    """EVA attention: q, k, v [B, T, H, D], khat, vhat [B, T // c, H, D] ->
    ([B, T, H, D], key blocks of ATTN_KEY_BLOCK own keys multiplied, of
    summary keys, and 1 where the kernels ran, else 0).

    Where the shapes tile (`flash_attention.tiles` under the `Aligned` mask)
    and the device is a TPU, the two key segments are laid end to end and the
    Pallas kernels visit the tiles some query sees, scores in VMEM, one
    online softmax; the gradient of the summary rows comes back with the
    keys'. Otherwise ATTN_QUERY_BLOCK queries of one window at a time through
    XLA, each block recomputed in the backward pass."""
    bsz, t, h, d = q.shape
    w, c = cfg.window_size, cfg.chunk_size
    mask = flash_attention.Aligned(w, c, t)
    tiled = t % c == 0 and flash_attention.tiles(t, 1, d, d, mask)
    if tiled and flash_attention.on_tpu():
        tq, tk = tiled
        out = flash_attention.flash_attention(
            q[:, :, :, None], jnp.concatenate([k, khat], axis=1),
            jnp.concatenate([v, vhat], axis=1), mask, tq=tq, tk=tk)
        return (out[:, :, :, 0],) + flash_attention.aligned_key_blocks(
            tq, tk, mask, ATTN_KEY_BLOCK) + (1,)
    out, own_blocks, summary_blocks = [], 0, 0
    rows = min(ATTN_QUERY_BLOCK, w)
    for first in range(0, t, rows):
        last = min(t, first + rows)
        start = first // w * w
        block = jax.checkpoint(functools.partial(_attend_block, first=first - start))
        out.append(block(q[:, first:last], k[:, start:last], v[:, start:last],
                         khat[:, :start // c], vhat[:, :start // c]))
        own_blocks += -(-(last - start) // ATTN_KEY_BLOCK)
        summary_blocks += -(-(start // c) // ATTN_KEY_BLOCK)
    return jnp.concatenate(out, axis=1), own_blocks, summary_blocks, 0


# ------------------------------------------------------------------ the layer


def attention_mixer(p, x, cfg: EvaByteConfig, dtype):
    """The stream x [B, T, d] float32 -> (x + the attention's output, key
    blocks of own keys multiplied, of summary keys, 1 where the kernels ran)."""
    h, dh = cfg.num_attention_heads, cfg.head_dim
    bsz, t = x.shape[:2]
    with jax.named_scope("eva_in"):
        u = offset_norm(x, p["norm1"], cfg.rms_norm_eps, dtype)
        by_head = lambda name: _mm(u, _cast(p[name], dtype)).astype(u.dtype).reshape(bsz, t, h, dh)
        q, k, v = rope(by_head("q"), cfg), rope(by_head("k"), cfg), by_head("v")
    with jax.named_scope("eva_summary"):
        khat, vhat = summarise(k, v, p["phi"], p["mu"], cfg)
    with jax.named_scope("eva_attention"):
        a, own_blocks, summary_blocks, on_kernels = eva_attention(q, k, v, khat, vhat, cfg)
    with jax.named_scope("eva_out"):
        out = _mm(a.reshape(bsz, t, h * dh), _cast(p["o"], dtype)).astype(u.dtype)
        return x + out, own_blocks, summary_blocks, on_kernels


def layer(p, x, cfg: EvaByteConfig, dtype):
    """One layer on the float32 stream: (x, the layer's counters)."""
    x, own_blocks, summary_blocks, on_kernels = attention_mixer(p, x, cfg, dtype)
    with jax.named_scope("dense_mlp"):
        u2 = offset_norm(x, p["norm2"], cfg.rms_norm_eps, dtype)
        x = x + swiglu(u2, p["w_gate"], p["w_up"], p["w_down"], dtype)
    return x, {"attn_key_blocks_local": own_blocks, "attn_key_blocks_summary": summary_blocks,
               "attn_on_kernels": on_kernels, "swiglu_calls": 1}


# ------------------------------------------------------------------ the stack


def hidden_states(params, ids, cfg: EvaByteConfig, *, compute_dtype=None, remat: bool = True):
    """ids [B, T] -> (the last layer's output [B, T, d] float32, one counters
    dict a layer). `run_stack` is given no compute type: the embedding's rows
    and the stream stay float32, and a layer casts at its products."""

    def held(p, x, side):
        x, c = layer(p, x, cfg, compute_dtype)
        return x, side, c

    return run_stack(params, ids, [held] * cfg.num_hidden_layers, remat=remat)


def normed(params, x, cfg: EvaByteConfig, compute_dtype):
    return offset_norm(x, params["final_norm"], cfg.rms_norm_eps, compute_dtype).reshape(
        -1, x.shape[-1])


def logits(params, ids, cfg: EvaByteConfig, *, compute_dtype=None):
    """[B, T, K, V] float32: for the tests of the mask and of causality."""
    x, _ = hidden_states(params, ids, cfg, compute_dtype=compute_dtype, remat=False)
    out = _mm(normed(params, x, cfg, compute_dtype), _cast(params["head"], compute_dtype))
    return out.reshape(*ids.shape, cfg.num_pred_heads, cfg.vocab_size)


def lm_loss(params, ids, cfg: EvaByteConfig, *, compute_dtype=None,
            remat: bool = True) -> Tuple[jnp.ndarray, dict]:
    """The mean cross-entropy of the `num_pred_heads` heads, head m of
    position t predicting the byte at t + 1 + m (`hybrid_lm.next_token_loss`).
    Returns (loss, counters): the key blocks of own keys and of summary keys
    the layers multiplied this step, the summary keys they formed, the
    prediction heads, the layers whose recomputation reads the attention
    forward kernel's kept output (`hybrid_lm.forward_kept`'s count), and
    `laguna.swiglu_backward_staged`."""
    x, counted = hidden_states(params, ids, cfg, compute_dtype=compute_dtype, remat=remat)
    with jax.named_scope("lm_head_loss"):
        loss = next_token_loss(normed(params, x, cfg, compute_dtype),
                               _cast(params["head"], compute_dtype), ids, cfg.num_pred_heads)
    with jax.named_scope("step_metrics"):
        total = lambda name: jnp.float32(sum(c[name] for c in counted))
        counters = {
            "attn_forward_kept": forward_kept(counted, remat),
            "attn_key_blocks_local": total("attn_key_blocks_local"),
            "attn_key_blocks_summary": total("attn_key_blocks_summary"),
            "eva_summary_keys": jnp.float32(
                len(counted) * ids.shape[0] * (ids.shape[1] // cfg.chunk_size)),
            "lm_pred_heads": jnp.float32(cfg.num_pred_heads),
            "swiglu_backward_staged": swiglu_backward_staged(counted),
        }
    return loss, counters


def param_count(cfg: EvaByteConfig) -> int:
    return count_shapes(param_shapes(cfg))
