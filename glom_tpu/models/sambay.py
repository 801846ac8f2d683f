"""SambaY, the decoder-hybrid-decoder language model of
Phi-4-mini-flash-reasoning (`model_type: phi4flash`): pre-norm residual
layers `h += mixer(LN(h)); h += MLP(LN(h))` with LayerNorm (weight and bias),
a SwiGLU MLP, a final LayerNorm, a head tied to the embedding, no positional
encoding. The mixer of published layer `i` of N is `config.layer_kind`'s:

  M  Mamba-1, even i <= N/2: `[x, z] = u W_in`; `x = SiLU(conv(x))`;
     `[r, B, C] = x W_x`; `dt = softplus(r W_dt + b_dt)`; `A = -exp(A_log)`;
     `s_t = exp(dt_t A) s_{t-1} + (dt_t x_t) B_t^T`; `y_t = s_t C_t + D x_t`;
     out `(y * SiLU(z)) W_out`. Layer N/2 hands `y` on as the memory.
  W  differential attention under a window of `sliding_window` keys (the
     query's own among them), odd i < N/2: heads in pairs, `(softmax(q1 k1^T)
     - lambda softmax(q2 k2^T)) V` with a pair's values side by side, an
     RMSNorm over the pair's width, `(1 - lambda_init)`, `W_o`; biases on the
     projections.
  F  the same attention, causal and full, i = N/2 + 1; hands its keys and
     values on.
  G  Gated Memory Unit, even i > N/2 + 1: `(SiLU(u W_in) * memory) W_out`.
  X  cross-attention, odd i > N/2 + 1: its own queries, lambdas, norm and
     `W_o` over layer N/2 + 1's keys and values.

The stack (embedding, per-layer recomputation, blocked loss), the blocked
attention core and the causal conv are `hybrid_lm`'s; what a layer hands the
layers after it travels as `run_stack`'s `side`. XLA but for the attentions'
scores where the shapes tile (`kernels/flash_attention.py`; the route still
goes by `vjp_path: lm_xla`). Parameters are float32; with a compute dtype
the residual stream and the matrix products run in it, the norms'
statistics, the step `dt`, the recurrence's state, the softmaxes and the
loss in float32. Every device op sits under one of
`tracing.spans.SAMBAY_DEVICE_PHASES`.
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp

from glom_tpu.kernels import selective_scan as scan_kernels
from glom_tpu.models.hybrid_lm import init_leaf as hybrid_init_leaf
from glom_tpu.models.hybrid_lm import (
    _cast,
    _mm,
    _mm_back,
    _mm_onto,
    blocked_attention,
    causal_conv,
    count_shapes,
    forward_kept,
    init_tree,
    next_token_loss,
    rms_norm,
    run_stack,
)
from glom_tpu.utils.config import SambaYConfig

COUNTERS = ("attn_key_blocks_window", "attn_key_blocks_full", "scan_chunks",
            "attn_forward_kept", "scan_on_kernels", "mlp_backward_staged")
# The selective scan's schedule in its XLA form alone (the kernels' blocks are
# `kernels/selective_scan.blocks`): positions a carried state (a chunk,
# recomputed whole in the backward pass), and positions a segment (a chunk's
# segments are scanned side by side).
SCAN_CHUNK = 1024
SCAN_SEGMENT = 32
ATTENTION_SCOPE = {"W": "window_attention", "F": "full_attention", "X": "cross_attention"}


# ----------------------------------------------------------------- parameters


def layer_shapes(kind: str, cfg: SambaYConfig) -> dict:
    """{leaf: shape} of one layer of mixer `kind`."""
    d, f, di = cfg.hidden_size, cfg.intermediate_size, cfg.mamba_inner
    q = cfg.num_attention_heads * cfg.head_dim
    kv = cfg.num_key_value_heads * cfg.head_dim
    diff = {f"lambda_{name}": (cfg.head_dim,) for name in ("q1", "k1", "q2", "k2")}
    diff.update({"subln": (2 * cfg.head_dim,), "o": (q, d), "o_b": (d,)})
    mixer = {
        "M": {"in_proj": (d, 2 * di), "conv_w": (di, cfg.mamba_d_conv), "conv_b": (di,),
              "x_proj": (di, cfg.mamba_dt_rank + 2 * cfg.mamba_d_state),
              "dt_proj": (cfg.mamba_dt_rank, di), "dt_bias": (di,),
              "A_log": (di, cfg.mamba_d_state), "D": (di,), "out_proj": (di, d)},
        "W": {"qkv": (d, q + 2 * kv), "qkv_b": (q + 2 * kv,), **diff},
        "G": {"in_proj": (d, di), "out_proj": (di, d)},
        "X": {"q": (d, q), "q_b": (q,), **diff},
    }
    mixer["F"] = mixer["W"]
    return {"norm1_w": (d,), "norm1_b": (d,), **mixer[kind],
            "norm2_w": (d,), "norm2_b": (d,), "gate_up": (d, 2 * f), "down": (f, d)}


def param_shapes(cfg: SambaYConfig) -> dict:
    d = cfg.hidden_size
    return {"embed": (cfg.vocab_size, d),
            "layers": tuple(layer_shapes(kind, cfg) for kind in cfg.kinds),
            "final_norm_w": (d,), "final_norm_b": (d,)}


def init_leaf(key, name: str, shape, cfg: SambaYConfig):
    """One leaf's initial value, float32. Matrices are normal with std 0.02,
    the out-projections of every mixer and MLP scaled by 1/sqrt(2 x layers of
    the published stack); norms' weights one; biases zero; the lambda vectors
    normal with std 0.1 (the differential transformer's); the conv, the step
    projection, `dt_bias`, `A_log` (the log of 1..d_state in every channel)
    and `D` as the Mamba-1 reference initialises them."""
    if name in ("norm1_w", "norm2_w", "final_norm_w", "subln", "D"):
        return jnp.ones(shape, jnp.float32)
    if name.endswith("_b"):
        return jnp.zeros(shape, jnp.float32)
    if name.startswith("lambda_"):
        return 0.1 * jax.random.normal(key, shape, jnp.float32)
    if name == "conv_w":
        bound = cfg.mamba_d_conv ** -0.5
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    if name == "dt_proj":
        bound = cfg.mamba_dt_rank ** -0.5
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    if name == "dt_bias":
        return hybrid_init_leaf(key, name, shape, cfg)  # the same family, by `time_step_*`
    if name == "A_log":
        return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32)), shape)
    std = 0.02
    if name in ("out_proj", "o", "down"):
        std /= math.sqrt(2.0 * cfg.num_hidden_layers_total)
    return std * jax.random.normal(key, shape, jnp.float32)


@functools.partial(jax.jit, static_argnums=(1,))
def init_sambay(key: jax.Array, cfg: SambaYConfig):
    return init_tree(key, param_shapes(cfg), init_leaf, cfg)


def param_count(cfg: SambaYConfig) -> int:
    return count_shapes(param_shapes(cfg))


# --------------------------------------------------------------------- pieces


def layer_norm(x, weight, bias, eps: float):
    x32 = x.astype(jnp.float32)
    centred = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    y = centred * jax.lax.rsqrt(jnp.mean(jnp.square(centred), axis=-1, keepdims=True) + eps)
    return (y * weight + bias).astype(x.dtype)


def lambda_init(index: int) -> float:
    """The differential attention's constant, by the published layer index."""
    return 0.8 - 0.6 * math.exp(-0.3 * index)


def _gated(gate, up):
    return jax.nn.silu(gate) * up


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def gated_mlp(u, w_gate_up, w_down, dtype):
    """`(silu(gate) * up) W_down` in u's type, `[gate, up] = u W_gate_up`
    rounded to u's type as it leaves the one joined product, h = silu(gate) *
    up in u's type. The weights are cast to `dtype` at each product.

    Its derivative is its own, of the shape of `laguna.swiglu`'s and
    `hybrid_lm.relu2_mlp`'s. Kept: u, the weights and gate_up in u's type,
    which is what autodiff kept (under `run_stack`'s recomputation the joined
    product runs again and the down product falls out). The backward holds
    the operands of its products as ARRAYS in u's type, behind
    `lax.optimization_barrier`s: dy as it arrives; then dh = dy W_down^T,
    rounded as autodiff rounded it, and ONE elementwise pass over gate_up and
    dh that writes h, dgate and dup. dW_down = h^T dy, du = [dgate, dup]
    W_gate_up^T and dW_gate_up = u^T [dgate, dup] read those. Without the
    barrier the compiler fuses `silu(gate) * up` into dW_down's product as its
    operand's producer and evaluates it from gate_up once for every output
    tile that crosses a row (PERF.md section 7, trap 20). The pass is
    autodiff's own arithmetic in u's type, so no operand is rounded that was
    not, and the gradients are autodiff's to a product's summation order."""
    return _gated_mlp_fwd(u, w_gate_up, w_down, dtype)[0]


def _gated_mlp_fwd(u, w_gate_up, w_down, dtype):
    gate_up = _mm(u, _cast(w_gate_up, dtype)).astype(u.dtype)
    out = _mm(_gated(*jnp.split(gate_up, 2, axis=-1)), _cast(w_down, dtype)).astype(u.dtype)
    return out, (u, w_gate_up, w_down, gate_up)


def _gated_mlp_bwd(dtype, kept, dy):
    u, w_gate_up, w_down, gate_up = kept
    dy = jax.lax.optimization_barrier(dy)
    dh = _mm_back(dy, _cast(w_down, dtype)).astype(u.dtype)
    h, pull = jax.vjp(_gated, *jnp.split(gate_up, 2, axis=-1))
    h, dgate, dup = jax.lax.optimization_barrier((h, *pull(dh)))
    # joined after the barrier: the products fold the join into their operand,
    # where a joined array behind the barrier is a pass of its own
    d_gate_up = jnp.concatenate([dgate, dup], axis=-1)
    return (_mm_back(d_gate_up, _cast(w_gate_up, dtype)).astype(u.dtype),
            _mm_onto(u, d_gate_up).astype(w_gate_up.dtype), _mm_onto(h, dy).astype(w_down.dtype))


gated_mlp.defvjp(_gated_mlp_fwd, _gated_mlp_bwd)


def mlp_backward_staged(counted):
    """The records' `mlp_backward_staged`: the layers of the step, each of
    whose MLP reads staged operands in its backward (`gated_mlp`). `counted`
    is one dict a layer, holding `gated_mlp_calls` where the layer called it."""
    return jnp.float32(sum(c.get("gated_mlp_calls", 0) for c in counted))


def mlp(p, x_in, cfg: SambaYConfig, dtype):
    with jax.named_scope("mlp"):
        u = layer_norm(x_in, p["norm2_w"], p["norm2_b"], cfg.layer_norm_eps)
        return gated_mlp(u, p["gate_up"], p["down"], dtype)


# -------------------------------------------------------------------- Mamba-1


def scan_kernel_blocks(t: int, channels: int, states: int):
    """The (time block, channel block) at which the Pallas kernels run
    `selective_scan`'s recurrence, or None where the XLA form runs it: off a
    TPU, and at a width that does not tile (`kernels/selective_scan.blocks`)."""
    tiled = scan_kernels.blocks(t, channels, states)
    return tiled if tiled and scan_kernels.on_tpu() else None


def selective_scan(x, dt, a, b, c):
    """The selective state-space recurrence of Mamba-1,
        s_t = exp(dt_t A) s_{t-1} + (dt_t x_t) B_t^T,   y_t = s_t C_t,
    with a decay of its own for every channel and state: x [B, T, C], dt
    [B, T, C] float32, a [C, N] float32 (negative), b and c [B, T, N]. Returns
    (y [B, T, C] in x's type, the number of chunks). Float32 state, exp,
    products and sums on both paths; x, b and c are read in their own type
    and widened.

    On a TPU, with the channels a whole number of 128-lane registers and the
    states of 8 sublanes (`scan_kernel_blocks`), the two kernels of
    `kernels/selective_scan.py` run it a position at a time with the state [N,
    channel block] in VMEM for the whole pass; a chunk is a time block of
    theirs. For the backward they keep the inputs and the state entering
    every time block ([T / block, B, N, C] float32) and make a block's states
    again in VMEM. A length that is no whole number of time blocks is padded
    with steps of dt = 0, which neither decay the state nor add to it.

    Anywhere else (the CPU, a width that does not tile) the XLA form below
    runs, which is also what the kernels are tested against. A `lax.scan` over
    chunks of SCAN_CHUNK positions carries the state [B, N, C]
    (states before channels: the channels fill the lanes) and recomputes a
    chunk whole in the backward pass, so that what is kept of the [T, N, C]
    states is one a chunk. Within a chunk its segments of SCAN_SEGMENT positions
    are scanned side by side from a zero state (that many steps over [B,
    segments, N, C]); a second short scan carries the states over the
    segments' ends, and what the state entering a segment gives each of its
    positions, C_t . (exp(A sum_{u <= t} dt_u) * s), is added. The length is
    padded to whole chunks likewise."""
    tiled = scan_kernel_blocks(x.shape[1], x.shape[2], a.shape[1])
    if tiled:
        t, (block, channel_block) = x.shape[1], tiled
        pad = -t % block
        if pad:
            x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad), (0, 0))) for v in (x, dt, b, c))
        y = scan_kernels.selective_scan(x, dt, a, b, c, time_block=block,
                                        channel_block=channel_block)
        return y[:, :t], (t + pad) // block
    chunk, segment = SCAN_CHUNK, SCAN_SEGMENT
    if chunk % segment:
        raise ValueError(f"a chunk of {chunk} positions is no whole number of segments "
                         f"of {segment}")
    bsz, t, ch = x.shape
    n = a.shape[1]
    chunk = min(chunk, -(-t // segment) * segment)
    pad = -t % chunk
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad), (0, 0))) for v in (x, dt, b, c))
    n_chunks, n_seg = (t + pad) // chunk, chunk // segment
    at = a.T                                                   # [N, C]

    def by_chunk(v):  # [B, T, F] -> [chunks, segment, B, segments, F]
        return v.reshape(bsz, n_chunks, n_seg, segment, -1).transpose(1, 3, 0, 2, 4)

    def one_chunk(s_in, inp):
        dt, dtx, b, c = inp

        def step(h, at_t):                                     # h [B, segments, N, C]
            dt_t, dtx_t, b_t, c_t = at_t
            h = jnp.exp(dt_t[..., None, :] * at) * h + b_t[..., :, None] * dtx_t[..., None, :]
            return h, jnp.sum(h * c_t[..., :, None], axis=-2)

        h0 = jnp.zeros((bsz, n_seg, n, ch), jnp.float32)
        h_end, y = jax.lax.scan(step, h0, (dt, dtx, b, c))     # y [segment, B, segments, C]
        since = jnp.cumsum(dt, axis=0)                         # the segment's dt so far

        def over_ends(s, seg):                                 # s [B, N, C]
            total, h = seg
            return jnp.exp(total[:, None, :] * at) * s + h, s

        s_out, entering = jax.lax.scan(
            over_ends, s_in, (jnp.moveaxis(since[-1], 1, 0), jnp.moveaxis(h_end, 1, 0)))
        entering = jnp.moveaxis(entering, 0, 1)                # [B, segments, N, C]
        y = y + jnp.sum(c[..., :, None] * jnp.exp(since[..., None, :] * at) * entering, axis=-2)
        return s_out, y

    x32 = x.astype(jnp.float32)
    inputs = tuple(by_chunk(v) for v in (dt, dt * x32, b.astype(jnp.float32),
                                         c.astype(jnp.float32)))
    _, y = jax.lax.scan(jax.checkpoint(one_chunk), jnp.zeros((bsz, n, ch), jnp.float32), inputs)
    y = y.transpose(2, 0, 3, 1, 4).reshape(bsz, t + pad, ch)
    return y[:, :t].astype(x.dtype), n_chunks


def mamba_mixer(p, x_in, cfg: SambaYConfig, dtype):
    """The layer's input [B, T, d] -> (the mixer's output [B, T, d], the
    scan's output before the gate [B, T, 2d], the scan's chunks, 1 where the
    scan ran the kernels)."""
    di, n, r = cfg.mamba_inner, cfg.mamba_d_state, cfg.mamba_dt_rank
    with jax.named_scope("mamba_in"):
        u = layer_norm(x_in, p["norm1_w"], p["norm1_b"], cfg.layer_norm_eps)
        x, z = jnp.split(_mm(u, _cast(p["in_proj"], dtype)).astype(u.dtype), 2, axis=-1)
        x = jax.nn.silu(causal_conv(x, _cast(p["conv_w"], dtype), _cast(p["conv_b"], dtype)))
        low, b, c = jnp.split(_mm(x, _cast(p["x_proj"], dtype)).astype(u.dtype), [r, r + n],
                              axis=-1)
        dt = jax.nn.softplus(_mm(low, _cast(p["dt_proj"], dtype)) + p["dt_bias"])  # float32
        a = -jnp.exp(p["A_log"])
    with jax.named_scope("selective_scan"):
        y, chunks = selective_scan(x, dt, a, b, c)
        on_kernels = int(scan_kernel_blocks(x.shape[1], di, n) is not None)
    with jax.named_scope("mamba_out"):
        y = y + _cast(p["D"], dtype) * x
        out = _mm(y * jax.nn.silu(z), _cast(p["out_proj"], dtype)).astype(u.dtype)
    return out, y, chunks, on_kernels


def gmu_mixer(p, x_in, memory, cfg: SambaYConfig, dtype):
    with jax.named_scope("gmu"):
        u = layer_norm(x_in, p["norm1_w"], p["norm1_b"], cfg.layer_norm_eps)
        gate = jax.nn.silu(_mm(u, _cast(p["in_proj"], dtype)).astype(u.dtype))
        return _mm(gate * memory, _cast(p["out_proj"], dtype)).astype(u.dtype)


# --------------------------------------------------- differential attention


def differential_attention(p, q, k, v, cfg: SambaYConfig, index: int, dtype, window=None):
    """q [B, T, heads x D], k and v [B, T, KV heads x D] -> ([B, T, d], key
    blocks multiplied, 1 where the kernels ran). Heads pair up in their
    order: query heads (2j, 2j+1) are pair j's (q1, q2), KV heads (2g, 2g+1)
    pair g's (k1, k2), whose values lie side by side; query pair j reads KV
    pair j // (pairs a KV pair). Both softmaxes go through one
    `blocked_attention`: KV head (g, s) is read by the members s of its
    pair's query pairs, against the pair's values, which it is handed once a
    pair."""
    hq, hkv, dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    bsz, t = q.shape[:2]
    pairs, per = hkv // 2, hq // hkv
    q = q.reshape(bsz, t, pairs, per, 2, dh).swapaxes(3, 4).reshape(bsz, t, hkv, per, dh)
    k = k.reshape(bsz, t, hkv, dh)
    a, key_blocks, on_kernels = blocked_attention(q, k, v.reshape(bsz, t, pairs, 2 * dh), window)
    a = a.reshape(bsz, t, pairs, 2, per, 2 * dh)
    lam0 = lambda_init(index)
    lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
           - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + lam0)
    o = a[:, :, :, 0] - lam.astype(a.dtype) * a[:, :, :, 1]    # [B, T, pairs, per, 2 D]
    o = rms_norm(o, p["subln"], cfg.layer_norm_eps) * (1.0 - lam0)
    out = _mm(o.reshape(bsz, t, hq * dh).astype(q.dtype), _cast(p["o"], dtype))
    return (out + p["o_b"]).astype(q.dtype), key_blocks, on_kernels


def attention_mixer(kind: str, index: int, p, x_in, shared_kv, cfg: SambaYConfig, dtype):
    """`W`, `F` (own keys and values) or `X` (`shared_kv`'s). Returns (the
    mixer's output, the keys and values it read, key blocks multiplied, 1
    where the kernels ran)."""
    q_width = cfg.num_attention_heads * cfg.head_dim
    kv_width = cfg.num_key_value_heads * cfg.head_dim
    with jax.named_scope(ATTENTION_SCOPE[kind]):
        u = layer_norm(x_in, p["norm1_w"], p["norm1_b"], cfg.layer_norm_eps)
        if kind == "X":
            q = (_mm(u, _cast(p["q"], dtype)) + p["q_b"]).astype(u.dtype)
            k, v = shared_kv
        else:
            qkv = (_mm(u, _cast(p["qkv"], dtype)) + p["qkv_b"]).astype(u.dtype)
            q, k, v = jnp.split(qkv, [q_width, q_width + kv_width], axis=-1)
        out, key_blocks, on_kernels = differential_attention(
            p, q, k, v, cfg, index, dtype, cfg.sliding_window if kind == "W" else None)
    return out, (k, v), key_blocks, on_kernels


# ------------------------------------------------------------------ the stack


def layer(kind: str, index: int, p, x, side, cfg: SambaYConfig, dtype):
    """One layer, published index `index`, as `run_stack` calls it: `side`
    is (the memory or None, the shared keys and values or None). Returns
    (x, side, the layer's counters)."""
    memory, shared_kv = side
    n = cfg.num_hidden_layers_total
    counters = {"gated_mlp_calls": 1}
    if kind == "M":
        out, y, chunks, on_kernels = mamba_mixer(p, x, cfg, dtype)
        counters.update(scan_chunks=chunks, scan_on_kernels=on_kernels)
        if index == n // 2:
            memory = y
    elif kind == "G":
        out = gmu_mixer(p, x, memory, cfg, dtype)
    else:
        out, kv, key_blocks, on_kernels = attention_mixer(
            kind, index, p, x, shared_kv, cfg, dtype)
        counters["attn_key_blocks_window" if kind == "W" else "attn_key_blocks_full"] = key_blocks
        counters["attn_on_kernels"] = on_kernels
        if index == n // 2 + 1:
            shared_kv = kv
    x = x + out
    return x + mlp(p, x, cfg, dtype), (memory, shared_kv), counters


def hidden_states(params, ids, cfg: SambaYConfig, *, compute_dtype=None, remat: bool = True):
    """ids [B, T] -> (the last layer's output [B, T, d], one counters dict a
    layer: what the layer's own loops counted as they ran)."""
    layers = [functools.partial(layer, kind, cfg.layer_offset + i, cfg=cfg, dtype=compute_dtype)
              for i, kind in enumerate(cfg.kinds)]
    return run_stack(params, ids, layers, compute_dtype=compute_dtype, remat=remat,
                     side=(None, None))


def lm_loss(params, ids, cfg: SambaYConfig, *, compute_dtype=None,
            remat: bool = True) -> Tuple[jnp.ndarray, dict]:
    """Next-token cross-entropy over the vocabulary rows held here, under the
    tied embedding (`hybrid_lm.next_token_loss`). Returns (loss, counters):
    the key blocks the window layers and the full-length layers (`F`, `X`)
    multiplied this step, the chunks of the recurrence a Mamba layer ran, the
    Mamba layers whose recurrence ran the kernels, `hybrid_lm.forward_kept`
    and `mlp_backward_staged`."""
    x, counted = hidden_states(params, ids, cfg, compute_dtype=compute_dtype, remat=remat)
    with jax.named_scope("lm_head_loss"):
        h = layer_norm(x, params["final_norm_w"], params["final_norm_b"],
                       cfg.layer_norm_eps).reshape(-1, x.shape[-1])
        loss = next_token_loss(h, _cast(params["embed"], compute_dtype).T, ids)
    with jax.named_scope("step_metrics"):
        of = lambda name: jnp.stack([jnp.float32(c.get(name, 0)) for c in counted])
        counters = {"attn_key_blocks_window": jnp.sum(of("attn_key_blocks_window")),
                    "attn_key_blocks_full": jnp.sum(of("attn_key_blocks_full")),
                    "scan_chunks": jnp.max(of("scan_chunks")),
                    "scan_on_kernels": jnp.sum(of("scan_on_kernels")),
                    "attn_forward_kept": forward_kept(counted, remat),
                    "mlp_backward_staged": mlp_backward_staged(counted)}
    return loss, counters
