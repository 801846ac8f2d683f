"""The plain reference of the Laguna language model (`model_type: laguna`):
forward pass, next-token loss, gradients and Adam in straightforward float32
`jax.numpy`.

Written from the published configuration's layer equations (below; the
configuration file lists every reading that was assumed), not from the
program: no kernel, no sort, no grouped product and no row ladder (every
expert held runs over every token of a block, weighted by what the router
gave it, zero where it was not chosen), no query tile tied to a kernel, whole
rows of scores against all T keys under a mask. It imports nothing of the
program and takes nothing the program has made. Matrix products run at
`precision="highest"`, or, for the control that `correct` has to fail, with
both operands rounded to a lower type first.

A sequence goes through the stack a layer at a time: the forward pass keeps
each layer's input, the backward pass recomputes one layer and takes its
gradient. Inside a layer the scores are held a head and QUERY_BLOCK queries
at a time and the experts EXPERT_ROWS tokens at a time, by `lax.map` (a
Python loop of checkpointed blocks compiles for minutes), each block
recomputed in the backward pass; so two sequences of 8,192 tokens at the
published widths fit beside the float32 parameters and their gradient.

Weights are a flat dict: `embed`, `final_norm`, `head`, and `L<i>.<leaf>` for
layer i of those held (`layer_kinds`). Layer i of the published stack, with
H_i query heads (full: `num_attention_heads`, sliding:
`num_sliding_attention_heads`), G KV heads, head size D, positions p = 0..T-1:

  u  = RMSNorm(h)                                    eps, weight
  q  = u Wq [T, H_i, D];  k = u Wk [T, G, D];  v = u Wv [T, G, D]
  q, k = rotate(q, k, p)
     full:    the first r = D x partial_rotary_factor dimensions of a head,
              x_i paired with x_{i + r/2}; inverse frequencies over r (YaRN):
              f_e = theta^(-2j/r), f_i = f_e / factor,
              low, high = floor, ceil of r ln(L / (b 2 pi)) / (2 ln theta) at
              b = beta_fast, beta_slow (L the original length), clamped to
              [0, r - 1]; ramp_j = clip((j - low) / (high - low), 0, 1);
              f_j = f_i ramp_j + f_e (1 - ramp_j); cos and sin both times
              attention_factor
     sliding: all D dimensions, f_j = theta_s^(-2j/D)
  a  = softmax over the keys j <= t (sliding: and j > t - window) of
       (q k^T / sqrt(D)) v;  head h reads KV head h // (H_i / G)
  a  = a * sigmoid(u Wg) a head;  h = h + flatten(a) Wo
  u2 = RMSNorm(h)
  dense:   h = h + (silu(u2 W_gate) * (u2 W_up)) W_down
  experts: s = sigmoid(float32(u2) W_r); the k largest; w_k = s_k / sum s_k *
           scaling;  h = h + sum over the experts held of w_e E_e(u2) +
           E_shared(u2), every E a SwiGLU
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.nemotron_h_ref import (
    _frozen,
    _mm_f32,
    adam_update,
    layer_weights,
    leaf_norms,
    rms_norm,
    rounding_in,
    silu,
)
from benchmark.reference.sambay_ref import change_compared  # noqa: F401  (the drivers' rule)

QUERY_BLOCK = 1024  # queries of one head whose scores are held at a time
EXPERT_ROWS = 1024  # tokens that go through all the experts held at a time
LOSS_ROWS = 2048    # rows of logits held at a time


def layer_kinds(model: dict) -> list:
    """(attention, MLP) letters of the layers held, in order."""
    held = slice(model["layer_offset"], model["layer_offset"] + model["num_hidden_layers"])
    return list(zip(model["layer_types"][held], model["mlp_layer_types"][held]))


def query_heads(attention: str, model: dict) -> int:
    return model["num_attention_heads" if attention == "F" else "num_sliding_attention_heads"]


# --------------------------------------------------------------------- rotary


def inverse_frequencies(attention: str, model: dict):
    """(f_j over the rotated dimensions' pairs, float64; the factor on cos
    and sin)."""
    d = model["head_dim"]
    if attention == "S":
        return model["rope_theta_sliding"] ** (-2.0 * np.arange(d // 2) / d), 1.0
    r = int(d * model["partial_rotary_factor"])
    theta, length = model["rope_theta_full"], model["yarn_original_max_position_embeddings"]
    j = np.arange(r // 2, dtype=np.float64)
    f_e = theta ** (-2.0 * j / r)
    f_i = f_e / model["yarn_factor"]
    edge = lambda b: r * math.log(length / (b * 2.0 * math.pi)) / (2.0 * math.log(theta))
    low = max(math.floor(edge(model["yarn_beta_fast"])), 0)
    high = min(math.ceil(edge(model["yarn_beta_slow"])), r - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((j - low) / (high - low), 0.0, 1.0)
    return f_i * ramp + f_e * (1.0 - ramp), model["yarn_attention_factor"]


def rotate(x, attention: str, model: dict):
    """x [T, heads, D] at positions 0..T-1."""
    f, factor = inverse_frequencies(attention, model)
    half = f.shape[0]
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(f, jnp.float32)
    cos, sin = jnp.cos(angle)[:, None, :] * factor, jnp.sin(angle)[:, None, :] * factor
    x1, x2 = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., 2 * half:]], axis=-1)


# ------------------------------------------------------------------ the layer


def attention(kind: str, w, u, model, rnd):
    """u [T, d] -> [T, d]: one query head after another (`lax.map`), and
    within a head one block of QUERY_BLOCK queries after another against all
    T keys."""
    hq, g, dh = query_heads(kind, model), model["num_key_value_heads"], model["head_dim"]
    t = u.shape[0]
    q = _mm_f32("tk,kn->tn", rnd(u), rnd(w["q"])).reshape(t, hq, dh)
    k = _mm_f32("tk,kn->tn", rnd(u), rnd(w["k"])).reshape(t, g, dh)
    v = _mm_f32("tk,kn->tn", rnd(u), rnd(w["v"])).reshape(t, g, dh)
    q, k = rotate(q, kind, model), rotate(k, kind, model)
    window = model["sliding_window"] if kind == "S" else None
    kpos = jnp.arange(t)[None, :]
    n_blocks = -(-t // QUERY_BLOCK)

    def one_head(head):
        q_h, k_h, v_h = head                         # [T, D] x 3

        @jax.checkpoint  # one block of scores at a time, recomputed in the backward pass
        def block(rows):
            q_rows, first = rows
            # rows past T are padding: they look from the last position and are dropped
            qpos = jnp.minimum(first + jnp.arange(QUERY_BLOCK), t - 1)[:, None]
            seen = kpos <= qpos
            if window is not None:
                seen &= kpos > qpos - window
            scores = _mm_f32("qd,kd->qk", rnd(q_rows), rnd(k_h)) / math.sqrt(dh)
            pr = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
            return _mm_f32("qk,kd->qd", rnd(pr), rnd(v_h))

        q_blocks = jnp.pad(q_h, ((0, n_blocks * QUERY_BLOCK - t), (0, 0))).reshape(
            n_blocks, QUERY_BLOCK, dh)
        out = jax.lax.map(block, (q_blocks, jnp.arange(n_blocks) * QUERY_BLOCK))
        return out.reshape(n_blocks * QUERY_BLOCK, dh)[:t]

    kv_head = jnp.arange(hq) // (hq // g)
    by_head = lambda x: jnp.moveaxis(x, 1, 0)                     # [heads, T, D]
    a = jax.lax.map(one_head, (by_head(q), by_head(k)[kv_head], by_head(v)[kv_head]))
    gate = jax.nn.sigmoid(_mm_f32("tk,kn->tn", rnd(u), rnd(w["gate"])))   # [T, heads]
    a = jnp.moveaxis(a, 0, 1) * gate[:, :, None]
    return _mm_f32("tk,kn->tn", rnd(a.reshape(t, hq * dh)), rnd(w["o"]))


def swiglu(u, w_gate, w_up, w_down, rnd):
    h = silu(_mm_f32("tk,kn->tn", rnd(u), rnd(w_gate))) * _mm_f32("tk,kn->tn", rnd(u), rnd(w_up))
    return _mm_f32("tk,kn->tn", rnd(h), rnd(w_down))


def router(w, u, model):
    """(experts chosen [T, k], weights [T, k]); float32 whatever the
    precision of the rest."""
    s = jax.nn.sigmoid(_mm_f32("tk,kn->tn", u, w["router"]))
    top_s, top_i = jax.lax.top_k(s, model["num_experts_per_tok"])
    return top_i, (top_s / jnp.sum(top_s, axis=-1, keepdims=True)
                   * model["moe_routed_scaling_factor"])


def moe_routed(w, u, model, rnd):
    """The part of the routed sum that the experts held here give: every
    expert held over every token, weighted by the router (0 where the token
    did not choose it), EXPERT_ROWS tokens at a time."""
    top_i, top_w = router(w, u, model)
    t, d = u.shape
    held = model["expert_offset"] + jnp.arange(model["num_experts"])
    # weight[t, e]: the router's weight of expert e for token t, 0 where not chosen
    weight = jnp.sum(jnp.where(top_i[:, :, None] == held[None, None, :], top_w[:, :, None], 0.0),
                     axis=1)
    n_blocks = -(-t // EXPERT_ROWS)
    pad = lambda x: jnp.pad(x, ((0, n_blocks * EXPERT_ROWS - t), (0, 0))).reshape(
        n_blocks, EXPERT_ROWS, x.shape[1])

    @jax.checkpoint
    def block(rows):
        u_rows, weight_rows = rows
        gate = _mm_f32("tk,ekf->etf", rnd(u_rows), rnd(w["e_gate"]))
        up = _mm_f32("tk,ekf->etf", rnd(u_rows), rnd(w["e_up"]))
        y = _mm_f32("etf,efd->etd", rnd(silu(gate) * up), rnd(w["e_down"]))
        return jnp.einsum("etd,te->td", y, weight_rows, precision="highest")

    out = jax.lax.map(block, (pad(u), pad(weight)))
    return out.reshape(n_blocks * EXPERT_ROWS, d)[:t], top_i


def layer(kinds, w, x, model, precision="float32"):
    """One layer on one sequence: x [T, d] -> (x, the router's choices [T, k]
    or None)."""
    attn_kind, mlp_kind = kinds
    rnd = rounding_in(precision)
    eps = model["rms_norm_eps"]
    x = x + attention(attn_kind, w, rms_norm(x, w["norm1"], eps), model, rnd)
    u2 = rms_norm(x, w["norm2"], eps)
    if mlp_kind == "D":
        return x + swiglu(u2, w["w_gate"], w["w_up"], w["w_down"], rnd), None
    routed, top_i = moe_routed(w, u2, model, rnd)
    return x + routed + swiglu(u2, w["s_gate"], w["s_up"], w["s_down"], rnd), top_i


def head_loss(w_norm, w_head, x, ids, model, precision="float32"):
    """Sum over the sequence's T - 1 positions that have a next token of the
    cross-entropy of that token, float32 logits."""
    rnd = rounding_in(precision)
    h = rms_norm(x, w_norm, model["rms_norm_eps"])[:-1]
    targets = ids[1:]

    @jax.checkpoint
    def rows(h_rows, t_rows):
        logits = _mm_f32("tk,kn->tn", rnd(h_rows), rnd(w_head))
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1)
                       - jnp.take_along_axis(logits, t_rows[:, None], axis=-1)[:, 0])

    return sum(rows(h[i:i + LOSS_ROWS], targets[i:i + LOSS_ROWS])
               for i in range(0, h.shape[0], LOSS_ROWS))


# ------------------------------------------- a layer at a time, all sequences


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def _layer_fwd(kinds, w, xs, model_items, precision):
    model = dict(model_items)
    return jax.vmap(lambda x: layer(kinds, w, x, model, precision))(xs)


@functools.partial(jax.jit, static_argnums=(0, 4, 5))
def _layer_bwd(kinds, w, xs, gs, model_items, precision):
    model = dict(model_items)
    f = lambda w, xs: jax.vmap(lambda x: layer(kinds, w, x, model, precision)[0])(xs)
    return jax.vjp(f, w, xs)[1](gs)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _head(w_norm, w_head, xs, ids, model_items, precision):
    model = dict(model_items)
    n = ids.shape[0] * (ids.shape[1] - 1)

    def f(w_norm, w_head, xs):
        return jnp.sum(jax.vmap(lambda x, i: head_loss(w_norm, w_head, x, i, model,
                                                       precision))(xs, ids)) / n

    return jax.value_and_grad(f, argnums=(0, 1, 2))(w_norm, w_head, xs)


def forward(w: dict, ids, model: dict, *, precision="float32", keep=None):
    """ids [B, T] -> (the last layer's output [B, T, d], the routers' choices
    [E layers][B, T, k]). `keep`, a list, receives every layer's input."""
    items = _frozen(model)
    xs = w["embed"][ids]
    chosen = []
    for i, kinds in enumerate(layer_kinds(model)):
        if keep is not None:
            keep.append(xs)
        xs, top_i = _layer_fwd(kinds, layer_weights(w, i), xs, items, precision)
        if top_i is not None:
            chosen.append(top_i)
    return xs, chosen


def logits(w: dict, ids, model: dict, *, precision="float32"):
    """[B, T, V]: for the tests of causality and of the vocabulary's shares."""
    xs, _ = forward(w, ids, model, precision=precision)
    h = rms_norm(xs, w["final_norm"], model["rms_norm_eps"])
    rnd = rounding_in(precision)
    return _mm_f32("btk,kn->btn", rnd(h), rnd(w["head"]))


def loss_and_grads(w: dict, ids, model: dict, *, precision="float32"):
    """(loss, gradient as a flat dict like `w`, the routers' choices)."""
    items = _frozen(model)
    keep = []
    xs, chosen = forward(w, ids, model, precision=precision, keep=keep)
    loss, (g_norm, g_head, gs) = _head(w["final_norm"], w["head"], xs, ids, items, precision)
    grads = {"final_norm": g_norm, "head": g_head}
    kinds = layer_kinds(model)
    for i in reversed(range(len(kinds))):
        g_w, gs = _layer_bwd(kinds[i], layer_weights(w, i), keep.pop(), gs, items, precision)
        grads.update({f"L{i:02d}.{k}": v for k, v in g_w.items()})
    grads["embed"] = jnp.zeros_like(w["embed"]).at[ids].add(gs)
    return loss, grads, chosen


def train_reference(make_w0, batches, model: dict, *, lr, precision="float32") -> dict:
    """Follow the first len(batches) training steps from the weights
    `make_w0()` gives (a callable, so that no second copy of the initial
    weights is held while the steps run). Returns each step's loss, the first
    gradient, its per-leaf norms and the root mean square of its entries
    (what `change_compared` reads), the per-leaf norms of the parameters'
    change over all the steps, and the first step's routing choices
    [E layers, B, T, k]."""
    with jax.default_matmul_precision("highest"):
        w = make_w0()
        mu = nu = None   # Adam's moments wait on the host while the layers run
        losses, out = [], {}
        for t, ids in enumerate(batches, start=1):
            loss, grads, chosen = loss_and_grads(w, jnp.asarray(ids), model, precision=precision)
            losses.append(float(loss))
            if t == 1:
                out["first_grad_norms"] = {k: float(v) for k, v in leaf_norms(grads).items()}
                out["first_grad"] = {k: np.asarray(v, np.float32) for k, v in grads.items()}
                out["first_grad_rms"] = {
                    k: float(np.sqrt(np.mean(np.square(v, dtype=np.float64))))
                    for k, v in out["first_grad"].items()}
                out["choices"] = np.stack([np.asarray(c) for c in chosen]) if chosen else None
            zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, w)
            moments = (zeros(), zeros()) if mu is None else jax.device_put((mu, nu))
            w, mu, nu = adam_update(w, *moments, grads, jnp.float32(t), jnp.float32(lr))
            del grads
            mu, nu = jax.device_get((mu, nu)) if t < len(batches) else (None, None)
        out["losses"] = losses
        out["delta_norms"] = {k: float(v) for k, v in leaf_norms(w, make_w0()).items()}
    return out
