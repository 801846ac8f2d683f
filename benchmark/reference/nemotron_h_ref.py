"""The plain reference of the `nemotron_h` hybrid language model: forward
pass, next-token loss, gradients and Adam in straightforward float32
`jax.numpy`.

Written from the published layer equations (PERF.md section 4 lists them and
every size that was assumed), not from the program: no chunked scan (the
Mamba-2 recurrence runs a step at a time), no sort and no grouped product (a
Python loop over the experts held, each over all the tokens, weighted by
what the router gave), no query blocks tied to a kernel, no cache. It
imports nothing of the program and takes nothing the program has made,
except, where the caller asks for it, the program's routing choices
(`choices=`), so that a gradient comparison is not decided by which of two
nearly equal router scores rounding put first. Matrix products run at
`precision="highest"`, or, for the control that `correct` has to fail, with
both operands rounded to a lower type first.

A sequence goes through the stack a layer at a time: the forward pass keeps
each layer's input, the backward pass recomputes one layer and takes its
gradient, so that 8,192 tokens at the published widths fit beside the
float32 parameters, their gradient and Adam's two moments.

Weights are a flat dict: `embed`, `final_norm`, `head`, and `L<i>.<leaf>` for
layer i of the pattern held (`layer_kinds`).

Layers (pre-norm residual, x <- x + Mixer(RMSNorm(x)), eps from the config):
  M  [z | xBC | dt] = u W_in; xBC <- SiLU(causal depthwise conv_k(xBC) + b);
     x, B, C = split(xBC); dt <- softplus(dt + dt_bias); A = -exp(A_log);
     h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T; y_t = h_t C_t + D x_t;
     y <- RMSNorm within each group of (y * SiLU(z)); out = y W_out.
  *  grouped-query causal attention, scale 1/sqrt(head size), no positions.
  E  s = sigmoid(float32(u) W_r); the k largest; w_k = s_k / sum_k s_k * scale;
     v = u W_down; routed = (sum over the experts held of w_e relu2(v W1_e) W2_e) W_up;
     shared = relu2(u S1) S2; out = routed + shared.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
SCAN_BLOCK = 128   # steps of the recurrence between two kept states
LOSS_ROWS = 2048   # rows of logits held at a time

# ---------------------------------------------------------------- matmuls


def _mm_f32(eq, a, b):
    return jnp.einsum(eq, a, b, precision="highest", preferred_element_type=jnp.float32)


def _round_to(x, dtype):
    """Round a float32 tensor to `dtype`'s grid and back (float8 with a
    per-tensor scale to its largest finite value), straight-through for
    gradients: the products see rounded operands forward and backward."""
    if dtype == jnp.bfloat16:
        q = x.astype(jnp.bfloat16).astype(jnp.float32)
    else:
        top = float(jnp.finfo(dtype).max)
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
        q = (x / scale).astype(dtype).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def rounding_in(precision: str):
    """What an operand of a product goes through first: nothing in
    "float32", rounding to the type's grid in "bfloat16" or "float8"."""
    if precision == "float32":
        return lambda x: x
    dtype = {"bfloat16": jnp.bfloat16, "float8": jnp.float8_e4m3fn}[precision]
    return functools.partial(_round_to, dtype=dtype)


# ----------------------------------------------------------------- pieces


def layer_kinds(model: dict) -> str:
    first = model["layer_offset"]
    return model["hybrid_override_pattern"][first:first + model["num_hidden_layers"]]


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def silu(x):
    return x * jax.nn.sigmoid(x)


def _recurrence(x, dt, a, b, c):
    """h_t = exp(dt_t a) h_{t-1} + dt_t x_t b_t^T, y_t = h_t c_t, a step at
    a time. x [T, H, P], dt [T, H], a [H], b and c [T, H, N] (a head's
    group's). Blocks of steps are recomputed in the backward pass so that
    only one state a block is kept."""
    t = x.shape[0]
    pad = -t % SCAN_BLOCK
    xs = [jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1)) for v in (x, dt, b, c)]
    xs = [v.reshape(-1, SCAN_BLOCK, *v.shape[1:]) for v in xs]

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        h = (jnp.exp(dt_t * a)[:, None, None] * h
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return h, jnp.sum(h * c_t[:, None, :], axis=-1)

    @jax.checkpoint
    def block(h, inp):
        return jax.lax.scan(step, h, inp)

    h0 = jnp.zeros((x.shape[1], x.shape[2], b.shape[-1]), jnp.float32)
    _, y = jax.lax.scan(block, h0, tuple(xs))
    return y.reshape(-1, *y.shape[2:])[:t]


def mamba(w, u, model, rnd):
    """u [T, d] -> [T, d]."""
    heads, p = model["mamba_num_heads"], model["mamba_head_dim"]
    g, n, k = model["n_groups"], model["ssm_state_size"], model["conv_kernel"]
    di = heads * p
    t = u.shape[0]
    zxbcdt = _mm_f32("tk,kn->tn", rnd(u), rnd(w["in_proj"]))
    z, xbc, dt = zxbcdt[:, :di], zxbcdt[:, di:2 * di + 2 * g * n], zxbcdt[:, 2 * di + 2 * g * n:]
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), jnp.float32), xbc])
    conv = w["conv_b"] + sum(padded[j:j + t] * w["conv_w"][:, j] for j in range(k))
    xbc = silu(conv)
    x = xbc[:, :di].reshape(t, heads, p)
    b = xbc[:, di:di + g * n].reshape(t, g, n)
    c = xbc[:, di + g * n:].reshape(t, g, n)
    dt = jax.nn.softplus(dt + w["dt_bias"])
    a = -jnp.exp(w["A_log"])
    per_head = lambda v: jnp.repeat(v, heads // g, axis=1)   # a head reads its group's B and C
    y = _recurrence(rnd(x), dt, a, per_head(rnd(b)), per_head(rnd(c)))
    y = y + w["D"][:, None] * x
    y = (y.reshape(t, di) * silu(z)).reshape(t, g, di // g)
    y = rms_norm(y, w["gnorm"].reshape(g, di // g), model["layer_norm_epsilon"])
    return _mm_f32("tk,kn->tn", rnd(y.reshape(t, di)), rnd(w["out_proj"]))


def attention(w, u, model, rnd):
    hq, hkv, dh = model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"]
    t = u.shape[0]
    q = _mm_f32("tk,kn->tn", rnd(u), rnd(w["q"])).reshape(t, hq, dh)
    k = _mm_f32("tk,kn->tn", rnd(u), rnd(w["k"])).reshape(t, hkv, dh)
    v = _mm_f32("tk,kn->tn", rnd(u), rnd(w["v"])).reshape(t, hkv, dh)
    causal = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint  # one head's [T, T] scores at a time, recomputed in the backward pass
    def head(q_h, k_h, v_h):
        s = _mm_f32("qd,kd->qk", rnd(q_h), rnd(k_h)) * dh ** -0.5
        pr = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return _mm_f32("qk,kd->qd", rnd(pr), rnd(v_h))

    out = [head(q[:, i], k[:, i // (hq // hkv)], v[:, i // (hq // hkv)]) for i in range(hq)]
    return _mm_f32("tk,kn->tn", rnd(jnp.concatenate(out, axis=-1)), rnd(w["o"]))


def router(w, u, model, choices=None):
    """(experts chosen [T, k], weights [T, k]); the scores in float32
    whatever the precision of the rest. `choices` replaces the selection,
    not the scores."""
    s = jax.nn.sigmoid(_mm_f32("tk,kn->tn", u, w["router"]))
    if choices is None:
        top_s, top_i = jax.lax.top_k(s, model["num_experts_per_tok"])
    else:
        top_i = choices
        top_s = jnp.take_along_axis(s, top_i, axis=-1)
    return top_i, top_s / jnp.sum(top_s, axis=-1, keepdims=True) * model["routed_scaling_factor"]


def moe_routed(w, u, model, rnd, choices=None):
    """The part of the routed sum that the experts held here give."""
    top_i, top_w = router(w, u, model, choices)
    v = _mm_f32("tk,kn->tn", rnd(u), rnd(w["down"]))
    latent = jnp.zeros_like(v)
    for e in range(model["n_routed_experts"]):
        chose = top_i == model["expert_offset"] + e                     # [T, k]
        weight = jnp.sum(jnp.where(chose, top_w, 0.0), axis=-1)        # 0 where not chosen
        h = relu2(_mm_f32("tk,kn->tn", rnd(v), rnd(w["w1"][e])))
        latent = latent + weight[:, None] * _mm_f32("tk,kn->tn", rnd(h), rnd(w["w2"][e]))
    return _mm_f32("tk,kn->tn", rnd(latent), rnd(w["up"])), top_i


def moe_shared(w, u, rnd):
    h = relu2(_mm_f32("tk,kn->tn", rnd(u), rnd(w["s1"])))
    return _mm_f32("tk,kn->tn", rnd(h), rnd(w["s2"]))


def layer(kind: str, w, x, model, precision="float32", choices=None):
    """One layer on one sequence: x [T, d] -> (x, the router's choices or
    None)."""
    rnd = rounding_in(precision)
    u = rms_norm(x, w["norm"], model["layer_norm_epsilon"])
    if kind == "M":
        return x + mamba(w, u, model, rnd), None
    if kind == "*":
        return x + attention(w, u, model, rnd), None
    routed, top_i = moe_routed(w, u, model, rnd, choices)
    return x + routed + moe_shared(w, u, rnd), top_i


def head_loss(w_norm, w_head, x, ids, model, precision="float32"):
    """Sum over the sequence's T - 1 positions that have a next token of the
    cross-entropy of that token, float32 logits."""
    rnd = rounding_in(precision)
    h = rms_norm(x, w_norm, model["layer_norm_epsilon"])[:-1]
    targets = ids[1:]

    @jax.checkpoint
    def rows(h_rows, t_rows):
        logits = _mm_f32("tk,kn->tn", rnd(h_rows), rnd(w_head))
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1)
                       - jnp.take_along_axis(logits, t_rows[:, None], axis=-1)[:, 0])

    return sum(rows(h[i:i + LOSS_ROWS], targets[i:i + LOSS_ROWS])
               for i in range(0, h.shape[0], LOSS_ROWS))


# ------------------------------------------- a layer at a time, all sequences


def _frozen(model: dict):
    return tuple(sorted((k, v) for k, v in model.items()
                        if isinstance(v, (int, float, bool, str))))


def layer_weights(w: dict, i: int) -> dict:
    prefix = f"L{i:02d}."
    return {k[len(prefix):]: v for k, v in w.items() if k.startswith(prefix)}


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def _layer_fwd(kind, w, xs, model_items, precision, choices):
    model = dict(model_items)
    f = lambda x, ch: layer(kind, w, x, model, precision, ch)
    return jax.vmap(f)(xs, choices) if choices is not None else jax.vmap(
        lambda x: f(x, None))(xs)


@functools.partial(jax.jit, static_argnums=(0, 4, 5))
def _layer_bwd(kind, w, xs, gs, model_items, precision, choices):
    model = dict(model_items)

    def f(w, xs):
        one = lambda x, ch: layer(kind, w, x, model, precision, ch)[0]
        return jax.vmap(one)(xs, choices) if choices is not None else jax.vmap(
            lambda x: one(x, None))(xs)

    return jax.vjp(f, w, xs)[1](gs)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _head(w_norm, w_head, xs, ids, model_items, precision):
    model = dict(model_items)
    n = ids.shape[0] * (ids.shape[1] - 1)

    def f(w_norm, w_head, xs):
        return jnp.sum(jax.vmap(lambda x, i: head_loss(w_norm, w_head, x, i, model,
                                                       precision))(xs, ids)) / n

    return jax.value_and_grad(f, argnums=(0, 1, 2))(w_norm, w_head, xs)


def forward(w: dict, ids, model: dict, *, precision="float32", choices=None, keep=None):
    """ids [B, T] -> (the last layer's output [B, T, d], the routers'
    choices [E layers, B, T, k]). `keep`, a list, receives every layer's
    input. `choices` [E layers, B, T, k] replaces the routers' selections."""
    items = _frozen(model)
    xs = w["embed"][ids]
    chosen, e = [], 0
    for i, kind in enumerate(layer_kinds(model)):
        if keep is not None:
            keep.append(xs)
        ch = choices[e] if kind == "E" and choices is not None else None
        xs, top_i = _layer_fwd(kind, layer_weights(w, i), xs, items, precision, ch)
        if kind == "E":
            chosen.append(top_i)
            e += 1
    return xs, chosen


def loss_and_grads(w: dict, ids, model: dict, *, precision="float32", choices=None):
    """(loss, gradient as a flat dict like `w`, the routers' choices)."""
    items = _frozen(model)
    keep = []
    xs, chosen = forward(w, ids, model, precision=precision, choices=choices, keep=keep)
    loss, (g_norm, g_head, gs) = _head(w["final_norm"], w["head"], xs, ids, items, precision)
    grads = {"final_norm": g_norm, "head": g_head}
    kinds = layer_kinds(model)
    e = kinds.count("E")
    for i in reversed(range(len(kinds))):
        ch = None
        if kinds[i] == "E":
            e -= 1
            ch = chosen[e]  # the backward pass recomputes the layer with the forward's choices
        g_w, gs = _layer_bwd(kinds[i], layer_weights(w, i), keep.pop(), gs, items, precision, ch)
        grads.update({f"L{i:02d}.{k}": v for k, v in g_w.items()})
    grads["embed"] = jnp.zeros_like(w["embed"]).at[ids].add(gs)
    return loss, grads, chosen


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def adam_update(w, mu, nu, grads, t, lr):
    """One step of Adam (Kingma & Ba) with bias correction; t counts from 1."""
    mu = jax.tree_util.tree_map(lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g, mu, grads)
    nu = jax.tree_util.tree_map(lambda v, g: ADAM_B2 * v + (1 - ADAM_B2) * g * g, nu, grads)
    c1, c2 = 1 - ADAM_B1 ** t, 1 - ADAM_B2 ** t
    w = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + ADAM_EPS), w, mu, nu)
    return w, mu, nu


@jax.jit
def leaf_norms(tree, minus=None):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v if minus is None else v - minus[k])))
            for k, v in tree.items()}


def train_reference(make_w0, batches, model: dict, *, lr, precision="float32",
                    first_choices=None) -> dict:
    """Follow the first len(batches) training steps from the weights
    `make_w0()` gives (a callable, so that no second copy of the initial
    weights is held while the steps run: it is called again at the end for
    the parameters' change). Returns each step's loss, the first gradient
    and its per-leaf norms, the per-leaf norms of the parameters' change over
    all the steps, and the first step's routing choices [E layers, B, T, k].
    `first_choices` replaces the routers' selections in the first step."""
    w = make_w0()
    mu = jax.tree_util.tree_map(jnp.zeros_like, w)
    nu = jax.tree_util.tree_map(jnp.zeros_like, w)
    losses, out = [], {}
    for t, ids in enumerate(batches, start=1):
        loss, grads, chosen = loss_and_grads(
            w, jnp.asarray(ids), model, precision=precision,
            choices=first_choices if t == 1 else None)
        losses.append(float(loss))
        if t == 1:
            out["first_grad_norms"] = {k: float(v) for k, v in leaf_norms(grads).items()}
            out["first_grad"] = {k: np.asarray(v, np.float32) for k, v in grads.items()}
            out["choices"] = np.stack([np.asarray(c) for c in chosen]) if chosen else None
        w, mu, nu = adam_update(w, mu, nu, grads, jnp.float32(t), jnp.float32(lr))
        del grads
    del mu, nu
    out["losses"] = losses
    out["delta_norms"] = {k: float(v) for k, v in leaf_norms(w, make_w0()).items()}
    return out
