"""The plain reference of the Kimi Linear language model (`model_type:
kimi_linear`): forward pass, next-token loss, gradients and Adam in
straightforward float32 `jax.numpy`.

Written from the published configuration's layer equations (below; the
configuration file lists every reading that was assumed), not from the
program: no chunk, no WY form and no solve (the delta rule runs token by
token, the state decayed and corrected a position at a time), no kernel and
no query tile tied to one (whole rows of scores against all T keys under a
mask), no sort, no grouped product and no row ladder (every expert held runs
over every token of a block, weighted by what the router gave it). It
imports nothing of the program and takes nothing the program has made. Matrix
products run at `precision="highest"`, or, for the control that `correct` has
to fail, with both operands rounded to a lower type first (the projections',
the scores' and the experts'; the recurrence itself stays in float32, as the
other references' do).

A sequence goes through the stack a layer at a time: the forward pass keeps
each layer's input, the backward pass recomputes one layer and takes its
gradient. Inside a layer the recurrence is a `lax.scan` over positions inside
a checkpointed `lax.scan` over blocks of SCAN_BLOCK positions, so that the
backward pass holds one state a block and one block's states, not T of them
(T = 16,384: 34 GB); the scores are held a head and QUERY_BLOCK queries at a
time and the experts EXPERT_ROWS tokens at a time, by `lax.map`, each block
recomputed in the backward pass.

Weights are a flat dict: `embed`, `final_norm`, `head`, and `L<i>.<leaf>` for
layer i of those held (`layer_kinds`). A layer, u = RMSNorm(h) (eps, weight):

  KDA (H heads of size D; conv a causal depthwise convolution over positions
  of `short_conv_kernel_size` taps, no bias; l2norm(x) = x / sqrt(sum x^2 +
  1e-6)):
    q = l2norm(silu(conv(u Wq)))  k = l2norm(silu(conv(u Wk)))  v = silu(conv(u Wv))
    g = -exp(A_log[h]) softplus((u Wf1) Wf2 + dt_bias)     [T, H, D], <= 0
    beta = sigmoid(u Wb)                                    [T, H]
    S <- Diag(exp(g_t)) S;  S <- S + beta_t k_t (v_t - S^T k_t)^T;  o_t = S^T q_t D^-1/2
    h = h + (RMSNorm_head(o) * sigmoid((u Wg1) Wg2)) Wo
  latent attention (H heads; no positions of any kind):
    q = u Wq [T, H, nope + rope];  [c, kr] = u Wkva;  [kn, v] = RMSNorm(c) Wkvb
    k = [kn, kr for every head];  a = softmax_{j <= t}(q k^T (nope + rope)^-1/2) v
    h = h + flatten(a) Wo
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

from benchmark.reference import laguna_ref
from benchmark.reference.laguna_ref import head_loss, swiglu  # noqa: F401  (the same pieces)
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

SCAN_BLOCK = 128    # positions of the recurrence between two kept states
QUERY_BLOCK = 1024  # queries of one head whose scores are held at a time
L2_EPS = 1e-6


def layer_kinds(model: dict) -> list:
    """(mixer, MLP) letters of the layers held, in order: `K` or `A`; `D` or `E`."""
    held = range(model["layer_offset"], model["layer_offset"] + model["num_hidden_layers"])
    return [(model["layer_types"][i], "D" if i < model["first_k_dense_replace"] else "E")
            for i in held]


# ------------------------------------------------------------------------ KDA


def causal_conv(x, w):
    """x [T, C], w [C, K]: out[t] = sum_j w[:, j] x[t - (K - 1) + j], zeros before 0."""
    taps = w.shape[1]
    xp = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    return sum(xp[j:j + x.shape[0]] * w[:, j] for j in range(taps))


def l2norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def delta_rule(q, k, v, g, beta):
    """The recurrence, a position at a time. q, k, v, g [T, H, D], beta [T,
    H] -> o [T, H, D]. Blocks of positions are recomputed in the backward
    pass so that only one state a block is kept."""
    t, h, d = q.shape
    pad = -t % SCAN_BLOCK
    xs = [jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)) for x in (q, k, v, g, beta)]
    xs = [x.reshape(-1, SCAN_BLOCK, *x.shape[1:]) for x in xs]

    def step(s, inp):
        q_t, k_t, v_t, g_t, b_t = inp                          # [H, D] x 4, [H]
        s = jnp.exp(g_t)[:, :, None] * s                       # decay along the key rows
        seen = jnp.sum(s * k_t[:, :, None], axis=1)            # S^T k  [H, D]
        s = s + (b_t[:, None] * k_t)[:, :, None] * (v_t - seen)[:, None, :]
        return s, jnp.sum(s * q_t[:, :, None], axis=1)         # S^T q

    @jax.checkpoint
    def block(s, inp):
        return jax.lax.scan(step, s, inp)

    _, o = jax.lax.scan(block, jnp.zeros((h, d, d), jnp.float32), tuple(xs))
    return o.reshape(-1, h, d)[:t]


def kda_inputs(w, u, model, rnd):
    """u [T, d] -> what the recurrence reads: q, k, v, g [T, H, D], beta [T, H]."""
    h, d = model["linear_num_heads"], model["linear_head_dim"]
    t = u.shape[0]
    mm = lambda a, b: _mm_f32("tk,kn->tn", rnd(a), rnd(b))
    branch = lambda name: silu(causal_conv(mm(u, w[name]), w["conv_" + name])).reshape(t, h, d)
    q, k, v = l2norm(branch("q")) / math.sqrt(d), l2norm(branch("k")), branch("v")
    g = -jnp.exp(w["A_log"])[:, None] * jax.nn.softplus(
        (mm(mm(u, w["f1"]), w["f2"]) + w["dt_bias"]).reshape(t, h, d))
    return q, k, v, g, jax.nn.sigmoid(mm(u, w["beta"]))


def kda(w, u, model, rnd):
    """u [T, d] -> [T, d]."""
    h, d = model["linear_num_heads"], model["linear_head_dim"]
    t = u.shape[0]
    mm = lambda a, b: _mm_f32("tk,kn->tn", rnd(a), rnd(b))
    o = delta_rule(*kda_inputs(w, u, model, rnd))
    gate = jax.nn.sigmoid(mm(mm(u, w["g1"]), w["g2"])).reshape(t, h, d)
    y = rms_norm(o, w["onorm"], model["rms_norm_eps"]) * gate
    return mm(y.reshape(t, h * d), w["o"])


# ----------------------------------------------------------- latent attention


def latent_attention(w, u, model, rnd):
    """u [T, d] -> [T, d]: one head after another (`lax.map`), and within a
    head one block of QUERY_BLOCK queries after another against all T keys."""
    h, lat = model["num_attention_heads"], model["kv_lora_rank"]
    nope, rope, dv = model["qk_nope_head_dim"], model["qk_rope_head_dim"], model["v_head_dim"]
    t = u.shape[0]
    mm = lambda a, b: _mm_f32("tk,kn->tn", rnd(a), rnd(b))
    q = mm(u, w["q"]).reshape(t, h, nope + rope)
    c, shared = jnp.split(mm(u, w["kva"]), [lat], axis=-1)
    kv = mm(rms_norm(c, w["kv_norm"], model["rms_norm_eps"]), w["kvb"]).reshape(t, h, nope + dv)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(shared[:, None, :], (t, h, rope))], -1)
    v = kv[..., nope:]
    kpos = jnp.arange(t)[None, :]
    n_blocks = -(-t // QUERY_BLOCK)

    def one_head(head):
        q_h, k_h, v_h = head

        @jax.checkpoint  # one block of scores at a time, recomputed in the backward pass
        def block(rows):
            q_rows, first = rows
            # rows past T are padding: they look from the last position and are dropped
            qpos = jnp.minimum(first + jnp.arange(QUERY_BLOCK), t - 1)[:, None]
            scores = _mm_f32("qd,kd->qk", rnd(q_rows), rnd(k_h)) / math.sqrt(nope + rope)
            pr = jax.nn.softmax(jnp.where(kpos <= qpos, scores, -jnp.inf), axis=-1)
            return _mm_f32("qk,kd->qd", rnd(pr), rnd(v_h))

        q_blocks = jnp.pad(q_h, ((0, n_blocks * QUERY_BLOCK - t), (0, 0))).reshape(
            n_blocks, QUERY_BLOCK, nope + rope)
        out = jax.lax.map(block, (q_blocks, jnp.arange(n_blocks) * QUERY_BLOCK))
        return out.reshape(n_blocks * QUERY_BLOCK, dv)[:t]

    by_head = lambda x: jnp.moveaxis(x, 1, 0)
    a = jax.lax.map(one_head, (by_head(q), by_head(k), by_head(v)))
    return mm(jnp.moveaxis(a, 0, 1).reshape(t, h * dv), w["o"])


# ------------------------------------------------------------------ the layer


def _routed_model(model: dict) -> dict:
    """The keys `laguna_ref`'s router and experts read, under its names."""
    return dict(model, num_experts_per_tok=model["num_experts_per_token"],
                moe_routed_scaling_factor=model["routed_scaling_factor"])


def moe_routed(w, u, model, rnd):
    """The part of the routed sum that the experts held here give, and the
    router's choices: `laguna_ref`'s (the same router rule, the same SwiGLU
    experts, a loop over those held)."""
    return laguna_ref.moe_routed(w, u, _routed_model(model), rnd)


def layer(kinds, w, x, model, precision="float32"):
    """One layer on one sequence: x [T, d] -> (x, the router's choices [T, k]
    or None)."""
    mixer, mlp_kind = kinds
    rnd = rounding_in(precision)
    eps = model["rms_norm_eps"]
    u = rms_norm(x, w["norm1"], eps)
    x = x + (kda if mixer == "K" else latent_attention)(w, u, model, rnd)
    u2 = rms_norm(x, w["norm2"], eps)
    if mlp_kind == "D":
        return x + swiglu(u2, w["w_gate"], w["w_up"], w["w_down"], rnd), None
    routed, top_i = moe_routed(w, u2, model, rnd)
    return x + routed + swiglu(u2, w["s_gate"], w["s_up"], w["s_down"], rnd), top_i


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
