"""The plain reference of the EvaByte language model (`model_type: evabyte`,
`attention_class: eva`): forward pass, the eight heads' loss, gradients and
Adam in straightforward float32 `jax.numpy`.

Written from the published configuration's layer equations (below; the
configuration file lists every reading that was assumed), not from the
program: no kernel, no key tile and no schedule (a block of queries of one
head scores ALL T + T / c keys, the positions' own and then the chunks'
summaries, under a visibility mask written out from L(t) and C(t)), no
one-pass rotation, no key segment cut to a window. It imports nothing of the
program and takes nothing the program has made. Matrix products run at
`precision="highest"`, or, for the control that `correct` has to fail, with
both operands rounded to a lower type first.

A sequence goes through the stack a layer at a time: the forward pass keeps
each layer's input, the backward pass recomputes one layer and takes its
gradient. The scores are held a head and QUERY_BLOCK queries at a time by
`lax.map`, each block recomputed in the backward pass.

Weights are a flat dict: `embed`, `final_norm`, `head`, and `L<i>.<leaf>` for
layer i of those held. With d the hidden size, H heads of D held here, W the
window, c the chunk, s = D^-1/2, no bias:

    u  = x * rsqrt(mean(x^2) + eps) * (1 + w1)
    q, k, v = u Wq, u Wk, u Wv [T, H, D]; q, k rotated over all D dimensions
        (dimension i pairs with i + D / 2, frequencies theta^(-2i / D))
    chunk j = positions c j .. c j + c - 1, head h, phi_h, mu_h in R^D:
        a_i = s (k_i . phi_h);  w = softmax_i(a);  khat_j = sum_i w_i k_i + mu_h;
        vhat_j = sum_i w_i v_i                     (of the rotated keys)
    query t, n = t // W:  L(t) = {i : n W <= i <= t},  C(t) = {j : c j < n W}
        o_t = softmax over L(t) and C(t) together of s q_t . (k_i | khat_j),
              times (v_i | vhat_j)
    x  = x + o Wo;   x = x + Wdown(silu(Wgate u2) * (Wup u2)),  u2 the norm with w2
    h = norm_f(x);  logits = h Whead [T, K, V]
    loss = mean over m < K and t with t + 1 + m < T of CE(logits[t, m], ids[t + 1 + m])

What this chip holds of a layer is H of the model's heads (the columns of Wq,
Wk, Wv, the rows of Wo, and phi, mu of those heads): it computes its heads'
term `o_share Wo_share` and leaves the other chips' out.

`fault=` puts one wrong reading of the equations in the reference's place,
for the control that sets the limits (`control_evabyte.py`): each has to read
incorrect. None is ever the default.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.laguna_ref import swiglu
from benchmark.reference.nemotron_h_ref import (
    _frozen,
    _mm_f32,
    adam_update,
    layer_weights,
    leaf_norms,
    rounding_in,
)
from benchmark.reference.sambay_ref import change_compared  # noqa: F401  (the drivers' rule)

QUERY_BLOCK = 1024  # queries of one head whose scores are held at a time
LOSS_ROWS = 2048    # rows of logits held at a time
# One wrong reading each (`fault=`): the summaries left out (a model of windows
# alone); a sliding window of W keys in the aligned one's place (no summaries
# either: what `flash_attention`'s older mask would give); `mu` not added to
# the summary keys; the chunk's weights replaced by the mean; only head 0 of
# the K in the loss; the stream rounded to bfloat16 after every add.
FAULTS = ("no_summaries", "sliding_window", "no_mu", "mean_weights", "head0_only",
          "bfloat16_stream")


def norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + weight)


def rotate(x, model: dict):
    """x [T, heads, D] at positions 0..T-1, every dimension, halves paired."""
    d = model["head_dim"]
    f = model["rope_theta"] ** (-2.0 * np.arange(d // 2) / d)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(f, jnp.float32)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def summaries(k, v, phi, mu, model: dict, fault=None):
    """k, v [T, H, D] -> (khat, vhat [T // c, H, D]): the chunks' summaries."""
    t, h, d = k.shape
    c = model["chunk_size"]
    n = t // c
    kc, vc = k[:n * c].reshape(n, c, h, d), v[:n * c].reshape(n, c, h, d)
    a = jnp.einsum("nchd,hd->nch", kc, phi, precision="highest") / math.sqrt(d)
    w = jax.nn.softmax(a, axis=1)
    if fault == "mean_weights":
        w = jnp.full_like(w, 1.0 / c)
    khat = jnp.einsum("nch,nchd->nhd", w, kc, precision="highest")
    vhat = jnp.einsum("nch,nchd->nhd", w, vc, precision="highest")
    return (khat if fault == "no_mu" else khat + mu), vhat


def visible(qpos, t: int, model: dict, fault=None):
    """[queries, T + T // c] bool: which of the positions' own keys, then of
    the chunks' summaries, the queries at `qpos` [queries, 1] see."""
    w, c = model["window_size"], model["chunk_size"]
    own, chunk = jnp.arange(t)[None, :], jnp.arange(t // c)[None, :]
    start = qpos // w * w                                  # n W
    if fault == "sliding_window":
        return jnp.concatenate([(own <= qpos) & (own > qpos - w),
                                jnp.zeros((qpos.shape[0], t // c), bool)], axis=1)
    seen = c * chunk < start                               # C(t)
    if fault == "no_summaries":
        seen = jnp.zeros_like(seen)
    return jnp.concatenate([(own >= start) & (own <= qpos), seen], axis=1)   # L(t), C(t)


def eva(q, k, v, phi, mu, model: dict, rnd=lambda x: x, fault=None):
    """EVA attention of rotated q, k and v [T, H, D] -> [T, H, D]: one head
    after another (`lax.map`), and within a head one block of QUERY_BLOCK
    queries after another against all T + T // c keys."""
    t, h, d = q.shape
    khat, vhat = summaries(k, v, phi, mu, model, fault)
    keys, values = jnp.concatenate([k, khat]), jnp.concatenate([v, vhat])
    n_blocks = -(-t // QUERY_BLOCK)

    def one_head(head):
        q_h, k_h, v_h = head                         # [T, D], [T + T // c, D] x 2

        @jax.checkpoint  # one block of scores at a time, recomputed in the backward pass
        def block(rows):
            q_rows, first = rows
            # rows past T are padding: they look from the last position and are dropped
            qpos = jnp.minimum(first + jnp.arange(QUERY_BLOCK), t - 1)[:, None]
            scores = _mm_f32("qd,kd->qk", rnd(q_rows), rnd(k_h)) / math.sqrt(d)
            pr = jax.nn.softmax(jnp.where(visible(qpos, t, model, fault), scores, -jnp.inf),
                                axis=-1)
            return _mm_f32("qk,kd->qd", rnd(pr), rnd(v_h))

        q_blocks = jnp.pad(q_h, ((0, n_blocks * QUERY_BLOCK - t), (0, 0))).reshape(
            n_blocks, QUERY_BLOCK, d)
        out = jax.lax.map(block, (q_blocks, jnp.arange(n_blocks) * QUERY_BLOCK))
        return out.reshape(n_blocks * QUERY_BLOCK, d)[:t]

    by_head = lambda x: jnp.moveaxis(x, 1, 0)
    return jnp.moveaxis(jax.lax.map(one_head, (by_head(q), by_head(keys), by_head(values))), 0, 1)


def attention_inputs(w, u, model: dict, rnd=lambda x: x):
    """u [T, d] -> rotated q, k and v [T, H, D]."""
    h, d = model["num_attention_heads"], model["head_dim"]
    t = u.shape[0]
    proj = lambda name: _mm_f32("tk,kn->tn", rnd(u), rnd(w[name])).reshape(t, h, d)
    return rotate(proj("q"), model), rotate(proj("k"), model), proj("v")


def attention(w, u, model: dict, rnd, fault=None):
    """u [T, d] -> the held heads' term of the layer's attention [T, d]."""
    a = eva(*attention_inputs(w, u, model, rnd), w["phi"], w["mu"], model, rnd, fault)
    return _mm_f32("tk,kn->tn", rnd(a.reshape(u.shape[0], -1)), rnd(w["o"]))


def layer(w, x, model, precision="float32", fault=None):
    """One layer on one sequence: x [T, d] -> x."""
    rnd = rounding_in(precision)
    stream = ((lambda x: x.astype(jnp.bfloat16).astype(jnp.float32))
              if fault == "bfloat16_stream" else (lambda x: x))
    eps = model["rms_norm_eps"]
    x = stream(x + attention(w, norm(x, w["norm1"], eps), model, rnd, fault))
    u2 = norm(x, w["norm2"], eps)
    return stream(x + swiglu(u2, w["w_gate"], w["w_up"], w["w_down"], rnd))


def head_loss(w_norm, w_head, x, ids, model, precision="float32", fault=None):
    """Sum over the K heads and the positions that have the head's byte of the
    cross-entropy of that byte, float32 logits: head m of position t predicts
    ids[t + 1 + m]."""
    rnd = rounding_in(precision)
    k, v = model["num_pred_heads"], model["vocab_size"]
    t = ids.shape[0]
    h = norm(x, w_norm, model["rms_norm_eps"])
    heads = range(1) if fault == "head0_only" else range(k)
    # a byte past the row's end is no target: its place holds any row, weighed zero
    targets = jnp.stack([jnp.roll(ids, -(1 + m)) for m in heads], axis=-1)
    weigh = jnp.stack([jnp.arange(t) + 1 + m < t for m in heads], axis=-1)

    @jax.checkpoint
    def rows(h_rows, t_rows, w_rows):
        logits = _mm_f32("tk,kn->tn", rnd(h_rows), rnd(w_head)).reshape(-1, k, v)[:, :len(heads)]
        nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, t_rows[..., None], axis=-1)[..., 0]
        return jnp.sum(jnp.where(w_rows, nll, 0.0))

    return sum(rows(h[i:i + LOSS_ROWS], targets[i:i + LOSS_ROWS], weigh[i:i + LOSS_ROWS])
               for i in range(0, t, LOSS_ROWS))


def targets_of(model: dict, t: int, fault=None) -> int:
    """How many (head, position) pairs of one row have a byte to predict."""
    heads = 1 if fault == "head0_only" else model["num_pred_heads"]
    return sum(t - 1 - m for m in range(heads))


# ------------------------------------------- a layer at a time, all sequences


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _layer_fwd(w, xs, model_items, precision, fault):
    model = dict(model_items)
    return jax.vmap(lambda x: layer(w, x, model, precision, fault))(xs)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _layer_bwd(w, xs, gs, model_items, precision, fault):
    model = dict(model_items)
    f = lambda w, xs: jax.vmap(lambda x: layer(w, x, model, precision, fault))(xs)
    return jax.vjp(f, w, xs)[1](gs)


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _head(w_norm, w_head, xs, ids, model_items, precision, fault):
    model = dict(model_items)
    n = ids.shape[0] * targets_of(model, ids.shape[1], fault)

    def f(w_norm, w_head, xs):
        return jnp.sum(jax.vmap(lambda x, i: head_loss(w_norm, w_head, x, i, model, precision,
                                                       fault))(xs, ids)) / n

    return jax.value_and_grad(f, argnums=(0, 1, 2))(w_norm, w_head, xs)


def forward(w: dict, ids, model: dict, *, precision="float32", fault=None, keep=None):
    """ids [B, T] -> the last layer's output [B, T, d]. `keep`, a list,
    receives every layer's input."""
    items = _frozen(model)
    xs = w["embed"][ids]
    for i in range(model["num_hidden_layers"]):
        if keep is not None:
            keep.append(xs)
        xs = _layer_fwd(layer_weights(w, i), xs, items, precision, fault)
    return xs


def logits(w: dict, ids, model: dict, *, precision="float32"):
    """[B, T, K, V]: for the tests of the mask and of causality."""
    xs = forward(w, ids, model, precision=precision)
    h = norm(xs, w["final_norm"], model["rms_norm_eps"])
    rnd = rounding_in(precision)
    out = _mm_f32("btk,kn->btn", rnd(h), rnd(w["head"]))
    return out.reshape(*ids.shape, model["num_pred_heads"], model["vocab_size"])


def loss_and_grads(w: dict, ids, model: dict, *, precision="float32", fault=None):
    """(loss, gradient as a flat dict like `w`)."""
    items = _frozen(model)
    keep = []
    xs = forward(w, ids, model, precision=precision, fault=fault, keep=keep)
    loss, (g_norm, g_head, gs) = _head(w["final_norm"], w["head"], xs, ids, items, precision,
                                       fault)
    grads = {"final_norm": g_norm, "head": g_head}
    for i in reversed(range(model["num_hidden_layers"])):
        g_w, gs = _layer_bwd(layer_weights(w, i), keep.pop(), gs, items, precision, fault)
        grads.update({f"L{i:02d}.{k}": v for k, v in g_w.items()})
    grads["embed"] = jnp.zeros_like(w["embed"]).at[ids].add(gs)
    return loss, grads


def train_reference(make_w0, batches, model: dict, *, lr, precision="float32",
                    fault=None) -> dict:
    """Follow the first len(batches) training steps from the weights
    `make_w0()` gives (a callable, so that no second copy of the initial
    weights is held while the steps run). Returns each step's loss, the first
    gradient, its per-leaf norms and the root mean square of its entries
    (what `change_compared` reads), and the per-leaf norms of the parameters'
    change over all the steps."""
    with jax.default_matmul_precision("highest"):
        w = make_w0()
        mu = nu = None   # Adam's moments wait on the host while the layers run
        losses, out = [], {}
        for t, ids in enumerate(batches, start=1):
            loss, grads = loss_and_grads(w, jnp.asarray(ids), model, precision=precision,
                                         fault=fault)
            losses.append(float(loss))
            if t == 1:
                out["first_grad_norms"] = {k: float(v) for k, v in leaf_norms(grads).items()}
                out["first_grad"] = {k: np.asarray(v, np.float32) for k, v in grads.items()}
                out["first_grad_rms"] = {
                    k: float(np.sqrt(np.mean(np.square(v, dtype=np.float64))))
                    for k, v in out["first_grad"].items()}
            zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, w)
            moments = (zeros(), zeros()) if mu is None else jax.device_put((mu, nu))
            w, mu, nu = adam_update(w, *moments, grads, jnp.float32(t), jnp.float32(lr))
            del grads
            mu, nu = jax.device_get((mu, nu)) if t < len(batches) else (None, None)
        out["losses"] = losses
        out["delta_norms"] = {k: float(v) for k, v in leaf_norms(w, make_w0()).items()}
    return out
