"""The plain reference of the Ouro looped language model (`model_type: ouro`;
"Scaling Latent Reasoning via Looped Language Models", arXiv:2510.25741):
forward pass, the exit-weighed loss, gradients and Adam in straightforward
float32 `jax.numpy`.

Written from the published configuration and the paper's equations (below;
the configuration file lists every reading that was assumed), not from the
program: no kernel, no key tile (a block of queries of one head scores ALL T
keys under the causal mask written out), no one-pass rotation, no logarithms
in the exit distribution (plain products of the gate's sigmoids), the passes
a plain Python loop over the same weight arrays with a looped weight's
gradient summed by hand over its uses. It imports nothing of the program and
takes nothing the program has made. Matrix products run at
`precision="highest"`, or, for the control that `correct` has to fail, with
both operands rounded to a lower type first.

A sequence goes through the loop a layer application at a time: the forward
pass keeps each application's input, the backward pass recomputes one
application and takes its gradient. The scores are held a head and
QUERY_BLOCK queries at a time, the logits a pass and LOSS_ROWS rows at a
time, by `lax.map`, each block recomputed in the backward pass.

Weights are a flat dict: `embed`, `final_norm`, `head`, `gate_w`, `gate_b`,
and `L<i>.<leaf>` for layer i of those held. With `N(x; g) = x *
rsqrt(mean(x^2) + eps) * g`, L layers held, T_max passes, H heads of D over G
KV heads, no bias:

    h(0) = E[ids]
    for t = 1..T_max:                                 the same L layers at every t
        x = h(t-1)
        for l = 0..L-1:
            u = N(x; g1);  q, k, v = u Wq, u Wk, u Wv;  q, k rotated over all D
                dimensions (dimension i pairs with i + D / 2, theta^(-2i / D))
            a = causal softmax(q k^T / sqrt(D)) v;   x = x + N(a Wo; g2)
            m = Wdown(silu(Wgate N(x; g3)) * Wup N(x; g3));   x = x + N(m; g4)
        h(t) = N(x; g_f);   z(t) = h(t) W_head;   lambda_t = sigmoid(h(t) . w_gate + b_gate)
    p(1) = lambda_1;  p(t) = lambda_t prod_{j<t}(1 - lambda_j), t < T_max;
    p(T_max) = prod_{j<T_max}(1 - lambda_j)
    loss = mean over the positions i with a next token of
           sum_t p_i(t) CE(z_i(t), ids_{i+1}) - beta H(p_i),    H(p) = -sum_t p(t) log p(t)

`fault=` puts one wrong reading of the loop in the reference's place, for the
control that sets the limits (`control_ouro.py`): each has to read incorrect.
None is ever the default.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.evabyte_ref import rotate
from benchmark.reference.laguna_ref import swiglu
from benchmark.reference.nemotron_h_ref import (
    _frozen,
    _mm_f32,
    adam_update,
    layer_weights,
    leaf_norms,
    rms_norm,
    rounding_in,
)
from benchmark.reference.sambay_ref import change_compared  # noqa: F401  (the drivers' rule)

QUERY_BLOCK = 1024  # queries of one head whose scores are held at a time
LOSS_ROWS = 2048    # rows of one pass's logits held at a time
# One wrong reading each (`fault=`): a looped weight's gradient taken from its
# last use alone (what a `stop_gradient` on the earlier passes' weights, or a
# copy a pass, would give); pass t + 1 started from the stream before the
# closing norm; the two norms on the branches' outputs left out; the loss read
# off the last pass alone; the entropy term left out; three passes run for
# four; the gate's two leaves left out of the update (a zero gradient).
FAULTS = ("last_use_gradient", "no_closing_norm", "no_post_norms", "last_pass_loss",
          "no_entropy", "three_passes", "gate_not_updated")


def passes_of(model: dict, fault=None) -> int:
    return 3 if fault == "three_passes" else model["total_ut_steps"]


def attention(w, u, model: dict, rnd):
    """u [T, d] -> [T, d]: one query head after another (`lax.map`), and
    within a head one block of QUERY_BLOCK queries after another against all
    T keys under the causal mask."""
    hq, g, dh = model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"]
    t = u.shape[0]
    proj = lambda name, heads: _mm_f32("tk,kn->tn", rnd(u), rnd(w[name])).reshape(t, heads, dh)
    q, k, v = rotate(proj("q", hq), model), rotate(proj("k", g), model), proj("v", g)
    kpos = jnp.arange(t)[None, :]
    n_blocks = -(-t // QUERY_BLOCK)

    def one_head(head):
        q_h, k_h, v_h = head                         # [T, D] x 3

        @jax.checkpoint  # one block of scores at a time, recomputed in the backward pass
        def block(rows):
            q_rows, first = rows
            # rows past T are padding: they look from the last position and are dropped
            qpos = jnp.minimum(first + jnp.arange(QUERY_BLOCK), t - 1)[:, None]
            scores = _mm_f32("qd,kd->qk", rnd(q_rows), rnd(k_h)) / math.sqrt(dh)
            pr = jax.nn.softmax(jnp.where(kpos <= qpos, scores, -jnp.inf), axis=-1)
            return _mm_f32("qk,kd->qd", rnd(pr), rnd(v_h))

        q_blocks = jnp.pad(q_h, ((0, n_blocks * QUERY_BLOCK - t), (0, 0))).reshape(
            n_blocks, QUERY_BLOCK, dh)
        out = jax.lax.map(block, (q_blocks, jnp.arange(n_blocks) * QUERY_BLOCK))
        return out.reshape(n_blocks * QUERY_BLOCK, dh)[:t]

    kv_head = jnp.arange(hq) // (hq // g)
    by_head = lambda x: jnp.moveaxis(x, 1, 0)                     # [heads, T, D]
    a = jax.lax.map(one_head, (by_head(q), by_head(k)[kv_head], by_head(v)[kv_head]))
    return _mm_f32("tk,kn->tn", rnd(jnp.moveaxis(a, 0, 1).reshape(t, hq * dh)), rnd(w["o"]))


def layer(w, x, model, precision="float32", fault=None):
    """One application of one layer on one sequence: x [T, d] -> x."""
    rnd = rounding_in(precision)
    eps = model["rms_norm_eps"]
    after = (lambda y, g: y) if fault == "no_post_norms" else (lambda y, g: rms_norm(y, g, eps))
    x = x + after(attention(w, rms_norm(x, w["norm1"], eps), model, rnd), w["norm2"])
    m = swiglu(rms_norm(x, w["norm3"], eps), w["w_gate"], w["w_up"], w["w_down"], rnd)
    return x + after(m, w["norm4"])


def close(w_norm, x, model):
    """The norm that closes a pass: h(t) of x."""
    return rms_norm(x, w_norm, model["rms_norm_eps"])


def exit_distribution(lam):
    """The gate's sigmoids lam [T_max, T] -> p [T_max, T], written out."""
    n = lam.shape[0]
    left, p = jnp.ones_like(lam[0]), []          # what has not left before pass t
    for t in range(n - 1):
        p.append(lam[t] * left)
        left = left * (1.0 - lam[t])
    return jnp.stack(p + [left])


def sequence_loss(w_head, gate_w, gate_b, hs, ids, model, precision="float32", fault=None):
    """Sum over the positions of one row that have a next token of the passes'
    cross-entropies weighed by the exit distribution less beta times its
    entropy. hs [T_max, T, d] are the closed states; float32 logits, one pass
    and LOSS_ROWS rows at a time."""
    rnd = rounding_in(precision)
    n, t, d = hs.shape
    targets = jnp.roll(ids, -1)
    has_next = jnp.arange(t) < t - 1     # the last position's place holds any row, weighed zero

    @jax.checkpoint
    def rows(block):
        h_rows, t_rows = block
        logits = _mm_f32("tk,kn->tn", rnd(h_rows), rnd(w_head))
        return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, t_rows[:, None], axis=-1)[:, 0]

    n_blocks = -(-t // LOSS_ROWS)
    pad = n_blocks * LOSS_ROWS - t
    h_blocks = jnp.pad(hs, ((0, 0), (0, pad), (0, 0))).reshape(n * n_blocks, LOSS_ROWS, d)
    t_blocks = jnp.tile(jnp.pad(targets, (0, pad)).reshape(n_blocks, LOSS_ROWS), (n, 1))
    ce = jax.lax.map(rows, (h_blocks, t_blocks)).reshape(n, n_blocks * LOSS_ROWS)[:, :t]
    if fault == "last_pass_loss":
        return jnp.sum(jnp.where(has_next, ce[-1], 0.0))
    lam = jax.nn.sigmoid(_mm_f32("ntk,k->nt", rnd(hs), rnd(gate_w)) + gate_b[0])
    p = exit_distribution(lam)
    # 0 log 0 = 0: a gate that has made up its mind (|h . w_gate| past 17, which three steps of
    # Adam reach) rounds a sigmoid to 1 in float32 and leaves the later passes a mass of 0
    some = p > 0.0
    entropy = -jnp.sum(jnp.where(some, p * jnp.log(jnp.where(some, p, 1.0)), 0.0), axis=0)
    beta = 0.0 if fault == "no_entropy" else model["exit_entropy_beta"]
    return jnp.sum(jnp.where(has_next, jnp.sum(p * ce, axis=0) - beta * entropy, 0.0))


# --------------------------------- a layer application at a time, all sequences


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _layer_fwd(w, xs, model_items, precision, fault):
    model = dict(model_items)
    return jax.vmap(lambda x: layer(w, x, model, precision, fault))(xs)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _layer_bwd(w, xs, gs, model_items, precision, fault):
    model = dict(model_items)
    f = lambda w, xs: jax.vmap(lambda x: layer(w, x, model, precision, fault))(xs)
    return jax.vjp(f, w, xs)[1](gs)


@functools.partial(jax.jit, static_argnums=(3,))
def _close_bwd(w_norm, xs, gs, model_items):
    return jax.vjp(lambda w_norm, xs: close(w_norm, xs, dict(model_items)), w_norm, xs)[1](gs)


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _head(w_head, gate_w, gate_b, hs, ids, model_items, precision, fault):
    """hs [T_max, B, T, d] -> (loss, its gradient to the head, the gate's two
    leaves and every closed state)."""
    model = dict(model_items)
    n = ids.shape[0] * (ids.shape[1] - 1)

    def f(w_head, gate_w, gate_b, hs):
        one = lambda h, i: sequence_loss(w_head, gate_w, gate_b, h, i, model, precision, fault)
        return jnp.sum(jax.vmap(one, in_axes=(1, 0))(hs, ids)) / n

    return jax.value_and_grad(f, argnums=(0, 1, 2, 3))(w_head, gate_w, gate_b, hs)


def forward(w: dict, ids, model: dict, *, precision="float32", fault=None, keep=None):
    """ids [B, T] -> the closed states h(t) [T_max, B, T, d]. `keep`, a dict
    of two lists, receives every layer application's input (`layers`) and
    every pass's stream before its closing norm (`closes`)."""
    items = _frozen(model)
    xs = w["embed"][ids]
    hs = []
    for _ in range(passes_of(model, fault)):
        for i in range(model["num_hidden_layers"]):
            if keep is not None:
                keep["layers"].append(xs)
            xs = _layer_fwd(layer_weights(w, i), xs, items, precision, fault)
        if keep is not None:
            keep["closes"].append(xs)
        hs.append(close(w["final_norm"], xs, model))
        if fault != "no_closing_norm":
            xs = hs[-1]
    return jnp.stack(hs)


def logits(w: dict, ids, model: dict, *, precision="float32"):
    """[T_max, B, T, V]: for the tests of causality."""
    rnd = rounding_in(precision)
    return _mm_f32("nbtk,kv->nbtv", rnd(forward(w, ids, model, precision=precision)),
                   rnd(w["head"]))


def loss_and_grads(w: dict, ids, model: dict, *, precision="float32", fault=None):
    """(loss, gradient as a flat dict like `w`): a looped leaf's gradient is
    the sum of its `total_ut_steps` uses', added up here pass by pass."""
    items = _frozen(model)
    keep = {"layers": [], "closes": []}
    hs = forward(w, ids, model, precision=precision, fault=fault, keep=keep)
    loss, (g_head, g_gate_w, g_gate_b, g_hs) = _head(
        w["head"], w["gate_w"], w["gate_b"], hs, ids, items, precision, fault)
    if fault == "gate_not_updated":
        g_gate_w, g_gate_b = jnp.zeros_like(g_gate_w), jnp.zeros_like(g_gate_b)
    grads = {"head": g_head, "gate_w": g_gate_w, "gate_b": g_gate_b,
             "final_norm": jnp.zeros_like(w["final_norm"])}
    n = passes_of(model, fault)
    gs = jnp.zeros_like(g_hs[0])                 # what the next pass's first layer hands back
    for t in reversed(range(n)):
        # h(t) feeds the head and the gate, and (but under the fault) the next pass
        g_norm, g_x = _close_bwd(w["final_norm"], keep["closes"].pop(),
                                 g_hs[t] if fault == "no_closing_norm" else g_hs[t] + gs, items)
        grads["final_norm"] = grads["final_norm"] + g_norm
        gs = g_x + gs if fault == "no_closing_norm" else g_x
        for i in reversed(range(model["num_hidden_layers"])):
            g_w, gs = _layer_bwd(layer_weights(w, i), keep["layers"].pop(), gs, items,
                                 precision, fault)
            for k, v in g_w.items():
                name = f"L{i:02d}.{k}"
                if fault == "last_use_gradient" and t < n - 1:
                    continue
                grads[name] = grads[name] + v if name in grads else v
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
