"""The plain reference of the SambaY language model (`phi4flash`,
Phi-4-mini-flash-reasoning): forward pass, next-token loss, gradients and
Adam in straightforward float32 `jax.numpy`, every matrix product at
`precision="highest"` and the whole of `train_reference` under
`jax.default_matmul_precision("highest")`.

Written from the layer equations below (PERF.md section 4 lists them and
every size that was assumed), not from the program: the recurrence runs a
position at a time, attention is whole masked softmaxes over all T keys, a
head pair and a block of queries at a time, the two softmaxes of a pair are
computed apart and subtracted, nothing is sliced away for being masked. It imports nothing
of the program and takes nothing the program has made. For the control that
`correct` has to fail, both operands of every matrix product (and the
recurrence's x, B and C) are rounded to a lower type first.

Layers, for published index i of N (`layer_kinds`): h <- h + mixer(LN1(h));
h <- h + W_down(SiLU(g) * u), [g, u] = LN2(h) W_gate_up; LayerNorm with weight
and bias; a final LayerNorm; logits h E^T with the embedding E; no positions.
  M  (i even, i <= N/2) [x, z] = u W_in; x <- SiLU(causal depthwise conv_k(x)
     + b); [r, B, C] = x W_x; dt = softplus(r W_dt + b_dt); A = -exp(A_log);
     s_t = exp(dt_t A) s_{t-1} + (dt_t x_t) B_t^T; y_t = s_t C_t + D x_t;
     out = (y * SiLU(z)) W_out. Layer N/2's y is the memory m.
  W  (i odd, i < N/2) [q, k, v] = u W_qkv + b; query heads (2j, 2j+1) are
     pair j's (q1, q2), KV heads (2g, 2g+1) pair g's (k1, k2), V_g = [v_2g |
     v_2g+1]; query pair j reads KV pair j // (query pairs a KV pair);
     P1 = softmax(q1 k1^T / sqrt(D)), P2 = softmax(q2 k2^T / sqrt(D)), both
     under the mask: key j' for query t iff t - window < j' <= t;
     lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init, lambda_init =
     0.8 - 0.6 exp(-0.3 i); o = RMSNorm_2D((P1 - lambda P2) V) (1 -
     lambda_init); out = concat(o) W_o + b_o.
  F  (i = N/2 + 1) the same, causal without a window; its k, v are the shared KV.
  G  (i even, i > N/2 + 1) out = (SiLU(u W_in) * m) W_out.
  X  (i odd, i > N/2 + 1) q = u W_q + b_q against the shared KV, causal, with
     this layer's own lambda vectors, norm and W_o.

Departures from the description, all of them about memory and none about the
mathematics: a sequence goes through the stack a layer at a time (the forward
pass keeps each layer's input and what it was handed, the backward pass
recomputes one layer and takes its gradient); the recurrence keeps one state
a block of SCAN_BLOCK positions and recomputes the block in its backward
pass; a head pair's block of scores and a block of logits rows are recomputed
likewise; Adam's two moments are kept on the host between updates. So 8,192
tokens at the published widths fit beside the float32 parameters and their
gradient.

Weights are a flat dict: `embed`, `final_norm_w`, `final_norm_b`, and
`L<i>.<leaf>` for layer i of those held.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.nemotron_h_ref import (
    ADAM_EPS,
    _frozen,
    _mm_f32,
    adam_update,
    layer_weights,
    leaf_norms,
    rms_norm,
    rounding_in,
    silu,
)

SCAN_BLOCK = 128    # positions of the recurrence between two kept states
QUERY_BLOCK = 1024  # queries whose scores are held at a time
LOSS_ROWS = 2048    # rows of logits held at a time


def layer_kinds(model: dict) -> str:
    """The mixers of the layers held, by the published rule."""
    n, every = model["num_hidden_layers_total"], model["mb_per_layer"]

    def kind(i):
        if i % every == 0:
            return "M" if i <= n // 2 else "G"
        if i < n // 2:
            return "W"
        return "F" if i == n // 2 + 1 else "X"

    first = model["layer_offset"]
    return "".join(kind(i) for i in range(first, first + model["num_hidden_layers"]))


def layer_norm(x, weight, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * weight + bias


def lambda_init(index: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * index)


# ----------------------------------------------------------------- mixers


def recurrence(x, dt, a, b, c):
    """s_t = exp(dt_t a) s_{t-1} + (dt_t x_t) b_t^T, y_t = s_t c_t, a
    position at a time. x and dt [T, C], a [C, N], b and c [T, N]."""
    t = x.shape[0]
    pad = -t % SCAN_BLOCK
    xs = [jnp.pad(v, ((0, pad), (0, 0))).reshape(-1, SCAN_BLOCK, v.shape[1])
          for v in (x, dt, b, c)]

    def step(s, at_t):
        x_t, dt_t, b_t, c_t = at_t
        s = jnp.exp(dt_t[:, None] * a) * s + (dt_t * x_t)[:, None] * b_t[None, :]
        return s, jnp.sum(s * c_t[None, :], axis=-1)

    @jax.checkpoint
    def block(s, inp):
        return jax.lax.scan(step, s, inp)

    _, y = jax.lax.scan(block, jnp.zeros(a.shape, jnp.float32), tuple(xs))
    return y.reshape(-1, y.shape[-1])[:t]


def mamba(w, u, model, rnd):
    """u [T, d] -> (out [T, d], y [T, 2d])."""
    n, k = model["mamba_d_state"], model["mamba_d_conv"]
    rank = -(-model["hidden_size"] // 16)
    t = u.shape[0]
    x, z = jnp.split(_mm_f32("tk,kn->tn", rnd(u), rnd(w["in_proj"])), 2, axis=-1)
    padded = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), jnp.float32), x])
    x = silu(w["conv_b"] + sum(padded[j:j + t] * w["conv_w"][:, j] for j in range(k)))
    low = _mm_f32("tk,kn->tn", rnd(x), rnd(w["x_proj"]))
    r, b, c = low[:, :rank], low[:, rank:rank + n], low[:, rank + n:]
    dt = jax.nn.softplus(_mm_f32("tk,kn->tn", rnd(r), rnd(w["dt_proj"])) + w["dt_bias"])
    y = recurrence(rnd(x), dt, -jnp.exp(w["A_log"]), rnd(b), rnd(c)) + w["D"] * x
    return _mm_f32("tk,kn->tn", rnd(y * silu(z)), rnd(w["out_proj"])), y


def differential_attention(w, q, k, v, model, index, rnd, window):
    """q [T, heads, D], k and v [T, KV heads, D] -> [T, d]. One query pair
    after another (`lax.map`), and within a pair one block of QUERY_BLOCK
    queries after another against all T keys."""
    hq, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    t, _, dh = q.shape
    lam0 = lambda_init(index)
    lam = (jnp.exp(jnp.sum(w["lambda_q1"] * w["lambda_k1"]))
           - jnp.exp(jnp.sum(w["lambda_q2"] * w["lambda_k2"])) + lam0)
    kpos = jnp.arange(t)[None, :]
    n_blocks = -(-t // QUERY_BLOCK)

    def softmaxed(q_rows, keys, seen):
        scores = _mm_f32("qd,kd->qk", rnd(q_rows), rnd(keys)) / math.sqrt(dh)
        return jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)

    def one_pair(pair):
        q1, q2, k1, k2, vv = pair                    # [T, D] x 4, [T, 2 D]

        @jax.checkpoint  # one block of scores at a time, recomputed in the backward pass
        def block(rows):
            q1_rows, q2_rows, first = rows
            # rows past T are padding: they look from the last position and are dropped
            qpos = jnp.minimum(first + jnp.arange(QUERY_BLOCK), t - 1)[:, None]
            seen = kpos <= qpos
            if window is not None:
                seen &= kpos > qpos - window
            return (_mm_f32("qk,kd->qd", rnd(softmaxed(q1_rows, k1, seen)), rnd(vv))
                    - lam * _mm_f32("qk,kd->qd", rnd(softmaxed(q2_rows, k2, seen)), rnd(vv)))

        blocks = lambda x: jnp.pad(x, ((0, n_blocks * QUERY_BLOCK - t), (0, 0))).reshape(
            n_blocks, QUERY_BLOCK, dh)
        out = jax.lax.map(block, (blocks(q1), blocks(q2), jnp.arange(n_blocks) * QUERY_BLOCK))
        out = out.reshape(n_blocks * QUERY_BLOCK, 2 * dh)[:t]
        return rms_norm(out, w["subln"], model["layer_norm_eps"]) * (1.0 - lam0)

    # query pair j is heads (2j, 2j+1) and reads KV pair j // (query pairs a KV pair)
    kv_pair = jnp.arange(hq // 2) // (hq // hkv)
    by_pair = lambda x, members: jnp.moveaxis(x[:, members::2], 1, 0)     # [pairs, T, D]
    vv = jnp.concatenate([by_pair(v, 0), by_pair(v, 1)], axis=-1)         # [KV pairs, T, 2 D]
    o = jax.lax.map(one_pair, (by_pair(q, 0), by_pair(q, 1), by_pair(k, 0)[kv_pair],
                               by_pair(k, 1)[kv_pair], vv[kv_pair]))      # [pairs, T, 2 D]
    return _mm_f32("tk,kn->tn", rnd(jnp.moveaxis(o, 0, 1).reshape(t, hq * dh)),
                   rnd(w["o"])) + w["o_b"]


def attention(kind, w, u, shared_kv, model, index, rnd):
    """`W`, `F` or `X`: (out [T, d], the keys and values read)."""
    hq, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    dh = model["hidden_size"] // hq
    t = u.shape[0]
    if kind == "X":
        q = _mm_f32("tk,kn->tn", rnd(u), rnd(w["q"])) + w["q_b"]
        k, v = shared_kv
    else:
        qkv = _mm_f32("tk,kn->tn", rnd(u), rnd(w["qkv"])) + w["qkv_b"]
        q, k, v = qkv[:, :hq * dh], qkv[:, hq * dh:(hq + hkv) * dh], qkv[:, (hq + hkv) * dh:]
        k, v = k.reshape(t, hkv, dh), v.reshape(t, hkv, dh)
    window = model["sliding_window"] if kind == "W" else None
    return differential_attention(w, q.reshape(t, hq, dh), k, v, model, index, rnd,
                                  window), (k, v)


def gmu(w, u, memory, rnd):
    gate = silu(_mm_f32("tk,kn->tn", rnd(u), rnd(w["in_proj"])))
    return _mm_f32("tk,kn->tn", rnd(gate * memory), rnd(w["out_proj"]))


def mlp(w, u, rnd):
    gate, up = jnp.split(_mm_f32("tk,kn->tn", rnd(u), rnd(w["gate_up"])), 2, axis=-1)
    return _mm_f32("tk,kn->tn", rnd(silu(gate) * up), rnd(w["down"]))


def layer(kind: str, index: int, w, x, side, model, precision="float32"):
    """One layer on one sequence: x [T, d], side = (the memory or None, the
    shared keys and values or None) -> (x, side)."""
    rnd = rounding_in(precision)
    memory, shared_kv = side
    n, eps = model["num_hidden_layers_total"], model["layer_norm_eps"]
    u = layer_norm(x, w["norm1_w"], w["norm1_b"], eps)
    if kind == "M":
        out, y = mamba(w, u, model, rnd)
        if index == n // 2:
            memory = y
    elif kind == "G":
        out = gmu(w, u, memory, rnd)
    else:
        out, kv = attention(kind, w, u, shared_kv, model, index, rnd)
        if index == n // 2 + 1:
            shared_kv = kv
    x = x + out
    return x + mlp(w, layer_norm(x, w["norm2_w"], w["norm2_b"], eps), rnd), (memory, shared_kv)


def head_loss(norm_w, norm_b, embed, x, ids, model, precision="float32"):
    """Sum over the sequence's T - 1 positions that have a next token of the
    cross-entropy of that token under the tied head, float32 logits."""
    rnd = rounding_in(precision)
    h = layer_norm(x, norm_w, norm_b, model["layer_norm_eps"])[:-1]
    targets = ids[1:]

    @jax.checkpoint
    def rows(h_rows, t_rows):
        logits = _mm_f32("tk,nk->tn", rnd(h_rows), rnd(embed))
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1)
                       - jnp.take_along_axis(logits, t_rows[:, None], axis=-1)[:, 0])

    return sum(rows(h[i:i + LOSS_ROWS], targets[i:i + LOSS_ROWS])
               for i in range(0, h.shape[0], LOSS_ROWS))


# ------------------------------------------- a layer at a time, all sequences


def _all(kind, index, w, model, precision):
    one = lambda x, side: layer(kind, index, w, x, side, model, precision)
    return jax.vmap(one)


@functools.partial(jax.jit, static_argnums=(0, 1, 5, 6))
def _layer_fwd(kind, index, w, xs, side, model_items, precision):
    return _all(kind, index, w, dict(model_items), precision)(xs, side)


@functools.partial(jax.jit, static_argnums=(0, 1, 6, 7))
def _layer_bwd(kind, index, w, xs, side, gs, model_items, precision):
    """The gradients of (w, xs, side) for the cotangents `gs` of (xs, side)
    out. A layer that hands `side` through as it got it adds its cotangent
    to what the layers before it see."""
    f = lambda w, xs, side: _all(kind, index, w, dict(model_items), precision)(xs, side)
    return jax.vjp(f, w, xs, side)[1](gs)


@functools.partial(jax.jit, static_argnums=(5, 6))
def _head(norm_w, norm_b, embed, xs, ids, model_items, precision):
    model = dict(model_items)
    n = ids.shape[0] * (ids.shape[1] - 1)

    def f(norm_w, norm_b, embed, xs):
        return jnp.sum(jax.vmap(lambda x, i: head_loss(norm_w, norm_b, embed, x, i, model,
                                                       precision))(xs, ids)) / n

    return jax.value_and_grad(f, argnums=(0, 1, 2, 3))(norm_w, norm_b, embed, xs)


def forward(w: dict, ids, model: dict, *, precision="float32", keep=None):
    """ids [B, T] -> (the last layer's output [B, T, d], the side values at
    the stack's end). `keep`, a list, receives every layer's inputs."""
    items = _frozen(model)
    xs, side = w["embed"][ids], (None, None)
    for i, kind in enumerate(layer_kinds(model)):
        if keep is not None:
            keep.append((xs, side))
        xs, side = _layer_fwd(kind, model["layer_offset"] + i, layer_weights(w, i), xs, side,
                              items, precision)
    return xs, side


def logits(w: dict, ids, model: dict, *, precision="float32"):
    """[B, T, V]: for the tests of the tied head and the vocabulary's shares."""
    xs, _ = forward(w, ids, model, precision=precision)
    h = layer_norm(xs, w["final_norm_w"], w["final_norm_b"], model["layer_norm_eps"])
    rnd = rounding_in(precision)
    return _mm_f32("btk,nk->btn", rnd(h), rnd(w["embed"]))


def loss_and_grads(w: dict, ids, model: dict, *, precision="float32"):
    """(loss, gradient as a flat dict like `w`)."""
    items = _frozen(model)
    keep = []
    xs, side = forward(w, ids, model, precision=precision, keep=keep)
    loss, (g_nw, g_nb, g_embed, gs) = _head(w["final_norm_w"], w["final_norm_b"], w["embed"],
                                            xs, ids, items, precision)
    grads = {"final_norm_w": g_nw, "final_norm_b": g_nb}
    g_side = jax.tree_util.tree_map(jnp.zeros_like, side)
    kinds = layer_kinds(model)
    for i in reversed(range(len(kinds))):
        xs_in, side_in = keep.pop()
        g_w, gs, g_side = _layer_bwd(kinds[i], model["layer_offset"] + i, layer_weights(w, i),
                                     xs_in, side_in, (gs, g_side), items, precision)
        grads.update({f"L{i:02d}.{k}": v for k, v in g_w.items()})
    grads["embed"] = g_embed.at[ids].add(gs)
    return loss, grads


def bias_parts(tree: dict, model: dict) -> dict:
    """The flat dict with every `qkv_b` as the three biases it is made of
    (`.q`, `.k`, `.v`), for the comparison of the parameters' change: the keys'
    bias moves every score of a query alike, so its true gradient is zero, and
    it must not hide behind the queries' and the values' in one leaf."""
    dh = model["hidden_size"] // model["num_attention_heads"]
    q, kv = model["num_attention_heads"] * dh, model["num_key_value_heads"] * dh
    out = {}
    for name, leaf in tree.items():
        if name.endswith(".qkv_b"):
            out.update({f"{name}.q": leaf[:q], f"{name}.k": leaf[q:q + kv],
                        f"{name}.v": leaf[q + kv:]})
        else:
            out[name] = leaf
    return out


def change_compared(numbers: dict) -> dict:
    """`train_reference`'s `delta_norms` without the parts whose first
    gradient is, entry for entry (root mean square), at or under Adam's eps.
    There the update m / (sqrt(v) + eps) is set by eps and by what rounding
    leaves of a gradient that is zero, in float32 here and in another type in
    the program: no size of that change is this reference's to vouch for. The
    rule reads this reference alone, never the program."""
    return {k: v for k, v in numbers["delta_norms"].items()
            if numbers["first_grad_rms"][k] > ADAM_EPS}


def train_reference(make_w0, batches, model: dict, *, lr, precision="float32") -> dict:
    """Follow the first len(batches) training steps from the weights
    `make_w0()` gives (a callable, so that no second copy of the initial
    weights is held while the steps run). Returns each step's loss, the first
    gradient and its per-leaf norms, and by `bias_parts` the norms of the
    parameters' change over all the steps and the root mean square of the
    first gradient's entries (what `change_compared` reads)."""
    with jax.default_matmul_precision("highest"):
        w = make_w0()
        mu = nu = None   # Adam's moments wait on the host while the layers run
        losses, out = [], {}
        for t, ids in enumerate(batches, start=1):
            loss, grads = loss_and_grads(w, jnp.asarray(ids), model, precision=precision)
            losses.append(float(loss))
            if t == 1:
                out["first_grad_norms"] = {k: float(v) for k, v in leaf_norms(grads).items()}
                out["first_grad"] = {k: np.asarray(v, np.float32) for k, v in grads.items()}
                out["first_grad_rms"] = {
                    k: float(np.sqrt(np.mean(np.square(v, dtype=np.float64))))
                    for k, v in bias_parts(out["first_grad"], model).items()}
            zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, w)
            moments = (zeros(), zeros()) if mu is None else jax.device_put((mu, nu))
            w, mu, nu = adam_update(w, *moments, grads, jnp.float32(t), jnp.float32(lr))
            del grads
            mu, nu = jax.device_get((mu, nu)) if t < len(batches) else (None, None)
        out["losses"] = losses
        out["delta_norms"] = {k: float(v) for k, v in leaf_norms(
            bias_parts(w, model), bias_parts(make_w0(), model)).items()}
    return out
