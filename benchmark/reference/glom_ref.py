"""The plain reference: GLOM's forward pass, the denoising loss, its
gradients and Adam, in straightforward float32 `jax.numpy`.

A port of `tests/oracle_np.py` (itself written from the behavioural spec,
SURVEY.md section 3.2), with no kernels, no cache, no batching tricks. It
imports nothing of the program and takes nothing the program has made: the
weights are the benchmark's (`benchmark/weights.py`), the inputs come from
the seed. Matrix products run at `precision="highest"`, or, for the control
that `correct` has to fail, with both operands rounded to a lower type
first (`matmul=` below).

Contract items it keeps (each is a subtlety of the published model):
pos-emb added only to the top-down net's input; k-only L2 normalisation in
consensus, scale d^-1/2; the diagonal similarity replaced by -5e-4; pairs
farther than the local radius hard-masked; the mean's divisor 4, and 3 at
the top level; the loss reads the top level after T//2 + 1 of T = 2L
iterations.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

SELF_VALUE = -5e-4
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8

# ---------------------------------------------------------------- matmuls


def _mm_f32(eq, a, b):
    return jnp.einsum(eq, a, b, precision="highest",
                      preferred_element_type=jnp.float32)


def _round_to(x, dtype):
    """Round a float32 tensor to `dtype`'s grid and back. float8 takes a
    per-tensor scale to its largest finite value first (the usual recipe).
    The rounding is straight-through for gradients: the products see rounded
    operands in the forward and the backward pass, the gradient signal itself
    stays float32 (rounding it on the operand's scale underflows to zero)."""
    if dtype == jnp.bfloat16:
        q = x.astype(jnp.bfloat16).astype(jnp.float32)
    else:
        top = float(jnp.finfo(dtype).max)
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
        q = (x / scale).astype(dtype).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def matmul_in(precision: str):
    """einsum(eq, a, b) computing in `precision`: "float32" (highest),
    "bfloat16" or "float8" (operands rounded, float32 accumulation)."""
    if precision == "float32":
        return _mm_f32
    dtype = {"bfloat16": jnp.bfloat16, "float8": jnp.float8_e4m3fn}[precision]

    def mm(eq, a, b):
        return _mm_f32(eq, _round_to(a, dtype), _round_to(b, dtype))

    return mm


# ---------------------------------------------------------------- forward


def patchify(img, p):
    b, c, H, W = img.shape
    h, w = H // p, W // p
    x = img.reshape(b, c, h, p, w, p).transpose(0, 2, 4, 3, 5, 1)
    return x.reshape(b, h * w, p * p * c)


def unpatchify(x, p, image_size, c):
    b = x.shape[0]
    h = image_size // p
    x = x.reshape(b, h, h, p, p, c).transpose(0, 5, 1, 3, 2, 4)
    return x.reshape(b, c, h * p, h * p)


def local_mask(side: int, radius: float):
    """[n, n] bool, True = masked (farther than `radius`); None at radius 0."""
    if radius <= 0:
        return None
    hs, ws = np.meshgrid(np.arange(side), np.arange(side), indexing="ij")
    coords = np.stack([hs, ws], -1).reshape(-1, 2).astype(np.float64)
    dist = np.linalg.norm(coords[:, None] - coords[None, :], axis=-1)
    return dist > radius


def _gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def _ffw(mm, w1, b1, w2, b2, x):
    h = _gelu(mm("bngd,gdf->bngf", x, w1) + b1)
    return mm("bngf,gfd->bngd", h, w2) + b2


def _consensus(mm, levels, mask, attend_self):
    b, n, L, d = levels.shape
    norm = jnp.sqrt(jnp.sum(levels * levels, axis=-1, keepdims=True))
    k = levels / jnp.maximum(norm, 1e-12)
    sim = mm("bild,bjld->blij", levels, k) * (d ** -0.5)
    if not attend_self:
        sim = jnp.where(jnp.eye(n, dtype=bool)[None, None], SELF_VALUE, sim)
    if mask is not None:
        sim = jnp.where(jnp.asarray(mask)[None, None],
                        -jnp.finfo(jnp.float32).max, sim)
    attn = jax.nn.softmax(sim, axis=-1)
    return mm("blij,bjld->bild", attn, levels)


def forward(w, img, model, iters, *, levels0=None, precision="float32"):
    """[b, c, H, W] -> column state [b, n, L, d] after `iters` updates from
    the cold start (or from `levels0`)."""
    mm = matmul_in(precision)
    L, p = model["levels"], model["patch_size"]
    side = model["image_size"] // p
    mask = local_mask(side, model.get("local_consensus_radius", 0))
    attend_self = bool(model.get("consensus_self", False))
    tokens = mm("bnp,pd->bnd", patchify(img, p), w["token_w"]) + w["token_b"]
    b, n, d = tokens.shape
    pos = w["pos_emb"][None, :, None, :]
    bottom = tokens[:, :, None, :]
    levels = (jnp.broadcast_to(w["init_levels"][None, None], (b, n, L, d))
              if levels0 is None else levels0)
    divisor = jnp.asarray([4.0] * (L - 1) + [3.0], jnp.float32)[:, None]
    for _ in range(iters):
        with_input = jnp.concatenate([bottom, levels], axis=2)
        bu = _ffw(mm, w["bu_w1"], w["bu_b1"], w["bu_w2"], w["bu_b2"],
                  with_input[:, :, :-1])
        td = _ffw(mm, w["td_w1"], w["td_b1"], w["td_w2"], w["td_b2"],
                  with_input[:, :, 2:] + pos)
        td = jnp.concatenate([td, jnp.zeros_like(td[:, :, :1])], axis=2)
        cons = _consensus(mm, levels, mask, attend_self)
        levels = (levels + bu + td + cons) / divisor
    return levels


def denoise_loss(w, img, noise, model, *, precision="float32"):
    """MSE between the clean image and the reconstruction read from the top
    level after T//2 + 1 iterations on the noised image."""
    iters = (2 * model["levels"]) // 2 + 1
    top = forward(w, img + noise, model, iters, precision=precision)[:, :, -1]
    mm = matmul_in(precision)
    recon = unpatchify(mm("bnd,dp->bnp", top, w["pix_w"]) + w["pix_b"],
                       model["patch_size"], model["image_size"],
                       model["channels"])
    return jnp.mean((img - recon) ** 2)


# ------------------------------------------------------- loss, grads, Adam


@functools.partial(jax.jit, static_argnums=(3, 4))
def _block_value_and_grad(w, img, noise, model_items, precision):
    model = dict(model_items)
    return jax.value_and_grad(
        lambda ww: denoise_loss(ww, img, noise, model, precision=precision)
    )(w)


def loss_and_grads(w, img, noise, model, *, block_rows, precision="float32",
                   devices=None):
    """Loss and gradients over the whole batch, computed in blocks of rows so
    that the float32 residuals of one block are all that is live. Equal
    blocks, so the mean of block means is the batch mean. With several
    `devices` the blocks are dealt round-robin over them (same arithmetic,
    the blocks of a four-chip cell's batch of 256 in a quarter of the time)."""
    b = img.shape[0]
    if b % block_rows:
        raise ValueError(f"batch {b} is not a multiple of block_rows {block_rows}")
    items = tuple(sorted((k, v) for k, v in model.items()
                         if isinstance(v, (int, float, bool))))
    devices = list(devices) if devices else [None]
    w_on = [w if d is None else jax.device_put(w, d) for d in devices]
    nb = b // block_rows
    parts = []
    for i in range(nb):
        sl = slice(i * block_rows, (i + 1) * block_rows)
        d = devices[i % len(devices)]
        bi, bn = img[sl], noise[sl]
        if d is not None:
            bi, bn = jax.device_put(bi, d), jax.device_put(bn, d)
        parts.append(_block_value_and_grad(w_on[i % len(devices)], bi, bn,
                                           items, precision))
    home = devices[0]
    loss, grads = 0.0, None
    for l, g in parts:
        if home is not None:
            l, g = jax.device_put(l, home), jax.device_put(g, home)
        loss = loss + l / nb
        grads = (jax.tree_util.tree_map(lambda t: t / nb, g) if grads is None
                 else jax.tree_util.tree_map(lambda a, t: a + t / nb, grads, g))
    return loss, grads


@jax.jit
def adam_update(w, mu, nu, grads, t, lr):
    """One step of Adam (Kingma & Ba) with bias correction; t counts from 1."""
    mu = jax.tree_util.tree_map(lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g, mu, grads)
    nu = jax.tree_util.tree_map(lambda v, g: ADAM_B2 * v + (1 - ADAM_B2) * g * g, nu, grads)
    c1, c2 = 1 - ADAM_B1 ** t, 1 - ADAM_B2 ** t
    w = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + ADAM_EPS), w, mu, nu)
    return w, mu, nu


def leaf_norms(tree) -> dict:
    return {k: float(jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))))
            for k, v in tree.items()}


def train_reference(w0, batches, noises, model, *, lr, block_rows,
                    precision="float32", devices=None):
    """Follow the first len(batches) training steps. Returns each step's
    loss, the per-leaf norm of the first gradient, and the per-leaf norm of
    the parameters' change over all the steps."""
    w = w0
    mu = jax.tree_util.tree_map(jnp.zeros_like, w0)
    nu = jax.tree_util.tree_map(jnp.zeros_like, w0)
    losses, first_grad = [], None
    for t, (img, noise) in enumerate(zip(batches, noises), start=1):
        loss, grads = loss_and_grads(w, img, noise, model, block_rows=block_rows,
                                     precision=precision, devices=devices)
        losses.append(float(loss))
        if first_grad is None:
            first_grad = leaf_norms(grads)
            first_grad_full = {k: np.asarray(v, np.float32) for k, v in grads.items()}
        w, mu, nu = adam_update(w, mu, nu, grads, jnp.float32(t), jnp.float32(lr))
    delta = leaf_norms({k: w[k] - w0[k] for k in w0})
    return {"losses": losses, "first_grad_norms": first_grad,
            "first_grad": first_grad_full, "delta_norms": delta}


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _forward_jit(w, img, model_items, iters, precision):
    return forward(w, img, dict(model_items), iters, precision=precision)


def serve_reference(w, img, model, iters, *, precision="float32"):
    """Column state of `img` [b, c, H, W] after `iters` iterations from the
    cold start, as a served request that ran `iters` iterations should
    return it."""
    items = tuple(sorted((k, v) for k, v in model.items()
                         if isinstance(v, (int, float, bool))))
    return _forward_jit(w, img, items, int(iters), precision)
