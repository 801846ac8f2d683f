"""Seeded weights, made on the device in one jitted call.

The benchmark makes the weights, not the program: the same dict feeds the
system under test (installed the way a checkpoint resume installs a state)
and the plain reference, which may take nothing the program has made. The
families are the architecture's published init (fan-in uniform for linear
and grouped MLP weights, unit normal for the position table and the
initial columns).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LEAVES = (
    "token_w", "token_b", "pos_emb", "init_levels",
    "bu_w1", "bu_b1", "bu_w2", "bu_b2",
    "td_w1", "td_b1", "td_w2", "td_b2",
    "pix_w", "pix_b",
)


def _shapes(model: dict) -> dict:
    d, L, m = model["dim"], model["levels"], model["mult"]
    p, c = model["patch_size"], model["channels"]
    n = (model["image_size"] // p) ** 2
    f, pd = d * m, p * p * c
    return {
        "token_w": ((pd, d), pd), "token_b": ((d,), pd),
        "pos_emb": ((n, d), None), "init_levels": ((L, d), None),
        "bu_w1": ((L, d, f), d), "bu_b1": ((L, f), d),
        "bu_w2": ((L, f, d), f), "bu_b2": ((L, d), f),
        "td_w1": ((L - 1, d, f), d), "td_b1": ((L - 1, f), d),
        "td_w2": ((L - 1, f, d), f), "td_b2": ((L - 1, d), f),
        "pix_w": ((d, pd), d), "pix_b": ((pd,), d),
    }


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, model_items):
    model = dict(model_items)
    out = {}
    for i, (name, (shape, fan_in)) in enumerate(_shapes(model).items()):
        k = jax.random.fold_in(key, i)
        if fan_in is None:
            out[name] = jax.random.normal(k, shape, jnp.float32)
        else:
            s = fan_in ** -0.5
            out[name] = jax.random.uniform(k, shape, jnp.float32, -s, s)
    return out


def make_weights(seed: int, model: dict) -> dict:
    """All 14 leaves in float32 (the trainer's master type; the serve engine
    casts to its serving type inside its programs)."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed % (2**32)), 0x77)
    items = tuple(sorted((k, v) for k, v in model.items() if isinstance(v, (int, float, bool))))
    return _make(key, items)
