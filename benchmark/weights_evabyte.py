"""Seeded weights of the EvaByte language model, made on the device in one
jitted call, as a flat dict: `embed`, `final_norm`, `head`, and `L<i>.<leaf>`
for layer i of those held.

The benchmark makes the weights, not the program: the same dict feeds the
system under test (installed the way a checkpoint resume installs a state)
and the plain reference. The families are the ones the configuration file
states under `assumed`: matrices normal with std 0.01275, the out-projections
(`o`, `w_down`) scaled by 1/sqrt(2 x published layers); the summariser's `phi`
and `mu` normal clipped to +-1 (a chunk's 16 weights are then far from
uniform: a key's entries have std 0.8 behind a normed input, so its scaled
product with `phi` has std 0.7); the norms' weights normal round zero with
std 0.02 (they multiply by 1 + weight), so that none is a no-op in the
comparison.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.weights_lm import (  # noqa: F401  (the same key and the same trees)
    from_program_params,
    seed_key,
    to_program_params,
)

INIT_STD = 0.01275


def shapes(model: dict) -> dict:
    """{leaf name: shape} of everything this chip holds."""
    d, f, v = model["hidden_size"], model["intermediate_size"], model["vocab_size"]
    h, dh = model["num_attention_heads"], model["head_dim"]
    layer = {"norm1": (d,), "q": (d, h * dh), "k": (d, h * dh), "v": (d, h * dh),
             "phi": (h, dh), "mu": (h, dh), "o": (h * dh, d),
             "norm2": (d,), "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}
    out = {"embed": (v, d)}
    for i in range(model["num_hidden_layers"]):
        out.update({f"L{i:02d}.{k}": s for k, s in layer.items()})
    out.update({"final_norm": (d,), "head": (d, model["num_pred_heads"] * v)})
    return out


def _leaf(key, name: str, shape, model: dict):
    leaf = name.rpartition(".")[2]
    if "norm" in leaf:
        return 0.02 * jax.random.normal(key, shape, jnp.float32)
    if leaf in ("phi", "mu"):
        return jnp.clip(jax.random.normal(key, shape, jnp.float32), -1.0, 1.0)
    std = INIT_STD
    if leaf in ("o", "w_down"):
        std /= math.sqrt(2.0 * model["num_hidden_layers_total"])
    return std * jax.random.normal(key, shape, jnp.float32)


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, model_items):
    model = dict(model_items)
    return {name: _leaf(jax.random.fold_in(key, i), name, shape, model)
            for i, (name, shape) in enumerate(shapes(model).items())}


def weights_from_key(key, model: dict) -> dict:
    items = tuple(sorted((k, v) for k, v in model.items()
                         if isinstance(v, (int, float, bool, str))))
    return _make(key, items)


def make_weights(seed: int, model: dict) -> dict:
    """Every leaf in float32, the trainer's master type."""
    return weights_from_key(seed_key(seed), model)
