"""Seeded weights of the Laguna language model, made on the device in one
jitted call, as a flat dict: `embed`, `final_norm`, `head`, and `L<i>.<leaf>`
for layer i of those held.

The benchmark makes the weights, not the program: the same dict feeds the
system under test (installed the way a checkpoint resume installs a state)
and the plain reference. The families are the ones the configuration file
states under `assumed`: matrices normal with std 0.02, the out-projections
(attention `o`, every `*_down`) scaled by 1/sqrt(2 x published layers); the
norms' weights normal round one with std 0.02, so that none is a no-op in the
comparison.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference.laguna_ref import layer_kinds, query_heads
from benchmark.weights_lm import (  # noqa: F401  (the same key and the same trees)
    from_program_params,
    seed_key,
    to_program_params,
)


def shapes(model: dict) -> dict:
    """{leaf name: shape} of everything this chip holds."""
    d, v, dh = model["hidden_size"], model["vocab_size"], model["head_dim"]
    kv = model["num_key_value_heads"] * dh
    f, e = model["intermediate_size"], model["num_experts"]
    fe, fs = model["moe_intermediate_size"], model["shared_expert_intermediate_size"]
    mlp = {"D": {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)},
           "E": {"router": (d, model["num_experts_total"]), "e_gate": (e, d, fe),
                 "e_up": (e, d, fe), "e_down": (e, fe, d), "s_gate": (d, fs), "s_up": (d, fs),
                 "s_down": (fs, d)}}
    out = {"embed": (v, d)}
    for i, (attention, mlp_kind) in enumerate(layer_kinds(model)):
        heads = query_heads(attention, model)
        layer = {"norm1": (d,), "q": (d, heads * dh), "k": (d, kv), "v": (d, kv),
                 "gate": (d, heads), "o": (heads * dh, d), "norm2": (d,), **mlp[mlp_kind]}
        out.update({f"L{i:02d}.{k}": s for k, s in layer.items()})
    out.update({"final_norm": (d,), "head": (d, v)})
    return out


def _leaf(key, name: str, shape, model: dict):
    leaf = name.rpartition(".")[2]
    if leaf.startswith("norm") or leaf == "final_norm":
        return 1.0 + 0.02 * jax.random.normal(key, shape, jnp.float32)
    std = 0.02
    if leaf == "o" or leaf.endswith("_down"):
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
