"""Seeded weights of the Ouro looped language model, made on the device in one
jitted call, as a flat dict: `embed`, `final_norm`, `head`, `gate_w`,
`gate_b`, and `L<i>.<leaf>` for layer i of those held.

The benchmark makes the weights, not the program: the same dict feeds the
system under test (installed the way a checkpoint resume installs a state)
and the plain reference. The families are the ones the configuration file
states under `assumed`: matrices normal with std 0.02, the out-projections
(`o`, `w_down`) scaled by 1/sqrt(2 x published layers); the four norms of a
layer and the closing norm normal round 1 with std 0.02, so that none is a
no-op in the comparison; the gate's direction normal with std 1/sqrt(hidden),
so that `h . gate_w` has std about 1 behind the closing norm and no lambda
sits at 1/2, and its bias normal with std 0.5.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark import weights_lm
from benchmark.weights_lm import seed_key  # noqa: F401  (the same key)

GATE = ("gate_w", "gate_b")


def shapes(model: dict) -> dict:
    """{leaf name: shape} of everything this chip holds."""
    d, f, v = model["hidden_size"], model["intermediate_size"], model["vocab_size"]
    q = model["num_attention_heads"] * model["head_dim"]
    kv = model["num_key_value_heads"] * model["head_dim"]
    layer = {"norm1": (d,), "q": (d, q), "k": (d, kv), "v": (d, kv), "o": (q, d), "norm2": (d,),
             "norm3": (d,), "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d), "norm4": (d,)}
    out = {"embed": (v, d)}
    for i in range(model["num_hidden_layers"]):
        out.update({f"L{i:02d}.{k}": s for k, s in layer.items()})
    out.update({"final_norm": (d,), "head": (d, v), "gate_w": (d,), "gate_b": (1,)})
    return out


def _leaf(key, name: str, shape, model: dict):
    leaf = name.rpartition(".")[2]
    draw = jax.random.normal(key, shape, jnp.float32)
    if "norm" in leaf:
        return 1.0 + 0.02 * draw
    if leaf in GATE:
        return draw * (0.5 if leaf == "gate_b" else model["hidden_size"] ** -0.5)
    std = 0.02
    if leaf in ("o", "w_down"):
        std /= math.sqrt(2.0 * model["num_hidden_layers_total"])
    return std * draw


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


def to_program_params(w: dict) -> dict:
    """The flat dict as the program's tree: the language models' four parts
    and the gate's two leaves beside them."""
    return {**weights_lm.to_program_params(w), **{k: w[k] for k in GATE}}


def from_program_params(p: dict) -> dict:
    return {**weights_lm.from_program_params(p), **{k: p[k] for k in GATE}}
