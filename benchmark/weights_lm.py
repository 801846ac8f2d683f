"""Seeded weights of the hybrid language model, made on the device in one
jitted call, as a flat dict: `embed`, `final_norm`, `head`, and `L<i>.<leaf>`
for layer i of the pattern held.

The benchmark makes the weights, not the program: the same dict feeds the
system under test (installed the way a checkpoint resume installs a state)
and the plain reference. The families are the ones the configuration file
states under `assumed`: matrices normal with std 0.02, the Mamba-2 and
attention out-projections scaled by 1/sqrt(2 x published layers); norms one;
the conv uniform in +-1/sqrt(kernel); `dt_bias` the inverse softplus of a
log-uniform time step; `A_log` the log of uniform(1, 16); `D` one.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def layer_kinds(model: dict) -> str:
    """The mixers of the layers held, in order: a slice of the published pattern."""
    first = model["layer_offset"]
    return model["hybrid_override_pattern"][first:first + model["num_hidden_layers"]]


def shapes(model: dict) -> dict:
    """{leaf name: shape} of everything this chip holds."""
    d, v = model["hidden_size"], model["vocab_size"]
    heads, p = model["mamba_num_heads"], model["mamba_head_dim"]
    g, n = model["n_groups"], model["ssm_state_size"]
    di, conv = heads * p, heads * p + 2 * g * n
    q = model["num_attention_heads"] * model["head_dim"]
    kv = model["num_key_value_heads"] * model["head_dim"]
    e, lat = model["n_routed_experts"], model["moe_latent_size"]
    f, fs = model["moe_intermediate_size"], model["moe_shared_expert_intermediate_size"]
    per_kind = {
        "M": {"norm": (d,), "in_proj": (d, 2 * di + 2 * g * n + heads),
              "conv_w": (conv, model["conv_kernel"]), "conv_b": (conv,), "dt_bias": (heads,),
              "A_log": (heads,), "D": (heads,), "gnorm": (di,), "out_proj": (di, d)},
        "*": {"norm": (d,), "q": (d, q), "k": (d, kv), "v": (d, kv), "o": (q, d)},
        "E": {"norm": (d,), "router": (d, model["n_routed_experts_total"]), "down": (d, lat),
              "up": (lat, d), "w1": (e, lat, f), "w2": (e, f, lat), "s1": (d, fs),
              "s2": (fs, d)},
    }
    out = {"embed": (v, d)}
    for i, kind in enumerate(layer_kinds(model)):
        out.update({f"L{i:02d}.{k}": s for k, s in per_kind[kind].items()})
    out.update({"final_norm": (d,), "head": (d, v)})
    return out


def _leaf(key, name: str, shape, model: dict):
    leaf = name.rpartition(".")[2]
    if leaf in ("norm", "gnorm", "final_norm", "D"):
        return jnp.ones(shape, jnp.float32)
    if leaf in ("conv_w", "conv_b"):
        bound = model["conv_kernel"] ** -0.5
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    if leaf == "dt_bias":
        lo, hi = math.log(model["time_step_min"]), math.log(model["time_step_max"])
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32) * (hi - lo) + lo)
        dt = jnp.maximum(dt, model["time_step_floor"])
        return dt + jnp.log(-jnp.expm1(-dt))
    if leaf == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    std = 0.02
    if leaf in ("out_proj", "o"):
        std /= math.sqrt(2.0 * model["num_hidden_layers_total"])
    return std * jax.random.normal(key, shape, jnp.float32)


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, model_items):
    model = dict(model_items)
    return {name: _leaf(jax.random.fold_in(key, i), name, shape, model)
            for i, (name, shape) in enumerate(shapes(model).items())}


def seed_key(seed: int):
    """The key the weights are drawn from. Made outside any jitted program
    and passed in as data, so that one compiled program serves every seed."""
    return jax.random.fold_in(jax.random.PRNGKey(int(seed) % (2**32)), 0x6C6D)


def weights_from_key(key, model: dict) -> dict:
    items = tuple(sorted((k, v) for k, v in model.items()
                         if isinstance(v, (int, float, bool, str))))
    return _make(key, items)


def make_weights(seed: int, model: dict) -> dict:
    """Every leaf in float32, the trainer's master type."""
    return weights_from_key(seed_key(seed), model)


def to_program_params(w: dict) -> dict:
    """The flat dict as the program's tree: {"embed", "layers": (one dict a
    layer), "final_norm", "head"}."""
    n = 1 + max(int(k[1:3]) for k in w if k[0] == "L" and k[3:4] == ".")
    layers = tuple({k[4:]: v for k, v in w.items() if k.startswith(f"L{i:02d}.")}
                   for i in range(n))
    return {"embed": w["embed"], "layers": layers, "final_norm": w["final_norm"],
            "head": w["head"]}


def from_program_params(p: dict) -> dict:
    out = {"embed": p["embed"], "final_norm": p["final_norm"], "head": p["head"]}
    for i, layer in enumerate(p["layers"]):
        out.update({f"L{i:02d}.{k}": v for k, v in layer.items()})
    return out
