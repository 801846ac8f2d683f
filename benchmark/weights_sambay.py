"""Seeded weights of the SambaY language model, made on the device in one
jitted call, as a flat dict: `embed`, `final_norm_w`, `final_norm_b`, and
`L<i>.<leaf>` for layer i of those held.

The benchmark makes the weights, not the program: the same dict feeds the
system under test (installed the way a checkpoint resume installs a state)
and the plain reference. The families are the ones the configuration file
states under `assumed`: matrices normal with std 0.02, every out-projection
(`out_proj`, `o`, `down`) scaled by 1/sqrt(2 x published layers); the norms'
weights one; every bias, the norms' among them, normal with std 0.02, so that
none is a no-op in the comparison; the lambda vectors normal with std 0.1;
the conv uniform in +-1/sqrt(kernel) and the step projection in
+-1/sqrt(dt_rank); `dt_bias` the inverse softplus of a log-uniform time
step; `A_log` the log of 1..d_state in every channel; `D` one.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference.sambay_ref import layer_kinds
from benchmark.weights_lm import seed_key  # noqa: F401  (the same key for a seed)


def shapes(model: dict) -> dict:
    """{leaf name: shape} of everything this chip holds."""
    d, f, v = model["hidden_size"], model["intermediate_size"], model["vocab_size"]
    di, n, k = model["mamba_expand"] * d, model["mamba_d_state"], model["mamba_d_conv"]
    rank = -(-d // 16)
    dh = d // model["num_attention_heads"]
    q, kv = model["num_attention_heads"] * dh, model["num_key_value_heads"] * dh
    diff = {"lambda_q1": (dh,), "lambda_k1": (dh,), "lambda_q2": (dh,), "lambda_k2": (dh,),
            "subln": (2 * dh,), "o": (q, d), "o_b": (d,)}
    own = {"qkv": (d, q + 2 * kv), "qkv_b": (q + 2 * kv,), **diff}
    mixer = {
        "M": {"in_proj": (d, 2 * di), "conv_w": (di, k), "conv_b": (di,),
              "x_proj": (di, rank + 2 * n), "dt_proj": (rank, di), "dt_bias": (di,),
              "A_log": (di, n), "D": (di,), "out_proj": (di, d)},
        "W": own, "F": own,
        "G": {"in_proj": (d, di), "out_proj": (di, d)},
        "X": {"q": (d, q), "q_b": (q,), **diff},
    }
    out = {"embed": (v, d)}
    for i, kind in enumerate(layer_kinds(model)):
        leaves = {"norm1_w": (d,), "norm1_b": (d,), **mixer[kind],
                  "norm2_w": (d,), "norm2_b": (d,), "gate_up": (d, 2 * f), "down": (f, d)}
        out.update({f"L{i:02d}.{name}": s for name, s in leaves.items()})
    out.update({"final_norm_w": (d,), "final_norm_b": (d,)})
    return out


def _leaf(key, name: str, shape, model: dict):
    leaf = name.rpartition(".")[2]
    if leaf in ("norm1_w", "norm2_w", "final_norm_w", "subln", "D"):
        return jnp.ones(shape, jnp.float32)
    if leaf.startswith("lambda_"):
        return 0.1 * jax.random.normal(key, shape, jnp.float32)
    if leaf in ("conv_w", "dt_proj"):
        bound = (model["mamba_d_conv"] if leaf == "conv_w" else shape[0]) ** -0.5
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    if leaf == "dt_bias":
        lo, hi = math.log(model["time_step_min"]), math.log(model["time_step_max"])
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32) * (hi - lo) + lo)
        dt = jnp.maximum(dt, model["time_step_floor"])
        return dt + jnp.log(-jnp.expm1(-dt))
    if leaf == "A_log":
        return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32)), shape)
    std = 0.02
    if leaf in ("out_proj", "o", "down"):
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


def to_program_params(w: dict) -> dict:
    """The flat dict as the program's tree: {"embed", "layers": (one dict a
    layer), "final_norm_w", "final_norm_b"}."""
    n = 1 + max(int(k[1:3]) for k in w if k[0] == "L" and k[3:4] == ".")
    layers = tuple({k[4:]: v for k, v in w.items() if k.startswith(f"L{i:02d}.")}
                   for i in range(n))
    return {"embed": w["embed"], "layers": layers, "final_norm_w": w["final_norm_w"],
            "final_norm_b": w["final_norm_b"]}


def from_program_params(p: dict) -> dict:
    out = {k: p[k] for k in ("embed", "final_norm_w", "final_norm_b")}
    for i, layer in enumerate(p["layers"]):
        out.update({f"L{i:02d}.{k}": v for k, v in layer.items()})
    return out
