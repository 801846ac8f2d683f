"""Seeded weights of the Kimi Linear language model, made on the device in
one jitted call, as a flat dict: `embed`, `final_norm`, `head`, and
`L<i>.<leaf>` for layer i of those held.

The benchmark makes the weights, not the program: the same dict feeds the
system under test (installed the way a checkpoint resume installs a state)
and the plain reference. The families are the ones the configuration file
states under `assumed`: matrices normal with std 0.02, the out-projections
(the mixers' `o`, every `*_down`) scaled by 1/sqrt(2 x published layers); the
convolutions uniform in +-1/sqrt(taps); `A_log` the log of uniform(1, 16) a
head; `dt_bias` the inverse softplus of a log-uniform time step in [1e-3,
1e-1] a channel; the norms' weights normal round one with std 0.02, so that
none is a no-op in the comparison.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference.kimi_linear_ref import layer_kinds
from benchmark.weights_lm import (  # noqa: F401  (the same key and the same trees)
    from_program_params,
    seed_key,
    to_program_params,
)

TIME_STEP_MIN, TIME_STEP_MAX = 1e-3, 1e-1


def shapes(model: dict) -> dict:
    """{leaf name: shape} of everything this chip holds."""
    d, v = model["hidden_size"], model["vocab_size"]
    h, dk, taps = model["linear_num_heads"], model["linear_head_dim"], model[
        "short_conv_kernel_size"]
    w = h * dk
    ha, lat = model["num_attention_heads"], model["kv_lora_rank"]
    nope, rope, dv = model["qk_nope_head_dim"], model["qk_rope_head_dim"], model["v_head_dim"]
    f, e, fe = model["intermediate_size"], model["num_experts"], model["moe_intermediate_size"]
    fs = fe * model["num_shared_experts"]
    mixer = {"K": {"norm1": (d,), "q": (d, w), "k": (d, w), "v": (d, w), "conv_q": (w, taps),
                   "conv_k": (w, taps), "conv_v": (w, taps), "f1": (d, dk), "f2": (dk, w),
                   "dt_bias": (w,), "A_log": (h,), "beta": (d, h), "g1": (d, dk), "g2": (dk, w),
                   "onorm": (dk,), "o": (w, d)},
             "A": {"norm1": (d,), "q": (d, ha * (nope + rope)), "kva": (d, lat + rope),
                   "kv_norm": (lat,), "kvb": (lat, ha * (nope + dv)), "o": (ha * dv, d)}}
    mlp = {"D": {"norm2": (d,), "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)},
           "E": {"norm2": (d,), "router": (d, model["num_experts_total"]), "e_gate": (e, d, fe),
                 "e_up": (e, d, fe), "e_down": (e, fe, d), "s_gate": (d, fs), "s_up": (d, fs),
                 "s_down": (fs, d)}}
    out = {"embed": (v, d)}
    for i, (mixer_kind, mlp_kind) in enumerate(layer_kinds(model)):
        out.update({f"L{i:02d}.{k}": s for k, s in {**mixer[mixer_kind], **mlp[mlp_kind]}.items()})
    out.update({"final_norm": (d,), "head": (d, v)})
    return out


def _leaf(key, name: str, shape, model: dict):
    leaf = name.rpartition(".")[2]
    if "norm" in leaf:
        return 1.0 + 0.02 * jax.random.normal(key, shape, jnp.float32)
    if leaf.startswith("conv_"):
        bound = model["short_conv_kernel_size"] ** -0.5
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    if leaf == "dt_bias":
        lo, hi = math.log(TIME_STEP_MIN), math.log(TIME_STEP_MAX)
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32) * (hi - lo) + lo)
        return dt + jnp.log(-jnp.expm1(-dt))
    if leaf == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
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
