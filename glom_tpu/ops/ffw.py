"""Grouped per-level feed-forward network.

Reference parity: GroupedFeedForward (glom_pytorch/glom_pytorch.py:21-34).
The reference implements "one independent d -> d*mult -> d MLP per level" via a
reshape + Conv1d(groups=L) trick. On TPU that trick is an anti-pattern (1x1
grouped convs map poorly onto the MXU); the idiomatic equivalent is a single
batched einsum over stacked per-level weight tensors:

    h   = gelu(einsum('...gd,gdf->...gf', x, w1) + b1)
    out =      einsum('...gf,gfd->...gd', h, w2) + b2

with weights [G, d, d*mult] / [G, d*mult, d]. This is bit-for-bit the same math
(each group g sees only its own slice — no cross-level mixing) but lets XLA
tile one large batched matmul onto the systolic array instead of L small ones.

Used twice by the model: bottom_up (groups = L) and top_down (groups = L-1).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class GroupedFFWParams(NamedTuple):
    """Per-group MLP weights. Leading axis = group (level)."""

    w1: jnp.ndarray  # [G, d, d*mult]
    b1: jnp.ndarray  # [G, d*mult]
    w2: jnp.ndarray  # [G, d*mult, d]
    b2: jnp.ndarray  # [G, d]


def init_grouped_ffw(
    key: jax.Array, groups: int, dim: int, mult: int = 4, dtype=jnp.float32
) -> GroupedFFWParams:
    """Fan-in-scaled uniform init (the same family as torch Conv1d's default:
    U(-1/sqrt(fan_in), 1/sqrt(fan_in)), where grouped-conv fan_in is the
    per-group channel count)."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    hidden = dim * mult
    s1 = 1.0 / jnp.sqrt(dim)
    s2 = 1.0 / jnp.sqrt(hidden)
    return GroupedFFWParams(
        w1=jax.random.uniform(k1, (groups, dim, hidden), dtype, -s1, s1),
        b1=jax.random.uniform(k2, (groups, hidden), dtype, -s1, s1),
        w2=jax.random.uniform(k3, (groups, hidden, dim), dtype, -s2, s2),
        b2=jax.random.uniform(k4, (groups, dim), dtype, -s2, s2),
    )


def grouped_ffw(
    params: GroupedFFWParams,
    x: jnp.ndarray,
    *,
    compute_dtype=None,
) -> jnp.ndarray:
    """Apply the per-group MLP.

    x: [..., G, d]  ->  [..., G, d], no mixing across the G axis.

    GELU is the exact (erf) variant, matching the reference's nn.GELU default.
    Matmuls accumulate in float32 via preferred_element_type so bfloat16
    compute stays numerically safe on the MXU.
    """
    w1, b1, w2, b2 = params
    if compute_dtype is not None:
        x = x.astype(compute_dtype)
        w1, b1, w2, b2 = (t.astype(compute_dtype) for t in (w1, b1, w2, b2))
    # Always accumulate in float32 (2048-term contractions in bf16 lose
    # digits, and off-TPU backends honor the accumulation dtype literally).
    # The bf16-traffic win comes from the astype below, which XLA fuses into
    # the matmul epilogue — the [..., G, 4d] hidden tensor hits HBM in bf16.
    acc = jnp.float32
    h = jnp.einsum("...gd,gdf->...gf", x, w1, preferred_element_type=acc)
    h = h + b1
    h = jax.nn.gelu(h, approximate=False)
    h = h.astype(x.dtype)
    out = jnp.einsum("...gf,gfd->...gd", h, w2, preferred_element_type=acc)
    out = out + b2
    return out.astype(x.dtype)


def grouped_ffw_lm(
    params: GroupedFFWParams, x: jnp.ndarray, *, add: jnp.ndarray | None = None
) -> jnp.ndarray:
    """Level-major form: x [G, M, d] -> [G, M, d]. Same math as grouped_ffw
    (group axis leading instead of next-to-last) — the layout the fused
    kernel and the level-major scan carry use natively. add: optional
    [n, d] positional addend with M = b*n (n inner), summed into x first
    (the signature kernels.fused_grouped_ffw_lm folds into its loads)."""
    w1, b1, w2, b2 = params
    if add is not None:
        x = x + jnp.tile(add, (x.shape[1] // add.shape[0], 1))[None]
    acc = jnp.float32
    h = jnp.einsum("gmd,gdf->gmf", x, w1, preferred_element_type=acc)
    h = jax.nn.gelu(h + b1[:, None, :], approximate=False).astype(x.dtype)
    out = jnp.einsum("gmf,gfd->gmd", h, w2, preferred_element_type=acc)
    return (out + b2[:, None, :]).astype(x.dtype)
