"""Operation counts the roofline share divides: what the loop of one
column iteration of one image needs, from the configuration's shapes alone.

The program's `utils/metrics.flops_per_column_iter` counts consensus over all
n*n pairs whatever `local_consensus_radius` is. A roofline numerator counts
what the algorithm needs, and with a local window the masked pairs need
nothing, so consensus is counted here over unmasked pairs only. At radius 0
the two counts are equal (checked in tests/test_flops.py).
"""

from __future__ import annotations

import numpy as np


def unmasked_pairs(side: int, radius: float) -> int:
    """Number of (i, j) patch pairs consensus attends over: all n*n at
    radius 0, else those within Euclidean grid distance `radius` (the
    program's `build_local_mask` keeps dist <= radius)."""
    n = side * side
    if radius <= 0:
        return n * n
    r = int(np.floor(radius))
    # offsets (dh, dw) inside the disc, each valid for (side-|dh|)*(side-|dw|) anchors
    total = 0
    for dh in range(-r, r + 1):
        for dw in range(-r, r + 1):
            if dh * dh + dw * dw <= radius * radius:
                total += max(0, side - abs(dh)) * max(0, side - abs(dw))
    return total


def ffw_flops_per_col_iter(model: dict) -> float:
    """Bottom-up (L groups) and top-down (L-1 groups) MLPs, two matmuls
    each, 2 FLOPs a multiply-add, forward only, one image."""
    side = model["image_size"] // model["patch_size"]
    n, L, d, m = side * side, model["levels"], model["dim"], model["mult"]
    return float(2 * 2 * n * (2 * L - 1) * d * (d * m))


def consensus_flops_per_col_iter(model: dict) -> float:
    """q.k^T and attn.v over the unmasked pairs, per level, forward only."""
    side = model["image_size"] // model["patch_size"]
    pairs = unmasked_pairs(side, model.get("local_consensus_radius", 0))
    return float(2 * 2 * model["levels"] * pairs * model["dim"])


def loop_flops_per_col_iter(model: dict) -> float:
    return ffw_flops_per_col_iter(model) + consensus_flops_per_col_iter(model)


def train_loop_flops_per_step(model: dict, batch: int, loop_iters: int) -> float:
    """Forward plus backward (2x forward) of the loop of one training step.
    Recomputed operations do not count."""
    return 3.0 * batch * loop_iters * loop_flops_per_col_iter(model)


def train_loop_iters(model: dict) -> int:
    """Loop iterations a training step executes: the denoising loss reads the
    state at index T//2 + 1 of T = 2L, and later iterations are dead code."""
    return (2 * model["levels"]) // 2 + 1
