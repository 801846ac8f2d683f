"""Ulysses-style sequence parallelism for consensus attention.

Consensus attention is INDEPENDENT per level (sim is [b, L, n, n] with no
cross-level terms — reference :58), so the L axis plays exactly the role
heads play in Ulysses: an `all_to_all` trades n-sharding for L-sharding,
each shard runs the plain dense attention over the FULL patch axis for its
L/S levels, and a second all_to_all restores n-sharding. Exact (not an
approximation), two collectives per call, and the inner op is the
well-fused dense kernel.

Prefer this when L % S == 0 and n^2 * L/S fits in memory; prefer the ring
(ring.py) when n is huge or L is small/indivisible.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
from jax import lax

from glom_tpu.ops.consensus import consensus_attention


def ulysses_consensus_shard(
    x,
    *,
    axis_name: str,
    attend_self: bool,
    side: Optional[int] = None,
    radius: float = 0.0,
):
    """Per-shard body (under shard_map, n sharded over `axis_name`).

    x: [b, n_loc, L, d] -> [b, n_loc, L, d]; requires S | L.
    The local-radius mask (side, radius) is computed IN-GRAPH from iota
    inside the shard (ops.consensus.iota_local_mask) — no [n, n] host
    buffer is built at trace time or embedded per-shard as a constant
    (round-4 weak #5: the old local_mask= plumbing reintroduced the
    reference's O(n^2) init cost, reference :42-52, on this path).
    """
    S = lax.axis_size(axis_name)
    L = x.shape[2]
    if L % S != 0:
        raise ValueError(f"Ulysses needs levels ({L}) divisible by mesh axis ({S})")
    # [b, n_loc, L, d] -> [b, n, L/S, d]: gather the patch axis, scatter levels
    y = lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)
    out = consensus_attention(
        y, attend_self=attend_self, side=side, radius=radius
    )
    # [b, n, L/S, d] -> [b, n_loc, L, d]
    return lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2, tiled=True)


def make_ulysses_consensus(
    mesh,
    *,
    attend_self: bool,
    side: Optional[int] = None,
    radius: float = 0.0,
    axis_name: str = "seq",
):
    """Build a consensus_fn: [b, n, L, d] -> [b, n, L, d], n sharded over
    `axis_name`. Drop-in for glom_forward(consensus_fn=...)."""
    fn = partial(
        ulysses_consensus_shard,
        axis_name=axis_name,
        attend_self=attend_self,
        side=side,
        radius=radius,
    )
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=jax.sharding.PartitionSpec(None, axis_name, None, None),
        out_specs=jax.sharding.PartitionSpec(None, axis_name, None, None),
        axis_names={axis_name},
    )
