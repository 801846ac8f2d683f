"""Halo-exchange consensus for the local-radius path (BASELINE config 3).

When `local_consensus_radius` r > 0 the reference still materializes the
full n x n similarity and masks it (glom_pytorch/glom_pytorch.py:65-67).
But locality means a patch only attends within r grid rows/cols — so with
the patch grid sharded into contiguous ROW BANDS over the 'seq' axis, each
shard needs exactly `floor(r)` rows from each neighbor (grid distances are
integers: a patch within Euclidean radius r is at most floor(r) rows away),
not the whole ring: two nearest-neighbor ppermutes (one up, one down, both
riding a single ICI hop) instead of S ring steps. Communication
O(r * side * L * d) per shard, independent of n.

Requires rows_per_shard >= floor(r) (one-hop halo — the predicate is
helpers.halo_supported); use the ring for larger radii or finer shardings.

Out-of-image halo slots (top shard's upper halo, bottom shard's lower halo)
arrive zero-filled from the non-periodic ppermute and are hard-masked via
their global indices, so they contribute exactly zero attention.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from glom_tpu.parallel.ring import _block_sim_masks
from glom_tpu.utils.helpers import halo_supported, l2norm


def halo_consensus_shard(
    x: jnp.ndarray,
    *,
    axis_name: str,
    attend_self: bool,
    side: int,
    radius: float,
) -> jnp.ndarray:
    """Per-shard body (under shard_map; n sharded over `axis_name` in
    row-major row bands). x: [b, n_loc, L, d] -> [b, n_loc, L, d]."""
    S = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    b, n_loc, L, d = x.shape
    n_total = n_loc * S
    rows_per_shard = n_loc // side
    # Grid distances are integers: a patch within Euclidean distance r is at
    # most floor(r) rows away (ceil would falsely reject workable configs
    # and ship a whole extra masked row per neighbor for fractional radii).
    halo_rows = min(int(math.floor(radius)), rows_per_shard)
    h = halo_rows * side  # halo size in patches
    scale = d ** -0.5

    q = x.astype(jnp.float32)
    k_loc = l2norm(q, axis=-1)
    v_loc = q

    # Non-periodic neighbor exchange: shard p's bottom rows become p+1's top
    # halo; p's top rows become p-1's bottom halo. Missing neighbors (grid
    # edges) arrive zero-filled and are masked below by global index.
    down_perm = [(i, i + 1) for i in range(S - 1)]
    up_perm = [(i + 1, i) for i in range(S - 1)]

    def exchange(t):
        top_halo = lax.ppermute(t[:, -h:], axis_name, down_perm)  # from p-1
        bot_halo = lax.ppermute(t[:, :h], axis_name, up_perm)  # from p+1
        return jnp.concatenate([top_halo, t, bot_halo], axis=1)

    if h > 0:
        k_ext = exchange(k_loc)  # [b, n_loc + 2h, L, d]
        v_ext = exchange(v_loc)
    else:
        # radius < 1: no cross-shard pairs are within reach (adjacent grid
        # rows are distance 1 apart), so skip the exchange entirely. The
        # h == 0 slice t[:, -0:] would otherwise select the WHOLE block and
        # mislabel a full neighbor copy with local global indices.
        k_ext, v_ext = k_loc, v_loc

    i_offset = my * n_loc
    j_offset = i_offset - h  # the extended block starts h patches earlier

    sim = (
        jnp.einsum("bild,bjld->blij", q, k_ext, preferred_element_type=jnp.float32)
        * scale
    )
    sim = _block_sim_masks(
        sim,
        i_offset,
        j_offset,
        n_loc,
        n_loc + 2 * h,
        attend_self=attend_self,
        side=side,
        radius=radius,
        n_total=n_total,
    )
    attn = jax.nn.softmax(sim, axis=-1)
    out = jnp.einsum("blij,bjld->blid", attn, v_ext, preferred_element_type=jnp.float32)
    return jnp.transpose(out, (0, 2, 1, 3)).astype(x.dtype)


def make_halo_consensus(
    mesh,
    *,
    attend_self: bool,
    side: int,
    radius: float,
    axis_name: str = "seq",
):
    """Build a consensus_fn for the local-radius path; n sharded over
    `axis_name`. Validates the one-hop halo precondition at build time —
    the same predicate callers can pre-check via helpers.halo_supported."""
    seq = mesh.shape[axis_name]
    if not halo_supported(seq, side, radius):
        if radius <= 0:
            raise ValueError("halo consensus requires local_consensus_radius > 0")
        if side % seq != 0:
            raise ValueError(f"grid side {side} not divisible by seq axis {seq}")
        raise ValueError(
            f"radius {radius} needs {math.floor(radius)} halo rows but shards "
            f"only hold {side // seq}; use ring consensus instead"
        )
    fn = partial(
        halo_consensus_shard,
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
