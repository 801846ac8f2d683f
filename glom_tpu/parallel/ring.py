"""Ring consensus: exact sequence-parallel consensus attention.

The reference materializes a dense [b, L, n, n] similarity on one device
(glom_pytorch/glom_pytorch.py:58) — O(n^2) memory, single-chip. Here the
patch axis n is sharded over the 'seq' mesh axis; each step every shard
computes attention of its local queries against the k/v block it currently
holds, then rotates k/v to its ring neighbor with `lax.ppermute` (ICI
nearest-neighbor), accumulating with an online (flash-style) softmax. After
S steps every query has seen every key: bitwise-equivalent attention, O(n/S)
memory per chip, and the ppermute for step r+1 is issued before step r's
compute so XLA overlaps communication with the einsums.

Mask parity with the dense op (SURVEY.md §3.2 items 3-4):
  * self mask: global-index diagonal REPLACED with -5e-4 (soft), computed
    from the rotating block's global offset;
  * local-radius mask: hard -finfo.max beyond Euclidean patch-grid radius,
    recomputed per block from global row/col coordinates (integer-exact:
    squared distances compared against radius^2).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from glom_tpu.utils.helpers import TOKEN_ATTEND_SELF_VALUE, l2norm

NEG_MAX = -jnp.finfo(jnp.float32).max


def _grid_coords(idx: jnp.ndarray, side: int):
    return idx // side, idx % side


def _block_sim_masks(
    sim: jnp.ndarray,
    i_offset: jnp.ndarray,
    j_offset: jnp.ndarray,
    n_i: int,
    n_j: int,
    *,
    attend_self: bool,
    side: int,
    radius: float,
    n_total: int,
) -> jnp.ndarray:
    """Apply self/local/validity masks to one [b, L, n_i, n_j] sim block whose
    rows/cols sit at global offsets i_offset/j_offset."""
    idx_i = i_offset + lax.iota(jnp.int32, n_i)[:, None]  # [n_i, 1]
    idx_j = j_offset + lax.iota(jnp.int32, n_j)[None, :]  # [1, n_j]

    if not attend_self:
        eye = idx_i == idx_j
        sim = jnp.where(eye[None, None], TOKEN_ATTEND_SELF_VALUE, sim)

    invalid = (idx_j < 0) | (idx_j >= n_total)  # out-of-image halo positions
    if radius > 0:
        ri, ci = _grid_coords(idx_i, side)
        rj, cj = _grid_coords(idx_j, side)
        dist2 = (ri - rj) ** 2 + (ci - cj) ** 2
        invalid = invalid | (dist2.astype(jnp.float32) > radius * radius)
    sim = jnp.where(invalid[None, None], NEG_MAX, sim)
    return sim


def ring_consensus_shard(
    x: jnp.ndarray,
    *,
    axis_name: str,
    attend_self: bool,
    side: int,
    radius: float,
) -> jnp.ndarray:
    """Per-shard body (call under shard_map with n sharded over `axis_name`).

    x: [b, n_loc, L, d] local block -> [b, n_loc, L, d].
    """
    S = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    b, n_loc, L, d = x.shape
    n_total = n_loc * S
    scale = d ** -0.5
    perm = [(i, (i - 1) % S) for i in range(S)]  # shard p receives p+1's block

    q = x.astype(jnp.float32)
    k0 = l2norm(q, axis=-1)
    v0 = q
    i_offset = my * n_loc

    # The accumulators start device-invariant but become device-varying via
    # the rotating blocks; mark them varying up front so the fori_loop carry
    # types line up (JAX vma tracking under shard_map). Match x's varying
    # axes, not just the ring axis — this body may run inside a larger
    # manual region (e.g. parallel.manual's (data, seq) shard_map).
    vma = tuple(jax.typeof(x).vma)

    def varying(t):
        # check_vma=False regions carry an empty vma: nothing to align.
        return lax.pcast(t, vma, to="varying") if vma else t

    m0 = varying(jnp.full((b, L, n_loc, 1), NEG_MAX, jnp.float32))
    s0 = varying(jnp.zeros((b, L, n_loc, 1), jnp.float32))
    o0 = varying(jnp.zeros((b, L, n_loc, d), jnp.float32))

    def body(r, carry):
        m, s, o, k_blk, v_blk = carry
        # Issue next rotation first — no data dependence on this step's
        # compute, so XLA overlaps the ICI transfer with the einsums.
        k_nxt = lax.ppermute(k_blk, axis_name, perm)
        v_nxt = lax.ppermute(v_blk, axis_name, perm)

        owner = (my + r) % S  # whose block we hold at step r
        j_offset = owner * n_loc
        sim = (
            jnp.einsum("bild,bjld->blij", q, k_blk, preferred_element_type=jnp.float32)
            * scale
        )
        sim = _block_sim_masks(
            sim,
            i_offset,
            j_offset,
            n_loc,
            n_loc,
            attend_self=attend_self,
            side=side,
            radius=radius,
            n_total=n_total,
        )
        blk_max = jnp.max(sim, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, blk_max)
        corr = jnp.exp(m - m_new)
        p = jnp.exp(sim - m_new)
        s_new = s * corr + jnp.sum(p, axis=-1, keepdims=True)
        o_new = o * corr + jnp.einsum(
            "blij,bjld->blid", p, v_blk, preferred_element_type=jnp.float32
        )
        return m_new, s_new, o_new, k_nxt, v_nxt

    m, s, o, _, _ = lax.fori_loop(0, S, body, (m0, s0, o0, k0, v0))
    out = o / s  # [b, L, n_loc, d]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(x.dtype)


def make_ring_consensus(
    mesh,
    *,
    attend_self: bool,
    side: int,
    radius: float = 0.0,
    axis_name: str = "seq",
):
    """Build a consensus_fn: [b, n, L, d] -> [b, n, L, d] with n sharded over
    `axis_name`. Drop-in for glom_forward(consensus_fn=...)."""
    fn = partial(
        ring_consensus_shard,
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
