"""The sharded serving forward: bucket batches over a (data, seq) mesh
with early exit legal inside the loop.

PR 4's InferenceEngine runs every bucket on one device. This module is the
multi-chip route: the SAME bucket/AOT-warmup/donation discipline, but the
forward is one manual `shard_map` over ('data', 'seq') — batch rows
sharded over 'data', the patch axis over 'seq' — so a bucket too big (or a
model too slow) for one chip serves across a slice. The structural
constraint the training path never had: the consensus-attention and
witness collectives must be legal INSIDE the `iters="auto"`
`lax.while_loop` body, whose trip count is data-dependent. They are —
shard_map collectives trace like any other op in a while body (every shard
runs the same loop, and the exit decision is itself a psum, so all shards
agree on every trip) — but each one is a wire-moving site the measured
collective counters must price, hence every psum here sits in a
`record_collective`-calling function and this module is registered with
glom-lint's collective-coverage checker (analysis/core.py
registration_modules).

Witness decomposition over 'seq': per-row agreement needs the mean over
the FULL patch axis, so the per-shard partial sums psum over 'seq' (two
[b_loc, ...] f32 hops per iteration); the quorum count psums its int32
scalar over 'data'. With seq == 1 the witness is computed by the exact
single-device `batch_agreement` reduction — no collective, and the
data-sharded forward is row-for-row the same program as the single-device
engine (the threshold-0 parity test in tests/test_serve_mesh.py holds
BITWISE on the CPU mesh).

Pricing convention: while_loop bodies trace once but execute up to the
static budget, so the engine's counting trace wraps the loop in
`counters.scaled(max_iters)` — the recorded bytes price the BUDGET (the
bound the wire must provision for), not the data-dependent realized trip
count. The fixed route's scan prices per execution the same way the
training scans do.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from glom_tpu.models.core import contribution_divisor, update_step
from glom_tpu.ops.patch import image_to_tokens
from glom_tpu.parallel.manual import shard_consensus_fn
from glom_tpu.parallel.mesh import make_mesh
from glom_tpu.telemetry import counters as tele_counters
from glom_tpu.utils.config import GlomConfig, MeshConfig, ServeConfig

# Module-level axis constants (the *_AXIS vocabulary glom-lint's
# collective checker resolves statically): same names, same meaning as
# parallel/manual.py's training mesh.
DATA_AXIS = "data"
SEQ_AXIS = "seq"


def make_serve_mesh(scfg: ServeConfig, devices: Optional[list] = None):
    """The engine's mesh, or None for the single-device route. Axis names
    reuse the training vocabulary ('data', 'seq') so the collective
    counters, glom-lint's axis vocabulary, and the docs all speak one
    language; 'model' stays 1 — serve-side TP is ROADMAP item 3's seam."""
    if scfg.mesh_data == 1 and scfg.mesh_seq == 1:
        return None
    return make_mesh(
        MeshConfig(data=scfg.mesh_data, seq=scfg.mesh_seq), devices
    )


def serve_shardings(mesh, params, *, warm: bool = False, paged: bool = False):
    """(in_shardings, out_shardings) for one sharded bucket signature:
    params replicated, the image batch and validity mask sharded over
    'data', a warm levels carry over ('data', 'seq') — or, on the PAGED
    route, the pool buffer sharded on its PAGE axis over 'data' plus the
    replicated page-index map; outputs mirror the forward's (levels,
    iters_run, row_converged, row_iters) contract. Spec resolution lives
    HERE (one place) so the engine's AOT compile and its per-attempt
    device_put can never disagree about layout."""
    if warm and paged:
        raise ValueError("warm (host levels0) and paged are exclusive")
    rep = NamedSharding(mesh, P())
    batch = NamedSharding(mesh, P(DATA_AXIS))
    rows = NamedSharding(mesh, P(DATA_AXIS))
    lv = NamedSharding(mesh, P(DATA_AXIS, SEQ_AXIS))
    pool_sh = NamedSharding(mesh, P(DATA_AXIS))
    param_sh = jax.tree_util.tree_map(lambda _: rep, params)
    in_sh = (param_sh, batch, rows)
    if warm:
        in_sh = in_sh + (lv,)
    elif paged:
        in_sh = in_sh + (pool_sh, rep)
    out_sh = (lv, rep, rows, rows)
    return in_sh, out_sh


def _psum_wire(x, axis_name: str, k: int, site: str = "serve_psum"):
    """A registered allreduce: the one wrapper every wire-moving psum in
    this module goes through, so the measured counters (and glom-lint's
    coverage rule) see each site. `site` names the call site for the
    per-collective wall-time harness (counters.timed_collective — the
    capacity observatory's timing seam; distinct witness/quorum sites
    stamp distinct collective_time rows)."""
    return tele_counters.timed_collective(
        site, axis_name, "reduce",
        tele_counters.ring_allreduce_bytes(x, k),
        lambda v: lax.psum(v, axis_name), x, collective="psum",
    )


def _gather_pages_wire(pool_loc, k: int):
    """The WHOLE-POOL page gather (docs/SERVING.md, "Paged column
    memory"): the pool buffer shards its page axis over 'data', and a
    paged warm dispatch materializes the full pool per shard with one
    registered all_gather before the page-index take. Wire is priced at
    the whole pool shard ((k-1) x local bytes — the provisioning bound;
    ServeConfig.page_gather picks this or the needed-pages exchange)."""
    return tele_counters.timed_collective(
        "page_pool_all_gather", DATA_AXIS, "gather",
        tele_counters.ring_all_gather_bytes(pool_loc, k),
        lambda p: lax.all_gather(p, DATA_AXIS, axis=0, tiled=True),
        pool_loc, collective="all_gather", dim=0,
    )


def _scatter_needed_pages_wire(pool_loc, page_idx, k: int, b_loc: int):
    """The NEEDED-PAGES-ONLY exchange (the PR 11 follow-on): instead of
    all_gathering the whole pool, every shard contributes the pages it
    OWNS of every destination shard's referenced list, and one registered
    psum_scatter delivers shard d exactly its own rows' pages — wire is
    k x rows x pages-per-row page payloads, independent of pool size.

    The payload moves as BITCAST integers: exactly one shard owns any
    referenced page (the rest contribute zero words), so the integer sum
    reproduces the owner's bit pattern EXACTLY — float summation would
    turn a stored -0.0 into +0.0 and break the threshold-0 bitwise
    parity contract. Unowned slots (page index -1) deliver zeros; the
    caller's cold-init select replaces them.

    page_idx: [k*b_loc, pages_per_row] replicated int32. Returns
    [b_loc, pages_per_row, page_tokens, L, d] — this shard's rows' pages.
    """
    import jax

    pps = pool_loc.shape[0]  # pages per shard
    ppr = page_idx.shape[1]
    int_t = jnp.int16 if pool_loc.dtype == jnp.bfloat16 else jnp.int32
    flat = page_idx.reshape(k, b_loc * ppr)  # destination-major needs
    didx = lax.axis_index(DATA_AXIS)
    owner = jnp.where(flat >= 0, flat // pps, -1)
    local = jnp.clip(flat - didx * pps, 0, pps - 1)
    mine = owner == didx
    pool_bits = jax.lax.bitcast_convert_type(pool_loc, int_t)
    contrib = jnp.where(
        mine[..., None, None, None],
        pool_bits[local],
        jnp.zeros((), int_t),
    )  # [k, b_loc*ppr, pt, L, d] as integers
    got = tele_counters.timed_collective(
        "page_needed_psum_scatter", DATA_AXIS, "reduce_scatter",
        tele_counters.ring_reduce_scatter_bytes(contrib, k),
        lambda c: lax.psum_scatter(
            c, DATA_AXIS, scatter_dimension=0, tiled=True
        ),
        contrib, collective="psum_scatter", dim=0,
    )
    pages = jax.lax.bitcast_convert_type(
        got.reshape(b_loc, ppr, *pool_loc.shape[1:]), pool_loc.dtype
    )
    return pages


def _sharded_row_agreement(levels, n: int, seq: int) -> jnp.ndarray:
    """Per-row [b_loc, L] consensus agreement over the FULL patch axis
    from a seq-sharded [b_loc, n_loc, L, d] state: the
    early_exit.batch_agreement reduction decomposed into local partial
    sums + two psums over 'seq'. seq == 1 callers use batch_agreement
    directly (bitwise-identical, collective-free)."""
    x = levels.astype(jnp.float32)
    eps = 1e-8
    xhat = x / (jnp.linalg.norm(x, axis=-1, keepdims=True) + eps)
    part = jnp.sum(xhat, axis=1, keepdims=True)  # [b_loc, 1, L, d]
    mean = _psum_wire(part, SEQ_AXIS, seq, site="witness_mean_psum") / n
    mhat = mean / (jnp.linalg.norm(mean, axis=-1, keepdims=True) + eps)
    cos = jnp.sum(jnp.sum(xhat * mhat, axis=-1), axis=1)  # [b_loc, L]
    return _psum_wire(cos, SEQ_AXIS, seq, site="witness_cos_psum") / n


def make_serve_forward(
    mesh,
    cfg: GlomConfig,
    *,
    route,
    max_iters: Optional[int] = None,
    threshold: float = 1e-3,
    min_iters: int = 1,
    quorum: float = 1.0,
    compute_dtype=None,
    use_pallas: bool = False,
    sp_strategy: str = "auto",
    warm: bool = False,
    page_tokens: Optional[int] = None,
    page_gather: str = "auto",
):
    """Build the sharded bucket forward for one engine signature.

    route: "auto" (tiered early exit, budget `max_iters`) or an int (fixed
    iteration count — the ladder's capped route and the non-auto configs).
    Returns fn(params, img [b,c,H,W], mask [b]) — plus levels0
    [b, n, L, d] when warm — -> (levels [b,n,L,d], iters_run int32,
    row_converged [b] bool, row_iters [b] int32): the same 4-tuple contract
    as the single-device tiered route, so the engine treats both
    identically. The per-shard loop body is the reference-layout
    `update_step` (the SAME contract as serve/early_exit), with consensus
    swapped for the per-shard ring/ulysses/halo body when seq > 1.

    page_tokens selects the PAGED warm variant instead: the signature
    takes (pool [n_pages, page_tokens, L, d] sharded on its page axis
    over 'data', page_idx [b, pages_per_row] replicated int32, -1 =
    cold row) and each shard assembles its rows' levels0 in-graph — one
    registered all_gather of the pool over 'data' (the sharded page
    gather), a page-index take, then the seq band slice. Warm column
    state never crosses the host boundary on this route.
    """
    from glom_tpu.serve.early_exit import (
        _validate_auto_args,
        batch_agreement,
        quorum_need,
        row_agreement_delta,
    )

    seq = mesh.shape[SEQ_AXIS]
    dp = mesh.shape[DATA_AXIS]
    auto = route == "auto"
    if auto:
        T = max_iters if max_iters is not None else cfg.default_iters
        _validate_auto_args(T, min_iters, threshold)
    else:
        T = int(route)
        if T < 1:
            raise ValueError(f"route={route!r}: an int >= 1 or 'auto'")
    if cfg.num_patches % seq != 0:
        raise ValueError(
            f"patches {cfg.num_patches} not divisible by seq axis {seq}"
        )

    if use_pallas:
        from glom_tpu.kernels import fused_grouped_ffw

        ffw_fn = fused_grouped_ffw
    else:
        from glom_tpu.ops.ffw import grouped_ffw

        ffw_fn = grouped_ffw

    consensus_shard = shard_consensus_fn(cfg, seq, sp_strategy)
    if consensus_shard is None:
        # seq == 1: the dense single-device consensus — the branch the
        # bitwise parity test pins against the single-device engine.
        from functools import partial

        from glom_tpu.ops.consensus import build_local_mask, consensus_attention

        local_mask = build_local_mask(
            cfg.num_patches_side, cfg.local_consensus_radius
        )
        consensus_shard = partial(
            consensus_attention,
            attend_self=cfg.consensus_self,
            local_mask=local_mask,
        )

    n = cfg.num_patches
    n_loc = n // seq
    thr = jnp.float32(threshold)

    def body_fn(glom_params, img, mask, levels0):
        # Identical prologue ORDER to early_exit._build_update_step: cast
        # once, tokenize, then slice this shard's patch band.
        if compute_dtype is not None:
            glom_params = jax.tree_util.tree_map(
                lambda t: t.astype(compute_dtype), glom_params
            )
            img = img.astype(compute_dtype)
            if levels0 is not None:
                levels0 = levels0.astype(compute_dtype)

        tokens = image_to_tokens(
            glom_params.token_embed, img, cfg.patch_size
        )  # [b_loc, n, d]
        seq_idx = lax.axis_index(SEQ_AXIS)
        tokens_loc = lax.dynamic_slice_in_dim(
            tokens, seq_idx * n_loc, n_loc, axis=1
        )
        pos_loc = lax.dynamic_slice_in_dim(
            glom_params.pos_emb, seq_idx * n_loc, n_loc, axis=0
        )
        b_loc = tokens_loc.shape[0]
        pos = pos_loc[None, :, None, :]  # [1, n_loc, 1, d]
        bottom = tokens_loc[:, :, None, :]  # [b_loc, n_loc, 1, d]
        if levels0 is None:
            levels = jnp.broadcast_to(
                glom_params.init_levels[None, None],
                (b_loc, n_loc, cfg.levels, tokens_loc.shape[-1]),
            ).astype(tokens_loc.dtype)
        else:
            levels = levels0
        divisor = contribution_divisor(cfg.levels, jnp.float32)

        def step(lv):
            return update_step(
                glom_params, lv, bottom, pos, divisor,
                consensus_fn=consensus_shard, ffw_fn=ffw_fn,
            )

        def row_agreement(lv):
            if seq == 1:
                return batch_agreement(lv)
            return _sharded_row_agreement(lv, n, seq)

        valid = mask.astype(bool)

        if not auto:
            # Fixed route: scan T updates; every row "converged" by fiat
            # (there is no witness and no continuation on this route).
            with tele_counters.scaled(T):
                final, _ = lax.scan(
                    lambda lv, _: (step(lv), None), levels, None, length=T
                )
            return (
                final,
                jnp.int32(T),
                jnp.ones((b_loc,), bool),
                jnp.full((b_loc,), T, jnp.int32),
            )

        # The quorum target over ALL valid rows: one registered int hop
        # over 'data' outside the loop.
        n_valid = _psum_wire(
            jnp.sum(valid.astype(jnp.float32)), DATA_AXIS, dp,
            site="quorum_valid_psum",
        )
        need = quorum_need(quorum, n_valid)

        def cond(carry):
            lv, prev_rows, i, conv, row_iters = carry
            n_conv_loc = jnp.sum(
                jnp.logical_and(conv, valid).astype(jnp.int32)
            )
            n_conv = _psum_wire(
                n_conv_loc, DATA_AXIS, dp, site="quorum_exit_psum"
            )
            return jnp.logical_and(i < T, n_conv < need)

        def body(carry):
            lv, prev_rows, i, conv, row_iters = carry
            new = step(lv)
            agree_rows = row_agreement(new)  # [b_loc, L]
            delta = row_agreement_delta(agree_rows, prev_rows)
            newly = jnp.logical_and(i + 1 >= min_iters, delta < thr)
            first = jnp.logical_and(newly, jnp.logical_not(conv))
            row_iters = jnp.where(first, i + 1, row_iters)
            return (
                new, agree_rows, i + 1,
                jnp.logical_or(conv, newly), row_iters,
            )

        init_rows = row_agreement(levels)
        with tele_counters.scaled(T):
            final, _, iters_run, conv, row_iters = lax.while_loop(
                cond,
                body,
                (
                    levels,
                    init_rows,
                    jnp.int32(0),
                    jnp.zeros((b_loc,), bool),
                    jnp.full((b_loc,), T, jnp.int32),
                ),
            )
        row_iters = jnp.where(conv, row_iters, iters_run)
        return final, iters_run, conv, row_iters

    batch_spec = P(DATA_AXIS)
    lv_spec = P(DATA_AXIS, SEQ_AXIS)
    out_specs = (lv_spec, P(), P(DATA_AXIS), P(DATA_AXIS))

    if warm and page_tokens is not None:
        raise ValueError("warm (host levels0) and page_tokens are exclusive")
    if page_tokens is not None:
        if n % page_tokens != 0:
            raise ValueError(
                f"page_tokens {page_tokens} does not divide patches {n}"
            )
        pt = page_tokens

        if page_gather not in ("auto", "pool", "needed"):
            raise ValueError(
                f"page_gather {page_gather!r}: 'auto', 'pool', or 'needed'"
            )

        def paged_body(glom_params, img, mask, pool_loc, page_idx):
            # The sharded page materialization: pool pages live 1/dp per
            # shard. Two registered routes (ServeConfig.page_gather):
            # "pool" all_gathers the WHOLE pool (the provisioning bound),
            # "needed" psum_scatters ONLY the referenced pages; "auto"
            # picks whichever moves fewer bytes at this signature's
            # STATIC shapes — decided at trace time, and the compile
            # trace's counted bytes record the choice.
            b_loc = img.shape[0]
            didx = lax.axis_index(DATA_AXIS)
            mode = page_gather
            if mode == "auto":
                elt = pool_loc.dtype.itemsize
                page_elts = pt * cfg.levels * cfg.dim
                whole = (dp - 1) * pool_loc.shape[0] * page_elts * elt
                needed = (
                    (dp - 1) * b_loc * page_idx.shape[1] * page_elts * elt
                )
                mode = "needed" if needed < whole else "pool"
            if mode == "needed":
                with jax.named_scope("page_scatter_needed"):
                    pages = _scatter_needed_pages_wire(
                        pool_loc, page_idx, dp, b_loc
                    )
                my_idx = lax.dynamic_slice_in_dim(
                    page_idx, didx * b_loc, b_loc, axis=0
                )
            else:
                with jax.named_scope("page_gather"):
                    pool_full = _gather_pages_wire(pool_loc, dp)
                my_idx = lax.dynamic_slice_in_dim(
                    page_idx, didx * b_loc, b_loc, axis=0
                )  # [b_loc, pages_per_row]
                with jax.named_scope("page_take"):
                    pages = pool_full[
                        jnp.clip(my_idx, 0, pool_full.shape[0] - 1)
                    ]
            init = jnp.broadcast_to(
                glom_params.init_levels[None],
                (pt, cfg.levels, cfg.dim),
            ).astype(pool_loc.dtype)
            pages = jnp.where(
                (my_idx >= 0)[..., None, None, None], pages, init
            )
            lv_full = pages.reshape(b_loc, n, cfg.levels, cfg.dim)
            seq_idx = lax.axis_index(SEQ_AXIS)
            lv_loc = lax.dynamic_slice_in_dim(
                lv_full, seq_idx * n_loc, n_loc, axis=1
            )
            return body_fn(glom_params, img, mask, lv_loc)

        return jax.shard_map(
            paged_body,
            mesh=mesh,
            in_specs=(P(), batch_spec, batch_spec, P(DATA_AXIS), P()),
            out_specs=out_specs,
            check_vma=False,
        )
    if warm:
        return jax.shard_map(
            body_fn,
            mesh=mesh,
            in_specs=(P(), batch_spec, batch_spec, lv_spec),
            out_specs=out_specs,
            check_vma=False,
        )
    return jax.shard_map(
        lambda p, img, mask: body_fn(p, img, mask, None),
        mesh=mesh,
        in_specs=(P(), batch_spec, batch_spec),
        out_specs=out_specs,
        check_vma=False,
    )
