"""Fully-manual SPMD training path: Pallas kernels composed with DP x SP.

Round-1 limitation (review item, weak #6): the fused Pallas kernels were illegal
inside GSPMD-sharded regions (custom calls carry no partitioning rule), so
`use_pallas` evaporated exactly where perf matters — the distributed
configs. The TPU-native fix is NOT a partitioning rule per kernel but this
module: the ENTIRE loss runs inside ONE `shard_map` over ('data', 'seq'),
where every array is physically local and a Pallas call is plain per-device
work. Collectives are explicit and minimal:

  * DP   — batch sharded over 'data'; the gradient all-reduce appears
           automatically when shard_map transposes the replicated-in params
           (a psum of the per-shard cotangents) — the same collective GSPMD
           would have inserted, now riding the manual region.
  * SP   — the patch axis n sharded over 'seq'; consensus attention runs the
           existing per-shard ring / halo / ulysses bodies (ring.py /
           halo.py / ulysses.py), which were written exactly for this
           context (lax.ppermute / all_to_all over 'seq'). With seq=1 the
           fused consensus+update kernel runs whole.
  * loss — per-shard MSE over the local (batch-band x patch-band) block,
           pmean'd over both axes. Reconstruction compares PATCHES (the
           pixel set is identical to the reference's image-space MSE, so the
           value is exact — unpatchify would need an n all-gather for
           nothing).

  * TP   — the grouped-FFW hidden axis f sharded over 'model'
           (Megatron-style, same layout as sharding.ffw_specs): each rank
           runs the fused kernel on its [G, d, f/mp] / [G, f/mp, d] weight
           shards and ONE hand-written psum on the second matmul's output
           reconstructs the full FFW result. b2 is added in-kernel scaled
           by 1/mp so the psum reconstructs it exactly (mp is a power of
           two, so the scale is exact in bf16). Gradient correctness under
           check_vma=False is locked by
           tests/test_manual.py::test_manual_tp_grads_match_dense:
           a RAW lax.psum composes correctly with the shard_map transpose —
           partial dx cotangents get psum'd over 'model', sharded-weight
           cotangents stay local, replicated-param cotangents come out
           unscaled. No custom_vjp link functions needed.

Reference parity: the per-shard loop IS models/core.level_major_loop (the
body single-device jobs run, and its one dispatch to the whole-loop VJP);
this module slices the shard, builds the TP FFW wrapper and picks the
per-shard consensus. Parity is locked by tests/test_manual.py against the
single-device dense and fused forwards.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import PartitionSpec as P

from glom_tpu.models.core import level_major_loop
from glom_tpu.ops.patch import image_to_tokens, patchify
from glom_tpu.parallel.halo import halo_consensus_shard
from glom_tpu.parallel.ring import ring_consensus_shard
from glom_tpu.telemetry import counters as tele_counters
from glom_tpu.telemetry import diagnostics as diag
from glom_tpu.train.objectives import DenoiseParams, default_recon_index
from glom_tpu.train.trainer import TrainState, pinned_grad_accum
from glom_tpu.utils.config import GlomConfig, TrainConfig

DATA_AXIS = "data"
SEQ_AXIS = "seq"
MODEL_AXIS = "model"


def manual_supported(mesh, tp_axis: str = "hidden") -> bool:
    """The manual fused path covers DP x SP x hidden-TP. The EP-style
    'levels' TP shards the group axis with a different collective pattern
    and stays on the GSPMD path."""
    return mesh.shape.get(MODEL_AXIS, 1) == 1 or tp_axis == "hidden"


def shard_consensus_fn(cfg: GlomConfig, seq: int, sp_strategy: str):
    """Pick the per-shard consensus body ([b, n_loc, L, d] -> same) for the
    'seq'-manual region. None means seq is unsharded and the caller should
    use the fused consensus+update kernel instead.

    Resolution (auto + fallbacks + warnings) is runtime.effective_sp_strategy
    — the single policy source; this is construction only. 'none' with a
    sharded seq axis builds ring: the manual region's n-shards must
    communicate, and ring is the exact default mechanism."""
    from glom_tpu.parallel.runtime import effective_sp_strategy

    sp_strategy = effective_sp_strategy(cfg, seq, sp_strategy)
    if seq == 1:
        return None
    radius = float(cfg.local_consensus_radius)
    if sp_strategy == "ulysses":
        from glom_tpu.parallel.ulysses import ulysses_consensus_shard

        return partial(
            ulysses_consensus_shard,
            axis_name=SEQ_AXIS,
            attend_self=cfg.consensus_self,
            side=cfg.num_patches_side,
            radius=radius,
        )
    if sp_strategy == "halo":
        return partial(
            halo_consensus_shard,
            axis_name=SEQ_AXIS,
            attend_self=cfg.consensus_self,
            side=cfg.num_patches_side,
            radius=radius,
        )
    return partial(
        ring_consensus_shard,
        axis_name=SEQ_AXIS,
        attend_self=cfg.consensus_self,
        side=cfg.num_patches_side,
        radius=radius,
    )


def _forward_local(
    glom_params,
    noised: jnp.ndarray,
    cfg: GlomConfig,
    *,
    iters: int,
    seq: int,
    mp: int,
    consensus_shard,
    remat: bool,
    use_pallas: bool,
    unroll: bool = False,
    levels0_lm: Optional[jnp.ndarray] = None,
    return_mode: str = "top",
    interpret: bool = False,
) -> jnp.ndarray:
    """Per-shard forward: slice this shard's patch band and hand the local
    batch, band and FFW hidden shard to models/core.level_major_loop — the
    one loop body, and the one dispatch to the WHOLE-LOOP VJP (taken when
    seq == 1, mp == 1 and the shard-local shape admits it). levels0_lm
    optionally carries in a [L, b_loc, n_loc, d] initial state (the
    temporal API). return_mode:
      'top'   — final top level [b_loc, n_loc, d] (the training loss path);
      'final' — full final carry [L, b_loc, n_loc, d];
      'all'   — all T+1 states [T+1, L, b_loc, n_loc, d] incl. the initial
                (reference return_all contract, T+1 states)."""
    from glom_tpu.kernels.grouped_mlp import fused_grouped_ffw_lm
    from glom_tpu.ops.ffw import grouped_ffw_lm

    if consensus_shard is None and not use_pallas:
        raise ValueError(
            "seq=1 without use_pallas has no per-shard consensus body; pass "
            "one (make_manual_loss builds the dense composition for this case)"
        )
    ffw_lm = fused_grouped_ffw_lm if use_pallas else grouped_ffw_lm
    if mp > 1:
        # Megatron TP: this rank's weights cover f/mp hidden units; the
        # kernel output is a partial sum over f, completed by one psum.
        # b2 is added in-kernel, so scale it 1/mp (exact: mp is a power of
        # two) and let the psum reconstruct it. Raw psum composes correctly
        # with the shard_map transpose under check_vma=False — verified
        # against dense-reference grads (tests/test_manual.py).
        inner_ffw, inv_mp = ffw_lm, 1.0 / mp

        def ffw_lm(p, x, add=None):
            p = p._replace(b2=p.b2 * jnp.asarray(inv_mp, p.b2.dtype))
            out = inner_ffw(p, x, add=add)
            # This is a WIRE-MOVING collective (full FFW activations over
            # 'model', every scan iteration — the loop below runs under
            # scaled(iters) so the trace-time record prices every
            # execution), found unregistered by glom-lint's
            # collective-coverage pass: the drift reconciliation could
            # never see TP forward traffic. Recording only fires inside a
            # counters.recording() context, so no runtime change outside
            # the counting trace. NOTE: comm_volume_model prices the
            # gradient/update path only (no TP term), and the trainer's
            # counting trace can never reach this site today (manual x
            # zero>=1 degrades to zero 0 on model>1 meshes, see
            # runtime.py) — if a future route records a TP config, the
            # model needs a TP term FIRST or comm_model_drift becomes a
            # permanent false alarm. The per-execution pricing contract
            # is pinned by test_telemetry's TP counting test. Routed
            # through the shared timing wrapper (counters.timed_collective
            # — the capacity observatory's per-collective wall-time seam):
            # byte recording is unchanged, and a timing-enabled trace
            # additionally registers the site for the sampled re-dispatch.
            return tele_counters.timed_collective(
                "tp_ffw_psum", MODEL_AXIS, "reduce",
                tele_counters.ring_allreduce_bytes(out, mp),
                lambda o: lax.psum(o, MODEL_AXIS), out, collective="psum",
            )

    L, d = cfg.levels, cfg.dim
    n_loc = cfg.num_patches // seq

    # Patchify the full image, then slice this shard's patch band. The patch
    # grid is row-major, so a contiguous n-band is a contiguous row band —
    # the layout ring/halo assume. (Patchify+embed on the full image is
    # O(n * p^2 * c * d), noise vs one scan iteration; slicing after keeps
    # the code free of pixel-band geometry.)
    with jax.named_scope("image_to_tokens"):
        tokens = image_to_tokens(
            glom_params.token_embed, noised, cfg.patch_size
        )  # [b_loc, n, d]
        seq_idx = lax.axis_index(SEQ_AXIS)
        tokens_loc = lax.dynamic_slice_in_dim(
            tokens, seq_idx * n_loc, n_loc, axis=1
        )
    pos_loc = lax.dynamic_slice_in_dim(
        glom_params.pos_emb, seq_idx * n_loc, n_loc, axis=0
    )

    b_loc = tokens_loc.shape[0]
    if levels0_lm is not None:
        levels_lm = levels0_lm.astype(tokens_loc.dtype)
    else:
        levels_lm = jnp.broadcast_to(
            glom_params.init_levels[:, None, None], (L, b_loc, n_loc, d)
        ).astype(tokens_loc.dtype)
        # The initial carry is device-invariant (broadcast replicated
        # params) but the loop body's output varies over both mesh axes (it
        # consumes the local tokens); align the vma types up front (see
        # ring.py). Under check_vma=False the vma set is empty and pcast
        # must not run. (A carried-in levels0 is already sharded input —
        # already varying — and must NOT be pcast.)
        vma = tuple(jax.typeof(tokens_loc).vma)
        if vma:
            levels_lm = lax.pcast(levels_lm, vma, to="varying")

    # scaled(iters): the loop body traces ONCE but executes per iteration —
    # collective sites inside it (the TP psum) must price every execution
    # (same convention as the stage-2 microbatch hook).
    with tele_counters.scaled(iters):
        out = level_major_loop(
            glom_params.bottom_up, glom_params.top_down, pos_loc, tokens_loc,
            levels_lm, cfg, iters=iters, remat=remat, unroll=unroll,
            stack=return_mode == "all", ffw_lm=ffw_lm,
            consensus_shard=consensus_shard, interpret=interpret,
        )
    # 'all': [T+1, L, ...]; 'final': [L, b_loc, n_loc, d]; 'top': its last
    return out[-1] if return_mode == "top" else out


def _build_local_loss(
    mesh,
    cfg: GlomConfig,
    tcfg: TrainConfig,
    *,
    sp_strategy: str = "none",
    interpret: bool = False,
):
    """The per-shard loss body both manual train steps share: returns
    (local_loss, seq, mp) where local_loss(params, img, noise) -> scalar is
    the mean over the LOCAL batch band (pmean'd over 'seq' so every data
    replica holds its full-image loss, NOT yet reduced over 'data').
    make_manual_loss pmeans it over 'data' and lets the shard_map
    transpose emit the grad psum; the ZeRO step differentiates it directly
    inside the region and writes its own reduce-scatter instead."""
    seq = mesh.shape[SEQ_AXIS]
    mp = mesh.shape.get(MODEL_AXIS, 1)
    T = tcfg.iters if tcfg.iters is not None else cfg.default_iters
    k = (
        tcfg.recon_iter_index
        if tcfg.recon_iter_index is not None
        else default_recon_index(T)
    )
    if not 1 <= k <= T:
        raise ValueError(f"recon_index {k} outside 1..{T}")
    compute_dtype = jnp.bfloat16 if tcfg.compute_dtype == "bfloat16" else None
    consensus_shard = shard_consensus_fn(cfg, seq, sp_strategy)
    use_pallas = tcfg.use_pallas

    # seq==1 with use_pallas=False has no kernel to fuse — the caller
    # (DistributedTrainer) only routes here when use_pallas is set, but keep
    # the plain-XLA composition correct for direct users/tests.
    if consensus_shard is None and not use_pallas:
        from glom_tpu.ops.consensus import build_local_mask, consensus_attention

        mask = build_local_mask(cfg.num_patches_side, cfg.local_consensus_radius)

        def dense_shard(x):  # [b, n_loc=n, L, d]
            return consensus_attention(
                x, attend_self=cfg.consensus_self, local_mask=mask
            )

        consensus_shard = dense_shard

    def loss_body(params: DenoiseParams, img: jnp.ndarray, noise: jnp.ndarray):
        glom_params = params.glom
        if compute_dtype is not None:
            glom_params = jax.tree_util.tree_map(
                lambda t: t.astype(compute_dtype), glom_params
            )
        with jax.named_scope("noise"):
            noised = (img + noise).astype(
                compute_dtype if compute_dtype is not None else img.dtype
            )
        top = _forward_local(
            glom_params,
            noised,
            cfg,
            iters=k,
            seq=seq,
            mp=mp,
            consensus_shard=consensus_shard,
            remat=tcfg.remat,
            use_pallas=use_pallas,
            unroll=tcfg.scan_unroll,
            interpret=interpret,
        )  # [b_loc, n_loc, d]

        # Reconstruction + MSE in PATCH space: identical pixel set to the
        # reference's image-space MSE (patchify is a permutation), no
        # all-gather needed for the local band.
        with jax.named_scope("reconstruction"):
            recon = top.astype(img.dtype) @ params.to_pixels.w + params.to_pixels.b
            target = patchify(img, cfg.patch_size)  # [b_loc, n, p*p*c]
            n_loc = cfg.num_patches // seq
            seq_idx = lax.axis_index(SEQ_AXIS)
            target_loc = lax.dynamic_slice_in_dim(
                target, seq_idx * n_loc, n_loc, axis=1
            )
            local_mse = jnp.mean((target_loc - recon) ** 2)
            return lax.pmean(local_mse, SEQ_AXIS)

    return loss_body, seq, mp


def _manual_param_spec(mp: int):
    """in/out param spec for the manual regions: pre-sharded over 'model'
    on the hidden axis when TP is on (the same layout DistributedTrainer
    device_puts — sharding.denoise_param_specs — so no resharding at the
    boundary), replicated otherwise."""
    if mp > 1:
        from glom_tpu.parallel.sharding import denoise_param_specs

        return denoise_param_specs("hidden")
    return P()


def make_manual_loss(
    mesh,
    cfg: GlomConfig,
    tcfg: TrainConfig,
    *,
    sp_strategy: str = "none",
    interpret: bool = False,
):
    """Build loss(params, img, noise) -> scalar: the whole computation one
    shard_map over (data, seq, model). Differentiable; the params cotangent
    psum (the DP gradient all-reduce) comes from the shard_map transpose,
    and the TP psum on the FFW output is written by hand in the body."""
    local_loss, seq, mp = _build_local_loss(
        mesh, cfg, tcfg, sp_strategy=sp_strategy, interpret=interpret
    )

    def loss_body(params: DenoiseParams, img: jnp.ndarray, noise: jnp.ndarray):
        loss = local_loss(params, img, noise)
        with jax.named_scope("reconstruction"):
            return lax.pmean(loss, DATA_AXIS)

    batch_spec = P(DATA_AXIS)  # [b, c, H, W]; replicated over seq (sliced in-body)
    param_spec = _manual_param_spec(mp)
    return jax.shard_map(
        loss_body,
        mesh=mesh,
        in_specs=(param_spec, batch_spec, batch_spec),
        out_specs=P(),
        # Fully manual — over EVERY mesh axis, including the size-1 'model'
        # axis. Leaving any axis auto keeps the body in GSPMD context, and
        # Mosaic (Pallas) custom calls refuse to lower there.
        # pallas_call's out_shape carries no vma type, which trips the
        # varying-axes checker when a kernel actually lowers (on TPU; the
        # CPU tests take the XLA fallbacks and never hit it). The pmean on
        # the loss makes the out_specs=P() replication correct by
        # construction; ring.py's pcast self-adapts (typeof(x).vma is empty
        # with the checker off).
        check_vma=False,
    )


def make_manual_forward(
    mesh,
    cfg: GlomConfig,
    *,
    iters: Optional[int] = None,
    sp_strategy: str = "none",
    compute_dtype=None,
    use_pallas: bool = True,
    return_all: bool = False,
    with_levels: bool = False,
    remat: bool = False,
):
    """Sharded INFERENCE through the fused kernels: glom_forward's contract
    (final [b, n, L, d], or all T+1 states with return_all) as one
    shard_map over (data, seq, model) — the path `Glom(mesh=...)` uses so
    the preserved API reaches the Pallas kernels under a mesh (round-2
    Review item (weak #5): training got the manual fused region, inference
    didn't). with_levels=True compiles the temporal variant taking a
    [b, n, L, d] carried-in state sharded (data, seq)."""
    seq = mesh.shape[SEQ_AXIS]
    mp = mesh.shape.get(MODEL_AXIS, 1)
    T = iters if iters is not None else cfg.default_iters
    consensus_shard = shard_consensus_fn(cfg, seq, sp_strategy)
    if consensus_shard is None and not use_pallas:
        from glom_tpu.ops.consensus import build_local_mask, consensus_attention

        mask = build_local_mask(cfg.num_patches_side, cfg.local_consensus_radius)

        def consensus_shard(x):  # noqa: F811 - deliberate dense fallback
            return consensus_attention(
                x, attend_self=cfg.consensus_self, local_mask=mask
            )

    def fwd_body(glom_params, img, levels0):
        if compute_dtype is not None:
            glom_params = jax.tree_util.tree_map(
                lambda t: t.astype(compute_dtype), glom_params
            )
            img = img.astype(compute_dtype)
        levels0_lm = (
            None if levels0 is None else jnp.transpose(levels0, (2, 0, 1, 3))
        )
        out = _forward_local(
            glom_params,
            img,
            cfg,
            iters=T,
            seq=seq,
            mp=mp,
            consensus_shard=consensus_shard,
            remat=remat,
            use_pallas=use_pallas,
            levels0_lm=levels0_lm,
            return_mode="all" if return_all else "final",
        )
        # level-major -> reference layout [.., b, n, L, d]
        if return_all:
            return jnp.transpose(out, (0, 2, 3, 1, 4))
        return jnp.transpose(out, (1, 2, 0, 3))

    batch_spec = P(DATA_AXIS)
    if mp > 1:
        from glom_tpu.parallel.sharding import glom_param_specs

        param_spec = glom_param_specs("hidden")
    else:
        param_spec = P()
    lv_spec = P(DATA_AXIS, SEQ_AXIS)
    out_spec = P(None, DATA_AXIS, SEQ_AXIS) if return_all else lv_spec

    if with_levels:
        return jax.shard_map(
            fwd_body,
            mesh=mesh,
            in_specs=(param_spec, batch_spec, lv_spec),
            out_specs=out_spec,
            check_vma=False,
        )
    return jax.shard_map(
        lambda p, img: fwd_body(p, img, None),
        mesh=mesh,
        in_specs=(param_spec, batch_spec),
        out_specs=out_spec,
        check_vma=False,
    )


def make_manual_train_step(
    mesh,
    cfg: GlomConfig,
    tcfg: TrainConfig,
    optimizer: optax.GradientTransformation,
    *,
    sp_strategy: str = "none",
    with_grad_norm: bool = True,
    interpret: bool = False,
):
    """(state, img, rng) -> (state, metrics): the manual-region analog of
    train.trainer.make_train_step, same metrics contract (incl. the
    with_grad_norm fast variant for non-logging steps, and the telemetry
    scalars + NaN/Inf guard at tcfg.telemetry_level != "off" — "full"
    degrades to "scalars" here, see resolve_telemetry_level)."""
    if tcfg.compute_dtype not in ("float32", "bfloat16"):
        raise ValueError(
            f"compute_dtype={tcfg.compute_dtype!r}: must be 'float32' or 'bfloat16'"
        )
    accum = pinned_grad_accum(tcfg)
    if tcfg.batch_size % accum != 0:
        raise ValueError(
            f"grad_accum={accum} must divide batch_size={tcfg.batch_size}"
        )
    if (tcfg.batch_size // accum) % mesh.shape[DATA_AXIS] != 0:
        raise ValueError(
            f"microbatch {tcfg.batch_size // accum} not divisible "
            f"by data axis {mesh.shape[DATA_AXIS]}"
        )
    level = diag.resolve_telemetry_level(tcfg, supports_full=False)
    loss_fn = make_manual_loss(
        mesh, cfg, tcfg, sp_strategy=sp_strategy, interpret=interpret
    )

    def train_step(state: TrainState, img: jnp.ndarray, rng: jax.Array):
        with jax.named_scope("noise"):
            noise_rng = jax.random.fold_in(rng, state.step)
            noise = tcfg.noise_std * jax.random.normal(
                noise_rng, img.shape, img.dtype
            )
        if accum > 1:
            from glom_tpu.train.trainer import accumulate_grads

            loss, grads = accumulate_grads(
                loss_fn, state.params, img, noise, accum
            )
        else:
            loss, grads = jax.value_and_grad(loss_fn)(state.params, img, noise)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(
                grads, state.opt_state, state.params
            )
            params = optax.apply_updates(state.params, updates)
        metrics = {"loss": loss, "step": state.step}
        with jax.named_scope("step_metrics"):
            if with_grad_norm or level != "off":
                grad_norm = optax.global_norm(grads)
            if with_grad_norm:
                metrics["grad_norm"] = grad_norm
            if level != "off":
                # The grads/updates here are full replicated trees (the
                # shard_map transpose already reduced them), so the scalar
                # taps and the guard run OUTSIDE the manual region — same
                # fused-reduction cost as the GSPMD step's.
                taps = diag.scalar_taps(
                    loss=loss, grad_norm=grad_norm, updates=updates,
                    params=params,
                )
                nonfinite = taps.pop("nonfinite")
                if tcfg.nonfinite_policy == "skip":
                    params = diag.guard_update(nonfinite, params, state.params)
                    opt_state = diag.guard_update(
                        nonfinite, opt_state, state.opt_state
                    )
                    metrics["skipped_nonfinite"] = nonfinite.astype(jnp.int32)
                metrics.update(taps)
                metrics["nonfinite_step"] = nonfinite.astype(jnp.int32)
        return TrainState(params, opt_state, state.step + 1), metrics

    return train_step


def _zero_shard_axes(zero_pspecs):
    """Param-shaped tree of shard-axis indices from the ZeRO spec tree:
    the position 'data' occupies in each leaf's PartitionSpec, or -1 for
    leaves that stay replicated (no dp-divisible free axis). -1 rather
    than None so the tree keeps its leaves under tree_map."""

    def axis_of(spec):
        for i, entry in enumerate(tuple(spec)):
            names = entry if isinstance(entry, tuple) else (entry,)
            if DATA_AXIS in names:
                return i
        return -1

    return jax.tree_util.tree_map(
        axis_of, zero_pspecs, is_leaf=lambda x: isinstance(x, P)
    )


def make_manual_zero_train_step(
    mesh,
    cfg: GlomConfig,
    tcfg: TrainConfig,
    optimizer: optax.GradientTransformation,
    *,
    zero_stage: int,
    zero_pspecs,
    opt_pspecs,
    sp_strategy: str = "none",
    with_grad_norm: bool = True,
    interpret: bool = False,
):
    """The EXPLICIT form of the ZeRO weight update (the GSPMD form lives in
    train.trainer.make_train_step): one shard_map over (data, seq, model)
    in which every collective of the schedule is written out, so the wire
    pattern is inspectable in the jaxpr rather than inferred from GSPMD:

      1. value_and_grad of the LOCAL loss inside the region — no shard_map
         transpose, hence no automatic grad psum to fight;
      2. `lax.psum` of the cotangents over 'seq' (params are replicated
         over the patch bands, each band contributes a partial);
      3. `lax.psum_scatter(..., scatter_dimension=leaf's zero axis,
         tiled=True) / dp` over 'data' — THE reduce-scatter: each replica
         leaves the reduction holding exactly its owned 1/dp shard
         (leaves with no dp-divisible axis take a plain pmean and stay
         replicated);
      4. optimizer.update on the shard triple (grad shard, moment shard
         from the sharded-in opt state, param shard sliced at
         axis_index('data') * shard_size — the ownership partition);
      5. `lax.all_gather(..., tiled=True)` of the updated shards over
         'data' back to the replicated params the next forward reads.

    Stage 2 moves step 3 inside the microbatch scan so the accumulator
    only ever holds the owned shard.

    Requires model == 1: composing the ownership partition with TP-sharded
    weight shards is routed to the GSPMD form by DistributedTrainer."""
    if mesh.shape.get(MODEL_AXIS, 1) > 1:
        raise ValueError(
            "manual ZeRO step supports model == 1; the GSPMD path handles "
            "ZeRO x TP composition"
        )
    accum = pinned_grad_accum(tcfg)
    if tcfg.batch_size % accum != 0:
        raise ValueError(
            f"grad_accum={accum} must divide batch_size={tcfg.batch_size}"
        )
    dp = mesh.shape[DATA_AXIS]
    if (tcfg.batch_size // accum) % dp != 0:
        raise ValueError(
            f"microbatch {tcfg.batch_size // accum} not divisible "
            f"by data axis {dp}"
        )
    local_loss, seq, mp = _build_local_loss(
        mesh, cfg, tcfg, sp_strategy=sp_strategy, interpret=interpret
    )
    shard_axes = _zero_shard_axes(zero_pspecs)
    level = diag.resolve_telemetry_level(tcfg, supports_full=False)

    # The explicit collective pipeline: seq pre-reduction -> per-leaf
    # scatter/pmean. Every site reports its measured per-replica ring wire
    # bytes to telemetry.counters (recorded once, at trace time, inside
    # DistributedTrainer's counting eval_shape — see counters.recording).

    def seq_reduce(grads):
        if seq <= 1:
            return grads

        def leaf(g):
            return tele_counters.timed_collective(
                "zero_seq_psum", SEQ_AXIS, "reduce",
                tele_counters.ring_allreduce_bytes(g, seq),
                lambda x: lax.psum(x, SEQ_AXIS), g, collective="psum",
            )

        return jax.tree_util.tree_map(leaf, grads)

    def scatter_leaf(g, ax):
        if ax < 0:
            # No dp-divisible axis: the leaf stays replicated via a full
            # allreduce — a schedule detail comm_volume_model does NOT
            # price (it treats all of G as scattered), so the measured
            # counter is what keeps the drift honest.
            return tele_counters.timed_collective(
                "zero_pmean_fallback", DATA_AXIS, "reduce",
                tele_counters.ring_reduce_scatter_bytes(g, dp) * 2,
                lambda x: lax.pmean(x, DATA_AXIS), g, collective="pmean",
            )
        return tele_counters.timed_collective(
            "zero_psum_scatter", DATA_AXIS, "reduce",
            tele_counters.ring_reduce_scatter_bytes(g, dp),
            lambda x: lax.psum_scatter(
                x, DATA_AXIS, scatter_dimension=ax, tiled=True
            ) / dp,
            g, collective="psum_scatter", dim=ax,
        )

    def reduce_scatter_tree(grads):
        """Whole tree (non-accumulated / post-accumulation) or one
        microbatch of the stage-2 hook: tree -> tree of owned shards."""
        return jax.tree_util.tree_map(
            scatter_leaf, seq_reduce(grads), shard_axes
        )

    def shard_zeros(p, ax):
        if ax < 0:
            return jnp.zeros_like(p)
        shape = list(p.shape)
        shape[ax] //= dp
        return jnp.zeros(shape, p.dtype)

    def slice_shard(p, ax):
        if ax < 0:
            return p
        size = p.shape[ax] // dp
        return lax.dynamic_slice_in_dim(
            p, lax.axis_index(DATA_AXIS) * size, size, axis=ax
        )

    def gather_shard(p_shard, ax):
        if ax < 0:
            return p_shard
        return tele_counters.timed_collective(
            "zero_all_gather", DATA_AXIS, "gather",
            tele_counters.ring_all_gather_bytes(p_shard, dp),
            lambda x: lax.all_gather(x, DATA_AXIS, axis=ax, tiled=True),
            p_shard, collective="all_gather", dim=ax,
        )

    def sharded_grad_norm(g_shards):
        # sum-of-squares decomposes over the ownership partition: psum the
        # scattered leaves' local sums over 'data', count replicated leaves
        # once (identical on every replica).
        sq_scattered = jnp.zeros((), jnp.float32)
        sq_replicated = jnp.zeros((), jnp.float32)
        for g, ax in zip(
            jax.tree_util.tree_leaves(g_shards),
            jax.tree_util.tree_leaves(shard_axes),
        ):
            s = jnp.sum(jnp.square(g.astype(jnp.float32)))
            if ax < 0:
                sq_replicated = sq_replicated + s
            else:
                sq_scattered = sq_scattered + s
        return jnp.sqrt(lax.psum(sq_scattered, DATA_AXIS) + sq_replicated)

    def update_body(params, opt_state, img, noise):
        if accum > 1:
            # trainer.accumulate_grads on the LOCAL band — the strided
            # grouping applies per shard exactly as it does globally
            # (b_loc % accum == 0 is guaranteed by the checks above, so
            # local row j of microbatch i is global row k*b_loc + j with
            # the same i = j % accum). ZeRO-2 rides its stage-2 hook:
            # scatter each microbatch BEFORE accumulating, zeros at the
            # owned-shard shapes, so the buffer never holds a full leaf.
            from glom_tpu.train.trainer import accumulate_grads

            def scatter_microbatch(g):
                # One trace, `accum` executions: scale the measured
                # counters so they price the whole step's wire traffic.
                with tele_counters.scaled(accum), jax.named_scope("grad_reduce"):
                    return reduce_scatter_tree(g)

            gkw = (
                dict(
                    grad_transform=scatter_microbatch,
                    grad_init=lambda: jax.tree_util.tree_map(
                        shard_zeros, params, shard_axes
                    ),
                )
                if zero_stage >= 2
                else {}
            )
            loss_loc, grads = accumulate_grads(
                local_loss, params, img, noise, accum, **gkw
            )
            if zero_stage >= 2:
                g_shards = grads
            else:
                with jax.named_scope("grad_reduce"):
                    g_shards = reduce_scatter_tree(grads)
        else:
            loss_loc, grads = jax.value_and_grad(local_loss)(params, img, noise)
            with jax.named_scope("grad_reduce"):
                g_shards = reduce_scatter_tree(grads)

        with jax.named_scope("optimizer"):
            p_shards = jax.tree_util.tree_map(slice_shard, params, shard_axes)
            updates, new_opt = optimizer.update(g_shards, opt_state, p_shards)
            new_p_shards = optax.apply_updates(p_shards, updates)
        with jax.named_scope("grad_reduce"):
            # the all-gather half of the sharded update's wire pattern
            new_params = jax.tree_util.tree_map(
                gather_shard, new_p_shards, shard_axes
            )
        with jax.named_scope("reconstruction"):
            loss = lax.pmean(loss_loc, DATA_AXIS)
        metrics = {"loss": loss}
        with jax.named_scope("step_metrics"):
            if with_grad_norm or level != "off":
                # grad_norm is part of the scalars bundle on every path (it
                # is computed for the guard anyway): the fast-variant record
                # must carry the same keys here as on the GSPMD/manual steps.
                gnorm = sharded_grad_norm(g_shards)
                metrics["grad_norm"] = gnorm
            if level != "off":
                # In-region telemetry on the sharded triple: update norm
                # via the same ownership-partition decomposition as the
                # grad norm; param norm on the gathered (replicated) tree
                # is collective-free. The guard's where() runs on the
                # gathered params and the sharded opt state alike — the
                # non-finite flag is built from psum'd scalars, so it is
                # replica-invariant.
                from glom_tpu.telemetry.diagnostics import nonfinite_flag

                metrics["update_norm"] = sharded_grad_norm(updates)
                metrics["param_norm"] = optax.global_norm(new_params)
                nonfinite = nonfinite_flag(loss, gnorm)
                if tcfg.nonfinite_policy == "skip":
                    new_params = diag.guard_update(nonfinite, new_params, params)
                    new_opt = diag.guard_update(nonfinite, new_opt, opt_state)
                    metrics["skipped_nonfinite"] = nonfinite.astype(jnp.int32)
                metrics["nonfinite_step"] = nonfinite.astype(jnp.int32)
        return new_params, new_opt, metrics

    batch_spec = P(DATA_AXIS)
    param_spec = _manual_param_spec(mp)
    metric_keys = ["loss"]
    if with_grad_norm or level != "off":
        metric_keys.append("grad_norm")
    if level != "off":
        metric_keys += ["update_norm", "param_norm", "nonfinite_step"]
        if tcfg.nonfinite_policy == "skip":
            metric_keys.append("skipped_nonfinite")
    update_sm = jax.shard_map(
        update_body,
        mesh=mesh,
        in_specs=(param_spec, opt_pspecs, batch_spec, batch_spec),
        out_specs=(param_spec, opt_pspecs, {k: P() for k in metric_keys}),
        check_vma=False,
    )

    def train_step(state: TrainState, img: jnp.ndarray, rng: jax.Array):
        with jax.named_scope("noise"):
            noise_rng = jax.random.fold_in(rng, state.step)
            noise = tcfg.noise_std * jax.random.normal(
                noise_rng, img.shape, img.dtype
            )
        new_params, new_opt, metrics = update_sm(
            state.params, state.opt_state, img, noise
        )
        metrics = dict(metrics, step=state.step)
        return TrainState(new_params, new_opt, state.step + 1), metrics

    return train_step
