"""The functional GLOM core: parameter init, one column-update step, and the
scanned T-iteration forward.

Reference parity: Glom.__init__ / Glom.forward (glom_pytorch/glom_pytorch.py:
75-152); the full behavioral contract is SURVEY.md §3.2 and is locked by
tests/test_model.py against the NumPy oracle. Where the reference runs T
eager iterations (one CUDA kernel launch per op), this core is a single
`lax.scan` body compiled once by XLA — the loop is fused, weights stay
resident, and the T iterations pipeline on-chip.

Design notes (TPU-first, not a port):
  * Pure functions over a `GlomParams` pytree — jit/grad/vmap/pjit compose.
  * `iters` is a static scan length (no data-dependent control flow).
  * `consensus_fn` is injectable so the dense op can be swapped for the
    Pallas blockwise kernel or the ring/Ulysses sharded forms without
    touching the core update equation.
  * `remat=True` wraps the scan body in jax.checkpoint — BASELINE config 5's
    "ckpt over iters" — trading recompute for O(1) activation memory in T.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from einops import rearrange

from glom_tpu.ops.consensus import build_local_mask, consensus_attention
from glom_tpu.ops.ffw import GroupedFFWParams, grouped_ffw, init_grouped_ffw
from glom_tpu.ops.patch import LinearParams, image_to_tokens, init_linear
from glom_tpu.utils.config import GlomConfig
from glom_tpu.utils.helpers import default, exists

ConsensusFn = Callable[[jnp.ndarray], jnp.ndarray]
FFWFn = Callable[[GroupedFFWParams, jnp.ndarray], jnp.ndarray]


def _on_tpu() -> bool:
    """Seam for the dispatch policy's platform check (tests patch this to
    exercise the TPU-side routing on the CPU mesh)."""
    return jax.devices()[0].platform == "tpu"


class GlomParams(NamedTuple):
    """Learnable state. Mirrors the reference module tree (SURVEY.md §3.1)."""

    token_embed: LinearParams  # Linear(p*p*c -> d)        (reference :88-91)
    pos_emb: jnp.ndarray  # [n, d] learned position table   (reference :92)
    init_levels: jnp.ndarray  # [L, d] learned column init  (reference :95)
    bottom_up: GroupedFFWParams  # groups = L               (reference :98)
    top_down: GroupedFFWParams  # groups = L - 1            (reference :99)


def init_glom(key: jax.Array, cfg: GlomConfig, dtype=jnp.float32) -> GlomParams:
    k_tok, k_pos, k_lvl, k_bu, k_td = jax.random.split(key, 5)
    return GlomParams(
        token_embed=init_linear(k_tok, cfg.patch_dim, cfg.dim, dtype),
        pos_emb=jax.random.normal(k_pos, (cfg.num_patches, cfg.dim), dtype),
        init_levels=jax.random.normal(k_lvl, (cfg.levels, cfg.dim), dtype),
        bottom_up=init_grouped_ffw(k_bu, cfg.levels, cfg.dim, cfg.mult, dtype),
        top_down=init_grouped_ffw(k_td, cfg.levels - 1, cfg.dim, cfg.mult, dtype),
    )


def contribution_divisor(levels: int, dtype=jnp.float32) -> jnp.ndarray:
    """[L, 1] per-level mean divisor: 4 contributions everywhere except the
    top level, which has no top-down input and divides by 3 (reference
    :121-122 — a naive mean-of-stack is wrong at the top)."""
    div = np.full((levels, 1), 4.0, dtype=np.float64)
    div[-1] = 3.0
    return jnp.asarray(div, dtype)


def update_step(
    params: GlomParams,
    levels: jnp.ndarray,
    bottom: jnp.ndarray,
    pos: jnp.ndarray,
    divisor: jnp.ndarray,
    *,
    consensus_fn: ConsensusFn,
    ffw_fn: FFWFn = grouped_ffw,
) -> jnp.ndarray:
    """One column update: the mean of (previous value, bottom-up, top-down,
    consensus). The §3.2 loop body (reference :124-140).

    levels: [b, n, L, d]   bottom: [b, n, 1, d]   pos: [1, n, 1, d]
    """
    with_input = jnp.concatenate([bottom, levels], axis=-2)  # [b, n, L+1, d]
    # Bottom-up sees (image tokens, levels 1..L-1) -> update for levels 1..L:
    # level 1 re-reads the RAW tokens every iteration (reference :127).
    with jax.named_scope("bottom_up"):
        bottom_up_out = ffw_fn(params.bottom_up, with_input[..., :-1, :])
    # Top-down sees levels 2..L with the positional embedding injected HERE
    # and only here (reference :129); produces updates for levels 1..L-1,
    # zero-padded at the top (reference :130).
    with jax.named_scope("top_down"):
        top_down_out = ffw_fn(params.top_down, with_input[..., 2:, :] + pos)
        top_down_out = jnp.pad(top_down_out, ((0, 0), (0, 0), (0, 1), (0, 0)))
    with jax.named_scope("consensus"):
        consensus = consensus_fn(levels)
    with jax.named_scope("mean_update"):
        new_levels = (levels + bottom_up_out + top_down_out + consensus) / divisor
    return new_levels.astype(levels.dtype)


def glom_forward(
    params: GlomParams,
    img: jnp.ndarray,
    cfg: GlomConfig,
    *,
    iters: Optional[int] = None,
    levels: Optional[jnp.ndarray] = None,
    return_all: bool = False,
    remat: bool = False,
    compute_dtype=None,
    consensus_fn: Optional[ConsensusFn] = None,
    use_pallas: bool = False,
    unroll: bool = False,
) -> jnp.ndarray:
    """The T-iteration GLOM forward (reference :103-152).

    img: [b, c, H, W] -> [b, n, L, d], or [T+1, b, n, L, d] with return_all
    (T+1 includes the INITIAL state, reference :119/:140/:143).

    `levels` may be passed in to continue from a previous call (the README
    temporal/video recipe — detach between frames with lax.stop_gradient).
    `iters`/`return_all`/`remat` are static under jit.

    use_pallas=True selects the fully-fused TPU path: a LEVEL-MAJOR
    [L, b, n, d] scan carry (zero layout transposes between ops), the
    Pallas fused grouped-MLP for both FFWs, and the Pallas blockwise
    consensus+mean kernel (kernels/consensus_update.py) for the rest of
    the update. Auto-falls back to XLA ops off-TPU / unsupported shapes.
    Leave False inside GSPMD-sharded model-parallel regions — the custom
    calls have no partitioning rule for sharded weights.

    unroll=True unrolls the scan into straight-line code (identical math;
    see TrainConfig.scan_unroll for the trade-off).
    """
    T = default(iters, cfg.default_iters)

    if use_pallas and consensus_fn is None:
        if compute_dtype is not None:
            params = jax.tree_util.tree_map(
                lambda t: t.astype(compute_dtype), params
            )
            img = img.astype(compute_dtype)
            if exists(levels):
                levels = levels.astype(compute_dtype)
        return _glom_forward_fused(
            params, img, cfg, iters=T, levels_in=levels,
            return_all=return_all, remat=remat, unroll=unroll,
        )

    if use_pallas:
        # Custom consensus_fn + Pallas FFWs: reference-layout path with the
        # fused MLP swapped in (used by sharded per-shard bodies).
        from glom_tpu.kernels import fused_grouped_ffw

        ffw_fn: FFWFn = fused_grouped_ffw
    else:
        ffw_fn = grouped_ffw

    if consensus_fn is None:
        local_mask = build_local_mask(cfg.num_patches_side, cfg.local_consensus_radius)
        consensus_fn = partial(
            consensus_attention,
            attend_self=cfg.consensus_self,
            local_mask=local_mask,
        )

    # Cast params and inputs ONCE, outside the scan — casting inside the body
    # would re-run (and re-run again under remat) every iteration.
    if compute_dtype is not None:
        params = jax.tree_util.tree_map(lambda t: t.astype(compute_dtype), params)
        img = img.astype(compute_dtype)
        if exists(levels):
            levels = levels.astype(compute_dtype)

    with jax.named_scope("image_to_tokens"):
        tokens = image_to_tokens(params.token_embed, img, cfg.patch_size)  # [b,n,d]
    b, n, d = tokens.shape
    pos = rearrange(params.pos_emb, "n d -> 1 n 1 d")
    bottom = rearrange(tokens, "b n d -> b n 1 d")

    if not exists(levels):
        levels = jnp.broadcast_to(
            params.init_levels[None, None], (b, n, cfg.levels, d)
        ).astype(tokens.dtype)

    divisor = contribution_divisor(cfg.levels, jnp.float32)

    def body(carry, _):
        new = update_step(
            params, carry, bottom, pos, divisor,
            consensus_fn=consensus_fn, ffw_fn=ffw_fn,
        )
        return new, (new if return_all else None)

    if remat:
        body = jax.checkpoint(body)

    # "loop" holds the scan and what autodiff adds around its body (saved
    # residuals, counters); the body's own phases nest inside it.
    with jax.named_scope("loop"):
        final, stacked = jax.lax.scan(body, levels, None, length=T, unroll=unroll)

    if return_all:
        return jnp.concatenate([levels[None], stacked], axis=0)  # [T+1, b, n, L, d]
    return final


def resolve_vjp_path(
    cfg: GlomConfig,
    b: int,
    iters: int,
    *,
    remat: bool = False,
    use_pallas: bool = False,
    itemsize: int = 2,
    custom_consensus: bool = False,
    return_all: bool = False,
    scan_only: bool = False,
    assume_on_tpu: bool = False,
) -> str:
    """THE single resolution source for which backward implementation a
    training forward at these static shapes will use. Both the dispatch
    (_use_fused_loop) and the trainers' metric logging call this, so a run
    can never train on a different backward than its records claim (the
    same discipline effective_sp_strategy applies to collectives).

    Returns one of:
      'fused_loop'     — the hand-rolled whole-loop VJP (kernels/fused_loop)
      'scan_blockwise' — lax.scan forward, Pallas blockwise consensus bwd
      'scan_dense'     — lax.scan forward, dense XLA/stats consensus bwd

    scan_only=True excludes the fused loop regardless of eligibility — the
    manual TP shard bodies (parallel/manual.py, mp > 1) scan the kernels
    directly and never dispatch to the whole-loop VJP.

    assume_on_tpu=True bypasses only the platform check (the CPU
    interpret-mode shard tests drive the real dispatch policy without
    hardware).
    """
    from glom_tpu.kernels.consensus_update import _use_blockwise_bwd
    from glom_tpu.kernels.fused_loop import loop_supported

    n, d, L = cfg.num_patches, cfg.dim, cfg.levels
    if not use_pallas or custom_consensus or not (assume_on_tpu or _on_tpu()):
        return "scan_dense"
    if (
        not scan_only
        and not return_all
        and b >= 8
        and loop_supported(
            L, b, n, d, d * cfg.mult, itemsize, iters, n, remat
        )
    ):
        return "fused_loop"
    blockwise = _use_blockwise_bwd(
        (L, b, n, d), cfg.num_patches_side,
        float(cfg.local_consensus_radius), "auto", itemsize,
    )
    return "scan_blockwise" if blockwise else "scan_dense"


def _use_fused_loop(
    bottom_up: GroupedFFWParams, pos: jnp.ndarray, levels_lm: jnp.ndarray,
    cfg: GlomConfig, iters: int, stack: bool, remat: bool,
    interpret: bool = False,
) -> bool:
    """Dispatch to the hand-rolled whole-loop VJP (kernels/fused_loop.py)
    on the flagship training regime: TPU, final-state-only, the
    single-tile consensus row, tileable FFW shapes, and the measured
    batched regime where the in-VMEM backward wins (B >= 8 — see
    consensus_update._use_blockwise_bwd's crossover table). remat=True
    rides the loop too (round 5): the VJP's recompute-per-iteration mode
    keeps the glue-free structure at BASELINE config 5's
    checkpoint-over-iters regime.

    Thin shape-consistency gate over resolve_vjp_path (the single
    resolution source — the b<8 / return_all policy lives THERE): this
    checks only what requires the actual arrays (dtype agreement,
    pos-emb/config coherence, whole rows and whole FFW weights — a
    sequence or tensor-parallel shard holds neither). interpret=True (the
    CPU shard_map tests) bypasses only the platform check."""
    _, b, n, d = levels_lm.shape
    dtype = bottom_up.w1.dtype
    if levels_lm.dtype != dtype:
        return False
    if (n, d) != (cfg.num_patches, cfg.dim) or pos.shape[0] != n:
        return False
    if bottom_up.w1.shape[-1] != d * cfg.mult:
        return False
    return (
        resolve_vjp_path(
            cfg, b, iters, remat=remat, use_pallas=True,
            itemsize=dtype.itemsize, return_all=stack,
            assume_on_tpu=interpret,
        )
        == "fused_loop"
    )


def level_major_loop(
    bottom_up: GroupedFFWParams,
    top_down: GroupedFFWParams,
    pos: jnp.ndarray,
    tokens: jnp.ndarray,
    levels_lm: jnp.ndarray,
    cfg: GlomConfig,
    *,
    iters: int,
    remat: bool,
    unroll: bool = False,
    stack: bool = False,
    ffw_lm=None,
    consensus_shard: Optional[ConsensusFn] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """THE level-major GLOM loop, on arrays local to the caller: `iters`
    column updates of levels_lm [L, b, n, d] from tokens [b, n, d] and
    pos [n, d]. Returns the final carry, or with stack=True all T+1 states
    [T+1, L, b, n, d] including the initial one. Two callers: the
    single-device forward below (whole rows, whole weights) and
    parallel/manual.py's shard body (its band of rows, its share of the
    FFW hidden axis), which differ in two arguments only they can know:

      ffw_lm           the level-major grouped FFW, (params, x [G, M, d],
                       add=[n, d] | None) -> [G, M, d]. None: the Pallas
                       kernel, which manual passes too unless it wraps it
                       for tensor parallelism (a psum over 'model') or
                       runs the XLA form.
      consensus_shard  [b, n, L, d] -> same, for rows that span devices
                       (ring / halo / Ulysses) or the dense XLA op. None:
                       consensus and the 4-way mean run fused in the
                       Pallas consensus kernel.

    With the plain Pallas FFW and no consensus_shard the loop is whole on
    this device, and where _use_fused_loop admits the shape it runs as the
    hand-rolled whole-loop VJP: the ONE dispatch site of fused_glom_loop."""
    from glom_tpu.kernels import fused_consensus_update
    from glom_tpu.kernels.grouped_mlp import fused_grouped_ffw_lm

    L, b, n, d = levels_lm.shape
    side, radius = cfg.num_patches_side, float(cfg.local_consensus_radius)
    ffw_lm = default(ffw_lm, fused_grouped_ffw_lm)

    if (
        ffw_lm is fused_grouped_ffw_lm
        and consensus_shard is None
        and _use_fused_loop(
            bottom_up, pos, levels_lm, cfg, iters, stack, remat, interpret
        )
    ):
        from glom_tpu.kernels.fused_loop import fused_glom_loop

        # One scope for the whole-loop VJP: its kernels (`loop_*`) run
        # bottom-up, top-down and consensus of every iteration, and the
        # glue XLA leaves around them belongs to no single one of those.
        with jax.named_scope("loop"):
            return fused_glom_loop(
                bottom_up, top_down, pos, tokens, levels_lm, iters, side,
                radius, cfg.consensus_self, interpret, remat,
            )

    tokens_lm = tokens[None]  # [1, b, n, d]
    if exists(consensus_shard):
        divisor_lm = contribution_divisor(L, jnp.float32).reshape(L, 1, 1, 1)

    def body(carry, _):
        lv = carry
        # Bottom-up input: (image tokens, levels 1..L-1) — level 1 re-reads
        # the RAW tokens every iteration (reference :127).
        with jax.named_scope("bottom_up"):
            bu_in = jnp.concatenate([tokens_lm, lv[:-1]], axis=0)
            bu_out = ffw_lm(
                bottom_up, bu_in.reshape(L, b * n, d)
            ).reshape(L, b, n, d)
        # Top-down input: levels 2..L with pos-emb injected HERE only
        # (reference :129); the top level's zero pad + the 4-vs-3 divisor
        # live in the consensus kernel's epilogue. The pos addend folds
        # into the kernel's tile loads (add=) — the [L-1, b, n, d] sum
        # never materializes on the fused path.
        with jax.named_scope("top_down"):
            td_out = ffw_lm(
                top_down,
                lv[1:].reshape(L - 1, b * n, d),
                add=pos,
            ).reshape(L - 1, b, n, d)
        if not exists(consensus_shard):
            with jax.named_scope("consensus_update"):
                new = fused_consensus_update(
                    lv, bu_out, td_out,
                    side=side,
                    radius=radius,
                    attend_self=cfg.consensus_self,
                )
        else:
            with jax.named_scope("consensus"):
                cons = consensus_shard(jnp.transpose(lv, (1, 2, 0, 3)))
                cons_lm = jnp.transpose(cons, (2, 0, 1, 3))
            with jax.named_scope("mean_update"):
                td_full = jnp.concatenate(
                    [td_out, jnp.zeros_like(td_out[:1])], axis=0
                )
                new = (
                    (
                        lv.astype(jnp.float32)
                        + bu_out.astype(jnp.float32)
                        + td_full.astype(jnp.float32)
                        + cons_lm.astype(jnp.float32)
                    )
                    / divisor_lm
                ).astype(lv.dtype)
        return new, (new if stack else None)

    if remat:
        body = jax.checkpoint(body)

    with jax.named_scope("loop"):
        final, stacked = jax.lax.scan(
            body, levels_lm, None, length=iters, unroll=unroll
        )

    if stack:
        return jnp.concatenate([levels_lm[None], stacked], axis=0)
    return final


def _glom_forward_fused(
    params: GlomParams,
    img: jnp.ndarray,
    cfg: GlomConfig,
    *,
    iters: int,
    levels_in: Optional[jnp.ndarray],
    return_all: bool,
    remat: bool,
    unroll: bool = False,
) -> jnp.ndarray:
    """The fused TPU forward: level-major carry + Pallas kernels.

    Same behavioral contract as the reference path (locked by
    tests/test_model.py::TestPallasParity); the differences are purely
    physical: the scan carry is [L, b, n, d] so the grouped-FFW batched
    matmuls and the per-(level, image) consensus tiles are layout-native,
    and the whole 4-way mean update runs inside the consensus kernel's
    epilogue instead of as separate XLA HBM sweeps.
    """
    with jax.named_scope("image_to_tokens"):
        tokens = image_to_tokens(params.token_embed, img, cfg.patch_size)
    b, n, d = tokens.shape

    if exists(levels_in):
        # Keep the caller's carry dtype (the reference path's scan carry is
        # new.astype(levels.dtype) — the temporal recipe must see identical
        # dtype behavior under both flags).
        levels_lm = jnp.transpose(levels_in, (2, 0, 1, 3))
    else:
        levels_lm = jnp.broadcast_to(
            params.init_levels[:, None, None], (cfg.levels, b, n, d)
        ).astype(tokens.dtype)

    out = level_major_loop(
        params.bottom_up, params.top_down, params.pos_emb, tokens, levels_lm,
        cfg, iters=iters, remat=remat, unroll=unroll, stack=return_all,
    )
    if return_all:
        return jnp.transpose(out, (0, 2, 3, 1, 4))  # [T+1, b, n, L, d]
    return jnp.transpose(out, (1, 2, 0, 3))  # [b, n, L, d]
