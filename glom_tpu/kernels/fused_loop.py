"""Hand-rolled VJP over the WHOLE T-iteration GLOM loop.

Why this exists (measured on a v5e profile in round 3): with the
per-op custom_vjps the train step was ~86% Pallas kernels, and the
remaining ~6% device time was XLA glue BETWEEN them that op-local autodiff
cannot remove:

  * the per-iteration `concatenate([tokens, levels[:-1]])` feeding the
    bottom-up FFW (1.0 ms) and its transpose split in the backward
    (1.8 ms);
  * the cross-iteration dw/db gradient accumulation: each unrolled
    iteration's FFW backward emits fresh [G, d, f] f32 weight grads and
    XLA sums them with add_any HBM sweeps (2.5 ms);
  * the d(td) cotangent slice `dmean[:L-1]` copied between the consensus
    backward and the top-down FFW backward (1.2 ms).

This module replaces the scanned/unrolled loop with ONE jax.custom_vjp
whose forward and backward are Python loops over the same Pallas kernels,
re-plumbed so the glue disappears structurally:

  * the carry is an [L+1]-SLOT level-major array `ext` with the image
    tokens pinned in slot 0 and level l in slot l+1. Every consumer reads
    its slice via BlockSpec index-map OFFSETS on the shared buffer —
    bottom-up input = slots 0..L-1 (map g -> g), top-down input = slots
    2..L (map g -> g+2), consensus levels = slots 1..L (map g -> g+1) —
    so no concatenate/slice ever materializes (reference hot loop
    glom_pytorch/glom_pytorch.py:124-140 rebuilt without its cat).
  * the FFW backward kernels take INCOMING dw/db (and pos-emb da) f32
    accumulators and seed their m==0 init from them
    (grouped_mlp._mlp_bwd_tail inc=), so weight-gradient accumulation
    across the T iterations happens in-kernel, not in XLA add_any sweeps.
  * the consensus backward kernel reads THREE cotangent streams — the
    previous iteration's consensus dlevels, dx_bu (slot-shifted), and
    dx_td (slot-shifted) — via clamped index maps and combines them
    in-register; the top-down FFW backward then reads the resulting
    dmean's slots 0..L-2 directly off the [L, ...] buffer (grid has L-1
    groups), so the dmean[:L-1] slice never exists.

Scope: the flagship training regime — no remat (the loop IS unrolled),
return_all=False (the trainer's loss reads one iteration: the loop runs
exactly `iters` steps), single-tile consensus rows (n <= 512), tileable
FFW shapes. Everything else stays on models/core's scan paths.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from glom_tpu.kernels.consensus_update import (
    _SMALL_BWD_N,
    _consensus_update_kernel,
    _fit_tile_b,
    _forward as _cons_forward,
    _pick_tile as _pick_cons_tile,
    _pick_tile_b as _pick_cons_tile_b,
    _small_bwd_math,
)
from glom_tpu.kernels.grouped_mlp import (
    _WS_BUDGET,
    _bwd_compiler_params,
    _bwd_ws,
    _fused_forward,
    _fused_forward_add,
    _mlp_bwd_kernel_saved,
    _mlp_bwd_kernel_saved_add,
    _mlp_bwd_tail,
    _mlp_kernel,
    _mlp_kernel_add,
    _pick_bwd_tile,
    _pick_tile,
    _tiled_add,
)
from glom_tpu.ops.ffw import GroupedFFWParams

_VMEM_64M = pltpu.CompilerParams(vmem_limit_bytes=64 * 1024 * 1024)
_VMEM_32M = pltpu.CompilerParams(vmem_limit_bytes=32 * 1024 * 1024)

# Per-iteration residual budget for the whole loop (saved ext carries +
# both FFW pre-activations + consensus stats, times `iters`). Above this
# the non-remat residual stack risks HBM exhaustion and the scan paths
# (whose save-pre gates handle their own budgets) take over. 10GB of a
# v5e's 16GB: batch 96 at the flagship (9.0GB of residuals) stays on the
# loop; batch 128 (12GB) falls back.
_RESIDUAL_BUDGET = 10 * 1024 * 1024 * 1024


def _ffw_fwd_ext(
    params: GroupedFFWParams,
    ext2: jnp.ndarray,  # [L+1, M, d] slot carry (reshaped level-major)
    offset: int,
    G: int,
    *,
    tile_m: int,
    interpret: bool,
    add: jnp.ndarray | None = None,
    save_pre: bool = True,
):
    """Grouped-FFW forward reading group g's input from carry slot
    g + offset — the index map IS the slice. Saves the pre-activation by
    default (the non-remat training forward); the REMAT forward passes
    save_pre=False so the [G, M, f] pre never hits HBM — the backward
    recomputes it per iteration via _pre_fwd_ext instead. (The no-grad
    primal uses grouped_mlp's plain forms.) Returns (out, pre|None)."""
    M, d = ext2.shape[1], ext2.shape[2]
    f = params.w1.shape[-1]
    grid = (G, M // tile_m)
    out_shape = (
        jax.ShapeDtypeStruct((G, M, d), ext2.dtype),
        jax.ShapeDtypeStruct((G, M, f), ext2.dtype),
    )
    out_spec = (
        pl.BlockSpec((1, tile_m, d), lambda g, m: (g, m, 0)),
        pl.BlockSpec((1, tile_m, f), lambda g, m: (g, m, 0)),
    )
    if not save_pre:
        out_shape, out_spec = out_shape[:1], out_spec[:1]
    x_spec = pl.BlockSpec(
        (1, tile_m, d), lambda g, m, _o=offset: (g + _o, m, 0)
    )
    w_specs = [
        pl.BlockSpec((1, d, f), lambda g, m: (g, 0, 0)),  # w1
        pl.BlockSpec((1, 1, f), lambda g, m: (g, 0, 0)),  # b1
        pl.BlockSpec((1, f, d), lambda g, m: (g, 0, 0)),  # w2
        pl.BlockSpec((1, 1, d), lambda g, m: (g, 0, 0)),  # b2
    ]
    if add is not None:
        out = pl.pallas_call(
            _mlp_kernel_add,
            out_shape=out_shape,
            grid=grid,
            in_specs=[x_spec, pl.BlockSpec(add.shape, lambda g, m: (0, 0))]
            + w_specs,
            out_specs=out_spec,
            compiler_params=_VMEM_64M,
            interpret=interpret,
            name="loop_ffw_add_fwd",
        )(ext2, add, params.w1, params.b1[:, None, :], params.w2,
          params.b2[:, None, :])
    else:
        out = pl.pallas_call(
            _mlp_kernel,
            out_shape=out_shape,
            grid=grid,
            in_specs=[x_spec] + w_specs,
            out_specs=out_spec,
            compiler_params=_VMEM_64M,
            interpret=interpret,
            name="loop_ffw_fwd",
        )(ext2, params.w1, params.b1[:, None, :], params.w2,
          params.b2[:, None, :])
    return out if save_pre else (out[0], None)


def _pre_kernel(x_ref, w1_ref, b1_ref, pre_ref):
    """First-matmul-only recompute: pre = x @ w1 + b1 in the compute dtype —
    bit-identical to the pre the training forward would have saved
    (_mlp_kernel computes it with the same f32-accumulate dot + cast)."""
    pre = jnp.dot(x_ref[0], w1_ref[0], preferred_element_type=jnp.float32)
    pre = pre + b1_ref[0].astype(jnp.float32)
    pre_ref[0] = pre.astype(x_ref.dtype)


def _pre_add_kernel(x_ref, a_ref, w1_ref, b1_ref, pre_ref):
    """_pre_kernel with the positional addend folded into the input load
    (matches _mlp_kernel_add's pre exactly)."""
    xa = _tiled_add(x_ref[0], a_ref[...]).astype(x_ref.dtype)
    pre = jnp.dot(xa, w1_ref[0], preferred_element_type=jnp.float32)
    pre = pre + b1_ref[0].astype(jnp.float32)
    pre_ref[0] = pre.astype(xa.dtype)


def _pre_fwd_ext(
    params: GroupedFFWParams,
    ext2: jnp.ndarray,  # [L+1, M, d] saved slot carry
    offset: int,
    G: int,
    *,
    tile_m: int,
    interpret: bool,
    add: jnp.ndarray | None = None,
):
    """REMAT-mode pre-activation recompute for one iteration: only the
    first matmul re-runs (the second matmul's output never feeds the
    backward — the consensus stats (m, l) are saved instead of recomputed),
    so the remat tax is HALF the FFW forward, not a full forward re-run."""
    M, d = ext2.shape[1], ext2.shape[2]
    f = params.w1.shape[-1]
    grid = (G, M // tile_m)
    x_spec = pl.BlockSpec((1, tile_m, d), lambda g, m, _o=offset: (g + _o, m, 0))
    w1_spec = pl.BlockSpec((1, d, f), lambda g, m: (g, 0, 0))
    b1_spec = pl.BlockSpec((1, 1, f), lambda g, m: (g, 0, 0))
    out_shape = jax.ShapeDtypeStruct((G, M, f), ext2.dtype)
    out_spec = pl.BlockSpec((1, tile_m, f), lambda g, m: (g, m, 0))
    if add is not None:
        return pl.pallas_call(
            _pre_add_kernel,
            out_shape=out_shape,
            grid=grid,
            in_specs=[
                x_spec, pl.BlockSpec(add.shape, lambda g, m: (0, 0)),
                w1_spec, b1_spec,
            ],
            out_specs=out_spec,
            compiler_params=_VMEM_64M,
            interpret=interpret,
            name="loop_ffw_add_pre_fwd",
        )(ext2, add, params.w1, params.b1[:, None, :])
    return pl.pallas_call(
        _pre_kernel,
        out_shape=out_shape,
        grid=grid,
        in_specs=[x_spec, w1_spec, b1_spec],
        out_specs=out_spec,
        compiler_params=_VMEM_64M,
        interpret=interpret,
        name="loop_ffw_pre_fwd",
    )(ext2, params.w1, params.b1[:, None, :])


def _ffw_bwd_acc_kernel(
    x_ref, w1_ref, pre_ref, w2_ref, g_ref,
    dw1i_ref, db1i_ref, dw2i_ref, db2i_ref,
    dx_ref, dw1_ref, db1_ref, dw2_ref, db2_ref,
):
    """Saved-pre FFW backward with incoming weight-grad accumulators: the
    m==0 init seeds from the previous iteration's totals (see
    _mlp_bwd_tail inc=)."""
    _mlp_bwd_tail(
        pre_ref[0].astype(jnp.float32), x_ref[0], g_ref[0], w1_ref[0],
        w2_ref[0], dx_ref, dw1_ref, db1_ref, dw2_ref, db2_ref,
        inc=(dw1i_ref, db1i_ref, dw2i_ref, db2i_ref),
    )


def _ffw_bwd_acc_add_kernel(
    x_ref, a_ref, w1_ref, pre_ref, w2_ref, g_ref,
    dw1i_ref, db1i_ref, dw2i_ref, db2i_ref, dai_ref,
    dx_ref, dw1_ref, db1_ref, dw2_ref, db2_ref, da_ref,
):
    """_ffw_bwd_acc_kernel for the folded positional addend: the true layer
    input is x + tile(a), and da accumulates across the whole grid AND
    across loop iterations (seeded from dai at the first program)."""
    xa = _tiled_add(x_ref[0], a_ref[...]).astype(x_ref.dtype)
    dx32 = _mlp_bwd_tail(
        pre_ref[0].astype(jnp.float32), xa, g_ref[0], w1_ref[0], w2_ref[0],
        dx_ref, dw1_ref, db1_ref, dw2_ref, db2_ref,
        inc=(dw1i_ref, db1i_ref, dw2i_ref, db2i_ref),
    )
    tm, d = dx32.shape
    n = a_ref.shape[0]
    da_step = jnp.sum(dx32.reshape(tm // n, n, d), axis=0)
    first = (pl.program_id(0) == 0) & (pl.program_id(1) == 0)

    @pl.when(first)
    def _init_da():
        da_ref[...] = dai_ref[...] + da_step

    @pl.when(jnp.logical_not(first))
    def _accum_da():
        da_ref[...] += da_step


def _chain_ws_ok(bt: int, d: int, f: int, itemsize: int, n: int) -> bool:
    """Can the accumulator-CHAINED backward kernels fit the working-set
    budget? Chaining adds the incoming dw1/dw2 f32 blocks (2*d*f*4) and
    the in+out da pair (n*d*8) to the per-op backward working set. At the
    flagship (d=512, f=2048) that is ~34.5MB — fits; at the pod per-TP-rank
    shape (d=1024, f=2048) it is ~58.7MB > the 48MB budget, so the loop
    there runs the UNCHAINED variant (fresh per-iteration dw, XLA adds) —
    the same per-op kernel footprint that measured 75-78M of Mosaic stack
    under the 100MB grant on silicon."""
    return _bwd_ws(bt, d, f, itemsize) + 2 * d * f * 4 + n * d * 8 <= _WS_BUDGET


def _ffw_bwd_ext(
    params: GroupedFFWParams,
    ext2: jnp.ndarray,      # [L+1, M, d] saved carry (this iteration's input)
    offset: int,
    G: int,
    pre: jnp.ndarray,       # [G, M, f] saved pre-activation
    gcot2: jnp.ndarray,     # [L, M, d] dmean — G <= L reads slots 0..G-1
    acc: GroupedFFWParams,  # incoming f32 dw/db accumulators
    *,
    tile_m: int,
    interpret: bool,
    add: jnp.ndarray | None = None,
    da_in: jnp.ndarray | None = None,
    chain: bool = True,
):
    """One iteration's FFW backward: x via slot-offset map, cotangent read
    directly off the full dmean buffer (the td call's G = L-1 grid IS the
    [:L-1] slice), dw/db (and da) chained through incoming accumulators.

    chain=False (shapes where _chain_ws_ok fails, e.g. the pod per-TP-rank
    d=1024) runs the per-op saved-pre kernels with the SAME slot-offset /
    direct-dmean specs — the concat/slice glue stays dead — and the
    cross-iteration dw/da accumulation happens here in XLA adds instead of
    in-kernel seeding. Returns the same (accumulated grads, dx, da)."""
    M, d = ext2.shape[1], ext2.shape[2]
    f = params.w1.shape[-1]
    f32 = jnp.float32
    grid = (G, M // tile_m)
    x_spec = pl.BlockSpec((1, tile_m, d), lambda g, m, _o=offset: (g + _o, m, 0))
    row_spec = pl.BlockSpec((1, tile_m, d), lambda g, m: (g, m, 0))
    acc_specs = [
        pl.BlockSpec((1, d, f), lambda g, m: (g, 0, 0)),
        pl.BlockSpec((1, 1, f), lambda g, m: (g, 0, 0)),
        pl.BlockSpec((1, f, d), lambda g, m: (g, 0, 0)),
        pl.BlockSpec((1, 1, d), lambda g, m: (g, 0, 0)),
    ]
    out_shapes = (
        jax.ShapeDtypeStruct((G, M, d), ext2.dtype),  # dx
        jax.ShapeDtypeStruct((G, d, f), f32),
        jax.ShapeDtypeStruct((G, 1, f), f32),
        jax.ShapeDtypeStruct((G, f, d), f32),
        jax.ShapeDtypeStruct((G, 1, d), f32),
    )
    out_specs = (row_spec,) + tuple(acc_specs)
    common = [
        x_spec,  # x (slot-offset)
        pl.BlockSpec((1, d, f), lambda g, m: (g, 0, 0)),  # w1
        pl.BlockSpec((1, tile_m, f), lambda g, m: (g, m, 0)),  # pre
        pl.BlockSpec((1, f, d), lambda g, m: (g, 0, 0)),  # w2
        row_spec,  # g cotangent (dmean slots 0..G-1)
    ]
    compiler_params = (
        _VMEM_64M if chain
        else _bwd_compiler_params(tile_m, d, f, ext2.dtype.itemsize)
    )
    if add is not None:
        n = add.shape[0]
        a_spec = pl.BlockSpec(add.shape, lambda g, m: (0, 0))
        da_spec = pl.BlockSpec((n, d), lambda g, m: (0, 0))
        if chain:
            dx, dw1, db1, dw2, db2, da = pl.pallas_call(
                _ffw_bwd_acc_add_kernel,
                out_shape=out_shapes + (jax.ShapeDtypeStruct((n, d), f32),),
                grid=grid,
                in_specs=[common[0], a_spec] + common[1:] + acc_specs + [da_spec],
                out_specs=out_specs + (da_spec,),
                compiler_params=compiler_params,
                interpret=interpret,
                name="loop_ffw_add_acc_bwd",
            )(ext2, add, params.w1, pre, params.w2, gcot2,
              acc.w1, acc.b1, acc.w2, acc.b2, da_in)
            return GroupedFFWParams(dw1, db1, dw2, db2), dx, da
        dx, dw1, db1, dw2, db2, da = pl.pallas_call(
            _mlp_bwd_kernel_saved_add,
            out_shape=out_shapes + (jax.ShapeDtypeStruct((n, d), f32),),
            grid=grid,
            in_specs=[common[0], a_spec] + common[1:],
            out_specs=out_specs + (da_spec,),
            compiler_params=compiler_params,
            interpret=interpret,
            name="loop_ffw_add_bwd",
        )(ext2, add, params.w1, pre, params.w2, gcot2)
        fresh = GroupedFFWParams(dw1, db1, dw2, db2)
        return (
            jax.tree_util.tree_map(jnp.add, acc, fresh),
            dx,
            da_in + da,
        )
    if chain:
        dx, dw1, db1, dw2, db2 = pl.pallas_call(
            _ffw_bwd_acc_kernel,
            out_shape=out_shapes,
            grid=grid,
            in_specs=common + acc_specs,
            out_specs=out_specs,
            compiler_params=compiler_params,
            interpret=interpret,
            name="loop_ffw_acc_bwd",
        )(ext2, params.w1, pre, params.w2, gcot2,
          acc.w1, acc.b1, acc.w2, acc.b2)
        return GroupedFFWParams(dw1, db1, dw2, db2), dx, None
    dx, dw1, db1, dw2, db2 = pl.pallas_call(
        _mlp_bwd_kernel_saved,
        out_shape=out_shapes,
        grid=grid,
        in_specs=common,
        out_specs=out_specs,
        compiler_params=compiler_params,
        interpret=interpret,
        name="loop_ffw_bwd",
    )(ext2, params.w1, pre, params.w2, gcot2)
    fresh = GroupedFFWParams(dw1, db1, dw2, db2)
    return jax.tree_util.tree_map(jnp.add, acc, fresh), dx, None


def _cons_fwd_ext(
    ext: jnp.ndarray,   # [L+1, B, n, d] slot carry
    bu: jnp.ndarray,    # [L, B, n, d]
    td: jnp.ndarray,    # [L-1, B, n, d]
    *,
    side: int,
    radius: float,
    attend_self: bool,
    interpret: bool,
):
    """Fused consensus+mean update on the slot carry: level g's q/k/v read
    slot g+1, and the output writes slots 1..L of a fresh [L+1] buffer
    (slot 0 is re-pinned to the tokens by the caller's in-place
    dynamic_update_slice — the buffer's only other use). Always emits the
    (m, l) stats — the only caller is the training forward."""
    Lp1, B, n, d = ext.shape
    L = Lp1 - 1
    tile_i = _pick_cons_tile(n)
    tile_j = _pick_cons_tile(n, cap=512 if radius <= 0 else 256)
    tile_b = _pick_cons_tile_b(
        B, n, d, tile_i, tile_j, ext.dtype.itemsize, streamed=False
    )
    kw = dict(
        levels_count=L, side=side, radius=float(radius),
        attend_self=attend_self, tile_i=tile_i, tile_j=tile_j, n=n,
    )

    def lv_spec(last):
        return pl.BlockSpec(
            (1, tile_b, tile_i, last), lambda g, b, i: (g + 1, b, i, 0)
        )

    def g_spec(last):
        return pl.BlockSpec(
            (1, tile_b, tile_i, last), lambda g, b, i: (g, b, i, 0)
        )

    stat_shape = jax.ShapeDtypeStruct((L, B, n, 1), jnp.float32)
    out_shape = (
        jax.ShapeDtypeStruct((Lp1, B, n, d), ext.dtype), stat_shape, stat_shape
    )
    out_spec = (lv_spec(d), g_spec(1), g_spec(1))
    in_specs = [
        lv_spec(d),  # x (self tile): slot g+1
        pl.BlockSpec(
            (1, tile_b, n, d), lambda g, b, i: (g + 1, b, 0, 0)
        ),  # kv rows: slot g+1
        # `0 + g`, not `g`: the add is part of the index map the ledger's
        # runs were lowered from (the deleted one-grid layout kept its bu
        # groups at an offset here); dropping it changes the kernel's bytes.
        pl.BlockSpec(
            (1, tile_b, tile_i, d), lambda g, b, i: (0 + g, b, i, 0)
        ),  # bu
        pl.BlockSpec(
            (1, tile_b, tile_i, d),
            lambda g, b, i, _L=L: (jnp.minimum(g, _L - 2), b, i, 0),
        ),  # td (clamped top, masked in-kernel)
    ]
    return pl.pallas_call(
        partial(_consensus_update_kernel, **kw),
        out_shape=out_shape,
        grid=(L, B // tile_b, n // tile_i),
        in_specs=in_specs,
        out_specs=out_spec,
        interpret=interpret,
        name="loop_consensus_fwd",
    )(ext, ext, bu, td)


def _cons_bwd_combine_kernel(
    x_ref, dg_ref, *refs,
    side, radius, attend_self, n, levels_count,
):
    """Single-tile consensus backward with the three cotangent streams
    combined in-register: the complete output cotangent of level g is

        dg[g] + dx_bu[g+1] (g < L-1)  + dx_td[g-1] (g >= 1)

    (bu input slot g+1 is level g for g <= L-2; td input slot g+2 is level
    g+1) — read via clamped index maps and masked here, so the XLA
    pad+add sweeps between backward kernels disappear. Emits the complete
    consensus dlevels AND dmean (= combined cotangent / div)."""
    dlv_ref, dmean_ref = refs[-2:]
    ins = refs[:-2]
    f32 = jnp.float32
    g_id = pl.program_id(0)
    cot = dg_ref[0].astype(f32)
    if len(ins) == 4:
        dxbu_ref, dxtd_ref, m_ref, l_ref = ins
        cot = cot + jnp.where(
            g_id < levels_count - 1, dxbu_ref[0].astype(f32), 0.0
        )
        cot = cot + jnp.where(g_id >= 1, dxtd_ref[0].astype(f32), 0.0)
    else:
        m_ref, l_ref = ins
    div = jnp.where(g_id == levels_count - 1, 3.0, 4.0)
    dcons = cot / div
    dlv = _small_bwd_math(
        x_ref[0], dcons, m_ref[0], l_ref[0],
        side=side, radius=radius, attend_self=attend_self, n=n,
    )
    dlv_ref[0] = dlv.astype(dlv_ref.dtype)
    dmean_ref[0] = dcons.astype(dmean_ref.dtype)


def _cons_bwd_ext(
    ext: jnp.ndarray,            # [L+1, B, n, d] saved carry
    m: jnp.ndarray,
    l: jnp.ndarray,
    dg: jnp.ndarray,             # [L, B, n, d] consensus-dlv cotangent stream
    dx_bu: jnp.ndarray | None,   # [L, B, n, d] (slot layout) or None
    dx_td: jnp.ndarray | None,   # [L-1, B, n, d] or None
    *,
    side: int,
    radius: float,
    attend_self: bool,
    interpret: bool,
):
    Lp1, B, n, d = ext.shape
    L = Lp1 - 1
    itemsize = ext.dtype.itemsize
    tile_b = _fit_tile_b(
        B,
        lambda tb: 3 * tb * n * n * 4 + 8 * tb * n * d * (itemsize + 1),
    )

    def spec(last, map_fn):
        return pl.BlockSpec((1, tile_b, n, last), map_fn)

    ident = lambda g, b: (g, b, 0, 0)
    in_specs = [spec(d, lambda g, b: (g + 1, b, 0, 0)), spec(d, ident)]
    ins = [ext, dg]
    if dx_bu is not None:
        in_specs += [
            spec(  # `0 +`: as in _cons_fwd_ext, the lowered bytes' own add
                d,
                lambda g, b, _L=L: (0 + jnp.minimum(g + 1, _L - 1), b, 0, 0),
            ),
            spec(d, lambda g, b: (jnp.maximum(g - 1, 0), b, 0, 0)),
        ]
        ins += [dx_bu, dx_td]
    in_specs += [spec(1, ident), spec(1, ident)]
    ins += [m, l]
    dlv, dmean = pl.pallas_call(
        partial(
            _cons_bwd_combine_kernel,
            side=side, radius=float(radius), attend_self=attend_self,
            n=n, levels_count=L,
        ),
        out_shape=(
            jax.ShapeDtypeStruct((L, B, n, d), ext.dtype),
            jax.ShapeDtypeStruct((L, B, n, d), ext.dtype),
        ),
        grid=(L, B // tile_b),
        in_specs=in_specs,
        out_specs=(spec(d, ident), spec(d, ident)),
        compiler_params=_VMEM_32M,
        interpret=interpret,
        name="loop_consensus_bwd",
    )(*ins)
    return dlv, dmean


def loop_supported(
    L: int, B: int, n: int, d: int, f: int, itemsize: int, iters: int,
    pos_n: int, remat: bool = False,
) -> bool:
    """Static eligibility for the hand-rolled loop VJP (the flagship
    training regime); callers fall back to the scan paths otherwise.

    remat=True is the recompute-per-iteration mode (BASELINE config 5's
    "ckpt over iters"): the residual stack drops the [G, M, f]
    pre-activations — the dominant term, (2L-1)·M·f vs (L+1)·M·d, ~6x at
    mult=4 — because the backward re-runs the FIRST FFW matmul per
    iteration (_pre_fwd_ext); only the carries and the tiny consensus
    stats are saved."""
    M = B * n
    tile = _pick_tile(M, d, f, itemsize)
    bt = _pick_bwd_tile(M, d, f, itemsize)
    if iters < 1 or tile is None or bt is None:
        return False
    if d % 128 != 0 or f % 128 != 0 or n % 8 != 0 or L < 2:
        return False
    if n > _SMALL_BWD_N:
        return False
    # pos-emb fold constraints (the td kernels tile the addend per row tile)
    if pos_n != n or M % n or tile % n or bt % n:
        return False
    # the backward must fit EITHER accumulator-chained (the flagship
    # configuration) or unchained (per-op footprint + the resident da —
    # the pod per-TP-rank d=1024 shape; see _chain_ws_ok)
    if not _chain_ws_ok(bt, d, f, itemsize, n) and (
        _bwd_ws(bt, d, f, itemsize) + n * d * 4 > _WS_BUDGET
    ):
        return False
    per_iter = (
        (L + 1) * M * d * itemsize          # saved carry
        + 2 * L * M * 4                     # consensus stats
    )
    if not remat:
        per_iter += (2 * L - 1) * M * f * itemsize  # both FFW pre-activations
    return iters * per_iter <= _RESIDUAL_BUDGET


@partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def fused_glom_loop(
    bu_params: GroupedFFWParams,
    td_params: GroupedFFWParams,
    pos_emb: jnp.ndarray,    # [n, d]
    tokens: jnp.ndarray,     # [B, n, d]
    levels0: jnp.ndarray,    # [L, B, n, d] level-major
    iters: int,
    side: int,
    radius: float,
    attend_self: bool,
    interpret: bool = False,
    remat: bool = False,
):
    """Run `iters` GLOM column updates and return the final level-major
    [L, B, n, d] state.

    PRIMAL path (this body; jax runs it only when NOT differentiating):
    the plain level-major iteration with an [L] carry — the slot machinery
    exists purely for the BACKWARD's benefit, and for pure forwards its
    per-iteration slot-0 re-pin and final [1:] slice measured a ~2%
    forward-bench tax (13.9k vs 14.2k col-iters/s). The [L+1]-slot form
    lives in _loop_fwd, which runs under jax.vjp/grad.

    remat=True (identical math; static) switches the VJP to
    recompute-per-iteration: _loop_fwd saves only (carry, consensus stats)
    and _loop_bwd re-runs the first FFW matmul per iteration — BASELINE
    config 5's checkpoint-over-iters regime without the scan-path glue."""
    L = levels0.shape[0]
    B, n, d = tokens.shape
    M = B * n
    tile_m = _pick_tile(M, d, bu_params.w1.shape[-1], tokens.dtype.itemsize)
    lv = levels0
    tokens_lm = tokens[None]
    for _ in range(iters):
        bu_in = jnp.concatenate([tokens_lm, lv[:-1]], axis=0)
        bu = _fused_forward(
            bu_params, bu_in.reshape(L, M, d), tile_m=tile_m,
            interpret=interpret,
        ).reshape(L, B, n, d)
        td = _fused_forward_add(
            td_params, lv[1:].reshape(L - 1, M, d), pos_emb,
            tile_m=tile_m, interpret=interpret,
        ).reshape(L - 1, B, n, d)
        lv = _cons_forward(
            lv, bu, td,
            side=side, radius=radius, attend_self=attend_self,
            interpret=interpret,
        )
    return lv


def _loop_fwd(
    bu_params, td_params, pos_emb, tokens, levels0,
    iters, side, radius, attend_self, interpret, remat=False,
):
    L = levels0.shape[0]
    B, n, d = tokens.shape
    ext = jnp.concatenate([tokens[None], levels0], axis=0)
    ext2_shape = (L + 1, B * n, d)
    tile_m = _pick_tile(B * n, d, bu_params.w1.shape[-1], tokens.dtype.itemsize)
    saved = []
    for _ in range(iters):
        ext2 = ext.reshape(ext2_shape)
        bu, pre_bu = _ffw_fwd_ext(
            bu_params, ext2, 0, L, tile_m=tile_m, interpret=interpret,
            save_pre=not remat,
        )
        td, pre_td = _ffw_fwd_ext(
            td_params, ext2, 2, L - 1, tile_m=tile_m, interpret=interpret,
            add=pos_emb, save_pre=not remat,
        )
        new_ext, m, l = _cons_fwd_ext(
            ext, bu.reshape(L, B, n, d), td.reshape(L - 1, B, n, d),
            side=side, radius=radius, attend_self=attend_self,
            interpret=interpret,
        )
        # Remat mode saves only the carry + the tiny [L, B, n, 1] stats;
        # the pre-activations (the dominant residual) are recomputed per
        # iteration in _loop_bwd.
        saved.append((ext, m, l) if remat else (ext, pre_bu, pre_td, m, l))
        ext = jax.lax.dynamic_update_slice(new_ext, tokens[None], (0, 0, 0, 0))
    return ext[1:], (bu_params, td_params, pos_emb, tuple(saved))


def _loop_bwd(iters, side, radius, attend_self, interpret, remat, res, g):
    bu_params, td_params, pos_emb, saved = res
    L_, B, n, d = g.shape
    L = L_
    M = B * n
    f32 = jnp.float32
    f_bu = bu_params.w1.shape[-1]
    bt = _pick_bwd_tile(M, d, f_bu, g.dtype.itemsize)

    zeros_acc = lambda p: GroupedFFWParams(
        jnp.zeros(p.w1.shape, f32),
        jnp.zeros((p.b1.shape[0], 1, p.b1.shape[1]), f32),
        jnp.zeros(p.w2.shape, f32),
        jnp.zeros((p.b2.shape[0], 1, p.b2.shape[1]), f32),
    )
    acc_bu = zeros_acc(bu_params)
    acc_td = zeros_acc(td_params)
    da = jnp.zeros((n, d), f32)
    dtok = jnp.zeros((B, n, d), f32)
    dlv = g
    dx_bu = dx_td = None

    tile_fwd = _pick_tile(M, d, f_bu, g.dtype.itemsize)
    chain = _chain_ws_ok(bt, d, f_bu, g.dtype.itemsize, n)

    for t in reversed(range(iters)):
        if remat:
            ext, m, l = saved[t]
            ext2_r = ext.reshape(L + 1, M, d)
            pre_bu = _pre_fwd_ext(
                bu_params, ext2_r, 0, L, tile_m=tile_fwd,
                interpret=interpret,
            )
            pre_td = _pre_fwd_ext(
                td_params, ext2_r, 2, L - 1, tile_m=tile_fwd,
                interpret=interpret, add=pos_emb,
            )
        else:
            ext, pre_bu, pre_td, m, l = saved[t]
        dlv, dmean = _cons_bwd_ext(
            ext, m, l, dlv, dx_bu, dx_td,
            side=side, radius=radius, attend_self=attend_self,
            interpret=interpret,
        )
        ext2 = ext.reshape(L + 1, M, d)
        dmean2 = dmean.reshape(L, M, d)
        acc_td, dx_td2, da = _ffw_bwd_ext(
            td_params, ext2, 2, L - 1, pre_td, dmean2, acc_td,
            tile_m=bt, interpret=interpret, add=pos_emb, da_in=da,
            chain=chain,
        )
        acc_bu, dx_bu2, _ = _ffw_bwd_ext(
            bu_params, ext2, 0, L, pre_bu, dmean2, acc_bu,
            tile_m=bt, interpret=interpret, chain=chain,
        )
        dx_bu = dx_bu2.reshape(L, B, n, d)
        dx_td = dx_td2.reshape(L - 1, B, n, d)
        dtok = dtok + dx_bu[0].astype(f32)

    # Final combine at the loop entry: d(levels0) gathers all three streams.
    # Written as slice-adds + one concatenate (NOT .at[].add, which lowers
    # to a slow TPU scatter-add): XLA fuses the slices into the adds, so
    # each stream is read once and the result written once. The leading
    # f32 cast keeps the 3-term middle sum single-rounded (fused into the
    # adds; the final astype keeps the HBM write in the carry dtype).
    if L > 2:
        dlv0 = jnp.concatenate(
            [
                dlv[:1].astype(f32) + dx_bu[1:2],
                dlv[1 : L - 1].astype(f32) + dx_bu[2:] + dx_td[: L - 2],
                dlv[L - 1 :].astype(f32) + dx_td[L - 2 :],
            ],
            axis=0,
        )
    else:
        dlv0 = jnp.concatenate(
            [dlv[:1].astype(f32) + dx_bu[1:2], dlv[1:].astype(f32) + dx_td],
            axis=0,
        )

    def cast_grads(acc, p):
        return GroupedFFWParams(
            acc.w1.astype(p.w1.dtype),
            acc.b1[:, 0].astype(p.b1.dtype),
            acc.w2.astype(p.w2.dtype),
            acc.b2[:, 0].astype(p.b2.dtype),
        )

    return (
        cast_grads(acc_bu, bu_params),
        cast_grads(acc_td, td_params),
        da.astype(pos_emb.dtype),
        dtok.astype(g.dtype),
        dlv0.astype(g.dtype),
    )


fused_glom_loop.defvjp(_loop_fwd, _loop_bwd)
