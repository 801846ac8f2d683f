"""Pallas TPU kernel: fused per-group MLP (the grouped feed-forward hot op).

The per-iteration cost of the scanned GLOM update is dominated by the two
grouped FFWs (PERF.md, section 5: the FFW kernels' share of a step); XLA materializes
the [.., G, 4d] hidden activations in HBM between the two matmuls. This
kernel computes  out = gelu(x @ w1 + b1) @ w2 + b2  per group with the
hidden tile resident in VMEM — HBM sees only x, the weights, and out.

Grid layout: (G, M_tiles) with the m axis innermost, so each group's weight
pair stays resident in VMEM across all of its row tiles (revisits cost
nothing; the next group triggers one weight DMA).

Backward: custom_vjp over ONE fully-fused Pallas kernel that emits dx and
accumulates all four weight/bias grads in-kernel (f32 accumulators on
constant-index output blocks across the inner m grid axis). On the bf16
training path the forward also saves the pre-activation so the backward
skips its recompute matmul (4 matmuls/tile; f32 keeps the 5-matmul
recompute form — see _fwd for the measured trade. The history: the
plain-XLA backward ran the dw matmuls at 33% MFU off scan-residual
fusions, the two-stage
kernel+einsum design fixed that, and folding dw/db+save-pre in-kernel
removed the [G, M, f] round trips entirely; 1955 -> ~3470
column-iters/s on v5e across those generations).

Falls back to the XLA einsum path (ops/ffw.py) off-TPU, under interpret
testing, and for shapes that don't tile cleanly.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from glom_tpu.ops.ffw import GroupedFFWParams, grouped_ffw, grouped_ffw_lm



def _erf(x):
    """Abramowitz & Stegun 7.1.26 rational approximation (max err 1.5e-7).
    The Pallas TPU lowering has no erf/erfc primitive; this uses only
    mul/add/exp, all VPU-native. 1.5e-7 is far below bf16 resolution and
    inside the f32 test tolerances."""
    sign = jnp.sign(x)
    x = jnp.abs(x)
    t = 1.0 / (1.0 + 0.3275911 * x)
    poly = t * (
        0.254829592
        + t * (-0.284496736 + t * (1.421413741 + t * (-1.453152027 + t * 1.061405429)))
    )
    return sign * (1.0 - poly * jnp.exp(-x * x))


SQRT_2_OVER_PI = 0.7978845608028654
GELU_TANH_C = 0.044715


def _gelu_value_and_grad(z, *, tanh_approx, erf=_erf):
    """GELU value + derivative in f32, the single source of truth for every
    backward path (fused kernel and XLA fallback). tanh_approx selects the
    tanh form (matching the bf16 forward's activation); otherwise the exact
    erf form, with the erf implementation injectable (rational approx inside
    Pallas, jax.lax.erf in XLA). Callers needing only the value rely on DCE
    to drop the derivative."""
    if tanh_approx:
        u = SQRT_2_OVER_PI * (z + GELU_TANH_C * z * z * z)
        t = jnp.tanh(u)
        val = 0.5 * z * (1.0 + t)
        grad = 0.5 * (1.0 + t) + 0.5 * z * (1.0 - t * t) * SQRT_2_OVER_PI * (
            1.0 + 3.0 * GELU_TANH_C * z * z
        )
    else:
        phi = jnp.exp(-0.5 * z * z) * (1.0 / jnp.sqrt(2.0 * jnp.pi))
        Phi = 0.5 * (1.0 + erf(z * 0.7071067811865476))
        val = z * Phi
        grad = Phi + z * phi
    return val, grad


def _gelu_exact(x):
    """Exact (erf-based) GELU, matching jax.nn.gelu(approximate=False)."""
    return _gelu_value_and_grad(x, tanh_approx=False)[0]


def _mlp_kernel(x_ref, w1_ref, b1_ref, w2_ref, b2_ref, out_ref, *pre_ref):
    """One (group, row-tile) program: [TM, d] -> [TM, d] through the f-wide
    hidden layer entirely in VMEM.

    Activation precision: in bfloat16 compute the tanh GELU replaces the
    exact-erf one — their difference (<~1.1e-3 absolute) is below bf16
    resolution at GELU-scale activations, and the erf rational costs ~13%
    of the whole kernel on the VPU (measured 156 -> 179 TF/s). Float32
    compute keeps the exact erf so the f32 path stays bit-comparable to
    the reference contract.

    When a trailing `pre_ref` output is present (the training forward under
    custom_vjp), the pre-activation is also emitted (compute dtype) so the
    backward kernel can skip its recompute matmul — see _fwd for the trade.
    """
    x = x_ref[0]  # [TM, d]
    pre = jnp.dot(x, w1_ref[0], preferred_element_type=jnp.float32)
    pre = pre + b1_ref[0].astype(jnp.float32)  # b1_ref[0]: [1, f], broadcasts
    if pre_ref:
        pre_ref[0][0] = pre.astype(x.dtype)
    if x.dtype == jnp.bfloat16:
        h = jax.nn.gelu(pre, approximate=True)
    else:
        h = _gelu_exact(pre)
    h = h.astype(x.dtype)
    out = jnp.dot(h, w2_ref[0], preferred_element_type=jnp.float32)
    out = out + b2_ref[0].astype(jnp.float32)
    out_ref[0] = out.astype(out_ref.dtype)


def _tiled_add(x, a):
    """x [TM, d] + a [n, d] with TM % n == 0: the positional addend
    repeats every n rows (M = b*n with n inner), so the tile-local add is
    a reshape-broadcast — no materialized [G, M, d] sum ever hits HBM."""
    tm, d = x.shape
    n = a.shape[0]
    return (x.reshape(tm // n, n, d) + a[None]).reshape(tm, d)


def _mlp_kernel_add(x_ref, a_ref, w1_ref, b1_ref, w2_ref, b2_ref, out_ref,
                    *pre_ref):
    """_mlp_kernel with a positional addend folded into the input load:
    pre = (x + a)@w1 + b1. A trailing pre output is present only on the
    training forward (no-grad forwards skip the [G, M, f] HBM write);
    GELU form follows the dtype like _mlp_kernel."""
    xa = _tiled_add(x_ref[0], a_ref[...]).astype(x_ref.dtype)
    pre = jnp.dot(xa, w1_ref[0], preferred_element_type=jnp.float32)
    pre = pre + b1_ref[0].astype(jnp.float32)
    if pre_ref:
        pre_ref[0][0] = pre.astype(xa.dtype)
    if xa.dtype == jnp.bfloat16:
        h = jax.nn.gelu(pre, approximate=True)
    else:
        h = _gelu_exact(pre)
    h = h.astype(xa.dtype)
    out = jnp.dot(h, w2_ref[0], preferred_element_type=jnp.float32)
    out = out + b2_ref[0].astype(jnp.float32)
    out_ref[0] = out.astype(out_ref.dtype)


def _fused_forward(
    params: GroupedFFWParams,
    x: jnp.ndarray,
    *,
    tile_m: int,
    interpret: bool,
    save_pre: bool = False,
):
    """x: [G, M, d] -> [G, M, d] (group-major so every block keeps the
    tile-aligned [TM, d] trailing dims the TPU lowering requires).
    save_pre=True additionally returns the [G, M, f] pre-activation
    (compute dtype) for the backward."""
    G, M, d = x.shape
    f = params.w1.shape[-1]
    # m innermost: each group's weight pair stays VMEM-resident across all
    # of its row tiles.
    grid = (G, M // tile_m)
    out_shape = jax.ShapeDtypeStruct((G, M, d), x.dtype)
    out_spec = pl.BlockSpec((1, tile_m, d), lambda g, m: (g, m, 0))
    if save_pre:
        out_shape = (out_shape, jax.ShapeDtypeStruct((G, M, f), x.dtype))
        out_spec = (out_spec, pl.BlockSpec((1, tile_m, f), lambda g, m: (g, m, 0)))
    return pl.pallas_call(
        _mlp_kernel,
        out_shape=out_shape,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tile_m, d), lambda g, m: (g, m, 0)),  # x
            pl.BlockSpec((1, d, f), lambda g, m: (g, 0, 0)),  # w1
            # biases as [G, 1, f]: block dims equal to array dims satisfy the
            # TPU (8, 128)-tiling rule without padding
            pl.BlockSpec((1, 1, f), lambda g, m: (g, 0, 0)),  # b1
            pl.BlockSpec((1, f, d), lambda g, m: (g, 0, 0)),  # w2
            pl.BlockSpec((1, 1, d), lambda g, m: (g, 0, 0)),  # b2
        ],
        out_specs=out_spec,
        # The save_pre variant (training fwd) carries the extra [TM, f]
        # output block, and d>=1024 shapes carry 16MB+ of resident weights
        # — both overflow Mosaic's default 16MB scope (the d=1024/f=4096
        # pod shape needs 44MB); v5e has 128MB physical. Smaller inference
        # shapes keep the default budget (the measured-fast configuration).
        compiler_params=(
            pltpu.CompilerParams(vmem_limit_bytes=64 * 1024 * 1024)
            if save_pre or _fwd_ws(tile_m, d, f, x.dtype.itemsize) > 14 * 1024 * 1024
            else None
        ),
        interpret=interpret,
        name="ffw_fwd",
    )(x, params.w1, params.b1[:, None, :], params.w2, params.b2[:, None, :])


def _fused_forward_add(
    params: GroupedFFWParams,
    x: jnp.ndarray,
    a: jnp.ndarray,
    *,
    tile_m: int,
    interpret: bool,
    save_pre: bool = False,
):
    """Forward with the positional addend folded in-kernel. x [G, M, d],
    a [n, d] with tile_m % n == 0; save_pre only on the training path
    (a no-grad forward must not write the [G, M, f] pre to HBM)."""
    G, M, d = x.shape
    f = params.w1.shape[-1]
    grid = (G, M // tile_m)
    out_shape = jax.ShapeDtypeStruct((G, M, d), x.dtype)
    out_spec = pl.BlockSpec((1, tile_m, d), lambda g, m: (g, m, 0))
    if save_pre:
        out_shape = (out_shape, jax.ShapeDtypeStruct((G, M, f), x.dtype))
        out_spec = (out_spec, pl.BlockSpec((1, tile_m, f), lambda g, m: (g, m, 0)))
    return pl.pallas_call(
        _mlp_kernel_add,
        out_shape=out_shape,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tile_m, d), lambda g, m: (g, m, 0)),  # x
            pl.BlockSpec(a.shape, lambda g, m: (0, 0)),  # add (resident)
            pl.BlockSpec((1, d, f), lambda g, m: (g, 0, 0)),  # w1
            pl.BlockSpec((1, 1, f), lambda g, m: (g, 0, 0)),  # b1
            pl.BlockSpec((1, f, d), lambda g, m: (g, 0, 0)),  # w2
            pl.BlockSpec((1, 1, d), lambda g, m: (g, 0, 0)),  # b2
        ],
        out_specs=out_spec,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="ffw_add_fwd",
    )(x, a, params.w1, params.b1[:, None, :], params.w2, params.b2[:, None, :])


# Forward row tiles. 1024 overflowed the default scope in-scan when this was
# tuned and 512 remains the measured sweet spot; the save_pre variant raises
# vmem_limit_bytes for its extra output block, not to admit bigger tiles.
TILE_CANDIDATES = (512, 256, 128)

# Working-set budget per kernel program, under the 64MB scoped-vmem caps
# (v5e: 128MB physical, and the whole PROGRAM must co-schedule buffers,
# register-spill slots, and remat recompute — measured 131-144M > 128M at
# d=1024/f=4096 where the backward's resident f32 dw accumulators alone
# are 32M+32M). 48M sends that shape to the XLA backward while keeping
# the kernel at the flagship (24M @ tile 512) and at the pod's declared
# per-TP-rank f/mp=2048 (40M @ tile 512).
_WS_BUDGET = 48 * 1024 * 1024


def _fwd_ws(tile: int, d: int, f: int, itemsize: int) -> int:
    """Forward working set: resident weight pair + f32 pre scratch +
    2x-buffered x/out (+pre out on the save_pre path, counted always —
    it is the training configuration)."""
    weights = 2 * d * f * itemsize
    pre_scratch = tile * f * 4
    blocks = tile * d * itemsize * 2 * 2 + tile * f * itemsize * 2
    return weights + pre_scratch + blocks


def _bwd_ws(tile: int, d: int, f: int, itemsize: int) -> int:
    """Backward working set: weights + f32 dw accumulators (resident
    across the m axis) + f32 dpre + 2x-buffered x/g/pre-in/dx blocks."""
    weights = 2 * d * f * itemsize
    accums = 2 * d * f * 4 + (d + f) * 4
    dpre = tile * f * 4
    blocks = tile * (2 * d * itemsize * 2 + f * itemsize * 2 + d * itemsize * 2)
    return weights + accums + dpre + blocks


def _pick_tile(M: int, d: int = 512, f: int = 2048, itemsize: int = 2) -> int | None:
    """Largest MXU-friendly row tile dividing M whose forward working set
    fits the budget (None -> no clean tiling)."""
    for t in TILE_CANDIDATES:
        if M % t == 0 and _fwd_ws(t, d, f, itemsize) <= _WS_BUDGET:
            return t
    return None


def _supported(params: GroupedFFWParams, x: jnp.ndarray, tile_m: int | None) -> bool:
    if x.ndim < 3 or tile_m is None:
        return False
    f = params.w1.shape[-1]
    d = x.shape[-1]
    # Clean MXU tiling: row tiles divide M (via _pick_tile); d/f on 128-lane
    # boundaries.
    return d % 128 == 0 and f % 128 == 0


def _mlp_bwd_kernel(
    x_ref,      # [1, TM, d]
    w1_ref,     # [1, d, f]
    b1_ref,     # [1, 1, f]
    w2_ref,     # [1, f, d]
    g_ref,      # [1, TM, d]   upstream cotangent
    dx_ref,     # [1, TM, d]
    dw1_ref,    # [1, d, f]    f32 accumulator (index constant across m)
    db1_ref,    # [1, 1, f]    f32 accumulator
    dw2_ref,    # [1, f, d]    f32 accumulator
    db2_ref,    # [1, 1, d]    f32 accumulator
):
    """One (group, row-tile) program of the FULLY-fused backward: recompute
    the pre-activation in VMEM, apply the GELU derivative, emit dx, and
    accumulate ALL FOUR weight/bias grads in-kernel. The m axis is the
    inner grid dimension, so the f32 dw/db output blocks keep a constant
    block index across a group's row tiles — they live in VMEM as
    accumulators (single-buffered; ~8MB at d=512/f=2048) and flush to HBM
    once per group. Compared to the earlier two-stage design (kernel emits
    dpre/h, XLA einsums contract them), the [G, M, f] dpre/h tensors never
    touch HBM at all and the separate db reduction sweeps disappear —
    measured ~8% step-time win at the flagship config.

    The per-tile dw matmuls contract the TM row axis on the MXU (tile
    picked from BWD_TILE_CANDIDATES; 512 measured best — see the comment
    there); operands are downcast to the compute dtype exactly as the XLA
    einsum path's operands were, so the math is unchanged.

    GELU derivative matches the forward's per-dtype choice: tanh-GELU in
    bfloat16 (the fwd kernel's bf16 activation), exact erf in float32.
    """
    pre = jnp.dot(
        x_ref[0], w1_ref[0], preferred_element_type=jnp.float32
    ) + b1_ref[0].astype(jnp.float32)
    _mlp_bwd_tail(
        pre, x_ref[0], g_ref[0], w1_ref[0], w2_ref[0],
        dx_ref, dw1_ref, db1_ref, dw2_ref, db2_ref,
    )


def _mlp_bwd_tail(pre, x, g, w1, w2, dx_ref, dw1_ref, db1_ref, dw2_ref, db2_ref,
                  inc=None):
    """Shared tail of both backward kernels (recompute and saved-pre): the
    dh/dx matmuls, the in-kernel dw/db accumulation, and the init/accum
    revisit logic. `pre` is f32 however the caller obtained it.

    inc: optional (dw1_in, db1_in, dw2_in, db2_in) refs of INCOMING f32
    accumulators (same block indices as the outputs) — the cross-iteration
    accumulation the hand-rolled loop VJP (kernels/fused_loop.py) chains
    through the backward instead of XLA add_any sweeps: the init-at-m==0
    branch seeds from the incoming value rather than zero."""
    f32 = jnp.float32
    m = pl.program_id(1)

    h32, dact = _gelu_value_and_grad(pre, tanh_approx=x.dtype == jnp.bfloat16)
    h = h32.astype(x.dtype)

    # dh = g @ w2^T  (contract the d axis of both)
    dh = jax.lax.dot_general(g, w2, (((1,), (1,)), ((), ())), preferred_element_type=f32)
    dpre = (dh * dact).astype(x.dtype)

    # dx = dpre @ w1^T (contract f)
    dx = jax.lax.dot_general(dpre, w1, (((1,), (1,)), ((), ())), preferred_element_type=f32)
    dx_ref[0] = dx.astype(dx_ref.dtype)
    dx32 = dx  # returned for the add-variant's da accumulation

    # Weight/bias grad contributions of this row tile (contract TM).
    dw1_step = jax.lax.dot_general(
        x, dpre, (((0,), (0,)), ((), ())), preferred_element_type=f32
    )  # [d, f]
    dw2_step = jax.lax.dot_general(
        h, g, (((0,), (0,)), ((), ())), preferred_element_type=f32
    )  # [f, d]
    db1_step = jnp.sum(dpre.astype(f32), axis=0, keepdims=True)  # [1, f]
    db2_step = jnp.sum(g.astype(f32), axis=0, keepdims=True)  # [1, d]

    @pl.when(m == 0)
    def _init():
        if inc is None:
            dw1_ref[0] = dw1_step
            db1_ref[0] = db1_step
            dw2_ref[0] = dw2_step
            db2_ref[0] = db2_step
        else:
            dw1_ref[0] = inc[0][0] + dw1_step
            db1_ref[0] = inc[1][0] + db1_step
            dw2_ref[0] = inc[2][0] + dw2_step
            db2_ref[0] = inc[3][0] + db2_step

    @pl.when(m != 0)
    def _accum():
        dw1_ref[0] += dw1_step
        db1_ref[0] += db1_step
        dw2_ref[0] += dw2_step
        db2_ref[0] += db2_step

    return dx32


def _mlp_bwd_kernel_saved(
    x_ref,      # [1, TM, d]
    w1_ref,     # [1, d, f]
    pre_ref,    # [1, TM, f]   pre-activation SAVED by the forward (compute
                #              dtype) — replaces the recompute matmul
    w2_ref,     # [1, f, d]
    g_ref,      # [1, TM, d]
    dx_ref,     # [1, TM, d]
    dw1_ref,    # [1, d, f]    f32 accumulators, as in _mlp_bwd_kernel
    db1_ref,    # [1, 1, f]
    dw2_ref,    # [1, f, d]
    db2_ref,    # [1, 1, d]
):
    """_mlp_bwd_kernel minus the pre-activation recompute: 4 matmuls per
    tile instead of 5. Used on the bf16 path where the forward saved pre
    (see _fwd for the measured trade); the GELU value/derivative are
    re-derived from the SAVED (rounded-to-bf16) pre, which differs from
    the recompute path by at most one bf16 ulp of pre — inside the bf16
    training tolerance."""
    _mlp_bwd_tail(
        pre_ref[0].astype(jnp.float32), x_ref[0], g_ref[0], w1_ref[0], w2_ref[0],
        dx_ref, dw1_ref, db1_ref, dw2_ref, db2_ref,
    )


def _mlp_bwd_kernel_saved_add(
    x_ref,      # [1, TM, d]   RAW x (addend NOT applied)
    a_ref,      # [n, d]       positional addend (resident)
    w1_ref,     # [1, d, f]
    pre_ref,    # [1, TM, f]   saved pre (already includes the addend)
    w2_ref,     # [1, f, d]
    g_ref,      # [1, TM, d]
    dx_ref,     # [1, TM, d]
    dw1_ref,    # [1, d, f]    f32 accumulators (constant index across m)
    db1_ref,    # [1, 1, f]
    dw2_ref,    # [1, f, d]
    db2_ref,    # [1, 1, d]
    da_ref,     # [n, d]       f32 accumulator, constant index across the
                #              WHOLE grid: da = sum over groups, batch
                #              copies, and tiles of dx
):
    """_mlp_bwd_kernel_saved for the folded positional addend: the dw1
    contraction uses xa = x + tile(a) (the true layer input), dx is the
    cotangent of BOTH x and (reduced) a — the da reduction rides the
    kernel instead of a separate XLA sweep."""
    xa = _tiled_add(x_ref[0], a_ref[...]).astype(x_ref.dtype)
    dx32 = _mlp_bwd_tail(
        pre_ref[0].astype(jnp.float32), xa, g_ref[0], w1_ref[0], w2_ref[0],
        dx_ref, dw1_ref, db1_ref, dw2_ref, db2_ref,
    )
    tm, d = dx32.shape
    n = a_ref.shape[0]
    da_step = jnp.sum(dx32.reshape(tm // n, n, d), axis=0)
    first = (pl.program_id(0) == 0) & (pl.program_id(1) == 0)

    @pl.when(first)
    def _init_da():
        da_ref[...] = da_step

    @pl.when(jnp.logical_not(first))
    def _accum_da():
        da_ref[...] += da_step


# Larger row tiles give the in-kernel dw matmuls a longer contraction axis;
# the raised vmem_limit_bytes scope makes them fit.
# 512 measured best on v5e at the flagship config (3227 col-iters/s vs 2907
# at 128 and 2975 at 1024 — long enough dw contraction without starving the
# pipeline); 1024 regresses despite fitting the raised budget.
BWD_TILE_CANDIDATES = (512, 256, 128)


def _pick_bwd_tile(
    M: int, d: int = 512, f: int = 2048, itemsize: int = 2
) -> int | None:
    for t in BWD_TILE_CANDIDATES:
        if M % t == 0 and _bwd_ws(t, d, f, itemsize) <= _WS_BUDGET:
            return t
    return None



def _bwd_compiler_params(tile_m: int, d: int, f: int, itemsize: int):
    """Scoped-VMEM grant for the backward kernels, shared by the plain and
    add-fold variants: the d=512-class resident set lands ~0.5MB over
    Mosaic's default 16MB scope; d=1024-class shapes (the pod's per-TP-rank
    f=2048) measure 75-78M of Mosaic stack at tile 512, so shapes past the
    32MB model estimate get the 100MB grant (v5e: 128MB physical)."""
    big = _bwd_ws(tile_m, d, f, itemsize) > 32 * 1024 * 1024
    return pltpu.CompilerParams(
        vmem_limit_bytes=(100 if big else 64) * 1024 * 1024
    )


def _fused_backward(params, x, g, *, tile_m: int, interpret: bool, pre=None):
    G, M, d = x.shape
    f = params.w1.shape[-1]
    f32 = jnp.float32
    grid = (G, M // tile_m)
    out_shapes = (
        jax.ShapeDtypeStruct((G, M, d), x.dtype),  # dx
        jax.ShapeDtypeStruct((G, d, f), f32),  # dw1
        jax.ShapeDtypeStruct((G, 1, f), f32),  # db1
        jax.ShapeDtypeStruct((G, f, d), f32),  # dw2
        jax.ShapeDtypeStruct((G, 1, d), f32),  # db2
    )
    if pre is not None:
        kernel = _mlp_bwd_kernel_saved
        second_in = pre
        second_spec = pl.BlockSpec((1, tile_m, f), lambda gi, m: (gi, m, 0))
    else:
        kernel = _mlp_bwd_kernel
        second_in = params.b1[:, None, :]
        second_spec = pl.BlockSpec((1, 1, f), lambda gi, m: (gi, 0, 0))
    dx, dw1, db1, dw2, db2 = pl.pallas_call(
        kernel,
        out_shape=out_shapes,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tile_m, d), lambda gi, m: (gi, m, 0)),  # x
            pl.BlockSpec((1, d, f), lambda gi, m: (gi, 0, 0)),  # w1
            second_spec,  # b1 (recompute) or saved pre
            pl.BlockSpec((1, f, d), lambda gi, m: (gi, 0, 0)),  # w2
            pl.BlockSpec((1, tile_m, d), lambda gi, m: (gi, m, 0)),  # g
        ],
        out_specs=(
            pl.BlockSpec((1, tile_m, d), lambda gi, m: (gi, m, 0)),  # dx
            pl.BlockSpec((1, d, f), lambda gi, m: (gi, 0, 0)),  # dw1
            pl.BlockSpec((1, 1, f), lambda gi, m: (gi, 0, 0)),  # db1
            pl.BlockSpec((1, f, d), lambda gi, m: (gi, 0, 0)),  # dw2
            pl.BlockSpec((1, 1, d), lambda gi, m: (gi, 0, 0)),  # db2
        ),
        compiler_params=_bwd_compiler_params(tile_m, d, f, x.dtype.itemsize),
        interpret=interpret,
        name="ffw_bwd",
    )(x, params.w1, second_in, params.w2, g)

    w1, b1, w2, b2 = params
    grads = GroupedFFWParams(
        dw1.astype(w1.dtype),
        db1[:, 0].astype(b1.dtype),
        dw2.astype(w2.dtype),
        db2[:, 0].astype(b2.dtype),
    )
    return grads, dx


def _fused_backward_add(params, x, a, pre, g, *, tile_m: int, interpret: bool):
    """_fused_backward for the folded-addend path (saved-pre form only):
    additionally emits da [n, d] accumulated in-kernel across the whole
    grid."""
    G, M, d = x.shape
    f = params.w1.shape[-1]
    f32 = jnp.float32
    n = a.shape[0]
    dx, dw1, db1, dw2, db2, da = pl.pallas_call(
        _mlp_bwd_kernel_saved_add,
        out_shape=(
            jax.ShapeDtypeStruct((G, M, d), x.dtype),  # dx
            jax.ShapeDtypeStruct((G, d, f), f32),  # dw1
            jax.ShapeDtypeStruct((G, 1, f), f32),  # db1
            jax.ShapeDtypeStruct((G, f, d), f32),  # dw2
            jax.ShapeDtypeStruct((G, 1, d), f32),  # db2
            jax.ShapeDtypeStruct((n, d), f32),  # da
        ),
        grid=(G, M // tile_m),
        in_specs=[
            pl.BlockSpec((1, tile_m, d), lambda gi, m: (gi, m, 0)),  # x
            pl.BlockSpec((n, d), lambda gi, m: (0, 0)),  # a (resident)
            pl.BlockSpec((1, d, f), lambda gi, m: (gi, 0, 0)),  # w1
            pl.BlockSpec((1, tile_m, f), lambda gi, m: (gi, m, 0)),  # pre
            pl.BlockSpec((1, f, d), lambda gi, m: (gi, 0, 0)),  # w2
            pl.BlockSpec((1, tile_m, d), lambda gi, m: (gi, m, 0)),  # g
        ],
        out_specs=(
            pl.BlockSpec((1, tile_m, d), lambda gi, m: (gi, m, 0)),  # dx
            pl.BlockSpec((1, d, f), lambda gi, m: (gi, 0, 0)),  # dw1
            pl.BlockSpec((1, 1, f), lambda gi, m: (gi, 0, 0)),  # db1
            pl.BlockSpec((1, f, d), lambda gi, m: (gi, 0, 0)),  # dw2
            pl.BlockSpec((1, 1, d), lambda gi, m: (gi, 0, 0)),  # db2
            pl.BlockSpec((n, d), lambda gi, m: (0, 0)),  # da (whole-grid acc)
        ),
        compiler_params=_bwd_compiler_params(tile_m, d, f, x.dtype.itemsize),
        interpret=interpret,
        name="ffw_add_bwd",
    )(x, a, params.w1, pre, params.w2, g)

    w1, b1, w2, b2 = params
    grads = GroupedFFWParams(
        dw1.astype(w1.dtype),
        db1[:, 0].astype(b1.dtype),
        dw2.astype(w2.dtype),
        db2[:, 0].astype(b2.dtype),
    )
    return grads, dx, da.astype(a.dtype)


def _weight_grads(params, x, dpre, h, g):
    """The four weight/bias grads shared by both backward paths: batched
    matmuls with f32 accumulation, results cast back to the param dtypes."""
    w1, b1, w2, b2 = params
    f32 = jnp.float32
    dw1 = jnp.einsum("gmd,gmf->gdf", x, dpre, preferred_element_type=f32)
    db1 = jnp.sum(dpre.astype(f32), axis=1)
    dw2 = jnp.einsum("gmf,gmd->gfd", h, g, preferred_element_type=f32)
    db2 = jnp.sum(g.astype(f32), axis=1)
    return GroupedFFWParams(
        dw1.astype(w1.dtype),
        db1.astype(b1.dtype),
        dw2.astype(w2.dtype),
        db2.astype(b2.dtype),
    )


def _xla_backward(params, x, g):
    """XLA fallback backward for shapes the bwd kernel can't tile. Still the
    VJP of the PALLAS forward, so the GELU derivative follows the same
    per-dtype choice as the fwd kernel (tanh in bf16, exact erf in f32)."""
    w1, b1, w2, b2 = params
    f32 = jnp.float32
    # Recompute the hidden pre-activation (one extra matmul) rather than
    # saving the [G, M, f] tensor — same memory/recompute trade as flash
    # attention's backward. EVERY contraction and reduction below pins
    # float32 accumulation (preferred_element_type / f32 dpre), matching the
    # forward paths' invariant — bf16 accumulation over f=4d or M=b*n terms
    # loses digits.
    pre = jnp.einsum("gmd,gdf->gmf", x, w1, preferred_element_type=f32)
    pre = pre + b1.astype(f32)[:, None, :]
    h32, dact = _gelu_value_and_grad(
        pre, tanh_approx=x.dtype == jnp.bfloat16, erf=jax.lax.erf
    )
    h = h32.astype(x.dtype)

    dh = jnp.einsum("gmd,gfd->gmf", g, w2, preferred_element_type=f32)
    dpre = (dh * dact).astype(x.dtype)

    dx = jnp.einsum("gmf,gdf->gmd", dpre, w1, preferred_element_type=f32)
    return _weight_grads(params, x, dpre, h, g), dx.astype(x.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _fused_lm(params, x, tile_m, interpret):
    """Level-major core: x [G, M, d] -> [G, M, d]. The layout the kernel
    wants natively — callers that keep a level-major carry pay zero
    transposes."""
    return _fused_forward(params, x, tile_m=tile_m, interpret=interpret)


# Per-call cap on the saved [G, M, f] pre-activation residual. Under a
# NON-remat scan the residual is stacked once per iteration, so an
# unconditional save at larger-than-flagship configs (d=1024-class) risks
# HBM exhaustion where the recompute form previously fit; the flagship
# bf16 config (~400MB/FFW call) stays under and keeps its measured win.
# Remat configs never stack (the body recomputes), so they are safe either
# way.
_SAVE_PRE_LIMIT = 512 * 1024 * 1024


def _save_pre_ok(params: GroupedFFWParams, x: jnp.ndarray) -> bool:
    """Single source of the save-pre eligibility (bf16, bwd-tileable,
    residual under the memory cap) — shared by the plain training forward
    and the folded-addend gate so the invariant cannot drift."""
    f = params.w1.shape[-1]
    save_bytes = x.shape[0] * x.shape[1] * f * x.dtype.itemsize
    return (
        x.dtype == jnp.bfloat16
        and _pick_bwd_tile(x.shape[1], x.shape[2], f, x.dtype.itemsize)
        is not None
        and save_bytes <= _SAVE_PRE_LIMIT
    )


def _fwd(params, x, tile_m, interpret):
    # bf16 training: ALSO save the pre-activation so the backward kernel
    # drops its recompute matmul (5 -> 4 per tile). The [G, M, f] bf16
    # round trip (~1.7 ms/step at the flagship config) costs less than the
    # ~3.5 ms of MXU recompute it replaces — the opposite verdict from the
    # PRE-merged-kernel measurement, because
    # back then the backward also emitted dpre/h and the extra output
    # overflowed VMEM at useful tiles. f32 keeps the recompute (saving f32
    # pre doubles the traffic and f32 runs are parity/testing paths).
    # Gated on _save_pre_ok so large non-remat configs keep recompute.
    if _save_pre_ok(params, x):
        out, pre = _fused_forward(
            params, x, tile_m=tile_m, interpret=interpret, save_pre=True
        )
        return out, (params, x, pre)
    return _fused_lm(params, x, tile_m, interpret), (params, x, None)


def _bwd(tile_m, interpret, res, g):
    params, x, pre = res  # x: [G, M, d]
    bt = _pick_bwd_tile(x.shape[1], x.shape[2], params.w1.shape[-1], x.dtype.itemsize)
    if bt is not None:
        return _fused_backward(params, x, g, tile_m=bt, interpret=interpret, pre=pre)
    # Inside a scan's backward, x arrives as a dynamic-slice of the stacked
    # residuals and the dw outputs feed the gradient-accumulation add; XLA
    # fuses both INTO the dw matmuls (select_add / slice fusions), dropping
    # them to ~33% MFU (profiled on v5e: 64 GF/s vs ~180 clean). The
    # barrier forces clean materialized operands so the einsums run as
    # plain matmuls at MXU rate.
    params, x, g = jax.lax.optimization_barrier((params, x, g))
    return _xla_backward(params, x, g)


_fused_lm.defvjp(_fwd, _bwd)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fused_lm_add(params, x, a, tile_m, interpret):
    """Level-major core with a folded positional addend: equals
    _fused_lm(params, x + tile(a)) but the [G, M, d] sum never exists —
    the kernels add the [n, d] addend on tile load (forward AND backward),
    and da is reduced in-kernel. The primal (no-grad forward) skips the
    pre write; the training forward saves it (callers gate eligibility
    via _save_pre_ok)."""
    return _fused_forward_add(params, x, a, tile_m=tile_m, interpret=interpret)


def _fwd_add(params, x, a, tile_m, interpret):
    out, pre = _fused_forward_add(
        params, x, a, tile_m=tile_m, interpret=interpret, save_pre=True
    )
    return out, (params, x, a, pre)


def _bwd_add(tile_m, interpret, res, g):
    params, x, a, pre = res
    bt = _pick_bwd_tile(x.shape[1], x.shape[2], params.w1.shape[-1], x.dtype.itemsize)
    if bt is not None and bt % a.shape[0] == 0:
        return _fused_backward_add(
            params, x, a, pre, g, tile_m=bt, interpret=interpret
        )
    # Fallback (shouldn't trigger given the caller gate, but stays exact):
    # recompute xa in XLA and reduce da there.
    G, M, d = x.shape
    reps = M // a.shape[0]
    xa = x + jnp.tile(a, (reps, 1))[None]
    params_b, xa_b, g_b = jax.lax.optimization_barrier((params, xa, g))
    grads, dxa = _xla_backward(params_b, xa_b, g_b)
    da = jnp.sum(
        dxa.astype(jnp.float32).reshape(G, reps, a.shape[0], d), axis=(0, 1)
    )
    return grads, dxa, da.astype(a.dtype)


_fused_lm_add.defvjp(_fwd_add, _bwd_add)


_xla_lm = grouped_ffw_lm  # XLA fallback in level-major layout


def fused_grouped_ffw_lm(
    params: GroupedFFWParams,
    x: jnp.ndarray,
    *,
    add: jnp.ndarray | None = None,
    tile_m: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Level-major entry: x [G, M, d] -> [G, M, d] through the Pallas kernel
    (XLA einsum fallback off-TPU / unsupported shapes).

    add: optional [n, d] positional addend with M = b*n (n inner): the
    result equals fused_grouped_ffw_lm(params, x + tile(add)) but on the
    bf16 training path the add folds into the kernels' tile loads and the
    [G, M, d] sum never touches HBM (~2 ms/step at the flagship config).
    Unsupported shapes/dtypes fall back to the explicit add."""
    G, M, d = x.shape
    if tile_m is None:
        tile_m = _pick_tile(M, d, params.w1.shape[-1], x.dtype.itemsize)
    elif M % tile_m != 0:
        tile_m = None
    on_tpu = jax.devices()[0].platform == "tpu"
    kernel_ok = _supported(params, x, tile_m) and (on_tpu or interpret)
    if add is not None:
        n = add.shape[0]
        f = params.w1.shape[-1]
        bt = (
            _pick_bwd_tile(M, d, f, x.dtype.itemsize) if kernel_ok else None
        )
        # The add-backward keeps two extra residents the generic _bwd_ws
        # model doesn't count: the [n, d] addend block and the whole-grid
        # f32 da accumulator.
        add_extra = n * d * (x.dtype.itemsize + 4)
        fold = (
            kernel_ok
            # bf16 is the production fold; f32 folds only under interpret
            # (CI coverage of the add kernels — f32 save-pre stays off the
            # hardware path, same verdict as the plain save-pre gate).
            and (_save_pre_ok(params, x) or (interpret and x.dtype == jnp.float32))
            # No dtype-promotion surprise: the fold computes in x.dtype,
            # so only take it when the explicit x + add would too.
            and jnp.result_type(x.dtype, add.dtype) == x.dtype
            and M % n == 0
            and tile_m % n == 0
            and bt is not None
            and bt % n == 0
            and _bwd_ws(bt, d, f, x.dtype.itemsize) + add_extra <= _WS_BUDGET
        )
        if fold:
            return _fused_lm_add(params, x, add.astype(x.dtype), tile_m, interpret)
        # Fallback preserves jnp promotion semantics (e.g. f32 pos_emb +
        # bf16 carry promotes to f32, exactly like the explicit add did).
        x = x + jnp.tile(add, (M // n, 1))[None]
    if not kernel_ok:
        return _xla_lm(params, x)
    return _fused_lm(params, x, tile_m, interpret)


def fused_grouped_ffw(
    params: GroupedFFWParams,
    x: jnp.ndarray,
    *,
    tile_m: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Drop-in replacement for ops.ffw.grouped_ffw ([..., G, d] -> [..., G, d]).

    Uses the Pallas kernel on TPU (or anywhere under interpret=True); falls
    back to the XLA einsum path otherwise. tile_m=None picks the largest
    clean row tile automatically (e.g. 256 at batch=1/n=256), capped at
    512 by VMEM (TILE_CANDIDATES). Transposes to/from level-major around
    the kernel; hot
    loops should prefer fused_grouped_ffw_lm and keep the carry level-major.
    """
    M = 1
    for s in x.shape[:-2]:
        M *= s
    if tile_m is None:
        tile_m = _pick_tile(M, x.shape[-1], params.w1.shape[-1], x.dtype.itemsize)
    elif M % tile_m != 0:
        tile_m = None
    on_tpu = jax.devices()[0].platform == "tpu"
    if not _supported(params, x, tile_m) or not (on_tpu or interpret):
        return grouped_ffw(params, x)
    *lead, G, d = x.shape
    x2 = jnp.moveaxis(x.reshape(-1, G, d), 1, 0)  # [G, M, d]
    out = _fused_lm(params, x2, tile_m, interpret)
    return jnp.moveaxis(out, 0, 1).reshape(*lead, G, d)
