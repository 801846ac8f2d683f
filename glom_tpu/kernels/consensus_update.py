"""Pallas TPU kernel: blockwise consensus attention fused with the 4-way
mean column update.

Reference parity: ConsensusAttention.forward + the update mean
(glom_pytorch/glom_pytorch.py:54-71 and :124-140). One kernel program
computes, for one (level g, image b, row-tile i):

    cons = softmax_j( q_i . normalize(k)_j * d^-1/2  [dual masks] ) @ v
    out  = (levels_i + bottom_up_i + top_down_i + cons) / div_g

with a flash-style ONLINE softmax over j-tiles — the [n, n] similarity is
never materialized (O(n) memory in the patch axis), which is the
long-context path SURVEY.md §2.2 calls for. Both reference mask semantics
live in the inner loop:

  * attend_self=False: the DIAGONAL similarity is REPLACED by the soft
    -5e-4 penalty (reference TOKEN_ATTEND_SELF_VALUE, :9/:61-63);
  * local radius > 0: pairs farther than `radius` in Euclidean patch-grid
    distance are hard-masked to -3e38 (reference cdist buffer, :42-52).
    The mask is computed in-register from iota (no [n, n] HBM buffer at
    all — the reference's O(n^2) init-time cost disappears), and j-tiles
    that are ENTIRELY outside the radius band are skipped (block
    sparsity): rows i and j can only interact if their grid rows differ
    by <= radius, so the live j-window per i-tile is static arithmetic.

The epilogue folds in the per-level mean (4 contributions, 3 at the top
level — reference :121-122) and the zero top-down of the top level
(reference :130 F.pad) by masking the g = L-1 top-down tile, so XLA's
separate pad + add + divide HBM sweeps disappear.

Layout: level-major [L, B, n, d] ("lm") — the batched-matmul-natural
layout; glom_tpu.models.core keeps the scan carry in this layout so no
transposes appear between kernels.

Backward: custom_vjp over two more Pallas kernels (flash-attention-style).
The training forward additionally saves the per-row softmax statistics
(m, l) — two [L, B, n, 1] f32 outputs, the flash-attention logsumexp
residual trade — so NEITHER backward kernel re-derives them online:
p_ij = exp(s_ij - m_i) / l_i directly, which makes both passes pure
accumulations that stream k/v (resp. q/dcons) tiles through a WINDOWED
INNER GRID AXIS with f32 VMEM scratch accumulators. No full [n, d] row
ever sits resident in VMEM (the round-2 design's _BWD_ROW_LIMIT and its
dense fallback past n=4096 are gone — any n streams at O(n) memory,
double-buffered by the Mosaic pipeline).

The dq pass avoids needing D = rowsum(dcons . cons) up front via the
decomposition ds_ij = p_ij (dP_ij - D_i):

    dq_i = scale * (A_i - D_i * B_i),  A = sum_j (p*dP)~ @ k,
                                       B = sum_j p~ @ k,
                                       D = sum_j rowsum(p*dP)

(~ = diagonal zeroed when attend_self=False; D keeps the full sum) — one
j-sweep, 4 matmuls per tile, emitting D as a byproduct for the dkv pass.
The dkv pass accumulates dv_j and dk_j over the i-window, pushes dk
through the row-local k-normalization VJP, and its epilogue folds the
complete dlevels (dmean + dq + dv + dk-VJP) into one output write. Both
passes skip dead tiles under the local-radius band: the inner grid axis
is sized to the LIVE window (static arithmetic), with edge duplicates
masked by pl.when.

Dispatch: the dense-recompute VJP (one XLA fusion over the materialized
[n, n] similarity) beats the blockwise kernels where n is small or the
mask has no sparsity to skip — _fused_bwd picks by a measured crossover
on (n, radius); see _use_blockwise_bwd for the table.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from glom_tpu.utils.helpers import TOKEN_ATTEND_SELF_VALUE

_NEG_MAX = float(jnp.finfo(jnp.float32).min)

def _row_col(idx, side):
    """Patch-grid (row, col) coordinates of flat patch indices (int32, never
    negative). Divided as uint32: Python's floor division carries its sign
    corrections into the kernel (three selects a vreg), the unsigned one
    does not, and by a power of two it compiles to a shift and a mask."""
    u, sd = idx.astype(jnp.uint32), jnp.uint32(side)
    return (
        jax.lax.div(u, sd).astype(jnp.int32),
        jax.lax.rem(u, sd).astype(jnp.int32),
    )


def _tile_ids(origin, extent, axis):
    """Flat patch indices origin .. origin + extent - 1 laid along `axis` of
    a score tile's trailing two dims: an int32 [extent, 1] (axis 0) or
    [1, extent] (axis 1) vector, never a whole tile."""
    shape = (extent, 1) if axis == 0 else (1, extent)
    return origin + jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _apply_masks(s, ids_a, ids_b, *, side, radius, attend_self):
    """The dual mask semantics shared by EVERY kernel (reference :9/:61-67):
    diagonal REPLACED by the soft -5e-4 when attend_self=False; pairs past
    the Euclidean grid radius hard-masked to -3e38. ids_a [A, 1] / ids_b
    [1, B] are the flat patch indices (_tile_ids) along s's trailing two
    dims; both masks are symmetric in the pair, so a transposed score tile
    passes its key indices as ids_a. The grid coordinates are formed on
    those two vectors (A + B divisions, not A * B); only the differences,
    squares, sum, comparison and select run on the score tile."""
    if not attend_self:
        s = jnp.where((ids_a == ids_b)[None], TOKEN_ATTEND_SELF_VALUE, s)
    if radius > 0:
        ra, ca = _row_col(ids_a, side)
        rb, cb = _row_col(ids_b, side)
        dist2 = (ra - rb) ** 2 + (ca - cb) ** 2
        # dist2 is an integer, so `dist2 > floor(r^2)` is the reference's
        # `dist2 > r^2` without a tile-wide convert
        s = jnp.where(
            (dist2 > math.floor(radius * radius))[None], _NEG_MAX, s
        )
    return s


def _norm_vjp(dk, x):
    """VJP of the row-local k-normalization k = x / max(||x||, eps)
    (helpers.l2norm), shared by every backward kernel. dk f32, x compute
    dtype; returns f32."""
    f32 = jnp.float32
    x32 = x.astype(f32)
    r = jnp.sqrt(jnp.sum(x32 * x32, axis=-1, keepdims=True))
    inv = 1.0 / jnp.maximum(r, 1e-12)
    a = jnp.sum(dk * x32, axis=-1, keepdims=True)
    return dk * inv - jnp.where(r >= 1e-12, a * x32 * inv * inv / r, 0.0)


def _consensus_update_kernel(
    x_ref,      # [1, TB, TI, d] levels q/self tile
    kv_ref,     # [1, TB, n, d]  full rows of levels for (g, b-tile): k and v
    bu_ref,     # [1, TB, TI, d] bottom-up contribution tile
    td_ref,     # [1, TB, TI, d] top-down tile (index-clamped at the top level)
    out_ref,    # [1, TB, TI, d]
    *stats_refs,  # training fwd: m_ref, l_ref [1, TB, TI, 1] f32 — the
                #   flash-style softmax residuals the backward kernels
                #   consume instead of recomputing the row statistics
    levels_count: int,
    side: int,
    radius: float,
    attend_self: bool,
    tile_i: int,
    tile_j: int,
    n: int,
):
    """One program: a (level g, image-tile, row-tile i) block. The TB images
    ride the batch dimension of a single batched dot_general per j-step, so
    small-n configs still feed the MXU one large op instead of TB tiny ones.
    """
    g = pl.program_id(0)
    i = pl.program_id(2)
    tb = x_ref.shape[1]
    d = x_ref.shape[-1]
    scale = d ** -0.5

    x = x_ref[0]  # [TB, TI, d]
    q32 = x.astype(jnp.float32)

    row_ids = _tile_ids(i * tile_i, tile_i, 0)

    # Block sparsity for the local mask: the live j-window for this i-tile
    # (i is traced, so the window is int32 arithmetic; fori_loop takes
    # dynamic bounds). Shared with both backward kernels via _window.
    j_lo, j_hi = _window(i * tile_i, tile_i, tile_j, n // tile_j, side, radius)

    m0 = jnp.full((tb, tile_i, 1), _NEG_MAX, jnp.float32)
    l0 = jnp.zeros((tb, tile_i, 1), jnp.float32)
    acc0 = jnp.zeros((tb, tile_i, d), jnp.float32)

    def j_body(j, carry):
        m, l, acc = carry
        kv = kv_ref[0, :, pl.ds(j * tile_j, tile_j), :]  # [TB, TJ, d]
        # k-only L2 normalization (reference :56): v stays raw.
        k = _normalized_k(kv)
        s = (
            jax.lax.dot_general(
                x, k, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )
            * scale
        )  # [TB, TI, TJ]

        s = _apply_masks(
            s, row_ids, _tile_ids(j * tile_j, tile_j, 1),
            side=side, radius=radius, attend_self=attend_self,
        )

        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        # Downcast the probabilities for the MXU, matching the dense op's
        # softmax(...).astype(levels.dtype) before attn @ v.
        pv = jax.lax.dot_general(
            p.astype(x.dtype), kv, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        acc_new = acc * corr + pv
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(j_lo, j_hi, j_body, (m0, l0, acc0))
    cons = acc / l
    if stats_refs:
        m_ref, l_ref = stats_refs[:2]
        m_ref[0] = m
        l_ref[0] = l
        if len(stats_refs) == 3:
            # cons residual for the one-sweep long-row backward (it makes
            # D_i = rowsum(dcons_i * cons_i) row-local there)
            stats_refs[2][0] = cons.astype(stats_refs[2].dtype)

    bu = bu_ref[0].astype(jnp.float32)
    td = td_ref[0].astype(jnp.float32)
    # Top level: no top-down contribution (its tile is index-clamped junk)
    # and a 3-way divisor (reference :121-122, :130).
    is_top = g == levels_count - 1
    td = jnp.where(is_top, 0.0, td)
    div = jnp.where(is_top, 3.0, 4.0)
    new = (q32 + bu + td + cons) / div
    out_ref[0] = new.astype(out_ref.dtype)


def _consensus_update_kernel_streamed(
    x_ref,      # [1, TB, TI, d] levels q/self tile (resident across jw)
    kv_ref,     # [1, TB, TJ, d] STREAMED levels j-tile
    bu_ref,     # [1, TB, TI, d] (resident; epilogue)
    td_ref,     # [1, TB, TI, d] (resident, index-clamped at the top level)
    out_ref,    # [1, TB, TI, d] written at the last jw step
    *stats_refs,  # optional m_ref, l_ref [1, TB, TI, 1] f32 outs
    levels_count: int,
    side: int,
    radius: float,
    attend_self: bool,
    tile_i: int,
    tile_j: int,
    n: int,
):
    """Large-n forward: the same online softmax as _consensus_update_kernel
    but with the j sweep as a STREAMED inner grid axis (windowed under the
    local-radius band) and the (m, l, acc) carry in VMEM scratch — no full
    [n, d] k/v row residency, O(n) VMEM at any n. Dispatched by _forward
    when the resident-row working set would overflow the scoped-VMEM
    budget (measured: bf16 n=9216 needs 47MB > the 43MB scope with the
    resident-row kernel)."""
    m_acc, l_acc, acc_acc = stats_refs[-3:]
    out_stats = stats_refs[:-3]
    g = pl.program_id(0)
    i = pl.program_id(2)
    jw = pl.program_id(3)
    num_jw = pl.num_programs(3)
    d = x_ref.shape[-1]
    scale = d ** -0.5
    f32 = jnp.float32
    n_tj = n // tile_j

    @pl.when(jw == 0)
    def _init():
        m_acc[...] = jnp.full_like(m_acc, _NEG_MAX)
        l_acc[...] = jnp.zeros_like(l_acc)
        acc_acc[...] = jnp.zeros_like(acc_acc)

    lo = _win_lo_tile(i, tile_i, tile_j, side, radius)
    hi = _win_hi_tile(i, tile_i, tile_j, n_tj, side, radius)
    j = lo + jw

    @pl.when(j < hi)
    def _step():
        x = x_ref[0]
        kv = kv_ref[0]
        k = _normalized_k(kv)
        s = (
            jax.lax.dot_general(
                x, k, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=f32,
            )
            * scale
        )
        s = _apply_masks(
            s, _tile_ids(i * tile_i, tile_i, 0), _tile_ids(j * tile_j, tile_j, 1),
            side=side, radius=radius, attend_self=attend_self,
        )
        m = m_acc[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_acc[...] = l_acc[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(x.dtype), kv, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=f32,
        )
        acc_acc[...] = acc_acc[...] * corr + pv
        m_acc[...] = m_new

    @pl.when(jw == num_jw - 1)
    def _final():
        m = m_acc[...]
        l = l_acc[...]
        cons = acc_acc[...] / l
        if out_stats:
            out_stats[0][0] = m
            out_stats[1][0] = l
            if len(out_stats) == 3:
                out_stats[2][0] = cons.astype(out_stats[2].dtype)
        bu = bu_ref[0].astype(f32)
        td = td_ref[0].astype(f32)
        is_top = g == levels_count - 1
        td = jnp.where(is_top, 0.0, td)
        div = jnp.where(is_top, 3.0, 4.0)
        out_ref[0] = ((x_ref[0].astype(f32) + bu + td + cons) / div).astype(
            out_ref.dtype
        )


# Resident-row cap for the FORWARD kernel: beyond this the [TB, n, d] k/v
# block (double-buffered by the pipeline) pushes the scoped-VMEM working
# set over Mosaic's budget and the streamed-forward variant dispatches.
_FWD_ROW_LIMIT = 4 * 1024 * 1024

# Largest n the single-tile fused backward handles (whole row as one block;
# sim + tiles stay within the VMEM budget at d<=1024).
_SMALL_BWD_N = 512


def _pick_tile(n: int, cap: int = 256) -> int:
    for t in (512, 256, 128, 64, 32, 16, 8):
        if t <= cap and n % t == 0 and t <= n:
            return t
    return n


# Shared per-program VMEM budget for picking the batch tile (the kernels'
# scoped limits are higher; this leaves pipelining headroom).
_TILE_B_BUDGET = 12 * 1024 * 1024


def _fit_tile_b(B: int, ws_of_tb) -> int:
    """Largest batch tile in (8, 4, 2, 1) dividing B whose working set
    (bytes, per ws_of_tb(tb)) fits _TILE_B_BUDGET. The single source of
    the candidate ladder + budget for all three consensus kernels."""
    for tb in (8, 4, 2, 1):
        if B % tb == 0 and ws_of_tb(tb) <= _TILE_B_BUDGET:
            return tb
    return 1


def _pick_tile_b(
    B: int, n: int, d: int, tile_i: int, tile_j: int, itemsize: int,
    *, streamed: bool = False,
) -> int:
    """Batch tile for the FORWARD: ~2x-buffered in/out blocks + f32
    accumulators + the sim tile. The streamed layout replaces the resident
    k/v rows with one 2x-buffered j-tile + the f32 (m, l, acc) scratch."""

    def ws(tb):
        blocks = 5 * tb * tile_i * d * itemsize * 2  # x/bu/td/out/kv, 2x buffered
        if streamed:
            kv_extra = tb * tile_j * d * itemsize * 2
        else:
            kv_extra = tb * (n - tile_i) * d * itemsize * 2 if n > tile_i else 0
        scratch = tb * tile_i * (d + 1) * 4 * 2 + tb * tile_i * tile_j * 4
        return blocks + kv_extra + scratch

    return _fit_tile_b(B, ws)


def _forward(
    levels_lm: jnp.ndarray,
    bu_lm: jnp.ndarray,
    td_lm: jnp.ndarray,
    *,
    side: int,
    radius: float,
    attend_self: bool,
    interpret: bool,
    save_stats: bool = False,
    save_cons: bool = False,
):
    """save_stats=True (the training forward under custom_vjp) also emits
    the f32 row statistics (m, l) consumed by the backward kernels.
    save_cons=True additionally emits the attention output `cons` (compute
    dtype) — the residual that lets the ONE-SWEEP long-row backward
    compute D_i = rowsum(dcons_i * cons_i) row-locally instead of needing
    a separate D-producing pass.

    Two grid layouts behind one contract: resident-row (k/v rows live in
    VMEM, fori_loop over j — fastest when they fit) vs streamed (j as a
    windowed inner grid axis, (m, l, acc) in scratch — O(n) VMEM at any
    n); dispatched on _FWD_ROW_LIMIT."""
    L, B, n, d = levels_lm.shape
    tile_i = _pick_tile(n)
    # Global consensus: a wider j-tile halves the online-softmax correction
    # steps (measured 1.91 -> 1.69 ms at n=4096, beating the dense XLA
    # path). Local radius: keep j-tiles at 256 so the block-sparse window
    # stays fine-grained (a 512 tile erases the skip at side<=32).
    tile_j = _pick_tile(n, cap=512 if radius <= 0 else 256)
    streamed = n * d * levels_lm.dtype.itemsize > _FWD_ROW_LIMIT
    tile_b = _pick_tile_b(
        B, n, d, tile_i, tile_j, levels_lm.dtype.itemsize, streamed=streamed
    )

    kw = dict(
        levels_count=L,
        side=side,
        radius=float(radius),
        attend_self=attend_self,
        tile_i=tile_i,
        tile_j=tile_j,
        n=n,
    )
    out_shape = jax.ShapeDtypeStruct((L, B, n, d), levels_lm.dtype)
    if streamed:
        def i_spec(last):
            return pl.BlockSpec(
                (1, tile_b, tile_i, last), lambda g, b, i, jw: (g, b, i, 0)
            )

        n_tj = n // tile_j

        def kv_map(g, b, i, jw, _tj=n_tj):
            lo = _win_lo_tile(i, tile_i, tile_j, side, radius)
            return (g, b, jnp.minimum(lo + jw, _tj - 1), 0)

        out_spec = i_spec(d)
        if save_stats:
            stat_shape = jax.ShapeDtypeStruct((L, B, n, 1), jnp.float32)
            out_shape = (out_shape, stat_shape, stat_shape)
            out_spec = (out_spec, i_spec(1), i_spec(1))
            if save_cons:
                out_shape = out_shape + (
                    jax.ShapeDtypeStruct((L, B, n, d), levels_lm.dtype),
                )
                out_spec = out_spec + (i_spec(d),)
        f32 = jnp.float32
        return pl.pallas_call(
            partial(_consensus_update_kernel_streamed, **kw),
            out_shape=out_shape,
            grid=(
                L, B // tile_b, n // tile_i,
                _win_len(tile_i, tile_j, n_tj, side, radius),
            ),
            in_specs=[
                i_spec(d),  # x
                pl.BlockSpec((1, tile_b, tile_j, d), kv_map),  # streamed kv
                i_spec(d),  # bu
                pl.BlockSpec(
                    (1, tile_b, tile_i, d),
                    lambda g, b, i, jw, _L=L: (jnp.minimum(g, _L - 2), b, i, 0),
                ),  # td (clamped top)
            ],
            out_specs=out_spec,
            scratch_shapes=[
                pltpu.VMEM((tile_b, tile_i, 1), f32),  # m
                pltpu.VMEM((tile_b, tile_i, 1), f32),  # l
                pltpu.VMEM((tile_b, tile_i, d), f32),  # acc
            ],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=32 * 1024 * 1024
            ),
            interpret=interpret,
            name="consensus_update_streamed_fwd",
        )(levels_lm, levels_lm, bu_lm, td_lm)

    grid = (L, B // tile_b, n // tile_i)
    out_spec = pl.BlockSpec((1, tile_b, tile_i, d), lambda g, b, i: (g, b, i, 0))
    if save_stats:
        stat_shape = jax.ShapeDtypeStruct((L, B, n, 1), jnp.float32)
        stat_spec = pl.BlockSpec((1, tile_b, tile_i, 1), lambda g, b, i: (g, b, i, 0))
        out_shape = (out_shape, stat_shape, stat_shape)
        out_spec = (out_spec, stat_spec, stat_spec)
        if save_cons:
            out_shape = out_shape + (
                jax.ShapeDtypeStruct((L, B, n, d), levels_lm.dtype),
            )
            out_spec = out_spec + (
                pl.BlockSpec(
                    (1, tile_b, tile_i, d), lambda g, b, i: (g, b, i, 0)
                ),
            )
    return pl.pallas_call(
        partial(_consensus_update_kernel, **kw),
        out_shape=out_shape,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tile_b, tile_i, d), lambda g, b, i: (g, b, i, 0)),  # x
            pl.BlockSpec((1, tile_b, n, d), lambda g, b, i: (g, b, 0, 0)),  # kv
            pl.BlockSpec((1, tile_b, tile_i, d), lambda g, b, i: (g, b, i, 0)),  # bu
            # td has L-1 groups; clamp the top level's index (masked in-kernel)
            pl.BlockSpec(
                (1, tile_b, tile_i, d),
                lambda g, b, i, _L=L: (jnp.minimum(g, _L - 2), b, i, 0),
            ),
        ],
        out_specs=out_spec,
        # The cons residual output adds a 2x-buffered [TB, TI, d] block the
        # default 16MB scope doesn't fit at resident-row n=1024 (measured
        # 68K over); v5e has 128MB physical.
        compiler_params=(
            pltpu.CompilerParams(vmem_limit_bytes=32 * 1024 * 1024)
            if save_cons
            else None
        ),
        interpret=interpret,
        name="consensus_update_fwd",
    )(levels_lm, levels_lm, bu_lm, td_lm)


def _normalized_k(kv_tile):
    """k-only L2 normalization in f32, downcast to the compute dtype
    (reference :56 / helpers.l2norm: x / max(||x||, 1e-12))."""
    kv32 = kv_tile.astype(jnp.float32)
    norm = jnp.sqrt(jnp.sum(kv32 * kv32, axis=-1, keepdims=True))
    return (kv32 / jnp.maximum(norm, 1e-12)).astype(kv_tile.dtype)


def _window(center_lo, extent, tile, n_tiles, side, radius):
    """Live tile-window [lo, hi) along the opposite attention axis: flat
    indices interact only when their grid rows differ by <= radius, i.e.
    they are within (radius + 1) * side flat positions."""
    if radius <= 0:
        return 0, n_tiles
    reach = int(radius + 1) * side
    lo = center_lo - reach
    hi = center_lo + extent + reach
    return jnp.maximum(lo // tile, 0), jnp.minimum(-(-hi // tile), n_tiles)


def _win_lo_tile(t, tile_self, tile_other, side, radius):
    """First live tile index on the opposite attention axis for tile `t`
    (traced int32): flat indices interact only within (radius+1)*side."""
    if radius <= 0:
        return jnp.int32(0)
    reach = int(radius + 1) * side
    return jnp.maximum((t * tile_self - reach) // tile_other, 0)


def _win_hi_tile(t, tile_self, tile_other, n_tiles, side, radius):
    """One-past-last live tile index (traced int32)."""
    if radius <= 0:
        return jnp.int32(n_tiles)
    reach = int(radius + 1) * side
    return jnp.minimum(-(-(t * tile_self + tile_self + reach) // tile_other), n_tiles)


def _win_len(tile_self, tile_other, n_tiles, side, radius) -> int:
    """STATIC upper bound on live tiles per window — the size of the inner
    streaming grid axis. Edge tiles whose (lo + w) lands past hi are DMA'd
    clamped and masked off with pl.when."""
    if radius <= 0:
        return n_tiles
    reach = int(radius + 1) * side
    return min(n_tiles, (tile_self + 2 * reach) // tile_other + 2)


def _consensus_bwd_dq_kernel(
    x_ref,      # [1, TB, TI, d]  levels q tile (resident across jw)
    kv_ref,     # [1, TB, TJ, d]  STREAMED levels j-tile (k_j and v_j)
    dm_ref,     # [1, TB, TI, d]  RAW output-cotangent tile (compute dtype;
                #                 the 4-vs-3 mean divisor is applied HERE,
                #                 from the level grid index — feeding the
                #                 kernel g directly avoids a separate
                #                 divide+downcast HBM sweep in the caller)
    m_ref,      # [1, TB, TI, 1]  f32 row max SAVED BY THE FORWARD
    l_ref,      # [1, TB, TI, 1]  f32 row softmax denominator (forward)
    dq_ref,     # [1, TB, TI, d]  f32 out (written at the last jw step)
    dd_ref,     # [1, TB, TI, 1]  f32 out: D_i = sum_j p_ij dP_ij,
                #                 consumed by the dkv pass
    a_acc,      # VMEM scratch [TB, TI, d] f32: sum_j (p*dP)~ @ k
    b_acc,      # VMEM scratch [TB, TI, d] f32: sum_j p~ @ k
    d_acc,      # VMEM scratch [TB, TI, 1] f32: running D
    *, side, radius, attend_self, tile_i, tile_j, n,
):
    """Pass 1 of the blockwise consensus backward: ONE streamed j-sweep.
    With (m, l) saved by the forward, p_ij = exp(s_ij - m_i)/l_i directly,
    and the D-before-ds ordering problem dissolves via

        dq_i = scale * (A_i - D_i B_i),
        A = sum_j (p*dP)~ @ k,  B = sum_j p~ @ k,  D = sum_j rowsum(p*dP)

    (~ = diagonal zeroed when attend_self=False — the diagonal score was
    REPLACED by a constant so no grad flows through it; D keeps the FULL
    sum, since D_i = rowsum(dcons_i * cons_i) includes the diagonal's v).
    The inner grid axis jw walks the live j-window (block sparsity under
    the local-radius band is grid-level: dead tiles are never DMA'd);
    accumulators persist in VMEM scratch across jw."""
    i = pl.program_id(2)
    jw = pl.program_id(3)
    num_jw = pl.num_programs(3)
    # dcons = g / div: top level (last grid-0 index) averages 3. program_id
    # must be read at kernel top level — inside a pl.when branch (a
    # lax.cond) the interpret-mode substitution misses it.
    div = jnp.where(pl.program_id(0) == pl.num_programs(0) - 1, 3.0, 4.0)
    d = x_ref.shape[-1]
    scale = d ** -0.5
    f32 = jnp.float32
    n_tj = n // tile_j

    @pl.when(jw == 0)
    def _init():
        a_acc[...] = jnp.zeros_like(a_acc)
        b_acc[...] = jnp.zeros_like(b_acc)
        d_acc[...] = jnp.zeros_like(d_acc)

    lo = _win_lo_tile(i, tile_i, tile_j, side, radius)
    hi = _win_hi_tile(i, tile_i, tile_j, n_tj, side, radius)
    j = lo + jw

    @pl.when(j < hi)
    def _step():
        x = x_ref[0]
        dcons = dm_ref[0].astype(f32) / div
        m = m_ref[0]
        l = l_ref[0]
        kv = kv_ref[0]
        k = _normalized_k(kv)
        s = (
            jax.lax.dot_general(
                x, k, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=f32,
            )
            * scale
        )
        row_ids = _tile_ids(i * tile_i, tile_i, 0)
        col_ids = _tile_ids(j * tile_j, tile_j, 1)
        s = _apply_masks(
            s, row_ids, col_ids,
            side=side, radius=radius, attend_self=attend_self,
        )
        p = jnp.exp(s - m) / l  # [TB, TI, TJ] f32
        dp = jax.lax.dot_general(
            dcons.astype(x.dtype), kv, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=f32,
        )  # dP_ij = dcons_i . v_j
        t = p * dp
        d_acc[...] += jnp.sum(t, axis=-1, keepdims=True)
        if not attend_self:
            diag = (row_ids == col_ids)[None]
            t = jnp.where(diag, 0.0, t)
            p = jnp.where(diag, 0.0, p)
        a_acc[...] += jax.lax.dot_general(
            t.astype(x.dtype), k, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=f32,
        )
        b_acc[...] += jax.lax.dot_general(
            p.astype(x.dtype), k, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=f32,
        )

    @pl.when(jw == num_jw - 1)
    def _final():
        dd = d_acc[...]
        dq_ref[0] = (a_acc[...] - dd * b_acc[...]) * scale
        dd_ref[0] = dd


def _consensus_bwd_small_kernel(
    x_ref,      # [1, TB, n, d]  levels (q = k-source = v), whole row
    dm_ref,     # [1, TB, n, d]  RAW output cotangent (compute dtype)
    m_ref,      # [1, TB, n, 1]  f32 forward stats
    l_ref,      # [1, TB, n, 1]
    dlv_ref,    # [1, TB, n, d]  COMPLETE dlevels (levels dtype)
    dmean_ref,  # [1, TB, n, d]  g/div downcast — the d(bu) cotangent
                #                (d(td) is its [:L-1] slice), emitted here
                #                so the caller's divide+downcast sweep of g
                #                disappears
    *, side, radius, attend_self, n,
):
    """Single-tile consensus backward: when the whole patch row fits one
    tile (n <= 512 — the flagship n=256 lives here), the i- and j-ranges
    coincide, so ONE program computes the scores ONCE and emits the
    complete dlevels: 5 matmuls (s, dP, dq, dv, dk) vs the 8 of the
    two-pass form, ONE exp, and — the dominant saving at train shapes —
    no [L, B, n, d] f32 dq / [L, B, n, 1] stats round-tripping through
    HBM between passes (~200 MB per scan iteration at the flagship).
    With dd known in-register the ds = p*(dP - dd) form needs no A/B
    decomposition."""
    f32 = jnp.float32
    div = jnp.where(pl.program_id(0) == pl.num_programs(0) - 1, 3.0, 4.0)
    x = x_ref[0]              # [TB, n, d]
    dcons = dm_ref[0].astype(f32) / div
    dlv = _small_bwd_math(
        x, dcons, m_ref[0], l_ref[0],
        side=side, radius=radius, attend_self=attend_self, n=n,
    )
    dlv_ref[0] = dlv.astype(dlv_ref.dtype)
    dmean_ref[0] = dcons.astype(dmean_ref.dtype)


def _small_bwd_math(x, dcons, m, l, *, side, radius, attend_self, n):
    """The single-tile backward's math, shared with the hand-rolled loop
    VJP's combine kernel (kernels/fused_loop.py): given the whole patch row
    x [TB, n, d] and the DIVIDED f32 output cotangent dcons, return the
    complete f32 d(levels) = dcons + dq + dv + norm-VJP(dk)."""
    f32 = jnp.float32
    d = x.shape[-1]
    scale = d ** -0.5
    k = _normalized_k(x)

    s = (
        jax.lax.dot_general(
            x, k, (((2,), (2,)), ((0,), (0,))), preferred_element_type=f32
        )
        * scale
    )  # [TB, n, n]
    row_ids = _tile_ids(0, n, 0)
    col_ids = _tile_ids(0, n, 1)
    diag = (row_ids == col_ids)[None]
    s = _apply_masks(
        s, row_ids, col_ids, side=side, radius=radius, attend_self=attend_self
    )

    p = jnp.exp(s - m) / l  # [TB, n(i), n(j)] f32
    dp = jax.lax.dot_general(
        dcons.astype(x.dtype), x, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=f32,
    )  # dP_ij = dcons_i . v_j
    dd = jnp.sum(p * dp, axis=-1, keepdims=True)  # FULL sum incl. diagonal
    ds = p * (dp - dd)
    if not attend_self:
        ds = jnp.where(diag, 0.0, ds)
    dsc = ds.astype(x.dtype)

    # dq_i = scale * sum_j ds_ij k_j
    dq = jax.lax.dot_general(
        dsc, k, (((2,), (1,)), ((0,), (0,))), preferred_element_type=f32
    ) * scale
    # dv_j = sum_i p_ij dcons_i  (UNMASKED p: the diagonal feeds v)
    dv = jax.lax.dot_general(
        p.astype(x.dtype), dcons.astype(x.dtype), (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=f32,
    )
    # dk_j = scale * sum_i ds_ij q_i
    dk = jax.lax.dot_general(
        dsc, x, (((1,), (1,)), ((0,), (0,))), preferred_element_type=f32
    ) * scale

    dxn = _norm_vjp(dk, x)
    return dcons + dq + dv + dxn


def _consensus_bwd_dkv_kernel(
    xj_ref,     # [1, TB, TJ, d]  levels j-tile (k_j, v_j; resident)
    gj_ref,     # [1, TB, TJ, d]  RAW cotangent j-tile (resident; epilogue)
    dqj_ref,    # [1, TB, TJ, d]  f32 dq tile from pass 1 (resident; epilogue)
    q_ref,      # [1, TB, TI, d]  STREAMED levels i-tile (queries)
    dm_ref,     # [1, TB, TI, d]  STREAMED raw cotangent i-tile (the mean
                #                 divisor is applied here, as in the dq pass)
    m_ref,      # [1, TB, TI, 1]  STREAMED f32 stats (forward / dq pass)
    l_ref,      # [1, TB, TI, 1]
    dd_ref,     # [1, TB, TI, 1]
    out_ref,    # [1, TB, TJ, d]  levels dtype: the COMPLETE dlevels tile
                #                 (dmean + dq + dv + normalizeVJP(dk)) —
                #                 folding the sum here removes the separate
                #                 XLA add/convert HBM sweeps
    dv_acc,     # VMEM scratch [TB, TJ, d] f32
    dk_acc,     # VMEM scratch [TB, TJ, d] f32
    *, side, radius, attend_self, tile_i, tile_j, n,
):
    """Pass 2: for each j-tile, stream the live i-window (inner grid axis
    iw) and accumulate dv_j = sum_i p_ij dcons_i and
    dk_j = scale * sum_i ds_ij q_i in VMEM scratch; the last iw step pushes
    dk through the row-local k-normalization VJP and finishes dlevels:
    out_j = g_j/div + dq_j + dv_j + dxn_j, downcast once."""
    j = pl.program_id(2)
    iw = pl.program_id(3)
    num_iw = pl.num_programs(3)
    # program_id reads must stay at kernel top level (see the dq kernel).
    inv_div = 1.0 / jnp.where(
        pl.program_id(0) == pl.num_programs(0) - 1, 3.0, 4.0
    )
    d = xj_ref.shape[-1]
    scale = d ** -0.5
    f32 = jnp.float32
    n_ti = n // tile_i

    @pl.when(iw == 0)
    def _init():
        dv_acc[...] = jnp.zeros_like(dv_acc)
        dk_acc[...] = jnp.zeros_like(dk_acc)

    lo = _win_lo_tile(j, tile_j, tile_i, side, radius)
    hi = _win_hi_tile(j, tile_j, tile_i, n_ti, side, radius)
    i = lo + iw

    # g / div applied via the LINEAR uses of dcons: dv and dP are both
    # linear in dcons, so the divide moves onto the accumulated dots.
    xj = xj_ref[0]            # [TB, TJ, d] raw levels (v_j; k_j after norm)

    @pl.when(i < hi)
    def _step():
        k = _normalized_k(xj)
        col_ids = _tile_ids(j * tile_j, tile_j, 0)
        q = q_ref[0]              # [TB, TI, d]
        dcons = dm_ref[0]         # [TB, TI, d] raw
        m = m_ref[0][..., 0]      # [TB, TI]
        l = l_ref[0][..., 0]
        dd = dd_ref[0][..., 0]

        # s2[b, tj, ti] = s[i, j] transposed
        s2 = (
            jax.lax.dot_general(
                k, q, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=f32,
            )
            * scale
        )  # [TB, TJ, TI]
        row_ids = _tile_ids(i * tile_i, tile_i, 1)
        # query index along the LAST axis here (both masks are symmetric in
        # the pair, so the transposed orientation reuses the helper)
        s2 = _apply_masks(
            s2, col_ids, row_ids,
            side=side, radius=radius, attend_self=attend_self,
        )

        p2 = jnp.exp(s2 - m[:, None, :]) / l[:, None, :]     # [TB, TJ, TI]
        p2c = p2.astype(xj.dtype)
        dv_acc[...] += jax.lax.dot_general(
            p2c, dcons, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=f32,
        )
        dp2 = (
            jax.lax.dot_general(
                xj, dcons, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=f32,
            )
            * inv_div
        )  # dP2[b, tj, ti] = v_j . (dcons_i / div_i); dd is already divided
        ds2 = p2 * (dp2 - dd[:, None, :])
        if not attend_self:
            ds2 = jnp.where((col_ids == row_ids)[None], 0.0, ds2)
        dk_acc[...] += jax.lax.dot_general(
            ds2.astype(xj.dtype), q, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=f32,
        )

    @pl.when(iw == num_iw - 1)
    def _final():
        dv = dv_acc[...] * inv_div  # accumulated against the RAW cotangents
        dk = dk_acc[...] * scale
        dxn = _norm_vjp(dk, xj)
        # Epilogue: complete dlevels for this j-tile. dmean_j = g_j / div.
        gj = gj_ref[0].astype(f32) * inv_div
        out_ref[0] = (gj + dqj_ref[0] + dv + dxn).astype(out_ref.dtype)


def _consensus_bwd_onesweep_kernel(
    xj_ref,     # [1, TB, TJ, d]  levels j-tile (k_j and v_j; resident)
    gj_ref,     # [1, TB, TJ, d]  RAW cotangent j-tile (resident; epilogue)
    q_ref,      # [1, TB, TI, d]  STREAMED levels i-tile (queries)
    dm_ref,     # [1, TB, TI, d]  STREAMED raw cotangent i-tile
    cons_ref,   # [1, TB, TI, d]  STREAMED attention output SAVED by the
                #                 forward: D_i = rowsum(dcons_i * cons_i)
                #                 becomes row-LOCAL, which is what lets dq
                #                 and dkv share one sweep (the two-pass
                #                 design existed only because D had to be
                #                 produced before ds could be formed)
    m_ref,      # [1, TB, TI, 1]  f32 forward stats
    l_ref,      # [1, TB, TI, 1]
    out_ref,    # [1, TB, TJ, d]  PARTIAL dlevels j-tile: dmean + dv + dk-VJP
                #                 (dq joins in XLA — its rows finish only at
                #                 the end of the whole (g, b) subgrid)
    dq_ref,     # [1, TB, n, d]   f32 dq accumulator, RESIDENT across the
                #                 entire (j, iw) subgrid (constant index)
    dv_acc,     # VMEM scratch [TB, TJ, d] f32
    dk_acc,     # VMEM scratch [TB, TJ, d] f32
    *, side, radius, attend_self, tile_i, tile_j, n,
):
    """ONE-sweep blockwise consensus backward for long rows: for each
    j-tile, stream the live i-window once, computing the scores ONCE per
    (i, j) pair and accumulating ALL of dv_j, dk_j (VMEM scratch) and
    dq_i (a whole-row resident f32 block, row-sliced stores) — 5 matmuls
    per pair vs the two-pass form's 8 (which computed s and dP twice and
    round-tripped dq/D through HBM between passes)."""
    j = pl.program_id(2)
    iw = pl.program_id(3)
    num_iw = pl.num_programs(3)
    first = (j == 0) & (iw == 0)
    inv_div = 1.0 / jnp.where(
        pl.program_id(0) == pl.num_programs(0) - 1, 3.0, 4.0
    )
    d = xj_ref.shape[-1]
    scale = d ** -0.5
    f32 = jnp.float32
    n_ti = n // tile_i

    @pl.when(first)
    def _init_dq():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    @pl.when(iw == 0)
    def _init():
        dv_acc[...] = jnp.zeros_like(dv_acc)
        dk_acc[...] = jnp.zeros_like(dk_acc)

    lo = _win_lo_tile(j, tile_j, tile_i, side, radius)
    hi = _win_hi_tile(j, tile_j, tile_i, n_ti, side, radius)
    i = lo + iw

    xj = xj_ref[0]            # [TB, TJ, d]

    @pl.when(i < hi)
    def _step():
        k = _normalized_k(xj)
        q = q_ref[0]              # [TB, TI, d]
        dcons = dm_ref[0].astype(f32) * inv_div
        dd = jnp.sum(dcons * cons_ref[0].astype(f32), axis=-1)  # [TB, TI]
        m = m_ref[0][..., 0]
        l = l_ref[0][..., 0]

        col_ids = _tile_ids(j * tile_j, tile_j, 0)
        row_ids = _tile_ids(i * tile_i, tile_i, 1)
        s2 = (
            jax.lax.dot_general(
                k, q, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=f32,
            )
            * scale
        )  # [TB, TJ, TI] — s transposed; masks are pair-symmetric
        s2 = _apply_masks(
            s2, col_ids, row_ids,
            side=side, radius=radius, attend_self=attend_self,
        )
        p2 = jnp.exp(s2 - m[:, None, :]) / l[:, None, :]
        dconsc = dcons.astype(xj.dtype)
        dv_acc[...] += jax.lax.dot_general(
            p2.astype(xj.dtype), dconsc, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=f32,
        )
        dp2 = jax.lax.dot_general(
            xj, dconsc, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=f32,
        )  # dP2[b, tj, ti] = v_j . dcons_i
        ds2 = p2 * (dp2 - dd[:, None, :])
        if not attend_self:
            ds2 = jnp.where((col_ids == row_ids)[None], 0.0, ds2)
        ds2c = ds2.astype(xj.dtype)
        dk_acc[...] += jax.lax.dot_general(
            ds2c, q, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=f32,
        )
        # dq_i += scale * sum_j ds_ij k_j  (contract TJ); row-sliced store
        # into the resident whole-row accumulator.
        dq_step = jax.lax.dot_general(
            ds2c, k, (((1,), (1,)), ((0,), (0,))),
            preferred_element_type=f32,
        ) * scale  # [TB, TI, d]
        dq_ref[0, :, pl.ds(i * tile_i, tile_i), :] += dq_step

    @pl.when(iw == num_iw - 1)
    def _final():
        dk = dk_acc[...] * scale
        dxn = _norm_vjp(dk, xj)
        gj = gj_ref[0].astype(f32) * inv_div
        out_ref[0] = (gj + dv_acc[...] + dxn).astype(out_ref.dtype)


def _onesweep_ws(tb: int, n: int, d: int, tile: int, itemsize: int) -> int:
    """One-sweep working set: the whole-row resident f32 dq block + resident
    xj/gj + 2x-buffered streamed tiles + f32 scratch + sim tiles + out."""
    dq = tb * n * d * 4
    resident = 2 * tb * tile * d * itemsize * 2
    streamed = 3 * tb * tile * d * itemsize * 2 + 2 * tb * tile * 4 * 2
    scratch = 2 * tb * tile * d * 4
    sim = 3 * tb * tile * tile * 4
    out = tb * tile * d * itemsize * 2
    return dq + resident + streamed + scratch + sim + out


_ONESWEEP_BUDGET = 48 * 1024 * 1024


def _onesweep_ok(B: int, n: int, d: int, itemsize: int) -> bool:
    """Eligibility of the one-sweep backward: its whole-row f32 dq
    accumulator must fit VMEM alongside the tiles even at batch tile 1."""
    return _onesweep_ws(1, n, d, _pick_tile(n), itemsize) <= _ONESWEEP_BUDGET


def _consensus_bwd_onesweep(
    levels_lm, graw, m, l, cons, *, side, radius, attend_self, interpret
):
    L, B, n, d = levels_lm.shape
    tile = _pick_tile(n)
    itemsize = levels_lm.dtype.itemsize
    tile_b = _fit_tile_b(B, lambda tb: _onesweep_ws(tb, n, d, tile, itemsize))
    f32 = jnp.float32
    n_t = n // tile

    def _j_spec(last):
        return pl.BlockSpec(
            (1, tile_b, tile, last), lambda g, b, j, iw: (g, b, j, 0)
        )

    def _i_map(g, b, j, iw, _ti=n_t):
        lo = _win_lo_tile(j, tile, tile, side, radius)
        return (g, b, jnp.minimum(lo + iw, _ti - 1), 0)

    def _i_spec(last):
        return pl.BlockSpec((1, tile_b, tile, last), _i_map)

    iw_len = _win_len(tile, tile, n_t, side, radius)
    out, dq = pl.pallas_call(
        partial(
            _consensus_bwd_onesweep_kernel,
            side=side, radius=float(radius), attend_self=attend_self,
            tile_i=tile, tile_j=tile, n=n,
        ),
        out_shape=(
            jax.ShapeDtypeStruct((L, B, n, d), levels_lm.dtype),
            jax.ShapeDtypeStruct((L, B, n, d), f32),
        ),
        grid=(L, B // tile_b, n_t, iw_len),
        in_specs=[
            _j_spec(d),   # xj (resident)
            _j_spec(d),   # gj (resident, epilogue)
            _i_spec(d),   # streamed q
            _i_spec(d),   # streamed raw cotangent
            _i_spec(d),   # streamed cons residual
            _i_spec(1),   # m
            _i_spec(1),   # l
        ],
        out_specs=(
            _j_spec(d),
            pl.BlockSpec((1, tile_b, n, d), lambda g, b, j, iw: (g, b, 0, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((tile_b, tile, d), f32),
            pltpu.VMEM((tile_b, tile, d), f32),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="consensus_update_bwd_onesweep",
    )(levels_lm, graw, levels_lm, graw, cons, m, l)
    # dq rows complete only at the end of each (g, b) subgrid — joined here
    # (one fused add sweep, O(n*d), vs the O(n^2) kernel work).
    return (out.astype(f32) + dq).astype(levels_lm.dtype)


def _pick_tile_b_bwd(B: int, n: int, d: int, tile: int, itemsize: int) -> int:
    """Batch tile for the BACKWARD kernels. Nothing full-row is resident
    any more (the i/j windows stream through the inner grid axis); the
    working set is resident tiles (x/dm or xj/gj/dqj), one streamed tile
    pair 2x-buffered, the f32 scratch accumulators, and the out block."""

    def ws(tb):
        resident = tb * tile * d * (2 * itemsize + 4)      # x/dm + f32 dqj
        streamed = 2 * tb * tile * d * (itemsize + itemsize)  # q + dm tiles
        scratch = 2 * tb * tile * d * 4 + tb * tile * 4    # A/B (or dv/dk) + D
        sim = 2 * tb * tile * tile * 4                     # p / dp tiles
        out = tb * tile * d * (4 + itemsize)
        return resident + streamed + scratch + sim + out

    return _fit_tile_b(B, ws)


def _consensus_update_bwd(
    levels_lm, g, m, l, cons=None, *, side, radius, attend_self, interpret
):
    """Blockwise backward for the fused consensus+update: returns the
    COMPLETE d(levels) = dmean + dq + (dv + dk-through-normalization), in
    the levels dtype. `g` is the RAW output cotangent in the compute dtype
    — the 4-vs-3 mean divisor is applied inside the kernels from the level
    grid index, and the dkv pass's epilogue folds dmean + dq into its
    output, so neither a divided copy of g nor the f32 partial sums ever
    make a separate HBM round trip. (m, l) are the forward's saved row
    statistics; both passes stream their opposite-axis tiles through a
    windowed inner grid axis — O(n) VMEM at ANY n.

    Returns (dlv, dmean): dmean (= g/div, levels dtype — the d(bu)
    cotangent; d(td) is its [:L-1] slice) is non-None only on the
    single-tile path, whose kernel emits it for free."""
    L, B, n, d = levels_lm.shape
    tile_i = _pick_tile(n)
    f32 = jnp.float32
    graw = g.astype(levels_lm.dtype)

    if n <= _SMALL_BWD_N:
        # Whole row in one tile (flagship n=256 and smaller): the fused
        # single-pass kernel — scores once, complete dlv + dmean out,
        # nothing between passes because there are no passes.
        itemsize = levels_lm.dtype.itemsize
        tile_b = _fit_tile_b(
            B,
            lambda tb: (
                3 * tb * n * n * 4  # s/p + dp + ds live f32
                + 6 * tb * n * d * (itemsize + 1)  # x/g/k/dcons/outs
            ),
        )

        def spec(last):
            return pl.BlockSpec((1, tile_b, n, last), lambda g_, b: (g_, b, 0, 0))

        dlv, dmean = pl.pallas_call(
            partial(
                _consensus_bwd_small_kernel,
                side=side, radius=float(radius), attend_self=attend_self, n=n,
            ),
            out_shape=(
                jax.ShapeDtypeStruct((L, B, n, d), levels_lm.dtype),
                jax.ShapeDtypeStruct((L, B, n, d), levels_lm.dtype),
            ),
            grid=(L, B // tile_b),
            in_specs=[spec(d), spec(d), spec(1), spec(1)],
            out_specs=(spec(d), spec(d)),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=32 * 1024 * 1024
            ),
            interpret=interpret,
            name="consensus_update_bwd_small",
        )(levels_lm, graw, m, l)
        return dlv, dmean

    if cons is not None and _onesweep_ok(B, n, d, levels_lm.dtype.itemsize):
        dlv = _consensus_bwd_onesweep(
            levels_lm, graw, m, l, cons,
            side=side, radius=radius, attend_self=attend_self,
            interpret=interpret,
        )
        return dlv, None

    tile_j = _pick_tile(n)
    tile_b = _pick_tile_b_bwd(
        B, n, d, max(tile_i, tile_j), levels_lm.dtype.itemsize
    )
    n_ti, n_tj = n // tile_i, n // tile_j

    kw = dict(
        side=side, radius=float(radius), attend_self=attend_self,
        tile_i=tile_i, tile_j=tile_j, n=n,
    )

    def _i_spec(shape_last):
        return pl.BlockSpec(
            (1, tile_b, tile_i, shape_last), lambda g, b, i, jw: (g, b, i, 0)
        )

    def _kv_map(g, b, i, jw, _tj=n_tj):
        lo = _win_lo_tile(i, tile_i, tile_j, side, radius)
        return (g, b, jnp.minimum(lo + jw, _tj - 1), 0)

    jw_len = _win_len(tile_i, tile_j, n_tj, side, radius)
    dq, dd = pl.pallas_call(
        partial(_consensus_bwd_dq_kernel, **kw),
        out_shape=(
            jax.ShapeDtypeStruct((L, B, n, d), f32),
            jax.ShapeDtypeStruct((L, B, n, 1), f32),
        ),
        grid=(L, B // tile_b, n_ti, jw_len),
        in_specs=[
            _i_spec(d),  # x
            pl.BlockSpec((1, tile_b, tile_j, d), _kv_map),  # streamed kv
            _i_spec(d),  # dm (raw cotangent)
            _i_spec(1),  # m
            _i_spec(1),  # l
        ],
        out_specs=(_i_spec(d), _i_spec(1)),
        scratch_shapes=[
            pltpu.VMEM((tile_b, tile_i, d), f32),
            pltpu.VMEM((tile_b, tile_i, d), f32),
            pltpu.VMEM((tile_b, tile_i, 1), f32),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=32 * 1024 * 1024),
        interpret=interpret,
        name="consensus_update_bwd_dq",
    )(levels_lm, levels_lm, graw, m, l)

    def _j_spec(shape_last):
        return pl.BlockSpec(
            (1, tile_b, tile_j, shape_last), lambda g, b, j, iw: (g, b, j, 0)
        )

    def _q_map(g, b, j, iw, _ti=n_ti):
        lo = _win_lo_tile(j, tile_j, tile_i, side, radius)
        return (g, b, jnp.minimum(lo + iw, _ti - 1), 0)

    def _qspec(shape_last):
        return pl.BlockSpec((1, tile_b, tile_i, shape_last), _q_map)

    iw_len = _win_len(tile_j, tile_i, n_ti, side, radius)
    dlv = pl.pallas_call(
        partial(_consensus_bwd_dkv_kernel, **kw),
        out_shape=jax.ShapeDtypeStruct((L, B, n, d), levels_lm.dtype),
        grid=(L, B // tile_b, n_tj, iw_len),
        in_specs=[
            _j_spec(d),   # xj (resident)
            _j_spec(d),   # gj (resident, epilogue)
            _j_spec(d),   # dq j-tile (resident, epilogue)
            _qspec(d),    # streamed q i-tile
            _qspec(d),    # streamed dm i-tile
            _qspec(1),    # m
            _qspec(1),    # l
            _qspec(1),    # dd
        ],
        out_specs=_j_spec(d),
        scratch_shapes=[
            pltpu.VMEM((tile_b, tile_j, d), f32),
            pltpu.VMEM((tile_b, tile_j, d), f32),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=32 * 1024 * 1024),
        interpret=interpret,
        name="consensus_update_bwd_dkv",
    )(levels_lm, graw, dq, levels_lm, graw, m, l, dd)

    return dlv, None


def _xla_reference(levels_lm, bu_lm, td_lm, *, side, radius, attend_self):
    """Plain-XLA recomputation of the fused op (used for the backward pass).
    Must match the kernel's math contract bit-for-bit at the op level."""
    from glom_tpu.ops.consensus import build_local_mask, consensus_attention

    L, B, n, d = levels_lm.shape
    levels = jnp.transpose(levels_lm, (1, 2, 0, 3))  # [B, n, L, d]
    mask = build_local_mask(side, radius)
    cons = consensus_attention(levels, attend_self=attend_self, local_mask=mask)
    cons_lm = jnp.transpose(cons, (2, 0, 1, 3))  # [L, B, n, d]
    td_full = jnp.concatenate(
        [td_lm[: L - 1], jnp.zeros_like(td_lm[:1])], axis=0
    )
    div = jnp.concatenate(
        [jnp.full((L - 1, 1, 1, 1), 4.0), jnp.full((1, 1, 1, 1), 3.0)]
    ).astype(jnp.float32)
    new = (
        levels_lm.astype(jnp.float32)
        + bu_lm.astype(jnp.float32)
        + td_full.astype(jnp.float32)
        + cons_lm.astype(jnp.float32)
    ) / div
    return new.astype(levels_lm.dtype)


# Dense sim-buffer cap where the backend has no allocator stats (the CPU:
# memory_stats() is None there).
_DENSE_SIM_LIMIT = 2 * 1024 * 1024 * 1024


def _dense_bwd_budget() -> int:
    """HBM budget for the dense backward's [L*B, n, n] f32 intermediates,
    derived from the device's reported capacity rather than a constant
    (the 2GB cap forced blockwise at shapes whose dense buffers
    demonstrably fit a 16GB chip; a v5e reports bytes_limit
    16,909,336,064). A 0.3 fraction leaves the rest for params/opt state,
    residual stacks, and XLA workspace — batch-aware because the caller
    multiplies by the actual [L, B, n, n] bytes. A TPU that reports no
    limit is an error, not a reason to guess."""
    dev = jax.devices()[0]
    stats = dev.memory_stats()
    if stats and stats.get("bytes_limit"):
        return int(0.3 * stats["bytes_limit"])
    if dev.platform == "tpu":
        raise RuntimeError(
            f"{dev.device_kind} reports no bytes_limit in memory_stats(); "
            "the dense-backward budget cannot be derived"
        )
    return _DENSE_SIM_LIMIT


def _use_blockwise_bwd(
    levels_shape, side, radius, bwd_impl: str, itemsize: int = 2
) -> bool:
    """Which side serves the fused op at this static shape: the blockwise
    Pallas kernels (True) or the dense XLA composition (False). Decided
    from (L, B, n, d), side, radius and itemsize alone — no environment
    variable, config field or preset name reaches it.

    Two bodies of evidence stand behind the branches, and each branch says
    which it rests on:

      * v5e, the full train step through benchmark/run.py, default against
        forced side on shared seeds (PERF.md section 6, PR 26): the
        batched long-row branch;
      * results/longctx_bench.jsonl, unstamped B=1 timings of the isolated
        op from a remote chip (ROADMAP D9): the band-sparsity branch, the
        n >= 4096 one-sweep branch and the dense default they leave. They
        keep their text until a cell measures them.

    The HBM gate at the end is arithmetic, not a timing: the dense side is
    not an option where its [L, B, n, n] f32 scores do not fit.

    bwd_impl forces a side ('blockwise' / 'dense') for tests and benches.
    `itemsize` is the compute dtype's — callers on the training path pass
    the real one so the one-sweep branches and _fused_fwd's save_cons gate
    share one predicate (an f32 long row must not be routed blockwise
    without its cons residual).
    """
    if bwd_impl not in ("auto", "blockwise", "dense"):
        raise ValueError(
            f"bwd_impl={bwd_impl!r}: one of 'auto', 'blockwise', 'dense'"
        )
    L, B, n, d = levels_shape
    if bwd_impl == "blockwise":
        return True
    if bwd_impl == "dense":
        return False
    # (B=1 file) A local band that prunes at least half the row: the
    # kernels' grids never visit dead tiles.
    if radius > 0:
        reach = int(radius + 1) * side
        live = min(n, 2 * reach + _pick_tile(n))
        if 2 * live <= n:  # window covers <= half the row: sparsity pays
            return True
    # Batched-training regime AT SINGLE-TILE ROWS: the fused single-tile
    # backward keeps the scores in VMEM while the dense VJP sweeps the
    # [B, L, n, n] scores through HBM several times — measured at the
    # flagship train step (B=64, n=256): ~3950 vs 3522 col-iters/s
    # full-step (round 4's remote chip; the flagship cells resolve
    # fused_loop before they reach this line, so no cell re-reads it).
    if B >= 8 and n <= _SMALL_BWD_N:
        return True
    # Batched LONG rows, 512 < n < 4096, at the 256-wide tile (PR 26, v5e,
    # full train step at L=6 d=512 bf16, dense -> blockwise):
    #   n=1024 r=7  B=32  373.34 -> 317.72 ms a step (596.1 -> 699.7
    #               col-iters/s/chip, peak HBM 15.53 -> 8.67 GB)
    #   n=1024 r=7  B=16  169.67 -> 147.26 ms;  B=8  85.54 -> 72.27 ms
    #               (642.0 -> 756.0): the B >= 8 read at n=256 holds here
    #   n=1024 r=0  B=32  373.26 -> 322.25 ms (596.6 -> 689.9): the band is
    #               not what wins — the kernels' time rises 6% from 10 to
    #               16 of 16 tile pairs, XLA's dense f32 scores cost
    #               151.6-151.8 ms at either radius — so radius is not a
    #               condition of this branch
    #   n=1024 r=0  B=8   85.34 -> 73.44 ms
    #   n=2304 r=0  B=8   286.39 -> 226.30 ms (peak HBM 16.39 -> 10.74 GB;
    #               the dense side fills the chip there)
    # and the edge it stops at: n=576 r=7 B=32, where _pick_tile gives 64
    # and the kernels LOSE, 171.63 -> 188.07 ms (1291.9 -> 1178.2). A
    # square grid's n tiles at 256 or at 64 and below, nothing between, so
    # the branch asks for the tile it was measured at. B < 8 is unmeasured
    # at the train step and stays on the dense side of the B=1 file.
    if (
        B >= 8
        and _SMALL_BWD_N < n < 4096
        and _pick_tile(n) == 256
        and _onesweep_ok(B, n, d, itemsize)
    ):
        return True
    # (B=1 file) Long global rows: the one-sweep kernel (scores once, no
    # inter-pass HBM round trips) wins where its whole-row dq accumulator
    # fits VMEM — measured 5.61 vs 7.23 ms at n=4096 r=0 B=1 and 27.6 vs
    # 30.5 ms at n=9216 r=0 (results/longctx_bench.jsonl, round 4; the
    # round-3 two-pass form LOST 38.8 vs 30.5 there). Below the crossover
    # the dense path keeps the small-batch mid-n regime (0.281 vs 0.388 at
    # n=1024 B=1). The HBM budget remains the hard gate for dense
    # regardless.
    if n >= 4096 and _onesweep_ok(B, n, d, itemsize):
        return True
    return 2 * L * B * n * n * 4 > _dense_bwd_budget()


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _fused(levels_lm, bu_lm, td_lm, side, radius, attend_self, interpret,
           bwd_impl="auto"):
    return _forward(
        levels_lm, bu_lm, td_lm,
        side=side, radius=radius, attend_self=attend_self, interpret=interpret,
    )


def _fused_fwd(levels_lm, bu_lm, td_lm, side, radius, attend_self, interpret,
               bwd_impl):
    """Training forward: ALWAYS saves the (m, l) row statistics — the flash
    logsumexp residual trade. On the blockwise side they feed the backward
    kernels; on the dense side they feed the explicit stats-based dense
    backward (one s recompute, no second forward — the jax.vjp-recompute
    form it replaces measured 17-19% over the raw dense VJP at n<=1024,
    round-3 longctx bench). The one-sweep long-row branch additionally
    saves the attention output `cons`, which makes D row-local there.
    bu/td are NOT residuals: their cotangent is g/div, values never
    needed."""
    L, B, n, d = levels_lm.shape
    blockwise = _use_blockwise_bwd(
        levels_lm.shape, side, radius, bwd_impl, levels_lm.dtype.itemsize
    )
    save_cons = (
        blockwise
        and n > _SMALL_BWD_N
        and _onesweep_ok(B, n, d, levels_lm.dtype.itemsize)
    )
    outs = _forward(
        levels_lm, bu_lm, td_lm,
        side=side, radius=radius, attend_self=attend_self,
        interpret=interpret, save_stats=True, save_cons=save_cons,
    )
    if save_cons:
        out, m, l, cons = outs
    else:
        (out, m, l), cons = outs, None
    # The backward-path decision is made HERE, once per trace, and rides
    # the residual PYTREE STRUCTURE (an empty tuple vs None has no array
    # leaves, so it stays static through the transpose): _dense_bwd_budget
    # reads allocator state, and re-evaluating it in _fused_bwd could
    # silently pick a different path than the one whose residuals were
    # saved (advisor round 4).
    return out, (levels_lm, m, l, cons, () if blockwise else None)


def _fused_bwd(side, radius, attend_self, interpret, bwd_impl, res, g):
    """The mean is linear (d bu = d td = dout/div); the attention part runs
    in the blockwise kernels (single-tile at n <= 512, one-sweep where the
    cons residual was saved, two-pass streamed otherwise — O(n) memory at
    any n) or through the explicit stats-based dense backward where that
    measured faster — decided ONCE in _fused_fwd and carried in the
    residual structure."""
    from glom_tpu.models.core import contribution_divisor  # lazy: no cycle

    levels_lm, m, l, cons, blockwise_flag = res
    L, B, n, d = levels_lm.shape
    f32 = jnp.float32
    if blockwise_flag is not None:
        # The kernels take the RAW cotangent, apply the divisor in-kernel
        # (from the level grid index), and emit the COMPLETE dlv in the
        # levels dtype — no divided/partial-sum copies of g hit HBM. The
        # single-tile kernel also emits dmean (the d(bu)/d(td) cotangent)
        # so the caller-side divide+downcast sweep of g disappears too.
        dlv, dmean_k = _consensus_update_bwd(
            levels_lm, g, m, l, cons,
            side=side, radius=radius, attend_self=attend_self,
            interpret=interpret,
        )
        if dmean_k is not None:
            return dlv, dmean_k, dmean_k[: L - 1]
    else:
        # Explicit dense backward from the saved stats: the same math as
        # the single-tile kernel (_small_bwd_math), batched over [L*B] in
        # XLA — recomputes s once, never re-runs the forward's softmax
        # reductions or attn@v.
        div = contribution_divisor(L, dtype=f32).reshape(L, 1, 1, 1)
        dcons = (g.astype(f32) / div).reshape(L * B, n, d)
        dlv = _small_bwd_math(
            levels_lm.reshape(L * B, n, d), dcons,
            m.reshape(L * B, n, 1), l.reshape(L * B, n, 1),
            side=side, radius=radius, attend_self=attend_self, n=n,
        ).reshape(L, B, n, d).astype(levels_lm.dtype)
    div = contribution_divisor(L, dtype=f32).reshape(L, 1, 1, 1)
    dmean = g.astype(f32) / div
    return (
        dlv,
        dmean.astype(levels_lm.dtype),
        dmean[: L - 1].astype(levels_lm.dtype),
    )


_fused.defvjp(_fused_fwd, _fused_bwd)


def fused_consensus_update(
    levels_lm: jnp.ndarray,
    bu_lm: jnp.ndarray,
    td_lm: jnp.ndarray,
    *,
    side: int,
    radius: float = 0.0,
    attend_self: bool = False,
    interpret: bool = False,
    bwd_impl: str = "auto",
) -> jnp.ndarray:
    """new_levels = (levels + bu + pad(td) + consensus(levels)) / div, fused.

    levels_lm: [L, B, n, d] level-major; bu_lm: [L, B, n, d];
    td_lm: [L-1, B, n, d] (top level's zero contribution is implicit).
    Returns [L, B, n, d]. Falls back to the XLA composition off-TPU.
    bwd_impl: 'auto' dispatches between the dense XLA composition and the
    blockwise kernels by _use_blockwise_bwd's measured regions;
    'blockwise'/'dense' force a side (tests, benches).
    """
    L, B, n, d = levels_lm.shape
    on_tpu = jax.devices()[0].platform == "tpu"
    supported = d % 128 == 0 and n % 8 == 0 and L >= 2
    if not supported or not (on_tpu or interpret):
        return _xla_reference(
            levels_lm, bu_lm, td_lm,
            side=side, radius=radius, attend_self=attend_self,
        )
    # Auto-resolved-dense small/mid rows: the XLA dense op wins BOTH
    # directions there (fwd 0.118 vs 0.139 ms, autodiff bwd 0.281 vs 0.354
    # at n=1024 B=1 — longctx bench), so hand the WHOLE op to XLA autodiff:
    # zero custom_vjp overhead by construction (round-3 weak #3's 17%).
    # A forced side (bwd_impl) keeps the custom_vjp so tests and benches
    # still reach the kernel paths; n >= 4096 keeps the hybrid (the Pallas
    # forward wins there: 1.66 vs 3.13 ms). The forward rides the same
    # decision: where the predicate says blockwise, a forward-only call
    # (evaluation, serving) runs consensus_update_fwd too.
    if (
        bwd_impl == "auto"
        and n < 4096
        and not _use_blockwise_bwd(
            (L, B, n, d), side, radius, bwd_impl, levels_lm.dtype.itemsize
        )
    ):
        return _xla_reference(
            levels_lm, bu_lm, td_lm,
            side=side, radius=radius, attend_self=attend_self,
        )
    return _fused(
        levels_lm, bu_lm, td_lm, side, radius, attend_self, interpret, bwd_impl
    )
