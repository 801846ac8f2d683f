"""Pallas TPU kernels: the selective state-space recurrence of Mamba-1,
forward and backward, for `models/sambay.selective_scan`.

    s_t = exp(dt_t A) s_{t-1} + (dt_t x_t) B_t^T,   y_t = s_t C_t

a position at a time in float32, the state [N, channels] (states on sublanes,
channels on lanes) in VMEM for the whole pass: what the XLA form
(`sambay.selective_scan`) carries through HBM a loop step at a time.

Grid (batch, time blocks, channel blocks), the last two sequential: a time
block's B and C are fetched once for all its channel blocks, and every
channel block's state waits in scratch [channel blocks, N, block] for the
next time block. x, dt and y are [B, T, C] as the mixer has them. A position's
B_t and C_t multiply the state as columns [N, 1] across the lanes; they come
in already laid across one register's lanes, [B, T, N, 128] float32 (an XLA
broadcast of the two small inputs, 67 MB each at 8,192 positions, read once
a time block), so that the kernel takes a position's two registers by a
plain load. Positions go in groups of 8: a group's rows of dt and dt x are
one aligned load, and its 8 sums over the states (y's rows; in the backward
the rows of d(dt x) and d(dt)) are made side by side by `_sums_as_rows`, 7
sublane rotations for the 24 that 8 separate reductions take, and stored as
one whole tile.

Forward: y in x's type and the state entering every time block, [T / block,
B, N, C] float32 (10.5 MB at a block of 256), the only residual beside the
inputs.

Backward, time blocks from last to first: the block's states and decays are
made again from the kept entering state into VMEM ([block, N, channel block]
each), then the block is walked in reverse carrying ds [N, channel block]:
dx and d(dt) leave as [B, T, C]; dA [N, channel block] adds up in the
resident output over the whole time axis; dB and dC add up over the channel
blocks with the channels of a register's lane still apart, [B, T, N, 128]
float32, and the caller sums the lanes. No [T, N, C] array reaches HBM in
either direction.

Shapes served (`blocks`): channels a whole number of 128-lane registers,
states a whole number of 8 sublanes; the caller pads the length to whole time
blocks with dt = 0 steps, which neither decay the state nor add to it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from glom_tpu.kernels.flash_attention import on_tpu  # noqa: F401  (the dispatch asks this module)

TIME_BLOCK = 256
CHANNEL_BLOCKS = (512, 256, 128)
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
_LANES = 128
_GROUP = 8   # positions a loop step: one register's sublanes


def blocks(t: int, channels: int, states: int):
    """(time block, channel block) for T positions, or None where the kernels
    do not serve the shape. The time block is TIME_BLOCK, or the whole length
    in groups of 8 where that is shorter; the length is padded to it."""
    if channels % _LANES or states % _GROUP:
        return None
    cb = next(block for block in CHANNEL_BLOCKS if channels % block == 0)
    return min(TIME_BLOCK, -(-t // _GROUP) * _GROUP), cb


def _across(col, width: int):
    """A position's column, [N, 128] with every lane alike, as wide as the state."""
    return jnp.concatenate([col] * (width // _LANES), axis=1)


def _fold_states(q):
    """[N, W] -> [8, W]: the states' sublane tiles added."""
    return sum(q[r:r + _GROUP] for r in range(0, q.shape[0], _GROUP))


def _fold_lanes(q):
    """[N, W] -> [N, 128]: the channels' lane tiles added."""
    return sum(q[:, l:l + _LANES] for l in range(0, q.shape[1], _LANES))


def _sums_as_rows(parts):
    """8 arrays [8, W] -> [8, W] whose row k is the sum of parts[k]'s 8 rows.
    Three levels, each adding a pair of arrays into one: the rows that keep
    their place take the other array's rows rotated onto them (by 1, 2, 4), so
    that after the third every array's 8 rows have met in its own row."""
    row = jax.lax.broadcasted_iota(jnp.int32, parts[0].shape, 0)
    for shift in (1, 2, 4):
        keep = (row & shift) == 0
        parts = [jnp.where(keep, a, b) + pltpu.roll(jnp.where(keep, b, a), shift, 0)
                 for a, b in zip(parts[::2], parts[1::2])]
    return parts[0]


# -------------------------------------------------------------------- forward


def _fwd_kernel(x_ref, dt_ref, at_ref, b_ref, c_ref, y_ref, enter_ref, s_sc, dtx_sc, y_sc):
    """One (time block, channel block): x_ref, dt_ref [tb, cb], at_ref [N, cb],
    b_ref, c_ref [tb, N, 128] -> y_ref [tb, cb], enter_ref [N, cb] (the state
    as the block finds it). The blocks' leading dimensions of one are squeezed
    away (`None` in the specs)."""
    i, j = pl.program_id(1), pl.program_id(2)
    tb, cb = dtx_sc.shape
    f32 = jnp.float32

    @pl.when(i == 0)
    def _():
        s_sc[j] = jnp.zeros(s_sc.shape[1:], f32)

    enter_ref[...] = s_sc[j]
    dtx_sc[...] = dt_ref[...] * x_ref[...].astype(f32)
    at = at_ref[...]

    def group(g, s):
        t0 = pl.multiple_of(g * _GROUP, _GROUP)
        dt8, dtx8 = dt_ref[pl.ds(t0, _GROUP), :], dtx_sc[pl.ds(t0, _GROUP), :]
        rows = []
        for k in range(_GROUP):
            s = jnp.exp(dt8[k:k + 1] * at) * s + _across(b_ref[t0 + k], cb) * dtx8[k:k + 1]
            rows.append(_fold_states(s * _across(c_ref[t0 + k], cb)))
        y_sc[pl.ds(t0, _GROUP), :] = _sums_as_rows(rows)
        return s

    s_sc[j] = jax.lax.fori_loop(0, tb // _GROUP, group, s_sc[j])
    y_ref[...] = y_sc[...].astype(y_ref.dtype)


# `jax.jit` for its cache of traces: a process's two step variants call the
# forward eight times and the backward four (two layers, the recomputation), and
# a kernel's body, a thousand operations in the backward, is traced anew at every
# `pallas_call`: 2.1 s a backward and 0.1-0.2 s a forward on the chip's host, 8 s
# of warm set-up where the XLA form's loops traced in 1 (PR 44). One trace a
# kernel and shape now serves every call; XLA inlines the calls.
@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _forward(x, dt, at, b4, c4, tb, cb, interpret):
    """x [B, T, C], dt [B, T, C] float32, at [N, C] float32, b4 and c4 [B, T,
    N, 128] float32 -> (y [B, T, C] in x's type, the states entering the time
    blocks [T / tb, B, N, C] float32)."""
    bsz, t, ch = x.shape
    n = at.shape[0]
    f32 = jnp.float32
    wide = pl.BlockSpec((None, tb, cb), lambda b, i, j: (b, i, j))
    col = pl.BlockSpec((None, tb, n, _LANES), lambda b, i, j: (b, i, 0, 0))
    return pl.pallas_call(
        _fwd_kernel,
        out_shape=(jax.ShapeDtypeStruct((bsz, t, ch), x.dtype),
                   jax.ShapeDtypeStruct((t // tb, bsz, n, ch), f32)),
        grid=(bsz, t // tb, ch // cb),
        in_specs=[wide, wide, pl.BlockSpec((n, cb), lambda b, i, j: (0, j)), col, col],
        out_specs=(wide, pl.BlockSpec((None, None, n, cb), lambda b, i, j: (i, b, 0, j))),
        scratch_shapes=[pltpu.VMEM((ch // cb, n, cb), f32),   # every channel block's state
                        pltpu.VMEM((tb, cb), f32),            # dt x
                        pltpu.VMEM((tb, cb), f32)],           # y before its cast
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="selective_scan_fwd",
    )(x, dt, at, b4, c4)


# ------------------------------------------------------------------- backward


def _bwd_kernel(x_ref, dt_ref, at_ref, b_ref, c_ref, dy_ref, enter_ref,
                dx_ref, ddt_ref, da_ref, db_ref, dc_ref,
                ds_sc, before_sc, decay_sc, dtx_sc, dy_sc, u_sc, w_sc):
    """One (time block, from the last; channel block): the forward's inputs,
    dy_ref [tb, cb] and enter_ref [N, cb] -> dx_ref, ddt_ref [tb, cb]; da_ref
    [channel blocks, N, cb], resident for the batch row; db_ref, dc_ref [tb,
    N, 128], resident for the time block."""
    i, j = pl.program_id(1), pl.program_id(2)
    tb, cb = dtx_sc.shape
    f32 = jnp.float32

    @pl.when(i == 0)
    def _():
        ds_sc[j] = jnp.zeros(ds_sc.shape[1:], f32)
        da_ref[j] = jnp.zeros(da_ref.shape[1:], f32)

    @pl.when(j == 0)
    def _():
        db_ref[...] = jnp.zeros_like(db_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)

    x32 = x_ref[...].astype(f32)
    dtx_sc[...] = dt_ref[...] * x32
    dy_sc[...] = dy_ref[...].astype(f32)
    at = at_ref[...]

    def again(g, s):   # the block's forward pass: every position's entering state and decay
        t0 = pl.multiple_of(g * _GROUP, _GROUP)
        dt8, dtx8 = dt_ref[pl.ds(t0, _GROUP), :], dtx_sc[pl.ds(t0, _GROUP), :]
        for k in range(_GROUP):
            decay = jnp.exp(dt8[k:k + 1] * at)
            before_sc[t0 + k] = s
            decay_sc[t0 + k] = decay
            s = decay * s + _across(b_ref[t0 + k], cb) * dtx8[k:k + 1]
        return s

    s_last = jax.lax.fori_loop(0, tb // _GROUP, again, enter_ref[...])

    def back(r, carry):
        ds, s, da = carry   # dL/ds_t from the positions after t, s_t, dA so far
        t0 = pl.multiple_of((tb // _GROUP - 1 - r) * _GROUP, _GROUP)
        at8 = pl.ds(t0, _GROUP)
        dt8, dtx8, dy8 = dt_ref[at8, :], dtx_sc[at8, :], dy_sc[at8, :]
        us, ws = [None] * _GROUP, [None] * _GROUP
        for k in reversed(range(_GROUP)):
            t = t0 + k
            total = ds + _across(c_ref[t], cb) * dy8[k:k + 1]
            dc_ref[t] += _fold_lanes(s * dy8[k:k + 1])
            db_ref[t] += _fold_lanes(total * dtx8[k:k + 1])
            us[k] = _fold_states(total * _across(b_ref[t], cb))   # d(dt x)
            ds = total * decay_sc[t]
            s = before_sc[t]
            through = ds * s                                          # d(dt A)
            da = da + through * dt8[k:k + 1]
            ws[k] = _fold_states(through * at)
        u_sc[at8, :] = _sums_as_rows(us)
        w_sc[at8, :] = _sums_as_rows(ws)
        return ds, s, da

    ds, _, da = jax.lax.fori_loop(0, tb // _GROUP, back,
                                  (ds_sc[j], s_last, jnp.zeros(at.shape, f32)))
    ds_sc[j] = ds
    da_ref[j] += da
    dx_ref[...] = (u_sc[...] * dt_ref[...]).astype(dx_ref.dtype)
    ddt_ref[...] = w_sc[...] + u_sc[...] * x32


@functools.partial(jax.jit, static_argnums=(7, 8, 9))
def _backward(x, dt, at, b4, c4, dy, enter, tb, cb, interpret):
    """-> (dx [B, T, C] in x's type, d(dt) [B, T, C] float32, dA^T a batch row
    and channel block [B, C / cb, N, cb], dB and dC with a register's lanes
    apart [B, T, N, 128])."""
    bsz, t, ch = x.shape
    n = at.shape[0]
    nt, nc = t // tb, ch // cb
    f32 = jnp.float32
    wide = pl.BlockSpec((None, tb, cb), lambda b, i, j: (b, nt - 1 - i, j))
    col = pl.BlockSpec((None, tb, n, _LANES), lambda b, i, j: (b, nt - 1 - i, 0, 0))
    return pl.pallas_call(
        _bwd_kernel,
        out_shape=(jax.ShapeDtypeStruct((bsz, t, ch), x.dtype),
                   jax.ShapeDtypeStruct((bsz, t, ch), f32),
                   jax.ShapeDtypeStruct((bsz, nc, n, cb), f32),
                   jax.ShapeDtypeStruct((bsz, t, n, _LANES), f32),
                   jax.ShapeDtypeStruct((bsz, t, n, _LANES), f32)),
        grid=(bsz, nt, nc),
        in_specs=[wide, wide, pl.BlockSpec((n, cb), lambda b, i, j: (0, j)), col, col, wide,
                  pl.BlockSpec((None, None, n, cb), lambda b, i, j: (nt - 1 - i, b, 0, j))],
        out_specs=(wide, wide, pl.BlockSpec((None, nc, n, cb), lambda b, i, j: (b, 0, 0, 0)),
                   col, col),
        scratch_shapes=[pltpu.VMEM((nc, n, cb), f32),    # every channel block's ds
                        pltpu.VMEM((tb, n, cb), f32),    # the state before each position
                        pltpu.VMEM((tb, n, cb), f32),    # each position's decay
                        pltpu.VMEM((tb, cb), f32),       # dt x
                        pltpu.VMEM((tb, cb), f32),       # dy
                        pltpu.VMEM((tb, cb), f32),       # d(dt x)
                        pltpu.VMEM((tb, cb), f32)],      # d(dt) through the decay
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="selective_scan_bwd",
    )(x, dt, at, b4, c4, dy, enter)


# ------------------------------------------------------------------ the op


def _columns(v):
    """[B, T, N] -> [B, T, N, 128] float32: a position's column on every lane."""
    return jnp.broadcast_to(v.astype(jnp.float32)[..., None], (*v.shape, _LANES))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _scan(x, dt, a, b, c, tb, cb, interpret):
    return _scan_fwd(x, dt, a, b, c, tb, cb, interpret)[0]


def _scan_fwd(x, dt, a, b, c, tb, cb, interpret):
    y, enter = _forward(x, dt, a.T, _columns(b), _columns(c), tb, cb, interpret)
    return y, (x, dt, a, b, c, enter)


def _scan_bwd(tb, cb, interpret, kept, dy):
    x, dt, a, b, c, enter = kept
    dx, ddt, da, db, dc = _backward(x, dt, a.T, _columns(b), _columns(c), dy, enter,
                                    tb, cb, interpret)
    da = jnp.sum(da, axis=0).transpose(0, 2, 1).reshape(a.shape)   # [C / cb, N, cb] -> [C, N]
    return (dx, ddt, da, jnp.sum(db, axis=-1).astype(b.dtype),
            jnp.sum(dc, axis=-1).astype(c.dtype))


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(x, dt, a, b, c, *, time_block: int, channel_block: int,
                   interpret: bool = False):
    """x [B, T, C], dt [B, T, C] float32, a [C, N] float32, b and c [B, T, N]
    -> y [B, T, C] in x's type, differentiable in all five, by the kernels at
    the blocks given (`blocks` chooses them; T a multiple of the one, C of the
    other)."""
    return _scan(x, dt, a, b, c, time_block, channel_block, interpret)
