"""Pallas TPU kernels: causal, optionally windowed, grouped-query attention
by a blockwise online softmax, forward and backward, for the language
models' `hybrid_lm.blocked_attention`.

    out[t] = sum_j softmax_j(q[t] . k[j] * D^-1/2) v[j],  t - window < j <= t

The scores of a (query tile, key tile) pair live and die in VMEM: float32
scores from the MXU, float32 running maximum `m`, sum `l` (128 lanes wide
until the tile's last pair) and accumulator in scratch, probabilities cast
to the operands' type for the second product (float32 accumulation), which
is what the XLA loop (`hybrid_lm._attend`) computes a whole query block at a
time through HBM.

Layout inside: heads before positions, q [B, G, R, T, D], k [B, G, T, D], v
[B, Gv, T, Dv] (G KV heads of R query heads each; KV head g reads value head
g // (G / Gv): SambaY's pairs share their values, and an index map does what
a broadcast copy would). The R query heads of a KV head fold into the query
tile's rows, so a key tile is loaded once a group; where a whole group's dq
would not stay resident in the backward (`head_parts`), the group folds in
equal parts, each a grid row of its own that reads the same KV head by the
same kind of index map, and the parts' dk are added outside.

Shapes served: T a multiple of 128, D and Dv multiples of 64, and some equal
part of a group whose dq [R / parts, T, D in whole registers of 128 lanes]
float32 fits DQ_RESIDENT_BYTES twice. At D = 128 that is every R to T = 32,768
(T = 8,192: R = 4 and R = 6 whole, R = 8 in two parts of 4; T = 16,384: R = 4
in two parts, R = 6 in two parts of 3, R = 8 in four); at T = 65,536 and beyond
a single head's dq is 64 MiB and the shape goes to the XLA loop, as does any
length that is no multiple of 128 and any head size that is no multiple of 64.
At D = 192, Dv = 128 (a latent attention's 128 + 64 key dimensions: one query
head a KV head) a head's dq is 256 lanes wide, 32 MiB twice at T = 16,384,
and is served to T = 16,384.

A second mask kind, `Aligned(window, chunk, length)` in `window`'s place, is
EVA attention's (`models/evabyte.py`): the keys are two segments laid end to
end, `length` positions' own keys and then `length / chunk` summary keys, one a
chunk of `chunk` positions, under one softmax. A query at t in the aligned
window n = t // window sees the keys n window .. t of its own window and the
summaries of every chunk of the windows before it, j chunk < n window: not a
sliding window, and a summary never expires. The kernels are the same two: a
key tile is a tile of one segment or the other (`tiles` takes key tiles that
divide both), and dk and dv of the summary rows come back with the keys'.

`schedule` is the one place that says which (query tile, key tile) pairs
exist: the grids are its steps (scalar-prefetched, so a pair no query of
the tile can see is neither a grid step nor a DMA), its flags say where a
tile's accumulators start and end and which pairs the diagonal or the
window's edge crosses (only those are masked), and `key_blocks` counts it
for the model's counters.

Backward, one sweep (`consensus_update_bwd_onesweep`'s shape): for each key
tile the query tiles that see it, scores transposed ([keys, queries]: the
saved log-sum-exp and `delta = rowsum(do * o)` are rows, lane-dense),
`p` rebuilt from the log-sum-exp, `dv += p^T do`, `dp = do v^T`, `ds = p *
(dp - delta)`, `dk += ds^T q` in scratch, and `dq += ds k` into a float32
block of the whole group that stays in VMEM until the group ends: five
products a pair, the scores computed once. What the custom VJP keeps is q,
k, v, the output and the log-sum-exp as rows, [B, G, R, T] float32: the
forward kernel writes a column, [..., T, 1], whose last dimension HBM pads to
128 lanes (537 MB for a Laguna sliding layer of two sequences where the rows
are 4 MB), and the column is sliced as it leaves the kernel, so that it is
never a residual. The output and the rows carry the checkpoint names
KEPT_OUTPUT and KEPT_LSE: a `jax.checkpoint` whose policy saves those two
names (`hybrid_lm.run_stack`'s) recomputes q, k and v for the backward kernel
and reads the forward kernel's results where it would have run it again.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

QUERY_TILES = (512, 256, 128)
KEY_TILES = (1024, 512, 256, 128)
QUERY_ROWS = 1024  # a query tile's rows, the group's R heads folded in
# The backward keeps the dq [R, T, D in whole 128-lane registers] float32 of the query heads
# folded into one grid row in VMEM, twice (the pipeline's two buffers); past
# this it does not fit beside the score tiles, and `head_parts` folds the group
# in parts, or, where one head's is too much, `tiles` sends the shape to the
# XLA loop.
DQ_RESIDENT_BYTES = 48 * 1024 * 1024
VMEM_LIMIT_BYTES = 100 * 1024 * 1024
# The checkpoint names of what the forward kernel made and the backward reads:
# the output, head-major in the operands' type, and the log-sum-exp rows.
KEPT_OUTPUT = "attn_flash_out"
KEPT_LSE = "attn_flash_lse"
_NEG = -1e30  # a masked score: finite, so that exp(m - m) of a row not yet seen is no NaN
_FIRST, _LAST, _MASKED = 1, 2, 4
_LANES = 128
_NT = (((1,), (1,)), ((), ()))  # a [m, d] x b [n, d] -> [m, n]
_TN = (((0,), (0,)), ((), ()))  # a [k, m] x b [k, n] -> [m, n]


class Aligned(NamedTuple):
    """EVA attention's mask over keys [length own keys; length / chunk
    summaries]: see the module docstring. Whole windows and whole chunks."""
    window: int
    chunk: int
    length: int

    @property
    def summaries(self) -> int:
        return self.length // self.chunk


def on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


# ------------------------------------------------------------------- schedule


def head_parts(t: int, r: int, d: int):
    """In how many equal parts a KV head's R query heads fold into query
    tiles: the fewest whose dq stays resident (1 where the whole group's
    does: 2 * R * T * lanes(D) * 4 bytes <= DQ_RESIDENT_BYTES with lanes(D) =
    D rounded up to whole registers of 128 lanes, which is what VMEM holds of
    a row: 128 for D = 64 and 128, 256 for D = 192; an equality at R = 6, D =
    128, T = 8,192), or None where one head's does not."""
    lanes = -(-d // _LANES) * _LANES
    return next((parts for parts in range(1, r + 1) if r % parts == 0
                 and 2 * (r // parts) * t * lanes * 4 <= DQ_RESIDENT_BYTES), None)


def tiles(t: int, r: int, d: int, dv: int, window=None):
    """(query tile, key tile) for T positions, R query heads a KV head and
    head sizes D and Dv, or None where the kernels do not serve the shape:
    T a multiple of 128, D and Dv of 64, and some part of the group's dq
    resident (`head_parts`; the module docstring lists what that serves). The
    query tile is the largest that keeps a part's folded rows at QUERY_ROWS. The
    key tile is the largest there is, and under a window the largest no
    longer than the window: the forward takes its row maxima, which reduce
    across lanes, once a (row, key tile) whatever the tile's width (a SambaY
    full-length layer's forward: 8.2 ms at key tiles of 512, 6.8 at 1,024,
    8.1 at 2,048, where the tiles the diagonal crosses waste more than the
    maxima save; PR 32's builder's chip runs), and a key tile past the window is
    keys that no query of the tile sees."""
    parts = head_parts(t, r, d)
    if t % 128 or d % 64 or dv % 64 or parts is None:
        return None
    r //= parts
    if isinstance(window, Aligned):
        return _aligned_tiles(r, window)
    reach = t if window is None else max(window, 128)
    tq = next(tile for tile in QUERY_TILES if t % tile == 0 and (
        r * tile <= QUERY_ROWS or tile == QUERY_TILES[-1]))
    tk = next(tile for tile in KEY_TILES if t % tile == 0 and tile <= reach)
    return tq, tk


def _aligned_tiles(r: int, mask: Aligned):
    """`tiles` under an `Aligned` mask: a query tile lies in one window and a
    key tile in one window or among the summaries, so both divide the window
    and the key tile the summaries' count too (window and chunk powers of two:
    the kernels take a position's window by a mask of bits). The key tile is
    the query tile's size at most: a window's tiles below its diagonal are
    then whole, and only the diagonal's own are masked (at 512 the kernels
    visit 1.11 of the key blocks the mask needs at 16,384 positions, at 1,024
    1.41)."""
    w, c, t = mask
    if w & (w - 1) or c & (c - 1) or t % w or w % c:
        return None
    tq = next((tile for tile in QUERY_TILES if w % tile == 0 and (
        r * tile <= QUERY_ROWS or tile == QUERY_TILES[-1])), None)
    tk = tq and next((tile for tile in KEY_TILES if tile <= tq and w % tile == 0
                      and mask.summaries % tile == 0), None)
    return (tq, tk) if tk else None


@functools.lru_cache(maxsize=None)
def schedule(t: int, tq: int, tk: int, window=None):
    """The (query tile i, key tile j) pairs in which some query sees some key
    (j <= t and, with a window, j > t - window), as int32 arrays: (i, j, flags)
    in the forward's order (by i, then j) and (j, i, flags) in the backward's
    (by j, then i). Flags: _FIRST and _LAST of the run of pairs that share
    the order's outer tile, _MASKED where some pair of positions in the tile
    pair is not seen."""
    pairs = []
    for i in range(t // tq):
        q_lo, q_hi = i * tq, (i + 1) * tq - 1
        if isinstance(window, Aligned):
            pairs += [(i, j, flag) for j, flag in _aligned_key_tiles(q_lo, q_hi, tk, window)]
            continue
        first_key = 0 if window is None else max(0, q_lo - window + 1)
        for j in range(first_key // tk, q_hi // tk + 1):
            k_lo, k_hi = j * tk, (j + 1) * tk - 1
            masked = k_hi > q_lo or (window is not None and k_lo <= q_hi - window)
            pairs.append((i, j, _MASKED if masked else 0))

    def ordered(outer):
        rows = sorted(pairs, key=lambda p: (p[outer], p[1 - outer]))
        out = np.asarray(rows, np.int32).reshape(-1, 3)
        run = out[:, outer]
        out[:, 2] |= np.where(np.r_[True, run[1:] != run[:-1]], _FIRST, 0).astype(np.int32)
        out[:, 2] |= np.where(np.r_[run[1:] != run[:-1], True], _LAST, 0).astype(np.int32)
        return tuple(np.ascontiguousarray(out[:, c]) for c in (outer, 1 - outer, 2))

    return ordered(0), ordered(1)


def _aligned_key_tiles(q_lo: int, q_hi: int, tk: int, mask: Aligned):
    """(key tile, _MASKED or 0) for the queries q_lo..q_hi under `mask`: the
    tiles of own keys from the first query's window's start to the last
    query, then the summary tiles (numbered on from length / tk) that hold a
    chunk of a window before the last query's. A tile is masked where some
    query of the tile does not see some key of it."""
    w, c, t = mask
    first, last = q_lo // w * w, q_hi // w * w     # the windows' starts
    for j in range(first // tk, q_hi // tk + 1):
        k_lo, k_hi = j * tk, (j + 1) * tk - 1
        yield j, _MASKED if k_hi > q_lo or k_lo < last else 0
    for j in range(-(-(last // c) // tk)):         # summaries 0 .. last / c - 1 are seen by some
        yield t // tk + j, _MASKED if (j + 1) * tk > first // c else 0


def key_blocks(t: int, tq: int, tk: int, window=None, block: int = 128) -> int:
    """Key blocks of `block` keys the kernels visit, summed over the query
    tiles: the schedule's steps, in the model's unit."""
    return len(schedule(t, tq, tk, window)[0][0]) * tk // block


def aligned_key_blocks(tq: int, tk: int, mask: Aligned, block: int = 128):
    """`key_blocks` under an `Aligned` mask, by segment: (blocks of own keys,
    blocks of summary keys)."""
    kj = schedule(mask.length, tq, tk, mask)[0][1]
    own = int(np.sum(kj < mask.length // tk))
    return own * tk // block, (len(kj) - own) * tk // block


def _seen(q_pos, k_pos, window):
    if isinstance(window, Aligned):
        w, c, t = window
        start = q_pos & ~(w - 1)   # the window's start: `tiles` takes powers of two alone
        own = (k_pos < t) & (k_pos <= q_pos) & (k_pos >= start)
        return own | ((k_pos >= t) & ((k_pos - t) * c < start))
    seen = k_pos <= q_pos
    if window is not None:
        seen &= k_pos > q_pos - window
    return seen


# -------------------------------------------------------------------- forward


def _fwd_kernel(qi_ref, kj_ref, flag_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_sc, l_sc, acc_sc, *, scale, window, tq, tk):
    """One (query tile, key tile) pair: q_ref [1, 1, R, tq, D], k_ref [1, 1,
    tk, D], v_ref [1, 1, tk, Dv] -> at the tile's last pair o_ref [1, 1, R,
    tq, Dv] and lse_ref [1, 1, R, tq, 1]."""
    step = pl.program_id(2)
    i, j, flag = qi_ref[step], kj_ref[step], flag_ref[step]
    r = q_ref.shape[2]
    rows = r * tq
    f32 = jnp.float32

    @pl.when(flag & _FIRST != 0)
    def _():
        m_sc[...] = jnp.full_like(m_sc, _NEG)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def update(masked: bool):
        q = q_ref[0, 0].reshape(rows, q_ref.shape[-1])
        v = v_ref[0, 0]
        # the products q . k as they leave the MXU: the maximum is taken of
        # them and D^-1/2 (positive) applied inside the exponent, one pass over
        # the tile fewer than scaling it first
        s = jax.lax.dot_general(q, k_ref[0, 0], _NT, preferred_element_type=f32)
        if masked:
            # positions from the tile's coordinates, on a column and a row
            row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
            q_pos = i * tq + (row & (tq - 1))
            k_pos = j * tk + jax.lax.broadcasted_iota(jnp.int32, (1, tk), 1)
            s = jnp.where(_seen(q_pos, k_pos, window), s, _NEG)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp((m_prev - m_new) * scale)
        p = jnp.exp((s - m_new) * scale)
        # the row sums stay 128 lanes wide until the tile's last pair: adding
        # a key tile's lane groups is elementwise, a sum across lanes is not
        l_sc[...] = alpha * l_sc[...] + sum(
            p[:, c:c + _LANES] for c in range(0, tk, _LANES))
        acc_sc[...] = alpha * acc_sc[...] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=f32)
        m_sc[...] = m_new

    pl.when(flag & _MASKED != 0)(functools.partial(update, True))
    pl.when(flag & _MASKED == 0)(functools.partial(update, False))

    @pl.when(flag & _LAST != 0)
    def _():
        l = jnp.sum(l_sc[...], axis=1, keepdims=True)
        o_ref[0, 0] = (acc_sc[...] / l).reshape(r, tq, -1).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_sc[...] * scale + jnp.log(l)).reshape(r, tq, 1)


def _forward(q, k, v, window, tq, tk, interpret):
    """Head-major q [B, G, R, T, D], k [B, Gk, T, D], v [B, Gv, T, Dv] (G a
    multiple of Gk and of Gv: grid row h reads key head h // (G / Gk)) ->
    (o [B, G, R, T, Dv] in q's type, log-sum-exp [B, G, R, T, 1] float32)."""
    bsz, g, r, t, d = q.shape
    dv = v.shape[-1]
    k_share, share = g // k.shape[1], g // v.shape[1]
    qi, kj, flags = schedule(t, tq, tk, window)[0]
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=d ** -0.5, window=window, tq=tq, tk=tk),
        out_shape=(jax.ShapeDtypeStruct((bsz, g, r, t, dv), q.dtype),
                   jax.ShapeDtypeStruct((bsz, g, r, t, 1), f32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(bsz, g, len(qi)),
            in_specs=[
                pl.BlockSpec((1, 1, r, tq, d), lambda b, h, s, qi, kj, fl: (b, h, 0, qi[s], 0)),
                pl.BlockSpec((1, 1, tk, d),
                             lambda b, h, s, qi, kj, fl: (b, h // k_share, kj[s], 0)),
                pl.BlockSpec((1, 1, tk, dv),
                             lambda b, h, s, qi, kj, fl: (b, h // share, kj[s], 0)),
            ],
            out_specs=(
                pl.BlockSpec((1, 1, r, tq, dv), lambda b, h, s, qi, kj, fl: (b, h, 0, qi[s], 0)),
                pl.BlockSpec((1, 1, r, tq, 1), lambda b, h, s, qi, kj, fl: (b, h, 0, qi[s], 0)),
            ),
            scratch_shapes=[
                pltpu.VMEM((r * tq, 1), f32),   # m
                pltpu.VMEM((r * tq, _LANES), f32),   # l, by lane
                pltpu.VMEM((r * tq, dv), f32),  # accumulator
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="attn_flash_fwd",
    )(qi, kj, flags, q, k, v)


# ------------------------------------------------------------------- backward


def _bwd_kernel(kj_ref, qi_ref, flag_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, dk_sc, dv_sc, *, scale, window, tq, tk):
    """One (key tile, query tile) pair, scores transposed [tk, R tq]: q_ref
    [1, 1, R, tq, D], do_ref [1, 1, R, tq, Dv], lse_ref and delta_ref [1, 1,
    1, 1, R tq] -> dq_ref [1, 1, R, T, D] float32 (the group's, resident),
    and at the key tile's last pair dk_ref [1, 1, tk, D], dv_ref [1, 1, tk,
    Dv]."""
    step = pl.program_id(2)
    j, i, flag = kj_ref[step], qi_ref[step], flag_ref[step]
    r = q_ref.shape[2]
    rows = r * tq
    f32 = jnp.float32

    @pl.when(step == 0)
    def _():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    @pl.when(flag & _FIRST != 0)
    def _():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    def update(masked: bool):
        q = q_ref[0, 0].reshape(rows, q_ref.shape[-1])
        do = do_ref[0, 0].reshape(rows, do_ref.shape[-1])
        k, v = k_ref[0, 0], v_ref[0, 0]
        st = jax.lax.dot_general(k, q, _NT, preferred_element_type=f32) * scale
        if masked:
            col = jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
            q_pos = i * tq + (col & (tq - 1))
            k_pos = j * tk + jax.lax.broadcasted_iota(jnp.int32, (tk, 1), 0)
            st = jnp.where(_seen(q_pos, k_pos, window), st, _NEG)
        pt = jnp.exp(st - lse_ref[0, 0, 0])
        dv_sc[...] += jnp.dot(pt.astype(do.dtype), do, preferred_element_type=f32)
        dpt = jax.lax.dot_general(v, do, _NT, preferred_element_type=f32)
        dst = (pt * (dpt - delta_ref[0, 0, 0])).astype(q.dtype)
        dk_sc[...] += jnp.dot(dst, q, preferred_element_type=f32)
        dq = jax.lax.dot_general(dst, k, _TN, preferred_element_type=f32)
        at = pl.ds(pl.multiple_of(i * tq, tq), tq)
        dq_ref[0, 0, :, at, :] += dq.reshape(r, tq, -1)

    pl.when(flag & _MASKED != 0)(functools.partial(update, True))
    pl.when(flag & _MASKED == 0)(functools.partial(update, False))

    @pl.when(flag & _LAST != 0)
    def _():
        dk_ref[0, 0] = (dk_sc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_sc[...].astype(dv_ref.dtype)


def _tile_rows(x, tq):
    """[B, G, R, T] -> [B, G, T / tq, 1, R tq]: a query tile's folded rows
    (head r's tq positions, then head r + 1's) laid along the lanes."""
    bsz, g, r, t = x.shape
    return x.reshape(bsz, g, r, t // tq, tq).swapaxes(2, 3).reshape(bsz, g, t // tq, 1, r * tq)


def _backward(q, k, v, o, lse, do, window, tq, tk, interpret):
    """The three gradients, head-major, from the forward's output and its
    log-sum-exp rows [B, G, R, T]: dq in float32 and unscaled (the caller
    scales it as it casts), and dk and dv for every grid row (the caller adds
    those that share a key head or a value head)."""
    bsz, g, r, t, d = q.shape
    dv = v.shape[-1]
    k_share, share = g // k.shape[1], g // v.shape[1]
    kj, qi, flags = schedule(t, tq, tk, window)[1]
    f32 = jnp.float32
    delta = jnp.sum(do.astype(f32) * o.astype(f32), axis=-1)
    q_spec = lambda last: pl.BlockSpec(
        (1, 1, r, tq, last), lambda b, h, s, kj, qi, fl: (b, h, 0, qi[s], 0))
    row_spec = pl.BlockSpec((1, 1, 1, 1, r * tq), lambda b, h, s, kj, qi, fl: (b, h, qi[s], 0, 0))
    k_spec = lambda last: pl.BlockSpec(
        (1, 1, tk, last), lambda b, h, s, kj, qi, fl: (b, h, kj[s], 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale=d ** -0.5, window=window, tq=tq, tk=tk),
        out_shape=(jax.ShapeDtypeStruct((bsz, g, r, t, d), f32),
                   jax.ShapeDtypeStruct((bsz, g, k.shape[2], d), k.dtype),
                   jax.ShapeDtypeStruct((bsz, g, k.shape[2], dv), v.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(bsz, g, len(kj)),
            in_specs=[
                q_spec(d),
                pl.BlockSpec((1, 1, tk, d),
                             lambda b, h, s, kj, qi, fl: (b, h // k_share, kj[s], 0)),
                pl.BlockSpec((1, 1, tk, dv),
                             lambda b, h, s, kj, qi, fl: (b, h // share, kj[s], 0)),
                q_spec(dv),   # do
                row_spec,     # log-sum-exp
                row_spec,     # delta
            ],
            out_specs=(
                pl.BlockSpec((1, 1, r, t, d), lambda b, h, s, kj, qi, fl: (b, h, 0, 0, 0)),
                k_spec(d),
                k_spec(dv),
            ),
            scratch_shapes=[pltpu.VMEM((tk, d), f32), pltpu.VMEM((tk, dv), f32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="attn_flash_bwd_onesweep",
    )(kj, qi, flags, q, k, v, do, _tile_rows(lse, tq), _tile_rows(delta, tq))


# ------------------------------------------------------------------ the op


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, window, tq, tk, interpret):
    return _forward(q, k, v, window, tq, tk, interpret)[0]


def _flash_fwd(q, k, v, window, tq, tk, interpret):
    o, lse = _forward(q, k, v, window, tq, tk, interpret)
    o = checkpoint_name(o, KEPT_OUTPUT)
    return o, (q, k, v, o, checkpoint_name(lse[..., 0], KEPT_LSE))


def _flash_bwd(window, tq, tk, interpret, kept, do):
    q, k, v, o, lse = kept
    dq, dk, dv = _backward(q, k, v, o, lse, do, window, tq, tk, interpret)
    if isinstance(window, Aligned):
        # no query sees a summary of the last window: a key tile of those alone is no grid
        # step, and its rows of dk and dv are never written
        w, c, t = window
        seen = (jnp.arange(k.shape[2]) < t + (t - 1) // w * w // c)[:, None]
        dk, dv = jnp.where(seen, dk, 0), jnp.where(seen, dv, 0)

    def shared(dx, x):   # grid rows that share a key head or a value head
        if dx.shape == x.shape:
            return dx
        bsz, heads, t, width = x.shape
        return jnp.sum(dx.reshape(bsz, heads, -1, t, width).astype(jnp.float32),
                       axis=2).astype(x.dtype)

    return (dq * q.shape[-1] ** -0.5).astype(q.dtype), shared(dk, k), shared(dv, v)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, window=None, *, tq: int, tk: int, interpret: bool = False):
    """q [B, T, G, R, D], k [B, T, G, D], v [B, T, Gv, Dv] (G a multiple of
    Gv) -> [B, T, G, R, Dv], differentiable in all three, by the kernels at
    the tiles given (`tiles` chooses them; T a multiple of both). A group
    folds into the query tiles in `head_parts` parts. Under an `Aligned` mask
    in `window`'s place k and v hold T + T / chunk rows, the summaries after
    the positions' own."""
    bsz, t, g, r, d = q.shape
    parts = head_parts(t, r, d)
    o = _flash(q.transpose(0, 2, 3, 1, 4).reshape(bsz, g * parts, r // parts, t, d),
               k.swapaxes(1, 2), v.swapaxes(1, 2), window, tq, tk, interpret)
    return o.reshape(bsz, g, r, t, -1).transpose(0, 3, 1, 2, 4)
