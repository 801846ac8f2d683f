"""Pallas TPU kernel: block-banded ragged consensus attention that reads
k/v PAGES in place.

The ragged serving path packs mixed-resolution rows page-aligned onto a
flat [T, L, d] token axis; consensus attention restricts each token to
its own row's page band of W = full-res-pages x page_tokens slots
(serve/early_exit.py). The jnp reference routes still build a duplicated
k/v working set per iteration — W column states per token (windowed) or
per page (banded). This kernel removes the copy entirely: one program
per (query page p, band page j) streams the band's k/v pages straight
from the flat state via a scalar-prefetched band-start map, with a
flash-style ONLINE softmax over j — the only per-program residency is
one [page_tokens, L, d] q/k/v tile and the f32 VMEM accumulators. Peak
ragged working set drops to the pages themselves, which is what lets
the largest admitted ragged signature per chip grow (--banded-ab).

Mask semantics are the reference routes' exactly: slots past the row's
real length hard-masked to -3e38, the self slot soft-masked to -5e-4
when attend_self=False, both computed in-register from iota + the
prefetched per-page (band start, row length) scalars. Rows occupy whole
pages with page-aligned starts, so both scalars are constant within a
page — the precondition the banded decomposition rests on.

Parity contract: kernel-parity TOLERANCE against the jnp banded route
(the fused dense route's contract — an online softmax reorders the
reduction), NOT the bitwise bar; the jnp banded route is the one proven
bitwise against the windowed gather at threshold 0. Off-TPU (and not
interpret=True) the wrapper falls back to the jnp banded reference, so
CPU serving keeps the bitwise contract end to end.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from glom_tpu.utils.helpers import TOKEN_ATTEND_SELF_VALUE

_NEG_MAX = float(jnp.finfo(jnp.float32).min)


def _banded_kernel(
    band_ref,   # [P] int32 scalar-prefetch: band's first page per page
    len_ref,    # [P] int32 scalar-prefetch: row length per page
    q_ref,      # [1, pt, L, d] query page
    kv_ref,     # [1, pt, L, d] band page j (k and v read from ONE ref)
    o_ref,      # [1, pt, L, d] output page
    m_ref,      # [pt, L, 1] f32 scratch: running max
    l_ref,      # [pt, L, 1] f32 scratch: running sum
    acc_ref,    # [pt, L, d] f32 scratch: running weighted values
    *,
    pt: int,
    n_band: int,
    attend_self: bool,
    scale: float,
):
    p = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, _NEG_MAX, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    q = q_ref[0].astype(jnp.float32)                       # [pt, L, d]
    kv = kv_ref[0].astype(jnp.float32)                     # [pt, L, d]
    # The one consensus k convention: q/v raw, k L2-normalized
    # (helpers.l2norm — x / max(||x||, eps)).
    norm = jnp.sqrt(jnp.sum(kv * kv, axis=-1, keepdims=True))
    k = kv / jnp.maximum(norm, 1e-12)

    # s[l, q, u] = q[q, l, :] . k[u, l, :]
    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((1,), (1,))),
        preferred_element_type=jnp.float32,
    ) * scale                                              # [L, pt, pt]

    u = jax.lax.broadcasted_iota(jnp.int32, (pt, pt), 1)   # k slot in page
    qq = jax.lax.broadcasted_iota(jnp.int32, (pt, pt), 0)  # q slot in page
    w_slot = j * pt + u                                    # band offset
    if not attend_self:
        # Self slot: band-global position == query's flat token index.
        self_slot = (band_ref[p] + j) * pt + u == p * pt + qq
        s = jnp.where(self_slot[None], TOKEN_ATTEND_SELF_VALUE, s)
    s = jnp.where((w_slot < len_ref[p])[None], s, _NEG_MAX)

    s = jnp.transpose(s, (1, 0, 2))                        # [pt, L, pt]
    m_prev = m_ref[...][..., 0]                            # [pt, L]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    corr = jnp.exp(m_prev - m_new)
    pexp = jnp.exp(s - m_new[..., None])                   # [pt, L, pt]
    l_ref[...] = (
        l_ref[...][..., 0] * corr + jnp.sum(pexp, axis=-1)
    )[..., None]
    pv = jax.lax.dot_general(
        pexp, kv, (((2,), (0,)), ((1,), (1,))),
        preferred_element_type=jnp.float32,
    )                                                      # [L, pt, d]
    acc_ref[...] = (
        acc_ref[...] * corr[..., None] + jnp.transpose(pv, (1, 0, 2))
    )
    m_ref[...] = m_new[..., None]

    @pl.when(j == n_band - 1)
    def _done():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def banded_ragged_consensus(
    levels: jnp.ndarray,
    *,
    row_start: jnp.ndarray,
    row_len: jnp.ndarray,
    window: int,
    page_tokens: int,
    attend_self: bool = False,
    interpret: bool = False,
) -> jnp.ndarray:
    """Drop-in replacement for banded_ragged_consensus_attention
    (serve/early_exit.py) running the streaming Pallas kernel on TPU (or
    anywhere under interpret=True); falls back to the jnp banded route
    otherwise, which keeps CPU serving on the bitwise contract."""
    from glom_tpu.serve.early_exit import banded_ragged_consensus_attention

    on_tpu = jax.devices()[0].platform == "tpu"
    if not (on_tpu or interpret):
        return banded_ragged_consensus_attention(
            levels, row_start=row_start, row_len=row_len, window=window,
            page_tokens=page_tokens, attend_self=attend_self,
        )
    T, L, d = levels.shape
    pt = page_tokens
    if T % pt or window % pt:
        raise ValueError(
            f"banded consensus needs page-aligned shapes: T={T}, "
            f"window={window}, page_tokens={pt}"
        )
    P = T // pt
    n_band = window // pt
    band_page0 = (row_start[::pt] // pt).astype(jnp.int32)  # [P]
    len_page = row_len[::pt].astype(jnp.int32)              # [P]
    pages = levels.reshape(P, pt, L, d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(P, n_band),
        in_specs=[
            pl.BlockSpec((1, pt, L, d), lambda p, j, band, ln: (p, 0, 0, 0)),
            pl.BlockSpec(
                (1, pt, L, d),
                lambda p, j, band, ln: (
                    jnp.minimum(band[p] + j, P - 1), 0, 0, 0
                ),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, pt, L, d), lambda p, j, band, ln: (p, 0, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((pt, L, 1), jnp.float32),
            pltpu.VMEM((pt, L, 1), jnp.float32),
            pltpu.VMEM((pt, L, d), jnp.float32),
        ],
    )
    kernel = partial(
        _banded_kernel,
        pt=pt, n_band=n_band, attend_self=attend_self,
        scale=d ** -0.5,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((P, pt, L, d), levels.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        interpret=interpret,
        name="consensus_banded_fwd",
    )(band_page0, len_page, pages, pages)
    return out.reshape(T, L, d)
