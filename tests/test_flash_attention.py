"""The blockwise attention kernels (kernels/flash_attention.py) in interpret
mode on the CPU, against the XLA loop they replace where the shapes tile
(`hybrid_lm.blocked_attention`'s other branch, which tests/test_sambay.py
holds to whole [T, T] masked scores): the output and the three gradients at
both cells' head shapes; the schedule, which is the grids, the masks' flags
and the model's counters at once, against the masks themselves; the shapes
that fall back; a SambaY stage whose attentions run the kernels; and the
kernels compiled for a described v5e at the cells' real sizes.

`blocked_attention` has no option that says where it runs: it asks
`flash_attention.on_tpu()`. The tests that want its kernel branch on the CPU
answer for the chip and hand it the kernels in interpret mode (`kernels_here`).

Tolerances: in float32 the kernels differ from the loop by summation order
(2e-6 of an array's norm); in bfloat16 the loop rounds normalised
probabilities and the kernels unnormalised ones before the second product,
and the backward pass rounds `ds` once where XLA's rounds it in the product
(under 0.4% measured, 1% allowed).
"""

import collections
import contextlib
import dataclasses
import functools
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights_sambay as ws
from glom_tpu.kernels import flash_attention as fa
from glom_tpu.models import evabyte, hybrid_lm, laguna, sambay
from glom_tpu.utils.config import EvaByteConfig
from glom_tpu.utils.presets import get_preset

# (KV heads, value heads, query heads a KV head, D, Dv): SambaY's pairs share
# their values; the other model's one group of four,
# their own group of four; Kimi Linear's latent attention, one query head a KV head with 128 +
# 64 key dimensions against 128 of values
HEADS = {"sambay_d64_dv128_r2": (2, 1, 2, 64, 128), "nemotron_d128_dv128_r4": (1, 1, 4, 128, 128),
         "kimi_latent_d192_dv128_r1": (3, 3, 1, 192, 128)}
TILE = 128


def rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def inputs(t, heads, dtype, seed=0, bsz=1):
    g, gv, r, d, dv = heads
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (bsz, t, g, r, d), dtype),
            jax.random.normal(ks[1], (bsz, t, g, d), dtype),
            jax.random.normal(ks[2], (bsz, t, gv, dv), dtype),
            jax.random.normal(ks[3], (bsz, t, g, r, dv), dtype))


def out_and_grads(f, q, k, v, cot):
    out, pull = jax.vjp(f, q, k, v)
    return (out,) + pull(cot)


@pytest.fixture
def kernels_here(monkeypatch):
    """`blocked_attention` takes its kernel branch on the CPU: the platform
    answers as the chip's does, and the kernels run in interpret mode."""
    monkeypatch.setattr(fa, "flash_attention", functools.partial(fa.flash_attention, interpret=True))
    monkeypatch.setattr(fa, "on_tpu", lambda: True)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("query_tiles", [2, 5])
@pytest.mark.parametrize("window", [None, 40, 200],
                         ids=["full", "window_under_a_tile", "window_no_multiple_of_a_tile"])
@pytest.mark.parametrize("heads", list(HEADS.values()), ids=list(HEADS))
def test_the_kernels_match_the_xla_loop_forward_and_in_every_gradient(
        heads, window, query_tiles, dtype):
    q, k, v, cot = inputs(query_tiles * TILE, heads, dtype)
    got = jax.jit(lambda *a: out_and_grads(
        lambda q, k, v: fa.flash_attention(q, k, v, window, tq=TILE, tk=TILE, interpret=True),
        *a))(q, k, v, cot)
    want = jax.jit(lambda *a: out_and_grads(
        lambda q, k, v: hybrid_lm.blocked_attention(q, k, v, window)[0], *a))(q, k, v, cot)
    tol = 2e-6 if dtype == jnp.float32 else 1e-2
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert rel(a, b) < tol, (name, rel(a, b))


@pytest.mark.parametrize("tq, tk", [(128, 256), (256, 128)])
def test_query_and_key_tiles_of_different_sizes(tq, tk):
    q, k, v, cot = inputs(768, HEADS["sambay_d64_dv128_r2"], jnp.float32, seed=3, bsz=2)
    for window in (None, 300):
        got = out_and_grads(
            lambda q, k, v: fa.flash_attention(q, k, v, window, tq=tq, tk=tk, interpret=True),
            q, k, v, cot)
        want = out_and_grads(lambda q, k, v: hybrid_lm.blocked_attention(q, k, v, window)[0],
                             q, k, v, cot)
        assert max(rel(a, b) for a, b in zip(got, want)) < 2e-6


# (KV heads, value heads, query heads a KV head, D, Dv): Laguna's sliding and
# full layers, 8 and 6 query heads a KV head of 128
LAGUNA_HEADS = {"laguna_sliding_r8": (2, 2, 8, 128, 128), "laguna_full_r6": (2, 2, 6, 128, 128)}


@pytest.mark.parametrize("parts", [1, 2], ids=["group_whole", "group_in_two_parts"])
@pytest.mark.parametrize("window", [None, 200], ids=["full", "window"])
@pytest.mark.parametrize("heads", list(LAGUNA_HEADS.values()), ids=list(LAGUNA_HEADS))
def test_a_group_of_eight_or_six_heads_of_128_whole_and_folded_in_parts(
        heads, window, parts, monkeypatch):
    """Against `_attend` over the whole length, forward and in every gradient.
    At 8,192 positions a group of 8 folds in two parts of 4 (`head_parts`);
    here the resident bytes are cut so that 384 positions do the same: each
    part is a grid row of its own that reads the group's KV head, and the
    parts' dk are added."""
    g, gv, r, d, dv = heads
    t = 3 * TILE
    if parts > 1:
        monkeypatch.setattr(fa, "DQ_RESIDENT_BYTES", 2 * (r // parts) * t * d * 4)
    assert fa.head_parts(t, r, d) == parts
    q, k, v, cot = inputs(t, heads, jnp.float32, seed=5, bsz=2)
    got = out_and_grads(
        lambda q, k, v: fa.flash_attention(q, k, v, window, tq=TILE, tk=TILE, interpret=True),
        q, k, v, cot)
    want = out_and_grads(lambda q, k, v: hybrid_lm._attend(q, k, v, 0, 0, window), q, k, v, cot)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.shape == b.shape and rel(a, b) < 2e-6, (name, rel(a, b))


@pytest.mark.parametrize("t, r, parts, tiles", [
    (8192, 6, 1, (128, 1024)),    # 2 * 6 * 8192 * 128 * 4 = 48 MiB exactly: whole, by an equality
    (8192, 8, 2, (256, 1024)),    # 64 MiB whole: two parts of 4
    (8192, 4, 1, (256, 1024)),    # the other language model's group, as it was
    (16384, 4, 2, (512, 1024)),   # 64 MiB whole: two parts of 2
    (16384, 6, 2, (256, 1024)),   # 96 MiB whole: two parts of 3, again by an equality
    (16384, 8, 4, (512, 1024)),
    (65536, 1, None, None),       # one head's dq is 64 MiB: the XLA loop
], ids=lambda v: str(v))
def test_the_resident_dq_decides_the_parts_and_the_equality_at_six_heads_holds(
        t, r, parts, tiles):
    """DQ_RESIDENT_BYTES is 48 MiB and the comparison admits equality: a
    later change of either that dropped a layer of 6 query heads a KV head at
    8,192 positions to the XLA loop, or split it, fails here."""
    assert fa.DQ_RESIDENT_BYTES == 48 * 1024 * 1024
    assert fa.head_parts(t, r, 128) == parts
    assert fa.tiles(t, r, 128, 128) == tiles
    if tiles:
        assert fa.tiles(t, r, 128, 128, 512) == (tiles[0], 512)


# ------------------------------------------------------------ dispatch by shape


@pytest.mark.parametrize("t, heads", [(200, (2, 1, 2, 64, 128)), (256, (2, 2, 2, 32, 128)),
                                      (256, (2, 2, 2, 64, 96))],
                         ids=["length_no_multiple_of_128", "head_size_32", "value_size_96"])
def test_a_shape_that_does_not_tile_takes_the_xla_loop(t, heads, monkeypatch):
    """Also where the device is a TPU: the same results, the same count, no
    kernel in the program."""
    g, gv, r, d, dv = heads
    assert fa.tiles(t, r, d, dv) is None
    q, k, v, cot = inputs(t, heads, jnp.float32)
    run = lambda: out_and_grads(lambda *a: hybrid_lm.blocked_attention(*a, 64)[0], q, k, v, cot)
    want, want_blocks = run(), hybrid_lm.blocked_attention(q, k, v, 64)[1]
    monkeypatch.setattr(fa, "on_tpu", lambda: True)
    monkeypatch.setattr(fa, "flash_attention", None)   # not to be called
    assert all(jnp.array_equal(a, b) for a, b in zip(run(), want))
    assert hybrid_lm.blocked_attention(q, k, v, 64)[1] == want_blocks


def test_blocked_attention_takes_the_kernels_where_the_shapes_tile_on_a_tpu(kernels_here):
    """The program holds the two kernels by name, the counter is the
    schedule's, and what comes out is what the XLA loop gives on the CPU."""
    q, k, v, cot = inputs(256, HEADS["sambay_d64_dv128_r2"], jnp.float32)
    attend = lambda *a: hybrid_lm.blocked_attention(*a, 64)
    text = str(jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(attend(*a)[0]), argnums=(0, 1, 2)))(
        q, k, v))
    assert "attn_flash_fwd" in text and "attn_flash_bwd_onesweep" in text
    tq, tk = fa.tiles(256, 2, 64, 128, 64)
    assert (tq, tk) == (256, 128)
    assert attend(q, k, v)[1] == fa.key_blocks(256, tq, tk, 64, hybrid_lm.ATTN_KEY_BLOCK) == 2
    got = out_and_grads(lambda *a: attend(*a)[0], q, k, v, cot)
    with pytest.MonkeyPatch.context() as on_the_cpu:
        on_the_cpu.setattr(fa, "on_tpu", lambda: False)
        assert "attn_flash" not in str(jax.make_jaxpr(attend)(q, k, v))
        want = out_and_grads(lambda *a: attend(*a)[0], q, k, v, cot)
    assert max(rel(a, b) for a, b in zip(got, want)) < 2e-6


def test_the_tiles_follow_the_shapes_and_the_window_alone():
    assert fa.tiles(8192, 2, 64, 128) == fa.tiles(8192, 1, 64, 128) == (512, 1024)
    assert fa.tiles(8192, 4, 128, 128) == (256, 1024)      # the folded rows stay at 1,024
    assert fa.tiles(8192, 2, 64, 128, 512) == (512, 512)   # no key tile past the window
    assert fa.tiles(8192, 2, 64, 128, 40) == (512, 128)
    assert fa.tiles(8192, 2, 64, 128, 10 ** 6) == fa.tiles(8192, 2, 64, 128)
    assert fa.tiles(384, 2, 64, 128) == (128, 128)
    # a group's dq no longer resident: the group folds in parts, whose rows
    # the query tile follows; one head's no longer resident: back to the loop
    assert fa.tiles(8192, 8, 128, 128) == fa.tiles(8192, 4, 128, 128)
    assert fa.tiles(32768, 4, 128, 128) == (512, 1024)
    assert fa.tiles(8192, 4, 128, 128) and fa.tiles(65536, 4, 128, 128) is None
    # a latent attention's head: 192 key dimensions are two registers of lanes, so one head's
    # dq is resident to 16,384 positions (32 MiB twice) and not at 32,768
    assert fa.head_parts(16384, 1, 192) == 1 and fa.tiles(16384, 1, 192, 128) == (512, 1024)
    assert fa.head_parts(32768, 1, 192) is None and fa.tiles(32768, 1, 192, 128) is None
    assert fa.head_parts(32768, 1, 128) == 1 and fa.head_parts(8192, 6, 64) == 1


# ------------------------------------------------------------------ the schedule


def seen_mask(t, window):
    q_pos, k_pos = np.arange(t)[:, None], np.arange(t)[None, :]
    seen = k_pos <= q_pos
    return seen if window is None else seen & (k_pos > q_pos - window)


@pytest.mark.parametrize("t, tq, tk, window", [
    (1024, 128, 128, None), (1024, 128, 128, 512), (1024, 256, 128, 100), (1024, 128, 256, 129),
    (2048, 512, 512, 512), (2048, 256, 512, 1), (1536, 512, 256, 2000)])
def test_the_schedule_is_the_tiles_whose_mask_has_a_true_entry(t, tq, tk, window):
    """By brute force over the whole mask: a pair is a step exactly where
    some query of the tile sees some key of the tile, is masked exactly where
    it also holds a pair that is not seen, each order starts and ends its
    runs at the outer tile's first and last pair, and the counter is the
    steps in blocks of 128 keys."""
    seen = seen_mask(t, window)
    tile = lambda i, j: seen[i * tq:(i + 1) * tq, j * tk:(j + 1) * tk]
    want = {(i, j): not tile(i, j).all()
            for i in range(t // tq) for j in range(t // tk) if tile(i, j).any()}
    (qi, kj, flags), (kj2, qi2, flags2) = fa.schedule(t, tq, tk, window)
    assert {(i, j): bool(f & fa._MASKED) for i, j, f in zip(qi, kj, flags)} == want
    assert {(i, j): bool(f & fa._MASKED) for i, j, f in zip(qi2, kj2, flags2)} == want
    for outer, inner, fl in ((qi, kj, flags), (kj2, qi2, flags2)):
        assert all(a.dtype == np.int32 for a in (outer, inner, fl))
        order = list(zip(outer.tolist(), inner.tolist()))
        assert order == sorted(order)
        starts = [n == 0 or outer[n] != outer[n - 1] for n in range(len(outer))]
        ends = [n == len(outer) - 1 or outer[n] != outer[n + 1] for n in range(len(outer))]
        assert [bool(f & fa._FIRST) for f in fl] == starts
        assert [bool(f & fa._LAST) for f in fl] == ends
    assert fa.key_blocks(t, tq, tk, window) == len(want) * tk // 128


def test_the_windows_share_of_the_full_layers_key_blocks_at_8192_tokens():
    """`window_keys_visited_pct.train` as PERF.md states it for the cell: the
    counters' ratio at the tiles `phi4flash.train`'s shapes get. By the masks
    alone it is 12.1%."""
    window, full = (fa.key_blocks(8192, *fa.tiles(8192, 2, 64, 128, w), w) for w in (512, None))
    assert (window, full) == (124, 576)   # 31 pairs of 512 x 512, 72 of 512 x 1,024
    assert round(100 * window / full, 2) == 21.53
    assert round(100 * seen_mask(8192, 512).sum() / seen_mask(8192, None).sum(), 1) == 12.1


# ------------------------------------------- whole masked scores, and a SambaY stage


def masked_softmax_attention(q, k, v, window=None):
    """Whole [T, T] scores under the mask, value head g // (G / Gv): what the
    tiles must add up to."""
    t, g = q.shape[1], k.shape[2]
    v = jnp.repeat(v, g // v.shape[2], axis=2)
    s = jnp.einsum("bqgrd,bkgd->bgrqk", q, k) * q.shape[-1] ** -0.5
    return jnp.einsum("bgrqk,bkgd->bqgrd",
                      jax.nn.softmax(jnp.where(seen_mask(t, window), s, -jnp.inf), -1), v)


@pytest.mark.parametrize("window, same_as_full", [(384, True), (900, True), (100, False),
                                                  (16, False), (1, False)])
def test_a_window_at_least_the_length_is_full_attention_and_a_shorter_one_is_not(
        window, same_as_full, kernels_here):
    """Through `blocked_attention`, at three tiles of 128: results, gradients
    and the count of key blocks. A window of 1 is every row's own key alone,
    so each row's first tile is its last, and no row is a NaN."""
    q, k, v, cot = inputs(384, HEADS["sambay_d64_dv128_r2"], jnp.float32, seed=1, bsz=2)
    blocked = lambda window: jax.jit(lambda *a: hybrid_lm.blocked_attention(*a, window))
    full, full_blocks, on_kernels = blocked(None)(q, k, v)
    got, blocks, _ = blocked(window)(q, k, v)
    assert (rel(got, full) < 1e-6) == same_as_full
    assert (blocks == full_blocks) == same_as_full and blocks <= full_blocks and on_kernels == 1
    grads = lambda f: jax.jit(jax.grad(lambda *a: jnp.sum(f(*a) * cot), argnums=(0, 1, 2)))(q, k, v)
    got = (got,) + grads(lambda *a: hybrid_lm.blocked_attention(*a, window)[0])
    want = (masked_softmax_attention(q, k, v, window),) + grads(
        lambda *a: masked_softmax_attention(*a, window))
    scale = float(jnp.linalg.norm(cot))   # under a window of 1 dq and dk are zero
    for a, b in zip(got, want):
        assert bool(jnp.all(jnp.isfinite(a)))
        assert float(jnp.linalg.norm(a - b)) < 5e-6 * max(float(jnp.linalg.norm(b)), scale)


# hidden 256 in 4 heads of 64 over one KV pair, 384 tokens: the shapes tile, so
# with `kernels_here` the three attentions run the kernels at tiles of 128
KERNEL_SHAPED = dataclasses.replace(
    get_preset("sambay-tiny").model, layer_offset=2, num_hidden_layers=6,   # MWMFGX of N = 8
    hidden_size=256, num_attention_heads=4, num_key_value_heads=2, sliding_window=160, seq_len=384)


def test_no_position_of_a_sambay_stage_sees_a_later_token_through_the_kernels(kernels_here):
    """tests/test_sambay.py's test of the same name, where the attentions are
    the kernels: every token from position t on replaced, the logits before t
    stay what they were, bit for bit, and the logits at t do not. t inside a
    tile, at a tile's edge, past the window's reach, and the last. The
    model's counters are the kernels' schedule, in the module's key blocks."""
    cfg = KERNEL_SHAPED
    w = ws.to_program_params(ws.make_weights(23, dataclasses.asdict(cfg)))

    @jax.jit
    def logits(ids):
        x, counted = sambay.hidden_states(w, ids, cfg)
        h = sambay.layer_norm(x, w["final_norm_w"], w["final_norm_b"], cfg.layer_norm_eps)
        return jnp.einsum("btd,vd->btv", h, w["embed"]), counted

    ids = jax.random.randint(jax.random.PRNGKey(41), (2, cfg.seq_len), 0, cfg.vocab_size)
    assert "attn_flash_fwd" in str(jax.make_jaxpr(logits)(ids))
    base, counted = logits(ids)
    tiles = lambda window: fa.tiles(cfg.seq_len, 2, cfg.head_dim, 2 * cfg.head_dim, window)
    assert tiles(None) == tiles(cfg.sliding_window) == (128, 128)
    blocks = [c.get("attn_key_blocks_window", c.get("attn_key_blocks_full"))
              for kind, c in zip(cfg.kinds, counted) if kind in "WFX"]
    assert blocks == [fa.key_blocks(cfg.seq_len, *tiles(window), window, hybrid_lm.ATTN_KEY_BLOCK)
                      for window in (cfg.sliding_window, None, None)] == [1 + 2 + 3, 6, 6]
    for t in (7, 128, 200, 382):
        later = ids.at[:, t:].set((ids[:, t:] + 1 + t) % cfg.vocab_size)
        got, _ = logits(later)
        assert jnp.array_equal(got[:, :t], base[:, :t]), t
        assert not jnp.array_equal(got[:, t], base[:, t]), t


# ------------------------------ what a recomputed layer keeps (`hybrid_lm.run_stack`)


def _tiny(preset, **shapes):
    return dataclasses.replace(get_preset(preset).model, **shapes)


# The three families' tiny presets at shapes that tile (heads of 64, 128
# tokens, one tile): (the model's module, its init, the configuration, its
# attention layers, the same configuration cut to one attention layer).
FAMILIES = {
    "laguna": (laguna, laguna.init_laguna, _tiny("laguna-tiny", head_dim=64, seq_len=128), 5,
               dict(layer_offset=1, num_hidden_layers=1)),                      # S + E
    "sambay": (sambay, sambay.init_sambay,
               dataclasses.replace(KERNEL_SHAPED, sliding_window=64, seq_len=128), 3,   # MWMFGX
               dict(layer_offset=1, num_hidden_layers=1, num_hidden_layers_total=8)),   # W
    "hybrid_lm": (hybrid_lm, hybrid_lm.init_hybrid_lm,
                  _tiny("hybrid-lm-tiny", head_dim=64, seq_len=128), 1,         # ME*EM
                  dict(layer_offset=2, num_hidden_layers=1)),                   # *
}

# EvaByte's tiny preset at shapes that tile under the aligned mask: two windows of 128, chunks of
# 2 (128 summaries: one key tile), heads of 64. The two tests that read the residuals' shapes know
# the older families' (a bfloat16 stream of 128 tokens); the two that count kernels take every one.
KEPT_FAMILIES = {**FAMILIES, "evabyte": (
    evabyte, evabyte.init_evabyte,
    _tiny("evabyte-tiny", head_dim=64, window_size=128, chunk_size=2, seq_len=256), 3, None)}


@contextlib.contextmanager
def parents_policy():
    """`run_stack` as it was: a recomputed layer keeps its inputs alone."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax.checkpoint_policies, "save_only_these_names",
                      lambda *names: jax.checkpoint_policies.nothing_saveable)
        yield


def _params_and_ids(family, cfg):
    params = KEPT_FAMILIES[family][1](jax.random.PRNGKey(3), cfg)
    return params, jax.random.randint(jax.random.PRNGKey(4), (2, cfg.seq_len), 0, cfg.vocab_size)


def _loss_and_params(family, dtype=jnp.bfloat16, **kw):
    model, _, cfg, _, _ = KEPT_FAMILIES[family]
    params, ids = _params_and_ids(family, cfg)
    return (lambda p: model.lm_loss(p, ids, cfg, compute_dtype=dtype, **kw)), params


def _kernel_calls(loss, params):
    """(forward, backward) kernel calls in the gradient's program, every
    sub-program counted as often as it is called (the printed jaxpr shows a
    sub-program that several layers share once)."""
    calls = collections.Counter()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                calls[eqn.params["name"]] += 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(jax.grad(loss, has_aux=True))(params).jaxpr)
    return calls["attn_flash_fwd"], calls["attn_flash_bwd_onesweep"]


@pytest.mark.parametrize("remat", [True, False], ids=["remat", "no_remat"])
@pytest.mark.parametrize("family", list(KEPT_FAMILIES))
def test_the_recomputation_reads_the_forward_kernels_results_and_does_not_run_it_again(
        family, remat, kernels_here):
    """The gradient's program holds one forward and one backward kernel call
    an attention layer, recomputed or not, and the records' counter says how
    many layers' recomputation read what the forward kept."""
    layers = KEPT_FAMILIES[family][3]
    loss, params = _loss_and_params(family, remat=remat)
    assert _kernel_calls(loss, params) == (layers, layers)
    assert float(jax.jit(loss)(params)[1]["attn_forward_kept"]) == (layers if remat else 0)


@pytest.mark.parametrize("family", list(KEPT_FAMILIES))
def test_the_kept_results_change_no_bit_of_a_gradient(family, kernels_here):
    """Against `run_stack` with the parent's policy, which runs the forward
    kernel twice a layer: the same loss and the same gradients, bit for bit.
    In float32: under `jit` the CPU keeps a bfloat16 intermediate in float32
    where it fuses its producer with its consumer, so a result that is kept
    (rounded) and one that is rebuilt beside its consumer differ there by a
    rounding (0.5% of a gradient; none op by op, none without
    `xla_allow_excess_precision`), which is the compiler's and not the
    policy's."""
    layers = KEPT_FAMILIES[family][3]
    loss, params = _loss_and_params(family, dtype=None, remat=True)
    value_and_grad = lambda: jax.jit(jax.value_and_grad(lambda p: loss(p)[0]))(params)
    got = value_and_grad()
    with parents_policy():
        assert _kernel_calls(loss, params) == (2 * layers, layers)
        want = value_and_grad()
    assert all(jnp.array_equal(a, b) for a, b in zip(jax.tree_util.tree_leaves(got),
                                                     jax.tree_util.tree_leaves(want)))


def _saved(family, capsys):
    """What differentiating one recomputed attention layer keeps beyond the
    arguments and the constants, as `print_saved_residuals` words it, without
    the source's place; and the layer's configuration."""
    model, _, whole, _, one_layer = FAMILIES[family]
    cfg = dataclasses.replace(whole, **one_layer)
    params, ids = _params_and_ids(family, cfg)
    capsys.readouterr()
    jax.ad_checkpoint.print_saved_residuals(lambda p: jnp.sum(model.hidden_states(
        p, ids, cfg, compute_dtype=jnp.bfloat16, remat=True)[0].astype(jnp.float32)), params)
    return cfg, [line.split(" from /")[0] for line in capsys.readouterr().out.splitlines()
                 if " from the argument " not in line and " from a constant" not in line]


@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_recomputed_attention_layer_keeps_the_output_and_the_log_sum_exp_rows(
        family, kernels_here, capsys):
    """Beyond the layer's input (the embedding's rows, and the ids that took
    them): one array of B x heads x T x Dv in the compute type, head-major,
    and one float32 array of B x heads x T. No [..., T, 1] column, no q, k or
    v, nothing of the layer's second half."""
    cfg, saved = _saved(family, capsys)
    heads = cfg.heads("S") if family == "laguna" else cfg.num_attention_heads
    dv = 2 * cfg.head_dim if family == "sambay" else cfg.head_dim   # a pair's values
    inputs = ["i32[2,128,1] output of broadcast_in_dim",
              f"bf16[2,128,{cfg.hidden_size}] output of convert_element_type"]
    assert saved[:2] == inputs and len(saved) == 4, saved
    # a residual that the forward pass reads too comes out of the
    # `reduce_precision` that `jax.checkpoint` puts on it, and loses its name
    size = lambda line: math.prod(int(n) for n in re.match(r"\w+\[([\d,]+)\] ", line)[1].split(","))
    assert saved[2].startswith("bf16[") and saved[2].endswith("output of reduce_precision")
    assert size(saved[2]) == 2 * heads * 128 * dv
    assert saved[3].startswith("f32[") and saved[3].endswith(f"named '{fa.KEPT_LSE}'")
    assert size(saved[3]) == 2 * heads * 128


@pytest.mark.parametrize("family", list(FAMILIES))
def test_on_the_xla_loop_a_recomputed_layer_keeps_what_it_kept(family, capsys):
    """No kernel, no name: the policy keeps nothing, the counter reads 0, and
    the residuals are the parent's, the layer's input alone."""
    _, saved = _saved(family, capsys)
    assert len(saved) == 2 and not any("attn_flash" in line for line in saved), saved
    with parents_policy():
        assert _saved(family, capsys)[1] == saved
    loss, params = _loss_and_params(family, remat=True)
    assert float(jax.jit(loss)(params)[1]["attn_forward_kept"]) == 0


# ------------------------------------- the aligned mask (EVA attention, `fa.Aligned`)


def eva_config(t, window, chunk, heads=2, d=64):
    return EvaByteConfig(hidden_size=64, num_attention_heads=heads, num_attention_heads_total=heads,
                         head_dim=d, window_size=window, chunk_size=chunk, num_hidden_layers=1,
                         num_hidden_layers_total=1, seq_len=t)


def eva_inputs(cfg, dtype, seed=0):
    t, h, d = cfg.seq_len, cfg.num_attention_heads, cfg.head_dim
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k, v, cot = (jax.random.normal(key, (1, t, h, d), dtype) for key in ks[:4])
    return q, k, v, jax.random.normal(ks[4], (h, d)), jax.random.normal(ks[5], (h, d)), cot


def eva_by_the_kernels(cfg, tq, tk):
    """`evabyte.eva_attention`'s kernel branch at the tiles given, in interpret mode."""
    def f(q, k, v, phi, mu):
        khat, vhat = evabyte.summarise(k, v, phi, mu, cfg)
        mask = fa.Aligned(cfg.window_size, cfg.chunk_size, cfg.seq_len)
        return fa.flash_attention(q[:, :, :, None], jnp.concatenate([k, khat], axis=1),
                                  jnp.concatenate([v, vhat], axis=1), mask, tq=tq, tk=tk,
                                  interpret=True)[:, :, :, 0]
    return f


def eva_by_the_xla_loop(cfg):
    return lambda q, k, v, phi, mu: evabyte.eva_attention(
        q, k, v, *evabyte.summarise(k, v, phi, mu, cfg), cfg)[0]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("t, window, chunk, tq, tk", [
    (512, 256, 4, 128, 128), (512, 128, 2, 128, 128), (1024, 512, 4, 256, 256),
    (512, 256, 2, 256, 128), (768, 256, 2, 128, 128)],
    ids=["two_windows", "four_windows", "key_tile_of_256", "query_tile_over_key_tile",
         "three_windows"])
def test_under_the_aligned_mask_the_kernels_match_the_xla_loop_in_every_gradient(
        t, window, chunk, tq, tk, dtype):
    """Both key segments in one online softmax: the output, dq, and through
    the concatenated keys' dk and dv the gradients of k, v and of the
    summariser's `phi` and `mu` (the summary rows' dk and dv)."""
    cfg = eva_config(t, window, chunk)
    *xs, cot = eva_inputs(cfg, dtype)
    grads = lambda f: jax.jit(lambda *a: (lambda o, pull: (o,) + pull(cot))(*jax.vjp(f, *a)))(*xs)
    got, want = grads(eva_by_the_kernels(cfg, tq, tk)), grads(eva_by_the_xla_loop(cfg))
    tol = 3e-6 if dtype == jnp.float32 else 2e-2
    for name, a, b in zip(("out", "dq", "dk", "dv", "dphi", "dmu"), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert rel(a, b) < tol, (name, rel(a, b))
    assert rel(got[4], 0 * got[4]) > 0 and rel(got[5], 0 * got[5]) > 0   # the summaries matter


def aligned_seen(mask):
    q_pos = np.arange(mask.length)[:, None]
    k_pos = np.arange(mask.length + mask.summaries)[None, :]
    return np.asarray(fa._seen(jnp.asarray(q_pos), jnp.asarray(k_pos), mask))


@pytest.mark.parametrize("t, window, chunk, tq, tk", [
    (1024, 256, 8, 128, 128), (2048, 512, 4, 256, 128), (2048, 1024, 8, 256, 256),
    (1024, 256, 2, 128, 128), (4096, 1024, 8, 512, 512), (2048, 512, 16, 512, 128)])
def test_the_aligned_schedule_visits_no_tile_the_mask_empties(t, window, chunk, tq, tk):
    """By brute force over the whole mask of both segments: a pair is a step
    exactly where some query of the tile sees some key of it, masked exactly
    where it also holds a pair that is not seen; the counters split the steps
    by segment."""
    mask = fa.Aligned(window, chunk, t)
    seen = aligned_seen(mask)
    tile = lambda i, j: seen[i * tq:(i + 1) * tq, j * tk:(j + 1) * tk]
    want = {(i, j): not tile(i, j).all() for i in range(t // tq)
            for j in range((t + mask.summaries) // tk) if tile(i, j).any()}
    (qi, kj, flags), (kj2, qi2, flags2) = fa.schedule(t, tq, tk, mask)
    assert {(i, j): bool(f & fa._MASKED) for i, j, f in zip(qi, kj, flags)} == want
    assert {(i, j): bool(f & fa._MASKED) for i, j, f in zip(qi2, kj2, flags2)} == want
    own = sum(j < t // tk for _, j in want)
    assert fa.aligned_key_blocks(tq, tk, mask) == (own * tk // 128, (len(want) - own) * tk // 128)
    assert fa.key_blocks(t, tq, tk, mask) == len(want) * tk // 128


def test_the_aligned_mask_case_by_case():
    """A window's first query sees one key of its own and every earlier
    chunk's summary; the first window sees no summary; no query sees a
    summary of its own window or a key of another."""
    mask = fa.Aligned(256, 16, 1024)
    seen = aligned_seen(mask)
    own, summary = seen[:, :1024], seen[:, 1024:]
    assert not summary[:256].any() and (own[:256, :256] == np.tril(np.ones((256, 256), bool))).all()
    for n in range(4):
        t = 256 * n
        assert own[t].sum() == 1 and own[t, t] and summary[t].sum() == n * 16
        assert own[t + 255].sum() == 256 and own[t + 255, t:t + 256].all()
        assert summary[t + 255].sum() == n * 16 and summary[t + 255, :n * 16].all()
    assert summary.shape[1] == 64 and not summary[:, 48:].any()   # the last window's: seen by none


def test_the_cells_tiles_and_key_blocks_under_the_aligned_mask():
    """`evabyte.train`'s shapes: 16,384 positions in windows of 2,048, chunks
    of 16, one query head a KV head of 128: tiles of 512 by 512, 320 blocks
    of own keys and 160 of summaries a layer where the mask needs 320 and 112
    (`eva_key_blocks_visited_pct.train` 111.1); a window or a chunk that is no
    power of two, or summaries that no key tile divides, take the XLA loop;
    the older masks' tiles are what they were."""
    mask = fa.Aligned(2048, 16, 16384)
    assert fa.tiles(16384, 1, 128, 128, mask) == (512, 512)
    assert fa.aligned_key_blocks(512, 512, mask) == (320, 160)
    from benchmark import flops_evabyte
    model = dataclasses.asdict(get_preset("evabyte-stage4tp4").model)
    assert flops_evabyte.needed_key_blocks(model, 16384) == (320, 112)
    assert fa.tiles(6144, 1, 128, 128, fa.Aligned(1536, 16, 6144)) is None
    assert fa.tiles(4096, 1, 128, 128, fa.Aligned(2048, 24, 4096)) is None
    assert fa.tiles(2048, 1, 128, 128, fa.Aligned(1024, 32, 2048)) is None   # 64 summaries
    assert fa.tiles(2048, 1, 128, 128, fa.Aligned(1024, 16, 2048)) == (512, 128)
    assert fa.tiles(8192, 8, 128, 128, 512) == (256, 512) and fa.tiles(8192, 4, 128, 128) == (
        256, 1024)


# ----------------------------------------------- compiled for the chip, not run


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("heads, window, t", [
    ((20, 10, 2, 64, 128), None, 8192), ((20, 10, 2, 64, 128), 512, 8192),
    ((1, 1, 4, 128, 128), None, 8192), ((8, 8, 8, 128, 128), 512, 8192),
    ((8, 8, 6, 128, 128), None, 8192), ((32, 32, 1, 192, 128), None, 16384)],
    ids=["phi4flash_full", "phi4flash_window", "nemotron3super", "lagunaxs2_sliding",
         "lagunaxs2_full", "kimilinear_latent"])
def test_the_kernels_compile_for_a_v5e_at_the_cells_sizes(one_chip, heads, window, t):
    """The cells' tokens (8,192; Kimi Linear's row 16,384) in bfloat16 at the
    tiles the shapes get: Mosaic takes the layouts (a head of 192 is a register
    and a half of lanes), the transposed product and the group's resident dq,
    which interpret mode cannot say. Nothing runs."""
    g, gv, r, d, dv = heads
    tq, tk = fa.tiles(t, r, d, dv, window)
    arg = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(lambda *a: out_and_grads(
        lambda q, k, v: fa.flash_attention(q, k, v, window, tq=tq, tk=tk), *a)).lower(
        arg(1, t, g, r, d), arg(1, t, g, d), arg(1, t, gv, dv), arg(1, t, g, r, dv)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2
    assert f"{t},{t}]" not in text and f"{tq},{t}]" not in text   # no [queries, keys] array


def test_the_kernels_compile_for_a_v5e_under_the_aligned_mask_at_the_cells_size(one_chip):
    """`evabyte.train`'s attention: 8 heads of 128, 16,384 positions and 1,024
    summaries after them, windows of 2,048, in bfloat16 (the step's) and in
    float32 (`correct`'s pass of the attention alone). Nothing runs."""
    t, h, d = 16384, 8, 128
    mask = fa.Aligned(2048, 16, t)
    tq, tk = fa.tiles(t, 1, d, d, mask)
    for dtype in (jnp.bfloat16, jnp.float32):
        arg = lambda *shape: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        compiled = jax.jit(lambda *a: out_and_grads(
            lambda q, k, v: fa.flash_attention(q, k, v, mask, tq=tq, tk=tk), *a)).lower(
            arg(1, t, h, 1, d), arg(1, t + t // 16, h, d), arg(1, t + t // 16, h, d),
            arg(1, t, h, 1, d)).compile()
        text = compiled.as_text()
        assert text.count("tpu_custom_call") >= 2
        assert f"{tq},{t}]" not in text and f"{t},{t + t // 16}]" not in text


def test_the_selective_scans_kernels_compile_for_a_v5e_at_the_cells_size(one_chip):
    """`phi4flash.train`'s Mamba-1 scan (kernels/selective_scan.py; here for
    this file's one description of the chip): one row of 8,192 positions, 5,120
    channels, 16 states, x, b and c in bfloat16, at the blocks the shape gets.
    Mosaic takes the group's sublane rotations, the columns laid across a
    register's lanes and the 16 MB of a block's states and decays in VMEM;
    no [T, N, C] array is an operand or a result. Nothing runs."""
    from glom_tpu.kernels import selective_scan as ss

    t, ch, n = 8192, 5120, 16
    tb, cb = ss.blocks(t, ch, n)
    arg = lambda dtype, *shape: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    bf, f32 = jnp.bfloat16, jnp.float32

    def out_and_five_grads(x, dt, a, b, c, cot):
        y, pull = jax.vjp(lambda *v: ss.selective_scan(*v, time_block=tb, channel_block=cb),
                          x, dt, a, b, c)
        return (y,) + pull(cot)

    text = jax.jit(out_and_five_grads).lower(
        arg(bf, 1, t, ch), arg(f32, 1, t, ch), arg(f32, ch, n), arg(bf, 1, t, n),
        arg(bf, 1, t, n), arg(bf, 1, t, ch)).compile().as_text()
    assert text.count("tpu_custom_call") >= 2
    assert f"{t},{n},{ch}]" not in text and f"{n},{t},{ch}]" not in text
