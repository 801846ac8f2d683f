"""Paged column memory: pool conservation, ragged bitwise parity, the
zero-transfer warm path, and session-affinity routing (ISSUE 11).

The parity locks are the contract the ragged route ships under:

  * threshold-0 ragged dispatch is BITWISE the per-row lone dispatches
    it replaced (the PR 8 fold-parity pattern on the page axis);
  * a full-resolution ragged row is BITWISE the dense engine's cold
    dispatch (same embed, same update ops, same reductions — the
    row-windowed consensus gather reproduces the dense attention
    layout exactly);
  * the paged warm path is BITWISE the host-levels0 warm path while
    moving ZERO levels0 bytes host->device (the acceptance counter).

Pool/cache tests are host-side accounting: pages_used + pages_free ==
pages_total through arbitrary alloc/free/evict/invalidate churn, pinned
blocks survive eviction pressure, and the TTL sweep reclaims dead
sessions' pages under pressure without a lookup ever touching the key.
"""

import dataclasses
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from glom_tpu.models.core import init_glom
from glom_tpu.ops.patch import patchify
from glom_tpu.serve.batcher import DynamicBatcher, _patchify_host
from glom_tpu.serve.column_cache import ColumnCache, PageHit
from glom_tpu.serve.engine import InferenceEngine
from glom_tpu.serve.paged_columns import (
    PagedColumnPool,
    page_state_bytes,
    pages_for_tokens,
    resolve_page_tokens,
)
from glom_tpu.utils.config import GlomConfig, ServeConfig

CFG = GlomConfig(dim=32, levels=3, image_size=16, patch_size=4)  # n=16
SCFG = ServeConfig(
    buckets=(1, 2, 4), max_batch=4, max_delay_ms=2.0,
    iters="auto", max_auto_iters=6, exit_threshold=0.0,
    page_pool_pages=32, page_tokens=4, ragged=True,
    dispatch_retries=0,
)


def _imgs(rng, n=1, hw=16):
    return (100.0 * rng.normal(size=(n, CFG.channels, hw, hw))).astype(
        np.float32
    )


def _flat(rows, pt=4, pages_sig=None):
    """Pack host-patchified rows page-aligned (the batcher's layout)."""
    counts = [r.shape[0] for r in rows]
    need = sum(pages_for_tokens(c, pt) for c in counts)
    P = pages_sig if pages_sig is not None else need
    flat = np.zeros((P * pt, rows[0].shape[1]), np.float32)
    off = 0
    for r, c in zip(rows, counts):
        flat[off:off + c] = r
        off += pages_for_tokens(c, pt) * pt
    return flat, counts


@pytest.fixture(scope="module")
def engine():
    return InferenceEngine(CFG, SCFG, key=jax.random.PRNGKey(0))


class TestPageTokens:
    def test_explicit_must_divide(self):
        with pytest.raises(ValueError, match="does not divide"):
            resolve_page_tokens(
                CFG, dataclasses.replace(SCFG, page_tokens=5)
            )

    def test_auto_resolves_quarter_row(self):
        # n=16 -> 4-token pages (four pages per full row); flagship
        # n=256 -> 64-token pages (the cap).
        assert resolve_page_tokens(
            CFG, dataclasses.replace(SCFG, page_tokens=0)
        ) == 4
        big = GlomConfig(dim=32, levels=3, image_size=224, patch_size=14)
        assert resolve_page_tokens(
            big, dataclasses.replace(SCFG, page_tokens=0)
        ) == 64

    def test_pages_for_tokens(self):
        assert pages_for_tokens(16, 4) == 4
        assert pages_for_tokens(9, 4) == 3
        assert pages_for_tokens(1, 4) == 1
        with pytest.raises(ValueError):
            pages_for_tokens(0, 4)


class TestPoolConservation:
    def _pool(self):
        return PagedColumnPool(CFG, SCFG, name="t")

    def _check(self, pool):
        rec = pool.record()
        assert rec["pages_used"] + rec["pages_free"] == rec["pages_total"]
        assert rec["bytes_in_use"] == rec["pages_used"] * rec["page_bytes"]

    def test_alloc_free_churn_conserves(self):
        pool = self._pool()
        rng = np.random.default_rng(3)
        live = set()
        for step in range(200):
            op = rng.integers(0, 3)
            sid = f"s{rng.integers(0, 12)}"
            if op == 0:
                n = int(rng.integers(1, 17))
                pages = pool.alloc(sid, n)
                if pages is not None:
                    live.add(sid)
                    assert len(pages) == pages_for_tokens(n, 4)
                    assert len(set(pages)) == len(pages)
            elif op == 1:
                pool.free(sid)
                live.discard(sid)
            else:
                self._check(pool)
        self._check(pool)
        rec = pool.record()
        assert rec["n_sessions"] == len(live)
        pool.free_all()
        self._check(pool)
        assert pool.record()["pages_used"] == 0

    def test_alloc_fails_loudly_when_full(self):
        pool = self._pool()
        for i in range(8):  # 8 x 4 pages = the whole 32-page pool
            assert pool.alloc(f"s{i}", 16) is not None
        assert pool.alloc("overflow", 16) is None
        assert pool.record()["n_alloc_fails"] == 1
        self._check(pool)
        pool.free("s3")
        assert pool.alloc("overflow", 16) is not None
        self._check(pool)

    def test_same_size_realloc_reuses_pages(self):
        pool = self._pool()
        first = pool.alloc("s", 9)
        again = pool.alloc("s", 9)
        assert first == again
        resized = pool.alloc("s", 16)
        assert len(resized) == 4
        self._check(pool)

    def test_defrag_compacts_and_preserves_contents(self):
        pool = self._pool()
        lv = {}
        for i in range(4):
            n = 8
            arr = np.random.default_rng(i).normal(
                size=(n, CFG.levels, CFG.dim)
            ).astype(np.float32)
            assert pool.write_back(f"s{i}", jnp.asarray(arr), n)
            lv[f"s{i}"] = arr
        pool.free("s0")
        pool.free("s2")
        moved = pool.defrag()
        assert moved > 0
        self._check(pool)
        used_pages = sorted(
            p for sid in ("s1", "s3") for p in pool.lookup(sid)[0]
        )
        assert used_pages == list(range(len(used_pages)))  # compacted low
        for sid in ("s1", "s3"):
            np.testing.assert_array_equal(pool.read_block(sid), lv[sid])

    def test_pin_protects_free_force_overrides(self):
        pool = self._pool()
        pool.alloc("s", 16)
        pool.lookup("s", pin=True)
        assert pool.is_pinned("s")
        # free() is the force path (invalidation): it drops even pinned.
        assert pool.free("s") == 4
        self._check(pool)

    def test_write_back_read_block_roundtrip(self):
        pool = self._pool()
        arr = np.random.default_rng(0).normal(
            size=(9, CFG.levels, CFG.dim)
        ).astype(np.float32)
        assert pool.write_back("s", jnp.asarray(arr), 9)
        np.testing.assert_array_equal(pool.read_block("s"), arr)
        assert pool.lookup("s")[1] == 9
        assert len(pool.lookup("s")[0]) == 3  # ceil(9/4) pages


class TestRaggedParity:
    def test_threshold0_mixed_bitwise_equals_lone_dispatches(self, engine):
        """THE ragged contract: one mixed dispatch == the per-row lone
        dispatches it replaced, bit for bit, at threshold 0."""
        rng = np.random.default_rng(7)
        big = _imgs(rng)[0]
        small = _imgs(rng, hw=8)[0]
        rows = [
            _patchify_host(big, 4),
            _patchify_host(small, 4),
        ]
        flat, counts = _flat(rows, pages_sig=engine.pick_pages(5))
        mixed = engine.infer_ragged(flat, counts)
        assert mixed.iters_run == 6  # threshold 0: the full budget
        lone_a = engine.infer_ragged(
            *_flat([rows[0]], pages_sig=engine.pick_pages(4))
        )
        lone_b = engine.infer_ragged(
            *_flat([rows[1]], pages_sig=engine.pick_pages(1))
        )
        m = np.asarray(mixed.levels)
        np.testing.assert_array_equal(m[0:16], np.asarray(lone_a.levels)[0:16])
        np.testing.assert_array_equal(
            m[16:20], np.asarray(lone_b.levels)[0:4]
        )

    def test_full_res_ragged_matches_dense_cold(self, engine):
        """Cross-route: a full-resolution ragged row reproduces the
        dense engine's cold dispatch (same embed, same update ops, W == n
        so even the softmax axis length matches) to float32 rounding, not
        bit for bit: they are two compiled programs, and on this JAX XLA
        sums their reductions in different orders (largest difference
        seen 1.9e-6 = one ulp at the values' 23; 64% of elements differ).
        A wrong route would miss by orders more."""
        rng = np.random.default_rng(8)
        img = _imgs(rng)[0]
        dense = engine.infer(img[None], n_valid=1)
        ragged = engine.infer_ragged(
            *_flat([_patchify_host(img, 4)], pages_sig=4)
        )
        np.testing.assert_allclose(
            np.asarray(dense.levels[0]), np.asarray(ragged.levels)[0:16],
            rtol=0, atol=7.6e-6,
        )
        assert ragged.levels0_h2d_bytes == 0

    def test_pad_positions_never_vote(self, engine):
        """Garbage in the page-tail pad positions must not change any
        row's output: pads are masked out of attention, the witness,
        and the quorum."""
        rng = np.random.default_rng(9)
        small = _imgs(rng, hw=8)[0]
        flat, counts = _flat(
            [_patchify_host(small, 4)], pages_sig=engine.pick_pages(1)
        )
        clean = engine.infer_ragged(flat, counts)
        dirty = flat.copy()
        dirty[counts[0]:] = 1e6  # page tail: pad positions
        poisoned = engine.infer_ragged(dirty, counts)
        np.testing.assert_array_equal(
            np.asarray(clean.levels)[: counts[0]],
            np.asarray(poisoned.levels)[: counts[0]],
        )

    def test_host_patchify_matches_einops(self):
        rng = np.random.default_rng(10)
        img = _imgs(rng)[0]
        ref = np.asarray(patchify(jnp.asarray(img)[None], 4))[0]
        np.testing.assert_array_equal(_patchify_host(img, 4), ref)


class TestPagedWarmPath:
    def test_paged_bitwise_equals_host_warm_and_moves_zero_bytes(self):
        """The tentpole claim in one test: page-warm == host-warm
        bitwise, with levels0_h2d_bytes 0 vs > 0."""
        scfg = dataclasses.replace(SCFG, ragged=False)
        eng = InferenceEngine(CFG, scfg, key=jax.random.PRNGKey(1))
        rng = np.random.default_rng(11)
        imgs = _imgs(rng, n=2)
        cold = eng.infer(imgs, n_valid=2)
        assert cold.levels0_h2d_bytes == 0
        eng.pool.write_back("s", cold.levels[0], CFG.num_patches)
        pages = eng.pool.lookup("s")[0]
        prow = np.full((2, 4), -1, np.int32)
        prow[0] = pages
        paged = eng.infer(imgs, n_valid=2, page_rows=prow)
        lv0 = np.zeros((2, CFG.num_patches, CFG.levels, CFG.dim), np.float32)
        lv0[0] = np.asarray(cold.levels[0])
        lv0[1] = eng.cold_levels()
        host = eng.infer(imgs, n_valid=2, levels0=lv0)
        np.testing.assert_array_equal(
            np.asarray(paged.levels), np.asarray(host.levels)
        )
        assert paged.levels0_h2d_bytes == 0
        assert host.levels0_h2d_bytes == lv0.nbytes
        assert eng.levels0_h2d_bytes_total == lv0.nbytes
        # Cold rows of the paged dispatch are bitwise the plain cold
        # route (page_idx -1 takes the forward's own init).
        np.testing.assert_array_equal(
            np.asarray(paged.levels[1]), np.asarray(cold.levels[1])
        )


class TestPagesCache:
    def _setup(self, budget_pages=8, ttl=None):
        pool = PagedColumnPool(
            CFG, dataclasses.replace(SCFG, page_pool_pages=budget_pages),
            name="e0",
        )
        clock = [0.0]
        cache = ColumnCache(
            budget_pages * pool.page_bytes,
            pools={"e0": pool},
            ttl_s=ttl,
            clock=lambda: clock[0],
        )
        return pool, cache, clock

    def _state(self, n=16):
        return jnp.asarray(
            np.random.default_rng(0).normal(
                size=(n, CFG.levels, CFG.dim)
            ).astype(np.float32)
        )

    def test_store_lookup_returns_page_hit(self):
        pool, cache, _ = self._setup()
        assert cache.store("sA", self._state(), engine="e0", n_tokens=16)
        hit = cache.lookup("sA")
        assert isinstance(hit, PageHit)
        assert hit.engine == "e0" and hit.n_tokens == 16
        assert len(hit.pages) == 4
        assert cache.engine_of("sA") == "e0"
        assert pool.record()["pages_used"] == 4

    def test_lru_eviction_frees_pages(self):
        pool, cache, _ = self._setup(budget_pages=8)
        cache.store("sA", self._state(), engine="e0", n_tokens=16)
        cache.store("sB", self._state(), engine="e0", n_tokens=16)
        # Pool (and budget) hold exactly two: the third evicts LRU sA.
        cache.store("sC", self._state(), engine="e0", n_tokens=16)
        assert cache.lookup("sA") is None
        assert isinstance(cache.lookup("sC"), PageHit)
        assert pool.record()["pages_used"] == 8
        assert cache.n_evictions == 1

    def test_pinned_block_survives_eviction_pressure(self):
        pool, cache, _ = self._setup(budget_pages=8)
        cache.store("sA", self._state(), engine="e0", n_tokens=16)
        cache.store("sB", self._state(), engine="e0", n_tokens=16)
        hit = cache.lookup("sA", pin=True)  # in-flight dispatch
        assert isinstance(hit, PageHit)
        cache.store("sC", self._state(), engine="e0", n_tokens=16)
        # sA was LRU but pinned: sB pays instead.
        assert pool.holds("sA") and not pool.holds("sB")
        cache.unpin("sA")
        assert not pool.is_pinned("sA")

    def test_ttl_expiry_at_lookup_frees_pages(self):
        pool, cache, clock = self._setup(ttl=10.0)
        cache.store("sA", self._state(), engine="e0", n_tokens=16)
        clock[0] = 11.0
        assert cache.lookup("sA") is None
        assert cache.n_expirations == 1
        assert pool.record()["pages_used"] == 0

    def test_pressure_sweep_reclaims_expired_without_lookup(self):
        """The TTL-at-lookup-only leak (ISSUE 11 satellite): a dead
        session's pages stay pinned until someone touches the key —
        eviction pressure now sweeps expired entries FIRST, before any
        live LRU victim pays."""
        pool, cache, clock = self._setup(budget_pages=8, ttl=10.0)
        cache.store("dead", self._state(), engine="e0", n_tokens=16)
        clock[0] = 5.0
        cache.store("live", self._state(), engine="e0", n_tokens=16)
        clock[0] = 12.0  # "dead" expired, never looked up again
        cache.store("new", self._state(), engine="e0", n_tokens=16)
        # The sweep reclaimed "dead"; "live" survived the pressure.
        assert cache.n_expirations == 1 and cache.n_evictions == 0
        assert isinstance(cache.lookup("live"), PageHit)
        assert cache.lookup("dead") is None

    def test_invalidate_engine_frees_pool_pages(self):
        pool, cache, _ = self._setup()
        cache.store("sA", self._state(), engine="e0", n_tokens=16)
        assert cache.invalidate_engine("e0") == 1
        assert pool.record()["pages_used"] == 0
        assert cache.lookup("sA") is None

    def test_host_mode_pressure_sweep(self):
        """The sweep satellite applies to the PR 8 host-array cache too
        (same leak, same fix)."""
        clock = [0.0]
        entry = np.zeros((16, CFG.levels, CFG.dim), np.float32)
        cache = ColumnCache(
            2 * entry.nbytes, ttl_s=10.0, clock=lambda: clock[0]
        )
        cache.store("dead", entry, engine="e0")
        clock[0] = 5.0
        cache.store("live", entry, engine="e0")
        clock[0] = 12.0
        cache.store("new", entry, engine="e0")
        assert cache.n_expirations == 1 and cache.n_evictions == 0
        assert cache.lookup("live") is not None


@pytest.mark.slow
class TestRaggedBatcher:
    def _engines(self, n=1, **over):
        scfg = dataclasses.replace(SCFG, **over) if over else SCFG
        params = init_glom(jax.random.PRNGKey(0), CFG)
        return [
            InferenceEngine(CFG, scfg, params=params, name=f"e{i}")
            for i in range(n)
        ]

    def test_mixed_resolution_batch_resolves_correct_shapes(self):
        engines = self._engines()
        rng = np.random.default_rng(12)
        big = _imgs(rng)[0]
        small = _imgs(rng, hw=8)[0]
        with DynamicBatcher(engines=engines) as b:
            ta = b.submit(big)
            tb = b.submit(small)
            lv_a, _, _ = ta.result(timeout=120)
            lv_b, _, _ = tb.result(timeout=120)
            s = b.summary_record()
        assert lv_a.shape == (16, CFG.levels, CFG.dim)
        assert lv_b.shape == (4, CFG.levels, CFG.dim)
        assert s["n_served"] == 2
        assert s["pad_fraction_mean"] > 0  # page-tail round-up, stamped
        assert s["levels0_h2d_bytes"] == 0
        assert s["page_pools"]["e0"]["pages_total"] == 32

    def test_batcher_ragged_threshold0_bitwise_vs_lone(self):
        """Fold-parity through the REAL batcher: the rows of one ragged
        batcher dispatch equal the engine's lone ragged dispatches."""
        engines = self._engines()
        eng = engines[0]
        rng = np.random.default_rng(13)
        big = _imgs(rng)[0]
        small = _imgs(rng, hw=8)[0]
        b = DynamicBatcher(engines=engines)
        ta = b.submit(big)
        tb = b.submit(small)
        b.start()  # both queued before the worker runs: ONE dispatch
        lv_a, iters_a, _ = ta.result(timeout=120)
        lv_b, iters_b, _ = tb.result(timeout=120)
        b.stop()
        lone_a = eng.infer_ragged(
            *_flat([_patchify_host(big, 4)], pages_sig=eng.pick_pages(4))
        )
        lone_b = eng.infer_ragged(
            *_flat([_patchify_host(small, 4)], pages_sig=eng.pick_pages(1))
        )
        np.testing.assert_array_equal(
            np.asarray(lv_a), np.asarray(lone_a.levels)[0:16]
        )
        np.testing.assert_array_equal(
            np.asarray(lv_b), np.asarray(lone_b.levels)[0:4]
        )
        assert iters_a == lone_a.iters_run == 6  # threshold 0: budget

    def test_session_affinity_routes_to_page_holder(self):
        engines = self._engines(
            n=2, exit_threshold=1e-3, column_cache_bytes=1 << 20
        )
        rng = np.random.default_rng(14)
        base = _imgs(rng)[0]
        with DynamicBatcher(engines=engines) as b:
            b.submit(base, session_id="sA").result(timeout=120)
            holder = b.cache.engine_of("sA")
            assert holder in ("e0", "e1")
            frame2 = base + 0.05 * rng.normal(size=base.shape).astype(
                np.float32
            )
            _, iters2, _ = b.submit(frame2, session_id="sA").result(
                timeout=120
            )
            s = b.summary_record()
        assert s["n_affinity"] >= 1
        assert s["n_page_warm"] >= 1
        assert s["levels0_h2d_bytes"] == 0
        assert iters2 < 6  # warm start exited early

    def test_affinity_falls_back_on_engine_death(self):
        """Session-affinity routing falls back cleanly when the page
        holder dies: pages freed, stream re-served cold on the sibling,
        every ticket terminal."""
        fail = {"e0": False}

        def hook(ctx):
            if fail["e0"]:
                raise RuntimeError("injected engine fault")

        scfg = dataclasses.replace(
            SCFG, exit_threshold=1e-3, column_cache_bytes=1 << 20
        )
        params = init_glom(jax.random.PRNGKey(0), CFG)
        e0 = InferenceEngine(
            CFG, scfg, params=params, name="e0", fault_hook=hook
        )
        e1 = InferenceEngine(CFG, scfg, params=params, name="e1")
        rng = np.random.default_rng(15)
        base = _imgs(rng)[0]
        with DynamicBatcher(
            engines=[e0, e1], engine_fail_threshold=1
        ) as b:
            # Warm sA wherever it lands; force it onto e0 by serving
            # until e0 holds it (2 workers race; retry with new streams).
            sid = None
            for k in range(8):
                cand = f"s{k}"
                b.submit(base, session_id=cand).result(timeout=120)
                if b.cache.engine_of(cand) == "e0":
                    sid = cand
                    break
            assert sid is not None, "no stream landed on e0"
            fail["e0"] = True  # e0 now fails every dispatch
            frame2 = base + 0.05 * rng.normal(size=base.shape).astype(
                np.float32
            )
            lv, iters, _ = b.submit(frame2, session_id=sid).result(
                timeout=120
            )
            assert lv.shape[0] == CFG.num_patches
            s = b.summary_record()
        assert s["engines"]["e0"]["alive"] is False
        assert e0.pool.record()["pages_used"] == 0  # death freed pages
        # Every ticket terminal, nothing lost: conservation holds across
        # the failover (the re-served frame ran cold on the sibling).
        assert s["n_failed"] == 0
        assert s["n_requests"] == s["n_served"] + s["n_shed"] + s["n_failed"]


def test_ragged_continuations_need_auto_route():
    """Ragged COMPOSES with the continuation queue now (ISSUE 16) — but
    only on the auto route: a fixed iteration count has no witness, so
    there are no stragglers to re-enter."""
    ServeConfig(iters="auto", ragged=True, max_continuations=2)
    with pytest.raises(ValueError, match="auto"):
        ServeConfig(iters=4, ragged=True, max_continuations=2)


@pytest.mark.slow
class TestRaggedContinuation:
    def test_ragged_straggler_bitwise_parity_and_iter_conservation(self):
        """Ragged x continuation composition (ISSUE 16): a ragged
        straggler exited at the quorum re-enters the RAGGED route as a
        row carrying its mid-flight columns and remaining budget, and
        lands on BITWISE the same final columns, after the same TOTAL
        iteration count, as its lone ragged run to convergence (the
        dense two-tier correctness lock, on the page axis)."""
        rng = np.random.default_rng(21)
        # Seeded convergence disparity: the 10x rows settle by iter 10,
        # the 1x row needs 12 — so the 0.5 quorum exits the cold
        # dispatch with the 1x row mid-flight.
        easy = [
            (10.0 * rng.normal(size=(CFG.channels, 16, 16))).astype(
                np.float32
            )
            for _ in range(2)
        ]
        hard = rng.normal(size=(CFG.channels, 16, 16)).astype(np.float32)
        scfg = dataclasses.replace(
            SCFG, exit_threshold=1e-3, max_auto_iters=16,
            exit_quorum=0.5, max_continuations=3,
        )
        params = init_glom(jax.random.PRNGKey(0), CFG)
        eng = InferenceEngine(CFG, scfg, params=params, name="e0")
        b = DynamicBatcher(engines=[eng])
        tickets = [b.submit(easy[0]), b.submit(hard), b.submit(easy[1])]
        b.start()  # all queued before the worker runs: ONE cold dispatch
        outs = [t.result(timeout=300.0) for t in tickets]
        summary = b.summary_record()
        b.stop()
        assert summary["n_served"] == 3 and summary["n_failed"] == 0
        assert summary["n_continued"] >= 1  # the hard row re-entered
        # Reference: the hard row alone on the ragged route, run to its
        # own convergence in ONE dispatch (a quorum of one row is the
        # row itself).
        ref_eng = InferenceEngine(
            CFG,
            dataclasses.replace(
                scfg, exit_quorum=1.0, max_continuations=0
            ),
            params=params,
        )
        ref = ref_eng.infer_ragged(
            *_flat(
                [_patchify_host(hard, 4)], pages_sig=ref_eng.pick_pages(4)
            )
        )
        levels, total_iters, _ = outs[1]
        assert total_iters == ref.iters_run
        np.testing.assert_array_equal(
            levels, np.asarray(ref.levels)[0:16]
        )


def test_ragged_ladder_must_hold_a_full_row():
    """A ragged_pages ladder below one full-resolution row's page count
    would turn every full-size request into a dispatch-time failure
    that reads as an engine fault — rejected at construction."""
    scfg = dataclasses.replace(SCFG, ragged_pages=(2,))
    with pytest.raises(ValueError, match="full-resolution row"):
        InferenceEngine(CFG, scfg, key=jax.random.PRNGKey(0))


def test_mixed_pool_fleet_rejected():
    """Pages mode must cover the whole fleet: a pool-less engine next to
    pooled siblings would receive PageHits its host path cannot use —
    a loud constructor error, never a mid-traffic worker crash."""
    scfg = dataclasses.replace(
        SCFG, ragged=False, column_cache_bytes=1 << 20
    )
    pooled = InferenceEngine(CFG, scfg, key=jax.random.PRNGKey(0), name="e0")
    plain = InferenceEngine(
        CFG, dataclasses.replace(scfg, page_pool_pages=0),
        key=jax.random.PRNGKey(0), name="e1",
    )
    with pytest.raises(ValueError, match="no page pool"):
        DynamicBatcher(engines=[pooled, plain])


def test_page_state_bytes_live_form():
    assert page_state_bytes(CFG, SCFG, 4) == 4 * CFG.levels * CFG.dim * 4
    bf16 = dataclasses.replace(SCFG, compute_dtype="bfloat16")
    assert page_state_bytes(CFG, bf16, 4) == 4 * CFG.levels * CFG.dim * 2
