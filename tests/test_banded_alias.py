"""Block-banded ragged consensus parity matrix + in-place pool aliasing
torture suite (ISSUE 16).

THE PARITY CONTRACT is per-row page spans: the banded route is BITWISE
the windowed gather on every row's span (valid tokens AND intra-row pad
slots) at every iteration count. Tokens in completely UNUSED trailing
pages sit outside the contract: row_len == 0 hard-masks every slot, so
their softmax is a uniform average over route-dependent clamped garbage
values — and they are semantically dead (the convergence witness masks
them, the batcher resolves only row slices, write-backs and straggler
carries are per-row spans). The Pallas kernel holds the fused-route
TOLERANCE contract instead (an online softmax reorders the reduction);
off-TPU the wrapper falls back to the jnp banded route, which keeps CPU
serving on the bitwise bar end to end.

The aliasing half tortures the write seam: donated in-place write-backs
gated by read pins, the loud copy-on-write fallback when a dispatch has
the buffer pinned, byte-moved accounting (aliased writes move pages,
CoW writes move the whole pool), refcounted shared-base isolation, and
pool conservation under churn with aliasing on.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from glom_tpu.models.core import init_glom
from glom_tpu.serve.engine import InferenceEngine
from glom_tpu.serve.early_exit import (
    banded_ragged_consensus_attention,
    ragged_consensus_attention,
    ragged_window_bytes,
)
from glom_tpu.serve.paged_columns import PagedColumnPool, pages_for_tokens
from glom_tpu.utils.config import GlomConfig, ServeConfig

CFG = GlomConfig(dim=32, levels=3, image_size=16, patch_size=4)  # n=16
SCFG = ServeConfig(
    buckets=(1, 2, 4), max_batch=4, max_delay_ms=2.0,
    iters="auto", max_auto_iters=6, exit_threshold=0.0,
    page_pool_pages=32, page_tokens=4, ragged=True,
    dispatch_retries=0,
)
PT = 4


def _layout(counts, pt=PT, pages_sig=None):
    """Page-aligned (row_start, row_len, T, starts) for per-token maps —
    the host mirror of serve/early_exit.ragged_row_layout."""
    pages = [pages_for_tokens(c, pt) for c in counts]
    P = pages_sig if pages_sig is not None else sum(pages)
    T = P * pt
    row_start = np.zeros((T,), np.int32)
    row_len = np.zeros((T,), np.int32)
    starts = []
    off = 0
    for c, k in zip(counts, pages):
        s = off * pt
        starts.append(s)
        row_start[s:s + k * pt] = s
        row_len[s:s + k * pt] = c
        off += k
    return row_start, row_len, T, starts


def _spans(arr, counts, starts, pt=PT):
    """Each row's FULL page span (valid tokens + intra-row pads) — the
    unit the parity contract covers."""
    out = []
    for c, s in zip(counts, starts):
        out.append(np.asarray(arr)[s:s + pages_for_tokens(c, pt) * pt])
    return out


class TestBandedParityMatrix:
    COUNTS = [5, 3, 16, 1]  # mixed: intra-row pads on three rows

    def _levels(self, T, seed=7):
        rng = np.random.default_rng(seed)
        return jnp.asarray(
            rng.normal(size=(T, CFG.levels, CFG.dim)).astype(np.float32)
        )

    def test_attention_matches_per_row_span(self):
        """One attention application: banded == windowed on every row
        span, window == the largest row's page band, to float32 rounding:
        two programs whose softmax sums run in different orders on this
        JAX (largest difference seen 1.8e-7 at values up to 2.3; 59% of
        elements differ), so not bit for bit."""
        row_start, row_len, T, starts = _layout(self.COUNTS)
        lv = self._levels(T)
        window = pages_for_tokens(max(self.COUNTS), PT) * PT
        rs, rl = jnp.asarray(row_start), jnp.asarray(row_len)
        win = ragged_consensus_attention(
            lv, row_start=rs, row_len=rl, window=window
        )
        band = banded_ragged_consensus_attention(
            lv, row_start=rs, row_len=rl, window=window, page_tokens=PT
        )
        for a, b in zip(
            _spans(win, self.COUNTS, starts),
            _spans(band, self.COUNTS, starts),
        ):
            np.testing.assert_allclose(a, b, rtol=0, atol=7e-7)

    def test_engine_threshold0_windowed_matches_banded(self):
        """Cross-route at the engine: a threshold-0 mixed dispatch lands
        on the same row spans under both attentions, at the same
        iteration count, for every iteration budget — to float32
        rounding (the two attentions' reduction orders differ on this
        JAX: largest difference seen 1.8e-7 over budgets 1, 3, 6, up to
        65% of elements)."""
        params = init_glom(jax.random.PRNGKey(0), CFG)
        ew = InferenceEngine(CFG, SCFG, params=params, name="w")
        eb = InferenceEngine(
            CFG,
            dataclasses.replace(SCFG, ragged_attention="banded"),
            params=params,
            name="b",
        )
        rng = np.random.default_rng(11)
        counts = [16, 4]
        row_start, row_len, T, starts = _layout(
            counts, pages_sig=ew.pick_pages(5)
        )
        flat = np.zeros((T, CFG.patch_dim), np.float32)
        for c, s in zip(counts, starts):
            flat[s:s + c] = rng.normal(size=(c, CFG.patch_dim))
        for budget in (1, 3, 6):
            rw = ew.infer_ragged(flat, counts, iters_override=budget)
            rb = eb.infer_ragged(flat, counts, iters_override=budget)
            assert rw.iters_run == rb.iters_run
            for a, b in zip(
                _spans(rw.levels, counts, starts),
                _spans(rb.levels, counts, starts),
            ):
                np.testing.assert_allclose(a, b, rtol=0, atol=7e-7)

    def test_banded_full_res_row_bitwise_equals_dense_cold(self):
        """The banded route keeps the windowed route's cross-route lock:
        a full-resolution banded ragged row reproduces the dense
        engine's cold dispatch bitwise."""
        params = init_glom(jax.random.PRNGKey(0), CFG)
        eb = InferenceEngine(
            CFG,
            dataclasses.replace(SCFG, ragged_attention="banded"),
            params=params,
            name="b",
        )
        ed = InferenceEngine(
            CFG,
            dataclasses.replace(SCFG, ragged=False, page_pool_pages=0),
            params=params,
            name="d",
        )
        rng = np.random.default_rng(12)
        img = (100.0 * rng.normal(size=(3, 16, 16))).astype(np.float32)
        from glom_tpu.serve.batcher import _patchify_host

        row = _patchify_host(img, 4)
        T = eb.pick_pages(4) * PT
        flat = np.zeros((T, CFG.patch_dim), np.float32)
        flat[:16] = row
        ragged = eb.infer_ragged(flat, [16])
        dense = ed.infer(img[None], n_valid=1)
        assert ragged.iters_run == dense.iters_run
        np.testing.assert_array_equal(
            np.asarray(dense.levels[0]), np.asarray(ragged.levels)[0:16]
        )

    def test_pad_poisoning_invariance(self):
        """Garbage in intra-row pad slots and unused trailing pages must
        not move any row span — the banded mask is airtight."""
        row_start, row_len, T, starts = _layout(self.COUNTS, pages_sig=10)
        lv = np.asarray(self._levels(T))
        rs, rl = jnp.asarray(row_start), jnp.asarray(row_len)
        window = pages_for_tokens(max(self.COUNTS), PT) * PT
        clean = banded_ragged_consensus_attention(
            jnp.asarray(lv), row_start=rs, row_len=rl, window=window,
            page_tokens=PT,
        )
        dirty = lv.copy()
        valid = np.zeros((T,), bool)
        for c, s in zip(self.COUNTS, starts):
            valid[s:s + c] = True
        dirty[~valid] = 1e30  # poison pads AND unused trailing pages
        poisoned = banded_ragged_consensus_attention(
            jnp.asarray(dirty), row_start=rs, row_len=rl, window=window,
            page_tokens=PT,
        )
        for c, s in zip(self.COUNTS, starts):
            # VALID tokens only: intra-row pad slots were themselves
            # poisoned (their q changed), but no valid token may see it.
            np.testing.assert_array_equal(
                np.asarray(clean)[s:s + c], np.asarray(poisoned)[s:s + c]
            )

    def test_pallas_interpret_matches_jnp_banded(self):
        """The fused kernel's tolerance contract: interpret-mode Pallas
        vs the jnp banded reference (online softmax reorders the
        reduction — close, not bitwise)."""
        from glom_tpu.kernels import banded_ragged_consensus

        row_start, row_len, T, starts = _layout(self.COUNTS)
        lv = self._levels(T, seed=9)
        window = pages_for_tokens(max(self.COUNTS), PT) * PT
        rs, rl = jnp.asarray(row_start), jnp.asarray(row_len)
        ref = banded_ragged_consensus_attention(
            lv, row_start=rs, row_len=rl, window=window, page_tokens=PT
        )
        fused = banded_ragged_consensus(
            lv, row_start=rs, row_len=rl, window=window, page_tokens=PT,
            interpret=True,
        )
        for a, b in zip(
            _spans(ref, self.COUNTS, starts),
            _spans(fused, self.COUNTS, starts),
        ):
            np.testing.assert_allclose(a, b, rtol=2e-6, atol=2e-6)

    def test_window_bytes_banded_is_page_tokens_fold_smaller(self):
        """The number the --banded-ab gate prices: the banded working
        set is exactly page_tokens-fold below the windowed one."""
        w = ragged_window_bytes(64, 16, 3, 32, 4, PT, attention="windowed")
        b = ragged_window_bytes(64, 16, 3, 32, 4, PT, attention="banded")
        assert w == b * PT
        with pytest.raises(ValueError):
            ragged_window_bytes(64, 16, 3, 32, 4, PT, attention="dense")


class TestPoolAliasing:
    def _pool(self, **over):
        scfg = dataclasses.replace(SCFG, pool_aliasing=True, **over)
        return PagedColumnPool(CFG, scfg, name="t")

    def _row(self, n=16, seed=0):
        rng = np.random.default_rng(seed)
        return jnp.asarray(
            rng.normal(size=(n, CFG.levels, CFG.dim)).astype(np.float32)
        )

    def test_alias_write_bumps_epoch_and_moves_page_bytes(self):
        pool = self._pool()
        assert pool.write_back("sA", self._row(), 16)
        assert pool.epoch() == 1
        rec = pool.record()
        assert rec["alias"]["n_alias_writes"] == 1
        assert rec["alias"]["n_alias_fallbacks"] == 0
        assert rec["alias"]["alias_bytes_moved"] == 4 * pool.page_bytes
        assert rec["cow_bytes_moved"] == 0
        assert rec["alias"]["alias_rate"] == 1.0

    def test_pinned_read_forces_loud_cow_fallback(self):
        """The serialization seam itself: a dispatch holding a read pin
        forces the concurrent write-back onto copy-on-write (the pinned
        buffer stays valid), epoch does NOT advance (same logical
        contents, old identity preserved), and the fallback is stamped."""
        pool = self._pool()
        pinned = pool.acquire_read()
        assert pool.read_pins() == 1
        assert pool.write_back("sA", self._row(seed=1), 16)
        rec = pool.record()
        assert rec["alias"]["n_alias_fallbacks"] == 1
        assert rec["alias"]["n_alias_writes"] == 0
        assert pool.epoch() == 0
        assert rec["cow_bytes_moved"] == pool.pool_bytes
        # The pinned buffer survived the write — still all zeros.
        assert not np.asarray(pinned).any()
        pool.release_read()
        # Pin gone: the next write aliases again.
        assert pool.write_back("sA", self._row(seed=2), 16)
        assert pool.epoch() == 1
        assert pool.record()["alias"]["alias_rate"] == 0.5

    def test_read_pin_discipline_is_loud(self):
        pool = self._pool()
        with pytest.raises(RuntimeError, match="release_read"):
            pool.release_read()
        pool.release()
        with pytest.raises(RuntimeError, match="released"):
            pool.acquire_read()

    def test_aliasing_off_is_byte_for_byte_unchanged(self):
        """The acceptance lock: the same write/read sequence through an
        aliasing pool and a CoW pool lands on identical bytes; the CoW
        pool's record carries no alias block."""
        on = self._pool()
        off = PagedColumnPool(CFG, SCFG, name="t0")
        for seed, sid in ((3, "sA"), (4, "sB"), (5, "sA")):
            row = self._row(seed=seed)
            assert on.write_back(sid, row, 16)
            assert off.write_back(sid, row, 16)
        for sid in ("sA", "sB"):
            np.testing.assert_array_equal(
                on.read_block(sid), off.read_block(sid)
            )
        rec = off.record()
        assert "alias" not in rec
        assert rec["cow_bytes_moved"] == 3 * off.pool_bytes
        assert on.record()["cow_bytes_moved"] == 0

    def test_conservation_under_churn_with_aliasing(self):
        """The pool conservation invariant survives aliased churn with
        interleaved read pins (pins only steer writes onto the CoW
        fallback — they never leak pages or double-free)."""
        pool = self._pool()
        rng = np.random.default_rng(6)
        pins = 0
        for step in range(120):
            op = rng.integers(0, 4)
            sid = f"s{rng.integers(0, 6)}"
            if op == 0:
                pool.write_back(sid, self._row(seed=step), 16)
            elif op == 1:
                pool.free(sid)
            elif op == 2 and pins < 2:
                pool.acquire_read()
                pins += 1
            elif op == 3 and pins > 0:
                pool.release_read()
                pins -= 1
            rec = pool.record()
            assert (
                rec["pages_used"] + rec["pages_free"] == rec["pages_total"]
            )
        rec = pool.record()
        writes = (
            rec["alias"]["n_alias_writes"] + rec["alias"]["n_alias_fallbacks"]
        )
        assert writes == rec["n_writebacks"]
        assert (
            rec["alias"]["alias_bytes_moved"] + rec["cow_bytes_moved"]
            == rec["alias"]["n_alias_writes"] * 4 * pool.page_bytes
            + rec["alias"]["n_alias_fallbacks"] * pool.pool_bytes
        )

    def test_shared_base_refcount_isolation_under_aliasing(self):
        """Delta-mode shared bases stay isolated when writes alias: a
        second stream aliasing the same content-hashed base, then
        appending its own delta, must not move the first stream's
        reconstruction by a single bit."""
        pool = self._pool(
            delta_streaming=True, ragged=False, delta_page_atol=0.0
        )
        base_row = self._row(seed=7)
        h = "hash-base"
        assert pool.write_back_stream("sA", base_row, 16, content_hash=h)
        assert pool.write_back_stream("sB", base_row, 16, content_hash=h)
        assert pool.base_refs("sA") == 2  # shared, refcounted
        before_a = np.array(pool.read_block("sA"))
        # sB diverges: its delta pages are fresh allocations, scattered
        # in place (aliased) — never into the shared base's pages.
        drift = np.asarray(base_row).copy()
        drift[5] += 1.0
        assert pool.write_back_stream("sB", jnp.asarray(drift), 16)
        np.testing.assert_array_equal(pool.read_block("sA"), before_a)
        np.testing.assert_array_equal(
            pool.read_block("sB"),
            np.asarray(drift, dtype=np.asarray(before_a).dtype),
        )
        assert pool.record()["alias"]["n_alias_writes"] >= 2

    def test_alias_events_are_stamped(self):
        """page_alias / alias_fallback events ride the pool's writer
        with the engine stamp — the observability the A/B gate and
        `telemetry compare` read."""

        class Sink:
            def __init__(self):
                self.records = []

            def write(self, rec):
                self.records.append(rec)

        sink = Sink()
        scfg = dataclasses.replace(SCFG, pool_aliasing=True)
        pool = PagedColumnPool(CFG, scfg, writer=sink, name="e9")
        pool.write_back("sA", self._row(seed=8), 16)
        pinned = pool.acquire_read()
        pool.write_back("sA", self._row(seed=9), 16)
        pool.release_read()
        del pinned
        ev = [r.get("event") for r in sink.records]
        assert "page_alias" in ev and "alias_fallback" in ev
        alias = next(r for r in sink.records if r["event"] == "page_alias")
        assert alias["engine"] == "e9"
        assert alias["n_pages"] == 4 and alias["epoch"] == 1
        assert alias["bytes_moved"] == 4 * pool.page_bytes
        fb = next(r for r in sink.records if r["event"] == "alias_fallback")
        assert fb["read_pins"] == 1
        assert fb["bytes_moved"] == pool.pool_bytes
