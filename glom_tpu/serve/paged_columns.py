"""Device-resident paged column memory: the HBM page pool behind the warm
serving path and ragged admission.

PR 8's ColumnCache killed repeated convergence but kept the cached
`[n, L, d]` columns HOST-side: every warm frame re-uploaded its columns
over PCIe before the forward even started. Following *Ragged Paged
Attention* (PAPERS.md) — pages as the residency unit, a page table as the
indirection — this module keeps warm column state WHERE IT IS USED:

  * ONE preallocated device buffer of `[n_pages, page_tokens, L, d]` per
    engine (the pool), sized by `ServeConfig.page_pool_pages` and priced
    in the same analytic live-bytes form as `column_state_bytes`;
  * a host-side PAGE TABLE mapping `(session_id, block ordinal)` to page
    indices — allocation hands out free pages (no contiguity needed: the
    dispatch gathers by index), free returns them, and `defrag()`
    compacts allocated pages toward low indices (a device-to-device
    gather/scatter, stamped `page_defrag`) so long-lived pools keep
    gather locality;
  * write-back on resolve copies converged columns DEVICE-TO-DEVICE into
    the session's owned pages (`write_back`: a memoized jitted scatter —
    the columns never visit the host), and the warm dispatch assembles
    `levels0` IN-GRAPH via a page-index take (engine.py's paged
    signatures) — zero host<->device levels0 transfer on the warm path,
    which tests/test_paged_columns.py asserts via the engine's transfer
    counters;
  * pages are PINNED while a dispatch reads them (`pin`/`unpin`): the
    cache's eviction policy skips pinned blocks, so an in-flight gather
    can never read pages a concurrent eviction re-issued. Engine death
    force-frees (the dispatch that observed the death demotes its rows
    to cold on requeue — serve/batcher.py).

The pool buffer defaults to copy-on-write (a write-back builds the next
buffer functionally and swaps the reference under the lock): in-flight
dispatches keep reading the buffer they snapshotted, so the scatter is
never donated. That is correct but doubles pool traffic — every
write-back copies the whole pool to change a few pages. With
`ServeConfig.pool_aliasing` the pool promotes write-backs to DONATED
in-graph updates behind an explicit serialization seam: dispatches pin
the buffer they read (`acquire_read`/`release_read` — the engine wraps
every pool dispatch in the pair), a write-back donates the buffer ONLY
when no read pin is live (bumping the pool EPOCH — the donated buffer
is dead, the epoch names the new one), and falls back to CoW LOUDLY
(stamped `alias_fallback`, counted) when a snapshot is pinned. Chain
compaction and defrag stay CoW (their src/dst page ranges can overlap
— an in-place scatter would read half-moved state). Refcounted shared
bases and delta chains are unaffected: the page TABLE never aliases,
only the buffer update does. Aliasing off is byte-for-byte the old CoW
behavior. XLA reuses the dropped buffer's HBM either way; under CoW the
transient double-residency window is one write-back wide, under
aliasing it is gone.

Accounting: every alloc/free/defrag is a stamped "serve" event
(`page_alloc`/`page_free`/`page_defrag`, docs/OBSERVABILITY.md) and
`record()` rolls pages/bytes/churn into the batcher summary in the same
live-bytes vocabulary the column cache uses.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional

import numpy as np


def resolve_page_tokens(cfg, scfg) -> int:
    """The page granularity in patch tokens. An explicit
    `ServeConfig.page_tokens` must tile the full-resolution row (the
    bucket route maps `[bucket, num_patches]` onto whole pages); 0
    resolves to the largest divisor of `num_patches` that is at most
    min(64, num_patches // 4) — at least FOUR pages per full-resolution
    row (coarser and a half-resolution row pays a whole-row page, which
    is the pad tax back again), capped at 64 tokens so the page-index
    take stays coarse-grained on big models (flagship 256 patches ->
    64-token pages)."""
    n = cfg.num_patches
    if scfg.page_tokens > 0:
        if n % scfg.page_tokens != 0:
            raise ValueError(
                f"page_tokens {scfg.page_tokens} does not divide "
                f"num_patches {n} (pages must tile the full-resolution row)"
            )
        return scfg.page_tokens
    for cand in range(max(1, min(64, n // 4)), 0, -1):
        if n % cand == 0:
            return cand
    return n  # pragma: no cover — cand=1 always divides


def pages_for_tokens(n_tokens: int, page_tokens: int) -> int:
    """ceil(n_tokens / page_tokens): pages one row's columns occupy."""
    if n_tokens < 1:
        raise ValueError(f"n_tokens {n_tokens} must be >= 1")
    return -(-n_tokens // page_tokens)


def page_state_bytes(cfg, scfg, page_tokens: Optional[int] = None) -> int:
    """The live-bytes price of ONE pool page — `page_tokens x levels x
    dim` in the serving dtype, the per-page unit `column_state_bytes`
    decomposes into (docs/SERVING.md, "Paged column memory")."""
    pt = page_tokens if page_tokens is not None else resolve_page_tokens(cfg, scfg)
    itemsize = 2 if scfg.compute_dtype == "bfloat16" else 4
    return pt * cfg.levels * cfg.dim * itemsize


class _Block:
    """One session's page-table entry: the ordered page indices holding
    its column state (block ordinal k covers tokens [k*pt, (k+1)*pt))."""

    __slots__ = ("pages", "n_tokens", "pins")

    def __init__(self, pages: List[int], n_tokens: int):
        self.pages = pages
        self.n_tokens = n_tokens
        self.pins = 0


class _BaseBlock:
    """A DELTA-mode base: whole-row page set, REFCOUNTED so sessions with
    content-identical bases (hash-matched at write-back) alias the same
    read-only pool pages — two cameras on one scene pay for one base.
    Pages free only when the last referencing session drops."""

    __slots__ = ("pages", "n_tokens", "refs", "hkey")

    def __init__(self, pages: List[int], n_tokens: int, hkey=None):
        self.pages = pages
        self.n_tokens = n_tokens
        self.refs = 1
        self.hkey = hkey


class _DeltaBlock:
    """A DELTA-mode session entry: a (possibly shared) base plus a chain
    of frame-to-frame deltas, each a {block ordinal -> page index} map of
    ONLY the pages whose column residual exceeded `delta_page_atol`. The
    effective page map is base overridden by the chain newest-last —
    base+Σdeltas resolved to plain page indices, so reconstruction rides
    the SAME in-graph page-index take every paged dispatch already uses."""

    __slots__ = ("base", "deltas", "n_tokens", "pins")

    def __init__(self, base: _BaseBlock, n_tokens: int):
        self.base = base
        self.deltas: List[Dict[int, int]] = []
        self.n_tokens = n_tokens
        self.pins = 0

    def effective(self) -> List[int]:
        pages = list(self.base.pages)
        for d in self.deltas:
            for ordinal, page in d.items():
                pages[ordinal] = page
        return pages

    def delta_pages(self) -> List[int]:
        return [p for d in self.deltas for p in d.values()]


class PagedColumnPool:
    """Fixed-size device page pool + host page table for one engine.

    `mesh`/`pool_sharding` route the buffer through a NamedSharding on
    the page axis (the sharded engines' pool — parallel/serve_mesh.py
    gathers it with a registered all_gather); None keeps the
    single-device buffer. The injectable `writer` delivers the stamped
    page events through the usual writer-else-flight path."""

    def __init__(
        self,
        cfg,
        scfg,
        *,
        writer=None,
        name: str = "engine0",
        pool_sharding=None,
    ):
        import jax
        import jax.numpy as jnp

        if scfg.page_pool_pages < 1:
            raise ValueError(
                f"page_pool_pages {scfg.page_pool_pages} must be >= 1 to "
                "build a pool (0 disables paged columns — resolve first)"
            )
        self.cfg = cfg
        self.scfg = scfg
        self.name = name
        self.writer = writer
        self.page_tokens = resolve_page_tokens(cfg, scfg)
        self.n_pages = int(scfg.page_pool_pages)
        self.page_bytes = page_state_bytes(cfg, scfg, self.page_tokens)
        self.pool_bytes = self.n_pages * self.page_bytes
        self._dtype = (
            jnp.bfloat16 if scfg.compute_dtype == "bfloat16" else jnp.float32
        )
        self._lock = threading.Lock()
        self._table: Dict[str, _Block] = {}
        self._free: List[int] = list(range(self.n_pages - 1, -1, -1))
        self._scatter_fns: Dict = {}
        self._gather_fns: Dict = {}
        self.n_allocs = 0
        self.n_frees = 0
        self.n_alloc_fails = 0
        self.n_writebacks = 0
        self.n_defrag_moves = 0
        self._pages_peak = 0
        # Delta streaming (ServeConfig.delta_streaming, docs/SERVING.md
        # "Delta streaming"): sessions written through write_back_stream
        # hold a refcounted BASE plus a chain of sparse deltas instead of
        # a whole-row block. The atol decides what counts as a changed
        # page (0.0 = any changed BIT — bitcast-compared, so -0.0 vs 0.0
        # still stores); the chain compacts at the cap; content-identical
        # bases alias via the hash index.
        self.delta = bool(getattr(scfg, "delta_streaming", False))
        self.delta_page_atol = float(getattr(scfg, "delta_page_atol", 0.0))
        self.delta_chain_cap = int(getattr(scfg, "delta_chain_cap", 4))
        self._share = bool(getattr(scfg, "delta_base_share", True))
        self._hash_index: Dict[str, _BaseBlock] = {}
        self._residual_fns: Dict = {}
        self._delta_scatter_fns: Dict = {}
        self._compact_fns: Dict = {}
        self.n_delta_writes = 0
        self.n_delta_pages = 0
        self.n_delta_empty = 0
        self.n_compactions = 0
        self.n_compact_deferred = 0
        self.n_base_shares = 0
        self.n_superseded = 0
        # In-place aliasing (ServeConfig.pool_aliasing, module
        # docstring): donated write-backs gated by the read-pin count;
        # the epoch counts buffer identities (every donated update kills
        # the previous buffer). Bytes-moved counters price the A/B in
        # the analytic live-bytes form — a CoW write copies the whole
        # pool to change a few pages, an aliased write moves only the
        # pages written.
        self.aliasing = bool(getattr(scfg, "pool_aliasing", False))
        self._epoch = 0
        self._read_pins = 0
        self.n_alias_writes = 0
        self.n_alias_fallbacks = 0
        self.alias_bytes_moved = 0
        self.cow_bytes_moved = 0
        # THE preallocated buffer: pages x page_tokens x L x d, zeros.
        # One allocation up front — warm traffic never grows it.
        # Computed where it will live (a NamedSharding on the serve mesh,
        # one device for a pinned engine, else the default device): a
        # zeros-then-device_put, or jnp.zeros(device=...), stages the
        # whole pool through the default device first (four pinned
        # engines peaked device 0 at 4.4 GB on the four-chip host).
        self._buffer = jax.jit(
            lambda: jnp.zeros(
                (self.n_pages, self.page_tokens, cfg.levels, cfg.dim),
                self._dtype,
            ),
            out_shardings=pool_sharding,
        )()
        self._pool_sharding = pool_sharding

    # -- the page table ----------------------------------------------------

    def buffer(self):
        """The current pool buffer (snapshot for one dispatch). The
        reference swaps copy-on-write under the lock; pinned pages stay
        valid in every later buffer, so a dispatch built from (buffer,
        pinned indices) reads a consistent state. NOT safe as a dispatch
        handle under aliasing — a donated write-back invalidates
        unpinned snapshots; the dispatch path takes `acquire_read()`
        instead (glom-lint's donation-safety flags the bare form)."""
        with self._lock:
            return self._buffer

    def acquire_read(self):
        """Pin the CURRENT buffer for one dispatch and return it. While
        any read pin is live, write-backs cannot donate (they fall back
        to CoW, stamped `alias_fallback`), so the returned reference
        stays valid for the dispatch's whole lifetime — snapshot through
        block_until_ready. Pair with `release_read()` in a finally.
        With aliasing off this is `buffer()` plus a free counter."""
        with self._lock:
            if self._buffer is None:
                raise RuntimeError(
                    f"pool {self.name!r} released: dispatch against a "
                    "drained replica is a fleet-bookkeeping bug"
                )
            self._read_pins += 1
            return self._buffer

    def release_read(self) -> None:
        """Drop one dispatch's read pin (the `acquire_read` pair)."""
        with self._lock:
            if self._read_pins <= 0:
                raise RuntimeError(
                    "release_read without a matching acquire_read"
                )
            self._read_pins -= 1

    def read_pins(self) -> int:
        with self._lock:
            return self._read_pins

    def epoch(self) -> int:
        """Buffer-identity counter: bumps on every DONATED write-back
        (the previous buffer is dead). CoW swaps keep the epoch — the
        old snapshot stays readable."""
        with self._lock:
            return self._epoch

    def pages_used(self) -> int:
        with self._lock:
            return self.n_pages - len(self._free)

    def bytes_in_use(self) -> int:
        return self.pages_used() * self.page_bytes

    def holds(self, session_id: str) -> bool:
        with self._lock:
            return session_id in self._table

    def lookup(self, session_id: str, *, pin: bool = False):
        """(pages, n_tokens) for the session, or None. pin=True takes a
        read pin (the dispatch path): the block survives eviction until
        the matching unpin — cache eviction skips pinned blocks."""
        with self._lock:
            blk = self._table.get(session_id)
            if blk is None:
                return None
            if pin:
                blk.pins += 1
            if isinstance(blk, _DeltaBlock):
                # The EFFECTIVE map: base overridden by the delta chain
                # newest-last — base+Σdeltas as plain page indices, ready
                # for the same in-graph page-index take as any warm
                # dispatch (zero levels0 H2D, the PR 11 contract).
                return blk.effective(), blk.n_tokens
            return list(blk.pages), blk.n_tokens

    def unpin(self, session_id: str) -> None:
        with self._lock:
            blk = self._table.get(session_id)
            if blk is not None and blk.pins > 0:
                blk.pins -= 1

    def is_pinned(self, session_id: str) -> bool:
        with self._lock:
            blk = self._table.get(session_id)
            return blk is not None and blk.pins > 0

    def alloc(self, session_id: str, n_tokens: int) -> Optional[List[int]]:
        """Own `ceil(n_tokens / page_tokens)` pages under the session
        key. An existing block of the right size is reused (the steady
        warm path — a stream's frames share a resolution); a resized
        session frees and re-allocates. None when the pool lacks free
        pages (the CALLER evicts — residency policy lives in the cache,
        mechanism here)."""
        need = pages_for_tokens(n_tokens, self.page_tokens)
        events = []
        with self._lock:
            blk = self._table.get(session_id)
            if isinstance(blk, _DeltaBlock):
                raise ValueError(
                    f"session {session_id!r} holds a delta-chain block; "
                    "whole-state alloc() does not compose with "
                    "write_back_stream on one key"
                )
            if blk is not None:
                if len(blk.pages) == need:
                    blk.n_tokens = n_tokens
                    return list(blk.pages)
                events.append(self._free_locked(session_id, blk, "resize"))
            if len(self._free) < need:
                self.n_alloc_fails += 1
                self._flush(events)
                return None
            pages = [self._free.pop() for _ in range(need)]
            self._table[session_id] = _Block(pages, n_tokens)
            self.n_allocs += 1
            used = self.n_pages - len(self._free)
            self._pages_peak = max(self._pages_peak, used)
            events.append(
                {
                    "event": "page_alloc",
                    "session": session_id,
                    "n_pages": need,
                    "n_tokens": n_tokens,
                    "pages_used": used,
                    "pages_total": self.n_pages,
                    "bytes_in_use": used * self.page_bytes,
                }
            )
        self._flush(events)
        return list(pages)

    def free(self, session_id: str, *, reason: str = "evict") -> int:
        """Return the session's pages to the free list (eviction, TTL
        expiry, engine-death invalidation). Returns pages freed (0 when
        absent). Force-frees pinned blocks too — the only force callers
        are death/invalidation paths whose in-flight readers demote to
        cold on requeue."""
        with self._lock:
            blk = self._table.get(session_id)
            if blk is None:
                return 0
            ev = self._free_locked(session_id, blk, reason)
            n = ev["n_pages"]
        self._flush([ev])
        return n

    def free_all(self, *, reason: str = "engine-death") -> int:
        """Drop EVERY block — the engine just died; its pool state is
        unreachable warmth. One stamped page_free with the totals."""
        with self._lock:
            n = self.n_pages - len(self._free)
            sessions = len(self._table)
            if not sessions:
                return 0
            self._table.clear()
            self._hash_index.clear()
            self._free = list(range(self.n_pages - 1, -1, -1))
            self.n_frees += sessions
            ev = {
                "event": "page_free",
                "reason": reason,
                "n_sessions": sessions,
                "n_pages": n,
                "pages_used": 0,
                "bytes_in_use": 0,
            }
        self._flush([ev])
        return n

    def _free_locked(self, session_id: str, blk, reason: str) -> dict:
        # Caller holds the lock. A delta block frees its chain pages and
        # DECREFS its base — the base's pages return to the free list
        # only when the last aliasing session drops (refcount 0), which
        # is exactly what "two cameras pay for one base" requires on the
        # way OUT too.
        self._table.pop(session_id, None)
        if isinstance(blk, _DeltaBlock):
            freed = blk.delta_pages()
            blk.base.refs -= 1
            if blk.base.refs == 0:
                freed = freed + blk.base.pages
                if blk.base.hkey is not None:
                    stored = self._hash_index.get(blk.base.hkey)
                    if stored is blk.base:
                        del self._hash_index[blk.base.hkey]
        else:
            freed = blk.pages
        self._free.extend(reversed(freed))
        self.n_frees += 1
        used = self.n_pages - len(self._free)
        return {
            "event": "page_free",
            "session": session_id,
            "reason": reason,
            "n_pages": len(freed),
            "pages_used": used,
            "bytes_in_use": used * self.page_bytes,
        }

    # -- device-side data movement ----------------------------------------

    def _donate_jit_kw(self, donate: bool) -> dict:
        """donate_argnums for the pool arg, TPU only — CPU jit ignores
        donation (with a warning), so off-TPU the "aliased" write is the
        same functional scatter and only the accounting differs. The
        seam logic (pins, epoch, fallback) is platform-independent."""
        if not donate:
            return {}
        import jax

        if jax.devices()[0].platform != "tpu":
            return {}
        return {"donate_argnums": (0,)}

    def _writeback_fn(self, k: int, n: int, *, donate: bool = False):
        """Memoized jitted scatter for a (pages, tokens) shape class:
        pad the row's [n, L, d] columns to whole pages and set them at
        the block's indices. Functional update — the result is the NEXT
        pool buffer. donate=True is the aliasing seam's in-place
        variant: the input pool buffer is donated, so the scatter
        updates the pages in place instead of copying the pool (see
        module docstring; only `_scatter_locked` may call it)."""
        key = (k, n, bool(donate))
        if key not in self._scatter_fns:
            import jax
            import jax.numpy as jnp

            pt = self.page_tokens
            L, d = self.cfg.levels, self.cfg.dim
            dtype = self._dtype

            def fn(pool, idx, row):
                flat = jnp.pad(
                    row.astype(dtype), ((0, k * pt - n), (0, 0), (0, 0))
                )
                return pool.at[idx].set(flat.reshape(k, pt, L, d))

            self._scatter_fns[key] = jax.jit(
                fn, **self._donate_jit_kw(donate)
            )
        return self._scatter_fns[key]

    def _scatter_locked(
        self,
        make_fn,
        args,
        *,
        pages_written: int,
        session_id: Optional[str],
        events: List[dict],
    ) -> None:
        """The ONE write seam (caller holds the lock): route a buffer
        update through aliasing when enabled AND no dispatch holds a
        read pin — the donated scatter kills the previous buffer, so
        the epoch bumps and `page_alias` stamps what moved. Any live
        pin forces the CoW fallback LOUDLY (`alias_fallback` + counter):
        correct, just back to paying the whole-pool copy. make_fn(donate)
        returns the memoized jitted scatter for that variant."""
        if self.aliasing and self._read_pins == 0:
            self._buffer = make_fn(True)(self._buffer, *args)
            self._epoch += 1
            self.n_alias_writes += 1
            self.alias_bytes_moved += pages_written * self.page_bytes
            events.append(
                {
                    "event": "page_alias",
                    "session": session_id,
                    "n_pages": pages_written,
                    "epoch": self._epoch,
                    "bytes_moved": pages_written * self.page_bytes,
                }
            )
        else:
            self._buffer = make_fn(False)(self._buffer, *args)
            self.cow_bytes_moved += self.pool_bytes
            if self.aliasing:
                self.n_alias_fallbacks += 1
                events.append(
                    {
                        "event": "alias_fallback",
                        "session": session_id,
                        "n_pages": pages_written,
                        "read_pins": self._read_pins,
                        "bytes_moved": self.pool_bytes,
                    }
                )

    def write_back(self, session_id: str, levels_row, n_tokens: int) -> bool:
        """Copy one resolved row's converged columns device-to-device
        into the session's pages (allocating on first write). levels_row
        is the DEVICE [n_tokens, L, d] slice of the dispatch output — it
        never visits the host. False when allocation failed (pool full:
        the cache's eviction pressure path frees and retries)."""
        pages = self.alloc(session_id, n_tokens)
        if pages is None:
            return False
        import jax.numpy as jnp

        k = len(pages)
        idx = jnp.asarray(np.asarray(pages, np.int32))
        events: List[dict] = []
        with self._lock:
            # The scatter runs under the lock: buffer swaps serialize
            # (two concurrent write-backs must not both extend the same
            # parent buffer and drop one update on the swap), and the
            # read-pin check that gates donation is atomic with the
            # update itself.
            self._scatter_locked(
                lambda donate: self._writeback_fn(
                    k, n_tokens, donate=donate
                ),
                (idx, levels_row),
                pages_written=k,
                session_id=session_id,
                events=events,
            )
            self.n_writebacks += 1
        self._flush(events)
        return True

    # -- delta streaming (docs/SERVING.md, "Delta streaming") --------------

    def _alloc_raw_locked(self, need: int) -> Optional[List[int]]:
        """Pop `need` free pages (caller holds the lock), or None."""
        if len(self._free) < need:
            self.n_alloc_fails += 1
            return None
        return [self._free.pop() for _ in range(need)]

    def _idx(self, pages) -> "object":
        import jax.numpy as jnp

        return jnp.asarray(np.asarray(pages, np.int32))

    def _residual_fn(self, k: int, n: int):
        """Memoized per-page residual probe for a (pages, tokens) shape
        class: compare one row's new [n, L, d] columns against its
        current effective pages and return ([k] any-bit-differs bool,
        [k] max-abs f32) — the host picks by atol (0.0 reads the BITCAST
        channel, so the stored/skipped decision is literally bitwise)."""
        key = (k, n)
        if key not in self._residual_fns:
            import jax
            import jax.numpy as jnp

            pt = self.page_tokens
            dtype = self._dtype
            int_t = jnp.int16 if dtype == jnp.bfloat16 else jnp.int32

            def fn(pool, eff_idx, row):
                flat = jnp.pad(
                    row.astype(dtype), ((0, k * pt - n), (0, 0), (0, 0))
                ).reshape(k, pt, *row.shape[1:])
                cur = pool[eff_idx]
                bits = jnp.any(
                    jax.lax.bitcast_convert_type(cur, int_t)
                    != jax.lax.bitcast_convert_type(flat, int_t),
                    axis=(1, 2, 3),
                )
                diff = jnp.max(
                    jnp.abs(
                        cur.astype(jnp.float32) - flat.astype(jnp.float32)
                    ),
                    axis=(1, 2, 3),
                )
                return bits, diff

            self._residual_fns[key] = jax.jit(fn)
        return self._residual_fns[key]

    def _delta_scatter_fn(self, kc: int, k: int, n: int, *, donate: bool = False):
        """Memoized scatter of `kc` CHANGED pages out of a row's `k`:
        (pool, dst_idx [kc], row [n, L, d], ordinals [kc]) -> next pool
        buffer (functional by default; donate=True is the aliasing
        seam's in-place variant — only `_scatter_locked` may call it).
        Delta pages scatter to FRESH pool pages, so the donated update
        never overwrites a page any effective map still resolves to."""
        key = (kc, k, n, bool(donate))
        if key not in self._delta_scatter_fns:
            import jax
            import jax.numpy as jnp

            pt = self.page_tokens
            dtype = self._dtype

            def fn(pool, dst_idx, row, ordinals):
                flat = jnp.pad(
                    row.astype(dtype), ((0, k * pt - n), (0, 0), (0, 0))
                ).reshape(k, pt, *row.shape[1:])
                return pool.at[dst_idx].set(flat[ordinals])

            self._delta_scatter_fns[key] = jax.jit(
                fn, **self._donate_jit_kw(donate)
            )
        return self._delta_scatter_fns[key]

    def _copy_pages_fn(self, k: int):
        """Memoized device-to-device page copy: (pool, src_idx [k],
        dst_idx [k]) -> next buffer with dst pages holding src content
        (reads the PRE-move buffer, so src/dst never alias mid-copy)."""
        if k not in self._compact_fns:
            import jax

            def fn(pool, src_idx, dst_idx):
                return pool.at[dst_idx].set(pool[src_idx])

            self._compact_fns[k] = jax.jit(fn)
        return self._compact_fns[k]

    def delta_chain_len(self, session_id: str) -> Optional[int]:
        with self._lock:
            blk = self._table.get(session_id)
            if not isinstance(blk, _DeltaBlock):
                return None
            return len(blk.deltas)

    def base_refs(self, session_id: str) -> Optional[int]:
        with self._lock:
            blk = self._table.get(session_id)
            if not isinstance(blk, _DeltaBlock):
                return None
            return blk.base.refs

    def _compact_locked(self, session_id: str, blk: _DeltaBlock, events) -> bool:
        """Fold base+Σdeltas into ONE base, device-to-device, under the
        pool's pin/conservation rules: a PINNED session defers (an
        in-flight dispatch snapshotted its chain's page indices — freeing
        them would let a re-allocation rewrite what that snapshot's NEXT
        buffer read resolves to); a sole-owner base compacts IN PLACE
        (only overridden ordinals copy); a SHARED base copies on write
        into fresh pages so the aliasing sessions keep theirs bit-for-bit.
        Returns True when the chain actually folded."""
        if blk.pins > 0:
            self.n_compact_deferred += 1
            return False
        overridden = sorted({o for d in blk.deltas for o in d.keys()})
        eff = blk.effective()
        chain_pages = blk.delta_pages()
        if blk.base.refs == 1:
            # In place: copy each overridden ordinal's newest page into
            # the base's page; unchanged ordinals already hold the base.
            if overridden:
                src = [eff[o] for o in overridden]
                dst = [blk.base.pages[o] for o in overridden]
                fn = self._copy_pages_fn(len(overridden))
                self._buffer = fn(
                    self._buffer, self._idx(src), self._idx(dst)
                )
            if blk.base.hkey is not None:
                # Content changed: the registered hash no longer names
                # these pages — de-index so no future session aliases a
                # stale fingerprint.
                stored = self._hash_index.get(blk.base.hkey)
                if stored is blk.base:
                    del self._hash_index[blk.base.hkey]
                blk.base.hkey = None
        else:
            fresh = self._alloc_raw_locked(len(blk.base.pages))
            if fresh is None:
                # Pool too tight to copy-on-write a shared base: keep the
                # over-cap chain (correct, just unfolded) and let
                # eviction pressure free room first.
                self.n_compact_deferred += 1
                return False
            fn = self._copy_pages_fn(len(eff))
            self._buffer = fn(self._buffer, self._idx(eff), self._idx(fresh))
            blk.base.refs -= 1
            blk.base = _BaseBlock(fresh, blk.n_tokens, hkey=None)
        blk.deltas = []
        if chain_pages:
            self._free.extend(reversed(chain_pages))
            used = self.n_pages - len(self._free)
            events.append(
                {
                    "event": "page_free",
                    "session": session_id,
                    "reason": "compact",
                    "n_pages": len(chain_pages),
                    "pages_used": used,
                    "bytes_in_use": used * self.page_bytes,
                }
            )
        self.n_compactions += 1
        return True

    def write_back_stream(
        self,
        session_id: str,
        levels_row,
        n_tokens: int,
        *,
        content_hash: Optional[str] = None,
    ) -> Optional[dict]:
        """The DELTA-mode write-back: first store lays down (or aliases)
        a BASE; every later store probes the row's per-page residual
        against the session's effective state and appends a delta holding
        ONLY the pages past `delta_page_atol` (atol 0.0 = any changed
        bit). The chain folds base <- base+Σdeltas at `delta_chain_cap`.
        `content_hash` (the batcher's hash of the exact row bytes) keys
        cross-stream base sharing. Returns an info dict for the cache's
        stamped cache_delta/cache_compact/cache_share events, or None
        when the pool lacks pages (the cache evicts and retries)."""
        need = pages_for_tokens(n_tokens, self.page_tokens)
        events: List[dict] = []
        info: Optional[dict] = None
        with self._lock:
            blk = self._table.get(session_id)
            if blk is not None and not isinstance(blk, _DeltaBlock):
                events.append(
                    self._free_locked(session_id, blk, "delta-convert")
                )
                blk = None
            if blk is not None and blk.n_tokens != n_tokens:
                events.append(self._free_locked(session_id, blk, "resize"))
                blk = None
            if blk is None:
                shared = None
                if content_hash is not None and self._share:
                    cand = self._hash_index.get(content_hash)
                    if cand is not None and cand.n_tokens == n_tokens:
                        shared = cand
                if shared is not None:
                    shared.refs += 1
                    self._table[session_id] = _DeltaBlock(shared, n_tokens)
                    self.n_base_shares += 1
                    info = {
                        "kind": "share",
                        "pages_written": 0,
                        "chain_len": 0,
                        "base_refs": shared.refs,
                    }
                else:
                    pages = self._alloc_raw_locked(need)
                    if pages is None:
                        self._flush(events)
                        return None
                    self._scatter_locked(
                        lambda donate: self._writeback_fn(
                            need, n_tokens, donate=donate
                        ),
                        (self._idx(pages), levels_row),
                        pages_written=need,
                        session_id=session_id,
                        events=events,
                    )
                    self.n_writebacks += 1
                    base = _BaseBlock(pages, n_tokens, hkey=content_hash)
                    if content_hash is not None and self._share:
                        self._hash_index[content_hash] = base
                    self._table[session_id] = _DeltaBlock(base, n_tokens)
                    self.n_allocs += 1
                    used = self.n_pages - len(self._free)
                    self._pages_peak = max(self._pages_peak, used)
                    events.append(
                        {
                            "event": "page_alloc",
                            "session": session_id,
                            "n_pages": need,
                            "n_tokens": n_tokens,
                            "delta_base": True,
                            "pages_used": used,
                            "pages_total": self.n_pages,
                            "bytes_in_use": used * self.page_bytes,
                        }
                    )
                    info = {
                        "kind": "base",
                        "pages_written": need,
                        "chain_len": 0,
                        "base_refs": 1,
                    }
            else:
                eff = blk.effective()
                probe = self._residual_fn(need, n_tokens)
                bits, diff = probe(self._buffer, self._idx(eff), levels_row)
                if self.delta_page_atol <= 0.0:
                    changed_mask = np.asarray(bits)
                else:
                    changed_mask = np.asarray(diff) > self.delta_page_atol
                ordinals = [int(o) for o in np.nonzero(changed_mask)[0]]
                if not ordinals:
                    self.n_delta_empty += 1
                    info = {
                        "kind": "delta",
                        "pages_written": 0,
                        "chain_len": len(blk.deltas),
                        "empty": True,
                    }
                else:
                    pages = self._alloc_raw_locked(len(ordinals))
                    if pages is None:
                        self._flush(events)
                        return None
                    self._scatter_locked(
                        lambda donate: self._delta_scatter_fn(
                            len(ordinals), need, n_tokens, donate=donate
                        ),
                        (
                            self._idx(pages),
                            levels_row,
                            self._idx(ordinals),
                        ),
                        pages_written=len(ordinals),
                        session_id=session_id,
                        events=events,
                    )
                    blk.deltas.append(dict(zip(ordinals, pages)))
                    self.n_delta_writes += 1
                    self.n_delta_pages += len(ordinals)
                    self.n_writebacks += 1
                    # Prune SUPERSEDED chain pages (unpinned blocks
                    # only): an ordinal overridden by a newer delta is
                    # never read again — the effective map takes the
                    # newest — so its page returns to the pool NOW
                    # instead of waiting for the cap compaction. This is
                    # what keeps a stream that keeps perturbing the same
                    # region at ~constant pages. Pinned blocks defer: an
                    # in-flight dispatch snapshotted those indices.
                    if blk.pins == 0 and len(blk.deltas) > 1:
                        covered = set(blk.deltas[-1].keys())
                        kept = [blk.deltas[-1]]
                        superseded: List[int] = []
                        for d in reversed(blk.deltas[:-1]):
                            for o in [o for o in d if o in covered]:
                                superseded.append(d.pop(o))
                            if d:
                                covered |= set(d.keys())
                                kept.append(d)
                        kept.reverse()
                        blk.deltas = kept
                        if superseded:
                            self._free.extend(reversed(superseded))
                            self.n_superseded += len(superseded)
                            used = self.n_pages - len(self._free)
                            events.append(
                                {
                                    "event": "page_free",
                                    "session": session_id,
                                    "reason": "superseded",
                                    "n_pages": len(superseded),
                                    "pages_used": used,
                                    "bytes_in_use": used * self.page_bytes,
                                }
                            )
                    used = self.n_pages - len(self._free)
                    self._pages_peak = max(self._pages_peak, used)
                    events.append(
                        {
                            "event": "page_alloc",
                            "session": session_id,
                            "n_pages": len(ordinals),
                            "n_tokens": n_tokens,
                            "delta": True,
                            "chain_len": len(blk.deltas),
                            "pages_used": used,
                            "pages_total": self.n_pages,
                            "bytes_in_use": used * self.page_bytes,
                        }
                    )
                    info = {
                        "kind": "delta",
                        "pages_written": len(ordinals),
                        "chain_len": len(blk.deltas),
                    }
                    if len(blk.deltas) >= self.delta_chain_cap:
                        if self._compact_locked(session_id, blk, events):
                            info["kind"] = "compact"
                            info["chain_len"] = 0
                        else:
                            info["compact_deferred"] = True
            if info is not None:
                blk = self._table[session_id]
                info["session_pages"] = len(blk.delta_pages()) + (
                    len(blk.base.pages) if blk.base.refs == 1 else 0
                )
                info["base_pages"] = len(blk.base.pages)
                info["base_refs"] = blk.base.refs
        self._flush(events)
        return info

    def read_block(self, session_id: str) -> Optional[np.ndarray]:
        """HOST copy of one session's [n_tokens, L, d] columns — the
        tests' parity window and the cold-path fallback, NOT the warm
        dispatch path (which takes pages in-graph)."""
        got = self.lookup(session_id)
        if got is None:
            return None
        pages, n_tokens = got
        key = len(pages)
        if key not in self._gather_fns:
            import jax

            pt = self.page_tokens
            L, d = self.cfg.levels, self.cfg.dim

            def fn(pool, idx):
                return pool[idx].reshape(key * pt, L, d)

            self._gather_fns[key] = jax.jit(fn)
        import jax.numpy as jnp

        # The gather runs OUTSIDE the lock but under a read pin: without
        # it an aliased write-back could donate (kill) the snapshot
        # mid-gather.
        buf = self.acquire_read()
        try:
            flat = self._gather_fns[key](
                buf, jnp.asarray(np.asarray(pages, np.int32))
            )
            return np.asarray(flat)[:n_tokens]
        finally:
            self.release_read()

    def defrag(self) -> int:
        """Compact allocated, UNPINNED pages toward low indices (one
        device gather/scatter from the pre-move buffer, so overlapping
        src/dst ranges read original values). Returns pages moved;
        stamps page_defrag. Allocation never NEEDS this (the take is
        index-addressed) — it is a locality/accounting pass for
        long-lived pools."""
        import jax.numpy as jnp

        if self.delta:
            # Delta blocks interleave shared bases and chain pages; the
            # take is index-addressed, so locality compaction buys
            # nothing a chain compaction doesn't — skip rather than move
            # pages a sibling session aliases.
            return 0
        with self._lock:
            blocks = sorted(
                (
                    (sid, blk)
                    for sid, blk in self._table.items()
                    if blk.pins == 0
                ),
                key=lambda kv: min(kv[1].pages),
            )
            pinned_pages = {
                p
                for blk in self._table.values()
                if blk.pins > 0
                for p in blk.pages
            }
            # Targets: lowest indices not owned by pinned blocks.
            targets = iter(
                i for i in range(self.n_pages) if i not in pinned_pages
            )
            src: List[int] = []
            dst: List[int] = []
            for sid, blk in blocks:
                new_pages = []
                for p in blk.pages:
                    t = next(targets)
                    new_pages.append(t)
                    if t != p:
                        src.append(p)
                        dst.append(t)
                blk.pages = new_pages
            if not src:
                return 0
            used_pages = {
                p for blk in self._table.values() for p in blk.pages
            }
            self._free = sorted(
                (i for i in range(self.n_pages) if i not in used_pages),
                reverse=True,
            )
            self._buffer = self._buffer.at[
                jnp.asarray(np.asarray(dst, np.int32))
            ].set(self._buffer[jnp.asarray(np.asarray(src, np.int32))])
            self.n_defrag_moves += len(src)
            ev = {
                "event": "page_defrag",
                "n_moved": len(src),
                "pages_used": self.n_pages - len(self._free),
                "pages_total": self.n_pages,
            }
        self._flush([ev])
        return len(src)

    def release(self) -> None:
        """A drained engine's device release (serve/elastic.py): free
        every block (one stamped page_free totals event), then drop the
        HBM buffer reference itself — the bytes a scaled-in replica was
        holding. The pool stays a valid accounting husk (record() keeps
        working) but any further write/read fails loudly on the None
        buffer — a dispatch against a released pool is a
        fleet-bookkeeping bug, not a degraded mode."""
        self.free_all(reason="drain-release")
        with self._lock:
            self._buffer = None

    # -- observability -----------------------------------------------------

    def _flush(self, events) -> None:
        from glom_tpu.serve.events import emit_serve

        for rec in events:
            if rec:
                emit_serve(self.writer, dict(rec, engine=self.name))

    def record(self) -> dict:
        """The pool rollup the batcher nests under its summary: capacity
        and churn in the live-bytes form (pages x page_state_bytes), the
        conservation pair the churn test reads (pages_used + pages_free
        == pages_total always)."""
        with self._lock:
            used = self.n_pages - len(self._free)
            rec = {
                "page_tokens": self.page_tokens,
                "page_bytes": self.page_bytes,
                "pages_total": self.n_pages,
                "pages_used": used,
                "pages_free": len(self._free),
                "pages_peak": self._pages_peak,
                "pool_bytes": self.pool_bytes,
                "bytes_in_use": used * self.page_bytes,
                "n_sessions": len(self._table),
                "n_allocs": self.n_allocs,
                "n_frees": self.n_frees,
                "n_alloc_fails": self.n_alloc_fails,
                "n_writebacks": self.n_writebacks,
                "n_defrag_moves": self.n_defrag_moves,
                # CoW traffic priced analytically (whole pool per CoW
                # write) — the aliasing A/B's baseline side, present
                # with aliasing off so the comparison has both arms.
                "cow_bytes_moved": self.cow_bytes_moved,
            }
            if self.aliasing:
                writes = self.n_alias_writes + self.n_alias_fallbacks
                rec["alias"] = {
                    "epoch": self._epoch,
                    "n_alias_writes": self.n_alias_writes,
                    "n_alias_fallbacks": self.n_alias_fallbacks,
                    "alias_bytes_moved": self.alias_bytes_moved,
                    "alias_rate": (
                        round(self.n_alias_writes / writes, 4)
                        if writes else None
                    ),
                }
            if self.delta:
                # The delta rollup the acceptance reads: bytes_per_stream
                # is ACTUAL pool pages over live sessions (shared bases
                # and sparse chains both shrink it — the several-fold
                # drop the delta cache exists for), chain stats price the
                # reconstruction depth, and the atol is the explicit
                # tolerance stamp the compare gate reads (0.0 = bitwise).
                chains = [
                    len(b.deltas)
                    for b in self._table.values()
                    if isinstance(b, _DeltaBlock)
                ]
                rec["delta"] = {
                    "delta_page_atol": self.delta_page_atol,
                    "delta_chain_cap": self.delta_chain_cap,
                    "bytes_per_stream": (
                        round(used * self.page_bytes / len(self._table), 1)
                        if self._table
                        else None
                    ),
                    "delta_chain_len_mean": (
                        round(sum(chains) / len(chains), 3) if chains else 0.0
                    ),
                    "delta_chain_len_max": max(chains) if chains else 0,
                    "n_delta_writes": self.n_delta_writes,
                    "n_delta_pages": self.n_delta_pages,
                    "n_delta_empty": self.n_delta_empty,
                    "n_compactions": self.n_compactions,
                    "n_compact_deferred": self.n_compact_deferred,
                    "n_base_shares": self.n_base_shares,
                    "n_superseded": self.n_superseded,
                }
            return rec


def resolve_page_pool(
    cfg, scfg, *, writer=None, name: str = "engine0", pool_sharding=None
) -> Optional[PagedColumnPool]:
    """The one config -> pool resolution: `page_pool_pages > 0` builds
    the device pool, 0 keeps the PR 8 host-array column cache."""
    if getattr(scfg, "page_pool_pages", 0) <= 0:
        return None
    return PagedColumnPool(
        cfg, scfg, writer=writer, name=name, pool_sharding=pool_sharding
    )
