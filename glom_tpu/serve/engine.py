"""The inference engine: params + one AOT-compiled forward per signature.

The trainer's throughput discipline (compile once, static shapes, donated
buffers) applied to serving. `Glom.__call__` jit-compiles on FIRST call —
fine for a notebook, a multi-second latency cliff for the first user to hit
a fresh shape in production. The engine inverts that:

  * every (bucket batch, iters route, warm/cold) signature is AOT-compiled
    — lowered and compiled EXPLICITLY via jax.jit(...).lower(...).compile()
    from abstract shapes, no dummy batch materialized — either eagerly by
    `warmup()` before traffic or lazily on first miss (which emits a
    "serve" warmup event either way, so a mid-traffic compile is always
    attributable in the stream);
  * compiled programs are memoized by signature for the engine's lifetime;
    the batcher only ever dispatches bucket shapes, so steady-state traffic
    never compiles;
  * the input buffers (image batch, and the warm levels carry on
    continuation dispatches) are donated on TPU (ServeConfig.donate=None
    resolves by platform) so XLA reuses the padded batch's HBM for outputs;
  * every forward returns (levels, iters_run, row_converged, row_iters):
    the fixed route stamps its constant (all rows "converged" by fiat),
    the "auto" route (serve/early_exit.glom_forward_tiered) returns the
    actual executed count plus PER-ROW convergence — the two-tier early
    exit's raw material (docs/SERVING.md, "Continuation queue").

Sharded route (parallel/serve_mesh.py): when ServeConfig.mesh_data/.mesh_seq
describe a mesh, every signature compiles the manual shard_map forward over
('data', 'seq') instead — same buckets, same warmup, same donation, and the
compile-time counting trace records the per-dispatch collective wire bytes
(telemetry/counters.py) onto the signature's stats record.

Latency accounting rides telemetry/sinks.StepTimeStats per signature
(compile split out, p50/p95/p99/max), drained by `stats_records()` into
schema "serve" events.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from glom_tpu.models.core import GlomParams, glom_forward, init_glom
from glom_tpu.serve.early_exit import glom_forward_tiered
from glom_tpu.telemetry import schema
from glom_tpu.telemetry.sinks import StepTimeStats
from glom_tpu.utils.config import GlomConfig, ServeConfig


class ServeResult(NamedTuple):
    """One dispatched batch's outcome. `levels` is the full padded
    [bucket, n, L, d] state (callers slice their valid rows); `iters_run`
    is a host int (the auto route's early-exit count, or the fixed
    budget); `latency_s` is dispatch-to-fetch wall time for the batch.
    `row_converged`/`row_iters` are the PER-ROW tiered-exit outcome
    ([bucket] host arrays; fixed-route dispatches mark every row
    converged — there are no stragglers without a witness).
    `levels0_h2d_bytes` is what the dispatch UPLOADED of warm column
    state (host levels0 x attempts; 0 on the cold and PAGED routes — the
    zero the ragged bench gate asserts). `phases` is the engine-side
    latency decomposition when ServeConfig.phase_split is on
    ({"h2d_ms", "resolve_ms"} raw floats, summed across retry attempts;
    the batcher derives device_ms as the engine wall minus both and adds
    its own queue_wait/pack phases — docs/OBSERVABILITY.md, "Capacity
    observatory")."""

    levels: jax.Array
    iters_run: int
    latency_s: float
    bucket: int
    compiled: bool  # True when this call paid the signature's compile
    row_converged: Optional[np.ndarray] = None
    row_iters: Optional[np.ndarray] = None
    levels0_h2d_bytes: int = 0
    phases: Optional[dict] = None


class RaggedServeResult(NamedTuple):
    """One RAGGED dispatch's outcome. `levels` is the FLAT page-aligned
    [T, L, d] device state (row r's columns at [start_r, start_r +
    n_patches[r]) — serve/early_exit.ragged_row_layout); `pages` is the
    compiled page-count signature this dispatch rode."""

    levels: jax.Array
    iters_run: int
    latency_s: float
    pages: int
    compiled: bool
    row_converged: np.ndarray
    row_iters: np.ndarray
    levels0_h2d_bytes: int = 0
    phases: Optional[dict] = None


def mosaic_calls(compiled) -> int:
    """Mosaic (Pallas TPU) custom calls in a compiled program's text —
    what the warmup event reports next to `use_pallas`, which only echoes
    the config flag: off-TPU or at a shape a kernel does not take the
    dispatch gives way to XLA and this reads 0."""
    return compiled.as_text().count("tpu_custom_call")


def _resolve_donate(donate: Optional[bool]) -> bool:
    if donate is not None:
        return donate
    return jax.devices()[0].platform == "tpu"


class InferenceEngine:
    """Owns params + memoized AOT-compiled forwards per bucket signature.

    The engine is the device-side half of the serving stack (the host-side
    half is serve/batcher.DynamicBatcher, which owns admission, padding,
    and the continuation queue). It is thread-compatible the way jax
    itself is: compiled executables may be CALLED from any thread;
    `warmup`/first-miss compilation is serialized by the GIL + dict
    memoization. `name` labels this engine's records in multi-engine
    fan-out deployments (one engine per replica behind one batcher).
    `device` pins a single-device engine — params, page pool, staged
    inputs and compiled programs — to that device, so one process runs
    one replica per chip; None leaves everything on JAX's default device.
    A serve mesh places the engine instead and excludes `device`.
    """

    def __init__(
        self,
        cfg: GlomConfig,
        scfg: Optional[ServeConfig] = None,
        *,
        params: Optional[GlomParams] = None,
        key: Optional[jax.Array] = None,
        writer=None,
        retry=None,
        fault_hook=None,
        mesh=None,
        device=None,
        name: str = "engine0",
    ):
        self.cfg = cfg
        self.scfg = scfg = scfg if scfg is not None else ServeConfig()
        self.name = name
        if params is None:
            key = key if key is not None else jax.random.PRNGKey(0)
            params = init_glom(key, cfg)
        self.writer = writer
        self._donate = _resolve_donate(scfg.donate)
        self._compute_dtype = (
            jnp.bfloat16 if scfg.compute_dtype == "bfloat16" else None
        )
        # Serve mesh: an explicit mesh wins; else resolve from the config
        # (mesh axes of 1 mean the single-device route).
        if mesh is None and (scfg.mesh_data > 1 or scfg.mesh_seq > 1):
            from glom_tpu.parallel.serve_mesh import make_serve_mesh

            mesh = make_serve_mesh(scfg)
        self.mesh = mesh
        # Params live where the programs run: on the pinned device, or
        # replicated over the serve mesh (the sharded signatures'
        # in_sharding) — params left on the default device would be
        # re-transferred by every dispatch.
        self._device_sharding = None
        if device is not None:
            if mesh is not None:
                raise ValueError(
                    "device pins a single-device engine; a serve mesh "
                    "places the engine itself"
                )
            self._device_sharding = jax.sharding.SingleDeviceSharding(device)
            params = jax.device_put(params, self._device_sharding)
        elif mesh is not None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            params = jax.device_put(params, NamedSharding(mesh, P()))
        self.params = params
        if mesh is not None and cfg.num_patches % scfg.mesh_seq != 0:
            raise ValueError(
                f"patches {cfg.num_patches} not divisible by "
                f"mesh_seq={scfg.mesh_seq}"
            )
        self._compiled: Dict[Tuple, object] = {}
        self._cold_levels: Optional[np.ndarray] = None
        self._stats: Dict[Tuple, StepTimeStats] = {}
        self._comm: Dict[Tuple, dict] = {}  # sharded route: counted wire bytes
        self._shardings: Dict = {}  # warm mode -> (in_sh, out_sh)
        # Per-collective wall-time (docs/OBSERVABILITY.md, "Capacity
        # observatory"): resolved like telemetry_level. Only the sharded
        # route has collectives — a single-device engine resolves any
        # configured mode to "off", loudly, so no record can claim a
        # timing harness with no sites to time. "full" brackets every
        # execution of every witness/gather site with io_callbacks
        # inserted at the AOT trace; "sampled" re-dispatches each site as
        # its own timed sub-graph every collective_timing_interval-th
        # dispatch (telemetry/comm_time.py).
        from glom_tpu.telemetry.counters import (
            CollectiveTimeLog,
            resolve_collective_timing,
        )

        if mesh is not None:
            self.collective_timing = resolve_collective_timing(
                scfg.collective_timing, supports_full=True
            )
        else:
            resolve_collective_timing(scfg.collective_timing)  # validate
            if scfg.collective_timing != "off":
                import warnings

                warnings.warn(
                    "collective_timing has no sites on a single-device "
                    "engine (no collectives) — resolving 'off'",
                    stacklevel=2,
                )
            self.collective_timing = "off"
        self._coll_log = (
            CollectiveTimeLog() if self.collective_timing == "full" else None
        )
        self._coll_sites: Dict[Tuple, dict] = {}  # (site, shape) -> site
        self._coll_sampler = None
        self._coll_samples: list = []  # sampled-mode stamped records
        self._coll_dispatches = 0
        self._coll_lock = threading.Lock()
        # Serializes the SAMPLING PASS itself (sub-graph compiles + timed
        # dispatches) separately from the cheap counter/buffer lock, so a
        # concurrent dispatch's tick never stalls behind another thread's
        # sample — the "cost lands on one dispatch in N" contract.
        self._coll_sample_lock = threading.Lock()
        # Host-side toggle for the latency decomposition's engine half
        # (the input sync + fetch attribution in infer): resolved from
        # the config, but a plain attribute so the phase-overhead A/B
        # can flip it per arm on SHARED engines without a recompile —
        # the split never touches the compiled program.
        self.phase_split = bool(getattr(scfg, "phase_split", True))
        # Paged column memory (serve/paged_columns.py): page_pool_pages
        # > 0 preallocates THIS engine's device page pool — warm column
        # state lives in HBM pages, assembled in-graph by a page-index
        # take (zero host->device levels0 bytes on the paged warm path).
        # On the sharded route the pool buffer shards its PAGE axis over
        # 'data' and the forward gathers it with a registered all_gather
        # (parallel/serve_mesh.py).
        from glom_tpu.serve.paged_columns import resolve_page_pool

        pool_sharding = self._device_sharding
        if mesh is not None and getattr(scfg, "page_pool_pages", 0) > 0:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            if scfg.page_pool_pages % scfg.mesh_data != 0:
                raise ValueError(
                    f"page_pool_pages {scfg.page_pool_pages} not divisible "
                    f"by mesh_data={scfg.mesh_data} (the pool's page axis "
                    "shards over 'data')"
                )
            pool_sharding = NamedSharding(mesh, P("data"))
        self.pool = resolve_page_pool(
            cfg, scfg, writer=writer, name=name, pool_sharding=pool_sharding
        )
        if getattr(scfg, "ragged", False):
            if mesh is not None:
                raise ValueError(
                    "ragged admission rides the single-device route only "
                    "(the sharded ragged gather is a follow-on; "
                    "docs/SERVING.md)"
                )
            if cfg.local_consensus_radius > 0:
                raise ValueError(
                    "ragged admission requires local_consensus_radius == 0"
                )
            from glom_tpu.serve.paged_columns import (
                pages_for_tokens,
                resolve_page_tokens,
            )

            ppr = pages_for_tokens(
                cfg.num_patches, resolve_page_tokens(cfg, scfg)
            )
            if scfg.ragged_pages and max(scfg.ragged_pages) < ppr:
                # Admission allows any row up to num_patches tokens —
                # a ladder that cannot hold one full-resolution row
                # would turn every such request into a dispatch-time
                # failure that reads as an ENGINE fault.
                raise ValueError(
                    f"ragged_pages top {max(scfg.ragged_pages)} is below "
                    f"one full-resolution row's {ppr} pages — every "
                    "full-size request would fail at dispatch"
                )
        # Host levels0 upload accounting (the PR 8 warm path's PCIe tax;
        # the paged route's reason to exist): total bytes of warm column
        # state this engine transferred host->device. The ragged bench
        # gate asserts this stays ZERO on the paged warm path.
        self.levels0_h2d_bytes_total = 0
        # Transient-dispatch retry (glom_tpu/resilience/retry.py): None
        # resolves from the config (scfg.dispatch_retries; 0 disables).
        # The policy is watchdog-aware — a FLAPPING backend retries (the
        # gap closes), a DOWN backend fails fast into the shed path.
        if retry is None and scfg.dispatch_retries > 0:
            from glom_tpu.resilience.retry import RetryPolicy

            retry = RetryPolicy(
                retries=scfg.dispatch_retries,
                backoff_s=scfg.retry_backoff_ms / 1e3,
                writer=writer,
                site=f"{name}-dispatch",
            )
        self.retry = retry
        # Chaos seam (glom_tpu/resilience/faults.dispatch_fault): called
        # once per dispatch ATTEMPT with {bucket, n_valid, attempt}; a
        # raise here is exactly a transient backend failure as far as the
        # retry policy and the batcher are concerned. None in production.
        self._fault_hook = fault_hook
        # Flipped by release() after a graceful drain: the engine is an
        # evidence husk — its device state is freed and it must never
        # serve again (the batcher already removed it from the fleet).
        self.released = False

    # -- signatures --------------------------------------------------------

    @property
    def iters_key(self):
        """The route component of every signature: "auto" or the resolved
        fixed iteration count."""
        if self.scfg.iters == "auto":
            return "auto"
        return (
            self.scfg.iters
            if self.scfg.iters is not None
            else self.cfg.default_iters
        )

    @property
    def auto_budget(self) -> int:
        """The auto route's full iteration budget — the per-REQUEST cap
        the two-tier continuation path never exceeds (a straggler's
        continuation runs the REMAINING budget, so initial + continuation
        iterations total at most this)."""
        return (
            self.scfg.max_auto_iters
            if self.scfg.max_auto_iters is not None
            else self.cfg.default_iters
        )

    def cold_levels(self) -> np.ndarray:
        """The cold-start column state for ONE row — `init_levels`
        broadcast to [n_patches, L, d] in the serving dtype, exactly the
        init the forward builds when no `levels0` is carried. The batcher
        uses it to fold COLD rows into a warm-signature dispatch (mixed
        warm/cold buckets): a cold row whose levels0 is this state lands
        on bitwise the same columns as a cold dispatch, because the
        forward's own init IS this broadcast (locked by tests). Host
        array, memoized (read-only — callers copy into their staging
        buffer)."""
        if self._cold_levels is None:
            lv_dtype = (
                self._compute_dtype if self._compute_dtype is not None
                else np.float32
            )
            init = np.asarray(self.params.init_levels, lv_dtype)  # [L, d]
            self._cold_levels = np.ascontiguousarray(
                np.broadcast_to(init[None], (self.cfg.num_patches, *init.shape))
            )
        return self._cold_levels

    def pick_bucket(self, n: int) -> int:
        """Smallest precompile bucket admitting n requests. n above the
        largest bucket is the BATCHER's invariant to maintain (it never
        gathers more than max_batch <= max bucket); a direct caller gets
        the loud error."""
        if n < 1:
            raise ValueError(f"n={n} must be >= 1")
        for b in self.scfg.buckets:
            if n <= b:
                return b
        raise ValueError(
            f"n={n} exceeds the largest bucket {max(self.scfg.buckets)}"
        )

    @property
    def ragged_rows(self) -> int:
        """Static row capacity of every ragged signature (row slots past
        the gathered count mask out with n_patches 0)."""
        return self.scfg.max_batch

    @property
    def ragged_page_buckets(self) -> Tuple[int, ...]:
        """The ascending page-count ladder the ragged signatures
        precompile — `ServeConfig.ragged_pages` when set, else
        full-row-page strides from one full-resolution row up to
        max_batch rows (at most ~8 signatures). DENSER than buckets x
        pages-per-row on purpose: the ladder rounds a dispatch UP to its
        page count, and a coarse ladder hands the round-up right back to
        the pad tax the ragged route exists to kill."""
        if self.scfg.ragged_pages:
            return tuple(self.scfg.ragged_pages)
        from glom_tpu.serve.paged_columns import (
            pages_for_tokens,
            resolve_page_tokens,
        )

        ppr = pages_for_tokens(
            self.cfg.num_patches, resolve_page_tokens(self.cfg, self.scfg)
        )
        top = self.scfg.max_batch * ppr
        stride = ppr * max(1, -(-self.scfg.max_batch // 8))
        return tuple(range(stride, top + 1, stride))

    def pick_pages(self, n_pages: int) -> int:
        """Smallest ragged ladder entry admitting n_pages total pages
        (the page-axis pick_bucket)."""
        if n_pages < 1:
            raise ValueError(f"n_pages={n_pages} must be >= 1")
        for p in self.ragged_page_buckets:
            if n_pages <= p:
                return p
        raise ValueError(
            f"n_pages={n_pages} exceeds the largest ragged signature "
            f"{max(self.ragged_page_buckets)}"
        )

    def signature(
        self,
        bucket,
        iters_override: Optional[int] = None,
        *,
        auto_budget: Optional[int] = None,
        warm=False,
    ) -> Tuple:
        if iters_override is not None:
            route = iters_override
        elif auto_budget is not None and self.iters_key == "auto":
            route = f"auto:{auto_budget}"
        else:
            route = self.iters_key
        return (bucket, route, self.scfg.use_pallas, warm)

    # -- compilation -------------------------------------------------------

    def _build_fn(
        self,
        bucket: int,
        iters_override: Optional[int] = None,
        *,
        auto_budget: Optional[int] = None,
        warm: bool = False,
    ):
        """The pure forward for one signature: (params, img [bucket,c,H,W],
        mask [bucket][, levels0 [bucket,n,L,d]]) -> (levels
        [bucket,n,L,d], iters_run int32, row_converged [bucket] bool,
        row_iters [bucket] int32). The mask only matters on the auto route
        (pad rows must not vote on the early-exit witness or the quorum);
        the fixed route carries it for a uniform calling convention.

        iters_override (the degradation ladder's capped_iters rung) pins
        a FIXED budget regardless of the configured route; auto_budget
        caps the auto route's max_iters (a continuation dispatch runs its
        stragglers' REMAINING budget); warm compiles the variant taking a
        carried-in levels state. Each is its own memoized signature."""
        cfg, scfg = self.cfg, self.scfg
        compute_dtype = self._compute_dtype
        auto = iters_override is None and self.iters_key == "auto"
        if auto:
            max_iters = (
                auto_budget if auto_budget is not None else self.auto_budget
            )
        else:
            max_iters = (
                iters_override if iters_override is not None else self.iters_key
            )

        if self.mesh is not None:
            if warm == "paged-inc":
                raise ValueError(
                    "the incremental route rides the single-device paged "
                    "path only (sharded incremental is a documented "
                    "follow-on; docs/SERVING.md)"
                )
            from glom_tpu.parallel.serve_mesh import make_serve_forward

            return make_serve_forward(
                self.mesh, cfg,
                route="auto" if auto else max_iters,
                max_iters=max_iters if auto else None,
                threshold=scfg.exit_threshold,
                min_iters=min(scfg.min_iters, max_iters),
                quorum=scfg.exit_quorum,
                compute_dtype=compute_dtype,
                use_pallas=scfg.use_pallas,
                warm=warm is True,
                page_tokens=(
                    self.pool.page_tokens if warm == "paged" else None
                ),
                page_gather=getattr(scfg, "page_gather", "auto"),
            )

        if auto:

            def fn(params, img, mask, levels0=None):
                res = glom_forward_tiered(
                    params, img, cfg,
                    max_iters=max_iters,
                    threshold=scfg.exit_threshold,
                    min_iters=min(scfg.min_iters, max_iters),
                    quorum=scfg.exit_quorum,
                    levels=levels0,
                    valid_mask=mask,
                    compute_dtype=compute_dtype,
                    use_pallas=scfg.use_pallas,
                )
                return res.levels, res.iters_run, res.row_converged, res.row_iters

        else:
            iters = max_iters

            def fn(params, img, mask, levels0=None):
                del mask  # pad rows are harmless on the fixed route
                final = glom_forward(
                    params, img, cfg, iters=iters,
                    levels=levels0,
                    compute_dtype=compute_dtype,
                    use_pallas=scfg.use_pallas,
                )
                b = final.shape[0]
                return (
                    final,
                    jnp.int32(iters),
                    jnp.ones((b,), bool),
                    jnp.full((b,), iters, jnp.int32),
                )

        if warm in ("paged", "paged-inc"):
            # The PAGED warm variant: levels0 never crosses the host
            # boundary — the dispatch carries tiny int32 page indices and
            # the compiled program assembles the warm state by a
            # page-index take from the device-resident pool
            # (serve/paged_columns.py). page_idx rows of -1 are COLD:
            # they take the forward's own init broadcast, bitwise the
            # cold_levels() contract. With a delta-chain page table the
            # indices are the session's EFFECTIVE base+Σdeltas map — the
            # reconstruction IS this same take.
            pt = self.pool.page_tokens

            def take_pages(params, pool, page_idx, b):
                with jax.named_scope("page_take"):
                    pages = pool[jnp.clip(page_idx, 0, pool.shape[0] - 1)]
                    init = jnp.broadcast_to(
                        params.init_levels[None],
                        (pt, cfg.levels, cfg.dim),
                    ).astype(pool.dtype)
                    pages = jnp.where(
                        (page_idx >= 0)[..., None, None, None], pages, init
                    )
                    return pages.reshape(
                        b, cfg.num_patches, cfg.levels, cfg.dim
                    )

            if warm == "paged-inc":
                # The INCREMENTAL route (docs/SERVING.md, "Delta
                # streaming"): the dispatch additionally carries the
                # input delta's [b, pages_per_row] page support — rows
                # whose frame did not change start pre-converged, changed
                # rows exit on the support-masked witness. auto-route
                # only (a fixed budget has no exit to seed).
                if not auto:
                    raise ValueError(
                        "the incremental route needs iters='auto' (a "
                        "fixed budget has no early exit to seed)"
                    )
                from glom_tpu.serve.early_exit import (
                    glom_forward_incremental,
                )

                def paged_inc_fn(params, img, mask, pool, page_idx, support):
                    b = img.shape[0]
                    levels0 = take_pages(params, pool, page_idx, b)
                    support_tok = jnp.repeat(support, pt, axis=1)  # [b, n]
                    res = glom_forward_incremental(
                        params, img, cfg,
                        max_iters=max_iters,
                        threshold=scfg.exit_threshold,
                        min_iters=min(scfg.min_iters, max_iters),
                        quorum=scfg.exit_quorum,
                        levels=levels0,
                        support_mask=support_tok,
                        valid_mask=mask,
                        compute_dtype=compute_dtype,
                        use_pallas=scfg.use_pallas,
                    )
                    return (
                        res.levels, res.iters_run,
                        res.row_converged, res.row_iters,
                    )

                return paged_inc_fn

            def paged_fn(params, img, mask, pool, page_idx):
                levels0 = take_pages(params, pool, page_idx, img.shape[0])
                return fn(params, img, mask, levels0)

            return paged_fn
        if warm:
            return fn
        return lambda params, img, mask: fn(params, img, mask)

    def _build_ragged_fn(
        self,
        iters_override: Optional[int] = None,
        *,
        auto_budget: Optional[int] = None,
        cont: bool = False,
    ):
        """The ragged signature's pure forward
        (serve/early_exit.glom_forward_ragged): (params, patches
        [T, patch_dim], n_patches [R][, pool, page_idx [P]]) -> (levels
        [T, L, d], iters_run, row_converged [R], row_iters [R]). The
        pool args exist exactly when the engine owns a page pool — one
        program serves cold and page-warm ragged dispatches (cold pages
        are index -1). cont=True builds the CONTINUATION variant
        instead: (params, patches, n_patches, levels0 [T, L, d]) —
        straggler groups re-enter with host-carried warm state (ragged x
        continuation composition; page warmth does not apply, the rows'
        columns are mid-flight, not resolved)."""
        from glom_tpu.serve.early_exit import glom_forward_ragged

        cfg, scfg = self.cfg, self.scfg
        compute_dtype = self._compute_dtype
        auto = iters_override is None and self.iters_key == "auto"
        if auto:
            max_iters = (
                auto_budget if auto_budget is not None else self.auto_budget
            )
            route = "auto"
        else:
            route = max_iters = (
                iters_override if iters_override is not None else self.iters_key
            )
        pt = self.pool.page_tokens if self.pool is not None else None
        if pt is None:
            from glom_tpu.serve.paged_columns import resolve_page_tokens

            pt = resolve_page_tokens(cfg, scfg)
        kw = dict(
            page_tokens=pt,
            route=route,
            max_iters=max_iters if auto else None,
            threshold=scfg.exit_threshold,
            min_iters=min(scfg.min_iters, max_iters),
            quorum=scfg.exit_quorum,
            compute_dtype=compute_dtype,
            use_pallas=scfg.use_pallas,
            ragged_attention=scfg.ragged_attention,
        )
        if cont:

            def fn(params, patches, n_patches, levels0):
                res = glom_forward_ragged(
                    params, patches, cfg, n_patches=n_patches,
                    levels0=levels0, **kw,
                )
                return (
                    res.levels, res.iters_run,
                    res.row_converged, res.row_iters,
                )

        elif self.pool is not None:

            def fn(params, patches, n_patches, pool, page_idx):
                res = glom_forward_ragged(
                    params, patches, cfg, n_patches=n_patches,
                    pool=pool, page_idx=page_idx, **kw,
                )
                return (
                    res.levels, res.iters_run,
                    res.row_converged, res.row_iters,
                )

        else:

            def fn(params, patches, n_patches):
                res = glom_forward_ragged(
                    params, patches, cfg, n_patches=n_patches, **kw,
                )
                return (
                    res.levels, res.iters_run,
                    res.row_converged, res.row_iters,
                )

        return fn

    def _ragged_key(self, pages: int) -> str:
        """The ragged signature's bucket key. The attention mode rides
        the key when it departs from the default windowed gather — a
        banded program is a DIFFERENT compiled artifact (same bitwise
        outputs at threshold 0, per the parity suite), so it must not
        collide with a windowed signature compiled earlier in the same
        process."""
        mode = self.scfg.ragged_attention
        if mode == "windowed":
            return f"ragged{pages}"
        return f"ragged{pages}:{mode}"

    def _compile(
        self,
        bucket: int,
        iters_override: Optional[int] = None,
        *,
        auto_budget: Optional[int] = None,
        warm: bool = False,
    ):
        """AOT-compile one bucket signature from abstract shapes and emit
        the "serve" warmup event (compile seconds attributed per bucket).
        Sharded signatures additionally run the lowering inside a
        collective-counting context, so the per-dispatch wire bytes land
        on the signature's stats record (while-loop sites price the
        BUDGET — see parallel/serve_mesh.py)."""
        sig = self.signature(
            bucket, iters_override, auto_budget=auto_budget, warm=warm
        )
        if sig in self._compiled:
            return self._compiled[sig]
        cfg = self.cfg
        img_abs = jax.ShapeDtypeStruct(
            (bucket, cfg.channels, cfg.image_size, cfg.image_size), jnp.float32
        )
        mask_abs = jax.ShapeDtypeStruct((bucket,), jnp.bool_)
        params_abs = jax.tree_util.tree_map(
            lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype), self.params
        )
        lv_dtype = (
            self._compute_dtype if self._compute_dtype is not None
            else jnp.float32
        )
        if warm in ("paged", "paged-inc"):
            pool = self.pool
            pool_abs = jax.ShapeDtypeStruct(
                (pool.n_pages, pool.page_tokens, cfg.levels, cfg.dim),
                pool.buffer().dtype,
            )
            pidx_abs = jax.ShapeDtypeStruct(
                (bucket, cfg.num_patches // pool.page_tokens), jnp.int32
            )
            abstract = (params_abs, img_abs, mask_abs, pool_abs, pidx_abs)
            if warm == "paged-inc":
                supp_abs = jax.ShapeDtypeStruct(
                    (bucket, cfg.num_patches // pool.page_tokens), jnp.bool_
                )
                abstract = abstract + (supp_abs,)
        else:
            lv_abs = jax.ShapeDtypeStruct(
                (bucket, cfg.num_patches, cfg.levels, cfg.dim), lv_dtype
            )
            abstract = (params_abs, img_abs, mask_abs) + (
                (lv_abs,) if warm else ()
            )
        # Donate the image batch, and the warm levels carry with it. The
        # POOL is never donated BY A DISPATCH: it is the persistent page
        # store every later dispatch reads. Write-backs update it on the
        # pool's own seam — copy-on-write by default, donated in place
        # under ServeConfig.pool_aliasing, gated by the read pins this
        # dispatch holds via acquire_read (serve/paged_columns.py).
        donate = (
            ((1, 3) if warm is True else (1,)) if self._donate else ()
        )
        fn = self._build_fn(
            bucket, iters_override, auto_budget=auto_budget, warm=warm
        )
        jit_kw = {"donate_argnums": donate, **self._pinned_jit_kw()}
        if self.mesh is not None:
            in_sh, out_sh = self._serve_shardings(warm)
            jit_kw.update(in_shardings=in_sh, out_shardings=out_sh)
        t0 = time.perf_counter()
        if self.mesh is not None:
            from glom_tpu.telemetry.counters import (
                CollectiveCounters,
                recording,
                timing,
            )

            counters = CollectiveCounters()
            # The timing context is TRACE-scoped: "full" makes every
            # registered site lower with its io_callback brackets (the
            # callbacks close over this engine's log); "sampled"/"off"
            # insert nothing. Either way the counting trace populates the
            # site registry the sampler re-dispatches from.
            with recording(counters), timing(
                self.collective_timing, self._coll_log
            ):
                lowered = jax.jit(fn, **jit_kw).lower(*abstract)
            self._comm[sig] = counters.totals()
            # A lazy mid-traffic compile runs on a WORKER thread while
            # another worker's sampling tick reads the registry: the
            # merge rides the same lock.
            with self._coll_lock:
                for site in counters.sites:
                    self._coll_sites.setdefault(
                        (site["site"], site["shape"]), site
                    )
        else:
            lowered = jax.jit(fn, **jit_kw).lower(*abstract)
        compiled = lowered.compile()
        dt = time.perf_counter() - t0
        self._compiled[sig] = compiled
        self._stats.setdefault(sig, StepTimeStats()).observe(dt, is_compile=True)
        self._emit(
            {
                "event": "warmup",
                "bucket": bucket,
                "iters": sig[1],
                "warm_state": warm,
                "degraded": iters_override is not None,
                "sharded": self.mesh is not None,
                "use_pallas": self.scfg.use_pallas,
                "mosaic_calls": mosaic_calls(compiled),
                "devices": self._param_devices(),
                "compile_time_s": round(dt, 4),
            }
        )
        return compiled

    def _compile_ragged(
        self,
        pages: int,
        iters_override: Optional[int] = None,
        *,
        auto_budget: Optional[int] = None,
        cont: bool = False,
    ):
        """AOT-compile one RAGGED page-count signature (flat token axis
        of pages x page_tokens; the pool args exactly when the engine
        owns one). Same warmup-event discipline as the bucket route.
        cont=True compiles the continuation variant (warm levels0 rides
        the dispatch; the straggler re-entry path)."""
        if cont:
            warm = "cont"
        else:
            warm = "pool" if self.pool is not None else "ragged"
        sig = self.signature(
            self._ragged_key(pages), iters_override,
            auto_budget=auto_budget, warm=warm,
        )
        if sig in self._compiled:
            return self._compiled[sig]
        from glom_tpu.serve.paged_columns import resolve_page_tokens

        cfg = self.cfg
        pt = (
            self.pool.page_tokens if self.pool is not None
            else resolve_page_tokens(cfg, self.scfg)
        )
        T = pages * pt
        patches_abs = jax.ShapeDtypeStruct((T, cfg.patch_dim), jnp.float32)
        n_abs = jax.ShapeDtypeStruct((self.ragged_rows,), jnp.int32)
        params_abs = jax.tree_util.tree_map(
            lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype), self.params
        )
        abstract = (params_abs, patches_abs, n_abs)
        if cont:
            lv_dtype = (
                self._compute_dtype if self._compute_dtype is not None
                else jnp.float32
            )
            lv_abs = jax.ShapeDtypeStruct(
                (T, cfg.levels, cfg.dim), lv_dtype
            )
            abstract = abstract + (lv_abs,)
            # Patches AND the carried levels donate — the straggler's
            # warm state is consumed by exactly this dispatch.
            donate = (1, 3) if self._donate else ()
        else:
            if self.pool is not None:
                pool_abs = jax.ShapeDtypeStruct(
                    (self.pool.n_pages, pt, cfg.levels, cfg.dim),
                    self.pool.buffer().dtype,
                )
                pidx_abs = jax.ShapeDtypeStruct((pages,), jnp.int32)
                abstract = abstract + (pool_abs, pidx_abs)
            donate = (1,) if self._donate else ()
        fn = self._build_ragged_fn(
            iters_override, auto_budget=auto_budget, cont=cont
        )
        t0 = time.perf_counter()
        compiled = jax.jit(
            fn, donate_argnums=donate, **self._pinned_jit_kw()
        ).lower(*abstract).compile()
        dt = time.perf_counter() - t0
        self._compiled[sig] = compiled
        self._stats.setdefault(sig, StepTimeStats()).observe(
            dt, is_compile=True
        )
        self._emit(
            {
                "event": "warmup",
                "bucket": sig[0],
                "iters": sig[1],
                "warm_state": sig[3],
                "degraded": iters_override is not None,
                "sharded": False,
                "use_pallas": self.scfg.use_pallas,
                "mosaic_calls": mosaic_calls(compiled),
                "devices": self._param_devices(),
                "compile_time_s": round(dt, 4),
            }
        )
        return compiled

    def warmup(
        self,
        buckets: Optional[Tuple[int, ...]] = None,
        *,
        iters_override: Optional[int] = None,
        warm: bool = False,
    ) -> dict:
        """Precompile every bucket signature BEFORE traffic. Returns
        {bucket: compile_seconds}; already-compiled signatures are free.
        Call again with iters_override=<degraded budget> to pre-warm the
        ladder's capped_iters route, or warm=True for the continuation
        path's warm-state shape (continuation dispatches at partial
        budgets still compile on first miss — each remaining budget is
        its own signature, attributable in the warmup stream)."""
        out = {}
        for b in buckets if buckets is not None else self.scfg.buckets:
            sig = self.signature(b, iters_override, warm=warm)
            already = sig in self._compiled
            t0 = time.perf_counter()
            self._compile(b, iters_override, warm=warm)
            out[b] = 0.0 if already else time.perf_counter() - t0
        return out

    # -- dispatch ----------------------------------------------------------

    def _serve_shardings(self, warm) -> Tuple:
        """Memoized (in_shardings, out_shardings) for the sharded route —
        resolved once per (engine, warm mode) rather than per dispatch
        (the param tree_map is pure overhead in the request hot path).
        warm is False | True (host levels0 carry) | "paged" (pool +
        page-index take)."""
        if warm not in self._shardings:
            from glom_tpu.parallel.serve_mesh import serve_shardings

            self._shardings[warm] = serve_shardings(
                self.mesh, self.params,
                warm=warm is True, paged=warm == "paged",
            )
        return self._shardings[warm]

    def _param_devices(self) -> list:
        """Ids of the devices the params actually live on — the warmup
        event's witness of where this engine runs."""
        return sorted(d.id for d in self.params.pos_emb.devices())

    def _pinned_jit_kw(self) -> dict:
        """in/out shardings that compile a program FOR the pinned device
        (AOT lowering from abstract shapes would otherwise target the
        default one); empty when unpinned."""
        if self._device_sharding is None:
            return {}
        return {
            "in_shardings": self._device_sharding,
            "out_shardings": self._device_sharding,
        }

    def _device_input(self, src, sharding_spec=None):
        """One fresh device buffer per attempt (donation invalidates the
        previous one). On the sharded route the host array device_puts
        straight into its NamedSharding, on a pinned engine onto its
        device; otherwise the plain transfer."""
        if sharding_spec is None:
            sharding_spec = self._device_sharding
        if sharding_spec is not None:
            return jax.device_put(np.asarray(src), sharding_spec)
        return jnp.asarray(src)

    def infer(
        self,
        imgs,
        n_valid: Optional[int] = None,
        *,
        iters_override: Optional[int] = None,
        levels0=None,
        auto_budget: Optional[int] = None,
        page_rows=None,
        support_rows=None,
    ) -> ServeResult:
        """Run one padded batch. `imgs` is [b, c, H, W] (numpy or jax) with
        b equal to a bucket size — callers that batch themselves pass an
        exact bucket; the DynamicBatcher always does. `n_valid` marks how
        many leading rows are real requests (default: all).

        iters_override pins a fixed iteration budget for THIS dispatch
        (the degradation ladder's capped_iters rung); None runs the
        configured route. levels0 [b, n, L, d] carries warm column state
        in (the continuation path), and auto_budget caps the auto route's
        max_iters to the stragglers' remaining budget. page_rows
        [b, pages_per_row] int32 selects the PAGED warm signature
        instead: each row's levels0 assembles in-graph from the engine's
        pool pages (-1 rows take the cold init) — zero levels0 bytes
        cross the host boundary (serve/paged_columns.py). Transient dispatch
        failures retry per the engine's RetryPolicy — a failed attempt
        against an up-or-flapping backend backs off and re-dispatches from
        FRESH input buffers (donation invalidates the old ones), while a
        down backend raises straight into the batcher's shed path."""
        if iters_override is not None and (
            not isinstance(iters_override, int) or iters_override < 1
        ):
            raise ValueError(
                f"iters_override={iters_override!r}: an int >= 1 or None"
            )
        if auto_budget is not None:
            if not isinstance(auto_budget, int) or auto_budget < 1:
                raise ValueError(
                    f"auto_budget={auto_budget!r}: an int >= 1 or None"
                )
            if iters_override is not None:
                raise ValueError(
                    "auto_budget composes with the auto route only, not "
                    "with a fixed iters_override"
                )
        b = np.shape(imgs)[0]
        if b not in self.scfg.buckets:
            raise ValueError(
                f"batch {b} is not a bucket shape {self.scfg.buckets}; pad "
                "to a bucket (DynamicBatcher does) or add the bucket"
            )
        n_valid = b if n_valid is None else n_valid
        if not 1 <= n_valid <= b:
            raise ValueError(f"n_valid={n_valid} outside 1..{b}")
        if page_rows is not None:
            if self.pool is None:
                raise ValueError(
                    "page_rows needs a page pool "
                    "(ServeConfig.page_pool_pages > 0)"
                )
            if levels0 is not None:
                raise ValueError("pass levels0 OR page_rows, not both")
            page_rows = np.asarray(page_rows, np.int32)
            ppr = self.cfg.num_patches // self.pool.page_tokens
            if page_rows.shape != (b, ppr):
                raise ValueError(
                    f"page_rows shape {page_rows.shape} != ({b}, {ppr})"
                )
        if support_rows is not None:
            # The INCREMENTAL route: a paged dispatch carrying the input
            # delta's page support (docs/SERVING.md, "Delta streaming").
            if page_rows is None:
                raise ValueError(
                    "support_rows rides page_rows (the incremental route "
                    "is a paged dispatch)"
                )
            if self.iters_key != "auto" or iters_override is not None:
                raise ValueError(
                    "support_rows needs the iters='auto' route (a fixed "
                    "budget has no early exit to seed)"
                )
            if self.mesh is not None:
                raise ValueError(
                    "the incremental route rides the single-device paged "
                    "path only (sharded incremental is a follow-on)"
                )
            support_rows = np.asarray(support_rows, bool)
            if support_rows.shape != page_rows.shape:
                raise ValueError(
                    f"support_rows shape {support_rows.shape} != "
                    f"{page_rows.shape}"
                )
        if page_rows is not None:
            warm = "paged-inc" if support_rows is not None else "paged"
        else:
            warm = levels0 is not None
        if warm is True and np.shape(levels0)[0] != b:
            raise ValueError(
                f"levels0 batch {np.shape(levels0)[0]} != bucket {b}"
            )
        lv_dtype = (
            self._compute_dtype if self._compute_dtype is not None
            else np.float32
        )
        img_sh = mask_sh = lv_sh = pidx_sh = None
        if self.mesh is not None:
            in_sh, _ = self._serve_shardings(warm)
            img_sh, mask_sh = in_sh[1], in_sh[2]
            lv_sh = in_sh[3] if warm is True else None
            pidx_sh = in_sh[4] if warm == "paged" else None
        levels0_h2d = [0]
        if self._donate:
            # Every ATTEMPT needs fresh device buffers: the compiled call
            # donates its inputs, so a retry after a failed dispatch must
            # never reuse a possibly-invalidated array. Hold the sources
            # on the HOST (np.asarray of a caller-held jax array fetches a
            # copy, so the caller's buffer is never the donated one) and
            # re-transfer per attempt.
            src = np.asarray(imgs, np.float32)
            make_input = lambda: self._device_input(src, img_sh)
            if warm is True:
                lv_src = np.asarray(levels0, lv_dtype)

                def make_levels():
                    levels0_h2d[0] += lv_src.nbytes
                    return self._device_input(lv_src, lv_sh)

            else:
                make_levels = None
        else:
            dev = self._device_input(np.asarray(imgs, np.float32), img_sh)
            make_input = lambda: dev
            if warm is True:
                lv_src = np.asarray(levels0, lv_dtype)
                levels0_h2d[0] += lv_src.nbytes
                lv_dev = self._device_input(lv_src, lv_sh)
                make_levels = lambda: lv_dev
            else:
                make_levels = None
        mask_host = np.arange(b) < n_valid
        mask = self._device_input(mask_host, mask_sh)
        if warm in ("paged", "paged-inc"):
            # The whole point: the warm state stays device-resident —
            # only the tiny int32 page map (plus, on the incremental
            # route, the bool support map) crosses the host boundary.
            pidx_dev = self._device_input(page_rows, pidx_sh)
            supp_dev = (
                self._device_input(support_rows)
                if warm == "paged-inc" else None
            )
        sig = self.signature(
            b, iters_override, auto_budget=auto_budget, warm=warm
        )
        compiled_before = sig in self._compiled
        fn = self._compile(
            b, iters_override, auto_budget=auto_budget, warm=warm
        )
        stats = self._stats.setdefault(sig, StepTimeStats())
        attempts = [0]
        # Latency decomposition (ServeConfig.phase_split, default ON): the
        # engine attributes its own wall between h2d (staging the inputs,
        # forced resident with block_until_ready — without the sync the
        # async transfer would hide inside the compiled call) and resolve
        # (fetching the outputs back); the compiled call plus whatever the
        # split cannot see (validation, retry backoff) is the batcher's
        # device_ms remainder. Accumulated across retry attempts, like
        # levels0_h2d.
        split = self.phase_split
        ph = {"h2d_s": 0.0, "resolve_s": 0.0}

        def attempt():
            attempts[0] += 1
            if self._fault_hook is not None:
                self._fault_hook(
                    {"bucket": b, "n_valid": n_valid, "attempt": attempts[0]}
                )
            t_h = time.perf_counter()
            staged = make_input()
            args = (self.params, staged, mask)
            lv_staged = None
            pinned = False
            try:
                if warm in ("paged", "paged-inc"):
                    # Snapshot per attempt: the freshest write-backs,
                    # PINNED for the dispatch's lifetime — under pool
                    # aliasing the pin blocks donation of the buffer
                    # this program reads (a CoW pool is unaffected; the
                    # pin is a free counter).
                    args = args + (self.pool.acquire_read(), pidx_dev)
                    pinned = True
                    if warm == "paged-inc":
                        args = args + (supp_dev,)
                elif warm:
                    lv_staged = make_levels()
                    args = args + (lv_staged,)
                if split:
                    jax.block_until_ready(staged)
                    if lv_staged is not None:
                        jax.block_until_ready(lv_staged)
                    ph["h2d_s"] += time.perf_counter() - t_h
                # args is attempt-local and never read after the dispatch:
                # every attempt rebuilds it from make_input()/make_levels(),
                # so a donated buffer is re-staged before any retry reads it.
                # glom-lint: ok[donation-safety] attempt-local splat, rebuilt per retry
                levels, iters_run, conv, row_iters = fn(*args)
                levels.block_until_ready()  # syncs: serving is request/
                # response — the caller needs the answer now, and the
                # wait IS the device latency being measured.
            finally:
                if pinned:
                    self.pool.release_read()
            t_r = time.perf_counter()
            iters_host = int(jax.device_get(iters_run))
            out = (
                levels,
                iters_host,
                np.asarray(jax.device_get(conv)),
                np.asarray(jax.device_get(row_iters)),
            )
            if split:
                ph["resolve_s"] += time.perf_counter() - t_r
            return out

        t0 = time.perf_counter()
        if self.retry is not None:
            out = self.retry.run(attempt, bucket=b, n_valid=n_valid)
        else:
            out = attempt()
        levels, iters_host, conv, row_iters = out
        dt = time.perf_counter() - t0
        stats.observe(dt, is_compile=False)
        self.levels0_h2d_bytes_total += levels0_h2d[0]
        self._tick_collective_timing()
        return ServeResult(
            levels=levels,
            iters_run=iters_host,
            latency_s=dt,
            bucket=b,
            compiled=not compiled_before,
            row_converged=conv,
            row_iters=row_iters,
            levels0_h2d_bytes=levels0_h2d[0],
            phases=(
                {"h2d_ms": 1e3 * ph["h2d_s"],
                 "resolve_ms": 1e3 * ph["resolve_s"]}
                if split else None
            ),
        )

    def infer_ragged(
        self,
        patches,
        n_patches,
        *,
        page_idx=None,
        levels0=None,
        auto_budget: Optional[int] = None,
        iters_override: Optional[int] = None,
    ) -> RaggedServeResult:
        """Run one RAGGED dispatch: rows of DIFFERING patch counts packed
        page-aligned on a flat token axis (docs/SERVING.md, "Ragged
        admission").

        patches: [T, patch_dim] host-patchified rows in row order, page
        padded (T = P x page_tokens with P a ragged-ladder entry — the
        batcher packs with the same `ragged_row_layout` the compiled
        program derives in-graph). n_patches: per-row patch counts (at
        most `ragged_rows`; padded with 0 internally). page_idx: [P]
        int32 pool pages per dispatch-page slot, -1 = cold (requires the
        engine's pool; None = all cold). Warm state rides the POOL ONLY
        — there is no host levels0 on this route, which is exactly what
        `levels0_h2d_bytes == 0` asserts. EXCEPT the continuation
        re-entry: levels0 [T, L, d] flat (row-packed like patches)
        carries straggler groups' mid-flight columns back in (mutually
        exclusive with page_idx — unresolved state has no pages), and
        its H2D bytes are reported, not asserted zero."""
        if self.mesh is not None:
            raise ValueError("ragged dispatch: single-device route only")
        if iters_override is not None and (
            not isinstance(iters_override, int) or iters_override < 1
        ):
            raise ValueError(
                f"iters_override={iters_override!r}: an int >= 1 or None"
            )
        if auto_budget is not None:
            if not isinstance(auto_budget, int) or auto_budget < 1:
                raise ValueError(
                    f"auto_budget={auto_budget!r}: an int >= 1 or None"
                )
            if iters_override is not None:
                raise ValueError(
                    "auto_budget composes with the auto route only"
                )
        from glom_tpu.serve.paged_columns import (
            pages_for_tokens,
            resolve_page_tokens,
        )

        pt = (
            self.pool.page_tokens if self.pool is not None
            else resolve_page_tokens(self.cfg, self.scfg)
        )
        patches = np.asarray(patches, np.float32)
        T = patches.shape[0]
        if T % pt != 0:
            raise ValueError(f"T={T} is not a multiple of page_tokens {pt}")
        P = T // pt
        if P not in self.ragged_page_buckets:
            raise ValueError(
                f"{P} pages is not a ragged signature "
                f"{self.ragged_page_buckets}; pack to a ladder entry "
                "(DynamicBatcher does)"
            )
        n_list = [int(n) for n in np.asarray(n_patches).reshape(-1)]
        R = self.ragged_rows
        if len(n_list) > R:
            raise ValueError(f"{len(n_list)} rows exceed ragged_rows {R}")
        if any(n < 0 or n > self.cfg.num_patches for n in n_list):
            raise ValueError(
                f"n_patches {n_list}: each row needs 0..{self.cfg.num_patches}"
                " patches (the pos table bounds the row length)"
            )
        need = sum(pages_for_tokens(n, pt) for n in n_list if n > 0)
        if need > P:
            raise ValueError(f"rows need {need} pages > dispatch size {P}")
        n_host = np.zeros((R,), np.int32)
        n_host[: len(n_list)] = n_list
        if page_idx is not None and self.pool is None:
            raise ValueError(
                "page_idx needs a page pool (ServeConfig.page_pool_pages)"
            )
        cont = levels0 is not None
        if cont:
            if page_idx is not None:
                raise ValueError(
                    "levels0 OR page_idx: a continuation's columns are "
                    "mid-flight, not pool-resident"
                )
            lv_dtype = (
                self._compute_dtype if self._compute_dtype is not None
                else jnp.float32
            )
            lv_host = np.asarray(levels0)
            if lv_host.shape != (T, self.cfg.levels, self.cfg.dim):
                raise ValueError(
                    f"levels0 shape {lv_host.shape} != "
                    f"({T}, {self.cfg.levels}, {self.cfg.dim}) (flat "
                    "row-packed, page padded like patches)"
                )
        if self.pool is not None and not cont:
            pidx_host = (
                np.full((P,), -1, np.int32) if page_idx is None
                else np.asarray(page_idx, np.int32)
            )
            if pidx_host.shape != (P,):
                raise ValueError(
                    f"page_idx shape {pidx_host.shape} != ({P},)"
                )
        if cont:
            warm = "cont"
        else:
            warm = "pool" if self.pool is not None else "ragged"
        sig = self.signature(
            self._ragged_key(P), iters_override,
            auto_budget=auto_budget, warm=warm,
        )
        compiled_before = sig in self._compiled
        fn = self._compile_ragged(
            P, iters_override, auto_budget=auto_budget, cont=cont
        )
        stats = self._stats.setdefault(sig, StepTimeStats())
        n_dev = self._device_input(n_host)
        attempts = [0]
        split = self.phase_split
        ph = {"h2d_s": 0.0, "resolve_s": 0.0}
        levels0_h2d = [0]

        def attempt():
            attempts[0] += 1
            if self._fault_hook is not None:
                self._fault_hook(
                    {
                        "bucket": self._ragged_key(P),
                        "n_valid": sum(1 for n in n_list if n > 0),
                        "attempt": attempts[0],
                    }
                )
            t_h = time.perf_counter()
            staged = self._device_input(patches)
            args = (self.params, staged, n_dev)
            pinned = False
            try:
                if cont:
                    lv_staged = self._device_input(lv_host.astype(lv_dtype))
                    levels0_h2d[0] += lv_staged.nbytes
                    args = args + (lv_staged,)
                elif self.pool is not None:
                    # Pin the snapshot for the dispatch's whole
                    # lifetime: under pool aliasing the pin blocks
                    # donation of the buffer this program reads (a CoW
                    # pool is unaffected — the pin is a free counter).
                    args = args + (
                        self.pool.acquire_read(),
                        self._device_input(pidx_host),
                    )
                    pinned = True
                if split:
                    jax.block_until_ready(staged)
                    ph["h2d_s"] += time.perf_counter() - t_h
                # args is attempt-local and never read after the dispatch:
                # every attempt rebuilds it from make_input()/make_levels(),
                # so a donated buffer is re-staged before any retry reads it.
                # glom-lint: ok[donation-safety] attempt-local splat, rebuilt per retry
                levels, iters_run, conv, row_iters = fn(*args)
                levels.block_until_ready()
            finally:
                if pinned:
                    self.pool.release_read()
            t_r = time.perf_counter()
            out = (
                levels,
                int(jax.device_get(iters_run)),
                np.asarray(jax.device_get(conv)),
                np.asarray(jax.device_get(row_iters)),
            )
            if split:
                ph["resolve_s"] += time.perf_counter() - t_r
            return out

        t0 = time.perf_counter()
        if self.retry is not None:
            out = self.retry.run(
                attempt, bucket=self._ragged_key(P),
                n_valid=sum(1 for n in n_list if n > 0),
            )
        else:
            out = attempt()
        levels, iters_host, conv, row_iters = out
        dt = time.perf_counter() - t0
        stats.observe(dt, is_compile=False)
        self.levels0_h2d_bytes_total += levels0_h2d[0]
        return RaggedServeResult(
            levels=levels,
            iters_run=iters_host,
            latency_s=dt,
            pages=P,
            compiled=not compiled_before,
            row_converged=conv,
            row_iters=row_iters,
            levels0_h2d_bytes=levels0_h2d[0],
            phases=(
                {"h2d_ms": 1e3 * ph["h2d_s"],
                 "resolve_ms": 1e3 * ph["resolve_s"]}
                if split else None
            ),
        )

    # -- telemetry ---------------------------------------------------------

    def _tick_collective_timing(self) -> None:
        """Sampled-mode cadence: every collective_timing_interval-th
        dispatch re-dispatches each registered site as its own timed
        sub-graph (telemetry/comm_time.py) and buffers the stamped
        records for collective_time_records(). The sample runs ON the
        dispatching thread after its result is already resolved — the
        cost lands on one dispatch in N, which is exactly what the
        collective-timing overhead A/B prices."""
        if self.collective_timing != "sampled":
            return
        # The cheap lock decides DUE and snapshots the registry; the
        # sampling pass itself (sub-graph compiles + timed dispatches —
        # seconds on a first tick) runs under the dedicated sample lock
        # so a concurrent dispatch's tick only ever waits for the
        # counter, never for another thread's sample.
        with self._coll_lock:
            if not self._coll_sites:
                return
            self._coll_dispatches += 1
            if (
                self._coll_dispatches
                % self.scfg.collective_timing_interval != 0
            ):
                return
            sites = list(self._coll_sites.values())
        from glom_tpu.telemetry.comm_time import (
            CollectiveTimeSampler,
            collective_time_records,
        )

        with self._coll_sample_lock:
            if self._coll_sampler is None:
                self._coll_sampler = CollectiveTimeSampler(
                    self.mesh, sites, interval=1
                )
            else:
                # Sites registered by lazy compiles AFTER the sampler was
                # built (a new bucket/warm signature) join the rotation —
                # a frozen registry would silently never time them.
                self._coll_sampler.update_sites(sites)
            recs = collective_time_records(
                self._coll_sampler.sample(), path=self.name,
                mode="sampled",
            )
        with self._coll_lock:
            self._coll_samples.extend(
                dict(r, engine=self.name) for r in recs
            )

    def collective_time_records(self) -> list:
        """Drain the per-collective wall-time evidence: full-mode
        io_callback brackets aggregate per (site, axis, bytes); sampled-
        mode buffered re-dispatch rows pass through. Every row is a
        stamped schema "collective_time" record carrying the α-β
        comm_time_model fit + drift; empty when timing is off (the
        acceptance contract: off-mode leaves NO records)."""
        out: list = []
        if self._coll_log is not None:
            samples = self._coll_log.drain()
            if samples:
                from glom_tpu.telemetry.comm_time import (
                    collective_time_records,
                )

                out.extend(
                    dict(r, engine=self.name)
                    for r in collective_time_records(
                        samples, path=self.name, mode="full"
                    )
                )
        with self._coll_lock:
            buffered, self._coll_samples = self._coll_samples, []
        out.extend(buffered)
        return out

    def release(self) -> None:
        """Free this engine's device-side state after a graceful drain
        (serve/elastic.py scale-in, step 4: release devices). Drops the
        memoized compiled executables, the sharding/cold-init caches,
        and the page pool's buffer + table — the HBM a drained replica
        was holding. The object stays a valid EVIDENCE husk (name,
        stats_records, collective_time_records) but can no longer serve;
        the batcher has already removed it from the fleet, so nothing
        dispatches here again."""
        self._compiled.clear()
        self._shardings.clear()
        self._cold_levels = None
        self.released = True
        if self.pool is not None:
            self.pool.release()
        self._emit({"event": "engine_release"})

    def _emit(self, rec: dict) -> None:
        from glom_tpu.serve.events import emit_serve

        emit_serve(self.writer, dict(rec, engine=self.name))

    def stats_records(self) -> list:
        """One stamped "serve" event per compiled signature with the
        per-bucket latency histogram (p50/p95/p99/max, compile split) and,
        on the sharded route, the counted per-dispatch collective wire
        bytes from the lowering trace."""
        out = []
        for sig, stats in sorted(
            self._stats.items(), key=lambda kv: str(kv[0])
        ):
            bucket, iters_key, pallas, warm = sig
            rec = {
                "event": "bucket_stats",
                "engine": self.name,
                "bucket": bucket,
                "iters": iters_key,
                "warm_state": warm,
                "use_pallas": pallas,
                **stats.summary(),
            }
            if sig in self._comm:
                rec.update(self._comm[sig])
            out.append(schema.stamp(rec, kind="serve"))
        return out
