"""The distributed training runtime: mesh + shardings + pjit-ed train step.

This is the TPU-native replacement for the torch-DDP/NCCL layer the
reference never had (SURVEY.md §2.2): the data-parallel gradient allreduce,
the TP psum, and the SP ring/halo/all-to-all all ride ICI, emitted by XLA
from sharding annotations (GSPMD) or written explicitly in the shard_map
consensus ops.

Composition:
  * DP  — batch sharded on 'data'; XLA inserts the grad allreduce.
  * TP  — grouped-FFW hidden axis sharded on 'model' (sharding.py).
  * SP  — 'seq' axis is MANUAL: the consensus_fn built here is a shard_map
          region (ring/ulysses/halo) over 'seq' while 'data'/'model' stay
          automatic; the n axis of the level state is pinned to 'seq' by the
          shard_map in/out specs and flows through the scan carry.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from glom_tpu.models.core import ConsensusFn
from glom_tpu.parallel.halo import make_halo_consensus
from glom_tpu.parallel.manual import make_manual_train_step, manual_supported
from glom_tpu.parallel.mesh import make_mesh
from glom_tpu.parallel.ring import make_ring_consensus
from glom_tpu.parallel.sharding import (
    batch_spec,
    denoise_param_specs,
    opt_state_specs,
    to_named,
    zero_param_specs,
)
from glom_tpu.parallel.ulysses import make_ulysses_consensus
from glom_tpu.telemetry import diagnostics as diag
from glom_tpu.train.trainer import (
    TrainState,
    ZeroShardings,
    create_train_state,
    fit_loop,
    make_train_step,
    pinned_grad_accum,
    resolve_zero_stage,
)
from glom_tpu.utils.config import GlomConfig, MeshConfig, TrainConfig
from glom_tpu.utils.helpers import halo_supported

SP_STRATEGIES = ("none", "ring", "ulysses", "halo", "auto")


# Ulysses-vs-ring mechanism (the model BEHIND the measured crossover, not a
# magic number): total attention FLOPs are identical (2 * n^2/seq * L * d per
# einsum either way) and so is collective volume (n*d*L/seq in, same out) —
# the difference is the per-level similarity WORKING SET. Ulysses runs dense
# full-row attention on L/seq levels, so its f32 similarity block is n^2 * 4
# bytes per level; while that streams through VMEM the big matmuls run at
# full MXU rate and Ulysses wins on granularity (fewer, larger matmuls, ONE
# softmax instead of seq-1 online-combine passes). Past the VMEM-resident
# scale the similarity spills to HBM-streamed tiles and the advantage
# inverts — ring's [n/seq, n/seq] chunks stay resident at any n. The
# measured table (results/sp_crossover.jsonl, v5e) brackets the flip
# between n=1024 (4MB sim, Ulysses >= ring at every seq) and n=4096 (64MB,
# ring wins ~2.1x at every seq); n^2 * 4 <= 16MB -> n <= 2048 encodes it.
# The model is d-, L-, and batch-independent: d scales only the
# linear-in-n k/v tiles, and L/seq and batch multiply the NUMBER of
# independent per-(batch, level) attention instances identically on both
# sides — each instance's resident similarity block is still n^2 * 4
# (instances stream through VMEM sequentially; total bytes touched grow
# with b but the per-instance working set that decides spill does not).
# The committed rows are B=1 and unstamped (they predate the benchmark).
# tests/test_parallel.py asserts this predicate against every measured row
# of the committed table.
_ULYSSES_SIM_BUDGET = 16 * 1024 * 1024


def ulysses_preferred(n: int) -> bool:
    """True when Ulysses' full-row similarity block is VMEM-scale (see the
    working-set model above) — the measured ring/Ulysses crossover.
    STRICT inequality: the committed table brackets the flip between
    n=1024 and n=4096, so the exactly-at-budget point n=2048 (16MB) is
    UNMEASURED — auto-selection keeps the prior ring behavior there until
    an sp_crossover row for n=2048 lands (ADVICE round 5, low)."""
    return n * n * 4 < _ULYSSES_SIM_BUDGET


def select_sp_strategy(cfg: GlomConfig, seq: int) -> str:
    """Resolve sp_strategy='auto': pick the SP mechanism from the config's
    geometry and the measured ring-vs-Ulysses crossover (the working-set
    model above; results/sp_crossover.jsonl):

      * local radius with one-hop-coverable shards -> halo (neighbor-row
        exchange only; the cheapest exact form, by construction);
      * global (or halo-impossible) small/mid n -> Ulysses when the levels
        axis divides the seq axis: measured 4.2x over ring at n=256/seq=8,
        2.0x at n=1024/seq=8, parity at n=1024/seq=2 (L plays the role of
        heads — the all-to-all trades n-sharding for exact L-sharding);
      * long rows -> ring: at n=4096 Ulysses loses 2.1x (each shard then
        runs FULL-n attention on L/seq levels, and the spilled similarity
        working set dwarfs the ring's ppermute overlap).
    """
    if seq <= 1:
        return "none"
    radius = float(cfg.local_consensus_radius)
    if radius > 0 and halo_supported(seq, cfg.num_patches_side, radius):
        return "halo"
    if cfg.levels % seq == 0 and ulysses_preferred(cfg.num_patches):
        return "ulysses"
    return "ring"


def effective_sp_strategy(cfg: GlomConfig, seq: int, strategy: str) -> str:
    """The strategy a config ACTUALLY runs — THE single source of the
    resolution policy (both consensus-fn builders and the trainers' metric
    logging call this, so a run can never train on a different collective
    pattern than its records claim): resolves 'auto' through the selector
    and applies the exactness fallbacks (impossible halo, indivisible
    Ulysses -> ring, which is exact for any geometry). Downgrades of an
    EXPLICITLY requested strategy warn; 'auto' resolves silently (picking
    is its job). Idempotent: re-resolving an already-effective strategy is
    a no-op, so the trainers' up-front resolve suppresses double warnings.
    """
    if strategy not in SP_STRATEGIES:
        raise ValueError(
            f"unknown SP strategy {strategy!r}; one of {SP_STRATEGIES}"
        )
    if strategy == "auto":
        return select_sp_strategy(cfg, seq)
    if seq <= 1:
        return "none"
    radius = float(cfg.local_consensus_radius)
    if strategy == "halo" and not halo_supported(
        seq, cfg.num_patches_side, radius
    ):
        # Halo is only the cheaper special case when one-hop neighbor rows
        # cover the radius; fall back instead of crashing the config
        # (BASELINE config 3: radius 7 on an 8-row grid, seq=2).
        warnings.warn(
            f"halo consensus unsupported (radius={radius}, "
            f"side={cfg.num_patches_side}, seq={seq}); falling back to "
            "ring consensus",
            stacklevel=3,
        )
        return "ring"
    if strategy == "ulysses" and cfg.levels % seq != 0:
        warnings.warn(
            f"ulysses needs levels ({cfg.levels}) divisible by the seq "
            f"axis ({seq}); using ring (identical result, different "
            "collectives)",
            stacklevel=3,
        )
        return "ring"
    return strategy


def make_consensus_fn(
    mesh, cfg: GlomConfig, strategy: str, axis_name: str = "seq"
) -> Optional[ConsensusFn]:
    """Build the sequence-parallel consensus op for `strategy`, or None for
    the dense/GSPMD default. Resolution (auto + fallbacks) happens in
    effective_sp_strategy — this is construction only."""
    strategy = effective_sp_strategy(cfg, mesh.shape[axis_name], strategy)
    if strategy == "none":
        return None
    if strategy == "ring":
        return make_ring_consensus(
            mesh,
            attend_self=cfg.consensus_self,
            side=cfg.num_patches_side,
            radius=float(cfg.local_consensus_radius),
            axis_name=axis_name,
        )
    if strategy == "ulysses":
        return make_ulysses_consensus(
            mesh,
            attend_self=cfg.consensus_self,
            side=cfg.num_patches_side,
            radius=float(cfg.local_consensus_radius),
            axis_name=axis_name,
        )
    return make_halo_consensus(
        mesh,
        attend_self=cfg.consensus_self,
        side=cfg.num_patches_side,
        radius=float(cfg.local_consensus_radius),
        axis_name=axis_name,
    )


def make_engine_meshes(
    scfg, n_engines: int, devices: Optional[list] = None
) -> list:
    """One serve mesh (or None for single-device engines) per engine
    replica: the device list partitions into contiguous
    (mesh_data * mesh_seq)-sized groups (parallel/mesh.py
    replica_device_groups), each group hosting one InferenceEngine behind
    the shared-admission batcher (multi-engine fan-out, docs/SERVING.md).
    Lives here because it is mesh + spec RESOLUTION, the seam ROADMAP
    item 5's unified runtime extracts — a new serve parallelism should
    land in one place, not per caller."""
    import jax as _jax

    from glom_tpu.parallel.mesh import replica_device_groups
    from glom_tpu.parallel.serve_mesh import make_serve_mesh

    if n_engines < 1:
        raise ValueError(f"n_engines {n_engines} must be >= 1")
    per = scfg.mesh_data * scfg.mesh_seq
    if per == 1:
        return [None] * n_engines
    devices = devices if devices is not None else _jax.devices()
    groups = replica_device_groups(devices, per)
    if len(groups) < n_engines:
        raise ValueError(
            f"{len(devices)} devices host only {len(groups)} "
            f"{per}-device engine replicas; {n_engines} requested"
        )
    return [make_serve_mesh(scfg, g) for g in groups[:n_engines]]


def engine_mesh_for(
    scfg, index: int, devices: Optional[list] = None
):
    """The mesh for ONE engine replica by fleet index — the elastic
    scale-out's device-group resolution (serve/elastic.py): a spawned
    replica takes the NEXT contiguous group the static partitioning
    would have given it, so a fleet that grew at runtime occupies
    exactly the devices `--engines N` would have. Raises (loudly — the
    autoscaler's spawn_rollback path) when the device pool has no group
    `index` left; returns None on the single-device route."""
    if index < 0:
        raise ValueError(f"index {index} must be >= 0")
    return make_engine_meshes(scfg, index + 1, devices=devices)[index]


class DistributedTrainer:
    """Sharded trainer over an explicit device mesh.

    `sp_strategy` selects how consensus attention is parallelized over the
    'seq' axis; 'none' leaves everything to GSPMD (which will all-gather k/v
    — correct, but the explicit ring/halo beat it at scale).
    """

    def __init__(
        self,
        cfg: GlomConfig,
        tcfg: TrainConfig,
        mesh_cfg: MeshConfig,
        *,
        sp_strategy: str = "none",
        tp_axis: str = "hidden",
        optimizer: Optional[optax.GradientTransformation] = None,
        metrics_writer=None,
        devices: Optional[list] = None,
    ):
        if tcfg.batch_size % mesh_cfg.data != 0:
            raise ValueError(
                f"batch {tcfg.batch_size} not divisible by data axis {mesh_cfg.data}"
            )
        accum_base = pinned_grad_accum(tcfg)
        if (
            accum_base > 1
            and (tcfg.batch_size // accum_base) % mesh_cfg.data != 0
        ):
            # Both step paths (GSPMD and manual) scan over microbatches;
            # an indivisible microbatch would silently pad/idle devices.
            raise ValueError(
                f"microbatch {tcfg.batch_size // accum_base} "
                f"(batch {tcfg.batch_size} / grad_accum {accum_base}) "
                f"not divisible by data axis {mesh_cfg.data}"
            )
        if cfg.num_patches % mesh_cfg.seq != 0:
            raise ValueError(
                f"patches {cfg.num_patches} not divisible by seq axis {mesh_cfg.seq}"
            )
        self.cfg = cfg
        self.tcfg = tcfg
        self.mesh_cfg = mesh_cfg
        self.mesh = make_mesh(mesh_cfg, devices)
        self.metrics_writer = metrics_writer
        # Resolve 'auto' and the exactness fallbacks ONCE, pass the
        # resolved mechanism everywhere, and report it in every metrics
        # record — a run must not train on a different collective pattern
        # than its logs claim (round-3 weak #6: the fallbacks only warned).
        self.sp_strategy = effective_sp_strategy(cfg, mesh_cfg.seq, sp_strategy)
        sp_strategy = self.sp_strategy

        # use_pallas routes through the fully-manual shard_map path (the
        # kernels are per-device-legal there), including hidden-axis TP
        # (Megatron psum hand-written in the manual body). Only the
        # EP-style 'levels' TP stays GSPMD-only.
        self.use_manual = bool(tcfg.use_pallas)
        if self.use_manual and not manual_supported(self.mesh, tp_axis):
            warnings.warn(
                "use_pallas=True with tp_axis='levels': the manual fused path "
                "implements hidden-axis TP only, and the fused kernels have no "
                "GSPMD partitioning rule for TP-sharded weights; falling back "
                "to the GSPMD path without Pallas",
                stacklevel=2,
            )
            self.use_manual = False
            # Clear the flag for the GSPMD step too — glom_forward would
            # otherwise emit Mosaic custom calls under TP-sharded weights,
            # exactly the illegal configuration this fallback avoids.
            tcfg = dataclasses.replace(tcfg, use_pallas=False)
            self.tcfg = tcfg

        consensus_fn = (
            None if self.use_manual else make_consensus_fn(self.mesh, cfg, sp_strategy)
        )

        # Telemetry level resolution ONCE the step path is known (same
        # discipline as sp_strategy: the stamped level is the resolved
        # one). The manual shard_map path has no aux channel for "full" —
        # degrade loudly here, then pass the RESOLVED level down so the
        # step builders' re-resolve is a silent no-op.
        self.telemetry_level = diag.resolve_telemetry_level(
            tcfg, supports_full=not self.use_manual
        )
        if self.telemetry_level != tcfg.telemetry_level:
            tcfg = dataclasses.replace(
                tcfg, telemetry_level=self.telemetry_level
            )
            self.tcfg = tcfg

        # Resolve the backward path for the metric records (round-4 weak
        # #3: the vjp dispatch must be as visible as the SP strategy). The
        # manual shard_map bodies never reach the whole-loop VJP; with a
        # seq-sharded consensus (manual OR GSPMD) the backward is the SP
        # collective op's own transpose — labeled 'scan_sharded'
        # consistently on both paths (the mechanism itself is in
        # sp_strategy).
        self.grad_accum = accum_base
        if self.use_manual and mesh_cfg.seq > 1:
            self.vjp_path = "scan_sharded"
        elif self.use_manual:
            from glom_tpu.models.core import resolve_vjp_path
            from glom_tpu.train.trainer import resolve_route_keys

            k, itemsize = resolve_route_keys(cfg, tcfg)
            # seq=1/mp=1 manual shards dispatch to the whole-loop VJP at
            # the shard-local batch when admissible (the one dispatch,
            # models/core.level_major_loop, makes the same
            # resolve_vjp_path call) — the label must follow the
            # dispatch; TP shards (mp>1) stay scan-only.
            self.vjp_path = resolve_vjp_path(
                cfg,
                tcfg.batch_size // accum_base // mesh_cfg.data,
                k,
                remat=tcfg.remat,
                use_pallas=True,
                itemsize=itemsize,
                scan_only=mesh_cfg.model > 1,
            )
        else:
            self.vjp_path = None  # filled from make_train_step in build()

        key = jax.random.PRNGKey(tcfg.seed)
        self.rng, init_key = jax.random.split(key)

        # ZeRO resolution (single source: resolve_zero_stage) BEFORE the
        # state layout is built — the stage decides the optimizer-state
        # sharding the train state is device_put into.
        self.zero_stage = resolve_zero_stage(tcfg, mesh_cfg.data)
        if (
            self.zero_stage >= 1
            and self.use_manual
            and mesh_cfg.model > 1
        ):
            # The explicit manual ZeRO region does not compose the
            # ownership partition with TP-sharded weight shards; the GSPMD
            # form does, but mixing per-step paths would desync state
            # layout from step fn. Degrade loudly.
            warnings.warn(
                "zero_stage >= 1 on the manual (use_pallas) path supports "
                "model == 1 only; running this mesh with zero_stage=0 "
                "(replicated optimizer state)",
                stacklevel=2,
            )
            self.zero_stage = 0

        # Host-side init, then device_put into the sharded layout. (At true
        # pod scale you would jit the init with out_shardings instead; this
        # keeps the init path simple and testable.)
        state, self.optimizer = create_train_state(init_key, cfg, tcfg, optimizer)
        pspecs = denoise_param_specs(tp_axis)
        if self.zero_stage >= 1:
            # Optimizer moments live 1/dp per replica on each leaf's
            # zero_shard_axis; global SHAPES are unchanged, so checkpoints
            # restore across zero_stage / dp changes (test_resilience).
            zpspecs = zero_param_specs(state.params, mesh_cfg.data, tp_axis)
            opt_specs = opt_state_specs(state.opt_state, zpspecs)
        else:
            zpspecs = None
            opt_specs = opt_state_specs(state.opt_state, pspecs)
        state_specs = TrainState(
            params=pspecs,
            opt_state=opt_specs,
            step=P(),
        )
        self.state_shardings = to_named(self.mesh, state_specs)
        self.batch_sharding = NamedSharding(self.mesh, batch_spec())
        self.state = jax.device_put(state, self.state_shardings)
        self.zero_shardings = (
            None
            if zpspecs is None
            else ZeroShardings(
                grads=to_named(self.mesh, zpspecs),
                params=self.state_shardings.params,
            )
        )
        abstract_state = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype), state
        )

        def build(with_grad_norm):
            if self.use_manual and self.zero_stage >= 1:
                from glom_tpu.parallel.manual import make_manual_zero_train_step

                fn = make_manual_zero_train_step(
                    self.mesh, cfg, tcfg, self.optimizer,
                    zero_stage=self.zero_stage,
                    zero_pspecs=zpspecs,
                    opt_pspecs=opt_specs,
                    sp_strategy=sp_strategy,
                    with_grad_norm=with_grad_norm,
                )
            elif self.use_manual:
                fn = make_manual_train_step(
                    self.mesh, cfg, tcfg, self.optimizer,
                    sp_strategy=sp_strategy, with_grad_norm=with_grad_norm,
                )
            else:
                # scan_only: the whole-loop Pallas custom_vjp has no GSPMD
                # partitioning rule, so this build must neither dispatch
                # it nor auto-split the batch chasing it — the single-chip
                # routing heuristics would otherwise evaluate against the
                # GLOBAL batch here (ADVICE round 5, medium).
                fn = make_train_step(
                    cfg, tcfg, self.optimizer, consensus_fn=consensus_fn,
                    with_grad_norm=with_grad_norm,
                    zero_stage=self.zero_stage,
                    zero_shardings=self.zero_shardings,
                    scan_only=True,
                )
                # A GSPMD SP consensus_fn means the backward runs the
                # sharded op's transpose — same label as the manual SP
                # path, not the generic custom-consensus 'scan_dense'.
                self.vjp_path = (
                    "scan_sharded" if consensus_fn is not None else fn.vjp_path
                )
                self.grad_accum = fn.grad_accum
            self._raw_step = fn
            return jax.jit(
                fn,
                in_shardings=(self.state_shardings, self.batch_sharding, None),
                out_shardings=(self.state_shardings, None),
                donate_argnums=(0,),
            )

        self._step = build(True)
        self._step_fast = build(False)
        # Persistent across fit() calls: span 2+ of a checkpointed run is
        # warm, and its first steps are steady-state samples, not compiles.
        self._compile_tracker = set()
        # As persistent: the time between logging boundaries (fit_loop).
        from glom_tpu.tracing.spans import IntervalAccount

        self._interval_account = IntervalAccount()

        # Static observability record, computed AFTER build() so the
        # comm-volume model prices the grad_accum the step actually runs
        # (GSPMD auto-accum can raise it). Pure analytics over abstract
        # shapes — recorded identically with or without a chip.
        from glom_tpu.utils.metrics import (
            comm_volume_model,
            live_bytes_model,
            tree_bytes_per_replica,
        )

        axis_sizes = dict(zip(self.mesh_cfg.axis_names, self.mesh_cfg.shape))
        grad_specs = (
            zpspecs if (self.zero_stage >= 2 and zpspecs is not None) else pspecs
        )
        mem = live_bytes_model(
            abstract_state.params,
            abstract_state.opt_state,
            axis_sizes=axis_sizes,
            param_specs=pspecs,
            opt_specs=opt_specs,
            grad_specs=grad_specs,
        )
        # Wire payload for the DP gradient path: the full (data-replicated)
        # grad bytes each replica contributes — model/seq sharding already
        # divided out, 'data' not (that division is what the collective does).
        wire_bytes = tree_bytes_per_replica(
            abstract_state.params, pspecs, axis_sizes
        )
        # Per-collective wall-time mode (docs/OBSERVABILITY.md, "Capacity
        # observatory"): resolved ONCE like telemetry_level and stamped.
        # Only the manual zero>=1 route has registered sites; everywhere
        # else the mode resolves to "off" (stamped — a record must never
        # claim a timing harness that didn't run). "full" degrades to
        # "sampled" loudly: the jit-on-first-call trainer has no AOT seam
        # for the io_callback brackets (the serve engine's has).
        from glom_tpu.telemetry.counters import resolve_collective_timing

        timing_sites_reachable = self.use_manual and self.zero_stage >= 1
        if timing_sites_reachable:
            self.collective_timing = resolve_collective_timing(
                tcfg.collective_timing,
                supports_full=False,
                path="the manual trainer",
            )
        else:
            resolve_collective_timing(tcfg.collective_timing)  # validate
            if tcfg.collective_timing != "off":
                warnings.warn(
                    "collective_timing has no registered sites on this "
                    "route (GSPMD, or manual zero_stage 0) — resolving "
                    "'off'; the stamped mode is the resolved one",
                    stacklevel=2,
                )
            self.collective_timing = "off"
        self.collective_sampler = None
        self._static_record = {
            "zero_stage": self.zero_stage,
            "telemetry_level": self.telemetry_level,
            "collective_timing": self.collective_timing,
            **mem,
            **comm_volume_model(
                wire_bytes,
                wire_bytes,
                self.mesh_cfg.data,
                self.zero_stage,
                grad_accum=self.grad_accum,
            ),
        }

        # MEASURED collective counters (telemetry/counters.py): one
        # abstract trace of the step with the recording context active —
        # the manual ZeRO path's explicit psum/psum_scatter/all_gather
        # sites report their actual per-replica ring wire bytes, and the
        # measured-vs-modeled drift is stamped on every record (the model
        # silently diverging from the emitted collectives is itself the
        # bug telemetry exists to catch). Gated on telemetry_level (the
        # extra trace is not free) and on the path that HAS explicit
        # sites; GSPMD steps carry the model only.
        if (
            self.telemetry_level != "off" or self.collective_timing != "off"
        ) and timing_sites_reachable:
            from glom_tpu.telemetry.counters import (
                CollectiveCounters,
                comm_drift,
                recording,
            )

            counters = CollectiveCounters()
            abstract_batch = jax.ShapeDtypeStruct(
                (tcfg.batch_size, cfg.channels, cfg.image_size, cfg.image_size),
                jnp.float32,
            )
            with recording(counters):
                jax.eval_shape(
                    self._raw_step, abstract_state, abstract_batch,
                    jax.random.PRNGKey(0),
                )
            measured = counters.totals()
            self._static_record.update(measured)
            self._static_record.update(
                comm_drift(measured, self._static_record)
            )
            if self.collective_timing != "off":
                # The sampled-mode harness (telemetry/comm_time.py): the
                # counting trace just populated the site registry (site,
                # axis, shard-local shape, scatter/gather dim) — every
                # collective_timing_interval-th fit-loop logging boundary
                # re-dispatches each site as its own timed sub-graph and
                # stamps "collective_time" records with the α-β
                # comm_time_model drift (fit() wires the probe).
                from glom_tpu.telemetry.comm_time import (
                    CollectiveTimeSampler,
                )

                self.collective_sampler = CollectiveTimeSampler(
                    self.mesh,
                    counters.sites,
                    interval=tcfg.collective_timing_interval,
                )

        from glom_tpu.tracing.memory import model_live_bytes_total

        self._model_live_bytes = model_live_bytes_total(self._static_record)

    def step(self, batch: np.ndarray):
        # device_put on the host array shards directly host->devices in one
        # transfer (no staging of the full batch on device 0 first); a no-op
        # when the batch was already staged by prefetch_to_device.
        batch = jax.device_put(batch, self.batch_sharding)
        self.rng, step_rng = jax.random.split(self.rng)
        self.state, metrics = self._step(self.state, batch, step_rng)
        return self._annotate(metrics)

    def _annotate(self, metrics) -> dict:
        """Static routing facts attached OUTSIDE jit (strings can't ride
        the compiled metrics dict) — same record shape as Trainer's,
        including the watchdog backend state."""
        from glom_tpu.telemetry.watchdog import backend_record

        metrics = dict(metrics)
        metrics["sp_strategy"] = self.sp_strategy
        metrics["vjp_path"] = self.vjp_path
        metrics["grad_accum"] = self.grad_accum
        metrics.update(self._static_record)
        metrics.update(backend_record())
        return metrics

    def step_fast(self, batch: np.ndarray):
        """Non-logging iteration: no grad-norm sweep."""
        batch = jax.device_put(batch, self.batch_sharding)
        self.rng, step_rng = jax.random.split(self.rng)
        self.state, metrics = self._step_fast(self.state, batch, step_rng)
        return self._annotate(metrics)

    def _memory_record(self) -> dict:
        """Live HBM watermarks (device 0 of the mesh) reconciled against
        the analytic PER-REPLICA live-bytes model — the measured
        counterpart of the `*_bytes_per_replica` keys, same discipline as
        the collective counters' comm_model_drift."""
        from glom_tpu.tracing.memory import memory_record

        return memory_record(
            self._model_live_bytes, device=self.mesh.devices.flat[0]
        )

    def collective_time_records(self, *, force: bool = False) -> list:
        """Stamped "collective_time" rows from the sampled timing harness
        (empty off-mode, and between sampling intervals unless `force`).
        fit() drains this at every logging boundary; direct step() drivers
        (benches) call it themselves."""
        if self.collective_sampler is None:
            return []
        path = f"train-zero{self.zero_stage}"
        if force:
            from glom_tpu.telemetry.comm_time import collective_time_records

            return collective_time_records(
                self.collective_sampler.sample(), path=path, mode="sampled"
            )
        return self.collective_sampler.maybe_sample(path=path)

    def fit(
        self,
        data: Iterator,
        num_steps: int,
        *,
        log_every: int = 10,
        prefetch: int = 0,
        trace_capture=None,
    ) -> list[dict]:
        """prefetch > 0 stages that many upcoming batches SHARDED on their
        target devices from a background thread (the step's device_put then
        sees already-committed shards and is a no-op).

        CAUTION: the wrap is PER CALL — repeated fit(prefetch=N) over one
        shared iterator discards staged batches at every boundary; wrap
        once with data.prefetch_to_device for that pattern (see
        train/cli.py)."""
        if prefetch > 0:
            from glom_tpu.data import prefetch_to_device

            data = prefetch_to_device(
                data, size=prefetch, sharding=self.batch_sharding
            )
        return fit_loop(
            self.step,
            data,
            num_steps,
            log_every=log_every,
            metrics_writer=self.metrics_writer,
            step_fast=self.step_fast,
            compile_tracker=self._compile_tracker,
            trace_capture=trace_capture,
            memory_probe=self._memory_record,
            aux_records_probe=(
                self.collective_time_records
                if self.collective_sampler is not None else None
            ),
            interval_account=self._interval_account,
        )
