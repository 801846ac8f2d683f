"""The training loop the reference never had (SURVEY.md §5: trainer = absent
in reference; README recipe only). TPU-native design:

  * `train_step` is a pure function (state, batch, rng) -> (state, metrics),
    jitted once; under a mesh it is pjit-sharded by glom_tpu.parallel.
  * optimizer = any optax GradientTransformation (Adam by default).
  * donate_argnums on the state so XLA updates parameters in place —
    essential at pod scale where two copies of the optimizer state would
    blow HBM.
"""

from __future__ import annotations

import sys
import time
from contextlib import nullcontext
from typing import Any, Callable, Iterator, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from glom_tpu.models.core import ConsensusFn, resolve_vjp_path
from glom_tpu.telemetry import diagnostics as diag
from glom_tpu.train.objectives import (
    Objective,
    default_recon_index,
    denoise_loss,
    init_params,
    lm_objective,
)
from glom_tpu.utils.config import GlomConfig, TrainConfig


class TrainState(NamedTuple):
    params: Any  # the objective's: DenoiseParams, or the language model's tree
    opt_state: Any
    step: jnp.ndarray  # scalar int32


def create_train_state(
    key: jax.Array,
    cfg,
    tcfg: TrainConfig,
    optimizer: Optional[optax.GradientTransformation] = None,
) -> Tuple[TrainState, optax.GradientTransformation]:
    optimizer = optimizer if optimizer is not None else default_optimizer(tcfg)
    params = init_params(key, cfg)
    return (
        TrainState(
            params=params,
            opt_state=optimizer.init(params),
            step=jnp.zeros((), jnp.int32),
        ),
        optimizer,
    )


def make_lr_schedule(tcfg: TrainConfig):
    """Learning-rate schedule from the config: a float (constant) or an
    optax schedule fn. Cosine decays to lr_final_fraction * lr; for
    warmup_cosine, schedule_steps is the TOTAL length INCLUDING the
    linear warmup (optax semantics: cosine decay runs over
    schedule_steps - warmup_steps)."""
    if tcfg.lr_schedule == "constant":
        return tcfg.learning_rate
    if tcfg.lr_schedule == "cosine":
        return optax.cosine_decay_schedule(
            tcfg.learning_rate, tcfg.schedule_steps, alpha=tcfg.lr_final_fraction
        )
    if tcfg.lr_schedule == "warmup_cosine":
        if not 0 <= tcfg.warmup_steps < tcfg.schedule_steps:
            raise ValueError(
                f"warmup_steps={tcfg.warmup_steps} must be < schedule_steps="
                f"{tcfg.schedule_steps} (schedule_steps is the TOTAL length "
                "including warmup)"
            )
        return optax.warmup_cosine_decay_schedule(
            init_value=0.0,
            peak_value=tcfg.learning_rate,
            warmup_steps=tcfg.warmup_steps,
            decay_steps=tcfg.schedule_steps,
            end_value=tcfg.learning_rate * tcfg.lr_final_fraction,
        )
    raise ValueError(
        f"lr_schedule={tcfg.lr_schedule!r}: one of 'constant', 'cosine', "
        "'warmup_cosine'"
    )


def pinned_grad_accum(tcfg: TrainConfig) -> int:
    """The microbatch count an EXPLICIT TrainConfig.grad_accum pins, or the
    single-pass base (1) when None — None is the auto-routing sentinel and
    only resolve_training_route may raise it. THE single None-resolution
    source: every numeric use of tcfg.grad_accum (validation, manual-path
    scans, comm pricing) goes through here so an explicit user value is
    never silently overridden (ADVICE round 5)."""
    accum = 1 if tcfg.grad_accum is None else tcfg.grad_accum
    if accum < 1:
        raise ValueError(f"grad_accum={tcfg.grad_accum} must be >= 1 or None")
    return accum


def accumulate_grads(loss_fn, params, img, noise, accum: int,
                     grad_transform=None, grad_init=None, has_aux=False):
    """Exact microbatch gradient accumulation shared by the single-device,
    GSPMD, and manual-shard_map train steps: STRIDED split (microbatch i
    takes rows i, i+accum, ...) so a batch sharded over a 'data' mesh axis
    keeps every microbatch row-local to its shard (a contiguous split would
    reshuffle half the batch across devices on every scan step); the
    accumulated sum over all examples is invariant to the grouping, so
    loss/grads equal the full-batch values exactly (mean of microbatch
    means). Returns (loss, grads).

    grad_transform/grad_init are the ZeRO stage-2 hook — the scatter must
    happen per microbatch so the accumulation buffer only ever holds the
    1/dp owned shard (the sum over microbatches commutes with the linear
    scatter, so the math is still exact):
      * GSPMD step: transform = with_sharding_constraint to the
        data-sharded layout (XLA lowers to a per-microbatch
        reduce-scatter); init = zeros under the same constraint.
      * manual ZeRO step: transform = the explicit psum_scatter tree;
        init = zeros at the 1/dp shard shapes (the carry must match the
        transformed gradients, which is why init is a separate hook).

    has_aux=True mirrors jax.value_and_grad(has_aux=True): loss_fn returns
    (loss, aux) and the call returns ((loss, aux_mean), grads) — the
    telemetry "full" diagnostics ride the microbatch scan as a mean over
    microbatches (every aux stat here is itself a mean, so the grouping
    invariance argument above applies to it too)."""
    imgs = img.reshape(-1, accum, *img.shape[1:]).swapaxes(0, 1)
    noises = noise.reshape(-1, accum, *noise.shape[1:]).swapaxes(0, 1)

    def micro(carry, xs):
        acc_l, acc_aux, acc_g = carry
        mi, mn = xs
        if has_aux:
            (l, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(
                params, mi, mn
            )
            acc_aux = jax.tree_util.tree_map(jnp.add, acc_aux, aux)
        else:
            l, g = jax.value_and_grad(loss_fn)(params, mi, mn)
        if grad_transform is not None:
            g = grad_transform(g)
        return (acc_l + l, acc_aux, jax.tree_util.tree_map(jnp.add, acc_g, g)), None

    zeros = (
        grad_init()
        if grad_init is not None
        else jax.tree_util.tree_map(jnp.zeros_like, params)
    )
    if has_aux:
        # Abstract-eval one microbatch for the aux accumulator's shapes
        # (the carry must be built before the scan body ever runs).
        _, aux_shape = jax.eval_shape(loss_fn, params, imgs[0], noises[0])
        aux_zeros = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), aux_shape
        )
    else:
        aux_zeros = ()
    (loss_sum, aux_sum, grads_sum), _ = jax.lax.scan(
        micro, (jnp.zeros((), jnp.float32), aux_zeros, zeros), (imgs, noises)
    )
    loss = loss_sum / accum
    grads = jax.tree_util.tree_map(lambda t: t / accum, grads_sum)
    if has_aux:
        aux = jax.tree_util.tree_map(lambda t: t / accum, aux_sum)
        return (loss, aux), grads
    return loss, grads


def resolve_route_keys(cfg: GlomConfig, tcfg: TrainConfig) -> Tuple[int, int]:
    """(effective loss iters k, compute itemsize) for vjp-path resolution —
    the ONE copy of the T/k defaulting + dtype prologue (both
    resolve_training_route and DistributedTrainer's manual-branch labeling
    use it; two copies would let a rule change silently resolve different
    backward labels at different call sites)."""
    T = tcfg.iters if tcfg.iters is not None else cfg.default_iters
    k = (
        tcfg.recon_iter_index
        if tcfg.recon_iter_index is not None
        else default_recon_index(T)
    )
    return k, 2 if tcfg.compute_dtype == "bfloat16" else 4


def resolve_training_route(
    cfg: GlomConfig,
    tcfg: TrainConfig,
    *,
    custom_consensus: bool = False,
    scan_only: bool = False,
) -> Tuple[int, str]:
    """Effective (grad_accum, vjp_path) for this training config.

    The framework must never hand out a below-baseline regime it knows how
    to beat (round-4 batch-128 measured 0.96x vs baseline on the scan path
    while grad_accum=2 over batch-64 microbatches rides the fused-loop VJP
    at 1.17x): when grad_accum is None (auto) and the full batch misses
    the fused loop, try power-of-two microbatch splits and take the first
    that lands on it — the accumulation is exact (accumulate_grads), so
    this changes the schedule, never the math. An EXPLICIT grad_accum —
    INCLUDING 1 — is always honored as given (1 is the supported opt-out
    for the single-pass full-batch step; ADVICE round 5).

    scan_only=True (the GSPMD DistributedTrainer build) excludes the fused
    loop AND the auto-split that exists only to reach it: the whole-loop
    Pallas custom_vjp has no partitioning rule, so dispatching it on
    GSPMD-sharded arrays — which the auto-split's single-chip heuristics
    evaluated against the GLOBAL batch could do — is a compile failure or
    full-replication OOM, not a speedup."""
    k, itemsize = resolve_route_keys(cfg, tcfg)
    kw = dict(
        remat=tcfg.remat,
        use_pallas=tcfg.use_pallas,
        itemsize=itemsize,
        custom_consensus=custom_consensus,
        scan_only=scan_only,
    )
    accum = pinned_grad_accum(tcfg)
    path = resolve_vjp_path(cfg, tcfg.batch_size // accum, k, **kw)
    if (
        tcfg.grad_accum is None
        and not scan_only
        and path != "fused_loop"
    ):
        a = 2
        while a <= 16 and tcfg.batch_size % a == 0 and tcfg.batch_size // a >= 8:
            if resolve_vjp_path(cfg, tcfg.batch_size // a, k, **kw) == "fused_loop":
                return a, "fused_loop"
            a *= 2
    return accum, path


def resolve_zero_stage(tcfg: TrainConfig, dp: int) -> int:
    """Effective ZeRO stage for this run — THE single resolution source
    (same discipline as resolve_vjp_path / effective_sp_strategy: both
    trainer paths call this once and stamp its output into every metrics
    record, so a run can never shard differently than its logs claim).
    dp == 1 has nothing to shard and resolves to 0 silently, mirroring
    seq <= 1 resolving sp_strategy to 'none'."""
    if tcfg.zero_stage not in (0, 1, 2):
        raise ValueError(
            f"zero_stage={tcfg.zero_stage!r}: must be 0 (replicated), "
            "1 (sharded optimizer state), or 2 (+ sharded grad accumulator)"
        )
    if dp <= 1:
        return 0
    return tcfg.zero_stage


class ZeroShardings(NamedTuple):
    """The two NamedSharding trees the GSPMD ZeRO step constrains with:
    `grads` (param-shaped, 'data'-sharded on each leaf's zero_shard_axis —
    the reduce-scatter layout, also the optimizer-moment layout) and
    `params` (the base data-replicated layout the all-gather restores)."""

    grads: Any
    params: Any


def default_optimizer(tcfg: TrainConfig) -> optax.GradientTransformation:
    lr = make_lr_schedule(tcfg)
    if tcfg.weight_decay > 0:
        return optax.adamw(lr, weight_decay=tcfg.weight_decay)
    return optax.adam(lr)


def denoise_objective(
    cfg: GlomConfig,
    tcfg: TrainConfig,
    *,
    consensus_fn: Optional[ConsensusFn] = None,
    scan_only: bool = False,
) -> Objective:
    """GLOM's self-supervised denoising as the trainer's objective: the
    noise is what is drawn, on the device, outside the gradient; the route
    through the kernels (`resolve_training_route`) is resolved here, once."""
    if tcfg.compute_dtype not in ("float32", "bfloat16"):
        raise ValueError(
            f"compute_dtype={tcfg.compute_dtype!r}: must be 'float32' or 'bfloat16'"
        )
    pinned = pinned_grad_accum(tcfg)
    if tcfg.batch_size % pinned != 0:
        raise ValueError(
            f"grad_accum={tcfg.grad_accum} must divide batch_size="
            f"{tcfg.batch_size}"
        )
    compute_dtype = jnp.bfloat16 if tcfg.compute_dtype == "bfloat16" else None
    # Auto-route oversized batches through exact microbatch accumulation
    # when that recovers the fused-loop VJP (see resolve_training_route);
    # the decision is static, exposed on the step fn (.grad_accum /
    # .vjp_path), and logged by the trainers next to sp_strategy.
    grad_accum, vjp_path = resolve_training_route(
        cfg, tcfg, custom_consensus=consensus_fn is not None,
        scan_only=scan_only,
    )
    full = diag.resolve_telemetry_level(tcfg) == "full"

    def draw(rng, step, img):
        with jax.named_scope("noise"):
            noise_rng = jax.random.fold_in(rng, step)
            return tcfg.noise_std * jax.random.normal(
                noise_rng, img.shape, img.dtype
            )

    def loss_of(params, img, noise):
        return denoise_loss(
            params,
            img,
            noise,
            cfg,
            recon_index=tcfg.recon_iter_index,
            iters=tcfg.iters,
            remat=tcfg.remat,
            compute_dtype=compute_dtype,
            consensus_fn=consensus_fn,
            use_pallas=tcfg.use_pallas,
            unroll=tcfg.scan_unroll,
            with_diagnostics=full,
        )

    return Objective(
        draw=draw,
        loss=loss_of,
        has_aux=full,
        batch_shape=(cfg.channels, cfg.image_size, cfg.image_size),
        batch_dtype=jnp.float32,
        grad_accum=grad_accum,
        vjp_path=vjp_path,
    )


def objective_for(
    cfg,
    tcfg: TrainConfig,
    *,
    consensus_fn: Optional[ConsensusFn] = None,
    scan_only: bool = False,
) -> Objective:
    """The objective a model configuration trains by: the seam between a
    model family and the one step builder, fit loop and trainer."""
    if isinstance(cfg, GlomConfig):
        return denoise_objective(
            cfg, tcfg, consensus_fn=consensus_fn, scan_only=scan_only
        )
    if consensus_fn is not None:
        raise ValueError("consensus_fn belongs to GLOM's objective")
    return lm_objective(cfg, tcfg)


def make_train_step(
    cfg,
    tcfg: TrainConfig,
    optimizer: optax.GradientTransformation,
    *,
    consensus_fn: Optional[ConsensusFn] = None,
    with_grad_norm: bool = True,
    zero_stage: int = 0,
    zero_shardings: Optional[ZeroShardings] = None,
    scan_only: bool = False,
) -> Callable[[TrainState, jnp.ndarray, jax.Array], Tuple[TrainState, dict]]:
    """Build the pure train step. Noise is generated ON DEVICE from the rng
    (no host->device transfer of noise tensors).

    with_grad_norm=False omits the grad-norm metric: optax.global_norm is
    a full extra sweep over every gradient buffer, pure observability —
    the fit loops compile BOTH variants and run the fast one on
    non-logging steps (the sustained-throughput step).

    zero_stage >= 1 with zero_shardings runs the GSPMD form of the ZeRO
    weight update (Xu et al. 2020): gradients are constrained to the
    data-sharded layout before optimizer.update — XLA lowers the DP
    reduction to a reduce-scatter instead of an allreduce — the update
    (reading the 1/dp optimizer-moment shard the state carries) computes
    only on the owned shard, and the updated params are constrained back
    to the replicated layout, which lowers to the all-gather. Stage 2
    additionally pushes the constraint inside the microbatch accumulation
    so the grad buffer itself lives sharded.

    scan_only=True (the GSPMD DistributedTrainer build) keeps both the
    fused-loop dispatch AND the auto grad-accum off this step — the Pallas
    whole-loop custom_vjp is illegal on GSPMD-sharded arrays.

    tcfg.telemetry_level != "off" adds the in-graph diagnostics
    (telemetry/diagnostics.py): grad/update/param norms and the NaN/Inf
    guard on EVERY variant including the fast one (a guard that only runs
    on logging steps misses 9 of every 10 anomalies), plus per-level
    consensus agreement at "full"."""
    objective = objective_for(
        cfg, tcfg, consensus_fn=consensus_fn, scan_only=scan_only
    )
    grad_accum, vjp_path = objective.grad_accum, objective.vjp_path
    level = diag.resolve_telemetry_level(tcfg)

    def train_step(state: TrainState, batch: jnp.ndarray, rng: jax.Array):
        drawn = objective.draw(rng, state.step, batch)

        if grad_accum > 1:
            if zero_stage >= 2 and zero_shardings is not None:
                constrain = lambda g: jax.lax.with_sharding_constraint(
                    g, zero_shardings.grads
                )
                gkw = dict(
                    grad_transform=constrain,
                    grad_init=lambda: constrain(
                        jax.tree_util.tree_map(jnp.zeros_like, state.params)
                    ),
                )
            else:
                gkw = {}
            loss, grads = accumulate_grads(
                objective.loss, state.params, batch, drawn, grad_accum,
                has_aux=objective.has_aux, **gkw
            )
        else:
            loss, grads = jax.value_and_grad(
                objective.loss, has_aux=objective.has_aux
            )(state.params, batch, drawn)
        aux = None
        if objective.has_aux:
            loss, aux = loss
        metrics = {}
        with jax.named_scope("optimizer"):
            if zero_stage >= 1 and zero_shardings is not None:
                # Reduce-scatter: the cross-replica grad reduction lands
                # each leaf already split on its zero_shard_axis.
                grads = jax.lax.with_sharding_constraint(
                    grads, zero_shardings.grads
                )
            updates, opt_state = optimizer.update(
                grads, state.opt_state, state.params
            )
            params = optax.apply_updates(state.params, updates)
            if zero_stage >= 1 and zero_shardings is not None:
                # All-gather the updated shards back to the replicated
                # layout the next forward reads.
                params = jax.lax.with_sharding_constraint(
                    params, zero_shardings.params
                )
        metrics.update({"loss": loss, "step": state.step})
        # What only observability needs: the grad-norm sweep of the logging
        # variant, and the telemetry taps and guard of every variant.
        with jax.named_scope("step_metrics"):
            if with_grad_norm or level != "off":
                grad_norm = optax.global_norm(grads)
            if with_grad_norm:
                metrics["grad_norm"] = grad_norm
            if level != "off":
                taps = diag.scalar_taps(
                    loss=loss, grad_norm=grad_norm, updates=updates,
                    params=params,
                )
                nonfinite = taps.pop("nonfinite")
                if tcfg.nonfinite_policy == "skip":
                    # Drop the poisoned update in-graph: params AND
                    # optimizer state keep their previous values; the step
                    # counter still advances so schedules/logs stay aligned.
                    params = diag.guard_update(nonfinite, params, state.params)
                    opt_state = diag.guard_update(
                        nonfinite, opt_state, state.opt_state
                    )
                    metrics["skipped_nonfinite"] = nonfinite.astype(jnp.int32)
                metrics.update(taps)
                metrics["nonfinite_step"] = nonfinite.astype(jnp.int32)
            if aux is not None:
                # What the objective reports beside its loss: GLOM's
                # per-level agreement (telemetry "full"), the language
                # model's routing counters.
                metrics.update(aux)
        return TrainState(params, opt_state, state.step + 1), metrics

    # Static routing facts for the trainers' metric records (strings can't
    # ride the jitted metrics dict).
    train_step.grad_accum = grad_accum
    train_step.vjp_path = vjp_path
    return train_step


def _jsonable(v):
    """Metrics-record value -> JSON scalar (strings/bools/None pass
    through; device scalars fetch)."""
    if v is None or isinstance(v, (str, bool)):
        return v
    return float(v)


def fit_loop(
    step: Callable[[Any], dict],
    data: Iterator,
    num_steps: int,
    *,
    log_every: int = 10,
    metrics_writer=None,
    step_fast: Optional[Callable[[Any], dict]] = None,
    compile_tracker: Optional[set] = None,
    trace_capture=None,
    memory_probe: Optional[Callable[[], dict]] = None,
    aux_records_probe: Optional[Callable[[], list]] = None,
    interval_account=None,
) -> list[dict]:
    """Shared training loop: pull batches, step, log every `log_every`.
    Used by both the single-device Trainer and the DistributedTrainer.
    step_fast (when given) runs the non-logging iterations — the variant
    without observability-only work (grad-norm sweep).

    Every logging record is a schema-stamped "train_step" event carrying
    the step-time histogram (compile split out per jit variant — see
    sinks.StepTimeStats for the async-dispatch reading of p50 vs p95); a
    step flagged non-finite by the in-graph guard emits a structured
    "anomaly" event into the metrics stream at the next logging step. The
    flags of NON-logging steps are kept as device scalars and fetched
    only at the log boundary (by then they are long computed, so the
    fetch adds no pipeline stall and every incident is reported — not
    just the ones landing on a logging step). The returned history stays
    homogeneous train_step records (consumers index loss/steps_per_sec);
    anomaly events go to the writer only.

    compile_tracker: pass a PERSISTENT set when calling fit_loop more than
    once over the same jitted steps (the trainers do — fit() per
    checkpoint span): the jit cache is warm in span 2+, and a fresh
    tracker would mislabel each span's first steps as compiles, faking a
    compile_time_s and dropping real samples from the percentiles.

    interval_account: the same, for the time between logging boundaries —
    a tracing.spans.IntervalAccount the trainers own and hand to every
    call (without one the call makes its own and remembers nothing of the
    last). Every logging record carries its interval, from this account's
    previous boundary (or its first entry here) to the end of this
    boundary's host_log_fetch: interval_steps, interval_ms,
    interval_other_ms (the wall outside the loop's three spans),
    host_gc_ms / host_gc_collections / host_gc_gen2, host_cpu_ms,
    host_run_delay_ms (where /proc has it), host_nivcsw, host_majflt, and
    stall_ms with, where it is not 0, stall_phase and stall_cause
    (spans.judge_interval); a stalled interval also writes one line to
    standard error.

    Tracing hooks (glom_tpu/tracing/, docs/OBSERVABILITY.md):
      * host spans — host_data_next / host_step_dispatch / host_log_fetch
        are aggregated per phase between logging steps (SpanAggregator)
        and drained as one "span" record per phase into the metrics
        stream at each log boundary, together with the prefetch worker's
        host_prefetch_next / host_prefetch_stage when `data` is a
        prefetched stream; each span also enters a profiler
        TraceAnnotation of its name carrying this loop's step index, so a
        device trace shows which phase the host was in;
      * trace_capture — a tracing.capture.TraceCapture whose [A, B] step
        window this loop advances (the capture's counter persists across
        fit() calls; the CALLER owns close());
      * memory_probe — called at logging steps; its dict (HBM watermarks
        + model drift, tracing.memory.memory_record) rides the record;
      * aux_records_probe — called at logging steps; returns a list of
        ALREADY-STAMPED standalone records written to the same stream
        (the collective-timing sampler's "collective_time" rows —
        DistributedTrainer wires it; docs/OBSERVABILITY.md, Capacity
        observatory);
      * flight recorder — every record this loop produces reaches the
        global recorder (via MetricsWriter.write, or directly when no
        writer is attached), and an unhandled exception dumps the buffer
        (`fit-loop-exception`) before re-raising — the crash postmortem
        rounds 4-5 never had."""
    from glom_tpu.telemetry import schema
    from glom_tpu.telemetry.sinks import StepTimeStats
    from glom_tpu.tracing import flight
    from glom_tpu.tracing.spans import IntervalAccount, SpanAggregator, span

    history = []
    stats = StepTimeStats()
    spans = SpanAggregator()
    account = interval_account or IntervalAccount()
    account.enter()
    # Which jit variant's compile step was seen, keyed by role (bound
    # methods get fresh ids per access, so identity keys wouldn't survive
    # a second fit() call even with a shared tracker).
    compiled = compile_tracker if compile_tracker is not None else set()
    pending_flags = []  # (step index, device-scalar nonfinite flag)
    # A prefetched stream (data.prefetch_to_device) times its worker
    # thread's phases itself; its rollups join the loop's at each boundary.
    data_span_records = getattr(data, "span_records", None)
    t0 = time.perf_counter()
    i = -1
    try:
        for i in range(num_steps):
            logging_step = (i + 1) % log_every == 0 or i == num_steps - 1
            use_full = logging_step or step_fast is None
            fn = step if use_full else step_fast
            key = "step" if use_full else "step_fast"
            first_call = key not in compiled
            compiled.add(key)
            # Pull the batch BEFORE the timer: host data-generation time is
            # a data-pipeline signal, not step time — folding it in would
            # make a loader stall read as a step/compile regression on
            # every record.
            with span("host_data_next", aggregator=spans, step=i):
                batch = next(data)
            # The capture's unit goes around the span, not inside it: a unit
            # that opens or closes the profiler does so outside the
            # dispatch's time, and the window's first dispatch is a span the
            # trace holds.
            with trace_capture.unit() if trace_capture is not None else nullcontext():
                with span("host_step_dispatch", aggregator=spans, step=i) as dispatch:
                    metrics = fn(batch)
            # Each jit variant's first call is trace+compile — both the
            # fast step's (iteration 0) and the logging step's (first
            # log boundary) — and must not pollute the steady-state
            # percentiles.
            stats.observe(dispatch.dur_s, is_compile=first_call)
            account.step(first_call)
            if "nonfinite_step" in metrics and not logging_step:
                pending_flags.append((i, metrics["nonfinite_step"]))
            if not logging_step:
                continue
            with span("host_log_fetch", aggregator=spans, step=i):
                metrics = diag.split_level_agreement(metrics)
                metrics = {k: _jsonable(v) for k, v in metrics.items()}
            t_boundary = time.perf_counter()
            at_step = {"step": metrics.get("step", float(i))}
            span_recs = spans.records(extra=at_step)
            interval, stall_line = account.close(
                t_boundary, at_step["step"],
                {r["name"]: 1e3 * r["dur_s"] for r in span_recs})
            if stall_line is not None:
                print(stall_line, file=sys.stderr, flush=True)
            metrics["steps_per_sec"] = (i + 1) / (time.perf_counter() - t0)
            metrics.update(stats.summary())
            metrics.update(interval)
            if memory_probe is not None:
                metrics.update(memory_probe() or {})
            rec = schema.stamp(metrics, kind="train_step")
            history.append(rec)
            if metrics_writer is not None:
                metrics_writer.write(rec)
            else:
                # No writer: feed the flight recorder directly so a crash
                # in a writerless run still has a postmortem trail.
                flight.observe_event(rec)
            if data_span_records is not None:
                span_recs += data_span_records(extra=at_step)
            for srec in span_recs:
                if metrics_writer is not None:
                    metrics_writer.write(srec)
                else:
                    flight.observe_event(srec)
            if aux_records_probe is not None:
                # Already-stamped standalone records minted at the logging
                # boundary (the collective-timing sampler's
                # "collective_time" rows — DistributedTrainer wires it):
                # unlike memory_probe's dict these do NOT merge into the
                # train_step record; they are their own schema kinds.
                for arec in aux_records_probe() or []:
                    if metrics_writer is not None:
                        metrics_writer.write(arec)
                    else:
                        flight.observe_event(arec)
            flagged = [k for k, v in pending_flags if float(v)]
            pending_flags = []
            if rec.get("nonfinite_step"):
                flagged.append(i)
            if flagged:
                anomaly = schema.stamp(
                    {
                        "step": rec.get("step", float(i)),
                        "reason": "nonfinite_loss_or_grad",
                        "policy": (
                            "skip" if "skipped_nonfinite" in rec else "warn"
                        ),
                        "count": len(flagged),
                        "flagged_iterations": flagged,
                        "loss": rec.get("loss"),
                        "grad_norm": rec.get("grad_norm"),
                    },
                    kind="anomaly",
                )
                if metrics_writer is not None:
                    metrics_writer.write(anomaly)
                else:
                    flight.observe_event(anomaly)
    except BaseException as e:
        # The postmortem the crash would otherwise take with it: dump the
        # last-N event buffer (no-op without a global recorder), then
        # re-raise unchanged.
        flight.dump_flight_recorder(
            "fit-loop-exception",
            context={
                "exception": f"{type(e).__name__}: {e}"[:300],
                "at_iteration": i,
            },
        )
        raise
    return history


class Trainer:
    """Single-host convenience wrapper: jit, data iteration, metric logging.

    The distributed path (glom_tpu.parallel.runtime.DistributedTrainer)
    reuses make_train_step under pjit — this class is the 1-device base.
    """

    def __init__(
        self,
        cfg,  # a GlomConfig or a language model's config: whatever objective_for knows
        tcfg: TrainConfig,
        *,
        optimizer: Optional[optax.GradientTransformation] = None,
        consensus_fn: Optional[ConsensusFn] = None,
        metrics_writer=None,
    ):
        self.cfg = cfg
        self.tcfg = tcfg
        key = jax.random.PRNGKey(tcfg.seed)
        self.rng, init_key = jax.random.split(key)
        self.state, self.optimizer = create_train_state(init_key, cfg, tcfg, optimizer)
        # Single device: dp == 1, so ZeRO resolves to 0 (validating the
        # configured value), and the live-bytes model reports the fully
        # replicated layout with zero collective traffic — the baseline
        # row the distributed records are compared against.
        self.zero_stage = resolve_zero_stage(tcfg, 1)
        self.telemetry_level = diag.resolve_telemetry_level(tcfg)
        step_fn = make_train_step(
            cfg, tcfg, self.optimizer, consensus_fn=consensus_fn,
        )
        self.vjp_path = step_fn.vjp_path
        self.grad_accum = step_fn.grad_accum
        from glom_tpu.utils.metrics import comm_volume_model, live_bytes_model

        mem = live_bytes_model(
            self.state.params, self.state.opt_state, axis_sizes={},
            param_specs=None, opt_specs=None, grad_specs=None,
        )
        self._static_record = {
            "zero_stage": self.zero_stage,
            "telemetry_level": self.telemetry_level,
            **mem,
            **comm_volume_model(
                mem["grads_bytes_per_replica"],
                mem["params_bytes_per_replica"],
                1,
                self.zero_stage,
            ),
        }
        from glom_tpu.tracing.memory import model_live_bytes_total

        self._model_live_bytes = model_live_bytes_total(self._static_record)
        self._step = jax.jit(step_fn, donate_argnums=(0,))
        fast_fn = make_train_step(
            cfg, tcfg, self.optimizer,
            consensus_fn=consensus_fn, with_grad_norm=False,
        )
        self._step_fast = jax.jit(fast_fn, donate_argnums=(0,))
        self.metrics_writer = metrics_writer
        # Persistent across fit() calls: span 2+ of a checkpointed run is
        # warm, and its first steps are steady-state samples, not compiles.
        self._compile_tracker = set()
        # As persistent, for the time between logging boundaries: what an
        # interval usually costs outlives the fit() call that measured it.
        from glom_tpu.tracing.spans import IntervalAccount

        self._interval_account = IntervalAccount()

    def _annotate(self, metrics) -> dict:
        """Static routing facts, attached OUTSIDE jit (strings can't ride
        the compiled metrics dict) — a run's records must name the backward
        it actually used (same discipline as sp_strategy). Watchdog backend
        state rides every record too (a dict read; the probe itself lives
        in the global watchdog, not here)."""
        from glom_tpu.telemetry.watchdog import backend_record

        metrics = dict(metrics)
        metrics["vjp_path"] = self.vjp_path
        metrics["grad_accum"] = self.grad_accum
        metrics.update(self._static_record)
        metrics.update(backend_record())
        return metrics

    def step(self, batch) -> dict:
        self.rng, step_rng = jax.random.split(self.rng)
        self.state, metrics = self._step(self.state, batch, step_rng)
        return self._annotate(metrics)

    def step_fast(self, batch) -> dict:
        """The sustained-throughput step: no grad-norm sweep (fit runs this
        on non-logging iterations)."""
        self.rng, step_rng = jax.random.split(self.rng)
        self.state, metrics = self._step_fast(self.state, batch, step_rng)
        return self._annotate(metrics)

    def _memory_record(self) -> dict:
        """Live HBM watermarks reconciled against the analytic live-bytes
        model (tracing/memory.py) — {} on backends with no allocator stats
        (the CPU fallback). fit_loop stamps this on every logging record."""
        from glom_tpu.tracing.memory import memory_record

        return memory_record(self._model_live_bytes)

    def fit(
        self,
        data: Iterator[jnp.ndarray],
        num_steps: int,
        *,
        log_every: int = 10,
        prefetch: int = 0,
        trace_capture=None,
    ) -> list[dict]:
        """Run `num_steps` updates pulling batches of the objective's shape
        from `data` ([b, c, H, W] images for GLOM, [b, T] token ids for the
        language model).
        prefetch > 0 stages that many upcoming batches on device from a
        background thread (hides the host->device transfer).

        CAUTION: prefetch wraps `data` PER CALL. Calling fit(prefetch=N)
        repeatedly over one shared iterator (e.g. a checkpoint-span loop)
        discards up to N staged batches at every boundary, skewing the
        stream vs prefetch=0. For that pattern, wrap once yourself with
        data.prefetch_to_device and pass prefetch=0 here — see
        train/cli.py for the reference usage."""
        if prefetch > 0:
            from glom_tpu.data import prefetch_to_device

            data = prefetch_to_device(data, size=prefetch)
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
            interval_account=self._interval_account,
        )
