"""ZeRO A/B bench: step time of the sharded weight update vs the replicated
baseline on a data-parallel mesh, plus the quantized-reduce arm.

Four arms, one JSON line each (the queue's pricing rows):
  zero0        — replicated optimizer state, monolithic grad allreduce
  zero1        — reduce-scatter grads -> owned-shard update -> param all-gather
  zero2_accum  — stage 2 with grad_accum=2 (the sharded-accumulator case; it
                 differs from stage 1 only under accumulation)
  zero1_quant  — stage 1 with the EQuARX-style int8 block-scaled reduce
                 emulation (prices the quant/dequant compute and stamps the
                 in-graph quantization-error probe; the wire saving itself
                 needs the real XLA collective hook). Stage 1, not 0: the
                 manual path's quantization hook lives on the explicit
                 reduce-scatter — stage 0's transpose-psum has no hook and
                 resolves the flag off (loudly), so a stage-0 quant arm
                 would measure nothing.

Every arm runs telemetry_level="scalars", so each row carries the MEASURED
collective wire bytes of the schedule it ran next to the modeled ones, and
the measured-vs-modeled drift (telemetry/counters.py).

Every line carries the static observability record the trainers stamp
(zero_stage, per-replica live bytes, per-step comm-volume model), so the
memory/comm claims in docs/PARALLELISM.md are re-derived on every run.

Topology: dp = all visible devices, at least 2 — a four-chip host prices
the A/B for real. Under an explicit JAX_PLATFORMS=cpu the caller provides
the replicas (XLA_FLAGS=--xla_force_host_platform_device_count=8, as CI
does): real collectives, meaningless absolute times, but the RATIO and the
analytics are load-bearing and CI asserts them; those rows are labelled
"(cpu-fallback)" and carry no MFU. With fewer than 2 devices the script
exits 1.

Timing: whole Python-loop steps with a terminal block_until_ready, min over
repeats. Both arms pay identical per-step dispatch, so the A/B ratio is
honest (the absolute numbers are bench.py's chained-loop methodology's).
"""

import time


def _time_steps(trainer, batch, k: int, repeats: int) -> float:
    import jax

    trainer.step_fast(batch)  # compile + first-touch
    jax.block_until_ready(trainer.state)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(k):
            trainer.step_fast(batch)
        jax.block_until_ready(trainer.state)
        best = min(best, (time.perf_counter() - t0) / k)
    return best


def main() -> None:
    from glom_tpu.telemetry.sinks import bench_bootstrap

    if not bench_bootstrap("zero_ab train_step column_iters_per_sec_per_chip"):
        raise SystemExit(1)
    import dataclasses

    import jax

    from glom_tpu.data import gaussian_dataset
    from glom_tpu.parallel import DistributedTrainer
    from glom_tpu.telemetry.sinks import emit
    from glom_tpu.utils.config import GlomConfig, MeshConfig, TrainConfig
    from glom_tpu.utils.metrics import detect_chip, mfu

    chip = detect_chip()
    on_tpu = chip != "cpu"
    dp = len(jax.devices())
    if dp < 2:
        raise SystemExit(f"bench_zero.py needs >= 2 devices, {dp} visible")
    if on_tpu:
        # Flagship BASELINE config 4 at its declared dp topology.
        # telemetry_level="scalars" on every arm: the records must carry
        # the MEASURED collective bytes + model drift (the uniform in-graph
        # cost rides all four arms identically, so the A/B ratio is clean).
        cfg = GlomConfig(dim=512, levels=6, image_size=224, patch_size=14)
        per_replica_batch, k, repeats = 4, 8, 3
        base = TrainConfig(
            batch_size=per_replica_batch * dp,
            learning_rate=1e-3,
            compute_dtype="bfloat16",
            use_pallas=True,  # manual shard_map path: explicit psum_scatter
            telemetry_level="scalars",
        )
    else:
        cfg = GlomConfig(dim=64, levels=4, image_size=16, patch_size=4)
        per_replica_batch, k, repeats = 2, 4, 2
        base = TrainConfig(
            batch_size=per_replica_batch * dp, learning_rate=1e-3,
            use_pallas=True, telemetry_level="scalars",
        )
        emit(
            {
                "note": "JAX_PLATFORMS=cpu functional drive: ZeRO A/B on "
                f"the caller's {dp}-device CPU mesh (cpu-fallback) — "
                "ratios and live-bytes/comm analytics are the signal, "
                "not absolute times"
            },
            kind="note",
        )

    arms = [
        ("zero0", dict(zero_stage=0)),
        ("zero1", dict(zero_stage=1)),
        ("zero2_accum", dict(zero_stage=2, grad_accum=2)),
        ("zero1_quant", dict(zero_stage=1, quantized_reduce=True)),
    ]
    times = {}
    for name, overrides in arms:
        tcfg = dataclasses.replace(base, **overrides)
        trainer = DistributedTrainer(cfg, tcfg, MeshConfig(data=dp))
        batch = next(gaussian_dataset(tcfg.batch_size, cfg.image_size, seed=0))
        per_step = _time_steps(trainer, batch, k, repeats)
        times[name] = per_step
        iters = cfg.default_iters
        col_per_sec = tcfg.batch_size * iters / per_step / dp
        label = f"dp={dp}, {chip}" if on_tpu else f"dp={dp}, cpu-fallback"
        mfu_field = (
            {"mfu": round(mfu(cfg, col_per_sec, chip=chip, backward=True), 4)}
            if on_tpu
            else {}
        )
        emit(
            {
                "metric": f"zero_ab {name} train_step "
                f"column_iters_per_sec_per_chip ({label})",
                "value": round(col_per_sec, 2),
                "unit": "column-iters/s/chip",
                "step_time_s": round(per_step, 5),
                "vs_zero0": round(times["zero0"] / per_step, 4),
                **mfu_field,
                **trainer._static_record,
            }
        )


if __name__ == "__main__":
    main()
