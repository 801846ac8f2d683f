"""Command-line trainer: `python -m glom_tpu.train.cli --preset cifar10 ...`

The reference has no CLI (configuration is six constructor kwargs and a
README snippet); this is the framework's operational entry point —
presets, distributed meshes, checkpointing/resume, metrics, profiling.
"""

from __future__ import annotations

import argparse
import dataclasses
import sys

import jax


def _nonneg_int(s: str) -> int:
    v = int(s)
    if v < 0:
        raise argparse.ArgumentTypeError(f"must be >= 0, got {v}")
    return v


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="glom-tpu-train",
        description="Train GLOM (self-supervised denoising) or, by an LM preset, "
        "the hybrid language model (next-token loss)",
    )
    p.add_argument("--preset", default="cifar10", help="see glom_tpu.utils.presets")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--batch-size", type=int, default=None)
    p.add_argument("--learning-rate", type=float, default=None)
    p.add_argument(
        "--lr-schedule", choices=["constant", "cosine", "warmup_cosine"],
        default=None,
    )
    p.add_argument("--warmup-steps", type=int, default=None)
    p.add_argument(
        "--schedule-steps", type=int, default=None,
        help="cosine decay horizon (defaults to --steps when a schedule is set)",
    )
    p.add_argument(
        "--grad-accum", type=int, default=None, metavar="A",
        help="split each batch into A microbatches, accumulate grads, one "
        "optimizer update (peak activation memory of one microbatch)",
    )
    p.add_argument(
        "--zero-stage", type=int, choices=[0, 1, 2], default=None,
        help="ZeRO sharded weight update over the 'data' mesh axis: 1 "
        "shards optimizer state (reduce-scatter grads, all-gather params), "
        "2 also shards the grad accumulator; dp=1 resolves to 0 "
        "(docs/PARALLELISM.md, ZeRO section)",
    )
    p.add_argument(
        "--telemetry-level", choices=["off", "scalars", "full"], default=None,
        help="in-graph diagnostics depth (docs/OBSERVABILITY.md): scalars "
        "= grad/update/param norms + NaN/Inf guard inside the jitted step; "
        "full adds per-level consensus agreement (GSPMD/single-device)",
    )
    p.add_argument(
        "--nonfinite-policy", choices=["skip", "warn"], default=None,
        help="what the NaN/Inf guard does (telemetry on): skip drops the "
        "poisoned update in-graph, warn applies it and flags the record",
    )
    p.add_argument(
        "--watchdog-interval", type=float, default=0.0, metavar="SECONDS",
        help="backend-liveness heartbeat: round-trip a scalar through this "
        "process's devices every N seconds, stamping up/down/flapping "
        "transitions into the metrics stream (0 = off)",
    )
    p.add_argument("--seed", type=int, default=None)
    p.add_argument("--data", choices=["shapes", "gaussian"], default="shapes")
    p.add_argument(
        "--data-dir", default=None, metavar="PATH",
        help="train on REAL data: a directory of images (resized to the "
        "config's image_size), a .npy file, or a directory of .npy shards "
        "([N,H,W,C] or [N,C,H,W], uint8 or float). Overrides --data. "
        "Multi-host runs shard the file list by process automatically.",
    )
    p.add_argument(
        "--prefetch", type=_nonneg_int, default=2, metavar="N",
        help="stage N batches on device from a background thread (0 = off)",
    )
    p.add_argument("--metrics-file", default=None, help="JSONL metrics path")
    p.add_argument(
        "--tensorboard", default=None, metavar="DIR",
        help="also mirror scalar metrics to TensorBoard summaries in DIR",
    )
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--checkpoint-every", type=int, default=100)
    p.add_argument(
        "--checkpoint-keep", type=int, default=3, metavar="N",
        help="checkpoint retention (orbax max_to_keep). Pod runs keep "
        "more: the preemption barrier commits the gang MIN step, and a "
        "host past it must still RETAIN it (docs/RESILIENCE.md)",
    )
    p.add_argument("--resume", action="store_true", help="resume from latest ckpt")
    p.add_argument(
        "--pod-index", type=int, default=None, metavar="I",
        help="this process's index in a multi-process pod (0-based); "
        "enables the coordinated preemption barrier + cross-host restore "
        "reconciliation (docs/RESILIENCE.md). Requires --pod-count, "
        "--pod-dir, and a --checkpoint-dir named host_<I> under a shared "
        "pod root",
    )
    p.add_argument(
        "--pod-count", type=int, default=None, metavar="N",
        help="total processes in the pod (>= 2 for coordination)",
    )
    p.add_argument(
        "--pod-dir", default=None, metavar="DIR",
        help="shared coordination directory for the pod rendezvous "
        "(barrier messages + the pod commit marker)",
    )
    p.add_argument(
        "--supervise", type=_nonneg_int, default=None, metavar="RESTARTS",
        help="run under the fit_supervised restart loop (docs/RESILIENCE.md): "
        "on an unhandled training exception, restore the latest VALID "
        "checkpoint and retry with bounded exponential backoff, up to "
        "RESTARTS restarts; every decision is a stamped 'recovery' event. "
        "Requires --checkpoint-dir; implies --resume semantics.",
    )
    p.add_argument(
        "--preempt-deadline", type=float, default=30.0, metavar="SECONDS",
        help="SIGTERM (preemption) grace budget: with --flight-recorder and "
        "--checkpoint-dir, the SIGTERM hook saves a checkpoint bounded by "
        "this deadline before dumping the flight ring (docs/RESILIENCE.md)",
    )
    p.add_argument(
        "--profile-dir", default=None,
        help="capture an XProf trace of the WHOLE run (for step-windowed "
        "capture use --trace-steps)",
    )
    p.add_argument(
        "--trace-steps", default=None, metavar="A:B",
        help="programmatic XLA capture: open jax.profiler.start_trace "
        "right before global step A and close it after step B (inclusive; "
        "a bare 'A' captures one step). Window metadata is stamped into "
        "the metrics stream; view with tensorboard --logdir <trace dir>",
    )
    p.add_argument(
        "--trace-dir", default="/tmp/glom_tpu_trace", metavar="DIR",
        help="where --trace-steps writes the XProf trace",
    )
    p.add_argument(
        "--flight-recorder", default=None, metavar="DIR",
        help="crash flight recorder: keep a ring of the last "
        "--flight-events telemetry events and dump flight_<ts>.jsonl into "
        "DIR on backend-down, anomaly storm, SIGTERM/exit, or an "
        "unhandled training-loop exception (docs/OBSERVABILITY.md)",
    )
    p.add_argument(
        "--flight-events", type=int, default=256, metavar="N",
        help="flight-recorder ring capacity (default 256)",
    )
    p.add_argument(
        "--distributed",
        action="store_true",
        help="use the preset's mesh (scaled to available devices) + SP strategy",
    )
    p.add_argument(
        "--check-parity",
        action="store_true",
        help="run the sharded and single-device trainers side by side and "
        "compare losses (the sanity mode for new meshes)",
    )
    p.add_argument("--debug-nans", action="store_true")
    return p


def main(argv=None) -> int:
    from glom_tpu.utils.startup import enable_compile_cache

    enable_compile_cache()
    args = build_parser().parse_args(argv)

    if args.debug_nans:
        jax.config.update("jax_debug_nans", True)

    from glom_tpu.utils.metrics import MetricsWriter
    from glom_tpu.utils.presets import get_preset

    preset = get_preset(args.preset)
    tcfg = preset.train
    overrides = {}
    if args.batch_size is not None:
        overrides["batch_size"] = args.batch_size
    if args.learning_rate is not None:
        overrides["learning_rate"] = args.learning_rate
    if args.lr_schedule is not None:
        overrides["lr_schedule"] = args.lr_schedule
        overrides["schedule_steps"] = (
            args.schedule_steps if args.schedule_steps is not None else args.steps
        )
    elif args.schedule_steps is not None or args.warmup_steps is not None:
        # Fail loudly instead of silently training at a constant LR.
        raise SystemExit(
            "--schedule-steps/--warmup-steps require --lr-schedule "
            "(the preset's default schedule is 'constant')"
        )
    if args.warmup_steps is not None:
        overrides["warmup_steps"] = args.warmup_steps
    if args.grad_accum is not None:
        overrides["grad_accum"] = args.grad_accum
    if args.zero_stage is not None:
        overrides["zero_stage"] = args.zero_stage
    if args.telemetry_level is not None:
        overrides["telemetry_level"] = args.telemetry_level
    if args.nonfinite_policy is not None:
        overrides["nonfinite_policy"] = args.nonfinite_policy
    if args.seed is not None:
        overrides["seed"] = args.seed
    if overrides:
        tcfg = dataclasses.replace(tcfg, **overrides)
    cfg = preset.model

    writer = MetricsWriter(
        args.metrics_file, echo=True, tensorboard_dir=args.tensorboard
    )

    # Backend-liveness heartbeat: transitions (up/down/flapping) land in
    # the SAME stream as the training records, and every record stamps the
    # current state via the global registration.
    # Crash flight recorder FIRST: even a setup failure (bad --data-dir,
    # preset error) then leaves a postmortem trail of whatever telemetry
    # preceded it. The atexit/SIGTERM hooks stay installed for the process
    # lifetime (dump() is a no-op when nothing new arrived); the GLOBAL
    # registration is cleared on the way out so in-process callers (tests,
    # CI) don't keep feeding a dead run's buffer.
    fr = None
    if args.flight_recorder:
        from glom_tpu.tracing.flight import (
            FlightRecorder,
            set_global_flight_recorder,
        )

        fr = FlightRecorder(args.flight_recorder, capacity=args.flight_events)
        fr.install_process_hooks()
        set_global_flight_recorder(fr)

    wd = None
    if args.watchdog_interval > 0:
        from glom_tpu.telemetry.watchdog import (
            BackendWatchdog,
            set_global_watchdog,
        )

        wd = BackendWatchdog(
            interval_s=args.watchdog_interval, writer=writer
        )
        set_global_watchdog(wd)
        wd.start()
    # EVERYTHING past the heartbeat start runs under its try/finally: a
    # setup failure (bad --data-dir, preset error, trainer build) must not
    # leak a probing daemon thread into in-process callers (tests, CI).
    try:
        return _train_body(args, preset, cfg, tcfg, writer)
    finally:
        if wd is not None:
            wd.stop()
            # Unregister too: a stopped watchdog's last probed state would
            # otherwise stay frozen on every later record an in-process
            # caller (tests, CI) writes in this process.
            set_global_watchdog(None)
        if fr is not None:
            # Final dump before unregistering: the in-process caller path
            # never reaches the atexit hook with the buffer still global.
            fr.dump("run-end")
            from glom_tpu.tracing.flight import set_global_flight_recorder

            set_global_flight_recorder(None)


def _pod_setup(args, writer):
    """(PodCoordinator, peer host dirs) for a pod run; (None, None) for
    the single-host path. Partial pod flags fail loudly — a pod member
    that silently fell back to single-host preemption is exactly the
    inconsistent-resume hazard the coordinator exists to close."""
    pod_args = (args.pod_index, args.pod_count, args.pod_dir)
    if all(a is None for a in pod_args):
        return None, None
    if any(a is None for a in pod_args):
        raise SystemExit(
            "--pod-index/--pod-count/--pod-dir come together (pod "
            "coordination, docs/RESILIENCE.md)"
        )
    if args.pod_count < 2:
        raise SystemExit("--pod-count must be >= 2 (one host is the "
                         "single-host path; drop the pod flags)")
    if not args.checkpoint_dir:
        raise SystemExit("pod coordination requires --checkpoint-dir "
                         "(the pod root's host_<i> dir)")
    from glom_tpu.resilience.coordinator import (
        DirectoryTransport,
        PodCoordinator,
        peer_host_dirs,
    )

    try:
        peers = peer_host_dirs(
            args.checkpoint_dir, args.pod_index, args.pod_count
        )
    except ValueError as e:
        raise SystemExit(str(e)) from None
    transport = DirectoryTransport(
        args.pod_dir, args.pod_index, args.pod_count
    )
    return PodCoordinator(transport, writer=writer), peers


def _train_body(args, preset, cfg, tcfg, writer) -> int:
    from glom_tpu.data import gaussian_dataset, shapes_dataset
    from glom_tpu.train import Trainer, objective_for
    from glom_tpu.utils.config import GlomConfig

    # What the feed yields a row of: [c, H, W] images or [T] token ids.
    example_size = objective_for(cfg, tcfg).batch_shape[-1]
    if not isinstance(cfg, GlomConfig):
        # The language-model family: token ids, one chip (what it holds of a
        # layer is the config's; the sharded trainers are GLOM's).
        if args.distributed or args.check_parity or args.data_dir is not None:
            raise SystemExit(
                f"--preset {preset.name} trains token ids on one chip: "
                "--distributed, --check-parity and --data-dir are GLOM's"
            )
        from glom_tpu.data import token_dataset

        def make_data(batch_size, seq_len, seed=0):
            return token_dataset(batch_size, seq_len, cfg.vocab_size, seed=seed)

    elif args.data_dir is not None:
        from glom_tpu.data import file_dataset

        def make_data(batch_size, image_size, seed=0):
            return file_dataset(
                args.data_dir, batch_size, image_size, seed=seed,
                shard_index=jax.process_index(), num_shards=jax.process_count(),
            )
    else:
        make_data = shapes_dataset if args.data == "shapes" else gaussian_dataset

    pod_coord, pod_peers = _pod_setup(args, writer)

    if args.supervise is not None:
        # The restart loop owns trainer/data/checkpoint lifecycle per
        # attempt (factories: a crashed attempt's state never leaks).
        from glom_tpu.train.supervise import TrainSupervisor, fit_supervised

        if not args.checkpoint_dir:
            raise SystemExit("--supervise requires --checkpoint-dir (the "
                             "restart loop resumes from checkpoints)")
        if args.check_parity or args.profile_dir or args.trace_steps:
            raise SystemExit(
                "--supervise does not compose with --check-parity/"
                "--profile-dir/--trace-steps (one concern per run)"
            )
        if args.prefetch > 0:
            print(
                "note: --prefetch is ignored under --supervise (the data "
                "stream is rebuilt per attempt)", file=sys.stderr,
            )

        def make_trainer():
            if args.distributed:
                from glom_tpu.parallel import DistributedTrainer

                scaled = preset.scaled_to(len(jax.devices()))
                return DistributedTrainer(
                    cfg, tcfg, scaled.mesh,
                    sp_strategy=scaled.sp_strategy, metrics_writer=writer,
                )
            return Trainer(cfg, tcfg, metrics_writer=writer)

        fit_supervised(
            make_trainer,
            lambda: make_data(tcfg.batch_size, example_size, seed=tcfg.seed),
            args.steps,
            checkpoint_dir=args.checkpoint_dir,
            checkpoint_every=args.checkpoint_every,
            log_every=args.log_every,
            supervisor=TrainSupervisor(max_restarts=args.supervise, writer=writer),
            metrics_writer=writer,
            max_to_keep=args.checkpoint_keep,
            preemption_deadline_s=args.preempt_deadline,
            gang=pod_coord,
            pod_peers=pod_peers,
        )
        return 0

    data = make_data(tcfg.batch_size, example_size, seed=tcfg.seed)

    if args.check_parity:
        from glom_tpu.parallel import DistributedTrainer

        scaled = preset.scaled_to(len(jax.devices()))
        single = Trainer(cfg, tcfg)
        dist = DistributedTrainer(
            cfg, tcfg, scaled.mesh, sp_strategy=scaled.sp_strategy
        )
        d1 = make_data(tcfg.batch_size, example_size, seed=tcfg.seed)
        d2 = make_data(tcfg.batch_size, example_size, seed=tcfg.seed)
        h1 = single.fit(d1, num_steps=args.steps, log_every=args.log_every)
        h2 = dist.fit(d2, num_steps=args.steps, log_every=args.log_every)
        worst = max(
            abs(a["loss"] - b["loss"]) / max(abs(a["loss"]), 1e-9)
            for a, b in zip(h1, h2)
        )
        print(f"parity: worst relative loss deviation = {worst:.2e}")
        return 0 if worst < 1e-2 else 1

    if args.distributed:
        from glom_tpu.parallel import DistributedTrainer

        scaled = preset.scaled_to(len(jax.devices()))
        print(
            f"mesh {scaled.mesh.shape} (axes data/seq/model), "
            f"sp={scaled.sp_strategy}",
            file=sys.stderr,
        )
        trainer = DistributedTrainer(
            cfg,
            tcfg,
            scaled.mesh,
            sp_strategy=scaled.sp_strategy,
            metrics_writer=writer,
        )
    else:
        trainer = Trainer(cfg, tcfg, metrics_writer=writer)

    ckpt = None
    start_step = 0
    if args.checkpoint_dir:
        from glom_tpu.utils.checkpoint import CheckpointManager, abstract_like

        ckpt = CheckpointManager(
            args.checkpoint_dir,
            metrics_writer=writer,
            max_to_keep=args.checkpoint_keep,
            pod_peers=pod_peers,
        )
        if args.resume and ckpt.latest_step() is not None:
            start_step, trainer.state = ckpt.restore(
                abstract_state=abstract_like(trainer.state)
            )
            # The resume IS a recovery action — stamped into the same
            # stream as everything else, so a kill-and-resume run's
            # evidence trail reconciles without parsing stderr.
            from glom_tpu.telemetry import schema

            writer.write(
                schema.stamp(
                    {"action": "resume-from-checkpoint", "step": int(start_step)},
                    kind="recovery",
                )
            )
            print(f"resumed from step {start_step}", file=sys.stderr)
        from glom_tpu.tracing.flight import get_global_flight_recorder

        fr_live = get_global_flight_recorder()
        if fr_live is not None:
            # Preemption grace path: SIGTERM saves the live state bounded
            # by --preempt-deadline, then dumps the flight ring. In pod
            # mode the save rides the two-phase barrier instead — every
            # host commits ONE common step or the round aborts loudly.
            if pod_coord is not None:

                def _preempt_save(trainer=trainer, start=start_step):
                    from glom_tpu.resilience.coordinator import (
                        pod_preemption_save,
                    )

                    return pod_preemption_save(
                        pod_coord, args.checkpoint_dir, trainer.state,
                        int(trainer.state.step),
                        # The barrier budget sits INSIDE the hook's join
                        # deadline so an abort stamps before the dump
                        # gives up on the hook thread.
                        deadline_s=args.preempt_deadline * 0.8,
                        round_id=f"preempt-g{int(start)}",
                        metrics_writer=writer,
                    )

            else:

                def _preempt_save(trainer=trainer):
                    from glom_tpu.utils.checkpoint import preemption_save

                    return preemption_save(
                        args.checkpoint_dir, trainer.state,
                        int(trainer.state.step), metrics_writer=writer,
                    )

            fr_live.set_checkpoint_hook(
                _preempt_save, deadline_s=args.preempt_deadline
            )

    if args.prefetch > 0:
        # Wrap ONCE, outside the checkpoint-span loop: a per-span wrap over
        # the shared iterator would discard its staged batches at every
        # span boundary (skewing the data stream vs a --prefetch 0 run)
        # and race the dying worker against the next span's on the same
        # generator. Negative values fail here, at the call site.
        from glom_tpu.data import prefetch_to_device

        data = prefetch_to_device(
            data,
            size=args.prefetch,
            sharding=getattr(trainer, "batch_sharding", None),
            metrics_writer=writer,
        )

    # Step-windowed XLA capture: ONE TraceCapture across every checkpoint
    # span (its step counter is global to the run), closed in the finally
    # so a crash or a window past --steps can't leak a profiler session.
    cap = None
    if args.trace_steps and args.profile_dir:
        # jax allows one active trace: the step window opening inside the
        # whole-run trace would RuntimeError mid-training — reject up
        # front instead.
        raise SystemExit(
            "--profile-dir (whole-run trace) and --trace-steps (step "
            "window) are mutually exclusive — jax runs one profile at a "
            "time; pick one"
        )
    if args.trace_steps:
        from glom_tpu.tracing.capture import TraceCapture

        cap = TraceCapture.parse(
            args.trace_steps, args.trace_dir, writer=writer
        )

    def run(steps):
        remaining = steps - start_step
        if remaining <= 0:
            print("nothing to do (already past --steps)", file=sys.stderr)
            return
        done = 0
        while done < remaining:
            span = min(args.checkpoint_every, remaining - done) if ckpt else remaining
            trainer.fit(
                data, num_steps=span, log_every=args.log_every,
                trace_capture=cap,
            )
            done += span
            if ckpt:
                ckpt.save(start_step + done, trainer.state)
        if ckpt:
            ckpt.wait()

    try:
        if args.profile_dir:
            from glom_tpu.utils.profiling import trace

            with trace(args.profile_dir):
                run(args.steps)
        else:
            run(args.steps)
    finally:
        if cap is not None:
            cap.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
