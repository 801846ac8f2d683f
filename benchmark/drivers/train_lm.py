"""A training cell of the hybrid language model: the program's one trainer,
built as `train/cli.py:main` builds it from the preset, driven through `fit`
in spans of k steps with data, prefetch and logging running. The structure
is `drivers/train.py`'s.

Set-up builds ONE trainer, installs the seeded weights the way a checkpoint
resume installs a state, drives it through its first three steps by the
window's own call and feed (`fit` over the prefetched pool), warms up, and
hands that same trainer to the window. After the window the trainer is
freed, the program's forward gives its routing choices on the first batch
from the same seeded weights, and the plain reference
(`reference/nemotron_h_ref.py`) follows the same three steps.

`train_col_iters_per_s_per_chip` reads here as sequences x layers held a
second a chip: a sequence's positions are its columns, a layer is one update
of all of them. Tokens a second are logged on an earlier line.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import time

import numpy as np

from benchmark import correct as cmp
from benchmark import datagen, harness, reduce_phases, weights_lm
from benchmark.drivers.train import _adam_mu, _leaf_norms
from benchmark.harness import log

ADAM_B1 = 0.9


def build_trainer(cell: dict, seed: int, writer):
    """The Trainer exactly as `train/cli.py:main` makes it from the preset;
    the configuration file's values and the traffic's sequence length are
    laid over the preset's, so the files are what runs."""
    from glom_tpu.train import Trainer
    from glom_tpu.utils.presets import get_preset

    cfgf, traf = cell["config_file"], cell["traffic_file"]
    preset = get_preset(cfgf["preset"])
    cfg = dataclasses.replace(preset.model, **{**cfgf["model"], "seq_len": int(traf["seq_len"])})
    train = dict(cfgf["train"])
    batch = int(train.pop("batch_per_chip")) * cell["chips"]
    tcfg = dataclasses.replace(preset.train, **train, batch_size=batch, seed=int(seed))
    return Trainer(cfg, tcfg, metrics_writer=writer), cfg, tcfg


def model_of(cfg) -> dict:
    """The shapes as the benchmark's own code reads them: a plain dict."""
    return dataclasses.asdict(cfg)


def install_weights(trainer, seed: int, model: dict) -> None:
    """Put the benchmark's weights in the trainer's state, with a fresh
    optimizer state and step 0: what the CLI's checkpoint resume does. The
    state the trainer was built with is dropped first; two do not fit."""
    import jax
    import jax.numpy as jnp

    from glom_tpu.train.trainer import TrainState

    trainer.state = None

    @jax.jit
    def fresh_state(key):
        params = weights_lm.to_program_params(weights_lm.weights_from_key(key, model))
        return TrainState(params=params, opt_state=trainer.optimizer.init(params),
                          step=jnp.zeros((), jnp.int32))

    trainer.state = fresh_state(weights_lm.seed_key(seed))


def token_pool(seed: int, batch: int, seq_len: int, vocab: int, n_batches: int) -> list:
    """`n_batches` distinct [batch, seq_len] int32 batches of token ids,
    uniform over the vocabulary rows held, as host arrays (the trainer's
    feed uploads every step's batch, as a file reader's would)."""
    rng = np.random.default_rng([int(seed), 0x746F6B])
    return [rng.integers(0, vocab, (batch, seq_len), dtype=np.int32)
            for _ in range(n_batches)]


def first_three_steps(trainer, data, seed: int, model: dict) -> dict:
    """The first three steps, through the window's own call and feed. Step 1
    runs the logging variant, step 2 the fast variant the window mostly runs,
    step 3 the logging variant again (fit logs a span's last step), so the
    losses of steps 1 and 3 are what the program reports. The first gradient
    as the optimizer got it is Adam's first moment after one step over
    1 - b1."""
    import jax

    program = {"loss_steps": [0, 2]}
    h1 = trainer.fit(data, num_steps=1, log_every=1)
    mu = weights_lm.from_program_params(_adam_mu(trainer.state.opt_state))
    program["first_grad_norms"] = _leaf_norms(mu, 1.0 / (1.0 - ADAM_B1))
    program["first_grad"] = {k: np.asarray(v, np.float32) / (1.0 - ADAM_B1)
                             for k, v in jax.device_get(mu).items()}
    del mu
    h3 = trainer.fit(data, num_steps=2, log_every=2)
    program["losses"] = [h1[-1]["loss"], h3[-1]["loss"]]
    program["counters"] = {k: h1[-1].get(k) for k in
                           ("moe_pairs_here", "moe_rows_computed", "moe_max_expert_load")}
    w0 = weights_lm.make_weights(seed, model)
    program["delta_norms"] = _leaf_norms(
        weights_lm.from_program_params(trainer.state.params), minus=w0)
    return program


def program_choices(cfg, tcfg, seed: int, model: dict, ids) -> np.ndarray:
    """The experts the program's forward chooses for every token of `ids` in
    every expert layer, from the seeded weights, in the trainer's compute
    type: [E layers, B * T, k]. (The step itself returns no choices: they
    would be a [layers, T, k] array in every record.)"""
    import jax
    import jax.numpy as jnp

    from glom_tpu.models import hybrid_lm

    dtype = jnp.bfloat16 if tcfg.compute_dtype == "bfloat16" else None

    @jax.jit
    def choose(key, ids):
        params = weights_lm.to_program_params(weights_lm.weights_from_key(key, model))
        return hybrid_lm.routing_choices(params, ids, cfg, compute_dtype=dtype)

    return np.asarray(choose(weights_lm.seed_key(seed), jnp.asarray(ids)))


def routing_agreement(program: np.ndarray, reference: np.ndarray) -> float:
    """Share of the program's choices (k a token a layer) that the
    reference makes too."""
    ref = reference.reshape(program.shape)
    return float((program[..., :, None] == ref[..., None, :]).any(axis=-1).mean())


def reference_numbers(cfg, tcfg, seed: int, batches: list,
                      precision: str = "float32", first_choices=None) -> dict:
    """The plain reference follows the same three steps from the same
    weights and batches. `precision` below float32 makes it the control that
    `correct` has to fail."""
    from benchmark.reference import nemotron_h_ref

    model = model_of(cfg)
    return nemotron_h_ref.train_reference(
        lambda: weights_lm.make_weights(seed, model), batches, model,
        lr=tcfg.learning_rate, precision=precision, first_choices=first_choices)


def judge(verdict, cell: dict, program: dict, ref: dict, agreement: float) -> None:
    """The numbers `drivers/train.py` compares, each beside its limit, and
    the routing agreement, which has a floor."""
    limits = dict(cell["limits"])
    floor = limits.pop("routing_agreement")
    verdict.numbers(cmp.train_numbers(program, ref), limits)
    verdict.fact("routing_agreement", round(agreement, 6), f"at least {floor}",
                 agreement >= floor)


def run(cell: dict, args, clock) -> int:
    import jax

    cfgf, traf = cell["config_file"], cell["traffic_file"]
    chips, seed = cell["chips"], int(args.seed)
    dev = harness.start_jax(chips)
    counter = harness.CompileCounter()

    from glom_tpu.data import prefetch_to_device

    writer = harness.Collector()
    trainer, cfg, tcfg = build_trainer(cell, seed, writer)
    model = model_of(cfg)
    batch, k = tcfg.batch_size, int(cfgf["bench"]["span_steps"])
    layers = cfg.num_hidden_layers
    log(f"route vjp_path={trainer.vjp_path} grad_accum={trainer.grad_accum} "
        f"batch={batch} seq_len={cfg.seq_len} span_steps={k} layers held={layers} "
        f"({cfg.pattern})")
    log("trainer built")
    install_weights(trainer, seed, model)
    log("weights installed")
    pool = token_pool(seed, batch, cfg.seq_len, cfg.vocab_size, int(traf["pool_batches"]))
    data = prefetch_to_device(datagen.cycle(pool, seed), size=int(traf["prefetch"]),
                              metrics_writer=writer)
    log("prefetch started")
    program = first_three_steps(trainer, data, seed, model)
    log(f"first three steps done; counters of step 1: {program['counters']}")
    # Warm-up: one whole span, so that both variants and the prefetch
    # queue are where the window will find them.
    trainer.fit(data, num_steps=k, log_every=k)
    setup_compiles = counter.n
    writer.records.clear()

    cap = trace_dir = None
    if args.trace:
        trace_dir = harness.fresh_trace_dir(cell["name"])
        cap = harness.StepWindow(k, 3 * k - 1, trace_dir)  # spans 2 and 3, whole
    setup_s = clock.since_start()
    t0 = time.perf_counter()
    steps, bad_spans, last_loss = 0, 0, float("nan")
    try:
        while True:
            hist = trainer.fit(data, num_steps=k, log_every=k, trace_capture=cap)
            steps += k
            last_loss = hist[-1]["loss"]
            bad_spans += 0 if math.isfinite(last_loss) else 1
            t1 = time.perf_counter()
            if cap is not None:
                cap.stop_if_due()
            if t1 - t0 >= args.seconds:
                break
    finally:
        if cap is not None:
            cap.close()
    window_s = t1 - t0
    compiles_in_window = counter.n - setup_compiles
    peak = harness.memory_peak_bytes(chips)
    records = list(writer.records)
    rate = steps * batch * layers / window_s / chips
    log(f"window {window_s:.3f}s steps {steps} step_ms {1e3 * window_s / steps:.3f} "
        f"sequences x layers/s/chip {rate:.3f} tokens/s/chip "
        f"{steps * batch * cfg.seq_len / window_s / chips:.1f} last loss {last_loss:.6f} "
        f"compiles in window {compiles_in_window} (set-up {setup_compiles}) peak {peak} B")
    paths = {r.get("vjp_path") for r in records if r.get("kind") == "train_step"}
    route = (trainer.vjp_path, trainer.grad_accum)
    kernels = None
    if cap is not None:
        xplane = harness.find_xplane(trace_dir)
        phases = reduce_phases.load(xplane, chips) if xplane else None
        kernels = phases["step"]["by_kernel"] if phases and phases.get("step") else {}

    # Free the program's state and programs, then its choices and the reference.
    del data, trainer
    gc.collect()
    jax.clear_caches()
    t_ref = time.perf_counter()
    chosen = program_choices(cfg, tcfg, seed, model, pool[0])
    log(f"the program's routing choices took {time.perf_counter() - t_ref:.2f}s")
    ref = reference_numbers(cfg, tcfg, seed, pool[:3])
    verdict = cmp.Verdict()
    judge(verdict, cell, program, ref, routing_agreement(chosen, ref["choices"]))
    log(f"reference took {time.perf_counter() - t_ref:.2f}s")
    cmp.hold_route(verdict, route[0], paths, cfgf["bench"].get("expect_vjp_path"))
    if kernels is not None:
        # The route's kernel names, as `correct.hold_route` holds GLOM's to
        # `routes/<route>.json`; this route's table is the configuration's
        # (`benchmark/tests/test_route.py` keeps `routes/` to the routes of
        # `models/core.py` and to Pallas kernels' names).
        table = cfgf["bench"]["route_kernels"]
        fits, wrong = cmp.kernels_fit(kernels, table)
        verdict.fact("route_kernels",
                     (" ".join(sorted(kernels)) or "(no custom call in the traced step)")
                     + (f" ({wrong})" if wrong else ""),
                     "some " + ", ".join(table["required"]) + "; none of "
                     + ", ".join(table["forbidden"]), fits)
    verdict.number("spans_with_nonfinite_loss", bad_spans, 0)

    return harness.report(
        cell, args, verdict=verdict, attempted=steps, failed=bad_spans * k,
        end_to_end={
            "train_col_iters_per_s_per_chip": {"value": rate, "unit": "col-iters/s/chip"},
            "setup_s": {"value": setup_s, "unit": "s"}},
        device=dict(dev, memory_peak_bytes=peak),
        ctx={"kind": "train", "records": records, "steps": steps,
             "window_s": window_s, "compiles_in_window": compiles_in_window,
             "peak_bytes": peak, "model": model, "batch": batch, "chips": chips,
             "seq_len": cfg.seq_len, "device_kind": dev["kind"], "route": route,
             "steps_traced": cap.steps_traced if cap else 0},
        trace_dir=trace_dir)
