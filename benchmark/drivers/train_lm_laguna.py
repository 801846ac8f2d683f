"""A training cell of the Laguna language model: the program's one trainer,
built as `train/cli.py:main` builds it from the preset, driven through `fit`
in spans of k steps with data, prefetch and logging running. The structure
is `drivers/train_lm.py`'s, and what that file has that knows no model is
imported from it: the trainer's construction, the token pool, the shapes as
a dict, the routing agreement. What knows the model is here: the seeded
weights (`weights_laguna.py`), the step counters, the program's routing
choices, the reference (`reference/laguna_ref.py`).

`train_col_iters_per_s_per_chip` reads here as in the other language-model
cells: sequences x layers held a second a chip. Tokens a second are logged on
an earlier line.
"""

from __future__ import annotations

import gc
import importlib
import math
import time

import numpy as np

from benchmark import correct as cmp
from benchmark import datagen, harness, reduce_phases, weights_laguna
from benchmark.drivers.train import _adam_mu, _leaf_norms
from benchmark.drivers.train_lm import (
    ADAM_B1,
    build_trainer,
    model_of,
    routing_agreement,
    token_pool,
)
from benchmark.harness import log

COUNTERS = ("moe_pairs_here", "moe_rows_computed", "moe_rows_full_share", "moe_max_expert_load",
            "attn_key_blocks_window", "attn_key_blocks_full")


def program_has_the_family() -> None:
    """A checkout without the Laguna model (this cell's parent commit) stops
    here, at once and before it reaches for the chip."""
    try:
        importlib.import_module("glom_tpu.models.laguna")
    except ImportError as e:
        raise SystemExit(f"benchmark: this checkout cannot run the Laguna cell: {e}")


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
        params = weights_laguna.to_program_params(weights_laguna.weights_from_key(key, model))
        return TrainState(params=params, opt_state=trainer.optimizer.init(params),
                          step=jnp.zeros((), jnp.int32))

    trainer.state = fresh_state(weights_laguna.seed_key(seed))


def log_memory(stage: str) -> None:
    """What the allocator has seen so far, its two peaks apart
    (`harness.memory_peak_bytes` is their sum, whenever each was reached: the
    arrays alive at once, and a running program's scratch, which the TPU's
    allocator counts as reserved), so that a run cut at the memory limit can
    be laid to the step or to set-up."""
    import jax

    st = jax.local_devices()[0].memory_stats() or {}
    log(f"memory after {stage}: " + " ".join(
        f"{k} {int(st.get(k, 0))}" for k in ("bytes_in_use", "peak_bytes_in_use",
                                             "peak_bytes_reserved")))


def first_three_steps(trainer, data, seed: int, model: dict) -> dict:
    """The first three steps, through the window's own call and feed: step 1
    by the logging variant, step 2 by the fast one, step 3 by the logging one
    again, as `drivers/train_lm.py` takes them. The first gradient as the
    optimizer got it is Adam's first moment after one step over 1 - b1. The
    parameters' change is taken against the seeded weights made again inside
    the program that takes the norms, as scratch the compiler reuses: kept as
    a second copy of the weights beside the state they were set-up's peak,
    2.77 GB over the step's (PERF.md section 4, PR 36)."""
    import jax
    import jax.numpy as jnp

    program = {"loss_steps": [0, 2]}
    h1 = trainer.fit(data, num_steps=1, log_every=1)
    mu = weights_laguna.from_program_params(_adam_mu(trainer.state.opt_state))
    program["first_grad_norms"] = _leaf_norms(mu, 1.0 / (1.0 - ADAM_B1))
    program["first_grad"] = {k: np.asarray(v, np.float32) / (1.0 - ADAM_B1)
                             for k, v in jax.device_get(mu).items()}
    del mu
    h3 = trainer.fit(data, num_steps=2, log_every=2)
    program["losses"] = [h1[-1]["loss"], h3[-1]["loss"]]
    program["counters"] = {k: h1[-1].get(k) for k in COUNTERS}

    @jax.jit
    def change_norms(key, params):
        w0 = weights_laguna.weights_from_key(key, model)
        return {k: jnp.sqrt(jnp.sum(jnp.square(v - w0[k])))
                for k, v in weights_laguna.from_program_params(params).items()}

    program["delta_norms"] = {k: float(v) for k, v in jax.device_get(
        change_norms(weights_laguna.seed_key(seed), trainer.state.params)).items()}
    return program


def program_choices(cfg, tcfg, seed: int, model: dict, ids) -> np.ndarray:
    """The experts the program's forward chooses for every token of `ids` in
    every expert layer, from the seeded weights, in the trainer's compute
    type: [E layers, B * T, k]. A pass of the check's own, after the window:
    `laguna.routing_choices` is the step's forward (the same `run_stack`, the
    same kernels) compiled again without recomputation, not the timed step,
    which returns no choices (they would be a [layers, B * T, k] array in
    every record)."""
    import jax
    import jax.numpy as jnp

    from glom_tpu.models import laguna

    dtype = jnp.bfloat16 if tcfg.compute_dtype == "bfloat16" else None

    @jax.jit
    def choose(key, ids):
        params = weights_laguna.to_program_params(weights_laguna.weights_from_key(key, model))
        return laguna.routing_choices(params, ids, cfg, compute_dtype=dtype)

    return np.asarray(choose(weights_laguna.seed_key(seed), jnp.asarray(ids)))


def reference_numbers(cfg, tcfg, seed: int, batches: list, precision: str = "float32") -> dict:
    """The plain reference follows the same three steps from the same
    weights and batches. `precision` below float32 makes it the control that
    `correct` has to fail."""
    from benchmark.reference import laguna_ref

    model = model_of(cfg)
    return laguna_ref.train_reference(
        lambda: weights_laguna.make_weights(seed, model), batches, model,
        lr=tcfg.learning_rate, precision=precision)


def judged_numbers(program: dict, ref: dict) -> dict:
    """`correct.train_numbers`, with one difference. The parameters' change is
    compared where the reference vouches for it (`laguna_ref.change_compared`:
    not where its own first gradient is within Adam's eps; PERF.md trap 11);
    what is left out is logged."""
    from benchmark.reference.laguna_ref import change_compared

    kept = change_compared(ref)
    rms = ref["first_grad_rms"]
    least = min(kept, key=rms.get)
    log("parameters' change not compared (the reference's first gradient, rms, is within "
        f"Adam's eps): { {k: rms[k] for k in ref['delta_norms'] if k not in kept} }; compared "
        f"in {len(kept)} leaves, the smallest such gradient {least} {rms[least]:.6g}")
    return cmp.train_numbers(program, dict(ref, delta_norms=kept))


def judge(verdict, limits: dict, numbers: dict, agreement: float) -> None:
    """Each number beside its limit, and the routing agreement, a floor."""
    limits = dict(limits)
    floor = limits.pop("routing_agreement")
    verdict.numbers(numbers, limits)
    verdict.fact("routing_agreement", round(agreement, 6), f"at least {floor}",
                 agreement >= floor)


def run(cell: dict, args, clock) -> int:
    program_has_the_family()
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
        f"({' '.join(a + m for a, m in cfg.kinds)}, published "
        f"{cfg.layer_offset}-{cfg.layer_offset + layers - 1})")
    log_memory("the trainer was built")
    install_weights(trainer, seed, model)
    log_memory("the weights were installed")
    pool = token_pool(seed, batch, cfg.seq_len, cfg.vocab_size, int(traf["pool_batches"]))
    data = prefetch_to_device(datagen.cycle(pool, seed), size=int(traf["prefetch"]),
                              metrics_writer=writer)
    log("prefetch started")
    program = first_three_steps(trainer, data, seed, model)
    log(f"first three steps done; counters of step 1: {program['counters']}")
    log_memory("the first three steps")
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
    log_memory("the window")
    records = list(writer.records)
    rate = steps * batch * layers / window_s / chips
    log(f"window {window_s:.3f}s steps {steps} step_ms {1e3 * window_s / steps:.3f} "
        f"sequences x layers/s/chip {rate:.3f} tokens/s/chip "
        f"{steps * batch * cfg.seq_len / window_s / chips:.1f} last loss {last_loss:.6f} "
        f"compiles in window {compiles_in_window} (set-up {setup_compiles}) peak {peak} B")
    logged = [r for r in records if r.get("kind") == "train_step"]
    # the rate follows the routing (a layer whose pairs pass the small rung runs
    # the full count): the logging records' series say what it did in this window
    for name in COUNTERS[:4]:
        log(f"window's records, {name}: " + " ".join(f"{r[name]:g}" for r in logged if name in r))
    paths = {r.get("vjp_path") for r in logged}
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
    judge(verdict, cell["limits"], judged_numbers(program, ref),
          routing_agreement(chosen, ref["choices"]))
    log(f"reference took {time.perf_counter() - t_ref:.2f}s")
    cmp.hold_route(verdict, route[0], paths, cfgf["bench"].get("expect_vjp_path"))
    if kernels is not None:
        # The route's kernel names against the configuration's own table:
        # the attention kernels and the compiler's grouped products have to be
        # there, so that a run that fell back to the XLA loop reads incorrect.
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
