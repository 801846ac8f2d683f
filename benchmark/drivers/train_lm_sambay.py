"""A training cell of the SambaY language model: the program's one trainer,
built as `train/cli.py:main` builds it from the preset, driven through `fit`
in spans of k steps with data, prefetch and logging running. The structure
is `drivers/train_lm.py`'s, and what that file has that knows no model is
imported from it: the trainer's construction, the token pool, the shapes as
a dict. What knows the model is here: the seeded weights
(`weights_sambay.py`), the step counters, the reference
(`reference/sambay_ref.py`). There is no routing to agree on.

`train_col_iters_per_s_per_chip` reads here as in the other language-model
cell: sequences x layers held a second a chip. Tokens a second are logged on
an earlier line.
"""

from __future__ import annotations

import gc
import math
import time

import numpy as np

from benchmark import correct as cmp
from benchmark import datagen, harness, reduce_phases, weights_sambay
from benchmark.drivers.train import _adam_mu, _leaf_norms
from benchmark.drivers.train_lm import ADAM_B1, build_trainer, model_of, token_pool
from benchmark.harness import log

COUNTERS = ("attn_key_blocks_window", "attn_key_blocks_full", "scan_chunks")


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
        params = weights_sambay.to_program_params(weights_sambay.weights_from_key(key, model))
        return TrainState(params=params, opt_state=trainer.optimizer.init(params),
                          step=jnp.zeros((), jnp.int32))

    trainer.state = fresh_state(weights_sambay.seed_key(seed))


def first_three_steps(trainer, data, seed: int, model: dict) -> dict:
    """The first three steps, through the window's own call and feed: step 1
    by the logging variant, step 2 by the fast one, step 3 by the logging one
    again, as `drivers/train_lm.py` takes them. The first gradient as the
    optimizer got it is Adam's first moment after one step over 1 - b1."""
    import jax

    program = {"loss_steps": [0, 2]}
    h1 = trainer.fit(data, num_steps=1, log_every=1)
    mu = weights_sambay.from_program_params(_adam_mu(trainer.state.opt_state))
    program["first_grad_norms"] = _leaf_norms(mu, 1.0 / (1.0 - ADAM_B1))
    program["first_grad"] = {k: np.asarray(v, np.float32) / (1.0 - ADAM_B1)
                             for k, v in jax.device_get(mu).items()}
    del mu
    h3 = trainer.fit(data, num_steps=2, log_every=2)
    program["losses"] = [h1[-1]["loss"], h3[-1]["loss"]]
    program["counters"] = {k: h1[-1].get(k) for k in COUNTERS}
    from benchmark.reference.sambay_ref import bias_parts

    w0 = weights_sambay.make_weights(seed, model)
    program["delta_norms"] = _leaf_norms(
        bias_parts(weights_sambay.from_program_params(trainer.state.params), model),
        minus=bias_parts(w0, model))
    return program


def reference_numbers(cfg, tcfg, seed: int, batches: list, precision: str = "float32") -> dict:
    """The plain reference follows the same three steps from the same
    weights and batches. `precision` below float32 makes it the control that
    `correct` has to fail."""
    from benchmark.reference import sambay_ref

    model = model_of(cfg)
    return sambay_ref.train_reference(
        lambda: weights_sambay.make_weights(seed, model), batches, model,
        lr=tcfg.learning_rate, precision=precision)


def judged_numbers(program: dict, ref: dict) -> dict:
    """`correct.train_numbers`, with two differences. The parameters' change
    is compared where the reference vouches for it (`sambay_ref.
    change_compared`: not where its own first gradient is within Adam's eps,
    which at these sizes is the keys' bias of the two layers that have one);
    what is left out is logged, with the smallest gradient that was kept. And
    the gap between the losses is logged and not judged: on the chip it reads
    2e-5 to 1.9e-4 sound, 5e-5 to 2e-4 with the fast variant's update lost or
    the state left unchanged, 1.2e-4 to 5.7e-4 with float8 products (PERF.md
    section 4), so no limit tells a fault from a sound run by it. A lost
    update shows in the parameters' change, a wrong loss in the first
    gradient, one that is not finite in `spans_with_nonfinite_loss`."""
    from benchmark.reference.sambay_ref import change_compared

    kept = change_compared(ref)
    rms = ref["first_grad_rms"]
    least = min(kept, key=rms.get)
    log("parameters' change not compared (the reference's first gradient, rms, is within "
        f"Adam's eps): { {k: rms[k] for k in ref['delta_norms'] if k not in kept} }; compared "
        f"in {len(kept)} parts, the smallest such gradient {least} {rms[least]:.6g}")
    numbers = cmp.train_numbers(program, dict(ref, delta_norms=kept))
    log(f"loss_gap = {numbers.pop('loss_gap'):.6g} (logged, not judged)")
    return numbers


def loss_outside_the_pool(trainer, seed: int, cfg, tcfg, pool_batches: int) -> float:
    """The loss of a batch the trainer has never seen, by one more logging
    step (a step's loss is its forward pass's, before its update). Ids are
    uniform and independent, so nothing that looks only backwards can do
    better than ln(vocabulary) on it: a low loss on the pool's batches beside
    that much here is the pool learnt by heart, a low loss here is a position
    that sees ahead (PERF.md section 7, trap 12)."""
    unseen = token_pool(seed, tcfg.batch_size, cfg.seq_len, cfg.vocab_size, pool_batches + 1)[-1]
    return trainer.fit(iter([unseen]), num_steps=1, log_every=1)[-1]["loss"]


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
        f"({cfg.kinds}, published {cfg.layer_offset}-{cfg.layer_offset + layers - 1})")
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

    unseen = loss_outside_the_pool(trainer, seed, cfg, tcfg, len(pool))
    log(f"loss of a batch outside the pool {unseen:.6f} (ln of the {cfg.vocab_size} rows held: "
        f"{math.log(cfg.vocab_size):.6f}; the window's last, on a batch of the pool: "
        f"{last_loss:.6f})")
    # Free the program's state and programs, then run the reference.
    del data, trainer
    gc.collect()
    jax.clear_caches()
    t_ref = time.perf_counter()
    ref = reference_numbers(cfg, tcfg, seed, pool[:3])
    verdict = cmp.Verdict()
    verdict.numbers(judged_numbers(program, ref), cell["limits"])
    log(f"reference took {time.perf_counter() - t_ref:.2f}s")
    cmp.hold_route(verdict, route[0], paths, cfgf["bench"].get("expect_vjp_path"))
    if kernels is not None:
        # The route's kernel names against the configuration's own table, as
        # `drivers/train_lm.py` holds the other language model's: this step
        # has no custom call at all.
        table = cfgf["bench"]["route_kernels"]
        fits, wrong = cmp.kernels_fit(kernels, table)
        verdict.fact("route_kernels",
                     (" ".join(sorted(kernels)) or "(no custom call in the traced step)")
                     + (f" ({wrong})" if wrong else ""),
                     "none of " + ", ".join(table["forbidden"]), fits)
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
