"""A training cell of the Ouro looped language model: the program's one
trainer, built as `train/cli.py:main` builds it from the preset, driven
through `fit` in spans of k steps with data, prefetch and logging running.
The structure is `drivers/train_lm_evabyte.py`'s without a check of the
attention alone (plain causal attention is the older cells'), and what the
older drivers have that knows no model is imported from them: the trainer's
construction, the token pool, the shapes as a dict, the memory lines. What
knows the model is here: the seeded weights (`weights_ouro.py`), the step
counters, the reference (`reference/ouro_ref.py`), and two requirements held
by the step's own counts on untraced runs too: every layer application's
attention ran in the kernels and its recomputation read their kept output
(`attn_forward_kept` = layers x passes, `bench.attention_on_kernels`), and the
stack ran `total_ut_steps` times (`ut_steps`).

`train_col_iters_per_s_per_chip` counts rows x layers held x passes a second
a chip: a layer application is one update of all of a row's positions, as a
GLOM iteration is (64 a step at two rows, 8 layers, 4 passes). Tokens a
second are logged on an earlier line.
"""

from __future__ import annotations

import gc
import importlib
import math
import time

import numpy as np

from benchmark import correct as cmp
from benchmark import datagen, harness, reduce_phases, weights_ouro
from benchmark.drivers.train import _adam_mu, _leaf_norms
from benchmark.drivers.train_lm import ADAM_B1, build_trainer, model_of, token_pool
from benchmark.drivers.train_lm_laguna import log_memory
from benchmark.harness import log

COUNTERS = ("ut_steps", "layer_applications", "attn_forward_kept", "attn_key_blocks_full",
            "exit_entropy", "exit_mass_last", "swiglu_backward_staged")


def program_has_the_family() -> None:
    """A checkout without the Ouro model (this cell's parent commit) stops
    here, at once and before it reaches for the chip."""
    try:
        importlib.import_module("glom_tpu.models.ouro")
    except ImportError as e:
        raise SystemExit(f"benchmark: this checkout cannot run the Ouro cell: {e}")


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
        params = weights_ouro.to_program_params(weights_ouro.weights_from_key(key, model))
        return TrainState(params=params, opt_state=trainer.optimizer.init(params),
                          step=jnp.zeros((), jnp.int32))

    trainer.state = fresh_state(weights_ouro.seed_key(seed))


def first_three_steps(trainer, data, seed: int, model: dict) -> dict:
    """The first three steps through the window's own call and feed: step 1
    by the logging variant (its loss and counters, and the first gradient,
    which is Adam's first moment after one step over 1 - b1: a looped leaf's
    is the sum over its four uses), step 2 by the fast one, step 3 by the
    logging one again, as `drivers/train_lm_laguna.py` takes them (the
    parameters' change is taken against the seeded weights made again inside
    the program that takes the norms)."""
    import jax
    import jax.numpy as jnp

    h1 = trainer.fit(data, num_steps=1, log_every=1)
    mu = weights_ouro.from_program_params(_adam_mu(trainer.state.opt_state))
    program = {"losses": [h1[-1]["loss"]], "loss_steps": [0, 2],
               "counters": {k: h1[-1].get(k) for k in COUNTERS},
               "first_grad_norms": _leaf_norms(mu, 1.0 / (1.0 - ADAM_B1)),
               "first_grad": {k: np.asarray(v, np.float32) / (1.0 - ADAM_B1)
                              for k, v in jax.device_get(mu).items()}}
    del mu
    program["losses"].append(trainer.fit(data, num_steps=2, log_every=2)[-1]["loss"])

    @jax.jit
    def change_norms(key, params):
        w0 = weights_ouro.weights_from_key(key, model)
        return {k: jnp.sqrt(jnp.sum(jnp.square(v - w0[k])))
                for k, v in weights_ouro.from_program_params(params).items()}

    program["delta_norms"] = {k: float(v) for k, v in jax.device_get(
        change_norms(weights_ouro.seed_key(seed), trainer.state.params)).items()}
    return program


def reference_numbers(cfg, tcfg, seed: int, batches: list, precision: str = "float32",
                      fault=None) -> dict:
    """The plain reference follows the same three steps from the same
    weights and batches. `precision` below float32, or a `fault`, makes it
    the control that `correct` has to fail."""
    from benchmark.reference import ouro_ref

    model = model_of(cfg)
    return ouro_ref.train_reference(
        lambda: weights_ouro.make_weights(seed, model), batches, model,
        lr=tcfg.learning_rate, precision=precision, fault=fault)


def judged_numbers(program: dict, ref: dict) -> dict:
    """`correct.train_numbers`, the parameters' change compared where the
    reference vouches for it (`ouro_ref.change_compared`: not where its own
    first gradient is within Adam's eps; PERF.md trap 11). A reference whose
    own numbers are not finite is no reference: an error, not a verdict."""
    from benchmark.reference.ouro_ref import change_compared

    strayed = [k for k in ("losses", "first_grad_norms", "delta_norms")
               for v in (ref[k].values() if isinstance(ref[k], dict) else ref[k])
               if not math.isfinite(v)]
    if strayed:
        # `correct.train_numbers` takes maxima, which pass over a NaN: say so here
        raise FloatingPointError(
            f"the reference's own numbers are not finite: {sorted(set(strayed))}")
    kept = change_compared(ref)
    left_out = sorted(set(ref["delta_norms"]) - set(kept))
    log(f"parameters' change compared in {len(kept)} leaves; left out (the reference's first "
        f"gradient within Adam's eps): {left_out}")
    numbers = cmp.train_numbers(program, dict(ref, delta_norms=kept))
    # step 1's loss on its own: the exit gate saturates within three steps, and what a rounding
    # does to the third loss follows the seed (`loss_gap` covers both steps)
    numbers["first_loss_gap"] = cmp._rel(program["losses"][0], ref["losses"][0])
    return numbers


def judge(verdict, numbers: dict, limits: dict) -> None:
    """Every number the cell's limits name beside its limit; a number they do
    not name is logged and not judged (`limits/ouro26b.train.json` says why)."""
    verdict.numbers({k: v for k, v in numbers.items() if k in limits}, limits)
    for name in sorted(set(numbers) - set(limits)):
        log(f"logged and not judged: {name} = {numbers[name]:.6g}")


def hold_the_loop(verdict, program: dict, logged: list, cfg, on_kernels: bool) -> None:
    """Untraced runs too: the step's own counts, in step 1 and in every record
    of the window. `ut_steps`: the passes the stack ran. `attn_forward_kept`
    (where the configuration asks for the kernels): the layer applications
    whose recomputation read the attention forward kernel's kept output, 0
    from the XLA loop, so that the rate is the named path's."""
    want = {"ut_steps": cfg.total_ut_steps}
    if on_kernels:
        want["attn_forward_kept"] = cfg.total_ut_steps * cfg.num_hidden_layers
    for name, count in want.items():
        seen = {program["counters"][name]} | {r.get(name) for r in logged}
        verdict.fact(name, " ".join(sorted(f"{v:g}" if v is not None else "none" for v in seen)),
                     f"only {count}", seen == {float(count)})


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
    layers, passes = cfg.num_hidden_layers, cfg.total_ut_steps
    log(f"route vjp_path={trainer.vjp_path} grad_accum={trainer.grad_accum} "
        f"batch={batch} seq_len={cfg.seq_len} span_steps={k} layers held={layers} of "
        f"{cfg.num_hidden_layers_total} passes={passes} heads={cfg.num_attention_heads} "
        f"vocabulary={cfg.vocab_size} beta={cfg.exit_entropy_beta}")
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
    rate = steps * batch * layers * passes / window_s / chips
    log(f"window {window_s:.3f}s steps {steps} step_ms {1e3 * window_s / steps:.3f} "
        f"rows x layers x passes/s/chip {rate:.3f} tokens/s/chip "
        f"{steps * batch * cfg.seq_len / window_s / chips:.1f} last loss {last_loss:.6f} "
        f"compiles in window {compiles_in_window} (set-up {setup_compiles}) peak {peak} B")
    logged = [r for r in records if r.get("kind") == "train_step"]
    for name in COUNTERS:
        log(f"window's records, {name}: " + " ".join(
            sorted({f"{r[name]:g}" for r in logged if name in r})))
    paths = {r.get("vjp_path") for r in logged}
    route = (trainer.vjp_path, trainer.grad_accum)
    kernels = None
    if cap is not None:
        xplane = harness.find_xplane(trace_dir)
        phases = reduce_phases.load(xplane, chips) if xplane else None
        kernels = phases["step"]["by_kernel"] if phases and phases.get("step") else {}

    # Free the program's state and programs, then the reference.
    del data, trainer
    gc.collect()
    jax.clear_caches()
    t_ref = time.perf_counter()
    ref = reference_numbers(cfg, tcfg, seed, pool[:3])
    verdict = cmp.Verdict()
    judge(verdict, judged_numbers(program, ref), cell["limits"])
    log(f"reference took {time.perf_counter() - t_ref:.2f}s")
    cmp.hold_route(verdict, route[0], paths, cfgf["bench"].get("expect_vjp_path"))
    hold_the_loop(verdict, program, logged, cfg, bool(cfgf["bench"].get("attention_on_kernels")))
    if kernels is not None:
        # The route's kernel names against the configuration's own table: the
        # attention kernels have to be there, so that a run that fell back to
        # the XLA loop reads incorrect.
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
