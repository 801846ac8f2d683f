"""A training cell of the EvaByte language model: the program's one trainer,
built as `train/cli.py:main` builds it from the preset, driven through `fit`
in spans of k steps with data, prefetch and logging running. The structure
is `drivers/train_lm_kimi.py`'s without a router, and what the older drivers
have that knows no model is imported from them: the trainer's construction,
the token pool, the shapes as a dict, the memory lines. What knows the model
is here: the seeded weights (`weights_evabyte.py`), the step counters, EVA
attention alone against the reference's (`attention_numbers`), the reference
(`reference/evabyte_ref.py`), and the requirement that every layer's attention
ran in the kernels, held by the step's own count on untraced runs too
(`bench.attention_on_kernels`).

`train_col_iters_per_s_per_chip` reads here as in the other language-model
cells: sequences x layers held a second a chip. Bytes a second are logged on
an earlier line.
"""

from __future__ import annotations

import gc
import importlib
import math
import time

import numpy as np

from benchmark import correct as cmp
from benchmark import datagen, harness, reduce_phases, weights_evabyte
from benchmark.drivers.train import _adam_mu, _leaf_norms
from benchmark.drivers.train_lm import ADAM_B1, build_trainer, model_of, token_pool
from benchmark.drivers.train_lm_laguna import log_memory
from benchmark.harness import log

COUNTERS = ("attn_forward_kept", "attn_key_blocks_local", "attn_key_blocks_summary",
            "eva_summary_keys", "lm_pred_heads")


def program_has_the_family() -> None:
    """A checkout without the EvaByte model (this cell's parent commit) stops
    here, at once and before it reaches for the chip."""
    try:
        importlib.import_module("glom_tpu.models.evabyte")
    except ImportError as e:
        raise SystemExit(f"benchmark: this checkout cannot run the EvaByte cell: {e}")


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
        params = weights_evabyte.to_program_params(weights_evabyte.weights_from_key(key, model))
        return TrainState(params=params, opt_state=trainer.optimizer.init(params),
                          step=jnp.zeros((), jnp.int32))

    trainer.state = fresh_state(weights_evabyte.seed_key(seed))


def first_step(trainer, data) -> dict:
    """Step 1 through the window's own call and feed, by the logging variant:
    its loss and counters, and the first gradient, which is Adam's first
    moment after one step over 1 - b1."""
    import jax

    h1 = trainer.fit(data, num_steps=1, log_every=1)
    mu = weights_evabyte.from_program_params(_adam_mu(trainer.state.opt_state))
    return {"losses": [h1[-1]["loss"]], "loss_steps": [0],
            "counters": {k: h1[-1].get(k) for k in COUNTERS},
            "first_grad_norms": _leaf_norms(mu, 1.0 / (1.0 - ADAM_B1)),
            "first_grad": {k: np.asarray(v, np.float32) / (1.0 - ADAM_B1)
                           for k, v in jax.device_get(mu).items()}}


def first_three_steps(trainer, data, seed: int, model: dict) -> dict:
    """The first three steps: step 1 by the logging variant (`first_step`),
    step 2 by the fast one, step 3 by the logging one again, as
    `drivers/train_lm_laguna.py` takes them (the parameters' change is taken
    against the seeded weights made again inside the program that takes the
    norms)."""
    import jax
    import jax.numpy as jnp

    program = dict(first_step(trainer, data), loss_steps=[0, 2])
    program["losses"].append(trainer.fit(data, num_steps=2, log_every=2)[-1]["loss"])

    @jax.jit
    def change_norms(key, params):
        w0 = weights_evabyte.weights_from_key(key, model)
        return {k: jnp.sqrt(jnp.sum(jnp.square(v - w0[k])))
                for k, v in weights_evabyte.from_program_params(params).items()}

    program["delta_norms"] = {k: float(v) for k, v in jax.device_get(
        change_norms(weights_evabyte.seed_key(seed), trainer.state.params)).items()}
    return program


def attention_numbers(seed: int, model: dict, ids, fault=None) -> dict:
    """EVA attention alone, at the step's own length and in float32 from end
    to end: the program's summariser and `evabyte.eva_attention` (on the chip
    the kernels, both key segments in one softmax) against the reference's
    `eva`, a block of queries against all T + T / 16 keys under the mask
    written out, on what the first layer's seeded weights make of the embedded
    row `ids` [T]: the output, and the gradient of one seeded cotangent to q,
    k, v, phi and mu; the worst of the six, each a difference's norm over the
    larger of the two norms. A pass of the check's own after the window: the
    functions the step calls, compiled again with float32 inputs, so that nothing but the
    attention's own arithmetic (which keys a query sees, how a chunk is
    weighed, what joins the segments) is between the two; the step's bfloat16
    round it would hide a wrong mask in no other number as plainly. `fault`
    puts one of `evabyte_ref.FAULTS` in the reference's place: the control's."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import evabyte_ref as ref
    from glom_tpu.models import evabyte
    from glom_tpu.utils.config import EvaByteConfig

    cfg = EvaByteConfig(**model)

    @jax.jit
    def made(key, row):
        w = weights_evabyte.weights_from_key(key, model)
        lw = ref.layer_weights(w, 0)
        u = ref.norm(w["embed"][row], lw["norm1"], model["rms_norm_eps"])
        xs = ref.attention_inputs(lw, u, model) + (lw["phi"], lw["mu"])
        return xs, jax.random.normal(jax.random.fold_in(key, 1), xs[0].shape, jnp.float32)

    def with_gradients(rule):
        def f(xs, ct):
            o, vjp = jax.vjp(rule, *xs)
            return (o,) + vjp(ct)
        return jax.jit(f)

    def program(q, k, v, phi, mu):
        q, k, v = q[None], k[None], v[None]
        return evabyte.eva_attention(q, k, v, *evabyte.summarise(k, v, phi, mu, cfg), cfg)[0][0]

    reference = lambda q, k, v, phi, mu: ref.eva(q, k, v, phi, mu, model, fault=fault)
    with jax.default_matmul_precision("highest"):
        xs, ct = made(weights_evabyte.seed_key(seed), jnp.asarray(ids))
        want = with_gradients(reference)(xs, ct)
        got = with_gradients(program)(xs, ct)
        # over the larger of the two norms: a fault that zeroes the reference's gradient of
        # `phi` or `mu` then reads 1 and not a division by zero
        norm = jnp.linalg.norm
        diffs = {name: float(norm(g - w) / jnp.maximum(norm(g), norm(w)))
                 for name, g, w in zip(("o", "dq", "dk", "dv", "dphi", "dmu"), got, want)}
    log(f"correct: EVA attention alone, program against reference: {diffs}")
    return {"eva_attention_diff": max(diffs.values())}


def reference_numbers(cfg, tcfg, seed: int, batches: list, precision: str = "float32",
                      fault=None) -> dict:
    """The plain reference follows the same three steps from the same
    weights and batches. `precision` below float32, or a `fault`, makes it
    the control that `correct` has to fail."""
    from benchmark.reference import evabyte_ref

    model = model_of(cfg)
    return evabyte_ref.train_reference(
        lambda: weights_evabyte.make_weights(seed, model), batches, model,
        lr=tcfg.learning_rate, precision=precision, fault=fault)


def judged_numbers(program: dict, ref: dict) -> dict:
    """`correct.train_numbers`, the parameters' change compared where the
    reference vouches for it (`evabyte_ref.change_compared`: not where its own
    first gradient is within Adam's eps; PERF.md trap 11)."""
    from benchmark.reference.evabyte_ref import change_compared

    kept = change_compared(ref)
    left_out = sorted(set(ref["delta_norms"]) - set(kept))
    log(f"parameters' change compared in {len(kept)} leaves; left out (the reference's first "
        f"gradient within Adam's eps): {left_out}")
    return cmp.train_numbers(program, dict(ref, delta_norms=kept))


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
        f"heads held={cfg.num_attention_heads} of {cfg.num_attention_heads_total} "
        f"window={cfg.window_size} chunk={cfg.chunk_size} pred heads={cfg.num_pred_heads}")
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
        f"sequences x layers/s/chip {rate:.3f} bytes/s/chip "
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

    # Free the program's state and programs, then the attention alone and the reference.
    del data, trainer
    gc.collect()
    jax.clear_caches()
    t_ref = time.perf_counter()
    alone = attention_numbers(seed, model, pool[0][0])
    log(f"EVA attention alone took {time.perf_counter() - t_ref:.2f}s")
    ref = reference_numbers(cfg, tcfg, seed, pool[:3])
    verdict = cmp.Verdict()
    verdict.numbers({**judged_numbers(program, ref), **alone}, cell["limits"])
    log(f"reference took {time.perf_counter() - t_ref:.2f}s")
    cmp.hold_route(verdict, route[0], paths, cfgf["bench"].get("expect_vjp_path"))
    if cfgf["bench"].get("attention_on_kernels"):
        # Untraced runs too: the step's own count of the layers whose attention
        # ran in the kernels (0 from the XLA loop), in step 1 and in every
        # record of the window, so that the rate is the named path's.
        kept = {program["counters"]["attn_forward_kept"]} | {
            r.get("attn_forward_kept") for r in logged}
        verdict.fact("attn_forward_kept", " ".join(sorted(f"{v:g}" if v is not None else "none"
                                                          for v in kept)),
                     f"only {layers}", kept == {float(layers)})
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
