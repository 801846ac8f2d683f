"""A training cell of the Kimi Linear language model: the program's one
trainer, built as `train/cli.py:main` builds it from the preset, driven
through `fit` in spans of k steps with data, prefetch and logging running.
The structure is `drivers/train_lm_laguna.py`'s, and what that file and
`drivers/train_lm.py` have that knows no model is imported from them: the
trainer's construction, the token pool, the shapes as a dict, the routing
agreement, the memory lines, the judged numbers. What knows the model is
here: the seeded weights (`weights_kimi.py`), the step counters, the
program's routing choices, the reference (`reference/kimi_linear_ref.py`).

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
from benchmark import datagen, harness, reduce_phases, weights_kimi
from benchmark.drivers.train import _adam_mu, _leaf_norms
from benchmark.drivers.train_lm import (
    ADAM_B1,
    build_trainer,
    model_of,
    routing_agreement,
    token_pool,
)
from benchmark.drivers.train_lm_laguna import judge, judged_numbers, log_memory
from benchmark.harness import log

ROUTED_COUNTERS = ("moe_pairs_here", "moe_rows_computed", "moe_rows_full_share",
                   "moe_max_expert_load")
COUNTERS = ROUTED_COUNTERS + ("attn_key_blocks_full", "attn_forward_kept", "kda_chunks",
                              "kda_log_decay_min")
MIXER_LEAVES = frozenset(
    "norm1 q k v conv_q conv_k conv_v f1 f2 dt_bias A_log beta g1 g2 onorm o kva kv_norm kvb".split())


def program_has_the_family() -> None:
    """A checkout without the Kimi Linear model (this cell's parent commit)
    stops here, at once and before it reaches for the chip."""
    try:
        importlib.import_module("glom_tpu.models.kimi_linear")
    except ImportError as e:
        raise SystemExit(f"benchmark: this checkout cannot run the Kimi Linear cell: {e}")


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
        params = weights_kimi.to_program_params(weights_kimi.weights_from_key(key, model))
        return TrainState(params=params, opt_state=trainer.optimizer.init(params),
                          step=jnp.zeros((), jnp.int32))

    trainer.state = fresh_state(weights_kimi.seed_key(seed))


def first_step(trainer, data) -> dict:
    """Step 1 through the window's own call and feed, by the logging variant:
    its loss and counters, and the first gradient, which is Adam's first
    moment after one step over 1 - b1."""
    import jax

    h1 = trainer.fit(data, num_steps=1, log_every=1)
    mu = weights_kimi.from_program_params(_adam_mu(trainer.state.opt_state))
    return {"losses": [h1[-1]["loss"]], "counters": {k: h1[-1].get(k) for k in COUNTERS},
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
        w0 = weights_kimi.weights_from_key(key, model)
        return {k: jnp.sqrt(jnp.sum(jnp.square(v - w0[k])))
                for k, v in weights_kimi.from_program_params(params).items()}

    program["delta_norms"] = {k: float(v) for k, v in jax.device_get(
        change_norms(weights_kimi.seed_key(seed), trainer.state.params)).items()}
    return program


def program_choices(cfg, tcfg, seed: int, model: dict, ids) -> np.ndarray:
    """The experts the program's forward chooses for every token of `ids` in
    every expert layer, from the seeded weights, in the trainer's compute
    type: [E layers, B * T, k]. A pass of the check's own, after the window:
    `kimi_linear.routing_choices` is the step's forward (the same
    `run_stack`, the same scan, the same kernels) compiled again without
    recomputation, not the timed step, which returns no choices."""
    import jax
    import jax.numpy as jnp

    from glom_tpu.models import kimi_linear

    dtype = jnp.bfloat16 if tcfg.compute_dtype == "bfloat16" else None

    @jax.jit
    def choose(key, ids):
        params = weights_kimi.to_program_params(weights_kimi.weights_from_key(key, model))
        return kimi_linear.routing_choices(params, ids, cfg, compute_dtype=dtype)

    return np.asarray(choose(weights_kimi.seed_key(seed), jnp.asarray(ids)))


def mixer_numbers(program: dict, ref: dict) -> dict:
    """`first_grad_diff` over the two mixers' leaves alone. Over all leaves the
    worst is an expert's or a router's, where a routing choice that differs
    from the float32 reference's is a jump (0.2-0.3); the mixers' leaves read a
    fifth of that, and the cell is theirs."""
    mine = lambda leaves: {k: v for k, v in leaves.items()
                           if k.rpartition(".")[2] in MIXER_LEAVES}
    worst, leaf = cmp.worst_leaf_diff(mine(program["first_grad"]), mine(ref["first_grad"]))
    log(f"correct: worst gradient leaf of the mixers {leaf} (difference)")
    return {"mixer_grad_diff": worst}


def scan_numbers(seed: int, model: dict, ids) -> dict:
    """The delta rule alone, at the step's own length and in float32 from end
    to end: `kimi_linear.kda_chunked` against the reference's recurrence a
    position at a time, on what the first KDA layer's seeded weights make of
    the embedded row `ids` [T], in the output and in the gradient of one
    seeded cotangent to q, k, v, g and beta; the worst of the six, each a
    difference's norm over the reference's. A pass of the check's own, as
    `program_choices` is: the function the step calls, compiled again with
    float32 inputs, so that nothing but the chunked form's own arithmetic (the
    carried state's type, the solve's, a decay formed the wrong way) is
    between the two. The step's bfloat16 round it hides that in every other
    number."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import kimi_linear_ref as ref
    from glom_tpu.models import kimi_linear

    layer = next(i for i, (mixer, _) in enumerate(ref.layer_kinds(model)) if mixer == "K")

    @jax.jit
    def made(key, row):
        w = weights_kimi.weights_from_key(key, model)
        lw = ref.layer_weights(w, layer)
        u = ref.rms_norm(w["embed"][row], lw["norm1"], model["rms_norm_eps"])
        xs = ref.kda_inputs(lw, u, model, lambda x: x)
        return xs, jax.random.normal(jax.random.fold_in(key, 1), xs[0].shape, jnp.float32)

    def with_gradients(rule):
        def f(xs, ct):
            o, vjp = jax.vjp(rule, *xs)
            return (o,) + vjp(ct)
        return jax.jit(f)

    chunked = lambda *xs: kimi_linear.kda_chunked(*(x[None] for x in xs))[0][0]
    with jax.default_matmul_precision("highest"):
        xs, ct = made(weights_kimi.seed_key(seed), jnp.asarray(ids))
        want = with_gradients(ref.delta_rule)(xs, ct)
        got = with_gradients(chunked)(xs, ct)
        diffs = {name: float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
                 for name, g, w in zip(("o", "dq", "dk", "dv", "dg", "dbeta"), got, want)}
    log(f"correct: the delta rule alone, chunked against token by token: {diffs}")
    return {"kda_scan_diff": max(diffs.values())}


def reference_numbers(cfg, tcfg, seed: int, batches: list, precision: str = "float32") -> dict:
    """The plain reference follows the same three steps from the same
    weights and batches. `precision` below float32 makes it the control that
    `correct` has to fail."""
    from benchmark.reference import kimi_linear_ref

    model = model_of(cfg)
    return kimi_linear_ref.train_reference(
        lambda: weights_kimi.make_weights(seed, model), batches, model,
        lr=tcfg.learning_rate, precision=precision)


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
        f"({' '.join(m + f for m, f in cfg.kinds)}, published "
        f"{cfg.layer_offset + 1}-{cfg.layer_offset + layers})")
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
    for name in ROUTED_COUNTERS + ("kda_log_decay_min",):
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
    scan = scan_numbers(seed, model, pool[0][0])
    ref = reference_numbers(cfg, tcfg, seed, pool[:3])
    verdict = cmp.Verdict()
    judge(verdict, cell["limits"],
          {**judged_numbers(program, ref), **mixer_numbers(program, ref), **scan},
          routing_agreement(chosen, ref["choices"]))
    log(f"reference took {time.perf_counter() - t_ref:.2f}s")
    cmp.hold_route(verdict, route[0], paths, cfgf["bench"].get("expect_vjp_path"))
    if kernels is not None:
        # The route's kernel names against the configuration's own table: the
        # latent attention's kernels and the compiler's grouped products have
        # to be there, so that a run that fell back to the XLA loop reads
        # incorrect.
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
