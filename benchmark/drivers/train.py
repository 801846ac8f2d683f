"""A training cell: the program's trainer, built as `train/cli.py:main`
builds it, driven through `fit` in spans of k steps with data, prefetch and
logging running.

Set-up builds ONE trainer, installs the seeded weights the way a checkpoint
resume installs a state, drives it through its first three steps by the
window's own call and feed (`fit` over the prefetched pool), warms up, and
hands that same trainer to the window. After the window the trainer is
freed and the plain reference follows the same three steps.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import time

import numpy as np

from benchmark import correct as cmp
from benchmark import datagen, flops, harness, reduce_phases
from benchmark.harness import log


# ------------------------------------------------- program <-> plain weights


def to_program_params(w: dict):
    from glom_tpu.models.core import GlomParams
    from glom_tpu.ops.ffw import GroupedFFWParams
    from glom_tpu.ops.patch import LinearParams
    from glom_tpu.train.objectives import DenoiseParams

    return DenoiseParams(
        glom=GlomParams(
            token_embed=LinearParams(w["token_w"], w["token_b"]),
            pos_emb=w["pos_emb"],
            init_levels=w["init_levels"],
            bottom_up=GroupedFFWParams(w["bu_w1"], w["bu_b1"], w["bu_w2"], w["bu_b2"]),
            top_down=GroupedFFWParams(w["td_w1"], w["td_b1"], w["td_w2"], w["td_b2"]),
        ),
        to_pixels=LinearParams(w["pix_w"], w["pix_b"]),
    )


def from_program_params(p) -> dict:
    g = p.glom
    return {
        "token_w": g.token_embed.w, "token_b": g.token_embed.b,
        "pos_emb": g.pos_emb, "init_levels": g.init_levels,
        "bu_w1": g.bottom_up.w1, "bu_b1": g.bottom_up.b1,
        "bu_w2": g.bottom_up.w2, "bu_b2": g.bottom_up.b2,
        "td_w1": g.top_down.w1, "td_b1": g.top_down.b1,
        "td_w2": g.top_down.w2, "td_b2": g.top_down.b2,
        "pix_w": p.to_pixels.w, "pix_b": p.to_pixels.b,
    }


def _leaf_norms(tree: dict, scale: float = 1.0, minus: dict = None) -> dict:
    """Per-leaf norms of `tree` (or of `tree - minus`), in one program."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(a, b):
        return {k: jnp.sqrt(jnp.sum(jnp.square(
            (v if b is None else v - b[k]).astype(jnp.float32)))) for k, v in a.items()}

    return {k: scale * float(v) for k, v in jax.device_get(norms(tree, minus)).items()}


def _adam_mu(opt_state):
    for part in opt_state:
        if hasattr(part, "mu"):
            return part.mu
    raise RuntimeError("no Adam first moment in the optimizer state")


def step_noises(seed: int, noise_std: float, shape, n_steps: int) -> list:
    """The noise the trainer adds at steps 0..n-1, from the seed by the
    trainer's published protocol: rng = split(PRNGKey(seed))[0]; each step
    splits it and folds the step number in."""
    import jax
    import jax.numpy as jnp

    rng, _ = jax.random.split(jax.random.PRNGKey(seed))
    out = []
    for step in range(n_steps):
        rng, step_rng = jax.random.split(rng)
        key = jax.random.fold_in(step_rng, step)
        out.append(noise_std * jax.random.normal(key, shape, jnp.float32))
    return out


# ------------------------------------------------------------------ the run


def build_trainer(cell: dict, seed: int, writer):
    """Trainer or DistributedTrainer exactly as `train/cli.py:main` makes
    them from the preset; the configuration file's values are laid over the
    preset's, so the file is what runs."""
    from glom_tpu.utils.presets import get_preset

    cfgf, traf = cell["config_file"], cell["traffic_file"]
    preset = get_preset(cfgf["preset"])
    cfg = dataclasses.replace(preset.model, **cfgf["model"])
    train = dict(cfgf["train"])
    batch = int(train.pop("batch_per_chip")) * cell["chips"]
    tcfg = dataclasses.replace(preset.train, **train, batch_size=batch,
                               seed=int(seed))
    preset = dataclasses.replace(preset, model=cfg, train=tcfg)
    if traf.get("distributed"):
        from glom_tpu.parallel import DistributedTrainer

        scaled = preset.scaled_to(cell["chips"])
        log(f"mesh {scaled.mesh.shape} (data, seq, model) sp={scaled.sp_strategy}")
        trainer = DistributedTrainer(cfg, tcfg, scaled.mesh,
                                     sp_strategy=scaled.sp_strategy,
                                     metrics_writer=writer)
    else:
        from glom_tpu.train import Trainer

        trainer = Trainer(cfg, tcfg, metrics_writer=writer)
    return trainer, cfg, tcfg


def install_weights(trainer, w: dict) -> None:
    """Put the benchmark's weights in the trainer's state, with a fresh
    optimizer state and step 0: what the CLI's checkpoint resume does."""
    import jax
    import jax.numpy as jnp

    from glom_tpu.train.trainer import TrainState

    @jax.jit
    def fresh_state(w):
        params = to_program_params({k: jnp.copy(v) for k, v in w.items()})
        return TrainState(params=params, opt_state=trainer.optimizer.init(params),
                          step=jnp.zeros((), jnp.int32))

    state = fresh_state(w)
    shardings = getattr(trainer, "state_shardings", None)
    trainer.state = state if shardings is None else jax.device_put(state, shardings)


def first_three_steps(trainer, data, seed: int, model: dict) -> dict:
    """The first three steps, through the window's own call and feed. Step 1
    runs the logging variant, step 2 the fast variant the window mostly runs,
    step 3 the logging variant again (fit logs a span's last step), so the
    losses of steps 1 and 3 are what the program reports. The first gradient
    as the optimizer got it is Adam's first moment after one step over
    1 - b1."""
    import jax

    from benchmark.weights import make_weights

    program = {"loss_steps": [0, 2]}
    h1 = trainer.fit(data, num_steps=1, log_every=1)
    mu = from_program_params(_adam_mu(trainer.state.opt_state))
    program["first_grad_norms"] = _leaf_norms(mu, 1.0 / (1.0 - 0.9))
    # the gradient itself, on the host, for the difference the reference takes
    program["first_grad"] = {k: np.asarray(v, np.float32) / (1.0 - 0.9)
                             for k, v in jax.device_get(mu).items()}
    del mu
    h3 = trainer.fit(data, num_steps=2, log_every=2)
    program["losses"] = [h1[-1]["loss"], h3[-1]["loss"]]
    w0 = make_weights(seed, model)
    now = from_program_params(trainer.state.params)
    program["delta_norms"] = _leaf_norms(now, minus=w0)
    return program


def live_feed(traf: dict, batch: int, size: int, seed: int, first: list):
    """The program's own generator, as `train/cli.py:main` calls it, for a
    traffic file that says `"data_source": "live"`. The first three batches
    are kept in `first` for the reference to follow."""
    from glom_tpu import data as program_data

    make = getattr(program_data, traf["data"] + "_dataset")
    for b in make(batch, size, seed=seed):
        if len(first) < 3:
            first.append(b)
        yield b


def run(cell: dict, args, clock) -> int:
    import jax

    from benchmark.weights import make_weights

    cfgf, traf = cell["config_file"], cell["traffic_file"]
    model, chips, seed = cfgf["model"], cell["chips"], int(args.seed)
    dev = harness.start_jax(chips)
    counter = harness.CompileCounter()

    from glom_tpu.data import prefetch_to_device

    writer = harness.Collector()
    trainer, cfg, tcfg = build_trainer(cell, seed, writer)
    batch, k = tcfg.batch_size, int(cfgf["bench"]["span_steps"])
    loop_iters = flops.train_loop_iters(model)
    log(f"route vjp_path={trainer.vjp_path} grad_accum={trainer.grad_accum} "
        f"batch={batch} span_steps={k} loop_iters={loop_iters}")
    log("trainer built")
    w = make_weights(seed, model)
    install_weights(trainer, w)
    del w
    log("weights installed")
    if traf.get("data_source", "pool") == "live":
        pool = []  # filled by the feed with the batches of the first three steps
        feed = live_feed(traf, batch, model["image_size"], seed, pool)
    else:
        pool = datagen.train_pool(seed, batch, model["image_size"],
                                  int(traf["pool_batches"]))
        feed = datagen.cycle(pool, seed)
    data = prefetch_to_device(
        feed, size=int(traf["prefetch"]),
        sharding=getattr(trainer, "batch_sharding", None),
        metrics_writer=writer)

    log(f"data source {traf.get('data_source', 'pool')}, prefetch started")
    program = first_three_steps(trainer, data, seed, model)
    log("first three steps done")
    # Warm-up: one whole span, so that both variants and the prefetch
    # queue are where the window will find them.
    trainer.fit(data, num_steps=k, log_every=k)
    setup_compiles = counter.n
    writer.records.clear()

    cap = trace_dir = None
    if args.trace:
        trace_dir = harness.fresh_trace_dir(cell["name"])
        # spans 2 and 3 of the window, whole
        cap = harness.StepWindow(k, 3 * k - 1, trace_dir)
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
    rate = steps * batch * loop_iters / window_s / chips
    log(f"window {window_s:.3f}s steps {steps} step_ms {1e3 * window_s / steps:.3f} "
        f"col-iters/s/chip {rate:.2f} last loss {last_loss:.6f} compiles in "
        f"window {compiles_in_window} (set-up {setup_compiles}) peak {peak} B")
    paths = {r.get("vjp_path") for r in records if r.get("kind") == "train_step"}
    route = (trainer.vjp_path, trainer.grad_accum)
    # The step's device time by Mosaic kernel name: what the route the
    # program reports is held against. The per-layer readers find the same
    # reduction cached.
    kernels = None
    if cap is not None:
        xplane = harness.find_xplane(trace_dir)
        phases = reduce_phases.load(xplane, chips) if xplane else None
        kernels = phases["step"]["by_kernel"] if phases and phases.get("step") else {}

    # Free the program's state and programs, then run the reference.
    del data, trainer
    gc.collect()
    jax.clear_caches()
    t_ref = time.perf_counter()
    ref = reference_numbers(cell, seed, pool[:3], tcfg)
    verdict = cmp.Verdict()
    verdict.numbers(cmp.train_numbers(program, ref), cell["limits"])
    log(f"reference took {time.perf_counter() - t_ref:.2f}s")
    cmp.hold_route(verdict, route[0], paths, cfgf["bench"].get("expect_vjp_path"), kernels)
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
             "loop_iters": loop_iters, "device_kind": dev["kind"], "route": route,
             "steps_traced": cap.steps_traced if cap else 0},
        trace_dir=trace_dir)


def reference_numbers(cell: dict, seed: int, batches: list, tcfg,
                      precision: str = "float32") -> dict:
    """The plain reference follows the same three steps from the same
    weights, batches and noise, in blocks of rows. `precision` below float32
    makes it the control that `correct` has to fail."""
    import jax.numpy as jnp

    from benchmark.reference import glom_ref
    from benchmark.weights import make_weights

    import jax

    cfgf = cell["config_file"]
    model = cfgf["model"]
    w = make_weights(seed, model)
    noises = step_noises(seed, tcfg.noise_std, batches[0].shape, len(batches))
    devices = jax.local_devices()[:cell["chips"]] if cell["chips"] > 1 else None
    return glom_ref.train_reference(
        w, [jnp.asarray(b) for b in batches], noises, model,
        lr=tcfg.learning_rate, devices=devices,
        block_rows=int(cfgf["bench"]["reference_block_rows"]), precision=precision)
