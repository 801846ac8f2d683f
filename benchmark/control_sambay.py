#!/usr/bin/env python3
"""The readings the SambaY cell's limits are set from, the control that
`correct` has to fail (what `control_lm.py` does for the other language
model, whose readings include a routing agreement this model has none of),
and the faults that the limits on the loss and on the parameters' change are
set against:

    python3 benchmark/control_sambay.py --workload <name> --seeds 1,2,3 \\
        [--controls N] [--faults N] [--precision float8]

For every seed, in one process: the program's numbers against the float32
reference (sound). For the first `--controls` seeds (all, unless said): the
reference put in the program's place in the nearest precision below the
configuration's (bfloat16: float8) against the same float32 reference. For
the first `--faults` seeds (none, unless said): the program again from the
same weights with updates lost (`losing_updates`): the fast variant's alone,
which is one update of the first three and two of every three in the window,
and both variants', a state left unchanged. Prints every row, then the
largest sound reading and the smallest control and fault reading of every
number, which is what PERF.md records beside each limit. The benchmark's own
runs never run this.
"""
import argparse
import contextlib
import gc
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

FAULTS = {"fast_update_lost": ("step_fast",), "state_unchanged": ("step", "step_fast")}


@contextlib.contextmanager
def losing_updates(*variants):
    """Inside, the trainer's `step` and/or `step_fast` run and report as they
    do, and what they did to the state is lost: parameters and optimizer are
    what they were, only the step's number goes on. The real step is given
    its state for good (`donate_argnums`), so what it was waits on the host."""
    import jax

    from glom_tpu.train.trainer import Trainer

    def losing(real):
        def step(self, batch):
            before = jax.device_get(self.state)
            metrics = real(self, batch)
            number, self.state = self.state.step, None   # two states do not fit
            self.state = jax.device_put(before)._replace(step=number)
            return metrics
        return step

    real = {name: getattr(Trainer, name) for name in variants}
    try:
        for name, fn in real.items():
            setattr(Trainer, name, losing(fn))
        yield
    finally:
        for name, fn in real.items():
            setattr(Trainer, name, fn)


def readings(cell, seeds, precision, controls=None, faults=0):
    import jax

    from glom_tpu.data import prefetch_to_device

    from benchmark import harness
    from benchmark.drivers import train_lm_sambay as drv

    harness.start_jax(cell["chips"])
    rows = []
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        trainer, cfg, tcfg = drv.build_trainer(cell, seed, harness.Collector())
        model = drv.model_of(cfg)
        pool = drv.token_pool(seed, tcfg.batch_size, cfg.seq_len, cfg.vocab_size, 3)

        def three_steps():
            drv.install_weights(trainer, seed, model)
            return drv.first_three_steps(trainer, prefetch_to_device(iter(pool), size=2),
                                         seed, model)

        programs = {"sound": three_steps()}
        for name, variants in FAULTS.items() if i < faults else ():
            with losing_updates(*variants):
                programs[name] = three_steps()
        del trainer
        gc.collect()
        jax.clear_caches()
        ref = drv.reference_numbers(cfg, tcfg, seed, pool)
        if controls is None or i < controls:
            low = drv.reference_numbers(cfg, tcfg, seed, pool, precision=precision)
            low["loss_steps"] = programs["sound"]["loss_steps"]
            low["losses"] = [low["losses"][s] for s in low["loss_steps"]]
            programs["control"] = low
        rows.append({"seed": seed, **{name: drv.judged_numbers(program, ref)
                                      for name, program in programs.items()},
                     "seconds": time.perf_counter() - t0})
        del ref, programs
        gc.collect()
        print("READING", json.dumps(rows[-1]), flush=True)
    return rows


def summarise(rows):
    """For every number: the sound runs' largest, and the smallest of the
    control's and of each fault's, over the seeds that have one."""
    out = {}
    for name in rows[0]["sound"]:
        out[name] = {"sound_worst": max(r["sound"][name] for r in rows)}
        for kind in ("control", *FAULTS):
            got = [r[kind][name] for r in rows if kind in r]
            if got:
                out[name][f"{kind}_best"] = min(got)
    return out


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__,
                                formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--precision", default="float8")
    p.add_argument("--controls", type=int, default=None)
    p.add_argument("--faults", type=int, default=0)
    args = p.parse_args(argv)

    from benchmark import harness

    cell = harness.load_cell(args.workload)
    rows = readings(cell, [int(s) for s in args.seeds.split(",")], args.precision,
                    args.controls, args.faults)
    summary = summarise(rows)
    for name, s in summary.items():
        print(f"SUMMARY {args.workload} {name}: " + "  ".join(
            f"{k} {v:.6g}" for k, v in s.items())
            + f"  limit now {cell['limits'].get(name)}", flush=True)
    os.makedirs(harness.OUT_DIR, exist_ok=True)
    with open(os.path.join(harness.OUT_DIR, f"control_{args.workload}.json"), "w") as fh:
        json.dump({"rows": rows, "summary": summary, "precision": args.precision}, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
