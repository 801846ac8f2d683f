#!/usr/bin/env python3
"""The readings the Laguna cell's limits are set from, the control that
`correct` has to fail, and the faults that the limit on the parameters'
change is set against (what `control_lm.py` and `control_sambay.py` do for
the other two language models):

    python3 benchmark/control_laguna.py --workload <name> --seeds 1,2,3 \\
        [--controls N] [--faults N] [--precision float8]

For every seed, in one process: the program's numbers against the float32
reference (sound), with the routing agreement. For the first `--controls`
seeds (all, unless said): the reference put in the program's place in the
nearest precision below the configuration's (bfloat16: float8) against the
same float32 reference. For the first `--faults` seeds (none, unless said):
the program again from the same weights with updates lost
(`control_sambay.losing_updates`), and once more with half of the batch
left out (`HALF_BATCH`: the cell trains two sequences a step; the step is fed
the first twice, which is the mean over one sequence in the step's own shapes,
while the reference follows both). Prints every row with each number's worst
leaf on the lines before it, then the largest sound reading and the smallest
control and fault reading of every number, which is what PERF.md records
beside each limit. The benchmark's own runs never run this.
"""
import argparse
import gc
import json
import os
import sys
import time

import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


HALF_BATCH = "half_batch_left_out"


def readings(cell, seeds, precision, controls=None, faults=0):
    import jax

    from glom_tpu.data import prefetch_to_device

    from benchmark import harness
    from benchmark.control_sambay import FAULTS, losing_updates
    from benchmark.drivers import train_lm_laguna as drv

    harness.start_jax(cell["chips"])
    rows = []
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        trainer, cfg, tcfg = drv.build_trainer(cell, seed, harness.Collector())
        model = drv.model_of(cfg)
        pool = drv.token_pool(seed, tcfg.batch_size, cfg.seq_len, cfg.vocab_size, 3)
        halved = [np.repeat(b[:len(b) // 2], 2, axis=0) for b in pool]

        def three_steps(batches=pool):
            drv.install_weights(trainer, seed, model)
            return drv.first_three_steps(trainer, prefetch_to_device(iter(batches), size=2),
                                         seed, model)

        programs = {"sound": three_steps()}
        for name, variants in FAULTS.items() if i < faults else ():
            with losing_updates(*variants):
                programs[name] = three_steps()
        if i < faults:
            programs[HALF_BATCH] = three_steps(halved)
        del trainer
        gc.collect()
        jax.clear_caches()
        chosen = drv.program_choices(cfg, tcfg, seed, model, pool[0])
        choices = {name: chosen for name in programs}
        if HALF_BATCH in programs:
            choices[HALF_BATCH] = drv.program_choices(cfg, tcfg, seed, model, halved[0])
        ref = drv.reference_numbers(cfg, tcfg, seed, pool)
        if controls is None or i < controls:
            low = drv.reference_numbers(cfg, tcfg, seed, pool, precision=precision)
            low["loss_steps"] = programs["sound"]["loss_steps"]
            low["losses"] = [low["losses"][s] for s in low["loss_steps"]]
            programs["control"], choices["control"] = low, low["choices"]
        rows.append({"seed": seed, "seconds": time.perf_counter() - t0, **{
            name: dict(drv.judged_numbers(program, ref),
                       routing_agreement=drv.routing_agreement(choices[name], ref["choices"]))
            for name, program in programs.items()}})
        del ref, programs
        gc.collect()
        print("READING", json.dumps(rows[-1]), flush=True)
    return rows


def summarise(rows):
    """For every number: the sound runs' largest, and the smallest of the
    control's and of each fault's, over the seeds that have one (the other
    way round for the agreement, which has a floor)."""
    from benchmark.control_sambay import FAULTS

    out = {}
    for name in rows[0]["sound"]:
        worst, best = (min, max) if name == "routing_agreement" else (max, min)
        out[name] = {"sound_worst": worst(r["sound"][name] for r in rows)}
        for kind in ("control", *FAULTS, HALF_BATCH):
            got = [r[kind][name] for r in rows if kind in r]
            if got:
                out[name][f"{kind}_best"] = best(got)
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
