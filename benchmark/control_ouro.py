#!/usr/bin/env python3
"""The readings the Ouro cell's limits are set from, the control that
`correct` has to fail, and the faults the limits are set against (what
`control_evabyte.py` does for its cell):

    python3 benchmark/control_ouro.py --workload <name> --seeds 1,2,3 \\
        [--sound N] [--controls N] [--loop-faults N] [--faults N]

For every seed, in one process: the float32 reference. For the first
`--sound` seeds (all, unless said) the program's numbers against it. For the
first `--controls` seeds (all, unless said): the reference put in the
program's place in the nearest precision below the configuration's (bfloat16:
float8) against the same float32 reference. For the first `--loop-faults`
seeds (none, unless said): the reference with one wrong reading of the loop
(`ouro_ref.FAULTS`: a looped weight's gradient from its last use alone, the
closing norm left out between passes, the norms on the branches' outputs left
out, the loss on the last pass alone, the entropy term left out, three passes
for four, the gate's two leaves left out of the update) put in the program's
place against the sound reference: step 1 alone (the loss of step 1, the first
gradient's two numbers), but the gate's fault, which is followed through all
three steps so that the parameters' change is read too. A fault read this way
is the fault alone, without the program's bfloat16 round it: the smallest it
can read. For the first `--faults` seeds (none, unless said), the program
again from the same weights with updates lost (`control_sambay.losing_updates`:
the fast variant's, and every step's, which is a state left unchanged). Prints
every row, then the largest sound reading and the smallest control and fault
reading of every number, which is what PERF.md records beside each limit. The
benchmark's own runs never run this.
"""
import argparse
import gc
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmark.control_evabyte import summarise  # noqa: E402,F401  (the same table of readings)

THREE_STEPS = ("gate_not_updated",)   # the faults followed through all three steps


def readings(cell, seeds, precision, sound=None, controls=None, loop_faults=0, faults=0):
    import jax

    from glom_tpu.data import prefetch_to_device

    from benchmark import correct as cmp
    from benchmark import harness
    from benchmark.control_sambay import FAULTS, losing_updates
    from benchmark.drivers import train_lm_ouro as drv
    from benchmark.reference import ouro_ref

    harness.start_jax(cell["chips"])
    rows = []
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        trainer, cfg, tcfg = drv.build_trainer(cell, seed, harness.Collector())
        del trainer   # for the configuration as the files give it; every program builds its own
        gc.collect()
        model = drv.model_of(cfg)
        pool = drv.token_pool(seed, tcfg.batch_size, cfg.seq_len, cfg.vocab_size, 3)
        ref = drv.reference_numbers(cfg, tcfg, seed, pool)
        row = {"seed": seed}

        def said(name, numbers):
            row[name] = numbers
            print("PART", name, json.dumps(numbers), flush=True)

        def program(name):
            """A trainer of its own and its first three steps."""
            trainer, _, _ = drv.build_trainer(cell, seed, harness.Collector())
            drv.install_weights(trainer, seed, model)
            numbers = drv.first_three_steps(trainer, prefetch_to_device(iter(pool), size=2),
                                            seed, model)
            del trainer
            gc.collect()
            jax.clear_caches()
            said(name, drv.judged_numbers(numbers, ref))

        def in_the_programs_place(name, low, steps):
            """A reference run judged as a program's would be, by the steps it has."""
            low["loss_steps"] = steps
            low["losses"] = [low["losses"][s] for s in steps]
            said(name, drv.judged_numbers(low, ref) if len(steps) == 2 else {
                "first_loss_gap": cmp._rel(low["losses"][0], ref["losses"][0]),
                "first_grad_norm_gap": cmp.worst_leaf_gap(low["first_grad_norms"],
                                                          ref["first_grad_norms"])[0],
                "first_grad_diff": cmp.worst_leaf_diff(low["first_grad"], ref["first_grad"])[0]})

        if sound is None or i < sound:
            program("sound")
        if controls is None or i < controls:
            low = drv.reference_numbers(cfg, tcfg, seed, pool, precision=precision)
            in_the_programs_place("control", low, [0, 2])
        if i < loop_faults:
            for fault in ouro_ref.FAULTS:
                whole = fault in THREE_STEPS
                in_the_programs_place(
                    fault, drv.reference_numbers(cfg, tcfg, seed, pool if whole else pool[:1],
                                                 fault=fault), [0, 2] if whole else [0])
                jax.clear_caches()
        if i < faults:
            for name, variants in FAULTS.items():
                with losing_updates(*variants):
                    program(name)
        row["seconds"] = time.perf_counter() - t0
        rows.append(row)
        del ref
        gc.collect()
        print("READING", json.dumps(row), flush=True)
    return rows


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__,
                                formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--precision", default="float8")
    p.add_argument("--sound", type=int, default=None)
    p.add_argument("--controls", type=int, default=None)
    p.add_argument("--loop-faults", type=int, default=0)
    p.add_argument("--faults", type=int, default=0)
    args = p.parse_args(argv)

    from benchmark import harness

    cell = harness.load_cell(args.workload)
    rows = readings(cell, [int(s) for s in args.seeds.split(",")], args.precision,
                    args.sound, args.controls, args.loop_faults, args.faults)
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
