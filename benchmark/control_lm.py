#!/usr/bin/env python3
"""The readings the language-model cell's limits are set from, and the
control that `correct` has to fail (what `control.py` does for the GLOM
cells, whose `main` tells training from serving by the traffic's kind and
knows no third):

    python3 benchmark/control_lm.py --workload <name> --seeds 1,2,3 [--precision float8]

For every seed, in one process: the program's numbers against the float32
reference (sound), and the reference put in the program's place in the
nearest precision below the configuration's (bfloat16: float8; the control)
against the same float32 reference, each with its routing agreement. With
`--forced`, also the sound numbers against a reference that takes the
program's routing choices in the first step. Prints all per seed, then the
largest sound reading and the smallest control reading of every number,
which is what PERF.md records beside each limit. The benchmark's own runs
never run this.
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


def readings(cell, seeds, precision, forced=False):
    import jax

    from glom_tpu.data import prefetch_to_device

    from benchmark import correct as cmp
    from benchmark import harness
    from benchmark.drivers import train_lm as drv

    harness.start_jax(cell["chips"])
    rows = []
    for seed in seeds:
        t0 = time.perf_counter()
        trainer, cfg, tcfg = drv.build_trainer(cell, seed, harness.Collector())
        model = drv.model_of(cfg)
        drv.install_weights(trainer, seed, model)
        pool = drv.token_pool(seed, tcfg.batch_size, cfg.seq_len, cfg.vocab_size, 3)
        data = prefetch_to_device(iter(pool), size=2)
        program = drv.first_three_steps(trainer, data, seed, model)
        del data, trainer
        gc.collect()
        jax.clear_caches()
        chosen = drv.program_choices(cfg, tcfg, seed, model, pool[0])
        ref = drv.reference_numbers(cfg, tcfg, seed, pool)
        low = drv.reference_numbers(cfg, tcfg, seed, pool, precision=precision)
        low["loss_steps"] = program["loss_steps"]
        low["losses"] = [low["losses"][s] for s in low["loss_steps"]]
        row = {"seed": seed,
               "sound": dict(cmp.train_numbers(program, ref),
                             routing_agreement=drv.routing_agreement(chosen, ref["choices"])),
               "control": dict(cmp.train_numbers(low, ref),
                               routing_agreement=drv.routing_agreement(low["choices"],
                                                                       ref["choices"]))}
        if forced:
            held = drv.reference_numbers(
                cfg, tcfg, seed, pool,
                first_choices=list(chosen.reshape(ref["choices"].shape)))
            row["sound_forced"] = cmp.train_numbers(program, held)
            del held
        row["seconds"] = time.perf_counter() - t0
        rows.append(row)
        del ref, low, program
        gc.collect()
        print("READING", json.dumps(rows[-1]), flush=True)
    return rows


def summarise(rows):
    """For every number: the sound runs' largest and the control's smallest
    (the other way round for the agreement, which has a floor)."""
    out = {}
    for name in rows[0]["sound"]:
        hi, lo = (min, max) if name == "routing_agreement" else (max, min)
        out[name] = {"sound_worst": hi(r["sound"][name] for r in rows),
                     "control_best": lo(r["control"][name] for r in rows)}
        if "sound_forced" in rows[0] and name in rows[0]["sound_forced"]:
            out[name]["sound_forced_worst"] = hi(r["sound_forced"][name] for r in rows)
    return out


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--precision", default="float8")
    p.add_argument("--forced", action="store_true")
    args = p.parse_args(argv)

    from benchmark import harness

    cell = harness.load_cell(args.workload)
    rows = readings(cell, [int(s) for s in args.seeds.split(",")], args.precision, args.forced)
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
