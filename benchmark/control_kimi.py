#!/usr/bin/env python3
"""The readings the Kimi Linear cell's limits are set from, the control that
`correct` has to fail, and the faults the limits are set against (what
`control_laguna.py` does for its cell):

    python3 benchmark/control_kimi.py --workload <name> --seeds 1,2,3 \\
        [--controls N] [--faults N] [--scan-faults N] [--precision float8]

For every seed, in one process: the float32 reference, then the program's
numbers against it (sound), with the routing agreement; every program is
judged and dropped before the next runs. For the first `--scan-faults` seeds
(none, unless said), the program with the delta rule's carried state, then
its in-chunk solve, in bfloat16 (`SCAN_FAULTS`: the two types `kimi_linear`
names for this): step 1 alone, so the gradient's numbers and the delta rule's
own (a fault that compiles the step anew pays for one variant of it, not
two). For the first `--controls` seeds (all, unless said): the reference put
in the program's place in the nearest precision below the configuration's
(bfloat16: float8) against the same float32 reference. For the first
`--faults` seeds (none, unless said), the program again from the same
weights: with updates lost (`control_sambay.losing_updates`: the fast
variant's, and every step's, which is a state left unchanged); and with half
of the row left out (`HALF_ROW`: the cell trains one row of 16,384 tokens a
step; the step is fed the row's first half twice, in the step's own shapes,
while the reference follows the whole row). Prints every row with each
number's worst leaf on the lines before it, then the largest sound reading
and the smallest control and fault reading of every number, which is what
PERF.md records beside each limit. The benchmark's own runs never run this.
"""
import argparse
import contextlib
import gc
import json
import os
import sys
import time

import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


HALF_ROW = "half_row_left_out"
SCAN_FAULTS = {"scan_state_bfloat16": "SCAN_STATE_DTYPE", "scan_solve_bfloat16": "SCAN_SOLVE_DTYPE"}


@contextlib.contextmanager
def scan_in_bfloat16(constant: str):
    """Inside, a step compiled anew runs the delta rule with `constant` (the
    carried state's type, or the in-chunk solve's) in bfloat16."""
    import jax
    import jax.numpy as jnp

    from glom_tpu.models import kimi_linear

    real = getattr(kimi_linear, constant)
    setattr(kimi_linear, constant, jnp.bfloat16)
    jax.clear_caches()
    try:
        yield
    finally:
        setattr(kimi_linear, constant, real)
        jax.clear_caches()


def readings(cell, seeds, precision, controls=None, faults=0, scan_faults=0):
    import jax

    from glom_tpu.data import prefetch_to_device

    from benchmark import correct as cmp
    from benchmark import harness
    from benchmark.control_sambay import FAULTS, losing_updates
    from benchmark.drivers import train_lm_kimi as drv

    harness.start_jax(cell["chips"])
    rows = []
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        trainer, cfg, tcfg = drv.build_trainer(cell, seed, harness.Collector())
        del trainer   # for the configuration as the files give it; every program builds its own
        gc.collect()
        model = drv.model_of(cfg)
        pool = drv.token_pool(seed, tcfg.batch_size, cfg.seq_len, cfg.vocab_size, 3)
        half = cfg.seq_len // 2
        halved = [np.concatenate([b[:, :half], b[:, :half]], axis=1) for b in pool]
        # The reference first, while the device is free; then one program at a time, judged and
        # dropped: a program's first gradient is 2.4 GB on the host, and seven of them beside the
        # state a lost update parks there are more than the machine has.
        ref = drv.reference_numbers(cfg, tcfg, seed, pool)
        row = {"seed": seed}

        def judged(name, program, choices, scan):
            row[name] = dict(drv.judged_numbers(program, ref), **drv.mixer_numbers(program, ref),
                             **scan,
                             routing_agreement=drv.routing_agreement(choices, ref["choices"]))
            print("PART", name, json.dumps(row[name]), flush=True)

        def program(name, batches=pool):
            """A trainer of its own: the step is compiled under whatever the
            fault has patched; then the same program's choices."""
            trainer, _, _ = drv.build_trainer(cell, seed, harness.Collector())
            drv.install_weights(trainer, seed, model)
            numbers = drv.first_three_steps(trainer, prefetch_to_device(iter(batches), size=2),
                                            seed, model)
            del trainer
            gc.collect()
            jax.clear_caches()
            judged(name, numbers, drv.program_choices(cfg, tcfg, seed, model, batches[0]),
                   drv.scan_numbers(seed, model, batches[0][0]))

        def first_step_alone(name):
            trainer, _, _ = drv.build_trainer(cell, seed, harness.Collector())
            drv.install_weights(trainer, seed, model)
            numbers = drv.first_step(trainer, prefetch_to_device(iter(pool), size=2))
            del trainer
            gc.collect()
            jax.clear_caches()
            row[name] = {
                "first_grad_norm_gap": cmp.worst_leaf_gap(numbers["first_grad_norms"],
                                                          ref["first_grad_norms"])[0],
                "first_grad_diff": cmp.worst_leaf_diff(numbers["first_grad"],
                                                       ref["first_grad"])[0],
                **drv.mixer_numbers(numbers, ref),
                **drv.scan_numbers(seed, model, pool[0][0])}
            print("PART", name, json.dumps(row[name]), flush=True)

        program("sound")
        sound_scan = {"kda_scan_diff": row["sound"]["kda_scan_diff"]}
        if i < scan_faults:
            for name, constant in SCAN_FAULTS.items():
                with scan_in_bfloat16(constant):
                    first_step_alone(name)
        if controls is None or i < controls:
            low = drv.reference_numbers(cfg, tcfg, seed, pool, precision=precision)
            low["loss_steps"] = [0, 2]
            low["losses"] = [low["losses"][s] for s in low["loss_steps"]]
            judged("control", low, low["choices"], sound_scan)   # the recurrence is no product
            del low
        if i < faults:
            program(HALF_ROW, halved)
            for name, variants in FAULTS.items():
                with losing_updates(*variants):
                    program(name)
        row["seconds"] = time.perf_counter() - t0
        rows.append(row)
        del ref
        gc.collect()
        print("READING", json.dumps(row), flush=True)
    return rows


def summarise(rows):
    """For every number: the sound runs' largest, and the smallest of the
    control's and of each fault's, over the seeds that have one (the other
    way round for the agreement, which has a floor)."""
    kinds = [k for k in rows[0] if isinstance(rows[0][k], dict) and k != "sound"]
    out = {}
    for name in rows[0]["sound"]:
        worst, best = (min, max) if name == "routing_agreement" else (max, min)
        out[name] = {"sound_worst": worst(r["sound"][name] for r in rows)}
        for kind in kinds:
            have = [r[kind][name] for r in rows if name in r.get(kind, ())]
            if have:
                out[name][f"{kind}_best"] = best(have)
    return out


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__,
                                formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--precision", default="float8")
    p.add_argument("--controls", type=int, default=None)
    p.add_argument("--faults", type=int, default=0)
    p.add_argument("--scan-faults", type=int, default=0)
    args = p.parse_args(argv)

    from benchmark import harness

    cell = harness.load_cell(args.workload)
    rows = readings(cell, [int(s) for s in args.seeds.split(",")], args.precision,
                    args.controls, args.faults, args.scan_faults)
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
