#!/usr/bin/env python3
"""PERF.md section 7, trap 18, kept so that it can be run again: the Kimi
Linear cell's step with the two halves of `kimi_linear.kda_mixer` NOT
recomputed on their own (`kimi_linear.recomputed_half` taken away) computed,
on the chip at the cell's size, a first gradient ten to thirty-eight times
the reference's in every KDA layer under a right loss, at a peak of 16.71 of
the chip's 16.91 GB.

    python3 benchmark/repro_kimi_halves.py --workload kimilinear.train --seed N \\
        [--rung-loads 2,5] [--committed]

The float32 reference's first gradient, then for every `--rung-loads` (the
small rung of the routed part in balanced loads; the cell's 5, and 2, which
takes 1.1 GB of the step's temporaries away) the faulty form's step 1 through
the trainer: `first_grad_diff`, `mixer_grad_diff`, the worst leaves and the
peak of the device's memory (the process's so far, hence the smaller rung
first). A wrong gradient at both says the compiled program is at fault
whatever the memory; at the cell's rung alone, the memory's edge. `--committed` reads the committed form the same way first. The
benchmark's own runs never run this.
"""
import argparse
import dataclasses
import gc
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__,
                                formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--rung-loads", default="2,5")
    p.add_argument("--committed", action="store_true")
    args = p.parse_args(argv)

    import jax

    from glom_tpu.data import prefetch_to_device
    from glom_tpu.models import kimi_linear

    from benchmark import correct as cmp
    from benchmark import harness
    from benchmark.drivers import train_lm_kimi as drv

    cell = harness.load_cell(args.workload)
    harness.start_jax(cell["chips"])
    seed = args.seed
    trainer, cfg, tcfg = drv.build_trainer(cell, seed, harness.Collector())
    del trainer   # for the configuration as the files give it; its state would starve the reference
    gc.collect()
    model = drv.model_of(cfg)
    pool = drv.token_pool(seed, tcfg.batch_size, cfg.seq_len, cfg.vocab_size, 1)
    ref = drv.reference_numbers(cfg, tcfg, seed, pool)

    def step_one(name, rung_loads, halves):
        cell["config_file"]["model"]["moe_rung_loads"] = rung_loads
        kimi_linear.recomputed_half = halves
        jax.clear_caches()
        try:
            trainer, _, _ = drv.build_trainer(cell, seed, harness.Collector())
            drv.install_weights(trainer, seed, model)
            got = drv.first_step(trainer, prefetch_to_device(iter(pool), size=1))
            del trainer
        finally:
            kimi_linear.recomputed_half = jax.checkpoint
            gc.collect()
            jax.clear_caches()
        diff, leaf = cmp.worst_leaf_diff(got["first_grad"], ref["first_grad"])
        print(f"REPRO {name} moe_rung_loads {rung_loads}: loss {got['losses'][0]:.6f} "
              f"(reference {ref['losses'][0]:.6f}) first_grad_diff {diff:.6g} at {leaf} "
              f"mixer_grad_diff {drv.mixer_numbers(got, ref)['mixer_grad_diff']:.6g} "
              f"memory_peak_bytes {harness.memory_peak_bytes(cell['chips'])}", flush=True)

    loads = [int(x) for x in args.rung_loads.split(",")]
    if args.committed:
        step_one("halves recomputed (committed)", loads[0], jax.checkpoint)
    for rung_loads in loads:
        step_one("halves not recomputed", rung_loads, lambda f: f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
