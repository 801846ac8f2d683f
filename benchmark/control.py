#!/usr/bin/env python3
"""The readings a limit is set from, and the control that `correct` has to
fail:

    python3 benchmark/control.py --workload <name> --seeds 1,2,3 [--precision float8]

For every seed, in one process: the program's numbers against the float32
reference (sound), and the reference put in the program's place in the
nearest precision below the configuration's (bfloat16 configurations:
float8; the control) against the same float32 reference. Prints both per
seed, then the largest sound reading and the smallest control reading of
every number, which is what PERF.md records beside each limit. The
benchmark's own runs never run this.

A training cell needs no measured window (the three steps are the reading);
a serving cell gets a short one at the cell's own rate.
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


def train_readings(cell, seeds, precision):
    import jax

    from glom_tpu.data import prefetch_to_device

    from benchmark import correct as cmp
    from benchmark import datagen, harness
    from benchmark.drivers import train as drv
    from benchmark.weights import make_weights

    harness.start_jax(cell["chips"])
    model = cell["config_file"]["model"]
    rows = []
    for seed in seeds:
        t0 = time.perf_counter()
        trainer, cfg, tcfg = drv.build_trainer(cell, seed, harness.Collector())
        drv.install_weights(trainer, make_weights(seed, model))
        pool = datagen.train_pool(seed, tcfg.batch_size, model["image_size"], 3)
        data = prefetch_to_device(iter(pool), size=2,
                                  sharding=getattr(trainer, "batch_sharding", None))
        program = drv.first_three_steps(trainer, data, seed, model)
        del data, trainer
        gc.collect()
        jax.clear_caches()
        ref = drv.reference_numbers(cell, seed, pool, tcfg)
        low = drv.reference_numbers(cell, seed, pool, tcfg, precision=precision)
        low["loss_steps"] = program["loss_steps"]
        low["losses"] = [low["losses"][s] for s in low["loss_steps"]]
        rows.append({"seed": seed,
                     "sound": cmp.train_numbers(program, ref),
                     "control": cmp.train_numbers(low, ref),
                     "seconds": time.perf_counter() - t0})
        print("READING", json.dumps(rows[-1]), flush=True)
    return rows


def serve_readings(cell, seeds, precision, seconds):
    import jax
    import numpy as np

    from benchmark import correct as cmp
    from benchmark import datagen, harness
    from benchmark.drivers import serve as drv

    harness.start_jax(cell["chips"])
    traf = cell["traffic_file"]
    rows = []
    for seed in seeds:
        t0 = time.perf_counter()
        engine, batcher, cfg, scfg = drv.build_server(cell, seed, harness.Collector(keep=drv.KEEP_EVENTS))
        shape = (cfg.channels, cfg.image_size, cfg.image_size)
        images = datagen.serve_images(seed, int(traf["image_pool"]), shape)
        due = datagen.arrival_times(seed, float(traf["rate_per_s"]), seconds)
        keep = drv.choose_sample(seed, len(due), int(traf["check_requests"]))
        with batcher:
            loop = drv.OpenLoop(batcher, images, due, keep, 60.0)
            loop.run()
        kept, iters = loop.kept, loop.iters
        engine.release()
        del engine, batcher, loop
        gc.collect()
        jax.clear_caches()
        ref = drv.reference_columns(cell, seed, images, kept, iters)
        low = drv.reference_columns(cell, seed, images, kept, iters, precision=precision)
        sound = [(kept[i], ref[i]) for i in sorted(kept)]
        control = [(low[i], ref[i]) for i in sorted(kept)]
        errs = lambda pairs: [cmp.serve_numbers([p])["columns_rel_rms_worst"] for p in pairs]
        s_err, c_err = errs(sound), errs(control)
        rows.append({"seed": seed,
                     "sound": {"columns_rel_rms_worst": max(s_err)},
                     "control": {"columns_rel_rms_worst": min(c_err)},
                     "sound_median": float(np.median(s_err)),
                     "control_median": float(np.median(c_err)),
                     "seconds": time.perf_counter() - t0})
        print("READING", json.dumps(rows[-1]), flush=True)
    return rows


def summarise(rows):
    """For every number: the sound runs' largest and the control's smallest.
    (For the serve cell `control` already holds each seed's smallest.)"""
    out = {}
    for name in rows[0]["sound"]:
        out[name] = {"sound_max": max(r["sound"][name] for r in rows),
                     "control_min": min(r["control"][name] for r in rows)}
    return out


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--precision", default="float8")
    p.add_argument("--seconds", type=float, default=3.0)
    args = p.parse_args(argv)

    from benchmark import harness

    cell = harness.load_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    if cell["traffic_file"]["kind"] == "train":
        rows = train_readings(cell, seeds, args.precision)
    else:
        rows = serve_readings(cell, seeds, args.precision, args.seconds)
    summary = summarise(rows)
    for name, s in summary.items():
        ratio = s["control_min"] / max(s["sound_max"], 1e-30)
        print(f"SUMMARY {args.workload} {name}: sound max {s['sound_max']:.6g}  "
              f"control min {s['control_min']:.6g}  ratio {ratio:.2f}  "
              f"limit now {cell['limits'].get(name)}", flush=True)
    os.makedirs(harness.OUT_DIR, exist_ok=True)
    with open(os.path.join(harness.OUT_DIR, f"control_{args.workload}.json"), "w") as fh:
        json.dump({"rows": rows, "summary": summary, "precision": args.precision}, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
