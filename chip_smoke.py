"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two main paths once, through the entry points a user calls, at
the full width of preset `imagenet224-dp8` (L=6, d=512, 224 px, patch 14,
n=256, bf16, Pallas) with random weights from a seed:

  1. parity  — the fused Pallas forward against the plain XLA float32
               forward on a small batch (depth cut to 3 iterations);
  2. train   — `glom_tpu.train.cli.main`, batch 64, a few steps: every
               record must say vjp_path=fused_loop (the whole-loop Pallas
               VJP; off-TPU the same command resolves scan_dense), finite
               loss;
  3. serve   — `glom_tpu.serve.cli.main`, the preset's ServeConfig, AOT
               warm-up then paced session traffic: every request answered,
               none failed or shed, later frames on the paged warm route
               with donation live, the page pool conserving, and every
               compiled bucket program carrying Mosaic custom calls.

Everything runs in this one process (a chip belongs to one process); the
platform is the one the environment gives JAX and anything but a TPU is a
failure. No phase is wrapped in try/except: whatever raises ends the run
non-zero. Compile time is reported apart from run time, with the
persistent-cache hit count (utils/startup.enable_compile_cache says where
the cache lives). The last stdout line is the result object.

Run: `python chip_smoke.py` from the checkout root. Writes its streams
under chiprun_out/chip_smoke/.
"""

import gc
import json
import math
import os
import sys
import time

PRESET = "imagenet224-dp8"
TRAIN_STEPS = 6
SERVE_REQUESTS = 48
SERVE_STREAMS = 4
ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, "chiprun_out", "chip_smoke")


def check(cond, msg):
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


def read_jsonl(path):
    with open(path) as fh:
        return [json.loads(line) for line in fh if line.startswith("{")]


class CacheCounter:
    """Counts JAX's persistent-compilation-cache events per phase."""

    def __init__(self):
        import jax.monitoring

        self.requests = self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def take(self):
        out = {"cache_requests": self.requests, "cache_hits": self.hits}
        self.requests = self.hits = 0
        return out


def phase_parity(cfg):
    """Pallas bf16 forward vs XLA float32 forward, same params and image.
    Tolerance is bf16's: 8 mantissa bits through 3 iterations of 4-way
    means of O(1) values (tpu_validate.py's kernel checks use the same
    5e-2)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from glom_tpu.models.core import glom_forward, init_glom
    from glom_tpu.serve.engine import mosaic_calls

    params = init_glom(jax.random.PRNGKey(0), cfg)
    img = jax.random.normal(
        jax.random.PRNGKey(1), (2, cfg.channels, cfg.image_size, cfg.image_size)
    )
    fused = jax.jit(
        lambda p, x: glom_forward(
            p, x, cfg, iters=3, compute_dtype=jnp.bfloat16, use_pallas=True
        )
    )
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, x: glom_forward(p, x, cfg, iters=3))(params, img)
    t0 = time.perf_counter()
    compiled = fused.lower(params, img).compile()
    compile_s = time.perf_counter() - t0
    mosaic = mosaic_calls(compiled)
    check(mosaic > 0, "the fused forward compiled without a Mosaic call")
    got = np.asarray(compiled(params, img), np.float32)
    want = np.asarray(want, np.float32)
    check(
        got.shape == (2, cfg.num_patches, cfg.levels, cfg.dim),
        f"forward shape {got.shape}",
    )
    check(np.isfinite(got).all(), "non-finite forward output")
    err = float(np.max(np.abs(got - want)))
    check(
        np.allclose(got, want, rtol=5e-2, atol=5e-2),
        f"Pallas bf16 forward departs from the XLA f32 reference: max abs err {err}",
    )
    return {"compile_s": round(compile_s, 2), "mosaic_calls": mosaic,
            "max_abs_err": round(err, 5)}


def phase_train(tcfg):
    from glom_tpu.train.cli import main as train_main

    path = os.path.join(OUT, "train.jsonl")
    if os.path.exists(path):
        os.remove(path)  # MetricsWriter appends
    t0 = time.perf_counter()
    rc = train_main(
        ["--preset", PRESET, "--steps", str(TRAIN_STEPS), "--log-every", "1",
         "--metrics-file", path]
    )
    wall = time.perf_counter() - t0
    check(rc == 0, f"train cli exit code {rc}")
    steps = [r for r in read_jsonl(path) if r.get("kind") == "train_step"]
    check(len(steps) == TRAIN_STEPS, f"{len(steps)} train_step records")
    for r in steps:
        check(r["vjp_path"] == "fused_loop", f"vjp_path {r['vjp_path']!r}")
        check(r["grad_accum"] == 1, f"grad_accum {r['grad_accum']}")
        check(math.isfinite(r["loss"]), f"loss {r['loss']} at step {r['step']}")
    compile_s = steps[-1]["compile_time_s"]
    return {
        "batch": tcfg.batch_size,
        "steps": len(steps),
        "loss_first": round(steps[0]["loss"], 5),
        "loss_last": round(steps[-1]["loss"], 5),
        "vjp_path": "fused_loop",
        "compile_s": round(compile_s, 2),
        "run_s": round(wall - compile_s, 2),
    }


def phase_serve():
    from glom_tpu.serve.cli import main as serve_main

    path = os.path.join(OUT, "serve.jsonl")
    if os.path.exists(path):
        os.remove(path)
    t0 = time.perf_counter()
    # Paced: frame t+1 of a session warm-starts only from frame t's
    # written-back columns, so frames of one stream must not share a batch.
    rc = serve_main(
        ["--preset", PRESET, "--synthetic", str(SERVE_REQUESTS),
         "--streams", str(SERVE_STREAMS), "--request-gap-ms", "20",
         "--out", path]
    )
    wall = time.perf_counter() - t0
    check(rc == 0, f"serve cli exit code {rc}")
    recs = read_jsonl(path)
    summary = [r for r in recs if r.get("event") == "summary"][-1]
    check(
        summary["n_requests"] == summary["n_served"] == SERVE_REQUESTS,
        f"served {summary['n_served']} of {summary['n_requests']}",
    )
    check(summary["n_failed"] == 0, f"n_failed {summary['n_failed']}")
    check(summary["n_shed"] == 0, f"n_shed {summary['n_shed']}")
    check(summary["n_page_warm"] > 0, "no dispatch took the paged warm route")
    for name, pool in summary["page_pools"].items():
        check(
            pool["pages_used"] + pool["pages_free"] == pool["pages_total"],
            f"page pool {name} does not conserve: {pool}",
        )
    responses = [r for r in recs if r.get("event") == "response"]
    check(len(responses) == SERVE_REQUESTS, f"{len(responses)} responses")
    for r in responses:
        check(r["ok"], f"response {r['id']} failed: {r.get('reason')}")
        check(
            math.isfinite(r["top_level_norm"]) and r["top_level_norm"] > 0,
            f"response {r['id']} top_level_norm {r['top_level_norm']}",
        )
    warmups = [r for r in recs if r.get("event") == "warmup"]
    check(warmups, "no warmup events")
    for r in warmups:
        check(
            r["mosaic_calls"] > 0,
            f"bucket {r['bucket']} (warm_state={r['warm_state']}) compiled "
            "without a Mosaic call",
        )
    compile_s = sum(r["compile_time_s"] for r in warmups)
    return {
        "n_served": summary["n_served"],
        "n_page_warm": summary["n_page_warm"],
        "programs": len(warmups),
        "mosaic_calls_min": min(r["mosaic_calls"] for r in warmups),
        "compile_s": round(compile_s, 2),
        "run_s": round(wall - compile_s, 2),
    }


def main():
    t_start = time.perf_counter()
    import jax

    devs = jax.devices()
    print(
        f"platform={devs[0].platform} device_kind={devs[0].device_kind} "
        f"count={len(devs)}",
        flush=True,
    )
    from glom_tpu.utils.presets import get_preset
    from glom_tpu.utils.startup import enable_compile_cache, require_tpu

    device = require_tpu("chip_smoke.py")
    cache_dir = enable_compile_cache()
    print(f"compile cache: {cache_dir}", flush=True)
    os.makedirs(OUT, exist_ok=True)
    preset = get_preset(PRESET)
    cache = CacheCounter()
    report = {"cache_dir": cache_dir}

    report["parity"] = {**phase_parity(preset.model), **cache.take()}
    print(json.dumps({"phase": "parity", **report["parity"]}), flush=True)

    report["train"] = {**phase_train(preset.train), **cache.take()}
    # The trainer's state is released before the engine allocates its
    # 1 GiB page pool: nothing of the train phase may still be resident.
    gc.collect()
    held = devs[0].memory_stats()["bytes_in_use"]
    check(held < 64 << 20, f"{held} bytes still on the device after training")
    report["train"]["bytes_in_use_after"] = held
    print(json.dumps({"phase": "train", **report["train"]}), flush=True)

    report["serve"] = {**phase_serve(), **cache.take()}
    print(json.dumps({"phase": "serve", **report["serve"]}), flush=True)

    report["total_s"] = round(time.perf_counter() - t_start, 1)
    with open(os.path.join(OUT, "report.json"), "w") as fh:
        json.dump({"device": device, **report}, fh, indent=1)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
