"""A serving cell: `InferenceEngine` + `DynamicBatcher` built as
`serve/cli.py:main` builds them from the preset, under an open loop at a
rate fixed in the traffic file.

One process: the generator (the main thread) submits on schedule from a
pool of images made in set-up, a collector thread waits on the tickets in
order, the batcher's own worker thread drives the chip. A request's
latency runs from the time it was *due* to its result; a shed or failed
request counts as missing. After the window the engine is freed and the
plain reference recomputes a seeded sample of the finished requests.
"""

from __future__ import annotations

import dataclasses
import gc
import threading
import time

import numpy as np

from benchmark import correct as cmp
from benchmark import datagen, harness
from benchmark.drivers.train import to_program_params
from benchmark.harness import log


KEEP_EVENTS = ("dispatch", "warmup", "continuation")


def choose_sample(seed: int, n_requests: int, n_check: int) -> set:
    """The requests whose answers are kept and compared, drawn from the seed."""
    rng = np.random.default_rng([seed, 0x636865636B])
    return set(int(i) for i in rng.choice(n_requests, min(n_check, n_requests),
                                          replace=False))


def build_server(cell: dict, seed: int, writer):
    """(engine, batcher, cfg, scfg): the flagship server as the CLI makes
    it; the configuration file's values are laid over the preset's."""
    from glom_tpu.serve.batcher import DynamicBatcher
    from glom_tpu.serve.cli import engine_device
    from glom_tpu.serve.engine import InferenceEngine
    from glom_tpu.utils.presets import get_preset

    from benchmark.weights import make_weights

    cfgf = cell["config_file"]
    preset = get_preset(cfgf["preset"])
    cfg = dataclasses.replace(preset.model, **cfgf["model"])
    serve = {k: tuple(v) if isinstance(v, list) else v
             for k, v in cfgf["serve"].items()}
    scfg = dataclasses.replace(preset.serve, **serve)
    params = to_program_params(make_weights(seed, cfgf["model"])).glom
    engine = InferenceEngine(cfg, scfg, params=params, writer=writer,
                             device=engine_device(0), name="engine0")
    engine.warmup()
    batcher = DynamicBatcher(engines=[engine], writer=writer)
    return engine, batcher, cfg, scfg


class OpenLoop:
    """Offers `images[i % len]` at `t0 + due[i]`; collects in order."""

    def __init__(self, batcher, images, due, keep: set, drain_s: float):
        self.batcher, self.images, self.due = batcher, images, due
        self.keep, self.drain_s = keep, drain_s
        n = len(due)
        self.late_ms = np.zeros(n)
        self.latency_ms = np.full(n, np.inf)   # due -> result
        self.done_at = np.full(n, np.inf)      # seconds after t0
        self.iters = np.zeros(n, np.int64)
        self.kept = {}                         # i -> float32 [n, L, d]
        self.errors = []
        self._tickets = [None] * n
        self._submitted = threading.Semaphore(0)

    def _collect(self, t0: float):
        for i in range(len(self.due)):
            self._submitted.acquire()
            ticket = self._tickets[i]
            self._tickets[i] = None
            if ticket is None:
                continue
            try:
                levels, iters_run, latency_s = ticket.result(timeout=self.drain_s)
            except Exception as e:  # noqa: BLE001 - a failed request is a count
                self.errors.append(f"{type(e).__name__}: {e}"[:160])
                continue
            done = ticket.t_submit + latency_s - t0
            self.done_at[i] = done
            self.latency_ms[i] = 1e3 * (done - self.due[i])
            self.iters[i] = iters_run
            if i in self.keep:
                self.kept[i] = np.asarray(levels).astype(np.float32)

    def run(self) -> float:
        """Offers every request, waits for the last result; returns t0."""
        import jax

        n_img = len(self.images)
        t0 = time.perf_counter()
        collector = threading.Thread(target=self._collect, args=(t0,),
                                     name="bench-collector", daemon=True)
        collector.start()
        for i, due in enumerate(self.due):
            wait = t0 + due - time.perf_counter()
            if wait > 0:
                with jax.profiler.TraceAnnotation("loadgen_wait_for_due_time"):
                    time.sleep(wait)
            self.late_ms[i] = 1e3 * (time.perf_counter() - t0 - due)
            try:
                with jax.profiler.TraceAnnotation("loadgen_submit"):
                    self._tickets[i] = self.batcher.submit(self.images[i % n_img])
            except Exception as e:  # noqa: BLE001 - shed at admission
                self.errors.append(f"{type(e).__name__}: {e}"[:160])
            self._submitted.release()
        collector.join(timeout=self.drain_s + 30.0)
        if collector.is_alive():
            raise RuntimeError("the collector did not finish: a ticket never resolved")
        return t0


def run(cell: dict, args, clock) -> int:
    import jax

    cfgf, traf = cell["config_file"], cell["traffic_file"]
    model, seed = cfgf["model"], int(args.seed)
    dev = harness.start_jax(cell["chips"])
    counter = harness.CompileCounter()
    writer = harness.Collector(keep=KEEP_EVENTS)
    engine, batcher, cfg, scfg = build_server(cell, seed, writer)
    warmups = [r for r in writer.records if r.get("event") == "warmup"]
    mosaic_ok = bool(warmups) and all(r.get("mosaic_calls", 0) > 0 for r in warmups)
    log(f"warm-up: {len(warmups)} programs, mosaic calls "
        f"{sorted({r.get('mosaic_calls') for r in warmups})}")

    rate = float(traf["rate_per_s"])
    shape = (cfg.channels, cfg.image_size, cfg.image_size)
    images = datagen.serve_images(seed, int(traf["image_pool"]), shape)
    trace_dir = harness.fresh_trace_dir(cell["name"]) if args.trace else None
    # A traced run keeps the last `trace_seconds` of its window for the
    # profiler: opening a trace under load stalls the process for a second or
    # more (PR 23), so the untraced part drains first, the trace opens on an
    # idle server, and the same traffic goes on under it.
    trace_s = float(traf["trace_seconds"]) if args.trace else 0.0
    seconds = float(args.seconds) - trace_s
    due = datagen.arrival_times(seed, rate, seconds)
    keep = choose_sample(seed, len(due), int(traf["check_requests"]))
    n_check = len(keep)

    with batcher:
        # Unmeasured warm-up traffic at the cell's own rate, until a pass
        # compiles nothing: programs the warm-up above does not cover
        # compile on first use, and that belongs to set-up.
        for attempt in range(int(traf["warmup_passes_max"])):
            before = counter.n
            for n in range(1, scfg.max_batch + 1):  # every (bucket, rows) pair
                for t in [batcher.submit(images[j % len(images)]) for j in range(n)]:
                    t.result(timeout=60.0)
            warm_due = datagen.arrival_times(seed + 1 + attempt, rate,
                                             float(traf["warmup_seconds"]))
            OpenLoop(batcher, images, warm_due, set(), 60.0).run()
            log(f"warm-up traffic pass {attempt}: {len(warm_due)} requests, "
                f"{counter.n - before} programs compiled")
            if counter.n == before:
                break
        setup_compiles = counter.n
        writer.records.clear()
        gc.collect()
        loop = OpenLoop(batcher, images, due, keep, float(traf["drain_seconds"]))
        setup_s = clock.since_start()
        t0 = loop.run()
        t_end = time.perf_counter() - t0
        compiles_in_window = counter.n - setup_compiles
        records = list(writer.records)
        if args.trace:
            harness.start_trace(trace_dir)
            try:
                OpenLoop(batcher, images,
                         datagen.arrival_times(seed + 7, rate, trace_s), set(), 60.0).run()
            finally:
                jax.profiler.stop_trace()
        summary = batcher.summary_record()
    peak = harness.memory_peak_bytes(cell["chips"])

    finished = np.isfinite(loop.latency_ms)
    attempted, failed = len(due), int((~finished).sum())
    # A request that was shed or failed misses every limit: its latency
    # stands as the time from its due time to the end of the run, at least.
    lat = np.where(finished, loop.latency_ms, 1e3 * (t_end - due))
    lat_sorted = sorted(float(x) for x in lat)
    in_window = int((loop.done_at <= seconds).sum())
    p50 = harness.quantile(lat_sorted, 0.50)
    p95 = harness.quantile(lat_sorted, 0.95)
    images_per_s = in_window / seconds
    log(f"window {seconds:.1f}s offered {attempted} at {rate}/s, finished "
        f"{int(finished.sum())}, in window {in_window}, failed {failed}; drained at "
        f"{t_end:.3f}s; p50 {p50:.3f} p95 {p95:.3f} max {lat_sorted[-1]:.3f} ms; "
        f"generator late p95 {np.quantile(loop.late_ms, 0.95):.3f} ms; compiles in "
        f"window {compiles_in_window} (set-up {setup_compiles}); peak {peak} B")
    if loop.errors:
        log(f"errors ({len(loop.errors)}): {sorted(set(loop.errors))[:3]}")

    # Free the server, then recompute the sampled requests.
    kept, iters, late_ms = loop.kept, loop.iters, loop.late_ms
    del loop
    engine.release()
    del engine, batcher
    gc.collect()
    jax.clear_caches()
    t_ref = time.perf_counter()
    ref = reference_columns(cell, seed, images, kept, iters)
    verdict = cmp.Verdict()
    verdict.numbers(cmp.serve_numbers([(kept[i], ref[i]) for i in sorted(kept)]),
                    cell["limits"])
    log(f"reference took {time.perf_counter() - t_ref:.2f}s")
    if cfgf["bench"].get("expect_mosaic_calls", True):
        verdict.fact("warmed_programs", "all hold a Mosaic call" if mosaic_ok
                     else "one holds none", "a Mosaic call in each", mosaic_ok)
    verdict.fact("requests_compared", len(kept), f"{max(1, n_check - failed)} or more",
                 len(kept) >= max(1, n_check - failed))

    # Whichever of these the cell's entry lists as end-to-end is reported as
    # such; the latencies are in `ctx` for their per-layer readers as well.
    user_facing = {"serve_images_per_s": images_per_s, "serve_p50_ms": p50,
                   "serve_p95_ms": p95, "setup_s": setup_s}
    return harness.report(
        cell, args, verdict=verdict, attempted=attempted, failed=failed,
        end_to_end={m["name"]: {"value": user_facing[m["name"]], "unit": m["unit"]}
                    for m in cell["end_to_end"]},
        device=dict(dev, memory_peak_bytes=peak),
        ctx={"kind": "serve", "records": records,
             "dispatches": [r for r in records if r.get("event") == "dispatch"],
             "summary": summary, "late_ms": late_ms, "window_s": seconds,
             "p50_ms": p50, "p95_ms": p95,
             "compiles_in_window": compiles_in_window, "peak_bytes": peak,
             "model": model, "chips": cell["chips"], "device_kind": dev["kind"]},
        trace_dir=trace_dir)


def reference_columns(cell: dict, seed: int, images, kept: dict, iters,
                      precision: str = "float32") -> dict:
    """{request index: reference columns} for each sampled request: the
    reference run for the iterations its response reports, from the cold
    start, in blocks. `precision` below float32 makes it the control."""
    import jax.numpy as jnp

    from benchmark.reference import glom_ref
    from benchmark.weights import make_weights

    cfgf = cell["config_file"]
    model = cfgf["model"]
    w = make_weights(seed, model)
    block = int(cfgf["bench"]["reference_block_rows"])
    by_iters = {}
    for i in sorted(kept):
        by_iters.setdefault(int(iters[i]), []).append(i)
    out = {}
    for n_iters, idx in sorted(by_iters.items()):
        for lo in range(0, len(idx), block):
            part = idx[lo:lo + block]
            # pad to the block so that one program serves every block
            rows = part + [part[-1]] * (block - len(part))
            img = jnp.asarray(np.stack([images[i % len(images)] for i in rows]))
            want = np.asarray(glom_ref.serve_reference(w, img, model, n_iters,
                                                       precision=precision))
            out.update((i, want[j]) for j, i in enumerate(part))
    log(f"correct: iterations run by the sampled requests: "
        f"{ {k: len(v) for k, v in sorted(by_iters.items())} }")
    return out
