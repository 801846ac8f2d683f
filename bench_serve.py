"""Serving benchmark: offered-load sweep -> latency percentiles + throughput.

The training benches measure steady-state step time; serving is judged on
the LATENCY DISTRIBUTION under load — p50 is what the median user feels,
p95/p99 are what the SLO is written against, and throughput is what the
fleet bill is written against. This harness drives the real stack
(InferenceEngine + DynamicBatcher, glom_tpu/serve) end to end:

  1. AOT warmup of every bucket (compile time per bucket on the record —
     the cliff warmup exists to remove);
  2. a closed-loop ceiling measurement: back-to-back full-bucket
     dispatches -> max sustainable requests/sec;
  3. an open-loop offered-load sweep at fractions of that ceiling:
     requests submitted at the offered rate through the batcher, per-
     request latency collected from tickets -> p50/p95/p99 + achieved
     throughput per load point (StepTimeStats percentiles);
  4. with iters="auto": the early-exit iteration histogram — how many
     column updates requests ACTUALLY ran vs the fixed budget;
  5. with --two-tier-ab: the two-tier A/B — heterogeneous synthetic
     traffic (easy requests converge in ~B-3 iterations, hard 100x-scale
     requests near the budget B; --hetero sets the hard fraction) served
     under batch-level exit (quorum 1.0, no continuations) vs two-tier
     exit (quorum + continuation queue), emitting the per-request
     executed-iters histogram SPLIT BY TIER and the mean-executed-iters
     rows the reduction claim is measured by (docs/SERVING.md).

--engines N fans the batcher out over N engine replicas (shared params,
shared admission); --mesh-data/--mesh-seq route every bucket through the
sharded shard_map forward (parallel/serve_mesh.py).

Rows ride sinks.emit / bench_bootstrap like every other bench: UNMEASURED
is an "error" record with value null (never a dead zero), every row stamps
the watchdog backend state, and the output lints with
`python -m glom_tpu.telemetry FILE` and gates with `... compare`
(run_hw_queue.sh serve steps).
"""

from __future__ import annotations

import argparse
import json
import time


def _make_engines(cfg, scfg, n_engines: int):
    import jax

    from glom_tpu.serve.engine import InferenceEngine

    params = None
    if n_engines > 1 or scfg.mesh_data > 1 or scfg.mesh_seq > 1:
        from glom_tpu.models.core import init_glom

        params = init_glom(jax.random.PRNGKey(0), cfg)
    if scfg.mesh_data > 1 or scfg.mesh_seq > 1:
        from glom_tpu.parallel.runtime import make_engine_meshes

        meshes = make_engine_meshes(scfg, n_engines)
    else:
        meshes = [None] * n_engines
    return [
        InferenceEngine(
            cfg, scfg, params=params, mesh=meshes[i], name=f"engine{i}"
        )
        for i in range(n_engines)
    ]


def run_sweep(cfg, scfg, label: str, *, n_requests: int, load_fracs,
              ceiling_repeats: int, n_engines: int = 1) -> None:
    import numpy as np

    from glom_tpu.serve.batcher import DynamicBatcher, ShedError
    from glom_tpu.telemetry.sinks import StepTimeStats, emit

    engines = _make_engines(cfg, scfg, n_engines)
    engine = engines[0]
    for eng in engines:
        for bucket, dt in eng.warmup().items():
            emit(
                {"event": "warmup", "engine": eng.name, "bucket": bucket,
                 "compile_time_s": round(dt, 4), "config": label},
                kind="serve",
            )

    top = max(scfg.buckets)
    rng = np.random.default_rng(0)
    imgs = rng.normal(size=(top, cfg.channels, cfg.image_size, cfg.image_size)
                      ).astype(np.float32)

    # 2. Closed-loop ceiling: back-to-back full buckets, min over repeats
    # (jitter only ever slows things down — bench.py's convention). One
    # engine's ceiling; N engines admit up to N x this.
    per_batch = min(
        engine.infer(imgs, n_valid=top).latency_s
        for _ in range(ceiling_repeats)
    )
    ceiling = top / per_batch * n_engines
    emit(
        {
            "metric": f"serve_throughput_ceiling ({label})",
            "value": round(ceiling, 2),
            "unit": "req/s",
            "bucket": top,
            "engines": n_engines,
            "batch_latency_ms": round(1e3 * per_batch, 3),
        }
    )

    # 3. Open-loop offered-load sweep through the batcher.
    for frac in load_fracs:
        rate = max(ceiling * frac, 1e-6)
        stats = StepTimeStats()
        stats.observe(0.0, is_compile=True)  # no compile phase here
        served = shed = 0
        t0 = time.perf_counter()
        with DynamicBatcher(engines=engines) as batcher:
            tickets = []
            for i in range(n_requests):
                target = t0 + i / rate
                delay = target - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                try:
                    tickets.append(batcher.submit(imgs[i % top]))
                except ShedError:
                    shed += 1
            for t in tickets:
                try:
                    _, _, latency_s = t.result(timeout=600.0)
                except Exception:
                    shed += 1
                    continue
                served += 1
                stats.observe(latency_s, is_compile=False)
        wall = time.perf_counter() - t0
        s = stats.summary()
        base = f"load={frac:.2f}x, {label}"
        if served == 0:
            # Every request shed or failed: these rows are UNMEASURED —
            # kind "error", value null — never the 0.0ms/0rps dead zeros
            # the compare gate would read as a massive improvement.
            for name, unit in (
                (f"serve_p50_latency ({base})", "ms"),
                (f"serve_p95_latency ({base})", "ms"),
                (f"serve_p99_latency ({base})", "ms"),
                (f"serve_throughput ({base})", "req/s"),
            ):
                emit(
                    {
                        "metric": name,
                        "value": None,
                        "unit": unit,
                        "error": "no-requests-served",
                        "note": f"UNMEASURED: all {n_requests} requests "
                        f"shed or failed ({shed} shed)",
                    },
                    kind="error",
                )
            emit(dict(batcher.summary_record(), config=base), kind="serve")
            continue
        emit(
            {
                "metric": f"serve_p50_latency ({base})",
                "value": s["step_time_p50_ms"],
                "unit": "ms",
                "offered_rps": round(rate, 2),
                "served": served,
                "shed": shed,
            }
        )
        emit(
            {
                "metric": f"serve_p95_latency ({base})",
                "value": s["step_time_p95_ms"],
                "unit": "ms",
            }
        )
        emit(
            {
                "metric": f"serve_p99_latency ({base})",
                "value": s["step_time_p99_ms"],
                "unit": "ms",
            }
        )
        emit(
            {
                "metric": f"serve_throughput ({base})",
                "value": round(served / wall, 2) if wall > 0 else 0.0,
                "unit": "req/s",
            }
        )
        # The batcher's own evidence: dispatch mix + iteration histogram.
        emit(dict(batcher.summary_record(), config=base), kind="serve")

    # 4. Early-exit accounting (only meaningful on the auto route).
    # Genuinely closed-loop: submit in windows no larger than half the
    # queue and drain each window before the next, so --requests beyond
    # queue_depth cannot overrun the bounded queue; a failed request
    # drops one sample, never the histogram rows the gate expects.
    if engine.iters_key == "auto":
        iters = []
        window = max(1, min(scfg.queue_depth // 2, 32))
        with DynamicBatcher(engines=engines) as batcher:
            for start in range(0, n_requests, window):
                tickets = []
                for i in range(start, min(start + window, n_requests)):
                    try:
                        tickets.append(batcher.submit(imgs[i % top]))
                    except ShedError:
                        continue
                for t in tickets:
                    try:
                        _, iters_run, _ = t.result(timeout=600.0)
                    except Exception:
                        continue
                    iters.append(iters_run)
        budget = engine.auto_budget
        if iters:
            hist: dict = {}
            for it in iters:
                hist[str(it)] = hist.get(str(it), 0) + 1
            emit(
                {
                    "event": "iter_histogram",
                    "config": label,
                    "budget": budget,
                    "histogram": hist,
                    "n": len(iters),
                },
                kind="serve",
            )
            emit(
                {
                    "metric": f"serve_auto_mean_iters ({label})",
                    "value": round(sum(iters) / len(iters), 3),
                    "unit": "iters/request",
                    "budget": budget,
                }
            )
        else:
            emit(
                {
                    "metric": f"serve_auto_mean_iters ({label})",
                    "value": None,
                    "unit": "iters/request",
                    "error": "no-requests-served",
                    "note": "UNMEASURED: early-exit pass served nothing",
                },
                kind="error",
            )
    for eng in engines:
        for rec in eng.stats_records():
            emit(dict(rec, config=label), kind="serve")
        for rec in eng.collective_time_records():
            # Per-collective wall-time evidence (sharded route, timing
            # on): already stamped kind "collective_time" — printed next
            # to the bucket stats so the compare gate sees the wall_ms
            # cost rows (docs/OBSERVABILITY.md, Capacity observatory).
            print(json.dumps(dict(rec, config=label)), flush=True)


def run_two_tier_ab(cfg, scfg, label: str, *, n_requests: int,
                    hard_frac: float, n_engines: int = 1,
                    quorum: float = 0.5, continuations: int = 3) -> dict:
    """Batch-level vs two-tier exit over HETEROGENEOUS traffic: the same
    request stream (easy gaussian images interleaved with hard 100x-scale
    ones — far from the consensus attractor, they converge near the
    budget) served under both exit policies, with the per-request
    executed-iters histogram split by tier. Returns {arm: mean} so CI can
    assert the reduction as a measured number, not a claim."""
    import dataclasses

    import numpy as np

    from glom_tpu.serve.batcher import DynamicBatcher, ShedError
    from glom_tpu.telemetry.sinks import emit

    if scfg.iters != "auto":
        emit(
            {"note": "two-tier A/B skipped: the configured route is not "
             "iters='auto' (no witness, no stragglers)"},
            kind="note",
        )
        return {}
    rng = np.random.default_rng(7)
    shape = (cfg.channels, cfg.image_size, cfg.image_size)
    n_hard = int(round(hard_frac * n_requests))
    hard_idx = (
        set(np.linspace(0, n_requests - 1, n_hard).astype(int).tolist())
        if n_hard
        else set()
    )
    imgs = []
    for i in range(n_requests):
        img = rng.normal(size=shape).astype(np.float32)
        if i in hard_idx:
            img *= 100.0
        imgs.append(img)

    arms = (
        ("batch-level", dataclasses.replace(
            scfg, exit_quorum=1.0, max_continuations=0)),
        ("two-tier", dataclasses.replace(
            scfg, exit_quorum=quorum, max_continuations=continuations)),
    )
    means: dict = {}
    for arm, arm_scfg in arms:
        engines = _make_engines(cfg, arm_scfg, n_engines)
        for eng in engines:
            eng.warmup()
        window = max(2, min(arm_scfg.max_batch, arm_scfg.queue_depth // 2))
        got = 0
        with DynamicBatcher(engines=engines) as batcher:
            for start in range(0, n_requests, window):
                tickets = []
                for i in range(start, min(start + window, n_requests)):
                    try:
                        tickets.append(batcher.submit(imgs[i]))
                    except ShedError:
                        continue
                for t in tickets:
                    try:
                        t.result(timeout=600.0)
                        got += 1
                    except Exception:
                        continue
            summary = batcher.summary_record()
        mean = summary.get("mean_executed_iters")
        emit(
            {
                "event": "iter_histogram_tiered",
                "arm": arm,
                "config": label,
                "budget": engines[0].auto_budget,
                "quorum": arm_scfg.exit_quorum,
                "max_continuations": arm_scfg.max_continuations,
                "hard_frac": hard_frac,
                "histogram_by_tier": summary["iters_histogram_by_tier"],
                "n_continued": summary["n_continued"],
                "n": got,
            },
            kind="serve",
        )
        if mean is None:
            emit(
                {
                    "metric": f"serve_mean_executed_iters ({arm}, {label})",
                    "value": None,
                    "unit": "iters/request",
                    "error": "no-requests-served",
                    "note": f"UNMEASURED: {arm} arm served nothing",
                },
                kind="error",
            )
        else:
            means[arm] = mean
            emit(
                {
                    "metric": f"serve_mean_executed_iters ({arm}, {label})",
                    "value": mean,
                    "unit": "iters/request",
                    "hard_frac": hard_frac,
                    "served": got,
                }
            )
    return means


def run_temporal(cfg, scfg, label: str, *, n_streams: int, n_frames: int,
                 perturb: float, n_engines: int = 1) -> dict:
    """Frame-sequence (streaming) traffic: warm-start vs cold-start A/B.

    S streams, F frames each; every frame is a small perturbation of its
    stream's base image (hard 100x-scale bases — the convergence-depth
    lever from the hetero mode, so a cold start runs near the budget).
    Frames advance in lockstep rounds — frame t of every stream resolves
    before frame t+1 submits, the temporal contract a video frontend
    provides — and the same traffic is served twice:

      * cold — column cache disabled: every frame pays full convergence;
      * warm — cache sized for all S streams: frame t+1 dispatches from
        frame t's converged columns (the engine's warm levels0 route).

    The measured number is mean executed column-iters/request per arm
    (`serve_temporal_mean_iters`); the warm arm's summary additionally
    carries the cache rollup, whose `bytes_peak <= budget_bytes` the CI
    gate asserts. Returns {arm: mean} so CI can assert warm < cold as a
    measured fact."""
    import dataclasses

    import numpy as np

    from glom_tpu.serve.batcher import DynamicBatcher, ShedError
    from glom_tpu.serve.column_cache import column_state_bytes
    from glom_tpu.telemetry.sinks import emit

    if scfg.iters != "auto":
        emit(
            {"note": "temporal A/B skipped: the configured route is not "
             "iters='auto' (a fixed budget saves no iterations warm)"},
            kind="note",
        )
        return {}
    rng = np.random.default_rng(11)
    shape = (cfg.channels, cfg.image_size, cfg.image_size)
    bases = [
        (100.0 * rng.normal(size=shape)).astype(np.float32)
        for _ in range(n_streams)
    ]
    frames = [
        [
            (bases[s] + perturb * rng.normal(size=shape)).astype(np.float32)
            for _ in range(n_frames)
        ]
        for s in range(n_streams)
    ]

    budget_bytes = (n_streams + 1) * column_state_bytes(cfg, scfg)
    arms = (
        ("cold", dataclasses.replace(scfg, column_cache_bytes=0)),
        ("warm", dataclasses.replace(scfg, column_cache_bytes=budget_bytes)),
    )
    means: dict = {}
    for arm, arm_scfg in arms:
        engines = _make_engines(cfg, arm_scfg, n_engines)
        for eng in engines:
            eng.warmup()
        served = 0
        with DynamicBatcher(engines=engines) as batcher:
            for f in range(n_frames):
                tickets = []
                for s in range(n_streams):
                    try:
                        tickets.append(
                            batcher.submit(frames[s][f], session_id=f"s{s}")
                        )
                    except ShedError:
                        continue
                for t in tickets:
                    try:
                        t.result(timeout=600.0)
                        served += 1
                    except Exception:
                        continue
            summary = batcher.summary_record()
        mean = summary.get("mean_executed_iters")
        emit(
            {
                "event": "temporal_summary",
                "arm": arm,
                "config": label,
                "budget": engines[0].auto_budget,
                "n_streams": n_streams,
                "n_frames": n_frames,
                "perturb": perturb,
                "n": served,
                "iters_histogram": summary["iters_histogram"],
                "column_cache": summary.get("column_cache"),
            },
            kind="serve",
        )
        if mean is None:
            emit(
                {
                    "metric": f"serve_temporal_mean_iters ({arm}, {label})",
                    "value": None,
                    "unit": "iters/request",
                    "error": "no-requests-served",
                    "note": f"UNMEASURED: temporal {arm} arm served nothing",
                },
                kind="error",
            )
        else:
            means[arm] = mean
            emit(
                {
                    "metric": f"serve_temporal_mean_iters ({arm}, {label})",
                    "value": mean,
                    "unit": "iters/request",
                    "n_streams": n_streams,
                    "n_frames": n_frames,
                    "served": served,
                }
            )
    if "cold" in means and "warm" in means and means["cold"] > 0:
        emit(
            {
                "metric": f"serve_temporal_iters_saved ({label})",
                "value": round(
                    100.0 * (1.0 - means["warm"] / means["cold"]), 2
                ),
                "unit": "%",
                "cold_mean": means["cold"],
                "warm_mean": means["warm"],
            }
        )
    return means


def run_temporal_delta(cfg, scfg, label: str, *, n_streams: int,
                       n_frames: int, cameras: int, perturb: float,
                       period: int, atol: float) -> dict:
    """Delta-encoded streaming A/B (ISSUE 12, docs/SERVING.md "Delta
    streaming"): whole-state paged warm vs delta-chain + incremental.

    The traffic is O(1)-shaped video: `cameras` streams per SCENE share
    an identical first frame (the cross-stream base-sharing case — N
    cameras, one scene), and after that each camera's frames alternate
    HOLDS (bitwise-identical — most frames at video rate) with a small
    REGION perturbation every `period` frames (one patch of the canvas —
    the moving object). The same traffic is served twice:

      * whole-state — the PR 11 paged warm route: every write-back
        rewrites the session's whole page block, every warm frame runs
        the full-width tiered exit;
      * delta — write-backs store only the pages whose column residual
        exceeds `delta_page_atol` (the stamped tolerance), bases alias
        across cameras, and warm frames ride the INCREMENTAL route
        seeded from the input delta's support (holds pay the min_iters
        floor).

    Measured rows: `serve_delta_mean_iters` per arm (the <2 acceptance),
    `serve_delta_bytes_per_stream` per arm (actual pool pages per live
    stream — the >=3x acceptance), and `serve_delta_parity` (a
    threshold-0/atol-0 probe asserting base+Σdeltas reconstruction is
    BITWISE the whole-state warm dispatch). Returns {arm: mean_iters}."""
    import dataclasses

    import numpy as np

    from glom_tpu.serve.batcher import DynamicBatcher, ShedError
    from glom_tpu.serve.paged_columns import (
        pages_for_tokens,
        resolve_page_tokens,
    )
    from glom_tpu.telemetry.sinks import emit

    if scfg.iters != "auto":
        emit(
            {"note": "delta A/B skipped: the configured route is not "
             "iters='auto' (no exit to seed incrementally)"},
            kind="note",
        )
        return {}
    cameras = cameras if cameras > 0 else n_streams
    # Page granularity: ONE page per patch row of the canvas keeps the
    # delta support sharp (the perturbed patch is exactly one page).
    pt = 1 if cfg.num_patches <= 64 else resolve_page_tokens(cfg, scfg)
    ppr = pages_for_tokens(cfg.num_patches, pt)
    pool_pages = (n_streams + 4) * ppr
    top = max(8, n_streams)
    common = dict(
        buckets=(1, 2, 4, top) if top > 4 else (1, 2, 4),
        max_batch=top, max_delay_ms=2.0,
        page_pool_pages=pool_pages, page_tokens=pt,
        column_cache_bytes=(n_streams + 2) * ppr
        * pt * cfg.levels * cfg.dim
        * (2 if scfg.compute_dtype == "bfloat16" else 4),
        max_continuations=0, mesh_data=1, mesh_seq=1,
    )
    arms = (
        ("whole-state", dataclasses.replace(
            scfg, **common, delta_streaming=False)),
        ("delta", dataclasses.replace(
            scfg, **common, delta_streaming=True,
            delta_page_atol=atol, delta_chain_cap=4,
            delta_incremental=True, delta_base_share=True)),
    )
    rng = np.random.default_rng(17)
    p = cfg.patch_size
    shape = (cfg.channels, cfg.image_size, cfg.image_size)
    n_scenes = -(-n_streams // cameras)
    scene_base = [
        (100.0 * rng.normal(size=shape)).astype(np.float32)
        for _ in range(n_scenes)
    ]
    # Per-camera frame sequences: frame 0 is the scene base VERBATIM
    # (content-identical converged columns -> shared base pages); later
    # frames perturb one patch-sized region every `period` frames and
    # HOLD (bitwise) otherwise.
    frames = []
    for s in range(n_streams):
        seq = [scene_base[s // cameras]]
        for f in range(1, n_frames):
            if (f - 1) % period == 0:
                img = seq[-1].copy()
                img[:, 0:p, 0:p] += (
                    perturb * 100.0 * rng.normal(size=(cfg.channels, p, p))
                ).astype(np.float32)
                seq.append(img)
            else:
                seq.append(seq[-1])
        frames.append(seq)

    means: dict = {}
    bytes_per_stream: dict = {}
    for arm, arm_scfg in arms:
        engines = _make_engines(cfg, arm_scfg, 1)
        engines[0].warmup()
        served = 0
        with DynamicBatcher(engines=engines) as batcher:
            for f in range(n_frames):
                tickets = []
                for s in range(n_streams):
                    try:
                        tickets.append(
                            batcher.submit(frames[s][f], session_id=f"s{s}")
                        )
                    except ShedError:
                        continue
                for t in tickets:
                    try:
                        t.result(timeout=600.0)
                        served += 1
                    except Exception:
                        continue
            summary = batcher.summary_record()
        pool_rec = summary.get("page_pools", {}).get("engine0", {})
        bps = (
            round(pool_rec["bytes_in_use"] / pool_rec["n_sessions"], 1)
            if pool_rec.get("n_sessions")
            else None
        )
        mean = summary.get("mean_executed_iters")
        emit(dict(summary, config=f"{arm}, {label}"), kind="serve")
        emit(
            {
                "event": "delta_summary",
                "arm": arm,
                "config": label,
                "budget": engines[0].auto_budget,
                "n_streams": n_streams,
                "n_frames": n_frames,
                "cameras": cameras,
                "period": period,
                "delta_page_atol": atol if arm == "delta" else None,
                "n": served,
                "n_incremental": summary.get("n_incremental"),
                "column_cache": summary.get("column_cache"),
            },
            kind="serve",
        )
        for metric, value, unit in (
            (f"serve_delta_mean_iters ({arm}, {label})", mean,
             "iters/request"),
            (f"serve_delta_bytes_per_stream ({arm}, {label})", bps,
             "bytes"),
        ):
            if value is None:
                emit(
                    {
                        "metric": metric, "value": None, "unit": unit,
                        "error": "no-requests-served",
                        "note": f"UNMEASURED: delta A/B {arm} arm served "
                        "nothing",
                    },
                    kind="error",
                )
            else:
                emit(
                    {
                        "metric": metric, "value": value, "unit": unit,
                        "served": served,
                        "delta_page_atol": atol if arm == "delta" else None,
                    }
                )
        if mean is not None:
            means[arm] = mean
        if bps is not None:
            bytes_per_stream[arm] = bps

    # Threshold-0 / atol-0 parity probe: base+Σdeltas reconstruction must
    # be BITWISE the whole-state warm dispatch (the exactness contract
    # the test suite locks; CI reads this row as a 1.0-or-fail gate).
    probe_scfg = dataclasses.replace(
        arms[1][1], iters="auto", exit_threshold=0.0, delta_page_atol=0.0,
        max_auto_iters=4,
    )
    eng = _make_engines(cfg, probe_scfg, 1)[0]
    img1 = frames[0][0][None]
    lv1 = np.asarray(eng.infer(img1, n_valid=1).levels)[0]
    eng.pool.write_back_stream("d", lv1, cfg.num_patches)
    eng.pool.write_back("w", lv1, cfg.num_patches)

    def _warm(sid, img):
        prow = np.asarray([eng.pool.lookup(sid)[0]], np.int32)
        return np.asarray(eng.infer(img, n_valid=1, page_rows=prow).levels)[0]

    img2 = img1 + 0.05 * rng.normal(size=img1.shape).astype(np.float32)
    out_d, out_w = _warm("d", img2), _warm("w", img2)
    eng.pool.write_back_stream("d", out_d, cfg.num_patches)
    eng.pool.write_back("w", out_w, cfg.num_patches)
    img3 = img2 + 0.05 * rng.normal(size=img1.shape).astype(np.float32)
    bitwise = bool(
        np.array_equal(out_d, out_w)
        and np.array_equal(_warm("d", img3), _warm("w", img3))
    )
    emit(
        {
            "metric": f"serve_delta_parity ({label})",
            "value": 1.0 if bitwise else 0.0,
            "unit": "bool",
            "note": "threshold-0/atol-0 base+deltas reconstruction vs "
            "whole-state warm dispatch, bitwise",
            "chain_len": eng.pool.delta_chain_len("d"),
        }
    )
    if "whole-state" in bytes_per_stream and "delta" in bytes_per_stream:
        emit(
            {
                "metric": f"serve_delta_bytes_ratio ({label})",
                "value": round(
                    bytes_per_stream["whole-state"]
                    / max(bytes_per_stream["delta"], 1e-9),
                    2,
                ),
                "unit": "x",
                "whole_state": bytes_per_stream["whole-state"],
                "delta": bytes_per_stream["delta"],
            }
        )
    return means


def run_ragged(cfg, scfg, label: str, *, n_streams: int, n_frames: int,
               perturb: float) -> dict:
    """Mixed-resolution sweep: the ragged paged route vs the bucket
    ladder (docs/SERVING.md, "Paged column memory" / "Ragged admission").

    S streams at CYCLING resolutions (full, 3/4, 1/2 of the canvas — the
    new workload class: mixed resolutions/aspect ratios), F frames each,
    hard 100x-scale bases plus a small per-frame perturbation. The same
    traffic is served twice:

      * bucket-ladder — every image PADDED host-side to the full canvas
        and row-padded to a bucket shape; warm frames ride the PR 8
        host-array cache (levels0 re-uploaded per warm dispatch);
      * ragged-paged — native resolutions packed page-aligned onto the
        ragged page ladder; warm frames take pool pages IN-GRAPH (zero
        levels0 upload).

    The measured numbers: `serve_pad_waste` per arm (true useful tokens
    over dispatched token slots — the bucket arm's canvas padding counts
    as waste, because the MXU multiplies it), warm/cold dispatch latency
    per arm, and `serve_levels0_h2d_bytes` per arm (the ragged arm's
    MUST be zero — the CI gate asserts both claims). Returns
    {arm: pad_waste_pct}."""
    import dataclasses

    import numpy as np

    from glom_tpu.serve.batcher import DynamicBatcher, ShedError
    from glom_tpu.serve.column_cache import column_state_bytes
    from glom_tpu.serve.paged_columns import (
        pages_for_tokens,
        resolve_page_tokens,
    )
    from glom_tpu.telemetry.sinks import emit

    if scfg.iters != "auto":
        emit(
            {"note": "ragged sweep skipped: the configured route is not "
             "iters='auto' (warm frames save nothing on a fixed budget)"},
            kind="note",
        )
        return {}
    rng = np.random.default_rng(13)
    p = cfg.patch_size
    side = cfg.image_size
    # Cycling resolutions: full, ~3/4, ~1/2 of the canvas, rounded to
    # patch multiples (all >= one patch).
    sizes = sorted(
        {max(p, (side * f // (4 * p)) * p) for f in (4, 3, 2)}, reverse=True
    )
    stream_size = [sizes[s % len(sizes)] for s in range(n_streams)]
    bases = [
        (100.0 * rng.normal(size=(cfg.channels, hw, hw))).astype(np.float32)
        for hw in stream_size
    ]
    frames = [
        [
            (bases[s] + perturb * rng.normal(size=bases[s].shape)).astype(
                np.float32
            )
            for _ in range(n_frames)
        ]
        for s in range(n_streams)
    ]
    n_tokens = [(hw // p) ** 2 for hw in stream_size]
    useful = sum(n_tokens) * n_frames

    pt = resolve_page_tokens(cfg, scfg)
    ppr = pages_for_tokens(cfg.num_patches, pt)
    cache_bytes = (n_streams + 1) * column_state_bytes(cfg, scfg)
    pool_pages = (n_streams + 2) * ppr
    arms = (
        ("bucket-ladder", dataclasses.replace(
            scfg, ragged=False, page_pool_pages=0, max_continuations=0,
            column_cache_bytes=cache_bytes)),
        ("ragged-paged", dataclasses.replace(
            scfg, ragged=True, page_pool_pages=pool_pages, page_tokens=pt,
            max_continuations=0, column_cache_bytes=cache_bytes)),
    )
    waste: dict = {}
    for arm, arm_scfg in arms:
        engines = _make_engines(cfg, arm_scfg, 1)
        engine = engines[0]
        if arm == "ragged-paged":
            engine.warmup_ragged()
        else:
            engine.warmup()
        served = 0
        with DynamicBatcher(engines=engines) as batcher:
            for f in range(n_frames):
                tickets = []
                for s in range(n_streams):
                    img = frames[s][f]
                    if arm == "bucket-ladder":
                        # The pad tax, literally: embed the small image
                        # into the full canvas (zeros elsewhere) so the
                        # fixed-shape engine can serve it at all.
                        canvas = np.zeros(
                            (cfg.channels, side, side), np.float32
                        )
                        canvas[:, : img.shape[1], : img.shape[2]] = img
                        img = canvas
                    try:
                        tickets.append(
                            batcher.submit(img, session_id=f"s{s}")
                        )
                    except ShedError:
                        continue
                for t in tickets:
                    try:
                        t.result(timeout=600.0)
                        served += 1
                    except Exception:
                        continue
            summary = batcher.summary_record()
            dispatches = list(batcher.dispatches)
        # True token-slot accounting per arm: the bucket arm's slots are
        # bucket x full-resolution patches (canvas padding included);
        # the ragged arm's are its page-aligned totals.
        if arm == "ragged-paged":
            slots = sum(
                d["n_pages"] * pt for d in dispatches if d.get("ragged")
            )
        else:
            slots = sum(d["bucket"] * cfg.num_patches for d in dispatches)
        pct = round(100.0 * (1.0 - useful / slots), 2) if slots else None
        warm_lat = [
            d["latency_ms"] for d in dispatches
            if d.get("n_cache_warm", 0) or d.get("n_page_warm", 0)
        ]
        cold_lat = [
            d["latency_ms"] for d in dispatches
            if not (d.get("n_cache_warm", 0) or d.get("n_page_warm", 0))
        ]
        emit(dict(summary, config=f"{arm}, {label}"), kind="serve")
        if pct is None:
            emit(
                {
                    "metric": f"serve_pad_waste ({arm}, {label})",
                    "value": None,
                    "unit": "percent",
                    "error": "no-requests-served",
                    "note": f"UNMEASURED: ragged sweep {arm} served nothing",
                },
                kind="error",
            )
            continue
        waste[arm] = pct
        emit(
            {
                "metric": f"serve_pad_waste ({arm}, {label})",
                "value": pct,
                "unit": "percent",
                "useful_tokens": useful,
                "slot_tokens": slots,
                "served": served,
            }
        )
        emit(
            {
                "metric": f"serve_levels0_h2d_bytes ({arm}, {label})",
                "value": summary["levels0_h2d_bytes"],
                "unit": "bytes",
                "n_page_warm": summary["n_page_warm"],
            }
        )
        for name, lat in (("warm", warm_lat), ("cold", cold_lat)):
            if lat:
                emit(
                    {
                        "metric": (
                            f"serve_{name}_dispatch_ms ({arm}, {label})"
                        ),
                        "value": round(sum(lat) / len(lat), 3),
                        "unit": "ms",
                        "n_dispatches": len(lat),
                    }
                )
        mean = summary.get("mean_executed_iters")
        if mean is not None:
            emit(
                {
                    "metric": f"serve_ragged_mean_iters ({arm}, {label})",
                    "value": mean,
                    "unit": "iters/request",
                }
            )
    if "bucket-ladder" in waste and "ragged-paged" in waste:
        # Informational (kind "note", not a gated bench row: a LARGER
        # saving is better, which the cost-unit heuristics would read
        # backwards — the per-arm serve_pad_waste rows are what gate).
        emit(
            {
                "note": "ragged pad-waste saving",
                "config": label,
                "saved_pct_points": round(
                    waste["bucket-ladder"] - waste["ragged-paged"], 2
                ),
                "bucket_ladder_pct": waste["bucket-ladder"],
                "ragged_paged_pct": waste["ragged-paged"],
            },
            kind="note",
        )
    return waste


def run_banded_ab(cfg, scfg, label: str, *, n_streams: int, n_frames: int,
                  perturb: float) -> dict:
    """Block-banded consensus vs the windowed gather, and aliased vs
    copy-on-write pool write-backs, over the SAME ragged streamed
    traffic (docs/SERVING.md, "Block-banded ragged consensus" / "Pool
    aliasing").

    Three arms serve identical mixed-resolution frame streams through
    the ragged paged route:

      * windowed     — the per-token W-fold k/v gather, CoW pool writes;
      * banded       — the per-page block-banded route, CoW pool writes;
      * banded-alias — banded attention + in-place pool aliasing.

    The measured numbers: `serve_ragged_peak_window_bytes` per arm (the
    duplicated k/v working set at the largest DISPATCHED signature —
    banded must sit strictly below windowed: the gate's cost row),
    `serve_ragged_max_signature_pages` per arm (the largest signature
    the windowed arm's top-of-ladder byte budget admits — it must
    strictly GROW under banded), `serve_pool_bytes_moved` per arm
    (aliased write-backs must move fewer bytes than CoW),
    `serve_levels0_h2d_bytes` per arm (zero on the pool warm path,
    aliasing or not), and the threshold-0 `serve_banded_parity` row: one
    mixed dispatch through both attentions, compared BITWISE on every
    row's page span — the 1.0-or-fail gate (unused trailing pages sit
    outside the contract; tests/test_banded_alias.py). Returns
    {arm: peak_window_bytes}."""
    import dataclasses

    import numpy as np

    from glom_tpu.serve.batcher import DynamicBatcher, ShedError
    from glom_tpu.serve.column_cache import column_state_bytes
    from glom_tpu.serve.early_exit import ragged_window_bytes
    from glom_tpu.serve.paged_columns import (
        pages_for_tokens,
        resolve_page_tokens,
    )
    from glom_tpu.telemetry.sinks import emit

    if scfg.iters != "auto":
        emit(
            {"note": "banded A/B skipped: the configured route is not "
             "iters='auto' (the ragged warm path needs the auto route)"},
            kind="note",
        )
        return {}
    rng = np.random.default_rng(17)
    p = cfg.patch_size
    side = cfg.image_size
    sizes = sorted(
        {max(p, (side * f // (4 * p)) * p) for f in (4, 3, 2)}, reverse=True
    )
    stream_size = [sizes[s % len(sizes)] for s in range(n_streams)]
    bases = [
        (100.0 * rng.normal(size=(cfg.channels, hw, hw))).astype(np.float32)
        for hw in stream_size
    ]
    frames = [
        [
            (bases[s] + perturb * rng.normal(size=bases[s].shape)).astype(
                np.float32
            )
            for _ in range(n_frames)
        ]
        for s in range(n_streams)
    ]

    pt = resolve_page_tokens(cfg, scfg)
    ppr = pages_for_tokens(cfg.num_patches, pt)
    window = ppr * pt
    itemsize = 2 if scfg.compute_dtype == "bfloat16" else 4
    cache_bytes = (n_streams + 1) * column_state_bytes(cfg, scfg)
    ragged_base = dict(
        ragged=True, page_pool_pages=(n_streams + 2) * ppr, page_tokens=pt,
        max_continuations=0, column_cache_bytes=cache_bytes,
    )
    arms = (
        ("windowed", dataclasses.replace(
            scfg, ragged_attention="windowed", **ragged_base)),
        ("banded", dataclasses.replace(
            scfg, ragged_attention="banded", **ragged_base)),
        ("banded-alias", dataclasses.replace(
            scfg, ragged_attention="banded", pool_aliasing=True,
            **ragged_base)),
    )
    peak: dict = {}
    for arm, arm_scfg in arms:
        attention = "windowed" if arm == "windowed" else "banded"
        engines = _make_engines(cfg, arm_scfg, 1)
        engine = engines[0]
        engine.warmup_ragged()
        top_pages = max(engine.ragged_page_buckets)
        served = 0
        with DynamicBatcher(engines=engines) as batcher:
            for f in range(n_frames):
                tickets = []
                for s in range(n_streams):
                    try:
                        tickets.append(
                            batcher.submit(frames[s][f], session_id=f"s{s}")
                        )
                    except ShedError:
                        continue
                for t in tickets:
                    try:
                        t.result(timeout=600.0)
                        served += 1
                    except Exception:
                        continue
            summary = batcher.summary_record()
            dispatches = list(batcher.dispatches)
        pool_rec = engine.pool.record() if engine.pool is not None else {}
        sig_pages = [d["n_pages"] for d in dispatches if d.get("ragged")]
        emit(dict(summary, config=f"{arm}, {label}"), kind="serve")
        if not sig_pages:
            emit(
                {
                    "metric": (
                        f"serve_ragged_peak_window_bytes ({arm}, {label})"
                    ),
                    "value": None,
                    "unit": "bytes",
                    "error": "no-requests-served",
                    "note": f"UNMEASURED: banded A/B {arm} served nothing",
                },
                kind="error",
            )
            continue
        peak[arm] = ragged_window_bytes(
            max(sig_pages) * pt, window, cfg.levels, cfg.dim, itemsize,
            pt, attention=attention,
        )
        emit(
            {
                "metric": (
                    f"serve_ragged_peak_window_bytes ({arm}, {label})"
                ),
                "value": peak[arm],
                "unit": "bytes",
                "peak_signature_pages": max(sig_pages),
                "window": window,
                "served": served,
            }
        )
        # The admission headroom the smaller working set buys: the
        # largest signature whose duplicated k/v set still fits the
        # WINDOWED route's budget at its top-of-ladder signature. Both
        # routes are linear in the page count, so one page prices the
        # whole ladder.
        budget = ragged_window_bytes(
            top_pages * pt, window, cfg.levels, cfg.dim, itemsize, pt,
            attention="windowed",
        )
        per_page = ragged_window_bytes(
            pt, window, cfg.levels, cfg.dim, itemsize, pt,
            attention=attention,
        )
        emit(
            {
                "metric": (
                    f"serve_ragged_max_signature_pages ({arm}, {label})"
                ),
                "value": budget // per_page,
                "unit": "pages",
                "byte_budget": budget,
                "bytes_per_page": per_page,
            }
        )
        emit(
            {
                "metric": f"serve_levels0_h2d_bytes ({arm}, {label})",
                "value": summary["levels0_h2d_bytes"],
                "unit": "bytes",
                "n_page_warm": summary["n_page_warm"],
            }
        )
        alias = pool_rec.get("alias") or {}
        emit(
            {
                "metric": f"serve_pool_bytes_moved ({arm}, {label})",
                "value": (
                    pool_rec.get("cow_bytes_moved", 0)
                    + alias.get("alias_bytes_moved", 0)
                ),
                "unit": "bytes",
                "cow_bytes_moved": pool_rec.get("cow_bytes_moved", 0),
                "alias_bytes_moved": alias.get("alias_bytes_moved", 0),
                "n_alias_fallbacks": alias.get("n_alias_fallbacks", 0),
                "alias_rate": alias.get("alias_rate"),
                "n_writebacks": pool_rec.get("n_writebacks", 0),
            }
        )

    # Threshold-0 parity probe: ONE mixed dispatch through both
    # attentions (fresh engines, identical default params), bitwise on
    # every row's page span — CI reads this row as a 1.0-or-fail gate.
    ew = _make_engines(
        cfg,
        dataclasses.replace(scfg, ragged_attention="windowed", **ragged_base),
        1,
    )[0]
    eb = _make_engines(
        cfg,
        dataclasses.replace(scfg, ragged_attention="banded", **ragged_base),
        1,
    )[0]
    counts = [cfg.num_patches, max(1, cfg.num_patches // 4)]
    pages = [pages_for_tokens(c, pt) for c in counts]
    T = ew.pick_pages(sum(pages)) * pt
    flat = np.zeros((T, cfg.patch_dim), np.float32)
    starts, off = [], 0
    for c, k in zip(counts, pages):
        starts.append(off * pt)
        flat[off * pt:off * pt + c] = rng.normal(size=(c, cfg.patch_dim))
        off += k
    rw = ew.infer_ragged(flat, counts, iters_override=2)
    rb = eb.infer_ragged(flat, counts, iters_override=2)
    lw, lb = np.asarray(rw.levels), np.asarray(rb.levels)
    bitwise = all(
        np.array_equal(lw[s:s + k * pt], lb[s:s + k * pt])
        for s, k in zip(starts, pages)
    )
    emit(
        {
            "metric": f"serve_banded_parity ({label})",
            "value": 1.0 if bitwise else 0.0,
            "unit": "bool",
            "note": "threshold-0 banded vs windowed mixed dispatch, "
            "bitwise on every row's page span",
            "counts": counts,
        }
    )
    if "windowed" in peak and "banded" in peak:
        # Informational (kind "note"): the per-arm rows are what gate.
        emit(
            {
                "note": "banded working-set saving",
                "config": label,
                "windowed_peak_bytes": peak["windowed"],
                "banded_peak_bytes": peak["banded"],
                "fold": round(
                    peak["windowed"] / max(peak["banded"], 1), 1
                ),
            },
            kind="note",
        )
    return peak


def run_ramp(cfg, scfg, label: str, *, profile: str = "4x100,56x0,12x200",
             max_engines: int = 2) -> dict:
    """The ELASTIC ramp (docs/SERVING.md "Elastic serving"): an
    offered-load ramp (low -> spike -> low) driven through the real
    autoscaler. The fleet starts at ONE engine; the spike must force a
    scale-out (spawn + warmup off the hot path + admission), the
    post-spike calm a scale-in (graceful drain + device release) — and
    every ticket must resolve: the bench ASSERTS tickets-conserved
    (served+shed+failed == requests with failed == 0) and emits the
    fleet-size TIMELINE row the CI elastic gate reads:

      * serve_ramp_n_engines_peak (count; the timeline rides the row);
      * serve_ramp_spawn_ms (ms — the scale-out's off-hot-path price);
      * serve_ramp_p99 (spike | tail, ms) — recovery made a number;
      * serve_ramp_tickets_conserved (1.0 only when conservation held).
    """
    import dataclasses

    from glom_tpu.serve.batcher import DynamicBatcher, ShedError
    from glom_tpu.serve.cli import parse_ramp
    from glom_tpu.serve.elastic import Autoscaler, resolve_policy
    from glom_tpu.serve.engine import InferenceEngine
    from glom_tpu.telemetry.sinks import emit

    import numpy as np

    phases = parse_ramp(profile)
    scfg = dataclasses.replace(
        scfg,
        elastic=True, min_engines=1, max_engines=max_engines,
        elastic_low_water=0.5, elastic_high_water=0.8,
        elastic_dwell_s=0.1, elastic_cooldown_s=0.5,
        elastic_window_s=2.0, elastic_interval_s=0.05,
        elastic_p99_ms=100.0,
    )
    engines = _make_engines(cfg, scfg, 1)
    params = engines[0].params
    for eng in engines:
        eng.warmup()
    rng = np.random.default_rng(7)
    shape = (cfg.channels, cfg.image_size, cfg.image_size)
    seq = [len(engines)]

    def factory():
        i = seq[0]
        eng = InferenceEngine(cfg, scfg, params=params, name=f"engine{i}")
        seq[0] += 1
        return eng

    lat_by_phase: dict = {}
    n_total = sum(n for n, _ in phases)
    with DynamicBatcher(engines=engines) as batcher:
        # The ramp is a FORECASTED run (ISSUE 17 acceptance): every
        # closed window stamps a "forecast" record carrying its
        # predicted-vs-realized error — the evidence PR 18's
        # anticipatory policy will consume.
        from glom_tpu.telemetry.forecast import ForecastEmitter

        batcher.enable_admission_events()
        forecaster = ForecastEmitter(
            lambda r: emit(r, kind="forecast"),
            interval_s=0.25, window_s=2.0, horizon_s=0.5,
        )
        batcher.add_event_tap(forecaster.tap)
        scaler = Autoscaler(
            batcher, factory, policy=resolve_policy(scfg),
            rules={"p99_ms": scfg.elastic_p99_ms},
            interval_s=scfg.elastic_interval_s,
        ).start()
        try:
            tickets = []
            for phase, (n, gap) in enumerate(phases):
                for _ in range(n):
                    if gap and tickets:
                        time.sleep(gap)
                    try:
                        # HARD traffic (100x scale — the convergence-
                        # depth lever): every request runs near the full
                        # budget, so the spike actually queues instead
                        # of evaporating on a fast host.
                        tickets.append(
                            (phase, batcher.submit(
                                (100.0 * rng.normal(size=shape)).astype(
                                    np.float32
                                )
                            ))
                        )
                    except ShedError:
                        tickets.append((phase, None))
            for phase, t in tickets:
                if t is None:
                    continue
                try:
                    _, _, latency_s = t.result(timeout=600.0)
                except Exception:
                    continue
                lat_by_phase.setdefault(phase, []).append(1e3 * latency_s)
            # Settle: wait (bounded) for the post-spike scale-in.
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline:
                if scaler.record()["n_scale_ins"] >= 1:
                    break
                time.sleep(0.05)
        finally:
            scaler.stop()
        forecaster.close()
        summary = batcher.summary_record()
    el = summary.get("elastic") or {}
    conserved = (
        summary["n_served"] + summary["n_shed"] + summary["n_failed"]
        == summary["n_requests"] == n_total
        and summary["n_failed"] == 0
    )
    emit(
        {
            "event": "ramp_summary",
            "config": label,
            "profile": profile,
            "n_requests": n_total,
            "n_served": summary["n_served"],
            "n_shed": summary["n_shed"],
            "n_failed": summary["n_failed"],
            "elastic": el,
        },
        kind="serve",
    )
    emit(
        {
            "metric": f"serve_ramp_n_engines_peak ({label})",
            "value": el.get("n_engines_peak"),
            "unit": "count",
            "n_scale_outs": el.get("n_scale_outs"),
            "n_scale_ins": el.get("n_scale_ins"),
            # THE timeline row: [t_rel_s, n_engines] per fleet change —
            # capacity following load, as data (perfetto renders it as
            # the fleet counter track).
            "timeline": el.get("timeline"),
        }
    )
    if el.get("spawn_ms_mean") is not None:
        emit(
            {
                "metric": f"serve_ramp_spawn_ms ({label})",
                "value": el["spawn_ms_mean"],
                "unit": "ms",
                "spawn_ms_max": el.get("spawn_ms_max"),
            }
        )
    q = lambda xs, f: sorted(xs)[min(len(xs) - 1, int(f * len(xs)))]
    spike = lat_by_phase.get(1, [])
    tail_all = lat_by_phase.get(len(phases) - 1, [])
    # Steady-state half = the CHRONOLOGICALLY later half (tail_all is in
    # submission order): the first tail requests are submitted while the
    # spike backlog still drains, and their latency is the spike's
    # shadow, not the scaled fleet's.
    tail = tail_all[len(tail_all) // 2:]
    for arm, vals in (("spike", spike), ("tail", tail)):
        if vals:
            emit(
                {
                    "metric": f"serve_ramp_p99 ({arm}, {label})",
                    "value": round(q(vals, 0.99), 3),
                    "unit": "ms",
                    "n": len(vals),
                }
            )
    emit(
        {
            "metric": f"serve_ramp_tickets_conserved ({label})",
            "value": 1.0 if conserved else 0.0,
            "unit": "count",
        }
    )
    assert conserved, (
        "ramp tickets NOT conserved: "
        f"{ {k: summary[k] for k in ('n_requests', 'n_served', 'n_shed', 'n_failed')} }"
    )
    return {
        "elastic": el,
        "conserved": conserved,
        "p99_spike": q(spike, 0.99) if spike else None,
        "p99_tail": q(tail, 0.99) if tail else None,
    }


def run_workload(cfg, scfg, label: str, records, *, source: str,
                 time_scale: float = 1.0, workload_out=None,
                 max_engines: int = 2, hard: bool = True) -> dict:
    """Drive a WORKLOAD artifact through the real elastic stack
    (docs/SERVING.md "Record and replay"): re-offer the records with
    faithful inter-arrival pacing, score a live load forecast on every
    window, and assert ticket conservation — the workload observatory's
    end-to-end gate. `records` come from a recorded run
    (--replay FILE) or a scenario generator (--scenario NAME); either
    way the run emits:

      * "forecast" rows (kind forecast) with forecast_abs_err stamped
        on EVERY window — finite once predictions mature;
      * serve_workload_pacing_lag (ms) — how late the replay offered
        vs the artifact's arrival times;
      * serve_workload_forecast_abs_err (rps) — the matured mean;
      * serve_workload_n_engines_peak (count, with the timeline) and
        serve_workload_tickets_conserved — the elastic gate pair;
      * optionally re-records ITS OWN offered traffic to workload_out,
        closing the record -> replay -> record loop.
    """
    import dataclasses

    from glom_tpu.serve import workload as wl
    from glom_tpu.serve.batcher import DynamicBatcher, ShedError
    from glom_tpu.serve.elastic import Autoscaler, resolve_policy
    from glom_tpu.serve.engine import InferenceEngine
    from glom_tpu.telemetry.forecast import ForecastEmitter
    from glom_tpu.telemetry.sinks import emit

    scfg = dataclasses.replace(
        scfg,
        elastic=True, min_engines=1, max_engines=max_engines,
        elastic_low_water=0.5, elastic_high_water=0.8,
        elastic_dwell_s=0.1, elastic_cooldown_s=0.5,
        elastic_window_s=2.0, elastic_interval_s=0.05,
        elastic_p99_ms=100.0,
    )
    engines = _make_engines(cfg, scfg, 1)
    params = engines[0].params
    for eng in engines:
        eng.warmup()
    seq = [len(engines)]

    def factory():
        i = seq[0]
        eng = InferenceEngine(cfg, scfg, params=params, name=f"engine{i}")
        seq[0] += 1
        return eng

    n_total = len(records)
    signatures = []
    with DynamicBatcher(engines=engines) as batcher:
        recorder = wl.WorkloadRecorder().attach(batcher)
        forecaster = ForecastEmitter(
            lambda r: emit(r, kind="forecast"),
            interval_s=0.25, window_s=2.0, horizon_s=0.5,
        )
        batcher.add_event_tap(forecaster.tap)
        scaler = Autoscaler(
            batcher, factory, policy=resolve_policy(scfg),
            rules={"p99_ms": scfg.elastic_p99_ms},
            interval_s=scfg.elastic_interval_s,
        ).start()
        try:
            tickets = []

            def offer(rec, i):
                signatures.append(rec.get("signature"))
                img = wl.synth_input(rec, i)
                if hard:
                    # HARD traffic (the ramp's 100x convergence-depth
                    # lever): the replayed load must queue, not
                    # evaporate, or the autoscaler has nothing to do.
                    img = 100.0 * img
                try:
                    tickets.append(
                        batcher.submit(img, session_id=rec.get("session"))
                    )
                except ShedError:
                    raise  # replay() counts it; traffic drives on

            stats = wl.replay(records, offer, time_scale=time_scale)
            for t in tickets:
                try:
                    t.result(timeout=600.0)
                except Exception:  # noqa: BLE001 — summary counts it
                    pass
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline:
                if scaler.record()["n_scale_ins"] >= 1:
                    break
                time.sleep(0.05)
        finally:
            scaler.stop()
        forecaster.close()
        summary = batcher.summary_record()
        captured = recorder.records()
        if workload_out:
            recorder.write(workload_out, source=f"bench:{source}")
    el = summary.get("elastic") or {}
    conserved = (
        summary["n_served"] + summary["n_shed"] + summary["n_failed"]
        == summary["n_requests"] == n_total
        and summary["n_failed"] == 0
    )
    # The determinism contract (ISSUE 17 acceptance): the replayed
    # stream re-offers the artifact's signature sequence EXACTLY —
    # what the recorder captured must match what the artifact said.
    sig_match = signatures == [r.get("signature") for r in records] and (
        [c.get("signature") for c in captured] == signatures
    )
    emit(
        {
            "event": "workload_summary",
            "config": label,
            "source": source,
            "n_requests": n_total,
            "n_served": summary["n_served"],
            "n_shed": summary["n_shed"],
            "n_failed": summary["n_failed"],
            "signature_sequence_match": sig_match,
            "forecast_windows": forecaster.n_windows,
            "elastic": el,
            **stats,
        },
        kind="serve",
    )
    emit(
        {
            "metric": f"serve_workload_pacing_lag ({source}, {label})",
            "value": stats["pacing_lag_mean_ms"],
            "unit": "ms",
            "pacing_lag_max_ms": stats["pacing_lag_max_ms"],
        }
    )
    mae = forecaster.forecaster._err_sum / forecaster.forecaster._n_scored \
        if forecaster.forecaster._n_scored else None
    if mae is not None:
        emit(
            {
                "metric": (
                    f"serve_workload_forecast_abs_err ({source}, {label})"
                ),
                "value": round(mae, 4),
                "unit": "rps",
                "n_scored": forecaster.forecaster._n_scored,
                "n_windows": forecaster.n_windows,
            }
        )
    lead = forecaster.lead_model.lead_time_ms()
    if lead is not None:
        emit(
            {
                "metric": f"serve_workload_lead_time_ms ({source}, {label})",
                "value": lead,
                "unit": "ms",
                "n_spawns": len(forecaster.lead_model._samples),
            }
        )
    emit(
        {
            "metric": f"serve_workload_n_engines_peak ({source}, {label})",
            "value": el.get("n_engines_peak"),
            "unit": "count",
            "timeline": el.get("timeline"),
        }
    )
    emit(
        {
            "metric": (
                f"serve_workload_tickets_conserved ({source}, {label})"
            ),
            "value": 1.0 if (conserved and sig_match) else 0.0,
            "unit": "count",
        }
    )
    assert conserved, (
        "workload tickets NOT conserved: "
        f"{ {k: summary[k] for k in ('n_requests', 'n_served', 'n_shed', 'n_failed')} }"
    )
    assert sig_match, (
        "replayed signature sequence diverged from the artifact "
        f"(offered {len(signatures)}, recorded {len(captured)}, "
        f"artifact {n_total})"
    )
    return {
        "elastic": el,
        "conserved": conserved,
        "stats": stats,
        "n_forecast_windows": forecaster.n_windows,
    }


def run_elastic_ab(cfg, scfg, label: str, records, *, source: str,
                   time_scale: float = 1.0, out_prefix: str = "elastic_ab",
                   max_engines: int = 2, gate: bool = False) -> dict:
    """Anticipatory-vs-reactive autoscaling A/B over ONE replayed
    workload artifact (docs/SERVING.md "Anticipatory autoscaling"): the
    same records drive two independent fleets —

      * reactive       — the PR 14 baseline (no forecast wired, no
                         warm pool); and
      * anticipatory   — the PR 18 policy (forecast + spawn-lead-time
                         model + one warm-pool spare),

    each writing its decisions, serve events, and forecasts to its OWN
    JSONL file ({out_prefix}_{arm}.jsonl) so the decision chains stay
    per-arm and `python -m glom_tpu.telemetry audit` scores each arm's
    counterfactual regret independently. Emits per-arm
    serve_elastic_ab_p99 / _failed / _regret rows plus the deltas
    (anticipatory minus reactive; negative = anticipation won). With
    gate=True (the flash-crowd CI gate) the run ASSERTS the
    anticipatory arm shed-or-failed no more tickets AND landed a
    strictly lower p99 than the reactive arm.
    """
    import dataclasses

    from glom_tpu.serve import workload as wl
    from glom_tpu.serve.batcher import DynamicBatcher, ShedError
    from glom_tpu.serve.elastic import Autoscaler, resolve_policy
    from glom_tpu.serve.engine import InferenceEngine
    from glom_tpu.serve.events import stamp_serve
    from glom_tpu.telemetry import schema
    from glom_tpu.telemetry.audit import audit_records, load_records
    from glom_tpu.telemetry.forecast import ForecastEmitter
    from glom_tpu.telemetry.sinks import emit
    from glom_tpu.utils.metrics import MetricsWriter

    scfg_base = dataclasses.replace(
        scfg,
        elastic=True, min_engines=1, max_engines=max_engines,
        elastic_low_water=0.5, elastic_high_water=0.8,
        elastic_dwell_s=0.1, elastic_cooldown_s=0.5,
        elastic_window_s=2.0, elastic_interval_s=0.05,
        elastic_p99_ms=100.0,
    )
    n_total = len(records)
    q = lambda xs, f: sorted(xs)[min(len(xs) - 1, int(f * len(xs)))]

    def _arm(arm: str, *, anticipatory: bool, warm_pool: int) -> dict:
        scfg_arm = dataclasses.replace(
            scfg_base,
            elastic_anticipatory=anticipatory,
            warm_pool=warm_pool,
        )
        path = f"{out_prefix}_{arm}.jsonl"
        writer = MetricsWriter(path, echo=False)
        engines = _make_engines(cfg, scfg_arm, 1)
        params = engines[0].params
        for eng in engines:
            eng.warmup()
        seq = [len(engines)]

        def factory():
            i = seq[0]
            eng = InferenceEngine(
                cfg, scfg_arm, params=params, name=f"engine{i}"
            )
            seq[0] += 1
            return eng

        latencies: list = []
        with DynamicBatcher(engines=engines, writer=writer) as batcher:
            batcher.enable_admission_events()
            forecaster = ForecastEmitter(
                lambda r: writer.write(
                    schema.stamp(dict(r), kind="forecast")
                ),
                # A 1 s window matures the fit within the scenario's
                # pre-crowd base phase; the 2 s default never closes
                # enough scored windows before the burst lands.
                interval_s=0.25, window_s=1.0, horizon_s=0.5,
            )
            batcher.add_event_tap(forecaster.tap)
            scaler = Autoscaler(
                batcher, factory, policy=resolve_policy(scfg_arm),
                rules={"p99_ms": scfg_arm.elastic_p99_ms},
                writer=writer,
                interval_s=scfg_arm.elastic_interval_s,
                # The reactive arm IS the PR 14 baseline: no forecast
                # wired even though the emitter runs (its rows score the
                # counterfactual), no spares.
                forecast=forecaster if anticipatory else None,
                warm_pool=warm_pool,
                fleet=arm,
            ).start()
            try:
                tickets = []

                def offer(rec, i):
                    # HARD traffic, same 100x lever as run_workload: the
                    # crowd must queue or neither arm has anything to do.
                    img = 100.0 * wl.synth_input(rec, i)
                    tickets.append(
                        batcher.submit(img, session_id=rec.get("session"))
                    )

                stats = wl.replay(records, offer, time_scale=time_scale)
                for t in tickets:
                    try:
                        _, _, latency_s = t.result(timeout=600.0)
                        latencies.append(1e3 * latency_s)
                    except Exception:  # noqa: BLE001 — summary counts it
                        pass
            finally:
                scaler.stop()
            forecaster.close()
            srec = scaler.record()
            summary = batcher.summary_record()
            writer.write(stamp_serve(dict(summary)))
        writer.close()
        audit = audit_records(load_records(path))
        assert not audit["errors"], (
            f"{arm} arm decision chain failed its own audit: "
            f"{audit['errors'][:3]}"
        )
        failed = summary["n_shed"] + summary["n_failed"]
        return {
            "arm": arm,
            "path": path,
            "p99_ms": round(q(latencies, 0.99), 3) if latencies else None,
            "n_served": summary["n_served"],
            "failed": failed,
            "regret": audit["regret_total"],
            "regret_per_decision": audit["regret_per_decision"],
            "n_decisions": srec["n_decisions"],
            "decisions_late": srec["decisions_late"],
            "spawn_lead_violations": srec["spawn_lead_violations"],
            "n_promotions": srec["n_promotions"],
            "pacing_lag_mean_ms": stats["pacing_lag_mean_ms"],
            "conserved": (
                summary["n_served"] + summary["n_shed"]
                + summary["n_failed"] == summary["n_requests"] == n_total
            ),
        }

    arms = {
        "reactive": _arm("reactive", anticipatory=False, warm_pool=0),
        "anticipatory": _arm("anticipatory", anticipatory=True,
                             warm_pool=1),
    }
    emit(
        {
            "event": "elastic_ab_summary",
            "config": label,
            "source": source,
            "n_requests": n_total,
            "arms": arms,
        },
        kind="serve",
    )
    for arm, r in arms.items():
        if r["p99_ms"] is not None:
            emit(
                {
                    "metric": f"serve_elastic_ab_p99 ({arm}, {source}, "
                              f"{label})",
                    "value": r["p99_ms"],
                    "unit": "ms",
                    "n": r["n_served"],
                }
            )
        emit(
            {
                "metric": f"serve_elastic_ab_failed ({arm}, {source}, "
                          f"{label})",
                "value": r["failed"],
                "unit": "count",
            }
        )
        emit(
            {
                "metric": f"serve_elastic_ab_regret ({arm}, {source}, "
                          f"{label})",
                "value": r["regret"],
                "unit": "count",
                "regret_per_decision": r["regret_per_decision"],
                "n_decisions": r["n_decisions"],
                "decisions_late": r["decisions_late"],
                "spawn_lead_violations": r["spawn_lead_violations"],
                "log": r["path"],
            }
        )
    rx, ax = arms["reactive"], arms["anticipatory"]
    if rx["p99_ms"] is not None and ax["p99_ms"] is not None:
        emit(
            {
                "metric": f"serve_elastic_ab_p99_delta ({source}, {label})",
                "value": round(ax["p99_ms"] - rx["p99_ms"], 3),
                "unit": "ms",
            }
        )
    emit(
        {
            "metric": f"serve_elastic_ab_failed_delta ({source}, {label})",
            "value": ax["failed"] - rx["failed"],
            "unit": "count",
        }
    )
    emit(
        {
            "metric": f"serve_elastic_ab_regret_delta ({source}, {label})",
            "value": round(ax["regret"] - rx["regret"], 6),
            "unit": "count",
        }
    )
    assert rx["conserved"] and ax["conserved"], (
        f"elastic A/B tickets NOT conserved: reactive={rx}, "
        f"anticipatory={ax}"
    )
    if gate:
        assert ax["failed"] <= rx["failed"], (
            "anticipatory arm shed/failed MORE tickets than reactive: "
            f"{ax['failed']} > {rx['failed']}"
        )
        assert (
            ax["p99_ms"] is not None and rx["p99_ms"] is not None
            and ax["p99_ms"] < rx["p99_ms"]
        ), (
            "anticipatory arm did not beat reactive p99: "
            f"{ax['p99_ms']} vs {rx['p99_ms']}"
        )
    return arms


def run_qos_ab(cfg, scfg, label: str, records, *, source: str,
               time_scale: float = 1.0, out_prefix: str = "qos_ab",
               max_engines: int = 2, gate: bool = False) -> dict:
    """Classless-vs-QoS serving A/B over ONE mixed-class flash-crowd
    artifact (docs/SERVING.md "SLO classes"): the same records drive two
    independent elastic fleets —

      * classless — one shared FIFO queue (the PR 18 baseline); every
                    submit still CARRIES its recorded slo_class label,
                    so per-class latency attributes on both sides; and
      * qos       — three declared SLO classes (premium/standard/batch,
                    8/2/1 weights, per-class lanes partitioning the SAME
                    total queue depth) through the deficit-weighted-fair
                    scheduler, class-aware shed, and class-scoped
                    monitor rules,

    each writing its decision chain to its own JSONL ({out_prefix}_
    {arm}.jsonl) and audited STRICTLY (errors AND warnings fail — the
    acceptance bar). Emits per-(arm, class) p99 / served-fraction /
    shed rows plus the premium-p99 delta. Both arms must conserve
    tickets EXACTLY per class. With gate=True the run additionally
    ASSERTS premium p99 strictly below the classless baseline and the
    batch served fraction at or above the starvation floor.
    """
    import dataclasses

    from glom_tpu.serve import workload as wl
    from glom_tpu.serve.batcher import DynamicBatcher
    from glom_tpu.serve.elastic import Autoscaler, resolve_policy
    from glom_tpu.serve.engine import InferenceEngine
    from glom_tpu.serve.events import stamp_serve
    from glom_tpu.serve.qos import class_slo_rules, resolve_slo_classes
    from glom_tpu.telemetry.audit import audit_records, load_records
    from glom_tpu.telemetry.sinks import emit
    from glom_tpu.utils.metrics import MetricsWriter

    scfg_base = dataclasses.replace(
        scfg,
        elastic=True, min_engines=1, max_engines=max_engines,
        elastic_low_water=0.5, elastic_high_water=0.8,
        elastic_dwell_s=0.1, elastic_cooldown_s=0.5,
        elastic_window_s=2.0, elastic_interval_s=0.05,
        elastic_p99_ms=100.0,
    )
    # The QoS arm's lanes PARTITION the classless arm's queue depth —
    # identical total admission capacity, so the A/B isolates the
    # scheduler, not a bigger buffer.
    qd = scfg_base.queue_depth
    floor = 0.1
    qos_classes = (
        f"premium:weight=8,p99_ms={scfg_base.elastic_p99_ms},"
        f"queue_depth={max(1, qd // 2)}",
        f"standard:weight=2,queue_depth={max(1, qd // 4)}",
        f"batch:weight=1,queue_depth={max(1, qd - qd // 2 - qd // 4)}",
    )
    n_total = len(records)
    qtile = lambda xs, f: sorted(xs)[min(len(xs) - 1, int(f * len(xs)))]

    def _arm(arm: str, *, classed: bool) -> dict:
        scfg_arm = (
            dataclasses.replace(
                scfg_base,
                slo_classes=qos_classes,
                slo_starvation_floor=floor,
            )
            if classed else scfg_base
        )
        path = f"{out_prefix}_{arm}.jsonl"
        writer = MetricsWriter(path, echo=False)
        engines = _make_engines(cfg, scfg_arm, 1)
        params = engines[0].params
        for eng in engines:
            eng.warmup()
        seq = [len(engines)]

        def factory():
            i = seq[0]
            eng = InferenceEngine(
                cfg, scfg_arm, params=params, name=f"engine{i}"
            )
            seq[0] += 1
            return eng

        rules = {"p99_ms": scfg_arm.elastic_p99_ms}
        if classed:
            rules.update(class_slo_rules(resolve_slo_classes(scfg_arm)))
        lat_by_class: dict = {}
        with DynamicBatcher(engines=engines, writer=writer) as batcher:
            batcher.enable_admission_events()
            scaler = Autoscaler(
                batcher, factory, policy=resolve_policy(scfg_arm),
                rules=rules,
                writer=writer,
                interval_s=scfg_arm.elastic_interval_s,
                fleet=arm,
            ).start()
            try:
                tickets = []

                def offer(rec, i):
                    # HARD traffic, the same 100x lever as the elastic
                    # A/B: the crowd must queue or the scheduler has
                    # nothing to arbitrate. A ShedError propagates to
                    # replay(), which counts it and drives on — the
                    # batcher already attributed it to the class.
                    img = 100.0 * wl.synth_input(rec, i)
                    cls = rec.get("slo_class")
                    tickets.append(
                        (cls, batcher.submit(
                            img,
                            session_id=rec.get("session"),
                            slo_class=cls,
                        ))
                    )

                wl.replay(records, offer, time_scale=time_scale)
                for cls, t in tickets:
                    try:
                        _, _, latency_s = t.result(timeout=600.0)
                        lat_by_class.setdefault(cls, []).append(
                            1e3 * latency_s
                        )
                    except Exception:  # noqa: BLE001 — summary counts it
                        pass
            finally:
                scaler.stop()
            summary = batcher.summary_record()
            writer.write(stamp_serve(dict(summary)))
        writer.close()
        audit = audit_records(load_records(path))
        # The acceptance bar is `telemetry audit --strict`: structural
        # errors AND warnings (un-actuated decisions) both fail.
        assert not audit["errors"] and not audit["warnings"], (
            f"{arm} arm failed its strict audit: "
            f"{(audit['errors'] + audit['warnings'])[:3]}"
        )
        classes = summary.get("classes") or {}
        for cls, cnt in classes.items():
            # EXACT per-class ticket conservation — every admitted
            # request settles under the class it was admitted with,
            # across sheds, failover, and continuations.
            assert (
                cnt["n_served"] + cnt["n_shed"] + cnt["n_failed"]
                == cnt["n_requests"]
            ), f"{arm} arm class {cls!r} tickets NOT conserved: {cnt}"
        assert (
            sum(c["n_requests"] for c in classes.values())
            == summary["n_requests"] == n_total
        ), (
            f"{arm} arm class rows do not cover the offered load: "
            f"{classes} vs {n_total}"
        )
        return {
            "arm": arm,
            "path": path,
            "p99_ms": {
                cls: round(qtile(ls, 0.99), 3)
                for cls, ls in sorted(lat_by_class.items())
                if ls
            },
            "classes": classes,
            "regret": audit["regret_total"],
            "regret_weighted": audit["regret_weighted"],
            "n_decisions": audit["n_decisions"],
        }

    arms = {
        "classless": _arm("classless", classed=False),
        "qos": _arm("qos", classed=True),
    }
    emit(
        {
            "event": "qos_ab_summary",
            "config": label,
            "source": source,
            "n_requests": n_total,
            "starvation_floor": floor,
            "arms": arms,
        },
        kind="serve",
    )
    for arm, r in arms.items():
        for cls, p99 in r["p99_ms"].items():
            emit(
                {
                    "metric": f"serve_qos_ab_p99 ({cls}, {arm}, "
                              f"{source}, {label})",
                    "value": p99,
                    "unit": "ms",
                }
            )
        for cls, cnt in sorted(r["classes"].items()):
            if cnt.get("served_fraction") is not None:
                emit(
                    {
                        "metric": "serve_qos_ab_served_fraction "
                                  f"({cls}, {arm}, {source}, {label})",
                        "value": cnt["served_fraction"],
                        "unit": "fraction",
                    }
                )
            emit(
                {
                    "metric": f"serve_qos_ab_shed ({cls}, {arm}, "
                              f"{source}, {label})",
                    "value": cnt["n_shed"],
                    "unit": "count",
                }
            )
        emit(
            {
                "metric": f"serve_qos_ab_regret_weighted ({arm}, "
                          f"{source}, {label})",
                "value": r["regret_weighted"],
                "unit": "count",
                "n_decisions": r["n_decisions"],
                "log": r["path"],
            }
        )
    base, qos = arms["classless"], arms["qos"]
    prem_base = base["p99_ms"].get("premium")
    prem_qos = qos["p99_ms"].get("premium")
    if prem_base is not None and prem_qos is not None:
        emit(
            {
                "metric": f"serve_qos_ab_premium_p99_delta ({source}, "
                          f"{label})",
                "value": round(prem_qos - prem_base, 3),
                "unit": "ms",
            }
        )
    if gate:
        assert prem_base is not None and prem_qos is not None, (
            "qos A/B gate needs premium latencies on both arms: "
            f"classless={prem_base}, qos={prem_qos}"
        )
        assert prem_qos < prem_base, (
            "QoS arm did not beat the classless premium p99: "
            f"{prem_qos} vs {prem_base}"
        )
        batch_served = (qos["classes"].get("batch") or {}).get(
            "served_fraction"
        )
        assert batch_served is not None and batch_served >= floor, (
            "QoS arm starved the batch class below its floor: "
            f"served_fraction={batch_served} < {floor}"
        )
    return arms


def run_trace_ab(cfg, scfg, label: str, *, n_requests: int,
                 n_engines: int = 1, repeats: int = 3) -> dict:
    """Request-tracing overhead A/B (docs/OBSERVABILITY.md, Request
    tracing): the same closed-loop traffic served with trace stamping ON
    (ids minted per submit, per-dispatch scope, per-request resolve
    leaves) vs OFF (context keys stamp as null, no resolve leaves), both
    arms writing through a real MetricsWriter so serialization is priced.
    Arms alternate per repeat and each keeps its BEST mean (min-of-noise,
    the bench convention), emitting `serve_trace_mean_latency` per arm
    and `serve_trace_overhead` in percent — the <2% bar run_hw_queue's
    step 9g gates. Returns {arm: mean_ms}."""
    import numpy as np

    from glom_tpu.serve.batcher import DynamicBatcher, ShedError
    from glom_tpu.telemetry.sinks import emit
    from glom_tpu.utils.metrics import MetricsWriter

    rng = np.random.default_rng(3)
    shape = (cfg.channels, cfg.image_size, cfg.image_size)
    imgs = [
        rng.normal(size=shape).astype(np.float32) for _ in range(n_requests)
    ]
    # ONE engine set serves both arms: tracing is purely host-side, and a
    # per-arm engine would hand the A/B a compiled-program / allocator
    # state difference far larger than the stamping cost being measured.
    engines = _make_engines(cfg, scfg, n_engines)
    for eng in engines:
        eng.warmup()
    window = max(1, min(scfg.queue_depth // 2, 16))
    best: dict = {}
    for rep in range(repeats + 1):
        for arm, flag in (("trace-off", False), ("trace-on", True)):
            writer = MetricsWriter(None, echo=False)
            lat = []
            with DynamicBatcher(
                engines=engines, writer=writer, trace=flag
            ) as batcher:
                for start in range(0, n_requests, window):
                    tickets = []
                    for i in range(start, min(start + window, n_requests)):
                        try:
                            tickets.append(batcher.submit(imgs[i]))
                        except ShedError:
                            continue
                    for t in tickets:
                        try:
                            _, _, latency_s = t.result(timeout=600.0)
                        except Exception:
                            continue
                        lat.append(latency_s)
            writer.close()
            if rep == 0:
                continue  # warm-up pass: first-touch noise, not data
            if lat:
                mean_ms = 1e3 * sum(lat) / len(lat)
                if arm not in best or mean_ms < best[arm]:
                    best[arm] = mean_ms
    for arm in ("trace-off", "trace-on"):
        if arm in best:
            emit(
                {
                    "metric": f"serve_trace_mean_latency ({arm}, {label})",
                    "value": round(best[arm], 4),
                    "unit": "ms",
                    "requests": n_requests,
                    "repeats": repeats,
                }
            )
        else:
            emit(
                {
                    "metric": f"serve_trace_mean_latency ({arm}, {label})",
                    "value": None,
                    "unit": "ms",
                    "error": "no-requests-served",
                    "note": f"UNMEASURED: trace A/B {arm} arm served nothing",
                },
                kind="error",
            )
    if "trace-off" in best and "trace-on" in best and best["trace-off"] > 0:
        overhead = 100.0 * (best["trace-on"] - best["trace-off"]) / best[
            "trace-off"
        ]
        emit(
            {
                "metric": f"serve_trace_overhead ({label})",
                "value": round(overhead, 2),
                "unit": "percent",
                "trace_off_ms": round(best["trace-off"], 4),
                "trace_on_ms": round(best["trace-on"], 4),
                "budget_percent": 2.0,
            }
        )
    return best


def run_phase_ab(cfg, scfg, label: str, *, n_requests: int,
                 n_engines: int = 1, repeats: int = 3) -> dict:
    """Latency-decomposition overhead A/B (docs/OBSERVABILITY.md,
    "Capacity observatory"): the same closed-loop traffic served with the
    phase split ON (queue_wait/pack/h2d/device/resolve stamped on every
    dispatch, bit-exact latency_ms sum, per-request phase totals on the
    resolve leaf) vs OFF (keys null, bare engine wall). The split's cost
    is a handful of perf_counter reads plus the engine-side input sync —
    this bench is what keeps the <2% claim measured, not assumed. Same
    shared-engine interleaved-arm methodology as run_trace_ab (a per-arm
    engine would hand the A/B a compiled-program state difference far
    larger than the phase clocks being measured); the split never touches
    the compiled program, so the ENGINE-side half toggles per arm via the
    host-side `engine.phase_split` attribute — the off arm pays neither
    the batcher clocks nor the input sync."""
    import numpy as np

    from glom_tpu.serve.batcher import DynamicBatcher, ShedError
    from glom_tpu.telemetry.sinks import emit
    from glom_tpu.utils.metrics import MetricsWriter

    rng = np.random.default_rng(5)
    shape = (cfg.channels, cfg.image_size, cfg.image_size)
    imgs = [
        rng.normal(size=shape).astype(np.float32) for _ in range(n_requests)
    ]
    engines = _make_engines(cfg, scfg, n_engines)
    for eng in engines:
        eng.warmup()
    window = max(1, min(scfg.queue_depth // 2, 16))
    best: dict = {}
    for rep in range(repeats + 1):
        for arm, flag in (("phase-off", False), ("phase-on", True)):
            writer = MetricsWriter(None, echo=False)
            lat = []
            for eng in engines:
                eng.phase_split = flag  # host-side; no recompile
            with DynamicBatcher(
                engines=engines, writer=writer, phase_split=flag
            ) as batcher:
                for start in range(0, n_requests, window):
                    tickets = []
                    for i in range(start, min(start + window, n_requests)):
                        try:
                            tickets.append(batcher.submit(imgs[i]))
                        except ShedError:
                            continue
                    for t in tickets:
                        try:
                            _, _, latency_s = t.result(timeout=600.0)
                        except Exception:
                            continue
                        lat.append(latency_s)
            writer.close()
            if rep == 0:
                continue  # warm-up pass: first-touch noise, not data
            if lat:
                mean_ms = 1e3 * sum(lat) / len(lat)
                if arm not in best or mean_ms < best[arm]:
                    best[arm] = mean_ms
    for arm in ("phase-off", "phase-on"):
        if arm in best:
            emit(
                {
                    "metric": f"serve_phase_mean_latency ({arm}, {label})",
                    "value": round(best[arm], 4),
                    "unit": "ms",
                    "requests": n_requests,
                    "repeats": repeats,
                }
            )
        else:
            emit(
                {
                    "metric": f"serve_phase_mean_latency ({arm}, {label})",
                    "value": None,
                    "unit": "ms",
                    "error": "no-requests-served",
                    "note": f"UNMEASURED: phase A/B {arm} arm served nothing",
                },
                kind="error",
            )
    if "phase-off" in best and "phase-on" in best and best["phase-off"] > 0:
        overhead = 100.0 * (best["phase-on"] - best["phase-off"]) / best[
            "phase-off"
        ]
        emit(
            {
                "metric": f"serve_phase_overhead ({label})",
                "value": round(overhead, 2),
                "unit": "percent",
                "phase_off_ms": round(best["phase-off"], 4),
                "phase_on_ms": round(best["phase-on"], 4),
                "budget_percent": 2.0,
            }
        )
    return best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--requests", type=int, default=None,
                    help="requests per load point (default: 48 TPU, 16 CPU)")
    ap.add_argument("--iters", default=None,
                    help="override the preset route: an int or 'auto'")
    ap.add_argument("--engines", type=int, default=1, metavar="N",
                    help="engine replicas behind one shared batcher")
    ap.add_argument("--mesh-data", type=int, default=None, metavar="D",
                    help="shard each engine's buckets over a D-way 'data' "
                    "axis (parallel/serve_mesh.py)")
    ap.add_argument("--mesh-seq", type=int, default=None, metavar="S",
                    help="shard the patch axis over an S-way 'seq' axis")
    ap.add_argument("--two-tier-ab", action="store_true",
                    help="run the batch-level vs two-tier exit A/B over "
                    "heterogeneous traffic (tiered executed-iters rows)")
    ap.add_argument("--hetero", type=float, default=0.5, metavar="FRAC",
                    help="fraction of HARD (slow-converging) requests in "
                    "the two-tier A/B's synthetic traffic (default 0.5)")
    ap.add_argument("--ragged", action="store_true",
                    help="run the mixed-resolution ragged-vs-bucket sweep "
                    "INSTEAD of the load sweep: the same streamed traffic "
                    "served padded through the bucket ladder vs packed "
                    "through the ragged page ladder, measuring pad-waste "
                    "fraction, warm/cold dispatch latency, and warm-path "
                    "levels0 upload bytes per arm (docs/SERVING.md)")
    ap.add_argument("--banded-ab", action="store_true",
                    help="run the block-banded vs windowed ragged "
                    "consensus A/B INSTEAD of the load sweep: the same "
                    "mixed-resolution streamed traffic under the "
                    "windowed gather, the banded route, and banded + "
                    "in-place pool aliasing — emitting the peak "
                    "duplicated k/v working set per arm, the largest "
                    "admissible ragged signature under the windowed "
                    "byte budget, pool bytes moved per arm, and the "
                    "threshold-0 bitwise parity row (docs/SERVING.md)")
    ap.add_argument("--temporal", action="store_true",
                    help="run the streaming warm-vs-cold A/B INSTEAD of "
                    "the load sweep: frame-sequence traffic per stream "
                    "through the session column cache, measuring mean "
                    "executed iters/request per arm (docs/SERVING.md)")
    ap.add_argument("--streams", type=int, default=4, metavar="S",
                    help="temporal mode: number of concurrent streams")
    ap.add_argument("--frames", type=int, default=4, metavar="F",
                    help="temporal mode: frames per stream")
    ap.add_argument("--perturb", type=float, default=None, metavar="P",
                    help="temporal mode: per-frame perturbation scale "
                    "relative to the stream's base image (default 0.05; "
                    "delta mode perturbs a one-patch REGION and defaults "
                    "to 0.5 — strong enough that the global witness "
                    "re-settles while the support witness exits)")
    ap.add_argument("--delta", action="store_true",
                    help="with --temporal: run the DELTA streaming A/B "
                    "instead of the warm/cold one — whole-state paged "
                    "warm vs delta-chain storage + the incremental "
                    "update path, over O(1)-shaped traffic (shared scene "
                    "bases, bitwise hold frames, a one-patch moving "
                    "region), measuring mean executed iters/frame, "
                    "actual bytes_per_stream per arm, and the "
                    "threshold-0 bitwise reconstruction parity "
                    "(docs/SERVING.md, Delta streaming)")
    ap.add_argument("--cameras", type=int, default=0, metavar="C",
                    help="delta mode: streams per scene sharing an "
                    "identical first frame (0 = all streams, one scene)")
    ap.add_argument("--delta-atol", type=float, default=0.5, metavar="A",
                    help="delta mode: per-page column residual tolerance "
                    "for the delta arm (stamped on every row; the parity "
                    "probe always runs at 0.0). The default sits mid-gap "
                    "between a perturbed page's residual (~4.0 at the "
                    "default traffic) and unperturbed one-iteration "
                    "drift (~0.1)")
    ap.add_argument("--delta-period", type=int, default=4, metavar="K",
                    help="delta mode: a region perturbation every K "
                    "frames, bitwise holds between (default 4)")
    ap.add_argument("--trace-ab", action="store_true",
                    help="run the request-tracing overhead A/B INSTEAD of "
                    "the load sweep: the same closed-loop traffic with "
                    "trace stamping on vs off, emitting the per-arm mean "
                    "latency and serve_trace_overhead in percent — the "
                    "<2% bar (docs/OBSERVABILITY.md, Request tracing)")
    ap.add_argument("--ramp", action="store_true",
                    help="run the ELASTIC ramp INSTEAD of the load sweep: "
                    "an offered-load ramp (low -> spike -> low) through "
                    "the real autoscaler — the spike must scale the "
                    "fleet OUT, the calm back IN, with every ticket "
                    "conserved; emits the n_engines timeline row and "
                    "spawn/p99 costs (docs/SERVING.md, Elastic serving)")
    ap.add_argument("--ramp-profile", default="4x100,56x0,12x200",
                    metavar="N1xG1,...",
                    help="ramp mode: requests x gap_ms per phase")
    ap.add_argument("--replay", default=None, metavar="FILE",
                    help="replay a recorded workload artifact "
                    "(serve/workload.py) through the real elastic stack "
                    "INSTEAD of the load sweep: faithful inter-arrival "
                    "pacing, a scored live forecast on every window, "
                    "ticket conservation asserted")
    ap.add_argument("--scenario", default=None,
                    choices=("diurnal", "flash-crowd", "rolling-outage"),
                    help="generate a workload scenario (pure-stdlib, "
                    "seeded) and drive it like --replay — chaos-grade "
                    "elastic traffic reproducible from a seed alone")
    ap.add_argument("--scenario-duration", type=float, default=6.0,
                    metavar="S", help="scenario length in seconds")
    ap.add_argument("--scenario-seed", type=int, default=0, metavar="K",
                    help="scenario arrival-process seed")
    ap.add_argument("--scenario-crowd-rps", type=float, default=None,
                    metavar="R",
                    help="flash-crowd only: crowd arrival rate during "
                    "the burst (default 50; raise past one engine's "
                    "service rate to force a genuine capacity crunch "
                    "for the --elastic-ab gate)")
    ap.add_argument("--time-scale", type=float, default=1.0, metavar="X",
                    help="replay/scenario: stretch (>1) or compress (<1) "
                    "the inter-arrival gaps")
    ap.add_argument("--elastic-ab", action="store_true",
                    help="with --replay/--scenario: drive the SAME "
                    "records through a reactive (PR 14 baseline) and an "
                    "anticipatory (forecast + warm pool) fleet, each "
                    "logging its decision chain to its own JSONL file, "
                    "and score counterfactual regret per arm "
                    "(docs/SERVING.md 'Anticipatory autoscaling'); "
                    "flash-crowd runs GATE on the p99 + failed-ticket "
                    "deltas")
    ap.add_argument("--elastic-ab-out", default="elastic_ab",
                    metavar="PREFIX",
                    help="per-arm decision-log path prefix "
                    "(PREFIX_reactive.jsonl / PREFIX_anticipatory.jsonl)")
    ap.add_argument("--class-mix", default=None, metavar="SPEC",
                    help="scenario only: deal each arrival an SLO class "
                    "by seeded fraction, e.g. "
                    "'premium=0.2,standard=0.3,batch=0.5' "
                    "(docs/SERVING.md 'SLO classes')")
    ap.add_argument("--qos-ab", action="store_true",
                    help="with --replay/--scenario: drive the SAME "
                    "records through a classless (shared FIFO) and a "
                    "QoS (premium/standard/batch weighted-fair) fleet, "
                    "audit each arm's decision log STRICTLY, and emit "
                    "per-class p99 / served-fraction rows; flash-crowd "
                    "runs GATE on premium p99 beating the classless "
                    "baseline with batch held at the starvation floor")
    ap.add_argument("--qos-ab-out", default="qos_ab",
                    metavar="PREFIX",
                    help="per-arm decision-log path prefix "
                    "(PREFIX_classless.jsonl / PREFIX_qos.jsonl)")
    ap.add_argument("--workload-out", default=None, metavar="FILE",
                    help="replay/scenario: re-record THIS run's offered "
                    "traffic as a workload artifact (closes the "
                    "record -> replay -> record loop)")
    ap.add_argument("--phase-ab", action="store_true",
                    help="run the latency-decomposition overhead A/B: the "
                    "same traffic with the dispatch phase split on vs "
                    "off, emitting serve_phase_overhead in percent — the "
                    "<2%% bar (docs/OBSERVABILITY.md, Capacity "
                    "observatory)")
    args = ap.parse_args(argv)

    from glom_tpu.telemetry.sinks import bench_bootstrap, emit

    if not bench_bootstrap("serve_p95_latency", "ms"):
        return 1

    import dataclasses

    import jax

    from glom_tpu.utils.config import GlomConfig, ServeConfig
    from glom_tpu.utils.metrics import detect_chip
    from glom_tpu.utils.presets import get_preset

    chip = detect_chip()
    on_tpu = chip != "cpu"
    if on_tpu:
        preset = get_preset("imagenet224-dp8")
        cfg, scfg = preset.model, preset.serve
        label = f"ImageNet-224 L6 d512 bf16, {chip}"
        n_requests = args.requests or 48
        load_fracs = (0.25, 0.5, 0.8)
        ceiling_repeats = 5
    else:
        # The caller asked for the CPU: the labelled small config, a
        # functional drive of the harness for CI. The budget is
        # raised past the config's 2L default so the two-tier A/B's easy
        # requests have room to converge inside it (~budget-6 at
        # threshold 1e-3; hard 100x requests land near the budget).
        cfg = GlomConfig(dim=64, levels=3, image_size=16, patch_size=4)
        scfg = ServeConfig(
            buckets=(1, 2, 4), max_batch=4, max_delay_ms=2.0,
            iters="auto", exit_threshold=1e-3, max_auto_iters=16,
        )
        label = "cpu-fallback cfg"
        n_requests = args.requests or 16
        load_fracs = (0.5,)
        ceiling_repeats = 2
        emit(
            {"note": "JAX_PLATFORMS=cpu functional drive at the labelled "
             "cpu-fallback serve config: a harness check, not a device "
             "measurement"},
            kind="note",
        )
    overrides = {}
    if args.iters is not None:
        overrides["iters"] = (
            "auto" if args.iters == "auto" else int(args.iters)
        )
    if args.mesh_data is not None:
        overrides["mesh_data"] = args.mesh_data
    if args.mesh_seq is not None:
        overrides["mesh_seq"] = args.mesh_seq
    mesh_data = overrides.get("mesh_data", scfg.mesh_data)
    if mesh_data > 1:
        # Buckets must divide by the data axis; drop the ones that don't
        # (a preset ladder with a 1-bucket tail can't shard its rows) and
        # cap the admission ceiling to what remains.
        buckets = tuple(b for b in scfg.buckets if b % mesh_data == 0)
        if not buckets:
            buckets = (mesh_data,)
        overrides["buckets"] = buckets
        overrides["max_batch"] = min(scfg.max_batch, max(buckets))
    if overrides:
        scfg = dataclasses.replace(scfg, **overrides)
    if args.engines > 1:
        label = f"{label}, engines={args.engines}"
    if scfg.mesh_data > 1 or scfg.mesh_seq > 1:
        label = f"{label}, mesh={scfg.mesh_data}x{scfg.mesh_seq}"
    del jax  # imported to fail fast before any measurement if broken
    if args.replay or args.scenario:
        from glom_tpu.serve.workload import generate, load_workload

        if args.replay:
            records = load_workload(args.replay)
            source = args.replay
            # A faithful replay re-offers the artifact's exact shapes —
            # if the artifact was recorded against a different model
            # config (a preset server, say, vs this driver's fallback
            # cfg), rebuild the engine config around the recorded
            # resolution instead of failing every ticket on a shape
            # mismatch. Only unambiguous fixed-resolution artifacts
            # qualify; mixed/ragged traffic keeps the configured cfg.
            shapes = {
                tuple(r["shape"]) for r in records
                if r.get("shape") is not None
                and str(r.get("signature", "")).startswith("bucket:")
            }
            if len(shapes) == 1:
                (c, h, w), = shapes
                if h == w and (c, h) != (cfg.channels, cfg.image_size):
                    patch = next(
                        p for p in (cfg.patch_size, 7, 4, 2, 1)
                        if h % p == 0
                    )
                    cfg = dataclasses.replace(
                        cfg, channels=c, image_size=h, patch_size=patch,
                    )
                    emit(
                        {"note": f"replay artifact carries {c}x{h}x{w} "
                         "requests; rebuilding the engine config to "
                         "match the recorded resolution"},
                        kind="note",
                    )
        else:
            scen_kw = {}
            if args.scenario_crowd_rps is not None:
                if args.scenario != "flash-crowd":
                    ap.error("--scenario-crowd-rps only applies to "
                             "--scenario flash-crowd")
                scen_kw["crowd_rps"] = args.scenario_crowd_rps
            if args.class_mix is not None:
                from glom_tpu.serve.workload import parse_class_mix

                scen_kw["class_mix"] = parse_class_mix(args.class_mix)
            records = generate(
                args.scenario, args.scenario_duration,
                seed=args.scenario_seed,
                shapes=((cfg.channels, cfg.image_size, cfg.image_size),),
                **scen_kw,
            )
            source = f"scenario:{args.scenario}"
        if args.elastic_ab:
            run_elastic_ab(
                cfg, scfg, label, records,
                source=source,
                time_scale=args.time_scale,
                out_prefix=args.elastic_ab_out,
                # The acceptance gate rides the flash-crowd scenario:
                # the crowd is exactly the shape anticipation must beat.
                gate="flash-crowd" in source,
            )
            return 0
        if args.qos_ab:
            if not any(rec.get("slo_class") for rec in records):
                ap.error("--qos-ab needs classed arrivals: record the "
                         "workload with classes or pass --class-mix "
                         "(e.g. 'premium=0.2,standard=0.3,batch=0.5')")
            run_qos_ab(
                cfg, scfg, label, records,
                source=source,
                time_scale=args.time_scale,
                out_prefix=args.qos_ab_out,
                # Same shape as the elastic gate: the flash crowd is
                # exactly the contention QoS must arbitrate.
                gate="flash-crowd" in source,
            )
            return 0
        run_workload(
            cfg, scfg, label, records,
            source=source,
            time_scale=args.time_scale,
            workload_out=args.workload_out,
        )
        return 0
    if args.ramp:
        run_ramp(cfg, scfg, label, profile=args.ramp_profile)
        return 0
    if args.trace_ab:
        run_trace_ab(
            cfg, scfg, label,
            n_requests=n_requests,
            n_engines=args.engines,
        )
        return 0
    if args.phase_ab:
        run_phase_ab(
            cfg, scfg, label,
            n_requests=n_requests,
            n_engines=args.engines,
        )
        return 0
    if args.banded_ab:
        run_banded_ab(
            cfg, scfg, label,
            n_streams=args.streams,
            n_frames=args.frames,
            perturb=args.perturb if args.perturb is not None else 0.05,
        )
        return 0
    if args.ragged:
        run_ragged(
            cfg, scfg, label,
            n_streams=args.streams,
            n_frames=args.frames,
            perturb=args.perturb if args.perturb is not None else 0.05,
        )
        return 0
    if args.temporal and args.delta:
        run_temporal_delta(
            cfg, scfg, label,
            n_streams=args.streams,
            n_frames=args.frames,
            cameras=args.cameras,
            perturb=args.perturb if args.perturb is not None else 0.5,
            period=args.delta_period,
            atol=args.delta_atol,
        )
        return 0
    if args.temporal:
        run_temporal(
            cfg, scfg, label,
            n_streams=args.streams,
            n_frames=args.frames,
            perturb=args.perturb if args.perturb is not None else 0.05,
            n_engines=args.engines,
        )
        return 0
    run_sweep(
        cfg, scfg, label,
        n_requests=n_requests,
        load_fracs=load_fracs,
        ceiling_repeats=ceiling_repeats,
        n_engines=args.engines,
    )
    if args.two_tier_ab:
        run_two_tier_ab(
            cfg, scfg, label,
            n_requests=n_requests,
            hard_frac=args.hetero,
            n_engines=args.engines,
        )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
