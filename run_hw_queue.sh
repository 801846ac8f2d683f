#!/usr/bin/env bash
# Hardware work queue: everything that needs the real TPU chip, in
# priority order, each step logged and failure-isolated. Steps run one
# after another — a chip belongs to one process at a time. chip_smoke.py
# is the quick check that the main paths start; this is the long form.
#
# Usage: bash run_hw_queue.sh        (from the checkout root, on a TPU host)
set -u
cd "$(dirname "$0")"
mkdir -p results/hw_queue
log() { echo "=== [$(date +%H:%M:%S)] $*"; }

step() {  # step <name> <timeout_s> <cmd...>; returns the command's rc
    local name=$1 to=$2; shift 2
    log "START $name"
    timeout "$to" "$@" 2>&1 | tee "results/hw_queue/${name}.log"
    local rc=${PIPESTATUS[0]}
    log "DONE $name (rc=$rc)"
    return "$rc"
}

# 0. Pre-flight: glom-lint (glom_tpu/analysis) over the tree against the
#    reviewed baseline. Pure-CPU whole-program AST pass, seconds — a
#    hardware window must never start on code with a known
#    collective/schema/lockset violation (exactly the class of silent
#    mismatch that burns a pod session before anyone notices the
#    evidence trail is wrong). The fingerprint cache makes repeat queue
#    runs near-instant; staleness is content-hashed per dependency
#    closure, so a stale reuse is impossible, not just unlikely.
step lint 300 python -m glom_tpu.analysis glom_tpu/ --baseline analysis_baseline.json \
    --cache results/hw_queue/lint_cache.json || {
    log "glom-lint found NEW violations — fix (or review into the baseline) before burning a hardware window"; exit 1; }

# 0b. Gate: is the backend actually up? (bounded — never hangs)
step probe 120 python -c "import jax; print(jax.devices())" || true
grep -q "TpuDevice\|tpu" results/hw_queue/probe.log || {
    log "backend still down; aborting queue"; exit 1; }

# 1. Hardware parity first (16 checks incl. the fused-loop
#    primal-vs-VJP, remat-grad and banded checks) — the
#    measurement steps below are meaningless if these fail, so a parity
#    failure STOPS the queue here.
step tpu_validate 2400 python -u tpu_validate.py || {
    log "hardware parity FAILED — not measuring on broken kernels"; exit 1; }

# 2. The driver metric of record: fwd + train-step lines.
step bench 2400 python -u bench.py

# 3. Pod per-TP-rank anchor — round 4 measured 673 on the scan-path
#    backward; the whole-loop VJP (remat mode, unchained dw) now covers
#    this shape. Median of 3.
for i in 1 2 3; do
    step "pod_anchor_$i" 1800 python -u bench_train.py --preset imagenet224-pod --batch 16 --mult 2
done

# 4. Batch-128 point on the AUTO-ROUTED path (grad_accum=2 over
#    batch-64 fused-loop microbatches; round-4 scan-path row was 3489 =
#    0.96x vs baseline).
for i in 1 2 3; do
    step "batch128_$i" 1800 python -u bench_train.py --batch 128
done

# 5. SP crossover rows at the shapes the selector governs (pod
#    d=1024/L=12, L=6 class, batched B=8) — appends to
#    results/sp_crossover.jsonl; re-run the table-driven selector test
#    afterwards.
step sp_crossover 2400 python -u bench_sp_crossover.py

# 7. ZeRO weight-update A/Bs (this round's distributed-optimizer PR):
#    zero_stage 0 vs 1 vs 2-with-accum, and quantized vs f32 reduce, at
#    dp = all visible devices (needs >= 2: the four-chip host; on one
#    chip the script exits 1 and the queue moves on);
#    at dp>=8, expect zero1 ~= zero0 step time (same total wire bytes,
#    (dp-1)/dp*(G+P) vs 2(dp-1)/dp*G) with opt-state HBM down ~dp x.
for i in 1 2 3; do
    step "zero_ab_$i" 1800 python -u bench_zero.py
done

# 8. Pod-shape ZeRO anchor: the per-TP-rank single-chip anchor (step 3)
#    re-run with sharded-update analytics stamped on the record — pairs
#    with the dp=64 pod projection in docs/PARALLELISM.md (ZeRO section).
step pod_zero_record 1800 python -u bench_train.py --preset imagenet224-pod --batch 16 --mult 2

# 9. Telemetry overhead A/B on the real chip (the < 2% per-step bar for
#    telemetry_level=scalars; docs/OBSERVABILITY.md) — if this exceeds
#    budget on hardware, the scalars bundle needs a diet before the
#    always-on rollout.
step telemetry_ab 1800 python -u bench_train.py --telemetry-ab

# 9b. Span-overhead bar (< 1% per-step for the fit loop's host spans) and
#     the per-preset memory table with MEASURED HBM watermarks — the
#     analytic live-bytes model finally reconciled against a real
#     allocator (docs/OBSERVABILITY.md, HBM accounting).
step span_ab 900 python -u bench_train.py --span-ab
step memory_table 900 python -u bench_train.py --memory-table

# 9c. One step-windowed XLA trace of the flagship loss-curve path (steps
#     20:24, past compile) for the XProf phase breakdown — trace dir is
#     stamped into the log's note records.
step trace_capture 1800 python -u bench_train.py --loss-curve 30 \
    --out results/hw_queue/trace_curve.jsonl \
    --trace-steps 20:24 --trace-dir results/hw_queue/xla_trace

# 9d-. Chaos gate BEFORE the serve sweep (docs/RESILIENCE.md): SIGKILL a
#      real training worker mid-run and require the resumed worker to
#      finish with a continuous, schema-clean evidence trail. A serving
#      stack about to be load-swept on real hardware must first prove it
#      survives a kill — recovery bugs found during the sweep burn the
#      window.
step chaos 1200 python -m glom_tpu.resilience --scenario kill-train \
    --dir results/hw_queue/chaos --steps 6 || {
    log "chaos kill-and-resume FAILED — not sweeping a serving stack that cannot recover"; exit 1; }

# 9d--. Pod-preemption gate (docs/RESILIENCE.md, coordinated preemption):
#       SIGTERM a strict subset of a 2-process pod, then all of it — the
#       two-phase save barrier must commit ONE common step on every host
#       inside the grace deadline and the relaunched gang must resume
#       from it. A pod about to burn a real multi-host window must first
#       prove its grace save cannot leave hosts committed at different
#       steps (the silent-inconsistent-resume failure class).
step chaos_pod 1200 python -m glom_tpu.resilience --scenario preempt-pod \
    --dir results/hw_queue/chaos_pod --steps 8 --hosts 2 || {
    log "pod-preemption barrier FAILED — an uncoordinated pod checkpoint would corrupt the window's resume"; exit 1; }

# 9d. Serving SLO sweep (glom_tpu/serve, docs/SERVING.md): AOT warmup per
#     bucket, closed-loop throughput ceiling, offered-load p50/p95/p99
#     latency rows, and the consensus early-exit iteration histogram on
#     the flagship bf16 fused route. Gated against its own baseline in
#     step 11b.
step bench_serve 2400 python -u bench_serve.py

# 9e. Pod-scale serving (this round's tentpole, docs/SERVING.md): the
#     two-tier exit A/B over heterogeneous traffic with 2-engine fan-out
#     (the serve_mean_executed_iters pair is the measured per-request
#     early-exit win), then the SHARDED engine route — every bucket
#     through the (data=4) serve mesh with the while-loop witness
#     collectives counted on the bucket_stats records. First live window:
#     read the sharded ceiling vs 9d's single-chip ceiling (the
#     serve-mesh wire cost is provisioned at the budget, so the delta is
#     the real witness-psum price), then baseline both via step 11b.
step bench_serve_two_tier 2400 python -u bench_serve.py --engines 2 --two-tier-ab --hetero 0.5
step bench_serve_sharded 2400 python -u bench_serve.py --mesh-data 4

# 9f. Streaming warm-start A/B (this round's tentpole, docs/SERVING.md
#     "Streaming"): frame-sequence traffic per stream through the
#     session column cache vs cold-start — the
#     serve_temporal_mean_iters pair plus serve_temporal_iters_saved is
#     the measured per-request win on real hardware (bf16 flagship
#     route: the warm levels0 staging and donation actually resolve
#     here, unlike the CPU smoke). Baselined via step 11b.
step bench_serve_temporal 2400 python -u bench_serve.py --temporal --streams 8 --frames 6

# 9h. Ragged paged sweep + paged warm-path A/B (this round's tentpole,
#     docs/SERVING.md "Paged column memory"/"Ragged admission"): the
#     same mixed-resolution streamed traffic served padded through the
#     bucket ladder vs packed through the ragged page ladder. On real
#     hardware this measures what the CPU smoke cannot: the actual
#     PCIe-vs-HBM warm-path dispatch latency delta (the paged arm's
#     levels0_h2d_bytes is 0 — its warm state never leaves HBM) and the
#     MXU time the pad tokens stop burning. The serve_pad_waste pair,
#     both arms' warm/cold dispatch-latency rows, and the per-arm
#     levels0_h2d_bytes feed the step 11b serve compare baseline (pad
#     and h2d rows gate as COSTS — telemetry/compare.py).
step bench_serve_ragged 2400 python -u bench_serve.py --ragged --streams 8 --frames 6
step ragged_gate 120 python - results/hw_queue/bench_serve_ragged.log <<'EOF'
import sys
from glom_tpu.telemetry import schema
rows = [r for _, r in schema.iter_json_lines(open(sys.argv[1]))]
waste, h2d = {}, {}
for r in rows:
    m = r.get("metric", "")
    if m.startswith("serve_pad_waste ("):
        waste[m.split("(")[1].split(",")[0]] = r["value"]
    if m.startswith("serve_levels0_h2d_bytes ("):
        h2d[m.split("(")[1].split(",")[0]] = (r["value"], r.get("n_page_warm", 0))
assert set(waste) == {"bucket-ladder", "ragged-paged"}, f"arms missing: {waste}"
assert waste["ragged-paged"] < waste["bucket-ladder"], f"pad waste not reduced: {waste}"
b, w = h2d.get("ragged-paged", (None, 0))
assert b == 0 and w > 0, f"paged warm path not zero-transfer: {h2d}"
print(f"OK: pad waste {waste['bucket-ladder']}% -> {waste['ragged-paged']}%; "
      f"0 warm levels0 bytes over {w} page-warm rows")
EOF

# 9i. Delta streaming A/B gate (ISSUE 12, docs/SERVING.md "Delta
#     streaming"): whole-state paged warm vs delta-chain storage + the
#     sparse incremental route over O(1)-shaped frame traffic (shared
#     scene bases, bitwise holds, a one-patch moving region). On real
#     hardware this prices what the CPU smoke cannot: the residual
#     probe + sparse scatter on the device write-back path, and the HBM
#     actually freed per live stream. The gate requires the delta arm
#     STRICTLY below whole-state on BOTH mean executed iters/frame
#     (and < 2) and bytes_per_stream (>= 3x), with the threshold-0
#     reconstruction parity probe BITWISE — rows feed the step 11b
#     serve baseline (bytes/chain rows gate as costs).
step bench_serve_delta 2400 python -u bench_serve.py --temporal --delta --streams 8 --frames 16
step delta_gate 120 python - results/hw_queue/bench_serve_delta.log <<'EOF'
import sys
from glom_tpu.telemetry import schema
rows = [r for _, r in schema.iter_json_lines(open(sys.argv[1]))]
iters, bps, parity = {}, {}, None
for r in rows:
    m = r.get("metric", "")
    if m.startswith("serve_delta_mean_iters ("):
        iters[m.split("(")[1].split(",")[0]] = r["value"]
    if m.startswith("serve_delta_bytes_per_stream ("):
        bps[m.split("(")[1].split(",")[0]] = r["value"]
    if m.startswith("serve_delta_parity ("):
        parity = r["value"]
assert set(iters) == {"whole-state", "delta"}, f"arms missing: {iters}"
assert iters["delta"] < 2.0 and iters["delta"] < iters["whole-state"], (
    f"incremental path did not beat the bar: {iters}")
assert bps["delta"] * 3 <= bps["whole-state"], f"bytes not >=3x down: {bps}"
assert parity == 1.0, "threshold-0 delta reconstruction is NOT bitwise"
print(f"OK: iters {iters['whole-state']} -> {iters['delta']}, bytes/stream "
      f"{bps['whole-state']} -> {bps['delta']}, parity bitwise")
EOF

# 9l. Block-banded consensus + pool-aliasing A/B gate (ISSUE 16,
#     docs/SERVING.md "Block-banded ragged consensus" / "Pool
#     aliasing"): the same ragged streamed traffic under the windowed
#     gather vs the banded route vs banded + in-place aliasing. On real
#     hardware this prices what the CPU smoke cannot: the HBM the
#     W-fold k/v gather actually duplicates per dispatch (the banded
#     working set is page_tokens-fold smaller — the admission ceiling
#     moves), and the pool bytes the donated in-place write-back stops
#     copying. The gate requires banded peak_window_bytes STRICTLY
#     below windowed, the largest admissible ragged signature STRICTLY
#     larger, aliased pool bytes moved STRICTLY below CoW with the
#     warm path still zero-transfer, and the threshold-0 parity row
#     BITWISE — rows feed the step 11b serve baseline (peak-window and
#     pool-bytes rows gate as costs).
step bench_serve_banded 2400 python -u bench_serve.py --banded-ab --streams 8 --frames 6
step banded_gate 120 python - results/hw_queue/bench_serve_banded.log <<'EOF'
import sys
from glom_tpu.telemetry import schema
rows = [r for _, r in schema.iter_json_lines(open(sys.argv[1]))]
peak, sig, moved, h2d, parity = {}, {}, {}, {}, None
for r in rows:
    m = r.get("metric", "")
    if m.startswith("serve_ragged_peak_window_bytes ("):
        peak[m.split("(")[1].split(",")[0]] = r["value"]
    if m.startswith("serve_ragged_max_signature_pages ("):
        sig[m.split("(")[1].split(",")[0]] = r["value"]
    if m.startswith("serve_pool_bytes_moved ("):
        moved[m.split("(")[1].split(",")[0]] = r["value"]
    if m.startswith("serve_levels0_h2d_bytes ("):
        h2d[m.split("(")[1].split(",")[0]] = (r["value"], r.get("n_page_warm", 0))
    if m.startswith("serve_banded_parity ("):
        parity = r["value"]
assert set(peak) == {"windowed", "banded", "banded-alias"}, f"arms missing: {peak}"
assert peak["banded"] < peak["windowed"], f"banded working set not smaller: {peak}"
assert sig["banded"] > sig["windowed"], f"max signature did not grow: {sig}"
assert moved["banded-alias"] < moved["banded"], f"aliasing moved no fewer bytes: {moved}"
b, w = h2d.get("banded-alias", (None, 0))
assert b == 0 and w > 0, f"aliased warm path not zero-transfer: {h2d}"
assert parity == 1.0, "threshold-0 banded vs windowed dispatch is NOT bitwise"
print(f"OK: peak window {peak['windowed']} -> {peak['banded']} bytes; max "
      f"signature {sig['windowed']} -> {sig['banded']} pages; pool bytes "
      f"{moved['banded']} -> {moved['banded-alias']}; parity bitwise")
EOF

# 9g. Request-tracing overhead gate + pod aggregation (this round's
#     tentpole, docs/OBSERVABILITY.md): full trace stamping (ids minted
#     per submit, per-dispatch scope, per-request resolve leaves) must
#     cost < 2% end-to-end latency on real hardware — the A/B emits
#     serve_trace_overhead in percent and the gate reads it back. Then
#     the preempt-pod gate's per-host streams (step 9d--) must merge
#     into ONE consistent pod timeline: clock families reconciled via
#     the anchor records, barrier chains complete, --strict gating.
step bench_serve_trace_ab 2400 python -u bench_serve.py --trace-ab
step trace_overhead_gate 120 python - results/hw_queue/bench_serve_trace_ab.log <<'EOF'
import sys
from glom_tpu.telemetry import schema  # noise-tolerant line reader
rows = [r for _, r in schema.iter_json_lines(open(sys.argv[1]))]
ov = [r for r in rows if r.get("metric", "").startswith("serve_trace_overhead")]
assert ov, "no serve_trace_overhead row in the trace A/B log"
v = ov[-1]["value"]
assert isinstance(v, (int, float)), f"trace overhead UNMEASURED: {ov[-1]}"
assert v <= 2.0, f"trace overhead {v}% exceeds the 2% stamping budget"
print(f"OK: trace stamping overhead {v}% within the 2% budget")
EOF
step pod_aggregate 300 python -m glom_tpu.telemetry aggregate \
    results/hw_queue/chaos_pod/metrics_h0.jsonl \
    results/hw_queue/chaos_pod/metrics_h1.jsonl --strict --timeline 20

# 9j. Capacity observatory (ISSUE 13, docs/OBSERVABILITY.md): the first
#     real TPU window measures per-collective wall-time on the manual
#     zero1 path (the standing hardware-window debt item) and RE-FITS
#     the α-β comm_time_model from the measured points — the
#     collective_time rows land in the bench log, so the next window's
#     drift is priced against THIS window's fit via the compare gate.
#     Both overhead gates hold the <2% bar on real hardware: the sampled
#     timing harness amortized at the deployed cadence, and the dispatch
#     phase split (queue_wait/pack/h2d/device/resolve) on the serve path
#     — on a real chip the h2d/device split finally prices the PCIe-vs-
#     HBM boundary the CPU smoke cannot see.
step collective_timing_ab 1800 python -u bench_train.py --collective-timing-ab
step collective_timing_gate 120 python - results/hw_queue/collective_timing_ab.log <<'EOF'
import sys
from glom_tpu.telemetry import schema
rows = [r for _, r in schema.iter_json_lines(open(sys.argv[1]))]
ov = [r for r in rows if r.get("metric", "").startswith("collective_timing_overhead")]
assert ov, "no collective_timing_overhead row in the A/B log"
v = ov[-1]["value"]
assert isinstance(v, (int, float)), f"timing overhead UNMEASURED: {ov[-1]}"
assert v <= 2.0, f"sampled collective-timing overhead {v}% exceeds the 2% bar"
sites = [r for r in rows if r.get("kind") == "collective_time"
         and r.get("site") not in (None, "comm_time_model")]
model = [r for r in rows if r.get("site") == "comm_time_model"]
assert sites and model, "no measured collective_time rows / model fit in the log"
assert all(r["wall_ms"] > 0 for r in sites), "zero wall_ms on a measured site"
print(f"OK: timing overhead {v}% within 2%; {len(sites)} sites measured, "
      f"alpha={model[-1]['alpha_ms']}ms beta={model[-1]['beta_ms_per_byte']}ms/B")
EOF
step phase_ab 2400 python -u bench_serve.py --phase-ab
step phase_overhead_gate 120 python - results/hw_queue/phase_ab.log <<'EOF'
import sys
from glom_tpu.telemetry import schema
rows = [r for _, r in schema.iter_json_lines(open(sys.argv[1]))]
ov = [r for r in rows if r.get("metric", "").startswith("serve_phase_overhead")]
assert ov, "no serve_phase_overhead row in the phase A/B log"
v = ov[-1]["value"]
assert isinstance(v, (int, float)), f"phase overhead UNMEASURED: {ov[-1]}"
assert v <= 2.0, f"phase-split overhead {v}% exceeds the 2% stamping budget"
print(f"OK: phase-split overhead {v}% within the 2% budget")
EOF

# 9k. Elastic serving ramp gate (ISSUE 15, docs/SERVING.md "Elastic
#     serving"): the offered-load ramp through the REAL autoscaler on
#     real hardware — the spike must scale the fleet OUT (spawn + full
#     AOT warmup off the hot path, admission strictly after precompile),
#     the calm must scale it back IN (graceful drain: migrate sessions,
#     release devices), and every ticket must be conserved. On TPU the
#     spawn_ms row finally prices a real device-group warmup (the number
#     a production autoscaler's dwell must exceed), and the row joins
#     the 11b serve baseline so spawn-latency regressions gate.
step ramp_serve 2400 python -u bench_serve.py --ramp
step ramp_serve_gate 120 python - results/hw_queue/ramp_serve.log <<'EOF'
import sys
from glom_tpu.telemetry import schema
rows = [r for _, r in schema.iter_json_lines(open(sys.argv[1]))]
peak = [r for r in rows if r.get("metric", "").startswith("serve_ramp_n_engines_peak")]
cons = [r for r in rows if r.get("metric", "").startswith("serve_ramp_tickets_conserved")]
assert peak and cons, "ramp rows missing from the elastic bench log"
assert peak[-1]["value"] >= 2, f"fleet never scaled out: {peak[-1]}"
assert peak[-1]["n_scale_ins"] >= 1, f"fleet never scaled back in: {peak[-1]}"
assert cons[-1]["value"] == 1.0, f"ramp tickets NOT conserved: {cons[-1]}"
tl = peak[-1]["timeline"]
print(f"OK: fleet timeline {tl}, tickets conserved")
EOF

# 9m. Workload-observatory gate (ISSUE 17, docs/SERVING.md "Record and
#     replay"): a seeded diurnal scenario replayed through the REAL
#     autoscaler on real hardware. The gate requires exact ticket
#     conservation + the same per-request signature sequence as the
#     artifact, AND live forecast evidence: forecast records on every
#     closed window, each carrying the forecast_abs_err key, with at
#     least one matured (finite) predicted-vs-realized error — a
#     forecast that never scores is the silent-absence failure this
#     observatory exists to kill. Rows join the 11b serve baseline so
#     pacing/forecast regressions gate.
step workload_serve 2400 python -u bench_serve.py --scenario diurnal \
    --scenario-duration 6
step workload_gate 120 python - results/hw_queue/workload_serve.log <<'EOF'
import sys
from glom_tpu.telemetry import schema
rows = [r for _, r in schema.iter_json_lines(open(sys.argv[1]))]
cons = [r for r in rows
        if r.get("metric", "").startswith("serve_workload_tickets_conserved")]
assert cons, "workload rows missing from the bench log"
assert cons[-1]["value"] == 1.0, f"replay tickets NOT conserved: {cons[-1]}"
ws = [r for r in rows if r.get("event") == "workload_summary"][-1]
assert ws["signature_sequence_match"] is True, ws
fc = [r for r in rows if r.get("kind") == "forecast"]
assert fc, "no forecast records emitted over the scenario"
missing = [r for r in fc if "forecast_abs_err" not in r]
assert not missing, f"forecast records without the error key: {missing[:2]}"
scored = [r for r in fc
          if isinstance(r.get("forecast_abs_err"), (int, float))]
assert scored, "no forecast window ever matured (error never scored)"
lag = [r for r in rows
       if r.get("metric", "").startswith("serve_workload_pacing_lag")]
print(f"OK: {len(fc)} forecast records ({len(scored)} scored, last "
      f"abs_err {scored[-1]['forecast_abs_err']}), pacing lag "
      f"{lag[-1]['value'] if lag else '?'}ms, tickets conserved")
EOF

# 9n. Decision-observatory gate (PR 18, docs/SERVING.md "Anticipatory
#     autoscaling" + docs/OBSERVABILITY.md schema v10): the flash-crowd
#     anticipatory-vs-reactive A/B on real hardware — a crowd past one
#     engine's service rate drives the SAME replayed records through the
#     PR 14 reactive baseline and the forecast + warm-pool fleet. The
#     bench ASSERTS the anticipatory arm failed no more tickets AND
#     landed a strictly lower p99; both arms' decision chains must then
#     reconstruct from the JSONL alone under `telemetry audit --strict`
#     (evidence conservation bit-for-bit, chain integrity, regret
#     scored). On TPU the spare's spawn_ms prices a REAL precompiled
#     device-group promote vs a cold spawn. Rows join the 11b serve
#     baseline so regret/late-decision/lead-violation growth gates.
step elastic_ab 2400 python -u bench_serve.py --scenario flash-crowd \
    --scenario-duration 12 --scenario-crowd-rps 400 --elastic-ab \
    --elastic-ab-out results/hw_queue/elastic_ab
step elastic_audit 120 python -m glom_tpu.telemetry audit --strict \
    results/hw_queue/elastic_ab_reactive.jsonl \
    results/hw_queue/elastic_ab_anticipatory.jsonl

# 9o. Multi-tenant QoS gate (ISSUE 19, docs/SERVING.md "SLO classes" +
#     docs/OBSERVABILITY.md schema v11): the same flash crowd, dealt a
#     seeded premium/standard/batch mix, drives a classless shared-FIFO
#     fleet and the deficit-weighted-fair QoS fleet whose lanes
#     PARTITION the same queue depth. The bench ASSERTS premium p99
#     strictly below the classless baseline, batch held at or above the
#     starvation floor, EXACT per-class ticket conservation on both
#     arms, and both decision chains passing `telemetry audit --strict`
#     (weighted regret scored from the stamped class_weights). Rows
#     join the 11b serve baseline so per-class p99 / served-fraction /
#     shed growth gates.
step qos_ab 2400 python -u bench_serve.py --scenario flash-crowd \
    --scenario-duration 12 --scenario-crowd-rps 400 \
    --class-mix 'premium=0.2,standard=0.3,batch=0.5' --qos-ab \
    --qos-ab-out results/hw_queue/qos_ab
step qos_audit 120 python -m glom_tpu.telemetry audit --strict \
    results/hw_queue/qos_ab_classless.jsonl \
    results/hw_queue/qos_ab_qos.jsonl

# 10. Schema lint: every JSON row this queue produced must validate
#     against the versioned event schema (glom_tpu/telemetry/schema.py).
#     Shell noise in the logs is skipped; --allow-unstamped because the
#     scratch harnesses still emit legacy unstamped rows — the
#     bench*.py rows (incl. longctx/sp_crossover since PR 3) are all
#     stamped and validate strictly (CI enforces that on every push).
step schema_lint 300 python -m glom_tpu.telemetry --allow-unstamped results/hw_queue/*.log

# 11. Bench-trajectory regression gate: this queue's metric-of-record rows
#     vs the last committed good trajectory. UNMEASURED rows are MISSING,
#     never zero (the round-5 pollution this gate exists to end); a
#     beyond-noise regression fails the queue loudly. On pass, the fresh
#     rows become the next baseline.
if [ -f results/bench_baseline.jsonl ]; then
    step bench_compare 300 python -m glom_tpu.telemetry compare \
        results/bench_baseline.jsonl results/hw_queue/bench.log || {
        log "bench trajectory REGRESSION (results/hw_queue/bench_compare.log)"
        exit 1
    }
fi
grep -ah '^{' results/hw_queue/bench.log > results/bench_baseline.jsonl 2>/dev/null || true

# 11b. Serving-trajectory gate: the SLO rows (latency percentiles regress
#      UP, throughput/ceiling regress DOWN, auto-iters regress UP — unit-
#      derived) against the last good serve baseline; refresh on pass.
grep -ah '^{' results/hw_queue/bench_serve.log \
    results/hw_queue/bench_serve_two_tier.log \
    results/hw_queue/bench_serve_sharded.log \
    results/hw_queue/bench_serve_temporal.log \
    results/hw_queue/bench_serve_ragged.log \
    results/hw_queue/bench_serve_delta.log \
    results/hw_queue/bench_serve_banded.log \
    results/hw_queue/collective_timing_ab.log \
    results/hw_queue/phase_ab.log \
    results/hw_queue/ramp_serve.log \
    results/hw_queue/workload_serve.log \
    results/hw_queue/elastic_ab.log \
    results/hw_queue/qos_ab.log \
    > results/hw_queue/serve_candidate.jsonl 2>/dev/null || true
if [ -f results/serve_baseline.jsonl ]; then
    step serve_compare 300 python -m glom_tpu.telemetry compare \
        results/serve_baseline.jsonl results/hw_queue/serve_candidate.jsonl || {
        log "serve trajectory REGRESSION (results/hw_queue/serve_compare.log)"
        exit 1
    }
fi
cp results/hw_queue/serve_candidate.jsonl results/serve_baseline.jsonl 2>/dev/null || true

log "queue complete — record numbers with their origin in PERF.md, "
log "docs/PARALLELISM.md (pod anchor + ZeRO table), results/batch_curve.jsonl,"
log "and re-run: python -m pytest tests/test_parallel.py tests/test_zero.py -q"
