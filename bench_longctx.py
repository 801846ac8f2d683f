"""Long-context benchmark: consensus+update at large patch counts n.

The patch axis n is GLOM's sequence axis (SURVEY.md §2.2): at the flagship
ImageNet-224/14 config n is only 256 and the grouped MLPs dominate, but at
larger images / smaller patches (n = 1024, 4096, ...) the O(n^2) consensus
attention takes over — the regime the blockwise Pallas kernel
(kernels/consensus_update.py) and its block-sparse local-radius skipping
exist for.

Measures one consensus+mean update (the scan body's attention half) at
L=6, d=512, bf16 on one chip, for each implementation:

  * dense   — the XLA composition that materializes the [L, B, n, n]
              similarity (ops/consensus.py semantics via _xla_reference);
  * fused   — the blockwise Pallas kernel, O(n) memory;
  * both again at local radius 7 (BASELINE config 3's window), where the
    fused kernel skips j-tiles entirely outside the radius band while the
    dense path still pays the full n^2.

Timing: same methodology as bench.py (chained fori_loop, scalar-fetch sync,
per-op = (t_chain - t_rtt) / k with an auto-calibrated chain length — see
glom_tpu/utils/timing.py), except the chain length adapts per variant
because op costs here span µs..ms.

Writes one schema-stamped JSON line per measurement to stdout (kind
"bench"; failed rows — OOM, compile errors — are kind "error" with value
null, never a fake number) and appends them to results/longctx_bench.jsonl.
Every row carries the watchdog backend state (bench_bootstrap registers it
before any backend touch).
"""

import argparse
import json

import jax
import jax.numpy as jnp

from glom_tpu.kernels.consensus_update import _xla_reference, fused_consensus_update
from glom_tpu.telemetry.sinks import emit
from glom_tpu.utils.metrics import detect_chip
from glom_tpu.utils.timing import calibrated_chain_time


def bench_variant(name, op, levels, bu, td, side, radius, repeats,
                  flops_mult=1):
    # levels/bu/td ride as jit ARGUMENTS, not closure constants: closed-over
    # arrays embed in the lowered program (B=8, n=4096 -> 200MB+).
    def multi(lv, bu_, td_, k):
        def body(_, acc):
            # genuinely data-dependent ~1e-9-scale coupling (an `acc*0`
            # form could be folded, letting the compiler hoist the body)
            out = op(lv + acc.astype(lv.dtype), bu_, td_,
                     side=side, radius=radius)
            # FULL-output reduction: a partial slice would let XLA
            # dead-code-eliminate the unobserved rows/levels of the
            # dense einsums (measured: "847 TF/s" dense at radius 7).
            return jnp.sum(out).astype(jnp.float32) * 1e-9

        return jax.lax.fori_loop(0, k, body, jnp.float32(0.0))

    multi_jit = jax.jit(multi)

    # calibrated_chain_time re-measures RTT right before the measured chain
    # (a per-n RTT taken minutes earlier would drift).
    per_call = calibrated_chain_time(
        lambda k: multi_jit(levels, bu, td, k), levels, repeats=repeats
    )
    L, B, n, d = levels.shape
    # Dense-equivalent attention FLOPs (two n^2 contractions); for radius
    # runs this is the work the dense path still does and the fused kernel
    # skips, so fused radius throughput can exceed "peak" — that's the point.
    tflops_equiv = flops_mult * 4 * B * L * n * n * d / per_call / 1e12
    rec = {"impl": name, "n": n, "radius": radius,
           "ms_per_call": round(per_call * 1e3, 3),
           "dense_equiv_tflops": round(tflops_equiv, 2)}
    if B > 1:
        rec["batch"] = B
    return rec


def main(only_sides=None, batch=1):
    chip = detect_chip()
    on_tpu = chip != "cpu"
    L, B, d = 6, batch, 512
    # side 16 = the flagship n=256 (anchors the dispatch crossover at the
    # config the train bench runs); side 96 -> n=9216, the past-the-old-cap
    # long-context point the streamed backward unlocked (dense grad at this
    # n materializes a ~2GB sim twice — measured if it fits, recorded as
    # oom otherwise).
    sides = (16, 32, 64, 96) if on_tpu else (8,)
    if only_sides is not None:
        if not only_sides:
            raise ValueError("--sides given but empty; pass side values")
        sides = tuple(only_sides)
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    repeats = 3 if on_tpu else 2

    def dense(lv, bu, td, *, side, radius):
        return _xla_reference(lv, bu, td, side=side, radius=radius, attend_self=False)

    def fused(lv, bu, td, *, side, radius):
        return fused_consensus_update(lv, bu, td, side=side, radius=radius)

    def fused_bw(lv, bu, td, *, side, radius):
        # force the blockwise backward so the kernel is measured even where
        # the auto dispatch would (correctly) route to the dense VJP
        return fused_consensus_update(
            lv, bu, td, side=side, radius=radius, bwd_impl="blockwise"
        )

    def grad_of(op):
        def gop(lv, bu_, td_, *, side, radius):
            def loss(a, b, c):
                out = op(a, b, c, side=side, radius=radius)
                return jnp.mean(out.astype(jnp.float32) ** 2)

            glv, gbu, gtd = jax.grad(loss, argnums=(0, 1, 2))(lv, bu_, td_)
            # same output contract as the fwd ops so bench_variant's full-sum
            # sync covers every gradient element
            return glv + gbu + jnp.concatenate([gtd, gtd[:1]], axis=0)

        return gop

    for side in sides:
        n = side * side
        key = jax.random.PRNGKey(side)
        k1, k2, k3 = jax.random.split(key, 3)
        levels = jax.random.normal(k1, (L, B, n, d), dtype)
        bu = jax.random.normal(k2, (L, B, n, d), dtype)
        td = jax.random.normal(k3, (L - 1, B, n, d), dtype)
        variants = [
            ("dense_xla", dense, 1),
            ("fused_pallas", fused, 1),
            # training direction: value+grad through the op (bwd counted as
            # 2x fwd) — the dense VJP materializes [L, B, n, n] TWICE
            # (fwd + bwd); the blockwise backward keeps O(n) memory
            ("dense_xla_grad", grad_of(dense), 3),
            ("fused_pallas_grad", grad_of(fused_bw), 3),
            ("auto_dispatch_grad", grad_of(fused), 3),
        ]
        for radius in (0.0, 7.0):
            for name, op, mult in variants:
                label = (
                    f"longctx {name} (n={side * side}, radius={radius:g}, "
                    f"B={B}, {chip})"
                )
                try:
                    rec = bench_variant(
                        name, op, levels, bu, td, side, radius, repeats,
                        flops_mult=mult,
                    )
                    rec.update(
                        metric=label, value=rec["ms_per_call"], unit="ms/call"
                    )
                    kind = "bench"
                except Exception as e:  # noqa: BLE001 - record OOM/compile fails
                    # An unmeasurable row is an "error" record with value
                    # null — the compare gate reads it as MISSING, never as
                    # a zero or an infinitely-fast kernel.
                    rec = {"metric": label, "value": None, "unit": "ms/call",
                           "impl": name, "n": side * side, "radius": radius,
                           "error": f"{type(e).__name__}: {e}"[:200]}
                    kind = "error"
                rec["chip"] = chip
                stamped = emit(rec, kind=kind)
                if on_tpu:
                    # append-as-you-go: a failure mid-run must not lose
                    # the completed measurements
                    with open("results/longctx_bench.jsonl", "a") as f:
                        f.write(json.dumps(stamped) + "\n")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--sides", type=int, nargs="*", default=None,
        help="restrict to these grid sides (rerun specific rows)",
    )
    ap.add_argument(
        "--batch", type=int, default=1,
        help="batch size B (the batched long-row regime record)",
    )
    ap.add_argument(
        "--trace-dir", default=None, metavar="DIR",
        help="capture an XProf trace of the measured chains into DIR",
    )
    args = ap.parse_args()
    from glom_tpu.telemetry.sinks import bench_bootstrap

    if not bench_bootstrap("longctx consensus ms_per_call", "ms/call"):
        raise SystemExit(1)
    if args.trace_dir:
        from glom_tpu.tracing.capture import trace

        with trace(args.trace_dir):
            main(args.sides, batch=args.batch)
        emit({"note": "xla-trace captured", "trace_dir": args.trace_dir},
             kind="note")
    else:
        main(args.sides, batch=args.batch)
