"""Ring-vs-Ulysses crossover probe: the single-chip-measurable component.

docs/PARALLELISM.md claims Ulysses wins when L >= seq and n/seq is too
small to feed the MXU. With one physical chip, the COMM side (ring's
seq-1 ppermute hops vs Ulysses' one-shot all_to_all — both O(n*d*L/seq)
volume) cannot be measured; what CAN be measured is the COMPUTE-SHAPE
side of the claim, which is the mechanism behind it:

  * ring: each device runs seq sequential attention steps over
    [n/seq x n/seq] similarity chunks per level (L-batched small matmuls
    + seq-1 online-softmax combine passes);
  * ulysses: one dense attention over the FULL [n x n] similarity for
    L/seq levels (big matmuls, one softmax).

Total device FLOPs are identical (2 * n^2/seq * L * d per einsum either
way); the difference is pure matmul granularity + online-softmax
overhead — measured here per (n, seq, L) on the real chip, bf16, B=1.
Appends schema-stamped JSONL rows (kind "bench", watchdog backend state
riding every row via bench_bootstrap) to results/sp_crossover.jsonl.
"""

import json

import jax
import jax.numpy as jnp
from jax import lax

from glom_tpu.ops.consensus import consensus_attention
from glom_tpu.telemetry.sinks import emit
from glom_tpu.utils.helpers import l2norm
from glom_tpu.utils.metrics import detect_chip
from glom_tpu.utils.timing import calibrated_chain_time


def ring_compute(levels_full, n_loc, seq):
    """The per-device compute of one ring consensus pass, comms elided:
    queries = this shard's n_loc rows; k/v chunks arrive over `seq` steps
    (here: sliced from the resident full array — same matmul shapes and
    online-softmax combine as ring.py, zero ppermute)."""
    b, n, L, d = levels_full.shape
    q = levels_full[:, :n_loc]  # this shard's query band
    scale = d ** -0.5

    def step(s, carry):
        m, l, acc = carry
        kv = lax.dynamic_slice_in_dim(levels_full, s * n_loc, n_loc, axis=1)
        k = l2norm(kv)
        sim = jnp.einsum("bild,bjld->blij", q, k) * scale
        m_new = jnp.maximum(m, jnp.max(sim, axis=-1, keepdims=True))
        p = jnp.exp(sim - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.einsum("blij,bjld->bild", p.astype(levels_full.dtype), kv)
        acc_new = acc * corr.transpose(0, 2, 1, 3) + pv
        return m_new, l_new, acc_new

    m0 = jnp.full((b, L, n_loc, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, L, n_loc, 1), jnp.float32)
    a0 = jnp.zeros((b, n_loc, L, d), jnp.float32)
    m, l, acc = lax.fori_loop(0, seq, step, (m0, l0, a0))
    return acc / l.transpose(0, 2, 1, 3)


def main():
    chip = detect_chip()
    on_tpu = chip != "cpu"
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    # (side, L, seq, d, B). Beyond the original d=512/L=8/B=1 grid, the
    # shapes the auto-selector actually GOVERNS (round-4 missing #4 — the
    # threshold must not be an extrapolation): the pod preset's
    # d=1024/L=12, the imagenet64-local L=6 class, and BATCHED rows (the
    # selector's working-set model claims b multiplies instance count on
    # both sides without moving the per-instance spill point — these rows
    # are that claim's check), at n spanning the modeled crossover
    # (runtime.ulysses_preferred: per-instance sim working set n^2*4 vs
    # VMEM).
    cases = (
        [(16, 8, s, 512, 1) for s in (2, 4, 8)]  # n=256: small-n/seq regime
        + [(32, 8, s, 512, 1) for s in (2, 4, 8)]  # n=1024
        + [(64, 8, s, 512, 1) for s in (2, 4)]  # n=4096: MXU fed either way
        + [(16, 12, s, 1024, 1) for s in (2, 4)]  # pod shape, n=256
        + [(32, 12, s, 1024, 1) for s in (2, 4)]  # pod shape, n=1024
        + [(64, 12, 2, 1024, 1)]                  # pod shape, n=4096
        + [(16, 6, 2, 512, 1), (32, 6, 2, 512, 1), (64, 6, 2, 512, 1)]
        + [(32, 8, 2, 512, 8), (64, 8, 2, 512, 8)]  # batched: b-independence
    ) if on_tpu else [(8, 4, 2, 64, 1)]

    for side, L, seq, d, B in cases:
        n = side * side
        levels = jax.random.normal(
            jax.random.PRNGKey(side + seq), (B, n, L, d), dtype
        )

        def ring_chain(k, _lv=levels, _s=seq, _nl=n // seq):
            def body(i, acc):
                out = ring_compute(_lv + acc.astype(_lv.dtype), _nl, _s)
                return jnp.sum(out).astype(jnp.float32) * 1e-9
            return lax.fori_loop(0, k, body, jnp.float32(0.0))

        def uly_chain(k, _lv=levels[:, :, : max(L // seq, 1)], _n=n):
            # ulysses local compute: full n, L/seq levels, dense
            def body(i, acc):
                out = consensus_attention(
                    _lv + acc.astype(_lv.dtype), attend_self=False
                )
                return jnp.sum(out).astype(jnp.float32) * 1e-9
            return lax.fori_loop(0, k, body, jnp.float32(0.0))

        # target_s=2.5: the fastest cases here are ~5 us/op; a long chain
        # keeps (t_chain - rtt) well clear of the round-trip jitter.
        t_ring = calibrated_chain_time(
            jax.jit(ring_chain), levels, repeats=4, calib_k=8, target_s=2.5
        )
        t_uly = calibrated_chain_time(
            jax.jit(uly_chain), levels, repeats=4, calib_k=8, target_s=2.5
        )
        rec = {
            "metric": (
                f"sp_crossover ulysses_speedup (n={n}, L={L}, seq={seq}, "
                f"d={d}, B={B}, {chip})"
            ),
            "value": round(t_ring / t_uly, 3),
            "unit": "x",
            "n": n, "L": L, "seq": seq, "d": d, "B": B,
            "ring_compute_ms": round(t_ring * 1e3, 4),
            "ulysses_compute_ms": round(t_uly * 1e3, 4),
            "ulysses_speedup": round(t_ring / t_uly, 3),
            "chip": chip,
        }
        stamped = emit(rec)
        if on_tpu:
            with open("results/sp_crossover.jsonl", "a") as f:
                f.write(json.dumps(stamped) + "\n")


if __name__ == "__main__":
    import argparse

    from glom_tpu.telemetry.sinks import bench_bootstrap

    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--trace-dir", default=None, metavar="DIR",
        help="capture an XProf trace of the measured chains into DIR",
    )
    args = ap.parse_args()
    if not bench_bootstrap("sp_crossover ulysses_speedup", "x"):
        raise SystemExit(1)
    if args.trace_dir:
        from glom_tpu.tracing.capture import trace

        with trace(args.trace_dir):
            main()
        emit({"note": "xla-trace captured", "trace_dir": args.trace_dir},
             kind="note")
    else:
        main()
