"""Chain-timing helpers shared by the bench harnesses (bench*.py).

The benches sync by fetching a device-side-reduced scalar to host (a valid
sync on any backend: the value cannot arrive before the work is done), and
every fetch pays a fixed dispatch + round-trip cost that is not device
throughput. So all benches time K ops chained inside one compiled
fori_loop (a data-dependent carry serializes iterations so the compiler
cannot dedup/overlap/hoist them) and compute

    per_op = (t_chain - t_rtt) / K

with ONE long chain carrying ~seconds of device work and t_rtt measured on
a trivial jitted scalar. A two-chain slope, (t_long - t_short) / dK, was
tried and REJECTED: the chains run at different clock-ramp states and the
slope attributes the ramp to fixed cost — it read 5-25% above the physical
matmul-bound floor (audited against a pure-matmul probe that pinned the
chip's achievable bf16 peak at 196.6 TF/s). The long-chain form can only
over-credit by rtt-jitter / t_chain.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp


def best_fetch_time(fn, *args, repeats: int = 6) -> float:
    """Min wall time of `float(fn(*args))` over `repeats`, after a warm
    (compile) call. `fn` must return a scalar; fetching it to host is the
    sync. Min, not mean: jitter and throttling only ever slow things down,
    and a finiteness check on every fetch catches silent NaNs."""
    warm = float(fn(*args))
    if not jnp.isfinite(warm):
        raise RuntimeError(f"non-finite benchmark output: {warm}")
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = float(fn(*args))
        times.append(time.perf_counter() - t0)
        if not jnp.isfinite(out):
            raise RuntimeError(f"non-finite benchmark output: {out}")
    return min(times)


def measure_rtt(example, repeats: int = 6) -> float:
    """Fixed dispatch+fetch cost of one call: time a trivial jitted scalar
    derived from `example` (kept data-dependent so nothing constant-folds
    the round trip away)."""
    return best_fetch_time(
        jax.jit(lambda x: jnp.sum(x) * 1e-30 + 1.0), example, repeats=repeats
    )


def calibrated_chain_time(
    chain,
    rtt_example,
    *,
    repeats: int = 6,
    calib_k: int = 32,
    target_s: float = 1.0,
    max_k: int = 50_000,
) -> float:
    """Per-iteration time of `chain(k) -> scalar` (k a traced fori_loop
    bound, so ONE jit serves every k). For ops whose cost spans µs..ms the
    chain length must adapt: first estimate per-op cost from a short
    calibration chain, then size k to put ~target_s of device work in the
    measured chain, and return (t_chain - rtt) / k.

    `rtt_example`: a device-resident array the RTT probe reads. RTT is
    re-measured HERE, immediately before the measured chain — a stale RTT
    taken minutes earlier would re-introduce drift the subtraction exists
    to cancel. target_s=1.0 keeps the rtt-jitter error bound at ~2%."""

    def best(k):
        return best_fetch_time(chain, jnp.int32(k), repeats=repeats)

    rtt0 = measure_rtt(rtt_example, repeats=repeats)
    t_calib = best(calib_k)
    per_est = max((t_calib - rtt0) / calib_k, 1e-7)
    k = int(min(max(target_s / per_est, calib_k), max_k))
    rtt = measure_rtt(rtt_example, repeats=repeats)
    per = (best(k) - rtt) / k
    if per <= 0:
        raise RuntimeError(f"degenerate chain timing: k={k} rtt={rtt:.4f}")
    return per
