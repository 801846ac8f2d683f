"""Structured sinks: step-time histograms and the stamped bench emitter.

Two host-side pieces that complete the telemetry loop:

  * StepTimeStats — wall-clock per-step durations with the COMPILE step
    split out (the first step of a jitted loop is trace+compile; folding
    it into steady-state percentiles made round 4's "slow step" reports
    unreadable). Logging records carry p50/p95/max of steady state plus
    the compile time, so a step-time regression and a compile-time
    regression are separately attributable. Dispatch is async under jax —
    non-logging steps measure enqueue time, logging steps (which fetch the
    metrics) absorb the device sync, so p95/max bound the true step time
    while p50 tracks dispatch; docs/OBSERVABILITY.md spells out the
    reading. Pure host arithmetic: nanoseconds per step of overhead.

  * emit() — the benches' print(json.dumps(...)) replacement: stamps
    schema_version/kind and the current watchdog backend state on the
    record, so driver-parsed bench lines and trainer JSONL are one
    schema (`python -m glom_tpu.telemetry.schema` lints them all).

  * bench_bootstrap() — the shared gate every bench entrypoint runs
    first: place the compile cache, probe the backend through the
    watchdog and register it globally so every subsequent record stamps
    backend_state. The platform is the one the caller's environment gave
    JAX; when that is not a measurable one the gate emits ONE "error"
    record with `value: null` (never a zero the trajectory tooling would
    ingest) and the caller exits non-zero.
"""

from __future__ import annotations

import json
import sys
from typing import List, Optional

from glom_tpu.telemetry import schema, watchdog


def nearest_rank(sorted_samples: List[float], q: float) -> float:
    """Nearest-rank quantile over pre-sorted samples — THE 'p99'
    definition for the whole stack (per-host step histograms here, pod
    rollups in telemetry/aggregate.py), so the two never drift apart."""
    if not sorted_samples:
        return 0.0
    idx = min(len(sorted_samples) - 1, int(q * (len(sorted_samples) - 1) + 0.5))
    return sorted_samples[idx]


class StepTimeStats:
    """Streaming per-step wall-time stats with compile split out.

    observe(dt, is_compile=None): is_compile=None (standalone use) treats
    the FIRST observation as the compile step; fit_loop passes it
    explicitly per jit variant — BOTH the fast step's first call and the
    logging step's first call are trace+compile, and a multi-second
    compile landing in the steady-state samples would make p95/max
    unreadable. compile_time_s accumulates (total seconds spent
    compiling); the samples hold only steady-state steps."""

    def __init__(self, max_samples: int = 4096):
        self.compile_time_s: Optional[float] = None
        self._samples: List[float] = []
        self._max = max_samples
        self._count = 0
        self._running_max = 0.0

    def observe(self, dt_s: float, is_compile: Optional[bool] = None) -> None:
        if is_compile is None:
            is_compile = self.compile_time_s is None
        if is_compile:
            self.compile_time_s = (self.compile_time_s or 0.0) + dt_s
            return
        self._count += 1
        self._running_max = max(self._running_max, dt_s)
        if len(self._samples) < self._max:
            self._samples.append(dt_s)
        else:
            # Reservoir-free decimation: keep every other sample once full
            # (percentiles stay representative, memory stays bounded).
            self._samples = self._samples[::2]
            self._max = max(self._max, 2 * len(self._samples))
            self._samples.append(dt_s)

    _quantile = staticmethod(nearest_rank)

    def summary(self) -> dict:
        """The stamped histogram fields (milliseconds; compile in s)."""
        s = sorted(self._samples)
        return {
            "compile_time_s": round(self.compile_time_s or 0.0, 4),
            "step_time_p50_ms": round(1e3 * self._quantile(s, 0.50), 3),
            "step_time_p95_ms": round(1e3 * self._quantile(s, 0.95), 3),
            "step_time_p99_ms": round(1e3 * self._quantile(s, 0.99), 3),
            "step_time_max_ms": round(1e3 * self._running_max, 3),
            "steps_timed": self._count,
        }


def emit(rec: dict, kind: str = "bench", stream=None) -> dict:
    """Stamp (schema_version, kind, watchdog backend state) and print one
    JSON line. Returns the stamped record (benches reuse it for totals).
    Keys already present win — a bench that carries its own backend
    timeline is not overwritten."""
    stamped = schema.stamp(rec, kind=kind)
    for k, v in watchdog.backend_record().items():
        stamped.setdefault(k, v)
    from glom_tpu.tracing.flight import observe_event

    observe_event(stamped)
    print(json.dumps(stamped), file=stream or sys.stdout, flush=True)
    return stamped


def bench_bootstrap(
    metric: str,
    unit: str = "column-iters/s/chip",
    *,
    probe_timeout: float = 120.0,
) -> bool:
    """Gate for bench entrypoints. Returns True when the backend answers
    and is one a bench may run on: a TPU, or the CPU when the caller
    asked for it by name (JAX_PLATFORMS=cpu — CI's functional drives;
    their rows carry no MFU). Otherwise — backend down, or the CPU JAX
    fell back to because it found no chip — emits the UNMEASURED record
    (kind "error", `value: null`, which `python -m glom_tpu.telemetry
    compare` treats as missing) with the watchdog timeline and returns
    False; the caller exits non-zero. The watchdog stays registered
    either way, so every line the bench then emits carries the backend
    state."""
    from glom_tpu.telemetry.watchdog import BackendWatchdog, set_global_watchdog
    from glom_tpu.utils.startup import (
        cpu_requested,
        device_summary,
        enable_compile_cache,
    )

    enable_compile_cache()
    wd = BackendWatchdog(probe_timeout=probe_timeout)
    set_global_watchdog(wd)
    if wd.probe_once() == "down":
        error, note = (
            "backend-init-unavailable",
            "UNMEASURED: jax backend init failed or hung",
        )
    else:
        dev = device_summary()
        if dev["platform"] == "tpu" or cpu_requested():
            return True
        error, note = (
            "no-accelerator",
            f"UNMEASURED: no TPU (platform={dev['platform']}) and the "
            "caller did not ask for the CPU",
        )
    # The metric label stays the BARE one the measured rows carry: the
    # compare gate matches rows by label, and a decorated label would
    # make the outage read as a vanished metric instead of an UNMEASURED
    # one. The error field carries the machine-readable cause.
    emit(
        {
            "metric": metric,
            "value": None,
            "unit": unit,
            "error": error,
            "note": note,
            "watchdog_timeline": wd.timeline(),
        },
        kind="error",
    )
    return False
