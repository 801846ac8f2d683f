"""Measured collective counters for the manual shard_map path.

`utils/metrics.comm_volume_model` PRICES the gradient/update wire schedule
from top-level aggregates (G, P, dp, stage). This module MEASURES it: the
explicit collectives in `parallel/manual.py` (the seq-psum, the ZeRO
psum_scatter / pmean, the param all-gather) report their per-replica ring
wire bytes from the ACTUAL arrays at each call site while the step traces,
so aggregation decisions the model cannot see — leaves with no dp-divisible
axis falling back to a replicated allreduce, the seq-axis pre-reduction,
per-microbatch scatters — show up as measured-vs-modeled drift, which is
itself a stamped metric (`comm_model_drift`).

Recording is trace-time: collective shapes are static, so one abstract
trace (jax.eval_shape in DistributedTrainer) captures exactly what every
compiled step will move. Counters record only inside a `recording(...)`
context — re-traces of the same step (the with/without-grad-norm jit pair)
cannot double-count.

Wire formulas (ring algorithms, matching comm_volume_model's pricing):
  psum (allreduce)   2*(k-1)/k * B      B = local payload bytes
  psum_scatter       (k-1)/k   * B
  pmean fallback     2*(k-1)/k * B      (replicated leaf: full allreduce)
  all_gather         (k-1)     * B_sh   B_sh = per-shard bytes
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import List, Optional


class CollectiveCounters:
    """Accumulated per-replica per-step wire bytes by collective kind.

    `sites` additionally keeps one entry per NAMED site registration
    (the `timed_collective` wrapper passes site metadata; legacy
    byte-only `record_collective` calls contribute to the totals but not
    the registry): {site, axis, collective, wire_bytes (per call),
    calls, shape, dtype, dim} — the raw material the per-collective
    wall-time harness (telemetry/comm_time.py) re-dispatches and the
    capacity observatory's α-β time model is fitted from."""

    def __init__(self):
        self.reduce_bytes = 0  # psum + psum_scatter + pmean (gradient path)
        self.gather_bytes = 0  # all_gather (param path)
        self.n_reduce = 0
        self.n_gather = 0
        self.sites: List[dict] = []

    def record(self, kind: str, wire_bytes: int) -> None:
        if kind == "gather":
            self.gather_bytes += int(wire_bytes)
            self.n_gather += 1
        else:
            self.reduce_bytes += int(wire_bytes)
            self.n_reduce += 1

    def record_site(
        self,
        *,
        site: str,
        axis: str,
        collective: str,
        wire_bytes: int,
        calls: int,
        shape,
        dtype,
        dim: int,
    ) -> None:
        """One named-site registration (same (site, shape) seen again —
        e.g. a re-trace of the with/without-grad-norm jit pair inside one
        recording — accumulates calls rather than duplicating)."""
        for s in self.sites:
            if s["site"] == site and s["shape"] == tuple(shape):
                s["calls"] += calls
                return
        self.sites.append(
            {
                "site": site,
                "axis": axis,
                "collective": collective,
                "wire_bytes": int(wire_bytes),
                "calls": int(calls),
                "shape": tuple(int(d) for d in shape),
                "dtype": str(dtype),
                "dim": int(dim),
            }
        )

    def totals(self) -> dict:
        """The stamped record fields (measured counterpart of
        comm_volume_model's comm_*_bytes_per_step keys)."""
        return {
            "comm_measured_reduce_bytes_per_step": self.reduce_bytes,
            "comm_measured_gather_bytes_per_step": self.gather_bytes,
            "comm_measured_bytes_per_step": self.reduce_bytes + self.gather_bytes,
            "comm_measured_collective_count": self.n_reduce + self.n_gather,
        }


_local = threading.local()


def _stack() -> List[CollectiveCounters]:
    if not hasattr(_local, "stack"):
        _local.stack = []
    return _local.stack


@contextmanager
def recording(counters: CollectiveCounters):
    """Activate `counters` for collectives recorded on THIS thread (tracing
    is single-threaded per step; thread-local keeps parallel test runs
    honest)."""
    _stack().append(counters)
    try:
        yield counters
    finally:
        _stack().pop()


def _scale() -> int:
    return getattr(_local, "scale", 1)


@contextmanager
def scaled(k: int):
    """Multiply recorded bytes by `k` inside this context: a collective
    site inside a lax.scan body TRACES once but EXECUTES per iteration —
    the stage-2 per-microbatch reduce-scatter hook wraps itself in
    scaled(grad_accum) so the measured count prices every execution, not
    the single trace."""
    prev = _scale()
    _local.scale = prev * int(k)
    try:
        yield
    finally:
        _local.scale = prev


def record_collective(kind: str, wire_bytes: int) -> None:
    """Called from the instrumented collective sites in parallel/manual.py.
    No-op unless a recording() context is active — the sites stay free to
    trace/retrace without double-counting."""
    scale = _scale()
    for c in _stack():
        c.record(kind, wire_bytes * scale)


# -- per-collective wall-time (the capacity observatory's timing layer) -----

# tcfg.collective_timing / scfg.collective_timing vocabulary, resolved ONCE
# per path like telemetry_level (docs/OBSERVABILITY.md, "Capacity
# observatory"):
#   "off"     — no timing anywhere (the default; the overhead A/Bs hold the
#               off-mode step bit-identical to the pre-timing program);
#   "sampled" — every Nth step/dispatch OUTSIDE jit, each registered site's
#               collective is re-dispatched as its own timed sub-graph
#               (telemetry/comm_time.CollectiveTimeSampler): exact
#               block_until_ready wall clocks, zero hot-path cost between
#               samples. The mode every path supports.
#   "full"    — every execution of every registered site is bracketed
#               IN-GRAPH by dataflow-ordered io_callbacks stamping host
#               clocks (the only way to see per-execution variance, e.g. a
#               congested link on one while_loop trip). Supported only on
#               paths with an AOT trace seam (the serve engine's
#               .lower().compile()); the jit-on-first-call trainer paths
#               degrade to "sampled" loudly — the stamped mode is always
#               the resolved one.
TIMING_MODES = ("off", "sampled", "full")


def resolve_collective_timing(
    mode: str, *, supports_full: bool = True, path: str = ""
) -> str:
    """THE single resolution source for the collective-timing mode (the
    resolve_telemetry_level discipline): validates the vocabulary and
    degrades full -> sampled loudly where per-execution bracketing has no
    trace seam to ride."""
    if mode not in TIMING_MODES:
        raise ValueError(
            f"collective_timing={mode!r}: one of {TIMING_MODES}"
        )
    if mode == "full" and not supports_full:
        import warnings

        warnings.warn(
            f"collective_timing='full' is unavailable on {path or 'this'} "
            "path (no AOT trace seam to insert the io_callback brackets); "
            "running 'sampled' — the stamped mode is the resolved one",
            stacklevel=3,
        )
        return "sampled"
    return mode


class CollectiveTimeLog:
    """Host-side sink for the full-mode io_callback brackets: thread-safe
    (engine worker threads dispatch concurrently), bounded (a long-running
    server must not grow one entry per collective execution forever —
    drain() aggregates per site and resets)."""

    def __init__(self, max_events: int = 100_000):
        self._events: List[tuple] = []
        self._lock = threading.Lock()
        self._max = max_events
        self.base = time.perf_counter()

    def add(self, site: str, axis: str, collective: str,
            wire_bytes: int, dt_s: float) -> None:
        with self._lock:
            if len(self._events) < self._max:
                self._events.append(
                    (site, axis, collective, int(wire_bytes), float(dt_s))
                )

    def drain(self) -> List[dict]:
        """Aggregate and reset: one dict per (site, axis) with the mean /
        max wall_ms over the drained executions (each shard's callback
        pair contributes one sample)."""
        with self._lock:
            events, self._events = self._events, []
        agg: dict = {}
        for site, axis, collective, nbytes, dt in events:
            slot = agg.setdefault(
                (site, axis, nbytes),
                {"site": site, "axis": axis, "collective": collective,
                 "wire_bytes": nbytes, "calls": 0, "_sum": 0.0, "_max": 0.0},
            )
            slot["calls"] += 1
            slot["_sum"] += dt
            slot["_max"] = max(slot["_max"], dt)
        out = []
        for slot in agg.values():
            calls = slot.pop("calls")
            total = slot.pop("_sum")
            mx = slot.pop("_max")
            out.append(
                dict(
                    slot,
                    calls=calls,
                    wall_ms=round(1e3 * total / calls, 6) if calls else 0.0,
                    wall_ms_max=round(1e3 * mx, 6),
                    mode="full",
                )
            )
        return sorted(out, key=lambda r: r["site"])


def _timing_state():
    return getattr(_local, "timing", None)


@contextmanager
def timing(mode: str, log: Optional[CollectiveTimeLog]):
    """Activate a collective-timing mode for code TRACED on this thread
    (the serve engine wraps its AOT .lower() in timing('full', log) so the
    compiled program carries the callback brackets; 'sampled'/'off' insert
    nothing — the sampler runs outside jit entirely)."""
    prev = _timing_state()
    _local.timing = (mode, log)
    try:
        yield
    finally:
        _local.timing = prev


def timed_collective(
    site: str,
    axis_name: str,
    kind: str,
    wire_bytes: int,
    fn,
    x,
    *,
    collective: str,
    dim: int = 0,
):
    """THE shared timing wrapper every registered collective site routes
    through (glom-lint's collective-coverage checker enforces it: a site
    that hand-rolls clocks or callbacks around a collective inside traced
    code is a finding — the trace-purity checker already bans bare host
    clocks there, and this wrapper is the one sanctioned route).

    Always: records the wire bytes exactly as record_collective did, plus
    the site's identity/shape into the active recording's site registry
    (what the sampled-mode re-dispatch and the α-β time model read).

    Under timing('full', log) — active only during an AOT trace — the
    collective is additionally bracketed by io_callbacks whose ORDER is
    enforced by dataflow, not ordered effects (ordered effects are not
    legal inside shard_map): the enter callback's clock value is tied to
    the collective's input through lax.optimization_barrier (bitwise
    no-op on the payload), and the exit callback takes both that clock
    and a scalar read of the output, so it cannot run before the
    collective completes. Each shard's pair contributes one wall-clock
    sample to the log at every execution."""
    record_collective(kind, wire_bytes)
    scale = _scale()
    for c in _stack():
        c.record_site(
            site=site, axis=axis_name, collective=collective,
            wire_bytes=wire_bytes, calls=scale,
            shape=getattr(x, "shape", ()), dtype=getattr(x, "dtype", "?"),
            dim=dim,
        )
    state = _timing_state()
    if not state or state[0] != "full" or state[1] is None:
        return fn(x)
    log = state[1]
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import io_callback

    base = log.base

    def _enter(_witness):
        import numpy as np

        return np.float32(time.perf_counter() - base)

    def _exit(t0, _witness):
        log.add(
            site, axis_name, collective, wire_bytes,
            (time.perf_counter() - base) - float(t0),
        )

    # f32 seconds since the log's base keep the clock's resolution in the
    # microseconds for hours of uptime — far under the callback dispatch
    # noise this mode already carries (the sampled mode is the calibrated
    # route; full mode buys per-execution VISIBILITY, not precision).
    witness_in = jnp.ravel(x)[0] if getattr(x, "ndim", 0) else x
    t0 = io_callback(
        _enter, jax.ShapeDtypeStruct((), jnp.float32), witness_in
    )
    x, t0 = lax.optimization_barrier((x, t0))
    out = fn(x)
    witness_out = jnp.ravel(out)[0] if getattr(out, "ndim", 0) else out
    io_callback(_exit, None, t0, witness_out)
    return out


# -- wire-byte helpers for the instrumented sites --------------------------


def _nbytes(x) -> int:
    import numpy as np

    size = 1
    for s in x.shape:
        size *= int(s)
    return size * np.dtype(x.dtype).itemsize


def ring_allreduce_bytes(x, k: int) -> int:
    return int(2 * (k - 1) / k * _nbytes(x)) if k > 1 else 0


def ring_reduce_scatter_bytes(x, k: int) -> int:
    return int((k - 1) / k * _nbytes(x)) if k > 1 else 0


def ring_all_gather_bytes(x_shard, k: int) -> int:
    return int((k - 1) * _nbytes(x_shard)) if k > 1 else 0


def comm_drift(measured: dict, modeled: dict) -> dict:
    """Measured-vs-modeled reconciliation, itself a stamped metric: the
    relative drift of total per-step wire bytes ((measured - modeled) /
    modeled). A model that stops matching the collectives a step actually
    emits is a silent-pricing bug — stamping the drift on every record is
    what makes it impossible to miss."""
    meas = measured.get("comm_measured_bytes_per_step", 0)
    model = modeled.get("comm_bytes_per_step", 0)
    if model <= 0:
        drift = 0.0 if meas == 0 else float("inf")
    else:
        drift = (meas - model) / model
    return {"comm_model_drift": round(drift, 6) if drift != float("inf") else 1e9}
