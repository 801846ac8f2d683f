"""Tracing: software spans, programmatic XLA capture, HBM accounting, and
the crash flight recorder (docs/OBSERVABILITY.md).

The 90-line profiling stub grew into this subsystem because the telemetry
stream (PR 2) can say a run was slow or died, but not WHERE the time and
HBM went or what the last N steps looked like before the crash. Layers:

    spans    — host-side span() context manager + per-phase aggregation,
               emitting versioned "span" JSONL events; works on the CPU
               fallback where XProf cannot
    capture  — programmatic XLA trace windows (--trace-steps A:B around
               jax.profiler.start_trace/stop_trace) + the whole-block
               trace() context manager and profiler server
    memory   — live HBM watermarks from device memory stats, reconciled
               against the analytic live-bytes model (utils/metrics.py)
    flight   — bounded ring buffer of the last N telemetry events, dumped
               to flight_<ts>.jsonl on backend-down, anomaly storm,
               SIGTERM/atexit, or an unhandled fit_loop exception

Re-exports are LAZY (PEP 562, same pattern as glom_tpu/telemetry): spans
and flight are pure stdlib and must stay importable in a jax-broken
environment (the wedged-image scenario the flight recorder exists for);
capture/memory import jax only inside the functions that need it.
"""

_EXPORTS = {
    "PHASES": "spans",
    "HOST_PHASES": "spans",
    "DEVICE_PHASES": "spans",
    "LM_DEVICE_PHASES": "spans",
    "SAMBAY_DEVICE_PHASES": "spans",
    "KIMI_DEVICE_PHASES": "spans",
    "EVABYTE_DEVICE_PHASES": "spans",
    "OURO_DEVICE_PHASES": "spans",
    "SpanAggregator": "spans",
    "span": "spans",
    "spanned": "spans",
    "TraceCapture": "capture",
    "annotate": "capture",
    "start_server": "capture",
    "trace": "capture",
    "hbm_watermarks": "memory",
    "memory_record": "memory",
    "FlightRecorder": "flight",
    "dump_flight_recorder": "flight",
    "get_global_flight_recorder": "flight",
    "observe_event": "flight",
    "set_global_flight_recorder": "flight",
}
_SUBMODULES = ("spans", "capture", "memory", "flight")

__all__ = sorted([*_EXPORTS, *_SUBMODULES])


def __getattr__(name):
    import importlib

    if name in _SUBMODULES:
        return importlib.import_module(f"glom_tpu.tracing.{name}")
    if name in _EXPORTS:
        module = importlib.import_module(f"glom_tpu.tracing.{_EXPORTS[name]}")
        return getattr(module, name)
    raise AttributeError(f"module 'glom_tpu.tracing' has no attribute {name!r}")
