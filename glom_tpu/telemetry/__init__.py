"""Telemetry: in-graph diagnostics, collective counters, structured sinks,
and the backend-liveness watchdog (docs/OBSERVABILITY.md).

The subsystem exists because rounds 4-5 produced zero driver-recorded
numbers when the backend was unreachable — the run itself must emit
schema-stable evidence (step timings, health scalars, collective volumes,
backend state) without a human tailing logs. Import surface:

    schema       — versioned JSONL event contract + lint CLI
    diagnostics  — in-graph scalars, NaN/Inf guard, consensus agreement
    counters     — measured collective wire bytes (manual shard_map path)
    sinks        — step-time histograms, stamped bench emitter
    watchdog     — backend-liveness heartbeat + state machine
    compare      — bench-trajectory regression gate (compare BASE NEW)
    perfetto     — span/flight JSONL -> Perfetto JSON trace (perfetto FILE)

Re-exports are LAZY (PEP 562, same pattern as glom_tpu/__init__):
diagnostics imports jax, and the lint entry point
(`python -m glom_tpu.telemetry FILE`) must work in a jax-broken or
jax-less environment — the exact wedged-image scenario schema.py's
pure-stdlib contract exists for.
"""

_EXPORTS = {
    "CollectiveCounters": "counters",
    "comm_drift": "counters",
    "record_collective": "counters",
    "recording": "counters",
    "TELEMETRY_LEVELS": "diagnostics",
    "resolve_telemetry_level": "diagnostics",
    "SCHEMA_VERSION": "schema",
    "stamp": "schema",
    "validate_record": "schema",
    "StepTimeStats": "sinks",
    "emit": "sinks",
    "BackendWatchdog": "watchdog",
    "backend_record": "watchdog",
    "get_global_watchdog": "watchdog",
    "set_global_watchdog": "watchdog",
}
_SUBMODULES = (
    "compare", "counters", "diagnostics", "perfetto", "schema", "sinks",
    "watchdog",
)

__all__ = sorted([*_EXPORTS, *_SUBMODULES])


def __getattr__(name):
    import importlib

    if name in _SUBMODULES:
        return importlib.import_module(f"glom_tpu.telemetry.{name}")
    if name in _EXPORTS:
        module = importlib.import_module(
            f"glom_tpu.telemetry.{_EXPORTS[name]}"
        )
        return getattr(module, name)
    raise AttributeError(f"module 'glom_tpu.telemetry' has no attribute {name!r}")
