"""glom-lint: JAX-aware static analysis for the framework's own hazards.

`python -m glom_tpu.analysis [PATHS] [--baseline FILE]` runs five
checkers grounded in invariants the repo otherwise enforces by
convention (docs/ANALYSIS.md has the catalog and the suppression
workflow):

    collective-coverage  manual-path collectives: declared mesh axes +
                         telemetry.counters registration
    trace-purity         no host side effects reachable from jit /
                         shard_map / while_loop bodies
    donation-safety      no use of a buffer after a donated dispatch
    schema-emit          emit/stamp sites use registered kinds;
                         UNMEASURED is null, never 0.0
    lockset              threaded-class shared attributes stay behind
                         their lock (runtime companion: tests/test_races)

Pure stdlib — the pass runs where jax is wedged, which is when the
evidence trail matters most. CI runs it as the `lint` job.
"""

from glom_tpu.analysis.core import (
    Checker,
    Context,
    Finding,
    SourceModule,
    default_checkers,
    run,
)

__all__ = [
    "Checker",
    "Context",
    "Finding",
    "SourceModule",
    "default_checkers",
    "run",
]
