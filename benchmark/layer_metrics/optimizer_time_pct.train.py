"""Share of the step program's device time under the `optimizer` scope:
`optimizer.update` and `apply_updates` with the casts they need."""
from benchmark.reduce_phases import phase_pct


def read(ctx):
    return phase_pct(ctx, ("optimizer",))
