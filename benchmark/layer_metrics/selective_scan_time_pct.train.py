"""Share of the step program's device time under `selective_scan`: the
Mamba-1 recurrence alone (the decays, the segments' scans, the states carried
over segments and chunks, the read-out), forward, recomputed and backward;
the projections, conv, step, skip and gate around it are `mamba_in` and
`mamba_out`."""
from benchmark.reduce_sambay import phase_pct


def read(ctx):
    return phase_pct(ctx, ("selective_scan",))
