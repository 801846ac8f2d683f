"""Share of the step program's device time under `ssd_scan`: the chunked
Mamba-2 recurrence (decays, the chunk-local products, the states between
chunks) and the D skip, forward and backward; the projections, conv and
gated norm around it are `mamba_in` and `mamba_out`."""
from benchmark.reduce_lm import phase_pct


def read(ctx):
    return phase_pct(ctx, ("ssd_scan",))
