"""Share of the step program's device time under `kda_scan`: the delta rule
in chunks alone (`kimi_linear.kda_chunked`: cumulative decays, the in-chunk
products, the solve, the pass over the chunks that carries the state, and the
outputs), forward, recomputed (by the layer and by the segment) and
backward. The projections, convolutions, gate and norms round it are
`kda_in` and `kda_out`."""
from benchmark.reduce_kimi import phase_pct


def read(ctx):
    return phase_pct(ctx, ("kda_scan",))
