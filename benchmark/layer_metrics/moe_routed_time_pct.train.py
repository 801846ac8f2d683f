"""Share of the step program's device time under the routed experts' scopes:
`moe_router` (scores, top-k, weights), `moe_dispatch` (latent down-projection,
sort by expert, gather), `moe_experts` (the grouped products) and
`moe_combine` (weights, scatter back, latent up-projection), forward and
backward. The shared expert is not in it."""
from benchmark.reduce_lm import MOE_ROUTED_PHASES, phase_pct


def read(ctx):
    return phase_pct(ctx, MOE_ROUTED_PHASES)
