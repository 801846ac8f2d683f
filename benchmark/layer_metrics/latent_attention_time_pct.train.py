"""Share of the step program's device time under `latent_attention`: a latent
layer's whole first half (input norm, query and latent projections, the
latent's norm and expansion, the scores in the kernels, the out-projection;
forward, recomputed and backward)."""
from benchmark.reduce_kimi import phase_pct


def read(ctx):
    return phase_pct(ctx, ("latent_attention",))
