"""Share of the step program's device time under `full_attention` in the
Ouro step: the causal scores alone (`hybrid_lm.blocked_attention`: the
attention kernels' calls forward and backward, 32 of each a step, with the
transposes to their head-major layout and back and the slice of the
log-sum-exp). The projections and the rotation are `ouro_in`, the
out-projection `ouro_out`."""
from benchmark.reduce_ouro import phase_pct


def read(ctx):
    return phase_pct(ctx, ("full_attention",))
