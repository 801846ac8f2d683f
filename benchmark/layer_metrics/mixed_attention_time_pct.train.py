"""Share of the step program's device time under Laguna's two attention
scopes together: `window_attention` and `full_attention` (input norm,
projections, the rotation, the scores in the kernels, the gate, the
out-projection; forward, recomputed and backward)."""
from benchmark.reduce_laguna import ATTENTION_PHASES, phase_pct


def read(ctx):
    return phase_pct(ctx, ATTENTION_PHASES)
