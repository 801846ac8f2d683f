"""Share of the step program's device time under the three differential
attentions' scopes together: `window_attention`, `full_attention`,
`cross_attention` (input norm, projections, both softmaxes of every head
pair, the pair's norm, out-projection; forward, recomputed and backward)."""
from benchmark.reduce_sambay import ATTENTION_PHASES, phase_pct


def read(ctx):
    return phase_pct(ctx, ATTENTION_PHASES)
