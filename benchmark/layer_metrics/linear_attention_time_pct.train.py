"""Share of the step program's device time under the three scopes of the
Kimi Delta Attention layers' first half together: `kda_in` (input norm, the
three projections with their convolutions, the decay's, beta's and the gate's
projections), `kda_scan` (the delta rule in chunks) and `kda_out` (head norm,
gate, out-projection); forward, recomputed and backward."""
from benchmark.reduce_kimi import LINEAR_ATTENTION_PHASES, phase_pct


def read(ctx):
    return phase_pct(ctx, LINEAR_ATTENTION_PHASES)
