"""Share of the step program's device time under `eva_summary`: the chunk
summariser (`evabyte.summarise`: a chunk's 16 keys against `phi`, a softmax
of 16, two weighted sums, `mu`), forward, recomputed and backward, in
float32 through XLA."""
from benchmark.reduce_evabyte import phase_pct


def read(ctx):
    return phase_pct(ctx, ("eva_summary",))
