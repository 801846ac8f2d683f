"""Share of the step program's device time under `eva_attention`: EVA
attention alone (`evabyte.eva_attention`: the two key segments laid end to
end, the attention kernels' calls forward and backward with the transposes to
their head-major layout and back, the slice of the log-sum-exp and the split
of dk and dv between the keys and the summaries). The projections and the
rotation are `eva_in`, the summariser `eva_summary`, the out-projection
`eva_out`."""
from benchmark.reduce_evabyte import phase_pct


def read(ctx):
    return phase_pct(ctx, ("eva_attention",))
