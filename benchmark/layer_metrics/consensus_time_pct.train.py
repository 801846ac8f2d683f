"""Share of the step program's device time in consensus: every op, XLA's
and the `consensus_*` kernels', forward and backward, whose `op_name` lies
under the `consensus` or the `consensus_update` scope. Read where consensus
is an op of its own (n=1024); the whole-loop VJP's `loop_consensus_*`
kernels are in the run's table by kernel name."""
from benchmark.reduce_phases import phase_pct


def read(ctx):
    return phase_pct(ctx, ("consensus", "consensus_update"))
