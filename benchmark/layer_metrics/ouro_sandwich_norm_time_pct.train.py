"""Share of the step program's device time under `sandwich_norm` and
`ut_close` in the Ouro step: the two norms on the branches' outputs with
their adds into the stream (64 a step, forward, recomputed and backward), and
the norm that closes a pass with the loop's own ops (the copies that stack a
pass's kept arrays for the backward pass). Bandwidth, no product."""
from benchmark.reduce_ouro import phase_pct


def read(ctx):
    return phase_pct(ctx, ("sandwich_norm", "ut_close"))
