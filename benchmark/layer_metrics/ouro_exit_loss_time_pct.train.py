"""Share of the step program's device time under `lm_head_loss` and
`exit_gate` in the Ouro step: what the loop adds to a step beside its layers.
The head's 2,048 x 49,152 product over every pass's closed state, forward,
recomputed and backward, with the weighed cross-entropy (`lm_head_loss`), and
the gate's sigmoids, the exit distribution and its entropy (`exit_gate`)."""
from benchmark.reduce_ouro import phase_pct


def read(ctx):
    return phase_pct(ctx, ("lm_head_loss", "exit_gate"))
