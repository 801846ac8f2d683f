"""Share of the step program's device time in leaf ops under no phase of
the vocabulary (collectives, found by opcode, count as attributed). It is
what keeps the vocabulary honest after a refactor."""
from benchmark.reduce_phases import UNATTRIBUTED, phase_pct


def read(ctx):
    return phase_pct(ctx, (UNATTRIBUTED,))
