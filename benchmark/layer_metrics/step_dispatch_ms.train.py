"""The enqueue of a step: the fit loop's `host_step_dispatch` span records
over the whole window, per step (the device runs behind it)."""
from benchmark.reduce_phases import span_ms


def read(ctx):
    return span_ms(ctx, "host_step_dispatch", per="step")
