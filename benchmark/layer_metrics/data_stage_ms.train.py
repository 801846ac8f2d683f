"""What the prefetch worker took to start one batch's transfer to the
device(s): mean of its `host_prefetch_stage` spans (38 MB a step on one
chip, 154 MB over four)."""
from benchmark.reduce_phases import span_ms


def read(ctx):
    return span_ms(ctx, "host_prefetch_stage", per="count")
