"""What the prefetch worker took to pull one batch from its source: mean of
its `host_prefetch_next` spans, which `fit_loop` drains from the prefetched
stream at every log boundary. A program that does not drain them there (the
parent of PR 24) leaves nothing to read."""
from benchmark.reduce_phases import span_ms


def read(ctx):
    return span_ms(ctx, "host_prefetch_next", per="count")
