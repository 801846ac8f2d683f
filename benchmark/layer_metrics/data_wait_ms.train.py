"""What the fit loop waited for a batch: its `host_data_next` span records
over the whole window, per step. A fed loop finds the batch staged (well
under a millisecond); a starved one waits here for the generator."""
from benchmark.reduce_phases import span_ms


def read(ctx):
    return span_ms(ctx, "host_data_next", per="step")
