"""Share of the rows the experts' grouped product ran that were token-expert
pairs: `moe_pairs_here` / `moe_rows_computed`, from the window's logging
records (the step counts both on the device). The rest is room kept for the
worst imbalance."""
from benchmark.reduce_lm import counter_mean


def read(ctx):
    pairs, rows = counter_mean(ctx, "moe_pairs_here"), counter_mean(ctx, "moe_rows_computed")
    if pairs is None or not rows:
        return None
    return 100.0 * pairs / rows
