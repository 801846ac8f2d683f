"""Share of the device's idle time (gaps of 20 us and more, as
`device_idle_pct.train`, from the fit loop's first span in the trace on) whose
midpoint lies inside one of the program's own spans, on any host thread:
how much of the idle time the program can name. Nothing to read where the
trace holds none of the program's spans."""
from benchmark.reduce_phases import idle_of


def read(ctx):
    idle = idle_of(ctx)
    if not idle or idle["idle_s"] <= 0:
        return None
    return 100.0 * idle["attributed_s"] / idle["idle_s"]
