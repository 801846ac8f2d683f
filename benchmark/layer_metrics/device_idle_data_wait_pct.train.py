"""Share of the traced window in which the device was idle while the fit
loop waited for a batch (`host_data_next` open): the data layer's part of
`device_idle_pct.train`."""
from benchmark.reduce_phases import idle_of


def read(ctx):
    idle = idle_of(ctx)
    return 100.0 * idle["data_wait_s"] / idle["window_s"] if idle else None
