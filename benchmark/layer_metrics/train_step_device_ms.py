"""Device time of one training step: median duration of the runs of the
step program (the XLA module that took most of the traced time)."""


def read(ctx):
    t = ctx.get("trace")
    if not t or t.get("main_module_median_s") is None:
        return None
    return 1e3 * t["main_module_median_s"]
