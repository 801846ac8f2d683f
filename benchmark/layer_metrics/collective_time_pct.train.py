"""Share of the traced window spent in collective operations (mean over the
chips). The part with no other op running is on an earlier line of the run
(`collective_exposed_s` in the trace summary)."""


def read(ctx):
    t = ctx.get("trace")
    if not t or ctx.get("kind") != "train" or ctx.get("chips", 1) < 2:
        return None
    return 100.0 * t["collective_s"] / t["window_s"]
