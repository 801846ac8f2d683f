"""Share of the device's busy time spent in Mosaic (Pallas) calls."""


def read(ctx):
    t = ctx.get("trace")
    if not t or ctx.get("kind") != "train" or t["busy_s"] <= 0:
        return None
    return 100.0 * t["mosaic_s"] / t["busy_s"]
