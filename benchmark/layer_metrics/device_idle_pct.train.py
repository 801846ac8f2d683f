"""Share of the traced steady steps in which no operation ran on the device
(1 - union of device-op intervals over the window; mean over the chips)."""


def read(ctx):
    t = ctx.get("trace")
    if not t or ctx.get("kind") != "train":
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
