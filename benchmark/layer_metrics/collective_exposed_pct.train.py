"""Share of the traced window in which a collective ran and no other op did
(mean over the chips): the part of `collective_time_pct.train` that hiding
the collective behind compute would win back."""


def read(ctx):
    t = ctx.get("trace")
    if not t or ctx.get("kind") != "train" or ctx.get("chips", 1) < 2:
        return None
    return 100.0 * t["collective_exposed_s"] / t["window_s"]
